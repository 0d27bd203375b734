#!/usr/bin/env python
"""Communication/compute overlap bench leg: bucketed grad collectives +
prefetched all-gathers vs serialized ZeRO on the dp=8 in-process mesh
(ROADMAP item 4; the denominator is PR 9's serialized reduce-scatter →
update → all-gather schedule, whose wait share PR 13's attribution
measures).

Trains one Adam MLP two ways — serialized ZeRO (per-grad
zero_reduce_scatter, updates + all-gathers at the program tail) and the
overlapped schedule (size-targeted zero_bucket_reduce_scatter buckets
fired at each bucket's last grad, shard updates + zero_all_gathers
hoisted to their dataflow frontier) — and reports:

* measured steady-state step time for both schedules (interleaved
  round-medians, so drift hits both alike) and the overlap speedup;
* ``perf.wait_fraction.collective`` before/after (the PR-13 attribution
  split) plus the cost model's exposed-wire estimate and
  ``collective.overlap_ratio``;
* loss parity: fp32 BITWISE overlapped == serialized, int8 overlapped
  BITWISE == per-grad int8 and within the PR-9 tolerance of fp32;
* ``collective.buckets`` / ``collective.bucket_bytes`` counters.

Gates (exit 1 on violation unless --no-gate):

* overlapped measured step time <= serialized (speedup >= 1.0);
* fp32 bitwise + int8 parity as above;
* measured ``perf.wait_fraction.collective`` drops vs serialized;
* the overlap-aware estimate actually hides wire (overlap_ratio > 0)
  and the snapshot carries the bucket counters.

Usage:
    python tools/bench_overlap.py [--steps N] [--dump SNAP.json]
                                  [--no-gate]

Prints ONE JSON line (the bench.py dp_overlap leg parses it). Always
re-executes itself in a child pinned to an 8-device virtual CPU platform
(the __graft_entry__.dryrun_multichip pattern), so it behaves identically
from a TPU-attached driver and from CPU CI.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

DP = 8
_CHILD_ENV = "_PADDLE_TPU_OVERLAP_CHILD"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# model shape: 12 fc layers x 256 wide — enough dense grads that the
# serialized schedule issues ~27 collectives per step while compute still
# dominates (the regime the overlap schedule is built for)
B, D, H, L = 16, 256, 256, 12
BUCKET_BYTES = 1 << 20


def _respawn(argv):
    env = dict(os.environ)
    env[_CHILD_ENV] = "1"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={DP}"
    ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__)] + argv,
        env=env, cwd=REPO, capture_output=True, text=True, timeout=1200,
    )
    sys.stderr.write(proc.stderr)
    sys.stdout.write(proc.stdout)
    return proc.returncode


def _feed(i):
    import numpy as np

    rng = np.random.RandomState(100 + i)
    return {"x": rng.randn(B, D).astype(np.float32),
            "y": rng.randn(B, 1).astype(np.float32)}


def _build(overlapped, quant=None):
    import jax
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.framework import unique_name
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.parallel import make_mesh, shard_program
    from paddle_tpu.parallel.transpiler import ShardedWeightUpdate

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    scope = Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard():
        x = fluid.data("x", [B, D])
        y = fluid.data("y", [B, 1])
        h = x
        for _ in range(L):
            h = layers.fc(h, H, act="relu")
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        _, pg = fluid.optimizer.Adam(0.001).minimize(loss, startup)
        blk = main.global_block
        ShardedWeightUpdate(
            DP, quant=quant,
            bucket_bytes=BUCKET_BYTES if overlapped else None,
            prefetch=overlapped,
        ).transpile(main, startup, pg)
        blk.append_op("scale", {"X": [loss.name]}, {"Out": [loss.name]},
                      {"scale": 1.0 / DP, "bias": 0.0})
        blk.append_op("c_allreduce_sum", {"X": [loss.name]},
                      {"Out": [loss.name]}, {"axis_name": "dp"})
        shard_program(main, make_mesh({"dp": DP}, jax.devices()[:DP]),
                      {"x": ("dp",), "y": ("dp",)})
    return main, startup, scope, loss


def _run_steps(exe, prog, steps, first_feed=0):
    """Run `steps` steps on the return_numpy path (the one that publishes
    the perf.step_attribution sample); returns the loss trajectory."""
    import numpy as np

    main, _startup, scope, loss = prog
    losses = []
    for i in range(steps):
        (lv,) = exe.run(main, feed=_feed(first_feed + i),
                        fetch_list=[loss], scope=scope)
        losses.append(float(np.asarray(lv).reshape(-1)[0]))
    return losses


def _attribution_phase(exe, prog, steps):
    """Reset metrics, run a steady-state window, and return (losses,
    snapshot) — the snapshot carries this schedule's wait fractions.
    The collective.* counters advance at TRACE time (once per compiled
    site), so one uncached step re-traces the program inside the window
    to land them in the snapshot."""
    from paddle_tpu import observability

    main, _startup, scope, loss = prog
    observability.reset()
    exe.run(main, feed=_feed(0), fetch_list=[loss], scope=scope,
            use_program_cache=False)
    losses = _run_steps(exe, prog, steps)
    return losses, observability.snapshot()


def run(steps, dump, gate):
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import observability

    exe = fluid.Executor()
    serial = _build(False)
    overlap = _build(True)
    for prog in (serial, overlap):
        exe.run(prog[1], scope=prog[2])
        _run_steps(exe, prog, 1)  # compile carry

    # -- timing: interleaved rounds, medians per round -------------------
    rounds, per_round = 6, 5
    t_serial, t_overlap = [], []
    fidx = 1
    for _ in range(rounds):
        for prog, sink in ((serial, t_serial), (overlap, t_overlap)):
            dts = []
            for _ in range(per_round):
                t0 = time.perf_counter()
                _run_steps(exe, prog, 1, first_feed=fidx)
                dts.append(time.perf_counter() - t0)
                fidx += 1
            sink.append(float(np.median(dts)))
    step_serial = float(np.median(t_serial))
    step_overlap = float(np.median(t_overlap))
    speedup = step_serial / step_overlap if step_overlap else 0.0

    # -- parity: fp32 bitwise, int8 bitwise vs per-grad int8 -------------
    # fresh builds (fresh scopes) so both schedules see identical initial
    # params and feeds; the pairs are then reused for the attribution
    # windows below (already compiled, steady state)
    par_steps = max(3, min(steps, 6))
    serial2, overlap2 = _build(False), _build(True)
    q_ser, q_over = _build(False, quant="int8"), _build(True, quant="int8")
    for prog in (serial2, overlap2, q_ser, q_over):
        exe.run(prog[1], scope=prog[2])
    loss_serial = _run_steps(exe, serial2, par_steps)
    loss_overlap = _run_steps(exe, overlap2, par_steps)
    q_serial = _run_steps(exe, q_ser, par_steps)
    q_overlap = _run_steps(exe, q_over, par_steps)
    parity_fp32 = bool(np.array_equal(loss_serial, loss_overlap))
    parity_int8 = bool(np.array_equal(q_serial, q_overlap))
    int8_tolerance = bool(np.allclose(loss_serial, q_overlap,
                                      rtol=5e-2, atol=5e-2))

    # -- attribution: wait fraction before (serialized) / after ----------
    _, snap_serial = _attribution_phase(exe, serial2, steps)
    _, snap_overlap = _attribution_phase(exe, overlap2, steps)
    if dump:
        observability.dump(dump)  # the overlapped schedule's snapshot

    def _wait(snap):
        return float(
            snap["gauges"].get("perf.wait_fraction.collective", 0.0)
        )

    def _attr(snap):
        return (snap.get("tables") or {}).get("perf.step_attribution") or {}

    wait_serial, wait_overlap = _wait(snap_serial), _wait(snap_overlap)
    attr_o = _attr(snap_overlap)
    counters = snap_overlap.get("counters", {})
    overlap_ratio = float(
        snap_overlap["gauges"].get("collective.overlap_ratio", 0.0)
    )

    result = {
        "metric": "dp_overlap",
        "dp": DP,
        "model": {"batch": B, "width": H, "layers": L,
                  "bucket_bytes": BUCKET_BYTES},
        "step_ms_serialized": round(step_serial * 1e3, 3),
        "step_ms_overlapped": round(step_overlap * 1e3, 3),
        "overlap_speedup": round(speedup, 4),
        "loss_parity_fp32_bitwise": parity_fp32,
        "loss_parity_int8_bitwise": parity_int8,
        "int8_within_tolerance": int8_tolerance,
        "wait_fraction_collective_serialized": round(wait_serial, 4),
        "wait_fraction_collective_overlapped": round(wait_overlap, 4),
        "est_wait_fraction_overlapped": round(
            float(attr_o.get("est_wait_fraction", 0.0)), 4
        ),
        "est_wire_hidden_seconds": float(
            attr_o.get("est_wire_hidden_seconds", 0.0)
        ),
        "est_overlap_ratio": overlap_ratio,
        "collective_buckets": int(counters.get("collective.buckets", 0)),
        "collective_bucket_bytes": int(
            counters.get("collective.bucket_bytes", 0)
        ),
        "final_loss": {"serialized": loss_serial[-1],
                       "overlapped": loss_overlap[-1]},
    }
    failures = []
    if speedup < 1.0:
        failures.append(
            f"overlapped step {step_overlap * 1e3:.2f} ms slower than "
            f"serialized {step_serial * 1e3:.2f} ms (speedup {speedup:.3f})"
        )
    if not parity_fp32:
        failures.append("overlapped fp32 losses diverge from serialized")
    if not parity_int8:
        failures.append("overlapped int8 losses diverge from per-grad int8")
    if not int8_tolerance:
        failures.append("int8 overlapped losses out of PR-9 tolerance")
    if not wait_overlap < wait_serial:
        failures.append(
            f"wait_fraction.collective did not drop "
            f"({wait_serial:.4f} -> {wait_overlap:.4f})"
        )
    if not 0.0 < overlap_ratio <= 1.0:
        failures.append(
            f"collective.overlap_ratio={overlap_ratio} (no wire hidden)"
        )
    if result["collective_buckets"] <= 0:
        failures.append("no collective.buckets recorded")
    result["gate_failures"] = failures
    print(json.dumps(result))
    if failures and gate:
        print(f"overlap gates FAILED: {failures}", file=sys.stderr)
        return 1
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=6,
                    help="steps per attribution window")
    ap.add_argument("--dump", default=None,
                    help="write the overlapped schedule's observability "
                         "snapshot here")
    ap.add_argument("--no-gate", action="store_true",
                    help="report only, never fail the exit code")
    args = ap.parse_args(argv)
    if os.environ.get(_CHILD_ENV) != "1":
        return _respawn(
            ["--steps", str(args.steps)]
            + (["--dump", args.dump] if args.dump else [])
            + (["--no-gate"] if args.no_gate else [])
        )
    return run(args.steps, args.dump, gate=not args.no_gate)


if __name__ == "__main__":
    sys.exit(main())
