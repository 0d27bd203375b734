#!/usr/bin/env python
"""dp-sharding bench leg: weight-update sharding + quantized collectives
on the virtual mesh (the multichip dryrun environment).

Trains one Adam MLP three ways on a dp=8 in-process mesh — per-grad
allreduce baseline, ZeRO sharded update (fp32 wire), sharded update with
int8 block-quantized collectives — and reports:

* collective payload (wire) bytes per step, from the ``collective.*``
  counters the emitters record at trace time;
* optimizer-state bytes per rank (sharded gauges) vs the replicated
  baseline layout;
* loss-trajectory parity across the three builds.

Gates (exit 1 on violation unless --no-gate):

* int8 collective payload <= 0.6x the allreduce baseline wire bytes
  (the ">=40% payload reduction" acceptance);
* optimizer-state bytes/rank <= 1.25x (full / dp) — "~1/N";
* sharded fp32 losses match the baseline (rtol 1e-5; the dp=8 reduction
  tree may legally reorder adds), int8 within 5e-2.

Usage:
    python tools/bench_dp_sharding.py [--steps N] [--dump SNAP.json]
                                      [--no-gate]

Prints ONE JSON line (the bench.py dp_sharding leg parses it). Always
re-executes itself in a child process pinned to an 8-device virtual CPU
platform, so it behaves identically from a TPU-attached driver and from
CPU CI (the __graft_entry__.dryrun_multichip pattern).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

DP = 8
_CHILD_ENV = "_PADDLE_TPU_DP_SHARDING_CHILD"
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


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
        env=env, cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)
        )),
        capture_output=True, text=True, timeout=1200,
    )
    sys.stderr.write(proc.stderr)
    sys.stdout.write(proc.stdout)
    return proc.returncode


def _build_and_train(mode, steps, quant=None):
    import numpy as np

    import jax
    import paddle_tpu as fluid
    from paddle_tpu import layers, observability
    from paddle_tpu.framework import unique_name
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.parallel import make_mesh, shard_program
    from paddle_tpu.parallel.transpiler import (
        GradAllReduce,
        ShardedWeightUpdate,
    )

    b, d, h = 16, 512, 256
    before = dict(observability.snapshot()["counters"])
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    scope = Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard():
        x = fluid.data("x", [b, d])
        y = fluid.data("y", [b, 1])
        hid = layers.fc(x, h, act="relu")
        hid = layers.fc(hid, h, act="relu")
        pred = layers.fc(hid, 1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        _, pg = fluid.optimizer.Adam(0.001).minimize(loss, startup)
        blk = main.global_block
        if mode == "allreduce":
            GradAllReduce(DP).transpile(main, pg)
        else:
            ShardedWeightUpdate(DP, quant=quant).transpile(main, startup, pg)
        blk.append_op("scale", {"X": [loss.name]}, {"Out": [loss.name]},
                      {"scale": 1.0 / DP, "bias": 0.0})
        blk.append_op("c_allreduce_sum", {"X": [loss.name]},
                      {"Out": [loss.name]}, {"axis_name": "dp"})
        shard_program(main, make_mesh({"dp": DP}, jax.devices()[:DP]),
                      {"x": ("dp",), "y": ("dp",)})
        exe = fluid.Executor()
        exe.run(startup, scope=scope)
        losses = []
        for i in range(steps):
            rng = np.random.RandomState(100 + i)
            feed = {"x": rng.randn(b, d).astype(np.float32),
                    "y": rng.randn(b, 1).astype(np.float32)}
            # return_numpy=True: this loop materializes the loss every
            # step anyway (no pipelining to preserve), and the numpy
            # path is the one that publishes the perf.step_attribution
            # sample the CI attribution gate reads
            (lv,) = exe.run(main, feed=feed, fetch_list=[loss],
                            scope=scope)
            losses.append(float(np.asarray(lv).reshape(-1)[0]))
        # baseline optimizer-state bytes: the replicated accumulators
        state_bytes = 0
        for v in main.list_vars():
            if getattr(v, "_accum_of", None) is not None:
                n = 1
                for dim in v.shape or ():
                    n *= int(dim)
                state_bytes += n * 4
        shard_gauges = {
            k: v for k, v in observability.snapshot()["gauges"].items()
            if k.startswith("collective.zero_")
        }
    after = observability.snapshot()["counters"]
    delta = {
        k: after[k] - before.get(k, 0)
        for k in after
        if k.startswith("collective.") and after[k] != before.get(k, 0)
    }
    return {
        "losses": losses,
        "counters": delta,
        "replicated_state_bytes": state_bytes,
        "gauges": shard_gauges,
    }


def run(steps, dump, gate):
    import numpy as np

    from paddle_tpu import observability

    base = _build_and_train("allreduce", steps)
    shard = _build_and_train("sharded", steps)
    quant = _build_and_train("sharded", steps, quant="int8")

    # wire bytes: the zero counters already carry the (n-1)/n ring factor;
    # the allreduce counter records raw payload, x 2(n-1)/n on the wire
    ring = 2.0 * (DP - 1) / DP
    base_wire = base["counters"].get(
        "collective.c_allreduce_sum.bytes", 0
    ) * ring
    fp_wire = (
        shard["counters"].get("collective.bytes.reduce_scatter_fp32", 0)
        + shard["counters"].get("collective.bytes.all_gather_fp32", 0)
    )
    q_wire = (
        quant["counters"].get("collective.bytes.reduce_scatter_int8", 0)
        + quant["counters"].get("collective.bytes.all_gather_int8", 0)
    )
    g = shard["gauges"]
    per_rank = g.get("collective.zero_optimizer_state_bytes_per_rank", 0)
    full = g.get("collective.zero_optimizer_state_bytes_full", 0)
    master = g.get("collective.zero_master_shard_bytes_per_rank", 0)
    # independent cross-check: the transpiler's "full" gauge must equal a
    # plain walk of the BASELINE build's accumulator vars
    base_full = base["replicated_state_bytes"]
    state_gauge_consistent = bool(
        full and abs(full - base_full) <= 0.02 * base_full
    )

    parity_fp = bool(np.allclose(base["losses"], shard["losses"],
                                 rtol=1e-5, atol=1e-6))
    parity_q = bool(np.allclose(base["losses"], quant["losses"],
                                rtol=5e-2, atol=5e-2))
    payload_reduction = 1.0 - (q_wire / base_wire) if base_wire else 0.0
    state_ratio = per_rank / full if full else 1.0

    result = {
        "metric": "dp_sharding",
        "dp": DP,
        "steps": steps,
        "baseline_allreduce_wire_bytes": int(base_wire),
        "sharded_fp32_wire_bytes": int(fp_wire),
        "sharded_int8_wire_bytes": int(q_wire),
        "int8_payload_reduction": round(payload_reduction, 4),
        "optimizer_state_bytes_replicated": int(full),
        "optimizer_state_bytes_replicated_recount": int(base_full),
        "optimizer_state_gauge_consistent": state_gauge_consistent,
        "optimizer_state_bytes_per_rank": int(per_rank),
        "optimizer_state_ratio": round(state_ratio, 4),
        "master_shard_bytes_per_rank": int(master),
        "loss_parity_fp32": parity_fp,
        "loss_parity_int8": parity_q,
        "final_loss": {
            "allreduce": base["losses"][-1],
            "sharded": shard["losses"][-1],
            "sharded_int8": quant["losses"][-1],
        },
    }
    failures = []
    if payload_reduction < 0.40:
        failures.append(
            f"int8 payload reduction {payload_reduction:.1%} < 40%"
        )
    if state_ratio > 1.25 / DP:
        failures.append(
            f"optimizer-state bytes/rank ratio {state_ratio:.4f} > "
            f"1.25/{DP}"
        )
    if not parity_fp:
        failures.append("sharded fp32 losses diverge from allreduce")
    if not parity_q:
        failures.append("sharded int8 losses out of tolerance")
    if not state_gauge_consistent:
        failures.append(
            f"transpiler state gauge {full} disagrees with the baseline "
            f"accumulator recount {base_full}"
        )
    result["gate_failures"] = failures
    if dump:
        observability.dump(dump)
    print(json.dumps(result))
    if failures and gate:
        print(f"dp-sharding gates FAILED: {failures}", file=sys.stderr)
        return 1
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--dump", default=None,
                    help="write the observability snapshot here")
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
