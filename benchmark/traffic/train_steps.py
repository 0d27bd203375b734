"""Traffic kind `train_steps`: one training step after another.

Parameters (the cell file's "traffic"): `batch`, `seq` and whatever the
family's builder reads; `pool` host batches made from the seed; every
`log_every` steps the host reads a loss (a logging interval, which also
bounds how far the host runs ahead of the device): the loss of the step
dispatched `read_lag` steps earlier, so the read never empties the
device's queue; `warmup_steps`; `trace_steps` (the length of a traced
run's window, in steps); `logits_tol` and `loss_rtol` for the comparison
with the plain reference.

The rate is `log_every` steps' tokens over the MEDIAN time between two
consecutive reads (`stats.median_rate`), not the window's steps over its
seconds: the one-chip machine shares its host, and one stall of the host
(PR 22: up to 1.9 s of a 20 s window) moves a mean by what the check's
whole bound allows and the median of ten blocks by nothing. A cell that
does something every N steps keeps `log_every` a multiple of N, so that
every block holds the same work.

The comparison runs after the window, on the state the window left, and
after the device's peak has been read: it copies every parameter to the
device in float32 and runs two more programs there, and the device keeps
one high-water mark for the whole process, so run before the window it
would be counted into the step's peak (PR 22: 0.6 to 0.9 GB of it).
"""

from __future__ import annotations

import importlib
import json
import time

import numpy as np

from benchmark.harness import device, hlo, stats
from benchmark.harness.context import Run, Window


def run(ctx):
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.framework.scope import Scope

    annotate = jax.profiler.TraceAnnotation
    traffic = ctx.traffic
    builder = importlib.import_module(
        f"benchmark.builders.{ctx.config['builder']}"
    )
    build = builder.build_train(ctx.config, traffic, ctx.chips,
                                ctx.rehearse, ctx.seed)
    scope, exe = Scope(), fluid.Executor()
    exe.run(build.startup, scope=scope)

    rng = np.random.RandomState(ctx.seed)
    pool = [build.make_feed(rng) for _ in range(traffic["pool"])]
    fetch = [build.loss]

    def step(i):
        with annotate("bench.exe_run"):
            (lv,) = exe.run(build.main, feed=pool[i % len(pool)],
                            fetch_list=fetch, scope=scope,
                            return_numpy=False)
        return lv

    def read(lv):
        with annotate("bench.fetch_loss"):
            return float(np.asarray(lv).reshape(-1)[0])

    # set-up: the first step compiles (or loads); the compiled step's HLO
    # says which kernels and collectives are in it
    warm = [read(step(i)) for i in range(traffic["warmup_steps"])]
    text = exe.lower(build.main, feed=pool[0], fetch_list=fetch,
                     scope=scope).compile().as_text()
    facts = {
        "custom_calls": hlo.custom_calls(text),
        "custom_call_names": hlo.custom_call_names(text),
        "collectives": hlo.collectives(text),
        "tokens_per_step": build.tokens_per_step,
        "flops_per_token": build.flops_per_token,
    }
    if build.kernel_cost is not None:
        facts["kernel_cost"] = build.kernel_cost()
    checks = {"kernels": _expect_kernels(ctx, facts)}
    if ctx.chips > 1:
        checks["collectives"] = _expect_collectives(ctx, facts, scope,
                                                    build)
    peak_setup = device.peak_bytes(ctx.devices)
    print(json.dumps({"setup": {
        "warmup_losses": warm, "peak_bytes_after_setup": peak_setup,
        "custom_calls": facts["custom_calls"],
        "hlo_collectives": facts["collectives"], **checks,
    }}), flush=True)

    # the window
    log_every, lag = traffic["log_every"], traffic["read_lag"]
    budget_steps = traffic["trace_steps"] if ctx.trace else None
    kept, failed, attempted = [], 0, 0
    stamps = []  # host clock after each logging read
    with Window(ctx) as win:
        i = 0
        while True:
            attempted += 1
            try:
                lv = step(i)
            except Exception as exc:  # boundary: a step that raised failed
                failed += 1
                print(json.dumps({"step_error": f"{type(exc).__name__}: "
                                  f"{exc}"[:500]}), flush=True)
                break
            kept.append(lv)
            i += 1
            if i % log_every == 0:
                read(kept[max(0, i - 1 - lag)])
                stamps.append(time.perf_counter())
            if budget_steps is not None:
                if i >= budget_steps:
                    break
            elif time.perf_counter() - win.t0 >= ctx.seconds:
                break
        if kept:
            read(kept[-1])
            jax.block_until_ready(
                scope.find_var(build.main.all_parameters()[0].name)
            )
        win.close()
    losses = [float(np.asarray(x).reshape(-1)[0]) for x in kept]
    failed += sum(not np.isfinite(v) for v in losses)
    steps = len(kept)
    facts.update(win.facts(), steps=steps, peak_bytes_after_setup=peak_setup)
    peak = facts["peak_bytes"]
    memory = device.memory_record(ctx.devices)
    checks["reference"] = build.check(exe, scope,
                                      np.random.RandomState(ctx.seed + 1))

    n = min(10, max(1, steps // 2))
    first, last = float(np.mean(losses[:n])), float(np.mean(losses[-n:]))
    checks["loss_falls"] = {"ok": bool(steps >= 2 and last < first),
                            "first": first, "last": last, "over": n}
    checks["compiles_in_window"] = win.compile_check()
    whole_window = steps * build.tokens_per_step / win.seconds
    # a window too short for two reads has no block to take a median of
    tokens_per_s = stats.median_rate(
        stamps, log_every * build.tokens_per_step
    ) or whole_window
    facts["tokens_per_s"] = tokens_per_s
    block_ms = [1e3 * (b - a) for a, b in zip(stamps, stamps[1:])]
    print(json.dumps({"window": {
        "steps": steps, "seconds": win.seconds,
        "step_ms": 1e3 * win.seconds / max(1, steps),
        "tokens_per_s": tokens_per_s,
        "tokens_per_s_whole_window": whole_window,
        "block_ms": block_ms,
        "loss_first": first, "loss_last": last,
        "peak_bytes": peak, "memory_stats": memory,
        "reference": checks["reference"],
        "peak_bytes_with_check": device.peak_bytes(ctx.devices),
    }}), flush=True)

    end_to_end = {"train_tokens_per_s": tokens_per_s}
    if peak is not None:
        end_to_end["train_peak_hbm_gib"] = peak / 2.0 ** 30
    exe.close()
    return win.attach(Run(attempted=attempted, failed=failed, checks=checks,
                          end_to_end=end_to_end, facts=facts))


def _expect_kernels(ctx, facts):
    want = ctx.expect.get("custom_calls")
    got = facts["custom_calls"]
    if ctx.rehearse:
        return {"ok": True, "skipped": "off the chip the kernels take "
                "their jnp path", "custom_calls": got, "expected": want}
    return {"ok": got == want, "custom_calls": got, "expected": want}


def _expect_collectives(ctx, facts, scope, build):
    """The cell's collective kinds are in the step, and the state lives
    on every device."""
    want = ctx.expect.get("collectives", [])
    missing = [k for k in want if not facts["collectives"].get(k)]
    name = build.main.all_parameters()[0].name
    on = sorted(d.id for d in scope.find_var(name).devices())
    return {"ok": not missing and len(on) == ctx.chips,
            "missing": missing, "found": facts["collectives"],
            "state_on_devices": on}
