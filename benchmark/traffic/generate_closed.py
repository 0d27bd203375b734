"""Traffic kind `generate_closed`: a closed loop of clients against a
generate endpoint behind `serving.Server`.

Parameters (the cell file's "traffic"): `batch` (the generator's batch
and the endpoint's only bucket), `prompt_len`, `new_tokens`, `clients`
(threads that submit, wait for the reply and submit again),
`max_wait_ms` (the router's co-batching wait), `prompts` (size of the
seeded pool the clients draw from), `trace_seconds` (the length of a
traced run's window), `logits_tol` for the comparison with the plain
reference.
"""

from __future__ import annotations

import importlib
import json

import numpy as np

from benchmark.harness import device, loadgen, stats
from benchmark.harness.context import Run, Window

ENDPOINT = "generate"


def _annotating_executor():
    """An Executor whose `run` sits inside a profiler annotation named
    after the program it runs, so that the device trace of a traced run
    can be cut per prefill and per decode step. Traced runs only: the
    end-to-end numbers are taken with the program's own Executor."""
    import jax

    import paddle_tpu as fluid

    class AnnotatingExecutor(fluid.Executor):
        names = {}

        def run(self, program=None, *args, **kwargs):
            name = self.names.get(id(program), "bench.exe_run")
            with jax.profiler.TraceAnnotation(name):
                return super().run(program, *args, **kwargs)

    return AnnotatingExecutor()


def run(ctx):
    from paddle_tpu.serving import EndpointConfig, Server

    traffic = ctx.traffic
    builder = importlib.import_module(
        f"benchmark.builders.{ctx.config['builder']}"
    )
    exe = _annotating_executor() if ctx.trace else None
    build = builder.build_generate(ctx.config, traffic, ctx.rehearse,
                                   ctx.seed, executor=exe)
    gen = build.generator
    if exe is not None:
        exe.names = {id(gen.prefill_prog): "bench.exe_run.prefill",
                     id(gen.decode_prog): "bench.exe_run.decode"}
    batch, new = traffic["batch"], traffic["new_tokens"]

    server = Server()
    server.add_endpoint(
        ENDPOINT, build.runner,
        EndpointConfig(buckets=(batch,), max_wait_ms=traffic["max_wait_ms"],
                       max_queue=4 * traffic["clients"]),
    )
    try:
        # the probe of the reference comparison is also the warm-up: it
        # runs the prefill and the decode program as a request's batch
        # does, so both are compiled before the window (`Server.warmup()`
        # would add a whole 128-token generation, 6.7 s, to every run's
        # set-up; `compiles_in_window` = 0 holds the equivalence). The
        # comparison itself puts the parameters on the device once more
        # and runs the reference there, so it waits until the window is
        # over and the device's peak has been read.
        seen = build.probe(np.random.RandomState(ctx.seed + 1))
        peak_setup = device.peak_bytes(ctx.devices)
        print(json.dumps({"setup": {"peak_bytes_after_setup": peak_setup}}),
              flush=True)

        rng = np.random.RandomState(ctx.seed)
        prompts = [build.make_prompt(rng) for _ in range(traffic["prompts"])]
        bad_tokens = []

        def submit(prompt):
            fut = server.submit(ENDPOINT, {"context_ids": prompt})
            fut.add_done_callback(_token_check)
            return fut

        def _token_check(fut):
            if fut.exception() is not None:
                return
            tokens = np.asarray(fut.result()[0])
            if tokens.shape != (new,) or tokens.min() < 0 \
                    or tokens.max() >= build.vocab_size:
                bad_tokens.append(tokens.shape)

        seconds = traffic["trace_seconds"] if ctx.trace else ctx.seconds
        with Window(ctx) as win:
            load = loadgen.closed_loop(
                submit, lambda r: prompts[r.randint(len(prompts))],
                traffic["clients"], seconds, ctx.seed,
            )
            win.close()
    finally:
        drained = server.close(timeout=60)

    lat_ms = [1e3 * x for x in load.latencies]
    beyond = stats.samples_beyond(len(lat_ms), 95)
    facts = win.facts()
    peak = facts["peak_bytes"]
    memory = device.memory_record(ctx.devices)
    checks = {
        "reference": build.check(seen),
        "tokens_in_range": {"ok": not bad_tokens, "bad": len(bad_tokens)},
        "compiles_in_window": win.compile_check(),
        "drained": {"ok": bool(drained)},
    }
    out_tokens_per_s = load.completed * new / win.seconds
    print(json.dumps({"window": {
        "seconds": win.seconds, "requests_completed": load.completed,
        "requests_attempted": load.attempted, "failed": load.failed,
        "errors": load.errors[:5],
        "latency_ms_p50": stats.median(lat_ms),
        "latency_ms_p95": stats.percentile(lat_ms, 95),
        "samples_beyond_p95": beyond, "peak_bytes": peak,
        "memory_stats": memory, "reference": checks["reference"],
        "peak_bytes_with_check": device.peak_bytes(ctx.devices),
    }}), flush=True)
    if beyond < 10 and not ctx.trace and not ctx.rehearse:
        checks["p95_samples"] = {"ok": False, "beyond": beyond,
                                 "need": 10}

    end_to_end = {"output_tokens_per_s": out_tokens_per_s}
    if lat_ms:
        end_to_end["request_latency_p95_ms"] = stats.percentile(lat_ms, 95)
    facts.update(peak_bytes_after_setup=peak_setup, new_tokens=new,
                 batch=batch, tokens_per_s=out_tokens_per_s,
                 requests_completed=load.completed)
    return win.attach(Run(attempted=load.attempted, failed=load.failed,
                          checks=checks, end_to_end=end_to_end, facts=facts))
