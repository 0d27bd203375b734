#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: checks the device, builds the cell from its files, warms up
the cell's own shapes, measures for `--seconds`, reads the device's peak,
then checks the outputs against the plain reference (after the window, so
that the reference's own memory is in no number), and prints as the LAST
line of standard output

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}[, "breakdown": {...}]}

with the cell's end-to-end metrics (`--trace 0`) or its per-layer
metrics from a short profiled window (`--trace 1`). Sample counts,
losses, checks and compile-cache hits go on earlier lines. Without a TPU,
or with fewer chips than the cell asks for, it exits with code 2 before
it builds anything and prints no result.

`--rehearse` (not used by the driver) runs the same code on the CPU at
the configuration's `tiny` sizes (a several-chip cell on that many
virtual devices), prints no metric, and names the device it ran on.
"""

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TRACE_DIR = os.path.join(ROOT, ".bench_traces")
# libtpu otherwise logs under /tmp, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    return ap.parse_args(argv)


def _span_table(spans):
    """{span name: count and milliseconds at a few quantiles} of the
    program's spans inside the window (for the record line)."""
    from benchmark.harness import stats

    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s["dur"] / 1e3)
    return {
        name: {"n": len(ms), "min": min(ms),
               **{f"p{q}": stats.percentile(ms, q) for q in (10, 50, 90)}}
        for name, ms in sorted(by_name.items())
    }


def main(argv=None):
    args = parse(argv)
    from benchmark.harness import manifest as mf

    manifest = mf.load()
    entry, cell_file = mf.cell(manifest, args.workload)
    chips = entry["chips"]
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}"
        ).strip()

    from benchmark.harness import device, spans
    from benchmark.harness.context import Context
    from benchmark.harness.meter import CompileMeter

    spans.reserve()
    import jax

    if args.rehearse:
        record = device.record()
        if record["count"] < chips:
            raise SystemExit(f"benchmark: rehearsal needs {chips} devices, "
                             f"JAX reports {record}")
    else:
        record = device.require_tpu(chips)
    meter = CompileMeter()
    if not args.rehearse:
        from paddle_tpu.core import compile_cache

        print(json.dumps({"compile_cache": compile_cache.enable(),
                          "jax": jax.__version__}), flush=True)

    traffic = dict(cell_file["traffic"])
    if args.rehearse:
        traffic.update(cell_file.get("rehearse", {}))
    seconds = args.seconds
    if seconds is None:
        seconds = 2.0 if args.rehearse else float(manifest["run_seconds"])
    ctx = Context(
        chips=chips,
        config=mf.config(manifest, entry["config"]), traffic=traffic,
        expect=cell_file.get("expect", {}), seed=args.seed,
        seconds=seconds, trace=bool(args.trace), rehearse=args.rehearse,
        devices=jax.devices()[:chips], meter=meter,
        trace_dir=os.path.join(TRACE_DIR, args.workload),
    )
    run = mf.driver(traffic["kind"]).run(ctx)
    run.end_to_end["setup_s"] = (
        run.facts["t_window_start"] - T_PROCESS_START
    )

    total = meter.since()
    print(json.dumps({"compile": {
        **{f"setup_{k}_s": run.facts["setup_meter"][k]
           for k in ("compile", "trace", "lower", "backend")},
        "cache_hits": total["hits"], "cache_misses": total["misses"],
        "programs": total["compiles"],
        "compiles_in_window": run.facts["compiles_in_window"],
    }, "checks": {k: v.get("ok") for k, v in run.checks.items()}}),
        flush=True)

    if ctx.trace:
        print(json.dumps({"spans": _span_table(run.spans)}), flush=True)
    section = "per_layer" if ctx.trace else "end_to_end"
    metrics = {}
    for m in mf.metrics_of(manifest, section, args.workload):
        if ctx.trace:
            try:
                value = mf.reader(m["name"])(run)
            except KeyError:
                # off the chip there is no table of peaks to read from
                if not args.rehearse:
                    raise
                value = None
        else:
            value = run.end_to_end.get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    record["memory_peak_bytes"] = run.facts["peak_bytes"]
    line = {"correct": run.correct, "attempted": run.attempted,
            "failed": run.failed, "metrics": metrics, "device": record}
    if ctx.trace:
        from benchmark.harness import breakdown

        if not args.rehearse:
            line["device"].update(breakdown.busy_and_window(run))
        line["breakdown"] = breakdown.of(run)
    if args.rehearse:
        # a CPU run gives no time, rate or share of a device
        line["metrics"] = {}
        line["rehearsal"] = {"reported": sorted(metrics),
                             "checks": run.checks}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
