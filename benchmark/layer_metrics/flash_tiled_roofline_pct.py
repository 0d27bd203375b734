"""Roofline share of the KV-tiled flash attention family (forward, dkv,
dq) in a step where every Pallas call is of that family: the least time
the chip could take for the calls' operations and bytes
(benchmark/harness/flops.py) over the time the kernels took."""

import json

from benchmark.harness import flops, peaks

from . import pallas_ms_per_step


def read(run):
    cost = run.facts.get("kernel_cost")
    took_ms = pallas_ms_per_step.read(run)
    if cost is None or not took_ms:
        return None
    least_s, bound = flops.roofline_seconds(
        *cost, peaks.peaks(run.facts["device_kind"])
    )
    print(json.dumps({"flash_tiled_roofline": {
        "bound": bound, "least_ms": 1e3 * least_s, "took_ms": took_ms,
        "flops_per_step": cost[0], "bytes_per_step": cost[1],
    }}), flush=True)
    return 100.0 * 1e3 * least_s / took_ms
