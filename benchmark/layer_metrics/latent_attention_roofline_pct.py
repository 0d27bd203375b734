"""Roofline share of the absorbed latent attention over the latent
caches in decode steps: the least time the chip could take for what one
step NEEDS, the larger of a memory time (of every layer's cache the rows
a query at the step's position may see, once, at their r + dr lanes: the
program's host-side counters `kv_cache.decode_bytes_needed` over
`kv_cache.decode_steps`) and a compute time (those rows scored by every
head and summed: benchmark/harness/mla_cost.py), over the time the
`decode_attention` events took a step. 128 heads read ONE row, so the
call sits at the ridge (242 operations a byte against the chip's 240)
and either bound may hold; the line printed says which. No row beyond
the position and nothing twice is counted, so no implementation can read
above 100%. None where the program publishes another family's table."""

import json

from benchmark.harness import flops, mla_cost, peaks

from . import moe_expert_ms_per_token
from .decode_attention_ms_per_token import KERNEL


def read(run):
    from paddle_tpu import observability as obs

    found = moe_expert_ms_per_token.in_decode_loops(run, KERNEL)
    model = obs.get_tables().get("serving.generate.model") or {}
    counters = obs.get_counters()
    steps = counters.get("kv_cache.decode_steps", 0)
    if found is None or not steps or model.get("family") != "dots_vlm":
        return None
    took_ms = found[0] / found[1]
    need = mla_cost.decode_attention_need(
        model, counters["kv_cache.decode_bytes_needed"] / steps)
    least_s, bound = flops.roofline_seconds(
        *need, peaks.peaks(run.facts["device_kind"]))
    print(json.dumps({"latent_attention_roofline": {
        "bound": bound, "least_ms": 1e3 * least_s, "took_ms": took_ms,
        "flops": need[0], "bytes": need[1],
    }}), flush=True)
    return 100.0 * 1e3 * least_s / took_ms
