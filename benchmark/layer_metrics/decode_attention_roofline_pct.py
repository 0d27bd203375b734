"""Roofline share of the decode attention over the KV caches: the least
time the chip could take for what one decode step NEEDS to read (of
every K and V cache the slots a query at the step's position may see,
once: the program's host-side counters `kv_cache.decode_bytes_needed`
over `kv_cache.decode_steps`, arithmetic on the positions it feeds) at
the published HBM rate, over the time the `decode_attention` events took
a step. The step is bound by that traffic (a few matrix-product
operations a byte); the need counts no slot beyond the position and
nothing twice, so no implementation can read above 100%."""

import json

from benchmark.harness import peaks

from . import moe_expert_ms_per_token
from .decode_attention_ms_per_token import KERNEL


def read(run):
    from paddle_tpu import observability as obs

    found = moe_expert_ms_per_token.in_decode_loops(run, KERNEL)
    counters = obs.get_counters()
    steps = counters.get("kv_cache.decode_steps", 0)
    if found is None or not steps:
        return None
    took_ms = found[0] / found[1]
    need = counters["kv_cache.decode_bytes_needed"] / steps
    rate = peaks.peaks(run.facts["device_kind"])["hbm_bytes_per_s"]
    least_ms = 1e3 * need / rate
    print(json.dumps({"decode_attention_roofline": {
        "bound": "memory", "least_ms": least_ms, "took_ms": took_ms,
        "bytes": need,
    }}), flush=True)
    return 100.0 * least_ms / took_ms
