"""Roofline share of the delta-rule state update in decode steps: the
least time the chip could take for what one linear layer's step NEEDS
(the float32 state of the batch read once and written once, plus the
step's q, k, v, b, al rows and o; benchmark/harness/qwen3_next_cost.py)
over the time the `gdn_state_update` events took per linear layer and
step. The update is bound by the state's traffic; no implementation
that keeps the state in HBM can read above 100%."""

import json

from benchmark.harness import flops, peaks, qwen3_next_cost

from . import gdn_generate_mfu_pct, moe_expert_ms_per_token
from .gdn_update_ms_per_token import KERNEL


def read(run):
    found = moe_expert_ms_per_token.in_decode_loops(run, KERNEL)
    counted = gdn_generate_mfu_pct.counted(run)
    if found is None or counted is None:
        return None
    _totals, model = counted
    layers = sum(1 for kind, _ffn in model["layer_kinds"]
                 if kind == qwen3_next_cost.LINEAR)
    took_ms = found[0] / (found[1] * layers)
    need = qwen3_next_cost.decode_gdn_need(model, model["batch"])
    least_s, bound = flops.roofline_seconds(
        *need, peaks.peaks(run.facts["device_kind"])
    )
    print(json.dumps({"gdn_update_roofline": {
        "bound": bound, "least_ms": 1e3 * least_s, "took_ms": took_ms,
        "flops": need[0], "bytes": need[1],
    }}), flush=True)
    return 100.0 * 1e3 * least_s / took_ms
