"""Roofline share of the routed-expert products in decode steps: the
least time the chip could take for what one expert layer's step NEEDS
(the weights of the experts actually hit, from the program's
`decode_experts_hit` counter, read once, plus the routed rows;
benchmark/harness/moe_cost.py) over the time the `moe_gmm` events took
per expert layer and step. Weights of experts no token chose are not
needed, so no implementation can read above 100%."""

import json

from benchmark.harness import flops, moe_cost, peaks

from . import moe_counters, moe_expert_ms_per_token


def read(run):
    found = moe_expert_ms_per_token.in_decode_loops(
        run, moe_expert_ms_per_token.KERNEL)
    counted = moe_counters.window_counters(run)
    if found is None or counted is None:
        return None
    totals, model = counted
    calls = totals.get("moe.decode_calls", 0)
    if not calls:
        return None
    layers = sum(1 for _a, ffn in model["layer_kinds"] if ffn == "experts")
    took_ms = found[0] / (found[1] * layers)
    need = moe_cost.decode_expert_need(
        model, totals["moe.decode_experts_hit"] / calls,
        totals["moe.decode_assignments_local"] / calls,
    )
    least_s, bound = flops.roofline_seconds(
        *need, peaks.peaks(run.facts["device_kind"])
    )
    print(json.dumps({"moe_expert_roofline": {
        "bound": bound, "least_ms": 1e3 * least_s, "took_ms": took_ms,
        "experts_hit_per_layer_step": totals["moe.decode_experts_hit"] / calls,
        "assignments_per_layer_step":
            totals["moe.decode_assignments_local"] / calls,
        "flops": need[0], "bytes": need[1],
    }}), flush=True)
    return 100.0 * 1e3 * least_s / took_ms
