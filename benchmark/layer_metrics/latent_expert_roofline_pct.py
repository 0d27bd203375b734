"""Roofline share of the latent routed-expert products in decode steps
(as `moe_expert_roofline_pct`, with this family's closed form): the
least time the chip could take for what one expert block's step NEEDS
(the weights of the experts actually hit, from the program's
`decode_experts_hit` counter, read once at 2 x latent x width an expert,
plus the routed rows; benchmark/harness/nemotron_h_cost.py) over the
time the `moe_gmm` events took per expert block and step."""

import json

from benchmark.harness import flops, nemotron_h_cost, peaks

from . import hybrid_generate_mfu_pct, moe_expert_ms_per_token


def read(run):
    found = moe_expert_ms_per_token.in_decode_loops(
        run, moe_expert_ms_per_token.KERNEL)
    counted = hybrid_generate_mfu_pct.counted(run)
    if found is None or counted is None:
        return None
    totals, model = counted
    calls = totals.get("moe.decode_calls", 0)
    if not calls:
        return None
    blocks = model["pattern"].count(nemotron_h_cost.EXPERTS)
    took_ms = found[0] / (found[1] * blocks)
    hit = totals["moe.decode_experts_hit"] / calls
    assignments = totals["moe.decode_assignments_local"] / calls
    need = nemotron_h_cost.decode_expert_need(model, hit, assignments)
    least_s, bound = flops.roofline_seconds(
        *need, peaks.peaks(run.facts["device_kind"])
    )
    print(json.dumps({"latent_expert_roofline": {
        "bound": bound, "least_ms": 1e3 * least_s, "took_ms": took_ms,
        "experts_hit_per_block_step": hit,
        "assignments_per_block_step": assignments,
        "flops": need[0], "bytes": need[1],
    }}), flush=True)
    return 100.0 * 1e3 * least_s / took_ms
