"""Roofline share of the EVA decode attention: the least time the chip
could take for what one layer's attention NEEDS in a decode step (the
live ring rows and the visible summary rows of K and V read once, the
step's q and o rows; benchmark/harness/evabyte_cost.py), averaged over a
batch's decode positions, over the time the `eva_decode_attention`
events took per layer and step. The call is bound by that traffic; no
implementation that keeps the ring and the summaries in HBM can read
above 100%."""

import json

from benchmark.harness import evabyte_cost, flops, peaks

from . import eva_generate_mfu_pct, moe_expert_ms_per_token
from .eva_attention_ms_per_token import KERNEL


def read(run):
    found = moe_expert_ms_per_token.in_decode_loops(run, KERNEL)
    model = eva_generate_mfu_pct.model(run)
    if found is None or model is None:
        return None
    took_ms = found[0] / (found[1] * model["num_layers"])
    need = evabyte_cost.decode_loop_eva_need(
        model, model["batch"], model["context_len"], run.facts["new_tokens"])
    least_s, bound = flops.roofline_seconds(
        *need, peaks.peaks(run.facts["device_kind"]))
    print(json.dumps({"eva_attention_roofline": {
        "bound": bound, "least_ms": 1e3 * least_s, "took_ms": took_ms,
        "flops": need[0], "bytes": need[1],
    }}), flush=True)
    return 100.0 * 1e3 * least_s / took_ms
