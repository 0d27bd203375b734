"""Roofline share of the state-space state update in decode steps: the
least time the chip could take for what one Mamba block's step NEEDS
(the float32 state of the batch read once and written once, plus the
step's x, B, C, dt rows and y; benchmark/harness/nemotron_h_cost.py)
over the time the `ssm_state_update` events took per Mamba block and
step. The update is bound by the state's traffic; no implementation
that keeps the state in HBM can read above 100%."""

import json

from benchmark.harness import flops, nemotron_h_cost, peaks

from . import hybrid_generate_mfu_pct, moe_expert_ms_per_token
from .ssm_update_ms_per_token import KERNEL


def read(run):
    found = moe_expert_ms_per_token.in_decode_loops(run, KERNEL)
    counted = hybrid_generate_mfu_pct.counted(run)
    if found is None or counted is None:
        return None
    _totals, model = counted
    blocks = model["pattern"].count(nemotron_h_cost.MAMBA)
    took_ms = found[0] / (found[1] * blocks)
    need = nemotron_h_cost.decode_ssm_need(model, model["batch"])
    least_s, bound = flops.roofline_seconds(
        *need, peaks.peaks(run.facts["device_kind"])
    )
    print(json.dumps({"ssm_update_roofline": {
        "bound": bound, "least_ms": 1e3 * least_s, "took_ms": took_ms,
        "flops": need[0], "bytes": need[1],
    }}), flush=True)
    return 100.0 * 1e3 * least_s / took_ms
