"""Roofline share of the Lightning state update in decode steps: the
least time the chip could take for what one Lightning layer's step NEEDS
(the float32 state of the batch read once and written once, plus the
step's q, k, v rows and o; benchmark/harness/minicpm_sala_cost.py) over
the time the `lightning_state_update` events took per Lightning layer
and step. The update is bound by the state's traffic; no implementation
that keeps the state in HBM can read above 100%."""

import json

from benchmark.harness import flops, minicpm_sala_cost, peaks

from . import moe_expert_ms_per_token, sala_generate_mfu_pct
from .lightning_update_ms_per_token import KERNEL


def read(run):
    found = moe_expert_ms_per_token.in_decode_loops(run, KERNEL)
    model = sala_generate_mfu_pct.model(run)
    if found is None or model is None:
        return None
    layers = model["layer_kinds"].count(minicpm_sala_cost.LIGHTNING)
    took_ms = found[0] / (found[1] * layers)
    need = minicpm_sala_cost.decode_lightning_need(model, model["batch"])
    least_s, bound = flops.roofline_seconds(
        *need, peaks.peaks(run.facts["device_kind"])
    )
    print(json.dumps({"lightning_update_roofline": {
        "bound": bound, "least_ms": 1e3 * least_s, "took_ms": took_ms,
        "flops": need[0], "bytes": need[1],
    }}), flush=True)
    return 100.0 * 1e3 * least_s / took_ms
