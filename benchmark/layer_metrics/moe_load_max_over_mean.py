"""Imbalance of the routed experts held here: the most loaded expert's
rows over the mean expert's, summed over the window's expert-layer calls
(prefill blocks and decode steps alike; program counters)."""

from . import moe_counters


def read(run):
    counted = moe_counters.window_counters(run)
    if counted is None:
        return None
    totals, model = counted
    local = totals.get("moe.assignments_local", 0)
    if not local:
        return None
    return totals["moe.max_expert_load_sum"] * model["num_local_experts"] / local
