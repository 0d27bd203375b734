"""Share of the chip's bfloat16 peak that the window's requests needed:
closed-form matrix-product operations of the prompts prefilled and the
tokens generated (benchmark/harness/moe_cost.py; the routed experts'
part from the program's `assignments_local` counter, a mean per token
and layer), times the requests completed, over window seconds times the
published peak."""

from benchmark.harness import moe_cost, peaks

from . import moe_counters


def read(run):
    counted = moe_counters.window_counters(run)
    if counted is None:
        return None
    totals, model = counted
    if not totals.get("moe.assignments_total"):
        return None
    local = model["top_k"] * totals["moe.assignments_local"] \
        / totals["moe.assignments_total"]
    f = run.facts
    flops = f["requests_completed"] * moe_cost.request_flops(
        model, model["context_len"], f["new_tokens"], local
    )
    peak = peaks.peaks(f["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / (f["window_s"] * f["chips"] * peak)
