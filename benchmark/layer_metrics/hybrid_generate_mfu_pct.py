"""Share of the chip's bfloat16 peak that the window's requests needed,
for a Nemotron-H decoder: closed-form matrix-product and scan operations
of the prompts prefilled and the tokens generated
(benchmark/harness/nemotron_h_cost.py; the routed experts' part from the
program's `assignments_local` counter, a mean per token and expert
block), times the requests completed, over window seconds times the
published peak. None where the program publishes another family's table
or none."""

from benchmark.harness import nemotron_h_cost, peaks

from . import moe_counters


def counted(run):
    """(`moe_counters.window_counters`' totals, the model table) where
    the table is a Nemotron-H decoder's; None otherwise."""
    found = moe_counters.window_counters(run)
    if found is None or found[1].get("family") != "nemotron_h":
        return None
    return found


def read(run):
    found = counted(run)
    if found is None:
        return None
    totals, model = found
    if not totals.get("moe.assignments_total"):
        return None
    local = model["top_k"] * totals["moe.assignments_local"] \
        / totals["moe.assignments_total"]
    f = run.facts
    flops = f["requests_completed"] * nemotron_h_cost.request_flops(
        model, model["context_len"], f["new_tokens"], local
    )
    peak = peaks.peaks(f["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / (f["window_s"] * f["chips"] * peak)
