"""Share of the chip's bfloat16 peak that the window's requests needed,
for a Qwen3-Next decoder: closed-form operations of the prompts
prefilled and the tokens generated (benchmark/harness/qwen3_next_cost.py:
every product of both mixers, the delta rule at 7 operations a state
element and token, the convolution; the routed experts' part from the
program's `assignments_local` counter, a mean per token and layer),
times the requests completed, over window seconds times the published
peak. None where the program publishes another family's table or
none."""

from benchmark.harness import peaks, qwen3_next_cost

from . import moe_counters


def counted(run):
    """(`moe_counters.window_counters`' totals, the model table) where
    the table is a Qwen3-Next decoder's; None otherwise."""
    found = moe_counters.window_counters(run)
    if found is None or found[1].get("family") != "qwen3_next":
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
    flops = f["requests_completed"] * qwen3_next_cost.request_flops(
        model, model["context_len"], f["new_tokens"], local
    )
    peak = peaks.peaks(f["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / (f["window_s"] * f["chips"] * peak)
