"""Share of the chip's bfloat16 peak that the window's requests needed,
for a latent-attention decoder: closed-form matrix-product operations of
the prompts prefilled in the expanded form and the tokens generated in
the absorbed form (benchmark/harness/mla_cost.py; causal keys; the
routed experts' part from the program's `assignments_local` counter, a
mean per token and expert layer), times the requests completed, over
window seconds times the published peak. None where the program
publishes another family's table or none."""

from benchmark.harness import mla_cost, peaks

from . import moe_counters


def read(run):
    found = moe_counters.window_counters(run)
    if found is None or found[1].get("family") != "dots_vlm":
        return None
    totals, model = found
    if not totals.get("moe.assignments_total"):
        return None
    local = model["top_k"] * totals["moe.assignments_local"] \
        / totals["moe.assignments_total"]
    f = run.facts
    flops = f["requests_completed"] * mla_cost.request_flops(
        model, model["context_len"], f["new_tokens"], local
    )
    peak = peaks.peaks(f["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / (f["window_s"] * f["chips"] * peak)
