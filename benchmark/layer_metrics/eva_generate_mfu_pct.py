"""Share of the chip's bfloat16 peak that the window's requests needed,
for an EvaByte decoder: closed-form operations of the prompts prefilled
and the bytes generated (benchmark/harness/evabyte_cost.py: every
product, the exact keys and the summaries each query scores, the
pooling of each chunk closed), times the requests completed, over window
seconds times the published peak. None where the program publishes
another family's table or none."""

from benchmark.harness import evabyte_cost, peaks


def model(run):
    """The model table the program published where it is an EvaByte
    decoder's; None otherwise."""
    from paddle_tpu import observability as obs

    table = obs.get_tables().get("serving.generate.model")
    if not table or table.get("family") != "evabyte":
        return None
    return table


def read(run):
    table = model(run)
    if table is None:
        return None
    f = run.facts
    flops = f["requests_completed"] * evabyte_cost.request_flops(
        table, table["context_len"], f["new_tokens"])
    peak = peaks.peaks(f["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / (f["window_s"] * f["chips"] * peak)
