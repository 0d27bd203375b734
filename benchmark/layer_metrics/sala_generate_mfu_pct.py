"""Share of the chip's bfloat16 peak that the window's requests needed,
for a MiniCPM-SALA decoder: closed-form operations of the prompts
prefilled and the tokens generated (benchmark/harness/
minicpm_sala_cost.py: every product of both mixers and the FFN, the
Lightning recurrence at 5 operations a state element and token, the
sparse layers' causal keys, at most the selected blocks' beyond
`dense_len`), times the requests completed, over window seconds times
the published peak. None where the program publishes another family's
table or none."""

from benchmark.harness import minicpm_sala_cost, peaks


def model(run):
    """The model table the program published where it is a MiniCPM-SALA
    decoder's; None otherwise."""
    from paddle_tpu import observability as obs

    table = obs.get_tables().get("serving.generate.model")
    if not table or table.get("family") != "minicpm_sala":
        return None
    return table


def read(run):
    table = model(run)
    if table is None:
        return None
    f = run.facts
    flops = f["requests_completed"] * minicpm_sala_cost.request_flops(
        table, table["context_len"], f["new_tokens"])
    peak = peaks.peaks(f["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops / (f["window_s"] * f["chips"] * peak)
