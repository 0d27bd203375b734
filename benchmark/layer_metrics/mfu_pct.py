"""Model FLOP/s utilization: closed-form forward + backward operations
per token (benchmark/harness/flops.py) times the tokens per second of
this run's window, over chips times the chip's published bf16 peak."""

from benchmark.harness import peaks


def read(run):
    f = run.facts
    peak = peaks.peaks(f["device_kind"])["bf16_flops_per_s"]
    return 100.0 * f["flops_per_token"] * f["tokens_per_s"] / (
        f["chips"] * peak
    )
