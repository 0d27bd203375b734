"""The part of `decode_idle_ms_per_token` under `executor.fetch`: the
device is idle while the host waits for, then copies, the step's logits."""

from benchmark.harness import program_trace


def read(run):
    return program_trace.decode_idle_ms(run, ("executor.fetch",))
