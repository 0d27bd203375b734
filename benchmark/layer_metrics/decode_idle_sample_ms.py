"""The part of `decode_idle_ms_per_token` under `serving.sample`: argmax
over the fetched logits and the next step's feed, on the host."""

from benchmark.harness import program_trace


def read(run):
    return program_trace.decode_idle_ms(run, ("serving.sample",))
