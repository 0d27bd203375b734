"""Device time of the expert layer (router, top-k, dispatch, the grouped
products, combine, shared expert) in one batch's prefill: self time of
the `jit_<family>_prefill` module's events whose scope begins `moe`,
inside the window's whole `serving.prefill` spans, a span
(`harness/sections.py`)."""

from benchmark.harness import sections


def read(run):
    return sections.section_ms(run, "prefill", "moe")
