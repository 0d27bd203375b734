"""Device time of the expert layer (router, top-k, dispatch, the grouped
products, combine, shared expert) in one decode step: self time of the
`jit_<family>_decode` module's events whose scope begins `moe`, inside
the window's whole `serving.decode_loop` spans, an `executor.step` span
inside them (`harness/sections.py`)."""

from benchmark.harness import sections


def read(run):
    return sections.section_ms(run, "decode", "moe")
