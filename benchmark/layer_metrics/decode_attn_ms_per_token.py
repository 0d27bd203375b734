"""Device time of the attention block (norm, projections, rotary, cache
write, the attention call) in one decode step: self time of the
`jit_<family>_decode` module's events whose scope begins `attn`, inside
the window's whole `serving.decode_loop` spans, an `executor.step` span
inside them (`harness/sections.py`)."""

from benchmark.harness import sections


def read(run):
    return sections.section_ms(run, "decode", "attn")
