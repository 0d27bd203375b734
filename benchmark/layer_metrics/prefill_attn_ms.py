"""Device time of the attention block (norm, projections, rotary, cache
write, the attention call) in one batch's prefill: self time of the
`jit_<family>_prefill` module's events whose scope begins `attn`, inside
the window's whole `serving.prefill` spans, a span
(`harness/sections.py`)."""

from benchmark.harness import sections


def read(run):
    return sections.section_ms(run, "prefill", "attn")
