"""Device time of the embedding, the final norm, the vocabulary projection
and the greedy choice in one batch's prefill: self time of the
`jit_<family>_prefill` module's events whose scope begins `head` or
`embed`, inside the window's whole `serving.prefill` spans, a span
(`harness/sections.py`)."""

from benchmark.harness import sections


def read(run):
    return sections.section_ms(run, "prefill", "head")
