"""Device time of the embedding, the final norm, the vocabulary projection
and the greedy choice in one decode step: self time of the
`jit_<family>_decode` module's events whose scope begins `head` or
`embed`, inside the window's whole `serving.decode_loop` spans, an
`executor.step` span inside them (`harness/sections.py`)."""

from benchmark.harness import sections


def read(run):
    return sections.section_ms(run, "decode", "head")
