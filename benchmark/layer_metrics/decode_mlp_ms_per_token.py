"""Device time of the dense feed-forward and its norm in one decode step:
self time of the `jit_<family>_decode` module's events whose scope
begins `mlp`, inside the window's whole `serving.decode_loop` spans, an
`executor.step` span inside them (`harness/sections.py`)."""

from benchmark.harness import sections


def read(run):
    return sections.section_ms(run, "decode", "mlp")
