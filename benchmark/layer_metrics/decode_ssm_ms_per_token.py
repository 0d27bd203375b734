"""Device time of the Mamba mixer (projections, convolution, scan or one-
token update) in one decode step: self time of the `jit_<family>_decode`
module's events whose scope begins `ssm`, inside the window's whole
`serving.decode_loop` spans, an `executor.step` span inside them
(`harness/sections.py`)."""

from benchmark.harness import sections


def read(run):
    return sections.section_ms(run, "decode", "ssm")
