"""Device time of the Mamba mixer (projections, convolution, scan or one-
token update) in one batch's prefill: self time of the
`jit_<family>_prefill` module's events whose scope begins `ssm`, inside
the window's whole `serving.prefill` spans, a span
(`harness/sections.py`)."""

from benchmark.harness import sections


def read(run):
    return sections.section_ms(run, "prefill", "ssm")
