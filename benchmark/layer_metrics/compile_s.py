"""Seconds of set-up spent tracing, lowering and compiling (or loading
from the persistent cache), as `jax.monitoring` reports them."""


def read(run):
    return run.facts["setup_meter"]["compile"]
