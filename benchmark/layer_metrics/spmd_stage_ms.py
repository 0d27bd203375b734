"""Placing one step's arguments on the mesh: the median `spmd.stage`
span (inside `spmd.dispatch`, inside `executor.dispatch`)."""

from benchmark.harness import spans, stats


def read(run):
    return stats.median(spans.durations_ms(run.spans, "spmd.stage"))
