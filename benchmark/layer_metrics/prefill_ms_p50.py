"""Median of the program's `serving.prefill` span: today's stand-in for
the time to the first token (no token leaves before the last)."""

from benchmark.harness import spans, stats


def read(run):
    return stats.median(spans.durations_ms(run.spans, "serving.prefill"))
