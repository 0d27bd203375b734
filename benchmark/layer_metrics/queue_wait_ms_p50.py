"""Median of the program's `serving.queue_wait` span: enqueue to the
instant the request's batch formed."""

from benchmark.harness import spans, stats


def read(run):
    return stats.median(spans.durations_ms(run.spans, "serving.queue_wait"))
