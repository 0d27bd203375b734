"""Median over batches of `serving.decode_loop` / (tokens - 1): today's
stand-in for the gap between tokens."""

from benchmark.harness import stats


def read(run):
    gaps = [s["dur"] / 1e3 / (s["args"]["tokens"] - 1)
            for s in run.spans
            if s["name"] == "serving.decode_loop"
            and s["args"].get("tokens", 0) > 1]
    return stats.median(gaps)
