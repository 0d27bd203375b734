"""Share of the traced window in which no op ran on the device (the
device where that share is largest, on several chips)."""

from benchmark.harness import xplane


def read(run):
    t0, t1 = run.window_ns
    busy = xplane.busy_seconds(run.trace, run.window_ns)
    if not busy:
        return None
    return 100.0 * (1.0 - min(busy.values()) / ((t1 - t0) / 1e9))
