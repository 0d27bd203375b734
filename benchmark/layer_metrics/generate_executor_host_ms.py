"""Host share of one decode step: the median `executor.step` span inside
`serving.decode_loop` (which there includes the blocking fetch) minus the
device time of a decode step from the trace."""

from benchmark.harness import spans, stats

from . import decode_device_ms


def read(run):
    steps = spans.inside(run.spans, "executor.step", "serving.decode_loop")
    whole = stats.median([s["dur"] / 1e3 for s in steps])
    device = decode_device_ms.read(run)
    if whole is None or device is None:
        return None
    return whole - device
