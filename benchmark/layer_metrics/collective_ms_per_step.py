"""Device time with a collective in flight (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all; an asynchronous one from
its -start to its -done) per step, on the device where it is largest."""

from benchmark.harness import xplane


def per_device(run):
    """[(seconds in collectives, seconds of them exposed)] by device."""
    out = []
    for plane in run.trace.device_planes():
        ops = xplane.device_ops(run.trace, plane, run.window_ns)
        in_flight = xplane.clip(run.trace.line(plane, xplane.ASYNC_LINE),
                                *run.window_ns)
        out.append(xplane.collective_seconds(ops, in_flight))
    return out


def read(run):
    worst = max((c for c, _e in per_device(run)), default=0.0)
    return 1e3 * worst / run.facts["steps"] if worst else None
