"""The traced run's device record and `breakdown`."""

from __future__ import annotations

from . import xplane


def busy_and_window(run):
    """`busy_s` (seconds in which an op ran, averaged over the chips
    used) and `window_s` of the traced window."""
    t0, t1 = run.window_ns
    busy = xplane.busy_seconds(run.trace, run.window_ns)
    if not busy:
        raise RuntimeError("the trace holds no device plane with ops")
    return {"busy_s": sum(busy.values()) / len(busy),
            "window_s": (t1 - t0) / 1e9}


def of(run, top=10):
    """The device op kinds that took most time and the idle time by what
    the host was doing, on the first device."""
    planes = run.trace.device_planes()
    if not planes:
        return {"device_ops": [], "idle_gaps": []}
    plane = planes[0]
    ops = run.device_ops()
    kinds = xplane.sum_by(ops, xplane.op_kind)
    return {
        "device_ops": [[k, v] for k, v in list(kinds.items())[:top]],
        "idle_gaps": xplane.idle_gap_owners(run.trace, plane,
                                            run.window_ns, top),
    }
