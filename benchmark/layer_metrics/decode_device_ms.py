"""Device time of one decode step: the union of the op intervals on the
first device inside each `bench.exe_run.decode` annotation (the traced
run's Executor wraps `run`; a decode step blocks on its fetch, so the
step's device work lies inside the annotation), median over steps."""

from benchmark.harness import stats, xplane


def read(run):
    ops = run.device_ops()
    if not ops:
        return None
    starts = [e[1] for e in ops]
    busy = []
    for name, start, dur in run.trace.host_events("bench.exe_run.decode"):
        t0, t1 = start, start + dur
        if t0 < run.window_ns[0] or t1 > run.window_ns[1]:
            continue
        busy.append(xplane.busy_inside(ops, starts, t0, t1) / 1e6)
    return stats.median(busy)
