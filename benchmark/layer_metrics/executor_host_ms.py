"""Host work of one `Executor.run(..., return_numpy=False)`: prologue,
dispatch, write-back. A stand-in: the SHORTEST of the program's
`executor.step` spans in the window. A call's span is the host's work
plus whatever time the call waited for room in the dispatch queue, so
the shortest is the closest to the host alone; most calls wait a device
step (PR 22: 182 ms at the median of all calls against 20 ms at their
10th percentile on four chips). The window's logging reads leave two
steps queued, so only its first call is sure to find the device idle
(while the reads emptied the queue, the first call after each read did,
and this metric was their median). The tracing issue's span around the
prologue alone replaces this."""


def read(run):
    ms = [s["dur"] / 1e3 for s in run.spans if s["name"] == "executor.step"]
    return min(ms) if ms else None
