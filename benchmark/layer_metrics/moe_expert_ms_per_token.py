"""Device time of the routed-expert products in one decode step: the
`moe_gmm` kernel's events (the `name=` the program gave its Pallas call)
on the first device inside the window's `serving.decode_loop` spans,
summed, over the `executor.step` spans inside those loops (the program's
own spans: the reading does not hang on how the harness wraps a step)."""

import bisect

from benchmark.harness import program_trace, xplane

KERNEL = "moe_gmm"


def decode_loops(run):
    """(the window's whole `serving.decode_loop` spans as merged
    intervals, the number of `executor.step` spans inside them); None
    where the capture holds no such span (a parent commit's)."""
    spans = program_trace.spans_of(program_trace.of(run))
    t0, t1 = run.window_ns
    loops = xplane.union([
        (n, s, e - s) for n, s, e in spans
        if n == "serving.decode_loop" and s >= t0 and e <= t1
    ])
    if not loops:
        return None
    starts = [a for a, _b in loops]
    steps = 0
    for n, s, e in spans:
        if n == "executor.step":
            k = bisect.bisect_right(starts, s) - 1
            steps += k >= 0 and e <= loops[k][1]
    return (loops, steps) if steps else None


def in_decode_loops(run, prefix):
    """(summed milliseconds of the first device's events inside the
    window's decode loops whose instruction name starts with `prefix`,
    the decode steps in them). None where there is nothing to read."""
    found = decode_loops(run)
    ops = [e for e in program_trace.device_ops(run) or ()
           if xplane.op_kind(e[0]).startswith(prefix)]
    if not ops or found is None:
        return None
    loops, steps = found
    starts = [e[1] for e in ops]
    total = 0.0
    for a, b in loops:
        inside = xplane.clip(xplane.between(ops, starts, a, b), a, b)
        total += sum(d for _n, _s, d in inside) / 1e6
    return total, steps


def read(run):
    found = in_decode_loops(run, KERNEL)
    return None if found is None else found[0] / found[1]
