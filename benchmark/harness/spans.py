"""The program's own spans and counters, as the benchmark reads them.

The program keeps its spans in a ring (`PADDLE_TPU_SPAN_BUFFER`, 4096 by
default); `run.py` sets the variable before `paddle_tpu` is imported, and
`collect` fails when the ring filled, because a full ring has dropped
spans and a median over what is left would be of the newest only.
"""

from __future__ import annotations

import os

BUFFER_ENV = "PADDLE_TPU_SPAN_BUFFER"
BUFFER = 1 << 20


def reserve():
    """Called before `paddle_tpu` is imported."""
    os.environ[BUFFER_ENV] = str(BUFFER)


def collect(t0_wall_s, t1_wall_s):
    """The program's spans that began inside [t0, t1] (wall clock,
    seconds), each {"name", "ts" (us), "dur" (us), "tid", "args", ...}."""
    from paddle_tpu import observability as obs

    cap = int(os.environ.get(BUFFER_ENV, "4096"))
    if obs.span_count() >= cap:
        raise RuntimeError(
            f"the program's span ring is full ({cap}): spans were dropped"
        )
    lo, hi = t0_wall_s * 1e6, t1_wall_s * 1e6
    return [s for s in obs.get_spans() if lo <= s["ts"] <= hi]


def durations_ms(spans, name):
    return [s["dur"] / 1e3 for s in spans if s["name"] == name]


def inside(spans, inner_name, outer_name):
    """The `inner_name` spans that lie within an `outer_name` span of the
    same thread."""
    outers = [(s["tid"], s["ts"], s["ts"] + s["dur"])
              for s in spans if s["name"] == outer_name]
    out = []
    for s in spans:
        if s["name"] != inner_name:
            continue
        a, b = s["ts"], s["ts"] + s["dur"]
        if any(tid == s["tid"] and lo <= a and b <= hi
               for tid, lo, hi in outers):
            out.append(s)
    return out
