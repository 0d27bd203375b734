"""Reduction of a profiler trace (`.xplane.pb`) to device numbers.

The JAX profiler writes one `.xplane.pb` per capture; `jax.profiler.
ProfileData` reads it with nothing but JAX. A device plane
(`/device:TPU:<n>`) carries a line "XLA Ops" with one event per executed
HLO instruction (its name is the instruction's whole HLO line), a line
"Async XLA Ops" with one event per asynchronous operation from its
`-start` to its `-done` (further lines, "XLA Modules" and "Steps", hold
one event per executed program); host threads are lines of `/host:CPU`, where the benchmark's own
`jax.profiler.TraceAnnotation` spans (`bench.*`) land on the same clock.

Everything below the loader works on plain `(name, start_ns, dur_ns)`
tuples, so the arithmetic is testable on hand-made intervals and a
recorded trace can be kept as a small JSON file (`dump` / `load`).
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import gzip
import json
import os
import re

from .hlo import COLLECTIVE_KINDS

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"


@dataclasses.dataclass
class Line:
    plane: str
    name: str
    events: list  # [(name, start_ns, dur_ns)], by start


@dataclasses.dataclass
class Trace:
    lines: list

    def device_planes(self):
        """Names of the device planes that carry an ops line, in order."""
        names = {ln.plane for ln in self.lines
                 if ln.name == OPS_LINE and _is_device(ln.plane)}
        return sorted(names, key=_plane_index)

    def line(self, plane, name):
        for ln in self.lines:
            if ln.plane == plane and ln.name == name:
                return ln.events
        return []

    def host_events(self, prefix):
        """Events of host threads whose name starts with `prefix`."""
        out = []
        for ln in self.lines:
            if not _is_device(ln.plane):
                out += [e for e in ln.events if e[0].startswith(prefix)]
        return sorted(out, key=lambda e: e[1])


def _is_device(plane):
    return plane.startswith("/device:")


def _plane_index(plane):
    m = re.search(r":(\d+)\s*$", plane)
    return int(m.group(1)) if m else 0


def newest_xplane(trace_dir):
    files = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    ), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path, keep_host_prefix="bench."):
    """A Trace from an `.xplane.pb`, or from a JSON file `dump` wrote.
    Device planes are kept whole; of the host planes only the events
    whose name starts with `keep_host_prefix` (host planes are large and
    the reduction reads nothing else from them)."""
    if path.endswith((".json", ".json.gz")):
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as f:
            raw = json.load(f)
        return Trace([
            Line(r["plane"], r["name"], [tuple(e) for e in r["events"]])
            for r in raw["lines"]
        ])
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    lines = []
    for plane in pd.planes:
        device = _is_device(plane.name)
        for ln in plane.lines:
            events = [
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in ln.events
                if device or ev.name.startswith(keep_host_prefix)
            ]
            if events:
                events.sort(key=lambda e: e[1])
                lines.append(Line(plane.name, ln.name, events))
    return Trace(lines)


def dump(trace, path, window=None):
    """Write `trace` (cut to `window` = (t0, t1) where given) as JSON."""
    rows = []
    for ln in trace.lines:
        events = ln.events if window is None else clip(ln.events, *window)
        if events:
            rows.append({"plane": ln.plane, "name": ln.name,
                         "events": [list(e) for e in events]})
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "wt") as f:
        json.dump({"lines": rows}, f)


# ---------------------------------------------------------------------------
# interval arithmetic (nanoseconds)
# ---------------------------------------------------------------------------


def clip(events, t0, t1):
    """The events that overlap [t0, t1], cut to it."""
    out = []
    for name, start, dur in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a or (dur == 0 and t0 <= start <= t1):
            out.append((name, a, max(0.0, b - a)))
    return out


def union(events):
    """Merged, sorted [(start, end)] of the events' intervals."""
    spans = sorted((s, s + d) for _n, s, d in events if d > 0)
    out = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals):
    return sum(b - a for a, b in intervals)


def subtract(intervals, holes):
    """The part of merged `intervals` not covered by merged `holes`."""
    out = []
    j = 0
    for a, b in intervals:
        cur = a
        while j < len(holes) and holes[j][1] <= cur:
            j += 1
        k = j
        while k < len(holes) and holes[k][0] < b:
            ha, hb = holes[k]
            if ha > cur:
                out.append((cur, min(ha, b)))
            cur = max(cur, hb)
            if cur >= b:
                break
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


def gaps(busy, t0, t1):
    """The idle intervals of [t0, t1] given the merged busy intervals."""
    return subtract([(t0, t1)], busy)


# ---------------------------------------------------------------------------
# names
# ---------------------------------------------------------------------------


def op_name(event_name):
    """The HLO instruction name of an "XLA Ops" event: `%fusion.3 = ...`
    and `fusion.3` both give `fusion.3`."""
    m = re.match(r"%?([\w.\-]+)", event_name)
    return m.group(1) if m else event_name


def op_kind(event_name):
    """Base kind of an op event: the leading identifier up to XLA's
    `.<id>` instance suffix (`fusion.2` -> `fusion`)."""
    m = re.match(r"%?([a-zA-Z0-9\-_]+)", event_name)
    return m.group(1) if m else event_name[:24]


def is_collective(event_name):
    kind = op_kind(event_name)
    for suffix in ("-start", "-done"):
        if kind.endswith(suffix):
            kind = kind[: -len(suffix)]
    return kind in COLLECTIVE_KINDS


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------


def window_of(trace, name="bench.window"):
    """(t0, t1) of the benchmark's window annotation on the trace's
    clock; None when the annotation is not in the trace."""
    events = [e for e in trace.host_events(name) if e[0] == name]
    if not events:
        return None
    _n, start, dur = max(events, key=lambda e: e[2])
    return start, start + dur


def device_ops(trace, plane, window):
    return clip(trace.line(plane, OPS_LINE), *window)


def busy_seconds(trace, window):
    """Per device plane, the seconds of `window` in which an op ran."""
    return {
        plane: total(union(device_ops(trace, plane, window))) / 1e9
        for plane in trace.device_planes()
    }


def sum_by(events, key):
    """{key(name): seconds}, by time descending."""
    out = {}
    for name, _s, dur in events:
        k = key(name)
        out[k] = out.get(k, 0.0) + dur / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def collective_seconds(ops, async_ops=()):
    """(seconds in which a collective was in flight, seconds of them
    during which no other op ran) for one device: `ops` its clipped
    "XLA Ops" events (synchronous collectives, and the `-start` / `-done`
    instructions of asynchronous ones), `async_ops` its clipped "Async
    XLA Ops" events (an asynchronous collective from start to done)."""
    coll = union([e for e in [*ops, *async_ops] if is_collective(e[0])])
    other = union([e for e in ops if not is_collective(e[0])])
    return total(coll) / 1e9, total(subtract(coll, other)) / 1e9


def idle_gap_owners(trace, plane, window, top=10):
    """[[owner, seconds]]: the device's idle time in `window`, by what
    the host was doing. A gap belongs to the `bench.*` annotation (other
    than the window's own) that covers most of it; gaps no annotation
    covers go to "unannotated"."""
    busy = union(device_ops(trace, plane, window))
    hosts = [e for e in trace.host_events("bench.")
             if e[0] != "bench.window"]
    owners = {}
    for a, b in gaps(busy, *window):
        best, best_cover = "unannotated", 0.0
        for name, start, dur in hosts:
            if start >= b:
                break
            cover = min(b, start + dur) - max(a, start)
            if cover > best_cover:
                best, best_cover = name, cover
        owners[best] = owners.get(best, 0.0) + (b - a) / 1e9
    ranked = sorted(owners.items(), key=lambda kv: -kv[1])
    return [[k, v] for k, v in ranked[:top]]


def busy_inside(events, starts, t0, t1):
    """Nanoseconds of [t0, t1] covered by `events` (sorted by start,
    `starts` their start times)."""
    return total(union(clip(between(events, starts, t0, t1), t0, t1)))


def between(events, starts, t0, t1):
    """The events (sorted by start, `starts` their start times) that may
    overlap [t0, t1]."""
    lo = bisect.bisect_left(starts, t0)
    # an event that started earlier may still run into the interval:
    # step back while the previous one ends after t0
    while lo > 0 and events[lo - 1][1] + events[lo - 1][2] > t0:
        lo -= 1
    hi = bisect.bisect_right(starts, t1)
    return events[lo:hi]
