"""The program's own spans and kernel names in a traced run's capture.

Since PR 24 every live span of the program (`executor.*`, `spmd.*`,
`serving.*`) is also a `jax.profiler.TraceAnnotation`: under a capture it
sits on its thread's line of the host plane, on the clock of the device
lines, and each Pallas kernel's `name=` is the name of its instruction and
so of its event on the device's "XLA Ops" line (`flash_tiled_dkv.3`). The
harness's loader keeps only `bench.*` host events, so `of(run)` opens the
run's capture once more (the newest `.xplane.pb` under `.bench_traces/`)
keeping the program's prefixes.

The idle arithmetic: each idle gap of the first device inside the window
is cut at the boundaries of the program's spans, and each piece goes to
the INNERMOST span that covers it, on any host thread: of the spans that
cover a piece, the one that began last. Pieces under no span are unowned.
A parent commit's capture holds none of these spans and none of these
kernel names: every reduction here then returns None.
"""

from __future__ import annotations

import bisect
import heapq
import os

from . import manifest, xplane

PREFIXES = ("executor.", "spmd.", "serving.")
TRACE_ROOT = os.path.join(manifest.ROOT, ".bench_traces")
UNOWNED = "unowned"

# the Pallas kernel families by the `name=` their `pallas_call` passes
FAMILIES = {
    "attention": ("flash_attention", "flash_tiled", "ring_block"),
    "residual_ln": ("fused_residual", "layer_norm"),
}


def _once(run, key, make):
    """`make()` once per run, kept under `key` in the run's facts (nine
    readers share the reductions below)."""
    if key not in run.facts:
        run.facts[key] = make()
    return run.facts[key]


def of(run):
    """The run's capture with the program's spans kept (a
    `harness.xplane.Trace`), read once per run. `facts["program_trace"]`
    names a file to read in place of the newest capture (tests)."""
    trace = run.facts.get("_program_trace")
    if trace is None:
        path = run.facts.get("program_trace") \
            or xplane.newest_xplane(TRACE_ROOT)
        trace = xplane.load(path, keep_host_prefix=PREFIXES)
        run.facts["_program_trace"] = trace
        run.facts["_program_trace_path"] = path
    return trace


def spans_of(trace):
    """The program's spans on every host thread, [(name, start, end)] by
    start (nanoseconds on the capture's clock)."""
    out = []
    for ln in trace.lines:
        if not ln.plane.startswith("/device:"):
            out += [(n, s, s + d) for n, s, d in ln.events
                    if n.startswith(PREFIXES)]
    return sorted(out, key=lambda e: e[1])


def _spans(run):
    return _once(run, "_program_spans", lambda: spans_of(of(run)))


def device_ops(run):
    """The first device's op events inside the window. A CPU rehearsal's
    capture has no device plane: there the host-plane events that carry
    an `hlo_op` stat (the CPU client's executed instructions) stand in,
    so that a rehearsal runs every reader; it prints no number."""
    ops = run.device_ops()
    if ops is None and run.facts.get("device_kind", "").lower() == "cpu":
        of(run)
        ops = xplane.clip(_cpu_ops(run.facts["_program_trace_path"]),
                          *run.window_ns)
    return ops


def _cpu_ops(path):
    if path.endswith((".json", ".json.gz")):
        return []
    from jax.profiler import ProfileData

    events = [
        (ev.name, float(ev.start_ns), float(ev.duration_ns))
        for plane in ProfileData.from_file(path).planes
        if not plane.name.startswith("/device:")
        for ln in plane.lines for ev in ln.events
        if ev.duration_ns > 0 and any(k == "hlo_op" for k, _v in ev.stats)
    ]
    return sorted(events, key=lambda e: e[1])


# ---------------------------------------------------------------------------
# idle ownership
# ---------------------------------------------------------------------------


def own(gaps, spans):
    """[(start, end, owner)]: the merged, sorted idle `gaps` [(a, b)] cut
    at the boundaries of `spans` [(name, start, end)], each piece with the
    innermost span that covers it (the covering span that began last; of
    two that began together, the one that ends first, then the one that
    comes later in `spans`) or None."""
    by_start = sorted(spans, key=lambda s: (s[1], -s[2]))
    cuts = sorted({t for _n, s, e in spans for t in (s, e)})
    active = []  # heap: the covering span that began last on top
    pieces, i = [], 0
    for a, b in gaps:
        lo = bisect.bisect_right(cuts, a)
        hi = bisect.bisect_left(cuts, b)
        points = [a, *cuts[lo:hi], b]
        for p, q in zip(points, points[1:]):
            while i < len(by_start) and by_start[i][1] <= p:
                _n, s, e = by_start[i]
                heapq.heappush(active, (-s, e, -i, by_start[i]))
                i += 1
            # a span that ended lies below the top until it surfaces:
            # only the top is ever read
            while active and active[0][1] <= p:
                heapq.heappop(active)
            pieces.append((p, q, active[0][3] if active else None))
    return pieces


def idle_pieces(run):
    """`own` of the window's idle gaps on the first device by the
    program's spans; None where the capture holds no device ops or none
    of the program's spans (a parent commit's)."""
    def make():
        ops, spans = device_ops(run), _spans(run)
        if not ops or not spans:
            return None
        return own(xplane.gaps(xplane.union(ops), *run.window_ns), spans)

    return _once(run, "_idle_pieces", make)


def by_owner(pieces):
    """{owner name (UNOWNED for none): nanoseconds}, largest first."""
    out = {}
    for a, b, span in pieces:
        name = span[0] if span else UNOWNED
        out[name] = out.get(name, 0.0) + (b - a)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def inside(pieces, intervals):
    """The pieces that lie within one of the merged, sorted `intervals`
    (a piece never straddles a span's boundary, so its start decides)."""
    starts = [a for a, _b in intervals]
    out = []
    for piece in pieces:
        k = bisect.bisect_right(starts, piece[0]) - 1
        if k >= 0 and piece[1] <= intervals[k][1]:
            out.append(piece)
    return out


def decode_idle(run):
    """Device idle inside the window's `serving.decode_loop` spans:
    {"steps": the `executor.step` spans inside them, "total_ns", "owners":
    by_owner of the pieces}; None where there is nothing to read."""
    return _once(run, "_decode_idle", lambda: _decode_idle(run))


def _decode_idle(run):
    pieces = idle_pieces(run)
    if pieces is None:
        return None
    t0, t1 = run.window_ns
    spans = _spans(run)
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
    if not steps:
        return None
    owned = inside(pieces, loops)
    return {"steps": steps, "owners": by_owner(owned),
            "total_ns": sum(b - a for a, b, _o in owned)}


def decode_idle_ms(run, owners=None):
    """Milliseconds of device idle per decode step: all of it, or the
    part whose innermost span is one of `owners`."""
    idle = decode_idle(run)
    if idle is None:
        return None
    ns = idle["total_ns"] if owners is None else sum(
        idle["owners"].get(name, 0.0) for name in owners
    )
    return ns / idle["steps"] / 1e6


# ---------------------------------------------------------------------------
# kernel families
# ---------------------------------------------------------------------------


def family_of(event_name):
    """The family of a Pallas kernel's device event by its instruction
    name (`flash_tiled_dkv.3` -> "attention"), None for any other name
    (`traced.7`: a kernel compiled without a `name=`)."""
    kind = xplane.op_kind(event_name)
    for family, prefixes in FAMILIES.items():
        if kind.startswith(prefixes):
            return family
    return None


def pallas_seconds(run):
    """{family: seconds on the first device in the window} of the events
    that carry the names of the compiled step's `tpu_custom_call`
    instructions; those of no family under "other". None where there are
    no such events or none has a family (a parent commit's)."""
    def make():
        names = set(run.facts.get("custom_call_names", ()))
        out = {}
        for name, _s, dur in run.device_ops() or ():
            if xplane.op_name(name) in names:
                family = family_of(name) or "other"
                out[family] = out.get(family, 0.0) + dur / 1e9
        return out if set(out) - {"other"} else None

    return _once(run, "_pallas_seconds", make)


def pallas_ms_per_step(run, family):
    seconds = pallas_seconds(run)
    if seconds is None or family not in seconds:
        return None
    return 1e3 * seconds[family] / run.facts["steps"]


# ---------------------------------------------------------------------------
# the ring's spans (durations need no common clock)
# ---------------------------------------------------------------------------


def per_call_ms(ring_spans, outer, inner_names):
    """For each `outer` span of the ring, the summed milliseconds of the
    `inner_names` spans inside it on the same thread (stamps in
    microseconds; 10 us of slack, since a start is read on the wall clock
    and a duration on the monotonic one); outer spans with none inside
    are left out."""
    inner = [s for s in ring_spans if s["name"] in inner_names]
    out = []
    for o in ring_spans:
        if o["name"] != outer:
            continue
        lo, hi = o["ts"], o["ts"] + o["dur"]
        ms = [s["dur"] / 1e3 for s in inner
              if s["tid"] == o["tid"] and lo <= s["ts"]
              and s["ts"] + s["dur"] <= hi + 10.0]
        if ms:
            out.append(sum(ms))
    return out
