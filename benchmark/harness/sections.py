"""Device time of a generate cell by phase and by model section.

Since PR 35 the program names what a capture used to leave anonymous.
Each compiled step is an XLA module named after its program
(`jit_<family>_prefill`, `jit_<family>_decode`: the events of the
device's "XLA Modules" line), so an op event's PHASE is the module event
that encloses it. Each instruction's `op_name` holds the
`fluid.name_scope` it was built in (`attn/proj/mul`), and the capture
files every module's HLO in its `/host:metadata` plane, which
`paddle_tpu.profiler.capture_scopes` reads: an op event's SCOPE is its
module's entry for its instruction, its SECTION the scope's first
component (`embed` counts under `head`). Time is SELF time: an event's
duration less the events nested inside it on the line (a `while` and its
body's ops are both there), so nothing is counted twice and a phase's
sections add up to its busy time.

A parent commit's capture has one module name for every program
(`jit_traced`) and its program has no `capture_scopes`: every reduction
here then returns None and the line leaves the metric out.
"""

from __future__ import annotations

import bisect
import json
import re

from . import program_trace, xplane

MODULES_LINE = "XLA Modules"
SECTIONS = ("attn", "mlp", "moe", "ssm", "head")
UNSCOPED = "unscoped"
PREFILL_SPAN = "serving.prefill"
HANDOVER = ("serving.complete", "serving.assemble", "serving.form_batch")


def phase_of(module):
    """"prefill" or "decode" for the module of a generator's program
    (`jit_afmoe_decode(1234)`, a second compile's `jit_gpt_prefill_0f3a9c1e`),
    None for any other."""
    m = re.match(r"jit_\w+?_(prefill|decode)(?:_[0-9a-f]{8})?(?:\(|$)",
                 module or "")
    return m.group(1) if m else None


def section_of(scope):
    """A scope's section: its first component, `embed` under `head`;
    UNSCOPED for no scope or a component outside the vocabulary."""
    first = (scope or "").split("/", 1)[0]
    if first == "embed":
        return "head"
    return first if first in SECTIONS else UNSCOPED


def two_levels(scope):
    return "/".join(scope.split("/")[:2]) if scope else UNSCOPED


def self_times(events):
    """Self nanoseconds of each of one line's `events` [(name, start,
    dur)], sorted by start: its duration less the events nested directly
    inside it."""
    out = [dur for _n, _s, dur in events]
    enclosing = []  # indices of the events that are still open
    for i, (_n, start, dur) in enumerate(events):
        while enclosing:
            _pn, ps, pd = events[enclosing[-1]]
            if ps + pd > start:
                break
            enclosing.pop()
        if enclosing:
            _pn, ps, pd = events[enclosing[-1]]
            if start + dur <= ps + pd:
                out[enclosing[-1]] -= dur
        enclosing.append(i)
    return out


def modules_of(events, modules):
    """For each of `events` (sorted by start) the name of the `modules`
    event (sorted, disjoint) whose interval holds its start, else None."""
    starts = [s for _n, s, _d in modules]
    out = []
    for _n, start, _d in events:
        k = bisect.bisect_right(starts, start) - 1
        inside = k >= 0 and start < modules[k][1] + modules[k][2]
        out.append(modules[k][0] if inside else None)
    return out


def _cpu_lines(path):
    """A CPU rehearsal's stand-in for the device's lines: per host
    thread, the client's events that carry `hlo_op`, each with its module
    as the metadata plane names it (`hlo_module(program_id)`)."""
    if path.endswith((".json", ".json.gz")):
        return []
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for ln in plane.lines:
            rows = []
            for ev in ln.events:
                stats = dict(ev.stats)
                if ev.duration_ns > 0 and "hlo_op" in stats:
                    module = "{}({})".format(stats.get("hlo_module"),
                                             stats.get("program_id"))
                    rows.append((module, (ev.name, float(ev.start_ns),
                                          float(ev.duration_ns))))
            rows.sort(key=lambda r: r[1][1])
            if rows:
                lines.append(([m for m, _e in rows], [e for _m, e in rows]))
    return lines


def _scopes(run):
    """{module: {instruction: scope}} of the run's capture; None where
    the program cannot read one (a parent commit's). `facts["scopes"]`
    stands in for a recorded capture (tests)."""
    if "scopes" in run.facts:
        return run.facts["scopes"]
    from paddle_tpu import profiler

    read = getattr(profiler, "capture_scopes", None)
    program_trace.of(run)
    path = run.facts["_program_trace_path"]
    if read is None or path.endswith((".json", ".json.gz")):
        return None
    return read(path)


def rows(run):
    """[(module, scope, self_ns, start_ns, dur_ns)] of the first device's
    op events inside the window, by start; None where the capture names no
    phase (no `jit_<family>_prefill` / `_decode` module) or the program
    reads no scopes."""
    return program_trace._once(run, "_section_rows", lambda: _rows(run))


def _rows(run):
    scopes = _scopes(run)
    if scopes is None:
        return None
    t0, t1 = run.window_ns
    trace = program_trace.of(run)
    planes = trace.device_planes()
    if planes:
        ops = trace.line(planes[0], xplane.OPS_LINE)
        lines = [(modules_of(ops, trace.line(planes[0], MODULES_LINE)), ops)]
    else:
        lines = _cpu_lines(run.facts["_program_trace_path"])
    out = []
    for modules, ops in lines:
        for module, (name, start, dur), ns in zip(modules, ops,
                                                  self_times(ops)):
            if t0 <= start < t1:
                scope = scopes.get(module, {}).get(xplane.op_name(name), "")
                out.append((module, scope, ns, start, dur))
    if not any(phase_of(row[0]) for row in out):
        return None
    return sorted(out, key=lambda r: r[3])


def _whole_spans(run, name):
    """The window's whole `name` spans as merged intervals."""
    t0, t1 = run.window_ns
    return xplane.union([
        (n, s, e - s) for n, s, e in program_trace._spans(run)
        if n == name and s >= t0 and e <= t1
    ])


def phases(run):
    """{phase: {"n": what the phase's times are divided by (prefill: the
    window's whole `serving.prefill` spans = batches; decode: the
    `executor.step` spans inside its whole `serving.decode_loop` spans),
    "sections": {section: ns}, "scopes": {scope: ns}, "busy_ns": the
    union of the phase's events}}, over the phase module's events inside
    those spans; None where `rows` is."""
    return program_trace._once(run, "_section_phases", lambda: _phases(run))


def _phases(run):
    table = rows(run)
    if table is None:
        return None
    from benchmark.layer_metrics.moe_expert_ms_per_token import decode_loops

    out = {}
    prefills = _whole_spans(run, PREFILL_SPAN)
    # (a phase's whole spans, what its times are divided by)
    found = {"prefill": (prefills, len(prefills)),
             "decode": decode_loops(run) or ((), 0)}
    for phase, (spans, n) in found.items():
        if not spans or not n:
            continue
        starts = [a for a, _b in spans]
        sections, scopes, events = {}, {}, []
        for module, scope, ns, start, dur in table:
            k = bisect.bisect_right(starts, start) - 1
            if phase_of(module) != phase or k < 0 or start >= spans[k][1]:
                continue
            section = section_of(scope)
            sections[section] = sections.get(section, 0.0) + ns
            scopes[scope] = scopes.get(scope, 0.0) + ns
            events.append((module, start, dur))
        if sections:
            out[phase] = {"n": n, "sections": sections, "scopes": scopes,
                          "busy_ns": xplane.total(xplane.union(events))}
    return out or None


def section_ms(run, phase, section):
    """Milliseconds of `section` a batch (prefill) or a step (decode);
    None where the phase was not read or has no such section."""
    found = phases(run)
    if not found or section not in found.get(phase, {}).get("sections", {}):
        return None
    return found[phase]["sections"][section] / found[phase]["n"] / 1e6


def _top(table, n, key=lambda k: k):
    out = {}
    for scope, ns in table.items():
        out[key(scope)] = out.get(key(scope), 0.0) + ns
    ranked = sorted(out.items(), key=lambda kv: -kv[1])[:n]
    return {k: v / 1e6 for k, v in ranked}


def unscoped_pct(run):
    """Share of the window's busy self time whose event has no module of
    a phase or whose instruction has no scope in the vocabulary. Prints
    one line a phase (the sections, the ten largest two-level and the
    ten largest whole scopes, in milliseconds a batch or a step) and one
    for the modules of no phase."""
    table = rows(run)
    if table is None:
        return None
    total = unscoped = 0.0
    other = {}
    for module, scope, ns, _start, _dur in table:
        total += ns
        if phase_of(module) is None:
            name = (module or "no module").split("(")[0]
            other[name] = other.get(name, 0.0) + ns
            unscoped += ns
        elif section_of(scope) == UNSCOPED:
            unscoped += ns
    for phase, found in (phases(run) or {}).items():
        n = found["n"]
        per = {k: v / n for k, v in found["scopes"].items()}
        print(json.dumps({"sections": {
            "phase": phase, "per": "batch" if phase == "prefill" else "step",
            "n": n,
            "sections_ms": {k: v / n / 1e6
                            for k, v in sorted(found["sections"].items())},
            "sum_ms": sum(found["sections"].values()) / n / 1e6,
            "busy_ms": found["busy_ns"] / n / 1e6,
            "two_level_ms": _top(per, 10, two_levels),
            "scopes_ms": _top(per, 10),
        }}), flush=True)
    print(json.dumps({"sections": {
        "other_modules_ms": _top(other, 10), "window_self_ms": total / 1e6,
        "unscoped_ms": unscoped / 1e6,
    }}), flush=True)
    return 100.0 * unscoped / total if total else None


def idle_inside_ms(run, span):
    """Device idle inside the window's whole `span` spans, in
    milliseconds a span; None where there is nothing to read."""
    pieces = program_trace.idle_pieces(run)
    spans = _whole_spans(run, span)
    if pieces is None or not spans:
        return None
    inside = program_trace.inside(pieces, spans)
    return sum(b - a for a, b, _o in inside) / len(spans) / 1e6


def handover_idle_ms(run):
    """Device idle whose innermost span is one of the router's three
    hand-over spans, in milliseconds a `serving.batch` span of the
    window; prints the three apart. None where the capture holds no
    `serving.complete` span (a parent commit's)."""
    pieces = program_trace.idle_pieces(run)
    batches = _whole_spans(run, "serving.batch")
    names = {n for n, _s, _e in program_trace._spans(run)}
    if pieces is None or not batches or "serving.complete" not in names:
        return None
    owners = program_trace.by_owner(pieces)
    parts = {name: owners.get(name, 0.0) / len(batches) / 1e6
             for name in HANDOVER}
    print(json.dumps({"handover_idle_ms": parts,
                      "batches": len(batches)}), flush=True)
    return sum(parts.values())
