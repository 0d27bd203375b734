"""Profiler (reference platform/profiler.h:126 RecordEvent,
EnableProfiler/DisableProfiler :208-211, fluid/profiler.py:255 context
manager, tools/timeline.py Chrome-trace conversion).

TPU-native: jax.profiler captures BOTH host events and device (TPU) events
into an xplane trace — the role CUPTI's DeviceTracer played for CUDA.
`profiler()` wraps start/stop; `RecordEvent` annotates host spans that show
up inline with device ops; `summary()` aggregates the captured xplane into
the reference's per-op time table (EnableProfiler's table) without needing
TensorBoard: by XLA instruction kind (`fusion`, `copy`), or, with
``by="scope"``, by the model's section and Fluid op.

The scopes come from the capture itself. The runtime files the HLO of
every module it executed in the capture's `/host:metadata` plane (one
serialized `HloProto` a module, named `jit_<label>(<program id>)` as the
module's events on the device's "XLA Modules" line; seen on the TPU and on
the CPU client, JAX 0.9.0), and each instruction's `op_name` holds the
`jax.named_scope` path it was traced under: `fluid.name_scope` and the
op's type (framework/registry.py::run_op). `capture_scopes` reads them
with a few lines of protobuf wire format (no schema package is imported),
so nothing is lowered a second time and a capture taken by another
process reads the same.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
import tempfile

_active_dir = None


def start_profiler(state="All", tracer_option="Default", log_dir=None):
    """reference fluid.profiler.start_profiler(:131). state/tracer_option
    accepted for parity; jax.profiler always captures host+device."""
    global _active_dir
    import jax

    _active_dir = log_dir or tempfile.mkdtemp(prefix="paddle_tpu_prof_")
    jax.profiler.start_trace(_active_dir)
    return _active_dir


def stop_profiler(sorted_key=None, profile_path=None):
    """reference fluid.profiler.stop_profiler(:198): stop + print summary."""
    global _active_dir
    import jax

    # clear _active_dir BEFORE stop_trace: if the runtime raises mid-stop,
    # a later start_profiler must not see a phantom active session
    out_dir, _active_dir = _active_dir, None
    jax.profiler.stop_trace()
    # the per-Fluid-op table where the capture holds the programs' scopes
    table = summary(out_dir, by="scope")
    if not any(row[0] != UNSCOPED for row in table):
        table = summary(out_dir)
    if table:
        print(_format_table(table))
    if profile_path:
        import shutil

        os.makedirs(os.path.dirname(profile_path) or ".", exist_ok=True)
        shutil.copytree(out_dir, profile_path, dirs_exist_ok=True)
    return out_dir


@contextlib.contextmanager
def profiler(state="All", sorted_key="total", profile_path=None,
             tracer_option="Default", log_dir=None):
    """reference fluid.profiler.profiler context (:255)."""
    start_profiler(state, tracer_option, log_dir)
    try:
        yield
    finally:
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def RecordEvent(name):
    """Host-span annotation visible in the trace (platform/profiler.h:126)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


record_event = RecordEvent


def cuda_profiler(*args, **kwargs):  # pragma: no cover - API parity shim
    raise RuntimeError(
        "cuda_profiler is CUDA-only (reference profiler.py:39); use "
        "profiler()/start_profiler on TPU"
    )


def _op_kind(name):
    """Base op kind of an xplane event name: the leading identifier chars —
    digits included, so `fusion.2`, `all-reduce.1` and names *starting* with
    a digit all aggregate by base kind (XLA's `.<id>` instance suffix stops
    at the dot); anything unmatched falls back to 24-char truncation."""
    m = re.match(r"%?([a-zA-Z0-9\-_]+)", name)
    return m.group(1) if m else name[:24]


def _newest_xplane(trace_dir):
    files = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    )
    return files[-1] if files else None


def summary(trace_dir, by="kind"):
    """Aggregate device-op time from the xplane capture, device planes
    first (a CPU capture has none: the host client's executed
    instructions stand in).

    ``by="kind"``: [(op_kind, total_ms, count)] sorted by time, by XLA
    instruction kind; on the chip its first row is `fusion`.
    ``by="scope"``: the reference's per-op-type profile table,
    [(scope, total_ms, calls, share)] by the scope an instruction was
    traced under (`attn/proj/mul`: fluid.name_scope, then the Fluid op's
    type), by SELF time: an event's duration less the events nested
    inside it on its line (a `while` and its body's ops are both there),
    so the rows add up to the device's busy time. Events whose module or
    instruction the capture's metadata does not hold go under
    ``"(unscoped)"``."""
    path = _newest_xplane(trace_dir)
    if path is None:
        return []
    if by not in ("kind", "scope"):
        raise ValueError(f"summary: by={by!r} (\"kind\" or \"scope\")")
    scopes = capture_scopes(path) if by == "scope" else None
    agg, total = {}, 0
    for line in _executed(path):
        times = [dur for _m, _n, _s, dur in line]
        if scopes is not None:
            times = self_times([(start, dur) for _m, _n, start, dur in line])
        for (module, name, _start, _dur), ns in zip(line, times):
            if scopes is None:
                key = _op_kind(name)
            else:
                key = scopes.get(module, {}).get(_op_name(name)) or UNSCOPED
            t, c = agg.get(key, (0, 0))
            agg[key] = (t + ns, c + 1)
            total += ns
    rows = sorted(agg.items(), key=lambda kv: -kv[1][0])
    if scopes is None:
        return [(k, ns / 1e6, c) for k, (ns, c) in rows]
    return [(k, ns / 1e6, c, ns / total if total else 0.0)
            for k, (ns, c) in rows]


def _executed(path):
    """The executed instructions in a capture, a list a line, each
    [(module, event name, start_ns, duration_ns)] by start: the "XLA Ops"
    line of every device plane, each event with the "XLA Modules" event
    of its plane that encloses it (None for none); where there is no
    device plane (the CPU backend), the host client's events that carry
    `hlo_op` / `hlo_module` / `program_id` stats, else every host event
    (so that the table still shows activity)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    lines = []
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        by_name = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in by_name:
            continue
        modules = sorted(
            (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for ev in getattr(by_name.get("XLA Modules"), "events", ())
        )
        ops = sorted((ev.start_ns, ev.duration_ns, ev.name)
                     for ev in by_name["XLA Ops"].events)
        line, i = [], 0
        for start, dur, name in ops:
            while i < len(modules) and modules[i][1] <= start:
                i += 1
            inside = i < len(modules) and modules[i][0] <= start
            line.append((modules[i][2] if inside else None, name, start,
                         dur))
        lines.append(line)
    if lines:
        return lines
    rest = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for ln in plane.lines:
            if ln.name == "python":
                continue
            line = []
            for ev in ln.events:
                stats = dict(ev.stats)
                module = None
                if "hlo_op" in stats:
                    module = "{}({})".format(stats.get("hlo_module"),
                                             stats.get("program_id"))
                (rest if module is None else line).append(
                    (module, ev.name, ev.start_ns, ev.duration_ns))
            if line:
                lines.append(sorted(line, key=lambda e: e[2]))
    return lines or [sorted(rest, key=lambda e: e[2])]


def self_times(events):
    """The self time of each of one line's `events` [(start, duration)],
    sorted by start: its duration less that of the events nested
    directly inside it (a child ends before its parent does)."""
    out = [dur for _start, dur in events]
    enclosing = []  # indices of the events still open, outermost first
    for i, (start, dur) in enumerate(events):
        while enclosing:
            p_start, p_dur = events[enclosing[-1]]
            if p_start + p_dur > start:
                break
            enclosing.pop()
        if enclosing:
            p_start, p_dur = events[enclosing[-1]]
            if start + dur <= p_start + p_dur:
                out[enclosing[-1]] -= dur
        enclosing.append(i)
    return out


UNSCOPED = "(unscoped)"


# ---------------------------------------------------------------------------
# what a capture says about the programs it ran
# ---------------------------------------------------------------------------


def _op_name(event_name):
    """The HLO instruction's name of a device event: the event's name is
    the instruction's whole line (`%fusion.3 = ...`), or its name."""
    m = re.match(r"%?([\w.\-]+)", event_name)
    return m.group(1) if m else event_name


def _fields(buf):
    """(field number, wire type, value) of each field of one protobuf
    message: a varint as an int, a length-delimited field as a
    memoryview of its bytes (a sub-message, a string, packed varints),
    fixed-width fields as their bytes. The readers below name the few
    fields they read by number, from xplane.proto and hlo.proto."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key = shift = 0
        while True:
            b = buf[i]
            i += 1
            key |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                break
        number, wire = key >> 3, key & 7
        if wire == 0:
            value = shift = 0
            while True:
                b = buf[i]
                i += 1
                value |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
        elif wire == 2:
            size = shift = 0
            while True:
                b = buf[i]
                i += 1
                size |= (b & 0x7F) << shift
                shift += 7
                if b < 0x80:
                    break
            value = buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value = buf[i:i + size]
            i += size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield number, wire, value


def _packed(buf):
    """The values of a packed run of varints."""
    out, value, shift = [], 0, 0
    for b in bytes(buf):
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            out.append(value)
            value = shift = 0
    return out


def _field(buf, number):
    """The values of every occurrence of field `number` in a message."""
    return [v for n, _w, v in _fields(buf) if n == number]


def capture_modules(path):
    """{module: serialized HloProto} of the programs a capture ran, from
    its `/host:metadata` plane: `module` is `jit_<label>(<program id>)`,
    the name of the module's events on a device's "XLA Modules" line
    (and `hlo_module(program_id)` of a CPU client's events)."""
    with open(path, "rb") as f:
        space = f.read()
    out = {}
    for plane in _field(space, 1):                   # XSpace.planes
        name = _field(plane, 2)                      # XPlane.name
        if not name or bytes(name[0]) != b"/host:metadata":
            continue
        for entry in _field(plane, 4):               # .event_metadata
            for meta in _field(entry, 2):            # the map's value
                names = _field(meta, 2)              # XEventMetadata.name
                protos = [blob for stat in _field(meta, 5)  # .stats
                          for blob in _field(stat, 6)]  # XStat.bytes_value
                if names and protos:
                    out[bytes(names[0]).decode()] = protos[0]
    return out


# opcodes whose called computations run as instructions of their own
_CALLS = frozenset({"while", "conditional", "call", "async-start"})
# opcodes that never run as an instruction of their own
_NEVER_RUN = frozenset({"parameter", "constant", "tuple",
                        "get-tuple-element", "bitcast"})


def scope_of(op_name):
    """The scope in an instruction's `op_name`: the `jit(...)`
    components (the program's, a kernel wrapper's) and the wrappers of
    transformed code (`transpose(jvp(...))`) taken out and the trailing
    primitive's name cut off:
    `jit(afmoe_decode)/jit(main)/moe/experts/moe_local_experts/dot_general`
    -> `moe/experts/moe_local_experts`."""
    path = re.sub(r"\b[a-z_]+\(|\)", "", re.sub(r"jit\([^)]*\)/?", "",
                                                 op_name))
    return "/".join([p for p in path.split("/") if p][:-1])


def op_scopes(hlo_proto):
    """{instruction name: scope} of a serialized HloProto's instructions
    that can appear as a device event: those of the entry computation,
    of `while` bodies and of called computations, not the inside of a
    fused computation or of a reduction's scalar one. The scope is `scope_of` the instruction's own
    `op_name`; an instruction without one (a copy the compiler put in,
    an asynchronous pair) gets the scope most of a fusion's fused
    instructions, else most of its operands' producers, else most of
    its users have, else ""."""
    module = next(v for n, _w, v in _fields(hlo_proto) if n == 1)
    scope, operands, called, listed = {}, {}, {}, []
    inside = {}  # computation id -> (its instructions' ids, fused)
    entry = None
    for n, _w, comp in _fields(module):
        if n == 6:                                   # .entry_computation_id
            entry = comp
        if n != 3:                                   # .computations
            continue
        comp_id, fused, ids = None, False, []
        for c, _w, v in _fields(comp):
            if c == 5:                               # .id
                comp_id = v
            elif c == 7:                             # .is_fusion_computation
                fused = bool(v)
            elif c == 2:                             # .instructions
                name = opcode = ""
                own, iid, ops, calls = "", None, [], []
                for f, fw, x in _fields(v):
                    if f == 1:
                        name = bytes(x).decode()
                    elif f == 2:
                        opcode = bytes(x).decode()
                    elif f == 7:                     # .metadata.op_name
                        own = next((bytes(y).decode()
                                    for g, _w, y in _fields(x) if g == 2), "")
                    elif f == 35:
                        iid = x
                    elif f == 36:
                        ops += [x] if fw == 0 else _packed(x)
                    elif f == 38:
                        calls += [x] if fw == 0 else _packed(x)
                ids.append(iid)
                scope[iid] = scope_of(own) if own else ""
                operands[iid], called[iid] = ops, calls
                listed.append((iid, name, opcode))
        inside[comp_id] = (ids, fused)

    def most(ids):
        votes = {}
        for i in ids:
            if scope.get(i):
                votes[scope[i]] = votes.get(scope[i], 0) + 1
        return max(votes, key=votes.get) if votes else ""

    users = {}
    for iid, ops in operands.items():
        for i in ops:
            users.setdefault(i, []).append(iid)
    # operands come before their users in a computation's list; a second
    # pass settles what a computation listed later decides, and gives
    # what reads parameters alone (a weight's convert or copy) the scope
    # of what reads IT
    for _pass in range(2):
        for iid, _name, _opcode in listed:
            if not scope[iid]:
                scope[iid] = most(
                    i for c in called[iid] for i in inside.get(c, ((),))[0]
                ) or most(operands[iid]) or most(users.get(iid, ()))
    # what runs as an instruction of its own: the entry computation's
    # and, from there, the bodies that control flow calls (not a
    # reduction's or a sort's scalar computation, not a fusion's)
    opcode_of = {iid: opcode for iid, _name, opcode in listed}
    runs, todo = set(), [entry]
    while todo:
        comp = todo.pop()
        if comp in runs or comp not in inside:
            continue
        runs.add(comp)
        for iid in inside[comp][0]:
            if opcode_of[iid] in _CALLS:
                todo += called[iid]
    running = {i for comp in runs for i in inside[comp][0]}
    return {name: scope[iid] for iid, name, opcode in listed
            if iid in running and opcode not in _NEVER_RUN}


def capture_scopes(path):
    """{module: {instruction name: scope}} of every program a capture
    (an `.xplane.pb`) ran: `op_scopes` of the HLO the capture itself
    holds (`capture_modules`)."""
    return {module: op_scopes(proto)
            for module, proto in capture_modules(path).items()}


def _format_table(table):
    """`summary`'s rows as text: by kind (three columns) or by scope
    (four: with each row's share of the whole)."""
    by_scope = table and len(table[0]) == 4
    lines = ["-------- device op profile --------",
             f"{'scope' if by_scope else 'op kind':<56}{'total ms':>12}"
             f"{'count':>8}" + (f"{'share':>8}" if by_scope else "")]
    for row in table[:30]:
        line = f"{row[0]:<56}{row[1]:>12.3f}{row[2]:>8}"
        lines.append(line + (f"{100 * row[3]:>7.1f}%" if by_scope else ""))
    return "\n".join(lines)
