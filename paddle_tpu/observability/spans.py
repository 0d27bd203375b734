"""Host spans: named regions in a bounded ring buffer AND in the profiler.

`span("name")` records always (unless the monitor kill-switch is off) into
a deque capped at PADDLE_TPU_SPAN_BUFFER entries (default 4096) — old
spans fall off, counted as ``trace.spans_dropped``, so a long-running
trainer never grows memory. The ring's stamps are wall-clock; a
jax.profiler capture runs on a clock of its own (events start near 0 at
``start_trace``), so the ring alone cannot be laid over the device's
lines. Every LIVE span therefore also enters a
``jax.profiler.TraceAnnotation(name)`` — the mechanism behind
profiler.RecordEvent: an atomic flag read while no capture runs; under a
capture the span sits on its thread's line of the host plane, on the
device lines' clock and nested as the code nests. :func:`record` spans
are retrospective (their start is already past) and stay ring-only.

Causal tracing (trace.py): when a TraceContext is active on the recording
thread, the span record additionally carries ``trace_id`` / ``span_id`` /
``parent_id`` and pushes its own child context while the body runs, so
nested spans — and spans on other threads holding a capture()/activate()
handoff of this context — chain into one reconstructible tree.
:func:`record` writes a span retrospectively (known duration, ended now)
for costs measured after the fact, e.g. a request's queue wait.

The kill-switch is the ONE metrics switch: every write path here consults
``metrics.enabled()`` (PADDLE_TPU_MONITOR=0 / set_enabled), never a local
flag, so spans and traces die with counters — not just when the buffer is
sized to zero.

Export goes through tools/timeline._ChromeTraceFormatter, so host spans
are ordinary Chrome-trace "X" events (trace ids ride in ``args``): load
them alone (`chrome_trace()`) or merged with a jax.profiler device
capture (`tools.timeline.Timeline(dir, include_host_spans=True)`) in one
Perfetto-loadable JSON.
"""

from __future__ import annotations

import collections
import functools
import os
import threading
import time

from jax.profiler import TraceAnnotation

from . import metrics, trace

try:
    # clamp: deque(maxlen=negative) raises; malformed env must not break
    # `import paddle_tpu`
    _MAX_SPANS = max(0, int(os.environ.get("PADDLE_TPU_SPAN_BUFFER", "4096")))
except ValueError:
    _MAX_SPANS = 4096
_lock = threading.Lock()
_spans: collections.deque = collections.deque(maxlen=_MAX_SPANS)


def _push(rec):
    """Append to the ring; a span the full ring pushes out is counted."""
    with _lock:
        dropped = _spans.maxlen and len(_spans) == _spans.maxlen
        _spans.append(rec)
    if dropped:
        metrics.add("trace.spans_dropped")


class _Span:
    """Context manager AND decorator recording one span: a ring-buffer
    record plus a profiler annotation while the body runs."""

    __slots__ = ("name", "category", "args", "seconds", "_wall_us", "_t0",
                 "_trace", "_annotation")

    def __init__(self, name, category="host", args=None):
        self.name = name
        self.category = category
        self.args = args or {}
        # the span's own duration once it has ended (None while it runs,
        # and when monitoring is off): callers that split a step by its
        # child spans read this instead of a second clock
        self.seconds = None
        self._t0 = None
        self._trace = None  # (trace_id, span_id, parent_id) when traced

    @property
    def span_id(self):
        """This span's id once entered under an active TraceContext
        (None otherwise) — lets producers parent later work under it."""
        return self._trace[1] if self._trace else None

    def __enter__(self):
        self._trace = None
        self.seconds = None
        if metrics.enabled():
            self._wall_us = time.time_ns() / 1e3
            self._t0 = time.perf_counter_ns()
            ctx = trace.current()
            if ctx is not None:
                sid = trace.new_id()
                self._trace = (ctx.trace_id, sid, ctx.span_id)
                trace._push(ctx.child(sid))
            self._annotation = TraceAnnotation(self.name)
            self._annotation.__enter__()
        else:
            self._t0 = None
        return self

    def refresh(self):
        """Enter the profiler annotation anew; the ring's record stays
        one. A span entered before `start_trace` is not in the capture at
        all: a span that may be open long before anybody starts one (the
        scheduler's wait for work) calls this as it polls, so that the
        capture shows it from the next poll on, as consecutive pieces
        under its one name."""
        if self._t0 is not None:
            self._annotation.__exit__(None, None, None)
            self._annotation = TraceAnnotation(self.name)
            self._annotation.__enter__()

    def __exit__(self, *exc):
        if self._t0 is not None:
            self._annotation.__exit__(*exc)
            if self._trace is not None:
                trace._pop()
            dur_us = (time.perf_counter_ns() - self._t0) / 1e3
            self.seconds = dur_us / 1e6
            rec = {
                "name": self.name,
                "cat": self.category,
                "ts": self._wall_us,
                "dur": dur_us,
                "tid": threading.get_ident(),
                "args": self.args,
            }
            if self._trace is not None:
                rec["trace_id"], rec["span_id"], rec["parent_id"] = \
                    self._trace
                metrics.add("trace.spans")
            _push(rec)
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with _Span(self.name, self.category, self.args):
                return fn(*args, **kwargs)

        return wrapper


def span(name: str, category: str = "host", **args) -> _Span:
    """``with span("executor.step", step=i): ...`` or ``@span("f")``."""
    return _Span(name, category, args)


def record(name, duration_s, category="host", ctx=None, args=None):
    """Retrospectively record a span that ENDED now and lasted
    ``duration_s`` — for costs only measurable after the fact (a
    request's queue wait, a batch slot's dispatch share). ``ctx`` parents
    the span (default: the thread's active context; pass a captured
    context to file it under another thread's trace). Returns the new
    span_id, or None when monitoring is off. Ring-only: a profiler
    annotation cannot be entered in the past, so a capture does not show
    these."""
    if not metrics.enabled():
        return None
    if ctx is None:
        ctx = trace.current()
    dur_us = max(0.0, float(duration_s)) * 1e6
    rec = {
        "name": name,
        "cat": category,
        "ts": time.time_ns() / 1e3 - dur_us,
        "dur": dur_us,
        "tid": threading.get_ident(),
        "args": dict(args or {}),
    }
    sid = None
    if ctx is not None:
        sid = trace.new_id()
        rec["trace_id"] = ctx.trace_id
        rec["span_id"] = sid
        rec["parent_id"] = ctx.span_id
        metrics.add("trace.spans")
    _push(rec)
    return sid


def get_spans() -> list[dict]:
    with _lock:
        return list(_spans)


def span_count() -> int:
    with _lock:
        return len(_spans)


def reset() -> None:
    with _lock:
        _spans.clear()


def emit_into(fmt, pid: int = 0) -> None:
    """Write the buffered spans into a _ChromeTraceFormatter as process
    `pid`, one trace tid per host thread. Trace ids (when present) ride
    in each event's args so export files alone reconstruct causality."""
    recs = get_spans()
    fmt.emit_pid("paddle_tpu host spans", pid)
    tids: dict[int, int] = {}
    for rec in recs:
        tid = tids.setdefault(rec["tid"], len(tids))
    for native_tid, tid in sorted(tids.items(), key=lambda kv: kv[1]):
        fmt.emit_tid(f"thread-{native_tid}", pid, tid)
    for rec in recs:
        args = rec["args"]
        if "trace_id" in rec:
            args = dict(args)
            args["trace_id"] = rec["trace_id"]
            args["span_id"] = rec["span_id"]
            if rec.get("parent_id") is not None:
                args["parent_id"] = rec["parent_id"]
        fmt.emit_region(
            rec["ts"], rec["dur"], pid, tids[rec["tid"]], rec["cat"],
            rec["name"], args,
        )


def chrome_trace(pretty: bool = False) -> str:
    """Buffered spans alone as Chrome-trace JSON ("M" metadata + "X"
    duration events; chrome://tracing / Perfetto loadable)."""
    from ..tools.timeline import _ChromeTraceFormatter

    fmt = _ChromeTraceFormatter()
    emit_into(fmt, pid=0)
    return fmt.format_to_string(pretty)


def save_chrome_trace(path: str, pretty: bool = False) -> str:
    with open(path, "w") as f:
        f.write(chrome_trace(pretty))
    return path
