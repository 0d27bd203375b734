"""Metric primitives: counters, gauges, histograms, timers.

The registry is process-global and thread-safe (one lock; every public
entry point is a handful of dict ops under it). The whole subsystem is
default-on and cheap; setting ``PADDLE_TPU_MONITOR=0`` in the environment
turns every hook into an early-return no-op (the reference's STAT_ADD
macros compiled out the same way under WITH_PROFILER=OFF).

Histograms follow the Prometheus model: fixed upper-bound buckets plus
count/sum, extended with min/max because a snapshot without them cannot
answer "was there one terrible step?". Bucket edges are *inclusive*
(``value <= le`` lands in the ``le`` bucket); snapshots report cumulative
bucket counts so the Prometheus exporter is a straight dump.
"""

from __future__ import annotations

import bisect
import functools
import os
import threading
import time

# latency-oriented default edges, in seconds (sub-ms compile-cache hits up
# to multi-second cold compiles); generic value histograms can pass their own
DEFAULT_BUCKETS = (
    0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
    0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
)


def _env_enabled() -> bool:
    return os.environ.get("PADDLE_TPU_MONITOR", "1").lower() not in (
        "0", "false", "off",
    )


_enabled = _env_enabled()
_lock = threading.Lock()
_counters: dict[str, int] = {}
_gauges: dict[str, float] = {}
_histograms: dict[str, "_Histogram"] = {}
# structured tables (plain-JSON dicts, last value wins): richer artifacts a
# scalar cannot carry — e.g. the generator publishes its model's shape as
# "serving.generate.model", the watcher its "watch.findings"
_tables: dict[str, dict] = {}


def enabled() -> bool:
    return _enabled


def set_enabled(flag: bool | None) -> None:
    """Toggle the whole subsystem; ``None`` re-reads PADDLE_TPU_MONITOR."""
    global _enabled
    _enabled = _env_enabled() if flag is None else bool(flag)


class _Histogram:
    __slots__ = ("bounds", "bucket_counts", "count", "total", "min", "max")

    def __init__(self, buckets):
        self.bounds = tuple(sorted(float(b) for b in buckets))
        self.bucket_counts = [0] * (len(self.bounds) + 1)  # +1 = +Inf
        self.count = 0
        self.total = 0.0
        self.min = float("inf")
        self.max = float("-inf")

    def observe(self, value: float) -> None:
        self.bucket_counts[bisect.bisect_left(self.bounds, value)] += 1
        self.count += 1
        self.total += value
        if value < self.min:
            self.min = value
        if value > self.max:
            self.max = value

    def to_dict(self) -> dict:
        cum, buckets = 0, []
        for le, c in zip(self.bounds, self.bucket_counts):
            cum += c
            buckets.append([le, cum])
        buckets.append(["+Inf", self.count])
        return {
            "count": self.count,
            "sum": self.total,
            "min": self.min if self.count else None,
            "max": self.max if self.count else None,
            "buckets": buckets,
        }


# -- write side -------------------------------------------------------------
def add(name: str, value: int = 1) -> None:
    """Bump the monotonic counter `name` (reference STAT_ADD)."""
    if not _enabled:
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + int(value)


def set_gauge(name: str, value: float) -> None:
    """Write the gauge `name` (last value wins)."""
    if not _enabled:
        return
    with _lock:
        _gauges[name] = float(value)


def observe(name: str, value: float, buckets=None) -> None:
    """Record `value` into the histogram `name` (created on first use;
    `buckets` only takes effect at creation)."""
    if not _enabled:
        return
    with _lock:
        h = _histograms.get(name)
        if h is None:
            h = _histograms[name] = _Histogram(buckets or DEFAULT_BUCKETS)
        h.observe(float(value))


def drop_gauges(prefix: str) -> None:
    """Remove every gauge whose name starts with `prefix`. For publishers
    whose gauge SET varies with the source (one gauge per family, per
    replica, ...): dropping before re-publishing keeps gauges of a
    previous source from surviving as stale."""
    with _lock:
        for k in [k for k in _gauges if k.startswith(prefix)]:
            del _gauges[k]


def set_table(name: str, table: dict) -> None:
    """Publish the structured table `name` (plain JSON types; last value
    wins — snapshots carry it under "tables")."""
    if not _enabled:
        return
    with _lock:
        _tables[name] = table


def drop_tables(prefix: str) -> None:
    """Remove every table whose name starts with `prefix` — the table
    analogue of :func:`drop_gauges`, for publishers whose table describes
    ONE source: dropping on source switch keeps a stale table from being
    read as live for the new source."""
    with _lock:
        for k in [k for k in _tables if k.startswith(prefix)]:
            del _tables[k]


class _Timed:
    """Context manager AND decorator: wall time -> histogram `name`."""

    __slots__ = ("name", "buckets", "_t0")

    def __init__(self, name, buckets=None):
        self.name = name
        self.buckets = buckets
        self._t0 = None

    def __enter__(self):
        self._t0 = time.perf_counter() if _enabled else None
        return self

    def __exit__(self, *exc):
        if self._t0 is not None:
            observe(self.name, time.perf_counter() - self._t0, self.buckets)
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with _Timed(self.name, self.buckets):
                return fn(*args, **kwargs)

        return wrapper


def timed(name: str, buckets=None) -> _Timed:
    """``with timed("executor.step_latency"): ...`` or ``@timed("f")``."""
    return _Timed(name, buckets)


def window_p99(prev_buckets, cur_buckets, q=0.99):
    """p99 (or `q`-quantile) upper-bound estimate from the bucket-count
    delta between two cumulative-bucket snapshots — the one shared
    windowed-quantile primitive (the Watcher's SLO check, the brownout
    controller's watcher-less fallback, fleet_report's cross-process p99
    and the Watcher's journal mode all call this, so their answers agree
    by construction). Both sides are cumulative Prometheus buckets
    (``[[le, cum], ..., ["+Inf", count]]``); per-bucket subtraction
    yields the window's cumulative counts directly; ``prev_buckets=None``
    treats the window as all of `cur_buckets`. A quantile landing in
    +Inf reports the largest finite edge x2 — an upper bound is the
    conservative answer an SLO check wants. None when the window saw no
    observations."""
    prev = {str(le): c for le, c in (prev_buckets or [])}
    deltas = [(le, cum - prev.get(str(le), 0)) for le, cum in cur_buckets]
    total = deltas[-1][1] if deltas else 0
    if total <= 0:
        return None
    target = q * total
    finite = [float(le) for le, _ in deltas if not isinstance(le, str)]
    for le, cum_d in deltas:
        if cum_d >= target:
            if isinstance(le, str):  # +Inf bucket
                return (max(finite) * 2.0) if finite else float("inf")
            return float(le)
    return (max(finite) * 2.0) if finite else float("inf")


def merge_cumulative_buckets(bucket_lists):
    """Merge cumulative Prometheus bucket lists from SEVERAL histograms
    (e.g. one per process) into one cumulative list over the union of
    their edges. Each input's cumulative count at a foreign edge is its
    count at its own largest edge <= that edge — exact for the step
    function a cumulative histogram is. The merged list feeds
    :func:`window_p99` directly: cross-process quantiles reconstructed
    from per-process bucket state."""
    lists = [b for b in bucket_lists if b]
    finite = sorted({
        float(le) for b in lists for le, _ in b if not isinstance(le, str)
    })
    merged = []
    for le in finite:
        total = 0
        for b in lists:
            cum = 0
            for ble, bcum in b:
                if isinstance(ble, str) or float(ble) > le:
                    break
                cum = bcum
            total += cum
        merged.append([le, total])
    merged.append(["+Inf", sum(b[-1][1] for b in lists)] if lists
                  else ["+Inf", 0])
    return merged


# -- read side --------------------------------------------------------------
def get_counters() -> dict[str, int]:
    with _lock:
        return dict(_counters)


def get_gauges() -> dict[str, float]:
    with _lock:
        return dict(_gauges)


def get_histograms() -> dict[str, dict]:
    with _lock:
        return {k: h.to_dict() for k, h in _histograms.items()}


def get_tables() -> dict[str, dict]:
    with _lock:
        return dict(_tables)


def reset() -> None:
    with _lock:
        _counters.clear()
        _gauges.clear()
        _histograms.clear()
        _tables.clear()
