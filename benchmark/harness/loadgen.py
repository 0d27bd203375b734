"""Load generators: a closed loop of waiting clients, and an open loop
that sends on a schedule.

`submit(payload)` returns a future with `.result(timeout)`; payloads come
from `make_payload(rng)` with one seeded `numpy` generator per client, so
the same seed offers the same requests. Latencies are seconds.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time

import numpy as np


@dataclasses.dataclass
class LoadResult:
    latencies: list        # seconds, of the requests that completed
    attempted: int
    failed: int
    t_start: float         # perf_counter at the window's first instant
    t_end: float           # perf_counter when the last reply arrived
    errors: list
    lateness: list = dataclasses.field(default_factory=list)

    @property
    def window_s(self):
        return self.t_end - self.t_start

    @property
    def completed(self):
        return len(self.latencies)


def _annotator():
    """The profiler's host annotation where JAX is there, else nothing
    (the generators themselves need no JAX). Resolved before a window
    opens: the first import of JAX takes seconds."""
    try:
        import jax

        return jax.profiler.TraceAnnotation
    except ImportError:
        return lambda _name: contextlib.nullcontext()


def closed_loop(submit, make_payload, n_clients, seconds, seed,
                timeout_s=120.0):
    """`n_clients` threads, each submit -> wait -> repeat. A client sends
    nothing new once `seconds` have passed; the window ends when the last
    outstanding reply has arrived, so every request sent is answered
    inside it."""
    annotate = _annotator()
    lock = threading.Lock()
    lats, errors = [], []
    attempted = [0]
    t_start = time.perf_counter()
    stop = t_start + seconds

    def client(idx):
        rng = np.random.RandomState([seed, idx])
        while time.perf_counter() < stop:
            payload = make_payload(rng)
            t0 = time.perf_counter()
            with lock:
                attempted[0] += 1
            try:
                with annotate("bench.submit"):
                    fut = submit(payload)
                with annotate("bench.wait_result"):
                    fut.result(timeout=timeout_s)
            except Exception as exc:  # boundary: counted as failed
                with lock:
                    errors.append(f"{type(exc).__name__}: {exc}"[:300])
                continue
            dt = time.perf_counter() - t0
            with lock:
                lats.append(dt)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=seconds + 2 * timeout_s)
    t_end = time.perf_counter()
    hung = sum(t.is_alive() for t in threads)
    if hung:
        errors.append(f"{hung} client thread(s) did not return")
    return LoadResult(lats, attempted[0], len(errors), t_start, t_end, errors)


def open_loop(submit, make_payload, rate_per_s, seconds, seed,
              timeout_s=120.0):
    """Poisson arrivals at `rate_per_s` from one sender thread, whatever
    the system does. A request's latency runs from the instant it was DUE
    to the instant its future resolved (stamped by a done-callback), so a
    stalled sender charges the stall to the requests it delayed;
    `lateness` holds, per request, how long after its due time it was
    really sent."""
    annotate = _annotator()
    rng = np.random.RandomState([seed, 0])
    lock = threading.Lock()
    lats, errors, lateness, futures = [], [], [], []
    t_start = time.perf_counter()
    stop = t_start + seconds
    due = t_start
    while True:
        due += rng.exponential(1.0 / rate_per_s)
        if due >= stop:
            break
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        payload = make_payload(rng)
        lateness.append(max(0.0, time.perf_counter() - due))
        try:
            with annotate("bench.submit"):
                fut = submit(payload)
        except Exception as exc:  # boundary: a refused request failed
            errors.append(f"{type(exc).__name__}: {exc}"[:300])
            futures.append(None)
            continue

        def _done(f, due=due):
            now = time.perf_counter()
            with lock:
                if f.exception() is None:
                    lats.append(now - due)
                else:
                    errors.append(repr(f.exception())[:300])

        fut.add_done_callback(_done)
        futures.append(fut)
    t_end = time.perf_counter()
    for fut in futures:
        if fut is None:
            continue
        try:
            fut.result(timeout=timeout_s)
        except Exception:  # counted by the callback, or a timeout:
            if not fut.done():
                with lock:
                    errors.append("timed out")
        t_end = max(t_end, time.perf_counter())
    return LoadResult(lats, len(futures), len(errors), t_start, t_end,
                      errors, lateness)
