"""Request router with continuous/dynamic batching over bucketed shapes.

One request = one sample (feed arrays WITHOUT the leading batch axis).
Requests are admitted into per-endpoint, per-priority-class queues; a
scheduler thread forms batches continuously: it waits until either enough
requests queue to fill the largest bucket or the OLDEST queued request
hits the max-wait deadline, then pads the batch up to the nearest
configured bucket and runs it as ONE program dispatch. Because every
batch lands on a bucket shape with the endpoint's exact fetch set, the
executor's per-(program, feed-shapes, fetch-set) executable LRU serves
every request after warmup with zero compiles — the serving analogue of
the PR-6 "one wide program" argument (arXiv:2301.13062: many small
per-request programs lose badly to one bucketed one).

Fault domain (r15) — the serving-side analog of the training stack's
elastic-restart/rollback story:

* **Deadline propagation** — ``submit(..., deadline_ms=)`` stamps the
  request with an absolute expiry. The scheduler drops already-expired
  requests BEFORE batch formation (their futures resolve with the typed
  ``errors.DeadlineExceededError``; ``serving.expired`` counters), and
  the batch-former's fill wait is clamped to the tightest surviving
  deadline, so a queued request is dispatched before it would expire and
  stale work never pads a bucket or burns a dispatch.
* **Priority classes + load shedding** — requests carry a priority class
  (``INTERACTIVE`` < ``BATCH`` < ``BACKGROUND``; lower value = more
  important). Batches form in strict priority order (FIFO within a
  class). When the queue is full, an arriving request evicts the
  youngest request of a strictly LOWER class instead of being rejected —
  the victim's future resolves with ``errors.RequestShedError``
  (``serving.shed`` counters) — and only when nothing lower-class is
  queued does the arrival itself get rejected (``serving.rejected``, the
  r8 behavior).
* **Brownout** — :meth:`Endpoint.apply_brownout` installs graceful-
  degradation knobs the :class:`serving.brownout.BrownoutController`
  ladder drives from watcher findings: a ``wait_scale`` shrinking the
  effective max-wait, a ``bucket_frac`` capping the bucket set (smaller
  batches dispatch sooner), and a ``shed_priority`` refusing whole
  priority classes at admission.
* **Goodput** — completions are split into ``serving.goodput``
  (resolved within their deadline; deadline-less requests count) vs
  ``serving.late_completions``, so "QPS" under overload means work
  somebody was still waiting for.

Replica failover lives in :mod:`serving.replica` — a
:class:`ReplicaSet` is just a runner, so an endpoint fronts N frozen
replicas with per-replica circuit breakers without the router changing.

Lifecycle: ``Server.drain()`` stops admission, flushes every in-flight
batch (expired requests still resolve with their typed error — a drain
never hangs on dead work), and stops the scheduler threads; the
remaining drain budget is PRO-RATED across endpoints so ``drain(t)``
takes ~t, not endpoints*t. :func:`install_preemption_handler` rides the
PR-3 SIGTERM/exit-75 contract.

Observability (PR-1 registry): ``serving.requests`` / ``.rejected`` /
``.requests_served`` / ``.request_errors`` / ``.expired`` / ``.shed`` /
``.goodput`` / ``.late_completions`` counters (+ per-endpoint and
per-class variants), ``serving.queue_depth`` / ``.brownout_level``
gauges, ``serving.batches`` counter, ``serving.batch_fill`` +
``serving.padding_waste`` histograms, ``serving.request_latency`` +
``serving.batch_latency`` histograms, ``serving.drained`` counter.

Fault seams: request ingestion passes ``fault_point("serving.ingest")``
under a retry policy; batch dispatch passes
``fault_point("serving.dispatch")`` (in :class:`ReplicaSet` the seam
fires per replica attempt under its breaker/timeout machinery; on a
plain endpoint a raising kind fails the batch with its typed error and a
``hang`` wedges the scheduler — the failure mode ReplicaSet exists to
bound).
"""

from __future__ import annotations

import contextlib
import itertools
import math
import queue as _queue
import threading
import time
from collections import deque
from concurrent.futures import Future

import numpy as np

from ..errors import (
    DeadlineExceededError,
    InvalidArgumentError,
    PreconditionNotMetError,
    RequestShedError,
)

# batch-fill / padding-waste are ratios in [0, 1]; latency histograms use
# the registry's default latency edges
_RATIO_BUCKETS = (0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0)

# the batch-former wakes this far BEFORE the tightest queued deadline:
# waking exactly AT it would find the request already expired and drop
# work that one early dispatch would have served in-budget
_DEADLINE_MARGIN_S = 0.002

# priority classes: lower value = more important. Any non-negative int is
# accepted (the ladder sheds ">= shed_priority"), these three are the
# named contract.
INTERACTIVE = 0
BATCH = 1
BACKGROUND = 2

PRIORITY_NAMES = {INTERACTIVE: "interactive", BATCH: "batch",
                  BACKGROUND: "background"}

_RID = itertools.count(1)


def class_name(priority):
    """Metric label for a priority class (named, else the raw int)."""
    return PRIORITY_NAMES.get(priority, str(int(priority)))


class ServerDrainingError(PreconditionNotMetError):
    """Admission refused: the server is draining (SIGTERM) or stopped."""


class EndpointConfig:
    """Batching knobs for one endpoint.

    * ``buckets`` — allowed batch sizes, ascending; a formed batch pads up
      to the smallest bucket that fits (largest bucket caps batch size).
    * ``max_wait_ms`` — how long the OLDEST queued request may wait for
      co-batching before the scheduler dispatches a partial batch.
    * ``max_queue`` — admission bound; beyond it submits first try to
      evict a lower-priority queued request (``serving.shed``) and only
      then reject (``serving.rejected``), so an overloaded server
      degrades by shedding the least important work first.
    """

    def __init__(self, buckets=(1, 2, 4, 8), max_wait_ms=5.0,
                 max_queue=1024):
        sizes = sorted(int(b) for b in buckets)
        if not sizes or sizes[0] <= 0:
            raise InvalidArgumentError(
                f"endpoint buckets must be positive, got {sizes}"
            )
        self.buckets = tuple(sizes)
        self.max_wait = float(max_wait_ms) / 1000.0
        self.max_queue = int(max_queue)


class _Request:
    __slots__ = ("feeds", "future", "t_enqueue", "ctx", "deadline",
                 "priority", "rid")

    def __init__(self, feeds, deadline_s=None, priority=INTERACTIVE):
        self.feeds = feeds
        self.future = Future()
        self.t_enqueue = time.perf_counter()
        # absolute expiry on the same clock as t_enqueue; None = patient
        self.deadline = (
            None if deadline_s is None else self.t_enqueue + deadline_s
        )
        self.priority = int(priority)
        # idempotency token for failover: a ReplicaSet re-routes a failed
        # batch's requests to a healthy replica EXACTLY once, keyed on
        # these ids
        self.rid = next(_RID)
        # TraceContext parenting this request's scheduler-side spans
        # (queue wait, dispatch) under its ingest span — the explicit
        # capture/activate handoff across the scheduler thread boundary
        self.ctx = None


class FrozenRunner:
    """Default runner: a FrozenModel executed through an Executor/Scope.

    Feed variables must be declared batch-leading (shape[0] == -1 or the
    sample rank excludes the batch axis); fetches must be per-sample
    tensors with the batch leading, the same contract as
    ``AnalysisConfig.set_batch_buckets``.
    """

    def __init__(self, frozen, executor=None, scope=None):
        from ..framework.executor import Executor
        from ..framework.scope import global_scope

        self.frozen = frozen
        self.executor = executor or Executor()
        self.scope = scope or global_scope()
        self.feed_names = tuple(frozen.feed_names)
        self.fetch_names = tuple(frozen.fetch_names)
        self._sample_specs = {}
        blk = frozen.program.global_block
        for n in self.feed_names:
            v = blk.var(n)
            shape = tuple(v.shape or ())
            if not shape or shape[0] not in (-1, None):
                raise InvalidArgumentError(
                    f"serving feed {n!r} must be declared batch-leading "
                    f"(-1 first dim), got {shape}"
                )
            self._sample_specs[n] = (tuple(shape[1:]), v.dtype)

    def sample_spec(self, name):
        """(per-sample shape, dtype) for feed `name`."""
        return self._sample_specs[name]

    def run(self, feed):
        """Run one padded bucket batch; returns batch-leading outputs."""
        return self.executor.run(
            self.frozen.program, feed=feed,
            fetch_list=list(self.fetch_names), scope=self.scope,
        )


class Endpoint:
    """One servable model: queue + scheduler thread + bucketed dispatch."""

    def __init__(self, name, runner, config=None):
        from .. import observability as _obs
        from ..resilience.retry import retry

        self.name = name
        self.runner = runner
        self.config = config or EndpointConfig()
        # runners with static shape constraints (e.g. the GPT generator's
        # compiled cache batch) veto incompatible bucket configs up front
        validate = getattr(runner, "validate_config", None)
        if validate is not None:
            validate(self.config)
        # per-priority-class FIFO deques; batches form in priority order
        self._queues: dict[int, deque] = {}
        # how many QUEUED requests carry a deadline: the expiry/clamp
        # helpers early-out on 0, so the deadline-less path (and any
        # deadline-less backlog) never pays per-wake full-queue scans
        self._deadline_count = 0
        self._cond = threading.Condition()
        # serializes runner.run between the scheduler thread and warmup():
        # stateful runners (the GPT generator's shared KV-cache scope)
        # must never see two interleaved dispatches
        self._run_lock = threading.Lock()
        self._draining = False
        self._stopped = False
        # brownout knobs (apply_brownout); read by admission + scheduler
        self._brownout_level = 0
        self._wait_scale = 1.0
        self._bucket_cap = None
        self._shed_priority = None
        self._obs = _obs
        self._ingest_retry = retry(
            max_attempts=3, base_delay=0.005, max_delay=0.1,
            name="serving.ingest",
        )
        # runners fronting N processes (ProcessReplicaSet) advertise
        # max_concurrency: that many dispatcher threads run batches in
        # parallel — serial dispatch would serialize N workers right
        # back into single-process throughput. The handoff queue is
        # maxsize=1 so the batch-former stages at most one batch ahead
        # (backpressure, not an unbounded buffer).
        self._concurrency = max(
            1, int(getattr(runner, "max_concurrency", 1) or 1)
        )
        self._dispatch_q = None
        self._dispatchers = []
        if self._concurrency > 1:
            self._dispatch_q = _queue.Queue(maxsize=1)
            for i in range(self._concurrency):
                t = threading.Thread(
                    target=self._dispatch_loop, daemon=True,
                    name=f"serving-{name}-d{i}",
                )
                t.start()
                self._dispatchers.append(t)
        self._thread = threading.Thread(
            target=self._schedule_loop, daemon=True,
            name=f"serving-{name}",
        )
        self._thread.start()

    # -- queue helpers (call with self._cond held) -------------------------
    def _qsize_locked(self):
        return sum(len(q) for q in self._queues.values())

    def _gauge_depth_locked(self):
        self._obs.set_gauge(
            f"serving.queue_depth.{self.name}", self._qsize_locked()
        )

    def _oldest_enqueue_locked(self):
        return min(q[0].t_enqueue for q in self._queues.values() if q)

    def _tightest_deadline_locked(self):
        """Smallest absolute deadline among queued requests, or None.
        O(queued-with-deadlines) with an O(1) all-patient early-out; the
        queue itself is bounded by ``max_queue``."""
        if not self._deadline_count:
            return None
        tight = None
        for q in self._queues.values():
            for r in q:
                if r.deadline is not None and (
                        tight is None or r.deadline < tight):
                    tight = r.deadline
        return tight

    def _drop_expired_locked(self, now=None):
        """Remove every queued request whose deadline has passed; the
        caller resolves them (with the cond lock RELEASED — a future's
        done-callbacks may re-enter submit)."""
        if not self._deadline_count:
            return []
        now = time.perf_counter() if now is None else now
        expired = []
        for p, q in self._queues.items():
            if any(r.deadline is not None and now > r.deadline for r in q):
                keep = deque()
                for r in q:
                    if r.deadline is not None and now > r.deadline:
                        expired.append(r)
                    else:
                        keep.append(r)
                self._queues[p] = keep
        if expired:
            self._deadline_count -= len(expired)
            self._gauge_depth_locked()
        return expired

    def _evict_lower_locked(self, priority):
        """Pop the YOUNGEST request of the LOWEST class strictly below
        `priority`'s importance (highest class value), or None."""
        victim_class = None
        for p, q in self._queues.items():
            if p > priority and q and (victim_class is None
                                       or p > victim_class):
                victim_class = p
        if victim_class is None:
            return None
        victim = self._queues[victim_class].pop()
        if victim.deadline is not None:
            self._deadline_count -= 1
        return victim

    def _pop_batch_locked(self, n):
        batch = []
        for p in sorted(self._queues):
            q = self._queues[p]
            while q and len(batch) < n:
                batch.append(q.popleft())
            if len(batch) >= n:
                break
        self._deadline_count -= sum(
            1 for r in batch if r.deadline is not None
        )
        return batch

    def _effective_buckets(self):
        cap = self._bucket_cap
        if cap is None:
            return self.config.buckets
        capped = tuple(b for b in self.config.buckets if b <= cap)
        return capped or (self.config.buckets[0],)

    # -- expiry / shed resolution (lock NOT held) --------------------------
    def _resolve_expired(self, expired):
        from ..observability import spans

        now = time.perf_counter()
        for r in expired:
            self._obs.add("serving.expired")
            self._obs.add(f"serving.expired.{self.name}")
            self._obs.add(f"serving.expired_class.{class_name(r.priority)}")
            spans.record(
                "serving.expired", now - r.t_enqueue, category="serving",
                ctx=r.ctx, args={"endpoint": self.name},
            )
            r.future.set_exception(DeadlineExceededError(
                f"request expired in {self.name!r} queue after "
                f"{now - r.t_enqueue:.3f}s (deadline "
                f"{r.deadline - r.t_enqueue:.3f}s); never dispatched"
            ))

    def _count_shed(self, req):
        self._obs.add("serving.shed")
        self._obs.add(f"serving.shed.{self.name}")
        self._obs.add(f"serving.shed_class.{class_name(req.priority)}")

    # -- brownout ----------------------------------------------------------
    def apply_brownout(self, level=0, wait_scale=1.0, bucket_frac=1.0,
                       shed_priority=None):
        """Install one rung of the brownout ladder: scale the effective
        max-wait, cap the bucket set to its lowest ``bucket_frac``
        fraction, and refuse admission for classes ``>= shed_priority``.
        ``apply_brownout()`` with no args restores full service."""
        if wait_scale <= 0 or not 0.0 < bucket_frac <= 1.0:
            raise InvalidArgumentError(
                f"brownout wants wait_scale > 0 and 0 < bucket_frac <= 1, "
                f"got {wait_scale}/{bucket_frac}"
            )
        buckets = self.config.buckets
        cap = (None if bucket_frac >= 1.0 else
               buckets[max(0, math.ceil(len(buckets) * bucket_frac) - 1)])
        with self._cond:
            self._brownout_level = int(level)
            self._wait_scale = float(wait_scale)
            self._bucket_cap = cap
            self._shed_priority = (
                None if shed_priority is None else int(shed_priority)
            )
            self._cond.notify_all()
        self._obs.set_gauge(
            f"serving.brownout_level.{self.name}", float(level)
        )

    @property
    def brownout_level(self):
        return self._brownout_level

    # -- admission ---------------------------------------------------------
    def submit(self, feeds, deadline_ms=None, priority=INTERACTIVE):
        """Admit one single-sample request; returns its Future.

        ``deadline_ms`` is the client's end-to-end latency budget: once it
        elapses the scheduler drops the request pre-dispatch and the
        future raises ``DeadlineExceededError``. ``priority`` is the
        request's class (``INTERACTIVE``/``BATCH``/``BACKGROUND`` or any
        non-negative int; lower = more important) — under pressure the
        lowest class sheds first (``RequestShedError``)."""
        if deadline_ms is not None and float(deadline_ms) <= 0:
            raise InvalidArgumentError(
                f"deadline_ms must be positive, got {deadline_ms}"
            )
        if int(priority) < 0:
            raise InvalidArgumentError(
                f"priority class must be >= 0, got {priority}"
            )
        deadline_s = None if deadline_ms is None else float(deadline_ms) / 1e3
        try:
            return self._ingest_retry.call(
                self._ingest, feeds, deadline_s, int(priority)
            )
        except ServerDrainingError:
            self._obs.add("serving.rejected")
            self._obs.add(f"serving.rejected.{self.name}")
            raise

    def _ingest(self, feeds, deadline_s, priority):
        from ..observability import trace
        from ..resilience.faults import fault_point

        # the chaos seam (dataloader.fetch analogue): an armed fault
        # raises HERE, before any state mutation, so the retry re-admits
        # the identical request with no double-enqueue hazard
        fault_point("serving.ingest")
        feeds = {
            n: np.asarray(feeds[n]) for n in self.runner.feed_names
        }
        req = _Request(feeds, deadline_s, priority)
        evicted = None
        # each request gets a causal trace: join the submitter's active
        # trace when there is one (the client's own span becomes the
        # root), else start a fresh one — either way the scheduler-side
        # spans parent under THIS ingest span via the request's context
        tr = trace.ensure()
        try:
            with trace.activate(tr), \
                    self._obs.span("serving.ingest", category="serving",
                                   endpoint=self.name) as ingest_span:
                with self._cond:
                    if self._draining or self._stopped:
                        raise ServerDrainingError(
                            f"endpoint {self.name!r} is draining; request "
                            "refused"
                        )
                    shed_at = self._shed_priority
                    if shed_at is not None and req.priority >= shed_at:
                        self._count_shed(req)
                        raise RequestShedError(
                            f"endpoint {self.name!r} browned out (level "
                            f"{self._brownout_level}): class "
                            f"{class_name(req.priority)!r} is shed"
                        )
                    if self._qsize_locked() >= self.config.max_queue:
                        evicted = self._evict_lower_locked(req.priority)
                        if evicted is None:
                            self._obs.add("serving.rejected")
                            self._obs.add(f"serving.rejected.{self.name}")
                            raise PreconditionNotMetError(
                                f"endpoint {self.name!r} queue full "
                                f"({self.config.max_queue}) with nothing "
                                "lower-priority to shed; back off or add "
                                "capacity"
                            )
                    if tr is not None and ingest_span.span_id is not None:
                        req.ctx = tr.child(ingest_span.span_id)
                    self._queues.setdefault(req.priority, deque()).append(
                        req
                    )
                    if req.deadline is not None:
                        self._deadline_count += 1
                    self._gauge_depth_locked()
                    self._cond.notify_all()
        finally:
            # resolve the victim with the cond lock released: future
            # done-callbacks run inline and may re-enter submit
            if evicted is not None:
                self._count_shed(evicted)
                evicted.future.set_exception(RequestShedError(
                    f"request shed from {self.name!r}: queue full and a "
                    f"class-{class_name(priority)!r} admission outranked "
                    f"class {class_name(evicted.priority)!r}"
                ))
        self._obs.add("serving.requests")
        self._obs.add(f"serving.requests.{self.name}")
        return req.future

    # -- scheduling --------------------------------------------------------
    def _schedule_loop(self):
        while True:
            expired = []
            batch = None
            # live on the scheduler thread from the moment it starts
            # waiting for work to the moment the batch is popped: a device
            # idle for want of requests has this owner in a profiler
            # capture (batch_size 0: the wait ended with nothing to run)
            forming = self._obs.span(
                "serving.form_batch", category="serving",
                endpoint=self.name, batch_size=0,
            )
            with forming, self._cond:
                while not self._qsize_locked() and not self._stopped:
                    self._cond.wait(0.05)
                    # a capture that began during the wait sees its owner
                    # from here on (the idle at a traced window's start)
                    forming.refresh()
                if self._stopped and not self._qsize_locked():
                    break
                # already-expired requests leave BEFORE batch formation:
                # late work never pads a bucket or burns a dispatch
                expired.extend(self._drop_expired_locked())
                if self._qsize_locked():
                    max_bucket = self._effective_buckets()[-1]
                    # continuous batching: admit late arrivals until the
                    # largest bucket fills, the oldest request's max-wait
                    # expires, or the TIGHTEST surviving deadline is
                    # reached (draining flushes immediately)
                    while (self._qsize_locked() < max_bucket
                           and not self._draining and not self._stopped):
                        wait_deadline = (
                            self._oldest_enqueue_locked()
                            + self.config.max_wait * self._wait_scale
                        )
                        tight = self._tightest_deadline_locked()
                        if tight is not None:
                            wait_deadline = min(
                                wait_deadline, tight - _DEADLINE_MARGIN_S
                            )
                        remaining = wait_deadline - time.perf_counter()
                        if remaining <= 0:
                            break
                        self._cond.wait(remaining)
                        expired.extend(self._drop_expired_locked())
                        if not self._qsize_locked():
                            break
                        max_bucket = self._effective_buckets()[-1]
                    batch = self._pop_batch_locked(
                        min(self._qsize_locked(), max_bucket)
                    )
                    forming.args["batch_size"] = len(batch)
                    # the bucket is chosen under the SAME lock hold that
                    # formed the batch: a concurrent brownout bucket-cap
                    # change must not shrink the target below the batch
                    # already popped
                    bucket = (
                        self._bucket_for_locked(len(batch)) if batch
                        else None
                    )
                    self._gauge_depth_locked()
            self._resolve_expired(expired)
            if batch:
                if self._dispatch_q is not None:
                    self._dispatch_q.put((batch, bucket))
                else:
                    self._run_batch(batch, bucket)
        # drain path: every staged batch runs before the scheduler
        # thread exits — Server.drain joins THIS thread, so "drained"
        # still means every admitted request resolved
        if self._dispatch_q is not None:
            for _ in self._dispatchers:
                self._dispatch_q.put(None)
            for t in self._dispatchers:
                t.join()

    def _dispatch_loop(self):
        """One dispatcher: runs staged batches until the sentinel."""
        while True:
            item = self._dispatch_q.get()
            if item is None:
                return
            self._run_batch(*item)

    def _bucket_for_locked(self, n):
        buckets = self._effective_buckets()
        for b in buckets:
            if b >= n:
                return b
        return buckets[-1]

    def _run_batch(self, batch, bucket):
        from ..observability import spans, trace
        from ..resilience.faults import fault_point

        t0 = time.perf_counter()
        n = len(batch)
        first = batch[0].ctx
        try:
            # the hand-over INTO the batch has an owner of its own (a
            # device idle here waits for the host to record and stack):
            # live, so it is in a profiler capture, under the first
            # request's trace as `serving.batch` is
            with trace.activate(first), \
                    self._obs.span("serving.assemble", category="serving",
                                   endpoint=self.name, batch_size=n):
                # queue wait ends the moment the batch forms: recorded
                # per request under ITS trace (the capture/activate
                # handoff — this runs on the scheduler thread, the
                # context was captured at ingest), so "where did this
                # request's latency go" splits into queue-wait vs
                # dispatch from the trace alone
                for r in batch:
                    spans.record(
                        "serving.queue_wait", t0 - r.t_enqueue,
                        category="serving", ctx=r.ctx,
                        args={"endpoint": self.name, "batch_size": n},
                    )
                feed = {}
                for name in self.runner.feed_names:
                    rows = np.stack([r.feeds[name] for r in batch])
                    if n < bucket:
                        pad = np.zeros(
                            (bucket - n,) + rows.shape[1:], rows.dtype
                        )
                        rows = np.concatenate([rows, pad], axis=0)
                    feed[name] = rows
            # concurrent dispatchers skip the run lock: a runner that
            # declared max_concurrency > 1 (the process fleet) is
            # thread-safe by contract, and serializing here would undo it
            guard = (
                contextlib.nullcontext() if self._concurrency > 1
                else self._run_lock
            )
            with guard:
                # the live dispatch span (and everything the runner
                # records inside: executor.step, GPT prefill/decode)
                # files under the FIRST request's trace; the other
                # requests get their dispatch share recorded
                # retrospectively below, so every trace is complete
                with trace.activate(first), \
                        self._obs.span("serving.batch", category="serving",
                                       endpoint=self.name, bucket=bucket,
                                       batch_size=n):
                    if getattr(self.runner, "wants_request_ids", False):
                        # failover runners (ReplicaSet) key exactly-once
                        # re-routing on the request ids; they own the
                        # serving.dispatch fault seam per replica attempt
                        outs = self.runner.run(
                            feed, request_ids=[r.rid for r in batch]
                        )
                    else:
                        fault_point("serving.dispatch")
                        outs = self.runner.run(feed)
                    outs = [np.asarray(o) for o in outs]
        except Exception as exc:
            self._obs.add("serving.request_errors", n)
            for r in batch:
                r.future.set_exception(exc)
            return
        # the hand-over OUT of the batch: the records, the histograms and
        # the futures (each `set_result` wakes a client thread, which in a
        # closed loop submits again under the GIL): `resolve_ms` is the
        # time inside `set_result` alone
        completing = self._obs.span(
            "serving.complete", category="serving", endpoint=self.name,
            batch_size=n, resolve_ms=0.0,
        )
        with trace.activate(first), completing:
            completing.args["resolve_ms"] = self._complete(
                batch, bucket, outs, t0
            )

    def _complete(self, batch, bucket, outs, t0):
        """Record a finished batch and resolve its futures; returns the
        milliseconds spent inside `set_result`."""
        from ..observability import spans

        n = len(batch)
        dt = time.perf_counter() - t0
        now = time.perf_counter()
        for r in batch:
            spans.record(
                "serving.dispatch", now - t0, category="serving",
                ctx=r.ctx,
                args={"endpoint": self.name, "bucket": bucket,
                      "batch_size": n},
            )
        self._obs.add("serving.batches")
        self._obs.add(f"serving.bucket_runs.{self.name}.{bucket}")
        self._obs.observe("serving.batch_latency", dt)
        self._obs.observe(
            "serving.batch_fill", n / bucket, buckets=_RATIO_BUCKETS
        )
        self._obs.observe(
            "serving.padding_waste", (bucket - n) / bucket,
            buckets=_RATIO_BUCKETS,
        )
        goodput = late = 0
        resolving = 0.0
        # the per-endpoint histogram is what a brownout watcher reads
        own_latency = f"serving.request_latency.{self.name}"
        for i, r in enumerate(batch):
            t_set = time.perf_counter()
            r.future.set_result([o[i] for o in outs])
            resolving += time.perf_counter() - t_set
            lat = now - r.t_enqueue
            if r.deadline is None or now <= r.deadline:
                goodput += 1
            else:
                late += 1
            self._obs.observe("serving.request_latency", lat)
            self._obs.observe(own_latency, lat)
        self._obs.add("serving.requests_served", n)
        # goodput = completions somebody was still waiting for: the
        # in-deadline share (deadline-less requests count — their client
        # is patient by declaration)
        if goodput:
            self._obs.add("serving.goodput", goodput)
            self._obs.add(f"serving.goodput.{self.name}", goodput)
        if late:
            self._obs.add("serving.late_completions", late)
            self._obs.add(f"serving.late_completions.{self.name}", late)
        return 1e3 * resolving

    # -- warmup ------------------------------------------------------------
    def warmup(self):
        """Compile the EXACT (bucket-shape, fetch-set) executables serving
        will dispatch: one zero-feed run per bucket through the same
        ``runner.run`` entry the scheduler uses. The executor's executable
        cache (and its flops/estimate digests) key on the fetch set, so a
        warmup with a different fetch list — or a different batch shape —
        would leave every real bucket cold and push the first compile into
        a user-visible request latency (the PR-6 bench warmup lesson).
        A ReplicaSet exposes ``warmup_run``, which warms EVERY replica —
        a cold standby would otherwise pay its compiles during a
        failover, exactly when latency matters most.

        When ``PADDLE_TPU_HBM_BYTES`` is set and the runner exposes its
        frozen program, the static HBM plan for every (bucket, fetch-set)
        executable is validated FIRST — resident state once plus the
        worst bucket's transient peak times the runner's concurrency must
        fit the budget, or warmup refuses with a typed error *before*
        compiling anything (the concurrency-planning math the paged KV
        cache consumes)."""
        from ..core.dtypes import to_numpy_dtype

        self.plan_memory()
        run = getattr(self.runner, "warmup_run", None) or self.runner.run
        for b in self.config.buckets:
            feed = {}
            for name in self.runner.feed_names:
                shape, dtype = self.runner.sample_spec(name)
                feed[name] = np.zeros((b,) + shape, to_numpy_dtype(dtype))
            with self._run_lock:
                run(feed)
            self._obs.add("serving.warmup_runs")
        return len(self.config.buckets)

    def plan_memory(self, budget=None):
        """Static per-bucket HBM plan for this endpoint: resident bytes
        once + max-over-buckets (feeds + transient peak) × concurrency.
        Returns the plan dict (None when the runner exposes no program),
        publishes ``serving.warmup_peak_bytes.<endpoint>``, and raises
        :class:`~paddle_tpu.errors.PreconditionNotMetError` when a budget
        (argument, else ``PADDLE_TPU_HBM_BYTES``) is exceeded."""
        from ..analysis.memory import (
            _fmt_bytes, hbm_budget, plan_memory,
        )

        frozen = getattr(self.runner, "frozen", None)
        program = getattr(frozen, "program", None)
        if program is None:
            return None
        if budget is None:
            budget = hbm_budget()
        fetch_names = tuple(getattr(self.runner, "fetch_names", ()) or ())
        feed_names = tuple(getattr(self.runner, "feed_names", ()) or ())
        resident = 0.0
        per_bucket = {}
        worst = 0.0
        for b in self.config.buckets:
            feed_shapes = {}
            for name in feed_names:
                shape, _dtype = self.runner.sample_spec(name)
                feed_shapes[name] = (b,) + tuple(shape)
            mt = plan_memory(
                program, feed_names=feed_names, fetch_names=fetch_names,
                feed_shapes=feed_shapes, budget=None,
            )
            resident = max(resident, mt.resident_bytes)
            dynamic = mt.feed_bytes + mt.transient_peak_bytes
            per_bucket[b] = dynamic
            worst = max(worst, dynamic)
        planned = resident + worst * self._concurrency
        self._obs.set_gauge(
            f"serving.warmup_peak_bytes.{self.name}", planned
        )
        plan = {
            "resident_bytes": resident,
            "per_bucket_dynamic_bytes": per_bucket,
            "concurrency": self._concurrency,
            "planned_peak_bytes": planned,
            "budget_bytes": budget,
        }
        if budget is not None and planned > budget:
            from ..errors import PreconditionNotMetError

            raise PreconditionNotMetError(
                f"endpoint {self.name!r} cannot fit the HBM budget: "
                f"resident {_fmt_bytes(resident)} + worst bucket "
                f"{_fmt_bytes(worst)} x concurrency {self._concurrency} "
                f"= {_fmt_bytes(planned)} > "
                f"{_fmt_bytes(budget)} (PADDLE_TPU_HBM_BYTES); shrink "
                "the buckets, the cache, or the replica concurrency"
            )
        return plan

    # -- lifecycle ---------------------------------------------------------
    def pending(self):
        with self._cond:
            return self._qsize_locked()

    def drain(self, timeout=None):
        """Stop admitting, flush the queue through the scheduler, stop the
        thread. Returns True when everything in flight completed (expired
        requests resolve with their typed error during the flush — dead
        work cannot hang a drain)."""
        with self._cond:
            self._draining = True
            self._stopped = True
            self._cond.notify_all()
        self._thread.join(timeout)
        return not self._thread.is_alive() and not self.pending()


class Server:
    """A set of endpoints behind one admission/drain lifecycle."""

    def __init__(self):
        self._endpoints = {}
        self._draining = False
        self._drained = threading.Event()
        self._lock = threading.Lock()

    def add_endpoint(self, name, runner, config=None, frozen=None,
                     executor=None, scope=None):
        """Register (and start) an endpoint. Pass a ``runner`` with the
        FrozenRunner interface, or ``frozen=`` to wrap a FrozenModel."""
        if frozen is not None:
            runner = FrozenRunner(frozen, executor=executor, scope=scope)
        if runner is None:
            raise InvalidArgumentError(
                "add_endpoint needs runner= or frozen="
            )
        with self._lock:
            if self._draining:
                raise ServerDrainingError("server is draining")
            if name in self._endpoints:
                raise InvalidArgumentError(
                    f"endpoint {name!r} already registered"
                )
            ep = Endpoint(name, runner, config)
            self._endpoints[name] = ep
        return ep

    def __getitem__(self, name):
        return self._endpoints[name]

    def endpoints(self):
        return dict(self._endpoints)

    def submit(self, endpoint, feeds, deadline_ms=None,
               priority=INTERACTIVE):
        if self._draining:
            from .. import observability as _obs

            _obs.add("serving.rejected")
            raise ServerDrainingError("server is draining")
        return self._endpoints[endpoint].submit(
            feeds, deadline_ms=deadline_ms, priority=priority
        )

    def warmup(self):
        """Warm every endpoint's bucket executables; returns total runs."""
        return sum(ep.warmup() for ep in self._endpoints.values())

    @property
    def draining(self):
        return self._draining

    def drain(self, timeout=None):
        """Graceful shutdown: stop admission, complete every admitted
        request, stop scheduler threads, then bump ``serving.drained``.
        Idempotent; returns True when fully drained. The budget is
        pro-rated: `timeout` bounds the WHOLE drain — each endpoint gets
        the remaining slice, not a fresh full timeout (the r8 bug: N
        endpoints with one wedged dispatch each could stall a SIGTERM
        for N*timeout)."""
        from .. import observability as _obs

        with self._lock:
            first = not self._draining
            self._draining = True
            eps = list(self._endpoints.values())
        deadline = None if timeout is None else time.monotonic() + timeout
        ok = True
        for ep in eps:
            remaining = (
                None if deadline is None
                else max(0.0, deadline - time.monotonic())
            )
            ok = ep.drain(remaining) and ok
        if first:
            _obs.add("serving.drained")
            _obs.set_gauge("serving.draining", 1.0)
            from ..observability import recorder as _recorder

            # flight-recorder trigger: a drain usually precedes exit(75)
            # — capture the serving window while the process still can
            _recorder.flight_dump("serving_drain", detail={
                "endpoints": [ep.name for ep in eps], "clean": ok,
            })
        if ok:
            self._drained.set()
        return ok

    def wait_drained(self, timeout=None):
        return self._drained.wait(timeout)

    def close(self, timeout=None):
        """Drain, then release runner-held resources: every runner
        exposing ``close`` (the process fleet's worker pod) is torn
        down. Zero orphan worker processes after this call is the
        contract the fleet-chaos CI stage asserts."""
        from .. import observability as _obs

        ok = self.drain(timeout)
        for ep in self._endpoints.values():
            close = getattr(ep.runner, "close", None)
            if close is not None:
                close()
        _obs.add("serving.server_closes")
        return ok


def install_preemption_handler(server, exit_on_drain=True, timeout=None):
    """SIGTERM -> drain -> exit ``PREEMPTION_EXIT_CODE`` (75), riding the
    PR-3 preemption contract: the launcher treats 75 as a clean preempt
    (no restart-budget burn). The signal handler only spawns the drain
    thread (handlers must stay tiny); with ``exit_on_drain=False`` the
    caller observes ``server.wait_drained()`` instead — the in-process
    test shape."""
    import os
    import signal

    from ..resilience.health import PREEMPTION_EXIT_CODE

    def _drain_then_exit():
        server.drain(timeout)
        if exit_on_drain:
            # handlers/threads cannot sys.exit the main thread; preemption
            # wants no further cleanup anyway (checkpointless server)
            os._exit(PREEMPTION_EXIT_CODE)

    def _on_sigterm(signum, frame):
        threading.Thread(
            target=_drain_then_exit, daemon=True,
            name="serving-drain",
        ).start()

    old = signal.getsignal(signal.SIGTERM)
    signal.signal(signal.SIGTERM, _on_sigterm)
    return old
