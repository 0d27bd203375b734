"""Serving fault domain tests (r15): deadline propagation, priority load
shedding + brownout ladder, circuit-broken replica failover, and the
pro-rated Server.drain budget.

Everything here runs on executor-free stub runners (the queue/batcher/
breaker machinery without XLA in the loop) so the suite stays fast; the
end-to-end frozen-graph legs live in bench_serving.py's ``overload`` and
``failover`` mixes, gated by ci.sh's serving-chaos stage."""

import os
import time

import numpy as np
import pytest

from paddle_tpu import errors, observability
from paddle_tpu.errors import (
    DeadlineExceededError,
    InvalidArgumentError,
    PreconditionNotMetError,
    RequestShedError,
)
from paddle_tpu.resilience import faults
from paddle_tpu.serving import Server
from paddle_tpu.serving.brownout import DEFAULT_LADDER, BrownoutController
from paddle_tpu.serving.replica import ReplicaSet
from paddle_tpu.serving.router import (
    BACKGROUND,
    BATCH,
    INTERACTIVE,
    Endpoint,
    EndpointConfig,
)


class _StubRunner:
    """Executor-free runner: doubles its input; optional per-batch delay
    and forced failure. Records the first feed column of every batch so
    tests can assert WHAT was dispatched, not just how much."""

    feed_names = ("x",)

    def __init__(self, delay=0.0, name="stub"):
        self.delay = delay
        self.name = name
        self.fail_with = None
        self.batches = []  # list of row-0 values per dispatched batch

    def sample_spec(self, name):
        return (2,), "float32"

    def run(self, feed):
        if self.fail_with is not None:
            raise self.fail_with
        if self.delay:
            time.sleep(self.delay)
        self.batches.append([float(row[0]) for row in feed["x"]])
        return [feed["x"] * 2.0]


def _feed(v=0.0):
    """One SAMPLE (no batch axis) — the Endpoint.submit shape."""
    return {"x": np.full(2, v, np.float32)}


def _bfeed(v=0.0, n=1):
    """One BATCH (batch-leading) — the shape runners/ReplicaSet.run see."""
    return {"x": np.full((n, 2), v, np.float32)}


def _counter(name):
    return observability.get_counters().get(name, 0)


# ---------------------------------------------------------------------------
# deadline propagation
# ---------------------------------------------------------------------------


def test_expired_request_resolves_typed_and_never_dispatches():
    runner = _StubRunner(delay=0.15)
    ep = Endpoint("exp", runner, EndpointConfig(buckets=(1,),
                                                max_wait_ms=0.0))
    c0 = _counter("serving.expired")
    blocker = ep.submit(_feed(1.0))  # occupies the runner
    doomed = ep.submit(_feed(2.0), deadline_ms=30)
    with pytest.raises(DeadlineExceededError):
        doomed.result(timeout=5)
    blocker.result(timeout=5)
    assert ep.drain(timeout=5)
    assert _counter("serving.expired") == c0 + 1
    assert _counter("serving.expired.exp") == 1
    # the expired request never padded a bucket or burned a dispatch
    assert [1.0] in runner.batches and all(
        2.0 not in b for b in runner.batches
    ), runner.batches


def test_expired_requests_never_pad_the_surviving_batch():
    """Bucket formation after an expiry wave carries ONLY live work."""
    runner = _StubRunner(delay=0.12)
    ep = Endpoint("pad", runner,
                  EndpointConfig(buckets=(4,), max_wait_ms=1.0))
    blocker = ep.submit(_feed(9.0))
    time.sleep(0.03)  # the blocker dispatches ALONE and occupies the runner
    doomed = [ep.submit(_feed(1.0), deadline_ms=25) for _ in range(2)]
    live = [ep.submit(_feed(5.0)) for _ in range(2)]
    time.sleep(0.05)  # both deadlines pass while the blocker runs
    for f in doomed:
        with pytest.raises(DeadlineExceededError):
            f.result(timeout=5)
    for f in live:
        np.testing.assert_array_equal(
            f.result(timeout=5)[0], np.full(2, 10.0)
        )
    blocker.result(timeout=5)
    ep.drain(timeout=5)
    # the survivors' batch is zero-PADDED to the bucket, never padded
    # with expired requests' rows
    assert [5.0, 5.0, 0.0, 0.0] in runner.batches, runner.batches
    assert all(1.0 not in b for b in runner.batches), runner.batches


def test_batch_former_wait_clamped_to_tightest_deadline():
    """A lonely request with a 500ms budget must not sit out the full
    5s max_wait waiting for bucket-8 co-batching. The budget is wide
    enough for a loaded machine (the suite runs six workers to a few
    cores): what is held is that the wait ends at the deadline, far
    below max_wait, not that the stub's dispatch also ENDS inside the
    budget (a completion counts, in time or late)."""
    runner = _StubRunner()
    ep = Endpoint("clamp", runner,
                  EndpointConfig(buckets=(8,), max_wait_ms=5000.0))
    for _attempt in range(3):
        t0 = time.perf_counter()
        fut = ep.submit(_feed(3.0), deadline_ms=500)
        try:
            out = fut.result(timeout=4)[0]
        except DeadlineExceededError:
            # the scheduler thread woke more than the 2 ms margin late:
            # the request expired AT its deadline, which is still the
            # clamped wait; ask again for one that is dispatched
            out = None
        waited = time.perf_counter() - t0
        assert waited < 2.5, \
            f"dispatch waited {waited:.3f}s past the deadline"
        if out is not None:
            break
    ep.drain(timeout=5)
    assert out is not None, "three lonely requests expired undispatched"
    np.testing.assert_array_equal(out, np.full(2, 6.0))
    assert (_counter("serving.goodput.clamp")
            + _counter("serving.late_completions.clamp")) >= 1


def test_goodput_vs_late_split():
    """A dispatch that outlives the deadline still resolves with its
    result, but counts as late, not goodput."""
    runner = _StubRunner(delay=0.08)
    ep = Endpoint("good", runner,
                  EndpointConfig(buckets=(1,), max_wait_ms=0.0))
    late = ep.submit(_feed(1.0), deadline_ms=20)  # expires mid-dispatch
    ok = ep.submit(_feed(2.0))
    late.result(timeout=5), ok.result(timeout=5)
    ep.drain(timeout=5)
    assert _counter("serving.late_completions.good") == 1
    assert _counter("serving.goodput.good") == 1


def test_submit_validation():
    ep = Endpoint("val", _StubRunner(), EndpointConfig(buckets=(1,)))
    try:
        with pytest.raises(InvalidArgumentError):
            ep.submit(_feed(), deadline_ms=0)
        with pytest.raises(InvalidArgumentError):
            ep.submit(_feed(), deadline_ms=-5)
        with pytest.raises(InvalidArgumentError):
            ep.submit(_feed(), priority=-1)
    finally:
        ep.drain(timeout=5)


# ---------------------------------------------------------------------------
# priority classes + shedding
# ---------------------------------------------------------------------------


def test_queue_pressure_sheds_lowest_class_first():
    runner = _StubRunner(delay=0.1)
    ep = Endpoint("shed", runner,
                  EndpointConfig(buckets=(1,), max_wait_ms=0.0,
                                 max_queue=2))
    blocker = ep.submit(_feed(9.0))
    time.sleep(0.02)  # scheduler takes the blocker; queue now empty
    bg_old = ep.submit(_feed(1.0), priority=BACKGROUND)
    bg_young = ep.submit(_feed(2.0), priority=BACKGROUND)
    hi = ep.submit(_feed(3.0), priority=INTERACTIVE)  # evicts bg_young
    with pytest.raises(RequestShedError):
        bg_young.result(timeout=5)
    np.testing.assert_array_equal(
        hi.result(timeout=10)[0], np.full(2, 6.0)
    )
    bg_old.result(timeout=10)
    blocker.result(timeout=5)
    ep.drain(timeout=10)
    assert _counter("serving.shed.shed") == 1
    assert _counter("serving.shed_class.background") == 1


def test_queue_full_same_class_still_rejects():
    runner = _StubRunner(delay=0.1)
    ep = Endpoint("rej", runner,
                  EndpointConfig(buckets=(1,), max_wait_ms=0.0,
                                 max_queue=1))
    blocker = ep.submit(_feed())
    time.sleep(0.02)
    filler = ep.submit(_feed(), priority=BATCH)
    c0 = _counter("serving.rejected")
    with pytest.raises(PreconditionNotMetError) as ei:
        ep.submit(_feed(), priority=BATCH)  # nothing lower-class queued
    assert not isinstance(ei.value, RequestShedError)
    assert _counter("serving.rejected") == c0 + 1
    blocker.result(timeout=5), filler.result(timeout=5)
    ep.drain(timeout=5)


def test_batches_form_in_priority_order():
    """An interactive arrival jumps ahead of earlier-queued background
    work at batch formation (FIFO within a class)."""
    runner = _StubRunner(delay=0.08)
    ep = Endpoint("prio", runner,
                  EndpointConfig(buckets=(2,), max_wait_ms=0.0))
    blocker = ep.submit(_feed(9.0))
    time.sleep(0.02)
    bg = [ep.submit(_feed(float(i)), priority=BACKGROUND)
          for i in (1, 2, 3)]
    hi = ep.submit(_feed(7.0), priority=INTERACTIVE)
    for f in bg + [hi, blocker]:
        f.result(timeout=10)
    ep.drain(timeout=10)
    # first post-blocker batch: the interactive request leads, then the
    # OLDEST background; the remaining background pair follows
    assert runner.batches[1] == [7.0, 1.0], runner.batches
    assert runner.batches[2] == [2.0, 3.0], runner.batches


# ---------------------------------------------------------------------------
# brownout ladder
# ---------------------------------------------------------------------------


def test_brownout_ladder_escalates_and_rearms():
    ep = Endpoint("bo", _StubRunner(),
                  EndpointConfig(buckets=(1, 2, 4, 8), max_wait_ms=40.0))
    server_like = {"bo": ep}

    class _S:
        def endpoints(self):
            return server_like

    ctl = BrownoutController(_S(), slo_p99_s=0.1, escalate_after=2,
                             recover_after=3)
    try:
        assert ctl.level == 0
        ctl.observe(p99=0.5)
        assert ctl.level == 0, "one breach observation must not escalate"
        ctl.observe(p99=0.5)
        assert ctl.level == 1 and ep._wait_scale == 0.5
        for _ in range(2):
            ctl.observe(p99=0.5)
        assert ctl.level == 2 and ep._shed_priority == BACKGROUND
        with pytest.raises(RequestShedError):
            ep.submit(_feed(), priority=BACKGROUND)
        assert _counter("serving.shed_class.background") >= 1
        # batch class still admitted at rung 2, shed at rung 3
        ep.submit(_feed(1.0), priority=BATCH).result(timeout=5)
        for _ in range(2):
            ctl.observe(p99=0.5)
        assert ctl.level == 3 and ep._shed_priority == BATCH
        with pytest.raises(RequestShedError):
            ep.submit(_feed(), priority=BATCH)
        assert ep._bucket_cap is None, (
            "capacity-reducing bucket cap must come AFTER shedding"
        )
        # rung 4 — the last-ditch bucket cap
        for _ in range(2):
            ctl.observe(p99=0.5)
        assert ctl.level == 4
        assert ep._wait_scale == 0.25
        assert ep._bucket_cap == 2  # lower half of (1, 2, 4, 8)
        assert ep._effective_buckets() == (1, 2)
        # interactive still admitted at the top rung
        ep.submit(_feed(1.0), priority=INTERACTIVE).result(timeout=5)
        # recovery walks the ladder back down with hysteresis
        for _ in range(2):
            ctl.observe(p99=0.01)
        assert ctl.level == 4, "recovery must be sustained, not one tick"
        for _ in range(16):
            ctl.observe(p99=0.01)
        assert ctl.level == 0
        assert ep._wait_scale == 1.0 and ep._bucket_cap is None
        ep.submit(_feed(2.0), priority=BACKGROUND).result(timeout=5)
        g = observability.get_gauges()
        assert g.get("serving.brownout_level") == 0.0
        assert g.get("serving.brownout_level.bo") == 0.0
        assert _counter("serving.brownout_escalations") == 4
        assert _counter("serving.brownout_recoveries") == 4
    finally:
        ep.drain(timeout=5)


def test_watcher_slo_breach_drives_brownout_both_directions():
    """The satellite contract: a REAL Watcher over the latency histogram
    latches slo_breach -> the controller escalates; recovery re-arms the
    watcher AND walks the controller back down."""
    from paddle_tpu.observability.watch import Watcher

    metric = "serving.request_latency.bo2"
    ep = Endpoint("bo2", _StubRunner(),
                  EndpointConfig(buckets=(1, 2), max_wait_ms=5.0))

    class _S:
        def endpoints(self):
            return {"bo2": ep}

    watcher = Watcher(latency_metric=metric, slo_p99_s=0.05)
    ctl = BrownoutController(_S(), slo_p99_s=0.05, watcher=watcher,
                             escalate_after=1, recover_after=2)
    try:
        # breach window: p99 ~ 0.25s >> 50ms SLO
        for _ in range(40):
            observability.observe(metric, 0.2)
        ctl.poll()
        assert watcher.breaching
        assert _counter("watch.findings.slo_breach") >= 1
        assert ctl.level >= 1
        level_after_breach = ctl.level
        # recovery windows: p99 ~ 1ms; the watcher re-arms its latch and
        # the gauge it maintains drives the controller back to 0
        for _ in range(8):
            for _ in range(40):
                observability.observe(metric, 0.001)
            ctl.poll()
        assert not watcher.breaching
        assert ctl.level == 0 < level_after_breach
        # a SECOND excursion latches a fresh finding (re-armed)
        for _ in range(40):
            observability.observe(metric, 0.2)
        ctl.poll()
        assert watcher.breaching and ctl.level >= 1
        assert _counter("watch.findings.slo_breach") >= 2
    finally:
        ep.drain(timeout=5)


def test_default_ladder_shape():
    assert DEFAULT_LADDER[0] == {"wait_scale": 1.0, "bucket_frac": 1.0,
                                 "shed_priority": None}
    # shedding (demand reduction) strictly precedes the bucket cap
    # (capacity reduction): the first capped rung must already shed
    first_capped = next(
        r for r in DEFAULT_LADDER if r["bucket_frac"] < 1.0
    )
    assert first_capped["shed_priority"] is not None
    assert DEFAULT_LADDER[2]["shed_priority"] == BACKGROUND
    assert DEFAULT_LADDER[-1]["shed_priority"] == BATCH
    with pytest.raises(InvalidArgumentError):
        BrownoutController(object(), ladder=(DEFAULT_LADDER[0],))


# ---------------------------------------------------------------------------
# replica failover
# ---------------------------------------------------------------------------


def test_breaker_opens_after_threshold_and_fails_over():
    a, b = _StubRunner(name="a"), _StubRunner(name="b")
    rs = ReplicaSet({"a": a, "b": b}, breaker_threshold=2, cooldown_s=60)
    ep = Endpoint("fo", rs, EndpointConfig(buckets=(2,), max_wait_ms=2.0))
    ep.submit(_feed(0.0)).result(timeout=5)
    a.fail_with = errors.UnavailableError("replica died")
    c0 = _counter("serving.requeued")
    futs = [ep.submit(_feed(float(i))) for i in range(6)]
    for f in futs:
        f.result(timeout=10)  # every request resolves despite the kill
    ep.drain(timeout=10)
    assert rs.states()["a"] == "open"
    g = observability.get_gauges()
    assert g.get("serving.breaker_state.a") == 1.0
    assert g.get("serving.breaker_state.b") == 0.0
    assert _counter("serving.requeued") > c0
    assert _counter("serving.breaker_opened.a") == 1


def test_half_open_probe_closes_breaker_on_recovery():
    clock = [0.0]
    a, b = _StubRunner(name="a"), _StubRunner(name="b")
    rs = ReplicaSet({"a": a, "b": b}, breaker_threshold=1, cooldown_s=5.0,
                    clock=lambda: clock[0])
    a.fail_with = errors.UnavailableError("down")
    rs.run(_bfeed(1.0), request_ids=[1])  # fails over a->b, opens a
    assert rs.states()["a"] == "open"
    rs.run(_bfeed(2.0), request_ids=[2])  # a still cooling: b serves
    assert rs.states()["a"] == "open"
    clock[0] += 6.0
    a.fail_with = None  # replica healed
    rs.run(_bfeed(3.0), request_ids=[3])  # the half-open probe
    assert rs.states()["a"] == "closed"
    assert observability.get_gauges().get("serving.breaker_state.a") == 0.0
    assert _counter("serving.breaker_closed.a") == 1


def test_half_open_probe_failure_reopens():
    clock = [0.0]
    a, b = _StubRunner(name="a"), _StubRunner(name="b")
    rs = ReplicaSet({"a": a, "b": b}, breaker_threshold=1, cooldown_s=5.0,
                    clock=lambda: clock[0])
    a.fail_with = errors.UnavailableError("down")
    rs.run(_bfeed(1.0), request_ids=[1])
    clock[0] += 6.0
    rs.run(_bfeed(2.0), request_ids=[2])  # probe fails -> re-open + reroute
    assert rs.states()["a"] == "open"
    assert observability.get_gauges().get("serving.breaker_state.a") == 1.0
    clock[0] += 3.0  # cooldown restarts at the failed probe
    rs.run(_bfeed(3.0), request_ids=[3])
    assert rs.states()["a"] == "open", "cooldown must restart on re-open"


def test_failover_is_exactly_once_per_request_id():
    a, b = _StubRunner(name="a"), _StubRunner(name="b")
    rs = ReplicaSet({"a": a, "b": b}, breaker_threshold=99, cooldown_s=0.0)
    a.fail_with = errors.UnavailableError("down")
    rs.run(_bfeed(1.0, n=2), request_ids=[11, 12])  # a->b, re-route spent
    assert [1.0, 1.0] in b.batches
    # ids 12/13 fail on a again: the failure must surface TYPED instead
    # of re-routing a second time (12 already spent its one re-route)
    with pytest.raises(errors.UnavailableError):
        rs.run(_bfeed(2.0, n=2), request_ids=[12, 13])
    assert [2.0, 2.0] not in b.batches, (
        "a second re-route executed the batch again"
    )


def test_both_replicas_down_surfaces_typed_error():
    a, b = _StubRunner(name="a"), _StubRunner(name="b")
    rs = ReplicaSet({"a": a, "b": b}, breaker_threshold=1, cooldown_s=60)
    a.fail_with = errors.UnavailableError("a down")
    b.fail_with = errors.UnavailableError("b down")
    with pytest.raises(errors.UnavailableError):
        rs.run(_bfeed(), request_ids=[1])
    assert rs.states() == {"a": "open", "b": "open"}
    # and with every breaker open, the next call refuses immediately
    with pytest.raises(errors.UnavailableError):
        rs.run(_bfeed(), request_ids=[2])


def test_replica_set_validates_feed_names():
    class _Other(_StubRunner):
        feed_names = ("y",)

    with pytest.raises(InvalidArgumentError):
        ReplicaSet({"a": _StubRunner(), "b": _Other()})
    with pytest.raises(InvalidArgumentError):
        ReplicaSet({})
    with pytest.raises(InvalidArgumentError):
        ReplicaSet({"a": _StubRunner()}, breaker_threshold=0)


def test_heartbeat_informed_health(tmp_path):
    from paddle_tpu.resilience.health import Heartbeat

    hb_dir = str(tmp_path)
    hb_a = Heartbeat(hb_dir, rank=0)
    hb_b = Heartbeat(hb_dir, rank=1)
    hb_a.beat(), hb_b.beat()
    a, b = _StubRunner(name="a"), _StubRunner(name="b")
    rs = ReplicaSet(
        {"a": a, "b": b},
        heartbeats={"a": hb_a.path, "b": hb_b.path},
        heartbeat_timeout=0.2,
    )
    rs.run(_bfeed(1.0), request_ids=[1])
    time.sleep(0.3)
    hb_b.touch()  # only b stays fresh; a's beat goes stale
    for i in range(4):
        rs.run(_bfeed(float(i)), request_ids=[10 + i])
    assert not any(
        batch for batch in a.batches[1:]
    ), "stale-beat replica kept receiving dispatches"
    assert len(b.batches) >= 3


def test_replica_drain_keeps_set_live():
    a, b = _StubRunner(name="a"), _StubRunner(name="b")
    rs = ReplicaSet({"a": a, "b": b})
    ep = Endpoint("pd", rs, EndpointConfig(buckets=(1,), max_wait_ms=0.0))
    ep.submit(_feed(1.0)).result(timeout=5)
    assert rs.drain_replica("a") is True
    assert rs.states()["a"] == "draining"
    for i in range(3):
        ep.submit(_feed(float(i))).result(timeout=5)
    assert len(b.batches) >= 3, "set did not stay live on the survivor"
    assert len(a.batches) == 1
    rs.restore_replica("a")
    assert rs.states()["a"] == "closed"
    ep.submit(_feed(5.0)).result(timeout=5)
    ep.drain(timeout=5)
    assert _counter("serving.replica_drains") == 1


def test_warmup_warms_every_replica():
    a, b = _StubRunner(name="a"), _StubRunner(name="b")
    rs = ReplicaSet({"a": a, "b": b})
    ep = Endpoint("warm", rs,
                  EndpointConfig(buckets=(1, 2, 4), max_wait_ms=1.0))
    ep.warmup()
    ep.drain(timeout=5)
    assert len(a.batches) == 3 and len(b.batches) == 3, (
        "a cold standby pays its compiles during failover"
    )


# ---------------------------------------------------------------------------
# the serving.dispatch fault seam
# ---------------------------------------------------------------------------


def test_dispatch_fault_fails_plain_endpoint_batch_typed():
    runner = _StubRunner()
    ep = Endpoint("seam", runner,
                  EndpointConfig(buckets=(1,), max_wait_ms=0.0))
    faults.inject("serving.dispatch", "io", prob=1.0, seed=0, max_fires=1)
    try:
        f1 = ep.submit(_feed(1.0))
        with pytest.raises(errors.ExternalError):
            f1.result(timeout=5)
        ep.submit(_feed(2.0)).result(timeout=5)  # seam healed
    finally:
        faults.clear("serving.dispatch")
        ep.drain(timeout=5)
    assert _counter("resilience.faults_injected.serving.dispatch") == 1
    assert _counter("serving.request_errors") >= 1


def test_dispatch_fault_heals_through_failover():
    a, b = _StubRunner(name="a"), _StubRunner(name="b")
    rs = ReplicaSet({"a": a, "b": b}, breaker_threshold=3, cooldown_s=60)
    ep = Endpoint("heal", rs, EndpointConfig(buckets=(1,),
                                             max_wait_ms=0.0))
    faults.inject("serving.dispatch", "io", prob=1.0, seed=0, max_fires=1)
    try:
        out = ep.submit(_feed(3.0)).result(timeout=5)[0]
        np.testing.assert_array_equal(out, np.full(2, 6.0))
    finally:
        faults.clear("serving.dispatch")
        ep.drain(timeout=5)
    assert _counter("serving.requeued") >= 1


def test_per_replica_seam_targets_one_replica():
    a, b = _StubRunner(name="a"), _StubRunner(name="b")
    rs = ReplicaSet({"a": a, "b": b}, breaker_threshold=1, cooldown_s=60)
    faults.inject("serving.dispatch.a", "unavailable", prob=1.0, seed=0)
    try:
        for i in range(4):
            rs.run(_bfeed(float(i)), request_ids=[i])
        assert rs.states()["a"] == "open"
        assert len(b.batches) == 4
    finally:
        faults.clear("serving.dispatch.a")


def test_dispatch_hang_bounded_by_attempt_timeout():
    """A hung replica dispatch surfaces as a typed timeout after
    attempt_timeout and the batch fails over — the scheduler thread is
    never wedged for the hang duration."""
    a, b = _StubRunner(name="a"), _StubRunner(name="b")
    rs = ReplicaSet({"a": a, "b": b}, breaker_threshold=1, cooldown_s=60,
                    attempt_timeout=0.3)
    ep = Endpoint("hang", rs, EndpointConfig(buckets=(1,),
                                             max_wait_ms=0.0))
    os.environ[faults.HANG_SECONDS_ENV] = "5"
    faults.inject("serving.dispatch.a", "hang", prob=1.0, seed=0,
                  max_fires=1)
    try:
        t0 = time.perf_counter()
        out = ep.submit(_feed(4.0)).result(timeout=10)[0]
        dt = time.perf_counter() - t0
        np.testing.assert_array_equal(out, np.full(2, 8.0))
        assert dt < 3.0, f"hang was not bounded ({dt:.1f}s)"
        assert rs.states()["a"] == "open"
    finally:
        os.environ.pop(faults.HANG_SECONDS_ENV, None)
        faults.clear("serving.dispatch.a")
        ep.drain(timeout=10)


# ---------------------------------------------------------------------------
# drain semantics
# ---------------------------------------------------------------------------


def test_drain_resolves_expired_requests_instead_of_hanging():
    """The satellite contract: SIGTERM drain with expired-deadline
    requests still queued — they must resolve with the typed error and
    the drain must complete."""
    from paddle_tpu.serving import install_preemption_handler

    runner = _StubRunner(delay=0.1)
    server = Server()
    server.add_endpoint(
        "dr", runner, EndpointConfig(buckets=(4,), max_wait_ms=1.0)
    )
    import signal

    old = install_preemption_handler(server, exit_on_drain=False)
    try:
        blocker = server.submit("dr", _feed(9.0))
        time.sleep(0.02)
        doomed = [server.submit("dr", _feed(1.0), deadline_ms=20)
                  for _ in range(3)]
        live = [server.submit("dr", _feed(2.0)) for _ in range(2)]
        time.sleep(0.05)  # deadlines pass while the blocker dispatch runs
        os.kill(os.getpid(), signal.SIGTERM)
        assert server.wait_drained(timeout=30), "drain hung on dead work"
        for f in doomed:
            with pytest.raises(DeadlineExceededError):
                f.result(timeout=5)
        for f in live:
            f.result(timeout=5)
        blocker.result(timeout=5)
        assert _counter("serving.expired.dr") == 3
        assert _counter("serving.drained") == 1
    finally:
        signal.signal(signal.SIGTERM, old)


def test_server_drain_prorates_timeout_across_endpoints():
    """The r8 bug: drain(t) handed every endpoint the FULL t, so N wedged
    endpoints drained in N*t. The budget must bound the whole drain."""
    server = Server()
    for i in range(3):
        server.add_endpoint(
            f"slow{i}", _StubRunner(delay=1.0),
            EndpointConfig(buckets=(1,), max_wait_ms=0.0),
        )
        server.submit(f"slow{i}", _feed())
    time.sleep(0.05)  # every scheduler enters its 1s dispatch
    t0 = time.monotonic()
    ok = server.drain(timeout=0.5)
    took = time.monotonic() - t0
    assert took < 1.2, (
        f"drain(0.5) took {took:.2f}s — budget not pro-rated"
    )
    assert ok is False  # truthful: the dispatches outlived the budget
    server.drain(timeout=10)  # now let them finish for clean teardown


def test_server_submit_passes_deadline_and_priority_through():
    runner = _StubRunner(delay=0.1)
    server = Server()
    server.add_endpoint("pass", runner,
                        EndpointConfig(buckets=(1,), max_wait_ms=0.0))
    blocker = server.submit("pass", _feed(0.0))
    fut = server.submit("pass", _feed(1.0), deadline_ms=25,
                        priority=BACKGROUND)
    with pytest.raises(DeadlineExceededError):
        fut.result(timeout=5)
    blocker.result(timeout=5)
    server.drain(timeout=5)
    assert _counter("serving.expired_class.background") == 1
