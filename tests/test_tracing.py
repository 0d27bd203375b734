"""End-to-end causal tracing (ISSUE 13): TraceContext propagation across
threads (serving scheduler, AsyncCheckpointer publisher, embedding
Prefetcher worker) and ranks (heartbeat stamps), the live watcher's
structured findings, and the trace_report reconstruction tooling — plus
the unified PADDLE_TPU_MONITOR kill-switch across metrics, spans AND
traces."""

import importlib.util
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observability as obs
from paddle_tpu.framework import unique_name
from paddle_tpu.observability import trace, watch
from paddle_tpu.resilience import faults
from paddle_tpu.resilience.health import Heartbeat

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

HANG_ENV = "PADDLE_TPU_FAULT_HANG_SECONDS"


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_ROOT, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def fresh_metrics():
    obs.reset()
    obs.set_enabled(True)
    faults.clear()
    old = os.environ.pop(HANG_ENV, None)
    yield
    faults.clear()
    if old is not None:
        os.environ[HANG_ENV] = old
    obs.reset()
    obs.set_enabled(None)


@pytest.fixture
def fresh_programs():
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.framework.scope.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard():
        yield main, startup, scope


def _traced_spans():
    return [s for s in obs.get_spans() if "trace_id" in s]


def _by_name(name):
    return [s for s in _traced_spans() if s["name"] == name]


# -- context primitives ------------------------------------------------------


def test_span_nesting_builds_parent_chain():
    tr = trace.new_trace()
    with trace.activate(tr):
        with obs.span("outer") as outer:
            with obs.span("inner"):
                pass
    inner, = _by_name("inner")
    outer_rec, = _by_name("outer")
    assert outer_rec["trace_id"] == inner["trace_id"] == tr.trace_id
    assert outer_rec["parent_id"] is None
    assert inner["parent_id"] == outer_rec["span_id"] == outer.span_id


def test_activate_none_masks_outer_context():
    with trace.activate(trace.new_trace()):
        with trace.activate(None):
            with obs.span("masked"):
                pass
        with obs.span("visible"):
            pass
    assert not _by_name("masked")
    assert _by_name("visible")


def test_record_retrospective_span():
    tr = trace.new_trace()
    sid = obs.record("retro", 0.25, ctx=tr, args={"k": 1})
    rec, = _by_name("retro")
    assert rec["span_id"] == sid and rec["trace_id"] == tr.trace_id
    assert rec["dur"] == pytest.approx(0.25e6)
    # ts was back-dated by the duration
    assert rec["ts"] <= time.time_ns() / 1e3 - 0.24e6


def test_capture_activate_across_thread():
    import threading

    with trace.activate(trace.new_trace()):
        with obs.span("producer") as prod:
            ctx = trace.capture()

            def worker():
                with trace.activate(ctx):
                    with obs.span("consumer"):
                        pass

            t = threading.Thread(target=worker)
            t.start()
            t.join()
    cons, = _by_name("consumer")
    assert cons["parent_id"] == prod.span_id
    assert cons["tid"] != _by_name("producer")[0]["tid"]


def test_chrome_export_carries_trace_ids():
    import json

    with trace.activate(trace.new_trace()):
        with obs.span("exported"):
            pass
    events = json.loads(obs.chrome_trace())["traceEvents"]
    xs = [e for e in events if e.get("ph") == "X"]
    assert xs and "trace_id" in xs[0]["args"] and "span_id" in xs[0]["args"]


# -- the unified kill-switch (satellite bugfix) ------------------------------


def test_kill_switch_disables_spans_and_traces():
    obs.set_enabled(False)
    assert trace.new_trace() is None
    # even under a pre-captured live context, nothing records
    obs.set_enabled(True)
    tr = trace.new_trace()
    obs.reset()  # drop the traces_started bump from the line above
    obs.set_enabled(False)
    with trace.activate(tr):
        with obs.span("dead"):
            pass
        assert obs.record("dead.retro", 0.1) is None
    w = watch.Watcher()
    assert w.poll() == []
    snap = obs.snapshot()
    assert snap["span_count"] == 0
    assert snap["counters"] == {}
    # PR 16: the same switch silences the telemetry plane — no publisher
    # or flight-recorder thread starts, not one journal/bundle file lands
    import tempfile

    from paddle_tpu.observability import recorder, timeline

    with tempfile.TemporaryDirectory() as d:
        pub = timeline.TelemetryPublisher(
            directory=d, rank=0, interval=0.01
        ).start(register=False)
        rec = recorder.FlightRecorder(directory=d, rank=0,
                                      interval=0.01).start(register=False)
        assert pub._thread is None and rec._thread is None
        assert pub.publish() is None and rec.dump("exception") is None
        assert os.listdir(d) == []


# -- serving: request traces across the scheduler handoff --------------------


class _ToyRunner:
    feed_names = ("x",)

    def sample_spec(self, name):
        return ((2,), "float32")

    def run(self, feed):
        with obs.span("runner.work"):
            return [np.asarray(feed["x"]) * 2]


def _drain_endpoint(ep, n=3):
    futs = [ep.submit({"x": np.ones(2, np.float32)}) for _ in range(n)]
    for f in futs:
        f.result(timeout=30)
    ep.drain(timeout=10)


def test_serving_request_trace_is_complete_and_cross_thread():
    from paddle_tpu.serving.router import Endpoint, EndpointConfig

    ep = Endpoint("toy", _ToyRunner(),
                  EndpointConfig(buckets=(1, 2), max_wait_ms=2.0))
    _drain_endpoint(ep, n=3)
    traces = {}
    for s in _traced_spans():
        traces.setdefault(s["trace_id"], []).append(s)
    assert len(traces) == 3  # one trace per request
    for ss in traces.values():
        names = {s["name"] for s in ss}
        assert {"serving.ingest", "serving.queue_wait",
                "serving.dispatch"} <= names
        ids = {s["span_id"] for s in ss}
        assert all(
            s["parent_id"] in ids for s in ss if s["parent_id"]
        ), "orphan span in request trace"
        # ingest on the caller thread, scheduling on the scheduler thread
        assert len({s["tid"] for s in ss}) >= 2
        ingest, = [s for s in ss if s["name"] == "serving.ingest"]
        qw, = [s for s in ss if s["name"] == "serving.queue_wait"]
        assert qw["parent_id"] == ingest["span_id"]


def test_serving_joins_callers_active_trace():
    from paddle_tpu.serving.router import Endpoint, EndpointConfig

    ep = Endpoint("toy2", _ToyRunner(),
                  EndpointConfig(buckets=(1,), max_wait_ms=1.0))
    tr = trace.new_trace()
    with trace.activate(tr), obs.span("client.request"):
        fut = ep.submit({"x": np.ones(2, np.float32)})
    fut.result(timeout=30)
    ep.drain(timeout=10)
    ingest, = _by_name("serving.ingest")
    client, = _by_name("client.request")
    assert ingest["trace_id"] == tr.trace_id
    assert ingest["parent_id"] == client["span_id"]


def test_gpt_generator_decode_spans_under_request_trace():
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.serving import GPTGenerator

    cfg = GPTConfig(
        vocab_size=32, hidden_size=16, num_layers=1, num_heads=2,
        intermediate_size=32, max_position=12, use_fused_attention=False,
    )
    gen = GPTGenerator(cfg, batch=1, context_len=4, max_len=12)
    gen.init_params(seed=3)
    tr = trace.new_trace()
    with trace.activate(tr):
        gen.generate(np.zeros((1, 4), np.int64), 3)
    prefill, = _by_name("serving.prefill")
    decode, = _by_name("serving.decode_loop")
    assert prefill["trace_id"] == decode["trace_id"] == tr.trace_id
    # executor steps nested under the decode loop
    steps = [s for s in _by_name("executor.step")
             if s["parent_id"] == decode["span_id"]]
    assert len(steps) == 2  # 3 tokens -> 2 decode dispatches
    # no step of a batch waits for its fetch: the host reads the ids once
    assert not [s for s in _by_name("executor.fetch")
                if s.get("trace_id") == tr.trace_id]


# -- async checkpointer: publish parents to the SURVIVING save ---------------


def _build_sgd_model():
    x = fluid.data("x", [-1, 4])
    y = fluid.data("y", [-1, 1])
    pred = layers.fc(x, 1)
    loss = layers.mean(layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(0.05).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    return exe, loss


def _fleet():
    from paddle_tpu.fleet import collective as fc
    from paddle_tpu.fleet.role_maker import UserDefinedRoleMaker

    f = fc.Fleet()
    f.init(UserDefinedRoleMaker())
    return f


def _step(exe, loss, rng):
    xa = rng.randn(8, 4).astype(np.float32)
    exe.run(feed={"x": xa, "y": xa @ np.ones((4, 1), np.float32)},
            fetch_list=[loss])


def test_async_publish_span_joins_saving_step_trace(
    tmp_path, fresh_programs
):
    from paddle_tpu.fleet import collective as fc

    exe, loss = _build_sgd_model()
    fleet = _fleet()
    rng = np.random.RandomState(0)
    with fc.AsyncCheckpointer(fleet, str(tmp_path / "ck"),
                              executor=exe) as saver:
        _step(exe, loss, rng)
        tr = trace.new_trace()
        with trace.activate(tr), obs.span("train.step"):
            handle = saver.save(fc.TrainStatus(0, global_step=1))
        assert handle.result(timeout=30) == 0
        saver.wait(timeout=30)
    snap_span, = _by_name("checkpoint.snapshot")
    pub_span, = _by_name("checkpoint.publish")
    step_span, = _by_name("train.step")
    assert snap_span["trace_id"] == pub_span["trace_id"] == tr.trace_id
    assert snap_span["parent_id"] == step_span["span_id"]
    # cross-thread: publish on the publisher thread, parented under the
    # step thread's snapshot span
    assert pub_span["parent_id"] == snap_span["span_id"]
    assert pub_span["tid"] != snap_span["tid"]


def test_coalesced_publish_parents_to_surviving_save_trace(
    tmp_path, fresh_programs
):
    from paddle_tpu.fleet import collective as fc

    exe, loss = _build_sgd_model()
    fleet = _fleet()
    rng = np.random.RandomState(0)
    saver = fc.AsyncCheckpointer(fleet, str(tmp_path / "ck"),
                                 executor=exe,
                                 remain_all_checkpoint=True)
    # the first publish is held until saves 2 and 3 have landed behind
    # it, so 2 is superseded by 3 — its trace must never own a publish
    # span. An ordering the test waits on, not a sleep it hopes to beat:
    # the slowed publish says when it is in flight, the test when to go on
    in_flight, release = threading.Event(), threading.Event()
    publish = saver._publish

    def held_publish(job):
        if not in_flight.is_set():
            in_flight.set()
            assert release.wait(timeout=30)
        return publish(job)

    saver._publish = held_publish
    try:
        handles, traces = [], []
        for i in range(3):
            _step(exe, loss, rng)
            tr = trace.new_trace()
            traces.append(tr)
            with trace.activate(tr):
                handles.append(
                    saver.save(fc.TrainStatus(i, global_step=i + 1))
                )
            if i == 0:
                assert in_flight.wait(timeout=30)
        release.set()
        for h in handles:
            h.result(timeout=30)
        saver.wait(timeout=30)
    finally:
        release.set()
        saver.close()
    assert obs.get_counters().get("checkpoint.coalesced", 0) >= 1
    pub_traces = [s["trace_id"] for s in _by_name("checkpoint.publish")]
    assert traces[0].trace_id in pub_traces  # the in-flight save
    assert traces[2].trace_id in pub_traces  # the survivor
    assert traces[1].trace_id not in pub_traces  # superseded: no publish


def test_liveness_pulse_span_under_publish_trace(tmp_path, fresh_programs):
    from paddle_tpu.fleet import collective as fc

    exe, loss = _build_sgd_model()
    fleet = _fleet()
    hb = Heartbeat(str(tmp_path / "hb"), rank=0)
    os.environ[HANG_ENV] = "0.6"
    saver = fc.AsyncCheckpointer(fleet, str(tmp_path / "ck"),
                                 executor=exe, heartbeat=hb)
    try:
        _step(exe, loss, np.random.RandomState(0))
        tr = trace.new_trace()
        faults.inject("fs.upload", "hang", 1.0, 0, 1)
        with trace.activate(tr):
            saver.save(fc.TrainStatus(0, global_step=1)).result(timeout=30)
        saver.wait(timeout=30)
    finally:
        saver.close()
    pub, = _by_name("checkpoint.publish")
    pulses = [s for s in _by_name("health.pulse")
              if s["trace_id"] == tr.trace_id]
    assert pulses, "liveness pulse did not record under the save trace"
    # the pulse runs on its own thread, parented under the publish span
    assert pulses[0]["parent_id"] == pub["span_id"]
    assert len({pub["tid"], pulses[0]["tid"],
                _by_name("checkpoint.snapshot")[0]["tid"]}) == 3


# -- prefetcher worker handoff + restart-after-error -------------------------


class _PlanEngine:
    def __init__(self, fail_at=None):
        self.fail_at = fail_at
        self.calls = 0

    def plan(self, feed):
        self.calls += 1
        if self.fail_at is not None and self.calls == self.fail_at:
            raise RuntimeError("seeded plan failure")
        return {"plan_for": feed["i"]}

    def apply(self, plans, feed, scope):
        return feed


def test_prefetcher_plan_spans_join_constructing_trace():
    from paddle_tpu.embedding.prefetch import Prefetcher

    tr = trace.new_trace()
    with trace.activate(tr), obs.span("driver") as driver:
        pf = Prefetcher(_PlanEngine(), [{"i": i} for i in range(3)],
                        scope=None)
    got = list(pf)
    assert [f["i"] for f in got] == [0, 1, 2]
    plans = _by_name("embedding.prefetch_plan")
    assert len(plans) == 3
    main_tid = driver.span_id and _by_name("driver")[0]["tid"]
    for p in plans:
        assert p["trace_id"] == tr.trace_id
        assert p["parent_id"] == driver.span_id
        assert p["tid"] != main_tid  # recorded on the worker thread


def test_prefetcher_restart_after_error_rejoins_trace():
    from paddle_tpu.embedding.prefetch import Prefetcher

    feeds = [{"i": i} for i in range(4)]
    tr = trace.new_trace()
    with trace.activate(tr):
        pf = Prefetcher(_PlanEngine(fail_at=2), feeds, scope=None)
        got = []
        with pytest.raises(RuntimeError, match="seeded plan failure"):
            for f in pf:
                got.append(f["i"])
        pf.close()
        # restart: a fresh prefetcher over the remaining feeds re-captures
        # the (still active) trace — the restarted worker's spans rejoin it
        pf2 = Prefetcher(_PlanEngine(), feeds[len(got):], scope=None)
        rest = [f["i"] for f in pf2]
    assert got + rest == [0, 1, 2, 3]
    plans = _by_name("embedding.prefetch_plan")
    assert len(plans) >= 1 + len(rest)
    assert {p["trace_id"] for p in plans} == {tr.trace_id}


# -- cross-rank: heartbeat trace stamps --------------------------------------


def test_heartbeat_stamps_active_trace(tmp_path):
    from paddle_tpu.resilience.health import read_beat

    hb = Heartbeat(str(tmp_path), rank=1)
    tr = trace.new_trace()
    with trace.activate(tr), obs.span("train.step") as sp:
        hb.beat(step=7)
    beat = read_beat(hb.path)
    assert beat["step"] == 7
    assert beat["trace_id"] == tr.trace_id
    assert beat["span_id"] == sp.span_id
    # outside any trace the stamp is absent (no stale ids)
    hb.beat(step=8)
    assert "trace_id" not in read_beat(hb.path)


# -- live watcher ------------------------------------------------------------


def test_watcher_flags_straggling_rank(tmp_path):
    d = str(tmp_path)
    Heartbeat(d, rank=0).beat(step=10)
    Heartbeat(d, rank=1).beat(step=3)
    w = watch.Watcher(heartbeat_dir=d, skew_steps=2)
    findings = w.poll()
    assert [f["kind"] for f in findings] == ["straggler"]
    assert findings[0]["detail"]["lagging_ranks"] == [1]
    assert findings[0]["detail"]["skew_steps"] == 7
    # latched: same excursion raises once
    assert w.poll() == []
    # recovery re-arms, a new excursion fires again
    Heartbeat(d, rank=1).beat(step=10)
    assert w.poll() == []
    Heartbeat(d, rank=1).beat(step=10)
    Heartbeat(d, rank=0).beat(step=20)
    assert [f["kind"] for f in w.poll()] == ["straggler"]
    c = obs.get_counters()
    assert c["watch.findings.straggler"] == 2
    assert c["watch.polls"] == 4
    assert "watch.findings" in obs.snapshot()["tables"]


def test_watcher_flags_step_time_regression():
    w = watch.Watcher(min_window=4, drift_tolerance=0.25)
    for _ in range(4):
        obs.observe("executor.step_latency", 0.010)
    assert w.poll() == []  # first poll only anchors the window
    for _ in range(4):
        obs.observe("executor.step_latency", 0.010)
    assert w.poll() == []  # establishes the best window
    for _ in range(4):
        obs.observe("executor.step_latency", 0.050)
    findings = w.poll()
    assert [f["kind"] for f in findings] == ["step_regression"]
    assert findings[0]["detail"]["ratio"] == pytest.approx(5.0, rel=0.01)
    assert obs.get_gauges()["watch.step_time_ratio"] > 1.25


def test_watcher_flags_slo_breach_and_rearms():
    w = watch.Watcher(slo_p99_s=0.1)
    for _ in range(10):
        obs.observe("serving.request_latency", 0.02)
    assert w.poll() == []
    for _ in range(5):
        obs.observe("serving.request_latency", 0.8)
    findings = w.poll()
    assert [f["kind"] for f in findings] == ["slo_breach"]
    assert findings[0]["severity"] == "error"
    assert findings[0]["detail"]["p99_s"] >= 0.8
    # back under the SLO -> re-armed
    for _ in range(50):
        obs.observe("serving.request_latency", 0.01)
    assert w.poll() == []
    for _ in range(5):
        obs.observe("serving.request_latency", 0.9)
    assert [f["kind"] for f in w.poll()] == ["slo_breach"]


# -- trace_report reconstruction ---------------------------------------------


def test_trace_report_check_passes_on_cross_thread_export(tmp_path):
    from paddle_tpu.serving.router import Endpoint, EndpointConfig

    ep = Endpoint("toy3", _ToyRunner(),
                  EndpointConfig(buckets=(1, 2), max_wait_ms=2.0))
    _drain_endpoint(ep, n=2)
    path = str(tmp_path / "trace_rank0.json")
    obs.save_chrome_trace(path)
    tr_tool = _load_tool("trace_report")
    rc = tr_tool.main([path, "--check", "--min-threads", "2",
                       "--require-span", "serving.ingest", "--quiet"])
    assert rc == 0
    # a bar no export meets must fail
    rc = tr_tool.main([path, "--check", "--min-threads", "7", "--quiet"])
    assert rc != 0


def test_trace_report_broken_fixture_exits_nonzero():
    tr_tool = _load_tool("trace_report")
    assert tr_tool.main(["--broken-fixture"]) != 0
