"""The program's spans in the profiler's trace (ISSUE 24): every live
span is also a `jax.profiler.TraceAnnotation`, so under a capture it sits
in the host plane on the device lines' clock, nested as the code nests;
the ring keeps the same names; `record()` spans stay ring-only; a span
the full ring pushes out is counted."""

import collections
import glob
import itertools
import os
import time

import jax
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observability as obs
from paddle_tpu.framework import unique_name
from paddle_tpu.observability import spans as span_ring

PROGRAM_PREFIXES = ("executor.", "spmd.", "serving.")
STEP_CHILDREN = ["executor.prologue", "executor.dispatch",
                 "executor.writeback"]


@pytest.fixture(autouse=True)
def fresh():
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.framework.scope.Scope()
    obs.set_enabled(True)
    obs.reset()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard():
        yield
    obs.set_enabled(None)
    obs.reset()


class Capture:
    """A profiler capture (python tracer off) around the body; afterwards
    `lines` = {host thread line: [(name, start_ns, end_ns)]} of the
    program's annotations, by start."""

    def __init__(self, path):
        self.path = str(path)
        self.lines = {}

    def __enter__(self):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.path, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        from jax.profiler import ProfileData

        jax.profiler.stop_trace()
        files = glob.glob(os.path.join(self.path, "**", "*.xplane.pb"),
                          recursive=True)
        data = ProfileData.from_file(max(files, key=os.path.getmtime))
        for plane in data.planes:
            if plane.name.startswith("/device:"):
                continue
            # threads share a line name ("python"): key each by position
            for k, ln in enumerate(plane.lines):
                events = sorted(
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in ln.events
                    if ev.name.startswith(PROGRAM_PREFIXES)
                )
                if events:
                    self.lines[f"{ln.name}#{k}"] = sorted(
                        events, key=lambda e: e[1])
        return False

    def named(self, name):
        return [(line, e) for line, events in self.lines.items()
                for e in events if e[0] == name]

    def children(self, line, parent):
        """Names of the annotations directly inside `parent` on `line`,
        in order (nested deeper ones left out)."""
        _n, lo, hi = parent
        inside = [e for e in self.lines[line]
                  if e is not parent and lo <= e[1] and e[2] <= hi]
        return [e[0] for e in inside
                if not any(o is not e and o[1] <= e[1] and e[2] <= o[2]
                           for o in inside)]


def _fit_a_line():
    x = fluid.data("x", [-1, 4], "float32")
    y = fluid.data("y", [-1, 1], "float32")
    loss = layers.reduce_mean(
        layers.square_error_cost(layers.fc(x, size=1), y)
    )
    fluid.optimizer.SGD(0.01).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(8, 4).astype("float32"),
            "y": rng.randn(8, 1).astype("float32")}
    return exe, loss, feed


def _ring_names():
    return [s["name"] for s in obs.get_spans()]


@pytest.mark.parametrize("return_numpy", [True, False])
def test_executor_step_children_in_capture_and_ring(tmp_path, return_numpy):
    exe, loss, feed = _fit_a_line()
    exe.run(feed=feed, fetch_list=[loss])  # compile outside the capture
    obs.reset()
    with Capture(tmp_path) as cap:
        exe.run(feed=feed, fetch_list=[loss], return_numpy=return_numpy)
    want = STEP_CHILDREN + (["executor.fetch"] if return_numpy else [])
    (line, step), = cap.named("executor.step")
    # one thread of the host plane holds the step and all its children
    assert cap.children(line, step) == want
    (_l, prologue), = cap.named("executor.prologue")
    assert cap.children(line, prologue) == ["executor.rng_key"]
    assert sorted(_ring_names()) == sorted(
        want + ["executor.step", "executor.rng_key"])
    # the ring's children lie inside the ring's step, too
    by_name = {s["name"]: s for s in obs.get_spans()}
    outer = by_name["executor.step"]
    for name in want:
        s = by_name[name]
        assert outer["ts"] <= s["ts"]
        assert s["ts"] + s["dur"] <= outer["ts"] + outer["dur"] + 1.0


def test_compile_is_a_child_of_the_prologue(tmp_path):
    exe, loss, feed = _fit_a_line()
    with Capture(tmp_path) as cap:
        exe.run(feed=feed, fetch_list=[loss])
    (line, step), = cap.named("executor.step")
    (_l, prologue), = cap.named("executor.prologue")
    assert cap.children(line, step)[0] == "executor.prologue"
    assert cap.children(line, prologue) == ["executor.compile",
                                            "executor.rng_key"]


def test_monitor_off_leaves_neither_ring_nor_annotation(tmp_path):
    exe, loss, feed = _fit_a_line()
    exe.run(feed=feed, fetch_list=[loss])
    obs.reset()
    obs.set_enabled(False)
    with Capture(tmp_path) as cap:
        (lv,) = exe.run(feed=feed, fetch_list=[loss])
    assert np.isfinite(lv).all()
    assert cap.lines == {}
    assert obs.get_spans() == []


def test_step_latency_is_the_step_spans_own_duration():
    """`executor.step_latency` is the `executor.step` span's duration: no
    clock beside the spans."""
    exe, loss, feed = _fit_a_line()
    exe.run(feed=feed, fetch_list=[loss])
    obs.reset()
    exe.run(feed=feed, fetch_list=[loss])
    dur = {s["name"]: s["dur"] / 1e6 for s in obs.get_spans()}
    hist = obs.get_histograms()
    assert hist["executor.step_latency"]["sum"] == pytest.approx(
        dur["executor.step"], rel=1e-9
    )


@pytest.mark.parametrize("return_numpy", [True, False])
@pytest.mark.parametrize("dp", [1, 4])
def test_run_publishes_spans_and_counters_and_estimates_nothing(
        monkeypatch, dp, return_numpy):
    """One instrument (ISSUE 29): with monitoring on, `Executor.run`
    leaves no `perf.*` gauge, table or histogram and no
    `collective.overlap_ratio`, and never walks the graph through
    `Program.estimate` — the cost model is the offline estimator's."""
    from paddle_tpu.parallel import make_mesh, shard_program

    estimate, calls = fluid.Program.estimate, []

    def counted(self, *a, **k):
        calls.append(self)
        return estimate(self, *a, **k)

    x = fluid.data("x", [-1, 4], "float32")
    out = layers.fc(x, size=2)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    main = fluid.default_main_program()
    if dp > 1:
        main.global_block.append_op(
            "c_allreduce_sum", {"X": [out.name]}, {"Out": [out.name]},
            {"axis_name": "dp"})
        shard_program(main, make_mesh({"dp": dp}, jax.devices()[:dp]),
                      {"x": ("dp",), out.name: ("dp",)})
    obs.reset()
    monkeypatch.setattr(fluid.Program, "estimate", counted)
    for _ in range(3):
        exe.run(feed={"x": np.ones((8, 4), np.float32)}, fetch_list=[out],
                return_numpy=return_numpy)
    snap = obs.snapshot()
    published = [
        name
        for kind in ("gauges", "tables", "histograms")
        for name in snap.get(kind, {})
        if name.startswith("perf.") or name == "collective.overlap_ratio"
    ]
    assert published == []
    assert calls == []
    assert snap["counters"]["executor.run_steps"] == 3
    assert snap["histograms"]["executor.step_latency"]["count"] == 3


def test_spmd_stage_inside_dispatch_on_four_devices(tmp_path):
    from paddle_tpu.parallel import make_mesh, shard_program

    x = fluid.data("x", [-1, 4], "float32")
    out = layers.fc(x, size=2)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    mesh = make_mesh({"dp": 4}, devices=jax.devices()[:4])
    shard_program(fluid.default_main_program(), mesh,
                  {"x": ("dp",), out.name: ("dp",)})
    feed = {"x": np.ones((8, 4), np.float32)}
    exe.run(feed=feed, fetch_list=[out])
    obs.reset()
    with Capture(tmp_path) as cap:
        exe.run(feed=feed, fetch_list=[out])
    (line, dispatch), = cap.named("executor.dispatch")
    assert cap.children(line, dispatch) == ["spmd.dispatch"]
    (_l, spmd), = cap.named("spmd.dispatch")
    assert cap.children(line, spmd) == ["spmd.stage"]
    ring = {s["name"]: s for s in obs.get_spans()}
    assert ring["spmd.dispatch"]["args"] == {"mesh": "dp4"}
    assert ring["spmd.stage"]["dur"] <= ring["spmd.dispatch"]["dur"]


def _gpt_generator():
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.serving import GPTGenerator

    cfg = GPTConfig.tiny()
    cfg.use_fused_attention = False
    gen = GPTGenerator(cfg, batch=2, context_len=12, max_len=24)
    gen.init_params(seed=11)
    return gen, 1


def _afmoe_generator():
    from paddle_tpu.models.afmoe import AfmoeConfig, AfmoeDecoder
    from paddle_tpu.serving import GPTGenerator

    gen = GPTGenerator(AfmoeDecoder(AfmoeConfig.tiny(prefill_rows=1)),
                       batch=2, context_len=12, max_len=24)
    gen.init_params(seed=11)
    return gen, 2   # prefill dispatches a batch


@pytest.mark.parametrize("make", [_gpt_generator, _afmoe_generator],
                         ids=["gpt", "afmoe"])
def test_generate_spans_no_fetch_in_the_loop_and_one_sample_a_token(
        tmp_path, make):
    """The decode loop's tree (ISSUE 28): one `executor.step` and one
    `serving.sample` a token, no step waits for its fetch (no
    `executor.fetch` anywhere under `serving.batch`'s children), the
    loop ends with the batch's one read, after its last child."""
    gen, prefills = make()
    ctx = np.random.RandomState(0).randint(
        0, gen.cfg.vocab_size, size=(2, 12)).astype(np.int64)
    tokens = 8
    first = gen.generate(ctx, tokens)  # compiles
    obs.reset()
    with Capture(tmp_path) as cap:
        again = gen.generate(ctx, tokens)
    np.testing.assert_array_equal(first, again)
    names = collections.Counter(_ring_names())
    assert names["serving.sample"] == tokens
    assert names["serving.cache_reset"] == 1
    assert names["serving.prefill"] == names["serving.decode_loop"] == 1
    assert names["executor.step"] == prefills + tokens - 1
    assert names["executor.fetch"] == 0
    assert names["serving.step_counters"] == (gen.decoder.counters_var
                                              is not None)
    (line, loop), = cap.named("serving.decode_loop")
    kids = cap.children(line, loop)
    assert kids == ["executor.step", "serving.sample"] * (tokens - 1)
    (_l, prefill), = cap.named("serving.prefill")
    assert cap.children(line, prefill) == ["executor.step"] * prefills
    steps = [e for e in cap.lines[line] if e[0] == "executor.step"
             and loop[1] <= e[1] and e[2] <= loop[2]]
    for step in steps:
        assert cap.children(line, step) == STEP_CHILDREN
    # the loop's last child ends before the loop does: the one read
    last = max(e[2] for e in cap.lines[line]
               if e[0] == "serving.sample" and e[2] <= loop[2])
    assert last < loop[2]
    # reset -> prefill -> first sample -> loop, all on the caller's thread
    order = [e[0] for e in cap.lines[line] if e[0].startswith("serving.")]
    assert order[:4] == ["serving.cache_reset", "serving.prefill",
                         "serving.sample", "serving.decode_loop"]
    assert prefill[2] <= loop[1]


class _Doubler:
    feed_names = ("x",)

    def sample_spec(self, name):
        return (2,), "float32"

    def run(self, feed):
        return [feed["x"] * 2.0]


def test_form_batch_ends_where_the_batch_begins(tmp_path):
    from paddle_tpu.serving import Endpoint, EndpointConfig

    with Capture(tmp_path) as cap:
        ep = Endpoint("stub", _Doubler(),
                      EndpointConfig(buckets=(4,), max_wait_ms=2000.0))
        futs = [ep.submit({"x": np.full(2, i, np.float32)})
                for i in range(4)]
        for f in futs:
            f.result(timeout=10)
        ep.drain(timeout=10)
    ring = obs.get_spans()
    formed = [s for s in ring if s["name"] == "serving.form_batch"
              and s["args"]["batch_size"]]
    (form,), (batch,) = formed, [s for s in ring
                                 if s["name"] == "serving.batch"]
    assert form["args"] == {"endpoint": "stub", "batch_size": 4}
    assert form["tid"] == batch["tid"]
    gap_us = batch["ts"] - (form["ts"] + form["dur"])
    assert -50.0 <= gap_us < 50e3
    # a wait that ended with nothing to run (the drain) says so
    idle = [s for s in ring if s["name"] == "serving.form_batch"
            and not s["args"]["batch_size"]]
    assert all(s["ts"] >= batch["ts"] for s in idle)
    # the same pair, in order, on the scheduler thread's line of the trace
    # (a wait that polled shows as consecutive pieces under the one name)
    (line, _e), = cap.named("serving.batch")
    order = [k for k, _g in itertools.groupby(
        e[0] for e in cap.lines[line]
        if e[0] in ("serving.form_batch", "serving.batch"))]
    assert order[:2] == ["serving.form_batch", "serving.batch"]


def test_assemble_and_complete_own_the_hand_over(tmp_path):
    """Between `serving.form_batch` and `serving.batch` the router records
    the queue waits and stacks the feeds; after the batch it records,
    observes and resolves the futures: each under a live span of its own
    (ISSUE 35), filed under the first request's trace as the batch is."""
    from paddle_tpu.serving import Endpoint, EndpointConfig

    with Capture(tmp_path) as cap:
        ep = Endpoint("stub", _Doubler(),
                      EndpointConfig(buckets=(4,), max_wait_ms=2000.0))
        futs = [ep.submit({"x": np.full(2, i, np.float32)})
                for i in range(4)]
        for f in futs:
            f.result(timeout=10)
        ep.drain(timeout=10)
    ring = {s["name"]: s for s in obs.get_spans()
            if s["name"] in ("serving.assemble", "serving.batch",
                             "serving.complete")}
    assemble, batch, complete = (ring[f"serving.{n}"] for n in
                                 ("assemble", "batch", "complete"))
    assert assemble["args"] == {"endpoint": "stub", "batch_size": 4}
    assert set(complete["args"]) == {"endpoint", "batch_size", "resolve_ms"}
    assert 0.0 < complete["args"]["resolve_ms"] <= complete["dur"] / 1e3
    assert assemble["trace_id"] == batch["trace_id"] == complete["trace_id"]
    assert assemble["parent_id"] == batch["parent_id"]
    # in order, end to end, on the scheduler thread's line of the capture
    (line, _e), = cap.named("serving.batch")
    order = [list(g)[-1] for _k, g in itertools.groupby(
        (e for e in cap.lines[line]
         if e[0] in ("serving.form_batch", "serving.assemble",
                     "serving.batch", "serving.complete")),
        key=lambda e: e[0])][:4]
    assert [e[0] for e in order] == [
        "serving.form_batch", "serving.assemble", "serving.batch",
        "serving.complete"]
    for before, after in zip(order, order[1:]):
        assert before[2] <= after[1]
    # the retrospective spans they hold are in the ring, one a request
    names = _ring_names()
    assert names.count("serving.queue_wait") == 4
    assert names.count("serving.dispatch") == 4


def test_a_refreshed_span_shows_in_a_capture_begun_after_it(tmp_path):
    """A span entered before `start_trace` is not in the capture; one
    that refreshes its annotation is, from the refresh on, and the ring
    still holds it once (PERF.md 7 k: the scheduler's wait for work)."""
    waiting = obs.span("serving.form_batch", batch_size=0)
    silent = obs.span("serving.live")
    with waiting, silent:
        with Capture(tmp_path) as cap:
            time.sleep(0.002)
            waiting.refresh()
            time.sleep(0.002)
            waiting.refresh()
    pieces = cap.named("serving.form_batch")
    assert len(pieces) == 1             # the piece that ended in the capture
    assert cap.named("serving.live") == []
    assert sorted(_ring_names()) == ["serving.form_batch", "serving.live"]


def test_recorded_spans_stay_out_of_the_capture(tmp_path):
    with Capture(tmp_path) as cap:
        with obs.span("serving.live"):
            obs.record("serving.queue_wait", 0.25)
    assert [n for n, _e in cap.named("serving.queue_wait")] == []
    assert len(cap.named("serving.live")) == 1
    assert sorted(_ring_names()) == ["serving.live", "serving.queue_wait"]


def test_dropped_spans_are_counted(monkeypatch):
    monkeypatch.setattr(span_ring, "_spans", collections.deque(maxlen=8))
    for i in range(8):
        with obs.span(f"s{i}"):
            pass
    assert "trace.spans_dropped" not in obs.get_counters()
    for i in range(8, 11):
        with obs.span(f"s{i}"):
            pass
    obs.record("late", 0.001)
    obs.record("later", 0.001)
    assert obs.get_counters()["trace.spans_dropped"] == 5
    assert _ring_names() == [f"s{i}" for i in range(5, 11)] + ["late",
                                                              "later"]


def test_span_reports_its_own_seconds():
    with obs.span("a") as a:
        assert a.seconds is None
    assert a.seconds == pytest.approx(obs.get_spans()[-1]["dur"] / 1e6)
    obs.set_enabled(False)
    with obs.span("b") as b:
        pass
    assert b.seconds is None

    @obs.span("decorated")
    def f():
        return 3

    obs.set_enabled(True)
    assert f() == 3 and _ring_names()[-1] == "decorated"


def test_lowered_step_carries_op_type_scopes():
    exe, loss, feed = _fit_a_line()
    text = exe.lower(feed=feed, fetch_list=[loss]).as_text(debug_info=True)
    ops = {op.type for op in fluid.default_main_program().global_block.ops}
    scoped = {t for t in ops if f'"jit(train_step)/{t}/' in text}
    # forward, backward and optimizer ops alike; an op whose emitter adds
    # no instruction of its own (assign) leaves no location to carry one
    assert {"mul", "elementwise_add", "square_error_cost", "reduce_mean",
            "__vjp__", "sgd"} <= scoped, sorted(ops - scoped)
    # a generic grad op names the forward op it replays
    assert '"jit(train_step)/__vjp__/transpose(jvp(mul))/dot_general"' in text
