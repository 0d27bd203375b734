"""Serving subsystem tests: freeze parity, bucket padding, KV-cache decode
parity, warmup compile coverage, and graceful drain.

The small-classifier fixtures share one Scope/Executor per module so the
XLA compiles amortize across tests (the executor cache is keyed per
(program, feed-shapes, fetch-set) — exactly the digest the serving warmup
satellite is about)."""

import os
import signal
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observability
from paddle_tpu.framework.scope import Scope, scope_guard
from paddle_tpu.serving import (
    GPTGenerator,
    Server,
    freeze_program,
)
from paddle_tpu.serving.router import (
    Endpoint,
    EndpointConfig,
    ServerDrainingError,
)


# ---------------------------------------------------------------------------
# fixtures: a trained-ish tiny classifier, frozen
# ---------------------------------------------------------------------------


class _Classifier:
    def __init__(self):
        self.scope = Scope()
        self.main, self.startup = fluid.Program(), fluid.Program()
        self.main.random_seed = self.startup.random_seed = 7
        with fluid.program_guard(self.main, self.startup):
            x = fluid.data("x", [-1, 16])
            lab = fluid.data("lab", [-1, 1], "int64")
            h = layers.fc(x, 32, act="relu")
            logits = layers.fc(h, 4)
            self.prob = layers.softmax(logits)
            loss = layers.mean(
                layers.softmax_with_cross_entropy(logits, lab)
            )
            fluid.optimizer.Adam(1e-3).minimize(loss, self.startup)
        self.loss = loss
        self.exe = fluid.Executor()
        with scope_guard(self.scope):
            self.exe.run(self.startup, scope=self.scope)
            # a couple of real train steps so freeze sees trained state
            rng = np.random.RandomState(0)
            for _ in range(2):
                self.exe.run(
                    self.main,
                    feed={
                        "x": rng.randn(4, 16).astype(np.float32),
                        "lab": rng.randint(0, 4, (4, 1)).astype(np.int64),
                    },
                    fetch_list=[loss],
                    scope=self.scope,
                )
        self.frozen = freeze_program(
            self.main, [self.prob], feed_names=("x",)
        )


@pytest.fixture(scope="module")
def clf():
    return _Classifier()


# ---------------------------------------------------------------------------
# freeze
# ---------------------------------------------------------------------------


def test_freeze_drops_training_ops(clf):
    from paddle_tpu.analysis.structural import is_training_only_op

    ops = [op.type for op in clf.frozen.program.global_block.ops]
    assert not any(is_training_only_op(t) for t in ops), ops
    assert "softmax" in ops
    assert clf.frozen.meta["ops_pruned"] > 0
    assert clf.frozen.program._is_inference


def test_freeze_default_feeds_exclude_training_inputs(clf):
    """Without explicit feed_names the contract is the data vars the
    PRUNED graph reads — the label input must not survive into it (a
    router request would otherwise need a label array per submit)."""
    fm = freeze_program(clf.main, [clf.prob])
    assert fm.feed_names == ("x",), fm.feed_names


def test_generate_runner_rejects_mismatched_buckets():
    from paddle_tpu.errors import InvalidArgumentError
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.serving.generate import GPTGenerateRunner

    cfg = GPTConfig.tiny()
    cfg.use_fused_attention = False
    gen = GPTGenerator(cfg, batch=1, context_len=8, max_len=16)
    runner = GPTGenerateRunner(gen, max_new_tokens=4)
    with pytest.raises(InvalidArgumentError):
        Endpoint("gen", runner, EndpointConfig(buckets=(1, 2)))
    with pytest.raises(InvalidArgumentError):
        gen.generate(np.zeros((1, 8), np.int64), 0)


def test_freeze_parity_bitwise(clf):
    """Frozen outputs == clone(for_test=True) outputs, bitwise.

    The reference graph still CONTAINS the optimizer ops (fetch only
    selects outputs; the whole block executes), so it runs in a COPY of
    the scope — running it in clf.scope would silently train the shared
    fixture params (the exact hazard freeze_program removes)."""
    xa = np.random.RandomState(3).randn(4, 16).astype(np.float32)
    with scope_guard(clf.scope):
        (frozen_out,) = clf.exe.run(
            clf.frozen.program, feed={"x": xa},
            fetch_list=list(clf.frozen.fetch_names), scope=clf.scope,
        )
    ref_scope = Scope()
    for name in clf.scope.local_var_names():
        # host-copy: the reference run's optimizer ops DONATE their param
        # buffers; sharing arrays would invalidate clf.scope's copies
        ref_scope.set_var(
            name, np.array(np.asarray(clf.scope.find_var(name)))
        )
    test_prog = clf.main.clone(for_test=True)
    with scope_guard(ref_scope):
        (ref_out,) = clf.exe.run(
            test_prog,
            feed={"x": xa, "lab": np.zeros((4, 1), np.int64)},
            fetch_list=[clf.prob.name], scope=ref_scope,
        )
    np.testing.assert_array_equal(frozen_out, ref_out)


def test_freeze_strict_verify(clf):
    """A frozen program compiles under PADDLE_TPU_VERIFY=strict."""
    from paddle_tpu.analysis import set_verify_mode

    set_verify_mode("strict")
    try:
        scope = Scope()
        exe = fluid.Executor()
        with scope_guard(scope):
            exe.run(clf.startup, scope=scope)
            exe.run(
                clf.frozen.program,
                feed={"x": np.zeros((2, 16), np.float32)},
                fetch_list=list(clf.frozen.fetch_names), scope=scope,
            )
    finally:
        set_verify_mode(None)


def test_training_op_in_inference_finding():
    """The structural verifier flags training ops ONLY in programs marked
    as frozen inference graphs."""
    from paddle_tpu.analysis import verify_program
    from paddle_tpu.analysis.findings import TRAINING_OP_IN_INFERENCE

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 4])
        pred = layers.fc(x, 2)
        loss = layers.mean(pred)
        fluid.optimizer.SGD(0.1).minimize(loss, startup)
    report = verify_program(main, ("x",), (loss.name,))
    assert not report.by_category(TRAINING_OP_IN_INFERENCE)

    main._is_inference = True
    main._bump()  # invalidate the verify cache
    report = verify_program(main, ("x",), (loss.name,))
    found = report.by_category(TRAINING_OP_IN_INFERENCE)
    assert found and found[0].severity.name == "ERROR"
    assert any(f.op_type == "sgd" for f in found)


def test_freeze_refuses_training_fetch(clf):
    """Fetching a var produced by the optimizer keeps the update op in the
    slice; freeze must refuse, not silently serve a mutating graph."""
    from paddle_tpu.errors import ProgramVerifyError

    w = clf.main.global_block.all_parameters()[0]
    with pytest.raises(ProgramVerifyError):
        freeze_program(clf.main, [w.name], feed_names=("x", "lab"))


def test_freeze_int8_leg(clf):
    """int8_scales bakes fixed-scale qdq chains into the frozen graph and
    the graph still runs (outputs close to the fp32 freeze)."""
    xa = np.random.RandomState(5).randn(4, 16).astype(np.float32)
    with scope_guard(clf.scope):
        (ref,) = clf.exe.run(
            clf.frozen.program, feed={"x": xa},
            fetch_list=list(clf.frozen.fetch_names), scope=clf.scope,
        )
    # calibrated activation scales for every quantizable-op input
    scales = {}
    blk = clf.main.clone(for_test=True).global_block
    for op in blk.ops:
        if op.type in ("mul", "matmul"):
            for n in op.input_names():
                scales.setdefault(n, 4.0)
    fm8 = freeze_program(
        clf.main, [clf.prob], feed_names=("x",), int8_scales=scales
    )
    assert fm8.int8
    qdq = [
        op.type for op in fm8.program.global_block.ops
        if "quantize" in op.type
    ]
    assert qdq, "INT8 freeze inserted no quant-dequant ops"
    with scope_guard(clf.scope):
        (q_out,) = clf.exe.run(
            fm8.program, feed={"x": xa},
            fetch_list=list(fm8.fetch_names), scope=clf.scope,
        )
    np.testing.assert_allclose(q_out, ref, atol=0.15)


# ---------------------------------------------------------------------------
# router: bucketing, padding, warmup
# ---------------------------------------------------------------------------


def test_bucket_padding_row_correctness(clf):
    """Row b of a padded bucket run equals the same request served alone
    (the acceptance contract for zero-padding into buckets)."""
    server = Server()
    server.add_endpoint(
        "clf", None,
        EndpointConfig(buckets=(1, 2, 4), max_wait_ms=2.0),
        frozen=clf.frozen, executor=clf.exe, scope=clf.scope,
    )
    server.warmup()
    rng = np.random.RandomState(11)
    samples = [rng.randn(16).astype(np.float32) for _ in range(3)]
    futs = [server.submit("clf", {"x": s}) for s in samples]
    got = [f.result(timeout=10)[0] for f in futs]
    server.drain(timeout=5)
    for s, row in zip(samples, got):
        with scope_guard(clf.scope):
            (alone,) = clf.exe.run(
                clf.frozen.program, feed={"x": s[None]},
                fetch_list=list(clf.frozen.fetch_names), scope=clf.scope,
            )
        np.testing.assert_allclose(row, alone[0], rtol=1e-5, atol=1e-6)


def test_warmup_covers_every_bucket_and_fetch_set(clf):
    """Regression for the per-fetch-set executable digest: after warmup,
    NO latency-measured request may trace — a cold (bucket, fetch-set)
    pair would push a multi-second compile into a request."""
    server = Server()
    server.add_endpoint(
        "clf", None,
        EndpointConfig(buckets=(1, 2, 4, 8), max_wait_ms=1.0),
        frozen=clf.frozen, executor=clf.exe, scope=clf.scope,
    )
    server.warmup()
    c0 = observability.get_counters().get("executor.compile_count", 0)
    rng = np.random.RandomState(0)
    # hit every bucket size: 1, 2, 4, 8 and a padded 3->4
    for n in (1, 2, 3, 8):
        futs = [
            server.submit("clf", {"x": rng.randn(16).astype(np.float32)})
            for _ in range(n)
        ]
        for f in futs:
            f.result(timeout=10)
    c1 = observability.get_counters().get("executor.compile_count", 0)
    server.drain(timeout=5)
    assert c1 == c0, (
        f"{c1 - c0} compile(s) inside latency-measured requests — warmup "
        "missed a (bucket-shape, fetch-set) pair"
    )
    # negative control: the SAME bucket shape with a DIFFERENT fetch set
    # is a different executable digest (the bug the warmup must mirror)
    with scope_guard(clf.scope):
        clf.exe.run(
            clf.frozen.program,
            feed={"x": np.zeros((8, 16), np.float32)},
            fetch_list=[], scope=clf.scope,
        )
    c2 = observability.get_counters().get("executor.compile_count", 0)
    assert c2 == c1 + 1, "fetch-set change did not re-key the executable"


class _StubRunner:
    """Executor-free runner: doubles its input, optional per-batch delay.
    Lets the queue/batcher/drain machinery run without XLA in the loop."""

    feed_names = ("x",)

    def __init__(self, delay=0.0):
        self.delay = delay
        self.batches = []

    def sample_spec(self, name):
        return (2,), "float32"

    def run(self, feed):
        if self.delay:
            time.sleep(self.delay)
        self.batches.append(feed["x"].shape[0])
        return [feed["x"] * 2.0]


def test_router_continuous_batching_metrics():
    runner = _StubRunner()
    ep = Endpoint(
        "stub", runner, EndpointConfig(buckets=(2, 4), max_wait_ms=20.0)
    )
    futs = [
        ep.submit({"x": np.full(2, i, np.float32)}) for i in range(4)
    ]
    got = [f.result(timeout=5)[0] for f in futs]
    ep.drain(timeout=5)
    for i, row in enumerate(got):
        np.testing.assert_array_equal(row, np.full(2, 2.0 * i))
    c = observability.get_counters()
    assert c.get("serving.requests_served", 0) >= 4
    assert c.get("serving.batches", 0) >= 1
    h = observability.get_histograms()
    assert h["serving.request_latency"]["count"] >= 4
    assert h["serving.batch_fill"]["count"] >= 1


def test_router_rejects_on_full_queue():
    from paddle_tpu.errors import PreconditionNotMetError

    runner = _StubRunner(delay=0.2)
    ep = Endpoint(
        "tiny", runner,
        EndpointConfig(buckets=(1,), max_wait_ms=0.0, max_queue=2),
    )
    futs, rejected = [], 0
    for i in range(12):
        try:
            futs.append(ep.submit({"x": np.zeros(2, np.float32)}))
        except PreconditionNotMetError:
            rejected += 1
    assert rejected > 0, "queue bound never shed load"
    for f in futs:
        f.result(timeout=20)
    ep.drain(timeout=20)
    assert observability.get_counters().get("serving.rejected", 0) > 0


# ---------------------------------------------------------------------------
# KV-cache decode
# ---------------------------------------------------------------------------


def _np_ref_cache_attention(q, k, v, pos, nh, scale, prob_scale=1.0):
    """k, v: the caches in logical form, [B, S, H]."""
    b, t, h = q.shape
    s = k.shape[1]
    dh = h // nh
    qh = q.reshape(b, t, nh, dh).transpose(0, 2, 1, 3)
    kh = k.reshape(b, s, nh, dh).transpose(0, 2, 3, 1)
    scores = (qh @ kh) * scale
    qpos = pos - (t - 1) + np.arange(t)
    mask = np.arange(s)[None, None, None, :] <= qpos[None, None, :, None]
    scores = np.where(mask, scores, -1e9)
    e = np.exp(scores - scores.max(-1, keepdims=True))
    probs = e / e.sum(-1, keepdims=True) * prob_scale
    vh = v.reshape(b, s, nh, dh).transpose(0, 2, 1, 3)
    return (probs @ vh).transpose(0, 2, 1, 3).reshape(b, t, h)


_KV_B, _KV_S, _KV_NH, _KV_DH = 2, 8, 3, 4  # nh * dh = 12 != S


def to_logical(cache):
    """A stored cache as the [B, S, H] rows the layers wrote: what
    kv_cache.cache_shape stores ([B, slots, nh * dh]) IS those rows."""
    c = np.asarray(cache)
    assert c.ndim == 3
    return c


def _stored(logical):
    """[B, S, H] rows -> the stored layout (inverse of to_logical)."""
    from paddle_tpu.ops.kv_cache import cache_shape

    b, s, h = logical.shape
    assert logical.shape == cache_shape(b, s, _KV_NH, h // _KV_NH)
    return np.ascontiguousarray(logical)


@pytest.mark.parametrize("prob_scale", [1.0, 0.9])
@pytest.mark.parametrize(
    "t,pos",
    [(1, 0), (1, 3), (1, _KV_S - 1), (4, 3), (4, 6)],
    ids=["decode-first", "decode-middle", "decode-last-slot",
         "prefill-at-0", "prefill-offset"],
)
def test_kv_cache_op_goldens(t, pos, prob_scale):
    """Rows go in as the layer produces them ([B, T, H]) and land at
    slots Pos-(T-1)..Pos of the stored cache; attention over the stored
    caches equals the numpy reference over the logical ones."""
    import jax.numpy as jnp

    from paddle_tpu.framework.registry import OpView
    from paddle_tpu.ops.kv_cache import (_kv_cache_attention,
                                         _kv_cache_write, cache_shape)

    h = _KV_NH * _KV_DH
    rng = np.random.RandomState(7 * t + pos)
    k0, v0 = (rng.randn(_KV_B, _KV_S, h).astype(np.float32)
              for _ in range(2))
    krows, vrows, q = (rng.randn(_KV_B, t, h).astype(np.float32)
                       for _ in range(3))
    first = pos - (t - 1)

    def write(cache, rows):
        return _kv_cache_write(
            None, OpView("kv_cache_write", {}),
            {"Cache": [jnp.asarray(_stored(cache))],
             "X": [jnp.asarray(rows)], "Pos": [jnp.asarray([first])]},
        )["Out"][0]

    ck, cv = write(k0, krows), write(v0, vrows)
    want_k, want_v = k0.copy(), v0.copy()
    want_k[:, first:pos + 1, :] = krows
    want_v[:, first:pos + 1, :] = vrows
    assert ck.shape == cache_shape(_KV_B, _KV_S, _KV_NH, _KV_DH)
    np.testing.assert_array_equal(to_logical(ck), want_k)
    np.testing.assert_array_equal(to_logical(cv), want_v)

    attn = _kv_cache_attention(
        None,
        OpView("kv_cache_attention",
               {"num_heads": _KV_NH, "scale": 0.5,
                "prob_scale": prob_scale}),
        {"Q": [jnp.asarray(q)], "CacheK": [ck], "CacheV": [cv],
         "Pos": [jnp.asarray([pos])]},
    )["Out"][0]
    ref = _np_ref_cache_attention(
        q, want_k, want_v, pos, _KV_NH, 0.5, prob_scale
    )
    np.testing.assert_allclose(np.asarray(attn), ref, rtol=1e-5, atol=1e-6)


def test_kv_cache_write_then_attend_program_round_trip():
    """Both ops through a two-op Program, twice: the Executor donates the
    caches (``mutates`` aliases Out onto Cache) and writes them back, so
    the second run attends over what the first one stored."""
    from paddle_tpu.ops.kv_cache import cache_shape

    h = _KV_NH * _KV_DH
    shape = cache_shape(_KV_B, _KV_S, _KV_NH, _KV_DH)
    main = fluid.Program()
    with fluid.program_guard(main, fluid.Program()):
        q = fluid.data("q", [_KV_B, 1, h])
        rows = fluid.data("rows", [_KV_B, 1, h])
        pos = fluid.data("pos", [1], dtype="int32")
        blk = main.global_block
        for name in ("ck", "cv"):
            blk.create_var(name=name, shape=shape, dtype="float32",
                           persistable=True)
            blk.append_op(
                "kv_cache_write",
                {"Cache": [name], "X": [rows.name], "Pos": [pos.name]},
                {"Out": [name]},
            )
        out = blk.create_var(name="attn", shape=[_KV_B, 1, h],
                             dtype="float32")
        blk.append_op(
            "kv_cache_attention",
            {"Q": [q.name], "CacheK": ["ck"], "CacheV": ["cv"],
             "Pos": [pos.name]},
            {"Out": [out.name]},
            {"num_heads": _KV_NH, "scale": 0.5, "prob_scale": 1.0},
        )
    rng = np.random.RandomState(3)
    logical = np.zeros((_KV_B, _KV_S, h), np.float32)
    scope, exe = Scope(), fluid.Executor()
    with scope_guard(scope):
        for name in ("ck", "cv"):
            scope.set_var(name, np.zeros(shape, np.float32))
        for step in (2, 3):
            qv, rv = (rng.randn(_KV_B, 1, h).astype(np.float32)
                      for _ in range(2))
            logical[:, step:step + 1, :] = rv
            (got,) = exe.run(
                main,
                feed={"q": qv, "rows": rv,
                      "pos": np.array([step], np.int32)},
                fetch_list=[out.name], scope=scope,
            )
            ref = _np_ref_cache_attention(
                qv, logical, logical, step, _KV_NH, 0.5
            )
            np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(to_logical(scope.find_var("ck")), logical)
    np.testing.assert_array_equal(to_logical(scope.find_var("cv")), logical)


def _relayouts(jaxpr, min_elems):
    """(primitive, operand shape) of every transpose / copy / reshape in
    `jaxpr` (sub-jaxprs included) whose operand has >= min_elems elements."""
    found = []
    for eqn in jaxpr.eqns:
        for sub in eqn.params.values():
            inner = getattr(sub, "jaxpr", sub)
            if hasattr(inner, "eqns"):
                found += _relayouts(inner, min_elems)
        if eqn.primitive.name in ("transpose", "copy", "reshape"):
            shape = eqn.invars[0].aval.shape
            if int(np.prod(shape)) >= min_elems:
                found.append((eqn.primitive.name, tuple(shape)))
    return found


def _users_of(jaxpr, shape):
    """Primitives that take one of `jaxpr`'s inputs of `shape`, followed
    through a nested `jit` (the kernel's call has one of its own)."""
    taken = [v for v in jaxpr.invars if v.aval.shape == shape]
    users = set()
    for e in jaxpr.eqns:
        if any(v in taken for v in e.invars):
            users |= (_users_of(e.params["jaxpr"].jaxpr, shape)
                      if e.primitive.name == "jit" else {e.primitive.name})
    return users


def test_kv_cache_emitters_relayout_only_the_new_rows(monkeypatch):
    """The structural half of the layout contract, no chip needed: at a
    decode shape, on the path the TPU takes (traced, not compiled),
    neither emitter transposes, copies or reshapes anything of a cache's
    size (a cache is an entry parameter of the step, so any of these
    would be a whole-cache copy per layer per token): the rows go in as
    they are, and a cache goes straight into the Pallas call."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    import jax.numpy as jnp

    from paddle_tpu.framework.registry import OpView
    from paddle_tpu.ops.kv_cache import (_kv_cache_attention,
                                         _kv_cache_write, cache_shape)

    b, s, nh, dh = 2, 40, 3, 8
    h = nh * dh
    cache = jnp.zeros(cache_shape(b, s, nh, dh), jnp.float32)
    x = jnp.zeros((b, 1, h), jnp.float32)
    pos = jnp.asarray([5], jnp.int32)
    write = jax.make_jaxpr(lambda c, r, p: _kv_cache_write(
        None, OpView("kv_cache_write", {}),
        {"Cache": [c], "X": [r], "Pos": [p]})["Out"][0])(cache, x, pos)
    attend = jax.make_jaxpr(lambda q, k, v, p: _kv_cache_attention(
        None,
        OpView("kv_cache_attention",
               {"num_heads": nh, "scale": 0.5, "prob_scale": 0.9}),
        {"Q": [q], "CacheK": [k], "CacheV": [v], "Pos": [p]},
    )["Out"][0])(x, cache, cache, pos)
    for closed, reader in ((write, "dynamic_update_slice"),
                           (attend, "pallas_call")):
        assert _relayouts(closed.jaxpr, cache.size) == []
        small = _relayouts(closed.jaxpr, x.size)
        assert all(int(np.prod(sh)) == x.size for _, sh in small)
        # a cache goes straight from the step's parameters into the op
        # that reads it in place
        assert _users_of(closed.jaxpr, cache.shape) == {reader}


def test_kv_cache_shape_has_one_owner():
    """models/gpt.py's cache vars, GPTGenerator.reset's arrays and
    kv_cache.cache_shape agree, for nh * dh != max_len."""
    from paddle_tpu.models.gpt import GPTConfig, gpt_cache_names
    from paddle_tpu.ops.kv_cache import cache_shape

    cfg = GPTConfig.tiny()
    cfg.use_fused_attention = False
    batch, max_len = 3, 20
    nh, dh = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    assert nh * dh != max_len and dh != max_len
    want = cache_shape(batch, max_len, nh, dh)
    assert want == (batch, max_len, nh * dh)
    gen = GPTGenerator(cfg, batch=batch, context_len=6, max_len=max_len)
    gen.reset()
    names = gpt_cache_names(cfg)
    assert len(names) == 2 * cfg.num_layers
    for prog in (gen.prefill_prog, gen.decode_prog):
        for name in names:
            assert tuple(prog.global_block.var(name).shape) == want
    for name in names:
        arr = gen.scope.find_var(name)
        assert tuple(arr.shape) == want and arr.dtype == np.float32


def test_kv_decode_parity_with_full_recompute():
    """Cached generation matches full-context recompute token-for-token
    (and the cached path reuses ONE decode executable across steps)."""
    from paddle_tpu.models.gpt import GPTConfig

    cfg = GPTConfig.tiny()
    cfg.use_fused_attention = False
    gen = GPTGenerator(cfg, batch=2, context_len=12, max_len=24)
    gen.init_params(seed=11)
    rng = np.random.RandomState(0)
    ctx = rng.randint(0, cfg.vocab_size, size=(2, 12)).astype(np.int64)
    cached = gen.generate(ctx, 8)
    full = gen.generate_full_recompute(ctx, 8)
    np.testing.assert_array_equal(cached, full)
    c = observability.get_counters()
    assert c.get("serving.decode_steps", 0) >= 7
    # second generation must add zero compiles (shapes static)
    c0 = observability.get_counters().get("executor.compile_count", 0)
    cached2 = gen.generate(ctx, 8)
    np.testing.assert_array_equal(cached2, cached)
    c1 = observability.get_counters().get("executor.compile_count", 0)
    assert c1 == c0, "decode path recompiled despite static shapes"


# ---------------------------------------------------------------------------
# drain
# ---------------------------------------------------------------------------


def test_drain_completes_all_admitted_requests():
    """SIGTERM during load: every admitted request completes, late
    admissions are refused, serving.drained fires exactly once."""
    from paddle_tpu.serving import install_preemption_handler

    runner = _StubRunner(delay=0.01)
    server = Server()
    server.add_endpoint(
        "stub", runner, EndpointConfig(buckets=(4,), max_wait_ms=50.0)
    )
    old = install_preemption_handler(server, exit_on_drain=False)
    try:
        futs = [
            server.submit("stub", {"x": np.full(2, i, np.float32)})
            for i in range(30)
        ]
        os.kill(os.getpid(), signal.SIGTERM)
        assert server.wait_drained(timeout=30), "drain never completed"
        done = [f.result(timeout=5)[0] for f in futs]
        assert len(done) == 30
        for i, row in enumerate(done):
            np.testing.assert_array_equal(row, np.full(2, 2.0 * i))
        with pytest.raises(ServerDrainingError):
            server.submit("stub", {"x": np.zeros(2, np.float32)})
        c = observability.get_counters()
        assert c.get("serving.drained", 0) == 1
        assert c.get("serving.requests_served", 0) >= 30
    finally:
        signal.signal(signal.SIGTERM, old)


def test_ingest_fault_is_retried():
    """An injected fault on the ingestion seam is retried (the
    dataloader.fetch-style chaos contract): the request still serves."""
    from paddle_tpu.resilience import faults

    runner = _StubRunner()
    ep = Endpoint(
        "chaos", runner, EndpointConfig(buckets=(1,), max_wait_ms=0.0)
    )
    faults.inject("serving.ingest", "io", prob=1.0, seed=0, max_fires=2)
    futs = [
        ep.submit({"x": np.full(2, i, np.float32)}) for i in range(3)
    ]
    got = [f.result(timeout=5)[0] for f in futs]
    ep.drain(timeout=5)
    for i, row in enumerate(got):
        np.testing.assert_array_equal(row, np.full(2, 2.0 * i))
    c = observability.get_counters()
    assert c.get("resilience.faults_injected", 0) >= 2
    assert c.get("resilience.retries", 0) >= 2
    assert c.get("serving.requests", 0) == 3


@pytest.mark.slow
def test_drain_worker_exits_75():
    """Full preemption contract in a subprocess: SIGTERM during load ->
    all in-flight requests complete -> exit PREEMPTION_EXIT_CODE."""
    import json
    import subprocess
    import sys
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        proc = subprocess.Popen(
            [sys.executable,
             os.path.join(os.path.dirname(__file__),
                          "serving_drain_worker.py"),
             d],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        )
        ready = os.path.join(d, "ready")
        for _ in range(600):
            if os.path.exists(ready):
                break
            if proc.poll() is not None:
                raise AssertionError(
                    f"worker died early: {proc.stderr.read().decode()}"
                )
            time.sleep(0.1)
        else:
            proc.kill()
            raise AssertionError("worker never became ready")
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
        assert rc == 75, (
            f"expected PREEMPTION_EXIT_CODE 75, got {rc}: "
            f"{proc.stderr.read().decode()}"
        )
        with open(os.path.join(d, "result.json")) as f:
            result = json.load(f)
        assert result["dropped"] == 0, result
        # every admitted request RESOLVED: served, or typed expired/shed
        # for the deadline/priority slice (the r15 drain contract)
        assert (result["served"] + result["expired"] + result["shed"]
                == result["admitted"]), result
        assert result["served"] > 0, result
        assert result["drained_counter"] == 1, result
