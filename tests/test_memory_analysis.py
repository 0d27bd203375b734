"""Memory & liveness analysis family (analysis/memory.py).

Covers the static peak-HBM planner's live-interval accounting (buffer
reuse, liveness kills, feed pinning, sharded/pipeline/hot-tier byte
math), the donation verifier's broken fixtures (use-after-donate,
missed-donation, recompute-no-savings, oom-risk), the strict-mode
budget-gated compile, the ``Program.estimate`` integration, and the
serving warmup budget check. The estimate-vs-XLA calibration over the
zoo is the slow tail (``-m slow``; CI runs it in its own stage).
"""

import os
import warnings

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.analysis import (
    MISSED_DONATION,
    OOM_RISK,
    RECOMPUTE_NO_SAVINGS,
    USE_AFTER_DONATE,
    Severity,
    hbm_budget,
    plan_memory,
    set_verify_mode,
    verify_program,
)
from paddle_tpu.errors import PreconditionNotMetError, ProgramVerifyError
from paddle_tpu.framework import unique_name
from paddle_tpu.ops.kv_cache import cache_shape


@pytest.fixture(autouse=True)
def fresh():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    scope = fluid.framework.scope.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard():
        yield main, startup, scope
    set_verify_mode(None)
    os.environ.pop("PADDLE_TPU_HBM_BYTES", None)


def _cats(findings):
    return {f.category for f in findings}


F32 = 4  # bytes


# ---------------------------------------------------------------------------
# budget knob
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("raw,expect", [
    ("1024", 1024.0),
    ("2k", 2 * 2 ** 10),
    ("1.5m", 1.5 * 2 ** 20),
    ("16G", 16 * 2 ** 30),
    ("2T", 2 * 2 ** 40),
    ("junk", None),
    ("", None),
    ("-5", None),
    ("0", None),
])
def test_hbm_budget_parsing(monkeypatch, raw, expect):
    monkeypatch.setenv("PADDLE_TPU_HBM_BYTES", raw)
    assert hbm_budget() == expect


def test_hbm_budget_unset(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_HBM_BYTES", raising=False)
    assert hbm_budget() is None


# ---------------------------------------------------------------------------
# live-interval goldens
# ---------------------------------------------------------------------------


def test_elementwise_chain_reuses_buffers(fresh):
    """XLA writes an elementwise output over its dying input: a relu
    chain holds ONE activation buffer, not one per op."""
    main, _, _ = fresh
    x = fluid.data("x", [256, 1024])  # 1 MiB
    h = x
    for _ in range(4):
        h = layers.relu(h)
    mt = plan_memory(main, fetch_names=(h.name,))
    assert mt.transient_peak_bytes == 256 * 1024 * F32


def test_matmul_holds_inputs_and_output(fresh):
    """No reuse across a matmul: both operands stay live under the
    output (the MXU reads them while writing)."""
    main, _, _ = fresh
    x = fluid.data("x", [64, 64])
    a = layers.relu(x)          # 16 KiB transient
    b = layers.relu(x)          # 16 KiB transient
    y = layers.matmul(a, b)     # 16 KiB transient
    mt = plan_memory(main, fetch_names=(y.name,))
    assert mt.transient_peak_bytes == 3 * 64 * 64 * F32


def test_liveness_frees_dead_temps(fresh):
    """A temp dies at its last read; a deep matmul chain peaks at two
    live activations, not the whole chain."""
    main, _, _ = fresh
    x = fluid.data("x", [64, 64])
    h = x
    for _ in range(5):
        h = layers.matmul(h, h)
    mt = plan_memory(main, fetch_names=(h.name,))
    assert mt.transient_peak_bytes == 2 * 64 * 64 * F32


def test_resident_counts_each_referenced_persistable_once(fresh):
    main, _, _ = fresh
    x = fluid.data("x", [8, 32])
    h = layers.fc(x, 16)            # w [32,16] + b [16]
    h = layers.fc(h, 16)            # w [16,16] + b [16]
    mt = plan_memory(main, fetch_names=(h.name,))
    expect = (32 * 16 + 16 + 16 * 16 + 16) * F32
    assert mt.resident_bytes == expect
    assert sum(b for _, b in mt.residents) == expect


def test_unreferenced_persistable_costs_nothing(fresh):
    main, _, _ = fresh
    x = fluid.data("x", [8, 8])
    y = layers.relu(x)
    main.global_block.create_var(
        name="orphan_table", shape=[1024, 1024], dtype="float32",
        persistable=True,
    )
    mt = plan_memory(main, fetch_names=(y.name,))
    assert mt.resident_bytes == 0.0


def test_feed_shapes_pin_batch_dim(fresh):
    main, _, _ = fresh
    x = fluid.data("x", [-1, 8])
    y = layers.relu(x)
    pinned = plan_memory(main, fetch_names=(y.name,),
                         feed_shapes={"x": (32, 8)})
    assert pinned.feed_bytes == 32 * 8 * F32
    hinted = plan_memory(main, fetch_names=(y.name,))
    assert hinted.feed_bytes == 1 * 8 * F32  # batch hint 1
    assert any("pinned" in a for a in hinted.assumptions)


def test_watermark_names_the_source_line(fresh):
    main, _, _ = fresh
    x = fluid.data("x", [64, 64])
    y = layers.matmul(layers.relu(x), layers.relu(x))
    mt = plan_memory(main, fetch_names=(y.name,))
    assert mt.watermark is not None
    assert "test_memory_analysis.py" in (mt.watermark["loc"] or "")
    assert mt.watermark["live_bytes"] == mt.peak_bytes
    assert len(mt.timeline) > 0


def test_fetches_stay_live_to_the_end(fresh):
    """A fetched temp cannot be freed at its last in-graph read: the
    host still reads it after the step."""
    main, _, _ = fresh
    x = fluid.data("x", [64, 64])
    a = layers.relu(x)
    b = layers.relu(a)
    c = layers.relu(b)
    fetched = plan_memory(main, fetch_names=(a.name, c.name))
    unfetched = plan_memory(main, fetch_names=(c.name,))
    assert fetched.transient_peak_bytes > unfetched.transient_peak_bytes


# ---------------------------------------------------------------------------
# donation verifier
# ---------------------------------------------------------------------------


# rows [B=1, T=4, H=8] into a cache of 16 slots, 2 heads of 4
_KV_CACHE = list(cache_shape(batch=1, max_len=16, num_heads=2, head_dim=4))


def _kv_donation_program(main, read_after=True):
    rows = fluid.data("rows", [1, 4, 8])
    pos = fluid.data("pos", [1], dtype="int32")
    blk = main.global_block
    blk.create_var(name="cache", shape=_KV_CACHE, dtype="float32",
                   persistable=True)
    blk.create_var(name="cache_new", shape=_KV_CACHE, dtype="float32",
                   persistable=True)
    blk.append_op(
        "kv_cache_write",
        {"Cache": ["cache"], "X": [rows.name], "Pos": [pos.name]},
        {"Out": ["cache_new"]},
    )
    blk.create_var(name="reader", shape=_KV_CACHE, dtype="float32")
    src = "cache" if read_after else "cache_new"
    blk.append_op("scale", {"X": [src]}, {"Out": ["reader"]},
                  {"scale": 2.0})
    return ("rows", "pos"), ("reader",)


def test_use_after_donate_detected(fresh):
    main, _, _ = fresh
    feeds, fetches = _kv_donation_program(main, read_after=True)
    mt = plan_memory(main, feed_names=feeds, fetch_names=fetches)
    bad = [f for f in mt.findings if f.category == USE_AFTER_DONATE]
    assert len(bad) == 1
    f = bad[0]
    assert f.severity == Severity.ERROR
    assert "cache" in f.names
    assert "kv_cache_write" in f.message
    # the family is wired into the verifier proper
    report = verify_program(main, feeds, fetches)
    assert USE_AFTER_DONATE in _cats(report.findings)
    assert not report.ok


def test_reading_the_donated_output_is_clean(fresh):
    main, _, _ = fresh
    feeds, fetches = _kv_donation_program(main, read_after=False)
    mt = plan_memory(main, feed_names=feeds, fetch_names=fetches)
    assert USE_AFTER_DONATE not in _cats(mt.findings)


def test_same_name_cache_write_is_clean(fresh):
    """The zoo idiom — Out under the SAME name as Cache — is the
    executor's write-back donation, not a hazard."""
    main, _, _ = fresh
    rows = fluid.data("rows", [1, 4, 8])
    pos = fluid.data("pos", [1], dtype="int32")
    blk = main.global_block
    blk.create_var(name="cache", shape=_KV_CACHE, dtype="float32",
                   persistable=True)
    blk.append_op(
        "kv_cache_write",
        {"Cache": ["cache"], "X": [rows.name], "Pos": [pos.name]},
        {"Out": ["cache"]},
    )
    blk.create_var(name="reader", shape=_KV_CACHE, dtype="float32")
    blk.append_op("scale", {"X": ["cache"]}, {"Out": ["reader"]},
                  {"scale": 2.0})
    mt = plan_memory(main, feed_names=("rows", "pos"),
                     fetch_names=("reader",))
    assert USE_AFTER_DONATE not in _cats(mt.findings)


def test_rewritten_donated_name_is_a_fresh_buffer(fresh):
    """Writing the donated name again rebinds it to a live buffer; a
    read after the rewrite is fine."""
    main, _, _ = fresh
    feeds, _ = _kv_donation_program(main, read_after=False)
    blk = main.global_block
    blk.append_op(
        "fill_constant", {}, {"Out": ["cache"]},
        {"shape": _KV_CACHE, "dtype": "float32", "value": 0.0},
    )
    blk.create_var(name="reader2", shape=_KV_CACHE, dtype="float32")
    blk.append_op("scale", {"X": ["cache"]}, {"Out": ["reader2"]},
                  {"scale": 1.0})
    mt = plan_memory(main, feed_names=feeds, fetch_names=("reader2",))
    assert USE_AFTER_DONATE not in _cats(mt.findings)


def test_missed_donation_detected(fresh):
    main, _, _ = fresh
    blk = main.global_block
    blk.create_var(name="table", shape=[256, 256], dtype="float32",
                   persistable=True)  # 256 KiB: over the noise floor
    blk.create_var(name="table_scaled", shape=[256, 256], dtype="float32")
    blk.append_op("scale", {"X": ["table"]}, {"Out": ["table_scaled"]},
                  {"scale": 0.99})
    mt = plan_memory(main, feed_names=(), fetch_names=("table_scaled",))
    hits = [f for f in mt.findings if f.category == MISSED_DONATION]
    assert len(hits) == 1
    assert hits[0].severity == Severity.INFO
    assert set(hits[0].names) == {"table", "table_scaled"}


def test_small_buffers_skip_missed_donation(fresh):
    main, _, _ = fresh
    blk = main.global_block
    blk.create_var(name="lr", shape=[4, 4], dtype="float32",
                   persistable=True)
    blk.create_var(name="lr2", shape=[4, 4], dtype="float32")
    blk.append_op("scale", {"X": ["lr"]}, {"Out": ["lr2"]}, {"scale": 0.5})
    mt = plan_memory(main, feed_names=(), fetch_names=("lr2",))
    assert MISSED_DONATION not in _cats(mt.findings)


def test_optimizer_write_back_is_not_a_missed_donation(fresh):
    """sgd writes ParamOut under the Param name — the in-place update
    the executor already aliases."""
    main, _, _ = fresh
    x = fluid.data("x", [64, 64])
    loss = layers.mean(layers.fc(x, 64))
    fluid.optimizer.SGD(0.1).minimize(loss)
    mt = plan_memory(main, fetch_names=(loss.name,))
    assert MISSED_DONATION not in _cats(mt.findings)
    assert USE_AFTER_DONATE not in _cats(mt.findings)


# ---------------------------------------------------------------------------
# recompute
# ---------------------------------------------------------------------------


def test_recompute_without_backward_saves_nothing(fresh):
    from paddle_tpu.incubate.recompute import apply_recompute

    main, _, _ = fresh
    x = fluid.data("x", [8, 32])
    h = layers.relu(layers.fc(x, 32))
    out = layers.fc(h, 32)
    apply_recompute(main, [h.name])
    mt = plan_memory(main, fetch_names=(out.name,))
    hits = [f for f in mt.findings
            if f.category == RECOMPUTE_NO_SAVINGS]
    assert hits and hits[0].severity == Severity.INFO
    assert "forward-only" in hits[0].message


def test_recompute_with_backward_is_clean_and_charges_rematerialize(fresh):
    from paddle_tpu.incubate.recompute import apply_recompute

    main, _, _ = fresh
    x = fluid.data("x", [8, 32])
    h = layers.relu(layers.fc(x, 32))
    loss = layers.mean(layers.fc(h, 32))
    apply_recompute(main, [h.name])
    fluid.optimizer.SGD(0.1).minimize(loss)
    mt = plan_memory(main, fetch_names=(loss.name,))
    assert RECOMPUTE_NO_SAVINGS not in _cats(mt.findings)


# ---------------------------------------------------------------------------
# oom-risk + the budget-gated compile
# ---------------------------------------------------------------------------


def _mlp_program(main):
    x = fluid.data("x", [64, 256])
    h = layers.relu(layers.fc(x, 256))
    return ("x",), (layers.fc(h, 256).name,)


def test_oom_risk_fires_over_budget(fresh):
    main, _, _ = fresh
    feeds, fetches = _mlp_program(main)
    mt = plan_memory(main, feed_names=feeds, fetch_names=fetches,
                     budget=1024.0)
    hits = [f for f in mt.findings if f.category == OOM_RISK]
    assert len(hits) == 1
    f = hits[0]
    assert f.severity == Severity.WARNING
    assert "PADDLE_TPU_HBM_BYTES" in f.message
    assert f.loc and "test_memory_analysis.py" in f.loc  # watermark op
    assert mt.budget_bytes == 1024.0


def test_oom_risk_quiet_under_budget(fresh):
    main, _, _ = fresh
    feeds, fetches = _mlp_program(main)
    mt = plan_memory(main, feed_names=feeds, fetch_names=fetches,
                     budget=float(2 ** 30))
    assert OOM_RISK not in _cats(mt.findings)


def test_env_budget_reaches_the_verifier(fresh, monkeypatch):
    main, _, _ = fresh
    feeds, fetches = _mlp_program(main)
    monkeypatch.setenv("PADDLE_TPU_HBM_BYTES", "1k")
    report = verify_program(main, feeds, fetches)
    assert OOM_RISK in _cats(report.findings)
    # WARNING normally; an error only under strict escalation
    assert report.ok
    assert any(f.category == OOM_RISK for f in report.strict_errors())


def test_strict_mode_refuses_over_budget_compile(fresh, monkeypatch):
    """The acceptance gate: strict + tiny budget refuses the compile
    with a typed finding naming the watermark op's source line."""
    main, _, _ = fresh
    feeds, fetches = _mlp_program(main)
    monkeypatch.setenv("PADDLE_TPU_HBM_BYTES", "1k")
    set_verify_mode("strict")
    exe = fluid.Executor()
    with pytest.raises(ProgramVerifyError) as ei:
        exe.run(main, feed={"x": np.ones((64, 256), "float32")},
                fetch_list=[fetches[0]])
    msg = str(ei.value)
    assert "oom-risk" in msg
    assert "test_memory_analysis.py" in msg


def test_warn_mode_warns_and_still_runs(fresh, monkeypatch):
    main, startup, _ = fresh
    feeds, fetches = _mlp_program(main)
    monkeypatch.setenv("PADDLE_TPU_HBM_BYTES", "1k")
    set_verify_mode("warn")
    exe = fluid.Executor()
    exe.run(startup)
    with warnings.catch_warnings(record=True) as got:
        warnings.simplefilter("always")
        out, = exe.run(main, feed={"x": np.ones((64, 256), "float32")},
                       fetch_list=[fetches[0]])
    assert out.shape == (64, 256)
    assert any("oom-risk" in str(w.message) for w in got)


# ---------------------------------------------------------------------------
# sharding / pipeline / hot-tier byte math
# ---------------------------------------------------------------------------


def test_sharded_persistables_divide_by_axis_size(fresh):
    from paddle_tpu.parallel import make_mesh

    main, _, _ = fresh
    x = fluid.data("x", [8, 64])
    y = layers.fc(x, 64)  # w [64,64], b [64]
    base = plan_memory(main, fetch_names=(y.name,)).resident_bytes
    w = main.global_block.all_parameters()[0]
    main._mesh = make_mesh({"dp": 8})  # conftest's 8 virtual devices
    main._sharding = {w.name: (("dp",), None)}
    mt = plan_memory(main, fetch_names=(y.name,))
    # w drops to an eighth; the bias is unsharded
    assert mt.resident_bytes == base - (64 * 64 * F32) * 7 / 8


def test_pipeline_stage_peaks_reported(fresh):
    from paddle_tpu.parallel.pipeline import slice_program_into_stages

    main, _, _ = fresh
    x = fluid.data("x", [8, 64])
    with fluid.device_guard("pipeline:0"):
        h = layers.fc(x, 64)
    with fluid.device_guard("pipeline:1"):
        loss = layers.mean(layers.fc(h, 64))
    main._pipeline = {"num_microbatches": 2, "axis_name": "pp"}
    slice_program_into_stages(main, loss)
    mt = plan_memory(main, feed_names=("x",), fetch_names=(loss.name,))
    assert set(mt.stage_peaks) == {0, 1}
    assert all(v > 0 for v in mt.stage_peaks.values())


def test_hot_tier_shrink_drops_resident(fresh):
    """EmbeddingEngine rewrites cached tables' declared shapes to the
    hot-row count; the planner sees the shrunk table with no special
    case."""
    from paddle_tpu.embedding import EmbeddingEngine

    main, startup, _ = fresh
    ids = fluid.data("ids", [8, 1], "int64")
    emb = layers.sparse_embedding(ids, size=[4096, 16])
    loss = layers.mean(emb)
    table = main.global_block.all_parameters()[0]
    before = plan_memory(main, fetch_names=(loss.name,))
    assert dict(before.residents)[table.name] == 4096 * 16 * F32
    EmbeddingEngine(main, startup, hot_rows={table.name: 64})
    fluid.optimizer.SGD(0.1).minimize(loss)
    after = plan_memory(main, fetch_names=(loss.name,))
    assert dict(after.residents)[table.name] == 64 * 16 * F32


# ---------------------------------------------------------------------------
# estimate() integration + serving warmup budget
# ---------------------------------------------------------------------------


def test_estimate_carries_the_memory_plan(fresh):
    main, _, _ = fresh
    feeds, fetches = _mlp_program(main)
    est = main.estimate(feed_shapes={"x": (64, 256)})
    mt = plan_memory(main, feed_names=feeds, fetch_names=(),
                     feed_shapes={"x": (64, 256)}, budget=None)
    assert est.peak_bytes == mt.peak_bytes
    assert est.resident_bytes == mt.resident_bytes
    assert "static memory:" in est.format()
    d = est.to_dict()
    assert d["peak_bytes"] == mt.peak_bytes
    assert d["memory"]["watermark"] is not None


def _frozen_classifier(main, startup, scope):
    from paddle_tpu.serving import freeze_program

    x = fluid.data("x", [-1, 16])
    prob = layers.softmax(layers.fc(layers.fc(x, 32, act="relu"), 4))
    exe = fluid.Executor()
    exe.run(startup, scope=scope)
    return exe, freeze_program(main, [prob], feed_names=("x",))


def test_serving_warmup_respects_hbm_budget(fresh, monkeypatch):
    from paddle_tpu.serving import Server
    from paddle_tpu.serving.router import EndpointConfig

    main, startup, scope = fresh
    exe, frozen = _frozen_classifier(main, startup, scope)
    server = Server()
    ep = server.add_endpoint(
        "clf", None, EndpointConfig(buckets=(1, 4), max_wait_ms=1),
        frozen=frozen, executor=exe, scope=scope,
    )
    try:
        plan = ep.plan_memory()
        assert plan["planned_peak_bytes"] > plan["resident_bytes"] > 0
        assert plan["per_bucket_dynamic_bytes"][4] > \
            plan["per_bucket_dynamic_bytes"][1]
        monkeypatch.setenv("PADDLE_TPU_HBM_BYTES", "1k")
        with pytest.raises(PreconditionNotMetError, match="HBM budget"):
            server.warmup()
        monkeypatch.setenv("PADDLE_TPU_HBM_BYTES", "1g")
        assert server.warmup() >= 1  # fits: warmup actually compiles
    finally:
        for e in server.endpoints().values():
            e.drain(timeout=10)


# ---------------------------------------------------------------------------
# the zoo: clean bill + estimate-vs-XLA calibration (slow tail)
# ---------------------------------------------------------------------------


def test_small_zoo_models_are_memory_clean(fresh):
    from paddle_tpu.models import build_model

    for name in ("deepfm", "gpt"):
        bm = build_model(name)
        mt = plan_memory(bm.main, feed_names=bm.feed_names or None,
                         fetch_names=bm.fetch_names)
        assert not mt.findings, (name, [f.format() for f in mt.findings])
        assert mt.peak_bytes > mt.resident_bytes > 0


@pytest.mark.slow
def test_zoo_estimate_vs_xla_memory(fresh):
    """Static peak within 25% of XLA memory_analysis (arg+out+temp-alias)
    on all but <=2 of the XLA-checkable zoo models."""
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.models import MODEL_BUILDERS, build_model

    divergent, checked = [], 0
    for name in MODEL_BUILDERS:
        bm = build_model(name)
        mt = plan_memory(bm.main, feed_names=bm.feed_names or None,
                         fetch_names=bm.fetch_names)
        assert not mt.findings, (  # clean bill across the whole zoo
            name, [f.format() for f in mt.findings])
        if getattr(bm.main, "_mesh", None) is not None:
            continue  # shard_map wants the whole virtual pod
        est = bm.main.estimate()
        exe = fluid.Executor()
        scope = Scope()
        exe.run(bm.startup, scope=scope)
        feed = {}
        blk = bm.main.global_block
        for fn in bm.feed_names:
            v = blk._find_var_recursive(fn)
            shape = [d if d not in (-1, None) else 4 for d in v.shape]
            feed[fn] = np.zeros(shape, np.dtype(v.dtype or "float32"))
        ma = exe.memory_analysis(bm.main, feed=feed,
                                 fetch_list=list(bm.fetch_names),
                                 scope=scope)
        if ma is None:
            continue  # backend without memory_analysis: counted, not failed
        checked += 1
        div = abs(est.peak_bytes - ma["peak_bytes"]) / ma["peak_bytes"]
        if div > 0.25:
            divergent.append((name, round(div, 3)))
    assert checked >= 5, f"only {checked} models were XLA-checkable"
    assert len(divergent) <= 2, divergent
