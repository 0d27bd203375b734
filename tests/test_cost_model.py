"""Per-op cost attribution (paddle_tpu/analysis/cost.py): closed-form
goldens for the core op families, `Program.estimate()` against XLA's own
cost_analysis, the executor's live perf.* telemetry, and the
tools/perf_report.py multi-rank timeline merge."""

import importlib.util
import json
import os
import types

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observability
from paddle_tpu.analysis import estimate_program, family_of, op_cost
from paddle_tpu.analysis.cost import (
    DEFAULT_PEAK_GBPS,
    DEFAULT_PEAK_TFLOPS,
    peak_flops,
)
from paddle_tpu.errors import CostAnalysisUnavailableWarning
from paddle_tpu.framework import unique_name
from paddle_tpu.framework.registry import OpView
from paddle_tpu.framework.scope import Scope

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_ROOT, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def fresh():
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    scope = Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard():
        yield main, startup, scope


def _f32(shape):
    return (tuple(shape), 4)


# ---------------------------------------------------------------------------
# per-op goldens (op_cost on synthetic specs)
# ---------------------------------------------------------------------------


class TestOpGoldens:
    def test_matmul(self):
        op = OpView("mul", {"x_num_col_dims": 1})
        flops, nbytes = op_cost(
            op,
            {"X": [_f32((8, 16))], "Y": [_f32((16, 32))]},
            {"Out": [_f32((8, 32))]},
        )
        assert flops == 2 * 8 * 32 * 16
        assert nbytes == (8 * 16 + 16 * 32 + 8 * 32) * 4

    def test_matmul_transpose_x(self):
        # x [K, M] with transpose_X: contraction length is x's second-last
        op = OpView("matmul", {"transpose_X": True})
        flops, _ = op_cost(
            op,
            {"X": [_f32((16, 8))], "Y": [_f32((16, 32))]},
            {"Out": [_f32((8, 32))]},
        )
        assert flops == 2 * 8 * 32 * 16

    def test_conv_no_padding(self):
        # 8x8 VALID 3x3: every output tap lands on real input
        op = OpView("conv2d", {"paddings": [0, 0], "strides": [1, 1]})
        flops, _ = op_cost(
            op,
            {"Input": [_f32((2, 3, 8, 8))], "Filter": [_f32((4, 3, 3, 3))]},
            {"Output": [_f32((2, 4, 6, 6))]},
        )
        assert flops == 2 * (2 * 4 * 6 * 6) * (3 * 3 * 3)

    def test_conv_padding_discounts_dead_taps(self):
        # SAME 3x3 on 4x4: border taps land in padding and must not count
        full = 2 * (2 * 4 * 4 * 4) * (3 * 3 * 3)
        op = OpView("conv2d", {"paddings": [1, 1], "strides": [1, 1]})
        flops, _ = op_cost(
            op,
            {"Input": [_f32((2, 3, 4, 4))], "Filter": [_f32((4, 3, 3, 3))]},
            {"Output": [_f32((2, 4, 4, 4))]},
        )
        assert 0 < flops < full
        # separable taps: per dim 3*4 - 2 dead columns = 10 of 12
        assert flops == pytest.approx(full * (10 / 12) ** 2)

    def test_attention_fwd_and_grad(self):
        qkv = {"QKV": [_f32((2, 16, 3 * 32))]}
        fwd, _ = op_cost(OpView("fused_qkv_attention", {}), qkv, {})
        assert fwd == 4.0 * 2 * 16 * 16 * 32
        causal, _ = op_cost(
            OpView("fused_qkv_attention", {"causal": True}), qkv, {}
        )
        assert causal == fwd / 2
        bwd, _ = op_cost(OpView("fused_qkv_attention_grad", {}), qkv, {})
        assert bwd == 2.5 * fwd

    def test_elementwise_weights(self):
        flops, _ = op_cost(OpView("relu", {}), {}, {"Out": [_f32((4, 4))]})
        assert flops == 16
        flops, _ = op_cost(OpView("gelu", {}), {}, {"Out": [_f32((4, 4))]})
        assert flops == 8 * 16

    def test_data_movement_zero_flops(self):
        flops, nbytes = op_cost(
            OpView("reshape2", {}),
            {"X": [_f32((4, 4))]}, {"Out": [_f32((16,))]},
        )
        assert flops == 0.0
        assert nbytes == 2 * 16 * 4

    def test_reduce_is_one_pass_over_input(self):
        flops, _ = op_cost(
            OpView("reduce_sum", {}),
            {"X": [_f32((8, 32))]}, {"Out": [_f32((8,))]},
        )
        assert flops == 8 * 32

    def test_optimizer_per_param_weight(self):
        flops, _ = op_cost(
            OpView("adam", {}), {"Param": [_f32((100,))]}, {}
        )
        assert flops == 12.0 * 100

    def test_collective_ring_payload(self):
        specs = {"X": [_f32((1024,))]}
        op = OpView("c_allreduce_sum", {"axis_name": "dp"})
        flops, wire = op_cost(op, specs, {}, axis_sizes={"dp": 4})
        assert wire == pytest.approx(1024 * 4 * 2 * 3 / 4)
        assert flops == 1024
        # unbound axis degrades to identity: no wire traffic, no flops
        assert op_cost(op, specs, {}, axis_sizes={}) == (0.0, 0.0)
        _, ag = op_cost(
            OpView("c_allgather", {"axis_name": "dp"}), specs, {},
            axis_sizes={"dp": 4},
        )
        assert ag == pytest.approx(1024 * 4 * 3 / 4)

    def test_gather_moves_rows_not_the_table(self):
        # lookup over a 1M x 8 table: only the gathered rows (~output
        # sized) count as bytes moved, never the whole table
        table = _f32((1_000_000, 8))
        ids = ((64, 1), 8)  # int64 ids
        out = _f32((64, 8))
        flops, nbytes = op_cost(
            OpView("lookup_table_v2", {}),
            {"W": [table], "Ids": [ids]}, {"Out": [out]},
        )
        assert flops == 0.0
        assert nbytes == (
            64 * 8  # ids
            + 2 * 64 * 8 * 4  # rows read from the table + output written
        )
        # non-table data movement is unchanged
        _, plain = op_cost(
            OpView("concat", {}), {"X": [_f32((4, 4))]},
            {"Out": [_f32((4, 4))]},
        )
        assert plain == 2 * 16 * 4

    def test_zero_sharded_collective_wire_bytes(self):
        """Sharded weight-update collectives: wire bytes follow the PADDED
        flat payload at the quantized element size, with reduce-scatter
        and all-gather each moving (n-1)/n of it."""
        n, pad, block = 4, 4096, 256
        grad = _f32((60, 64))  # 3840 elements, padded to 4096
        rs = OpView("zero_reduce_scatter", {
            "axis_name": "dp", "pad_len": pad, "quant": "none",
            "quant_block": block, "scale": 0.25,
        })
        flops, wire = op_cost(rs, {"X": [grad]}, {}, axis_sizes={"dp": n})
        assert wire == pytest.approx(pad * 4 * (n - 1) / n)
        assert flops == pad  # n contributions summed per element
        # int8 blocks: 1 byte/elem + 4-byte fp32 scale per block
        rs_q = OpView("zero_reduce_scatter", {
            "axis_name": "dp", "pad_len": pad, "quant": "int8",
            "quant_block": block,
        })
        _, wire_q = op_cost(rs_q, {"X": [grad]}, {}, axis_sizes={"dp": n})
        assert wire_q == pytest.approx(
            pad * (1 + 4 / block) * (n - 1) / n
        )
        assert wire_q < 0.3 * wire  # the >=40% payload-reduction headline
        ag = OpView("zero_all_gather", {
            "axis_name": "dp", "pad_len": pad, "quant": "none",
            "shape": [60, 64],
        })
        shard = _f32((pad,))
        ag_flops, ag_wire = op_cost(
            ag, {"X": [shard]}, {}, axis_sizes={"dp": n}
        )
        assert ag_flops == 0.0
        assert ag_wire == pytest.approx(pad * 4 * (n - 1) / n)
        # unbound axis: identity degrade, no wire traffic
        assert op_cost(rs, {"X": [grad]}, {}, axis_sizes={}) == (0.0, 0.0)
        # found-inf any-reduce is a [1]-element allreduce
        anyop = OpView("c_allreduce_any", {"axis_name": "dp"})
        _, any_wire = op_cost(
            anyop, {"X": [((1,), 1)]}, {}, axis_sizes={"dp": n}
        )
        assert any_wire == pytest.approx(1 * 2 * (n - 1) / n)

    def test_zero_collectives_in_program_estimate(self, fresh):
        """A ShardedWeightUpdate-transpiled program's estimate carries the
        new collective sites with quantized wire bytes smaller than the
        fp32 build's."""
        import jax

        from paddle_tpu.parallel import make_mesh, shard_program
        from paddle_tpu.parallel.transpiler import ShardedWeightUpdate

        def build(quant):
            main, startup = fluid.Program(), fluid.Program()
            scope = Scope()
            with fluid.program_guard(main, startup), \
                    fluid.scope_guard(scope), unique_name.guard():
                # a 512x64 weight: big enough that int8 padding overhead
                # cannot mask the 4x element shrink
                x = fluid.data("x", [8, 512])
                loss = layers.mean(layers.square(layers.fc(x, 64)))
                _, pg = fluid.optimizer.Adam(0.01).minimize(loss, startup)
                ShardedWeightUpdate(2, quant=quant).transpile(
                    main, startup, pg
                )
                shard_program(
                    main, make_mesh({"dp": 2}, jax.devices()[:2]),
                    {"x": ("dp",)},
                )
            return main.estimate(feed_shapes={"x": (8, 512)})

        est_fp = build(None)
        est_q = build("int8")
        kinds_fp = {e.op_type for e in est_fp.ops}
        assert {"zero_reduce_scatter", "zero_all_gather"} <= kinds_fp

        def coll_bytes(est):
            return sum(
                e.bytes for e in est.ops
                if e.op_type in ("zero_reduce_scatter", "zero_all_gather")
            )

        assert coll_bytes(est_q) < 0.6 * coll_bytes(est_fp)

    def test_family_of(self):
        assert family_of("matmul") == "matmul"
        assert family_of("conv2d") == "conv"
        assert family_of("ring_attention") == "attention"
        assert family_of("layer_norm") == "normalization"
        assert family_of("lookup_table_v2") == "embedding"
        assert family_of("adam") == "optimizer"
        assert family_of("c_allreduce_sum") == "collective"
        assert family_of("reshape2") == "data_movement"
        assert family_of("relu") == "elementwise"

    def test_recorded_grad_family_strips_suffix(self):
        """_record resolves the family from the FORWARD op type for every
        synthesized *_grad entry — incl. bases like ring_attention whose
        _grad form is not itself a registered attention op."""
        from paddle_tpu.analysis.cost import CostTable, _Estimator

        table = CostTable(peak_flops=1e12, peak_bandwidth=1e11)
        est = _Estimator.__new__(_Estimator)
        est.table = table
        for t, fam in (("ring_attention_grad", "attention"),
                       ("conv2d_grad", "conv"),
                       ("layer_norm_grad", "normalization")):
            est._record(None, t, 1.0, 1.0, 1, 0, 0, loc="")
            assert table.ops[-1].family == fam, t

    def test_fused_lookup_unique_row_gather_bytes(self):
        """fused_lookup_table forward: ids + outputs + the UNIQUE-row
        gather — bounded by min(total ids, total table rows), never the
        whole table, never one row per occurrence."""
        v, d, b = 1_000_000, 8, 64
        tables = [_f32((v, d))] * 4
        ids = [((b, 1), 8)] * 4  # 4 slots of int64 [64, 1] ids
        outs = [_f32((b, d))] * 4
        op = OpView("fused_lookup_table", {"axis_name": "ps"})
        flops, nbytes = op_cost(
            op, {"Ids": ids, "W": tables}, {"Out": outs}
        )
        assert flops == 0.0
        total_ids = 4 * b
        assert nbytes == (
            total_ids * 8          # ids read
            + total_ids * d * 4    # outputs written
            + total_ids * d * 4    # unique-row gather (<= total ids rows)
        )
        # a table smaller than the batch bounds the gather by its rows
        tiny = [_f32((16, d))]
        _, small = op_cost(
            OpView("fused_lookup_table", {}),
            {"Ids": [((b,), 8)], "W": tiny}, {"Out": [_f32((b, d))]},
        )
        assert small == b * 8 + b * d * 4 + 16 * d * 4
        # dedup=False: the legacy per-occurrence gather (output-sized)
        _, nodedup = op_cost(
            OpView("fused_lookup_table", {"dedup": False}),
            {"Ids": [((b,), 8)], "W": tiny}, {"Out": [_f32((b, d))]},
        )
        assert nodedup == b * 8 + 2 * b * d * 4

    def test_fused_lookup_sharded_exchange_wire(self):
        """Row partition adds the psum row-assembly wire; the backward
        segment-sum (via __vjp__) adds the grad exchange at the quantized
        element size when int8 is opted in."""
        from paddle_tpu.analysis.cost import _lookup_grad_cost

        v, d, b, n = 4096, 16, 32, 8
        ins = {"Ids": [((b,), 8)], "W": [_f32((v, d))]}
        outs = {"Out": [_f32((b, d))]}
        base_op = OpView("fused_lookup_table", {"axis_name": "ps"})
        _, local = op_cost(base_op, ins, outs, axis_sizes={})
        _, sharded = op_cost(base_op, ins, outs, axis_sizes={"ps": n})
        assert sharded - local == pytest.approx(
            b * d * 4 * 2 * (n - 1) / n
        )
        # backward: fp32 grad exchange vs int8 block-quantized wire
        g_flops, g_fp32 = _lookup_grad_cost(
            base_op, ins, outs, {"ps": n}
        )
        assert g_flops >= b * d  # segment-sum adds + shard accumulation
        q_op = OpView("fused_lookup_table", {
            "axis_name": "ps", "quant": "int8", "quant_block": 256,
        })
        _, g_int8 = _lookup_grad_cost(q_op, ins, outs, {"ps": n})
        fixed = 2 * b * d * 4 + b * d * 4  # segment-sum local traffic
        assert (g_int8 - fixed) < 0.3 * (g_fp32 - fixed)
        # col partition: all-gather forward, no quantized grad exchange
        col_op = OpView("fused_lookup_table", {
            "axis_name": "ps", "partition": "col",
        })
        _, col = op_cost(col_op, ins, outs, axis_sizes={"ps": n})
        assert col - local == pytest.approx(b * d * 4 * (n - 1) / n)

    def test_fused_lookup_family_is_embedding(self):
        assert family_of("fused_lookup_table") == "embedding"
        assert family_of("distributed_lookup_table") == "embedding"


# ---------------------------------------------------------------------------
# Program.estimate()
# ---------------------------------------------------------------------------


def _fc_train(main, startup):
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 16])
        h = layers.fc(x, 32, act="relu")
        loss = layers.mean(h)
        fluid.optimizer.SGD(0.1).minimize(loss, startup)
    return loss


class TestProgramEstimate:
    def test_feed_shapes_pin_batch(self, fresh):
        main, startup, _ = fresh
        _fc_train(main, startup)
        est8 = main.estimate(feed_shapes={"x": (8, 16)})
        est16 = main.estimate(feed_shapes={"x": (16, 16)})
        assert est16.total_flops > est8.total_flops
        # every -1 pin is recorded, never silent
        assert any("batch hint 8" in a for a in est8.assumptions)
        # no feed: batch hint falls back to 1
        assert any("batch hint 1" in a for a in main.estimate().assumptions)

    def test_cond_branch_costed_and_pins_surfaced(self, fresh):
        main, startup, _ = fresh
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [-1, 16])
            p = fluid.data("p", [1], "float32")
            pred = layers.greater_than(
                p, layers.fill_constant([1], "float32", 0.0)
            )
            layers.cond(pred, lambda: layers.fc(x, 32),
                        lambda: layers.fc(x, 32))
        est = main.estimate()
        # the charged branch's ops land in the table...
        assert any(e.op_type == "mul" for e in est.ops)
        # ...and -1 pins made INSIDE the branch are still recorded
        assert any("pinned" in a for a in est.assumptions)

    def test_grad_ops_attributed_to_forward_family(self, fresh):
        main, startup, _ = fresh
        _fc_train(main, startup)
        est = main.estimate(feed_shapes={"x": (8, 16)})
        types = {e.op_type for e in est.ops}
        assert {"mul", "mul_grad", "relu_grad", "sgd"} <= types
        grad = next(e for e in est.ops if e.op_type == "mul_grad")
        fwd = next(e for e in est.ops if e.op_type == "mul")
        # first-layer mul: x is a feed, so only dW is wanted — one
        # forward-sized contraction, not two
        assert grad.flops == fwd.flops
        assert grad.family == "matmul"
        fams = est.by_family()
        assert fams["matmul"]["flops"] == pytest.approx(2 * fwd.flops)

    def test_table_views_and_serialization(self, fresh):
        main, startup, _ = fresh
        _fc_train(main, startup)
        est = main.estimate(feed_shapes={"x": (8, 16)})
        top = est.top(3)
        assert len(top) == 3
        assert top[0].latency == max(e.latency for e in est.ops)
        d = est.to_dict(top=5)
        assert d["total_flops"] == est.total_flops
        assert len(d["ops"]) == 5
        json.dumps(d)  # must be a plain-JSON artifact (set_table contract)
        text = est.format(top=2)
        assert "by family" in text and "top 2 op sites" in text
        assert est.mfu_at(1.0) == pytest.approx(
            est.total_flops / est.peak_flops
        )
        assert est.mfu_at(0.0) == 0.0

    def test_peak_env_overrides(self, fresh, monkeypatch):
        main, startup, _ = fresh
        _fc_train(main, startup)
        monkeypatch.setenv("PADDLE_TPU_PEAK_TFLOPS", "100")
        monkeypatch.setenv("PADDLE_TPU_PEAK_GBPS", "500")
        est = main.estimate(feed_shapes={"x": (8, 16)})
        assert est.peak_flops == 100e12
        assert est.peak_bandwidth == 500e9
        monkeypatch.setenv("PADDLE_TPU_PEAK_TFLOPS", "not-a-number")
        assert peak_flops() == DEFAULT_PEAK_TFLOPS * 1e12
        # explicit args beat the env
        est = main.estimate(feed_shapes={"x": (8, 16)}, peak_tflops=1.0,
                            peak_gbps=DEFAULT_PEAK_GBPS)
        assert est.peak_flops == 1e12

    def test_bounded_while_counts_static_trips(self, fresh):
        main, startup, _ = fresh
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [4, 8])
            i = layers.fill_constant([1], "int32", 0)
            n = layers.fill_constant([1], "int32", 5)
            acc = layers.fill_constant([4, 8], "float32", 0.0)
            cond = layers.less_than(i, n)
            w = layers.While(cond, max_iters=5)
            with w.block():
                layers.assign(layers.elementwise_add(acc, x), acc)
                layers.increment(i)
                layers.assign(layers.less_than(i, n), cond)
        est = main.estimate()
        adds = [e for e in est.ops if e.op_type == "elementwise_add"]
        # the body's add is charged once per static trip (max_iters),
        # not once total, and no trip-count assumption is emitted
        assert adds and all(e.count == 5 for e in adds)
        assert not any("counted once" in a for a in est.assumptions)

    def test_estimate_matches_xla_on_small_program(self, fresh):
        main, startup, scope = fresh
        loss = _fc_train(main, startup)
        exe = fluid.Executor()
        exe.run(startup, scope=scope)
        feed = {"x": np.ones((8, 16), "float32")}
        exe.run(main, feed=feed, fetch_list=[loss.name], scope=scope)
        xla = exe.flops(
            main, feed=feed, fetch_list=[loss.name], scope=scope
        )
        est = main.estimate(feed_shapes={"x": (8, 16)})
        assert xla > 0
        assert abs(est.total_flops - xla) / xla < 0.25


@pytest.mark.slow
@pytest.mark.parametrize(
    "name",
    sorted(__import__("paddle_tpu.models",
                      fromlist=["MODEL_BUILDERS"]).MODEL_BUILDERS),
)
def test_zoo_estimate_vs_xla(name):
    """`Program.estimate()` within 25% of XLA cost_analysis for every
    bundled model (meshed models are estimate-only: their shard_map
    executable wants the whole virtual pod). Mirrors the ci.sh
    perf_report stage so a regression fails in pytest too."""
    from paddle_tpu.models import build_model

    perf_report = _load_tool("perf_report")

    bm = build_model(name)
    feed = perf_report._synthetic_feed(bm)
    est = bm.main.estimate(
        feed_shapes={k: v.shape for k, v in feed.items()}
    )
    assert est.total_flops > 0
    assert est.ops
    if getattr(bm.main, "_mesh", None) is not None:
        return
    exe = fluid.Executor()
    scope = Scope()
    exe.run(bm.startup, scope=scope)
    xla = exe.flops(
        bm.main, feed=feed, fetch_list=list(bm.fetch_names), scope=scope
    )
    if not xla:
        pytest.skip("XLA cost_analysis reported no FLOP data")
    assert abs(est.total_flops - xla) / xla <= 0.25


# ---------------------------------------------------------------------------
# the tables kind, and the two "XLA returned nothing" counters
# ---------------------------------------------------------------------------


class TestPerfTelemetry:
    def test_tables_reset_and_snapshot_backcompat(self):
        observability.reset()
        assert "tables" not in observability.snapshot()  # nothing published
        observability.set_table("some.table", {"total_flops": 1.0})
        assert observability.get_tables() == {
            "some.table": {"total_flops": 1.0}
        }
        observability.reset()
        assert observability.get_tables() == {}

    def test_cost_analysis_unavailable_is_loud(self, fresh):
        main, startup, scope = fresh
        loss = _fc_train(main, startup)
        exe = fluid.Executor()
        exe.run(startup, scope=scope)
        feed = {"x": np.ones((8, 16), "float32")}
        exe.run(main, feed=feed, fetch_list=[loss.name], scope=scope)
        # the cache holds startup's executable too; main's was used last
        compiled = list(exe._cache.values())[-1]

        class _NoCost:
            def compile(self):
                return self

            def cost_analysis(self):
                return None

        compiled.fn = types.SimpleNamespace(
            lower=lambda *a, **k: _NoCost()
        )
        observability.reset()
        with pytest.warns(CostAnalysisUnavailableWarning):
            val = exe.flops(
                main, feed=feed, fetch_list=[loss.name], scope=scope
            )
        assert val == 0.0
        snap = observability.snapshot()
        assert snap["counters"]["perf.cost_analysis_unavailable"] == 1


# ---------------------------------------------------------------------------
# multi-rank timeline merge (tools/perf_report.py)
# ---------------------------------------------------------------------------


def _rank_trace(steps):
    """Synthetic chrome trace: one executor.step X event per (ts, dur)."""
    events = [{
        "name": "thread_name", "ph": "M", "tid": 0, "pid": 0,
        "args": {"name": "thread-0"},
    }]
    for ts, dur in steps:
        events.append({
            "name": "executor.step", "ph": "X", "cat": "host",
            "ts": ts, "dur": dur, "tid": 0, "pid": 0, "args": {},
        })
    return {"traceEvents": events}


class TestTimelineMerge:
    def test_two_rank_merge_skew_and_straggler(self, tmp_path):
        perf_report = _load_tool("perf_report")
        # rank 0 ends steps at 1500/3500 us; rank 1 at 1700/3900:
        # skews 200 and 400 -> mean 300, max 400, straggler rank 1
        p0 = tmp_path / "trace_rank0.json"
        p1 = tmp_path / "trace_rank1.json"
        p0.write_text(json.dumps(_rank_trace([(1000, 500), (3000, 500)])))
        p1.write_text(json.dumps(_rank_trace([(1100, 600), (3200, 700)])))
        trace, stats = perf_report.merge_traces([str(p0), str(p1)])
        assert {e.get("pid") for e in trace["traceEvents"]} == {0, 1}
        steps = [
            e for e in trace["traceEvents"]
            if e.get("ph") == "X" and e["name"] == "executor.step"
        ]
        assert len(steps) == 4
        assert stats["ranks"] == [0, 1]
        assert stats["aligned_steps"] == 2
        assert stats["step_skew_us"]["mean"] == pytest.approx(300.0)
        assert stats["step_skew_us"]["max"] == pytest.approx(400.0)
        assert stats["straggler_gap_us"] == pytest.approx(300.0)
        assert stats["straggler_rank"] == 1
        assert stats["straggler_last_finishes"] == {1: 2}

    def test_straggler_gap_isolates_last_finisher(self, tmp_path):
        perf_report = _load_tool("perf_report")
        # ranks 0/1 finish 5 us apart; rank 2 trails by a full 1000 us:
        # skew = 1005 (first vs last) but the straggler GAP — the stall
        # rank 2 alone causes — is last vs second-to-last = 1000
        paths = []
        for r, steps in enumerate(
            [[(1000, 500)], [(1000, 505)], [(1000, 1505)]]
        ):
            p = tmp_path / f"trace_rank{r}.json"
            p.write_text(json.dumps(_rank_trace(steps)))
            paths.append(str(p))
        _, stats = perf_report.merge_traces(paths)
        assert stats["step_skew_us"]["mean"] == pytest.approx(1005.0)
        assert stats["straggler_gap_us"] == pytest.approx(1000.0)
        assert stats["straggler_rank"] == 2

    def test_count_mismatch_aligns_trailing_steps(self, tmp_path):
        perf_report = _load_tool("perf_report")
        # rank 0 kept 3 steps; rank 1's ring buffer dropped the oldest and
        # kept 2. Trailing alignment pairs r0's LAST two steps with r1's
        # (ends 1100/2100 vs 1150/2150 -> skew 50), where leading-index
        # pairing would compare unrelated steps (skew 1050); the mismatch
        # is still flagged.
        p0 = tmp_path / "trace_rank0.json"
        p1 = tmp_path / "trace_rank1.json"
        p0.write_text(json.dumps(
            _rank_trace([(0, 100), (1000, 100), (2000, 100)])
        ))
        p1.write_text(json.dumps(_rank_trace([(1000, 150), (2000, 150)])))
        _, stats = perf_report.merge_traces([str(p0), str(p1)])
        assert stats["count_mismatch"] is True
        assert stats["aligned_steps"] == 2
        assert stats["step_skew_us"]["mean"] == pytest.approx(50.0)
        assert stats["straggler_rank"] == 1

    def test_rank_from_filename_else_position(self, tmp_path):
        perf_report = _load_tool("perf_report")
        a = tmp_path / "leg_a.json"
        b = tmp_path / "rank3.json"
        a.write_text(json.dumps(_rank_trace([(0, 10)])))
        b.write_text(json.dumps(_rank_trace([(0, 20)])))
        trace, stats = perf_report.merge_traces([str(a), str(b)])
        # a has no rank in its name -> positional 0; b -> parsed 3
        assert stats["ranks"] == [0, 3]

    def test_heartbeats_fold_in_as_instants(self, tmp_path):
        perf_report = _load_tool("perf_report")
        p0 = tmp_path / "trace_rank0.json"
        p0.write_text(json.dumps(_rank_trace([(1000, 500)])))
        hb = tmp_path / "hb"
        hb.mkdir()
        (hb / "hb_rank0").write_text(
            json.dumps({"rank": 0, "step": 1, "time": 0.0015})
        )
        (hb / "hb_rank1.tmp.123").write_text("{torn")  # must be ignored
        trace, _ = perf_report.merge_traces(
            [str(p0)], heartbeat_dir=str(hb)
        )
        beats = [
            e for e in trace["traceEvents"] if e.get("cat") == "health"
        ]
        assert len(beats) == 1
        assert beats[0]["ph"] == "I" and beats[0]["pid"] == 0
        assert beats[0]["ts"] == pytest.approx(1500.0)

    def test_merged_trace_loads_like_chrome_trace(self, tmp_path):
        # end to end with REAL span exports: step a program on two fake
        # ranks, export, merge, and require a well-formed trace JSON
        perf_report = _load_tool("perf_report")
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [4, 8])
            loss = layers.mean(layers.fc(x, 8))
        exe = fluid.Executor()
        exe.run(startup)
        paths = []
        for rank in (0, 1):
            observability.reset()
            for _ in range(2):
                exe.run(main, feed={"x": np.ones((4, 8), "float32")},
                        fetch_list=[loss.name])
            p = tmp_path / f"trace_rank{rank}.json"
            observability.spans.save_chrome_trace(str(p))
            paths.append(str(p))
        trace, stats = perf_report.merge_traces(paths)
        assert stats["aligned_steps"] == 2
        assert stats["steps_per_rank"] == {0: 2, 1: 2}
        out = tmp_path / "pod.json"
        out.write_text(json.dumps(trace))
        reloaded = json.loads(out.read_text())
        assert {e.get("pid") for e in reloaded["traceEvents"]} == {0, 1}
