"""Communication/compute overlap (ROADMAP item 4): bucketed grad
collectives + prefetched all-gathers.

The schedule transforms are pure reorderings/regroupings, so every fp32
leg here asserts BITWISE parity against the serialized per-grad schedule
(dp=2 and dp=8 in-process submeshes), int8 against the per-grad int8 path
(bitwise too: member pads are block-aligned, so the quant blocks and
scales are identical). The lint leg proves a rank-divergent bucketing is
a build-time ERROR, and the cost-model leg pins the overlap-aware
scheduled estimate's op goldens.
"""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observability
from paddle_tpu.framework import unique_name
from paddle_tpu.framework.scope import Scope
from paddle_tpu.parallel import make_mesh, shard_program
from paddle_tpu.parallel.transpiler import (
    GradAllReduce,
    ShardedWeightUpdate,
    plan_grad_buckets,
)

B, D, H, STEPS = 8, 16, 32, 4


@pytest.fixture(autouse=True)
def fresh_programs():
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.framework.scope.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard():
        yield


def _feed(i):
    rng = np.random.RandomState(100 + i)
    return {
        "x": rng.randn(B, D).astype(np.float32),
        "y": rng.randn(B, 1).astype(np.float32),
    }


def _train(mode, nranks=2, steps=STEPS, quant=None, bucket=None,
           prefetch=False, depth=2, return_numpy=False):
    """Train the reference MLP under `mode` ("allreduce" | "sharded") on
    a dp=`nranks` in-process submesh with the requested overlap knobs;
    returns (losses, main program)."""
    import jax

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    scope = Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard():
        x = fluid.data("x", [B, D])
        y = fluid.data("y", [B, 1])
        h = x
        for _ in range(depth):
            h = layers.fc(h, H, act="relu")
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        _, pg = fluid.optimizer.Adam(0.01).minimize(loss, startup)
        blk = main.global_block
        if mode == "allreduce":
            GradAllReduce(nranks, bucket_bytes=bucket).transpile(main, pg)
        else:
            ShardedWeightUpdate(
                nranks, quant=quant, bucket_bytes=bucket, prefetch=prefetch,
            ).transpile(main, startup, pg)
        blk.append_op("scale", {"X": [loss.name]}, {"Out": [loss.name]},
                      {"scale": 1.0 / nranks, "bias": 0.0})
        blk.append_op("c_allreduce_sum", {"X": [loss.name]},
                      {"Out": [loss.name]}, {"axis_name": "dp"})
        shard_program(
            main, make_mesh({"dp": nranks}, jax.devices()[:nranks]),
            {"x": ("dp",), "y": ("dp",)},
        )
        exe = fluid.Executor()
        exe.run(startup, scope=scope)
        losses = []
        for i in range(steps):
            (lv,) = exe.run(main, feed=_feed(i), fetch_list=[loss],
                            scope=scope, return_numpy=return_numpy)
            losses.append(np.asarray(lv).reshape(-1)[0].copy())
    return np.array(losses), main


# ---------------------------------------------------------------------------
# bucket planning goldens
# ---------------------------------------------------------------------------


class _FakeBlock:
    """Minimal producer stream for plan_grad_buckets: op i produces
    grad gi."""

    def __init__(self, names):
        class _Op:
            def __init__(self, name):
                self._n = name
                self.type = "relu"

            def output_names(self):
                return [self._n]

        self.ops = [_Op(n) for n in names]


def test_bucket_plan_straddle_golden():
    """A grad that would push a non-empty bucket past the target CLOSES
    it and opens the next — straddling grads move whole, never split; an
    oversize grad gets a bucket of its own."""
    blk = _FakeBlock(["g0", "g1", "g2", "g3"])
    entries = [
        {"name": "g0", "numel": 10, "nbytes": 40, "group": "float32"},
        {"name": "g1", "numel": 10, "nbytes": 40, "group": "float32"},
        {"name": "g2", "numel": 10, "nbytes": 40, "group": "float32"},  # straddles
        {"name": "g3", "numel": 100, "nbytes": 400, "group": "float32"},  # oversize
    ]
    buckets = plan_grad_buckets(blk, entries, bucket_bytes=100)
    got = [[e["name"] for e in b["members"]] for b in buckets]
    assert got == [["g0", "g1"], ["g2"], ["g3"]], got
    # each bucket fires just after its LAST member's producer
    assert [b["pos"] for b in buckets] == [2, 3, 4]


def test_bucket_plan_orders_by_production_and_groups_dtype():
    """Grads bucket in backward-production (reverse-topological) order
    regardless of entry order, and dtypes never share a bucket (members
    concatenate into one exchange buffer)."""
    blk = _FakeBlock(["g0", "g1", "g2"])
    entries = [  # handed over in reversed order on purpose
        {"name": "g2", "numel": 1, "nbytes": 4, "group": "float32"},
        {"name": "g1", "numel": 1, "nbytes": 2, "group": "bfloat16"},
        {"name": "g0", "numel": 1, "nbytes": 4, "group": "float32"},
    ]
    buckets = plan_grad_buckets(blk, entries, bucket_bytes=1 << 20)
    by_group = {b["group"]: [e["name"] for e in b["members"]]
                for b in buckets}
    assert by_group["float32"] == ["g0", "g2"]  # production order
    assert by_group["bfloat16"] == ["g1"]
    with pytest.raises(ValueError, match="positive"):
        plan_grad_buckets(blk, entries, bucket_bytes=0)


def test_bucketed_firing_order_is_reverse_topological():
    """In the transpiled program the bucket collectives appear in
    backward-production order (last forward layer's grads fire first) and
    each sits at its last member's producer — NOT at the program tail."""
    import re

    _, main = _train("sharded", bucket=600, prefetch=False, depth=3)
    block = main.global_block
    bucket_idx = [i for i, op in enumerate(block.ops)
                  if op.type == "zero_bucket_reduce_scatter"]
    assert len(bucket_idx) > 1
    assert bucket_idx == sorted(bucket_idx)

    def layer_of(name):  # fc_w_3@GRAD -> 3
        return int(re.search(r"_(\d+)@", name).group(1))

    # reverse-topological: the FIRST bucket carries the LAST fc layer's
    # grads (produced earliest in the backward), the last bucket the
    # first layer's
    first_members = block.ops[bucket_idx[0]].inputs["X"]
    last_members = block.ops[bucket_idx[-1]].inputs["X"]
    assert max(layer_of(n) for n in first_members) > max(
        layer_of(n) for n in last_members
    )
    # the first bucket fires while backward compute REMAINS — grad
    # producers (vjp ops) still follow it, so its wire can hide
    later_types = [op.type for op in block.ops[bucket_idx[0] + 1:]]
    assert "__vjp__" in later_types, (
        "first bucket must fire while backward compute remains"
    )
    # membership is disjoint and covers all dense grads
    all_members = [n for i in bucket_idx for n in block.ops[i].inputs["X"]]
    assert len(all_members) == len(set(all_members))
    assert set(last_members).isdisjoint(first_members)


# ---------------------------------------------------------------------------
# bitwise parity: overlapped vs serialized
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("nranks", [2, 8])
def test_bucketed_allreduce_bitwise_matches_per_grad(nranks):
    """Satellite bugfix leg: the non-ZeRO dp path routed through the
    bucketing machinery is BITWISE the per-grad c_allreduce_sum schedule
    (elementwise sums are unchanged by concatenation), at dp=2 and dp=8."""
    la, main_a = _train("allreduce", nranks=nranks)
    lb, main_b = _train("allreduce", nranks=nranks, bucket=1 << 20)
    np.testing.assert_array_equal(la, lb)
    types_a = [op.type for op in main_a.global_block.ops]
    types_b = [op.type for op in main_b.global_block.ops]
    # per-grad: one allreduce per grad (+ the loss mean); bucketed: ONE
    # bucket collective, only the loss allreduce left per-tensor
    assert types_b.count("c_bucket_allreduce_sum") == 1
    assert types_b.count("c_allreduce_sum") == 1
    assert types_a.count("c_allreduce_sum") > 2


def test_overlapped_zero_bitwise_matches_serialized():
    """Tentpole parity: bucketed reduce-scatters + prefetched all-gathers
    reproduce the serialized ZeRO loss trajectory BITWISE in fp32."""
    l0, m0 = _train("sharded")
    l1, m1 = _train("sharded", bucket=1 << 20, prefetch=True)
    l2, m2 = _train("sharded", prefetch=True)  # per-grad + prefetch only
    np.testing.assert_array_equal(l0, l1)
    np.testing.assert_array_equal(l0, l2)
    assert not getattr(m0, "_overlap_schedule", False)
    assert getattr(m1, "_overlap_schedule", False)
    assert getattr(m2, "_overlap_schedule", False)
    # prefetch interleaved the updates + all-gathers into the backward:
    # the first all-gather sits before the last grad producer (per-grad
    # reduce-scatters fire at each grad's true production point, so the
    # hoisted update/gather pair rides right behind it)
    types = [op.type for op in m2.global_block.ops]
    first_gather = types.index("zero_all_gather")
    last_vjp = max(i for i, t in enumerate(types) if t == "__vjp__")
    assert first_gather < last_vjp


def test_overlapped_zero_int8_matches_per_grad_int8():
    """int8 leg: member pads are aligned to nranks*quant_block, so the
    bucketed exchange quantizes the SAME blocks with the SAME scales as
    the per-grad path — bitwise, not just tolerance."""
    q0, _ = _train("sharded", quant="int8")
    q1, _ = _train("sharded", quant="int8", bucket=1 << 20, prefetch=True)
    np.testing.assert_array_equal(q0, q1)
    # and the int8 trajectory stays within the PR-9 tolerance of fp32
    f0, _ = _train("allreduce")
    np.testing.assert_allclose(f0, q0, rtol=5e-2, atol=5e-2)


def test_multi_bucket_zero_bitwise():
    """Several small buckets (grads straddling bucket boundaries in a
    real program) still reproduce the serialized trajectory bitwise."""
    l0, _ = _train("sharded", depth=3)
    l1, main = _train("sharded", bucket=600, prefetch=True, depth=3)
    np.testing.assert_array_equal(l0, l1)
    n_buckets = sum(1 for op in main.global_block.ops
                    if op.type == "zero_bucket_reduce_scatter")
    assert n_buckets > 1


def test_fleet_bucket_knob_and_refusal():
    """DistributedStrategy.collective_bucket_mb=0 restores the per-grad
    schedule; a negative bucket size refuses loudly."""
    from paddle_tpu.fleet import collective as fc
    from paddle_tpu.fleet.role_maker import UserDefinedRoleMaker

    def minimize(bucket_mb):
        main, startup = fluid.Program(), fluid.Program()
        scope = Scope()
        with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
                unique_name.guard():
            x = fluid.data("x", [B, D])
            y = fluid.data("y", [B, 1])
            loss = layers.mean(
                layers.square_error_cost(layers.fc(x, 1), y)
            )
            fleet = fc.Fleet()
            fleet.init(UserDefinedRoleMaker())
            strategy = fc.DistributedStrategy()
            strategy.collective_bucket_mb = bucket_mb
            opt = fleet.distributed_optimizer(
                fluid.optimizer.SGD(0.1), strategy
            )
            opt.minimize(loss)
        return main

    per_grad = minimize(0)
    types = [op.type for op in per_grad.global_block.ops]
    assert "c_bucket_allreduce_sum" not in types
    assert types.count("c_allreduce_sum") >= 2  # per-grad + loss mean
    bucketed = minimize(25.0)
    assert any(op.type == "c_bucket_allreduce_sum"
               for op in bucketed.global_block.ops)
    with pytest.raises(ValueError, match="bucket"):
        minimize(-1.0)


# ---------------------------------------------------------------------------
# rank-divergent bucketing is a build-time ERROR
# ---------------------------------------------------------------------------


def _divergent_bucket_program():
    """Pipeline stages that bucket the same exchange differently — the
    wire-layout mismatch the lint must reject at build time."""
    from paddle_tpu.parallel.pipeline import slice_program_into_stages

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), unique_name.guard():
        x = fluid.data("x", [8, 4])
        with fluid.device_guard("pipeline:0"):
            h = layers.fc(x, 4)
        with fluid.device_guard("pipeline:1"):
            loss = layers.mean(layers.fc(h, 4))
        main._pipeline = {"num_microbatches": 2, "axis_name": "pp"}
        _, pipe_op = slice_program_into_stages(main, loss)
    for si, pads in ((0, [256, 256]), (1, [512])):
        stage = main.blocks[pipe_op.attr("stage_blocks")[si]]
        gname = f"divg{si}"
        stage.create_var(name=gname, shape=[4, 4], dtype="float32")
        stage.append_op("fill_constant", {}, {"Out": [gname]},
                        {"shape": [4, 4], "dtype": "float32", "value": 0.0})
        outs = []
        for j, p in enumerate(pads):
            on = f"divs{si}_{j}"
            stage.create_var(name=on, shape=[p], dtype="float32")
            outs.append(on)
        stage.append_op(
            "zero_bucket_reduce_scatter",
            {"X": [gname] * len(pads)}, {"Out": outs},
            {"axis_name": "dp", "pad_lens": pads, "quant": "none"},
        )
    shard_program(main, make_mesh({"dp": 4, "pp": 2}), {"x": ("dp",)})
    return main


def test_rank_divergent_bucketing_is_build_time_error():
    from paddle_tpu.analysis.collectives import analyze_collectives
    from paddle_tpu.analysis.findings import Severity

    findings = analyze_collectives(_divergent_bucket_program())
    errs = [f for f in findings if f.severity == Severity.ERROR]
    assert errs, "rank-divergent bucket membership must ERROR"
    assert any("zero_bucket_reduce_scatter[256,256]" in f.format()
               or "zero_bucket_reduce_scatter[512]" in f.format()
               for f in errs)


def test_quantized_bucket_kind_is_distinct():
    """fp32-vs-int8 bucket wire formats are DISTINCT site kinds, exactly
    like the per-grad zero collectives (PR 9)."""
    from paddle_tpu.analysis.collectives import collective_axis
    from paddle_tpu.framework.registry import OpView

    fp = OpView("zero_bucket_reduce_scatter",
                {"axis_name": "dp", "pad_lens": [256], "quant": "none"})
    q = OpView("zero_bucket_reduce_scatter",
               {"axis_name": "dp", "pad_lens": [256], "quant": "int8"})
    _, kfp = collective_axis(fp)
    _, kq = collective_axis(q)
    assert kfp == "zero_bucket_reduce_scatter[256]"
    assert kq == "zero_bucket_reduce_scatter[256]:int8"
    ar = OpView("c_bucket_allreduce_sum",
                {"axis_name": "dp", "bucket_numels": [10, 20]})
    _, kar = collective_axis(ar)
    assert kar == "c_bucket_allreduce_sum[10,20]"


# ---------------------------------------------------------------------------
# overlap-aware cost model
# ---------------------------------------------------------------------------


def test_bucket_collective_op_cost_goldens():
    """Closed forms: a bucket moves exactly its members' summed (padded,
    possibly quantized) ring bytes."""
    from paddle_tpu.analysis.cost import _quant_elem_bytes, op_cost
    from paddle_tpu.framework.registry import OpView

    n = 8
    pads = [2048, 4096]
    rs = OpView("zero_bucket_reduce_scatter",
                {"axis_name": "dp", "pad_lens": pads, "quant": "none"})
    grads = [((2000,), 4), ((4000,), 4)]
    flops, wire = op_cost(rs, {"X": grads}, {}, axis_sizes={"dp": n})
    assert wire == pytest.approx(sum(pads) * 4 * (n - 1) / n)
    assert flops == pytest.approx(sum(pads))
    q = OpView("zero_bucket_reduce_scatter",
               {"axis_name": "dp", "pad_lens": pads, "quant": "int8",
                "quant_block": 256})
    _, qwire = op_cost(q, {"X": grads}, {}, axis_sizes={"dp": n})
    assert qwire == pytest.approx(
        sum(pads) * _quant_elem_bytes("int8", 256, 4) * (n - 1) / n
    )
    assert qwire < 0.4 * wire
    ar = OpView("c_bucket_allreduce_sum", {"axis_name": "dp"})
    flops, arwire = op_cost(ar, {"X": grads}, {}, axis_sizes={"dp": n})
    assert arwire == pytest.approx(6000 * 4 * 2 * (n - 1) / n)
    assert flops == pytest.approx(6000)
    # unbound axis: identity degrade
    assert op_cost(ar, {"X": grads}, {}, axis_sizes={}) == (0.0, 0.0)


def test_scheduled_latency_simulation_golden():
    """The two-resource sim: a collective overlaps following compute
    until something READS its output; a serialized consumer chain
    degrades to the sum."""
    from paddle_tpu.analysis.cost import _scheduled_latency

    # compute 10, wire 6 issued, compute 10 (independent), read -> step:
    # wire runs [10, 16] while compute runs [10, 20] -> 20, then consumer 1
    entries = [
        (10.0, False, ("a",), ("b",)),
        (6.0, True, ("b",), ("c",)),
        (10.0, False, ("a",), ("d",)),
        (1.0, False, ("c",), ("e",)),  # waits for the wire (already done)
    ]
    assert _scheduled_latency(entries) == pytest.approx(21.0)
    # wire longer than the remaining compute: the tail is exposed
    entries = [
        (10.0, False, ("a",), ("b",)),
        (30.0, True, ("b",), ("c",)),
        (10.0, False, ("a",), ("d",)),
        (1.0, False, ("c",), ("e",)),
    ]
    assert _scheduled_latency(entries) == pytest.approx(41.0)
    # immediate consumer = fully serialized
    entries = [
        (10.0, False, ("a",), ("b",)),
        (6.0, True, ("b",), ("c",)),
        (1.0, False, ("c",), ("e",)),
    ]
    assert _scheduled_latency(entries) == pytest.approx(17.0)


def test_program_estimate_overlap_aware():
    """Program.estimate() on an overlap-transpiled program: scheduled
    step <= serialized sum, exposed wire <= total wire, overlap metrics
    in to_dict, and the serialized build keeps the PR-13 semantics."""
    _, m_serial = _train("sharded")
    _, m_over = _train("sharded", bucket=1 << 20, prefetch=True)
    feeds = {"x": (B, D), "y": (B, 1)}
    est_s = m_serial.estimate(feed_shapes=feeds)
    est_o = m_over.estimate(feed_shapes=feeds)
    assert est_s.scheduled_latency is None
    assert est_s.step_latency == est_s.total_latency
    assert est_s.wire_exposed_latency == pytest.approx(est_s.wire_latency)
    assert est_s.overlap_ratio == 0.0
    assert est_o.scheduled_latency is not None
    assert est_o.step_latency <= est_o.total_latency
    assert 0.0 < est_o.wire_exposed_latency <= est_o.wire_latency
    # the schedule hides some wire, and exposes less of it than the
    # serialized build does
    assert 0.0 < est_o.overlap_ratio <= 1.0
    assert est_o.wire_exposed_latency < est_s.wire_exposed_latency
    d = est_o.to_dict()
    for key in ("scheduled_latency", "wire_latency",
                "wire_exposed_latency", "overlap_ratio"):
        assert key in d
    assert any("overlap schedule" in a for a in d["assumptions"])


def test_overlapped_zero_dp8_bitwise_and_bucket_counters():
    """At dp=8 too the overlapped schedule reproduces the serialized
    ZeRO trajectory BITWISE, and the trace leaves the bucket counters."""
    l0, _ = _train("sharded", nranks=8, steps=3)
    observability.reset()
    l1, _ = _train("sharded", nranks=8, bucket=1 << 20, prefetch=True,
                   steps=3)
    np.testing.assert_array_equal(l0, l1)
    counters = observability.snapshot()["counters"]
    assert counters.get("collective.buckets", 0) > 0
    assert counters.get("collective.bucket_bytes", 0) > 0
