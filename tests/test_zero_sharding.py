"""Cross-replica weight-update sharding (ZeRO, arXiv:2004.13336) +
block-quantized collectives (EQuARX, arXiv:2506.17615).

Fast legs run in-process on the 8-virtual-CPU-device mesh (dp=2 submesh,
where reduce-scatter and allreduce share one deterministic add order, so
fp32 parity is asserted BITWISE); the 2-process gloo golden equivalence —
the MULTICHIP dryrun path — is @slow and drives tests/dist_zero_worker.py
through the real launcher.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, observability
from paddle_tpu.framework import unique_name
from paddle_tpu.framework.scope import Scope
from paddle_tpu.parallel import make_mesh, shard_program
from paddle_tpu.parallel.transpiler import (
    _SHARD_SUFFIX,
    GradAllReduce,
    ShardedWeightUpdate,
)

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

B, D, H, STEPS = 8, 16, 32, 5


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


def _train(mode, quant=None, optimizer=None, nranks=2, steps=STEPS,
           amp=False):
    """Train the reference MLP `steps` steps under `mode`
    ("allreduce" | "sharded") on a dp=`nranks` in-process submesh; returns
    (losses, trainable params, main program, scope)."""
    import jax

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    scope = Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard():
        x = fluid.data("x", [B, D])
        y = fluid.data("y", [B, 1])
        h = layers.fc(x, H, act="relu")
        pred = layers.fc(h, 1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        opt = optimizer() if optimizer else fluid.optimizer.Adam(0.01)
        if amp:
            from paddle_tpu.contrib import mixed_precision as mp

            opt = mp.decorate(
                opt, init_loss_scaling=2.0**4,
                use_dynamic_loss_scaling=True, incr_every_n_steps=3,
                dest_dtype="bfloat16",
            )
        _, pg = opt.minimize(loss, startup)
        blk = main.global_block
        if mode == "allreduce":
            GradAllReduce(nranks).transpile(main, pg)
        else:
            ShardedWeightUpdate(nranks, quant=quant).transpile(
                main, startup, pg
            )
        # global-mean loss, both modes (the fleet transpile does the same)
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
                            scope=scope, return_numpy=False)
            losses.append(np.asarray(lv).reshape(-1)[0].copy())
        params = {
            v.name: np.asarray(scope.find_var(v.name))
            for v in main.all_parameters()
            if getattr(v, "trainable", False)
        }
    return np.array(losses), params, main, scope


# ---------------------------------------------------------------------------
# equivalence
# ---------------------------------------------------------------------------


def test_sharded_update_bitwise_matches_allreduce():
    """dp=2 fp32: reduce-scatter + shard update + all-gather must be
    BITWISE loss- and weight-equivalent to the plain allreduce transpile
    (sum order is a single commutative add at n=2)."""
    la, pa, _, _ = _train("allreduce")
    ls, ps, main, scope = _train("sharded")
    np.testing.assert_array_equal(la, ls)
    assert sorted(pa) == sorted(ps)
    for name in pa:
        np.testing.assert_array_equal(pa[name], ps[name])
    # optimizer state is genuinely sharded: moment shards exist, the full
    # moments are gone from both the program and the scope
    shard_vars = list(main._zero_shard_vars)
    assert any("moment" in n for n in shard_vars)
    for n in shard_vars:
        assert scope.find_var(n) is not None
        full_name = n[: -len(_SHARD_SUFFIX)]
        if "moment" in full_name:
            assert not main.global_block.has_var(full_name)
            assert scope.find_var(full_name) is None


def test_sharded_update_int8_collectives_within_tolerance():
    la, _, _, _ = _train("allreduce")
    lq, _, main, _ = _train("sharded", quant="int8")
    assert main._zero_quant == "int8"
    assert np.all(np.isfinite(lq))
    np.testing.assert_allclose(la, lq, rtol=5e-2, atol=5e-2)


def test_amp_sharded_matches_allreduce_and_scale_stays_uniform():
    """bf16 AMP: the grad shards feed check_finite_and_unscale /
    update_loss_scaling, FoundInfinite is any-reduced across dp, and the
    whole trajectory (loss + dynamic loss scale automaton) matches the
    allreduce AMP run bitwise."""
    la, _, main_a, scope_a = _train(
        "allreduce", optimizer=lambda: fluid.optimizer.Momentum(0.01, 0.9),
        amp=True,
    )
    ls, _, main_s, scope_s = _train(
        "sharded", optimizer=lambda: fluid.optimizer.Momentum(0.01, 0.9),
        amp=True,
    )
    np.testing.assert_array_equal(la, ls)
    assert any(
        op.type == "c_allreduce_any" for op in main_s.global_block.ops
    )

    def _scale(main, scope):
        name = next(
            v.name for v in main.list_vars() if "loss_scaling" in v.name
        )
        return float(np.asarray(scope.find_var(name)).reshape(-1)[0])

    assert _scale(main_a, scope_a) == _scale(main_s, scope_s)


# ---------------------------------------------------------------------------
# state sizing + observability
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("nranks", [2, 8])
def test_optimizer_state_bytes_per_rank_is_one_over_n(nranks):
    """At dp=2 and dp=8: optimizer state per rank is ~1/N, the "full"
    gauge agrees with a recount, and the losses match allreduce."""
    la, _, main_a, _ = _train("allreduce", nranks=nranks)
    observability.reset()
    ls, _, _, _ = _train("sharded", nranks=nranks)
    g = observability.snapshot()["gauges"]
    per_rank = g["collective.zero_optimizer_state_bytes_per_rank"]
    full = g["collective.zero_optimizer_state_bytes_full"]
    assert full > 0
    # moments shard exactly 1/N; [1] beta pows stay replicated; padding
    # adds a little — 1/N within 25% covers both
    assert per_rank <= full / nranks * 1.25, (per_rank, full)
    assert g["collective.zero_master_shard_bytes_per_rank"] > 0
    # the transpiler's "full" gauge is a plain walk of the allreduce
    # build's replicated accumulators
    recount = sum(
        4 * int(np.prod(v.shape or ()))
        for v in main_a.list_vars()
        if getattr(v, "_accum_of", None) is not None
    )
    assert abs(full - recount) <= 0.02 * recount, (full, recount)
    # the dp=8 reduction tree may legally reorder adds: close, not bitwise
    np.testing.assert_allclose(la, ls, rtol=1e-5, atol=1e-6)


def test_payload_byte_counters_by_kind_and_precision():
    observability.reset()
    _train("sharded", steps=1)
    c_fp = dict(observability.snapshot()["counters"])
    observability.reset()
    _train("sharded", quant="int8", steps=1)
    c_q = dict(observability.snapshot()["counters"])
    assert c_fp["collective.reduce_scatter"] > 0
    assert c_fp["collective.all_gather"] > 0
    assert c_fp["collective.bytes.reduce_scatter_fp32"] > 0
    assert c_fp["collective.bytes.all_gather_fp32"] > 0
    assert c_q["collective.bytes.reduce_scatter_int8"] > 0
    assert c_q["collective.bytes.all_gather_int8"] > 0
    # the headline claim needs a non-padding-dominated tensor (this tiny
    # model pads every grad up to quant_block): check the wire-byte
    # accounting the emitters record, on a 16k-element payload
    import jax.numpy as jnp

    from paddle_tpu.ops.collective import _record_zero

    class _Op:
        def __init__(self, quant):
            self._q = quant

        def attr(self, name, default=None):
            return {"quant": self._q, "quant_block": 256}.get(name, default)

    n = 64 * 256
    observability.reset()
    for quant in ("none", "int8"):
        _record_zero("reduce_scatter", _Op(quant), n, jnp.float32,
                     "dp", 2)
    c = observability.snapshot()["counters"]
    fp = c["collective.bytes.reduce_scatter_fp32"]
    q8 = c["collective.bytes.reduce_scatter_int8"]
    assert q8 < 0.6 * fp, (q8, fp)


# ---------------------------------------------------------------------------
# fleet strategy knob
# ---------------------------------------------------------------------------


def _fleet_minimize(shard, quant=None):
    from paddle_tpu.fleet import collective as fc
    from paddle_tpu.fleet.role_maker import UserDefinedRoleMaker

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 7
    scope = Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard():
        x = fluid.data("x", [B, D])
        y = fluid.data("y", [B, 1])
        pred = layers.fc(layers.fc(x, H, act="relu"), 1)
        loss = layers.mean(layers.square_error_cost(pred, y))
        fleet = fc.Fleet()
        fleet.init(UserDefinedRoleMaker())
        strategy = fc.DistributedStrategy()
        strategy.shard_weight_update = shard
        strategy.collective_quant = quant
        opt = fleet.distributed_optimizer(
            fluid.optimizer.Adam(0.01), strategy
        )
        opt.minimize(loss)
        exe = fluid.Executor()
        exe.run(startup, scope=scope)
        losses = []
        for i in range(3):
            (lv,) = exe.run(main, feed=_feed(i), fetch_list=[loss],
                            scope=scope, return_numpy=False)
            losses.append(float(np.asarray(lv).reshape(-1)[0]))
    return losses, main


def test_fleet_shard_weight_update_knob():
    """strategy.shard_weight_update routes minimize through the ZeRO
    transpile on the full dp=8 virtual mesh and tracks the allreduce
    strategy's losses (dp=8 changes the reduction tree, so tolerance)."""
    base, main_b = _fleet_minimize(shard=False)
    shard, main_s = _fleet_minimize(shard=True)
    zero_kinds = ("zero_reduce_scatter", "zero_bucket_reduce_scatter")
    assert not any(
        op.type in zero_kinds for op in main_b.global_block.ops
    )
    # the strategy's default collective_bucket_mb routes the sharded path
    # through BUCKETED reduce-scatters (PR 14's overlap schedule); the
    # per-grad kind comes back with collective_bucket_mb=0
    assert any(
        op.type == "zero_bucket_reduce_scatter"
        for op in main_s.global_block.ops
    )
    assert not any(
        op.type == "c_allreduce_sum" and "grad" in str(op.inputs).lower()
        for op in main_s.global_block.ops
    )
    np.testing.assert_allclose(base, shard, rtol=1e-4, atol=1e-5)


def test_fleet_sharding_refuses_grad_clip_and_lamb():
    from paddle_tpu.fleet import collective as fc
    from paddle_tpu.fleet.role_maker import UserDefinedRoleMaker

    x = fluid.data("x", [B, D])
    y = fluid.data("y", [B, 1])
    loss = layers.mean(layers.square_error_cost(layers.fc(x, 1), y))
    fleet = fc.Fleet()
    fleet.init(UserDefinedRoleMaker())
    strategy = fc.DistributedStrategy()
    strategy.shard_weight_update = True
    from paddle_tpu.clip import GradientClipByNorm

    opt = fleet.distributed_optimizer(
        fluid.optimizer.SGD(0.1, grad_clip=GradientClipByNorm(1.0)),
        strategy,
    )
    with pytest.raises(NotImplementedError, match="grad_clip"):
        opt.minimize(loss)

    opt2 = fleet.distributed_optimizer(fluid.optimizer.Lamb(0.01), strategy)
    with pytest.raises(NotImplementedError, match="lamb"):
        opt2.minimize(loss)


# ---------------------------------------------------------------------------
# checkpointing sharded optimizer state
# ---------------------------------------------------------------------------


def test_sharded_state_checkpoint_roundtrip(tmp_path):
    """save_check_point(local_vars=<shard vars>) persists each rank's
    optimizer-state shards through the PR-4 per-rank machinery; load
    restores them bitwise (single-process mesh: shards are addressable)."""
    from paddle_tpu.fleet import collective as fc
    from paddle_tpu.fleet.role_maker import UserDefinedRoleMaker

    _, _, main, scope = _train("sharded", steps=2)
    shard_vars = list(main._zero_shard_vars)
    with fluid.scope_guard(scope):
        fleet = fc.Fleet()
        fleet.init(UserDefinedRoleMaker())
        exe = fluid.Executor()
        fleet.save_check_point(
            exe, str(tmp_path), fc.TrainStatus(0), main_program=main,
            local_vars=shard_vars,
        )
        before = {n: np.asarray(scope.find_var(n)).copy()
                  for n in shard_vars}
        import jax.numpy as jnp

        for n in shard_vars:  # poison, then prove load restores
            scope.set_var(n, jnp.zeros_like(scope.find_var(n)))
        status = fleet.load_check_point(exe, str(tmp_path),
                                        main_program=main)
        assert status.epoch_no == 0
        for n in shard_vars:
            np.testing.assert_array_equal(
                before[n], np.asarray(scope.find_var(n))
            )


def test_warm_start_rederives_master_shards(tmp_path):
    """Loading weights saved from a NON-sharded layout into a sharded
    program must refresh the @ZERO_SHARD masters — otherwise the first
    all-gather would revert the loaded params to their startup values."""
    import jax.numpy as jnp

    _, _, main, scope = _train("sharded", steps=2)
    with fluid.scope_guard(scope):
        # a plain (non-sharded-layout) params-only save
        pnames = [v.name for v in main.all_parameters()
                  if getattr(v, "trainable", False)]
        import paddle_tpu.io as pio

        saved = {n: np.asarray(scope.find_var(n)) for n in pnames}
        # a replicated-era checkpoint also carries FULL moments: they must
        # convert into the moment shards and not strand in the scope
        moment_shard = next(n for n in main._zero_shard_vars
                            if "moment" in n)
        full_moment = moment_shard[: -len(_SHARD_SUFFIX)]
        moment_vals = np.arange(
            np.asarray(scope.find_var(moment_shard)).size, dtype=np.float32
        )
        saved[full_moment] = moment_vals
        os.makedirs(tmp_path / "plain", exist_ok=True)
        np.savez(tmp_path / "plain" / "__params__.npz", **saved)
        pio._write_manifest(
            str(tmp_path / "plain" / pio.MANIFEST_NAME),
            str(tmp_path / "plain" / "__params__.npz"), saved,
        )
        # poison both the params and their master shards, then load
        for n in pnames:
            scope.set_var(n, jnp.zeros_like(scope.find_var(n)))
        for n in main._zero_shard_vars:
            scope.set_var(n, jnp.zeros_like(scope.find_var(n)))
        pio.load_persistables(fluid.Executor(), str(tmp_path / "plain"),
                              main)
        for n in pnames:
            shard = np.asarray(scope.find_var(n + _SHARD_SUFFIX))
            flat = saved[n].reshape(-1)
            np.testing.assert_array_equal(shard[: flat.size], flat)
        # the full moment converted into its shard and was then dropped
        # (its program var no longer exists — keeping it would strand
        # 2x-params of host memory)
        np.testing.assert_array_equal(
            np.asarray(scope.find_var(moment_shard)), moment_vals
        )
        assert scope.find_var(full_moment) is None
        c = observability.snapshot()["counters"]
        assert c.get("collective.zero_shards_rederived", 0) > len(pnames)


def test_transpiler_refuses_unknown_update_op_and_clip():
    """Direct-transpile guards (not just the fleet wrapper): a param
    whose update op the pass does not understand, or a clipped gradient,
    must refuse loudly — silence would leave rank-local gradients."""
    from paddle_tpu.clip import GradientClipByNorm

    x = fluid.data("x", [B, D])
    y = fluid.data("y", [B, 1])
    loss = layers.mean(layers.square_error_cost(layers.fc(x, 1), y))
    main = fluid.default_main_program()
    startup = fluid.default_startup_program()
    _, pg = fluid.optimizer.SGD(
        0.1, grad_clip=GradientClipByNorm(1.0)
    ).minimize(loss)
    with pytest.raises(NotImplementedError, match="clip"):
        ShardedWeightUpdate(2).transpile(main, startup, pg)

    with pytest.raises(ValueError, match="quantization"):
        ShardedWeightUpdate(2, quant="fp8")

    # a params_grads entry with no update op in the block at all
    main2, startup2 = fluid.Program(), fluid.Program()
    with fluid.program_guard(main2, startup2), unique_name.guard():
        x2 = fluid.data("x", [B, D])
        loss2 = layers.mean(layers.fc(x2, 1))
        _, pg2 = fluid.optimizer.SGD(0.1).minimize(loss2)
        for op in list(main2.global_block.ops):
            if op.type == "sgd":
                main2.global_block.ops.remove(op)
        with pytest.raises(NotImplementedError, match="no supported"):
            ShardedWeightUpdate(2).transpile(main2, startup2, pg2)


def test_slice_overlay_restores_rank_slice():
    """The cross-process shard path: a persisted dim-0 slice keyed
    '<name>@@off<start>' overlays onto the startup-initialized full value
    (what a real pod's per-rank load does for non-addressable state)."""
    import jax.numpy as jnp

    from paddle_tpu.fleet.collective import _SLICE_MARK, _overlay_slice
    from paddle_tpu.framework.scope import global_scope

    scope = global_scope()
    scope.set_var("zstate", jnp.zeros([8], jnp.float32))
    ok = _overlay_slice(
        scope, f"zstate{_SLICE_MARK}4", np.arange(4, dtype=np.float32)
    )
    assert ok
    np.testing.assert_array_equal(
        np.asarray(scope.find_var("zstate")),
        np.array([0, 0, 0, 0, 0, 1, 2, 3], np.float32),
    )
    assert not _overlay_slice(
        scope, f"missing{_SLICE_MARK}0", np.zeros(2, np.float32)
    )


# ---------------------------------------------------------------------------
# 2-process gloo golden equivalence (the MULTICHIP dryrun path)
# ---------------------------------------------------------------------------


def _free_port_pair():
    import random
    import socket

    for _ in range(128):
        base = random.randint(20000, 60000)
        try:
            with socket.socket() as a, socket.socket() as b:
                a.bind(("127.0.0.1", base))
                b.bind(("127.0.0.1", base + 1))
            return base
        except OSError:
            continue
    raise RuntimeError("no free port pair found")


def _launch_zero(mode, out_dir):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [
            sys.executable, "-m", "paddle_tpu.distributed.launch",
            "--nproc_per_node=2", f"--started_port={_free_port_pair()}",
            "--simulate_cpu",
            os.path.join(HERE, "dist_zero_worker.py"), mode, str(out_dir),
        ],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=540,
    )
    if proc.returncode != 0 and (
        "Multiprocess computations aren't implemented" in proc.stdout
        or "Multiprocess computations aren't implemented" in proc.stderr
    ):
        # this jaxlib build has no cross-process CPU collectives (the same
        # limitation the tests/test_dist_spmd.py suite trips here); the
        # in-process dp=2 bitwise tests above cover the math, this leg
        # covers the real gloo exchange where the backend supports it
        pytest.skip("jaxlib CPU backend lacks multiprocess collectives")
    assert proc.returncode == 0, f"stdout:{proc.stdout}\nstderr:{proc.stderr}"


@pytest.mark.slow
def test_two_process_sharded_matches_allreduce_bitwise(tmp_path):
    """Golden equivalence on the real 2-process gloo path: the sharded
    weight update must reproduce the plain-allreduce loss trajectory and
    final weights BITWISE in fp32, and within tolerance with int8
    collectives; the collective.* counters must show the int8 payload
    shrink."""
    for mode in ("baseline", "sharded", "sharded_int8"):
        d = tmp_path / mode
        d.mkdir()
        _launch_zero(mode, d)

    def _result(mode, rank=0):
        r = json.load(open(tmp_path / mode / f"result_{rank}.json"))
        params = np.load(tmp_path / mode / f"params_{rank}.npz")
        return r, params

    base, pb = _result("baseline")
    shard, ps = _result("sharded")
    quant, pq = _result("sharded_int8")
    # both ranks agree with themselves (replicated fetches)
    for mode in ("baseline", "sharded", "sharded_int8"):
        r0, _ = _result(mode, 0)
        r1, _ = _result(mode, 1)
        np.testing.assert_array_equal(r0["losses"], r1["losses"])
    # fp32 sharded == allreduce, bitwise
    np.testing.assert_array_equal(base["losses"], shard["losses"])
    for name in pb.files:
        assert pb[name].tobytes() == ps[name].tobytes(), name
    # int8: tolerance-bounded, still finite and training
    np.testing.assert_allclose(
        base["losses"], quant["losses"], rtol=5e-2, atol=5e-2
    )
    # counters: sharded run exchanged reduce-scatter/all-gather payloads;
    # the int8 run's wire bytes are measurably smaller
    cs = shard["counters"]
    cq = quant["counters"]
    assert cs["collective.bytes.reduce_scatter_fp32"] > 0
    assert cs["collective.bytes.all_gather_fp32"] > 0
    assert cq["collective.bytes.reduce_scatter_int8"] > 0
    q_wire = (cq["collective.bytes.reduce_scatter_int8"]
              + cq["collective.bytes.all_gather_int8"])
    f_wire = (cs["collective.bytes.reduce_scatter_fp32"]
              + cs["collective.bytes.all_gather_fp32"])
    assert q_wire < 0.6 * f_wire, (q_wire, f_wire)
    # optimizer state really lives 1/N per rank
    gq = shard["gauges"]
    assert gq["collective.zero_optimizer_state_bytes_per_rank"] <= (
        gq["collective.zero_optimizer_state_bytes_full"] / 2 * 1.25
    )
