"""Tiled flash-attention kernel vs the jnp reference (VERDICT r2 item 4:
the KV-tiled online-softmax kernel that removes the whole-row MAX_SEQ
cap). Interpret mode on CPU; dropout=0 (interpreter PRNG is a stub, same
restriction as the round-2 whole-row kernel tests)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels.flash_tiled import (
    flash_tiled, flash_tiled_fwd, supports_tiled,
)

B, S, H, D = 1, 1024, 2, 64  # 2x2 tiles at BQ=BK=512


def _setup(seed=0):
    rng = np.random.RandomState(seed)
    qkv = jnp.asarray(rng.randn(B, S, 3 * H * D).astype(np.float32) * 0.3)
    bias = jnp.asarray(rng.randn(B, S).astype(np.float32) * 0.5)
    return qkv, bias


def _statics(causal):
    return dict(scale=0.125, rate=0.0, is_test=True, upscale=False,
                causal=causal)


@pytest.mark.parametrize("causal", [False, True])
def test_tiled_forward_matches_reference(causal):
    assert supports_tiled(S, H, D, jnp.float32)
    qkv, bias = _setup()
    statics = _statics(causal)
    seed = jnp.zeros((2,), jnp.uint32)
    out, lse = flash_tiled_fwd(qkv, bias, seed, H, D, statics,
                               interpret=True)
    ref = fa._reference_qkv(qkv, bias, jax.random.key(0), H, **statics)
    assert np.allclose(np.asarray(out), np.asarray(ref), atol=2e-5), (
        np.abs(np.asarray(out) - np.asarray(ref)).max()
    )
    # lse finite on every row
    assert np.all(np.isfinite(np.asarray(lse)))


@pytest.mark.parametrize("causal", [False, True])
def test_tiled_grads_match_reference(causal):
    qkv, bias = _setup(1)
    statics = _statics(causal)
    seed = jnp.zeros((2,), jnp.uint32)

    def f_tiled(qkv_, bias_):
        out = flash_tiled(qkv_, bias_, seed, H, D,
                          tuple(statics.items()), True)
        return jnp.sum(out * jnp.cos(out * 0.1))

    def f_ref(qkv_, bias_):
        out = fa._reference_qkv(qkv_, bias_, jax.random.key(0), H, **statics)
        return jnp.sum(out * jnp.cos(out * 0.1))

    g_t = jax.grad(f_tiled, argnums=(0, 1))(qkv, bias)
    g_r = jax.grad(f_ref, argnums=(0, 1))(qkv, bias)
    for a, b_ in zip(g_t, g_r):
        err = np.abs(np.asarray(a) - np.asarray(b_)).max()
        scale = np.abs(np.asarray(b_)).max() + 1e-6
        assert err / scale < 2e-4, err / scale


def _case(S_, dtype, seed):
    """(qkv, bias, w) for a [1, S_, H=2, D=64] call; the reference reads
    the same values in float32."""
    rng = np.random.RandomState(seed)
    qkv = jnp.asarray(rng.randn(1, S_, 3 * H * D).astype(np.float32) * 0.3,
                      dtype)
    bias = jnp.asarray(rng.randn(1, S_).astype(np.float32) * 0.5)
    w = jnp.asarray(rng.randn(1, S_, H * D).astype(np.float32))
    return qkv, bias, w


def _tiled_and_reference(qkv, bias, w, causal):
    """out and d(sum(out * w)) / d(qkv, bias): the kernels in interpret
    mode, the dense reference on the float32 values."""
    st = _statics(causal)
    seed = jnp.zeros((2,), jnp.uint32)
    got = jax.value_and_grad(
        lambda x, b: jnp.sum(flash_tiled(
            x, b, seed, H, D, tuple(st.items()), True).astype(jnp.float32)
            * w), argnums=(0, 1))(qkv, bias)
    ref = jax.value_and_grad(
        lambda x, b: jnp.sum(fa._reference_qkv(
            x, b, jax.random.key(0), H, **st) * w), argnums=(0, 1))(
                qkv.astype(jnp.float32), bias)
    return got, ref


def _rel(a, b_):
    a = np.asarray(a.astype(jnp.float32))
    b_ = np.asarray(b_)
    return np.abs(a - b_).max() / (np.abs(b_).max() + 1e-6)


# what each length runs of the kernels' walk (tile = the largest of
# 512 / 256 / 128 that divides S): 128 one block, so the diagonal part
# alone and a loop of no trips; 1024 two blocks of 512; 384 three of 128;
# 1536 three of 512
LOOP_LENGTHS = (128, 1024, 384, 1536)
# max|diff| / max|reference| allowed: (forward, gradients)
LOOP_TOL = {"float32": (2e-5, 2e-4), "bfloat16": (2e-2, 4e-2)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("S_", LOOP_LENGTHS)
def test_loop_forward_matches_reference(S_, causal, dtype):
    assert supports_tiled(S_, H, D, jnp.dtype(dtype))
    qkv, bias, _ = _case(S_, jnp.dtype(dtype), S_)
    st = _statics(causal)
    out, lse = flash_tiled_fwd(qkv, bias, jnp.zeros((2,), jnp.uint32), H, D,
                               st, interpret=True)
    ref = fa._reference_qkv(qkv.astype(jnp.float32), bias,
                            jax.random.key(0), H, **st)
    assert out.dtype == qkv.dtype and lse.dtype == jnp.float32
    assert _rel(out, ref) < LOOP_TOL[dtype][0], _rel(out, ref)
    assert np.all(np.isfinite(np.asarray(lse)))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("S_", LOOP_LENGTHS)
def test_loop_grads_match_reference(S_, causal, dtype):
    qkv, bias, w = _case(S_, jnp.dtype(dtype), S_ + 1)
    (_, (dqkv, dbias)), (_, (rqkv, rbias)) = _tiled_and_reference(
        qkv, bias, w, causal)
    tol = LOOP_TOL[dtype][1]
    # dq, dk and dv each against its own section of the reference
    for sec in range(3):
        cols = slice(sec * H * D, (sec + 1) * H * D)
        err = _rel(dqkv[..., cols], rqkv[..., cols])
        assert err < tol, (sec, err)
    assert _rel(dbias, rbias) < tol, _rel(dbias, rbias)


@pytest.mark.parametrize("causal", [False, True])
def test_super_blocks_when_the_operands_do_not_fit(monkeypatch, causal):
    """With the resident budget lowered to 3 MiB a float32 S=2048 call
    keeps two blocks of K and V at a time (fwd, dq: two super-blocks on
    the last grid axis) and one block of Q, dO, lse, delta (dkv: four),
    so the outer axis, the held block index and the dead super-blocks
    run: same answers as with everything resident."""
    from paddle_tpu import observability as obs
    from paddle_tpu.kernels import flash_tiled as ft
    from paddle_tpu.kernels import vmem

    S_ = 2048
    monkeypatch.setattr(vmem, "RESIDENT_VMEM_LIMIT_BYTES",
                        vmem._BODY_BYTES + 3 * 2**20)
    assert ft._resident_rows(S_, 512, [jnp.float32] * 2) == 1024
    assert ft._resident_rows(S_, 512, [jnp.float32] * 4) == 512
    qkv, bias, w = _case(S_, jnp.float32, 5)
    (out, (dqkv, dbias)), (rout, (rqkv, rbias)) = _tiled_and_reference(
        qkv, bias, w, causal)
    assert abs(float(out) - float(rout)) < 2e-4 * abs(float(rout)) + 1e-3
    for sec in range(3):
        cols = slice(sec * H * D, (sec + 1) * H * D)
        assert _rel(dqkv[..., cols], rqkv[..., cols]) < 2e-4, sec
    assert _rel(dbias, rbias) < 2e-4
    # the forward's walk of one (batch, lane group): 4 q blocks against 2
    # super-blocks of 2; causal, q blocks 0 and 1 each spend one grid step
    # on the dead second super-block
    g = obs.get_gauges()
    assert g["kernels.flash_tiled.tiles_computed"] == (10 if causal else 16)
    assert g["kernels.flash_tiled.tiles_visited"] == (12 if causal else 16)


@pytest.mark.parametrize("causal,want", [(True, 36), (False, 64)])
def test_walk_gauges_at_eight_blocks(causal, want):
    """The counter that says the loop form engaged: at S=4096 (eight
    blocks of 512) the kernel visits the tiles it computes and no other:
    36 / 36 causal (the static grid visited 64), 64 / 64 without. Traced,
    not run."""
    from paddle_tpu import observability as obs

    S_ = 4096
    obs.drop_gauges("kernels.flash_tiled.")
    before = obs.get_counters().get("kernels.flash_tiled", 0)
    jax.eval_shape(
        lambda x, b: flash_tiled(x, b, jnp.zeros((2,), jnp.uint32), H, D,
                                 tuple(_statics(causal).items()), True),
        jax.ShapeDtypeStruct((1, S_, 3 * H * D), jnp.bfloat16),
        jax.ShapeDtypeStruct((1, S_), jnp.float32))
    g = obs.get_gauges()
    assert obs.get_counters()["kernels.flash_tiled"] == before + 1
    assert g["kernels.flash_tiled.tiles_visited"] == want
    assert g["kernels.flash_tiled.tiles_computed"] == want


@pytest.mark.parametrize("rows,blk,dtypes,want", [
    # the benchmark cell and chip_smoke's longctx: S=4096 bfloat16
    (4096, 512, ("bfloat16",) * 2, 4096),
    (4096, 512, ("bfloat16", "bfloat16", "float32", "float32"), 4096),
    # the ring comparison's one-device call: float32 S=8192 (32 MiB of dkv
    # operands, double-buffered)
    (8192, 512, ("float32",) * 4, 8192),
    (16384, 512, ("bfloat16", "bfloat16", "float32", "float32"), 16384),
    # beyond the budget: whole shares of S, in blocks
    (16384, 512, ("float32",) * 4, 8192),
    (32768, 512, ("bfloat16", "bfloat16", "float32", "float32"), 16384),
    (384, 128, ("float32",) * 4, 384),
])
def test_resident_rows_rule(rows, blk, dtypes, want):
    from paddle_tpu.kernels import flash_tiled as ft

    got = ft._resident_rows(rows, blk, [jnp.dtype(d) for d in dtypes])
    assert got == want
    assert rows % got == 0 and got % blk == 0


def test_adaptive_tile_sizes_fwd_bwd():
    """r4: S need only be a multiple of 128 (adaptive BQ/BK) and causal
    tiles above the diagonal are skipped — fwd+bwd vs dense reference at a
    non-512-multiple S."""
    import jax

    from paddle_tpu.kernels import flash_attention as fa
    from paddle_tpu.kernels.flash_tiled import (flash_tiled, flash_tiled_fwd,
                                                supports_tiled)

    rng = np.random.RandomState(7)
    H, D, S = 4, 64, 1280
    assert supports_tiled(S, H, D, jnp.float32)
    assert supports_tiled(384, H, D, jnp.float32)
    qkv = jnp.asarray(rng.randn(1, S, 3 * H * D).astype(np.float32)) * 0.3
    bias = jnp.zeros((1, S), jnp.float32)
    st = dict(scale=0.125, rate=0.0, is_test=True, upscale=False,
              causal=True)
    out, _ = flash_tiled_fwd(qkv, bias, jnp.zeros(2, jnp.uint32), H, D, st,
                             interpret=True)
    ref = fa._reference_qkv(qkv, bias, jax.random.key(0), H, **st)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    w = jnp.asarray(rng.randn(1, S, H * D).astype(np.float32))
    stt = tuple(st.items())
    g = jax.grad(lambda x: jnp.sum(flash_tiled(
        x, bias, jnp.zeros(2, jnp.uint32), H, D, stt, True) * w))(qkv)
    gr = jax.grad(lambda x: jnp.sum(fa._reference_qkv(
        x, bias, jax.random.key(0), H, **st) * w))(qkv)
    scale = np.abs(np.asarray(gr)).max()
    np.testing.assert_allclose(np.asarray(g) / scale, np.asarray(gr) / scale,
                               atol=1e-4)


def test_saved_lse_wired_into_grad_op(monkeypatch):
    """r4: when the build-time predicate says the tiled kernel will run,
    the grad maker wires the forward's saved (Out, Lse) into the
    dedicated grad op so the backward skips its forward re-run. The
    predicate is TPU-only, so force it here and assert graph structure."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.framework import unique_name
    from paddle_tpu.ops import fused as fused_ops

    monkeypatch.setattr(fused_ops, "_qkv_tiled_at_build",
                        lambda op, block: True)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), unique_name.guard():
        x = fluid.data("x", [1, 2048, 64], "float32")
        qkv = layers.fc(x, 3 * 8 * 64, num_flatten_dims=2)  # param -> grads
        out = layers.fused_qkv_attention(qkv, 8, causal=True)
        loss = layers.reduce_mean(out)
        fluid.optimizer.SGD(0.1).minimize(loss, startup)
    grad_ops = [op for op in main.global_block.ops
                if op.type == "fused_qkv_attention_grad"]
    assert grad_ops, "no dedicated grad op emitted"
    g = grad_ops[0]
    assert g.inputs.get("Out") and g.inputs.get("Lse"), g.inputs
    fwd = [op for op in main.global_block.ops
           if op.type == "fused_qkv_attention"][0]
    assert g.inputs["Lse"] == fwd.outputs["Lse"]
    assert g.inputs["Out"] == fwd.outputs["Out"]
