"""Fused dropout + residual add + LayerNorm as Pallas TPU kernels.

The transformer residual tail `LN(x + dropout(y))` appears twice per
encoder layer. Composed, it costs XLA four+ HBM passes per site (dropout
RNG + apply, mask store, add, fp32 normalization with a separate reduce
pass — profiled ~0.4 ms/site forward on BERT-base, BASELINE.md round 4);
fused it is one read of x and y and one write of out, with the dropout
mask regenerated from the hardware PRNG and the fp32 row statistics held
in registers.

This is the TPU analogue of the reference's hand-fused CUDA residual
kernels (operators/fused/fused_embedding_eltwise_layernorm, and the
add+LN fusions in math/bert_encoder_functor.cu): the fusion XLA cannot
get on its own because the RNG draw and the row reduction sit between
producer and consumer.

Backward recomputes z = x + dropout(y) and the row statistics from the
primal inputs (one extra in-register pass vs an HBM round-trip of
mean/rstd and z), regenerating the identical dropout mask from the same
per-row-block PRNG seeding — so no mask and no intermediate tensor ever
reach HBM.

Shapes: callers flatten to [R, N]; N % 128 == 0, R % 16 == 0 (else the
jnp reference path runs); the row block shrinks with N (kernels/vmem.py). Dropout semantics are fluid's dropout_op.cc,
as in flash_attention.py.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .vmem import row_block


def supports(rows: int, n: int, dtype) -> bool:
    # rows % 16: the dropout keep-mask draws (blk/2, N) PRNG words, and
    # Mosaic refuses a draw of fewer than 8 rows — so the smallest block,
    # and with it every `rows`, is a multiple of 16
    return (
        n % 128 == 0
        and n <= 8192
        and rows % 16 == 0
        and jnp.dtype(dtype) in (jnp.dtype(jnp.float32),
                                 jnp.dtype(jnp.bfloat16))
    )


def _row_block(rows, n, x_dtype, y_dtype):
    """One block size for forward AND backward (the per-row-block PRNG
    seeding must regenerate the identical mask), sized for the backward's
    five [blk, N] blocks: x, y, dout, dx (x's dtype) and dy (y's). At
    N = 8192 fp32 that is 32 rows; at BERT's 768 the full 256."""
    return row_block(rows, n, [x_dtype] * 3 + [y_dtype] * 2)


def _seed_block(seed_ref):
    r = pl.program_id(0).astype(jnp.uint32)
    pltpu.prng_seed(
        seed_ref[0] + r * jnp.uint32(0x9E3779B1),
        seed_ref[1] ^ (r * jnp.uint32(0x85EBCA6B)),
    )


from .prng_mask import keep_mask as _keep_mask  # fwd/bwd mask parity


def _z_block(x_ref, y_ref, seed_ref, rate, is_test, upscale):
    """(fp32 z = x + dropout(y), keep mask or None) for one row block;
    seeds + draws the PRNG exactly once when training with dropout, so
    the forward and backward kernels regenerate the identical mask."""
    x = x_ref[:].astype(jnp.float32)
    y = y_ref[:].astype(jnp.float32)
    keep = None
    if rate > 0.0:
        if is_test:
            y = y if upscale else y * (1.0 - rate)
        else:
            _seed_block(seed_ref)
            keep = _keep_mask(y.shape, rate)
            y = jnp.where(keep, y / (1.0 - rate) if upscale else y, 0.0)
    return x + y, keep


def _fwd_kernel(seed_ref, x_ref, y_ref, g_ref, c_ref, o_ref,
                *, rate, is_test, upscale, eps):
    z, _ = _z_block(x_ref, y_ref, seed_ref, rate, is_test, upscale)
    mean = jnp.mean(z, axis=1, keepdims=True)
    var = jnp.maximum(
        jnp.mean(jnp.square(z), axis=1, keepdims=True) - jnp.square(mean), 0.0
    )
    rstd = jax.lax.rsqrt(var + eps)
    zhat = (z - mean) * rstd
    o_ref[:] = (
        zhat * g_ref[0].astype(jnp.float32) + c_ref[0].astype(jnp.float32)
    ).astype(o_ref.dtype)


def _bwd_kernel(seed_ref, x_ref, y_ref, g_ref, do_ref,
                dx_ref, dy_ref, dg_ref, dc_ref,
                *, rate, is_test, upscale, eps):
    z, keep = _z_block(x_ref, y_ref, seed_ref, rate, is_test, upscale)
    drop_scale = (1.0 / (1.0 - rate)) if upscale else 1.0
    mean = jnp.mean(z, axis=1, keepdims=True)
    var = jnp.maximum(
        jnp.mean(jnp.square(z), axis=1, keepdims=True) - jnp.square(mean), 0.0
    )
    rstd = jax.lax.rsqrt(var + eps)
    zhat = (z - mean) * rstd
    do = do_ref[:].astype(jnp.float32)
    dyw = do * g_ref[0].astype(jnp.float32)
    m1 = jnp.mean(dyw, axis=1, keepdims=True)
    m2 = jnp.mean(dyw * zhat, axis=1, keepdims=True)
    dz = rstd * (dyw - m1 - zhat * m2)
    dx_ref[:] = dz.astype(dx_ref.dtype)
    if rate > 0.0:
        if is_test:
            dy = dz if upscale else dz * (1.0 - rate)
        else:
            dy = jnp.where(keep, dz * drop_scale, 0.0)
    else:
        dy = dz
    dy_ref[:] = dy.astype(dy_ref.dtype)
    dg = jnp.sum(do * zhat, axis=0, keepdims=True)
    dc = jnp.sum(do, axis=0, keepdims=True)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dg_ref[:] = dg
        dc_ref[:] = dc

    @pl.when(pl.program_id(0) != 0)
    def _acc():
        dg_ref[:] = dg_ref[:] + dg
        dc_ref[:] = dc_ref[:] + dc


def _vec_spec(n):
    return pl.BlockSpec((1, n), lambda r: (0, 0), memory_space=pltpu.VMEM)


def _row_spec(blk, n):
    return pl.BlockSpec((blk, n), lambda r: (r, 0), memory_space=pltpu.VMEM)


def fused_dropout_add_ln_fwd(x2d, y2d, g, c, seed, rate, is_test, upscale,
                             eps, interpret=False):
    R, N = x2d.shape
    if g is None:
        g = jnp.ones((N,), jnp.float32)
    if c is None:
        c = jnp.zeros((N,), jnp.float32)
    blk = _row_block(R, N, x2d.dtype, y2d.dtype)
    kern = functools.partial(
        _fwd_kernel, rate=float(rate), is_test=bool(is_test),
        upscale=bool(upscale), eps=float(eps),
    )
    return pl.pallas_call(
        kern,
        name="fused_residual_fwd",
        grid=(R // blk,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            _row_spec(blk, N),
            _row_spec(blk, N),
            _vec_spec(N),
            _vec_spec(N),
        ],
        out_specs=_row_spec(blk, N),
        out_shape=jax.ShapeDtypeStruct((R, N), x2d.dtype),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(seed, x2d, y2d, g.reshape(1, N), c.reshape(1, N))


def fused_dropout_add_ln_bwd(x2d, y2d, g, seed, d_out, rate, is_test,
                             upscale, eps, interpret=False):
    """-> (dx [R,N], dy [R,N], dscale [N] f32, dlnbias [N] f32)."""
    R, N = x2d.shape
    if g is None:
        g = jnp.ones((N,), jnp.float32)
    blk = _row_block(R, N, x2d.dtype, y2d.dtype)
    kern = functools.partial(
        _bwd_kernel, rate=float(rate), is_test=bool(is_test),
        upscale=bool(upscale), eps=float(eps),
    )
    dx, dy, dg, dc = pl.pallas_call(
        kern,
        name="fused_residual_bwd",
        grid=(R // blk,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            _row_spec(blk, N),
            _row_spec(blk, N),
            _vec_spec(N),
            _row_spec(blk, N),
        ],
        out_specs=[
            _row_spec(blk, N),
            _row_spec(blk, N),
            _vec_spec(N),
            _vec_spec(N),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, N), x2d.dtype),
            jax.ShapeDtypeStruct((R, N), y2d.dtype),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
        ],
        interpret=pltpu.InterpretParams() if interpret else False,
    )(seed, x2d, y2d, g.reshape(1, N), d_out)
    return dx, dy, dg.reshape(N), dc.reshape(N)


# differentiable wrapper (dygraph tape / any jax.vjp path); the static
# graph uses the dedicated fused_dropout_add_ln_grad op instead so the
# forward kernel is not replayed (XLA does not CSE custom-calls)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def fused_dropout_add_ln(x2d, y2d, g, c, seed, statics, interpret):
    from .. import observability as _obs

    _obs.add("kernels.fused_dropout_add_ln")
    st = dict(statics)
    return fused_dropout_add_ln_fwd(
        x2d, y2d, g, c, seed, st["rate"], st["is_test"], st["upscale"],
        st["eps"], interpret,
    )


def _fdal_fwd(x2d, y2d, g, c, seed, statics, interpret):
    out = fused_dropout_add_ln(x2d, y2d, g, c, seed, statics, interpret)
    return out, (x2d, y2d, g, c, seed)


def _fdal_bwd(statics, interpret, res, dout):
    x2d, y2d, g, c, seed = res
    st = dict(statics)
    dx, dy, dg, dc = fused_dropout_add_ln_bwd(
        x2d, y2d, g, seed, dout, st["rate"], st["is_test"], st["upscale"],
        st["eps"], interpret,
    )
    dseed = np.zeros(seed.shape, dtype=jax.dtypes.float0)
    return dx, dy, dg.astype(g.dtype), dc.astype(c.dtype), dseed


fused_dropout_add_ln.defvjp(_fdal_fwd, _fdal_bwd)


def reference_fwd(x2d, y2d, g, c, rng_key, rate, is_test, upscale, eps):
    """jnp oracle (CPU path): same math, mask from jax.random."""
    x = x2d.astype(jnp.float32)
    y = y2d.astype(jnp.float32)
    if rate > 0.0:
        if is_test:
            y = y if upscale else y * (1.0 - rate)
        else:
            keep = jax.random.bernoulli(rng_key, 1.0 - rate, y.shape)
            y = jnp.where(keep, y / (1.0 - rate) if upscale else y, 0.0)
    z = x + y
    mean = jnp.mean(z, axis=1, keepdims=True)
    var = jnp.maximum(
        jnp.mean(jnp.square(z), axis=1, keepdims=True) - jnp.square(mean), 0.0
    )
    zhat = (z - mean) * jax.lax.rsqrt(var + eps)
    out = zhat
    if g is not None:
        out = out * g.astype(jnp.float32)
    if c is not None:
        out = out + c.astype(jnp.float32)
    return out.astype(x2d.dtype)
