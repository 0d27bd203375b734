"""LayerNorm as Pallas TPU kernels (forward + backward).

Reference parity: operators/layer_norm_op.cc (+ the fused CUDA kernels in
layer_norm_op.cu). Motivation here is HBM traffic, not FLOPs: the jnp
formulation under AMP converts the bf16 activation to fp32 for the
mean/var/normalize chain, and XLA materializes fp32 temporaries between
the passes (profiled as the largest non-matmul cost in the BERT step).
The kernel reads each row block once, keeps the fp32 statistics in
registers, and writes bf16 — one read + one write per pass.

Backward recomputes the row statistics from x (cheaper than saving them:
one extra in-register reduction vs an HBM round-trip of mean/rstd) and
accumulates dscale/dbias across row-blocks in a resident output block
(the grid's row axis is innermost for those outputs).

Shapes: x [R, N] (callers flatten leading dims); N % 128 == 0 and the
row-block divides R. Dispatch mirrors flash_attention: Pallas on TPU,
jnp reference elsewhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .vmem import row_block


def supports(rows: int, n: int, dtype) -> bool:
    return (
        n % 128 == 0
        and n <= 8192
        and rows % 8 == 0
        and jnp.dtype(dtype) in (jnp.dtype(jnp.float32),
                                 jnp.dtype(jnp.bfloat16))
    )


def _row_block(rows, n, dtype):
    """Sized for the backward's three [blk, N] blocks (x, dy, dx); the
    forward (x, y and two [blk, 1] statistics) fits wherever that does."""
    return row_block(rows, n, [dtype] * 3)


def _fwd_kernel(x_ref, scale_ref, bias_ref, y_ref, mean_ref, var_ref, *, eps):
    x = x_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(x), axis=1, keepdims=True) - jnp.square(mean)
    var = jnp.maximum(var, 0.0)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mean) * rstd
    y = xhat * scale_ref[0] + bias_ref[0]
    y_ref[:] = y.astype(y_ref.dtype)
    # stats kept 2-D [rows, 1]: 1-D outputs would need their block tiled
    # to XLA's 1-D layout (T(1024)), which Mosaic rejects
    mean_ref[:] = mean
    var_ref[:] = var


def _bwd_kernel(x_ref, scale_ref, dy_ref, dx_ref, dscale_ref, dbias_ref,
                *, eps):
    x = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    mean = jnp.mean(x, axis=1, keepdims=True)
    var = jnp.mean(jnp.square(x), axis=1, keepdims=True) - jnp.square(mean)
    rstd = jax.lax.rsqrt(jnp.maximum(var, 0.0) + eps)
    xhat = (x - mean) * rstd
    dyw = dy * scale_ref[0].astype(jnp.float32)
    m1 = jnp.mean(dyw, axis=1, keepdims=True)
    m2 = jnp.mean(dyw * xhat, axis=1, keepdims=True)
    dx_ref[:] = (rstd * (dyw - m1 - xhat * m2)).astype(dx_ref.dtype)
    ds = jnp.sum(dy * xhat, axis=0, keepdims=True)
    db = jnp.sum(dy, axis=0, keepdims=True)

    @pl.when(pl.program_id(0) == 0)
    def _init():
        dscale_ref[:] = ds
        dbias_ref[:] = db

    @pl.when(pl.program_id(0) != 0)
    def _acc():
        dscale_ref[:] = dscale_ref[:] + ds
        dbias_ref[:] = dbias_ref[:] + db


def _vec_spec(n):
    return pl.BlockSpec((1, n), lambda r: (0, 0), memory_space=pltpu.VMEM)


def layer_norm_fwd(x2d, scale, bias, eps, interpret=False):
    """(y, mean, var) over rows of x2d [R, N]; scale/bias [N] or None."""
    from .. import observability as _obs

    _obs.add("kernels.layer_norm")
    R, N = x2d.shape
    if scale is None:
        scale = jnp.ones((N,), jnp.float32)
    if bias is None:
        bias = jnp.zeros((N,), jnp.float32)
    blk = _row_block(R, N, x2d.dtype)
    y, mean, var = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=float(eps)),
        name="layer_norm_fwd",
        grid=(R // blk,),
        in_specs=[
            pl.BlockSpec((blk, N), lambda r: (r, 0),
                         memory_space=pltpu.VMEM),
            _vec_spec(N),
            _vec_spec(N),
        ],
        out_specs=[
            pl.BlockSpec((blk, N), lambda r: (r, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((blk, 1), lambda r: (r, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((blk, 1), lambda r: (r, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, N), x2d.dtype),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
            jax.ShapeDtypeStruct((R, 1), jnp.float32),
        ],
        interpret=pltpu.InterpretParams() if interpret else False,
    )(x2d, scale.reshape(1, N), bias.reshape(1, N))
    return y, mean.reshape(R), var.reshape(R)


def layer_norm_bwd(x2d, scale, d_y, eps, interpret=False):
    """(dx, dscale, dbias); statistics recomputed from x2d."""
    R, N = x2d.shape
    if scale is None:
        scale = jnp.ones((N,), jnp.float32)
    blk = _row_block(R, N, x2d.dtype)
    dx, ds, db = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=float(eps)),
        name="layer_norm_bwd",
        grid=(R // blk,),
        in_specs=[
            pl.BlockSpec((blk, N), lambda r: (r, 0),
                         memory_space=pltpu.VMEM),
            _vec_spec(N),
            pl.BlockSpec((blk, N), lambda r: (r, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((blk, N), lambda r: (r, 0),
                         memory_space=pltpu.VMEM),
            _vec_spec(N),
            _vec_spec(N),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((R, N), x2d.dtype),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
            jax.ShapeDtypeStruct((1, N), jnp.float32),
        ],
        interpret=pltpu.InterpretParams() if interpret else False,
    )(x2d, scale.reshape(1, N), d_y)
    return dx, ds.reshape(N), db.reshape(N)


# custom VJP so ANY differentiation path through the Pallas forward works
# (the dedicated layer_norm_grad op is the fast path; the generic __vjp__
# fallback and the dygraph tape differentiate the emitter directly, and a
# pallas_call has no built-in differentiation rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def layer_norm_fwd_diff(x2d, scale, bias, eps, interpret=False):
    return layer_norm_fwd(x2d, scale, bias, eps, interpret)


def _lnd_fwd(x2d, scale, bias, eps, interpret):
    out = layer_norm_fwd(x2d, scale, bias, eps, interpret)
    return out, (x2d, scale)


def _lnd_bwd(eps, interpret, res, cts):
    x2d, scale = res
    dy, dmean, dvar = cts
    dx, ds, db = layer_norm_bwd(x2d, scale, dy, eps, interpret)
    # rare cotangents on the statistics outputs (only when a loss consumes
    # Mean/Variance directly): mean = sum(x)/N, var = E[x^2] - mean^2
    n = x2d.shape[1]
    xf = x2d.astype(jnp.float32)
    mean = jnp.mean(xf, axis=1, keepdims=True)
    extra = dmean[:, None] / n + dvar[:, None] * 2.0 * (xf - mean) / n
    dx = (dx.astype(jnp.float32) + extra).astype(dx.dtype)
    return dx, ds.astype(scale.dtype), db.astype(scale.dtype)


layer_norm_fwd_diff.defvjp(_lnd_fwd, _lnd_bwd)


def reference_fwd(x2d, scale, bias, eps):
    """jnp oracle with identical math (fp32 stats, E[x^2]-E[x]^2)."""
    xf = x2d.astype(jnp.float32)
    mean = jnp.mean(xf, axis=1, keepdims=True)
    var = jnp.maximum(
        jnp.mean(jnp.square(xf), axis=1, keepdims=True) - jnp.square(mean),
        0.0,
    )
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    if scale is not None:
        y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x2d.dtype), mean[:, 0], var[:, 0]
