"""Grouped matrix product for routed experts: rows sorted by expert, each
expert's rows padded to whole row tiles, one weight matrix per tile.

    out[i*tm:(i+1)*tm] = x[i*tm:(i+1)*tm] @ w[tile_expert[i]]   for i < num_active

`tile_expert` and `num_active` are scalar-prefetched, so the weight
block's index is known before the body runs and the pipeline fetches the
right expert's block. Because every tile lies inside one expert's group,
the body is a plain product with no masking. The sorted buffer is sized
for the worst case (every assignment local), so most of its tiles are
usually past `num_active`: those steps are skipped, and all three of
their block indices (rows, weights, output) are those of the LAST active
step, so the pipeline fetches nothing and writes nothing back for them
(PR 27: with only the expert frozen, the column index still moved and
every skipped step fetched a 6 MB weight block: 2.4 of a decode layer's
3.6 ms). Rows of skipped tiles are never written; the caller never reads
them. Weights of experts no row was sent to are never read.

The grid is (row tiles, column blocks); a step holds the tile's whole K
(both products of an expert layer contract 3072 or less at the published
widths), so there is no accumulator to carry.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# bytes of one weight block (double-buffered by the pipeline)
WEIGHT_BLOCK_BYTES = 6 * 2 ** 20
VMEM_LIMIT_BYTES = 48 * 2 ** 20


def column_block(k, n, itemsize, budget=WEIGHT_BLOCK_BYTES):
    """Widest multiple of 128 dividing `n` whose [k, tn] block fits."""
    if n % 128:
        return n
    tn = max(128, min(n, budget // (k * itemsize) // 128 * 128))
    while n % tn:
        tn -= 128
    return tn


def _kernel(tile_expert_ref, num_active_ref, x_ref, w_ref, o_ref):
    del tile_expert_ref

    @pl.when(pl.program_id(0) < num_active_ref[0])
    def _():
        o_ref[...] = jnp.dot(
            x_ref[...], w_ref[0], preferred_element_type=jnp.float32
        ).astype(o_ref.dtype)


def gmm(x, w, tile_expert, num_active, tm, interpret=False):
    """x [M, K] (M a multiple of `tm`), w [E, K, N], tile_expert [M // tm]
    int32, num_active [1] int32 -> [M, N] in x's dtype; rows of skipped
    tiles are left as they were allocated."""
    m, k = x.shape
    _, _, n = w.shape
    tn = column_block(k, n, w.dtype.itemsize)
    last_col = n // tn - 1

    def row(i, na):
        return jnp.minimum(i, jnp.maximum(na[0] - 1, 0))

    def col(i, j, na):
        return jnp.where(i < na[0], j, last_col)

    return pl.pallas_call(
        _kernel,
        name="moe_gmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(m // tm, n // tn),
            in_specs=[
                pl.BlockSpec((tm, k), lambda i, j, te, na: (row(i, na), 0)),
                pl.BlockSpec((1, k, tn),
                             lambda i, j, te, na: (te[i], 0, col(i, j, na))),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda i, j, te, na: (row(i, na), col(i, j, na))),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(tile_expert, num_active, x, w)


def gmm_reference(x, w, tile_expert, num_active, tm):
    """The same product in `jnp` (the CPU path): every tile against its
    expert's matrix, skipped tiles zero."""
    m, k = x.shape
    tiles = m // tm
    xt = x.reshape(tiles, tm, k)
    from ..ops._helpers import einsum_f32

    out = einsum_f32("itk,ikn->itn", xt, w[tile_expert])
    live = jnp.arange(tiles)[:, None, None] < num_active[0]
    return jnp.where(live, out, 0.0).astype(x.dtype).reshape(m, -1)
