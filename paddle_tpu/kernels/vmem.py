"""Row-block sizing for the row-wise Pallas kernels (fused_residual,
layer_norm) against Mosaic's scoped-VMEM limit.

A grid step of those kernels holds, in VMEM at once: every [blk, N] input
and output block TWICE (the pipeline double-buffers each BlockSpec'd
operand) plus the fp32 [blk, N] values the body keeps live across its row
reductions (z / zhat / dy*w ...). The compiler refuses the kernel
(RESOURCE_EXHAUSTED "scoped vmem") when that exceeds its limit, so the row
block must shrink as N grows. tests/test_tpu_compile.py compiles every
supports() corner against a described v5e and holds this model to it.
"""

from __future__ import annotations

import jax.numpy as jnp

# Mosaic's default scoped-VMEM limit on TPU v5e ("limit 16.00M" in the
# compiler's own message) — the smallest default of the chips in use
SCOPED_VMEM_BYTES = 16 * 2**20
# rows per grid step when VMEM is no constraint (N <= 1024)
MAX_ROW_BLOCK = 256
# fp32 [blk, N] temporaries budgeted for the kernel body. Measured by
# compiling for v5e: the forward kernels need 1-2, the backward kernels
# 3-4; the larger figure serves both so a forward/backward pair always
# agrees on the block (fused_residual's dropout mask depends on it)
_F32_TEMPS = 4


def row_block(rows: int, n: int, block_dtypes) -> int:
    """Largest power-of-two row block <= MAX_ROW_BLOCK that fits VMEM,
    halved further until it divides `rows` (a `rows` below the cap is its
    own block). `block_dtypes`: dtype of every [blk, n] input and output
    block of the kernel's widest (backward) call."""
    per_row = n * (
        2 * sum(jnp.dtype(d).itemsize for d in block_dtypes)
        + 4 * _F32_TEMPS
    )
    blk = MAX_ROW_BLOCK
    while blk > 1 and blk * per_row > SCOPED_VMEM_BYTES:
        blk //= 2
    blk = min(blk, rows)
    while rows % blk:
        blk //= 2
    return blk


# -- the resident super-block of the KV-tiled flash kernels (flash_tiled) --
# What those kernels state as `vmem_limit_bytes`: they keep a whole lane
# group's K and V (fwd, dq) or Q, dO, lse, delta (dkv) in VMEM and loop
# over it, which Mosaic's 16 MiB default cannot hold at S=4096. Half of a
# v5e core's 128 MiB
RESIDENT_VMEM_LIMIT_BYTES = 64 * 2**20
# of it, left to the kernel body: the [tile, tile] fp32 temporaries of a
# tile's element-wise chain (tile <= 512: 1 MiB each, 8-10 live in dkv),
# the grid axis's own blocks and the scratch accumulators
_BODY_BYTES = 16 * 2**20


def resident_rows(rows: int, blk: int, row_bytes: int) -> int:
    """Rows R of the super-block a kernel may keep resident: the largest
    whole share of `rows`, in blocks of `blk`, whose operands
    (`row_bytes` a row, all resident operands together, double-buffered
    by the pipeline) fit beside the body. R == rows wherever that fits;
    beyond it the kernel's last grid axis walks rows // R super-blocks."""
    n = rows // blk
    for parts in range(1, n + 1):
        if n % parts == 0 and (
            2 * (rows // parts) * row_bytes
            <= RESIDENT_VMEM_LIMIT_BYTES - _BODY_BYTES
        ):
            return rows // parts
    return blk
