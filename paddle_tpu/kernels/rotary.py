"""Rotate-half rotary positions over a prefill's rows where they lie.

What `ops/llm.py::rotary` computes, forward only, for a call whose rows
make whole blocks: x ``[B, T, heads * head_dim]`` is read as the 2-D
array ``[B * T, width]`` it is in HBM (the projection's own row-major
layout, a free reshape) and written back in the same layout. The `jnp`
form views x as ``[B, T, heads, head_dim]``, cuts each head's rotary
group in halves and joins them again; XLA lays that view out
sequence-minor, so its fusion pays an operand-sized transposing copy in
and out (PERF.md 7 c3).

Here, in float32 and in VMEM, with one rounding to x's dtype at the end:

    out = x * cos + roll(x, +half) * sin_a + roll(x, -half) * sin_b

over a unit of ``lcm(head_dim, 128)`` lanes (whole heads and whole lane
tiles: 128 for heads of 128, 384 = two heads of 192, 256 for a head of
256). `roll(x, +half)` puts a group's first half under its second, where
``sin_a`` is +sin; `roll(x, -half)` the second under the first, where
``sin_b`` is -sin. ``cos`` is 1 and both sines are 0 on the lanes that
pass, and a roll that crosses a head's or a group's edge meets a 0 in
the table. So one body serves the whole head, its last `rotary_dim`
lanes (dots_vlm: 64 of 192, YaRN) and its leading ones (Qwen3-Next: 64
of 256), and the arithmetic is the `jnp` form's term for term:
``x1 cos - x2 sin``, ``x2 cos + x1 sin``, each lane's one other term a
product with 0.

The grid is (row block, lane block), lanes innermost. A row block is
the largest share of a sequence in rows of 16 up to `MAX_ROWS` (896 of
the cells' 896: one sequence); a lane block the most units that keep
x's block within `BLOCK_BYTES`. The three float32 tables ``[T, unit]``
(`ops/llm.py::rotary_tables`: a few MB, built in XLA from the runtime
first position) are indexed by the row block's place in its sequence
only, so Pallas fetches them once a row block, once a call where a
block is a whole sequence, and every lane block of a row reuses them.
The body walks a block 16 rows at a time, the tables' rows loaded once
for the units of the lane block.

Not taken (`supports`): one row a sequence (a decode step), a width that
the unit does not divide (dots_vlm's shared rotary key: one head of 64
lanes), a sequence that is no multiple of 16 rows, a dtype other than
float32 and bfloat16. The op then runs its `jnp` form.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import vmem as _vmem

LANES = 128
# rows a step of the body: one packed bfloat16 tile
CHUNK = 16
# rows a block at most
MAX_ROWS = 1024
# x's block at most: the lane block grows to it
BLOCK_BYTES = 2 ** 20


def unit(head_dim):
    """Lanes the tables span: whole heads in whole lane tiles."""
    return math.lcm(int(head_dim), LANES)


def supports(seq_len, width, head_dim, dtype):
    """Does the kernel take this call? Shapes and dtype only."""
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16)):
        return False
    return seq_len > 1 and seq_len % CHUNK == 0 \
        and width % unit(head_dim) == 0


def blocks(seq_len, width, u, dtype):
    """(rows, lanes) of a block: the largest share of the sequence in
    rows of `CHUNK` up to `MAX_ROWS`, and the most units of `u` lanes
    dividing the width that keep x's block within `BLOCK_BYTES` (one at
    least)."""
    rows = max(r for r in range(CHUNK, min(seq_len, MAX_ROWS) + 1, CHUNK)
               if seq_len % r == 0)
    fit = max(1, BLOCK_BYTES // (rows * u * jnp.dtype(dtype).itemsize))
    n = max(k for k in range(1, min(fit, width // u) + 1)
            if (width // u) % k == 0)
    return rows, n * u


def _kernel(x_ref, cos_ref, sin_a_ref, sin_b_ref, o_ref, *, half):
    rows, lanes = x_ref.shape
    u = cos_ref.shape[1]

    def step(i, carry):
        at = pl.ds(pl.multiple_of(i * CHUNK, CHUNK), CHUNK)
        cos, sin_a, sin_b = cos_ref[at, :], sin_a_ref[at, :], sin_b_ref[at, :]
        for c in range(lanes // u):
            x = x_ref[at, c * u:(c + 1) * u].astype(jnp.float32)
            out = (x * cos + pltpu.roll(x, half, 1) * sin_a
                   + pltpu.roll(x, u - half, 1) * sin_b)
            o_ref[at, c * u:(c + 1) * u] = out.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, rows // CHUNK, step, 0)


# The pallas_call sits in a jit of its own: a model's layers share shapes,
# so a prefill traces and lowers the kernel once, not once a layer
@functools.partial(jax.jit, static_argnames=("half", "interpret"))
def rotate(x, cos, sin_a, sin_b, *, half, interpret=False):
    """x [B, T, width] -> the same, turned by the tables [T, unit]
    (`ops/llm.py::rotary_tables`) whose rotary groups are `2 * half`
    lanes; `supports` must hold."""
    b, t, w = x.shape
    u = cos.shape[1]
    rows, lanes = blocks(t, w, u, x.dtype)
    per_seq = t // rows
    block = pl.BlockSpec((rows, lanes), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM)
    table = pl.BlockSpec((rows, u), lambda i, j: (i % per_seq, 0),
                         memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_kernel, half=half),
        name="rotary",
        grid=(b * per_seq, w // lanes),
        in_specs=[block, table, table, table],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((b * t, w), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_vmem.RESIDENT_VMEM_LIMIT_BYTES,
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(x.reshape(b * t, w), cos, sin_a, sin_b)
    return out.reshape(b, t, w)
