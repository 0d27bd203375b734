"""Kernels of the routed experts: rows sorted by expert, each expert's
rows padded to whole row tiles, one weight matrix per tile.

    out[i*tm:(i+1)*tm] = act(x[i*tm:(i+1)*tm] @ w[tile_expert[i]])   for i < num_active

The sorted buffer is ALLOCATED for the worst case (every assignment
local: dropless, no capacity), so most of its tiles are usually past
`num_active`. Every kernel here TOUCHES the live tiles only: `gmm` (the
grouped product, with the experts' activation as its epilogue),
`gather_rows` (the tokens' rows into the sorted buffer) and
`combine_rows` (the sorted rows, weighted, back into their tokens). All
three walk the row tiles on a grid axis and take `num_active` by scalar
prefetch; the steps past it are skipped, and ALL their block indices
(rows, weights, output, the tile's slice of `token_of_slot`) are those
of the LAST active step, so the pipeline fetches nothing and writes
nothing back for them (PR 27: with only the expert frozen, the column
index still moved and every skipped step fetched a 6 MB weight block:
2.4 of a decode layer's 3.6 ms). Rows of skipped tiles are never
written and never enter a result; rows that pad a live tile
(`token_of_slot` < 0) go through the products as copies of row 0 and
are left out of the sums. Weights of experts no row was sent to are
never read.

`gmm`'s grid is (row tiles, column blocks); a step holds the tile's
whole K (both products of an expert layer contract 7168 or less at the
published widths), so there is no accumulator to carry. The activation
sees the float32 product of a live tile, before the one cast: `relu2`
of the block itself, `swiglu` of a gate block and the up block of the
same columns (`w` is [E, K, 2F], gate in columns 0..F, up in F..2F: two
block specs over the one array, each as wide as the plain product's
block: at K = 7168 a block of half the width is rows of 256 bytes, and
a decode step's products read their weights 1% slower), and the kernel
writes [M, F].

The chip's compiler takes no DMA of ONE row out of a tiled array (a
slice of the second-minor dimension must be whole tiles), so the two
row kernels move rows inside VMEM: a column block of the tokens (resp.
of the result) stays resident in float32 while the live row tiles
stream past it, and a row is one dynamic-sublane load and store a lane
tile. Their grid is (column blocks, steps of whole row tiles:
`step_rows`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# bytes of one weight block (double-buffered by the pipeline; the gated
# form holds two a step)
WEIGHT_BLOCK_BYTES = 6 * 2 ** 20
VMEM_LIMIT_BYTES = 48 * 2 ** 20
# elements of the [tokens, columns] block a row kernel keeps resident: its
# float32 copy and the pipeline's two buffers are 8 bytes an element
RESIDENT_ELEMENTS = 8 * 2 ** 20
ROWS_VMEM_LIMIT_BYTES = 100 * 2 ** 20
# a row kernel's per-row scalars come to SMEM this many rows at a time
# (XLA tiles a long 1-D array by 1024; a row tile divides it)
SCALAR_BLOCK = 1024


def column_block(k, n, itemsize, budget=WEIGHT_BLOCK_BYTES):
    """Widest multiple of 128 dividing `n` whose [k, tn] block fits."""
    if n % 128:
        return n
    tn = max(128, min(n, budget // (k * itemsize) // 128 * 128))
    while n % tn:
        tn -= 128
    return tn


def _relu2(acc):
    r = jnp.maximum(acc, 0.0)
    return r * r


def _swiglu(gate, up):
    return jax.nn.silu(gate) * up


# the experts' forms on float32 products: (column blocks of `w` a step
# multiplies, what it makes of them); None is the plain product
ACTIVATIONS = {None: (1, lambda acc: acc), "relu2": (1, _relu2),
               "swiglu": (2, _swiglu)}


def _form(name):
    if name not in ACTIVATIONS:
        raise ValueError(f"moe_gmm: no expert activation {name!r}")
    return ACTIVATIONS[name]


def activate(name, *products):
    """The experts' form `name` of its float32 products: non-gated
    `relu2` of one, gated `swiglu` of a gate and an up product."""
    return _form(name)[1](*products)


def _frozen(i, na):
    """A row tile's block index: its own while it is live, the last live
    one's past `num_active`."""
    return jnp.minimum(i, jnp.maximum(na[0] - 1, 0))


def _gmm_kernel(activation, tile_expert_ref, num_active_ref, x_ref, *refs):
    del tile_expert_ref
    *w_refs, o_ref = refs

    @pl.when(pl.program_id(0) < num_active_ref[0])
    def _():
        x = x_ref[...]
        acc = [jnp.dot(x, w[0], preferred_element_type=jnp.float32)
               for w in w_refs]
        o_ref[...] = activate(activation, *acc).astype(o_ref.dtype)


# each entry point is a `jit` of its own, so a program of several expert
# layers traces and lowers a kernel once (PR 30: warm set-up)
@functools.partial(jax.jit, static_argnames=("tm", "activation", "interpret"))
def gmm(x, w, tile_expert, num_active, tm, activation=None, interpret=False):
    """x [M, K] (M a multiple of `tm`), w [E, K, N], tile_expert [M // tm]
    int32, num_active [1] int32 -> act(product) [M, N] in x's dtype
    ([M, N // 2] for the gated `swiglu`); rows of skipped tiles are left
    as they were allocated."""
    m, k = x.shape
    parts, _ = _form(activation)
    n = w.shape[2] // parts
    tn = column_block(k, n, w.dtype.itemsize)
    blocks = n // tn

    def col(i, j, na):
        return jnp.where(i < na[0], j, blocks - 1)

    def weights(first):
        return pl.BlockSpec(
            (1, k, tn),
            lambda i, j, te, na: (te[i], 0, first + col(i, j, na)))

    return pl.pallas_call(
        lambda *refs: _gmm_kernel(activation, *refs),
        name="moe_gmm" + (f"_{activation}" if activation else ""),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(m // tm, blocks),
            in_specs=[
                pl.BlockSpec((tm, k),
                             lambda i, j, te, na: (_frozen(i, na), 0)),
            ] + [weights(part * blocks) for part in range(parts)],
            out_specs=pl.BlockSpec(
                (tm, tn),
                lambda i, j, te, na: (_frozen(i, na), col(i, j, na))),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(tile_expert, num_active, x, *[w] * parts)


def gmm_reference(x, w, tile_expert, num_active, tm, activation=None):
    """The same product in `jnp` (the CPU path): every tile against its
    expert's matrix, the activation on the float32 product, skipped
    tiles zero."""
    m, k = x.shape
    tiles = m // tm
    xt = x.reshape(tiles, tm, k)
    from ..ops._helpers import einsum_f32

    out = einsum_f32("itk,ikn->itn", xt, w[tile_expert])
    parts, form = _form(activation)
    out = form(*jnp.split(out, parts, axis=-1))
    live = jnp.arange(tiles)[:, None, None] < num_active[0]
    return jnp.where(live, out, 0.0).astype(x.dtype).reshape(m, -1)


# ---------------------------------------------------------------------------
# rows into the sorted buffer, and back
# ---------------------------------------------------------------------------

def resident_columns(n_rows, k):
    """Widest multiple of 128 dividing `k` whose [n_rows, columns] block
    a row kernel may keep resident."""
    if k % 128:
        return k
    kb = max(128, min(k, RESIDENT_ELEMENTS // n_rows // 128 * 128))
    while k % kb:
        kb -= 128
    return kb


def step_rows(m, tm):
    """Rows of the buffer one grid step of a row kernel walks: whole
    tiles, as many as divide the buffer, up to the scalars' block (a
    step costs 0.2 us whether its rows are live or not)."""
    rows = tm
    while rows * 2 <= SCALAR_BLOCK and m % (rows * 2) == 0:
        rows *= 2
    return rows


def _each_live_row(rows, tm, num_active_ref, token_ref, no_token, body):
    """`body(row of the block, row of the scalars' block, token)` for
    this step's rows in live tiles, eight at a time with no branch: a
    row that pads its tile names `no_token`."""
    first = pl.program_id(1) * rows
    live = jnp.clip(num_active_ref[0] * tm - first, 0, rows)
    first = first % SCALAR_BLOCK

    def group(g, carry):
        base = pl.multiple_of(g * 8, 8)
        for u in range(8):
            token = token_ref[first + base + u]
            body(base + u, first + base + u,
                 jnp.where(token < 0, no_token, token))
        return carry

    lax.fori_loop(0, live // 8, group, 0)


def _is_live(rows, tm, num_active_ref):
    return pl.program_id(1) * rows < num_active_ref[0] * tm


def _gather_kernel(tm, num_active_ref, token_ref, src_ref, o_ref, src32,
                   block32):
    rows = o_ref.shape[0]

    @pl.when(pl.program_id(1) == 0)
    def _():
        src32[...] = src_ref[...].astype(jnp.float32)

    @pl.when(_is_live(rows, tm, num_active_ref))
    def _():
        def copy(r, _slot, token):
            block32[pl.ds(r, 1), :] = src32[pl.ds(token, 1), :]

        # a padding row copies row 0: whole tiles go to the product
        _each_live_row(rows, tm, num_active_ref, token_ref, 0, copy)
        o_ref[...] = block32[...].astype(o_ref.dtype)


def _combine_kernel(tm, num_active_ref, token_ref, weight_ref, y_ref, o_ref,
                    acc, block32):
    rows, n_tokens = y_ref.shape[0], o_ref.shape[0]

    @pl.when(pl.program_id(1) == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    @pl.when(_is_live(rows, tm, num_active_ref))
    def _():
        block32[...] = y_ref[...].astype(jnp.float32)

        def add(r, slot, token):
            acc[pl.ds(token, 1), :] += (
                weight_ref[slot] * block32[pl.ds(r, 1), :])

        # a padding row lands past the tokens, in rows nobody reads
        _each_live_row(rows, tm, num_active_ref, token_ref, n_tokens, add)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = acc[pl.ds(0, n_tokens), :].astype(o_ref.dtype)


def _rows_call(kernel, name, num_active, scalars, array, whole, spare, tm,
               interpret):
    """A row kernel over the grid (column blocks, steps of `step_rows`
    of the buffer). `scalars` [M] (what every row of the buffer carries)
    ride in SMEM a block at a time. Of the VMEM operand `array` and the
    output, one walks the buffer with the steps and the other is the
    resident block of `whole` rows: the output where `array` is the
    buffer [M, K]. A step past the live rows keeps every block index of
    the last live one. The scratch is the resident block's float32 copy
    (`spare` rows longer) and one step's."""
    m, k = scalars[0].shape[0], array.shape[1]
    kb = resident_columns(whole + spare, k)
    rows = step_rows(m, tm)
    pad = -m % SCALAR_BLOCK
    scalars = [jnp.pad(s, (0, pad), constant_values=-1) for s in scalars]

    def step(i, na):
        return jnp.minimum(i, jnp.maximum((na[0] * tm - 1) // rows, 0))

    walking = pl.BlockSpec((rows, kb), lambda j, i, na: (step(i, na), j))
    resident = pl.BlockSpec((whole, kb), lambda j, i, na: (0, j))
    walks_in = array.shape[0] == m
    return pl.pallas_call(
        kernel,
        name=name,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(k // kb, m // rows),
            in_specs=[
                pl.BlockSpec(
                    (SCALAR_BLOCK,),
                    lambda j, i, na: (step(i, na) * rows // SCALAR_BLOCK,),
                    memory_space=pltpu.SMEM)
                for _ in scalars] + [walking if walks_in else resident],
            out_specs=resident if walks_in else walking,
            scratch_shapes=[pltpu.VMEM((whole + spare, kb), jnp.float32),
                            pltpu.VMEM((rows, kb), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((whole if walks_in else m, k),
                                       array.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=ROWS_VMEM_LIMIT_BYTES,
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(num_active, *scalars, array)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def gather_rows(src, token_of_slot, num_active, tm, interpret=False):
    """src [T, K], token_of_slot [M] int32 (M a multiple of `tm`; < 0
    where a row pads its tile) -> [M, K] with out[r] = src[token_of_slot
    [r]] in the first `num_active` tiles. A padding row copies row 0;
    rows past the last live step (`step_rows`) are left as they were
    allocated, the dead tiles of that step hold whatever."""
    return _rows_call(
        lambda *refs: _gather_kernel(tm, *refs), "moe_rows_gather",
        num_active, [token_of_slot], src, src.shape[0], 0, tm, interpret)


@functools.partial(jax.jit, static_argnames=("tm", "n_tokens", "interpret"))
def combine_rows(y_sorted, token_of_slot, weight_of_slot, num_active, tm,
                 n_tokens, interpret=False):
    """y_sorted [M, K], token_of_slot [M] int32, weight_of_slot [M]
    float32 -> [n_tokens, K] in y_sorted's dtype: out[t] = the float32
    sum of weight x row over the rows of the first `num_active` tiles
    whose token is t, cast once; a token no such row names is zero. No
    other row of `y_sorted` enters a sum (a padding row's lands in the
    accumulator's spare rows)."""
    return _rows_call(
        lambda *refs: _combine_kernel(tm, *refs), "moe_rows_combine",
        num_active, [token_of_slot, weight_of_slot], y_sorted, n_tokens, 8,
        tm, interpret)
