"""Decode attention over a KV cache stored as rows, read in place.

One query token a sequence against that sequence's keys and values as
`ops/kv_cache.py::cache_shape` stores them, ``[B, slots, nkv * dh]``: a
position is one contiguous row of every KV head's `dh` lanes side by side,
which is what lets a decode step WRITE its token as a row. XLA's two
products cannot read that layout without transposing the cache; this
kernel can, because it never takes a head's lanes apart:

    scores [R, S] = Qbd [R, nkv * dh] . K [S, nkv * dh]^T      (NT)
    out    [R, nkv * dh] = softmax(scores) [R, S] . V          (NN)

over the WHOLE lane width, with the query laid block-diagonally: row
``j * kp + k`` of `Qbd` holds query head ``k * g + j`` in KV head k's own
`dh` lanes and zero elsewhere (`g = nh / nkv` query heads share a KV
head; `kp` = nkv rounded up to a sublane tile, the rows beyond nkv all
zero). So the g query heads that read a KV head are g rows of ONE
product, each cache byte is read once, and of the value product's
``[R, nkv * dh]`` result a row's own lanes are the head's output: masked
with the same diagonal and summed over the `kp` sublanes they land as
``[g, nkv * dh]``, which for `g == 1` (GPT-2) is the layer's ``[nh * dh]``
row as it is and for grouped heads one small transposition of the
result away from it.

A layer may have ONE cache whose rows hold the values too (latent
attention in its absorbed form: a row is the key/value latent beside the
shared rotary key part, and the values are the latent, the row's leading
`value_width` lanes). Then there is one cache operand, a block is
fetched ONCE and feeds both products (two operands on one array would
move every byte twice), and with one KV head nothing is laid
block-diagonally and no query row is padding: `kp` is 1 and the
`g` = 128 query heads are the product's 128 rows (padded to a sublane
tile of KV heads they would be 1,024, 896 of them zero, on a call that
sits at the chip's ridge: 128 heads x (640 + 512) lanes x 2 operations
over 1,280 bytes a slot). Several KV heads in one shared array multiply
the whole row and keep each head's leading lanes afterwards.

The grid is (sequence, block of slots). Scores, the running maximum and
sum and the accumulator are float32 (the online softmax of the flash
kernels); probabilities are cast to the cache's dtype before the value
product, as `ops/kv_cache.py::grouped_attention` casts them. The position
is scalar-prefetched: the validity of `ops/kv_cache.py::attention_mask`
(written yet, ring, window) is computed from it in the kernel, and a
block that lies wholly beyond the position is skipped WITH its block
index frozen at the last live block's, so the pipeline fetches nothing
for it (PR 27's lesson, `moe_gmm`).

float32 caches (GPT-2) are multiplied as float32 (`Precision.HIGHEST`),
as the `jnp` form's products on the vector unit were: at 16 query rows the
matrix unit's passes hide under the block's DMA (on the chip the call takes
the same 0.54 ms with one bfloat16 pass, with two parts a product and with
the native float32 product; PERF.md, Findings PR 32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# bytes of one K (or V) block; the pipeline holds two of each
BLOCK_BYTES = 4 * 2 ** 20
VMEM_LIMIT_BYTES = 64 * 2 ** 20
_MASKED = -1e9


def slot_block(slots, row_bytes, sublanes):
    """Slots a block: all of them where they fit `BLOCK_BYTES`, else the
    largest divisor of `slots` in whole sublane tiles that does."""
    if slots * row_bytes <= BLOCK_BYTES:
        return slots
    blk = BLOCK_BYTES // row_bytes // sublanes * sublanes
    while blk >= sublanes and slots % blk:
        blk -= sublanes
    return blk if blk >= sublanes else slots


def _product(a, b, dims):
    """`a` (float32, a few rows) against a block of the cache `b`, in the
    cache's dtype, into float32."""
    exact = jax.lax.Precision.HIGHEST if b.dtype == jnp.float32 else None
    return jax.lax.dot_general(
        a.astype(b.dtype), b, (dims, ((), ())), precision=exact,
        preferred_element_type=jnp.float32)


def _kernel(pos_ref, q_ref, k_ref, *refs, nkv, kp, blk, slots, window, scale,
            prob_scale):
    # one cache operand: the values are lanes of the key rows
    v_ref = refs[0] if len(refs) == 5 else None
    o_ref, m_ref, l_ref, acc_ref = refs[-4:]
    j = pl.program_id(1)
    pos = pos_ref[0]
    g, hk = q_ref.shape[1:]
    dh = hk // nkv
    if kp > 1:
        head = jax.lax.broadcasted_iota(jnp.int32, (kp, hk), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (kp, hk), 1)
        own = (lane >= head * dh) & (lane < (head + 1) * dh)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, -jnp.inf, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(j <= _last_live(pos, slots, blk))
    def _():
        qbd = q_ref[0].astype(jnp.float32)
        if kp > 1:
            qbd = jnp.concatenate(
                [jnp.where(own, qbd[i:i + 1, :], 0.0) for i in range(g)],
                axis=0)
        kb = k_ref[0]
        s = _product(qbd, kb, ((1,), (1,))) * scale         # [R, blk]
        # slot c holds position pos - age, age = (pos - c) mod slots
        col = j * blk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        at = jax.lax.rem(pos, slots)
        age = jnp.where(col <= at, at - col, at - col + slots)
        valid = age <= pos
        if window:
            valid = valid & (age < window)
        s = jnp.where(valid, s, _MASKED)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc = alpha * acc_ref[...]
        vb = kb[:, :acc.shape[1]] if v_ref is None else v_ref[0]
        acc_ref[...] = acc + _product(p, vb, ((1,), (0,)))
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        out = acc_ref[...] * (prob_scale / l_ref[...])
        if kp == 1:
            o_ref[0] = out.astype(o_ref.dtype)
        else:
            for i in range(g):
                mine = jnp.where(own, out[i * kp:(i + 1) * kp], 0.0)
                o_ref[0, i:i + 1, :] = jnp.sum(
                    mine, axis=0, keepdims=True).astype(o_ref.dtype)


def _last_live(pos, slots, blk):
    """The last block that holds a slot the query may see: a cache that
    has not wrapped is written up to `pos`."""
    return jnp.minimum(pos, slots - 1) // blk


def attend(q, k, v, pos, *, num_kv_heads, scale, window=0, prob_scale=1.0,
           value_width=None, interpret=False):
    """q [B, nh * dh] (one token a sequence, at position `pos`, an int32
    scalar) over k, v [B, slots, nkv * dh] as stored -> [B, nh * dh] in
    q's dtype. Query head n reads KV head n // (nh / nkv). Without `v`
    the values are the leading `value_width` lanes of each KV head's key
    row (a latent cache): one cache operand, each block fetched once for
    both products, and the result is [B, nh * value_width]."""
    b, slots, hk = k.shape
    nkv = int(num_kv_heads)
    dh = hk // nkv
    g = q.shape[1] // hk
    blk = slot_block(slots, hk * k.dtype.itemsize, 32 // k.dtype.itemsize)
    # lanes of the value product: one KV head's values are a prefix of
    # the row; several heads' lie apart, so the whole row is multiplied
    # and each head's leading lanes are kept below
    wv = int(value_width) if v is None and nkv == 1 else hk
    # query head k * g + j -> row j, KV head k's lanes
    qj = q.reshape(b, nkv, g, dh).transpose(0, 2, 1, 3).reshape(b, g, hk)
    out = _call(
        jnp.reshape(pos, (1,)).astype(jnp.int32), qj, k, v, nkv=nkv,
        blk=blk, wv=wv, window=int(window), scale=float(scale),
        prob_scale=float(prob_scale), interpret=interpret)
    out = out.reshape(b, g, nkv, wv // nkv)
    if v is None:
        out = out[..., :int(value_width)]
    return out.transpose(0, 2, 1, 3).reshape(b, -1)


# The pallas_call sits in a jit of its own: a model's layers share shapes
# and statics, so a decode step traces and lowers the kernel once for each
# kind of layer, not once a layer (kernels/flash_tiled.py, PR 30)
@functools.partial(jax.jit, static_argnames=(
    "nkv", "blk", "wv", "window", "scale", "prob_scale", "interpret"))
def _call(pos, qj, k, v, *, nkv, blk, wv, window, scale, prob_scale,
          interpret):
    b, slots, hk = k.shape
    g = qj.shape[1]
    # one KV head: every query row reads the whole row, nothing to lay
    # block-diagonally and no rows to pad
    kp = 1 if nkv == 1 else -(-nkv // 8) * 8

    def cache_block(i, j, pos_ref):
        return i, jnp.minimum(j, _last_live(pos_ref[0], slots, blk)), 0

    def rows(width):
        return pl.BlockSpec((1, g, width), lambda i, j, pos_ref: (i, 0, 0))

    cache = pl.BlockSpec((1, blk, hk), cache_block)
    caches = (k,) if v is None else (k, v)
    return pl.pallas_call(
        functools.partial(
            _kernel, nkv=nkv, kp=kp, blk=blk, slots=slots, window=window,
            scale=scale, prob_scale=prob_scale),
        name="decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, slots // blk),
            in_specs=[rows(hk)] + [cache] * len(caches),
            out_specs=rows(wv),
            scratch_shapes=[pltpu.VMEM((g * kp, 1), jnp.float32),
                            pltpu.VMEM((g * kp, 1), jnp.float32),
                            pltpu.VMEM((g * kp, wv), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, g, wv), qj.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(pos, qj, *caches)
