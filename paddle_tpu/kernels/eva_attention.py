"""EVA decode attention: the live slots of a window's ring and the visible
chunk summaries before the window, in ONE online softmax.

One query token a sequence at position `pos`, against two key sets of
that sequence, each stored as rows (`ops/kv_cache.py::cache_shape`, a
position a row of every KV head's `dh` lanes side by side):

* the ring of the current aligned window, ``[B, slots, nkv * dh]`` K and
  V, position p in slot ``p % slots``; the window is aligned
  (``w(t) = t // window``), so its live slots are ``0 .. pos % slots``;
* the chunk summaries, ``[B, rows, nkv * dh]`` K and V, row c the
  summary of positions ``c * chunk .. c * chunk + chunk - 1``, of which
  the rows of the windows before pos's are visible:
  ``(pos // window) * (window // chunk)``.

    scores = [Qbd K_ring^T | Qbd K_sum^T] * scale     (one softmax)
    out    = softmax(scores) . [V_ring ; V_sum]

The grid is (sequence, ring block, then summary block): the ring's blocks
come first and the summaries' after them, through the same running
maximum, sum and accumulator (float32), so the two sets are one softmax
exactly. The position is scalar-prefetched; a block that lies wholly
beyond its set's live range is skipped WITH its block index frozen at the
last live block's, so the pipeline fetches nothing for it
(`kernels/decode_attention.py`, whose layout this kernel shares: the
query laid block-diagonally over the whole lane width, so that a head's
lanes are never taken apart and every cache byte is read once).
Probabilities are cast to the caches' dtype before the value product.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .decode_attention import _MASKED, VMEM_LIMIT_BYTES, _product

# bytes of one K (or V) block of either set; the pipeline holds two of
# each of the four
BLOCK_BYTES = 2 * 2 ** 20


def block_rows(rows, row_bytes, sublanes):
    """Rows a block: all of them where they fit `BLOCK_BYTES`, else the
    largest divisor of `rows` in whole sublane tiles that does."""
    if rows * row_bytes <= BLOCK_BYTES:
        return rows
    blk = BLOCK_BYTES // row_bytes // sublanes * sublanes
    while blk >= sublanes and rows % blk:
        blk -= sublanes
    return blk if blk >= sublanes else rows


def live(pos, slots, window, chunk):
    """(the ring's last live slot, the visible summaries) at `pos`."""
    return jax.lax.rem(pos, slots), (pos // window) * (window // chunk)


def _kernel(pos_ref, q_ref, k_ref, v_ref, sk_ref, sv_ref, o_ref, m_ref,
            l_ref, acc_ref, *, nkv, kp, blk, sblk, nr, slots, window, chunk,
            scale):
    j = pl.program_id(1)
    at, visible = live(pos_ref[0], slots, window, chunk)
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

    def update(kb, vb, first, count):
        """The running softmax over the block's rows first .. of the
        set, those below `count` valid."""
        qbd = q_ref[0].astype(jnp.float32)
        if kp > 1:
            qbd = jnp.concatenate(
                [jnp.where(own, qbd[i:i + 1, :], 0.0) for i in range(g)],
                axis=0)
        s = _product(qbd, kb, ((1,), (1,))) * scale          # [R, rows]
        col = first + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < count, s, _MASKED)
        m_old = m_ref[...]
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_old - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + _product(p, vb, ((1,), (0,)))
        m_ref[...] = m_new

    @pl.when(j <= at // blk)
    def _():
        update(k_ref[0], v_ref[0], j * blk, at + 1)

    @pl.when((j >= nr) & ((j - nr) * sblk < visible))
    def _():
        update(sk_ref[0], sv_ref[0], (j - nr) * sblk, visible)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        out = acc_ref[...] / l_ref[...]
        if kp == 1:
            o_ref[0] = out.astype(o_ref.dtype)
        else:
            for i in range(g):
                mine = jnp.where(own, out[i * kp:(i + 1) * kp], 0.0)
                o_ref[0, i:i + 1, :] = jnp.sum(
                    mine, axis=0, keepdims=True).astype(o_ref.dtype)


def attend(q, k, v, sk, sv, pos, *, num_kv_heads, scale, window, chunk,
           interpret=False):
    """q [B, nh * dh] (one token a sequence, at position `pos`, an int32
    scalar) over the ring k, v [B, slots, nkv * dh] and the summaries
    sk, sv [B, rows, nkv * dh] as stored -> [B, nh * dh] in q's dtype.
    Query head n reads KV head n // (nh / nkv)."""
    b, slots, hk = k.shape
    nkv = int(num_kv_heads)
    dh = hk // nkv
    g = q.shape[1] // hk
    row_bytes, sublanes = hk * k.dtype.itemsize, 32 // k.dtype.itemsize
    # query head k * g + j -> row j, KV head k's lanes
    qj = q.reshape(b, nkv, g, dh).transpose(0, 2, 1, 3).reshape(b, g, hk)
    out = _call(
        jnp.reshape(pos, (1,)).astype(jnp.int32), qj, k, v, sk, sv,
        nkv=nkv, blk=block_rows(slots, row_bytes, sublanes),
        sblk=block_rows(sk.shape[1], row_bytes, sublanes),
        window=int(window), chunk=int(chunk), scale=float(scale),
        interpret=interpret)
    return out.reshape(b, g, nkv, dh).transpose(0, 2, 1, 3).reshape(b, -1)


# The pallas_call sits in a jit of its own: the layers share shapes and
# statics, so a decode step traces and lowers the kernel once
@functools.partial(jax.jit, static_argnames=(
    "nkv", "blk", "sblk", "window", "chunk", "scale", "interpret"))
def _call(pos, qj, k, v, sk, sv, *, nkv, blk, sblk, window, chunk, scale,
          interpret):
    b, slots, hk = k.shape
    g = qj.shape[1]
    kp = 1 if nkv == 1 else -(-nkv // 8) * 8
    nr, ns = slots // blk, sk.shape[1] // sblk

    def ring_block(i, j, pos_ref):
        at, _visible = live(pos_ref[0], slots, window, chunk)
        return i, jnp.minimum(j, at // blk), 0

    def summary_block(i, j, pos_ref):
        _at, visible = live(pos_ref[0], slots, window, chunk)
        last = jnp.maximum(visible - 1, 0) // sblk
        return i, jnp.clip(j - nr, 0, last), 0

    rows = pl.BlockSpec((1, g, hk), lambda i, j, pos_ref: (i, 0, 0))
    ring = pl.BlockSpec((1, blk, hk), ring_block)
    summary = pl.BlockSpec((1, sblk, hk), summary_block)
    return pl.pallas_call(
        functools.partial(
            _kernel, nkv=nkv, kp=kp, blk=blk, sblk=sblk, nr=nr, slots=slots,
            window=window, chunk=chunk, scale=scale),
        name="eva_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, nr + ns),
            in_specs=[rows, ring, ring, summary, summary],
            out_specs=rows,
            scratch_shapes=[pltpu.VMEM((g * kp, 1), jnp.float32),
                            pltpu.VMEM((g * kp, 1), jnp.float32),
                            pltpu.VMEM((g * kp, hk), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((b, g, hk), qj.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(pos, qj, k, v, sk, sv)
