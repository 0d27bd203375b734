"""Causal prefill attention over a call's own rows, its scores kept in VMEM.

What `ops/llm.py::causal_gqa_attention` computes, forward only:

    out = softmax(scale * Q K^T, causal) * prob_scale . V

with the operands as the op has them, ``Q [rows, S, nh * dk]``,
``K [rows, S, nkv * dk]``, ``V [rows, S, nkv * dv]`` ->
``[rows, S, nh * dv]``, read in place by lane blocks: query head n reads
KV head ``n // (nh / nkv)``, a value head may be narrower than a key head
(latent attention expanded: dk 192, dv 128). The blocked `jnp` form writes
a block's float32 ``[queries, keys]`` scores to HBM and reads them back
four to five times (mask, max, exp, sum, cast); here a score tile lives in
VMEM between its two products and never leaves it.

The grid is (row, lane block of KV heads, query head of the group,
super-block of queries). A lane block is the fewest KV heads whose keys
AND values are whole 128-lane tiles: one head of 128 lanes (Trinity,
Nemotron), two of 64 (GPT-2), two of 192 / 128 (384 key lanes, 256 value
lanes: dots_vlm); two heads are sliced apart in the kernel as
`flash_tiled.py` slices its two. The lane block's K and V of the WHOLE
sequence are resident: their block index moves with the first two grid
axes only, so they are fetched once for the `g = nh / nkv` query heads
that share them and for every super-block. A super-block is the largest
whole share of the sequence up to 1,024 queries (896 of the cells' 896:
one grid step a head). Inside it everything is static: a block of 128
queries takes the key blocks before it and its own, the diagonal one, as
ONE ``[128, keys]`` tile under the triangle col <= row, so a sequence
of one super-block needs no running state at all (a plain softmax a query
block, the result straight out), and the live tiles are the only ones
computed: 28 of 49 at seven blocks (`tiles_visited` / `tiles_computed`).
A longer sequence walks the super-blocks of keys before its own in a
`fori_loop`, unmasked, with the online softmax's maximum, sum and
accumulator in scratch. Both ranges are `flash_tiled._walk`'s, by import:
one owner of the causal block ranges (PR 30).

Measured alone (PERF.md, Findings PR 34): the first form of this kernel
walked key blocks of 128 under each query block with the state in
scratch, as the training kernels do, and ran at 32-60 G score elements a
second; what bound it was the state's traffic and the misaligned head
slices a tile, not the products. One tile a query block runs at 145-186.

Arithmetic is the `jnp` form's (`ops/kv_cache.py::grouped_attention`):
scores, maximum, sum, the exponential and the accumulator in float32
(without the training kernels' bfloat16 exp tail: the serving cells'
routing comparisons sit on these logits), probabilities cast to V's dtype
before the value product, `prob_scale` applied once on the way out as
`decode_attention.py` does. The products run at the backend's default
precision, as the `jnp` form's `einsum_f32` does: bfloat16 operands as
stored; float32 operands (GPT-2) in one bfloat16 pass on the chip, which
is what XLA compiles the parent's products to (its HLO converts both
operands to bfloat16 and states no `operand_precision`), and exactly
under `interpret` on the CPU.

Not taken (`supports`): a window that binds (0 < window < S: one mask
description first, ROADMAP C7), an S that 128 does not divide, a head
width that is no multiple of 64 lanes, grouped heads narrower than a lane
tile, a dtype other than float32 and bfloat16, K and V too long to stay
resident. The op then runs its blocked `jnp` form.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import vmem as _vmem
from .flash_tiled import NEG_INF, _tile_counts, _walk

# queries and keys a block: the diagonal block's triangle is static
BLOCK = 128
LANES = 128
# query rows a grid step owns at most
MAX_ROWS = 1024


def heads_a_block(num_kv_heads, *widths):
    """KV heads side by side in one lane block: the fewest at which every
    one of a head's `widths` (its queries', keys', values' lanes) makes
    whole 128-lane tiles (0: none divides the heads)."""
    for n in (1, 2):
        if all((n * w) % LANES == 0 for w in widths):
            return n if num_kv_heads % n == 0 else 0
    return 0


def supports(seq_len, num_heads, num_kv_heads, key_dim, value_dim, dtype,
             window=0, shared_dim=0):
    """Does the kernel take this call? Shapes and attributes only.
    `key_dim` is a query head's width; with `shared_dim` its last lanes
    are scored against ONE key part all heads share (`attend`)."""
    if window and window < seq_len:
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16)):
        return False
    own = key_dim - shared_dim
    if seq_len % BLOCK or key_dim % 64 or own % 64 or value_dim % 64 \
            or own <= 0 or num_heads % num_kv_heads:
        return False
    hb = heads_a_block(num_kv_heads, key_dim, own, value_dim)
    if hb > 1 and num_heads != num_kv_heads:
        return False        # grouped heads narrower than a lane tile
    # K and V of a lane block stay resident, double-buffered
    resident = 2 * seq_len * (hb * (own + value_dim) + shared_dim) \
        * jnp.dtype(dtype).itemsize
    return hb > 0 and resident <= _vmem.RESIDENT_VMEM_LIMIT_BYTES // 2


def _product(a, b, dims):
    """float32 result at the backend's default precision."""
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _kernel(q_ref, k_ref, v_ref, *refs, hb, dq, dv, ds, scale, prob_scale):
    # with a shared key part: its rows, [S, ds], after V; then the output
    # and, for a sequence of several super-blocks, the softmax's state
    refs = list(refs)
    ks_ref = refs.pop(0) if ds else None
    o_ref, *state = refs
    dk = dq - ds
    rows_q = q_ref.shape[1]                 # a super-block of queries
    nb, nsb = rows_q // BLOCK, k_ref.shape[1] // rows_q
    x = pl.program_id(3)

    def attend_block(r, keys, ahead, carried):
        """Query block r of the super-block against the key rows `keys`
        (a slice of the resident K and V), every head of the lane block.
        `ahead`: None where every key precedes every query, else how many
        positions key column 0 lies before the block's first query: the
        static triangle col <= row + ahead. `carried`: the block's
        running maximum, sum and accumulator live in the scratch (a
        sequence of several super-blocks); else these keys are all the
        block will see and the result goes straight out."""
        rows = slice(r * BLOCK, (r + 1) * BLOCK)
        for h in range(hb):
            q = q_ref[0, rows, h * dq:(h + 1) * dq]
            k = k_ref[0, keys, h * dk:(h + 1) * dk]
            v = v_ref[0, keys, h * dv:(h + 1) * dv]
            s = _product(q[:, :dk], k, ((1,), (1,)))        # [128, keys]
            if ds:
                s = s + _product(q[:, dk:], ks_ref[0, keys, :],
                                 ((1,), (1,)))
            s = s * scale
            if ahead is not None:
                row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                s = jnp.where(col <= row + ahead, s, NEG_INF)
            m = jnp.max(s, axis=1, keepdims=True)
            if carried:
                m_scr, l_scr, acc_scr = state
                m_old = m_scr[h, rows]
                m = jnp.maximum(m_old, m)
                alpha = jnp.exp(m_old - m)
                m_scr[h, rows] = m
            p = jnp.exp(s - m)
            l = jnp.sum(p, axis=1, keepdims=True)
            acc = _product(p.astype(v.dtype), v, ((1,), (0,)))
            if carried:
                l = l_scr[h, rows] = alpha * l_scr[h, rows] + l
                acc = acc_scr[h, rows] = alpha * acc_scr[h, rows] + acc
            if ahead is not None:       # the block's last keys
                o_ref[0, rows, h * dv:(h + 1) * dv] = (
                    acc * (prob_scale / l)).astype(o_ref.dtype)

    def key_rows(first, c0, c1):
        return pl.ds(pl.multiple_of((first * nb + c0) * BLOCK, BLOCK),
                     (c1 - c0) * BLOCK)

    # the super-blocks of keys before the queries': no mask
    lo, hi, d = _walk(x, 0, nsb, True, before=True)
    if nsb > 1:
        for ref, zero in zip(state, (NEG_INF, 0.0, 0.0)):
            ref[...] = jnp.full(ref.shape, zero, jnp.float32)

        def before(j, _):
            for r in range(nb):
                attend_block(r, key_rows(j, 0, nb), None, True)

        jax.lax.fori_loop(lo, hi, before, None)
    # the queries' own super-block, all static: a query block against
    # the key blocks before it and its own, the diagonal one, as ONE
    # tile under the triangle
    for r in range(nb):
        first, live, diag = _walk(r, 0, nb, True, before=True)
        attend_block(r, key_rows(d, first, diag + 1), (live - first) * BLOCK,
                     nsb > 1)


def super_block(seq_len):
    """Query rows a grid step owns: the largest whole share of the
    sequence in blocks of 128 up to `MAX_ROWS` (896 of 896, 1024 of
    4096). Inside it the causal structure is static."""
    blocks = seq_len // BLOCK
    for parts in range(1, blocks + 1):
        if blocks % parts == 0 and seq_len // parts <= MAX_ROWS:
            return seq_len // parts
    return BLOCK


def attend(q, k, v, *, num_heads, num_kv_heads, scale, prob_scale=1.0,
           k_shared=None, interpret=False):
    """q [rows, S, nh * dq], k [rows, S, nkv * dk], v [rows, S, nkv * dv]
    -> [rows, S, nh * dv] in q's dtype, causal; `supports` must hold.
    Without `k_shared` dk == dq. With it, [rows, S, ds], a key head is
    [its own dk lanes | the ds lanes every head shares], dq = dk + ds
    (latent attention expanded: the rotary key part), scored as
    q[:dk] . k + q[dk:] . k_shared, so the shared part is never copied
    into the heads."""
    from .. import observability as _obs

    blocks = q.shape[1] // BLOCK
    visited, computed = _tile_counts(blocks, blocks, True)
    _obs.set_gauge("kernels.prefill_attention.tiles_visited", visited)
    _obs.set_gauge("kernels.prefill_attention.tiles_computed", computed)
    return _call(q, k, v, k_shared, nh=int(num_heads), nkv=int(num_kv_heads),
                 scale=float(scale), prob_scale=float(prob_scale),
                 interpret=interpret)


# The pallas_call sits in a jit of its own: a model's layers share shapes
# and statics, so a prefill traces and lowers the kernel once, not once a
# layer (kernels/flash_tiled.py, PR 30)
@functools.partial(jax.jit, static_argnames=(
    "nh", "nkv", "scale", "prob_scale", "interpret"))
def _call(q, k, v, k_shared, *, nh, nkv, scale, prob_scale, interpret):
    b, s, _ = q.shape
    dq, dk, dv = q.shape[2] // nh, k.shape[2] // nkv, v.shape[2] // nkv
    ds = dq - dk
    g = nh // nkv
    hb = heads_a_block(nkv, dq, dk, dv)
    rows = super_block(s)

    # lane block n of K and V holds the KV heads of query lane blocks
    # n * g .. n * g + g - 1 (`supports`: hb == 1 or g == 1)
    def own(width):
        return pl.BlockSpec((1, rows, hb * width),
                            lambda r, n, i, x: (r, x, n * g + i),
                            memory_space=pltpu.VMEM)

    def resident(width):
        return pl.BlockSpec((1, s, hb * width),
                            lambda r, n, i, x: (r, 0, n),
                            memory_space=pltpu.VMEM)

    shared = [pl.BlockSpec((1, s, ds), lambda r, n, i, x: (r, 0, 0),
                           memory_space=pltpu.VMEM)] if ds else []
    return pl.pallas_call(
        functools.partial(_kernel, hb=hb, dq=dq, dv=dv, ds=ds, scale=scale,
                          prob_scale=prob_scale),
        name="prefill_attention",
        grid=(b, nkv // hb, g, s // rows),
        in_specs=[own(dq), resident(dk), resident(dv)] + shared,
        out_specs=own(dv),
        out_shape=jax.ShapeDtypeStruct((b, s, nh * dv), q.dtype),
        # the online softmax's state, where a sequence is several
        # super-blocks
        scratch_shapes=[pltpu.VMEM((hb, rows, w), jnp.float32)
                        for w in ((1, 1, dv) if s > rows else ())],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=_vmem.RESIDENT_VMEM_LIMIT_BYTES,
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(q, k, v, *([k_shared] if ds else []))
