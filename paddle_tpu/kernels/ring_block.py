"""Per-shard flash-attention blocks for sequence parallelism (ring /
Ulysses) — the VMEM-resident inner kernel of parallel/ring_attention.py.

Contract: one K/V *shard* at a time. q is this device's resident query
shard [B, Sq, H*D] (heads packed in lanes, flash_tiled layout); k/v is the
visiting shard [B, Sk, H*D]. Global causal coordinates come in as TRACED
scalars (row0, col0) through SMEM — the ring loop is a lax.scan whose step
index decides which shard is visiting, so the offsets cannot be Python
ints. Outputs are fp32: the caller merges shards with logsumexp weights
(associative online-softmax merge), so per-shard results must not round
to bf16 between steps.

forward:  (o_s, lse_s) — the shard's own normalized attention and row
          logsumexp; a fully-masked row yields o=0, lse=NEG_INF, which the
          logaddexp merge treats as "contributes nothing".
backward: the standard flash two-kernel split given the GLOBAL lse and
          delta=rowsum(do*out): shard_dq accumulates this q-shard's dq
          over the visiting kv; shard_dkv produces dk/dv for the visiting
          shard (they travel around the ring with it).

Unlike flash_tiled there is no bias/dropout plumbing: the SP attention op
(layers.ring_attention) exposes neither, and dropping them halves the
kernel surface. Tile sizes follow flash_tiled (512/256/128 divisors); in
interpret mode any shape is allowed whole-block so the virtual-CPU mesh
tests and the driver dryrun exercise this exact kernel path.

Reference role: greenfield — the reference has no sequence parallelism
(SURVEY.md §5); the kernel structure follows kernels/flash_tiled.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _tile_dim(s: int, interpret: bool) -> int:
    # keep the divisor ladder in sync with flash_tiled._tile (the packed
    # QKV kernels); the interpret whole-block allowance is ring-only
    for b in (512, 256, 128):
        if s % b == 0:
            return b
    return s if interpret else 0


def ring_supports(sq: int, sk: int, num_heads: int, head_dim: int, dtype,
                  interpret: bool) -> bool:
    if not (head_dim and 128 % head_dim == 0):
        return False
    if num_heads % (128 // head_dim):
        return False
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16)):
        return False
    return _tile_dim(sq, interpret) > 0 and _tile_dim(sk, interpret) > 0


def _scores(q, k, row0, col0, qb, kb, BQ, BK, scale, causal):
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    if causal:
        row = row0 + qb * BQ + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = col0 + kb * BK + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col <= row, s, NEG_INF)
    return s


def _live(row0, col0, qb, kb, BQ, BK, causal):
    """False iff the tile is strictly above the global causal diagonal."""
    if not causal:
        return True
    return col0 + kb * BK <= row0 + qb * BQ + (BQ - 1)


# ---------------------------------------------------------------------------
# forward: per-shard online softmax -> (normalized o_s, row lse_s)
# ---------------------------------------------------------------------------


def _fwd_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, D, BQ, BK, scale, causal):
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    nk = pl.num_programs(3)
    row0, col0 = off_ref[0], off_ref[1]
    G = 128 // D

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(_live(row0, col0, qb, kb, BQ, BK, causal))
    def _compute():
        for i in range(G):
            sl = slice(i * D, (i + 1) * D)
            q = q_ref[0, :, sl]
            k = k_ref[0, :, sl]
            v = v_ref[0, :, sl]
            s = _scores(q, k, row0, col0, qb, kb, BQ, BK, scale, causal)
            m_prev = m_scr[:, sl][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            e = jnp.exp(s - m_new)
            if causal:
                # a NEG_INF-masked entry exps to 0 already, but the fully-
                # masked-row case leaves m_new == NEG_INF and e == exp(0):
                # zero those lanes explicitly
                row = row0 + qb * BQ + jax.lax.broadcasted_iota(
                    jnp.int32, e.shape, 0)
                col = col0 + kb * BK + jax.lax.broadcasted_iota(
                    jnp.int32, e.shape, 1)
                e = jnp.where(col <= row, e, 0.0)
            l_prev = l_scr[:, sl][:, :1]
            l_new = l_prev * alpha + jnp.sum(e, axis=-1, keepdims=True)
            pv = jnp.dot(e.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
            acc_scr[:, sl] = acc_scr[:, sl] * alpha + pv
            m_scr[:, sl] = jnp.broadcast_to(m_new, (BQ, D))
            l_scr[:, sl] = jnp.broadcast_to(l_new, (BQ, D))

    @pl.when(kb == nk - 1)
    def _finalize():
        for i in range(G):
            sl = slice(i * D, (i + 1) * D)
            l = l_scr[:, sl][:, :1]
            safe = l > 0.0
            o_ref[0, :, sl] = jnp.where(
                safe, acc_scr[:, sl] / jnp.maximum(l, 1e-30), 0.0
            )
            lse_ref[0, :, sl] = jnp.broadcast_to(
                jnp.where(
                    safe,
                    m_scr[:, sl][:, :1] + jnp.log(jnp.maximum(l, 1e-30)),
                    NEG_INF,
                ),
                (BQ, D),
            )


def _q_spec(BQ):
    return pl.BlockSpec((1, BQ, 128), lambda b, g, qb, kb: (b, qb, g),
                        memory_space=pltpu.VMEM)


def _kv_spec(BK):
    return pl.BlockSpec((1, BK, 128), lambda b, g, qb, kb: (b, kb, g),
                        memory_space=pltpu.VMEM)


def shard_fwd(q, k, v, offs, H, D, causal, scale, interpret):
    """q [B,Sq,H*D], k/v [B,Sk,H*D], offs int32[2] (row0, col0) ->
    (o_s f32 [B,Sq,H*D], lse_s f32 [B,Sq,H*D] column-replicated per head)."""
    B, Sq, HD = q.shape
    assert HD == H * D, (HD, H, D)
    Sk = k.shape[1]
    NG = HD // 128
    BQ = _tile_dim(Sq, interpret)
    BK = _tile_dim(Sk, interpret)
    kern = functools.partial(_fwd_kernel, D=D, BQ=BQ, BK=BK,
                             scale=scale, causal=causal)
    return pl.pallas_call(
        kern,
        name="ring_block_fwd",
        grid=(B, NG, Sq // BQ, Sk // BK),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            _q_spec(BQ), _kv_spec(BK), _kv_spec(BK),
        ],
        out_specs=[_q_spec(BQ), _q_spec(BQ)],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sq, HD), jnp.float32),
            jax.ShapeDtypeStruct((B, Sq, HD), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((BQ, 128), jnp.float32),
            pltpu.VMEM((BQ, 128), jnp.float32),
            pltpu.VMEM((BQ, 128), jnp.float32),
        ],
        interpret=bool(interpret),
    )(offs, q, k, v)


# ---------------------------------------------------------------------------
# backward: given GLOBAL lse + delta, per-shard dq and dk/dv
# ---------------------------------------------------------------------------


def _probs(q, k, lse_col, row0, col0, qb, kb, BQ, BK, scale, causal):
    s = _scores(q, k, row0, col0, qb, kb, BQ, BK, scale, causal)
    return jnp.exp(s - lse_col)  # masked entries: exp(NEG-lse) == 0


def _dq_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_scr, *, D, BQ, BK, scale, causal):
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    nk = pl.num_programs(3)
    row0, col0 = off_ref[0], off_ref[1]
    G = 128 // D

    @pl.when(kb == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(_live(row0, col0, qb, kb, BQ, BK, causal))
    def _compute():
        for i in range(G):
            sl = slice(i * D, (i + 1) * D)
            q = q_ref[0, :, sl]
            k = k_ref[0, :, sl]
            v = v_ref[0, :, sl]
            do = do_ref[0, :, sl]
            lse_col = lse_ref[0, :, sl][:, :1]
            delta_col = delta_ref[0, :, sl][:, :1]
            p = _probs(q, k, lse_col, row0, col0, qb, kb, BQ, BK, scale,
                       causal)
            dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
            ds = p * (dp - delta_col)
            dq_scr[:, sl] += jnp.dot(
                ds.astype(k.dtype), k, preferred_element_type=jnp.float32
            ) * scale

    @pl.when(kb == nk - 1)
    def _write():
        dq_ref[0] = dq_scr[...]


def _dkv_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, D, BQ, BK, scale, causal):
    kb = pl.program_id(2)
    qb = pl.program_id(3)
    nq = pl.num_programs(3)
    row0, col0 = off_ref[0], off_ref[1]
    G = 128 // D

    @pl.when(qb == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(_live(row0, col0, qb, kb, BQ, BK, causal))
    def _compute():
        for i in range(G):
            sl = slice(i * D, (i + 1) * D)
            q = q_ref[0, :, sl]
            k = k_ref[0, :, sl]
            v = v_ref[0, :, sl]
            do = do_ref[0, :, sl]
            lse_col = lse_ref[0, :, sl][:, :1]
            delta_col = delta_ref[0, :, sl][:, :1]
            p = _probs(q, k, lse_col, row0, col0, qb, kb, BQ, BK, scale,
                       causal)
            dv_scr[:, sl] += jnp.dot(
                p.astype(do.dtype).T, do, preferred_element_type=jnp.float32
            )
            dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
            ds = p * (dp - delta_col)
            dk_scr[:, sl] += jnp.dot(
                ds.astype(q.dtype).T, q, preferred_element_type=jnp.float32
            ) * scale

    @pl.when(qb == nq - 1)
    def _write():
        dk_ref[0] = dk_scr[...]
        dv_ref[0] = dv_scr[...]


def shard_dq(q, k, v, do, lse, delta, offs, H, D, causal, scale, interpret):
    """dq f32 [B,Sq,H*D] for this q-shard against one visiting kv shard."""
    B, Sq, HD = q.shape
    assert HD == H * D, (HD, H, D)
    Sk = k.shape[1]
    NG = HD // 128
    BQ = _tile_dim(Sq, interpret)
    BK = _tile_dim(Sk, interpret)
    kern = functools.partial(_dq_kernel, D=D, BQ=BQ, BK=BK,
                             scale=scale, causal=causal)
    return pl.pallas_call(
        kern,
        name="ring_block_dq",
        grid=(B, NG, Sq // BQ, Sk // BK),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            _q_spec(BQ), _kv_spec(BK), _kv_spec(BK),
            _q_spec(BQ), _q_spec(BQ), _q_spec(BQ),
        ],
        out_specs=_q_spec(BQ),
        out_shape=jax.ShapeDtypeStruct((B, Sq, HD), jnp.float32),
        scratch_shapes=[pltpu.VMEM((BQ, 128), jnp.float32)],
        interpret=bool(interpret),
    )(offs, q, k, v, do, lse, delta)


def shard_dkv(q, k, v, do, lse, delta, offs, H, D, causal, scale, interpret):
    """(dk, dv) f32 [B,Sk,H*D] — the visiting shard's gradient
    contribution from THIS device's queries (accumulated around the ring
    by the caller)."""
    B, Sq, HD = q.shape
    assert HD == H * D, (HD, H, D)
    Sk = k.shape[1]
    NG = HD // 128
    BQ = _tile_dim(Sq, interpret)
    BK = _tile_dim(Sk, interpret)
    kern = functools.partial(_dkv_kernel, D=D, BQ=BQ, BK=BK,
                             scale=scale, causal=causal)
    kv_out = pl.BlockSpec((1, BK, 128), lambda b, g, kb, qb: (b, kb, g),
                          memory_space=pltpu.VMEM)
    q_in = pl.BlockSpec((1, BQ, 128), lambda b, g, kb, qb: (b, qb, g),
                        memory_space=pltpu.VMEM)
    kv_in = pl.BlockSpec((1, BK, 128), lambda b, g, kb, qb: (b, kb, g),
                         memory_space=pltpu.VMEM)
    return pl.pallas_call(
        kern,
        name="ring_block_dkv",
        grid=(B, NG, Sk // BK, Sq // BQ),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            q_in, kv_in, kv_in, q_in, q_in, q_in,
        ],
        out_specs=[kv_out, kv_out],
        out_shape=[
            jax.ShapeDtypeStruct((B, Sk, HD), jnp.float32),
            jax.ShapeDtypeStruct((B, Sk, HD), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((BK, 128), jnp.float32),
            pltpu.VMEM((BK, 128), jnp.float32),
        ],
        interpret=bool(interpret),
    )(offs, q, k, v, do, lse, delta)
