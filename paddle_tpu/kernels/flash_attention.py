"""Fused multihead attention as a Pallas TPU kernel (flash-attention style).

Replaces the reference's CUDA hand fusion (operators/fused/
multihead_matmul_op.cu, math/bert_encoder_functor.cu) with the TPU
equivalent: one Mosaic kernel per (batch, head) that computes
softmax(QK^T * scale + bias) V without ever writing the [S, S] probability
matrix to HBM. At BERT-base shapes (S=512) the probs tensor is the single
largest HBM stream in the dense formulation; keeping it in VMEM is the
memory-complexity win XLA cannot get on its own (it will not re-associate
softmax across two matmuls).

Semantics match the composed fluid ops exactly (matmul -> softmax ->
dropout -> matmul), including fluid's "downgrade_in_infer" dropout
(train: drop without rescale; infer: scale by 1-p — dropout_op.cc).

Backward is a second Pallas kernel over the same grid that recomputes the
probabilities from (q, k, v) — flash attention's standard recompute trade —
and regenerates the identical dropout mask from the same hardware PRNG seed,
so no mask tensor is ever materialized (the reference saves an explicit
uint8 mask; determinism makes that free here).

Scope: whole-row kernel — each grid step owns a full [S, S] score tile in
VMEM, so S is capped (fp32 scores: S=1024 -> 4 MB). Long-context beyond the
cap is the job of sequence parallelism (parallel/ring_attention.py), which
shards S before attention runs. Dispatch:
  * TPU backend  -> Pallas kernels (fwd + custom-vjp bwd)
  * other        -> jnp reference (same math; CPU tests + sharded fallback)
  * interpret=True forces the kernel through the Mosaic interpreter on CPU
    (kernel-logic tests; the interpreter's prng_random_bits is a zero stub,
    so dropout>0 training is TPU-only through the kernel path).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# whole-row kernel holds an [S, S] fp32 score tile in VMEM
MAX_SEQ = 1024


def supports(seq_len: int, head_dim: int, dtype) -> bool:
    """Can the Pallas kernel take these shapes? (else: jnp reference)."""
    return (
        seq_len % 128 == 0
        and seq_len <= MAX_SEQ
        and head_dim % 8 == 0
        and jnp.dtype(dtype) in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))
    )


def _probs(q, k, bias_row, scale, causal):
    """Softmax probabilities for one head: q [S,D], k [S,D], bias [1,S].

    Matmul inputs keep the MODEL dtype (bf16 under AMP) with fp32
    accumulation (preferred_element_type) — upcasting the inputs would run
    the MXU in fp32 mode at a fraction of bf16 throughput. The [S, S]
    elementwise tail (exp, normalize) follows the model dtype too (see
    _probs_unnorm); the scores and row statistics stay fp32."""
    e, l = _probs_unnorm(q, k, bias_row, scale, causal)
    if e.dtype == jnp.float32:
        return e / l
    # normalize in the compute dtype: a bf16 divide would promote; an
    # [S,1] reciprocal broadcast-mul keeps the full-tile pass in bf16
    return e * (1.0 / l).astype(e.dtype)


def _probs_unnorm(q, k, bias_row, scale, causal):
    """(exp(s - m), rowsum) — normalization deferred so the forward can
    scale the [S, D] output instead of the [S, S] probabilities (one less
    full-tile VPU pass; softmax cost dominates the kernel at D=64).

    Under AMP (bf16 q/k/v) the exp and everything downstream of it on the
    [S, S] tile runs in bf16 — the VPU packs 2x the lanes per op and the
    later MXU cast disappears. The scores, the row max, and the row sum
    (fp32 accumulation) stay fp32, so numerical stability is the standard
    flash-attention argument; what drops to 8 mantissa bits is the
    normalized probabilities (|p| <= 1), ~0.4% relative noise on an op
    whose training-mode consumer is a stochastic regularizer anyway."""
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    s = s + bias_row
    if causal:
        n = s.shape[0]
        row = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
        s = jnp.where(col <= row, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    edt = jnp.bfloat16 if q.dtype == jnp.bfloat16 else jnp.float32
    e = jnp.exp((s - m).astype(edt))
    return e, jnp.sum(e, axis=-1, keepdims=True, dtype=jnp.float32)


def _seed_prng(seed_ref):
    # Mosaic accepts at most 2 seed words; mix the (batch, head) grid index
    # in arithmetically (Knuth/Murmur multiplicative constants, uint32 wrap)
    head = (
        pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
    ).astype(jnp.uint32)
    s0 = seed_ref[0] + head * jnp.uint32(0x9E3779B1)
    s1 = seed_ref[1] ^ (head * jnp.uint32(0x85EBCA6B))
    pltpu.prng_seed(s0, s1)


from .prng_mask import keep_mask as _keep_mask  # fwd/bwd mask parity


def _apply_dropout(p, rate, is_test, upscale):
    """fluid dropout semantics on probabilities p (static rate/flags)."""
    if rate == 0.0:
        return p
    if is_test:
        return p if upscale else p * (1.0 - rate)
    keep = _keep_mask(p.shape, rate)
    dropped = jnp.where(keep, p / (1.0 - rate) if upscale else p, 0.0)
    return dropped


def _head_fwd(q, k, v, bias_row, scale, rate, is_test, upscale, causal):
    """One head's attention output [S, D] (fp32). Draws ONE dropout mask
    from the already-seeded PRNG when training with dropout — callers must
    keep the per-head call order identical between forward and backward.

    The softmax division is applied to the [S, D] OUTPUT rows (1/l), not
    the [S, S] probabilities — dropout commutes with the row-scale, so the
    math is identical and a full score-tile VPU pass disappears."""
    e, l = _probs_unnorm(q, k, bias_row, scale, causal)
    e = _apply_dropout(e, rate, is_test, upscale)
    out = jnp.dot(e.astype(v.dtype), v, preferred_element_type=jnp.float32)
    return out / l


def _head_bwd(q, k, v, bias_row, do, scale, rate, is_test, upscale, causal):
    """One head's (dq, dk, dv [S,D] fp32, dbias [1,S]); same single PRNG
    draw as _head_fwd."""
    p = _probs(q, k, bias_row, scale, causal)
    dob = do.astype(v.dtype)  # bf16 MXU inputs, fp32 accumulation
    if rate > 0.0 and not is_test:
        keep = _keep_mask(p.shape, rate)
        inv = 1.0 / (1.0 - rate) if upscale else 1.0
        pm = jnp.where(keep, p * inv, 0.0)
        dpm = jnp.dot(dob, v.T, preferred_element_type=jnp.float32)
        dp = jnp.where(keep, dpm * inv, 0.0)
    else:
        test_scale = 1.0 if (rate == 0.0 or upscale) else 1.0 - rate
        pm = p * test_scale
        dpm = jnp.dot(dob, v.T, preferred_element_type=jnp.float32)
        dp = dpm * test_scale
    dv = jnp.dot(pm.astype(v.dtype).T, dob, preferred_element_type=jnp.float32)
    # softmax backward: dS = P * (dP - rowsum(dP * P))
    d = jnp.sum(dp * p, axis=-1, keepdims=True)
    ds = p * (dp - d)
    dsb = ds.astype(v.dtype)
    dq = jnp.dot(dsb, k, preferred_element_type=jnp.float32) * scale
    dk = jnp.dot(dsb.T, q, preferred_element_type=jnp.float32) * scale
    return dq, dk, dv, jnp.sum(ds, axis=0, keepdims=True)


def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref,
                *, scale, rate, is_test, upscale, causal):
    q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
    if rate > 0.0 and not is_test:
        _seed_prng(seed_ref)
    o_ref[0, 0] = _head_fwd(
        q, k, v, bias_ref[0], scale, rate, is_test, upscale, causal
    ).astype(o_ref.dtype)


def _bwd_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dbias_ref,
                *, scale, rate, is_test, upscale, causal):
    q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
    do = do_ref[0, 0].astype(jnp.float32)
    if rate > 0.0 and not is_test:
        # identical seeding sequence as _fwd_kernel -> identical mask
        _seed_prng(seed_ref)
    dq, dk, dv, db = _head_bwd(
        q, k, v, bias_ref[0], do, scale, rate, is_test, upscale, causal
    )
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)
    # bias broadcasts over heads and query rows -> grad reduces over both.
    # The h grid axis is innermost, so this output block (indexed by b only)
    # stays resident while heads accumulate into it.
    @pl.when(pl.program_id(1) == 0)
    def _init():
        dbias_ref[0] = db

    @pl.when(pl.program_id(1) != 0)
    def _acc():
        dbias_ref[0] = dbias_ref[0] + db


def _head_spec(S, D):
    return pl.BlockSpec(
        (1, 1, S, D), lambda b, h: (b, h, 0, 0), memory_space=pltpu.VMEM
    )


def _bias_spec(S):
    # bias is passed as [B, 1, S]: a (1, 1, S) block's trailing two dims
    # equal the array's, satisfying Mosaic's (8, 128)-divisibility rule
    return pl.BlockSpec(
        (1, 1, S), lambda b, h: (b, 0, 0), memory_space=pltpu.VMEM
    )


def _pallas_fwd(q, k, v, bias, seed, statics, interpret):
    B, H, S, D = q.shape
    bias = bias.reshape(B, 1, S)
    kern = functools.partial(_fwd_kernel, **statics)
    return pl.pallas_call(
        kern,
        name="flash_attention_fwd",
        grid=(B, H),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            _head_spec(S, D),
            _head_spec(S, D),
            _head_spec(S, D),
            _bias_spec(S),
        ],
        out_specs=_head_spec(S, D),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(seed, q, k, v, bias)


def _pallas_bwd(q, k, v, bias, seed, do, statics, interpret):
    B, H, S, D = q.shape
    bias = bias.reshape(B, 1, S)
    kern = functools.partial(_bwd_kernel, **statics)
    dq, dk, dv, dbias = pl.pallas_call(
        kern,
        name="flash_attention_bwd",
        grid=(B, H),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            _head_spec(S, D),
            _head_spec(S, D),
            _head_spec(S, D),
            _bias_spec(S),
            _head_spec(S, D),
        ],
        out_specs=[
            _head_spec(S, D),
            _head_spec(S, D),
            _head_spec(S, D),
            _bias_spec(S),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
            jax.ShapeDtypeStruct(bias.shape, jnp.float32),
        ],
        interpret=pltpu.InterpretParams() if interpret else False,
    )(seed, q, k, v, bias, do)
    return dq, dk, dv, dbias.reshape(B, S)


# ---------------------------------------------------------------------------
# packed-QKV variant: reads the fused [B, S, 3H] projection directly
# ---------------------------------------------------------------------------
#
# The [B,H,S,D] kernels above force the model to materialize head-split
# transposes around the custom call (XLA cannot fuse a transpose INTO a
# Mosaic call): at BERT-base that is 8 copies of [B,S,H] per layer per
# step, ~2.4 GB of pure layout traffic. The packed kernels instead index
# the qkv projection output [B, S, 3*H*D] in place — q/k/v are the SAME
# operand passed three times with different column-block index maps — and
# emit [B, S, H*D]. Each grid step owns a 128-lane column group
# (G = 128//D heads) so the lane dimension is full.


def uses_tiled_path(seq_len: int, num_heads: int, head_dim: int, dtype):
    """True when fused_attention_qkv will dispatch the KV-tiled kernel for
    these static properties on the TPU backend — the op builder uses this
    at graph-build time to wire the saved (Out, Lse) into the dedicated
    grad op (ops/fused.py), so it must mirror the dispatch below."""
    from .flash_tiled import supports_tiled

    return (
        jax.default_backend() == "tpu"
        and not supports_packed(seq_len, num_heads, head_dim, dtype)
        and seq_len > MAX_SEQ
        and supports_tiled(seq_len, num_heads, head_dim, dtype)
    )


def supports_packed(seq_len: int, num_heads: int, head_dim: int, dtype):
    g = 128 // head_dim if head_dim and 128 % head_dim == 0 else 0
    return (
        g > 0
        and num_heads % g == 0
        and seq_len % 128 == 0
        and seq_len <= MAX_SEQ
        and jnp.dtype(dtype) in (jnp.dtype(jnp.float32),
                                 jnp.dtype(jnp.bfloat16))
    )


def _group_spec(S, section, num_groups):
    """(1, S, 128) blocks over [B, S, 3*H*D]; section 0/1/2 = q/k/v."""
    return pl.BlockSpec(
        (1, S, 128),
        lambda b, g: (b, 0, section * num_groups + g),
        memory_space=pltpu.VMEM,
    )


def _out_group_spec(S):
    return pl.BlockSpec(
        (1, S, 128), lambda b, g: (b, 0, g), memory_space=pltpu.VMEM
    )


def _bias_spec2(S):
    return pl.BlockSpec(
        (1, 1, S), lambda b, g: (b, 0, 0), memory_space=pltpu.VMEM
    )


def _fwd_kernel_qkv(seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref,
                    *, D, scale, rate, is_test, upscale, causal):
    if rate > 0.0 and not is_test:
        _seed_prng(seed_ref)
    qg, kg, vg, bias = q_ref[0], k_ref[0], v_ref[0], bias_ref[0]
    for i in range(128 // D):
        sl = slice(i * D, (i + 1) * D)
        o_ref[0, :, sl] = _head_fwd(
            qg[:, sl], kg[:, sl], vg[:, sl], bias,
            scale, rate, is_test, upscale, causal,
        ).astype(o_ref.dtype)


def _bwd_kernel_qkv(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref,
                    dq_ref, dk_ref, dv_ref, dbias_ref,
                    *, D, scale, rate, is_test, upscale, causal):
    if rate > 0.0 and not is_test:
        # same seed + same per-head draw order as _fwd_kernel_qkv
        _seed_prng(seed_ref)
    qg, kg, vg, bias = q_ref[0], k_ref[0], v_ref[0], bias_ref[0]
    db_total = jnp.zeros((1, bias.shape[-1]), jnp.float32)
    for i in range(128 // D):
        sl = slice(i * D, (i + 1) * D)
        do = do_ref[0, :, sl].astype(jnp.float32)
        dq, dk, dv, db = _head_bwd(
            qg[:, sl], kg[:, sl], vg[:, sl], bias, do,
            scale, rate, is_test, upscale, causal,
        )
        dq_ref[0, :, sl] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, sl] = dk.astype(dk_ref.dtype)
        dv_ref[0, :, sl] = dv.astype(dv_ref.dtype)
        db_total = db_total + db

    @pl.when(pl.program_id(1) == 0)
    def _init():
        dbias_ref[0] = db_total

    @pl.when(pl.program_id(1) != 0)
    def _acc():
        dbias_ref[0] = dbias_ref[0] + db_total


def _pallas_fwd_qkv(qkv, bias, seed, H, D, statics, interpret):
    B, S, _ = qkv.shape
    num_groups = H * D // 128
    bias = bias.reshape(B, 1, S)
    kern = functools.partial(_fwd_kernel_qkv, D=D, **statics)
    return pl.pallas_call(
        kern,
        name="flash_attention_qkv_fwd",
        grid=(B, num_groups),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            _group_spec(S, 0, num_groups),
            _group_spec(S, 1, num_groups),
            _group_spec(S, 2, num_groups),
            _bias_spec2(S),
        ],
        out_specs=_out_group_spec(S),
        out_shape=jax.ShapeDtypeStruct((B, S, H * D), qkv.dtype),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(seed, qkv, qkv, qkv, bias)


def _pallas_bwd_qkv(qkv, bias, seed, do, H, D, statics, interpret):
    B, S, _ = qkv.shape
    num_groups = H * D // 128
    bias = bias.reshape(B, 1, S)
    kern = functools.partial(_bwd_kernel_qkv, D=D, **statics)
    dq, dk, dv, dbias = pl.pallas_call(
        kern,
        name="flash_attention_qkv_bwd",
        grid=(B, num_groups),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            _group_spec(S, 0, num_groups),
            _group_spec(S, 1, num_groups),
            _group_spec(S, 2, num_groups),
            _bias_spec2(S),
            _out_group_spec(S),
        ],
        out_specs=[
            _out_group_spec(S),
            _out_group_spec(S),
            _out_group_spec(S),
            _bias_spec2(S),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, H * D), qkv.dtype),
            jax.ShapeDtypeStruct((B, S, H * D), qkv.dtype),
            jax.ShapeDtypeStruct((B, S, H * D), qkv.dtype),
            jax.ShapeDtypeStruct((B, 1, S), jnp.float32),
        ],
        interpret=pltpu.InterpretParams() if interpret else False,
    )(seed, qkv, qkv, qkv, bias, do)
    dqkv = jnp.concatenate([dq, dk, dv], axis=-1)
    return dqkv, dbias.reshape(B, S)


def _reference_qkv(qkv, bias, rng_key, H, **statics):
    B, S, three_hd = qkv.shape
    D = three_hd // 3 // H
    q, k, v = _unpack_qkv(qkv, H)
    out = _reference(q, k, v, bias, rng_key, **statics)
    return out.transpose(0, 2, 1, 3).reshape(B, S, H * D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_qkv(qkv, bias, seed, H, D, statics, interpret):
    return _pallas_fwd_qkv(qkv, bias, seed, H, D, dict(statics), interpret)


def _flash_qkv_fwd(qkv, bias, seed, H, D, statics, interpret):
    out = _pallas_fwd_qkv(qkv, bias, seed, H, D, dict(statics), interpret)
    return out, (qkv, bias, seed)


def _flash_qkv_bwd(H, D, statics, interpret, res, g):
    qkv, bias, seed = res
    dqkv, dbias = _pallas_bwd_qkv(
        qkv, bias, seed, g, H, D, dict(statics), interpret
    )
    dseed = np.zeros(seed.shape, dtype=jax.dtypes.float0)
    return dqkv, dbias, dseed


_flash_qkv.defvjp(_flash_qkv_fwd, _flash_qkv_bwd)


def fused_attention_qkv(
    qkv,
    num_heads,
    key_bias=None,
    *,
    scale=None,
    dropout_rate=0.0,
    is_test=True,
    dropout_implementation="downgrade_in_infer",
    causal=False,
    rng_key=None,
    interpret=False,
    force_reference=False,
    return_lse=False,
):
    """Attention over a packed qkv projection [B, S, 3*H*D] -> [B, S, H*D].
    Same semantics as fused_attention; the packed layout avoids every
    head-split transpose/copy around the kernel. With return_lse=True the
    TILED path returns (out, lse) so a later backward can skip the
    forward re-run; every other path returns (out, None)."""
    from .. import observability as _obs

    B, S, three_hd = qkv.shape
    D = three_hd // 3 // num_heads
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    statics = dict(
        scale=float(scale),
        rate=float(dropout_rate),
        is_test=bool(is_test),
        upscale=dropout_implementation == "upscale_in_train",
        causal=bool(causal),
    )
    bias = (
        jnp.zeros((B, S), jnp.float32)
        if key_bias is None
        else key_bias.astype(jnp.float32)
    )
    training_dropout = dropout_rate > 0.0 and not is_test
    if rng_key is None:
        if training_dropout:
            raise ValueError("fused_attention_qkv: dropout needs rng_key")
        rng_key = jax.random.key(0)
    use_pallas = (
        not force_reference
        and (interpret or jax.default_backend() == "tpu")
        and supports_packed(S, num_heads, D, qkv.dtype)
    )
    if not use_pallas:
        from .flash_tiled import flash_tiled, supports_tiled

        if (
            not force_reference
            and (interpret or jax.default_backend() == "tpu")
            and S > MAX_SEQ
            and supports_tiled(S, num_heads, D, qkv.dtype)
        ):
            # beyond the whole-row cap: KV-tiled online-softmax kernel
            # (flash_tiled.py) — same packed layout, any S
            if interpret and training_dropout:
                raise ValueError(
                    "fused_attention_qkv: training dropout is unsupported "
                    "in interpret mode (interpreter PRNG is a stub)"
                )
            seed = _seed_words(rng_key)
            if return_lse:
                from .flash_tiled import flash_tiled_outs

                return flash_tiled_outs(
                    qkv, bias, seed, num_heads, D, tuple(statics.items()),
                    interpret,
                )
            return flash_tiled(
                qkv, bias, seed, num_heads, D, tuple(statics.items()),
                interpret,
            )
        if (
            not force_reference
            and not interpret
            and jax.default_backend() == "tpu"
            and supports(S, D, qkv.dtype)
        ):
            # packed layout unsupported (e.g. odd head grouping) but the
            # 4-D kernel can run: pay the unpack transposes, never the
            # dense [B,H,S,S]-in-HBM cliff
            q, k, v = _unpack_qkv(qkv, num_heads)
            seed = _seed_words(rng_key)
            out4 = _flash(q, k, v, bias, seed, tuple(statics.items()), False)
            B_, H_, S_, D_ = out4.shape
            out = out4.transpose(0, 2, 1, 3).reshape(B_, S_, H_ * D_)
            return (out, None) if return_lse else out
        out = _reference_qkv(qkv, bias, rng_key, num_heads, **statics)
        return (out, None) if return_lse else out
    if interpret and training_dropout:
        raise ValueError(
            "fused_attention_qkv: training dropout is unsupported in "
            "interpret mode (interpreter PRNG is a stub)"
        )
    seed = _seed_words(rng_key)
    # counted past the dispatch decision: traces that took the packed
    # Pallas kernel, not calls (the tiled path counts kernels.flash_tiled)
    _obs.add("kernels.fused_attention_qkv")
    out = _flash_qkv(
        qkv, bias, seed, num_heads, D, tuple(statics.items()), interpret
    )
    return (out, None) if return_lse else out


def _unpack_qkv(qkv, H):
    B, S, three_hd = qkv.shape
    D = three_hd // 3 // H
    def part(i):
        sec = qkv[..., i * H * D:(i + 1) * H * D]
        return sec.reshape(B, S, H, D).transpose(0, 2, 1, 3)
    return part(0), part(1), part(2)


def attention_grads_qkv(qkv, num_heads, key_bias, d_out, rng_key, *,
                        scale=None, dropout_rate=0.0, is_test=True,
                        dropout_implementation="downgrade_in_infer",
                        causal=False, force_reference=False,
                        interpret=False, saved_out=None, saved_lse=None):
    """(dqkv, dbias) without re-running the forward kernel (see
    attention_grads)."""
    B, S, three_hd = qkv.shape
    D = three_hd // 3 // num_heads
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    statics = dict(
        scale=float(scale),
        rate=float(dropout_rate),
        is_test=bool(is_test),
        upscale=dropout_implementation == "upscale_in_train",
        causal=bool(causal),
    )
    bias = (
        jnp.zeros((B, S), jnp.float32)
        if key_bias is None
        else key_bias.astype(jnp.float32)
    )
    if rng_key is None:
        if dropout_rate > 0.0 and not is_test:
            # silently substituting a fixed key would draw a mask UNRELATED
            # to the forward's -> silently wrong gradients
            raise ValueError("attention_grads_qkv: dropout needs rng_key")
        rng_key = jax.random.key(0)
    if interpret and dropout_rate > 0.0 and not is_test:
        # interpreter PRNG is a zero stub -> mask unrelated to any forward
        raise ValueError(
            "attention_grads_qkv: training dropout is unsupported in "
            "interpret mode (interpreter PRNG is a stub)"
        )
    use_pallas = (
        not force_reference
        and (interpret or jax.default_backend() == "tpu")
        and supports_packed(S, num_heads, D, qkv.dtype)
    )
    if use_pallas:
        seed = _seed_words(rng_key)
        return _pallas_bwd_qkv(
            qkv, bias, seed, d_out, num_heads, D, statics, interpret
        )
    from .flash_tiled import flash_tiled_bwd, flash_tiled_fwd, supports_tiled

    if (
        not force_reference
        and (interpret or jax.default_backend() == "tpu")
        and S > MAX_SEQ
        and supports_tiled(S, num_heads, D, qkv.dtype)
    ):
        seed = _seed_words(rng_key)
        if saved_out is not None and saved_lse is not None:
            # the forward op saved (out, lse): straight to the two-kernel
            # tiled backward — no forward re-run (a full extra fwd per
            # layer per step otherwise; XLA does not CSE custom calls)
            out, lse = saved_out, saved_lse
        else:
            out, lse = flash_tiled_fwd(
                qkv, bias, seed, num_heads, D, statics, interpret
            )
        return flash_tiled_bwd(
            qkv, bias, seed, d_out, out, lse, num_heads, D, statics,
            interpret,
        )
    if (
        not force_reference
        and not interpret
        and jax.default_backend() == "tpu"
        and supports(S, D, qkv.dtype)
    ):
        # mirror fused_attention_qkv's 4-D kernel fallback exactly (same
        # seed -> same dropout masks as the forward it pairs with)
        q, k, v = _unpack_qkv(qkv, num_heads)
        do4 = d_out.reshape(B, S, num_heads, D).transpose(0, 2, 1, 3)
        seed = _seed_words(rng_key)
        dq, dk, dv, dbias = _pallas_bwd(
            q, k, v, bias, seed, do4, statics, False
        )
        def pack(t):
            return t.transpose(0, 2, 1, 3).reshape(B, S, num_heads * D)
        return jnp.concatenate([pack(dq), pack(dk), pack(dv)], -1), dbias
    _, vjp = jax.vjp(
        lambda qkv_, b_: _reference_qkv(qkv_, b_, rng_key, num_heads,
                                        **statics),
        qkv, bias,
    )
    return vjp(d_out)


def _reference(q, k, v, bias, rng_key, *, scale, rate, is_test, upscale,
               causal):
    """Same math as the kernels in plain jnp (CPU path / oracle). Dropout
    masks come from jax.random instead of the TPU hardware PRNG — same
    distribution, different stream."""
    s = jnp.einsum(
        "bhqd,bhkd->bhqk",
        q.astype(jnp.float32),
        k.astype(jnp.float32),
    ) * scale
    s = s + bias[:, None, None, :]
    if causal:
        S = s.shape[-1]
        row = jax.lax.broadcasted_iota(jnp.int32, (S, S), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (S, S), 1)
        s = jnp.where((col <= row)[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if rate > 0.0:
        if is_test:
            p = p if upscale else p * (1.0 - rate)
        else:
            keep = jax.random.bernoulli(rng_key, 1.0 - rate, p.shape)
            p = jnp.where(keep, p / (1.0 - rate) if upscale else p, 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def attention_grads(q, k, v, key_bias, d_out, rng_key, *, scale=None,
                    dropout_rate=0.0, is_test=True,
                    dropout_implementation="downgrade_in_infer",
                    causal=False, force_reference=False, interpret=False):
    """(dq, dk, dv, dbias) for fused_attention, computed WITHOUT re-running
    the forward kernel — the flash backward needs no forward residuals.
    Used by the fused_multihead_attention_grad op so training programs run
    one forward + one backward Mosaic call (XLA does not CSE custom-calls,
    so the generic vjp-replay pattern would pay the forward twice)."""
    B, H, S, D = q.shape
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    statics = dict(
        scale=float(scale),
        rate=float(dropout_rate),
        is_test=bool(is_test),
        upscale=dropout_implementation == "upscale_in_train",
        causal=bool(causal),
    )
    bias = (
        jnp.zeros((B, S), jnp.float32)
        if key_bias is None
        else key_bias.astype(jnp.float32)
    )
    if rng_key is None:
        if dropout_rate > 0.0 and not is_test:
            # a substitute key would draw a mask unrelated to the forward's
            raise ValueError("attention_grads: dropout needs rng_key")
        rng_key = jax.random.key(0)
    if interpret and dropout_rate > 0.0 and not is_test:
        raise ValueError(
            "attention_grads: training dropout is unsupported in interpret "
            "mode (interpreter PRNG is a stub)"
        )
    use_pallas = not force_reference and (
        interpret
        or (jax.default_backend() == "tpu" and supports(S, D, q.dtype))
    )
    if use_pallas:
        seed = _seed_words(rng_key)
        return _pallas_bwd(q, k, v, bias, seed, d_out, statics, interpret)
    _, vjp = jax.vjp(
        lambda q_, k_, v_, b_: _reference(q_, k_, v_, b_, rng_key, **statics),
        q, k, v, bias,
    )
    return vjp(d_out)


def _seed_words(rng_key):
    seed = jnp.ravel(jax.random.key_data(rng_key)).astype(jnp.uint32)[:2]
    if seed.shape[0] < 2:  # rbg/other impls may expose a single word
        seed = jnp.concatenate([seed, jnp.zeros(1, jnp.uint32)])
    return seed


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _flash(q, k, v, bias, seed, statics, interpret):
    return _pallas_fwd(q, k, v, bias, seed, dict(statics), interpret)


def _flash_fwd(q, k, v, bias, seed, statics, interpret):
    out = _pallas_fwd(q, k, v, bias, seed, dict(statics), interpret)
    return out, (q, k, v, bias, seed)


def _flash_bwd(statics, interpret, res, g):
    q, k, v, bias, seed = res
    dq, dk, dv, dbias = _pallas_bwd(
        q, k, v, bias, seed, g, dict(statics), interpret
    )
    dseed = np.zeros(seed.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, dbias, dseed


_flash.defvjp(_flash_fwd, _flash_bwd)


def fused_attention(
    q,
    k,
    v,
    key_bias=None,
    *,
    scale=None,
    dropout_rate=0.0,
    is_test=True,
    dropout_implementation="downgrade_in_infer",
    causal=False,
    rng_key=None,
    interpret=False,
    force_reference=False,
):
    """softmax(q k^T * scale + key_bias) v with fused dropout.

    q, k, v: [B, H, S, D]; key_bias: additive [B, S] fp32 (e.g. padding mask
    as 0 / -1e4), broadcast over heads and query positions. Differentiable
    in q, k, v, key_bias. `rng_key` (a jax PRNG key) feeds dropout; required
    when dropout_rate > 0 and not is_test.
    """
    from .. import observability as _obs

    B, H, S, D = q.shape
    if scale is None:
        scale = 1.0 / float(np.sqrt(D))
    upscale = dropout_implementation == "upscale_in_train"
    statics = dict(
        scale=float(scale),
        rate=float(dropout_rate),
        is_test=bool(is_test),
        upscale=upscale,
        causal=bool(causal),
    )
    if key_bias is None:
        bias = jnp.zeros((B, S), jnp.float32)
    else:
        bias = key_bias.astype(jnp.float32)
    training_dropout = dropout_rate > 0.0 and not is_test
    if rng_key is None:
        if training_dropout:
            raise ValueError("fused_attention: dropout needs rng_key")
        rng_key = jax.random.key(0)
    use_pallas = not force_reference and (
        interpret
        or (jax.default_backend() == "tpu" and supports(S, D, q.dtype))
    )
    if not use_pallas:
        return _reference(q, k, v, bias, rng_key, **statics)
    if interpret and training_dropout:
        # the Mosaic interpreter's prng_random_bits is a zero stub: every
        # probability would be dropped and the kernel would silently return
        # zeros — refuse instead
        raise ValueError(
            "fused_attention: training dropout is unsupported in interpret "
            "mode (interpreter PRNG is a stub); test dropout on TPU or via "
            "the jnp reference path (force_reference=True)"
        )
    seed = _seed_words(rng_key)
    # counted past the dispatch decision: kernel traces, not calls
    _obs.add("kernels.fused_attention")
    return _flash(q, k, v, bias, seed, tuple(statics.items()), interpret)
