"""Ops of today's decoder blocks: RMSNorm, rotary positions, SwiGLU and
causal grouped-query attention over one call's own keys.

Each is one emitter (so one `jax.named_scope` in the compiled step) and
keeps its statistics in float32 whatever the activations' dtype: a
bfloat16 serving graph (models/afmoe.py) rounds once, on the way out.
None is differentiable: they exist in inference graphs only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework.registry import register_op
from .kv_cache import _pos_scalar, attention_mask, grouped_attention


@register_op("rms_norm", inputs=["X", "Scale"], outputs=["Out"],
             differentiable=False)
def _rms_norm(ctx, op, ins):
    """x * rsqrt(mean(x^2) + eps) * gain over groups of `Scale`'s width
    along the last axis: the whole hidden size, or each head's slice of a
    [..., heads * head_dim] projection (QK-norm)."""
    x, gain = ins["X"][0], ins["Scale"][0]
    w = gain.shape[0]
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (x.shape[-1] // w, w))
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + float(op.attr("epsilon", 1e-5)))
    out = out * gain.astype(jnp.float32)
    return {"Out": [out.reshape(x.shape).astype(x.dtype)]}


def rotary(x, first_pos, head_dim, theta):
    """Rotate-half rotary positions over each head's whole width:
    x [B, T, heads * head_dim], row i at position `first_pos + i`."""
    b, t, h = x.shape
    half = head_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                         / head_dim)
    pos = (first_pos + jnp.arange(t, dtype=jnp.int32)).astype(jnp.float32)
    angle = pos[:, None] * inv_freq[None, :]          # [T, half]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    xf = x.astype(jnp.float32).reshape(b, t, h // head_dim, head_dim)
    x1, x2 = xf[..., :half], xf[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.reshape(b, t, h).astype(x.dtype)


@register_op("rotary_embedding", inputs=["X", "Pos"], outputs=["Out"],
             differentiable=False)
def _rotary_embedding(ctx, op, ins):
    """`Pos` is the position of the LAST row (as `kv_cache_attention`
    has it), a runtime value."""
    x = ins["X"][0]
    last = _pos_scalar(ins["Pos"][0])
    return {"Out": [rotary(x, last - (x.shape[1] - 1),
                           int(op.attr("head_dim")),
                           float(op.attr("theta", 10000.0)))]}


@register_op("swiglu", inputs=["X"], outputs=["Out"], differentiable=False)
def _swiglu(ctx, op, ins):
    """silu(gate) * up of a fused [..., 2F] projection (gate first)."""
    return {"Out": [swiglu(ins["X"][0])]}


def swiglu(x):
    f = x.shape[-1] // 2
    gate, up = x[..., :f].astype(jnp.float32), x[..., f:].astype(jnp.float32)
    return (jax.nn.silu(gate) * up).astype(x.dtype)


# float32 scores one block of queries may hold; the blocks walk the batch
# and then the queries so that a 48-head prefill never holds [B, nh, S, S]
SCORE_BLOCK_BYTES = 384 * 2 ** 20


def _query_block(batch, heads, seq):
    """(rows, queries) of one block: whole rows while they fit, else one
    row cut along the queries; both divide their axis."""
    budget = SCORE_BLOCK_BYTES
    per_row = heads * seq * seq * 4
    if per_row <= budget:
        rows = max(1, min(batch, budget // per_row))
        while batch % rows:
            rows -= 1
        return rows, seq
    queries = max(1, budget // (heads * seq * 4))
    while seq % queries:
        queries -= 1
    return 1, queries


@register_op("causal_gqa_attention", inputs=["Q", "K", "V"], outputs=["Out"],
             differentiable=False)
def _causal_gqa_attention(ctx, op, ins):
    """Prefill attention over the call's own rows: Q [B, S, nh * dh]
    against K, V [B, S, nkv * dh], query head n on KV head
    n // (nh / nkv), causal, and with `window` > 0 only keys closer than
    it. Plain products in blocks of (rows, queries); softmax in float32;
    `prob_scale` as `kv_cache_attention` has it."""
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    nh, kvh = int(op.attr("num_heads")), int(op.attr("num_kv_heads"))
    window = int(op.attr("window", 0))
    scale = float(op.attr("scale", 1.0))
    prob_scale = float(op.attr("prob_scale", 1.0))
    b, s, h = q.shape
    rows, queries = _query_block(b, nh, s)
    nb, nq = b // rows, s // queries

    def block(i):
        r0, q0 = (i // nq) * rows, (i % nq) * queries
        qb = jax.lax.dynamic_slice(q, (r0, q0, 0), (rows, queries, h))
        kb = jax.lax.dynamic_slice_in_dim(k, r0, rows, axis=0)
        vb = jax.lax.dynamic_slice_in_dim(v, r0, rows, axis=0)
        qpos = q0 + jnp.arange(queries, dtype=jnp.int32)
        valid = attention_mask(qpos, s, window)
        return grouped_attention(qb, kb, vb, valid, kvh, scale, prob_scale)

    if nb * nq == 1:
        out = block(jnp.int32(0))
    else:
        out = jax.lax.map(block, jnp.arange(nb * nq, dtype=jnp.int32))
        out = out.reshape(nb, nq, rows, queries, h).transpose(0, 2, 1, 3, 4)
    return {"Out": [out.reshape(b, s, h)]}
