"""KV-cache ops for the serving decode path (serving/generate.py).

Autoregressive generation re-running the full context every token is
O(S^2) recompute per sequence; the serving decode path instead keeps each
transformer layer's key/value tensors in persistable scope vars (the same
donation/write-back aliasing the optimizer uses for parameters, so the
cache update is an in-place HBM dynamic-update-slice) and runs a
single-token program per step.

A cache is stored head-major with the sequence as the minor dimension,
``[B, nh, dh, S]`` (``cache_shape`` is the one place that says so), which
is the layout the decode attention consumes: the score product contracts
K's ``dh`` axis and the value product contracts ``S`` on both operands,
so a cache is only ever read in place and updated in place. Only the new
rows (``[B, T, H]`` as the layer produces them) are transposed, on the
way in. A cache is an entry parameter of the step with a fixed layout,
so a stored layout the two products cannot consume as it is (``[B, S, H]``)
costs a copy of the whole cache per layer per token. The minor dimension
is S and not ``dh`` because the chip tiles fp32 as (8, 128): a 64-wide
minor dimension is padded to 128 and doubles the cache.

* ``kv_cache_write`` — write the current step's K/V rows into the cache at
  a runtime position (``jax.lax.dynamic_update_slice_in_dim`` along the
  sequence axis; the output aliases the cache input, which the Executor
  donates).
* ``kv_cache_attention`` — one fused emitter for masked decode attention:
  Q for the current token against the full cache, positions beyond ``Pos``
  masked out. XLA sees one [B, nh, T, S] score tensor per layer instead of
  a chain of mask/where/softmax ops (the PR-6 "one wide op" argument).

Neither op is differentiable: they exist only in frozen inference graphs
(serving/freeze.py verifies no training op survives next to them).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework.registry import register_op


def cache_shape(batch, max_len, num_heads, head_dim):
    """Stored shape of ONE layer's K (or V) cache: ``[B, nh, dh, S]``.
    The graph builder (models/gpt.py) and the code that allocates the
    arrays (serving/generate.py) both ask here."""
    return (int(batch), int(num_heads), int(head_dim), int(max_len))


def _pos_scalar(pos):
    """Feeds arrive as [1]-shaped arrays; indices must be 0-d."""
    return jnp.reshape(pos, ()).astype(jnp.int32)


@register_op(
    "kv_cache_write",
    inputs=["Cache", "X", "Pos"],
    outputs=["Out"],
    differentiable=False,
    mutates=(("Out", "Cache"),),
)
def _kv_cache_write(ctx, op, ins):
    cache = ins["Cache"][0]  # [B, nh, dh, S]
    x = ins["X"][0]  # [B, T, H], H = nh * dh
    pos = _pos_scalar(ins["Pos"][0])
    b, nh, dh, _ = cache.shape
    rows = x.astype(cache.dtype).reshape(b, -1, nh, dh).transpose(0, 2, 3, 1)
    out = jax.lax.dynamic_update_slice_in_dim(cache, rows, pos, axis=3)
    return {"Out": [out]}


@register_op(
    "kv_cache_attention",
    inputs=["Q", "CacheK", "CacheV", "Pos"],
    outputs=["Out"],
    differentiable=False,
)
def _kv_cache_attention(ctx, op, ins):
    q = ins["Q"][0]  # [B, T, H]
    k = ins["CacheK"][0]  # [B, nh, dh, S]
    v = ins["CacheV"][0]  # [B, nh, dh, S]
    pos = _pos_scalar(ins["Pos"][0])
    nh = int(op.attr("num_heads"))
    scale = float(op.attr("scale", 1.0))
    # inference residue of fluid's downgrade_in_infer attention dropout:
    # probs scale by (1 - dropout_prob) so cached decode matches the
    # training graph's test-mode numerics exactly
    prob_scale = float(op.attr("prob_scale", 1.0))
    b, t, h = q.shape
    s = k.shape[3]
    qh = q.reshape(b, t, nh, h // nh).transpose(0, 2, 1, 3)  # [B, nh, T, dh]
    scores = jnp.matmul(qh, k).astype(jnp.float32) * scale  # [B, nh, T, S]
    # Pos is the cache position of the LAST query row; query row i sits at
    # position Pos - (T-1) + i and may attend keys 0..that position
    # (causal within a prefill window, the single current slot in decode;
    # later cache slots hold garbage or future rows)
    qpos = pos - (t - 1) + jnp.arange(t, dtype=jnp.int32)
    valid = (
        jnp.arange(s, dtype=jnp.int32)[None, None, None, :]
        <= qpos[None, None, :, None]
    )
    scores = jnp.where(valid, scores, jnp.float32(-1e9))
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if prob_scale != 1.0:
        probs = probs * jnp.asarray(prob_scale, q.dtype)
    # contracts S, the minor dimension of both operands: no V^T is built
    out = jnp.einsum("bnts,bnds->bntd", probs, v)  # [B, nh, T, dh]
    return {"Out": [out.transpose(0, 2, 1, 3).reshape(b, t, h)]}
