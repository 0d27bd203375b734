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
* ``greedy_token`` — the step's greedy choice, kept on the device: the
  argmax of the last position's logits goes into a ``[B, 1]`` persistable
  (the next step's token feed, handed over as a device array) and into
  the step's column of a ``[B, new_tokens]`` persistable the host reads
  once per batch.

No op here is differentiable: they exist only in frozen inference graphs
(serving/freeze.py verifies no training op survives next to them).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..errors import InvalidArgumentError
from ..framework.registry import register_op
from ._helpers import einsum_f32


def cache_shape(batch, max_len, num_heads, head_dim, window=0):
    """Stored shape of ONE layer's K (or V) cache: ``[B, nh, dh, slots]``,
    `nh` the KV heads the layer stores (its query heads may be a multiple
    of them). A full-attention layer holds ``max_len`` slots; a layer
    whose keys are only visible for `window` positions holds a ring of
    ``min(max_len, window)`` slots, position p in slot ``p % slots``.
    The graph builders (models/) and the code that allocates the arrays
    (serving/generate.py) both ask here."""
    slots = min(int(max_len), int(window)) if window else int(max_len)
    return (int(batch), int(num_heads), int(head_dim), slots)


def ssm_state_shape(batch, num_heads, head_dim, state_size, num_groups):
    """Stored shape of ONE state-space layer's recurrent state:
    ``[B, H / pack, N, pack * P]`` float32, whatever `max_len` is. The
    state dimension N lies on the sublanes and `pack` heads' P channels
    side by side on the lanes (a full 128-lane row where P divides 128),
    the layout the decode update (kernels/ssm_update.py) reads and
    writes in place with no transposition; the heads of a pack share a
    B / C group. `ops/ssm.py::pack_state` converts from [B, H, P, N]."""
    per_group = int(num_heads) // int(num_groups)
    pack = max(1, min(128 // int(head_dim), per_group))
    while per_group % pack:
        pack -= 1
    return (int(batch), int(num_heads) // pack, int(state_size),
            pack * int(head_dim))


def conv_tail_shape(batch, channels, kernel):
    """Stored shape of ONE causal convolution's tail: the last
    `kernel - 1` un-convolved rows of every sequence, ``[B, kernel - 1,
    C]``, whatever `max_len` is."""
    return (int(batch), int(kernel) - 1, int(channels))


def attention_mask(qpos, slots, window=0):
    """[T, slots] bool: may the query at position `qpos[i]` read slot j?
    Slot j holds position p = qpos - ((qpos - j) mod slots), the newest
    one written there; it is visible iff it has been written (p >= 0)
    and, under a `window`, qpos - p < window. With `slots` beyond every
    position this is the causal mask j <= qpos."""
    j = jnp.arange(slots, dtype=jnp.int32)[None, :]
    age = jnp.mod(qpos[:, None] - j, slots)
    valid = age <= qpos[:, None]
    if window:
        valid = valid & (age < window)
    return valid


def grouped_attention(q, k, v, valid, num_kv_heads, scale):
    """q [B, T, nh * dh] over k, v [B, nkv, dh, S] read in place: query
    head n reads KV head n // (nh / nkv), so a KV head is never repeated
    in HBM. `valid` [T, S]. Scores and softmax in float32."""
    b, t, h = q.shape
    dh = k.shape[2]
    g = h // dh // num_kv_heads
    qh = q.reshape(b, t, num_kv_heads, g, dh)
    scores = einsum_f32("btkgd,bkds->bkgts", qh, k) * scale
    scores = jnp.where(valid[None, None, None], scores, jnp.float32(-1e9))
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgts,bkds->btkgd", probs, v)
    return out.reshape(b, t, h)


def _pos_scalar(pos):
    """Feeds arrive as [1]-shaped arrays; indices must be 0-d."""
    return jnp.reshape(pos, ()).astype(jnp.int32)


@register_op(
    "kv_cache_write",
    inputs=["Cache", "X", "Pos", "Row"],
    outputs=["Out"],
    differentiable=False,
    mutates=(("Out", "Cache"),),
)
def _kv_cache_write(ctx, op, ins):
    cache = ins["Cache"][0]  # [B, nh, dh, slots]
    x = ins["X"][0]  # [B, T, H], H = nh * dh
    pos = _pos_scalar(ins["Pos"][0])
    b, nh, dh, slots = cache.shape
    if op.attr("ring", False):
        return {"Out": [_ring_write(cache, x, pos, ins.get("Row"))]}
    if ins.get("Row"):
        raise InvalidArgumentError(
            "kv_cache_write: `Row` (a block of the batch's rows) needs "
            "ring=True; the plain write takes the whole batch"
        )
    rows = x.astype(cache.dtype).reshape(b, -1, nh, dh).transpose(0, 2, 3, 1)
    out = jax.lax.dynamic_update_slice_in_dim(cache, rows, pos, axis=3)
    return {"Out": [out]}


def _ring_write(cache, x, pos, row):
    """Rows for positions pos .. pos + T - 1 into slots ``p % slots`` of
    batch rows `row` .. (a prefill block of a larger batch). Of more rows
    than slots only the newest are kept; one row (decode) is an in-place
    update, several are rotated into place and written at slot 0."""
    _, nh, dh, slots = cache.shape
    rb, t = x.shape[0], x.shape[1]
    rows = x.astype(cache.dtype).reshape(rb, t, nh, dh).transpose(0, 2, 3, 1)
    r0 = jnp.int32(0) if row is None else _pos_scalar(row[0])
    if t == 1:
        return jax.lax.dynamic_update_slice(
            cache, rows, (r0, 0, 0, jnp.mod(pos, slots)))
    if t >= slots:
        rows, pos = rows[..., t - slots:], pos + (t - slots)
        rows = jnp.roll(rows, jnp.mod(pos, slots), axis=3)
        return jax.lax.dynamic_update_slice(cache, rows, (r0, 0, 0, 0))
    # fewer rows than slots: they must not wrap (a prefill from 0)
    return jax.lax.dynamic_update_slice(cache, rows, (r0, 0, 0, pos))


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
    kvh, window = int(op.attr("num_kv_heads", nh)), int(op.attr("window", 0))
    if kvh != nh or window:
        t = q.shape[1]
        qpos = pos - (t - 1) + jnp.arange(t, dtype=jnp.int32)
        valid = attention_mask(qpos, k.shape[3], window)
        return {"Out": [grouped_attention(q, k, v, valid, kvh, scale)]}
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


@register_op(
    "greedy_token",
    inputs=["Logits", "Tokens", "Next", "Pos", "Row"],
    outputs=["TokensOut", "NextOut"],
    differentiable=False,
    mutates=(("TokensOut", "Tokens"), ("NextOut", "Next")),
)
def _greedy_token(ctx, op, ins):
    """argmax over the vocabulary of `Logits` [R, T, V] at the last
    position (first index on a tie, as ``np.argmax``), written to column
    ``Pos + column`` (`column` alone without `Pos`) of `Tokens` [B, N]
    and handed out as `NextOut` [B, 1] in `Tokens`' dtype. R = B rows,
    or a block of them starting at row `Row`, which then also needs
    `Next`, the [B, 1] array the block's rows are written into."""
    tokens = ins["Tokens"][0]
    picked = jnp.argmax(ins["Logits"][0][:, -1, :], axis=-1)
    picked = picked.astype(tokens.dtype)[:, None]
    col = jnp.int32(int(op.attr("column", 0)))
    if ins.get("Pos"):
        col = col + _pos_scalar(ins["Pos"][0])
    row = jnp.int32(0)
    if ins.get("Row"):
        if not ins.get("Next"):
            raise InvalidArgumentError(
                "greedy_token: a block of rows (`Row`) is written into "
                "`Next`, which must then be an input"
            )
        row = _pos_scalar(ins["Row"][0])
        picked_all = jax.lax.dynamic_update_slice(
            ins["Next"][0], picked, (row, jnp.int32(0)))
    else:
        picked_all = picked
    out = jax.lax.dynamic_update_slice(tokens, picked, (row, col))
    return {"TokensOut": [out], "NextOut": [picked_all]}
