"""KV-cache ops for the serving decode path (serving/generate.py).

Autoregressive generation re-running the full context every token is
O(S^2) recompute per sequence; the serving decode path instead keeps each
transformer layer's key/value tensors in persistable scope vars (the same
donation/write-back aliasing the optimizer uses for parameters, so the
cache update is an in-place HBM dynamic-update-slice) and runs a
single-token program per step.

A cache is stored as the layers produce their rows, ``[B, slots,
nkv * dh]`` (``cache_shape`` is the one place that says so): a position
is one contiguous row, every KV head's `dh` lanes side by side. So a
decode step WRITES its token as a row, a few tiles a sequence, with no
transposition on the way in. (PR 26 stored ``[B, nh, dh, S]``, sequence
minor, which XLA's two products read in place; its price was the write,
a COLUMN at a runtime lane offset that touched every tile of the cache:
38% of GPT-2's decode step and 15% of Trinity's, ledger PR 31.) XLA's
products cannot read the row layout without copying the whole cache a
layer and token, so a decode step reads it through ONE Pallas kernel,
``kernels/decode_attention.py``, which contracts the whole lane width
with the query laid block-diagonally and never takes a head's lanes
apart. The minor dimension is a whole number of 128-lane tiles: the K
and V widths served (768 / 1024 / 256) are, and a latent-attention
layer's row (576 lanes of data: ``latent_cache_shape``) is padded to 640,
because a minor dimension that is not whole tiles is not kept minor at
all by the TPU's default layout. A layer has two cache operands (K and
V) or ONE whose rows hold the values too (the latent kind: the values
are the rows' leading lanes, ``value_lanes``).

* ``kv_cache_write`` — write the current step's K/V rows into the cache at
  a runtime position (``jax.lax.dynamic_update_slice`` along the slot
  axis; the output aliases the cache input, which the Executor donates).
* ``kv_cache_attention`` — masked attention of the step's queries over
  the cache, one path for every decoder: grouped query heads (``g = 1``
  is GPT-2, ONE KV head under 128 query heads the absorbed form of
  latent attention), causal, ring and window validity from
  ``attention_mask``; with no ``CacheV`` the values are lanes of the key
  rows.
  One token a sequence on the TPU is the kernel; several (rows appended
  to a cache that holds earlier ones) and the CPU take the same attention
  in `jnp` (``grouped_attention``). The gauge ``kernels.decode_attention.calls``
  is the count of kernel calls in the decode step lowered last (0: the
  `jnp` path ran).
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
    """Stored shape of ONE layer's K (or V) cache: ``[B, slots, nh * dh]``,
    `nh` the KV heads the layer stores (its query heads may be a multiple
    of them). A full-attention layer holds ``max_len`` slots; a layer
    whose keys are only visible for `window` positions holds a ring of
    ``min(max_len, window)`` slots, position p in slot ``p % slots``.
    The graph builders (models/) and the code that allocates the arrays
    (serving/generate.py) both ask here."""
    slots = min(int(max_len), int(window)) if window else int(max_len)
    return (int(batch), slots, int(num_heads) * int(head_dim))


LANES = 128


def latent_cache_shape(batch, max_len, width):
    """Stored shape of ONE latent-attention layer's only cache:
    ``[B, max_len, lanes]``, a position's `width` lanes (the normed
    latent, then the rotated shared key part) in a row padded with
    zeros to whole 128-lane tiles. A row that is not whole tiles is not
    stored as a row at all: compiled for a v5e, a ``[64, 1024, 576]``
    array gets the SLOTS as its minor dimension and every kernel call a
    cache-sized transposing copy (PERF.md, Findings PR 33); 640 lanes
    are read in place."""
    return (int(batch), int(max_len), -(-int(width) // LANES) * LANES)


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


def grouped_attention(q, k, v, valid, num_kv_heads, scale, prob_scale=1.0):
    """q [B, T, nh * dh] over k [B, S, nkv * dh] and v [B, S, nkv * dv]
    (a cache as stored, or a call's own rows; `dv` read from `v`, the
    result is [B, T, nh * dv]): query head n reads KV head
    n // (nh / nkv), so a KV head is never repeated in HBM. `valid`
    [T, S]. Scores and
    softmax in float32; `prob_scale` is the inference residue of fluid's
    downgrade_in_infer attention dropout (probabilities scale by
    1 - dropout_prob, so that cached decode matches the training graph's
    test-mode numerics)."""
    b, t, h = q.shape
    s, hk = k.shape[1:]
    dh = hk // num_kv_heads
    qh = q.reshape(b, t, num_kv_heads, h // hk, dh)
    kh = k.reshape(b, s, num_kv_heads, dh)
    vh = v.reshape(b, s, num_kv_heads, -1)
    scores = einsum_f32("btkgd,bskd->bkgts", qh, kh) * scale
    scores = jnp.where(valid[None, None, None], scores, jnp.float32(-1e9))
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    if prob_scale != 1.0:
        probs = probs * jnp.asarray(prob_scale, q.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", probs, vh)
    return out.reshape(b, t, -1)


def value_lanes(k, num_kv_heads, value_width):
    """The values of a cache that keeps them inside its key rows (a
    latent cache): the leading `value_width` lanes of each KV head."""
    b, s, _hk = k.shape
    return k.reshape(b, s, num_kv_heads, -1)[..., :value_width].reshape(
        b, s, num_kv_heads * value_width)


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
    cache = ins["Cache"][0]  # [B, slots, H], H = nh * dh
    x = ins["X"][0].astype(cache.dtype)  # [rows, T, H]
    pos = _pos_scalar(ins["Pos"][0])
    if op.attr("ring", False):
        return {"Out": [_ring_write(cache, x, pos, ins.get("Row"))]}
    if ins.get("Row"):
        raise InvalidArgumentError(
            "kv_cache_write: `Row` (a block of the batch's rows) needs "
            "ring=True; the plain write takes the whole batch"
        )
    out = jax.lax.dynamic_update_slice_in_dim(cache, x, pos, axis=1)
    return {"Out": [out]}


def _ring_write(cache, x, pos, row):
    """Rows for positions pos .. pos + T - 1 into slots ``p % slots`` of
    batch rows `row` .. (a prefill block of a larger batch). Of more rows
    than slots only the newest are kept; one row (decode) is an in-place
    update, several are rotated into place and written at slot 0."""
    slots = cache.shape[1]
    t = x.shape[1]
    r0 = jnp.int32(0) if row is None else _pos_scalar(row[0])
    if t == 1:
        return jax.lax.dynamic_update_slice(
            cache, x, (r0, jnp.mod(pos, slots), 0))
    if t >= slots:
        x, pos = x[:, t - slots:], pos + (t - slots)
        x = jnp.roll(x, jnp.mod(pos, slots), axis=1)
        return jax.lax.dynamic_update_slice(cache, x, (r0, 0, 0))
    # fewer rows than slots: they must not wrap (a prefill from 0)
    return jax.lax.dynamic_update_slice(cache, x, (r0, pos, 0))


@register_op(
    "kv_cache_attention",
    inputs=["Q", "CacheK", "CacheV", "Pos"],
    outputs=["Out"],
    differentiable=False,
)
def _kv_cache_attention(ctx, op, ins):
    """`Pos` is the cache position of the LAST query row; query row i
    sits at position Pos - (T - 1) + i and may read what `attention_mask`
    says (causal within a prefill, the slots written so far in decode;
    later slots hold garbage or future rows). A layer with no `CacheV`
    keeps its values inside `CacheK`, the leading `value_width` lanes of
    each KV head's row (`value_lanes`); Out is then
    [B, T, nh * value_width]."""
    from .. import observability as _obs

    q = ins["Q"][0]  # [B, T, nh * dh]
    k = ins["CacheK"][0]  # [B, slots, nkv * dh]
    v = (ins.get("CacheV") or [None])[0]
    width = op.attr("value_width", None)
    pos = _pos_scalar(ins["Pos"][0])
    nh = int(op.attr("num_heads"))
    kvh, window = int(op.attr("num_kv_heads", nh)), int(op.attr("window", 0))
    scale = float(op.attr("scale", 1.0))
    prob_scale = float(op.attr("prob_scale", 1.0))
    t = q.shape[1]
    if t > 1:
        qpos = pos - (t - 1) + jnp.arange(t, dtype=jnp.int32)
        valid = attention_mask(qpos, k.shape[1], window)
        if v is None:
            v = value_lanes(k, kvh, width)
        return {"Out": [grouped_attention(q, k, v, valid, kvh, scale,
                                          prob_scale)]}
    out, kernel = decode_attention(q[:, 0], k, v, pos, kvh, scale, window,
                                   prob_scale, width)
    if ctx is not None and not ctx.abstract:
        # one EmitContext a lowered step: the last call leaves the count
        ctx.decode_attention_calls = kernel + getattr(
            ctx, "decode_attention_calls", 0)
        _obs.set_gauge("kernels.decode_attention.calls",
                       ctx.decode_attention_calls)
    return {"Out": [out[:, None]]}


def decode_attention(q, k, v, pos, num_kv_heads, scale, window=0,
                     prob_scale=1.0, value_width=None, interpret=False):
    """One token a sequence, q [B, nh * dh] at position `pos`, over the
    caches as stored (`v` None: the values are `value_lanes` of `k`, and
    the kernel takes the one array): on the TPU (and with `interpret`)
    the Pallas kernel (kernels/decode_attention.py), elsewhere the same
    in `jnp`. Returns (out [B, nh * dv], whether the kernel ran)."""
    kernel = interpret or jax.default_backend() == "tpu"
    if kernel:
        from ..kernels import decode_attention as _kernel

        return _kernel.attend(
            q, k, v, pos, num_kv_heads=num_kv_heads, scale=scale,
            window=window, prob_scale=prob_scale, value_width=value_width,
            interpret=interpret,
        ), True
    if v is None:
        v = value_lanes(k, num_kv_heads, value_width)
    valid = attention_mask(pos[None], k.shape[1], window)
    return grouped_attention(q[:, None], k, v, valid, num_kv_heads, scale,
                             prob_scale)[:, 0], False


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


# ---------------------------------------------------------------------------
# rows pooled from complete chunks of keys: the compressed-key index of
# block-sparse attention, the chunk summaries of EVA attention
# ---------------------------------------------------------------------------
#
# A layer that selects the blocks it attends to by content keeps, beside
# its K and V caches, an index of compressed keys: row j is the mean of
# the keys at positions stride * j .. stride * j + kernel - 1, written
# once that window is complete (in a prefill from the prompt's keys, in
# a decode step when its position completes one). `ops/llm.py`'s
# `sparse_block_select` scores it. The gauge `kv_cache.bytes.index` is
# its bytes (the state kind "index", `models/decoder.py::state`).
#
# An EVA layer keeps, beside the ring of its current window, a summary
# key and a summary value for every chunk of `kernel` = `stride`
# positions, pooled by a learned softmax over the chunk: per head h,
# weights softmax_j(mu_h . k_j) over the chunk's keys, the summary
# sum_j w_j x_j of its keys (x = k) or its values (x = v). The same op
# writes them with `pooling` "softmax" (`pool_chunks`), one array each;
# `ops/eva.py` attends over them (state kind "summary").

def index_shape(batch, max_len, stride, num_heads, head_dim):
    """Stored shape of ONE layer's compressed-key index: ``[B, max_len /
    stride, nkv * dh]`` in the keys' dtype, a row a stride."""
    return (int(batch), int(max_len) // int(stride),
            int(num_heads) * int(head_dim))


def compress_keys(k, kernel, stride):
    """k [R, S, H] -> the complete windows' means [R, (S - kernel) //
    stride + 1, H] in k's dtype (summed in float32 a stride at a time;
    `kernel` a multiple of `stride`)."""
    r, s, h = k.shape
    chunks = s // stride
    per = kernel // stride
    if chunks < per:
        return jnp.zeros((r, 0, h), k.dtype)
    part = k[:, :chunks * stride].astype(jnp.float32).reshape(
        r, chunks, stride, h).sum(axis=2)
    rows = chunks - per + 1
    total = sum(part[:, m:m + rows] for m in range(per))
    return (total / kernel).astype(k.dtype)


def pool_chunks(k, mu, chunk, values=None):
    """Softmax pooling of every complete chunk of `chunk` rows: k [R, S,
    nh * dh] and `mu` [nh, dh] -> [R, S // chunk, nh * dv], per head h
    sum_j softmax_j(mu_h . k_j) x_j over the chunk's rows, x = `values`
    [R, S, nh * dv] (k where None). Logits, weights and sums in float32,
    element-wise (no matrix unit pass rounds them); out in k's dtype."""
    r, s, _ = k.shape
    nh, dh = mu.shape
    n = s // chunk

    def chunks(x):
        return x[:, :n * chunk].astype(jnp.float32).reshape(
            r, n, chunk, nh, -1)

    kc = chunks(k)
    logits = jnp.sum(kc * mu.astype(jnp.float32), axis=-1)    # [R,n,C,nh]
    w = jax.nn.softmax(logits, axis=2)
    x = kc if values is None else chunks(values)
    pooled = jnp.sum(w[..., None] * x, axis=2)                 # [R,n,nh,dv]
    return pooled.reshape(r, n, -1).astype(k.dtype)


@register_op(
    "kv_index_write",
    inputs=["Index", "K", "Pos", "Row", "V", "Mu"],
    outputs=["IndexOut"],
    differentiable=False,
    mutates=(("IndexOut", "Index"),),
)
def _kv_index_write(ctx, op, ins):
    """Without `carry` (a prefill from position 0): K is the call's own
    keys [R, S, H], rows `Row` .. of the batch; every complete window's
    row is written. With `carry` (a decode step): K is the cache after
    the step's write [B, slots, H] (a ring of fewer slots than the index
    covers: position p in slot p % slots) and `Pos` the step's position;
    where that position completes a window, its row is written.
    `pooling` "mean" (the default): the mean of the window's keys
    (`compress_keys`); "softmax": `pool_chunks` with `Mu` [nh, dh] over
    windows of `kernel` = `stride` rows, of the keys, or of the values
    `V` (the call's own, or the value cache) where it is given."""
    index, k = ins["Index"][0], ins["K"][0]
    kernel, stride = int(op.attr("kernel")), int(op.attr("stride"))
    softmax = op.attr("pooling", "mean") == "softmax"
    if softmax and kernel != stride:
        raise InvalidArgumentError(
            "kv_index_write: softmax pooling takes whole chunks "
            f"(kernel {kernel} == stride {stride})")
    values = (ins.get("V") or [None])[0]

    def pool(keys, vals):
        if softmax:
            return pool_chunks(keys, ins["Mu"][0], kernel, vals)
        return compress_keys(keys, kernel, stride)

    if not op.attr("carry", False):
        rows = pool(k, values)
        r0 = jnp.int32(0) if not ins.get("Row") \
            else _pos_scalar(ins["Row"][0])
        if rows.shape[1] == 0:
            return {"IndexOut": [index]}
        return {"IndexOut": [jax.lax.dynamic_update_slice(
            index, rows.astype(index.dtype), (r0, jnp.int32(0),
                                               jnp.int32(0)))]}
    pos = _pos_scalar(ins["Pos"][0])
    first = jnp.maximum(pos - (kernel - 1), 0)
    slots = k.shape[1]
    if slots < index.shape[1] * stride:
        # a ring: a window's rows are contiguous in it where its length
        # is whole windows
        if slots % kernel:
            raise InvalidArgumentError(
                f"kv_index_write: a ring of {slots} slots is no whole "
                f"number of windows of {kernel}")
        first = jnp.mod(first, slots)
    done = pos + 1 - kernel
    row = jnp.clip(done // stride, 0, index.shape[1] - 1)
    if softmax:
        # a chunk closes one step in `kernel`: the others branch past
        # the pooling's dozen small passes
        def write(index):
            pooled = pool(*(jax.lax.dynamic_slice_in_dim(
                x, first, kernel, axis=1) if x is not None else None
                for x in (k, values)))
            return jax.lax.dynamic_update_slice(
                index, pooled.astype(index.dtype),
                (jnp.int32(0), row, jnp.int32(0)))

        return {"IndexOut": [jax.lax.cond(
            (done >= 0) & (done % stride == 0), write, lambda i: i,
            index)]}
    window = jax.lax.dynamic_slice_in_dim(k, first, kernel, axis=1)
    mean = (window.astype(jnp.float32).sum(axis=1, keepdims=True)
            / kernel).astype(index.dtype)
    old = jax.lax.dynamic_slice_in_dim(index, row, 1, axis=1)
    new = jnp.where((done >= 0) & (done % stride == 0), mean, old)
    return {"IndexOut": [jax.lax.dynamic_update_slice(
        index, new, (jnp.int32(0), row, jnp.int32(0)))]}
