"""Ops of today's decoder blocks: RMSNorm, rotary positions (whole or
part of a head, plain or YaRN frequencies), SwiGLU, causal grouped-query
attention over one call's own keys (on the TPU one Pallas kernel,
kernels/prefill_attention.py), and the two forms of multi-head latent
attention's up-projection: expanded to keys and values for a prefill,
absorbed into the query and the output for a decode step.

Each is one emitter (so one `jax.named_scope` in the compiled step) and
keeps its statistics in float32 whatever the activations' dtype: a
bfloat16 serving graph (models/afmoe.py) rounds once, on the way out.
None is differentiable: they exist in inference graphs only.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework.registry import register_op
from ._helpers import einsum_f32
from .kv_cache import _pos_scalar, attention_mask, grouped_attention


@register_op("rms_norm", inputs=["X", "Scale"], outputs=["Out"],
             differentiable=False)
def _rms_norm(ctx, op, ins):
    """x * rsqrt(mean(x^2) + eps) * gain over groups of `Scale`'s width
    along the last axis: the whole hidden size, or each head's slice of a
    [..., heads * head_dim] projection (QK-norm). With `unit_offset`
    the stored gain is w and the gain applied 1 + w, summed in float32
    (a family that learns the gain's distance from one)."""
    x, gain = ins["X"][0], ins["Scale"][0]
    w = gain.shape[0]
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (x.shape[-1] // w, w))
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + float(op.attr("epsilon", 1e-5)))
    gain = gain.astype(jnp.float32)
    if op.attr("unit_offset", False):
        gain = 1.0 + gain
    out = out * gain
    return {"Out": [out.reshape(x.shape).astype(x.dtype)]}


def yarn_ramp(rotary_dim, theta, yarn):
    """YaRN's blend of each rotary frequency, [rotary_dim / 2] in [0, 1]
    (0: the frequency as it is, 1: divided by `factor`), and where it
    turns: pair i makes ``d^-1(i)`` rotations over the original context,
    d(r) = dim ln(original / (2 pi r)) / (2 ln theta); pairs below
    ``low = floor(d(beta_fast))`` keep their frequency, pairs above
    ``high = ceil(d(beta_slow))`` are interpolated, between them a
    linear ramp. Returns (ramp, low, high)."""
    import math

    import numpy as np

    def pair_of(rotations):
        return rotary_dim * math.log(
            yarn["original_max_position_embeddings"]
            / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair_of(yarn["beta_fast"])), 0)
    high = min(math.ceil(pair_of(yarn["beta_slow"])), rotary_dim - 1)
    span = max(high - low, 0.001)
    i = np.arange(rotary_dim // 2, dtype=np.float32)
    return np.clip((i - low) / span, 0.0, 1.0), low, high


def yarn_mscale(factor, mscale):
    """YaRN's attention temperature for a context stretched `factor`
    times: 0.1 mscale ln(factor) + 1 (1 where nothing is stretched)."""
    import math

    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rotary(x, first_pos, head_dim, theta, rotary_dim=None, yarn=None,
           leading=False):
    """Rotate-half rotary positions: x [B, T, heads * head_dim], row i
    at position `first_pos + i`. The LAST `rotary_dim` lanes of each
    head are rotated (all of them by default), the lanes before pass;
    with `leading` the FIRST `rotary_dim` lanes turn and the rest pass.
    `yarn` (factor, original_max_position_embeddings, beta_fast,
    beta_slow, mscale, mscale_all_dim) blends the frequencies as
    `yarn_ramp` says and scales cos and sin by the ratio of the two
    temperatures."""
    b, t, h = x.shape
    rot = head_dim if rotary_dim is None else int(rotary_dim)
    half = rot // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                         / rot)
    scale = 1.0
    if yarn:
        ramp = jnp.asarray(yarn_ramp(rot, theta, yarn)[0])
        inv_freq = inv_freq * (1.0 - ramp) + inv_freq / yarn["factor"] * ramp
        scale = yarn_mscale(yarn["factor"], yarn.get("mscale", 1.0)) \
            / yarn_mscale(yarn["factor"], yarn.get("mscale_all_dim", 0.0))
    pos = (first_pos + jnp.arange(t, dtype=jnp.int32)).astype(jnp.float32)
    angle = pos[:, None] * inv_freq[None, :]          # [T, half]
    cos = jnp.cos(angle)[None, :, None, :]
    sin = jnp.sin(angle)[None, :, None, :]
    if scale != 1.0:
        cos, sin = cos * scale, sin * scale
    xf = x.astype(jnp.float32).reshape(b, t, h // head_dim, head_dim)
    if rot == head_dim:
        turned = xf
    else:
        turned = xf[..., :rot] if leading else xf[..., head_dim - rot:]
    x1, x2 = turned[..., :half], turned[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    if rot != head_dim and leading:
        out = jnp.concatenate([out, xf[..., rot:]], -1)
    elif rot != head_dim:
        out = jnp.concatenate([xf[..., :head_dim - rot], out], -1)
    return out.reshape(b, t, h).astype(x.dtype)


@register_op("rotary_embedding", inputs=["X", "Pos"], outputs=["Out"],
             differentiable=False)
def _rotary_embedding(ctx, op, ins):
    """`Pos` is the position of the LAST row (as `kv_cache_attention`
    has it), a runtime value. `rotary_dim`, `yarn` and `leading` (which
    end of a head turns) as `rotary` takes them, absent the whole head
    at the plain frequencies; a prefill's rows as `rotary_prefill` says."""
    x = ins["X"][0]
    last = _pos_scalar(ins["Pos"][0])
    return {"Out": [rotary_prefill(ctx, x, last - (x.shape[1] - 1),
                                   int(op.attr("head_dim")),
                                   float(op.attr("theta", 10000.0)),
                                   op.attr("rotary_dim", None),
                                   op.attr("yarn", None),
                                   bool(op.attr("leading", False)))]}


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


def _query_block(batch, heads, seq, keys=None):
    """(rows, queries) of one block of [heads, seq, keys (seq)] scores:
    whole rows while they fit, else one row cut along the queries."""
    budget, keys = SCORE_BLOCK_BYTES, keys or seq
    per_row = heads * seq * keys * 4
    if per_row <= budget:
        rows = max(1, min(batch, budget // per_row))
        while batch % rows:
            rows -= 1
        return rows, seq
    queries = max(1, budget // (heads * keys * 4))
    while seq % queries:
        queries -= 1
    return 1, queries


@register_op("causal_gqa_attention", inputs=["Q", "K", "V", "KShared"],
             outputs=["Out"], differentiable=False)
def _causal_gqa_attention(ctx, op, ins):
    """Prefill attention over the call's own rows: Q [B, S, nh * dh]
    against K [B, S, nkv * dh] and V [B, S, nkv * dv] (a value head may
    be narrower than a key head; Out is [B, S, nh * dv]), query head n
    on KV head n // (nh / nkv), causal, and with `window` > 0 only keys
    closer than it; softmax in float32; `prob_scale` as
    `kv_cache_attention` has it. With `KShared` [B, S, ds], K holds a key
    head's own lanes only and every head's key is [its own | KShared]
    (`mla_expand`). The gauge `kernels.prefill_attention.calls` is the
    count of kernel calls in the prefill lowered last (0: the `jnp` path
    ran)."""
    from .. import observability as _obs

    out, kernel = prefill_attention(
        ins["Q"][0], ins["K"][0], ins["V"][0], int(op.attr("num_heads")),
        int(op.attr("num_kv_heads")), float(op.attr("scale", 1.0)),
        int(op.attr("window", 0)), float(op.attr("prob_scale", 1.0)),
        (ins.get("KShared") or [None])[0])
    if ctx is not None and not ctx.abstract:
        # one EmitContext a lowered prefill: the last call leaves the count
        ctx.prefill_attention_calls = kernel + getattr(
            ctx, "prefill_attention_calls", 0)
        _obs.set_gauge("kernels.prefill_attention.calls",
                       ctx.prefill_attention_calls)
    return {"Out": [out]}


def prefill_attention(q, k, v, num_heads, num_kv_heads, scale, window=0,
                      prob_scale=1.0, k_shared=None, interpret=False):
    """Causal attention of a call's own rows: on the TPU (and with
    `interpret`) the Pallas kernel (kernels/prefill_attention.py) for the
    calls it takes (`supports`: no window that binds, S in blocks of 128,
    heads of whole half lane tiles, float32 or bfloat16), else the same
    in plain products over blocks of (rows, queries), the shared key
    part copied into every head first. Returns
    (out [B, S, nh * dv], whether the kernel ran)."""
    from ..kernels import prefill_attention as _kernel

    b, s, _ = q.shape
    shared = 0 if k_shared is None else k_shared.shape[2]
    if (interpret or jax.default_backend() == "tpu") and _kernel.supports(
            s, num_heads, num_kv_heads, q.shape[2] // num_heads,
            v.shape[2] // num_kv_heads, q.dtype, window, shared):
        return _kernel.attend(
            q, k, v, num_heads=num_heads, num_kv_heads=num_kv_heads,
            scale=scale, prob_scale=prob_scale, k_shared=k_shared,
            interpret=interpret), True
    if shared:
        k = jnp.concatenate([
            k.reshape(b, s, num_kv_heads, -1),
            jnp.broadcast_to(k_shared[:, :, None, :],
                             (b, s, num_kv_heads, shared))], -1)
        k = k.reshape(b, s, -1)
    return _blocked_attention(q, k, v, num_heads, num_kv_heads, scale,
                              window, prob_scale), False


def _blocked_attention(q, k, v, nh, kvh, scale, window, prob_scale):
    """`grouped_attention` in blocks of (rows, queries) walked by
    `jax.lax.map`, so that no block holds more than `SCORE_BLOCK_BYTES`
    of float32 scores."""
    b, s, h = q.shape
    hv = v.shape[-1] // kvh * nh
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
        out = out.reshape(nb, nq, rows, queries, hv).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, s, hv)


@register_op("mla_expand", inputs=["Latent", "WKVB"], outputs=["K", "V"],
             differentiable=False)
def _mla_expand(ctx, op, ins):
    """Latent attention's prefill form. `WKVB` [nh, r, dn + dv] is the
    stored up-projection, a head's key part W_UK [r, dn] beside its
    value part W_UV [r, dv] (head-major, so that the decode form's two
    products below read their halves in place). `Latent` [B, S, r]
    (normed) times each part, laid [r, nh * d] (a copy of the weight, not
    of an activation), gives every head its non-rotary key part,
    K [B, S, nh * dn], and its values, V [B, S, nh * dv], as two plain
    products whose results are rows as `causal_gqa_attention`'s kernel
    reads them. The ONE rotary key part all heads share goes to that op
    beside them (`KShared`), never copied into the heads: one product
    into [B, S, nh, dn + dv], cut and joined with it, came out
    sequence-minor on the TPU and cost the kernel three operand-sized
    copies a layer (PERF.md, Findings PR 34)."""
    c, w = ins["Latent"][0], ins["WKVB"][0]
    r, dn = w.shape[1], int(op.attr("nope_dim"))

    def expand(part):
        flat = part.transpose(1, 0, 2).reshape(r, -1)
        return einsum_f32("bsr,rn->bsn", c, flat).astype(c.dtype)

    return {"K": [expand(w[..., :dn])], "V": [expand(w[..., dn:])]}


@register_op("mla_absorb_query", inputs=["Q", "WKVB"], outputs=["Out"],
             differentiable=False)
def _mla_absorb_query(ctx, op, ins):
    """The decode form's query: Q [B, T, nh * (dn + dr)], a head
    [q_nope | q_pe], becomes [q_nope W_UK^T | q_pe | 0] of `row_width`
    lanes a head, which scores against a latent cache's rows
    [c_kv | k_pe | 0] as q_nope . (c_kv W_UK) + q_pe . k_pe does."""
    q, w = ins["Q"][0], ins["WKVB"][0]
    nh, dn = w.shape[0], int(op.attr("nope_dim"))
    b, t, _h = q.shape
    qh = q.reshape(b, t, nh, -1)
    q_lat = einsum_f32("bthd,hrd->bthr", qh[..., :dn], w[..., :dn])
    parts = [q_lat.astype(q.dtype), qh[..., dn:]]
    pad = int(op.attr("row_width")) - sum(p.shape[-1] for p in parts)
    if pad:
        parts.append(jnp.zeros((b, t, nh, pad), q.dtype))
    return {"Out": [jnp.concatenate(parts, -1).reshape(b, t, -1)]}


@register_op("mla_absorb_output", inputs=["X", "WKVB"], outputs=["Out"],
             differentiable=False)
def _mla_absorb_output(ctx, op, ins):
    """The decode form's output: X [B, T, nh * r], a head's
    probability-weighted sum of latents, times W_UV -> [B, T, nh * dv]."""
    x, w = ins["X"][0], ins["WKVB"][0]
    nh, dn = w.shape[0], int(op.attr("nope_dim"))
    b, t, _h = x.shape
    out = einsum_f32("bthr,hrd->bthd", x.reshape(b, t, nh, -1), w[..., dn:])
    return {"Out": [out.astype(x.dtype).reshape(b, t, -1)]}


# ---------------------------------------------------------------------------
# block-sparse attention selected by content (InfLLM v2)
# ---------------------------------------------------------------------------
#
# A query group scores the layer's compressed keys (`kv_cache.
# compress_keys`: the mean of `kernel` keys every `stride` positions,
# visible to query t once its window ends at or before t), sums the
# softmax over them across the group's query heads, pools the sums to
# blocks of `block_size` positions by the maximum over the compressed
# keys that overlap a block, and attends to `topk` blocks: the first
# `init_blocks`, those meeting the last `window` positions up to t, and
# the best-scoring others. Up to `dense_len` keys (the prompt's length
# in a prefill, the position + 1 in a decode step) attention is dense.

def select_blocks(q, index, qpos, *, num_kv_heads, kernel, stride,
                  block_size, window, init_blocks, topk, scale):
    """q [R, T, nh * dh] at positions `qpos` [T] over `index` [R, J,
    nkv * dh] (J = slots / stride) -> the blocks each (row, KV head,
    query) reads [R, nkv, T, topk] int32, -1 where fewer are visible;
    the scores are float32."""
    r, t, _ = q.shape
    j, hk = index.shape[1:]
    dh = hk // num_kv_heads
    per_block = block_size // stride
    # every block a position of the J strides' slots may lie in
    blocks = -(-(j * stride + stride - 1) // block_size)
    qh = q.reshape(r, t, num_kv_heads, -1, dh)
    ch = index.reshape(r, j, num_kv_heads, dh)
    scores = einsum_f32("btkgd,bjkd->bkgtj", qh, ch) * scale
    ends = jnp.arange(j, dtype=jnp.int32) * stride + kernel - 1
    seen = ends[None, :] <= qpos[:, None]                       # [T, J]
    scores = jnp.where(seen, scores, -jnp.inf)
    top = jnp.max(scores, axis=-1, keepdims=True)
    top = jnp.where(jnp.isfinite(top), top, 0.0)
    e = jnp.where(seen, jnp.exp(scores - top), 0.0)
    p = jnp.sum(e / jnp.maximum(jnp.sum(e, -1, keepdims=True), 1e-30),
                axis=2)                                         # [R,nkv,T,J]
    p = jnp.where(seen, p, -jnp.inf)
    # a block's score: the best of the compressed keys overlapping it,
    # j in [B * per_block - lead, (B + 1) * per_block)
    width = blocks * per_block
    p = jnp.pad(p, [(0, 0)] * 3 + [(0, width - j)], constant_values=-jnp.inf)
    pooled = jnp.max(p.reshape(r, num_kv_heads, t, blocks, per_block), -1)
    lead = -(-kernel // stride) - 1
    for i in range(1, lead + 1):
        before = jnp.pad(p, [(0, 0)] * 3 + [(i, 0)],
                         constant_values=-jnp.inf)[..., :width]
        pooled = jnp.maximum(pooled, before[..., ::per_block])
    starts = jnp.arange(blocks, dtype=jnp.int32) * block_size
    causal = starts[None, :] <= qpos[:, None]                   # [T, nb]
    forced = causal & (
        (jnp.arange(blocks) < init_blocks)[None, :]
        | (starts[None, :] + block_size > qpos[:, None] - window + 1))
    score = jnp.where(forced, jnp.inf, jnp.where(causal, pooled, -jnp.inf))
    value, picked = jax.lax.top_k(score, topk)
    return jnp.where(value > -jnp.inf, picked, -1).astype(jnp.int32)


@register_op(
    "sparse_block_select",
    inputs=["Q", "Index", "Pos", "Row", "Counters"],
    outputs=["Selected", "CountersOut"],
    differentiable=False,
    mutates=(("CountersOut", "Counters"),),
)
def _sparse_block_select(ctx, op, ins):
    """Scoring, pooling and selection of the blocks `select_blocks`
    says, for queries Q [R, T, nh * dh] whose last row is at `Pos`
    against the stored `Index` (rows `Row` .. of the batch's). Where
    the keys are at most `dense_len` (the last row's position + 1), the
    attention will be dense and nothing is counted; else `Counters`
    [2] int32 gain the blocks selected and the blocks a query could see
    (a block that starts at or before it), summed over rows, KV heads
    and queries. Queries go in blocks of `SCORE_BLOCK_BYTES` of scores.
    The gauge `sparse_attention.selecting_layers` is the count of such
    ops in the program lowered last."""
    from .. import observability as _obs

    q, index = ins["Q"][0], ins["Index"][0]
    counters = ins["Counters"][0]
    r, t, _ = q.shape
    if ins.get("Row"):
        index = jax.lax.dynamic_slice_in_dim(
            index, _pos_scalar(ins["Row"][0]), r, axis=0)
    pos = _pos_scalar(ins["Pos"][0])
    qpos = pos - (t - 1) + jnp.arange(t, dtype=jnp.int32)
    attrs = {n: int(op.attr(n)) for n in (
        "num_kv_heads", "kernel", "stride", "block_size", "window",
        "init_blocks", "topk")}
    scale = float(op.attr("scale"))
    rows, queries = _query_block(r, int(op.attr("num_heads")), t,
                                 index.shape[1])
    nq = t // queries

    def block(i):
        r0, q0 = (i // nq) * rows, (i % nq) * queries
        qb = jax.lax.dynamic_slice(q, (r0, q0, 0), (rows, queries,
                                                    q.shape[2]))
        ib = jax.lax.dynamic_slice_in_dim(index, r0, rows, axis=0)
        return select_blocks(qb, ib, jax.lax.dynamic_slice_in_dim(
            qpos, q0, queries), scale=scale, **attrs)

    n = (r // rows) * nq
    if n == 1:
        picked = block(jnp.int32(0))
    else:
        picked = jax.lax.map(block, jnp.arange(n, dtype=jnp.int32))
        picked = picked.reshape((r // rows, nq) + picked.shape[1:])
        picked = jnp.moveaxis(picked, 1, 3).reshape(
            r, attrs["num_kv_heads"], t, attrs["topk"])
    sparse = (pos + 1 > int(op.attr("dense_len"))).astype(jnp.int32)
    chosen = jnp.sum(picked >= 0, dtype=jnp.int32)
    seen = r * attrs["num_kv_heads"] * jnp.sum(
        qpos // attrs["block_size"] + 1, dtype=jnp.int32)
    counted = counters + sparse * jnp.stack([chosen, seen]).astype(
        counters.dtype)
    if ctx is not None and not ctx.abstract:
        ctx.selecting_layers = 1 + getattr(ctx, "selecting_layers", 0)
        _obs.set_gauge("sparse_attention.selecting_layers",
                       ctx.selecting_layers)
    return {"Selected": [picked], "CountersOut": [counted]}


def _sparse_decode(q, k, v, pos, selected, num_kv_heads, block_size,
                   scale):
    """One token a row over the caches' selected blocks, gathered by
    index: q [B, nh * dh], k, v [B, slots, nkv * dh], selected [B, nkv,
    topk] -> [B, nh * dh]."""
    b, slots, hk = k.shape
    dh = hk // num_kv_heads
    kpos = (jnp.maximum(selected, 0)[..., None] * block_size
            + jnp.arange(block_size)).reshape(b, num_kv_heads, -1)
    valid = (jnp.repeat(selected, block_size, axis=-1) >= 0) & (kpos <= pos)
    at = jnp.minimum(kpos, slots - 1)[..., None]          # [B, nkv, n, 1]
    outs = []
    for g in range(num_kv_heads):
        kg, vg = (jnp.take_along_axis(c[..., g * dh:(g + 1) * dh], at[:, g],
                                      axis=1) for c in (k, v))  # [B, n, dh]
        qg = q.reshape(b, num_kv_heads, -1, dh)[:, g]    # [B, e, dh]
        s = einsum_f32("bed,bnd->ben", qg, kg) * scale
        s = jnp.where(valid[:, g][:, None], s, jnp.float32(-1e9))
        probs = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        outs.append(jnp.einsum("ben,bnd->bed", probs, vg))
    return jnp.stack(outs, axis=1).reshape(b, -1)


def _sparse_prefill(q, k, v, selected, num_heads, num_kv_heads,
                    block_size, scale):
    """A call's own rows from position 0, each (KV head, query) over
    its selected blocks' keys at or before it: the blocked attention
    with a block mask (the cost of dense, the result of sparse)."""
    b, s, h = q.shape
    hk = k.shape[2]
    dh = hk // num_kv_heads
    blocks = -(-s // block_size)
    rows, queries = _query_block(b, num_heads, s)
    nb, nq = b // rows, s // queries

    def block(i):
        r0, q0 = (i // nq) * rows, (i % nq) * queries
        qb = jax.lax.dynamic_slice(q, (r0, q0, 0), (rows, queries, h))
        kb = jax.lax.dynamic_slice_in_dim(k, r0, rows, axis=0)
        vb = jax.lax.dynamic_slice_in_dim(v, r0, rows, axis=0)
        sel = jax.lax.dynamic_slice(
            selected, (r0, 0, q0, 0),
            (rows, num_kv_heads, queries, selected.shape[3]))
        hit = jnp.any(sel[..., None] == jnp.arange(blocks), axis=-2)
        keys = jnp.repeat(hit, block_size, axis=-1)[..., :s]
        qpos = q0 + jnp.arange(queries)
        keys = keys & (jnp.arange(s)[None, :] <= qpos[:, None])
        qh = qb.reshape(rows, queries, num_kv_heads, -1, dh)
        scores = einsum_f32("btkgd,bskd->bkgts", qh,
                            kb.reshape(rows, s, num_kv_heads, dh)) * scale
        scores = jnp.where(keys[:, :, None], scores, jnp.float32(-1e9))
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        out = jnp.einsum("bkgts,bskd->btkgd", probs,
                         vb.reshape(rows, s, num_kv_heads, -1))
        return out.reshape(rows, queries, -1)

    if nb * nq == 1:
        return block(jnp.int32(0))
    out = jax.lax.map(block, jnp.arange(nb * nq, dtype=jnp.int32))
    return out.reshape(nb, nq, rows, queries, -1).transpose(
        0, 2, 1, 3, 4).reshape(b, s, -1)


@register_op(
    "block_sparse_attention",
    inputs=["Q", "K", "V", "Selected", "Pos"],
    outputs=["Out"],
    differentiable=False,
)
def _block_sparse_attention(ctx, op, ins):
    """Attention over the blocks `sparse_block_select` chose. Q [R, T,
    nh * dh]; a prefill (T > 1, from position 0) over the call's own
    K and V rows with a block mask; a decode step (T = 1, at `Pos`)
    over the caches, the selected blocks' rows gathered by index, or
    dense (`decode_attention`) while the keys are at most
    `dense_len`."""
    q, k, v, selected = (ins[n][0] for n in ("Q", "K", "V", "Selected"))
    nh, kvh = int(op.attr("num_heads")), int(op.attr("num_kv_heads"))
    bs, scale = int(op.attr("block_size")), float(op.attr("scale"))
    if q.shape[1] > 1:
        return {"Out": [_sparse_prefill(q, k, v, selected, nh, kvh, bs,
                                        scale)]}
    pos = _pos_scalar(ins["Pos"][0])

    def dense(_):
        from .kv_cache import decode_attention

        return decode_attention(q[:, 0], k, v, pos, kvh, scale)[0]

    def sparse(_):
        return _sparse_decode(q[:, 0], k, v, pos, selected[:, :, 0], kvh,
                              bs, scale)

    out = jax.lax.cond(pos + 1 > int(op.attr("dense_len")), sparse, dense,
                       None)
    return {"Out": [out[:, None]]}


# ---------------------------------------------------------------------------
# rotary positions over a prefill's rows where they lie (kernels/rotary.py)
# ---------------------------------------------------------------------------

def rotary_prefill(ctx, x, first_pos, head_dim, theta, rotary_dim=None,
                   yarn=None, leading=False, interpret=False):
    """`rotary`; on the TPU (and with `interpret`) the Pallas kernel
    (kernels/rotary.py) for the calls it takes (`supports`: a prefill's
    rows in blocks of 16, a width of whole `unit`s of lanes, float32 or
    bfloat16), with the tables `rotary_tables` reads off `rotary`. The
    gauge `kernels.rotary.calls` is the count of kernel calls in the
    program lowered last (0: a decode step, or the `jnp` path ran)."""
    from .. import observability as _obs
    from ..kernels import rotary as _kernel

    _b, t, w = x.shape
    kernel = (interpret or jax.default_backend() == "tpu") \
        and _kernel.supports(t, w, head_dim, x.dtype)
    if ctx is not None and not ctx.abstract:
        # one EmitContext a lowered program: the last call leaves the count
        ctx.rotary_calls = int(kernel) + getattr(ctx, "rotary_calls", 0)
        _obs.set_gauge("kernels.rotary.calls", ctx.rotary_calls)
    if not kernel:
        return rotary(x, first_pos, head_dim, theta, rotary_dim, yarn,
                      leading)
    tables = rotary_tables(first_pos, t, head_dim, _kernel.unit(head_dim),
                           theta, rotary_dim, yarn, leading)
    half = (head_dim if rotary_dim is None else int(rotary_dim)) // 2
    return _kernel.rotate(x, *tables, half=half, interpret=interpret)


def rotary_tables(first_pos, seq_len, head_dim, unit, theta,
                  rotary_dim=None, yarn=None, leading=False):
    """The kernel's float32 tables [seq_len, unit] (`unit` lanes of whole
    heads), row i at position `first_pos + i`: cos, 1 on the lanes that
    pass; sin_a, +sin on a rotary group's second half (it multiplies x
    rolled by +half, the first half's lanes); sin_b, -sin on its first
    half (x rolled by -half); both 0 elsewhere. Read off `rotary`
    itself, which is linear in x: a head of ones before each group's
    middle turns into [cos | sin] (1 where lanes pass), a head of ones
    after it into [-sin | cos], each value the one `rotary` multiplies
    by, exactly."""
    import numpy as np

    rot = head_dim if rotary_dim is None else int(rotary_dim)
    lane = np.arange(unit) % head_dim - (0 if leading else head_dim - rot)
    second = (lane >= rot // 2) & (lane < rot)
    first = (lane >= 0) & (lane < rot // 2)

    def turned(ones):
        x = jnp.broadcast_to(jnp.asarray(ones, jnp.float32),
                             (1, seq_len, unit))
        return rotary(x, first_pos, head_dim, theta, rotary_dim, yarn,
                      leading)[0]

    a, b = turned(~second), turned(second)
    return (jnp.where(second, b, a), jnp.where(second, a, 0.0),
            jnp.where(first, b, 0.0))
