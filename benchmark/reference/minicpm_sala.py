"""Plain reference of the MiniCPM-SALA decoder (`model_type`
"minicpm_sala"): the whole forward pass in `jax.numpy`, float32, highest
matmul precision; the Lightning mixer as the token-by-token recurrence
(`lax.scan` over the positions), no chunks, no cache, no kernel; the
sparse mixer's selection computed per query from the equations below,
its attention over the selected keys. Written from the equations, not
from `paddle_tpu/models/minicpm_sala.py`; it reads the program's weights
by their names.

    x0 = scale_emb E[ids]
    every layer:  h = x + c Mixer(N(x));  y = h + c FFN(N(h))
                  c = scale_depth / sqrt(mup_denominator)
    logits = (N(y_last) / (hidden / dim_model_base)) W_head
    N(x) = x / sqrt(mean(x^2) + eps) * w           (a plain gain)
    FFN(x) = (silu(x W_g) * (x W_u)) W_d            (W_g | W_u stored side
                                                    by side)
    No projection carries a bias.

    Lightning mixer (H heads of d lanes, head h):
        q = RoPE(N_q(a W_q)), k = RoPE(N_k(a W_k)) per head: rotate-half
        over all d lanes at theta^(-2i / d) and the token's position;
        v = a W_v
        S_t = lambda_h S_{t-1} + k_t^T v_t   (S [d, d], zero at the start)
        o_t = q_t S_t / sqrt(d);   lambda_h = exp(-2^(-8 (h + 1) / H))
        Mixer = (N_o(o) * sigmoid(a W_gate)) W_o   (N_o over all H d lanes)

    Sparse mixer (nh query heads over nkv KV heads of dh lanes, no
    positions; query head n reads KV head n // (nh / nkv)):
        q = N_q(a W_q), k = N_k(a W_k) per head, v = a W_v
        compressed keys of KV head g: c_j = mean(k_{stride j ..
        stride j + kernel - 1}), visible to query t iff
        stride j + kernel - 1 <= t
        p_{t,j} = sum over the heads n of g of softmax_j(q_t^n . c_j /
        sqrt(dh)) over the visible j
        block B (positions block B .. block (B + 1) - 1) scores
        max p_{t,j} over the visible j whose window overlaps it
        selected(t, g): blocks B < init_blocks, the blocks meeting
        positions t - window + 1 .. t, then the best-scoring others
        (ties to the lower block), up to topk in all; only blocks that
        start at or before t
        o_t^n = softmax over the selected keys s <= t of q_t^n . k_s /
        sqrt(dh), times v;  Mixer = (o * sigmoid(a W_gate)) W_o
        Dense (every key s <= t) where the keys were at most
        `dense_len` when the position was computed: the prompt's length
        for a prompt token, t + 1 for a generated one.

Departures, as in the program: the muP constant c takes the published
depth (`mup_denominator` 32), not the layers this cut runs.

On the chip the weights stay resident in bfloat16 and are cast up one
layer at a time; the positions go through the products and the sparse
attention in blocks, so that a 16k-token pair fits beside the program.
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

FAMILY = "minicpm_sala"
ROWS = 2048         # positions a block of the position-wise products
QUERIES = 64        # queries a block of the sparse attention


def reference_config(cfg):
    """The sizes the reference needs, from a program config object or a
    dict with the same names."""
    get = cfg.get if isinstance(cfg, dict) else lambda k: getattr(cfg, k)
    keys = ("layer_kinds", "num_heads", "num_kv_heads", "head_dim",
            "lightning_heads", "lightning_head_dim", "rope_theta",
            "rms_norm_eps", "scale_emb", "residual_scale", "head_divisor",
            "sparse_kernel", "sparse_stride", "init_blocks", "block_size",
            "window_size", "topk", "dense_len")
    return {k: get(k) for k in keys}


def param_names(layer_kinds):
    names = [f"{FAMILY}_embed", f"{FAMILY}_norm_f", f"{FAMILY}_head_w"]
    for i, kind in enumerate(layer_kinds):
        p = f"{FAMILY}_l{i}"
        names += [f"{p}_n1", f"{p}_n2", f"{p}_mlp_gate_up_w",
                  f"{p}_mlp_down_w"]
        if kind == "lightning-attn":
            names += [f"{p}_{n}" for n in (
                "q_w", "k_w", "v_w", "gate_w", "o_w", "qn", "kn",
                "out_norm")]
        else:
            names += [f"{p}_attn_{n}" for n in (
                "q_w", "k_w", "v_w", "gate_w", "o_w", "qn", "kn")]
    return names


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(w)


def _by_rows(fn, x, rows=ROWS):
    """fn over blocks of `rows` positions of x [B, S, ...] (position-wise
    work: its result at a position reads that position alone)."""
    s = x.shape[1]
    if s <= rows:
        return fn(x)
    pad = -s % rows
    xs = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
    xs = jnp.moveaxis(xs.reshape((x.shape[0], -1, rows) + x.shape[2:]), 1, 0)
    out = jax.lax.map(fn, xs)
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape((x.shape[0], -1) + out.shape[3:])[:, :s]


def rotate(x, theta):
    """x [B, S, heads, d]: rotate-half over all d lanes at its position."""
    s, d = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def lightning_mixer(p, a, cfg, decay=True, output_gate=True,
                    state_dtype=None):
    """a [B, S, hidden] -> the mixer's output. `decay` False sets every
    lambda to 1, `output_gate` False drops sigmoid(a W_gate), and
    `state_dtype` rounds the carried state after every step: readings of
    another mixer resp. below the stated precision, never used by a
    cell's check."""
    h, d = cfg["lightning_heads"], cfg["lightning_head_dim"]
    b, s, _ = a.shape
    eps = cfg["rms_norm_eps"]

    def heads(name, norm=None):
        x = (a @ _f32(p[name])).reshape(b, s, h, d)
        return x if norm is None else rms_norm(x, p[norm], eps)

    q = rotate(heads("q_w", "qn"), cfg["rope_theta"])
    k = rotate(heads("k_w", "kn"), cfg["rope_theta"])
    v = heads("v_w")
    slopes = 2.0 ** (-8.0 * jnp.arange(1, h + 1, dtype=jnp.float32) / h)
    lam = jnp.exp(-slopes) if decay else jnp.ones((h,), jnp.float32)

    def step(state, inp):
        q_t, k_t, v_t = inp                             # [B, H, d] each
        state = lam[None, :, None, None] * state \
            + k_t[..., :, None] * v_t[..., None, :]
        if state_dtype is not None:
            info = jnp.finfo(state_dtype)
            state = jax.lax.reduce_precision(state, info.nexp, info.nmant)
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state) / math.sqrt(d)

    _final, o = jax.lax.scan(
        step, jnp.zeros((b, h, d, d), jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v)))
    o = rms_norm(jnp.moveaxis(o, 0, 1).reshape(b, s, h * d), p["out_norm"],
                 eps)
    if output_gate:
        o = o * jax.nn.sigmoid(a @ _f32(p["gate_w"]))
    return o @ _f32(p["o_w"])


def compressed_keys(k, kernel, stride):
    """k [B, S, nkv, dh] -> c [B, J, nkv, dh], c_j the mean of keys
    stride j .. stride j + kernel - 1, for every j whose window ends
    inside the sequence."""
    s = k.shape[1]
    count = (s - kernel) // stride + 1 if s >= kernel else 0
    return jnp.stack([jnp.mean(k[:, stride * j:stride * j + kernel], 1)
                      for j in range(count)], 1) if count else \
        jnp.zeros(k.shape[:1] + (0,) + k.shape[2:], k.dtype)


def selection(q, c, t, cfg, seq):
    """Which blocks each (row, KV head, query) selects: q [B, T, nkv, e,
    dh] at positions t [T], c [B, J, nkv, dh] -> [B, nkv, T, blocks]
    bool, blocks = ceil(seq / block)."""
    kernel, stride = cfg["sparse_kernel"], cfg["sparse_stride"]
    block, dh = cfg["block_size"], q.shape[-1]
    blocks = -(-seq // block)
    j = np.arange(c.shape[1])
    starts = np.arange(blocks) * block
    # c_j's window [stride j, stride j + kernel) meets block B's
    overlap = ((stride * j[:, None] < starts[None, :] + block)
               & (stride * j[:, None] + kernel > starts[None, :]))
    visible = (stride * j + kernel - 1)[None, :] <= t[:, None]     # [T, J]
    scores = jnp.einsum("btged,bjgd->bgetj", q, c) / math.sqrt(dh)
    scores = jnp.where(visible, scores, -jnp.inf)
    probs = jnp.where(visible, jax.nn.softmax(scores, -1), 0.0)
    group = jnp.sum(probs, axis=2)                              # [B,g,T,J]
    usable = visible[:, :, None] & overlap[None]                # [T, J, nb]
    score = jnp.max(jnp.where(usable[None, None], group[..., None],
                              -jnp.inf), axis=-2)               # [B,g,T,nb]
    causal = starts[None, :] <= t[:, None]
    forced = causal & ((np.arange(blocks) < cfg["init_blocks"])[None, :]
                       | (starts[None, :] + block - 1
                          >= t[:, None] - cfg["window_size"] + 1))
    rank = jnp.where(forced, jnp.inf, jnp.where(causal, score, -jnp.inf))
    order = jnp.argsort(-rank, axis=-1, stable=True)
    kept = jnp.take_along_axis(rank, order, -1) > -jnp.inf
    kept = kept & (jnp.arange(blocks) < cfg["topk"])
    picked = jnp.zeros(rank.shape, bool)
    return jax.vmap(jax.vmap(jax.vmap(lambda m, o, k: m.at[o].set(k))))(
        picked, order, kept)


def sparse_mixer(p, a, cfg, prompt_len, select=True, output_gate=True):
    """a [B, S, hidden] -> the mixer's output; the first `prompt_len`
    positions were one prefill. `select` False makes every query dense
    (a reading of another attention, never used by a cell's check)."""
    nh, kvh, dh = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    b, s, _ = a.shape
    e = nh // kvh
    eps = cfg["rms_norm_eps"]
    q = rms_norm((a @ _f32(p["attn_q_w"])).reshape(b, s, kvh, e, dh),
                 p["attn_qn"], eps)
    k = rms_norm((a @ _f32(p["attn_k_w"])).reshape(b, s, kvh, dh),
                 p["attn_kn"], eps)
    v = (a @ _f32(p["attn_v_w"])).reshape(b, s, kvh, dh)
    c = compressed_keys(k, cfg["sparse_kernel"], cfg["sparse_stride"])
    block = cfg["block_size"]
    queries = min(QUERIES, s)
    pad = -s % queries
    qp = jnp.pad(q, [(0, 0), (0, pad)] + [(0, 0)] * 3)

    def one(q0):
        qb = jax.lax.dynamic_slice_in_dim(qp, q0, queries, axis=1)
        t = q0 + jnp.arange(queries)
        keys = jnp.arange(s)[None, :] <= t[:, None]              # [T, S]
        # the keys a position saw when it was computed
        seen = jnp.where(t < prompt_len, prompt_len, t + 1)
        sparse = select & (seen > cfg["dense_len"])
        if select:
            chosen = selection(qb, c, t, cfg, s)                 # [B,g,T,nb]
            in_block = jnp.repeat(chosen, block, axis=-1)[..., :s]
            keys = keys & jnp.where(sparse[None, None, :, None], in_block,
                                    True)
        else:
            keys = jnp.broadcast_to(keys, (b, kvh) + keys.shape)
        scores = jnp.einsum("btged,bsgd->bgets", qb, k) / math.sqrt(dh)
        scores = jnp.where(keys[:, :, None], scores, -jnp.inf)
        return jnp.einsum("bgets,bsgd->btged", jax.nn.softmax(scores, -1),
                          v)

    outs = jax.lax.map(one, jnp.arange(0, s + pad, queries))
    o = jnp.moveaxis(outs, 0, 1).reshape(b, s + pad, nh * dh)[:, :s]
    if output_gate:
        o = o * jax.nn.sigmoid(a @ _f32(p["attn_gate_w"]))
    return o @ _f32(p["attn_o_w"])


def ffn(p, m):
    w = _f32(p["mlp_gate_up_w"])
    f = w.shape[1] // 2
    gu = m @ w
    return (jax.nn.silu(gu[..., :f]) * gu[..., f:]) @ _f32(p["mlp_down_w"])


def forward(params, ids, cfg, prompt_len=None, decay=True, output_gate=True,
            select=True, state_dtype=None):
    """ids [B, S] -> {"logits": next-token logits after the last position
    [B, V] float32}. The first `prompt_len` positions (all by default)
    were one prefill. `params` maps the program's parameter names to
    arrays of any float dtype. `decay`, `output_gate`, `select` and
    `state_dtype` as the mixers take them."""
    cfg = reference_config(cfg)
    eps, c = cfg["rms_norm_eps"], cfg["residual_scale"]
    ids = jnp.asarray(ids)
    prompt_len = ids.shape[1] if prompt_len is None else int(prompt_len)
    with jax.default_matmul_precision("highest"):
        x = cfg["scale_emb"] * _f32(jnp.asarray(
            params[f"{FAMILY}_embed"])[ids])
        # one compilation a kind: the layers of a kind share shapes
        lightning = jax.jit(lambda p, a: lightning_mixer(
            p, a, cfg, decay, output_gate, state_dtype))
        sparse = jax.jit(lambda p, a: sparse_mixer(
            p, a, cfg, prompt_len, select, output_gate))
        norm = jax.jit(lambda w, x: _by_rows(lambda r: rms_norm(r, w, eps),
                                             x))
        mlp = jax.jit(lambda p, w, h: h + c * _by_rows(
            lambda r: ffn(p, rms_norm(r, w, eps)), h))
        for i, kind in enumerate(cfg["layer_kinds"]):
            prefix = f"{FAMILY}_l{i}_"
            # read by key: a mapping that rounds what it hands out
            # (a reading below the stated precision) rounds these too
            own = {k[len(prefix):]: params[k] for k in params
                   if k.startswith(prefix)}
            a = norm(own["n1"], x)
            mix = lightning if kind == "lightning-attn" else sparse
            h = x + c * mix(own, a)
            x = mlp({k: own[k] for k in ("mlp_gate_up_w", "mlp_down_w")},
                    own["n2"], h)
        last = rms_norm(x[:, -1, :], _f32(params[f"{FAMILY}_norm_f"]), eps)
        logits = (last / cfg["head_divisor"]) @ _f32(
            params[f"{FAMILY}_head_w"])
    return {"logits": np.asarray(logits)}
