"""Plain reference of the EvaByte decoder (`model_type` "evabyte"): the
whole forward pass in `jax.numpy`, float32, highest matmul precision;
EVA attention computed per query from the equations below, with the
window's keys and the summaries a query sees as explicit sets: no ring,
no cache, no kernel. Written from the equations, not from
`paddle_tpu/models/evabyte.py`; it reads the program's weights by their
names.

    x0 = E[ids]                        (no scale)
    every layer:  h = x + Attn(N(x));  y = h + FFN(N(h))
    logits = N(y_last) W_head
    N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)
    FFN(a) = (silu(a W_g) * (a W_u)) W_d    (W_g | W_u stored side by side)
    No projection carries a bias.

    EVA attention, head h of H, d lanes, window W, chunk C:
        q_t = RoPE(a W_q), k_t = RoPE(a W_k) (rotate-half over all d
        lanes at theta^(-2i / d) and the token's position), v_t = a W_v
        ksum_c = sum_{j in chunk c} softmax_j(mu_h . k_j) k_j
        vsum_c = sum_{j in chunk c} softmax_j(phi_h . k_j) v_j
        query t: ONE softmax, scale 1 / sqrt(d), over the keys
        {k_j : j // W == t // W, j <= t} and {ksum_c : c < (t // W) W / C},
        weighting [v_j | vsum_c]; Attn = o W_o

The residual stream is float32 throughout (the program's too). On the
chip the weights stay resident in bfloat16 and are cast up one layer at
a time; the position-wise products go in blocks of rows and the
attention in blocks of queries inside one window, so that a 16k-position
pair fits beside the program.

Readings below what the configuration states, never a cell's check:
`summaries` False (each query attends its window only), `pooling`
"mean" (mu = phi = 0: a chunk's plain mean), `stream_dtype` (the
residual stream rounded after every add).
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

from .minicpm_sala import _by_rows, _f32, ffn, rotate

FAMILY = "evabyte"
QUERIES = 256       # queries a block of the attention


def reference_config(cfg):
    """The sizes the reference needs, from a program config object or a
    dict with the same names."""
    get = cfg.get if isinstance(cfg, dict) else lambda k: getattr(cfg, k)
    keys = ("num_layers", "num_heads", "head_dim", "rope_theta",
            "rms_norm_eps", "window_size", "chunk_size")
    return {k: get(k) for k in keys}


def param_names(num_layers):
    names = [f"{FAMILY}_embed", f"{FAMILY}_norm_f", f"{FAMILY}_head_w"]
    for i in range(num_layers):
        p = f"{FAMILY}_l{i}"
        names += [f"{p}_n1", f"{p}_n2", f"{p}_mlp_gate_up_w",
                  f"{p}_mlp_down_w"]
        names += [f"{p}_attn_{n}" for n in (
            "q_w", "k_w", "v_w", "o_w", "mu", "phi")]
    return names


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + _f32(w))


def chunk_summaries(k, v, mu, phi, chunk, pooling="softmax"):
    """k, v [B, S, H, d] -> (ksum, vsum) [B, S // chunk, H, d] of every
    complete chunk; `pooling` "mean" weighs a chunk's rows alike."""
    b, s, h, d = k.shape
    n = s // chunk
    kc = k[:, :n * chunk].reshape(b, n, chunk, h, d)
    vc = v[:, :n * chunk].reshape(b, n, chunk, h, d)
    if pooling == "mean":
        wk = wv = jnp.full((b, n, chunk, h), 1.0 / chunk, jnp.float32)
    else:
        wk = jax.nn.softmax(jnp.einsum("bnchd,hd->bnch", kc, _f32(mu)), 2)
        wv = jax.nn.softmax(jnp.einsum("bnchd,hd->bnch", kc, _f32(phi)), 2)
    return (jnp.einsum("bnch,bnchd->bnhd", wk, kc),
            jnp.einsum("bnch,bnchd->bnhd", wv, vc))


def eva_attention(q, k, v, ksum, vsum, window, chunk, summaries=True):
    """q, k, v [B, S, H, d], the summaries [B, n, H, d] -> [B, S, H, d]:
    query t over its window's keys j <= t and the summaries
    c < (t // W) W / C, one softmax."""
    b, s, h, d = q.shape
    queries = min(QUERIES, window)
    while window % queries:
        queries -= 1
    pad = -s % window
    qp, kp, vp = (jnp.pad(x, [(0, 0), (0, pad), (0, 0), (0, 0)])
                  for x in (q, k, v))
    n = ksum.shape[1]
    per = window // chunk

    def block(q0):
        t = q0 + jnp.arange(queries)                              # [T]
        w0 = (q0 // window) * window
        qb = jax.lax.dynamic_slice_in_dim(qp, q0, queries, axis=1)
        kw = jax.lax.dynamic_slice_in_dim(kp, w0, window, axis=1)
        vw = jax.lax.dynamic_slice_in_dim(vp, w0, window, axis=1)
        j = w0 + jnp.arange(window)
        exact = j[None, :] <= t[:, None]                          # [T, W]
        scores = jnp.einsum("bthd,bjhd->bhtj", qb, kw) / math.sqrt(d)
        scores = jnp.where(exact, scores, -jnp.inf)
        vals = vw
        if summaries and n:
            seen = jnp.arange(n)[None, :] < (t // window * per)[:, None]
            more = jnp.einsum("bthd,bchd->bhtc", qb, ksum) / math.sqrt(d)
            scores = jnp.concatenate(
                [scores, jnp.where(seen, more, -jnp.inf)], -1)
            vals = jnp.concatenate([vw, vsum], 1)
        return jnp.einsum("bhtj,bjhd->bthd", jax.nn.softmax(scores, -1),
                          vals)

    outs = jax.lax.map(block, jnp.arange(0, s + pad, queries))
    return jnp.moveaxis(outs, 0, 1).reshape(b, s + pad, h, d)[:, :s]


def attention(p, a, cfg, summaries=True, pooling="softmax"):
    """a [B, S, hidden] -> the attention branch's output."""
    h, d = cfg["num_heads"], cfg["head_dim"]
    b, s, _ = a.shape

    def heads(name):
        return _by_rows(lambda r: r @ _f32(p[name]), a).reshape(b, s, h, d)

    q = rotate(heads("attn_q_w"), cfg["rope_theta"])
    k = rotate(heads("attn_k_w"), cfg["rope_theta"])
    v = heads("attn_v_w")
    ksum, vsum = chunk_summaries(k, v, p["attn_mu"], p["attn_phi"],
                                 cfg["chunk_size"], pooling)
    o = eva_attention(q, k, v, ksum, vsum, cfg["window_size"],
                      cfg["chunk_size"], summaries)
    return _by_rows(lambda r: r @ _f32(p["attn_o_w"]), o.reshape(b, s, -1))


def forward(params, ids, cfg, summaries=True, pooling="softmax",
            stream_dtype=None):
    """ids [B, S] -> {"logits": next-byte logits after the last position
    [B, V] float32}. `params` maps the program's parameter names to
    arrays of any float dtype; `summaries`, `pooling` and `stream_dtype`
    as the module says."""
    cfg = reference_config(cfg)
    eps = cfg["rms_norm_eps"]
    ids = jnp.asarray(ids)

    def stream(x):
        if stream_dtype is None:
            return x
        info = jnp.finfo(stream_dtype)
        return jax.lax.reduce_precision(x, info.nexp, info.nmant)

    with jax.default_matmul_precision("highest"):
        x = stream(_f32(jnp.asarray(params[f"{FAMILY}_embed"])[ids]))
        # one compilation for every layer: they share shapes
        attn = jax.jit(lambda p, w, x: stream(x + attention(
            p, _by_rows(lambda r: rms_norm(r, w, eps), x), cfg, summaries,
            pooling)))
        mlp = jax.jit(lambda p, w, h: stream(h + _by_rows(
            lambda r: ffn(p, rms_norm(r, w, eps)), h)))
        for i in range(cfg["num_layers"]):
            prefix = f"{FAMILY}_l{i}_"
            # read by key: a mapping that rounds what it hands out
            # (a reading below the stated precision) rounds these too
            own = {k[len(prefix):]: params[k] for k in params
                   if k.startswith(prefix)}
            x = attn({k: own[k] for k in own if k.startswith("attn_")},
                     own["n1"], x)
            x = mlp({k: own[k] for k in ("mlp_gate_up_w", "mlp_down_w")},
                    own["n2"], x)
        last = rms_norm(x[:, -1, :], params[f"{FAMILY}_norm_f"], eps)
        logits = last @ _f32(params[f"{FAMILY}_head_w"])
    return {"logits": np.asarray(logits)}
