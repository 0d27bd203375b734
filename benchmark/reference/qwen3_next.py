"""Plain reference of the Qwen3-Next decoder (`model_type` "qwen3_next"):
the whole forward pass in `jax.numpy`, float32, highest matmul
precision; the Gated DeltaNet mixer as the token-by-token recurrence
(`lax.scan` over the positions), no chunks, no solve, no cache, no
kernels; the attention over the whole sequence. Written from the
equations below, not from `paddle_tpu/models/qwen3_next.py`; it reads the
program's weights by their names and is given the same share of the
deployment (which routed experts and which rows of the vocabulary live
here).

    x0 = E[ids]
    every layer:  h = x + Mixer(N(x));  y = h + FFN(N(h))
    after the last: logits = N(y) W_head    (the vocabulary rows held here)
    N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)
    No projection carries a bias, nor does the convolution.

    Gated DeltaNet mixer (a layer whose published number i has
    (i + 1) % 4 != 0; Hk key heads, Hv value heads of dk = dv lanes,
    value head h reads key head h // (Hv / Hk)):
        [q | k | v | z] = a W_qkvz           (Hk dk | Hk dk | Hv dv | Hv dv)
        [b | al] = a W_ba                    (Hv | Hv)
        [q | k | v]_t <- silu(sum_{j=0..3} w_c[:, j] * [q | k | v]_{t-3+j})
                 depthwise and causal, zeros before the first token
        per value head h and position t:
        beta_t = sigmoid(b_t);  g_t = -exp(A_log[h]) softplus(al_t + dt_bias[h])
        alpha_t = exp(g_t)
        q_t <- q_t / sqrt(sum q_t^2 + 1e-6) / sqrt(dk)
        k_t <- k_t / sqrt(sum k_t^2 + 1e-6)
        S'_t = alpha_t S_{t-1};  u_t = beta_t (v_t - S'_t^T k_t)
        S_t = S'_t + k_t (outer) u_t         S [dk, dv], S_-1 = 0
        o_t = S_t^T q_t
        o_t <- o_t / sqrt(mean(o_t^2) + eps) * w_n * silu(z_t)   per head;
               w_n [dv] is shared by the heads and is a plain gain; the
               gate is applied AFTER the norm
        Mixer = concat_h(o_t) W_out
    Gated attention mixer ((i + 1) % 4 == 0; nh query heads over nkv KV
    heads of dh lanes):
        [q | gate] = a W_q  (nh dh | nh dh);  k = a W_k;  v = a W_v
        q <- N_q(q), k <- N_k(k) per head over dh lanes (gains 1 + w)
        the LEADING r lanes of each q and k head turn by rotate-half at
        theta^(-2i / r), i = 0 .. r/2 - 1; the other dh - r pass
        scores = q k^T / sqrt(dh), query head n reads KV head
        n // (nh / nkv), key j visible to query i iff j <= i, softmax
        Mixer = (softmax v * sigmoid(gate)) W_o
    FFN (every layer): p = softmax(m W_r) over all E
        sel = top_k(p);  w = p[sel] / sum(p[sel])
        FFN = sum_{e in sel, e held here} w_e silu(m Wg_e) * (m Wu_e) Wd_e
              + sigmoid(m w_sg) * silu(m Wsg) * (m Wsu) Wsd   (shared expert)

Departures, as in the program: W_qkvz, W_ba and W_q are laid out part by
part (the published matrices interleave the parts by key head, resp. by
query head: with seeded weights a relabelling of columns); the
multi-token-prediction module is left out (no key in the config);
`router_aux_loss_coef` and `output_router_logits` are training terms.

On the chip the weights stay resident in bfloat16 and are cast up one
layer, and one group of experts, at a time.
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

EXPERT_BLOCK = 16       # experts cast up to float32 at a time
FAMILY = "qwen3_next"


def reference_config(cfg):
    """The sizes the reference needs, from a program config object or a
    dict with the same names."""
    get = cfg.get if isinstance(cfg, dict) else lambda k: getattr(cfg, k)
    keys = ("layer_kinds", "num_heads", "num_kv_heads", "head_dim",
            "rotary_dim", "rope_theta", "linear_num_key_heads",
            "linear_num_value_heads", "linear_key_head_dim",
            "linear_value_head_dim", "linear_conv_kernel_dim", "top_k",
            "route_norm", "expert_offset", "rms_norm_eps")
    return {k: get(k) for k in keys}


def param_names(layer_kinds):
    names = [f"{FAMILY}_embed", f"{FAMILY}_norm_f", f"{FAMILY}_head_w"]
    for i, (kind, _ffn) in enumerate(layer_kinds):
        p = f"{FAMILY}_l{i}"
        names += [f"{p}_n1", f"{p}_n2"]
        if kind == "linear":
            names += [f"{p}_{n}" for n in (
                "in_qkvz_w", "in_ba_w", "conv_w", "a_log", "dt_bias",
                "gate_norm", "out_w")]
        else:
            names += [f"{p}_attn_{n}" for n in (
                "q_w", "k_w", "v_w", "o_w", "qn", "kn")]
        names += [f"{p}_{n}" for n in (
            "router_w", "experts_gate_up_w", "experts_down_w",
            "shared_gate_up_w", "shared_down_w", "shared_gate_w")]
    return names


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def rms_norm(x, w, eps):
    """The stored gain is w, the gain applied 1 + w."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + _f32(w))


def swiglu(x, w_gate_up, w_down):
    f = w_gate_up.shape[-1] // 2
    gu = x @ _f32(w_gate_up)
    return (jax.nn.silu(gu[..., :f]) * gu[..., f:]) @ _f32(w_down)


def delta_mixer(p, a, cfg, state_dtype=None, correction=True):
    """a [B, S, hidden] -> the mixer's output. `state_dtype` rounds the
    carried state after every step, `correction` False drops the delta
    rule's correction (u_t = beta_t v_t): readings below the stated
    precision resp. of another recurrence, never used by a cell's
    check."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    taps = cfg["linear_conv_kernel_dim"]
    kd, vd = hk * dk, hv * dv
    bsz, s, _ = a.shape
    proj = a @ _f32(p["in_qkvz_w"])
    qkv, z = proj[..., :2 * kd + vd], proj[..., 2 * kd + vd:]
    ba = a @ _f32(p["in_ba_w"])
    b, al = ba[..., :hv], ba[..., hv:]

    w_c = _f32(p["conv_w"])
    padded = jnp.pad(qkv, [(0, 0), (taps - 1, 0), (0, 0)])
    qkv = jax.nn.silu(sum(w_c[:, j] * padded[:, j:j + s]
                          for j in range(taps)))
    q = qkv[..., :kd].reshape(bsz, s, hk, dk)
    k = qkv[..., kd:2 * kd].reshape(bsz, s, hk, dk)
    v = qkv[..., 2 * kd:].reshape(bsz, s, hv, dv)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) \
        / math.sqrt(dk)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    # value head h reads key head h // (Hv / Hk)
    q = jnp.repeat(q, hv // hk, axis=2)
    k = jnp.repeat(k, hv // hk, axis=2)
    beta = jax.nn.sigmoid(b)                                # [B, S, Hv]
    alpha = jnp.exp(-jnp.exp(_f32(p["a_log"]))
                    * jax.nn.softplus(al + _f32(p["dt_bias"])))

    def step(state, inp):
        q_t, k_t, v_t, beta_t, alpha_t = inp    # [B,Hv,dk] x2 [B,Hv,dv] [B,Hv] x2
        state = alpha_t[..., None, None] * state
        held = jnp.einsum("bhkv,bhk->bhv", state, k_t) if correction else 0.0
        u_t = beta_t[..., None] * (v_t - held)
        state = state + k_t[..., :, None] * u_t[..., None, :]
        if state_dtype is not None:
            # not a pair of casts: XLA may keep the excess precision
            info = jnp.finfo(state_dtype)
            state = jax.lax.reduce_precision(state, info.nexp, info.nmant)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    _final, os = jax.lax.scan(
        step, jnp.zeros((bsz, hv, dk, dv), jnp.float32),
        tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, beta, alpha)),
    )
    o = jnp.moveaxis(os, 0, 1)                              # [B, S, Hv, dv]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg["rms_norm_eps"]) * _f32(p["gate_norm"])
    o = o * jax.nn.silu(z.reshape(bsz, s, hv, dv))
    return o.reshape(bsz, s, vd) @ _f32(p["out_w"])


def rotate_leading(x, rot, theta):
    """x [B, S, heads, dh]: the leading `rot` lanes of each head turned
    by rotate-half at its position, the rest passed."""
    s = x.shape[1]
    inv_freq = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def attention_mixer(p, a, cfg):
    b, s, _ = a.shape
    nh, kvh, dh = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    qg = a @ _f32(p["attn_q_w"])
    q, gate = qg[..., :nh * dh], qg[..., nh * dh:]
    q = rms_norm(q.reshape(b, s, nh, dh), p["attn_qn"], eps)
    k = rms_norm((a @ _f32(p["attn_k_w"])).reshape(b, s, kvh, dh),
                 p["attn_kn"], eps)
    v = (a @ _f32(p["attn_v_w"])).reshape(b, s, kvh, dh)
    q = rotate_leading(q, cfg["rotary_dim"], cfg["rope_theta"])
    k = rotate_leading(k, cfg["rotary_dim"], cfg["rope_theta"])
    group = nh // kvh
    visible = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    outs = []
    for kv in range(kvh):       # query heads kv*group .. read KV head kv
        qs = q[:, :, kv * group:(kv + 1) * group]
        scores = jnp.einsum("bind,bjd->bnij", qs, k[:, :, kv]) / math.sqrt(dh)
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        outs.append(jnp.einsum("bnij,bjd->bind", jax.nn.softmax(scores, -1),
                               v[:, :, kv]))
    out = jnp.concatenate(outs, axis=2).reshape(b, s, nh * dh)
    return (out * jax.nn.sigmoid(gate)) @ _f32(p["attn_o_w"])


def route(p, m, cfg, follow=None, tie_eps=0.0):
    """(selected [.., k], weights [.., k], report). With `follow` (the
    ids the program selected for the same tokens) a token whose program
    choice differs from the reference's takes the program's ids where
    each of them has a router LOGIT within `tie_eps` of the reference's
    k-th (an ambiguous top-k, decided by rounding; the softmax scores
    are of order 1 / E and say nothing in absolute terms), and is
    counted; any other difference is a mismatch and keeps the
    reference's ids."""
    k = cfg["top_k"]
    logits = m @ _f32(p["router_w"])
    scores = jax.nn.softmax(logits, -1)
    top, sel = jax.lax.top_k(logits, k)
    report = {}
    if follow is not None:
        theirs = jnp.asarray(follow, jnp.int32)
        differs = jnp.any(jnp.sort(theirs, -1) != jnp.sort(sel, -1), -1)
        their_logits = jnp.take_along_axis(logits, theirs, -1)
        gap = top[..., -1] - jnp.min(their_logits, -1)
        tie = differs & (gap <= tie_eps)
        sel = jnp.where(tie[..., None], theirs, sel)
        report = {"tokens": int(differs.size),
                  "near_ties": int(jnp.sum(tie)),
                  "mismatches": int(jnp.sum(differs & ~tie)),
                  "largest_gap": float(jnp.max(jnp.where(differs, gap, 0.0)))}
    w = jnp.take_along_axis(scores, sel, -1)
    if cfg["route_norm"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return sel, w, report


@jax.jit
def _expert_block(m, sel, w, wgu_b, wd_b, first):
    """The part of experts `first` .. of a block of them cast up."""
    out = jnp.zeros_like(m)
    for e in range(wgu_b.shape[0]):
        w_e = jnp.sum(jnp.where(sel == first + e, w, 0.0), -1, keepdims=True)
        out = out + w_e * swiglu(m, wgu_b[e], wd_b[e])
    return out


def routed_part(p, m, sel, w, cfg):
    """sum over the selected experts held here of w_e SwiGLU_e(m), the
    experts cast up `EXPERT_BLOCK` at a time (one compilation for all
    layers: they share shapes)."""
    wgu, wd = p["experts_gate_up_w"], p["experts_down_w"]
    out = jnp.zeros_like(m)
    for e0 in range(0, wgu.shape[0], EXPERT_BLOCK):
        out = out + _expert_block(
            m, sel, w, wgu[e0:e0 + EXPERT_BLOCK], wd[e0:e0 + EXPERT_BLOCK],
            jnp.int32(cfg["expert_offset"] + e0))
    return out


def shared_part(p, m):
    """The shared expert behind its own sigmoid gate: every chip
    computes it alike."""
    return jax.nn.sigmoid(m @ _f32(p["shared_gate_w"])) \
        * swiglu(m, p["shared_gate_up_w"], p["shared_down_w"])


def ffn(p, m, cfg, follow=None, tie_eps=0.0):
    sel, w, report = route(p, m, cfg, follow, tie_eps)
    return routed_part(p, m, sel, w, cfg) + shared_part(p, m), sel, report


def forward(params, ids, cfg, follow=None, tie_eps=0.0, state_dtype=None,
            correction=True):
    """ids [B, S] -> {"logits": next-token logits after the last position
    [B, V] float32, "selected": [layers][B, S, k], "routing": the
    `route` reports summed over the layers}. `params` maps the program's
    parameter names to arrays of any float dtype, on the host or the
    device."""
    cfg = reference_config(cfg)
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.asarray(params[f"{FAMILY}_embed"])[jnp.asarray(ids)])
        selected, routing = [], {}
        # one compilation a mixer kind: the layers of a kind share shapes
        linear = jax.jit(lambda p, a: delta_mixer(p, a, cfg, state_dtype,
                                                  correction))
        full = jax.jit(lambda p, a: attention_mixer(p, a, cfg))
        for i, (kind, _ffn) in enumerate(cfg["layer_kinds"]):
            prefix = f"{FAMILY}_l{i}_"
            # read by key: a mapping that rounds what it hands out
            # (a reading below the stated precision) rounds these too
            own = {k[len(prefix):]: params[k] for k in params
                   if k.startswith(prefix)}
            a = rms_norm(x, own["n1"], eps)
            h = x + (linear if kind == "linear" else full)(own, a)
            theirs = None if follow is None else follow[i]
            m, sel, report = ffn(own, rms_norm(h, own["n2"], eps), cfg,
                                 theirs, tie_eps)
            selected.append(np.asarray(sel))
            for key, value in report.items():
                routing[key] = max(routing.get(key, 0.0), value) \
                    if key == "largest_gap" \
                    else routing.get(key, 0) + value
            x = h + m
        last = rms_norm(x[:, -1, :], params[f"{FAMILY}_norm_f"], eps)
        logits = last @ _f32(params[f"{FAMILY}_head_w"])
    return {"logits": np.asarray(logits), "selected": selected,
            "routing": routing}
