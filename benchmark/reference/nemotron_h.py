"""Plain reference of the Nemotron-H decoder (NVIDIA Nemotron 3 family,
`model_type` "nemotron_h"): the whole forward pass in `jax.numpy`,
float32, highest matmul precision; the state-space mixer as the
token-by-token recurrence (`lax.scan` over the positions), no chunks, no
cache, no kernels. Written from the equations below, not from
`paddle_tpu/models/nemotron_h.py`; it reads the program's weights by
their names and is given the same share of the deployment (which routed
experts and which rows of the vocabulary live here).

    x0 = E[ids]                                             (no scale)
    every block:  y = x + Mixer(N(x));   N(x) = x rsqrt(mean(x^2) + eps) gain
    after the last block: logits = N_f(x) W_head   (the vocabulary rows held here)
    No projection carries a bias; the convolution does.

    Mamba-2 mixer `M` (H heads x P channels, G groups, state N,
    conv width C = H P + 2 G N):
        [z | xBC | dt] = a W_in                             (H P | C | H)
        xBC_t <- silu(b_c + sum_{j=0..k-1} w_c[:, j] * xBC_{t-k+1+j})
                 depthwise and causal, zeros before the first token
        xBC_t = [x_t (H x P) | B_t (G x N) | C_t (G x N)]; head h reads
                 group h // (H / G)
        dt_t = softplus(dt_t + dt_bias) per head (no clamp);  A = -exp(A_log)
        S_t = exp(dt_t A) S_{t-1} + dt_t * x_t (outer) B_t   S [H, P, N], S_-1 = 0
        y_t = S_t C_t + D * x_t
        g_t = y_t * silu(z_t), RMS-normalised inside each of the G groups
              of H P / G channels, times a gain of width H P (the gate is
              applied before the norm)
        Mixer = g_t W_out
    Attention mixer `*`: q = a W_q (nh x dh), k = a W_k, v = a W_v
        (nkv x dh); no QK-norm, no gate, NO positional term;
        scores = q k^T / sqrt(dh), query head n reads KV head
        n // (nh / nkv), key j visible to query i iff j <= i, softmax;
        Mixer = (softmax v) W_o
    Expert mixer `E` (latent experts): s = sigmoid(a W_r) over all E
        sel = top_k(s + b)       (b: the score-correction bias, selection only)
        w = s[sel] / (sum(s[sel]) + 1e-20) * routed_scaling_factor
        u = a W_dn                                          (hidden -> latent)
        r = sum_{e in sel, e held here} w_e relu(u W1_e)^2 W2_e
        Mixer = r W_up + relu(a Ws1)^2 Ws2                  (shared expert)

Left out, as in the program: the multi-token-prediction module (a
training head and a drafter, not part of the main forward pass);
`rescale_prenorm_residual` and `time_step_*` are initialisations.

On the chip the weights stay resident in bfloat16 and are cast up one
block, and one group of experts, at a time.
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

EXPERT_BLOCK = 8        # experts cast up to float32 at a time


def reference_config(cfg):
    """The sizes the reference needs, from a program config object or a
    dict with the same names."""
    get = cfg.get if isinstance(cfg, dict) else lambda k: getattr(cfg, k)
    keys = ("pattern", "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
            "n_groups", "conv_kernel", "num_heads", "num_kv_heads",
            "head_dim", "top_k", "route_scale", "route_norm",
            "expert_offset", "rms_norm_eps")
    return {k: get(k) for k in keys}


def param_names(pattern):
    names = ["nemotron_embed", "nemotron_norm_f", "nemotron_head_w"]
    for i, kind in enumerate(pattern):
        p = f"nemotron_l{i}"
        names.append(f"{p}_norm")
        if kind == "M":
            names += [f"{p}_{n}" for n in (
                "in_w", "conv_w", "conv_b", "a_log", "d", "dt_bias",
                "gate_norm", "out_w")]
        elif kind == "*":
            names += [f"{p}_attn_{n}_w" for n in "qkvo"]
        else:
            names += [f"{p}_{n}" for n in (
                "router_w", "expert_bias", "latent_down_w", "latent_up_w",
                "experts_up_w", "experts_down_w", "shared_up_w",
                "shared_down_w")]
    return names


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(gain)


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def mamba_mixer(p, a, cfg, state_dtype=None):
    """a [B, S, hidden] -> the mixer's output. `state_dtype` rounds the
    carried state after every step (a reading below the stated
    precision; never used by a cell's check)."""
    h, hp, n = cfg["mamba_num_heads"], cfg["mamba_head_dim"], \
        cfg["ssm_state_size"]
    g, k = cfg["n_groups"], cfg["conv_kernel"]
    d = h * hp
    bsz, s, _ = a.shape
    proj = a @ _f32(p["in_w"])
    z, xbc, dt = proj[..., :d], proj[..., d:d + d + 2 * g * n], \
        proj[..., d + d + 2 * g * n:]

    w_c, b_c = _f32(p["conv_w"]), _f32(p["conv_b"])
    padded = jnp.pad(xbc, [(0, 0), (k - 1, 0), (0, 0)])
    conv = b_c + sum(w_c[:, j] * padded[:, j:j + s] for j in range(k))
    xbc = jax.nn.silu(conv)
    x = xbc[..., :d].reshape(bsz, s, h, hp)
    b_t = xbc[..., d:d + g * n].reshape(bsz, s, g, n)
    c_t = xbc[..., d + g * n:].reshape(bsz, s, g, n)
    # head h reads group h // (H / G)
    b_t = jnp.repeat(b_t, h // g, axis=2)
    c_t = jnp.repeat(c_t, h // g, axis=2)
    dt = jax.nn.softplus(dt + _f32(p["dt_bias"]))     # [B, S, H]
    a_neg = -jnp.exp(_f32(p["a_log"]))
    d_skip = _f32(p["d"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp           # [B,H,P] [B,H,N] [B,H,N] [B,H]
        state = jnp.exp(dt_t * a_neg)[..., None, None] * state \
            + (dt_t[..., None] * x_t)[..., :, None] * b_t[..., None, :]
        if state_dtype is not None:
            # not a pair of casts: XLA may keep the excess precision
            info = jnp.finfo(state_dtype)
            state = jax.lax.reduce_precision(state, info.nexp, info.nmant)
        y_t = jnp.einsum("bhpn,bhn->bhp", state, c_t) \
            + d_skip[:, None] * x_t
        return state, y_t

    _final, ys = jax.lax.scan(
        step, jnp.zeros((bsz, h, hp, n), jnp.float32),
        tuple(jnp.moveaxis(v, 1, 0) for v in (x, b_t, c_t, dt)),
    )
    y = jnp.moveaxis(ys, 0, 1).reshape(bsz, s, d)
    gated = (y * jax.nn.silu(z)).reshape(bsz, s, g, d // g)
    gated = gated * jax.lax.rsqrt(
        jnp.mean(gated * gated, -1, keepdims=True) + cfg["rms_norm_eps"])
    gated = gated.reshape(bsz, s, d) * _f32(p["gate_norm"])
    return gated @ _f32(p["out_w"])


def attention_mixer(p, a, cfg):
    b, s, _ = a.shape
    nh, kvh, dh = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    q = (a @ _f32(p["attn_q_w"])).reshape(b, s, nh, dh)
    k = (a @ _f32(p["attn_k_w"])).reshape(b, s, kvh, dh)
    v = (a @ _f32(p["attn_v_w"])).reshape(b, s, kvh, dh)
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
    return out @ _f32(p["attn_o_w"])


def route(p, a, cfg, follow=None, tie_eps=0.0):
    """(selected [.., k], weights [.., k], report). With `follow` (the
    ids the program selected for the same tokens) a token whose program
    choice differs from the reference's takes the program's ids where
    each of them scores within `tie_eps` of the reference's k-th score
    (an ambiguous top-k, decided by rounding), and is counted; any other
    difference is a mismatch and keeps the reference's ids."""
    k = cfg["top_k"]
    s = jax.nn.sigmoid(a @ _f32(p["router_w"]))
    biased = s + _f32(p["expert_bias"])
    top, sel = jax.lax.top_k(biased, k)
    report = {}
    if follow is not None:
        theirs = jnp.asarray(follow, jnp.int32)
        differs = jnp.any(jnp.sort(theirs, -1) != jnp.sort(sel, -1), -1)
        their_scores = jnp.take_along_axis(biased, theirs, -1)
        gap = top[..., -1] - jnp.min(their_scores, -1)
        tie = differs & (gap <= tie_eps)
        sel = jnp.where(tie[..., None], theirs, sel)
        report = {"tokens": int(differs.size),
                  "near_ties": int(jnp.sum(tie)),
                  "mismatches": int(jnp.sum(differs & ~tie)),
                  "largest_gap": float(jnp.max(jnp.where(differs, gap, 0.0)))}
    w = jnp.take_along_axis(s, sel, -1)
    if cfg["route_norm"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return sel, w * cfg["route_scale"], report


def routed_part(p, u, sel, w, cfg):
    """sum over the selected experts held here of w_e relu(u W1_e)^2 W2_e
    in the latent, the experts cast up `EXPERT_BLOCK` at a time."""
    w1, w2 = p["experts_up_w"], p["experts_down_w"]
    offset = cfg["expert_offset"]

    @jax.jit
    def block(u, sel, w, w1_b, w2_b, first):
        out = jnp.zeros_like(u)
        for e in range(w1_b.shape[0]):
            w_e = jnp.sum(jnp.where(sel == first + e, w, 0.0), -1,
                          keepdims=True)
            out = out + w_e * (relu2(u @ _f32(w1_b[e])) @ _f32(w2_b[e]))
        return out

    out = jnp.zeros_like(u)
    for e0 in range(0, w1.shape[0], EXPERT_BLOCK):
        out = out + block(u, sel, w, w1[e0:e0 + EXPERT_BLOCK],
                          w2[e0:e0 + EXPERT_BLOCK], offset + e0)
    return out


def expert_mixer(p, a, cfg, follow=None, tie_eps=0.0):
    sel, w, report = route(p, a, cfg, follow, tie_eps)
    u = a @ _f32(p["latent_down_w"])
    out = routed_part(p, u, sel, w, cfg) \
        @ _f32(p["latent_up_w"])
    shared = relu2(a @ _f32(p["shared_up_w"])) \
        @ _f32(p["shared_down_w"])
    return out + shared, sel, report


def forward(params, ids, cfg, follow=None, tie_eps=0.0, state_dtype=None):
    """ids [B, S] -> {"logits": next-token logits after the last position
    [B, V] float32, "selected": [expert blocks][B, S, k], "routing": the
    `route` reports summed over the expert blocks}. `params` maps the
    program's parameter names to arrays of any float dtype, on the host
    or the device."""
    cfg = reference_config(cfg)
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.asarray(params["nemotron_embed"])[jnp.asarray(ids)])
        selected, routing, block_no = [], {}, 0
        # one compilation a block kind: the blocks of a kind share shapes
        mamba = jax.jit(lambda p, a: mamba_mixer(p, a, cfg, state_dtype))
        attention = jax.jit(lambda p, a: attention_mixer(p, a, cfg))
        for i, kind in enumerate(cfg["pattern"]):
            prefix = f"nemotron_l{i}_"
            own = {k[len(prefix):]: v for k, v in params.items()
                   if k.startswith(prefix)}
            a = rms_norm(x, own["norm"], eps)
            if kind == "M":
                m = mamba(own, a)
            elif kind == "*":
                m = attention(own, a)
            else:
                theirs = None if follow is None else follow[block_no]
                m, sel, report = expert_mixer(own, a, cfg, theirs, tie_eps)
                selected.append(np.asarray(sel))
                for key, value in report.items():
                    routing[key] = max(routing.get(key, 0.0), value) \
                        if key == "largest_gap" \
                        else routing.get(key, 0) + value
                block_no += 1
            x = x + m
        last = rms_norm(x[:, -1, :], params["nemotron_norm_f"], eps)
        logits = last @ _f32(params["nemotron_head_w"])
    return {"logits": np.asarray(logits), "selected": selected,
            "routing": routing}
