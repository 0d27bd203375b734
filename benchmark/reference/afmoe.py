"""Plain reference of the AFMoE decoder (arcee-ai Trinity family): the
whole forward pass in `jax.numpy`, float32, highest matmul precision, no
cache, no kernels, no batching tricks. Written from the equations below,
not from `paddle_tpu/models/afmoe.py`; it reads the program's weights by
their names and is given the same share of the deployment (which experts
and which rows of the vocabulary live here).

    x0 = E[ids] * sqrt(H)                                  (mup_enabled)
    layer:  h = x + N2(Attn(N1(x)));  y = h + N4(FFN(N3(h)))
    N(x)  = x * rsqrt(mean(x^2) + eps) * gain              (eps 1e-5)
    Attn(a): q = a Wq (nh x dh), k = a Wk, v = a Wv (nkv x dh), g = a Wg
        q, k RMS-normalised per head with a learned gain of width dh;
        sliding layers: rotate-half rotary positions (theta, whole head)
        on q and k; full layers: no positional term at all;
        scores = q k^T / sqrt(dh), query head n reads KV head n // (nh/nkv);
        key j visible to query i iff j <= i, on sliding layers also
        i - j < window; softmax in float32;
        out = (softmax(scores) v * sigmoid(g)) Wo
    dense FFN:  Wd(silu(Wg x) * Wu x)
    expert FFN: s = sigmoid(x Wr) over all E experts, float32
        sel = top_k(s + b)        (b: the expert bias buffer, selection only)
        w = s[sel] / (sum(s[sel]) + 1e-20) * route_scale
        FFN(x) = Shared(x) + sum_{e in sel, e held here} w_e Expert_e(x)
    logits = N_f(x) W_head          (the vocabulary rows held here)

`load_balance_coeff` is a training term and is not used. "Depth-scaled"
sandwich norm is an initialisation of the gains; no forward term.

On the chip the weights stay resident in bfloat16 and are cast up one
layer, and one block of experts, at a time (float32 copies of all of them
would not fit beside them).
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

SLIDING = "sliding_attention"
EXPERT_BLOCK = 4        # experts cast up to float32 at a time


def reference_config(cfg):
    """The sizes the reference needs, from a program config object or a
    dict with the same names."""
    get = cfg.get if isinstance(cfg, dict) else lambda k: getattr(cfg, k)
    keys = ("hidden_size", "num_heads", "num_kv_heads", "head_dim",
            "top_k", "route_scale", "route_norm", "expert_offset",
            "sliding_window", "rope_theta", "rms_norm_eps", "mup_enabled",
            "num_shared_experts", "layer_kinds")
    return {k: get(k) for k in keys}


def param_names(layer_kinds):
    names = ["afmoe_embed", "afmoe_norm_f", "afmoe_head_w"]
    for i, (_attn, ffn) in enumerate(layer_kinds):
        p = f"afmoe_l{i}"
        names += [f"{p}_{n}" for n in (
            "n1", "n2", "n3", "n4", "attn_q_w", "attn_k_w", "attn_v_w",
            "attn_g_w", "attn_o_w", "attn_qn", "attn_kn")]
        if ffn == "dense":
            names += [f"{p}_mlp_gate_up_w", f"{p}_mlp_down_w"]
        else:
            names += [f"{p}_{n}" for n in (
                "router_w", "expert_bias", "experts_gate_up_w",
                "experts_down_w", "shared_gate_up_w", "shared_down_w")]
    return names


def _f32(x):
    return jnp.asarray(x).astype(jnp.float32)


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * _f32(gain)


def rotate_half_rope(x, theta):
    """x [B, S, n, dh], row s at position s."""
    dh = x.shape[-1]
    inv = theta ** (-np.arange(0, dh, 2, dtype=np.float32) / dh)
    ang = np.arange(x.shape[1], dtype=np.float32)[:, None] * inv[None]
    cos = jnp.asarray(np.concatenate([np.cos(ang), np.cos(ang)], -1))
    sin = jnp.asarray(np.concatenate([np.sin(ang), np.sin(ang)], -1))
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    rotated = jnp.concatenate([-x2, x1], -1)
    return x * cos[None, :, None, :] + rotated * sin[None, :, None, :]


def swiglu_ffn(x, w_gate_up, w_down):
    gu = x @ _f32(w_gate_up)
    f = gu.shape[-1] // 2
    return (jax.nn.silu(gu[..., :f]) * gu[..., f:]) @ _f32(w_down)


def attention(p, prefix, a, cfg, sliding):
    b, s, _ = a.shape
    nh, kvh, dh = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    q = (a @ _f32(p[f"{prefix}_attn_q_w"])).reshape(b, s, nh, dh)
    k = (a @ _f32(p[f"{prefix}_attn_k_w"])).reshape(b, s, kvh, dh)
    v = (a @ _f32(p[f"{prefix}_attn_v_w"])).reshape(b, s, kvh, dh)
    g = a @ _f32(p[f"{prefix}_attn_g_w"])
    q = rms_norm(q, p[f"{prefix}_attn_qn"], eps)
    k = rms_norm(k, p[f"{prefix}_attn_kn"], eps)
    if sliding:
        q = rotate_half_rope(q, cfg["rope_theta"])
        k = rotate_half_rope(k, cfg["rope_theta"])
    group = nh // kvh
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    visible = j <= i
    if sliding:
        visible = visible & (i - j < cfg["sliding_window"])
    outs = []
    for kv in range(kvh):       # query heads kv*group .. read KV head kv
        qs = q[:, :, kv * group:(kv + 1) * group]
        scores = jnp.einsum("bind,bjd->bnij", qs, k[:, :, kv]) / math.sqrt(dh)
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        outs.append(jnp.einsum("bnij,bjd->bind", jax.nn.softmax(scores, -1),
                               v[:, :, kv]))
    out = jnp.concatenate(outs, axis=2)
    out = out.reshape(b, s, nh * dh) * jax.nn.sigmoid(g)
    return out @ _f32(p[f"{prefix}_attn_o_w"])


def route(p, prefix, x, cfg, follow=None, tie_eps=0.0, score_dtype=None):
    """(selected [.., k], weights [.., k], report). With `follow` (the
    ids the program selected for the same tokens) a token whose program
    choice differs from the reference's takes the program's ids where
    each of them scores within `tie_eps` of the reference's k-th score
    (an ambiguous top-k, decided by rounding), and is counted; any other
    difference is a mismatch and keeps the reference's ids."""
    k = cfg["top_k"]
    logits = x @ _f32(p[f"{prefix}_router_w"])
    if score_dtype is not None:
        # a reading in a lower precision than the configuration states
        logits = logits.astype(score_dtype).astype(jnp.float32)
    s = jax.nn.sigmoid(logits)
    biased = s + _f32(p[f"{prefix}_expert_bias"])
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


def routed_part(p, prefix, x, sel, w, cfg):
    """sum over the selected experts held here of w_e Expert_e(x), the
    experts cast up `EXPERT_BLOCK` at a time."""
    wgu, wd = p[f"{prefix}_experts_gate_up_w"], p[f"{prefix}_experts_down_w"]
    offset = cfg["expert_offset"]

    @jax.jit
    def block(x, sel, w, wgu_b, wd_b, first):
        out = jnp.zeros_like(x)
        for e in range(wgu_b.shape[0]):
            w_e = jnp.sum(jnp.where(sel == first + e, w, 0.0), -1,
                          keepdims=True)
            out = out + w_e * swiglu_ffn(x, wgu_b[e], wd_b[e])
        return out

    out = jnp.zeros_like(x)
    for e0 in range(0, wgu.shape[0], EXPERT_BLOCK):
        out = out + block(x, sel, w, wgu[e0:e0 + EXPERT_BLOCK],
                          wd[e0:e0 + EXPERT_BLOCK], offset + e0)
    return out


def expert_ffn(p, prefix, x, cfg, follow=None, tie_eps=0.0,
               score_dtype=None):
    sel, w, report = route(p, prefix, x, cfg, follow, tie_eps, score_dtype)
    out = routed_part(p, prefix, x, sel, w, cfg)
    if cfg["num_shared_experts"]:
        out = out + swiglu_ffn(x, p[f"{prefix}_shared_gate_up_w"],
                               p[f"{prefix}_shared_down_w"])
    return out, sel, report


def forward(params, ids, cfg, follow=None, tie_eps=0.0, score_dtype=None):
    """ids [B, S] -> {"logits": next-token logits after the last position
    [B, V] float32, "selected": [expert layers][B, S, k], "routing":
    the `route` reports summed over the expert layers}. `params` maps the
    program's parameter names to arrays of any float dtype, on the host
    or the device. `score_dtype` rounds the router's logits (a reading
    below the stated precision; never used by a cell's check)."""
    cfg = reference_config(cfg)
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.asarray(params["afmoe_embed"])[jnp.asarray(ids)])
        if cfg["mup_enabled"]:
            x = x * math.sqrt(cfg["hidden_size"])
        selected, routing, layer_no = [], {}, 0
        for i, (attn_kind, ffn_kind) in enumerate(cfg["layer_kinds"]):
            prefix = f"afmoe_l{i}"
            a = rms_norm(x, params[f"{prefix}_n1"], eps)
            sliding = attn_kind == SLIDING
            attn = jax.jit(
                lambda p, a: attention(p, prefix, a, cfg, sliding)
            )({k: v for k, v in params.items()
               if k.startswith(f"{prefix}_attn_")}, a)
            h = x + rms_norm(attn, params[f"{prefix}_n2"], eps)
            m = rms_norm(h, params[f"{prefix}_n3"], eps)
            if ffn_kind == "dense":
                m = swiglu_ffn(m, params[f"{prefix}_mlp_gate_up_w"],
                               params[f"{prefix}_mlp_down_w"])
            else:
                theirs = None if follow is None else follow[layer_no]
                m, sel, report = expert_ffn(params, prefix, m, cfg, theirs,
                                            tie_eps, score_dtype)
                selected.append(np.asarray(sel))
                for key, value in report.items():
                    routing[key] = max(routing.get(key, 0.0), value) \
                        if key == "largest_gap" \
                        else routing.get(key, 0) + value
                layer_no += 1
            x = h + rms_norm(m, params[f"{prefix}_n4"], eps)
        last = rms_norm(x[:, -1, :], params["afmoe_norm_f"], eps)
        logits = last @ _f32(params["afmoe_head_w"])
    return {"logits": np.asarray(logits), "selected": selected,
            "routing": routing}
