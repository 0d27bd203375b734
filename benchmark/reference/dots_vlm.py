"""Plain reference of the dots.vlm1 text decoder (rednote-hilab,
`model_type` "dots_vlm", 672B-A37B): the whole forward pass in
`jax.numpy`, float32, highest matmul precision, latent attention in the
EXPANDED form at every position: no absorption, no cache, no kernel.
Written from the equations below, not from
`paddle_tpu/models/dots_vlm.py`; it reads the program's weights by their
names and is given the same share of the deployment (which routed
experts and which rows of the vocabulary live here).

    x0 = E[ids]                                             (no scale)
    layer:  h = x + Attn(N(x));  y = h + FFN(N(h))
    N(x) = x * rsqrt(mean(x^2) + eps) * gain                (eps 1e-6)
    after the last layer: logits = N_f(x) W_head  (the vocabulary rows here)
    No projection carries a bias.

    Latent attention (nh heads; a head has dn non-rotary + dr rotary
    query/key lanes and dv value lanes; ranks rq, r):
        c_q = N_q(a W_qa)  (rq);   q = c_q W_qb -> per head [q_nope | q_pe]
        [c_kv | k_pe] = a W_kva  (r | dr);   c_kv <- N_kv(c_kv)
        k_pe <- R_t(k_pe): ONE rotated key part a token, shared by all
        heads;  q_pe <- R_t(q_pe) per head
        [k_nope | v] of head h = c_kv W_kvb[h]     (stored [nh, r, dn + dv]:
                                                    W_kvb[h] = [W_UK | W_UV])
        score_ij[h] = (q_nope_i[h] . k_nope_j[h] + q_pe_i[h] . k_pe_j) * s
        key j visible to query i iff j <= i; softmax in float32
        Attn = concat_h(softmax v[h]) W_o
        s = (dn + dr)^-0.5 * m^2,  m = 0.1 * mscale_all_dim * ln(factor) + 1
    R_t: rotate-half over dr lanes at position t with YaRN frequencies:
        f_i = theta^(-2i/dr), i < dr/2
        d(rot) = dr ln(original / (2 pi rot)) / (2 ln theta)
        low = floor(d(beta_fast)), high = ceil(d(beta_slow))
        ramp_i = clip((i - low) / (high - low), 0, 1)
        inv_freq_i = f_i (1 - ramp_i) + (f_i / factor) ramp_i
        cos and sin scaled by mscale(factor, mscale) /
        mscale(factor, mscale_all_dim)                    (1 as published)
    dense FFN (leading layers): Wd(silu(Wg m) * Wu m)
    expert FFN: sc = sigmoid(m W_r) over all E experts, float32
        c = sc + b    (b: e_score_correction_bias, selection only)
        group g (E / n_group consecutive experts) scores the sum of its
        two largest c; the topk_group best groups are kept, every other
        expert's c is set to 0;  sel = top_k(c)
        w = sc[sel] / (sum(sc[sel]) + 1e-20) * routed_scaling_factor
        FFN = sum_{e in sel, e held here} w_e Expert_e(m) + Shared(m)

Left out, as in the program: the multi-token-prediction module (a
training head and a drafter), the vision encoder (the catalog row gives
it no size), `seq_aux` (a training loss), `ep_size` (a launcher setting).
The published code permutes the rotary lanes from interleaved pairs to
halves first: with seeded weights a relabelling of W_qb's and W_kva's
columns.

On the chip the weights stay resident in bfloat16 and are cast up a
block of heads, of the dense width, or of experts at a time.
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

HEAD_BLOCK = 16         # heads attended (and cast up) at a time
FFN_BLOCK = 4608        # columns of a dense FFN cast up at a time
EXPERT_BLOCK = 4        # experts cast up at a time


def reference_config(cfg):
    """The sizes the reference needs, from a program config object or a
    dict with the same names."""
    get = cfg.get if isinstance(cfg, dict) else lambda k: getattr(cfg, k)
    keys = ("num_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "top_k", "n_group",
            "topk_group", "route_scale", "route_norm", "expert_offset",
            "num_shared_experts", "rope_theta", "rope_scaling",
            "rms_norm_eps", "layer_kinds")
    return {k: get(k) for k in keys}


def param_names(layer_kinds):
    names = ["dots_embed", "dots_norm_f", "dots_head_w"]
    for i, (_attn, ffn) in enumerate(layer_kinds):
        p = f"dots_l{i}"
        names += [f"{p}_{n}" for n in (
            "n1", "n2", "attn_q_a_w", "attn_q_a_n", "attn_q_b_w",
            "attn_kv_a_w", "attn_kv_a_n", "attn_kv_b_w", "attn_o_w")]
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


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim, theta, yarn):
    """(inv_freq [dim / 2], the scale of cos and sin)."""
    f = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not yarn:
        return f, 1.0

    def d(rotations):
        return dim * math.log(yarn["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(d(yarn["beta_fast"])), 0)
    high = min(math.ceil(d(yarn["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 0.001),
                   0.0, 1.0)
    scale = _mscale(yarn["factor"], yarn.get("mscale", 1.0)) \
        / _mscale(yarn["factor"], yarn.get("mscale_all_dim", 0.0))
    return f * (1 - ramp) + f / yarn["factor"] * ramp, scale


def rotate_half(x, cfg):
    """x [B, S, n, dr], row s at position s."""
    dr = x.shape[-1]
    inv, scale = yarn_inv_freq(dr, cfg["rope_theta"], cfg["rope_scaling"])
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.concatenate([np.cos(ang)] * 2, -1) * scale,
                      jnp.float32)
    sin = jnp.asarray(np.concatenate([np.sin(ang)] * 2, -1) * scale,
                      jnp.float32)
    x1, x2 = x[..., : dr // 2], x[..., dr // 2:]
    rotated = jnp.concatenate([-x2, x1], -1)
    return x * cos[None, :, None, :] + rotated * sin[None, :, None, :]


def softmax_scale(cfg):
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    yarn = cfg["rope_scaling"]
    if yarn and yarn.get("mscale_all_dim"):
        s *= _mscale(yarn["factor"], yarn["mscale_all_dim"]) ** 2
    return s


def attention(p, prefix, a, cfg, latent_dtype=None):
    """The expanded form, `HEAD_BLOCK` heads at a time. `latent_dtype`
    rounds what a latent cache would hold of a position, [c_kv | k_pe]
    (a reading below the stated precision; never a cell's check)."""
    b, s, _ = a.shape
    nh, r = cfg["num_heads"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    eps = cfg["rms_norm_eps"]
    c_q = rms_norm(a @ _f32(p[f"{prefix}_attn_q_a_w"]),
                   p[f"{prefix}_attn_q_a_n"], eps)
    kv_a = a @ _f32(p[f"{prefix}_attn_kv_a_w"])
    c_kv = rms_norm(kv_a[..., :r], p[f"{prefix}_attn_kv_a_n"], eps)
    k_pe = rotate_half(kv_a[..., r:][:, :, None, :], cfg)[:, :, 0]
    if latent_dtype is not None:
        c_kv, k_pe = (t.astype(latent_dtype).astype(jnp.float32)
                      for t in (c_kv, k_pe))
    scale = softmax_scale(cfg)
    visible = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]

    @jax.jit
    def heads(c_q, c_kv, k_pe, w_qb, w_kvb, w_o):
        n = w_o.shape[0] // dv
        q = (c_q @ _f32(w_qb)).reshape(b, s, n, dn + dr)
        q_nope, q_pe = q[..., :dn], rotate_half(q[..., dn:], cfg)
        kv = jnp.einsum("bsr,hrd->bshd", c_kv, _f32(w_kvb))
        k_nope, v = kv[..., :dn], kv[..., dn:]
        scores = (jnp.einsum("bihd,bjhd->bhij", q_nope, k_nope)
                  + jnp.einsum("bihd,bjd->bhij", q_pe, k_pe)) * scale
        scores = jnp.where(visible[None, None], scores, -jnp.inf)
        out = jnp.einsum("bhij,bjhd->bihd", jax.nn.softmax(scores, -1), v)
        return out.reshape(b, s, n * dv) @ _f32(w_o)

    w_qb, w_kvb = p[f"{prefix}_attn_q_b_w"], p[f"{prefix}_attn_kv_b_w"]
    w_o = p[f"{prefix}_attn_o_w"]
    out = jnp.zeros(a.shape[:2] + (w_o.shape[1],), jnp.float32)
    for h0 in range(0, nh, HEAD_BLOCK):
        h1 = min(h0 + HEAD_BLOCK, nh)
        out = out + heads(
            c_q, c_kv, k_pe, w_qb[:, h0 * (dn + dr):h1 * (dn + dr)],
            w_kvb[h0:h1], w_o[h0 * dv:h1 * dv])
    return out


def gated_ffn(x, w_gate_up, w_down, block=FFN_BLOCK):
    """Wd(silu(Wg x) * Wu x) with [Wg | Wu] stored side by side, `block`
    columns of the width at a time."""
    f = w_down.shape[0]

    @jax.jit
    def part(x, w_gate, w_up, w_down):
        return (jax.nn.silu(x @ _f32(w_gate)) * (x @ _f32(w_up))) \
            @ _f32(w_down)

    out = jnp.zeros(x.shape[:-1] + (w_down.shape[1],), jnp.float32)
    for c0 in range(0, f, block):
        c1 = min(c0 + block, f)
        out = out + part(x, w_gate_up[:, c0:c1], w_gate_up[:, f + c0:f + c1],
                         w_down[c0:c1])
    return out


def route(p, prefix, x, cfg, follow=None, tie_eps=0.0):
    """(selected [.., k], weights [.., k], report). With `follow` (the
    ids the program selected for the same tokens) a token whose program
    choice differs from the reference's takes the program's ids where
    the difference is an ambiguous selection, decided by rounding, and
    is counted: (a) every group a program id lies in scores within
    2 `tie_eps` of the reference's last kept group (a group's score is
    a sum of two), and (b) with the program's groups kept (filled up to
    `topk_group` with the reference's best others) every program id has
    a biased score within `tie_eps` of the k-th best there. Where no
    group changed, (b) compares with the reference's own k-th; where one
    did, the k-th is another number, since the swapped group takes
    candidates with it. Any other difference is a mismatch and keeps
    the reference's ids."""
    k, groups, kept = cfg["top_k"], cfg["n_group"], cfg["topk_group"]
    sc = jax.nn.sigmoid(x @ _f32(p[f"{prefix}_router_w"]))
    c = sc + _f32(p[f"{prefix}_expert_bias"])
    per_group = c.shape[-1] // groups
    grouped = c.reshape(c.shape[:-1] + (groups, per_group))
    group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], -1)

    def limit(score):
        """Top-`kept` groups by `score`; the others' experts set to 0."""
        top, which = jax.lax.top_k(score, kept)
        keep = jnp.any(which[..., None] == jnp.arange(groups), -2)
        return top, jnp.where(keep[..., None], grouped, 0.0).reshape(c.shape)

    group_top, limited = limit(group_score)
    _top, sel = jax.lax.top_k(limited, k)
    report = {}
    if follow is not None:
        theirs = jnp.asarray(follow, jnp.int32)
        differs = jnp.any(jnp.sort(theirs, -1) != jnp.sort(sel, -1), -1)
        their_groups = theirs // per_group
        group_gap = group_top[..., -1] - jnp.min(
            jnp.take_along_axis(group_score, their_groups, -1), -1)
        in_theirs = jnp.any(their_groups[..., None] == jnp.arange(groups), -2)
        _t, explained = limit(group_score + jnp.where(in_theirs, 1e3, 0.0))
        gap = jax.lax.top_k(explained, k)[0][..., -1] - jnp.min(
            jnp.take_along_axis(explained, theirs, -1), -1)
        tie = differs & (gap <= tie_eps) & (group_gap <= 2 * tie_eps)
        sel = jnp.where(tie[..., None], theirs, sel)
        report = {
            "tokens": int(differs.size), "near_ties": int(jnp.sum(tie)),
            "mismatches": int(jnp.sum(differs & ~tie)),
            "groups_differ": int(jnp.sum(differs & (group_gap > 0))),
            "largest_gap": float(jnp.max(jnp.where(differs, gap, 0.0))),
            "largest_group_gap": float(jnp.max(
                jnp.where(differs, group_gap, 0.0))),
        }
    w = jnp.take_along_axis(sc, sel, -1)
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
            gu = x @ _f32(wgu_b[e])
            f = gu.shape[-1] // 2
            out = out + w_e * (
                (jax.nn.silu(gu[..., :f]) * gu[..., f:]) @ _f32(wd_b[e]))
        return out

    out = jnp.zeros_like(x)
    for e0 in range(0, wgu.shape[0], EXPERT_BLOCK):
        out = out + block(x, sel, w, wgu[e0:e0 + EXPERT_BLOCK],
                          wd[e0:e0 + EXPERT_BLOCK], offset + e0)
    return out


def expert_ffn(p, prefix, x, cfg, follow=None, tie_eps=0.0):
    sel, w, report = route(p, prefix, x, cfg, follow, tie_eps)
    out = routed_part(p, prefix, x, sel, w, cfg)
    if cfg["num_shared_experts"]:
        out = out + gated_ffn(x, p[f"{prefix}_shared_gate_up_w"],
                              p[f"{prefix}_shared_down_w"])
    return out, sel, report


def forward(params, ids, cfg, follow=None, tie_eps=0.0, latent_dtype=None):
    """ids [B, S] -> {"logits": next-token logits after the last position
    [B, V] float32, "selected": [expert layers][B, S, k], "routing": the
    `route` reports summed over the expert layers}. `params` maps the
    program's parameter names to arrays of any float dtype, on the host
    or the device. `latent_dtype` as `attention` takes it."""
    cfg = reference_config(cfg)
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.asarray(params["dots_embed"])[jnp.asarray(ids)])
        selected, routing, layer_no = [], {}, 0
        for i, (_attn, ffn_kind) in enumerate(cfg["layer_kinds"]):
            prefix = f"dots_l{i}"
            h = x + attention(
                params, prefix, rms_norm(x, params[f"{prefix}_n1"], eps), cfg,
                latent_dtype)
            m = rms_norm(h, params[f"{prefix}_n2"], eps)
            if ffn_kind == "dense":
                m = gated_ffn(m, params[f"{prefix}_mlp_gate_up_w"],
                              params[f"{prefix}_mlp_down_w"])
            else:
                theirs = None if follow is None else follow[layer_no]
                m, sel, report = expert_ffn(params, prefix, m, cfg, theirs,
                                            tie_eps)
                selected.append(np.asarray(sel))
                for key, value in report.items():
                    routing[key] = max(routing.get(key, 0.0), value) \
                        if key.startswith("largest") \
                        else routing.get(key, 0) + value
                layer_no += 1
            x = h + m
        last = rms_norm(x[:, -1, :], params["dots_norm_f"], eps)
        logits = last @ _f32(params["dots_head_w"])
    return {"logits": np.asarray(logits), "selected": selected,
            "routing": routing}
