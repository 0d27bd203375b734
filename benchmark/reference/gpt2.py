"""Plain reference of the GPT-2 decoder in test mode, reading weights by
the program's parameter names.

Follows openai/gpt-2 `model.py` (pre-LN blocks, tanh GELU, learned
positions, final LN) with the departures the configuration file lists
under `assumed`: the output head `lm_head_w` is a matrix of its own (the
published model ties it to `wte`), and dropout sites scale by (1 - p) in
test mode (see `common.attention`). No cache: every call is a full
forward pass over the whole prefix.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import attention, cross_entropy, f32, gelu_tanh, layer_norm


def param_names(layers):
    names = ["wte", "wpe", "gpt_lnf_scale", "gpt_lnf_bias", "lm_head_w"]
    for i in range(layers):
        p = f"gpt_l{i}"
        for part in ("attn_qkv", "attn_out", "mlp_in", "mlp_out"):
            names += [f"{p}_{part}_w", f"{p}_{part}_b"]
        for ln in ("ln1", "ln2"):
            names += [f"{p}_{ln}_scale", f"{p}_{ln}_bias"]
    return names


def hidden_states(params, ids, layers, heads, hidden_keep, attn_keep):
    """[B, S] ids -> final hidden states [B, S, H]."""
    p = {k: f32(v) for k, v in params.items()}
    s = ids.shape[1]
    x = (p["wte"][ids] + p["wpe"][:s][None]) * hidden_keep
    for i in range(layers):
        n = f"gpt_l{i}"
        a = layer_norm(x, p[f"{n}_ln1_scale"], p[f"{n}_ln1_bias"])
        qkv = a @ p[f"{n}_attn_qkv_w"] + p[f"{n}_attn_qkv_b"]
        ctx = attention(qkv, heads, attn_keep, causal=True)
        attn = ctx @ p[f"{n}_attn_out_w"] + p[f"{n}_attn_out_b"]
        x = x + attn * hidden_keep
        m = layer_norm(x, p[f"{n}_ln2_scale"], p[f"{n}_ln2_bias"])
        m = gelu_tanh(m @ p[f"{n}_mlp_in_w"] + p[f"{n}_mlp_in_b"])
        m = m @ p[f"{n}_mlp_out_w"] + p[f"{n}_mlp_out_b"]
        x = x + m * hidden_keep
    return layer_norm(x, p["gpt_lnf_scale"], p["gpt_lnf_bias"])


def last_logits(params, ids, *, layers, heads, hidden_dropout,
                attention_dropout):
    """Next-token logits [B, V] after the last position of `ids`."""
    with jax.default_matmul_precision("highest"):
        h = hidden_states(params, jnp.asarray(ids), layers, heads,
                          1.0 - hidden_dropout, 1.0 - attention_dropout)
        return h[:, -1, :] @ f32(params["lm_head_w"])


def lm_loss(params, ids, *, layers, heads, hidden_dropout,
            attention_dropout, chunk=1024):
    """Mean next-token loss over every position but the last, one
    sequence at a time and the vocabulary head in chunks of positions
    (the [S, V] logits of a long sequence are large)."""
    ids = jnp.asarray(ids)
    head = f32(params["lm_head_w"])
    total, count = 0.0, 0
    with jax.default_matmul_precision("highest"):
        for row in range(ids.shape[0]):
            h = hidden_states(params, ids[row:row + 1], layers, heads,
                              1.0 - hidden_dropout,
                              1.0 - attention_dropout)[0]
            n = ids.shape[1] - 1
            for lo in range(0, n, chunk):
                hi = min(n, lo + chunk)
                loss = cross_entropy(h[lo:hi] @ head, ids[row, lo + 1:hi + 1])
                total = total + loss * (hi - lo)
                count += hi - lo
    return total / count
