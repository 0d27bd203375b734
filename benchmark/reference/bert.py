"""Plain reference of the BERT encoder with the masked-LM head, in test
mode, reading weights by the program's parameter names.

Follows google-research/bert `modeling.py` (post-LN encoder, tanh GELU,
learned positions, token types) with the departures the configuration
file lists under `assumed`: the MLM head is one vocabulary projection on
the gathered positions (no transform layer, not tied to the embedding),
there is no next-sentence head, and dropout sites scale by (1 - p) in
test mode (see `common.attention`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import attention, cross_entropy, f32, gelu_tanh, layer_norm


def param_names(layers):
    names = ["word_embedding", "pos_embedding", "type_embedding",
             "emb_ln_scale", "emb_ln_bias", "mlm_out_w", "mlm_out_b"]
    for i in range(layers):
        p = f"bert_l{i}"
        for part in ("attn_qkv", "attn_out", "ffn_in", "ffn_out"):
            names += [f"{p}_{part}_w", f"{p}_{part}_b"]
        for ln in ("ln1", "ln2"):
            names += [f"{p}_{ln}_scale", f"{p}_{ln}_bias"]
    return names


def encode(params, ids, types, layers, heads, hidden_keep, attn_keep):
    """[B, S] ids and token types -> [B, S, H]; no padding (every key is
    attended, as the cells' batches have it)."""
    p = {k: f32(v) for k, v in params.items()}
    s = ids.shape[1]
    x = p["word_embedding"][ids] + p["pos_embedding"][:s][None] \
        + p["type_embedding"][types]
    x = layer_norm(x, p["emb_ln_scale"], p["emb_ln_bias"]) * hidden_keep
    for i in range(layers):
        n = f"bert_l{i}"
        qkv = x @ p[f"{n}_attn_qkv_w"] + p[f"{n}_attn_qkv_b"]
        ctx = attention(qkv, heads, attn_keep, causal=False)
        attn = ctx @ p[f"{n}_attn_out_w"] + p[f"{n}_attn_out_b"]
        x = layer_norm(x + attn * hidden_keep,
                       p[f"{n}_ln1_scale"], p[f"{n}_ln1_bias"])
        ffn = gelu_tanh(x @ p[f"{n}_ffn_in_w"] + p[f"{n}_ffn_in_b"])
        ffn = ffn @ p[f"{n}_ffn_out_w"] + p[f"{n}_ffn_out_b"]
        x = layer_norm(x + ffn * hidden_keep,
                       p[f"{n}_ln2_scale"], p[f"{n}_ln2_bias"])
    return x


def mlm_logits(params, ids, types, mask_pos, *, layers, heads,
               hidden_dropout, attention_dropout):
    """Vocabulary logits [P, V] at `mask_pos`, which indexes the
    flattened [B*S] rows."""
    with jax.default_matmul_precision("highest"):
        seq = encode(params, jnp.asarray(ids), jnp.asarray(types), layers,
                     heads, 1.0 - hidden_dropout, 1.0 - attention_dropout)
        rows = seq.reshape(-1, seq.shape[-1])[jnp.asarray(mask_pos)]
        return rows @ f32(params["mlm_out_w"]) + f32(params["mlm_out_b"])


def mlm_loss(params, ids, types, mask_pos, labels, **kwargs):
    """(mean masked-LM loss, the logits it is taken from)."""
    logits = mlm_logits(params, ids, types, mask_pos, **kwargs)
    return cross_entropy(logits, jnp.asarray(labels)), logits
