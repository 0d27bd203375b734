"""Closed-form parameters, operations and bytes of a latent-attention
decoder (dots_vlm: multi-head latent attention, dense SwiGLU layers then
group-limited routed experts with a shared expert), from the sizes the
program publishes (`serving.generate.model`, the decoder's `describe()`)
and its counters. Matrix-product operations only, 2 per multiply-add.
The routed experts are gated experts at the hidden width, so
`moe_cost.expert_params` / `decode_expert_need` are this family's form
too and are not repeated here."""

from __future__ import annotations

from .moe_cost import expert_params


def _heads(m):
    return (m["num_heads"], m["qk_nope_head_dim"], m["qk_rope_head_dim"],
            m["v_head_dim"])


def attention_params(m):
    """q_a, q_b, kv_a, the up-projection kv_b, o."""
    h, rq, r = m["hidden_size"], m["q_lora_rank"], m["kv_lora_rank"]
    nh, dn, dr, dv = _heads(m)
    return h * rq + rq * nh * (dn + dr) + h * (r + dr) \
        + nh * r * (dn + dv) + nh * dv * h


def ffn_params(m, ffn_kind):
    """(what every token multiplies by, per routed assignment)."""
    h = m["hidden_size"]
    if ffn_kind == "dense":
        return 3 * h * m["intermediate_size"], 0
    return h * m["num_experts"] + m["num_shared_experts"] * expert_params(m), \
        expert_params(m)


def resident_params(m):
    """Everything this chip holds, norms and buffers included."""
    h = m["hidden_size"]
    total = 2 * m["vocab_size"] * h + h
    for _attn, ffn in m["layer_kinds"]:
        always, per_expert = ffn_params(m, ffn)
        total += attention_params(m) + always \
            + m["num_local_experts"] * per_expert \
            + 2 * h + m["q_lora_rank"] + m["kv_lora_rank"]
        if ffn != "dense":
            total += m["num_experts"]          # the bias buffer
    return total


def attention_flops(m, keys, absorbed):
    """One token's attention block over `keys` visible keys. Expanded
    (prefill): every matrix, then per key and head a 192-wide score and
    a 128-wide value product. Absorbed (decode): the up-projection is
    two per-head products on the query and the output instead, and per
    key and head the score runs over the cache row's r + dr lanes and
    the value product over its r."""
    r = m["kv_lora_rank"]
    nh, dn, dr, dv = _heads(m)
    if not absorbed:
        return 2.0 * attention_params(m) + 2.0 * nh * (dn + dr + dv) * keys
    matrices = attention_params(m) - nh * r * (dn + dv)
    absorb = nh * dn * r + nh * r * dv
    return 2.0 * (matrices + absorb) + 2.0 * nh * ((r + dr) + r) * keys


def token_flops(m, keys, local_assignments, absorbed, with_head):
    """Forward operations of one token that sees `keys` keys and has
    `local_assignments` routed assignments an expert layer on this chip
    (a mean, from the counters)."""
    flops = 0.0
    for _attn, ffn in m["layer_kinds"]:
        always, per_assignment = ffn_params(m, ffn)
        flops += attention_flops(m, keys, absorbed) \
            + 2.0 * (always + local_assignments * per_assignment)
    if with_head:
        flops += 2.0 * m["hidden_size"] * m["vocab_size"]
    return flops


def request_flops(m, context_len, new_tokens, local_assignments):
    """One request: the prompt's tokens in the expanded form (causal:
    token i sees i + 1 keys; the head on the last only), then
    `new_tokens - 1` decode steps in the absorbed form (the first new
    token comes from the prefill's logits)."""
    flops = sum(token_flops(m, i + 1, local_assignments, False, False)
                for i in range(context_len))
    flops += 2.0 * m["hidden_size"] * m["vocab_size"]
    flops += sum(token_flops(m, context_len + t, local_assignments, True,
                             True)
                 for t in range(1, new_tokens))
    return flops


def decode_attention_need(m, cache_bytes):
    """(operations, bytes) the absorbed attention cores of one decode
    step NEED, given the bytes of latent cache rows its queries may see
    (all layers and sequences, each once: the program's
    `kv_cache.decode_bytes_needed` a step): every such row is scored by
    all heads over its r + dr lanes and summed over its r."""
    r, dr = m["kv_lora_rank"], m["qk_rope_head_dim"]
    rows = cache_bytes / ((r + dr) * m["bytes_per_param"])
    return 2.0 * rows * m["num_heads"] * ((r + dr) + r), float(cache_bytes)
