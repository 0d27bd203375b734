"""Closed-form operations and bytes of an expert-layer decoder, from the
sizes the program publishes (`serving.generate.model`, the decoder's
`describe()`) and its routing counters. Matrix-product operations only,
2 per multiply-add."""

from __future__ import annotations


def expert_params(m):
    """Parameters of ONE routed (or shared) expert: gate, up, down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def attention_params(m):
    """q, the output gate and o (heads x head_dim wide), k and v (KV
    heads x head_dim wide)."""
    h = m["hidden_size"]
    wide = m["num_heads"] * m["head_dim"]
    narrow = m["num_kv_heads"] * m["head_dim"]
    return 3 * h * wide + 2 * h * narrow


def layer_params(m, ffn_kind):
    """Parameters of one layer that a token's forward pass multiplies by,
    the routed experts left out: (always, per routed assignment)."""
    if ffn_kind == "dense":
        return attention_params(m) + 3 * m["hidden_size"] \
            * m["intermediate_size"], 0
    always = attention_params(m) + m["hidden_size"] * m["num_experts"] \
        + m["num_shared_experts"] * expert_params(m)
    return always, expert_params(m)


def resident_params(m):
    """Everything this chip holds: the layers with their local experts,
    the embedding and the head over the vocabulary rows held here."""
    total = 2 * m["vocab_size"] * m["hidden_size"]
    for _attn, ffn in m["layer_kinds"]:
        always, per_expert = layer_params(m, ffn)
        total += always + m["num_local_experts"] * per_expert
    return total


def _keys_seen(m, attn_kind, keys):
    return min(keys, m["sliding_window"]) \
        if attn_kind == "sliding_attention" else keys


def token_flops(m, keys, local_assignments, with_head):
    """Forward operations of one token that sees `keys` keys (on a
    window layer at most the window) and has `local_assignments` routed
    assignments a layer on this chip (a mean, from the counters)."""
    per_key = 4.0 * m["num_heads"] * m["head_dim"]     # QK^T and PV
    flops = 0.0
    for attn, ffn in m["layer_kinds"]:
        always, per_assignment = layer_params(m, ffn)
        flops += 2.0 * (always + local_assignments * per_assignment)
        flops += per_key * _keys_seen(m, attn, keys)
    if with_head:
        flops += 2.0 * m["hidden_size"] * m["vocab_size"]
    return flops


def request_flops(m, context_len, new_tokens, local_assignments):
    """One request: the prompt's tokens (causal: token i sees i + 1 keys;
    the head on the last only), then `new_tokens - 1` decode steps (the
    first new token comes from the prefill's logits)."""
    flops = sum(token_flops(m, i + 1, local_assignments, False)
                for i in range(context_len))
    flops += 2.0 * m["hidden_size"] * m["vocab_size"]
    flops += sum(token_flops(m, context_len + t, local_assignments, True)
                 for t in range(1, new_tokens))
    return flops


def decode_expert_need(m, experts_hit, assignments):
    """(operations, bytes) a decode step's routed-expert products NEED
    in one expert layer: the rows' products, the weights of the experts
    actually hit read once, the rows read and written once."""
    b = m["bytes_per_param"]
    h, f = m["hidden_size"], m["moe_intermediate_size"]
    flops = 2.0 * assignments * expert_params(m)
    rows = assignments * (h + 2 * f + f + h) * b
    return flops, experts_hit * expert_params(m) * b + rows
