"""Closed-form parameters, operations and bytes of a Qwen3-Next decoder
(Gated DeltaNet and gated attention mixers, softmax-routed gated experts
with a gated shared expert), from the sizes the program publishes
(`serving.generate.model`, the decoder's `describe()`) and its routing
counters. Matrix-product operations count 2 per multiply-add; the gated
delta rule counts what its cheapest form needs a state element and
token: the decay's multiply, the correction's readout, the outer
product's and the output readout's multiply-adds, 7."""

from __future__ import annotations

LINEAR, FULL = "linear", "full"
STATE_OPS = 7.0


def _key_dim(m):
    return m["linear_num_key_heads"] * m["linear_key_head_dim"]


def _value_dim(m):
    return m["linear_num_value_heads"] * m["linear_value_head_dim"]


def _conv_dim(m):
    return 2 * _key_dim(m) + _value_dim(m)


def linear_matrix_params(m):
    """in_qkvz (q | k | v | z), in_ba (b | al) and out."""
    h, v = m["hidden_size"], _value_dim(m)
    return h * (_conv_dim(m) + v) + h * 2 * m["linear_num_value_heads"] \
        + v * h


def linear_params(m):
    """A Gated DeltaNet mixer whole: the three projections, the
    convolution (no bias), A_log and dt_bias, the output norm's gain."""
    return linear_matrix_params(m) \
        + _conv_dim(m) * m["linear_conv_kernel_dim"] \
        + 2 * m["linear_num_value_heads"] + m["linear_value_head_dim"]


def attention_matrix_params(m):
    """q with its gate (2 x heads x head_dim wide), k and v (KV heads x
    head_dim), o."""
    h = m["hidden_size"]
    wide, narrow = m["num_heads"] * m["head_dim"], \
        m["num_kv_heads"] * m["head_dim"]
    return h * 2 * wide + 2 * h * narrow + wide * h


def attention_params(m):
    """With the two QK-norm gains."""
    return attention_matrix_params(m) + 2 * m["head_dim"]


def expert_params(m):
    """ONE routed expert: gate, up, down."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def ffn_always(m):
    """What every token multiplies by in a layer's FFN: the router, the
    shared expert and its gate."""
    h = m["hidden_size"]
    return h * m["num_experts"] + 3 * h * m["shared_intermediate_size"] + h


def resident_params(m):
    """Everything this chip holds, norms included."""
    h = m["hidden_size"]
    total = 2 * m["vocab_size"] * h + h
    for kind, _ffn in m["layer_kinds"]:
        total += linear_params(m) if kind == LINEAR else attention_params(m)
        total += 2 * h + ffn_always(m) \
            + m["num_local_experts"] * expert_params(m)
    return total


def state_elements(m):
    """Elements of one sequence's delta-rule state in one linear layer."""
    return _value_dim(m) * m["linear_key_head_dim"]


def token_flops(m, keys, local_assignments, with_head):
    """Forward operations of one token that sees `keys` keys in the full
    layers and has `local_assignments` routed assignments a layer on
    this chip (a mean, from the counters)."""
    flops = 0.0
    for kind, _ffn in m["layer_kinds"]:
        if kind == LINEAR:
            flops += 2.0 * linear_matrix_params(m) \
                + STATE_OPS * state_elements(m) \
                + 2.0 * m["linear_conv_kernel_dim"] * _conv_dim(m)
        else:
            flops += 2.0 * attention_matrix_params(m) \
                + 4.0 * m["num_heads"] * m["head_dim"] * keys
        flops += 2.0 * (ffn_always(m) + local_assignments * expert_params(m))
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


def decode_gdn_need(m, batch):
    """(operations, bytes) ONE linear layer's state update NEEDS in one
    decode step of `batch` sequences: the float32 state read once and
    written once, the step's q, k, v, b and al rows read and o written
    in the activations' dtype."""
    act = m["bytes_per_param"]
    rows = _conv_dim(m) + 2 * m["linear_num_value_heads"] + _value_dim(m)
    return STATE_OPS * batch * state_elements(m), \
        float(batch * (2 * 4 * state_elements(m) + act * rows))
