"""Closed-form parameters, operations and bytes of a Nemotron-H decoder
(Mamba-2, latent relu2 experts, position-free GQA), from the sizes the
program publishes (`serving.generate.model`, the decoder's `describe()`)
and its routing counters. Matrix-product operations count 2 per
multiply-add; the state-space recurrence counts what its cheapest form
needs: the update's outer product and multiply-add and the readout's
multiply-add, 5 per state element and token."""

from __future__ import annotations

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


def _d_inner(m):
    return m["mamba_num_heads"] * m["mamba_head_dim"]


def _conv_dim(m):
    return _d_inner(m) + 2 * m["n_groups"] * m["ssm_state_size"]


def mamba_matrix_params(m):
    """in_proj (z | xBC | dt) and out_proj."""
    d = _d_inner(m)
    return m["hidden_size"] * (d + _conv_dim(m) + m["mamba_num_heads"]) \
        + d * m["hidden_size"]


def mamba_params(m):
    """A Mamba-2 block whole: the two projections, the convolution with
    its bias, A_log, D and dt_bias, the gated norm's gain, the block's
    norm."""
    return mamba_matrix_params(m) \
        + _conv_dim(m) * (m["conv_kernel"] + 1) \
        + 3 * m["mamba_num_heads"] + _d_inner(m) + m["hidden_size"]


def attention_params(m):
    """q and o (heads x head_dim wide), k and v (KV heads x head_dim)."""
    h = m["hidden_size"]
    return 2 * h * m["num_heads"] * m["head_dim"] \
        + 2 * h * m["num_kv_heads"] * m["head_dim"]


def expert_params(m):
    """ONE routed expert: up and down, in the latent, no gate."""
    return 2 * m["moe_latent_size"] * m["moe_intermediate_size"]


def expert_block_always(m):
    """What every token multiplies by in an expert block: the router,
    the two latent projections, the shared expert at the hidden width."""
    h = m["hidden_size"]
    return h * m["num_experts"] + 2 * h * m["moe_latent_size"] \
        + 2 * h * m["shared_intermediate_size"]


def resident_params(m):
    """Everything this chip holds, norms and buffers included."""
    h = m["hidden_size"]
    total = 2 * m["vocab_size"] * h + h
    for kind in m["pattern"]:
        if kind == MAMBA:
            total += mamba_params(m)
        elif kind == ATTENTION:
            total += attention_params(m) + h
        else:
            total += expert_block_always(m) + m["num_experts"] + h \
                + m["num_local_experts"] * expert_params(m)
    return total


def state_elements(m):
    """Elements of one sequence's recurrent state in one Mamba block."""
    return _d_inner(m) * m["ssm_state_size"]


def token_flops(m, keys, local_assignments, with_head):
    """Forward operations of one token that sees `keys` keys in the
    attention blocks and has `local_assignments` routed assignments an
    expert block on this chip (a mean, from the counters)."""
    flops = 0.0
    for kind in m["pattern"]:
        if kind == MAMBA:
            flops += 2.0 * mamba_matrix_params(m) + 5.0 * state_elements(m) \
                + 2.0 * m["conv_kernel"] * _conv_dim(m)
        elif kind == ATTENTION:
            flops += 2.0 * attention_params(m) \
                + 4.0 * m["num_heads"] * m["head_dim"] * keys
        else:
            flops += 2.0 * (expert_block_always(m)
                            + local_assignments * expert_params(m))
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


def decode_ssm_need(m, batch):
    """(operations, bytes) ONE Mamba block's state update NEEDS in one
    decode step of `batch` sequences: the float32 state read once and
    written once, the step's x, B, C and dt rows read and y written in
    the activations' dtype."""
    act = m["bytes_per_param"]
    rows = _conv_dim(m) + m["mamba_num_heads"] + _d_inner(m)
    return 5.0 * batch * state_elements(m), \
        float(batch * (2 * 4 * state_elements(m) + act * rows))


def decode_expert_need(m, experts_hit, assignments):
    """(operations, bytes) a decode step's routed-expert products NEED in
    one expert block: the rows' products, the weights of the experts
    actually hit read once, the rows read and written once (latent in,
    the intermediate out and in, latent out)."""
    b = m["bytes_per_param"]
    lat, f = m["moe_latent_size"], m["moe_intermediate_size"]
    flops = 2.0 * assignments * expert_params(m)
    rows = assignments * (lat + f + f + lat) * b
    return flops, experts_hit * expert_params(m) * b + rows
