"""Closed-form parameters, operations and bytes of a MiniCPM-SALA decoder
(Lightning linear-attention and block-sparse attention mixers, a dense
SwiGLU FFN in every layer), from the sizes the program publishes
(`serving.generate.model`, the decoder's `describe()`). Matrix-product
operations count 2 per multiply-add; the Lightning recurrence counts 5
operations a state element and token, as the state-space recurrence is
counted (benchmark/harness/nemotron_h_cost.py): the decay's multiply, the
outer product's multiply-add and the readout's multiply-add."""

from __future__ import annotations

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
STATE_OPS = 5.0


def _width(m):
    return m["lightning_heads"] * m["lightning_head_dim"]


def lightning_matrix_params(m):
    """q, k, v, the gate (hidden -> H d each) and o (H d -> hidden)."""
    return 5 * m["hidden_size"] * _width(m)


def sparse_matrix_params(m):
    """q, the gate and o (hidden x heads x head_dim each), k and v
    (hidden x KV heads x head_dim each)."""
    h = m["hidden_size"]
    return 3 * h * m["num_heads"] * m["head_dim"] \
        + 2 * h * m["num_kv_heads"] * m["head_dim"]


def ffn_params(m):
    return 3 * m["hidden_size"] * m["intermediate_size"]


def layer_params(m, kind):
    """A layer whole: its mixer with the mixer's gains (QK-norm, and a
    Lightning layer's output norm), the FFN and the two norms."""
    if kind == LIGHTNING:
        mixer = lightning_matrix_params(m) + 2 * m["lightning_head_dim"] \
            + _width(m)
    else:
        mixer = sparse_matrix_params(m) + 2 * m["head_dim"]
    return mixer + ffn_params(m) + 2 * m["hidden_size"]


def resident_params(m):
    """Everything this chip holds: the layers, the embedding, the final
    norm and the untied head."""
    h = m["hidden_size"]
    return sum(layer_params(m, kind) for kind in m["layer_kinds"]) \
        + 2 * m["vocab_size"] * h + h


def state_elements(m):
    """Elements of one sequence's Lightning state in one layer."""
    return _width(m) * m["lightning_head_dim"]


def keys_read(m, keys, sparse):
    """Keys a sparse layer's query attends to when `keys` are visible:
    all of them where attention is dense, else at most the selected
    blocks' (`topk` x `block_size`)."""
    return min(keys, m["topk"] * m["block_size"]) if sparse else keys


def token_flops(m, keys, sparse, with_head):
    """Forward operations of one token that sees `keys` keys (itself
    included) in the sparse layers, where attention is `sparse` or
    dense."""
    flops = 0.0
    for kind in m["layer_kinds"]:
        if kind == LIGHTNING:
            flops += 2.0 * lightning_matrix_params(m) \
                + STATE_OPS * state_elements(m)
        else:
            flops += 2.0 * sparse_matrix_params(m) + 4.0 * m["num_heads"] \
                * m["head_dim"] * keys_read(m, keys, sparse)
        flops += 2.0 * ffn_params(m)
    if with_head:
        flops += 2.0 * m["hidden_size"] * m["vocab_size"]
    return flops


def request_flops(m, context_len, new_tokens):
    """One request: the prompt's tokens (causal: token i sees i + 1
    keys, sparse where the prompt is longer than `dense_len`; the head
    on the last only), then `new_tokens - 1` decode steps (the first new
    token comes from the prefill's logits), each sparse once its keys
    pass `dense_len`."""
    dense_len = m["dense_len"]
    flops = sum(token_flops(m, i + 1, context_len > dense_len, False)
                for i in range(context_len))
    flops += 2.0 * m["hidden_size"] * m["vocab_size"]
    flops += sum(token_flops(m, context_len + t + 1,
                             context_len + t + 1 > dense_len, True)
                 for t in range(1, new_tokens))
    return flops


def decode_lightning_need(m, batch):
    """(operations, bytes) ONE Lightning layer's state update NEEDS in
    one decode step of `batch` sequences: the float32 state read once
    and written once, the step's q, k and v rows read and o written in
    the activations' dtype. Memory-bound."""
    act = m["bytes_per_param"]
    return STATE_OPS * batch * state_elements(m), \
        float(batch * (2 * 4 * state_elements(m) + act * 4 * _width(m)))
