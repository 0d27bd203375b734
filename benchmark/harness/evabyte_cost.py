"""Closed-form parameters, operations and bytes of an EvaByte decoder (EVA
attention: exact inside an aligned window, one softmax-pooled summary per
chunk of the windows before it; a dense SwiGLU FFN in every layer), from
the sizes the program publishes (`serving.generate.model`, the decoder's
`describe()`). Matrix-product operations count 2 per multiply-add, as do
the pooling's: a summary of a chunk of C rows takes C multiply-adds a
lane for its logits and C for its weighted sum, for the keys and again
for the values."""

from __future__ import annotations


def _width(m):
    return m["num_heads"] * m["head_dim"]


def matrix_params(m):
    """q, k, v, o (hidden x heads x head_dim each) and the FFN's gate,
    up and down."""
    h = m["hidden_size"]
    return 4 * h * _width(m) + 3 * h * m["intermediate_size"]


def layer_params(m):
    """A layer whole: its products, the two norms and the pooling
    vectors mu and phi."""
    return matrix_params(m) + 2 * m["hidden_size"] + 2 * _width(m)


def resident_params(m):
    """Everything this chip holds: the layers, the embedding, the final
    norm and the untied head."""
    h = m["hidden_size"]
    return m["num_layers"] * layer_params(m) + 2 * m["vocab_size"] * h + h


def keys_seen(m, pos):
    """(the exact keys, the summaries) a query at `pos` scores: its
    aligned window's up to itself, the chunks of the windows before."""
    w, c = m["window_size"], m["chunk_size"]
    return pos % w + 1, (pos // w) * (w // c)


def token_flops(m, pos, with_head):
    """Forward operations of the token at `pos`: every product, the
    attention over the keys it sees, the pooling of the chunk it closes."""
    width = _width(m)
    exact, summaries = keys_seen(m, pos)
    per_layer = 2.0 * matrix_params(m) + 4.0 * width * (exact + summaries)
    if pos % m["chunk_size"] == m["chunk_size"] - 1:
        per_layer += 8.0 * m["chunk_size"] * width
    flops = m["num_layers"] * per_layer
    if with_head:
        flops += 2.0 * m["hidden_size"] * m["vocab_size"]
    return flops


def request_flops(m, context_len, new_tokens):
    """One request: the prompt's bytes (the head on the last only), then
    `new_tokens - 1` decode steps (the first new byte comes from the
    prefill's logits) at positions context_len .. context_len +
    new_tokens - 2."""
    flops = sum(token_flops(m, p, False) for p in range(context_len))
    flops += 2.0 * m["hidden_size"] * m["vocab_size"]
    flops += sum(token_flops(m, context_len + t, True)
                 for t in range(new_tokens - 1))
    return flops


def decode_eva_need(m, batch, pos):
    """(operations, bytes) ONE layer's EVA attention NEEDS in the decode
    step at `pos` for `batch` sequences: the live ring rows and the
    visible summary rows of K and V read once, the step's q rows read and
    o rows written, in the activations' dtype. Memory-bound."""
    act, width = m["bytes_per_param"], _width(m)
    rows = sum(keys_seen(m, pos))
    return 4.0 * batch * width * rows, \
        float(batch * act * width * (2 * rows + 2))


def decode_loop_eva_need(m, batch, context_len, new_tokens):
    """`decode_eva_need` averaged over a batch's decode steps."""
    steps = [decode_eva_need(m, batch, context_len + t)
             for t in range(new_tokens - 1)]
    return tuple(sum(x) / len(steps) for x in zip(*steps))
