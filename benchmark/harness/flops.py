"""Closed-form operation and byte counts, from shapes alone.

Matrix-multiply operations only (2 per multiply-add), as the model
utilization convention has it: forward + backward = 3 x forward, recomputed
operations not counted. Causal attention counts half the key positions.
"""

from __future__ import annotations


def encoder_train_flops_per_token(hidden, layers, seq, vocab, head_share):
    """BERT-style encoder + vocabulary head on `head_share` of the
    positions: per layer qkv 6h^2 + attention-out 2h^2 + feed-forward
    16h^2 (intermediate = 4h) + attention 4*s*h."""
    fwd = layers * (24 * hidden * hidden + 4 * seq * hidden)
    fwd += 2 * hidden * vocab * head_share
    return 3.0 * fwd


def decoder_train_flops_per_token(hidden, layers, seq, vocab):
    """GPT-style causal decoder with the vocabulary head on every
    position; a query sees seq/2 keys on average."""
    fwd = layers * (24 * hidden * hidden + 4 * (seq / 2) * hidden)
    fwd += 2 * hidden * vocab
    return 3.0 * fwd


def flash_tiled_step_cost(batch, heads, seq, head_dim, layers,
                          bytes_per_el=2):
    """Operations and HBM bytes of one training step's KV-tiled flash
    attention calls (per layer: forward, dkv, dq), causal.

    Each call is charged what its own algorithm needs from its inputs:
    forward QK^T + PV (4*S^2*D); dkv recomputes the scores, then dP, dV,
    dK (8*S^2*D); dq recomputes the scores, then dP, dQ (6*S^2*D); halved
    for the causal mask. Bytes: every operand read once and every result
    written once (q, k, v, o, do and the gradients at `bytes_per_el`, the
    row statistics in float32)."""
    s2d = float(seq) * seq * head_dim / 2.0
    bh = batch * heads
    flops = layers * bh * (4 + 8 + 6) * s2d
    tensor = bh * seq * head_dim * bytes_per_el
    rows = bh * seq * 4
    fwd = 4 * tensor + rows              # q k v -> o, lse
    dkv = 5 * tensor + 2 * rows + 2 * tensor   # q k v o do, lse delta -> dk dv
    dq = 5 * tensor + 2 * rows + tensor        # same operands -> dq
    return flops, float(layers * (fwd + dkv + dq))


def roofline_seconds(flops, bytes_moved, peak):
    """(least seconds the chip could take, which bound applies)."""
    t_compute = flops / peak["bf16_flops_per_s"]
    t_memory = bytes_moved / peak["hbm_bytes_per_s"]
    if t_compute >= t_memory:
        return t_compute, "compute"
    return t_memory, "memory"
