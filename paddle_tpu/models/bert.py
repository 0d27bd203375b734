"""BERT — transformer encoder flagship (BERT-base benchmark in BASELINE.md).

Built entirely on the public layers API; equivalent in coverage to the
reference's ERNIE/BERT workloads (its fused ops multihead_matmul
operators/fused/multihead_matmul_op.cu and bert_encoder_functor.cu exist
only because CUDA needed hand fusion — on TPU, XLA fuses the unfused graph,
so the model is written in plain ops).

Tensor-parallel ready: every projection weight has a deterministic name, and
`bert_tp_shardings` returns Megatron-style GSPMD annotations over the "mp"
mesh axis (column-parallel QKV / FFN-in, row-parallel attn-out / FFN-out),
consumed by the executor's gspmd mode (parallel/spmd.py:wrap_gspmd).
"""

from __future__ import annotations

import math

from .. import layers
from ..framework.program import name_scope
from ..param_attr import ParamAttr


class BertConfig:
    def __init__(
        self,
        vocab_size=30522,
        hidden_size=768,
        num_layers=12,
        num_heads=12,
        intermediate_size=3072,
        max_position=512,
        type_vocab_size=2,
        hidden_dropout=0.1,
        attention_dropout=0.1,
        initializer_range=0.02,
        use_fused_attention=True,
        use_fused_residual=True,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size
        self.max_position = max_position
        self.type_vocab_size = type_vocab_size
        self.hidden_dropout = hidden_dropout
        self.attention_dropout = attention_dropout
        self.initializer_range = initializer_range
        # one fused attention op (Pallas flash kernel on TPU) vs composed
        # matmul/softmax/dropout ops. The composed path is what TP/gspmd
        # sharding tests exercise; the fused op itself degrades to the same
        # math when the kernel cannot run (see ops/fused.py).
        self.use_fused_attention = use_fused_attention
        # one fused op for the residual tail LN(x + dropout(y)) — the
        # Pallas kernel in kernels/fused_residual.py; the composed path
        # stays for gspmd sharding propagation tests
        self.use_fused_residual = use_fused_residual

    @classmethod
    def base(cls):
        return cls()

    @classmethod
    def tiny(cls):
        """For tests / dry runs: 2 layers, 128 hidden."""
        return cls(
            vocab_size=1024, hidden_size=128, num_layers=2, num_heads=4,
            intermediate_size=512, max_position=128,
        )


def _init(cfg):
    from ..initializer import Normal

    return Normal(0.0, cfg.initializer_range)


def _dense(x, size, name, cfg, act=None):
    return layers.fc(
        x,
        size=size,
        num_flatten_dims=2,
        act=act,
        param_attr=ParamAttr(name=f"{name}_w", initializer=_init(cfg)),
        bias_attr=ParamAttr(name=f"{name}_b"),
    )


def _attention(x, attn_bias, cfg, prefix, is_test):
    h = cfg.hidden_size
    with name_scope("proj"):
        # [B,S,3H] one fused matmul
        qkv = _dense(x, 3 * h, f"{prefix}_qkv", cfg)
    with name_scope("core"):
        ctxv = _attention_core(qkv, attn_bias, cfg, is_test)
    with name_scope("proj"):
        return _dense(ctxv, h, f"{prefix}_out", cfg)


def _attention_core(qkv, attn_bias, cfg, is_test):
    b, s, _ = qkv.shape
    h = cfg.hidden_size
    nh, dh = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    if cfg.use_fused_attention:
        # one op straight off the qkv matmul: the Pallas flash kernel
        # indexes the packed [B,S,3H] projection in place (no head-split
        # transposes, no [B,nh,S,S] probs in HBM); attn_bias is the [B,S]
        # key mask (0 keep / -1e4 pad)
        return layers.fused_qkv_attention(
            qkv, nh, key_bias=attn_bias,
            scale=1.0 / math.sqrt(dh),
            dropout_prob=cfg.attention_dropout, is_test=is_test,
        )

    # dense path: slice along the feature dim + per-tensor [B,nh,S,dh]
    # transposes (XLA folds the slices into the producing matmul and fuses
    # the transposes with their consuming dots)
    def head(t):
        return layers.transpose(layers.reshape(t, [b, s, nh, dh]), [0, 2, 1, 3])

    q = head(layers.slice(qkv, [2], [0], [h]))
    k = head(layers.slice(qkv, [2], [h], [2 * h]))
    v = head(layers.slice(qkv, [2], [2 * h], [3 * h]))
    bias4 = None
    if attn_bias is not None:
        bias4 = layers.reshape(attn_bias, [b, 1, 1, s])
    scores = layers.matmul(q, k, transpose_y=True, alpha=1.0 / math.sqrt(dh))
    if bias4 is not None:
        scores = scores + bias4  # [B,1,1,S] additive mask broadcast
    probs = layers.softmax(scores, axis=-1)
    probs = layers.dropout(
        probs, dropout_prob=cfg.attention_dropout, is_test=is_test
    )
    ctxv = layers.matmul(probs, v)  # [B,nh,S,dh]
    ctxv = layers.transpose(ctxv, [0, 2, 1, 3])
    return layers.reshape(ctxv, [b, s, h])


def _residual_ln(x, branch, cfg, ln_name, is_test):
    """LN(x + dropout(branch)): one fused op (Pallas residual-tail kernel)
    or the composed dropout/add/layer_norm ops — same math, same param
    names either way."""
    if cfg.use_fused_residual:
        return layers.fused_dropout_add_ln(
            x, branch, cfg.hidden_dropout, is_test=is_test,
            param_attr=ParamAttr(name=f"{ln_name}_scale"),
            bias_attr=ParamAttr(name=f"{ln_name}_bias"),
        )
    branch = layers.dropout(branch, cfg.hidden_dropout, is_test=is_test)
    return layers.layer_norm(
        x + branch,
        begin_norm_axis=2,
        param_attr=ParamAttr(name=f"{ln_name}_scale"),
        bias_attr=ParamAttr(name=f"{ln_name}_bias"),
    )


def _encoder_layer(x, attn_bias, cfg, prefix, is_test):
    with name_scope("attn"):
        attn = _attention(x, attn_bias, cfg, f"{prefix}_attn", is_test)
        x = _residual_ln(x, attn, cfg, f"{prefix}_ln1", is_test)
    # tanh-approximate GELU (the original BERT implementation's formula).
    # On TPU the exact erf lowers to a long VPU polynomial — profiled at
    # ~0.77 ms/layer fwd on [32,512,3072] (BASELINE.md round 4); tanh is
    # the canonical-and-cheaper form.
    with name_scope("mlp"):
        ffn = _dense(x, cfg.intermediate_size, f"{prefix}_ffn_in", cfg)
        ffn = layers.gelu(ffn, approximate=True)
        ffn = _dense(ffn, cfg.hidden_size, f"{prefix}_ffn_out", cfg)
        return _residual_ln(x, ffn, cfg, f"{prefix}_ln2", is_test)


def _attn_bias(input_mask):
    """[B,S] float mask -> additive key-side attention bias [B,S]
    (0 keep, -1e4 mask; bf16-safe). Kept 2-D: the fused attention op takes
    the key bias directly, the dense path reshapes to [B,1,1,S]."""
    return layers.scale(input_mask, scale=1e4, bias=-1e4)


def bert_encoder_layers(x, input_mask, cfg, start=0, end=None, is_test=False,
                        checkpoints=None):
    """Run encoder layers [start, end) over [B,S,H] input — the unit of
    pipeline-stage splitting (device_guard slices the layer stack).
    `checkpoints`: optional list collecting per-layer outputs for
    RecomputeOptimizer segment boundaries."""
    with name_scope("attn"):
        attn_bias = _attn_bias(input_mask)
    end = cfg.num_layers if end is None else end
    for i in range(start, end):
        x = _encoder_layer(x, attn_bias, cfg, f"bert_l{i}", is_test)
        if checkpoints is not None:
            checkpoints.append(x)
    return x


def bert_encoder(input_ids, token_type_ids, input_mask, cfg, is_test=False,
                 num_layers=None, checkpoints=None):
    """input_ids/token_type_ids: [B,S] int64; input_mask: [B,S] float32.
    Returns sequence output [B,S,H]. num_layers limits the stack (pipeline
    stage 0 = embeddings + first half; see bert_encoder_layers)."""
    b, s = input_ids.shape
    with name_scope("embed"):
        word_emb = layers.embedding(
            input_ids,
            size=[cfg.vocab_size, cfg.hidden_size],
            param_attr=ParamAttr(name="word_embedding",
                                 initializer=_init(cfg)),
        )
        pos_ids = layers.reshape(
            layers.range(0, s, 1, "int64"), [1, s]
        )
        pos_emb = layers.embedding(
            pos_ids,
            size=[cfg.max_position, cfg.hidden_size],
            param_attr=ParamAttr(name="pos_embedding", initializer=_init(cfg)),
        )
        type_emb = layers.embedding(
            token_type_ids,
            size=[cfg.type_vocab_size, cfg.hidden_size],
            param_attr=ParamAttr(name="type_embedding",
                                 initializer=_init(cfg)),
        )
        emb = word_emb + pos_emb + type_emb
        emb = layers.layer_norm(
            emb,
            begin_norm_axis=2,
            param_attr=ParamAttr(name="emb_ln_scale"),
            bias_attr=ParamAttr(name="emb_ln_bias"),
        )
        emb = layers.dropout(emb, cfg.hidden_dropout, is_test=is_test)
    n = cfg.num_layers if num_layers is None else num_layers
    return bert_encoder_layers(
        emb, input_mask, cfg, 0, n, is_test, checkpoints=checkpoints
    )


def bert_mlm_head(seq, mlm_labels, cfg):
    """Masked-LM loss head over [B,S,H] sequence output; mlm_labels [B,S]
    int64 with ignore_index -100 on unmasked positions."""
    with name_scope("head"):
        b, s, h = seq.shape
        seq2 = layers.reshape(seq, [b * s, h])
        logits = layers.fc(
            seq2,
            size=cfg.vocab_size,
            param_attr=ParamAttr(name="mlm_out_w", initializer=_init(cfg)),
            bias_attr=ParamAttr(name="mlm_out_b"),
        )
        labels = layers.reshape(mlm_labels, [b * s, 1])
        loss = layers.softmax_with_cross_entropy(logits, labels,
                                                 ignore_index=-100)
        # average over the *masked* positions only: ignored positions
        # contribute zero loss, so a plain mean would scale loss/grads by
        # the masking ratio. [1]-shaped constant broadcasts, so the head
        # stays batch-size agnostic (pipeline microbatching shrinks the
        # runtime batch)
        ignore = layers.fill_constant([1], "int64", -100)
        valid = layers.cast(layers.not_equal(labels, ignore), "float32")
        denom = layers.elementwise_max(
            layers.reduce_sum(valid), layers.fill_constant([1], "float32", 1.0)
        )
        return layers.elementwise_div(layers.reduce_sum(loss), denom)


def bert_mlm_head_gather(seq, mask_pos, mask_labels, cfg):
    """MLM head over the MASKED positions only (the reference's BERT
    pretraining gathers mask_pos before the vocab projection — the
    standard formulation; computing [B*S, V] logits wastes ~85% of the
    head FLOPs). mask_pos: [P] int32 indices into the flattened [B*S]
    sequence (padded entries point at any row with label -100);
    mask_labels: [P] vocab ids with -100 padding."""
    with name_scope("head"):
        b, s, h = seq.shape
        seq2 = layers.reshape(seq, [b * s, h])
        picked = layers.gather(seq2, mask_pos)  # [P, h]
        logits = layers.fc(
            picked,
            size=cfg.vocab_size,
            param_attr=ParamAttr(name="mlm_out_w", initializer=_init(cfg)),
            bias_attr=ParamAttr(name="mlm_out_b"),
        )
        labels = layers.reshape(mask_labels, [-1, 1])
        loss = layers.softmax_with_cross_entropy(logits, labels,
                                                 ignore_index=-100)
        ignore = layers.fill_constant([1], "int64", -100)
        valid = layers.cast(layers.not_equal(labels, ignore), "float32")
        denom = layers.elementwise_max(
            layers.reduce_sum(valid), layers.fill_constant([1], "float32", 1.0)
        )
        return layers.elementwise_div(layers.reduce_sum(loss), denom)


def bert_pretrain(input_ids, token_type_ids, input_mask, mlm_labels, cfg,
                  is_test=False, checkpoints=None, mask_pos=None):
    """End-to-end MLM pretraining loss (encoder + head). With mask_pos
    [P], mlm_labels is the gathered [P] label vector and the vocab
    projection runs only on masked rows (reference mask_pos contract)."""
    seq = bert_encoder(
        input_ids, token_type_ids, input_mask, cfg, is_test,
        checkpoints=checkpoints,
    )
    if mask_pos is not None:
        return bert_mlm_head_gather(seq, mask_pos, mlm_labels, cfg)
    return bert_mlm_head(seq, mlm_labels, cfg)


def bert_tp_shardings(cfg, axis="mp"):
    """Megatron-style tensor-parallel GSPMD annotations for every encoder
    layer: QKV & FFN-in column-parallel (shard output features), attn-out &
    FFN-out row-parallel (shard input features); XLA propagation inserts the
    reduce where row-parallel outputs merge. Vocab-sharded embedding/MLM head
    included (vocab dim over `axis`)."""
    sh = {
        "word_embedding": (axis, None),
        "mlm_out_w": (None, axis),
    }
    for i in range(cfg.num_layers):
        p = f"bert_l{i}"
        sh[f"{p}_attn_qkv_w"] = (None, axis)
        sh[f"{p}_attn_qkv_b"] = (axis,)
        sh[f"{p}_attn_out_w"] = (axis, None)
        sh[f"{p}_ffn_in_w"] = (None, axis)
        sh[f"{p}_ffn_in_b"] = (axis,)
        sh[f"{p}_ffn_out_w"] = (axis, None)
    return sh
