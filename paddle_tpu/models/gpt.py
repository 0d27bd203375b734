"""GPT — decoder-only causal LM (the reference era's ERNIE-GEN/GPT-2
workloads; BASELINE.md lists ERNIE dygraph pretrain as the stretch
target). Pre-LN transformer decoder built on the public layers API; the
causal mask runs INSIDE the packed-QKV Pallas flash kernel (causal=True),
so no [B,nh,S,S] mask or probability tensor ever reaches HBM.

Tensor-parallel ready like models/bert.py: deterministic parameter names +
`gpt_tp_shardings` Megatron annotations over the "mp" axis.
"""

from __future__ import annotations

import math

from .. import layers
from ..framework.program import name_scope
from ..param_attr import ParamAttr


class GPTConfig:
    def __init__(
        self,
        vocab_size=50257,
        hidden_size=768,
        num_layers=12,
        num_heads=12,
        intermediate_size=3072,
        max_position=1024,
        hidden_dropout=0.1,
        attention_dropout=0.1,
        initializer_range=0.02,
        use_fused_attention=True,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.intermediate_size = intermediate_size
        self.max_position = max_position
        self.hidden_dropout = hidden_dropout
        self.attention_dropout = attention_dropout
        self.initializer_range = initializer_range
        self.use_fused_attention = use_fused_attention

    @classmethod
    def small(cls):
        return cls()

    @classmethod
    def tiny(cls):
        return cls(
            vocab_size=512, hidden_size=128, num_layers=2, num_heads=4,
            intermediate_size=256, max_position=128,
        )


def _init(cfg):
    from ..initializer import Normal

    return Normal(0.0, cfg.initializer_range)


def _dense(x, size, name, cfg, act=None):
    return layers.fc(
        x, size=size, num_flatten_dims=2, act=act,
        param_attr=ParamAttr(name=f"{name}_w", initializer=_init(cfg)),
        bias_attr=ParamAttr(name=f"{name}_b"),
    )


def _ln(x, name):
    return layers.layer_norm(
        x, begin_norm_axis=2,
        param_attr=ParamAttr(name=f"{name}_scale"),
        bias_attr=ParamAttr(name=f"{name}_bias"),
    )


def _decoder_layer(x, cfg, prefix, is_test):
    h = cfg.hidden_size
    # pre-LN attention block
    with name_scope("attn"):
        a = _ln(x, f"{prefix}_ln1")
        with name_scope("proj"):
            qkv = _dense(a, 3 * h, f"{prefix}_attn_qkv", cfg)
        with name_scope("core"):
            ctxv = _attention(qkv, cfg, is_test)
        with name_scope("proj"):
            attn = _dense(ctxv, h, f"{prefix}_attn_out", cfg)
        x = x + layers.dropout(attn, cfg.hidden_dropout, is_test=is_test)
    # pre-LN MLP block
    with name_scope("mlp"):
        m = _ln(x, f"{prefix}_ln2")
        # tanh-approximate GELU — GPT-2's canonical formula, and ~2x
        # cheaper than exact erf on the TPU VPU (see models/bert.py)
        m = _dense(m, cfg.intermediate_size, f"{prefix}_mlp_in", cfg)
        m = layers.gelu(m, approximate=True)
        m = _dense(m, cfg.hidden_size, f"{prefix}_mlp_out", cfg)
        return x + layers.dropout(m, cfg.hidden_dropout, is_test=is_test)


def _attention(qkv, cfg, is_test):
    """Causal self-attention over the packed [B, S, 3H] projections."""
    b, s, _ = qkv.shape
    h = cfg.hidden_size
    nh, dh = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    if cfg.use_fused_attention:
        return layers.fused_qkv_attention(
            qkv, nh, causal=True, scale=1.0 / math.sqrt(dh),
            dropout_prob=cfg.attention_dropout, is_test=is_test,
        )

    def head(t):
        return layers.transpose(
            layers.reshape(t, [b, s, nh, dh]), [0, 2, 1, 3]
        )

    q = head(layers.slice(qkv, [2], [0], [h]))
    k = head(layers.slice(qkv, [2], [h], [2 * h]))
    v = head(layers.slice(qkv, [2], [2 * h], [3 * h]))
    scores = layers.matmul(
        q, k, transpose_y=True, alpha=1.0 / math.sqrt(dh)
    )
    # causal additive mask: 0 on/below the diagonal, -1e4 above
    mask = layers.reshape(
        layers.scale(
            layers.tril(layers.fill_constant([s, s], "float32", 1.0)),
            scale=1e4, bias=-1e4,
        ),
        [1, 1, s, s],
    )
    scores = scores + mask
    probs = layers.softmax(scores, axis=-1)
    probs = layers.dropout(
        probs, cfg.attention_dropout, is_test=is_test
    )
    return layers.reshape(
        layers.transpose(layers.matmul(probs, v), [0, 2, 1, 3]),
        [b, s, h],
    )


def gpt_decoder(input_ids, cfg, is_test=False):
    """input_ids [B, S] int64 -> final hidden states [B, S, H]."""
    b, s = input_ids.shape
    with name_scope("embed"):
        tok = layers.embedding(
            input_ids, size=[cfg.vocab_size, cfg.hidden_size],
            param_attr=ParamAttr(name="wte", initializer=_init(cfg)),
        )
        pos_ids = layers.reshape(layers.range(0, s, 1, "int64"), [1, s])
        pos = layers.embedding(
            pos_ids, size=[cfg.max_position, cfg.hidden_size],
            param_attr=ParamAttr(name="wpe", initializer=_init(cfg)),
        )
        x = layers.dropout(tok + pos, cfg.hidden_dropout, is_test=is_test)
    for i in range(cfg.num_layers):
        x = _decoder_layer(x, cfg, f"gpt_l{i}", is_test)
    with name_scope("head"):
        return _ln(x, "gpt_lnf")


def _lm_head(hidden, cfg):
    """The (shared-name) vocab projection every GPT graph variant uses —
    one definition so the `lm_head_w` checkpoint contract cannot drift."""
    return layers.fc(
        hidden, cfg.vocab_size, num_flatten_dims=2, bias_attr=False,
        param_attr=ParamAttr(name="lm_head_w", initializer=_init(cfg)),
    )


def gpt_lm_loss(input_ids, cfg, is_test=False, labels=None):
    """Next-token LM loss; labels default to input_ids shifted left (the
    final position predicts nothing and is dropped)."""
    b, s = input_ids.shape
    hidden = gpt_decoder(input_ids, cfg, is_test=is_test)
    # slice the HIDDEN states, not the logits: slicing after the vocab
    # projection copies a [B, S, V] tensor (~0.5 GB at S=2048/V=32k);
    # slicing before it is a [B, S, H] copy and the head matmul computes
    # only the s-1 predicted positions
    with name_scope("head"):
        pred_h = layers.slice(hidden, [1], [0], [s - 1])
        pred = _lm_head(pred_h, cfg)
        if labels is None:
            tgt = layers.slice(input_ids, [1], [1], [s])
        else:
            tgt = layers.slice(labels, [1], [1], [s])
        loss = layers.softmax_with_cross_entropy(
            layers.reshape(pred, [b * (s - 1), cfg.vocab_size]),
            layers.reshape(tgt, [b * (s - 1), 1]),
        )
        return layers.mean(loss)


def gpt_logits(input_ids, cfg, is_test=True):
    """Full-context logits [B, S, V] — the serving/full-recompute head
    (no label shift, no loss): every position's next-token distribution."""
    hidden = gpt_decoder(input_ids, cfg, is_test=is_test)
    with name_scope("head"):
        return _lm_head(hidden, cfg)


# --- KV-cache serving graphs (prefill + single-token decode) ---------------
#
# Generation through the training graph re-runs the whole context every
# token (O(S) recompute per emitted token). The serving split keeps each
# layer's K/V rows in persistable scope vars shared BETWEEN two programs:
# a prefill program that embeds the full context once and fills the cache,
# and a single-token decode program that appends one K/V row and attends
# over the cache — O(1) recompute per token. A cache var is stored as the
# layers produce their rows, [B, max_len, H]: the shape is
# ops/kv_cache.py::cache_shape's to say, the layers hand their [B, T, H]
# rows to kv_cache_write as they are. Parameter names match
# gpt_decoder/gpt_logits exactly, so a trained checkpoint loads into
# either graph unchanged (serving/generate.py drives the pair).


def gpt_cache_names(cfg):
    """The persistable cache var names both serving programs share."""
    out = []
    for i in range(cfg.num_layers):
        out += [f"gpt_l{i}_cache_k", f"gpt_l{i}_cache_v"]
    return out


def _cached_decoder_layer(x, cfg, prefix, write_pos, attend_pos, max_len):
    """Pre-LN decoder layer routed through the layer's KV cache: write this
    call's K/V rows at `write_pos`, then attend Q over the cache up to
    `attend_pos` (inclusive), or, with `attend_pos` None (a prefill from
    position 0), over the call's own rows, which are all the cache holds.
    Dropout sites keep their test-mode ``downgrade_in_infer`` (1 - p)
    scaling so outputs match the training graph's ``is_test`` numerics
    (the freeze-parity contract)."""
    from ..framework.program import default_main_program
    from ..layers.tensor import _simple
    from .decoder import kv_cache

    b, t, h = x.shape
    nh, dh = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    with name_scope("attn"):
        a = _ln(x, f"{prefix}_ln1")
        with name_scope("proj"):
            qkv = _dense(a, 3 * h, f"{prefix}_attn_qkv", cfg)
        q = layers.slice(qkv, [2], [0], [h])
        k = layers.slice(qkv, [2], [h], [2 * h])
        v = layers.slice(qkv, [2], [2 * h], [3 * h])
        with name_scope("core"):
            ck, cv = kv_cache(prefix, b, max_len, nh, dh, "float32")
            blk = default_main_program().global_block
            for cache, rows in ((ck, k), (cv, v)):
                blk.append_op(
                    "kv_cache_write",
                    {"Cache": [cache.name], "X": [rows.name],
                     "Pos": [write_pos.name]},
                    {"Out": [cache.name]},
                )
            attrs = {"num_heads": nh, "num_kv_heads": nh,
                     "scale": 1.0 / math.sqrt(dh),
                     "prob_scale": 1.0 - cfg.attention_dropout}
            if attend_pos is None:
                ctxv = _simple("causal_gqa_attention",
                               {"Q": [q], "K": [k], "V": [v]}, attrs)
            else:
                ctxv = _simple(
                    "kv_cache_attention",
                    {"Q": [q], "CacheK": [ck], "CacheV": [cv],
                     "Pos": [attend_pos]},
                    attrs)
        with name_scope("proj"):
            attn = _dense(ctxv, h, f"{prefix}_attn_out", cfg)
        x = x + layers.dropout(attn, cfg.hidden_dropout, is_test=True)
    with name_scope("mlp"):
        m = _ln(x, f"{prefix}_ln2")
        m = _dense(m, cfg.intermediate_size, f"{prefix}_mlp_in", cfg)
        m = layers.gelu(m, approximate=True)
        m = _dense(m, cfg.hidden_size, f"{prefix}_mlp_out", cfg)
        return x + layers.dropout(m, cfg.hidden_dropout, is_test=True)


def gpt_prefill(context_ids, cfg, max_len):
    """Prefill graph body: embed the full [B, S] context, fill every
    layer's KV cache rows 0..S-1, and return the LAST position's
    next-token logits [B, 1, V]. `max_len` bounds the cache (must cover
    context + generated tokens; <= cfg.max_position)."""
    b, s = context_ids.shape
    if max_len > cfg.max_position:
        from ..errors import InvalidArgumentError

        raise InvalidArgumentError(
            f"max_len {max_len} exceeds cfg.max_position {cfg.max_position}"
        )
    with name_scope("embed"):
        tok = layers.embedding(
            context_ids, size=[cfg.vocab_size, cfg.hidden_size],
            param_attr=ParamAttr(name="wte", initializer=_init(cfg)),
        )
        pos_ids = layers.reshape(layers.range(0, s, 1, "int64"), [1, s])
        pos = layers.embedding(
            pos_ids, size=[cfg.max_position, cfg.hidden_size],
            param_attr=ParamAttr(name="wpe", initializer=_init(cfg)),
        )
        x = layers.dropout(tok + pos, cfg.hidden_dropout, is_test=True)
        write_pos = layers.fill_constant([1], "int32", 0)
    for i in range(cfg.num_layers):
        x = _cached_decoder_layer(
            x, cfg, f"gpt_l{i}", write_pos, None, max_len
        )
    with name_scope("head"):
        x = _ln(x, "gpt_lnf")
        last_h = layers.slice(x, [1], [s - 1], [s])
        return _lm_head(last_h, cfg)


def gpt_decode_step(token_ids, pos_ids, cfg, max_len):
    """Single-token decode graph body: embed the [B, 1] token at position
    `pos_ids` ([1, 1] int64 feed), append its K/V rows to every layer's
    cache at that position, attend over the cache, and return next-token
    logits [B, 1, V]. Run repeatedly with the SAME shapes — one compiled
    executable serves the whole generation."""
    b = token_ids.shape[0]
    with name_scope("embed"):
        # [B, 1] ids hit the v1 lookup_table (trailing-1 squeeze): restore
        # the [B, T=1, H] layout the layer stack expects
        tok = layers.reshape(
            layers.embedding(
                token_ids, size=[cfg.vocab_size, cfg.hidden_size],
                param_attr=ParamAttr(name="wte", initializer=_init(cfg)),
            ),
            [b, 1, cfg.hidden_size],
        )
        pos = layers.reshape(
            layers.embedding(
                pos_ids, size=[cfg.max_position, cfg.hidden_size],
                param_attr=ParamAttr(name="wpe", initializer=_init(cfg)),
            ),
            [1, 1, cfg.hidden_size],
        )
        x = layers.dropout(tok + pos, cfg.hidden_dropout, is_test=True)
    for i in range(cfg.num_layers):
        x = _cached_decoder_layer(
            x, cfg, f"gpt_l{i}", pos_ids, pos_ids, max_len
        )
    with name_scope("head"):
        x = _ln(x, "gpt_lnf")
        return _lm_head(x, cfg)


class GPTDecoder:
    """What `serving.GPTGenerator` asks of a decoder: the prefill body
    and the decode body over the caches the two programs share."""

    prefill_rows = None     # one prefill dispatch takes the whole batch
    counters_var = None

    def __init__(self, cfg):
        self.cfg = cfg

    def prefill(self, context_ids, batch, max_len, row_ids=None):
        """(logits, further variables fetched beside them: none)."""
        return gpt_prefill(context_ids, self.cfg, max_len), []

    def decode_step(self, token_ids, pos_ids, max_len):
        return gpt_decode_step(token_ids, pos_ids, self.cfg, max_len), []

    def logits(self, input_ids):
        """The full-context graph (`generate_full_recompute`)."""
        return gpt_logits(input_ids, self.cfg, is_test=True)

    def describe(self):
        cfg = self.cfg
        return {"family": "gpt", "hidden_size": cfg.hidden_size,
                "num_layers": cfg.num_layers, "num_heads": cfg.num_heads,
                "intermediate_size": cfg.intermediate_size,
                "vocab_size": cfg.vocab_size}


def gpt_tp_shardings(cfg, axis="mp"):
    """Megatron column/row-parallel annotations (see bert_tp_shardings)."""
    sh = {"wte": (axis, None), "lm_head_w": (None, axis)}
    for i in range(cfg.num_layers):
        p = f"gpt_l{i}"
        sh[f"{p}_attn_qkv_w"] = (None, axis)
        sh[f"{p}_attn_qkv_b"] = (axis,)
        sh[f"{p}_attn_out_w"] = (axis, None)
        sh[f"{p}_mlp_in_w"] = (None, axis)
        sh[f"{p}_mlp_in_b"] = (axis,)
        sh[f"{p}_mlp_out_w"] = (axis, None)
    return sh
