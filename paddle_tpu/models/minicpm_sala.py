"""MiniCPM-SALA decoder (`model_type` "minicpm_sala") for the serving
path: a dense pre-norm stack whose token mixers are of two kinds, a
dense SwiGLU FFN in every layer, and muP's scalings on the normal path.

    h0 = scale_emb * E[ids]
    every layer:  h = x + c Mixer(N(x));  y = h + c FFN(N(h))
                  c = scale_depth / sqrt(mup_denominator)
    logits = W_head (N(h_last) / (hidden_size / dim_model_base))
    N(x) = x / sqrt(mean(x^2) + eps) * w          (a plain gain)
    FFN(x) = W_d (silu(W_g x) * W_u x)

`lightning-attn` mixer (linear attention with a constant decay a head;
H heads of d lanes, no grouping):
    q = RoPE(N_q(x W_q)), k = RoPE(N_k(x W_k)) per head (theta over all
    d lanes), v = x W_v
    S_t = lambda_h S_{t-1} + k_t^T v_t,  o_t = q_t S_t / sqrt(d)
    lambda_h = exp(-s_h),  s_h = 2^(-8 (h + 1) / H)
    Mixer = W_o [N_o(o) * sigmoid(x W_gate)]      (N_o over all H * d)
The recurrence is the state-space scan and update with dt = 1 and one
group a head (`ops/ssm.py`: `lightning_chunk_scan` over `ssd_chunked`,
`lightning_state_update` through `kernels/ssm_update.py`).

`minicpm4` mixer (InfLLM v2: grouped attention with no positions,
block-sparse beyond `dense_len` keys):
    q = N_q(x W_q), k = N_k(x W_k), v = x W_v per head;
    Mixer = W_o [Attn(q, k, v) * sigmoid(x W_gate)]
Attn is causal grouped attention up to `dense_len` keys (the prompt's
length in a prefill, position + 1 in a decode step), beyond it each
query group's attention over the blocks `ops/llm.py`'s
`sparse_block_select` chooses from the layer's compressed-key index
(`ops/kv_cache.py`). The index, the selection and the sparse attention
are lowered only where the program's `max_len` exceeds `dense_len`.

Like `models/qwen3_next.py` the model is two graph bodies over shared
parameter names on `models/decoder.py`'s base, a prefill and a one-token
decode step, each piece of state declared once with its kind: a
lightning layer carries its float32 state, which does not
grow with `max_len`; a sparse layer its K and V caches, and its index
where it selects. Parameters, activations and caches are `cfg.dtype`
(bfloat16 in serving); the state, the decays, every norm's statistics
and the selection's scores are float32 inside their ops, and the logits
leave the head in float32.
"""

from __future__ import annotations

import math

from .. import layers
from ..framework.program import name_scope
from ..layers.tensor import _simple
from .decoder import (
    Decoder, attend, cache_rows, embed, kv_cache, normal, param, pool_rows,
    proj, rms, rotary, state, swiglu_ffn,
)

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
# the name scope (fluid.name_scope) of a mixer of each kind
SECTIONS = {LIGHTNING: "ssm", SPARSE: "attn"}
COUNTERS_VAR = "minicpm_sala_sparse_counters"
FAMILY = "minicpm_sala"


class MiniCPMSalaConfig:
    def __init__(
        self,
        vocab_size=73448,
        hidden_size=4096,
        mixer_types=(SPARSE,) + (LIGHTNING,) * 6 + (SPARSE,),
        num_heads=32,
        num_kv_heads=2,
        head_dim=128,
        lightning_heads=32,
        lightning_head_dim=128,
        rope_theta=10000.0,
        intermediate_size=16384,
        rms_norm_eps=1e-6,
        scale_emb=12.0,
        scale_depth=1.4,
        dim_model_base=256,
        mup_denominator=32,
        sparse_kernel=32,
        sparse_stride=16,
        init_blocks=1,
        block_size=64,
        window_size=2048,
        topk=64,
        dense_len=8192,
        chunk_size=64,
        initializer_range=0.02,
        attn_qk_gain=2.0,
        dtype="bfloat16",
        prefill_rows=None,
    ):
        unknown = set(mixer_types) - {LIGHTNING, SPARSE}
        if unknown:
            raise ValueError(f"unknown mixer types {sorted(unknown)}")
        if block_size % sparse_stride or sparse_kernel % sparse_stride:
            raise ValueError("block_size and the compression kernel must "
                             "be whole strides")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.layer_kinds = tuple(mixer_types)
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.lightning_heads = lightning_heads
        self.lightning_head_dim = lightning_head_dim
        self.rope_theta = rope_theta
        self.intermediate_size = intermediate_size
        self.rms_norm_eps = rms_norm_eps
        self.scale_emb = scale_emb
        # muP: every residual branch times c (the published depth's
        # constant: `mup_denominator`, not the layers run), the last
        # hidden state over hidden_size / dim_model_base
        self.residual_scale = scale_depth / math.sqrt(mup_denominator)
        self.head_divisor = hidden_size / dim_model_base
        self.sparse_kernel = sparse_kernel
        self.sparse_stride = sparse_stride
        self.init_blocks = init_blocks
        self.block_size = block_size
        self.window_size = window_size
        self.topk = topk
        self.dense_len = dense_len
        self.chunk_size = chunk_size
        # initialisations only: the spread of every projection, the
        # embedding, the head and the norms' gains, and where the sparse
        # layers' QK-norm gains are seeded (a temperature: at one their
        # attention over thousands of keys is nearly uniform)
        self.initializer_range = initializer_range
        self.attn_qk_gain = attn_qk_gain
        self.dtype = dtype
        # rows of the batch one prefill dispatch takes (None: all of them)
        self.prefill_rows = prefill_rows

    @property
    def num_layers(self):
        return len(self.layer_kinds)

    def selects(self, max_len):
        """Whether a program of `max_len` positions lowers the index, the
        selection and the sparse attention."""
        return max_len > self.dense_len

    @classmethod
    def tiny(cls, **kw):
        return cls(**{**dict(
            vocab_size=256, hidden_size=64,
            mixer_types=(SPARSE, LIGHTNING, LIGHTNING, LIGHTNING),
            num_heads=4, num_kv_heads=2, head_dim=16, lightning_heads=4,
            lightning_head_dim=16, intermediate_size=128, sparse_kernel=4,
            sparse_stride=2, block_size=4, window_size=4, topk=5,
            dense_len=16, chunk_size=8,
        ), **kw})


def _lightning_mixer(a, cfg, prefix, batch, row_ids, at, decode):
    """q, k, v and the gate; QK-norm and rotary positions on q and k;
    the recurrence with its state; the output norm gated by
    sigmoid(gate) after it; W_o."""
    from ..framework.program import default_main_program
    from ..ops.kv_cache import ssm_state_shape

    h, d = cfg.lightning_heads, cfg.lightning_head_dim
    with name_scope("proj"):
        q = rms(proj(a, h * d, f"{prefix}_q_w", cfg), f"{prefix}_qn", cfg,
                d)
        k = rms(proj(a, h * d, f"{prefix}_k_w", cfg), f"{prefix}_kn", cfg,
                d)
        v = proj(a, h * d, f"{prefix}_v_w", cfg)
        gate = proj(a, h * d, f"{prefix}_gate_w", cfg)
        q, k = (rotary(x, at, d, cfg.rope_theta) for x in (q, k))
    lightning = state(f"{prefix}_lightning_state",
                      ssm_state_shape(batch, h, d, d, h), "float32", "linear")
    blk = default_main_program().global_block
    o = blk.create_var(name=f"{prefix}_o", shape=v.shape, dtype=v.dtype)
    ins = {"Q": [q.name], "K": [k.name], "V": [v.name],
           "State": [lightning.name]}
    outs = {"Out": [o.name], "StateOut": [lightning.name]}
    with name_scope("scan"):
        if decode:
            blk.append_op("lightning_state_update", ins, outs,
                          {"num_heads": h})
        else:
            row = {} if row_ids is None else {"Row": [row_ids.name]}
            blk.append_op("lightning_chunk_scan", {**ins, **row}, outs,
                          {"num_heads": h, "head_dim": d,
                           "chunk": cfg.chunk_size})
    gain = param(f"{prefix}_out_norm", [h * d], cfg,
                 normal(cfg, 1.0, cfg.initializer_range))
    with name_scope("norm"):
        g = _simple("gated_rms_norm", {"X": [o], "Gate": [gate],
                                       "Scale": [gain]},
                    {"epsilon": cfg.rms_norm_eps, "gate_after": True,
                     "activation": "sigmoid"})
    with name_scope("proj"):
        return proj(g, cfg.hidden_size, f"{prefix}_o_w", cfg)


def _sparse_mixer(a, cfg, prefix, batch, max_len, row_ids, pos_ids):
    """q, k, v and the gate with QK-norm and no positions; the K and V
    caches written at the rows' positions; dense grouped attention, or
    beyond `dense_len` keys the selected blocks' (with the index and
    the selection where the program may get there); the output gated by
    sigmoid(gate) before W_o."""
    from ..framework.program import default_main_program
    from ..ops.kv_cache import index_shape

    nh, kvh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    with name_scope("proj"):
        q = rms(proj(a, nh * dh, f"{prefix}_attn_q_w", cfg),
                f"{prefix}_attn_qn", cfg, dh, seeded=cfg.attn_qk_gain)
        k = rms(proj(a, kvh * dh, f"{prefix}_attn_k_w", cfg),
                f"{prefix}_attn_kn", cfg, dh, seeded=cfg.attn_qk_gain)
        v = proj(a, kvh * dh, f"{prefix}_attn_v_w", cfg)
        gate = proj(a, nh * dh, f"{prefix}_attn_gate_w", cfg)
    caches = kv_cache(prefix, batch, max_len, kvh, dh, cfg.dtype)
    attrs = {"num_heads": nh, "num_kv_heads": kvh, "window": 0,
             "scale": 1.0 / math.sqrt(dh)}
    prefill = pos_ids is None
    seq = a.shape[1]
    out = None
    at = pos_ids
    if prefill:
        with name_scope("core"):
            at = layers.fill_constant([1], "int32", 0)
    cache_rows(caches, k, v, at, row_ids)
    if cfg.selects(max_len):
        ck = caches[0]
        blk = default_main_program().global_block
        index = state(f"{prefix}_index", index_shape(
            batch, max_len, cfg.sparse_stride, kvh, dh), cfg.dtype, "index")
        row = {} if row_ids is None else {"Row": [row_ids.name]}
        with name_scope("index"):
            pool_rows(index, k if prefill else ck, at, row_ids,
                      carry=not prefill, kernel=cfg.sparse_kernel,
                      stride=cfg.sparse_stride)
        if not prefill or seq > cfg.dense_len:
            last = pos_ids if not prefill else layers.fill_constant(
                [1], "int32", seq - 1)
            counters = state(COUNTERS_VAR, (2,), "int32")
            selected = blk.create_var(
                name=f"{prefix}_selected", dtype="int32",
                shape=(a.shape[0], kvh, seq, cfg.topk))
            sparse = {**attrs, "dense_len": cfg.dense_len,
                      "block_size": cfg.block_size}
            with name_scope("select"):
                blk.append_op(
                    "sparse_block_select",
                    {"Q": [q.name], "Index": [index.name],
                     "Pos": [last.name], "Counters": [counters.name],
                     **row},
                    {"Selected": [selected.name],
                     "CountersOut": [counters.name]},
                    {**sparse, "kernel": cfg.sparse_kernel,
                     "stride": cfg.sparse_stride,
                     "window": cfg.window_size,
                     "init_blocks": cfg.init_blocks, "topk": cfg.topk})
            kv = (k, v) if prefill else caches
            with name_scope("core"):
                out = _simple("block_sparse_attention",
                              {"Q": [q], "K": [kv[0]], "V": [kv[1]],
                               "Selected": [selected], "Pos": [last]},
                              sparse)
    if out is None:
        out = attend(q, k, v, caches, pos_ids, **attrs)
    with name_scope("proj"):
        return proj(out * layers.sigmoid(gate), cfg.hidden_size,
                    f"{prefix}_attn_o_w", cfg)


class MiniCPMSalaDecoder(Decoder):
    """MiniCPM-SALA's bodies on `models/decoder.py`'s base, with muP's
    scalings; no layer routes. Its counters are the block-sparse layers':
    blocks selected and blocks a query could see, summed."""

    prefix = FAMILY
    counters_var = COUNTERS_VAR
    counter_names = ("sparse_attention.blocks_selected",
                     "sparse_attention.blocks_visible")
    counter_gauges = frozenset()

    @property
    def head_scale(self):
        """The muP division of the last hidden state."""
        return 1.0 / self.cfg.head_divisor

    def body(self, ids, batch, max_len, row_ids=None, pos_ids=None):
        cfg = self.cfg
        x = embed(ids, cfg, f"{FAMILY}_embed", cfg.scale_emb)
        at = pos_ids
        if pos_ids is None:
            # the rotary positions' anchor: the LAST row's, as the op
            # takes it
            with name_scope("embed"):
                at = layers.fill_constant([1], "int32", ids.shape[1] - 1)
        c = cfg.residual_scale
        for i, kind in enumerate(cfg.layer_kinds):
            prefix = f"{FAMILY}_l{i}"
            with name_scope(SECTIONS[kind]):
                a = rms(x, f"{prefix}_n1", cfg)
                if kind == LIGHTNING:
                    m = _lightning_mixer(a, cfg, prefix, batch, row_ids, at,
                                         decode=pos_ids is not None)
                else:
                    m = _sparse_mixer(a, cfg, prefix, batch, max_len,
                                      row_ids, pos_ids)
                h = x + layers.scale(m, scale=c)
            with name_scope("mlp"):
                m = swiglu_ffn(rms(h, f"{prefix}_n2", cfg),
                               cfg.intermediate_size, f"{prefix}_mlp", cfg)
                x = h + layers.scale(m, scale=c)
        return x, []

    def describe(self):
        """The sizes a cost model needs (benchmark/harness/
        minicpm_sala_cost.py), and the bytes of one sequence's state a
        layer of each kind."""
        cfg = self.cfg
        act = 2 if cfg.dtype == "bfloat16" else 4
        kv_row = cfg.num_kv_heads * cfg.head_dim
        return {
            "family": FAMILY, "hidden_size": cfg.hidden_size,
            "layer_kinds": list(cfg.layer_kinds),
            "num_heads": cfg.num_heads, "num_kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim,
            "lightning_heads": cfg.lightning_heads,
            "lightning_head_dim": cfg.lightning_head_dim,
            "intermediate_size": cfg.intermediate_size,
            "chunk_size": cfg.chunk_size,
            "sparse_kernel": cfg.sparse_kernel,
            "sparse_stride": cfg.sparse_stride,
            "init_blocks": cfg.init_blocks, "block_size": cfg.block_size,
            "window_size": cfg.window_size, "topk": cfg.topk,
            "dense_len": cfg.dense_len, "vocab_size": cfg.vocab_size,
            "bytes_per_param": act,
            "state_bytes_per_sequence": {
                "linear": 4 * cfg.lightning_heads
                * cfg.lightning_head_dim ** 2,
                "full_per_position": act * 2 * kv_row,
                "index_per_position": act * kv_row / cfg.sparse_stride,
            },
        }
