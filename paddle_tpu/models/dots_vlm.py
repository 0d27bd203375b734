"""dots.vlm1 text decoder (rednote-hilab, `model_type` "dots_vlm") for the
serving path: pre-norm blocks, `h = x + Attn(RMSNorm(x))`,
`y = h + FFN(RMSNorm(h))`, whose attention is multi-head latent
attention and whose FFN is dense SwiGLU in the leading layers and
group-limited sigmoid-routed experts with a shared expert after them.

Latent attention keeps ONE cache a layer: a position's row is the normed
key/value latent `c_kv` (`kv_lora_rank` lanes) beside the ONE rotated key
part `k_pe` all heads share (`qk_rope_head_dim` lanes), padded to whole
lane tiles (`ops/kv_cache.py::latent_cache_shape`). The up-projection
`kv_b` that turns a latent into every head's keys and values is ONE
stored parameter, `[heads, r, dn + dv]` (a head's W_UK beside its W_UV),
used in two forms (`ops/llm.py`):

* the prefill EXPANDS: `k_nope = c_kv W_UK`, `v = c_kv W_UV` for all
  heads (`mla_expand`), a head's key is `[k_nope | k_pe]` (192 lanes,
  the last 64 the ONE rotary part all heads share, handed over apart as
  `KShared` and never copied into the heads) and its value 128, and the
  block attends over its own rows (`causal_gqa_attention`);
* the decode step ABSORBS: `q_lat[h] = q_nope[h] W_UK[h]^T` scores
  against the cached latents themselves, the probabilities sum the
  latents, and `o[h] = o_lat[h] W_UV[h]`: all heads read the one cached
  row (`kv_cache_attention` with one KV head, no `CacheV`, the values
  the row's leading `kv_lora_rank` lanes), and nothing per head is ever
  stored.

Rotary positions turn the last `qk_rope_head_dim` lanes of a query head
and the shared key part, at YaRN's blended frequencies; the softmax
scale carries YaRN's temperature squared.

Like `models/afmoe.py` the model is two graph bodies over shared
parameter names on `models/decoder.py`'s base, each layer's cache
declared once with its kind ("latent") and the lanes that carry data;
one chip's share of an expert-parallel deployment is a configuration
(`num_local_experts`, `expert_offset`, `vocab_size`), not a code path.

Parameters, activations and the latent cache are `cfg.dtype` (bfloat16
in serving); norms, softmax, rotary angles and the router keep float32
statistics inside their ops, and the logits leave the head in float32.
"""

from __future__ import annotations

from .. import layers
from ..framework.program import name_scope
from ..layers.tensor import _simple
from .decoder import (
    DENSE, EXPERTS, Decoder, embed, expert_ffn, normal, param, proj, rms,
    rotary, slice_last, state, swiglu_ffn, write_cache,
)

LATENT = "latent_attention"
COUNTERS_VAR = "dots_moe_counters"


class DotsVlmConfig:
    def __init__(
        self,
        vocab_size=129280,
        hidden_size=7168,
        num_heads=128,
        q_lora_rank=1536,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        intermediate_size=18432,
        moe_intermediate_size=2048,
        num_experts=256,
        num_local_experts=None,
        expert_offset=0,
        top_k=8,
        n_group=8,
        topk_group=4,
        num_shared_experts=1,
        route_scale=2.5,
        route_norm=True,
        rope_theta=10000.0,
        rope_scaling=None,
        rms_norm_eps=1e-6,
        layer_kinds=((LATENT, DENSE),) + ((LATENT, EXPERTS),) * 4,
        initializer_range=0.02,
        expert_bias_std=0.001,
        dtype="bfloat16",
        prefill_rows=None,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_heads = num_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts
        self.num_local_experts = (
            num_experts if num_local_experts is None else num_local_experts
        )
        self.expert_offset = expert_offset
        self.top_k = top_k
        self.n_group = n_group
        self.topk_group = topk_group
        self.num_shared_experts = num_shared_experts
        self.route_scale = route_scale
        self.route_norm = route_norm
        self.rope_theta = rope_theta
        # YaRN: factor, original_max_position_embeddings, beta_fast,
        # beta_slow, mscale, mscale_all_dim (None: plain frequencies)
        self.rope_scaling = dict(rope_scaling) if rope_scaling else None
        self.rms_norm_eps = rms_norm_eps
        self.layer_kinds = tuple(tuple(k) for k in layer_kinds)
        # initialisations only, no forward term
        self.initializer_range = initializer_range
        self.expert_bias_std = expert_bias_std
        self.dtype = dtype
        # rows of the batch one prefill dispatch takes (None: all of them)
        self.prefill_rows = prefill_rows

    @property
    def num_layers(self):
        return len(self.layer_kinds)

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_width(self):
        """Lanes of a cache row that carry data: [c_kv | k_pe]."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self):
        """qk_head_dim^-0.5 times YaRN's temperature squared."""
        from ..ops.llm import yarn_mscale

        scale = self.qk_head_dim ** -0.5
        yarn = self.rope_scaling
        if yarn and yarn.get("mscale_all_dim"):
            scale *= yarn_mscale(yarn["factor"], yarn["mscale_all_dim"]) ** 2
        return scale

    @classmethod
    def tiny(cls, **kw):
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, num_heads=4, q_lora_rank=16,
            kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, intermediate_size=128, moe_intermediate_size=32,
            num_experts=16, num_local_experts=4, top_k=4, n_group=4,
            topk_group=2,
            rope_scaling=dict(factor=40, original_max_position_embeddings=64,
                              beta_fast=32, beta_slow=1, mscale=1.0,
                              mscale_all_dim=1.0),
            layer_kinds=((LATENT, DENSE), (LATENT, EXPERTS),
                         (LATENT, EXPERTS)),
        ), **kw})


def _rotary(x, pos, cfg, head_dim):
    """YaRN rotary positions on the last `qk_rope_head_dim` lanes of
    each `head_dim`-wide head."""
    yarn = {"yarn": dict(cfg.rope_scaling)} if cfg.rope_scaling else {}
    return rotary(x, pos, head_dim, cfg.rope_theta,
                  rotary_dim=cfg.qk_rope_head_dim, **yarn)


def _latent_attention(a, cfg, prefix, batch, max_len, row_ids, pos_ids):
    """One layer's attention over `a` [rows, T, H]: a prefill of the
    rows' own T positions from 0 (`pos_ids` None: expanded form), or a
    decode step at `pos_ids` over the cache (absorbed form). Both write
    the rows `[c_kv | k_pe]` into the layer's one cache."""
    from ..ops.kv_cache import latent_cache_shape

    nh, r, dr = cfg.num_heads, cfg.kv_lora_rank, cfg.qk_rope_head_dim
    dn, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
    with name_scope("proj"):
        c_q = rms(proj(a, cfg.q_lora_rank, f"{prefix}_attn_q_a_w", cfg),
                  f"{prefix}_attn_q_a_n", cfg)
        q = proj(c_q, nh * (dn + dr), f"{prefix}_attn_q_b_w", cfg)
        kv_a = proj(a, r + dr, f"{prefix}_attn_kv_a_w", cfg)
        c_kv = rms(slice_last(kv_a, 0, r), f"{prefix}_attn_kv_a_n", cfg)
    last = pos_ids
    if last is None:
        last = layers.fill_constant([1], "int32", a.shape[1] - 1)
    q = _rotary(q, last, cfg, dn + dr)
    k_pe = _rotary(slice_last(kv_a, r, r + dr), last, cfg, dr)
    w_kvb = param(f"{prefix}_attn_kv_b_w", [nh, r, dn + dv], cfg,
                  normal(cfg))
    shape = latent_cache_shape(batch, max_len, cfg.cache_width)
    cache = state(f"{prefix}_cache_kv", shape, cfg.dtype, "latent",
                  cfg.cache_width)
    split = {"nope_dim": dn}
    # the latent's own products (`mla_expand`, `mla_absorb_*`) sit in
    # `attn` under their op types; `core` is the cache write and the call
    if pos_ids is None:
        with name_scope("core"):
            row = layers.concat([c_kv, k_pe], axis=-1)
            first = layers.fill_constant([1], "int32", 0)
            write_cache(cache, row, first, row_ids, ring=True)
        k, v = _simple(
            "mla_expand",
            {"Latent": [c_kv], "WKVB": [w_kvb]}, split,
            out_slots=("K", "V"),
        )
        with name_scope("core"):
            out = _simple(
                "causal_gqa_attention",
                {"Q": [q], "K": [k], "V": [v], "KShared": [k_pe]},
                {"num_heads": nh, "num_kv_heads": nh, "window": 0,
                 "scale": cfg.softmax_scale},
            )
    else:
        with name_scope("core"):
            row = layers.concat([c_kv, k_pe], axis=-1)
            write_cache(cache, row, pos_ids, None, ring=True)
        q_abs = _simple("mla_absorb_query", {"Q": [q], "WKVB": [w_kvb]},
                        {**split, "row_width": shape[2]})
        with name_scope("core"):
            o_lat = _simple(
                "kv_cache_attention",
                {"Q": [q_abs], "CacheK": [cache], "Pos": [pos_ids]},
                {"num_heads": nh, "num_kv_heads": 1, "value_width": r,
                 "window": 0, "scale": cfg.softmax_scale},
            )
        out = _simple("mla_absorb_output", {"X": [o_lat], "WKVB": [w_kvb]},
                      split)
    with name_scope("proj"):
        return proj(out, cfg.hidden_size, f"{prefix}_attn_o_w", cfg)


class DotsVlmDecoder(Decoder):
    """dots.vlm1's bodies on `models/decoder.py`'s base. A layer's one
    cache carries `cache_width` lanes of data in a row padded to whole
    lane tiles."""

    prefix = "dots"
    counters_var = COUNTERS_VAR

    def body(self, ids, batch, max_len, row_ids=None, pos_ids=None):
        cfg = self.cfg
        x = embed(ids, cfg, "dots_embed")
        selected = []
        for i, (_attn, ffn_kind) in enumerate(cfg.layer_kinds):
            prefix = f"dots_l{i}"
            with name_scope("attn"):
                h = x + _latent_attention(
                    rms(x, f"{prefix}_n1", cfg), cfg, prefix, batch,
                    max_len, row_ids, pos_ids)
            with name_scope("mlp" if ffn_kind == DENSE else "moe"):
                m = rms(h, f"{prefix}_n2", cfg)
                if ffn_kind == DENSE:
                    m = swiglu_ffn(m, cfg.intermediate_size,
                                   f"{prefix}_mlp", cfg)
                else:
                    m, sel = expert_ffn(m, prefix, cfg, COUNTERS_VAR,
                                        n_group=cfg.n_group,
                                        topk_group=cfg.topk_group)
                    selected.append(sel)
                x = h + m
        return x, selected

    def describe(self):
        """The sizes a cost model needs (benchmark/harness/mla_cost.py;
        `layer_kinds`' second elements and the expert sizes as
        moe_cost.py reads them)."""
        cfg = self.cfg
        return {
            "family": "dots_vlm", "hidden_size": cfg.hidden_size,
            "num_heads": cfg.num_heads, "q_lora_rank": cfg.q_lora_rank,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim,
            "intermediate_size": cfg.intermediate_size,
            "moe_intermediate_size": cfg.moe_intermediate_size,
            "num_experts": cfg.num_experts,
            "num_local_experts": cfg.num_local_experts,
            "top_k": cfg.top_k, "n_group": cfg.n_group,
            "topk_group": cfg.topk_group,
            "num_shared_experts": cfg.num_shared_experts,
            "vocab_size": cfg.vocab_size,
            "layer_kinds": [list(k) for k in cfg.layer_kinds],
            "bytes_per_param": 2 if cfg.dtype == "bfloat16" else 4,
        }
