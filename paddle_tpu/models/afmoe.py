"""AFMoE decoder (arcee-ai Trinity family, `model_type` "afmoe") for the
serving path: sandwich RMSNorm blocks, gated grouped-query attention with
QK-norm, sliding-window layers with rotary positions beside full layers
with none, dense SwiGLU layers followed by sigmoid-routed expert layers
with a shared expert.

Like every serving family, the model is two graph bodies over shared
parameter names on `models/decoder.py`'s base: a prefill that embeds a
block of prompts, fills every layer's cache and returns the last
position's logits, and a decode step for one token at a runtime
position. Each cache is declared once, with its kind ("window" or
"full"), where the bodies create it.

One chip's share of an expert-parallel deployment is a configuration,
not a code path: `num_local_experts` / `expert_offset` say which experts
live here (the router still scores all `num_experts`), `vocab_size` is
the rows of the vocabulary held here. What the absent experts would add
is absent; nothing stands in for it.

Parameters, activations and caches are `cfg.dtype` (bfloat16 in
serving); norms, softmax and the router keep float32 statistics inside
their ops, and the logits leave the head in float32.
"""

from __future__ import annotations

import math

from .. import layers
from ..framework.program import name_scope
from .decoder import (
    DENSE, EXPERTS, Decoder, cached_attention, embed, expert_ffn, kv_cache,
    proj, rms, rotary, swiglu_ffn,
)

SLIDING, FULL = "sliding_attention", "full_attention"
COUNTERS_VAR = "afmoe_moe_counters"


class AfmoeConfig:
    def __init__(
        self,
        vocab_size=200192,
        hidden_size=3072,
        num_heads=48,
        num_kv_heads=8,
        head_dim=128,
        intermediate_size=12288,
        moe_intermediate_size=3072,
        num_experts=256,
        num_local_experts=None,
        expert_offset=0,
        top_k=4,
        num_shared_experts=1,
        route_scale=2.448,
        route_norm=True,
        sliding_window=4096,
        rope_theta=10000.0,
        rms_norm_eps=1e-5,
        layer_kinds=((SLIDING, DENSE), (SLIDING, EXPERTS), (SLIDING, EXPERTS),
                     (SLIDING, EXPERTS), (FULL, EXPERTS)),
        mup_enabled=True,
        initializer_range=0.02,
        norm_out_gain=1.0,
        expert_bias_std=0.01,
        dtype="bfloat16",
        prefill_rows=None,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.num_experts = num_experts
        self.num_local_experts = (
            num_experts if num_local_experts is None else num_local_experts
        )
        self.expert_offset = expert_offset
        self.top_k = top_k
        self.num_shared_experts = num_shared_experts
        self.route_scale = route_scale
        self.route_norm = route_norm
        self.sliding_window = sliding_window
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.layer_kinds = tuple(tuple(k) for k in layer_kinds)
        self.mup_enabled = mup_enabled
        self.initializer_range = initializer_range
        # initialisations only, no forward term: where the gains of a
        # layer's two output norms (N2, N4) are seeded ("depth-scaled"
        # sandwich norm: 1/sqrt(depth) there, 1 here), and how far the
        # router's bias buffer starts from balance
        self.norm_out_gain = norm_out_gain
        self.expert_bias_std = expert_bias_std
        self.dtype = dtype
        # rows of the batch one prefill dispatch takes (None: all of them)
        self.prefill_rows = prefill_rows

    @property
    def num_layers(self):
        return len(self.layer_kinds)

    @classmethod
    def tiny(cls, **kw):
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, num_heads=4, num_kv_heads=2,
            head_dim=16, intermediate_size=128, moe_intermediate_size=64,
            num_experts=16, num_local_experts=2, sliding_window=8,
        ), **kw})

    def window_of(self, layer):
        return self.sliding_window if self.layer_kinds[layer][0] == SLIDING \
            else 0


def _layer(x, cfg, i, batch, max_len, row_ids, first, last, pos_ids):
    """One sandwich block: h = x + N2(Attn(N1(x))), y = h + N4(FFN(N3(h))).
    A window layer turns q and k at `last` and keeps a ring of the
    window's slots; the rows are written from `first`. Returns (y, the
    expert op's `Selected` ids, or None)."""
    prefix = f"afmoe_l{i}"
    ffn_kind = cfg.layer_kinds[i][1]
    nh, kvh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    window = cfg.window_of(i)
    with name_scope("attn"):
        a = rms(x, f"{prefix}_n1", cfg)
        with name_scope("proj"):
            q = rms(proj(a, nh * dh, f"{prefix}_attn_q_w", cfg),
                    f"{prefix}_attn_qn", cfg, dh)
            k = rms(proj(a, kvh * dh, f"{prefix}_attn_k_w", cfg),
                    f"{prefix}_attn_kn", cfg, dh)
            v = proj(a, kvh * dh, f"{prefix}_attn_v_w", cfg)
            gate = proj(a, nh * dh, f"{prefix}_attn_g_w", cfg)
        if window:
            q, k = (rotary(t, last, dh, cfg.rope_theta) for t in (q, k))
        caches = kv_cache(prefix, batch, max_len, kvh, dh, cfg.dtype, window)
        out = cached_attention(
            q, k, v, caches, first, row_ids, pos_ids, num_heads=nh,
            num_kv_heads=kvh, window=window, scale=1.0 / math.sqrt(dh))
        with name_scope("proj"):
            out = proj(out * layers.sigmoid(gate), cfg.hidden_size,
                       f"{prefix}_attn_o_w", cfg)
        h = x + rms(out, f"{prefix}_n2", cfg, seeded=cfg.norm_out_gain)
    with name_scope("mlp" if ffn_kind == DENSE else "moe"):
        m = rms(h, f"{prefix}_n3", cfg)
        selected = None
        if ffn_kind == DENSE:
            m = swiglu_ffn(m, cfg.intermediate_size, f"{prefix}_mlp", cfg)
        else:
            m, selected = expert_ffn(m, prefix, cfg, COUNTERS_VAR)
        return h + rms(m, f"{prefix}_n4", cfg,
                       seeded=cfg.norm_out_gain), selected


class AfmoeDecoder(Decoder):
    """Trinity's bodies on `models/decoder.py`'s base. The prefill
    attends over the call's own keys (a prompt may be longer than a
    window layer's ring) and fills every cache with what later steps may
    still read; a decode step writes slot ``pos % slots`` of a window
    layer's ring and reads what the ring still holds, a full layer
    appends."""

    prefix = "afmoe"
    counters_var = COUNTERS_VAR

    def body(self, ids, batch, max_len, row_ids=None, pos_ids=None):
        cfg = self.cfg
        x = embed(ids, cfg, "afmoe_embed",
                  math.sqrt(cfg.hidden_size) if cfg.mup_enabled else None)
        first = last = pos_ids
        if pos_ids is None:
            with name_scope("attn"):
                first = layers.fill_constant([1], "int32", 0)
                last = layers.fill_constant([1], "int32", ids.shape[1] - 1)
        selected = []
        for i in range(cfg.num_layers):
            x, sel = _layer(x, cfg, i, batch, max_len, row_ids, first, last,
                            pos_ids)
            if sel is not None:
                selected.append(sel)
        return x, selected

    def describe(self):
        """The sizes a cost model needs (benchmark/harness/moe_cost.py)."""
        cfg = self.cfg
        return {
            "family": "afmoe", "hidden_size": cfg.hidden_size,
            "num_heads": cfg.num_heads, "num_kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim,
            "intermediate_size": cfg.intermediate_size,
            "moe_intermediate_size": cfg.moe_intermediate_size,
            "num_experts": cfg.num_experts,
            "num_local_experts": cfg.num_local_experts,
            "top_k": cfg.top_k, "num_shared_experts": cfg.num_shared_experts,
            "vocab_size": cfg.vocab_size,
            "sliding_window": cfg.sliding_window,
            "layer_kinds": [list(k) for k in cfg.layer_kinds],
            "bytes_per_param": 2 if cfg.dtype == "bfloat16" else 4,
        }
