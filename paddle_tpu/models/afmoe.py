"""AFMoE decoder (arcee-ai Trinity family, `model_type` "afmoe") for the
serving path: sandwich RMSNorm blocks, gated grouped-query attention with
QK-norm, sliding-window layers with rotary positions beside full layers
with none, dense SwiGLU layers followed by sigmoid-routed expert layers
with a shared expert.

Like `models/gpt.py`'s serving pair, the model is two graph bodies over
shared parameter names: a prefill that embeds a block of prompts, fills
every layer's cache and returns the last position's logits, and a decode
step for one token at a runtime position. `AfmoeDecoder` bundles the pair
with the specs of the state they share, which is what
`serving.GPTGenerator` asks of a decoder.

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
from ..layers.helper import LayerHelper
from ..layers.tensor import _simple
from ..param_attr import ParamAttr

SLIDING, FULL = "sliding_attention", "full_attention"
DENSE, EXPERTS = "dense", "experts"
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


def _normal(cfg, mean=0.0, std=None):
    from ..initializer import Normal

    return Normal(mean, cfg.initializer_range if std is None else std)


def _param(name, shape, cfg, init, dtype=None):
    return LayerHelper("afmoe").create_parameter(
        ParamAttr(name=name, initializer=init), list(shape),
        dtype or cfg.dtype,
    )


def _proj(x, size, name, cfg, init=None):
    return layers.fc(
        x, size=size, num_flatten_dims=2, bias_attr=False,
        param_attr=ParamAttr(name=name, initializer=init or _normal(cfg)),
    )


def _rms(x, name, cfg, width=None, seeded=1.0):
    """RMSNorm with a learned gain over `width` (the hidden size, or one
    head's width: QK-norm). Gains are seeded near `seeded`."""
    gain = _param(name, [width or x.shape[-1]], cfg,
                  _normal(cfg, seeded, seeded * cfg.initializer_range))
    return _simple("rms_norm", {"X": [x], "Scale": [gain]},
                   {"epsilon": cfg.rms_norm_eps})


def _swiglu_ffn(x, width, prefix, cfg):
    gate_up = _proj(x, 2 * width, f"{prefix}_gate_up_w", cfg)
    return _proj(_simple("swiglu", {"X": [gate_up]}, {}), cfg.hidden_size,
                 f"{prefix}_down_w", cfg)


def _state_var(name, shape, dtype):
    """A persistable both serving programs share by name (a cache, the
    counters): declared once per program, allocated by `reset()`."""
    from ..framework.program import default_main_program

    blk = default_main_program().global_block
    if blk.has_var(name):
        return blk.var(name)
    return blk.create_var(name=name, shape=shape, dtype=dtype,
                          persistable=True)


def _expert_ffn(x, prefix, cfg, counters_var=COUNTERS_VAR, expert_bias=True,
                shared_gate=False, **route_attrs):
    """Shared expert (every chip computes it) + this chip's routed part.
    `route_attrs`: further attributes of `moe_local_experts` (a family's
    group-limited selection, its scoring). A family without the
    selection's bias buffer says `expert_bias` False; with `shared_gate`
    the shared expert is scaled by sigmoid(x w), a gate of its own.
    Returns (output, the op's `Selected` ids [B, T, k])."""
    from ..framework import unique_name
    from ..framework.program import default_main_program
    from ..parallel.moe import MOE_COUNTERS

    h, f = cfg.hidden_size, cfg.moe_intermediate_size
    e_local = cfg.num_local_experts
    router_w = _param(f"{prefix}_router_w", [h, cfg.num_experts], cfg,
                      _normal(cfg))
    ins = {}
    if expert_bias:
        # a buffer, not a weight: moves the selection only; float32,
        # seeded small and non-zero so that it is exercised
        bias = _param(f"{prefix}_expert_bias", [cfg.num_experts], cfg,
                      _normal(cfg, std=cfg.expert_bias_std), dtype="float32")
        ins["ExpertBias"] = [bias.name]
    w_gate_up = _param(f"{prefix}_experts_gate_up_w", [e_local, h, 2 * f],
                       cfg, _normal(cfg))
    w_down = _param(f"{prefix}_experts_down_w", [e_local, f, h], cfg,
                    _normal(cfg))
    counters = _state_var(counters_var, (len(MOE_COUNTERS),), "int32")
    blk = default_main_program().global_block
    routed = blk.create_var(name=unique_name.generate(f"{prefix}_routed"),
                            shape=x.shape, dtype=x.dtype)
    selected = blk.create_var(
        name=f"{prefix}_selected", shape=tuple(x.shape[:2]) + (cfg.top_k,),
        dtype="int32",
    )
    # router, top-k, dispatch, the grouped products and the combine are
    # ONE op: the emitter's own scopes (`moe_router`, `moe_dispatch`,
    # `moe_experts`, `moe_combine`) tell them apart beneath this one
    with name_scope("experts"):
        blk.append_op(
            "moe_local_experts",
            {"X": [x.name], "RouterW": [router_w.name], **ins,
             "WGateUp": [w_gate_up.name],
             "WDown": [w_down.name], "Counters": [counters.name]},
            {"Out": [routed.name], "Selected": [selected.name],
             "CountersOut": [counters.name]},
            {"top_k": cfg.top_k, "route_scale": cfg.route_scale,
             "route_norm": cfg.route_norm,
             "expert_offset": cfg.expert_offset, **route_attrs},
        )
    if cfg.num_shared_experts:
        with name_scope("shared"):
            shared = _swiglu_ffn(
                x, f * cfg.num_shared_experts, f"{prefix}_shared", cfg
            )
            if shared_gate:
                shared = shared * layers.sigmoid(
                    _proj(x, 1, f"{prefix}_shared_gate_w", cfg))
            routed = routed + shared
    return routed, selected


def _layer(x, cfg, i, attend):
    """One sandwich block: h = x + N2(Attn(N1(x))), y = h + N4(FFN(N3(h))).
    `attend(prefix, q, k, v, sliding)` writes the layer's cache and returns the
    attention output [B, T, nh * dh]."""
    prefix = f"afmoe_l{i}"
    attn_kind, ffn_kind = cfg.layer_kinds[i]
    nh, kvh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    with name_scope("attn"):
        a = _rms(x, f"{prefix}_n1", cfg)
        with name_scope("proj"):
            q = _rms(_proj(a, nh * dh, f"{prefix}_attn_q_w", cfg),
                     f"{prefix}_attn_qn", cfg, dh)
            k = _rms(_proj(a, kvh * dh, f"{prefix}_attn_k_w", cfg),
                     f"{prefix}_attn_kn", cfg, dh)
            v = _proj(a, kvh * dh, f"{prefix}_attn_v_w", cfg)
            gate = _proj(a, nh * dh, f"{prefix}_attn_g_w", cfg)
        out = attend(prefix, q, k, v, attn_kind == SLIDING)
        with name_scope("proj"):
            out = _proj(out * layers.sigmoid(gate), cfg.hidden_size,
                        f"{prefix}_attn_o_w", cfg)
        h = x + _rms(out, f"{prefix}_n2", cfg, seeded=cfg.norm_out_gain)
    selected = None
    if ffn_kind == DENSE:
        with name_scope("mlp"):
            m = _rms(h, f"{prefix}_n3", cfg)
            m = _swiglu_ffn(m, cfg.intermediate_size, f"{prefix}_mlp", cfg)
            return h + _rms(m, f"{prefix}_n4", cfg,
                            seeded=cfg.norm_out_gain), selected
    with name_scope("moe"):
        m = _rms(h, f"{prefix}_n3", cfg)
        m, selected = _expert_ffn(m, prefix, cfg)
        return h + _rms(m, f"{prefix}_n4", cfg,
                        seeded=cfg.norm_out_gain), selected


def _cache_vars(prefix, cfg, batch, max_len, window):
    from ..ops.kv_cache import cache_shape

    shape = cache_shape(batch, max_len, cfg.num_kv_heads, cfg.head_dim,
                        window)
    return [_state_var(f"{prefix}_cache_{which}", shape, cfg.dtype)
            for which in ("k", "v")]


def _write_cache(cache, rows, pos, row, ring):
    from ..framework.program import default_main_program

    ins = {"Cache": [cache.name], "X": [rows.name], "Pos": [pos.name]}
    if row is not None:
        ins["Row"] = [row.name]
    default_main_program().global_block.append_op(
        "kv_cache_write", ins, {"Out": [cache.name]}, {"ring": bool(ring)}
    )


def _embed(ids, cfg, seq):
    with name_scope("embed"):
        x = layers.embedding(
            ids, size=[cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            param_attr=ParamAttr(name="afmoe_embed",
                                 initializer=_normal(cfg)),
        )
        x = layers.reshape(x, [ids.shape[0], seq, cfg.hidden_size])
        if cfg.mup_enabled:
            x = layers.scale(x, scale=math.sqrt(cfg.hidden_size))
        return x


def _head(x, cfg, family="afmoe", norm=None):
    """Final norm (`norm`: a family's own, `_rms` by default), then the
    untied head over the vocabulary held here; float32 out of the
    product (not a rounded bfloat16 cast up)."""
    with name_scope("head"):
        x = (norm or _rms)(x, f"{family}_norm_f", cfg)
        w = _param(f"{family}_head_w", [cfg.hidden_size, cfg.vocab_size],
                   cfg, _normal(cfg))
        return _simple("mul", {"X": [x], "Y": [w]},
                       {"x_num_col_dims": 2, "y_num_col_dims": 1,
                        "out_dtype": "float32"})


def _rotary(x, pos, cfg):
    return _simple("rotary_embedding", {"X": [x], "Pos": [pos]},
                   {"head_dim": cfg.head_dim, "theta": cfg.rope_theta})


def _side_by_side(selected):
    """The expert layers' `Selected` ids as one variable (one fetch a
    step beside the logits), or None where no layer routes."""
    if not selected:
        return None
    with name_scope("head"):
        return selected[0] if len(selected) == 1 \
            else layers.concat(selected, axis=-1)


def afmoe_prefill(context_ids, cfg, batch, max_len, row_ids=None):
    """Prefill body: `context_ids` [rows, S] are rows `row_ids` .. of a
    batch of `batch` (all of it when `row_ids` is None). Attention runs
    over the call's own keys (a prompt may be longer than a window
    layer's ring); every layer's cache is filled with what later steps
    may still read. Returns (last-position logits [rows, 1, V] float32,
    the expert layers' `Selected` ids side by side, [rows, S, layers * k]
    int32)."""
    s = context_ids.shape[1]
    x = _embed(context_ids, cfg, s)
    with name_scope("attn"):
        first = layers.fill_constant([1], "int32", 0)
        last = layers.fill_constant([1], "int32", s - 1)
    scale = 1.0 / math.sqrt(cfg.head_dim)

    def attend(prefix, q, k, v, sliding):
        window = cfg.sliding_window if sliding else 0
        if sliding:
            q, k = _rotary(q, last, cfg), _rotary(k, last, cfg)
        with name_scope("core"):
            ck, cv = _cache_vars(prefix, cfg, batch, max_len, window)
            _write_cache(ck, k, first, row_ids, ring=True)
            _write_cache(cv, v, first, row_ids, ring=True)
            return _simple(
                "causal_gqa_attention", {"Q": [q], "K": [k], "V": [v]},
                {"num_heads": cfg.num_heads,
                 "num_kv_heads": cfg.num_kv_heads,
                 "window": window, "scale": scale},
            )

    selected = []
    for i in range(cfg.num_layers):
        x, sel = _layer(x, cfg, i, attend)
        if sel is not None:
            selected.append(sel)
    with name_scope("head"):
        last_h = layers.slice(x, [1], [s - 1], [s])
    return _head(last_h, cfg), _side_by_side(selected)


def afmoe_decode_step(token_ids, pos_ids, cfg, max_len):
    """Decode body: one token a row at runtime position `pos_ids`
    ([1, 1] int64). A window layer writes slot ``pos % slots`` of its
    ring and reads what the ring still holds; a full layer appends.
    Returns (logits [B, 1, V] float32, the `Selected` ids
    [B, 1, layers * k] int32)."""
    b = token_ids.shape[0]
    x = _embed(token_ids, cfg, 1)
    scale = 1.0 / math.sqrt(cfg.head_dim)

    def attend(prefix, q, k, v, sliding):
        window = cfg.sliding_window if sliding else 0
        if sliding:
            q, k = _rotary(q, pos_ids, cfg), _rotary(k, pos_ids, cfg)
        with name_scope("core"):
            ck, cv = _cache_vars(prefix, cfg, b, max_len, window)
            _write_cache(ck, k, pos_ids, None, ring=True)
            _write_cache(cv, v, pos_ids, None, ring=True)
            return _simple(
                "kv_cache_attention",
                {"Q": [q], "CacheK": [ck], "CacheV": [cv],
                 "Pos": [pos_ids]},
                {"num_heads": cfg.num_heads,
                 "num_kv_heads": cfg.num_kv_heads,
                 "window": window, "scale": scale},
            )

    selected = []
    for i in range(cfg.num_layers):
        x, sel = _layer(x, cfg, i, attend)
        if sel is not None:
            selected.append(sel)
    return _head(x, cfg), _side_by_side(selected)


class MoeCounters:
    """How `serving.GPTGenerator` reads an expert decoder's device-side
    step counters (`parallel/moe.py::MOE_COUNTERS`, one int32 vector
    named by the decoder's `counters_var`)."""

    @property
    def counter_names(self):
        from ..parallel.moe import MOE_COUNTERS

        return tuple(f"moe.{name}" for name in MOE_COUNTERS)

    # the fullest expert's rows in one call: a maximum, not a sum
    counter_gauges = frozenset({"moe.max_expert_load"})


class AfmoeDecoder(MoeCounters):
    """What `serving.GPTGenerator` asks of a decoder: the two bodies, the
    state they share and how to read its counters."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.prefill_rows = cfg.prefill_rows

    def prefill(self, context_ids, batch, max_len, row_ids=None):
        """(logits, [the selected expert ids]): the ids are fetched with
        the logits, 16 bytes a token and expert layer, so that a check
        against a reference follows the routing of the executables that
        serve (benchmark/builders/afmoe.py)."""
        logits, selected = afmoe_prefill(
            context_ids, self.cfg, batch, max_len, row_ids
        )
        return logits, [] if selected is None else [selected]

    def decode_step(self, token_ids, pos_ids, max_len):
        logits, selected = afmoe_decode_step(
            token_ids, pos_ids, self.cfg, max_len
        )
        return logits, [] if selected is None else [selected]

    def state_specs(self, batch, max_len):
        """[(name, shape, dtype)] of everything `reset()` zeroes: each
        layer's K and V cache by its kind, and the routing counters."""
        from ..ops.kv_cache import cache_shape
        from ..parallel.moe import MOE_COUNTERS

        cfg = self.cfg
        specs = []
        for i in range(cfg.num_layers):
            shape = cache_shape(batch, max_len, cfg.num_kv_heads,
                                cfg.head_dim, cfg.window_of(i))
            specs += [(f"afmoe_l{i}_cache_{w}", shape, cfg.dtype)
                      for w in ("k", "v")]
        if any(kind == EXPERTS for _a, kind in cfg.layer_kinds):
            specs.append((COUNTERS_VAR, (len(MOE_COUNTERS),), "int32"))
        return specs

    def cache_kind(self, name):
        """"window" or "full" for a cache's name, None for other state."""
        if "_cache_" not in name:
            return None
        layer = int(name.split("_")[1][1:])
        return "window" if self.cfg.window_of(layer) else "full"

    counters_var = COUNTERS_VAR

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
