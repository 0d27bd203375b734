"""Nemotron-H decoder (NVIDIA Nemotron 3 family, `model_type`
"nemotron_h") for the serving path: a stack of blocks with ONE mixer
each, `y = x + Mixer(RMSNorm(x))`, the mixer's kind read from a pattern
string: `M` a Mamba-2 state-space mixer, `E` sigmoid-routed experts that
work in a latent with a shared expert at the hidden width, `*`
grouped-query attention with no positional term (order comes from the
Mamba blocks).

Like `models/afmoe.py` the model is two graph bodies over shared
parameter names, a prefill and a one-token decode step, bundled with the
specs of the state they share as `serving.GPTGenerator` asks of a
decoder. That state is of three kinds here: a Mamba block carries its
recurrent state (float32) and its convolution's tail, neither of which
grows with `max_len`; an attention block a full KV cache
(`ops/kv_cache.py` owns all three shapes).

One chip's share of an expert-parallel deployment is a configuration,
not a code path: `num_local_experts` / `expert_offset` say which routed
experts live here (the router still scores all `num_experts`),
`vocab_size` is the rows of the vocabulary held here.

Parameters, activations, the conv tail and the KV cache are `cfg.dtype`
(bfloat16 in serving); the recurrent state, dt, the decays, the router's
scores, softmax and every norm's statistics are float32 inside their
ops, and the logits leave the head in float32.
"""

from __future__ import annotations

import math

from .. import layers
from ..framework.program import name_scope
from ..initializer import Constant, Initializer, Normal, Uniform
from ..layers.tensor import _simple
from ..param_attr import ParamAttr
from .afmoe import (
    MoeCounters, _head, _normal, _param, _proj, _rms, _side_by_side,
    _state_var, _write_cache,
)

MAMBA, EXPERTS, ATTENTION = "M", "E", "*"
# the name scope (fluid.name_scope) of a block of each kind
SECTIONS = {MAMBA: "ssm", EXPERTS: "moe", ATTENTION: "attn"}
COUNTERS_VAR = "nemotron_moe_counters"


class NemotronHConfig:
    def __init__(
        self,
        vocab_size=131072,
        hidden_size=4096,
        pattern="MEMEMEMEM*E",
        mamba_num_heads=128,
        mamba_head_dim=64,
        ssm_state_size=128,
        n_groups=8,
        conv_kernel=4,
        chunk_size=128,
        num_heads=32,
        num_kv_heads=2,
        head_dim=128,
        num_experts=512,
        num_local_experts=None,
        expert_offset=0,
        top_k=22,
        moe_latent_size=1024,
        moe_intermediate_size=2688,
        shared_intermediate_size=5376,
        route_scale=5.0,
        route_norm=True,
        rms_norm_eps=1e-5,
        initializer_range=0.02,
        expert_bias_std=0.001,
        a_range=(1.0, 16.0),
        time_step=(0.001, 0.1, 1e-4),
        dtype="bfloat16",
        prefill_rows=None,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.pattern = "".join(pattern)
        unknown = set(self.pattern) - {MAMBA, EXPERTS, ATTENTION}
        if unknown:
            raise ValueError(f"unknown block kinds {sorted(unknown)} in "
                             f"pattern {self.pattern!r}")
        self.mamba_num_heads = mamba_num_heads
        self.mamba_head_dim = mamba_head_dim
        self.ssm_state_size = ssm_state_size
        self.n_groups = n_groups
        self.conv_kernel = conv_kernel
        self.chunk_size = chunk_size
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.num_experts = num_experts
        self.num_local_experts = (
            num_experts if num_local_experts is None else num_local_experts
        )
        self.expert_offset = expert_offset
        self.top_k = top_k
        self.moe_latent_size = moe_latent_size
        self.moe_intermediate_size = moe_intermediate_size
        self.shared_intermediate_size = shared_intermediate_size
        self.route_scale = route_scale
        self.route_norm = route_norm
        self.rms_norm_eps = rms_norm_eps
        # initialisations only, no forward term: the spread of the
        # projections, of the router's bias buffer, the range A is drawn
        # from, and dt's (min, max, floor)
        self.initializer_range = initializer_range
        self.expert_bias_std = expert_bias_std
        self.a_range = tuple(a_range)
        self.time_step = tuple(time_step)
        self.dtype = dtype
        # rows of the batch one prefill dispatch takes (None: all of them)
        self.prefill_rows = prefill_rows

    @property
    def num_layers(self):
        return len(self.pattern)

    @property
    def d_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @classmethod
    def tiny(cls, **kw):
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, mamba_num_heads=8,
            mamba_head_dim=16, ssm_state_size=16, n_groups=2, chunk_size=8,
            num_heads=4, num_kv_heads=2, head_dim=16, num_experts=16,
            num_local_experts=4, top_k=6, moe_latent_size=32,
            moe_intermediate_size=48, shared_intermediate_size=96,
        ), **kw})


class _StartupChain(Initializer):
    """A parameter drawn uniformly and pushed through a chain of
    element-wise startup ops: [(op type, attrs)], each reading what the
    one before wrote."""

    def __init__(self, low, high, chain):
        self.low, self.high, self.chain = low, high, chain

    def __call__(self, block, name, shape, dtype):
        Uniform(self.low, self.high)(block, name, shape, dtype)
        for op_type, attrs in self.chain:
            block.append_op(op_type, {"X": [name]}, {"Out": [name]}, attrs)


def _a_log_init(cfg):
    """A_log = log(uniform(a_range)): A = -exp(A_log) in [-16, -1]."""
    return _StartupChain(*cfg.a_range, [("log", {})])


def _dt_bias_init(cfg):
    """The inverse softplus of a step size drawn log-uniformly between
    the config's `time_step_min` and `_max` and floored at `_floor`:
    softplus(dt_bias) is that step size. softplus^-1(t) = log(e^t - 1)."""
    lo, hi, floor = cfg.time_step
    return _StartupChain(math.log(lo), math.log(hi), [
        ("exp", {}), ("clip", {"min": floor, "max": 1e30}),
        ("exp", {}), ("scale", {"scale": 1.0, "bias": -1.0}), ("log", {}),
    ])


class _MeanFreeNormal(Initializer):
    """normal(0, std) with the mean over `axis` (the features a down
    projection sums over) subtracted. relu^2 features are non-negative:
    through a zero-mean random matrix their mean adds the SAME vector to
    every token (17% of the output's power), which every later router
    reads as a standing preference for some experts. A trained,
    load-balanced model has no such term; an initialisation, no forward
    term."""

    def __init__(self, std, axis):
        self.std, self.axis = std, axis

    def __call__(self, block, name, shape, dtype):
        Normal(0.0, self.std)(block, name, shape, dtype)
        mean = block.create_var(
            name=f"{name}_mean", dtype=dtype,
            shape=[1 if i == self.axis else n for i, n in enumerate(shape)])
        block.append_op("reduce_mean", {"X": [name]}, {"Out": [mean.name]},
                        {"dim": [self.axis], "keep_dim": True})
        block.append_op("elementwise_sub", {"X": [name], "Y": [mean.name]},
                        {"Out": [name]}, {"axis": -1})


def _slice_last(x, start, end):
    return layers.slice(x, [2], [start], [end])


def _mamba_mixer(a, cfg, prefix, batch, row_ids, decode):
    """[z | xBC | dt] = a W_in; convolution over xBC with its tail; the
    recurrence with its state; gate, group norm, W_out."""
    from ..framework.program import default_main_program
    from ..ops.kv_cache import conv_tail_shape, ssm_state_shape

    h, p = cfg.mamba_num_heads, cfg.mamba_head_dim
    d, conv = cfg.d_inner, cfg.conv_dim
    with name_scope("proj"):
        zxbcdt = _proj(a, d + conv + h, f"{prefix}_in_w", cfg)
    z = _slice_last(zxbcdt, 0, d)
    xbc = _slice_last(zxbcdt, d, d + conv)
    dt = _slice_last(zxbcdt, d + conv, d + conv + h)

    # a depthwise convolution's fan-in is its kernel: seeded as the
    # family leaves it, uniform within 1 / sqrt(k) (at the projections'
    # 0.02 x, B and C would be so small that the recurrence adds nothing)
    bound = 1.0 / math.sqrt(cfg.conv_kernel)
    conv_w = _param(f"{prefix}_conv_w", [conv, cfg.conv_kernel], cfg,
                    Uniform(-bound, bound))
    conv_b = _param(f"{prefix}_conv_b", [conv], cfg, _normal(cfg))
    tail = _state_var(f"{prefix}_conv_tail",
                      conv_tail_shape(batch, conv, cfg.conv_kernel),
                      cfg.dtype)
    state = _state_var(
        f"{prefix}_ssm_state",
        ssm_state_shape(batch, h, p, cfg.ssm_state_size, cfg.n_groups),
        "float32")
    blk = default_main_program().global_block
    row = {} if row_ids is None else {"Row": [row_ids.name]}

    convolved = blk.create_var(name=f"{prefix}_xbc", shape=xbc.shape,
                               dtype=xbc.dtype)
    blk.append_op(
        "causal_conv1d",
        {"X": [xbc.name], "W": [conv_w.name], "Bias": [conv_b.name],
         "Tail": [tail.name], **row},
        {"Out": [convolved.name], "TailOut": [tail.name]},
        {"carry": bool(decode)},
    )
    small = {k: _param(f"{prefix}_{k}", [h], cfg, init, dtype="float32")
             for k, init in (("a_log", _a_log_init(cfg)),
                             ("d", Constant(1.0)),
                             ("dt_bias", _dt_bias_init(cfg)))}
    y = blk.create_var(name=f"{prefix}_y", shape=z.shape, dtype=z.dtype)
    attrs = {"num_heads": h, "head_dim": p, "num_groups": cfg.n_groups,
             "state_size": cfg.ssm_state_size}
    ins = {"XBC": [convolved.name], "Dt": [dt.name],
           "ALog": [small["a_log"].name], "D": [small["d"].name],
           "DtBias": [small["dt_bias"].name], "State": [state.name]}
    with name_scope("scan"):
        if decode:
            blk.append_op("ssm_state_update", ins,
                          {"Out": [y.name], "StateOut": [state.name]},
                          attrs)
        else:
            blk.append_op("ssd_chunk_scan", {**ins, **row},
                          {"Out": [y.name], "StateOut": [state.name]},
                          {**attrs, "chunk": cfg.chunk_size})
    gain = _param(f"{prefix}_gate_norm", [d], cfg,
                  _normal(cfg, 1.0, cfg.initializer_range))
    g = _simple("gated_rms_norm", {"X": [y], "Gate": [z], "Scale": [gain]},
                {"num_groups": cfg.n_groups, "epsilon": cfg.rms_norm_eps})
    with name_scope("proj"):
        return _proj(g, cfg.hidden_size, f"{prefix}_out_w", cfg)


def _attention_mixer(a, cfg, prefix, batch, max_len, row_ids, pos_ids):
    """q, k, v with no bias, no norm and no positional term; the full KV
    cache written at the rows' positions; causal grouped attention."""
    from ..ops.kv_cache import cache_shape

    nh, kvh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    with name_scope("proj"):
        q = _proj(a, nh * dh, f"{prefix}_attn_q_w", cfg)
        k = _proj(a, kvh * dh, f"{prefix}_attn_k_w", cfg)
        v = _proj(a, kvh * dh, f"{prefix}_attn_v_w", cfg)
    shape = cache_shape(batch, max_len, kvh, dh)
    ck, cv = (_state_var(f"{prefix}_cache_{w}", shape, cfg.dtype)
              for w in ("k", "v"))
    attrs = {"num_heads": nh, "num_kv_heads": kvh, "window": 0,
             "scale": 1.0 / math.sqrt(dh)}
    with name_scope("core"):
        if pos_ids is None:
            first = layers.fill_constant([1], "int32", 0)
            _write_cache(ck, k, first, row_ids, ring=True)
            _write_cache(cv, v, first, row_ids, ring=True)
            out = _simple("causal_gqa_attention",
                          {"Q": [q], "K": [k], "V": [v]}, attrs)
        else:
            _write_cache(ck, k, pos_ids, None, ring=True)
            _write_cache(cv, v, pos_ids, None, ring=True)
            out = _simple(
                "kv_cache_attention",
                {"Q": [q], "CacheK": [ck], "CacheV": [cv],
                 "Pos": [pos_ids]},
                attrs)
    with name_scope("proj"):
        return _proj(out, cfg.hidden_size, f"{prefix}_attn_o_w", cfg)


def _relu2_ffn(x, width, out_width, prefix, cfg):
    up = _proj(x, width, f"{prefix}_up_w", cfg)
    return _proj(_simple("relu2", {"X": [up]}, {}), out_width,
                 f"{prefix}_down_w", cfg,
                 init=_MeanFreeNormal(cfg.initializer_range, axis=0))


def _expert_mixer(a, cfg, prefix):
    """This chip's routed experts in the latent (down, the op, up) plus
    the shared expert at the hidden width; the router scores `a`.
    Returns (output, the op's `Selected` ids [B, T, k])."""
    from ..framework import unique_name
    from ..framework.program import default_main_program
    from ..parallel.moe import MOE_COUNTERS

    h, lat, f = cfg.hidden_size, cfg.moe_latent_size, \
        cfg.moe_intermediate_size
    e_local = cfg.num_local_experts
    router_w = _param(f"{prefix}_router_w", [h, cfg.num_experts], cfg,
                      _normal(cfg))
    # a buffer, not a weight: moves the selection only
    bias = _param(f"{prefix}_expert_bias", [cfg.num_experts], cfg,
                  _normal(cfg, std=cfg.expert_bias_std), dtype="float32")
    w_up = _param(f"{prefix}_experts_up_w", [e_local, lat, f], cfg,
                  _normal(cfg))
    w_down = _param(f"{prefix}_experts_down_w", [e_local, f, lat], cfg,
                    _MeanFreeNormal(cfg.initializer_range, axis=1))
    counters = _state_var(COUNTERS_VAR, (len(MOE_COUNTERS),), "int32")
    with name_scope("latent"):
        u = _proj(a, lat, f"{prefix}_latent_down_w", cfg)
    blk = default_main_program().global_block
    routed = blk.create_var(name=unique_name.generate(f"{prefix}_routed"),
                            shape=u.shape, dtype=u.dtype)
    selected = blk.create_var(
        name=f"{prefix}_selected", shape=tuple(a.shape[:2]) + (cfg.top_k,),
        dtype="int32",
    )
    with name_scope("experts"):
        blk.append_op(
            "moe_local_experts",
            {"X": [u.name], "RouterX": [a.name],
             "RouterW": [router_w.name], "ExpertBias": [bias.name],
             "WGateUp": [w_up.name], "WDown": [w_down.name],
             "Counters": [counters.name]},
            {"Out": [routed.name], "Selected": [selected.name],
             "CountersOut": [counters.name]},
            {"top_k": cfg.top_k, "route_scale": cfg.route_scale,
             "route_norm": cfg.route_norm,
             "expert_offset": cfg.expert_offset, "activation": "relu2"},
        )
    with name_scope("latent"):
        out = _proj(routed, h, f"{prefix}_latent_up_w", cfg)
    with name_scope("shared"):
        out = out + _relu2_ffn(a, cfg.shared_intermediate_size, h,
                               f"{prefix}_shared", cfg)
    return out, selected


def _body(ids, cfg, batch, max_len, row_ids=None, pos_ids=None):
    """Both bodies: a prefill of `ids` [rows, S] (rows `row_ids` .. of
    the batch) without `pos_ids`, a decode step of [B, 1] at `pos_ids`
    with. Returns (hidden [.., H], [the expert blocks' Selected ids])."""
    seq = ids.shape[1]
    with name_scope("embed"):
        x = layers.embedding(
            ids, size=[cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            param_attr=ParamAttr(name="nemotron_embed",
                                 initializer=_normal(cfg)),
        )
        x = layers.reshape(x, [ids.shape[0], seq, cfg.hidden_size])
    selected = []
    for i, kind in enumerate(cfg.pattern):
        prefix = f"nemotron_l{i}"
        # a block is one section: its norm, its mixer, its residual sum
        with name_scope(SECTIONS[kind]):
            a = _rms(x, f"{prefix}_norm", cfg)
            if kind == MAMBA:
                m = _mamba_mixer(a, cfg, prefix, batch, row_ids,
                                 decode=pos_ids is not None)
            elif kind == ATTENTION:
                m = _attention_mixer(a, cfg, prefix, batch, max_len,
                                     row_ids, pos_ids)
            else:
                m, sel = _expert_mixer(a, cfg, prefix)
                selected.append(sel)
            x = x + m
    return x, selected


def _extras(selected):
    """The expert blocks' `Selected` ids as the one extra fetch."""
    ids = _side_by_side(selected)
    return [] if ids is None else [ids]


class NemotronHDecoder(MoeCounters):
    """What `serving.GPTGenerator` asks of a decoder: the two bodies, the
    state they share and how to read its counters."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.prefill_rows = cfg.prefill_rows

    def prefill(self, context_ids, batch, max_len, row_ids=None):
        """(last-position logits [rows, 1, V] float32, [the expert
        blocks' `Selected` ids side by side, [rows, S, blocks * k]])."""
        x, selected = _body(context_ids, self.cfg, batch, max_len, row_ids)
        s = context_ids.shape[1]
        with name_scope("head"):
            last = layers.slice(x, [1], [s - 1], [s])
        return _head(last, self.cfg, "nemotron"), _extras(selected)

    def decode_step(self, token_ids, pos_ids, max_len):
        x, selected = _body(token_ids, self.cfg, token_ids.shape[0],
                            max_len, pos_ids=pos_ids)
        return _head(x, self.cfg, "nemotron"), _extras(selected)

    def state_specs(self, batch, max_len):
        """[(name, shape, dtype)] of everything `reset()` zeroes, by
        block kind: a Mamba block's state and conv tail, an attention
        block's K and V cache, and the routing counters."""
        from ..ops.kv_cache import (
            cache_shape, conv_tail_shape, ssm_state_shape,
        )
        from ..parallel.moe import MOE_COUNTERS

        cfg = self.cfg
        specs = []
        for i, kind in enumerate(cfg.pattern):
            p = f"nemotron_l{i}"
            if kind == MAMBA:
                specs += [
                    (f"{p}_ssm_state", ssm_state_shape(
                        batch, cfg.mamba_num_heads, cfg.mamba_head_dim,
                        cfg.ssm_state_size, cfg.n_groups), "float32"),
                    (f"{p}_conv_tail", conv_tail_shape(
                        batch, cfg.conv_dim, cfg.conv_kernel), cfg.dtype),
                ]
            elif kind == ATTENTION:
                shape = cache_shape(batch, max_len, cfg.num_kv_heads,
                                    cfg.head_dim)
                specs += [(f"{p}_cache_{w}", shape, cfg.dtype)
                          for w in ("k", "v")]
        if EXPERTS in cfg.pattern:
            specs.append((COUNTERS_VAR, (len(MOE_COUNTERS),), "int32"))
        return specs

    def cache_kind(self, name):
        """"ssm", "conv" or "full" for a piece of per-sequence state by
        its name, None for other state."""
        for suffix, kind in (("_ssm_state", "ssm"), ("_conv_tail", "conv"),
                             ("_cache_k", "full"), ("_cache_v", "full")):
            if name.endswith(suffix):
                return kind
        return None

    counters_var = COUNTERS_VAR

    def describe(self):
        """The sizes a cost model needs (benchmark/harness/
        nemotron_h_cost.py), and the bytes of one sequence's state a
        block of each kind."""
        cfg = self.cfg
        act = 2 if cfg.dtype == "bfloat16" else 4
        return {
            "family": "nemotron_h", "hidden_size": cfg.hidden_size,
            "pattern": cfg.pattern,
            "mamba_num_heads": cfg.mamba_num_heads,
            "mamba_head_dim": cfg.mamba_head_dim,
            "ssm_state_size": cfg.ssm_state_size, "n_groups": cfg.n_groups,
            "conv_kernel": cfg.conv_kernel, "chunk_size": cfg.chunk_size,
            "num_heads": cfg.num_heads, "num_kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "num_experts": cfg.num_experts,
            "num_local_experts": cfg.num_local_experts, "top_k": cfg.top_k,
            "moe_latent_size": cfg.moe_latent_size,
            "moe_intermediate_size": cfg.moe_intermediate_size,
            "shared_intermediate_size": cfg.shared_intermediate_size,
            "vocab_size": cfg.vocab_size, "bytes_per_param": act,
            "state_bytes_per_sequence": {
                "ssm": 4 * cfg.d_inner * cfg.ssm_state_size,
                "conv": act * (cfg.conv_kernel - 1) * cfg.conv_dim,
                "full_per_position": act * 2 * cfg.num_kv_heads
                * cfg.head_dim,
            },
        }
