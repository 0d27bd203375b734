"""Nemotron-H decoder (NVIDIA Nemotron 3 family, `model_type`
"nemotron_h") for the serving path: a stack of blocks with ONE mixer
each, `y = x + Mixer(RMSNorm(x))`, the mixer's kind read from a pattern
string: `M` a Mamba-2 state-space mixer, `E` sigmoid-routed experts that
work in a latent with a shared expert at the hidden width, `*`
grouped-query attention with no positional term (order comes from the
Mamba blocks).

Like `models/afmoe.py` the model is two graph bodies over shared
parameter names on `models/decoder.py`'s base, a prefill and a one-token
decode step, each piece of state declared once with its kind. That state
is of three kinds here: a Mamba block carries its recurrent state
(float32) and its convolution's tail, neither of which grows with
`max_len`; an attention block a full KV cache (`ops/kv_cache.py` owns
all three shapes).

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
from .decoder import (
    Decoder, StartupChain, cached_attention, dt_bias_init, embed, kv_cache,
    normal, param, proj, rms, route_experts, slice_last, state,
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


def _a_log_init(cfg):
    """A_log = log(uniform(a_range)): A = -exp(A_log) in [-16, -1]."""
    return StartupChain(*cfg.a_range, [("log", {})])


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


def _mamba_mixer(a, cfg, prefix, batch, row_ids, decode):
    """[z | xBC | dt] = a W_in; convolution over xBC with its tail; the
    recurrence with its state; gate, group norm, W_out."""
    from ..framework.program import default_main_program
    from ..ops.kv_cache import conv_tail_shape, ssm_state_shape

    h, p = cfg.mamba_num_heads, cfg.mamba_head_dim
    d, conv = cfg.d_inner, cfg.conv_dim
    with name_scope("proj"):
        zxbcdt = proj(a, d + conv + h, f"{prefix}_in_w", cfg)
    z = slice_last(zxbcdt, 0, d)
    xbc = slice_last(zxbcdt, d, d + conv)
    dt = slice_last(zxbcdt, d + conv, d + conv + h)

    # a depthwise convolution's fan-in is its kernel: seeded as the
    # family leaves it, uniform within 1 / sqrt(k) (at the projections'
    # 0.02 x, B and C would be so small that the recurrence adds nothing)
    bound = 1.0 / math.sqrt(cfg.conv_kernel)
    conv_w = param(f"{prefix}_conv_w", [conv, cfg.conv_kernel], cfg,
                   Uniform(-bound, bound))
    conv_b = param(f"{prefix}_conv_b", [conv], cfg, normal(cfg))
    tail = state(f"{prefix}_conv_tail",
                 conv_tail_shape(batch, conv, cfg.conv_kernel), cfg.dtype,
                 "conv")
    ssm = state(
        f"{prefix}_ssm_state",
        ssm_state_shape(batch, h, p, cfg.ssm_state_size, cfg.n_groups),
        "float32", "ssm")
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
    small = {k: param(f"{prefix}_{k}", [h], cfg, init, dtype="float32")
             for k, init in (("a_log", _a_log_init(cfg)),
                             ("d", Constant(1.0)),
                             ("dt_bias", dt_bias_init(cfg)))}
    y = blk.create_var(name=f"{prefix}_y", shape=z.shape, dtype=z.dtype)
    attrs = {"num_heads": h, "head_dim": p, "num_groups": cfg.n_groups,
             "state_size": cfg.ssm_state_size}
    ins = {"XBC": [convolved.name], "Dt": [dt.name],
           "ALog": [small["a_log"].name], "D": [small["d"].name],
           "DtBias": [small["dt_bias"].name], "State": [ssm.name]}
    with name_scope("scan"):
        if decode:
            blk.append_op("ssm_state_update", ins,
                          {"Out": [y.name], "StateOut": [ssm.name]},
                          attrs)
        else:
            blk.append_op("ssd_chunk_scan", {**ins, **row},
                          {"Out": [y.name], "StateOut": [ssm.name]},
                          {**attrs, "chunk": cfg.chunk_size})
    gain = param(f"{prefix}_gate_norm", [d], cfg,
                 normal(cfg, 1.0, cfg.initializer_range))
    g = _simple("gated_rms_norm", {"X": [y], "Gate": [z], "Scale": [gain]},
                {"num_groups": cfg.n_groups, "epsilon": cfg.rms_norm_eps})
    with name_scope("proj"):
        return proj(g, cfg.hidden_size, f"{prefix}_out_w", cfg)


def _attention_mixer(a, cfg, prefix, batch, max_len, row_ids, pos_ids):
    """q, k, v with no bias, no norm and no positional term; the full KV
    cache written at the rows' positions; causal grouped attention."""
    nh, kvh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    with name_scope("proj"):
        q = proj(a, nh * dh, f"{prefix}_attn_q_w", cfg)
        k = proj(a, kvh * dh, f"{prefix}_attn_k_w", cfg)
        v = proj(a, kvh * dh, f"{prefix}_attn_v_w", cfg)
    caches = kv_cache(prefix, batch, max_len, kvh, dh, cfg.dtype)
    at = pos_ids
    if pos_ids is None:
        with name_scope("core"):
            at = layers.fill_constant([1], "int32", 0)
    out = cached_attention(q, k, v, caches, at, row_ids, pos_ids,
                           num_heads=nh, num_kv_heads=kvh, window=0,
                           scale=1.0 / math.sqrt(dh))
    with name_scope("proj"):
        return proj(out, cfg.hidden_size, f"{prefix}_attn_o_w", cfg)


def _relu2_ffn(x, width, out_width, prefix, cfg):
    up = proj(x, width, f"{prefix}_up_w", cfg)
    return proj(_simple("relu2", {"X": [up]}, {}), out_width,
                f"{prefix}_down_w", cfg,
                init=_MeanFreeNormal(cfg.initializer_range, axis=0))


def _expert_mixer(a, cfg, prefix):
    """This chip's routed experts in the latent (down, the op, up) plus
    the shared expert at the hidden width; the router scores `a`.
    Returns (output, the op's `Selected` ids [B, T, k])."""
    from ..parallel.moe import MOE_COUNTERS

    h, lat, f = cfg.hidden_size, cfg.moe_latent_size, \
        cfg.moe_intermediate_size
    e_local = cfg.num_local_experts
    router_w = param(f"{prefix}_router_w", [h, cfg.num_experts], cfg,
                     normal(cfg))
    # a buffer, not a weight: moves the selection only
    bias = param(f"{prefix}_expert_bias", [cfg.num_experts], cfg,
                 normal(cfg, std=cfg.expert_bias_std), dtype="float32")
    w_up = param(f"{prefix}_experts_up_w", [e_local, lat, f], cfg,
                 normal(cfg))
    w_down = param(f"{prefix}_experts_down_w", [e_local, f, lat], cfg,
                   _MeanFreeNormal(cfg.initializer_range, axis=1))
    counters = state(COUNTERS_VAR, (len(MOE_COUNTERS),), "int32")
    with name_scope("latent"):
        u = proj(a, lat, f"{prefix}_latent_down_w", cfg)
    routed, selected = route_experts(
        u, prefix, cfg,
        {"RouterX": [a.name], "RouterW": [router_w.name],
         "ExpertBias": [bias.name], "WGateUp": [w_up.name],
         "WDown": [w_down.name], "Counters": [counters.name]},
        activation="relu2")
    with name_scope("latent"):
        out = proj(routed, h, f"{prefix}_latent_up_w", cfg)
    with name_scope("shared"):
        out = out + _relu2_ffn(a, cfg.shared_intermediate_size, h,
                               f"{prefix}_shared", cfg)
    return out, selected


class NemotronHDecoder(Decoder):
    """Nemotron-H's bodies on `models/decoder.py`'s base: a block is one
    section, its norm, its mixer and its residual sum."""

    prefix = "nemotron"
    counters_var = COUNTERS_VAR

    def body(self, ids, batch, max_len, row_ids=None, pos_ids=None):
        cfg = self.cfg
        x = embed(ids, cfg, "nemotron_embed")
        selected = []
        for i, kind in enumerate(cfg.pattern):
            prefix = f"nemotron_l{i}"
            with name_scope(SECTIONS[kind]):
                a = rms(x, f"{prefix}_norm", cfg)
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
