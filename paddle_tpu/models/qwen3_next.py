"""Qwen3-Next decoder (`model_type` "qwen3_next") for the serving path:
every layer is a token mixer of one of two kinds AND an expert FFN,
`h = x + Mixer(N(x))`, `y = h + FFN(N(h))`. Three layers of four mix
with a Gated DeltaNet (linear attention: a depthwise convolution over
q | k | v, then the gated delta rule over a fixed-size recurrent state,
a per-head RMSNorm gated AFTER it), the fourth with gated grouped-query
attention whose 256-wide heads turn their LEADING quarter and whose
output gate comes out of the query projection. The FFN routes by
softmax over all experts, top-k renormalised, beside a shared expert
behind a sigmoid gate of its own. RMSNorm gains are stored as their
distance from one (`1 + w`).

Like `models/nemotron_h.py` the model is two graph bodies over shared
parameter names on `models/decoder.py`'s base, a prefill and a one-token
decode step, each piece of state declared once with its kind. That state
is of three kinds side by side: a linear layer carries its delta-rule
state (float32) and its convolution's tail, neither of which grows with
`max_len`; a full layer a KV cache (`ops/kv_cache.py` owns all three
shapes).

One chip's share of an expert-parallel deployment is a configuration,
not a code path: `num_local_experts` / `expert_offset` say which routed
experts live here (the router still scores all `num_experts`),
`vocab_size` is the rows of the vocabulary held here.

Parameters, activations, the conv tail and the KV caches are `cfg.dtype`
(bfloat16 in serving); the delta-rule state, its decay and beta, the L2
norms of q and k, the solve inside a chunk, the router's softmax and
every norm's statistics are float32 inside their ops, and the logits
leave the head in float32.
"""

from __future__ import annotations

import math

from .. import layers
from ..framework.program import name_scope
from ..initializer import Constant, Uniform
from ..layers.tensor import _simple
from .decoder import (
    EXPERTS, Decoder, StartupChain, cached_attention, dt_bias_init, embed,
    expert_ffn, kv_cache, param, proj, rms, rotary, slice_last, state,
)

LINEAR, FULL = "linear", "full"
# the name scope (fluid.name_scope) of a mixer of each kind
SECTIONS = {LINEAR: "ssm", FULL: "attn"}
COUNTERS_VAR = "qwen3_next_moe_counters"
FAMILY = "qwen3_next"


class Qwen3NextConfig:
    def __init__(
        self,
        vocab_size=151936,
        hidden_size=2048,
        num_layers=48,
        full_attention_interval=4,
        first_layer=0,
        num_heads=16,
        num_kv_heads=2,
        head_dim=256,
        partial_rotary_factor=0.25,
        rope_theta=10000000.0,
        linear_num_key_heads=16,
        linear_num_value_heads=32,
        linear_key_head_dim=128,
        linear_value_head_dim=128,
        linear_conv_kernel_dim=4,
        chunk_size=64,
        num_experts=512,
        num_local_experts=None,
        expert_offset=0,
        top_k=10,
        moe_intermediate_size=512,
        shared_intermediate_size=512,
        route_norm=True,
        rms_norm_eps=1e-6,
        initializer_range=0.02,
        a_range=(0.0, 16.0, 0.01),
        time_step=(0.001, 0.1, 1e-4),
        dtype="bfloat16",
        prefill_rows=None,
    ):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        # layer i of the run is published layer `first_layer + i`: full
        # attention where (its number + 1) % interval == 0, else linear
        self.layer_kinds = tuple(
            (FULL if (first_layer + i + 1) % full_attention_interval == 0
             else LINEAR, EXPERTS) for i in range(num_layers))
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.rotary_dim = int(head_dim * partial_rotary_factor)
        self.rope_theta = rope_theta
        self.linear_num_key_heads = linear_num_key_heads
        self.linear_num_value_heads = linear_num_value_heads
        self.linear_key_head_dim = linear_key_head_dim
        self.linear_value_head_dim = linear_value_head_dim
        self.linear_conv_kernel_dim = linear_conv_kernel_dim
        if chunk_size & (chunk_size - 1):
            raise ValueError(f"chunk_size {chunk_size} is no power of two")
        self.chunk_size = chunk_size
        self.num_experts = num_experts
        self.num_local_experts = (
            num_experts if num_local_experts is None else num_local_experts
        )
        self.expert_offset = expert_offset
        self.top_k = top_k
        self.moe_intermediate_size = moe_intermediate_size
        if shared_intermediate_size % moe_intermediate_size:
            raise ValueError("the shared expert is no whole number of "
                             "routed experts' widths")
        self.shared_intermediate_size = shared_intermediate_size
        self.num_shared_experts = \
            shared_intermediate_size // moe_intermediate_size
        self.route_norm = route_norm
        self.route_scale = 1.0
        self.rms_norm_eps = rms_norm_eps
        # initialisations only, no forward term: the spread of the
        # projections and of every stored gain w (applied as 1 + w), the
        # range A is drawn from with its floor, dt's (min, max, floor)
        self.initializer_range = initializer_range
        self.a_range = tuple(a_range)
        self.time_step = tuple(time_step)
        self.dtype = dtype
        # rows of the batch one prefill dispatch takes (None: all of them)
        self.prefill_rows = prefill_rows

    @property
    def num_layers(self):
        return len(self.layer_kinds)

    @property
    def key_dim(self):
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self):
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self):
        return 2 * self.key_dim + self.value_dim

    @classmethod
    def tiny(cls, **kw):
        return cls(**{**dict(
            vocab_size=256, hidden_size=64, num_layers=4, num_heads=4,
            num_kv_heads=2, head_dim=32, linear_num_key_heads=2,
            linear_num_value_heads=4, linear_key_head_dim=16,
            linear_value_head_dim=16, chunk_size=8, num_experts=16,
            num_local_experts=4, top_k=4, moe_intermediate_size=32,
            shared_intermediate_size=32,
        ), **kw})


def _norm(x, name, cfg, width=None):
    """RMSNorm whose stored gain w is applied as 1 + w, over `width` (the
    hidden size, or one head's width: QK-norm). w is zero in the family's
    initialisation; seeded like the projections here so that the `1 +`
    is seen."""
    return rms(x, name, cfg, width, unit_offset=True)


def _a_log_init(cfg):
    """A_log = log(max(uniform(low, high), floor)): the decay rate A =
    exp(A_log) in (0, 16] as the family draws it, kept off log(0)."""
    low, high, floor = cfg.a_range
    return StartupChain(low, high, [("clip", {"min": floor, "max": 1e30}),
                                    ("log", {})])


def _delta_mixer(a, cfg, prefix, batch, row_ids, decode):
    """[q | k | v | z] = a W_qkvz, [b | al] = a W_ba; convolution over
    q | k | v with its tail; the gated delta rule with its state; the
    per-head norm gated by z after it; W_out."""
    from ..framework.program import default_main_program
    from ..ops.kv_cache import conv_tail_shape, ssm_state_shape

    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    conv, vd, kernel = cfg.conv_dim, cfg.value_dim, cfg.linear_conv_kernel_dim
    with name_scope("proj"):
        qkvz = proj(a, conv + vd, f"{prefix}_in_qkvz_w", cfg)
        ba = proj(a, 2 * hv, f"{prefix}_in_ba_w", cfg)
    qkv, z = slice_last(qkvz, 0, conv), slice_last(qkvz, conv, conv + vd)
    b, al = slice_last(ba, 0, hv), slice_last(ba, hv, 2 * hv)

    # a depthwise convolution's fan-in is its 4 taps: seeded uniform
    # within 1 / sqrt(k), as models/nemotron_h.py found necessary (at the
    # projections' 0.02 the state would add nothing that a check sees)
    bound = 1.0 / math.sqrt(kernel)
    conv_w = param(f"{prefix}_conv_w", [conv, kernel], cfg,
                   Uniform(-bound, bound))
    tail = state(f"{prefix}_conv_tail",
                 conv_tail_shape(batch, conv, kernel), cfg.dtype, "conv")
    gdn = state(f"{prefix}_gdn_state",
                ssm_state_shape(batch, hv, dv, dk, hk), "float32", "linear")
    blk = default_main_program().global_block
    row = {} if row_ids is None else {"Row": [row_ids.name]}

    convolved = blk.create_var(name=f"{prefix}_qkv", shape=qkv.shape,
                               dtype=qkv.dtype)
    with name_scope("conv"):
        blk.append_op(
            "causal_conv1d",
            {"X": [qkv.name], "W": [conv_w.name], "Tail": [tail.name], **row},
            {"Out": [convolved.name], "TailOut": [tail.name]},
            {"carry": bool(decode)},
        )
    a_log = param(f"{prefix}_a_log", [hv], cfg, _a_log_init(cfg),
                  dtype="float32")
    dt_bias = param(f"{prefix}_dt_bias", [hv], cfg, dt_bias_init(cfg),
                    dtype="float32")
    o = blk.create_var(name=f"{prefix}_o", shape=z.shape, dtype=z.dtype)
    ins = {"QKV": [convolved.name], "B": [b.name], "A": [al.name],
           "ALog": [a_log.name], "DtBias": [dt_bias.name],
           "State": [gdn.name]}
    attrs = {"key_heads": hk, "value_heads": hv, "key_dim": dk,
             "value_dim": dv}
    with name_scope("scan"):
        if decode:
            blk.append_op("gated_delta_state_update", ins,
                          {"Out": [o.name], "StateOut": [gdn.name]}, attrs)
        else:
            blk.append_op("gated_delta_chunk_scan", {**ins, **row},
                          {"Out": [o.name], "StateOut": [gdn.name]},
                          {**attrs, "chunk": cfg.chunk_size})
    # one gain of a head's width, shared by the heads; ones as the family
    # leaves it (it is a plain gain, not 1 + w)
    gain = param(f"{prefix}_gate_norm", [dv], cfg, Constant(1.0))
    with name_scope("norm"):
        g = _simple("gated_rms_norm",
                    {"X": [o], "Gate": [z], "Scale": [gain]},
                    {"num_groups": hv, "epsilon": cfg.rms_norm_eps,
                     "gate_after": True})
    with name_scope("proj"):
        return proj(g, cfg.hidden_size, f"{prefix}_out_w", cfg)


def _attention_mixer(a, cfg, prefix, batch, max_len, row_ids, pos_ids):
    """[q | gate] = a W_q, k, v with no bias; QK-norm per head; the
    leading `rotary_dim` lanes of each q and k head turned; the full KV
    cache written at the rows' positions; causal grouped attention;
    the output gated by sigmoid(gate) before W_o."""
    nh, kvh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    with name_scope("proj"):
        qg = proj(a, 2 * nh * dh, f"{prefix}_attn_q_w", cfg)
        q = _norm(slice_last(qg, 0, nh * dh), f"{prefix}_attn_qn", cfg, dh)
        gate = slice_last(qg, nh * dh, 2 * nh * dh)
        k = _norm(proj(a, kvh * dh, f"{prefix}_attn_k_w", cfg),
                  f"{prefix}_attn_kn", cfg, dh)
        v = proj(a, kvh * dh, f"{prefix}_attn_v_w", cfg)
    caches = kv_cache(prefix, batch, max_len, kvh, dh, cfg.dtype)
    first = last = pos_ids
    if pos_ids is None:
        first = layers.fill_constant([1], "int32", 0)
        last = layers.fill_constant([1], "int32", a.shape[1] - 1)
    q, k = (rotary(x, last, dh, cfg.rope_theta, rotary_dim=cfg.rotary_dim,
                   leading=True) for x in (q, k))
    out = cached_attention(q, k, v, caches, first, row_ids, pos_ids,
                           num_heads=nh, num_kv_heads=kvh, window=0,
                           scale=1.0 / math.sqrt(dh))
    with name_scope("proj"):
        return proj(out * layers.sigmoid(gate), cfg.hidden_size,
                    f"{prefix}_attn_o_w", cfg)


class Qwen3NextDecoder(Decoder):
    """Qwen3-Next's bodies on `models/decoder.py`'s base: every layer a
    mixer of its kind, then the expert FFN."""

    prefix = FAMILY
    norm = staticmethod(_norm)
    counters_var = COUNTERS_VAR

    def body(self, ids, batch, max_len, row_ids=None, pos_ids=None):
        cfg = self.cfg
        x = embed(ids, cfg, f"{FAMILY}_embed")
        selected = []
        for i, (kind, _ffn) in enumerate(cfg.layer_kinds):
            prefix = f"{FAMILY}_l{i}"
            with name_scope(SECTIONS[kind]):
                a = _norm(x, f"{prefix}_n1", cfg)
                if kind == LINEAR:
                    m = _delta_mixer(a, cfg, prefix, batch, row_ids,
                                     decode=pos_ids is not None)
                else:
                    m = _attention_mixer(a, cfg, prefix, batch, max_len,
                                         row_ids, pos_ids)
                h = x + m
            with name_scope("moe"):
                m, sel = expert_ffn(
                    _norm(h, f"{prefix}_n2", cfg), prefix, cfg,
                    COUNTERS_VAR, expert_bias=False, shared_gate=True,
                    scoring="softmax")
                x = h + m
            selected.append(sel)
        return x, selected

    def describe(self):
        """The sizes a cost model needs (benchmark/harness/
        qwen3_next_cost.py; `layer_kinds` as pairs, so that the accepted
        expert readers apply), and the bytes of one sequence's state a
        layer of each kind."""
        cfg = self.cfg
        act = 2 if cfg.dtype == "bfloat16" else 4
        return {
            "family": FAMILY, "hidden_size": cfg.hidden_size,
            "layer_kinds": [list(k) for k in cfg.layer_kinds],
            "num_heads": cfg.num_heads, "num_kv_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "rotary_dim": cfg.rotary_dim,
            "linear_num_key_heads": cfg.linear_num_key_heads,
            "linear_num_value_heads": cfg.linear_num_value_heads,
            "linear_key_head_dim": cfg.linear_key_head_dim,
            "linear_value_head_dim": cfg.linear_value_head_dim,
            "linear_conv_kernel_dim": cfg.linear_conv_kernel_dim,
            "chunk_size": cfg.chunk_size, "num_experts": cfg.num_experts,
            "num_local_experts": cfg.num_local_experts, "top_k": cfg.top_k,
            "moe_intermediate_size": cfg.moe_intermediate_size,
            "shared_intermediate_size": cfg.shared_intermediate_size,
            "num_shared_experts": cfg.num_shared_experts,
            "vocab_size": cfg.vocab_size, "bytes_per_param": act,
            "state_bytes_per_sequence": {
                "linear": 4 * cfg.value_dim * cfg.linear_key_head_dim,
                "conv": act * (cfg.linear_conv_kernel_dim - 1)
                * cfg.conv_dim,
                "full_per_position": act * 2 * cfg.num_kv_heads
                * cfg.head_dim,
            },
        }
