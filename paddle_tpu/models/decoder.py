"""The vocabulary the serving decoders share, and their base class.

A serving family (`models/afmoe.py`, `nemotron_h.py`, `dots_vlm.py`,
`qwen3_next.py`, `minicpm_sala.py`) is its config, its mixers, its block
loop (`Decoder.body`), its head's norm and scale and `describe()`, built
from the pieces here:

* parameters, projections and RMSNorm (`normal`, `param`, `proj`, `rms`);
* the FFNs: `swiglu_ffn`, and `expert_ffn` over `route_experts` (one
  op for this chip's routed experts, with the step counters it writes);
* the prologue and the epilogue: `embed`, `head`, `slice_last`,
  `rotary`, and `side_by_side` (the expert layers' selected ids as one
  extra fetch);
* the caches: `kv_cache` declares a layer's K and V caches, `write_cache`
  writes rows into one, and `cached_attention` is the core a family's
  attention mixer calls (`cache_rows` then `attend`); `pool_rows`
  writes rows pooled from complete windows of keys or values (a
  compressed-key index, chunk summaries);
* start-up initialisers: `StartupChain`, `dt_bias_init`.

State is declared ONCE, where a body creates it: `state(name, shape,
dtype, kind, lanes)` is the persistable both serving programs share by
name, with its kind and, for a padded cache row, the lanes that carry
data. `serving.GPTGenerator` reads back what its two programs
declared (`declared_state`): what `reset()` zeroes, the
`kv_cache.bytes.<kind>` gauges and the slots a decode step may read.

Parameters, activations and caches are `cfg.dtype` (bfloat16 in
serving); norms, softmax and routers keep float32 statistics inside their
ops, and the logits leave the head in float32.
"""

from __future__ import annotations

import math

from .. import layers
from ..framework.program import default_main_program, name_scope
from ..initializer import Initializer, Normal, Uniform
from ..layers.helper import LayerHelper
from ..layers.tensor import _simple
from ..param_attr import ParamAttr

# a layer's FFN: dense SwiGLU, or routed experts
DENSE, EXPERTS = "dense", "experts"


def normal(cfg, mean=0.0, std=None):
    return Normal(mean, cfg.initializer_range if std is None else std)


def param(name, shape, cfg, init, dtype=None):
    return LayerHelper("decoder").create_parameter(
        ParamAttr(name=name, initializer=init), list(shape),
        dtype or cfg.dtype,
    )


def proj(x, size, name, cfg, init=None):
    return layers.fc(
        x, size=size, num_flatten_dims=2, bias_attr=False,
        param_attr=ParamAttr(name=name, initializer=init or normal(cfg)),
    )


def rms(x, name, cfg, width=None, seeded=1.0, unit_offset=False):
    """RMSNorm with a learned gain over `width` (the hidden size, or one
    head's width: QK-norm). The gain applied is seeded near `seeded`;
    with `unit_offset` the stored gain w is applied as 1 + w (a family
    that learns the gain's distance from one)."""
    attrs = {"epsilon": cfg.rms_norm_eps}
    mean = seeded
    if unit_offset:
        attrs["unit_offset"], mean = True, seeded - 1.0
    gain = param(name, [width or x.shape[-1]], cfg,
                 normal(cfg, mean, seeded * cfg.initializer_range))
    return _simple("rms_norm", {"X": [x], "Scale": [gain]}, attrs)


def slice_last(x, start, end):
    """Lanes `start` .. `end` of the last axis."""
    return layers.slice(x, [2], [start], [end])


def rotary(x, pos, head_dim, theta, **attrs):
    """Rotary positions on each `head_dim`-wide head of `x`, the rows'
    last at `pos`; `attrs` as `rotary_embedding` takes them (which lanes
    turn, YaRN's frequencies)."""
    return _simple("rotary_embedding", {"X": [x], "Pos": [pos]},
                   {"head_dim": head_dim, "theta": theta, **attrs})


def swiglu_ffn(x, width, prefix, cfg):
    gate_up = proj(x, 2 * width, f"{prefix}_gate_up_w", cfg)
    return proj(_simple("swiglu", {"X": [gate_up]}, {}), cfg.hidden_size,
                f"{prefix}_down_w", cfg)


def state(name, shape, dtype, kind=None, lanes=None):
    """A persistable both serving programs share by name (a cache, a
    recurrent state, the counters): declared once per program, zeroed by
    `GPTGenerator.reset()`. `kind` says what per-sequence state it is: a
    K or V cache of a "full" or a "window" layer, a "latent"-attention
    layer's one cache, an "ssm" or a "linear"-attention layer's
    recurrent state, a "conv" tail, a block-sparse layer's compressed-key
    "index", an EVA layer's chunk "summary" keys or values (None: other
    state, such as the step counters); `lanes`, the lanes of a padded
    row that carry data."""
    blk = default_main_program().global_block
    if blk.has_var(name):
        return blk.var(name)
    var = blk.create_var(name=name, shape=shape, dtype=dtype,
                         persistable=True)
    var.state_kind, var.state_lanes = kind, lanes
    return var


def declared_state(*programs):
    """{name: (shape, dtype, kind, lanes)} of what `programs` declared
    with `state`. A name declared twice must be declared alike."""
    from ..errors import InvalidArgumentError

    found = {}
    for program in programs:
        for var in program.global_block.vars.values():
            if not hasattr(var, "state_kind"):
                continue
            spec = (var.shape, var.dtype, var.state_kind, var.state_lanes)
            if found.setdefault(var.name, spec) != spec:
                raise InvalidArgumentError(
                    f"state {var.name!r} declared as {found[var.name]} and "
                    f"as {spec}")
    return found


def route_experts(x, prefix, cfg, ins, **route_attrs):
    """This chip's routed experts over `x` as ONE `moe_local_experts` op
    (router, top-k, dispatch, the grouped products and the combine: the
    emitter's own scopes `moe_router`, `moe_dispatch`, `moe_experts`,
    `moe_combine` tell them apart beneath this one). `ins`: the op's
    weights and its `Counters`, which it adds to; `route_attrs`: further
    attributes (a family's selection, scoring, activation). Returns
    (output, the op's `Selected` ids [B, T, k])."""
    from ..framework import unique_name

    blk = default_main_program().global_block
    routed = blk.create_var(name=unique_name.generate(f"{prefix}_routed"),
                            shape=x.shape, dtype=x.dtype)
    selected = blk.create_var(
        name=f"{prefix}_selected", shape=tuple(x.shape[:2]) + (cfg.top_k,),
        dtype="int32",
    )
    with name_scope("experts"):
        blk.append_op(
            "moe_local_experts", {"X": [x.name], **ins},
            {"Out": [routed.name], "Selected": [selected.name],
             "CountersOut": ins["Counters"]},
            {"top_k": cfg.top_k, "route_scale": cfg.route_scale,
             "route_norm": cfg.route_norm,
             "expert_offset": cfg.expert_offset, **route_attrs},
        )
    return routed, selected


def expert_ffn(x, prefix, cfg, counters_var, expert_bias=True,
               shared_gate=False, **route_attrs):
    """Shared expert (every chip computes it) + this chip's routed SwiGLU
    experts (`route_experts`). A family without the selection's bias
    buffer says `expert_bias` False; with `shared_gate` the shared expert
    is scaled by sigmoid(x w), a gate of its own. The op adds to the
    int32 step counters `counters_var`.
    Returns (output, the op's `Selected` ids [B, T, k])."""
    from ..parallel.moe import MOE_COUNTERS

    h, f = cfg.hidden_size, cfg.moe_intermediate_size
    e_local = cfg.num_local_experts
    router_w = param(f"{prefix}_router_w", [h, cfg.num_experts], cfg,
                     normal(cfg))
    ins = {"RouterW": [router_w.name]}
    if expert_bias:
        # a buffer, not a weight: moves the selection only; float32,
        # seeded small and non-zero so that it is exercised
        bias = param(f"{prefix}_expert_bias", [cfg.num_experts], cfg,
                     normal(cfg, std=cfg.expert_bias_std), dtype="float32")
        ins["ExpertBias"] = [bias.name]
    w_gate_up = param(f"{prefix}_experts_gate_up_w", [e_local, h, 2 * f],
                      cfg, normal(cfg))
    w_down = param(f"{prefix}_experts_down_w", [e_local, f, h], cfg,
                   normal(cfg))
    counters = state(counters_var, (len(MOE_COUNTERS),), "int32")
    routed, selected = route_experts(
        x, prefix, cfg, {**ins, "WGateUp": [w_gate_up.name],
                         "WDown": [w_down.name],
                         "Counters": [counters.name]},
        **route_attrs)
    if cfg.num_shared_experts:
        with name_scope("shared"):
            shared = swiglu_ffn(
                x, f * cfg.num_shared_experts, f"{prefix}_shared", cfg
            )
            if shared_gate:
                shared = shared * layers.sigmoid(
                    proj(x, 1, f"{prefix}_shared_gate_w", cfg))
            routed = routed + shared
    return routed, selected


def embed(ids, cfg, name, scale=None):
    """The prologue: `ids` [rows, T] through the table `name`, as
    [rows, T, H], times `scale` where one is given."""
    with name_scope("embed"):
        x = layers.embedding(
            ids, size=[cfg.vocab_size, cfg.hidden_size], dtype=cfg.dtype,
            param_attr=ParamAttr(name=name, initializer=normal(cfg)),
        )
        x = layers.reshape(x, [ids.shape[0], ids.shape[1], cfg.hidden_size])
        if scale is not None:
            x = layers.scale(x, scale=scale)
        return x


def head(x, cfg, prefix, norm=rms, scale=None):
    """Final norm (`norm`: a family's own, `rms` by default), times
    `scale` where one is given, then the untied head over the vocabulary
    held here; float32 out of the product (not a rounded bfloat16 cast
    up)."""
    with name_scope("head"):
        x = norm(x, f"{prefix}_norm_f", cfg)
        if scale is not None:
            x = layers.scale(x, scale=scale)
        w = param(f"{prefix}_head_w", [cfg.hidden_size, cfg.vocab_size],
                  cfg, normal(cfg))
        return _simple("mul", {"X": [x], "Y": [w]},
                       {"x_num_col_dims": 2, "y_num_col_dims": 1,
                        "out_dtype": "float32"})


def side_by_side(selected):
    """The expert layers' `Selected` ids as one variable (one fetch a
    step beside the logits), or None where no layer routes."""
    if not selected:
        return None
    with name_scope("head"):
        return selected[0] if len(selected) == 1 \
            else layers.concat(selected, axis=-1)


def kv_cache(prefix, batch, max_len, heads, head_dim, dtype, window=0):
    """A layer's K and V caches (`ops/kv_cache.py::cache_shape`: a ring
    of the window's slots where `window` binds), kind "window" or
    "full"."""
    from ..ops.kv_cache import cache_shape

    shape = cache_shape(batch, max_len, heads, head_dim, window)
    kind = "window" if window else "full"
    return tuple(state(f"{prefix}_cache_{which}", shape, dtype, kind)
                 for which in ("k", "v"))


def write_cache(cache, rows, pos, row, ring):
    ins = {"Cache": [cache.name], "X": [rows.name], "Pos": [pos.name]}
    if row is not None:
        ins["Row"] = [row.name]
    default_main_program().global_block.append_op(
        "kv_cache_write", ins, {"Out": [cache.name]}, {"ring": bool(ring)}
    )


def cache_rows(caches, k, v, at, row_ids=None):
    """A call's K and V rows into the layer's `caches` from position
    `at` (rows `row_ids` .. of the batch in a prefill block)."""
    with name_scope("core"):
        for cache, rows in zip(caches, (k, v)):
            write_cache(cache, rows, at, row_ids, ring=True)


def attend(q, k, v, caches, pos_ids, **attrs):
    """Grouped attention: causal over the call's own rows in a prefill
    (`pos_ids` None), over the cached ones up to `pos_ids` in a decode
    step; `attrs` (heads, window, scale) as the ops take them."""
    with name_scope("core"):
        if pos_ids is None:
            return _simple("causal_gqa_attention",
                           {"Q": [q], "K": [k], "V": [v]}, attrs)
        ck, cv = caches
        return _simple(
            "kv_cache_attention",
            {"Q": [q], "CacheK": [ck], "CacheV": [cv], "Pos": [pos_ids]},
            attrs)


def pool_rows(index, k, at, row_ids, carry, v=None, mu=None, **attrs):
    """Write into `index` the rows pooled from complete windows of `k`
    (`kv_index_write`: the call's own keys in a prefill, the cache after
    the step's write with `carry`), of the values `v` where they are
    given; `mu` the softmax pooling's vector where `attrs` says
    `pooling` "softmax" (`kernel`, `stride` as the op takes them)."""
    ins = {"Index": [index.name], "K": [k.name], "Pos": [at.name]}
    if row_ids is not None:
        ins["Row"] = [row_ids.name]
    for slot, var in (("V", v), ("Mu", mu)):
        if var is not None:
            ins[slot] = [var.name]
    default_main_program().global_block.append_op(
        "kv_index_write", ins, {"IndexOut": [index.name]},
        {**attrs, "carry": bool(carry)})


def cached_attention(q, k, v, caches, at, row_ids, pos_ids, **attrs):
    """The attention core of a layer with a K and a V cache: the rows
    written at `at` (the caller's position variable: the prefill's first
    position, or the decode step's `pos_ids`), then `attend`."""
    cache_rows(caches, k, v, at, row_ids)
    return attend(q, k, v, caches, pos_ids, **attrs)


class StartupChain(Initializer):
    """A parameter drawn uniformly and pushed through a chain of
    element-wise startup ops: [(op type, attrs)], each reading what the
    one before wrote."""

    def __init__(self, low, high, chain):
        self.low, self.high, self.chain = low, high, chain

    def __call__(self, block, name, shape, dtype):
        Uniform(self.low, self.high)(block, name, shape, dtype)
        for op_type, attrs in self.chain:
            block.append_op(op_type, {"X": [name]}, {"Out": [name]}, attrs)


def dt_bias_init(cfg):
    """The inverse softplus of a step size drawn log-uniformly between
    the config's `time_step_min` and `_max` and floored at `_floor`:
    softplus(dt_bias) is that step size. softplus^-1(t) = log(e^t - 1)."""
    lo, hi, floor = cfg.time_step
    return StartupChain(math.log(lo), math.log(hi), [
        ("exp", {}), ("clip", {"min": floor, "max": 1e30}),
        ("exp", {}), ("scale", {"scale": 1.0, "bias": -1.0}), ("log", {}),
    ])


class Decoder:
    """What `serving.GPTGenerator` asks of a decoder. A family gives its
    config (`prefill_rows`: rows of the batch one prefill dispatch
    takes, None for all), `prefix` (its parameters' and state's names),
    `body`, its head's `norm` and `head_scale`, and `describe()` (the
    sizes a cost model reads). Its expert layers add to the int32 vector
    `counters_var`, read once a batch under `counter_names`."""

    prefix = None
    norm = staticmethod(rms)
    head_scale = None
    counters_var = None
    # the fullest expert's rows in one call: a maximum, not a sum
    counter_gauges = frozenset({"moe.max_expert_load"})

    def __init__(self, cfg):
        self.cfg = cfg
        self.prefill_rows = cfg.prefill_rows

    @property
    def counter_names(self):
        from ..parallel.moe import MOE_COUNTERS

        return tuple(f"moe.{name}" for name in MOE_COUNTERS)

    def body(self, ids, batch, max_len, row_ids=None, pos_ids=None):
        """Both bodies: a prefill of `ids` [rows, S] (rows `row_ids` ..
        of the batch) without `pos_ids`, a decode step of [B, 1] at
        `pos_ids` with. Returns (hidden [.., H], [the expert layers'
        Selected ids])."""
        raise NotImplementedError

    def prefill(self, context_ids, batch, max_len, row_ids=None):
        """(last-position logits [rows, 1, V] float32, [the expert
        layers' `Selected` ids side by side, [rows, S, layers * k]], or
        no extras where no layer routes). The ids are fetched with the
        logits so that a check against a reference follows the routing
        of the executables that serve."""
        x, selected = self.body(context_ids, batch, max_len, row_ids)
        s = context_ids.shape[1]
        with name_scope("head"):
            x = layers.slice(x, [1], [s - 1], [s])
        return self._logits(x), self._extras(selected)

    def decode_step(self, token_ids, pos_ids, max_len):
        """(logits [B, 1, V] float32, the extras as `prefill`'s) of one
        token a row at runtime position `pos_ids` ([1, 1] int64)."""
        x, selected = self.body(token_ids, token_ids.shape[0], max_len,
                                pos_ids=pos_ids)
        return self._logits(x), self._extras(selected)

    def _logits(self, x):
        return head(x, self.cfg, self.prefix, self.norm, self.head_scale)

    @staticmethod
    def _extras(selected):
        ids = side_by_side(selected)
        return [] if ids is None else [ids]
