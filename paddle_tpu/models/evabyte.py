"""EvaByte decoder (`model_type` "evabyte") for the serving path: a
byte-level dense pre-norm stack whose attention is EVA's, exact inside
an aligned window and over one learned summary per chunk before it.

    x0 = E[ids]                         (no scale), carried in float32
    every layer:  h = x + Attn(N(x));  y = h + FFN(N(h))
    logits = N(y_last) W_head           (untied, float32 out)
    N(x) = x / sqrt(mean(x^2) + eps) * (1 + w)        (unit-offset gain)
    FFN(a) = W_d (silu(W_g a) * W_u a)
    No projection carries a bias.

The residual stream x, h, y is float32 (EvaByte's `fp32_skip_add`): the
branches run in `cfg.dtype` (bfloat16 in serving) and are added in
float32. The logits leave the head in float32 (`fp32_logits`).

EVA attention, per head h of H, d lanes, window W, chunk C (Zheng et
al., "Efficient Attention via Control Variates", ICLR 2023; the EvaByte
release, 2025):
    q_t = RoPE(a W_q), k_t = RoPE(a W_k) (rotate-half over all d lanes),
    v_t = a W_v
    windows and chunks are aligned: w(t) = t // W, chunk c holds
    positions c C .. c C + C - 1
    ksum_c = sum_{j in c} softmax_j(mu_h . k_j) k_j
    vsum_c = sum_{j in c} softmax_j(phi_h . k_j) v_j
        (mu_h, phi_h learned per head: EvaByte's `adaptive_mu_k`,
        `adaptive_phi`; float32 logits, no scale)
    o_t = softmax over [q_t . k_j / sqrt(d) for j in w(t)'s window, j <= t]
          and [q_t . ksum_c / sqrt(d) for c < w(t) W / C], ONE softmax,
          times [v_j | vsum_c]
    Attn = W_o o
Scores and softmax in float32 (`mixedp_attn`).

Each layer carries its window's ring of K and V (`kv_cache(..,
window)`, kind "window": position p in slot p % W, the live slots
0 .. p % W since the window is aligned) and the summaries of its
complete chunks (kind "summary", `[B, max_len / C, H d]` each for K and
V), written by `kv_index_write` with softmax pooling as each chunk
closes: the prompt's in a prefill, a row in the decode step whose
position ends a chunk. A summary is written at once and seen from the
next window on. `ops/eva.py` attends over both; its counters are the
summaries written and the rows a decode step's attention reads.
"""

from __future__ import annotations

import math

from .. import layers
from ..framework.program import default_main_program, name_scope
from ..ops.eva import COUNTERS
from .decoder import (
    Decoder, cache_rows, embed, kv_cache, normal, param, pool_rows, proj, rms,
    rotary, state, swiglu_ffn,
)

FAMILY = "evabyte"
COUNTERS_VAR = "evabyte_eva_counters"


class EvaByteConfig:
    def __init__(
        self,
        vocab_size=320,
        hidden_size=4096,
        num_layers=32,
        num_heads=32,
        head_dim=128,
        intermediate_size=11008,
        rope_theta=100000.0,
        rms_norm_eps=1e-5,
        window_size=2048,
        chunk_size=16,
        initializer_range=0.01275,
        pool_std=0.25,
        dtype="bfloat16",
        prefill_rows=None,
    ):
        if window_size % chunk_size:
            raise ValueError("a window must be whole chunks")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size
        self.rope_theta = rope_theta
        self.rms_norm_eps = rms_norm_eps
        self.window_size = window_size
        self.chunk_size = chunk_size
        # initialisations only: the spread of every projection, the
        # embedding, the head and the norms' offsets w, and of the pooling
        # vectors mu and phi (a temperature: near zero a chunk's softmax
        # is its mean)
        self.initializer_range = initializer_range
        self.pool_std = pool_std
        self.dtype = dtype
        # rows of the batch one prefill dispatch takes (None: all of them)
        self.prefill_rows = prefill_rows

    @classmethod
    def tiny(cls, **kw):
        return cls(**{**dict(
            hidden_size=64, num_layers=2, num_heads=4, head_dim=16,
            intermediate_size=128, window_size=16, chunk_size=4,
        ), **kw})


def _norm(x, name, cfg):
    """N over the float32 stream, out in the branches' dtype."""
    return layers.cast(rms(x, name, cfg, unit_offset=True), cfg.dtype)


def _attention(a, cfg, prefix, batch, max_len, row_ids, first, last,
               pos_ids):
    """q, k, v with rotary positions on q and k; the ring written at the
    rows' positions, the closed chunks' summaries after it; EVA attention
    over the window and the summaries; W_o."""
    nh, dh = cfg.num_heads, cfg.head_dim
    width, decode = nh * dh, pos_ids is not None
    with name_scope("proj"):
        q = proj(a, width, f"{prefix}_attn_q_w", cfg)
        k = proj(a, width, f"{prefix}_attn_k_w", cfg)
        v = proj(a, width, f"{prefix}_attn_v_w", cfg)
        q, k = (rotary(t, last, dh, cfg.rope_theta) for t in (q, k))
    ring = kv_cache(prefix, batch, max_len, nh, dh, cfg.dtype,
                    cfg.window_size)
    summaries = summary_state(prefix, batch, max_len, cfg)
    cache_rows(ring, k, v, first, row_ids)
    pooling = [param(f"{prefix}_attn_{n}", [nh, dh], cfg,
                     normal(cfg, std=cfg.pool_std)) for n in ("mu", "phi")]
    keys, values = ring if decode else (k, v)
    c = cfg.chunk_size
    with name_scope("summary"):
        for index, mu, pooled in zip(summaries, pooling, (None, values)):
            pool_rows(index, keys, first, row_ids, carry=decode, v=pooled,
                      mu=mu, kernel=c, stride=c, pooling="softmax")
    counters = state(COUNTERS_VAR, (len(COUNTERS),), "int32")
    blk = default_main_program().global_block
    out = blk.create_var(name=f"{prefix}_attn_out", shape=q.shape,
                         dtype=q.dtype)
    ins = {"Q": [q.name], "K": [keys.name], "V": [values.name],
           "SumK": [summaries[0].name], "SumV": [summaries[1].name],
           "Pos": [first.name], "Counters": [counters.name]}
    if row_ids is not None:
        ins["Row"] = [row_ids.name]
    with name_scope("core"):
        blk.append_op(
            "eva_attention", ins,
            {"Out": [out.name], "CountersOut": [counters.name]},
            {"num_heads": nh, "num_kv_heads": nh,
             "scale": 1.0 / math.sqrt(dh), "window": cfg.window_size,
             "chunk": c, "decode": decode})
    with name_scope("proj"):
        return proj(out, cfg.hidden_size, f"{prefix}_attn_o_w", cfg)


def summary_state(prefix, batch, max_len, cfg):
    """A layer's summary keys and values, a row per chunk of `max_len`."""
    from ..ops.kv_cache import index_shape

    shape = index_shape(batch, max_len, cfg.chunk_size, cfg.num_heads,
                        cfg.head_dim)
    return tuple(state(f"{prefix}_summary_{which}", shape, cfg.dtype,
                       "summary") for which in ("k", "v"))


class EvaByteDecoder(Decoder):
    """EvaByte's bodies on `models/decoder.py`'s base: the next-byte head
    alone (the further prediction heads of self-speculative decoding are
    not served); no layer routes. Its counters are EVA attention's."""

    prefix = FAMILY
    norm = staticmethod(_norm)
    counters_var = COUNTERS_VAR
    counter_names = tuple(f"eva.{n}" for n in COUNTERS)
    counter_gauges = frozenset()

    def body(self, ids, batch, max_len, row_ids=None, pos_ids=None):
        cfg = self.cfg
        x = embed(ids, cfg, f"{FAMILY}_embed")
        with name_scope("embed"):
            x = layers.cast(x, "float32")
        first = last = pos_ids
        if pos_ids is None:
            # the prefill's first row, and its last (the rotary
            # positions' anchor, as the op takes it)
            with name_scope("attn"):
                first = layers.fill_constant([1], "int32", 0)
                last = layers.fill_constant([1], "int32", ids.shape[1] - 1)
        for i in range(cfg.num_layers):
            prefix = f"{FAMILY}_l{i}"
            with name_scope("attn"):
                m = _attention(_norm(x, f"{prefix}_n1", cfg), cfg, prefix,
                               batch, max_len, row_ids, first, last,
                               pos_ids)
                h = x + layers.cast(m, "float32")
            with name_scope("mlp"):
                m = swiglu_ffn(_norm(h, f"{prefix}_n2", cfg),
                               cfg.intermediate_size, f"{prefix}_mlp", cfg)
                x = h + layers.cast(m, "float32")
        return x, []

    def describe(self):
        """The sizes a cost model needs (benchmark/harness/
        evabyte_cost.py)."""
        cfg = self.cfg
        return {
            "family": FAMILY, "hidden_size": cfg.hidden_size,
            "num_layers": cfg.num_layers, "num_heads": cfg.num_heads,
            "head_dim": cfg.head_dim,
            "intermediate_size": cfg.intermediate_size,
            "vocab_size": cfg.vocab_size, "window_size": cfg.window_size,
            "chunk_size": cfg.chunk_size,
            "bytes_per_param": 2 if cfg.dtype == "bfloat16" else 4,
        }
