"""EVA attention (EvaByte's `attention_class` "eva") for the serving path.

Per head, query t attends in ONE softmax to two key sets:

* the exact keys of its aligned window, ``{k_j : j // window == t //
  window, j <= t}``;
* one summary key per chunk of `chunk` positions in the windows before
  it, ``{ksum_c : c < (t // window) * (window // chunk)}``, with its
  summary value; a summary is the softmax-pooled chunk
  (`ops/kv_cache.py::pool_chunks`, written by `kv_index_write`).

A decode step reads the window's ring (`cache_shape(.., window)`, slots
``0 .. pos % slots`` live: the window is aligned, not sliding) and the
visible summary rows through ONE Pallas call, `kernels/eva_attention.py`
(call name `eva_decode_attention`); the CPU takes the same attention in
`jnp`. A prefill attends window by window through `ops/llm.py::
prefill_attention` (the Pallas kernel `prefill_attention` on the TPU),
each window's rows after the summaries it sees: causal attention over
``[summaries ; the window's rows]`` is exactly the one softmax over both
sets for the window's queries (the summary rows' own outputs are
dropped, as are those of the zero rows a call may end with so that the
kernel takes it in super-blocks of several blocks: `trailing_rows`).

The op adds to an int32 counters vector ``[summaries written, summary
rows read, ring rows read]`` (`COUNTERS`; a decode step's reads, a row of
K and V of one sequence, over every head); the gauge
``kernels.eva_attention.calls`` is the count of kernel calls in the decode
step lowered last, ``kernels.prefill_attention.calls`` in the prefill
(0: the `jnp` path ran).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..framework.registry import register_op
from .kv_cache import _pos_scalar, grouped_attention

COUNTERS = ("summaries_written", "summary_rows_read", "ring_rows_read")


def visible_summaries(pos, window, chunk):
    """Summary rows a query at `pos` sees: those of the windows before
    its own."""
    return (pos // window) * (window // chunk)


def decode_jnp(q, k, v, sk, sv, pos, num_kv_heads, scale, window, chunk):
    """The decode step's attention in `jnp`: q [B, nh * dh] at `pos` over
    the ring k, v [B, slots, nkv * dh] (slots 0 .. pos % slots live) and
    the summaries sk, sv [B, rows, nkv * dh] (the visible ones)."""
    slots, rows = k.shape[1], sk.shape[1]
    valid = jnp.concatenate([
        jnp.arange(slots) <= jnp.mod(pos, slots),
        jnp.arange(rows) < visible_summaries(pos, window, chunk)])
    return grouped_attention(
        q[:, None], jnp.concatenate([k, sk], 1), jnp.concatenate([v, sv], 1),
        valid[None], num_kv_heads, scale)[:, 0]


def decode(q, k, v, sk, sv, pos, num_kv_heads, scale, window, chunk,
           interpret=False):
    """On the TPU (and with `interpret`) the Pallas kernel, elsewhere
    `decode_jnp`. Returns (out [B, nh * dh], whether the kernel ran)."""
    if interpret or jax.default_backend() == "tpu":
        from ..kernels import eva_attention as _kernel

        return _kernel.attend(
            q, k, v, sk, sv, pos, num_kv_heads=num_kv_heads, scale=scale,
            window=window, chunk=chunk, interpret=interpret), True
    return decode_jnp(q, k, v, sk, sv, pos, num_kv_heads, scale, window,
                      chunk), False


def trailing_rows(length):
    """Rows of padding after a window's own that let the prefill kernel
    cut a call of `length` rows into super-blocks of at least four
    blocks: a length of a prime number of blocks (the 2,176 rows of a
    second window after its 128 summaries) would take one-block
    super-blocks, the kernel's slow form. No earlier query sees a
    trailing row."""
    from ..kernels.prefill_attention import BLOCK, super_block

    pad = 0
    while not length % BLOCK and \
            super_block(length + pad) < min(length, 4 * BLOCK):
        pad += BLOCK
    return pad


def prefill(q, k, v, sk, sv, num_heads, num_kv_heads, scale, window, chunk):
    """A prompt's attention from position 0: q [R, S, nh * dh], k, v
    [R, S, nkv * dh] the call's own rows, sk, sv [R, rows, nkv * dh] the
    summaries of its complete chunks. Window w's queries attend causally
    over [the w * window / chunk summaries before it ; its own rows] (and
    `trailing_rows` of zeros after them)."""
    from .llm import prefill_attention

    r, s, _ = q.shape
    per = window // chunk
    outs, kernels = [], 0
    for w, lo in enumerate(range(0, s, window)):
        hi = min(s, lo + window)
        n = w * per
        pad = trailing_rows(n + hi - lo)

        def rows(x, lead):
            zeros = (r, pad, x.shape[2])
            return jnp.concatenate(
                [lead, x[:, lo:hi], jnp.zeros(zeros, x.dtype)], 1)

        qw, kw, vw = (rows(x, lead) for x, lead in (
            (q, jnp.zeros((r, n, q.shape[2]), q.dtype)), (k, sk[:, :n]),
            (v, sv[:, :n])))
        out, kernel = prefill_attention(qw, kw, vw, num_heads, num_kv_heads,
                                        scale)
        outs.append(out[:, n:n + hi - lo])
        kernels += kernel
    return (outs[0] if len(outs) == 1 else jnp.concatenate(outs, 1)), kernels


@register_op(
    "eva_attention",
    inputs=["Q", "K", "V", "SumK", "SumV", "Pos", "Row", "Counters"],
    outputs=["Out", "CountersOut"],
    differentiable=False,
    mutates=(("CountersOut", "Counters"),),
)
def _eva_attention(ctx, op, ins):
    """A prefill (`decode` False: K and V the call's own rows [R, S, ..]
    from position 0, rows `Row` .. of the batch's summaries `SumK`,
    `SumV` [B, rows, ..], already written) or a decode step (`decode`:
    K and V the window's ring after the step's write, `Pos` the step's
    position). `num_heads`, `num_kv_heads`, `scale`, `window`, `chunk`.
    Out is [.., T, nh * dh] in Q's dtype."""
    from .. import observability as _obs

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    sk, sv = ins["SumK"][0], ins["SumV"][0]
    counters = ins["Counters"][0]
    nh, kvh = int(op.attr("num_heads")), int(op.attr("num_kv_heads"))
    scale = float(op.attr("scale"))
    window, chunk = int(op.attr("window")), int(op.attr("chunk"))
    if not op.attr("decode", False):
        r, s = q.shape[:2]
        if ins.get("Row"):
            r0 = _pos_scalar(ins["Row"][0])
            sk, sv = (jax.lax.dynamic_slice_in_dim(x, r0, r, axis=0)
                      for x in (sk, sv))
        out, kernels = prefill(q, k, v, sk, sv, nh, kvh, scale, window,
                               chunk)
        if ctx is not None and not ctx.abstract:
            ctx.prefill_attention_calls = kernels + getattr(
                ctx, "prefill_attention_calls", 0)
            _obs.set_gauge("kernels.prefill_attention.calls",
                           ctx.prefill_attention_calls)
        written = jnp.array([r * (s // chunk), 0, 0], counters.dtype)
        return {"Out": [out], "CountersOut": [counters + written]}
    pos = _pos_scalar(ins["Pos"][0])
    out, kernel = decode(q[:, 0], k, v, sk, sv, pos, kvh, scale, window,
                         chunk)
    b = q.shape[0]
    read = jnp.stack([
        jnp.where(jnp.mod(pos, chunk) == chunk - 1, b, 0),
        b * visible_summaries(pos, window, chunk),
        b * (jnp.mod(pos, k.shape[1]) + 1)]).astype(counters.dtype)
    if ctx is not None and not ctx.abstract:
        # one EmitContext a lowered step: the last call leaves the count
        ctx.eva_attention_calls = kernel + getattr(
            ctx, "eva_attention_calls", 0)
        _obs.set_gauge("kernels.eva_attention.calls", ctx.eva_attention_calls)
    return {"Out": [out[:, None]], "CountersOut": [counters + read]}
