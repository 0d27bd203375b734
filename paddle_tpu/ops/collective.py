"""Collective communication ops.

TPU-native replacement for the reference's NCCL collective ops
(operators/collective/c_allreduce_op.h:33-112, c_broadcast_op, c_allgather_op,
c_reducescatter_op, collective_helper.h): each op emits an XLA collective
(psum/all_gather/psum_scatter/ppermute/all_to_all). Under the Executor's SPMD
mode the block runs inside jax.shard_map over a Mesh, so these lower to ICI
collectives; ring construction/topology is XLA's job (no ring_id/comm maps).

Outside a mesh (single-chip run) every collective degrades to identity /
no-op, which is also the reference's nranks==1 behavior.

The reference's ring_id attr maps to our "axis_name" attr (default "dp"): a
named mesh axis replaces a communicator ring. c_sync_*_stream ops are no-ops:
XLA's dataflow ordering replaces stream synchronization.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..framework.registry import register_op


def _axis(ctx, op):
    """Mesh axis this collective runs over, or None when not under shard_map."""
    name = op.attr("axis_name", "dp")
    return name if name in ctx.mesh_axes else None


def _record(kind, x, ax):
    """Count the collective and its per-shard payload bytes by kind.

    Emitters run at TRACE time, so these counters advance once per program
    compile (per collective op in the block), not once per device step —
    the right granularity for "how much ICI traffic does one step issue",
    since the compiled step replays the same collectives every run."""
    if ax is None:
        return
    from .. import observability as _obs
    from ..resilience.faults import fault_point

    # chaos seam: an armed "collective.dispatch" fault aborts the trace,
    # modeling a peer dropping out mid-compile (EQuARX-style collective
    # layer failures); surfaced to the Executor as a typed error
    fault_point("collective.dispatch")
    _obs.add(f"collective.{kind}")
    try:
        nbytes = int(x.size) * x.dtype.itemsize
    except (AttributeError, TypeError):
        return
    _obs.add(f"collective.{kind}.bytes", nbytes)


def _register_allreduce(op_type, reducer):
    @register_op(op_type, inputs=["X"], outputs=["Out"], differentiable=False)
    def emit(ctx, op, ins):
        x = ins["X"][0]
        ax = _axis(ctx, op)
        _record(op_type, x, ax)
        return {"Out": [x if ax is None else reducer(x, ax)]}

    return emit


_register_allreduce("c_allreduce_sum", lambda x, ax: lax.psum(x, ax))
_register_allreduce("c_allreduce_max", lambda x, ax: lax.pmax(x, ax))
_register_allreduce("c_allreduce_min", lambda x, ax: lax.pmin(x, ax))
_register_allreduce(
    "c_allreduce_prod", lambda x, ax: jnp.exp(lax.psum(jnp.log(x), ax))
)
_register_allreduce("allreduce", lambda x, ax: lax.psum(x, ax))


@register_op("mp_allreduce_sum", inputs=["X"], outputs=["Out"])
def _mp_allreduce_sum(ctx, op, ins):
    """DIFFERENTIABLE in-graph allreduce (reference
    operators/collective/c_allreduce_op.h with use_model_parallel — the
    forward-graph allreduce of tensor/sequence parallelism, unlike
    c_allreduce_sum which the transpilers append post-backward). Under
    shard_map psum transposes to psum, so each replica's unit cotangent
    would arrive axis_size-fold; the correction keeps the forward value
    while scaling the cotangent down (same trick as pipeline.py:196)."""
    x = ins["X"][0]
    ax = _axis(ctx, op)
    _record("mp_allreduce_sum", x, ax)
    if ax is None:
        return {"Out": [x]}
    n = ctx.axis_sizes[ax]
    total = lax.psum(x, ax)
    return {"Out": [total / n + lax.stop_gradient(total * (n - 1) / n)]}


@register_op("c_broadcast", inputs=["X"], outputs=["Out"], differentiable=False)
def _c_broadcast(ctx, op, ins):
    x = ins["X"][0]
    ax = _axis(ctx, op)
    _record("c_broadcast", x, ax)
    if ax is None:
        return {"Out": [x]}
    root = op.attr("root", 0)
    idx = lax.axis_index(ax)
    src = jnp.where(idx == root, x, jnp.zeros_like(x))
    return {"Out": [lax.psum(src, ax)]}


@register_op("c_allgather", inputs=["X"], outputs=["Out"], differentiable=False)
def _c_allgather(ctx, op, ins):
    x = ins["X"][0]
    ax = _axis(ctx, op)
    _record("c_allgather", x, ax)
    if ax is None:
        return {"Out": [x]}
    out = lax.all_gather(x, ax)  # [nranks, ...]
    return {"Out": [out.reshape((-1,) + x.shape[1:])]}


@register_op(
    "c_reducescatter", inputs=["X"], outputs=["Out"], differentiable=False
)
def _c_reducescatter(ctx, op, ins):
    x = ins["X"][0]
    ax = _axis(ctx, op)
    _record("c_reducescatter", x, ax)
    if ax is None:
        return {"Out": [x]}
    return {"Out": [lax.psum_scatter(x, ax, scatter_dimension=0, tiled=True)]}


@register_op("alltoall", inputs=["X"], outputs=["Out"], differentiable=False)
def _alltoall(ctx, op, ins):
    x = ins["X"][0]
    ax = _axis(ctx, op)
    _record("alltoall", x, ax)
    if ax is None:
        return {"Out": [x]}
    n = lax.axis_size(ax)
    xs = x.reshape((n, x.shape[0] // n) + x.shape[1:])
    out = lax.all_to_all(xs, ax, split_axis=0, concat_axis=0, tiled=False)
    return {"Out": [out.reshape(x.shape)]}


@register_op(
    "collective_permute", inputs=["X"], outputs=["Out"], differentiable=False
)
def _collective_permute(ctx, op, ins):
    x = ins["X"][0]
    ax = _axis(ctx, op)
    _record("collective_permute", x, ax)
    if ax is None:
        return {"Out": [x]}
    n = lax.axis_size(ax)
    shift = op.attr("shift", 1)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return {"Out": [lax.ppermute(x, ax, perm)]}


@register_op(
    "c_allreduce_any", inputs=["X"], outputs=["Out"], differentiable=False
)
def _c_allreduce_any(ctx, op, ins):
    """Cross-rank logical OR (max over int cast) — the AMP FoundInfinite
    reduction of the sharded weight update: after a reduce-scatter each
    rank checks finiteness of only ITS 1/N grad shard, so the loss-scale
    automaton must see "any rank saw a non-finite" or the ranks' scales
    silently diverge (the ZeRO analog of the reference's nccl allreduce
    on found_inf)."""
    x = ins["X"][0]
    ax = _axis(ctx, op)
    _record("c_allreduce_any", x, ax)
    if ax is None:
        return {"Out": [x]}
    return {"Out": [lax.pmax(x.astype(jnp.int32), ax).astype(x.dtype)]}


# ---------------------------------------------------------------------------
# ZeRO-style weight-update sharding collectives (arXiv:2004.13336) with an
# opt-in EQuARX-style block-quantized wire format (arXiv:2506.17615).
#
# Data layout contract (parallel/transpiler.py ShardedWeightUpdate is the
# only producer): gradients/optimizer state travel as FLAT [pad_len]
# vectors, pad_len a multiple of nranks (and of quant_block when
# quantized); the dp-sharded state vars are declared at global [pad_len]
# with spec ("dp",) so each rank's shard_map body sees its [pad_len/n]
# shard. Outside a mesh both ops degrade to the identity pipeline
# (flatten+pad / unpad+reshape), which is also the single-chip math.
# ---------------------------------------------------------------------------


def _quant_precision(quant, dtype):
    if quant and quant != "none":
        return quant
    return {"float32": "fp32", "bfloat16": "bf16", "float16": "fp16",
            "float64": "fp64"}.get(str(jnp.dtype(dtype)), str(dtype))


def _record_zero(kind, op, payload_elems, dtype, ax, n):
    """Count a sharded-update collective and its estimated ring WIRE bytes
    (payload x (n-1)/n, plus per-block scale overhead when quantized) by
    kind and precision: collective.bytes.reduce_scatter_int8 etc. Trace-
    time granularity, like _record (once per compiled collective site)."""
    if ax is None:
        return
    from .. import observability as _obs
    from ..resilience.faults import fault_point

    fault_point("collective.dispatch")
    quant = op.attr("quant", "none")
    block = int(op.attr("quant_block", 256) or 256)
    if quant and quant != "none":
        payload = payload_elems * 1.0 + (payload_elems / block) * 4.0
        precision = quant
    else:
        payload = float(payload_elems) * jnp.dtype(dtype).itemsize
        precision = _quant_precision(None, dtype)
    wire = int(payload * (n - 1) / n) if n > 1 else 0
    _obs.add(f"collective.{kind}")
    _obs.add(f"collective.bytes.{kind}_{precision}", wire)


def _block_quantize(x, block):
    """int8-quantize `x` (fp, last dim a multiple of `block`) in blocks
    with per-block fp32 abs-max scales. Returns (q int8 same shape,
    scales fp32 [..., nblocks])."""
    xb = x.reshape(x.shape[:-1] + (x.shape[-1] // block, block))
    xb = xb.astype(jnp.float32)
    scale = jnp.max(jnp.abs(xb), axis=-1) / 127.0
    safe = jnp.where(scale > 0, scale, 1.0)
    q = jnp.clip(jnp.round(xb / safe[..., None]), -127, 127).astype(jnp.int8)
    return q.reshape(x.shape), safe


def _block_dequantize(q, scales, block):
    """fp32 dequantization of :func:`_block_quantize` output."""
    qb = q.reshape(q.shape[:-1] + (q.shape[-1] // block, block))
    return (qb.astype(jnp.float32) * scales[..., None]).reshape(q.shape)


def _record_bucket(members, payload_bytes):
    """Count one bucketed collective site: how many buckets the compiled
    step issues and how many payload bytes ride in them. Trace-time
    granularity like every other collective counter (once per compiled
    site, which the step replays)."""
    from .. import observability as _obs

    _obs.add("collective.buckets")
    _obs.add("collective.bucket_bytes", int(payload_bytes))
    _obs.add("collective.bucket_members", int(members))


@register_op(
    "c_bucket_allreduce_sum", inputs=["X"], outputs=["Out"],
    differentiable=False,
)
def _c_bucket_allreduce_sum(ctx, op, ins):
    """Bucketed gradient allreduce (the DP overlap schedule): flatten and
    concatenate the member gradients (optional 1/N scale folded in), issue
    ONE psum over the bucket, split the reduced buffer back per member.
    Elementwise sums are unchanged by concatenation, so the fp32 result is
    BITWISE the per-grad c_allreduce_sum sequence — the bucket only
    changes how many collectives the wire sees and how early each fires.
    Bucket membership and order are part of the cross-rank contract
    (analysis/collectives.py carries them in the site kind)."""
    # no None-filtering: every member slot must hold a real gradient, and
    # dropping one would silently misalign the split-back below
    xs = list(ins["X"])
    ax = _axis(ctx, op)
    scale = op.attr("scale", None)
    if scale is not None:
        xs = [x * jnp.asarray(scale, x.dtype) for x in xs]
    if ax is None:
        return {"Out": list(xs)}
    sizes = [int(x.size) for x in xs]
    flat = jnp.concatenate([x.reshape(-1) for x in xs])
    _record("c_bucket_allreduce_sum", flat, ax)
    _record_bucket(len(xs), int(flat.size) * flat.dtype.itemsize)
    total = lax.psum(flat, ax)
    out, off = [], 0
    for x, n in zip(xs, sizes):
        out.append(total[off:off + n].reshape(x.shape))
        off += n
    return {"Out": out}


@register_op(
    "zero_reduce_scatter", inputs=["X"], outputs=["Out"],
    differentiable=False,
)
def _zero_reduce_scatter(ctx, op, ins):
    """Flatten + optional scale + pad a gradient to [pad_len], then
    reduce-scatter it over `axis_name`: each rank ends with the globally
    summed [pad_len/n] shard it will update. quant="int8" swaps the
    fp-wire psum_scatter for block-quantized all_to_all + fp32-accumulated
    local sum (EQuARX: quantize per hop, accumulate full precision)."""
    x = ins["X"][0]
    ax = _axis(ctx, op)
    pad_len = int(op.attr("pad_len"))
    scale = op.attr("scale", None)
    quant = op.attr("quant", "none") or "none"
    block = int(op.attr("quant_block", 256) or 256)
    flat = x.reshape(-1)
    if scale is not None:
        flat = flat * jnp.asarray(scale, flat.dtype)
    if pad_len > flat.shape[0]:
        flat = jnp.pad(flat, (0, pad_len - flat.shape[0]))
    n = int(ctx.axis_sizes.get(ax, 1)) if ax is not None else 1
    _record_zero("reduce_scatter", op, pad_len, flat.dtype, ax, n)
    if ax is None:
        return {"Out": [flat]}
    if quant == "none":
        return {"Out": [
            lax.psum_scatter(flat, ax, scatter_dimension=0, tiled=True)
        ]}
    # int8 path: quantize each destination rank's shard in blocks, exchange
    # int8 payload + fp32 per-block scales, dequantize and SUM IN FP32
    shards = flat.reshape(n, pad_len // n)
    q, scales = _block_quantize(shards, block)
    q = lax.all_to_all(q, ax, split_axis=0, concat_axis=0, tiled=False)
    scales = lax.all_to_all(
        scales, ax, split_axis=0, concat_axis=0, tiled=False
    )
    acc = jnp.sum(_block_dequantize(q, scales, block), axis=0)
    return {"Out": [acc.astype(x.dtype)]}


@register_op(
    "zero_bucket_reduce_scatter", inputs=["X"], outputs=["Out"],
    differentiable=False,
)
def _zero_bucket_reduce_scatter(ctx, op, ins):
    """Bucketed ZeRO gradient reduce-scatter: every member gradient is
    flattened + scaled + padded to its own [pad_len_i] exactly like
    zero_reduce_scatter, then the members' per-rank shards are interleaved
    into ONE [sum(pad)] exchange — rank r's slice of the bucket is the
    concatenation of the members' rank-r shards, so each output shard is
    elementwise identical to the per-grad op's. One collective per bucket
    instead of one per gradient; the bucket fires as soon as its LAST
    member gradient is produced (transpiler), so earlier buckets' wire
    time hides behind the remaining backward compute.

    quant="int8" runs the same EQuARX block-quantized exchange as
    zero_reduce_scatter; every member pad is aligned to nranks*quant_block
    (ShardedWeightUpdate._pad_len), so quant blocks never straddle member
    boundaries and the per-block scales equal the per-grad path's.

    Exchange layout: members sharing a pad length STACK into one
    [m, n, pad/n] buffer — a contiguous concatenation of their flat
    [pad] vectors viewed rank-major, zero data movement beyond the copy —
    and scatter over the rank dim in ONE collective; distinct pad lengths
    within a bucket each get their own stack. An interleaved single-buffer
    layout would need a strided transpose of the whole bucket per step,
    which costs more than the collectives it saves."""
    # no None-filtering: members zip pairwise against pad_lens and the
    # declared Out shards, so a dropped slot would shift every later
    # member onto the wrong pad/output
    xs = list(ins["X"])
    ax = _axis(ctx, op)
    pad_lens = [int(p) for p in op.attr("pad_lens")]
    scale = op.attr("scale", None)
    quant = op.attr("quant", "none") or "none"
    block = int(op.attr("quant_block", 256) or 256)
    flats = []
    for x, pad in zip(xs, pad_lens):
        flat = x.reshape(-1)
        if scale is not None:
            flat = flat * jnp.asarray(scale, flat.dtype)
        if pad > flat.shape[0]:
            flat = jnp.pad(flat, (0, pad - flat.shape[0]))
        flats.append(flat)
    total = sum(pad_lens)
    n = int(ctx.axis_sizes.get(ax, 1)) if ax is not None else 1
    dtype = flats[0].dtype if flats else jnp.float32
    _record_zero("bucket_reduce_scatter", op, total, dtype, ax, n)
    if ax is not None:
        _record_bucket(len(xs), total * jnp.dtype(dtype).itemsize)
    if ax is None:
        return {"Out": flats}
    # group members by pad length (deterministic from pad_lens, so the
    # grouping is rank-uniform by construction)
    groups = {}
    for i, pad in enumerate(pad_lens):
        groups.setdefault(pad, []).append(i)
    out = [None] * len(flats)
    for pad, idxs in groups.items():
        k = pad // n
        stacked = jnp.stack([flats[i] for i in idxs]).reshape(
            len(idxs), n, k
        )
        if quant == "none":
            shards = lax.psum_scatter(
                stacked, ax, scatter_dimension=1, tiled=True
            )  # [m, 1, k]: rank r holds the summed member rows r
        else:
            q, scales = _block_quantize(stacked, block)
            q = lax.all_to_all(
                q, ax, split_axis=1, concat_axis=1, tiled=True
            )
            scales = lax.all_to_all(
                scales, ax, split_axis=1, concat_axis=1, tiled=True
            )
            deq = _block_dequantize(
                q.reshape(len(idxs), n, k), scales, block
            )
            shards = jnp.sum(deq, axis=1, keepdims=True).astype(dtype)
        shards = shards.reshape(len(idxs), k)
        for j, i in enumerate(idxs):
            out[i] = shards[j]
    return {"Out": out}


@register_op(
    "zero_all_gather", inputs=["X"], outputs=["Out"], differentiable=False
)
def _zero_all_gather(ctx, op, ins):
    """All-gather a rank's updated [pad_len/n] parameter shard back to the
    full parameter: concatenate shards, drop padding, reshape to `shape`.
    quant="int8" ships the shards block-quantized (the EQuARX trade: the
    replicated working copy is transport-quantized; the rank's own master
    shard keeps full precision)."""
    x = ins["X"][0]
    ax = _axis(ctx, op)
    shape = tuple(int(d) for d in op.attr("shape"))
    pad_len = int(op.attr("pad_len"))
    numel = 1
    for d in shape:
        numel *= d
    quant = op.attr("quant", "none") or "none"
    block = int(op.attr("quant_block", 256) or 256)
    n = int(ctx.axis_sizes.get(ax, 1)) if ax is not None else 1
    _record_zero("all_gather", op, pad_len, x.dtype, ax, n)
    if ax is None:
        full = x
    elif quant == "none":
        full = lax.all_gather(x, ax, tiled=True)
    else:
        q, scales = _block_quantize(x, block)
        q = lax.all_gather(q, ax, tiled=True)
        scales = lax.all_gather(scales, ax, tiled=True)
        full = _block_dequantize(q, scales, block).astype(x.dtype)
    return {"Out": [full[:numel].reshape(shape)]}


@register_op(
    "zero_pad_flatten", inputs=["X"], outputs=["Out"], differentiable=False
)
def _zero_pad_flatten(ctx, op, ins):
    """Startup-side init of a sharded-update state var: flatten X and
    zero-pad to [pad_len] (the global flat layout zero_reduce_scatter /
    zero_all_gather exchange). Runs meshless in the startup program; the
    executor's SPMD staging slices each rank's shard out afterwards."""
    x = ins["X"][0]
    pad_len = int(op.attr("pad_len"))
    flat = x.reshape(-1)
    if pad_len > flat.shape[0]:
        flat = jnp.pad(flat, (0, pad_len - flat.shape[0]))
    return {"Out": [flat]}


@register_op("c_identity", inputs=["X"], outputs=["Out"])
def _c_identity(ctx, op, ins):
    return {"Out": [ins["X"][0]]}


def _register_noop(op_type, io=("X", "Out")):
    @register_op(op_type, inputs=[io[0]], outputs=[io[1]], differentiable=False)
    def emit(ctx, op, ins):
        vals = ins.get(io[0], [])
        return {io[1]: list(vals)}

    return emit


# stream sync is meaningless under XLA's dataflow ordering; kept for API parity
_register_noop("c_sync_calc_stream")
_register_noop("c_sync_comm_stream")


@register_op("c_comm_init_all", inputs=[], outputs=[], differentiable=False)
def _c_comm_init_all(ctx, op, ins):
    return {}


@register_op("barrier", inputs=["X"], outputs=["Out"], differentiable=False)
def _barrier(ctx, op, ins):
    x = ins["X"][0] if ins.get("X") and ins["X"][0] is not None else jnp.zeros([1])
    ax = _axis(ctx, op)
    _record("barrier", None, ax)  # zero-payload sync: count the op, no bytes
    if ax is None:
        return {"Out": [x]}
    return {"Out": [x + 0 * lax.psum(jnp.zeros([1], x.dtype), ax)]}
