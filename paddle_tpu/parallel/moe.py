"""Mixture-of-Experts with expert parallelism (EP) — GShard-style.

Absent from the reference (SURVEY.md §2.3: "no MoE ops"); designed
TPU-first: experts shard over the "ep" mesh axis, token dispatch/return are
two lax.all_to_all exchanges over ICI, expert FFNs run as one batched
einsum on the MXU. Top-2 gating with capacity dropping + the standard
load-balancing auxiliary loss (mean(fraction * prob) * E).

Without a mesh (or no "ep" axis) the same math runs dense on one chip —
the dispatch einsums are identical, only the all_to_alls drop out.

`moe_local_experts` is the serving-side layer of today's sparse models
(sigmoid scores, top-k over ALL experts, no capacity, nothing dropped):
told which experts it holds, it computes the part of the result its own
experts give. That is what expert parallelism asks of a chip; on one chip
it runs without the exchange, and the absent experts' part is absent.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ..framework.registry import register_op


def _top2_dispatch(gates, capacity):
    """gates [T, E] softmax-ed. Returns dispatch [T, E, C] (0/1), combine
    [T, E, C] (weights), aux load-balance loss (scalar)."""
    t, e = gates.shape
    c = int(capacity)

    idx1 = jnp.argmax(gates, axis=-1)
    mask1 = jax.nn.one_hot(idx1, e, dtype=gates.dtype)  # [T,E]
    gates2 = gates * (1.0 - mask1)
    idx2 = jnp.argmax(gates2, axis=-1)
    mask2 = jax.nn.one_hot(idx2, e, dtype=gates.dtype)

    # aux loss on first-choice assignment (GShard eq. 4)
    density = mask1.mean(axis=0)  # fraction of tokens per expert
    density_proxy = gates.mean(axis=0)  # mean router prob per expert
    aux = (density * density_proxy).sum() * e

    # position of each token within its expert's capacity buffer
    pos1 = jnp.cumsum(mask1, axis=0) - mask1  # [T,E]
    keep1 = mask1 * (pos1 < c)
    # second choices queue behind ALL first choices of that expert
    count1 = mask1.sum(axis=0, keepdims=True)
    pos2 = jnp.cumsum(mask2, axis=0) - mask2 + count1
    keep2 = mask2 * (pos2 < c)

    g1 = (gates * keep1).sum(axis=-1, keepdims=True)
    g2 = (gates * keep2).sum(axis=-1, keepdims=True)
    denom = jnp.clip(g1 + g2, 1e-9, None)
    w1 = g1 / denom
    w2 = g2 / denom

    oh_pos1 = jax.nn.one_hot(
        (pos1 * mask1).sum(axis=-1).astype(jnp.int32), c, dtype=gates.dtype
    )  # [T,C]
    oh_pos2 = jax.nn.one_hot(
        (pos2 * mask2).sum(axis=-1).astype(jnp.int32), c, dtype=gates.dtype
    )
    dispatch = (
        keep1[:, :, None] * oh_pos1[:, None, :]
        + keep2[:, :, None] * oh_pos2[:, None, :]
    )
    combine = (
        (keep1 * w1)[:, :, None] * oh_pos1[:, None, :]
        + (keep2 * w2)[:, :, None] * oh_pos2[:, None, :]
    )
    return dispatch, combine, aux


def moe_ffn(x, gate_w, w1, b1, w2, b2, axis_name=None, axis_size=1,
            capacity_factor=2.0, activation=jax.nn.gelu):
    """x [B, S, H] (local shard). w1 [E_local, H, F], w2 [E_local, F, H]
    (expert-sharded over `axis_name` when set; full E otherwise).
    Returns (y [B,S,H], aux_loss scalar)."""
    b, s, h = x.shape
    n = int(axis_size) if axis_name else 1
    e_local = w1.shape[0]
    e = e_local * n
    tokens = x.reshape(b * s, h)
    t = tokens.shape[0]
    capacity = max(1, int(capacity_factor * t * 2 / e))

    logits = tokens.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    gates = jax.nn.softmax(logits, axis=-1)  # [T, E] over GLOBAL experts
    dispatch, combine, aux = _top2_dispatch(gates, capacity)

    expert_in = jnp.einsum(
        "tec,th->ech", dispatch.astype(x.dtype), tokens
    )  # [E, C, H]
    if n > 1:
        # token exchange: each device keeps its E_local experts' buffers and
        # receives the matching slices from every peer
        expert_in = lax.all_to_all(
            expert_in, axis_name, split_axis=0, concat_axis=1, tiled=True
        )  # [E_local, n*C, H]
    hmid = activation(
        jnp.einsum("ekh,ehf->ekf", expert_in, w1) + b1[:, None, :]
    )
    expert_out = jnp.einsum("ekf,efh->ekh", hmid, w2) + b2[:, None, :]
    if n > 1:
        expert_out = lax.all_to_all(
            expert_out, axis_name, split_axis=1, concat_axis=0, tiled=True
        )  # [E, C, H]
    y = jnp.einsum("tec,ech->th", combine.astype(x.dtype), expert_out)
    return y.reshape(b, s, h), aux.astype(jnp.float32)


@register_op(
    "moe_ffn",
    inputs=["X", "GateW", "W1", "B1", "W2", "B2"],
    outputs=["Out", "AuxLoss"],
)
def _moe_ffn_op(ctx, op, ins):
    x, gate_w, w1, b1, w2, b2 = (
        ins[k][0] for k in ("X", "GateW", "W1", "B1", "W2", "B2")
    )
    axis = op.attr("axis_name", "ep")
    cf = op.attr("capacity_factor", 2.0)
    if axis in ctx.mesh_axes:
        y, aux = moe_ffn(
            x, gate_w, w1, b1, w2, b2, axis_name=axis,
            axis_size=ctx.axis_sizes[axis], capacity_factor=cf,
        )
    else:
        y, aux = moe_ffn(x, gate_w, w1, b1, w2, b2, capacity_factor=cf)
    return {"Out": [y], "AuxLoss": [aux.reshape([1])]}


# ---------------------------------------------------------------------------
# dropless sigmoid-routed experts, one chip's share
# ---------------------------------------------------------------------------

# counters a step accumulates in place (one int32 vector, the order below)
MOE_COUNTERS = ("assignments_local", "assignments_total", "experts_hit",
                "max_expert_load", "max_expert_load_sum", "calls",
                # the same of one-token (decode) calls alone
                "decode_assignments_local", "decode_experts_hit",
                "decode_calls",
                # rows of the sorted buffer the passes touch (the live
                # tiles), and rows it is allocated for
                "rows_live", "rows_buffer")


def sigmoid_topk_route(tokens, router_w, expert_bias, top_k, route_scale,
                       route_norm=True, n_group=1, topk_group=1,
                       scoring="sigmoid"):
    """tokens [T, H] -> (selected [T, k] int32 over all experts, weights
    [T, k] float32). Scores are sigmoids in float32 (a product of
    bfloat16 operands accumulated in float32 is exact), or with
    `scoring` "softmax" a softmax over all the experts; `expert_bias`
    (None for none) moves the selection only; the weights are the
    selected scores over their sum (over all k, wherever those experts
    live), times `route_scale`. With `n_group` > 1 the selection is
    group-limited:
    the experts lie in `n_group` runs of consecutive ids, a run scores
    the sum of its two largest biased scores, the `topk_group` best runs
    are kept and every other expert's biased score is set to 0 before
    the top-k."""
    from ..ops._helpers import einsum_f32

    logits = einsum_f32("th,he->te", tokens, router_w)
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        scores = jax.nn.sigmoid(logits)
    biased = scores
    if expert_bias is not None:
        biased = scores + expert_bias.astype(jnp.float32)
    if n_group > 1:
        runs = biased.reshape(biased.shape[0], n_group, -1)
        run_score = jnp.sum(lax.top_k(runs, 2)[0], axis=-1)     # [T, G]
        _, kept = lax.top_k(run_score, topk_group)
        keep = jnp.any(kept[:, :, None] == jnp.arange(n_group), axis=1)
        biased = jnp.where(keep[:, :, None], runs, 0.0).reshape(biased.shape)
    _, sel = lax.top_k(biased, top_k)
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if route_norm:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return sel.astype(jnp.int32), w * route_scale


# rows of one tile of the grouped product: MXU-sized once the experts see
# hundreds of rows each, the bfloat16 sublane pack for a decode step's
# handful
MXU_TILE, PACK_TILE = 256, 16


def _row_tile(assignments):
    return MXU_TILE if assignments >= 8192 else PACK_TILE


def buffer_tiles(assignments, n_local):
    """(rows of a tile, tiles) of the sorted buffer: the worst case,
    every assignment local and every expert's last tile padded."""
    tm = _row_tile(assignments)
    return tm, -(-assignments // tm) + n_local


# assignments a block of the sort's running count: one pass of the
# matrix unit a block (a decode step's fewer assignments are one block)
SORT_BLOCK = 256


def sort_blocks(assignments):
    """(assignments a block, blocks) of the sort's running count."""
    tb = min(SORT_BLOCK, assignments)
    return tb, -(-assignments // tb)


def _strictly_lower(n):
    """[n, n] 0/1 in bfloat16: row i holds ones at the columns j < i."""
    i = jnp.arange(n)
    return (i[:, None] > i[None, :]).astype(jnp.bfloat16)


def sort_layout(expert, n_local, tm, n_tiles):
    """The sorted buffer's layout. `expert` [A] int32 is each
    assignment's local expert, `n_local` where it is not local. Each
    expert's group holds its assignments in their order, padded to whole
    tiles of `tm` rows; the groups follow in expert order.

    Returns (slot [A]: each assignment's row of the buffer, past the
    buffer where it is not local; assign_of_slot [n_tiles * tm]: the
    assignment a row holds, -1 where it pads its tile; counts [n_local]
    int32; tile_expert [n_tiles] int32: the expert whose run of tiles
    covers a tile, tiles past the last active one repeating its expert
    (no new weight fetch); num_active: the tiles the groups fill).

    No pass walks the assignments in order. An assignment's rank is how
    many of its expert's assignments come before it: within its block
    of `sort_blocks` by ONE product of the blocks' one-hot with a
    strictly lower triangle, before its block by a second such product
    over the blocks' totals (0/1 and counts up to a block in bfloat16,
    sums in float32: exact), read out by the same one-hot. A tile's
    expert is one comparison with the groups' ends. One scatter places
    the assignments; their tokens and weights follow from it."""
    n_assign = expert.shape[0]
    tb, n_blocks = sort_blocks(n_assign)
    padded = jnp.pad(expert, (0, n_blocks * tb - n_assign),
                     constant_values=n_local).reshape(n_blocks, tb)
    # [blocks, experts, assignments of the block]: a block's assignments
    # along the lanes
    onehot = padded[:, None, :] == jnp.arange(n_local)[:, None]
    ones = onehot.astype(jnp.bfloat16)
    within = jnp.einsum("bej,ij->bei", ones, _strictly_lower(tb),
                        preferred_element_type=jnp.float32)
    totals = within[..., -1] + ones[..., -1]                 # [nb, E]
    before = jnp.dot(_strictly_lower(n_blocks), totals.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)     # [nb, E]
    counts = (before[-1] + totals[-1]).astype(jnp.int32)
    group_tiles = -(-counts // tm)
    ends = jnp.cumsum(group_tiles)
    num_active = ends[-1]
    first = before.astype(jnp.int32) + (ends - group_tiles) * tm
    row = within.astype(jnp.int32) + first[..., None]
    slot = jnp.where(padded < n_local,
                     jnp.sum(jnp.where(onehot, row, 0), axis=1),
                     n_tiles * tm).reshape(-1)[:n_assign]
    assign_of_slot = jnp.full((n_tiles * tm,), -1, jnp.int32).at[slot].set(
        jnp.arange(n_assign, dtype=jnp.int32), mode="drop")
    tile = jnp.minimum(jnp.arange(n_tiles), jnp.maximum(num_active - 1, 0))
    tile_expert = jnp.minimum(
        jnp.sum(ends[None, :] <= tile[:, None], axis=1), n_local - 1,
    ).astype(jnp.int32)
    return slot, assign_of_slot, counts, tile_expert, num_active


def of_slot(assign_of_slot, values, fill):
    """What each row of the sorted buffer holds of its assignment:
    `values` [A] at the row's assignment, `fill` where the row pads."""
    return jnp.where(assign_of_slot < 0, fill,
                     values[jnp.maximum(assign_of_slot, 0)])


def local_experts_ffn(x, router_w, expert_bias, w_gate_up, w_down, *,
                      top_k, route_scale, expert_offset, route_norm=True,
                      activation="swiglu", router_x=None, n_group=1,
                      topk_group=1, scoring="sigmoid", interpret=False):
    """The routed part of an expert layer that THIS chip's experts give.

    x [B, T, K], K the width the experts work at; router_w [H, E] over
    all E experts, scored on `router_x` [B, T, H] (x itself where the
    experts work at the hidden width), `n_group` / `topk_group` /
    `scoring` as `sigmoid_topk_route` takes them (`expert_bias` None:
    no bias buffer); w_gate_up [E_local, K, 2F]
    (`swiglu`) or [E_local, K, F] (`relu2`), w_down [E_local, F, K]:
    experts `expert_offset` .. `expert_offset + E_local - 1`. Every
    token is scored against all E, its top-k chosen, and the assignments
    that fall on a local expert are sorted by expert (each group padded
    to whole row tiles), pushed through a grouped product with the
    activation as its epilogue (kernels/moe_gmm.py), and summed back
    into their tokens with their weights. No capacity, nothing dropped:
    the sorted buffer is ALLOCATED for the worst case, every assignment
    local. What is TOUCHED follows the rows that are live (`num_active`
    tiles): the products skip the other tiles, and once the tiles are
    MXU-sized (a prefill) the rows go in and come back through
    `gather_rows` / `combine_rows`, which skip them too and read no row
    for an assignment that is not local. A decode step's small buffer
    keeps the `jnp` gathers, whose cost there is a few microseconds.

    Returns (y [B, T, K], selected [B, T, k], counts [E_local] int32)."""
    from .. import observability as _obs
    from ..kernels import moe_gmm

    b, t, h = x.shape
    n_local = w_gate_up.shape[0]
    tokens = x.reshape(b * t, h)
    n_tok = b * t
    scored = tokens if router_x is None else router_x.reshape(n_tok, -1)
    # the op's parts by name in the compiled step's `op_name`, beneath
    # the op's own scope: router, sort, dispatch, experts, combine
    with jax.named_scope("moe_router"):
        sel, weights = sigmoid_topk_route(
            scored, router_w, expert_bias, top_k, route_scale, route_norm,
            n_group, topk_group, scoring,
        )
    n_assign = n_tok * top_k
    tm, n_tiles = buffer_tiles(n_assign, n_local)
    # off the TPU the same in `jnp` (kernel tests: `interpret`)
    kernels = interpret or jax.default_backend() == "tpu"
    row_kernels = interpret or (kernels and tm == MXU_TILE)

    _obs.set_gauge("moe.sort.blocks", sort_blocks(n_assign)[1])
    with jax.named_scope("moe_sort"):
        local = sel - expert_offset
        is_local = (local >= 0) & (local < n_local)
        expert = jnp.where(is_local, local, n_local).reshape(-1)  # [A]
        slot, assign_of_slot, counts, tile_expert, num_active = sort_layout(
            expert, n_local, tm, n_tiles)
        # a row's token: none (-1 // top_k) where the row pads its tile
        token_of_slot = assign_of_slot // top_k
        active = num_active.reshape(1).astype(jnp.int32)

    def product(lhs, rhs, act=None):
        if kernels:
            return moe_gmm.gmm(lhs, rhs, tile_expert, active, tm, act,
                               interpret=interpret)
        return moe_gmm.gmm_reference(lhs, rhs, tile_expert, active, tm, act)

    with jax.named_scope("moe_dispatch"):
        if row_kernels:
            x_sorted = moe_gmm.gather_rows(tokens, token_of_slot, active, tm,
                                           interpret=interpret)
        else:
            x_sorted = tokens[jnp.maximum(token_of_slot, 0)]    # [M, H]
    with jax.named_scope("moe_experts"):
        hidden = product(x_sorted, w_gate_up, activation)       # [M, F]
        y_sorted = product(hidden, w_down)                      # [M, H]
    with jax.named_scope("moe_combine"):
        if row_kernels:
            y = moe_gmm.combine_rows(
                y_sorted, token_of_slot,
                of_slot(assign_of_slot, weights.reshape(-1), 0.0),
                active, tm, n_tok, interpret=interpret)
        else:
            picked = y_sorted[jnp.minimum(slot, n_tiles * tm - 1)]  # [A, H]
            # a slot of a non-local assignment reads a row nobody wrote:
            # select, never multiply by zero
            part = jnp.where(
                is_local.reshape(-1, 1),
                picked.astype(jnp.float32) * weights.reshape(-1, 1), 0.0,
            )
            y = jnp.sum(part.reshape(n_tok, top_k, h), axis=1).astype(
                x.dtype)
    return y.reshape(b, t, h), sel.reshape(b, t, top_k), counts


@register_op(
    "moe_local_experts",
    inputs=["X", "RouterW", "ExpertBias", "WGateUp", "WDown", "Counters",
            "RouterX"],
    outputs=["Out", "Selected", "CountersOut"],
    differentiable=False,
    mutates=(("CountersOut", "Counters"),),
)
def _moe_local_experts_op(ctx, op, ins):
    """`activation` ("swiglu" by default) names the experts' form;
    `RouterX`, where given, is what the router scores (experts that work
    in a latent read `X`, the router the hidden state); `n_group` and
    `topk_group` (1 by default) limit a token to its best groups;
    `scoring` ("sigmoid" by default, or "softmax") is how the router
    scores, and `ExpertBias` may be absent."""
    x, router_w, wgu, wd, counters = (
        ins[k][0] for k in ("X", "RouterW", "WGateUp", "WDown", "Counters")
    )
    bias = (ins.get("ExpertBias") or [None])[0]
    top_k = int(op.attr("top_k"))
    y, sel, counts = local_experts_ffn(
        x, router_w, bias, wgu, wd, top_k=top_k,
        route_scale=float(op.attr("route_scale", 1.0)),
        route_norm=bool(op.attr("route_norm", True)),
        expert_offset=int(op.attr("expert_offset", 0)),
        activation=op.attr("activation", "swiglu"),
        router_x=(ins.get("RouterX") or [None])[0],
        n_group=int(op.attr("n_group", 1)),
        topk_group=int(op.attr("topk_group", 1)),
        scoring=op.attr("scoring", "sigmoid"),
    )
    most = jnp.max(counts)
    local, hit = jnp.sum(counts), jnp.sum(counts > 0).astype(jnp.int32)
    decode = jnp.int32(x.shape[1] == 1)
    assignments = x.shape[0] * x.shape[1] * top_k
    tm, n_tiles = buffer_tiles(assignments, wgu.shape[0])
    step = jnp.stack([
        local, jnp.int32(assignments), hit,
        jnp.int32(0), most, jnp.int32(1),
        decode * local, decode * hit, decode,
        jnp.sum(-(-counts // tm)) * tm, jnp.int32(n_tiles * tm),
    ])
    new = (counters + step).at[3].set(jnp.maximum(counters[3], most))
    return {"Out": [y], "Selected": [sel], "CountersOut": [new]}
