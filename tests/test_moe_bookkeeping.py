"""The expert layer's sort bookkeeping (parallel/moe.py::sort_layout):
the layout of the sorted buffer against the running-count formulation it
replaced, element for element, at the four expert families' shapes and at
the edges; and what the layer lowers to: no sequential pass, one
scatter, and the gauge `moe.sort.blocks`. CPU, tiny widths."""

import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu.kernels import moe_gmm
from paddle_tpu.parallel import moe

# E_local, experts in all, top_k, scoring: the four expert cells' chips
FAMILIES = {
    "qwen3_next": (64, 512, 10, "softmax"),
    "nemotron": (128, 512, 22, "sigmoid"),
    "dots_vlm": (16, 256, 8, "sigmoid"),
    "trinity": (32, 256, 4, "sigmoid"),
}
# one expert a token: every assignment can land on one expert
SHAPES = dict(FAMILIES, top_1=(8, 64, 1, "sigmoid"))
H, F = 32, 16


def running_count_layout(sel, weights, n_local, offset, top_k, tm, n_tiles):
    """The layout as the running count gave it: one cumulative sum per
    expert along the assignments, ranks gathered out of it, one scatter
    for the rows' tokens and one for their weights, the tiles' experts by
    a binary search."""
    n_assign = sel.size
    local = sel - offset
    is_local = (local >= 0) & (local < n_local)
    expert = jnp.where(is_local, local, n_local).reshape(-1)
    onehot = expert[:, None] == jnp.arange(n_local)[None, :]
    running = jnp.cumsum(onehot.astype(jnp.int32), axis=0)
    counts = running[-1]
    clamped = jnp.minimum(expert, n_local - 1)
    rank = jnp.take_along_axis(running, clamped[:, None], axis=1)[:, 0] - 1
    group_tiles = -(-counts // tm)
    tiles_before = jnp.cumsum(group_tiles) - group_tiles
    num_active = jnp.sum(group_tiles)
    slot = jnp.where(expert < n_local, tiles_before[clamped] * tm + rank,
                     n_tiles * tm)

    def scattered(values, fill):
        return jnp.full((n_tiles * tm,), fill, values.dtype).at[slot].set(
            values, mode="drop")

    tile = jnp.minimum(jnp.arange(n_tiles), jnp.maximum(num_active - 1, 0))
    return dict(
        expert=expert, counts=counts, rank=rank, slot=slot,
        token_of_slot=scattered(
            jnp.arange(n_assign, dtype=jnp.int32) // top_k, -1),
        weight_of_slot=scattered(weights.reshape(-1), 0.0),
        tile_expert=jnp.minimum(
            jnp.searchsorted(jnp.cumsum(group_tiles), tile, side="right"),
            n_local - 1).astype(jnp.int32),
        num_active=num_active)


def running_count_layer(x, w, *, top_k, offset, scoring, interpret):
    """The expert layer around that layout: route, dispatch, products,
    combine as `local_experts_ffn` does them."""
    b, t, h = x.shape
    n_local = w["wgu"].shape[0]
    tokens = x.reshape(b * t, h)
    sel, weights = moe.sigmoid_topk_route(
        tokens, w["router_w"], w["bias"], top_k, 1.5, True, scoring=scoring)
    tm, n_tiles = moe.buffer_tiles(sel.size, n_local)
    lay = running_count_layout(sel, weights, n_local, offset, top_k, tm,
                               n_tiles)
    active = lay["num_active"].reshape(1).astype(jnp.int32)

    def product(lhs, rhs, act=None):
        if interpret:
            return moe_gmm.gmm(lhs, rhs, lay["tile_expert"], active, tm, act,
                               interpret=True)
        return moe_gmm.gmm_reference(lhs, rhs, lay["tile_expert"], active,
                                     tm, act)

    if interpret:
        x_sorted = moe_gmm.gather_rows(tokens, lay["token_of_slot"], active,
                                       tm, interpret=True)
    else:
        x_sorted = tokens[jnp.maximum(lay["token_of_slot"], 0)]
    y_sorted = product(product(x_sorted, w["wgu"], "swiglu"), w["wd"])
    if interpret:
        y = moe_gmm.combine_rows(y_sorted, lay["token_of_slot"],
                                 lay["weight_of_slot"], active, tm, b * t,
                                 interpret=True)
    else:
        picked = y_sorted[jnp.minimum(lay["slot"], n_tiles * tm - 1)]
        is_local = (lay["expert"] < n_local).reshape(-1, 1)
        part = jnp.where(is_local, picked.astype(jnp.float32)
                         * weights.reshape(-1, 1), 0.0)
        y = jnp.sum(part.reshape(b * t, top_k, h), axis=1).astype(x.dtype)
    return y.reshape(b, t, h), sel, weights, lay


def weights_for(seed, n_local, n_all, edge):
    rs = np.random.RandomState(seed)

    def bf16(*shape, scale):
        return jnp.asarray(rs.normal(0, scale, shape), jnp.bfloat16)

    w = dict(router_w=bf16(H, n_all, scale=0.3),
             bias=jnp.asarray(rs.normal(0, 0.01, n_all), jnp.float32),
             wgu=bf16(n_local, H, 2 * F, scale=0.2),
             wd=bf16(n_local, F, H, scale=0.2))
    if edge == "ties":              # every score equal: top_k's ties
        w["router_w"] = jnp.zeros_like(w["router_w"])
        w["bias"] = None
    elif edge == "none_local":      # the local experts in nobody's top-k
        w["bias"] = w["bias"].at[n_local:2 * n_local].set(-10.0)
    elif edge == "one_expert":      # every assignment on one local expert
        w["bias"] = w["bias"].at[n_local + 3].set(10.0)
    return w


def bits(a):
    a = np.asarray(a)
    return a.view(f"u{a.dtype.itemsize}")


# (family, tokens, edge, interpret): the decode step's 64 rows (row tile
# 16) and a prefill's thousands of assignments (row tile 256, A no whole
# number of blocks); the row kernels through the interpreter where the
# buffer is small
CASES = [(family, 64, None, family != "nemotron") for family in FAMILIES] + [
    ("qwen3_next", 821, None, False),    # A = 8,210
    ("nemotron", 373, None, False),      # A = 8,206
    ("dots_vlm", 1026, None, False),     # A = 8,208
    ("trinity", 2051, None, False),      # A = 8,204
    ("trinity", 24, "none_local", True),
    ("trinity", 2051, "none_local", False),
    ("top_1", 8204, "one_expert", False),
    ("top_1", 24, "one_expert", True),
    ("qwen3_next", 64, "ties", True),
    ("dots_vlm", 1026, "ties", False),
]


@pytest.mark.parametrize(
    "family,tokens,edge,interpret", CASES,
    ids=[f"{f}-{t}-{e or 'seeded'}" for f, t, e, _i in CASES])
def test_the_layout_is_the_running_counts_element_for_element(
        family, tokens, edge, interpret):
    n_local, n_all, top_k, scoring = SHAPES[family]
    offset = 0 if edge == "ties" else n_local      # chip 1 of the layer
    w = weights_for(tokens, n_local, n_all, edge)
    x = jnp.asarray(np.random.RandomState(tokens + 1).normal(
        0, 1, (1, tokens, H)), jnp.bfloat16)

    want_y, sel, weights, want = running_count_layer(
        x, w, top_k=top_k, offset=offset, scoring=scoring,
        interpret=interpret)
    tm, n_tiles = moe.buffer_tiles(sel.size, n_local)
    assert tm == (16 if sel.size < 8192 else 256)
    slot, assign_of_slot, counts, tile_expert, num_active = moe.sort_layout(
        want["expert"], n_local, tm, n_tiles)

    local = np.asarray(want["expert"]) < n_local
    starts = np.cumsum(-(-np.asarray(counts) // tm)) * tm
    starts = starts - (-(-np.asarray(counts) // tm)) * tm
    rank = np.asarray(slot) - starts[np.minimum(np.asarray(want["expert"]),
                                                n_local - 1)]
    np.testing.assert_array_equal(counts, want["counts"])
    np.testing.assert_array_equal(rank[local], np.asarray(want["rank"])[local])
    np.testing.assert_array_equal(np.asarray(slot)[local],
                                  np.asarray(want["slot"])[local])
    assert (np.asarray(slot)[~local] >= n_tiles * tm).all()
    np.testing.assert_array_equal(assign_of_slot // top_k,
                                  want["token_of_slot"])
    np.testing.assert_array_equal(
        bits(moe.of_slot(assign_of_slot, weights.reshape(-1), 0.0)),
        bits(want["weight_of_slot"]))
    np.testing.assert_array_equal(tile_expert, want["tile_expert"])
    assert int(num_active) == int(want["num_active"])
    if edge == "none_local":
        assert int(num_active) == 0 and not local.any()
    if edge == "one_expert":
        assert int(counts[3]) == sel.size == int(counts.sum())

    y, got_sel, got_counts = moe.local_experts_ffn(
        x, w["router_w"], w["bias"], w["wgu"], w["wd"], top_k=top_k,
        route_scale=1.5, expert_offset=offset, scoring=scoring,
        interpret=interpret)
    np.testing.assert_array_equal(got_sel.reshape(sel.shape), sel)
    np.testing.assert_array_equal(got_counts, want["counts"])
    np.testing.assert_array_equal(bits(y), bits(want_y))        # bitwise


@pytest.mark.parametrize("phase,tokens,blocks",
                         [("decode", 64, 3), ("prefill", 821, 33)])
def test_the_sort_lowers_to_parallel_passes_and_one_scatter(
        phase, tokens, blocks, monkeypatch):
    """Lowered as for the chip (the row kernels in a prefill): no `while`
    in the layer, so none in its sort; no running sum as long as the
    assignments; ONE scatter places the rows, tokens and weights
    following from it; the gauge reads the sort's blocks."""
    n_local, n_all, top_k, scoring = FAMILIES["qwen3_next"]
    width = 256                          # whole lane tiles for the kernels
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    shapes = [((1, tokens, width), jnp.bfloat16),
              ((width, n_all), jnp.bfloat16),
              ((n_local, width, 2 * width), jnp.bfloat16),
              ((n_local, width, width), jnp.bfloat16)]
    layer = jax.jit(lambda x, rw, wgu, wd: moe.local_experts_ffn(
        x, rw, None, wgu, wd, top_k=top_k, route_scale=1.0,
        expert_offset=0, scoring=scoring))
    text = layer.trace(*(jax.ShapeDtypeStruct(*s) for s in shapes)).lower(
        lowering_platforms=("tpu",)).as_text()
    n_assign = tokens * top_k

    assert "tpu_custom_call" in text                  # the kernels' path
    assert "stablehlo.while" not in text
    windows = [[int(d) for d in w.split(",")] for w in re.findall(
        r"window_dimensions = array<i64: ([\d, ]+)>", text)]
    assert windows and all(max(w) < n_assign for w in windows)
    assert len(re.findall(r"\"?stablehlo\.scatter\"?\(", text)) == 1
    assert moe.sort_blocks(n_assign) == (min(256, n_assign), blocks)
    assert obs.get_gauges()["moe.sort.blocks"] == blocks
