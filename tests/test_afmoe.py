"""AFMoE serving path (models/afmoe.py): each new op against `jnp`, the
caches by layer kind, the dropless expert layer and its share of an
expert-parallel deployment, prefill + cached decode against the plain
reference (benchmark/reference/afmoe.py), bfloat16 parameters, and the
one generator serving both decoders. CPU, tiny sizes, seeded weights."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.layers.tensor import _simple
from paddle_tpu.models.afmoe import (
    DENSE, EXPERTS, FULL, SLIDING, AfmoeConfig, AfmoeDecoder,
)
from paddle_tpu.ops import kv_cache
from paddle_tpu.parallel import moe
from paddle_tpu.serving import GPTGenerator

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_op(op_type, feeds, attrs, in_slots, out_slots=("Out",)):
    """One op through Program / Executor: `feeds` {name: array},
    `in_slots` {slot: feed name}."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        vars_ = {n: fluid.data(n, list(a.shape), str(a.dtype))
                 for n, a in feeds.items()}
        outs = _simple(op_type, {s: [vars_[n]] for s, n in in_slots.items()},
                       attrs, out_slots=out_slots)
    outs = outs if isinstance(outs, tuple) else (outs,)
    got = fluid.Executor().run(main, feed=feeds,
                               fetch_list=[o.name for o in outs])
    return got[0] if len(got) == 1 else got


def rand(seed, *shape, dtype=np.float32, scale=1.0):
    return (np.random.RandomState(seed).randn(*shape) * scale).astype(dtype)


# -- the new ops against jnp -------------------------------------------------

@pytest.mark.parametrize("heads", [1, 4])
def test_rms_norm_over_hidden_and_per_head(heads):
    x, gain = rand(0, 2, 5, heads * 16), 1 + rand(1, 16, scale=0.1)
    got = run_op("rms_norm", {"x": x, "g": gain}, {"epsilon": 1e-5},
                 {"X": "x", "Scale": "g"})
    xs = x.reshape(2, 5, heads, 16)
    want = xs / np.sqrt((xs ** 2).mean(-1, keepdims=True) + 1e-5) * gain
    np.testing.assert_allclose(got, want.reshape(x.shape), rtol=2e-6,
                               atol=2e-6)


def test_rotary_at_a_runtime_position_matches_the_reference():
    from benchmark.reference import afmoe as reference

    x = rand(2, 2, 3, 4 * 16)
    last = np.array([[10]], np.int64)            # rows sit at 8, 9, 10
    got = run_op("rotary_embedding", {"x": x, "p": last},
                 {"head_dim": 16, "theta": 10000.0}, {"X": "x", "Pos": "p"})
    # the reference rotates row s at position s: pad 8 rows in front
    padded = np.concatenate([np.zeros((2, 8, 64), np.float32), x], 1)
    want = reference.rotate_half_rope(
        jnp.asarray(padded).reshape(2, 11, 4, 16), 10000.0
    ).reshape(2, 11, 64)[:, 8:]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_swiglu_matches_jnp():
    x = rand(3, 2, 3, 32)
    got = run_op("swiglu", {"x": x}, {}, {"X": "x"})
    want = jax.nn.silu(x[..., :16]) * x[..., 16:]
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_mul_keeps_float32_out_of_a_bfloat16_product():
    x = rand(4, 2, 1, 64).astype(jnp.bfloat16)
    w = rand(5, 64, 32).astype(jnp.bfloat16)
    got = run_op("mul", {"x": x, "w": w},
                 {"x_num_col_dims": 2, "y_num_col_dims": 1,
                  "out_dtype": "float32"}, {"X": "x", "Y": "w"})
    assert got.dtype == np.float32
    want = x.astype(np.float32).reshape(2, 64) @ w.astype(np.float32)
    np.testing.assert_allclose(got.reshape(2, 32), want, rtol=1e-6)
    # not the bfloat16 product cast up: that has 8 bits
    assert np.abs(got.reshape(2, 32) - want).max() < \
        np.abs(want.astype(jnp.bfloat16).astype(np.float32) - want).max()


def dense_attention(q, k, v, nh, kvh, window, scale):
    b, s, _ = q.shape
    dh = q.shape[-1] // nh
    qh = q.reshape(b, s, nh, dh)
    kh = np.repeat(k.reshape(b, s, kvh, dh), nh // kvh, axis=2)
    vh = np.repeat(v.reshape(b, s, kvh, dh), nh // kvh, axis=2)
    scores = np.einsum("bind,bjnd->bnij", qh, kh) * scale
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    ok = (j <= i) & ((i - j < window) if window else True)
    scores = np.where(ok, scores, -np.inf)
    p = np.exp(scores - scores.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bnij,bjnd->bind", p, vh).reshape(b, s, nh * dh)


@pytest.mark.parametrize("window,budget", [(0, None), (5, None), (5, 1200)])
def test_causal_gqa_attention_grouped_heads_window_and_blocks(
        window, budget, monkeypatch):
    from paddle_tpu.ops import llm

    if budget:      # rows, then queries, walked in blocks
        monkeypatch.setattr(llm, "SCORE_BLOCK_BYTES", budget)
        assert llm._query_block(2, 4, 12) == (1, 6)
    q, k, v = rand(6, 2, 12, 64), rand(7, 2, 12, 32), rand(8, 2, 12, 32)
    got = run_op("causal_gqa_attention", {"q": q, "k": k, "v": v},
                 {"num_heads": 4, "num_kv_heads": 2, "window": window,
                  "scale": 0.25}, {"Q": "q", "K": "k", "V": "v"})
    want = dense_attention(q, k, v, 4, 2, window, 0.25)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# -- caches by layer kind ------------------------------------------------------

def test_cache_shape_owns_the_kind():
    assert kv_cache.cache_shape(2, 32, 4, 16) == (2, 32, 4 * 16)
    assert kv_cache.cache_shape(2, 32, 2, 16, window=8) == (2, 8, 2 * 16)
    assert kv_cache.cache_shape(2, 6, 2, 16, window=8) == (2, 6, 2 * 16)


@pytest.mark.parametrize("slots,window", [(32, 0), (8, 8), (32, 8)])
def test_attention_mask_is_the_ring_readers_view(slots, window):
    for pos in (0, 5, 7, 8, 19):
        if pos >= slots and not window:
            continue
        got = np.asarray(kv_cache.attention_mask(
            jnp.array([pos], jnp.int32), slots, window))[0]
        held = {p % slots: p for p in range(pos + 1)}   # newest wins
        want = np.array([
            j in held and (not window or pos - held[j] < window)
            for j in range(slots)])
        np.testing.assert_array_equal(got, want)


def test_ring_cache_prefill_longer_than_the_ring_then_decode_wraps():
    """A prefill of 20 rows into a ring of 8, then 5 decode writes: every
    slot holds the newest position congruent to it; a block of rows of a
    larger batch lands at its row offset."""
    nh, dh, slots = 2, 4, 8
    cache = np.zeros(kv_cache.cache_shape(3, 64, nh, dh, window=slots),
                     np.float32)
    rows = rand(9, 2, 20, nh * dh)
    got = run_op("kv_cache_write",
                 {"c": cache, "x": rows, "p": np.array([0], np.int32),
                  "r": np.array([1], np.int64)}, {"ring": True},
                 {"Cache": "c", "X": "x", "Pos": "p", "Row": "r"})
    want = cache.copy()
    for p in range(20):
        want[1:3, p % slots] = rows[:, p]
    np.testing.assert_array_equal(got, want)
    cache = got
    for p in range(20, 25):
        row = rand(p, 3, 1, nh * dh)
        cache = run_op("kv_cache_write",
                       {"c": cache, "x": row, "p": np.array([[p]], np.int64)},
                       {"ring": True}, {"Cache": "c", "X": "x", "Pos": "p"})
        want[:, p % slots] = row[:, 0]
    np.testing.assert_array_equal(cache, want)


def test_cached_decode_attention_reads_grouped_heads_in_place():
    """48-over-8 in miniature: 4 query heads on 2 KV heads of a cache
    that holds 2, against attention with the KV heads repeated."""
    q = rand(10, 2, 1, 64)
    shape = kv_cache.cache_shape(2, 12, 2, 16)
    ck, cv = rand(11, *shape), rand(12, *shape)
    got = run_op(
        "kv_cache_attention",
        {"q": q, "k": ck, "v": cv, "p": np.array([[9]], np.int64)},
        {"num_heads": 4, "num_kv_heads": 2, "scale": 0.25},
        {"Q": "q", "CacheK": "k", "CacheV": "v", "Pos": "p"})
    k, v = ck[:, :10], cv[:, :10]       # stored as the rows went in
    qfull = np.concatenate([np.zeros((2, 9, 64), np.float32), q], 1)
    want = dense_attention(qfull, k, v, 4, 2, 0, 0.25)[:, -1:]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# -- the dropless expert layer ------------------------------------------------

def expert_weights(seed, n, h=32, f=16, e_total=16):
    return dict(
        router_w=rand(seed, h, e_total, scale=0.3),
        bias=rand(seed + 1, e_total, scale=0.01),
        wgu=rand(seed + 2, n, h, 2 * f, scale=0.2),
        wd=rand(seed + 3, n, f, h, scale=0.2),
    )


def dense_routed(x, w, offset, top_k=4, scale=2.448):
    from benchmark.reference import afmoe as reference

    cfg = {"top_k": top_k, "route_scale": scale, "route_norm": True,
           "expert_offset": offset}
    p = {"l_router_w": w["router_w"], "l_expert_bias": w["bias"],
         "l_experts_gate_up_w": w["wgu"], "l_experts_down_w": w["wd"]}
    with jax.default_matmul_precision("highest"):
        sel, weights, _ = reference.route(p, "l", jnp.asarray(x), cfg)
        return np.asarray(reference.routed_part(
            p, "l", jnp.asarray(x), sel, weights, cfg)), np.asarray(sel)


@pytest.mark.parametrize("interpret", [False, True])
def test_dropless_layer_matches_the_dense_sum(interpret):
    w = expert_weights(20, 4)
    x = rand(24, 2, 24, 32)
    y, sel, counts = moe.local_experts_ffn(
        jnp.asarray(x), w["router_w"], w["bias"], w["wgu"], w["wd"],
        top_k=4, route_scale=2.448, expert_offset=8, interpret=interpret)
    want, want_sel = dense_routed(x, w, 8)
    np.testing.assert_array_equal(np.sort(sel, -1), np.sort(want_sel, -1))
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    local = (want_sel >= 8) & (want_sel < 12)
    assert int(counts.sum()) == int(local.sum())


@pytest.mark.parametrize("interpret", [False, True])
def test_dropless_every_token_to_one_expert(interpret):
    """A router that sends every token to expert 9 first: its group is
    the whole batch (no capacity, nothing dropped)."""
    w = expert_weights(30, 4)
    w["router_w"] = np.zeros_like(w["router_w"])
    w["bias"] = np.zeros_like(w["bias"])
    w["bias"][9] = 1.0
    x = rand(31, 3, 40, 32)
    y, sel, counts = moe.local_experts_ffn(
        jnp.asarray(x), w["router_w"], w["bias"], w["wgu"], w["wd"],
        top_k=4, route_scale=2.448, expert_offset=8, interpret=interpret)
    assert int(counts[1]) == 120 and (np.asarray(sel) == 9).any(-1).all()
    want, _ = dense_routed(x, w, 8)
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)



# -- what the expert layer touches: the live rows ------------------------------

def dense_sum(x, w, offset, activation, top_k=4, scale=2.448):
    """Every token's weighted sum over its LOCAL experts in float32
    numpy, routed by the op's own router: what dispatch, products and
    combine have to add up to, for either activation."""
    from paddle_tpu.kernels import moe_gmm

    tokens = x.reshape(-1, x.shape[-1])
    sel, weights = moe.sigmoid_topk_route(
        jnp.asarray(tokens), w["router_w"], w["bias"], top_k, scale)
    sel, weights = np.asarray(sel), np.asarray(weights)
    out = np.zeros_like(tokens)
    for t, row in enumerate(tokens):
        for e, weight in zip(sel[t] - offset, weights[t]):
            if 0 <= e < w["wgu"].shape[0]:
                first = row @ w["wgu"][e]
                halves = (np.split(first, 2) if activation == "swiglu"
                          else [first])
                hidden = np.asarray(moe_gmm.activate(
                    activation, *map(jnp.asarray, halves)))
                out[t] += weight * (hidden @ w["wd"][e])
    return out.reshape(x.shape), sel


def forced_router(w, chosen, shunned=()):
    """Every token's first choice is `chosen`; the `shunned` experts are
    in nobody's top-k."""
    w = dict(w, bias=np.zeros_like(w["bias"]))
    w["bias"][list(chosen)] = 1.0
    w["bias"][list(shunned)] = -10.0
    return w


@pytest.mark.parametrize("activation", ["swiglu", "relu2"])
@pytest.mark.parametrize("live", ["no_tile", "one_tile", "every_assignment"])
def test_the_kernels_match_the_dense_sum_at_any_fill(activation, live):
    """Gather, fused product, product and scatter-add through the
    interpreter (uninitialised memory is NaN there, a read out of bounds
    raises) with nothing, one tile and every assignment of the buffer
    live."""
    def weights(**kw):
        w = expert_weights(60, 4, **kw)
        if activation == "relu2":       # one block, not gate and up
            w["wgu"] = w["wgu"][..., :16]
        return w

    w = weights()
    if live == "no_tile":           # experts 8..11 are in nobody's top-4
        w, x, offset = forced_router(w, (), range(8, 12)), rand(61, 2, 9, 32), 8
    elif live == "one_tile":        # 12 rows of expert 9, no other local
        w = forced_router(w, (9,), (8, 10, 11))
        x, offset = rand(62, 1, 12, 32), 8
    else:                           # four experts in all: top-4 is all of them
        w = weights(e_total=4)
        x, offset = rand(63, 3, 11, 32), 0
    y, sel, counts = moe.local_experts_ffn(
        jnp.asarray(x), w["router_w"], w["bias"], w["wgu"], w["wd"],
        top_k=4, route_scale=2.448, expert_offset=offset,
        activation=activation, interpret=True)
    want, want_sel = dense_sum(x, w, offset, activation)
    np.testing.assert_array_equal(sel.reshape(want_sel.shape), want_sel)
    assert int(counts.sum()) == {"no_tile": 0, "one_tile": 12,
                                 "every_assignment": 33 * 4}[live]
    if live == "no_tile":
        np.testing.assert_array_equal(y, np.zeros_like(x))      # exact
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)


def sorted_tiles(seed, counts, tm, n_tokens, tiles):
    """A buffer of `tiles` row tiles with `counts[e]` rows of expert e:
    (token_of_slot with -1 where a row pads, weight_of_slot,
    tile_expert, num_active)."""
    rs = np.random.RandomState(seed)
    token = np.full(tiles * tm, -1, np.int32)
    weight = np.zeros(tiles * tm, np.float32)
    owner, at = [], 0
    for e, n in enumerate(counts):
        token[at:at + n] = np.sort(rs.choice(n_tokens, n, replace=False))
        weight[at:at + n] = rs.uniform(0.1, 1.0, n)
        owner += [e] * -(-n // tm)
        at += -(-n // tm) * tm
    active = len(owner)
    owner += [owner[-1] if owner else 0] * (tiles - active)
    return (jnp.asarray(token), jnp.asarray(weight),
            jnp.asarray(owner, jnp.int32), jnp.asarray([active], jnp.int32))


@pytest.mark.parametrize("activation", ["swiglu", "relu2"])
def test_fused_activation_is_the_activation_of_the_float32_product(
        activation):
    """The epilogue against a reference that rounds once (exactly), and
    against the parent's two roundings, product then activation (within
    bfloat16's step); skipped tiles hold what they were allocated with."""
    from paddle_tpu.kernels import moe_gmm
    from paddle_tpu.ops.llm import swiglu
    from paddle_tpu.ops.ssm import relu2

    tm, k, f = 16, 128, 128
    _tok, _w, owner, active = sorted_tiles(70, [20, 0, 5], tm, 40, 6)
    # quarters and small integers: every float32 sum is exact whatever
    # its order, so one rounding is one result
    rs = np.random.RandomState(71)
    x = jnp.asarray(rs.randint(-3, 4, (6 * tm, k)), jnp.bfloat16)
    wide = 2 * f if activation == "swiglu" else f
    w = jnp.asarray(rs.randint(-2, 3, (3, k, wide)) / 4, jnp.bfloat16)
    got = moe_gmm.gmm(x, w, owner, active, tm, activation, interpret=True)
    once = moe_gmm.gmm_reference(x, w, owner, active, tm, activation)
    live = int(active[0]) * tm
    assert got.shape == (6 * tm, f) and got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got[:live], np.float32),
                                  np.asarray(once[:live], np.float32))
    assert np.isnan(np.asarray(got[live:], np.float32)).all()
    twice = {"swiglu": swiglu, "relu2": relu2}[activation](
        moe_gmm.gmm_reference(x, w, owner, active, tm))
    np.testing.assert_allclose(np.asarray(got[:live], np.float32),
                               np.asarray(twice[:live], np.float32),
                               rtol=2 ** -7, atol=2 ** -7)


@pytest.mark.parametrize("dtype,tiles,step", [
    ("float32", 9, 16), ("bfloat16", 9, 16), ("bfloat16", 160, 512)])
def test_gather_and_combine_touch_the_live_rows_only(dtype, tiles, step):
    """`gather_rows` writes no row past the last live step of the grid
    (a step walks `step_rows` of the buffer; what it leaves still holds
    the interpreter's NaN), and `combine_rows` sums only rows that hold
    a token: a buffer that is NaN everywhere else gives the exact
    weighted sums."""
    from paddle_tpu.kernels import moe_gmm

    tm, k, n_tokens = 16, 256, 50
    assert moe_gmm.step_rows(tiles * tm, tm) == step
    token, weight, _owner, active = sorted_tiles(
        80, [17, 3, 0, 33], tm, n_tokens, tiles)
    src = jnp.asarray(rand(81, n_tokens, k), dtype)
    rows = moe_gmm.gather_rows(src, token, active, tm, interpret=True)
    held = np.asarray(token) >= 0
    np.testing.assert_array_equal(
        np.asarray(rows, np.float32)[held],
        np.asarray(src, np.float32)[np.asarray(token)[held]])
    live = int(active[0]) * tm
    assert live == 6 * tm and np.isfinite(
        np.asarray(rows[:live], np.float32)).all()
    assert np.isnan(np.asarray(rows[-(-live // step) * step:],
                               np.float32)).all()
    poisoned = jnp.where(jnp.asarray(held)[:, None], rows, jnp.nan)
    got = moe_gmm.combine_rows(poisoned, token, weight, active, tm, n_tokens,
                               interpret=True)
    want = np.zeros((n_tokens, k), np.float32)
    np.add.at(want, np.asarray(token)[held],
              np.asarray(weight)[held, None]
              * np.asarray(rows, np.float32)[held])
    assert got.dtype == src.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(jnp.asarray(want).astype(dtype), np.float32),
        rtol=2 ** -7 if dtype == "bfloat16" else 1e-6, atol=1e-6)
    named = np.zeros(n_tokens, bool)
    named[np.asarray(token)[held]] = True
    assert not np.asarray(got, np.float32)[~named].any()        # exact zeros


def test_eight_shares_and_the_shared_expert_once_make_the_whole_layer():
    """The share test: each of the eight chips' routed part (2 of 16
    experts), plus the shared expert counted once, add up to the uncut
    reference layer."""
    from benchmark.reference import afmoe as reference

    w = expert_weights(40, 16)
    shared_gu, shared_d = rand(44, 32, 32, scale=0.2), rand(45, 16, 32,
                                                            scale=0.2)
    x = rand(46, 2, 12, 32)
    total = np.zeros_like(x)
    for chip in range(8):
        part, _sel, _n = moe.local_experts_ffn(
            jnp.asarray(x), w["router_w"], w["bias"],
            w["wgu"][2 * chip:2 * chip + 2], w["wd"][2 * chip:2 * chip + 2],
            top_k=4, route_scale=2.448, expert_offset=2 * chip)
        total += np.asarray(part)
    with jax.default_matmul_precision("highest"):
        total += np.asarray(reference.swiglu_ffn(jnp.asarray(x), shared_gu,
                                                 shared_d))
        p = {"l_router_w": w["router_w"], "l_expert_bias": w["bias"],
             "l_experts_gate_up_w": w["wgu"], "l_experts_down_w": w["wd"],
             "l_shared_gate_up_w": shared_gu, "l_shared_down_w": shared_d}
        whole, _sel, _r = reference.expert_ffn(
            p, "l", jnp.asarray(x),
            {"top_k": 4, "route_scale": 2.448, "route_norm": True,
             "expert_offset": 0, "num_shared_experts": 1})
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)


def test_expert_bias_moves_the_selection_only():
    w = expert_weights(50, 4)
    x = rand(51, 1, 6, 32)
    tokens = jnp.asarray(x.reshape(6, 32))
    sel0, w0 = moe.sigmoid_topk_route(tokens, w["router_w"],
                                      np.zeros(16, np.float32), 4, 1.0)
    pushed = int(sel0[0, 0])
    bias = np.zeros(16, np.float32)
    bias[pushed] = -10.0                   # out of every token's top-4
    sel1, w1 = moe.sigmoid_topk_route(tokens, w["router_w"], bias, 4, 1.0)
    assert pushed not in np.asarray(sel1)
    np.testing.assert_allclose(np.asarray(w1).sum(-1), 1.0, rtol=1e-5)
    # tokens that never chose it keep ids AND weights: the bias is in no
    # weight
    same = ~(np.asarray(sel0) == pushed).any(-1)
    assert same.any()
    np.testing.assert_array_equal(np.asarray(sel0)[same],
                                  np.asarray(sel1)[same])
    np.testing.assert_allclose(np.asarray(w0)[same], np.asarray(w1)[same])
    # and a token that did choose it now weighs its new four by their
    # own scores, not by scores + bias
    scores = jax.nn.sigmoid(tokens @ w["router_w"])
    picked = np.take_along_axis(np.asarray(scores), np.asarray(sel1), -1)
    np.testing.assert_allclose(
        w1, picked / picked.sum(-1, keepdims=True), rtol=1e-5)


# -- the decoder through the generator ---------------------------------------

def tiny_generator(batch=2, context=24, new=8, **kw):
    cfg = AfmoeConfig.tiny(**kw)
    gen = GPTGenerator(AfmoeDecoder(cfg), batch=batch, context_len=context,
                       max_len=context + new)
    gen.init_params(seed=7)
    return gen


def test_layer_kinds_and_state_specs():
    gen = tiny_generator()
    kinds = gen.cfg.layer_kinds
    assert kinds[0] == (SLIDING, DENSE) and kinds[-1] == (FULL, EXPERTS)
    assert sum(k == (SLIDING, EXPERTS) for k in kinds) == 3
    specs = {n: (s, d) for n, s, d in gen._state_specs}
    assert specs["afmoe_l0_cache_k"] == (
        kv_cache.cache_shape(2, 32, 2, 16, window=8), "bfloat16")   # ring
    assert specs["afmoe_l4_cache_v"] == (
        kv_cache.cache_shape(2, 32, 2, 16), "bfloat16")             # full
    assert specs["afmoe_moe_counters"][1] == "int32"
    gen.reset()
    for name, (shape, _d) in specs.items():
        held = gen.scope.find_var(name)
        assert held.shape == shape and not np.asarray(held).any()


@pytest.mark.parametrize("prefill_rows", [None, 1])
def test_prefill_then_cached_decode_match_the_reference(prefill_rows):
    """Context 24 = 3 x the tiny window of 8, so every window layer's
    ring has wrapped before the first decode step; the full layer reads
    all 24 + t. Both against the reference's full forward pass."""
    from benchmark.builders import afmoe as builder

    gen = tiny_generator(prefill_rows=prefill_rows)
    prompts = np.random.RandomState(5).randint(0, 256, (2, 24))
    seen = builder.probe_generator(gen, prompts, decode_steps=8)
    report = builder.compare(gen, seen, tol=2e-2)
    assert report["ok"], report
    assert report["decode_routing"]["mismatches"] == 0
    assert report["decode_routing"]["tokens"] == 4 * 2 * 32


def test_depth_scaled_gains_and_the_bias_spread_are_initialisations_only():
    """`norm_out_gain` seeds the two output norms (N2, N4) of every layer
    and no other gain, `expert_bias_std` the router's bias buffer; the
    forward equations are the reference's as before (it reads the gains
    it is given)."""
    from benchmark.builders import afmoe as builder

    gen = tiny_generator(norm_out_gain=0.25, expert_bias_std=0.001)

    def held(name):
        return np.asarray(gen.scope.find_var(name)).astype(np.float32)

    for i in range(gen.cfg.num_layers):
        for n, want in (("n1", 1.0), ("n2", 0.25), ("n3", 1.0), ("n4", 0.25),
                        ("attn_qn", 1.0), ("attn_kn", 1.0)):
            gain = held(f"afmoe_l{i}_{n}")
            assert abs(gain.mean() / want - 1) < 0.02, (i, n)
            assert 0.005 < gain.std() / want < 0.04, (i, n)
    assert abs(held("afmoe_norm_f").mean() - 1) < 0.02
    bias = held("afmoe_l1_expert_bias")
    assert 0 < np.abs(bias).max() < 0.005
    assert np.abs(held("afmoe_l1_router_w")).max() > 0.02
    prompts = np.random.RandomState(5).randint(0, 256, (2, 24))
    seen = builder.probe_generator(gen, prompts, decode_steps=8)
    report = builder.compare(gen, seen, tol=2e-2)
    assert report["ok"], report


def test_window_layers_forget_and_full_layers_remember():
    """Changing a token further back than the window changes the next
    logits only through the full-attention layer; with every layer
    sliding it changes nothing at all."""
    def next_logits(kinds, prompts):
        gen = tiny_generator(context=24, layer_kinds=kinds)
        logits = gen.executor.run(
            gen.prefill_prog, feed={"context_ids": prompts},
            fetch_list=gen._prefill_fetch, scope=gen.scope)[0]
        return np.asarray(logits)

    a = np.random.RandomState(6).randint(0, 256, (2, 24))
    b = a.copy()
    b[:, 2] = (b[:, 2] + 1) % 256
    sliding = ((SLIDING, DENSE), (SLIDING, EXPERTS))
    np.testing.assert_array_equal(next_logits(sliding, a),
                                  next_logits(sliding, b))
    mixed = ((SLIDING, DENSE), (FULL, EXPERTS))
    assert np.abs(next_logits(mixed, a) - next_logits(mixed, b)).max() > 0


def test_counters_are_read_once_a_batch():
    from paddle_tpu import observability as obs

    obs.reset()
    gen = tiny_generator()
    gen.generate(np.random.RandomState(8).randint(0, 256, (2, 24)), 8)
    got = obs.get_counters()
    layers, tokens = 4, 2 * 24 + 2 * 7
    assert got["moe.assignments_total"] == layers * tokens * 4
    assert got["moe.calls"] == layers * (1 + 7)
    assert got["moe.decode_calls"] == layers * 7
    assert 0 < got["moe.assignments_local"] < got["moe.assignments_total"]
    assert got["moe.experts_hit"] <= got["moe.calls"] * 2
    # the sorted buffer: per call ceil(A / 16) + 2 tiles of 16 rows are
    # allocated; the live ones hold the local rows and, an expert and
    # call, less than a tile of padding
    assert got["moe.rows_buffer"] == layers * 16 * (
        (2 * 24 * 4 // 16 + 2) + 7 * (1 + 2))
    assert got["moe.rows_live"] % 16 == 0
    assert 0 <= got["moe.rows_live"] - got["moe.assignments_local"] <= (
        got["moe.experts_hit"] * 15)
    assert got["moe.rows_live"] < got["moe.rows_buffer"]
    spans = [s for s in obs.get_spans()
             if s["name"] == "serving.step_counters"]
    assert len(spans) == 1
    assert spans[0]["args"]["moe.assignments_total"] == layers * tokens * 4
    assert spans[0]["args"]["moe.rows_live"] == got["moe.rows_live"]
    gauges = obs.get_gauges()
    assert gauges["kv_cache.bytes.window"] == 4 * 2 * (2 * 2 * 16 * 8) * 2
    assert gauges["kv_cache.bytes.full"] == 2 * (2 * 2 * 16 * 32) * 2


def test_bfloat16_parameters_stay_bfloat16_through_startup_save_load(tmp_path):
    gen = tiny_generator()
    params = gen._param_vars()
    assert len(params) > 40
    for v in params:
        held = gen.scope.find_var(v.name)
        want = "float32" if v.name.endswith("_expert_bias") else "bfloat16"
        assert str(held.dtype) == want == v.dtype, v.name
    before = {v.name: np.asarray(gen.scope.find_var(v.name)) for v in params}
    path = str(tmp_path / "afmoe")
    gen.save_params(path)
    other = GPTGenerator(AfmoeDecoder(gen.cfg), batch=2, context_len=24,
                         max_len=32)
    other.load_params(path)
    for name, value in before.items():
        held = other.scope.find_var(name)
        assert str(held.dtype) == str(value.dtype), name
        np.testing.assert_array_equal(np.asarray(held), value)
    ids = np.random.RandomState(9).randint(0, 256, (2, 24))
    np.testing.assert_array_equal(gen.generate(ids, 4),
                                  other.generate(ids, 4))


def test_one_generator_class_serves_both_decoders():
    from paddle_tpu.models.gpt import GPTConfig, GPTDecoder
    from paddle_tpu.serving.generate import GPTGenerateRunner

    gpt = GPTGenerator(GPTConfig.tiny(), batch=2, context_len=8, max_len=12)
    gpt.init_params(seed=1)
    assert isinstance(gpt.decoder, GPTDecoder)
    afmoe = tiny_generator()
    assert type(gpt) is type(afmoe)
    for gen in (gpt, afmoe):
        runner = GPTGenerateRunner(gen, max_new_tokens=3)
        ids = np.zeros((2, gen.context_len), np.int64)
        (tokens,) = runner.run({"context_ids": ids})
        assert tokens.shape == (2, 3)
    with pytest.raises(fluid.errors.InvalidArgumentError):
        afmoe.generate_full_recompute(
            np.zeros((2, 24), np.int64), 2)


def test_configuration_file_keeps_every_published_width():
    with open(os.path.join(
            ROOT, "benchmark/configs/trinity_large_ep8.json")) as f:
        cfg_json = json.load(f)
    from benchmark.builders import afmoe as builder

    cfg = builder.model_config(cfg_json)
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.intermediate_size, cfg.moe_intermediate_size) == \
        (3072, 48, 8, 128, 12288, 3072)
    assert (cfg.num_experts, cfg.num_local_experts, cfg.top_k,
            cfg.route_scale, cfg.sliding_window) == (256, 32, 4, 2.448, 4096)
    assert cfg.layer_kinds == (
        (SLIDING, DENSE), (SLIDING, EXPERTS), (SLIDING, EXPERTS),
        (SLIDING, EXPERTS), (FULL, EXPERTS))
    assert set(cfg_json["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"}


def test_the_probe_runs_the_executables_that_serve():
    """The benchmark's probe uses the generator's own fetch lists (the
    logits and, beside them, the selected expert ids), so what it
    compares with the reference is what a request's batch computes: no
    executable is compiled for it alone, and a request's ids are the
    argmax chain of the probed logits, bit for bit."""
    from benchmark.builders import afmoe as builder

    gen = tiny_generator()
    assert len(gen._prefill_fetch) == len(gen._decode_fetch) == 2
    prompts = np.random.RandomState(11).randint(0, 256, (2, 24))
    seen = builder.probe_generator(gen, prompts, decode_steps=5)
    assert [p.shape for p in seen[1][2]] == [(2, 29, 4)] * 4
    compiled = len(gen.executor._cache)
    ids = gen.generate(prompts, 6)
    # the start-up program, the prefill and the decode step
    assert len(gen.executor._cache) == compiled == 3
    np.testing.assert_array_equal(ids[:, :5], seen[1][0][:, 24:])
    # GPT-2's programs are left alone: the logits are all they fetch
    from paddle_tpu.models.gpt import GPTConfig

    gpt = GPTGenerator(GPTConfig.tiny(), batch=2, context_len=8, max_len=12)
    assert len(gpt._prefill_fetch) == len(gpt._decode_fetch) == 1


def test_the_router_orders_scores_a_bfloat16_router_would_tie():
    """Router scores are float32 out of bfloat16 operands (the
    configuration states a float32 router). Two experts whose logits
    differ by 2^-9 at 1.0 are one value in bfloat16, where top-k would
    take the lower index; float32 tells them apart, and the weights
    carry the difference."""
    bf16 = jnp.bfloat16
    tokens = jnp.asarray([[1.0, 2.0 ** -9, 0.0, 0.0]] * 3, bf16)
    router_w = jnp.asarray(np.array(
        # expert:  0     1     2     3
        [[3.0, 1.0, 1.0, -4.0],
         [0.0, 0.0, 1.0, 0.0],
         [0.0, 0.0, 0.0, 0.0],
         [0.0, 0.0, 0.0, 0.0]], np.float32), bf16)
    logits = np.array([3.0, 1.0, 1.0 + 2.0 ** -9, -4.0])
    assert float(jnp.asarray(logits[2], bf16)) == 1.0     # bfloat16 ties
    sel, w = moe.sigmoid_topk_route(tokens, router_w,
                                    np.zeros(4, np.float32), 2, 1.0)
    np.testing.assert_array_equal(np.sort(np.asarray(sel), -1),
                                  [[0, 2]] * 3)
    scores = 1.0 / (1.0 + np.exp(-logits))
    want = scores[[0, 2]] / scores[[0, 2]].sum()
    order = np.argsort(np.asarray(sel), -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(w), order, -1), [want] * 3, rtol=2e-6)
    tied = scores[[0, 1]] / scores[[0, 1]].sum()
    assert abs(tied[1] - want[1]) > 50 * 2e-6 * want[1]


def test_cache_write_takes_a_row_block_only_into_a_ring_layout():
    cache = np.zeros(kv_cache.cache_shape(4, 8, 2, 16), np.float32)
    rows = rand(12, 2, 8, 32)
    feeds = {"c": cache, "x": rows, "p": np.zeros(1, np.int32),
             "r": np.array([2], np.int64)}
    slots = {"Cache": "c", "X": "x", "Pos": "p", "Row": "r"}
    got = run_op("kv_cache_write", feeds, {"ring": True}, slots)
    assert not got[:2].any() and got[2:].any()
    with pytest.raises(fluid.errors.InvalidArgumentError):
        run_op("kv_cache_write", feeds, {"ring": False}, slots)
