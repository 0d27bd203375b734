"""dots_vlm serving path (models/dots_vlm.py): YaRN rotary positions over
part of a head, attention whose value heads are narrower than its key
heads, group-limited routing and its shares of an expert-parallel
deployment, the one-operand form of the decode attention kernel, the
latent cache's bookkeeping, and the decoder (prefill EXPANDED, cached
decode ABSORBED) against the plain reference (benchmark/reference/
dots_vlm.py, expanded everywhere). CPU, tiny sizes, seeded weights."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu.kernels import decode_attention as kernel
from paddle_tpu.models.dots_vlm import (
    DENSE, EXPERTS, LATENT, DotsVlmConfig, DotsVlmDecoder,
)
from paddle_tpu.ops import kv_cache, llm
from paddle_tpu.parallel import moe
from paddle_tpu.serving import GPTGenerator

from test_afmoe import dense_attention, rand, run_op

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
YARN = dict(factor=40, original_max_position_embeddings=4096, beta_fast=32,
            beta_slow=1, mscale=1, mscale_all_dim=1)


# -- rotary positions ---------------------------------------------------------

def yarn_closed_form(dim, theta, yarn):
    """The issue's section 1, transcribed."""
    i = np.arange(dim // 2)
    f = theta ** (-2.0 * i / dim)

    def d(r):
        return dim * np.log(yarn["original_max_position_embeddings"]
                            / (2 * np.pi * r)) / (2 * np.log(theta))

    low, high = np.floor(d(yarn["beta_fast"])), np.ceil(d(yarn["beta_slow"]))
    ramp = np.clip((i - low) / (high - low), 0, 1)
    return f * (1 - ramp) + f / yarn["factor"] * ramp, int(low), int(high)


def test_yarn_ramp_turns_at_pairs_10_and_23_at_the_published_sizes():
    ramp, low, high = llm.yarn_ramp(64, 10000.0, YARN)
    assert (low, high) == (10, 23)
    assert ramp[:11].max() == 0.0 and ramp[23:].min() == 1.0
    assert 0.0 < ramp[11] < ramp[22] < 1.0
    inv, want_low, want_high = yarn_closed_form(64, 10000.0, YARN)
    assert (want_low, want_high) == (10, 23)
    f = 10000.0 ** (-np.arange(32) * 2.0 / 64)
    np.testing.assert_allclose(f * (1 - ramp) + f / 40 * ramp, inv,
                               rtol=1e-6)
    assert llm.yarn_mscale(40, 1) == pytest.approx(1.36889, abs=1e-5)
    assert llm.yarn_mscale(1, 1) == 1.0


@pytest.mark.parametrize("head_dim,rotary_dim", [(64, 64), (192, 64)])
def test_yarn_rotary_against_the_closed_form(head_dim, rotary_dim):
    """Rotate-half over the LAST `rotary_dim` lanes of each head at the
    blended frequencies; the lanes before them pass through."""
    x = rand(3, 2, 5, 2 * head_dim)
    got = run_op("rotary_embedding",
                 {"x": x, "pos": np.array([[1003]], np.int64)},
                 {"head_dim": head_dim, "theta": 10000.0,
                  "rotary_dim": rotary_dim, "yarn": YARN},
                 {"X": "x", "Pos": "pos"})
    inv, _l, _h = yarn_closed_form(rotary_dim, 10000.0, YARN)
    ang = (999 + np.arange(5))[:, None] * inv[None]
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    xh = x.reshape(2, 5, 2, head_dim).astype(np.float64)
    keep = xh[..., :head_dim - rotary_dim]
    turn = xh[..., head_dim - rotary_dim:]
    x1, x2 = turn[..., :rotary_dim // 2], turn[..., rotary_dim // 2:]
    want = np.concatenate(
        [keep, x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    np.testing.assert_allclose(got, want.reshape(x.shape), rtol=2e-4,
                               atol=2e-4)


def test_rotary_without_the_new_attributes_is_todays():
    """Absent `rotary_dim` and `yarn`: the whole head at the plain
    frequencies, bit for bit what the function computed before them."""
    x = jnp.asarray(rand(4, 2, 6, 64))

    def before(x, first_pos, head_dim, theta):      # PR 32's body
        b, t, h = x.shape
        half = head_dim // 2
        inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0
                             / head_dim)
        pos = (first_pos + jnp.arange(t, dtype=jnp.int32)).astype(
            jnp.float32)
        angle = pos[:, None] * inv_freq[None, :]
        cos = jnp.cos(angle)[None, :, None, :]
        sin = jnp.sin(angle)[None, :, None, :]
        xf = x.astype(jnp.float32).reshape(b, t, h // head_dim, head_dim)
        x1, x2 = xf[..., :half], xf[..., half:]
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
        return out.reshape(b, t, h).astype(x.dtype)

    args = (x, jnp.int32(17), 16, 10000.0)
    np.testing.assert_array_equal(llm.rotary(*args), before(*args))
    assert str(jax.make_jaxpr(llm.rotary, static_argnums=(2, 3))(*args)) \
        == str(jax.make_jaxpr(before, static_argnums=(2, 3))(*args))


# -- attention with a value width of its own ----------------------------------

@pytest.mark.parametrize("budget,block", [(None, (2, 12)), (1000, (1, 4))])
def test_causal_attention_with_narrower_values_and_a_cut_query_axis(
        budget, block, monkeypatch):
    """Key heads of 24 lanes, value heads of 16; with a small score
    budget one sequence's queries are walked in blocks of 4."""
    if budget:
        monkeypatch.setattr(llm, "SCORE_BLOCK_BYTES", budget)
    assert llm._query_block(2, 4, 12) == block
    q, k, v = rand(6, 2, 12, 96), rand(7, 2, 12, 96), rand(8, 2, 12, 64)
    got = run_op("causal_gqa_attention", {"q": q, "k": k, "v": v},
                 {"num_heads": 4, "num_kv_heads": 4, "window": 0,
                  "scale": 0.2}, {"Q": "q", "K": "k", "V": "v"})
    assert got.shape == (2, 12, 64)
    # the dense form on values padded to the key width, cut back
    vp = np.concatenate([v.reshape(2, 12, 4, 16),
                         np.zeros((2, 12, 4, 8), np.float32)], -1)
    want = dense_attention(q, k, vp.reshape(2, 12, 96), 4, 4, 0, 0.2)
    want = want.reshape(2, 12, 4, 24)[..., :16].reshape(2, 12, 64)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# -- the decode kernel with one cache operand ---------------------------------

BLOCK = 16
# name: (query heads, KV heads, row width a KV head, value width, tolerance)
SHARED = {
    "latent-128over1x576": (128, 1, 576, 512, 3e-2),
    "latent-4over1x576": (4, 1, 576, 512, 3e-2),
    "latent-128over1x640-whole-tiles": (128, 1, 640, 512, 3e-2),
    "two-kv-heads-8over2x256": (8, 2, 256, 128, 3e-2),
}
POSITIONS = {"first": 0, "inside-a-block": BLOCK + 5,
             "block-last": 2 * BLOCK - 1, "last-slot": 63}


@pytest.mark.parametrize("position", list(POSITIONS))
@pytest.mark.parametrize("geometry", list(SHARED))
def test_decode_kernel_reads_values_out_of_the_key_rows(
        geometry, position, monkeypatch):
    """ONE cache operand: the values are the leading `value_width` lanes
    of each KV head's row. The kernel (interpreted) against the `jnp`
    form over `value_lanes`, four blocks of 16 slots."""
    nh, nkv, dh, vw, tol = SHARED[geometry]
    pos = POSITIONS[position]
    monkeypatch.setattr(kernel, "BLOCK_BYTES", BLOCK * nkv * dh * 2)
    rng = np.random.RandomState(nh + pos)
    q = jnp.asarray(rng.randn(2, nh * dh) * 0.3, jnp.bfloat16)
    k = jnp.asarray(rng.randn(2, 64, nkv * dh), jnp.bfloat16)
    args = (q, k, None, jnp.int32(pos), nkv, dh ** -0.5, 0, 1.0, vw)
    want, ran = kv_cache.decode_attention(*args)
    assert not ran and want.shape == (2, nh * vw)
    values = kv_cache.value_lanes(k, nkv, vw)
    np.testing.assert_array_equal(
        np.asarray(values, np.float32).reshape(2, 64, nkv, vw),
        np.asarray(k, np.float32).reshape(2, 64, nkv, dh)[..., :vw])
    got, ran = kv_cache.decode_attention(*args, interpret=True)
    assert ran and got.dtype == q.dtype and got.shape == want.shape
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=0)


def test_one_kv_head_is_not_padded_to_a_sublane_tile_of_heads():
    """With one KV head every query row reads the whole row: 128 query
    rows in the kernel's scratch, not 8 x 128."""
    seen = {}
    real = kernel.pl.pallas_call

    def spy(body, **kw):
        seen["scratch"] = [s.shape for s in kw["grid_spec"].scratch_shapes]
        seen["in_specs"] = len(kw["grid_spec"].in_specs)
        seen["name"] = kw["name"]
        return real(body, **kw)

    import unittest.mock as mock

    q = jnp.zeros((1, 128 * 640), jnp.bfloat16)
    k = jnp.zeros((1, 32, 640), jnp.bfloat16)
    with mock.patch.object(kernel.pl, "pallas_call", spy):
        kernel.attend(q, k, None, jnp.int32(3), num_kv_heads=1, scale=1.0,
                      value_width=512, interpret=True)
    assert seen["scratch"] == [(128, 1), (128, 1), (128, 512)]
    assert seen["in_specs"] == 2            # the query and ONE cache
    assert seen["name"].startswith("decode_attention")


def test_latent_cache_rows_are_whole_lane_tiles():
    assert kv_cache.latent_cache_shape(64, 1024, 576) == (64, 1024, 640)
    assert kv_cache.latent_cache_shape(2, 32, 12) == (2, 32, 128)
    assert kv_cache.latent_cache_shape(2, 32, 256) == (2, 32, 256)


# -- group-limited routing ----------------------------------------------------

def numpy_route(tokens, router_w, bias, top_k, scale, n_group, topk_group):
    """Section 1's expert layer routing, transcribed."""
    sc = 1.0 / (1.0 + np.exp(-(tokens.astype(np.float64)
                               @ router_w.astype(np.float64))))
    c = sc + bias
    t, e = c.shape
    groups = c.reshape(t, n_group, e // n_group)
    group_score = np.sort(groups, -1)[..., -2:].sum(-1)
    kept = np.argsort(-group_score, -1, kind="stable")[:, :topk_group]
    mask = np.zeros((t, n_group), bool)
    np.put_along_axis(mask, kept, True, -1)
    c = np.where(mask[:, :, None], groups, 0.0).reshape(t, e)
    sel = np.argsort(-c, -1, kind="stable")[:, :top_k]
    w = np.take_along_axis(sc, sel, -1)
    return sel, w / (w.sum(-1, keepdims=True) + 1e-20) * scale, kept


def test_group_limited_routing_against_numpy():
    tokens, router_w = rand(60, 40, 32), rand(61, 32, 32, scale=0.4)
    bias = rand(62, 32, scale=0.01)
    sel, w = moe.sigmoid_topk_route(jnp.asarray(tokens), router_w, bias, 4,
                                    2.5, True, n_group=8, topk_group=3)
    want_sel, want_w, kept = numpy_route(tokens, router_w, bias, 4, 2.5, 8, 3)
    np.testing.assert_array_equal(np.sort(sel, -1), np.sort(want_sel, -1))
    np.testing.assert_allclose(np.sort(w, -1), np.sort(want_w, -1),
                               rtol=1e-5)
    # every selected expert lies in one of its token's kept groups
    assert all(set(np.asarray(sel[t]) // 4) <= set(kept[t])
               for t in range(40))
    # and some token's best expert overall lies in a group it dropped
    scores = 1 / (1 + np.exp(-(tokens @ router_w))) + bias
    best = scores.argmax(-1)
    dropped = [t for t in range(40) if best[t] // 4 not in kept[t]]
    assert dropped and all(best[t] not in np.asarray(sel[t])
                           for t in dropped)


def test_a_best_expert_in_a_dropped_group_is_not_selected():
    """Expert 0 scores highest for the token but stands alone in group
    0, whose two-best sum loses to the groups with two good experts."""
    router_w = np.zeros((4, 8), np.float32)
    bias = np.array([0.9, -0.4, 0.5, 0.5, 0.45, 0.45, 0.1, 0.1], np.float32)
    tokens = jnp.zeros((1, 4), jnp.float32)      # every sigmoid is 1/2
    sel, w = moe.sigmoid_topk_route(tokens, router_w, bias, 2, 1.0, True,
                                    n_group=4, topk_group=2)
    assert sorted(np.asarray(sel[0]).tolist()) == [2, 3]
    np.testing.assert_allclose(w, [[0.5, 0.5]])  # the bias is in no weight
    free, _w = moe.sigmoid_topk_route(tokens, router_w, bias, 2, 1.0, True)
    assert 0 in np.asarray(free[0])


def test_one_group_is_todays_selection():
    tokens, router_w = rand(63, 12, 32), rand(64, 32, 16, scale=0.4)
    bias = rand(65, 16, scale=0.01)
    args = (jnp.asarray(tokens), router_w, bias, 4, 2.448)

    def before(tokens, router_w, expert_bias, top_k, route_scale):  # PR 32
        from paddle_tpu.ops._helpers import einsum_f32

        logits = einsum_f32("th,he->te", tokens, router_w)
        scores = jax.nn.sigmoid(logits)
        _, sel = jax.lax.top_k(scores + expert_bias.astype(jnp.float32),
                               top_k)
        w = jnp.take_along_axis(scores, sel, axis=-1)
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
        return sel.astype(jnp.int32), w * route_scale

    for got, want in zip(moe.sigmoid_topk_route(*args, n_group=1,
                                                topk_group=1),
                         before(*args)):
        np.testing.assert_array_equal(got, want)
    static = (3, 4)
    assert str(jax.make_jaxpr(moe.sigmoid_topk_route,
                              static_argnums=static)(*args)) \
        == str(jax.make_jaxpr(before, static_argnums=static)(*args))


def test_the_shares_and_the_shared_expert_once_make_the_whole_layer():
    """The share test under group-limited routing: each of four chips'
    routed part (4 of 16 experts = one whole group of 4 here; on the
    chip 16 experts are HALF a group of 32), plus the shared expert
    counted once, add up to the uncut reference layer."""
    from benchmark.reference import dots_vlm as reference

    h, f, e = 32, 16, 16
    router_w, bias = rand(70, h, e, scale=0.3), rand(71, e, scale=0.01)
    wgu, wd = rand(72, e, h, 2 * f, scale=0.2), rand(73, e, f, h, scale=0.2)
    shared_gu, shared_d = rand(74, h, 2 * f, scale=0.2), rand(75, f, h,
                                                              scale=0.2)
    x = rand(76, 2, 12, h)
    total = np.zeros_like(x)
    local = []
    for chip in range(4):
        part, _sel, counts = moe.local_experts_ffn(
            jnp.asarray(x), router_w, bias, wgu[4 * chip:4 * chip + 4],
            wd[4 * chip:4 * chip + 4], top_k=4, route_scale=2.5,
            expert_offset=4 * chip, n_group=4, topk_group=2)
        total += np.asarray(part)
        local.append(int(counts.sum()))
    assert sum(local) == 2 * 12 * 4         # every assignment lives somewhere
    cfg = {"top_k": 4, "n_group": 4, "topk_group": 2, "route_scale": 2.5,
           "route_norm": True, "expert_offset": 0, "num_shared_experts": 1}
    p = {"l_router_w": router_w, "l_expert_bias": bias,
         "l_experts_gate_up_w": wgu, "l_experts_down_w": wd,
         "l_shared_gate_up_w": shared_gu, "l_shared_down_w": shared_d}
    with jax.default_matmul_precision("highest"):
        total += np.asarray(reference.gated_ffn(jnp.asarray(x), shared_gu,
                                                shared_d))
        whole, sel, _r = reference.expert_ffn(p, "l", jnp.asarray(x), cfg)
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-5)
    # a token reaches at most two of the four chips
    assert (np.array([len(set(row // 4)) for row in
                      np.asarray(sel).reshape(-1, 4)]) <= 2).all()


def test_the_reference_follows_a_flipped_group_only_through_ambiguity():
    """Two groups whose scores differ by less than 2 x tie_eps: the
    program may keep either, and its ids are then judged against the
    k-th best of ITS groups; a group that loses clearly is a mismatch."""
    from benchmark.reference import dots_vlm as reference

    cfg = {"top_k": 2, "n_group": 4, "topk_group": 1, "route_scale": 1.0,
           "route_norm": True}
    logit = lambda s: np.log(s / (1 - s))                       # noqa: E731
    scores = np.array([[0.60, 0.50, 0.599, 0.5005, 0.40, 0.40, 0.30, 0.30]])
    p = {"l_router_w": logit(scores).astype(np.float32),
         "l_expert_bias": np.zeros(8, np.float32)}
    x = jnp.ones((1, 1), jnp.float32)
    with jax.default_matmul_precision("highest"):
        sel, _w, _r = reference.route(p, "l", x, cfg)
        assert sorted(np.asarray(sel[0]).tolist()) == [0, 1]
        near = reference.route(p, "l", x, cfg, follow=[[2, 3]], tie_eps=0.01)
        far = reference.route(p, "l", x, cfg, follow=[[4, 5]], tie_eps=0.01)
    assert sorted(np.asarray(near[0][0]).tolist()) == [2, 3]
    assert near[2]["near_ties"] == 1 and near[2]["mismatches"] == 0
    assert near[2]["groups_differ"] == 1
    assert sorted(np.asarray(far[0][0]).tolist()) == [0, 1]
    assert far[2]["mismatches"] == 1


# -- the decoder through the generator ----------------------------------------

def tiny_generator(batch=2, context=24, new=10, **kw):
    cfg = DotsVlmConfig.tiny(**kw)
    gen = GPTGenerator(DotsVlmDecoder(cfg), batch=batch, context_len=context,
                       max_len=context + new)
    gen.init_params(seed=7)
    return gen


def test_one_latent_cache_a_layer_and_its_bookkeeping():
    obs.reset()
    gen = tiny_generator()
    assert gen.cfg.layer_kinds == ((LATENT, DENSE), (LATENT, EXPERTS),
                                   (LATENT, EXPERTS))
    specs = {n: (s, d) for n, s, d in gen._state_specs}
    caches = [n for n in specs if "_cache_" in n]
    assert caches == [f"dots_l{i}_cache_kv" for i in range(3)]
    # kv_lora_rank 8 + rope 4 = 12 lanes of data in one 128-lane tile
    assert specs["dots_l0_cache_kv"] == ((2, 34, 128), gen.cfg.dtype)
    assert gen._state_kinds["dots_l1_cache_kv"] == "latent"
    assert gen._state_kinds["dots_moe_counters"] is None
    itemsize = 2 if gen.cfg.dtype == "bfloat16" else 4
    assert obs.get_gauges()["kv_cache.bytes.latent"] == \
        3 * 2 * 34 * 128 * itemsize
    model = obs.get_tables()["serving.generate.model"]
    assert model["family"] == "dots_vlm" and model["kv_lora_rank"] == 8
    assert [k[1] for k in model["layer_kinds"]] == ["dense", "experts",
                                                    "experts"]
    # a decode step NEEDS the 12 lanes that carry data of every row a
    # query may see, once a layer
    prompts = np.random.RandomState(3).randint(0, 256, (2, 24))
    gen.generate(prompts, 10)
    want = sum(3 * 2 * 12 * itemsize * (p + 1) for p in range(24, 33))
    got = obs.get_counters()
    assert got["kv_cache.decode_bytes_needed"] == want
    assert got["kv_cache.decode_steps"] == 9


@pytest.mark.parametrize("prefill_rows", [None, 1])
def test_prefill_expanded_then_decode_absorbed_match_the_reference(
        prefill_rows):
    """float32: the prefill attends in the expanded form, nine cached
    steps in the absorbed form through the latent cache; the reference
    is expanded at every position of the grown prefix."""
    from benchmark.builders import dots_vlm as builder

    gen = tiny_generator(prefill_rows=prefill_rows, dtype="float32")
    prompts = np.random.RandomState(5).randint(0, 256, (2, 24))
    seen = builder.probe_generator(gen, prompts, decode_steps=9)
    report = builder.compare(gen, seen, tol=2e-5)
    assert report["ok"], report
    assert report["prefill_err"] < 2e-5 and report["decode_err"] < 2e-5
    assert report["decode_routing"]["mismatches"] == 0
    assert report["decode_routing"]["tokens"] == 2 * 2 * 33


def test_the_absorbed_step_through_the_kernel_matches_too(monkeypatch):
    """The same in bfloat16 with the decode steps' attention through the
    Pallas kernel (interpreted): one call a layer, one cache operand."""
    import functools

    from benchmark.builders import dots_vlm as builder

    obs.reset()
    monkeypatch.setattr(
        kv_cache, "decode_attention",
        functools.partial(kv_cache.decode_attention, interpret=True))
    gen = tiny_generator()
    prompts = np.random.RandomState(6).randint(0, 256, (2, 24))
    seen = builder.probe_generator(gen, prompts, decode_steps=8)
    report = builder.compare(gen, seen, tol=4e-2)
    assert report["ok"], report
    assert obs.get_gauges()["kernels.decode_attention.calls"] == 3


def test_cached_decode_equals_a_fresh_prefill_of_the_grown_prompt():
    """Token for token: what the absorbed steps choose is what an
    expanded prefill of the same, longer, prompt chooses next."""
    gen = tiny_generator(dtype="float32", context=16, new=6)
    prompts = np.random.RandomState(8).randint(0, 256, (2, 16))
    ids = gen.generate(prompts, 6)
    longer = GPTGenerator(DotsVlmDecoder(gen.cfg), batch=2, context_len=20,
                          max_len=22, scope=gen.scope)
    grown = np.concatenate([prompts, ids[:, :4]], axis=1)
    np.testing.assert_array_equal(longer.generate(grown, 2), ids[:, 4:6])


def test_configuration_file_keeps_every_published_width():
    with open(os.path.join(
            ROOT, "benchmark/configs/dots_vlm1_ep16.json")) as f:
        cfg_json = json.load(f)
    from benchmark.builders import dots_vlm as builder
    from benchmark.harness import mla_cost

    cfg = builder.model_config(cfg_json)
    assert (cfg.hidden_size, cfg.num_heads, cfg.q_lora_rank,
            cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.intermediate_size,
            cfg.moe_intermediate_size) == \
        (7168, 128, 1536, 512, 128, 64, 128, 18432, 2048)
    assert (cfg.num_experts, cfg.num_local_experts, cfg.top_k, cfg.n_group,
            cfg.topk_group, cfg.route_scale, cfg.num_shared_experts) == \
        (256, 16, 8, 8, 4, 2.5, 1)
    assert cfg.layer_kinds == ((LATENT, DENSE),) + ((LATENT, EXPERTS),) * 4
    assert cfg.rope_scaling["factor"] == 40 and cfg.rms_norm_eps == 1e-6
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * 1.36889 ** 2,
                                              rel=1e-5)
    assert cfg.cache_width == 576
    assert set(cfg_json["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size"}
    assert set(cfg_json["published"]) == set(cfg_json["reduced"])
    # 4.566B parameters = 9.13 GB in bfloat16, by the decoder's own table
    params = mla_cost.resident_params(DotsVlmDecoder(cfg).describe())
    assert params == 4_565_721_088
    tiny = builder.model_config(cfg_json, tiny=True)
    assert (tiny.num_experts, tiny.num_local_experts, tiny.n_group,
            tiny.topk_group, tiny.num_heads) == (16, 4, 4, 2, 4)
