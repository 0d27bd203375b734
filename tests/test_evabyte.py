"""EvaByte serving path (models/evabyte.py): EVA attention, exact inside
an aligned window and over one softmax-pooled summary per chunk before
it, through `serving.GPTGenerator`. The chunk summaries and the pooled
rows of `kv_index_write` (softmax and mean pooling, a prefill and a
decode step's ring) against their equations; the decode kernel
(interpreted) against the `jnp` path; prefill + cached decode across
window and chunk boundaries against the plain reference
(benchmark/reference/evabyte.py); what the check sees when the summaries
are dropped or the pooling is a mean; the parameters' closed form; the
counters and gauges. CPU, tiny sizes, seeded weights."""

import functools

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu.models.evabyte import (
    COUNTERS_VAR, EvaByteConfig, EvaByteDecoder,
)
from paddle_tpu.ops import eva, kv_cache
from paddle_tpu.serving import GPTGenerator

from test_nemotron_h import rand, run_ops

H, D, W, C = 4, 16, 16, 4       # the tiny sizes: heads, lanes, window, chunk
# the cell's spreads at the tiny widths (benchmark/configs/evabyte_pp8.json)
WEIGHTS = dict(initializer_range=0.1, pool_std=0.7)


# -- the pooled rows -----------------------------------------------------------

def pooled_by_hand(k, mu, values=None):
    """Every complete chunk of C rows of k [R, S, H * D]: per head
    sum_j softmax_j(mu_h . k_j) x_j, x the values (the keys where None),
    in numpy float64."""
    r, s, _ = k.shape
    n = s // C
    kc = k[:, :n * C].astype(np.float64).reshape(r, n, C, H, D)
    x = kc if values is None else values[:, :n * C].astype(
        np.float64).reshape(r, n, C, H, D)
    logits = np.einsum("rnchd,hd->rnch", kc, mu)
    w = np.exp(logits - logits.max(2, keepdims=True))
    w /= w.sum(2, keepdims=True)
    return np.einsum("rnch,rnchd->rnhd", w, x).reshape(r, n, H * D)


def index_write(index, keys, pos, carry, **extra):
    """`kv_index_write` through Program / Executor: (the index after,
    as a host array)."""
    feeds = {"k": keys, "pos": np.array([pos], np.int32)}
    feeds.update({n: a for n, a in extra.items() if a is not None})
    pooling = "softmax" if "mu" in extra else "mean"
    attrs = {"kernel": C, "stride": C, "carry": carry}
    if pooling == "softmax":
        attrs["pooling"] = "softmax"

    def fn(vs, blk):
        ins = {"Index": ["index"], "K": [vs["k"].name],
               "Pos": [vs["pos"].name]}
        ins.update({slot: [vs[n].name] for slot, n in (("V", "v"),
                                                       ("Mu", "mu"))
                    if n in vs})
        blk.append_op("kv_index_write", ins, {"IndexOut": ["index"]}, attrs)
        return [vs["pos"]]

    return run_ops(fn, feeds, {"index": index})[1]["index"]


def test_softmax_pooling_writes_every_closed_chunk_and_the_step_that_closes_one():
    """A prefill of 10 rows writes the summaries of its two complete
    chunks (keys, and values weighted by the keys' logits); a decode
    step reading a ring of 8 slots writes the row of the chunk its
    position closes, from the slots the chunk's positions lie in, and a
    step that closes none writes nothing."""
    k, v = rand(1, 2, 10, H * D), rand(2, 2, 10, H * D)
    mu = rand(3, H, D)
    index = np.zeros(kv_cache.index_shape(2, 24, C, H, D), np.float32)
    assert index.shape == (2, 6, H * D)
    keys = index_write(index, k, 0, False, mu=mu)
    np.testing.assert_allclose(keys[:, :2], pooled_by_hand(k, mu),
                               rtol=1e-5, atol=1e-6)
    assert not keys[:, 2:].any()
    vals = index_write(index, k, 0, False, mu=mu, v=v)
    np.testing.assert_allclose(vals[:, :2], pooled_by_hand(k, mu, v),
                               rtol=1e-5, atol=1e-6)
    # positions 0 .. 19 written into a ring of 8: position p in slot p % 8
    seq_k, seq_v = rand(4, 2, 20, H * D), rand(5, 2, 20, H * D)
    ring_k, ring_v = np.zeros((2, 8, H * D), np.float32), \
        np.zeros((2, 8, H * D), np.float32)
    for p in range(20):
        ring_k[:, p % 8], ring_v[:, p % 8] = seq_k[:, p], seq_v[:, p]
    # position 19 closes chunk 4 (16-19), in slots 0-3
    after = index_write(vals, ring_k, 19, True, mu=mu, v=ring_v)
    np.testing.assert_allclose(
        after[:, 4], pooled_by_hand(seq_k[:, 16:20], mu, seq_v[:, 16:20])[
            :, 0], rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.delete(after, 4, axis=1),
                                  np.delete(vals, 4, axis=1))
    assert np.array_equal(index_write(vals, ring_k, 18, True, mu=mu,
                                      v=ring_v), vals)


def test_mean_pooling_is_what_it_was():
    """The mean mode, the op's default and the compressed-key index's:
    a prefill writes each complete window's mean, a decode step over a
    cache indexed by position writes the window its position completes;
    neither reads a `V` or a `Mu`."""
    k = rand(6, 2, 11, H * D)
    index = np.zeros((2, 6, H * D), np.float32)
    got = index_write(index, k, 0, False)
    np.testing.assert_allclose(
        got[:, :2], k[:, :8].reshape(2, 2, C, H * D).mean(2), rtol=1e-6)
    np.testing.assert_allclose(
        got[:, :2], np.asarray(kv_cache.compress_keys(jnp.asarray(k), C, C))
        [:, :2], rtol=1e-6)
    cache = np.zeros((2, 24, H * D), np.float32)
    cache[:, :11] = k
    after = index_write(got, cache, 11, True)
    np.testing.assert_allclose(after[:, 2], cache[:, 8:12].mean(1),
                               rtol=1e-6)
    assert np.array_equal(index_write(got, cache, 10, True), got)


# -- the decode kernel ---------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [("float32", 2e-6), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("pos", [0, 31, 39, 190])
def test_the_decode_kernel_is_the_jnp_paths_attention(dtype, tol, pos):
    """One query a sequence over a ring of 32 slots (a window of 32) and
    48 summaries (chunks of 4): the live slots 0 .. pos % 32 and the
    pos // 32 * 8 visible summaries in one softmax, the kernel
    (interpreted, blocks of 8 ring slots and 16 summaries) against the
    `jnp` path."""
    from paddle_tpu.kernels import eva_attention

    b, nh, dh = 3, 2, 128
    hk = nh * dh
    ring_k, ring_v = (jnp.asarray(rand(s, b, 32, hk), dtype)
                      for s in (7, 8))
    sum_k, sum_v = (jnp.asarray(rand(s, b, 48, hk), dtype)
                    for s in (9, 10))
    q = jnp.asarray(rand(11, b, hk), dtype)
    p = jnp.int32(pos)
    want = eva.decode_jnp(q, ring_k, ring_v, sum_k, sum_v, p, nh,
                          dh ** -0.5, 32, 4)
    got = eva_attention._call(
        jnp.reshape(p, (1,)), q.reshape(b, 1, hk), ring_k, ring_v, sum_k,
        sum_v, nkv=nh, blk=8, sblk=16, window=32, chunk=4,
        scale=dh ** -0.5, interpret=True).reshape(b, hk)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)
    got, ran = eva.decode(q, ring_k, ring_v, sum_k, sum_v, p, nh,
                          dh ** -0.5, 32, 4, interpret=True)
    assert ran
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


# -- the decoder through the generator ---------------------------------------

def tiny_generator(context=40, new=26, dtype="float32", **kw):
    cfg = EvaByteConfig.tiny(dtype=dtype, **{**WEIGHTS, **kw})
    gen = GPTGenerator(EvaByteDecoder(cfg), batch=2, context_len=context,
                       max_len=context + new)
    gen.init_params(seed=7)
    return gen


def probe(gen, seed=5, steps=25):
    from benchmark.builders import evabyte as builder

    prompts = np.random.RandomState(seed).randint(
        0, 320, (2, gen.context_len))
    return builder, builder.probe_generator(gen, prompts, steps)


@pytest.mark.parametrize("context,dtype,tol", [
    (13, "float32", 2e-5), (40, "bfloat16", 3e-2),
])
def test_prefill_then_cached_decode_match_the_reference(context, dtype, tol,
                                                        monkeypatch):
    """The batch prefilled a row a dispatch, then 25 cached steps: from
    13 they cross the window boundaries at 16 and 32, where the first
    summaries turn visible, from 40 those at 48 and 64 and six chunk
    boundaries (and in float32 `test_the_check_sees_...`); against the
    reference's full forward pass with the window's keys and the
    summaries as explicit sets. The bfloat16 case takes its decode
    steps' attention through the Pallas kernel (interpreted): one call a
    layer and step."""
    obs.reset()
    kernel = dtype == "bfloat16"
    if kernel:
        monkeypatch.setattr(eva, "decode", functools.partial(
            eva.decode, interpret=True))
    gen = tiny_generator(context=context, dtype=dtype, prefill_rows=1)
    builder, seen = probe(gen)
    report = builder.compare(gen, seen, tol=tol)
    assert report["ok"], report
    assert report["decode_steps"] == 25
    assert obs.get_gauges()["kernels.eva_attention.calls"] == 2 * kernel


def test_the_check_sees_the_summaries_and_their_pooling():
    """In float32, a prompt of 40 prefilled a row a dispatch and 25
    cached steps across the window boundaries at 48 and 64, the program
    agrees with the reference to rounding; the reference with the
    summaries dropped, or pooled by a plain mean, is another model by
    orders of magnitude more."""
    gen = tiny_generator(prefill_rows=1)
    builder, seen = probe(gen)
    stated = builder.compare(gen, seen, tol=2e-5)
    assert stated["ok"], stated
    assert stated["decode_steps"] == 25
    base = max(stated["prefill_err"], stated["decode_err"])
    for below in (dict(summaries=False), dict(pooling="mean")):
        other = builder.compare(gen, seen, tol=2e-5, **below)
        assert not other["ok"], below
        assert min(other["prefill_err"], other["decode_err"]) > 100 * base


@pytest.fixture(scope="module")
def gen22():
    """A prompt of 22 bytes and up to 20 new: max_len 42."""
    return tiny_generator(context=22, new=20)


def test_the_prefills_summaries_are_the_pooling_by_the_equation(gen22):
    """Layer 0's summaries after a prefill of 22 bytes: its 5 complete
    chunks, keys and values pooled from N(embedding) through the
    rotated keys by the reference's equation; the rows beyond them
    untouched."""
    import jax

    from benchmark.reference import evabyte as reference

    gen = gen22
    ids = np.random.RandomState(2).randint(0, 320, (2, 22))
    gen.generate(ids, 1)
    p = {n: np.asarray(gen.scope.find_var(n))
         for n in reference.param_names(2)}
    cfg = reference.reference_config(gen.cfg)
    with jax.default_matmul_precision("highest"):
        a = reference.rms_norm(jnp.asarray(p["evabyte_embed"])[ids],
                               p["evabyte_l0_n1"], 1e-5)
        k = reference.rotate((a @ p["evabyte_l0_attn_k_w"]).reshape(
            2, 22, H, D), cfg["rope_theta"])
        v = (a @ p["evabyte_l0_attn_v_w"]).reshape(2, 22, H, D)
        want = reference.chunk_summaries(k, v, p["evabyte_l0_attn_mu"],
                                         p["evabyte_l0_attn_phi"], C)
    for got, ref in zip(("evabyte_l0_summary_k", "evabyte_l0_summary_v"),
                        want):
        got = np.asarray(gen.scope.find_var(got))
        assert got.shape == (2, 42 // C, H * D)
        np.testing.assert_allclose(got[:, :5], np.asarray(ref).reshape(
            2, 5, H * D), rtol=2e-4, atol=2e-5)
        assert not got[:, 5:].any()


def test_state_specs_kinds_and_parameters():
    """The ring (kind window: min(max_len, W) slots) and the summaries
    (kind summary: a row per chunk of max_len), their gauges, the
    published sizes, and the closed form of the parameters against the
    built program's count."""
    from benchmark.harness import evabyte_cost as cost

    obs.reset()
    gen = tiny_generator(context=40, new=26)        # max_len 66
    specs = {n: (s, d) for n, s, d in gen._state_specs}
    assert specs["evabyte_l0_cache_k"] == ((2, W, H * D), "float32")
    assert specs["evabyte_l1_summary_v"] == ((2, 66 // C, H * D), "float32")
    assert [gen._state_kinds[n] for n in (
        "evabyte_l0_cache_v", "evabyte_l0_summary_k", COUNTERS_VAR)] == \
        ["window", "summary", None]
    gauges = obs.get_gauges()
    assert gauges["kv_cache.bytes.window"] == 2 * 2 * 2 * W * H * D * 4
    assert gauges["kv_cache.bytes.summary"] == 2 * 2 * 2 * 16 * H * D * 4
    table = obs.get_tables()["serving.generate.model"]
    assert table["family"] == "evabyte"
    built = sum(int(np.prod(v.shape)) for v in gen._param_vars())
    assert built == cost.resident_params(table)
    full = EvaByteConfig()
    assert (full.vocab_size, full.hidden_size, full.num_heads,
            full.head_dim, full.intermediate_size, full.window_size,
            full.chunk_size) == (320, 4096, 32, 128, 11008, 2048, 16)
    assert cost.resident_params({
        "hidden_size": 4096, "num_heads": 32, "head_dim": 128,
        "intermediate_size": 11008, "num_layers": 4,
        "vocab_size": 320}) == 812_191_744
    with pytest.raises(ValueError):
        EvaByteConfig.tiny(chunk_size=5)


def test_counters_and_gauges(gen22):
    """A prompt of 22 and 20 steps (positions 22 .. 40): the prefill
    writes 5 summaries a row and layer, the steps at 23, 27, 31, 35, 39
    one each; a step at p reads p % 16 + 1 ring rows and p // 16 * 4
    summaries a row and layer; all read once a batch. On the CPU the
    `jnp` path runs: the kernel's gauge reads 0."""
    obs.reset()
    gen22.generate(np.random.RandomState(3).randint(0, 320, (2, 22)), 20)
    counters = obs.get_counters()
    steps = np.arange(22, 41)
    rows_layers = 2 * 2
    assert counters["eva.summaries_written"] == rows_layers * (5 + 5)
    assert counters["eva.ring_rows_read"] == rows_layers * int(
        (steps % W + 1).sum())
    assert counters["eva.summary_rows_read"] == rows_layers * int(
        (steps // W * (W // C)).sum())
    assert obs.get_gauges()["kernels.eva_attention.calls"] == 0
