"""MiniCPM-SALA serving path (models/minicpm_sala.py): the Lightning
recurrence in its two forms (the state-space scan and the update
kernel, interpreted) against the token-by-token recurrence, the
sigmoid gate after the output norm, the compressed-key index and the
block selection against the reference's equations where the selection
binds, prefill + cached decode against the plain reference
(benchmark/reference/minicpm_sala.py) over both mixer kinds, what the
check sees when the decay, the gate or the selection is dropped, the
parameters' closed form, and the counters and gauges. CPU, tiny sizes,
seeded weights."""

import functools

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu.layers.tensor import _simple
from paddle_tpu.models.minicpm_sala import (
    LIGHTNING, SPARSE, MiniCPMSalaConfig, MiniCPMSalaDecoder,
)
from paddle_tpu.ops import kv_cache, llm, ssm
from paddle_tpu.serving import GPTGenerator

from test_nemotron_h import rand, run_ops

H, D = 4, 16            # the tiny Lightning sizes
# a tiny sparse layer whose selection binds: 2 KV heads x 16 under 8
# query heads, compressed keys of 4 every 2, blocks of 4, a window of 4,
# 5 blocks a query
SEL = dict(num_kv_heads=2, kernel=4, stride=2, block_size=4, window=4,
           init_blocks=1, topk=5)


# -- the Lightning recurrence --------------------------------------------------

def recurrence(q, k, v, state=None):
    """S_t = lambda_h S_{t-1} + k_t^T v_t, o_t = q_t S_t / sqrt(d), in
    numpy float64: (o [R, L, H * d], the final state [R, H, dk, dv])."""
    r, length, _ = q.shape
    q, k, v = (x.astype(np.float64).reshape(r, length, H, D)
               for x in (q, k, v))
    lam = np.exp(-2.0 ** (-8.0 * np.arange(1, H + 1) / H))
    s = np.zeros((r, H, D, D)) if state is None else state.astype(
        np.float64)
    out = []
    for t in range(length):
        s = lam[None, :, None, None] * s \
            + k[:, t, :, :, None] * v[:, t, :, None, :]
        out.append(np.einsum("rhk,rhkv->rhv", q[:, t], s) / np.sqrt(D))
    return np.stack(out, 1).reshape(r, length, H * D), s


def test_slopes_are_lightning_attentions():
    s = ssm.lightning_slopes(32)
    assert s[0] == pytest.approx(2 ** -0.25) and s[-1] == 2 ** -8
    lam = np.exp(-s)
    assert 0.42 < lam[0] < 0.44 and 0.995 < lam[-1] < 0.997
    assert np.all(np.diff(lam) > 0)


@pytest.mark.parametrize("length", [16, 21])
def test_chunk_scan_matches_the_recurrence(length):
    """`lightning_chunk_scan` (the state-space scan with dt = 1, one
    group a head) over chunks of 8, a length that is and one that is not
    whole chunks, rows written at `Row` of a batch of 3: the outputs and
    the final state in the stored layout."""
    q, k, v = (rand(s, 2, length, H * D, scale=0.5) for s in (1, 2, 3))
    shape = kv_cache.ssm_state_shape(3, H, D, D, H)
    assert shape == (3, H, D, D)

    def build(vs, blk):
        o = blk.create_var(name="o", shape=q.shape, dtype="float32")
        blk.append_op("lightning_chunk_scan",
                      {n: [vs[n.lower()].name] for n in
                       ("Q", "K", "V", "State", "Row")},
                      {"Out": ["o"], "StateOut": ["state"]},
                      {"num_heads": H, "head_dim": D, "chunk": 8})
        return [o]

    (o,), state = run_ops(
        build, {"q": q, "k": k, "v": v, "row": np.array([1], np.int64)},
        {"state": np.zeros(shape, np.float32)})
    want, final = recurrence(q, k, v)
    np.testing.assert_allclose(np.asarray(o), want, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(state["state"][1:], final, rtol=1e-4,
                               atol=1e-5)
    assert not state["state"][0].any()


@pytest.mark.parametrize("interpret", [False, True])
def test_state_update_continues_the_scan(interpret):
    """A decode step after a prefill: the update (jnp, or the Pallas
    kernel `lightning_state_update` interpreted) on the scan's final
    state is the recurrence one token further."""
    q, k, v = (rand(s, 2, 13, H * D, scale=0.5) for s in (4, 5, 6))
    _o, state = ssm.lightning_scan(
        jnp.asarray(q[:, :12]), jnp.asarray(k[:, :12]),
        jnp.asarray(v[:, :12]), num_heads=H, head_dim=D, chunk=8)
    stored = ssm.pack_state(state, D)
    o, new, kernel = ssm.lightning_update(
        *(jnp.asarray(x[:, 12:]) for x in (q, k, v)), stored, num_heads=H,
        interpret=interpret)
    assert kernel == interpret
    want, final = recurrence(q, k, v)
    np.testing.assert_allclose(np.asarray(o)[:, 0], want[:, 12], rtol=1e-4,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(new), final, rtol=1e-4, atol=1e-5)


def test_gated_norm_takes_a_sigmoid_after_the_whole_width():
    """The Lightning output: N_o over all lanes, times the gain, times
    sigmoid(gate); the default activation is still silu."""
    x, gate = rand(7, 2, 3, 64), rand(8, 2, 3, 64)
    gain = 1 + 0.1 * rand(9, 64)

    def build(act):
        def fn(vs, blk):
            attrs = {"epsilon": 1e-6, "gate_after": True}
            if act:
                attrs["activation"] = act
            return [_simple("gated_rms_norm", {"X": [vs["x"]],
                                               "Gate": [vs["g"]],
                                               "Scale": [vs["w"]]}, attrs)]
        return fn

    normed = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6) * gain
    for act, f in ((None, lambda z: z / (1 + np.exp(-z))),
                   ("sigmoid", lambda z: 1 / (1 + np.exp(-z)))):
        (got,), _ = run_ops(build(act), {"x": x, "g": gate, "w": gain})
        np.testing.assert_allclose(np.asarray(got), normed * f(gate),
                                   rtol=1e-5, atol=1e-6)


# -- the compressed-key index and the selection --------------------------------

def test_the_index_is_written_in_prefill_and_as_positions_complete():
    """A prefill writes every complete window's mean; a decode step whose
    position completes a window writes that row, and no other step
    writes anything."""
    k = rand(10, 2, 9, 32)
    rows = np.asarray(kv_cache.compress_keys(jnp.asarray(k), 4, 2))
    assert rows.shape == (2, 3, 32)     # windows 0-3, 2-5, 4-7
    np.testing.assert_allclose(rows[:, 1], k[:, 2:6].mean(1), rtol=1e-6)
    index = np.zeros(kv_cache.index_shape(2, 12, 2, 2, 16), np.float32)
    assert index.shape == (2, 6, 32)

    def write(carry, keys, pos):
        def fn(vs, blk):
            blk.append_op("kv_index_write",
                          {"Index": ["index"], "K": [vs["k"].name],
                           "Pos": [vs["pos"].name]},
                          {"IndexOut": ["index"]},
                          {"kernel": 4, "stride": 2, "carry": carry})
            return [vs["pos"]]
        return run_ops(fn, {"k": keys, "pos": np.array([pos], np.int32)},
                       {"index": index})[1]["index"]

    index = write(False, k[:, :8], 0)
    np.testing.assert_allclose(index[:, :3], rows, rtol=1e-6)
    assert not index[:, 3:].any()
    cache = np.zeros((2, 12, 32), np.float32)
    cache[:, :9] = k
    # position 8 completes no window (its first is 6-9); 9 completes 3
    assert np.array_equal(write(True, cache, 8), index)
    cache[:, 9] = rand(11, 2, 32)
    after = write(True, cache, 9)
    np.testing.assert_allclose(after[:, 3], cache[:, 6:10].mean(1),
                               rtol=1e-6)
    np.testing.assert_array_equal(after[:, :3], index[:, :3])


def test_the_selection_is_the_references_where_it_binds():
    """96 positions (24 blocks of 4) scored by 8 query heads over 2 KV
    heads: every query's selected blocks are the set the reference
    reaches from the equations (forced blocks, pooled scores, top 5)."""
    from benchmark.reference import minicpm_sala as reference

    s, dh = 96, 16
    q = rand(12, 2, s, 8 * dh)
    k = rand(13, 2, s, 2 * dh)
    index = np.zeros((2, s // 2, 2 * dh), np.float32)
    rows = np.asarray(kv_cache.compress_keys(jnp.asarray(k), 4, 2))
    index[:, :rows.shape[1]] = rows
    qpos = jnp.arange(s, dtype=jnp.int32)
    got = np.asarray(llm.select_blocks(
        jnp.asarray(q), jnp.asarray(index), qpos, scale=dh ** -0.5, **SEL))
    cfg = {"sparse_kernel": 4, "sparse_stride": 2, "block_size": 4,
           "window_size": 4, "init_blocks": 1, "topk": 5}
    want = np.asarray(reference.selection(
        jnp.asarray(q).reshape(2, s, 2, 4, dh),
        reference.compressed_keys(jnp.asarray(k).reshape(2, s, 2, dh), 4,
                                  2), np.arange(s), cfg, s))
    picked = np.zeros(want.shape, bool)
    for idx in np.ndindex(got.shape[:3]):
        ids = got[idx][got[idx] >= 0]
        assert len(set(ids.tolist())) == len(ids)
        picked[idx][ids] = True
    np.testing.assert_array_equal(picked, want)
    # the selection binds: late queries read 5 of their blocks
    late = picked[:, :, 40:].sum(-1)
    assert late.min() == late.max() == 5
    assert (np.arange(24)[None] <= np.arange(40, s)[:, None] // 4).sum(
        -1).min() > 10


# -- the decoder through the generator ---------------------------------------

def tiny_generator(context=24, new=9, dtype="float32", **kw):
    cfg = MiniCPMSalaConfig.tiny(dtype=dtype, **kw)
    gen = GPTGenerator(MiniCPMSalaDecoder(cfg), batch=2, context_len=context,
                       max_len=context + new)
    gen.init_params(seed=7)
    return gen


def probe(gen, seed=5, steps=8):
    from benchmark.builders import minicpm_sala as builder

    prompts = np.random.RandomState(seed).randint(
        0, 256, (2, gen.context_len))
    return builder, builder.probe_generator(gen, prompts, steps)


@pytest.mark.parametrize("context,dtype,tol", [
    (12, "float32", 2e-5), (40, "float32", 2e-5), (40, "bfloat16", 4e-2),
])
def test_prefill_then_cached_decode_match_the_reference(context, dtype, tol,
                                                        monkeypatch):
    """Both mixer kinds, the batch prefilled a row a dispatch; then 8
    cached steps read the Lightning states and the KV caches back. A
    prompt of 12 stays dense (max_len 21 is beyond `dense_len` 16, so
    the decode steps past position 15 select); a prompt of 40 selects in
    the prefill too (10 blocks, 5 read). Both against the reference's
    full forward pass (the recurrence token by token, the selection by
    the equations). The bfloat16 case takes its decode steps' recurrence
    through the Pallas kernel (interpreted): one call a Lightning layer
    and step."""
    obs.reset()
    kernel = dtype == "bfloat16"
    if kernel:
        monkeypatch.setattr(ssm, "lightning_update", functools.partial(
            ssm.lightning_update, interpret=True))
    gen = tiny_generator(context=context, dtype=dtype, prefill_rows=1)
    builder, seen = probe(gen)
    report = builder.compare(gen, seen, tol=tol)
    assert report["ok"], report
    assert report["decode_steps"] == 8
    assert obs.get_gauges()["kernels.lightning_update.calls"] == 3 * kernel


def test_the_check_sees_the_decay_the_gate_and_the_selection():
    """In float32 the program agrees with the reference to rounding; the
    reference with the decay dropped (lambda = 1), with both output
    gates dropped, or with dense attention where the program selects,
    is another model by orders of magnitude more."""
    gen = tiny_generator(context=40)
    builder, seen = probe(gen)
    stated = builder.compare(gen, seen, tol=2e-5)
    assert stated["ok"], stated
    base = max(stated["prefill_err"], stated["decode_err"])
    for below in (dict(decay=False), dict(output_gate=False),
                  dict(select=False)):
        other = builder.compare(gen, seen, tol=2e-5, **below)
        assert not other["ok"], below
        assert min(other["prefill_err"], other["decode_err"]) > 100 * base


def test_state_specs_kinds_and_parameters():
    """Specs by mixer kind (the index only where the program selects),
    the kinds behind the `kv_cache.bytes.*` gauges, the published
    layer pattern, and the closed form of the parameters against the
    built program's count."""
    from benchmark.harness import minicpm_sala_cost as cost

    obs.reset()
    gen = tiny_generator(context=8, new=4)      # max_len 12: dense only
    specs = {n: (s, d) for n, s, d in gen._state_specs}
    assert specs["minicpm_sala_l1_lightning_state"] == (
        (2, H, D, D), "float32")
    assert specs["minicpm_sala_l0_cache_k"] == ((2, 12, 32), "float32")
    assert "minicpm_sala_l0_index" not in specs
    gauges = obs.get_gauges()
    table = obs.get_tables()["serving.generate.model"]
    longer = GPTGenerator(gen.decoder, batch=2, context_len=8, max_len=20)
    assert {n: s for n, s, _d in longer._state_specs}[
        "minicpm_sala_l0_index"] == (2, 10, 32)
    assert [longer._state_kinds[n] for n in (
        "minicpm_sala_l1_lightning_state", "minicpm_sala_l0_cache_v",
        "minicpm_sala_l0_index", "minicpm_sala_sparse_counters")] == \
        ["linear", "full", "index", None]
    assert gauges["kv_cache.bytes.linear"] == 3 * 2 * H * D * D * 4
    assert gauges["kv_cache.bytes.full"] == 2 * 2 * 12 * 32 * 4
    assert table["family"] == "minicpm_sala"
    assert table["layer_kinds"] == [SPARSE, LIGHTNING, LIGHTNING, LIGHTNING]
    built = sum(int(np.prod(v.shape)) for v in gen._param_vars())
    assert built == cost.resident_params(table)
    full = MiniCPMSalaConfig()
    assert full.layer_kinds == (SPARSE,) + (LIGHTNING,) * 6 + (SPARSE,)
    assert full.residual_scale == pytest.approx(1.4 / 32 ** 0.5)
    assert full.head_divisor == 16
    with pytest.raises(ValueError):
        MiniCPMSalaConfig.tiny(block_size=5)


def test_counters_and_gauges_where_the_program_selects(monkeypatch):
    """A prompt of 40 and 8 steps in a program that selects: the
    selection's gauge counts its one sparse layer, the index has its
    bytes, and the counters read once a batch add the blocks chosen and
    the blocks a query could see over the prefill (40 queries) and the
    steps; where the program does not select they stay at zero."""
    obs.reset()
    gen = tiny_generator(context=40)
    gen.generate(np.random.RandomState(3).randint(0, 256, (2, 40)), 9)
    gauges, counters = obs.get_gauges(), obs.get_counters()
    assert gauges["sparse_attention.selecting_layers"] == 1
    assert gauges["kv_cache.bytes.index"] == 2 * 24 * 32 * 4
    assert gauges["kernels.lightning_update.calls"] == 0    # the CPU
    # a query at position t could see t // 4 + 1 blocks and reads at
    # most 5 of them; 2 rows x 2 KV heads
    positions = np.arange(48)
    seen = 4 * int((positions // 4 + 1).sum())
    chosen = 4 * int(np.minimum(positions // 4 + 1, 5).sum())
    assert counters["sparse_attention.blocks_visible"] == seen
    assert counters["sparse_attention.blocks_selected"] == chosen
    obs.reset()
    dense = tiny_generator(context=8, new=4)
    dense.generate(np.zeros((2, 8), np.int64), 4)
    assert obs.get_counters()["sparse_attention.blocks_selected"] == 0
    assert "sparse_attention.selecting_layers" not in obs.get_gauges()
