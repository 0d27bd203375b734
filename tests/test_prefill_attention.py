"""The prefill's attention kernel, `kernels/prefill_attention.py`, and the
op that reaches it (`ops/llm.py::causal_gqa_attention`): the kernel under
`interpret=True` against the `jnp` form at the four generate cells' head
geometries cut in S; a row's independence of its neighbours; what the
`supports` predicate refuses and that the `jnp` path then runs; the
gauges; and each of the four decoders at tiny widths whose heads the
kernel takes, prefill through the kernel (interpreted) and without,
against its reference, with the prefill -> cached-decode hand-off equal on
both paths. ONE parametrised test, so that every case counts."""

import functools
import importlib

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.kernels import prefill_attention as kernel
from paddle_tpu.ops import llm
from paddle_tpu.ops.kv_cache import attention_mask, grouped_attention

# name: (dtype, query heads, KV heads, key lanes, value lanes, prob_scale,
# tolerance)
GEOMETRIES = {
    "gpt2-f32-12x64": ("float32", 12, 12, 64, 64, 0.9, 2e-6),
    "trinity-bf16-48over8x128": ("bfloat16", 48, 8, 128, 128, 1.0, 2e-2),
    "nemotron-bf16-32over2x128": ("bfloat16", 32, 2, 128, 128, 1.0, 2e-2),
    "dots_vlm-bf16-128x192-128": ("bfloat16", 128, 128, 192, 128, 1.0, 2e-2),
}
# (rows, S): one block; several, an odd count (a single block after the
# wide trips) and an even one
SIZES = {"one-block-1-row": (1, 128), "three-blocks-2-rows": (2, 384),
         "four-blocks-1-row": (1, 512)}


def _operands(geometry, rows, seq, seed=0):
    dtype, nh, nkv, dk, dv, prob_scale, tol = GEOMETRIES[geometry]
    rng = np.random.RandomState(seed + nh)
    q = jnp.asarray(rng.randn(rows, seq, nh * dk), dtype)
    k = jnp.asarray(rng.randn(rows, seq, nkv * dk), dtype)
    v = jnp.asarray(rng.randn(rows, seq, nkv * dv), dtype)
    return (q, k, v, nh, nkv, dk ** -0.5), prob_scale, tol


def _close(got, want, tol):
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def _kernel_case(geometry, size, monkeypatch):
    rows, seq = SIZES[size]
    (q, k, v, nh, nkv, scale), prob_scale, tol = _operands(
        geometry, rows, seq)
    valid = attention_mask(jnp.arange(seq, dtype=jnp.int32), seq)
    want = grouped_attention(q, k, v, valid, nkv, scale, prob_scale)
    got, ran = llm.prefill_attention(q, k, v, nh, nkv, scale, 0, prob_scale)
    assert not ran                      # the CPU: the blocked `jnp` form
    _close(got, want, tol)
    got, ran = llm.prefill_attention(q, k, v, nh, nkv, scale, 0, prob_scale,
                                     interpret=True)
    assert ran and got.dtype == q.dtype and got.shape == want.shape
    _close(got, want, tol)


def _shared_case(size, monkeypatch):
    """dots_vlm's other form: K holds a head's own 128 lanes, the 64
    rotary lanes every head shares come apart (`KShared`) and are never
    copied into the heads. Against the `jnp` form on the joined keys."""
    rows, seq = SIZES[size]
    nh, dn, ds, dv, scale = 6, 128, 64, 128, 192 ** -0.5
    rng = np.random.RandomState(seq)
    q, k, shared, v = (jnp.asarray(rng.randn(rows, seq, w), "bfloat16")
                       for w in (nh * (dn + ds), nh * dn, ds, nh * dv))
    joined = jnp.concatenate([
        k.reshape(rows, seq, nh, dn),
        jnp.broadcast_to(shared[:, :, None], (rows, seq, nh, ds))], -1)
    valid = attention_mask(jnp.arange(seq, dtype=jnp.int32), seq)
    want = grouped_attention(q, joined.reshape(rows, seq, -1), v, valid, nh,
                             scale)
    for interpret in (False, True):
        got, ran = llm.prefill_attention(q, k, v, nh, nh, scale, 0, 1.0,
                                         shared, interpret=interpret)
        assert ran == interpret and got.shape == (rows, seq, nh * dv)
        _close(got, want, 2e-2)


def _long_case(monkeypatch):
    """Several super-blocks (2,048 tokens = two of 1,024): the earlier
    one is walked unmasked with the online softmax's state in scratch."""
    assert kernel.super_block(2048) == 1024 and kernel.super_block(896) == 896
    (q, k, v, nh, nkv, scale), prob_scale, tol = _operands(
        "gpt2-f32-12x64", 1, 2048)
    q, k, v = q[..., :128], k[..., :128], v[..., :128]      # two heads
    valid = attention_mask(jnp.arange(2048, dtype=jnp.int32), 2048)
    want = grouped_attention(q, k, v, valid, 2, scale, prob_scale)
    got = kernel.attend(q, k, v, num_heads=2, num_kv_heads=2, scale=scale,
                        prob_scale=prob_scale, interpret=True)
    _close(got, want, tol)


def _rows_case(geometry, monkeypatch):
    """A row's output is its own: beside other neighbours, bit for bit."""
    (q, k, v, nh, nkv, scale), prob_scale, _tol = _operands(geometry, 2, 256)
    run = functools.partial(kernel.attend, num_heads=nh, num_kv_heads=nkv,
                            scale=scale, prob_scale=prob_scale,
                            interpret=True)
    both = run(q, k, v)
    alone = run(q[1:], k[1:], v[1:])
    other = run(q.at[0].set(7.0), k.at[0].set(-3.0), v.at[0].set(5.0))
    np.testing.assert_array_equal(np.asarray(both[1], np.float32),
                                  np.asarray(alone[0], np.float32))
    np.testing.assert_array_equal(np.asarray(both[1], np.float32),
                                  np.asarray(other[1], np.float32))


# what the kernel does not take: (S, key lanes, window)
REFUSALS = {"window-binds": (256, 128, 100), "s-100": (100, 128, 0),
            "dk-80": (128, 80, 0)}
TAKEN = {"window-beyond-s": (256, 128, 4096), "window-equal-s": (256, 128, 256)}


def _emit(seq, dk, window, monkeypatch):
    """The op through Program / Executor on [2, seq] rows of 4 query
    over 2 KV heads with the kernel's gate open (interpreted): (out,
    numpy's answer, the calls gauge)."""
    from test_afmoe import dense_attention, rand, run_op

    monkeypatch.setattr(llm, "prefill_attention", functools.partial(
        llm.prefill_attention, interpret=True))
    obs.reset()
    q, k, v = (rand(n, 2, seq, heads * dk)
               for n, heads in ((1, 4), (2, 2), (3, 2)))
    scale = dk ** -0.5
    out = run_op("causal_gqa_attention", {"q": q, "k": k, "v": v},
                 {"num_heads": 4, "num_kv_heads": 2, "window": window,
                  "scale": scale}, {"Q": "q", "K": "k", "V": "v"})
    want = dense_attention(q, k, v, 4, 2, window, scale)
    return out, want, obs.get_gauges()["kernels.prefill_attention.calls"]


def _refusal_case(name, monkeypatch):
    seq, dk, window = REFUSALS[name]
    assert not kernel.supports(seq, 4, 2, dk, dk, "float32", window)
    assert not kernel.supports(128, 4, 2, 128, 128, "float16")
    # grouped heads narrower than a lane tile; K and V too long to stay
    assert not kernel.supports(128, 4, 2, 64, 64, "float32")
    assert kernel.supports(128, 4, 4, 64, 64, "float32")
    assert not kernel.supports(65536, 4, 4, 128, 128, "float32")
    out, want, calls = _emit(seq, dk, window, monkeypatch)
    assert calls == 0
    _close(out, want, 1e-5)


def _taken_case(name, monkeypatch):
    seq, dk, window = TAKEN[name]
    assert kernel.supports(seq, 4, 2, dk, dk, "float32", window)
    out, want, calls = _emit(seq, dk, window, monkeypatch)
    assert calls == 1
    _close(out, want, 1e-5)


def _gauges_case(monkeypatch):
    """Seven blocks, as the cells' 896 tokens: 28 live tiles of 49 a head
    group and sequence, every one visited computed."""
    obs.reset()
    (q, k, v, nh, nkv, scale), _p, _t = _operands(
        "nemotron-bf16-32over2x128", 1, 896)
    # traced only: the gauges are set where the call is lowered
    import jax

    jax.eval_shape(functools.partial(
        kernel.attend, num_heads=nh, num_kv_heads=nkv, scale=scale), q, k, v)
    gauges = obs.get_gauges()
    assert gauges["kernels.prefill_attention.tiles_visited"] == 28
    assert gauges["kernels.prefill_attention.tiles_computed"] == 28


# decoder: (tiny widths whose heads the kernel takes, over the cell's own
# `tiny`; attention layers = kernel calls of one prefill)
DECODERS = {
    "gpt2_small": ({"n_embd": 128, "n_head": 2, "n_positions": 256,
                    "n_ctx": 256}, 2),
    "trinity_large_ep8": ({"head_dim": 128, "sliding_window": 256}, 5),
    # the window binds on the four sliding layers: the full one alone
    "trinity_large_ep8-window-8": ({"head_dim": 128}, 1),
    "nemotron3_super_ep4": ({"head_dim": 128}, 1),
    "dots_vlm1_ep16": ({"qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
                        "v_head_dim": 128}, 5),
}


def _build(decoder, prompt_len, monkeypatch, interpret, dtype=None):
    from benchmark.harness import manifest as mf

    if interpret:
        monkeypatch.setattr(llm, "prefill_attention", functools.partial(
            llm.prefill_attention, interpret=True))
    manifest = mf.load()
    entry, cell = mf.cell(manifest,
                          f"{decoder.split('-')[0]}_generate_closed")
    cfg_json = dict(mf.config(manifest, entry["config"]))
    cfg_json["tiny"] = {**cfg_json["tiny"], **DECODERS[decoder][0]}
    if dtype and "serving" in cfg_json:     # GPT-2 is float32 as it is
        cfg_json["serving"] = {**cfg_json["serving"], "dtype": dtype}
    builder = importlib.import_module(
        f"benchmark.builders.{cfg_json['builder']}")
    traffic = {**cell["traffic"], **cell["rehearse"],
               "prompt_len": prompt_len}
    return builder.build_generate(cfg_json, traffic, True, seed=3), traffic


def _decoder_case(decoder, path, monkeypatch):
    """A prompt of 128 through the decoder's own two programs against its
    reference, eight cached steps after it; the gauge says which path
    the prefill took."""
    obs.reset()
    build, _traffic = _build(decoder, 128, monkeypatch, path == "kernel")
    report = build.check(build.probe(np.random.RandomState(1)))
    assert report["ok"] and report["decode_steps"] == 8, report
    calls = DECODERS[decoder][1] if path == "kernel" else 0
    assert obs.get_gauges()["kernels.prefill_attention.calls"] == calls


def _short_prompt_case(decoder, monkeypatch):
    """The cells' rehearsal prompt of 24 is no block of 128: the `jnp`
    path, whatever the gate says."""
    obs.reset()
    build, traffic = _build(decoder, 24, monkeypatch, True)
    prompts = np.stack([build.make_prompt(np.random.RandomState(i))
                        for i in range(traffic["batch"])])
    build.generator.generate(prompts, 2)
    assert obs.get_gauges()["kernels.prefill_attention.calls"] == 0


def _handoff_case(decoder, monkeypatch):
    """prefill -> cached decode, token for token: the ids a batch
    generates with the prefill through the kernel are the `jnp` path's
    (in float32: a bfloat16 near tie may fall either way)."""
    ids = []
    for interpret in (False, True):
        with monkeypatch.context() as patch:
            obs.reset()
            build, traffic = _build(decoder, 128, patch, interpret,
                                   "float32")
            prompts = np.stack([
                build.make_prompt(np.random.RandomState(10 + i))
                for i in range(traffic["batch"])])
            ids.append(np.asarray(build.generator.generate(prompts, 6)))
            assert bool(obs.get_gauges()[
                "kernels.prefill_attention.calls"]) == interpret
    np.testing.assert_array_equal(ids[0], ids[1])


def _cases():
    for geometry in GEOMETRIES:
        for size in SIZES:
            yield (f"kernel-{geometry}-{size}",
                   functools.partial(_kernel_case, geometry, size))
        yield f"rows-{geometry}", functools.partial(_rows_case, geometry)
    for size in SIZES:
        yield f"kernel-shared-key-part-{size}", functools.partial(
            _shared_case, size)
    yield "kernel-two-super-blocks", _long_case
    for name in REFUSALS:
        yield f"refused-{name}", functools.partial(_refusal_case, name)
    for name in TAKEN:
        yield f"taken-{name}", functools.partial(_taken_case, name)
    yield "gauges-seven-blocks", _gauges_case
    for decoder in DECODERS:
        for path in ("kernel", "jnp"):
            yield (f"decoder-{decoder}-{path}",
                   functools.partial(_decoder_case, decoder, path))
    for decoder in ("gpt2_small", "trinity_large_ep8",
                    "nemotron3_super_ep4", "dots_vlm1_ep16"):
        yield (f"short-prompt-{decoder}",
               functools.partial(_short_prompt_case, decoder))
        yield f"handoff-{decoder}", functools.partial(_handoff_case, decoder)


CASES = dict(_cases())


@pytest.mark.parametrize("case", list(CASES))
def test_prefill_attention(case, monkeypatch):
    CASES[case](monkeypatch=monkeypatch)
