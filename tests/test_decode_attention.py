"""The row-layout KV cache's one reader, `kernels/decode_attention.py`,
and what came with it: the kernel under `interpret=True` against the
`jnp` form at the three decoders' head geometries and the positions
where its block walk and its mask turn; the two cache ops through each
decoder's own programs against a full forward pass, with the kernel and
without, and the gauge that says which ran; the host-side count of the
bytes a batch's decode steps need. ONE parametrised test, so that every
case counts."""

import functools
import importlib

import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.kernels import decode_attention as kernel
from paddle_tpu.ops import kv_cache

BLOCK = 16      # slots a block in the kernel cases: four blocks of 64

# name: (dtype, query heads, KV heads, head_dim, slots, window, tolerance)
GEOMETRIES = {
    "gpt2-f32-12x64": ("float32", 12, 12, 64, 64, 0, 1e-5),
    "trinity-bf16-48over8x128-ring": ("bfloat16", 48, 8, 128, 64, 64, 2e-2),
    "trinity-bf16-48over8x128-window": ("bfloat16", 48, 8, 128, 64, 24,
                                        2e-2),
    "nemotron-bf16-32over2x128": ("bfloat16", 32, 2, 128, 64, 0, 2e-2),
}
POSITIONS = {"first": 0, "block-last": BLOCK - 1, "block-first": 2 * BLOCK,
             "last-slot": 63, "wrapped": 64 + 21}
# decoder: (its cell, kernel calls a decode step = attention layers at
# the cell's tiny sizes)
DECODERS = {"gpt2_small": 2, "trinity_large_ep8": 5,
            "nemotron3_super_ep4": 1}


def _kernel_case(geometry, position, monkeypatch):
    dtype, nh, nkv, dh, slots, window, tol = GEOMETRIES[geometry]
    pos = POSITIONS[position]
    row_bytes = nkv * dh * jnp.dtype(dtype).itemsize
    monkeypatch.setattr(kernel, "BLOCK_BYTES", BLOCK * row_bytes)
    assert kernel.slot_block(slots, row_bytes, 16) == BLOCK
    rng = np.random.RandomState(nh + pos)
    q = jnp.asarray(rng.randn(3, nh * dh), dtype)
    k, v = (jnp.asarray(rng.randn(3, slots, nkv * dh), dtype)
            for _ in range(2))
    args = (q, k, v, jnp.int32(pos), nkv, dh ** -0.5, window, 0.9)
    want, ran = kv_cache.decode_attention(*args)
    assert not ran and want.shape == q.shape
    got, ran = kv_cache.decode_attention(*args, interpret=True)
    assert ran and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=0)


def _build(config, monkeypatch, interpret):
    """The decoder of `config`'s generate cell at its rehearsal sizes,
    its decode steps' attention through the kernel (interpreted) or the
    `jnp` form."""
    from benchmark.harness import manifest as mf

    if interpret:
        monkeypatch.setattr(
            kv_cache, "decode_attention",
            functools.partial(kv_cache.decode_attention, interpret=True))
    manifest = mf.load()
    entry, cell = mf.cell(manifest, f"{config}_generate_closed")
    cfg_json = mf.config(manifest, entry["config"])
    builder = importlib.import_module(
        f"benchmark.builders.{cfg_json['builder']}")
    traffic = {**cell["traffic"], **cell["rehearse"]}
    return builder.build_generate(cfg_json, traffic, True, seed=3), traffic


def _decoder_case(config, path, monkeypatch):
    """Prefill, then eight cached steps that write a row and read the
    cache back, against the reference's full forward pass."""
    obs.reset()
    build, _traffic = _build(config, monkeypatch, path == "kernel")
    report = build.check(build.probe(np.random.RandomState(1)))
    assert report["ok"] and report["decode_steps"] == 8, report
    calls = DECODERS[config] if path == "kernel" else 0
    assert obs.get_gauges()["kernels.decode_attention.calls"] == calls


def _bytes_case(config, monkeypatch):
    """One batch: every decode step needs the slots written so far (a
    ring: at most its slots) of every K and V cache, once."""
    obs.reset()
    build, traffic = _build(config, monkeypatch, False)
    gen = build.generator
    ctx, new = traffic["prompt_len"], traffic["new_tokens"]
    prompts = np.stack([build.make_prompt(np.random.RandomState(i))
                        for i in range(traffic["batch"])])
    gen.generate(prompts, new)
    want = 0
    for name, shape, dtype in gen._state_specs:
        if "_cache_" in name:
            batch, slots, width = shape
            row = batch * width * (2 if dtype == "bfloat16" else 4)
            want += sum(min(p + 1, slots) * row
                        for p in range(ctx, ctx + new - 1))
    got = obs.get_counters()
    assert want > 0 and got["kv_cache.decode_bytes_needed"] == want
    assert got["kv_cache.decode_steps"] == new - 1


def _cases():
    for geometry, (_d, _nh, _nkv, _dh, slots, window, _t) in \
            GEOMETRIES.items():
        for position, pos in POSITIONS.items():
            if pos < slots or window >= slots:      # only a ring wraps
                yield (f"kernel-{geometry}-{position}",
                       functools.partial(_kernel_case, geometry, position))
    for config in DECODERS:
        for path in ("kernel", "jnp"):
            yield (f"decoder-{config}-{path}",
                   functools.partial(_decoder_case, config, path))
        yield f"bytes-{config}", functools.partial(_bytes_case, config)


CASES = dict(_cases())


@pytest.mark.parametrize("case", list(CASES))
def test_decode_attention(case, monkeypatch):
    CASES[case](monkeypatch=monkeypatch)
