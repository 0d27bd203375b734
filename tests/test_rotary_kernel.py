"""The prefill's rotary kernel, `kernels/rotary.py`, and the op that
reaches it (`ops/llm.py::rotary_embedding` -> `rotary_prefill`): the
kernel under `interpret=True` against `rotary`'s `jnp` form compiled,
to one ulp of the output dtype at the size of the two terms a lane sums
(XLA's CPU backend fuses ``x1 cos - x2 sin`` into a multiply-add in one
form and not in the other: where the terms nearly cancel, that is many
ulps of the small result), at a runtime first position other than 0,
in the three forms the cells run (the whole head of 128, the last 64 lanes of
192 with YaRN, the leading 64 of 256), bfloat16 and float32, and with a
sequence in several row blocks; and the calls that take the `jnp` path
and leave the gauge `kernels.rotary.calls` at 0: a decode step (T = 1),
a head of 64 lanes (dots_vlm's shared rotary key), rows that make no
whole block. ONE parametrised test, so that every case counts."""

import functools

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.kernels import rotary as kernel
from paddle_tpu.ops import llm

YARN = {"factor": 40, "original_max_position_embeddings": 64,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1.0,
        "mscale_all_dim": 1.0}
# form: (head_dim, heads, rotary_dim, yarn, leading)
FORMS = {
    "whole-128": (128, 3, None, None, False),
    "last-64-of-192-yarn": (192, 4, 64, YARN, False),
    "leading-64-of-256": (256, 2, 64, None, True),
}
# the `jnp` path: (rows, T, head_dim, heads)
JNP = {
    "decode-step-t1": (4, 1, 128, 2),
    "head-of-64-lanes": (2, 48, 64, 1),
    "rows-in-no-whole-block-t24": (2, 24, 128, 2),
}
LAST = 84          # the position of the last row: the first is not 0


def _ordered(a):
    """Bit patterns as integers in the floats' own order, so that two
    neighbouring values differ by 1."""
    a = np.asarray(a)
    bits = a.view(np.int16 if a.dtype.itemsize == 2 else np.int32)
    bits = bits.astype(np.int64)
    magnitude = bits & (2 ** (8 * a.dtype.itemsize - 1) - 1)
    return np.where(bits < 0, -magnitude, magnitude)


def _emit(x, head_dim, rotary_dim, yarn, leading, monkeypatch,
          interpret=True):
    """The op through Program / Executor, with `interpret` the kernel's
    gate open: (out, the calls gauge)."""
    from test_afmoe import run_op

    if interpret:
        monkeypatch.setattr(llm, "rotary_prefill", functools.partial(
            llm.rotary_prefill, interpret=True))
    obs.reset()
    attrs = {"head_dim": head_dim, "theta": 10000.0}
    if rotary_dim:
        attrs.update(rotary_dim=rotary_dim, leading=leading)
    if yarn:
        attrs["yarn"] = yarn
    out = run_op("rotary_embedding",
                 {"x": x, "pos": np.array([LAST], np.int32)}, attrs,
                 {"X": "x", "Pos": "pos"})
    return out, obs.get_gauges()["kernels.rotary.calls"]


def _want(x, head_dim, rotary_dim, yarn, leading):
    import jax

    return jax.jit(functools.partial(
        llm.rotary, head_dim=head_dim, theta=10000.0, rotary_dim=rotary_dim,
        yarn=yarn, leading=leading))(x, LAST - (x.shape[1] - 1))


def _ulp_of_terms(x, head_dim, rotary_dim, yarn, leading):
    """One ulp of x's dtype at |x cos| + |x' sin| a lane (x' its partner
    in the rotary group), the tables read as the kernel reads them."""
    u = kernel.unit(head_dim)
    half = (rotary_dim or head_dim) // 2
    cos, sin_a, sin_b = (np.asarray(t) for t in llm.rotary_tables(
        LAST - (x.shape[1] - 1), x.shape[1], head_dim, u, 10000.0,
        rotary_dim, yarn, leading))
    xf = x.astype(np.float32).reshape(x.shape[:2] + (-1, u))
    terms = (np.abs(xf * cos[:, None]) + np.abs(
        np.roll(xf, half, -1) * sin_a[:, None]) + np.abs(
        np.roll(xf, -half, -1) * sin_b[:, None])).reshape(x.shape)
    bits = 7 if x.dtype.itemsize == 2 else 23
    return np.ldexp(1.0, np.frexp(terms)[1] - 1 - bits)


def _rand(shape, dtype, seed=0):
    import jax.numpy as jnp

    x = np.random.RandomState(seed).randn(*shape).astype(np.float32)
    return np.asarray(jnp.asarray(x, dtype))


def _kernel_case(form, dtype, monkeypatch, seq=48):
    head_dim, heads, rot, yarn, leading = FORMS[form]
    x = _rand((2, seq, heads * head_dim), dtype)
    assert kernel.supports(seq, x.shape[2], head_dim, x.dtype)
    out, calls = _emit(x, head_dim, rot, yarn, leading, monkeypatch)
    assert calls == 1
    want = np.asarray(_want(x, head_dim, rot, yarn, leading))
    assert np.asarray(out).dtype == want.dtype
    diff = np.abs(np.asarray(out, np.float64) - want.astype(np.float64))
    assert (diff <= _ulp_of_terms(x, head_dim, rot, yarn, leading)).all()
    # the lanes that pass come out as they went in
    lanes = np.arange(x.shape[2]) % head_dim
    turn = lanes < (rot or head_dim) if leading \
        else lanes >= head_dim - (rot or head_dim)
    np.testing.assert_array_equal(np.asarray(out)[..., ~turn], x[..., ~turn])


def _row_blocks_case(monkeypatch):
    """A sequence of three row blocks: the tables follow a block's place
    in its sequence."""
    monkeypatch.setattr(kernel, "MAX_ROWS", 16)
    assert kernel.blocks(64, 256, 128, np.float32)[0] == 16
    _kernel_case("whole-128", "float32", monkeypatch, seq=64)


def _jnp_case(name, monkeypatch):
    rows, seq, head_dim, heads = JNP[name]
    x = _rand((rows, seq, heads * head_dim), "bfloat16", seed=1)
    assert not kernel.supports(seq, x.shape[2], head_dim, x.dtype)
    out, calls = _emit(x, head_dim, None, None, False, monkeypatch)
    assert calls == 0
    monkeypatch.undo()                  # the gate as the CPU has it
    closed, _calls = _emit(x, head_dim, None, None, False, monkeypatch,
                           interpret=False)
    np.testing.assert_array_equal(_ordered(out), _ordered(closed))


def _cases():
    cases = {f"kernel-{form}-{dtype}": functools.partial(
        _kernel_case, form, dtype)
        for form in FORMS for dtype in ("bfloat16", "float32")}
    cases["kernel-several-row-blocks"] = _row_blocks_case
    cases.update({f"jnp-{name}": functools.partial(_jnp_case, name)
                  for name in JNP})
    return cases


CASES = _cases()


@pytest.mark.parametrize("case", sorted(CASES))
def test_rotary_kernel(case, monkeypatch):
    CASES[case](monkeypatch)
