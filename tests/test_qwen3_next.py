"""Qwen3-Next serving path (models/qwen3_next.py): the gated delta rule
in its two forms against the token-by-token recurrence, the decode
kernel under the interpreter, the post-norm gate, the leading-lane
rotary, the `1 + w` gains, softmax routing and its share of an
expert-parallel deployment, prefill + cached decode against the plain
reference (benchmark/reference/qwen3_next.py) over both mixer kinds, and
the gauges of the three kernels. CPU, tiny sizes, seeded weights."""

import functools
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu.models.qwen3_next import (
    FULL, LINEAR, Qwen3NextConfig, Qwen3NextDecoder,
)
from paddle_tpu.ops import kv_cache, llm, ssm
from paddle_tpu.parallel import moe
from paddle_tpu.serving import GPTGenerator

from test_nemotron_h import rand, run_ops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HK, HV, DK, DV = 2, 4, 16, 16       # the tiny Gated DeltaNet sizes
TINY = (HK, HV, DK, DV)
# the cell's head sizes (two value heads a key head, 128 lanes each),
# which the prefill's kernel takes; fewer heads
WIDE = (2, 4, 128, 128)
WIDTH = 2 * HK * DK + HV * DV
ATTRS = {"key_heads": HK, "value_heads": HV, "key_dim": DK, "value_dim": DV}
SLOTS = {"QKV": ["qkv"], "B": ["b"], "A": ["a"], "ALog": ["a_log"],
         "DtBias": ["dt_bias"], "State": ["state"]}


# -- the gated delta rule ------------------------------------------------------

def attrs(sizes):
    return dict(zip(("key_heads", "value_heads", "key_dim", "value_dim"),
                    sizes))


def delta_inputs(seed, rows, length, sizes=TINY):
    """q | k | v after the convolution, raw b and al, and a layer's small
    parameters, drawn where the configuration's initialisation puts
    them."""
    hk, hv, dk, dv = sizes
    rng = np.random.RandomState(seed)
    return dict(
        qkv=rand(seed + 1, rows, length, 2 * hk * dk + hv * dv, scale=0.5),
        b=rand(seed + 2, rows, length, hv),
        a=rand(seed + 3, rows, length, hv),
        a_log=np.log(rng.uniform(0.01, 16, hv)).astype(np.float32),
        dt_bias=rng.uniform(-4, -1, hv).astype(np.float32),
    )


def recurrence(v, state=None, state_dtype=None, correction=True,
               sizes=TINY):
    """The token-by-token delta rule in numpy float64: (o [R, L, Hv * dv],
    the final state [R, Hv, dk, dv])."""
    HK, HV, DK, DV = sizes
    qkv = v["qkv"].astype(np.float64)
    r, length, _ = qkv.shape
    kd = HK * DK
    q = qkv[..., :kd].reshape(r, length, HK, DK)
    k = qkv[..., kd:2 * kd].reshape(r, length, HK, DK)
    val = qkv[..., 2 * kd:].reshape(r, length, HV, DV)
    q = q / np.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) / np.sqrt(DK)
    k = k / np.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    q, k = (np.repeat(x, HV // HK, axis=2) for x in (q, k))
    beta = 1 / (1 + np.exp(-v["b"].astype(np.float64)))
    alpha = np.exp(-np.exp(v["a_log"].astype(np.float64)) * np.log1p(
        np.exp(v["a"].astype(np.float64) + v["dt_bias"])))
    s = np.zeros((r, HV, DK, DV)) if state is None \
        else state.astype(np.float64)
    out = []
    for t in range(length):
        s = alpha[:, t, :, None, None] * s
        held = np.einsum("rhkv,rhk->rhv", s, k[:, t]) if correction else 0.0
        u = beta[:, t, :, None] * (val[:, t] - held)
        s = s + k[:, t, :, :, None] * u[:, :, None, :]
        if state_dtype is not None:
            s = np.asarray(jnp.asarray(s, jnp.float32).astype(state_dtype)
                           .astype(jnp.float32), np.float64)
        out.append(np.einsum("rhkv,rhk->rhv", s, q[:, t]))
    return np.stack(out, 1).reshape(r, length, HV * DV), s


def state_shape(rows, sizes=TINY):
    hk, hv, dk, dv = sizes
    return kv_cache.ssm_state_shape(rows, hv, dv, dk, hk)


def stored(state):
    """[R, Hv, dk, dv] -> the stored layout, and back."""
    shape = kv_cache.ssm_state_shape(state.shape[0], HV, DV, DK, HK)
    return np.asarray(ssm.pack_state(
        jnp.swapaxes(jnp.asarray(state, jnp.float32), 2, 3), shape[3]))


def unstored(state, sizes=TINY):
    return np.swapaxes(np.asarray(ssm.unpack_state(jnp.asarray(state),
                                                   sizes[3])), 2, 3)


def scan_op(rows, length, chunk, row=None, sizes=TINY):
    def build(v, blk):
        out = blk.create_var(name="o",
                             shape=(rows, length, sizes[1] * sizes[3]),
                             dtype="float32")
        ins = dict(SLOTS, **({"Row": [row]} if row else {}))
        blk.append_op("gated_delta_chunk_scan", ins,
                      {"Out": ["o"], "StateOut": ["state"]},
                      dict(attrs(sizes), chunk=chunk))
        return [out]
    return build


def interpreted_scan(monkeypatch):
    """The prefill's op takes its Pallas kernel, interpreted (the CPU
    takes the `jnp` form unless steered)."""
    monkeypatch.setattr(ssm, "gated_delta_scan", functools.partial(
        ssm.gated_delta_scan, interpret=True))


def update_op(v, blk):
    out = blk.create_var(name="o", shape=(v["qkv"].shape[0], 1, HV * DV),
                         dtype="float32")
    blk.append_op("gated_delta_state_update", SLOTS,
                  {"Out": ["o"], "StateOut": ["state"]}, ATTRS)
    return [out]


@pytest.mark.parametrize("length,chunk", [(16, 8), (24, 8), (13, 8), (5, 8),
                                          (9, 4), (70, 64)])
def test_chunked_delta_rule_matches_the_recurrence(length, chunk):
    """Outputs AND the final state, at lengths that are and are not whole
    chunks (and one shorter than a chunk), the published chunk of 64
    among them."""
    v = delta_inputs(20 + length, 2, length)
    shape = kv_cache.ssm_state_shape(2, HV, DV, DK, HK)
    (got,), state = run_ops(scan_op(2, length, chunk), v,
                            {"state": rand(1, *shape)})     # stale: not read
    want, final = recurrence(v)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(unstored(state["state"]), final,
                               rtol=2e-4, atol=2e-5)


def test_chunked_delta_rule_with_a_state_carried_in():
    """A sequence cut in two: the second half from the first half's
    state is the recurrence over all of it (11 + 14 rows, chunks of 8)."""
    v = delta_inputs(31, 2, 25)
    ins = [jnp.asarray(v[k]) for k in ("qkv", "b", "a", "a_log", "dt_bias")]

    def chunked(lo, hi, state):
        cut = [x[:, lo:hi] if x.ndim == 3 else x for x in ins]
        return ssm.gated_delta_chunked(
            *ssm.delta_rule_inputs(*cut, **ATTRS), 8, state=state)

    first, carried = chunked(0, 11, None)
    second, final = chunked(11, 25, carried)
    want, want_final = recurrence(v)
    got = np.concatenate([first, second], 1).reshape(2, 25, HV * DV)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(final, want_final, rtol=2e-4, atol=2e-5)


def test_the_solve_inverts_a_unit_lower_triangle():
    strict = np.tril(rand(33, 3, 2, 16, 16), -1)
    got = ssm.unit_lower_inverse(jnp.asarray(strict))
    np.testing.assert_allclose(
        got, np.linalg.inv(np.eye(16) + strict.astype(np.float64)),
        rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("sizes,chunk", [(TINY, 8), (WIDE, 16)],
                         ids=["jnp", "kernel"])
def test_chunked_scan_writes_a_row_block_of_the_batchs_state(
        sizes, chunk, monkeypatch):
    """The rows' final state lands at `Row` in the stored layout and no
    other row moves: through the `jnp` form at the tiny sizes (two heads
    a lane row, packed) and through the kernel, interpreted, at the
    cell's head sizes, where [dk, dv] of a value head IS the stored
    layout and nothing is swapped on the way."""
    obs.reset()
    kernel = sizes == WIDE
    if kernel:
        interpreted_scan(monkeypatch)
    v = delta_inputs(34, 2, 11, sizes)
    before = rand(35, *state_shape(5, sizes))
    (_o,), state = run_ops(scan_op(2, 11, chunk, row="row", sizes=sizes),
                           dict(v, row=np.array([2], np.int64)),
                           {"state": before})
    _want, final = recurrence(v, sizes=sizes)
    np.testing.assert_allclose(unstored(state["state"][2:4], sizes), final,
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(state["state"][[0, 1, 4]],
                                  before[[0, 1, 4]])
    assert obs.get_gauges()["kernels.gdn_chunk_scan.calls"] == kernel
    if kernel:
        np.testing.assert_allclose(state["state"][2:4], final, rtol=2e-4,
                                   atol=2e-5)


# -- the prefill's kernel ------------------------------------------------------

SMALLEST = (1, 2, 128, 128)     # one key head, the smallest chunk taken


@pytest.mark.parametrize("sizes,chunk,length,carried,dtype", [
    (SMALLEST, 16, 32, False, "float32"),
    (SMALLEST, 16, 21, True, "float32"),
    (WIDE, 64, 128, False, "float32"),
    (WIDE, 64, 70, True, "float32"),
    (WIDE, 64, 128, True, "bfloat16"),
    (WIDE, 64, 70, False, "bfloat16"),
])
def test_the_scan_kernel_in_interpret_mode(sizes, chunk, length, carried,
                                           dtype):
    """kernels/gdn_chunk_scan.py (the TPU path) against the `jnp` form
    AND the token-by-token recurrence: the smallest sizes it takes and
    the cell's head sizes (Hk < Hv, 128 lanes, chunks of 64), L whole
    chunks and not, from a zero state and from one carried in, float32
    and bfloat16 activations (the recurrence then reads the rounded
    activations in float64). The interpreter's uninitialised memory is
    NaN and a read out of bounds raises: nothing unwritten is read."""
    from paddle_tpu.kernels import gdn_chunk_scan

    assert gdn_chunk_scan.supports(*sizes, chunk, dtype)
    v = delta_inputs(70 + length, 2, length, sizes)
    v["qkv"] = np.asarray(jnp.asarray(v["qkv"]).astype(dtype)
                          .astype(jnp.float32))
    first = rand(71, 2, sizes[1], sizes[2], sizes[3], scale=0.3) \
        if carried else None
    ins = [jnp.asarray(v[k]) for k in ("b", "a", "a_log", "dt_bias")]
    qkv = jnp.asarray(v["qkv"]).astype(dtype)
    state = None if first is None else jnp.asarray(first)
    form_o, form_s = ssm.gated_delta_chunked(
        *ssm.delta_rule_inputs(qkv, *ins, **attrs(sizes)), chunk,
        state=state, lo=qkv.dtype)
    got_o, got_s = gdn_chunk_scan.scan(
        qkv, *ssm.delta_gates(*ins), state, chunk=chunk, interpret=True,
        **attrs(sizes))
    assert got_o.dtype == qkv.dtype and got_s.dtype == jnp.float32
    want_o, want_s = recurrence(v, first, sizes=sizes)
    got_o = np.asarray(got_o.astype(jnp.float32))
    form_o = np.asarray(form_o).reshape(got_o.shape)
    if dtype == "float32":
        limit = dict(rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(got_o, form_o, **limit)
        np.testing.assert_allclose(got_s, form_s, **limit)
        np.testing.assert_allclose(got_o, want_o, **limit)
        np.testing.assert_allclose(got_s, want_s, **limit)
    else:
        # bfloat16 operands in the four products of two activations: held
        # as the served model is, by the largest element (3e-2 there);
        # kernel and `jnp` form round at the same places
        def far(a, b):
            return np.abs(np.asarray(a) - b).max() / np.abs(b).max()
        assert far(got_o, want_o) < 1e-2 and far(got_s, want_s) < 1e-2
        assert far(form_o, want_o) < 1e-2
        assert far(got_o, form_o) < 1e-2 and far(got_s, np.asarray(
            form_s)) < 1e-3


@pytest.mark.parametrize("sizes,chunk,dtype,taken", [
    ((16, 32, 128, 128), 64, "bfloat16", True),     # the cell's
    (SMALLEST, 16, "float32", True),
    (TINY, 16, "float32", False),       # heads narrower than a lane tile
    (WIDE, 8, "float32", False),        # a chunk under a bfloat16 tile
    (WIDE, 48, "float32", False),       # no power of two
    ((3, 4, 128, 128), 64, "float32", False),   # key heads do not divide
    (WIDE, 64, "float16", False),
])
def test_what_the_scan_kernel_takes(sizes, chunk, dtype, taken):
    from paddle_tpu.kernels import gdn_chunk_scan

    assert gdn_chunk_scan.supports(*sizes, chunk, dtype) == taken


@pytest.mark.parametrize("interpret,sizes,chunk", [
    (False, TINY, 8), (True, TINY, 8), (True, WIDE, 16)],
    ids=["jnp", "update-kernel", "both-kernels"])
def test_forty_one_token_updates_continue_a_prefills_state(
        interpret, sizes, chunk, monkeypatch):
    """Prefill 11 rows by the chunked scan, then 40 one-token updates on
    the stored state (the `jnp` forms; the update's kernel interpreted;
    at the cell's head sizes BOTH kernels interpreted, the prefill's
    `gdn_chunk_scan` handing its state to `gdn_state_update`): the
    recurrence over all 51. A state rounded to bfloat16 a step drifts
    further from it than the limit the float32 one is held to."""
    obs.reset()
    if sizes == WIDE:
        interpreted_scan(monkeypatch)
    v = delta_inputs(40, 3, 51, sizes)
    head = {k: (a[:, :11] if a.ndim == 3 else a) for k, a in v.items()}
    shape = state_shape(3, sizes)
    (first,), state = run_ops(scan_op(3, 11, chunk, sizes=sizes), head,
                              {"state": np.zeros(shape, np.float32)})
    assert obs.get_gauges()["kernels.gdn_chunk_scan.calls"] == \
        (sizes == WIDE)
    got, held = [first], jnp.asarray(state["state"])
    for t in range(11, 51):
        step = [jnp.asarray(a[:, t:t + 1] if a.ndim == 3 else a)
                for a in (v[k] for k in ("qkv", "b", "a", "a_log",
                                         "dt_bias"))]
        o, held, kernel = ssm.gated_delta_update(
            *step, held, interpret=interpret, **attrs(sizes))
        assert kernel == interpret
        got.append(np.asarray(o))
    want, final = recurrence(v, sizes=sizes)
    limit = dict(rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.concatenate(got, 1), want, **limit)
    np.testing.assert_allclose(unstored(held, sizes), final, **limit)
    _o, rounded = recurrence(v, state_dtype=jnp.bfloat16, sizes=sizes)
    assert np.abs(rounded - final).max() > 20 * np.abs(
        unstored(held, sizes) - final).max()
    assert not np.allclose(rounded, final, **limit)


def test_the_update_op_writes_the_state_in_place():
    """The op around it: one step on a stored state through a program,
    the state persistable written back."""
    v = delta_inputs(41, 2, 1)
    before = rand(42, 2, HV, DK, DV, scale=0.3)
    (o,), state = run_ops(update_op, v, {"state": stored(before)})
    want, final = recurrence(v, before)
    np.testing.assert_allclose(o, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(unstored(state["state"]), final, rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("sizes", [(HV, DV, DK, HK), (32, 128, 128, 16)])
def test_the_update_kernel_in_interpret_mode(sizes):
    """kernels/ssm_update.py with the correction (the TPU path) against
    the `jnp` path on the stored layout: the tiny sizes (two heads a lane
    row) and the published ones (a head a row of 128 lanes, pack 1). The
    interpreter's uninitialised memory is NaN: nothing unwritten is
    read."""
    from paddle_tpu.kernels import ssm_update

    hv, dv, dk, hk = sizes
    shape = kv_cache.ssm_state_shape(2, hv, dv, dk, hk)
    packs, lanes = shape[1], shape[3]
    state = jnp.asarray(rand(50, *shape))
    val = jnp.asarray(rand(51, 2, packs, lanes))
    decay, beta = (jnp.asarray(np.random.RandomState(s).uniform(
        0.2, 1.0, (2, packs, lanes)).astype(np.float32)) for s in (52, 53))
    kt, qt = jnp.asarray(rand(54, 2, dk, hk)), jnp.asarray(rand(55, 2, dk, hk))
    want_o, want_s = ssm_update.update_reference(state, val, decay, kt, qt,
                                                 beta)
    got_o, got_s = ssm_update.delta_update(state, val, decay, kt, qt, beta,
                                           interpret=True)
    np.testing.assert_allclose(got_o, want_o, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got_s, want_s, rtol=1e-5, atol=1e-5)
    # the correction is not a no-op, and without `beta` the kernel is the
    # state-space update it was
    plain_o, plain_s = ssm_update.update(state, val, decay, kt, qt,
                                         interpret=True)
    assert np.abs(np.asarray(plain_s) - np.asarray(got_s)).max() > 0.1
    ref_o, ref_s = ssm_update.update_reference(state, val, decay, kt, qt)
    np.testing.assert_allclose(plain_o, ref_o, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(plain_s, ref_s, rtol=1e-5, atol=1e-5)


def test_the_correction_dropped_is_another_function():
    """u_t = beta_t v_t (no read of the state before it is written) is
    far from the delta rule on the same inputs: a check that holds the
    program to the recurrence sees the correction."""
    v = delta_inputs(60, 2, 24)
    shape = kv_cache.ssm_state_shape(2, HV, DV, DK, HK)
    (got,), _ = run_ops(scan_op(2, 24, 8), v,
                        {"state": np.zeros(shape, np.float32)})
    want, _ = recurrence(v)
    dropped, _ = recurrence(v, correction=False)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    assert np.abs(dropped - want).max() > 0.1 * np.abs(want).max()
    assert not np.allclose(got, dropped, rtol=2e-2, atol=2e-3)


def test_the_delta_state_takes_the_state_space_layout():
    # published: one value head a row of 128 lanes, the key dimension on
    # the sublanes, whatever the context
    assert kv_cache.ssm_state_shape(64, 32, 128, 128, 16) == \
        (64, 32, 128, 128)
    # tiny: the two value heads of a key head share a lane row
    assert kv_cache.ssm_state_shape(2, HV, DV, DK, HK) == (2, 2, 16, 32)
    s = rand(61, 2, HV, DK, DV)
    np.testing.assert_array_equal(unstored(stored(s)), s)
    # lane l of pack p is channel l % dv of value head 2 p + l // dv
    np.testing.assert_array_equal(stored(s)[1, 1, :, 16 + 5], s[1, 3, :, 5])


# -- gate, norm, rotary --------------------------------------------------------

def test_the_gate_after_the_norm_is_not_the_gate_before_it():
    x, z, gain = rand(62, 2, 3, 32), rand(63, 2, 3, 32), 1 + rand(
        64, 8, scale=0.1)

    def build(after):
        def inner(v, blk):
            from paddle_tpu.layers.tensor import _simple

            return [_simple(
                "gated_rms_norm",
                {"X": [v["x"]], "Gate": [v["z"]], "Scale": [v["g"]]},
                {"num_groups": 4, "epsilon": 1e-6, "gate_after": after})]
        return inner

    feeds = {"x": x, "z": z, "g": gain}
    (after,), _ = run_ops(build(True), feeds)
    (before,), _ = run_ops(build(False), feeds)
    silu = z / (1 + np.exp(-z))
    n = x.reshape(2, 3, 4, 8)
    want = (n / np.sqrt((n ** 2).mean(-1, keepdims=True) + 1e-6)
            * gain).reshape(2, 3, 32) * silu
    np.testing.assert_allclose(after, want, rtol=2e-5, atol=2e-6)
    g = (x * silu).reshape(2, 3, 4, 8)
    other = (g / np.sqrt((g ** 2).mean(-1, keepdims=True) + 1e-6)
             * gain).reshape(2, 3, 32)
    np.testing.assert_allclose(before, other, rtol=2e-5, atol=2e-6)
    assert np.abs(after - before).max() > 0.1


def test_rotary_turns_the_leading_lanes_and_passes_the_rest():
    x = rand(65, 2, 5, 3 * 16)          # three heads of 16, 4 lanes turn
    first = 7
    got = np.asarray(llm.rotary(jnp.asarray(x), first, 16, 1e7,
                                rotary_dim=4, leading=True))
    heads = x.reshape(2, 5, 3, 16)
    np.testing.assert_array_equal(got.reshape(2, 5, 3, 16)[..., 4:],
                                  heads[..., 4:])
    pos = first + np.arange(5)
    angle = pos[:, None] * (1e7 ** (-np.arange(2) * 2.0 / 4))[None, :]
    cos, sin = np.cos(angle)[None, :, None], np.sin(angle)[None, :, None]
    x1, x2 = heads[..., :2], heads[..., 2:4]
    want = np.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    np.testing.assert_allclose(got.reshape(2, 5, 3, 16)[..., :4], want,
                               rtol=1e-5, atol=1e-5)
    # the op's default still turns the LAST lanes
    last = np.asarray(llm.rotary(jnp.asarray(x), first, 16, 1e7,
                                 rotary_dim=4))
    np.testing.assert_array_equal(last.reshape(2, 5, 3, 16)[..., :12],
                                  heads[..., :12])
    assert np.abs(last - got).max() > 0.1


def test_a_stored_gain_is_its_distance_from_one():
    x, w = rand(66, 2, 3, 16), rand(67, 16, scale=0.1)

    def build(offset):
        def inner(v, blk):
            from paddle_tpu.layers.tensor import _simple

            return [_simple("rms_norm", {"X": [v["x"]], "Scale": [v["w"]]},
                            {"epsilon": 1e-6, "unit_offset": offset})]
        return inner

    (got,), _ = run_ops(build(True), {"x": x, "w": w})
    (plain,), _ = run_ops(build(False), {"x": x, "w": 1 + w})
    want = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) * (1 + w)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(plain, want, rtol=1e-5, atol=1e-6)


# -- softmax routing and the shares -------------------------------------------

def ffn_weights(seed, n, hidden=32, f=24, e_total=16):
    return {"router_w": rand(seed, hidden, e_total, scale=0.5),
            "experts_gate_up_w": rand(seed + 1, n, hidden, 2 * f, scale=0.3),
            "experts_down_w": rand(seed + 2, n, f, hidden, scale=0.3),
            "shared_gate_up_w": rand(seed + 3, hidden, 2 * f, scale=0.3),
            "shared_down_w": rand(seed + 4, f, hidden, scale=0.3),
            "shared_gate_w": rand(seed + 5, hidden, 1, scale=0.5)}


REF_CFG = {"top_k": 10, "route_norm": True}


def routed(m, w, offset, experts=slice(None), **kw):
    return moe.local_experts_ffn(
        jnp.asarray(m), w["router_w"], None,
        w["experts_gate_up_w"][experts], w["experts_down_w"][experts],
        top_k=10, route_scale=1.0, expert_offset=offset, scoring="softmax",
        **kw)


def test_softmax_routing_weighs_ten_experts_to_one_with_ties():
    """Softmax over all 16, top-10, the weights renormalised over the
    ten wherever they live; two experts with the SAME router column tie
    in every token and still make ten distinct choices."""
    w = ffn_weights(70, 16)
    w["router_w"][:, 5] = w["router_w"][:, 11]
    m = rand(77, 40, 32)
    sel, weights = moe.sigmoid_topk_route(
        jnp.asarray(m), w["router_w"], None, 10, 1.0, scoring="softmax")
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    assert all(len(set(row)) == 10 for row in np.asarray(sel))
    p = np.exp(m @ w["router_w"])
    p /= p.sum(-1, keepdims=True)
    picked = np.take_along_axis(p, np.asarray(sel), -1)
    np.testing.assert_allclose(weights, picked / picked.sum(-1, keepdims=True),
                               rtol=1e-5)
    # the ten largest: no expert left out scores above one taken
    left = np.where(np.isin(np.arange(16)[None], np.asarray(sel)[:, :, None])
                    .any(1), -np.inf, p)
    assert (left.max(-1) <= picked.min(-1) + 1e-9).all()
    # sigmoid scoring stays the default, with its bias buffer
    bias = rand(78, 16, scale=0.01)
    default = moe.sigmoid_topk_route(jnp.asarray(m), w["router_w"], bias, 4,
                                     2.0)
    named = moe.sigmoid_topk_route(jnp.asarray(m), w["router_w"], bias, 4,
                                   2.0, scoring="sigmoid")
    for got, want in zip(default, named):
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("interpret", [False, True])
def test_softmax_routed_experts_match_the_dense_sum(interpret):
    from benchmark.reference import qwen3_next as reference

    w = ffn_weights(80, 4)
    m = rand(88, 2, 20, 32)
    y, sel, counts = routed(m, w, 8, interpret=interpret)
    cfg = dict(REF_CFG, expert_offset=8)
    with jax.default_matmul_precision("highest"):
        want_sel, weights, _ = reference.route(w, jnp.asarray(m), cfg)
        want = reference.routed_part(w, jnp.asarray(m), want_sel, weights,
                                     cfg)
    np.testing.assert_array_equal(np.sort(sel, -1), np.sort(want_sel, -1))
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    local = (np.asarray(want_sel) >= 8) & (np.asarray(want_sel) < 12)
    assert int(counts.sum()) == int(local.sum())


def test_eight_shares_and_the_gated_shared_expert_once_make_the_whole_ffn():
    """The share test: each of the eight chips' routed part (2 of 16
    experts), plus the shared expert behind its gate counted once, add up
    to the uncut reference FFN."""
    from benchmark.reference import qwen3_next as reference

    w = ffn_weights(90, 16)
    m = rand(98, 2, 12, 32)
    total = np.zeros_like(m)
    for chip in range(8):
        part, _sel, _n = routed(m, w, 2 * chip, slice(2 * chip, 2 * chip + 2))
        total += np.asarray(part)
    with jax.default_matmul_precision("highest"):
        total += np.asarray(reference.shared_part(w, jnp.asarray(m)))
        whole, _sel, _r = reference.ffn(w, jnp.asarray(m),
                                        dict(REF_CFG, expert_offset=0))
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-5)
    # and the gate is there: the shared expert ungated is another sum
    ungated = np.asarray(reference.swiglu(
        jnp.asarray(m), w["shared_gate_up_w"], w["shared_down_w"]))
    gated = np.asarray(reference.shared_part(w, jnp.asarray(m)))
    assert np.abs(ungated - gated).max() > 0.05


# -- the decoder through the generator ---------------------------------------

def tiny_generator(batch=2, context=11, new=8, **kw):
    cfg = Qwen3NextConfig.tiny(**kw)
    gen = GPTGenerator(Qwen3NextDecoder(cfg), batch=batch,
                       context_len=context, max_len=context + new)
    gen.init_params(seed=7)
    return gen


@pytest.fixture(scope="module")
def served():
    """One float32 tiny generator for the tests that only serve with it
    (`generate` resets its state a batch)."""
    obs.reset()
    return tiny_generator(dtype="float32")


def test_layer_kinds_state_specs_and_gauges(served):
    gen = served
    assert [k for k, _f in gen.cfg.layer_kinds] == [LINEAR] * 3 + [FULL]
    # the published stack: every fourth layer attends in full; a later
    # pipeline stage starts where its first layer's number says
    full = Qwen3NextConfig()
    assert [i for i, (k, _f) in enumerate(full.layer_kinds) if k == FULL] \
        == list(range(3, 48, 4))
    later = Qwen3NextConfig(num_layers=5, first_layer=14)
    assert [k for k, _f in later.layer_kinds] == \
        [LINEAR, FULL, LINEAR, LINEAR, LINEAR]
    conv = 2 * HK * DK + HV * DV
    specs = {n: (s, d) for n, s, d in gen._state_specs}
    assert specs["qwen3_next_l0_gdn_state"] == ((2, 2, 16, 32), "float32")
    assert specs["qwen3_next_l0_conv_tail"] == ((2, 3, conv), "float32")
    assert specs["qwen3_next_l3_cache_k"] == (
        kv_cache.cache_shape(2, 19, 2, 32), "float32")
    assert specs["qwen3_next_moe_counters"][1] == "int32"
    assert sum(n.endswith("_gdn_state") for n in specs) == 3
    assert sum("_cache_" in n for n in specs) == 2
    assert [gen._state_kinds[n] for n in (
        "qwen3_next_l0_gdn_state", "qwen3_next_l0_conv_tail",
        "qwen3_next_l3_cache_v", "qwen3_next_moe_counters")] == \
        ["linear", "conv", "full", None]
    gen.reset()
    for name, (shape, _d) in specs.items():
        held = gen.scope.find_var(name)
        assert held.shape == shape and not np.asarray(held).any()
    gauges = obs.get_gauges()
    table = obs.get_tables()["serving.generate.model"]
    # the same state at another context: only the KV caches grow
    longer = GPTGenerator(gen.decoder, batch=2, context_len=40,
                          max_len=48)._state_specs
    grown = {n for n, s, _d in longer if s != specs[n][0]}
    assert grown == {"qwen3_next_l3_cache_k", "qwen3_next_l3_cache_v"}
    assert table["family"] == "qwen3_next"
    assert table["layer_kinds"] == [["linear", "experts"]] * 3 \
        + [["full", "experts"]]
    assert table["state_bytes_per_sequence"] == {
        "linear": 4 * HV * DK * DV, "conv": 4 * 3 * conv,
        "full_per_position": 4 * 2 * 2 * 32}
    assert gauges["kv_cache.bytes.linear"] == 3 * 2 * HV * DK * DV * 4
    assert gauges["kv_cache.bytes.conv"] == 3 * 2 * 3 * conv * 4
    assert gauges["kv_cache.bytes.full"] == 2 * 2 * 2 * 32 * 19 * 4
    with pytest.raises(ValueError):
        Qwen3NextConfig.tiny(chunk_size=12)


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 3e-2)])
def test_prefill_then_cached_decode_match_the_reference(dtype, tol,
                                                        monkeypatch):
    """Both mixer kinds; the batch of 2 is prefilled a row a dispatch
    (`prefill_rows` smaller than the batch), a context of 11 is not
    whole chunks of 8; then 8 cached steps read the conv tail, the
    delta-rule state and the KV cache back. Both against the reference's
    full forward pass (the recurrence, no chunks, no cache). In float32
    the two agree to rounding. The bfloat16 case takes its decode steps'
    delta rule through the Pallas kernel (interpreted): one call a
    linear layer and step, the state aliased in place."""
    from benchmark.builders import qwen3_next as builder

    obs.reset()
    kernel = dtype == "bfloat16"
    if kernel:
        monkeypatch.setattr(ssm, "gated_delta_update", functools.partial(
            ssm.gated_delta_update, interpret=True))
    gen = tiny_generator(batch=2, prefill_rows=1, dtype=dtype)
    prompts = np.random.RandomState(5).randint(0, 256, (2, 11))
    seen = builder.probe_generator(gen, prompts, decode_steps=8)
    report = builder.compare(gen, seen, tol=tol)
    assert report["ok"], report
    assert report["decode_routing"]["mismatches"] == 0
    assert report["decode_routing"]["tokens"] == 4 * 2 * 19
    # the CPU takes the `jnp` path unless steered
    assert obs.get_gauges()["kernels.gdn_update.calls"] == 3 * kernel
    assert obs.get_gauges()["kernels.gdn_chunk_scan.calls"] == 0
    if dtype == "float32":
        assert report["decode_routing"]["near_ties"] == 0
        # the check is not blind to the delta rule, nor to the carried
        # state's precision being the recurrence's own
        dropped = builder.compare(gen, seen, tol=tol, correction=False)
        assert not dropped["ok"]
        assert dropped["decode_err"] > 1e3 * report["decode_err"]


def test_the_cells_twelve_layers_lower_to_nine_and_three_kernel_calls(
        monkeypatch):
    """Twelve layers as the cell runs them (nine linear, three full) at
    heads the attention kernels take and a prompt of one block, the
    three dispatches steered to their kernels: lowered (the gauges are
    set where a step is lowered; nothing is compiled or run) a decode
    step holds 9 `gdn_state_update` and 3 `decode_attention` calls, a
    prefill dispatch 9 `gdn_chunk_scan` and 3 `prefill_attention`
    calls."""
    obs.reset()
    interpreted_scan(monkeypatch)
    monkeypatch.setattr(ssm, "gated_delta_update", functools.partial(
        ssm.gated_delta_update, interpret=True))
    monkeypatch.setattr(kv_cache, "decode_attention", functools.partial(
        kv_cache.decode_attention, interpret=True))
    monkeypatch.setattr(llm, "prefill_attention", functools.partial(
        llm.prefill_attention, interpret=True))
    cfg = Qwen3NextConfig.tiny(num_layers=12, head_dim=128,
                               linear_key_head_dim=128,
                               linear_value_head_dim=128, chunk_size=64)
    gen = GPTGenerator(Qwen3NextDecoder(cfg), batch=2, context_len=128,
                       max_len=130)
    gen.reset()
    for var in gen._param_vars():       # nothing runs: any values do
        gen.scope.set_var(var.name, jnp.zeros(var.shape, var.dtype))
    gen.executor.lower(
        gen.prefill_prog, feed={"context_ids": np.zeros((2, 128), np.int64)},
        fetch_list=gen._prefill_fetch, scope=gen.scope)
    gen.executor.lower(
        gen.decode_prog,
        feed={"token_ids": np.zeros((2, 1), np.int64),
              "pos_ids": np.full((1, 1), 128, np.int64)},
        fetch_list=gen._decode_fetch, scope=gen.scope)
    gauges = obs.get_gauges()
    assert gauges["kernels.gdn_update.calls"] == 9
    assert gauges["kernels.gdn_chunk_scan.calls"] == 9
    assert gauges["kernels.decode_attention.calls"] == 3
    assert gauges["kernels.prefill_attention.calls"] == 3


def test_a_second_batch_starts_from_a_zero_state(served):
    gen = served
    prompts = np.random.RandomState(8).randint(0, 256, (2, 11))
    first = np.asarray(gen.generate(prompts, 6))
    other = np.random.RandomState(9).randint(0, 256, (2, 11))
    gen.generate(other, 6)
    np.testing.assert_array_equal(np.asarray(gen.generate(prompts, 6)), first)


def test_counters_and_selected_ids_ride_with_the_steps(served):
    gen = served
    prompts = np.random.RandomState(10).randint(0, 256, (2, 11))
    gen.generate(prompts, 4)
    counters = np.asarray(gen.scope.find_var("qwen3_next_moe_counters"))
    names = gen.decoder.counter_names
    got = dict(zip(names, counters))
    # 4 layers x (one prefill call of 2 x 11 tokens + 3 decode calls of 2)
    assert got["moe.calls"] == 4 * 4 and got["moe.decode_calls"] == 4 * 3
    assert got["moe.assignments_total"] == 4 * (22 + 3 * 2) * 4
    assert 0 < got["moe.assignments_local"] < got["moe.assignments_total"]
    assert len(gen._prefill_fetch) == 2 and len(gen._decode_fetch) == 2


def test_the_configuration_file_keeps_every_published_width(served):
    from benchmark.builders import qwen3_next as builder
    from benchmark.harness import qwen3_next_cost as cost

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "qwen3_next_ep8.json")) as f:
        file = json.load(f)
    cfg = builder.model_config(file)
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
            cfg.rotary_dim, cfg.rope_theta) == (2048, 16, 2, 256, 64, 1e7)
    assert (cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel_dim, cfg.chunk_size) == \
        (16, 32, 128, 128, 4, 64)
    assert (cfg.num_experts, cfg.num_local_experts, cfg.top_k,
            cfg.moe_intermediate_size, cfg.shared_intermediate_size) == \
        (512, 64, 10, 512, 512)
    assert [k for k, _f in cfg.layer_kinds] == ([LINEAR] * 3 + [FULL]) * 3
    assert cfg.vocab_size * 8 == file["published"]["vocab_size"]
    assert file["reduced"] == ["num_hidden_layers", "num_experts",
                               "vocab_size"]
    dep = file["deployment"]
    assert (dep["chips_per_layer"], dep["pipeline_stages"], dep["this_chip"],
            dep["layers_run"]) == (8, 4, 0, list(range(12)))
    # the closed form counts what the program holds
    model = Qwen3NextDecoder(cfg).describe()
    assert cost.linear_matrix_params(model) == 25_165_824 + 131_072 \
        + 8_388_608
    assert cost.attention_matrix_params(model) == 16_777_216 \
        + 2 * 1_048_576 + 8_388_608
    assert cost.expert_params(model) == 3_145_728
    assert cost.ffn_always(model) == 1_048_576 + 3_145_728 + 2048
    held = sum(int(np.prod(v.shape)) for v in served._param_vars())
    assert held == cost.resident_params(served.decoder.describe())
