"""Nemotron-H serving path (models/nemotron_h.py): each new op against
`jnp`, the state kinds that do not grow with the context beside the KV
cache, the latent relu2 experts and their share of an expert-parallel
deployment, prefill + cached decode against the plain reference
(benchmark/reference/nemotron_h.py) over all three block kinds, the
float32 recurrent state held at the op, and the one generator serving
all three decoders. CPU, tiny sizes, seeded weights."""

import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu.models.nemotron_h import (
    ATTENTION, EXPERTS, MAMBA, NemotronHConfig, NemotronHDecoder,
)
from paddle_tpu.ops import kv_cache, ssm
from paddle_tpu.parallel import moe
from paddle_tpu.serving import GPTGenerator

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
H, P, G, N = 8, 16, 2, 16       # the tiny Mamba-2 sizes
CONV = H * P + 2 * G * N


def rand(seed, *shape, scale=1.0):
    return (np.random.RandomState(seed).randn(*shape) * scale).astype(
        np.float32)


def run_ops(build, feeds, state=None):
    """A graph of ops through Program / Executor. `build(vars)` appends
    the ops and returns the variables to fetch; `state` {name: array} are
    persistables set in the scope first and read back afterwards."""
    from paddle_tpu.framework.scope import Scope, scope_guard

    main, startup = fluid.Program(), fluid.Program()
    scope = Scope()
    with fluid.program_guard(main, startup):
        vars_ = {n: fluid.data(n, list(a.shape), str(a.dtype))
                 for n, a in feeds.items()}
        blk = main.global_block
        for n, a in (state or {}).items():
            vars_[n] = blk.create_var(name=n, shape=a.shape,
                                      dtype=str(a.dtype), persistable=True)
            scope.set_var(n, jnp.asarray(a))
        outs = build(vars_, blk)
    with scope_guard(scope):
        got = fluid.Executor().run(main, feed=feeds, scope=scope,
                                   fetch_list=[o.name for o in outs])
    return got, {n: np.asarray(scope.find_var(n)) for n in (state or {})}


# -- the convolution ----------------------------------------------------------

def conv_by_hand(x, w, b, history):
    k = w.shape[1]
    window = np.concatenate([history, x], axis=1)
    out = np.zeros_like(x) + b
    for t in range(x.shape[1]):
        for j in range(k):
            out[:, t] += w[:, j] * window[:, t + j]
    return out / (1.0 + np.exp(-out)), window[:, x.shape[1]:]


def conv_op(x_name, row=None, carry=False):
    def build(v, blk):
        out = blk.create_var(name="out", shape=v[x_name].shape,
                             dtype="float32")
        ins = {"X": [x_name], "W": ["w"], "Bias": ["b"], "Tail": ["tail"]}
        if row:
            ins["Row"] = [row]
        blk.append_op("causal_conv1d", ins,
                      {"Out": ["out"], "TailOut": ["tail"]},
                      {"carry": carry})
        return [out]
    return build


def test_conv_from_a_sequence_start_pads_zeros_and_leaves_its_tail():
    x, w, b = rand(0, 2, 7, 12), rand(1, 12, 4), rand(2, 12)
    stale = rand(3, 2, 3, 12)           # an earlier batch's tail: not read
    (got,), state = run_ops(conv_op("x"), {"x": x, "w": w, "b": b},
                            {"tail": stale})
    want, tail = conv_by_hand(x, w, b, np.zeros((2, 3, 12), np.float32))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(state["tail"], x[:, -3:])
    np.testing.assert_array_equal(tail, x[:, -3:])


def test_conv_with_a_carried_tail_continues_the_sequence():
    """A sequence cut in two calls, then token by token: the same as one
    call over all of it."""
    x, w, b = rand(4, 2, 9, 12), rand(5, 12, 4), rand(6, 12)
    whole, _ = conv_by_hand(x, w, b, np.zeros((2, 3, 12), np.float32))
    (first,), state = run_ops(conv_op("x"), {"x": x[:, :5], "w": w, "b": b},
                              {"tail": np.zeros((2, 3, 12), np.float32)})
    got = [first]
    for t in range(5, 9):               # decode steps: one row a call
        (step,), state = run_ops(conv_op("x", carry=True),
                                 {"x": x[:, t:t + 1], "w": w, "b": b}, state)
        got.append(step)
    np.testing.assert_allclose(np.concatenate(got, 1), whole, rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_array_equal(state["tail"], x[:, -3:])


def test_conv_takes_a_row_block_into_the_batchs_tail():
    x, w, b = rand(7, 2, 5, 12), rand(8, 12, 4), rand(9, 12)
    before = rand(10, 6, 3, 12)
    (got,), state = run_ops(
        conv_op("x", row="row"),
        {"x": x, "w": w, "b": b, "row": np.array([3], np.int64)},
        {"tail": before})
    want, _ = conv_by_hand(x, w, b, np.zeros((2, 3, 12), np.float32))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(state["tail"][3:5], x[:, -3:])
    np.testing.assert_array_equal(state["tail"][[0, 1, 2, 5]],
                                  before[[0, 1, 2, 5]])


# -- the recurrence -----------------------------------------------------------

def ssm_inputs(seed, rows, length):
    """xBC after the convolution, raw dt, and a block's small parameters,
    drawn where a Mamba-2 initialisation puts them."""
    rng = np.random.RandomState(seed)
    return dict(
        xbc=rand(seed + 1, rows, length, CONV, scale=0.5),
        dt=rand(seed + 2, rows, length, H),
        a_log=np.log(rng.uniform(1, 16, H)).astype(np.float32),
        d=rng.uniform(0.5, 1.5, H).astype(np.float32),
        dt_bias=rng.uniform(-4, -1, H).astype(np.float32),
    )


def recurrence(v, state=None, state_dtype=None):
    """The token-by-token recurrence in numpy: (y [R, L, H * P] with the
    D term, the final state [R, H, P, N])."""
    xbc, dt = v["xbc"].astype(np.float64), v["dt"].astype(np.float64)
    r, length, _ = xbc.shape
    x = xbc[..., :H * P].reshape(r, length, H, P)
    b = np.repeat(xbc[..., H * P:H * P + G * N].reshape(r, length, G, N),
                  H // G, axis=2)
    c = np.repeat(xbc[..., H * P + G * N:].reshape(r, length, G, N),
                  H // G, axis=2)
    dt = np.log1p(np.exp(dt + v["dt_bias"]))
    a = -np.exp(v["a_log"].astype(np.float64))
    s = np.zeros((r, H, P, N)) if state is None else state.astype(np.float64)
    ys = []
    for t in range(length):
        s = np.exp(dt[:, t] * a)[..., None, None] * s \
            + (dt[:, t, :, None] * x[:, t])[..., None] * b[:, t, :, None, :]
        if state_dtype is not None:
            s = np.asarray(jnp.asarray(s, jnp.float32).astype(state_dtype)
                           .astype(jnp.float32), np.float64)
        ys.append(np.einsum("rhpn,rhn->rhp", s, c[:, t])
                  + v["d"][:, None] * x[:, t])
    return np.stack(ys, 1).reshape(r, length, H * P), s


ATTRS = {"num_heads": H, "head_dim": P, "num_groups": G, "state_size": N}
SLOTS = {"XBC": ["xbc"], "Dt": ["dt"], "ALog": ["a_log"], "D": ["d"],
         "DtBias": ["dt_bias"], "State": ["state"]}


def scan_op(rows, length, chunk, row=None):
    def build(v, blk):
        out = blk.create_var(name="y", shape=(rows, length, H * P),
                             dtype="float32")
        ins = dict(SLOTS, **({"Row": [row]} if row else {}))
        blk.append_op("ssd_chunk_scan", ins,
                      {"Out": ["y"], "StateOut": ["state"]},
                      dict(ATTRS, chunk=chunk))
        return [out]
    return build


@pytest.mark.parametrize("length,chunk", [(16, 8), (24, 8), (13, 8), (5, 8),
                                          (9, 4)])
def test_chunked_scan_matches_the_recurrence(length, chunk):
    """Outputs AND the final state, at lengths that are and are not a
    multiple of the chunk (and one shorter than a chunk)."""
    v = ssm_inputs(20 + length, 2, length)
    shape = kv_cache.ssm_state_shape(2, H, P, N, G)
    (got,), state = run_ops(scan_op(2, length, chunk), v,
                            {"state": rand(1, *shape)})     # stale: not read
    want, final = recurrence(v)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        ssm.unpack_state(jnp.asarray(state["state"]), P), final,
        rtol=2e-4, atol=2e-5)


def test_chunked_scan_writes_a_row_block_of_the_batchs_state():
    v = ssm_inputs(30, 2, 11)
    shape = kv_cache.ssm_state_shape(5, H, P, N, G)
    before = rand(31, *shape)
    (_y,), state = run_ops(scan_op(2, 11, 8, row="row"),
                           dict(v, row=np.array([2], np.int64)),
                           {"state": before})
    _want, final = recurrence(v)
    np.testing.assert_allclose(
        ssm.unpack_state(jnp.asarray(state["state"][2:4]), P), final,
        rtol=2e-4, atol=2e-5)
    np.testing.assert_array_equal(state["state"][[0, 1, 4]],
                                  before[[0, 1, 4]])


def update_op(v, blk):
    out = blk.create_var(name="y", shape=(v["xbc"].shape[0], 1, H * P),
                         dtype="float32")
    blk.append_op("ssm_state_update", SLOTS,
                  {"Out": ["y"], "StateOut": ["state"]}, ATTRS)
    return [out]


def test_one_token_update_continues_a_prefills_state():
    """Prefill 11 rows by the chunked scan, then 6 one-token updates on
    the stored state: the recurrence over all 17."""
    v = ssm_inputs(40, 3, 17)
    head = {k: (a[:, :11] if a.ndim == 3 else a) for k, a in v.items()}
    shape = kv_cache.ssm_state_shape(3, H, P, N, G)
    (first,), state = run_ops(scan_op(3, 11, 8), head,
                              {"state": np.zeros(shape, np.float32)})
    got = [first]
    for t in range(11, 17):
        step = {k: (a[:, t:t + 1] if a.ndim == 3 else a)
                for k, a in v.items()}
        (y,), state = run_ops(update_op, step, state)
        got.append(y)
    want, final = recurrence(v)
    np.testing.assert_allclose(np.concatenate(got, 1), want, rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(
        ssm.unpack_state(jnp.asarray(state["state"]), P), final,
        rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("sizes", [(8, 16, 2, 16), (4, 64, 2, 128)])
def test_the_update_kernel_in_interpret_mode(sizes):
    """kernels/ssm_update.py (the TPU path) against the `jnp` path on the
    stored layout: the tiny sizes (4 heads a lane row) and the published
    head (two heads of 64 on 128 lanes, state 128)."""
    from paddle_tpu.kernels import ssm_update

    h, p, g, n = sizes
    shape = kv_cache.ssm_state_shape(3, h, p, n, g)
    packs, lanes = shape[1], shape[3]
    state = jnp.asarray(rand(50, *shape))
    xdt = jnp.asarray(rand(51, 3, packs, lanes))
    decay = jnp.asarray(np.random.RandomState(52).uniform(
        0.2, 1.0, (3, packs, lanes)).astype(np.float32))
    bt, ct = jnp.asarray(rand(53, 3, n, g)), jnp.asarray(rand(54, 3, n, g))
    want_y, want_s = ssm_update.update_reference(state, xdt, decay, bt, ct)
    got_y, got_s = ssm_update.update(state, xdt, decay, bt, ct,
                                     interpret=True)
    np.testing.assert_allclose(got_y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got_s, want_s, rtol=1e-6, atol=1e-6)
    # and the op's own dispatch through it
    v = ssm_inputs(55, 3, 1)
    if sizes == (H, P, G, N):
        y, new = ssm.ssm_update(
            *(jnp.asarray(v[k]) for k in ("xbc", "dt", "a_log", "d",
                                          "dt_bias")),
            state, interpret=True, **ATTRS)
        want, final = recurrence(v, np.asarray(ssm.unpack_state(state, p)))
        np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-5)
        np.testing.assert_allclose(ssm.unpack_state(new, p), final,
                                   rtol=2e-4, atol=2e-5)


def test_state_shapes_have_one_owner_and_do_not_grow_with_the_context():
    # published: two heads of 64 side by side on 128 lanes, N on sublanes
    assert kv_cache.ssm_state_shape(64, 128, 64, 128, 8) == \
        (64, 64, 128, 128)
    # tiny: the four heads of a group share a lane row
    assert kv_cache.ssm_state_shape(2, H, P, N, G) == (2, 2, 16, 64)
    # a pack never straddles two B / C groups
    assert kv_cache.ssm_state_shape(1, 6, 16, 16, 2) == (1, 2, 16, 48)
    assert kv_cache.conv_tail_shape(64, 10240, 4) == (64, 3, 10240)
    s = rand(60, 2, H, P, N)
    stored = ssm.pack_state(jnp.asarray(s), 64)
    assert stored.shape == (2, 2, 16, 64)
    # lane l of pack k is channel l % P of head k * 4 + l // P
    np.testing.assert_array_equal(stored[1, 1, :, 16 * 2 + 5], s[1, 6, 5, :])
    np.testing.assert_array_equal(ssm.unpack_state(stored, P), s)


def test_gated_group_norm_gates_before_it_normalises():
    x, z, gain = rand(61, 2, 3, 32), rand(62, 2, 3, 32), 1 + rand(
        63, 32, scale=0.1)

    def build(v, blk):
        from paddle_tpu.layers.tensor import _simple

        return [_simple("gated_rms_norm",
                        {"X": [v["x"]], "Gate": [v["z"]], "Scale": [v["g"]]},
                        {"num_groups": 4, "epsilon": 1e-5})]

    (got,), _ = run_ops(build, {"x": x, "z": z, "g": gain})
    g = (x * z / (1 + np.exp(-z))).reshape(2, 3, 4, 8)
    want = g / np.sqrt((g ** 2).mean(-1, keepdims=True) + 1e-5)
    want = want.reshape(2, 3, 32) * gain
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # norm-then-gate is another function
    n = x.reshape(2, 3, 4, 8)
    other = (n / np.sqrt((n ** 2).mean(-1, keepdims=True) + 1e-5)).reshape(
        2, 3, 32) * gain * z / (1 + np.exp(-z))
    assert np.abs(got - other).max() > 0.1


def test_relu2_squares_in_float32():
    x = rand(64, 2, 3, 16)

    def build(v, blk):
        from paddle_tpu.layers.tensor import _simple

        return [_simple("relu2", {"X": [v["x"]]}, {})]

    (got,), _ = run_ops(build, {"x": x})
    np.testing.assert_allclose(got, np.maximum(x, 0) ** 2, rtol=1e-6)
    lo = jnp.asarray(x * 3).astype(jnp.bfloat16)
    want = (np.maximum(np.asarray(lo, np.float32), 0) ** 2)
    np.testing.assert_array_equal(
        np.asarray(ssm.relu2(lo), np.float32),
        np.asarray(jnp.asarray(want).astype(jnp.bfloat16), np.float32))


# -- latent relu2 experts ------------------------------------------------------

def expert_weights(seed, n, hidden=32, latent=16, f=24, e_total=16):
    return dict(
        router_w=rand(seed, hidden, e_total, scale=0.3),
        bias=rand(seed + 1, e_total, scale=0.01),
        w1=rand(seed + 2, n, latent, f, scale=0.3),
        w2=rand(seed + 3, n, f, latent, scale=0.3),
        dn=rand(seed + 4, hidden, latent, scale=0.2),
        up=rand(seed + 5, latent, hidden, scale=0.2),
        s1=rand(seed + 6, hidden, 40, scale=0.2),
        s2=rand(seed + 7, 40, hidden, scale=0.2),
    )


REF_CFG = {"top_k": 6, "route_scale": 5.0, "route_norm": True}


def routed(a, w, offset, experts=slice(None), **kw):
    """This chip's routed part in the latent, through the program's op
    function: the router scores `a`, the experts work on a W_dn."""
    u = jnp.asarray(a) @ w["dn"]
    return moe.local_experts_ffn(
        u, w["router_w"], w["bias"], w["w1"][experts], w["w2"][experts],
        top_k=6, route_scale=5.0, expert_offset=offset, activation="relu2",
        router_x=jnp.asarray(a), **kw)


def reference_params(w):
    return {"router_w": w["router_w"], "expert_bias": w["bias"],
            "experts_up_w": w["w1"], "experts_down_w": w["w2"],
            "latent_down_w": w["dn"], "latent_up_w": w["up"],
            "shared_up_w": w["s1"], "shared_down_w": w["s2"]}


@pytest.mark.parametrize("interpret", [False, True])
def test_latent_relu2_experts_match_the_dense_sum(interpret):
    from benchmark.reference import nemotron_h as reference

    w = expert_weights(70, 4)
    a = rand(78, 2, 20, 32)
    y, sel, counts = routed(a, w, 8, interpret=interpret)
    cfg = dict(REF_CFG, expert_offset=8)
    p = reference_params(w)
    with jax.default_matmul_precision("highest"):
        want_sel, weights, _ = reference.route(p, jnp.asarray(a), cfg)
        want = reference.routed_part(p, jnp.asarray(a) @ w["dn"], want_sel,
                                     weights, cfg)
    np.testing.assert_array_equal(np.sort(sel, -1), np.sort(want_sel, -1))
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)
    local = (np.asarray(want_sel) >= 8) & (np.asarray(want_sel) < 12)
    assert int(counts.sum()) == int(local.sum())


def test_wide_routing_with_every_token_sent_to_one_expert():
    """Top-6 of 16 with a bias that puts expert 9 in every token's
    choice: its group is the whole batch (no capacity, nothing dropped),
    and with all six choices local the sorted buffer is at its worst
    case."""
    from benchmark.reference import nemotron_h as reference

    w = expert_weights(80, 16)
    w["bias"] = np.zeros_like(w["bias"])
    w["bias"][9] = 1.0
    a = rand(88, 3, 30, 32)
    y, sel, counts = routed(a, w, 0)
    assert int(counts[9]) == 90 and (np.asarray(sel) == 9).any(-1).all()
    assert int(counts.sum()) == 90 * 6          # every assignment local
    cfg = dict(REF_CFG, expert_offset=0)
    p = reference_params(w)
    with jax.default_matmul_precision("highest"):
        want_sel, weights, _ = reference.route(p, jnp.asarray(a), cfg)
        want = reference.routed_part(p, jnp.asarray(a) @ w["dn"], want_sel,
                                     weights, cfg)
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-5)


def test_four_shares_and_the_shared_expert_once_make_the_whole_block():
    """The share test: each of the four chips' routed part (4 of 16
    experts) through W_up, plus the shared expert counted once, add up to
    the uncut reference mixer."""
    from benchmark.reference import nemotron_h as reference

    w = expert_weights(90, 16)
    a = rand(98, 2, 12, 32)
    total = np.zeros_like(a)
    for chip in range(4):
        part, _sel, _n = routed(a, w, 4 * chip,
                                slice(4 * chip, 4 * chip + 4))
        total += np.asarray(part) @ w["up"]
    total += np.maximum(a @ w["s1"], 0) ** 2 @ w["s2"]
    with jax.default_matmul_precision("highest"):
        whole, _sel, _r = reference.expert_mixer(
            reference_params(w), jnp.asarray(a),
            dict(REF_CFG, expert_offset=0))
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-5)


def test_swiglu_stays_the_expert_ops_default():
    """An op without the attribute (every afmoe program) computes what
    `activation="swiglu"` does, and an unknown form is refused."""
    w = dict(router_w=rand(100, 32, 16, scale=0.3), bias=rand(101, 16),
             wgu=rand(102, 4, 32, 32, scale=0.2),
             wd=rand(103, 4, 16, 32, scale=0.2))
    x = jnp.asarray(rand(104, 2, 9, 32))
    args = (x, w["router_w"], w["bias"], w["wgu"], w["wd"])
    kw = dict(top_k=4, route_scale=2.448, expert_offset=8)
    default = moe.local_experts_ffn(*args, **kw)
    named = moe.local_experts_ffn(*args, activation="swiglu", **kw)
    for got, want in zip(default, named):
        np.testing.assert_array_equal(got, want)
    with pytest.raises(ValueError):
        moe.local_experts_ffn(*args, activation="gelu", **kw)


# -- the decoder through the generator ---------------------------------------

def tiny_generator(batch=2, context=11, new=8, **kw):
    cfg = NemotronHConfig.tiny(**kw)
    gen = GPTGenerator(NemotronHDecoder(cfg), batch=batch,
                       context_len=context, max_len=context + new)
    gen.init_params(seed=7)
    return gen


def test_block_kinds_state_specs_and_gauges():
    from paddle_tpu import observability as obs

    obs.reset()
    gen = tiny_generator()
    assert gen.cfg.pattern == "MEMEMEMEM*E"
    assert [gen.cfg.pattern.count(k) for k in (MAMBA, EXPERTS, ATTENTION)] \
        == [5, 5, 1]
    specs = {n: (s, d) for n, s, d in gen._state_specs}
    assert specs["nemotron_l0_ssm_state"] == ((2, 2, 16, 64), "float32")
    assert specs["nemotron_l0_conv_tail"] == ((2, 3, CONV), "bfloat16")
    assert specs["nemotron_l9_cache_k"] == (
        kv_cache.cache_shape(2, 19, 2, 16), "bfloat16")
    assert specs["nemotron_moe_counters"][1] == "int32"
    assert sum(n.endswith("_ssm_state") for n in specs) == 5
    assert sum("_cache_" in n for n in specs) == 2
    gen.reset()
    for name, (shape, _d) in specs.items():
        held = gen.scope.find_var(name)
        assert held.shape == shape and not np.asarray(held).any()
    # the same state at another context: only the KV cache grows
    longer = tiny_generator(context=40)
    grown = {n for n, s, _d in longer._state_specs if s != specs[n][0]}
    assert grown == {"nemotron_l9_cache_k", "nemotron_l9_cache_v"}
    gauges = obs.get_gauges()
    table = obs.get_tables()["serving.generate.model"]
    assert table["family"] == "nemotron_h" and table["pattern"] == \
        "MEMEMEMEM*E"
    assert table["state_bytes_per_sequence"] == {
        "ssm": 4 * H * P * N, "conv": 2 * 3 * CONV,
        "full_per_position": 2 * 2 * 2 * 16}
    # of the generator built last (context 40)
    assert gauges["kv_cache.bytes.ssm"] == 5 * 2 * H * P * N * 4
    assert gauges["kv_cache.bytes.conv"] == 5 * 2 * 3 * CONV * 2
    assert gauges["kv_cache.bytes.full"] == 2 * 2 * 2 * 16 * 48 * 2
    with pytest.raises(ValueError):
        NemotronHConfig.tiny(pattern="MEX")


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-4), ("bfloat16", 3e-2)])
def test_prefill_then_cached_decode_match_the_reference(dtype, tol):
    """All eleven block kinds; the batch of 4 is prefilled in blocks of
    2 rows (`prefill_rows` smaller than the batch), a context of 11 is
    not a multiple of the chunk of 8; then 8 cached steps read the conv
    tail, the recurrent state and the KV cache back. Both against the
    reference's full forward pass (the recurrence, no chunks, no cache).
    In float32 the two agree to rounding."""
    from benchmark.builders import nemotron_h as builder

    gen = tiny_generator(batch=4, prefill_rows=2, dtype=dtype)
    prompts = np.random.RandomState(5).randint(0, 256, (4, 11))
    seen = builder.probe_generator(gen, prompts, decode_steps=8)
    report = builder.compare(gen, seen, tol=tol)
    assert report["ok"], report
    assert report["decode_routing"]["mismatches"] == 0
    assert report["decode_routing"]["tokens"] == 5 * 2 * 19
    if dtype == "float32":
        assert report["decode_routing"]["near_ties"] == 0


def test_the_recurrent_state_is_float32_at_the_op():
    """The state array stays float32 under bfloat16 activations, and the
    difference is visible: the reference with its state rounded to
    bfloat16 after every step fails the comparison the program passes
    (in float32 otherwise, so that nothing else rounds)."""
    from benchmark.builders import nemotron_h as builder

    assert str(tiny_generator().scope.find_var(
        "nemotron_l0_ssm_state").dtype) == "float32"
    # projections seeded wide enough that x, B and C are of order 1 at
    # a hidden size of 64, as 0.02 makes them at 4096
    gen = tiny_generator(context=40, dtype="float32",
                         initializer_range=0.125)
    prompts = np.random.RandomState(6).randint(0, 256, (2, 40))
    seen = builder.probe_generator(gen, prompts, decode_steps=8)
    stated = builder.compare(gen, seen, tol=2e-4)
    assert stated["ok"], stated
    rounded = builder.compare(gen, seen, tol=2e-4,
                              state_dtype=jnp.bfloat16)
    assert not rounded["ok"]
    assert rounded["decode_err"] > 10 * stated["decode_err"]
    # and at the op: 40 one-token updates with the state rounded to
    # bfloat16 in between drift from the float32 recurrence
    v = ssm_inputs(110, 2, 40)
    want, _ = recurrence(v)
    lossy, _ = recurrence(v, state_dtype=jnp.bfloat16)
    state = jnp.zeros(kv_cache.ssm_state_shape(2, H, P, N, G), jnp.float32)
    step = jax.jit(lambda xbc, dt, state: ssm.ssm_update(
        xbc, dt, v["a_log"], v["d"], v["dt_bias"], state, **ATTRS))
    got = []
    for t in range(40):
        y, state = step(v["xbc"][:, t:t + 1], v["dt"][:, t:t + 1], state)
        got.append(y)
    got = np.concatenate(got, 1)
    assert np.abs(got - want).max() < 0.05 * np.abs(lossy - want).max()


def test_a_second_batch_starts_from_a_zero_state():
    """`reset()` zeroes the recurrent state and the conv tail with the
    caches: the same prompts generate the same tokens after another
    batch has run."""
    gen = tiny_generator()
    rng = np.random.RandomState(8)
    first, other = rng.randint(0, 256, (2, 2, 11))
    a = gen.generate(first, 8)
    gen.generate(other, 8)
    assert np.asarray(gen.scope.find_var("nemotron_l0_ssm_state")).any()
    np.testing.assert_array_equal(gen.generate(first, 8), a)


def test_counters_and_selected_ids_ride_with_the_steps():
    from paddle_tpu import observability as obs

    obs.reset()
    gen = tiny_generator(prefill_rows=1)
    assert len(gen._prefill_fetch) == len(gen._decode_fetch) == 2
    gen.generate(np.zeros((2, 11), np.int64), 4)
    got = obs.get_counters()
    # 2 prefill dispatches + 3 decode steps, 5 expert blocks each
    assert got["moe.calls"] == 5 * 5 and got["moe.decode_calls"] == 5 * 3
    assert got["moe.assignments_total"] == 5 * 6 * (2 * 11 + 3 * 2)
    spans = [s for s in obs.get_spans()
             if s["name"] == "serving.step_counters"]
    assert len(spans) == 1 and spans[0]["args"]["moe.calls"] == 25


def test_one_generator_class_serves_all_three_decoders():
    from paddle_tpu.models.afmoe import AfmoeConfig, AfmoeDecoder
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.serving.generate import GPTGenerateRunner

    gens = [
        GPTGenerator(GPTConfig.tiny(), batch=2, context_len=8, max_len=12),
        GPTGenerator(AfmoeDecoder(AfmoeConfig.tiny()), batch=2,
                     context_len=8, max_len=12),
        tiny_generator(context=8, new=4),
    ]
    assert len({type(g) for g in gens}) == 1
    for gen in gens:
        gen.init_params(seed=1)
        (tokens,) = GPTGenerateRunner(gen, max_new_tokens=3).run(
            {"context_ids": np.zeros((2, 8), np.int64)})
        assert tokens.shape == (2, 3)


def test_configuration_file_keeps_every_published_width():
    with open(os.path.join(
            ROOT, "benchmark/configs/nemotron3_super_ep4.json")) as f:
        cfg_json = json.load(f)
    from benchmark.builders import nemotron_h as builder

    cfg = builder.model_config(cfg_json)
    assert (cfg.hidden_size, cfg.mamba_num_heads, cfg.mamba_head_dim,
            cfg.ssm_state_size, cfg.n_groups, cfg.conv_kernel,
            cfg.chunk_size) == (4096, 128, 64, 128, 8, 4, 128)
    assert (cfg.d_inner, cfg.conv_dim) == (8192, 10240)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim) == (32, 2, 128)
    assert (cfg.num_experts, cfg.num_local_experts, cfg.top_k,
            cfg.route_scale, cfg.moe_latent_size, cfg.moe_intermediate_size,
            cfg.shared_intermediate_size) == (512, 128, 22, 5.0, 1024, 2688,
                                              5376)
    assert cfg.pattern == "MEMEMEMEM*E" and cfg.vocab_size == 32768
    published = cfg_json["published"]["hybrid_override_pattern"]
    first = cfg_json["deployment"]["blocks_run"][0]
    assert published[first:first + 11] == cfg.pattern
    assert set(cfg_json["reduced"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"}
    tiny = builder.model_config(cfg_json, tiny=True)
    assert tiny.d_inner == 2 * tiny.hidden_size     # expand, as published


def test_the_mamba_parameters_start_where_the_family_puts_them():
    gen = tiny_generator()
    scope = gen.scope
    for i in (0, 2, 4, 6, 8):
        a_log = np.asarray(scope.find_var(f"nemotron_l{i}_a_log"))
        dt = np.log1p(np.exp(np.asarray(
            scope.find_var(f"nemotron_l{i}_dt_bias"))))
        assert a_log.dtype == np.float32
        assert (np.exp(a_log) >= 1).all() and (np.exp(a_log) <= 16).all()
        assert (dt >= 0.99e-3).all() and (dt <= 0.101).all()
        assert (np.asarray(scope.find_var(f"nemotron_l{i}_d")) == 1).all()
    a0 = np.asarray(scope.find_var("nemotron_l0_a_log"))
    a2 = np.asarray(scope.find_var("nemotron_l2_a_log"))
    assert not np.array_equal(a0, a2)
