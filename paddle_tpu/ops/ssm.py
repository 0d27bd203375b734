"""Ops of a Mamba-2 state-space block on the serving path (models/
nemotron_h.py): the depthwise causal convolution with its carried tail,
the selective state-space recurrence in its two forms (a chunked scan
for a prefill, a one-token state update for a decode step), the gated
RMSNorm over groups of channels, and `relu2`.

    xBC_t <- silu(b_c + sum_j w_c[:, j] * xBC_{t-k+1+j})         (conv)
    dt_t = softplus(dt_t + dt_bias);  A = -exp(A_log)             per head
    S_t = exp(dt_t A) S_{t-1} + dt_t * x_t (outer) B_t            [H, P, N]
    y_t = S_t C_t + D * x_t

Each op is one emitter under its `jax.named_scope`. What a sequence
carries from one call to the next lives in two persistables per block
whose shapes `ops/kv_cache.py` owns (`ssm_state_shape`,
`conv_tail_shape`) and whose size does not depend on `max_len`: the
recurrent state S in float32 and the last `k - 1` un-convolved rows of
xBC. Both ops write them in place (the Executor donates mutated
persistables); a prefill that takes a block of the batch's rows (`Row`)
writes those rows' FINAL state into the batch's arrays.

Precision: S, dt, exp(dt A), the cumulative sums of dt A inside a chunk
and every statistic are float32; products whose operands are activations
take them in the activations' dtype and accumulate in float32; a product
that READS the float32 state reads it unrounded. None is differentiable:
they exist in inference graphs only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..framework.registry import register_op
from ._helpers import einsum_f32
from .kv_cache import _pos_scalar

F32 = jnp.float32


# ---------------------------------------------------------------------------
# state layout
# ---------------------------------------------------------------------------

def pack_state(state, lanes):
    """[B, H, P, N] -> the stored [B, H / pack, N, pack * P] (`lanes` =
    pack * P, from `kv_cache.ssm_state_shape`)."""
    b, h, p, n = state.shape
    pack = lanes // p
    return state.reshape(b, h // pack, pack, p, n).transpose(
        0, 1, 4, 2, 3).reshape(b, h // pack, n, lanes)


def unpack_state(stored, head_dim):
    """The stored layout back to [B, H, P, N]."""
    b, packs, n, lanes = stored.shape
    pack = lanes // head_dim
    return stored.reshape(b, packs, n, pack, head_dim).transpose(
        0, 1, 3, 4, 2).reshape(b, packs * pack, head_dim, n)


def _row_block(array, rows, row):
    """`rows` written over batch rows `row` .. of `array` (all of it
    without a `row`)."""
    if row is None:
        return rows.astype(array.dtype)
    start = (_pos_scalar(row[0]),) + (jnp.int32(0),) * (array.ndim - 1)
    return jax.lax.dynamic_update_slice(array, rows.astype(array.dtype),
                                        start)


# ---------------------------------------------------------------------------
# depthwise causal convolution
# ---------------------------------------------------------------------------

def causal_conv(x, weight, bias, history):
    """x [R, T, C], weight [C, k], bias [C], history [R, k - 1, C] (the
    rows before x's first; zeros at a sequence's start) -> (silu of the
    convolution [R, T, C] in x's dtype, the new tail [R, k - 1, C])."""
    k = weight.shape[1]
    t = x.shape[1]
    window = jnp.concatenate([history.astype(x.dtype), x], axis=1)
    acc = bias.astype(F32)[None, None, :]
    for j in range(k):
        acc = acc + window[:, j:j + t, :].astype(F32) \
            * weight[:, j].astype(F32)[None, None, :]
    return jax.nn.silu(acc).astype(x.dtype), window[:, t:, :]


@register_op(
    "causal_conv1d",
    inputs=["X", "W", "Bias", "Tail", "Row"],
    outputs=["Out", "TailOut"],
    differentiable=False,
    mutates=(("TailOut", "Tail"),),
)
def _causal_conv1d(ctx, op, ins):
    """`Tail` [B, k - 1, C] is the batch's carried tail. With `carry` the
    call continues its rows' sequences (a decode step: X is [B, 1, C]);
    without, X starts them (a prefill: zeros before the first token). X
    may be a block of the batch's rows starting at `Row`."""
    x, w, bias, tail = (ins[k][0] for k in ("X", "W", "Bias", "Tail"))
    row = ins.get("Row") or None
    rows, k1 = x.shape[0], tail.shape[1]
    if op.attr("carry", False):
        r0 = jnp.int32(0) if row is None else _pos_scalar(row[0])
        history = jax.lax.dynamic_slice_in_dim(tail, r0, rows, axis=0)
    else:
        history = jnp.zeros((rows, k1, x.shape[2]), x.dtype)
    out, new_tail = causal_conv(x, w, bias, history)
    return {"Out": [out], "TailOut": [_row_block(tail, new_tail, row)]}


# ---------------------------------------------------------------------------
# the recurrence
# ---------------------------------------------------------------------------

def _split_xbc(xbc, heads, head_dim, groups, state_size):
    """[R, T, H * P + 2 * G * N] -> x [R, T, H, P], B, C [R, T, G, N]."""
    r, t, _ = xbc.shape
    d, gn = heads * head_dim, groups * state_size
    return (xbc[..., :d].reshape(r, t, heads, head_dim),
            xbc[..., d:d + gn].reshape(r, t, groups, state_size),
            xbc[..., d + gn:].reshape(r, t, groups, state_size))


def _step_sizes(dt_raw, a_log, dt_bias):
    """(dt = softplus(dt + dt_bias), A = -exp(A_log)), float32."""
    dt = jax.nn.softplus(dt_raw.astype(F32) + dt_bias.astype(F32))
    return dt, -jnp.exp(a_log.astype(F32))


def ssd_chunked(x, dt, a, b, c, chunk):
    """The recurrence over a whole sequence from a zero state, a chunk
    at a time. x [R, L, H, P], dt [R, L, H] float32, a [H] float32,
    b, c [R, L, G, N] (head h reads group h // (H / G)); L need not be
    a multiple of `chunk` -> (y [R, L, H, P] float32 without the D term,
    the state after row L - 1 [R, H, P, N] float32).

    Inside a chunk, with cs the running sum of dt * A:
        y_l  = sum_{s <= l} (C_l . B_s) exp(cs_l - cs_s) dt_s x_s
               + exp(cs_l) C_l . S_in
        S_out = exp(cs_last) S_in + sum_s exp(cs_last - cs_s) dt_s x_s (x) B_s
    the chunks walked in order (`lax.scan`) with S carried in float32."""
    r, length, h, p = x.shape
    g, n = b.shape[2:]
    e = h // g
    lo = x.dtype
    pad = -length % chunk
    if pad:
        # a padded row has dt = 0: it decays nothing and adds nothing
        x, dt, b, c = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                       for v in (x, dt, b, c))
    nc = (length + pad) // chunk

    def chunks(v):      # [R, L, ...] -> [nc, R, chunk, ...]
        return jnp.moveaxis(v.reshape((r, nc, chunk) + v.shape[2:]), 1, 0)

    xs = chunks(x.reshape(r, -1, g, e, p))
    dts = chunks(dt.reshape(r, -1, g, e))
    a = a.reshape(g, e)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))

    def step(state, inp):
        xq, dtq, bq, cq = inp   # [R,Q,G,E,P] [R,Q,G,E] [R,Q,G,N] [R,Q,G,N]
        cs = jnp.cumsum(dtq * a, axis=1).transpose(0, 2, 3, 1)  # [R,G,E,Q]
        seg = cs[..., :, None] - cs[..., None, :]               # [R,G,E,l,s]
        decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
        cb = einsum_f32("rlgn,rsgn->rgls", cq, bq)
        xdt = xq.astype(F32) * dtq[..., None]                   # [R,Q,G,E,P]
        y = einsum_f32("rgels,rsgep->rlgep",
                       (cb[:, :, None] * decay).astype(lo), xdt.astype(lo))
        # the carried state is read as the float32 it is
        y = y + jnp.einsum(
            "rlgn,rgepn->rlgep", cq.astype(F32), state,
            precision=jax.lax.Precision.HIGHEST,
        ) * jnp.exp(cs).transpose(0, 3, 1, 2)[..., None]
        to_end = jnp.exp(cs[..., -1:] - cs).transpose(0, 3, 1, 2)  # [R,Q,G,E]
        new = einsum_f32("rsgn,rsgep->rgepn", bq,
                         (xdt * to_end[..., None]).astype(lo))
        state = jnp.exp(cs[..., -1])[..., None, None] * state + new
        return state, y

    state, ys = jax.lax.scan(
        step, jnp.zeros((r, g, e, p, n), F32),
        (xs, dts, chunks(b), chunks(c)),
    )
    y = jnp.moveaxis(ys, 0, 1).reshape(r, nc * chunk, h, p)[:, :length]
    return y, state.reshape(r, h, p, n)


@register_op(
    "ssd_chunk_scan",
    inputs=["XBC", "Dt", "ALog", "D", "DtBias", "State", "Row"],
    outputs=["Out", "StateOut"],
    differentiable=False,
    mutates=(("StateOut", "State"),),
)
def _ssd_chunk_scan(ctx, op, ins):
    """A prefill's recurrence: `XBC` [R, L, H * P + 2 * G * N] (after the
    convolution), `Dt` [R, L, H] before its bias and softplus. Yields y
    [R, L, H * P] (D * x added) and writes the rows' final state into
    `State` (the batch's, `kv_cache.ssm_state_shape`) at `Row`."""
    xbc, dt_raw, a_log, d, dt_bias, stored = (
        ins[k][0] for k in ("XBC", "Dt", "ALog", "D", "DtBias", "State"))
    heads, p = int(op.attr("num_heads")), int(op.attr("head_dim"))
    x, b, c = _split_xbc(xbc, heads, p, int(op.attr("num_groups")),
                         int(op.attr("state_size")))
    dt, a = _step_sizes(dt_raw, a_log, dt_bias)
    y, state = ssd_chunked(x, dt, a, b, c, int(op.attr("chunk")))
    y = y + d.astype(F32)[None, None, :, None] * x.astype(F32)
    out = y.astype(xbc.dtype).reshape(x.shape[0], x.shape[1], heads * p)
    new = _row_block(stored, pack_state(state, stored.shape[3]),
                     ins.get("Row") or None)
    return {"Out": [out], "StateOut": [new]}


def ssm_update(xbc, dt_raw, a_log, d, dt_bias, stored, *, num_heads,
               head_dim, num_groups, state_size, interpret=False):
    """One token a row against the stored state: xbc [B, 1, H * P +
    2 * G * N], dt_raw [B, 1, H] -> (y [B, 1, H * P] in xbc's dtype, the
    new stored state). On the TPU the Pallas kernel `ssm_state_update`
    (kernels/ssm_update.py), elsewhere the same in `jnp`."""
    from ..kernels import ssm_update as kernel

    bsz, packs, _n, lanes = stored.shape
    x, b, c = _split_xbc(xbc, num_heads, head_dim, num_groups, state_size)
    dt, a = _step_sizes(dt_raw[:, 0], a_log, dt_bias)           # [B, H]
    xf = x[:, 0].astype(F32)                                    # [B, H, P]
    xdt = (xf * dt[..., None]).reshape(bsz, packs, lanes)
    decay = jnp.broadcast_to(jnp.exp(dt * a)[..., None], xf.shape)
    step = kernel.update_reference
    if interpret or jax.default_backend() == "tpu":
        step = functools.partial(kernel.update, interpret=interpret)
    y, new = step(stored, xdt, decay.reshape(bsz, packs, lanes),
                  b[:, 0].astype(F32).transpose(0, 2, 1),
                  c[:, 0].astype(F32).transpose(0, 2, 1))
    y = y.reshape(xf.shape) + d.astype(F32)[None, :, None] * xf
    return y.astype(xbc.dtype).reshape(bsz, 1, num_heads * head_dim), new


@register_op(
    "ssm_state_update",
    inputs=["XBC", "Dt", "ALog", "D", "DtBias", "State"],
    outputs=["Out", "StateOut"],
    differentiable=False,
    mutates=(("StateOut", "State"),),
)
def _ssm_state_update(ctx, op, ins):
    """A decode step's recurrence, the whole batch, in place."""
    y, new = ssm_update(
        *(ins[k][0] for k in ("XBC", "Dt", "ALog", "D", "DtBias", "State")),
        num_heads=int(op.attr("num_heads")), head_dim=int(op.attr("head_dim")),
        num_groups=int(op.attr("num_groups")),
        state_size=int(op.attr("state_size")),
    )
    return {"Out": [y], "StateOut": [new]}


# ---------------------------------------------------------------------------
# gate, norm, activation
# ---------------------------------------------------------------------------

@register_op("gated_rms_norm", inputs=["X", "Gate", "Scale"],
             outputs=["Out"], differentiable=False)
def _gated_rms_norm(ctx, op, ins):
    """(x * silu(gate)), RMS-normalised inside each of `num_groups`
    groups of channels, times a gain of the whole width: the gate is
    applied BEFORE the norm."""
    x, gate, gain = ins["X"][0], ins["Gate"][0], ins["Scale"][0]
    groups = int(op.attr("num_groups", 1))
    g = x.astype(F32) * jax.nn.silu(gate.astype(F32))
    gg = g.reshape(g.shape[:-1] + (groups, g.shape[-1] // groups))
    var = jnp.mean(gg * gg, axis=-1, keepdims=True)
    out = (gg * jax.lax.rsqrt(var + float(op.attr("epsilon", 1e-5))))
    out = out.reshape(g.shape) * gain.astype(F32)
    return {"Out": [out.astype(x.dtype)]}


def relu2(x):
    """relu(x)^2, squared in float32."""
    r = jnp.maximum(x.astype(F32), 0.0)
    return (r * r).astype(x.dtype)


@register_op("relu2", inputs=["X"], outputs=["Out"], differentiable=False)
def _relu2(ctx, op, ins):
    return {"Out": [relu2(ins["X"][0])]}
