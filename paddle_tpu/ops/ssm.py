"""Ops of the recurrent mixers on the serving path: a Mamba-2
state-space block (models/nemotron_h.py) and a Gated DeltaNet
linear-attention layer (models/qwen3_next.py). The depthwise causal
convolution with its carried tail, each recurrence in its two forms (a
chunked scan for a prefill, a one-token state update for a decode step),
the gated RMSNorm over groups of channels (gate before or after the
norm), and `relu2`. The state-space recurrence first; the gated delta
rule has its own section below.

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
    """x [R, T, C], weight [C, k], bias [C] or None, history
    [R, k - 1, C] (the rows before x's first; zeros at a sequence's
    start) -> (silu of the convolution [R, T, C] in x's dtype, the new
    tail [R, k - 1, C])."""
    k = weight.shape[1]
    t = x.shape[1]
    window = jnp.concatenate([history.astype(x.dtype), x], axis=1)
    acc = 0.0 if bias is None else bias.astype(F32)[None, None, :]
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
    may be a block of the batch's rows starting at `Row`; `Bias` may be
    absent."""
    x, w, tail = (ins[k][0] for k in ("X", "W", "Tail"))
    bias = (ins.get("Bias") or [None])[0]
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
# the gated delta rule (linear attention)
# ---------------------------------------------------------------------------
#
#   S'_t = alpha_t S_{t-1};  u_t = beta_t (v_t - S'_t^T k_t)
#   S_t = S'_t + k_t (outer) u_t;  o_t = S_t^T q_t          S [dk, dv] a head
#
# The state is CORRECTED before it is written: what it already holds
# along k_t is read out and subtracted from the value. Hk key heads serve
# Hv value heads (value head h reads key head h // (Hv / Hk)). The state
# is stored as a Mamba-2 state is, `ssm_state_shape(B, Hv, dv, dk, Hk)`:
# the key dimension on the sublanes, the value dimension on the lanes.

def _split_qkv(qkv, key_heads, value_heads, key_dim, value_dim):
    """[R, T, 2 Hk dk + Hv dv] -> q, k [R, T, Hk, dk], v [R, T, Hv, dv]."""
    r, t, _ = qkv.shape
    d = key_heads * key_dim
    return (qkv[..., :d].reshape(r, t, key_heads, key_dim),
            qkv[..., d:2 * d].reshape(r, t, key_heads, key_dim),
            qkv[..., 2 * d:].reshape(r, t, value_heads, value_dim))


def delta_gates(b_raw, a_raw, a_log, dt_bias):
    """(g = -exp(A_log) softplus(a + dt_bias), the log of the decay
    alpha; beta = sigmoid(b)), float32."""
    g = -jnp.exp(a_log.astype(F32)) * jax.nn.softplus(
        a_raw.astype(F32) + dt_bias.astype(F32))
    return g, jax.nn.sigmoid(b_raw.astype(F32))


def delta_rule_inputs(qkv, b_raw, a_raw, a_log, dt_bias, *, key_heads,
                      value_heads, key_dim, value_dim):
    """What the recurrence reads, all float32: q L2-normalised a head and
    scaled by dk^-1/2, k L2-normalised, v, and `delta_gates`' g and
    beta."""
    q, k, v = (x.astype(F32) for x in _split_qkv(
        qkv, key_heads, value_heads, key_dim, value_dim))

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    return (unit(q) * key_dim ** -0.5, unit(k), v,
            *delta_gates(b_raw, a_raw, a_log, dt_bias))


def unit_lower_inverse(strict):
    """(I + L)^-1 for `strict` = L [..., C, C], strictly lower triangular,
    C a power of two: the inverses of the diagonal blocks, doubled in
    size log2(C) times ([[A, 0], [B, D]]^-1 = [[A^-1, 0], [-D^-1 B A^-1,
    D^-1]]; a block of one row is 1). Block forward substitution, so as
    stable as a row-by-row solve, in log2(C) batched steps. `inv` holds
    the diagonal blocks' inverses as ONE [C, C] matrix (zero off the
    blocks), so a step is `inv - inv B inv` with B the blocks of L that
    join the pairs: two whole-matrix products, float32
    (`Precision.HIGHEST`), whatever the block's size (blocks of one, two
    or four rows taken apart would each fill a tile of their own)."""
    c = strict.shape[-1]
    at = jnp.arange(c)
    inv = jnp.broadcast_to(jnp.eye(c, dtype=F32), strict.shape)
    b = 1
    while b < c:
        row, col = at[:, None] // b, at[None, :] // b
        joins = (row // 2 == col // 2) & (row % 2 == 1) & (col % 2 == 0)
        below = jnp.where(joins, strict, 0.0)
        inv = inv - jnp.matmul(
            jnp.matmul(inv, below, precision=jax.lax.Precision.HIGHEST),
            inv, precision=jax.lax.Precision.HIGHEST)
        b *= 2
    return inv


def gated_delta_chunked(q, k, v, g, beta, chunk, state=None, lo=F32):
    """The gated delta rule over a whole sequence, a chunk at a time. q,
    k [R, L, Hk, dk], v [R, L, Hv, dv], g, beta [R, L, Hv], all float32
    as `delta_rule_inputs` gives them; `state` [R, Hv, dk, dv] float32
    enters (zeros by default); L need not be a multiple of `chunk` (a
    power of two) -> (o [R, L, Hv, dv] float32, the state after row
    L - 1).

    Inside a chunk, with G the running sum of g and Gamma_ij =
    exp(G_i - G_j) for i >= j (from the differences: nothing overflows):
        L = strictly_lower((beta K) K^T * Gamma);  T = (I + L)^-1
        W = T (beta K exp(G));  U = T (beta V);  V' = U - W S
        O = (Q exp(G)) S + lower(Q K^T * Gamma) V'
        S <- exp(G_last) S + (K exp(G_last - G))^T V'
    T, W, U and the two score matrices do not read the state and are
    formed for all chunks at once; the chunks are then walked in order
    (`lax.scan`) with S carried in float32. A product of two activations
    takes them in `lo` (the activations' dtype) and accumulates in
    float32; the solve and every product that reads T or the state are
    float32 (`Precision.HIGHEST`). Every array is laid [chunks, R, key
    heads, (value heads a key head,) chunk, lanes] from the start, so
    that each product finds its operands as it wants them and the scan
    its chunks in front: q, k, v, g and beta are transposed once on the
    way in and o once on the way out."""
    r, length, hk, dk = q.shape
    hv, dv = v.shape[2:]
    e = hv // hk
    pad = -length % chunk
    if pad:
        # a padded row has beta = 0 and g = 0: it changes nothing
        q, k, v, g, beta = (
            jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    nc = (length + pad) // chunk
    exact = jax.lax.Precision.HIGHEST

    def chunks(x, *heads):      # heads: the head axes after L
        """[R, L, heads.., (d)] -> [nc, R, heads.., chunk, (d)]."""
        x = x.reshape((r, nc, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 2, 2 + len(heads)), 1, 0)

    # the op's parts by name in the compiled step's `op_name`, beneath
    # the op's own scope: layout, scores, solve, apply, scan
    with jax.named_scope("delta_layout"):
        qc, kc = chunks(q, hk), chunks(k, hk)           # [nc,R,Hk,C,dk]
        vc = chunks(v.reshape(r, -1, hk, e, dv), hk, e)     # [nc,R,Hk,e,C,dv]
        gc, bc = (chunks(x.reshape(r, -1, hk, e), hk, e) for x in (g, beta))
    with jax.named_scope("delta_scores"):
        run = jnp.cumsum(gc, axis=-1)                   # [nc,R,Hk,e,C]
        rows = jnp.arange(chunk)
        seg = run[..., :, None] - run[..., None, :]     # [.., i, j]
        gamma = jnp.exp(jnp.where(rows[:, None] >= rows[None, :], seg,
                                  -jnp.inf))
        k_lo = kc.astype(lo)
        kk = einsum_f32("nrhid,nrhjd->nrhij", k_lo, k_lo)   # [nc,R,Hk,C,C]
        qk = einsum_f32("nrhid,nrhjd->nrhij", qc.astype(lo), k_lo)
        scores = (qk[:, :, :, None] * gamma).astype(lo)     # lower, i = j too
    with jax.named_scope("delta_solve"):
        strict = jnp.where(rows[:, None] > rows[None, :],
                           kk[:, :, :, None] * gamma * bc[..., :, None], 0.0)
        solve = unit_lower_inverse(strict)              # [nc,R,Hk,e,C,C]
    with jax.named_scope("delta_apply"):
        k_e, q_e = kc[:, :, :, None], qc[:, :, :, None]     # [nc,R,Hk,1,C,dk]
        w = jnp.matmul(solve, k_e * (bc * jnp.exp(run))[..., None],
                       precision=exact)                 # [nc,R,Hk,e,C,dk]
        u = jnp.matmul(solve, vc * bc[..., None], precision=exact)
        q_in = q_e * jnp.exp(run)[..., None]            # [nc,R,Hk,e,C,dk]
        k_out = (k_e * jnp.exp(run[..., -1:] - run)[..., None]).astype(lo)
        keep = jnp.exp(run[..., -1])                    # [nc,R,Hk,e]

    def step(s, inp):
        w_c, u_c, scores_c, q_c, k_c, keep_c = inp
        # the carried state is read as the float32 it is
        fresh = u_c - jnp.matmul(w_c, s, precision=exact)   # [R,Hk,e,C,dv]
        fresh_lo = fresh.astype(lo)
        o = jnp.matmul(q_c, s, precision=exact) \
            + einsum_f32("rheij,rhejv->rheiv", scores_c, fresh_lo)
        s = keep_c[..., None, None] * s \
            + einsum_f32("rhejd,rhejv->rhedv", k_c, fresh_lo)
        return s, o

    first = jnp.zeros((r, hk, e, dk, dv), F32) if state is None \
        else state.astype(F32).reshape(r, hk, e, dk, dv)
    with jax.named_scope("delta_scan"):
        final, outs = jax.lax.scan(step, first,
                                   (w, u, scores, q_in, k_out, keep))
    with jax.named_scope("delta_layout"):
        # [nc, R, Hk, e, C, dv] -> [R, nc * C, Hv, dv]
        o = jnp.moveaxis(jnp.moveaxis(outs, 0, 1), 4, 2).reshape(
            r, nc * chunk, hv, dv)[:, :length]
    return o, final.reshape(r, hv, dk, dv)


def _delta_attrs(op):
    return dict(key_heads=int(op.attr("key_heads")),
                value_heads=int(op.attr("value_heads")),
                key_dim=int(op.attr("key_dim")),
                value_dim=int(op.attr("value_dim")))


def gated_delta_scan(qkv, b_raw, a_raw, a_log, dt_bias, *, chunk,
                     interpret=False, **sizes):
    """A dispatch's rows from a zero state: qkv [R, L, 2 Hk dk + Hv dv],
    b_raw, a_raw [R, L, Hv], `sizes` as `delta_rule_inputs` takes them ->
    (o [R, L, Hv * dv] in qkv's dtype, the state after row L - 1
    [R, Hv, dk, dv] float32, whether the kernel ran). On the TPU (and
    with `interpret`) the Pallas kernel `gdn_chunk_scan`
    (kernels/gdn_chunk_scan.py) for the calls it takes (`supports`: heads
    of whole 128-lane tiles, a chunk of at least 16 rows), elsewhere
    `gated_delta_chunked`."""
    from ..kernels import gdn_chunk_scan as kernel

    if (interpret or jax.default_backend() == "tpu") and kernel.supports(
            chunk=chunk, dtype=qkv.dtype, **sizes):
        # what XLA still does of the op is named beneath its scope, as
        # the `jnp` form's parts are (`delta_layout` ... `delta_scan`)
        with jax.named_scope("delta_gates"):
            gates = delta_gates(b_raw, a_raw, a_log, dt_bias)
        o, state = kernel.scan(qkv, *gates, chunk=chunk,
                               interpret=interpret, **sizes)
        return o, state, True
    o, state = gated_delta_chunked(
        *delta_rule_inputs(qkv, b_raw, a_raw, a_log, dt_bias, **sizes),
        chunk, lo=qkv.dtype)
    out = o.astype(qkv.dtype).reshape(o.shape[0], o.shape[1], -1)
    return out, state, False


@register_op(
    "gated_delta_chunk_scan",
    inputs=["QKV", "B", "A", "ALog", "DtBias", "State", "Row"],
    outputs=["Out", "StateOut"],
    differentiable=False,
    mutates=(("StateOut", "State"),),
)
def _gated_delta_chunk_scan(ctx, op, ins):
    """A prefill's delta rule: `QKV` [R, L, 2 Hk dk + Hv dv] (after the
    convolution), `B` and `A` [R, L, Hv] (beta and the decay before
    their sigmoid / softplus). Yields o [R, L, Hv * dv] and writes the
    rows' final state into `State` (the batch's,
    `kv_cache.ssm_state_shape(B, Hv, dv, dk, Hk)`) at `Row`. The gauge
    `kernels.gdn_chunk_scan.calls` is the count of kernel calls in the
    prefill lowered last (0: the `jnp` form ran)."""
    from .. import observability as _obs

    qkv, b_raw, a_raw, a_log, dt_bias, stored = (
        ins[k][0] for k in ("QKV", "B", "A", "ALog", "DtBias", "State"))
    out, state, kernel = gated_delta_scan(
        qkv, b_raw, a_raw, a_log, dt_bias, chunk=int(op.attr("chunk")),
        **_delta_attrs(op))
    if ctx is not None and not ctx.abstract:
        # one EmitContext a lowered prefill: the last call leaves the count
        ctx.gdn_chunk_scan_calls = kernel + getattr(
            ctx, "gdn_chunk_scan_calls", 0)
        _obs.set_gauge("kernels.gdn_chunk_scan.calls",
                       ctx.gdn_chunk_scan_calls)
    # [R, Hv, dk, dv] is the stored layout where a value head fills a
    # lane row (pack 1); else it is [B, H, N, P] and `pack_state` takes
    # [B, H, P, N]
    if stored.shape[3] != state.shape[3]:
        state = pack_state(jnp.swapaxes(state, 2, 3), stored.shape[3])
    new = _row_block(stored, state, ins.get("Row") or None)
    return {"Out": [out], "StateOut": [new]}


def gated_delta_update(qkv, b_raw, a_raw, a_log, dt_bias, stored, *,
                       interpret=False, **sizes):
    """One token a row against the stored state: qkv [B, 1, 2 Hk dk +
    Hv dv], b_raw, a_raw [B, 1, Hv], `sizes` as `delta_rule_inputs`
    takes them -> (o [B, 1, Hv * dv] in qkv's dtype, the new stored
    state, whether the kernel ran). On the TPU the Pallas kernel
    `gdn_state_update` (kernels/ssm_update.py: the state-space update
    with the correction), elsewhere the same in `jnp`."""
    from ..kernels import ssm_update as kernel

    bsz, packs, _n, lanes = stored.shape
    q, k, v, g, beta = (x[:, 0] for x in delta_rule_inputs(
        qkv, b_raw, a_raw, a_log, dt_bias, **sizes))

    def lane_rows(x):       # [B, Hv] -> [B, packs, lanes], a head's lanes
        return jnp.broadcast_to(x[..., None], v.shape).reshape(
            bsz, packs, lanes)

    use_kernel = interpret or jax.default_backend() == "tpu"
    step = functools.partial(kernel.delta_update, interpret=interpret) \
        if use_kernel else kernel.update_reference
    o, new = step(stored, v.reshape(bsz, packs, lanes),
                  lane_rows(jnp.exp(g)), k.transpose(0, 2, 1),
                  q.transpose(0, 2, 1), lane_rows(beta))
    return o.astype(qkv.dtype).reshape(bsz, 1, -1), new, use_kernel


@register_op(
    "gated_delta_state_update",
    inputs=["QKV", "B", "A", "ALog", "DtBias", "State"],
    outputs=["Out", "StateOut"],
    differentiable=False,
    mutates=(("StateOut", "State"),),
)
def _gated_delta_state_update(ctx, op, ins):
    """A decode step's delta rule, the whole batch, in place. The gauge
    `kernels.gdn_update.calls` is the count of kernel calls in the
    decode step lowered last (0: the `jnp` path ran)."""
    from .. import observability as _obs

    out, new, kernel = gated_delta_update(
        *(ins[k][0] for k in ("QKV", "B", "A", "ALog", "DtBias", "State")),
        **_delta_attrs(op))
    if ctx is not None and not ctx.abstract:
        # one EmitContext a lowered step: the last call leaves the count
        ctx.gdn_update_calls = kernel + getattr(ctx, "gdn_update_calls", 0)
        _obs.set_gauge("kernels.gdn_update.calls", ctx.gdn_update_calls)
    return {"Out": [out], "StateOut": [new]}


# ---------------------------------------------------------------------------
# gate, norm, activation
# ---------------------------------------------------------------------------

@register_op("gated_rms_norm", inputs=["X", "Gate", "Scale"],
             outputs=["Out"], differentiable=False)
def _gated_rms_norm(ctx, op, ins):
    """(x * act(gate)), RMS-normalised inside each of `num_groups`
    groups of channels (the norm's width is the width over `num_groups`:
    1, the whole width), times a gain of the whole width: the gate is
    applied BEFORE the norm. With `gate_after` x alone is normalised and
    the gate multiplies the normed, gained result (the linear mixers'
    form). `activation` is the gate's: silu (by default) or sigmoid. A
    gain as wide as one group is shared by the groups."""
    x, gate, gain = ins["X"][0], ins["Gate"][0], ins["Scale"][0]
    groups = int(op.attr("num_groups", 1))
    after = bool(op.attr("gate_after", False))
    act = {"silu": jax.nn.silu,
           "sigmoid": jax.nn.sigmoid}[op.attr("activation", "silu")]
    g = x.astype(F32)
    if not after:
        g = g * act(gate.astype(F32))
    gg = g.reshape(g.shape[:-1] + (groups, g.shape[-1] // groups))
    var = jnp.mean(gg * gg, axis=-1, keepdims=True)
    out = (gg * jax.lax.rsqrt(var + float(op.attr("epsilon", 1e-5))))
    shared = gain.shape[0] != g.shape[-1]
    if shared:
        out = out * gain.astype(F32)
    out = out.reshape(g.shape)
    if not shared:
        out = out * gain.astype(F32)
    if after:
        out = out * act(gate.astype(F32))
    return {"Out": [out.astype(x.dtype)]}


def relu2(x):
    """relu(x)^2, squared in float32."""
    r = jnp.maximum(x.astype(F32), 0.0)
    return (r * r).astype(x.dtype)


@register_op("relu2", inputs=["X"], outputs=["Out"], differentiable=False)
def _relu2(ctx, op, ins):
    return {"Out": [relu2(ins["X"][0])]}


# ---------------------------------------------------------------------------
# linear attention with a constant decay a head (Lightning Attention)
# ---------------------------------------------------------------------------
#
#   S_t = lambda_h S_{t-1} + k_t (outer) v_t;   o_t = S_t^T q_t / sqrt(d)
#
# The state-space recurrence above with dt = 1, x = v, B = k, C = q, one
# group a head and A = -s_h a constant of the head: `ssd_chunked` for a
# prefill, `kernels/ssm_update.py` for a decode step (its own call name,
# `lightning_state_update`). Stored as `ssm_state_shape(B, H, d, d, H)`:
# [B, H, dk, dv] float32, the key dimension on the sublanes.

def lightning_slopes(num_heads):
    """s_h = 2^(-8 (h + 1) / H), h = 0 .. H - 1 (Lightning Attention's
    ALiBi-style slopes; MiniMax-01's `_build_slope_tensor` for a power
    of two): the decay a position is lambda_h = exp(-s_h)."""
    import numpy as np

    return np.exp2(-8.0 * np.arange(1, num_heads + 1) / num_heads).astype(
        np.float32)


def lightning_scan(q, k, v, *, num_heads, head_dim, chunk):
    """A dispatch's rows from a zero state: q, k, v [R, L, H * d] (after
    their norms and positions) -> (o [R, L, H * d] float32, the state
    after row L - 1 [R, H, dv, dk] float32). 1 / sqrt(d) is applied to
    the output (the recurrence is linear in q)."""
    r, length, _ = q.shape
    shape = (r, length, num_heads, head_dim)
    y, state = ssd_chunked(
        v.reshape(shape), jnp.ones(shape[:3], F32),
        -jnp.asarray(lightning_slopes(num_heads)), k.reshape(shape),
        q.reshape(shape), chunk)
    return (y * head_dim ** -0.5).reshape(r, length, -1), state


@register_op(
    "lightning_chunk_scan",
    inputs=["Q", "K", "V", "State", "Row"],
    outputs=["Out", "StateOut"],
    differentiable=False,
    mutates=(("StateOut", "State"),),
)
def _lightning_chunk_scan(ctx, op, ins):
    """A prefill's recurrence: Q, K, V [R, L, H * d]. Yields o in V's
    dtype and writes the rows' final state into `State` (the batch's,
    `kv_cache.ssm_state_shape(B, H, d, d, H)`) at `Row`."""
    q, k, v, stored = (ins[n][0] for n in ("Q", "K", "V", "State"))
    o, state = lightning_scan(q, k, v, num_heads=int(op.attr("num_heads")),
                              head_dim=int(op.attr("head_dim")),
                              chunk=int(op.attr("chunk")))
    new = _row_block(stored, pack_state(state, stored.shape[3]),
                     ins.get("Row") or None)
    return {"Out": [o.astype(v.dtype)], "StateOut": [new]}


def lightning_update(q, k, v, stored, *, num_heads, interpret=False):
    """One token a row against the stored state: q, k, v [B, 1, H * d]
    -> (o [B, 1, H * d] in v's dtype, the new stored state, whether the
    kernel ran). On the TPU the Pallas kernel `lightning_state_update`
    (kernels/ssm_update.py), elsewhere the same in `jnp`."""
    from ..kernels import ssm_update as kernel

    bsz, heads, d, _lanes = stored.shape
    qh, kh, vh = (x[:, 0].astype(F32).reshape(bsz, num_heads, d)
                  for x in (q, k, v))
    decay = jnp.broadcast_to(
        jnp.exp(-jnp.asarray(lightning_slopes(num_heads)))[None, :, None],
        vh.shape)
    use_kernel = interpret or jax.default_backend() == "tpu"
    step = functools.partial(kernel.lightning_update, interpret=interpret) \
        if use_kernel else kernel.update_reference
    o, new = step(stored, vh, decay, kh.transpose(0, 2, 1),
                  qh.transpose(0, 2, 1))
    o = o * d ** -0.5
    return o.astype(v.dtype).reshape(bsz, 1, -1), new, use_kernel


@register_op(
    "lightning_state_update",
    inputs=["Q", "K", "V", "State"],
    outputs=["Out", "StateOut"],
    differentiable=False,
    mutates=(("StateOut", "State"),),
)
def _lightning_state_update(ctx, op, ins):
    """A decode step's recurrence, the whole batch, in place. The gauge
    `kernels.lightning_update.calls` is the count of kernel calls in the
    decode step lowered last (0: the `jnp` path ran)."""
    from .. import observability as _obs

    out, new, kernel = lightning_update(
        *(ins[n][0] for n in ("Q", "K", "V", "State")),
        num_heads=int(op.attr("num_heads")))
    if ctx is not None and not ctx.abstract:
        # one EmitContext a lowered step: the last call leaves the count
        ctx.lightning_update_calls = kernel + getattr(
            ctx, "lightning_update_calls", 0)
        _obs.set_gauge("kernels.lightning_update.calls",
                       ctx.lightning_update_calls)
    return {"Out": [out], "StateOut": [new]}
