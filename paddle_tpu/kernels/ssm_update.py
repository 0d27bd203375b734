"""One decode step of a recurrent state, in place: the Mamba-2
state-space recurrence, and with a `beta` operand the gated delta rule,
which CORRECTS the state before it writes it.

    S <- decay * S + B (outer) (dt x);    y = C . S          per head
    with beta:  S <- decay * S;  u = beta * (v - S^T k);
                S <- S + k (outer) u;  o = S^T q     (B, C, dt x = k, q, v)

The state is stored as `ops/kv_cache.py::ssm_state_shape` says:
``[B, H / pack, N, pack * P]`` float32, the state dimension N on the
sublanes and `pack` heads' channels side by side on the lanes (two heads
of 64 at the published sizes: a full 128-lane row). In that layout every
operand of the step is already where the vector unit wants it: `dt x` and
the per-head decay are lane rows ([1, pack * P], broadcast over the
sublanes), B and C are columns ([N, 1], broadcast over the lanes: the
caller hands them over as [B, N, G]), the update is two multiply-adds a
register and `y` is a sum over the sublanes that lands on the lanes,
[1, pack * P], the layout `y` leaves in. No transposition, no matrix unit
(a matrix-vector product per head would load the state as MXU weights:
reckoned at five times the HBM time).

One grid step holds one sequence's whole state (4 MB at 128 heads x 64 x
128) in and out; the state operand is aliased to the state output, so with
the Executor donating the persistable the update touches HBM once in each
direction and no second state exists. The step is bound by that traffic:
benchmark/harness/nemotron_h_cost.py counts it.

The delta rule is the same step with one more reduction over the
sublanes: what the decayed state already holds along k (a lane row, as
`y` is) is subtracted from v before the outer product is added. Same
layout (the key dimension on the sublanes, `ssm_state_shape(B, Hv, dv,
dk, Hk)`), same operands plus `beta` as a lane row, same traffic, so it
is this kernel with a switch and not a sibling; its call is named
`gdn_state_update` so that its events are found apart
(benchmark/harness/qwen3_next_cost.py counts its need). Without `beta`
the body traced is the one it was.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# state in + out, each double-buffered (16 MB at the published sizes),
# and the body's registers spilled
VMEM_LIMIT_BYTES = 48 * 2 ** 20


def _kernel(packs_per_group, corrected, state_ref, xdt_ref, decay_ref,
            bt_ref, ct_ref, *refs):
    beta_ref = refs[0] if corrected else None
    y_ref, out_ref = refs[-2:]
    packs, n, w = state_ref.shape[1:]
    for g in range(packs // packs_per_group):
        b_col = jnp.broadcast_to(bt_ref[0, :, g:g + 1], (n, w))
        c_col = jnp.broadcast_to(ct_ref[0, :, g:g + 1], (n, w))
        for k in range(g * packs_per_group, (g + 1) * packs_per_group):
            s = state_ref[0, k] * decay_ref[0, k:k + 1, :]
            x = xdt_ref[0, k:k + 1, :]
            if corrected:
                x = beta_ref[0, k:k + 1, :] * (
                    x - jnp.sum(s * b_col, axis=0, keepdims=True))
            s = s + b_col * x
            out_ref[0, k] = s
            y_ref[0, k:k + 1, :] = jnp.sum(s * c_col, axis=0, keepdims=True)


def update(state, xdt, decay, bt, ct, beta=None, interpret=False, name=None):
    """state [B, K, N, W] float32 (K packs of heads, W = pack * P lanes);
    xdt, decay [B, K, W] float32 (dt * x, and exp(dt * A) of the lane's
    head); bt, ct [B, N, G] float32, pack k reading group
    k // (K / G); `beta` [B, K, W] float32 turns the step into the delta
    rule (xdt is then v, bt and ct are k and q); `name` the call's. ->
    (y [B, K, W] float32, the new state: the old one's buffer)."""
    b, packs, n, w = state.shape
    groups = bt.shape[2]
    whole = lambda i: (i, 0, 0)                              # noqa: E731
    rows = pl.BlockSpec((1, packs, w), whole)
    cols = pl.BlockSpec((1, n, groups), whole)
    state_spec = pl.BlockSpec((1, packs, n, w), lambda i: (i, 0, 0, 0))
    corrected = beta is not None
    return pl.pallas_call(
        functools.partial(_kernel, packs // groups, corrected),
        name=name or ("gdn_state_update" if corrected else "ssm_state_update"),
        grid=(b,),
        in_specs=[state_spec, rows, rows, cols, cols] + [rows] * corrected,
        out_specs=[rows, state_spec],
        out_shape=[jax.ShapeDtypeStruct((b, packs, w), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={0: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(state, xdt, decay, bt, ct, *([beta] if corrected else []))


# The delta rule's call sits in a jit of its own: a model's layers share
# shapes, so the kernel is traced and lowered once a program, not once a
# layer (`update` is left as the state-space blocks lower it).
@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_update(state, v, decay, kt, qt, beta, interpret=False):
    """`update` as the gated delta rule: state [B, Hv, dk, dv] as stored,
    v, decay, beta [B, Hv, dv], kt, qt [B, dk, Hk]."""
    return update(state, v, decay, kt, qt, beta, interpret=interpret)


def update_reference(state, xdt, decay, bt, ct, beta=None):
    """The same step in `jnp` (the CPU path)."""
    b, packs, n, w = state.shape
    per_group = packs // bt.shape[2]
    b_col = jnp.repeat(bt.transpose(0, 2, 1), per_group, axis=1)  # [B,K,N]
    c_col = jnp.repeat(ct.transpose(0, 2, 1), per_group, axis=1)
    new = state * decay[:, :, None, :]
    if beta is not None:
        xdt = beta * (xdt - jnp.sum(new * b_col[..., None], axis=2))
    new = new + b_col[..., None] * xdt[:, :, None, :]
    return jnp.sum(new * c_col[..., None], axis=2), new


# A constant-decay linear mixer (models/minicpm_sala.py's Lightning
# layers) is the state-space step with dt = 1, one group a head and the
# decay a constant of the head: `update` as it stands, under a call name
# of its own (`lightning_state_update`) so that its events are found
# apart from the two others', in a jit of its own for the reason above.
@functools.partial(jax.jit, static_argnames=("interpret",))
def lightning_update(state, v, decay, kt, qt, interpret=False):
    """`update` as linear attention with a per-head decay: state
    [B, H, dk, dv] as stored, v and decay [B, H, dv], kt and qt
    [B, dk, H]; y = q . S, unscaled."""
    return update(state, v, decay, kt, qt, interpret=interpret,
                  name="lightning_state_update")
