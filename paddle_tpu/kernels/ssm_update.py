"""One decode step of the Mamba-2 state-space recurrence, in place.

    S <- decay * S + B (outer) (dt x);    y = C . S          per head

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


def _kernel(packs_per_group, state_ref, xdt_ref, decay_ref, bt_ref, ct_ref,
            y_ref, out_ref):
    packs, n, w = state_ref.shape[1:]
    for g in range(packs // packs_per_group):
        b_col = jnp.broadcast_to(bt_ref[0, :, g:g + 1], (n, w))
        c_col = jnp.broadcast_to(ct_ref[0, :, g:g + 1], (n, w))
        for k in range(g * packs_per_group, (g + 1) * packs_per_group):
            s = state_ref[0, k] * decay_ref[0, k:k + 1, :] \
                + b_col * xdt_ref[0, k:k + 1, :]
            out_ref[0, k] = s
            y_ref[0, k:k + 1, :] = jnp.sum(s * c_col, axis=0, keepdims=True)


def update(state, xdt, decay, bt, ct, interpret=False):
    """state [B, K, N, W] float32 (K packs of heads, W = pack * P lanes);
    xdt, decay [B, K, W] float32 (dt * x, and exp(dt * A) of the lane's
    head); bt, ct [B, N, G] float32, pack k reading group
    k // (K / G). Returns (y [B, K, W] float32, the new state, which is
    the old one's buffer)."""
    b, packs, n, w = state.shape
    groups = bt.shape[2]
    whole = lambda i: (i, 0, 0)                              # noqa: E731
    rows = pl.BlockSpec((1, packs, w), whole)
    cols = pl.BlockSpec((1, n, groups), whole)
    state_spec = pl.BlockSpec((1, packs, n, w), lambda i: (i, 0, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, packs // groups),
        name="ssm_state_update",
        grid=(b,),
        in_specs=[state_spec, rows, rows, cols, cols],
        out_specs=[rows, state_spec],
        out_shape=[jax.ShapeDtypeStruct((b, packs, w), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        input_output_aliases={0: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(state, xdt, decay, bt, ct)


def update_reference(state, xdt, decay, bt, ct):
    """The same step in `jnp` (the CPU path)."""
    b, packs, n, w = state.shape
    per_group = packs // bt.shape[2]
    b_col = jnp.repeat(bt.transpose(0, 2, 1), per_group, axis=1)  # [B,K,N]
    c_col = jnp.repeat(ct.transpose(0, 2, 1), per_group, axis=1)
    new = state * decay[:, :, None, :] \
        + b_col[..., None] * xdt[:, :, None, :]
    return jnp.sum(new * c_col[..., None], axis=2), new
