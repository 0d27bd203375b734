"""Shared emitter helpers (broadcasting rules, registration sugar).

Replaces the reference's operators/math library role for elementwise ops
(operators/elementwise/ broadcast rules): fluid's `axis` broadcast semantics
are implemented once here and shared by all elementwise emitters.
"""

from __future__ import annotations

import jax.numpy as jnp

from ..framework.registry import register_op


def einsum_f32(spec, x, y):
    """`jnp.einsum` of two low-precision operands into a float32 result:
    the products are exact and the accumulator is never rounded to the
    operands' dtype. XLA's CPU runtime lacks some bfloat16 x bfloat16 =
    float32 products, so off the TPU the operands are cast up first (the
    same numbers: every bfloat16 is a float32)."""
    import jax

    if jax.default_backend() != "tpu":
        x, y = x.astype(jnp.float32), y.astype(jnp.float32)
    return jnp.einsum(spec, x, y, preferred_element_type=jnp.float32)


def fluid_broadcast(x, y, axis):
    """fluid elementwise broadcasting: y's shape aligns to x starting at `axis`
    (elementwise_op_function.h in the reference). axis=-1 means numpy rules /
    trailing alignment."""
    if x.ndim == y.ndim or y.ndim == 0:
        return x, y
    if axis is None or axis == -1:
        axis = x.ndim - y.ndim
    # squeeze trailing size-1 dims fluid allows on y (e.g. bias [C] vs [C,1,1])
    new_shape = (1,) * axis + y.shape + (1,) * (x.ndim - axis - y.ndim)
    return x, y.reshape(new_shape)


def register_elementwise(op_type, fn):
    @register_op(op_type, inputs=["X", "Y"], outputs=["Out"])
    def emit(ctx, op, ins):
        x, y = ins["X"][0], ins["Y"][0]
        x, y = fluid_broadcast(x, y, op.attr("axis", -1))
        return {"Out": [fn(x, y)]}

    return emit


def register_unary(op_type, fn, differentiable=True):
    @register_op(
        op_type, inputs=["X"], outputs=["Out"], differentiable=differentiable
    )
    def emit(ctx, op, ins):
        return {"Out": [fn(ins["X"][0], op.attrs)]}

    return emit


def reduce_axes(attrs, ndim):
    if attrs.get("reduce_all", False):
        return None
    dim = attrs.get("dim", [0])
    if dim is None:
        return None
    if isinstance(dim, int):
        dim = [dim]
    return tuple(d % ndim if ndim else 0 for d in dim)


def register_reduce(op_type, fn):
    @register_op(op_type, inputs=["X"], outputs=["Out"])
    def emit(ctx, op, ins):
        x = ins["X"][0]
        axes = reduce_axes(op.attrs, x.ndim)
        keep = op.attr("keep_dim", False)
        out = fn(x, axis=axes, keepdims=keep)
        if out.ndim == 0:
            out = out.reshape([1])
        return {"Out": [out]}

    return emit


def stable_sigmoid_ce(x, z):
    """Numerically stable sigmoid cross-entropy from logits:
    max(x,0) - x*z + log(1+exp(-|x|)) — shared by the
    sigmoid_cross_entropy_with_logits emitter and yolov3_loss."""
    import jax

    return jnp.maximum(x, 0) - x * z + jax.nn.softplus(-jnp.abs(x))


def op_key(ctx, op):
    """Per-op RNG key: explicit seed attr wins (reference per-op seed
    semantics), else the counter-based ctx stream. Single definition —
    random.py / loss_ext.py / ctr_ops.py all share it."""
    import jax

    seed = op.attr("seed", 0)
    if seed:
        return jax.random.key(seed + op.uid)
    return ctx.key_for(op.uid, op.type)


def hash_mix(key_u32, num_hash):
    """Deterministic multiply-xorshift integer mix: num_hash parallel
    hashes of a uint32 key tensor -> [..., num_hash] uint32. Shared by the
    hash and pyramid_hash emitters (the reference links xxhash; this mix
    has the same contract — fixed, well-distributed, vectorizable)."""
    import numpy as np

    consts = jnp.asarray(
        np.array([0x9E3779B1 + 2 * k + 1 for k in range(num_hash)],
                 dtype=np.uint32)
    )
    h = key_u32[..., None] * consts
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x85EBCA77)
    h = h ^ (h >> 13)
    return h
