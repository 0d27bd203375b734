"""Dense math ops: elementwise, matmul, reductions, scale/sum/mean/clip.

Capability parity with the reference's operators/elementwise/,
operators/reduce_ops/, matmul_op.cc, mul_op.cc, scale_op.cc, sum_op.cc,
mean_op.cc, clip_op.cc — all as XLA emitters (matmul lands on the MXU via
jnp.matmul/dot_general; elementwise ops fuse into neighbors automatically).
"""

from __future__ import annotations

import math

import jax.numpy as jnp
from jax import lax

from ..framework.registry import register_op
from ._helpers import (
    fluid_broadcast,
    register_elementwise,
    register_reduce,
    register_unary,
)

register_elementwise("elementwise_add", jnp.add)
register_elementwise("elementwise_sub", jnp.subtract)
register_elementwise("elementwise_mul", jnp.multiply)
register_elementwise("elementwise_div", jnp.divide)
register_elementwise("elementwise_max", jnp.maximum)
register_elementwise("elementwise_min", jnp.minimum)
register_elementwise("elementwise_pow", jnp.power)
register_elementwise("elementwise_mod", jnp.mod)
register_elementwise("elementwise_floordiv", jnp.floor_divide)

register_unary("sqrt", lambda x, a: jnp.sqrt(x))
register_unary("rsqrt", lambda x, a: lax.rsqrt(x))
register_unary("square", lambda x, a: jnp.square(x))
register_unary("abs", lambda x, a: jnp.abs(x))
register_unary("exp", lambda x, a: jnp.exp(x))
register_unary("log", lambda x, a: jnp.log(x))
register_unary("log2", lambda x, a: jnp.log2(x))
register_unary("log1p", lambda x, a: jnp.log1p(x))
register_unary("floor", lambda x, a: jnp.floor(x))
register_unary("ceil", lambda x, a: jnp.ceil(x))
register_unary("round", lambda x, a: jnp.round(x))
register_unary("reciprocal", lambda x, a: jnp.reciprocal(x))
register_unary("sign", lambda x, a: jnp.sign(x))
register_unary("sin", lambda x, a: jnp.sin(x))
register_unary("cos", lambda x, a: jnp.cos(x))
register_unary("pow", lambda x, a: jnp.power(x, a.get("factor", 1.0)))
register_unary(
    "logical_not", lambda x, a: jnp.logical_not(x), differentiable=False
)
register_unary("isfinite", lambda x, a: jnp.isfinite(x), differentiable=False)

register_reduce("reduce_sum", jnp.sum)
register_reduce("reduce_mean", jnp.mean)
register_reduce("reduce_max", jnp.max)
register_reduce("reduce_min", jnp.min)
register_reduce("reduce_prod", jnp.prod)
register_reduce("reduce_all", jnp.all)
register_reduce("reduce_any", jnp.any)


@register_op("scale", inputs=["X"], outputs=["Out"])
def _scale(ctx, op, ins):
    x = ins["X"][0]
    scale = op.attr("scale", 1.0)
    bias = op.attr("bias", 0.0)
    if op.attr("bias_after_scale", True):
        out = x * scale + bias
    else:
        out = (x + bias) * scale
    return {"Out": [out.astype(x.dtype)]}


@register_op("sum", inputs=["X"], outputs=["Out"])
def _sum(ctx, op, ins):
    xs = [x for x in ins["X"] if x is not None]
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return {"Out": [out]}


@register_op("mean", inputs=["X"], outputs=["Out"])
def _mean(ctx, op, ins):
    return {"Out": [jnp.mean(ins["X"][0]).reshape([1])]}


@register_op("clip", inputs=["X"], outputs=["Out"])
def _clip(ctx, op, ins):
    return {"Out": [jnp.clip(ins["X"][0], op.attr("min"), op.attr("max"))]}


@register_op("clip_by_norm", inputs=["X"], outputs=["Out"])
def _clip_by_norm(ctx, op, ins):
    x = ins["X"][0]
    max_norm = op.attr("max_norm")
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    return {"Out": [x * (max_norm / jnp.maximum(norm, max_norm))]}


@register_op("matmul", inputs=["X", "Y"], outputs=["Out"])
def _matmul(ctx, op, ins):
    x, y = ins["X"][0], ins["Y"][0]
    if op.attr("transpose_X", False):
        x = jnp.swapaxes(x, -1, -2) if x.ndim > 1 else x
    if op.attr("transpose_Y", False):
        y = jnp.swapaxes(y, -1, -2) if y.ndim > 1 else y
    out = jnp.matmul(x, y)
    alpha = op.attr("alpha", 1.0)
    if alpha != 1.0:
        out = out * alpha
    return {"Out": [out]}


@register_op("mul", inputs=["X", "Y"], outputs=["Out"])
def _mul(ctx, op, ins):
    # fluid mul op (mul_op.cc): flatten x to 2-D at x_num_col_dims, y likewise
    x, y = ins["X"][0], ins["Y"][0]
    xnc = op.attr("x_num_col_dims", 1)
    ync = op.attr("y_num_col_dims", 1)
    xs, ys = x.shape, y.shape
    x2 = x.reshape((math.prod(xs[:xnc]), -1))
    y2 = y.reshape((math.prod(ys[:ync]), -1))
    out_dtype = op.attr("out_dtype", None)
    if out_dtype:
        # the product leaves the accumulator in `out_dtype` (float32
        # logits of a bfloat16 head), not rounded to the operands' first
        from ._helpers import einsum_f32

        out = einsum_f32("mk,kn->mn", x2, y2).astype(jnp.dtype(out_dtype))
    else:
        out = jnp.matmul(x2, y2)
    return {"Out": [out.reshape(xs[:xnc] + ys[ync:])]}


@register_op("dot", inputs=["X", "Y"], outputs=["Out"])
def _dot(ctx, op, ins):
    x, y = ins["X"][0], ins["Y"][0]
    return {"Out": [jnp.sum(x * y, axis=-1, keepdims=True)]}


@register_op("bmm", inputs=["X", "Y"], outputs=["Out"])
def _bmm(ctx, op, ins):
    return {"Out": [jnp.matmul(ins["X"][0], ins["Y"][0])]}


for _cmp_type, _cmp_fn in [
    ("equal", jnp.equal),
    ("not_equal", jnp.not_equal),
    ("less_than", jnp.less),
    ("less_equal", jnp.less_equal),
    ("greater_than", jnp.greater),
    ("greater_equal", jnp.greater_equal),
]:

    def _make(fn):
        def emit(ctx, op, ins):
            x, y = ins["X"][0], ins["Y"][0]
            x, y = fluid_broadcast(x, y, op.attr("axis", -1))
            return {"Out": [fn(x, y)]}

        return emit

    register_op(_cmp_type, inputs=["X", "Y"], outputs=["Out"], differentiable=False)(
        _make(_cmp_fn)
    )

for _log_type, _log_fn in [
    ("logical_and", jnp.logical_and),
    ("logical_or", jnp.logical_or),
    ("logical_xor", jnp.logical_xor),
]:

    def _make_l(fn):
        def emit(ctx, op, ins):
            return {"Out": [fn(ins["X"][0], ins["Y"][0])]}

        return emit

    register_op(_log_type, inputs=["X", "Y"], outputs=["Out"], differentiable=False)(
        _make_l(_log_fn)
    )
