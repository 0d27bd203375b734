"""Static-graph autodiff: append_backward.

Capability parity with the reference's python/paddle/fluid/backward.py
(append_backward at backward.py:1193, gradients at :1727): walks the block's
ops in reverse from the loss, appends grad ops, inserts `sum` ops where a
variable receives multiple gradient contributions, and returns (param, grad)
pairs for the optimizer.

TPU-native twist: instead of 430 hand-written grad kernels + GradOpMaker
registrations (grad_op_desc_maker.h in the reference), a single generic
"__vjp__" op replays the forward emitter under jax.vjp *inside the same XLA
trace*. XLA CSE merges the replayed forward with the original forward, so the
compiled HLO matches what hand-written grads would produce. Ops with custom
grad semantics (control flow, collectives) can still register grad_maker.
"""

from __future__ import annotations

from ..core.dtypes import is_float
from ..framework import unique_name
from .program import grad_var_name, scope_of
from .registry import OpView, get_op_def, register_op

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# the generic vjp grad op
# ---------------------------------------------------------------------------


@register_op("__vjp__", inputs=[], outputs=[], differentiable=False)
def _vjp_emit(ctx, op, ins):
    fwd_def = get_op_def(op.attr("fwd_type"))
    fwd_op = OpView(op.attr("fwd_type"), op.attr("fwd_attrs"))

    fwd_ins = {
        slot[len("FwdIn:"):]: vals
        for slot, vals in ins.items()
        if slot.startswith("FwdIn:")
    }
    out_grads = {
        slot[len("OutGrad:"):]: vals
        for slot, vals in ins.items()
        if slot.startswith("OutGrad:")
    }
    # positions (slot, idx) we need input grads for
    want = [
        (slot[len("InGrad:"):], i)
        for slot, names in op.outputs.items()
        if slot.startswith("InGrad:")
        for i, n in enumerate(names)
        if n
    ]

    def fwd_full(diff_vals):
        merged = {s: list(v) for s, v in fwd_ins.items()}
        for (slot, idx), val in zip(want, diff_vals):
            merged[slot][idx] = val
        # the replayed forward keeps its op's name beneath `__vjp__`
        # (whose own scope is the forward op's: `run_op`)
        with jax.named_scope(fwd_op.type):
            return fwd_def.emit(ctx, fwd_op, merged)

    diff_vals = [fwd_ins[slot][idx] for slot, idx in want]
    # structural pre-pass: which outputs are differentiable (inexact dtype).
    # The duplicate forward trace is merged away by XLA CSE.
    outs0 = fwd_full(diff_vals)
    keys = [
        (slot, i)
        for slot in sorted(outs0)
        for i, v in enumerate(outs0[slot])
        if v is not None and jnp.issubdtype(jnp.asarray(v).dtype, jnp.inexact)
    ]

    def fwd_flat(dv):
        outs = fwd_full(dv)
        return [outs[slot][i] for slot, i in keys]

    primals, vjp_fn = jax.vjp(fwd_flat, diff_vals)

    cts = []
    for (slot, i), primal in zip(keys, primals):
        g = None
        if slot in out_grads and i < len(out_grads[slot]):
            g = out_grads[slot][i]
        cts.append(
            jnp.zeros_like(primal) if g is None else g.astype(primal.dtype)
        )
    (in_grads,) = vjp_fn(cts)

    result = {}
    for (slot, idx), g in zip(want, in_grads):
        result.setdefault("InGrad:" + slot, {})[idx] = g
    # convert {idx: g} to dense lists matching op.outputs ordering
    out = {}
    for slot, names in op.outputs.items():
        if not slot.startswith("InGrad:"):
            continue
        vals = result.get(slot, {})
        out[slot] = [vals.get(i) for i in range(len(names))]
    return out


# ---------------------------------------------------------------------------
# the graph transform
# ---------------------------------------------------------------------------


def _ensure_var(block, name, like_name):
    if not block.has_var(name):
        src = block.var(like_name)
        block.create_var(name=name, shape=src.shape, dtype=src.dtype)
    return block.var(name)


def _append_grad_ops(op, op_def, block, contribs, finalize, needs_grad):
    """The grad ops of one forward op of the reverse walk."""
    out_has_grad = any(n in contribs for n in op.output_names())
    if not out_has_grad:
        return
    diff_inputs = [
        (slot, i, n)
        for slot, names in op.inputs.items()
        for i, n in enumerate(names)
        if n and n in needs_grad
    ]
    if not diff_inputs:
        return

    if op_def.grad_maker is not None:
        # a maker may decline (return False) to fall back to the
        # generic __vjp__ path, e.g. when a rarely-differentiated
        # auxiliary output turns out to carry gradients
        if op_def.grad_maker(op, block, contribs, finalize,
                             needs_grad=needs_grad) is not False:
            return

    # finalize the grads of this op's outputs
    grad_ins = {}
    for slot, names in op.outputs.items():
        grad_ins["OutGrad:" + slot] = [
            (finalize(n) or "") if n in contribs else "" for n in names
        ]
    fwd_in_slots = {"FwdIn:" + s: list(v) for s, v in op.inputs.items()}

    diff_set = set(diff_inputs)
    grad_outs = {}
    new_contribs = []
    for slot, names in op.inputs.items():
        outs = []
        for i, n in enumerate(names):
            if (slot, i, n) in diff_set:
                gname = unique_name.generate(grad_var_name(n) + "@RENAME")
                _ensure_var(block, gname, n)
                outs.append(gname)
                new_contribs.append((n, gname))
            else:
                outs.append("")
        grad_outs["InGrad:" + slot] = outs

    block.append_op(
        "__vjp__",
        {**fwd_in_slots, **grad_ins},
        grad_outs,
        {
            "fwd_type": op.type,
            "fwd_attrs": dict(op.attrs),
        },
    )
    for n, gname in new_contribs:
        contribs.setdefault(n, []).append(gname)


def append_backward(loss, parameter_list=None, no_grad_set=None, callbacks=None):
    """Append grad ops for `loss` into its block; return [(param, grad)]."""
    block = loss.block
    program = block.program
    no_grad = set(no_grad_set or ())

    # 1. which vars can require grads: forward reachability from trainable
    # params and explicitly differentiable (stop_gradient=False) data vars
    from .program import Parameter

    needs_grad = set()
    for v in block.vars.values():
        trainable_param = isinstance(v, Parameter) and v.trainable
        diff_input = not v.stop_gradient and v.is_data
        if (trainable_param or diff_input) and is_float(v.dtype):
            needs_grad.add(v.name)
    needs_grad -= no_grad
    fwd_ops = list(block.ops)  # snapshot before appending backward ops
    for op in fwd_ops:
        op_def = get_op_def(op.type)
        if not op_def.differentiable:
            continue
        if any(n in needs_grad for n in op.input_names()):
            for n in op.output_names():
                if (
                    n
                    and block.has_var(n)
                    and is_float(block.var(n).dtype)
                    and not block.var(n).stop_gradient
                    and n not in no_grad
                ):
                    needs_grad.add(n)

    # 2. seed loss gradient with 1.0 (reference: fill_constant at backward.py:1193)
    loss_grad = grad_var_name(loss.name)
    block.create_var(name=loss_grad, shape=loss.shape, dtype=loss.dtype)
    block.append_op(
        "fill_constant",
        {},
        {"Out": [loss_grad]},
        {"shape": list(loss.shape or (1,)), "dtype": loss.dtype, "value": 1.0},
    )

    # var name -> list of gradient contribution var names
    contribs = {loss.name: [loss_grad]}

    def finalize(name):
        """Collapse contributions into the canonical @GRAD var, summing."""
        c = contribs.get(name)
        if not c:
            return None
        canonical = grad_var_name(name)
        if len(c) == 1:
            if c[0] != canonical:
                _ensure_var(block, canonical, name)
                block.append_op("assign", {"X": [c[0]]}, {"Out": [canonical]})
        else:
            _ensure_var(block, canonical, name)
            block.append_op("sum", {"X": list(c)}, {"Out": [canonical]})
        contribs[name] = [canonical]
        return canonical

    # 3. reverse walk over the forward snapshot
    for op in reversed(fwd_ops):
        op_def = get_op_def(op.type)
        if not op_def.differentiable:
            continue
        # a grad op (and the sums that finalize its outputs' grads)
        # carries its forward op's name scope
        with scope_of(op):
            _append_grad_ops(op, op_def, block, contribs, finalize,
                             needs_grad)

    # 4. finalize parameter grads
    if parameter_list is not None:
        params = [
            block.var(p) if isinstance(p, str) else p for p in parameter_list
        ]
    else:
        params = [p for p in program.all_parameters() if p.trainable]
    result = []
    for p in params:
        if p.name in no_grad:
            continue
        g = finalize(p.name)
        if g is not None:
            result.append((p, block.var(g)))
    return result


def gradients(targets, inputs, target_gradients=None, no_grad_set=None):
    """fluid.gradients parity (backward.py:1727): grads of targets w.r.t inputs."""
    if not isinstance(targets, (list, tuple)):
        targets = [targets]
    if not isinstance(inputs, (list, tuple)):
        inputs = [inputs]
    assert len(targets) == 1, "multiple targets: sum them first"
    pairs = append_backward(
        targets[0], parameter_list=[v.name for v in inputs], no_grad_set=no_grad_set
    )
    by_name = {p.name: g for p, g in pairs}
    return [by_name.get(v.name) for v in inputs]
