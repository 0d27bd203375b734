"""Op registry: every op type is a JAX *emitter*, not a kernel.

TPU-native replacement for the reference's operator system (OpRegistry /
REGISTER_OP_CPU_KERNEL / REGISTER_OP_CUDA_KERNEL, op_registry.h:223-268, and
kernel dispatch at operator.cc:1032): instead of choosing a device kernel per
op at runtime, each op registers a pure function over jax arrays. The Executor
calls emitters inside a single jax.jit trace, so XLA sees the whole block and
fuses across op boundaries (the reference needed bespoke IR fusion passes for
this, ir/fuse_elewise_add_act_pass etc. — here the compiler does it).

Gradients: ops do NOT hand-write grad kernels. append_backward (backward.py)
emits a generic "__vjp__" op that re-applies the forward emitter under
jax.vjp inside the same trace; XLA CSE merges the re-traced forward with the
original, so cost matches a hand-written grad. Ops may still register a
custom grad maker (control flow, collectives) via grad_maker=.

Shape inference reuses the emitter through jax.eval_shape (abstract eval, no
compute) — one definition serves execution, shapes, and dtypes. -1 batch dims
are mapped through a prime sentinel.
"""

from __future__ import annotations

import itertools
import zlib

import numpy as np

import jax
import jax.numpy as jnp

from ..core.dtypes import to_numpy_dtype

# prime sentinel standing in for the -1 (batch) dim during abstract eval
BATCH_SENTINEL = 12289


class EmitContext:
    """Per-trace state handed to emitters: deterministic RNG, mode flags, mesh.

    RNG design (TPU-native): every Operator instance owns a stable uid; the key
    for op U at step S is fold_in(fold_in(seed_key, S), U). A "__vjp__" grad op
    replays its forward op under the *forward op's* uid, so e.g. the dropout
    mask in backward matches forward exactly — the reference saves an explicit
    mask tensor instead (dropout_op.cc); here determinism makes that free.
    """

    def __init__(
        self, step_key=None, is_test=False, mesh_axes=(), scope=None,
        abstract=False, axis_sizes=None, program=None,
    ):
        self.step_key = step_key
        self.is_test = is_test
        self.mesh_axes = tuple(mesh_axes)  # axis names visible inside shard_map
        # static axis sizes {name: size} (ring collectives need the step
        # count at trace time; mesh topology is static under SPMD)
        self.axis_sizes = dict(axis_sizes or {})
        self.scope = scope
        # True only during infer_shapes' eval_shape pass: emitters may then
        # substitute BATCH_SENTINEL for -1 dims; at run time -1 is an error
        self.abstract = abstract
        # the Program being traced: control-flow emitters resolve their
        # sub_block attr through it (while/cond/scan_block, ops/control_flow.py)
        self.program = program
        # >1 only while tracing inside pipeline_block stage bodies: runtime
        # batches are 1/divisor of graph-build shapes (microbatching), and
        # batch-shape-baking ops (reshape2) may re-derive their leading dim
        self.batch_divisor = 1

    def with_batch_divisor(self, divisor):
        c = EmitContext.__new__(EmitContext)
        c.__dict__.update(self.__dict__)
        c.batch_divisor = int(divisor)
        return c

    def with_key(self, new_key):
        """Shallow copy with a different step_key (loop bodies fold the
        iteration index in so dropout masks vary across iterations)."""
        c = EmitContext.__new__(EmitContext)
        c.__dict__.update(self.__dict__)
        c.step_key = new_key
        return c

    def key_for(self, op_uid: int, op_type: str = ""):
        # salt by op type: uids are per-Program, so two programs sharing a
        # random_seed (e.g. main + startup built together) could otherwise
        # collide at (seed, step=0, uid) — gaussian init correlating with a
        # dropout mask. Same-structure programs still get identical streams.
        salt = zlib.crc32(op_type.encode()) & 0x7FFFFFFF
        base = (
            jax.random.key(op_uid)
            if self.step_key is None
            else jax.random.fold_in(self.step_key, op_uid)
        )
        return jax.random.fold_in(base, salt)


class OpView:
    """Lightweight stand-in for an Operator (used when a grad op replays its
    forward op's emitter: same attrs, same uid => same RNG stream)."""

    def __init__(self, op_type, attrs, inputs=None, outputs=None):
        self.type = op_type
        self.attrs = dict(attrs or {})
        self.inputs = inputs or {}
        self.outputs = outputs or {}

    @property
    def uid(self):
        return self.attrs.get("__uid__", 0)

    def attr(self, name, default=None):
        return self.attrs.get(name, default)


class OpDef:
    def __init__(
        self,
        type,
        emit,
        input_slots,
        output_slots,
        differentiable=True,
        grad_maker=None,
        infer_shape=None,
        mutates=(),
    ):
        self.type = type
        self.emit = emit  # fn(ctx, op, ins) -> outs (dict slot -> list)
        self.input_slots = tuple(input_slots)
        self.output_slots = tuple(output_slots)
        self.differentiable = differentiable
        self.grad_maker = grad_maker  # fn(op, grad_out_names, block) -> ...
        self.infer_shape = infer_shape
        self.mutates = tuple(mutates)  # output slots aliasing an input slot


_REGISTRY: dict[str, OpDef] = {}


def register_op(type, inputs, outputs, **kw):
    """Decorator: @register_op("relu", inputs=["X"], outputs=["Out"])."""

    def deco(fn):
        _REGISTRY[type] = OpDef(type, fn, inputs, outputs, **kw)
        return fn

    return deco


def get_op_def(op_type: str) -> OpDef:
    if op_type not in _REGISTRY:
        from ..errors import UnimplementedError

        raise UnimplementedError(
            f"op type {op_type!r} is not registered"
        )
    return _REGISTRY[op_type]


def registered_ops():
    return sorted(_REGISTRY)


# ---------------------------------------------------------------------------
# shape inference via abstract eval of the emitter
# ---------------------------------------------------------------------------


def _spec_from_var(var):
    shape = []
    for s in var.shape or ():
        if s in (-1, None):
            shape.append(BATCH_SENTINEL)
        else:
            s = int(s)
            if s != 0 and s % BATCH_SENTINEL == 0:
                # a real dim that is a multiple of the sentinel would silently
                # round-trip to -1 in _shape_back; refuse instead of corrupting
                raise ValueError(
                    f"variable {var.name!r} has dim {s}, a multiple of the "
                    f"internal batch sentinel {BATCH_SENTINEL}; pad the dim "
                    "by one or use explicit infer_shape for this op"
                )
            shape.append(s)
    return jax.ShapeDtypeStruct(tuple(shape), to_numpy_dtype(var.dtype))


def _shape_back(shape):
    return tuple(
        -1 if (d != 0 and d % BATCH_SENTINEL == 0) else int(d) for d in shape
    )


def infer_shapes(op_type, block, inputs, attrs):
    """Return {slot: [(shape, dtype_name), ...]} for op outputs.

    inputs: {slot: [var names]}. Uses eval_shape over the emitter, so any
    registered op gets shape/dtype inference for free.
    """
    from ..core.dtypes import convert_dtype
    from .program import Operator

    op_def = get_op_def(op_type)
    if op_def.infer_shape is not None:
        return op_def.infer_shape(block, inputs, attrs)

    in_specs = {
        slot: [
            _spec_from_var(block.var(n)) if n else None for n in names
        ]
        for slot, names in (inputs or {}).items()
    }
    fake_op = Operator(block, op_type, inputs, {}, attrs)
    ctx = EmitContext(
        step_key=None, is_test=True, abstract=True, program=block.program
    )

    def absfn(specs):
        return op_def.emit(ctx, fake_op, specs)

    out = jax.eval_shape(absfn, in_specs)
    result = {}
    for slot, vals in out.items():
        result[slot] = [
            (None, None)
            if v is None
            else (_shape_back(v.shape), convert_dtype(v.dtype))
            for v in vals
        ]
    return result


# ---------------------------------------------------------------------------
# flat-call helper used by the executor and by the generic vjp grad op
# ---------------------------------------------------------------------------


def flatten_ins(op):
    """[(slot, idx, name)] for every non-empty input of an op, stable order."""
    out = []
    for slot in sorted(op.inputs):
        for i, n in enumerate(op.inputs[slot]):
            if n:
                out.append((slot, i, n))
    return out


def flatten_outs(op):
    out = []
    for slot in sorted(op.outputs):
        for i, n in enumerate(op.outputs[slot]):
            if n:
                out.append((slot, i, n))
    return out


def scoped_type(op):
    """"<fluid.name_scope the op was built in>/<op type>", the type alone
    where there is no scope: the name an op's emitter is traced under."""
    scope = op.attr("op_namescope")
    return f"{scope}/{op.type}" if scope else op.type


def run_op(ctx, op, env):
    """Execute one op's emitter against an env (name -> jax value)."""
    op_def = get_op_def(op.type)
    ins = {
        slot: [env[n] if n else None for n in names]
        for slot, names in op.inputs.items()
    }
    # the op's name scope and type ride into the compiled step's HLO
    # metadata (op_name), so a profiler's device events can be read by
    # the model's section and by Fluid op (profiler.op_scopes)
    with jax.named_scope(scoped_type(op)):
        outs = op_def.emit(ctx, op, ins)
    for slot, names in op.outputs.items():
        vals = outs.get(slot, [])
        for n, v in itertools.zip_longest(names, vals):
            if n and v is not None:
                env[n] = v
    return outs
