"""Program / Block / Operator / Variable — the static-graph IR.

Capability parity with the reference's ProgramDesc/BlockDesc/OpDesc/VarDesc
(framework/framework.proto:42-216 and python/paddle/fluid/framework.py in the
reference repo), re-designed for the XLA compilation model:

* The IR is a pure Python graph (no protobuf round-trip needed on the hot
  path): an Operator names its input/output Variables per slot; a Block is an
  ordered op list + var map; a Program is a list of Blocks.
* There are NO per-op kernels. Every op type registers a JAX *emitter*
  (see registry.py); the Executor lowers a whole block to one XLA computation
  (jit) instead of interpreting ops one-by-one (the reference's hot loop at
  executor.cc:469-476). This is the TPU-native analogue of the reference's
  ChooseKernel dispatch (operator.cc:1032).
* Values live in a Scope (name -> array), exactly as the reference's
  Scope/Variable (scope.h:46) — but buffers are jax Arrays already resident
  on device, and the Executor donates mutated persistables back, so optimizer
  updates are in-place at the XLA buffer level.

Shapes use -1 for the batch (data-dependent) dimension at graph-build time;
at Executor.run the feed arrays pin concrete shapes and the whole block is
compiled static-shape (XLA requirement). Recompiles are cached per shape set.
"""

from __future__ import annotations

import contextlib
import copy

import numpy as np

from ..core.dtypes import convert_dtype, to_numpy_dtype
from . import unique_name


class Variable:
    """Graph-time variable metadata. Runtime values live in a Scope."""

    def __init__(
        self,
        block,
        name,
        shape=None,
        dtype="float32",
        persistable=False,
        stop_gradient=False,
        is_data=False,
        lod_level=0,
        initializer=None,
    ):
        self.block = block
        self.name = name
        self.shape = tuple(int(s) for s in shape) if shape is not None else None
        self.dtype = convert_dtype(dtype)
        self.persistable = persistable
        self.stop_gradient = stop_gradient
        self.is_data = is_data
        self.lod_level = lod_level  # kept for API parity; ragged data is packed at the pipeline edge
        self.initializer = initializer

    # --- fluid-style operator sugar (builds ops in the variable's block) ---
    def _elementwise(self, other, op_type, reverse=False):
        from .. import layers

        fn = {
            "elementwise_add": layers.elementwise_add,
            "elementwise_sub": layers.elementwise_sub,
            "elementwise_mul": layers.elementwise_mul,
            "elementwise_div": layers.elementwise_div,
            "less_than": layers.less_than,
            "less_equal": layers.less_equal,
            "greater_than": layers.greater_than,
            "greater_equal": layers.greater_equal,
            "elementwise_floordiv": layers.elementwise_floordiv,
            "elementwise_mod": layers.elementwise_mod,
        }[op_type]
        if not isinstance(other, Variable):
            other = layers.fill_constant([1], self.dtype, float(other))
        a, b = (other, self) if reverse else (self, other)
        return fn(a, b)

    def __add__(self, other):
        return self._elementwise(other, "elementwise_add")

    __radd__ = __add__

    def __sub__(self, other):
        return self._elementwise(other, "elementwise_sub")

    def __rsub__(self, other):
        return self._elementwise(other, "elementwise_sub", reverse=True)

    def __mul__(self, other):
        return self._elementwise(other, "elementwise_mul")

    __rmul__ = __mul__

    def __truediv__(self, other):
        return self._elementwise(other, "elementwise_div")

    def __floordiv__(self, other):
        return self._elementwise(other, "elementwise_floordiv")

    def __rfloordiv__(self, other):
        return self._elementwise(other, "elementwise_floordiv", reverse=True)

    def __mod__(self, other):
        return self._elementwise(other, "elementwise_mod")

    # comparisons build compare ops (fluid math_op_patch parity) — used by
    # @declarative-converted tensor conditions in static mode
    def __lt__(self, other):
        return self._elementwise(other, "less_than")

    def __le__(self, other):
        return self._elementwise(other, "less_equal")

    def __gt__(self, other):
        return self._elementwise(other, "greater_than")

    def __ge__(self, other):
        return self._elementwise(other, "greater_equal")

    def __repr__(self):
        return (
            f"Variable(name={self.name!r}, shape={self.shape}, "
            f"dtype={self.dtype}, persistable={self.persistable})"
        )

    @property
    def grad_name(self):
        return grad_var_name(self.name)


class Parameter(Variable):
    """A trainable persistable variable (framework.py:4962 in the reference)."""

    def __init__(self, block, name, shape, dtype, trainable=True, **kw):
        kw.setdefault("persistable", True)
        kw.setdefault("stop_gradient", not trainable)
        super().__init__(block, name, shape=shape, dtype=dtype, **kw)
        self.trainable = trainable
        self.regularizer = kw.get("regularizer")
        self.need_clip = True


def _user_frame():
    """file:line of the first stack frame outside paddle_tpu (cheap: walks
    raw frames, no traceback formatting). Disabled by FLAGS_op_provenance."""
    from ..flags import flag

    if not flag("op_provenance"):
        return None
    import sys

    f = sys._getframe(2)
    while f is not None:
        fn = f.f_code.co_filename
        if "paddle_tpu" not in fn:
            return f"{fn}:{f.f_lineno}"
        f = f.f_back
    return None


_program_counter = 0

GRAD_SUFFIX = "@GRAD"


def grad_var_name(name: str) -> str:
    return name + GRAD_SUFFIX


class Operator:
    """One op instance: type + named input/output slots + attrs.

    Slots map slot-name -> list of variable names, mirroring the reference's
    OpDesc (framework.proto:42). Attrs must stay picklable (plain python data)
    so Programs serialize for save_inference_model.
    """

    def __init__(self, block, op_type, inputs=None, outputs=None, attrs=None):
        self.block = block
        self.type = op_type
        self.inputs = {k: list(v) for k, v in (inputs or {}).items()}
        self.outputs = {k: list(v) for k, v in (outputs or {}).items()}
        self.attrs = dict(attrs or {})
        # ambient placement annotation (reference op_device attr honored at
        # operator.cc:1050-1075; set by device_guard, consumed by
        # PipelineOptimizer's stage slicing)
        if _current_device is not None and "op_device" not in self.attrs:
            self.attrs["op_device"] = _current_device
        # what the region of the model this op belongs to is for
        # (fluid.name_scope); rides into the compiled step's metadata
        if _name_scopes and "op_namescope" not in self.attrs:
            self.attrs["op_namescope"] = "/".join(_name_scopes)
        # creation provenance: the user frame that built this op, attached
        # to trace/runtime errors (reference framework/op_call_stack.cc)
        if "__loc__" not in self.attrs:
            loc = _user_frame()
            if loc:
                self.attrs["__loc__"] = loc
        # stable identity used to derive per-op RNG keys (registry.EmitContext);
        # per-Program (not global) so two identically-built programs get
        # identical RNG streams; survives deepcopy/clone so test-mode
        # programs keep the same streams
        self.uid = self.attrs.setdefault("__uid__", block.program._next_uid())

    def input_names(self):
        return [n for vs in self.inputs.values() for n in vs]

    def output_names(self):
        return [n for vs in self.outputs.values() for n in vs]

    def attr(self, name, default=None):
        return self.attrs.get(name, default)

    def __repr__(self):
        ins = {k: v for k, v in self.inputs.items()}
        outs = {k: v for k, v in self.outputs.items()}
        return f"Op({self.type}: {ins} -> {outs})"


class Block:
    def __init__(self, program, idx, parent_idx=-1):
        self.program = program
        self.idx = idx
        self.parent_idx = parent_idx
        self.vars = {}
        self.ops = []

    @property
    def parent_block(self):
        if self.parent_idx < 0:
            return None
        return self.program.blocks[self.parent_idx]

    def var(self, name):
        v = self._find_var_recursive(name)
        if v is None:
            from ..errors import NotFoundError

            raise NotFoundError(self._not_found_message(name))
        return v

    def _not_found_message(self, name):
        """Lookup-failure diagnostic: nearest existing names (did-you-mean)
        plus the block's feed and persistable sets, so a typo'd fetch/feed
        is obvious without dumping the whole Program."""
        import difflib

        names, feeds, persist = [], [], []
        blk = self
        while blk is not None:
            for n, v in blk.vars.items():
                names.append(n)
                if v.is_data:
                    feeds.append(n)
                elif v.persistable:
                    persist.append(n)
            blk = blk.parent_block
        msg = [f"variable {name!r} not found in block {self.idx}"]
        close = difflib.get_close_matches(name, names, n=3, cutoff=0.6)
        if close:
            msg.append(
                "did you mean " + " / ".join(repr(c) for c in close) + "?"
            )

        def _fmt(group, cap=8):
            shown = ", ".join(sorted(group)[:cap])
            more = len(group) - cap
            return shown + (f", ... +{more} more" if more > 0 else "")

        msg.append(
            f"block declares {len(names)} vars"
            + (f"; feeds: [{_fmt(feeds)}]" if feeds else "; no feed vars")
            + (
                f"; persistables: [{_fmt(persist)}]"
                if persist else "; no persistables"
            )
        )
        return "; ".join(msg)

    def has_var(self, name):
        return self._find_var_recursive(name) is not None

    def _find_var_recursive(self, name):
        blk = self
        while blk is not None:
            if name in blk.vars:
                return blk.vars[name]
            blk = blk.parent_block
        return None

    def create_var(self, name=None, **kw):
        if name is None:
            name = unique_name.generate("tmp")
        v = Variable(self, name, **kw)
        if name in self.vars:
            self._note_redefinition(name, v)
        self.vars[name] = v
        self.program._bump()
        return v

    def create_parameter(self, name, shape, dtype, **kw):
        p = Parameter(self, name, shape, dtype, **kw)
        if name in self.vars:
            self._note_redefinition(name, p)
        self.vars[name] = p
        self.program._bump()
        return p

    def _note_redefinition(self, name, new_v):
        """create_var/create_parameter used to overwrite an existing entry
        in self.vars with no signal — orphaning the old Variable while ops
        keep referencing the name. Record the event for the static
        verifier (analysis/structural.py reports it; ERROR under strict)
        and warn immediately when the respec is observable (shape, dtype,
        persistability, or Parameter-ness changed)."""
        old = self.vars[name]
        changes = []
        if tuple(old.shape or ()) != tuple(new_v.shape or ()):
            changes.append(f"shape {old.shape} -> {new_v.shape}")
        if old.dtype != new_v.dtype:
            changes.append(f"dtype {old.dtype} -> {new_v.dtype}")
        if bool(old.persistable) != bool(new_v.persistable):
            changes.append(
                f"persistable {old.persistable} -> {new_v.persistable}"
            )
        if type(old) is not type(new_v):
            changes.append(
                f"class {type(old).__name__} -> {type(new_v).__name__}"
            )
        detail = ", ".join(changes) if changes else "identical spec"
        self.__dict__.setdefault("_redefinitions", []).append({
            "name": name,
            "spec_changed": bool(changes),
            "detail": detail,
            "loc": _user_frame(),
        })
        if changes:
            import warnings

            from ..errors import ProgramVerifyWarning

            warnings.warn(
                f"variable {name!r} in block {self.idx} silently redefined "
                f"({detail}); the previous Variable object is orphaned but "
                "existing ops still reference this name",
                ProgramVerifyWarning,
                stacklevel=3,
            )

    def append_op(self, type, inputs=None, outputs=None, attrs=None, index=None):
        op = Operator(self, type, inputs, outputs, attrs)
        if index is None:
            self.ops.append(op)
        else:
            self.ops.insert(index, op)
        self.program._bump()
        return op

    def all_parameters(self):
        return [v for v in self.vars.values() if isinstance(v, Parameter)]

    def infer_and_create_output(
        self, op_type, inputs, attrs, out_name=None, out_slot="Out", **var_kw
    ):
        """Create the output Variable for slot `out_slot` of an op, inferring
        shape and dtype from the op's JAX emitter (registry.infer_shapes)."""
        from .registry import infer_shapes

        out_specs = infer_shapes(op_type, self, inputs, attrs)
        shape, dtype = out_specs[out_slot][0]
        name = out_name or unique_name.generate(op_type)
        return self.create_var(name=name, shape=shape, dtype=dtype, **var_kw)


class Program:
    def __init__(self):
        self.blocks = [Block(self, 0)]
        self.current_block_idx = 0
        self.random_seed = 0
        self._version = 0
        self._mesh = None  # set by parallel transpilers / SPMD mode
        self._sharding = {}  # var name -> PartitionSpec-like tuple
        # "shard_map": explicit collective ops see mesh axis names (the
        # transpiled/fleet path). "gspmd": sharding annotations only, XLA
        # inserts collectives by propagation (the TP/auto path).
        self._spmd_mode = "shard_map"
        self._pipeline = None  # set by PipelineOptimizer
        # what the program's owner calls it (`gpt_decode`, `train_step`,
        # `startup`): the Executor names the compiled step after it, so
        # its XLA module is `jit_<label>` in a profiler capture
        self._label = None
        self._op_uid = 0
        # per-program run counter folded into the step RNG key; advances on
        # every Executor.run so seeded programs still vary dropout per step
        self._rng_step = 0
        # fluid treats random_seed=0 as "nondeterministic": unseeded programs
        # draw a per-instance nonce so independent Programs (and restarted
        # processes) get decorrelated RNG streams. clone() deep-copies the
        # nonce, so a for_test clone keeps its parent's streams.
        import random as _random

        self._rng_nonce = _random.SystemRandom().getrandbits(31) | 1
        # process-wide creation ordinal: rank-consistent when every process
        # builds its programs in the same order (see _structural_seed)
        global _program_counter
        _program_counter += 1
        self._creation_ordinal = _program_counter

    def _next_uid(self):
        uid = self._op_uid
        self._op_uid += 1
        return uid

    def _bump(self):
        self._version += 1

    def _structural_seed(self):
        """Deterministic seed from program structure + a process-wide
        creation ordinal: identical on every process of a multi-controller
        job that built its programs in the same order (the rank-consistency
        contract multi-controller SPMD already requires), while two
        same-structured programs in ONE job still get distinct streams.
        Cached per program version (executor hot path)."""
        cached = self.__dict__.get("_structural_seed_cache")
        if cached is not None and cached[0] == self._version:
            return cached[1]
        import zlib

        sig = ",".join(
            f"{op.type}:{op.uid}" for b in self.blocks for op in b.ops
        )
        sig += f"#{self._creation_ordinal}"
        seed = (zlib.crc32(sig.encode()) & 0x7FFFFFFF) | 1
        self._structural_seed_cache = (self._version, seed)
        return seed

    def rng_state(self):
        """Snapshot of the per-program RNG stream position: the seed mode,
        the per-run step counter the executor folds into each step key,
        and the unseeded-program nonce. Together with the scope's
        persistables this makes `Executor.run` bitwise replayable — the
        exact-resume checkpoint (TrainStatus v2) carries it."""
        return {
            "random_seed": int(self.random_seed),
            "rng_step": int(self._rng_step),
            "rng_nonce": int(self._rng_nonce),
        }

    def set_rng_state(self, state):
        """Restore :meth:`rng_state`. A rebuilt program in a restarted
        process draws a fresh nonce; restoring the saved one re-aligns the
        unseeded stream with the run that wrote the checkpoint."""
        if not state:
            return
        if "random_seed" in state:
            self.random_seed = int(state["random_seed"])
        if "rng_step" in state:
            self._rng_step = int(state["rng_step"])
        nonce = state.get("rng_nonce")
        if nonce:
            self._rng_nonce = int(nonce)

    def estimate(self, feed_shapes=None, peak_tflops=None, peak_gbps=None):
        """Analytic per-op FLOPs / bytes / roofline-latency table for ONE
        step of this program (analysis/cost.py): the offline estimator —
        ``tools/perf_report.py`` renders and cross-checks it against XLA,
        and the planned autotuner consumes it as its objective; nothing
        calls it while a step runs. `feed_shapes` ({var: shape}) pins -1
        batch dims; peaks default from ``PADDLE_TPU_PEAK_TFLOPS`` /
        ``PADDLE_TPU_PEAK_GBPS`` (TPU v5e bf16). The table also carries
        the static HBM plan (``peak_bytes`` / ``resident_bytes`` /
        ``memory`` — analysis/memory.py's live-interval walk), the number
        ``serving.Server.warmup`` budgets against. Pure graph walk over
        declared Variable shapes — no tracing, no compilation."""
        from ..analysis.cost import estimate_program

        return estimate_program(
            self, feed_shapes=feed_shapes, peak_tflops=peak_tflops,
            peak_gbps=peak_gbps,
        )

    @property
    def global_block(self):
        return self.blocks[0]

    def current_block(self):
        return self.blocks[self.current_block_idx]

    def create_block(self, parent_idx=None):
        parent = self.current_block_idx if parent_idx is None else parent_idx
        blk = Block(self, len(self.blocks), parent)
        self.blocks.append(blk)
        self.current_block_idx = blk.idx
        self._bump()
        return blk

    def rollback(self):
        self.current_block_idx = self.current_block().parent_idx
        if self.current_block_idx < 0:
            self.current_block_idx = 0

    def all_parameters(self):
        return [p for b in self.blocks for p in b.all_parameters()]

    def list_vars(self):
        for b in self.blocks:
            yield from b.vars.values()

    def __getstate__(self):
        # the Mesh holds live device handles — never serialized; a loaded
        # Program is re-attached to a mesh by the caller (shard_program).
        # The verifier's per-version report cache is transient state.
        state = self.__dict__.copy()
        state["_mesh"] = None
        state.pop("_verify_cache", None)
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        # fields added after a model file was saved get their defaults
        self.__dict__.setdefault("_rng_step", 0)
        if "_rng_nonce" not in self.__dict__:
            import random as _random

            self._rng_nonce = _random.SystemRandom().getrandbits(31) | 1
        if "_creation_ordinal" not in self.__dict__:
            global _program_counter
            _program_counter += 1
            self._creation_ordinal = _program_counter
        self.__dict__.setdefault("_spmd_mode", "shard_map")
        self.__dict__.setdefault("_pipeline", None)
        self.__dict__.setdefault("_label", None)

    def clone(self, for_test=False):
        """Deep copy. for_test=True flips is_test on ops that honor it
        (dropout/batch_norm), matching fluid Program.clone semantics."""
        p = copy.deepcopy(self)
        if for_test:
            for b in p.blocks:
                for op in b.ops:
                    if "is_test" in op.attrs:
                        op.attrs["is_test"] = True
        p._bump()
        return p

    def __repr__(self):
        lines = []
        for b in self.blocks:
            lines.append(f"block {b.idx} (parent {b.parent_idx}):")
            for op in b.ops:
                lines.append(f"  {op}")
        return "\n".join(lines)


_main_program = Program()
_startup_program = Program()
_startup_program._label = "startup"


def default_main_program() -> Program:
    return _main_program


def default_startup_program() -> Program:
    return _startup_program


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    global _main_program, _startup_program
    old_main, old_startup = _main_program, _startup_program
    _main_program = main_program
    if startup_program is not None:
        _startup_program = startup_program
        if startup_program._label is None:
            startup_program._label = "startup"
    try:
        yield
    finally:
        _main_program, _startup_program = old_main, old_startup


# --- device_guard (reference fluid.device_guard; op_device attr) ---
_current_device = None


@contextlib.contextmanager
def device_guard(device=None):
    """Annotate appended ops with a placement string. For pipeline
    parallelism use "pipeline:K" stage tags (reference PipelineOptimizer
    contract, optimizer.py:3556)."""
    global _current_device
    old = _current_device
    _current_device = device
    try:
        yield
    finally:
        _current_device = old


# --- name_scope (reference fluid.name_scope, framework.py:441) ---
_name_scopes = []


@contextlib.contextmanager
def name_scope(prefix):
    """Say what a region of ops is for: an op appended inside carries the
    attribute ``op_namescope`` (scopes nest with "/"), and the Executor
    traces it under ``jax.named_scope("<scope>/<op type>")``, so the
    scope is in the ``op_name`` of every instruction the op compiles to
    (``profiler.summary(by="scope")`` reads a capture by it). Build-time
    only: nothing runs per step. The models mark their sections with one
    vocabulary: ``embed``, ``attn``, ``mlp``, ``moe``, ``ssm``, ``head``
    (README section Observability)."""
    _name_scopes.append(str(prefix))
    try:
        yield
    finally:
        _name_scopes.pop()


@contextlib.contextmanager
def scope_of(op):
    """The name scope `op` was built in, around ops appended on its
    behalf (its grad ops)."""
    global _name_scopes
    scope = op.attr("op_namescope")
    old, _name_scopes = _name_scopes, [scope] if scope else []
    try:
        yield
    finally:
        _name_scopes = old


# --- dygraph mode switch (framework.py:180 in the reference) ---
_dygraph_tracer = None


def in_dygraph_mode() -> bool:
    return _dygraph_tracer is not None


def _set_dygraph_tracer(tracer):
    global _dygraph_tracer
    _dygraph_tracer = tracer


def _current_tracer():
    return _dygraph_tracer
