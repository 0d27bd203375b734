"""Eager-mode tracer: per-op execution + autograd tape.

Reference parity: imperative/tracer.cc:45 (TraceOp), basic_engine.cc:159
(BasicEngine backward walk), gradient_accumulator.cc. TPU-native changes:

* Ops execute through the SAME emitters as the static graph (registry.py) —
  no second kernel set, no core.ops.* codegen (the reference generated
  pybind fast-path functions per op, pybind/op_function_generator.cc).
* Each traced op with grad-requiring inputs runs under jax.vjp; the tape
  stores the vjp closure (residuals live on device). backward() is a
  reverse sweep accumulating into VarBase._grad by addition.
* Per-op jit caching (r4 — measured, not just claimed: the uncached
  tracer paid a fresh jax.vjp trace + op-by-op eager dispatch per op,
  22x the static executor on small shapes): the
  fused forward+vjp of each op is jax.jit-compiled once per (op_type,
  attrs, input avals) — jax.vjp's closure is a PYTREE (residual arrays
  as leaves), so it crosses the jit boundary as residual outputs. backward()
  applies tape closures through one shared jitted apply. This is the
  compiled analog of the reference's generated pybind fast paths
  (op_function_generator.cc) plus its dygraph kernel cache.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..framework.registry import EmitContext, OpView, get_op_def
from .varbase import VarBase

_tracer = None


def _current():
    return _tracer


def _require_tracer():
    if _tracer is None:
        raise RuntimeError("not in dygraph mode; use `with fluid.dygraph.guard():`")
    return _tracer


class TapeEntry:
    __slots__ = ("vjp_fn", "inputs", "outputs")

    def __init__(self, vjp_fn, inputs, outputs):
        self.vjp_fn = vjp_fn  # cotangents(list) -> input grads(list)
        self.inputs = inputs  # [VarBase] needing grad
        self.outputs = outputs  # [VarBase]


class Tracer:
    def __init__(self):
        self._tape = []
        self.enable_grad = True
        self._op_seq = 0
        self.train_mode = True
        # compiled (fwd, fwd+vjp) per (op_type, attrs, avals) — see
        # _build_jitted; False marks signatures jit cannot trace
        self._jit_cache = {}

    # ------------------------------------------------------------------
    def trace_op(self, op_type, ins, attrs, n_outs_hint=None):
        """ins: {slot: [VarBase|None]}. Returns {slot: [VarBase]}."""
        op_def = get_op_def(op_type)
        self._op_seq += 1
        attrs = dict(attrs or {})
        attrs.setdefault("__uid__", self._op_seq)
        seq = int(attrs["__uid__"])
        is_test = not self.train_mode

        flat_in = []  # (slot, idx, VarBase) for grad-requiring inputs
        raw = {}
        for slot, vs in ins.items():
            raw[slot] = [None if v is None else v.value for v in vs]
            for i, v in enumerate(vs):
                if (
                    v is not None
                    and not v.stop_gradient
                    and self.enable_grad
                    and op_def.differentiable
                    and jnp.issubdtype(v.value.dtype, jnp.inexact)
                ):
                    flat_in.append((slot, i, v))
        diff_pos = tuple((slot, i) for slot, i, _ in flat_in)
        diff_vals = [v.value for _, _, v in flat_in]

        # RNG stream: the emitter derives masks from (step_key, uid); the
        # cached trace pins uid=0 and varies the step-key SEED ARGUMENT per
        # occurrence — same per-sequence determinism, one compile
        seed_v = np.uint32(attrs.get("seed", 0) or seq)

        # explicit-seed RNG ops bake seed+uid into the trace
        # (ops/_helpers.py op_key / ctx.key_for) and every occurrence needs
        # a distinct stream — caching would either share one mask across
        # occurrences (uid pinned) or compile per call (uid in the key,
        # _op_seq never repeats). Rare ops; use the uncached path.
        key = (None if attrs.get("seed", 0)
               else self._cache_key(op_type, attrs, is_test, ins, diff_pos))
        entry = self._jit_cache.get(key) if key is not None else None
        if entry is None and key is not None:
            entry = self._build_jitted(op_type, op_def, attrs, is_test,
                                       diff_pos)
            self._jit_cache[key] = entry
        if entry is not None and entry is not False:
            try:
                if flat_in:
                    flat, vjp_fn = entry["fwd_vjp"](diff_vals, raw, seed_v)
                else:
                    flat = entry["fwd"](raw, seed_v)
                    vjp_fn = None
                spec = entry["spec"][0]
            except Exception:
                # an emitter this jit cannot trace (value-dependent python
                # control flow): permanently fall back for this signature
                self._jit_cache[key] = False
                entry = False
        if entry is None or entry is False:
            flat, vjp_fn, spec = self._trace_uncached(
                op_def, op_type, attrs, is_test, raw, flat_in, seq
            )

        outs = _unflatten_outs(flat, spec)
        wrapped = self._wrap(outs, stop_gradient=not flat_in)
        if flat_in:
            out_vbs = [
                v for vs in wrapped.values() for v in vs if v is not None
            ]
            in_vbs = [v for _, _, v in flat_in]
            self._tape.append(TapeEntry(vjp_fn, in_vbs, out_vbs))
        return wrapped

    def _trace_uncached(self, op_def, op_type, attrs, is_test, raw,
                        flat_in, seq):
        """Pre-r4 path: direct (untraced) emitter execution."""
        view = OpView(op_type, attrs)
        ctx = EmitContext(
            step_key=jax.random.key(attrs.get("seed", 0) or seq),
            is_test=is_test,
        )
        if not flat_in:
            outs = op_def.emit(ctx, view, raw)
            flat, spec = _flatten_outs(outs)
            return flat, None, spec

        def fwd(diff_vals):
            merged = {s: list(v) for s, v in raw.items()}
            for (slot, i, _), val in zip(flat_in, diff_vals):
                merged[slot][i] = val
            outs = op_def.emit(ctx, view, merged)
            flat, spec = _flatten_outs(outs)
            return flat, spec

        diff_vals = [v.value for _, _, v in flat_in]
        flat, vjp_fn, spec = jax.vjp(fwd, diff_vals, has_aux=True)
        return flat, vjp_fn, spec

    @staticmethod
    def _cache_key(op_type, attrs, is_test, ins, diff_pos):
        items = []
        for k, v in sorted(attrs.items()):
            # explicit-seed ops never reach here (trace_op routes them to
            # the uncached path), so __uid__ can always be dropped
            if k in ("__uid__", "__loc__"):
                continue
            if isinstance(v, list):
                v = tuple(tuple(e) if isinstance(e, list) else e for e in v)
            elif isinstance(v, np.ndarray):
                v = (v.shape, str(v.dtype), v.tobytes())
            if not isinstance(v, (int, float, bool, str, bytes, tuple,
                                  type(None))):
                return None  # unhashable attr -> uncached path
            items.append((k, v))
        sig = []
        for slot in sorted(ins):
            for i, v in enumerate(ins[slot]):
                sig.append((
                    slot, i,
                    None if v is None
                    else (tuple(v.value.shape), str(v.value.dtype)),
                ))
        key = (op_type, tuple(items), is_test, tuple(sig), diff_pos)
        try:
            hash(key)
        except TypeError:  # nested-unhashable attr survived the guards
            return None
        return key

    def _build_jitted(self, op_type, op_def, attrs, is_test, diff_pos):
        attrs_norm = dict(attrs)
        # one compile serves every occurrence: the RNG stream comes from
        # the seed ARGUMENT (varied per call), not the uid; explicit-seed
        # ops bypass this path entirely (see trace_op)
        attrs_norm["__uid__"] = 0
        view = OpView(op_type, attrs_norm)
        spec_holder = [None]

        def fwd_and_vjp(diff_vals, raw, seed_v):
            ctx = EmitContext(step_key=jax.random.key(seed_v),
                              is_test=is_test)

            def fwd(dv):
                merged = {s: list(v) for s, v in raw.items()}
                for (slot, i), val in zip(diff_pos, dv):
                    merged[slot][i] = val
                outs = op_def.emit(ctx, view, merged)
                flat, spec = _flatten_outs(outs)
                spec_holder[0] = spec  # trace-time capture (static per key)
                return flat

            flat, vjp_fn = jax.vjp(fwd, diff_vals)
            # vjp_fn is a pytree, so it crosses the jit boundary
            # (residuals as outputs, structure static) — see _apply_vjp
            return flat, vjp_fn

        def fwd_only(raw, seed_v):
            ctx = EmitContext(step_key=jax.random.key(seed_v),
                              is_test=is_test)
            outs = op_def.emit(ctx, view, raw)
            flat, spec = _flatten_outs(outs)
            spec_holder[0] = spec
            return flat

        return {
            "fwd_vjp": jax.jit(fwd_and_vjp),
            "fwd": jax.jit(fwd_only),
            "spec": spec_holder,
        }

    def _wrap(self, outs, stop_gradient):
        return {
            slot: [
                None if v is None else VarBase(v, stop_gradient=stop_gradient)
                for v in vals
            ]
            for slot, vals in outs.items()
        }

    # ------------------------------------------------------------------
    def run_backward(self, root, retain_graph=False):
        if not jnp.issubdtype(root.value.dtype, jnp.inexact):
            raise ValueError("backward() root must be floating point")
        root._grad = jnp.ones_like(root.value)
        # reverse sweep: outputs' accumulated grads -> vjp -> inputs' grads
        for entry in reversed(self._tape):
            if not any(o._grad is not None for o in entry.outputs):
                continue
            cts = [
                o._grad
                if o._grad is not None
                else jnp.zeros_like(o.value)
                for o in entry.outputs
            ]
            (in_grads,) = _apply_vjp(entry.vjp_fn, cts)
            for v, g in zip(entry.inputs, in_grads):
                v._grad = g if v._grad is None else v._grad + g
        # free intermediate grads + residuals
        if not retain_graph:
            for entry in self._tape:
                for o in entry.outputs:
                    if not o.persistable:
                        o._grad = None
            self._tape.clear()

    def clear(self):
        self._tape.clear()


# one shared jitted apply for tape closures: jax.vjp's closure is a
# PYTREE (residual arrays as leaves, stable treedef across calls), so
# jax.jit caches per (closure structure, cotangent avals) and the backward
# sweep dispatches compiled code per tape entry. Tape entries that are
# plain python closures (VarBase.__getitem__, dygraph_to_static) are told
# apart by pytree-ness, not by type name, and applied eagerly.
_apply_vjp_jit = jax.jit(lambda f, cts: f(cts))
_jittable_closure_types: dict = {}


def _is_pytree_closure(fn):
    t = type(fn)
    ok = _jittable_closure_types.get(t)
    if ok is None:
        # a registered pytree flattens to a non-leaf treedef; a plain
        # python closure is a single opaque leaf
        td = jax.tree_util.tree_structure(fn)
        ok = td != jax.tree_util.tree_structure(0)
        _jittable_closure_types[t] = ok
    return ok


def _apply_vjp(vjp_fn, cts):
    if _is_pytree_closure(vjp_fn):
        return _apply_vjp_jit(vjp_fn, cts)
    return vjp_fn(cts)  # plain python closure (uncached fallback path)


def _flatten_outs(outs):
    flat, spec = [], []
    for slot in sorted(outs):
        for i, v in enumerate(outs[slot]):
            if v is not None:
                flat.append(v)
                spec.append((slot, i, True))
            else:
                spec.append((slot, i, False))
    return flat, spec


def _unflatten_outs(flat, spec):
    outs = {}
    it = iter(flat)
    for slot, i, present in spec:
        outs.setdefault(slot, [])
        while len(outs[slot]) <= i:
            outs[slot].append(None)
        if present:
            outs[slot][i] = next(it)
    return outs


def trace_op(op_type, ins, attrs=None, out_slot="Out"):
    """Module-level helper: trace and return the first output of `out_slot`."""
    tr = _require_tracer()
    outs = tr.trace_op(op_type, ins, attrs)
    return outs[out_slot][0]


def trace_op_multi(op_type, ins, attrs=None):
    tr = _require_tracer()
    return tr.trace_op(op_type, ins, attrs)


def _record_getitem(tr, src, idx, res):
    """Autograd through VarBase.__getitem__ (a jax gather)."""
    _, vjp_fn = jax.vjp(lambda v: v[idx], src.value)
    tr._tape.append(
        TapeEntry(lambda cts: ([vjp_fn(cts[0])[0]],), [src], [res])
    )


def _set_tracer(tr):
    global _tracer
    _tracer = tr
    from ..framework import program as _prog

    _prog._set_dygraph_tracer(tr)
