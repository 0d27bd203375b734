"""Executor: lowers a ProgramDesc block to ONE XLA computation and runs it.

This replaces the reference's interpretive hot loop (Executor::Run at
executor.cc:184/307 running ops one-by-one at executor.cc:469-476, each
through kernel dispatch at operator.cc:1032) with whole-block compilation:

    feed vars + persistable state  ->  traced emitters  ->  fetches + new state

compiled by jax.jit, cached per (program version, feed shapes, fetch set).
Consequences, all TPU-native:
  * XLA fuses across op boundaries (no ir/ fusion pass zoo needed);
  * buffer lifetime is XLA buffer assignment (no GarbageCollector /
    memory_optimize passes needed — reference framework/garbage_collector.cc);
  * mutated persistables (optimizer ParamOut etc.) are donated, so parameter
    updates alias their input HBM buffers (reference relied on Scope mutation
    + share-buffer passes);
  * one host->device dispatch per step instead of per op.

SPMD: if program._mesh is set (by fleet / transpilers / the SPMD API), the
traced block runs under jax.shard_map over that Mesh — collective ops emit
ICI collectives, and feed/state are sharded per program._sharding. This is
the GSPMD replacement for the reference's ParallelExecutor SSA-graph runtime
(parallel_executor.cc:443, details/threaded_ssa_graph_executor.cc).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..core.place import default_place
from .program import Variable, default_main_program
from .registry import EmitContext, run_op
from .scope import global_scope


class _Compiled:
    __slots__ = ("fn", "state_ro", "state_mut", "fetch_names", "nan_ops",
                 "name")

    def __init__(self, fn, state_ro, state_mut, fetch_names, nan_ops=None,
                 name=None):
        self.fn = fn
        # the jitted function's name: the executable is the XLA module
        # `jit_<name>` in a profiler capture (None: loaded, not compiled)
        self.name = name
        self.state_ro = state_ro
        self.state_mut = state_mut
        self.fetch_names = fetch_names
        # ops list compiled with per-op NaN/Inf checks (FLAGS_check_nan_inf);
        # the extra trailing fetch indexes into this to name the offender
        self.nan_ops = nan_ops


def _analyze_block(block, feed_names, fetch_names):
    """Classify variable names: read-from-scope vs produced; mutated persistables."""
    produced = set()
    from_scope = []  # ordered; membership tracked in seen
    seen = set()
    mutated = set()
    for op in block.ops:
        for n in op.input_names():
            if n and n not in produced and n not in feed_names and n not in seen:
                from_scope.append(n)
                seen.add(n)
        for n in op.output_names():
            if not n:
                continue
            produced.add(n)
            v = block._find_var_recursive(n)
            if v is not None and v.persistable:
                mutated.add(n)
    for n in fetch_names:
        if n not in produced and n not in feed_names and n not in seen:
            from_scope.append(n)
            seen.add(n)
    state_mut = [n for n in from_scope if n in mutated]
    # vars produced without being read first but persistable (e.g. startup
    # program init ops) are still written back
    write_back = sorted(mutated)
    state_ro = [n for n in from_scope if n not in mutated]
    return state_ro, state_mut, write_back


class Executor:
    """fluid.Executor parity (python/paddle/fluid/executor.py:890)."""

    # bound on cached executables; eviction is LRU. The reference's
    # ExecutorPrepareContext cache had the same unbounded-growth hazard — a
    # cap keeps long-lived executors (many programs / shape buckets) sane.
    CACHE_CAPACITY = 128

    def __init__(self, place=None):
        from collections import OrderedDict

        from ..observability import timeline as _timeline

        # telemetry-plane opt-in (PR 16): with PADDLE_TPU_TELEMETRY_DIR
        # set, the first Executor in the process brings up the journal
        # publisher + flight recorder — launched trainers join the fleet
        # telemetry plane with no code changes (idempotent, cheap no-op
        # when the env is absent)
        _timeline.ensure_publisher()
        self.place = place if place is not None else default_place()
        # a place names a device kind: asking for one this process does
        # not have fails here (UnavailableError), never at a CPU's pace
        self.place.jax_device()
        self._cache = OrderedDict()

    def close(self):
        self._cache.clear()

    # ------------------------------------------------------------------
    def run(
        self,
        program=None,
        feed=None,
        fetch_list=None,
        scope=None,
        return_numpy=True,
        use_program_cache=True,
    ):
        from .. import observability as _obs

        _obs.add("executor.run_steps")
        step = _obs.span("executor.step")
        try:
            with step:
                result = self._run_body(
                    program, feed, fetch_list, scope, return_numpy,
                    use_program_cache,
                )
        finally:
            # the span's own stamps: None when monitoring is off
            if step.seconds is not None:
                _obs.observe("executor.step_latency", step.seconds)
        return result

    def _run_body(
        self, program, feed, fetch_list, scope, return_numpy,
        use_program_cache,
    ):
        """One step as four child spans of ``executor.step``: prologue
        (entry to just before the compiled function is called; the PRNG
        key's derivation is ``executor.rng_key`` inside it), dispatch
        (the call: staging, enqueue and any back-pressure of the
        runtime's queue), writeback (new state into the scope) and, for
        ``return_numpy`` callers, fetch (the wait for the device plus the
        copy to the host). In a profiler capture an idle device's gap
        falls under the one the host was in."""
        from .. import observability as _obs

        with _obs.span("executor.prologue"):
            compiled, scope, call_args = self._prologue(
                program, feed, fetch_list, scope, use_program_cache
            )
        with _obs.span("executor.dispatch"):
            fetches, new_state = compiled.fn(*call_args)
        # write-back FIRST: state_mut buffers were donated, so skipping the
        # write-back on error would leave the scope holding deleted arrays
        # (params irretrievably lost right when the user wants to inspect)
        with _obs.span("executor.writeback"):
            for n, v in new_state.items():
                scope.set_var(n, v)
        if return_numpy:
            with _obs.span("executor.fetch"):
                fetches = [np.asarray(f) for f in fetches]
        if compiled.nan_ops is not None:
            bad = np.asarray(fetches[-1])
            fetches = fetches[:-1]
            if bad.any():
                idx = int(np.argmax(bad))
                op = compiled.nan_ops[idx]
                from ..errors import NonFiniteError

                raise NonFiniteError(
                    f"NaN/Inf detected in outputs of op #{idx} "
                    f"{op.type!r} — FLAGS_check_nan_inf mode "
                    "(reference details/nan_inf_utils_detail.cc)",
                    op=op,
                    outputs=op.output_names(),
                )
        return list(fetches)

    def _prologue(self, program, feed, fetch_list, scope, use_program_cache):
        """Everything before the compiled call: (compiled, the resolved
        scope, the call's arguments)."""
        from .. import observability as _obs

        # the shared prologue keys the cache on the Program OBJECT
        # (identity hash, strong ref) so a freed Program's recycled id
        # cannot produce a stale hit; _prepared is the single source of
        # the key derivation for run/flops/AOT serialize+load
        (program, scope, block, feed_arrays, _feed_sig, fetch_names,
         key) = self._prepared(program, feed, fetch_list, scope)
        compiled = self._cache.get(key) if use_program_cache else None
        if compiled is None:
            if use_program_cache:
                _obs.add("executor.cache_misses")
            _obs.add("executor.compile_count")
            with _obs.timed("executor.compile_time"), \
                    _obs.span("executor.compile"):
                compiled = self._compile(
                    program, block, set(feed_arrays), fetch_names, scope, key
                )
            if use_program_cache:
                self._cache[key] = compiled
                while len(self._cache) > self.CACHE_CAPACITY:
                    self._cache.popitem(last=False)
                    _obs.add("executor.cache_evictions")
        else:
            _obs.add("executor.cache_hits")
            self._cache.move_to_end(key)

        state_ro = {n: self._from_scope(scope, n, block) for n in compiled.state_ro}
        state_mut = {n: self._from_scope(scope, n, block) for n in compiled.state_mut}

        # Per-step RNG folds in the program's own run counter: a fixed
        # random_seed pins the *sequence* (deterministic re-runs from a fresh
        # Program), while dropout masks still vary step to step — matching
        # the reference, which is deterministic per seed but advances its
        # generator every op execution.
        # seed 0 = nondeterministic (fluid semantics): fall back to the
        # program's own nonce so unseeded Programs are mutually decorrelated.
        # When the mesh spans processes the step key feeds a REPLICATED
        # shard_map input, so every process must derive the same value: use
        # a structural hash of the program instead of the per-process nonce
        # (identically-built programs hash identically on every rank).
        seed = program.random_seed
        if not seed:
            mesh = program._mesh
            multiproc = False
            if mesh is not None:
                cached = getattr(mesh, "_paddle_multiproc", None)
                if cached is None:
                    cached = any(
                        d.process_index != jax.process_index()
                        for d in mesh.devices.flat
                    )
                    try:
                        mesh._paddle_multiproc = cached
                    except AttributeError:
                        pass
                multiproc = cached
            seed = program._structural_seed() if multiproc else program._rng_nonce
        step = program._rng_step
        program._rng_step += 1
        from ..core.random import prng_impl

        # a span of its own inside the prologue: the key is derived by
        # tiny device programs, and on a mesh the runtime's wait for room
        # in its queue lands on them, not on the step's dispatch (PERF.md,
        # Findings PR 24)
        with _obs.span("executor.rng_key"):
            step_key = jax.random.fold_in(
                jax.random.key(seed, impl=prng_impl()), step
            )
        return compiled, scope, (
            feed_arrays, state_mut, state_ro, step_key
        )

    # ------------------------------------------------------------------
    def lower(self, program=None, feed=None, fetch_list=None, scope=None):
        """``jax.stages.Lowered`` for ONE step of `program` with this feed
        and fetch set: the same jitted function ``run`` dispatches (mesh
        programs included), state read from the scope, nothing executed.
        ``.compile()`` on it gives XLA's cost and memory analysis and the
        optimized HLO (``as_text()``: which collectives the partitioner
        put in, and whether a Pallas kernel is there — ``tpu_custom_call``).
        Reuses the executor's compile cache, so a ``run`` of the same
        step after ``.compile()`` was seen not to compile again (PR 21)."""
        (program, scope, block, feed_arrays, _feed_sig, fetch_names,
         key) = self._prepared(program, feed, fetch_list, scope)
        compiled = self._cache.get(key)
        if compiled is None:
            compiled = self._compile(
                program, block, set(feed_arrays), fetch_names, scope, key
            )
            self._cache[key] = compiled
        state_ro = {
            n: self._from_scope(scope, n, block) for n in compiled.state_ro
        }
        state_mut = {
            n: self._from_scope(scope, n, block) for n in compiled.state_mut
        }
        from ..core.random import prng_impl

        step_key = jax.random.key(0, impl=prng_impl())
        return compiled.fn.lower(feed_arrays, state_mut, state_ro, step_key)

    def flops(self, program=None, feed=None, fetch_list=None, scope=None):
        """XLA's static FLOP count for ONE step of `program` with this
        feed — the compiled executable's cost analysis (reference role:
        the per-op cost tooling of operators/benchmark/op_tester.cc).
        Reuses the executor's compile cache; run the same (program, feed)
        once first for a warm lookup. Pallas custom-call FLOPs are NOT
        visible to XLA — callers must add that term analytically
        (benchmark/harness/flops.py is the closed form the cells use)."""
        ca = self.lower(
            program, feed, fetch_list, scope
        ).compile().cost_analysis()
        if not ca or "flops" not in ca:
            # "backend reports no cost data" is NOT "zero-FLOP program":
            # callers deriving MFU from this must not read a silent 0.0
            import warnings

            from .. import observability as _obs
            from ..errors import CostAnalysisUnavailableWarning

            _obs.add("perf.cost_analysis_unavailable")
            warnings.warn(
                "XLA cost_analysis() returned no FLOP data for this "
                "executable; falling back to 0.0 — use "
                "Program.estimate() for an analytic count",
                CostAnalysisUnavailableWarning,
                stacklevel=2,
            )
            return 0.0
        return float(ca.get("flops", 0.0))

    # ------------------------------------------------------------------
    def memory_analysis(self, program=None, feed=None, fetch_list=None,
                        scope=None):
        """XLA's buffer-assignment memory breakdown for ONE step of
        `program` with this feed: a dict of ``argument_bytes`` /
        ``output_bytes`` / ``temp_bytes`` / ``alias_bytes`` plus
        ``peak_bytes`` (argument + output + temp − alias: every byte the
        executable holds at once, donated buffers counted once) from the
        compiled executable's ``memory_analysis()``. The ground truth the
        static plan (``Program.estimate().peak_bytes``) is cross-checked
        against (``tools/perf_report.py --check-memory``). Returns None —
        with a counter bump — when the backend reports nothing."""
        lowered = self.lower(program, feed, fetch_list, scope)
        try:
            ma = lowered.compile().memory_analysis()
        except Exception:
            ma = None
        fields = {
            "argument_bytes": "argument_size_in_bytes",
            "output_bytes": "output_size_in_bytes",
            "temp_bytes": "temp_size_in_bytes",
            "alias_bytes": "alias_size_in_bytes",
        }
        out = {
            k: float(getattr(ma, attr, 0.0) or 0.0)
            for k, attr in fields.items()
        }
        if ma is None or not any(out.values()):
            from .. import observability as _obs

            _obs.add("perf.memory_analysis_unavailable")
            return None
        out["peak_bytes"] = (
            out["argument_bytes"] + out["output_bytes"]
            + out["temp_bytes"] - out["alias_bytes"]
        )
        return out

    # ------------------------------------------------------------------
    def _prepared(self, program, feed, fetch_list, scope):
        """Shared prologue of run/flops/AOT paths: resolve the cache key,
        compile if needed, and assemble the argument pytrees."""
        program = program if program is not None else default_main_program()
        program = getattr(program, "program", program)
        scope = scope if scope is not None else global_scope()
        fetch_names = tuple(
            v.name if isinstance(v, Variable) else str(v)
            for v in (fetch_list or [])
        )
        block = program.global_block
        feed_arrays = {k: jnp.asarray(v) for k, v in dict(feed or {}).items()}
        feed_sig = tuple(
            (k, tuple(a.shape), str(a.dtype))
            for k, a in sorted(feed_arrays.items())
        )
        from ..flags import flag

        check_nan = bool(flag("check_nan_inf"))
        key = (program, program._version, feed_sig, fetch_names, check_nan)
        return (program, scope, block, feed_arrays, feed_sig, fetch_names,
                key)

    def serialize_executable(self, path, program=None, feed=None,
                             fetch_list=None, scope=None):
        """AOT-compile ONE step of (program, feed) and write the serialized
        XLA executable to `path` (reference role: AnalysisConfig's
        SetOptimCacheDir + the TRT engine serialization,
        inference/api/paddle_analysis_config.h). `load_executable` in a
        later process skips XLA compilation entirely for the same program
        structure + feed signature + device kind."""
        import pickle

        from jax.experimental import serialize_executable as se

        (program, scope, block, feed_arrays, feed_sig, fetch_names,
         key) = self._prepared(program, feed, fetch_list, scope)
        if key[-1]:  # check_nan flag in the cache key
            from ..errors import PreconditionNotMetError

            raise PreconditionNotMetError(
                "serialize_executable under FLAGS_check_nan_inf is not "
                "supported: the nan-flags fetch and its op table cannot be "
                "serialized; clear the flag around the serialization"
            )
        from ..core.random import prng_impl

        step_key = jax.random.key(0, impl=prng_impl())
        # An executable that was LOADED from the persistent compilation
        # cache serializes incompletely (XLA:CPU leaves backend
        # function-registry entries behind — "Function ... not found" on
        # deserialize). So the serialization pass never touches the serving
        # jit OR the disk cache: switch the persistent cache off (its
        # directory stays as placed), reset its module-global handle,
        # re-trace the block into a FRESH jit object, and AOT-compile that.
        from jax.experimental.compilation_cache import (
            compilation_cache as _cc,
        )

        cache_on = jax.config.jax_enable_compilation_cache
        try:
            jax.config.update("jax_enable_compilation_cache", False)
            _cc.reset_cache()
            compiled = self._compile(
                program, block, set(feed_arrays), fetch_names, scope, key
            )
            state_ro = {
                n: self._from_scope(scope, n, block)
                for n in compiled.state_ro
            }
            state_mut = {
                n: self._from_scope(scope, n, block)
                for n in compiled.state_mut
            }
            lowered = compiled.fn.lower(feed_arrays, state_mut, state_ro,
                                        step_key)
            payload, in_tree, out_tree = se.serialize(lowered.compile())
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)
            _cc.reset_cache()
        blob = {
            "payload": payload,
            "in_tree": in_tree,
            "out_tree": out_tree,
            "feed_sig": feed_sig,
            "fetch_names": fetch_names,
            "state_ro": list(compiled.state_ro),
            "state_mut": list(compiled.state_mut),
            "platform": jax.devices()[0].platform,
        }
        with open(path, "wb") as f:
            pickle.dump(blob, f)
        return path

    def load_executable(self, path, program=None, feed=None,
                        fetch_list=None, scope=None):
        """Install a serialized executable (serialize_executable) into this
        executor's cache for (program, feed signature, fetch set) — the
        next `run` dispatches it with NO XLA compilation. Raises
        InvalidArgumentError when the signature does not match."""
        import pickle

        from jax.experimental import serialize_executable as se

        from ..errors import InvalidArgumentError

        (program, scope, block, feed_arrays, feed_sig, fetch_names,
         key) = self._prepared(program, feed, fetch_list, scope)
        if key[-1]:
            raise InvalidArgumentError(
                "load_executable under FLAGS_check_nan_inf is not "
                "supported (serialized executables carry no nan-check op "
                "table); clear the flag for AOT serving"
            )
        with open(path, "rb") as f:
            blob = pickle.load(f)
        if blob["feed_sig"] != feed_sig or blob["fetch_names"] != fetch_names:
            raise InvalidArgumentError(
                f"serialized executable at {path!r} was built for feed "
                f"{blob['feed_sig']} / fetches {blob['fetch_names']}, got "
                f"{feed_sig} / {fetch_names}"
            )
        if blob["platform"] != jax.devices()[0].platform:
            raise InvalidArgumentError(
                f"serialized executable targets platform "
                f"{blob['platform']!r}; this process runs "
                f"{jax.devices()[0].platform!r}"
            )
        # pin the execution devices to the single default device the
        # executable was jit-compiled for — the default (all local devices)
        # breaks under a forced multi-device CPU (test mesh) topology
        loaded = se.deserialize_and_load(
            blob["payload"], blob["in_tree"], blob["out_tree"],
            execution_devices=[jax.devices()[0]],
        )
        self._cache[key] = _Compiled(
            loaded, blob["state_ro"], blob["state_mut"], fetch_names
        )
        return self._cache[key]

    # ------------------------------------------------------------------
    def train_from_dataset(
        self, program=None, dataset=None, scope=None, thread=0,
        debug=False, fetch_list=None, fetch_info=None, print_period=100,
    ):
        """Train over a fluid Dataset (reference executor.py:1323 ->
        TrainerFactory -> HogwildWorker op loops, hogwild_worker.cc:189).
        Here each parsed batch feeds the ONE jitted step — the per-thread
        op interpreter the reference needed is subsumed by XLA, so
        `thread` only tunes the host-side parse (dataset.set_thread)."""
        if dataset is None:
            from ..errors import InvalidArgumentError

            raise InvalidArgumentError(
                "train_from_dataset requires a dataset"
            )
        fetch_list = fetch_list or []
        fetch_info = fetch_info or [
            getattr(v, "name", str(v)) for v in fetch_list
        ]
        step = 0
        for feed in dataset.batches():
            outs = self.run(
                program, feed=feed, fetch_list=fetch_list, scope=scope,
            )
            step += 1
            if debug and fetch_list and step % print_period == 0:
                import numpy as _np

                vals = ", ".join(
                    f"{n}={_np.asarray(v).reshape(-1)[0]:.6g}"
                    for n, v in zip(fetch_info, outs)
                )
                print(f"step {step}: {vals}")
        return step

    def infer_from_dataset(self, program=None, dataset=None, **kw):
        """Like train_from_dataset but refuses programs containing update
        ops — the reference guarantees no parameter mutation here; pass a
        clone(for_test=True)/pruned inference program."""
        prog = program if program is not None else default_main_program()
        prog = getattr(prog, "program", prog)
        update_ops = {
            "sgd", "momentum", "lars_momentum", "adam", "adamw", "lamb",
            "adagrad", "decayed_adagrad", "adadelta", "rmsprop", "ftrl",
            "adamax", "dpsgd",
        }
        bad = [op.type for op in prog.global_block.ops
               if op.type in update_ops]
        if bad:
            from ..errors import InvalidArgumentError

            raise InvalidArgumentError(
                f"infer_from_dataset got a program with update ops {bad}; "
                "pass an inference program (clone(for_test=True) before "
                "minimize, or load_inference_model output)"
            )
        return self.train_from_dataset(program, dataset, **kw)

    # ------------------------------------------------------------------
    def _from_scope(self, scope, name, block):
        v = scope.find_var(name)
        if v is None:
            from ..errors import NotFoundError, PreconditionNotMetError

            var = block._find_var_recursive(name)
            if var is not None and var.is_data:
                raise NotFoundError(
                    f"feed variable {name!r} was not provided in `feed`"
                )
            raise PreconditionNotMetError(
                f"variable {name!r} is not initialized in the scope; "
                "run the startup program first (exe.run(startup_program))"
            )
        return v

    def _module_name(self, program, key):
        """What the jitted step is called, hence its XLA module
        (`jit_<name>` on a capture's "XLA Modules" line and in its
        events' `hlo_module`): the label the program's owner gave it
        (`<family>_prefill`, `train_step`, `startup`), else `program<n>`
        by the order of creation. Two executables this executor holds
        never share a name: a further compile of one program (another
        feed shape, another fetch set) appends a digest of what differs."""
        import zlib

        name = program._label or f"program{program._creation_ordinal}"
        if any(c.name == name for c in self._cache.values()):
            name += f"_{zlib.crc32(repr(key[1:]).encode()):08x}"
        return name

    def _compile(self, program, block, feed_names, fetch_names, scope,
                 key=()):
        from ..flags import flag

        # pre-trace static verification (PADDLE_TPU_VERIFY=strict|warn|0):
        # a malformed graph fails HERE with per-op provenance — strict mode
        # refuses to trace at all, so a rank-divergent collective schedule
        # can never reach the mesh and deadlock it
        from ..analysis import check_before_compile

        check_before_compile(program, feed_names, fetch_names)

        check_nan = bool(flag("check_nan_inf"))
        state_ro, state_mut, write_back = _analyze_block(
            block, feed_names, fetch_names
        )
        ops = list(block.ops)
        mesh = program._mesh
        spmd_mode = getattr(program, "_spmd_mode", "shard_map")
        # under gspmd there is no axis binding: collectives degrade to
        # identity and XLA derives cross-shard comms from shardings instead.
        # hybrid: only the program's manual axes are bound; the rest are
        # gspmd-Auto (emitters must not issue collectives over them)
        if mesh is None:
            mesh_axes = ()
        elif spmd_mode == "shard_map":
            mesh_axes = tuple(mesh.axis_names)
        elif spmd_mode == "hybrid":
            mesh_axes = tuple(getattr(program, "_manual_axes", ()))
        else:
            mesh_axes = ()

        # frozen inference programs (serving.freeze_program /
        # load_inference_model marks them _is_inference) trace in test
        # mode: even an op that missed its is_test attr flip must not run
        # train-only behavior (dropout masks, batch-norm stat updates)
        # while serving requests
        is_test = bool(getattr(program, "_is_inference", False))

        def traced(feeds, smut, sro, step_key):
            env = {}
            env.update(sro)
            env.update(smut)
            env.update(feeds)
            axis_sizes = dict(mesh.shape) if mesh is not None else {}
            ctx = EmitContext(
                step_key=step_key, is_test=is_test, mesh_axes=mesh_axes,
                axis_sizes=axis_sizes, program=program,
            )
            nan_flags = []
            for i, op in enumerate(ops):
                try:
                    run_op(ctx, op, env)
                except KeyError as e:
                    from ..errors import NotFoundError

                    raise NotFoundError(
                        f"op #{i} references undefined variable {e}",
                        op=op,
                    ) from None
                except Exception as e:
                    # attach op provenance to trace-time failures
                    # (reference framework/op_call_stack.cc); add_note keeps
                    # the original exception intact — many jax error classes
                    # cannot be reconstructed from a single message string
                    note = (
                        f"[while tracing op #{i} {op.type!r} created at "
                        f"{op.attr('__loc__', '<unknown>')}]"
                    )
                    e.add_note(note)
                    raise
                if check_nan:
                    bad = jnp.zeros((), bool)
                    for n in op.output_names():
                        v = env.get(n)
                        if v is not None and jnp.issubdtype(
                            jnp.asarray(v).dtype, jnp.inexact
                        ):
                            bad = bad | ~jnp.all(jnp.isfinite(v))
                    nan_flags.append(bad)
            fetches = tuple(env[n] for n in fetch_names)
            if check_nan and nan_flags:
                flags_arr = jnp.stack(nan_flags).astype(jnp.int32)
                # a NaN may live on one shard only (e.g. a row-sharded
                # table): reduce over every mesh axis so the replicated
                # fetch sees it regardless of which device it hit
                for ax in mesh_axes:
                    flags_arr = jax.lax.pmax(flags_arr, ax)
                fetches = fetches + (flags_arr,)
            new_state = {n: env[n] for n in write_back if n in env}
            return fetches, new_state

        traced.__name__ = name = self._module_name(program, key)
        if mesh is not None:
            from ..parallel.spmd import wrap_gspmd, wrap_shard_map

            wrap = wrap_gspmd if spmd_mode == "gspmd" else wrap_shard_map
            # the nan-check mode appends one extra (replicated) fetch; the
            # wrapper's out_specs must match the traced arity
            wrapped_fetches = (
                fetch_names + ("__nan_flags__",)
                if (check_nan and ops) else fetch_names
            )
            fn = wrap(
                traced, program, mesh, state_ro, state_mut, write_back,
                wrapped_fetches,
                manual_axes=(
                    mesh_axes if spmd_mode == "hybrid" else None
                ),
            )
        else:
            fn = jax.jit(traced, donate_argnums=(1,))
        return _Compiled(
            fn, state_ro, state_mut, fetch_names,
            nan_ops=ops if (check_nan and ops) else None, name=name,
        )


# fluid-parity helper: exe.run on the startup program is the "init" step;
# initializer ops (gaussian_random/fill_constant) produce the persistables.
