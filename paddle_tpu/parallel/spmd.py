"""SPMD execution: run a traced Program block under jax.shard_map over a Mesh.

This is the GSPMD replacement for the reference's ParallelExecutor SSA-graph
runtime (parallel_executor.cc:443): instead of cloning the graph per device
and scheduling op handles across threads/streams
(details/fast_threaded_ssa_graph_executor.cc:54), ONE program runs on every
shard; collective ops (ops/collective.py) see the mesh axis names and emit
ICI collectives; everything else is element-local and XLA partitions it.

Sharding metadata lives on the Program: `program._sharding` maps var name ->
tuple of mesh-axis names per dimension (None entries = replicated dim), the
moral equivalent of GSPMD sharding annotations. Unlisted vars are replicated
— the reference's default of broadcasting parameters to every device
(parallel_executor.cc:570 BCastParamsToDevices) without any copy loop.
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding, PartitionSpec as P


def spec_for(program, name) -> P:
    s = program._sharding.get(name)
    if not s:
        # same-shaped optimizer accumulators INHERIT their parameter's
        # spec (optimizer.py tags them with _accum_of). A TP/stage-sharded
        # weight must not drag a spec-less moment through its elementwise
        # update: inside a manual shard_map body the param arrives sliced
        # while the moment arrives full, and the update silently
        # broadcasts. Accumulator names carry a unique_name suffix, so
        # spec-by-name from the user cannot be relied on.
        v = program.global_block._find_var_recursive(name)
        parent = getattr(v, "_accum_of", None)
        if parent is not None and parent != name:
            pv = program.global_block._find_var_recursive(parent)
            if (
                pv is not None
                and tuple(v.shape or ()) == tuple(pv.shape or ())
            ):
                return spec_for(program, parent)
        return P()
    return P(*s)


def _spans_processes(mesh):
    """True when the mesh includes devices of other processes (multi-host
    SPMD: every participating process runs the same program)."""
    return any(
        d.process_index != jax.process_index() for d in mesh.devices.flat
    )


def stage_global(x, mesh, pspec, multiproc=None, local_is_full=False):
    """Make `x` a global array on the mesh.

    Single-process: plain device_put. Multi-process: assemble the global
    view with jax.make_array_from_process_local_data — the TPU-native
    replacement for the reference's per-trainer feed +
    BCastParamsToDevices bootstrap. Two local-data conventions:
      * feeds (local_is_full=False): each process holds only ITS shard
        (dp input pipeline), global shape is inferred by concatenation;
      * state (local_is_full=True): each process holds the FULL value
        (startup ran locally); global_shape=x.shape makes
        make_array_from_process_local_data slice out this process's part —
        required for cross-process-sharded state like ps tables.
    """
    import numpy as np

    sharding = NamedSharding(mesh, pspec)
    if isinstance(x, jax.Array) and not x.is_fully_addressable:
        return x  # already a global array (e.g. written-back state)
    if multiproc is None:
        multiproc = _spans_processes(mesh)
    if multiproc:
        arr = np.asarray(x)
        return jax.make_array_from_process_local_data(
            sharding, arr, global_shape=arr.shape if local_is_full else None
        )
    return jax.device_put(x, sharding)


def _project_spec(spec, manual):
    """Drop non-manual axis names from a PartitionSpec (hybrid mode: the
    shard_map body is manual over `manual` only; other mesh axes are Auto —
    their sharding rides on the arrays' NamedShardings and XLA propagation,
    exactly gspmd, while manual axes keep explicit collectives)."""
    out = []
    for e in spec:
        if e is None:
            out.append(None)
        elif isinstance(e, (tuple, list)):
            kept = tuple(a for a in e if a in manual)
            out.append(kept if kept else None)
        else:
            out.append(e if e in manual else None)
    return P(*out)


def _staged_dispatch(jitted, stage, counter, mesh):
    """fn(feeds, smut, sro, step_key): stage the arguments onto the mesh,
    then dispatch `jitted`. ``fn.lower`` stages the same way and lowers
    without dispatching (Executor.lower)."""
    from .. import observability as _obs

    _obs.set_gauge("collective.mesh_devices", mesh.size)
    mesh_desc = "x".join(f"{k}{v}" for k, v in mesh.shape.items())

    def fn(feeds, smut, sro, step_key):
        _obs.add(counter)
        # a traced child span under executor.step: in a causal trace the
        # staging+dispatch segment is attributable to the mesh, and the
        # mesh shape rides on the span for the pod-timeline merge;
        # spmd.stage is the host's part of it (placing the arguments),
        # the rest the enqueue and whatever back-pressure it meets
        with _obs.span("spmd.dispatch", category="spmd", mesh=mesh_desc):
            with _obs.span("spmd.stage", category="spmd"):
                staged = stage(feeds, smut, sro)
            return jitted(*staged, step_key)

    fn.lower = lambda feeds, smut, sro, step_key: jitted.lower(
        *stage(feeds, smut, sro), step_key
    )
    return fn


def wrap_shard_map(
    traced, program, mesh, state_ro, state_mut, write_back, fetch_names,
    manual_axes=None,
):
    """Wrap the executor's traced block for SPMD execution.

    traced(feeds, smut, sro, step_key) -> (tuple_of_fetches, new_state_dict)
    with static structure: new_state keys == write_back exactly.

    manual_axes: None = fully manual (classic shard_map). A subset of mesh
    axis names = HYBRID mode: the body is manual over those axes (explicit
    collective ops, lax.axis_index — what the pipeline scheduler needs)
    while the remaining axes are Auto — arrays stay global over them and
    the XLA SPMD partitioner shards per annotation, which is how Megatron
    tensor parallelism composes with the pipeline in ONE program. The
    reference could not express this mix (every strategy was a separate
    NCCL transpile); on TPU it is one jit.
    """
    manual = (
        frozenset(manual_axes) if manual_axes is not None
        else frozenset(mesh.axis_names)
    )
    partial_manual = manual != frozenset(mesh.axis_names)

    def body_spec(name):
        s = spec_for(program, name)
        return _project_spec(s, manual) if partial_manual else s

    def run(feeds, smut, sro, step_key):
        in_specs = (
            {k: body_spec(k) for k in feeds},
            {k: body_spec(k) for k in smut},
            {k: body_spec(k) for k in sro},
            P(),
        )
        out_specs = (
            tuple(body_spec(n) for n in fetch_names),
            {n: body_spec(n) for n in write_back},
        )
        sm = jax.shard_map(
            traced,
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            check_vma=False,
            **({"axis_names": manual} if partial_manual else {}),
        )
        return sm(feeds, smut, sro, step_key)

    run.__name__ = traced.__name__  # the executor's name for the module
    jitted = jax.jit(run, donate_argnums=(1,))
    multiproc = _spans_processes(mesh)

    def stage(feeds, smut, sro):
        feeds = {
            k: stage_global(v, mesh, spec_for(program, k), multiproc)
            for k, v in feeds.items()
        }
        if multiproc or partial_manual:
            # multi-process: state must be global arrays; each
            # process's scope holds the FULL value (startup ran
            # locally), so local_is_full slices out this process's
            # part.
            # hybrid: the Auto axes' sharding lives ONLY on the
            # arrays' committed NamedShardings (the body specs
            # project them away), so state must be staged with its
            # full spec or mp-annotated params silently stay
            # replicated on every device
            smut = {
                k: stage_global(
                    v, mesh, spec_for(program, k), multiproc,
                    local_is_full=True,
                )
                for k, v in smut.items()
            }
            sro = {
                k: stage_global(
                    v, mesh, spec_for(program, k), multiproc,
                    local_is_full=True,
                )
                for k, v in sro.items()
            }
        return feeds, smut, sro

    return _staged_dispatch(
        jitted, stage, "collective.shard_map_dispatches", mesh
    )


def wrap_gspmd(
    traced, program, mesh, state_ro, state_mut, write_back, fetch_names,
    manual_axes=None,
):
    """GSPMD mode: no explicit collectives, no shard_map. Inputs are committed
    to the mesh per their annotations; jax.jit + the XLA SPMD partitioner
    propagate shardings through the whole block and insert ICI collectives
    where the dataflow demands them (e.g. the psum after a row-parallel
    matmul in tensor parallelism). This is the design the reference could
    never reach with NCCL op handles: sharding is declared, not programmed.
    """

    jitted = jax.jit(traced, donate_argnums=(1,))
    multiproc = _spans_processes(mesh)

    def put(k, v):
        # multi-process gspmd convention: every process holds the FULL
        # value (feeds are replicated inputs, state came from a local
        # startup run) — stage_global(local_is_full=True) slices out this
        # process's addressable part and assembles the global array
        return stage_global(
            v, mesh, spec_for(program, k), multiproc, local_is_full=True
        )

    def stage(feeds, smut, sro):
        return tuple(
            {k: put(k, v) for k, v in d.items()} for d in (feeds, smut, sro)
        )

    return _staged_dispatch(jitted, stage, "collective.gspmd_dispatches", mesh)


def device_put_sharded(x, mesh, pspec):
    """Commit a host array onto the mesh with the given PartitionSpec."""
    return jax.device_put(x, NamedSharding(mesh, pspec))


def shard_program(program, mesh, shardings=None, mode="shard_map",
                  manual_axes=None):
    """Attach a mesh + sharding annotations to a Program (SPMD mode switch).

    shardings: {var_name: tuple_of_axis_names_per_dim}. E.g. a data-parallel
    feed image of rank 4 -> {"image": ("dp", None, None, None)} (in practice
    only leading axes need naming: ("dp",) suffices as a prefix spec).

    mode: "shard_map" (explicit collective ops, fleet/transpiled programs),
    "gspmd" (annotation-only, XLA-propagated — use for tensor parallelism),
    or "hybrid" (manual_axes are shard_map-manual with explicit collectives,
    every other mesh axis is gspmd-Auto — composes pipeline/dp collectives
    with tensor-parallel annotation propagation in one program).
    """
    program._mesh = mesh
    program._spmd_mode = mode
    if mode == "hybrid":
        if not manual_axes:
            raise ValueError("hybrid mode requires manual_axes")
        unknown = set(manual_axes) - set(mesh.axis_names)
        if unknown:
            raise ValueError(
                f"manual_axes {sorted(unknown)} not in mesh axes "
                f"{mesh.axis_names}"
            )
        program._manual_axes = tuple(manual_axes)
    if shardings:
        program._sharding.update(
            {k: tuple(v) for k, v in shardings.items()}
        )
    program._bump()
    return program
