#!/usr/bin/env python
"""Build every bundled model and run the static program verifier over it.

Usage:
    python tools/program_lint.py --all-models [--strict] [--memory]
    python tools/program_lint.py --model bert --model gpt --json
    python tools/program_lint.py --broken-fixture   # must exit non-zero

Exit status: 0 when no model produced an ERROR finding (under --strict,
escalated WARNINGs — silent redefinition, oom-risk — also count),
non-zero otherwise. ``--broken-fixture`` builds a deliberately malformed
Program (use-before-def + shape desync + rank-divergent collective) and
lints it: CI asserts the exit status is NON-zero, the linter's own
regression test. ``--broken-donation-fixture`` (a read of a donated KV
cache buffer) and ``--broken-oom-fixture`` (a program over a deliberately
tiny ``PADDLE_TPU_HBM_BYTES``) are the memory family's equivalents.

``--memory`` prints the static peak-HBM plan (analysis/memory.py) per
model; ``--json`` swaps the human report for one machine-readable JSON
document on stdout (per-model findings with severity/category/op/loc,
plus the memory summary) for dashboards and diffing.

Models are built through ``paddle_tpu.models.zoo`` (CI-sized configs,
training programs with optimizer applied); meshed models (bert_3d) get a
virtual-device mesh so the collective-schedule lint has bound axes.
"""

from __future__ import annotations

import argparse
import os
import sys

# runnable as `python tools/program_lint.py` from anywhere
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# an 8-device virtual CPU mesh for the meshed models, before jax loads
# (mirrors tests/conftest.py)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def _lint_one(name, strict, verbose, cost=False, memory=False,
              records=None):
    import time

    from paddle_tpu.analysis import Severity, verify_program
    from paddle_tpu.models import build_model

    t0 = time.time()
    bm = build_model(name)
    built = time.time() - t0
    report = verify_program(bm.main, bm.feed_names, bm.fetch_names)
    startup_report = verify_program(bm.startup, (), ())
    report.extend(startup_report.findings)
    verified = time.time() - t0 - built
    failing = report.strict_errors() if strict else report.errors
    status = "FAIL" if failing else "ok"
    mt = None
    if memory or records is not None:
        # the memory family's full table (the verifier only surfaces its
        # findings; the table carries the per-op liveness timeline)
        from paddle_tpu.analysis import plan_memory

        mt = plan_memory(bm.main, feed_names=bm.feed_names or None,
                         fetch_names=bm.fetch_names)
    if records is not None:
        records.append({
            "model": name,
            "status": status,
            "errors": len(report.errors),
            "warnings": len(report.warnings),
            "infos": len(report.infos),
            "findings": [f.to_dict() for f in report.findings],
            "memory": mt.to_dict() if mt is not None else None,
        })
        return not failing
    print(
        f"[{status}] {name:<10} build {built:5.1f}s verify {verified:5.1f}s"
        f"  errors={len(report.errors)} warnings={len(report.warnings)} "
        f"info={len(report.infos)}"
    )
    min_sev = Severity.INFO if verbose else Severity.WARNING
    shown = [f for f in report.findings if f.severity >= min_sev]
    for f in shown:
        print("    " + f.format())
    if memory:
        for line in mt.format(top=5).splitlines():
            print("    " + line)
    if cost:
        # the fourth analysis family: per-op FLOPs/bytes/roofline table
        # (analysis/cost.py) at the model's graph-build shapes
        for line in bm.main.estimate().format(top=10).splitlines():
            print("    " + line)
    return not failing


def _broken_fixture():
    """A deliberately malformed Program: the linter must reject it."""
    import paddle_tpu as fluid
    from paddle_tpu.parallel import make_mesh, shard_program
    from paddle_tpu.parallel.pipeline import slice_program_into_stages

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        from paddle_tpu import layers

        x = fluid.data("x", [8, 4])
        with fluid.device_guard("pipeline:0"):
            h = layers.fc(x, 4)
        with fluid.device_guard("pipeline:1"):
            loss = layers.mean(layers.fc(h, 4))
        main._pipeline = {"num_microbatches": 2, "axis_name": "pp"}
        _, pipe_op = slice_program_into_stages(main, loss)
        blk = main.global_block
        # use-before-def: a temp no op ever produces
        blk.create_var(name="never_written", shape=[8, 4], dtype="float32")
        blk.append_op("relu", {"X": ["never_written"]}, {"Out": ["r0"]})
        blk.create_var(name="r0", shape=[8, 4], dtype="float32")
        # shape desync: declaration disagrees with the emitter
        blk.create_var(name="desynced", shape=[3, 3], dtype="float32")
        blk.append_op("relu", {"X": ["r0"]}, {"Out": ["desynced"]})
    # rank-divergent collective: stage 0 allreduces, stage 1 does not
    stage0 = main.blocks[pipe_op.attr("stage_blocks")[0]]
    stage0.append_op(
        "c_allreduce_sum", {"X": [h.name]}, {"Out": [h.name]},
        {"axis_name": "dp"},
    )
    mesh = make_mesh({"dp": 4, "pp": 2})
    shard_program(main, mesh, {"x": ("dp",)})
    return main, ("x",), (loss.name,)


def _broken_frozen_fixture():
    """A "frozen" inference program with a surviving optimizer op: the
    ``training-op-in-inference`` structural finding must reject it (the
    serving freeze regression fixture)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 4])
        pred = layers.fc(x, 2)
        prob = layers.softmax(pred)
    blk = main.global_block
    # a leftover sgd update (as if prune missed it): params mutate while
    # serving — the exact defect the finding exists to catch
    w = blk.all_parameters()[0]
    blk.create_var(name="lr0", shape=[1], dtype="float32")
    blk.append_op(
        "fill_constant", {}, {"Out": ["lr0"]},
        {"shape": [1], "dtype": "float32", "value": 0.1},
    )
    blk.append_op(
        "sgd",
        {"Param": [w.name], "Grad": [w.name], "LearningRate": ["lr0"]},
        {"ParamOut": [w.name]},
    )
    main._is_inference = True
    return main, ("x",), (prob.name,)


def _broken_bucket_fixture():
    """A program whose pipeline stages BUCKET the same grad exchange
    differently (two members on stage 0, one fused member on stage 1):
    bucket membership is part of the cross-rank wire contract, so the
    collective-schedule lint must reject this at build time — on a pod it
    would deadlock (or silently corrupt) the exchange."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.parallel import make_mesh, shard_program
    from paddle_tpu.parallel.pipeline import slice_program_into_stages

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [8, 4])
        with fluid.device_guard("pipeline:0"):
            h = layers.fc(x, 4)
        with fluid.device_guard("pipeline:1"):
            loss = layers.mean(layers.fc(h, 4))
        main._pipeline = {"num_microbatches": 2, "axis_name": "pp"}
        _, pipe_op = slice_program_into_stages(main, loss)
    for si, pads in ((0, [256, 256]), (1, [512])):
        stage = main.blocks[pipe_op.attr("stage_blocks")[si]]
        gname = f"bucket_grad_{si}"
        stage.create_var(name=gname, shape=[4, 4], dtype="float32")
        stage.append_op(
            "fill_constant", {}, {"Out": [gname]},
            {"shape": [4, 4], "dtype": "float32", "value": 0.0},
        )
        outs = []
        for j, p in enumerate(pads):
            oname = f"bucket_shard_{si}_{j}"
            stage.create_var(name=oname, shape=[p], dtype="float32")
            outs.append(oname)
        stage.append_op(
            "zero_bucket_reduce_scatter",
            {"X": [gname] * len(pads)}, {"Out": outs},
            {"axis_name": "dp", "pad_lens": pads, "quant": "none"},
        )
    shard_program(main, make_mesh({"dp": 4, "pp": 2}), {"x": ("dp",)})
    return main, ("x",), (loss.name,)


def _broken_donation_fixture():
    """A decode step whose ``kv_cache_write`` emits the updated cache
    under a NEW name — donating the old buffer (``mutates`` aliases Out
    onto Cache) — and then reads the stale donated handle. On device the
    read observes the overwritten pages; the donation verifier must
    reject it with ``use-after-donate``."""
    import paddle_tpu as fluid
    from paddle_tpu.ops.kv_cache import cache_shape

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        rows = fluid.data("rows", [1, 4, 8])  # [B, T, H]
        pos = fluid.data("pos", [1], dtype="int32")
    blk = main.global_block
    shape = list(cache_shape(batch=1, max_len=16, num_heads=2, head_dim=4))
    blk.create_var(name="cache", shape=shape, dtype="float32",
                   persistable=True)
    blk.create_var(name="cache_new", shape=shape, dtype="float32",
                   persistable=True)
    blk.append_op(
        "kv_cache_write",
        {"Cache": ["cache"], "X": [rows.name], "Pos": [pos.name]},
        {"Out": ["cache_new"]},
    )
    # the defect: 'cache' was donated to 'cache_new' one op ago
    blk.create_var(name="stale", shape=shape, dtype="float32")
    blk.append_op("scale", {"X": ["cache"]}, {"Out": ["stale"]},
                  {"scale": 2.0})
    return main, ("rows", "pos"), ("stale",)


def _broken_oom_fixture():
    """A program whose static peak cannot fit the deliberately tiny
    ``PADDLE_TPU_HBM_BYTES`` the CI stage pins: the memory planner must
    emit ``oom-risk``, which strict verify escalates to a refusal."""
    import paddle_tpu as fluid
    from paddle_tpu import layers

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [64, 1024])
        h = layers.fc(x, 1024, act="relu")
        out = layers.fc(h, 1024)
    return main, ("x",), (out.name,)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--all-models", action="store_true",
                    help="lint every bundled model")
    ap.add_argument("--model", action="append", default=[],
                    help="lint one model by name (repeatable)")
    ap.add_argument("--strict", action="store_true",
                    help="escalated warnings (redefinition) also fail")
    ap.add_argument("--verbose", action="store_true",
                    help="print INFO findings too")
    ap.add_argument("--broken-fixture", action="store_true",
                    help="lint the seeded broken program (must fail)")
    ap.add_argument("--broken-frozen-fixture", action="store_true",
                    help="lint a frozen program with a surviving "
                         "training op (must fail)")
    ap.add_argument("--broken-bucket-fixture", action="store_true",
                    help="lint a program whose ranks bucket the same "
                         "grad exchange differently (must fail)")
    ap.add_argument("--broken-donation-fixture", action="store_true",
                    help="lint a program that reads a donated KV cache "
                         "buffer (must fail)")
    ap.add_argument("--broken-oom-fixture", action="store_true",
                    help="lint a program over a tiny PADDLE_TPU_HBM_BYTES "
                         "budget (must fail under the strict escalation)")
    ap.add_argument("--cost", action="store_true",
                    help="print the Program.estimate() cost table per model")
    ap.add_argument("--memory", action="store_true",
                    help="print the static peak-HBM plan per model")
    ap.add_argument("--json", action="store_true",
                    help="emit one machine-readable JSON document instead "
                         "of the human report")
    args = ap.parse_args(argv)

    if (args.broken_fixture or args.broken_frozen_fixture
            or args.broken_bucket_fixture or args.broken_donation_fixture
            or args.broken_oom_fixture):
        from paddle_tpu.analysis import OOM_RISK, verify_program

        if args.broken_frozen_fixture:
            program, feeds, fetches = _broken_frozen_fixture()
        elif args.broken_bucket_fixture:
            program, feeds, fetches = _broken_bucket_fixture()
        elif args.broken_donation_fixture:
            program, feeds, fetches = _broken_donation_fixture()
        elif args.broken_oom_fixture:
            # the oom gate needs a budget to be over; CI pins a tiny one,
            # and a bare invocation gets the same default
            os.environ.setdefault("PADDLE_TPU_HBM_BYTES", "1m")
            program, feeds, fetches = _broken_oom_fixture()
        else:
            program, feeds, fetches = _broken_fixture()
        report = verify_program(program, feeds, fetches)
        if args.broken_oom_fixture:
            # oom-risk is a WARNING that strict escalates; require the
            # category itself so another escalation can't mask a regression
            failing = [f for f in report.strict_errors()
                       if f.category == OOM_RISK]
        else:
            failing = report.errors
        if args.json:
            import json

            print(json.dumps({
                "fixture": True,
                "failing": len(failing),
                "findings": [f.to_dict() for f in report.findings],
            }, indent=2, sort_keys=True))
        else:
            for f in report.findings:
                print("    " + f.format())
        if failing:
            if not args.json:
                print(f"broken fixture: {len(failing)} blocking "
                      "finding(s) found (exit 1, as CI expects)")
            return 1
        print("broken fixture: linter found NO blocking findings — the "
              "verifier regressed", file=sys.stderr)
        return 0

    from paddle_tpu.models import MODEL_BUILDERS

    names = list(MODEL_BUILDERS) if args.all_models else args.model
    if not names:
        ap.error("pass --all-models, --model NAME, or --broken-fixture")
    unknown = [n for n in names if n not in MODEL_BUILDERS]
    if unknown:
        ap.error(f"unknown models {unknown}; have {sorted(MODEL_BUILDERS)}")
    records = [] if args.json else None
    ok = True
    for n in names:
        ok = _lint_one(n, args.strict, args.verbose, cost=args.cost,
                       memory=args.memory, records=records) and ok
    if args.json:
        import json

        print(json.dumps(
            {"models": records, "strict": args.strict, "ok": ok},
            indent=2, sort_keys=True,
        ))
    else:
        print("lint:", "PASS" if ok else "FAIL",
              f"({len(names)} model(s), strict={args.strict})")
    return 0 if ok else 2


if __name__ == "__main__":
    sys.exit(main())
