#!/usr/bin/env python
"""Per-op cost attribution + multi-rank timeline reports.

Three report modes over the analysis/cost.py IR cost model:

1. Cost tables — top-K op sites by roofline latency for any zoo model:
       python tools/perf_report.py --model bert --top-ops 15

2. Estimate-vs-XLA cross-check — `Program.estimate()` total FLOPs against
   the compiled executable's own `cost_analysis()` (Executor.flops; lower
   + compile only, never executes a step). The CI stage:
       python tools/perf_report.py --all-models --check-divergence \\
           --max-divergence 0.25 --allow-divergent 1
   exits non-zero when more than `--allow-divergent` models diverge past
   the threshold (divergences are always REPORTED, never hidden). Meshed
   models (bert_3d) are estimate-only: their shard_map executable wants
   the whole virtual pod stepping together. `--check-memory` runs the
   same cross-check for the static peak-HBM plan (analysis/memory.py)
   against XLA `memory_analysis` (arg+out+temp-alias), with its own
   `--allow-memory-divergent` budget: peak estimation carries fusion and
   scheduling error the FLOP count does not.

3. Merged pod timeline — fuse per-rank Chrome span exports
   (`observability.save_chrome_trace`, one file per rank) and optional
   heartbeat files (resilience/health.py `{dir}/hb_rank{K}`) into ONE
   chrome://tracing-loadable JSON, with per-rank step alignment stats:
       python tools/perf_report.py --merge r0.json r1.json \\
           --heartbeat-dir /ckpt/hb -o pod_trace.json
   Prints per-step skew (spread of "executor.step" end times across
   ranks, mean/max), the straggler gap (how far the last finisher trails
   the second-to-last), and which rank finishes last most often.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

# runnable as `python tools/perf_report.py` from anywhere
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# ---------------------------------------------------------------------------
# cost tables + estimate-vs-XLA
# ---------------------------------------------------------------------------


def _synthetic_feed(bm, batch_hint=4):
    """Random arrays matching the model's declared feed specs. Safe even
    for structured inputs (boxes, ids): the XLA check only lowers and
    compiles — no step ever executes on this data."""
    import numpy as np

    from paddle_tpu.core.dtypes import to_numpy_dtype

    rng = np.random.RandomState(0)
    feed = {}
    blk = bm.main.global_block
    for n in bm.feed_names:
        v = blk.var(n)
        shape = tuple(
            int(d) if d not in (-1, None) else batch_hint for d in v.shape
        )
        dt = np.dtype(to_numpy_dtype(v.dtype or "float32"))
        if np.issubdtype(dt, np.integer):
            feed[n] = rng.randint(0, 3, shape).astype(dt)
        else:
            feed[n] = rng.rand(*shape).astype(dt)
    return feed


def report_model(name, top_ops, check_divergence, max_divergence,
                 check_memory=False):
    """Print the model's report; return ``(flops_div, mem_div)`` where
    each is the measured divergence past ``max_divergence`` or None when
    the check passed / was skipped / was not requested."""
    import paddle_tpu as fluid
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.models import build_model

    bm = build_model(name)
    feed = _synthetic_feed(bm)
    est = bm.main.estimate(
        feed_shapes={k: v.shape for k, v in feed.items()}
    )
    print(f"==== {name} ====")
    print(est.format(top=top_ops))
    if not (check_divergence or check_memory):
        return None, None
    if getattr(bm.main, "_mesh", None) is not None:
        print(f"  [skip] {name}: meshed program — estimate-only "
              "(shard_map executable needs the whole pod)")
        return None, None
    exe = fluid.Executor()
    scope = Scope()
    exe.run(bm.startup, scope=scope)
    flops_div = mem_div = None
    if check_divergence:
        xla = exe.flops(
            bm.main, feed=feed, fetch_list=list(bm.fetch_names), scope=scope
        )
        if not xla:
            print(f"  [skip] {name}: XLA cost_analysis reported no "
                  "FLOP data")
        else:
            div = abs(est.total_flops - xla) / xla
            verdict = "ok" if div <= max_divergence else "DIVERGENT"
            print(
                f"  estimate {est.total_flops / 1e6:.3f}M vs XLA "
                f"{xla / 1e6:.3f}M FLOPs -> divergence {div:.1%} "
                f"[{verdict}]"
            )
            if div > max_divergence:
                flops_div = div
    if check_memory:
        ma = exe.memory_analysis(
            bm.main, feed=feed, fetch_list=list(bm.fetch_names), scope=scope
        )
        if ma is None or est.peak_bytes is None:
            print(f"  [skip] {name}: XLA memory_analysis unavailable")
        else:
            xla_peak = ma["peak_bytes"]
            div = abs(est.peak_bytes - xla_peak) / max(xla_peak, 1.0)
            verdict = "ok" if div <= max_divergence else "DIVERGENT"
            print(
                f"  peak-HBM estimate {est.peak_bytes / 2**20:.2f} MiB "
                f"vs XLA {xla_peak / 2**20:.2f} MiB (arg+out+temp-alias) "
                f"-> divergence {div:.1%} [{verdict}]"
            )
            if div > max_divergence:
                mem_div = div
    return flops_div, mem_div


# ---------------------------------------------------------------------------
# multi-rank timeline merge
# ---------------------------------------------------------------------------

_RANK_RE = re.compile(r"rank[_-]?(\d+)")


def _rank_of(path, position):
    m = _RANK_RE.search(os.path.basename(path))
    return int(m.group(1)) if m else position


def _step_spans(events):
    """Per-rank "executor.step" spans ordered by start time."""
    steps = [
        e for e in events
        if e.get("ph") == "X" and e.get("name") == "executor.step"
    ]
    return sorted(steps, key=lambda e: e["ts"])


def merge_traces(paths, heartbeat_dir=None):
    """Merge per-rank Chrome span exports into one trace dict + skew stats.

    Each input is one rank's `observability.save_chrome_trace` output
    (wall-clock ts in epoch microseconds, so ranks on a shared clock
    align). Rank K's events move to pid K; heartbeat beats (if a dir is
    given) land as instant events on the matching rank row.
    """
    merged = []
    per_rank_steps = {}
    trace_ranks = {}  # trace_id -> set of ranks that recorded it
    # two passes over the rank ids: collisions (same basename copied into
    # per-host dirs) remap to ids NO input declares, so a duplicate never
    # steals a later file's genuine rank
    declared = [_rank_of(p, i) for i, p in enumerate(paths)]
    ranks_assigned, used = [], set()
    for path, rank in zip(paths, declared):
        if rank in used:
            free = 0
            while free in used or free in declared:
                free += 1
            print(
                f"WARNING: {path} resolves to rank {rank}, already taken "
                f"— remapping to rank {free}",
                file=sys.stderr,
            )
            rank = free
        used.add(rank)
        ranks_assigned.append(rank)
    for rank, path in zip(ranks_assigned, paths):
        with open(path) as f:
            trace = json.load(f)
        events = trace.get("traceEvents", trace)
        merged.append({
            "name": "process_name", "ph": "M", "pid": rank,
            "args": {"name": f"rank {rank}"},
        })
        tid_seen = set()
        for e in events:
            if e.get("ph") == "M":
                if e.get("name") == "thread_name" \
                        and e.get("tid") not in tid_seen:
                    tid_seen.add(e.get("tid"))
                    merged.append({**e, "pid": rank})
                continue
            tr = (e.get("args") or {}).get("trace_id")
            if tr:
                trace_ranks.setdefault(tr, set()).add(rank)
            merged.append({**e, "pid": rank})
        per_rank_steps[rank] = _step_spans(
            [e for e in events if e.get("ph") == "X"]
        )
    if heartbeat_dir:
        for fn in sorted(os.listdir(heartbeat_dir)):
            if not fn.startswith("hb_rank") or ".tmp." in fn:
                continue
            # inlined resilience/health.py::read_beat (torn/missing beat
            # -> skip) so the merge path stays import-light: a login host
            # without jax must still merge copied rank artifacts
            try:
                with open(os.path.join(heartbeat_dir, fn)) as f:
                    beat = json.load(f)
            except (OSError, ValueError):
                continue
            if not isinstance(beat, dict):
                continue
            # beats carry the beating step's trace stamp (health.py):
            # the cross-RANK stitch — a trace whose spans live on one
            # rank and whose beat lands on another is one causal timeline
            if beat.get("trace_id"):
                trace_ranks.setdefault(beat["trace_id"], set()).add(
                    int(beat.get("rank", 0))
                )
            merged.append({
                "ph": "I", "s": "p", "pid": int(beat.get("rank", 0)),
                "tid": 0, "name": f"heartbeat step {beat.get('step')}",
                "ts": float(beat.get("time", 0.0)) * 1e6, "cat": "health",
                "args": dict(beat),
            })
    stats = _skew_stats(per_rank_steps)
    stats["traced_trace_ids"] = len(trace_ranks)
    stats["cross_rank_traces"] = sum(
        1 for ranks_ in trace_ranks.values() if len(ranks_) > 1
    )
    return {"traceEvents": merged}, stats


def _skew_stats(per_rank_steps):
    """Step-alignment stats across ranks: for step k, skew = spread of
    the ranks' step-END times (first vs last finisher), straggler gap =
    how far the LAST finisher trails the second-to-last (the pod-wide
    stall one slow rank alone causes — with 2 ranks the two coincide);
    the straggler is the rank that finishes last most often."""
    ranks = sorted(per_rank_steps)
    counts = {r: len(per_rank_steps[r]) for r in ranks}
    n_steps = min(counts.values()) if counts else 0
    # align the TRAILING n steps of every rank: the span ring buffer keeps
    # the most recent spans, so when counts differ it is the OLDEST steps a
    # longer rank dropped — leading-index pairing would compare unrelated
    # steps. A mismatch is still flagged: trailing alignment is a guess.
    tails = {r: per_rank_steps[r][-n_steps:] for r in ranks}
    skews, gaps, last_finisher = [], [], {}
    for k in range(n_steps):
        ends = {
            r: tails[r][k]["ts"] + tails[r][k]["dur"]
            for r in ranks
        }
        ordered = sorted(ends.values())
        skews.append(ordered[-1] - ordered[0])
        gaps.append(ordered[-1] - ordered[-2] if len(ordered) > 1 else 0.0)
        lag = max(ends, key=ends.get)
        last_finisher[lag] = last_finisher.get(lag, 0) + 1
    straggler = (
        max(last_finisher, key=last_finisher.get) if last_finisher else None
    )
    return {
        "ranks": ranks,
        "steps_per_rank": counts,
        "aligned_steps": n_steps,
        "count_mismatch": len(set(counts.values())) > 1,
        "step_skew_us": {
            "mean": sum(skews) / len(skews) if skews else 0.0,
            "max": max(skews) if skews else 0.0,
        },
        "straggler_gap_us": sum(gaps) / len(gaps) if gaps else 0.0,
        "straggler_rank": straggler,
        "straggler_last_finishes": last_finisher,
    }


def _print_merge_stats(stats):
    print(
        f"merged {len(stats['ranks'])} rank(s) "
        f"{stats['steps_per_rank']} -> {stats['aligned_steps']} aligned "
        "step(s)"
    )
    if stats.get("count_mismatch"):
        print(
            "WARNING: ranks recorded different step counts — stats pair "
            "the trailing steps of each rank and may misalign",
            file=sys.stderr,
        )
    sk = stats["step_skew_us"]
    print(
        f"step skew: mean {sk['mean']:.1f} us, max {sk['max']:.1f} us; "
        f"straggler gap {stats['straggler_gap_us']:.1f} us"
        + (
            f" (rank {stats['straggler_rank']} finishes last "
            f"{stats['straggler_last_finishes'][stats['straggler_rank']]}x)"
            if stats["straggler_rank"] is not None else ""
        )
    )


# ---------------------------------------------------------------------------


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--model", action="append", default=[],
                    help="zoo model to report on (repeatable)")
    ap.add_argument("--all-models", action="store_true",
                    help="report on every bundled model")
    ap.add_argument("--top-ops", type=int, default=10, metavar="N",
                    help="op sites to show per model (default 10)")
    ap.add_argument("--check-divergence", action="store_true",
                    help="cross-check estimate vs XLA cost_analysis")
    ap.add_argument("--max-divergence", type=float, default=0.25,
                    help="allowed |est-xla|/xla per model (default 0.25)")
    ap.add_argument("--allow-divergent", type=int, default=1,
                    help="models allowed past the threshold before the "
                         "exit status fails (default 1)")
    ap.add_argument("--check-memory", action="store_true",
                    help="cross-check the static peak-HBM estimate vs "
                         "XLA memory_analysis (arg+out+temp-alias)")
    ap.add_argument("--allow-memory-divergent", type=int, default=2,
                    help="models allowed past the memory threshold "
                         "before the exit status fails (default 2: the "
                         "planner does not model cross-op fusion or "
                         "XLA's scheduling freedom)")
    ap.add_argument("--merge", nargs="+", metavar="TRACE.json",
                    help="merge per-rank chrome span exports")
    ap.add_argument("--heartbeat-dir", metavar="DIR",
                    help="fold hb_rank* beats into the merged trace")
    ap.add_argument("-o", "--out", metavar="PATH",
                    help="write the merged trace JSON here")
    args = ap.parse_args(argv)

    if args.merge:
        trace, stats = merge_traces(args.merge, args.heartbeat_dir)
        _print_merge_stats(stats)
        if args.out:
            with open(args.out, "w") as f:
                json.dump(trace, f)
            print(f"merged trace -> {args.out}")
        print(json.dumps(stats))
        return 0

    # model reports need jax; the merge path above stays import-light so
    # it can run on a login host against copied rank artifacts
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")

    from paddle_tpu.models import MODEL_BUILDERS

    names = list(MODEL_BUILDERS) if args.all_models else args.model
    if not names:
        ap.error("pass --model NAME, --all-models, or --merge TRACES...")
    unknown = [n for n in names if n not in MODEL_BUILDERS]
    if unknown:
        ap.error(f"unknown models {unknown}; have {sorted(MODEL_BUILDERS)}")
    divergent, mem_divergent = [], []
    for n in names:
        flops_div, mem_div = report_model(
            n, args.top_ops, args.check_divergence, args.max_divergence,
            check_memory=args.check_memory,
        )
        if flops_div is not None:
            divergent.append((n, flops_div))
        if mem_div is not None:
            mem_divergent.append((n, mem_div))
    status = 0
    if args.check_divergence:
        print(
            f"divergence check: {len(names) - len(divergent)}/{len(names)} "
            f"within {args.max_divergence:.0%}"
            + (f"; divergent: {divergent}" if divergent else "")
        )
        if len(divergent) > args.allow_divergent:
            status = 2
    if args.check_memory:
        # a separate budget from the flops gate: peak estimation carries
        # fusion/scheduling error the FLOP count does not
        print(
            f"memory check: {len(names) - len(mem_divergent)}/{len(names)} "
            f"within {args.max_divergence:.0%}"
            + (f"; divergent: {mem_divergent}" if mem_divergent else "")
        )
        if len(mem_divergent) > args.allow_memory_divergent:
            status = 2
    return status


if __name__ == "__main__":
    sys.exit(main())
