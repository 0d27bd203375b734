#!/usr/bin/env python
"""Pretty-print a paddle_tpu observability snapshot.

Usage:
    python tools/stats_report.py SNAPSHOT.json [--require PREFIX ...]

SNAPSHOT.json is the file written by `paddle_tpu.observability.dump(path)`
(counters / gauges / histograms / span_count / tables). `--require PREFIX`
(repeatable) exits nonzero unless at least one metric name starts with
PREFIX — the CI guard that instrumentation did not silently go dead.
Per-op cost tables are the offline estimator's: `tools/perf_report.py`.
"""

from __future__ import annotations

import argparse
import json
import sys

_BARS = " ▁▂▃▄▅▆▇█"


def _sparkline(hist):
    """Non-cumulative bucket counts as a unicode mini-bar chart."""
    cum = [c for _, c in hist["buckets"]]
    per = [c - p for c, p in zip(cum, [0] + cum[:-1])]
    peak = max(per) if per and max(per) > 0 else 1
    return "".join(_BARS[round(c / peak * (len(_BARS) - 1))] for c in per)


def render(snap):
    lines = []
    counters = snap.get("counters", {})
    gauges = snap.get("gauges", {})
    hists = snap.get("histograms", {})
    tables = snap.get("tables", {})
    lines.append("==== paddle_tpu observability snapshot ====")
    if counters:
        lines.append(f"-- counters ({len(counters)}) --")
        width = max(len(n) for n in counters)
        for name in sorted(counters):
            lines.append(f"  {name:<{width}}  {counters[name]:>14}")
    if gauges:
        lines.append(f"-- gauges ({len(gauges)}) --")
        width = max(len(n) for n in gauges)
        for name in sorted(gauges):
            lines.append(f"  {name:<{width}}  {gauges[name]:>14.6g}")
    if hists:
        lines.append(f"-- histograms ({len(hists)}) --")
        for name in sorted(hists):
            h = hists[name]
            n = h["count"]
            mean = h["sum"] / n if n else 0.0
            lines.append(
                f"  {name}: count={n} sum={h['sum']:.6g} mean={mean:.6g} "
                f"min={h['min']} max={h['max']}  |{_sparkline(h)}|"
            )
    # two byte-counter generations share the table: the sharded-update
    # kinds record estimated ring WIRE bytes under
    # collective.bytes.<kind>_<precision>; the classic emitters record
    # raw per-shard PAYLOAD bytes under collective.<kind>.bytes — both
    # belong in one view or an allreduce leg reads as zero traffic
    payload = {
        n[len("collective.bytes."):] + " (wire)": c
        for n, c in counters.items() if n.startswith("collective.bytes.")
    }
    payload.update({
        n[len("collective."):-len(".bytes")] + " (payload)": c
        for n, c in counters.items()
        if n.startswith("collective.") and n.endswith(".bytes")
    })
    if payload:
        lines.append("-- collective bytes by kind --")
        width = max(len(n) for n in payload)
        for name in sorted(payload):
            lines.append(
                f"  {name:<{width}}  {payload[name] / 1e6:>10.3f} MB"
            )
    n_buckets = counters.get("collective.buckets", 0)
    if n_buckets:
        members = counters.get("collective.bucket_members", 0)
        lines.append("-- collective buckets --")
        lines.append(
            f"  {n_buckets} bucket(s), "
            f"{counters.get('collective.bucket_bytes', 0) / 1e6:.3f} "
            f"MB bucketed payload"
            + (f", {members} member grads" if members else "")
        )
    # checkpoint pipeline digest: the stage split (snapshot = the step
    # loop's only cost; publish = background), bandwidth, and the tiered
    # save mix — the numbers the async-checkpoint bench gates on
    snap_h, pub_h = hists.get("checkpoint.snapshot_latency"), hists.get(
        "checkpoint.publish_latency"
    )
    if snap_h or pub_h:
        lines.append("-- checkpoint pipeline --")

        def _mean_ms(h):
            return (h["sum"] / h["count"] * 1e3) if h and h["count"] else 0.0

        snap_ms, pub_ms = _mean_ms(snap_h), _mean_ms(pub_h)
        lines.append(
            f"  snapshot (on-loop) mean {snap_ms:.2f} ms | publish "
            f"(background) mean {pub_ms:.2f} ms"
            + (f" | off-loop ratio {pub_ms / snap_ms:.1f}x"
               if snap_ms > 0 else "")
        )
        bw = hists.get("checkpoint.save_bandwidth")
        if bw and bw["count"]:
            lines.append(
                f"  save bandwidth mean "
                f"{bw['sum'] / bw['count'] / 1e6:.1f} MB/s over "
                f"{bw['count']} publishes"
            )
        mix = {
            k: counters.get(f"checkpoint.{k}", 0)
            for k in ("full_saves", "delta_saves", "coalesced",
                      "cancelled", "publish_failures")
        }
        dropped = counters.get("checkpoint.delta_bytes_dropped", 0)
        lines.append(
            "  saves: " + " ".join(f"{k}={v}" for k, v in mix.items())
            + (f" delta_bytes_dropped={dropped / 1e6:.2f}MB"
               if dropped else "")
        )
    # serving fault-domain digest (r15): goodput vs shed/expired, the
    # brownout rung, and per-replica breaker states — the overload/
    # failover picture at a glance
    goodput = counters.get("serving.goodput", 0)
    shed = counters.get("serving.shed", 0)
    expired = counters.get("serving.expired", 0)
    breakers = {
        n[len("serving.breaker_state."):]: v
        for n, v in gauges.items()
        if n.startswith("serving.breaker_state.")
    }
    if goodput or shed or expired or breakers:
        lines.append("-- serving fault domain --")
        served = counters.get("serving.requests_served", 0)
        late = counters.get("serving.late_completions", 0)
        lines.append(
            f"  goodput {goodput} in-deadline of {served} served "
            f"({late} late) | expired {expired} | shed {shed} | "
            f"rejected {counters.get('serving.rejected', 0)}"
        )
        shed_by_class = {
            n[len("serving.shed_class."):]: c
            for n, c in counters.items()
            if n.startswith("serving.shed_class.")
        }
        if shed_by_class:
            lines.append(
                "  shed by class: " + " ".join(
                    f"{k}={v}" for k, v in sorted(shed_by_class.items())
                )
            )
        level = gauges.get("serving.brownout_level")
        if level is not None:
            lines.append(
                f"  brownout level {level:.0f} "
                f"(escalations={counters.get('serving.brownout_escalations', 0)}"
                f" recoveries={counters.get('serving.brownout_recoveries', 0)})"
            )
        if breakers:
            state_name = {0.0: "closed", 0.5: "half-open", 1.0: "open"}
            lines.append(
                "  breakers: " + " ".join(
                    f"{k}={state_name.get(v, v)}"
                    for k, v in sorted(breakers.items())
                )
                + f" | requeued {counters.get('serving.requeued', 0)}"
                + f" failovers {counters.get('serving.failovers', 0)}"
            )
    # live watcher digest: structured findings, newest last
    wf = (tables.get("watch.findings") or {}).get("findings") or []
    if wf:
        lines.append(f"-- watch findings ({len(wf)} recent) --")
        for f_ in wf[-8:]:
            detail = ", ".join(
                f"{k}={v}" for k, v in sorted(f_.get("detail", {}).items())
                if not isinstance(v, dict)
            )
            lines.append(
                f"  [{f_.get('severity', '?'):<7}] {f_.get('kind', '?')}: "
                f"{detail}"
            )
    # telemetry-plane digest (r16): journal liveness + flight dumps — a
    # frozen publishes counter in a fleet of live ranks IS the finding
    publishes = counters.get("telemetry.publishes", 0)
    dumps = counters.get("telemetry.flight_dumps", 0)
    if publishes or dumps:
        lines.append("-- telemetry plane --")
        lines.append(
            f"  {publishes} journal publishes, "
            f"{gauges.get('telemetry.journal_bytes', 0) / 1e3:.1f} KB "
            f"journaled, {counters.get('telemetry.rotations', 0)} "
            "rotation(s)"
        )
        triggers = {
            n[len("telemetry.flight_dumps."):]: c
            for n, c in counters.items()
            if n.startswith("telemetry.flight_dumps.")
        }
        if dumps:
            lines.append(
                f"  {dumps} flight-recorder dump(s): " + " ".join(
                    f"{k}={v}" for k, v in sorted(triggers.items())
                )
            )
    lines.append(f"span buffer: {snap.get('span_count', 0)} spans")
    if not (counters or gauges or hists):
        lines.append("(snapshot is empty — PADDLE_TPU_MONITOR=0, or nothing "
                     "instrumented ran)")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("snapshot", help="JSON file from observability.dump()")
    ap.add_argument(
        "--require", action="append", default=[], metavar="PREFIX",
        help="fail unless some metric name starts with PREFIX (repeatable)",
    )
    args = ap.parse_args(argv)
    with open(args.snapshot) as f:
        snap = json.load(f)
    print(render(snap))
    names = (
        list(snap.get("counters", {}))
        + list(snap.get("gauges", {}))
        + list(snap.get("histograms", {}))
        + list(snap.get("tables", {}))
    )
    missing = [
        p for p in args.require if not any(n.startswith(p) for n in names)
    ]
    if missing:
        print(f"MISSING required metric prefixes: {missing}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
