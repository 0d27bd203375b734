"""Multi-process launcher: `python -m paddle_tpu.distributed.launch ...`.

Reference: python/paddle/distributed/launch.py:193-227 — builds the cluster
model from --cluster_node_ips / PaddleCloud env, spawns one process per GPU
with PADDLE_TRAINER_ID / PADDLE_CURRENT_ENDPOINT / PADDLE_TRAINERS_NUM /
PADDLE_TRAINER_ENDPOINTS, and supervises the children
(utils.py watch_local_trainers: abort the pod when a child dies).

TPU-native changes:
  * the process unit is a HOST, not an accelerator: one JAX process drives
    all local chips, so --nproc_per_node defaults to 1 and exists mainly
    for localhost simulation (reference test_dist_base.py:506 pattern);
  * rank 0's endpoint doubles as the JAX coordination-service address
    (PADDLE_COORDINATOR), replacing the reference's gen_nccl_id RPC server;
  * when simulating several processes on one host, children are forced onto
    the CPU platform with gloo cross-process collectives — a real pod sets
    neither and each host claims its TPU chips.
"""

from __future__ import annotations

import argparse
import os
import random
import subprocess
import sys
import time

# full-jitter source for restart backoff: same-tick deaths draw
# independent delays instead of thundering back in lockstep (seedable in
# tests for determinism)
_restart_rng = random.Random()


def parse_args(argv=None):
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument("--cluster_node_ips", type=str, default="127.0.0.1",
                   help="comma-separated host ips")
    p.add_argument("--node_ip", type=str, default="127.0.0.1",
                   help="this host's ip")
    p.add_argument("--started_port", type=int, default=6170)
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="processes per host (1 for real TPU hosts; >1 "
                        "simulates a cluster on localhost over CPU)")
    p.add_argument("--simulate_cpu", action="store_true",
                   help="force children onto the CPU platform with gloo "
                        "collectives (localhost cluster simulation)")
    p.add_argument("--elastic", action="store_true",
                   help="restart dead children with bounded exponential "
                        "backoff instead of aborting the pod (rank 0 dying "
                        "still aborts: it owns the coordination service)")
    p.add_argument("--max_restarts", type=int, default=3,
                   help="per-rank restart budget under --elastic")
    p.add_argument("--restart_backoff", type=float, default=0.5,
                   help="base seconds for the restart backoff "
                        "(doubles per restart of that rank, capped at 10s, "
                        "full jitter so same-tick deaths respawn staggered)")
    p.add_argument("--heartbeat_dir", type=str, default=None,
                   help="directory of per-rank hb_rank{K} liveness files; "
                        "exported to children as PADDLE_HEARTBEAT_DIR so "
                        "TrainGuard/Heartbeat auto-beat once per step")
    p.add_argument("--heartbeat_timeout", type=float, default=0.0,
                   help="seconds without a heartbeat before a child is "
                        "declared HUNG and SIGTERM→SIGKILLed (then routed "
                        "through the --elastic restart path); 0 disables")
    p.add_argument("--log_dir", type=str, default=None)
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def build_cluster(args):
    ips = [ip for ip in args.cluster_node_ips.split(",") if ip]
    endpoints = []
    for ip in ips:
        for i in range(args.nproc_per_node):
            endpoints.append(f"{ip}:{args.started_port + i}")
    if args.node_ip not in ips:
        raise ValueError(
            f"--node_ip {args.node_ip} not in --cluster_node_ips {ips}"
        )
    node_idx = ips.index(args.node_ip)
    local_ranks = [
        node_idx * args.nproc_per_node + i for i in range(args.nproc_per_node)
    ]
    return endpoints, local_ranks


def _terminate_pod(procs, grace=10.0):
    """SIGTERM everyone, reap with a deadline, escalate to SIGKILL — a child
    blocked in a native collective often defers SIGTERM forever and would
    otherwise be orphaned holding its port. (Implementation shared with the
    serving process fleet: resilience/supervisor.py.)"""
    from ..resilience.supervisor import terminate_children

    terminate_children(procs, grace=grace)


def spawn_trainer(args, endpoints, rank, attempt=0):
    """Start (or restart) the trainer process for `rank`. Restarts append
    to the same per-rank log file so the crash that triggered the restart
    stays readable."""
    env = dict(os.environ)
    env.update(
        PADDLE_TRAINER_ID=str(rank),
        PADDLE_TRAINERS_NUM=str(len(endpoints)),
        PADDLE_TRAINER_ENDPOINTS=",".join(endpoints),
        PADDLE_CURRENT_ENDPOINT=endpoints[rank],
        PADDLE_COORDINATOR=endpoints[0],
        PADDLE_RESTART_ATTEMPT=str(attempt),
    )
    if args.simulate_cpu:
        env["JAX_PLATFORMS"] = "cpu"
        env["JAX_CPU_COLLECTIVES_IMPLEMENTATION"] = "gloo"
    if getattr(args, "heartbeat_dir", None):
        env["PADDLE_HEARTBEAT_DIR"] = args.heartbeat_dir
        if getattr(args, "heartbeat_timeout", 0):
            env["PADDLE_HEARTBEAT_TIMEOUT"] = str(args.heartbeat_timeout)
    cmd = [sys.executable, args.training_script] + args.training_script_args
    # fresh spawn truncates; a restart appends so the crash that triggered
    # it stays readable in the same per-rank log
    out = (
        open(
            os.path.join(args.log_dir, f"worker_{rank}.log"),
            "w" if attempt == 0 else "a",
        )
        if args.log_dir
        else None
    )
    proc = subprocess.Popen(cmd, env=env, stdout=out, stderr=out)
    proc._paddle_log = out
    proc._paddle_rank = rank
    # wall clock: heartbeat staleness compares against beat files written
    # by another process, and a fresh spawn must reset the stall baseline
    # even when a pre-kill beat file is still lying around
    proc._paddle_spawned = time.time()
    return proc


def start_local_trainers(args, endpoints, local_ranks):
    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)
    if getattr(args, "heartbeat_dir", None):
        os.makedirs(args.heartbeat_dir, exist_ok=True)
    return [spawn_trainer(args, endpoints, rank) for rank in local_ranks]


def _beat_staleness(args, proc, now_wall):
    """Seconds since `proc`'s rank last proved liveness: its newest beat
    file if one postdates the spawn, else the spawn itself (a rank hung
    BEFORE its first beat — e.g. a stuck init collective — must still
    trip the watchdog; size --heartbeat_timeout above worst-case
    compile+warmup)."""
    from ..resilience.health import heartbeat_path, read_beat

    ref = getattr(proc, "_paddle_spawned", now_wall)
    beat = read_beat(
        heartbeat_path(args.heartbeat_dir, getattr(proc, "_paddle_rank", 0))
    )
    if beat is not None:
        try:
            ref = max(ref, float(beat.get("time", ref)))
        except (TypeError, ValueError):
            pass
    return now_wall - ref


def _kill_hung(proc, grace=5.0):
    """SIGTERM a hung child, escalating to SIGKILL after `grace` without
    blocking the supervision scan (shared: resilience/supervisor.py)."""
    from ..resilience.supervisor import kill_hung

    kill_hung(proc, grace=grace)


def watch_local_trainers(procs, args=None, endpoints=None):
    """Supervise the pod (reference utils.py watch_local_trainers /
    launch.py:219-226). Default policy: any child failure aborts the pod.
    Under ``--elastic``: a failed non-rank-0 child is restarted with
    bounded, full-jittered exponential backoff up to ``--max_restarts``
    times per rank — each dead rank gets its own independent deadline, so
    two ranks dying in the same poll tick neither share a slot nor
    respawn in lockstep. Rank 0 dying always aborts immediately (it hosts
    the JAX coordination service, so its death already doomed every peer).

    Liveness: with ``--heartbeat_dir``/``--heartbeat_timeout`` a child
    whose newest beat (or spawn, if it never beat) is older than the
    timeout is declared HUNG, SIGTERM→SIGKILLed (``resilience.hangs``),
    and its eventual death is handled exactly like a crash — i.e. routed
    through the elastic restart path.

    Preemption: a child exiting with the distinguished
    ``PREEMPTION_EXIT_CODE`` (it drained after SIGTERM and wrote a final
    checkpoint) is a CLEAN exit — no pod abort, no restart-budget burn —
    unless the launcher itself killed it as hung.

    The scan/backoff/stale-beat loop itself lives in
    ``resilience.supervisor.Supervisor`` (shared with the serving process
    fleet); this function contributes the launcher policy — rank 0 and
    non-elastic deaths abort the pod, preemption exits are clean, and the
    historical log lines/counters stay byte-identical."""
    from ..resilience.health import PREEMPTION_EXIT_CODE
    from ..resilience.supervisor import Supervisor

    elastic = bool(args and getattr(args, "elastic", False))
    max_restarts = getattr(args, "max_restarts", 3) if args else 3
    backoff_base = getattr(args, "restart_backoff", 0.5) if args else 0.5
    hb_timeout = float(getattr(args, "heartbeat_timeout", 0) or 0) if args else 0
    hb_dir = getattr(args, "heartbeat_dir", None) if args else None
    watch_beats = bool(hb_dir and hb_timeout > 0)
    ranks = {i: getattr(p, "_paddle_rank", i) for i, p in enumerate(procs)}
    sup = Supervisor(
        # late-bound module lookup: tests monkeypatch launch.spawn_trainer
        # to steer restarts, and that must keep working
        spawn=lambda i, attempt: spawn_trainer(
            args, endpoints, ranks[i], attempt
        ),
        max_restarts=max_restarts,
        backoff_base=backoff_base,
        backoff_cap=10.0,
        staleness=(
            (lambda p, now_wall: _beat_staleness(args, p, now_wall))
            if watch_beats else None
        ),
        stale_after=hb_timeout if watch_beats else 0.0,
        clean_exit=lambda rc, hung: (
            rc == 0 or (rc == PREEMPTION_EXIT_CODE and not hung)
        ),
        restartable=lambda i, rc, hung: elastic and ranks[i] != 0,
        rng=_restart_rng,
    )
    for i, p in enumerate(procs):
        sup.adopt(i, p)
    try:
        while True:
            for ev in sup.poll():
                i, p, kind = ev["key"], ev["proc"], ev["kind"]
                rank = ranks[i]
                if kind == "hung":
                    print(
                        f"[launch] rank {rank} (pid {p.pid}) hung: "
                        f"no heartbeat in {hb_timeout}s; killing",
                        file=sys.stderr,
                    )
                    from .. import observability as _obs

                    _obs.add("resilience.hangs")
                    _obs.add("resilience.hangs.launcher")
                elif kind == "respawned":
                    # mirror into the caller's list: _terminate_pod on a
                    # later abort must see the live child, not the corpse
                    procs[i] = p
                elif kind == "restart_scheduled":
                    print(
                        f"[launch --elastic] rank {rank} "
                        + ("hung (killed)" if ev["hung"]
                           else f"died (rc={ev['rc']})")
                        + f"; restart {ev['attempt']}/{max_restarts} "
                        f"in {ev['delay']:.1f}s",
                        file=sys.stderr,
                    )
                elif kind == "fatal":
                    n = ev["restarts"]
                    _terminate_pod(procs)
                    raise RuntimeError(
                        f"trainer rank {rank} (pid {p.pid}) "
                        + ("hung (heartbeat stale) and was killed, exit "
                           if ev["hung"] else "exited with ")
                        + f"code {ev['rc']}"
                        + (f" after {n} restart(s)" if elastic and n else "")
                        + "; pod aborted"
                    )
            if not sup.some_active():
                _terminate_pod(procs)  # reaps + closes log handles
                return 0
            time.sleep(0.2)
    except KeyboardInterrupt:
        _terminate_pod(procs)
        raise


def launch(argv=None):
    args = parse_args(argv)
    endpoints, local_ranks = build_cluster(args)
    procs = start_local_trainers(args, endpoints, local_ranks)
    return watch_local_trainers(procs, args, endpoints)


if __name__ == "__main__":
    sys.exit(launch())
