#!/usr/bin/env bash
# CI driver (reference paddle/scripts/paddle_build.sh role, reduced to what
# a pure-Python+JAX framework needs): unit tests on the 8-virtual-device
# CPU mesh, the smoke and chaos stages below, the driver entry compile
# checks, and the op-surface report. A speed comes from benchmark/run.py
# on the chip, never from here.
set -euo pipefail
cd "$(dirname "$0")"

echo "== pytest (8 virtual CPU devices via tests/conftest.py) =="
# includes the batched-detection golden-parity suite
# (tests/test_detection_batched.py, CPU-sized; its >25s model-level
# loss-parity case is @slow so tier-1 'not slow' runs stay in budget —
# it still runs here)
# test_zoo_estimate_vs_xla deselected HERE only: the perf-report stage
# below runs the identical build+compile+cost_analysis over the whole zoo
# as the CI divergence gate — running both would double multi-minute XLA
# compile work (tier-1 'not slow' runs never included it)
python -m pytest tests/ -q \
    --deselect tests/test_cost_model.py::test_zoo_estimate_vs_xla \
    --deselect tests/test_memory_analysis.py::test_zoo_estimate_vs_xla_memory

echo "== program lint (static verifier over every bundled model) =="
# every bundled model must build and verify with ZERO error findings
# (strict also escalates silent-redefinition warnings); --all-models
# includes the r6 batched mask_rcnn graph (zoo: mask_rcnn_batched),
# which replays the batched detection-op infer_shapes signatures
python tools/program_lint.py --all-models --strict --memory
# ...and the linter itself must still catch a seeded broken program
# (use-before-def + shape desync + rank-divergent collective => exit 1)
if python tools/program_lint.py --broken-fixture > /dev/null 2>&1; then
    echo "program_lint failed to reject the seeded broken fixture" >&2
    exit 1
fi
# memory family regressions: a read of a donated KV cache buffer, and a
# program over a deliberately tiny PADDLE_TPU_HBM_BYTES budget (the
# strict oom-risk escalation) must both exit non-zero
if python tools/program_lint.py --broken-donation-fixture > /dev/null 2>&1; then
    echo "program_lint failed to reject the use-after-donate fixture" >&2
    exit 1
fi
if python tools/program_lint.py --broken-oom-fixture > /dev/null 2>&1; then
    echo "program_lint failed to reject the over-budget oom fixture" >&2
    exit 1
fi

# ...and the collective-schedule lint must reject a rank-divergent
# bucketing (bucket membership is part of the cross-rank wire contract)
if python tools/program_lint.py --broken-bucket-fixture > /dev/null 2>&1; then
    echo "program_lint failed to reject the rank-divergent bucket fixture" >&2
    exit 1
fi

echo "== embedding engine smoke: fused lookup + cache tier + prefetch =="
# fused-vs-per-slot op reduction, batch dedup, hot-tier capacity beyond
# the device-resident rows (cold host path, eviction+write-back), async
# prefetch overlap, and BITWISE cache-vs-full-table parity — the tool
# self-gates and its snapshot must carry the embedding.* telemetry
EMBED_DIR=$(mktemp -d)
python tools/bench_embedding.py --smoke \
    --dump "$EMBED_DIR/embedding_stats.json"
python tools/stats_report.py "$EMBED_DIR/embedding_stats.json" \
    --require embedding.cache_ --require embedding.hot_hit_rate \
    --require embedding.prefetch_overlap \
    --require embedding.unique_ids_per_batch \
    --require embedding.host_fetch_latency
rm -rf "$EMBED_DIR"
# checkpoints carrying cached (host-cold/device-hot) and ps-sharded
# tables must resume bitwise (Momentum state tiers included)
python tools/resume_audit.py --embedding

echo "== async checkpoint bench: save stall off the step loop =="
# sync-vs-async save-step jitter (gate >= 10x reduction: the step loop
# pays only the device->host snapshot) and delta shards on the
# embedding-cached model (gate: repeat-save dir <= 60% of the full save,
# row deltas keyed off the cache's write-back ticks, compressed, chain
# reload bitwise); the snapshot must carry the checkpoint.* telemetry
ACK_DIR=$(mktemp -d)
python tools/bench_async_checkpoint.py --smoke \
    --dump "$ACK_DIR/async_ck_stats.json"
python tools/stats_report.py "$ACK_DIR/async_ck_stats.json" \
    --require checkpoint. \
    --require checkpoint.snapshot_latency \
    --require checkpoint.publish_latency \
    --require checkpoint.save_bandwidth --require checkpoint.pending \
    --require checkpoint.delta_saves
rm -rf "$ACK_DIR"

echo "== async checkpoint chaos: injected snapshot + publish faults heal =="
# one fault on each new seam: the snapshot retries on the step loop, the
# publish retries on the publisher thread — the save must still commit a
# loadable checkpoint and the retry counters must show the healing
PADDLE_TPU_FAULT_INJECT="checkpoint.snapshot:io:1.0:0:1,checkpoint.publish:io:1.0:0:1" \
python - <<'EOF'
import shutil

import numpy as np
import paddle_tpu as fluid
from paddle_tpu import layers, observability
from paddle_tpu.fleet import collective as fc
from paddle_tpu.fleet.role_maker import UserDefinedRoleMaker

shutil.rmtree("/tmp/paddle_tpu_async_chaos_ckpt", ignore_errors=True)
x = fluid.data("x", [-1, 4])
y = fluid.data("y", [-1, 1])
pred = layers.fc(x, 1)
loss = layers.mean(layers.square_error_cost(pred, y))
fluid.optimizer.SGD(0.05).minimize(loss)
exe = fluid.Executor()
exe.run(fluid.default_startup_program())
fleet = fc.Fleet()
fleet.init(UserDefinedRoleMaker())
rng = np.random.RandomState(0)
with fc.AsyncCheckpointer(fleet, "/tmp/paddle_tpu_async_chaos_ckpt",
                          executor=exe, delta=True, full_every=2) as saver:
    for i in range(3):
        xa = rng.randn(8, 4).astype(np.float32)
        exe.run(feed={"x": xa, "y": xa @ np.ones((4, 1), np.float32)},
                fetch_list=[loss])
        saver.save(fc.TrainStatus(i, global_step=i + 1)).result(timeout=60)
status = fleet.load_check_point(exe, "/tmp/paddle_tpu_async_chaos_ckpt")
assert status.global_step == 3, status
c = observability.snapshot()["counters"]
assert c.get("resilience.faults_injected.checkpoint.snapshot", 0) == 1, c
assert c.get("resilience.faults_injected.checkpoint.publish", 0) == 1, c
assert c.get("resilience.retries.checkpoint.snapshot", 0) >= 1, c
assert c.get("resilience.retries.checkpoint.save", 0) >= 1, c
assert c.get("checkpoint.publish_failures", 0) == 0, c
print(f"async checkpoint chaos OK: snapshot+publish faults healed "
      f"({c['resilience.retries']} retries), "
      f"{c.get('checkpoint.delta_saves', 0)} delta links committed, "
      "resume lands on step 3")
EOF

echo "== serving smoke (load gen + chaos ingest + drain) =="
# short load-gen run over all three traffic mixes with a fault injected
# on the request-ingestion seam (dataloader.fetch-style): the router's
# retry policy must heal the two injected failures with zero dropped
# requests, the serving.* stats must land in the snapshot, and the
# acceptance ratios (batched >= 3x, KV decode >= 5x) gate the exit code
SERVING_DIR=$(mktemp -d)
PADDLE_TPU_FAULT_INJECT="serving.ingest:io:1.0:0:2" \
python bench_serving.py --smoke --dump "$SERVING_DIR/serving_stats.json"
python tools/stats_report.py "$SERVING_DIR/serving_stats.json" \
    --require serving. --require executor.
python - "$SERVING_DIR" <<'EOF'
import json, sys
snap = json.load(open(sys.argv[1] + "/serving_stats.json"))
c = snap["counters"]
assert c.get("resilience.faults_injected", 0) >= 2, c
assert c.get("resilience.retries", 0) >= 2, (
    "injected ingest faults were not retried", c)
assert c.get("serving.requests_served", 0) > 0, c
assert c.get("serving.batches", 0) > 0, c
assert c.get("serving.warmup_runs", 0) > 0, c
h = snap["histograms"]
assert h["serving.request_latency"]["count"] > 0, h.keys()
assert h["serving.batch_fill"]["count"] > 0, h.keys()
print(f"serving chaos OK: {c['serving.requests_served']} requests served "
      f"across {c['serving.batches']} batches, "
      f"{c['resilience.retries']} ingest retries healed")
EOF

# SIGTERM during serving load: every admitted request completes, the
# worker exits PREEMPTION_EXIT_CODE (75), serving.drained fires once
JAX_PLATFORMS=cpu python tests/serving_drain_worker.py "$SERVING_DIR" \
    > "$SERVING_DIR/drain.log" 2>&1 &
SPID=$!
for _ in $(seq 600); do
    [ -f "$SERVING_DIR/ready" ] && break
    kill -0 "$SPID" 2>/dev/null || { cat "$SERVING_DIR/drain.log"; exit 1; }
    sleep 0.2
done
[ -f "$SERVING_DIR/ready" ] || { echo "serving worker never ready"; exit 1; }
sleep 0.5  # let load build up before preempting
kill -TERM "$SPID"
rc=0; wait "$SPID" || rc=$?
[ "$rc" -eq 75 ] || {
    echo "expected serving drain exit 75, got $rc"
    cat "$SERVING_DIR/drain.log"; exit 1
}
python - "$SERVING_DIR" <<'EOF'
import json, sys
r = json.load(open(sys.argv[1] + "/result.json"))
assert r["dropped"] == 0, r
# every admitted request RESOLVED: served, or typed expired/shed for the
# deadline/priority slice (the r15 fault-domain drain contract)
assert r["served"] + r["expired"] + r["shed"] == r["admitted"], r
assert r["served"] > 0 and r["admitted"] > 0, r
assert r["drained_counter"] == 1, r
print(f"serving drain OK: {r['served']} served + {r['expired']} expired "
      f"+ {r['shed']} shed = {r['admitted']} admitted under SIGTERM, "
      "exit 75")
EOF
rm -rf "$SERVING_DIR"

echo "== serving chaos (fault domain: replica kill + overload goodput) =="
# leg 1 — replica failover under chaos: 3-replica set, one replica killed
# mid-run via its per-replica dispatch seam, PLUS an env-armed
# serving.dispatch:hang (a wedged executable the attempt timeout must
# bound). bench gates: every admitted request resolves (zero hangs), the
# killed replica's breaker opens, post-failover QPS within 20% of
# pre-kill. stats_report proves the breaker/requeue telemetry was alive.
FD_DIR=$(mktemp -d)
PADDLE_TPU_FAULT_INJECT="serving.dispatch:hang:1.0:0:1" \
PADDLE_TPU_FAULT_HANG_SECONDS=6 \
python bench_serving.py --smoke --mix failover \
    --dump "$FD_DIR/failover_stats.json"
python tools/stats_report.py "$FD_DIR/failover_stats.json" \
    --require serving.breaker --require serving.requeued \
    --require serving.dispatch_failures
python - "$FD_DIR" <<'EOF'
import json, sys
snap = json.load(open(sys.argv[1] + "/failover_stats.json"))
c, g = snap["counters"], snap["gauges"]
assert c.get("resilience.faults_injected.serving.dispatch", 0) == 1, (
    "the env-armed dispatch hang never fired", c)
assert c.get("serving.breaker_opened", 0) >= 1, c
assert c.get("serving.requeued", 0) > 0, c
assert g.get("serving.breaker_state.r0") == 1.0, g
print(f"failover chaos OK: {c['serving.requeued']} requests requeued, "
      f"breaker opened {c['serving.breaker_opened']}x, hang bounded")
EOF

# leg 2 — 2x-overload goodput: deadline+priority shedding + brownout
# ladder must deliver >= 1.3x the shed-nothing r8 baseline's goodput at
# equal-or-better interactive p99 (bench self-gates); the expired/shed/
# brownout counters must be alive in the snapshot.
python bench_serving.py --smoke --mix overload \
    --dump "$FD_DIR/overload_stats.json"
python tools/stats_report.py "$FD_DIR/overload_stats.json" \
    --require serving.expired --require serving.shed \
    --require serving.goodput --require serving.brownout
rm -rf "$FD_DIR"

# the frozen-graph verifier must reject a freeze that left a training op
if python tools/program_lint.py --broken-frozen-fixture > /dev/null 2>&1; then
    echo "program_lint failed to reject the broken frozen fixture" >&2
    exit 1
fi

echo "== fleet chaos (process replicas: SIGKILL + respawn + scale-out) =="
# 4 process-isolated workers behind one endpoint on the overload mix;
# one worker SIGKILLed mid-run. The bench self-gates: every admitted
# request resolves typed (zero hangs), the supervisor respawns the
# corpse back to full strength, the autoscaler adds capacity BEFORE any
# shedding (the brownout ladder's rung zero), and the goodput-scaling
# gate arms itself by core count (N processes on one core cannot scale
# by construction — correctness gates always apply). stats_report proves
# the fleet telemetry was alive; pgrep proves Server.close() left zero
# orphan workers.
FLEET_DIR=$(mktemp -d)
JAX_PLATFORMS=cpu python bench_serving.py --smoke --mix overload \
    --fleet 4 --fleet-kill --dump "$FLEET_DIR/fleet_stats.json"
python tools/stats_report.py "$FLEET_DIR/fleet_stats.json" \
    --require serving.fleet. --require serving.server_closes
python - "$FLEET_DIR" <<'EOF'
import json, sys
snap = json.load(open(sys.argv[1] + "/fleet_stats.json"))
c = snap["counters"]
assert c.get("serving.fleet.worker_deaths", 0) >= 1, c
assert c.get("serving.fleet.respawns", 0) >= 1, c
assert c.get("serving.fleet.scale_outs", 0) >= 1, c
assert c.get("serving.fleet.spawns", 0) >= 4, c
print(f"fleet chaos OK: {c['serving.fleet.spawns']} spawns, "
      f"{c['serving.fleet.worker_deaths']} death(s) -> "
      f"{c['serving.fleet.respawns']} respawn(s), "
      f"{c['serving.fleet.reroutes']} reroute(s), "
      f"{c['serving.fleet.scale_outs']} scale-out(s) before shedding")
EOF
if pgrep -f "paddle_tpu.serving.worker" > /dev/null 2>&1; then
    echo "orphan fleet workers survived Server.close():" >&2
    pgrep -af "paddle_tpu.serving.worker" >&2
    exit 1
fi
rm -rf "$FLEET_DIR"

echo "== live-publish chaos (delta rollout + SIGKILL mid-apply) =="
# leg 1 — the in-process live_update mix: 3 SubscribedRunner replicas
# serving while a trainer publishes delta bundles and the rollout
# controller canaries them through. The bench self-gates: goodput under
# live updates >= 0.9x the no-publish baseline, >= 1 version applied,
# zero torn rows (no batch mixed two versions' weights). stats_report
# proves the publish/staleness telemetry was alive.
LP_DIR=$(mktemp -d)
JAX_PLATFORMS=cpu python bench_serving.py --smoke --mix live_update \
    --dump "$LP_DIR/live_update_stats.json"
python tools/stats_report.py "$LP_DIR/live_update_stats.json" \
    --require publish. --require publish.applies \
    --require publish.commit_latency --require publish.apply_latency \
    --require serving.model_staleness

# leg 2 — the process-fleet respawn-consistency leg: a continuously
# trained model published to a 2-worker fleet in follow mode, with the
# publish.apply hang seam armed in every worker env and one worker
# SIGKILLed inside that window (killed MID-apply, the torn-apply
# window). Gates: the survivor completes the apply after the bounded
# hang, the corpse respawns and catch-up-polls BEFORE readiness, and
# every worker's scope digest is CRC-identical to a cold fold of the
# last committed version — delta-applied, hung, killed, and respawned
# replicas all land bitwise on the same weights. fleet_report renders
# the publish-version skew from the workers' journal shards.
JAX_PLATFORMS=cpu python - "$LP_DIR" <<'EOF'
import json, os, signal, sys, time
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import layers, observability
from paddle_tpu import io as _io
from paddle_tpu.framework.scope import Scope, scope_guard
from paddle_tpu.fleet.publish import ModelPublisher, load_version
from paddle_tpu.serving import ProcessReplicaSet, Server, freeze_program
from paddle_tpu.serving.router import EndpointConfig

observability.set_enabled(True)
workdir = os.path.join(sys.argv[1], "fleet")

main, startup = fluid.Program(), fluid.Program()
main.random_seed = startup.random_seed = 11
with fluid.program_guard(main, startup):
    x = fluid.data("x", [-1, 8])
    lab = fluid.data("lab", [-1, 1], "int64")
    logits = layers.fc(layers.fc(x, 16, act="relu"), 4)
    prob = layers.softmax(logits)
    loss = layers.mean(layers.softmax_with_cross_entropy(logits, lab))
    fluid.optimizer.Adam(1e-2).minimize(loss, startup)
scope = Scope()
exe = fluid.Executor()
with scope_guard(scope):
    exe.run(startup, scope=scope)
frozen = freeze_program(main, [prob], feed_names=("x",))
rng = np.random.RandomState(0)

def train(n=2):
    with scope_guard(scope):
        for _ in range(n):
            exe.run(main, feed={
                "x": rng.randn(8, 8).astype(np.float32),
                "lab": rng.randint(0, 4, (8, 1)).astype(np.int64),
            }, fetch_list=[loss], scope=scope)

model_dir = os.path.join(workdir, "model")
publish_dir = os.path.join(workdir, "publish")
frozen.save(model_dir, scope=scope)
pub = ModelPublisher(publish_dir, main_program=frozen.program,
                     scope=scope, full_every=3)

# No version is published yet: the workers come up on the cold
# model_dir load, so the FIRST follow-mode apply each worker runs is
# the one the armed hang seam (max_fires=1 per process) wedges — the
# SIGKILL below lands inside a genuinely in-flight apply.
fleet = ProcessReplicaSet(
    model_dir, n_workers=2, warm_buckets=(1, 2), attempt_timeout=30.0,
    spawn_timeout=300.0, name="livepub", workdir=workdir,
    publish_dir=publish_dir, publish_mode="follow", publish_poll=0.2,
    env={"PADDLE_TPU_FAULT_INJECT": "publish.apply:hang:1.0:0:1",
         "PADDLE_TPU_FAULT_HANG_SECONDS": "3"},
)
srv = Server()
srv.add_endpoint("livepub", fleet,
                 EndpointConfig(buckets=(1, 2), max_wait_ms=2.0))
srv.warmup()
srv.submit("livepub", {"x": np.ones(8, np.float32)}).result(timeout=30)

train(); v1 = pub.publish(step=1)
time.sleep(1.0)  # both workers are now INSIDE the armed apply hang
victim = fleet.worker_pids()[0]
os.kill(victim, signal.SIGKILL)  # shot mid-apply
print(f"SIGKILLed worker pid {victim} mid-apply (hang seam armed)")

def digests_at(version, timeout=90):
    deadline = time.time() + timeout
    while time.time() < deadline:
        try:
            seen = {w: fleet.worker_digest(w, timeout=10.0)
                    for w in list(fleet._clients)}
            if all(d.get("version") == version for d in seen.values()):
                return seen
        except Exception:
            pass
        time.sleep(0.5)
    raise SystemExit(f"fleet never converged on v{version}")

def check_bitwise(version):
    seen = digests_at(version)
    cold = load_version(publish_dir, version)
    expect = {n: _io._array_entry(np.asarray(a))["crc32"]
              for n, a in cold.items()}
    for w, d in seen.items():
        for name, crc in d["crc"].items():
            assert expect.get(name) == crc, (w, name)

check_bitwise(v1)  # survivor finished its hung apply; corpse respawned
train(); v2 = pub.publish(step=2)  # a delta on top, post-respawn
check_bitwise(v2)
c = observability.get_counters()
assert c.get("serving.fleet.respawns", 0) >= 1, c
time.sleep(1.5)  # let the workers journal the post-apply gauges
srv.close(timeout=120)
print(f"live-publish chaos OK: v{v2} served fleet-wide, "
      f"{c['serving.fleet.respawns']} respawn(s) caught up bitwise "
      f"(CRC digest == cold fold)")
EOF
# the workers' journal shards must render the publish-version skew
python tools/fleet_report.py "$LP_DIR/fleet/telemetry" --json \
    | python - <<'EOF'
import json, sys
report = json.load(sys.stdin)
skew = report["fleet"]["publish_skew"]
assert skew["per_rank_version"], report["fleet"]
assert skew["max_version"] >= 2, skew
print(f"fleet_report publish skew OK: versions {skew['per_rank_version']}"
      f" (max skew {skew['max_skew']})")
EOF
if pgrep -f "paddle_tpu.serving.worker" > /dev/null 2>&1; then
    echo "orphan fleet workers survived the live-publish stage:" >&2
    pgrep -af "paddle_tpu.serving.worker" >&2
    exit 1
fi
rm -rf "$LP_DIR"

echo "== observability smoke =="
python - <<'EOF'
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import layers, observability
from paddle_tpu.embedding import EmbeddingEngine, fuse_lookups
from paddle_tpu.framework.scope import Scope, scope_guard
from paddle_tpu.ops.detection_stats import record_roi_stats

main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    x = fluid.data("x", [4, 4])
    y = layers.scale(x, scale=2.0)
    # one cross-image batched detection op: rois [B, R, 4] against
    # feats [B, C, H, W] -> detection.* trace-time counters
    feats = fluid.data("feats", [2, 2, 8, 8])
    rois = fluid.data("rois", [2, 3, 4])
    pooled = layers.roi_align(feats, rois, pooled_height=2, pooled_width=2)
exe = fluid.Executor()
exe.run(startup)
rb = np.zeros((2, 3, 4), "float32"); rb[..., 2:] = 4.0
exe.run(main, feed={"x": np.ones((4, 4), "float32"),
                    "feats": np.ones((2, 2, 8, 8), "float32"),
                    "rois": rb}, fetch_list=[y, pooled])
# host-side padding-waste gauge + rois-per-image histogram
record_roi_stats(np.array([2, 3]), cap=3)

# one fused + hot-tier-cached lookup -> embedding.* counters, hit-rate
# gauge, unique-ids/dedup/host-fetch histograms
emain, estartup = fluid.Program(), fluid.Program()
escope = Scope()
with fluid.program_guard(emain, estartup):
    ids = fluid.data("ids", [8, 2], "int64")
    parts = [
        layers.sparse_embedding(
            layers.slice(ids, [1], [i], [i + 1]), [64, 4],
            param_attr=fluid.ParamAttr(name="obs_table"),
        )
        for i in range(2)
    ]
    assert fuse_lookups(emain) == 1
    engine = EmbeddingEngine(emain, estartup, hot_rows=32)
    out = layers.concat([layers.reshape(p, [8, 1, 4]) for p in parts], 1)
with scope_guard(escope):
    exe.run(estartup, scope=escope)
    engine.attach(escope)
    feed = engine.prepare_feed(
        {"ids": np.arange(16).reshape(8, 2).astype("int64")}, escope)
    exe.run(emain, feed=feed, fetch_list=[out], scope=escope)

observability.dump("/tmp/paddle_tpu_obs_snapshot.json")
EOF
python tools/stats_report.py /tmp/paddle_tpu_obs_snapshot.json \
    --require executor. --require analysis. --require detection. \
    --require embedding.

echo "== causal tracing: cross-thread traces, rank stamps, live watcher =="
# 2-rank mini-train with traces on: each step runs under its own trace;
# the async checkpoint save chains step -> snapshot -> publisher ->
# liveness pulse across THREE threads, heartbeats carry the trace stamp,
# and a serving request chains client -> ingest thread -> scheduler.
# trace_report must reconstruct complete >=3-thread traces from the
# export files alone; the watcher must flag the seeded straggler and
# SLO breach as structured watch.* findings.
TRACE_DIR=$(mktemp -d)
python - "$TRACE_DIR" <<'EOF'
import sys
import threading

import numpy as np
import paddle_tpu as fluid
from paddle_tpu import layers, observability as obs
from paddle_tpu.fleet import collective as fc
from paddle_tpu.fleet.role_maker import UserDefinedRoleMaker
from paddle_tpu.framework.scope import Scope, scope_guard
from paddle_tpu.observability import trace, watch
from paddle_tpu.resilience.health import Heartbeat
from paddle_tpu.serving import Server, freeze_program
from paddle_tpu.serving.router import EndpointConfig

out = sys.argv[1]
x = fluid.data("x", [-1, 4])
y = fluid.data("y", [-1, 1])
pred = layers.fc(x, 1)
loss = layers.mean(layers.square_error_cost(pred, y))
fluid.optimizer.SGD(0.05).minimize(loss)
exe = fluid.Executor()
exe.run(fluid.default_startup_program())
fleet = fc.Fleet()
fleet.init(UserDefinedRoleMaker())
rng = np.random.RandomState(0)

# -- two "ranks": same program stepped per rank, each under per-step
# traces with an async checkpoint mid-run, exporting its own span file
rank0_tr = None
for rank in (0, 1):
    obs.reset()
    hb = Heartbeat(out + "/hb", rank=rank)
    with fc.AsyncCheckpointer(fleet, f"{out}/ck_rank{rank}", executor=exe,
                              heartbeat=hb) as saver:
        for step in range(4):
            tr = trace.new_trace()
            if rank == 0 and step == 3:
                rank0_tr = tr  # spans land in rank 0's export below
            with trace.activate(tr), obs.span("train.step", step=step,
                                              rank=rank):
                xa = rng.randn(8, 4).astype(np.float32)
                exe.run(feed={"x": xa,
                              "y": xa @ np.ones((4, 1), np.float32)},
                        fetch_list=[loss])
                if step == 2:
                    saver.save(
                        fc.TrainStatus(0, global_step=step + 1)
                    ).result(timeout=60)
                hb.beat()
        saver.wait(timeout=60)
    if rank == 0:
        obs.spans.save_chrome_trace(f"{out}/trace_rank0.json")
# rank 1's buffer still holds its spans (reset happened between ranks)

# -- one serving request chaining three threads: the main thread's
# client.prepare span hands its context to a submitter thread
# (capture/activate), whose ingest hands off to the scheduler thread
smain, sstartup = fluid.Program(), fluid.Program()
sscope = Scope()
with fluid.program_guard(smain, sstartup):
    sx = fluid.data("sx", [-1, 4])
    sprob = layers.softmax(layers.fc(sx, 2))
with scope_guard(sscope):
    exe.run(sstartup, scope=sscope)
frozen = freeze_program(smain, [sprob], feed_names=("sx",))
server = Server()
server.add_endpoint("trace_demo", None,
                    EndpointConfig(buckets=(1, 2), max_wait_ms=2.0),
                    frozen=frozen, executor=exe, scope=sscope)
server.warmup()
req_tr = trace.new_trace()
with trace.activate(req_tr), obs.span("client.prepare") as prep:
    ctx = trace.capture()

def submit_and_wait():
    with trace.activate(ctx):
        server.submit(
            "trace_demo", {"sx": np.ones(4, np.float32)}
        ).result(timeout=30)

t = threading.Thread(target=submit_and_wait)
t.start(); t.join()
server.drain(timeout=30)

# -- cross-rank stitch: rank 1 beats INSIDE a trace that began on rank
# 0 (the pod contract: a step's trace spans ranks; the beat carries the
# stamp) — the merge below must count this trace on BOTH ranks
with trace.activate(rank0_tr):
    Heartbeat(out + "/hb", rank=1).beat(step=4)

# -- the live watcher over genuine signals: rank 0 races ahead of rank
# 1's final beat (straggler), and a 1us SLO guarantees the serving
# latencies breach it — both must land as structured findings
Heartbeat(out + "/hb", rank=0).beat(step=40)
w = watch.Watcher(heartbeat_dir=out + "/hb", skew_steps=2,
                  slo_p99_s=1e-6)
w.poll()
kinds = {f["kind"] for f in w.findings}
assert "straggler" in kinds, w.findings
assert "slo_breach" in kinds, w.findings

obs.spans.save_chrome_trace(f"{out}/trace_rank1.json")
obs.dump(f"{out}/trace_stats.json")
EOF
# reconstruction from export files ALONE: >= 1 complete trace spanning
# >= 3 threads containing the checkpoint publish (the training chain)
# and >= 1 containing the serving ingest (the request chain)
python tools/trace_report.py "$TRACE_DIR"/trace_rank*.json \
    --check --min-threads 3 --require-span checkpoint.publish --top 2
python tools/trace_report.py "$TRACE_DIR"/trace_rank*.json \
    --check --min-threads 3 --require-span serving.ingest --quiet
python tools/stats_report.py "$TRACE_DIR/trace_stats.json" \
    --require trace. --require watch. --require checkpoint.
# the heartbeat-carried trace stamp must stitch into the pod merge
python tools/perf_report.py \
    --merge "$TRACE_DIR"/trace_rank0.json "$TRACE_DIR"/trace_rank1.json \
    --heartbeat-dir "$TRACE_DIR/hb" -o "$TRACE_DIR/pod_trace.json" \
    | tee "$TRACE_DIR/trace_merge.out"
python - "$TRACE_DIR" <<'EOF'
import json, sys
stats = json.loads(
    open(sys.argv[1] + "/trace_merge.out").read().strip().splitlines()[-1]
)
assert stats["traced_trace_ids"] > 0, stats
# the heartbeat-carried stamp must have stitched rank 1's beat into a
# trace whose spans live on rank 0 — deleting either side of the stamp
# path (Heartbeat ctx stamping or the merge's beat handling) fails here
assert stats["cross_rank_traces"] >= 1, stats
print(f"trace merge OK: {stats['traced_trace_ids']} traces stitched "
      f"across ranks (cross-rank: {stats['cross_rank_traces']})")
EOF
# ...and the checker must still reject a seeded orphan-span export
if python tools/trace_report.py --broken-fixture > /dev/null 2>&1; then
    echo "trace_report failed to reject the orphan-span fixture" >&2
    exit 1
fi
rm -rf "$TRACE_DIR"

echo "== telemetry plane chaos: 2-rank journals + SIGKILL + offline replay =="
# two trainers join the plane via the one-env-var opt-in (the Executor
# constructor starts publisher + flight recorder). rank 0 finishes
# cleanly and dumps its live snapshot; rank 1 is SIGKILLed mid-run.
# everything below is read OFFLINE from the telemetry dir: the dead
# rank's journal must replay to its last published state, its periodic
# flight bundle must hold the pre-death window, fleet_report must merge
# both ranks, and a journal-mode watcher (no shared memory with either
# process) must flag the dead rank as the straggler.
TEL_DIR=$(mktemp -d)
PADDLE_TPU_TELEMETRY_DIR="$TEL_DIR" PADDLE_TPU_TELEMETRY_INTERVAL=0.05 \
    PADDLE_TRAINER_ID=1 JAX_PLATFORMS=cpu \
    python tests/telemetry_worker.py "$TEL_DIR" 0 \
    > "$TEL_DIR/rank1.log" 2>&1 &
TPID=$!
# wait for the doomed rank's journal AND black box to land, then kill -9
# (before the clean rank runs its 30 steps, so the dead rank's counter
# is unambiguously the lagging one)
for _ in $(seq 600); do
    grep -q "guard.steps" "$TEL_DIR/telemetry_rank1.jsonl" 2>/dev/null \
        && grep -q "train.step" "$TEL_DIR/flight_rank1.json" 2>/dev/null \
        && break
    kill -0 "$TPID" 2>/dev/null || { cat "$TEL_DIR/rank1.log"; exit 1; }
    sleep 0.2
done
grep -q "train.step" "$TEL_DIR/flight_rank1.json" 2>/dev/null || {
    echo "rank 1 never published journal progress + flight bundle"
    cat "$TEL_DIR/rank1.log"; exit 1
}
kill -9 "$TPID"; wait "$TPID" 2>/dev/null || true
PADDLE_TPU_TELEMETRY_DIR="$TEL_DIR" PADDLE_TPU_TELEMETRY_INTERVAL=0.05 \
    PADDLE_TRAINER_ID=0 JAX_PLATFORMS=cpu \
    python tests/telemetry_worker.py "$TEL_DIR" 30 \
    > "$TEL_DIR/rank0.log" 2>&1 \
    || { cat "$TEL_DIR/rank0.log"; exit 1; }
python - "$TEL_DIR" <<'EOF'
import json, sys
from paddle_tpu.observability import metrics, timeline, watch

d = sys.argv[1]
# 1) the DEAD rank: journal replay alone reconstructs its last published
# state — steps, goodput, latency histogram — no process to ask
replay = timeline.replay_journal(d + "/telemetry_rank1.jsonl")
snap1 = replay.snapshot()
steps1 = snap1["counters"]["guard.steps"]
assert steps1 > 0 and replay.meta["rank"] == 1, snap1["counters"]
assert "serving.request_latency" in snap1["histograms"]
# 2) its periodic flight bundle holds the pre-death window (spans +
# registry state published by the black-box thread, never by a trigger)
bundle = json.load(open(d + "/flight_rank1.json"))
assert bundle["trigger"] == "periodic" and bundle["rank"] == 1, bundle
assert any(s["name"] == "train.step" for s in bundle["spans"]), \
    [s["name"] for s in bundle["spans"]][:8]
assert bundle["counters"].get("guard.steps", 0) > 0
# 3) the CLEAN rank: offline replay lands bitwise on the snapshot the
# live process dumped after its final publish
snap0 = timeline.replay_journal(d + "/telemetry_rank0.jsonl").snapshot()
live0 = json.load(open(d + "/telemetry_stats.json"))
for section in ("counters", "gauges", "histograms"):
    assert snap0[section] == live0[section], section
assert snap0.get("tables", {}) == live0.get("tables", {})
assert live0["counters"]["telemetry.publishes"] > 1
# 4) a journal-mode watcher in THIS process (which shares memory with
# neither trainer) flags the dead rank as the straggler
metrics.reset()
w = watch.Watcher(journal_dir=d, skew_steps=2, slo_p99_s=None)
findings = w.poll()
strag = [f for f in findings if f["kind"] == "straggler"]
assert strag and strag[0]["detail"]["source"] == "journal", findings
assert strag[0]["detail"]["lagging_ranks"] == [1], strag[0]["detail"]
print(f"telemetry chaos OK: dead rank replayed to step {steps1}, "
      f"clean rank bitwise ({live0['counters']['telemetry.publishes']} "
      f"publishes), straggler flagged from journals alone")
EOF
# the fleet merge: both shards (one from a SIGKILLed writer) replayed
# into one report, with the dead rank's last steps reconstructed
python tools/fleet_report.py "$TEL_DIR" --expect-ranks 2 \
    --out "$TEL_DIR/fleet.json"
python - "$TEL_DIR" <<'EOF'
import json, sys
report = json.load(open(sys.argv[1] + "/fleet.json"))
by_rank = {s["rank"]: s for s in report["shards"]}
assert by_rank[0]["last_step"] == 30, by_rank[0]
assert by_rank[1]["last_step"] > 0, by_rank[1]
assert report["fleet"]["straggler"]["per_rank_last_step"]["1"] \
    == by_rank[1]["last_step"]
print(f"fleet report OK: ranks 0+1 merged, dead rank died at step "
      f"{by_rank[1]['last_step']} of lead {by_rank[0]['last_step']}")
EOF
# the clean rank's snapshot carries the plane's own counters
python tools/stats_report.py "$TEL_DIR/telemetry_stats.json" \
    --require telemetry.
rm -rf "$TEL_DIR"

echo "== perf report (IR cost model vs XLA over the zoo) =="
# every zoo model's Program.estimate() must stay within 25% of XLA's own
# cost_analysis (one model of slack for backend counting quirks), and the
# static peak-HBM plan within 25% of XLA memory_analysis on all but two
# models (peak estimation carries fusion/scheduling error FLOPs do not);
# divergences are printed, never hidden
python tools/perf_report.py --all-models --check-divergence \
    --max-divergence 0.25 --allow-divergent 1 --top-ops 3 \
    --check-memory --allow-memory-divergent 2

echo "== perf report: multi-rank timeline merge =="
PERF_DIR=$(mktemp -d)
python - "$PERF_DIR" <<'EOF'
import sys

import numpy as np
import paddle_tpu as fluid
from paddle_tpu import layers, observability
from paddle_tpu.resilience.health import Heartbeat

out = sys.argv[1]
main, startup = fluid.Program(), fluid.Program()
with fluid.program_guard(main, startup):
    x = fluid.data("x", [8, 16])
    loss = layers.mean(layers.fc(x, 16))
    fluid.optimizer.SGD(0.1).minimize(loss, startup)
exe = fluid.Executor()
exe.run(startup)
# two "ranks": same program stepped twice, each exporting its own span
# file + heartbeat (what a real pod writes per rank)
for rank in (0, 1):
    observability.reset()
    hb = Heartbeat(out + "/hb", rank=rank)
    for step in range(4):
        exe.run(main, feed={"x": np.ones((8, 16), "float32")},
                fetch_list=[loss])
        hb.beat()
    observability.spans.save_chrome_trace(f"{out}/trace_rank{rank}.json")
EOF
python tools/perf_report.py \
    --merge "$PERF_DIR"/trace_rank0.json "$PERF_DIR"/trace_rank1.json \
    --heartbeat-dir "$PERF_DIR/hb" -o "$PERF_DIR/pod_trace.json" \
    | tee "$PERF_DIR/merge.out"
python - "$PERF_DIR" <<'EOF'
import json, sys
d = sys.argv[1]
trace = json.load(open(d + "/pod_trace.json"))
pids = {e.get("pid") for e in trace["traceEvents"]}
assert pids == {0, 1}, f"expected both rank pids in the merged trace: {pids}"
stats = json.loads(open(d + "/merge.out").read().strip().splitlines()[-1])
assert stats["aligned_steps"] >= 1, stats
assert "straggler_gap_us" in stats, stats
print(f"timeline merge OK: {stats['aligned_steps']} aligned steps, "
      f"straggler gap {stats['straggler_gap_us']:.1f} us")
EOF
rm -rf "$PERF_DIR"

echo "== resilience chaos smoke (injected IO + dataloader faults) =="
PADDLE_TPU_FAULT_INJECT="io.save:io:1.0:0:1,dataloader.fetch:io:1.0:0:2" \
python - <<'EOF'
import shutil

import numpy as np
import paddle_tpu as fluid
from paddle_tpu import layers, observability
from paddle_tpu.dataloader.dataset import Dataset
from paddle_tpu.fleet import collective as fc
from paddle_tpu.fleet.role_maker import UserDefinedRoleMaker

shutil.rmtree("/tmp/paddle_tpu_chaos_ckpt", ignore_errors=True)
rng = np.random.RandomState(0)
W = rng.randn(4, 1).astype(np.float32)


class DS(Dataset):
    def __getitem__(self, i):
        x = rng.randn(4).astype(np.float32)
        return x, x @ W + 0.01 * rng.randn(1).astype(np.float32)

    def __len__(self):
        return 64


x = fluid.data("x", [-1, 4])
y = fluid.data("y", [-1, 1])
pred = layers.fc(x, 1)
loss = layers.mean(layers.square_error_cost(pred, y))
fluid.optimizer.SGD(0.05).minimize(loss)
exe = fluid.Executor()
exe.run(fluid.default_startup_program())

fleet = fc.Fleet()
fleet.init(UserDefinedRoleMaker())
loader = fluid.DataLoader(
    DS(), feed_list=[x, y], batch_size=8, num_workers=2,
    use_buffer_reader=False,
)
losses = []
for epoch in range(3):
    for feed in loader:
        (lv,) = exe.run(feed=feed, fetch_list=[loss])
        losses.append(float(np.asarray(lv).reshape(-1)[0]))
    # first epoch's save trips the injected io.save fault; the retry heals it
    fleet.save_check_point(exe, "/tmp/paddle_tpu_chaos_ckpt",
                           fc.TrainStatus(epoch))

status = fleet.load_check_point(exe, "/tmp/paddle_tpu_chaos_ckpt")
assert status.next() == 3, status._epoch_no
c = observability.snapshot()["counters"]
retries = c.get("resilience.retries", 0)
faults = c.get("resilience.faults_injected", 0)
assert faults >= 3, f"chaos faults never fired: {faults}"
assert retries > 0, f"injected faults were not retried: {c}"
first, last = np.mean(losses[:4]), np.mean(losses[-4:])
assert last < first, f"chaos run failed to converge: {first} -> {last}"
print(f"chaos smoke OK: loss {first:.4f} -> {last:.4f}, "
      f"faults={faults} retries={retries} "
      f"giveups={c.get('resilience.giveups', 0)}")
EOF

echo "== health-guard chaos smoke: nonfinite skip =="
python - <<'EOF'
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import layers, observability
from paddle_tpu.resilience import TrainGuard, faults

rng = np.random.RandomState(0)
W = rng.randn(4, 1).astype(np.float32)
x = fluid.data("x", [-1, 4])
y = fluid.data("y", [-1, 1])
pred = layers.fc(x, 1)
loss = layers.mean(layers.square_error_cost(pred, y))
fluid.optimizer.SGD(0.05).minimize(loss)
exe = fluid.Executor()
exe.run(fluid.default_startup_program())

# every 4th step arrives NaN-poisoned; the guard must skip each one with
# ZERO weight updates and the run must still converge
from paddle_tpu.framework.scope import global_scope

def params():
    return {
        v.name: np.asarray(global_scope().find_var(v.name)).copy()
        for v in fluid.default_main_program().list_vars()
        if v.persistable and global_scope().find_var(v.name) is not None
    }

losses, skipped = [], 0
with TrainGuard(exe) as g:
    for step in range(24):
        if step % 4 == 3:
            faults.inject("guard.step", "nonfinite", 1.0, 0, 1)
            before = params()
        xa = rng.randn(8, 4).astype(np.float32)
        out = g.step(feed={"x": xa, "y": xa @ W}, fetch_list=[loss])
        if step % 4 == 3:
            assert out is None, "poisoned step was not skipped"
            after = params()
            for name, val in before.items():
                np.testing.assert_array_equal(val, after[name])
            skipped += 1
        else:
            losses.append(float(np.asarray(out[0]).reshape(-1)[0]))
c = observability.snapshot()["counters"]
assert skipped == 6 and c.get("resilience.bad_steps", 0) == 6, c
first, last = np.mean(losses[:4]), np.mean(losses[-4:])
assert last < first, f"guarded run failed to converge: {first} -> {last}"
print(f"nonfinite chaos OK: loss {first:.4f} -> {last:.4f}, "
      f"bad_steps={c['resilience.bad_steps']} (all skipped, zero updates)")
EOF

echo "== health-guard chaos smoke: hung rank killed + restarted =="
# the workers are launched by script path, so the repo root must be
# importable from their sys.path
export PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}"
HANG_DIR=$(mktemp -d)
python -m paddle_tpu.distributed.launch \
    --nproc_per_node 2 --simulate_cpu --elastic \
    --max_restarts 2 --restart_backoff 0.1 \
    --heartbeat_dir "$HANG_DIR/hb" --heartbeat_timeout 20 \
    tests/dist_hang_worker.py "$HANG_DIR" 2> "$HANG_DIR/launch.log" \
    || { cat "$HANG_DIR/launch.log"; exit 1; }
grep -q "hung" "$HANG_DIR/launch.log"
grep -q "restart 1/2" "$HANG_DIR/launch.log"
python - "$HANG_DIR" <<'EOF'
import json, sys
r1 = json.load(open(sys.argv[1] + "/hang_losses_1.json"))
assert r1["attempt"] == 1, "rank 1 result not written by its restart"
assert r1["losses"][-1] < r1["losses"][0], "restarted rank did not converge"
print(f"hang chaos OK: rank 1 killed+restarted, "
      f"loss {r1['losses'][0]:.4f} -> {r1['losses'][-1]:.4f}")
EOF
rm -rf "$HANG_DIR"

echo "== health-guard chaos smoke: SIGTERM preemption drain =="
PRE_DIR=$(mktemp -d)
JAX_PLATFORMS=cpu python tests/dist_preempt_worker.py "$PRE_DIR" \
    > "$PRE_DIR/worker.log" 2>&1 &
WPID=$!
for _ in $(seq 600); do
    [ -f "$PRE_DIR/ready" ] && break
    kill -0 "$WPID" 2>/dev/null || { cat "$PRE_DIR/worker.log"; exit 1; }
    sleep 0.2
done
[ -f "$PRE_DIR/ready" ] || { echo "worker never ready"; exit 1; }
kill -TERM "$WPID"
rc=0; wait "$WPID" || rc=$?
[ "$rc" -eq 75 ] || {
    echo "expected PREEMPTION_EXIT_CODE 75, got $rc"
    cat "$PRE_DIR/worker.log"; exit 1
}
python - "$PRE_DIR" <<'EOF'
import sys
import paddle_tpu as fluid
from paddle_tpu.fleet import collective as fc
from paddle_tpu.fleet.role_maker import UserDefinedRoleMaker

fleet = fc.Fleet()
fleet.init(UserDefinedRoleMaker())
# load verifies the CRC manifest before any scope mutation
status = fleet.load_check_point(fluid.Executor(), sys.argv[1] + "/ckpts")
assert status == fc.TrainStatus(0), status
print("preemption chaos OK: exit code 75 + final checkpoint verified")
EOF
rm -rf "$PRE_DIR"

echo "== exact-resume chaos stage: 2-rank SIGKILL mid-epoch + elastic resume =="
# trains a 2-rank pod twice — control (uninterrupted) and kill (rank 1
# SIGKILLs itself mid-epoch, --elastic restarts it, the restart resumes
# from its newest COMPLETE checkpoint) — and asserts final weights and
# consumed-example logs are BITWISE identical, no example skipped or
# consumed twice, the resume counters fired, and a v1 (epoch-only)
# checkpoint still loads
python tools/resume_audit.py
# ...and again with dp-sharded optimizer state (Momentum velocity shards
# under the ZeRO weight-update transpile): kill/resume must stay bitwise
python tools/resume_audit.py --sharded

echo "== async-checkpoint chaos stage: SIGKILL mid-async-publish =="
# checkpoints through the async snapshot/publish pipeline (delta chains
# included); rank 1 wedges its in-flight publish (hang on the
# checkpoint.publish seam) and SIGKILLs itself — the elastic resume must
# come bitwise from the newest COMMITTED checkpoint, with the wedged
# publish leaving only ignorable tmp debris
python tools/resume_audit.py --async
# ...composed with dp-sharded optimizer state (per-rank shard tiers)
python tools/resume_audit.py --async --sharded
# ...and with the embedding engine (host stores as the aux payload,
# row deltas keyed off write-back ticks, compressed chain reload)
python tools/resume_audit.py --async --embedding

echo "== storage chaos (disk-pressure ladder + ENOSPC bursts + cross-plane GC) =="
# a 2-rank train+publish cell sharing ONE byte-budgeted volume: rank 0
# trains, checkpoints, and publishes model bundles; rank 1 subscribes and
# stamps its heartbeat with the applied model_version (the GC fence —
# retention must never delete a version a live reader's chain needs).
# Mid-run the fs.write:enospc seam bursts (typed StorageExhaustedError,
# zero residue, next attempt heals) AND the checkpoint root's byte budget
# is sized so accumulating checkpoints MUST drive the ladder to HARD:
# publishes freeze, emergency GC reclaims, the ladder re-arms to OK, and
# training converges anyway. Gates: newest committed checkpoint resumes,
# the subscriber ends on the latest committed bundle, gc_bytes_freed > 0,
# escalations AND recoveries fired, max level >= HARD, final level == OK,
# zero *.tmp.* residue anywhere under the volume.
SC_DIR=$(mktemp -d)
JAX_PLATFORMS=cpu python - "$SC_DIR" <<'EOF'
import json, os, sys, time
import numpy as np
import paddle_tpu as fluid
from paddle_tpu import errors, layers, observability as obs
from paddle_tpu import io as _io
from paddle_tpu.framework.scope import Scope, scope_guard
from paddle_tpu.fleet import collective as fc
from paddle_tpu.fleet.publish import ModelPublisher, ModelSubscriber, \
    load_version
from paddle_tpu.fleet.role_maker import UserDefinedRoleMaker
from paddle_tpu.observability.timeline import TelemetryPublisher
from paddle_tpu.resilience import faults, storage
from paddle_tpu.resilience.health import Heartbeat

obs.set_enabled(True)
root = sys.argv[1]
ck_dir = os.path.join(root, "ckpts")
pub_dir = os.path.join(root, "publish")
hb_dir = os.path.join(root, "hb")
tl_dir = os.path.join(root, "telemetry")

main, startup = fluid.Program(), fluid.Program()
main.random_seed = startup.random_seed = 23
with fluid.program_guard(main, startup):
    x = fluid.data("x", [-1, 8])
    lab = fluid.data("lab", [-1, 1], "int64")
    logits = layers.fc(layers.fc(x, 16, act="relu"), 4)
    loss = layers.mean(layers.softmax_with_cross_entropy(logits, lab))
    fluid.optimizer.Adam(1e-2).minimize(loss, startup)
scope = Scope()
exe = fluid.Executor()
with scope_guard(scope):
    exe.run(startup, scope=scope)
rng = np.random.RandomState(0)
w_true = rng.randn(8, 4).astype(np.float32)  # learnable labels

def train_step():
    xa = rng.randn(16, 8).astype(np.float32)
    la = (xa @ w_true).argmax(axis=1).reshape(16, 1).astype(np.int64)
    with scope_guard(scope):
        out = exe.run(main, feed={"x": xa, "lab": la},
                      fetch_list=[loss], scope=scope)
    return float(np.asarray(out[0]).reshape(-1)[0])

fleet = fc.Fleet()
fleet.init(UserDefinedRoleMaker(current_id=0, worker_num=1))
pub = ModelPublisher(pub_dir, main_program=main, scope=scope,
                     full_every=3)

# rank 1: the subscriber, folding into its own scope and stamping its
# heartbeat with the applied version — the retention fence
sub_scope = Scope()
hb1 = Heartbeat(hb_dir, rank=1)
sub = ModelSubscriber(pub_dir, main_program=main, scope=sub_scope,
                      heartbeat=hb1)
hb0 = Heartbeat(hb_dir, rank=0)
tl0 = TelemetryPublisher(directory=tl_dir, rank=0, interval=3600.0)
tl0.start(register=False)
tl1 = TelemetryPublisher(directory=tl_dir, rank=1, interval=3600.0)
tl1.start(register=False)

losses = []

def ckpt(step):
    with scope_guard(scope):
        fleet.save_check_point(
            exe, ck_dir, fc.TrainStatus(0, global_step=step),
            main_program=main, max_checkpoint_num=10,
        )

first = train_step()
ckpt(0)
one = storage._du(os.path.join(ck_dir, "__paddle_checkpoint__0"))
assert one > 0

# budget the volume off the measured checkpoint size: 6 checkpoints fit,
# SOFT below 3 free, HARD below 1.5 free — saves alone force the climb
monitor = storage.StorageMonitor(
    soft_bytes=int(one * 3), hard_bytes=int(one * 1.5),
    critical_bytes=int(one * 0.25), rearm=1.1, probe=True,
)
monitor.add_root("checkpoint", ck_dir, budget_bytes=int(one * 6))
monitor.install()
retention = storage.RetentionManager().add_checkpoint_plane(
    ck_dir, budget_bytes=int(one * 2.5),
).add_publish_plane(pub_dir, keep=2, heartbeat_dir=hb_dir)
ladder = storage.StoragePressureController(
    monitor, retention=retention, publish_control=pub,
    telemetry=tl0, gc_interval=0.0,
)

max_level = storage.OK
typed_failures = 0
skipped = 0
armed = False
for step in range(1, 25):
    losses.append(train_step())
    hb0.beat(step=step)
    if step == 6 and not armed:
        # the ENOSPC burst: raw OSError(ENOSPC) out of the fs.write seam,
        # seeded, capped — some saves/publishes in this window die typed
        faults.inject("fs.write", "enospc", 0.35, 1234, 3)
        armed = True
    try:
        ckpt(step)
    except errors.StorageExhaustedError:
        typed_failures += 1  # retryable-after-GC: next iteration heals
    try:
        v = pub.publish(step=step)
        if v is None and pub.frozen:
            skipped += 1
    except errors.StorageExhaustedError:
        typed_failures += 1
    sub.poll()
    level = ladder.poll()
    max_level = max(max_level, level)
    tl0.publish()
    tl1.publish()

faults.clear()
# the scheduled (cron-style) retention pass — emergency GC only runs at
# HARD+, so the tail checkpoints above the SOFT line are its job
retention.collect()
# drain the ladder: stepwise re-arm back to OK
for _ in range(6):
    final_level = ladder.poll()
tl0.publish()
tl1.publish()

# the post-recovery world must be fully writable again
ckpt(99)
v_final = pub.publish(step=99)
assert v_final is not None, "publish still frozen after recovery"
sub.poll()
tl0.publish(); tl1.publish()
tl0.stop(); tl1.stop()

c = obs.get_counters()
assert np.mean(losses[-5:]) < first * 0.7, (first, losses[-5:])
assert typed_failures >= 1, "no ENOSPC burst ever landed typed"
assert c.get("storage.enospc_errors", 0) >= 1, c
assert max_level >= storage.HARD, f"ladder never reached HARD ({max_level})"
assert final_level == storage.OK, f"ladder stuck at {final_level}"
assert c.get("storage.gc_bytes_freed", 0) > 0, c
assert c.get("storage.escalations", 0) >= 1, c
assert c.get("storage.recoveries", 0) >= 1, c
assert skipped >= 1 or c.get("publish.skipped_frozen", 0) >= 0

# newest committed checkpoint resumes
status = fleet.load_check_point(exe, ck_dir)
assert status.global_step == 99, status

# the subscriber sits on the latest committed bundle, folded bitwise
assert sub.version == v_final, (sub.version, v_final)
cold = load_version(pub_dir, v_final)
for name, arr in cold.items():
    live = sub_scope.find_var(name)
    if live is not None:
        assert np.asarray(live).tobytes() == np.asarray(arr).tobytes(), name

# zero tmp residue anywhere under the volume
residue = [os.path.join(d, f) for d, _dirs, fs in os.walk(root)
           for f in fs if ".tmp." in f]
assert not residue, residue

obs.dump(os.path.join(root, "storage_stats.json"))
print(f"storage chaos OK: {typed_failures} typed ENOSPC failure(s) healed, "
      f"ladder peaked at {storage.LEVEL_NAMES[max_level]} and re-armed, "
      f"{c['storage.gc_bytes_freed']} bytes GC'd, resumed step 99, "
      f"subscriber bitwise on v{v_final}")
EOF
# the storage telemetry must have been alive end to end
python tools/stats_report.py "$SC_DIR/storage_stats.json" \
    --require storage. --require storage.gc_bytes_freed \
    --require storage.escalations --require storage.recoveries \
    --require storage.enospc_errors
# ...and the journal shards must render the offline storage digest
python tools/fleet_report.py "$SC_DIR/telemetry" | tee /dev/stderr \
    | grep -q "storage:"
rm -rf "$SC_DIR"

echo "== driver entry points =="
python __graft_entry__.py

echo "== op surface =="
python tools/check_op_surface.py || true
