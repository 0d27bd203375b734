"""PR-18 live model publish plane: versioned delta bundles with a
commit-record visibility barrier, all-or-nothing subscriber applies
(torn-read fence), per-consumer delta-row cursors, canaried rollout with
automatic rollback, staleness gauges, and the brownout freeze rung.

Everything here is in-process (real Programs/Scopes, fake watcher, fault
seams instead of SIGKILL); the real multi-process leg — a worker shot
mid-apply respawning bitwise onto the last committed version — is
bench_serving.py's ``--mix live_update`` and ci.sh's live-publish chaos
stage."""

import importlib.util
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu import observability as obs
from paddle_tpu.errors import CheckpointCorruptionError
from paddle_tpu.fleet import publish as pub_mod
from paddle_tpu.fleet.publish import (
    PAYLOAD_NAME,
    ModelPublisher,
    ModelSubscriber,
    block_version,
    committed_versions,
    latest_version,
    load_version,
    read_blocked,
    resolve_chain,
    version_dir,
)
from paddle_tpu.framework.scope import Scope, scope_guard
from paddle_tpu.resilience import faults
from paddle_tpu.resilience.health import Heartbeat
from paddle_tpu.serving import freeze_program
from paddle_tpu.serving.brownout import DEFAULT_LADDER, BrownoutController
from paddle_tpu.serving.replica import ReplicaSet
from paddle_tpu.serving.rollout import RolloutController, SubscribedRunner
from paddle_tpu.serving.router import FrozenRunner

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_ROOT, "tools", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def fresh_metrics():
    obs.reset()
    obs.set_enabled(True)
    faults.clear()
    yield
    faults.clear()
    obs.reset()
    obs.set_enabled(None)


def _counter(name):
    return obs.get_counters().get(name, 0)


# ---------------------------------------------------------------------------
# fixture: a tiny trainable classifier + its frozen serving graph
# ---------------------------------------------------------------------------


class _Trainer:
    def __init__(self, seed=7):
        self.scope = Scope()
        self.main, self.startup = fluid.Program(), fluid.Program()
        self.main.random_seed = self.startup.random_seed = seed
        with fluid.program_guard(self.main, self.startup):
            x = fluid.data("x", [-1, 8])
            lab = fluid.data("lab", [-1, 1], "int64")
            h = layers.fc(x, 16, act="relu")
            logits = layers.fc(h, 4)
            self.prob = layers.softmax(logits)
            self.loss = layers.mean(
                layers.softmax_with_cross_entropy(logits, lab)
            )
            fluid.optimizer.Adam(1e-2).minimize(self.loss, self.startup)
        self.exe = fluid.Executor()
        self._rng = np.random.RandomState(seed)
        with scope_guard(self.scope):
            self.exe.run(self.startup, scope=self.scope)
        self.frozen = freeze_program(
            self.main, [self.prob], feed_names=("x",)
        )

    def step(self, n=2):
        with scope_guard(self.scope):
            for _ in range(n):
                self.exe.run(
                    self.main,
                    feed={
                        "x": self._rng.randn(4, 8).astype(np.float32),
                        "lab": self._rng.randint(
                            0, 4, (4, 1)
                        ).astype(np.int64),
                    },
                    fetch_list=[self.loss], scope=self.scope,
                )

    def serving_scope(self):
        """A cold replica scope: startup-initialized, same topology —
        what a fresh worker holds before its catch-up poll."""
        scope = Scope()
        with scope_guard(scope):
            self.exe.run(self.startup, scope=scope)
        return scope


@pytest.fixture()
def trainer():
    return _Trainer()


def _dense(arrays):
    """The dense persistables of a folded bundle (drop embedding
    host-store keys; row pairs never survive a fold)."""
    return {
        n: a for n, a in arrays.items() if "::host::" not in n
    }


def _assert_scope_matches(scope, arrays):
    for name, arr in _dense(arrays).items():
        live = scope.find_var(name)
        assert live is not None, name
        np.testing.assert_array_equal(np.asarray(live), np.asarray(arr))


# ---------------------------------------------------------------------------
# publisher: commit record = visibility barrier
# ---------------------------------------------------------------------------


def test_commit_seam_crash_is_invisible_and_number_reclaimed(
    trainer, tmp_path
):
    p = ModelPublisher(str(tmp_path), main_program=trainer.main,
                       scope=trainer.scope)
    faults.inject("publish.commit", "io", 1.0, 0, 1)
    with pytest.raises(Exception):
        p.publish(step=1)
    # payload may have landed; without its commit record the version
    # does not exist to any reader
    assert committed_versions(str(tmp_path)) == []
    assert latest_version(str(tmp_path)) is None
    # the seam healed (max_fires=1): the same version number is
    # reclaimed, not burned
    assert p.publish(step=1) == 1
    assert committed_versions(str(tmp_path)) == [1]


def test_failed_publish_advances_no_cursors(trainer, tmp_path):
    p = ModelPublisher(str(tmp_path), main_program=trainer.main,
                       scope=trainer.scope)
    assert p.publish(step=1) == 1
    trainer.step()
    faults.inject("publish.commit", "io", 1.0, 0, 1)
    with pytest.raises(Exception):
        p.publish(step=2)
    # the retried delta still carries everything trained since v1
    assert p.publish(step=2) == 2
    folded = load_version(str(tmp_path), 2)
    _assert_scope_matches(trainer.scope, folded)


def test_delta_chain_folds_bitwise_and_retires_safely(trainer, tmp_path):
    p = ModelPublisher(str(tmp_path), main_program=trainer.main,
                       scope=trainer.scope, full_every=4, max_versions=2)
    for s in range(6):
        trainer.step()
        p.publish(step=s)
    committed = committed_versions(str(tmp_path))
    # retention keeps the window plus every base a kept delta chains
    # through — all committed versions must still fold
    for v in committed:
        chain = resolve_chain(str(tmp_path), v)
        assert chain[-1] == v
    _assert_scope_matches(
        trainer.scope, load_version(str(tmp_path), committed[-1])
    )
    assert _counter("publish.versions") == 6
    assert obs.get_gauges()["publish.version"] == float(committed[-1])


# ---------------------------------------------------------------------------
# subscriber: epoch fence — all-or-nothing applies
# ---------------------------------------------------------------------------


def test_subscriber_incremental_applies_bitwise(trainer, tmp_path):
    p = ModelPublisher(str(tmp_path), main_program=trainer.main,
                       scope=trainer.scope, full_every=3)
    sub = ModelSubscriber(str(tmp_path), main_program=trainer.main,
                          scope=trainer.serving_scope())
    for s in range(5):
        trainer.step()
        v = p.publish(step=s)
        assert sub.poll() == v
        assert sub.version == v
        # the delta-applied scope is bitwise the cold fold of v — the
        # acceptance bar for a replica that never restarts
        _assert_scope_matches(sub._scope, load_version(str(tmp_path), v))
    assert _counter("publish.applies") == 5
    assert obs.get_gauges()["serving.model_version"] == float(sub.version)


def test_torn_payload_never_applies(trainer, tmp_path):
    p = ModelPublisher(str(tmp_path), main_program=trainer.main,
                       scope=trainer.scope)
    p.publish(step=1)
    sub = ModelSubscriber(str(tmp_path), main_program=trainer.main,
                          scope=trainer.serving_scope())
    assert sub.poll() == 1
    v1 = load_version(str(tmp_path), 1)
    trainer.step()
    v2 = p.publish(step=2)
    # poison the committed payload: flip bytes mid-file (a torn write a
    # crashed publisher could leave if commit.json were not the barrier)
    payload = os.path.join(version_dir(str(tmp_path), v2), PAYLOAD_NAME)
    size = os.path.getsize(payload)
    with open(payload, "r+b") as f:
        f.seek(size // 2)
        f.write(b"\xff" * min(64, size - size // 2))
    with pytest.raises(CheckpointCorruptionError):
        sub.poll()
    # the fence held: nothing was mutated, the version never moved
    assert sub.version == 1
    _assert_scope_matches(sub._scope, v1)
    assert obs.get_gauges()["serving.model_version"] == 1.0


def test_apply_fault_restores_pre_apply_state(trainer, tmp_path):
    p = ModelPublisher(str(tmp_path), main_program=trainer.main,
                       scope=trainer.scope)
    p.publish(step=1)
    sub = ModelSubscriber(str(tmp_path), main_program=trainer.main,
                          scope=trainer.serving_scope())
    sub.poll()
    v1 = load_version(str(tmp_path), 1)
    trainer.step()
    p.publish(step=2)
    faults.inject("publish.apply", "io", 1.0, 0, 1)
    with pytest.raises(Exception):
        sub.poll()
    # mid-apply failure: the snapshot restored, the version gauge never
    # flipped — no batch can ever observe a half-applied bundle
    assert sub.version == 1
    _assert_scope_matches(sub._scope, v1)
    assert _counter("publish.apply_failures") == 1
    # the seam healed: the next poll applies v2 fully
    assert sub.poll() == 2
    _assert_scope_matches(sub._scope, load_version(str(tmp_path), 2))


def test_respawn_after_killed_apply_matches_cold_load(trainer, tmp_path):
    """A worker SIGKILLed mid-apply respawns, catch-up-polls, and must be
    bitwise a cold load of the last committed version (the in-process
    equivalent: a fenced-off failed apply, then a FRESH scope + fresh
    subscriber — the respawned worker's exact path)."""
    p = ModelPublisher(str(tmp_path), main_program=trainer.main,
                       scope=trainer.scope)
    p.publish(step=1)
    sub = ModelSubscriber(str(tmp_path), main_program=trainer.main,
                          scope=trainer.serving_scope())
    sub.poll()
    trainer.step()
    v2 = p.publish(step=2)
    faults.inject("publish.apply", "io", 1.0, 0, 1)
    with pytest.raises(Exception):
        sub.poll()
    faults.clear()
    # the respawn: cold scope, new subscriber, catch-up before serving
    respawn = ModelSubscriber(str(tmp_path), main_program=trainer.main,
                              scope=trainer.serving_scope())
    assert respawn.poll() == v2
    _assert_scope_matches(
        respawn._scope, load_version(str(tmp_path), v2)
    )


def test_blocked_version_downgrades_via_full_refold(trainer, tmp_path):
    p = ModelPublisher(str(tmp_path), main_program=trainer.main,
                       scope=trainer.scope)
    p.publish(step=1)
    sub = ModelSubscriber(str(tmp_path), main_program=trainer.main,
                          scope=trainer.serving_scope())
    sub.poll()
    trainer.step()
    v2 = p.publish(step=2)
    sub.poll()
    assert sub.version == v2
    block_version(str(tmp_path), v2)
    assert read_blocked(str(tmp_path)) == {v2}
    assert latest_version(str(tmp_path)) == 1
    # rollback is data: the next poll targets the older version and
    # re-folds its chain — bitwise the cold start on v1
    assert sub.poll() == 1
    _assert_scope_matches(sub._scope, load_version(str(tmp_path), 1))
    assert _counter("publish.versions_blocked") == 1


def test_staleness_grows_between_applies_and_snaps_down(
    trainer, tmp_path
):
    p = ModelPublisher(str(tmp_path), main_program=trainer.main,
                       scope=trainer.scope)
    p.publish(step=1)
    sub = ModelSubscriber(str(tmp_path), main_program=trainer.main,
                          scope=trainer.serving_scope())
    sub.poll()
    t0 = time.time()
    s0 = sub.staleness_s(now=t0)
    assert s0 is not None and s0 >= 0.0
    # monotonic between applies...
    assert sub.staleness_s(now=t0 + 5.0) == pytest.approx(s0 + 5.0)
    assert sub.staleness_s(now=t0 + 9.0) > sub.staleness_s(now=t0 + 5.0)
    assert "serving.model_staleness_seconds" in obs.get_gauges()
    # ...and snaps down when a fresher bundle applies: at one and the
    # same instant it reads less than the replaced bundle would have
    # (no margin between two wall-clock intervals)
    replaced = sub.commit_time
    trainer.step()
    p.publish(step=2)
    sub.poll()
    assert sub.commit_time > replaced
    now = time.time() + 5.0
    assert sub.staleness_s(now=now) < now - replaced


def test_apply_stamps_heartbeat_with_model_version(trainer, tmp_path):
    hb_dir = tmp_path / "hb"
    hb = Heartbeat(str(hb_dir), rank=0)
    hb.beat()
    p = ModelPublisher(str(tmp_path / "pub"),
                       main_program=trainer.main, scope=trainer.scope)
    p.publish(step=1)
    sub = ModelSubscriber(str(tmp_path / "pub"),
                          main_program=trainer.main,
                          scope=trainer.serving_scope(), heartbeat=hb)
    sub.poll()
    with open(hb.path) as f:
        payload = json.load(f)
    # a fleet reader can tell which model version this worker serves
    # from its beat file alone
    assert payload["model_version"] == 1
    # sticky: every later beat carries it
    hb.beat()
    with open(hb.path) as f:
        assert json.load(f)["model_version"] == 1


# ---------------------------------------------------------------------------
# per-consumer delta-row cursors (embedding engine)
# ---------------------------------------------------------------------------


def _build_engine_model(seed=3):
    from paddle_tpu.embedding import EmbeddingEngine
    from paddle_tpu.framework import unique_name
    from paddle_tpu.models.deepfm import DeepFMConfig, deepfm

    cfg = DeepFMConfig(vocab_size=64, num_fields=4, embed_dim=4,
                       mlp_sizes=(8,))
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    scope = Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard():
        ids = fluid.data("feat_ids", [8, cfg.num_fields], "int64")
        label = fluid.data("label", [8, 1], "float32")
        loss, _pred = deepfm(ids, label, cfg)
        engine = EmbeddingEngine(main, startup, hot_rows=32)
        fluid.optimizer.SGD(0.1).minimize(loss)
        exe = fluid.Executor()
        exe.run(startup, scope=scope)
        engine.attach(scope)
    rng = np.random.RandomState(seed)

    def step(n=1):
        for _ in range(n):
            feed = {
                "feat_ids": (64 * rng.power(0.4, (8, cfg.num_fields))
                             ).astype(np.int64),
                "label": rng.rand(8, 1).astype(np.float32),
            }
            ff = engine.prepare_feed(feed, scope)
            exe.run(main, feed=ff, fetch_list=[loss], scope=scope)

    return main, scope, engine, step


def test_consumer_cursors_are_independent():
    main, scope, engine, step = _build_engine_model()
    step(2)
    group = engine.groups[0]
    # first "publish" payload: oracle(None) with no committed cursor =
    # no base = full; commit its marks
    oracles = engine.delta_row_oracles(consumer="pub")
    marks = {}
    for key, oracle in oracles.items():
        rows, mark = oracle(None)
        assert rows is None  # no base yet: store in full
        marks[key] = mark
    engine.commit_row_marks("pub", marks)
    pub_mark = group.consumer_mark("pub")
    assert pub_mark is not None
    # rows dirtied AFTER pub's payload...
    step(1)
    engine.flush(scope)
    # ...get consumed by a CHECKPOINT landing in between, committing its
    # OWN cursor — which must not touch pub's
    ck_oracles = engine.delta_row_oracles(consumer="ckpt")
    ck_marks = {}
    for key, oracle in ck_oracles.items():
        _rows, mark = oracle(None)
        ck_marks[key] = mark
    engine.commit_row_marks("ckpt", ck_marks)
    assert group.consumer_mark("pub") == pub_mark
    assert group.consumer_mark("ckpt") > pub_mark
    # a RESTARTED publisher (in-process marks gone: oracle(None)) falls
    # back to pub's committed cursor and still sees every row dirtied
    # since ITS last payload — the checkpoint swallowed nothing
    dirty = group.dirty_rows_since(pub_mark)
    assert dirty.size > 0
    for key, oracle in engine.delta_row_oracles(consumer="pub").items():
        rows, _mark = oracle(None)
        assert rows is not None
        np.testing.assert_array_equal(rows, dirty)
    # marks never regress: a stale late commit cannot re-expose rows
    group.commit_consumer_mark("ckpt", pub_mark)
    assert group.consumer_mark("ckpt") == ck_marks[
        max(ck_marks, key=lambda k: ck_marks[k])
    ]


def test_checkpoint_between_publishes_drops_no_rows(tmp_path):
    main, scope, engine, step = _build_engine_model()
    step(2)
    p = ModelPublisher(str(tmp_path), main_program=main, scope=scope,
                       engine=engine, full_every=8)
    p.publish(step=1)
    step(1)
    # a checkpoint consumes the delta-row oracles between two publishes
    # (the AsyncCheckpointer shape: its own consumer, its own commit)
    ck_marks = {}
    for key, oracle in engine.delta_row_oracles(
        consumer="checkpoint"
    ).items():
        _rows, mark = oracle(None)
        ck_marks[key] = mark
    engine.commit_row_marks("checkpoint", ck_marks)
    step(1)
    v = p.publish(step=2)
    # the invariant the cursors exist for: the folded publish chain
    # reproduces the trainer's host stores bitwise — every row dirtied
    # since v1 made it into v2 even though a checkpoint consumed the
    # oracles in between
    engine.flush(scope)
    folded = load_version(str(tmp_path), v)
    for g in engine.groups:
        for vname, store in g.host.items():
            key = f"{g.name}::host::{vname}"
            assert key in folded, key
            np.testing.assert_array_equal(folded[key], store)


# ---------------------------------------------------------------------------
# rollout: canary gating, staged rollout, automatic rollback
# ---------------------------------------------------------------------------


class _FakeWatcher:
    def __init__(self):
        self.findings = []
        self.breaching = False

    def poll(self):
        out, self.findings = self.findings, []
        return out


def _rollout_rig(trainer, tmp_path, n=2, **kwargs):
    p = ModelPublisher(str(tmp_path), main_program=trainer.main,
                       scope=trainer.scope)
    runners = {}
    for i in range(n):
        scope = trainer.serving_scope()
        sub = ModelSubscriber(str(tmp_path), main_program=trainer.main,
                              scope=scope, name=f"r{i}")
        runners[f"r{i}"] = SubscribedRunner(
            FrozenRunner(trainer.frozen, scope=scope), sub
        )
    rs = ReplicaSet(runners)
    watcher = _FakeWatcher()
    ctl = RolloutController(rs, str(tmp_path), watcher=watcher,
                            canary_soak_ticks=1, post_soak_ticks=4,
                            breach_ticks=2, **kwargs)
    return p, rs, watcher, ctl, runners


def test_canary_pass_promotes_fleet_wide(trainer, tmp_path):
    p, rs, _watcher, ctl, runners = _rollout_rig(trainer, tmp_path)
    v1 = p.publish(step=1)
    assert ctl.poll() == "canary"       # canary (r0) applied v1
    assert runners["r0"].version == v1
    assert runners["r1"].version is None
    assert ctl.poll() == "post"         # soak passed: staged rollout
    assert runners["r1"].version == v1
    assert ctl.version == v1
    assert _counter("publish.canary_passes") == 1
    assert _counter("publish.rollouts") == 1
    assert obs.get_gauges()["serving.model_version"] == float(v1)
    # replicas are bitwise the cold fold of the promoted version
    for r in runners.values():
        _assert_scope_matches(
            r.subscriber._scope, load_version(str(tmp_path), v1)
        )


def test_canary_fail_rolls_back_one_replica_and_blocks(
    trainer, tmp_path
):
    p, rs, watcher, ctl, runners = _rollout_rig(trainer, tmp_path)
    v1 = p.publish(step=1)
    ctl.poll(), ctl.poll(), ctl.poll()  # v1 rolled out + post soak
    while ctl.state != "idle":
        ctl.poll()
    trainer.step()
    v2 = p.publish(step=2)
    assert ctl.poll() == "canary"
    assert runners["r0"].version == v2
    # the canary soaks badly: a watcher p99 breach finding
    watcher.findings = [{"kind": "slo_breach", "severity": "error"}]
    assert ctl.poll() == "idle"
    # one-replica blast radius: the canary re-folded to last-good, the
    # follower never moved, the bad version is blocked for everyone
    assert runners["r0"].version == v1
    assert runners["r1"].version == v1
    assert read_blocked(str(tmp_path)) == {v2}
    assert _counter("publish.canary_fails") == 1
    assert _counter("publish.rollbacks") == 1
    _assert_scope_matches(
        runners["r0"].subscriber._scope, load_version(str(tmp_path), v1)
    )
    # blocked stays blocked: the controller does not retry the version
    assert ctl.poll() == "idle"
    assert runners["r0"].version == v1


def test_post_rollout_breach_rolls_back_fleet(trainer, tmp_path):
    p, rs, watcher, ctl, runners = _rollout_rig(trainer, tmp_path)
    v1 = p.publish(step=1)
    ctl.poll(), ctl.poll()
    while ctl.state != "idle":
        ctl.poll()
    trainer.step()
    v2 = p.publish(step=2)
    ctl.poll()                           # canary v2
    assert ctl.poll() == "post"          # fleet-wide on v2
    assert ctl.version == v2
    # sustained post-rollout breach (breach_ticks=2 consecutive polls)
    watcher.breaching = True
    assert ctl.poll() == "post"          # streak 1: not yet
    assert ctl.poll() == "idle"          # streak 2: automatic rollback
    watcher.breaching = False
    assert ctl.version == v1
    assert read_blocked(str(tmp_path)) == {v2}
    assert _counter("publish.rollbacks") == 1
    for r in runners.values():
        assert r.version == v1
        _assert_scope_matches(
            r.subscriber._scope, load_version(str(tmp_path), v1)
        )
    # a single transient breach tick must NOT roll back
    trainer.step()
    v3 = p.publish(step=3)
    ctl.poll(), ctl.poll()
    assert ctl.version == v3
    watcher.breaching = True
    ctl.poll()
    watcher.breaching = False
    assert ctl.poll() == "post"
    assert ctl.version == v3


def test_nonfinite_probe_fails_canary(trainer, tmp_path):
    probe = {"x": np.zeros((2, 8), np.float32)}
    p, rs, watcher, ctl, runners = _rollout_rig(
        trainer, tmp_path, probe_feed=probe
    )
    v1 = p.publish(step=1)
    ctl.poll(), ctl.poll()
    while ctl.state != "idle":
        ctl.poll()
    # poison the trainer: a bias full of NaN rides the next publish
    name = [
        n for n in trainer.scope.local_var_names() if "fc" in n
    ][0]
    trainer.scope.set_var(
        name, np.full_like(np.asarray(trainer.scope.find_var(name)),
                           np.nan)
    )
    v2 = p.publish(step=2)
    ctl.poll()                           # canary applies v2
    assert ctl.poll() == "idle"          # probe sees NaN: rollback
    assert _counter("publish.nonfinite_probes") >= 1
    assert _counter("publish.canary_fails") == 1
    assert read_blocked(str(tmp_path)) == {v2}
    assert all(r.version == v1 for r in runners.values())


def test_freeze_blocks_rollouts_and_brownout_rung_drives_it(
    trainer, tmp_path
):
    p, rs, _watcher, ctl, runners = _rollout_rig(trainer, tmp_path)
    v1 = p.publish(step=1)
    ctl.freeze()
    assert ctl.poll() == "idle"
    assert runners["r0"].version is None  # nothing moved while frozen
    assert _counter("publish.freezes") == 1
    ctl.unfreeze()
    assert ctl.poll() == "canary"
    assert runners["r0"].version == v1

    # the ladder's top rung freezes publishes; recovery unfreezes
    class _NoEndpoints:
        def endpoints(self):
            return {}

    bc = BrownoutController(_NoEndpoints(), slo_p99_s=0.1,
                            escalate_after=1, recover_after=1,
                            publish_control=ctl)
    assert "freeze_publishes" in DEFAULT_LADDER[-1]
    for _ in range(len(DEFAULT_LADDER) - 1):
        bc.observe(p99=5.0)
    assert bc.level == len(DEFAULT_LADDER) - 1
    assert ctl.frozen
    bc.observe(p99=0.01)
    assert not ctl.frozen


def test_restore_replica_rewarm_replays_warm_buckets(trainer):
    calls = []

    class _Counting:
        feed_names = ("x",)
        fetch_names = ("out",)

        def __init__(self, name):
            self.name = name

        def sample_spec(self, name):
            return ((8,), "float32")

        def run(self, feed):
            calls.append(self.name)
            return [np.zeros((len(feed["x"]), 1), np.float32)]

    rs = ReplicaSet({"a": _Counting("a"), "b": _Counting("b")})
    rs.warmup_run({"x": np.zeros((2, 8), np.float32)})
    rs.warmup_run({"x": np.zeros((4, 8), np.float32)})
    calls.clear()
    rs.drain_replica("a")
    rs.restore_replica("a", rewarm=True)
    # only the restored replica re-ran, once per warmed bucket size
    assert calls == ["a", "a"]
    assert _counter("serving.replica_rewarms") == 1
    # without rewarm the restore is knob-only
    rs.drain_replica("b")
    calls.clear()
    rs.restore_replica("b")
    assert calls == []


# ---------------------------------------------------------------------------
# fleet_report: publish-version skew across journal shards
# ---------------------------------------------------------------------------


def test_fleet_report_renders_publish_version_skew(tmp_path):
    now = time.time()
    for rank, version in ((0, 7.0), (1, 6.0)):
        with open(
            tmp_path / f"telemetry_rank{rank}.jsonl", "a"
        ) as f:
            f.write(json.dumps({
                "kind": "base", "rank": rank, "pid": 100 + rank,
                "seq": 1, "t": now - 1.0,
                "counters": {"publish.applies": 1},
                "gauges": {"serving.model_version": version,
                           "serving.model_staleness_seconds": 2.5},
            }) + "\n")
    fleet_report = _load_tool("fleet_report")
    report = fleet_report.build_report(str(tmp_path), now=now)
    by_rank = {s["rank"]: s for s in report["shards"]}
    assert by_rank[0]["model_version"] == 7
    assert by_rank[1]["model_version"] == 6
    skew = report["fleet"]["publish_skew"]
    assert skew["max_version"] == 7
    assert skew["min_version"] == 6
    assert skew["lagging_ranks"] == [1]
    assert "publish skew" in fleet_report.render(report)
