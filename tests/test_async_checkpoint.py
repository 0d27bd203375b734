"""Async tiered checkpointing: the snapshot/publish pipeline
(fleet.AsyncCheckpointer), bounded-queue coalescing, delta chains +
row-oracle tiering + compression, the TrainGuard rollback/drain
lifecycle, and the heartbeat-during-publish liveness contract.

The end-to-end SIGKILL-mid-async-publish proof lives in
tools/resume_audit.py --async (run by the ci.sh chaos stage and by the
slow test at the bottom); these tests pin each layer in isolation.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import errors, layers, observability
from paddle_tpu.fleet import collective as fc
from paddle_tpu.fleet.role_maker import UserDefinedRoleMaker
from paddle_tpu.framework import unique_name
from paddle_tpu.framework.scope import global_scope
from paddle_tpu.resilience import StepWatchdog, TrainGuard, faults

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

HANG_ENV = "PADDLE_TPU_FAULT_HANG_SECONDS"


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    old = os.environ.pop(HANG_ENV, None)
    yield
    faults.clear()
    if old is None:
        os.environ.pop(HANG_ENV, None)
    else:
        os.environ[HANG_ENV] = old


@pytest.fixture
def fresh_programs():
    main, startup = fluid.Program(), fluid.Program()
    scope = fluid.framework.scope.Scope()
    with fluid.program_guard(main, startup), fluid.scope_guard(scope), \
            unique_name.guard():
        yield main


def _build_model():
    x = fluid.data("x", [-1, 4])
    y = fluid.data("y", [-1, 1])
    pred = layers.fc(x, 1, param_attr=fluid.ParamAttr(name="ac_w"))
    loss = layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(0.05).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    return exe, loss


def _fleet(rank=0, nranks=1):
    f = fc.Fleet()
    f.init(UserDefinedRoleMaker(current_id=rank, worker_num=nranks))
    return f


def _persistable_state():
    scope = global_scope()
    return {
        v.name: np.asarray(scope.find_var(v.name)).copy()
        for v in fluid.default_main_program().list_vars()
        if v.persistable and scope.find_var(v.name) is not None
    }


def _step(exe, loss, rng):
    xa = rng.randn(8, 4).astype(np.float32)
    exe.run(feed={"x": xa, "y": xa @ np.ones((4, 1), np.float32)},
            fetch_list=[loss])


def _counter(name):
    return observability.snapshot()["counters"].get(name, 0)


# -- the basic pipeline ------------------------------------------------------
def test_async_save_commits_bitwise_snapshot(tmp_path, fresh_programs):
    exe, loss = _build_model()
    fleet = _fleet()
    rng = np.random.RandomState(0)
    path = str(tmp_path / "ck")
    with fc.AsyncCheckpointer(fleet, path, executor=exe) as saver:
        _step(exe, loss, rng)
        want = _persistable_state()
        handle = saver.save(fc.TrainStatus(0, global_step=1))
        # the snapshot is immutable: training past the save must not
        # change what lands on disk
        _step(exe, loss, rng)
        assert handle.result(timeout=30) == 0
    status = fleet.load_check_point(exe, path)
    assert status.global_step == 1
    for name, arr in want.items():
        got = np.asarray(global_scope().find_var(name))
        assert got.tobytes() == arr.tobytes(), name
    h = observability.snapshot()["histograms"]
    assert h["checkpoint.snapshot_latency"]["count"] >= 1
    assert h["checkpoint.publish_latency"]["count"] >= 1
    assert h["checkpoint.save_bandwidth"]["count"] >= 1


def test_save_returns_before_slow_publish(tmp_path, fresh_programs):
    exe, loss = _build_model()
    fleet = _fleet()
    path = str(tmp_path / "ck")
    os.environ[HANG_ENV] = "1.5"
    saver = fc.AsyncCheckpointer(fleet, path, executor=exe)
    try:
        _step(exe, loss, np.random.RandomState(0))
        faults.inject("checkpoint.publish", "hang", 1.0, 0, 1)
        t0 = time.perf_counter()
        handle = saver.save(fc.TrainStatus(0, global_step=1))
        stall = time.perf_counter() - t0
        assert stall < 1.0, (
            f"save() blocked {stall:.2f}s — the publish hang leaked onto "
            "the step loop"
        )
        assert handle.result(timeout=30) == 0
    finally:
        saver.close()


def test_coalesce_keeps_newest_and_resolves_superseded(
    tmp_path, fresh_programs
):
    exe, loss = _build_model()
    fleet = _fleet()
    rng = np.random.RandomState(0)
    path = str(tmp_path / "ck")
    os.environ[HANG_ENV] = "0.4"
    saver = fc.AsyncCheckpointer(fleet, path, executor=exe,
                                 remain_all_checkpoint=True)
    try:
        # first publish is slowed: the three saves behind it land while
        # it is in flight, so the queue must coalesce them to one
        faults.inject("checkpoint.publish", "hang", 1.0, 0, 1)
        handles, states = [], []
        for i in range(4):
            _step(exe, loss, rng)
            states.append(_persistable_state())
            handles.append(saver.save(fc.TrainStatus(i, global_step=i + 1)))
        final = handles[0].result(timeout=30)
        # every handle resolves (superseded ones through their successor)
        results = [h.result(timeout=30) for h in handles]
        assert results[-1] == max(results)
        saver.wait(timeout=30)
    finally:
        saver.close()
    assert _counter("checkpoint.coalesced") >= 1
    # the NEWEST state is what the last commit carries
    status = fleet.load_check_point(exe, path)
    assert status.global_step == 4
    for name, arr in states[-1].items():
        got = np.asarray(global_scope().find_var(name))
        assert got.tobytes() == arr.tobytes(), name
    assert final is not None


def test_block_policy_publishes_every_save(tmp_path, fresh_programs):
    exe, loss = _build_model()
    fleet = _fleet()
    rng = np.random.RandomState(0)
    path = str(tmp_path / "ck")
    with fc.AsyncCheckpointer(fleet, path, executor=exe,
                              queue_policy="block",
                              remain_all_checkpoint=True) as saver:
        for i in range(3):
            _step(exe, loss, rng)
            saver.save(fc.TrainStatus(i, global_step=i + 1))
        saver.wait(timeout=30)
    dirs = [d for d in os.listdir(path) if d.startswith("__paddle_")]
    assert len(dirs) == 3, dirs


def test_publish_failure_surfaces_and_transient_heals(
    tmp_path, fresh_programs
):
    exe, loss = _build_model()
    fleet = _fleet()
    path = str(tmp_path / "ck")
    _step(exe, loss, np.random.RandomState(0))
    # one injected fault heals through the checkpoint.save retry policy
    faults.inject("checkpoint.publish", "io", 1.0, 0, 1)
    r0 = _counter("resilience.retries.checkpoint.save")
    with fc.AsyncCheckpointer(fleet, path, executor=exe) as saver:
        assert saver.save(fc.TrainStatus(0)).result(timeout=30) == 0
    assert _counter("resilience.retries.checkpoint.save") - r0 >= 1
    # a persistent fault exhausts the retries and must surface loudly
    faults.inject("checkpoint.publish", "io", 1.0, 0, 50)
    saver = fc.AsyncCheckpointer(fleet, str(tmp_path / "ck2"), executor=exe)
    handle = saver.save(fc.TrainStatus(0))
    with pytest.raises(errors.ExternalError):
        handle.result(timeout=30)
    with pytest.raises(errors.ExternalError):
        saver.wait(timeout=30)
    faults.clear()
    with pytest.raises(errors.ExternalError):
        saver.save(fc.TrainStatus(1))  # dead saver refuses new work
    assert _counter("checkpoint.publish_failures") >= 1


def test_snapshot_fault_seam_retries(tmp_path, fresh_programs):
    exe, loss = _build_model()
    fleet = _fleet()
    path = str(tmp_path / "ck")
    faults.inject("checkpoint.snapshot", "io", 1.0, 0, 1)
    r0 = _counter("resilience.retries.checkpoint.snapshot")
    with fc.AsyncCheckpointer(fleet, path, executor=exe) as saver:
        assert saver.save(fc.TrainStatus(0)).result(timeout=30) == 0
    assert _counter("resilience.retries.checkpoint.snapshot") - r0 >= 1


# -- tiered saves: delta chains, row oracles, compression --------------------
def test_delta_chain_roundtrip_and_forced_full(tmp_path, fresh_programs):
    exe, loss = _build_model()
    fleet = _fleet()
    rng = np.random.RandomState(0)
    path = str(tmp_path / "ck")
    with fc.AsyncCheckpointer(fleet, path, executor=exe, delta=True,
                              full_every=2, queue_policy="block",
                              remain_all_checkpoint=True) as saver:
        for i in range(4):
            _step(exe, loss, rng)
            saver.save(fc.TrainStatus(i, global_step=i + 1)).result(30)
        want = _persistable_state()
    kinds = {
        int(d.rsplit("__", 1)[-1]): os.path.exists(
            os.path.join(path, d, "delta.json")
        )
        for d in os.listdir(path) if d.startswith("__paddle_")
    }
    # 0 full, 1-2 delta chain, 3 forced full (chain never exceeds K=2)
    assert kinds == {0: False, 1: True, 2: True, 3: False}, kinds
    status = fleet.load_check_point(exe, path)
    assert status.global_step == 4
    for name, arr in want.items():
        got = np.asarray(global_scope().find_var(name))
        assert got.tobytes() == arr.tobytes(), name
    # an explicitly requested mid-chain delta reconstructs too
    assert fleet.load_check_point(exe, path, checkpoint_no=2).global_step == 3
    assert _counter("checkpoint.delta_saves") >= 2
    assert _counter("resilience.checkpoint_chain_loads") >= 1


def test_delta_broken_chain_falls_back(tmp_path, fresh_programs):
    import shutil

    exe, loss = _build_model()
    fleet = _fleet()
    rng = np.random.RandomState(0)
    path = str(tmp_path / "ck")
    with fc.AsyncCheckpointer(fleet, path, executor=exe, delta=True,
                              full_every=1, queue_policy="block",
                              remain_all_checkpoint=True) as saver:
        for i in range(4):  # 0 full, 1 delta, 2 full, 3 delta
            _step(exe, loss, rng)
            saver.save(fc.TrainStatus(i, global_step=i + 1)).result(30)
    # rot the newest delta's base away: candidate 3's chain is broken,
    # candidate 1's chain (0 -> 1) still loads
    shutil.rmtree(os.path.join(path, "__paddle_checkpoint__2"))
    b0 = _counter("resilience.checkpoint_chain_broken")
    status = fleet.load_check_point(exe, path)
    assert status.global_step == 2, status
    assert _counter("resilience.checkpoint_chain_broken") - b0 >= 1
    # an explicitly requested broken delta refuses instead of falling back
    with pytest.raises(
        (errors.ResumeMismatchError, errors.CheckpointCorruptionError)
    ):
        fleet.load_check_point(exe, path, checkpoint_no=3)


def test_rotation_spares_delta_chain_bases(tmp_path, fresh_programs):
    exe, loss = _build_model()
    fleet = _fleet()
    rng = np.random.RandomState(0)
    path = str(tmp_path / "ck")
    with fc.AsyncCheckpointer(fleet, path, executor=exe, delta=True,
                              full_every=3, queue_policy="block",
                              max_checkpoint_num=2) as saver:
        for i in range(4):  # 0 full, 1-3 deltas based (transitively) on 0
            _step(exe, loss, rng)
            saver.save(fc.TrainStatus(i, global_step=i + 1)).result(30)
        want = _persistable_state()
    present = sorted(
        int(d.rsplit("__", 1)[-1])
        for d in os.listdir(path) if d.startswith("__paddle_")
    )
    # rotation wanted to keep only {2, 3}, but their chain needs 0 and 1
    assert present == [0, 1, 2, 3], present
    status = fleet.load_check_point(exe, path)
    assert status.global_step == 4
    for name, arr in want.items():
        got = np.asarray(global_scope().find_var(name))
        assert got.tobytes() == arr.tobytes(), name


def test_row_oracle_delta_and_aux_roundtrip(tmp_path, fresh_programs):
    exe, loss = _build_model()
    fleet = _fleet()
    rng = np.random.RandomState(0)
    path = str(tmp_path / "ck")
    table = rng.randn(4096, 16).astype(np.float32)
    tick, dirty = [0], [np.array([], np.int64)]

    def oracle(last):
        mark = tick[0]
        if last is None:
            return None, mark
        return dirty[0], mark

    with fc.AsyncCheckpointer(
        fleet, path, executor=exe, delta=True, full_every=4,
        queue_policy="block", remain_all_checkpoint=True,
        row_oracles={"tab": oracle},
    ) as saver:
        tables = []
        for i in range(3):
            _step(exe, loss, rng)
            if i:
                rows = rng.choice(4096, 7, replace=False)
                table[rows] += 1.0
                dirty[0] = np.sort(rows.astype(np.int64))
                tick[0] += 1
            saver.save(fc.TrainStatus(i, global_step=i + 1),
                       aux={"tab": table}).result(30)
            dirty[0] = np.array([], np.int64)
            tables.append(table.copy())
    # the delta aux payloads carry only the dirty rows, not 4096x16
    full_aux = os.path.getsize(
        os.path.join(path, "__paddle_checkpoint__0", "__aux__.npz")
    )
    delta_aux = os.path.getsize(
        os.path.join(path, "__paddle_checkpoint__2", "__aux__.npz")
    )
    assert delta_aux < full_aux / 10, (full_aux, delta_aux)
    status = fleet.load_check_point(exe, path, load_aux=True)
    assert status.aux["tab"].tobytes() == tables[-1].tobytes()
    mid = fleet.load_check_point(exe, path, checkpoint_no=1, load_aux=True)
    assert mid.aux["tab"].tobytes() == tables[1].tobytes()


def test_compressed_payload_roundtrip_and_smaller(tmp_path, fresh_programs):
    exe, loss = _build_model()
    # a compressible ballast persistable (zeros) dominates the payload
    main = fluid.default_main_program()
    main.global_block.create_parameter("ac_ballast", [2048, 32], "float32")
    global_scope().set_var("ac_ballast", np.zeros((2048, 32), np.float32))
    fleet = _fleet()
    _step(exe, loss, np.random.RandomState(0))
    want = _persistable_state()
    plain, packed = str(tmp_path / "plain"), str(tmp_path / "packed")
    with fc.AsyncCheckpointer(fleet, plain, executor=exe) as saver:
        saver.save(fc.TrainStatus(0)).result(30)
    with fc.AsyncCheckpointer(fleet, packed, executor=exe,
                              compress=True) as saver:
        saver.save(fc.TrainStatus(0)).result(30)
    p0 = os.path.getsize(
        os.path.join(plain, "__paddle_checkpoint__0", "__params__.npz")
    )
    p1 = os.path.getsize(
        os.path.join(packed, "__paddle_checkpoint__0", "__params__.npz")
    )
    assert p1 < p0 / 2, (p0, p1)
    fleet.load_check_point(exe, packed)
    for name, arr in want.items():
        got = np.asarray(global_scope().find_var(name))
        assert got.tobytes() == arr.tobytes(), name


# -- lifecycle: rollback race + drain ----------------------------------------
def test_rollback_cancels_pending_awaits_inflight(tmp_path, fresh_programs,
                                                  monkeypatch):
    exe, loss = _build_model()
    fleet = _fleet()
    rng = np.random.RandomState(0)
    path = str(tmp_path / "ck")
    saver = fc.AsyncCheckpointer(fleet, path, executor=exe,
                                 remain_all_checkpoint=True)
    # the in-flight publish stays at its fault seam until the rollback
    # has cancelled the snapshot queued behind it, and that snapshot is
    # taken only once the publish is at the seam (earlier it would
    # coalesce with it): the order the test is about, held by the
    # pipeline's own state and not by two sleeps
    queued = []
    at_seam = threading.Event()
    real_fault_point = faults.fault_point

    def wedged_until_cancel(site, *args, **kwargs):
        if site == "checkpoint.publish":
            at_seam.set()
            deadline = time.monotonic() + 20
            while not (queued and queued[0].cancelled):
                if time.monotonic() > deadline:
                    raise TimeoutError("the queued snapshot was never "
                                       "cancelled")
                time.sleep(0.002)
        return real_fault_point(site, *args, **kwargs)

    try:
        _step(exe, loss, rng)
        saver.save(fc.TrainStatus(0, global_step=1)).result(30)
        monkeypatch.setattr(faults, "fault_point", wedged_until_cancel)
        _step(exe, loss, rng)
        inflight_state = _persistable_state()
        inflight = saver.save(fc.TrainStatus(1, global_step=2))
        assert at_seam.wait(30)
        _step(exe, loss, rng)
        pending = saver.save(fc.TrainStatus(2, global_step=3))
        queued.append(pending)
        with TrainGuard(exe, checkpointer=saver, max_bad_steps=1,
                        snapshot=False) as g:
            bad = np.full((8, 4), np.nan, np.float32)
            out = g.step(feed={"x": bad, "y": np.ones((8, 1), np.float32)},
                         fetch_list=[loss])
        assert out is None and g.rollbacks == 1
        # the queued snapshot was cancelled, the in-flight one committed
        assert pending.cancelled
        with pytest.raises(errors.UnavailableError):
            pending.result(timeout=1)
        assert inflight.result(timeout=30) is not None
        # rollback restored the newest COMMITTED state (the in-flight
        # publish that quiesce awaited), not the cancelled one
        assert g.train_status.global_step == 2
        for name, arr in inflight_state.items():
            got = np.asarray(global_scope().find_var(name))
            assert got.tobytes() == arr.tobytes(), name
    finally:
        saver.close()
    assert _counter("checkpoint.cancelled") >= 1


def test_drain_awaits_async_final_checkpoint(tmp_path, fresh_programs):
    exe, loss = _build_model()
    fleet = _fleet()
    path = str(tmp_path / "ck")
    os.environ[HANG_ENV] = "0.5"
    saver = fc.AsyncCheckpointer(fleet, path, executor=exe,
                                 remain_all_checkpoint=True)
    try:
        faults.inject("checkpoint.publish", "hang", 1.0, 0, 1)
        with TrainGuard(exe, checkpointer=saver, exit_on_preempt=False,
                        train_status=fc.TrainStatus(3, global_step=7)) as g:
            _step(exe, loss, np.random.RandomState(0))
            g.draining = True  # what the SIGTERM handler sets
            assert g.step(feed={"x": np.ones((8, 4), np.float32),
                                "y": np.ones((8, 1), np.float32)},
                          fetch_list=[loss]) is None
        assert g.preempted
        # by the time the drain returned, the final checkpoint is
        # COMMITTED despite the slowed publish — never half-published
        status = fleet.load_check_point(exe, path)
        assert status.global_step == 7
    finally:
        saver.close()


# -- heartbeat during publish (satellite regression) -------------------------
def test_slow_sync_publish_starves_watchdog_without_heartbeat(
    tmp_path, fresh_programs
):
    exe, _ = _build_model()
    fleet = _fleet()
    os.environ[HANG_ENV] = "1.2"
    faults.inject("fs.upload", "hang", 1.0, 0, 1)
    with StepWatchdog(timeout=0.4, poll_interval=0.05) as wd:
        fleet.save_check_point(exe, str(tmp_path / "ck"), fc.TrainStatus(0))
    assert wd.stalls >= 1  # the failure mode the heartbeat fixes


def test_slow_sync_publish_with_heartbeat_never_reads_as_hang(
    tmp_path, fresh_programs
):
    exe, _ = _build_model()
    fleet = _fleet()
    os.environ[HANG_ENV] = "2.0"
    faults.inject("fs.upload", "hang", 1.0, 0, 1)
    with StepWatchdog(timeout=0.8, poll_interval=0.05) as wd:
        fleet.save_check_point(exe, str(tmp_path / "ck"), fc.TrainStatus(0),
                               heartbeat=wd.touch)
    assert wd.stalls == 0


def test_slow_async_publish_with_heartbeat_never_reads_as_hang(
    tmp_path, fresh_programs
):
    exe, loss = _build_model()
    fleet = _fleet()
    os.environ[HANG_ENV] = "2.0"
    faults.inject("fs.upload", "hang", 1.0, 0, 1)
    with StepWatchdog(timeout=0.8, poll_interval=0.05) as wd:
        with fc.AsyncCheckpointer(fleet, str(tmp_path / "ck"), executor=exe,
                                  heartbeat=wd.touch) as saver:
            saver.save(fc.TrainStatus(0)).result(timeout=30)
    assert wd.stalls == 0
    assert _counter("resilience.faults_injected.fs.upload") >= 1


def test_heartbeat_touch_is_thread_safe_and_keeps_step(tmp_path):
    from paddle_tpu.resilience.health import Heartbeat, read_beat

    hb = Heartbeat(str(tmp_path / "hb"), rank=0)
    hb.beat()
    t0 = read_beat(hb.path)
    time.sleep(0.01)
    hb.touch()
    t1 = read_beat(hb.path)
    assert t1["step"] == t0["step"] == 1
    assert t1["time"] > t0["time"]


# -- the full kill/resume-mid-async-publish audit (slow) ---------------------
@pytest.mark.slow
def test_async_resume_audit_end_to_end(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "resume_audit.py"),
         "--async", "--out", str(tmp_path / "audit")],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "resume audit OK" in proc.stdout
