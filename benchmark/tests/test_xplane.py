"""The trace reduction: interval arithmetic on hand-made intervals, and
the whole reduction on a small recorded trace kept beside this file."""

import os

import pytest

from benchmark.harness import xplane
from benchmark.harness.xplane import Line, Trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def ev(name, start, end):
    return (name, float(start), float(end - start))


def test_union_merges_overlaps_and_touching():
    events = [ev("a", 0, 10), ev("b", 5, 12), ev("c", 12, 15),
              ev("d", 20, 30), ev("zero", 40, 40)]
    assert xplane.union(events) == [(0, 15), (20, 30)]
    assert xplane.total(xplane.union(events)) == 25


def test_subtract_and_gaps():
    busy = [(0, 15), (20, 30)]
    assert xplane.gaps(busy, 0, 40) == [(15, 20), (30, 40)]
    assert xplane.gaps(busy, 5, 25) == [(15, 20)]
    assert xplane.subtract([(0, 10), (20, 30)], [(5, 25)]) == \
        [(0, 5), (25, 30)]
    assert xplane.subtract([(0, 10)], [(2, 3), (4, 5)]) == \
        [(0, 2), (3, 4), (5, 10)]
    assert xplane.subtract([(0, 10)], []) == [(0, 10)]


def test_clip_cuts_to_window():
    events = [ev("a", 0, 10), ev("b", 8, 30), ev("c", 40, 50)]
    assert xplane.clip(events, 5, 20) == [("a", 5.0, 5.0), ("b", 8.0, 12.0)]


def test_names():
    assert xplane.op_name("%fusion.3 = f32[8] fusion(...)") == "fusion.3"
    assert xplane.op_kind("fusion.3") == "fusion"
    assert xplane.op_kind("%all-reduce-start.1") == "all-reduce-start"
    assert xplane.is_collective("all-reduce-start.1")
    assert xplane.is_collective("%all-gather-done.7 = ...")
    assert xplane.is_collective("collective-permute.2")
    assert not xplane.is_collective("fusion.12")
    assert not xplane.is_collective("reduce.4")


def test_exposed_collective_time():
    # all-reduce 0-10 with a fusion running 2-6 beside it (another line):
    # 10 in collectives, 6 of them exposed; the second all-reduce is
    # wholly hidden behind fusion.2
    events = [ev("all-reduce.1", 0, 10), ev("fusion.1", 2, 6),
              ev("all-reduce-start.2", 20, 21), ev("fusion.2", 19, 30),
              ev("all-reduce-done.2", 28, 30)]
    coll, exposed = xplane.collective_seconds(events)
    assert coll == pytest.approx(13e-9)
    assert exposed == pytest.approx(6e-9)
    # the asynchronous one as the "Async XLA Ops" line has it, from start
    # to done: 10 in flight, of which fusion.2 hides 9 and the first
    # instant (20-21 is covered by fusion.2 too) none
    in_flight = [ev("all-reduce-start.2", 20, 30)]
    coll, exposed = xplane.collective_seconds(events, in_flight)
    assert coll == pytest.approx(20e-9)
    assert exposed == pytest.approx(6e-9)


def _trace():
    ops = [ev("fusion.1", 100, 200), ev("custom-call.1", 200, 260),
           ev("fusion.2", 400, 500), ev("copy.1", 900, 950)]
    host = [ev("bench.window", 50, 1000), ev("bench.exe_run", 60, 90),
            ev("bench.fetch_loss", 250, 420), ev("bench.exe_run", 500, 880)]
    return Trace([
        Line("/device:TPU:0", xplane.OPS_LINE, ops),
        Line("/host:CPU", "main", host),
        Line("/host:CPU", "other", [ev("unrelated", 0, 2000)]),
    ])


def test_window_busy_idle_and_owners():
    trace = _trace()
    window = xplane.window_of(trace)
    assert window == (50.0, 1000.0)
    assert trace.device_planes() == ["/device:TPU:0"]
    busy = xplane.busy_seconds(trace, window)
    assert busy["/device:TPU:0"] == pytest.approx(310e-9)
    owners = dict(xplane.idle_gap_owners(trace, "/device:TPU:0", window))
    # gaps: 50-100 (exe_run covers 30), 260-400 (fetch_loss), 500-900
    # (exe_run covers 380), 950-1000 (nothing)
    assert owners["bench.exe_run"] == pytest.approx(450e-9)
    assert owners["bench.fetch_loss"] == pytest.approx(140e-9)
    assert owners["unannotated"] == pytest.approx(50e-9)
    assert sum(owners.values()) == pytest.approx((950 - 310) * 1e-9)


def test_kinds_and_busy_inside():
    trace = _trace()
    ops = trace.line("/device:TPU:0", xplane.OPS_LINE)
    kinds = xplane.sum_by(ops, xplane.op_kind)
    assert list(kinds) == ["fusion", "custom-call", "copy"]
    assert kinds["fusion"] == pytest.approx(200e-9)
    starts = [e[1] for e in ops]
    assert xplane.busy_inside(ops, starts, 150, 450) == 160.0
    assert xplane.busy_inside(ops, starts, 600, 800) == 0.0


def test_dump_and_load_round_trip(tmp_path):
    trace = _trace()
    path = str(tmp_path / "t.json.gz")
    xplane.dump(trace, path, window=(50.0, 1000.0))
    again = xplane.load(path)
    assert again.line("/device:TPU:0", xplane.OPS_LINE) == \
        trace.line("/device:TPU:0", xplane.OPS_LINE)
    assert xplane.window_of(again) == (50.0, 1000.0)


RECORDED = os.path.join(DATA, "bert_base_mlm_train.v5e.json.gz")


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace beside the test")
def test_recorded_trace_reduces_to_the_numbers_written_beside_it():
    """A cut of `bert_base_mlm_train` recorded on a TPU v5e (PR 22;
    `expected.json` says how much); `expected.json` holds what the
    reduction gave when the trace was read by hand and checked against a
    numpy sweep over the raw events."""
    import json

    with open(os.path.join(DATA, "expected.json")) as f:
        want = json.load(f)
    trace = xplane.load(RECORDED)
    window = xplane.window_of(trace)
    plane = trace.device_planes()[0]
    ops = xplane.device_ops(trace, plane, window)
    busy = xplane.total(xplane.union(ops)) / 1e9
    span = (window[1] - window[0]) / 1e9
    assert busy <= span
    assert busy == pytest.approx(want["busy_s"], rel=1e-9)
    assert span == pytest.approx(want["window_s"], rel=1e-9)
    kinds = xplane.sum_by(ops, xplane.op_kind)
    for kind, seconds in want["kinds"].items():
        assert kinds[kind] == pytest.approx(seconds, rel=1e-9)
    names = set(want["custom_call_names"])
    pallas = sum(e[2] for e in ops if xplane.op_name(e[0]) in names) / 1e9
    assert pallas == pytest.approx(want["pallas_s"], rel=1e-9)
    owners = dict(xplane.idle_gap_owners(trace, plane, window))
    assert sum(owners.values()) == pytest.approx(span - busy, rel=1e-6)


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded trace beside the test")
def test_readers_on_the_recorded_trace():
    """The per-layer readers that take their number from the device
    trace, on the two recorded steps (164 ms a step on the chip)."""
    import json

    from benchmark.harness import breakdown
    from benchmark.harness import manifest as mf
    from benchmark.harness.context import Run

    with open(os.path.join(DATA, "expected.json")) as f:
        want = json.load(f)
    trace = xplane.load(RECORDED)
    run = Run(attempted=2, failed=0, checks={}, end_to_end={},
              facts={"steps": 2,
                     "custom_call_names": want["custom_call_names"]},
              trace=trace, window_ns=xplane.window_of(trace))
    assert mf.reader("device_step_ms")(run) == \
        pytest.approx(1e3 * want["busy_s"] / 2)
    assert mf.reader("pallas_ms_per_step")(run) == \
        pytest.approx(1e3 * want["pallas_s"] / 2)
    assert mf.reader("device_idle_pct")(run) == \
        pytest.approx(100 * (1 - want["busy_s"] / want["window_s"]))
    assert mf.reader("collective_ms_per_step")(run) is None
    assert mf.reader("decode_device_ms")(run) is None
    record = breakdown.busy_and_window(run)
    assert record["busy_s"] == pytest.approx(want["busy_s"])
    top = breakdown.of(run)
    assert top["device_ops"][0][0] == "fusion"
    assert len(top["device_ops"]) <= 10 and len(top["idle_gaps"]) <= 10
