"""The readers of the program's own spans and kernel names (PR 24): the
idle-ownership arithmetic on hand-made intervals, each new per-layer
reader on a small recorded trace, and the two rehearsals that list the
new metrics."""

import json
import os

import pytest

from benchmark.harness import manifest as mf
from benchmark.harness import program_trace as pt
from benchmark.harness import xplane
from benchmark.harness.context import Run
from benchmark.harness.xplane import Line, Trace

from .test_rehearse import rehearse

DATA = os.path.join(os.path.dirname(__file__), "data")
NEW = ("executor_prologue_ms", "spmd_stage_ms",
       "pallas_attention_ms_per_step", "pallas_residual_ln_ms_per_step",
       "decode_idle_ms_per_token", "decode_idle_fetch_ms",
       "decode_idle_prologue_ms", "decode_idle_sample_ms",
       "generate_idle_unowned_pct")


def names(pieces):
    return [(a, b, span[0] if span else None) for a, b, span in pieces]


def test_a_gap_is_split_across_the_spans_it_crosses():
    spans = [("executor.fetch", 0, 50), ("serving.sample", 50, 80)]
    assert names(pt.own([(40, 70)], spans)) == [
        (40, 50, "executor.fetch"), (50, 70, "serving.sample"),
    ]


def test_the_inner_span_wins_however_long_the_outer_one_is():
    spans = [("serving.batch", 0, 10_000), ("serving.decode_loop", 100, 9000),
             ("executor.step", 200, 400), ("executor.fetch", 300, 390)]
    got = names(pt.own([(150, 450)], spans))
    assert got == [
        (150, 200, "serving.decode_loop"), (200, 300, "executor.step"),
        (300, 390, "executor.fetch"), (390, 400, "executor.step"),
        (400, 450, "serving.decode_loop"),
    ]


def test_a_span_on_another_thread_owns_what_it_covers_last():
    # the scheduler thread forms a batch while a client thread ingests:
    # whichever began last is innermost, whatever thread it is on
    spans = [("serving.form_batch", 0, 1000),      # scheduler thread
             ("serving.ingest", 400, 500)]          # a client's thread
    assert names(pt.own([(300, 600)], spans)) == [
        (300, 400, "serving.form_batch"), (400, 500, "serving.ingest"),
        (500, 600, "serving.form_batch"),
    ]


def test_what_no_span_covers_is_unowned():
    spans = [("executor.step", 100, 200)]
    pieces = pt.own([(0, 50), (150, 300)], spans)
    assert names(pieces) == [(0, 50, None), (150, 200, "executor.step"),
                             (200, 300, None)]
    assert pt.by_owner(pieces) == {pt.UNOWNED: 150.0, "executor.step": 50.0}
    assert pt.own([(0, 10)], []) == [(0, 10, None)]


def test_spans_that_begin_together_and_ended_spans_under_the_top():
    # two spans begin at the same instant: the shorter is the inner one;
    # `b` ends while `c` (begun later) is still on top of the heap, and
    # must not come back once `c` ends
    spans = [("a", 0, 100), ("a.child", 0, 40), ("b", 50, 60),
             ("c", 55, 90)]
    got = names(pt.own([(10, 20), (52, 58), (70, 95)], spans))
    assert got == [(10, 20, "a.child"), (52, 55, "b"), (55, 58, "c"),
                   (70, 90, "c"), (90, 95, "a")]


def test_inside_keeps_the_pieces_within_the_intervals():
    pieces = [(0, 10, None), (20, 30, None), (30, 40, None), (95, 120, None)]
    assert pt.inside(pieces, [(15, 45), (90, 110)]) == pieces[1:3]


def test_families_by_kernel_name():
    assert pt.family_of("%flash_tiled_dkv.3 = (bf16[4,4096,768]) "
                        "custom-call(...)") == "attention"
    assert pt.family_of("flash_attention_qkv_bwd.11") == "attention"
    assert pt.family_of("ring_block_fwd") == "attention"
    assert pt.family_of("fused_residual_fwd.2") == "residual_ln"
    assert pt.family_of("layer_norm_bwd.1") == "residual_ln"
    # a kernel compiled without a name (the parent commit's), any other op
    assert pt.family_of("traced.7") is None
    assert pt.family_of("fusion.12") is None


def ring(name, ts, dur, tid=1):
    return {"name": name, "ts": ts, "dur": dur, "tid": tid, "args": {}}


def test_per_call_ms_sums_the_children_of_each_call():
    rows = [
        ring("executor.step", 0, 10_000),
        ring("executor.prologue", 10, 4000),
        ring("executor.dispatch", 4020, 5000),   # back-pressure: not host
        ring("executor.writeback", 9030, 500),
        ring("executor.step", 20_000, 3000),
        ring("executor.prologue", 20_010, 2000),
        ring("executor.writeback", 22_500, 400),
        ring("executor.prologue", 20_010, 9, tid=2),  # another thread
        ring("executor.step", 40_000, 100),           # no child recorded
    ]
    got = pt.per_call_ms(rows, "executor.step",
                         ("executor.prologue", "executor.writeback"))
    assert got == [4.5, 2.4]


def test_spmd_stage_ms_is_the_median_stage_span():
    rows = [ring("spmd.dispatch", 0, 9000), ring("spmd.stage", 5, 7000),
            ring("spmd.stage", 10_000, 9000), ring("spmd.stage", 20_000, 11_000)]
    run = Run(attempted=1, failed=0, checks={}, end_to_end={}, facts={},
              spans=rows)
    assert mf.reader("spmd_stage_ms")(run) == 9.0
    run.spans = rows[:1]
    assert mf.reader("spmd_stage_ms")(run) is None


def _run(trace_file, **facts):
    """A Run over a recorded cut: the device's ops through the harness's
    own Trace, the program's spans through `program_trace.of`."""
    path = os.path.join(DATA, trace_file)
    trace = xplane.load(path)
    with open(os.path.join(DATA, "expected_pr24.json")) as f:
        expected = json.load(f)[trace_file]
    facts = {"program_trace": path, **expected["facts"], **facts}
    run = Run(attempted=1, failed=0, checks={}, end_to_end={}, facts=facts,
              spans=expected["spans"], trace=trace,
              window_ns=tuple(expected["window"]))
    return run, expected["metrics"]


def test_generate_readers_on_a_recorded_trace():
    run, want = _run("gpt2_small_generate_closed.pr24.v5e.json.gz")
    got = {name: mf.reader(name)(run) for name in want}
    assert got == pytest.approx(want, rel=1e-9)
    parts = (got["decode_idle_fetch_ms"] + got["decode_idle_prologue_ms"]
             + got["decode_idle_sample_ms"])
    assert parts <= got["decode_idle_ms_per_token"]
    assert 0 <= got["generate_idle_unowned_pct"] <= 100


def test_pallas_readers_on_a_recorded_trace():
    run, want = _run("bert_base_mlm_train.pr24.v5e.json.gz")
    got = {name: mf.reader(name)(run) for name in want}
    assert got == pytest.approx(want, rel=1e-9)
    # the two families are all of the kernels: nothing without a family
    assert set(pt.pallas_seconds(run)) == {"attention", "residual_ln"}
    from benchmark.layer_metrics import pallas_ms_per_step

    assert got["pallas_attention_ms_per_step"] \
        + got["pallas_residual_ln_ms_per_step"] \
        == pytest.approx(pallas_ms_per_step.read(run), rel=1e-9)


def test_the_parents_trace_gives_nothing_and_raises_nothing():
    """PR 22's recorded BERT step: kernels named `traced.<n>`, none of the
    program's spans. Every new reader returns None (the driver lays this
    PR's benchmark files over the parent's checkout)."""
    path = os.path.join(DATA, "bert_base_mlm_train.v5e.json.gz")
    trace = xplane.load(path)
    window = xplane.window_of(trace)
    ops = xplane.device_ops(trace, trace.device_planes()[0], window)
    custom = sorted({xplane.op_name(e[0]) for e in ops
                     if xplane.op_kind(e[0]) == "traced"})
    assert custom
    run = Run(attempted=1, failed=0, checks={}, end_to_end={},
              facts={"program_trace": path, "steps": 2,
                     "custom_call_names": custom, "device_kind": "TPU v5 lite"},
              trace=trace, window_ns=window)
    assert set(NEW) <= {m["name"] for m in mf.load()["per_layer"]}
    assert {name: mf.reader(name)(run) for name in NEW} \
        == dict.fromkeys(NEW)


def test_readers_on_hand_made_spans_and_ops():
    """Two decode steps: the device runs 100-400 and 600-900; the host's
    spans own the idle between."""
    ev = lambda name, a, b: (name, float(a), float(b - a))  # noqa: E731
    host = [
        ev("serving.batch", 0, 1000), ev("serving.decode_loop", 50, 1000),
        ev("executor.step", 60, 500), ev("executor.prologue", 60, 90),
        ev("executor.dispatch", 90, 110), ev("executor.writeback", 110, 115),
        ev("executor.fetch", 115, 480), ev("serving.sample", 500, 560),
        ev("executor.step", 560, 980), ev("executor.prologue", 560, 590),
        ev("executor.dispatch", 590, 610), ev("executor.writeback", 610, 615),
        ev("executor.fetch", 615, 970), ev("serving.sample", 980, 1000),
    ]
    trace = Trace([
        Line("/device:TPU:0", xplane.OPS_LINE,
             [ev("fusion.1", 100, 400), ev("fusion.1", 600, 900)]),
        Line("/host:CPU", "python", host),
    ])
    run = Run(attempted=1, failed=0, checks={}, end_to_end={},
              facts={"_program_trace": trace}, trace=trace,
              window_ns=(0.0, 1000.0))
    idle = pt.decode_idle(run)
    assert idle["steps"] == 2
    # idle inside the loop: 50-100, 400-600, 900-1000 = 350 ns
    assert idle["total_ns"] == 350
    assert idle["owners"] == {
        "executor.fetch": 150.0,        # 400-480, 900-970
        "serving.sample": 80.0,         # 500-560, 980-1000
        "executor.prologue": 60.0,      # 60-90, 560-590
        "executor.step": 30.0,          # 480-500, 970-980
        "executor.dispatch": 20.0,      # 90-100, 590-600
        "serving.decode_loop": 10.0,    # 50-60
    }
    assert mf.reader("decode_idle_ms_per_token")(run) \
        == pytest.approx(350 / 2 / 1e6)
    assert mf.reader("decode_idle_fetch_ms")(run) \
        == pytest.approx(150 / 2 / 1e6)
    assert mf.reader("decode_idle_prologue_ms")(run) \
        == pytest.approx(80 / 2 / 1e6)
    assert mf.reader("decode_idle_sample_ms")(run) \
        == pytest.approx(80 / 2 / 1e6)
    # of the window's 400 idle ns, 0-50 lies under serving.batch alone
    # and 30 under executor.step itself
    assert mf.reader("generate_idle_unowned_pct")(run) \
        == pytest.approx(100.0 * 80 / 400)


@pytest.mark.parametrize("cell,new", [
    ("gpt2_small_generate_closed",
     {"decode_idle_ms_per_token", "decode_idle_fetch_ms",
      "decode_idle_prologue_ms", "decode_idle_sample_ms",
      "generate_idle_unowned_pct"}),
    ("bert_base_mlm_train_dp4", {"executor_prologue_ms", "spmd_stage_ms"}),
])
def test_traced_rehearsal_lists_the_new_metrics(cell, new):
    line = rehearse(mf.ROOT, cell, "--trace", "1")
    assert line["correct"] is True
    assert new <= set(line["rehearsal"]["reported"])
    assert line["metrics"] == {}
