"""Device time by phase and section (PR 35): the arithmetic of
`harness/sections.py` on hand-made intervals, the thirteen readers on a
hand-made batch, on a parent-shaped capture, and in a traced rehearsal."""

import os

import pytest

from benchmark.harness import manifest as mf
from benchmark.harness import sections, xplane
from benchmark.harness.context import Run
from benchmark.harness.xplane import Line, Trace

from .test_rehearse import rehearse

DATA = os.path.join(os.path.dirname(__file__), "data")
NEW = tuple(
    [f"prefill_{s}_ms" for s in sections.SECTIONS]
    + [f"decode_{s}_ms_per_token" for s in sections.SECTIONS]
    + ["device_unscoped_pct", "prefill_idle_ms", "handover_idle_ms"]
)


def ev(name, a, b):
    return (name, float(a), float(b - a))


def test_the_manifest_lists_the_thirteen_with_their_cells():
    per_layer = {m["name"]: m for m in mf.load()["per_layer"]}
    assert set(NEW) <= set(per_layer)
    generate = {w["name"] for w in mf.load()["workloads"]
                if w["traffic"] == "generate_closed"}
    for name in NEW:
        m = per_layer[name]
        assert m["moves"] == "output_tokens_per_s"
        assert m["source"] == "device_trace" and m["better"] == "lower"
        assert set(m["workloads"]) <= generate
        assert callable(mf.reader(name))
    assert set(per_layer["prefill_ssm_ms"]["workloads"]) \
        == {"nemotron3_super_ep4_generate_closed"}
    assert "nemotron3_super_ep4_generate_closed" \
        not in per_layer["decode_mlp_ms_per_token"]["workloads"]
    assert "gpt2_small_generate_closed" \
        not in per_layer["prefill_moe_ms"]["workloads"]
    for name in ("device_unscoped_pct", "prefill_idle_ms",
                 "handover_idle_ms", "prefill_attn_ms", "decode_head_ms_per_token"):
        assert set(per_layer[name]["workloads"]) == generate


@pytest.mark.parametrize("module, phase", [
    ("jit_gpt_prefill(5694549794985933706)", "prefill"),
    ("jit_nemotron_h_decode(12)", "decode"),
    ("jit_dots_vlm_prefill_0f3a9c1e(7)", "prefill"),
    ("jit_afmoe_decode", "decode"),
    ("jit_traced(10915817620366104051)", None),
    ("jit_broadcast_in_dim(3)", None),
    ("jit_startup(9)", None),
    ("jit_predecode_table(4)", None),
    (None, None),
])
def test_phase_by_module_name(module, phase):
    assert sections.phase_of(module) == phase


@pytest.mark.parametrize("scope, section, two", [
    ("attn/proj/mul", "attn", "attn/proj"),
    ("embed/lookup_table_v2", "head", "embed/lookup_table_v2"),
    ("moe/experts/moe_local_experts/moe_router", "moe", "moe/experts"),
    ("ssm/scan/ssm_state_update", "ssm", "ssm/scan"),
    ("head", "head", "head"),
    ("while/body/closed_call", sections.UNSCOPED, "while/body"),
    ("", sections.UNSCOPED, sections.UNSCOPED),
])
def test_section_and_two_levels_of_a_scope(scope, section, two):
    assert sections.section_of(scope) == section
    assert sections.two_levels(scope) == two


def test_module_by_enclosure():
    modules = [ev("jit_gpt_prefill(1)", 100, 200),
               ev("jit_gpt_decode(2)", 250, 300)]
    ops = [ev("a", 50, 60), ev("b", 100, 150), ev("c", 199, 200),
           ev("d", 200, 210), ev("e", 260, 270), ev("f", 300, 310)]
    assert sections.modules_of(ops, modules) == [
        None, "jit_gpt_prefill(1)", "jit_gpt_prefill(1)", None,
        "jit_gpt_decode(2)", None]
    assert sections.modules_of(ops, []) == [None] * 6


def test_self_time_under_nesting():
    # a `while` (0-100) with its body's ops on the same line, one of them
    # holding a deeper one; then an op of its own
    ops = [ev("while.1", 0, 100), ev("fusion.1", 10, 30),
           ev("call.1", 40, 90), ev("fusion.2", 50, 60),
           ev("fusion.3", 100, 120)]
    own = sections.self_times(ops)
    assert own == [30.0, 20.0, 40.0, 10.0, 20.0]
    # nothing is counted twice: the self times are the busy union
    assert sum(own) == xplane.total(xplane.union(ops)) == 120.0
    assert sections.self_times([]) == []


def _batch_run(scoped=True, handover=True):
    """One batch, nanoseconds: assemble 0-40, batch 40-960 (prefill
    50-300 = two dispatches, decode loop 320-940 = two steps), complete
    960-1000. The device runs what the `ops` say; between them it is
    idle."""
    host = [
        ev("serving.form_batch", -200, 0),
        ev("serving.batch", 40, 960),
        ev("serving.cache_reset", 42, 50),
        ev("serving.prefill", 50, 300),
        ev("executor.step", 55, 100), ev("executor.step", 160, 200),
        ev("serving.decode_loop", 320, 940),
        ev("executor.step", 330, 400), ev("executor.step", 600, 660),
        ev("bench.window", -100, 1100),
    ]
    if handover:
        host += [ev("serving.assemble", 0, 40),
                 ev("serving.complete", 960, 1000)]
    P, D, R = "jit_gpt_prefill(1)", "jit_gpt_decode(2)", "jit_fill(3)"
    modules = [ev(R, 44, 48), ev(P, 60, 150), ev(P, 170, 290),
               ev(D, 340, 560), ev(D, 620, 900)]
    ops = [
        ev("%fill.1 = f32[8] broadcast()", 44, 48),
        # prefill dispatch 1: embed 60-70, attn 70-120 (a while 80-120
        # whose body's op 90-110 is attn/core), mlp 120-150
        ev("gather.1", 60, 70), ev("fusion.1", 70, 80),
        ev("while.1", 80, 120), ev("fusion.2", 90, 110),
        ev("fusion.3", 120, 150),
        # dispatch 2: attn 170-250, head 250-290
        ev("fusion.1", 170, 250), ev("fusion.4", 250, 290),
        # decode step 1: attn 340-500, mlp 500-540, no scope 540-560
        ev("decode_attention.1", 340, 500), ev("fusion.9", 500, 540),
        ev("copy.7", 540, 560),
        # step 2: attn 620-800, mlp 800-860, head 860-900
        ev("decode_attention.1", 620, 800), ev("fusion.9", 800, 860),
        ev("fusion.8", 860, 900),
    ]
    scopes = {
        P: {"gather.1": "embed/lookup_table_v2", "fusion.1": "attn/proj/mul",
            "while.1": "attn/core/causal_gqa_attention",
            "fusion.2": "attn/core/causal_gqa_attention/while/body",
            "fusion.3": "mlp/mul", "fusion.4": "head/mul"},
        D: {"decode_attention.1": "attn/core/kv_cache_attention",
            "fusion.9": "mlp/gelu", "copy.7": "",
            "fusion.8": "head/greedy_token"},
    }
    trace = Trace([
        Line("/device:TPU:0", xplane.OPS_LINE, ops),
        Line("/device:TPU:0", sections.MODULES_LINE, modules),
        Line("/host:CPU", "python", host),
    ])
    facts = {"_program_trace": trace, "device_kind": "TPU v5 lite"}
    if scoped:
        facts["scopes"] = scopes
    return Run(attempted=1, failed=0, checks={}, end_to_end={}, facts=facts,
               trace=trace, window_ns=(-100.0, 1100.0))


def test_phases_sections_and_sums_on_a_hand_made_batch(capsys):
    run = _batch_run()
    found = sections.phases(run)
    pre, dec = found["prefill"], found["decode"]
    assert pre["n"] == 1 and dec["n"] == 2
    # the while's own 20 ns and its body's 20 are both attn, once each
    assert pre["sections"] == {"head": 50.0, "attn": 130.0, "mlp": 30.0}
    assert dec["sections"] == {"attn": 340.0, "mlp": 100.0, "head": 40.0,
                               sections.UNSCOPED: 20.0}
    for phase in (pre, dec):
        assert sum(phase["sections"].values()) == phase["busy_ns"]
    read = {name: mf.reader(name)(run) for name in NEW}
    assert read["prefill_attn_ms"] == pytest.approx(130 / 1e6)
    assert read["prefill_mlp_ms"] == pytest.approx(30 / 1e6)
    assert read["prefill_head_ms"] == pytest.approx(50 / 1e6)
    assert read["decode_attn_ms_per_token"] == pytest.approx(170 / 1e6)
    assert read["decode_mlp_ms_per_token"] == pytest.approx(50 / 1e6)
    assert read["decode_head_ms_per_token"] == pytest.approx(20 / 1e6)
    # a model without the section: nothing to report
    for name in ("prefill_moe_ms", "prefill_ssm_ms",
                 "decode_moe_ms_per_token", "decode_ssm_ms_per_token"):
        assert read[name] is None
    # busy self time 714: the fill (no phase's module) 4 and the copy
    # without a scope 20 are what the sections cannot name
    assert read["device_unscoped_pct"] == pytest.approx(100 * 24 / 714)
    # idle inside the prefill span 50-300: 50-60, 150-170, 290-300
    assert read["prefill_idle_ms"] == pytest.approx(40 / 1e6)
    # the hand-over: form_batch -100-0 (its part of the window),
    # assemble 0-40, complete 960-1000, a batch
    assert read["handover_idle_ms"] == pytest.approx(180 / 1e6)
    printed = capsys.readouterr().out
    assert '"two_level_ms"' in printed and '"attn/core"' in printed
    assert '"other_modules_ms": {"jit_fill"' in printed
    assert '"serving.assemble": 4e-05' in printed


def test_a_capture_with_no_scopes_or_no_phase_gives_nothing():
    """What the driver sees on the parent's side: this PR's benchmark
    files over a program whose modules are all `jit_traced` and which
    reads no scopes."""
    run = _batch_run(scoped=False, handover=False)
    run.facts["_program_trace_path"] = "recorded.json.gz"
    for name in NEW:
        if name != "prefill_idle_ms":       # the span is the parent's too
            assert mf.reader(name)(run) is None, name
    assert mf.reader("prefill_idle_ms")(run) == pytest.approx(40 / 1e6)
    # scopes, but one module name for every program
    run = _batch_run()
    trace = run.facts["_program_trace"]
    (modules,) = [ln for ln in trace.lines
                  if ln.name == sections.MODULES_LINE]
    modules.events = [("jit_traced(9)", s, d) for _n, s, d in modules.events]
    assert sections.rows(run) is None
    assert mf.reader("prefill_attn_ms")(run) is None
    assert mf.reader("device_unscoped_pct")(run) is None


def test_the_recorded_pr24_capture_gives_nothing_and_raises_nothing():
    path = os.path.join(DATA, "gpt2_small_generate_closed.pr24.v5e.json.gz")
    trace = xplane.load(path)
    run = Run(attempted=1, failed=0, checks={}, end_to_end={},
              facts={"program_trace": path, "device_kind": "TPU v5 lite"},
              trace=trace, window_ns=xplane.window_of(trace))
    got = {name: mf.reader(name)(run) for name in NEW}
    assert {k for k, v in got.items() if v is not None} \
        <= {"prefill_idle_ms"}


def test_traced_rehearsal_lists_the_new_metrics():
    line = rehearse(mf.ROOT, "gpt2_small_generate_closed", "--trace", "1")
    assert line["correct"] is True
    assert {"prefill_attn_ms", "prefill_mlp_ms", "prefill_head_ms",
            "decode_attn_ms_per_token", "decode_mlp_ms_per_token",
            "decode_head_ms_per_token", "device_unscoped_pct",
            "prefill_idle_ms", "handover_idle_ms"} \
        <= set(line["rehearsal"]["reported"])
    assert line["metrics"] == {}
