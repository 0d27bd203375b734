"""The `trinity_large_ep8_generate_closed` cell: its rehearsal on the
CPU at the `tiny` sizes (traced and untraced), `moe_cost.py` against
hand-counted parameters, operations and bytes, and the new readers on
hand-made events."""

import pytest

from benchmark.harness import manifest as mf
from benchmark.harness import moe_cost
from benchmark.harness.context import Run
from benchmark.harness.xplane import Line, Trace

from .test_rehearse import rehearse

CELL = "trinity_large_ep8_generate_closed"

# Trinity-Large-Preview's share on this chip, as the program publishes it
MODEL = {
    "family": "afmoe", "hidden_size": 3072, "num_heads": 48,
    "num_kv_heads": 8, "head_dim": 128, "intermediate_size": 12288,
    "moe_intermediate_size": 3072, "num_experts": 256,
    "num_local_experts": 32, "top_k": 4, "num_shared_experts": 1,
    "vocab_size": 25024, "sliding_window": 4096, "bytes_per_param": 2,
    "layer_kinds": [["sliding_attention", "dense"],
                    ["sliding_attention", "experts"],
                    ["sliding_attention", "experts"],
                    ["sliding_attention", "experts"],
                    ["full_attention", "experts"]],
}


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_rehearses(trace):
    line = rehearse(mf.ROOT, CELL, "--trace", trace)
    assert line["correct"] is True, line["rehearsal"]["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    check = line["rehearsal"]["checks"]["reference"]
    assert check["decode_routing"]["mismatches"] == 0
    assert check["decode_steps"] == 8
    reported = set(line["rehearsal"]["reported"])
    if trace == "1":
        # the CPU path runs no Pallas kernel, so the two readers of the
        # `moe_gmm` events find nothing; the counters' reader does
        assert {"compile_s", "prefill_ms_p50", "decode_gap_ms_p50",
                "moe_load_max_over_mean"} <= reported
    else:
        assert {"output_tokens_per_s", "setup_s"} <= reported


def test_the_manifest_holds_the_cell_by_entries_only():
    manifest = mf.load()
    entry, cell = mf.cell(manifest, CELL)
    assert entry["chips"] == 1 and entry["config"] == "trinity_large_ep8"
    _e, gpt_cell = mf.cell(manifest, "gpt2_small_generate_closed")
    skip = {"logits_tol"}
    assert {k: v for k, v in cell["traffic"].items() if k not in skip} == \
        {k: v for k, v in gpt_cell["traffic"].items() if k not in skip}
    per_layer = {m["name"] for m in mf.metrics_of(manifest, "per_layer", CELL)}
    assert {"moe_expert_ms_per_token", "moe_expert_roofline_pct",
            "moe_load_max_over_mean", "generate_mfu_pct", "compile_s",
            "peak_hbm_gib"} <= per_layer
    # the same generator loop as GPT-2's: every accepted generate reader
    # reads this cell too
    gpt_layers = {m["name"] for m in mf.metrics_of(
        manifest, "per_layer", "gpt2_small_generate_closed")}
    assert gpt_layers <= per_layer
    assert {"decode_device_ms", "generate_executor_host_ms"} <= gpt_layers
    compile_s = next(m for m in manifest["per_layer"]
                     if m["name"] == "compile_s")
    assert compile_s["workloads"] == [w["name"] for w in manifest["workloads"]]


def test_moe_cost_against_hand_counted_parameters():
    # attention: q 3072x6144, gate 3072x6144, o 6144x3072, k and v
    # 3072x1024 each
    assert moe_cost.attention_params(MODEL) == \
        3 * 3072 * 6144 + 2 * 3072 * 1024 == 62_914_560
    assert moe_cost.expert_params(MODEL) == 3 * 3072 * 3072 == 28_311_552
    dense, none = moe_cost.layer_params(MODEL, "dense")
    assert (dense, none) == (62_914_560 + 3 * 3072 * 12288, 0)
    always, each = moe_cost.layer_params(MODEL, "experts")
    assert always == 62_914_560 + 3072 * 256 + 28_311_552
    assert each == 28_311_552
    # 176.1M + 4 x (92.0M + 32 x 28.3M) + 2 x 76.9M = 4.32B
    assert moe_cost.resident_params(MODEL) == (
        176_160_768 + 4 * (92_012_544 + 32 * 28_311_552) + 2 * 76_873_728
    ) == 4_321_837_056


def test_moe_cost_against_hand_counted_operations_and_bytes():
    # one token, one visible key, half an assignment a layer on this chip
    per_layer_always = 2 * (176_160_768 + 4 * 92_012_544)
    routed = 2 * 4 * 0.5 * 28_311_552
    attention = 5 * 4 * 48 * 128 * 1
    head = 2 * 3072 * 25024
    assert moe_cost.token_flops(MODEL, 1, 0.5, True) == pytest.approx(
        per_layer_always + routed + attention + head)
    # a window layer stops counting keys at its window, a full one not
    far = moe_cost.token_flops(MODEL, 10_000, 0.0, False) \
        - moe_cost.token_flops(MODEL, 0, 0.0, False)
    assert far == pytest.approx(4 * 48 * 128 * (4 * 4096 + 10_000))
    # a request: 896 prompt tokens (token i sees i + 1 keys, one head)
    # then 127 decode steps; 1.27 GFLOP a token as ISSUE 27 reckons
    flops = moe_cost.request_flops(MODEL, 896, 128, 0.5)
    by_hand = 896 * (per_layer_always + routed) \
        + 5 * 4 * 48 * 128 * (896 * 897 // 2) + head \
        + sum(per_layer_always + routed + 5 * 4 * 48 * 128 * (896 + t) + head
              for t in range(1, 128))
    assert flops == pytest.approx(by_hand)
    assert 1.2e9 < flops / 1024 < 1.5e9
    # a decode step's routed products in one layer: 20 experts hit by 32
    # assignments read 20 x 56.6 MB of weights and 32 rows in and out
    ops, nbytes = moe_cost.decode_expert_need(MODEL, 20, 32)
    assert ops == 2 * 32 * 28_311_552
    assert nbytes == 20 * 28_311_552 * 2 + 32 * (3072 + 6144 + 3072 + 3072) * 2


def _run(events, program_spans, spans):
    trace = Trace([
        Line("/device:TPU:0", "XLA Ops", events),
        Line("/host:CPU", "thread", [("bench.window", 0.0, 1e9)]),
    ])
    # the capture once more with the program's spans kept
    program = Trace([Line("/host:CPU", "scheduler", program_spans)])
    return Run(attempted=1, failed=0, checks={}, end_to_end={},
               facts={"device_kind": "TPU v5 lite", "window_s": 1.0,
                      "chips": 1, "requests_completed": 64,
                      "new_tokens": 128, "_program_trace": program},
               spans=spans, trace=trace, window_ns=(0.0, 1e9))


def test_the_moe_readers_on_hand_made_events():
    from benchmark.layer_metrics import (
        moe_expert_ms_per_token, moe_expert_roofline_pct,
        moe_load_max_over_mean,
    )
    from paddle_tpu import observability as obs

    events = [
        ("%moe_gmm.8 = bf16[768,6144] custom-call(...)", 1e6, 1.0e6),
        ("%moe_gmm.9 = bf16[768,3072] custom-call(...)", 2e6, 0.5e6),
        ("%fusion.3 = ...", 3e6, 2e6),
        ("%moe_gmm.8 = bf16[768,6144] custom-call(...)", 11e6, 1.0e6),
        ("%moe_gmm.9 = bf16[768,3072] custom-call(...)", 12e6, 0.5e6),
        # a prefill's products lie outside every decode loop
        ("%moe_gmm.2 = bf16[65536,6144] custom-call(...)", 30e6, 9e6),
    ]
    # one decode loop of two steps
    program = [("serving.prefill", 0.1e6, 0.3e6),
               ("serving.decode_loop", 0.5e6, 16e6),
               ("executor.step", 0.6e6, 5e6),
               ("executor.step", 10.6e6, 5e6)]
    counters = {"name": "serving.step_counters", "ts": 0, "dur": 1, "tid": 1,
                "args": {"moe.assignments_local": 3200,
                         "moe.assignments_total": 25600,
                         "moe.max_expert_load_sum": 400, "moe.calls": 100,
                         "moe.decode_assignments_local": 64,
                         "moe.decode_experts_hit": 40,
                         "moe.decode_calls": 2}}
    run = _run(events, program, [counters])
    assert moe_expert_ms_per_token.read(run) == pytest.approx(1.5)
    obs.reset()
    # a parent's program publishes no model table: nothing to read
    assert moe_expert_roofline_pct.read(run) is None
    assert moe_load_max_over_mean.read(run) is None
    obs.set_table("serving.generate.model",
                  {**MODEL, "context_len": 896, "batch": 64, "max_len": 1024})
    try:
        assert moe_load_max_over_mean.read(run) == pytest.approx(
            400 * 32 / 3200)
        # 20 experts hit and 32 rows a layer-step: 1.13 GB at 819 GB/s;
        # the two steps' 3 ms spread over 4 expert layers each
        _ops, nbytes = moe_cost.decode_expert_need(MODEL, 20, 32)
        want = 100.0 * (nbytes / 819e9) / (3.0e-3 / (2 * 4))
        assert moe_expert_roofline_pct.read(run) == pytest.approx(want)
    finally:
        obs.reset()


@pytest.mark.parametrize("what", ["beyond_window", "lower_precision"])
def test_the_once_only_runs_rehearse(what):
    """`benchmark/afmoe_once.py` at the tiny size on the CPU: beyond the
    window the program still agrees with the reference; float8 weights in
    the reference do not pass the cell's comparison."""
    import json
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(mf.ROOT, "benchmark", "afmoe_once.py"),
         what, "--rehearse"],
        capture_output=True, text=True, timeout=900, env=env, cwd=mf.ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["device"]["platform"] == "cpu"
    assert out["stated"]["ok"] is True, out["stated"]
    if what == "beyond_window":
        # 1 1/8 windows of prompt, then 64 steps round the ring 8 times
        assert out["prompt_len"] == 9 and out["decode_steps"] == 64
        assert out["stated"]["decode_routing"]["mismatches"] == 0
    else:
        assert out["float8_weights"]["ok"] is False
        assert out["float8_weights"]["decode_err"] > \
            2 * out["stated"]["decode_err"]
