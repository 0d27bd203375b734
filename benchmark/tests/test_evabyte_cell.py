"""The `evabyte_pp8_generate_closed_4k` cell: the manifest takes it by
entries only, the configuration file copies the catalog row, its
rehearsal on the CPU at the `tiny` sizes (traced and untraced),
`evabyte_cost.py` against hand-counted parameters, operations and bytes
at the cell's shapes, the three new readers on hand-made events (and
None for another family's table), and the once-only script's
rehearsal."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import evabyte_cost as cost
from benchmark.harness import manifest as mf

from .test_afmoe_cell import _run
from .test_rehearse import rehearse

CELL = "evabyte_pp8_generate_closed_4k"
CONFIG = "evabyte_pp8"

# EvaByte's layers 0-3 on this chip, as the program publishes them
MODEL = {
    "family": "evabyte", "hidden_size": 4096, "num_layers": 4,
    "num_heads": 32, "head_dim": 128, "intermediate_size": 11008,
    "vocab_size": 320, "window_size": 2048, "chunk_size": 16,
    "bytes_per_param": 2,
}
P = 4096 * 4096
FFN = 3 * 4096 * 11008
ROW = 32 * 128


def test_the_manifest_holds_the_cell_by_entries_only():
    manifest = mf.load()
    entry, cell = mf.cell(manifest, CELL)
    assert entry["chips"] == 1 and entry["config"] == CONFIG
    assert entry["traffic"] == cell["traffic"]["name"] == \
        "generate_closed_4k"
    assert cell["traffic"]["kind"] == "generate_closed"
    assert {k: cell["traffic"][k] for k in (
        "batch", "prompt_len", "new_tokens", "clients", "max_wait_ms",
        "prompts", "trace_seconds")} == {
        "batch": 64, "prompt_len": 4096, "new_tokens": 512, "clients": 128,
        "max_wait_ms": 2000.0, "prompts": 256, "trace_seconds": 8.0}
    assert [w["chips"] for w in manifest["workloads"]].count(4) == 1
    per_layer = {m["name"] for m in mf.metrics_of(manifest, "per_layer",
                                                   CELL)}
    assert per_layer == {
        "compile_s", "generate_compiles_in_window", "queue_wait_ms_p50",
        "prefill_ms_p50", "decode_gap_ms_p50", "generate_device_idle_pct",
        "peak_hbm_gib", "decode_idle_ms_per_token", "decode_idle_fetch_ms",
        "decode_idle_prologue_ms", "decode_idle_sample_ms",
        "generate_idle_unowned_pct", "prefill_attn_ms", "prefill_mlp_ms",
        "prefill_head_ms", "decode_attn_ms_per_token",
        "decode_mlp_ms_per_token", "decode_head_ms_per_token",
        "device_unscoped_pct", "prefill_idle_ms", "handover_idle_ms",
        "eva_attention_ms_per_token", "eva_attention_roofline_pct",
        "eva_generate_mfu_pct"}
    end_to_end = {m["name"] for m in mf.metrics_of(manifest, "end_to_end",
                                                   CELL)}
    assert end_to_end == {"output_tokens_per_s", "request_latency_p95_ms",
                          "setup_s"}
    config = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    file = mf.config(manifest, CONFIG)
    assert config["reduced"] == file["reduced"]
    assert config["source"] == file["source"]


def test_the_configuration_file_copies_the_catalog_row():
    """Every key of the public config.json as the catalog has it, under
    the same name; only the keys in `reduced` differ, each beside its
    published value; the stage runs published layers 0-3 of 32."""
    file = mf.config(mf.load(), CONFIG)
    assert set(file["published"]) == set(file["reduced"]) == {
        "num_hidden_layers", "num_pred_heads"}
    assert file["published"] == {"num_hidden_layers": 32,
                                 "num_pred_heads": 8}
    assert (file["num_hidden_layers"], file["num_pred_heads"]) == (4, 1)
    assert file["deployment"]["layers_run"] == [0, 1, 2, 3]
    assert file["deployment"]["pipeline_stages"] == 8
    assert (file["vocab_size"], file["hidden_size"],
            file["intermediate_size"], file["num_attention_heads"],
            file["num_key_value_heads"], file["window_size"],
            file["chunk_size"], file["rope_theta"]) == (
        320, 4096, 11008, 32, 32, 2048, 16, 100000)
    assert file["attention_class"] == "eva" and file["fp32_skip_add"]
    assert {"block", "precision", "eva_attention", "summaries",
            "aligned_windows", "weights"} <= set(file["assumed"])
    assert file["weights"]["init_std"] == file["init_std"] == 0.01275


def test_cost_against_hand_counted_parameters():
    layer = 4 * P + FFN + 2 * 4096 + 2 * ROW
    assert cost.matrix_params(MODEL) == 4 * P + FFN
    assert cost.layer_params(MODEL) == layer == 202_391_552
    assert cost.resident_params(MODEL) == \
        4 * layer + 2 * 320 * 4096 + 4096 == 812_191_744
    assert 1.62e9 < 2 * cost.resident_params(MODEL) < 1.63e9


def test_cost_against_hand_counted_operations_and_bytes():
    # a byte at position 4,100 (window 2, slot 4) scores 5 exact keys and
    # the 256 summaries of windows 0 and 1; it closes no chunk
    assert cost.keys_seen(MODEL, 4100) == (5, 256)
    want = 4 * (2 * (4 * P + FFN) + 4 * ROW * (5 + 256))
    assert cost.token_flops(MODEL, 4100, False) == pytest.approx(want)
    # 4,111 closes chunk 256: its two pooled rows, 16 rows a lane each
    assert cost.token_flops(MODEL, 4111, False) == pytest.approx(
        4 * (2 * (4 * P + FFN) + 4 * ROW * (16 + 256) + 8 * 16 * ROW))
    head = 2 * 4096 * 320
    assert cost.token_flops(MODEL, 4100, True) == pytest.approx(want + head)
    flops = cost.request_flops(MODEL, 4096, 512)
    by_hand = sum(cost.token_flops(MODEL, p, False) for p in range(4096)) \
        + head + sum(cost.token_flops(MODEL, 4096 + t, True)
                     for t in range(511))
    assert flops == pytest.approx(by_hand)
    # about 1.62 GFLOP a byte in the products; 7.77 TFLOP a request, of
    # which 0.31 in the attention and the pooling
    assert 1.61e9 < cost.token_flops(MODEL, 0, False) < 1.63e9
    assert 7.7e12 < flops < 7.8e12
    # one layer's decode step at 4,096 for 64 sequences: one ring row
    # and 256 summary rows of K and V, q and o
    ops, nbytes = cost.decode_eva_need(MODEL, 64, 4096)
    assert nbytes == 64 * 2 * ROW * (2 * 257 + 2)
    assert ops == 4 * 64 * ROW * 257
    assert ops / 197e12 < nbytes / 819e9    # memory holds
    # over the batch's 511 steps: 1 .. 511 ring rows beside 256
    ops, nbytes = cost.decode_loop_eva_need(MODEL, 64, 4096, 512)
    assert nbytes == pytest.approx(64 * 2 * ROW * (2 * (256 + 256) + 2))
    assert 537e6 < nbytes < 538e6


def test_the_new_readers_on_hand_made_events():
    from benchmark.layer_metrics import (
        eva_attention_ms_per_token, eva_attention_roofline_pct,
        eva_generate_mfu_pct,
    )
    from paddle_tpu import observability as obs

    call = "%eva_decode_attention.{} = bf16[64,1,4096] custom-call(...)"
    events = []
    for step in (0, 1):
        for layer in range(4):
            events.append((call.format(layer),
                           (1 + 10 * step) * 1e6 + layer * 1.1e6, 1.0e6))
    events += [
        ("%fusion.3 = ...", 6e6, 1e6),
        # another family's attention kernel is not this one's
        ("%decode_attention.1 = ...", 7e6, 0.5e6),
        # a kernel event outside every decode loop is not a step's
        (call.format(0), 30e6, 9e6),
    ]
    events.sort(key=lambda e: e[1])
    program = [("serving.prefill", 0.1e6, 0.3e6),
               ("serving.decode_loop", 0.5e6, 16e6),
               ("executor.step", 0.6e6, 5e6),
               ("executor.step", 10.6e6, 5e6)]
    run = _run(events, program, [])
    run.facts["new_tokens"] = 512
    readers = (eva_attention_roofline_pct, eva_generate_mfu_pct)
    obs.reset()
    # a parent's program publishes no model table: nothing to read
    assert [r.read(run) for r in readers] == [None, None]
    # nor is another family's table this one's
    obs.set_table("serving.generate.model", {"family": "minicpm_sala"})
    assert [r.read(run) for r in readers] == [None, None]
    # the events alone are read whatever the table
    assert eva_attention_ms_per_token.read(run) == pytest.approx(4 * 1.0)
    obs.set_table("serving.generate.model",
                  {**MODEL, "context_len": 4096, "batch": 64,
                   "max_len": 4608})
    try:
        # two steps' 8 calls of 1 ms; a layer's step needs 537.9 MB =
        # 0.6568 ms at 819 GB/s
        _ops, nbytes = cost.decode_loop_eva_need(MODEL, 64, 4096, 512)
        want = 100.0 * (nbytes / 819e9) / 1.0e-3
        assert eva_attention_roofline_pct.read(run) == pytest.approx(want)
        assert 65 < want < 66
        # 64 requests of 4,096 + 512 bytes in one second
        want = 100.0 * 64 * cost.request_flops(MODEL, 4096, 512) / 197e12
        assert eva_generate_mfu_pct.read(run) == pytest.approx(want)
    finally:
        obs.reset()
    # without its events the kernel's readers fall silent
    quiet = _run([e for e in events if "eva" not in e[0]], program, [])
    assert eva_attention_ms_per_token.read(quiet) is None


@pytest.mark.parametrize("trace", ["0", "1"])
def test_the_cell_rehearses(trace):
    line = rehearse(mf.ROOT, CELL, "--trace", trace)
    assert line["correct"] is True, line["rehearsal"]["checks"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["metrics"] == {} and line["device"]["platform"] == "cpu"
    check = line["rehearsal"]["checks"]["reference"]
    assert check["decode_steps"] == 8
    reported = set(line["rehearsal"]["reported"])
    if trace == "1":
        # the CPU path runs no Pallas kernel and has no table of peaks:
        # the kernel's readers and the share of the peak find nothing;
        # the sections' readers do
        assert {"compile_s", "prefill_ms_p50", "decode_gap_ms_p50",
                "prefill_attn_ms", "prefill_mlp_ms",
                "decode_attn_ms_per_token", "decode_mlp_ms_per_token"} \
            <= reported
        assert not {"eva_attention_ms_per_token",
                    "eva_attention_roofline_pct",
                    "eva_generate_mfu_pct"} & reported
    else:
        assert {"output_tokens_per_s", "setup_s"} <= reported


@pytest.mark.parametrize("what", ["lower_precision", "long_prompt"])
def test_the_once_only_runs_rehearse(what):
    """`benchmark/evabyte_once.py` at the tiny size on the CPU: the
    float8 weights, the dropped summaries and the mean pooling fail the
    comparison; a prompt of 60 bytes (windows of 16) and 12 cached steps
    across the window boundary at 64 agree with the reference, and the
    summaries are a share of what the steps read."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(mf.ROOT, "benchmark", "evabyte_once.py"),
         what, "--rehearse"],
        capture_output=True, text=True, timeout=900, env=env, cwd=mf.ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["device"]["platform"] == "cpu"
    assert out["stated"]["ok"] is True, out["stated"]
    assert out["no_summaries"]["ok"] is False
    if what == "long_prompt":
        assert out["prompt_len"] == 60 and out["decode_steps"] == 12
        assert 0 < out["eva"]["summary_share"] < 1
        assert out["summary_bytes"] > 0
    else:
        for below in ("float8_weights", "mean_pooling"):
            assert out[below]["ok"] is False, below
        assert "bfloat16_stream" in out
