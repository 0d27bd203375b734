"""The `minicpm_sala_pp4_generate_closed` cell: the manifest takes it by
entries only, its rehearsal on the CPU at the `tiny` sizes (traced and
untraced), `minicpm_sala_cost.py` against hand-counted parameters,
operations and bytes at the cell's shapes, the three new readers on
hand-made events (and None for another family's table), and the
once-only script's rehearsal."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import manifest as mf
from benchmark.harness import minicpm_sala_cost as cost

from .test_afmoe_cell import _run
from .test_rehearse import rehearse

CELL = "minicpm_sala_pp4_generate_closed"
CONFIG = "minicpm_sala_pp4"
S, L = "minicpm4", "lightning-attn"

# MiniCPM-SALA's layers 9-16 on this chip, as the program publishes them
MODEL = {
    "family": "minicpm_sala", "hidden_size": 4096,
    "layer_kinds": [S] + [L] * 6 + [S],
    "num_heads": 32, "num_kv_heads": 2, "head_dim": 128,
    "lightning_heads": 32, "lightning_head_dim": 128,
    "intermediate_size": 16384, "chunk_size": 64, "sparse_kernel": 32,
    "sparse_stride": 16, "init_blocks": 1, "block_size": 64,
    "window_size": 2048, "topk": 64, "dense_len": 8192,
    "vocab_size": 73448, "bytes_per_param": 2,
}
P = 4096 * 4096


def test_the_manifest_holds_the_cell_by_entries_only():
    manifest = mf.load()
    entry, cell = mf.cell(manifest, CELL)
    assert entry["chips"] == 1 and entry["config"] == CONFIG
    assert manifest["workloads"][-1]["name"] == CELL
    assert [w["chips"] for w in manifest["workloads"]].count(4) == 1
    for other in ("gpt2_small_generate_closed",
                  "qwen3_next_ep8_generate_closed"):
        _e, theirs = mf.cell(manifest, other)
        skip = {"logits_tol"}
        assert {k: v for k, v in cell["traffic"].items() if k not in skip} \
            == {k: v for k, v in theirs["traffic"].items() if k not in skip}
    per_layer = {m["name"] for m in mf.metrics_of(manifest, "per_layer", CELL)}
    assert {"lightning_update_ms_per_token", "lightning_update_roofline_pct",
            "sala_generate_mfu_pct", "decode_attention_ms_per_token",
            "decode_attention_roofline_pct", "prefill_ssm_ms",
            "prefill_mlp_ms", "decode_ssm_ms_per_token",
            "decode_mlp_ms_per_token", "compile_s", "peak_hbm_gib",
            "device_unscoped_pct", "handover_idle_ms"} <= per_layer
    # the expert readers and other families' closed forms are not fed it
    assert not {"moe_expert_ms_per_token", "moe_load_max_over_mean",
                "gdn_generate_mfu_pct", "hybrid_generate_mfu_pct",
                "prefill_moe_ms", "decode_moe_ms_per_token",
                "decode_device_ms", "generate_executor_host_ms"} & per_layer
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
    published value; the run's mixer types are published layers 9-16."""
    file = mf.config(mf.load(), CONFIG)
    published = file["published"]
    assert set(published) == set(file["reduced"]) == {
        "num_hidden_layers", "mixer_types"}
    assert published["num_hidden_layers"] == 32
    layers = file["deployment"]["layers_run"]
    assert layers == list(range(9, 17))
    assert file["mixer_types"] == [published["mixer_types"][i]
                                   for i in layers]
    assert file["mixer_types"].count(S) * 3 == file["mixer_types"].count(L)
    assert published["mixer_types"].count(S) == 8
    assert file["vocab_size"] == 73448 and file["hidden_size"] == 4096
    assert file["intermediate_size"] == 16384
    assert {"lightning", "lightning_slopes", "sparse_attention",
            "sparse_config", "mup", "precision"} <= set(file["assumed"])
    assert file["deployment"]["pipeline_stages"] == 4


def test_cost_against_hand_counted_parameters():
    assert cost.sparse_matrix_params(MODEL) == 3 * P + 2 * 1_048_576
    assert cost.layer_params(MODEL, S) == 253_763_840
    assert cost.lightning_matrix_params(MODEL) == 5 * P
    assert cost.layer_params(MODEL, L) == 285_225_216
    assert cost.resident_params(MODEL) == (
        2 * 253_763_840 + 6 * 285_225_216 + 2 * 73448 * 4096 + 4096
    ) == 2_820_569_088
    assert 5.64e9 < 2 * cost.resident_params(MODEL) < 5.65e9


def test_cost_against_hand_counted_operations_and_bytes():
    state = 32 * 128 * 128
    assert cost.state_elements(MODEL) == state
    ffn = 3 * 4096 * 16384
    # a token that sees 10 keys, dense
    want = 6 * (2 * 5 * P + 5 * state) \
        + 2 * (2 * (3 * P + 2 * 1_048_576) + 4 * 32 * 128 * 10) \
        + 8 * 2 * ffn
    assert cost.token_flops(MODEL, 10, False, False) == pytest.approx(want)
    head = 2 * 4096 * 73448
    assert cost.token_flops(MODEL, 10, False, True) == \
        pytest.approx(want + head)
    # beyond dense_len a query reads at most the 64 selected blocks
    assert cost.token_flops(MODEL, 16384, True, False) == \
        cost.token_flops(MODEL, 64 * 64, False, False)
    flops = cost.request_flops(MODEL, 896, 128)
    by_hand = sum(cost.token_flops(MODEL, i + 1, False, False)
                  for i in range(896)) + head \
        + sum(cost.token_flops(MODEL, 897 + t, False, True)
              for t in range(1, 128))
    assert flops == pytest.approx(by_hand)
    # about 4.4 GFLOP a token, 4.6 TFLOP a request, 298 a batch of 64
    assert 4.4e9 < cost.token_flops(MODEL, 1, False, False) < 4.5e9
    assert 4.6e12 < flops < 4.7e12
    # one Lightning layer's decode step for 64 sequences: the 2 MB state
    # of each read and written (268 MB), q, k, v, o rows of 4096 bfloat16
    ops, nbytes = cost.decode_lightning_need(MODEL, 64)
    assert ops == 5 * 64 * state
    assert nbytes == 64 * (2 * 4 * state + 2 * 4 * 4096)
    assert 270e6 < nbytes < 271e6
    assert ops / 197e12 < nbytes / 819e9    # memory holds


def test_the_new_readers_on_hand_made_events():
    from benchmark.layer_metrics import (
        lightning_update_ms_per_token, lightning_update_roofline_pct,
        sala_generate_mfu_pct,
    )
    from paddle_tpu import observability as obs

    call = "%lightning_state_update.{} = (f32[64,32,128], " \
        "f32[64,32,128,128]) custom-call(...)"
    events = []
    for step in (0, 1):
        for layer in range(6):
            events.append((call.format(layer),
                           (1 + 10 * step) * 1e6 + layer * 0.45e6, 0.4e6))
    events += [
        ("%fusion.3 = ...", 6e6, 1e6),
        # the other two updates' events are other families'
        ("%ssm_state_update.1 = ...", 7e6, 0.5e6),
        ("%gdn_state_update.1 = ...", 7.6e6, 0.5e6),
        # a kernel event outside every decode loop is not a step's
        (call.format(0), 30e6, 9e6),
    ]
    events.sort(key=lambda e: e[1])
    program = [("serving.prefill", 0.1e6, 0.3e6),
               ("serving.decode_loop", 0.5e6, 16e6),
               ("executor.step", 0.6e6, 5e6),
               ("executor.step", 10.6e6, 5e6)]
    run = _run(events, program, [])
    readers = (lightning_update_roofline_pct, sala_generate_mfu_pct)
    obs.reset()
    # a parent's program publishes no model table: nothing to read
    assert [r.read(run) for r in readers] == [None, None]
    # nor is another family's table this one's
    obs.set_table("serving.generate.model", {"family": "qwen3_next"})
    assert [r.read(run) for r in readers] == [None, None]
    # the events alone are read whatever the table
    assert lightning_update_ms_per_token.read(run) == pytest.approx(6 * 0.4)
    obs.set_table("serving.generate.model",
                  {**MODEL, "context_len": 896, "batch": 64, "max_len": 1024})
    try:
        # two steps' 12 calls of 0.4 ms; a layer's step needs 270.5 MB =
        # 0.3303 ms at 819 GB/s
        _ops, nbytes = cost.decode_lightning_need(MODEL, 64)
        want = 100.0 * (nbytes / 819e9) / 0.4e-3
        assert lightning_update_roofline_pct.read(run) == pytest.approx(want)
        assert 80 < want < 85
        # 64 requests of 896 + 128 tokens in one second
        want = 100.0 * 64 * cost.request_flops(MODEL, 896, 128) / 197e12
        assert sala_generate_mfu_pct.read(run) == pytest.approx(want)
    finally:
        obs.reset()
    # without its events the kernel's readers fall silent
    quiet = _run([e for e in events if "lightning" not in e[0]], program, [])
    assert lightning_update_ms_per_token.read(quiet) is None


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
                "prefill_ssm_ms", "prefill_attn_ms", "prefill_mlp_ms",
                "decode_ssm_ms_per_token", "decode_mlp_ms_per_token"} \
            <= reported
        assert not {"lightning_update_ms_per_token",
                    "lightning_update_roofline_pct",
                    "sala_generate_mfu_pct"} & reported
    else:
        assert {"output_tokens_per_s", "setup_s"} <= reported


@pytest.mark.parametrize("what", ["lower_precision", "long_prompt"])
def test_the_once_only_runs_rehearse(what):
    """`benchmark/minicpm_sala_once.py` at the tiny size on the CPU: the
    float8 weights, the dropped decay and the dropped gates fail the
    comparison; a prompt of 96 positions (24 blocks, dense up to 16)
    selects in the prefill and in 64 cached steps and agrees with the
    reference."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(mf.ROOT, "benchmark", "minicpm_sala_once.py"),
         what, "--rehearse"],
        capture_output=True, text=True, timeout=900, env=env, cwd=mf.ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["device"]["platform"] == "cpu"
    assert out["stated"]["ok"] is True, out["stated"]
    if what == "long_prompt":
        assert out["prompt_len"] == 96 and out["decode_steps"] == 64
        assert out["selecting_layers"] == 1
        assert 0 < out["blocks"]["decode_share"] < 0.25
        assert out["index_bytes"] > 0
    else:
        for below in ("float8_weights", "no_decay", "no_output_gate"):
            assert out[below]["ok"] is False, below
        assert "bfloat16_state" in out
