"""The `qwen3_next_ep8_generate_closed` cell: its rehearsal on the CPU at
the `tiny` sizes (traced and untraced), `qwen3_next_cost.py` against
hand-counted parameters, operations and bytes, the three new readers on
hand-made events, and the once-only script's rehearsal."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import manifest as mf
from benchmark.harness import qwen3_next_cost as cost

from .test_afmoe_cell import _run
from .test_rehearse import rehearse

CELL = "qwen3_next_ep8_generate_closed"
CONFIG = "qwen3_next_ep8"

# Qwen3-Next-80B-A3B's share on this chip, as the program publishes it
MODEL = {
    "family": "qwen3_next", "hidden_size": 2048,
    "layer_kinds": ([["linear", "experts"]] * 3 + [["full", "experts"]]) * 3,
    "num_heads": 16, "num_kv_heads": 2, "head_dim": 256, "rotary_dim": 64,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_key_head_dim": 128, "linear_value_head_dim": 128,
    "linear_conv_kernel_dim": 4, "chunk_size": 64, "num_experts": 512,
    "num_local_experts": 64, "top_k": 10, "moe_intermediate_size": 512,
    "shared_intermediate_size": 512, "num_shared_experts": 1,
    "vocab_size": 18992, "bytes_per_param": 2,
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
        # the CPU path runs no Pallas kernel, so the readers of the
        # `gdn_state_update`, `decode_attention` and `moe_gmm` events find
        # nothing (nor is there a table of peaks off the chip for a share
        # of one); the counters' and the sections' readers do
        assert {"compile_s", "prefill_ms_p50", "decode_gap_ms_p50",
                "moe_load_max_over_mean", "prefill_ssm_ms", "prefill_attn_ms",
                "prefill_moe_ms", "decode_ssm_ms_per_token"} <= reported
        assert not {"gdn_update_roofline_pct", "gdn_generate_mfu_pct",
                    "gdn_update_ms_per_token",
                    "decode_attention_ms_per_token"} & reported
    else:
        assert {"output_tokens_per_s", "setup_s"} <= reported


def test_the_manifest_holds_the_cell_by_entries_only():
    manifest = mf.load()
    entry, cell = mf.cell(manifest, CELL)
    assert entry["chips"] == 1 and entry["config"] == CONFIG
    assert [w["chips"] for w in manifest["workloads"]].count(4) == 1
    assert len(manifest["workloads"]) >= 8
    for other in ("gpt2_small_generate_closed",
                  "trinity_large_ep8_generate_closed",
                  "nemotron3_super_ep4_generate_closed",
                  "dots_vlm1_ep16_generate_closed"):
        _e, theirs = mf.cell(manifest, other)
        skip = {"logits_tol"}
        assert {k: v for k, v in cell["traffic"].items() if k not in skip} \
            == {k: v for k, v in theirs["traffic"].items() if k not in skip}
    per_layer = {m["name"] for m in mf.metrics_of(manifest, "per_layer", CELL)}
    assert {"gdn_update_ms_per_token", "gdn_update_roofline_pct",
            "gdn_generate_mfu_pct", "decode_attention_ms_per_token",
            "decode_attention_roofline_pct", "moe_expert_ms_per_token",
            "moe_expert_roofline_pct", "moe_load_max_over_mean",
            "prefill_ssm_ms", "decode_ssm_ms_per_token", "compile_s",
            "peak_hbm_gib", "device_unscoped_pct"} <= per_layer
    # other families' closed forms, the section this model lacks and the
    # two readers that no longer hold are not fed this model
    assert not {"generate_mfu_pct", "hybrid_generate_mfu_pct",
                "mla_generate_mfu_pct", "ssm_update_roofline_pct",
                "latent_expert_roofline_pct", "prefill_mlp_ms",
                "decode_mlp_ms_per_token", "decode_device_ms",
                "generate_executor_host_ms"} & per_layer
    for m in manifest["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["moves"] in ("output_tokens_per_s",
                                  "request_latency_p95_ms", "setup_s")
    config = next(c for c in manifest["configs"] if c["name"] == CONFIG)
    file = mf.config(manifest, CONFIG)
    assert config["reduced"] == file["reduced"]
    assert config["source"] == file["source"]


def test_the_configuration_file_copies_the_catalog_row():
    """Every key of the public config.json as the catalog has it, under
    the same name; only the keys in `reduced` differ, each beside its
    published value; no width among them."""
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936,
    }
    file = mf.config(mf.load(), CONFIG)
    differ = {k for k, v in published.items() if file[k] != v}
    assert differ == set(file["reduced"])
    assert {k: published[k] for k in differ} == file["published"]
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in file["reduced"])
    dep = file["deployment"]
    assert dep["chips_per_layer"] == 8 and dep["pipeline_stages"] == 4
    assert dep["router_width"] == 512 and dep["this_chip"] == 0
    assert dep["layers_run"] == list(range(12))
    assert file["vocab_size"] * 8 == published["vocab_size"]
    assert file["num_experts"] * 8 == published["num_experts"]
    assert {"gated_deltanet", "gated_attention", "chunk", "routing",
            "precision", "left_out"} <= set(file["assumed"])


def test_cost_against_hand_counted_parameters():
    # in_qkvz 2048 x 12288, in_ba 2048 x 64, out 4096 x 2048
    assert cost.linear_matrix_params(MODEL) == (
        25_165_824 + 131_072 + 8_388_608) == 33_685_504
    # + the convolution 8192 x 4, A_log and dt_bias, the norm's gain
    assert cost.linear_params(MODEL) == 33_685_504 + 32_768 + 64 + 128
    # q with its gate 2048 x 8192, k and v 2048 x 512, o 4096 x 2048
    assert cost.attention_matrix_params(MODEL) == (
        16_777_216 + 2 * 1_048_576 + 8_388_608) == 27_262_976
    assert cost.expert_params(MODEL) == 3 * 2048 * 512 == 3_145_728
    # router 2048 x 512, shared expert, its gate
    assert cost.ffn_always(MODEL) == 1_048_576 + 3_145_728 + 2048
    ffn = 4_196_352 + 64 * 3_145_728 + 2 * 2048      # with the two norms
    assert cost.resident_params(MODEL) == (
        9 * (33_718_464 + ffn) + 3 * (27_262_976 + 512 + ffn)
        + 2 * 18992 * 2048 + 2048
    ) == 2_929_374_400
    assert 5.85e9 < 2 * cost.resident_params(MODEL) < 5.87e9    # 5.86 GB


def test_cost_against_hand_counted_operations_and_bytes():
    linear, full, expert = 33_685_504, 27_262_976, 3_145_728
    always = 4_196_352
    state = 32 * 128 * 128
    assert cost.state_elements(MODEL) == state == 524_288
    # a token that sees 10 keys, 1.25 routed assignments a layer here
    want = 9 * (2 * linear + 7 * state + 2 * 4 * 8192) \
        + 3 * (2 * full + 4 * 16 * 256 * 10) \
        + 12 * 2 * (always + 1.25 * expert)
    assert cost.token_flops(MODEL, 10, 1.25, False) == pytest.approx(want)
    head = 2 * 2048 * 18992
    assert cost.token_flops(MODEL, 10, 1.25, True) == \
        pytest.approx(want + head)
    # about 40M multiply-adds a token and layer (ISSUE 38's reckoning)
    flat = cost.token_flops(MODEL, 0, 1.25, False)
    assert 38e6 < flat / 2 / 12 < 42e6
    flops = cost.request_flops(MODEL, 896, 128, 1.25)
    by_hand = sum(cost.token_flops(MODEL, i + 1, 1.25, False)
                  for i in range(896)) + head \
        + sum(cost.token_flops(MODEL, 896 + t, 1.25, True)
              for t in range(1, 128))
    assert flops == pytest.approx(by_hand)
    assert 0.9e12 < flops < 1.1e12          # a batch of 64: about 64 TFLOP
    # one linear layer's decode step for 64 sequences: the 2 MB state of
    # each read and written (268 MB), rows of 8192 + 64 + 4096 bfloat16
    ops, nbytes = cost.decode_gdn_need(MODEL, 64)
    assert ops == 7 * 64 * state
    assert nbytes == 64 * (2 * 4 * state + 2 * (8192 + 64 + 4096))
    assert 268e6 < nbytes < 271e6
    assert ops / 197e12 < nbytes / 819e9    # memory holds


def test_the_new_readers_on_hand_made_events():
    from benchmark.layer_metrics import (
        gdn_generate_mfu_pct, gdn_update_ms_per_token,
        gdn_update_roofline_pct,
    )
    from paddle_tpu import observability as obs

    call = "%gdn_state_update.{} = (f32[64,32,128], f32[64,32,128,128]) " \
        "custom-call(...)"
    events = []
    for step in (0, 1):
        for layer in range(9):
            events.append((call.format(layer),
                           (1 + 10 * step) * 1e6 + layer * 0.45e6, 0.4e6))
    events += [
        ("%fusion.3 = ...", 6e6, 1e6),
        # the state-space kernel's events are another family's
        ("%ssm_state_update.1 = ...", 7e6, 1e6),
        # a kernel event outside every decode loop is not a step's
        (call.format(0), 30e6, 9e6),
    ]
    events.sort(key=lambda e: e[1])
    program = [("serving.prefill", 0.1e6, 0.3e6),
               ("serving.decode_loop", 0.5e6, 16e6),
               ("executor.step", 0.6e6, 5e6),
               ("executor.step", 10.6e6, 5e6)]
    counters = {"name": "serving.step_counters", "ts": 0, "dur": 1, "tid": 1,
                "args": {"moe.assignments_local": 80_000,
                         "moe.assignments_total": 640_000,
                         "moe.max_expert_load_sum": 900, "moe.calls": 100,
                         "moe.decode_assignments_local": 800,
                         "moe.decode_experts_hit": 460,
                         "moe.decode_calls": 10}}
    run = _run(events, program, [counters])
    readers = (gdn_update_roofline_pct, gdn_generate_mfu_pct)
    obs.reset()
    # a parent's program publishes no model table: nothing to read
    assert [r.read(run) for r in readers] == [None, None]
    # nor is another family's table this one's
    obs.set_table("serving.generate.model", {"family": "nemotron_h"})
    assert [r.read(run) for r in readers] == [None, None]
    # the events alone are read whatever the table
    assert gdn_update_ms_per_token.read(run) == pytest.approx(9 * 0.4)
    obs.set_table("serving.generate.model",
                  {**MODEL, "context_len": 896, "batch": 64, "max_len": 1024})
    try:
        # two steps' 18 calls of 0.4 ms; a layer's step needs 269.3 MB =
        # 0.3288 ms at 819 GB/s
        _ops, nbytes = cost.decode_gdn_need(MODEL, 64)
        want = 100.0 * (nbytes / 819e9) / 0.4e-3
        assert gdn_update_roofline_pct.read(run) == pytest.approx(want)
        assert 80 < want < 85
        # 64 requests of 896 + 128 tokens with 10 * 80 / 640 = 1.25 local
        # assignments a token and layer, in one second
        want = 100.0 * 64 * cost.request_flops(MODEL, 896, 128, 1.25) / 197e12
        assert gdn_generate_mfu_pct.read(run) == pytest.approx(want)
    finally:
        obs.reset()
    # without its events the kernel's readers fall silent
    quiet = _run([e for e in events if "gdn_state_update" not in e[0]],
                 program, [counters])
    assert gdn_update_ms_per_token.read(quiet) is None


@pytest.mark.parametrize("what", ["odd_prompt", "lower_precision"])
def test_the_once_only_runs_rehearse(what):
    """`benchmark/qwen3_next_once.py` at the tiny size on the CPU: an odd
    prompt length (15 5/8 chunks), then 64 cached steps, still agrees
    with the token-by-token reference; float8 weights and the dropped
    correction in the reference do not pass the cell's comparison."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(mf.ROOT, "benchmark", "qwen3_next_once.py"),
         what, "--rehearse"],
        capture_output=True, text=True, timeout=900, env=env, cwd=mf.ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["device"]["platform"] == "cpu"
    assert out["stated"]["ok"] is True, out["stated"]
    if what == "odd_prompt":
        assert out["prompt_len"] == 125 and out["decode_steps"] == 64
        assert out["stated"]["decode_routing"]["mismatches"] == 0
    else:
        assert out["float8_weights"]["ok"] is False
        assert out["no_correction"]["ok"] is False
        tol = out["stated"]["tol"]
        assert out["no_correction"]["prefill_err"] > tol
        assert out["no_correction"]["decode_err"] > tol
        assert "bfloat16_state" in out
