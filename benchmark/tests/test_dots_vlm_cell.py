"""The `dots_vlm1_ep16_generate_closed` cell: its rehearsal on the CPU at
the `tiny` sizes (traced and untraced), `mla_cost.py` against
hand-counted parameters, operations and bytes, the two new readers on
hand-made events, and the once-only script's rehearsal."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import manifest as mf
from benchmark.harness import mla_cost as cost

from .test_afmoe_cell import _run
from .test_rehearse import rehearse

CELL = "dots_vlm1_ep16_generate_closed"

# dots.vlm1's text decoder's share on this chip, as the program publishes it
MODEL = {
    "family": "dots_vlm", "hidden_size": 7168, "num_heads": 128,
    "q_lora_rank": 1536, "kv_lora_rank": 512, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "v_head_dim": 128, "intermediate_size": 18432,
    "moe_intermediate_size": 2048, "num_experts": 256,
    "num_local_experts": 16, "top_k": 8, "n_group": 8, "topk_group": 4,
    "num_shared_experts": 1, "vocab_size": 16160, "bytes_per_param": 2,
    "layer_kinds": [["latent_attention", "dense"]]
    + [["latent_attention", "experts"]] * 4,
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
        # `decode_attention` and `moe_gmm` events find nothing (nor is
        # there a table of peaks off the chip for a share of one); the
        # counters' reader does
        assert {"compile_s", "prefill_ms_p50", "decode_gap_ms_p50",
                "moe_load_max_over_mean"} <= reported
        assert not {"latent_attention_roofline_pct", "mla_generate_mfu_pct",
                    "decode_attention_ms_per_token"} & reported
    else:
        assert {"output_tokens_per_s", "setup_s"} <= reported


def test_the_manifest_holds_the_cell_by_entries_only():
    manifest = mf.load()
    entry, cell = mf.cell(manifest, CELL)
    assert entry["chips"] == 1 and entry["config"] == "dots_vlm1_ep16"
    assert [w["chips"] for w in manifest["workloads"]].count(4) == 1
    assert len(manifest["workloads"]) >= 7
    for other in ("gpt2_small_generate_closed",
                  "trinity_large_ep8_generate_closed",
                  "nemotron3_super_ep4_generate_closed"):
        _e, theirs = mf.cell(manifest, other)
        skip = {"logits_tol"}
        assert {k: v for k, v in cell["traffic"].items() if k not in skip} \
            == {k: v for k, v in theirs["traffic"].items() if k not in skip}
    per_layer = {m["name"] for m in mf.metrics_of(manifest, "per_layer", CELL)}
    assert {"latent_attention_roofline_pct", "mla_generate_mfu_pct",
            "decode_attention_ms_per_token", "moe_expert_ms_per_token",
            "moe_expert_roofline_pct", "moe_load_max_over_mean",
            "compile_s", "peak_hbm_gib"} <= per_layer
    # a memory-only bound, afmoe's closed form and the two readers that
    # no longer hold are not fed this model
    assert not {"decode_attention_roofline_pct", "generate_mfu_pct",
                "hybrid_generate_mfu_pct", "decode_device_ms",
                "generate_executor_host_ms"} & per_layer
    for m in manifest["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["moves"] in ("output_tokens_per_s",
                                  "request_latency_p95_ms", "setup_s")
    config = next(c for c in manifest["configs"]
                  if c["name"] == "dots_vlm1_ep16")
    file = mf.config(manifest, "dots_vlm1_ep16")
    assert config["reduced"] == file["reduced"]
    assert config["source"] == file["source"]


def test_the_configuration_file_copies_the_catalog_row():
    """Every key of the public config.json as the catalog has it, under
    the same name; only the keys in `reduced` differ, each beside its
    published value; no width among them."""
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
        "hidden_act": "silu", "hidden_size": 7168,
        "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 163840, "model_type": "dots_vlm",
        "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 8,
        "n_routed_experts": 256, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 61,
        "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "seq_aux": True,
        "tie_word_embeddings": False, "topk_group": 4,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 129280,
    }
    file = mf.config(mf.load(), "dots_vlm1_ep16")
    differ = {k for k, v in published.items() if file[k] != v}
    assert differ == set(file["reduced"])
    assert {k: published[k] for k in differ} == file["published"]
    assert not any(k.endswith(("_dim", "_rank", "_size")) and k != "vocab_size"
                   for k in file["reduced"])
    assert file["deployment"]["chips_per_layer"] == 16
    assert file["deployment"]["router_width"] == 256
    assert file["vocab_size"] * 8 == published["vocab_size"]


def test_cost_against_hand_counted_parameters():
    # q_a 7168 x 1536, q_b 1536 x (128 x 192), kv_a 7168 x 576,
    # kv_b 128 x 512 x 256, o 16384 x 7168
    assert cost.attention_params(MODEL) == (
        11_010_048 + 37_748_736 + 4_128_768 + 16_777_216 + 117_440_512
    ) == 187_105_280
    assert cost.ffn_params(MODEL, "dense") == (3 * 7168 * 18432, 0)
    always, each = cost.ffn_params(MODEL, "experts")
    assert each == 3 * 7168 * 2048 == 44_040_192
    assert always == 7168 * 256 + 44_040_192
    # + per layer the two block norms and the two inner norms, the bias
    # buffer of an expert layer, the final norm: 4.566B = 9.13 GB
    norms = 2 * 7168 + 1536 + 512
    assert cost.resident_params(MODEL) == (
        187_105_280 + 396_361_728 + norms
        + 4 * (187_105_280 + 1_835_008 + 44_040_192 + 16 * 44_040_192
               + 256 + norms)
        + 2 * 16160 * 7168 + 7168
    ) == 4_565_721_088


def test_cost_against_hand_counted_operations_and_bytes():
    attn, expert = 187_105_280, 44_040_192
    dense = 3 * 7168 * 18432
    always = 7168 * 256 + expert
    # a prompt token (expanded form) that sees 10 keys, 1.5 routed
    # assignments a layer here: per key a 192-wide score and a 128-wide
    # value product in each of 128 heads
    core = 2 * 128 * (192 + 128) * 10
    want = 5 * (2 * attn + core) + 2 * dense + 4 * 2 * (always + 1.5 * expert)
    assert cost.token_flops(MODEL, 10, 1.5, False, False) == \
        pytest.approx(want)
    head = 2 * 7168 * 16160
    assert cost.token_flops(MODEL, 10, 1.5, False, True) == \
        pytest.approx(want + head)
    # 3.2 GFLOP a prompt token before its keys, 58% of it attention
    # matrices (ISSUE 33's reckoning)
    flat = cost.token_flops(MODEL, 0, 0.5, False, False)
    assert 3.1e9 < flat < 3.3e9
    assert 5 * 2 * attn / flat == pytest.approx(0.58, abs=0.01)
    # a decode token (absorbed form): kv_b's 16.8M parameters are not
    # multiplied whole; two per-head products stand in (128 x 128 x 512
    # each), and a key costs a 576-wide score and a 512-wide sum a head
    absorbed = attn - 16_777_216 + 2 * 128 * 128 * 512
    assert absorbed == attn
    core = 2 * 128 * (576 + 512) * 900
    want = 5 * (2 * absorbed + core) + 2 * dense \
        + 4 * 2 * (always + 1.5 * expert) + head
    assert cost.token_flops(MODEL, 900, 1.5, True, True) == \
        pytest.approx(want)
    flops = cost.request_flops(MODEL, 896, 128, 0.5)
    by_hand = sum(cost.token_flops(MODEL, i + 1, 0.5, False, False)
                  for i in range(896)) + head \
        + sum(cost.token_flops(MODEL, 896 + t, 0.5, True, True)
              for t in range(1, 128))
    assert flops == pytest.approx(by_hand)
    assert 3.4e12 < flops < 3.9e12          # a batch of 64: about 233 TFLOP
    # one decode step at position 959: 5 layers x 64 sequences x 960
    # rows of 1,152 bytes; each row scored by 128 heads over 576 lanes
    # and summed over 512: 242 operations a byte
    nbytes = 5 * 64 * 960 * 1152
    ops, got = cost.decode_attention_need(MODEL, nbytes)
    assert got == nbytes
    assert ops == 2 * 5 * 64 * 960 * 128 * (576 + 512)
    assert ops / nbytes == pytest.approx(241.8, abs=0.1)


def test_the_new_readers_on_hand_made_events():
    from benchmark.layer_metrics import (
        latent_attention_roofline_pct, mla_generate_mfu_pct,
    )
    from paddle_tpu import observability as obs

    events = [
        ("%decode_attention.3 = bf16[64,128,512] custom-call(...)", 1e6,
         0.2e6),
        ("%decode_attention.4 = bf16[64,128,512] custom-call(...)", 2e6,
         0.2e6),
        ("%fusion.3 = ...", 4.2e6, 1e6),
        ("%decode_attention.3 = bf16[64,128,512] custom-call(...)", 11e6,
         0.2e6),
        ("%decode_attention.4 = bf16[64,128,512] custom-call(...)", 12e6,
         0.2e6),
        # a kernel event outside every decode loop is not a step's
        ("%decode_attention.3 = bf16[64,128,512] custom-call(...)", 30e6,
         9e6),
    ]
    program = [("serving.prefill", 0.1e6, 0.3e6),
               ("serving.decode_loop", 0.5e6, 16e6),
               ("executor.step", 0.6e6, 5e6),
               ("executor.step", 10.6e6, 5e6)]
    counters = {"name": "serving.step_counters", "ts": 0, "dur": 1, "tid": 1,
                "args": {"moe.assignments_local": 16_000,
                         "moe.assignments_total": 256_000,
                         "moe.max_expert_load_sum": 900, "moe.calls": 100,
                         "moe.decode_assignments_local": 320,
                         "moe.decode_experts_hit": 139,
                         "moe.decode_calls": 10}}
    run = _run(events, program, [counters])
    readers = (latent_attention_roofline_pct, mla_generate_mfu_pct)
    obs.reset()
    # a parent's program publishes no model table: nothing to read
    assert [r.read(run) for r in readers] == [None, None]
    # nor is another family's table this one's
    obs.set_table("serving.generate.model", {"family": "afmoe"})
    obs.add("kv_cache.decode_steps", 2)
    obs.add("kv_cache.decode_bytes_needed", 2 * 5 * 64 * 960 * 1152)
    assert [r.read(run) for r in readers] == [None, None]
    obs.set_table("serving.generate.model",
                  {**MODEL, "context_len": 896, "batch": 64, "max_len": 1024})
    try:
        # two steps' 0.8 ms of calls; a step needs 354 MB = 0.432 ms by
        # memory, 85.6 GFLOP = 0.434 ms by compute: compute holds
        ops, nbytes = cost.decode_attention_need(MODEL, 5 * 64 * 960 * 1152)
        assert ops / 197e12 > nbytes / 819e9
        want = 100.0 * (ops / 197e12) / 0.4e-3
        assert latent_attention_roofline_pct.read(run) == pytest.approx(want)
        assert want < 110
        # 64 requests of 896 + 128 tokens with 8 * 16 / 256 = 0.5 local
        # assignments a token and layer, in one second
        want = 100.0 * 64 * cost.request_flops(MODEL, 896, 128, 0.5) / 197e12
        assert mla_generate_mfu_pct.read(run) == pytest.approx(want)
    finally:
        obs.reset()


@pytest.mark.parametrize("what", ["odd_prompt", "lower_precision"])
def test_the_once_only_runs_rehearse(what):
    """`benchmark/dots_vlm_once.py` at the tiny size on the CPU: an odd
    prompt length, then 64 absorbed steps, still agrees with the
    expanded reference; float8 weights in the reference do not pass the
    cell's comparison."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(mf.ROOT, "benchmark", "dots_vlm_once.py"),
         what, "--rehearse"],
        capture_output=True, text=True, timeout=900, env=env, cwd=mf.ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["device"]["platform"] == "cpu"
    assert out["stated"]["ok"] is True, out["stated"]
    if what == "odd_prompt":
        assert out["prompt_len"] == 31 and out["decode_steps"] == 64
        assert out["stated"]["decode_routing"]["mismatches"] == 0
    else:
        assert out["float8_weights"]["ok"] is False
        assert out["float8_weights"]["decode_err"] > \
            2 * out["stated"]["decode_err"]
        assert "float8_cache_rows" in out
