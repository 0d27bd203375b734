"""The `nemotron3_super_ep4_generate_closed` cell: its rehearsal on the
CPU at the `tiny` sizes (traced and untraced), `nemotron_h_cost.py`
against hand-counted parameters, operations and bytes, the new readers
on hand-made events, and the once-only script's rehearsal."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import manifest as mf
from benchmark.harness import nemotron_h_cost as cost

from .test_afmoe_cell import _run
from .test_rehearse import rehearse

CELL = "nemotron3_super_ep4_generate_closed"

# Nemotron-3-Super's share on this chip, as the program publishes it
MODEL = {
    "family": "nemotron_h", "hidden_size": 4096, "pattern": "MEMEMEMEM*E",
    "mamba_num_heads": 128, "mamba_head_dim": 64, "ssm_state_size": 128,
    "n_groups": 8, "conv_kernel": 4, "chunk_size": 128, "num_heads": 32,
    "num_kv_heads": 2, "head_dim": 128, "num_experts": 512,
    "num_local_experts": 128, "top_k": 22, "moe_latent_size": 1024,
    "moe_intermediate_size": 2688, "shared_intermediate_size": 5376,
    "vocab_size": 32768, "bytes_per_param": 2,
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
        # `ssm_state_update` and `moe_gmm` events find nothing; the
        # counters' reader does
        assert {"compile_s", "prefill_ms_p50", "decode_gap_ms_p50",
                "moe_load_max_over_mean"} <= reported
        assert not {"ssm_update_ms_per_token",
                    "ssm_update_roofline_pct"} & reported
    else:
        assert {"output_tokens_per_s", "setup_s"} <= reported


def test_the_manifest_holds_the_cell_by_entries_only():
    manifest = mf.load()
    entry, cell = mf.cell(manifest, CELL)
    assert entry["chips"] == 1 and entry["config"] == "nemotron3_super_ep4"
    assert [w["chips"] for w in manifest["workloads"]].count(4) == 1
    assert len(manifest["workloads"]) == 6
    for other in ("gpt2_small_generate_closed",
                  "trinity_large_ep8_generate_closed"):
        _e, theirs = mf.cell(manifest, other)
        skip = {"logits_tol"}
        assert {k: v for k, v in cell["traffic"].items() if k not in skip} \
            == {k: v for k, v in theirs["traffic"].items() if k not in skip}
    per_layer = {m["name"] for m in mf.metrics_of(manifest, "per_layer", CELL)}
    assert {"hybrid_generate_mfu_pct", "ssm_update_ms_per_token",
            "ssm_update_roofline_pct", "latent_expert_roofline_pct",
            "moe_expert_ms_per_token", "moe_load_max_over_mean",
            "compile_s", "peak_hbm_gib"} <= per_layer
    # afmoe's closed forms and the two readers that no longer hold are
    # not fed this model
    assert not {"generate_mfu_pct", "moe_expert_roofline_pct",
                "decode_device_ms", "generate_executor_host_ms"} & per_layer
    for m in manifest["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["moves"] in ("output_tokens_per_s",
                                  "request_latency_p95_ms", "setup_s")
    config = next(c for c in manifest["configs"]
                  if c["name"] == "nemotron3_super_ep4")
    assert config["reduced"] == mf.config(
        manifest, "nemotron3_super_ep4")["reduced"]


def test_cost_against_hand_counted_parameters():
    # in_proj 4096 x (8192 z + 10,240 xBC + 128 dt), out_proj 8192 x 4096
    assert cost.mamba_matrix_params(MODEL) == \
        4096 * 18_560 + 8192 * 4096 == 109_576_192
    # + conv 10,240 x 4 and its bias, A_log / D / dt_bias, two norms
    assert cost.mamba_params(MODEL) == \
        109_576_192 + 10_240 * 5 + 3 * 128 + 8192 + 4096 == 109_640_064
    # q and o 4096 x 4096, k and v 4096 x 256
    assert cost.attention_params(MODEL) == \
        2 * 4096 * 4096 + 2 * 4096 * 256 == 35_651_584
    assert cost.expert_params(MODEL) == 2 * 1024 * 2688 == 5_505_024
    # router 4096 x 512, latent down and up, shared expert 2 x 4096 x 5376
    assert cost.expert_block_always(MODEL) == \
        2_097_152 + 8_388_608 + 44_040_192 == 54_525_952
    assert cost.resident_params(MODEL) == (
        5 * 109_640_064
        + 5 * (54_525_952 + 512 + 4096 + 128 * 5_505_024)
        + 35_651_584 + 4096 + 2 * 134_217_728 + 4096
    ) == 4_648_163_712                              # 4.65B: 9.30 GB


def test_cost_against_hand_counted_operations_and_bytes():
    state = 128 * 64 * 128
    mamba = 2 * 109_576_192 + 5 * state + 2 * 4 * 10_240
    experts = 2 * (54_525_952 + 5.5 * 5_505_024)
    # one token that sees 10 keys, 5.5 routed assignments a block here
    want = 5 * mamba + 5 * experts + 2 * 35_651_584 + 4 * 32 * 128 * 10
    assert cost.token_flops(MODEL, 10, 5.5, False) == pytest.approx(want)
    head = 2 * 4096 * 32768
    assert cost.token_flops(MODEL, 10, 5.5, True) == pytest.approx(
        want + head)
    # a prompt token costs about 2.06 GFLOP, Mamba 55% and experts 41%
    assert 2.0e9 < want < 2.1e9
    assert 5 * mamba / want == pytest.approx(0.55, abs=0.01)
    assert 5 * experts / want == pytest.approx(0.41, abs=0.01)
    flops = cost.request_flops(MODEL, 896, 128, 5.5)
    per_key = 4 * 32 * 128
    by_hand = 896 * (5 * mamba + 5 * experts + 2 * 35_651_584) \
        + per_key * (896 * 897 // 2) + head \
        + sum(5 * mamba + 5 * experts + 2 * 35_651_584
              + per_key * (896 + t) + head for t in range(1, 128))
    assert flops == pytest.approx(by_hand)
    # a decode step's state update in one block: 64 states of 4 MB read
    # and written, and 64 rows of xBC, dt and y
    ops, nbytes = cost.decode_ssm_need(MODEL, 64)
    assert ops == 5 * 64 * state
    assert nbytes == 64 * (2 * 4 * state + 2 * (10_240 + 128 + 8192)) \
        == 539_246_592
    # the routed products of one block: 118 experts hit by 352
    # assignments read 118 x 11.0 MB of weights and 352 rows in and out
    ops, nbytes = cost.decode_expert_need(MODEL, 118, 352)
    assert ops == 2 * 352 * 5_505_024
    assert nbytes == 118 * 5_505_024 * 2 + 352 * (1024 + 2 * 2688 + 1024) * 2


def test_the_new_readers_on_hand_made_events():
    from benchmark.layer_metrics import (
        hybrid_generate_mfu_pct, latent_expert_roofline_pct,
        ssm_update_ms_per_token, ssm_update_roofline_pct,
    )
    from paddle_tpu import observability as obs

    events = [
        ("%ssm_state_update.5 = (f32[64,64,128], f32[64,64,128,128]) "
         "custom-call(...)", 1e6, 0.8e6),
        ("%moe_gmm.10 = bf16[3456,2688] custom-call(...)", 2e6, 1.0e6),
        ("%moe_gmm.11 = bf16[3456,1024] custom-call(...)", 3.1e6, 1.0e6),
        ("%fusion.3 = ...", 4.2e6, 1e6),
        ("%ssm_state_update.5 = (f32[64,64,128], f32[64,64,128,128]) "
         "custom-call(...)", 11e6, 0.8e6),
        ("%moe_gmm.10 = bf16[3456,2688] custom-call(...)", 12e6, 1.0e6),
        ("%moe_gmm.11 = bf16[3456,1024] custom-call(...)", 13.1e6, 1.0e6),
        # a prefill's products lie outside every decode loop
        ("%moe_gmm.2 = bf16[200704,2688] custom-call(...)", 30e6, 9e6),
    ]
    program = [("serving.prefill", 0.1e6, 0.3e6),
               ("serving.decode_loop", 0.5e6, 16e6),
               ("executor.step", 0.6e6, 5e6),
               ("executor.step", 10.6e6, 5e6)]
    counters = {"name": "serving.step_counters", "ts": 0, "dur": 1, "tid": 1,
                "args": {"moe.assignments_local": 55_000,
                         "moe.assignments_total": 220_000,
                         "moe.max_expert_load_sum": 900, "moe.calls": 100,
                         "moe.decode_assignments_local": 3520,
                         "moe.decode_experts_hit": 1180,
                         "moe.decode_calls": 10}}
    run = _run(events, program, [counters])
    readers = (hybrid_generate_mfu_pct, ssm_update_roofline_pct,
               latent_expert_roofline_pct)
    # the kernel's time needs the trace alone
    assert ssm_update_ms_per_token.read(run) == pytest.approx(0.8)
    obs.reset()
    # a parent's program publishes no model table: nothing to read
    assert [r.read(run) for r in readers] == [None] * 3
    # nor is another family's table this one's
    obs.set_table("serving.generate.model", {"family": "afmoe"})
    assert [r.read(run) for r in readers] == [None] * 3
    obs.set_table("serving.generate.model",
                  {**MODEL, "context_len": 896, "batch": 64, "max_len": 1024})
    try:
        # two steps' 1.6 ms of updates over 5 Mamba blocks each
        _ops, nbytes = cost.decode_ssm_need(MODEL, 64)
        want = 100.0 * (nbytes / 819e9) / (1.6e-3 / (2 * 5))
        assert ssm_update_roofline_pct.read(run) == pytest.approx(want)
        # 118 experts hit and 352 rows a block-step; the two steps' 4 ms
        # of products over 5 expert blocks each
        _ops, nbytes = cost.decode_expert_need(MODEL, 118, 352)
        want = 100.0 * (nbytes / 819e9) / (4.0e-3 / (2 * 5))
        assert latent_expert_roofline_pct.read(run) == pytest.approx(want)
        # 64 requests of 896 + 128 tokens with 22 * 55 / 220 = 5.5 local
        # assignments a token and block, in one second
        want = 100.0 * 64 * cost.request_flops(MODEL, 896, 128, 5.5) / 197e12
        assert hybrid_generate_mfu_pct.read(run) == pytest.approx(want)
    finally:
        obs.reset()


@pytest.mark.parametrize("what", ["odd_prompt", "lower_precision"])
def test_the_once_only_runs_rehearse(what):
    """`benchmark/nemotron_h_once.py` at the tiny size on the CPU: a
    prompt that is no multiple of the chunk, then 64 cached steps, still
    agrees with the reference; float8 weights in the reference do not
    pass the cell's comparison."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(mf.ROOT, "benchmark", "nemotron_h_once.py"),
         what, "--rehearse"],
        capture_output=True, text=True, timeout=900, env=env, cwd=mf.ROOT,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["device"]["platform"] == "cpu"
    assert out["stated"]["ok"] is True, out["stated"]
    if what == "odd_prompt":
        # 7 7/8 chunks of 8 (1,000 of 1,024 at the published chunk)
        assert out["prompt_len"] == 63 and out["decode_steps"] == 64
        assert out["stated"]["decode_routing"]["mismatches"] == 0
    else:
        assert out["float8_weights"]["ok"] is False
        assert out["float8_weights"]["decode_err"] > \
            2 * out["stated"]["decode_err"]
        assert "bfloat16_state" in out
