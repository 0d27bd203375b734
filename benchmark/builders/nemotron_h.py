"""Nemotron-H-family cells (NVIDIA Nemotron 3): generation through
`serving.GPTGenerator` handed `models/nemotron_h.py`'s decoder, as one
chip's share of an expert-parallel deployment."""

from __future__ import annotations

import numpy as np

from benchmark.reference import nemotron_h as reference

from . import common
from .afmoe import probe_generator, scope_arrays
from .gpt2 import GenerateBuild

# the reference takes the program's expert ids for a token where each of
# them scores within this of the reference's own 22nd (biased sigmoid)
# score. Ambiguous top-22 is common here: the 22nd of 512 scores has
# neighbours about 0.0025 away (a router logit of sd 1.28 has 34 experts
# a unit at the 96th percentile, a sigmoid's slope there is 0.09), and
# bfloat16 activations behind up to ten blocks of a bfloat16 residual
# stream move a logit by 0.03-0.05: a quarter of the compared tokens
# differ from the reference in a 22nd expert (which carries 1/22 of the
# routed part). Set between two chip readings (PERF.md section 2, PR 31):
# the largest gap adopted over the program's seeds, 0.0084, and the
# reference with float8 (e4m3) weights, 0.056 (and 2,005 tokens beyond
# 0.01). A bfloat16 recurrent STATE is not told apart by this cell
# (PERF.md 7 e); tests/test_nemotron_h.py holds the float32 at the op.
TIE_EPS = 0.02


def model_config(cfg_json, tiny=False):
    from paddle_tpu.models.nemotron_h import NemotronHConfig

    c = dict(cfg_json)
    dep, serving = dict(c["deployment"]), dict(c["serving"])
    weights = c["weights"]
    if tiny:
        t = c["tiny"]
        c.update({k: v for k, v in t.items() if k in c})
        dep["router_width"] = t["router_width"]
        serving["prefill_rows"] = t["prefill_rows"]
    pattern = c["hybrid_override_pattern"]
    if len(pattern) != c["num_hidden_layers"] \
            or len(dep["blocks_run"]) != len(pattern):
        raise ValueError("hybrid_override_pattern, num_hidden_layers and "
                         "deployment.blocks_run differ")
    if c["mamba_num_heads"] * c["mamba_head_dim"] \
            != c["expand"] * c["hidden_size"]:
        raise ValueError("mamba heads x head_dim is not expand x hidden")
    return NemotronHConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        pattern=pattern, mamba_num_heads=c["mamba_num_heads"],
        mamba_head_dim=c["mamba_head_dim"],
        ssm_state_size=c["ssm_state_size"], n_groups=c["n_groups"],
        conv_kernel=c["conv_kernel"], chunk_size=c["chunk_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        num_experts=dep["router_width"],
        num_local_experts=c["n_routed_experts"],
        expert_offset=dep["expert_offset"], top_k=c["num_experts_per_tok"],
        moe_latent_size=c["moe_latent_size"],
        moe_intermediate_size=c["moe_intermediate_size"],
        shared_intermediate_size=c["moe_shared_expert_intermediate_size"],
        route_scale=float(c["routed_scaling_factor"]),
        route_norm=c["norm_topk_prob"], rms_norm_eps=c["norm_eps"],
        initializer_range=weights["initializer_range"],
        expert_bias_std=weights["expert_bias_std"],
        a_range=weights["a_range"], time_step=weights["time_step"],
        dtype=serving["dtype"], prefill_rows=serving["prefill_rows"],
    )


def compare(gen, seen, tol, tie_eps=TIE_EPS, params=None, state_dtype=None):
    """`probe_generator`'s logits against the reference's full forward
    pass on the same (grown) prefix, the reference following the
    program's expert ids through ambiguous top-k only. `params` and
    `state_dtype` stand in for the scope's weights and the float32
    recurrent state in a reading below the stated precision
    (nemotron_h_once.py)."""
    cfg = gen.cfg
    if params is None:
        params = scope_arrays(gen.scope, reference.param_names(cfg.pattern))
    out = {"tol": tol, "tie_eps": tie_eps,
           "measure": "max|diff| / max|reference|"}
    ok = True
    for key, (prefix, got, picked) in zip(("prefill", "decode"), seen):
        ref = reference.forward(params, prefix, cfg, follow=picked,
                                tie_eps=tie_eps, state_dtype=state_dtype)
        err = common.logit_err(got, ref["logits"])
        out[f"{key}_err"] = err
        out[f"{key}_routing"] = ref["routing"]
        ok = ok and err <= tol and ref["routing"].get("mismatches", 0) == 0
    out["decode_steps"] = seen[1][0].shape[1] - seen[0][0].shape[1]
    out["ok"] = bool(ok)
    return out


def build_generate(cfg_json, traffic, tiny, seed, executor=None):
    """`traffic`: batch, prompt_len, new_tokens, logits_tol. Weights come
    from the generator's own startup program, seeded, in bfloat16."""
    from paddle_tpu.models.nemotron_h import NemotronHDecoder
    from paddle_tpu.serving import GPTGenerator
    from paddle_tpu.serving.generate import GPTGenerateRunner

    cfg = model_config(cfg_json, tiny)
    batch = traffic["batch"]
    prompt_len, new = traffic["prompt_len"], traffic["new_tokens"]
    gen = GPTGenerator(NemotronHDecoder(cfg), batch=batch,
                       context_len=prompt_len, max_len=prompt_len + new,
                       executor=executor)
    gen.init_params(seed=seed)

    def make_prompt(rng):
        return rng.randint(0, cfg.vocab_size, prompt_len).astype(np.int64)

    def probe(rng, decode_steps=8):
        prompts = np.stack([make_prompt(rng) for _ in range(batch)])
        return probe_generator(gen, prompts, decode_steps)

    def check(seen):
        return compare(gen, seen, traffic["logits_tol"])

    return GenerateBuild(
        generator=gen,
        runner=GPTGenerateRunner(gen, max_new_tokens=new),
        vocab_size=cfg.vocab_size, make_prompt=make_prompt, probe=probe,
        check=check,
    )
