"""dots.vlm1-family cells (rednote-hilab, `model_type` "dots_vlm"):
generation through `serving.GPTGenerator` handed `models/dots_vlm.py`'s
decoder, as one chip's share of an expert-parallel deployment."""

from __future__ import annotations

import numpy as np

from benchmark.reference import dots_vlm as reference

from . import common
from .afmoe import probe_generator, scope_arrays
from .gpt2 import GenerateBuild

# the reference takes the program's expert ids for a token where the
# difference is an ambiguous selection (reference/dots_vlm.py::route):
# every group a program id lies in scores within 2 x this of the
# reference's 4th group (a group's score is a sum of two), and with those
# groups kept every program id has a biased sigmoid score within this of
# the 8th best. bfloat16 activations over 7168 products and five layers
# of a bfloat16 residual stream move a score by a few thousandths, and
# group-limited selection is ambiguous often: 13-14% of the compared
# tokens take the program's ids, 4% with another group kept. Set between
# two chip readings (PERF.md section 2, PR 33): the largest gap the
# program gave over 22 seeded readings, 0.0183 (group gap 0.0104; the
# readings' largest gaps have mean 0.0112 and spread 0.0036), and the
# reference below the stated precision: cache rows rounded on to float8
# 0.046, float8 (e4m3) weights 0.146 (3,255 of 7,168 tokens beyond
# 0.01). 0.03 is about the geometric mean of 0.0183 and 0.046.
TIE_EPS = 0.03


def model_config(cfg_json, tiny=False):
    from paddle_tpu.models.dots_vlm import (
        DENSE, EXPERTS, LATENT, DotsVlmConfig,
    )

    c = dict(cfg_json)
    dep, serving = dict(c["deployment"]), dict(c["serving"])
    weights = c["weights"]
    if tiny:
        t = c["tiny"]
        c.update({k: v for k, v in t.items() if k in c})
        dep["router_width"] = t["router_width"]
        serving["prefill_rows"] = t["prefill_rows"]
    if len(dep["layers_run"]) != c["num_hidden_layers"]:
        raise ValueError("deployment.layers_run and num_hidden_layers differ")
    if c["rope_scaling"]["type"] != "yarn" or c["scoring_func"] != "sigmoid":
        raise ValueError("dots_vlm: YaRN positions and sigmoid scores only")
    kinds = [(LATENT, DENSE if n < c["first_k_dense_replace"] else EXPERTS)
             for n in range(c["num_hidden_layers"])]
    return DotsVlmConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_heads=c["num_attention_heads"], q_lora_rank=c["q_lora_rank"],
        kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        intermediate_size=c["intermediate_size"],
        moe_intermediate_size=c["moe_intermediate_size"],
        num_experts=dep["router_width"],
        num_local_experts=c["n_routed_experts"],
        expert_offset=dep["expert_offset"], top_k=c["num_experts_per_tok"],
        n_group=c["n_group"], topk_group=c["topk_group"],
        num_shared_experts=c["n_shared_experts"],
        route_scale=float(c["routed_scaling_factor"]),
        route_norm=c["norm_topk_prob"], rope_theta=float(c["rope_theta"]),
        rope_scaling={k: v for k, v in c["rope_scaling"].items()
                      if k != "type"},
        rms_norm_eps=c["rms_norm_eps"], layer_kinds=kinds,
        initializer_range=weights["initializer_range"],
        expert_bias_std=weights["expert_bias_std"], dtype=serving["dtype"],
        prefill_rows=serving["prefill_rows"],
    )


def compare(gen, seen, tol, tie_eps=TIE_EPS, params=None, latent_dtype=None):
    """`probe_generator`'s logits (prefill in the expanded form, decode
    steps in the absorbed form through the cache) against the reference's
    full forward pass in the expanded form on the same (grown) prefix,
    the reference following the program's expert ids through ambiguous
    selections only. `params` and `latent_dtype` stand in for the
    scope's weights and the bfloat16 cache rows in a reading below the
    stated precision (dots_vlm_once.py)."""
    cfg = gen.cfg
    if params is None:
        params = scope_arrays(gen.scope,
                              reference.param_names(cfg.layer_kinds))
    out = {"tol": tol, "tie_eps": tie_eps,
           "measure": "max|diff| / max|reference|"}
    ok = True
    for key, (prefix, got, picked) in zip(("prefill", "decode"), seen):
        ref = reference.forward(params, prefix, cfg, follow=picked,
                                tie_eps=tie_eps, latent_dtype=latent_dtype)
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
    from paddle_tpu.models.dots_vlm import DotsVlmDecoder
    from paddle_tpu.serving import GPTGenerator
    from paddle_tpu.serving.generate import GPTGenerateRunner

    cfg = model_config(cfg_json, tiny)
    batch = traffic["batch"]
    prompt_len, new = traffic["prompt_len"], traffic["new_tokens"]
    gen = GPTGenerator(DotsVlmDecoder(cfg), batch=batch,
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
