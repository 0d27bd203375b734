"""Qwen3-Next-family cells: generation through `serving.GPTGenerator`
handed `models/qwen3_next.py`'s decoder, as one chip's share of an
expert-parallel, pipelined deployment."""

from __future__ import annotations

import numpy as np

from benchmark.reference import qwen3_next as reference

from . import common
from .afmoe import probe_generator, scope_arrays
from .gpt2 import GenerateBuild

# the reference takes the program's expert ids for a token where each of
# them has a router LOGIT within this of the reference's own 10th: the
# softmax scores are of order 1/512 and say nothing in absolute terms.
# Ambiguous top-10 is the rule here, not the exception: a router logit
# has sd 0.9 and 28 experts a unit at the 10th of 512, and bfloat16
# activations behind up to 24 sub-layers, each as large as the residual
# stream, move a logit by up to 0.3 (37% of the compared tokens take the
# program's ids). Set between two chip readings (PERF.md section 2,
# PR 38): the largest gap adopted over the program's 42 seeded readings,
# 0.298, and the reference with float8 (e4m3) weights, 2.57 (3,998 of
# 21,504 tokens beyond 0.9). A bfloat16 delta-rule STATE is not told
# apart by this cell (gap 0.32, logits 8.3e-2 / 9.5e-2 against 5.8e-2 /
# 6.2e-2 stated); tests/test_qwen3_next.py holds the float32 at the op.
TIE_EPS = 0.9


def model_config(cfg_json, tiny=False):
    from paddle_tpu.models.qwen3_next import Qwen3NextConfig

    c = dict(cfg_json)
    dep, serving = dict(c["deployment"]), dict(c["serving"])
    weights = c["weights"]
    if tiny:
        t = c["tiny"]
        c.update({k: v for k, v in t.items() if k in c})
        dep["router_width"] = t["router_width"]
        dep["layers_run"] = dep["layers_run"][:t["num_hidden_layers"]]
        serving.update(prefill_rows=t["prefill_rows"],
                       chunk_size=t["chunk_size"])
    layers_run = dep["layers_run"]
    if len(layers_run) != c["num_hidden_layers"] \
            or layers_run != list(range(layers_run[0], layers_run[-1] + 1)):
        raise ValueError("deployment.layers_run is not num_hidden_layers "
                         "consecutive layers")
    if c["mlp_only_layers"] or c["decoder_sparse_step"] != 1:
        raise ValueError("every layer's FFN is the expert FFN here")
    return Qwen3NextConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_layers=c["num_hidden_layers"],
        full_attention_interval=c["full_attention_interval"],
        first_layer=layers_run[0], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        partial_rotary_factor=c["partial_rotary_factor"],
        rope_theta=float(c["rope_theta"]),
        linear_num_key_heads=c["linear_num_key_heads"],
        linear_num_value_heads=c["linear_num_value_heads"],
        linear_key_head_dim=c["linear_key_head_dim"],
        linear_value_head_dim=c["linear_value_head_dim"],
        linear_conv_kernel_dim=c["linear_conv_kernel_dim"],
        chunk_size=serving["chunk_size"], num_experts=dep["router_width"],
        num_local_experts=c["num_experts"],
        expert_offset=dep["expert_offset"], top_k=c["num_experts_per_tok"],
        moe_intermediate_size=c["moe_intermediate_size"],
        shared_intermediate_size=c["shared_expert_intermediate_size"],
        route_norm=c["norm_topk_prob"], rms_norm_eps=c["rms_norm_eps"],
        initializer_range=weights["initializer_range"],
        a_range=weights["a_range"], time_step=weights["time_step"],
        dtype=serving["dtype"], prefill_rows=serving["prefill_rows"],
    )


def compare(gen, seen, tol, tie_eps=TIE_EPS, params=None, **below):
    """`probe_generator`'s logits against the reference's full forward
    pass on the same (grown) prefix, the reference following the
    program's expert ids through ambiguous top-k only. `params` and
    `below` (`state_dtype`, `correction`) stand in for the scope's
    weights, the float32 delta-rule state and the delta rule itself in a
    reading below the stated precision (qwen3_next_once.py)."""
    cfg = gen.cfg
    if params is None:
        params = scope_arrays(gen.scope,
                              reference.param_names(cfg.layer_kinds))
    out = {"tol": tol, "tie_eps": tie_eps,
           "measure": "max|diff| / max|reference|"}
    ok = True
    for key, (prefix, got, picked) in zip(("prefill", "decode"), seen):
        ref = reference.forward(params, prefix, cfg, follow=picked,
                                tie_eps=tie_eps, **below)
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
    from paddle_tpu.models.qwen3_next import Qwen3NextDecoder
    from paddle_tpu.serving import GPTGenerator
    from paddle_tpu.serving.generate import GPTGenerateRunner

    cfg = model_config(cfg_json, tiny)
    batch = traffic["batch"]
    prompt_len, new = traffic["prompt_len"], traffic["new_tokens"]
    gen = GPTGenerator(Qwen3NextDecoder(cfg), batch=batch,
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
