"""AFMoE-family cells (arcee-ai Trinity): generation through
`serving.GPTGenerator` handed `models/afmoe.py`'s decoder, as one chip's
share of an expert-parallel deployment."""

from __future__ import annotations

import math

import numpy as np

from benchmark.reference import afmoe as reference

from . import common
from .gpt2 import GenerateBuild

# the reference takes the program's expert ids for a token where each of
# them scores within this of the reference's own k-th (biased sigmoid)
# score: bfloat16 activations (3072 products, each operand rounded to 8
# bits, over five layers of a bfloat16 residual stream) move a router
# logit by about 0.01 and a sigmoid's slope is at most 1/4. On the chip
# the largest gap adopted was 0.0042 with every gain seeded near 1 and
# 0.0024 with the configuration's depth-scaled output norms (PERF.md,
# PR 27): a quarter of this. float8 weights read 46 mismatches of 7,168
# tokens beyond it; a bfloat16 ROUTER is not told apart here (its score
# steps of about 0.002 are inside what the stated bfloat16 activations
# do): tests/test_afmoe.py holds the router's float32 at the op, on
# logits that tie in bfloat16.
TIE_EPS = 0.01


def model_config(cfg_json, tiny=False):
    from paddle_tpu.models.afmoe import DENSE, EXPERTS, AfmoeConfig

    c = dict(cfg_json)
    dep, serving = dict(c["deployment"]), dict(c["serving"])
    weights = c["weights"]
    if tiny:
        t = c["tiny"]
        c.update({k: v for k, v in t.items() if k in c})
        dep["router_width"] = t["router_width"]
        serving["prefill_rows"] = t["prefill_rows"]
    layers_run = dep["layers_run"]
    if len(layers_run) != c["num_hidden_layers"]:
        raise ValueError("deployment.layers_run and num_hidden_layers differ")
    kinds = [
        (c["layer_types"][layer], DENSE if n < c["num_dense_layers"]
         else EXPERTS)
        for n, layer in enumerate(layers_run)
    ]
    return AfmoeConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        intermediate_size=c["intermediate_size"],
        moe_intermediate_size=c["moe_intermediate_size"],
        num_experts=dep["router_width"], num_local_experts=c["num_experts"],
        expert_offset=dep["expert_offset"], top_k=c["num_experts_per_tok"],
        num_shared_experts=c["num_shared_experts"],
        route_scale=c["route_scale"], route_norm=c["route_norm"],
        sliding_window=c["sliding_window"], rope_theta=c["rope_theta"],
        rms_norm_eps=c["rms_norm_eps"], layer_kinds=kinds,
        mup_enabled=c["mup_enabled"],
        norm_out_gain=1.0 / math.sqrt(weights["norm_out_depth"]),
        expert_bias_std=weights["expert_bias_std"], dtype=serving["dtype"],
        prefill_rows=serving["prefill_rows"],
    )


def scope_arrays(scope, names):
    """{name: the scope's own array}: on the device, in the dtype the
    program keeps it (the reference casts up a block at a time)."""
    return {n: scope.find_var(n) for n in names}


def probe_generator(gen, prompts, decode_steps):
    """The prefill and `decode_steps` cached decode steps on `prompts`
    ([batch, context_len]) through the generator's own Executor, with
    the feeds and the fetch lists of a request's batch: the logits and,
    beside them, the expert layers' selected ids. So what is compared is
    what the two executables that serve the window compute, and both
    are compiled once this returns. For rows 0 and 1: [(prefix ids,
    next-token logits, selected ids [expert layers][2, len(prefix), k])]
    once after the prefill and once after the last step."""
    from paddle_tpu.framework.scope import scope_guard

    exe, scope = gen.executor, gen.scope
    ctx_len, k = gen.context_len, gen.cfg.top_k

    def by_layer(selected):
        ids = np.asarray(selected)[:2]
        return [ids[..., i:i + k] for i in range(0, ids.shape[-1], k)]

    gen.reset()
    with scope_guard(scope):
        logits, picked = [], None
        for feed in gen.prefill_feeds(prompts):
            got, selected = exe.run(gen.prefill_prog, feed=feed, scope=scope,
                                    fetch_list=gen._prefill_fetch)
            logits.append(np.asarray(got)[:, -1, :])
            if picked is None:
                picked = by_layer(selected)
            elif picked[0].shape[0] < 2:    # one row a dispatch
                picked = [np.concatenate([p, s]) for p, s in
                          zip(picked, by_layer(selected))]
        logits = np.concatenate(logits)
        seen = [(prompts[:2], logits[:2], picked)]
        grown = prompts
        for t in range(decode_steps):
            nxt = np.argmax(logits, axis=-1)
            grown = np.concatenate([grown, nxt[:, None]], axis=1)
            got, selected = exe.run(
                gen.decode_prog,
                feed={"token_ids": nxt[:, None].astype(np.int64),
                      "pos_ids": np.array([[ctx_len + t]], np.int64)},
                fetch_list=gen._decode_fetch, scope=scope,
            )
            logits = np.asarray(got)[:, -1, :]
            picked = [np.concatenate([p, s], axis=1)
                      for p, s in zip(picked, by_layer(selected))]
        seen.append((grown[:2], logits[:2], picked))
    return seen


def compare(gen, seen, tol, tie_eps=TIE_EPS, params=None, score_dtype=None):
    """`probe_generator`'s logits against the reference's full forward
    pass on the same (grown) prefix, the reference following the
    program's expert ids through ambiguous top-k only. `params` and
    `score_dtype` stand in for the scope's weights and the float32
    router in a reading below the stated precision (afmoe_once.py)."""
    cfg = gen.cfg
    if params is None:
        params = scope_arrays(gen.scope,
                              reference.param_names(cfg.layer_kinds))
    out = {"tol": tol, "tie_eps": tie_eps,
           "measure": "max|diff| / max|reference|"}
    ok = True
    for key, (prefix, got, picked) in zip(("prefill", "decode"), seen):
        ref = reference.forward(params, prefix, cfg, follow=picked,
                                tie_eps=tie_eps, score_dtype=score_dtype)
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
    from paddle_tpu.models.afmoe import AfmoeDecoder
    from paddle_tpu.serving import GPTGenerator
    from paddle_tpu.serving.generate import GPTGenerateRunner

    cfg = model_config(cfg_json, tiny)
    batch = traffic["batch"]
    prompt_len, new = traffic["prompt_len"], traffic["new_tokens"]
    gen = GPTGenerator(AfmoeDecoder(cfg), batch=batch,
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
