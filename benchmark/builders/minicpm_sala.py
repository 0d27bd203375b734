"""MiniCPM-SALA-family cells: generation through `serving.GPTGenerator`
handed `models/minicpm_sala.py`'s decoder, as one stage of a pipeline
(every layer whole on its chip)."""

from __future__ import annotations

import numpy as np

from benchmark.reference import minicpm_sala as reference

from . import common
from .afmoe import scope_arrays
from .gpt2 import GenerateBuild


def model_config(cfg_json, tiny=False):
    from paddle_tpu.models.minicpm_sala import MiniCPMSalaConfig

    c = dict(cfg_json)
    serving = dict(c["serving"])
    sparse = dict(c["assumed"]["sparse_config"])
    if tiny:
        t = c["tiny"]
        c.update({k: v for k, v in t.items() if k in c})
        c["mixer_types"] = c["mixer_types"][:t["num_hidden_layers"]]
        sparse.update(t["sparse_config"])
        serving.update(prefill_rows=t["prefill_rows"],
                       chunk_size=t["chunk_size"])
    if len(c["mixer_types"]) != c["num_hidden_layers"]:
        raise ValueError("mixer_types is not one kind a layer")
    if c["lightning_nh"] != c["lightning_nkv"]:
        raise ValueError("a Lightning layer's heads are not grouped here")
    return MiniCPMSalaConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        mixer_types=c["mixer_types"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        lightning_heads=c["lightning_nh"],
        lightning_head_dim=c["lightning_head_dim"],
        rope_theta=float(c["rope_theta"]),
        intermediate_size=c["intermediate_size"],
        rms_norm_eps=c["rms_norm_eps"], scale_emb=c["scale_emb"],
        scale_depth=c["scale_depth"], dim_model_base=c["dim_model_base"],
        mup_denominator=c["mup_denominator"],
        sparse_kernel=sparse["kernel_size"],
        sparse_stride=sparse["kernel_stride"],
        init_blocks=sparse["init_blocks"], block_size=sparse["block_size"],
        window_size=sparse["window_size"], topk=sparse["topk"],
        dense_len=sparse["dense_len"], chunk_size=serving["chunk_size"],
        initializer_range=c["weights"]["initializer_range"],
        attn_qk_gain=c["weights"]["attn_qk_gain"],
        dtype=serving["dtype"], prefill_rows=serving["prefill_rows"],
    )


def probe_generator(gen, prompts, decode_steps):
    """The prefill and `decode_steps` cached decode steps on `prompts`
    ([batch, context_len]) through the generator's own Executor, with
    the feeds and fetch lists of a request's batch, so that what is
    compared is what the two executables that serve the window compute
    (both are compiled once this returns). For rows 0 and 1: [(prefix
    ids, next-token logits)] once after the prefill and once after the
    last step."""
    from paddle_tpu.framework.scope import scope_guard

    exe, scope = gen.executor, gen.scope
    gen.reset()
    with scope_guard(scope):
        logits = np.concatenate([
            np.asarray(exe.run(gen.prefill_prog, feed=feed, scope=scope,
                               fetch_list=gen._prefill_fetch)[0])[:, -1]
            for feed in gen.prefill_feeds(prompts)])
        seen = [(prompts[:2], logits[:2])]
        grown = prompts
        for t in range(decode_steps):
            nxt = np.argmax(logits, axis=-1)
            grown = np.concatenate([grown, nxt[:, None]], axis=1)
            (got,) = exe.run(
                gen.decode_prog,
                feed={"token_ids": nxt[:, None].astype(np.int64),
                      "pos_ids": np.array([[gen.context_len + t]], np.int64)},
                fetch_list=gen._decode_fetch, scope=scope)
            logits = np.asarray(got)[:, -1]
        seen.append((grown[:2], logits[:2]))
    return seen


def compare(gen, seen, tol, params=None, **below):
    """`probe_generator`'s logits against the reference's full forward
    pass on the same (grown) prefix. `params` and `below` (`decay`,
    `output_gate`, `select`, `state_dtype`) stand in for the scope's
    weights and for the mathematics in a reading below the stated
    precision or of another mixer (minicpm_sala_once.py)."""
    cfg = gen.cfg
    if params is None:
        params = scope_arrays(gen.scope,
                              reference.param_names(cfg.layer_kinds))
    out = {"tol": tol, "measure": "max|diff| / max|reference|"}
    errs = []
    for key, (prefix, got) in zip(("prefill", "decode"), seen):
        ref = reference.forward(params, prefix, cfg,
                                prompt_len=gen.context_len, **below)
        errs.append(common.logit_err(got, ref["logits"]))
        out[f"{key}_err"] = errs[-1]
    out["decode_steps"] = seen[1][0].shape[1] - seen[0][0].shape[1]
    out["ok"] = bool(max(errs) <= tol)
    return out


def build_generate(cfg_json, traffic, tiny, seed, executor=None):
    """`traffic`: batch, prompt_len, new_tokens, logits_tol. Weights come
    from the generator's own startup program, seeded, in bfloat16."""
    from paddle_tpu.models.minicpm_sala import MiniCPMSalaDecoder
    from paddle_tpu.serving import GPTGenerator
    from paddle_tpu.serving.generate import GPTGenerateRunner

    cfg = model_config(cfg_json, tiny)
    batch = traffic["batch"]
    prompt_len, new = traffic["prompt_len"], traffic["new_tokens"]
    gen = GPTGenerator(MiniCPMSalaDecoder(cfg), batch=batch,
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
