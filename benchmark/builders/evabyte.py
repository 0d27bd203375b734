"""EvaByte-family cells: byte-level generation through
`serving.GPTGenerator` handed `models/evabyte.py`'s decoder, as one stage
of a pipeline (every layer whole on its chip)."""

from __future__ import annotations

import numpy as np

from benchmark.reference import evabyte as reference

from . import common
from .afmoe import scope_arrays
from .gpt2 import GenerateBuild
from .minicpm_sala import probe_generator


def model_config(cfg_json, tiny=False):
    from paddle_tpu.models.evabyte import EvaByteConfig

    c = dict(cfg_json)
    serving = dict(c["serving"])
    if tiny:
        c.update({k: v for k, v in c["tiny"].items() if k in c})
        serving.update(prefill_rows=c["tiny"]["prefill_rows"])
    if c["num_key_value_heads"] != c["num_attention_heads"]:
        raise ValueError("EVA attention's heads are not grouped here")
    return EvaByteConfig(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_layers=c["num_hidden_layers"],
        num_heads=c["num_attention_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        intermediate_size=c["intermediate_size"],
        rope_theta=float(c["rope_theta"]), rms_norm_eps=c["rms_norm_eps"],
        window_size=c["window_size"], chunk_size=c["chunk_size"],
        initializer_range=c["weights"]["init_std"],
        pool_std=c["weights"]["pool_std"], dtype=serving["dtype"],
        prefill_rows=serving["prefill_rows"],
    )


def compare(gen, seen, tol, params=None, **below):
    """`probe_generator`'s logits against the reference's full forward
    pass on the same (grown) prefix. `params` and `below` (`summaries`,
    `pooling`, `stream_dtype`) stand in for the scope's weights and for
    the mathematics in a reading below what the configuration states
    (evabyte_once.py)."""
    cfg = gen.cfg
    if params is None:
        params = scope_arrays(gen.scope, reference.param_names(
            cfg.num_layers))
    out = {"tol": tol, "measure": "max|diff| / max|reference|"}
    errs = []
    for key, (prefix, got) in zip(("prefill", "decode"), seen):
        ref = reference.forward(params, prefix, cfg, **below)
        errs.append(common.logit_err(got, ref["logits"]))
        out[f"{key}_err"] = errs[-1]
    out["decode_steps"] = seen[1][0].shape[1] - seen[0][0].shape[1]
    out["ok"] = bool(max(errs) <= tol)
    return out


def build_generate(cfg_json, traffic, tiny, seed, executor=None):
    """`traffic`: batch, prompt_len, new_tokens, logits_tol. Weights come
    from the generator's own startup program, seeded, in bfloat16; a
    prompt's ids are drawn from the whole vocabulary (256 bytes and the
    special ids)."""
    from paddle_tpu.models.evabyte import EvaByteDecoder
    from paddle_tpu.serving import GPTGenerator
    from paddle_tpu.serving.generate import GPTGenerateRunner

    cfg = model_config(cfg_json, tiny)
    batch = traffic["batch"]
    prompt_len, new = traffic["prompt_len"], traffic["new_tokens"]
    gen = GPTGenerator(EvaByteDecoder(cfg), batch=batch,
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
