"""GPT-2-family cells: causal-LM training of `models/gpt.py`, and
generation through `serving.GPTGenerator`."""

from __future__ import annotations

import dataclasses

import numpy as np

from benchmark.harness import flops
from benchmark.reference import gpt2 as reference

from . import common


def model_config(cfg_json, tiny=False, positions=None):
    from paddle_tpu.models.gpt import GPTConfig

    c = dict(cfg_json)
    if tiny:
        c.update(cfg_json["tiny"])
    return GPTConfig(
        vocab_size=c["vocab_size"], hidden_size=c["n_embd"],
        num_layers=c["n_layer"], num_heads=c["n_head"],
        intermediate_size=c["n_inner"],
        max_position=positions or c["n_positions"],
        hidden_dropout=c["resid_pdrop"], attention_dropout=c["attn_pdrop"],
        initializer_range=c["initializer_range"],
    )


def _reference_kwargs(cfg):
    return dict(layers=cfg.num_layers, heads=cfg.num_heads,
                hidden_dropout=cfg.hidden_dropout,
                attention_dropout=cfg.attention_dropout)


def build_train(cfg_json, traffic, chips, tiny, seed):
    """`traffic`: batch, seq; optional `positions` (rows of the learned
    position table, where the sequence is longer than the published
    context)."""
    import paddle_tpu as fluid
    from paddle_tpu.models import gpt_lm_loss

    batch, seq = traffic["batch"], traffic["seq"]
    cfg = model_config(cfg_json, tiny, traffic.get("positions"))
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        ids = fluid.data("ids", [batch // chips, seq], "int64")
        loss = gpt_lm_loss(ids, cfg)
        common.minimize(loss, startup, chips)

    def zipf_ids(rng, rows):
        return np.minimum(rng.zipf(1.3, (rows, seq)), cfg.vocab_size - 1)

    def make_feed(rng):
        return {"ids": zipf_ids(rng, batch).astype("int32")}

    def check(exe, scope, rng):
        """Two seeded sequences repeated to fill the batch: the mean loss
        over the batch is the mean over the two, and the logits compared
        are those after each one's last predicting position (the
        program's logits are [batch * (seq - 1), vocabulary])."""
        import jax

        two = zipf_ids(rng, 2)
        feed = {"ids": np.tile(two, (batch // 2, 1)).astype("int32")}
        got, logits = common.test_mode_forward(exe, scope, main, loss, feed)
        params = common.scope_params(
            scope, reference.param_names(cfg.num_layers)
        )
        kwargs = _reference_kwargs(cfg)
        want = float(jax.jit(
            lambda p, x: reference.lm_loss(p, x, **kwargs)
        )(params, two))
        ref_logits = jax.jit(
            lambda p, x: reference.last_logits(p, x, **kwargs)
        )(params, two[:, :-1])
        last = [r * (seq - 1) + seq - 2 for r in range(2)]
        return common.forward_check(got, want, logits[last], ref_logits,
                                    traffic)

    def kernel_cost():
        return flops.flash_tiled_step_cost(
            batch, cfg.num_heads, seq, cfg.hidden_size // cfg.num_heads,
            cfg.num_layers,
        )

    return common.TrainBuild(
        main=main, startup=startup, loss=loss, make_feed=make_feed,
        tokens_per_step=batch * seq,
        flops_per_token=flops.decoder_train_flops_per_token(
            cfg.hidden_size, cfg.num_layers, seq, cfg.vocab_size
        ),
        check=check, kernel_cost=kernel_cost,
    )


@dataclasses.dataclass
class GenerateBuild:
    """A generate cell, built: the generator behind its runner."""

    generator: object
    runner: object
    vocab_size: int
    make_prompt: object     # rng -> [prompt_len] int64
    probe: object           # rng -> what `check` compares; warms both programs
    check: object           # probe's result -> dict with "ok"


def build_generate(cfg_json, traffic, tiny, seed, executor=None):
    """`traffic`: batch, prompt_len, new_tokens. Weights come from the
    generator's own startup program, seeded."""
    from paddle_tpu.serving import GPTGenerator
    from paddle_tpu.serving.generate import GPTGenerateRunner

    cfg = model_config(cfg_json, tiny)
    batch = traffic["batch"]
    prompt_len, new = traffic["prompt_len"], traffic["new_tokens"]
    gen = GPTGenerator(cfg, batch=batch, context_len=prompt_len,
                       max_len=prompt_len + new, executor=executor)
    gen.init_params(seed=seed)

    def make_prompt(rng):
        return rng.randint(0, cfg.vocab_size, prompt_len).astype(np.int64)

    def probe(rng, decode_steps=8):
        """The prefill and `decode_steps` cached decode steps on a seeded
        batch, through the generator's own Executor with the feeds and
        fetches a request's batch uses (so both programs are compiled
        once this returns). For two rows, the prefix and the next-token
        logits after it: once after the prefill, once after the last
        step."""
        from paddle_tpu.framework.scope import scope_guard

        prompts = np.stack([make_prompt(rng) for _ in range(batch)])
        exe, scope = gen.executor, gen.scope
        gen.reset()
        with scope_guard(scope):
            # GPTGenerator hands out tokens only; its two programs and
            # their fetch names are read here to see the logits
            (logits,) = exe.run(gen.prefill_prog,
                                feed={"context_ids": prompts},
                                fetch_list=gen._prefill_fetch, scope=scope)
            logits = np.asarray(logits)[:, -1, :]
            seen = [(prompts[:2], logits[:2])]
            grown = prompts
            for t in range(decode_steps):
                nxt = np.argmax(logits, axis=-1)
                grown = np.concatenate([grown, nxt[:, None]], axis=1)
                (logits,) = exe.run(
                    gen.decode_prog,
                    feed={"token_ids": nxt[:, None].astype(np.int64),
                          "pos_ids": np.array([[prompt_len + t]], np.int64)},
                    fetch_list=gen._decode_fetch, scope=scope,
                )
                logits = np.asarray(logits)[:, -1, :]
            seen.append((grown[:2], logits[:2]))
        return seen

    def check(seen):
        """`probe`'s logits against the reference's full forward pass on
        the same (grown) prefix. Logits, not tokens: with random weights
        the largest logit changes on rounding."""
        import jax

        params = common.scope_params(
            gen.scope, reference.param_names(cfg.num_layers)
        )
        ref = jax.jit(
            lambda p, x: reference.last_logits(p, x, **_reference_kwargs(cfg))
        )
        errs = [common.logit_err(got, ref(params, prefix))
                for prefix, got in seen]
        tol = traffic["logits_tol"]
        return {"ok": bool(max(errs) <= tol), "prefill_err": errs[0],
                "decode_err": errs[1],
                "decode_steps": seen[1][0].shape[1] - prompt_len,
                "tol": tol, "measure": "max|diff| / max|reference|"}

    return GenerateBuild(
        generator=gen,
        runner=GPTGenerateRunner(gen, max_new_tokens=new),
        vocab_size=cfg.vocab_size, make_prompt=make_prompt, probe=probe,
        check=check,
    )

