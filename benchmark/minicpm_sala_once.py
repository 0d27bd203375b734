#!/usr/bin/env python3
"""Once-only chip runs of the `minicpm_sala_pp4` configuration, outside
its cell (PERF.md holds what they read):

    python3 benchmark/minicpm_sala_once.py lower_precision [--rehearse]
    python3 benchmark/minicpm_sala_once.py seeds [--count N] [--rehearse]
    python3 benchmark/minicpm_sala_once.py long_prompt [--rehearse]

`lower_precision`: at the cell's own sizes, what the cell's comparison
reads when the reference is computed below what the configuration
states, or as another mixer: weights rounded to float8 (e4m3, scaled per
tensor), the Lightning decay dropped (lambda = 1), both mixers' output
gates dropped, each of which must fail `logits_tol`; the Lightning state
rounded to bfloat16 after every step, reported as told apart or not.

`seeds`: the cell's comparison on `--count` further seeds of weights and
prompts through ONE generator (its start-up program run again a seed):
the largest errors the stated precision gives, which `logits_tol` has to
stay above.

`long_prompt`: batch 2, a prompt of 16,384 positions + 64 cached decode
steps at the published widths: `max_len` exceeds `dense_len`, so the
program lowers the compressed-key index and the selection, and a query
reads 64 of up to 257 blocks. Prefill logits and the logits after 64
steps against the reference's full forward pass, then against the same
reference with dense attention in place of the selection (which must
fail); the blocks selected over the blocks visible and
`kv_cache.bytes.index`.
"""

import argparse
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

CONFIG, CELL = "minicpm_sala_pp4", "minicpm_sala_pp4_generate_closed"


def prefill_counts(cfg, rows, length):
    """(blocks selected, blocks visible) the prefill of `rows` prompts of
    `length` adds to the counters, summed over the sparse layers: every
    query sees the blocks that start at or before it and reads at most
    `topk` of them."""
    import numpy as np

    from paddle_tpu.models.minicpm_sala import SPARSE

    seen = np.arange(length) // cfg.block_size + 1
    if length <= cfg.dense_len:
        return 0, 0
    per = rows * cfg.num_kv_heads * cfg.layer_kinds.count(SPARSE)
    return per * int(np.minimum(seen, cfg.topk).sum()), per * int(seen.sum())


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", choices=("lower_precision", "seeds",
                                     "long_prompt"))
    ap.add_argument("--count", type=int, default=12)
    ap.add_argument("--seed", type=int, default=3141592653)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import numpy as np

    from benchmark.afmoe_once import Float8Weights
    from benchmark.builders import minicpm_sala as builder
    from benchmark.harness import device, manifest as mf
    from benchmark.reference import minicpm_sala as reference

    record = device.record() if args.rehearse else device.require_tpu(1)
    manifest = mf.load()
    cfg_json = mf.config(manifest, CONFIG)
    _entry, cell = mf.cell(manifest, CELL)
    traffic = dict(cell["traffic"])
    steps = 8
    if args.what == "long_prompt":
        # 16,384 positions (at the tiny size 96: 24 blocks of 4, dense
        # up to 16) + 64 steps, a row a prefill dispatch
        traffic.update(batch=2, new_tokens=64,
                       prompt_len=96 if args.rehearse else 16384)
        cfg_json = copy.deepcopy(cfg_json)
        cfg_json["serving"]["prefill_rows"] = 1
        steps = 64
    elif args.rehearse:
        traffic.update(cell["rehearse"])
    build = builder.build_generate(cfg_json, traffic, args.rehearse,
                                   args.seed)
    gen = build.generator

    def probe(seed):
        rng = np.random.RandomState(seed % 2 ** 32)
        prompts = np.stack([build.make_prompt(rng)
                            for _ in range(traffic["batch"])])
        return builder.probe_generator(gen, prompts, steps)

    tol = traffic["logits_tol"]
    out = {"what": args.what, "device": record, "seed": args.seed,
           "batch": traffic["batch"], "prompt_len": traffic["prompt_len"],
           "decode_steps": steps}
    if args.what == "seeds":
        out["readings"] = []
        for n in range(1, args.count + 1):
            seed = args.seed + 7919 * n
            # the old weights go first: two sets need not fit the chip
            for name in list(gen.scope.local_var_names()):
                gen.scope.erase(name)
            gen.init_params(seed=seed)
            got = builder.compare(gen, probe(seed), tol)
            out["readings"].append({"seed": seed, **got})
            print(json.dumps(out["readings"][-1]), flush=True)
        for key in ("prefill_err", "decode_err"):
            out[f"largest_{key}"] = max(r[key] for r in out["readings"])
        print(json.dumps({k: v for k, v in out.items() if k != "readings"}),
              flush=True)
        return 0
    seen = probe(args.seed)
    out["stated"] = builder.compare(gen, seen, tol)
    if args.what == "long_prompt":
        from paddle_tpu import observability as obs
        from paddle_tpu.models.minicpm_sala import COUNTERS_VAR

        chosen, visible = (int(x) for x in np.asarray(
            gen.scope.find_var(COUNTERS_VAR)))
        pre = prefill_counts(gen.cfg, traffic["batch"],
                             traffic["prompt_len"])
        out["blocks"] = {
            "selected": chosen, "visible": visible,
            "share": chosen / visible,
            "decode_share": (chosen - pre[0]) / (visible - pre[1]),
            "prefill_closed_form": pre}
        out["index_bytes"] = obs.get_gauges().get("kv_cache.bytes.index")
        out["selecting_layers"] = obs.get_gauges().get(
            "sparse_attention.selecting_layers")
        out["dense_attention"] = builder.compare(gen, seen, tol,
                                                 select=False)
    else:
        import jax.numpy as jnp

        names = reference.param_names(gen.cfg.layer_kinds)
        out["float8_weights"] = builder.compare(
            gen, seen, tol,
            params=Float8Weights(builder.scope_arrays(gen.scope, names)))
        out["no_decay"] = builder.compare(gen, seen, tol, decay=False)
        out["no_output_gate"] = builder.compare(gen, seen, tol,
                                                output_gate=False)
        out["bfloat16_state"] = builder.compare(gen, seen, tol,
                                                state_dtype=jnp.bfloat16)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
