#!/usr/bin/env python3
"""Once-only chip runs of the `qwen3_next_ep8` configuration, outside
its cell (PERF.md holds what they read):

    python3 benchmark/qwen3_next_once.py odd_prompt [--rehearse]
    python3 benchmark/qwen3_next_once.py lower_precision [--rehearse]
    python3 benchmark/qwen3_next_once.py seeds [--count N] [--rehearse]

`odd_prompt`: batch 2, a prompt of 1,000 tokens (15 5/8 of the delta
rule's chunks of 64: the last chunk is padded with rows of beta = 0,
g = 0; no multiple of the prefill attention's query block either) + 64
new ones at the published widths, the two rows in one prefill dispatch;
prefill logits and the logits after 64 cached steps (conv tail,
delta-rule state and KV caches read back 64 times) against the
reference's full forward pass.

`lower_precision`: at the cell's own sizes, what the cell's comparison
reads when the reference is computed below what the configuration
states: weights rounded to float8 (e4m3, scaled per tensor), which must
fail; the delta rule's CORRECTION dropped (u_t = beta_t v_t), which must
fail by `logits_tol` (else the check is blind to the delta rule); the
delta-rule state rounded to bfloat16 after every step, reported as told
apart or not.

`seeds`: the cell's comparison on `--count` further seeds of weights and
prompts through ONE generator (its start-up program run again a seed),
with the routing followed through every difference (a wide `tie_eps`),
so that the largest gaps the stated precision gives are read, not
clipped: what `logits_tol` and `TIE_EPS` have to stay above.
"""

import argparse
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

CONFIG, CELL = "qwen3_next_ep8", "qwen3_next_ep8_generate_closed"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", choices=("odd_prompt", "lower_precision",
                                     "seeds"))
    ap.add_argument("--count", type=int, default=12)
    ap.add_argument("--seed", type=int, default=2236067977)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import numpy as np

    from benchmark.afmoe_once import Float8Weights
    from benchmark.builders import qwen3_next as builder
    from benchmark.harness import device, manifest as mf
    from benchmark.reference import qwen3_next as reference

    record = device.record() if args.rehearse else device.require_tpu(1)
    manifest = mf.load()
    cfg_json = mf.config(manifest, CONFIG)
    _entry, cell = mf.cell(manifest, CELL)
    traffic = dict(cell["traffic"])
    if args.what == "odd_prompt":
        sizes = cfg_json["tiny"] if args.rehearse else cfg_json["serving"]
        chunk = sizes["chunk_size"]
        # 15 5/8 chunks of prompt (1,000 at the published 64), then 64
        # steps; the two rows in one prefill dispatch
        traffic.update(batch=2, prompt_len=15 * chunk + 5 * chunk // 8,
                       new_tokens=64 + 1)
        cfg_json = copy.deepcopy(cfg_json)
        cfg_json["serving"]["prefill_rows"] = None
        cfg_json["tiny"]["prefill_rows"] = None
        steps = 64
    else:
        if args.rehearse:
            traffic.update(cell["rehearse"])
        steps = 8
    build = builder.build_generate(cfg_json, traffic, args.rehearse,
                                   args.seed)
    gen = build.generator

    def probe(seed):
        rng = np.random.RandomState(seed % 2 ** 32)
        prompts = np.stack([build.make_prompt(rng)
                            for _ in range(traffic["batch"])])
        return builder.probe_generator(gen, prompts, steps)

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
            got = builder.compare(gen, probe(seed), traffic["logits_tol"],
                                  tie_eps=1e9)
            out["readings"].append({"seed": seed, **got})
            print(json.dumps(out["readings"][-1]), flush=True)
        for key in ("prefill_err", "decode_err"):
            out[f"largest_{key}"] = max(r[key] for r in out["readings"])
        out["largest_gap"] = max(
            r[f"{phase}_routing"]["largest_gap"]
            for r in out["readings"] for phase in ("prefill", "decode"))
        print(json.dumps({k: v for k, v in out.items() if k != "readings"}),
              flush=True)
        return 0
    seen = probe(args.seed)
    tol = traffic["logits_tol"]
    out["stated"] = builder.compare(gen, seen, tol)
    if args.what == "lower_precision":
        import jax.numpy as jnp

        names = reference.param_names(gen.cfg.layer_kinds)
        out["float8_weights"] = builder.compare(
            gen, seen, tol,
            params=Float8Weights(builder.scope_arrays(gen.scope, names)))
        out["no_correction"] = builder.compare(gen, seen, tol,
                                               correction=False)
        out["bfloat16_state"] = builder.compare(gen, seen, tol,
                                                state_dtype=jnp.bfloat16)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
