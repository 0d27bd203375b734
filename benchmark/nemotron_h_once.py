#!/usr/bin/env python3
"""Once-only chip runs of the `nemotron3_super_ep4` configuration,
outside its cell (PERF.md holds what they read):

    python3 benchmark/nemotron_h_once.py odd_prompt [--rehearse]
    python3 benchmark/nemotron_h_once.py lower_precision [--rehearse]

`odd_prompt`: batch 2, a prompt of 1,000 tokens (not a multiple of the
scan's chunk of 128: the last chunk is padded with steps of size 0) + 64
new ones at the published widths, the two rows in one prefill dispatch;
prefill logits and the logits after 64 cached steps (conv tail,
recurrent state and KV cache read back 64 times) against the reference's
full forward pass.

`lower_precision`: at the cell's own sizes, what the cell's comparison
reads when the reference is computed below the precision the
configuration states: weights rounded to float8 (e4m3, scaled per
tensor), and the recurrent state rounded to bfloat16 after every step.
"""

import argparse
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

CONFIG, CELL = "nemotron3_super_ep4", "nemotron3_super_ep4_generate_closed"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", choices=("odd_prompt", "lower_precision"))
    ap.add_argument("--seed", type=int, default=3141592653)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import numpy as np

    from benchmark.afmoe_once import Float8Weights
    from benchmark.builders import nemotron_h as builder
    from benchmark.harness import device, manifest as mf
    from benchmark.reference import nemotron_h as reference

    record = device.record() if args.rehearse else device.require_tpu(1)
    manifest = mf.load()
    cfg_json = mf.config(manifest, CONFIG)
    _entry, cell = mf.cell(manifest, CELL)
    traffic = dict(cell["traffic"])
    if args.what == "odd_prompt":
        sizes = cfg_json["tiny"] if args.rehearse else cfg_json
        chunk = sizes["chunk_size"]
        # 7 13/16 chunks of prompt (1,000 at the published 128), then 64
        # steps; the two rows in one prefill dispatch
        traffic.update(batch=2, prompt_len=8 * chunk - 3 * chunk // 16,
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
    rng = np.random.RandomState(args.seed % 2 ** 32)
    prompts = np.stack([build.make_prompt(rng)
                        for _ in range(traffic["batch"])])
    seen = builder.probe_generator(gen, prompts, steps)
    out = {"what": args.what, "device": record, "seed": args.seed,
           "batch": traffic["batch"], "prompt_len": traffic["prompt_len"],
           "decode_steps": steps,
           "stated": builder.compare(gen, seen, traffic["logits_tol"])}
    if args.what == "lower_precision":
        import jax.numpy as jnp

        names = reference.param_names(gen.cfg.pattern)
        out["float8_weights"] = builder.compare(
            gen, seen, traffic["logits_tol"],
            params=Float8Weights(builder.scope_arrays(gen.scope, names)))
        out["bfloat16_state"] = builder.compare(
            gen, seen, traffic["logits_tol"], state_dtype=jnp.bfloat16)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
