#!/usr/bin/env python3
"""Once-only chip runs of the `trinity_large_ep8` configuration, outside
its cell (PERF.md holds what they read):

    python3 benchmark/afmoe_once.py beyond_window [--rehearse]
    python3 benchmark/afmoe_once.py lower_precision [--rehearse]

`beyond_window`: batch 2, a prompt of 4,608 tokens + 64 new ones at the
published widths (window 4,096): the prefill is longer than a window
layer's ring, the ring wraps, window and full layers see different
keys; prefill logits and the logits after 64 cached steps against the
reference's full forward pass.

`lower_precision`: at the cell's own sizes, what the cell's comparison
reads when the reference is computed below the precision the
configuration states: weights rounded to float8 (e4m3, scaled per
tensor), and the router's logits rounded to bfloat16.
"""

import argparse
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


class Float8Weights(dict):
    """The scope's arrays, each matrix rounded to float8 e4m3 (scaled to
    the format's range per tensor) as it is read."""

    def __getitem__(self, name):
        import jax.numpy as jnp

        w = super().__getitem__(name)
        if w.ndim < 2:
            return w
        scale = 448.0 / jnp.max(jnp.abs(w)).astype(jnp.float32)
        w8 = (w.astype(jnp.float32) * scale).astype(jnp.float8_e4m3fn)
        return (w8.astype(jnp.float32) / scale).astype(w.dtype)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", choices=("beyond_window", "lower_precision"))
    ap.add_argument("--seed", type=int, default=2718281828)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import numpy as np

    from benchmark.builders import afmoe as builder
    from benchmark.harness import device, manifest as mf
    from benchmark.reference import afmoe as reference

    record = device.record() if args.rehearse else device.require_tpu(1)
    manifest = mf.load()
    cfg_json = mf.config(manifest, "trinity_large_ep8")
    _entry, cell = mf.cell(manifest, "trinity_large_ep8_generate_closed")
    traffic = dict(cell["traffic"])
    if args.what == "beyond_window":
        sizes = cfg_json["tiny"] if args.rehearse else cfg_json
        window = sizes["sliding_window"]
        # 1 1/8 windows of prompt, then 64 steps; the two rows in one
        # prefill dispatch
        traffic.update(batch=2, prompt_len=window + window // 8,
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

        names = reference.param_names(gen.cfg.layer_kinds)
        out["float8_weights"] = builder.compare(
            gen, seen, traffic["logits_tol"],
            params=Float8Weights(builder.scope_arrays(gen.scope, names)))
        out["bfloat16_router"] = builder.compare(
            gen, seen, traffic["logits_tol"], score_dtype=jnp.bfloat16)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
