#!/usr/bin/env python3
"""Once-only chip runs of the `evabyte_pp8` configuration, outside its
cell (PERF.md holds what they read):

    python3 benchmark/evabyte_once.py lower_precision [--rehearse]
    python3 benchmark/evabyte_once.py seeds [--count N] [--rehearse]
    python3 benchmark/evabyte_once.py long_prompt [--rehearse]

`lower_precision`: at the cell's own sizes, what the cell's comparison
reads when the reference is computed below what the configuration
states: weights rounded to float8 (e4m3, scaled per tensor), the
summaries dropped (each query attends its window only), the pooling made
a plain mean (mu = phi = 0), each of which must fail `logits_tol`; the
residual stream rounded to bfloat16 after every add, reported as told
apart or not.

`seeds`: the cell's comparison on `--count` further seeds of weights and
prompts through ONE generator (its start-up program run again a seed):
the largest errors the stated precision gives, which `logits_tol` has to
stay above.

`long_prompt`: batch 2, a prompt of 16,000 bytes + 400 cached decode
steps at the published widths: the decoding crosses the window boundary
at 16,384, where the summaries of the window that just closed become
visible. Prefill logits and the logits after 400 steps against the
reference's full forward pass, then against the reference with the
summaries dropped (which must fail); the summaries' share of the rows a
decode step's attention read and `kv_cache.bytes.summary`.
"""

import argparse
import copy
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")

CONFIG, CELL = "evabyte_pp8", "evabyte_pp8_generate_closed_4k"


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("what", choices=("lower_precision", "seeds",
                                     "long_prompt"))
    ap.add_argument("--count", type=int, default=12)
    ap.add_argument("--seed", type=int, default=2147483659)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import numpy as np

    from benchmark.afmoe_once import Float8Weights
    from benchmark.builders import evabyte as builder
    from benchmark.harness import device, manifest as mf
    from benchmark.reference import evabyte as reference

    record = device.record() if args.rehearse else device.require_tpu(1)
    manifest = mf.load()
    cfg_json = mf.config(manifest, CONFIG)
    _entry, cell = mf.cell(manifest, CELL)
    traffic = dict(cell["traffic"])
    steps = 8
    if args.what == "long_prompt":
        # 16,000 bytes (at the tiny size 60: windows of 16) + 400 steps
        # (12), across the next window boundary; a row a prefill dispatch
        traffic.update(batch=2, new_tokens=13 if args.rehearse else 401,
                       prompt_len=60 if args.rehearse else 16000)
        cfg_json = copy.deepcopy(cfg_json)
        cfg_json["serving"]["prefill_rows"] = 1
        steps = traffic["new_tokens"] - 1
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
    out["no_summaries"] = builder.compare(gen, seen, tol, summaries=False)
    if args.what == "long_prompt":
        from paddle_tpu import observability as obs
        from paddle_tpu.models.evabyte import COUNTERS_VAR

        written, summary_rows, ring_rows = (int(x) for x in np.asarray(
            gen.scope.find_var(COUNTERS_VAR)))
        out["eva"] = {
            "summaries_written": written, "summary_rows_read": summary_rows,
            "ring_rows_read": ring_rows,
            "summary_share": summary_rows / (summary_rows + ring_rows)}
        out["summary_bytes"] = obs.get_gauges().get("kv_cache.bytes.summary")
        out["window_bytes"] = obs.get_gauges().get("kv_cache.bytes.window")
    else:
        import jax.numpy as jnp

        names = reference.param_names(gen.cfg.num_layers)
        out["float8_weights"] = builder.compare(
            gen, seen, tol,
            params=Float8Weights(builder.scope_arrays(gen.scope, names)))
        out["mean_pooling"] = builder.compare(gen, seen, tol, pooling="mean")
        out["bfloat16_stream"] = builder.compare(gen, seen, tol,
                                                 stream_dtype=jnp.bfloat16)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
