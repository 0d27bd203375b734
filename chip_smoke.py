#!/usr/bin/env python3
"""chip_smoke.py — does the main path still start on the chip?

Drives the system once through the entry points a user calls
(``fluid.Program`` / ``program_guard`` / ``optimizer.minimize`` /
``Executor.run``; ``serving.freeze_program`` + ``Server`` +
``EndpointConfig``; ``GPTGenerator`` + ``GPTGenerateRunner``) at the full
width of the models the repo supports, in ONE process (a chip belongs to
one process; this script starts no child), and checks what comes out by
the repo's own means. Weights are random, made from a seed.

    python chip_smoke.py            one chip: train, kernels, longctx,
                                    serve, generate
    python chip_smoke.py --chips 4  four chips: the BERT-base step on one
                                    device, then dp=4 / dp2 x mp2 GSPMD /
                                    dp=4 ZeRO, and ring attention sp=4

Every phase prints one JSON line (name, wall seconds, compile seconds,
compile-cache hits/misses, what it checked). The LAST line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with the device as JAX reports it. Without a TPU the script prints
``"ok": false`` and exits non-zero before it builds anything; any phase
failing does the same at the end. It sets no platform itself. A time is a
host-clock reading on the device named in that last line; no rate is
taken against a peak, because the script looks up no peak.

The compile cache follows paddle_tpu/core/compile_cache.py: where
``JAX_COMPILATION_CACHE_DIR`` is set it is used as is, otherwise
``<checkout>/.jax_cache`` — a second run's compile seconds fall and its
cache hits rise.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import threading
import time
import traceback

import numpy as np

SEED = 20260926
LEARNING_RATE = 1e-4
#: one bf16 ulp (8 mantissa bits), relative: the tolerance for two bf16
#: computations of the same loss by different routes (Pallas kernels vs
#: composed ops; one device vs a mesh layout)
BF16_RTOL = 2.0 ** -8
#: fp32 attention through two differently tiled kernels
F32_ATTN_TOL = 2e-3
#: served class probabilities vs a direct run of the same frozen fp32
#: program. The rows reach the device in whatever buckets the router
#: formed, the direct run in full ones; the chip runs fp32 matmuls as bf16
#: MXU passes by default and tiles them by batch size, so rows differ by
#: up to a bf16 ulp of a probability (measured 5.9e-4, PR 21)
SERVE_ATOL = BF16_RTOL
#: KV-cache decode vs full recompute logits at the first differing token
GEN_LOGIT_ATOL = 5e-2
#: the device-side token hand-off vs the host's argmax chain through the
#: same two executables: a row in which some step's two largest logits
#: lie closer than this is counted and left out of the comparison (the
#: two runs repeat each other to the bit unless the chip does not; a row
#: left out says so)
GEN_TIE_EPS = 1e-4


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the smoke runs at. REAL is the contract; tests/test_chip_smoke.py
    rehearses the same code on the CPU with a tiny instance, and
    tests/test_tpu_compile.py compiles the kernels at REAL's shapes."""

    bert: str            # BertConfig classmethod: "base" | "tiny"
    bert_batch: int
    bert_seq: int
    train_steps: int
    gpt: str             # GPTConfig classmethod: "small" | "tiny"
    long_batch: int
    long_seq: int
    serve_seq: int
    serve_buckets: tuple
    gen_context: int
    gen_new: int
    moe: str             # "cell": the expert configurations' files | "tiny"
    moe_batch: int
    moe_context: int
    moe_max_len: int
    moe_new: int
    ring_batch: int
    ring_heads: int
    ring_seq: int
    ring_head_dim: int


REAL = Sizes(
    bert="base", bert_batch=32, bert_seq=512, train_steps=8,
    gpt="small", long_batch=2, long_seq=4096,
    serve_seq=128, serve_buckets=(1, 2, 4, 8),
    gen_context=512, gen_new=16,
    # the Trinity generate cell's shapes: the whole decode batch, its caches
    moe="cell", moe_batch=64, moe_context=896, moe_max_len=1024, moe_new=9,
    ring_batch=1, ring_heads=12, ring_seq=8192, ring_head_dim=64,
)


#: parameters whose fresh values are digested to show that two builds
#: started from the same weights
BERT_PROBE = ("word_embedding", "bert_l0_attn_qkv_w", "mlm_out_w")


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def _check(cond, what):
    if not cond:
        raise SmokeFailure(what)


def _rel(a, b):
    return abs(a - b) / max(abs(b), 1e-30)


# ---------------------------------------------------------------------------
# compile accounting (jax.monitoring): seconds spent tracing, lowering and
# compiling, and persistent-cache hits/misses
# ---------------------------------------------------------------------------


class CompileMeter:
    """Seconds by stage and cache events, summed since the process began:
    `trace` + `lower` (Python: jaxpr, then StableHLO) are paid on every
    run; `backend` is XLA's compile or, on a cache hit, the load from the
    persistent cache (`retrieval` is the part of it spent reading)."""

    STAGES = {
        "/jax/core/compile/jaxpr_trace_duration": "trace",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
        "/jax/core/compile/backend_compile_duration": "backend",
        "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval",
    }
    EVENTS = {
        "/jax/compilation_cache/cache_hits": "hits",
        "/jax/compilation_cache/cache_misses": "misses",
    }

    def __init__(self):
        import jax.monitoring as mon

        self.totals = dict.fromkeys(
            [*self.STAGES.values(), *self.EVENTS.values()], 0
        )
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, duration_secs, **_kw):
        if event in self.STAGES:
            self.totals[self.STAGES[event]] += duration_secs

    def _on_event(self, event, **_kw):
        if event in self.EVENTS:
            self.totals[self.EVENTS[event]] += 1

    def since(self, before=None):
        """The line fields for what happened since `before` (a copy of
        `totals`); compile_s = trace + lower + backend."""
        d = {k: v - (before or {}).get(k, 0) for k, v in self.totals.items()}
        return {
            "compile_s": round(d["trace"] + d["lower"] + d["backend"], 2),
            "trace_s": round(d["trace"], 2),
            "lower_s": round(d["lower"], 2),
            "backend_s": round(d["backend"], 2),
            "cache_retrieval_s": round(d["retrieval"], 2),
            "cache_hits": d["hits"], "cache_misses": d["misses"],
        }


def run_phase(name, fn, meter, *args):
    """Run one phase; print its JSON line; return whether it passed."""
    before = dict(meter.totals)
    t0 = time.perf_counter()
    line = {"phase": name, "ok": False}
    try:
        line["checked"] = fn(*args)
        line["ok"] = True
    except Exception as exc:  # boundary: report, keep going, exit non-zero
        line["error"] = f"{type(exc).__name__}: {exc}"[:2000]
        traceback.print_exc(file=sys.stderr)
    line["wall_s"] = round(time.perf_counter() - t0, 2)
    line.update(meter.since(before))
    print(json.dumps(line), flush=True)
    gc.collect()
    return line["ok"]


# ---------------------------------------------------------------------------
# shared builders
# ---------------------------------------------------------------------------


def _amp(opt):
    """The AMP recipe of every training cell: bf16, static loss scale 1."""
    from paddle_tpu.contrib import mixed_precision as mp

    return mp.decorate(
        opt,
        amp_lists=mp.AutoMixedPrecisionLists(
            custom_white_list={"softmax", "layer_norm"}
        ),
        use_dynamic_loss_scaling=False,
        init_loss_scaling=1.0,
        dest_dtype="bfloat16",
    )


def bert_config(sz, dropout=None, kernels=True):
    from paddle_tpu.models import BertConfig

    cfg = getattr(BertConfig, sz.bert)()
    if dropout is not None:
        cfg.hidden_dropout = cfg.attention_dropout = dropout
    cfg.use_fused_attention = cfg.use_fused_residual = kernels
    return cfg


def gpt_config(sz, max_position=None):
    from paddle_tpu.models.gpt import GPTConfig

    cfg = getattr(GPTConfig, sz.gpt)()
    if max_position is not None:
        cfg.max_position = max_position
    return cfg


def build_bert_train(cfg, b, s, n_pred, minimize):
    """The BERT pre-training build: masked-position MLM head, `minimize`
    applied to the loss (plain AMP Adam, or a fleet optimizer)."""
    import paddle_tpu as fluid
    from paddle_tpu.models import bert_pretrain

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = SEED
    with fluid.program_guard(main, startup):
        ids = fluid.data("ids", [b, s], "int64")
        types = fluid.data("types", [b, s], "int64")
        mask = fluid.data("mask", [b, s], "float32")
        mask_pos = fluid.data("mask_pos", [n_pred], "int64")
        labels = fluid.data("labels", [n_pred], "int64")
        loss = bert_pretrain(ids, types, mask, labels, cfg,
                             mask_pos=mask_pos)
        minimize(loss, startup)
    return main, startup, loss


def _adam_amp(loss, startup):
    from paddle_tpu.optimizer import Adam

    _amp(Adam(LEARNING_RATE)).minimize(loss, startup)


def bert_batch(rng, cfg, b, s, n_pred, shards=1):
    """One MLM batch. Token ids follow a Zipf law and the label of a
    predicted position is the token there, so a few Adam steps lower the
    loss measurably (uniform random labels sit at ln(V) whatever the
    model does). Positions are drawn per batch shard: `local_pos` indexes
    a shard's own flattened [b/shards * s] rows (what a dp shard_map
    program gathers from), `mask_pos` the whole batch's."""
    ids = np.minimum(rng.zipf(1.3, (b, s)), cfg.vocab_size - 1)
    rows = b // shards * s
    per = n_pred // shards
    local = np.concatenate([
        rng.choice(rows, per, replace=False) for _ in range(shards)
    ])
    glob = local + np.repeat(np.arange(shards) * rows, per)
    feed = {
        "ids": ids.astype("int32"),
        "types": rng.randint(0, cfg.type_vocab_size, (b, s)).astype("int32"),
        "mask": np.ones((b, s), "float32"),
        "mask_pos": glob.astype("int32"),
        "labels": ids.reshape(-1)[glob].astype("int32"),
    }
    return feed, local.astype("int32")


def custom_calls(hlo_text):
    """Pallas (Mosaic) kernels in an optimized HLO module."""
    return hlo_text.count('custom_call_target="tpu_custom_call"')


def compiled_step(exe, program, feed, fetch_list, scope):
    """The compiled executable of the step `exe.run` dispatches for these
    arguments (a later `run` does not compile it again)."""
    return exe.lower(program, feed=feed, fetch_list=fetch_list,
                     scope=scope).compile()


_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")


def collectives(hlo_text):
    """Collective instructions in an optimized HLO module, by kind
    (`-start` counts the async form once; `-done` is not counted)."""
    import re

    out = {}
    for kind in _COLLECTIVES:
        n = len(re.findall(rf"= [^=\n]*\b{kind}(?:-start)?\(", hlo_text))
        if n:
            out[kind] = n
    return out


def live_bytes(devices):
    """Bytes in use on each device, as the device reports them; None
    where the backend keeps no such statistic (the CPU)."""
    out = []
    for d in devices:
        stats = d.memory_stats()
        out.append(None if not stats else int(stats.get("bytes_in_use", 0)))
    return out


def run_steps(main, startup, loss, feed, steps=2, probe=()):
    """Startup + `steps` training steps of one program on one fixed
    batch: the losses, the compiled step's HLO facts, the per-device live
    bytes while the state is held, and a digest of the freshly
    initialised `probe` parameters (same seed, same weights, whatever the
    graph variant or layout)."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.framework.scope import Scope

    gc.collect()
    devices = jax.devices()
    before = live_bytes(devices)
    scope, exe = Scope(), fluid.Executor()
    exe.run(startup, scope=scope)
    init = [float(np.asarray(scope.find_var(n), np.float64).sum())
            for n in probe]
    hlo = compiled_step(exe, main, feed, [loss], scope).as_text()
    losses = [
        _scalar(exe.run(main, feed=feed, fetch_list=[loss], scope=scope)[0])
        for _ in range(steps)
    ]
    after = live_bytes(devices)
    exe.close()
    return {
        "losses": losses,
        "init": init,
        "custom_calls": custom_calls(hlo),
        "collectives": collectives(hlo),
        "live_bytes": [
            None if a is None else a - b for a, b in zip(after, before)
        ],
    }


def _scalar(x):
    return float(np.asarray(x).reshape(-1)[0])


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------


def phase_train(sz, kernels, shared):
    """BERT MLM training: startup + train_steps steps on 4 fixed batches
    at a constant learning rate."""
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.framework.scope import Scope

    cfg = bert_config(sz)
    b, s = sz.bert_batch, sz.bert_seq
    n_pred = max(1, int(0.15 * b * s))
    main, startup, loss = build_bert_train(cfg, b, s, n_pred, _adam_amp)
    scope, exe = Scope(), fluid.Executor()
    exe.run(startup, scope=scope)

    rng = np.random.RandomState(SEED)
    batches = [
        {k: jnp.asarray(v) for k, v in
         bert_batch(rng, cfg, b, s, n_pred)[0].items()}
        for _ in range(4)
    ]
    compiled = compiled_step(exe, main, batches[0], [loss], scope)
    shared["train_custom_calls"] = custom_calls(compiled.as_text())
    shared["train_layers"] = cfg.num_layers

    losses, step_s = [], []
    for i in range(sz.train_steps):
        t0 = time.perf_counter()
        (lv,) = exe.run(main, feed=batches[i % 4], fetch_list=[loss],
                        scope=scope)
        step_s.append(time.perf_counter() - t0)
        losses.append(_scalar(lv))
    _check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    _check(losses[-1] < losses[0],
           f"loss did not fall: {losses[0]} -> {losses[-1]}")
    exe.close()
    return {
        "model": f"bert-{sz.bert}", "batch": b, "seq": s, "amp": "bf16",
        "hidden": cfg.hidden_size, "layers": cfg.num_layers,
        "vocab": cfg.vocab_size, "masked_positions": n_pred,
        "losses": [round(v, 4) for v in losses],
        # host clock around a blocking run; the first step carries the
        # (cache-hit) executable load
        "median_step_s": round(float(np.median(step_s[1:])), 4),
        "custom_calls": shared["train_custom_calls"],
    }


def phase_kernels(sz, kernels, shared):
    """Did the Pallas kernels really run, and do they compute what the
    composed ops compute? (1) the train step's own optimized HLO holds
    one packed-attention forward + backward and two fused residual-LN
    forwards + backwards per layer; (2) with dropout 0 and the same
    weights, two steps with the kernels give the loss of two steps with
    `BertConfig(use_fused_attention=False, use_fused_residual=False)`."""
    _check("train_custom_calls" in shared, "the train phase did not finish")
    want = 6 * shared["train_layers"] if kernels else 0
    got = shared["train_custom_calls"]
    _check(got == want,
           f"train step HLO holds {got} tpu_custom_call, expected {want}")

    b, s = sz.bert_batch, sz.bert_seq
    n_pred = max(1, int(0.15 * b * s))
    out = {}
    for name, fused in (("kernels", True), ("composed", False)):
        cfg = bert_config(sz, dropout=0.0, kernels=fused)
        main, startup, loss = build_bert_train(cfg, b, s, n_pred, _adam_amp)
        feed, _ = bert_batch(np.random.RandomState(SEED), cfg, b, s, n_pred)
        out[name] = run_steps(main, startup, loss, feed, probe=BERT_PROBE)
    k, c = out["kernels"], out["composed"]
    _check(k["init"] == c["init"], f"initial weights differ: {k} vs {c}")
    _check(k["custom_calls"] == want and c["custom_calls"] == 0,
           f"custom calls: kernels {k['custom_calls']} (want {want}), "
           f"composed {c['custom_calls']} (want 0)")
    rel = [_rel(a, r) for a, r in zip(k["losses"], c["losses"])]
    _check(all(np.isfinite(k["losses"] + c["losses"])), f"non-finite: {out}")
    _check(max(rel) <= BF16_RTOL,
           f"kernels {k['losses']} vs composed {c['losses']}: rel {rel} "
           f"> {BF16_RTOL}")
    return {
        "train_step_custom_calls": got, "expected": want,
        "per_layer": "attention fwd+bwd, 2x residual-LN fwd+bwd",
        "loss_kernels": k["losses"], "loss_composed": c["losses"],
        "rel_diff": [float(f"{r:.3g}") for r in rel],
        "rtol_bf16": BF16_RTOL,
    }


def phase_longctx(sz, kernels, shared):
    """GPT causal training at long context: two steps through the
    KV-tiled flash kernels (forward, dkv, dq: 3 per layer)."""
    import paddle_tpu as fluid
    from paddle_tpu.models import gpt_lm_loss
    from paddle_tpu.optimizer import Adam

    b, s = sz.long_batch, sz.long_seq
    cfg = gpt_config(sz, max_position=s)
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = SEED
    with fluid.program_guard(main, startup):
        ids = fluid.data("ids", [b, s], "int64")
        loss = gpt_lm_loss(ids, cfg)
        _amp(Adam(LEARNING_RATE)).minimize(loss, startup)
    rng = np.random.RandomState(SEED)
    feed = {"ids": np.minimum(rng.zipf(1.3, (b, s)),
                              cfg.vocab_size - 1).astype("int32")}
    leg = run_steps(main, startup, loss, feed)
    calls, losses = leg["custom_calls"], leg["losses"]
    from paddle_tpu.kernels.flash_attention import MAX_SEQ

    tiled = kernels and s > MAX_SEQ
    want = 3 * cfg.num_layers if tiled else (
        2 * cfg.num_layers if kernels else 0
    )
    _check(calls == want,
           f"long-context step HLO holds {calls} tpu_custom_call, "
           f"expected {want}")
    _check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    return {
        "model": f"gpt-{sz.gpt}", "batch": b, "seq": s, "amp": "bf16",
        "hidden": cfg.hidden_size, "layers": cfg.num_layers,
        "vocab": cfg.vocab_size, "losses": [round(v, 4) for v in losses],
        "custom_calls": calls, "expected": want,
    }


def build_bert_classifier(cfg, s):
    """bench_serving.py's BERT classifier: encoder + [CLS] head, built as
    a training program and frozen to its inference slice."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.models.bert import bert_encoder

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = SEED
    with fluid.program_guard(main, startup):
        ids = fluid.data("ids", [-1, s], "int64")
        types = fluid.data("types", [-1, s], "int64")
        mask = fluid.data("mask", [-1, s], "float32")
        seq = bert_encoder(ids, types, mask, cfg, is_test=False)
        pooled = layers.slice(seq, [1], [0], [1])
        logits = layers.fc(pooled, 4)
        prob = layers.softmax(logits)
        lab = fluid.data("lab", [-1, 1], "int64")
        loss = layers.mean(layers.softmax_with_cross_entropy(logits, lab))
        fluid.optimizer.Adam(1e-3).minimize(loss, startup)
    return main, startup, prob


def phase_serve(sz, kernels, shared):
    """BERT classifier frozen behind Server with buckets, warmed; 16
    requests from 4 client threads; rows equal a direct Executor.run of
    the frozen program on the same inputs."""
    import paddle_tpu as fluid
    from paddle_tpu.framework.scope import Scope, scope_guard
    from paddle_tpu.serving import EndpointConfig, Server, freeze_program

    cfg = bert_config(sz)
    s = sz.serve_seq
    main, startup, prob = build_bert_classifier(cfg, s)
    scope, exe = Scope(), fluid.Executor()
    with scope_guard(scope):
        exe.run(startup, scope=scope)
    frozen = freeze_program(main, [prob], feed_names=("ids", "types", "mask"))
    server = Server()
    server.add_endpoint(
        "bert_classify", None,
        EndpointConfig(buckets=sz.serve_buckets, max_wait_ms=4.0),
        frozen=frozen, executor=exe, scope=scope,
    )
    warm_runs = server.warmup()

    n_clients, per_client = 4, 4
    rng = np.random.RandomState(SEED)
    requests = [
        {"ids": rng.randint(0, cfg.vocab_size, s).astype(np.int64),
         "types": np.zeros(s, np.int64),
         "mask": np.ones(s, np.float32)}
        for _ in range(n_clients * per_client)
    ]
    answers = [None] * len(requests)
    errors = []

    def client(c):
        for i in range(c * per_client, (c + 1) * per_client):
            try:
                answers[i] = server.submit(
                    "bert_classify", requests[i]
                ).result(timeout=300)[0]
            except Exception as exc:  # recorded; the phase fails below
                errors.append(f"request {i}: {type(exc).__name__}: {exc}")

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    _check(not any(t.is_alive() for t in threads), "a client thread hung")
    drained = server.close(timeout=60)
    _check(not errors, f"requests failed: {errors}")
    _check(all(a is not None for a in answers), "a request went unanswered")
    served = np.stack(answers)

    top = max(sz.serve_buckets)
    direct = []
    with scope_guard(scope):
        for i in range(0, len(requests), top):
            chunk = requests[i:i + top]
            feed = {k: np.stack([r[k] for r in chunk]) for k in chunk[0]}
            (rows,) = exe.run(frozen.program, feed=feed,
                              fetch_list=list(frozen.fetch_names),
                              scope=scope)
            direct.append(rows)
    direct = np.concatenate(direct)
    _check(served.shape == direct.shape and len(served) == len(requests),
           f"shapes {served.shape} vs {direct.shape}")
    _check(np.all(np.isfinite(served)), "served rows not finite")
    err = float(np.max(np.abs(served - direct)))
    _check(err <= SERVE_ATOL,
           f"served rows differ from the direct run by {err}")
    calls = custom_calls(compiled_step(
        exe, frozen.program,
        {k: np.stack([r[k] for r in requests[:top]]) for k in requests[0]},
        list(frozen.fetch_names), scope,
    ).as_text())
    # frozen graph: packed attention forward + 2 residual-LN forwards a layer
    want = 3 * cfg.num_layers if kernels else 0
    _check(calls == want,
           f"frozen bucket-{top} HLO holds {calls} tpu_custom_call, "
           f"expected {want}")
    exe.close()
    return {
        "model": f"bert-{sz.bert} classifier", "seq": s,
        "buckets": list(sz.serve_buckets), "warmup_runs": warm_runs,
        "requests": len(requests), "clients": n_clients,
        "answered": len(answers), "drained": bool(drained),
        "max_abs_diff_vs_direct": err, "atol": SERVE_ATOL,
        "custom_calls": calls, "expected": want,
    }


def handoff_check(gen, prompts, new):
    """The ids `gen.generate` returns (the choice made and handed from
    step to step on the device, read once) against the argmax chain of
    the logits the SAME two executables fetch when the host feeds each
    token, as the benchmark's probes drive them: every row, `new`
    tokens. Rows with a step whose two largest logits lie within
    GEN_TIE_EPS are counted and left out; any other row must be equal."""
    from paddle_tpu.framework.scope import scope_guard

    exe, scope = gen.executor, gen.scope

    def last_logits(program, feed, fetch):
        got = exe.run(program, feed=feed, fetch_list=fetch, scope=scope)
        return np.asarray(got[0])[:, -1, :]

    gen.reset()
    with scope_guard(scope):
        logits = np.concatenate([
            last_logits(gen.prefill_prog, feed, gen._prefill_fetch)
            for feed in gen.prefill_feeds(prompts)
        ])
        chain, gaps = [], []
        for t in range(new):
            top2 = np.partition(logits, -2, axis=-1)[:, -2:]
            gaps.append(top2[:, 1] - top2[:, 0])
            chain.append(np.argmax(logits, axis=-1))
            if t + 1 < new:
                logits = last_logits(
                    gen.decode_prog,
                    {"token_ids": chain[-1][:, None].astype(np.int64),
                     "pos_ids": np.array([[gen.context_len + t]], np.int64)},
                    gen._decode_fetch,
                )
    chain, gaps = np.stack(chain, axis=1), np.stack(gaps, axis=1)
    got = gen.generate(prompts, new)
    tied = (gaps < GEN_TIE_EPS).any(axis=1)
    differ = (got != chain).any(axis=1)
    _check(not (differ & ~tied).any(),
           f"rows {np.nonzero(differ & ~tied)[0].tolist()}: generate's ids "
           "differ from the host argmax chain of the same executables' "
           f"logits with no two logits within {GEN_TIE_EPS}")
    return {
        "rows": int(got.shape[0]), "tokens": int(new),
        "rows_equal": int((~differ).sum()),
        "rows_left_out_near_tie": int(tied.sum()),
        "near_tie_rows_that_differ": int((differ & tied).sum()),
        "tie_eps": GEN_TIE_EPS, "smallest_top2_gap": float(gaps.min()),
        "distinct_rows": len({tuple(r) for r in got.tolist()}),
    }


def cell_generator(sz, config):
    """`serving.GPTGenerator` handed the decoder of a benchmark
    configuration by its own builder, at the published widths
    (bfloat16: 4.32B parameters for trinity_large_ep8, 4.57B for
    dots_vlm1_ep16, 2.93B for qwen3_next_ep8, 2.82B for
    minicpm_sala_pp4, 0.81B for evabyte_pp8) or its tiny cut."""
    import importlib

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benchmark", "configs", f"{config}.json")
    with open(path) as f:
        cfg_json = json.load(f)
    builder = importlib.import_module(
        f"benchmark.builders.{cfg_json['builder']}")
    traffic = {"batch": sz.moe_batch, "prompt_len": sz.moe_context,
               "new_tokens": sz.moe_max_len - sz.moe_context}
    return builder.build_generate(cfg_json, traffic, sz.moe == "tiny",
                                  SEED).generator


def phase_generate(sz, kernels, shared):
    """GPTGenerator behind Server: prefill + per-token KV-cache decode,
    against generate_full_recompute; then the token hand-off of the
    decoders against the host's argmax chain (`handoff_check`): the
    afmoe decoder, the latent-attention decoder (prefill expanded,
    cached steps absorbed), the linear-attention decoder (prefill by
    the chunked delta rule, cached steps through `gdn_state_update` on
    the float32 states in place) and the Lightning decoder (the
    state-space scan with a constant decay, cached steps through
    `lightning_state_update`) and the EVA decoder (cached steps through
    `eva_decode_attention` over the window's ring; no summary is visible
    below its first window of 2,048) at the whole decode batch, one after
    the other (each holds 1.6 to 9 GB of weights)."""
    out = _gpt_generate(sz)
    for name, config in (("afmoe", "trinity_large_ep8"),
                         ("dots_vlm", "dots_vlm1_ep16"),
                         ("qwen3_next", "qwen3_next_ep8"),
                         ("minicpm_sala", "minicpm_sala_pp4"),
                         ("evabyte", "evabyte_pp8")):
        gc.collect()    # the generator before, ahead of 9 GB of weights
        rng = np.random.RandomState(SEED + 1)
        gen = cell_generator(sz, config)
        prompts = rng.randint(0, gen.cfg.vocab_size,
                              (sz.moe_batch, sz.moe_context)).astype(np.int64)
        out[f"{name}_handoff"] = handoff_check(gen, prompts, sz.moe_new)
        del gen
    return out


def _gpt_generate(sz):
    import paddle_tpu as fluid
    from paddle_tpu.models.gpt import gpt_logits
    from paddle_tpu.serving import EndpointConfig, GPTGenerator, Server
    from paddle_tpu.serving.generate import GPTGenerateRunner

    ctx_len, new = sz.gen_context, sz.gen_new
    cfg = gpt_config(sz)
    gen = GPTGenerator(cfg, batch=1, context_len=ctx_len,
                       max_len=ctx_len + new)
    gen.init_params(seed=SEED)
    server = Server()
    server.add_endpoint(
        "gpt_generate", GPTGenerateRunner(gen, max_new_tokens=new),
        EndpointConfig(buckets=(1,), max_wait_ms=1.0),
    )
    server.warmup()
    rng = np.random.RandomState(SEED)
    context = rng.randint(0, cfg.vocab_size, (1, ctx_len)).astype(np.int64)
    (kv_tokens,) = server.submit(
        "gpt_generate", {"context_ids": context[0]}
    ).result(timeout=600)
    server.close(timeout=60)
    kv_tokens = np.asarray(kv_tokens).reshape(1, new)
    full_tokens = gen.generate_full_recompute(context, new)
    _check(kv_tokens.shape == full_tokens.shape == (1, new),
           f"token shapes {kv_tokens.shape} vs {full_tokens.shape}")
    _check(np.all((kv_tokens >= 0) & (kv_tokens < cfg.vocab_size)),
           "token id out of range")
    out = {
        "model": f"gpt-{sz.gpt}", "hidden": cfg.hidden_size,
        "layers": cfg.num_layers, "vocab": cfg.vocab_size, "batch": 1,
        "context": ctx_len, "new_tokens": new,
        "kv_tokens": kv_tokens[0].tolist(),
    }
    differ = np.nonzero(kv_tokens[0] != full_tokens[0])[0]
    if differ.size == 0:
        out["parity"] = "tokens equal"
        return out
    # Greedy argmax amplifies a near-tie: from the first differing step on
    # the two runs decode different prefixes. Compare the LOGITS of that
    # step, both computed on the (still common) prefix before it.
    t = int(differ[0])
    total = ctx_len + new
    prefix = np.zeros((1, total), np.int64)
    prefix[:, :ctx_len] = context
    prefix[:, ctx_len:ctx_len + t] = kv_tokens[:, :t]
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup):
        full_ids = fluid.data("full_ids", [1, total], "int64")
        logits = gpt_logits(full_ids, cfg, is_test=True)
    prog._is_inference = True
    (ref,) = gen.executor.run(prog, feed={"full_ids": prefix},
                              fetch_list=[logits], scope=gen.scope)
    ref = np.asarray(ref)[0, ctx_len + t - 1]
    a, b = int(kv_tokens[0, t]), int(full_tokens[0, t])
    gap = abs(float(ref[b] - ref[a]))
    _check(gap <= GEN_LOGIT_ATOL,
           f"step {t}: KV-cache chose {a}, recompute {b}; the reference "
           f"logits separate them by {gap} > {GEN_LOGIT_ATOL}")
    out["parity"] = (
        f"tokens equal up to step {t}; there KV-cache chose {a} and "
        f"recompute {b}, whose reference logits differ by {gap:.3g} "
        f"(<= {GEN_LOGIT_ATOL})"
    )
    return out


ONE_CHIP_PHASES = (
    ("train", phase_train),
    ("kernels", phase_kernels),
    ("longctx", phase_longctx),
    ("serve", phase_serve),
    ("generate", phase_generate),
)


# ---------------------------------------------------------------------------
# four-chip legs (--chips 4)
# ---------------------------------------------------------------------------

def _fleet_minimize(**strategy_fields):
    """`minimize` through fleet.distributed_optimizer: dp over every
    device, shard_map mode, explicit collectives."""
    def minimize(loss, startup):
        from paddle_tpu.fleet import collective as fc
        from paddle_tpu.fleet.role_maker import UserDefinedRoleMaker
        from paddle_tpu.optimizer import Adam

        fleet = fc.Fleet()
        fleet.init(UserDefinedRoleMaker())
        strategy = fc.DistributedStrategy()
        for k, v in strategy_fields.items():
            setattr(strategy, k, v)
        fleet.distributed_optimizer(
            _amp(Adam(LEARNING_RATE)), strategy
        ).minimize(loss, startup)

    return minimize


def phase_mesh_bert(sz, kernels, shared):
    """The BERT step on one device, then the same global batch under
    three mesh layouts; every loss within BF16_RTOL of the one-device
    loss, the expected collectives in each layout's HLO, and its state
    spread over the devices."""
    import jax

    from paddle_tpu.models.bert import bert_tp_shardings
    from paddle_tpu.parallel import make_mesh, shard_program

    _check(len(jax.devices()) == 4,
           f"need exactly 4 devices, have {len(jax.devices())}")
    b, s = sz.bert_batch, sz.bert_seq
    n_pred = max(4, int(0.15 * b * s) // 4 * 4)
    cfg = bert_config(sz, dropout=0.0)
    feed, local_pos = bert_batch(
        np.random.RandomState(SEED), cfg, b, s, n_pred, shards=4
    )
    dp_feed = dict(feed, mask_pos=local_pos)
    per_layer = 6 * cfg.num_layers if kernels else 0

    legs = {}
    main, startup, loss = build_bert_train(cfg, b, s, n_pred, _adam_amp)
    legs["one_device"] = run_steps(
        main, startup, loss, feed, probe=BERT_PROBE
    )

    # (a) dp=4, shard_map, bucketed c_allreduce of the gradients: the
    # program is per shard (b/4 rows, local mask positions)
    main, startup, loss = build_bert_train(
        cfg, b // 4, s, n_pred // 4, _fleet_minimize()
    )
    legs["dp4_allreduce"] = run_steps(
        main, startup, loss, dp_feed, probe=BERT_PROBE
    )

    # (b) dp=2 x mp=2 GSPMD: Megatron annotations, the partitioner
    # inserts the collectives; Pallas is refused under GSPMD by design
    # (ops/fused.py::_attn_ctx), so this layout takes the jnp path
    main, startup, loss = build_bert_train(cfg, b, s, n_pred, _adam_amp)
    shardings = bert_tp_shardings(cfg, axis="mp")
    for name in feed:
        shardings[name] = ("dp",)
    shard_program(main, make_mesh({"dp": 2, "mp": 2}), shardings,
                  mode="gspmd")
    legs["dp2_mp2_gspmd"] = run_steps(
        main, startup, loss, feed, probe=BERT_PROBE
    )

    # (c) dp=4 with the ZeRO weight-update sharding: reduce-scatter,
    # shard-local Adam, all-gather
    main, startup, loss = build_bert_train(
        cfg, b // 4, s, n_pred // 4,
        _fleet_minimize(shard_weight_update=True),
    )
    legs["dp4_zero"] = run_steps(
        main, startup, loss, dp_feed, probe=BERT_PROBE
    )

    ref = legs["one_device"]
    _check(all(np.isfinite(ref["losses"])), f"one-device loss: {ref}")
    _check(ref["custom_calls"] == per_layer,
           f"one-device custom calls {ref['custom_calls']} != {per_layer}")
    # each entry: any one of these kinds must be in the layout's HLO. The
    # v5e compiler was seen to turn ZeRO's bucketed reduce-scatters into
    # all-reduces (then slices), so the gradient reduction may show as
    # either; the parameter all-gathers stay.
    want = {
        "dp4_allreduce": (per_layer, [("all-reduce",)]),
        "dp2_mp2_gspmd": (0, [("all-reduce",)]),
        "dp4_zero": (per_layer, [("reduce-scatter", "all-reduce"),
                                 ("all-gather",)]),
    }
    for name, (calls, kinds) in want.items():
        leg = legs[name]
        rel = [_rel(a, r) for a, r in zip(leg["losses"], ref["losses"])]
        leg["rel_diff_vs_one_device"] = [float(f"{r:.3g}") for r in rel]
        _check(leg["init"] == ref["init"],
               f"{name}: initial weights differ from the one-device build")
        _check(all(np.isfinite(leg["losses"])), f"{name}: {leg['losses']}")
        _check(max(rel) <= BF16_RTOL,
               f"{name} losses {leg['losses']} vs one device "
               f"{ref['losses']}: rel {rel} > {BF16_RTOL}")
        _check(leg["custom_calls"] == calls,
               f"{name}: {leg['custom_calls']} tpu_custom_call, "
               f"expected {calls}")
        for any_of in kinds:
            _check(any(leg["collectives"].get(k, 0) > 0 for k in any_of),
                   f"{name}: none of {any_of} in its HLO: "
                   f"{leg['collectives']}")
        live = leg["live_bytes"]
        if live[0] is not None:
            _check(min(live) > 0.25 * max(live),
                   f"{name}: state is not spread over the devices: {live}")
    if ref["live_bytes"][0] is not None:
        one = ref["live_bytes"][0]
        for name in ("dp2_mp2_gspmd", "dp4_zero"):
            _check(max(legs[name]["live_bytes"]) < one,
                   f"{name} holds as much per device as one device "
                   f"holds alone: {legs[name]['live_bytes']} vs {one}")
    return {
        "model": f"bert-{sz.bert}", "global_batch": b, "seq": s,
        "masked_positions": n_pred, "rtol_bf16": BF16_RTOL, "legs": legs,
    }


def _ring_programs(sz, n):
    """(sp=n ring program, one-device reference program); each fetches
    the attention output and d(sum(out * w))/d(q, k, v)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.parallel import make_mesh, shard_program

    B, H, S, D = sz.ring_batch, sz.ring_heads, sz.ring_seq, sz.ring_head_dim

    def build(s_decl, attend):
        main, startup = fluid.Program(), fluid.Program()
        main.random_seed = startup.random_seed = SEED
        with fluid.program_guard(main, startup):
            q, k, v, w = (
                fluid.data(nm, [B, H, s_decl, D], "float32")
                for nm in ("q", "k", "v", "w")
            )
            for x in (q, k, v):
                x.stop_gradient = False
            out = attend(q, k, v)
            # under shard_map this is the shard's own sum; every shard
            # seeds its gradient with 1, so the gradients are those of the
            # global sum (the ring backward carries dk/dv between shards)
            total = layers.reduce_sum(out * w)
            grads = fluid.gradients(total, [q, k, v])
        return main, startup, [out] + grads

    ring = build(
        S // n,
        lambda q, k, v: layers.ring_attention(q, k, v, axis_name="sp",
                                              causal=True),
    )
    seq_sharded = (None, None, "sp")
    shard_program(
        ring[0], make_mesh({"sp": n}),
        {name: seq_sharded for name in
         ("q", "k", "v", "w", *(f.name for f in ring[2]))},
    )

    def tiled(q, k, v):
        def pack(x):
            return layers.reshape(layers.transpose(x, [0, 2, 1, 3]),
                                  [B, S, H * D])

        qkv = layers.concat([pack(q), pack(k), pack(v)], axis=2)
        o = layers.fused_qkv_attention(qkv, H, causal=True)
        return layers.transpose(layers.reshape(o, [B, S, H, D]),
                                [0, 2, 1, 3])

    return ring, build(S, tiled)


def phase_ring(sz, kernels, shared):
    """Ring attention over sp=4 (kernels/ring_block.py, compiled) against
    the one-device KV-tiled kernel on the same q, k, v: output and
    gradients."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.framework.scope import Scope

    _check(len(jax.devices()) == 4,
           f"need exactly 4 devices, have {len(jax.devices())}")
    ring, ref = _ring_programs(sz, 4)
    B, H, S, D = sz.ring_batch, sz.ring_heads, sz.ring_seq, sz.ring_head_dim
    rng = np.random.RandomState(SEED)
    feed = {nm: rng.randn(B, H, S, D).astype("float32")
            for nm in ("q", "k", "v", "w")}
    results, facts = {}, {}
    for name, (main, startup, fetches) in (("ring_sp4", ring),
                                           ("one_device_tiled", ref)):
        scope, exe = Scope(), fluid.Executor()
        exe.run(startup, scope=scope)
        hlo = compiled_step(exe, main, feed, fetches, scope).as_text()
        facts[name] = {"custom_calls": custom_calls(hlo),
                       "collectives": collectives(hlo)}
        results[name] = [
            np.asarray(x) for x in
            exe.run(main, feed=feed, fetch_list=fetches, scope=scope)
        ]
        exe.close()
    errs = {}
    for label, a, r in zip(("out", "dq", "dk", "dv"),
                           results["ring_sp4"], results["one_device_tiled"]):
        _check(a.shape == r.shape == (B, H, S, D),
               f"{label}: shapes {a.shape} vs {r.shape}")
        _check(np.all(np.isfinite(a)), f"{label}: ring result not finite")
        errs[label] = float(np.max(np.abs(a - r)) / np.max(np.abs(r)))
    _check(max(errs.values()) <= F32_ATTN_TOL,
           f"ring vs tiled, max|diff|/max|ref|: {errs} > {F32_ATTN_TOL}")
    _check(facts["ring_sp4"]["collectives"].get("collective-permute", 0) > 0,
           f"no collective-permute in the ring HLO: {facts['ring_sp4']}")
    if kernels:
        # forward, dq and dkv shard kernels / forward, dkv and dq tiles
        _check(facts["ring_sp4"]["custom_calls"] >= 3,
               f"ring kernels not compiled in: {facts['ring_sp4']}")
        _check(facts["one_device_tiled"]["custom_calls"] == 3,
               f"tiled reference: {facts['one_device_tiled']}")
    return {
        "shape_bhsd": [B, H, S, D], "causal": True, "dtype": "float32",
        "max_abs_diff_over_max_abs_ref": errs, "tol": F32_ATTN_TOL, **facts,
    }


def record_placement():
    """Not a check: what ReplicaSet and Executor.load_executable do on a
    host with several devices today (ISSUE 21 asks for the record, and
    for nothing to be built on it here)."""
    import os
    import tempfile

    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.framework.scope import Scope, scope_guard
    from paddle_tpu.serving import ReplicaSet, freeze_program
    from paddle_tpu.serving.router import FrozenRunner

    import jax

    devices = jax.devices()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = SEED
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 128], "float32")
        y = layers.fc(layers.fc(x, 128, act="relu"), 8)
    frozen = freeze_program(main, [y], feed_names=("x",))
    param = main.all_parameters()[0].name
    feed = {"x": np.ones((4, 128), np.float32)}
    runners = []
    for _ in range(len(devices)):
        scope, exe = Scope(), fluid.Executor()
        with scope_guard(scope):
            exe.run(startup, scope=scope)
        runners.append(FrozenRunner(frozen, executor=exe, scope=scope))
    replicas = ReplicaSet(runners)
    replicas.warmup_run(feed)
    note = {
        "record": "placement", "devices": len(devices),
        "replica_param_devices": [
            sorted(d.id for d in r.scope.find_var(param).devices())
            for r in runners
        ],
    }
    exe, scope = runners[0].executor, runners[0].scope
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "step.aotexe")
        try:
            exe.serialize_executable(path, frozen.program, feed=feed,
                                     fetch_list=[y.name], scope=scope)
            fresh = fluid.Executor()
            fresh.load_executable(path, frozen.program, feed=feed,
                                  fetch_list=[y.name], scope=scope)
            (out,) = fresh.run(frozen.program, feed=feed,
                               fetch_list=[y.name], scope=scope,
                               return_numpy=False)
            note["loaded_executable_output_devices"] = sorted(
                d.id for d in out.devices()
            )
        except Exception as exc:  # a record, not a check
            note["load_executable_error"] = (
                f"{type(exc).__name__}: {exc}"[:500]
            )
    print(json.dumps(note), flush=True)


FOUR_CHIP_PHASES = (
    ("mesh_bert", phase_mesh_bert),
    ("ring_attention", phase_ring),
)


# ---------------------------------------------------------------------------


def device_record():
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases (default); 4: the "
                         "four-chip legs and nothing else")
    args = ap.parse_args(argv)

    device = device_record()
    if device["platform"] != "tpu" or device["count"] < args.chips:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), JAX reports "
              f"{device}", file=sys.stderr)
        print(json.dumps({"ok": False, "device": device}))
        return 2

    import jax

    import paddle_tpu as fluid
    from paddle_tpu.core import compile_cache

    fluid.TPUPlace(0).jax_device()  # typed error if the place is not a TPU
    cache_dir = compile_cache.enable()
    meter = CompileMeter()
    print(json.dumps({"compile_cache": cache_dir, "chips": args.chips,
                      "jax": jax.__version__}), flush=True)

    phases = ONE_CHIP_PHASES if args.chips == 1 else FOUR_CHIP_PHASES
    shared = {}
    ok = True
    t0 = time.perf_counter()
    for name, fn in phases:
        ok = run_phase(name, fn, meter, REAL, True, shared) and ok
    if args.chips == 4:
        record_placement()
    print(json.dumps({
        "total_wall_s": round(time.perf_counter() - t0, 1),
        **meter.since(),
    }), flush=True)
    print(json.dumps({"ok": ok, "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
