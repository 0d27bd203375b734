"""Serving load generator: checkpoint -> frozen graph -> QPS.

Drives the paddle_tpu.serving router with traffic mixes and prints ONE
JSON line per mix:

  * ``bert_classify``  — tiny-BERT sequence classifier, closed-loop
    concurrent clients over buckets (1, 2, 4, 8);
  * ``resnet_classify`` — CIFAR-sized ResNet-18 softmax head, open-loop
    Poisson arrivals (tests deadline-driven partial batches);
  * ``ctr_rank``       — fused-embedding DeepFM ranker (PR 11);
  * ``gpt_generate``   — KV-cache generation endpoint (prefill + decode);
  * ``overload``       — r15 fault-domain mix: open-loop Poisson at 2x
    the measured sustainable rate, 30% interactive / 70% background with
    per-class deadlines, run twice — the shed-nothing r8 baseline vs
    deadline+priority shedding with the watcher-driven brownout ladder —
    reporting GOODPUT (in-deadline completions/s) and shed/expired rate
    per priority class. Gates goodput(shed) >= 1.3x goodput(baseline) at
    equal-or-better interactive p99.
  * ``failover``       — r15 chaos mix: a 3-replica ``ReplicaSet``
    behind one endpoint under closed-loop load; one replica is KILLED
    mid-run (per-replica ``serving.dispatch.r0`` fault). Gates: every
    admitted request resolves (success or typed error, zero hangs), the
    killed replica's breaker opens, and post-failover QPS stays within
    20% of pre-kill. Run it under
    ``PADDLE_TPU_FAULT_INJECT=serving.dispatch:hang:...`` (ci.sh does)
    to add a wedged-executable dispatch the attempt timeout must bound.
  * ``live_update``    — r18 live-publish mix: a 3-replica
    ``SubscribedRunner`` set serving while a trainer thread publishes
    delta bundles and a ``RolloutController`` canaries them through.
    Every version's weights are version-constant, so each response row
    identifies the version that produced it. Gates: goodput under live
    updates >= 0.9x the no-publish baseline, >= 1 version applied
    fleet-wide, zero torn rows (no batch mixed two versions' weights).

Per mix: QPS, p50/p99 request latency (client-measured), batch-size
histogram from the ``serving.bucket_runs.*`` counters, and the frozen
graph's ``Program.estimate()`` roofline as the per-batch lower bound
(estimate vs measured — the PR-7 cross-check; on CPU the v5e peaks make
the ratio an overhead indicator, not a target).

Two acceptance ratios ride along:

  * ``batched_speedup``  — bucket-8 batch throughput vs 8 sequential
    single-request dispatches on the same executable set (>= 3x CPU CI:
    the arXiv:2301.13062 one-wide-program argument applied to serving);
  * ``kv_decode_speedup`` — KV-cache generation vs full-context recompute
    at context >= 256 (>= 5x: the O(1)-per-token decode path).

``--smoke`` shrinks the run for CI; ``--dump PATH`` writes the
observability snapshot for ``stats_report --require serving.``;
``--mix a,b`` runs a subset
(bert,resnet,ctr,gpt,overload,failover,live_update).
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time

import numpy as np


def _percentiles(lat):
    lat = np.asarray(sorted(lat))
    if not len(lat):
        return {"p50_ms": None, "p99_ms": None}
    return {
        "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
        "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
    }


def _bucket_histogram(endpoint_name):
    from paddle_tpu import observability

    prefix = f"serving.bucket_runs.{endpoint_name}."
    return {
        k[len(prefix):]: v
        for k, v in observability.get_counters().items()
        if k.startswith(prefix)
    }


def _trace_latency_split(endpoint_name):
    """Queue-wait vs dispatch (compute) p50/p99 reconstructed from the
    request traces alone (serving.queue_wait / serving.dispatch spans the
    scheduler records under each request's TraceContext), cross-checked
    against the serving.* histograms: per request, queue_wait + dispatch
    must account for the request latency the endpoint histogram measured
    (mean-level check — the two are recorded by different clocks/sides,
    so the bar is agreement, not equality)."""
    from paddle_tpu import observability

    waits, disps = [], []
    for s in observability.get_spans():
        if (s.get("args") or {}).get("endpoint") != endpoint_name \
                or "trace_id" not in s:
            continue
        if s["name"] == "serving.queue_wait":
            waits.append(s["dur"] / 1e6)
        elif s["name"] == "serving.dispatch":
            disps.append(s["dur"] / 1e6)
    if not waits or not disps:
        return {"trace_spans": 0}
    hist = observability.get_histograms().get(
        f"serving.request_latency.{endpoint_name}"
    )
    consistent = None
    if hist and hist["count"]:
        hist_mean = hist["sum"] / hist["count"]
        trace_mean = (sum(waits) / len(waits)) + (sum(disps) / len(disps))
        # ingest/future-resolution overheads ride on the histogram side
        consistent = bool(
            trace_mean <= hist_mean * 1.25 + 2e-3
            and trace_mean >= hist_mean * 0.25
        )
    return {
        "trace_spans": len(waits) + len(disps),
        "trace_queue_wait_ms": _percentiles(waits),
        "trace_dispatch_ms": _percentiles(disps),
        "trace_vs_hist_consistent": consistent,
    }


def _roofline(frozen, bucket, feed_builder):
    """Program.estimate() at the largest bucket: analytic per-batch
    latency lower bound for the frozen graph."""
    try:
        feed = feed_builder(bucket)
        est = frozen.program.estimate(
            feed_shapes={k: tuple(v.shape) for k, v in feed.items()}
        )
        return {
            "est_batch_flops": float(est.total_flops),
            "est_batch_ms": round(est.total_latency * 1e3, 4),
        }
    except Exception as e:  # estimate failures must not kill the bench
        return {"est_error": str(e)[:120]}


def _closed_loop(server, endpoint, feed_builder, n_clients, duration):
    """N clients submit-wait-repeat; returns (latencies, n_done, wall)."""
    lats, lock = [], threading.Lock()
    stop = time.perf_counter() + duration

    def client(seed):
        rng = np.random.RandomState(seed)
        while time.perf_counter() < stop:
            t0 = time.perf_counter()
            fut = server.submit(endpoint, feed_builder(rng))
            fut.result(timeout=60)
            dt = time.perf_counter() - t0
            with lock:
                lats.append(dt)

    t_start = time.perf_counter()
    threads = [
        threading.Thread(target=client, args=(i,)) for i in range(n_clients)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return lats, len(lats), time.perf_counter() - t_start


def _build_classifier_endpoint(kind, scope, seed=7):
    """Build + 2-step-train + freeze a tiny classifier; returns
    (frozen, sample_feed_builder, exe)."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.framework.scope import scope_guard

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = seed
    with fluid.program_guard(main, startup):
        if kind == "bert":
            from paddle_tpu.models.bert import BertConfig, bert_encoder

            cfg = BertConfig.tiny()
            s = 16
            ids = fluid.data("ids", [-1, s], "int64")
            types = fluid.data("types", [-1, s], "int64")
            mask = fluid.data("mask", [-1, s], "float32")
            seq = bert_encoder(ids, types, mask, cfg, is_test=False)
            # [CLS]-style pooled head: first token's hidden state
            pooled = layers.slice(seq, [1], [0], [1])
            logits = layers.fc(pooled, 4)
            prob = layers.softmax(logits)
            lab = fluid.data("lab", [-1, 1], "int64")
            loss = layers.mean(
                layers.softmax_with_cross_entropy(logits, lab)
            )
            feeds = ("ids", "types", "mask")

            def build(rng_or_b):
                if isinstance(rng_or_b, int):
                    b = rng_or_b
                    return {
                        "ids": np.zeros((b, s), np.int64),
                        "types": np.zeros((b, s), np.int64),
                        "mask": np.ones((b, s), np.float32),
                    }
                rng = rng_or_b
                return {
                    "ids": rng.randint(0, cfg.vocab_size, s).astype(
                        np.int64
                    ),
                    "types": np.zeros(s, np.int64),
                    "mask": np.ones(s, np.float32),
                }
        else:
            from paddle_tpu.models.resnet import resnet

            img = fluid.data("image", [-1, 3, 32, 32], "float32")
            logits = resnet(img, class_num=10, depth=18, is_test=False)
            prob = layers.softmax(logits)
            lab = fluid.data("lab", [-1, 1], "int64")
            loss = layers.mean(
                layers.softmax_with_cross_entropy(logits, lab)
            )
            feeds = ("image",)

            def build(rng_or_b):
                if isinstance(rng_or_b, int):
                    return {
                        "image": np.zeros(
                            (rng_or_b, 3, 32, 32), np.float32
                        ),
                    }
                return {
                    "image": rng_or_b.randn(3, 32, 32).astype(np.float32),
                }
        fluid.optimizer.Adam(1e-3).minimize(loss, startup)

    exe = fluid.Executor()
    with scope_guard(scope):
        exe.run(startup, scope=scope)
    from paddle_tpu.serving import freeze_program

    frozen = freeze_program(main, [prob], feed_names=feeds)
    return frozen, build, exe


def bench_classify_mix(name, kind, buckets, mode, load, duration,
                       results):
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.serving import Server
    from paddle_tpu.serving.router import EndpointConfig

    scope = Scope()
    frozen, build, exe = _build_classifier_endpoint(kind, scope)
    server = Server()
    server.add_endpoint(
        name, None,
        EndpointConfig(buckets=buckets, max_wait_ms=4.0, max_queue=4096),
        frozen=frozen, executor=exe, scope=scope,
    )
    t0 = time.perf_counter()
    server.warmup()
    warmup_s = time.perf_counter() - t0

    if mode == "closed":
        lats, n, wall = _closed_loop(server, name, build, load, duration)
    else:
        lats, n, wall = _poisson_loop(server, name, build, load, duration)
    server.drain(timeout=30)
    entry = {
        "mix": name,
        "mode": mode,
        "load": load,
        "requests": n,
        "qps": round(n / wall, 2) if wall > 0 else None,
        "warmup_s": round(warmup_s, 2),
        "buckets": _bucket_histogram(name),
        **_percentiles(lats),
        **_roofline(frozen, buckets[-1], build),
        **_trace_latency_split(name),
    }
    results[name] = entry
    return frozen, build, exe, scope, entry


def _poisson_loop(server, endpoint, feed_builder, rate_qps, duration):
    """Open-loop Poisson arrivals; latency = submit -> future resolve,
    stamped by a done-callback at RESOLVE time (waiting and then reading
    the wall clock would inflate early requests' latency to ~run
    length)."""
    rng = np.random.RandomState(1234)
    lats, lock = [], threading.Lock()
    futs = []
    t_start = time.perf_counter()
    stop = t_start + duration
    next_t = t_start
    while time.perf_counter() < stop:
        next_t += rng.exponential(1.0 / rate_qps)
        delay = next_t - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        t0 = time.perf_counter()
        fut = server.submit(endpoint, feed_builder(rng))

        def _done(f, t0=t0):
            dt = time.perf_counter() - t0
            with lock:
                lats.append(dt)

        fut.add_done_callback(_done)
        futs.append(fut)
    for f in futs:
        f.result(timeout=60)
    wall = time.perf_counter() - t_start
    return lats, len(futs), wall


def bench_batched_vs_sequential(frozen, build, exe, scope, bucket=8,
                                rounds=3, iters=10):
    """Throughput of ONE bucket-N batch vs N sequential single-request
    dispatches against the same warm executables."""
    from paddle_tpu.framework.scope import scope_guard

    fetch = list(frozen.fetch_names)
    feed_b = build(bucket)
    feed_1 = build(1)
    with scope_guard(scope):
        exe.run(frozen.program, feed=feed_b, fetch_list=fetch, scope=scope)
        exe.run(frozen.program, feed=feed_1, fetch_list=fetch, scope=scope)
        best_b = best_1 = float("inf")
        for _ in range(rounds):
            t0 = time.perf_counter()
            for _ in range(iters):
                exe.run(frozen.program, feed=feed_b, fetch_list=fetch,
                        scope=scope)
            best_b = min(best_b, (time.perf_counter() - t0) / iters)
            t0 = time.perf_counter()
            for _ in range(iters):
                for _ in range(bucket):
                    exe.run(frozen.program, feed=feed_1, fetch_list=fetch,
                            scope=scope)
            best_1 = min(best_1, (time.perf_counter() - t0) / iters)
    qps_batched = bucket / best_b
    qps_seq = bucket / best_1
    return {
        "bucket": bucket,
        "batched_qps": round(qps_batched, 1),
        "sequential_qps": round(qps_seq, 1),
        "batched_speedup": round(qps_batched / qps_seq, 2),
    }


def bench_ctr_rank(smoke, duration, results):
    """Recommendation traffic mix (PR 11): a DeepFM CTR ranker served
    through the continuous-batching router — per-slot sparse lookups fused
    into one ``fused_lookup_table`` per table width by the embedding
    engine, frozen, and dispatched per bucket. Records the FIRST
    served-embedding QPS baseline (no ratio gate yet: the number exists so
    the next round has a denominator)."""
    import paddle_tpu as fluid
    from paddle_tpu.embedding import fuse_lookups
    from paddle_tpu.framework.scope import Scope, scope_guard
    from paddle_tpu.models.deepfm import DeepFMConfig, deepfm
    from paddle_tpu.serving import Server, freeze_program
    from paddle_tpu.serving.router import EndpointConfig

    cfg = DeepFMConfig(
        vocab_size=4096, num_fields=13, embed_dim=16, mlp_sizes=(64, 32),
    )
    scope = Scope()
    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 13
    with fluid.program_guard(main, startup):
        ids = fluid.data("feat_ids", [-1, cfg.num_fields], "int64")
        label = fluid.data("label", [-1, 1], "float32")
        loss, prob = deepfm(ids, label, cfg, per_slot=True)
        fused = fuse_lookups(main)
        fluid.optimizer.Adam(1e-3).minimize(loss, startup)
    assert fused == 2, f"expected 2 fused lookup sites, got {fused}"
    exe = fluid.Executor()
    with scope_guard(scope):
        exe.run(startup, scope=scope)
    frozen = freeze_program(main, [prob], feed_names=("feat_ids",))
    fused_frozen = sum(
        1 for op in frozen.program.global_block.ops
        if op.type == "fused_lookup_table"
    )

    server = Server()
    server.add_endpoint(
        "ctr_rank", None,
        EndpointConfig(buckets=(1, 2, 4, 8), max_wait_ms=4.0,
                       max_queue=4096),
        frozen=frozen, executor=exe, scope=scope,
    )
    server.warmup()

    def build(rng_or_b):
        if isinstance(rng_or_b, int):
            return {
                "feat_ids": np.zeros(
                    (rng_or_b, cfg.num_fields), np.int64
                ),
            }
        # power-law ids: the heavy-tailed CTR id distribution
        return {
            "feat_ids": (
                cfg.vocab_size * rng_or_b.power(0.35, cfg.num_fields)
            ).astype(np.int64),
        }

    lats, n, wall = _closed_loop(server, "ctr_rank", build, 8, duration)
    server.drain(timeout=30)
    entry = {
        "mix": "ctr_rank",
        "mode": "closed",
        "load": 8,
        "requests": n,
        "qps": round(n / wall, 2) if wall > 0 else None,
        "fused_lookup_sites_frozen": fused_frozen,
        "buckets": _bucket_histogram("ctr_rank"),
        **_percentiles(lats),
        **_roofline(frozen, 8, build),
        "baseline_note": "first served-embedding QPS baseline (r11)",
    }
    results["ctr_rank"] = entry
    return entry


def bench_gpt_generate(smoke, results):
    """KV-cache generation endpoint + the decode-vs-recompute ratio."""
    from paddle_tpu.models.gpt import GPTConfig
    from paddle_tpu.serving import GPTGenerator, Server
    from paddle_tpu.serving.generate import GPTGenerateRunner
    from paddle_tpu.serving.router import EndpointConfig

    # context >= 256 per the acceptance bar; 512 keeps the recompute
    # baseline's O(S) cost well clear of decode dispatch overhead on the
    # CPU CI leg (at 256 the ratio sits right at 5x and contention noise
    # can dip it under)
    context, new_tokens = (512, 32) if not smoke else (512, 24)
    cfg = GPTConfig(
        vocab_size=512, hidden_size=128, num_layers=2, num_heads=4,
        intermediate_size=256, max_position=context + new_tokens,
        use_fused_attention=False,
    )
    gen = GPTGenerator(
        cfg, batch=1, context_len=context, max_len=context + new_tokens
    )
    gen.init_params(seed=11)
    rng = np.random.RandomState(0)
    ctx = rng.randint(0, cfg.vocab_size, (1, context)).astype(np.int64)

    # decode vs full-recompute, best-of-3
    best_kv = best_full = float("inf")
    gen.generate(ctx, new_tokens)
    gen.generate_full_recompute(ctx, new_tokens)
    for _ in range(3):
        t0 = time.perf_counter()
        kv_tokens = gen.generate(ctx, new_tokens)
        best_kv = min(best_kv, time.perf_counter() - t0)
        t0 = time.perf_counter()
        full_tokens = gen.generate_full_recompute(ctx, new_tokens)
        best_full = min(best_full, time.perf_counter() - t0)
    parity = bool(np.array_equal(kv_tokens, full_tokens))

    # the generate endpoint through the router (closed-loop, 2 clients)
    server = Server()
    runner = GPTGenerateRunner(gen, max_new_tokens=new_tokens)
    server.add_endpoint(
        "gpt_generate", runner,
        EndpointConfig(buckets=(1,), max_wait_ms=1.0),
    )
    duration = 2.0 if smoke else 6.0

    def build(rng):
        return {
            "context_ids": rng.randint(0, cfg.vocab_size, context).astype(
                np.int64
            )
        }

    lats, n, wall = _closed_loop(server, "gpt_generate", build, 2,
                                 duration)
    server.drain(timeout=30)
    entry = {
        "mix": "gpt_generate",
        "mode": "closed",
        "load": 2,
        "context": context,
        "new_tokens": new_tokens,
        "requests": n,
        "qps": round(n / wall, 3) if wall > 0 else None,
        "decode_tok_s": round(new_tokens / best_kv, 1),
        "recompute_tok_s": round(new_tokens / best_full, 1),
        "kv_decode_speedup": round(best_full / best_kv, 2),
        "kv_parity": parity,
        **_percentiles(lats),
    }
    results["gpt_generate"] = entry
    return entry


def _overload_leg(server, ep_name, build, rate, duration, deadlines,
                  shed):
    """One open-loop Poisson leg at `rate` with a 30/70 interactive/
    background split; returns per-class outcome counts, latencies, and
    goodput (in-deadline completions/s — the baseline leg submits WITHOUT
    deadlines, so its completions are judged against the same budgets
    client-side: what the r8 router delivers when nobody sheds)."""
    from paddle_tpu.errors import (DeadlineExceededError,
                                   PreconditionNotMetError,
                                   RequestShedError)
    from paddle_tpu.serving import BACKGROUND, INTERACTIVE

    rng = np.random.RandomState(99)
    lock = threading.Lock()
    classes = ("interactive", "background")
    prio = {"interactive": INTERACTIVE, "background": BACKGROUND}
    # per-arrival accounting: a request either raises at SUBMIT time
    # (brownout/queue-full shed -> submit_shed) or becomes exactly one
    # future whose done-callback lands in exactly one outcome bucket
    # ("shed" there = evicted AFTER admission) — no arrival is counted
    # twice
    outcomes = {c: {"ok": 0, "late": 0, "expired": 0, "shed": 0,
                    "error": 0} for c in classes}
    submit_shed = {c: 0 for c in classes}
    lats = {c: [] for c in classes}
    resolved = [0]  # done-callback completions (result() can return
    # before callbacks have run; outcomes are read only once this
    # catches up to the admitted count)
    futs = []
    t_start = time.perf_counter()
    stop = t_start + duration
    next_t = t_start
    while time.perf_counter() < stop:
        next_t += rng.exponential(1.0 / rate)
        delay = next_t - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        cls = "interactive" if rng.random() < 0.3 else "background"
        dl_s = deadlines[cls]
        t0 = time.perf_counter()
        try:
            if shed:
                fut = server.submit(
                    ep_name, build(rng), deadline_ms=dl_s * 1e3,
                    priority=prio[cls],
                )
            else:
                fut = server.submit(ep_name, build(rng))
        except (RequestShedError, PreconditionNotMetError):
            with lock:
                submit_shed[cls] += 1
            continue

        def _done(f, t0=t0, cls=cls, dl=dl_s):
            dt = time.perf_counter() - t0
            with lock:
                try:
                    f.result()
                    lats[cls].append(dt)
                    outcomes[cls]["ok" if dt <= dl else "late"] += 1
                except DeadlineExceededError:
                    outcomes[cls]["expired"] += 1
                except RequestShedError:
                    outcomes[cls]["shed"] += 1
                except Exception:
                    outcomes[cls]["error"] += 1
                resolved[0] += 1

        fut.add_done_callback(_done)
        futs.append(fut)
    window = time.perf_counter() - t_start  # the arrival window
    unresolved = 0
    for f in futs:
        try:
            f.result(timeout=120)
        except Exception:
            if not f.done():
                unresolved += 1
    give_up = time.perf_counter() + 30.0
    while True:
        with lock:
            if resolved[0] >= len(futs) - unresolved:
                break
        if time.perf_counter() > give_up:
            break
        time.sleep(0.002)
    wall = time.perf_counter() - t_start
    in_deadline = sum(outcomes[c]["ok"] for c in classes)
    admitted = len(futs)
    arrived = admitted + sum(submit_shed.values())
    # goodput over the ARRIVAL window for BOTH legs: the baseline leg's
    # backlog keeps draining long after arrivals stop, and dividing by
    # that stretched wall would deflate its goodput by measurement
    # rather than by behavior (its late tail already contributes zero
    # to the numerator)
    return {
        "rate_qps": round(rate, 1),
        "arrived": arrived,
        "admitted": admitted,
        "unresolved": unresolved,
        "wall_s": round(wall, 2),
        "window_s": round(window, 2),
        "goodput_qps": (
            round(in_deadline / window, 2) if window > 0 else 0.0
        ),
        "outcomes": outcomes,
        "submit_shed": submit_shed,
        "shed_rate": {
            c: round(
                (submit_shed[c] + outcomes[c]["shed"])
                / max(1, submit_shed[c] + sum(outcomes[c].values())), 3
            )
            for c in classes
        },
        "interactive": _percentiles(lats["interactive"]),
        "background": _percentiles(lats["background"]),
    }


def bench_overload(smoke, duration, results):
    """The 2x-overload goodput mix: shed-nothing r8 baseline vs the r15
    fault domain (deadlines + priority shedding + brownout ladder), same
    arrival process. Self-gating: goodput >= 1.3x at equal-or-better
    interactive p99, and the expired/shed counters must be alive."""
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.observability.watch import Watcher
    from paddle_tpu.serving import BrownoutController, Server
    from paddle_tpu.serving.router import EndpointConfig

    scope = Scope()
    frozen, build, exe = _build_classifier_endpoint("bert", scope,
                                                    seed=17)

    # sustainable-capacity probe: a short closed-loop burst on a warm
    # endpoint; 2x this arrival rate is overload BY MEASUREMENT
    probe = Server()
    probe.add_endpoint(
        "overload_probe", None,
        EndpointConfig(buckets=(1, 2, 4, 8), max_wait_ms=4.0,
                       max_queue=4096),
        frozen=frozen, executor=exe, scope=scope,
    )
    probe.warmup()
    lats, n, wall = _closed_loop(probe, "overload_probe", build, 8,
                                 1.0 if smoke else 2.0)
    probe.drain(timeout=30)
    qps_cap = n / wall if wall > 0 else 100.0
    p50_cap = float(np.percentile(lats, 50)) if lats else 0.01
    rate = 2.0 * qps_cap
    # interactive budget 10x the uncontended p50 (floor 80ms): tight
    # enough that the baseline's growing queue blows it within a couple
    # hundred ms, loose enough that a shedding router serving near
    # capacity lands inside it rather than on the knife edge
    int_dl = max(10.0 * p50_cap, 0.08)
    deadlines = {"interactive": int_dl, "background": 4.0 * int_dl}

    def leg_server(name, shed):
        s = Server()
        s.add_endpoint(
            name, None,
            EndpointConfig(buckets=(1, 2, 4, 8), max_wait_ms=4.0,
                           max_queue=(256 if shed else 1_000_000)),
            frozen=frozen, executor=exe, scope=scope,
        )
        s.warmup()
        return s

    # leg 1 — the shed-nothing r8 baseline: no deadlines, no classes,
    # unbounded-ish queue; completions judged against the SAME budgets
    base_srv = leg_server("overload_base", shed=False)
    base = _overload_leg(base_srv, "overload_base", build, rate,
                         duration, deadlines, shed=False)
    base_srv.drain(timeout=60)

    # leg 2 — the fault domain: deadlines + priorities + the
    # watcher-driven brownout ladder on the interactive SLO
    shed_srv = leg_server("overload", shed=True)
    watcher = Watcher(latency_metric="serving.request_latency.overload",
                      slo_p99_s=deadlines["interactive"])
    ctl = BrownoutController(
        shed_srv, slo_p99_s=deadlines["interactive"], watcher=watcher,
        escalate_after=2, recover_after=2, interval=0.1,
    )
    ctl.start()
    shed = _overload_leg(shed_srv, "overload", build, rate, duration,
                         deadlines, shed=True)
    brownout_level_end = ctl.level
    ctl.stop()
    shed_srv.drain(timeout=60)

    from paddle_tpu import observability
    c = observability.get_counters()
    goodput_ratio = (
        shed["goodput_qps"] / base["goodput_qps"]
        if base["goodput_qps"] else float("inf")
    )
    p99_base = base["interactive"]["p99_ms"]
    p99_shed = shed["interactive"]["p99_ms"]
    entry = {
        "mix": "overload",
        "mode": "open-2x",
        "capacity_qps": round(qps_cap, 1),
        "deadline_ms": {k: round(v * 1e3, 1) for k, v in
                        deadlines.items()},
        "baseline": base,
        "shedding": shed,
        "goodput_ratio": round(goodput_ratio, 2),
        "interactive_p99_ms": {"baseline": p99_base, "shedding": p99_shed},
        "brownout_level_end": brownout_level_end,
        "brownout_escalations": c.get("serving.brownout_escalations", 0),
        "serving_expired": c.get("serving.expired", 0),
        "serving_shed": c.get("serving.shed", 0),
        "gates": {
            "goodput_ratio>=1.3": goodput_ratio >= 1.3,
            "interactive_p99<=baseline": bool(
                p99_shed is not None and p99_base is not None
                and p99_shed <= p99_base
            ),
            "expired_counter_alive": c.get("serving.expired", 0) > 0,
            "all_resolved": (base["unresolved"] == 0
                             and shed["unresolved"] == 0),
        },
    }
    entry["ok"] = all(entry["gates"].values())
    results["overload"] = entry
    return entry


def bench_failover(smoke, duration, results):
    """The replica-kill chaos mix: 3 FrozenRunner replicas behind one
    endpoint, closed-loop load, replica r0 killed mid-run via its
    per-replica dispatch fault. Self-gating: zero unresolved requests,
    breaker open on r0, post-failover QPS within 20% of pre-kill."""
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.resilience import faults
    from paddle_tpu.serving import ReplicaSet, Server
    from paddle_tpu.serving.router import EndpointConfig, FrozenRunner

    scope = Scope()
    frozen, build, exe = _build_classifier_endpoint("bert", scope,
                                                    seed=23)
    replicas = {
        f"r{i}": FrozenRunner(frozen, executor=exe, scope=scope)
        for i in range(3)
    }
    rs = ReplicaSet(replicas, breaker_threshold=2, cooldown_s=1.0,
                    attempt_timeout=1.0, name="failover")
    server = Server()
    server.add_endpoint(
        "failover", rs,
        EndpointConfig(buckets=(1, 2, 4), max_wait_ms=2.0,
                       max_queue=4096),
    )
    server.warmup()

    w = duration / 3.0
    done_times, lock = [], threading.Lock()
    unresolved = [0]
    typed_errors = [0]
    stop = time.perf_counter() + duration
    t_start = time.perf_counter()
    kill_at = t_start + 1.5 * w

    def client(seed):
        rng = np.random.RandomState(seed)
        while time.perf_counter() < stop:
            fut = server.submit("failover", build(rng))
            try:
                fut.result(timeout=30)
            except Exception:
                with lock:
                    if fut.done():
                        typed_errors[0] += 1  # resolved, typed: fine
                    else:
                        unresolved[0] += 1  # a hang: the gate-breaker
                continue
            with lock:
                done_times.append(time.perf_counter() - t_start)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(6)]
    for t in threads:
        t.start()
    # the mid-run kill: r0's dispatch seam raises from here on — the
    # same seam ci.sh's env-armed serving.dispatch:hang chaos rides
    while time.perf_counter() < kill_at:
        time.sleep(0.01)
    faults.inject("serving.dispatch.r0", "unavailable", prob=1.0, seed=0)
    for t in threads:
        t.join()
    faults.clear("serving.dispatch.r0")
    server.drain(timeout=30)

    pre = [t for t in done_times if 0.5 * w <= t < 1.5 * w]
    post = [t for t in done_times if 2.0 * w <= t < 3.0 * w]
    qps_pre = len(pre) / w
    qps_post = len(post) / w
    from paddle_tpu import observability
    c = observability.get_counters()
    g = observability.get_gauges()
    entry = {
        "mix": "failover",
        "mode": "closed",
        "load": 6,
        "requests": len(done_times),
        "kill_at_s": round(1.5 * w, 2),
        "qps_pre_kill": round(qps_pre, 1),
        "qps_post_failover": round(qps_post, 1),
        "qps_recovery": round(qps_post / qps_pre, 3) if qps_pre else None,
        "unresolved": unresolved[0],
        "typed_errors": typed_errors[0],
        "requeued": c.get("serving.requeued", 0),
        "breaker_opened": c.get("serving.breaker_opened", 0),
        "breaker_state": {
            r: g.get(f"serving.breaker_state.{r}") for r in replicas
        },
        "replica_states": rs.states(),
        "dispatch_hang_faults": c.get(
            "resilience.faults_injected.serving.dispatch", 0
        ),
        "gates": {
            "zero_hangs": unresolved[0] == 0,
            "breaker_open_on_r0": g.get(
                "serving.breaker_state.r0") == 1.0,
            "requeued>0": c.get("serving.requeued", 0) > 0,
            "qps_within_20pct": qps_pre > 0
            and qps_post >= 0.8 * qps_pre,
        },
    }
    entry["ok"] = all(entry["gates"].values())
    results["failover"] = entry
    return entry


def bench_live_update(smoke, duration, results):
    """The r18 live-publish mix: a 3-replica ``SubscribedRunner`` set
    serving while a trainer thread publishes delta bundles and a
    ``RolloutController`` canaries them through the fleet. The weights
    of every version are version-constant (a deterministic pattern of
    the version number), so each response row identifies exactly one
    committed version — a row matching NO version is a torn batch.

    Self-gating: goodput under live updates >= 0.9x the no-publish
    baseline (the apply stalls must cost < 10%), >= 1 version applied
    fleet-wide, zero torn rows."""
    import tempfile

    from paddle_tpu import observability
    from paddle_tpu.fleet.publish import (ModelPublisher, ModelSubscriber,
                                          committed_versions, load_version)
    from paddle_tpu.framework.scope import Scope, scope_guard
    from paddle_tpu.serving import ReplicaSet, Server, freeze_program
    from paddle_tpu.serving.rollout import (RolloutController,
                                            SubscribedRunner)
    from paddle_tpu.serving.router import EndpointConfig, FrozenRunner

    import paddle_tpu as fluid
    from paddle_tpu import layers

    main, startup = fluid.Program(), fluid.Program()
    main.random_seed = startup.random_seed = 11
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [-1, 8])
        prob = layers.softmax(layers.fc(x, 6))
    trainer_scope = Scope()
    exe = fluid.Executor()
    with scope_guard(trainer_scope):
        exe.run(startup, scope=trainer_scope)
    frozen = freeze_program(main, [prob], feed_names=("x",))
    pnames = sorted(
        n for n in trainer_scope.local_var_names()
        if trainer_scope.find_var(n) is not None
        and frozen.program.global_block.var(n) is not None
    )

    def stamp(version):
        # version-constant weights: every persistable becomes a pattern
        # of the version number, so softmax(ones @ W + b) is a distinct,
        # recomputable fingerprint per version
        for i, name in enumerate(pnames):
            cur = np.asarray(trainer_scope.find_var(name))
            size = cur.size
            pat = (np.arange(size, dtype=np.float64) % 5 - 2.0) / 10.0
            arr = ((version % 7 + 1) * 0.1 * (i + 1) * pat).reshape(
                cur.shape
            ).astype(cur.dtype)
            trainer_scope.set_var(name, arr)

    publish_dir = tempfile.mkdtemp(prefix="bench-live-publish-")
    publisher = ModelPublisher(publish_dir, main_program=frozen.program,
                               scope=trainer_scope, full_every=4,
                               max_versions=64)
    stamp(1)
    publisher.publish(step=1)

    feed_one = {"x": np.ones(8, np.float32)}
    outputs, out_lock = [], threading.Lock()

    def serve_leg(live):
        runners = {}
        for i in range(3):
            scope = Scope()
            with scope_guard(scope):
                exe.run(startup, scope=scope)
            sub = ModelSubscriber(publish_dir,
                                  main_program=frozen.program,
                                  scope=scope, name=f"r{i}")
            sub.poll()  # catch-up before serving (the respawn path)
            runners[f"r{i}"] = SubscribedRunner(
                FrozenRunner(frozen, executor=exe, scope=scope), sub
            )
        rs = ReplicaSet(runners, name="live")
        server = Server()
        server.add_endpoint(
            "live", rs,
            EndpointConfig(buckets=(1, 2, 4), max_wait_ms=2.0,
                           max_queue=4096),
        )
        server.warmup()
        ctl = RolloutController(rs, publish_dir, watcher=None,
                                error_counters=(), canary_soak_ticks=1,
                                post_soak_ticks=0, interval=0.05)
        ctl.version = publisher._next - 1  # baseline: already rolled out
        stop_pub = threading.Event()

        def train_and_publish():
            v = publisher._next
            while not stop_pub.wait(duration / 6.0):
                stamp(v)
                publisher.publish(step=v)
                v += 1

        pub_thread = threading.Thread(target=train_and_publish,
                                      daemon=True)
        stop = time.perf_counter() + duration
        done = [0]

        def client(seed):
            while time.perf_counter() < stop:
                fut = server.submit("live", feed_one)
                out = fut.result(timeout=30)
                done[0] += 1
                if live:
                    with out_lock:
                        outputs.append(np.asarray(out[0]))

        if live:
            pub_thread.start()
            ctl.start()
        t0 = time.perf_counter()
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if live:
            stop_pub.set()
            pub_thread.join()
            ctl.stop()
        server.drain(timeout=30)
        return done[0] / wall if wall > 0 else 0.0, ctl

    qps_base, _ = serve_leg(live=False)
    qps_live, ctl = serve_leg(live=True)

    # every served row must reproduce as the output of exactly one
    # committed version's cold fold — a row matching none is a batch
    # that mixed weights from two versions across the apply fence
    expected = []
    ref = FrozenRunner(frozen, executor=exe, scope=Scope())
    for v in committed_versions(publish_dir):
        folded = load_version(publish_dir, v)
        for name, arr in folded.items():
            ref.scope.set_var(name, arr)
        (out,) = ref.run({"x": np.ones((1, 8), np.float32)})
        expected.append((v, np.asarray(out)[0]))
    torn = 0
    for row in outputs:
        errs = [float(np.max(np.abs(row - e))) for _v, e in expected]
        if min(errs) > 1e-4:
            torn += 1

    c = observability.get_counters()
    g = observability.get_gauges()
    versions_applied = int(ctl.version or 0)
    entry = {
        "mix": "live_update",
        "mode": "closed",
        "load": 4,
        "requests": len(outputs),
        "qps_baseline": round(qps_base, 1),
        "qps_live": round(qps_live, 1),
        "goodput_ratio": round(qps_live / qps_base, 3) if qps_base
        else None,
        "versions_published": c.get("publish.versions", 0),
        "versions_served_through": versions_applied,
        "rollouts": c.get("publish.rollouts", 0),
        "applies": c.get("publish.applies", 0),
        "rollbacks": c.get("publish.rollbacks", 0),
        "torn_rows": torn,
        "model_version_gauge": g.get("serving.model_version"),
        "staleness_s": g.get("serving.model_staleness_seconds"),
        "gates": {
            "goodput_dip<10pct": qps_base > 0
            and qps_live >= 0.9 * qps_base,
            "versions_applied>=1": c.get("publish.rollouts", 0) >= 1,
            "zero_torn_rows": torn == 0,
            "zero_rollbacks": c.get("publish.rollbacks", 0) == 0,
        },
    }
    entry["ok"] = all(entry["gates"].values())
    results["live_update"] = entry
    return entry


def _pid_alive(pid):
    import os

    try:
        os.kill(pid, 0)
    except OSError:
        return False
    return True


def bench_fleet(smoke, duration, results, n_workers=4, kill=False):
    """Process-fleet mix: the overload arrival process against a
    ``ProcessReplicaSet`` of real worker processes.

    Legs:

    1. **single** — a 1-worker fleet: capacity probe, then the overload
       arrival process (1.25x the N-worker aggregate rate) with
       deadlines + shedding. The per-process baseline.
    2. **fleet** — N workers, same arrival process. Gate: goodput >=
       2.5x the single-worker leg when >= 4 cores back the workers
       (min(N, cores) scales the bar below that; on a 1-core host the
       ratio is reported, not gated — N processes on one core cannot
       scale by construction).
    3. **chaos** (``kill=True``) — N-1 workers with ``max_replicas=N``,
       the journal-mode Watcher + BrownoutController + FleetAutoscaler
       closing the loop, and a REAL ``SIGKILL`` of one worker mid-run.
       Gates: every admitted request resolves typed (zero hangs), the
       worker death is detected and the corpse respawned, the
       autoscaler scaled out BEFORE anything was shed, the fleet is
       back to full strength afterwards, and ``Server.close()`` leaves
       zero orphan processes.
    """
    import os
    import signal
    import tempfile

    from paddle_tpu import observability
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.observability import timeline
    from paddle_tpu.observability.watch import Watcher
    from paddle_tpu.serving import (BrownoutController, FleetAutoscaler,
                                    ProcessReplicaSet, Server)
    from paddle_tpu.serving.router import EndpointConfig

    scope = Scope()
    frozen, build, exe = _build_classifier_endpoint("bert", scope,
                                                    seed=29)
    model_dir = tempfile.mkdtemp(prefix="bench-fleet-model-")
    frozen.save(model_dir, scope=scope)
    buckets = (1, 2, 4, 8)
    cores = os.cpu_count() or 1
    gates = {}

    def start_fleet(name, n, max_replicas=None, workdir=None, env=None):
        fleet = ProcessReplicaSet(
            model_dir, n_workers=n, max_replicas=max_replicas or n,
            warm_buckets=buckets, attempt_timeout=20.0,
            heartbeat_timeout=10.0, spawn_timeout=300.0, name=name,
            workdir=workdir, env=env,
        )
        srv = Server()
        srv.add_endpoint(
            name, fleet,
            EndpointConfig(buckets=buckets, max_wait_ms=4.0,
                           max_queue=4096),
        )
        srv.warmup()
        return srv, fleet

    # -- leg 1: single-worker baseline ---------------------------------
    srv1, fleet1 = start_fleet("fleet1", 1)
    lats, n_done, wall = _closed_loop(
        srv1, "fleet1", build, 4, 1.0 if smoke else 2.0
    )
    cap1 = n_done / wall if wall > 0 else 50.0
    p50_cap = float(np.percentile(lats, 50)) if lats else 0.01
    int_dl = max(10.0 * p50_cap, 0.1)
    deadlines = {"interactive": int_dl, "background": 4.0 * int_dl}
    # the shared arrival process: overload for ONE worker, ~1.25x
    # saturation for the full fleet — the single leg sheds/expires its
    # way through, the fleet leg serves it, and the goodput ratio is
    # the scaling number
    rate = 1.25 * n_workers * cap1
    single = _overload_leg(srv1, "fleet1", build, rate, duration,
                           deadlines, shed=True)
    pids1 = fleet1.worker_pids()
    srv1.close(timeout=120)

    # -- leg 2: the N-worker fleet, same arrivals ----------------------
    srvN, fleetN = start_fleet(f"fleet{n_workers}", n_workers)
    fleet_leg = _overload_leg(srvN, f"fleet{n_workers}", build, rate,
                              duration, deadlines, shed=True)
    pidsN = fleetN.worker_pids()
    srvN.close(timeout=120)

    ratio = (
        fleet_leg["goodput_qps"] / single["goodput_qps"]
        if single["goodput_qps"] else float("inf")
    )
    effective = min(n_workers, cores)
    if effective >= 4:
        required_ratio = 2.5
    elif effective >= 2:
        required_ratio = 0.625 * effective
    else:
        required_ratio = None  # 1 core: nothing to scale onto
    gates["fleet_goodput_scaling"] = (
        ratio >= required_ratio if required_ratio is not None else True
    )
    gates["legs_all_resolved"] = (
        single["unresolved"] == 0 and fleet_leg["unresolved"] == 0
    )
    gates["scaling_legs_zero_orphans"] = not any(
        _pid_alive(p) for p in pids1 + pidsN
    )

    entry = {
        "mix": "fleet",
        "mode": "open-fleet",
        "n_workers": n_workers,
        "cores": cores,
        "capacity_qps_1worker": round(cap1, 1),
        "rate_qps": round(rate, 1),
        "deadline_ms": {k: round(v * 1e3, 1)
                        for k, v in deadlines.items()},
        "single": single,
        "fleet": fleet_leg,
        "goodput_ratio": round(ratio, 2),
        "required_ratio": required_ratio,
    }

    # -- leg 3: chaos — SIGKILL under load, autoscale-first ------------
    if kill:
        chaos_dur = max(duration, 4.0)
        workdir = tempfile.mkdtemp(prefix="bench-fleet-chaos-")
        telemetry_dir = os.path.join(workdir, "telemetry")
        os.makedirs(telemetry_dir, exist_ok=True)
        # the parent joins the fleet's telemetry plane (rank 99, clear
        # of the workers' ranks) so the journal-mode watcher reads the
        # router's latency histograms from a shard like any other
        # process — no shared memory with the control loop
        os.environ["PADDLE_TPU_TELEMETRY_DIR"] = telemetry_dir
        os.environ["PADDLE_TRAINER_ID"] = "99"
        os.environ["PADDLE_TPU_TELEMETRY_INTERVAL"] = "0.25"
        timeline.ensure_publisher()
        c0 = observability.get_counters()
        srvC, fleetC = start_fleet(
            "fleet_chaos", n_workers - 1, max_replicas=n_workers,
            workdir=workdir,
            env={"PADDLE_TPU_TELEMETRY_INTERVAL": "0.25"},
        )
        watcher = Watcher(
            latency_metric="serving.request_latency.fleet_chaos",
            slo_p99_s=deadlines["interactive"],
            journal_dir=telemetry_dir,
            dead_process_timeout=3.0,
        )
        autoscaler = FleetAutoscaler(
            fleetC, breach_after=2, idle_after=10 ** 9, cooldown_s=5.0,
        )
        ctl = BrownoutController(
            srvC, slo_p99_s=deadlines["interactive"], watcher=watcher,
            escalate_after=2, recover_after=2, interval=0.25,
            autoscaler=autoscaler,
        )
        ctl.start()
        victim = fleetC.worker_pids()[0]

        def _assassin():
            time.sleep(chaos_dur / 3.0)
            os.kill(victim, signal.SIGKILL)

        killer = threading.Thread(target=_assassin, daemon=True)
        killer.start()
        chaos = _overload_leg(srvC, "fleet_chaos", build, rate,
                              chaos_dur, deadlines, shed=True)
        killer.join()
        # respawn-to-strength: the supervisor restores the corpse (and
        # the autoscaler's spare may land on top) while the backlog
        # drains; full strength = the n-1 the leg started with
        target = n_workers - 1
        wait_until = time.perf_counter() + 120.0
        while (time.perf_counter() < wait_until
               and fleetC.healthy_count() < target):
            time.sleep(0.5)
        healthy_end = fleetC.healthy_count()
        ctl.stop()
        c1 = observability.get_counters()
        first_scale = fleetC.first_scale_out_state
        pidsC = fleetC.worker_pids()
        srvC.close(timeout=120)

        def delta(name):
            return c1.get(name, 0) - c0.get(name, 0)

        gates["chaos_all_resolved"] = chaos["unresolved"] == 0
        gates["chaos_worker_death_detected"] = (
            delta("serving.fleet.worker_deaths") >= 1
        )
        gates["chaos_respawned"] = delta("serving.fleet.respawns") >= 1
        gates["chaos_scaled_out"] = delta("serving.fleet.scale_outs") >= 1
        # the brownout ladder's first rung is CAPACITY: the first
        # scale-out must precede any shed of this leg's traffic
        gates["chaos_scale_out_before_shed"] = (
            first_scale is not None
            and first_scale["shed"] - c0.get("serving.shed", 0) <= 0
        )
        gates["chaos_respawn_to_strength"] = healthy_end >= target
        gates["chaos_zero_orphans"] = not any(
            _pid_alive(p) for p in pidsC
        )
        entry["chaos"] = {
            **chaos,
            "victim_pid": victim,
            "healthy_end": healthy_end,
            "target_strength": target,
            "worker_deaths": delta("serving.fleet.worker_deaths"),
            "respawns": delta("serving.fleet.respawns"),
            "reroutes": delta("serving.fleet.reroutes"),
            "scale_outs": delta("serving.fleet.scale_outs"),
            "brownout_scale_outs": delta("serving.brownout_scale_outs"),
            "dead_process_findings": delta(
                "watch.findings.dead_process"
            ),
            "first_scale_out_shed_delta": (
                None if first_scale is None
                else first_scale["shed"] - c0.get("serving.shed", 0)
            ),
        }

    entry["gates"] = gates
    entry["ok"] = all(gates.values())
    results["fleet"] = entry
    return entry


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run (short durations, small context)")
    ap.add_argument("--dump", default=None,
                    help="write the observability snapshot JSON here")
    ap.add_argument("--duration", type=float, default=None,
                    help="seconds of load per mix (default 2 smoke / 6)")
    ap.add_argument("--mix", default=None,
                    help="comma list of mixes to run "
                         "(bert,resnet,ctr,gpt,overload,failover,"
                         "live_update; default: all)")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="run the overload mix against an N-worker "
                         "process fleet (ProcessReplicaSet) instead of "
                         "the in-process servers")
    ap.add_argument("--fleet-kill", action="store_true",
                    help="with --fleet: add the chaos leg — SIGKILL a "
                         "worker mid-run and gate failover, respawn, "
                         "autoscale-before-shed, zero orphans")
    args = ap.parse_args(argv)
    duration = args.duration or (2.0 if args.smoke else 6.0)
    all_mixes = ("bert", "resnet", "ctr", "gpt", "overload", "failover",
                 "live_update")
    mixes = (
        tuple(m.strip() for m in args.mix.split(",") if m.strip())
        if args.mix else all_mixes
    )
    unknown = [m for m in mixes if m not in all_mixes]
    if unknown:
        print(f"unknown mixes {unknown} (want {all_mixes})",
              file=sys.stderr)
        return 2

    import traceback

    import jax

    from paddle_tpu.core import compile_cache

    compile_cache.enable()
    on_accel = jax.devices()[0].platform in ("tpu", "gpu")
    results = {}
    gates = {}
    out = {"batched": None, "ctr": None, "gpt": None}
    raised = []

    def mix_bert():
        bert = bench_classify_mix(
            "bert_classify", "bert", (1, 2, 4, 8), "closed", 8, duration,
            results,
        )
        print(json.dumps(results["bert_classify"]), flush=True)
        # batched-vs-sequential acceptance ratio on the BERT frozen graph
        frozen, build, exe, scope, _ = bert
        batched = bench_batched_vs_sequential(frozen, build, exe, scope)
        out["batched"] = batched
        print(json.dumps({"mix": "bert_classify", **batched}), flush=True)
        gates["batched_speedup>=3"] = batched["batched_speedup"] >= 3.0
        # the request traces must reconstruct the queue-wait/compute
        # split (tracing is the observability contract of this router)
        gates["bert_trace_reconstruction"] = (
            results["bert_classify"].get("trace_spans", 0) > 0
            and results["bert_classify"].get("trace_vs_hist_consistent")
            is not False
        )

    def mix_resnet():
        # open-loop rate sized to ~60-70% of the CPU leg's service
        # capacity so latency reflects batching, not a saturated queue
        bench_classify_mix(
            "resnet_classify", "resnet", (1, 2, 4), "open",
            40 if not args.smoke else 10, duration, results,
        )
        print(json.dumps(results["resnet_classify"]), flush=True)

    def mix_ctr():
        # recommendation mix: fused-embedding DeepFM ranker (PR 11)
        ctr = out["ctr"] = bench_ctr_rank(args.smoke, duration, results)
        print(json.dumps(ctr), flush=True)
        gates["ctr_qps>0"] = (ctr["qps"] or 0) > 0
        gates["ctr_fused_sites==2"] = (
            ctr["fused_lookup_sites_frozen"] == 2
        )

    def mix_gpt():
        gpt = out["gpt"] = bench_gpt_generate(args.smoke, results)
        print(json.dumps(gpt), flush=True)
        gates["kv_decode_speedup>=5"] = gpt["kv_decode_speedup"] >= 5.0
        gates["kv_parity"] = bool(gpt["kv_parity"])

    def mix_overload():
        if args.fleet:
            # process-fleet legs: the overload arrival process against
            # real worker processes (plus the SIGKILL chaos leg when
            # --fleet-kill is set)
            fl = bench_fleet(args.smoke, duration, results,
                             n_workers=args.fleet,
                             kill=args.fleet_kill)
            print(json.dumps(fl), flush=True)
            gates["fleet"] = fl["ok"]
        else:
            # r15 fault-domain goodput mix (2x sustainable arrival rate)
            ov = bench_overload(args.smoke, duration, results)
            print(json.dumps(ov), flush=True)
            gates["overload"] = ov["ok"]

    def mix_failover():
        # r15 replica-kill chaos mix (3x window duration)
        fo = bench_failover(args.smoke, max(duration, 4.5), results)
        print(json.dumps(fo), flush=True)
        gates["failover"] = fo["ok"]

    def mix_live_update():
        # r18 live-publish mix: delta rollout under load, goodput dip
        # < 10%, zero torn batches
        lu = bench_live_update(args.smoke, max(duration, 3.0), results)
        print(json.dumps(lu), flush=True)
        gates["live_update"] = lu["ok"]

    for name, fn in (
        ("bert", mix_bert), ("resnet", mix_resnet), ("ctr", mix_ctr),
        ("gpt", mix_gpt), ("overload", mix_overload),
        ("failover", mix_failover), ("live_update", mix_live_update),
    ):
        if name not in mixes:
            continue
        try:
            fn()
        except Exception as e:  # one mix raising must not hide the others
            traceback.print_exc()
            raised.append(name)
            print(json.dumps({
                "mix": name, "error": f"{type(e).__name__}: {e}"[:300],
            }), flush=True)
    batched, ctr, gpt = out["batched"], out["ctr"], out["gpt"]

    if args.dump:
        from paddle_tpu import observability

        observability.dump(args.dump)

    summary = {
        "metric": "serving_qps",
        "value": results.get("bert_classify", {}).get("qps"),
        "unit": "req/s (bert_classify closed-loop)",
        "on_accel": on_accel,
        "mixes": {
            k: {
                f: v.get(f)
                for f in ("qps", "p50_ms", "p99_ms", "requests")
            }
            for k, v in results.items()
        },
        "gates": gates,
        "raised": raised,
    }
    if batched is not None:
        summary["batched_speedup"] = batched["batched_speedup"]
        summary["trace_queue_wait_ms"] = results["bert_classify"].get(
            "trace_queue_wait_ms"
        )
        summary["trace_dispatch_ms"] = results["bert_classify"].get(
            "trace_dispatch_ms"
        )
        summary["trace_vs_hist_consistent"] = results[
            "bert_classify"].get("trace_vs_hist_consistent")
    if gpt is not None:
        summary["kv_decode_speedup"] = gpt["kv_decode_speedup"]
        summary["kv_parity"] = gpt["kv_parity"]
    if ctr is not None:
        summary["served_embedding_qps"] = ctr["qps"]
    if "overload" in results:
        summary["goodput_ratio"] = results["overload"]["goodput_ratio"]
    if "failover" in results:
        summary["qps_recovery"] = results["failover"]["qps_recovery"]
    print(json.dumps(summary), flush=True)
    if raised:
        print(f"serving mixes RAISED: {raised}", file=sys.stderr)
        return 1
    if not all(gates.values()):
        failed = [k for k, v in gates.items() if not v]
        print(f"serving acceptance ratios NOT met: {failed}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
