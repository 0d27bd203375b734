"""Benchmark driver: BERT-base MLM (primary metric) + ResNet-50 + YOLOv3
+ long-context GPT (S=2048/4096/8192 through the KV-tiled flash kernel)
+ DeepFM CTR + Mask R-CNN, all on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}
— the BERT tokens/s stays the headline metric (comparable across rounds);
the other configs ride in "extra_metrics" so regressions are visible per
round (VERDICT r2 item 4).

Methodology (round 4):
  * AMP bf16 (mixed_precision.decorate) — v5e MXU path.
  * Every leg reports tflops + MFU (VERDICT r3 item 2): transformer legs
    use the analytic matmul-flop model (XLA's cost analysis cannot see
    inside the Pallas attention custom-calls); vision/CTR legs use the
    compiled executable's own cost analysis (Executor.flops).
  * Every leg records per-round throughput samples so chip-contention
    claims are evidenced in the artifact (VERDICT r3 item 7).
  * MLM head computes logits on the MASKED positions only via mask_pos
    gather (the reference BERT pretraining contract); the flop model
    scales the head term by P/(B*S) accordingly.
  * Causal GPT attention counts s/2 useful key positions per token (the
    standard MFU convention; the tiled kernel skips the dead tiles, so
    hardware work tracks the same ratio).
  * Pre-staged device batches, pipelined steps, device-side fetches; the
    final loss materialization is the step barrier (see round-2 notes).
  * One v5e chip: BERT/GPT best-of-2, vision/CTR best-of-3 (20-step
    windows) — small-batch configs swung up to 3x between windows in the
    r3-r5 captures. YOLOv3 runs b=16 from round 4 (the b=8 leg swung 3x,
    VERDICT r3 weak item 10).
MFU peak: 197 TFLOP/s bf16 (TPU v5e per-chip).
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np

ROUND1_TOKENS_PER_SEC = 32585.0
ROUND2_RESNET_IMG_S = 1631.0
# round-3 recorded "~270-350 img/s" at b=8 (BASELINE.md r3); 300 is the
# midpoint — the denominator for the stabler b=16 leg introduced in r4
ROUND3_YOLO_IMG_S = 300.0
ROUND3_GPT2048_TOK_S = 50787.0
# r5 Mask R-CNN: AMP bf16 + dynamic loss scaling, 4x1-image unroll
# (BASELINE.md r5 table) — denominator for the r6 batched leg
ROUND5_MASK_RCNN_IMG_S = 20.99
# r5 DeepFM: per-slot gather path, b=4096 criteo shape (BENCH_r05 deepfm
# leg) — denominator for the r11 fused-embedding leg (acceptance >= 5x)
ROUND5_DEEPFM_EX_S = 266671.4


def _amp(opt):
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


def _timed_loop(exe, prog, scope, batches, loss, n_steps, rounds):
    """Best-of-N pipelined timing; returns (best_dt, [all dts], loss)."""
    dts, final_loss = [], None
    for _ in range(rounds):
        fetched = []
        t0 = time.perf_counter()
        for i in range(n_steps):
            (lv,) = exe.run(
                prog, feed=batches[i % len(batches)], fetch_list=[loss],
                scope=scope, return_numpy=False,
            )
            fetched.append(lv)
        final_loss = float(np.asarray(fetched[-1]).reshape(-1)[0])
        dts.append(time.perf_counter() - t0)
    assert np.isfinite(final_loss), "loss went non-finite during benchmark"
    return min(dts), dts, final_loss


def _mfu_fields(per_step_flops, best_dt, n_steps, on_accel):
    # the SAME configurable peak the live perf.mfu gauge divides by
    # (PADDLE_TPU_PEAK_TFLOPS, default v5e bf16), so offline and live MFU
    # agree by construction
    from paddle_tpu.analysis.cost import peak_flops

    achieved = per_step_flops * n_steps / best_dt
    return {
        "tflops": round(achieved / 1e12, 1),
        "mfu_vs_v5e_bf16_peak": (
            round(achieved / peak_flops(), 3) if on_accel else None
        ),
        # the denominator actually used: when PADDLE_TPU_PEAK_TFLOPS
        # overrides the v5e default the key above keeps its historical
        # name but this field keeps the artifact honest
        "mfu_peak_tflops": round(peak_flops() / 1e12, 1),
    }


def _samples(unit_count, dts):
    return [round(unit_count / dt, 1) for dt in dts]


def _estimated_step_flops(prog, feed, legacy=None, legacy_name=None,
                          xla_flops=None):
    """Per-step FLOPs from the IR cost model (`Program.estimate`), plus a
    one-time cross-check block against the retired hand-coded closed form
    (r1-r6 bench methodology) and/or XLA's own cost_analysis. >20%
    divergence from the legacy formula is loud on stderr — that formula
    anchored every per-round MFU comparison, so a silent drift would
    rewrite history."""
    est = prog.estimate(
        feed_shapes={k: tuple(np.asarray(v).shape) for k, v in feed.items()}
    )
    fields = {"estimated_step_tflops": round(est.total_flops / 1e12, 6)}
    if legacy:
        div = abs(est.total_flops - legacy) / legacy
        fields["legacy_formula_tflops"] = round(legacy / 1e12, 6)
        fields["divergence_vs_legacy"] = round(div, 3)
        if div > 0.20:
            print(
                f"WARNING: cost-model step FLOPs diverge "
                f"{div:.0%} from the retired {legacy_name or 'closed-form'} "
                f"formula ({est.total_flops / 1e12:.4f} vs "
                f"{legacy / 1e12:.4f} TFLOP/step)",
                file=sys.stderr,
            )
    if xla_flops:
        fields["xla_step_tflops"] = round(xla_flops / 1e12, 6)
        fields["divergence_vs_xla"] = round(
            abs(est.total_flops - xla_flops) / xla_flops, 3
        )
    return est.total_flops, fields


def _perf_gauge_fields(est_step_flops, best_dt, n_steps, on_accel):
    """Live perf.* gauges after a timed loop: the executor-side MFU must
    agree with the offline per-leg number (acceptance: within 2 points).
    Both sides of the delta use the SAME cost-model numerator
    (est_step_flops), so the delta measures only timing skew (gauge's
    mean steady-state window vs offline best-of-N) — never
    estimate-vs-XLA divergence, which flops_model reports separately.
    The executor drops stale perf gauges on every compile-carrying run,
    so the gauge read here is this leg's own."""
    from paddle_tpu import observability as obs
    from paddle_tpu.analysis.cost import peak_flops

    gauges = obs.snapshot()["gauges"]
    mfu = gauges.get("perf.mfu")
    out = {"perf_mfu_gauge": None if mfu is None else round(mfu, 4)}
    if mfu is not None and on_accel:
        offline = est_step_flops * n_steps / best_dt / peak_flops()
        out["perf_mfu_gauge_delta"] = round(mfu - offline, 4)
    return out


def bench_bert(on_accel):
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.models import BertConfig, bert_pretrain
    from paddle_tpu.optimizer import Adam

    b, s = (32, 512) if on_accel else (4, 64)
    cfg = BertConfig.base() if on_accel else BertConfig.tiny()
    P = max(1, int(0.15 * b * s))  # max_predictions budget

    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = startup.random_seed = 1
    with fluid.program_guard(main_prog, startup):
        ids = fluid.data("ids", [b, s], "int64")
        types = fluid.data("types", [b, s], "int64")
        mask = fluid.data("mask", [b, s], "float32")
        mask_pos = fluid.data("mask_pos", [P], "int64")
        labels = fluid.data("labels", [P], "int64")
        loss = bert_pretrain(ids, types, mask, labels, cfg,
                             mask_pos=mask_pos)
        lr = layers.linear_lr_warmup(
            layers.polynomial_decay(1e-4, 100000, 1e-5), 1000, 0.0, 1e-4
        )
        opt = Adam(lr)
        if on_accel:
            opt = _amp(opt)
        opt.minimize(loss, startup)

    scope = Scope()
    exe = fluid.Executor()
    exe.run(startup, scope=scope)

    rng = np.random.RandomState(0)
    batches = []
    for _ in range(4):
        pos = rng.choice(b * s, P, replace=False).astype("int32")
        batches.append({
            "ids": rng.randint(0, cfg.vocab_size, (b, s)).astype("int32"),
            "types": rng.randint(0, cfg.type_vocab_size, (b, s)).astype("int32"),
            "mask": np.ones((b, s), "float32"),
            "mask_pos": pos,
            "labels": rng.randint(0, cfg.vocab_size, P).astype("int32"),
        })
    batches = [{k: jnp.asarray(v) for k, v in bt.items()} for bt in batches]

    for i in range(3):
        (wv,) = exe.run(main_prog, feed=batches[i % 4], fetch_list=[loss],
                        scope=scope, return_numpy=False)
    np.asarray(wv)

    n_steps = 20 if on_accel else 5
    dt, dts, final_loss = _timed_loop(
        exe, main_prog, scope, batches, loss, n_steps, 2 if on_accel else 1
    )
    tokens_per_sec = n_steps * b * s / dt

    h, L, V = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
    # retired r1-r6 closed form, kept as the cross-check: fwd matmul
    # flops/token L*(qkv 6h^2 + attn-out 2h^2 + ffn 16h^2 + attention
    # 4sh) + MLM head 2hV * (P/B*s); training ~= 3x fwd
    legacy = 3 * (
        L * (24 * h * h + 4 * s * h) + 2 * h * V * P / (b * s)
    ) * b * s
    step_flops, flops_model = _estimated_step_flops(
        main_prog, batches[0], legacy=legacy, legacy_name="transformer"
    )
    mfu = _mfu_fields(step_flops, dt, n_steps, on_accel)
    return {
        "metric": ("bert_base_mlm_train_tokens_per_sec" if on_accel
                   else "bert_tiny_mlm_train_tokens_per_sec_cpu"),
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": (round(tokens_per_sec / ROUND1_TOKENS_PER_SEC, 3)
                        if on_accel else 1.0),
        "config": {"batch": b, "seq": s, "amp": bool(on_accel),
                   "mask_pos": P},
        "samples": _samples(n_steps * b * s, dts),
        **mfu,
        "flops_model": flops_model,
        **_perf_gauge_fields(step_flops, dt, n_steps, on_accel),
        "final_loss": round(final_loss, 4),
    }


def bench_resnet(on_accel):
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.models.resnet import resnet_train_net
    from paddle_tpu.optimizer import Momentum

    # b=128 from round 5: the canonical TPU batch amortizes BN-stat and
    # layout overheads (r5 study: b=64 15-20%, b=128 23%, b=256 23.5% MFU;
    # BASELINE.md ResNet batch-scaling table)
    b, hw, depth = (128, 224, 50) if on_accel else (4, 32, 18)
    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = startup.random_seed = 1
    with fluid.program_guard(main_prog, startup):
        image = fluid.data("image", [b, 3, hw, hw])
        label = fluid.data("label", [b, 1], "int64")
        loss, _acc = resnet_train_net(image, label, depth=depth)
        opt = Momentum(0.1, 0.9)
        if on_accel:
            opt = _amp(opt)
        opt.minimize(loss, startup)
    scope = Scope()
    exe = fluid.Executor()
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    batches = [
        {"image": jnp.asarray(rng.rand(b, 3, hw, hw).astype("float32")),
         "label": jnp.asarray(
             rng.randint(0, 1000, (b, 1)).astype("int32"))}
        for _ in range(2)
    ]
    for i in range(3):
        (wv,) = exe.run(main_prog, feed=batches[i % 2], fetch_list=[loss],
                        scope=scope, return_numpy=False)
    np.asarray(wv)
    step_flops = exe.flops(main_prog, feed=batches[0], fetch_list=[loss],
                           scope=scope)
    # on one v5e chip the vision wall-clocks swung 30%+ between rounds
    # (r3-r5 captures); best-of-3 tightens the floor
    n_steps = 20 if on_accel else 3
    dt, dts, final_loss = _timed_loop(
        exe, main_prog, scope, batches, loss, n_steps, 3 if on_accel else 1
    )
    img_s = n_steps * b / dt
    est_flops, flops_model = _estimated_step_flops(
        main_prog, batches[0], xla_flops=step_flops
    )
    mfu = _mfu_fields(step_flops, dt, n_steps, on_accel)
    return {
        "metric": "resnet50_train_images_per_sec" if on_accel
        else "resnet18_tiny_train_images_per_sec_cpu",
        "value": round(img_s, 1),
        "unit": "img/s",
        "vs_baseline": (round(img_s / ROUND2_RESNET_IMG_S, 3)
                        if on_accel else 1.0),
        "config": {"batch": b, "size": hw, "depth": depth,
                   "amp": bool(on_accel)},
        "samples": _samples(n_steps * b, dts),
        **mfu,
        "flops_model": flops_model,
        **_perf_gauge_fields(est_flops, dt, n_steps, on_accel),
        "final_loss": round(final_loss, 4),
    }


def bench_yolov3(on_accel):
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.models import yolov3
    from paddle_tpu.optimizer import Momentum

    if on_accel:
        # b=64 from round 5: the r5 limiter analysis (BASELINE.md) showed
        # the leg carries a fixed ~20ms/step host-side latency floor on
        # one v5e chip; b=64 amortizes it (b=16 measured 3-5% MFU, b=64
        # 10-24% between windows)
        b, hw = 64, 224
        cfg = yolov3.YoloConfig(class_num=80, scale=0.5)
    else:
        b, hw = 2, 64
        cfg = yolov3.YoloConfig.tiny()
    n_gt = 10
    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = startup.random_seed = 1
    with fluid.program_guard(main_prog, startup):
        image = fluid.data("image", [b, 3, hw, hw])
        gt_box = fluid.data("gt_box", [b, n_gt, 4])
        gt_label = fluid.data("gt_label", [b, n_gt], "int32")
        loss = yolov3.yolov3_train(image, gt_box, gt_label, cfg)
        opt = Momentum(0.01, 0.9)
        if on_accel:
            opt = _amp(opt)
        opt.minimize(loss, startup)
    scope = Scope()
    exe = fluid.Executor()
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    boxes = rng.rand(b, n_gt, 4).astype("float32") * 0.5
    boxes[..., 2:] += 0.2  # w, h
    batches = [{
        "image": jnp.asarray(rng.rand(b, 3, hw, hw).astype("float32")),
        "gt_box": jnp.asarray(boxes),
        "gt_label": jnp.asarray(rng.randint(
            0, cfg.class_num, (b, n_gt)).astype("int32")),
    }]
    for _ in range(3):
        (wv,) = exe.run(main_prog, feed=batches[0], fetch_list=[loss],
                        scope=scope, return_numpy=False)
    np.asarray(wv)
    step_flops = exe.flops(main_prog, feed=batches[0], fetch_list=[loss],
                           scope=scope)
    n_steps = 20 if on_accel else 3
    dt, dts, final_loss = _timed_loop(
        exe, main_prog, scope, batches, loss, n_steps, 3 if on_accel else 1
    )
    img_s = n_steps * b / dt
    est_flops, flops_model = _estimated_step_flops(
        main_prog, batches[0], xla_flops=step_flops
    )
    mfu = _mfu_fields(step_flops, dt, n_steps, on_accel)
    return {
        "metric": "yolov3_half_train_images_per_sec" if on_accel
        else "yolov3_tiny_train_images_per_sec_cpu",
        "value": round(img_s, 1),
        "unit": "img/s",
        "vs_baseline": (round(img_s / ROUND3_YOLO_IMG_S, 3)
                        if on_accel else 1.0),
        "baseline_note": "r3 b=8 best-of-3 midpoint (270-350 swing); "
                         "b=16 from r4",
        "config": {"batch": b, "size": hw, "scale": cfg.scale,
                   "amp": bool(on_accel)},
        "samples": _samples(n_steps * b, dts),
        **mfu,
        "flops_model": flops_model,
        **_perf_gauge_fields(est_flops, dt, n_steps, on_accel),
        "final_loss": round(final_loss, 4),
    }


def bench_gpt_longctx(on_accel, seq_len=2048, batch=4):
    """GPT-small at S>=2048 — past the whole-row kernel's 1024 cap, so the
    KV-tiled flash kernel (kernels/flash_tiled.py) carries the attention;
    causal dead tiles are skipped in-kernel (r4)."""
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.models import GPTConfig, gpt_lm_loss
    from paddle_tpu.optimizer import Adam

    if on_accel:
        b, s = batch, seq_len
        cfg = GPTConfig(vocab_size=32000, hidden_size=768, num_layers=12,
                        num_heads=12, intermediate_size=3072,
                        max_position=seq_len)
    else:
        b, s = 2, 64
        cfg = GPTConfig.tiny()
    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = startup.random_seed = 1
    with fluid.program_guard(main_prog, startup):
        ids = fluid.data("ids", [b, s], "int64")
        loss = gpt_lm_loss(ids, cfg)
        opt = Adam(1e-4)
        if on_accel:
            opt = _amp(opt)
        opt.minimize(loss, startup)
    scope = Scope()
    exe = fluid.Executor()
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    batches = [
        {"ids": jnp.asarray(
            rng.randint(0, cfg.vocab_size, (b, s)).astype("int32"))}
        for _ in range(2)
    ]
    for i in range(3):
        (wv,) = exe.run(main_prog, feed=batches[i % 2], fetch_list=[loss],
                        scope=scope, return_numpy=False)
    np.asarray(wv)
    n_steps = 10 if on_accel else 3
    dt, dts, final_loss = _timed_loop(
        exe, main_prog, scope, batches, loss, n_steps, 2 if on_accel else 1
    )
    tok_s = n_steps * b * s / dt
    h, L, V = cfg.hidden_size, cfg.num_layers, cfg.vocab_size
    # retired closed form (cross-check): causal attention counts s/2
    # useful key positions per token (standard MFU convention; the
    # kernel's dead-tile skip makes hardware work track it)
    legacy = 3 * (L * (24 * h * h + 4 * (s // 2) * h) + 2 * h * V) * b * s
    step_flops, flops_model = _estimated_step_flops(
        main_prog, batches[0], legacy=legacy, legacy_name="causal GPT"
    )
    mfu = _mfu_fields(step_flops, dt, n_steps, on_accel)
    vs = (round(tok_s / ROUND3_GPT2048_TOK_S, 3)
          if (on_accel and seq_len == 2048) else None)
    return {
        "metric": (f"gpt_small_s{s}_train_tokens_per_sec" if on_accel
                   else "gpt_tiny_train_tokens_per_sec_cpu"),
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "vs_baseline": vs if on_accel else 1.0,
        "config": {"batch": b, "seq": s, "amp": bool(on_accel),
                   "attention": "flash_tiled (S beyond whole-row cap)"
                   if on_accel else "whole-row"},
        "samples": _samples(n_steps * b * s, dts),
        **mfu,
        "flops_model": flops_model,
        **_perf_gauge_fields(step_flops, dt, n_steps, on_accel),
        "final_loss": round(final_loss, 4),
    }


def bench_deepfm(on_accel):
    """CTR path: DeepFM (Criteo shape) examples/sec on single chip —
    embedding-gather + small-matmul bound, so MFU is expected to be tiny;
    the number exists so sparse-path regressions are visible (VERDICT r3
    weak item 9)."""
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.models.deepfm import DeepFMConfig, deepfm
    from paddle_tpu.optimizer import Adam

    cfg = DeepFMConfig.criteo() if on_accel else DeepFMConfig(
        vocab_size=1000, num_fields=6, embed_dim=8, mlp_sizes=(16,),
        dense_dim=4,
    )
    b = 4096 if on_accel else 64
    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = startup.random_seed = 1
    with fluid.program_guard(main_prog, startup):
        feat = fluid.data("feat", [b, cfg.num_fields], "int64")
        dense = fluid.data("dense", [b, cfg.dense_dim], "float32")
        label = fluid.data("label", [b, 1], "float32")
        loss, _pred = deepfm(feat, label, cfg, dense_input=dense)
        Adam(1e-3).minimize(loss, startup)
    scope = Scope()
    exe = fluid.Executor()
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    batches = [{
        "feat": jnp.asarray(rng.randint(
            0, cfg.vocab_size, (b, cfg.num_fields)).astype("int32")),
        "dense": jnp.asarray(rng.rand(b, cfg.dense_dim).astype("float32")),
        "label": jnp.asarray(
            (rng.rand(b, 1) < 0.3).astype("float32")),
    } for _ in range(2)]
    for i in range(3):
        (wv,) = exe.run(main_prog, feed=batches[i % 2], fetch_list=[loss],
                        scope=scope, return_numpy=False)
    np.asarray(wv)
    step_flops = exe.flops(main_prog, feed=batches[0], fetch_list=[loss],
                           scope=scope)
    n_steps = 20 if on_accel else 3
    dt, dts, final_loss = _timed_loop(
        exe, main_prog, scope, batches, loss, n_steps, 3 if on_accel else 1
    )
    ex_s = n_steps * b / dt
    est_flops, flops_model = _estimated_step_flops(
        main_prog, batches[0], xla_flops=step_flops
    )
    mfu = _mfu_fields(step_flops, dt, n_steps, on_accel)
    return {
        "metric": "deepfm_criteo_train_examples_per_sec" if on_accel
        else "deepfm_tiny_train_examples_per_sec_cpu",
        "value": round(ex_s, 1),
        "unit": "examples/s",
        "vs_baseline": None if on_accel else 1.0,
        "baseline_note": "new leg in r4",
        "config": {"batch": b, "fields": cfg.num_fields,
                   "dense": cfg.dense_dim, "vocab": cfg.vocab_size,
                   "mlp": list(cfg.mlp_sizes)},
        "samples": _samples(n_steps * b, dts),
        **mfu,
        "flops_model": flops_model,
        **_perf_gauge_fields(est_flops, dt, n_steps, on_accel),
        "final_loss": round(final_loss, 4),
    }


def bench_deepfm_fused(on_accel):
    """CTR path through the PR-11 embedding engine: the per-slot reference
    layout (2F gather dispatch sites) coalesced into ONE fused_lookup_table
    per table width, batch-dedup on, async prefetch staging the next
    batch's rows. Self-gating structural proxies on the CPU leg (one fused
    gather for all slots, dedup active, prefetch overlap recorded); the
    accel leg reports examples/s against the r5 per-slot denominator
    (acceptance: >= 5x)."""
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import observability as _obs
    from paddle_tpu.embedding import EmbeddingEngine, Prefetcher, fuse_lookups
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.models.deepfm import DeepFMConfig, deepfm
    from paddle_tpu.optimizer import Adam

    cfg = DeepFMConfig.criteo() if on_accel else DeepFMConfig(
        vocab_size=4096, num_fields=8, embed_dim=8, mlp_sizes=(16,),
        dense_dim=4,
    )
    b = 4096 if on_accel else 64
    rng = np.random.RandomState(0)

    def make_batches(k):
        out = []
        for _ in range(k):
            # power-law ids: the skew that makes the hot tier and dedup
            # meaningful (criteo id frequency is heavy-tailed)
            idv = (cfg.vocab_size * rng.power(0.35, (b, cfg.num_fields)))
            out.append({
                "feat": jnp.asarray(idv.astype("int64")),
                "dense": jnp.asarray(
                    rng.rand(b, cfg.dense_dim).astype("float32")
                ),
                "label": jnp.asarray(
                    (rng.rand(b, 1) < 0.3).astype("float32")
                ),
            })
        return out

    def build(fused):
        main_prog, startup = fluid.Program(), fluid.Program()
        main_prog.random_seed = startup.random_seed = 1
        scope = Scope()
        with fluid.program_guard(main_prog, startup):
            feat = fluid.data("feat", [b, cfg.num_fields], "int64")
            dense = fluid.data("dense", [b, cfg.dense_dim], "float32")
            label = fluid.data("label", [b, 1], "float32")
            loss, _pred = deepfm(feat, label, cfg, dense_input=dense,
                                 per_slot=True)
            if fused:
                fuse_lookups(main_prog)
            Adam(1e-3).minimize(loss, startup)
        exe = fluid.Executor()
        exe.run(startup, scope=scope)
        return main_prog, scope, exe, loss

    def lookup_sites(prog):
        singles = sum(1 for op in prog.global_block.ops
                      if op.type == "distributed_lookup_table")
        fused = sum(1 for op in prog.global_block.ops
                    if op.type == "fused_lookup_table")
        return singles, fused

    batches = make_batches(4)
    n_steps = 20 if on_accel else 6
    rounds = 3 if on_accel else 1

    # per-slot unfused baseline (the r5 shape, measured in-run on CPU so
    # the structural comparison is like-for-like on this host)
    base_prog, base_scope, base_exe, base_loss = build(fused=False)
    base_singles, _ = lookup_sites(base_prog)
    for i in range(2):
        base_exe.run(base_prog, feed=batches[i % 4], fetch_list=[base_loss],
                     scope=base_scope)
    base_dt, _, _ = _timed_loop(
        base_exe, base_prog, base_scope, batches, base_loss, n_steps, rounds
    )
    base_ex_s = n_steps * b / base_dt

    # fused leg
    main_prog, scope, exe, loss = build(fused=True)
    singles_left, fused_sites = lookup_sites(main_prog)
    for i in range(3):
        exe.run(main_prog, feed=batches[i % 4], fetch_list=[loss],
                scope=scope)
    dt, dts, final_loss = _timed_loop(
        exe, main_prog, scope, batches, loss, n_steps, rounds
    )
    ex_s = n_steps * b / dt
    est_flops, flops_model = _estimated_step_flops(main_prog, batches[0])
    mfu = _mfu_fields(est_flops, dt, n_steps, on_accel)

    # dedup ratio on the actual batches (host-side truth)
    ratios = [
        len(np.unique(np.asarray(f["feat"]))) / np.asarray(f["feat"]).size
        for f in batches
    ]

    # short cached+prefetched segment: the hot tier holds half the vocab,
    # the prefetcher stages cold rows behind compute — structural proxy
    # that the engine composes (hit-rate + overlap metrics land)
    cache_prog, cache_startup = fluid.Program(), fluid.Program()
    cache_prog.random_seed = cache_startup.random_seed = 1
    cache_scope = Scope()
    with fluid.program_guard(cache_prog, cache_startup):
        feat = fluid.data("feat", [b, cfg.num_fields], "int64")
        dense = fluid.data("dense", [b, cfg.dense_dim], "float32")
        label = fluid.data("label", [b, 1], "float32")
        closs, _ = deepfm(feat, label, cfg, dense_input=dense,
                          per_slot=True)
        fuse_lookups(cache_prog)
        engine = EmbeddingEngine(
            cache_prog, cache_startup,
            hot_rows=max(b * cfg.num_fields, cfg.vocab_size // 2),
        )
        Adam(1e-3).minimize(closs, cache_startup)
    cache_exe = fluid.Executor()
    cache_exe.run(cache_startup, scope=cache_scope)
    engine.attach(cache_scope)
    feed_stream = [
        {k: np.asarray(v) for k, v in batches[i % 4].items()}
        for i in range(8 if not on_accel else 16)
    ]
    for f in Prefetcher(engine, feed_stream, cache_scope):
        cache_exe.run(cache_prog, feed=f, fetch_list=[closs],
                      scope=cache_scope)
    gauges = _obs.get_gauges()
    hists = _obs.get_histograms()
    hit_rate = next(
        (v for k, v in gauges.items()
         if k.startswith("embedding.hot_hit_rate.")), None
    )
    overlap = hists.get("embedding.prefetch_overlap", {})
    overlap_mean = (
        overlap["sum"] / overlap["count"] if overlap.get("count") else None
    )

    gates = {
        "one_fused_gather_per_width": fused_sites == 2 and singles_left <= 1,
        "lookup_sites_before": base_singles,
        "lookup_sites_after": fused_sites + singles_left,
        "dedup_active": all(r < 1.0 for r in ratios),
        "dedup_unique_ratio": round(float(np.mean(ratios)), 4),
        "prefetch_overlap_recorded": bool(overlap.get("count")),
        "prefetch_overlap_mean": (
            round(overlap_mean, 3) if overlap_mean is not None else None
        ),
        "hot_hit_rate": round(hit_rate, 3) if hit_rate is not None else None,
    }
    structural_ok = (
        gates["one_fused_gather_per_width"]
        and gates["dedup_active"]
        and gates["prefetch_overlap_recorded"]
    )
    if not structural_ok:
        raise RuntimeError(f"deepfm_fused structural gates failed: {gates}")
    return {
        "metric": "deepfm_fused_criteo_train_examples_per_sec" if on_accel
        else "deepfm_fused_tiny_train_examples_per_sec_cpu",
        "value": round(ex_s, 1),
        "unit": "examples/s",
        # r5 denominator: 266,671 ex/s (BENCH_r05 deepfm leg, per-slot
        # gather path on one v5e chip) — acceptance >= 5x on accel
        "vs_baseline": (
            round(ex_s / ROUND5_DEEPFM_EX_S, 3) if on_accel else None
        ),
        "vs_per_slot_in_run": round(ex_s / base_ex_s, 3),
        "per_slot_examples_per_sec": round(base_ex_s, 1),
        "config": {"batch": b, "fields": cfg.num_fields,
                   "vocab": cfg.vocab_size, "mlp": list(cfg.mlp_sizes),
                   "layout": "per_slot->fused", "dedup": True},
        "samples": _samples(n_steps * b, dts),
        **mfu,
        "flops_model": flops_model,
        "gates": gates,
        "final_loss": round(final_loss, 4),
    }


def bench_mask_rcnn_legacy(on_accel):
    """LEGACY Mask R-CNN leg (r5 configuration, kept for like-for-like
    comparison under PADDLE_TPU_BATCHED_DETECTION=0): AMP bf16 + dynamic
    loss scaling, FOUR one-image graphs unrolled into one program. The r5
    BASELINE.md limiter analysis measured ~50-58 ms/image of device-busy
    small-op bookkeeping in this unroll — the batched leg below is the
    re-architecture that deletes it."""
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.models import mask_rcnn
    from paddle_tpu.optimizer import Momentum

    if on_accel:
        size, n_gt, n_img = 256, 8, 4
        cfg = mask_rcnn.MaskRCNNConfig(
            class_num=81, scale=0.5, rpn_pre_nms=512, rpn_post_nms=128,
            batch_size_per_im=64, depth=50,
        )
    else:
        size, n_gt, n_img = 64, 2, 1
        cfg = mask_rcnn.MaskRCNNConfig.tiny()
    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = startup.random_seed = 1
    with fluid.program_guard(main_prog, startup):
        per_losses = []
        for i in range(n_img):
            image = fluid.data(f"image{i}", [1, 3, size, size])
            gt_boxes = fluid.data(f"gt_boxes{i}", [n_gt, 4])
            gt_classes = fluid.data(f"gt_classes{i}", [n_gt],
                                    dtype="int32")
            is_crowd = fluid.data(f"is_crowd{i}", [n_gt], dtype="int32")
            gt_segms = fluid.data(f"gt_segms{i}", [n_gt, size, size])
            im_info = fluid.data(f"im_info{i}", [1, 3])
            losses = mask_rcnn.mask_rcnn_train(
                image, gt_boxes, gt_classes, is_crowd, gt_segms, im_info,
                cfg,
            )
            per_losses.append(losses[0])
        loss = per_losses[0]
        for l in per_losses[1:]:
            loss = layers.elementwise_add(loss, l)
        if n_img > 1:
            loss = layers.scale(loss, scale=1.0 / n_img)
        opt = Momentum(0.002, 0.9)
        if on_accel:
            from paddle_tpu.contrib import mixed_precision as mp

            opt = mp.decorate(
                opt,
                amp_lists=mp.AutoMixedPrecisionLists(
                    custom_white_list={"softmax", "layer_norm"}),
                use_dynamic_loss_scaling=True,
                init_loss_scaling=2.0 ** 12,
                dest_dtype="bfloat16",
            )
        opt.minimize(loss, startup)
    scope = Scope()
    exe = fluid.Executor()
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    feed = {}
    for i in range(n_img):
        boxes = rng.rand(n_gt, 4).astype("float32") * (size / 2)
        boxes[:, 2:] = boxes[:, :2] + 8 + boxes[:, 2:] / 2
        feed.update({
            f"image{i}": jnp.asarray(
                rng.rand(1, 3, size, size).astype("float32")),
            f"gt_boxes{i}": jnp.asarray(boxes),
            f"gt_classes{i}": jnp.asarray(
                rng.randint(1, cfg.class_num, n_gt).astype("int32")),
            f"is_crowd{i}": jnp.asarray(np.zeros(n_gt, "int32")),
            f"gt_segms{i}": jnp.asarray(
                (rng.rand(n_gt, size, size) > 0.5).astype("float32")),
            f"im_info{i}": jnp.asarray(
                np.array([[size, size, 1.0]], "float32")),
        })
    for _ in range(3):
        (wv,) = exe.run(main_prog, feed=feed, fetch_list=[loss],
                        scope=scope, return_numpy=False)
    np.asarray(wv)
    step_flops = exe.flops(main_prog, feed=feed, fetch_list=[loss],
                           scope=scope)
    n_steps = 20 if on_accel else 3
    dt, dts, final_loss = _timed_loop(
        exe, main_prog, scope, [feed], loss, n_steps, 3 if on_accel else 1
    )
    img_s = n_steps * n_img / dt
    return {
        "metric": "mask_rcnn_half_train_images_per_sec" if on_accel
        else "mask_rcnn_tiny_train_images_per_sec_cpu",
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": None if on_accel else 1.0,
        "baseline_note": "r5: AMP bf16 + dynamic loss scaling, 4-image "
                         "unroll (r4 was fp32 b=1: 20.8 img/s; "
                         "like-for-like fp32-b=1 measured 13.5 under r5 "
                         "chip conditions)",
        "config": {"images_per_step": n_img, "size": size,
                   "scale": cfg.scale, "depth": cfg.depth,
                   "amp": bool(on_accel), "dynamic_loss_scaling": True,
                   "batched_detection_ops": False},
        "samples": _samples(n_steps * n_img, dts),
        **_mfu_fields(step_flops, dt, n_steps, on_accel),
        "final_loss": round(final_loss, 4),
    }


def bench_mask_rcnn(on_accel):
    """Mask R-CNN train step, r6 cross-image batched detection ops: ONE
    [B, ...] program feeds B images through batched roi_align /
    generate_proposals / NMS / target-assign / label ops (fixed per-image
    RoI caps + validity masks) — the re-architecture BASELINE.md r5 named
    as the only path past the ~50-58 ms/image bookkeeping floor of the
    per-image unroll. images_per_step=8 on accel (vs the r5 4x unroll);
    PADDLE_TPU_BATCHED_DETECTION=0 selects the legacy r5 leg for
    like-for-like comparison. The "unroll_proxy" fields evidence the
    elimination on CPU-only CI where MFU cannot be measured: 1 program
    for B images, and the batched op count vs what the unroll would cost.
    """
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.framework.scope import Scope
    from paddle_tpu.models import mask_rcnn
    from paddle_tpu.ops.detection_stats import record_roi_stats
    from paddle_tpu.optimizer import Momentum

    if not mask_rcnn.batched_detection_enabled():
        return bench_mask_rcnn_legacy(on_accel)

    if on_accel:
        size, n_gt, B = 256, 8, 8
        cfg = mask_rcnn.MaskRCNNConfig(
            class_num=81, scale=0.5, rpn_pre_nms=512, rpn_post_nms=128,
            batch_size_per_im=64, depth=50,
        )
    else:
        size, n_gt, B = 64, 2, 2
        cfg = mask_rcnn.MaskRCNNConfig.tiny()
    main_prog, startup = fluid.Program(), fluid.Program()
    main_prog.random_seed = startup.random_seed = 1
    with fluid.program_guard(main_prog, startup):
        images = fluid.data("images", [B, 3, size, size])
        gt_boxes = fluid.data("gt_boxes", [B, n_gt, 4])
        gt_classes = fluid.data("gt_classes", [B, n_gt], dtype="int32")
        is_crowd = fluid.data("is_crowd", [B, n_gt], dtype="int32")
        gt_segms = fluid.data("gt_segms", [B, n_gt, size, size])
        im_info = fluid.data("im_info", [B, 3])
        losses, aux = mask_rcnn.mask_rcnn_train_batched(
            images, gt_boxes, gt_classes, is_crowd, gt_segms, im_info, cfg,
        )
        loss = losses[0]
        batched_fwd_ops = len(main_prog.global_block.ops)
        opt = Momentum(0.002, 0.9)
        if on_accel:
            from paddle_tpu.contrib import mixed_precision as mp

            opt = mp.decorate(
                opt,
                amp_lists=mp.AutoMixedPrecisionLists(
                    custom_white_list={"softmax", "layer_norm"}),
                use_dynamic_loss_scaling=True,
                init_loss_scaling=2.0 ** 12,
                dest_dtype="bfloat16",
            )
        opt.minimize(loss, startup)
    batched_op_count = len(main_prog.global_block.ops)

    # unroll-eliminated proxy: what ONE legacy per-image graph costs in
    # FORWARD ops (build only, never run; no optimizer on either side of
    # the comparison) -> the unroll would be B x that
    legacy_prog, legacy_startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(legacy_prog, legacy_startup):
        li = fluid.data("image", [1, 3, size, size])
        lb = fluid.data("gt_boxes", [n_gt, 4])
        lc = fluid.data("gt_classes", [n_gt], dtype="int32")
        lcr = fluid.data("is_crowd", [n_gt], dtype="int32")
        ls = fluid.data("gt_segms", [n_gt, size, size])
        lii = fluid.data("im_info", [1, 3])
        mask_rcnn.mask_rcnn_train(li, lb, lc, lcr, ls, lii, cfg)
    legacy_ops_per_image = len(legacy_prog.global_block.ops)

    scope = Scope()
    exe = fluid.Executor()
    exe.run(startup, scope=scope)
    rng = np.random.RandomState(0)
    boxes = rng.rand(B, n_gt, 4).astype("float32") * (size / 2)
    boxes[..., 2:] = boxes[..., :2] + 8 + boxes[..., 2:] / 2
    feed = {
        "images": jnp.asarray(
            rng.rand(B, 3, size, size).astype("float32")),
        "gt_boxes": jnp.asarray(boxes),
        "gt_classes": jnp.asarray(
            rng.randint(1, cfg.class_num, (B, n_gt)).astype("int32")),
        "is_crowd": jnp.asarray(np.zeros((B, n_gt), "int32")),
        "gt_segms": jnp.asarray(
            (rng.rand(B, n_gt, size, size) > 0.5).astype("float32")),
        "im_info": jnp.asarray(
            np.tile([[size, size, 1.0]], (B, 1)).astype("float32")),
    }
    # padding stats fetch once, then warm the EXACT [loss] fetch set the
    # timed loop uses (executables are cached per fetch set; a cold set
    # would put trace+compile inside the timed region)
    wv, rois_num = exe.run(main_prog, feed=feed,
                           fetch_list=[loss, aux["rois_num"]],
                           scope=scope, return_numpy=False)
    padding_waste = record_roi_stats(
        np.asarray(rois_num), cfg.batch_size_per_im
    )
    for _ in range(3):
        (wv,) = exe.run(main_prog, feed=feed, fetch_list=[loss],
                        scope=scope, return_numpy=False)
    np.asarray(wv)
    step_flops = exe.flops(main_prog, feed=feed, fetch_list=[loss],
                           scope=scope)
    n_steps = 20 if on_accel else 3
    dt, dts, final_loss = _timed_loop(
        exe, main_prog, scope, [feed], loss, n_steps, 3 if on_accel else 1
    )
    img_s = n_steps * B / dt
    est_flops, flops_model = _estimated_step_flops(
        main_prog, feed, xla_flops=step_flops
    )
    mfu = _mfu_fields(step_flops, dt, n_steps, on_accel)
    return {
        "metric": "mask_rcnn_half_train_images_per_sec" if on_accel
        else "mask_rcnn_tiny_train_images_per_sec_cpu",
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": (round(img_s / ROUND5_MASK_RCNN_IMG_S, 3)
                        if on_accel else 1.0),
        "baseline_note": "r5 denominator 20.99 img/s = AMP bf16+DLS "
                         "4x1-image unroll at 256^2 half-width (r4 fp32 "
                         "b=1: 20.8); PADDLE_TPU_BATCHED_DETECTION=0 "
                         "re-runs that legacy leg like-for-like",
        "config": {"images_per_step": B, "size": size,
                   "scale": cfg.scale, "depth": cfg.depth,
                   "roi_cap_per_image": cfg.batch_size_per_im,
                   "amp": bool(on_accel),
                   "dynamic_loss_scaling": bool(on_accel),
                   "batched_detection_ops": True},
        "unroll_proxy": {
            "programs_per_step": 1,
            "images_per_program": B,
            "batched_op_count": batched_op_count,
            "batched_fwd_ops": batched_fwd_ops,
            "legacy_fwd_ops_per_image": legacy_ops_per_image,
            "legacy_fwd_ops_if_unrolled": legacy_ops_per_image * B,
        },
        "padding_waste": round(padding_waste, 3),
        "samples": _samples(n_steps * B, dts),
        **mfu,
        "flops_model": flops_model,
        **_perf_gauge_fields(est_flops, dt, n_steps, on_accel),
        "final_loss": round(final_loss, 4),
    }


def _run_bench_child(script):
    """Run a tools/ bench script in its own (virtual-mesh-pinned) child
    process and parse the ONE JSON line it prints as its result."""
    import os
    import subprocess

    proc = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "tools", script)],
        capture_output=True, text=True, timeout=1200,
    )
    line = (proc.stdout or "").strip().splitlines()
    if proc.returncode != 0 or not line:
        raise RuntimeError(
            f"{script} failed (rc={proc.returncode}): "
            f"{proc.stderr[-500:]}"
        )
    return json.loads(line[-1])


def bench_dp_sharding(on_accel):
    """ZeRO weight-update sharding + quantized collectives on the dp=8
    virtual mesh (tools/bench_dp_sharding.py in a pinned CPU child
    process — a payload/memory leg, not a throughput leg): collective
    wire bytes vs the allreduce baseline, optimizer-state bytes/rank,
    and loss parity. Gates: >=40% int8 payload reduction, state/rank
    ~1/8, fp32 parity."""
    m = _run_bench_child("bench_dp_sharding.py")
    return {
        **m,
        "metric": "dp_sharding_payload_reduction",
        "value": m["int8_payload_reduction"],
        "unit": "fraction_of_allreduce_wire_bytes_saved",
    }


def bench_dp_overlap(on_accel):
    """Communication/compute overlap on the dp=8 virtual mesh
    (tools/bench_overlap.py in a pinned CPU child): bucketed grad
    collectives + prefetched all-gathers vs PR 9's serialized ZeRO — the
    r9 schedule is the denominator, PR 13's wait-fraction attribution the
    measurement. Self-gating: overlapped step <= serialized, fp32 bitwise
    parity, int8 within the r9 tolerance, wait fraction drops."""
    m = _run_bench_child("bench_overlap.py")
    return {
        **m,
        "metric": "dp_overlap_speedup",
        "value": m["overlap_speedup"],
        "unit": "serialized_step_over_overlapped_step",
        "baseline_note": "serialized ZeRO (r9 schedule) on the same "
                         "model/mesh is the denominator",
    }


def main():
    import traceback

    import jax

    from paddle_tpu.core import compile_cache

    compile_cache.enable()
    on_accel = jax.devices()[0].platform != "cpu"
    extras = {}
    legs = [
        ("bert", lambda: bench_bert(on_accel)),
        ("resnet50", lambda: bench_resnet(on_accel)),
        ("yolov3", lambda: bench_yolov3(on_accel)),
        ("gpt_longctx", lambda: bench_gpt_longctx(on_accel, 2048, 4)),
        ("deepfm", lambda: bench_deepfm(on_accel)),
        ("deepfm_fused", lambda: bench_deepfm_fused(on_accel)),
        ("mask_rcnn", lambda: bench_mask_rcnn(on_accel)),
        ("dp_sharding", lambda: bench_dp_sharding(on_accel)),
        ("dp_overlap", lambda: bench_dp_overlap(on_accel)),
    ]
    if on_accel:
        legs += [
            ("gpt_s4096", lambda: bench_gpt_longctx(on_accel, 4096, 2)),
            ("gpt_s8192", lambda: bench_gpt_longctx(on_accel, 8192, 1)),
        ]
    for name, fn in legs:
        try:
            extras[name] = fn()
        except Exception as e:  # one leg failing must not hide the others
            traceback.print_exc()
            extras[name] = {"error": f"{type(e).__name__}: {e}"}
    failed = sorted(k for k, v in extras.items() if "error" in v)
    primary = extras.pop("bert")
    primary["extra_metrics"] = extras
    print(json.dumps(primary))
    # LAST line: compact all-legs summary. The driver records the TAIL of
    # stdout; r4's full JSON was truncated mid-line and lost the headline
    # legs entirely (VERDICT r4 weak #7). This line is small enough to
    # always survive whole and parses to every leg.
    def _leg_brief(m):
        if "error" in m:
            return {"error": m["error"][:120]}
        out = {"value": m.get("value"), "unit": m.get("unit")}
        mfu = m.get("mfu_vs_v5e_bf16_peak")
        if mfu is not None:
            out["mfu"] = mfu
        if m.get("samples"):
            out["samples"] = m["samples"]
        return out

    compact = {
        "metric": primary.get("metric"),
        "value": primary.get("value"),
        "unit": primary.get("unit"),
        "vs_baseline": primary.get("vs_baseline"),
        "mfu": primary.get("mfu_vs_v5e_bf16_peak"),
        "legs": {
            "bert": _leg_brief(primary),
            **{k: _leg_brief(v) for k, v in extras.items()},
        },
    }
    print(json.dumps(compact))
    if failed:
        # every other leg was printed above; the run itself did not pass
        print(f"bench legs FAILED: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
