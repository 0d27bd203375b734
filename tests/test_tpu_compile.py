"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached (on-chip-measurement guide, section 2): the chip's own
compiler is installed here and refuses what the chip would refuse — a
slice not aligned to the tiling, more VMEM than a kernel may use. Nothing
runs, so this says nothing about results or times.

Two groups, one parametrised test:

* every kernel chip_smoke.py reaches, at the shapes it reaches it with
  (chip_smoke.REAL), forward and backward;
* the corners of ``supports()`` of the two row-wise kernels: whatever
  ``supports()`` admits must compile (ISSUE 21: at rows=16384 the fixed
  256-row block ran out of VMEM from n=2048 on).

The dispatch gates read ``jax.default_backend()``, which is ``cpu`` here,
so the kernel entry points are compiled directly.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import functools  # noqa: E402
import math  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import pytest  # noqa: E402

import chip_smoke  # noqa: E402
from paddle_tpu.kernels import decode_attention  # noqa: E402
from paddle_tpu.kernels import eva_attention  # noqa: E402
from paddle_tpu.kernels import flash_attention as fa  # noqa: E402
from paddle_tpu.kernels import flash_tiled as ft  # noqa: E402
from paddle_tpu.kernels import fused_residual as fr  # noqa: E402
from paddle_tpu.kernels import gdn_chunk_scan  # noqa: E402
from paddle_tpu.kernels import layer_norm as ln  # noqa: E402
from paddle_tpu.kernels import moe_gmm  # noqa: E402
from paddle_tpu.kernels import prefill_attention  # noqa: E402
from paddle_tpu.kernels import ring_block as rb  # noqa: E402
from paddle_tpu.kernels import rotary  # noqa: E402
from paddle_tpu.kernels import ssm_update  # noqa: E402

SZ = chip_smoke.REAL
BF16, F32 = jnp.bfloat16, jnp.float32
H, D = 12, 64          # BERT-base / GPT-small heads
HID = H * D
ROWS = SZ.bert_batch * SZ.bert_seq  # 16384 rows of the train step


@pytest.fixture(scope="module")
def chip():
    """SingleDeviceSharding on one described v5e chip; the persistent
    compile cache is off while the module runs (an entry written for a
    described chip cannot be read back without one, and warns)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # no libtpu / no such topology in this image
        pytest.skip(f"cannot describe a v5e topology here: {exc}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _statics(rate, is_test=False, causal=False):
    return dict(scale=1.0 / math.sqrt(D), rate=rate, is_test=is_test,
                upscale=False, causal=causal)


def _packed(batch, seq, dtype, rate, is_test=False):
    """(fwd, bwd) of the packed-QKV whole-row kernel."""
    st = _statics(rate, is_test)
    qkv = ((batch, seq, 3 * HID), dtype)
    bias, seed = ((batch, seq), F32), ((2,), jnp.uint32)
    out = ((batch, seq, HID), dtype)
    fwd = (lambda q, b, s: fa._pallas_fwd_qkv(q, b, s, H, D, st, False),
           (qkv, bias, seed))
    bwd = (lambda q, b, s, do: fa._pallas_bwd_qkv(q, b, s, do, H, D, st,
                                                  False),
           (qkv, bias, seed, out))
    return fwd, bwd


def _whole_row(batch, seq, dtype):
    """(fwd, bwd) of the 4-D whole-row kernel at its S cap."""
    st = _statics(0.1)
    x = ((batch, H, seq, D), dtype)
    bias, seed = ((batch, seq), F32), ((2,), jnp.uint32)
    fwd = (lambda q, k, v, b, s: fa._pallas_fwd(q, k, v, b, s, st, False),
           (x, x, x, bias, seed))
    bwd = (lambda q, k, v, b, s, do: fa._pallas_bwd(q, k, v, b, s, do, st,
                                                    False),
           (x, x, x, bias, seed, x))
    return fwd, bwd


def _tiled(batch, seq, dtype, rate, causal=True):
    """(fwd, bwd) of the KV-tiled kernels."""
    st = _statics(rate, causal=causal)
    qkv = ((batch, seq, 3 * HID), dtype)
    bias, seed = ((batch, seq), F32), ((2,), jnp.uint32)
    out, lse = ((batch, seq, HID), dtype), ((batch, seq, HID), F32)
    fwd = (lambda q, b, s: ft.flash_tiled_fwd(q, b, s, H, D, st),
           (qkv, bias, seed))
    bwd = (lambda q, b, s, do, o, l: ft.flash_tiled_bwd(q, b, s, do, o, l,
                                                        H, D, st),
           (qkv, bias, seed, out, out, lse))
    return fwd, bwd


def _ring(batch, s_local, dtype):
    """(fwd, dq, dkv) shard kernels of ring attention, causal."""
    scale = 1.0 / math.sqrt(D)
    x = ((batch, s_local, HID), dtype)
    f32 = ((batch, s_local, HID), F32)
    offs = ((2,), jnp.int32)
    fwd = (lambda q, k, v, o: rb.shard_fwd(q, k, v, o, H, D, True, scale,
                                           False),
           (x, x, x, offs))
    args = (x, x, x, x, f32, f32, offs)
    dq = (lambda q, k, v, do, l, d, o: rb.shard_dq(
        q, k, v, do, l, d, o, H, D, True, scale, False), args)
    dkv = (lambda q, k, v, do, l, d, o: rb.shard_dkv(
        q, k, v, do, l, d, o, H, D, True, scale, False), args)
    return fwd, dq, dkv


def _fused_residual(rows, n, dtype, rate, is_test=False):
    x, g, seed = ((rows, n), dtype), ((n,), F32), ((2,), jnp.uint32)
    fwd = (lambda a, b, g_, c, s: fr.fused_dropout_add_ln_fwd(
        a, b, g_, c, s, rate, is_test, False, 1e-5), (x, x, g, g, seed))
    bwd = (lambda a, b, g_, s, do: fr.fused_dropout_add_ln_bwd(
        a, b, g_, s, do, rate, is_test, False, 1e-5), (x, x, g, seed, x))
    return fwd, bwd


def _layer_norm(rows, n, dtype):
    x, g = ((rows, n), dtype), ((n,), F32)
    fwd = (lambda a, g_, c: ln.layer_norm_fwd(a, g_, c, 1e-5), (x, g, g))
    bwd = (lambda a, g_, dy: ln.layer_norm_bwd(a, g_, dy, 1e-5), (x, g, x))
    return fwd, bwd


def _gmm(rows, tm, k, n, experts=32, activation=None):
    """The routed experts' grouped product at Trinity-Large's widths:
    `rows` sorted rows in tiles of `tm`, `experts` matrices of [k, n],
    the experts' activation as the first product's epilogue."""
    specs = (((rows, k), BF16), ((experts, k, n), BF16),
             ((rows // tm,), jnp.int32), ((1,), jnp.int32))
    return (lambda x, w, te, na: moe_gmm.gmm(x, w, te, na, tm, activation),
            specs),


def _moe_rows(rows, tm, k, tokens):
    """(gather, combine) of a prefill dispatch's `tokens` rows of width
    `k` into and out of a sorted buffer of `rows`: a column block of the
    tokens resident in float32, the live tiles streaming past it."""
    per_row = ((rows,), jnp.int32)
    active = ((1,), jnp.int32)
    gather = (lambda src, tok, na: moe_gmm.gather_rows(src, tok, na, tm),
              (((tokens, k), BF16), per_row, active))
    combine = (lambda y, tok, w, na: moe_gmm.combine_rows(
        y, tok, w, na, tm, tokens),
        (((rows, k), BF16), per_row, ((rows,), F32), active))
    return gather, combine


def _ssm_update(batch, heads=128, head_dim=64, state=128, groups=8):
    """The decode step's in-place state update at Nemotron-3-Super's
    sizes: the stored state [B, H / 2, N, 2 P] float32, aliased."""
    from paddle_tpu.ops.kv_cache import ssm_state_shape

    shape = ssm_state_shape(batch, heads, head_dim, state, groups)
    row, col = shape[:2] + shape[3:], (batch, state, groups)
    specs = tuple((s, F32) for s in (shape, row, row, col, col))
    return (ssm_update.update, specs),


def _gdn_update(batch, value_heads=32, value_dim=128, key_dim=128,
                key_heads=16):
    """The decode step's in-place delta-rule update at Qwen3-Next's
    sizes: the stored state [B, Hv, dk, dv] float32, aliased; the same
    kernel as `_ssm_update` with the correction's `beta` row."""
    from paddle_tpu.ops.kv_cache import ssm_state_shape

    shape = ssm_state_shape(batch, value_heads, value_dim, key_dim,
                            key_heads)
    row, col = shape[:2] + shape[3:], (batch, key_dim, key_heads)
    specs = tuple((s, F32) for s in (shape, row, row, col, col, row))
    return (ssm_update.delta_update, specs),


def _lightning_update(batch, heads=32, dim=128):
    """The decode step's in-place Lightning update at MiniCPM-SALA's
    sizes: the stored state [B, H, dk, dv] float32, aliased; the same
    kernel as `_ssm_update` with one group a head, under its own call
    name."""
    from paddle_tpu.ops.kv_cache import ssm_state_shape

    shape = ssm_state_shape(batch, heads, dim, dim, heads)
    row, col = shape[:2] + shape[3:], (batch, dim, heads)
    specs = tuple((s, F32) for s in (shape, row, row, col, col))
    return (ssm_update.lightning_update, specs),


def _gdn_scan(rows, length, dtype, carried=False, key_heads=16,
              value_heads=32, dim=128, chunk=64):
    """A prefill dispatch's gated delta rule at Qwen3-Next's sizes: q | k
    | v of the convolution as ONE array read by lane blocks, g and beta
    float32, the final state [R, Hv, dk, dv] float32 (the stored
    layout); `carried`: a state enters as well."""
    sizes = dict(key_heads=key_heads, value_heads=value_heads, key_dim=dim,
                 value_dim=dim, chunk=chunk)
    assert gdn_chunk_scan.supports(key_heads, value_heads, dim, dim, chunk,
                                   dtype)
    gate = ((rows, length, value_heads), F32)
    specs = (((rows, length, (2 * key_heads + value_heads) * dim), dtype),
             gate, gate)
    if carried:
        specs += (((rows, value_heads, dim, dim), F32),)
    return (functools.partial(gdn_chunk_scan.scan, **sizes), specs),


def _decode_attention(dtype, heads, kv_heads, head_dim, window=0, batch=64,
                      max_len=1024):
    """A decode step's attention over one layer's caches as stored
    (`cache_shape`: a position a row), one query token a sequence."""
    from paddle_tpu.ops.kv_cache import cache_shape

    cache = (cache_shape(batch, max_len, kv_heads, head_dim, window), dtype)
    specs = (((batch, heads * head_dim), dtype), cache, cache,
             ((), jnp.int32))
    return (lambda q, k, v, pos: decode_attention.attend(
        q, k, v, pos, num_kv_heads=kv_heads, scale=head_dim ** -0.5,
        window=window, prob_scale=0.9), specs),


def _eva_attention(batch=64, heads=32, head_dim=128, max_len=4608,
                   window=2048, chunk=16):
    """A decode step's EVA attention over one layer's ring and chunk
    summaries as stored (`cache_shape(.., window)`, `index_shape`): one
    query token a sequence, both key sets in one softmax."""
    from paddle_tpu.ops.kv_cache import cache_shape, index_shape

    ring = (cache_shape(batch, max_len, heads, head_dim, window), BF16)
    summary = (index_shape(batch, max_len, chunk, heads, head_dim), BF16)
    specs = (((batch, heads * head_dim), BF16), ring, ring, summary,
             summary, ((), jnp.int32))
    return (lambda q, k, v, sk, sv, pos: eva_attention.attend(
        q, k, v, sk, sv, pos, num_kv_heads=heads, scale=head_dim ** -0.5,
        window=window, chunk=chunk), specs),


def _latent_attention(heads=128, width=576, value_width=512, batch=64,
                      max_len=1024):
    """A decode step's absorbed attention over one layer's latent cache
    as stored (`latent_cache_shape`: whole lane tiles), the values the
    rows' leading lanes: one cache operand."""
    from paddle_tpu.ops.kv_cache import latent_cache_shape

    cache = latent_cache_shape(batch, max_len, width)
    specs = (((batch, heads * cache[2]), BF16), (cache, BF16),
             ((), jnp.int32))
    return (lambda q, k, pos: decode_attention.attend(
        q, k, None, pos, num_kv_heads=1, scale=0.1,
        value_width=value_width), specs),


def _prefill_attention(dtype, heads, kv_heads, key_dim, value_dim, rows,
                       seq=896, shared=0):
    """A prefill dispatch's attention over its own rows as the op hands
    them over: `key_dim` lanes a query head, of which the last `shared`
    are scored against one key part every head shares."""
    assert prefill_attention.supports(seq, heads, kv_heads, key_dim,
                                      value_dim, dtype, 0, shared)
    specs = [((rows, seq, heads * key_dim), dtype),
             ((rows, seq, kv_heads * (key_dim - shared)), dtype),
             ((rows, seq, kv_heads * value_dim), dtype)]
    if shared:
        specs.append(((rows, seq, shared), dtype))

    def attend(q, k, v, k_shared=None):
        return prefill_attention.attend(
            q, k, v, num_heads=heads, num_kv_heads=kv_heads, scale=0.1,
            k_shared=k_shared)

    return (attend, tuple(specs)),


def _rotary(rows, head_dim, width, rotary_dim=None, seq=896):
    """A prefill dispatch's q or k as `rotary_embedding` hands it over,
    bfloat16, beside the three float32 tables of a unit."""
    assert rotary.supports(seq, width, head_dim, BF16)
    half = (rotary_dim or head_dim) // 2
    specs = (((rows, seq, width), BF16),) \
        + (((seq, rotary.unit(head_dim)), F32),) * 3
    return (functools.partial(rotary.rotate, half=half), specs),


def _cases():
    cases = {}

    def add(name, parts, labels=("fwd", "bwd")):
        for label, part in zip(labels, parts):
            cases[f"{name}-{label}"] = part

    b, s = SZ.bert_batch, SZ.bert_seq
    # train / kernels phases: BERT-base step, AMP bf16, dropout 0.1
    add(f"packed-b{b}-s{s}-bf16", _packed(b, s, BF16, 0.1))
    add(f"fused_residual-{ROWS}x{HID}-bf16",
        _fused_residual(ROWS, HID, BF16, 0.1))
    add(f"layer_norm-{ROWS}x{HID}-bf16", _layer_norm(ROWS, HID, BF16))
    # four-chip dp legs: a shard's quarter of the batch
    add(f"packed-b{b // 4}-s{s}-bf16", _packed(b // 4, s, BF16, 0.0))
    # longctx phase: GPT-small causal at S=4096, AMP bf16, dropout 0.1
    add(f"tiled-b{SZ.long_batch}-s{SZ.long_seq}-bf16",
        _tiled(SZ.long_batch, SZ.long_seq, BF16, 0.1))
    # serve phase: frozen fp32 graph in test mode, largest and smallest bucket
    for bucket in (max(SZ.serve_buckets), min(SZ.serve_buckets)):
        add(f"packed-b{bucket}-s{SZ.serve_seq}-f32-test",
            _packed(bucket, SZ.serve_seq, F32, 0.1, is_test=True)[:1])
        add(f"fused_residual-{bucket * SZ.serve_seq}x{HID}-f32-test",
            _fused_residual(bucket * SZ.serve_seq, HID, F32, 0.1,
                            is_test=True)[:1])
    # whole-row kernels at their S cap (the packed layout's fallback)
    add(f"packed-b2-s{fa.MAX_SEQ}-bf16", _packed(2, fa.MAX_SEQ, BF16, 0.1))
    add(f"whole_row-b2-s{fa.MAX_SEQ}-f32", _whole_row(2, fa.MAX_SEQ, F32))
    # --chips 4 ring leg: fp32, S=8192 over sp=4, and its one-device
    # comparison through the KV-tiled kernels
    add(f"ring-b{SZ.ring_batch}-s{SZ.ring_seq // 4}-f32",
        _ring(SZ.ring_batch, SZ.ring_seq // 4, F32), ("fwd", "dq", "dkv"))
    add(f"tiled-b{SZ.ring_batch}-s{SZ.ring_seq}-f32",
        _tiled(SZ.ring_batch, SZ.ring_seq, F32, 0.0))
    # the tiled kernels' resident super-block (vmem.resident_rows) at the
    # sizes that press on it: K, V (fwd, dq) and Q, dO, lse, delta (dkv) of
    # a lane group held whole at S=8192 and bf16 S=16384 (48 MiB of dkv
    # operands, double-buffered), two super-blocks beyond; and the loops
    # without causality
    add("corner-tiled-b1-s8192-bf16", _tiled(1, 8192, BF16, 0.1))
    add("corner-tiled-b1-s16384-bf16", _tiled(1, 16384, BF16, 0.1))
    add("corner-tiled-b1-s16384-f32", _tiled(1, 16384, F32, 0.1))
    add("corner-tiled-b1-s4096-bf16-noncausal",
        _tiled(1, 4096, BF16, 0.1, causal=False))
    # generate phase of trinity_large_ep8: a decode step's 64 x 4
    # assignments in tiles of 16, a prefill block's 16 x 896 x 4 in tiles
    # of 256; gate+up (3072 -> 6144, `swiglu` as the epilogue: a gate
    # and an up block a step) and down (3072 -> 3072); the prefill's rows
    # into the buffer and back (14,336 tokens: 512 columns resident)
    for rows, tm in ((768, 16), (65536, 256)):
        add(f"moe_gmm-{rows}x3072x6144-tm{tm}-bf16",
            _gmm(rows, tm, 3072, 6144, activation="swiglu"), ("fwd",))
        add(f"moe_gmm-{rows}x3072x3072-tm{tm}-bf16",
            _gmm(rows, tm, 3072, 3072), ("fwd",))
    add("moe_rows-65536x3072-tm256-t14336-bf16",
        _moe_rows(65536, 256, 3072, 14336), ("gather", "combine"))
    # generate phase of nemotron3_super_ep4: a decode step's 64 x 22
    # assignments in tiles of 16, a prefill block's 8 x 896 x 22 in tiles
    # of 256, up (1024 -> 2688) and down (2688 -> 1024) over 128 experts;
    # and the recurrent state's update, 64 sequences of 4 MB
    for rows, tm in ((3456, 16), (190464, 256)):
        for k, n, act in ((1024, 2688, "relu2"), (2688, 1024, None)):
            add(f"moe_gmm-{rows}x{k}x{n}-tm{tm}-bf16",
                _gmm(rows, tm, k, n, experts=128, activation=act), ("fwd",))
    add("moe_rows-190464x1024-tm256-t7168-bf16",
        _moe_rows(190464, 256, 1024, 7168), ("gather", "combine"))
    add("ssm_state_update-b64-h128x64-n128-f32", _ssm_update(64), ("fwd",))
    # a decode step's attention over the caches of the three generate
    # cells (batch 64, 1024 slots): GPT-2's float32 12 x 64, Trinity's
    # 48 over 8 x 128 (full, and a ring layer whose window never binds),
    # Nemotron's 32 over 2 x 128; and a context that takes several blocks
    add("decode_attention-gpt2-12x64-f32", _decode_attention(F32, 12, 12, 64),
        ("fwd",))
    for window in (0, 4096):
        add(f"decode_attention-trinity-48over8x128-w{window}-bf16",
            _decode_attention(BF16, 48, 8, 128, window), ("fwd",))
    add("decode_attention-nemotron-32over2x128-bf16",
        _decode_attention(BF16, 32, 2, 128), ("fwd",))
    add("corner-decode_attention-trinity-s16384-bf16",
        _decode_attention(BF16, 48, 8, 128, batch=4, max_len=16384),
        ("fwd",))
    # generate phase of dots_vlm1_ep16: the absorbed decode step's
    # attention, 128 heads over ONE latent cache whose rows hold the
    # values (512 of 640 stored lanes), at the cell's 1,024 slots and at
    # a context of several blocks; and its expert products at K = 7168
    # over 16 experts (a step's 64 x 8 assignments in tiles of 16, a
    # prefill block's 8 x 896 x 8 in tiles of 256)
    add("decode_attention-latent-128over1x640-bf16", _latent_attention(),
        ("fwd",))
    add("corner-decode_attention-latent-s16384-bf16",
        _latent_attention(batch=4, max_len=16384), ("fwd",))
    for rows, tm in ((768, 16), (61440, 256)):
        for k, n, act in ((7168, 4096, "swiglu"), (2048, 7168, None)):
            add(f"moe_gmm-{rows}x{k}x{n}-tm{tm}-bf16",
                _gmm(rows, tm, k, n, experts=16, activation=act), ("fwd",))
    add("moe_rows-61440x7168-tm256-t7168-bf16",
        _moe_rows(61440, 256, 7168, 7168), ("gather", "combine"))
    # generate phase of qwen3_next_ep8: the delta-rule state's update, 64
    # sequences of 2 MB (32 value heads x 128 x 128 over 16 key heads);
    # attention at a 256-wide head, 16 over 2 (a cache row of 512 lanes),
    # a decode step's and a prefill dispatch's of 8 rows; the smallest
    # experts of any cell, K = 2048 over 64 experts of 512 (a step's
    # 64 x 10 assignments in tiles of 16, a prefill block's 8 x 896 x 10
    # in tiles of 256)
    add("gdn_state_update-b64-h32x128x128-f32", _gdn_update(64), ("fwd",))
    # ... and the prefill's delta rule, a dispatch of 8 rows x 896 tokens
    # (14 chunks of 64) over 16 / 32 heads x 128, bfloat16 in, float32
    # state; a prompt that is not whole chunks with a state carried in,
    # float32 activations, the smallest chunk taken
    add("gdn_chunk_scan-r8-l896-16over32x128-bf16", _gdn_scan(8, 896, BF16),
        ("fwd",))
    add("corner-gdn_chunk_scan-r2-l1000-carried-f32",
        _gdn_scan(2, 1000, F32, carried=True), ("fwd",))
    add("corner-gdn_chunk_scan-r2-l100-2over2x256-c16-bf16",
        _gdn_scan(2, 100, BF16, key_heads=2, value_heads=2, dim=256,
                  chunk=16), ("fwd",))
    # generate phase of minicpm_sala_pp4: the Lightning state's update,
    # 64 sequences of 2 MB (32 heads x 128 x 128, one group a head)
    add("lightning_state_update-b64-h32x128x128-f32", _lightning_update(64),
        ("fwd",))
    add("decode_attention-qwen3_next-16over2x256-bf16",
        _decode_attention(BF16, 16, 2, 256), ("fwd",))
    # generate phase of evabyte_pp8: 64 sequences at 4,096 + 512 bytes,
    # the window's ring of 2,048 slots and 288 chunk summaries, 32 x 128
    add("eva_decode_attention-evabyte-b64-32x128-w2048-c16-bf16",
        _eva_attention(), ("fwd",))
    add("prefill_attention-qwen3_next-16over2x256-bf16",
        _prefill_attention(BF16, 16, 2, 256, 256, 8), ("fwd",))
    for rows, tm in ((1664, 16), (88064, 256)):
        for k, n, act in ((2048, 1024, "swiglu"), (512, 2048, None)):
            add(f"moe_gmm-{rows}x{k}x{n}-tm{tm}-bf16",
                _gmm(rows, tm, k, n, experts=64, activation=act), ("fwd",))
    # a prefill dispatch's attention in the four generate cells (896
    # tokens = one super-block of seven blocks): GPT-2's float32 12 x 64
    # over 64 rows, Trinity's 48 over 8 x 128 over 16, Nemotron's 32 over
    # 2 x 128 over 8, dots_vlm's 128 heads of 192 key lanes (64 of them
    # the shared rotary part) and 128 value lanes over 8, and the same
    # with the shared part inside K; a sequence of four super-blocks
    # (the online softmax's state in scratch) and the longest K and V a
    # float32 lane block may keep resident
    add("prefill_attention-gpt2-12x64-f32",
        _prefill_attention(F32, 12, 12, 64, 64, 64), ("fwd",))
    add("prefill_attention-trinity-48over8x128-bf16",
        _prefill_attention(BF16, 48, 8, 128, 128, 16), ("fwd",))
    add("prefill_attention-nemotron-32over2x128-bf16",
        _prefill_attention(BF16, 32, 2, 128, 128, 8), ("fwd",))
    add("prefill_attention-dots_vlm-128x192-128-shared64-bf16",
        _prefill_attention(BF16, 128, 128, 192, 128, 8, shared=64), ("fwd",))
    add("prefill_attention-dots_vlm-128x192-128-bf16",
        _prefill_attention(BF16, 128, 128, 192, 128, 8), ("fwd",))
    # evabyte_pp8's prefill dispatch: 8 rows, the first window's 2,048
    # rows, and the second's after its 128 summaries, padded to 2,304
    # (`ops/eva.py::trailing_rows`), 32 x 128
    for seq in (2048, 2304):
        add(f"prefill_attention-evabyte-32x128-s{seq}-bf16",
            _prefill_attention(BF16, 32, 32, 128, 128, 8, seq=seq),
            ("fwd",))
    add("corner-prefill_attention-trinity-s4096-bf16",
        _prefill_attention(BF16, 48, 8, 128, 128, 2, seq=4096), ("fwd",))
    add("corner-prefill_attention-gpt2-s16384-f32",
        _prefill_attention(F32, 12, 12, 64, 64, 1, seq=16384), ("fwd",))
    # a prefill dispatch's rotary positions in the four cells that turn
    # them: dots_vlm's q (128 heads of 192, the last 64 lanes: a unit of
    # two heads), Trinity's q and k (48 and 8 heads of 128), Qwen3-Next's
    # (16 and 2 heads of 256, the leading 64), MiniCPM-SALA's Lightning q
    # and k (32 heads of 128)
    add("rotary-dots_vlm-q-128x192-r64-bf16", _rotary(8, 192, 24576, 64),
        ("fwd",))
    add("rotary-trinity-q-48x128-bf16", _rotary(16, 128, 6144), ("fwd",))
    add("rotary-trinity-k-8x128-bf16", _rotary(16, 128, 1024), ("fwd",))
    add("rotary-qwen3_next-q-16x256-r64-bf16", _rotary(8, 256, 4096, 64),
        ("fwd",))
    add("rotary-qwen3_next-k-2x256-r64-bf16", _rotary(8, 256, 512, 64),
        ("fwd",))
    add("rotary-minicpm_sala-32x128-bf16", _rotary(16, 128, 4096), ("fwd",))
    # supports() corners of the row-wise kernels
    for n in (768, 2048, 4096, 8192):
        for dtype in (BF16, F32):
            tag = f"{ROWS}x{n}-{jnp.dtype(dtype).name}"
            if fr.supports(ROWS, n, dtype):
                add(f"corner-fused_residual-{tag}",
                    _fused_residual(ROWS, n, dtype, 0.1))
            if ln.supports(ROWS, n, dtype):
                add(f"corner-layer_norm-{tag}", _layer_norm(ROWS, n, dtype))
    return cases


_CASES = _cases()


@pytest.mark.parametrize("name", sorted(_CASES))
def test_kernel_compiles_for_v5e(chip, name):
    fn, specs = _CASES[name]
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
        for shape, dtype in specs
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


def _named_cases():
    """One case per `pl.pallas_call` site -> the `name=` it passes (the
    tiled backward holds two kernels)."""
    b, s = SZ.bert_batch, SZ.bert_seq
    packed = f"packed-b{b}-s{s}-bf16"
    whole = f"whole_row-b2-s{fa.MAX_SEQ}-f32"
    tiled = f"tiled-b{SZ.long_batch}-s{SZ.long_seq}-bf16"
    ring = f"ring-b{SZ.ring_batch}-s{SZ.ring_seq // 4}-f32"
    rows = f"{ROWS}x{HID}-bf16"
    return {
        f"{packed}-fwd": ["flash_attention_qkv_fwd"],
        f"{packed}-bwd": ["flash_attention_qkv_bwd"],
        f"{whole}-fwd": ["flash_attention_fwd"],
        f"{whole}-bwd": ["flash_attention_bwd"],
        f"{tiled}-fwd": ["flash_tiled_fwd"],
        f"{tiled}-bwd": ["flash_tiled_dkv", "flash_tiled_dq"],
        f"{ring}-fwd": ["ring_block_fwd"],
        f"{ring}-dq": ["ring_block_dq"],
        f"{ring}-dkv": ["ring_block_dkv"],
        f"fused_residual-{rows}-fwd": ["fused_residual_fwd"],
        f"fused_residual-{rows}-bwd": ["fused_residual_bwd"],
        f"layer_norm-{rows}-fwd": ["layer_norm_fwd"],
        f"layer_norm-{rows}-bwd": ["layer_norm_bwd"],
        "moe_gmm-768x3072x6144-tm16-bf16-fwd": ["moe_gmm_swiglu"],
        "moe_gmm-3456x1024x2688-tm16-bf16-fwd": ["moe_gmm_relu2"],
        "moe_gmm-768x2048x7168-tm16-bf16-fwd": ["moe_gmm"],
        "moe_rows-61440x7168-tm256-t7168-bf16-gather": ["moe_rows_gather"],
        "moe_rows-61440x7168-tm256-t7168-bf16-combine":
            ["moe_rows_combine"],
        "ssm_state_update-b64-h128x64-n128-f32-fwd": ["ssm_state_update"],
        "gdn_state_update-b64-h32x128x128-f32-fwd": ["gdn_state_update"],
        "gdn_chunk_scan-r8-l896-16over32x128-bf16-fwd": ["gdn_chunk_scan"],
        "lightning_state_update-b64-h32x128x128-f32-fwd":
            ["lightning_state_update"],
        "decode_attention-qwen3_next-16over2x256-bf16-fwd":
            ["decode_attention"],
        "eva_decode_attention-evabyte-b64-32x128-w2048-c16-bf16-fwd":
            ["eva_decode_attention"],
        "prefill_attention-qwen3_next-16over2x256-bf16-fwd":
            ["prefill_attention"],
        "moe_gmm-1664x2048x1024-tm16-bf16-fwd": ["moe_gmm_swiglu"],
        "moe_gmm-88064x512x2048-tm256-bf16-fwd": ["moe_gmm"],
        "decode_attention-gpt2-12x64-f32-fwd": ["decode_attention"],
        "prefill_attention-trinity-48over8x128-bf16-fwd":
            ["prefill_attention"],
        "rotary-minicpm_sala-32x128-bf16-fwd": ["rotary"],
    }


_NAMED = _named_cases()


@pytest.mark.parametrize("name", sorted(_NAMED))
def test_kernel_name_rides_into_the_lowered_module(chip, name):
    """Each kernel's `name=` is the custom call's `kernel_name` in the
    module lowered for the chip: what a device trace's reader tells the
    families apart by (ISSUE 24). Lowered, not compiled: fast."""
    fn, specs = _CASES[name]
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
        for shape, dtype in specs
    ]
    text = jax.jit(fn).lower(*args).as_text()
    want = _NAMED[name]
    assert text.count("tpu_custom_call") >= len(want), name
    for kernel in want:
        assert f'kernel_name = "{kernel}"' in text, (name, kernel)


def test_supports_refuses_what_the_row_block_cannot_serve():
    """The other side of the corner contract: beyond n=8192, off the lane
    width, or (fused_residual) off the 16-row PRNG draw, supports() says
    False and the jnp path runs."""
    for mod in (fr, ln):
        assert not mod.supports(ROWS, 16384, BF16)
        assert not mod.supports(ROWS, 8192 + 128, F32)
        assert not mod.supports(ROWS, 100, F32)
        assert not mod.supports(ROWS, 768, jnp.float16)
    assert not fr.supports(8, 768, F32)
    assert fr.supports(16, 768, F32)
    assert ln.supports(8, 768, F32)


@pytest.mark.parametrize("n,dtype,expect", [
    (768, BF16, 256), (768, F32, 256), (2048, F32, 128),
    (4096, BF16, 64), (8192, BF16, 32), (8192, F32, 32),
])
def test_fused_residual_row_block(n, dtype, expect):
    """BERT width keeps the 256-row block (no numerics change there: the
    dropout mask is drawn per row block); wider rows shrink it."""
    assert fr._row_block(ROWS, n, dtype, dtype) == expect
    assert ROWS % fr._row_block(ROWS, n, dtype, dtype) == 0


# -- the prefill's rotary kernel inside the generate cells' programs ----------
# cell: (tiny widths whose heads the rotary kernel takes and whose expert
# products are whole lane tiles, over the cell's own `tiny`, with the
# cell's layers; `kernels.rotary.calls` of a prefill: q and k each a
# call, dots_vlm's shared 64-lane key the `jnp` form)
EXPERTS = {"hidden_size": 128, "moe_intermediate_size": 128}
ROTARY_CELLS = {
    "dots_vlm1_ep16": ({**EXPERTS, "qk_nope_head_dim": 128,
                        "qk_rope_head_dim": 64, "v_head_dim": 128}, 5),
    "trinity_large_ep8": ({**EXPERTS, "head_dim": 128}, 8),
    "qwen3_next_ep8": ({**EXPERTS, "shared_expert_intermediate_size": 128,
                        "head_dim": 256, "num_hidden_layers": 12}, 6),
    "minicpm_sala_pp4": ({"lightning_head_dim": 128,
                          "num_hidden_layers": 8}, 12),
    "nemotron3_super_ep4": ({}, 0),
    "gpt2_small": ({}, 0),
}


def _lowered_for_chip(gen, program, feed, fetch, chip):
    """The StableHLO of one step of `program` as `Executor.lower` builds
    it, lowered for the described chip: its arguments are shapes placed
    there."""
    from paddle_tpu.core.random import prng_impl

    exe = gen.executor
    program, scope, block, feeds, _sig, fetch_names, key = exe._prepared(
        program, feed, fetch, gen.scope)
    compiled = exe._compile(program, block, set(feeds), fetch_names, scope,
                            key)
    args = (feeds,
            {n: exe._from_scope(scope, n, block) for n in compiled.state_mut},
            {n: exe._from_scope(scope, n, block) for n in compiled.state_ro},
            jax.random.key(0, impl=prng_impl()))
    return compiled.fn.lower(*jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        args)).as_text()


@pytest.mark.parametrize("cell", sorted(ROTARY_CELLS))
def test_the_rotary_kernel_runs_in_the_prefills_only(chip, cell,
                                                     monkeypatch):
    """Each generate cell's prefill and decode programs at tiny widths
    with the cell's layers, lowered for the described v5e (the backend
    gates steered to the TPU's branch; nothing compiled): a prefill holds
    the rotary kernel as many times as the gauge says, q and k each a
    call; a decode step (T = 1) holds none and leaves the gauge at 0;
    Nemotron (no positions) and GPT-2 (learned positions) hold none."""
    import importlib

    import numpy as np

    from benchmark.harness import manifest as mf
    from paddle_tpu import observability as obs

    widths, calls = ROTARY_CELLS[cell]
    manifest = mf.load()
    entry, spec = mf.cell(manifest, f"{cell}_generate_closed")
    cfg_json = dict(mf.config(manifest, entry["config"]))
    cfg_json["tiny"] = {**cfg_json["tiny"], **widths}
    traffic = {**spec["traffic"], **spec["rehearse"], "prompt_len": 32}
    builder = importlib.import_module(
        f"benchmark.builders.{cfg_json['builder']}")
    gen = builder.build_generate(cfg_json, traffic, True, seed=3).generator
    rows, batch = gen.prefill_rows, gen.batch
    prefill = {"context_ids": np.zeros((rows, 32), np.int64)}
    if rows < batch:
        prefill["row_ids"] = np.zeros((1,), np.int64)
    decode = {"token_ids": np.zeros((batch, 1), np.int64),
              "pos_ids": np.full((1, 1), 32, np.int64)}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    obs.reset()
    text = _lowered_for_chip(gen, gen.prefill_prog, prefill,
                             gen._prefill_fetch, chip)
    assert obs.get_gauges().get("kernels.rotary.calls", 0) == calls
    assert ('kernel_name = "rotary"' in text) == bool(calls)
    text = _lowered_for_chip(gen, gen.decode_prog, decode,
                             gen._decode_fetch, chip)
    assert obs.get_gauges().get("kernels.rotary.calls", 0) == 0
    assert 'kernel_name = "rotary"' not in text
