"""Round-3 op-surface tests: the long-tail emitters added to close the
reference coverage gap (VERDICT r2 item 1). Each op is exercised directly
through its registered emitter; numeric checks mirror the reference
kernels (paddle/fluid/operators/, per-op files cited in the op modules).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401  (registers emitters)
from paddle_tpu.framework.registry import EmitContext, get_op_def


class _FakeOp:
    def __init__(self, type, attrs):
        self.type, self.attrs, self.uid = type, attrs, 7

    def attr(self, k, d=None):
        return self.attrs.get(k, d)


@pytest.fixture
def run():
    ctx = EmitContext()
    ctx.key_for = lambda uid, t: jax.random.key(uid)

    def _run(t, attrs, ins):
        return get_op_def(t).emit(ctx, _FakeOp(t, attrs), ins)

    return _run


@pytest.fixture
def rng():
    return np.random.RandomState(0)


# --- tensor surface -------------------------------------------------------


def test_v1_shape_aliases(run):
    x = jnp.arange(12.0).reshape(3, 4)
    assert run("reshape", {"shape": [4, 3]}, {"X": [x]})["Out"][0].shape == (4, 3)
    assert run("transpose", {"axis": [1, 0]}, {"X": [x]})["Out"][0].shape == (4, 3)
    assert run("squeeze", {"axes": []}, {"X": [x[None]]})["Out"][0].shape == (3, 4)
    assert run("unsqueeze", {"axes": [0]}, {"X": [x]})["Out"][0].shape == (1, 3, 4)
    o = run("unbind", {"axis": 0}, {"X": [x]})["Out"]
    assert len(o) == 3 and o[0].shape == (4,)
    o = run("reverse", {"axis": [0]}, {"X": [x]})["Out"][0]
    assert float(o[0, 0]) == 8.0


def test_crop_diag_fill(run):
    x = jnp.arange(12.0).reshape(3, 4)
    o = run("crop", {"shape": [2, 2], "offsets": [1, 1]}, {"X": [x]})["Out"][0]
    assert float(o[0, 0]) == 5.0
    o = run("crop_tensor", {"shape": [2, 2], "offsets": [0, 1]}, {"X": [x]})["Out"][0]
    assert float(o[0, 0]) == 1.0
    assert run("diag", {}, {"Diagonal": [jnp.ones(3)]})["Out"][0].shape == (3, 3)
    o = run("fill", {"value": [1.0, 2.0, 3.0, 4.0], "shape": [2, 2],
                     "dtype": "float32"}, {})["Out"][0]
    assert float(o[1, 1]) == 4.0
    assert not bool(run("is_empty", {}, {"X": [x]})["Out"][0])


def test_frobenius_partial_unfold(run):
    x = jnp.arange(12.0).reshape(3, 4)
    o = run("frobenius_norm", {"reduce_all": True}, {"X": [x]})["Out"][0]
    assert np.allclose(float(o), np.linalg.norm(np.arange(12.0).reshape(3, 4)))
    xs = [jnp.ones((2, 5)), 2 * jnp.ones((2, 5))]
    o = run("partial_concat", {"start_index": 1, "length": 2}, {"X": xs})["Out"][0]
    assert o.shape == (2, 4)
    o = run("partial_sum", {"start_index": 1, "length": 2}, {"X": xs})["Out"][0]
    assert float(o[0, 0]) == 3.0
    xi = jnp.arange(16.0).reshape(1, 1, 4, 4)
    o = run("unfold", {"kernel_sizes": [2, 2], "strides": [1, 1],
                       "paddings": [0, 0, 0, 0], "dilations": [1, 1]},
            {"X": [xi]})["Y"][0]
    assert o.shape == (1, 4, 9)
    assert np.allclose(np.asarray(o[0, :, 0]), [0, 1, 4, 5])


def test_unique_static_size_contract(run):
    u = jnp.array([3, 1, 3, 2])
    o = run("unique", {}, {"X": [u]})
    out, idx = np.asarray(o["Out"][0]), np.asarray(o["Index"][0])
    assert np.allclose(out[idx], np.asarray(u))
    o = run("unique_with_counts", {}, {"X": [u]})
    pos = int(np.argmax(np.asarray(o["Out"][0]) == 3))
    assert int(np.asarray(o["Count"][0])[pos]) == 2


def test_scatter_nd_add_hash_conv_shift(run):
    o = run("scatter_nd_add", {}, {
        "X": [jnp.zeros((3, 3))],
        "Index": [jnp.array([[0, 0], [1, 2]])],
        "Updates": [jnp.array([5.0, 7.0])],
    })["Out"][0]
    assert float(o[0, 0]) == 5.0 and float(o[1, 2]) == 7.0
    ids = jnp.array([[1], [2], [3]], dtype=jnp.int32)
    o = run("hash", {"num_hash": 2, "mod_by": 1000}, {"X": [ids]})["Out"][0]
    assert o.shape == (3, 2, 1) and int(jnp.max(o)) < 1000
    o = run("conv_shift", {}, {"X": [jnp.ones((2, 8))], "Y": [jnp.ones((2, 3))]})["Out"][0]
    assert np.allclose(np.asarray(o), 3.0)


def test_batch_size_like_rng_ops(run):
    x = jnp.zeros((3, 4))
    o = run("uniform_random_batch_size_like",
            {"shape": [0, 5], "dtype": "float32"}, {"Input": [x]})["Out"][0]
    assert o.shape == (3, 5)
    o = run("gaussian_random_batch_size_like",
            {"shape": [0, 5], "dtype": "float32"}, {"Input": [x]})["Out"][0]
    assert o.shape == (3, 5)
    o = run("sampling_id", {}, {"X": [jnp.ones((4, 6)) / 6.0]})["Out"][0]
    assert o.shape == (4,)


# --- nn surface -----------------------------------------------------------


def test_prelu_modes(run, rng):
    x = jnp.asarray(rng.randn(2, 3, 4, 4).astype(np.float32))
    a = jnp.asarray([0.1, 0.2, 0.3])
    o = run("prelu", {"mode": "channel"}, {"X": [x], "Alpha": [a]})["Out"][0]
    ref = np.where(np.asarray(x) > 0, np.asarray(x),
                   np.asarray(x) * np.array([0.1, 0.2, 0.3]).reshape(1, 3, 1, 1))
    assert np.allclose(np.asarray(o), ref, atol=1e-6)


def test_data_norm_stats(run, rng):
    xd = jnp.asarray(rng.randn(4, 6).astype(np.float32))
    o = run("data_norm", {}, {
        "X": [xd], "BatchSize": [jnp.full((6,), 10.0)],
        "BatchSum": [jnp.full((6,), 5.0)],
        "BatchSquareSum": [jnp.full((6,), 40.0)],
    })
    assert np.allclose(np.asarray(o["Means"][0]), 0.5)
    assert np.allclose(np.asarray(o["Scales"][0]), 0.5)


def test_spectral_norm_unit_sigma(run, rng):
    w = jnp.asarray(rng.randn(4, 5).astype(np.float32))
    o = run("spectral_norm", {"dim": 0, "power_iters": 30}, {
        "Weight": [w],
        "U": [jnp.asarray(rng.randn(4).astype(np.float32))],
        "V": [jnp.asarray(rng.randn(5).astype(np.float32))],
    })["Out"][0]
    top = np.linalg.svd(np.asarray(o), compute_uv=False)[0]
    assert abs(top - 1.0) < 1e-3


def test_pool3d_family(run, rng):
    x3 = jnp.asarray(rng.randn(1, 2, 4, 4, 4).astype(np.float32))
    o = run("pool3d", {"ksize": [2, 2, 2], "strides": [2, 2, 2],
                       "pooling_type": "avg"}, {"X": [x3]})["Out"][0]
    assert o.shape == (1, 2, 2, 2, 2)
    o = run("max_pool3d_with_index", {"ksize": [2, 2, 2], "strides": [2, 2, 2]},
            {"X": [x3]})
    xf = np.asarray(x3).reshape(1, 2, -1)
    idx = np.asarray(o["Mask"][0]).reshape(1, 2, -1)
    assert np.allclose(np.take_along_axis(xf, idx, axis=2),
                       np.asarray(o["Out"][0]).reshape(1, 2, -1))


def test_unpool_roundtrip(run, rng):
    x2 = jnp.asarray(rng.randn(1, 2, 4, 4).astype(np.float32))
    p = run("max_pool2d_with_index", {"ksize": [2, 2], "strides": [2, 2]},
            {"X": [x2]})
    up = run("unpool", {"ksize": [2, 2], "strides": [2, 2],
                        "unpooled_height": 4, "unpooled_width": 4},
             {"X": [p["Out"][0]], "Indices": [p["Mask"][0]]})["Out"][0]
    # unpooled map contains each pooled max at its argmax position
    assert np.allclose(np.asarray(up).sum(), np.asarray(p["Out"][0]).sum())


def test_spp_non_divisible_dims(run):
    # 5x5 map with pyramid_height=3 (4x4 bins): adaptive bins never empty
    x = jnp.ones((1, 2, 5, 5))
    for ptype in ("max", "avg"):
        o = run("spp", {"pyramid_height": 3, "pooling_type": ptype},
                {"X": [x]})["Out"][0]
        assert o.shape == (1, 2 * (1 + 4 + 16))
        assert np.all(np.isfinite(np.asarray(o)))
        assert np.allclose(np.asarray(o), 1.0)


def test_similarity_focus_greedy_one_per_row_col(run):
    # slice [[3,2],[1,0]]: greedy tags (0,0) then (1,1) — not row|col maxima
    x = jnp.asarray(np.array([[[[3.0, 2.0], [1.0, 0.0]]]], np.float32))
    o = run("similarity_focus", {"axis": 1, "indexes": [0]}, {"X": [x]})["Out"][0]
    assert np.allclose(np.asarray(o)[0, 0], [[1.0, 0.0], [0.0, 1.0]])


def test_tdm_child_trailing_dim(run):
    info = np.zeros((7, 5), np.int32)
    info[1] = [0, 1, 0, 2, 3]
    info[2] = [10, 2, 1, 0, 0]
    info[3] = [11, 2, 1, 0, 0]
    o = run("tdm_child", {"child_nums": 2}, {
        "X": [jnp.asarray([[1, 2, 3], [1, 1, 1]])],
        "TreeInfo": [jnp.asarray(info)],
    })
    assert o["Child"][0].shape == (2, 6)


def test_interp_modes(run, rng):
    x1d = jnp.asarray(rng.randn(2, 3, 8).astype(np.float32))
    assert run("linear_interp", {"out_w": 16}, {"X": [x1d]})["Out"][0].shape == (2, 3, 16)
    x = jnp.asarray(rng.randn(2, 3, 8, 8).astype(np.float32))
    assert run("bicubic_interp", {"out_h": 16, "out_w": 16}, {"X": [x]})["Out"][0].shape == (2, 3, 16, 16)
    x5 = jnp.asarray(rng.randn(1, 2, 4, 4, 4).astype(np.float32))
    assert run("trilinear_interp", {"out_d": 8, "out_h": 8, "out_w": 8},
               {"X": [x5]})["Out"][0].shape == (1, 2, 8, 8, 8)


def test_affine_grid_identity(run):
    theta = jnp.asarray(np.tile(np.array([[1., 0., 0.], [0., 1., 0.]],
                                         np.float32), (2, 1, 1)))
    g = run("affine_grid", {"output_shape": [2, 1, 4, 5]},
            {"Theta": [theta], "OutputShape": [None]})["Output"][0]
    assert g.shape == (2, 4, 5, 2)
    assert np.allclose(np.asarray(g)[0, 0, 0], [-1, -1])
    assert np.allclose(np.asarray(g)[0, -1, -1], [1, 1])


def test_deformable_conv_zero_offset_matches_conv2d(run, rng):
    xc = jnp.asarray(rng.randn(1, 4, 6, 6).astype(np.float32))
    wc = jnp.asarray(rng.randn(8, 4, 3, 3).astype(np.float32))
    off = jnp.zeros((1, 2 * 9, 6, 6), jnp.float32)
    mask = jnp.ones((1, 9, 6, 6), jnp.float32)
    attrs = {"strides": [1, 1], "paddings": [1, 1], "dilations": [1, 1],
             "groups": 1, "deformable_groups": 1}
    o = run("deformable_conv", attrs,
            {"Input": [xc], "Offset": [off], "Mask": [mask], "Filter": [wc]})["Output"][0]
    ref = run("conv2d", attrs, {"Input": [xc], "Filter": [wc]})["Output"][0]
    assert np.allclose(np.asarray(o), np.asarray(ref), atol=1e-4)


def test_psroi_prroi_shapes(run, rng):
    xp = jnp.asarray(rng.randn(1, 8, 8, 8).astype(np.float32))
    rois = jnp.asarray(np.array([[0., 0., 4., 4.], [2., 2., 6., 6.]], np.float32))
    o = run("psroi_pool", {"pooled_height": 2, "pooled_width": 2,
                           "output_channels": 2, "spatial_scale": 1.0},
            {"X": [xp], "ROIs": [rois], "RoisNum": [jnp.asarray([2])]})["Out"][0]
    assert o.shape == (2, 2, 2, 2)
    xc = jnp.asarray(rng.randn(1, 4, 8, 8).astype(np.float32))
    o = run("prroi_pool", {"pooled_height": 2, "pooled_width": 2,
                           "spatial_scale": 1.0},
            {"X": [xc], "ROIs": [rois], "BatchRoINums": [jnp.asarray([2])]})["Out"][0]
    assert o.shape == (2, 4, 2, 2)


def test_lstmp_attention_lstm(run, rng):
    xl = jnp.asarray(rng.randn(2, 5, 4).astype(np.float32))
    o = run("lstmp", {}, {
        "X": [xl],
        "WIH": [jnp.asarray(rng.randn(24, 4).astype(np.float32))],
        "WHH": [jnp.asarray(rng.randn(24, 3).astype(np.float32))],
        "ProjWeight": [jnp.asarray(rng.randn(6, 3).astype(np.float32))],
        "Bias": [None], "H0": [None], "C0": [None], "SeqLen": [None],
    })
    assert o["Projection"][0].shape == (2, 5, 3)
    o = run("attention_lstm", {}, {
        "X": [xl], "C0": [jnp.zeros((2, 6))], "H0": [None],
        "AttentionWeight": [jnp.asarray(rng.randn(10, 1).astype(np.float32))],
        "AttentionBias": [None], "AttentionScalar": [None],
        "AttentionScalarBias": [None],
        "LSTMWeight": [jnp.asarray(rng.randn(10, 24).astype(np.float32))],
        "LSTMBias": [None], "SeqLen": [None],
    })
    assert o["Hidden"][0].shape == (2, 5, 6)


# --- losses ---------------------------------------------------------------


def test_nce_hsigmoid_finite(run, rng):
    x = jnp.asarray(rng.randn(4, 8).astype(np.float32))
    o = run("nce", {"num_total_classes": 20, "num_neg_samples": 5}, {
        "Input": [x], "Label": [jnp.asarray(rng.randint(0, 20, (4, 1)))],
        "Weight": [jnp.asarray(rng.randn(20, 8).astype(np.float32))],
        "Bias": [jnp.asarray(rng.randn(20).astype(np.float32))],
        "SampleWeight": [None],
    })
    assert np.all(np.isfinite(np.asarray(o["Cost"][0])))
    o = run("hierarchical_sigmoid", {"num_classes": 10}, {
        "X": [x], "Label": [jnp.asarray(rng.randint(0, 10, (4,)))],
        "W": [jnp.asarray(rng.randn(9, 8).astype(np.float32))],
        "Bias": [jnp.asarray(rng.randn(9).astype(np.float32))],
        "PathTable": [None], "PathCode": [None],
    })
    assert np.all(np.asarray(o["Out"][0]) > 0)


def test_teacher_student_exact(run):
    xs = jnp.asarray(np.array([[0.5], [-0.5]], np.float32))
    o = run("teacher_student_sigmoid_loss", {}, {
        "X": [xs], "Label": [jnp.asarray(np.array([[-2.0], [-1.0]], np.float32))],
    })
    y = np.asarray(o["Y"][0]).ravel()
    assert np.allclose(y, [0.5 + np.log1p(np.exp(-0.5)),
                           0.5 + np.log1p(np.exp(-0.5))], atol=1e-5)


def test_warpctc_uniform_exact(run):
    # B=1, T=3, C=3, label=[1], uniform logits: 6 valid paths of prob (1/3)^3
    o = run("warpctc", {"blank": 0}, {
        "Logits": [jnp.zeros((1, 3, 3))], "Label": [jnp.asarray([[1]])],
        "LogitsLength": [jnp.asarray([3])], "LabelLength": [jnp.asarray([1])],
    })
    assert abs(float(np.asarray(o["Loss"][0])[0, 0]) + np.log(6 * (1 / 3) ** 3)) < 1e-3


def test_ctc_align_and_edit_distance(run):
    o = run("ctc_align", {"blank": 0}, {
        "Input": [jnp.asarray(np.array([[0, 1, 1, 0, 2, 2, 0]], np.int32))],
        "InputLength": [None],
    })
    out = np.asarray(o["Output"][0])[0]
    assert list(out[:2]) == [1, 2] and np.all(out[2:] == -1)

    def enc(s, L):
        return [ord(c) for c in s] + [0] * (L - len(s))

    o = run("edit_distance", {"normalized": False}, {
        "Hyps": [jnp.asarray([enc("kitten", 7)], jnp.int32)],
        "Refs": [jnp.asarray([enc("sitting", 7)], jnp.int32)],
        "HypsLength": [jnp.asarray([6])], "RefsLength": [jnp.asarray([7])],
    })
    assert float(np.asarray(o["Out"][0])[0, 0]) == 3.0


def test_chunk_eval_iob(run):
    lab = jnp.asarray([[0, 1, 4, 2]], jnp.int32)
    o = run("chunk_eval", {"chunk_scheme": "IOB", "num_chunk_types": 3},
            {"Inference": [lab], "Label": [lab], "SeqLength": [jnp.asarray([4])]})
    assert float(np.asarray(o["F1-Score"][0])) == 1.0
    o = run("chunk_eval", {"chunk_scheme": "IOB", "num_chunk_types": 3},
            {"Inference": [jnp.asarray([[0, 0, 4, 2]], jnp.int32)],
             "Label": [lab], "SeqLength": [jnp.asarray([4])]})
    assert float(np.asarray(o["Precision"][0])) < 1.0


def test_chunk_eval_outside_labels_not_chunks(run):
    # all-O sequence (label == num_chunk_types * 2): zero chunks
    o = run("chunk_eval", {"chunk_scheme": "IOB", "num_chunk_types": 1},
            {"Inference": [jnp.asarray([[2, 2, 2, 2]], jnp.int32)],
             "Label": [jnp.asarray([[2, 2, 2, 2]], jnp.int32)],
             "SeqLength": [jnp.asarray([4])]})
    assert int(np.asarray(o["NumLabelChunks"][0])) == 0
    assert float(np.asarray(o["F1-Score"][0])) == 0.0
    # B-x O B-x: two chunks split by the O
    o = run("chunk_eval", {"chunk_scheme": "IOB", "num_chunk_types": 1},
            {"Inference": [jnp.asarray([[0, 2, 0]], jnp.int32)],
             "Label": [jnp.asarray([[0, 2, 0]], jnp.int32)],
             "SeqLength": [jnp.asarray([3])]})
    assert int(np.asarray(o["NumLabelChunks"][0])) == 2
    assert float(np.asarray(o["F1-Score"][0])) == 1.0


def test_detection_map_accumulation(run):
    det = jnp.asarray(np.array([[0, 0.9, 0, 0, 10, 10],
                                [0, 0.8, 50, 50, 60, 60]], np.float32))
    gt = jnp.asarray(np.array([[0, 0, 0, 10, 10]], np.float32))
    attrs = {"class_num": 1, "overlap_threshold": 0.5}
    none_ins = {"HasState": [None], "PosCount": [None],
                "TruePos": [None], "FalsePos": [None]}
    o1 = run("detection_map", attrs, {"DetectRes": [det], "Label": [gt], **none_ins})
    # feed accumulators back: same batch again -> same mAP, doubled counts
    o2 = run("detection_map", attrs, {
        "DetectRes": [det], "Label": [gt],
        "HasState": [jnp.asarray([1])],
        "PosCount": [o1["AccumPosCount"][0]],
        "TruePos": [o1["AccumTruePos"][0]],
        "FalsePos": [o1["AccumFalsePos"][0]],
    })
    assert int(np.asarray(o2["AccumPosCount"][0])[0, 0]) == 2
    assert abs(float(np.asarray(o2["MAP"][0])[0])
               - float(np.asarray(o1["MAP"][0])[0])) < 1e-5


def test_precision_recall_micro(run):
    o = run("precision_recall", {"class_number": 3}, {
        "MaxProbs": [jnp.ones((6, 1))],
        "Indices": [jnp.asarray([[0], [1], [2], [0], [1], [2]])],
        "Labels": [jnp.asarray([[0], [1], [1], [0], [2], [2]])],
        "Weights": [None], "StatesInfo": [None],
    })
    bm = np.asarray(o["BatchMetrics"][0])
    assert abs(bm[3] - 4 / 6) < 1e-6  # micro precision


def test_positive_negative_pair(run):
    o = run("positive_negative_pair", {}, {
        "Score": [jnp.asarray([0.9, 0.1, 0.8, 0.2])],
        "Label": [jnp.asarray([1.0, 0.0, 1.0, 0.0])],
        "QueryID": [jnp.asarray([1, 1, 2, 2])],
        "Weight": [None], "AccumulatePositivePair": [None],
        "AccumulateNegativePair": [None], "AccumulateNeutralPair": [None],
    })
    assert float(np.asarray(o["PositivePair"][0])[0]) == 2.0


def test_detection_map_perfect(run):
    det = jnp.asarray(np.array([[0, 0.9, 0, 0, 10, 10],
                                [1, 0.8, 20, 20, 30, 30]], np.float32))
    gt = jnp.asarray(np.array([[0, 0, 0, 10, 10],
                               [1, 20, 20, 30, 30]], np.float32))
    o = run("detection_map", {"class_num": 2, "overlap_threshold": 0.5}, {
        "DetectRes": [det], "Label": [gt], "HasState": [None],
        "PosCount": [None], "TruePos": [None], "FalsePos": [None],
    })
    assert abs(float(np.asarray(o["MAP"][0])[0]) - 1.0) < 1e-5


# --- quantization ---------------------------------------------------------


def test_fake_quant_family(run, rng):
    x = jnp.asarray(rng.randn(4, 8).astype(np.float32))
    o = run("fake_quantize_abs_max", {"bit_length": 8}, {"X": [x]})
    assert abs(float(np.asarray(o["OutScale"][0])[0])
               - np.abs(np.asarray(x)).max()) < 1e-5
    o = run("fake_channel_wise_quantize_abs_max",
            {"bit_length": 8, "quant_axis": 0}, {"X": [x]})
    assert o["OutScale"][0].shape == (4,)
    o = run("fake_quantize_range_abs_max", {"bit_length": 8},
            {"X": [x], "InScale": [jnp.asarray([100.0])], "Iter": [None]})
    assert float(np.asarray(o["OutScale"][0])[0]) >= 100.0
    o = run("fake_dequantize_max_abs", {"max_range": 127.0},
            {"X": [jnp.asarray([[127.0]])], "Scale": [jnp.asarray([2.0])]})
    assert abs(float(np.asarray(o["Out"][0])[0, 0]) - 2.0) < 1e-6


def test_int8_pipeline(run):
    o = run("quantize", {"Scale": 127.0}, {"Input": [jnp.asarray([[0.5]])]})
    assert int(np.asarray(o["Output"][0])[0, 0]) == 64
    o = run("dequantize", {"Scale": 127.0},
            {"Input": [jnp.asarray([[64]], np.int8)]})
    assert abs(float(np.asarray(o["Output"][0])[0, 0]) - 64 / 127) < 1e-6
    o = run("dequantize_log", {}, {
        "X": [jnp.asarray([[5], [-4]], np.int8)], "Dict": [jnp.arange(128.0)],
    })
    out = np.asarray(o["Out"][0]).ravel()
    assert out[0] == 5.0 and out[1] == -124.0


# --- control flow / ps / optimizer ---------------------------------------


def test_tensor_array_ops(run):
    xa = jnp.asarray([1.0, 2.0])
    arr = run("write_to_array", {"capacity": 4},
              {"X": [xa], "I": [jnp.asarray(1)], "Array": [None]})["Out"][0]
    assert arr.shape == (4, 2) and float(arr[1, 0]) == 1.0
    o = run("read_from_array", {}, {"X": [arr], "I": [jnp.asarray(1)]})
    assert np.allclose(np.asarray(o["Out"][0]), [1.0, 2.0])
    o = run("tensor_array_to_tensor", {"axis": 0, "use_stack": False}, {"X": [arr]})
    assert o["Out"][0].shape == (8,)


def test_select_ops(run):
    o = run("select_input", {}, {
        "X": [jnp.asarray([1.0]), jnp.asarray([2.0])], "Mask": [jnp.asarray(1)],
    })
    assert float(np.asarray(o["Out"][0])[0]) == 2.0
    o = run("select_output", {"num_branches": 2},
            {"X": [jnp.asarray([3.0])], "Mask": [jnp.asarray(0)]})
    assert float(np.asarray(o["Out"][0])[0]) == 3.0
    assert float(np.asarray(o["Out"][1])[0]) == 0.0


def test_proximal_ops(run):
    p = jnp.asarray([1.0, -1.0])
    g = jnp.asarray([0.5, 0.5])
    o = run("proximal_gd", {"l1": 0.1, "l2": 0.1},
            {"Param": [p], "Grad": [g], "LearningRate": [jnp.asarray([0.1])]})
    prox = np.asarray(p) - 0.1 * np.asarray(g)
    exp = np.sign(prox) * np.maximum(np.abs(prox) - 0.01, 0) / 1.01
    assert np.allclose(np.asarray(o["ParamOut"][0]), exp, atol=1e-6)


def test_average_accumulates_state_machine(run):
    s = jnp.zeros((3,))
    o = run("average_accumulates",
            {"average_window": 0.5, "max_average_window": 100,
             "min_average_window": 2},
            {"param": [jnp.ones((3,))], "in_sum_1": [s], "in_sum_2": [s],
             "in_sum_3": [s],
             "in_num_accumulates": [jnp.asarray([0], np.int64)],
             "in_old_num_accumulates": [jnp.asarray([0], np.int64)],
             "in_num_updates": [jnp.asarray([0], np.int64)]})
    assert np.allclose(np.asarray(o["out_sum_1"][0]), 1.0)
    assert int(np.asarray(o["out_num_updates"][0])[0]) == 1


def test_tdm_and_instag(run, rng):
    info = np.zeros((7, 5), np.int32)
    info[1] = [0, 1, 0, 2, 3]
    info[2] = [10, 2, 1, 0, 0]
    info[3] = [11, 2, 1, 0, 0]
    o = run("tdm_child", {"child_nums": 2},
            {"X": [jnp.asarray([[1], [2]])], "TreeInfo": [jnp.asarray(info)]})
    ch = np.asarray(o["Child"][0])
    assert list(ch[0].ravel()) == [2, 3] and list(ch[1].ravel()) == [0, 0]

    rows = jnp.asarray(rng.randn(3, 4).astype(np.float32))
    tags = jnp.asarray(np.array([[1, -1], [2, 3], [5, -1]], np.int64))
    o = run("filter_by_instag", {}, {
        "Ins": [rows], "Ins_tag": [tags],
        "Filter_tag": [jnp.asarray([2, 5], np.int64)],
    })
    assert list(np.asarray(o["LossWeight"][0]).ravel()) == [0.0, 1.0, 1.0]


def test_coverage_target_reached():
    """The checker itself is the acceptance test for VERDICT r2 item 1.
    Without the reference tree there is nothing to count: it skips, with
    the checker's own message."""
    import subprocess
    import sys
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "check_op_surface.py")],
        capture_output=True, text=True, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    if proc.returncode == 3:  # check_op_surface.NO_REFERENCE
        pytest.skip(proc.stderr.strip().splitlines()[-1])
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = proc.stdout
    import re

    # r4 headline splits real emitters from documented subsumptions; the
    # acceptance bar is (a) every reference op covered one way or the
    # other, (b) a real-emitter share that keeps "covered" meaningful
    m = re.search(
        r"reference fwd ops: (\d+); (\d+) with real emitters \((\d+)%\) \+ "
        r"(\d+) documented subsumptions = (\d+) covered",
        out,
    )
    assert m, out.splitlines()[0]
    total, emitters, pct, subsumed, covered = map(int, m.groups())
    assert covered == total, out.splitlines()[0]
    assert pct >= 70, out.splitlines()[0]
