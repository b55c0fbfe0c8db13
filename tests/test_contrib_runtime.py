"""Contrib ops (ref: src/operator/contrib/*) + mx.runtime feature flags
+ mx.util parity shims."""
import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import nd


def test_fft_ifft_roundtrip():
    x = np.random.RandomState(0).randn(3, 8).astype("f4")
    f = nd.fft(nd.array(x))
    assert f.shape == (3, 16)
    # interleaved (re, im) matches numpy fft
    ref = np.fft.fft(x, axis=-1)
    got = f.asnumpy().reshape(3, 8, 2)
    np.testing.assert_allclose(got[..., 0], ref.real, atol=1e-4)
    np.testing.assert_allclose(got[..., 1], ref.imag, atol=1e-4)
    # reference ifft is unnormalized: ifft(fft(x)) == n * x
    back = nd.ifft(f).asnumpy()
    np.testing.assert_allclose(back, 8 * x, rtol=1e-4, atol=1e-4)


def test_index_copy_add():
    old = nd.zeros((4, 3))
    new = nd.array(np.ones((2, 3), "f4"))
    idx = nd.array(np.array([1.0, 3.0], "f4"))
    out = nd.index_copy(old, idx, new).asnumpy()
    assert out[1].sum() == 3 and out[3].sum() == 3 and out[0].sum() == 0
    out2 = nd.index_add(nd.array(out), idx, new).asnumpy()
    assert out2[1].sum() == 6


def test_count_sketch():
    x = np.array([[1.0, 2.0, 3.0]], dtype="f4")
    h = nd.array(np.array([0.0, 1.0, 0.0], "f4"))
    s = nd.array(np.array([1.0, -1.0, 1.0], "f4"))
    out = nd.count_sketch(nd.array(x), h, s, out_dim=2).asnumpy()
    np.testing.assert_allclose(out, [[4.0, -2.0]])


def test_boolean_mask():
    x = nd.array(np.arange(12, dtype="f4").reshape(4, 3))
    m = nd.array(np.array([1.0, 0.0, 1.0, 0.0], "f4"))
    out = nd.boolean_mask(x, m).asnumpy()
    np.testing.assert_array_equal(out, x.asnumpy()[[0, 2]])


def test_multibox_prior():
    data = nd.zeros((1, 16, 4, 4))
    anchors = nd.MultiBoxPrior(data, sizes=(0.5, 0.25), ratios=(1.0, 2.0))
    # A = len(sizes) + len(ratios) - 1 = 3 anchors per pixel
    assert anchors.shape == (1, 4 * 4 * 3, 4)
    a = anchors.asnumpy()[0]
    # first anchor at first pixel: size .5, ratio 1 centered at (1/8, 1/8)
    np.testing.assert_allclose(a[0], [0.125 - 0.25, 0.125 - 0.25,
                                      0.125 + 0.25, 0.125 + 0.25],
                               atol=1e-6)
    # reference enumeration order: sizes-first with ratios[0], then
    # remaining ratios with sizes[0] — anchor 1 is size .25/ratio 1,
    # anchor 2 is size .5/ratio 2
    np.testing.assert_allclose(a[1, 2] - a[1, 0], 0.25, atol=1e-6)
    np.testing.assert_allclose(a[2, 2] - a[2, 0], 0.5 * np.sqrt(2),
                               atol=1e-6)
    np.testing.assert_allclose(a[2, 3] - a[2, 1], 0.5 / np.sqrt(2),
                               atol=1e-6)
    # widths/heights positive, centers inside the unit square
    assert np.all(a[:, 2] > a[:, 0]) and np.all(a[:, 3] > a[:, 1])


def test_runtime_features():
    feats = mx.runtime.Features()
    assert feats.is_enabled("CPU")
    assert not feats.is_enabled("CUDA")
    assert "NATIVE_RECORDIO" in feats
    # flash-attention probe must agree with the op's own dispatch gate
    assert feats.is_enabled("FLASH_ATTENTION") == mx.context.on_tpu()
    lst = mx.runtime.feature_list()
    assert any(f.name == "TPU" for f in lst)


def test_util_shims():
    assert mx.util.is_np_shape() and mx.util.is_np_array()
    with mx.util.np_shape():
        pass

    @mx.util.use_np
    def f(x):
        return x + 1

    assert f(1) == 2
    import pytest

    with pytest.raises(RuntimeError):
        mx.util.get_cuda_compute_capability()


def test_multibox_target_matching_and_encoding():
    anchors = nd.array(np.array([[[0.1, 0.1, 0.5, 0.5],
                                  [0.6, 0.6, 0.9, 0.9]]], "f4"))
    label = nd.array(np.array([[[2, 0.1, 0.1, 0.5, 0.5],
                                [-1, 0, 0, 0, 0]]], "f4"))
    lt, lm, ct = nd.MultiBoxTarget(anchors, label, nd.zeros((1, 4, 2)))
    ct = ct.asnumpy()
    assert ct[0, 0] == 3  # class 2 -> target 2+1
    assert ct[0, 1] == 0  # unmatched -> background
    # exact-overlap anchor encodes ~zero offsets, mask covers only it
    np.testing.assert_allclose(lt.asnumpy()[0, :4], 0, atol=1e-5)
    np.testing.assert_allclose(lm.asnumpy()[0], [1, 1, 1, 1, 0, 0, 0, 0])


def test_multibox_target_force_matches_best_anchor():
    # gt overlaps anchor0 only weakly (< threshold) but must still get
    # its best anchor force-matched — INCLUDING when a cls=-1 padding
    # row is present (its meaningless argmax must not clobber the match)
    anchors = nd.array(np.array([[[0.0, 0.0, 0.4, 0.4],
                                  [0.6, 0.6, 1.0, 1.0]]], "f4"))
    for rows in ([[1, 0.3, 0.3, 0.7, 0.7]],
                 [[1, 0.3, 0.3, 0.7, 0.7], [-1, 0, 0, 0, 0]]):
        label = nd.array(np.array([rows], "f4"))
        _, _, ct = nd.MultiBoxTarget(anchors, label,
                                     nd.zeros((1, 3, 2)),
                                     overlap_threshold=0.9)
        assert (ct.asnumpy()[0] > 0).sum() == 1, rows


def test_multibox_detection_decode_and_nms():
    anchors = nd.array(np.array([[[0.1, 0.1, 0.5, 0.5],
                                  [0.1, 0.1, 0.5, 0.5],
                                  [0.6, 0.6, 0.9, 0.9]]], "f4"))
    # two identical anchors with same class: NMS keeps the higher score
    probs = nd.array(np.array([[[0.1, 0.2, 0.8],
                                [0.9, 0.7, 0.1],
                                [0.0, 0.1, 0.1]]], "f4"))
    det = nd.MultiBoxDetection(probs, nd.zeros((1, 12)), anchors,
                               nms_threshold=0.5).asnumpy()
    assert det.shape == (1, 3, 6)
    # rows are score-sorted: winner, the distant low-score box, then the
    # NMS-suppressed duplicate (-1) last
    r0, r1, r2 = det[0]
    assert r0[0] == 0 and abs(r0[1] - 0.9) < 1e-6
    assert r1[1] <= 0.2 and r1[0] >= 0
    assert r2[0] == -1
    # decoded boxes equal anchors for zero offsets
    np.testing.assert_allclose(r0[2:], [0.1, 0.1, 0.5, 0.5], atol=1e-5)


def test_multibox_detection_offset_decode():
    anchors = nd.array(np.array([[[0.2, 0.2, 0.6, 0.6]]], "f4"))
    probs = nd.array(np.array([[[0.1], [0.9]]], "f4"))
    # shift center by +0.1 in x: t_x = 0.1 / (0.1 variance * w 0.4) = 2.5
    loc = nd.array(np.array([[2.5, 0, 0, 0]], "f4"))
    det = nd.MultiBoxDetection(probs, loc, anchors).asnumpy()
    np.testing.assert_allclose(det[0, 0, 2:], [0.3, 0.2, 0.7, 0.6],
                               atol=1e-5)


def test_multibox_detection_nms_topk_caps_output():
    anchors = nd.array(np.array([[[0.1, 0.1, 0.3, 0.3],
                                  [0.6, 0.6, 0.9, 0.9]]], "f4"))
    probs = nd.array(np.array([[[0.1, 0.2], [0.9, 0.8]]], "f4"))
    det = nd.MultiBoxDetection(probs, nd.zeros((1, 8)), anchors,
                               nms_topk=1).asnumpy()
    assert abs(det[0, 0, 1] - 0.9) < 1e-6
    assert det[0, 1, 0] == -1  # beyond top-k invalidated


def test_proposal_shapes_and_validity():
    rng = np.random.RandomState(0)
    B, A, H, W = 2, 6, 4, 4
    cls = nd.array(rng.uniform(0, 1, (B, 2 * A, H, W)).astype("f4"))
    bbox = nd.array((rng.randn(B, 4 * A, H, W) * 0.1).astype("f4"))
    im_info = nd.array(np.array([[64, 64, 1.0], [64, 64, 1.0]], "f4"))
    rois, scores = nd.Proposal(
        cls, bbox, im_info, scales=(2, 4), ratios=(0.5, 1, 2),
        feature_stride=16, rpn_pre_nms_top_n=50, rpn_post_nms_top_n=8,
        rpn_min_size=4, output_score=True)
    r = rois.asnumpy()
    assert r.shape == (16, 5)
    np.testing.assert_array_equal(r[:8, 0], 0)
    np.testing.assert_array_equal(r[8:, 0], 1)
    assert (r[:, 1:3] >= 0).all() and (r[:, 3:] <= 63).all()
    assert (r[:, 3] >= r[:, 1]).all() and (r[:, 4] >= r[:, 2]).all()
    s0 = scores.asnumpy()[:8, 0]
    assert np.isfinite(s0).all()
    assert abs(s0.max() - s0[0]) < 1e-6  # best survivor leads


def test_proposal_nms_suppresses_duplicates():
    # one dominant location: high fg score everywhere forces NMS to thin
    B, A, H, W = 1, 1, 2, 2
    cls = np.zeros((B, 2, H, W), "f4")
    cls[0, 1] = 0.9  # all fg
    bbox = np.zeros((B, 4, H, W), "f4")
    im_info = nd.array(np.array([[32, 32, 1.0]], "f4"))
    rois = nd.Proposal(nd.array(cls), nd.array(bbox), im_info,
                       scales=(2,), ratios=(1.0,), feature_stride=16,
                       rpn_pre_nms_top_n=4, rpn_post_nms_top_n=4,
                       threshold=0.3, rpn_min_size=1).asnumpy()
    # 4 anchors at stride-16 cells of a 32px image, heavily overlapping
    # after clipping -> NMS keeps fewer distinct boxes; padding repeats
    # the top row, so all rows must be among the survivors
    uniq = np.unique(rois[:, 1:], axis=0)
    assert len(uniq) <= 3


def test_proposal_symbolic_two_outputs():
    import mxnet_tpu as mxx

    cls = mxx.sym.Variable("cls")
    bbox = mxx.sym.Variable("bbox")
    info = mxx.sym.Variable("info")
    p = mxx.sym.Proposal(cls, bbox, info, scales=(2,), ratios=(1.0,),
                         output_score=True)
    assert len(p.list_outputs()) == 2


def test_box_iou_and_nms():
    a = nd.array(np.array([[0, 0, 2, 2], [1, 1, 3, 3]], "f4"))
    b = nd.array(np.array([[0, 0, 2, 2]], "f4"))
    iou = nd.box_iou(a, b).asnumpy()
    np.testing.assert_allclose(iou[:, 0], [1.0, 1.0 / 7.0], atol=1e-5)
    # center format agrees with corner format
    ac = nd.array(np.array([[1, 1, 2, 2], [2, 2, 2, 2]], "f4"))
    bc = nd.array(np.array([[1, 1, 2, 2]], "f4"))
    iou_c = nd.box_iou(ac, bc, format="center").asnumpy()
    np.testing.assert_allclose(iou_c[:, 0], iou[:, 0], atol=1e-5)

    rows = np.array([[[0, 0.9, 0, 0, 2, 2],
                      [0, 0.8, 0.1, 0.1, 2, 2],
                      [1, 0.7, 5, 5, 6, 6],
                      [0, -1.0, 0, 0, 1, 1]]], "f4")
    out = nd.box_nms(nd.array(rows), overlap_thresh=0.5,
                     valid_thresh=0.0, id_index=0).asnumpy()
    # score-sorted survivors; the overlapping same-class duplicate and
    # the below-valid_thresh row are fully -1
    assert abs(out[0, 0, 1] - 0.9) < 1e-6
    assert abs(out[0, 1, 1] - 0.7) < 1e-6
    assert (out[0, 2] == -1).all() and (out[0, 3] == -1).all()
    # id_index + force_suppress=False: different class ids never
    # suppress each other even with full overlap
    rows2 = np.array([[[0, 0.9, 0, 0, 2, 2],
                       [1, 0.8, 0, 0, 2, 2]]], "f4")
    out2 = nd.box_nms(nd.array(rows2), id_index=0).asnumpy()
    assert (out2[0, :, 1] > 0).all()
    out3 = nd.box_nms(nd.array(rows2), id_index=0,
                      force_suppress=True).asnumpy()
    assert (out3[0, 1] == -1).all()


def test_proposal_reference_anchor_enumeration():
    """First anchor must equal py-faster-rcnn generate_anchors()[0] for
    base 16, ratio 0.5, scale 8: (-84, -40, 99, 55) at cell (0, 0)."""
    B, H, W = 1, 1, 1
    A = 1
    cls = np.zeros((B, 2 * A, H, W), "f4")
    cls[0, 1] = 1.0
    bbox = np.zeros((B, 4 * A, H, W), "f4")
    info = nd.array(np.array([[1000, 1000, 1.0]], "f4"))
    rois = nd.Proposal(nd.array(cls), nd.array(bbox), info,
                       scales=(8,), ratios=(0.5,), feature_stride=16,
                       rpn_pre_nms_top_n=1, rpn_post_nms_top_n=1,
                       rpn_min_size=0).asnumpy()
    # clipped to the (large) image, so the raw anchor passes through
    np.testing.assert_allclose(rois[0, 1:], [0, 0, 99, 55], atol=1e-4)
    # unclipped extents visible with an offset cell: anchor at cell (1,1)
    cls2 = np.zeros((1, 2, 2, 2), "f4"); cls2[0, 1, 1, 1] = 1.0
    bbox2 = np.zeros((1, 4, 2, 2), "f4")
    rois2 = nd.Proposal(nd.array(cls2), nd.array(bbox2), info,
                        scales=(8,), ratios=(0.5,), feature_stride=16,
                        rpn_pre_nms_top_n=1, rpn_post_nms_top_n=1,
                        rpn_min_size=0).asnumpy()
    # negative extents clip to the image (reference clips proposals too)
    np.testing.assert_allclose(rois2[0, 1:],
                               [0, 0, 99 + 16, 55 + 16], atol=1e-4)


def test_roi_align_bilinear_average():
    x = nd.array(np.full((1, 2, 8, 8), 3.0, "f4"))
    rois = nd.array(np.array([[0, 0, 0, 7, 7]], "f4"))
    out = nd.ROIAlign(x, rois, pooled_size=(2, 2))
    assert out.shape == (1, 2, 2, 2)
    np.testing.assert_allclose(out.asnumpy(), 3.0, atol=1e-5)
    # ramp: left bin average < right bin average, exact for 2-sample bins
    ramp = np.tile(np.arange(8, dtype="f4")[None, None, None, :],
                   (1, 1, 8, 1))
    o = nd.ROIAlign(nd.array(ramp), rois, pooled_size=(1, 2)).asnumpy()
    np.testing.assert_allclose(o[0, 0, 0], [1.75, 5.25], atol=1e-5)


def test_box_nms_topk_beyond_survives_unless_suppressed():
    # 3 disjoint boxes, topk=2: reference keeps all 3 (beyond-topk boxes
    # cannot suppress but do survive)
    rows = np.array([[[0, 0.9, 0, 0, 1, 1],
                      [0, 0.8, 2, 2, 3, 3],
                      [0, 0.7, 5, 5, 6, 6]]], "f4")
    out = nd.box_nms(nd.array(rows), topk=2, id_index=0).asnumpy()
    assert (out[0, :, 1] > 0).all()


def test_roi_align_out_of_image_samples_are_zero():
    x = nd.array(np.full((1, 1, 8, 8), 3.0, "f4"))
    rois = nd.array(np.array([[0, -20, -20, 7, 7]], "f4"))
    out = nd.ROIAlign(x, rois, pooled_size=(2, 2)).asnumpy()
    # top-left bin samples entirely outside the map -> 0; bottom-right
    # bin has 1 of its 4 samples inside (at 3.0) -> 0.75 exactly
    assert out[0, 0, 0, 0] < 1e-5
    np.testing.assert_allclose(out[0, 0, 1, 1], 0.75, atol=1e-5)


def test_deformable_convolution_zero_offsets_match_conv():
    rng = np.random.RandomState(0)
    x = rng.randn(2, 4, 9, 9).astype("f4")
    w = rng.randn(6, 2, 3, 3).astype("f4")
    off = np.zeros((2, 18, 5, 5), "f4")
    od = nd.DeformableConvolution(
        nd.array(x), nd.array(off), nd.array(w), None, kernel=(3, 3),
        stride=(2, 2), pad=(1, 1), num_group=2, no_bias=True).asnumpy()
    ref = nd.Convolution(nd.array(x), nd.array(w), None, kernel=(3, 3),
                         stride=(2, 2), pad=(1, 1), num_group=2,
                         no_bias=True).asnumpy()
    np.testing.assert_allclose(od, ref, atol=1e-4)


def test_deformable_convolution_integer_shift():
    rng = np.random.RandomState(1)
    x = rng.randn(1, 1, 6, 6).astype("f4")
    w = np.zeros((1, 1, 3, 3), "f4")
    w[0, 0, 0, 0] = 1.0  # kernel picks only tap (0, 0)
    off = np.ones((1, 18, 4, 4), "f4")  # every tap shifts (+1, +1)
    o = nd.DeformableConvolution(nd.array(x), nd.array(off), nd.array(w),
                                 None, kernel=(3, 3),
                                 no_bias=True).asnumpy()
    np.testing.assert_allclose(o[0, 0], x[0, 0][1:5, 1:5], atol=1e-5)


def test_deformable_convolution_fractional_offset_interpolates():
    # half-pixel x-shift averages horizontal neighbors
    x = np.zeros((1, 1, 4, 4), "f4")
    x[0, 0, 1, 1] = 2.0
    x[0, 0, 1, 2] = 4.0
    w = np.ones((1, 1, 1, 1), "f4")
    off = np.zeros((1, 2, 4, 4), "f4")
    off[0, 1] = 0.5  # dx = +0.5
    o = nd.DeformableConvolution(nd.array(x), nd.array(off), nd.array(w),
                                 None, kernel=(1, 1),
                                 no_bias=True).asnumpy()
    np.testing.assert_allclose(o[0, 0, 1, 1], 3.0, atol=1e-5)


def test_multibox_target_hard_negative_mining():
    # 1 gt matching anchor0; 4 pure negatives with distinct "hardness"
    # (hottest non-background score). ratio=2 -> 2 mined negatives stay
    # background (the 2 hottest), the rest become ignore_label.
    anchors = nd.array(np.array([[[0.1, 0.1, 0.5, 0.5],
                                  [0.52, 0.52, 0.6, 0.6],
                                  [0.62, 0.62, 0.7, 0.7],
                                  [0.72, 0.72, 0.8, 0.8],
                                  [0.82, 0.82, 0.9, 0.9]]], "f4"))
    label = nd.array(np.array([[[0, 0.1, 0.1, 0.5, 0.5]]], "f4"))
    # scores: (B, C=2, A=5); non-background row ranks neg hardness
    hard = np.array([[[0, 0, 0, 0, 0],
                      [0.0, 0.9, 0.1, 0.8, 0.2]]], "f4")
    _, _, ct = nd.MultiBoxTarget(anchors, label, nd.array(hard),
                                 negative_mining_ratio=2.0,
                                 negative_mining_thresh=0.5,
                                 ignore_label=-1.0)
    ct = ct.asnumpy()[0]
    assert ct[0] == 1  # matched -> class 0 + 1
    assert ct[1] == 0 and ct[3] == 0  # two hottest negatives kept
    assert ct[2] == -1 and ct[4] == -1  # mined out
    # without mining every negative trains as background
    _, _, ct0 = nd.MultiBoxTarget(anchors, label, nd.array(hard))
    assert (ct0.asnumpy()[0][1:] == 0).all()


def test_multibox_target_minimum_negative_samples():
    anchors = nd.array(np.array([[[0.1, 0.1, 0.5, 0.5],
                                  [0.52, 0.52, 0.6, 0.6],
                                  [0.62, 0.62, 0.7, 0.7]]], "f4"))
    # no gt at all -> num_pos 0 -> ratio alone keeps 0 negatives, so
    # minimum_negative_samples must floor it
    label = nd.array(np.array([[[-1, 0, 0, 0, 0]]], "f4"))
    hard = np.array([[[0, 0, 0], [0.3, 0.9, 0.1]]], "f4")
    _, _, ct = nd.MultiBoxTarget(anchors, label, nd.array(hard),
                                 negative_mining_ratio=3.0,
                                 minimum_negative_samples=1)
    ct = ct.asnumpy()[0]
    assert (ct == 0).sum() == 1 and ct[1] == 0  # the hottest one
    assert (ct == -1).sum() == 2


def test_roi_align_adaptive_sample_count():
    # big square ROI: bin size 3 -> adaptive picks ceil(6/2)=3 samples
    # per axis, identical to forcing sample_ratio=3
    rng = np.random.RandomState(0)
    x = nd.array(rng.uniform(-1, 1, (1, 3, 12, 12)).astype("f4"))
    rois = nd.array(np.array([[0, 2, 2, 8, 8]], "f4"))
    auto = nd.ROIAlign(x, rois, pooled_size=(2, 2)).asnumpy()
    forced = nd.ROIAlign(x, rois, pooled_size=(2, 2),
                         sample_ratio=3).asnumpy()
    np.testing.assert_allclose(auto, forced, atol=1e-6)
    # tiny ROI (smaller than the pooled grid): adaptive -> 1 sample/axis
    tiny = nd.array(np.array([[0, 3, 3, 4, 4]], "f4"))
    auto_t = nd.ROIAlign(x, tiny, pooled_size=(2, 2)).asnumpy()
    forced_t = nd.ROIAlign(x, tiny, pooled_size=(2, 2),
                           sample_ratio=1).asnumpy()
    np.testing.assert_allclose(auto_t, forced_t, atol=1e-6)
