"""Registry-wide numeric-gradient sweep (SURVEY §4 op-unit tier: the
reference's test mass is per-op backward-vs-central-difference checks in
tests/python/unittest/test_operator.py, ~9k lines).

Every differentiable op in the registry must either appear in SPEC below
(and pass check_numeric_gradient at float64) or be listed in EXEMPT with a
reason — test_sweep_is_complete enforces this, so newly registered ops
cannot silently skip gradient coverage. A bf16 pass checks the hot ops'
gradients stay finite and near their f32 values (round 2 shipped a bf16
conv/dot backward bug exactly this would have caught).
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.ops.registry import _OPS
from mxnet_tpu.test_utils import check_numeric_gradient

R = np.random.RandomState(7)


def _pos(*s):
    return R.uniform(0.5, 1.5, s)


def _unit(*s):
    return R.uniform(-0.8, 0.8, s)


def _any(*s):
    return R.uniform(-2.0, 2.0, s)


def _distinct(*s):
    """Values with well-separated magnitudes (kink-free for max/sort)."""
    n = int(np.prod(s))
    vals = np.linspace(0.1, 3.0, n)
    R.shuffle(vals)
    return vals.reshape(s)


def _sum_outputs(op, **kw):
    """Wrap a (possibly multi-output) op into a scalar-friendly fn."""
    def fn(*xs):
        out = op(*xs, **kw)
        if isinstance(out, (list, tuple)):
            total = out[0].sum()
            for o in out[1:]:
                total = total + o.sum()
            return total
        return out
    return fn


# op -> (input arrays, kwargs, grad_nodes or None)
SPEC = {
    # unary, full-real domain (kink-free regions where needed)
    "sin": ([_any(3, 4)], {}, None),
    "cos": ([_any(3, 4)], {}, None),
    "tan": ([_unit(3, 4)], {}, None),
    "sinh": ([_unit(3, 4)], {}, None),
    "cosh": ([_unit(3, 4)], {}, None),
    "tanh": ([_unit(3, 4)], {}, None),
    "arcsin": ([_unit(3, 4)], {}, None),
    "arccos": ([_unit(3, 4)], {}, None),
    "arctan": ([_any(3, 4)], {}, None),
    "arcsinh": ([_any(3, 4)], {}, None),
    "arccosh": ([_pos(3, 4) + 1.0], {}, None),
    "arctanh": ([_unit(3, 4) * 0.9], {}, None),
    "exp": ([_unit(3, 4)], {}, None),
    "expm1": ([_unit(3, 4)], {}, None),
    "log": ([_pos(3, 4)], {}, None),
    "log10": ([_pos(3, 4)], {}, None),
    "log2": ([_pos(3, 4)], {}, None),
    "log1p": ([_pos(3, 4)], {}, None),
    "sqrt": ([_pos(3, 4)], {}, None),
    "rsqrt": ([_pos(3, 4)], {}, None),
    "cbrt": ([_pos(3, 4)], {}, None),
    "rcbrt": ([_pos(3, 4)], {}, None),
    "reciprocal": ([_pos(3, 4)], {}, None),
    "square": ([_any(3, 4)], {}, None),
    "abs": ([_pos(3, 4)], {}, None),              # away from the kink
    "negative": ([_any(3, 4)], {}, None),
    "identity": ([_any(3, 4)], {}, None),
    "sigmoid": ([_any(3, 4)], {}, None),
    "softsign": ([_any(3, 4)], {}, None),
    "relu": ([_pos(3, 4)], {}, None),             # positive side
    "gelu": ([_any(3, 4)], {}, None),
    "hard_sigmoid": ([_unit(3, 4) * 0.4], {}, None),  # linear region
    "erf": ([_unit(3, 4)], {}, None),
    "erfinv": ([_unit(3, 4) * 0.7], {}, None),
    "gamma": ([_pos(3, 4) + 1.0], {}, None),
    "gammaln": ([_pos(3, 4) + 1.0], {}, None),
    "degrees": ([_any(3, 4)], {}, None),
    "radians": ([_any(3, 4)], {}, None),
    "smooth_l1": ([_any(3, 4)], {"scalar": 1.0}, None),
    "clip": ([_unit(3, 4) * 0.4], {"a_min": -0.9, "a_max": 0.9}, None),

    # scalar-arg binary
    "_plus_scalar": ([_any(3, 4)], {"scalar": 1.7}, None),
    "_minus_scalar": ([_any(3, 4)], {"scalar": 1.7}, None),
    "_rminus_scalar": ([_any(3, 4)], {"scalar": 1.7}, None),
    "_mul_scalar": ([_any(3, 4)], {"scalar": -2.1}, None),
    "_div_scalar": ([_any(3, 4)], {"scalar": 2.1}, None),
    "_rdiv_scalar": ([_pos(3, 4)], {"scalar": 2.1}, None),
    "_power_scalar": ([_pos(3, 4)], {"scalar": 2.5}, None),
    "_rpower_scalar": ([_unit(3, 4)], {"scalar": 2.0}, None),
    "_mod_scalar": ([_pos(3, 4) * 0.3], {"scalar": 1.0}, None),
    "_rmod_scalar": ([_pos(3, 4) + 2.0], {"scalar": 1.0}, None),
    "_hypot_scalar": ([_pos(3, 4)], {"scalar": 1.0}, None),
    "_maximum_scalar": ([_pos(3, 4) + 1.0], {"scalar": 0.5}, None),
    "_minimum_scalar": ([_pos(3, 4) + 1.0], {"scalar": 9.0}, None),

    # elemwise / broadcast binary
    "elemwise_add": ([_any(3, 4), _any(3, 4)], {}, None),
    "elemwise_sub": ([_any(3, 4), _any(3, 4)], {}, None),
    "elemwise_mul": ([_any(3, 4), _any(3, 4)], {}, None),
    "elemwise_div": ([_any(3, 4), _pos(3, 4)], {}, None),
    "_maximum": ([_pos(3, 4) + 1.0, _pos(3, 4) * 0.3], {}, None),
    "_minimum": ([_pos(3, 4) + 1.0, _pos(3, 4) * 0.3], {}, None),
    "_power": ([_pos(3, 4), _pos(3, 4)], {}, None),
    "_mod": ([_pos(3, 4) * 0.3, _pos(3, 4) + 1.0], {}, None),
    "arctan2": ([_pos(3, 4), _pos(3, 4)], {}, None),
    "broadcast_add": ([_any(3, 4), _any(1, 4)], {}, None),
    "broadcast_sub": ([_any(3, 4), _any(1, 4)], {}, None),
    "broadcast_mul": ([_any(3, 4), _any(1, 4)], {}, None),
    "broadcast_div": ([_any(3, 4), _pos(1, 4)], {}, None),
    "broadcast_power": ([_pos(3, 4), _pos(1, 4)], {}, None),
    "broadcast_maximum": ([_pos(3, 4) + 1.0, _pos(1, 4) * 0.3], {}, None),
    "broadcast_minimum": ([_pos(3, 4) + 1.0, _pos(1, 4) * 0.3], {}, None),
    "broadcast_mod": ([_pos(3, 4) * 0.3, _pos(1, 4) + 1.0], {}, None),
    "broadcast_hypot": ([_pos(3, 4), _pos(1, 4)], {}, None),

    # reductions
    "sum": ([_any(3, 4)], {"axis": 1}, None),
    "mean": ([_any(3, 4)], {"axis": 0}, None),
    "prod": ([_pos(3, 4)], {"axis": 1}, None),
    "nansum": ([_any(3, 4)], {}, None),
    "nanprod": ([_pos(3, 4)], {}, None),
    "max": ([_distinct(3, 4)], {"axis": 1}, None),
    "min": ([_distinct(3, 4)], {"axis": 1}, None),
    "logsumexp": ([_any(3, 4)], {"axis": 1}, None),
    "norm": ([_pos(3, 4)], {"ord": 2, "axis": 1}, None),
    "softmax": ([_any(3, 4)], {"axis": -1}, None),
    "softmin": ([_any(3, 4)], {"axis": -1}, None),
    "log_softmax": ([_any(3, 4)], {"axis": -1}, None),

    # shape / movement
    "reshape": ([_any(3, 4)], {"shape": (4, 3)}, None),
    "transpose": ([_any(3, 4)], {"axes": (1, 0)}, None),
    "flatten": ([_any(2, 3, 2)], {}, None),
    "expand_dims": ([_any(3, 4)], {"axis": 1}, None),
    "squeeze": ([_any(3, 1, 4)], {"axis": 1}, None),
    "flip": ([_any(3, 4)], {"axis": 1}, None),
    "tile": ([_any(2, 3)], {"reps": (2, 2)}, None),
    "repeat": ([_any(2, 3)], {"repeats": 2, "axis": 1}, None),
    "pad": ([_any(1, 1, 3, 3)],
            {"mode": "constant", "pad_width": (0, 0, 0, 0, 1, 1, 1, 1)},
            None),
    "slice": ([_any(4, 5)], {"begin": (1, 0), "end": (3, 4)}, None),
    "slice_axis": ([_any(4, 5)], {"axis": 1, "begin": 1, "end": 4}, None),
    "slice_like": ([_any(4, 5), np.zeros((2, 3))], {}, [0]),
    "broadcast_to": ([_any(1, 4)], {"shape": (3, 4)}, None),
    "broadcast_axis": ([_any(1, 4)], {"axis": 0, "size": 3}, None),
    "broadcast_like": ([_any(1, 4), np.zeros((3, 4))], {}, [0]),
    "swapaxes": ([_any(2, 3, 4)], {"dim1": 0, "dim2": 2}, None),
    "stack": ([_any(3, 4), _any(3, 4)], {"axis": 1}, None),
    "concat": ([_any(3, 2), _any(3, 3)], {"dim": 1}, None),
    "split": ([_any(3, 4)], {"num_outputs": 2, "axis": 1}, None),
    "split_v2": ([_any(3, 4)], {"indices_or_sections": 2, "axis": 1},
                 None),
    "diag": ([_any(4, 4)], {}, None),
    "where": ([np.array([[1.0, 0.0, 1.0]] * 2), _any(2, 3), _any(2, 3)],
              {}, [1, 2]),
    "sort": ([_distinct(3, 4)], {"axis": 1}, None),

    # indexing
    "take": ([_any(5, 3), np.array([0.0, 2.0, 4.0])], {"axis": 0}, [0]),
    "Embedding": ([np.array([[0.0, 2.0], [3.0, 1.0]]), _any(5, 3)],
                  {"input_dim": 5, "output_dim": 3}, [1]),
    "gather_nd": ([_any(4, 3), np.array([[0.0, 2.0], [1.0, 0.0]])],
                  {}, [0]),
    "scatter_nd": ([_any(2, 3), np.array([[0.0, 3.0]])],
                   {"shape": (5, 3)}, [0]),
    "pick": ([_any(3, 4), np.array([0.0, 2.0, 1.0])], {"axis": 1}, [0]),
    "index_add": ([_any(5, 3), np.array([1.0, 3.0]), _any(2, 3)],
                  {}, [0, 2]),
    "index_copy": ([_any(5, 3), np.array([1.0, 3.0]), _any(2, 3)],
                   {}, [0, 2]),
    "one_hot_like_ops": None,  # placeholder removed below

    # linear algebra
    "dot": ([_any(3, 4), _any(4, 2)], {}, None),
    "batch_dot": ([_any(2, 3, 4), _any(2, 4, 2)], {}, None),
    "khatri_rao": ([_any(2, 3), _any(4, 3)], {}, None),

    # NN ops
    "FullyConnected": ([_any(2, 5), _any(3, 5), _any(3)],
                       {"num_hidden": 3}, None),
    "Convolution": ([_any(1, 2, 5, 5), _any(3, 2, 3, 3), _any(3)],
                    {"kernel": (3, 3), "num_filter": 3}, None),
    "Deconvolution": ([_any(1, 3, 4, 4), _any(3, 2, 3, 3), _any(2)],
                      {"kernel": (3, 3), "num_filter": 2}, None),
    "Pooling": ([_any(1, 2, 4, 4)],
                {"kernel": (2, 2), "pool_type": "avg", "stride": (2, 2)},
                None),
    "Activation": ([_any(3, 4)], {"act_type": "softrelu"}, None),
    "LeakyReLU": ([_pos(3, 4)], {"act_type": "leaky", "slope": 0.3},
                  None),
    "LayerNorm": ([_any(3, 6), _pos(6), _any(6)], {}, None),
    "RMSNorm": ([_any(3, 6), _pos(6)], {}, None),
    "rotary_embedding": ([_any(2, 5, 8)], {"interleaved": True}, None),
    "swiglu": ([_any(3, 4), _any(3, 4)], {}, None),
    "gated_short_conv": ([_any(2, 5, 6), _any(2, 3)], {}, None),
    "causal_conv_silu": ([_any(2, 6, 3), _any(3, 4), _any(3)], {}, None),
    "GatedRMSNorm": ([_any(3, 6), _any(3, 6), _pos(6)], {}, None),
    # x, dt, A_log, B, C, D, dt_bias: two heads of 3 on one group of 4 states,
    # six tokens in chunks of 4 (a ragged last chunk)
    "ssd_scan": ([_unit(1, 6, 2, 3), _unit(1, 6, 2), _unit(2), _unit(1, 6, 1, 4),
                  _unit(1, 6, 1, 4), _unit(2), _unit(2)], {"chunk": 4}, None),
    "GroupNorm": ([_any(2, 4, 3), _pos(4), _any(4)],
                  {"num_groups": 2}, None),
    "InstanceNorm": ([_any(2, 3, 4), _pos(3), _any(3)], {}, None),
    "L2Normalization": ([_pos(3, 4)], {}, None),
    "LRN": ([_pos(1, 4, 3, 3)], {"nsize": 3}, None),
    "BatchNorm": ([_any(2, 3, 4), _pos(3), _any(3), np.zeros(3),
                   np.ones(3)],
                  {"fix_gamma": False, "use_global_stats": True},
                  [0, 1, 2]),
    "SequenceMask": ([_any(4, 2, 3), np.array([2.0, 4.0])],
                     {"use_sequence_length": True}, [0]),
    "SequenceLast": ([_any(4, 2, 3), np.array([2.0, 4.0])],
                     {"use_sequence_length": True}, [0]),
    "SequenceReverse": ([_any(4, 2, 3)], {}, None),
    "UpSampling": ([_any(1, 2, 3, 3)], {"scale": 2}, None),

    # plain fused loss (differentiable forward, label non-diff)
    "softmax_cross_entropy": ([_any(4, 5),
                               np.array([0.0, 2.0, 1.0, 4.0])], {}, [0]),
    "MakeLoss": ([_any(3, 4)], {}, None),

    # attention (the north-star hot kernel, CPU/interpret path here)
    "flash_attention": ([_unit(1, 2, 4, 8), _unit(1, 2, 4, 8),
                         _unit(1, 2, 4, 8)], {}, None),
    # the same attention fed from a fused (B, T, 3 x H x D) projection
    "flash_attention_qkv": ([_unit(1, 4, 3 * 2 * 8)], {"num_heads": 2}, None),
    # a router's float32 product, for a model whose router reads other rows
    # than its experts (moe_ffn(router_logits=...))
    "moe_router_logits": ([_any(3, 4), _any(5, 4)], {}, None),
}

# further argument sets of an op that is swept above: "<op>:<what>"
VARIANTS = {
    # a static window under causal, on grouped heads: a query sees itself and
    # the two keys before it
    "flash_attention:window": ([_unit(1, 4, 6, 8), _unit(1, 2, 6, 8),
                                _unit(1, 2, 6, 8)],
                               {"causal": True, "window": 3}, None),
}


def _spd(n, seed=3):
    m = np.random.RandomState(seed).rand(n, n)
    return m @ m.T + n * np.eye(n)


def _chol(n, seed=3):
    return np.linalg.cholesky(_spd(n, seed))


SPEC.update({
    # linalg family (ref: la_op) — SPD/triangular inputs where required
    "linalg_gemm": ([_any(3, 4), _any(4, 2), _any(3, 2)],
                    {"alpha": 1.3, "beta": 0.7}, None),
    "linalg_gemm2": ([_any(3, 4), _any(4, 2)], {"alpha": 1.3}, None),
    "linalg_potrf": ([_spd(3)], {}, None),
    "linalg_potri": ([_chol(3)], {}, None),
    "linalg_trsm": ([_chol(3) + np.eye(3), _any(3, 2)], {}, None),
    "linalg_trmm": ([_any(3, 3), _any(3, 2)], {}, None),
    "linalg_syrk": ([_any(3, 4)], {}, None),
    "linalg_makediag": ([_any(4)], {}, None),
    "linalg_extractdiag": ([_any(4, 4)], {}, None),
    "linalg_maketrian": ([_any(6)], {}, None),
    "linalg_extracttrian": ([_any(3, 3)], {}, None),
    "linalg_sumlogdiag": ([_chol(3) + np.eye(3)], {}, None),
    "linalg_det": ([_spd(3)], {}, None),
    "linalg_slogdet": ([_spd(3)], {}, [0]),
    "linalg_inverse": ([_spd(3)], {}, None),
    # round-3 extended families (matrix_op.cc block ops, ravel.cc,
    # im2col.h, moments.cc, amp_cast.cc, shrinks, vision transforms)
    "tril": ([_any(4, 4)], {}, None),
    "triu": ([_any(4, 4)], dict(k=1), None),
    "depth_to_space": ([_any(1, 8, 2, 3)], dict(block_size=2), None),
    "space_to_depth": ([_any(1, 2, 4, 6)], dict(block_size=2), None),
    "reshape_like": ([_any(2, 6), _any(3, 4)], {}, [0]),
    "batch_take": ([_distinct(3, 4),
                    np.array([1.0, 0.0, 3.0])], {}, [0]),
    "choose_element_0index": ([_distinct(3, 4),
                               np.array([1.0, 0.0, 3.0])], {}, [0]),
    "fill_element_0index": ([_any(3, 4), _any(3),
                             np.array([1.0, 0.0, 3.0])], {}, [0, 1]),
    "im2col": ([_any(1, 2, 5, 5)],
               dict(kernel=(3, 3), stride=(1, 1), pad=(1, 1)), None),
    "col2im": ([_any(1, 18, 25)],
               dict(output_size=(5, 5), kernel=(3, 3), stride=(1, 1),
                    pad=(1, 1)), None),
    "cumsum": ([_any(3, 4)], dict(axis=1), None),
    "cumprod": ([_pos(3, 4)], dict(axis=1), None),
    "moments": ([_any(3, 4)], dict(axes=(0,)), None),
    # shrinks: inputs kept away from the |x| = lambd kink
    "hardshrink": ([_pos(3, 4) + 1.0], dict(lambd=0.5), None),
    "softshrink": ([_pos(3, 4) + 1.0], dict(lambd=0.5), None),
    "digamma": ([_pos(3, 4) + 0.5], {}, None),
    "amp_cast": ([_any(3, 4)], dict(dtype="float64"), None),
    "amp_multicast": ([_any(3, 4), _any(3, 4)], {}, None),
    "GridGenerator": ([_unit(2, 6)],
                      dict(transform_type="affine",
                           target_shape=(4, 5)), None),
    # data grad through bilinear sampling is smooth away from integer
    # grid lines; theta grad flows through the affine grid
    "SpatialTransformer": ([_pos(1, 2, 6, 6), _unit(1, 6) * 0.3],
                           dict(target_shape=(5, 5)), None),
    "ROIPooling": ([_distinct(1, 2, 6, 6),
                    np.array([[0.0, 0.0, 0.0, 5.0, 5.0],
                              [0.0, 1.0, 1.0, 4.0, 4.0]])],
                   dict(pooled_size=(2, 2), spatial_scale=1.0), [0]),
    "Correlation": ([_any(1, 3, 5, 5), _any(1, 3, 5, 5)],
                    dict(kernel_size=1, max_displacement=1), None),
    # bilinear sampling is smooth away from integer grid lines; the
    # fractional roi keeps samples off them
    "ROIAlign": ([_any(1, 2, 6, 6),
                  np.array([[0.0, 0.3, 0.4, 4.6, 4.3]])],
                 dict(pooled_size=(2, 2), spatial_scale=1.0), [0]),
    # offsets bounded to [0.17, 0.33]: every bilinear sample stays well
    # clear of the integer-grid kinks, so the numeric grad is defined
    "DeformableConvolution": (
        [_any(1, 2, 5, 5), _unit(1, 18, 3, 3) * 0.1 + 0.25,
         _any(2, 2, 3, 3), _any(2)],
        dict(kernel=(3, 3)), None),
    # grid stays in [-0.12, 0.12] -> gx,gy in [2.2, 2.8]: strictly inside
    # the 6x6 map AND between integer grid lines (bilinear kink-free)
    "BilinearSampler": ([_pos(1, 2, 6, 6), _unit(1, 2, 3, 3) * 0.15],
                        {}, None),
    # spatial crop is a strided slice — gradient is a zero-padded scatter
    "Crop": ([_any(1, 2, 5, 5)], dict(h_w=(3, 3), offset=(1, 1)), None),
    # contrib family
    "fft": ([_any(3, 8)], {}, None),
    "ifft": ([_any(3, 16)], {}, None),
    "index_copy": ([_any(5, 4), np.array([0.0, 2.0]), _any(2, 4)],
                   {}, [0, 2]),
    "index_add": ([_any(5, 4), np.array([1.0, 3.0]), _any(2, 4)],
                  {}, [0, 2]),
    "count_sketch": ([_any(3, 6), np.array([0.0, 2, 1, 3, 0, 2]),
                      np.array([1.0, -1, 1, -1, 1, 1])],
                     dict(out_dim=4), [0]),
    # Kimi's delta attention (last, so that no earlier entry's draw moves): q,
    # k, v, log-decays a channel (<= 0), beta in (0, 2): two heads of 4 on
    # values of 3, six tokens in chunks of 4 (a ragged last chunk)
    "gated_delta_rule": ([_unit(1, 6, 2, 4), _unit(1, 6, 2, 4), _unit(1, 6, 2, 3),
                          -0.3 * _pos(1, 6, 2, 4), _pos(1, 6, 2)], {"chunk": 4}, None),
    "kda_log_decay": ([_any(1, 5, 6), _unit(2), _unit(6)], {}, None),
    "RMSNormSigmoidGate": ([_any(3, 6), _any(3, 6), _pos(6)], {}, None),
})
del SPEC["one_hot_like_ops"]

# ops whose internals compute in float32 regardless of input dtype (BN/LN
# cast for stability; flash accumulates at f32) — f32-ladder tolerances,
# like the reference's per-dtype tolerance ladder in check_consistency
F32_INTERNAL_TOL = {
    "BatchNorm": dict(eps=1e-2, rtol=2e-2, atol=1e-3),
    "LayerNorm": dict(eps=1e-2, rtol=2e-2, atol=1e-3),
    "flash_attention": dict(eps=1e-2, rtol=2e-2, atol=1e-3),
    "flash_attention_qkv": dict(eps=1e-2, rtol=2e-2, atol=1e-3),
    "RMSNorm": dict(eps=1e-2, rtol=2e-2, atol=1e-3),
    "rotary_embedding": dict(eps=1e-2, rtol=2e-2, atol=1e-3),
    "swiglu": dict(eps=1e-2, rtol=2e-2, atol=1e-3),
    "gated_short_conv": dict(eps=1e-2, rtol=2e-2, atol=1e-3),
    "causal_conv_silu": dict(eps=1e-2, rtol=2e-2, atol=1e-3),
    "GatedRMSNorm": dict(eps=1e-2, rtol=2e-2, atol=1e-3),
    "ssd_scan": dict(eps=1e-2, rtol=2e-2, atol=1e-3),
    "gated_delta_rule": dict(eps=1e-2, rtol=2e-2, atol=1e-3),
    "kda_log_decay": dict(eps=1e-2, rtol=2e-2, atol=1e-3),
    "RMSNormSigmoidGate": dict(eps=1e-2, rtol=2e-2, atol=1e-3),
    "moe_router_logits": dict(eps=1e-2, rtol=2e-2, atol=1e-3),
}

# differentiable in the registry but excluded from the numeric sweep,
# each with a reason
EXEMPT = {
    "moe_ffn": "top-k routing is piecewise constant and its extra outputs "
               "are integer counts, so a central difference steps over "
               "kinks; forward and every gradient are pinned against the "
               "plain float32 reference under three routings in "
               "tests/test_deepseek.py",
    "Custom": "escape hatch; needs a user-registered python op "
              "(tests/test_custom_compression.py covers fwd+bwd)",
    "RNN": "fused multi-layer recurrence; numeric grad is O(T*P^2) — "
           "covered by tests/test_gluon_rnn.py analytic checks",
    "Dropout": "stochastic in train mode, identity in test mode",
    "norm_like_cast": "dtype cast; gradient is the identity cast",
    "ones_like": "constant output, zero gradient by definition",
    "zeros_like": "constant output, zero gradient by definition",
    "CTCLoss": "integer labels break the sweep's perturb-everything "
               "harness; values AND input grads are pinned against "
               "torch.nn.functional.ctc_loss in "
               "tests/test_ctc_and_contrib_data.py",
}


def test_sweep_is_complete():
    """Every differentiable registry op is swept or explicitly exempted."""
    diff_ops = {n for n, op in _OPS.items() if op.differentiable}
    covered = set(SPEC) | set(EXEMPT) | set(LOSS_HEADS)
    missing = diff_ops - covered
    stale = covered - diff_ops
    assert not missing, "ops missing numeric-grad coverage: %s" % sorted(
        missing)
    assert not stale, "sweep entries for unregistered ops: %s" % sorted(
        stale)
    assert {v.partition(":")[0] for v in VARIANTS} <= set(SPEC)


def _op_fn(name):
    """Resolve through the registry — getattr(nd, name) can collide with
    module-internal names (e.g. '_mod' is nd's module alias)."""
    from mxnet_tpu.ops.registry import apply_op, get_op

    op = get_op(name)
    return lambda *xs, **kw: apply_op(op, *xs, **kw)


@pytest.mark.parametrize("name", sorted(SPEC) + sorted(VARIANTS))
def test_numeric_gradient(name):
    inputs, kwargs, grad_nodes = SPEC[name] if name in SPEC else VARIANTS[name]
    name = name.partition(":")[0]
    fn = _sum_outputs(_op_fn(name), **kwargs)
    tol = F32_INTERNAL_TOL.get(name,
                               dict(eps=1e-4, rtol=1e-4, atol=1e-5))
    check_numeric_gradient(
        fn, [nd.array(x.astype(np.float64)) for x in inputs],
        grad_nodes=grad_nodes, **tol)


# loss-head ops: backward IGNORES the cotangent and emits the fused loss
# gradient (reference "loss layer" semantics) — so they are checked
# against the numeric gradient of the loss they imply, not the forward's
# jacobian. num_output = size/batch mirrors regression_output-inl.h.
def _implied_linear(d, lbl):
    return 0.5 * np.sum((d - lbl) ** 2) / (d.size // d.shape[0])


def _implied_mae(d, lbl):
    return np.sum(np.abs(d - lbl)) / (d.size // d.shape[0])


def _implied_logistic(d, lbl):
    p = 1.0 / (1.0 + np.exp(-d))
    return np.sum(-lbl * np.log(p) - (1 - lbl) * np.log1p(-p)) / (
        d.size // d.shape[0])


def _implied_softmax(d, lbl):
    e = np.exp(d - d.max(-1, keepdims=True))
    p = e / e.sum(-1, keepdims=True)
    return -np.sum(np.log(p[np.arange(d.shape[0]), lbl.astype(int)]))


def _implied_svm(d, lbl):
    # L2-SVM (squared hinge), margin=1, C=1 — the SVMOutput defaults
    y = lbl.astype(int)
    total = 0.0
    for i in range(d.shape[0]):
        xy = d[i, y[i]]
        for j in range(d.shape[1]):
            if j != y[i]:
                v = max(0.0, 1.0 - (xy - d[i, j]))
                total += v * v
    return total


LOSS_HEADS = {
    "LinearRegressionOutput": (
        _any(3, 4), _any(3, 4), _implied_linear),
    "MAERegressionOutput": (
        _pos(3, 4) + 1.0, _pos(3, 4) * 0.3, _implied_mae),
    "LogisticRegressionOutput": (
        _any(3, 4), _pos(3, 4) * 0.4, _implied_logistic),
    "SoftmaxOutput": (
        _any(4, 5), np.array([0.0, 2.0, 1.0, 4.0]), _implied_softmax),
    "SVMOutput": (
        _any(4, 5), np.array([0.0, 2.0, 1.0, 4.0]), _implied_svm),
}


@pytest.mark.parametrize("name", sorted(LOSS_HEADS))
def test_loss_head_gradient(name):
    from mxnet_tpu import autograd as ag

    d_np, l_np, implied = LOSS_HEADS[name]
    d = nd.array(d_np.astype(np.float64))
    lbl = nd.array(l_np.astype(np.float64))
    d.attach_grad()
    with ag.record():
        out = _op_fn(name)(d, lbl)
        out.backward(nd.ones(out.shape, dtype="float64"))
    analytic = d.grad.asnumpy()
    eps = 1e-5
    numeric = np.zeros_like(d_np, dtype=np.float64)
    base = d_np.astype(np.float64).copy()
    for j in range(base.size):
        orig = base.flat[j]
        base.flat[j] = orig + eps
        fp = implied(base, l_np)
        base.flat[j] = orig - eps
        fm = implied(base, l_np)
        base.flat[j] = orig
        numeric.flat[j] = (fp - fm) / (2 * eps)
    np.testing.assert_allclose(analytic, numeric, rtol=1e-4, atol=1e-6)


BF16_OPS = ["dot", "batch_dot", "Convolution", "FullyConnected",
            "softmax", "LayerNorm", "flash_attention", "BatchNorm"]


@pytest.mark.parametrize("name", BF16_OPS)
def test_bf16_gradients_match_f32(name):
    """Hot ops: bf16 grads must be finite and near the f32 gradient
    (round 2's bf16 conv/dot backward bug would have failed here)."""
    from mxnet_tpu import autograd as ag

    inputs, kwargs, grad_nodes = SPEC[name]
    op = getattr(nd, name)
    grads = {}
    for dt in ("float32", "bfloat16"):
        arrs = [nd.array(x.astype(np.float32)).astype(dt) for x in inputs]
        for a in arrs:
            a.attach_grad()
        with ag.record():
            out = _sum_outputs(op, **kwargs)(*arrs)
            loss = (out * out).sum() if out.size > 1 else out
        loss.backward()
        gn = grad_nodes if grad_nodes is not None else range(len(arrs))
        grads[dt] = [arrs[i].grad.asnumpy().astype(np.float32)
                     for i in gn]
    for g32, g16 in zip(grads["float32"], grads["bfloat16"]):
        assert np.all(np.isfinite(g16))
        scale = np.abs(g32).max() + 1e-6
        assert np.abs(g32 - g16).max() / scale < 0.1, name
