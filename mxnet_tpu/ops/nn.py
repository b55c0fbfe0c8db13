"""Neural-net ops — the reference's hot kernels, rebuilt on XLA
(ref: src/operator/nn/*: convolution, fully_connected, batch_norm, pooling,
softmax, dropout, layer_norm; cuDNN paths become lax.conv_general_dilated /
dot_general / reduce_window, which XLA tiles onto the MXU/VPU).

Layout note: the reference defaults to NCHW. All ops accept ``layout`` and
the model zoo uses NHWC on TPU (better MXU tiling); NCHW stays the API
default for parity.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .registry import register
from .. import random as _random


@register("FullyConnected", aliases=("fully_connected",))
def fully_connected(data, weight, bias=None, num_hidden=None, no_bias=False,
                    flatten=True):
    """ref: src/operator/nn/fully_connected.cc. weight is (num_hidden, in)."""
    del num_hidden
    x = data
    if flatten and x.ndim > 2:
        x = x.reshape(x.shape[0], -1)
    # no preferred_element_type: the TPU MXU already accumulates bf16
    # operands in f32, and requesting an f32 output breaks the conv/dot
    # transpose rule in backward (dtype-mismatched cotangent)
    out = jax.lax.dot_general(
        x, weight,
        dimension_numbers=(((x.ndim - 1,), (1,)), ((), ())),
    )
    if not no_bias and bias is not None:
        out = out + bias.astype(out.dtype)
    return out


def _conv_dn(layout, nd):
    if layout in (None, "NCHW", "NCW", "NCDHW"):
        lhs = "NC" + "DHW"[3 - nd:]
        out = lhs
    elif layout in ("NHWC", "NWC", "NDHWC"):
        lhs = "N" + "DHW"[3 - nd:] + "C"
        out = lhs
    else:
        raise ValueError("unsupported layout %r" % (layout,))
    rhs = "OI" + "DHW"[3 - nd:]
    return (lhs, rhs, out)


@register("Convolution", aliases=("convolution",))
def convolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                pad=(), num_filter=0, num_group=1, no_bias=False,
                layout=None, workspace=None, cudnn_tune=None, cudnn_off=None):
    """ref: src/operator/nn/convolution.cc (+cudnn path). Weight logical
    layout is OIHW regardless of data layout, matching the reference."""
    del num_filter, workspace, cudnn_tune, cudnn_off
    nd = len(kernel)
    stride = tuple(stride) if stride else (1,) * nd
    dilate = tuple(dilate) if dilate else (1,) * nd
    pad = tuple(pad) if pad else (0,) * nd
    dn = _conv_dn(layout, nd)
    out = jax.lax.conv_general_dilated(
        data, weight,
        window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate,
        dimension_numbers=dn,
        feature_group_count=num_group,
    )
    if not no_bias and bias is not None:
        c_ax = dn[2].index("C")
        shape = [1] * out.ndim
        shape[c_ax] = bias.shape[0]
        out = out + bias.reshape(shape).astype(out.dtype)
    return out


@register("Deconvolution")
def deconvolution(data, weight, bias=None, kernel=(), stride=(), dilate=(),
                  pad=(), adj=(), num_filter=0, num_group=1, no_bias=False,
                  layout=None, target_shape=None, workspace=None):
    """ref: src/operator/nn/deconvolution.cc — transposed conv. weight is
    (in_ch, out_ch/group, kH, kW) in the reference; we honor that."""
    del num_filter, target_shape, workspace
    nd = len(kernel)
    stride = tuple(stride) if stride else (1,) * nd
    dilate = tuple(dilate) if dilate else (1,) * nd
    pad = tuple(pad) if pad else (0,) * nd
    adj = tuple(adj) if adj else (0,) * nd
    dn = _conv_dn(layout, nd)
    # transposed conv = lhs-dilated conv with flipped kernel, IO swapped
    kern = jnp.swapaxes(weight, 0, 1)
    kern = jnp.flip(kern, axis=tuple(range(2, 2 + nd)))
    pads = [
        (dilate[i] * (kernel[i] - 1) - pad[i],
         dilate[i] * (kernel[i] - 1) - pad[i] + adj[i])
        for i in range(nd)
    ]
    if num_group != 1:
        raise NotImplementedError("grouped deconvolution not yet supported")
    out = jax.lax.conv_general_dilated(
        data, kern,
        window_strides=(1,) * nd,
        padding=pads,
        lhs_dilation=stride,
        rhs_dilation=dilate,
        dimension_numbers=dn,
    )
    if not no_bias and bias is not None:
        c_ax = dn[2].index("C")
        shape = [1] * out.ndim
        shape[c_ax] = bias.shape[0]
        out = out + bias.reshape(shape)
    return out


@register("Activation", aliases=("activation",))
def activation_op(data, act_type="relu"):
    if act_type == "relu":
        return jnp.maximum(data, 0)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return jax.nn.soft_sign(data)
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "gelu_tanh":
        return jax.nn.gelu(data, approximate=True)
    if act_type == "silu" or act_type == "swish":
        return jax.nn.silu(data)
    raise ValueError("unknown act_type %r" % (act_type,))


@register("LeakyReLU")
def leaky_relu(data, gamma=None, act_type="leaky", slope=0.25,
               lower_bound=0.125, upper_bound=0.334):
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * jnp.expm1(data))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(data >= 0, data, alpha * jnp.expm1(data))
    if act_type == "prelu":
        g = gamma
        shape = [1] * data.ndim
        if g.ndim == 1 and data.ndim > 1:
            shape[1] = g.shape[0]
            g = g.reshape(shape)
        return jnp.where(data >= 0, data, g * data)
    if act_type == "rrelu":
        mid = (lower_bound + upper_bound) / 2.0
        return jnp.where(data >= 0, data, mid * data)
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    raise ValueError("unknown act_type %r" % (act_type,))


@register("softmax")
def softmax(data, axis=-1, temperature=None, length=None):
    x = data
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    if length is not None:
        # mask positions >= length along `axis` (reference masked softmax)
        idx = jnp.arange(x.shape[axis])
        shape = [1] * x.ndim
        shape[axis] = x.shape[axis]
        mask = idx.reshape(shape) < length.reshape(
            length.shape + (1,) * (x.ndim - length.ndim)
        )
        x = jnp.where(mask, x, -jnp.inf)
        out = jax.nn.softmax(x, axis=axis)
        return jnp.where(mask, out, 0.0)
    return jax.nn.softmax(x, axis=axis)


@register("log_softmax")
def log_softmax(data, axis=-1, temperature=None):
    x = data
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    return jax.nn.log_softmax(x, axis=axis)


@register("softmin")
def softmin(data, axis=-1):
    return jax.nn.softmax(-data, axis=axis)


@register("softmax_cross_entropy")
def softmax_cross_entropy(data, label):
    logp = jax.nn.log_softmax(data, axis=-1)
    lbl = label.astype(jnp.int32)
    picked = jnp.take_along_axis(logp, lbl[:, None], axis=-1)
    return -jnp.sum(picked)


def _softmax_output_fwd(data, label, grad_scale, ignore_label, use_ignore,
                        multi_output, normalization):
    out = jax.nn.softmax(data, axis=-1 if not multi_output else 1)
    return out, (out, label)


def _softmax_output_bwd(grad_scale, ignore_label, use_ignore, multi_output,
                        normalization, res, ct):
    out, label = res
    ax = 1 if multi_output else -1
    lbl = label.astype(jnp.int32)
    oh = jax.nn.one_hot(lbl, out.shape[ax], dtype=out.dtype, axis=ax)
    g = out - oh
    if use_ignore:
        keep = (lbl != int(ignore_label)).astype(out.dtype)
        g = g * jnp.expand_dims(keep, ax)
    scale = grad_scale
    if normalization == "batch":
        scale = scale / out.shape[0]
    elif normalization == "valid" and use_ignore:
        scale = scale / jnp.maximum((lbl != int(ignore_label)).sum(), 1)
    g = g * scale
    return (g, jnp.zeros_like(label))


_softmax_output_core = jax.custom_vjp(
    lambda data, label, grad_scale, ignore_label, use_ignore, multi_output,
    normalization: _softmax_output_fwd(
        data, label, grad_scale, ignore_label, use_ignore, multi_output,
        normalization)[0],
    nondiff_argnums=(2, 3, 4, 5, 6),
)
_softmax_output_core.defvjp(_softmax_output_fwd, _softmax_output_bwd)


@register("SoftmaxOutput", aliases=("Softmax",))
def softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                   use_ignore=False, multi_output=False, preserve_shape=False,
                   normalization="null", out_grad=False, smooth_alpha=0.0):
    """Legacy fused softmax+CE-grad op (ref: src/operator/softmax_output.cc):
    forward is softmax; backward emits (p - onehot(label)) * grad_scale
    regardless of incoming cotangent — reproduced with jax.custom_vjp."""
    del preserve_shape, out_grad, smooth_alpha
    return _softmax_output_core(
        data, label, float(grad_scale), float(ignore_label), bool(use_ignore),
        bool(multi_output), str(normalization)
    )


@register("Dropout")
def dropout(data, p=0.5, mode="training", axes=(), train_mode=False):
    """ref: src/operator/nn/dropout.cc. ``train_mode`` comes from the
    caller (gluon layers) or is injected from the autograd context by
    the eager/executor dispatch (registry.apply_op — the reference's
    ctx.is_train)."""
    if p <= 0 or (not train_mode and mode != "always"):
        return data
    shape = list(data.shape)
    for ax in axes:
        shape[ax] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(_random.new_key(), keep, tuple(shape))
    return jnp.where(mask, data / keep, 0.0).astype(data.dtype)


# --------------------------------------------------------------------------
# normalization
# --------------------------------------------------------------------------
def _bn_stats(x32, red):
    """Batch mean/var via the centered two-pass form. NOT E[x^2]-E[x]^2:
    that cancels catastrophically for |mean|/std >~ 1e3 (raw un-centered
    features straight into BN), clamping var to 0 and scaling outputs by
    rsqrt(eps). The second pass fuses with the normalize pass anyway."""
    mean = jnp.mean(x32, axis=red)
    shape = [1] * x32.ndim
    for i in range(x32.ndim):
        if i not in red:
            shape[i] = x32.shape[i]
    d = x32 - mean.reshape(shape)
    var = jnp.mean(d * d, axis=red)
    return mean, var


@jax.named_scope("batchnorm")
def _bn_core_fwd(eps, red, x, g, b):
    x32 = x.astype(jnp.float32)
    mean, var = _bn_stats(x32, red)
    inv = jax.lax.rsqrt(var + eps)
    shape = [1] * x.ndim
    ax = [i for i in range(x.ndim) if i not in red][0]
    shape[ax] = x.shape[ax]
    out = (x32 - mean.reshape(shape)) * (
        inv * g.astype(jnp.float32)).reshape(shape) \
        + b.astype(jnp.float32).reshape(shape)
    # residuals are the bf16 input + per-channel stats — backward
    # recomputes x32/xhat on the fly, so no f32 activation tensor is ever
    # written to HBM (the main BN traffic saving vs autodiff)
    try:
        from .. import tuning

        tuning.record_signature("batch_norm", {
            "x_shape": list(x.shape), "dtype": str(x.dtype),
            "g_shape": list(g.shape), "g_dtype": str(g.dtype),
            "eps": float(eps), "red": list(red)})
    except Exception:  # noqa: BLE001 — bookkeeping must not fail the op
        pass
    return (out.astype(x.dtype), mean, var), (x, g, mean, inv)


@jax.named_scope("batchnorm_bwd")
def _bn_core_bwd(eps, red, res, cts):
    x, g, mean, inv = res
    ct_out = cts[0]  # mean/var outputs feed stop_gradient paths only
    ax = [i for i in range(x.ndim) if i not in red][0]
    shape = [1] * x.ndim
    shape[ax] = x.shape[ax]
    n = 1
    for i in red:
        n *= x.shape[i]
    dy = ct_out.astype(jnp.float32)
    xhat = (x.astype(jnp.float32) - mean.reshape(shape)) * inv.reshape(shape)
    db = jnp.sum(dy, axis=red)
    dg = jnp.sum(dy * xhat, axis=red)
    dx = (g.astype(jnp.float32) * inv).reshape(shape) * (
        dy - (db / n).reshape(shape) - xhat * (dg / n).reshape(shape))
    return dx.astype(x.dtype), dg.astype(g.dtype), db.astype(g.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _bn_core(eps, red, x, g, b):
    return _bn_core_fwd(eps, red, x, g, b)[0]


_bn_core.defvjp(_bn_core_fwd, _bn_core_bwd)


@register("BatchNorm", aliases=("batch_norm", "BatchNorm_v1"), num_outputs=3)
def batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-5,
               momentum=0.9, fix_gamma=True, use_global_stats=False,
               output_mean_var=False, axis=1, cudnn_off=False,
               train_mode=False):
    """ref: src/operator/nn/batch_norm.cc. Returns (out, mean, var); in
    training mode mean/var are the *updated running stats* the layer writes
    back (the reference mutates aux states in-place inside the kernel).
    Train-mode normalize+stats is a custom-VJP kernel: single-pass f32
    stats, bf16-only residuals (backward recomputes x_hat)."""
    del output_mean_var, cudnn_off
    ax = axis % data.ndim
    red = tuple(i for i in range(data.ndim) if i != ax)
    shape = [1] * data.ndim
    shape[ax] = data.shape[ax]
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    if train_mode and not use_global_stats:
        out, mean, var = _bn_core(float(eps), red, data, g, beta)
        new_mean = momentum * moving_mean + (1 - momentum) * mean
        new_var = momentum * moving_var + (1 - momentum) * var
        return (out,
                jax.lax.stop_gradient(new_mean),
                jax.lax.stop_gradient(new_var))
    mean, var = moving_mean, moving_var
    inv = jax.lax.rsqrt(var + eps)
    out = (data.astype(jnp.float32) - mean.reshape(shape)) * (
        inv * g.astype(jnp.float32)
    ).reshape(shape) + beta.astype(jnp.float32).reshape(shape)
    return (out.astype(data.dtype),
            jax.lax.stop_gradient(moving_mean),
            jax.lax.stop_gradient(moving_var))


@jax.named_scope("layernorm")
def _ln_fwd(eps, ax, x, g, b):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=ax, keepdims=True)
    # centered two-pass variance — see _bn_stats for why not E[x^2]-E[x]^2
    var = jnp.mean(jnp.square(x32 - mean), axis=ax, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    shape = [1] * x.ndim
    shape[ax] = x.shape[ax]
    out = (x32 - mean) * inv * g.astype(jnp.float32).reshape(shape) + \
        b.astype(jnp.float32).reshape(shape)
    return out.astype(x.dtype), (x, g, mean, inv)


@jax.named_scope("layernorm_bwd")
def _ln_bwd(eps, ax, res, ct):
    x, g, mean, inv = res
    n = x.shape[ax]
    shape = [1] * x.ndim
    shape[ax] = n
    dy = ct.astype(jnp.float32) * g.astype(jnp.float32).reshape(shape)
    xhat = (x.astype(jnp.float32) - mean) * inv
    dy_ct = ct.astype(jnp.float32)
    other = tuple(i for i in range(x.ndim) if i != ax % x.ndim)
    dg = jnp.sum(dy_ct * xhat, axis=other)
    db = jnp.sum(dy_ct, axis=other)
    m1 = jnp.mean(dy, axis=ax, keepdims=True)
    m2 = jnp.mean(dy * xhat, axis=ax, keepdims=True)
    dx = inv * (dy - m1 - xhat * m2)
    return dx.astype(x.dtype), dg.astype(g.dtype), db.astype(g.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ln_core(eps, ax, x, g, b):
    return _ln_fwd(eps, ax, x, g, b)[0]


_ln_core.defvjp(_ln_fwd, _ln_bwd)


@register("LayerNorm", aliases=("layer_norm",))
def layer_norm(data, gamma, beta, axis=-1, eps=1e-5):
    """ref: src/operator/nn/layer_norm.cc — normalizes along one axis.
    Custom-VJP kernel: single-pass f32 stats, bf16-only residuals
    (backward recomputes x_hat instead of saving f32 intermediates)."""
    return _ln_core(float(eps), axis % data.ndim, data, gamma, beta)


# --------------------------------------------------------------------------
# RMSNorm, rotary positions, SwiGLU: the blocks of pre-norm decoders that
# have no bias and no mean to take off
# --------------------------------------------------------------------------
@jax.named_scope("rmsnorm")
def _rms_fwd(eps, ax, x, g):
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=ax, keepdims=True)
                        + eps)
    shape = [1] * x.ndim
    shape[ax] = x.shape[ax]
    out = x32 * inv * g.astype(jnp.float32).reshape(shape)
    return out.astype(x.dtype), (x, g, inv)


@jax.named_scope("rmsnorm_bwd")
def _rms_bwd(eps, ax, res, ct):
    x, g, inv = res
    shape = [1] * x.ndim
    shape[ax] = x.shape[ax]
    ct32 = ct.astype(jnp.float32)
    xhat = x.astype(jnp.float32) * inv
    dy = ct32 * g.astype(jnp.float32).reshape(shape)
    other = tuple(i for i in range(x.ndim) if i != ax)
    dg = jnp.sum(ct32 * xhat, axis=other)
    dx = inv * (dy - xhat * jnp.mean(dy * xhat, axis=ax, keepdims=True))
    return dx.astype(x.dtype), dg.astype(g.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _rms_core(eps, ax, x, g):
    return _rms_fwd(eps, ax, x, g)[0]


_rms_core.defvjp(_rms_fwd, _rms_bwd)


@register("RMSNorm", aliases=("rms_norm",))
def rms_norm(data, gamma, axis=-1, eps=1e-6):
    """``data / sqrt(mean(data^2) + eps) * gamma`` along one axis (Zhang &
    Sennrich 2019): statistics in float32, residuals in the input's type
    (the backward recomputes the normalised rows), as ``LayerNorm``."""
    return _rms_core(float(eps), axis % data.ndim, data, gamma)


@jax.named_scope("gated_rmsnorm")
def _gated_rms_fwd(eps, x, z, g):
    u = x.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(u), axis=-1, keepdims=True) + eps)
    return (u * inv * g.astype(jnp.float32)).astype(x.dtype), (x, z, g)


@jax.named_scope("gated_rmsnorm_bwd")
def _gated_rms_bwd(eps, res, ct):
    x, z, g = res
    x32, z32, ct32 = (a.astype(jnp.float32) for a in (x, z, ct))
    gate = jax.nn.sigmoid(z32)
    u = x32 * z32 * gate
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(u), axis=-1, keepdims=True) + eps)
    uhat = u * inv
    dy = ct32 * g.astype(jnp.float32)
    du = inv * (dy - uhat * jnp.mean(dy * uhat, axis=-1, keepdims=True))
    dg = jnp.sum(ct32 * uhat, axis=tuple(range(x.ndim - 1)))
    dz = du * x32 * gate * (1.0 + z32 * (1.0 - gate))  # silu's derivative
    return (du * z32 * gate).astype(x.dtype), dz.astype(z.dtype), dg.astype(g.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gated_rms_core(eps, x, z, g):
    return _gated_rms_fwd(eps, x, z, g)[0]


_gated_rms_core.defvjp(_gated_rms_fwd, _gated_rms_bwd)


@register("GatedRMSNorm", aliases=("gated_rms_norm",))
def gated_rms_norm(data, gate, gamma, eps=1e-6):
    """``RMSNorm(data * silu(gate)) * gamma`` over the last axis, the gate
    first and the norm after it (Mamba-2's ``RMSNormGated`` with
    ``norm_before_gate=False``, one group): float32 inside, residuals in the
    inputs' types (the backward recomputes the gated rows), as ``RMSNorm``."""
    return _gated_rms_core(float(eps), data, gate, gamma)


@jax.named_scope("rmsnorm_gate")
def _rms_gate_fwd(eps, x, z, g):
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    out = x32 * inv * g.astype(jnp.float32) * jax.nn.sigmoid(z.astype(jnp.float32))
    return out.astype(x.dtype), (x, z, g)


@jax.named_scope("rmsnorm_gate_bwd")
def _rms_gate_bwd(eps, res, ct):
    x, z, g = res
    x32, z32, ct32 = (a.astype(jnp.float32) for a in (x, z, ct))
    gate = jax.nn.sigmoid(z32)
    inv = jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    xhat = x32 * inv
    dn = ct32 * gate  # the normed rows' gradient, before the weight
    dy = dn * g.astype(jnp.float32)
    dx = inv * (dy - xhat * jnp.mean(dy * xhat, axis=-1, keepdims=True))
    dg = jnp.sum(dn * xhat, axis=tuple(range(x.ndim - 1)))
    dz = ct32 * xhat * g.astype(jnp.float32) * gate * (1.0 - gate)
    return dx.astype(x.dtype), dz.astype(z.dtype), dg.astype(g.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rms_gate_core(eps, x, z, g):
    return _rms_gate_fwd(eps, x, z, g)[0]


_rms_gate_core.defvjp(_rms_gate_fwd, _rms_gate_bwd)


@register("RMSNormSigmoidGate", aliases=("rms_norm_sigmoid_gate",))
def rms_norm_sigmoid_gate(data, gate, gamma, eps=1e-6):
    """``RMSNorm(data) * gamma * sigmoid(gate)`` over the last axis, the norm
    first and the gate after it (Kimi's delta attention closes a head so; the
    other order and the other gate from ``GatedRMSNorm``): float32 inside,
    residuals in the inputs' types, as ``RMSNorm``. Scopes ``rmsnorm_gate`` /
    ``rmsnorm_gate_bwd``."""
    return _rms_gate_core(float(eps), data, gate, gamma)


@register("rotary_embedding", aliases=("rope",))
def rotary_embedding(data, theta=10000.0, interleaved=False, seq_axis=-2):
    """Rotary positions (Su et al. 2021) on the last axis of ``data``, the
    position counted along ``seq_axis`` from 0. Pair j of the D/2
    pairs turns by ``pos * theta**(-2j/D)``. ``interleaved=False`` pairs
    entry j with entry j + D/2 (the rotate-half form). ``interleaved=True``
    pairs entries (2j, 2j+1): they are first taken apart to ``[evens,
    odds]`` and then turned in the rotate-half form, so the result is in
    that order too (what ``apply_rotary_pos_emb_interleave`` of the
    DeepSeek-V3 family does; queries and keys are reordered alike, so
    their products are those of the interleaved form)."""
    d = data.shape[-1]
    if d % 2:
        raise ValueError("rotary_embedding needs an even last axis, got %d" % d)
    half = d // 2
    ax = seq_axis % data.ndim
    with jax.named_scope("rope"):
        x = data.astype(jnp.float32)
        if interleaved:
            pairs = x.reshape(x.shape[:-1] + (half, 2))
            x1, x2 = pairs[..., 0], pairs[..., 1]
        else:
            x1, x2 = x[..., :half], x[..., half:]
        pos = jnp.arange(data.shape[ax], dtype=jnp.float32)
        freq = jnp.float32(theta) ** (
            -jnp.arange(half, dtype=jnp.float32) * (2.0 / d))
        angle = pos[:, None] * freq[None, :]  # (T, D/2)
        shape = [1] * data.ndim
        shape[ax], shape[-1] = data.shape[ax], half
        cos, sin = jnp.cos(angle).reshape(shape), jnp.sin(angle).reshape(shape)
        out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                              axis=-1)
        return out.astype(data.dtype)


@register("swiglu")
def swiglu(gate, up):
    """``silu(gate) * up`` (Shazeer 2020), the product in float32."""
    g = gate.astype(jnp.float32)
    return (jax.nn.silu(g) * up.astype(jnp.float32)).astype(gate.dtype)


@register("InstanceNorm")
def instance_norm(data, gamma, beta, eps=1e-3):
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    shape = [1, data.shape[1]] + [1] * (data.ndim - 2)
    return (data - mean) * jax.lax.rsqrt(var + eps) * gamma.reshape(shape) + \
        beta.reshape(shape)


@register("GroupNorm")
def group_norm(data, gamma, beta, num_groups=1, eps=1e-5):
    n, c = data.shape[0], data.shape[1]
    rest = data.shape[2:]
    x = data.reshape((n, num_groups, c // num_groups) + rest)
    red = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=red, keepdims=True)
    var = jnp.var(x, axis=red, keepdims=True)
    x = (x - mean) * jax.lax.rsqrt(var + eps)
    x = x.reshape(data.shape)
    shape = [1, c] + [1] * (data.ndim - 2)
    return x * gamma.reshape(shape) + beta.reshape(shape)


@register("LRN")
def lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5):
    """ref: src/operator/nn/lrn.cc — cross-channel local response norm."""
    sq = jnp.square(data)
    half = nsize // 2
    padded = jnp.pad(sq, [(0, 0), (half, half)] + [(0, 0)] * (data.ndim - 2))
    acc = sum(
        jax.lax.dynamic_slice_in_dim(padded, i, data.shape[1], axis=1)
        for i in range(nsize)
    )
    return data / jnp.power(knorm + alpha * acc / nsize, beta)


# --------------------------------------------------------------------------
# pooling (ref: src/operator/nn/pooling.cc) — lax.reduce_window
# --------------------------------------------------------------------------
@register("Pooling", aliases=("pooling", "Pooling_v1"))
def pooling(data, kernel=(), pool_type="max", global_pool=False, stride=(),
            pad=(), pooling_convention="valid", count_include_pad=True,
            cudnn_off=False, p_value=2, layout=None):
    del cudnn_off
    if layout in (None, "NCHW", "NCW", "NCDHW"):
        spatial = tuple(range(2, data.ndim))
    else:
        spatial = tuple(range(1, data.ndim - 1))
    nd = len(spatial)
    if global_pool:
        kernel = tuple(data.shape[ax] for ax in spatial)
        stride = (1,) * nd
        pad = (0,) * nd
    else:
        kernel = tuple(kernel)
        stride = tuple(stride) if stride else (1,) * nd
        pad = tuple(pad) if pad else (0,) * nd

    window = [1] * data.ndim
    strides = [1] * data.ndim
    pads = [(0, 0)] * data.ndim
    for i, ax in enumerate(spatial):
        window[ax] = kernel[i]
        strides[ax] = stride[i]
        if pooling_convention == "full":
            # ceil-mode: add extra right padding so the last window fits
            in_sz = data.shape[ax] + 2 * pad[i]
            rem = (in_sz - kernel[i]) % stride[i]
            extra = (stride[i] - rem) % stride[i] if rem else 0
            pads[ax] = (pad[i], pad[i] + extra)
        else:
            pads[ax] = (pad[i], pad[i])

    # NOTE: init values must be PYTHON scalars — jax pattern-matches
    # (max, -inf) / (add, 0) to reduce_window_max/sum primitives, which are
    # the ones with reverse-mode autodiff rules
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else \
            int(jnp.iinfo(data.dtype).min)
        return jax.lax.reduce_window(
            data, data.dtype.type(init), jax.lax.max, window, strides, pads)
    if pool_type in ("avg", "sum"):
        s = jax.lax.reduce_window(
            data, data.dtype.type(0), jax.lax.add, window, strides, pads)
        if pool_type == "sum":
            return s
        if count_include_pad:
            denom = 1
            for k in kernel:
                denom *= k
            return s / jnp.asarray(denom, data.dtype)
        ones = jnp.ones(data.shape, data.dtype)
        counts = jax.lax.reduce_window(
            ones, data.dtype.type(0), jax.lax.add, window, strides, pads)
        return s / counts
    if pool_type == "lp":
        s = jax.lax.reduce_window(
            jnp.power(jnp.abs(data), p_value), data.dtype.type(0),
            jax.lax.add, window, strides, pads)
        return jnp.power(s, 1.0 / p_value)
    raise ValueError("unknown pool_type %r" % (pool_type,))


@register("UpSampling")
def upsampling(data, scale=1, sample_type="nearest", num_args=1):
    del num_args
    if sample_type != "nearest":
        raise NotImplementedError("only nearest upsampling supported")
    for ax in (2, 3):
        data = jnp.repeat(data, scale, axis=ax)
    return data


@register("BilinearSampler")
def bilinear_sampler(data, grid):
    """ref: src/operator/bilinear_sampler.cc — grid in [-1, 1] NCHW.

    Out-of-image corner samples contribute ZERO (the reference's
    ``between()`` guard — zero padding, not border replication), which
    also makes the autodiff gradients match the reference's backward:
    d(data) scatters only into in-bounds corners and d(grid) sees no
    pull from outside the image."""
    n, c, h, w = data.shape
    gx = (grid[:, 0] + 1) * (w - 1) / 2
    gy = (grid[:, 1] + 1) * (h - 1) / 2
    x0 = jnp.floor(gx)
    y0 = jnp.floor(gy)
    wx = gx - x0
    wy = gy - y0

    def gather(yi, xi):
        valid = (yi >= 0) & (yi <= h - 1) & (xi >= 0) & (xi <= w - 1)
        yc = jnp.clip(yi.astype(jnp.int32), 0, h - 1)
        xc = jnp.clip(xi.astype(jnp.int32), 0, w - 1)
        flat = data.reshape(n, c, h * w)
        idx = (yc * w + xc).reshape(n, -1)
        out = jnp.take_along_axis(flat, idx[:, None, :], axis=2)
        out = out.reshape(n, c, *gx.shape[1:])
        return out * valid[:, None].astype(out.dtype)

    v00 = gather(y0, x0)
    v01 = gather(y0, x0 + 1)
    v10 = gather(y0 + 1, x0)
    v11 = gather(y0 + 1, x0 + 1)
    wx = wx[:, None]
    wy = wy[:, None]
    return (v00 * (1 - wx) * (1 - wy) + v01 * wx * (1 - wy)
            + v10 * (1 - wx) * wy + v11 * wx * wy)


# --------------------------------------------------------------------------
# spatial-transform / detection ops
# (ref: src/operator/{spatial_transformer,grid_generator,roi_pooling,
#  correlation}.cc)
# --------------------------------------------------------------------------
@register("GridGenerator")
def grid_generator(data, transform_type="affine", target_shape=(0, 0)):
    """ref: src/operator/grid_generator.cc. affine: (N, 6) theta ->
    (N, 2, H, W) sampling grid in [-1, 1]; warp: (N, 2, H, W) flow ->
    grid (flow added to the identity grid, normalized)."""
    if transform_type == "affine":
        h, w = target_shape
        n = data.shape[0]
        ys = jnp.linspace(-1.0, 1.0, h)
        xs = jnp.linspace(-1.0, 1.0, w)
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        ones = jnp.ones_like(gx)
        base = jnp.stack([gx.ravel(), gy.ravel(),
                          ones.ravel()]).astype(data.dtype)  # (3, HW)
        theta = data.reshape(n, 2, 3)
        out = jnp.einsum("nij,jk->nik", theta, base)  # (N, 2, HW)
        return out.reshape(n, 2, h, w)
    if transform_type == "warp":
        n, _, h, w = data.shape
        ys = jnp.arange(h, dtype=jnp.float32)
        xs = jnp.arange(w, dtype=jnp.float32)
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        fx = data[:, 0].astype(jnp.float32) + gx
        fy = data[:, 1].astype(jnp.float32) + gy
        nx = fx * 2.0 / max(w - 1, 1) - 1.0
        ny = fy * 2.0 / max(h - 1, 1) - 1.0
        return jnp.stack([nx, ny], axis=1).astype(data.dtype)
    raise ValueError("unknown transform_type %r" % (transform_type,))


@register("SpatialTransformer")
def spatial_transformer(data, loc, target_shape=(0, 0),
                        transform_type="affine", sampler_type="bilinear",
                        cudnn_off=None):
    """ref: src/operator/spatial_transformer.cc — affine grid + bilinear
    sampling of the input feature map."""
    del cudnn_off
    if sampler_type != "bilinear":
        raise ValueError("only bilinear sampler_type is supported")
    grid = grid_generator(loc, transform_type, target_shape)
    return bilinear_sampler(data, grid)


@register("ROIPooling")
def roi_pooling(data, rois, pooled_size=(1, 1), spatial_scale=1.0):
    """ref: src/operator/roi_pooling.cc — max pool each ROI into a fixed
    (ph, pw) grid. rois: (R, 5) [batch_idx, x1, y1, x2, y2] in image
    coords; boundaries replicate the reference's floor/ceil rounding."""
    ph, pw = pooled_size
    n, c, h, w = data.shape
    # at least f32 for the bin geometry, but never BELOW the input's
    # precision (f64 numeric-grad sweeps would otherwise see f32 noise)
    ct = jnp.promote_types(data.dtype, jnp.float32)

    def one_roi(roi):
        b = roi[0].astype(jnp.int32)
        x1 = jnp.round(roi[1] * spatial_scale).astype(ct)
        y1 = jnp.round(roi[2] * spatial_scale).astype(ct)
        x2 = jnp.round(roi[3] * spatial_scale).astype(ct)
        y2 = jnp.round(roi[4] * spatial_scale).astype(ct)
        rh = jnp.maximum(y2 - y1 + 1.0, 1.0)
        rw = jnp.maximum(x2 - x1 + 1.0, 1.0)
        bin_h = rh / ph
        bin_w = rw / pw
        fmap = data[b]  # (C, H, W)
        iy = jnp.arange(h, dtype=ct)
        ix = jnp.arange(w, dtype=ct)
        # bin index boundaries: [start, end) per output cell
        ys = y1 + jnp.arange(ph, dtype=ct) * bin_h
        ye = y1 + (jnp.arange(ph, dtype=ct) + 1) * bin_h
        xs_ = x1 + jnp.arange(pw, dtype=ct) * bin_w
        xe = x1 + (jnp.arange(pw, dtype=ct) + 1) * bin_w
        row_m = (iy[None, :] >= jnp.floor(ys)[:, None]) & \
                (iy[None, :] < jnp.ceil(ye)[:, None])      # (ph, H)
        col_m = (ix[None, :] >= jnp.floor(xs_)[:, None]) & \
                (ix[None, :] < jnp.ceil(xe)[:, None])      # (pw, W)
        mask = row_m[:, None, :, None] & col_m[None, :, None, :]
        neg = jnp.asarray(-jnp.inf, ct)
        vals = jnp.where(mask[None], fmap[:, None, None, :, :]
                         .astype(ct), neg)
        out = jnp.max(vals, axis=(3, 4))  # (C, ph, pw)
        return jnp.where(jnp.isfinite(out), out, 0.0)

    out = jax.vmap(one_roi)(rois.astype(ct))
    return out.astype(data.dtype)


@register("Correlation")
def correlation(data1, data2, kernel_size=1, max_displacement=1, stride1=1,
                stride2=1, pad_size=0, is_multiply=True):
    """ref: src/operator/correlation.cc (FlowNet cost volume). Output
    channel k is the per-pixel patch correlation of data1 with data2
    shifted by the k-th displacement in a (2d+1)^2 grid."""
    n, c, h, w = data1.shape
    d = max_displacement // stride2
    if pad_size:
        pad = [(0, 0), (0, 0), (pad_size, pad_size), (pad_size, pad_size)]
        data1 = jnp.pad(data1, pad)
        data2 = jnp.pad(data2, pad)
    # zero-pad by the displacement range so shifts bring in zeros at the
    # borders (the reference zero-pads; jnp.roll would wrap the far edge
    # around and correlate opposite borders)
    m2 = d * stride2
    hh, ww = data1.shape[2], data1.shape[3]
    data2p = jnp.pad(data2, [(0, 0), (0, 0), (m2, m2), (m2, m2)])
    outs = []
    for dy in range(-d, d + 1):
        for dx in range(-d, d + 1):
            oy = m2 + dy * stride2
            ox = m2 + dx * stride2
            shifted = data2p[:, :, oy:oy + hh, ox:ox + ww]
            if is_multiply:
                prod = data1 * shifted
            else:
                prod = jnp.abs(data1 - shifted)
            m = jnp.mean(prod, axis=1)  # mean over channels
            if kernel_size > 1:
                k = kernel_size
                m = jax.lax.reduce_window(
                    m, m.dtype.type(0), jax.lax.add, (1, k, k), (1, 1, 1),
                    [(0, 0), (k // 2, k // 2), (k // 2, k // 2)]
                ) / (k * k)
            outs.append(m)
    out = jnp.stack(outs, axis=1)  # (N, (2d+1)^2, H', W')
    if stride1 > 1:
        out = out[:, :, ::stride1, ::stride1]
    return out


@register("Crop", num_outputs=1)
def crop_op(*args, num_args=1, offset=(0, 0), h_w=(0, 0),
            center_crop=False):
    """Spatial crop of NCHW data (ref: src/operator/crop.cc — the
    FCN-era Crop op; `mx.nd.crop` is a different op, an alias of
    `slice`). With num_args=2 the second input is a shape reference and
    the output matches its (H, W); otherwise h_w gives the target size.
    center_crop centers the window, else `offset` is its top-left
    corner."""
    data = args[0]
    h, w = data.shape[2], data.shape[3]
    if num_args == 2 or len(args) == 2:
        th, tw = args[1].shape[2], args[1].shape[3]
    else:
        th, tw = h_w
    if th > h or tw > w:
        raise ValueError(
            "crop size (%d, %d) exceeds input (%d, %d)" % (th, tw, h, w))
    if center_crop:
        y0, x0 = (h - th) // 2, (w - tw) // 2
    else:
        y0, x0 = offset
        if y0 < 0 or x0 < 0 or y0 + th > h or x0 + tw > w:
            raise ValueError(
                "crop offset %s + size (%d, %d) outside input (%d, %d)"
                % (offset, th, tw, h, w))
    return data[:, :, y0:y0 + th, x0:x0 + tw]
