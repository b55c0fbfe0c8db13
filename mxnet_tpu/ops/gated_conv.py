"""Gated short convolution, the token mixer of LFM2's ``conv`` layers (Liquid
AI, LFM2 technical report; transformers' ``Lfm2ShortConv``): a causal
depthwise filter of a few taps between two elementwise gates.

One pure function, :func:`gated_short_conv`, on ``bcx`` (B, T, 3C), the input
projection's output ``[Bg, Cg, X]`` side by side, and ``weight`` (C, K), one
filter of K taps a channel::

    Z = Bg * X
    V[t] = sum_j weight[:, j] * Z[t - (K - 1) + j]      (Z is 0 before t = 0)
    Y = Cg * V

No bias, no activation; each sequence of the batch alone. It is written as K
shifted multiply-adds in ``jax.numpy`` (a depthwise ``lax.conv_general_dilated``
is a convolution program for what is three fused passes, and knows nothing of
the gates), float32 inside, with a backward of its own that keeps ``bcx`` alone
and recomputes ``Z`` and ``V``: forward and backward each read their operands
once and write their results once. The two halves run under the scopes
``gated_conv`` / ``gated_conv_bwd``, as ``attention`` / ``attention_bwd`` do,
and every traced call is counted by the branch it took
(``telemetry.gated_conv_branches()``: ``xla``; a later kernel counts
``kernel``).

:func:`causal_conv_silu` is the same filter as Mamba-2's mixer wears it
(transformers' ``GraniteMoeHybridMambaLayer``: ``Conv1d(groups=C, padding=K -
1)`` cut to the sequence, then SiLU): ``silu(V + bias)`` of ``data`` (B, T, C)
itself, no gates, over the same ``_filtered`` / ``_delay`` / ``_advance``,
float32 inside, with a backward that keeps ``data`` alone; scopes
``causal_conv`` / ``causal_conv_bwd``. Where ``causal_conv_pallas.kernel_takes``
accepts the call (a TPU, whole tiles of channels and tokens) both halves are
that module's kernels, which hold a tile of tokens with its halo in VMEM and
make no shifted copy; every other call is the formula here. Every traced call is
counted by the branch it took (``telemetry.causal_conv_branches()``: ``kernel``
or ``xla``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import telemetry as _telemetry
from ..base import MXNetError
from . import causal_conv_pallas as _kernels
from .registry import register

F32 = jnp.float32


def _delay(z, s):
    """``z`` (B, T, C) moved ``s`` steps later along T, zeros before."""
    if s == 0:
        return z
    return jnp.pad(z[:, :z.shape[1] - s], ((0, 0), (s, 0), (0, 0)))


def _advance(z, s):
    """``z`` moved ``s`` steps earlier along T, zeros after: ``_delay``'s
    transpose."""
    if s == 0:
        return z
    return jnp.pad(z[:, s:], ((0, 0), (0, s), (0, 0)))


def _split3(bcx):
    c = bcx.shape[-1] // 3
    return (bcx[..., :c].astype(F32), bcx[..., c:2 * c].astype(F32),
            bcx[..., 2 * c:].astype(F32))


def _filtered(z, w, move=_delay):
    """``V`` of the module's docstring from ``Z`` and float32 taps; with
    ``move=_advance`` the filter's transpose (``dZ`` from ``dV``)."""
    k = w.shape[1]
    v = w[:, k - 1] * z
    for j in range(k - 1):
        v = v + w[:, j] * move(z, k - 1 - j)
    return v


@jax.custom_vjp
def _gated_conv_core(bcx, weight):
    return _gated_conv_fwd(bcx, weight)[0]


@jax.named_scope("gated_conv")
def _gated_conv_fwd(bcx, weight):
    _telemetry.record_gated_conv("xla")
    bg, cg, x = _split3(bcx)
    y = cg * _filtered(bg * x, weight.astype(F32))
    return y.astype(bcx.dtype), (bcx, weight)


@jax.named_scope("gated_conv_bwd")
def _gated_conv_bwd(res, g):
    bcx, weight = res
    bg, cg, x = _split3(bcx)
    w, g = weight.astype(F32), g.astype(F32)
    k = w.shape[1]
    z = bg * x
    dv = g * cg
    dz = _filtered(dv, w, _advance)
    dbcx = jnp.concatenate([dz * x, g * _filtered(z, w), dz * bg], axis=-1)
    dw = jnp.stack([jnp.sum(dv * _delay(z, k - 1 - j), axis=(0, 1))
                    for j in range(k)], axis=-1)
    return dbcx.astype(bcx.dtype), dw.astype(weight.dtype)


_gated_conv_core.defvjp(_gated_conv_fwd, _gated_conv_bwd)


@register("gated_short_conv")
def gated_short_conv(data, weight):
    """``Cg * causal_depthwise_filter(Bg * X)`` of ``data`` (B, T, 3C) =
    ``[Bg, Cg, X]`` with ``weight`` (C, K), K taps a channel, the last tap on
    the current token: see the module's docstring. Returns (B, T, C)."""
    if data.ndim != 3 or weight.ndim != 2 or data.shape[-1] != 3 * weight.shape[0]:
        raise MXNetError("gated_short_conv: data %s is not (B, T, 3C) for weight "
                         "(C, K) = %s" % (tuple(data.shape), tuple(weight.shape)))
    if weight.shape[1] > data.shape[1]:
        raise MXNetError("gated_short_conv: %d taps on a sequence of %d"
                         % (weight.shape[1], data.shape[1]))
    return _gated_conv_core(data, weight)


def _filtered_silu(data, weight, bias):
    v = _filtered(data.astype(F32), weight.astype(F32)) + bias.astype(F32)
    return jax.nn.silu(v).astype(data.dtype)


def _filtered_silu_grads(data, weight, bias, g):
    z, w = data.astype(F32), weight.astype(F32)
    k = w.shape[1]
    v = _filtered(z, w) + bias.astype(F32)
    gate = jax.nn.sigmoid(v)
    dv = g.astype(F32) * gate * (1.0 + v * (1.0 - gate))  # silu's derivative
    dw = jnp.stack([jnp.sum(dv * _delay(z, k - 1 - j), axis=(0, 1))
                    for j in range(k)], axis=-1)
    return (_filtered(dv, w, _advance).astype(data.dtype), dw.astype(weight.dtype),
            jnp.sum(dv, axis=(0, 1)).astype(bias.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _conv_silu_core(data, weight, bias, columns):
    return _conv_silu_fwd(data, weight, bias, columns)[0]


def _kernel_takes(data, weight, columns):
    begin, end = columns
    return _kernels.kernel_takes(data.shape[:2] + (end - begin,), weight.shape[1],
                                 data.dtype, begin)


@jax.named_scope("causal_conv")
def _conv_silu_fwd(data, weight, bias, columns):
    begin, end = columns
    kernel = _kernel_takes(data, weight, columns)
    _telemetry.record_causal_conv("kernel" if kernel else "xla")
    if kernel:  # reads its columns where they lie
        return _kernels.conv_silu(data, weight, bias, begin), (data, weight, bias)
    return _filtered_silu(data[..., begin:end], weight, bias), (data, weight, bias)


@jax.named_scope("causal_conv_bwd")
def _conv_silu_bwd(columns, res, g):
    data, weight, bias = res
    begin, end = columns
    if _kernel_takes(data, weight, columns):
        dz, dw, db = _kernels.conv_silu_grads(data, weight, bias, g, begin)
    else:
        dz, dw, db = _filtered_silu_grads(data[..., begin:end], weight, bias, g)
    return jnp.pad(dz, ((0, 0), (0, 0), (begin, data.shape[-1] - end))), dw, db


_conv_silu_core.defvjp(_conv_silu_fwd, _conv_silu_bwd)


@register("causal_conv_silu")
def causal_conv_silu(data, weight, bias, columns=None):
    """``silu(causal_depthwise_filter(data) + bias)`` of ``data`` (B, T, C)
    with ``weight`` (C, K), K taps a channel, the last tap on the current
    token, zeros before the sequence, and ``bias`` (C,). Returns (B, T, C).
    With ``columns=(begin, end)`` ``data`` is a wider array (a fused
    projection's result) and the op filters its columns ``begin`` to ``end``,
    C of them: the kernels read them where they lie, which a slice handed to
    them is copied for."""
    begin, end = columns or (0, data.shape[-1])
    if data.ndim != 3 or weight.ndim != 2 or bias.shape != weight.shape[:1] \
            or not 0 <= begin < end <= data.shape[-1] or end - begin != weight.shape[0]:
        raise MXNetError("causal_conv_silu: columns %s of data %s are not (B, T, C) for "
                         "weight (C, K) = %s and bias %s"
                         % ((begin, end), tuple(data.shape), tuple(weight.shape),
                            tuple(bias.shape)))
    if weight.shape[1] > data.shape[1]:
        raise MXNetError("causal_conv_silu: %d taps on a sequence of %d"
                         % (weight.shape[1], data.shape[1]))
    return _conv_silu_core(data, weight, bias, (int(begin), int(end)))
