"""Indexing / gather / scatter / ordering / sequence ops
(ref: src/operator/tensor/indexing_op.cc, ordering_op.cc,
src/operator/sequence_*.cc).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import embedding_grad as _embedding_grad
from .registry import register
from .. import telemetry as _telemetry


@register("take")
def take(a, indices, axis=0, mode="clip"):
    idx = indices.astype(jnp.int32)
    if mode == "wrap":
        idx = jnp.mod(idx, a.shape[axis])
    else:  # clip (default) — also what makes gather TPU-safe
        idx = jnp.clip(idx, 0, a.shape[axis] - 1)
    return jnp.take(a, idx, axis=axis)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _lookup(weight, idx, vocab):
    """``jnp.take(weight, idx, axis=0)`` whose gradient is
    ``ops/embedding_grad.py``'s grouped product and not a scatter."""
    del vocab
    return jnp.take(weight, idx, axis=0)


def _lookup_fwd(weight, idx, vocab):
    return _lookup(weight, idx, vocab), idx


def _lookup_bwd(vocab, idx, dy):
    _telemetry.record_embedding_grad("kernel")
    with jax.named_scope("embedding_bwd"):
        dw = _embedding_grad.table_grad(idx.reshape(-1), dy.reshape(idx.size, -1), vocab)
    return dw, None


_lookup.defvjp(_lookup_fwd, _lookup_bwd)


@register("Embedding")
def embedding(data, weight, input_dim=None, output_dim=None, dtype=None,
              sparse_grad=False):
    """Embedding lookup (ref: src/operator/tensor/indexing_op.cc — Embedding).

    On TPU this is a gather feeding the MXU-free path; the row_sparse
    gradient variant lives in the sparse module. The weight's gradient is
    ``ops/embedding_grad.py``'s grouped product over the table's tiles where
    ``embedding_grad.kernel_takes`` admits the call (its docstring has the
    rule and the table that set it) and ``sparse_grad`` is off; every other
    call is ``jnp.take`` with its own transpose, a scatter-add. Counted at
    trace time (``telemetry.embedding_grad_branches()``: ``kernel`` a traced
    backward, ``xla`` a traced call whose backward, if taken, is XLA's).
    """
    del input_dim, output_dim, dtype
    idx = jnp.clip(data.astype(jnp.int32), 0, weight.shape[0] - 1)
    if not sparse_grad and weight.ndim == 2 and _embedding_grad.kernel_takes(
            *weight.shape, idx.size, weight.dtype):
        return _lookup(weight, idx, weight.shape[0])
    _telemetry.record_embedding_grad("xla")
    return jnp.take(weight, idx, axis=0)


@register("pick")
def pick(a, index, axis=-1, keepdims=False, mode="clip"):
    idx = jnp.clip(index.astype(jnp.int32), 0, a.shape[axis] - 1)
    out = jnp.take_along_axis(a, jnp.expand_dims(idx, axis=axis), axis=axis)
    if not keepdims:
        out = jnp.squeeze(out, axis=axis)
    return out


@register("one_hot", differentiable=False)
def one_hot(indices, depth=0, on_value=1.0, off_value=0.0, dtype="float32"):
    from ..base import get_dtype

    dt = get_dtype(dtype)
    oh = jax.nn.one_hot(indices.astype(jnp.int32), depth, dtype=dt)
    return oh * jnp.asarray(on_value, dt) + (1 - oh) * jnp.asarray(off_value, dt)


@register("gather_nd")
def gather_nd(data, indices):
    """indices shape (M, ...) selects from the first M axes of data
    (ref: indexing_op.cc — gather_nd)."""
    idx = indices.astype(jnp.int32)
    m = idx.shape[0]
    idx_tuple = tuple(
        jnp.clip(idx[i], 0, data.shape[i] - 1) for i in range(m)
    )
    return data[idx_tuple]


@register("scatter_nd")
def scatter_nd(data, indices, shape=None):
    idx = indices.astype(jnp.int32)
    m = idx.shape[0]
    out = jnp.zeros(tuple(shape), dtype=data.dtype)
    idx_tuple = tuple(idx[i] for i in range(m))
    return out.at[idx_tuple].add(data)


@register("index_copy")
def index_copy(old, index, new):
    return old.at[index.astype(jnp.int32)].set(new)


@register("index_add")
def index_add(old, index, new):
    return old.at[index.astype(jnp.int32)].add(new)


@register("boolean_mask", differentiable=False)
def boolean_mask(data, index, axis=0):
    """Dynamic-shape op: eager only (under jit the output shape cannot be
    static on TPU; reference's contrib BooleanMask has the same data
    dependence)."""
    import numpy as np

    mask = np.asarray(index).astype(bool)
    keep = np.flatnonzero(mask)
    return jnp.take(data, jnp.asarray(keep), axis=axis)


# --------------------------------------------------------------------------
# ordering (ref: src/operator/tensor/ordering_op.cc)
# --------------------------------------------------------------------------
@register("sort")
def sort(a, axis=-1, is_ascend=True):
    out = jnp.sort(a, axis=axis)
    if not is_ascend:
        out = jnp.flip(out, axis=axis)
    return out


@register("argsort", differentiable=False)
def argsort(a, axis=-1, is_ascend=True, dtype="float32"):
    from ..base import get_dtype

    out = jnp.argsort(a, axis=axis)
    if not is_ascend:
        out = jnp.flip(out, axis=axis)
    return out.astype(get_dtype(dtype))


@register("topk", differentiable=False)
def topk(a, axis=-1, k=1, ret_typ="indices", is_ascend=False, dtype="float32"):
    from ..base import get_dtype

    dt = get_dtype(dtype)
    ax = axis % a.ndim if axis is not None else a.ndim - 1
    src = -a if is_ascend else a
    moved = jnp.moveaxis(src, ax, -1)
    vals, idxs = jax.lax.top_k(moved, k)
    if is_ascend:
        vals = -vals
    vals = jnp.moveaxis(vals, -1, ax)
    idxs = jnp.moveaxis(idxs, -1, ax)
    if ret_typ == "indices":
        return idxs.astype(dt)
    if ret_typ == "value":
        return vals
    if ret_typ == "both":
        return (vals, idxs.astype(dt))
    if ret_typ == "mask":
        oh = jax.nn.one_hot(jnp.moveaxis(idxs, ax, -1), a.shape[ax], dtype=a.dtype)
        mask = oh.sum(axis=-2)
        return jnp.moveaxis(mask, -1, ax)
    raise ValueError("unknown ret_typ %r" % (ret_typ,))


# --------------------------------------------------------------------------
# sequence ops (ref: src/operator/sequence_mask.cc etc.) — axis layout
# (max_len, batch, ...) with use_sequence_length flag, as in the reference.
# --------------------------------------------------------------------------
def _seq_mask(lengths, maxlen):
    return jnp.arange(maxlen)[:, None] < lengths[None, :].astype(jnp.int32)


@register("SequenceMask")
def sequence_mask(data, sequence_length=None, use_sequence_length=False,
                  value=0.0, axis=0):
    if not use_sequence_length or sequence_length is None:
        return data
    t_ax = axis
    maxlen = data.shape[t_ax]
    mask = _seq_mask(sequence_length, maxlen)  # (T, B)
    if t_ax == 1:
        mask = mask.T
    shape = [1] * data.ndim
    shape[t_ax] = data.shape[t_ax]
    shape[1 - t_ax] = data.shape[1 - t_ax]
    mask = mask.reshape(shape)
    return jnp.where(mask, data, jnp.asarray(value, data.dtype))


@register("SequenceLast")
def sequence_last(data, sequence_length=None, use_sequence_length=False, axis=0):
    if not use_sequence_length or sequence_length is None:
        idx = [slice(None)] * data.ndim
        idx[axis] = -1
        return data[tuple(idx)]
    last = (sequence_length.astype(jnp.int32) - 1)  # (B,)
    moved = jnp.moveaxis(data, axis, 0)  # (T, B, ...)
    return jnp.take_along_axis(
        moved, last.reshape((1, -1) + (1,) * (moved.ndim - 2)), axis=0
    )[0]


@register("SequenceReverse")
def sequence_reverse(data, sequence_length=None, use_sequence_length=False, axis=0):
    moved = jnp.moveaxis(data, axis, 0)
    T = moved.shape[0]
    if not use_sequence_length or sequence_length is None:
        rev = jnp.flip(moved, axis=0)
    else:
        lens = sequence_length.astype(jnp.int32)  # (B,)
        t = jnp.arange(T)[:, None]  # (T,1)
        src = jnp.where(t < lens[None, :], lens[None, :] - 1 - t, t)  # (T,B)
        src = src.reshape((T, -1) + (1,) * (moved.ndim - 2))
        rev = jnp.take_along_axis(moved, jnp.broadcast_to(src, moved.shape), axis=0)
    return jnp.moveaxis(rev, 0, axis)
