"""Sparse NDArrays — ``row_sparse`` and ``csr`` storage (SURVEY §2.2
sparse-ops row / §2.4 PullRowSparse / build-plan P9; ref:
python/mxnet/ndarray/sparse.py + src/operator/tensor/cast_storage*).

TPU-native design stance (P9): XLA requires static shapes, so *inside* a
jitted step the embedding gradient is a dense scatter-add (what the take
VJP lowers to — MXU/HBM-optimal on TPU). The sparse storage classes here
serve the places where sparsity actually pays on this hardware:

- **communication** — KVStore push/pull of only touched rows
  (``row_sparse_pull``, sparse push merge by index union), the reference's
  main use of row_sparse (dist embedding training);
- **optimizer updates** — lazy/sparse SGD/Adam/AdaGrad/FTRL update only
  the rows present in the gradient (ref: ``_sparse_sgd_update`` etc.,
  src/operator/optimizer_op.cc), preserving the reference's lazy-update
  semantics (untouched rows' momentum does NOT decay);
- **storage / IO** — CSR datasets (LibSVM-style) and ``cast_storage``.

Component arrays live on device as jax buffers; index manipulation
(union, dedupe) runs eagerly where data-dependent shapes are fine.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np

from .base import MXNetError, get_dtype
from .ndarray.ndarray import NDArray

__all__ = [
    "BaseSparseNDArray", "RowSparseNDArray", "CSRNDArray",
    "row_sparse_array", "csr_matrix", "cast_storage", "sparse_retain",
    "retain_rows", "dot", "add", "zeros", "empty", "array",
]

_IDX_DT = jnp.int64  # ref: row_sparse indices are int64


def _dense_fallback_warning(op):
    warnings.warn(
        "%s: storage fallback — operating on the dense representation "
        "(ref behavior: 'op falls back to dense')" % op, stacklevel=3)


class BaseSparseNDArray(NDArray):
    """Common behavior for sparse storage types. Subclasses NDArray so
    sparse arrays flow through APIs that type-check NDArray, but the
    dense buffer is materialized only on explicit fallback."""

    __slots__ = ()

    def _init_handle(self):
        # NDArray slots, bypassing its dense-buffer __init__
        self._base = None
        self._key = None
        self._grad = None
        self._ag_node = None
        self._data = None

    # subclasses must implement _dense()
    @property
    def data(self):
        raise NotImplementedError

    def asnumpy(self):
        """Dense numpy copy (ref: sparse .asnumpy returns dense)."""
        return np.asarray(self._dense())

    def wait_to_read(self):
        from .ndarray.ndarray import _device_sync
        for c in self._components():
            _device_sync(c)
        return self

    wait_to_write = wait_to_read

    def copy(self):
        return self.tostype(self.stype)

    def __len__(self):
        return self.shape[0]

    # dense-fallback arithmetic (explicit, warned — ref storage fallback)
    def _fallback_binary(self, other, fn, opname):
        _dense_fallback_warning(opname)
        o = other._dense() if isinstance(other, BaseSparseNDArray) else \
            (other.data if isinstance(other, NDArray) else other)
        return NDArray(fn(self._dense(), o))

    def __sub__(self, other):
        return self._fallback_binary(other, lambda a, b: a - b, "subtract")

    def __truediv__(self, other):
        return self._fallback_binary(other, lambda a, b: a / b, "divide")


class RowSparseNDArray(BaseSparseNDArray):
    """Row-sparse: values for a subset of rows + sorted unique row indices
    (ref: kRowSparseStorage — aux ``indices``; NDArray.h RowSparseAux)."""

    __slots__ = ("_values", "_indices", "_shape")

    stype = "row_sparse"

    def __init__(self, values, indices, shape):
        self._init_handle()
        self._values = values if isinstance(values, jax.Array) else \
            jnp.asarray(values)
        self._indices = (indices if isinstance(indices, jax.Array)
                         else jnp.asarray(indices)).astype(_IDX_DT)
        self._shape = tuple(int(s) for s in shape)
        if self._values.ndim != len(self._shape):
            raise MXNetError(
                "row_sparse values ndim %d must equal dense ndim %d"
                % (self._values.ndim, len(self._shape)))
        if self._values.shape[0] != self._indices.shape[0]:
            raise MXNetError("values rows %d != indices %d"
                             % (self._values.shape[0],
                                self._indices.shape[0]))

    def _components(self):
        return (self._values, self._indices)

    # -- properties (reference API: .data = values, .indices = row ids) --
    @property
    def data(self):
        return NDArray(self._values)

    @property
    def indices(self):
        return NDArray(self._indices)

    @property
    def shape(self):
        return self._shape

    @property
    def dtype(self):
        return np.dtype(self._values.dtype)

    @property
    def context(self):
        from .context import current_context
        return current_context()

    @property
    def num_rows(self):
        return int(self._indices.shape[0])

    def __repr__(self):
        return "<RowSparseNDArray %s, %d/%d rows>" % (
            "x".join(map(str, self._shape)), self.num_rows, self._shape[0])

    def _dense(self):
        out = jnp.zeros(self._shape, self._values.dtype)
        if self.num_rows:
            out = out.at[self._indices].set(self._values)
        return out

    def todense(self):
        return NDArray(self._dense())

    def tostype(self, stype):
        if stype == "row_sparse":
            return RowSparseNDArray(self._values, self._indices,
                                    self._shape)
        if stype == "default":
            return self.todense()
        if stype == "csr":
            raise MXNetError("cast_storage row_sparse -> csr is not "
                             "supported (matches reference)")
        raise MXNetError("unknown stype %r" % (stype,))

    def astype(self, dtype):
        return RowSparseNDArray(self._values.astype(get_dtype(dtype)),
                                self._indices, self._shape)

    def copyto(self, other):
        if isinstance(other, RowSparseNDArray):
            other._values = self._values
            other._indices = self._indices
            other._shape = self._shape
            return other
        if isinstance(other, NDArray):
            other._set_data(self._dense())
            return other
        raise MXNetError("copyto: unsupported target %r" % (other,))

    def retain(self, indices):
        return sparse_retain(self, indices)

    def __add__(self, other):
        if isinstance(other, RowSparseNDArray):
            return add(self, other)
        return self._fallback_binary(other, lambda a, b: a + b, "add")

    __radd__ = __add__

    def __mul__(self, other):
        from .base import numeric_types
        if isinstance(other, numeric_types):
            return RowSparseNDArray(self._values * other, self._indices,
                                    self._shape)
        return self._fallback_binary(other, lambda a, b: a * b, "multiply")

    __rmul__ = __mul__


class CSRNDArray(BaseSparseNDArray):
    """Compressed sparse row, 2-D (ref: kCSRStorage — aux ``indptr`` +
    ``indices``)."""

    __slots__ = ("_values", "_indices", "_indptr", "_shape")

    stype = "csr"

    def __init__(self, values, indices, indptr, shape):
        self._init_handle()
        self._values = jnp.asarray(values)
        self._indices = jnp.asarray(indices).astype(_IDX_DT)
        self._indptr = jnp.asarray(indptr).astype(_IDX_DT)
        self._shape = tuple(int(s) for s in shape)
        if len(self._shape) != 2:
            raise MXNetError("csr arrays are 2-D, got shape %s"
                             % (self._shape,))
        if self._indptr.shape[0] != self._shape[0] + 1:
            raise MXNetError("indptr length %d != rows+1 (%d)"
                             % (self._indptr.shape[0], self._shape[0] + 1))

    def _components(self):
        return (self._values, self._indices, self._indptr)

    @property
    def data(self):
        return NDArray(self._values)

    @property
    def indices(self):
        return NDArray(self._indices)

    @property
    def indptr(self):
        return NDArray(self._indptr)

    @property
    def shape(self):
        return self._shape

    @property
    def dtype(self):
        return np.dtype(self._values.dtype)

    @property
    def context(self):
        from .context import current_context
        return current_context()

    def __repr__(self):
        return "<CSRNDArray %s, %d stored>" % (
            "x".join(map(str, self._shape)), int(self._values.shape[0]))

    def _row_ids(self):
        """Per-nnz row id from indptr (host-side; eager path)."""
        indptr = np.asarray(self._indptr)
        counts = np.diff(indptr)
        return jnp.asarray(np.repeat(np.arange(self._shape[0]), counts),
                           dtype=_IDX_DT)

    def _dense(self):
        out = jnp.zeros(self._shape, self._values.dtype)
        if int(self._values.shape[0]):
            out = out.at[self._row_ids(), self._indices].set(self._values)
        return out

    def todense(self):
        return NDArray(self._dense())

    def tostype(self, stype):
        if stype == "csr":
            return CSRNDArray(self._values, self._indices, self._indptr,
                              self._shape)
        if stype == "default":
            return self.todense()
        raise MXNetError("cast_storage csr -> %s is not supported" % stype)

    def astype(self, dtype):
        return CSRNDArray(self._values.astype(get_dtype(dtype)),
                          self._indices, self._indptr, self._shape)

    def __getitem__(self, key):
        """Row slicing (ref: CSRNDArray supports slice on dim 0)."""
        if isinstance(key, int):
            key = slice(key, key + 1)
        if not isinstance(key, slice) or key.step not in (None, 1):
            raise MXNetError("csr supports contiguous row slices only")
        start, stop, _ = key.indices(self._shape[0])
        indptr = np.asarray(self._indptr)
        lo, hi = int(indptr[start]), int(indptr[stop])
        return CSRNDArray(self._values[lo:hi], self._indices[lo:hi],
                          self._indptr[start:stop + 1] - lo,
                          (stop - start, self._shape[1]))


# ---------------------------------------------------------------------------
# constructors
# ---------------------------------------------------------------------------
def row_sparse_array(arg1, shape=None, ctx=None, dtype=None):
    """Build a RowSparseNDArray from ``(data, indices)`` or from a dense
    source (nonzero rows kept), ref: sparse.py — row_sparse_array."""
    del ctx
    if isinstance(arg1, RowSparseNDArray):
        return arg1.tostype("row_sparse")
    if isinstance(arg1, tuple) and len(arg1) == 2 and not isinstance(
            arg1[0], (int, np.integer)):
        data, indices = arg1
        data = data.data if isinstance(data, NDArray) else jnp.asarray(data)
        indices = indices.data if isinstance(indices, NDArray) \
            else jnp.asarray(indices)
        if dtype is not None:
            data = data.astype(get_dtype(dtype))
        if shape is None:
            raise MXNetError("row_sparse_array((data, indices)) requires "
                             "shape=")
        order = np.argsort(np.asarray(indices), kind="stable")
        if not np.all(order == np.arange(len(order))):
            data = data[jnp.asarray(order)]
            indices = indices[jnp.asarray(order)]
        return RowSparseNDArray(data, indices, shape)
    # dense source
    dense = arg1.data if isinstance(arg1, NDArray) else jnp.asarray(
        np.asarray(arg1))
    if dtype is not None:
        dense = dense.astype(get_dtype(dtype))
    if shape is not None and tuple(shape) != tuple(dense.shape):
        raise MXNetError("shape mismatch: %s vs %s"
                         % (shape, dense.shape))
    nz = np.nonzero(np.asarray(
        jnp.any(dense.reshape(dense.shape[0], -1) != 0, axis=1)))[0]
    idx = jnp.asarray(nz, dtype=_IDX_DT)
    return RowSparseNDArray(dense[idx], idx, dense.shape)


def csr_matrix(arg1, shape=None, ctx=None, dtype=None):
    """Build a CSRNDArray from ``(data, indices, indptr)`` or a dense
    source (ref: sparse.py — csr_matrix)."""
    del ctx
    if isinstance(arg1, CSRNDArray):
        return arg1.tostype("csr")
    if isinstance(arg1, tuple) and len(arg1) == 3:
        data, indices, indptr = (
            a.data if isinstance(a, NDArray) else jnp.asarray(a)
            for a in arg1)
        if dtype is not None:
            data = data.astype(get_dtype(dtype))
        if shape is None:
            raise MXNetError("csr_matrix((data, indices, indptr)) requires "
                             "shape=")
        return CSRNDArray(data, indices, indptr, shape)
    dense = np.asarray(arg1.asnumpy() if isinstance(arg1, NDArray)
                       else arg1)
    if dtype is not None:
        dense = dense.astype(get_dtype(dtype))
    if dense.ndim != 2:
        raise MXNetError("csr_matrix needs a 2-D source")
    rows, cols = np.nonzero(dense)
    counts = np.bincount(rows, minlength=dense.shape[0])
    indptr = np.concatenate([[0], np.cumsum(counts)])
    return CSRNDArray(jnp.asarray(dense[rows, cols]),
                      jnp.asarray(cols, dtype=_IDX_DT),
                      jnp.asarray(indptr, dtype=_IDX_DT), dense.shape)


def array(source, stype="default", dtype=None, ctx=None):
    if stype == "default":
        return NDArray(source if not isinstance(source, NDArray)
                       else source.data, dtype=dtype, ctx=ctx)
    if stype == "row_sparse":
        return row_sparse_array(source, dtype=dtype, ctx=ctx)
    if stype == "csr":
        return csr_matrix(source, dtype=dtype, ctx=ctx)
    raise MXNetError("unknown stype %r" % (stype,))


def zeros(stype, shape, ctx=None, dtype=None):
    """ref: sparse.zeros — an all-zero sparse array stores nothing."""
    del ctx
    dt = get_dtype(dtype) if dtype else jnp.float32
    if stype == "row_sparse":
        vshape = (0,) + tuple(shape[1:])
        return RowSparseNDArray(jnp.zeros(vshape, dt),
                                jnp.zeros((0,), _IDX_DT), shape)
    if stype == "csr":
        return CSRNDArray(jnp.zeros((0,), dt), jnp.zeros((0,), _IDX_DT),
                          jnp.zeros((shape[0] + 1,), _IDX_DT), shape)
    if stype == "default":
        return NDArray(jnp.zeros(shape, dt))
    raise MXNetError("unknown stype %r" % (stype,))


empty = zeros


# ---------------------------------------------------------------------------
# storage ops (ref: src/operator/tensor/cast_storage*, sparse_retain*)
# ---------------------------------------------------------------------------
def cast_storage(arr, stype="default"):
    """ref: cast_storage op — dense<->row_sparse<->csr conversions."""
    if isinstance(arr, BaseSparseNDArray):
        return arr.tostype(stype)
    if stype == "default":
        return arr.copy()
    if stype == "row_sparse":
        return row_sparse_array(arr)
    if stype == "csr":
        return csr_matrix(arr)
    raise MXNetError("unknown stype %r" % (stype,))


def sparse_retain(rsp, indices):
    """Keep only the requested rows (ref: sparse_retain op). Rows absent
    from ``rsp`` come back as missing (not zero-filled)."""
    if not isinstance(rsp, RowSparseNDArray):
        raise MXNetError("sparse_retain expects a RowSparseNDArray")
    req = np.unique(np.asarray(
        indices.data if isinstance(indices, NDArray) else indices
    ).astype(np.int64))
    have = np.asarray(rsp._indices)
    mask = np.isin(have, req)
    keep = jnp.asarray(np.nonzero(mask)[0])
    return RowSparseNDArray(rsp._values[keep],
                            rsp._indices[keep], rsp.shape)


def retain_rows(src, row_ids, out=None):
    """Gather rows of a dense NDArray into a RowSparseNDArray — the server
    side of ``KVStore::PullRowSparse`` (only touched rows travel)."""
    ids = np.unique(np.asarray(
        row_ids.data if isinstance(row_ids, NDArray) else row_ids
    ).astype(np.int64))
    idx = jnp.asarray(ids, dtype=_IDX_DT)
    if isinstance(src, RowSparseNDArray):
        result = sparse_retain(src, idx)
    else:
        vals = src.data[idx]
        result = RowSparseNDArray(vals, idx, src.shape)
    if out is not None:
        return result.copyto(out)
    return result


def add(lhs, rhs):
    """row_sparse + row_sparse -> row_sparse over the index union
    (ref: elemwise_add with FInferStorageType rsp,rsp->rsp)."""
    if not (isinstance(lhs, RowSparseNDArray)
            and isinstance(rhs, RowSparseNDArray)):
        raise MXNetError("sparse.add expects two RowSparseNDArrays")
    if lhs.shape != rhs.shape:
        raise MXNetError("shape mismatch %s vs %s" % (lhs.shape, rhs.shape))
    li, ri = np.asarray(lhs._indices), np.asarray(rhs._indices)
    union = np.union1d(li, ri)
    uj = jnp.asarray(union, dtype=_IDX_DT)
    vshape = (len(union),) + lhs.shape[1:]
    vals = jnp.zeros(vshape, jnp.promote_types(lhs.dtype, rhs.dtype))
    lpos = jnp.asarray(np.searchsorted(union, li))
    rpos = jnp.asarray(np.searchsorted(union, ri))
    vals = vals.at[lpos].add(lhs._values.astype(vals.dtype))
    vals = vals.at[rpos].add(rhs._values.astype(vals.dtype))
    return RowSparseNDArray(vals, uj, lhs.shape)


def dot(lhs, rhs, transpose_a=False):
    """Sparse matmul: csr @ dense (and csr^T @ dense — the Embedding-grad
    shape, ref: dot(csr.T, dense) kernel in src/operator/tensor/dot-inl.h).
    segment_sum over nnz keeps this MXU/VPU-friendly."""
    if not isinstance(lhs, CSRNDArray):
        raise MXNetError("sparse.dot expects a CSRNDArray lhs")
    dense = rhs.data if isinstance(rhs, NDArray) else jnp.asarray(rhs)
    rows = lhs._row_ids()
    cols = lhs._indices
    vals = lhs._values
    if not transpose_a:
        # out[r] = sum_nnz(v * dense[c]) grouped by row
        contrib = vals[:, None] * dense[cols]
        out = jax.ops.segment_sum(contrib, rows.astype(jnp.int32),
                                  num_segments=lhs.shape[0])
        return NDArray(out.astype(dense.dtype))
    contrib = vals[:, None] * dense[rows]
    out = jax.ops.segment_sum(contrib, cols.astype(jnp.int32),
                              num_segments=lhs.shape[1])
    return NDArray(out.astype(dense.dtype))


# ---------------------------------------------------------------------------
# sparse optimizer updates (ref: src/operator/optimizer_op.cc — the
# _sparse_* variants; lazy_update semantics: rows NOT in the gradient are
# untouched, including their momentum/history)
# ---------------------------------------------------------------------------
def _rows_of(grad):
    return grad._indices, grad._values


def sparse_sgd_update(weight, grad, lr, wd=0.0, rescale_grad=1.0,
                      clip_gradient=-1.0):
    idx, gvals = _rows_of(grad)
    w = weight.data
    g = gvals.astype(jnp.float32) * rescale_grad
    if clip_gradient > 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    rows = w[idx].astype(jnp.float32)
    new = rows - lr * (g + wd * rows)
    weight._set_data(w.at[idx].set(new.astype(w.dtype)))
    return weight


def sparse_sgd_mom_update(weight, grad, mom, lr, momentum=0.0, wd=0.0,
                          rescale_grad=1.0, clip_gradient=-1.0):
    idx, gvals = _rows_of(grad)
    w = weight.data
    m = mom.data
    g = gvals.astype(jnp.float32) * rescale_grad
    if clip_gradient > 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    rows = w[idx].astype(jnp.float32)
    m_rows = m[idx].astype(jnp.float32)
    m_new = momentum * m_rows - lr * (g + wd * rows)
    mom._set_data(m.at[idx].set(m_new.astype(m.dtype)))
    weight._set_data(w.at[idx].set((rows + m_new).astype(w.dtype)))
    return weight


def sparse_adam_update(weight, grad, mean, var, lr, beta1=0.9, beta2=0.999,
                       epsilon=1e-8, wd=0.0, rescale_grad=1.0,
                       clip_gradient=-1.0, t=None):
    """``t=None`` means the caller already folded bias correction into
    ``lr`` (the Optimizer.update convention); pass a step number to apply
    the classic correction here instead."""
    idx, gvals = _rows_of(grad)
    w = weight.data
    g = gvals.astype(jnp.float32) * rescale_grad
    if clip_gradient > 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    rows = w[idx].astype(jnp.float32)
    g = g + wd * rows
    m_rows = mean.data[idx].astype(jnp.float32)
    v_rows = var.data[idx].astype(jnp.float32)
    m_new = beta1 * m_rows + (1 - beta1) * g
    v_new = beta2 * v_rows + (1 - beta2) * g * g
    lr_t = lr if t is None else \
        lr * np.sqrt(1 - beta2 ** t) / (1 - beta1 ** t)
    new = rows - lr_t * m_new / (jnp.sqrt(v_new) + epsilon)
    mean._set_data(mean.data.at[idx].set(m_new.astype(mean.dtype)))
    var._set_data(var.data.at[idx].set(v_new.astype(var.dtype)))
    weight._set_data(w.at[idx].set(new.astype(w.dtype)))
    return weight


def sparse_adagrad_update(weight, grad, history, lr, epsilon=1e-7, wd=0.0,
                          rescale_grad=1.0, clip_gradient=-1.0):
    idx, gvals = _rows_of(grad)
    w = weight.data
    g = gvals.astype(jnp.float32) * rescale_grad
    if clip_gradient > 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    rows = w[idx].astype(jnp.float32)
    g = g + wd * rows
    h_rows = history.data[idx].astype(jnp.float32) + g * g
    new = rows - lr * g / (jnp.sqrt(h_rows) + epsilon)
    history._set_data(history.data.at[idx].set(
        h_rows.astype(history.dtype)))
    weight._set_data(w.at[idx].set(new.astype(w.dtype)))
    return weight


def sparse_ftrl_update(weight, grad, z, n, lr, lamda1=0.01, beta=1.0,
                       wd=0.0, rescale_grad=1.0, clip_gradient=-1.0):
    idx, gvals = _rows_of(grad)
    w = weight.data
    g = gvals.astype(jnp.float32) * rescale_grad
    if clip_gradient > 0:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    rows = w[idx].astype(jnp.float32)
    z_rows = z.data[idx].astype(jnp.float32)
    n_rows = n.data[idx].astype(jnp.float32)
    n_new = n_rows + g * g
    sigma = (jnp.sqrt(n_new) - jnp.sqrt(n_rows)) / lr
    z_new = z_rows + g - sigma * rows
    new = jnp.where(
        jnp.abs(z_new) <= lamda1,
        jnp.zeros_like(rows),
        -(z_new - jnp.sign(z_new) * lamda1)
        / ((beta + jnp.sqrt(n_new)) / lr + wd))
    z._set_data(z.data.at[idx].set(z_new.astype(z.dtype)))
    n._set_data(n.data.at[idx].set(n_new.astype(n.dtype)))
    weight._set_data(w.at[idx].set(new.astype(w.dtype)))
    return weight
