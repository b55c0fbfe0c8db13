"""NDArray — mutable tensor handle over immutable jax.Array.

Re-design of the reference NDArray (ref: include/mxnet/ndarray.h,
src/ndarray/ndarray.cc). The reference pairs each array with an engine
variable for async dependency tracking; here XLA's async dispatch plays the
ThreadedEngine, so the handle only needs to solve *mutation and aliasing*:

- the handle owns a swappable ``jax.Array`` (in-place ops rebind it);
- basic slicing returns a *view* holding (base, key): reads materialize
  ``base.data[key]`` lazily, writes funnel through ``base`` via ``.at[]`` —
  so view/base mutation stays coherent like the reference's shared Chunk;
- ``asnumpy``/``wait_to_read`` are the sync points; deferred XLA errors
  surface there (matching test_exc_handling semantics);
- autograd participation via ``_ag_node`` (see autograd.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError, get_dtype, dtype_name, numeric_types
from ..context import Context, current_context, cpu
from ..ops.registry import apply_op, get_op

__all__ = ["NDArray", "array", "empty", "zeros", "ones", "full", "arange",
           "linspace", "eye", "concatenate", "waitall", "save", "load",
           "zeros_like", "ones_like", "moveaxis", "_wrap_outputs"]


def _unwrap(x):
    return x.data if isinstance(x, NDArray) else x


def _leaf_type():
    from .. import autograd as ag

    return ag.AGLeaf


def _norm_key(key):
    """Normalize an index key; NDArray indices become jax arrays."""
    if isinstance(key, NDArray):
        return key.data.astype(jnp.int32)
    if isinstance(key, tuple):
        return tuple(_norm_key(k) for k in key)
    if isinstance(key, (list, np.ndarray)):
        return jnp.asarray(key)
    return key


def _is_basic_key(key):
    if isinstance(key, tuple):
        return all(_is_basic_key(k) for k in key)
    return isinstance(key, (int, np.integer, slice, type(None), type(Ellipsis)))


class NDArray:
    __slots__ = ("_data", "_base", "_key", "_grad", "_ag_node", "__weakref__")

    def __init__(self, data, ctx=None, dtype=None, _base=None, _key=None):
        self._base = _base
        self._key = _key
        self._grad = None
        self._ag_node = None
        if _base is not None:
            self._data = None
            return
        if isinstance(data, NDArray):
            data = data.data
        if not isinstance(data, jax.Array):
            if dtype is None and not isinstance(data, np.ndarray):
                # reference behavior: non-ndarray sources default to float32
                # (ndarray sources keep their dtype)
                npd = np.asarray(data).astype(np.float32)
            else:
                npd = np.asarray(data, dtype=get_dtype(dtype) if dtype else None)
            dev = (ctx or current_context()).jax_device
            data = jax.device_put(npd, dev)
        else:
            if dtype is not None and data.dtype != get_dtype(dtype):
                data = data.astype(get_dtype(dtype))
            if ctx is not None:
                dev = ctx.jax_device
                if data.device != dev:
                    data = jax.device_put(data, dev)
        self._data = data

    # -- storage protocol --------------------------------------------------
    @property
    def data(self):
        if self._base is None:
            return self._data
        return self._base.data[self._key]

    def _set_data(self, new):
        """Rebind the whole buffer (in-place op semantics)."""
        if self._base is None:
            self._data = new
        else:
            self._base._write(self._key, new)

    def _write(self, key, value):
        if self._base is None:
            self._data = self._data.at[key].set(value)
        else:
            sub = self.data.at[key].set(value)
            self._base._write(self._key, sub)

    # -- basic properties --------------------------------------------------
    @property
    def shape(self):
        return tuple(self.data.shape)

    @property
    def dtype(self):
        return np.dtype(self.data.dtype)

    @property
    def size(self):
        return int(np.prod(self.shape)) if self.shape else 1

    @property
    def ndim(self):
        return self.data.ndim

    @property
    def stype(self):
        return "default"

    @property
    def context(self):
        d = self.data.device
        try:
            platform = d.platform
        except AttributeError:  # sharded array: take first device
            d = list(self.data.devices())[0]
            platform = d.platform
        if platform == "cpu":
            return Context("cpu", d.id)
        return Context("tpu", d.id)

    ctx = context

    @property
    def grad(self):
        return self._grad

    @property
    def T(self):
        return apply_op("transpose", self)

    def __repr__(self):
        return "%s\n<NDArray %s @%s>" % (
            np.asarray(self.data),
            "x".join(str(s) for s in self.shape),
            self.context,
        )

    def __len__(self):
        if not self.shape:
            raise TypeError("len() of 0-d NDArray")
        return self.shape[0]

    def __bool__(self):
        if self.size != 1:
            raise ValueError("ambiguous truth value of multi-element NDArray")
        _note_host_sync()
        return bool(np.asarray(self.data))

    def __float__(self):
        _note_host_sync()
        return float(np.asarray(self.data).reshape(())[()])

    def __int__(self):
        _note_host_sync()
        return int(np.asarray(self.data).reshape(())[()])

    def __index__(self):
        _note_host_sync()
        return int(np.asarray(self.data).reshape(())[()])

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # -- sync points -------------------------------------------------------
    # wait_to_read is jax.block_until_ready plus the host-sync counter:
    # on the attached chip the call returns when the producing
    # computation has finished (chip_smoke.py prints the evidence on
    # every run — ten chained 8192^2 matmuls, time to block_until_ready
    # against time to asnumpy).
    def asnumpy(self):
        """Blocking copy to host (ref: MXNDArraySyncCopyToCPU — the sync
        point where deferred errors surface)."""
        _note_host_sync()
        return np.asarray(self.data)

    def asscalar(self):
        if self.size != 1:
            raise ValueError("the array is not scalar-sized")
        return self.asnumpy().reshape(())[()]

    def item(self):
        return self.asscalar()

    def wait_to_read(self):
        _device_sync(self.data)
        return self

    wait_to_write = wait_to_read

    # -- placement / dtype -------------------------------------------------
    def as_in_context(self, ctx):
        if ctx == self.context:
            return self
        return NDArray(jax.device_put(self.data, ctx.jax_device))

    as_in_ctx = as_in_context

    def copyto(self, other):
        if isinstance(other, NDArray):
            other._set_data(jax.device_put(self.data, other.data.device))
            return other
        if isinstance(other, Context):
            return NDArray(jax.device_put(self.data, other.jax_device))
        raise TypeError("copyto target must be NDArray or Context")

    def copy(self):
        return NDArray(jnp.copy(self.data))

    def astype(self, dtype, copy=True):
        dt = get_dtype(dtype)
        if not copy and self.dtype == dt:
            return self
        return NDArray(self.data.astype(dt))

    def tostype(self, stype):
        if stype == "default":
            return self
        from ..sparse import cast_storage

        return cast_storage(self, stype)

    def detach(self):
        out = NDArray(self.data)
        return out

    def attach_grad(self, grad_req="write", stype=None):
        """Make this array an autograd leaf (ref: ndarray.py attach_grad)."""
        del stype
        from .. import autograd as ag

        self._grad = NDArray(jnp.zeros(self.shape, self.dtype))
        self._ag_node = (ag.AGLeaf(self, grad_req), 0)

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        from .. import autograd as ag

        ag.backward(self, out_grad, retain_graph=retain_graph,
                    train_mode=train_mode)

    # -- indexing ----------------------------------------------------------
    def __getitem__(self, key):
        from .. import autograd as ag

        nkey = _norm_key(key)
        if _is_basic_key(nkey) and not ag.is_recording():
            return NDArray(None, _base=self, _key=nkey)
        # recorded or advanced indexing → op (gradient flows)
        data = self.data[nkey] if not ag.is_recording() else None
        if data is not None:
            return NDArray(data)

        def _index_fn(x, _key=nkey):
            return x[_key]

        from ..ops.registry import Op

        return apply_op(Op("_getitem", _index_fn), self)

    def __setitem__(self, key, value):
        nkey = _norm_key(key)
        if isinstance(value, NDArray):
            value = value.data
        elif isinstance(value, numeric_types):
            value = jnp.asarray(value, self.dtype)
        else:
            value = jnp.asarray(value, self.dtype)
        self._write(nkey, value.astype(self.dtype))
        # mutation invalidates recorded op history, but an attach_grad leaf
        # stays a leaf (reference: params are initialized by slice-assign
        # after attach_grad and must still receive gradients)
        if self._ag_node is not None and not isinstance(
            self._ag_node[0], _leaf_type()
        ):
            self._ag_node = None

    # -- arithmetic --------------------------------------------------------
    def _binary(self, other, op_name, scalar_op, rscalar_op=None, reverse=False):
        if isinstance(other, NDArray):
            a, b = (other, self) if reverse else (self, other)
            return apply_op(op_name, a, b)
        if isinstance(other, numeric_types):
            name = (rscalar_op or scalar_op) if reverse else scalar_op
            return apply_op(name, self, scalar=float(other))
        if isinstance(other, np.ndarray):
            o = NDArray(other, dtype=self.dtype)
            a, b = (o, self) if reverse else (self, o)
            return apply_op(op_name, a, b)
        return NotImplemented

    def __add__(self, o):
        return self._binary(o, "broadcast_add", "_plus_scalar")

    def __radd__(self, o):
        return self._binary(o, "broadcast_add", "_plus_scalar", reverse=True)

    def __sub__(self, o):
        return self._binary(o, "broadcast_sub", "_minus_scalar", "_rminus_scalar")

    def __rsub__(self, o):
        return self._binary(o, "broadcast_sub", "_minus_scalar", "_rminus_scalar",
                            reverse=True)

    def __mul__(self, o):
        return self._binary(o, "broadcast_mul", "_mul_scalar")

    def __rmul__(self, o):
        return self._binary(o, "broadcast_mul", "_mul_scalar", reverse=True)

    def __truediv__(self, o):
        return self._binary(o, "broadcast_div", "_div_scalar", "_rdiv_scalar")

    def __rtruediv__(self, o):
        return self._binary(o, "broadcast_div", "_div_scalar", "_rdiv_scalar",
                            reverse=True)

    def __mod__(self, o):
        return self._binary(o, "broadcast_mod", "_mod_scalar", "_rmod_scalar")

    def __rmod__(self, o):
        return self._binary(o, "broadcast_mod", "_mod_scalar", "_rmod_scalar",
                            reverse=True)

    def __pow__(self, o):
        return self._binary(o, "broadcast_power", "_power_scalar", "_rpower_scalar")

    def __rpow__(self, o):
        return self._binary(o, "broadcast_power", "_power_scalar",
                            "_rpower_scalar", reverse=True)

    def __neg__(self):
        return apply_op("negative", self)

    def __abs__(self):
        return apply_op("abs", self)

    def __eq__(self, o):
        if o is None:
            return False
        return self._binary(o, "broadcast_equal", "_equal_scalar")

    def __ne__(self, o):
        if o is None:
            return True
        return self._binary(o, "broadcast_not_equal", "_not_equal_scalar")

    def __lt__(self, o):
        return self._binary(o, "broadcast_lesser", "_lesser_scalar")

    def __le__(self, o):
        return self._binary(o, "broadcast_lesser_equal", "_lesser_equal_scalar")

    def __gt__(self, o):
        return self._binary(o, "broadcast_greater", "_greater_scalar")

    def __ge__(self, o):
        return self._binary(o, "broadcast_greater_equal", "_greater_equal_scalar")

    __hash__ = object.__hash__

    def _inplace(self, res):
        # keep autograd history coherent: the in-place result replaces both
        # the buffer and the recorded node (a dropped node would make
        # backward silently use the pre-mutation graph)
        self._set_data(res.data)
        if not isinstance(self._ag_node, tuple) or not isinstance(
            self._ag_node[0], _leaf_type()
        ):
            self._ag_node = res._ag_node
        return self

    def __iadd__(self, o):
        return self._inplace(self.__add__(o))

    def __isub__(self, o):
        return self._inplace(self.__sub__(o))

    def __imul__(self, o):
        return self._inplace(self.__mul__(o))

    def __itruediv__(self, o):
        return self._inplace(self.__truediv__(o))

    # -- op-method fallback ------------------------------------------------
    def __getattr__(self, name):
        # called only when normal lookup fails; route to registered ops so
        # x.relu(), x.sum(axis=1), x.reshape(...) etc. all work.
        try:
            op = get_op(name)
        except KeyError:
            raise AttributeError(
                "'NDArray' object has no attribute %r" % (name,)
            ) from None
        import functools

        return functools.partial(apply_op, op, self)

    # explicit methods whose names differ from op names or need sugar
    def reshape(self, *shape, **kwargs):
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        if not shape and "shape" in kwargs:
            shape = tuple(kwargs.pop("shape"))
        return apply_op("reshape", self, shape=shape, **kwargs)

    def reshape_like(self, other):
        return apply_op("reshape", self, shape=other.shape)

    def broadcast_to(self, shape):
        return apply_op("broadcast_to", self, shape=tuple(shape))

    def broadcast_like(self, other):
        return apply_op("broadcast_like", self, other)

    def transpose(self, *axes):
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        return apply_op("transpose", self, axes=axes if axes else None)

    def astype_like(self, other):
        return self.astype(other.dtype)

    def dot(self, other, **kwargs):
        return apply_op("dot", self, other, **kwargs)

    def norm(self, **kwargs):
        return apply_op("norm", self, **kwargs)

    def square(self):
        return apply_op("square", self)

    def as_np_ndarray(self):
        return self

    def tolist(self):
        return self.asnumpy().tolist()


def _wrap_outputs(raw):
    if isinstance(raw, (tuple, list)):
        return [NDArray(r) for r in raw]
    return NDArray(raw)


# --------------------------------------------------------------------------
# creation (ref: src/operator/tensor/init_op.cc + python ndarray/utils.py)
# --------------------------------------------------------------------------
def _creation_ctx(ctx):
    return (ctx or current_context()).jax_device


def array(source_array, ctx=None, dtype=None):
    return NDArray(source_array, ctx=ctx, dtype=dtype)


def empty(shape, ctx=None, dtype=None):
    return zeros(shape, ctx=ctx, dtype=dtype)


def zeros(shape, ctx=None, dtype=None, **kwargs):
    if isinstance(shape, int):
        shape = (shape,)
    with jax.default_device(_creation_ctx(ctx)):
        return NDArray(jnp.zeros(tuple(shape), get_dtype(dtype)))


def ones(shape, ctx=None, dtype=None, **kwargs):
    if isinstance(shape, int):
        shape = (shape,)
    with jax.default_device(_creation_ctx(ctx)):
        return NDArray(jnp.ones(tuple(shape), get_dtype(dtype)))


def full(shape, val, ctx=None, dtype=None):
    if isinstance(shape, int):
        shape = (shape,)
    with jax.default_device(_creation_ctx(ctx)):
        return NDArray(jnp.full(tuple(shape), val, get_dtype(dtype)))


def arange(start, stop=None, step=1.0, repeat=1, ctx=None, dtype=None):
    with jax.default_device(_creation_ctx(ctx)):
        out = jnp.arange(start, stop, step, get_dtype(dtype))
        if repeat != 1:
            out = jnp.repeat(out, repeat)
        return NDArray(out)


def linspace(start, stop, num, endpoint=True, ctx=None, dtype=None):
    with jax.default_device(_creation_ctx(ctx)):
        return NDArray(jnp.linspace(start, stop, num, endpoint=endpoint,
                                    dtype=get_dtype(dtype)))


def eye(N, M=0, k=0, ctx=None, dtype=None):
    with jax.default_device(_creation_ctx(ctx)):
        return NDArray(jnp.eye(N, M if M else None, k, get_dtype(dtype)))


def zeros_like(arr):
    return NDArray(jnp.zeros_like(arr.data))


def ones_like(arr):
    return NDArray(jnp.ones_like(arr.data))


def moveaxis(arr, source, destination):
    return NDArray(jnp.moveaxis(arr.data, source, destination))


def concatenate(arrays, axis=0):
    return apply_op("concat", *arrays, dim=axis)


_record_host_sync = None


def _note_host_sync():
    """Bump the profiler's host_syncs counter (lazy import: profiler is
    not yet importable while this module loads)."""
    global _record_host_sync
    if _record_host_sync is None:
        from .. import profiler

        _record_host_sync = profiler.record_host_sync
    _record_host_sync()


def _device_sync(d):
    """Wait until the computation producing ``d`` has finished, and
    count the wait as one host sync (see the sync-points note above)."""
    if getattr(d, "size", 0) == 0:
        return
    _note_host_sync()
    jax.block_until_ready(d)  # sync-ok: this IS the wait


def waitall():
    """Global sync barrier (ref: Engine::WaitForAll). Drains the async
    engine's in-flight step window first — deferred guard flags and their
    bookkeeping (update counts, loss-scale, skipped-step counter) land
    before this returns, so tests and chaos_matrix.sh can rely on it as
    a barrier — then blocks on XLA's effects barrier. Also flushes the
    telemetry JSONL sink: everything observed up to the barrier is on
    disk when this returns."""
    from .. import engine

    engine.wait_all()
    try:
        jax.effects_barrier()
    except Exception:
        pass
    from .. import telemetry

    telemetry.flush()


# --------------------------------------------------------------------------
# save / load (ref: src/ndarray/ndarray.cc — NDArray::Save/Load; C API
# MXNDArraySave/MXNDArrayLoad). Writes the reference's magic-tagged binary
# list format (mx_binary.py) so ``.params`` files cross the boundary in
# both directions; ``load`` additionally still reads the npz files earlier
# rounds of this framework wrote (format detected from the first bytes).
# --------------------------------------------------------------------------
def save(fname, data):
    from . import mx_binary
    if isinstance(data, NDArray):
        data = [data]
    if isinstance(data, (list, tuple)):
        arrays, names = list(data), []
    elif isinstance(data, dict):
        names = list(data.keys())
        arrays = [data[k] for k in names]
    else:
        raise TypeError("save expects NDArray, list, or dict")
    for a in arrays:
        if not isinstance(a, NDArray):
            raise TypeError("save expects NDArray values, got %r" % (a,))
    with open(fname, "wb") as f:
        f.write(mx_binary.dumps(arrays, names))


def load(fname):
    from . import mx_binary
    with open(fname, "rb") as f:
        head = f.read(8)
        if mx_binary.is_mx_binary(head):
            arrays, names = mx_binary.loads(head + f.read())
            if names:
                return dict(zip(names, arrays))
            return arrays
    # npz fallback (this framework's pre-r5 byte format)
    with np.load(fname, allow_pickle=False) as zf:
        keys = list(zf.keys())
        if keys and all(k.startswith("__mxt_list_") for k in keys):
            keys.sort(key=lambda k: int(k.rsplit("_", 1)[1]))
            return [NDArray(zf[k]) for k in keys]
        return {k: NDArray(zf[k]) for k in keys}
