"""Parameter / ParameterDict (ref: python/mxnet/gluon/parameter.py).

A Parameter owns one primary NDArray (data-parallel replication is handled
by the Trainer/KVStore layer over shardings, not by per-ctx copies as in the
reference — on TPU the mesh owns placement). Deferred init mirrors the
reference: unknown dims are 0 until the first forward infers them.

Trace support: while a CachedOp (hybridize) trace is running, ``data()``
returns the traced stand-in installed by the trace scope, so the same layer
code serves eager and compiled paths.
"""
from __future__ import annotations

import threading
from collections import OrderedDict

import numpy as np

from ..base import MXNetError, get_dtype
from ..context import current_context
from ..ndarray import ndarray as _nd
from ..ndarray.ndarray import NDArray
from .. import initializer as init_mod
from ..profiler import setup_scope

__all__ = ["Parameter", "Constant", "ParameterDict", "DeferredInitializationError",
           "param_trace_scope", "tracing_override"]


class DeferredInitializationError(MXNetError):
    """Raised when a deferred-init parameter's data is requested before the
    first forward (ref: parameter.py — DeferredInitializationError)."""


class _TraceState(threading.local):
    def __init__(self):
        super().__init__()
        self.maps = []


_trace_state = _TraceState()


class param_trace_scope:
    """Installs {Parameter -> NDArray} overrides during a CachedOp trace."""

    def __init__(self, mapping):
        self._mapping = mapping

    def __enter__(self):
        _trace_state.maps.append(self._mapping)
        return self

    def __exit__(self, *args):
        _trace_state.maps.pop()


def tracing_override(param):
    for m in reversed(_trace_state.maps):
        if param in m:
            return m[param]
    return None


class Parameter:
    def __init__(self, name, grad_req="write", shape=None, dtype="float32",
                 lr_mult=1.0, wd_mult=1.0, init=None,
                 allow_deferred_init=False, differentiable=True,
                 stype="default", grad_stype="default"):
        self.name = name
        self._grad_req = grad_req if differentiable else "null"
        if isinstance(shape, int):
            shape = (shape,)
        self._shape = tuple(shape) if shape is not None else None
        self.dtype = get_dtype(dtype)
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._differentiable = differentiable
        self._stype = stype
        self._grad_stype = grad_stype
        self._data = None  # NDArray
        self._deferred_init = None  # (initializer, ctx)

    # ------------------------------------------------------------------
    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(new_shape)
            return
        unknown = any(s == 0 or s == -1 for s in self._shape)
        if not unknown and tuple(new_shape) != self._shape:
            raise MXNetError(
                "cannot reset shape of %s from %s to %s"
                % (self.name, self._shape, tuple(new_shape)))
        merged = tuple(
            n if (s in (0, -1)) else s
            for s, n in zip(self._shape, new_shape)
        )
        self._shape = merged

    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        if req not in ("write", "add", "null"):
            raise MXNetError("invalid grad_req %r" % (req,))
        if not self._differentiable:
            req = "null"
        self._grad_req = req
        if self._data is not None:
            if req == "null":
                self._data._grad = None
                self._data._ag_node = None
            else:
                self._data.attach_grad(req)

    @property
    def stype(self):
        return self._stype

    def _shape_incomplete(self):
        return self._shape is None or any(s in (0, -1) for s in self._shape)

    # ------------------------------------------------------------------
    def initialize(self, init=None, ctx=None, default_init=None,
                   force_reinit=False):
        if self._data is not None and not force_reinit:
            return
        if isinstance(ctx, (list, tuple)):
            ctx = ctx[0] if ctx else None
        ctx = ctx or current_context()
        default_init = default_init or init_mod.Uniform(0.07)
        initializer = self.init or init or default_init
        if self._shape_incomplete():
            if self.allow_deferred_init:
                self._deferred_init = (initializer, ctx)
                return
            raise MXNetError(
                "cannot initialize %s: shape %s is incomplete and deferred "
                "init is not allowed" % (self.name, self._shape))
        self._init_impl(initializer, ctx)

    def _init_impl(self, initializer, ctx):
        with setup_scope("initialize"):
            arr = _nd.zeros(self._shape, ctx=ctx, dtype=self.dtype)
            if isinstance(initializer, str):
                initializer = init_mod.create(initializer)
            # a param-specific init rides in InitDesc attrs and bypasses
            # name-suffix dispatch (so bias_initializer='ones' actually wins)
            attrs = {"__init__": self.init} if self.init is not None else {}
            initializer(init_mod.InitDesc(self.name, attrs), arr)
        self._data = arr
        self._deferred_init = None
        if self._grad_req != "null":
            arr.attach_grad(self._grad_req)

    def _finish_deferred_init(self):
        if self._deferred_init is None:
            return
        if self._shape_incomplete():
            raise DeferredInitializationError(
                "parameter %s shape still incomplete: %s"
                % (self.name, self._shape))
        initializer, ctx = self._deferred_init
        self._init_impl(initializer, ctx)

    # ------------------------------------------------------------------
    def data(self, ctx=None):
        traced = tracing_override(self)
        if traced is not None:
            return traced
        if self._data is None:
            if self._deferred_init is not None:
                raise DeferredInitializationError(
                    "parameter %s deferred init not complete; run a forward "
                    "pass or set shape" % (self.name,))
            raise MXNetError(
                "parameter %s has not been initialized; call .initialize()"
                % (self.name,))
        del ctx  # single storage; Trainer/mesh own placement
        return self._data

    def list_data(self):
        return [self.data()]

    def grad(self, ctx=None):
        del ctx
        d = self.data()
        if d._grad is None:
            raise MXNetError(
                "parameter %s has grad_req='null'; no gradient buffer"
                % (self.name,))
        if self._grad_stype == "row_sparse":
            # TPU-native split (sparse.py design note): inside XLA the
            # embedding backward is a dense scatter-add; the row_sparse
            # view materializes here, at the framework boundary, so
            # Trainer/KVStore push and the optimizer update touch only
            # rows with nonzero gradient (ref: Embedding sparse_grad +
            # _sparse_*_update lazy semantics).
            # DOCUMENTED DEVIATION: rows are recovered from the dense
            # buffer's nonzero rows, not from the batch's index list —
            # a batch-touched row whose gradient cancels to exactly 0
            # is treated as untouched (skipping its wd/momentum decay),
            # where the reference would include it.
            from ..sparse import row_sparse_array
            return row_sparse_array(d._grad)
        return d._grad

    def list_grad(self):
        return [self.grad()]

    def list_ctx(self):
        if self._data is None:
            if self._deferred_init is not None:
                return [self._deferred_init[1]]
            raise MXNetError("parameter %s not initialized" % (self.name,))
        return [self._data.context]

    def set_data(self, data):
        if isinstance(data, NDArray):
            data = data.data
        import jax.numpy as jnp

        # shape setter raises on mismatch — keeps param.shape, the buffer,
        # and the grad buffer in sync (checkpoint loads with wrong shapes
        # must fail here, not deep inside XLA later)
        self.shape = tuple(data.shape)
        if self._data is None:
            self._deferred_init_default()
        # copy, never alias: the reference's set_data writes INTO the
        # param's own storage, and an aliased buffer would be invalidated
        # for this param when the source param's trainer donates it
        # (jax.jit donate_argnums in _FusedUpdate / ShardedTrainStep)
        with setup_scope("place"):
            self._data._set_data(jnp.array(data, dtype=self.dtype, copy=True))

    def _deferred_init_default(self):
        if self._data is None:
            if self._deferred_init is not None:
                self._finish_deferred_init()
            else:
                self._init_impl(init_mod.Zero(), current_context())

    def zero_grad(self):
        d = self._data
        if d is not None and d._grad is not None:
            import jax.numpy as jnp

            d._grad._set_data(jnp.zeros(d.shape, d.dtype))

    def reset_ctx(self, ctx):
        if self._data is not None:
            self._data = self._data.as_in_context(ctx)
            if self._grad_req != "null":
                self._data.attach_grad(self._grad_req)

    def cast(self, dtype):
        self.dtype = get_dtype(dtype)
        if self._data is not None:
            with setup_scope("cast"):
                had_grad = self._data._grad is not None
                self._data = self._data.astype(self.dtype)
                if had_grad:
                    self._data.attach_grad(self._grad_req)

    def var(self):
        from ..symbol.symbol import var

        return var(self.name, shape=self._shape, dtype=self.dtype)

    def __repr__(self):
        return "Parameter %s (shape=%s, dtype=%s)" % (
            self.name, self._shape, np.dtype(self.dtype).name)


class _ValueInit(init_mod.Initializer):
    """Fills with a fixed array — backs Constant so force_reinit restores
    the constant's value instead of zeroing it."""

    def __init__(self, value_np):
        super().__init__()
        self._value = value_np

    def _init_weight(self, name, arr):
        self._fill(arr, self._value)


class Constant(Parameter):
    """Non-learnable constant parameter (ref: parameter.py — Constant)."""

    def __init__(self, name, value):
        if not isinstance(value, NDArray):
            value = _nd.array(value)
        self.value = value
        super().__init__(name, grad_req="null", shape=value.shape,
                         dtype=value.dtype,
                         init=_ValueInit(value.asnumpy()),
                         differentiable=False)
        self._data = value


class ParameterDict:
    """Ordered name→Parameter mapping with a shared prefix
    (ref: parameter.py — ParameterDict)."""

    def __init__(self, prefix="", shared=None):
        self._prefix = prefix
        self._params = OrderedDict()
        self._shared = shared

    @property
    def prefix(self):
        return self._prefix

    def __getitem__(self, key):
        return self._params[key]

    def __iter__(self):
        return iter(self._params)

    def __len__(self):
        return len(self._params)

    def items(self):
        return self._params.items()

    def keys(self):
        return self._params.keys()

    def values(self):
        return self._params.values()

    def get(self, name, **kwargs):
        """Get-or-create parameter ``prefix+name``."""
        full = self._prefix + name
        if self._shared is not None and full in self._shared._params:
            # record the shared hit locally too (ref: parameter.py —
            # ParameterDict.get inserts found shared params): a tied
            # parameter must appear in the borrowing block's
            # collect_params(), else CachedOp traces it as a baked-in
            # constant instead of a live input (fatal once the trainer
            # donates the underlying buffer)
            param = self._shared._params[full]
            shape = kwargs.get("shape")
            if shape is not None and param.shape is not None:
                want, have = tuple(shape), tuple(param.shape)
                if len(want) != len(have) or any(
                        w and h and w != h for w, h in zip(want, have)):
                    raise MXNetError(
                        "tied parameter %s has shape %s, incompatible "
                        "with requested %s (ref: get() validates against "
                        "a shared-found parameter)" % (full, have, want))
            dtype = kwargs.get("dtype")
            if dtype is not None and param.dtype is not None and \
                    np.dtype(dtype) != np.dtype(param.dtype):
                raise MXNetError(
                    "tied parameter %s has dtype %s, incompatible with "
                    "requested %s" % (full, param.dtype, dtype))
            self._params[full] = param
            return param
        if full in self._params:
            param = self._params[full]
            for k, v in kwargs.items():
                if k == "shape" and v is not None:
                    param.shape = v
            return param
        param = Parameter(full, **kwargs)
        self._params[full] = param
        return param

    def get_constant(self, name, value=None):
        full = self._prefix + name
        if full in self._params:
            return self._params[full]
        c = Constant(full, value)
        self._params[full] = c
        return c

    def update(self, other):
        for k, v in other.items():
            if k in self._params and self._params[k] is not v:
                raise MXNetError("duplicate parameter name %s" % (k,))
            self._params[k] = v

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        del verbose
        for p in self._params.values():
            p.initialize(init=init, ctx=ctx, force_reinit=force_reinit)

    def zero_grad(self):
        for p in self._params.values():
            p.zero_grad()

    def setattr(self, name, value):
        for p in self._params.values():
            setattr(p, name, value)

    def reset_ctx(self, ctx):
        for p in self._params.values():
            p.reset_ctx(ctx)

    def save(self, fname, strip_prefix=""):
        payload = {}
        for name, p in self._params.items():
            key = name[len(strip_prefix):] if name.startswith(strip_prefix) \
                else name
            payload[key] = p.data()
        _nd.save(fname, payload)

    def load(self, fname, ctx=None, allow_missing=False,
             ignore_extra=False, restore_prefix=""):
        loaded = _nd.load(fname)
        loaded = {restore_prefix + k: v for k, v in loaded.items()}
        for name, p in self._params.items():
            if name in loaded:
                p.set_data(loaded[name])
            elif not allow_missing:
                raise MXNetError("parameter %s missing from %s" % (name, fname))
        if not ignore_extra:
            extra = set(loaded) - set(self._params)
            if extra:
                raise MXNetError(
                    "file %s contains extra parameters: %s" % (fname, extra))

    def __repr__(self):
        return "ParameterDict(%s)" % (", ".join(self._params),)
