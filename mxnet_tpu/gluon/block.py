"""Block / HybridBlock — the Gluon model layer
(ref: python/mxnet/gluon/block.py).

TPU-native CachedOp: ``hybridize()`` makes the block's whole forward ONE
jitted XLA program (ref: src/imperative/cached_op.cc — CachedOp::Forward;
the reference traces to an nnvm graph, we trace to a jaxpr). Parameters are
passed as traced inputs so gradients flow to their autograd leaves; aux-state
mutation inside the trace (BatchNorm running stats) is captured by rebind
detection and returned as extra outputs, then written back — replicating the
reference's in-kernel aux mutation without side effects in the trace.
"""
from __future__ import annotations

import re
import threading
from collections import OrderedDict

import jax
import numpy as np

from ..base import MXNetError
from .. import autograd as ag
from .. import random as _random
from ..ndarray.ndarray import NDArray
from ..ndarray import ndarray as _nd
from ..ops.registry import Op, apply_op
from .parameter import (
    Parameter, ParameterDict, DeferredInitializationError, param_trace_scope,
)

__all__ = ["Block", "HybridBlock"]


class _NameManager(threading.local):
    def __init__(self):
        super().__init__()
        self.counters = {}

    def get(self, hint):
        n = self.counters.get(hint, 0)
        self.counters[hint] = n + 1
        return "%s%d_" % (hint, n)


_name_manager = _NameManager()


class _BlockScope:
    """Per-block naming scope (ref: block.py — _BlockScope)."""

    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old = None

    @staticmethod
    def create(prefix, params, hint):
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                prefix = _name_manager.get(hint)
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            current._counter[hint] = count + 1
            prefix = "%s%d_" % (hint, count)
        if params is None:
            parent = current._block._params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        if self._block._empty_prefix:
            return self
        self._old = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        return self

    def __exit__(self, *args):
        if self._block._empty_prefix:
            return
        _BlockScope._current.value = self._old


class _TraceDepth(threading.local):
    def __init__(self):
        super().__init__()
        self.depth = 0
        self.prefix = ""  # of the block whose scope a trace is inside


_trace_depth = _TraceDepth()


class Block:
    """Base model-composition unit (ref: gluon/block.py — Block)."""

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(
            prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") \
            else self._prefix
        self._scope = _BlockScope(self)
        self._children = OrderedDict()
        self._reg_params = {}
        self._forward_hooks = []
        self._forward_pre_hooks = []

    def _alias(self):
        return type(self).__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    @property
    def params(self):
        return self._params

    def name_scope(self):
        return self._scope

    def __repr__(self):
        lines = [type(self).__name__ + "("]
        for key, child in self._children.items():
            child_repr = repr(child).replace("\n", "\n  ")
            lines.append("  (%s): %s" % (key, child_repr))
        lines.append(")")
        return "\n".join(lines)

    def __setattr__(self, name, value):
        if isinstance(value, Block):
            existing = self.__dict__.get("_children")
            if existing is not None:
                existing[name] = value
        elif isinstance(value, Parameter):
            reg = self.__dict__.get("_reg_params")
            if reg is not None:
                reg[name] = value
        super().__setattr__(name, value)

    def register_child(self, block, name=None):
        if name is None:
            name = str(len(self._children))
        self._children[name] = block

    def register_forward_hook(self, hook):
        self._forward_hooks.append(hook)
        return hook

    def register_forward_pre_hook(self, hook):
        self._forward_pre_hooks.append(hook)
        return hook

    def apply(self, fn):
        for child in self._children.values():
            child.apply(fn)
        fn(self)
        return self

    def collect_params(self, select=None):
        ret = ParameterDict(self._params.prefix)
        if select is None:
            ret.update(self._params)
        else:
            pat = re.compile(select)
            ret.update({
                name: p for name, p in self._params.items()
                if pat.match(name)
            })
        for child in self._children.values():
            ret.update(child.collect_params(select=select))
        return ret

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        self.collect_params().initialize(init, ctx, verbose, force_reinit)

    def cast(self, dtype):
        for child in self._children.values():
            child.cast(dtype)
        for p in self._params.values():
            p.cast(dtype)

    # -- structural save/load (ref: block.py — save_parameters uses
    # attribute-path keys, not prefixed names) -----------------------------
    def _collect_params_with_prefix(self, prefix=""):
        if prefix:
            prefix += "."
        ret = {prefix + key: val for key, val in self._reg_params.items()}
        for name, child in self._children.items():
            ret.update(child._collect_params_with_prefix(prefix + name))
        return ret

    def save_parameters(self, filename, deduplicate=False):
        params = self._collect_params_with_prefix()
        payload = {}
        seen = {}
        for key, p in params.items():
            if deduplicate and id(p) in seen:
                continue
            seen[id(p)] = key
            payload[key] = p.data()
        _nd.save(filename, payload)

    def load_parameters(self, filename, ctx=None, allow_missing=False,
                        ignore_extra=False, cast_dtype=False,
                        dtype_source="current"):
        del cast_dtype, dtype_source
        loaded = _nd.load(filename)
        params = self._collect_params_with_prefix()
        if not loaded and not params:
            return
        # legacy files may carry full-name keys (ParameterDict.save)
        if loaded and not any(k in params for k in loaded):
            self.collect_params().load(
                filename, ctx, allow_missing, ignore_extra)
            return
        for name, p in params.items():
            if name in loaded:
                p.set_data(loaded[name].as_in_context(
                    ctx if ctx is not None else loaded[name].context))
            elif not allow_missing:
                raise MXNetError(
                    "parameter %s missing in file %s" % (name, filename))
        if not ignore_extra:
            extra = set(loaded) - set(params)
            if extra:
                raise MXNetError(
                    "file %s has parameters not in this block: %s"
                    % (filename, sorted(extra)))

    # ------------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        for hook in self._forward_pre_hooks:
            hook(self, args)
        if _trace_depth.depth and args and isinstance(
                getattr(args[0], "data", None), jax.core.Tracer):
            out = self._traced_forward(args, kwargs)
        else:
            out = self.forward(*args, **kwargs)
        for hook in self._forward_hooks:
            hook(self, args, out)
        return out

    def forward(self, *args, **kwargs):
        raise NotImplementedError

    def _traced_forward(self, args, kwargs):
        """``forward`` under ``jax.named_scope(<this block's name>)``, the
        parent block's prefix taken off (a block that adds no prefix of its
        own, ``prefix=""``, goes by its type), so that the compiled
        program's operations, and the device trace, carry
        ``forward/resnetv10/stage1/bottleneckv1/conv2d0`` (profiler_trace.py
        reads it back). Entered only while JAX traces: a compiled step pays
        nothing and the eager path one comparison."""
        outer = _trace_depth.prefix
        name = self._name
        if self._prefix == outer:
            name = ""
        elif outer and name.startswith(outer):
            name = name[len(outer):]
        _trace_depth.prefix = self._prefix
        try:
            with jax.named_scope(name or self._alias()):
                return self.forward(*args, **kwargs)
        finally:
            _trace_depth.prefix = outer

    def summary(self, *inputs):
        out = self(*inputs)
        n_params = sum(
            int(np.prod(p.shape)) for p in self.collect_params().values()
            if p.shape is not None
        )
        print("%s: %d parameters, output %s" % (
            self.name, n_params,
            out.shape if hasattr(out, "shape") else type(out)))
        return out


class HybridBlock(Block):
    """Block whose forward can be compiled into one XLA program
    (ref: gluon/block.py — HybridBlock; hybridize() ≈ CachedOp ≈ jax.jit)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._active = False
        self._jit_cache = {}
        self._flags = {}
        # flat (sorted, initialized) Parameter list for _call_cached_op;
        # rebuilding it from collect_params() every call walks the whole
        # block tree — real per-step Python overhead on the hot path
        self._cached_flat_params = None

    def hybridize(self, active=True, static_alloc=False, static_shape=False,
                  **kwargs):
        """Compile subsequent forwards (ref: block.py — hybridize).
        static_alloc/static_shape are accepted for API parity; XLA always
        plans memory statically (buffer donation covers static_alloc)."""
        self._flags = dict(static_alloc=static_alloc,
                           static_shape=static_shape, **kwargs)
        self._active = active
        self._jit_cache = {}
        self._cached_flat_params = None
        for child in self._children.values():
            if isinstance(child, HybridBlock):
                child.hybridize(active, static_alloc=static_alloc,
                                static_shape=static_shape, **kwargs)

    def cast(self, dtype):
        self._jit_cache = {}
        self._cached_flat_params = None
        super().cast(dtype)

    def infer_shape(self, *args):
        """Layers with deferred-shape params override this; composite blocks
        don't need it (children infer for themselves)."""
        raise MXNetError(
            "%s has deferred-init parameters but does not implement "
            "infer_shape; give explicit shapes (e.g. in_units/in_channels) "
            "or implement infer_shape" % (type(self).__name__,))

    # ------------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        from ..symbol.symbol import Symbol

        if args and isinstance(args[0], Symbol):
            return Block.__call__(self, *args, **kwargs)
        if self._active and _trace_depth.depth == 0:
            return self._call_cached_op(*args, **kwargs)
        return super().__call__(*args, **kwargs)

    def forward(self, x, *args, **kwargs):
        from ..symbol.symbol import Symbol

        if isinstance(x, Symbol):
            # symbolic trace (export / SymbolBlock): params become variables
            from .. import symbol as F

            params = {k: p.var() for k, p in self._reg_params.items()}
            return self.hybrid_forward(F, x, *args, **params, **kwargs)
        try:
            params = {k: p.data() for k, p in self._reg_params.items()}
        except DeferredInitializationError:
            self._deferred_infer(x, *args)
            params = {k: p.data() for k, p in self._reg_params.items()}
        from .. import ndarray as F

        return self.hybrid_forward(F, x, *args, **params, **kwargs)

    def _deferred_infer(self, *args):
        self.infer_shape(*args)
        for p in self._reg_params.values():
            if p._deferred_init is not None:
                p._finish_deferred_init()

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    # -- CachedOp ------------------------------------------------------
    def _ensure_initialized(self, *args):
        """Finish any deferred inits by one throwaway eager forward in
        predict mode (shape inference happens layer-locally on call)."""
        needs = any(
            p._deferred_init is not None
            for p in self.collect_params().values()
        )
        if not needs:
            return
        from ..profiler import setup_scope

        with setup_scope("infer_shapes"), ag.pause(train_mode=False):
            _trace_depth.depth += 1
            try:
                super().__call__(*args)
            finally:
                _trace_depth.depth -= 1

    def _call_cached_op(self, *args, **kwargs):
        if kwargs:
            # keyword inputs fall back to eager (rare; matches CachedOp's
            # positional-only calling convention)
            return super().__call__(*args, **kwargs)
        from ..parallel.sequence import current_sequence_scope

        if current_sequence_scope() is not None:
            # a single-device whole-block jit cannot host the scope's
            # multi-device shard_map; run op-by-op eager instead — the
            # ring attention itself is still one compiled program, and
            # a stale non-ring trace is never reused inside the scope
            return super().__call__(*args, **kwargs)
        self._ensure_initialized(*args)
        param_objs = self._cached_flat_params
        if param_objs is None:
            # built once after deferred init resolves; invalidated by
            # hybridize()/cast() (structural changes require re-hybridize,
            # matching CachedOp). Buffers are NOT cached — p.data() below
            # stays live across set_data/force_reinit rebinds.
            param_objs = [
                p for _, p in sorted(self.collect_params().items())
                if p._data is not None
            ]
            self._cached_flat_params = param_objs
        param_nds = [p.data() for p in param_objs]
        train = ag.is_training()
        entry = self._jit_cache.get(train)
        if entry is None:
            entry = self._build_cached(train, param_objs)
            self._jit_cache[train] = entry
        jfn, meta, op = entry

        key = _random.new_key()
        flat_inputs = list(args) + param_nds + [key]
        result = apply_op(op, *flat_inputs)
        if not isinstance(result, tuple):
            result = (result,)
        n_outs = meta["n_outs"]
        outs = result[:n_outs]
        aux_vals = result[n_outs:]
        with ag.pause():
            for idx, val in zip(meta["aux_idx"], aux_vals):
                param_objs[idx]._data._set_data(val.data)
        if n_outs == 1:
            return outs[0]
        return list(outs)

    def _build_cached(self, train, param_objs):
        meta = {"n_outs": None, "aux_idx": None}
        block = self

        def raw_fn(*flat):
            n_params = len(param_objs)
            input_datas = flat[: len(flat) - n_params - 1]
            param_datas = flat[len(flat) - n_params - 1: -1]
            key = flat[-1]
            wrappers = [NDArray(d) for d in param_datas]
            mapping = dict(zip(param_objs, wrappers))
            _trace_depth.depth += 1
            try:
                with ag.pause(train_mode=train), _random.key_scope(key), \
                        param_trace_scope(mapping):
                    ins = [NDArray(d) for d in input_datas]
                    out = Block.__call__(block, *ins)
            finally:
                _trace_depth.depth -= 1
            outs = list(out) if isinstance(out, (list, tuple)) else [out]
            out_datas = [o.data for o in outs]
            aux_idx = []
            aux_datas = []
            for i, (w, d0) in enumerate(zip(wrappers, param_datas)):
                if w._data is not d0:  # aux state rebound during trace
                    aux_idx.append(i)
                    aux_datas.append(jax.lax.stop_gradient(w._data))
            meta["n_outs"] = len(out_datas)
            meta["aux_idx"] = aux_idx
            return tuple(out_datas) + tuple(aux_datas)

        jfn = jax.jit(raw_fn)
        op = Op("cached_op_%s" % self.name, jfn, differentiable=True)
        return jfn, meta, op

    # -- symbolic export (P6 wires this to Symbol/JSON) ----------------
    def export(self, path, epoch=0):
        from ..symbol.export import export_block

        return export_block(self, path, epoch)
