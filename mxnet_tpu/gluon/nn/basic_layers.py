"""Core Gluon layers (ref: python/mxnet/gluon/nn/basic_layers.py)."""
from __future__ import annotations

import numpy as np

from ... import autograd
from ...base import MXNetError
from ..block import Block, HybridBlock
from .layout import resolve_norm_axis

__all__ = ["Sequential", "HybridSequential", "Dense", "Dropout", "BatchNorm",
           "SyncBatchNorm",
           "Embedding", "Flatten", "Lambda", "HybridLambda", "Activation",
           "LayerNorm", "InstanceNorm", "GroupNorm", "RMSNorm", "GatedRMSNorm",
           "RMSNormSigmoidGate", "SwiGLU"]


class Sequential(Block):
    """Stack of Blocks executed in order (ref: basic_layers.py — Sequential)."""

    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def forward(self, x, *args):
        for block in self._children.values():
            x = block(x)
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, key):
        layers = list(self._children.values())
        if isinstance(key, slice):
            net = type(self)(prefix=self._prefix)
            with net.name_scope():
                for layer in layers[key]:
                    net.add(layer)
            return net
        return layers[key]

    def __iter__(self):
        return iter(self._children.values())


class HybridSequential(HybridBlock):
    def __init__(self, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)

    def add(self, *blocks):
        for block in blocks:
            self.register_child(block)

    def hybrid_forward(self, F, x):
        for block in self._children.values():
            x = block(x)
        return x

    def __len__(self):
        return len(self._children)

    def __getitem__(self, key):
        layers = list(self._children.values())
        if isinstance(key, slice):
            net = type(self)(prefix=self._prefix)
            with net.name_scope():
                for layer in layers[key]:
                    net.add(layer)
            return net
        return layers[key]

    def __iter__(self):
        return iter(self._children.values())


class Dense(HybridBlock):
    """Fully connected layer (ref: basic_layers.py — Dense; op:
    src/operator/nn/fully_connected.cc)."""

    def __init__(self, units, activation=None, use_bias=True, flatten=True,
                 dtype="float32", weight_initializer=None,
                 bias_initializer="zeros", in_units=0, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._units = units
        self._flatten = flatten
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(units, in_units), dtype=dtype,
                init=weight_initializer, allow_deferred_init=True)
            if use_bias:
                self.bias = self.params.get(
                    "bias", shape=(units,), dtype=dtype,
                    init=bias_initializer, allow_deferred_init=True)
            else:
                self.bias = None
            if activation is not None:
                self.act = Activation(activation, prefix=activation + "_")
            else:
                self.act = None

    def infer_shape(self, x, *args):
        in_units = int(np.prod(x.shape[1:])) if self._flatten else x.shape[-1]
        self.weight.shape = (self._units, in_units)

    def hybrid_forward(self, F, x, weight=None, bias=None):
        out = F.FullyConnected(
            x, weight, bias, num_hidden=self._units,
            no_bias=bias is None, flatten=self._flatten)
        if self.act is not None:
            out = self.act(out)
        return out


class Activation(HybridBlock):
    def __init__(self, activation, prefix=None, params=None):
        self._act_type = activation  # before super(): _alias() uses it
        super().__init__(prefix=prefix, params=params)

    def _alias(self):
        return self._act_type

    def hybrid_forward(self, F, x):
        return F.Activation(x, act_type=self._act_type)


class Dropout(HybridBlock):
    def __init__(self, rate, axes=(), prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._rate = rate
        self._axes = tuple(axes)

    def hybrid_forward(self, F, x):
        return F.Dropout(x, p=self._rate, axes=self._axes,
                         train_mode=autograd.is_training())


class BatchNorm(HybridBlock):
    """Batch normalization (ref: basic_layers.py — BatchNorm; op:
    src/operator/nn/batch_norm.cc). Running stats are aux params mutated on
    training forwards, exactly like the reference."""

    def __init__(self, axis=None, momentum=0.9, epsilon=1e-5, center=True,
                 scale=True, use_global_stats=False, beta_initializer="zeros",
                 gamma_initializer="ones",
                 running_mean_initializer="zeros",
                 running_variance_initializer="ones", in_channels=0,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        # axis=None resolves against nn.layout_scope (1, the reference
        # default, unless a channels-last scope is active)
        self._axis = resolve_norm_axis(axis)
        self._momentum = momentum
        self._epsilon = epsilon
        self._center = center
        self._scale = scale
        self._use_global_stats = use_global_stats
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True,
                grad_req="write" if scale else "null")
            self.beta = self.params.get(
                "beta", shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True,
                grad_req="write" if center else "null")
            self.running_mean = self.params.get(
                "running_mean", shape=(in_channels,),
                init=running_mean_initializer, allow_deferred_init=True,
                differentiable=False)
            self.running_var = self.params.get(
                "running_var", shape=(in_channels,),
                init=running_variance_initializer, allow_deferred_init=True,
                differentiable=False)

    def infer_shape(self, x, *args):
        c = x.shape[self._axis]
        for p in (self.gamma, self.beta, self.running_mean, self.running_var):
            p.shape = (c,)

    def cast(self, dtype):
        """The running statistics stay float32 under a 16-bit cast (ref:
        basic_layers.py — BatchNorm.cast keeps its parameters float32).
        The op computes and returns them in float32, so 16-bit storage
        would change dtype on the first training forward — and a fused
        step, whose aux inputs are its own aux outputs, would compile
        its whole program a second time on step 2."""
        super().cast(dtype)
        if np.dtype(self.gamma.dtype).itemsize < 4:
            self.running_mean.cast("float32")
            self.running_var.cast("float32")

    def hybrid_forward(self, F, x, gamma=None, beta=None, running_mean=None,
                       running_var=None):
        train = autograd.is_training()
        ret = F.BatchNorm(
            x, gamma, beta, running_mean, running_var,
            eps=self._epsilon, momentum=self._momentum,
            fix_gamma=not self._scale,
            use_global_stats=self._use_global_stats,
            axis=self._axis, train_mode=train)
        if not isinstance(ret, tuple):
            return ret  # symbolic trace: extra outputs are hidden
        out, new_mean, new_var = ret
        if train and not self._use_global_stats:
            with autograd.pause():
                self.running_mean.data()._set_data(new_mean.data)
                self.running_var.data()._set_data(new_var.data)
        return out


class SyncBatchNorm(BatchNorm):
    """Cross-device synchronized BatchNorm
    (ref: python/mxnet/gluon/contrib/nn — SyncBatchNorm over
    src/operator/contrib/sync_batch_norm.cc, which runs an explicit
    all-reduce of per-device sums inside the kernel).

    TPU-native design note: no explicit collective is needed. Inside a
    jitted SPMD step (ShardedTrainStep / pjit) the batch is a GLOBAL
    array sharded over the mesh's data axis, so the ``jnp.mean``/var in
    the BatchNorm kernel are already global reductions — GSPMD inserts
    the cross-device psum automatically, and partitioning stays XLA's
    job. This subclass therefore only exists for API parity: it IS
    synchronized wherever the reference's would be (inside the sharded
    step), and in pure single-device eager mode it degenerates to plain
    BatchNorm exactly like the reference's does in a 1-GPU run.
    ``num_devices``/``ndev`` are accepted and ignored (mesh size rules).
    tests/test_parallel.py pins the global-stats property on an 8-device
    mesh."""

    def __init__(self, in_channels=0, num_devices=None, ndev=None,
                 momentum=0.9, epsilon=1e-5, center=True, scale=True,
                 use_global_stats=False, beta_initializer="zeros",
                 gamma_initializer="ones",
                 running_mean_initializer="zeros",
                 running_variance_initializer="ones", **kwargs):
        del num_devices, ndev
        super().__init__(axis=kwargs.pop("axis", None), momentum=momentum,
                         epsilon=epsilon, center=center, scale=scale,
                         use_global_stats=use_global_stats,
                         beta_initializer=beta_initializer,
                         gamma_initializer=gamma_initializer,
                         running_mean_initializer=running_mean_initializer,
                         running_variance_initializer=(
                             running_variance_initializer),
                         in_channels=in_channels, **kwargs)


class Embedding(HybridBlock):
    def __init__(self, input_dim, output_dim, dtype="float32",
                 weight_initializer=None, sparse_grad=False, prefix=None,
                 params=None):
        super().__init__(prefix=prefix, params=params)
        self._input_dim = input_dim
        self._output_dim = output_dim
        self._sparse_grad = sparse_grad
        with self.name_scope():
            self.weight = self.params.get(
                "weight", shape=(input_dim, output_dim), dtype=dtype,
                init=weight_initializer,
                grad_stype="row_sparse" if sparse_grad else "default")

    def hybrid_forward(self, F, x, weight=None):
        return F.Embedding(x, weight, input_dim=self._input_dim,
                           output_dim=self._output_dim,
                           sparse_grad=self._sparse_grad)


class Flatten(HybridBlock):
    def hybrid_forward(self, F, x):
        return F.flatten(x)


class Lambda(Block):
    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            from ... import ndarray as F

            if not hasattr(F, function):
                raise MXNetError("unknown nd function %r" % (function,))
            self._func = getattr(F, function)
            self._name_ = function
        else:
            self._func = function
            self._name_ = getattr(function, "__name__", "lambda")

    def forward(self, *args):
        return self._func(*args)


class HybridLambda(HybridBlock):
    def __init__(self, function, prefix=None):
        super().__init__(prefix=prefix)
        if isinstance(function, str):
            self._func_name = function
            self._func = None
        else:
            self._func = function
            self._func_name = getattr(function, "__name__", "lambda")

    def hybrid_forward(self, F, *args):
        if self._func is not None:
            return self._func(F, *args)
        return getattr(F, self._func_name)(*args)


class LayerNorm(HybridBlock):
    def __init__(self, axis=-1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._axis = axis
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True,
                grad_req="write" if scale else "null")
            self.beta = self.params.get(
                "beta", shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True,
                grad_req="write" if center else "null")

    def infer_shape(self, x, *args):
        c = x.shape[self._axis]
        self.gamma.shape = (c,)
        self.beta.shape = (c,)

    def hybrid_forward(self, F, x, gamma=None, beta=None):
        return F.LayerNorm(x, gamma, beta, axis=self._axis,
                           eps=self._epsilon)


class RMSNorm(HybridBlock):
    """Root-mean-square norm with a learned scale and no shift (op:
    ``RMSNorm``): the norm of pre-norm decoders."""

    def __init__(self, axis=-1, epsilon=1e-6, gamma_initializer="ones",
                 in_channels=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._axis = axis
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True)

    def infer_shape(self, x, *args):
        self.gamma.shape = (x.shape[self._axis],)

    def hybrid_forward(self, F, x, gamma=None):
        return F.RMSNorm(x, gamma, axis=self._axis, eps=self._epsilon)


class GatedRMSNorm(HybridBlock):
    """``RMSNorm(x * silu(gate)) * gamma`` over the last axis (op
    ``GatedRMSNorm``): the norm that closes a Mamba-2 mixer, the gate applied
    before it."""

    def __init__(self, epsilon=1e-6, in_channels=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get("gamma", shape=(in_channels,), init="ones",
                                         allow_deferred_init=True)

    def infer_shape(self, x, *args):
        self.gamma.shape = (x.shape[-1],)

    def hybrid_forward(self, F, x, gate, gamma=None):
        return F.GatedRMSNorm(x, gate, gamma, eps=self._epsilon)


class RMSNormSigmoidGate(GatedRMSNorm):
    """``RMSNorm(x) * gamma * sigmoid(gate)`` over the last axis (op
    ``RMSNormSigmoidGate``): the norm first and a sigmoid gate after it, what
    closes a head of Kimi's delta attention. The other order and the other
    gate from ``GatedRMSNorm``; the same arguments and the one parameter
    ``gamma``."""

    def hybrid_forward(self, F, x, gate, gamma=None):
        return F.RMSNormSigmoidGate(x, gate, gamma, eps=self._epsilon)


class SwiGLU(HybridBlock):
    """Gated feed-forward block ``down(silu(gate(x)) * up(x))`` without
    biases (Shazeer 2020), on the last axis."""

    def __init__(self, units, hidden_size, weight_initializer=None,
                 prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            kw = dict(flatten=False, use_bias=False,
                      weight_initializer=weight_initializer)
            self.gate = Dense(hidden_size, in_units=units, prefix="gate_", **kw)
            self.up = Dense(hidden_size, in_units=units, prefix="up_", **kw)
            self.down = Dense(units, in_units=hidden_size, prefix="down_", **kw)

    def hybrid_forward(self, F, x):
        return self.down(F.swiglu(self.gate(x), self.up(x)))


class InstanceNorm(HybridBlock):
    def __init__(self, axis=1, epsilon=1e-5, center=True, scale=False,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True,
                grad_req="write" if scale else "null")
            self.beta = self.params.get(
                "beta", shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True,
                grad_req="write" if center else "null")

    def infer_shape(self, x, *args):
        c = x.shape[1]
        self.gamma.shape = (c,)
        self.beta.shape = (c,)

    def hybrid_forward(self, F, x, gamma=None, beta=None):
        return F.InstanceNorm(x, gamma, beta, eps=self._epsilon)


class GroupNorm(HybridBlock):
    def __init__(self, num_groups=1, epsilon=1e-5, center=True, scale=True,
                 beta_initializer="zeros", gamma_initializer="ones",
                 in_channels=0, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        self._num_groups = num_groups
        self._epsilon = epsilon
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", shape=(in_channels,), init=gamma_initializer,
                allow_deferred_init=True,
                grad_req="write" if scale else "null")
            self.beta = self.params.get(
                "beta", shape=(in_channels,), init=beta_initializer,
                allow_deferred_init=True,
                grad_req="write" if center else "null")

    def infer_shape(self, x, *args):
        c = x.shape[1]
        self.gamma.shape = (c,)
        self.beta.shape = (c,)

    def hybrid_forward(self, F, x, gamma=None, beta=None):
        return F.GroupNorm(x, gamma, beta, num_groups=self._num_groups,
                           eps=self._epsilon)
