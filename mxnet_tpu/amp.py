"""AMP — automatic mixed precision with autocast lists + dynamic loss
scaling (ref: python/mxnet/contrib/amp/{amp.py,lists/symbol.py}).

``init()`` patches the op registry the way the reference monkey-patches
the generated nd/sym namespaces: MXU-bound ops (TARGET_DTYPE_OPS) cast
their float inputs to the target dtype (bfloat16 on TPU — no loss scaling
*needed* for range, unlike fp16, but the dynamic scaler is still provided
for fp16 parity and for tiny-gradient regimes); numerically sensitive ops
(FP32_OPS) compute in float32.

``scale_loss``/``unscale`` + ``LossScaler`` implement the reference's
dynamic scaling: scale doubles every ``scale_window`` clean steps, halves
on overflow, and the overflow step is skipped by ``Trainer``.
"""
from __future__ import annotations

import contextlib

import numpy as np

from .base import MXNetError, get_dtype
from .ops import registry as _registry

__all__ = ["init", "init_trainer", "scale_loss", "unscale", "LossScaler",
           "TARGET_DTYPE_OPS", "FP32_OPS"]

# MXU-bound: run in the low-precision target (ref: lists/symbol.py
# TARGET_DTYPE_OPS — conv/FC/dot family)
TARGET_DTYPE_OPS = [
    "Convolution", "Deconvolution", "FullyConnected", "dot", "batch_dot",
    "flash_attention", "flash_attention_qkv", "RNN",
]

# numerically sensitive: force float32 compute (ref: FP32_FUNCS)
FP32_OPS = [
    "softmax", "log_softmax", "softmin", "SoftmaxOutput",
    "softmax_cross_entropy", "BatchNorm", "LayerNorm", "InstanceNorm",
    "GroupNorm", "L2Normalization", "LRN", "norm", "mean", "sum", "prod",
    "exp", "expm1", "log", "log1p", "log2", "log10", "logsumexp",
    "erfinv", "gamma", "gammaln",
]

_state = {"initialized": False, "target": None, "originals": {}}


def _wrap_target(op, target):
    orig = op.fn

    def cast_fn(*args, **kwargs):
        import jax.numpy as jnp
        cast = tuple(
            a.astype(target) if hasattr(a, "dtype")
            and jnp.issubdtype(a.dtype, jnp.floating)
            and a.dtype != target else a
            for a in args)
        return orig(*cast, **kwargs)

    cast_fn.__name__ = getattr(orig, "__name__", op.name)
    return cast_fn


def _wrap_fp32(op):
    orig = op.fn

    def f32_fn(*args, **kwargs):
        import jax.numpy as jnp
        in_dt = next((a.dtype for a in args if hasattr(a, "dtype")
                      and jnp.issubdtype(a.dtype, jnp.floating)), None)
        cast = tuple(
            a.astype(jnp.float32) if hasattr(a, "dtype")
            and jnp.issubdtype(a.dtype, jnp.floating)
            and a.dtype != jnp.float32 else a
            for a in args)
        out = orig(*cast, **kwargs)
        if in_dt is not None and in_dt != jnp.float32:
            if isinstance(out, tuple):
                out = tuple(o.astype(in_dt) for o in out)
            else:
                out = out.astype(in_dt)
        return out

    f32_fn.__name__ = getattr(orig, "__name__", op.name)
    return f32_fn


def init(target_dtype="bfloat16"):
    """Patch the registry for autocasting (ref: amp.init — which patches
    the generated op modules). Idempotent; ``target_dtype`` is 'bfloat16'
    (TPU-native) or 'float16'."""
    if _state["initialized"]:
        if np.dtype(get_dtype(target_dtype)) != np.dtype(_state["target"]):
            raise MXNetError("amp already initialized with %s"
                             % _state["target"])
        return
    target = get_dtype(target_dtype)
    for name in TARGET_DTYPE_OPS:
        op = _registry.get_op(name)
        _state["originals"][name] = op.fn
        op.fn = _wrap_target(op, target)
    for name in FP32_OPS:
        op = _registry.get_op(name)
        _state["originals"][name] = op.fn
        op.fn = _wrap_fp32(op)
    _state["initialized"] = True
    _state["target"] = np.dtype(target)


def _deinit_for_tests():
    """Undo init() — test helper, not reference API."""
    for name, fn in _state["originals"].items():
        _registry.get_op(name).fn = fn
    _state.update(initialized=False, target=None, originals={})


class LossScaler:
    """Dynamic loss scale (ref: amp/loss_scaler.py — LossScaler)."""

    def __init__(self, init_scale=2.0 ** 16, scale_factor=2.0,
                 scale_window=2000):
        self.loss_scale = float(init_scale)
        self._factor = scale_factor
        self._window = scale_window
        self._unskipped = 0

    def has_overflow(self, params):
        """True if any gradient is non-finite. ONE fused device check +
        one host read for the whole gradient set (ref: all_finite.cc —
        MultiAllFinite; a per-parameter loop would pay a launch and a
        host read per parameter)."""
        from .ndarray.ndarray import NDArray

        arrs = []
        for p in params:
            g = p.grad()
            if hasattr(g, "_values"):  # row_sparse
                arrs.append(NDArray(g._values.data
                                    if isinstance(g._values, NDArray)
                                    else g._values))
            else:
                arrs.append(g if isinstance(g, NDArray) else NDArray(g))
        if not arrs:
            return False
        from . import nd

        flag = nd.multi_all_finite(*arrs, num_arrays=len(arrs))
        return float(flag.asnumpy()[0]) == 0.0

    def update_scale(self, overflow):
        if overflow:
            self.loss_scale = max(1.0, self.loss_scale / self._factor)
            self._unskipped = 0
        else:
            self._unskipped += 1
            if self._unskipped >= self._window:
                self.loss_scale *= self._factor
                self._unskipped = 0

    # -- persistence (resilience.CheckpointManager rides this so a
    # resumed run re-enters with the backed-off scale, not the init one)
    def state_dict(self):
        return {"loss_scale": float(self.loss_scale),
                "unskipped": int(self._unskipped)}

    def load_state_dict(self, state):
        self.loss_scale = float(state["loss_scale"])
        self._unskipped = int(state.get("unskipped", 0))


def init_trainer(trainer):
    """Attach dynamic loss scaling to a Trainer (ref: amp.init_trainer):
    after this, ``trainer.step`` unscales gradients and SKIPS the update
    when they overflowed, then updates the scale.

    A ``CachedTrainStep`` built from this trainer with
    ``MXT_SKIP_NONFINITE=1`` drives the same scaler from its in-program
    overflow flag (one host read per step, no extra launches) — see
    resilience.py."""
    if getattr(trainer, "_amp_scaler", None) is not None:
        return
    scaler = LossScaler()
    trainer._amp_scaler = scaler
    orig_step = trainer.step

    def step(batch_size, ignore_stale_grad=False):
        params = [p for p in trainer._params if p.grad_req != "null"]
        overflow = scaler.has_overflow(params)
        if not overflow:
            scale = scaler.loss_scale
            if scale != 1.0:
                for p in params:
                    g = p.data()._grad
                    if g is not None:
                        p.data()._grad = g / scale
            orig_step(batch_size, ignore_stale_grad=ignore_stale_grad)
        scaler.update_scale(overflow)

    trainer.step = step


@contextlib.contextmanager
def scale_loss(loss, trainer):
    """``with amp.scale_loss(loss, trainer) as l: l.backward()`` — the
    reference API; multiplies the loss by the current scale (trainer.step
    then unscales the gradients)."""
    if getattr(trainer, "_amp_scaler", None) is None:
        init_trainer(trainer)
    scale = trainer._amp_scaler.loss_scale
    if isinstance(loss, (list, tuple)):
        yield [l * scale for l in loss]
    else:
        yield loss * scale
