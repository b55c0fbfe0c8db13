"""Device context model.

Re-design of the reference Context (ref: include/mxnet/base.h — Context,
python/mxnet/context.py). Devices are JAX devices; ``tpu`` is first-class and
``gpu`` is accepted as an alias for the accelerator so reference-era scripts
run unchanged. Contexts are usable as ``with`` scopes, exactly like the
reference's ``with mx.gpu(0):`` pattern.
"""
from __future__ import annotations

import threading

from .base import MXNetError

__all__ = [
    "Context",
    "cpu",
    "gpu",
    "tpu",
    "cpu_pinned",
    "current_context",
    "num_gpus",
    "num_tpus",
    "on_tpu",
]


class _CtxStack(threading.local):
    def __init__(self):
        super().__init__()
        self.stack = []


_ctx_stack = _CtxStack()


class Context:
    """A device context (device_type + device_id).

    device types mirror the reference enum (kCPU=1, kGPU=2, kCPUPinned=3,
    kCPUShared=5) plus kTPU=6 for the native accelerator. ``gpu`` resolves to
    the same physical accelerator as ``tpu`` — this build has no CUDA.
    """

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 5: "cpu_shared", 6: "tpu"}
    devstr2type = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if isinstance(device_type, str):
                if device_type not in Context.devstr2type:
                    raise MXNetError("unknown device type %r" % (device_type,))
                self.device_typeid = Context.devstr2type[device_type]
            else:
                self.device_typeid = int(device_type)
            self.device_id = int(device_id)

    @property
    def device_type(self):
        return Context.devtype2str[self.device_typeid]

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __repr__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    def __str__(self):
        return repr(self)

    def __enter__(self):
        _ctx_stack.stack.append(self)
        return self

    def __exit__(self, *args):
        _ctx_stack.stack.pop()

    # -- JAX resolution ----------------------------------------------------
    @property
    def jax_device(self):
        """Resolve this context to a concrete jax.Device. Under
        jax.distributed, contexts index this process's LOCAL devices
        (ref: a Context is per-worker; global placement is the mesh's
        job) — jax.devices() lists remote devices a process cannot
        address directly."""
        import jax

        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            try:
                devs = jax.local_devices(backend="cpu")
            except RuntimeError:
                devs = jax.local_devices()
        else:  # tpu / gpu → default accelerator backend
            devs = jax.local_devices()
        if self.device_id >= len(devs):
            raise MXNetError(
                "context %s out of range: only %d %s device(s) visible"
                % (self, len(devs), self.device_type)
            )
        return devs[self.device_id]

    def empty_cache(self):
        """Best-effort analog of the reference's storage-pool release
        (ref: src/storage — Storage::Get()->ReleaseAll via MXStorageEmptyCache)."""
        import gc

        gc.collect()


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def gpu(device_id=0):
    """Alias for the accelerator device; kept for reference API compat."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def current_context() -> Context:
    """Innermost ``with ctx:`` scope, else default.

    Default is the accelerator when one is visible, else cpu — unlike the
    reference (which defaults to cpu) this puts users on TPU out of the box;
    ``with mx.cpu():`` opts out.
    """
    if _ctx_stack.stack:
        return _ctx_stack.stack[-1]
    return default_context()


_default_ctx = None


def default_context() -> Context:
    global _default_ctx
    if _default_ctx is None:
        import jax

        if jax.default_backend() == "cpu":
            _default_ctx = cpu(0)
        else:
            _default_ctx = tpu(0)
    return _default_ctx


def on_tpu() -> bool:
    """The one device predicate: compiled Pallas kernels and the TPU
    feature flag ask this."""
    import jax

    return jax.default_backend() == "tpu"


def num_gpus() -> int:
    """Number of accelerator devices (reference: mx.context.num_gpus)."""
    return num_tpus()


def num_tpus() -> int:
    import jax

    if jax.default_backend() == "cpu":
        return 0
    return len(jax.devices())
