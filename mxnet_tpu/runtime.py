"""Runtime feature detection (ref: python/mxnet/runtime.py — Features /
feature_list over libinfo). The reference reports compile-time flags
(CUDA, CUDNN, MKLDNN, ...); here features reflect the TPU build: what
backend is live, which optional subsystems (native record engine, Pallas
flash attention) are usable on this machine.
"""
from __future__ import annotations

__all__ = ["Feature", "Features", "feature_list"]


class Feature:
    def __init__(self, name, enabled):
        self.name = name
        self.enabled = bool(enabled)

    def __repr__(self):
        return "%s %s" % ("✔" if self.enabled else "✖", self.name)


def _detect():
    feats = {}

    def add(name, enabled):
        feats[name] = Feature(name, enabled)

    from .context import on_tpu

    add("TPU", on_tpu())
    add("CPU", True)
    # reference compile-time flags that are inherently off in this build
    for flag in ("CUDA", "CUDNN", "NCCL", "TENSORRT", "MKLDNN", "OPENCV"):
        add(flag, False)
    add("BLAS_OPEN", True)  # XLA's own kernels play the BLAS role
    add("DIST_KVSTORE", True)  # jax.distributed + collectives path
    add("PROFILER", True)  # mx.profiler over jax.profiler
    add("SIGNAL_HANDLER", False)
    add("INT64_TENSOR_SIZE", True)
    # TPU-build-specific capabilities
    from . import native, tuning

    add("NATIVE_RECORDIO", native.available())
    add("FLASH_ATTENTION", on_tpu())  # the compiled Pallas kernels
    add("SEQUENCE_PARALLEL", True)
    add("INT8_QUANTIZATION", True)  # contrib.quantization, s8 MXU kernels
    # usable == decisions survive the process (a path is configured)
    add("KERNEL_AUTOTUNE", tuning.table().path is not None)
    add("COMPILE_CACHE", tuning.cache_dir() is not None)
    return feats


class Features(dict):
    """Mapping of feature name -> Feature (ref: runtime.py — Features)."""

    def __init__(self):
        super().__init__(_detect())

    def is_enabled(self, name):
        name = name.upper()
        if name not in self:
            raise RuntimeError("feature %r does not exist" % (name,))
        return self[name].enabled

    def __repr__(self):
        return "[%s]" % ", ".join(repr(v) for v in self.values())


def feature_list():
    """List of Feature objects (ref: runtime.py — feature_list)."""
    return list(Features().values())
