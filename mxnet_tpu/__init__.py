"""mxnet_tpu — a TPU-native framework with the capabilities of MXNet 1.x.

Built on JAX/XLA/Pallas: XLA async dispatch plays the ThreadedEngine, XLA
buffer assignment plays PlanMemory, jit plays CachedOp/GraphExecutor, and
sharding collectives over ICI play KVStore/NCCL. Blueprint: SURVEY.md.
"""
from __future__ import annotations

__version__ = "0.1.0"

import time as _time

_t_import = _time.perf_counter()  # profiler's setup.import: first line to last

import jax as _jax

# MXNet supports float64/int64 tensors as first-class dtypes; JAX gates them
# behind x64. Enable it — all framework defaults remain explicit float32.
_jax.config.update("jax_enable_x64", True)

from . import base
from .base import MXNetError
from . import context
from .context import Context, cpu, gpu, tpu, cpu_pinned, current_context, num_gpus
from . import operator  # registers the 'Custom' op before nd codegen
from . import ndarray
from . import ndarray as nd
from .ndarray.ndarray import NDArray
from . import autograd
from . import random
from . import test_utils
from . import initializer
from . import initializer as init
from . import lr_scheduler
from . import optimizer
from . import optimizer as opt
from . import metric
from . import kvstore
from . import kvstore as kv
from . import kvstore_server
from . import callback
from . import recordio
from . import io
from . import symbol
from . import symbol as sym
from .symbol import Symbol
from . import attribute
from .attribute import AttrScope
from . import name
from . import engine
from . import gluon
from . import module
from . import module as mod
from . import rnn
from .module import Module, BucketingModule, SequentialModule
from . import model
from .model import save_checkpoint, load_checkpoint
from . import parallel
from . import profiler
from . import monitor
from . import image
from . import config
from . import telemetry
telemetry._maybe_autostart()  # MXT_TELEMETRY_PORT exposition endpoint
from . import diagnostics
diagnostics._maybe_autostart()  # flight recorder tap (+ watchdog when
#                                 MXT_WATCHDOG_TIMEOUT is set)
# compile observability (jax.monitoring listeners) + persistent compile
# cache (MXT_COMPILE_CACHE_DIR) + the kernel tuning table
from . import tuning
from . import resilience
from . import membership
from . import embedding
from . import data_plane
from . import visualization
from . import visualization as viz
from . import amp
from . import contrib
from . import runtime
from . import util

profiler._record("setup.import", _time.perf_counter() - _t_import)

__all__ = [
    "nd", "ndarray", "autograd", "random", "context", "Context", "cpu",
    "gpu", "tpu", "NDArray", "MXNetError", "test_utils", "initializer",
    "init", "gluon", "optimizer", "opt", "metric", "kvstore", "kv",
    "lr_scheduler", "callback", "recordio", "io", "parallel", "symbol",
    "sym", "Symbol", "module", "mod", "Module", "BucketingModule", "model",
    "save_checkpoint", "load_checkpoint", "profiler", "monitor",
    "operator", "image", "config", "amp", "contrib", "resilience",
    "membership", "telemetry", "tuning", "diagnostics", "data_plane",
    "SequentialModule", "visualization", "viz", "runtime", "util", "rnn",
    "attribute", "AttrScope", "name", "engine",
]
