"""What the benchmark takes from the program besides the system under test:
the counters it already keeps, and its compile-cache switch."""
from __future__ import annotations

import os
import sys

from .loader import BENCH_DIR, REPO_DIR


def import_program():
    if REPO_DIR not in sys.path:
        sys.path.insert(0, REPO_DIR)
    import mxnet_tpu  # noqa: F401 — fails, as it must, where the program is absent

    return mxnet_tpu


def setup(rehearse=False):
    """Kernel choices from the cost model (the same programs on every run),
    and the persistent compile cache: where ``JAX_COMPILATION_CACHE_DIR``
    says, else at a fixed path inside the checkout. The program drops the
    cache's thresholds to zero; the benchmark raises the minimum compile time
    again so that the store holds the cell's few large programs and not
    hundreds of one-op entries."""
    import jax

    import_program()
    from mxnet_tpu import config, tuning

    config.set_default("MXT_TUNE_MODE", "heuristic")
    if rehearse:
        return None
    path = tuning.setup_compile_cache(os.path.join(BENCH_DIR, ".cache", "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return path


def counters():
    """One snapshot of the program's own counters."""
    from mxnet_tpu import profiler, tuning

    c = tuning.compile_stats()
    return {"launches": profiler.launch_count(),
            "host_syncs": profiler.host_sync_count(),
            "compiles": c["compiles"],
            "compile_seconds": c["compile_seconds"],
            "cache_hits": c["cache_hits"],
            "cache_misses": c["cache_misses"]}


def delta(after, before):
    return {k: after[k] - before[k] for k in after}
