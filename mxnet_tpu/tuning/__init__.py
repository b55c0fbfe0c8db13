"""Kernel autotuning + compile infrastructure (ROADMAP direction 3).

The MXNet heritage is ``MXNET_CUDNN_AUTOTUNE_DEFAULT`` — first call per
shape races the candidate algos, the winner is memoized. A call inside a
jitted step is traced, with nothing to race, so here a deterministic cost
model chooses and the table memoizes:

- **Block configs per shape** for the Pallas kernels (flash attention's
  (block_q, block_k), the paged decode kernel's head block) — the cost
  model's argmin over tiling-legal candidates (autotune.py).
- **XLA-vs-Pallas per shape**, by the same model.
- **A versioned persistent table** (table.py, ``MXT_TUNE_TABLE``) so
  decisions and recorded shape signatures survive the process.
- **Persistent compile cache + AOT warm-start** (compile_cache.py,
  warmup.py, ``MXT_COMPILE_CACHE_DIR``): ``tuning.warmup()`` compiles
  the canonical entry points ahead of the hot path; a second process
  replays compiles from disk — zero hot-path JIT on resume.

Telemetry: ``mxt_compile_seconds{phase}``, ``mxt_compiles_total``,
``mxt_compile_cache_{hits,misses}_total``,
``mxt_tune_cache_{hits,misses}_total``, ``mxt_warmup_seconds``.
"""
from __future__ import annotations

from . import autotune, compile_cache, table as _table_mod, warmup as _warmup
from .autotune import (attention_candidates, attention_cost,
                       heuristic_attention, heuristic_paged, heuristic_quant,
                       paged_candidates, paged_cost, quant_cost,
                       resolve_attention, resolve_paged,
                       resolve_quant)
from .compile_cache import (cache_dir, compile_stats, install_listeners,
                            setup as setup_compile_cache)
from .table import (TABLE_VERSION, TuneTable, attn_key, device_kind,
                    paged_key, quant_key, reset, save, table)
from .warmup import record_signature, register_step, signatures, warmup

__all__ = [
    "attention_candidates", "attention_cost",
    "heuristic_attention", "heuristic_paged", "heuristic_quant",
    "paged_candidates", "paged_cost", "quant_cost",
    "resolve_attention", "resolve_paged", "resolve_quant",
    "cache_dir", "compile_stats", "install_listeners",
    "setup_compile_cache",
    "TABLE_VERSION", "TuneTable", "attn_key", "device_kind",
    "paged_key", "quant_key", "reset", "save", "table",
    "record_signature", "register_step", "signatures", "warmup",
    "autotune", "compile_cache",
]

# passive compile observability + persistent cache activation when the
# env asks for it — importing mxnet_tpu is enough to start counting
install_listeners()
setup_compile_cache()
