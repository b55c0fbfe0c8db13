"""AOT warm-start — compile the hot path before the hot path needs it.

PERF.md's worst number is not a throughput: a 4-layer GPT forward
recompiled every attention call for 63 seconds, and the r4 outage was a
crash mid-compile. With the persistent compilation cache
(compile_cache.py) those compiles survive the process; this module
makes a NEW process replay them ahead of time:

- Kernel entry points (flash fwd/bwd, BN fwd/bwd) record their shape
  signatures into the tuning table at dispatch. ``warmup()`` rebuilds
  each signature as abstract ``ShapeDtypeStruct`` args and
  AOT-lowers-and-compiles the same programs — no device math, no real
  data, every XLA compile lands now (from the persistent cache when a
  previous process already paid it).

- Fused-step entry points (CachedTrainStep, the Trainer's
  ``_FusedUpdate``) register themselves when built; their
  ``aot_warmup()`` lowers the donated step program from the live
  parameter shapes. A resumed trainer calls ``tuning.warmup()`` after
  ``load_states`` and the first real step performs zero hot-path JIT.

Everything here is CPU-runnable: tier-1 asserts the compile counters
around a warmup() call and around a warm-started second process.
"""
from __future__ import annotations

import time
import weakref

from . import compile_cache
from . import table as _table_mod

_live_steps = weakref.WeakSet()


def _telemetry():
    from .. import telemetry

    return telemetry


def register_step(step):
    """Track a live fused entry point (an object with ``aot_warmup()``)
    so a bare ``warmup()`` can compile it without the caller threading
    references around."""
    _live_steps.add(step)


def record_signature(entry_point, spec):
    """Remember one dispatched shape signature for warm-start replay."""
    return _table_mod.table().record_signature(entry_point, spec)


def signatures(entry_point=None):
    return _table_mod.table().signatures(entry_point)


def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _warm_flash(spec):
    """AOT-compile the flash custom-VJP forward and backward programs
    for one recorded signature."""
    import jax

    from ..ops import attention as A

    causal = bool(spec["causal"])
    sm_scale = float(spec["sm_scale"])  # sync-ok: host float from JSON
    q = _sds(spec["q_shape"], spec["dtype"])
    k = _sds(spec["k_shape"], spec["dtype"])  # its own (fewer) heads
    v = _sds(spec["v_shape"], spec["dtype"])
    b = m = None
    if spec.get("bias_shape"):
        b = _sds(spec["bias_shape"], spec.get("bias_dtype", spec["dtype"]))
    if spec.get("mask_dtype"):  # a selection mask: (B, Tq, Tk)
        m = _sds([q.shape[0], q.shape[2], k.shape[2]], spec["mask_dtype"])

    def fwd(q_, k_, v_, b_, m_):
        return A._flash_core(q_, k_, v_, b_, m_, causal, sm_scale,
                             spec.get("window"))

    jax.jit(fwd).lower(q, k, v, b, m).compile()
    jax.jit(jax.grad(lambda *a: fwd(*a).sum(),
                     argnums=(0, 1, 2))).lower(q, k, v, b, m).compile()
    return "flash_attention"


def _warm_bn(spec):
    """AOT-compile the BatchNorm custom-VJP core (fwd + grad) for one
    recorded signature."""
    import jax

    from ..ops import nn as _nn

    eps = float(spec["eps"])  # sync-ok: host float from JSON
    red = tuple(spec["red"])
    x = _sds(spec["x_shape"], spec["dtype"])
    g = _sds(spec["g_shape"], spec.get("g_dtype", "float32"))
    b = _sds(spec["g_shape"], spec.get("g_dtype", "float32"))

    def fwd(x_, g_, b_):
        return _nn._bn_core(eps, red, x_, g_, b_)

    jax.jit(fwd).lower(x, g, b).compile()
    jax.jit(jax.grad(lambda x_, g_, b_: fwd(x_, g_, b_)[0].sum(),
                     argnums=(0, 1, 2))).lower(x, g, b).compile()
    return "batch_norm"


def _warm_delta_rule(spec):
    """AOT-compile the gated delta rule's custom-VJP forward and backward
    programs for one recorded signature."""
    import jax

    from ..ops import delta_rule as D

    q = _sds(spec["q_shape"], spec["dtype"])
    v = _sds(spec["v_shape"], spec["dtype"])
    g = _sds(spec["q_shape"], spec["g_dtype"])
    beta = _sds(spec["q_shape"][:3], spec["beta_dtype"])
    chunk = int(spec["chunk"])  # sync-ok: host number from JSON

    def fwd(q_, k_, v_, g_, beta_):
        return D._delta_core(chunk, q_, k_, v_, g_, beta_)

    jax.jit(fwd).lower(q, q, v, g, beta).compile()
    jax.jit(jax.grad(lambda *a: fwd(*a).sum(),
                     argnums=(0, 1, 2, 3, 4))).lower(q, q, v, g, beta).compile()
    return "gated_delta_rule"


def _warm_paged(spec):
    """AOT-compile the ragged paged attention decode program for one
    recorded signature (both the jitted dispatch a serving step traces
    through and the standalone op a request-path eval would hit)."""
    import jax
    import jax.numpy as jnp

    from ..ops import attention as A

    sm_scale = float(spec["sm_scale"])  # sync-ok: host float from JSON
    q = _sds(spec["q_shape"], spec["dtype"])
    pool_dtype = spec.get("pool_dtype", spec["dtype"])
    kp = _sds(spec["pool_shape"], pool_dtype)
    vp = _sds(spec["pool_shape"], pool_dtype)
    pt = _sds((spec["q_shape"][0], spec["max_pages"]), jnp.int32)
    cl = _sds((spec["q_shape"][0],), jnp.int32)
    if spec.get("quantized"):
        sc = _sds(tuple(spec["pool_shape"][:-1]), jnp.float32)

        def fwd(q_, kp_, vp_, pt_, cl_, ks_, vs_):
            return A.ragged_paged_attention(q_, kp_, vp_, pt_, cl_,
                                            sm_scale=sm_scale,
                                            k_scales=ks_, v_scales=vs_)

        jax.jit(fwd).lower(q, kp, vp, pt, cl, sc, sc).compile()
        return "paged_attention"

    def fwd(q_, kp_, vp_, pt_, cl_):
        return A.ragged_paged_attention(q_, kp_, vp_, pt_, cl_,
                                        sm_scale=sm_scale)

    jax.jit(fwd).lower(q, kp, vp, pt, cl).compile()
    return "paged_attention"


def warmup(steps=(), kernels=True, include_live=True, reason=None):
    """AOT-lower-and-compile the canonical entry points from recorded
    shape signatures.

    ``steps``: fused entry points (CachedTrainStep / _FusedUpdate /
    parallel.ShardedTrainStep — anything with ``aot_warmup()``) to
    compile in addition to every live registered one
    (``include_live=False`` restricts to ``steps``). ``kernels=False``
    skips the library-kernel (flash/BN) signatures. ``reason`` tags the
    emitted telemetry event — the elastic reshard path passes
    ``reason="reshard"`` so warm-compiles triggered by a mesh change are
    distinguishable from resume warm-starts in the JSONL stream.

    Returns a summary dict: entries warmed, compiles performed, compile
    seconds, cache hits/misses — on a warm persistent cache the same
    entries land as hits in a fraction of the time.
    """
    compile_cache.install_listeners()
    compile_cache.setup()
    t0 = time.perf_counter()
    before = compile_cache.compile_stats()
    warmed, errors = [], []
    if kernels:
        for kind, fn in (("flash_attention", _warm_flash),
                         ("batch_norm", _warm_bn),
                         ("paged_attention", _warm_paged),
                         ("gated_delta_rule", _warm_delta_rule)):
            for spec in signatures(kind):
                try:
                    warmed.append(fn(spec))
                except Exception as e:  # noqa: BLE001 — warmup is advisory
                    errors.append("%s: %r" % (kind, e))
    seen = set()
    live = list(_live_steps) if include_live else []
    for step in list(steps) + live:
        if id(step) in seen:
            continue
        seen.add(id(step))
        try:
            if step.aot_warmup() is not False:
                warmed.append(type(step).__name__)
        except Exception as e:  # noqa: BLE001
            errors.append("%s: %r" % (type(step).__name__, e))
    after = compile_cache.compile_stats()
    dt = time.perf_counter() - t0
    summary = {
        "entries": warmed,
        "errors": errors,
        "seconds": round(dt, 6),
        "compiles": after["compiles"] - before["compiles"],
        "compile_seconds": round(
            after["compile_seconds"] - before["compile_seconds"], 6),
        "cache_hits": after["cache_hits"] - before["cache_hits"],
        "cache_misses": after["cache_misses"] - before["cache_misses"],
        "cache_dir": compile_cache.cache_dir(),
    }
    if reason is not None:
        summary["reason"] = str(reason)
    tel = _telemetry()
    tel.histogram(
        "mxt_warmup_seconds",
        "Wall time of tuning.warmup() AOT warm-start passes.").observe(dt)
    tel.emit_event("warmup", **summary)
    # warm-start implies the table (incl. any new signatures) should
    # survive this process too
    try:
        _table_mod.save()
    except OSError:
        pass
    return summary
