"""Pallas fused BatchNorm backward (ref: src/operator/nn/batch_norm.cu —
the reference's hand-fused CUDA BN backward; PERF.md round-3 analysis:
ResNet-50's backward is HBM-bandwidth-bound and the BN backward's
reductions are the fusible traffic).

Shape model: activations flattened to (M, C) with channel last (the NHWC
fast path — lane dimension = channels). Two passes, each reading x and
dy exactly once:

  pass 1 (reduce): db = Σ dy,  dg = Σ dy·x̂   — one joint read
  pass 2 (dx):     dx = g·inv · (dy − db/n − x̂·dg/n)

x̂ is recomputed from (x, mean, inv) in both passes — no f32 activation
residual is ever materialized (same policy as the XLA custom-VJP path in
nn._bn_core_bwd). Cross-block accumulation exploits the TPU grid's
sequential iteration: the (1, C) accumulator block maps to the same
tile every step, zeroed at step 0.

Gated by ``MXT_BN_PALLAS=1`` (default off until chip-measured against
the XLA formulas). Interpret-mode-green kernels can still be refused by
the chip's compiler, so tests/test_aot_tpu_compile.py compiles this one
for the described chip and chip_smoke.py runs it there.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..context import on_tpu


def _block_rows(c, per_buf_bytes=1 << 21):
    """Rows per block so one f32 (BM, C) buffer stays ≤ per_buf_bytes."""
    bm = per_buf_bytes // (4 * max(c, 1))
    bm = max(8, min(1024, bm))
    return (bm // 8) * 8  # sublane multiple


def _reduce_kernel(m_true, x_ref, dy_ref, mean_ref, inv_ref,
                   db_ref, dg_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        db_ref[...] = jnp.zeros_like(db_ref)
        dg_ref[...] = jnp.zeros_like(dg_ref)

    bm = x_ref.shape[0]
    row = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
    mask = row < m_true
    # select-to-zero BOTH factors: an out-of-bounds row's padding is
    # unspecified — NaN·0 (a multiply mask) would still poison the sum
    dy = jnp.where(mask, dy_ref[...].astype(jnp.float32), 0.0)
    xhat = jnp.where(
        mask,
        (x_ref[...].astype(jnp.float32) - mean_ref[...]) * inv_ref[...],
        0.0)
    db_ref[...] += jnp.sum(dy, axis=0, keepdims=True)
    dg_ref[...] += jnp.sum(dy * xhat, axis=0, keepdims=True)


def _dx_kernel(n_scale, x_ref, dy_ref, mean_ref, inv_ref, g_ref,
               db_ref, dg_ref, dx_ref):
    dy = dy_ref[...].astype(jnp.float32)
    xhat = (x_ref[...].astype(jnp.float32) - mean_ref[...]) * inv_ref[...]
    dx = (g_ref[...] * inv_ref[...]) * (
        dy - db_ref[...] * n_scale - xhat * (dg_ref[...] * n_scale))
    dx_ref[...] = dx.astype(dx_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def bn_bwd_pallas(x2d, dy2d, mean, inv, g, interpret=False,
                  block_rows=None):
    """Fused BN backward on (M, C) channel-last activations.

    ``block_rows`` overrides the VMEM-budget heuristic with a tuned
    value (tuning/autotune.py — must be a positive multiple of 8; the
    kernel pads and row-masks the last block, so any legal value works
    for any M). Returns (dx (M, C) in x's dtype, dg (C,) f32,
    db (C,) f32).
    """
    m, c = x2d.shape
    bm = int(block_rows) if block_rows else _block_rows(c)
    if bm < 8 or bm % 8:
        raise ValueError("block_rows must be a positive multiple of 8 "
                         "(TPU sublane), got %d" % bm)
    grid = ((m + bm - 1) // bm,)
    mean_r = mean.reshape(1, c).astype(jnp.float32)
    inv_r = inv.reshape(1, c).astype(jnp.float32)
    g_r = g.reshape(1, c).astype(jnp.float32)

    # np.int32 zeros: under jax_enable_x64 a literal 0 in an index map
    # traces as i64, which Mosaic refuses (same repair as the flash kernel)
    z = np.int32(0)
    row_spec = pl.BlockSpec((bm, c), lambda i: (i, z))
    chan_spec = pl.BlockSpec((1, c), lambda i: (z, z))

    db, dg = pl.pallas_call(
        functools.partial(_reduce_kernel, m),
        grid=grid,
        in_specs=[row_spec, row_spec, chan_spec, chan_spec],
        out_specs=[chan_spec, chan_spec],
        out_shape=[jax.ShapeDtypeStruct((1, c), jnp.float32),
                   jax.ShapeDtypeStruct((1, c), jnp.float32)],
        interpret=interpret,
        name="bn_bwd_reduce",
    )(x2d, dy2d, mean_r, inv_r)

    n_scale = 1.0 / float(m)
    dx = pl.pallas_call(
        functools.partial(_dx_kernel, n_scale),
        grid=grid,
        in_specs=[row_spec, row_spec, chan_spec, chan_spec, chan_spec,
                  chan_spec, chan_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((m, c), x2d.dtype),
        interpret=interpret,
        name="bn_bwd_dx",
    )(x2d, dy2d, mean_r, inv_r, g_r, db, dg)
    return dx, dg.reshape(c), db.reshape(c)


def enabled():
    """MXT_BN_PALLAS is on AND the compiled kernel can run here (CPU
    tests drive the kernel directly with interpret=True instead)."""
    from .. import config

    return bool(config.get("MXT_BN_PALLAS")) and on_tpu()


def choose(m, c, dtype, arrays=None):
    """Per-shape routing decision for the channel-last BN backward —
    the per-call replacement for the global ``MXT_BN_PALLAS`` switch.

    Returns ``(use_pallas, block_rows)``. An explicit ``MXT_BN_PALLAS``
    (env or set_default) keeps its global meaning for A/B sweeps;
    otherwise the tuning table answers per shape bucket (heuristic
    default: XLA — the fused kernel stays opt-in until a measured entry
    says it wins here). ``arrays`` (concrete (x2d, dy2d, mean, inv, g))
    lets an eager backward feed the autotuner's timed path on device.
    """
    from .. import config

    if config.is_set("MXT_BN_PALLAS") \
            or str(config.get("MXT_TUNE_MODE")).lower() == "off":
        return bool(config.get("MXT_BN_PALLAS")), None
    from .. import tuning

    ent = tuning.resolve_bn(m, c, str(dtype), arrays=arrays)
    if ent.get("backend") == "pallas":
        return True, int(ent.get("block_rows") or 0) or None
    return False, None
