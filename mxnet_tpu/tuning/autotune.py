"""Block-size autotuner for the Pallas kernels + per-shape backend choice.

Two halves, per TVM's split (PAPERS.md arXiv 1802.04799 — search-based
config selection beats fixed heuristics), scoped to block/grid configs:

1. **Candidate generation + deterministic cost model** (always
   available, CPU/CI path). Candidates are TPU-tiling-legal by
   construction: multiples of 8 in the sublane dimension, lane-friendly
   (128-multiple preferred) in the key dimension, VMEM-budgeted — and
   tests/test_aot_tpu_compile.py holds the generators to it by
   compiling what they emit for the described chip. The cost model
   charges padded work (the kernels pad-and-mask partial blocks, so a
   block that divides the padded shape badly wastes real MXU cycles),
   per-grid-step overhead, and tile-shape penalties. It is a pure
   function of the shape: same inputs, same config, no measurement
   noise in CI.

2. **Timed micro-benchmarks on device** (`measure=True`, the default
   under ``MXT_TUNE_MODE=auto`` on a real TPU): each candidate runs a
   short timed loop and the empirical winner is recorded as
   ``source="measured"`` — which the table never lets a later heuristic
   overwrite. Measurement also settles the **XLA-vs-Pallas** choice per
   shape (the per-call replacement for the global ``MXT_BN_PALLAS`` /
   reference-path switches), per the fusion-analysis motivation (arXiv
   2301.13062): small shapes often lose to XLA's fused reference.

Measurement loops block on device results by design — they are the
tuning path, not the training hot path, and every sync is marked for
tools/check_host_syncs.py.
"""
from __future__ import annotations

import math
import time

from ..context import on_tpu
from . import table as _table_mod

# leave headroom under the chip compiler's 16 MiB scoped-VMEM default;
# every candidate the generators below emit under this budget compiles
# for the described v5e at the shapes in tests/test_aot_tpu_compile.py
_VMEM_BUDGET = 12 * 1024 * 1024
_LANE = 128
_SUBLANE = 8


def _config():
    from .. import config

    return config


def _round8(n):
    return max(_SUBLANE, -(-int(n) // _SUBLANE) * _SUBLANE)


def _pad_to(n, block):
    return -(-int(n) // int(block)) * int(block)


def _itemsize(dtype):
    import numpy as np

    try:
        return np.dtype(dtype).itemsize
    except TypeError:
        return 4


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------
def attention_candidates(tq, tk, d, dtype):
    """Tiling-legal (block_q, block_k) candidates for a (Tq, Tk, D)
    attention shape. Shape-aware: blocks never exceed the padded
    sequence, a Q block is whole 128-lane groups of the kernel's lse row
    or the whole sequence, what the kernel holds in VMEM fits the budget,
    and a non-multiple shape gets divisor-friendly small K/V blocks among
    the candidates instead of only worst-case-padding large ones."""
    tq8, tk8 = _round8(tq), _round8(tk)
    qs = sorted({min(b, tq8) for b in (128, 256, 512)})
    ks = sorted({min(b, tk8) for b in (32, 64, 128, 256, 512)})
    out = []
    isz = _itemsize(dtype)
    for bq in qs:
        for bk in ks:
            pk = _pad_to(tk, bk)
            # what _flash_forward_pallas's specs hold, each block twice (the
            # pipeline's two buffers): the Q and output blocks, the padded
            # K and V whole in the input dtype, the lse row; and the
            # kernel's own float32 accumulator and score tile, the tile
            # once more in the input dtype for the second matmul
            vmem = (2 * ((2 * bq * d + 2 * pk * d) * isz + bq * 4)
                    + bq * d * 4 + bq * bk * (8 + isz))
            if vmem > _VMEM_BUDGET:
                continue
            out.append((bq, bk))
    if not out:  # degenerate (huge D): minimal legal tile
        out.append((min(_LANE, tq8), _SUBLANE))
    return out


def attention_cost(tq, tk, d, bq, bk, dtype):
    """Deterministic relative cost of one (block_q, block_k) config:
    padded score-matrix work, grid-step overhead, and tile-shape
    penalties. Unitless — only the argmin matters."""
    pq, pk = _pad_to(tq, bq), _pad_to(tk, bk)
    cost = 1.0 * pq * pk  # compute incl. padding waste
    grid_q = pq // bq
    kv_steps = pk // bk
    # per-grid-step / per-kv-iteration fixed overhead (loop + DMA issue)
    cost *= 1.0 + 0.004 * grid_q + 0.001 * grid_q * kv_steps
    if bk % _LANE:
        cost *= 1.20  # lane dim off the 128 register width
    if bq < 64:
        cost *= 1.0 + (64 - bq) / 256.0  # underfilled MXU sublanes
    return cost


def heuristic_attention(q_shape, kv_len, dtype, causal):
    """Cost-model argmin config + backend choice for one shape."""
    _, _, tq, d = q_shape
    tk = kv_len
    best, best_cost = None, math.inf
    for bq, bk in attention_candidates(tq, tk, d, dtype):
        c = attention_cost(tq, tk, d, bq, bk, dtype)
        if c < best_cost:
            best, best_cost = (bq, bk), c
    # XLA-vs-Pallas per shape: tiny sequences don't amortize the kernel's
    # online-softmax bookkeeping — XLA's fused reference wins there
    backend = "pallas" if (tq >= 64 and tk >= 128) else "xla"
    return {"backend": backend, "block_q": best[0], "block_k": best[1],
            "source": "heuristic", "score": round(best_cost, 3)}


def measure_attention(q, k, v, bias, causal, sm_scale, interpret=False,
                      iters=None, candidates=None, mask=None):
    """Time each candidate (and the XLA reference) on the live arrays;
    returns the winning entry dict. Runs OUTSIDE the training hot path
    (first call per shape bucket, or an explicit sweep). A candidate
    the compiler refuses raises: the generator called it legal, so a
    refusal is a bug to see, not a row to drop."""
    from ..ops import attention as A

    iters = iters or int(_config().get("MXT_TUNE_ITERS"))
    tq, d = q.shape[2], q.shape[3]
    tk = k.shape[2]
    cands = candidates or attention_candidates(tq, tk, d, q.dtype)
    timings = {}
    for bq, bk in cands:
        def run(bq=bq, bk=bk):
            out, _ = A._flash_forward_pallas(
                q, k, v, bias, causal, sm_scale, bq, bk,
                interpret=interpret, mask=mask)
            return out
        timings[("pallas", bq, bk)] = _time(run, iters)

    def ref():
        return A._attention_reference(q, k, v, bias, causal, sm_scale, mask)
    timings[("xla", 0, 0)] = _time(ref, iters)

    (backend, bq, bk), score = min(timings.items(), key=lambda kv: kv[1])
    return {"backend": backend, "block_q": bq, "block_k": bk,
            "source": "measured", "score": round(score * 1e3, 6)}


# --------------------------------------------------------------------------
# ragged paged attention (decode)
# --------------------------------------------------------------------------
def paged_candidates(heads, head_dim, page_size, dtype):
    """Legal head-block widths for the paged decode kernel: divisors of
    the head count (the kernel statically unrolls per-head matvecs over
    the block, and gives the head block its own array axis so that any
    divisor is a tile the chip's compiler takes), VMEM-bounded by one
    page of K+V per head in the block plus the f32 softmax state."""
    isz = _itemsize(dtype)
    out = []
    for bh in (1, 2, 4, 8, 16, 32):
        if bh > heads or heads % bh:
            continue
        vmem = (2 * page_size * bh * head_dim + bh * head_dim) * isz \
            + bh * (page_size + 2 * _LANE + head_dim) * 4
        if vmem > _VMEM_BUDGET:
            continue
        out.append(bh)
    return out or [1]


def paged_cost(heads, head_dim, page_size, max_pages, bh):
    """Deterministic relative cost of one head-block width. Decode is
    grid-overhead dominated (every grid step moves one page and does a
    handful of matvecs), so wider head blocks amortize steps — charged
    against the unrolled-code/VMEM pressure of very wide blocks."""
    steps = (heads // bh) * max_pages
    work = steps * (8.0 + 0.002 * bh * page_size * head_dim)
    if bh > 8:
        work *= 1.0 + (bh - 8) / 32.0  # unroll bloat past one sublane tile
    return work


def heuristic_paged(q_shape, page_size, max_pages, dtype):
    """Cost-model argmin head block + backend choice for one decode
    shape. Short contexts (a page or two) lose the kernel's grid setup
    to XLA's fused gather+softmax; past that the paged kernel avoids
    materializing the gathered (B, T, H, D) stream every step."""
    _, h, d = q_shape
    best, best_cost = None, math.inf
    for bh in paged_candidates(h, d, page_size, dtype):
        c = paged_cost(h, d, page_size, max_pages, bh)
        if c < best_cost:
            best, best_cost = bh, c
    backend = "pallas" if page_size * max_pages >= 256 else "xla"
    return {"backend": backend, "block_h": best, "source": "heuristic",
            "score": round(best_cost, 3)}


def resolve_paged(q_shape, page_size, max_pages, dtype):
    """The per-call decision the paged decode kernel consumes: table
    hit, else the cost model, recorded under the decode-shape bucket.
    Decode dispatches happen inside the jitted serving step (tracers —
    nothing to time), so unlike the flash kernel there is no inline
    measurement path: measured entries arrive via offline sweeps writing
    the table, and are never downgraded by this heuristic re-record.
    ``MXT_TUNE_MODE=off`` bypasses the table (pure cost model), matching
    the flash kernel's legacy-global semantics."""
    if _mode() == "off":
        return heuristic_paged(q_shape, page_size, max_pages, dtype)
    tab = _table_mod.table()
    key = _table_mod.paged_key(q_shape, page_size, max_pages, dtype)
    ent = tab.lookup(key)
    if ent is not None:
        return ent
    return tab.record(key, heuristic_paged(q_shape, page_size, max_pages,
                                           dtype))


# --------------------------------------------------------------------------
# quantized-vs-float decode matmuls (weight-only int8 serving)
# --------------------------------------------------------------------------
def quant_cost(k, n, backend):
    """Deterministic relative cost of one (k, n) decode matmul on one
    backend. Decode matmuls are weight-BYTES-bound (batch is a handful
    of slots, the weight tile is read once per launch): float charges
    4 bytes/element; int8 charges 1 byte/element + the per-column amax
    plane + a dequant-epilogue tax + a fixed kernel-setup overhead that
    keeps tiny layers on the fused float path."""
    if backend == "int8":
        return 1.0 * k * n + 4.0 * n + 0.25 * k * n + 2048.0
    return 4.0 * k * n


def heuristic_quant(op, k, n, dtype):
    """Cost-model backend choice for one decode-matmul shape bucket:
    'int8' (weight-only-quantized kernel) when the quantized bytes +
    dequant tax undercut the float weight read, else 'fp'."""
    del op, dtype
    ci, cf = quant_cost(k, n, "int8"), quant_cost(k, n, "fp")
    backend = "int8" if ci < cf else "fp"
    return {"backend": backend, "source": "heuristic",
            "score": round(min(ci, cf), 3)}


def resolve_quant(op, k, n, dtype):
    """The per-shape quantized-vs-float decision a serving engine's
    weight quantization consults (TinyDecoder.quantize_params): table
    hit, else the cost model, recorded under the pow2 (k, n) bucket.
    Like resolve_paged there is no inline measurement (the decision is
    made at engine build, not dispatch) — measured entries arrive via
    offline sweeps writing the table and are never downgraded here.
    ``MXT_TUNE_MODE=off`` bypasses the table entirely."""
    if _mode() == "off":
        return heuristic_quant(op, k, n, dtype)
    tab = _table_mod.table()
    key = _table_mod.quant_key(op, k, n, dtype)
    ent = tab.lookup(key)
    if ent is not None:
        return ent
    return tab.record(key, heuristic_quant(op, k, n, dtype))


# --------------------------------------------------------------------------
# BN backward
# --------------------------------------------------------------------------
def bn_candidates(m, c):
    """Legal block_rows values for a (M, C) BN backward: sublane
    multiples, bounded by the padded row count and a per-buffer VMEM
    budget (two f32 (bm, C) buffers resident per pass)."""
    m8 = _round8(m)
    out = []
    for bm in (8, 16, 32, 64, 128, 256, 512, 1024):
        bm = min(bm, m8)
        if 2 * bm * int(c) * 4 > _VMEM_BUDGET // 2:
            continue
        if bm not in out:
            out.append(bm)
    return out or [_SUBLANE]


def bn_cost(m, c, bm):
    pm = _pad_to(m, bm)
    cost = 1.0 * pm * c
    cost *= 1.0 + 0.004 * (pm // bm)
    if bm < 64:
        cost *= 1.0 + (64 - bm) / 256.0
    return cost


def heuristic_bn(m, c, dtype):
    """Cost-model block_rows; backend stays 'xla' until a measurement
    says otherwise (the fused BN backward is opt-in per shape via
    measured entries or the MXT_BN_PALLAS global override)."""
    best, best_cost = None, math.inf
    for bm in bn_candidates(m, c):
        cc = bn_cost(m, c, bm)
        if cc < best_cost:
            best, best_cost = bm, cc
    return {"backend": "xla", "block_rows": best,
            "source": "heuristic", "score": round(best_cost, 3)}


def measure_bn(x2d, dy2d, mean, inv, g, interpret=False, iters=None,
               candidates=None):
    """Time candidate block_rows for the fused BN backward plus the XLA
    custom-VJP formulas; returns the winning entry dict. Like
    measure_attention, a candidate the compiler refuses raises."""
    import jax.numpy as jnp

    from ..ops import bn_pallas

    iters = iters or int(_config().get("MXT_TUNE_ITERS"))
    m, c = x2d.shape
    timings = {}
    for bm in (candidates or bn_candidates(m, c)):
        def run(bm=bm):
            return bn_pallas.bn_bwd_pallas(
                x2d, dy2d, mean, inv, g, interpret=interpret,
                block_rows=bm)
        timings[("pallas", bm)] = _time(run, iters)

    def ref():
        dy = dy2d.astype(jnp.float32)
        xhat = (x2d.astype(jnp.float32) - mean.reshape(1, c)) \
            * inv.reshape(1, c)
        db = jnp.sum(dy, axis=0)
        dg = jnp.sum(dy * xhat, axis=0)
        dx = (g.reshape(1, c) * inv.reshape(1, c)) * (
            dy - db.reshape(1, c) / m - xhat * dg.reshape(1, c) / m)
        return dx, dg, db
    timings[("xla", 0)] = _time(ref, iters)

    (backend, bm), score = min(timings.items(), key=lambda kv: kv[1])
    return {"backend": backend, "block_rows": bm,
            "source": "measured", "score": round(score * 1e3, 6)}


# --------------------------------------------------------------------------
# shared timing loop
# --------------------------------------------------------------------------
def _block(res):
    """Synchronize a result pytree (measurement only — never hot path)."""
    import jax

    for leaf in jax.tree_util.tree_leaves(res):
        if hasattr(leaf, "block_until_ready"):  # sync-ok: measurement loop
            leaf.block_until_ready()  # sync-ok: autotuner measurement loop


def _time(fn, iters):
    """Median-of-iters wall time of ``fn`` after one warm (compile)
    call. Median resists the one-off scheduling hiccup that would
    otherwise misrank close candidates."""
    _block(fn())  # compile + warm  # sync-ok: autotuner measurement loop
    samples = []
    for _ in range(max(1, iters)):
        t0 = time.perf_counter()
        _block(fn())
        samples.append(time.perf_counter() - t0)
    samples.sort()
    return samples[len(samples) // 2]


# --------------------------------------------------------------------------
# resolution: table -> measure/heuristic -> record
# --------------------------------------------------------------------------
def _mode():
    return str(_config().get("MXT_TUNE_MODE")).lower()


def _may_measure(arrays):
    """Measurement needs concrete arrays (not tracers — inside a jit
    trace there is nothing to time) and an allowing mode: 'measure'
    anywhere, 'auto' only on a real TPU."""
    import jax

    mode = _mode()
    if not (mode == "measure" or (mode == "auto" and on_tpu())):
        return False
    return not any(isinstance(a, jax.core.Tracer)
                   for a in arrays if a is not None)


def resolve_attention(q_shape, kv_len, dtype, causal, arrays=None,
                      kv_heads=None, mask=None):
    """The per-call decision the flash kernel consumes: table hit, else
    measure (when allowed) or cost model, recorded either way. The key
    carries the K/V head count and whether a selection mask is there."""
    tab = _table_mod.table()
    key = _table_mod.attn_key(q_shape, kv_len, dtype, causal,
                              kv_heads=kv_heads, masked=mask is not None)
    ent = tab.lookup(key)
    if ent is not None:
        return ent
    if arrays is not None and _may_measure(arrays):
        q, k, v, bias, sm_scale = arrays
        ent = measure_attention(q, k, v, bias, causal, sm_scale,
                                interpret=not on_tpu(), mask=mask)
    else:
        ent = heuristic_attention(q_shape, kv_len, dtype, causal)
    return tab.record(key, ent)


def resolve_bn(m, c, dtype, arrays=None):
    tab = _table_mod.table()
    key = _table_mod.bn_key(m, c, dtype)
    ent = tab.lookup(key)
    if ent is not None:
        return ent
    if arrays is not None and _may_measure(arrays):
        x2d, dy2d, mean, inv, g = arrays
        ent = measure_bn(x2d, dy2d, mean, inv, g, interpret=not on_tpu())
    else:
        ent = heuristic_bn(m, c, dtype)
    return tab.record(key, ent)
