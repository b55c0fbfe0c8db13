"""The cost model that picks each Pallas kernel's tiles, and XLA or the kernel
by shape.

Candidate generation and a deterministic cost model (TVM's split, PAPERS.md
arXiv 1802.04799, scoped to block/grid configs, without its search).
Candidates are TPU-tiling-legal by construction: multiples of 8 in the
sublane dimension, lane-friendly (128-multiple preferred) in the key
dimension, VMEM-budgeted; tests/test_aot_tpu_compile.py holds the generators
to it by compiling what they emit for the described chip. The cost model
charges padded work (the kernels pad-and-mask partial blocks, so a block that
divides the padded shape badly wastes real MXU cycles), per-grid-step
overhead, and tile-shape penalties. It is a pure function of the shape: same
inputs, same config, so a program built twice is the same program.

Nothing is timed here. A call inside a jitted step is traced, with nothing to
time; where the forward's candidates were timed by hand, at three cells'
shapes, the model's tiles held (PERF.md Findings, PR 29). A
``source="measured"`` entry reaches the table from a sweep that writes it
(``tuning.table().record``, or the table's file), and no resolver ever
overwrites one. ``MXT_TUNE_MODE=off`` means the same in every resolver: the
cost model's answer, the table neither read nor written.
"""
from __future__ import annotations

import math

from ..base import MXNetError
from ..ops import chip as _chip
from . import table as _table_mod

# leave headroom under the scoped VMEM the chip's compiler gives unasked;
# every candidate the generators below emit under this budget compiles
# for the described v5e at the shapes in tests/test_aot_tpu_compile.py
_VMEM_BUDGET = 3 * _chip.VMEM_SCOPED_DEFAULT // 4
_LANE = _chip.LANES
_SUBLANE = _chip.SUBLANES[4]

_MODES = ("heuristic", "off")  # what MXT_TUNE_MODE may say


def _mode():
    from .. import config

    mode = str(config.get("MXT_TUNE_MODE")).lower()
    if mode not in _MODES:
        raise MXNetError("MXT_TUNE_MODE=%r: one of %s" % (mode, ", ".join(_MODES)))
    return mode


def _resolve(key, choose):
    """What every resolver below does with its cost model ``choose``: the
    table's entry under ``key``, or ``choose()`` recorded there (a
    ``measured`` entry, written by hand, is never downgraded by a record).
    ``MXT_TUNE_MODE=off``: ``choose()``, the table neither read nor written."""
    if _mode() == "off":
        return choose()
    tab = _table_mod.table()
    ent = tab.lookup(key)
    return tab.record(key, choose()) if ent is None else ent


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


def resolve_attention(q_shape, kv_len, dtype, causal, kv_heads=None, mask=None):
    """The per-call decision the flash kernel consumes (``_resolve``). The
    key carries the K/V head count and whether a selection mask is there."""
    return _resolve(
        _table_mod.attn_key(q_shape, kv_len, dtype, causal, kv_heads=kv_heads,
                            masked=mask is not None),
        lambda: heuristic_attention(q_shape, kv_len, dtype, causal))


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
    """The per-call decision the paged decode kernel consumes, under the
    decode-shape bucket (``_resolve``)."""
    return _resolve(_table_mod.paged_key(q_shape, page_size, max_pages, dtype),
                    lambda: heuristic_paged(q_shape, page_size, max_pages, dtype))


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
    weight quantization consults (TinyDecoder.quantize_params), under the
    pow2 (k, n) bucket (``_resolve``)."""
    return _resolve(_table_mod.quant_key(op, k, n, dtype),
                    lambda: heuristic_quant(op, k, n, dtype))
