"""Lightning indexer: the learned key selection of DeepSeek-V3.2's sparse
attention (DeepSeek-AI 2025, "DeepSeek-V3.2-Exp"; the models that publish an
``sa_config`` / ``index_*`` group carry it), as a mask for
:func:`flash_attention`.

For every query ``t`` a few light heads score every earlier key,

    I[t, s] = sum_j w[t, j] * ReLU(q[t, j] . k[s])        (s <= t),

and the ``topk`` keys of largest ``I[t, .]`` are the set ``S_t`` the real
attention reads; a tie goes to the lower ``s`` (what ``lax.top_k`` gives) and
a row with at most ``topk`` candidates selects all of them, without a search.
The selection is exact. No gradient passes through it.

One function, :func:`lightning_indexer`, from ``q`` (B, T, Hi, Di), ``k``
(B, T, Di) and ``w`` (B, T, Hi) to the int8 mask (B, T, T) of ``S_t``.
Matmul operands stay in the input dtype; the sum over the heads, the scores
and every comparison are float32. ``I`` never reaches HBM: on a TPU one
Pallas kernel (``indexer_select``) takes a block of query rows, computes its
scores into VMEM (as sortable int32 keys, key-major so that a query's
threshold is a lane of a row), finds each row's ``topk``-th largest key by
bisection on its bits (32 counting passes over VMEM; ``lax.top_k`` is a full
sort on the chip) and the cut among the keys tied with it by bisection on
their position (14 passes at 8192), and writes the block's mask tiles.
Everywhere else the same scores are taken a block of rows at a time in XLA
and cut with ``lax.top_k``'s threshold: both are the exact selection of the
same float32 scores.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError
from ..context import on_tpu
from . import chip as _chip
from .registry import register

F32, I32 = jnp.float32, jnp.int32
_INT_MIN = np.int32(-2 ** 31)
_BLOCK_Q, _BLOCK_K = 256, 512  # query rows a grid step, keys a tile
_XLA_ROWS = 512                # query rows a block of the XLA path


def _sortable(scores):
    """float32 -> int32 with the same order (NaN aside): the bits of a
    non-negative float order as integers, those of a negative one in
    reverse."""
    bits = jax.lax.bitcast_convert_type(scores, I32)
    return jnp.where(bits < 0, jnp.bitwise_xor(bits, np.int32(0x7FFFFFFF)), bits)


def _scores(q, k, w, key_major=False):
    """``I`` of query rows against keys ``k`` (Tk, Di): ``q`` gives a head's
    (Tq, Di) queries and ``w`` its weights, shaped to broadcast against the
    tile, for each head in turn. (Tq, Tk) float32, or (Tk, Tq) ``key_major``.
    Operands as they come; products and the sum over the heads, head 0
    first, in float32 from +0. The kernel and the XLA path both call this."""
    nt = (((1,), (1,)), ((), ()))  # a @ b.T
    acc = None
    for qj, wj in zip(q, w):
        a, b = (k, qj) if key_major else (qj, k)
        s = jax.lax.dot_general(a, b, nt, preferred_element_type=F32)
        term = wj.astype(F32) * jnp.maximum(s, F32(0.0))
        acc = F32(0.0) + term if acc is None else acc + term
    return acc


# ---------------------------------------------------------------------------
# XLA path: a block of rows at a time, the threshold from lax.top_k
# ---------------------------------------------------------------------------
def _select_rows(q, k, w, row0, topk):
    """Mask (rows, T) of a block of query rows ``row0 ...`` of one sequence."""
    rows, T = q.shape[1], k.shape[0]
    with jax.named_scope("scores"):
        key = _sortable(_scores(q, k, w[:, :, None]))
    with jax.named_scope("select"):
        row = row0 + jnp.arange(rows, dtype=I32)[:, None]
        col = jnp.arange(T, dtype=I32)[None, :]
        causal = col <= row
        key = jnp.where(causal, key, _INT_MIN)
        kth = jax.lax.top_k(key, topk)[0][:, -1:]
        above, tied = key > kth, jnp.logical_and(key == kth, causal)
        need = topk - jnp.sum(above, axis=1, keepdims=True, dtype=I32)
        among = jnp.cumsum(tied, axis=1, dtype=I32)  # a tie goes to the lower s
        chosen = jnp.logical_or(above, jnp.logical_and(tied, among <= need))
        chosen = jnp.logical_or(chosen, row < topk)  # at most topk candidates
        return jnp.logical_and(chosen, causal).astype(jnp.int8)


def _select_xla(q, k, w, topk):
    """(B, Hi, T, Di), (B, T, Di), (B, Hi, T) -> (B, T, T) int8."""
    B, Hi, T, Di = q.shape
    rows = min(_XLA_ROWS, T)
    pad = (-T) % rows
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, 0), (0, pad)))
    nblk = (T + pad) // rows

    def one_sequence(qkw):
        qs, ks, ws = qkw
        blocks = jax.lax.map(
            lambda i: _select_rows(
                jax.lax.dynamic_slice_in_dim(qs, i * rows, rows, axis=1), ks,
                jax.lax.dynamic_slice_in_dim(ws, i * rows, rows, axis=1),
                i * rows, topk),
            jnp.arange(nblk, dtype=I32))
        return blocks.reshape(nblk * rows, T)[:T]

    return jax.lax.map(one_sequence, (q, k, w))


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------
def _select_kernel(q_ref, k_ref, w_ref, out_ref, keys_ref, *, topk, block_k,
                   cut_bits):
    """One (sequence, block of query rows) grid step. ``q_ref`` (1, Hi, bq,
    Di), ``k_ref`` (1, Tp, Di) whole, ``w_ref`` (1, Hi, 1, bq) rows,
    ``out_ref`` (1, 1, Tp / bk, bq, bk) int8 tiles, ``keys_ref`` (Tp, bq)
    int32 scratch. Tiles are held key-major, ``[key, query]``: a query's
    weights, threshold and counts are then lanes of a (1, bq) row that
    broadcasts over the sublanes, and a count over the keys is a sum of
    vector registers."""
    from jax.experimental import pallas as pl

    bq = q_ref.shape[2]
    nk = out_ref.shape[2]
    q_off = pl.program_id(1) * bq
    # key tiles up to the one that holds this block's last visible key
    nk_c = jnp.minimum(I32(nk), jax.lax.div(q_off + I32(bq + block_k - 1),
                                            I32(block_k)))
    one, zero8 = np.int32(1), jnp.zeros((bq, block_k), jnp.int8)

    def tile_pos(ik):
        """(key position, query position) of key tile ``ik``, key-major."""
        kcol = ik * block_k + jax.lax.broadcasted_iota(I32, (block_k, bq), 0)
        qrow = q_off + jax.lax.broadcasted_iota(I32, (block_k, bq), 1)
        return kcol, qrow

    def keys_of(ik):
        return keys_ref[pl.ds(pl.multiple_of(ik * block_k, block_k), block_k), :]

    @pl.when(q_off + bq <= topk)
    def _all_candidates():  # no row has more than topk: the causal pairs
        def body(ik, _):
            col = ik * block_k + jax.lax.broadcasted_iota(I32, (bq, block_k), 1)
            row = q_off + jax.lax.broadcasted_iota(I32, (bq, block_k), 0)
            out_ref[0, 0, ik] = (col <= row).astype(jnp.int8)
            return _
        jax.lax.fori_loop(I32(0), I32(nk), body, I32(0))

    @pl.when(q_off + bq > topk)
    def _search():
        q = [q_ref[0, j] for j in range(q_ref.shape[1])]
        w = [w_ref[0, j] for j in range(w_ref.shape[1])]  # (1, bq) rows

        def score(ik, _):
            k_blk = k_ref[0, pl.ds(pl.multiple_of(ik * block_k, block_k),
                                   block_k), :]
            acc = _scores(q, k_blk, w, key_major=True)
            kcol, qrow = tile_pos(ik)
            keys_ref[pl.ds(pl.multiple_of(ik * block_k, block_k), block_k), :] = (
                jnp.where(kcol <= qrow, _sortable(acc), _INT_MIN))
            return _
        jax.lax.fori_loop(I32(0), nk_c, score, I32(0))

        def count(pred):
            """(1, bq): keys of each query for which ``pred(tile, ik)``."""
            def body(ik, c):
                return c + jnp.sum(pred(keys_of(ik), ik).astype(I32), axis=0,
                                   keepdims=True, dtype=I32)
            return jax.lax.fori_loop(I32(0), nk_c, body, jnp.zeros((1, bq), I32))

        # the topk-th largest key of each row: the largest theta with
        # count(key >= theta) >= topk, built from the sign bit down
        def grow(theta, cand):
            enough = count(lambda t, ik: t >= cand) >= topk
            return jnp.where(enough, cand, theta)

        theta = grow(jnp.full((1, bq), _INT_MIN, I32), jnp.zeros((1, bq), I32))
        theta = jax.lax.fori_loop(
            I32(0), I32(31),
            lambda i, th: grow(th, jnp.bitwise_or(
                th, jnp.left_shift(one, I32(30) - i))), theta)
        # the keys tied at theta fill what the larger ones leave, the lower
        # positions first: the largest cut with count(tied, pos < cut) <= need
        need = topk - count(lambda t, ik: t > theta)

        def tied_below(cut):
            return count(lambda t, ik: jnp.logical_and(t == theta,
                                                       tile_pos(ik)[0] < cut))

        def grow_cut(i, cut):
            cand = jnp.bitwise_or(cut, jnp.left_shift(one, I32(cut_bits - 1) - i))
            return jnp.where(tied_below(cand) <= need, cand, cut)

        cut = jax.lax.fori_loop(I32(0), I32(cut_bits), grow_cut,
                                jnp.zeros((1, bq), I32))

        def write(ik, _):
            t = keys_of(ik)
            kcol, qrow = tile_pos(ik)
            chosen = jnp.logical_or(
                t > theta, jnp.logical_and(t == theta, kcol < cut))
            chosen = jnp.logical_or(chosen, qrow < topk)
            chosen = jnp.logical_and(chosen, kcol <= qrow)
            out_ref[0, 0, ik] = chosen.astype(I32).T.astype(jnp.int8)
            return _
        jax.lax.fori_loop(I32(0), nk_c, write, I32(0))

        def blank(ik, _):  # tiles wholly right of the diagonal
            out_ref[0, 0, ik] = zero8
            return _
        jax.lax.fori_loop(nk_c, I32(nk), blank, I32(0))


def _select_pallas(q, k, w, topk, interpret, block_q=_BLOCK_Q, block_k=_BLOCK_K):
    """(B, Hi, T, Di), (B, T, Di), (B, Hi, T) -> (B, T, T) int8 through the
    kernel. Held in VMEM at 8192 x 16 heads of 64 with (256, 512) blocks,
    minor dimensions rounded up to the 128 lanes: the keys of the block
    8.4 MB, K whole 2 x 2.1 MB, the Q block 2 x 1 MB, the mask tiles 2 x
    2.1 MB, and a few (512, 256) float32 tiles: 19.9 MB, 24.9 MB asked for."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Hi, T, Di = q.shape
    block_q, block_k = min(block_q, T), min(block_k, T)
    tile = max(block_q, block_k)
    if tile % block_q or tile % block_k:
        raise MXNetError("indexer blocks (%d, %d) do not nest" % (block_q, block_k))
    pad = (-T) % tile
    if pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, 0), (0, pad)))
    Tp = T + pad
    nq, nk = Tp // block_q, Tp // block_k
    lanes = -(-Di // 128) * 128
    held = (Tp * block_q * 4 + 2 * Tp * lanes * k.dtype.itemsize
            + 2 * Hi * block_q * lanes * q.dtype.itemsize + 2 * block_q * Tp
            + 2 * Hi * 8 * block_q * 4 + 6 * block_q * block_k * 4)
    z = np.int32(0)
    tiles = pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, block_k=block_k,
                          cut_bits=int(Tp).bit_length()),
        grid=(B, nq),
        in_specs=[
            pl.BlockSpec((1, Hi, block_q, Di), lambda b, iq: (b, z, iq, z),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, Tp, Di), lambda b, iq: (b, z, z),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, Hi, 1, block_q), lambda b, iq: (b, z, z, iq),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, nk, block_q, block_k),
                               lambda b, iq: (b, iq, z, z, z),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((B, nq, nk, block_q, block_k), jnp.int8),
        scratch_shapes=[pltpu.VMEM((Tp, block_q), I32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=max(held + held // 4, _chip.VMEM_SCOPED_DEFAULT)),
        interpret=interpret,
        name="indexer_select",
    )(q, k, w.reshape(B, Hi, 1, Tp))
    mask = tiles.transpose(0, 1, 3, 2, 4).reshape(B, Tp, Tp)
    return mask[:, :T, :T]


@register("lightning_indexer", differentiable=False, num_outputs=3)
def lightning_indexer(query, key, weights, topk=2048):
    """The selection mask of a lightning indexer: see the module's
    docstring. ``query`` (B, T, Hi, Di) and ``key`` (B, T, Di), both with
    their positions applied; ``weights`` (B, T, Hi), with every scale folded
    in. Returns ``(mask, selected, searched)``: int8 (B, T, T), 1 where key
    ``s`` is in ``S_t``; int64 () the pairs selected; int64 () the rows that
    had more than ``topk`` candidates and were searched. On a TPU the rows
    searched go through the kernel ``indexer_select``, elsewhere through XLA.
    Its operations stand under the scopes ``scores`` and ``select`` (the
    kernel, which does both, under ``select``), beneath whatever scope the
    caller is in (the model zoo's block: ``indexer``)."""
    topk = int(topk)
    if topk < 1:
        raise MXNetError("lightning_indexer: topk=%d" % topk)
    B, T, Hi, Di = query.shape
    if key.shape != (B, T, Di) or weights.shape != (B, T, Hi):
        raise MXNetError("lightning_indexer: key %s and weights %s do not fit "
                         "query %s" % (key.shape, weights.shape, query.shape))
    # no gradient passes through the selection: cut it before the kernel, which
    # has no differentiation rule and needs none
    query, key, weights = (jax.lax.stop_gradient(a) for a in (query, key, weights))
    if T <= topk:  # no row has more than topk candidates
        mask = jnp.broadcast_to(jnp.tril(jnp.ones((T, T), jnp.int8)), (B, T, T))
    else:
        q = jnp.transpose(query, (0, 2, 1, 3))    # (B, Hi, T, Di)
        w = jnp.transpose(weights, (0, 2, 1))     # (B, Hi, T)
        if on_tpu():
            with jax.named_scope("select"):  # scores and selection, fused
                mask = _select_pallas(q, key, w, topk, False)
        else:
            mask = _select_xla(q, key, w, topk)
    # a sequence's pairs fit int32 (T < 65536); a batch's may not
    selected = jnp.sum(jnp.sum(mask, axis=(1, 2), dtype=I32), dtype=jnp.int64)
    searched = jnp.asarray(B * max(T - topk, 0), jnp.int64)
    return mask, selected, searched
