"""Mamba-2's state-space scan (``ops/ssd.py``: ``ssd_scan``) as the program's
own kernels, which keep a chunk's scores, its decay mask and the masked scores
in VMEM: none of the (chunks x heads x chunk x chunk) tensors exists in HBM.

XLA's form of the op writes the masked scores out (268 MB of bfloat16 a layer
at the Granite cell's shape) for the product that reads them back, and the
backward builds that tensor again, reads it twice and writes a second one of
its shape. Here one grid step holds one chunk of ``_HEADS`` heads of a group:
the chunk's scores come from VMEM scratch (made once a chunk, shared by the
group's heads), each head's mask is built where it multiplies them, and the
chunks' carry rides in VMEM scratch over the grid's chunk axis.

**Tokens lie along the lanes**, as ``ops/causal_conv_pallas.py`` found the
compiled step to hold a Mamba-2 mixer: ``x`` and ``y`` as (B, H P, T), ``B`` and
``C`` as (B, G N, T), the float32 ``dt`` (after its bias and softplus) and the
running sums ``cum`` as (B, H, T); a chunk is ``chunk / 128`` lane tiles. The
``swapaxes`` around the calls are a choice of layout, not a copy. Everything of
a (chunk x chunk) shape is built transposed, ``[j, i]`` with the later token
``i`` along the lanes: a head's ``cum`` is a row as it arrives (``cum_i``) and
a column (``cum_j``) out of one small transpose of the step's ``_HEADS`` rows;
and by (128 x 128) tile at and under the diagonal: a tile above it is zeros, and
is neither built nor multiplied.

* ``ssd_chunk_fwd``, grid (batch, group, chunk, block of heads), the chunks in
  order: by the docstring's steps of ``ops/ssd.py`` (1) the scores ``C_i .
  B_j`` once a chunk; (2) for each head the mask ``exp(cum_i - cum_j)`` under
  the diagonal in float32, its product with the scores cast to the operands'
  type, and that times ``dt x``; (4) the read ``exp(cum_i) C_i . S_open`` of the
  opening state (all the step's heads in one product) and ``D x``; (3) the
  closing state (one product too) onto the carry ``S' = exp(cum_last) S +
  closing``, float32 in scratch. Writes ``y`` and each chunk's opening state,
  the backward's residual.
* ``ssd_chunk_bwd``, the same grid with the chunks in REVERSE: the reverse
  carry (what reaches a chunk's closing state from the reads after it) rides in
  scratch as the forward's does, and the group's ``dscores`` are summed over the
  block's heads and over the blocks of heads in scratch, so one kernel has both
  the heads' sum a chunk and the chunks' carry a head. Scores and mask are
  built once a (chunk, head) and used for ``y`` (the rows' sum), ``dx_within``,
  ``pairs`` and ``dscores``; then ``dB``, ``dC``, ``dx`` and, a row of tokens a
  head, ``ddt``'s first part, ``dcum`` and ``D``'s gradient before its sum. The
  rows and columns of ``dcum`` take the SAME rounded operands (``xdt`` and
  ``x_end`` in the operands' type), as the formula's do.

What stays XLA's, on (B, H, T) float32 arrays of 2 MB: ``softplus``, the
running sums before the calls; after the backward the sums' reverse walk
(``da``), ``ddt``'s second part and its softplus derivative, and the per-head
sums. So the kernels are named for what they are and the scopes ``ssd`` /
``ssd_bwd`` hold the whole op.

The precision is the formula's, letter for letter: ``softplus``, decays, sums,
masks and states float32; the operands of the products in ``x``'s type with
float32 accumulation; the result in ``x``'s type. ``kernel_takes`` is the rule:
a TPU, bfloat16 or float32, a chunk of whole lane tiles, P and N whole tiles,
groups of whole blocks of heads, the tiles within ``chip.VMEM_CEILING``. Every
other call is the ``jax.numpy`` formula in ``ops/ssd.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..context import on_tpu
from . import chip as _chip

F32 = jnp.float32
# Heads a grid step holds: a float32 tile's sublanes of dt and cum. Stand-alone at (1,
# 8192, 64, 64) x 128 in chunks of 256, ms a call forward / backward (my chip runs, PR 48):
# 8 heads 0.445 / 0.876, 16 heads 0.400 / 0.800 (half the grid's steps), and with one
# (256 x 256) tile a chunk in place of the three (128 x 128) at and under the diagonal 0.455
# / 0.922 and 0.405 / 0.837. 16 would save the Granite step 1.5 ms of 339 and refuse groups
# of 8 heads: 8 kept.
_HEADS = 8
_LANES = _chip.LANES


def _dot(a, b, i, j):
    """``a`` and ``b`` contracted over their axes ``i`` and ``j``, float32 out."""
    return jax.lax.dot_general(a, b, (((i,), (j,)), ((), ())), preferred_element_type=F32)


def _step(x_ref, cum_ref):
    """What both kernels read of a grid step: P, the tiles of its chunk's
    tokens (slices of 128), ``lower`` for a tile on the diagonal ((j, i):
    the later token along the lanes is at or after the earlier), the step's
    heads, and their running sums as rows (heads, Q) and as columns (Q, heads)."""
    heads = cum_ref.shape[1]
    p, q = x_ref.shape[1] // heads, x_ref.shape[2]
    j = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 1)
    cum = cum_ref[0]
    return p, [slice(a, a + _LANES) for a in range(0, q, _LANES)], i >= j, heads, cum, cum.T


def _masked(st_ref, tiles, lower, row, col, kind):
    """A head's decay mask and masked scores, transposed ((j, i)) and by tile
    {(a, b)} at and under the diagonal (a tile above it is zeros, and is
    neither built nor multiplied): ``exp(cum_i - cum_j)`` in float32, under a
    diagonal tile's own diagonal, and its product with the chunk's scores in
    the operands' type."""
    out = {}
    for a, rows in enumerate(tiles):
        for b, cols in enumerate(tiles[a:], a):
            decay = row[:, cols] - col[rows]
            mask = jnp.exp(jnp.where(lower, decay, -jnp.inf) if a == b else decay)
            out[a, b] = mask, (st_ref[rows, cols] * mask).astype(kind)
    return out


def _sum(parts):
    return functools.reduce(jnp.add, parts)


def _within(z, masked, tiles):
    """``z`` (P, j) times the masked scores (j, i): what a chunk's tokens give
    the later ones, (P, i)."""
    return jnp.concatenate([_sum(_dot(z[:, tiles[a]], masked[a, b][1], 1, 0) for a in range(b + 1))
                            for b in range(len(tiles))], axis=1)


def _within_t(z, masked, tiles):
    """``z`` (P, i) times the masked scores' transpose: what reaches a token
    from the later ones, (P, j)."""
    n = len(tiles)
    return jnp.concatenate([_sum(_dot(z[:, tiles[b]], masked[a, b][1], 1, 1) for b in range(a, n))
                            for a in range(n)], axis=1)


def _fwd_kernel(d_ref, x_ref, b_ref, c_ref, dt_ref, cum_ref, y_ref, open_ref,
                state_ref, st_ref):
    chunk, block = pl.program_id(2), pl.program_id(3)
    kind = x_ref.dtype
    p, tiles, lower, heads, cum, cum_t = _step(x_ref, cum_ref)
    first = (pl.program_id(1) * pl.num_programs(3) + block) * np.int32(heads)

    @pl.when(chunk == 0)
    def _open():
        state_ref[block] = jnp.zeros(state_ref.shape[1:], F32)

    @pl.when(block == 0)
    def _scores():  # (1): C_i . B_j as (j, i), once a chunk
        st_ref[...] = _dot(b_ref[0], c_ref[0], 0, 0)

    state = state_ref[block]
    open_ref[0, 0] = state
    read = _dot(state.astype(kind), c_ref[0], 1, 0)  # (4), every head of the step
    dt = dt_ref[0]
    ends, decays = [], []
    for k in range(heads):
        rows = slice(k * p, (k + 1) * p)
        row, col = cum[k:k + 1], cum_t[:, k:k + 1]
        masked = _masked(st_ref, tiles, lower, row, col, kind)
        x = x_ref[0, rows, :].astype(F32)
        xdt = x * dt[k:k + 1]
        y = _within(xdt.astype(kind), masked, tiles) + read[rows] * jnp.exp(row)  # (2), (4)
        y_ref[0, rows, :] = (y + d_ref[first + np.int32(k)] * x).astype(kind)
        last = row[:, -1:]
        ends.append((xdt * jnp.exp(last - row)).astype(kind))
        decays.append(jnp.broadcast_to(jnp.exp(last), (p, 1)))
    closing = _dot(jnp.concatenate(ends, axis=0), b_ref[0], 1, 1)  # (3)
    state_ref[block] = jnp.concatenate(decays, axis=0) * state + closing


def _bwd_kernel(d_ref, x_ref, dy_ref, b_ref, c_ref, dt_ref, cum_ref, open_ref,
                dx_ref, db_ref, dc_ref, ddt_ref, dcum_ref, dd_ref,
                dstate_ref, closed_ref, st_ref, dst_ref, dbc_ref):
    step, block = pl.program_id(2), pl.program_id(3)
    kind = x_ref.dtype
    p, tiles, lower, heads, cum, cum_t = _step(x_ref, cum_ref)
    first = (pl.program_id(1) * pl.num_programs(3) + block) * np.int32(heads)
    q = x_ref.shape[2]
    at_end = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1

    @pl.when(step == 0)  # the sequence's last chunk: nothing reads after it
    def _open():
        dstate_ref[block] = jnp.zeros(dstate_ref.shape[1:], F32)
        closed_ref[block] = jnp.zeros(closed_ref.shape[1:], F32)

    @pl.when(block == 0)
    def _scores():
        st_ref[...] = _dot(b_ref[0], c_ref[0], 0, 0)
        dst_ref[...] = jnp.zeros(dst_ref.shape, F32)
        dbc_ref[...] = jnp.zeros(dbc_ref.shape, F32)

    opening, dclosing, closed = open_ref[0, 0], dstate_ref[block], closed_ref[block]
    state, dstate = opening.astype(kind), dclosing.astype(kind)
    read = _dot(state, c_ref[0], 1, 0)  # the forward's read of the opening state
    dx_state = _dot(dstate, b_ref[0], 1, 0)
    carried = dstate.astype(F32) * closed  # what a chunk's last sum carries on
    dt = dt_ref[0]
    starts, ends, decays, ddt, dcum, dd = [], [], [], [], [], []
    for k in range(heads):
        rows = slice(k * p, (k + 1) * p)
        row, col = cum[k:k + 1], cum_t[:, k:k + 1]
        masked = _masked(st_ref, tiles, lower, row, col, kind)
        x, dy = x_ref[0, rows, :].astype(F32), dy_ref[0, rows, :].astype(F32)
        last = row[:, -1:]
        from_start, to_end = jnp.exp(row), jnp.exp(last - row)
        xdt32 = x * dt[k:k + 1]
        xdt, x_end = xdt32.astype(kind), (xdt32 * to_end).astype(kind)
        # the forward's result without D x, for the running sums' rows
        y = _within(xdt, masked, tiles) + read[rows] * from_start
        # dx^: the gradient of dt x, from its chunk's tokens and from the state
        dx_within = _within_t(dy_ref[0, rows, :], masked, tiles)
        dxdt = dx_within + dx_state[rows] * to_end
        dx = dt[k:k + 1] * dxdt + d_ref[first + np.int32(k)] * dy
        dx_ref[0, rows, :] = dx.astype(kind)
        ddt.append(jnp.sum(x * dxdt, axis=0, keepdims=True))
        # rows less columns of the same masked products, on the same rounded
        # operands, and at the chunk's last token what its closing state carries
        ddc = jnp.sum(dy * y - xdt.astype(F32) * dx_within
                      - x_end.astype(F32) * dx_state[rows], axis=0, keepdims=True)
        carry = jnp.sum(jnp.sum(carried[rows], axis=0, keepdims=True), axis=1, keepdims=True)
        dcum.append(ddc + jnp.where(at_end, carry, 0.0))
        dd.append(jnp.sum(dy * x, axis=0, keepdims=True))
        # the pairs' scores (j, i), a group's heads summed under their masks
        for (a, b), (mask, _) in masked.items():
            pairs = _dot(xdt[:, tiles[a]], dy_ref[0, rows, tiles[b]], 0, 0).astype(kind)
            dst_ref[tiles[a], tiles[b]] += pairs.astype(F32) * mask
        starts.append((dy * from_start).astype(kind))
        ends.append(x_end)
        decays.append(jnp.broadcast_to(jnp.exp(last), (p, 1)))
    ddt_ref[0], dcum_ref[0], dd_ref[0] = (jnp.concatenate(z, axis=0) for z in (ddt, dcum, dd))
    starts, ends = jnp.concatenate(starts, axis=0), jnp.concatenate(ends, axis=0)
    # the reverse carry: what reaches the closing state of the chunk BEFORE
    dstate_ref[block] = jnp.concatenate(decays, axis=0) * dclosing + _dot(starts, c_ref[0], 1, 1)
    closed_ref[block] = opening
    # B's and C's gradients from the states, (N, tokens), summed over the heads
    dbc_ref[0] += _dot(dstate, ends, 0, 0)
    dbc_ref[1] += _dot(state, starts, 0, 0)

    @pl.when(block + 1 == pl.num_programs(3))
    def _close():
        dscores = dst_ref[...].astype(kind)  # (j, i), zeros above the diagonal
        db_ref[0] = (_dot(c_ref[0], dscores, 1, 1) + dbc_ref[0]).astype(db_ref.dtype)
        dc_ref[0] = (_dot(b_ref[0], dscores, 1, 0) + dbc_ref[1]).astype(dc_ref.dtype)


def _vmem(p, n, q, itemsize, states):
    """Bytes the backward's step asks for: its blocks twice (x, dy, dx, the
    opening states, B, C and their gradients, six rows a head), the carry's two
    scratches over the group's ``states`` blocks of heads, the three (chunk x
    chunk) and the two (N x chunk) float32 scratches, and what a head's walk
    keeps at once (a dozen (P x chunk) and six (chunk x chunk) float32)."""
    heads = _HEADS
    blocks = 2 * (3 * heads * p * q * itemsize + heads * p * n * 4
                  + 4 * n * q * itemsize + 6 * heads * q * 4)
    scratch = 2 * states * heads * p * n * 4 + 3 * q * q * 4 + 2 * n * q * 4
    return blocks + scratch + heads * 12 * p * q * 4 + 6 * q * q * 4


def _grid(x, dt, groups, chunk):
    """(batch, groups, chunks, blocks of heads a group)."""
    return (x.shape[0], groups, x.shape[2] // chunk, dt.shape[1] // (groups * _HEADS))


def _specs(p, n, chunk, blocks, at):
    """``BlockSpec``s over a grid of (batch, group, chunk, block of heads), the
    chunk ``at(c)``: a block of heads' (heads P, chunk) of x-shaped arrays, a
    group's (N, chunk) of B and C, the block's (heads, chunk) rows of float32,
    and its (heads P, N) opening states."""
    heads, blocks = _HEADS, np.int32(blocks)
    return (pl.BlockSpec((1, heads * p, chunk), lambda b, g, c, s: (b, g * blocks + s, at(c))),
            pl.BlockSpec((1, n, chunk), lambda b, g, c, s: (b, g, at(c))),
            pl.BlockSpec((1, heads, chunk), lambda b, g, c, s: (b, g * blocks + s, at(c))),
            pl.BlockSpec((1, 1, heads * p, n),
                         lambda b, g, c, s: (b, at(c), g * blocks + s, np.int32(0))))


def _scalars(d):
    """A number a head, whole, in SMEM (an index map of its own: under
    jax_enable_x64 the default one counts in 64 bits)."""
    return pl.BlockSpec(d.shape, lambda b, g, c, s: (np.int32(0),), memory_space=pltpu.SMEM)


def _params(p, n, chunk, itemsize, blocks):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=int(_vmem(p, n, chunk, itemsize, blocks) * 5 // 4 + 2 ** 22))


@functools.partial(jax.jit, static_argnames=("groups", "chunk", "interpret"))
def _fwd_pallas(x, b, c, dt, cum, d, groups, chunk, interpret=False):
    """``y`` (B, H P, T) and the chunks' opening states (B, T / chunk, H P, N)
    float32 of ``x`` (B, H P, T), ``b`` / ``c`` (B, G N, T), float32 ``dt`` /
    ``cum`` (B, H, T) and ``d`` (H,), T whole chunks. A jitted function of its
    own: the call sites of one shape (every layer, the forward's second run)
    share one trace and one lowering."""
    batch, _, chunks, blocks = grid = _grid(x, dt, groups, chunk)
    p, n = x.shape[1] // dt.shape[1], b.shape[1] // groups
    wide, group, rows, states = _specs(p, n, chunk, blocks, lambda c: c)
    return pl.pallas_call(
        _fwd_kernel, grid=grid,
        in_specs=[_scalars(d), wide, group, group, rows, rows],
        out_specs=(wide, states),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((batch, chunks, x.shape[1], n), F32)),
        scratch_shapes=[pltpu.VMEM((blocks, _HEADS * p, n), F32), pltpu.VMEM((chunk, chunk), F32)],
        compiler_params=_params(p, n, chunk, x.dtype.itemsize, blocks),
        name="ssd_chunk_fwd", interpret=interpret)(d, x, b, c, dt, cum)


@functools.partial(jax.jit, static_argnames=("groups", "chunk", "interpret"))
def _bwd_pallas(x, dy, b, c, dt, cum, opening, d, groups, chunk, interpret=False):
    """Under ``dy`` (B, H P, T): the gradients of ``_fwd_pallas`` at ``x``, ``b``
    and ``c`` in their shapes and types and, float32 (B, H, T) rows, ``ddt``'s
    part through ``dt x``, ``dcum`` and ``D``'s gradient before its sum over
    the tokens."""
    batch, _, chunks, blocks = grid = _grid(x, dt, groups, chunk)
    p, n = x.shape[1] // dt.shape[1], b.shape[1] // groups
    end = np.int32(chunks - 1)
    wide, group, rows, states = _specs(p, n, chunk, blocks, lambda c: end - c)
    row = jax.ShapeDtypeStruct(dt.shape, F32)
    return pl.pallas_call(
        _bwd_kernel, grid=grid,
        in_specs=[_scalars(d), wide, wide, group, group, rows, rows, states],
        out_specs=(wide, group, group, rows, rows, rows),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype), row, row, row),
        scratch_shapes=[pltpu.VMEM((blocks, _HEADS * p, n), F32)] * 2
        + [pltpu.VMEM((chunk, chunk), F32)] * 2 + [pltpu.VMEM((2, n, chunk), F32)],
        compiler_params=_params(p, n, chunk, x.dtype.itemsize, blocks),
        name="ssd_chunk_bwd", interpret=interpret)(d, x, dy, b, c, dt, cum, opening)


def kernel_takes(x_shape, b_shape, chunk, dtype):
    """Whether ``ssd_scan`` of ``x`` (B, T, H, P) with ``B`` / ``C`` (B, T, G,
    N) of ``dtype`` in chunks of ``chunk`` is the kernels': everything the call
    can see."""
    dtype = jnp.dtype(dtype)
    if not on_tpu() or dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(F32)):
        return False
    (batch, t, h, p), (groups, n) = x_shape, b_shape[2:]
    if batch == 0 or t == 0 or chunk % _LANES or n % _LANES \
            or p % _chip.SUBLANES[dtype.itemsize] or h % (groups * _HEADS):
        return False
    return _vmem(p, n, chunk, dtype.itemsize, h // (groups * _HEADS)) <= _chip.VMEM_CEILING


def _turned(z, chunk):
    """(B, T, ...) -> (B, ..., T) with the trailing axes as one and T padded to
    whole chunks: tokens along the lanes."""
    z = jnp.swapaxes(z.reshape(z.shape[:2] + (-1,)), 1, 2)
    return jnp.pad(z, ((0, 0), (0, 0), (0, -z.shape[2] % chunk)))


def _back(z, shape):
    """``_turned``'s inverse: (B, ..., T padded) -> ``shape`` (B, T, ...)."""
    return jnp.swapaxes(z[:, :, :shape[1]], 1, 2).reshape(shape)


def _decays(dt, A_log, dt_bias, chunk):
    """float32, tokens along the lanes: ``dt`` after its bias and softplus (B,
    H, T padded with ``dt = 0``), the running sum of ``dt A`` inside each
    chunk, and ``A`` (H,)."""
    dt = _turned(jax.nn.softplus(dt.astype(F32) + dt_bias.astype(F32)), chunk)
    a = -jnp.exp(A_log.astype(F32))
    by_chunk = (dt * a[:, None]).reshape(dt.shape[:2] + (-1, chunk))
    return dt, jnp.cumsum(by_chunk, axis=3).reshape(dt.shape), a


def scan(chunk, x, dt, A_log, B, C, D, dt_bias, interpret=False):
    """``ssd_scan``'s result and the chunks' opening states (B, chunks, G, H /
    G, P, N) float32 by ``ssd_chunk_fwd``, for a call ``kernel_takes``
    accepts."""
    (batch, _, h, p), (groups, n) = x.shape, B.shape[2:]
    dts, cum, _ = _decays(dt, A_log, dt_bias, chunk)
    y, opening = _fwd_pallas(_turned(x, chunk), _turned(B, chunk), _turned(C, chunk), dts, cum,
                             D.astype(F32), groups=groups, chunk=chunk, interpret=interpret)
    return _back(y, x.shape), opening.reshape(batch, -1, groups, h // groups, p, n)


def scan_grads(chunk, x, dt, A_log, B, C, D, dt_bias, opening, dy, interpret=False):
    """The gradients of ``ssd_scan`` at its seven inputs under ``dy``, by
    ``ssd_chunk_bwd`` and, on the (B, H, T) rows it returns, XLA: the running
    sums' reverse walk, ``ddt``'s second part and softplus' derivative, the
    per-head sums."""
    h, p = x.shape[2:]
    dts, cum, a = _decays(dt, A_log, dt_bias, chunk)
    dx, dB, dC, ddt, dcum, dD = _bwd_pallas(
        _turned(x, chunk), _turned(dy, chunk), _turned(B, chunk), _turned(C, chunk), dts, cum,
        opening.reshape(opening.shape[:2] + (h * p, -1)), D.astype(F32), groups=B.shape[2],
        chunk=chunk, interpret=interpret)
    by_chunk = dcum.reshape(dcum.shape[:2] + (-1, chunk))
    da = jnp.flip(jnp.cumsum(jnp.flip(by_chunk, 3), axis=3), 3).reshape(dcum.shape)
    ddt = _back(ddt + a[:, None] * da, dt.shape) * jax.nn.sigmoid(
        dt.astype(F32) + dt_bias.astype(F32))  # softplus' derivative
    return (_back(dx, x.shape), ddt.astype(dt.dtype),
            (jnp.sum(dts * da, axis=(0, 2)) * a).astype(A_log.dtype),
            _back(dB, B.shape), _back(dC, C.shape), jnp.sum(dD, axis=(0, 2)).astype(D.dtype),
            jnp.sum(ddt, axis=(0, 1)).astype(dt_bias.dtype))
