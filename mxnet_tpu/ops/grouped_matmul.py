"""Grouped matmuls over rows sorted by group, as the program's own kernels
(Gale et al. 2022, MegaBlocks: a grid over the row tiles that hold rows).

``grouped_matmul(x, w, sizes)`` is ``jax.lax.ragged_dot``: ``x`` (C, K) holds
the rows of group 0, then group 1, ... (``sizes`` (G,) int32 says how many
each; rows past their sum belong to no group), ``w`` (G, K, N), and row r of
the result (C, N) is ``x[r] @ w[group of r]``, zero past ``sum(sizes)``. Its
gradient is two more grouped products, joined by one ``custom_vjp``:
``dx[r] = dy[r] @ w[group of r]^T`` and ``dw[g] = x[rows of g]^T @ dy[rows of
g]`` (zero for an empty group).

Where the call can see a TPU, bfloat16 or float32 operands of one type, K and
N whole 128-lane lengths, C a multiple of 512 and blocks that fit the chip's
VMEM (``_kernel_takes``), the three products are two Pallas kernels:

* ``grouped_matmul`` (the forward, and ``dx`` with the weight block read
  transposed): a one-dimensional grid over VISITS, one visit a (row tile,
  group) pair that shares a row, in order of group. How many there are is
  read on the device from ``sizes`` (``_plan``: scalars prefetched to SMEM);
  a step past the last visit either stores zeros over a tile past
  ``sum(sizes)`` or skips its body, and fetches nothing (its block indices
  repeat). A group's (K, N) weight block is one block, fetched when the group
  changes and held across its row tiles; a row tile two groups share is
  fetched once and visited once for each, the other's rows kept at the
  store. Inside a visit only the 128-row pieces that hold a row of the group
  are multiplied, each accumulated in float32 over the whole contraction and
  rounded once.
* ``grouped_matmul_dw``: the same walk (an empty group gets one visit, to
  store its zeros), a float32 (K, N) accumulator in VMEM scratch zeroed at a
  group's first visit, the rows of other groups zeroed in both operands, one
  rounding at the group's last visit.

Ms a call, bfloat16, TPU v5e (my chip run, PR 40: 20 calls in flight
by the host's clock, the walk's small XLA operations included; 16 groups that
hold about half the rows laid out), forward x @ w[g] of (C, 2048) x (16, 2048,
W) by rows a visit / rows a piece:
  C 12288, W 768: ``ragged_dot`` 0.498 (0.490 with C cut to the rows held:
  the compiler's kernel does not pay for rows laid out); 128 / 128 0.305,
  256 / 256 0.283, 512 / 512 0.331, 256 / 128 0.277, 512 / 128 0.268.
  C 16384, W 1536: ``ragged_dot`` 0.750; 128 / 128 0.576, 256 / 256 0.565,
  512 / 512 0.666, 256 / 128 0.540, 512 / 128 0.517.
  The weight gradient at 128 / 256 / 512 rows a visit: 0.328 / 0.319 / 0.357
  (``ragged_dot`` 0.586) and 0.612 / 0.611 / 0.705 (0.914).
Fetching a group's weight block a whole group ahead by the kernel's own DMA
(the pipeline's prefetch has one visit to hide 3-6 MB behind) read 0.246 and
0.483 at 512 / 128: 7-8 % of a call, 0.3-0.5 % of a step, not kept.

Every other call (the CPU, odd widths) is ``jax.lax.ragged_dot``, letter for
letter. Which a traced call takes is counted
(``telemetry.grouped_matmul_branches()``: ``{product: {branch: traces}}``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry as _telemetry
from ..context import on_tpu
from . import chip as _chip

F32 = jnp.float32
ROW_TILE = 512  # a call's rows are whole tiles of this many (ops/moe.py's bound)
_PIECE = 128  # rows a product inside a visit of ``grouped_matmul``
_DW_ROWS = 256  # rows a visit of ``grouped_matmul_dw``

_Z = np.int32(0)  # in an index map: under jax_enable_x64 a literal 0 is 64 bits wide
_NN = (((1,), (0,)), ((), ()))  # (m, k) x (k, n) -> (m, n)
_NT = (((1,), (1,)), ((), ()))  # (m, k) x (n, k) -> (m, n)
_TN = (((0,), (0,)), ((), ()))  # (r, k) x (r, n) -> (k, n)


def _plan(sizes, rows, tm, empty_visits):
    """The walk of a grid over ``steps = rows // tm + G - 1`` steps, from
    ``sizes`` on the device. Returns int32 arrays for SMEM: ``group`` and
    ``tile`` (steps,), what each step visits; ``starts`` (G + 1,), the first
    row of every group and one past the last; ``counts`` (2,): the visits
    (the steps that multiply) and, for ``grouped_matmul`` (``empty_visits``
    0), the steps that store: the visits and then the tiles that lie wholly
    past ``sum(sizes)``. Steps past a count repeat the last indices, so
    nothing is fetched for them. ``empty_visits`` 1 gives a group without
    rows one visit (the weight gradient stores its zeros there)."""
    groups = sizes.shape[0]
    tiles = rows // tm
    steps = tiles + groups - 1
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes, dtype=jnp.int32)
    starts = ends - sizes
    first = starts // tm
    visits = jnp.where(sizes > 0, (ends + tm - 1) // tm - first, empty_visits)
    cum = jnp.cumsum(visits, dtype=jnp.int32)
    n_visits = cum[-1]
    s = jnp.arange(steps, dtype=jnp.int32)
    at = jnp.minimum(s, jnp.maximum(n_visits - 1, 0))
    group = jnp.sum(cum[None, :] <= at[:, None], axis=1, dtype=jnp.int32)
    group = jnp.minimum(group, groups - 1)
    tile = first[group] + at - (cum[group] - visits[group])
    held_tiles = (ends[-1] + tm - 1) // tm
    tile = jnp.where(s < n_visits, tile, held_tiles + s - n_visits)
    tile = jnp.clip(tile, 0, tiles - 1)
    counts = jnp.stack([n_visits, n_visits + tiles - held_tiles])
    return group, tile, jnp.concatenate([starts[:1], ends]), counts


def _owned(starts, g, row0, rows):
    """Which of ``rows`` rows from ``row0`` on are group ``g``'s, (rows, 1)."""
    at = row0 + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    return jnp.logical_and(at >= starts[g], at < starts[g + 1])


def _gmm_kernel(group, tile, starts, counts, x_ref, w_ref, o_ref, *, tm,
                transpose_rhs):
    s = pl.program_id(0)
    g, row0 = group[s], tile[s] * tm

    def piece(j, _):
        """Rows j * 128 ... of the tile through the group's weights, the
        other groups' rows kept."""
        r = pl.multiple_of(j * _PIECE, _PIECE)
        at = (pl.ds(r, _PIECE), slice(None))
        acc = jax.lax.dot_general(x_ref[at], w_ref[...], _NT if transpose_rhs else _NN,
                                  preferred_element_type=F32)
        own = _owned(starts, g, row0 + r, _PIECE)
        o_ref[at] = jnp.where(own, acc, o_ref[at].astype(F32)).astype(o_ref.dtype)

    @pl.when(s < counts[0])
    def _visit():
        # the tile's first visit: what no group owns reads zero
        @pl.when(jnp.logical_or(s == 0, tile[jnp.maximum(s - 1, 0)] != tile[s]))
        def _zero():
            o_ref[...] = jnp.zeros_like(o_ref)

        # only the pieces that hold a row of the group
        lo = jax.lax.div(jnp.maximum(starts[g] - row0, 0), np.int32(_PIECE))
        hi = jax.lax.div(jnp.minimum(starts[g + 1] - row0, tm) + (_PIECE - 1),
                         np.int32(_PIECE))
        jax.lax.fori_loop(lo, hi, piece, None)

    @pl.when(jnp.logical_and(s >= counts[0], s < counts[1]))
    def _tail():
        o_ref[...] = jnp.zeros_like(o_ref)


def _tgmm_kernel(group, tile, starts, counts, x_ref, dy_ref, o_ref, acc_ref, *, tm):
    s = pl.program_id(0)
    g, row0 = group[s], tile[s] * tm
    visit = s < counts[0]

    @pl.when(jnp.logical_and(visit, jnp.logical_or(
        s == 0, group[jnp.maximum(s - 1, 0)] != g)))
    def _first():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_and(visit, starts[g + 1] > starts[g]))
    def _rows():
        # both operands: what lies in a row no group holds may be anything
        own = _owned(starts, g, row0, tm)
        x = jnp.where(own, x_ref[...], jnp.zeros((), x_ref.dtype))
        dy = jnp.where(own, dy_ref[...], jnp.zeros((), dy_ref.dtype))
        acc_ref[...] += jax.lax.dot_general(x, dy, _TN, preferred_element_type=F32)

    @pl.when(jnp.logical_and(visit, jnp.logical_or(
        s == counts[0] - 1, group[jnp.minimum(s + 1, pl.num_programs(0) - 1)] != g)))
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _gmm_vmem(tm, k, n, itemsize):
    """Bytes the ``grouped_matmul`` kernel holds: operand, weight and result
    blocks twice (the pipeline's), a piece's float32 product and what it
    keeps of the tile."""
    return 2 * itemsize * (tm * k + k * n + tm * n) + 3 * 4 * _PIECE * n


def _tgmm_vmem(tm, k, n, itemsize):
    """Bytes the ``grouped_matmul_dw`` kernel holds: both operand tiles and
    the result block twice, the float32 accumulator and one product beside
    it, the masked operands."""
    return 2 * itemsize * (tm * (k + n) + k * n) + 2 * 4 * k * n + itemsize * tm * (k + n)


def _params(held):
    return pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                vmem_limit_bytes=int(held + held // 4 + 2 ** 21))


@functools.partial(jax.jit, static_argnames=("transpose_rhs", "tm", "interpret"))
def _gmm_pallas(x, w, sizes, transpose_rhs=False, tm=ROW_TILE, interpret=False):
    """``x[rows of g] @ w[g]`` (``w[g]^T`` with ``transpose_rhs``) -> (C, N).
    A jitted function of its own: the call sites of one shape (gate and up,
    every layer) share one trace and one lowering."""
    rows, k = x.shape
    n = w.shape[1] if transpose_rhs else w.shape[2]
    plan = _plan(sizes, rows, tm, 0)
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(plan[0].shape[0],),
            in_specs=[
                pl.BlockSpec((tm, k), lambda s, group, tile, *_: (tile[s], _Z)),
                pl.BlockSpec((None,) + w.shape[1:],
                             lambda s, group, *_: (group[s], _Z, _Z))],
            out_specs=pl.BlockSpec((tm, n), lambda s, group, tile, *_: (tile[s], _Z))),
        compiler_params=_params(_gmm_vmem(tm, k, n, x.dtype.itemsize)),
        name="grouped_matmul", interpret=interpret,
    )(*plan, x, w)


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def _tgmm_pallas(x, dy, sizes, tm=_DW_ROWS, interpret=False):
    """``x[rows of g]^T @ dy[rows of g]`` -> (G, K, N); jitted as ``_gmm_pallas``."""
    rows, k = x.shape
    n = dy.shape[1]
    plan = _plan(sizes, rows, tm, 1)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((sizes.shape[0], k, n), x.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(plan[0].shape[0],),
            in_specs=[
                pl.BlockSpec((tm, k), lambda s, group, tile, *_: (tile[s], _Z)),
                pl.BlockSpec((tm, n), lambda s, group, tile, *_: (tile[s], _Z))],
            out_specs=pl.BlockSpec((None, k, n), lambda s, group, *_: (group[s], _Z, _Z)),
            scratch_shapes=[pltpu.VMEM((k, n), F32)]),
        compiler_params=_params(_tgmm_vmem(tm, k, n, x.dtype.itemsize)),
        name="grouped_matmul_dw", interpret=interpret,
    )(*plan, x, dy)


def _kernel_takes(product, rows, k, n, dtype):
    """Whether ``product`` (``fwd``, ``dx``, ``dw``) of a call whose forward
    is (rows, k) x (G, k, n) is the kernel's: everything the call can see."""
    dtype = jnp.dtype(dtype)
    if not on_tpu() or dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(F32)):
        return False
    if rows == 0 or rows % ROW_TILE or k % _chip.LANES or n % _chip.LANES:
        return False
    size = dtype.itemsize
    held = {"fwd": _gmm_vmem(ROW_TILE, k, n, size), "dx": _gmm_vmem(ROW_TILE, n, k, size),
            "dw": _tgmm_vmem(_DW_ROWS, k, n, size)}[product]
    return held <= _chip.VMEM_CEILING


def _kernel(product, x, w, dy, sizes, **kw):
    """``product`` of the forward's operands ``x``, ``w`` and the result's
    cotangent ``dy`` by the kernels (what a product does not read may be
    None)."""
    if product == "fwd":
        return _gmm_pallas(x, w, sizes, **kw)
    if product == "dx":
        return _gmm_pallas(dy, w, sizes, transpose_rhs=True, **kw)
    return _tgmm_pallas(x, dy, sizes, **kw)


def _ragged(product, x, w, dy, sizes):
    """The same product by ``jax.lax.ragged_dot`` and its own derivative."""
    if product == "fwd":
        return jax.lax.ragged_dot(x, w, sizes)
    vjp = jax.vjp(lambda x, w: jax.lax.ragged_dot(x, w, sizes), x, w)[1]
    return vjp(dy)[0 if product == "dx" else 1]


def _product(product, x, w, dy, sizes):
    """One of the three products by the branch the rule gives it, counted."""
    kernel = _kernel_takes(product, x.shape[0], *w.shape[1:], x.dtype)
    _telemetry.record_grouped_matmul(product, "kernel" if kernel else "ragged_dot")
    return (_kernel if kernel else _ragged)(product, x, w, dy, sizes)


@jax.custom_vjp
def _grouped(x, w, sizes):
    return _product("fwd", x, w, None, sizes)


def _grouped_fwd(x, w, sizes):
    return _grouped(x, w, sizes), (x, w, sizes)


def _grouped_bwd(res, dy):
    x, w, sizes = res
    with jax.named_scope("grouped_matmul_bwd"):
        return (_product("dx", x, w, dy, sizes), _product("dw", x, w, dy, sizes), None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_matmul(x, w, sizes):
    """``jax.lax.ragged_dot(x, w, sizes)``: (C, K) rows sorted by group, (G,
    K, N) weights, (G,) int32 rows a group -> (C, N), zero past
    ``sum(sizes)``; differentiable in ``x`` and ``w``. The kernels where the
    rule engages (the module's docstring), else ``ragged_dot`` itself with
    its own derivative."""
    rows, k = x.shape
    if x.dtype != w.dtype or not any(
            _kernel_takes(p, rows, k, w.shape[2], x.dtype) for p in ("fwd", "dx", "dw")):
        _telemetry.record_grouped_matmul("fwd", "ragged_dot")
        return jax.lax.ragged_dot(x, w, sizes)
    return _grouped(x, w, sizes)
