"""The expert layer's sum of rows back to their tokens as the program's own
kernel, which moves only the rows that exist (``ops/moe.py``: ``_sum_rows``,
the forward's ``combine`` and the backward of ``dispatch``).

``sum_rows(src, slot, held, tokens, k)``: ``src`` (C, H) holds a row for each
of the first ``held`` positions (``held`` is read on the device), row c being
entry ``slot[c] = m * k + j`` of token m; the result (tokens, H) sums every
token's rows in float32, in the order of j, rounded once. A token without a
row is zeros. XLA's form of it gathers a row for EVERY entry (``tokens * k``
of them, a zero row for those that do not exist), writes each column's
gather out and reads them all back to add them: three passes over ``tokens *
k`` rows where ``held`` exist, an eighth to a quarter of them.

Where the call can see a TPU, bfloat16 or float32 rows of whole 128-lane
lengths (twice that for bfloat16), whole tiles of 128 tokens, at most 16
columns and buffers that fit the chip's VMEM (``kernel_takes``), it is two
Pallas kernels:

* ``rows_as_words`` lays the first ``held`` rows out so that a row is the unit
  of a DMA: (C, L, 128) uint32, every row whole tiles of its own (L: its H /
  256 lane-lengths rounded up to 8), column c in the low half of a word and
  column c + H / 2 in the high half. (A
  bfloat16 row of a (C, H) array shares its (16, 128) tiles with fifteen
  others, and the compiler refuses a slice inside a tile: TPU v5e, jaxlib
  0.9.0.) float32 rows are laid out by a reshape.
* ``row_gather``: a grid over tiles of 128 tokens. The rows that exist are
  listed in the order of their tokens (one sort of the C slots, on the
  device), so a step walks its own stretch of the list and nothing else: one
  DMA a row, HBM to a VMEM buffer of (k, 128) row tiles, the next step's
  started before this step's are waited for. The buffer is zero wherever
  nothing was fetched (a step clears what it used), so the sum needs no mask:
  k dense float32 adds over the tile, rounded once and stored as the (128, H)
  block it is. A tile whose tokens hold no row stores zeros and fetches
  nothing.

Every other call (the CPU, odd widths, the one-column sum of the slots'
weights) is XLA's gather in ``ops/moe.py``, letter for letter. Which a traced
movement takes is counted (``telemetry.row_movement_branches()``).
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
BF16 = jnp.bfloat16
U32 = jnp.uint32
ROWS = 128  # tokens a grid step of ``row_gather``
_PACK_ROWS = 256  # rows a grid step of ``rows_as_words``
_MAX_COLUMNS = 16  # a listed row's column takes 4 bits, its token in the tile 7
_Z = np.int32(0)  # in an index map: under jax_enable_x64 a literal 0 is 64 bits wide


def _whole_tiles(lengths):
    """Lane-lengths of a row rounded up to whole sublane tiles of 8: a row of
    10 lengths in tiles it only part fills was laid out 40 times slower (my
    chip run, PR 42)."""
    tile = _chip.SUBLANES[4]
    return -(-lengths // tile) * tile


def _pack(x):
    """bfloat16 (R, H) -> uint32 (R, H / 2): column c in the low half of word
    c, column c + H / 2 in the high half."""
    half = x.shape[1] // 2
    bits = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(U32)
    return bits[:, :half] | (bits[:, half:] << 16)


def _halves(words):
    """uint32 words -> the float32 values of their low and high bfloat16."""
    as_f32 = functools.partial(jax.lax.bitcast_convert_type, new_dtype=F32)
    return as_f32(words << 16), as_f32(words & np.uint32(0xFFFF0000))


def _words_kernel(held, x_ref, o_ref):
    @pl.when(pl.program_id(0) * np.int32(_PACK_ROWS) < held[0])
    def _rows():
        words = _pack(x_ref[...])
        for c in range(words.shape[1] // 128):  # lane-length c of every row to sublane c of its tile
            o_ref[:, c, :] = words[:, c * 128:(c + 1) * 128]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _words_pallas(src, held, interpret=False):
    """bfloat16 (C, H) -> uint32 (C, L, 128): ``_pack`` of the first ``held``
    rows, in whole steps, lane-length c of a row at [c]; L is H / 256 rounded
    up to whole sublane tiles of 8. What lies past the rows held, and in a
    row's last L - H / 256 lengths, is not written."""
    rows, width = src.shape
    lengths = _whole_tiles(width // 256)
    held = jnp.reshape(held, (1,)).astype(jnp.int32)

    def step(i, held):  # a step past the rows held repeats the last one's blocks
        steps = jax.lax.div(held[0] + np.int32(_PACK_ROWS - 1), np.int32(_PACK_ROWS))
        return jnp.minimum(i, jnp.maximum(steps - 1, 0))

    return pl.pallas_call(
        _words_kernel, out_shape=jax.ShapeDtypeStruct((rows, lengths, 128), U32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(rows // _PACK_ROWS,),
            in_specs=[pl.BlockSpec((_PACK_ROWS, width), lambda i, held: (step(i, held), _Z))],
            out_specs=pl.BlockSpec((_PACK_ROWS, lengths, 128),
                                   lambda i, held: (step(i, held), _Z, _Z))),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name="rows_as_words", interpret=interpret,
    )(held, src)


def _listed(slot, held, tokens, k):
    """The rows that exist in the order of their tokens, int32 (C,): row c of
    token-in-tile r and column j as ``c << 11 | r << 4 | j`` (past ``held``:
    anything); and where each tile's stretch of that list starts, int32
    (tiles + 1,)."""
    rows = slot.shape[0]
    at = jnp.arange(rows, dtype=jnp.int32)
    entry = jnp.where(at < held, slot.astype(jnp.int32), np.int32(tokens * k))
    by_token, order = jax.lax.sort((entry, at), num_keys=1)
    token, column = by_token // k, by_token % k
    listed = (order << 11) | ((token % ROWS) << 4) | column
    edges = jnp.arange(tokens // ROWS + 1, dtype=jnp.int32) * (ROWS * k)
    # the rows before each tile's first entry, counted: one pass, no search
    return listed, jnp.sum(entry[None, :] < edges[:, None], axis=1, dtype=jnp.int32)


def _gather_kernel(listed, starts, src, o_ref, buf, sem, *, k, packed):
    i, tiles = pl.program_id(0), pl.num_programs(0)

    def row_copy(tile, t):
        """The DMA of listed row ``t``, a row of ``tile``."""
        row, at = listed[t], jax.lax.rem(tile, np.int32(2))
        token = jax.lax.shift_right_logical(row, np.int32(4)) & np.int32(ROWS - 1)
        return pltpu.make_async_copy(src.at[jax.lax.shift_right_logical(row, np.int32(11))],
                                     buf.at[at, row & np.int32(15), token], sem.at[at])

    def fetch(tile):
        jax.lax.fori_loop(starts[tile], starts[tile + 1],
                          lambda t, _: row_copy(tile, t).start(), None)

    @pl.when(i == 0)
    def _first():
        buf[...] = jnp.zeros_like(buf)
        fetch(i)

    @pl.when(i + 1 < tiles)
    def _ahead():
        fetch(i + 1)

    held = starts[i + 1] - starts[i]

    @pl.when(held == 0)
    def _empty():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(held > 0)
    def _sum():
        at = jax.lax.rem(i, np.int32(2))
        # one wait a row fetched: every row is as many bytes
        one = pltpu.make_async_copy(src.at[_Z], buf.at[at, _Z, _Z], sem.at[at])
        jax.lax.fori_loop(starts[i], starts[i + 1], lambda t, _: one.wait(), None)
        parts = _halves(buf[at, 0]) if packed else (buf[at, 0],)
        for j in range(1, k):
            more = _halves(buf[at, j]) if packed else (buf[at, j],)
            parts = tuple(a + b for a, b in zip(parts, more))
        lengths = o_ref.shape[1] // (128 * len(parts))  # those that hold the row's words
        for h, part in enumerate(parts):  # sublane c of every row's tile back to lane-length c
            for c in range(lengths):
                o_ref[:, (h * lengths + c) * 128:(h * lengths + c + 1) * 128] = \
                    part[:, c, :].astype(o_ref.dtype)
        # what the next tile in this half does not fetch must read zero
        buf[at] = jnp.zeros(buf.shape[1:], buf.dtype)


def _vmem(k, width, itemsize):
    """Bytes ``row_gather`` holds: two tiles' rows as fetched (a row's
    lane-lengths padded to whole sublane tiles of 8), the result block twice,
    the float32 sum and one column beside it."""
    row = _whole_tiles(width * itemsize // 4 // 128) * 128 * 4
    return 2 * k * ROWS * row + 2 * ROWS * width * itemsize + 4 * ROWS * row * (4 // itemsize)


@functools.partial(jax.jit, static_argnames=("tokens", "k", "interpret"))
def _sum_pallas(src, slot, held, tokens, k, interpret=False):
    """``sum_rows`` by the kernels. A jitted function of its own: the call
    sites of one shape (every layer, both passes) share one trace and one
    lowering."""
    rows, width = src.shape
    packed = src.dtype == BF16
    held = jnp.minimum(held.astype(jnp.int32), rows)
    if packed:
        words = _words_pallas(src, held, interpret=interpret)
    else:
        words = src.reshape(rows, width // 128, 128)
        words = jnp.pad(words, ((0, 0), (0, _whole_tiles(width // 128) - width // 128), (0, 0)))
    listed, starts = _listed(slot, held, tokens, k)
    return pl.pallas_call(
        functools.partial(_gather_kernel, k=k, packed=packed),
        out_shape=jax.ShapeDtypeStruct((tokens, width), src.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(tokens // ROWS,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((ROWS, width), lambda i, *_: (i, _Z)),
            scratch_shapes=[pltpu.VMEM((2, k, ROWS) + words.shape[1:], words.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=int(_vmem(k, width, src.dtype.itemsize) * 5 // 4 + 2 ** 21)),
        name="row_gather", interpret=interpret,
    )(listed, starts, words)


def kernel_takes(rows, tokens, k, width, dtype):
    """Whether the sum of ``rows`` rows of ``width`` back to (tokens, k)
    entries is the kernels': everything the call can see."""
    dtype = jnp.dtype(dtype)
    if not on_tpu() or dtype not in (jnp.dtype(BF16), jnp.dtype(F32)):
        return False
    lanes = _chip.LANES * 4 // dtype.itemsize  # a row is whole lane-lengths of 32-bit words
    if tokens == 0 or tokens % ROWS or rows == 0 or rows % _PACK_ROWS or width % lanes:
        return False
    if k > _MAX_COLUMNS or rows >= 2 ** 20:
        return False
    return _vmem(k, width, dtype.itemsize) <= _chip.VMEM_CEILING


def sum_rows(src, slot, held, tokens, k):
    """The kernels' sum (the module's docstring) for a call ``kernel_takes``
    accepts: (C, H) rows, int32 (C,) entries ``m * k + j``, int32 () rows that
    exist -> (tokens, H)."""
    return _sum_pallas(src, slot, held, tokens=tokens, k=k)
