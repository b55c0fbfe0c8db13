"""Mamba-2's causal filter under SiLU (``ops/gated_conv.py``:
``causal_conv_silu``) as the program's own kernels, which hold a tile of tokens
with its halo in VMEM so that every operand moves once.

XLA's form of the op builds each of the ``K - 1`` shifted operands as a float32
copy: a shift of one to three tokens does not align with a tile, so
``jnp.pad(z[:, :T - s])`` is written out and read again, in the forward three
times over and in the backward nine.

**Tokens lie along the lanes.** The kernels take ``data`` turned, (B, C, T):
that is the layout XLA itself gives a Mamba-2 mixer (``in_proj``'s 8512 columns
are no whole lane-lengths and 8192 tokens are, and the scan's products want a
head's tokens contiguous), so ``swapaxes`` around the calls is a choice of
layout for the projection's result and not a copy. Kernels on (B, T, C) rows
took the filter from 50.1 ms a step to 8.2 and cost the scan and the gated
norm 52 ms of transposes (my chip runs, PR 44: PERF.md, Findings).

Where the call can see a TPU, bfloat16 or float32 ``data`` with C (and the
first column filtered) whole tiles of 16 channels, T whole tiles of 512 tokens
and no more than ``_MAX_TAPS`` taps (``kernel_takes``), both halves are one
``pallas_call`` each over a grid of (channel tiles, batch, token tiles):

* ``causal_conv_silu_fwd`` reads a (channels, tokens) tile of ``data``
  (``_tiles``: 256 x 8192 at the Granite cell's shape) and, by a second
  ``BlockSpec`` on the same operand, the 128 tokens before it (zeros where the
  tile opens a sequence); takes the tile a chunk of channels at a time and
  ``_WALK`` tokens at a time in float32, the shifted operands made in
  registers (``_delayed``: a rotation along the lanes of the chunk beside the
  128 tokens before it), and writes ``silu(V + bias)`` once in ``data``'s
  type.
* ``causal_conv_silu_bwd`` reads the same tile and its 128 tokens before, the
  128 after it, and the result's gradient on the tile and the 128 after (zeros
  past the sequence's end); recomputes ``V``, keeps ``dV`` of a chunk of
  channels on the tile and the 128 tokens after it in VMEM, writes ``d data``
  from it once (``_advanced``: the filter's transpose) and adds the taps' and
  the bias's gradients into a float32 (K + 1, channels, 128) scratch that stays
  in VMEM across a channel tile's batch and token steps and is summed over its
  lanes once, a lane a tap.

A halo block and not a carried scratch: the backward needs tokens AFTER the
tile too, which no carry brings, and with blocks alone no step depends on
another (the token axis is sequential only for the backward's sums). The
chunks of channels are counted in a loop and a chunk's tokens are written out:
a rotation along the lanes is slow to arrive, and a loop's turn waits for it
where written-out steps overlap (some 100 cycles a turn: ``_WALK``). Every
other call is the ``jax.numpy`` formula in ``ops/gated_conv.py``, letter for
letter. Which a traced call takes is counted
(``telemetry.causal_conv_branches()``).

``_delayed`` / ``_advanced`` / ``_filter`` are the tile-and-halo part on values
in VMEM, for ``gated_short_conv``'s kernel to call between its two gates
(ROADMAP S18).
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
TOKENS = (8192, 4096, 2048, 1024, 512)  # tokens a grid step holds: the first that divides T
_CHANNELS = (256, 128, 64, 32, 16)  # channels a grid step holds: the first that divides C
# (channels, tokens) a step takes at a time: the channels counted in a loop, the
# tokens written out. Stand-alone at (1, 4352, 8192) x 4 taps, ms a call forward
# / backward (my chip runs, PR 44): (16, 2048) 0.261 / 0.570, (16, 1024) 0.257 /
# 0.574, (32, 1024) 0.252 / 0.578, (32, 512) 0.249 / 0.600, (32, 256) 0.247 /
# 0.628; the fewer steps are written out the less a start pays to trace them
# (8 steps of 1024: 4 s on the chip's host). A LOOP along the tokens costs some
# 100 cycles a turn that nothing hides (a rotation along the lanes is slow to
# arrive): (16, 512) 0.735 / 1.17 looped and 0.330 / 0.74 written out.
_WALK = (16, 2048)
_LANES = _chip.LANES  # a tile's lanes: a halo block's tokens, and what a rotation turns
_MAX_TAPS = 16  # the taps are written out, a rotation each
_Z = np.int32(0)  # in an index map: under jax_enable_x64 a literal 0 is 64 bits wide


def _groups(z):
    return [z[:, i:i + _LANES] for i in range(0, z.shape[1], _LANES)]


def _delayed(before, z, s):
    """``z`` (channels, tokens) float32 moved ``s`` tokens later, its first
    ``s`` the last ``s`` of ``before`` (channels, 128): one rotation along the
    lanes of both side by side (the compiler turns each group of 128 and takes
    its first ``s`` from the group before)."""
    if s == 0:
        return z
    return pltpu.roll(jnp.concatenate([before, z], axis=1), np.int32(s), 1)[:, _LANES:]


def _advanced(z, after, s):
    """``z`` moved ``s`` tokens earlier, its last ``s`` the first ``s`` of
    ``after`` (channels, 128): ``_delayed``'s transpose."""
    if s == 0:
        return z
    both = jnp.concatenate([z, after], axis=1)
    return pltpu.roll(both, np.int32(both.shape[1] - s), 1)[:, :z.shape[1]]


def _filter(taps, moved):
    """``sum_j taps[j] * moved[K - 1 - j]``: the filter of the module's
    docstring from ``moved[s]``, the operand ``s`` tokens later (with
    ``_advanced`` operands its transpose). ``taps[j]`` is (channels, 128), a
    channel's tap in every lane."""
    k = len(moved)
    v = _along(taps[k - 1], moved[0]) * moved[0]
    for s in range(1, k):
        v = v + _along(taps[k - 1 - s], moved[s]) * moved[s]
    return v


def _along(w, z):
    """``w`` (channels, 128) side by side over every group of 128 lanes of
    ``z``: the same registers again, nothing moved."""
    return jnp.concatenate([w] * (z.shape[1] // _LANES), axis=1)


def _taps_of(ref, at):
    """A chunk's taps and, after them, its bias, (channels, 128) float32 each: a
    channel's number in every lane, spread once a chunk and not where it is
    used."""
    columns = [ref[at, j:j + 1] for j in range(ref.shape[1])]
    spread = [jnp.broadcast_to(w, (w.shape[0], _LANES)) for w in columns]
    return spread[:-1], spread[-1]


def _chunks(ref, walk):
    """``at(r)`` / ``along(i)``: the channels of chunk ``r`` (counted in a loop)
    and the tokens of chunk ``i`` (written out: see ``_WALK``) of a (1,
    channels, tokens) block, and how many there are of each."""
    rows, lanes = walk

    def at(r):
        return pl.ds(pl.multiple_of(r * np.int32(rows), rows), rows)

    def along(i):
        return pl.ds(i * lanes, lanes)

    # an i32 bound: under jax_enable_x64 fori_loop's static form counts in i64
    return at, along, jnp.int32(ref.shape[1] // rows), ref.shape[2] // lanes


def _fwd_kernel(x_ref, before_ref, taps_ref, o_ref, *, k, walk):
    at, along, chunks, steps = _chunks(x_ref, walk)
    first = pl.program_id(2) == 0

    def chunk(r, _):
        taps, bias = _taps_of(taps_ref, at(r))
        before = before_ref[0, at(r), :].astype(F32)
        before = jnp.where(first, jnp.zeros_like(before), before)
        for i in range(steps):
            z = x_ref[0, at(r), along(i)].astype(F32)
            v = _filter(taps, [_delayed(before, z, s) for s in range(k)]) + _along(bias, z)
            o_ref[0, at(r), along(i)] = (v * jax.nn.sigmoid(v)).astype(o_ref.dtype)
            before = z[:, -_LANES:]

    jax.lax.fori_loop(jnp.int32(0), chunks, chunk, None)


def _bwd_kernel(x_ref, before_ref, after_ref, g_ref, g_after_ref, taps_ref,
                dx_ref, dtaps_ref, sums_ref, dv_ref, *, k, walk):
    at, along, chunks, steps = _chunks(x_ref, walk)
    first, last = pl.program_id(2) == 0, pl.program_id(2) + 1 == pl.num_programs(2)

    @pl.when(first & (pl.program_id(1) == 0))
    def _open():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    def fold(x):  # (channels, tokens) -> (channels, 128): the lanes are summed at the end
        return functools.reduce(jnp.add, _groups(x))

    def chunk(r, _):
        taps, bias = _taps_of(taps_ref, at(r))

        def dv_of(before, z, g):
            """``dV`` on the tokens of ``z``, and its sums with each shifted
            operand (the taps' gradients) and alone (the bias's)."""
            moved = [_delayed(before, z, s) for s in range(k)]
            v = _filter(taps, moved) + _along(bias, z)
            gate = jax.nn.sigmoid(v)
            dv = g * gate * (1.0 + v * (1.0 - gate))  # silu's derivative
            return dv, [fold(dv * moved[k - 1 - j]) for j in range(k)] + [fold(dv)]

        # dV on the tile and on the 128 tokens after it (their dV reaches back
        # into the tile's last K - 1), kept in VMEM: every chunk alone, so that
        # nothing waits for a rotation but the chunk it turns
        before = before_ref[0, at(r), :].astype(F32)
        before = jnp.where(first, jnp.zeros_like(before), before)
        sums = None
        for i in range(steps):
            z = x_ref[0, at(r), along(i)].astype(F32)
            dv, more = dv_of(before, z, g_ref[0, at(r), along(i)].astype(F32))
            dv_ref[:, along(i)] = dv
            sums = more if sums is None else [a + b for a, b in zip(sums, more)]
            before = z[:, -_LANES:]
        g_after = g_after_ref[0, at(r), :].astype(F32)
        g_after = jnp.where(last, jnp.zeros_like(g_after), g_after)
        dv_ref[:, pl.ds(steps * walk[1], _LANES)] = dv_of(
            before, after_ref[0, at(r), :].astype(F32), g_after)[0]
        for j, part in enumerate(sums):
            sums_ref[j, at(r), :] += part
        # d data: the filter's transpose over dV
        for i in range(steps):
            dv, after = dv_ref[:, along(i)], dv_ref[:, pl.ds((i + 1) * walk[1], _LANES)]
            dx_ref[0, at(r), along(i)] = _filter(
                taps, [_advanced(dv, after, s) for s in range(k)]).astype(dx_ref.dtype)

    jax.lax.fori_loop(jnp.int32(0), chunks, chunk, None)

    @pl.when(last & (pl.program_id(1) + 1 == pl.num_programs(1)))
    def _close():  # a lane a tap (the bias after them): the sums over the lanes, once
        lane = jax.lax.broadcasted_iota(jnp.int32, dtaps_ref.shape, 1)
        dtaps_ref[...] = functools.reduce(jnp.add, [
            jnp.where(lane == j, jnp.sum(sums_ref[j], axis=1, keepdims=True), 0.0)
            for j in range(k + 1)])


def _tiles(t, c, begin):
    """((channels, tokens) of a grid step, (channels, tokens) of its walk) at T
    tokens of C channels that start at channel ``begin`` of ``data``, None
    where they are no whole tiles."""
    tokens = next((n for n in TOKENS if t % n == 0), None)
    channels = next((n for n in _CHANNELS if c % n == 0 and begin % n == 0), None)
    rows, lanes = _WALK
    return tokens and channels and ((channels, tokens), (min(rows, channels), min(lanes, tokens)))


def _vmem(channels, tokens, k, itemsize):
    """Bytes the backward holds: three tiles and three halo blocks, each twice,
    the taps, their gradients' sums and a chunk's dV."""
    return (2 * 3 * (tokens + _LANES) * channels * itemsize
            + (k + 5) * channels * _LANES * 4 + _WALK[0] * (tokens + _LANES) * 4)


def _specs(t, channels, tokens, shift):
    """The ``BlockSpec``s of a tile, of the halo before it and of the halo after
    it, ``shift`` channel tiles into their operand, for a grid of (channel
    tiles, batch, token tiles)."""
    per, end = np.int32(tokens // _LANES), np.int32(t // _LANES - 1)
    shift = np.int32(shift)
    return (pl.BlockSpec((1, channels, tokens), lambda c, b, i: (b, c + shift, i)),
            pl.BlockSpec((1, channels, _LANES), lambda c, b, i: (
                b, c + shift, jnp.maximum(i * per - np.int32(1), _Z))),
            pl.BlockSpec((1, channels, _LANES), lambda c, b, i: (
                b, c + shift, jnp.minimum((i + np.int32(1)) * per, end))))


def _taps(weight, bias):
    """(C, K) taps and (C,) bias as float32 (C, K + 1): a lane a tap, the bias
    after them."""
    return jnp.concatenate([weight.astype(F32), bias.astype(F32)[:, None]], axis=1)


def _call(kernel, name, data, weight, tiles, semantics, backward, interpret, **specs):
    (b, _, t), (c, k), ((channels, tokens), walk) = data.shape, weight.shape, tiles
    return pl.pallas_call(
        functools.partial(kernel, k=k, walk=walk),
        grid=(c // channels, b, t // tokens),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics,
            vmem_limit_bytes=int(_vmem(channels, tokens, k, data.dtype.itemsize) * 5 // 4
                                 + 2 ** 22)),
        scratch_shapes=[pltpu.VMEM((k + 1, channels, _LANES), F32),
                        pltpu.VMEM((walk[0], tokens + _LANES), F32)] * backward,
        name=name, interpret=interpret, **specs)


@functools.partial(jax.jit, static_argnames=("begin", "tiles", "interpret"))
def _fwd_pallas(data, weight, bias, begin, tiles, interpret=False):
    """``silu(filter(data[:, begin:begin + C]) + bias)`` of ``data`` (B, W, T),
    the channels read where they lie; (B, C, T). A jitted function of its own:
    the call sites of one shape (every layer, the forward's second run) share
    one trace and one lowering."""
    (b, _, t), (c, k), ((channels, tokens), _) = data.shape, weight.shape, tiles
    tile, before, _ = _specs(t, channels, tokens, begin // channels)
    return _call(
        _fwd_kernel, "causal_conv_silu_fwd", data, weight, tiles, ("parallel",) * 3, False, interpret,
        out_shape=jax.ShapeDtypeStruct((b, c, t), data.dtype),
        in_specs=[tile, before, pl.BlockSpec((channels, k + 1), lambda c, b, i: (c, _Z))],
        out_specs=_specs(t, channels, tokens, 0)[0],
    )(data, data, _taps(weight, bias))


@functools.partial(jax.jit, static_argnames=("begin", "tiles", "interpret"))
def _bwd_pallas(data, weight, bias, g, begin, tiles, interpret=False):
    """The gradients of ``_fwd_pallas`` at its channels of ``data`` (B, C, T),
    at ``weight`` and at ``bias`` under the result's gradient ``g`` (B, C, T)."""
    (b, _, t), (c, k), ((channels, tokens), _) = data.shape, weight.shape, tiles
    tile, _, after = _specs(t, channels, tokens, 0)
    ddata, dtaps = _call(
        _bwd_kernel, "causal_conv_silu_bwd", data, weight, tiles,
        ("parallel", "arbitrary", "arbitrary"), True, interpret,
        out_shape=(jax.ShapeDtypeStruct((b, c, t), data.dtype),
                   jax.ShapeDtypeStruct((c, _LANES), F32)),
        in_specs=[*_specs(t, channels, tokens, begin // channels), tile, after,
                  pl.BlockSpec((channels, k + 1), lambda c, b, i: (c, _Z))],
        out_specs=(tile, pl.BlockSpec((channels, _LANES), lambda c, b, i: (c, _Z))),
    )(data, data, data, g, g, _taps(weight, bias))
    return ddata, dtaps[:, :k].astype(weight.dtype), dtaps[:, k].astype(bias.dtype)


def kernel_takes(shape, k, dtype, begin=0):
    """Whether ``causal_conv_silu`` of C columns from ``begin`` of ``data``, in
    all ``shape`` (B, T, C) of ``dtype``, under ``k`` taps is the kernels':
    everything the call can see."""
    dtype = jnp.dtype(dtype)
    if not on_tpu() or dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(F32)):
        return False
    b, t, c = shape
    tiles = _tiles(t, c, begin)
    if b == 0 or t == 0 or c == 0 or not tiles or k > _MAX_TAPS:
        return False
    return _vmem(*tiles[0], k, dtype.itemsize) <= _chip.VMEM_CEILING


def conv_silu(data, weight, bias, begin=0):
    """``silu(filter(data[..., begin:begin + C]) + bias)`` of ``data`` (B, T, W)
    by ``causal_conv_silu_fwd``, for a call ``kernel_takes`` accepts."""
    tiles = _tiles(data.shape[1], weight.shape[0], begin)
    turned = _fwd_pallas(jnp.swapaxes(data, 1, 2), weight, bias, begin=begin, tiles=tiles)
    return jnp.swapaxes(turned, 1, 2)


def conv_silu_grads(data, weight, bias, g, begin=0):
    """The gradients of ``conv_silu`` at its C columns of ``data``, at
    ``weight`` and at ``bias`` under the result's gradient ``g``, by
    ``causal_conv_silu_bwd``."""
    tiles = _tiles(data.shape[1], weight.shape[0], begin)
    ddata, dw, db = _bwd_pallas(jnp.swapaxes(data, 1, 2), weight, bias,
                                jnp.swapaxes(g, 1, 2), begin=begin, tiles=tiles)
    return jnp.swapaxes(ddata, 1, 2), dw, db
