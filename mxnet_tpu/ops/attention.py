"""Flash attention — Pallas TPU kernel (SURVEY §5 long-context plan; the
reference composes attention from batch_dot+softmax at GluonNLP level with
O(T^2) memory — no fused kernel exists there, this is the TPU-native
upgrade).

Forward is an online-softmax Pallas kernel: Q blocks stream over K/V blocks
held in VMEM, never materializing the (T, T) score matrix in HBM; it writes
the logsumexp as a row, 4 bytes a query. Backward recomputes the scores
tile by tile from that saved logsumexp (the flash-v2 recipe): wherever the
forward kernel ran, in a Pallas kernel (``flash_attention_bwd``: one pass,
five matmuls a tile, dq accumulated in VMEM); everywhere else in XLA, over
128-aligned K/V chunks when the score matrix is too big to materialize.
Both kernels take their matmul operands in the input dtype and accumulate
in float32. The branch each half of a traced call takes is counted
(``telemetry.flash_fwd_branches()`` / ``flash_bwd_branches()``).

Layout: (B, H, T, D) with D the head dim — MXU-friendly (T, D) @ (D, T)
tiles, fp32 accumulation via preferred_element_type. The value's head dim
may differ from the key's (latent attention: keys 192 wide, values 128):
every branch takes ``Dv`` from ``v`` and writes an output that wide.

Grouped heads: K and V may have fewer heads than Q (``Hq % Hkv == 0``);
query head ``h`` reads K/V head ``h // (Hq // Hkv)``. The kernels pick that
head in their block maps, so K and V are never copied a group's times in
HBM; the backward kernel writes ``dk`` / ``dv`` a query head in float32 and
the group's sum is one XLA reduction. The XLA branches repeat K/V (they are
the CPU's and the fallback's).

Selection mask: an optional ``mask`` (B, Tq, Tk), true where a (query, key)
pair may be attended, the same for every head of a sequence, data and not
shape, with no gradient, combined with ``causal`` and the bias. It reaches a
kernel as int8 tiles, laid out again for that kernel's blocks (forward
(B, Tq/bq, Tk/bk, bq, bk), backward key-major (B, Tk/bk, Tq/bq, bk, bq):
one XLA transpose each, Tq x Tk bytes read and written), and a grid step
takes the tiles of its row block (column block in the backward), bq x Tk
bytes, 4 MB at 512 x 8192. Both kernels fetch that block once a query
head: B x H x Tq x Tk bytes a call, 2 GB at one sequence of 8192 on 32
heads, behind the matmuls. With a mask the kernels ask for the VMEM they
hold (``_fwd_vmem_limit``, ``_bwd_vmem_limit``).

A call without a mask and with as many K/V heads as query heads traces to
the kernels it traced to before either was built.

Window: ``window=W`` (a static integer, under ``causal``) lets a query see
itself and the ``W - 1`` keys before it. Nothing is read for it: the forward's
loop over K/V blocks starts at the first block that holds a key inside the
Q block's window, the backward's loop over Q blocks ends at the last block
that can see the K/V block, and every tile visited carries one more
comparison. Those calls are ``pallas_call``s
under names of their own (``window_attention_fwd`` / ``window_attention_bwd``),
counted as branches of their own, with the blocks they visit beside the
causal call's (``telemetry.flash_window_blocks()``). A call without
``window``, or with one that reaches every key, is the causal call, letter
for letter.

Several heads a grid step: where a head is one tile (BERT's 128 x 128 and
512 x 512) both kernels take ``_heads_per_step``'s heads a step, blocks
``(G, T, D)`` on the flat batch x head axis, walked by a loop inside the
kernel whose body does not grow with ``G`` (``_each_head``). ``G`` is a
fixed function of the shape, counted a traced call
(``telemetry.flash_heads_per_step()``); every call that does not qualify
(a mask, ``causal``, grouped heads, several blocks a head, a padded length,
a grid of under 256 steps) lowers to the one-head program, letter for letter.

The fused projection in place: ``flash_attention_qkv`` takes (B, T, 3 x H x
D) as one matmul wrote it and returns (B, T, H x D). Where a head is one
plain tile (the rule above, and whole 128-lane blocks of whole heads:
``_in_place``) two further kernels under the same two names read that array
where it lies and write what the next matmul reads, the backward ONE
cotangent for it: no (B, T, H, D) <-> (B, H, T, D) copy. Every other call
turns the operands and is ``flash_attention``'s. Which a traced call takes
is counted (``telemetry.flash_layouts()``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry as _telemetry
from ..base import MXNetError
from ..context import on_tpu
from . import chip as _chip
from .registry import register


def _tuned_config(q, k, causal, mask=None):
    """Per-shape kernel decision: the tuning table answers, a hit or the
    cost model's choice recorded under this shape bucket (the bucket names
    the K/V head count where it is not the query's, and a selection mask
    where there is one: an entry written for a dense call is never taken
    for a masked one). The returned dict carries the XLA-vs-Pallas choice
    per shape; the device gate (context.on_tpu) still applies on top."""
    return _tuned_config_at(q.shape, k.shape[2], k.shape[1], str(q.dtype),
                            causal, mask)


def _tuned_config_at(q_shape, kv_len, kv_heads, dtype, causal, mask):
    """``_tuned_config`` by the call's (B, H, Tq, D) shape."""
    from .. import tuning

    return tuning.resolve_attention(
        q_shape, kv_len, dtype, causal, kv_heads=kv_heads, mask=mask)


_NEG_INF = -1e30
# lanes of a vector register: a column is broadcast over them to turn it
_LSE_LANES = _chip.LANES


def _kv_per_query_head(q, k, v):
    """K and V with a head a query head (the XLA branches): a group's K/V
    head repeated. Unchanged where the heads are as many."""
    group = q.shape[1] // k.shape[1]
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)


def _sum_kv_group(dx, kv_heads):
    """(B, Hq, Tk, D) gradient a query head -> (B, Hkv, Tk, D), the sum over
    each group in float32."""
    B, H, Tk, D = dx.shape
    if H == kv_heads:
        return dx
    return jnp.sum(dx.reshape(B, kv_heads, H // kv_heads, Tk, D)
                   .astype(jnp.float32), axis=2).astype(dx.dtype)


def _visible(row, col, shift, kv_len=None, causal=False, window=None):
    """The conditions under which query ``row`` sees key ``col``, in the order
    every branch combines them; all of them hold where it does. ``row`` and
    ``col`` are index arrays that broadcast against each other, ``jnp.arange``s
    or a kernel's iotas alike (``row`` is read only under ``causal``).

    - the key is there: ``col < kv_len`` (``kv_len`` None where the caller
      holds no padded key);
    - ``causal``, bottom-right aligned: the query stands at ``row + shift``
      (``shift = Tk - Tq``) and sees nothing after itself;
    - ``window`` (under ``causal``): itself and the ``window - 1`` keys before.

    The selection mask and the bias are data, and stay with each caller."""
    seen = []
    if kv_len is not None:
        seen.append(col < kv_len)
    if causal:
        seen.append(col <= row + shift)
        if window is not None:
            seen.append(row + (shift - window) < col)
    return seen


def _visible_band(tq, tk, window):
    """(tq, tk) booleans of ``_visible`` under ``causal``: the XLA branches
    that hold the whole score matrix."""
    return functools.reduce(jnp.logical_and, _visible(
        jnp.arange(tq)[:, None], jnp.arange(tk)[None, :], tk - tq,
        causal=True, window=window))


def window_blocks(tq, tk, block_q, block_k, window):
    """(visited, causal): the (Q block, K/V block) tiles a window call of
    these lengths and blocks visits, and those the causal call visits.
    From the shapes alone; the same for both kernels (the forward walks a
    Q block's row of tiles, the backward a K/V block's column)."""
    shift = tk - tq
    visited = causal = 0
    for q_off in range(0, tq, block_q):
        hi = min(-(-tk // block_k), (q_off + block_q + shift + block_k - 1) // block_k)
        lo = max(q_off + shift - (window - 1), 0) // block_k
        causal += hi
        visited += hi - min(lo, hi)
    return visited, causal


def _attention_reference(q, k, v, bias, causal, sm_scale, mask=None,
                         window=None):
    """Plain-XLA reference (also the CPU path). O(T^2) memory."""
    k, v = _kv_per_query_head(q, k, v)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores * sm_scale
    if bias is not None:
        scores = scores + bias.astype(jnp.float32)
    if causal:
        scores = jnp.where(_visible_band(*scores.shape[-2:], window), scores,
                           _NEG_INF)
    if mask is not None:
        scores = jnp.where(mask[:, None] != 0, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# Pallas forward kernel
# ---------------------------------------------------------------------------
_KV_INLINE = 4  # K/V blocks of a static loop the kernel writes out in line


def _each_head(heads, unroll, head):
    """``head(g, u)`` for the ``heads`` heads a grid step holds, by a loop
    INSIDE the kernel of ``heads / unroll`` trips. A trip holds ``unroll``
    heads in flight (so that one head's softmax can sit beside another's
    matmuls): an inner loop that the lowering writes out whole, so ``head``
    is traced ONCE whatever ``heads`` and ``unroll`` (a start pays that
    trace at every call site). ``u`` is which of a trip's heads it is. One
    head is the call it always was, index 0 and no loop."""
    if heads == 1:
        return head(0, 0)

    def trip(j, _):
        def one(u, _):
            head(j * unroll + u, u)
            return u + 1, None

        # a scan and not a fori_loop: its index is the i32 it is given
        # (fori_loop's static form counts in i64 under jax_enable_x64)
        jax.lax.scan(one, np.int32(0), None, length=unroll, unroll=True)

    # i32 bounds: under jax_enable_x64 a Python int traces as i64
    jax.lax.fori_loop(jnp.int32(0), jnp.int32(heads // unroll), trip, None)


def _flash_fwd_kernel(q_ref, k_ref, v_ref, bias_ref, mask_ref, o_ref, lse_ref,
                      *, block_k, causal, sm_scale, kv_len, q_len, unroll=1,
                      window=None):
    """One (batch x head, Q block) grid step of the forward: the head's K/V
    sit in VMEM whole, the Q block streams over them block_k keys at a time
    with the online softmax. Operands of both matmuls are in the input
    dtype, the accumulators float32. Where the blocks hold several heads
    (``_heads_per_step``: each head one tile) ``_each_head`` walks them,
    every head's arithmetic what it is alone.

    What is static here decides the vector work a score element pays:
    ``sm_scale`` goes onto the Q block once where that is exact (a power of
    two), else onto the scores; without ``causal`` only the tail block is
    masked, and only where the keys are padded, the first block has nothing
    to rescale, and up to ``_KV_INLINE`` blocks are written out in line.
    Under ``causal`` one loop runs to the diagonal with the mask on every
    block, two blocks an iteration so that the second block's scores can be
    issued beside the first's softmax. A selection mask (``mask_ref``: this
    Q block's int8 tiles, one a K/V block, picked by a leading index) joins
    the other masks on every block. Under a ``window`` the same loop starts
    at the first block that holds a key inside the Q block's window, with
    the window's comparison beside the diagonal's on every block (three
    loops, the blocks between the edges unmasked, gave back on the chip all
    that the skipped blocks save: 4.03 ms a call at (1, 28 on 4, 8192, 128)
    under 4096 against 3.36 so, the causal call 4.02). ``lse`` leaves as a
    lane-oriented row, 4 bytes a query."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    i32 = jnp.int32
    block_q, kv_pad = q_ref.shape[1], k_ref.shape[1]
    num_kv = kv_pad // block_k
    q_off = pl.program_id(1) * block_q
    shift = kv_len - q_len  # causal is bottom-right aligned, as the reference
    # pin scalars to 32-bit: with jax_enable_x64 on, Python floats trace as
    # f64 and Mosaic cannot lower the resulting f64 constants/casts
    neg_inf = f32(_NEG_INF)
    nt = (((1,), (1,)), ((), ()))  # a @ b.T
    # a power of two scales every product exactly, so scaling the Q block
    # gives bit for bit the scores the backward recomputes (k @ q.T * scale)
    fold = math.frexp(sm_scale)[0] == 0.5

    def head(g, _):
        q = q_ref[g]  # (BQ, D)
        if fold:
            q = (q.astype(f32) * f32(sm_scale)).astype(q.dtype)

        def step(ik, carry, masked):
            """Online-softmax update with K/V block ``ik``; ``carry`` None is
            the first block, which has nothing to rescale."""
            if num_kv == 1:
                ik = 0  # a lone block is any length: its offset has to be static
            k_off = ik * block_k
            if not isinstance(ik, int):
                k_off = pl.multiple_of(k_off, block_k)
            k_blk = k_ref[g, pl.ds(k_off, block_k), :]  # (BK, D)
            v_blk = v_ref[g, pl.ds(k_off, block_k), :]  # (BK, Dv)
            s = jax.lax.dot_general(q, k_blk, nt,
                                    preferred_element_type=f32)  # (BQ, BK)
            if not fold:
                s = s * f32(sm_scale)
            if bias_ref is not None:
                s = s + bias_ref[g, ik].astype(f32)  # (1, BK), over the rows
            masks = []
            if masked:
                col = k_off + jax.lax.broadcasted_iota(
                    i32, (block_q, block_k), 1)
                # tail-block padding, then (the row's iota after it, as the
                # kernel always traced) the diagonal and the window
                masks += _visible(None, col, shift,
                                  kv_len if kv_pad != kv_len else None)
                if causal:
                    row = q_off + jax.lax.broadcasted_iota(
                        i32, (block_q, block_k), 0)
                    masks += _visible(row, col, shift, causal=True, window=window)
            if mask_ref is not None:  # the selection: data, on every block
                masks.append(mask_ref[0, 0, ik].astype(i32) != 0)
            if masks:
                s = jnp.where(functools.reduce(jnp.logical_and, masks), s,
                              neg_inf)
            m_new = jnp.max(s, axis=1, keepdims=True)  # (BQ, 1)
            if carry is not None:
                m_i, l_i, acc_i = carry
                m_new = jnp.maximum(m_i, m_new)
            p = jnp.exp(s - m_new)
            l_new = jnp.sum(p, axis=1, keepdims=True)
            acc_new = jnp.dot(p.astype(v_blk.dtype), v_blk,
                              preferred_element_type=f32)  # (BQ, Dv)
            if carry is not None:
                alpha = jnp.exp(m_i - m_new)
                l_new = l_i * alpha + l_new
                acc_new = acc_i * alpha + acc_new
            return m_new, l_new, acc_new

        def loop(lo, hi, carry, masked):
            """Blocks lo .. hi - 1, two an iteration and then the odd one.
            i32 bounds: with jax_enable_x64 on (MXNet dtype parity) a plain
            Python-int loop index traces as i64, which Mosaic cannot lower;
            lax.div on non-negative i32: jnp's floor_divide does not lower."""
            lo, hi = i32(lo), i32(hi)
            pairs = jax.lax.div(hi - lo, i32(2))

            def pair(j, c):
                return step(lo + 2 * j + 1, step(lo + 2 * j, c, masked),
                            masked)

            carry = jax.lax.fori_loop(i32(0), pairs, pair, carry)
            return jax.lax.fori_loop(lo + 2 * pairs, hi,
                                     lambda ik, c: step(ik, c, masked), carry)

        if causal:
            n_all = i32(num_kv)
            if shift >= 0:
                # K/V blocks wholly right of the diagonal add exactly zero
                # (every row has seen a key by then, so exp(-1e30 - m) is
                # 0): stop at the block that holds this Q block's last
                # visible key
                n_all = jnp.minimum(n_all, jax.lax.div(
                    q_off + i32(block_q + shift + block_k - 1), i32(block_k)))
            carry = (jnp.full((block_q, 1), neg_inf, f32),
                     jnp.zeros((block_q, 1), f32),
                     jnp.zeros((block_q, v_ref.shape[2]), f32))
            lo = 0
            if window is not None:
                # (shift >= 0 here.) The first block that holds a key the
                # Q block's top row sees. A row that sees nothing of that
                # block carries exp(0) sums of it until its first real
                # score rescales them by exp(-1e30)
                lo = jax.lax.div(jnp.maximum(
                    q_off + i32(shift - window + 1), 0), i32(block_k))
            m, l, acc = loop(lo, n_all, carry, True)
        else:
            n_clear = num_kv - (kv_pad != kv_len)  # only the tail block is masked
            carry = step(0, None, n_clear == 0)
            if num_kv <= _KV_INLINE:
                for ik in range(1, num_kv):
                    carry = step(ik, carry, ik >= n_clear)
            else:
                carry = loop(1, n_clear, carry, False)
                if n_clear < num_kv:
                    carry = step(n_clear, carry, True)
            m, l, acc = carry
        l = jnp.maximum(l, f32(1e-30))
        o_ref[g] = (acc / l).astype(o_ref.dtype)
        lse = m + jnp.log(l)  # (BQ, 1)
        # lse leaves as a lane-oriented row, the backward kernel's own idiom
        # for its bias gradient: the (BQ, 1) column is broadcast over the
        # lanes and turned, and the first row of that is the (1, BQ) block
        lse_ref[g] = jnp.broadcast_to(lse, (block_q, _LSE_LANES)).T[:1, :]

    _each_head(q_ref.shape[0], unroll, head)


def _lanes(d):
    return -(-d // _chip.LANES) * _chip.LANES


def _fwd_vmem_held(tk, d, dv, block_q, block_k, itemsize, heads=1,
                   in_flight=1):
    """What a grid step of the forward kernel holds without a mask, each
    block twice for the pipeline's two buffers and minor dimensions rounded
    up to the 128 lanes: ``heads`` times the Q and output blocks and K and V
    whole, the float32 accumulator once a head ``in_flight``, and six
    float32 (block_q, block_k) tiles (once: see ``_bwd_vmem_limit``)."""
    return (heads * 2 * (block_q + tk) * (_lanes(d) + _lanes(dv)) * itemsize
            + in_flight * block_q * _lanes(dv) * 4 + 6 * block_q * block_k * 4)


def _fwd_vmem_limit(tk, d, dv, block_q, block_k, itemsize):
    """Scoped VMEM the forward kernel asks for when it carries a selection
    mask (without one it lives in what the compiler gives unasked, as every
    call did before the mask was built): what it holds and a quarter more,
    ``_fwd_vmem_held`` and the Q block's mask tiles (block_q x Tk bytes),
    twice. One sequence of 8192 at 128 + 128 in bfloat16 with 512 x 512
    blocks: 8.4 MB of K/V, 8.4 of mask, 23.9 MB held, 29.8 MB asked for."""
    held = _fwd_vmem_held(tk, d, dv, block_q, block_k, itemsize) \
        + 2 * block_q * tk
    return held + held // 4


# Several heads a grid step, where a head is ONE tile (BERT: 128 x 128 or
# 512 x 512, 1536 or 384 heads a call). Alone in a step, a head is a chain of
# latencies (matmul, row maximum, exp, row sum, matmul) behind the step's own
# 0.35-0.5 us. Device ms a call, forward / backward, bf16, TPU v5e (my chip
# run, PR 36, profiler trace of 20 calls), by heads a step G and heads written
# out an iteration U:
#   (128, 12, 128, 64): one head 0.684 / 0.943; G 16 at U 1 0.632 / 0.718, U 2
#   0.343 / 0.535, U 4 0.293 / 0.441; at U 4, G 4 0.304 / 0.498, G 8 0.294 /
#   0.451; G 32 at U 2 as G 16. The heads in flight buy the time, the grid
#   steps saved little; 2048 rows a step is where more stops paying.
#   (32, 12, 512, 64): one head 0.3465 / 0.7724; U 1 at any G 0.345 / 0.767;
#   U 2 at G 2 0.3358 / 0.7420, G 4 0.3358 / 0.7387, G 8 0.3369 / 0.7428; U 4 at
#   G 4 0.3399 / 0.7356 for twice the backward's compile (1.0 s). Tiles of 1 MB
#   leave little to interleave: two heads in flight, no more.
# Those runs wrote the U heads out in Python: tracing that body cost a start
# 0.31 s a backward call site of BERT's step at U 4 (0.03 at one head), 2.67 s
# of warm set-up at 128 tokens. ``_each_head`` leaves the writing out to the
# lowering: the same Mosaic modules, ``head`` traced once whatever G and U.
_HEAD_ROWS = 2048  # rows (heads x Tq) a grid step carries at most
_HEAD_MIN_GRID = 256  # grid steps under which a step's fixed cost is noise
# heads in flight an iteration of the in-kernel loop, by Tq: the two shapes
# measured; a length between them takes the longer's, one past them 2. At
# 128 rows four are faster (above) and cost BERT's warm start 2.25 s, over
# the 2 s it may cost: lowering writes the body out once a head in flight
_HEADS_IN_FLIGHT = {128: 2, 512: 2}


def _heads_per_step(kernel, forced, bh, tq, tk, one_plain_tile, fits):
    """(heads, unroll) of a grid step of a flash kernel, a fixed function of
    the call's shape (as ``_bwd_blocks``), counted once a traced call
    (``telemetry.flash_heads_per_step()``). One head, the kernel as it was,
    unless ``one_plain_tile``: a head is one unpadded block on both axes,
    of whole 128-lane lengths, in a call with no selection mask, no
    ``causal`` and as many K/V heads as query heads; and unless the grid is
    shorter than ``_HEAD_MIN_GRID`` steps (the deferred-shape forward at
    batch 1: 12 steps, 5 us: it keeps the one-head program and its
    compile). Then the largest power of two that divides ``bh``, carries
    ``_HEAD_ROWS`` rows or fewer and ``fits(heads, unroll)``: what a step
    then holds stays inside three quarters of the scoped VMEM the compiler
    gives unasked, by the parent's own measure (``_bwd_vmem_limit``), so
    nothing is asked for. ``unroll`` of them are in flight together
    (``_HEADS_IN_FLIGHT``). ``forced`` heads override the sizes, never
    ``one_plain_tile``: no caller outside the tests and stand-alone sweeps
    passes them."""
    one_plain_tile = (one_plain_tile and tq % _LSE_LANES == 0
                      and tk % _LSE_LANES == 0)
    in_flight = next((u for t, u in sorted(_HEADS_IN_FLIGHT.items())
                      if tq <= t), 2)
    heads = 1
    if forced is not None:
        heads = int(forced)
        if heads > 1 and not (one_plain_tile and bh % heads == 0):
            raise MXNetError("flash_attention: %d heads a grid step want one "
                             "plain tile a head and a divisor of %d"
                             % (heads, bh))
    elif one_plain_tile and bh >= _HEAD_MIN_GRID:
        heads = next((g for g in (32, 16, 8, 4, 2) if bh % g == 0
                      and g * tq <= _HEAD_ROWS
                      and fits(g, math.gcd(g, in_flight))), 1)
    _telemetry.record_flash_heads(kernel, heads)
    return heads, math.gcd(heads, in_flight)


def _mask_tiles(mask, block_q, block_k, pad_q, pad_k, key_major=False):
    """(B, Tq, Tk) selection -> int8 tiles (B, Tq/bq, Tk/bk, bq, bk), or
    key-major (B, Tk/bk, Tq/bq, bk, bq) for the backward's turned tile;
    padding selects nothing."""
    m = (mask != 0).astype(jnp.int8)
    if pad_q or pad_k:
        m = jnp.pad(m, ((0, 0), (0, pad_q), (0, pad_k)))
    B, Tq, Tk = m.shape
    m = m.reshape(B, Tq // block_q, block_q, Tk // block_k, block_k)
    return m.transpose(0, 3, 1, 4, 2) if key_major else m.transpose(0, 1, 3, 2, 4)


def _kv_head_of(group):
    """Flat (batch x query head) index -> flat (batch x K/V head) index, for
    a block map: query head h reads K/V head h // group, and with Hq = group
    x Hkv the flat index divides the same way."""
    if group == 1:
        return lambda bh: bh
    # lax.div on a non-negative i32: jnp's floor_divide does not lower
    return lambda bh: jax.lax.div(bh, np.int32(group))


def _flash_forward_pallas(q, k, v, bias, causal, sm_scale, block_q, block_k,
                          interpret, mask=None, heads_per_step=None,
                          window=None):
    """(out, lse) of the forward kernel: ``out`` (B, H, Tq, Dv) in the input
    dtype, ``lse`` (B, H, Tq) float32. The kernel writes ``lse`` as a
    (B*H, 1, Tq) array in (1, 1, block_q) row blocks. ``k`` / ``v`` hold
    ``H // group`` heads; ``mask`` is (B, Tq, Tk) or None. A grid step takes
    ``_heads_per_step``'s heads (``heads_per_step`` overrides the rule, for
    tests and stand-alone sweeps), counted by
    ``telemetry.flash_heads_per_step()``. With ``window`` (causal, Tk >= Tq)
    the call goes by the name ``window_attention_fwd`` and the tiles it
    visits are counted (``telemetry.flash_window_blocks()``)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Tq, D = q.shape
    Hkv, Tk, Dv = k.shape[1], k.shape[2], v.shape[3]
    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)
    if not interpret and block_q < Tq and block_q % _LSE_LANES:
        # the chip's compiler takes a block of the lse row that is whole
        # lanes wide, or the whole row: a narrower block (a caller's, or the
        # table's choice for a shorter sequence of the same bucket) widens
        block_q = min(-(-block_q // _LSE_LANES) * _LSE_LANES, Tq)
    # pad sequence dims to block multiples: partial blocks would otherwise
    # hit dynamic-slice start clamping and read/write shifted rows
    pad_q = (-Tq) % block_q
    pad_k = (-Tk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        if bias is not None:
            bias = jnp.pad(bias, ((0, 0), (0, 0), (0, 0), (0, pad_k)))
    Tqp, Tkp = Tq + pad_q, Tk + pad_k
    qf = q.reshape(B * H, Tqp, D)
    kf = k.reshape(B * Hkv, Tkp, D)
    vf = v.reshape(B * Hkv, Tkp, Dv)
    kv_head = _kv_head_of(H // Hkv)
    # G heads a grid step: the blocks' leading dimension, on the flat
    # batch x head axis (1: the program as it was)
    G, unroll = _heads_per_step(
        "fwd", heads_per_step, B * H, Tq, Tk,
        mask is None and not causal and H == Hkv
        and (block_q, block_k) == (Tq, Tk),
        lambda g, u: _fwd_vmem_held(
            Tkp, D, Dv, block_q, block_k, q.dtype.itemsize, g, u)
        <= 3 * _chip.VMEM_SCOPED_DEFAULT // 4)

    # index maps return np.int32 zeros: under jax_enable_x64 a literal 0
    # traces as i64, which Mosaic rejects in the index-map signature
    z = np.int32(0)
    in_specs = [
        pl.BlockSpec((G, block_q, D), lambda bh, iq: (bh, iq, z),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((G, Tkp, D), lambda bh, iq: (kv_head(bh), z, z),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((G, Tkp, Dv), lambda bh, iq: (kv_head(bh), z, z),
                     memory_space=pltpu.VMEM),
    ]
    args = [qf, kf, vf]
    nkv = Tkp // block_k
    if bias is not None:
        # additive key-bias (B, H, 1, Tk) or (B, 1, 1, Tk) → one (1, block_k)
        # row a K/V block, (B*H, Tkp / block_k, 1, block_k): the kernel
        # picks a block's row by a leading index, never by a lane offset
        bflat = jnp.broadcast_to(bias, (B, H, 1, Tkp)).reshape(
            B * H, nkv, 1, block_k)
        in_specs.append(pl.BlockSpec((G, nkv, 1, block_k),
                                     lambda bh, iq: (bh, z, z, z),
                                     memory_space=pltpu.VMEM))
        args.append(bflat)
    extra = {}
    if mask is not None:
        # the Q block's tiles, one a K/V block, the same for every head of
        # the sequence: picked by a leading index as the bias's rows are
        in_specs.append(pl.BlockSpec(
            (1, 1, nkv, block_q, block_k),
            lambda bh, iq: (jax.lax.div(bh, np.int32(H)), iq, z, z, z),
            memory_space=pltpu.VMEM))
        args.append(_mask_tiles(mask, block_q, block_k, pad_q, pad_k))
        extra["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=_fwd_vmem_limit(Tkp, D, Dv, block_q, block_k,
                                             q.dtype.itemsize))

    if window is not None:
        _telemetry.record_flash_window_blocks(
            "fwd", *window_blocks(Tq, Tk, block_q, block_k, window))

    def kernel(*refs):
        refs = list(refs)
        if bias is None:
            refs.insert(3, None)
        if mask is None:
            refs.insert(4, None)
        _flash_fwd_kernel(*refs, block_k=block_k, causal=causal,
                          sm_scale=sm_scale, kv_len=Tk, q_len=Tq, unroll=unroll,
                          window=window)

    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H // G, Tqp // block_q),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((G, block_q, Dv), lambda bh, iq: (bh, iq, z),
                         memory_space=pltpu.VMEM),
            # the same 3-D trick as the bias: a row block of a (B*H, 1, Tqp)
            # array, 4 bytes a query
            pl.BlockSpec((G, 1, block_q), lambda bh, iq: (bh, z, iq),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tqp, Dv), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, Tqp), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd" if window is None else "window_attention_fwd",
        **extra,
    )(*args)
    out = out.reshape(B, H, Tqp, Dv)[:, :, :Tq]
    lse = lse.reshape(B, H, Tqp)[:, :, :Tq]
    return out, lse


# ---------------------------------------------------------------------------
# Pallas backward kernel
# ---------------------------------------------------------------------------
def _flash_bwd_kernel(q_ref, k_ref, v_ref, do_ref, st_ref, bias_ref, mask_ref,
                      dq_ref, dk_ref, dv_ref, db_ref, dq_acc, *,
                      block_q, causal, sm_scale, kv_len, q_len, kv_pad,
                      unroll=1, window=None):
    """One (batch x head, K/V block) grid step of the backward: Q, dO and
    the row statistics of the whole head sit in VMEM, the K/V block's
    scores are recomputed from ``lse`` one Q block at a time, and five
    matmuls a tile give dv, dp, dk and dq. Operands of every matmul are in
    the input dtype, the accumulators float32. Where the blocks hold several
    heads (``_heads_per_step``: each head one tile, so the K/V axis of the
    grid is one step) ``_each_head`` walks them, every head's arithmetic
    what it is alone; ``dq_acc`` then has a plane for each of the ``unroll``
    heads in flight.

    The tile is held key-major, ``s_t[k, q]``: dv and dk are then plain
    matmuls, ``lse`` and ``delta`` are lane-oriented rows (no lane-padded
    column per query), and only ds turns once for dq. ``sm_scale`` is
    applied to ds after its matmuls, on the (T, D) sums. A selection mask
    (``mask_ref``: this K/V block's int8 tiles, key-major, one a Q block)
    joins the other masks on every tile. ``dk`` / ``dv`` leave in the type
    of their blocks: the input's, or float32 a query head where a group of
    query heads shares the K/V head and XLA sums the group. Under a
    ``window`` the loop over Q blocks ends at the last block that can see
    this K/V block, the window's comparison on every tile beside the
    diagonal's."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    ik = pl.program_id(1)
    heads = q_ref.shape[0]
    block_k = k_ref.shape[1]
    tq_pad = q_ref.shape[1]
    k_off = ik * block_k
    shift = kv_len - q_len  # causal is bottom-right aligned, as the forward
    sm_scale = f32(sm_scale)
    neg_inf = f32(_NEG_INF)
    nt = (((1,), (1,)), ((), ()))  # a @ b.T

    def at_step(which):
        """``pl.when`` on the K/V axis; with several heads a step that axis
        is one step long, and a head's first step is its last."""
        return pl.when(ik == which) if heads == 1 else (lambda f: f())

    def head(g, u):
        # one accumulator a head in flight: plane u of dq_acc, picked by a
        # leading index; one head has the one plane, indexed as it always was
        plane = () if heads == 1 else (u,)
        whole = plane + (...,) if plane else ...

        @at_step(0)
        def _zero():
            dq_acc[whole] = jnp.zeros(dq_acc.shape[-2:], f32)

        k = k_ref[g]  # (BK, Dk)
        v = v_ref[g]  # (BK, Dv)
        bias_col = None
        if bias_ref is not None:
            # the key bias is a lane-oriented row; a key-major tile wants it
            # as a column: turn a sublane-broadcast (128, BK) tile
            bias_col = jnp.broadcast_to(
                bias_ref[g].astype(f32), (_LSE_LANES, block_k)).T[:, :1]

        def body(iq, carry):
            dk_i, dv_i, db_i = carry
            q_off = pl.multiple_of(iq * block_q, block_q)
            q = q_ref[g, pl.ds(q_off, block_q), :]  # (BQ, Dk)
            do = do_ref[g, pl.ds(q_off, block_q), :]  # (BQ, Dv)
            lse = st_ref[g, 0:1, pl.ds(q_off, block_q)]  # (1, BQ)
            delta = st_ref[g, 1:2, pl.ds(q_off, block_q)]
            s_t = jax.lax.dot_general(
                k, q, nt, preferred_element_type=f32) * sm_scale  # (BK, BQ)
            if bias_col is not None:
                s_t = s_t + bias_col
            masks = []
            if kv_pad != kv_len or tq_pad != q_len or causal:
                kcol = k_off + jax.lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 0)
                qrow = q_off + jax.lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 1)
                # tail-block padding of the keys, of the queries (this
                # kernel's own: it holds the padded Q whole), the rest
                masks += _visible(qrow, kcol, shift,
                                  kv_len if kv_pad != kv_len else None)
                if tq_pad != q_len:
                    masks.append(qrow < q_len)
                masks += _visible(qrow, kcol, shift, causal=causal, window=window)
            if mask_ref is not None:  # the selection: data, on every tile
                masks.append(mask_ref[0, 0, iq].astype(jnp.int32) != 0)
            if masks:
                s_t = jnp.where(functools.reduce(jnp.logical_and, masks), s_t,
                                neg_inf)
            p_t = jnp.exp(s_t - lse)
            dv_i = dv_i + jnp.dot(p_t.astype(do.dtype), do,
                                  preferred_element_type=f32)  # (BK, D)
            dp_t = jax.lax.dot_general(v, do, nt, preferred_element_type=f32)
            ds_t = p_t * (dp_t - delta)
            if db_i is not None:
                db_i = db_i + jnp.sum(ds_t, axis=1, keepdims=True)
            dk_i = dk_i + jnp.dot(ds_t.astype(q.dtype), q,
                                  preferred_element_type=f32)
            dq_acc[plane + (pl.ds(q_off, block_q), slice(None))] += jnp.dot(
                ds_t.T.astype(k.dtype), k, preferred_element_type=f32)
            return dk_i, dv_i, db_i

        nq = tq_pad // block_q
        first = jnp.int32(0)
        if causal:
            # Q blocks wholly above the diagonal see none of this K/V block:
            # their tiles are exactly zero (lax.div on a non-negative i32:
            # jnp's floor_divide does not lower)
            first = jnp.minimum(jax.lax.div(
                jnp.maximum(k_off - shift, 0), jnp.int32(block_q)), nq)
        db0 = None if bias_ref is None else jnp.zeros((block_k, 1), f32)
        # i32 bounds: under jax_enable_x64 a Python int traces as i64
        last = jnp.int32(nq)
        if window is not None:
            # (shift >= 0 here.) One past the last Q block with a row that
            # sees this K/V block's last key
            last = jnp.minimum(jax.lax.div(jnp.maximum(
                k_off + jnp.int32(block_k + window - 2 - shift), 0),
                jnp.int32(block_q)) + 1, nq)
        dk, dv, db = jax.lax.fori_loop(
            first, last, body,
            (jnp.zeros(k.shape, f32), jnp.zeros(v.shape, f32), db0))
        dk_ref[g] = (dk * sm_scale).astype(dk_ref.dtype)
        dv_ref[g] = dv.astype(dv_ref.dtype)
        if db_ref is not None:
            db_ref[g] = jnp.broadcast_to(db, (block_k, _LSE_LANES)).T[:1, :]

        @at_step(pl.num_programs(1) - 1)
        def _finish():
            dq_ref[g] = (dq_acc[whole] * sm_scale).astype(dq_ref.dtype)

    _each_head(heads, unroll, head)


def _bwd_blocks(Tq, Tk):
    """(block_q, block_k) of the backward kernel, a fixed function of the
    shape: the largest of 512 / 256 / 128 that divides the length rounded
    up to the 128 lanes a tile's minor dimension wants."""
    def pick(t):
        t128 = -(-t // 128) * 128
        return next(b for b in (512, 256, 128) if t128 % b == 0)
    return pick(Tq), pick(Tk)


def _bwd_vmem_limit(tq, dk, dv, block_q, block_k, itemsize, mask=False,
                    out_itemsize=None, heads=1, in_flight=1):
    """Scoped VMEM the backward kernel asks for: nothing (the compiler's
    default) while what it holds fits that with room to spare, as every
    call of 1 MB of Q + dO does; else what it holds and a quarter
    more. Held, minor dimensions rounded up to the 128 lanes: Q, dO and dq
    whole and double-buffered, the float32 dq accumulator, the K/V and
    dk/dv blocks (the latter ``out_itemsize`` wide: float32 a query head
    under grouped heads), six float32 (block_k, block_q) tiles and, with a
    selection mask, the K/V block's int8 tiles (Tq x block_k bytes), twice.
    A head of 4096 rows at 192 + 128 asks for 28.5 MB; one of 8192 rows at
    128 + 128 holds 24.6 MB and asks for 30.8, and with the mask and
    float32 dk / dv blocks holds 33.5 MB and asks for 41.9 (of the chip's
    128 MB). With ``heads`` a grid step the blocks are held that many
    times and the dq accumulator once a head ``in_flight`` (the scratch is
    ``(in_flight, tq, dk)``); ``_heads_per_step`` takes only what this
    leaves at nothing asked for. The six tiles stay counted once: six is the
    allowance for ONE head's chain with every value live at once, which the
    compiler never needs (at 512 rows, 2 heads a step and 2 in flight it
    fits both heads' tiles and 4.2 MB of blocks and planes into the default
    16 MB, where twice six tiles alone would be 12.6); whether a step's
    heads fit is decided by the compiler, and ``tests/test_aot_tpu_compile.py``
    holds the rule's choices at BERT's two shapes to it."""
    lanes, out_itemsize = _lanes, out_itemsize or itemsize
    held = (heads * (2 * tq * (2 * lanes(dk) + lanes(dv)) * itemsize
                     + 2 * 8 * tq * 4 + 2 * block_k * (lanes(dk) + lanes(dv))
                     * (itemsize + out_itemsize))
            + in_flight * tq * lanes(dk) * 4 + 6 * block_q * block_k * 4)
    if mask:
        held += 2 * tq * block_k
    if held <= 3 * _chip.VMEM_SCOPED_DEFAULT // 4:
        return None
    return held + held // 4


def _flash_backward_pallas(q, k, v, bias, out, lse, do, causal, sm_scale,
                           block_q, block_k, interpret, mask=None,
                           heads_per_step=None, window=None):
    """dq, dk, dv (and dbias) of flash attention from the forward's
    residuals, never building a score tensor in HBM. ``k`` / ``v`` hold
    ``H // group`` heads; ``mask`` is (B, Tq, Tk) or None. A grid step takes
    ``_heads_per_step``'s heads, as the forward's. With ``window`` the call
    goes by the name ``window_attention_bwd``, as the forward's."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Tq, D = q.shape
    Hkv, Tk, Dv = k.shape[1], k.shape[2], v.shape[3]
    group = H // Hkv
    f32 = jnp.float32
    delta = jnp.sum(do.astype(f32) * out.astype(f32), axis=-1)  # (B,H,Tq)
    stats = jnp.stack([lse.astype(f32), delta], axis=2)  # (B,H,2,Tq)
    pad_q = (-Tq) % block_q
    pad_k = (-Tk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        do = jnp.pad(do, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
        stats = jnp.pad(stats, ((0, 0), (0, 0), (0, 0), (0, pad_q)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    Tqp, Tkp = Tq + pad_q, Tk + pad_k
    BH = B * H
    kv_head = _kv_head_of(group)
    # a K/V head that a group of query heads shares: each writes its own
    # dk / dv in float32 and XLA sums the group (the K/V axis of the grid
    # carries dq, so a group's heads cannot share an output block)
    part = f32 if group > 1 else None

    def vmem_limit(heads, in_flight):
        return _bwd_vmem_limit(
            Tqp, D, Dv, block_q, block_k, q.dtype.itemsize,
            mask=mask is not None, out_itemsize=4 if part else None,
            heads=heads, in_flight=in_flight)

    # G heads a grid step: the blocks' leading dimension, on the flat
    # batch x head axis (1: the program as it was); what fits is what asks
    # for no scoped VMEM
    G, unroll = _heads_per_step(
        "bwd", heads_per_step, BH, Tq, Tk,
        mask is None and not causal and group == 1
        and (block_q, block_k) == (Tq, Tk),
        lambda g, u: vmem_limit(g, u) is None)

    # np.int32 zeros in the index maps, as the forward (x64 is on)
    z = np.int32(0)
    def whole(d):
        return pl.BlockSpec((G, Tqp, d), lambda bh, ik: (bh, z, z),
                            memory_space=pltpu.VMEM)

    def kv_blk(d):
        return pl.BlockSpec((G, block_k, d),
                            lambda bh, ik: (kv_head(bh), ik, z),
                            memory_space=pltpu.VMEM)

    def dkv_blk(d):
        return pl.BlockSpec((G, block_k, d), lambda bh, ik: (bh, ik, z),
                            memory_space=pltpu.VMEM)

    key_row = pl.BlockSpec((G, 1, block_k), lambda bh, ik: (bh, z, ik),
                           memory_space=pltpu.VMEM)
    in_specs = [whole(D), kv_blk(D), kv_blk(Dv), whole(Dv),
                pl.BlockSpec((G, 2, Tqp), lambda bh, ik: (bh, z, z),
                             memory_space=pltpu.VMEM)]
    args = [q.reshape(BH, Tqp, D), k.reshape(B * Hkv, Tkp, D),
            v.reshape(B * Hkv, Tkp, Dv), do.reshape(BH, Tqp, Dv),
            stats.reshape(BH, 2, Tqp)]
    out_specs = [whole(D), dkv_blk(D), dkv_blk(Dv)]
    out_shape = [jax.ShapeDtypeStruct((BH, Tqp, D), q.dtype),
                 jax.ShapeDtypeStruct((BH, Tkp, D), part or k.dtype),
                 jax.ShapeDtypeStruct((BH, Tkp, Dv), part or v.dtype)]
    static = dict(block_q=block_q, causal=causal, sm_scale=sm_scale,
                  kv_len=Tk, q_len=Tq, kv_pad=Tkp, unroll=unroll, window=window)
    if window is not None:
        _telemetry.record_flash_window_blocks(
            "bwd", *window_blocks(Tq, Tk, block_q, block_k, window))
    if bias is not None:
        bflat = jnp.broadcast_to(bias.astype(f32), (B, H, 1, Tk))
        if pad_k:
            bflat = jnp.pad(bflat, ((0, 0), (0, 0), (0, 0), (0, pad_k)))
        in_specs.append(key_row)
        args.append(bflat.reshape(BH, 1, Tkp))
        out_specs.append(key_row)
        out_shape.append(jax.ShapeDtypeStruct((BH, 1, Tkp), f32))
    if mask is not None:
        # the K/V block's tiles, key-major as the kernel's own tile, one a
        # Q block, picked by a leading index
        in_specs.append(pl.BlockSpec(
            (1, 1, Tqp // block_q, block_k, block_q),
            lambda bh, ik: (jax.lax.div(bh, np.int32(H)), ik, z, z, z),
            memory_space=pltpu.VMEM))
        args.append(_mask_tiles(mask, block_q, block_k, pad_q, pad_k,
                                key_major=True))

    def kernel(*refs):
        refs = list(refs)
        if bias is None:  # no bias in
            refs.insert(5, None)
        if mask is None:
            refs.insert(6, None)
        if bias is None:  # no dbias out
            refs.insert(10, None)
        _flash_bwd_kernel(*refs, **static)

    outs = pl.pallas_call(
        kernel,
        grid=(BH // G, Tkp // block_k),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM(
            (Tqp, D) if G == 1 else (unroll, Tqp, D), f32)],
        # the K/V axis carries the dq accumulator: sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=vmem_limit(G, unroll)),
        interpret=interpret,
        name="flash_attention_bwd" if window is None else "window_attention_bwd",
    )(*args)
    dq = outs[0].reshape(B, H, Tqp, D)[:, :, :Tq]
    dk = outs[1].reshape(B, H, Tkp, D)[:, :, :Tk]
    dv = outs[2].reshape(B, H, Tkp, Dv)[:, :, :Tk]
    if part:
        dk = _sum_kv_group(dk, Hkv).astype(k.dtype)
        dv = _sum_kv_group(dv, Hkv).astype(v.dtype)
    dbias = None
    if bias is not None:
        dbias = _reduce_dbias(outs[3].reshape(B, H, 1, Tkp)[..., :Tk], bias)
    return dq, dk, dv, dbias


# ---------------------------------------------------------------------------
# The fused projection in place: qkv (B, T, 3 x H x D) in, (B, T, H x D) out
# ---------------------------------------------------------------------------
# BERT's block computes q, k and v by ONE matmul, (B, T, 3 x H x D): all of q,
# then k, then v, head h of each at lanes [h x D, (h + 1) x D). The kernels
# above want (B, H, T, D), and XLA pays for the difference: eight passes a
# layer over the step's tokens (q, k, v and dO turned, the output and dq, dk,
# dv turned back), as much time as the kernels they surround. The two kernels
# below read that array as it lies and write what the next matmul reads: a
# grid step holds whole rows of a few batch elements, a head's operands are
# 128-lane column blocks of them, and at D = 64 a block holds a PAIR of heads
# that the kernel never slices: a head's scores are (q_pair with the other
# head's lanes zeroed) @ k_pair^T (the zeroed lanes add exact zeros, and a
# 64-deep contraction costs the 128-deep MXU a whole pass already), p @ v_pair
# gives 128 lanes of which the head's own 64 are stored. Engaged only where a
# head is one plain tile (``_in_place``); everything else turns the
# operands and calls ``flash_attention``, inside ``flash_attention_qkv``.
def _own_lanes(u, rows, head_dim):
    """(rows, 128) booleans, true at the lanes of the ``u % (128 // head_dim)``-th
    head of a 128-lane block; None where a head is the whole block. ``u`` is a
    head's place among those in flight, which the lowering knows, so the
    mask is a constant of the compiled kernel."""
    per = _LSE_LANES // head_dim
    if per == 1:
        return None
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LSE_LANES), 1)
    lo = jax.lax.rem(u, np.int32(per)) * np.int32(head_dim)
    return jnp.logical_and(lane >= lo, lane < lo + np.int32(head_dim))


def _owned(x, own, factor=None):
    """An operand with the other head's lanes zeroed (``own`` None: none to
    zero) and ``factor`` applied in float32, back in its own dtype."""
    if own is None and factor is None:
        return x
    xf = x.astype(jnp.float32)
    if factor is not None:
        xf = xf * factor
    if own is not None:  # the zeroed lanes add exact zeros to a product
        xf = jnp.where(own, xf, jnp.float32(0))
    return xf.astype(x.dtype)


def _head_blocks(g, heads, head_dim):
    """Head ``g`` of a grid step's ``rows x heads`` -> (b, h, lanes): its batch
    element in the step, its head, and ``lanes(third)``, the 128-lane block
    that holds it in the ``third``-th of q, k, v (0 .. 2) of a ``(.., 3 x heads
    x head_dim)`` row, or in a ``(.., heads x head_dim)`` row at ``third`` 0."""
    from jax.experimental import pallas as pl

    i32 = np.int32
    per = _LSE_LANES // head_dim
    # lax.div / rem on non-negative i32: jnp's floor_divide does not lower
    b = jax.lax.div(g, i32(heads))
    h = jax.lax.rem(g, i32(heads))
    blk = h if per == 1 else jax.lax.div(h, i32(per))

    def lanes(third):
        off = (i32(third * (heads // per)) + blk) * i32(_LSE_LANES)
        return pl.ds(pl.multiple_of(off, _LSE_LANES), _LSE_LANES)

    return b, h, lanes


def _store_own(ref, b, lanes, value, own):
    """``value`` (T, 128) float32 into the 128-lane block ``lanes`` of row
    block ``b``: whole, or the head's own lanes of a pair's block, the other
    head's kept as they stand (the chip's compiler takes no masked store of a
    16-bit type; the pair's first head reads lanes nobody has written and
    hands them back unread)."""
    if own is not None:
        value = jnp.where(own, value, ref[b, :, lanes].astype(value.dtype))
    ref[b, :, lanes] = value.astype(ref.dtype)


def _row_of(col):
    """(T, 1) column -> (1, T) lane-oriented row: broadcast over the lanes
    and turned, the idiom of ``lse`` and the bias gradient above."""
    return jnp.broadcast_to(col, (col.shape[0], _LSE_LANES)).T[:1, :]


def _qkv_fwd_kernel(qkv_ref, bias_ref, o_ref, lse_ref, *, heads, head_dim,
                    sm_scale, unroll):
    """One grid step of the in-place forward: ``qkv_ref`` holds whole rows
    ``(rows, T, 3 x heads x head_dim)`` of a few batch elements, and
    ``_each_head`` walks their ``rows x heads`` heads, each ONE tile: the
    arithmetic of ``_flash_fwd_kernel``'s lone unmasked block (operands in
    the input dtype, float32 accumulator and softmax, ``sm_scale`` on the Q
    block where that is exact), on 128-lane blocks read where the projection
    wrote them. ``o_ref`` is ``(rows, T, heads x head_dim)``; ``lse_ref``
    ``(rows x heads, 1, T)``, a lane-oriented row a head; ``bias_ref``
    ``(rows, heads | 1, 1, T)`` or None."""
    f32 = jnp.float32
    T = qkv_ref.shape[1]
    nt = (((1,), (1,)), ((), ()))  # a @ b.T
    fold = math.frexp(sm_scale)[0] == 0.5  # as _flash_fwd_kernel

    def head(g, u):
        b, h, lanes = _head_blocks(g, heads, head_dim)
        own = _own_lanes(u, T, head_dim)
        # (T, 128) each: the head, or its pair
        q = _owned(qkv_ref[b, :, lanes(0)], own, f32(sm_scale) if fold else None)
        k = qkv_ref[b, :, lanes(1)]
        v = qkv_ref[b, :, lanes(2)]
        s = jax.lax.dot_general(q, k, nt, preferred_element_type=f32)  # (T, T)
        if not fold:
            s = s * f32(sm_scale)
        if bias_ref is not None:  # (1, T), over the rows
            s = s + bias_ref[b, h if bias_ref.shape[1] > 1 else 0].astype(f32)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.maximum(jnp.sum(p, axis=1, keepdims=True), f32(1e-30))
        acc = jnp.dot(p.astype(v.dtype), v, preferred_element_type=f32)
        _store_own(o_ref, b, lanes(0), acc / l, own)
        lse_ref[g] = _row_of(m + jnp.log(l))

    _each_head(qkv_ref.shape[0] * heads, unroll, head)


def _qkv_bwd_kernel(qkv_ref, o_ref, do_ref, lse_ref, bias_ref, dqkv_ref,
                    db_ref, *, heads, head_dim, sm_scale, unroll):
    """One grid step of the in-place backward, over the blocks of
    ``_qkv_fwd_kernel`` and ``do_ref`` / ``o_ref`` ``(rows, T, heads x
    head_dim)``: a head's key-major tile as ``_flash_bwd_kernel`` holds it
    (``s_t[k, q]`` recomputed from ``lse``, five matmuls, float32
    accumulators), ``delta`` summed here from the head's own lanes of dO x
    out, and dq, dk, dv stored into the head's lanes of the three thirds of
    ONE ``(rows, T, 3 x heads x head_dim)`` cotangent. At a pair of heads a
    block, K and V take the zeroed lanes (and ``sm_scale`` where that is
    exact: the same bits as scaling the scores); the products that leave
    through Q, K and dO carry the other head's lanes, which are not
    stored. ``db_ref`` ``(rows x heads, 1, T)`` float32 or None."""
    f32 = jnp.float32
    T = qkv_ref.shape[1]
    nt = (((1,), (1,)), ((), ()))  # a @ b.T
    fold = math.frexp(sm_scale)[0] == 0.5
    scale = f32(sm_scale)

    def head(g, u):
        b, h, lanes = _head_blocks(g, heads, head_dim)
        own = _own_lanes(u, T, head_dim)
        q = qkv_ref[b, :, lanes(0)]
        k = qkv_ref[b, :, lanes(1)]
        v = qkv_ref[b, :, lanes(2)]
        do = do_ref[b, :, lanes(0)]
        prod = do.astype(f32) * o_ref[b, :, lanes(0)].astype(f32)
        if own is not None:
            prod = jnp.where(own, prod, f32(0))
        delta = _row_of(jnp.sum(prod, axis=1, keepdims=True))  # (1, T)
        lse = lse_ref[g]  # (1, T)
        s_t = jax.lax.dot_general(_owned(k, own, scale if fold else None), q,
                                  nt, preferred_element_type=f32)  # (Tk, Tq)
        if not fold:
            s_t = s_t * scale
        if bias_ref is not None:
            # the key bias is a lane-oriented row; a key-major tile wants it
            # as a column: turn a sublane-broadcast (128, T) tile
            row = bias_ref[b, h if bias_ref.shape[1] > 1 else 0].astype(f32)
            s_t = s_t + jnp.broadcast_to(row, (_LSE_LANES, T)).T[:, :1]
        p_t = jnp.exp(s_t - lse)
        dv = jnp.dot(p_t.astype(do.dtype), do, preferred_element_type=f32)
        dp_t = jax.lax.dot_general(_owned(v, own), do, nt,
                                   preferred_element_type=f32)
        ds_t = p_t * (dp_t - delta)
        dk = jnp.dot(ds_t.astype(q.dtype), q, preferred_element_type=f32)
        dq = jnp.dot(ds_t.T.astype(k.dtype), k, preferred_element_type=f32)
        _store_own(dqkv_ref, b, lanes(0), dq * scale, own)
        _store_own(dqkv_ref, b, lanes(1), dk * scale, own)
        _store_own(dqkv_ref, b, lanes(2), dv, own)
        if db_ref is not None:
            db_ref[g] = _row_of(jnp.sum(ds_t, axis=1, keepdims=True))

    _each_head(qkv_ref.shape[0] * heads, unroll, head)


def _in_place_vmem_limit(kernel, rows, tokens, width, heads, itemsize, bias):
    """Scoped VMEM an in-place kernel asks for, by ``_bwd_vmem_limit``'s
    measure: nothing while what a grid step holds fits three quarters of
    what the compiler gives unasked, else that and a quarter more. Held: the
    blocks twice for the pipeline's two buffers (forward: qkv and out, 4 x
    ``width`` lanes a token; backward: qkv, out, dO and dqkv, 8 x), the
    ``lse`` rows (a (1, T) float32 block pads to 8 sublanes), the bias rows
    and their gradient's, and six float32 (T, T) tiles. 128 tokens x 768:
    0.39 MB a batch element forward, 0.79 backward, twice; 512 tokens: 12.9
    MB forward (16.1 asked for), 19.2 backward (24.0), of the chip's 128."""
    thirds = 4 if kernel == "fwd" else 8
    held = 2 * rows * tokens * thirds * _lanes(width) * itemsize \
        + 2 * rows * heads * 8 * tokens * 4 + 6 * tokens * tokens * 4
    if bias is not None:
        held += 2 * rows * bias.shape[1] * 8 * tokens * bias.dtype.itemsize
        if kernel == "bwd":
            held += 2 * rows * heads * 8 * tokens * 4
    if held <= 3 * _chip.VMEM_SCOPED_DEFAULT // 4:
        return None
    return held + held // 4


# Tokens (batch elements x T) a grid step of an in-place kernel holds, whole
# rows of them: a step's blocks are contiguous in HBM however it is cut, and
# the cut hardly shows. Device ms a call by the host's clock over 200 calls,
# forward / backward, bf16, 12 heads of 64, TPU v5e (my chip run, PR 38, call
# 2), by batch elements a step and heads in flight U:
#   128 tokens x 128 sequences: 1 at U 2 0.377 / 0.565; 2 0.370 / 0.562; 4
#   0.365 / 0.564; 8 0.367 / 0.572; at U 4, 2 a step 0.365 / 0.491 (PR 36's
#   finding again: four in flight are faster and cost a start its 2 s).
#   512 tokens x 32: 1 at U 2 0.360 / 0.744; 2 0.391 / 0.752; U 4 0.379 / 0.733.
# The heads-major kernels with the copies XLA makes around them, same clock:
# 0.865 forward, 2.234 forward + backward at 128 tokens (in place 0.918);
# 0.845 / 2.422 at 512 (in place 1.089). The forward's output is theirs to the
# last bit, the gradient within one place of bf16.
_IN_PLACE_TOKENS = 256


def _in_place_rows(batch, tokens):
    """Batch elements a grid step of either in-place kernel holds: the
    largest power of two that divides the batch and carries
    ``_IN_PLACE_TOKENS`` tokens or fewer, at least one: 2 at 128 tokens, 1 at
    256 and 512."""
    rows = max(1, _IN_PLACE_TOKENS // tokens)
    rows = 1 << (rows.bit_length() - 1)
    while batch % rows:
        rows //= 2
    return rows


def _in_place_call(kernel, qkv, heads, bias, rows):
    """What both in-place ``pallas_call``s share: the grid, the heads in
    flight, the block specs of whole rows, the compiler's parameters."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, width3 = qkv.shape
    width = width3 // 3
    rows = rows or _in_place_rows(B, T)
    per = _LSE_LANES // (width // heads)
    in_flight = next((u for t, u in sorted(_HEADS_IN_FLIGHT.items())
                      if T <= t), 2)
    # a trip of the loop holds whole 128-lane blocks, so that a head's place
    # in the trip says which lanes of its block are its own
    unroll = math.gcd(rows * heads, max(in_flight, per))
    _telemetry.record_flash_heads(kernel, rows * heads)
    # np.int32 zeros in the index maps, as the kernels above (x64 is on)
    z = np.int32(0)

    def whole(*minor):
        return pl.BlockSpec(
            (rows,) + minor, lambda i: (i,) + (z,) * len(minor),
            memory_space=pltpu.VMEM)

    # a (1, T) row a head: lse, the bias gradient
    head_rows = pl.BlockSpec((rows * heads, 1, T), lambda i: (i, z, z),
                             memory_space=pltpu.VMEM)

    params = pltpu.CompilerParams(
        dimension_semantics=("parallel",),
        vmem_limit_bytes=_in_place_vmem_limit(
            kernel, rows, T, width, heads, qkv.dtype.itemsize, bias))
    return B // rows, unroll, whole, head_rows, params


def _qkv_forward_pallas(qkv, bias, heads, sm_scale, interpret, rows=None):
    """(out, lse) of the in-place forward kernel: ``qkv`` (B, T, 3 x H x D)
    as the fused projection wrote it, ``out`` (B, T, H x D) as the output
    projection reads it, ``lse`` (B x H, 1, T) float32, the layout the
    backward kernel takes. ``bias`` (B, H | 1, 1, T) or None. ``rows``
    overrides the grid step's cut, for tests and stand-alone sweeps."""
    from jax.experimental import pallas as pl

    B, T, width3 = qkv.shape
    width = width3 // 3
    grid, unroll, whole, head_rows, params = _in_place_call(
        "fwd", qkv, heads, bias, rows)
    in_specs, args = [whole(T, width3)], [qkv]
    if bias is not None:
        in_specs.append(whole(bias.shape[1], 1, T))
        args.append(bias)

    def kernel(*refs):
        refs = list(refs)
        if bias is None:
            refs.insert(1, None)
        _qkv_fwd_kernel(*refs, heads=heads, head_dim=width // heads,
                        sm_scale=sm_scale, unroll=unroll)

    return pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=[whole(T, width), head_rows],
        out_shape=[jax.ShapeDtypeStruct((B, T, width), qkv.dtype),
                   jax.ShapeDtypeStruct((B * heads, 1, T), jnp.float32)],
        compiler_params=params,
        interpret=interpret,
        name="flash_attention_fwd",
    )(*args)


def _qkv_backward_pallas(qkv, bias, out, lse, do, heads, sm_scale, interpret,
                         rows=None):
    """(dqkv, dbias) of the in-place backward kernel from the forward's
    residuals: ONE (B, T, 3 x H x D) cotangent, written by the kernel where
    the projection's backward reads it."""
    from jax.experimental import pallas as pl

    B, T, width3 = qkv.shape
    width = width3 // 3
    grid, unroll, whole, head_rows, params = _in_place_call(
        "bwd", qkv, heads, bias, rows)
    in_specs = [whole(T, width3), whole(T, width), whole(T, width),
                head_rows]
    args = [qkv, out, do, lse]
    out_specs = [whole(T, width3)]
    out_shape = [jax.ShapeDtypeStruct(qkv.shape, qkv.dtype)]
    if bias is not None:
        in_specs.append(whole(bias.shape[1], 1, T))
        args.append(bias)
        out_specs.append(head_rows)
        out_shape.append(jax.ShapeDtypeStruct((B * heads, 1, T), jnp.float32))

    def kernel(*refs):
        refs = list(refs)
        if bias is None:  # no bias in, no dbias out
            refs.insert(4, None)
            refs.append(None)
        _qkv_bwd_kernel(*refs, heads=heads, head_dim=width // heads,
                        sm_scale=sm_scale, unroll=unroll)

    outs = pl.pallas_call(
        kernel,
        grid=(grid,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=params,
        interpret=interpret,
        name="flash_attention_bwd",
    )(*args)
    dbias = None
    if bias is not None:
        dbias = _reduce_dbias(outs[1].reshape(B, heads, 1, T), bias)
    return outs[0], dbias


# ---------------------------------------------------------------------------
# chunked-XLA path for long sequences (K/V too big for whole-sequence VMEM
# residency; lax.scan streams KV chunks with the same online softmax —
# O(Tq * chunk) memory, fused by XLA)
# ---------------------------------------------------------------------------
_VMEM_KV_BYTES = 4 * 1024 * 1024  # per-(batch,head) K+V budget
LONG_CHUNK = 1024


def _kv_fits_vmem(k, v=None):
    dv = k.shape[3] if v is None else v.shape[3]
    return k.shape[2] * (k.shape[3] + dv) * k.dtype.itemsize \
        <= _VMEM_KV_BYTES


def _chunk_kv(x, chunk):
    B, H, Tk, D = x.shape
    pad = (-Tk) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad), (0, 0)))
    return x.reshape(B, H, (Tk + pad) // chunk, chunk, D), pad


def _chunk_mask(mask, chunk, pad):
    """(B, Tq, Tk) selection -> (nchunks, B, 1, Tq, chunk) booleans, one a
    K/V chunk of a scan; padding selects nothing."""
    m = jnp.pad(mask != 0, ((0, 0), (0, 0), (0, pad)))
    B, Tq, Tkp = m.shape
    return jnp.moveaxis(m.reshape(B, Tq, Tkp // chunk, chunk), 2, 0)[:, :, None]


def _attention_scan_fwd(q, k, v, bias, causal, sm_scale, chunk=LONG_CHUNK,
                        mask=None, window=None):
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    k, v = _kv_per_query_head(q, k, v)
    kc, pad = _chunk_kv(k, chunk)
    vc, _ = _chunk_kv(v, chunk)
    nchunks = kc.shape[2]
    if bias is not None:
        bias_p = jnp.pad(bias, ((0, 0), (0, 0), (0, 0), (0, pad)),
                         constant_values=_NEG_INF)
        bc = jnp.moveaxis(
            bias_p.reshape(bias.shape[0], bias.shape[1], 1, nchunks, chunk),
            3, 0)
    qf = q.astype(jnp.float32)

    def body(carry, xs):
        m_i, l_i, acc_i = carry
        k_c, v_c, idx = xs[0], xs[1], xs[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_c.astype(jnp.float32),
                       preferred_element_type=jnp.float32) * sm_scale
        if bias is not None:
            s = s + xs[2].astype(jnp.float32)
        col = idx * chunk + jnp.arange(chunk)
        valid = functools.reduce(jnp.logical_and, _visible(
            jnp.arange(Tq)[:, None], col[None, :], Tk - Tq, Tk, causal, window))
        valid = valid[None, None]
        if mask is not None:
            valid = jnp.logical_and(valid, xs[-2])
        s = jnp.where(valid, s, _NEG_INF)
        m_new = jnp.maximum(m_i, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_i - m_new)
        l_new = l_i * alpha + jnp.sum(p, axis=-1)
        acc_new = acc_i * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_c.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    init = (jnp.full((B, H, Tq), _NEG_INF, jnp.float32),
            jnp.zeros((B, H, Tq), jnp.float32),
            jnp.zeros((B, H, Tq, v.shape[3]), jnp.float32))
    xs = (jnp.moveaxis(kc, 2, 0), jnp.moveaxis(vc, 2, 0))
    if bias is not None:
        xs += (bc,)
    if mask is not None:
        xs += (_chunk_mask(mask, chunk, pad),)
    (m, l, acc), _ = jax.lax.scan(body, init, xs + (jnp.arange(nchunks),))
    l = jnp.maximum(l, 1e-30)
    return (acc / l[..., None]).astype(q.dtype), m + jnp.log(l)


def _bwd_chunked(q, k, v, bias, out, lse, do, causal, sm_scale,
                 chunk=LONG_CHUNK, mask=None, window=None):
    """Backward over K/V chunks in XLA. Matmul operands stay in the input
    dtype (``p`` and ``ds`` are cast to it just before their matmuls) and
    accumulate in float32; ``exp``, ``delta`` and the ``dq`` carry are
    float32."""
    B, H, Tq, D = q.shape
    kv_heads, Tk = k.shape[1], k.shape[2]
    k, v = _kv_per_query_head(q, k, v)
    f32 = jnp.float32
    delta = jnp.sum(do.astype(f32) * out.astype(f32), axis=-1)  # (B,H,Tq)
    kc, pad = _chunk_kv(k, chunk)
    vc, _ = _chunk_kv(v, chunk)
    nchunks = kc.shape[2]
    if bias is not None:
        bias_p = jnp.pad(bias, ((0, 0), (0, 0), (0, 0), (0, pad)),
                         constant_values=_NEG_INF)
        bc = jnp.moveaxis(
            bias_p.reshape(bias.shape[0], bias.shape[1], 1, nchunks, chunk),
            3, 0)

    def body(dq_acc, xs):
        k_c, v_c, idx = xs[0], xs[1], xs[-1]
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k_c,
                       preferred_element_type=f32) * sm_scale
        if bias is not None:
            s = s + xs[2].astype(f32)
        col = idx * chunk + jnp.arange(chunk)
        valid = functools.reduce(jnp.logical_and, _visible(
            jnp.arange(Tq)[:, None], col[None, :], Tk - Tq, Tk, causal, window))
        valid = valid[None, None]
        if mask is not None:
            valid = jnp.logical_and(valid, xs[-2])
        s = jnp.where(valid, s, _NEG_INF)
        p = jnp.exp(s - lse[..., None])
        dv_c = jnp.einsum("bhqk,bhqd->bhkd", p.astype(do.dtype), do,
                          preferred_element_type=f32)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do, v_c,
                        preferred_element_type=f32)
        ds = p * (dp - delta[..., None]) * sm_scale
        ds_c = ds.astype(q.dtype)
        dq_acc = dq_acc + jnp.einsum("bhqk,bhkd->bhqd", ds_c, k_c,
                                     preferred_element_type=f32)
        dk_c = jnp.einsum("bhqk,bhqd->bhkd", ds_c, q,
                          preferred_element_type=f32)
        db_c = jnp.sum(ds, axis=2) / sm_scale  # (B,H,chunk)
        # a chunk's dk / dv are final, so this is their one cast
        return dq_acc, (dk_c.astype(k.dtype), dv_c.astype(v.dtype), db_c)

    xs = (jnp.moveaxis(kc, 2, 0), jnp.moveaxis(vc, 2, 0))
    if bias is not None:
        xs += (bc,)
    if mask is not None:
        xs += (_chunk_mask(mask, chunk, pad),)
    dq, (dk_s, dv_s, db_s) = jax.lax.scan(
        body, jnp.zeros((B, H, Tq, D), f32), xs + (jnp.arange(nchunks),))
    dk = jnp.moveaxis(dk_s, 0, 2).reshape(B, H, Tk + pad, D)[:, :, :Tk]
    dv = jnp.moveaxis(dv_s, 0, 2).reshape(
        B, H, Tk + pad, v.shape[3])[:, :, :Tk]
    dk, dv = _sum_kv_group(dk, kv_heads), _sum_kv_group(dv, kv_heads)
    dbias = None
    if bias is not None:
        db = jnp.moveaxis(db_s, 0, 2).reshape(B, H, Tk + pad)[:, :, :Tk]
        dbias = _reduce_dbias(db[:, :, None, :], bias)
    return dq.astype(q.dtype), dk, dv, dbias


def _reduce_dbias(db, bias):
    """(B, H, 1, Tk) per-head bias gradient → the bias's own shape and
    dtype (a (B, 1, 1, Tk) bias is broadcast over heads)."""
    if bias.shape[1] == 1:
        db = jnp.sum(db, axis=1, keepdims=True)
    return db.astype(bias.dtype)


# ---------------------------------------------------------------------------
# custom vjp: both halves recompute nothing they can keep in VMEM. Forward:
# the Pallas kernel where the tuning table picks it on a TPU, the scan when
# K/V outgrow VMEM, else the reference. Backward, from the forward's lse:
# the Pallas kernel wherever the forward kernel ran, else XLA (chunked over
# K/V when the scores are over _BWD_SCORE_BYTES, else materialised)
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_core(q, k, v, bias, mask, causal, sm_scale, window=None):
    out, _ = _flash_fwd(q, k, v, bias, mask, causal, sm_scale, window)
    return out


def _count_branch(kernel, name, window, xla_tiles=None):
    """Count the branch a traced ``kernel`` (``fwd`` or ``bwd``) pass takes;
    a window call's branches are its own (``window_<name>``). An XLA branch
    bounds no loop by the window: it counts the ``xla_tiles`` it walks as
    visited and as the causal call's alike
    (``telemetry.flash_window_blocks()``; the kernels count their own)."""
    if window is not None:
        name = "window_" + name
        if xla_tiles is not None:
            _telemetry.record_flash_window_blocks(kernel, xla_tiles, xla_tiles)
    (_telemetry.record_flash_fwd if kernel == "fwd"
     else _telemetry.record_flash_bwd)(name)


@jax.named_scope("attention")
def _flash_fwd(q, k, v, bias, mask, causal, sm_scale, window=None):
    """Forward of ``_flash_core``. The branch it takes is counted
    (``telemetry.flash_fwd_branches()``), once a trace as the backward's."""
    _record_flash_signature(q, k, v, bias, mask, causal, sm_scale, window)
    _telemetry.record_flash_layout("fwd", "heads_major")
    if not _kv_fits_vmem(k, v):
        _count_branch("fwd", "scan", window, -(-k.shape[2] // LONG_CHUNK))
        out, lse = _attention_scan_fwd(q, k, v, bias, causal, sm_scale,
                                       mask=mask, window=window)
    else:
        cfg = _tuned_config(q, k, causal, mask)
        if cfg.get("backend") == "pallas" and on_tpu():
            _count_branch("fwd", "kernel", window)
            out, lse = _flash_forward_pallas(
                q, k, v, bias, causal, sm_scale,
                int(cfg["block_q"]), int(cfg["block_k"]), interpret=False,
                mask=mask, window=window)
        else:
            # per-shape XLA choice (small shapes, or a tuned decision
            # that XLA's fused reference wins here), and every non-TPU
            # backend
            _count_branch("fwd", "reference", window, 1)
            out = _attention_reference(q, k, v, bias, causal, sm_scale, mask,
                                       window)
            lse = None
    return out, (q, k, v, bias, mask, out, lse)


def _record_flash_signature(q, k, v, bias, mask, causal, sm_scale, window=None):
    """Remember this dispatch's shape signature for tuning.warmup()'s
    AOT replay (deduplicated in the table; a fresh serving replica
    compiles these ahead of traffic). ``k_shape`` carries the K/V head
    count; ``mask_dtype`` says whether a selection mask is there;
    ``window`` the static window, on a call that has one."""
    try:
        from .. import tuning

        tuning.record_signature("flash_attention", {
            "q_shape": list(q.shape), "k_shape": list(k.shape),
            "v_shape": list(v.shape),
            "bias_shape": None if bias is None else list(bias.shape),
            "bias_dtype": None if bias is None else str(bias.dtype),
            "mask_dtype": None if mask is None else str(mask.dtype),
            "dtype": str(q.dtype), "causal": bool(causal),
            "sm_scale": float(sm_scale),
            **({} if window is None else {"window": int(window)})})
    except Exception:  # noqa: BLE001 — bookkeeping must not fail the op
        pass


_BWD_SCORE_BYTES = 256 * 1024 * 1024  # peak score-matrix budget in backward
# per-(batch,head) Q+dO budget of the backward kernel: up to 1 MB (BERT's
# 128 KB at 512 x 64) the call lives in the scoped VMEM the compiler gives
# unasked; over that it asks for what it holds (``_bwd_vmem_limit``). 4 MB
# admits a head of 4096 rows with keys 192 and values 128 wide (2.5 MB) and
# one of 8192 rows at 128 + 128 (4 MB: the call asks for 30.8 MB, and for
# 41.9 MB with a selection mask and grouped heads).
_VMEM_QDO_BYTES = 4 * 1024 * 1024


def _qdo_fits_vmem(q, v=None):
    dv = q.shape[3] if v is None else v.shape[3]
    return q.shape[2] * (q.shape[3] + dv) * q.dtype.itemsize \
        <= _VMEM_QDO_BYTES


def _bwd_chunk(B, H, Tq, Tk):
    """K/V chunk of the XLA backward: the score budget in whole 128-key
    tiles (at least one), Tk split evenly over the chunks that takes, so a
    chunk is never ragged and the padding stays under a tile a chunk."""
    cap = max(1, _BWD_SCORE_BYTES // max(1, B * H * Tq * 4) // 128)
    tiles = -(-Tk // 128)
    nchunks = -(-tiles // cap)
    return -(-tiles // nchunks) * 128


@jax.named_scope("attention_bwd")
def _flash_bwd(causal, sm_scale, res, do, window=None):
    """Backward of ``_flash_core``. The branch it takes is counted
    (``telemetry.flash_bwd_branches()``): once a trace, so once a compiled
    program that differentiates the op."""
    q, k, v, bias, mask, out, lse = res
    B, H, Tq, _ = q.shape
    kv_heads, Tk = k.shape[1], k.shape[2]
    _telemetry.record_flash_layout("bwd", "heads_major")
    if (lse is not None and on_tpu() and _kv_fits_vmem(k, v)
            and _qdo_fits_vmem(q, v)):
        # the forward kernel ran (its lse is here, its K/V fit VMEM) and a
        # head's Q and dO fit beside them: same recipe, tiled in VMEM
        _count_branch("bwd", "kernel", window)
        block_q, block_k = _bwd_blocks(Tq, Tk)
        return _flash_backward_pallas(
            q, k, v, bias, out, lse, do, causal, sm_scale, block_q, block_k,
            interpret=False, mask=mask, window=window) + (None,)
    score_bytes = B * H * Tq * Tk * 4
    if not _kv_fits_vmem(k, v) or score_bytes > _BWD_SCORE_BYTES:
        # keep backward O(Tq * chunk): a forward that fit VMEM can still
        # have a score matrix far too big to materialize (e.g. T=8k)
        chunk = _bwd_chunk(B, H, Tq, Tk)
        _count_branch("bwd", "chunked", window, -(-Tk // chunk))
        if lse is None:
            _, lse = _attention_scan_fwd(q, k, v, bias, causal, sm_scale,
                                         mask=mask, window=window)
        return _bwd_chunked(q, k, v, bias, out, lse, do, causal, sm_scale,
                            chunk=chunk, mask=mask, window=window) + (None,)
    _count_branch("bwd", "materialised", window, 1)
    k, v = _kv_per_query_head(q, k, v)
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf,
                   preferred_element_type=jnp.float32) * sm_scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if causal:
        s = jnp.where(_visible_band(*s.shape[-2:], window), s, _NEG_INF)
    if mask is not None:
        s = jnp.where(mask[:, None] != 0, s, _NEG_INF)
    if lse is not None:
        p = jnp.exp(s - lse[..., None])
    else:
        p = jax.nn.softmax(s, axis=-1)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, dof)
    dp = jnp.einsum("bhqd,bhkd->bhqk", dof, vf)
    delta = jnp.sum(dof * out.astype(jnp.float32), axis=-1, keepdims=True)
    ds = p * (dp - delta) * sm_scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, kf).astype(q.dtype)
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf).astype(k.dtype)
    dbias = None
    if bias is not None:
        dbias = _reduce_dbias(
            jnp.sum(ds / sm_scale, axis=2, keepdims=True), bias)
    return (dq, _sum_kv_group(dk, kv_heads),
            _sum_kv_group(dv.astype(v.dtype), kv_heads), dbias, None)


_flash_core.defvjp(
    _flash_fwd, lambda causal, sm_scale, window, res, do: _flash_bwd(
        causal, sm_scale, res, do, window))


@register("flash_attention", aliases=("_contrib_flash_attention",))
def flash_attention(query, key, value, bias=None, mask=None, causal=False,
                    sm_scale=None, window=None):
    """Fused scaled-dot-product attention. query: (B, H, T, D); key:
    (B, Hkv, Tk, D) and value: (B, Hkv, Tk, Dv) with ``H % Hkv == 0``: query
    head ``h`` reads K/V head ``h // (H // Hkv)`` (grouped heads; ``Hkv ==
    H`` is plain multi-head attention) and ``dk`` / ``dv`` are summed over
    the group; Dv = D unless the model says otherwise (latent attention);
    bias: optional additive (B, H|1, 1, Tk) mask (use large negatives to
    mask); mask: optional (B, Tq, Tk) selection, nonzero where a (query,
    key) pair may be attended, shared by the heads of a sequence, with no
    gradient, combined with ``causal`` and ``bias`` (a row that selects
    nothing is the caller's fault); window: optional static integer, under
    ``causal`` with ``Tk >= Tq``: a query sees itself and the ``window - 1``
    keys before it (a window that reaches every key is the plain causal
    call). Returns (B, H, Tq, Dv).

    Inside ``parallel.sequence_scope(mesh, axis, schedule)`` this
    dispatches to a sequence-parallel schedule (ring KV rotation, or
    Ulysses head all-to-all when heads divide and there is no bias) —
    the hook that makes every attention user sequence-parallel without
    model changes. Neither schedule takes a selection mask or grouped
    heads, nor a window."""
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(query.shape[-1]))
    if window is not None:
        window = int(window)
        if window < 1 or not causal or key.shape[2] < query.shape[2]:
            raise MXNetError("flash_attention: window=%d wants causal=True, "
                             "Tk >= Tq and at least the query's own key"
                             % window)
        if window >= key.shape[2]:
            window = None  # every key of the causal past is inside it
    if query.shape[1] % key.shape[1] or key.shape[1] != value.shape[1]:
        raise MXNetError("flash_attention: %d query heads on %d key and %d "
                         "value heads" % (query.shape[1], key.shape[1],
                                          value.shape[1]))
    if mask is not None and tuple(mask.shape) != (
            query.shape[0], query.shape[2], key.shape[2]):
        raise MXNetError("flash_attention: mask %s is not (B, Tq, Tk) = %s"
                         % (tuple(mask.shape), (query.shape[0], query.shape[2],
                                                key.shape[2])))
    from ..parallel.sequence import current_sequence_scope, ring_attention

    scope = current_sequence_scope()
    if scope is not None and query.shape[2] == key.shape[2]:
        # the scope covers sequence-sharded SELF-attention;
        # rectangular attention (cross-attention, Tq=1 decode steps)
        # falls through to the flash kernel untouched
        mesh, seq_axis, schedule = scope
        if jax.process_count() > 1:
            raise MXNetError(
                "sequence_scope's eager dispatch is single-process; on "
                "multi-host meshes call parallel.ring_attention inside "
                "your pjit/shard_map program instead")
        if (mask is not None or window is not None
                or query.shape[1] != key.shape[1]):
            raise MXNetError(
                "sequence_scope: neither schedule takes a selection mask, "
                "a window or grouped heads")
        from ..parallel.sequence import ulysses_attention

        if (schedule == "ulysses" and bias is None
                and query.shape[1] % mesh.shape[seq_axis] == 0):
            out = ulysses_attention(query, key, value, mesh=mesh,
                                    seq_axis=seq_axis,
                                    causal=bool(causal),
                                    sm_scale=float(sm_scale))
        else:  # ring handles biases and any head count
            out = ring_attention(query, key, value, bias=bias, mesh=mesh,
                                 seq_axis=seq_axis, causal=bool(causal),
                                 sm_scale=float(sm_scale))
        # bring the mesh-sharded result back to a single device so it
        # composes with unsharded surrounding ops on the eager path
        # (device_put is traceable; under full-program jit it's just a
        # sharding constraint XLA folds away)
        out = jax.device_put(
            out, jax.sharding.SingleDeviceSharding(
                mesh.devices.flat[0]))
        return out
    return _flash_core(query, key, value, bias, mask, bool(causal),
                       float(sm_scale), window)


# ---------------------------------------------------------------------------
# flash_attention_qkv: attention fed from the fused projection
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _qkv_core(qkv, bias, heads, sm_scale):
    return _qkv_fwd(qkv, bias, heads, sm_scale)[0]


@jax.named_scope("attention")
def _qkv_fwd(qkv, bias, heads, sm_scale):
    """Forward of ``_qkv_core``: the in-place kernel, counted as the kernel
    branch it is. The residuals are ``qkv`` itself (in place of its three
    turned copies), ``out`` and ``lse``."""
    _telemetry.record_flash_fwd("kernel")
    _telemetry.record_flash_layout("fwd", "in_place")
    out, lse = _qkv_forward_pallas(qkv, bias, heads, sm_scale, interpret=False)
    return out, (qkv, bias, out, lse)


@jax.named_scope("attention_bwd")
def _qkv_bwd(heads, sm_scale, res, do):
    """Backward of ``_qkv_core``: one cotangent for ``qkv``, written by the
    kernel."""
    qkv, bias, out, lse = res
    _telemetry.record_flash_bwd("kernel")
    _telemetry.record_flash_layout("bwd", "in_place")
    return _qkv_backward_pallas(qkv, bias, out, lse, do, heads, sm_scale,
                                interpret=False)


_qkv_core.defvjp(_qkv_fwd, _qkv_bwd)


def _in_place(qkv, heads, head_dim, bias, causal):
    """Whether a ``flash_attention_qkv`` call runs the in-place kernels: on
    a TPU, outside a sequence scope, where a head is one plain tile by the
    rule ``_heads_per_step`` states (no ``causal``; ``T`` whole 128-lane
    lengths and one block on both axes, in the forward by the tuning table's
    choice for the shape and in the backward by ``_bwd_blocks``; a grid of
    ``_HEAD_MIN_GRID`` steps or more at one head a step) and whole 128-lane
    blocks hold whole heads: ``head_dim`` 128, or 64 with an even head
    count. The table is asked as ``_flash_fwd`` asks it, and has to say
    ``pallas``."""
    from ..parallel.sequence import current_sequence_scope

    B, T, _ = qkv.shape
    if (causal or not on_tpu() or current_sequence_scope() is not None
            or not (head_dim == _LSE_LANES
                    or (2 * head_dim == _LSE_LANES and heads % 2 == 0))
            or T % _LSE_LANES or B * heads < _HEAD_MIN_GRID
            or _bwd_blocks(T, T) != (T, T)
            or (bias is not None and (
                bias.ndim != 4 or bias.shape[0] != B or bias.shape[1] not in
                (1, heads) or bias.shape[2:] != (1, T)))):
        return False
    cfg = _tuned_config_at((B, heads, T, head_dim), T, heads, str(qkv.dtype),
                           False, None)
    return (cfg.get("backend") == "pallas"
            and min(int(cfg["block_q"]), int(cfg["block_k"])) >= T)


def _heads_major(qkv, heads, head_dim):
    """(B, T, 3 x H x D) -> q, k, v, each (B, H, T, D): the reshape, split
    and transposes ``BERTSelfAttention`` made before this operator was."""
    B, T, _ = qkv.shape
    parts = jnp.split(jnp.reshape(qkv, (B, T, 3, heads, head_dim)), 3, axis=2)
    parts = [jnp.squeeze(x, axis=2) for x in parts]
    return tuple(jnp.transpose(x, (0, 2, 1, 3)) for x in parts)


@register("flash_attention_qkv", aliases=("_contrib_flash_attention_qkv",))
def flash_attention_qkv(qkv, bias=None, num_heads=None, causal=False,
                        sm_scale=None):
    """Self-attention fed from a fused QKV projection. qkv: (B, T, 3 x H x
    D) with ``H = num_heads``: all of q, then k, then v, head ``h`` of each
    at ``[h x D, (h + 1) x D)`` (what ``reshape(qkv, (0, 0, 3, H, D))``
    splits); bias: optional additive (B, H|1, 1, T) key bias. Returns (B, T,
    H x D), heads side by side: what an output projection reads. The TPU
    form of the reference's ``_contrib_interleaved_matmul_selfatt_qk`` /
    ``_valatt`` pair, as one fused kernel each way.

    Array inputs come first and ``num_heads`` is a keyword, as for every
    registered op (a Symbol's positional inputs are Symbols):
    ``F.flash_attention_qkv(qkv, bias, num_heads=H)``.

    Where a head is one plain tile (``_in_place``: BERT's training shapes)
    both flash kernels read ``qkv`` where the projection wrote it and write
    the result, and in the backward ONE cotangent for ``qkv``, where the next
    matmul reads it: no copy of q, k, v, the output or their gradients is
    made. Every other call (``causal``, an odd head count at 64, a length
    that is not whole lanes, a short grid, any CPU run, a sequence scope)
    turns the operands to (B, H, T, D) and is :func:`flash_attention`'s call
    on them. Which of the two a traced call takes is counted
    (``telemetry.flash_layouts()``)."""
    if not num_heads or qkv.ndim != 3 or qkv.shape[2] % (3 * num_heads):
        raise MXNetError("flash_attention_qkv: qkv %s is not (B, T, 3 x "
                         "num_heads x D) at num_heads=%r"
                         % (tuple(qkv.shape), num_heads))
    heads = int(num_heads)
    head_dim = qkv.shape[2] // (3 * heads)
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(head_dim))
    if _in_place(qkv, heads, head_dim, bias, causal):
        return _qkv_core(qkv, bias, heads, float(sm_scale))
    q, k, v = _heads_major(qkv, heads, head_dim)
    out = flash_attention(q, k, v, bias, causal=causal, sm_scale=sm_scale)
    B, T = qkv.shape[:2]
    return jnp.reshape(jnp.transpose(out, (0, 2, 1, 3)), (B, T, -1))


@register("attention_padding_bias", differentiable=False)
def make_padding_bias(valid_length, max_len=None, dtype="float32"):
    """(B,) lengths → additive (B, 1, 1, T) bias: 0 for valid, -1e30 after.
    ``max_len`` (the key sequence length) is required."""
    if not max_len:
        raise ValueError("attention_padding_bias requires max_len= (the "
                         "key sequence length)")
    idx = jnp.arange(max_len)[None, :]
    mask = idx < valid_length.astype(jnp.int32)[:, None]
    bias = jnp.where(mask, 0.0, _NEG_INF).astype(jnp.dtype(dtype))
    return bias[:, None, None, :]


# ---------------------------------------------------------------------------
# ragged / paged decode attention (serving; PAPERS.md arXiv 2604.15464)
# ---------------------------------------------------------------------------
def ragged_attention_reference(q, k, v, valid_length, sm_scale=None):
    """Dense masked reference for ragged decode — the correctness oracle
    for :func:`ragged_paged_attention`.

    One query token per sequence attends its own prefix: ``q`` is
    ``(B, H, D)`` (or ``(B, H, 1, D)``), ``k``/``v`` are dense
    ``(B, H, Tmax, D)``, ``valid_length`` is ``(B,)`` — sequence ``b``
    sees exactly keys ``[0, valid_length[b])``; everything after is
    masked with the same -1e30 bias ``make_padding_bias`` produces, so
    the paged kernel and this path share one masking definition."""
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, :, None, :]
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    bias = make_padding_bias(valid_length, max_len=k.shape[2],
                             dtype="float32")
    out = _attention_reference(q, k, v, bias, False, float(sm_scale))
    return out[:, :, 0] if squeeze else out


def _paged_gather_reference(q, k_pages, v_pages, page_table, context_lens,
                            sm_scale, k_scales=None, v_scales=None):
    """XLA path: gather the page table into dense K/V and run the masked
    reference. Correct everywhere (the CPU/serving-test path) and the
    per-shape alternative the tuning table may prefer on-chip for short
    contexts, where one fused gather+softmax beats the kernel's
    page-at-a-time grid.

    Quantized pools (int8 pages + per-(position, head) amax planes)
    dequantize AFTER the gather — only the sequence's own pages pay the
    int8->f32 convert, never the whole pool."""
    B = q.shape[0]
    P, S, H, D = k_pages.shape
    max_pages = page_table.shape[1]
    flat = page_table.reshape(-1)
    kg = k_pages[flat].reshape(B, max_pages, S, H, D)
    vg = v_pages[flat].reshape(B, max_pages, S, H, D)
    if k_scales is not None:
        kg = kg.astype(jnp.float32) * (
            k_scales[flat].reshape(B, max_pages, S, H)
            * (1.0 / 127.0))[..., None]
        vg = vg.astype(jnp.float32) * (
            v_scales[flat].reshape(B, max_pages, S, H)
            * (1.0 / 127.0))[..., None]
    k = jnp.transpose(kg.reshape(B, max_pages * S, H, D), (0, 2, 1, 3))
    v = jnp.transpose(vg.reshape(B, max_pages * S, H, D), (0, 2, 1, 3))
    return ragged_attention_reference(q, k, v, context_lens, sm_scale)


def _paged_decode_kernel(pt_ref, cl_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, page_size, block_h,
                         sm_scale):
    """One (sequence, head-block, page) grid step of the ragged paged
    decode kernel. The page axis is the innermost (sequential) grid
    dimension, so the online-softmax state rides VMEM scratch across a
    sequence's pages — the flash recipe with the KV stream indirected
    through the page table (pt_ref/cl_ref are scalar-prefetch refs; the
    BlockSpec index map already used pt_ref to DMA this step's page)."""
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    j = pl.program_id(2)
    npages = pl.num_programs(2)
    sm_scale = jnp.float32(sm_scale)
    neg_inf = jnp.float32(_NEG_INF)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, neg_inf, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q = q_ref[0, 0].astype(jnp.float32)       # (block_h, D)
    k = k_ref[0, :, 0].astype(jnp.float32)    # (page_size, block_h, D)
    v = v_ref[0, :, 0].astype(jnp.float32)
    length = cl_ref[b]
    # tokens this page covers; everything at/after the sequence length
    # (ragged tail, pages past the last used one) masks to -inf
    col = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (block_h, page_size), 1)
    valid = col < length

    # per-head matvecs, statically unrolled over the head block (the
    # head-batched dot_general has no Mosaic lowering; block_h is the
    # tuned unroll width)
    rows = [jax.lax.dot_general(q[h:h + 1], k[:, h, :],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for h in range(block_h)]
    s = jnp.concatenate(rows, axis=0) * sm_scale   # (block_h, page_size)
    s = jnp.where(valid, s, neg_inf)

    m_prev = m_scr[...]                        # (block_h, LANES), lane-bcast
    l_prev = l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.broadcast_to(
        jnp.max(s, axis=1, keepdims=True), m_prev.shape))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, :1])
    l_new = l_prev * alpha + jnp.broadcast_to(
        jnp.sum(p, axis=1, keepdims=True), l_prev.shape)
    pv_rows = [jax.lax.dot_general(p[h:h + 1], v[:, h, :],
                                   (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
               for h in range(block_h)]
    m_scr[...] = m_new
    l_scr[...] = l_new
    acc_scr[...] = acc_scr[...] * alpha[:, :1] \
        + jnp.concatenate(pv_rows, axis=0)

    @pl.when(j == npages - 1)
    def _finish():
        l = jnp.maximum(l_scr[...][:, :1], jnp.float32(1e-30))
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def _paged_decode_pallas(q, k_pages, v_pages, page_table, context_lens,
                         sm_scale, block_h, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, D = q.shape
    P, S, Hk, Dk = k_pages.shape
    max_pages = page_table.shape[1]
    block_h = max(1, min(int(block_h), H))
    while H % block_h:  # candidates are divisors; a caller's value may not be
        block_h -= 1
    page_table = page_table.astype(jnp.int32)
    context_lens = context_lens.astype(jnp.int32)

    # head blocks ride their own axis: a (block_h, D) tile of a (H, D)
    # array is legal on the chip only when block_h == H or block_h % 8
    # == 0, but a whole trailing (block_h, D) pair always is — so q/out
    # become (B, H/bh, bh, D) and the pools (P, S, H/bh, bh, D) (free
    # reshapes) and every divisor of H is a block the compiler takes
    nhb = H // block_h
    qb = q.reshape(B, nhb, block_h, D)
    kb = k_pages.reshape(P, S, nhb, block_h, D)
    vb = v_pages.reshape(P, S, nhb, block_h, D)

    z = np.int32(0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, nhb, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, block_h, D),
                         lambda b, hb, j, pt, cl: (b, hb, z, z)),
            # page indirection: the page table names which pool page this
            # grid step streams in (a padded slot reads page 0, fully
            # masked by the ragged length check)
            pl.BlockSpec((1, S, 1, block_h, D),
                         lambda b, hb, j, pt, cl: (pt[b, j], z, hb, z, z)),
            pl.BlockSpec((1, S, 1, block_h, D),
                         lambda b, hb, j, pt, cl: (pt[b, j], z, hb, z, z)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_h, D),
                               lambda b, hb, j, pt, cl: (b, hb, z, z)),
        scratch_shapes=[
            pltpu.VMEM((block_h, _LSE_LANES), jnp.float32),
            pltpu.VMEM((block_h, _LSE_LANES), jnp.float32),
            pltpu.VMEM((block_h, D), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_decode_kernel, page_size=S,
                               block_h=block_h, sm_scale=sm_scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, nhb, block_h, D), q.dtype),
        interpret=interpret,
        name="paged_decode",
    )(page_table, context_lens, qb, kb, vb)
    return out.reshape(B, H, D)


def _record_paged_signature(q, k_pages, page_table, sm_scale,
                            quantized=False):
    """Remember this decode dispatch's shape signature so a fresh
    serving replica's tuning.warmup() can AOT-compile the paged
    attention program before the first request lands."""
    try:
        from .. import tuning

        tuning.record_signature("paged_attention", {
            "q_shape": list(q.shape), "pool_shape": list(k_pages.shape),
            "max_pages": int(page_table.shape[1]),
            "pool_dtype": str(k_pages.dtype),
            "quantized": bool(quantized),
            "dtype": str(q.dtype), "sm_scale": float(sm_scale)})
    except Exception:  # noqa: BLE001 — bookkeeping must not fail the op
        pass


@register("ragged_paged_attention", differentiable=False)
def ragged_paged_attention(query, k_pages, v_pages, page_table,
                           context_lens, sm_scale=None, interpret=None,
                           k_scales=None, v_scales=None):
    """Decode-time attention over a paged KV cache — one query token per
    sequence gathers its K/V prefix through a page table (PAPERS.md
    arXiv 2604.15464; the serving sibling of :func:`flash_attention`).

    ``query``: (B, H, D) — this step's single token per sequence.
    ``k_pages``/``v_pages``: (num_pages, page_size, H, D) device pools.
    ``page_table``: (B, max_pages) int32 — pool page ids per sequence,
    in order; padded slots may repeat any valid page (they are masked).
    ``context_lens``: (B,) int32 — tokens of live prefix per sequence
    (ragged: any mix of lengths, including 1). Returns (B, H, D).

    Backend choice and the head-block config come from the tuning table
    (``tuning.resolve_paged``), exactly like the flash kernel's blocks;
    ``interpret=True`` forces the Pallas kernel in interpret mode (the
    CPU parity path tests use).

    ``k_scales``/``v_scales`` — (num_pages, page_size, H) per-row amax
    planes — mark the pools int8-quantized: the gather fallback
    dequantizes after the page gather. The Pallas kernel has no
    quantized lowering yet, so quantized pools always take the XLA
    path (the tuning-table backend choice applies to f32 pools only)."""
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(query.shape[-1]))
    sm_scale = float(sm_scale)
    _record_paged_signature(query, k_pages, page_table, sm_scale,
                            quantized=k_scales is not None)
    if k_scales is not None:
        return _paged_gather_reference(query, k_pages, v_pages,
                                       page_table, context_lens, sm_scale,
                                       k_scales, v_scales)
    from .. import tuning

    cfg = tuning.resolve_paged(
        query.shape, k_pages.shape[1], page_table.shape[1],
        str(query.dtype))
    if interpret:
        return _paged_decode_pallas(query, k_pages, v_pages, page_table,
                                    context_lens, sm_scale,
                                    int(cfg.get("block_h", 1)),
                                    interpret=True)
    if cfg.get("backend") == "pallas" and on_tpu():
        return _paged_decode_pallas(query, k_pages, v_pages, page_table,
                                    context_lens, sm_scale,
                                    int(cfg.get("block_h", 1)),
                                    interpret=False)
    return _paged_gather_reference(query, k_pages, v_pages, page_table,
                                   context_lens, sm_scale)
