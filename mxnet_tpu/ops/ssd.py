"""State-space scan of Mamba-2's mixer (Dao and Gu, "Transformers are SSMs",
arXiv:2405.21060: the SSD recurrence and its chunked form, Listing 1;
transformers' ``GraniteMoeHybridMambaLayer``).

One pure function, :func:`ssd_scan`, on ``x`` (B, T, H, P), ``dt`` (B, T, H)
before its bias, ``A_log``, ``D``, ``dt_bias`` (H,) and ``B``, ``C``
(B, T, G, N), a group of H / G heads sharing one B and C. A head's state
``S`` is a (P, N) matrix, zero before the sequence::

    dt_t = softplus(dt_t + dt_bias)            A = -exp(A_log)
    S_t  = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T
    y_t  = S_t C_t + D x_t

computed a chunk of ``chunk`` tokens at a time: with ``cum_i`` the running
sum of ``dt A`` inside a chunk,

1. the scores ``C_i . B_j`` of a chunk's pairs, a group;
2. under the decay mask ``exp(cum_i - cum_j)`` for ``i >= j``, their product
   with ``dt_j x_j``: what the chunk's own tokens give each other;
3. each chunk's closing state from its own tokens, ``sum_j exp(cum_last -
   cum_j) dt_j x_j B_j^T``, carried from chunk to chunk by a ``lax.scan``
   (``S_open' = exp(cum_last) S_open + closing``);
4. each token's read ``exp(cum_i) C_i . S_open`` of the state its chunk began
   with.

``softplus``, the decays, the running sums, the masks and the states are
float32; the operands of the four products are in ``x``'s type and accumulate
in float32; the result is in ``x``'s type. T is padded to whole chunks inside
(``dt = 0`` and ``x = 0`` change nothing); a sequence shorter than a chunk is
one chunk of its own length.

Two branches compute that, one algorithm at one precision; what differs is
where a chunk's (chunk x chunk) tiles live. Where
``ssd_pallas.kernel_takes`` accepts the call (a TPU, bfloat16 or float32, a
chunk of whole lane tiles, P and N whole tiles, groups of whole blocks of
heads) both halves are that module's kernels, ``ssd_chunk_fwd`` and
``ssd_chunk_bwd``: scores, mask and masked scores stay in VMEM, the carry
rides in scratch over the grid's chunk axis, and XLA keeps only ``softplus``,
the running sums and, after the backward, the sums' reverse walk and the
per-head sums, all on (B, H, T) float32 rows. Every other call (the CPU, a
chunk of 8 in the tests, a float32 reference's shapes that are no whole
tiles) is the ``jax.numpy`` formula here (``_scan`` / ``_scan_grads``), in
which a float32 (chunks x heads x chunk x chunk) tensor never leaves the
fusion that makes it: the mask is built where it multiplies the scores and
stored in the operands' type, and the backward's per-head product of the same
shape is stored in that type too.

The backward is the op's own (``jax.custom_vjp``): the forward keeps its
inputs and every chunk's opening state, the backward builds the masks again,
walks the chunks' carry in reverse and returns the gradient of every input.
With ``dx^`` the gradient of ``dt x``, the running sums' gradient needs no
pair of its own: a token's row of the masked products sums to ``dy_i . (y_i -
D x_i)`` and its column to ``dt_j x_j . dx^_j``.

The two halves run under the scopes ``ssd`` / ``ssd_bwd``, kernels and all,
and every traced call is counted by the branch it took
(``telemetry.ssd_branches()``: ``kernel`` or ``xla``).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .. import telemetry as _telemetry
from ..base import MXNetError
from . import ssd_pallas as _kernels
from .registry import register

F32 = jnp.float32


def _dot(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=F32)


def _decays(dt, A_log, dt_bias, chunk, groups):
    """float32, by chunk: ``dt`` after its bias and softplus (b, c, q, g, r),
    the running sum of ``dt A`` inside each chunk, and ``A`` (g, r). Rows of
    padding hold ``dt = 0``."""
    shape = (groups, dt.shape[-1] // groups)
    dt = jax.nn.softplus(dt.astype(F32) + dt_bias.astype(F32))
    dt = _by_chunk(dt, chunk, groups)
    a = -jnp.exp(A_log.astype(F32)).reshape(shape)
    return dt, jnp.cumsum(dt * a, axis=2), a


def _mask(cum):
    """exp(cum_i - cum_j) for i >= j and 0 above the diagonal, (b, c, g, r, i,
    j) from ``cum`` (b, c, q, g, r)."""
    rows = jnp.moveaxis(cum, 2, -1)
    q = rows.shape[-1]
    lower = jnp.tril(jnp.ones((q, q), bool))
    return jnp.exp(jnp.where(lower, rows[..., :, None] - rows[..., None, :], -jnp.inf))


def _carry(step, closing, reverse=False):
    """The chunks' states (b, c, g, r, p, n) BEFORE each chunk's own ``closing``
    is added, walking the chunks in order (or in reverse): ``s' = step * s +
    closing``, zero before the first."""
    def one(s, xs):
        decay, add = xs
        return decay[..., None, None] * s + add, s

    xs = (jnp.moveaxis(step, 1, 0), jnp.moveaxis(closing, 1, 0))
    _, before = jax.lax.scan(one, jnp.zeros_like(closing[:, 0]), xs, reverse=reverse)
    return jnp.moveaxis(before, 0, 1)


def _by_chunk(z, chunk, groups=None):
    """(b, t, ...) padded to whole chunks -> (b, c, q, ...); with ``groups``
    the heads (b, t, h, ...) as (g, r): a group's r heads side by side."""
    if groups is not None:
        z = z.reshape(z.shape[:2] + (groups, z.shape[2] // groups) + z.shape[3:])
    z = jnp.pad(z, ((0, 0), (0, -z.shape[1] % chunk)) + ((0, 0),) * (z.ndim - 2))
    return z.reshape((z.shape[0], z.shape[1] // chunk, chunk) + z.shape[2:])


def _within(x, dt, cum, B, C):
    """What both passes build alike: the masked scores in the operands' type
    (b, c, g, r, i, j), ``dt x`` in it, and ``dt x`` decayed to its chunk's
    end, the closing state's operand."""
    kind = x.dtype
    scores = _dot("bcign,bcjgn->bcgij", C, B)
    masked = (scores[:, :, :, None] * _mask(cum)).astype(kind)
    xdt = x.astype(F32) * dt[..., None]
    to_end = jnp.exp(cum[:, :, -1:] - cum)
    return masked, xdt.astype(kind), (xdt * to_end[..., None]).astype(kind), to_end


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ssd_core(chunk, x, dt, A_log, B, C, D, dt_bias):
    return _ssd_fwd(chunk, x, dt, A_log, B, C, D, dt_bias)[0]


@jax.named_scope("ssd")
def _ssd_fwd(chunk, x, dt, A_log, B, C, D, dt_bias):
    kernel = _kernels.kernel_takes(x.shape, B.shape, chunk, x.dtype)
    _telemetry.record_ssd("kernel" if kernel else "xla")
    y, opening = (_kernels.scan if kernel else _scan)(chunk, x, dt, A_log, B, C, D, dt_bias)
    return y, (x, dt, A_log, B, C, D, dt_bias, opening)


def _scan(chunk, x, dt, A_log, B, C, D, dt_bias):
    """The formula's forward: the result and every chunk's opening state."""
    t, g = x.shape[1], B.shape[2]
    xc, Bc, Cc = _by_chunk(x, chunk, g), _by_chunk(B, chunk), _by_chunk(C, chunk)
    dtc, cum, _ = _decays(dt, A_log, dt_bias, chunk, g)
    masked, xdt, x_end, _ = _within(xc, dtc, cum, Bc, Cc)
    y = _dot("bcgrij,bcjgrp->bcigrp", masked, xdt)
    closing = _dot("bcjgrp,bcjgn->bcgrpn", x_end, Bc)
    opening = _carry(jnp.exp(cum[:, :, -1]), closing)
    y = y + _dot("bcign,bcgrpn->bcigrp", Cc, opening.astype(x.dtype)) \
        * jnp.exp(cum)[..., None]
    y = y + D.astype(F32).reshape(dtc.shape[-2:])[:, :, None] * xc.astype(F32)
    y = y.reshape((x.shape[0], -1) + x.shape[2:])[:, :t]
    return y.astype(x.dtype), opening


@jax.named_scope("ssd_bwd")
def _ssd_bwd(chunk, res, dy):
    x, B = res[0], res[3]
    kernel = _kernels.kernel_takes(x.shape, B.shape, chunk, x.dtype)
    return (_kernels.scan_grads if kernel else _scan_grads)(chunk, *res, dy)


def _scan_grads(chunk, x, dt, A_log, B, C, D, dt_bias, opening, dy):
    """The formula's backward: the gradient of every input under ``dy``."""
    kind, (b, t, h, _), g = x.dtype, x.shape, B.shape[2]
    r = h // g
    xc, dyc = _by_chunk(x, chunk, g), _by_chunk(dy, chunk, g)
    Bc, Cc = _by_chunk(B, chunk), _by_chunk(C, chunk)
    dtc, cum, a = _decays(dt, A_log, dt_bias, chunk, g)
    masked, xdt, x_end, to_end = _within(xc, dtc, cum, Bc, Cc)
    x32, dy32, d = xc.astype(F32), dyc.astype(F32), D.astype(F32).reshape(g, r, 1)
    from_start = jnp.exp(cum)
    state = opening.astype(kind)
    # the forward's result without D x, for the running sums' rows
    y = _dot("bcgrij,bcjgrp->bcigrp", masked, xdt) \
        + _dot("bcign,bcgrpn->bcigrp", Cc, state) * from_start[..., None]
    # the reverse carry: what reaches each chunk's closing state from the
    # reads of the chunks after it
    dy_start = (dy32 * from_start[..., None]).astype(kind)
    last = jnp.exp(cum[:, :, -1])
    dclosing = _carry(last, _dot("bcigrp,bcign->bcgrpn", dy_start, Cc), reverse=True)
    dstate = dclosing.astype(kind)
    # dx^: the gradient of dt x, from its chunk's tokens and from the state
    dx_within = _dot("bcgrij,bcigrp->bcjgrp", masked, dyc)
    dx_state = _dot("bcgrpn,bcjgn->bcjgrp", dstate, Bc)
    dxdt = dx_within + dx_state * to_end[..., None]
    dx = dtc[..., None] * dxdt + d * dy32
    ddt = jnp.sum(x32 * dxdt, axis=-1)
    # the running sums: rows less columns, and at a chunk's last token what
    # its closing state (the next chunk's opening one) carries on. Rows and
    # columns sum the same products, so they take the same rounded operands:
    # what is left after they cancel is the gradient, not the rounding.
    dcum = jnp.sum(dy32 * y, axis=-1) - jnp.sum(xdt.astype(F32) * dx_within, axis=-1) \
        - jnp.sum(x_end.astype(F32) * dx_state, axis=-1)
    closed = jnp.concatenate([opening[:, 1:], jnp.zeros_like(opening[:, :1])], axis=1)
    dcum = dcum.at[:, :, -1].add(jnp.sum(dstate.astype(F32) * closed, axis=(-2, -1)))
    da = jnp.flip(jnp.cumsum(jnp.flip(dcum, 2), axis=2), 2)
    ddt = ddt + a * da
    dA_log = jnp.sum(dtc * da, axis=(0, 1, 2)) * a
    # B and C: the pairs' scores, a group's heads summed under their masks
    pairs = _dot("bcigrp,bcjgrp->bcgrij", dyc, xdt).astype(kind)
    dscores = jnp.sum(pairs.astype(F32) * _mask(cum), axis=3).astype(kind)
    dC = _dot("bcgij,bcjgn->bcign", dscores, Bc) \
        + _dot("bcigrp,bcgrpn->bcign", dy_start, state)
    dB = _dot("bcgij,bcign->bcjgn", dscores, Cc) \
        + _dot("bcjgrp,bcgrpn->bcjgn", x_end, dstate)
    dD = jnp.sum(dy32 * x32, axis=(0, 1, 2, 5))

    def rows(z, shape):  # (b, c, q, ...) -> (b, t, ...) without the padding
        return z.reshape((b, -1) + z.shape[3:])[:, :t].reshape(shape)

    ddt = rows(ddt, dt.shape) * jax.nn.sigmoid(
        dt.astype(F32) + dt_bias.astype(F32))  # softplus' derivative
    return (rows(dx, x.shape).astype(kind), ddt.astype(dt.dtype),
            dA_log.reshape(h).astype(A_log.dtype), rows(dB, B.shape).astype(B.dtype),
            rows(dC, C.shape).astype(C.dtype), dD.reshape(h).astype(D.dtype),
            jnp.sum(ddt, axis=(0, 1)).astype(dt_bias.dtype))


_ssd_core.defvjp(_ssd_fwd, _ssd_bwd)


@register("ssd_scan")
def ssd_scan(x, dt, A_log, B, C, D, dt_bias, chunk=256):
    """``y_t = S_t C_t + D x_t`` over ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
    B_t^T`` in the chunked form, ``chunk`` tokens at a time: see the module's
    docstring. ``x`` (B, T, H, P), ``dt`` (B, T, H), ``A_log`` / ``D`` /
    ``dt_bias`` (H,), ``B`` / ``C`` (B, T, G, N). Returns (B, T, H, P)."""
    if x.ndim != 4 or B.ndim != 4 or B.shape != C.shape \
            or dt.shape != x.shape[:3] or B.shape[:2] != x.shape[:2]:
        raise MXNetError("ssd_scan: x %s, dt %s, B %s, C %s are not (B, T, H, P), "
                         "(B, T, H), (B, T, G, N) twice"
                         % tuple(tuple(z.shape) for z in (x, dt, B, C)))
    h = x.shape[2]
    if h % B.shape[2]:
        raise MXNetError("ssd_scan: %d heads are not whole groups of %d"
                         % (h, B.shape[2]))
    if not all(tuple(z.shape) == (h,) for z in (A_log, D, dt_bias)):
        raise MXNetError("ssd_scan: A_log, D and dt_bias are one number a head "
                         "(%d), not %s" % (h, [tuple(z.shape) for z in (A_log, D, dt_bias)]))
    chunk = int(chunk)
    if chunk < 1:
        raise MXNetError("ssd_scan: chunk %d" % chunk)
    return _ssd_core(min(chunk, x.shape[1]), x, dt, A_log, B, C, D, dt_bias)
