"""Gated delta rule with a decay a channel, the recurrence of Kimi Linear's
delta attention (Kimi Team, "Kimi Linear", arXiv:2510.26692, section 3 and its
``KimiDeltaAttention``; the delta rule's chunked form is Yang et al.,
arXiv:2406.06484).

One pure function, :func:`gated_delta_rule`, on ``q``, ``k`` (B, T, H, K),
``v`` (B, T, H, V), ``g`` (B, T, H, K) the log-decays (<= 0, one a channel of
the key) and ``beta`` (B, T, H). A head's state ``S`` is a (K, V) matrix, zero
before the sequence::

    S'  = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

The op first takes ``q = q / |q|_2 * K ** -0.5`` and ``k = k / |k|_2`` a head,
in float32 under ``sqrt(sum of squares + 1e-6)``. ``beta`` in (0, 2) puts the
eigenvalue ``1 - beta`` of a step in (-1, 1) (``allow_neg_eigval``): that is
the caller's to make. :func:`kda_log_decay` makes ``g`` from the decay
projection's result: ``-exp(A_log) * softplus(f + dt_bias)`` in float32.

Computed a chunk of ``chunk`` tokens at a time. With ``G_i`` the running sum of
``g`` inside a chunk (a channel) and ``S`` the state the chunk opens on:

1. the pairs ``A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)`` (i > j) and
   ``P_ij = sum_c q_ic k_jc exp(G_ic - G_jc)`` (i >= j);
2. ``T = (I + A)^-1``, unit lower triangular; ``W = T (beta k exp(G))``,
   ``U = T (beta v)``;
3. the carry, a ``lax.scan`` over the chunks: ``V' = U - W S``,
   ``S_close = Diag(exp(G_last)) S + sum_j (k_j exp(G_last - G_j)) V'_j^T``;
4. ``o_i = (q_i exp(G_i)) S + sum_{j <= i} P_ij V'_j``.

``exp(G_i - G_j)`` is a product of two factors, and a decay a channel means
``exp(-G_j)`` alone overflows float32 within a chunk (``g`` of -1.6 a token
reaches -100 in 64). So the pairs are built a sub-block of rows at a time (16
tokens, or the largest divisor of ``chunk`` below it; the published kernels'
way): a row of sub-block ``a`` carries ``exp(G_i - R_a)``, ``R_a`` the running
sum where its sub-block opens, and a column ``exp(R_a - G_j)``, which is below
1 for every earlier sub-block's token and raises e to no more than one
sub-block's own decays for the tokens of sub-block ``a`` itself (a sub-block
whose decays sum under -87 in a channel, 5.4 a token, is past the op). No
exponent of a longer running sum is ever positive. The solve is exact forward
substitution inside a sub-block's (16 x 16) diagonal block and a nilpotent
series over the blocks (``(I + M)^-1 = (I - M)(I + M^2)...``, ``M`` strictly
block lower triangular), at float32 "highest".

The running sums, the decays, the solve and the states are float32; the
operands of every other product are in ``q``'s type and accumulate in float32;
the result is in ``q``'s type. T is padded to whole chunks inside (``g = 0``,
``beta = 0``, ``k = v = 0`` change nothing); a sequence shorter than a chunk is
one chunk, of its own length in whole sub-blocks.

The backward is the op's own (``jax.custom_vjp``): the forward keeps its inputs
and every chunk's opening state (float32), the backward builds steps 1 and 2
again, walks the carry in reverse with the transposes of its two products, and
takes the gradient of steps 1 and 2, which no chunk shares with another, from
``jax.vjp`` of the very function the forward ran: the rounding of each operand
is then the same in both passes. No (chunks x heads x chunk x chunk x K) tensor
lives between the passes. The other way, ``jax.checkpoint`` of the whole
forward under plain autodiff, runs the carry a second time and compiled to
more bytes (0.775 against 0.741 GB of temporaries for the described v5e:
PERF.md, Findings, PR 47); it is not built here.

Two branches compute that, one algorithm at one precision; what differs is
where a chunk's work lives. Where ``delta_rule_pallas.kernel_takes`` accepts
the call (a TPU, bfloat16 or float32, K and V whole lane tiles, a chunk of
whole sub-blocks of 16 and whole sublane tiles, heads in whole blocks of 8)
both halves are that module's kernels, ``kda_chunk_fwd`` and
``kda_chunk_bwd``: a grid step holds a chunk of a block of heads, the running
sums, lifts, pairs, ``T``, ``W``, ``U`` and ``V'`` stay in VMEM, the carry rides
in scratch over the grid's chunk axis both ways, and the forward keeps, beside
each chunk's opening state (transposed, (V, K)), its ``T`` (float32, (chunk,
chunk)) for the backward to read and not to solve again. XLA keeps the pads and
the reshapes. Every other call (the CPU, K of 16 in the tests, a chunk of 8 or
20, a float32 reference's shapes that are no whole tiles) is the ``jax.numpy``
formula here (``_forward`` / ``_grads``).

The two halves run under the scopes ``delta_rule`` / ``delta_rule_bwd``,
kernels and all, and every traced call is counted by the branch it took
(``telemetry.delta_rule_branches()``: ``kernel`` or ``xla``).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from .. import telemetry as _telemetry
from ..base import MXNetError
from . import delta_rule_pallas as _kernels
from .registry import register
from .ssd import _by_chunk  # (b, t, ...) padded to whole chunks -> (b, n, c, ...)

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
SUB_BLOCK = 16
_NORM_EPS = 1e-6


def _dot(spec, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=F32)


def _sub_block(chunk):
    return math.gcd(chunk, SUB_BLOCK)


def _unit(x, scale):
    x = x.astype(F32)
    return x * (jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _NORM_EPS) * scale)


def _inverse(a, sub):
    """``(I + a)^-1`` of strictly lower triangular ``a`` (..., c, c), float32:
    forward substitution inside each (sub x sub) diagonal block, then the
    series over the blocks."""
    c = a.shape[-1]
    n = c // sub
    eye = jnp.eye(sub, dtype=F32)
    blocks = a.reshape(a.shape[:-2] + (n, sub, n, sub))
    diag = jnp.stack([blocks[..., i, :, i, :] for i in range(n)], axis=-3)
    rows = [jnp.broadcast_to(eye[0], diag.shape[:-2] + (sub,))]
    for i in range(1, sub):
        before = jnp.stack(rows, axis=-2)  # (..., i, sub)
        rows.append(eye[i] - jnp.sum(diag[..., i, :i, None] * before, axis=-2))
    inv = jnp.stack(rows, axis=-2)  # (..., n, sub, sub)
    if n == 1:
        return inv[..., 0, :, :]
    whole = jnp.einsum("...aij,ab->...aibj", inv, jnp.eye(n, dtype=F32)).reshape(a.shape)
    same = jnp.arange(c) // sub
    off = jnp.where(same[:, None] == same[None, :], 0.0, a)
    mm = partial(jnp.matmul, precision=HI)
    m = mm(whole, off)
    unit = jnp.eye(c, dtype=F32)
    series, power, reach = unit - m, m, 2
    while reach < n:  # (I + M)^-1 = (I - M)(I + M^2)(I + M^4)...: M^n = 0
        power = mm(power, power)
        series = mm(series, unit + power)
        reach *= 2
    return mm(series, whole)


def _within(q, k, v, g, beta, sub):
    """Steps 1 and 2 of the module's docstring for every chunk at once, from the
    inputs by chunk (b, n, c, h, .), heads before tokens from here on (every
    later product then has its batch axes leading, the one order of the carry's
    products that XLA's CPU backend runs in bfloat16): ``q
    exp(G)`` and ``k exp(G_last - G)`` (b, n, h, c, K) and ``P`` (b, n, h, c, c)
    and ``W`` (b, n, h, c, K) in the operands' type, ``U`` (b, n, h, c, V) and
    ``exp(G_last)`` (b, n, h, K) float32."""
    kind = q.dtype
    b, n, c, h, _ = q.shape
    q32, k32 = _unit(q, q.shape[-1] ** -0.5), _unit(k, 1.0)
    beta = beta.astype(F32)[..., None]
    run = jnp.cumsum(g.astype(F32), axis=2)
    blocks = c // sub

    def by_sub(z):  # (b, n, c, h, K) -> (b, n, a, i, h, K)
        return z.reshape((b, n, blocks, sub) + z.shape[3:])

    def heads_first(z):  # (b, n, c, h, .) -> (b, n, h, c, .)
        return jnp.moveaxis(z, 3, 2)

    subs = by_sub(run)
    opens = jnp.concatenate([jnp.zeros_like(subs[:, :, :1, -1]), subs[:, :, :-1, -1]],
                            axis=2)  # R_a: (b, n, a, h, K)
    row = jnp.exp(subs - opens[:, :, :, None])
    q_row, k_row = (by_sub(q32) * row).astype(kind), (by_sub(k32) * row).astype(kind)
    # a column j under row sub-block a: exp(R_a - G_j) up to the sub-block's
    # own last token, nothing after it
    reach = jnp.arange(c)[None, :] < (jnp.arange(blocks)[:, None] + 1) * sub
    lift = jnp.where(reach[None, None, :, :, None, None],
                     opens[:, :, :, None] - run[:, :, None], -jnp.inf)
    k_col = (k32[:, :, None] * jnp.exp(lift)).astype(kind)  # (b, n, a, j, h, K)
    akk = _dot("bnaihk,bnajhk->bnhaij", k_row, k_col).reshape(b, n, h, c, c)
    aqk = _dot("bnaihk,bnajhk->bnhaij", q_row, k_col).reshape(b, n, h, c, c)
    lower = jnp.tril(jnp.ones((c, c), bool))
    a = jnp.where(jnp.tril(lower, -1), akk * heads_first(beta), 0.0)
    p = jnp.where(lower, aqk, 0.0).astype(kind)
    t = _inverse(a, sub).astype(kind)
    from_open = jnp.exp(run)
    w = _dot("bnhij,bnhjk->bnhik", t,
             heads_first(k32 * from_open * beta).astype(kind)).astype(kind)
    u = _dot("bnhij,bnhjv->bnhiv", t, heads_first(v.astype(F32) * beta).astype(kind))
    last = run[:, :, -1]
    k_end = heads_first(k32 * jnp.exp(last[:, :, None] - run)).astype(kind)
    return heads_first(q32 * from_open).astype(kind), k_end, p, w, u, jnp.exp(last)


def _carry(w, u, k_end, closing):
    """Step 3: every chunk's opening state (b, n, h, K, V) float32 and its
    ``V'`` (b, n, h, c, V) in the operands' type."""
    kind = w.dtype

    def one(s, xs):
        w_n, u_n, k_n, decay = xs
        new = (u_n - _dot("bhik,bhkv->bhiv", w_n, s.astype(kind))).astype(kind)
        return decay[..., None] * s + _dot("bhik,bhiv->bhkv", k_n, new), (s, new)

    first = jnp.zeros(w.shape[:1] + w.shape[2:3] + w.shape[4:] + u.shape[4:], F32)
    _, (opening, new) = jax.lax.scan(
        one, first, tuple(jnp.moveaxis(z, 1, 0) for z in (w, u, k_end, closing)))
    return jnp.moveaxis(opening, 0, 1), jnp.moveaxis(new, 0, 1)


def _read(q_open, p, opening, new):
    """Step 4, (b, n, h, c, V) float32."""
    return _dot("bnhik,bnhkv->bnhiv", q_open, opening.astype(q_open.dtype)) \
        + _dot("bnhij,bnhjv->bnhiv", p, new)


def _rows(z, t):
    """(b, n, h, c, .) -> (b, t, h, .) without the padding."""
    z = jnp.moveaxis(z, 2, 3)
    return z.reshape((z.shape[0], -1) + z.shape[3:])[:, :t]


def _forward(chunk, q, k, v, g, beta):
    """The whole op in plain ``jax.numpy``: the result and the opening states."""
    parts = [_by_chunk(z, chunk) for z in (q, k, v, g, beta)]
    q_open, k_end, p, w, u, closing = _within(*parts, _sub_block(chunk))
    opening, new = _carry(w, u, k_end, closing)
    o = _read(q_open, p, opening, new)
    return _rows(o, q.shape[1]).astype(q.dtype), opening


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _delta_core(chunk, q, k, v, g, beta):
    return _delta_fwd(chunk, q, k, v, g, beta)[0]


@jax.named_scope("delta_rule")
def _delta_fwd(chunk, q, k, v, g, beta):
    kernel = _kernels.kernel_takes(q.shape, v.shape, chunk, q.dtype)
    _telemetry.record_delta_rule("kernel" if kernel else "xla")
    o, opening = (_kernels.rule if kernel else _forward)(chunk, q, k, v, g, beta)
    return o, (q, k, v, g, beta, opening)


@jax.named_scope("delta_rule_bwd")
def _delta_bwd(chunk, res, do):
    q, v = res[0], res[2]
    kernel = _kernels.kernel_takes(q.shape, v.shape, chunk, q.dtype)
    return (_kernels.rule_grads if kernel else _grads)(chunk, *res, do)


def _grads(chunk, q, k, v, g, beta, opening, do):
    """The formula's backward: the gradient of every input under ``do``."""
    kind, t = q.dtype, q.shape[1]
    parts = [_by_chunk(z, chunk) for z in (q, k, v, g, beta)]
    (q_open, k_end, p, w, u, closing), pull = jax.vjp(
        partial(_within, sub=_sub_block(chunk)), *parts)
    state = opening.astype(kind)
    new = (u - _dot("bnhik,bnhkv->bnhiv", w, state)).astype(kind)
    do = jnp.moveaxis(_by_chunk(do.astype(kind), chunk), 3, 2)  # (b, n, h, c, V)
    # step 4's transposes, every chunk at once
    dq_open = _dot("bnhiv,bnhkv->bnhik", do, state)
    dp = _dot("bnhiv,bnhjv->bnhij", do, new)
    dnew_read = _dot("bnhij,bnhiv->bnhjv", p, do)
    dopen_read = _dot("bnhik,bnhiv->bnhkv", q_open, do)

    # step 3 in reverse: ``ds`` is the gradient of the state a chunk closes on
    def one(ds, xs):
        w_n, k_n, decay, dnew_n, dopen_n = xs
        dnew = dnew_n + _dot("bhik,bhkv->bhiv", k_n, ds.astype(kind))
        before = dopen_n + decay[..., None] * ds \
            - _dot("bhik,bhiv->bhkv", w_n, dnew.astype(kind))
        return before, (ds, dnew)

    xs = tuple(jnp.moveaxis(z, 1, 0) for z in (w, k_end, closing, dnew_read, dopen_read))
    _, (dclose, dnew) = jax.lax.scan(one, jnp.zeros_like(opening[:, 0]), xs, reverse=True)
    dclose, dnew = jnp.moveaxis(dclose, 0, 1), jnp.moveaxis(dnew, 0, 1)
    dw = -_dot("bnhiv,bnhkv->bnhik", dnew.astype(kind), state)
    dk_end = _dot("bnhiv,bnhkv->bnhik", new, dclose.astype(kind))
    dclosing = jnp.sum(opening * dclose, axis=-1)
    grads = pull((dq_open.astype(kind), dk_end.astype(kind), dp.astype(kind),
                  dw.astype(kind), dnew, dclosing))

    def rows(z, like):  # (b, n, c, ...) -> (b, t, ...) without the padding
        return z.reshape((z.shape[0], -1) + z.shape[3:])[:, :t].astype(like.dtype)

    return tuple(rows(dz, z) for dz, z in zip(grads, (q, k, v, g, beta)))


_delta_core.defvjp(_delta_fwd, _delta_bwd)


def _record_signature(q, v, g, beta, chunk):
    """Remember this call's shapes for ``tuning.warmup()``'s replay, as the
    flash kernels' calls are (deduplicated in the table)."""
    try:
        from .. import tuning

        tuning.record_signature("gated_delta_rule", {
            "q_shape": list(q.shape), "v_shape": list(v.shape), "dtype": str(q.dtype),
            "g_dtype": str(g.dtype), "beta_dtype": str(beta.dtype), "chunk": int(chunk)})
    except Exception:  # noqa: BLE001 — bookkeeping must not fail the op
        pass


@register("gated_delta_rule")
def gated_delta_rule(q, k, v, g, beta, chunk=64):
    """``o_t = S_t^T q_t`` over ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``,
    ``S' = Diag(exp(g_t)) S_{t-1}``, in the chunked form, ``chunk`` tokens at a
    time: see the module's docstring. ``q``, ``k``, ``g`` (B, T, H, K), ``v``
    (B, T, H, V), ``beta`` (B, T, H). Returns (B, T, H, V) in ``q``'s type."""
    if q.ndim != 4 or k.shape != q.shape or g.shape != q.shape or v.ndim != 4 \
            or v.shape[:3] != q.shape[:3] or beta.shape != q.shape[:3]:
        raise MXNetError("gated_delta_rule: q %s, k %s, v %s, g %s, beta %s are not "
                         "(B, T, H, K) twice, (B, T, H, V), (B, T, H, K), (B, T, H)"
                         % tuple(tuple(z.shape) for z in (q, k, v, g, beta)))
    chunk = int(chunk)
    if chunk < 1:
        raise MXNetError("gated_delta_rule: chunk %d" % chunk)
    t, sub = q.shape[1], _sub_block(chunk)
    if t < chunk:  # one chunk of the sequence's own length, in whole sub-blocks
        chunk = -(-t // sub) * sub
    _record_signature(q, v, g, beta, chunk)
    return _delta_core(chunk, q, k.astype(q.dtype), v.astype(q.dtype), g, beta)


@register("kda_log_decay")
def kda_log_decay(f, A_log, dt_bias):
    """The log-decays of Kimi's delta attention from the decay projection's
    result ``f`` (B, T, H K), ``A_log`` (H,) and ``dt_bias`` (H K,):
    ``-exp(A_log) * softplus(f + dt_bias)`` a head, (B, T, H, K) in float32
    whatever the inputs' type (a rounded decay compounds over a chunk)."""
    h = A_log.shape[0]
    if f.ndim != 3 or f.shape[-1] % h or dt_bias.shape != f.shape[-1:]:
        raise MXNetError("kda_log_decay: f %s, A_log %s, dt_bias %s are not (B, T, H K), "
                         "(H,), (H K,)" % tuple(tuple(z.shape) for z in (f, A_log, dt_bias)))
    with jax.named_scope("log_decay"):
        step = jax.nn.softplus(f.astype(F32) + dt_bias.astype(F32))
        step = step.reshape(f.shape[:2] + (h, -1))
        return -jnp.exp(A_log.astype(F32))[:, None] * step
