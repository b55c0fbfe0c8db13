"""The gated delta rule (``ops/delta_rule.py``: ``gated_delta_rule``) as the
program's own kernels, which keep a chunk's running sums, its row and column
lifts, the pairs ``A`` and ``P``, ``T = (I + A)^-1``, ``W``, ``U``, ``V'`` and
the heads' states in VMEM: nothing of a (chunk x chunk) or a (sub-blocks x
chunk x K) shape exists in HBM.

XLA's form of the op is some sixty fusions a pass over HBM arrays up to four
times ``k``'s size (the column operand a row sub-block), a solve of 15 unrolled
rows and a series of float32 products over (chunks x heads x 64 x 64), and a
``while`` loop of one turn a chunk each way. Here one grid step holds one chunk
of ``_HEADS`` heads, and the chunks' carry rides in VMEM scratch over the grid's
chunk axis, which is sequential.

**Tokens lie along the sublanes**: ``q``, ``k``, ``g`` as (B, T, H K) and ``v``,
``o`` as (B, T, H V), the arrays' own row-major order, so a (chunk, head) is a
(chunk x 128) block of whole lane tiles and every channel's decay is a lane of
its own. ``beta`` comes as (B, H / 8, T, 8) float32, a block's heads side by
side: a head's column is a masked sum along the lanes. A head's state is kept
TRANSPOSED, (V, K): the decay a channel of the key then multiplies along the
lanes, every product of both passes is a plain or a transposed-operand matmul,
and the opening states the forward writes for the backward are (B, chunks, H, V,
K) float32.

* ``kda_chunk_fwd``, grid (batch, block of heads, chunk), the chunks in order,
  by the steps of ``ops/delta_rule.py``'s docstring: the unit ``q`` and ``k``;
  the running sum of ``g`` inside each sub-block of 16 rows (four shifted adds
  along the sublanes) and the sums ``R_a`` where each sub-block opens; (1) the
  pairs a row sub-block at a time with the formula's anchors (``exp(G_i - R_a)``
  on the row, ``exp(R_a - G_j)`` on the column, only for the columns at or under
  the row's sub-block: 10 of the 16 (16 x 16) blocks), ``k``'s and ``q``'s rows
  of a sub-block in ONE product against the column operand they share; (2) the
  solve: forward substitution inside the diagonal blocks as 15 rank-one updates
  of all four at once, then the series over the blocks in float32 "highest";
  ``W`` and ``U`` in one product; (3) ``V' = U - W S`` and (4) ``o = (q exp(G))
  S + P V'``, ``W`` and ``q exp(G)`` in one product against the state; the
  state's step in scratch, zero at chunk 0.
* ``kda_chunk_bwd``, the same grid with the chunks in REVERSE, ``dS`` (what
  reaches a chunk's closing state from the chunks after it) in scratch. It
  builds steps 1-3 again from the inputs and the opening state, then the
  transposes of every product, ``dA = -strict_tril(T^T dT T^T)`` in float32
  "highest", the lifts' and the norms' derivatives, and ``dg`` as the reverse
  running sum inside the chunk of ``dG``: rows less columns of the same
  products, each side from the SAME rounded operands, subtracted element by
  element in float32 before the one sum.

The precision is the formula's: running sums, decays, lifts, the solve and the
states float32; the operands of every other product in ``q``'s type with
float32 accumulation; no exponent of a sum longer than a sub-block's own is
ever positive. ``kernel_takes`` is the rule: a TPU, bfloat16 or float32, K and V
whole lane tiles, a chunk of whole sub-blocks of 16, heads in whole blocks of 8,
the blocks and scratch within ``chip.VMEM_CEILING``. Every other call is the
``jax.numpy`` formula in ``ops/delta_rule.py``.
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
HI = jax.lax.Precision.HIGHEST
_HEADS = 8  # heads a grid step holds: independent chains that hide the MXU's latency
_SUB = 16  # rows of a sub-block: ``ops/delta_rule.py``'s SUB_BLOCK
_LANES = _chip.LANES
_NORM_EPS = 1e-6
_I = np.int32  # a whole number in a kernel: under jax_enable_x64 a Python one counts in 64 bits


def _dot(a, b, i, j, precision=None):
    """``a`` and ``b`` contracted over their axes ``i`` and ``j``, float32 out."""
    return jax.lax.dot_general(a, b, (((i,), (j,)), ((), ())), precision=precision,
                               preferred_element_type=F32)


def _rows(z, a):
    """Sub-block ``a`` of the rows of ``z``."""
    return z[a * _SUB:(a + 1) * _SUB]


def _stack(parts):
    return jnp.concatenate(parts, axis=0)


def _masks(c):
    """What every head of a step shares, (c, c): the column numbers, ``same``
    where row and column lie in one sub-block, ``lower`` and ``strict``, and the
    unit."""
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return dict(j=j, same=(i // _I(_SUB)) == (j // _I(_SUB)), lower=i >= j, strict=i > j,
                eye=(i == j).astype(F32))


def _sub_sums(g):
    """The running sum of ``g`` (c, lanes) float32 along the rows inside each
    sub-block: ``G_i - R_a``, shifted adds along the sublanes."""
    at = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0) % _I(_SUB)
    shift = 1
    while shift < _SUB:
        g = g + jnp.where(at >= _I(shift), pltpu.roll(g, _I(shift), 0), 0.0)
        shift *= 2
    return g


def _solve(a, m, x_ref, at_row):
    """``(I + a)^-1`` of strictly lower triangular ``a`` (c, c) float32, as
    ``ops/delta_rule.py``'s ``_inverse``: exact forward substitution inside each
    diagonal block (every block at once: column ``j`` of a block, a masked sum
    along the lanes, times its row ``j``, off all later rows; the (c, c) rows of
    ``x_ref`` from ``at_row`` on are where the rows are read from), then the
    series over the blocks. Stand-alone at the cell's shape the 15 updates are
    0.68 ms of the forward's 1.45 a call and the series' four products 0.36; the
    column out of a product with a fold of ones and a slice of one lane reads
    1.73 ms a call, the row out of a product 4.04, block rows in place of the
    series 1.40 (my chip runs, PR 49)."""
    c = a.shape[0]
    n = c // _SUB
    d = jnp.where(m["same"], a, 0.0)
    at = m["j"] % _I(_SUB)
    x = m["eye"]
    for j in range(_SUB - 1):
        col = jnp.sum(jnp.where(at == _I(j), d, 0.0), axis=1, keepdims=True)
        x_ref[at_row:at_row + c, :] = x
        row = _stack([jnp.broadcast_to(x_ref[at_row + b * _SUB + j:at_row + b * _SUB + j + 1, :],
                                       (_SUB, c)) for b in range(n)])
        x = x - col * row
    if n == 1:
        return x
    mm = functools.partial(_dot, i=1, j=0, precision=HI)
    step = mm(x, jnp.where(m["same"], 0.0, a))
    series, power, reach = m["eye"] - step, step, 2
    while reach < n:  # (I + M)^-1 = (I - M)(I + M^2)(I + M^4)...: M^n = 0
        power = mm(power, power)
        series = mm(series, m["eye"] + power)
        reach *= 2
    return mm(series, x)


def _unit(x_ref, lanes, scale):
    """A head's rows in float32, the reciprocal of their lengths times
    ``scale`` (c, 1), and the unit rows times ``scale``."""
    x = x_ref[0, :, lanes].astype(F32)
    r = jax.lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + _NORM_EPS) * scale
    return x, r, x * r


def _head(h, q_ref, k_ref, v_ref, beta_ref, gs_ref, state, m, solve):
    """Steps 1 to 3 of head ``h`` of a grid step, what both kernels build alike,
    from the blocks, the sub-blocks' running sums in ``gs_ref`` and the opening
    ``state`` (V, K) float32: a dict of everything the passes read. ``solve``
    gives ``T`` float32 from ``A``: the forward solves, the backward reads what
    the forward kept."""
    kind = q_ref.dtype
    c = q_ref.shape[1]
    n = c // _SUB
    width, wide = gs_ref.shape[1] // _HEADS, v_ref.shape[2] // _HEADS
    lanes, lanes_v = slice(h * width, (h + 1) * width), slice(h * wide, (h + 1) * wide)
    q32, rq, qn = _unit(q_ref, lanes, width ** -0.5)
    k32, rk, kn = _unit(k_ref, lanes, 1.0)
    v32 = v_ref[0, :, lanes_v].astype(F32)
    at = jax.lax.broadcasted_iota(jnp.int32, beta_ref.shape[2:], 1)
    beta = jnp.sum(jnp.where(at == _I(h), beta_ref[0, 0], 0.0), axis=1, keepdims=True)  # (c, 1)
    gs = gs_ref[:, lanes]
    opens = [jnp.zeros((1, width), F32)]  # R_a, and after the last the chunk's whole sum
    for a in range(n):
        opens.append(opens[a] + gs_ref[(a + 1) * _SUB - 1:(a + 1) * _SUB, lanes])
    last = opens[n]
    run = gs + _stack([jnp.broadcast_to(opens[a], (_SUB, width)) for a in range(n)])
    row = jnp.exp(gs)
    k_row, q_row = (kn * row).astype(kind), (qn * row).astype(kind)
    lifts, k_cols, rows, pairs = [], [], [], []
    for a in range(n):
        # a column j under row sub-block a: exp(R_a - G_j) up to the sub-block's own last
        # token; the columns after it do not exist (zeros, for the product's shape)
        reach = (a + 1) * _SUB
        lift = jnp.exp(opens[a] - run[:reach])
        if reach < c:
            lift = _stack([lift, jnp.zeros((c - reach, width), F32)])
        lifts.append(lift)
        k_cols.append((kn * lift).astype(kind))
        rows.append(_stack([_rows(k_row, a), _rows(q_row, a)]))
        pairs.append(_dot(rows[a], k_cols[a], 1, 1))  # (2 sub, c): k's rows, then q's
    akk = _stack([p[:_SUB] for p in pairs])
    a_ = jnp.where(m["strict"], akk * beta, 0.0)
    p = jnp.where(m["lower"], _stack([p[_SUB:] for p in pairs]), 0.0).astype(kind)
    t32 = solve(a_)
    t = t32.astype(kind)
    from_open, to_end, closing = jnp.exp(run), jnp.exp(last - run), jnp.exp(last)
    kb, vb = (kn * from_open * beta).astype(kind), (v32 * beta).astype(kind)
    wu = _dot(t, jnp.concatenate([kb, vb], axis=1), 1, 0)
    w, u = wu[:, :width].astype(kind), wu[:, width:]
    q_open, k_end = (qn * from_open).astype(kind), (kn * to_end).astype(kind)
    s = state.astype(kind)
    read = _dot(_stack([w, q_open]), s, 1, 1)  # W S, then (q exp(G)) S: (2 c, V)
    new = (u - read[:c]).astype(kind)
    return dict(
        lanes=lanes, lanes_v=lanes_v, q32=q32, rq=rq, qn=qn, k32=k32, rk=rk, kn=kn, v32=v32,
        beta=beta, closing=closing, row=row, k_row=k_row, q_row=q_row, lifts=lifts, k_cols=k_cols,
        rows=rows, akk=akk, p=p, t32=t32, t=t, from_open=from_open, to_end=to_end, kb=kb,
        vb=vb, w=w, q_open=q_open, k_end=k_end, s=s, read_q=read[c:], new=new)


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, open_ref, t_ref,
                state_ref, gs_ref, x_ref):
    kind, c = q_ref.dtype, q_ref.shape[1]

    @pl.when(pl.program_id(2) == 0)
    def _open():
        state_ref[...] = jnp.zeros(state_ref.shape, F32)

    gs_ref[...] = _sub_sums(g_ref[0])
    m = _masks(c)
    for h in range(_HEADS):
        state = state_ref[h]
        open_ref[0, 0, h] = state
        z = _head(h, q_ref, k_ref, v_ref, beta_ref, gs_ref, state, m,
                  lambda a, h=h: _solve(a, m, x_ref, h * c))
        t_ref[0, 0, h] = z["t32"]
        o_ref[0, :, z["lanes_v"]] = (z["read_q"] + _dot(z["p"], z["new"], 1, 0)).astype(kind)
        state_ref[h] = state * z["closing"] + _dot(z["new"], z["k_end"], 0, 0)


def _unit_grad(x32, r, scale, d):
    """The gradient of ``x r`` (``r`` the reciprocal length times ``scale``) at
    the rows ``x32`` under ``d``."""
    unit = x32 * (r * (1.0 / scale))
    return r * (d - unit * jnp.sum(d * unit, axis=1, keepdims=True))


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, open_ref, t_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate_ref, gs_ref):
    kind, c = q_ref.dtype, q_ref.shape[1]
    n = c // _SUB

    @pl.when(pl.program_id(2) == 0)  # the sequence's last chunk: nothing reads after it
    def _open():
        dstate_ref[...] = jnp.zeros(dstate_ref.shape, F32)

    gs_ref[...] = _sub_sums(g_ref[0])
    m = _masks(c)
    width = gs_ref.shape[1] // _HEADS
    at = jax.lax.broadcasted_iota(jnp.int32, (c, width), 0)
    heads = jax.lax.broadcasted_iota(jnp.int32, beta_ref.shape[2:], 1)
    dbeta = jnp.zeros(beta_ref.shape[2:], F32)

    def f32(z):
        return z.astype(F32)

    def rounded(z):  # a gradient handed on in the operands' type, as the formula hands it
        return z.astype(kind).astype(F32)

    for h in range(_HEADS):
        state, dstate = open_ref[0, 0, h], dstate_ref[h]
        z = _head(h, q_ref, k_ref, v_ref, beta_ref, gs_ref, state, m,
                  lambda a, h=h: t_ref[0, 0, h])
        do = do_ref[0, :, z["lanes_v"]]
        ds = dstate.astype(kind)
        # steps 4 and 3, transposed: V' from the read and from the closing state
        dnew = _dot(z["p"], do, 0, 0) + _dot(z["k_end"], ds, 1, 1)
        dnew_k = dnew.astype(kind)
        both = _dot(_stack([do, dnew_k]), z["s"], 1, 0)  # dO S^T, then dV' S^T: (2 c, K)
        dq_open, dw = rounded(both[:c]), (-both[c:]).astype(kind)
        dp = jnp.where(m["lower"], rounded(_dot(do, z["new"], 1, 1)), 0.0)
        dk_end = rounded(_dot(z["new"], ds, 1, 0))
        dlast = jnp.sum(state * dstate, axis=0, keepdims=True) * z["closing"]
        dstate_ref[h] = _dot(do, z["q_open"], 0, 0) + dstate * z["closing"] \
            - _dot(dnew_k, z["w"], 0, 0)
        # step 2: W = T Kb and U = T Vb, then the solve
        cat = jnp.concatenate([dw, dnew_k], axis=1)
        dt = jnp.where(m["lower"], rounded(_dot(
            cat, jnp.concatenate([z["kb"], z["vb"]], axis=1), 1, 1)), 0.0)
        dkv = _dot(z["t"], cat, 0, 0)
        dkb, dvb = dkv[:, :width], dkv[:, width:]
        da = jnp.where(m["strict"], -_dot(_dot(z["t32"], dt, 0, 0, HI), z["t32"], 1, 1, HI), 0.0)
        dbeta_h = jnp.sum(da * z["akk"], axis=1, keepdims=True) \
            + jnp.sum(dkb * z["kn"] * z["from_open"], axis=1, keepdims=True) \
            + jnp.sum(dvb * z["v32"], axis=1, keepdims=True)
        dbeta = dbeta + jnp.where(heads == _I(h), dbeta_h, 0.0)
        dv_ref[0, :, z["lanes_v"]] = (dvb * z["beta"]).astype(kind)
        # step 1: the pairs a row sub-block at a time, rows and columns of the same products
        dakk = da * z["beta"]
        d_rows, dkn, columns, anchors = [], dkb * z["from_open"] * z["beta"] \
            + dk_end * z["to_end"], jnp.zeros((c, width), F32), []
        for a in range(n):
            ct = _stack([_rows(dakk, a), _rows(dp, a)]).astype(kind)  # (2 sub, c)
            d_rows.append(_dot(ct, z["k_cols"][a], 1, 0))  # k's rows, then q's: (2 sub, K)
            d_col = _dot(ct, z["rows"][a], 0, 0)  # (c, K), zeros past the sub-block
            dkn = dkn + d_col * z["lifts"][a]
            column = d_col * f32(z["k_cols"][a])
            columns = columns + column
            anchors.append(jnp.sum(column, axis=0, keepdims=True))
        dk_row, dq_row = _stack([r[:_SUB] for r in d_rows]), _stack([r[_SUB:] for r in d_rows])
        dkn = dkn + dk_row * z["row"]
        dqn = dq_row * z["row"] + dq_open * z["from_open"]
        dq_ref[0, :, z["lanes"]] = _unit_grad(z["q32"], z["rq"], width ** -0.5, dqn).astype(kind)
        dk_ref[0, :, z["lanes"]] = _unit_grad(z["k32"], z["rk"], 1.0, dkn).astype(kind)
        # dG: rows less columns, element by element, then what each sum that anchors a
        # sub-block (its last row before) and the chunk's whole sum (the last row) carry
        lifted = dk_row * f32(z["k_row"]) + dq_row * f32(z["q_row"])
        ending = dk_end * f32(z["k_end"])
        dg = lifted + dq_open * f32(z["q_open"]) + dkb * f32(z["kb"]) - columns - ending
        for a in range(1, n):
            anchor = anchors[a] - jnp.sum(_rows(lifted, a), axis=0, keepdims=True)
            dg = dg + jnp.where(at == _I(a * _SUB - 1), anchor, 0.0)
        dg = dg + jnp.where(at == _I(c - 1), jnp.sum(ending, axis=0, keepdims=True) + dlast, 0.0)
        shift = 1
        while shift < c:  # g's gradient: the reverse running sum of dG inside the chunk
            dg = dg + jnp.where(at < _I(c - shift), pltpu.roll(dg, _I(c - shift), 0), 0.0)
            shift *= 2
        dg_ref[0, :, z["lanes"]] = dg
    dbeta_ref[0, 0] = dbeta


def _vmem(k, v, c, itemsize):
    """Bytes the backward's step asks for: its blocks twice (q, k, dq, dk and v,
    do, dv in the operands' type, g and dg float32, the opening states and ``T``,
    beta and its gradient a lane tile a row), the carry's and the two small scratches,
    and what a head's walk keeps at once (some forty (c x K) and twenty (c x c)
    float32)."""
    heads = _HEADS
    blocks = 2 * (heads * c * ((4 * k + 3 * v) * itemsize + 2 * k * 4)
                  + heads * (v * k + c * _LANES) * 4 + 2 * c * _LANES * 4)
    scratch = heads * (v * k + c * k + c * max(c, _LANES)) * 4
    return blocks + scratch + heads * (40 * c * max(k, v) + 20 * c * _LANES) * 4


def _specs(k, v, c, at):
    """``BlockSpec``s over a grid of (batch, block of heads, chunk), the chunk
    ``at(n)``: a block of heads' (c, heads K) and (c, heads V) columns of the
    rows, beta's (c, heads), the heads' (V, K) opening states and their (c, c)
    ``T``."""
    heads, zero = _HEADS, np.int32(0)

    def kept(*shape):  # (B, chunks, H, ., .)
        return pl.BlockSpec((1, 1, heads) + shape, lambda b, s, n: (b, at(n), s, zero, zero))

    return (pl.BlockSpec((1, c, heads * k), lambda b, s, n: (b, at(n), s)),
            pl.BlockSpec((1, c, heads * v), lambda b, s, n: (b, at(n), s)),
            pl.BlockSpec((1, 1, c, heads), lambda b, s, n: (b, s, at(n), zero)),
            kept(v, k), kept(c, c))


def _params(k, v, c, itemsize):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=int(_vmem(k, v, c, itemsize) * 5 // 4 + 2 ** 22))


def _sizes(q, v, beta):
    """(batch, tokens, blocks of heads, K, V) of the kernels' operands."""
    blocks = beta.shape[1]
    return (q.shape[0], q.shape[1], blocks, q.shape[2] // (blocks * _HEADS),
            v.shape[2] // (blocks * _HEADS))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _fwd_pallas(q, k, v, g, beta, chunk, interpret=False):
    """``o`` (B, T, H V) and, float32, what the backward reads again: the chunks'
    opening states (B, T / chunk, H, V, K) and their ``T`` (B, T / chunk, H,
    chunk, chunk); of ``q``, ``k`` (B, T, H K), ``v`` (B, T, H V), float32 ``g``
    (B, T, H K) and ``beta`` (B, H / 8, T, 8), T whole chunks. A jitted function
    of its own: the call sites of one shape (every layer) share one trace and
    one lowering."""
    batch, t, blocks, width, wide = _sizes(q, v, beta)
    keys, values, betas, states, solved = _specs(width, wide, chunk, lambda n: n)
    kept = (batch, t // chunk, blocks * _HEADS)
    return pl.pallas_call(
        _fwd_kernel, grid=(batch, blocks, t // chunk),
        in_specs=[keys, keys, values, keys, betas], out_specs=(values, states, solved),
        out_shape=(jax.ShapeDtypeStruct(v.shape, q.dtype),
                   jax.ShapeDtypeStruct(kept + (wide, width), F32),
                   jax.ShapeDtypeStruct(kept + (chunk, chunk), F32)),
        # the carry (heads, V, K), the sub-blocks' running sums (c, heads K) and the
        # solve's rows, a head after a head (heads c, c)
        scratch_shapes=[pltpu.VMEM((_HEADS, wide, width), F32),
                        pltpu.VMEM((chunk, _HEADS * width), F32),
                        pltpu.VMEM((_HEADS * chunk, chunk), F32)],
        compiler_params=_params(width, wide, chunk, q.dtype.itemsize),
        name="kda_chunk_fwd", interpret=interpret)(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _bwd_pallas(q, k, v, g, beta, opening, solved, do, chunk, interpret=False):
    """Under ``do`` (B, T, H V): the gradients of ``_fwd_pallas`` at ``q``, ``k``
    and ``v`` in their shapes and types and, float32, at ``g`` and ``beta`` in
    theirs."""
    batch, t, blocks, width, wide = _sizes(q, v, beta)
    end = np.int32(t // chunk - 1)
    keys, values, betas, states, kept = _specs(width, wide, chunk, lambda n: end - n)
    like = jax.ShapeDtypeStruct
    return pl.pallas_call(
        _bwd_kernel, grid=(batch, blocks, t // chunk),
        in_specs=[keys, keys, values, keys, betas, states, kept, values],
        out_specs=(keys, keys, values, keys, betas),
        out_shape=(like(q.shape, q.dtype), like(k.shape, k.dtype), like(v.shape, v.dtype),
                   like(g.shape, F32), like(beta.shape, F32)),
        scratch_shapes=[pltpu.VMEM((_HEADS, wide, width), F32),
                        pltpu.VMEM((chunk, _HEADS * width), F32)],
        compiler_params=_params(width, wide, chunk, q.dtype.itemsize),
        name="kda_chunk_bwd", interpret=interpret)(q, k, v, g, beta, opening, solved, do)


def kernel_takes(q_shape, v_shape, chunk, dtype):
    """Whether ``gated_delta_rule`` of ``q`` (B, T, H, K) and ``v`` (B, T, H, V)
    of ``dtype`` in chunks of ``chunk`` is the kernels': everything the call can
    see."""
    dtype = jnp.dtype(dtype)
    if not on_tpu() or dtype not in (jnp.dtype(jnp.bfloat16), jnp.dtype(F32)):
        return False
    (batch, t, h, k), v = q_shape, v_shape[3]
    if batch == 0 or t == 0 or k % _LANES or v % _LANES or h % _HEADS \
            or chunk % _SUB or chunk % _chip.SUBLANES[dtype.itemsize]:
        return False
    return _vmem(k, v, chunk, dtype.itemsize) <= _chip.VMEM_CEILING


def _flat(z, chunk):
    """(B, T, H, .) -> (B, T padded to whole chunks, H .): the array's own order."""
    z = z.reshape(z.shape[:2] + (-1,))
    return jnp.pad(z, ((0, 0), (0, -z.shape[1] % chunk), (0, 0)))


def _by_block(beta, chunk):
    """(B, T, H) -> float32 (B, H / 8, T padded, 8): a block's heads side by side."""
    b, t, h = beta.shape
    beta = jnp.moveaxis(beta.astype(F32).reshape(b, t, h // _HEADS, _HEADS), 2, 1)
    return jnp.pad(beta, ((0, 0), (0, 0), (0, -t % chunk), (0, 0)))


def rule(chunk, q, k, v, g, beta, interpret=False):
    """``gated_delta_rule``'s result (B, T, H, V) and what ``rule_grads`` reads
    again, float32: the chunks' opening states (B, chunks, H, V, K), a state
    TRANSPOSED, and their ``T`` (B, chunks, H, chunk, chunk); by
    ``kda_chunk_fwd``, for a call ``kernel_takes`` accepts."""
    o, *kept = _fwd_pallas(_flat(q, chunk), _flat(k, chunk), _flat(v, chunk),
                             _flat(g.astype(F32), chunk), _by_block(beta, chunk), chunk=chunk,
                             interpret=interpret)
    return o[:, :q.shape[1]].reshape(v.shape), tuple(kept)


def rule_grads(chunk, q, k, v, g, beta, kept, do, interpret=False):
    """The gradients of ``gated_delta_rule`` at its five inputs under ``do``, by
    ``kda_chunk_bwd``, from what ``rule`` kept."""
    t = q.shape[1]
    grads = _bwd_pallas(_flat(q, chunk), _flat(k, chunk), _flat(v, chunk),
                        _flat(g.astype(F32), chunk), _by_block(beta, chunk), *kept,
                        _flat(do.astype(q.dtype), chunk), chunk=chunk, interpret=interpret)
    dq, dk, dv, dg = (dz[:, :t].reshape(z.shape).astype(z.dtype)
                      for dz, z in zip(grads[:4], (q, k, v, g)))
    dbeta = jnp.moveaxis(grads[4][:, :, :t], 1, 2).reshape(beta.shape).astype(beta.dtype)
    return dq, dk, dv, dg, dbeta
