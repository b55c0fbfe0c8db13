"""Fused multi-layer RNN op (ref: src/operator/rnn.cc, cudnn_rnn-inl.h).

The reference runs the whole multi-layer LSTM/GRU/RNN over the sequence in
one cuDNN call with a packed flat weight vector. TPU-native equivalent: one
``lax.scan`` per layer inside a single traced program — XLA fuses the cell,
keeps weights resident, and the scan compiles to a tight loop feeding the
MXU with (B, gates*H) matmuls.

Packed layout (cuDNN-compatible ordering, gate order LSTM=[i,f,g,o],
GRU=[r,z,n]): for each layer, for each direction: W_i2h(G*H, in), then
W_h2h(G*H, H); after ALL weights, for each layer/direction: b_i2h(G*H),
b_h2h(G*H).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register

_GATES = {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}


def rnn_param_size(mode, input_size, state_size, num_layers=1,
                   bidirectional=False):
    g = _GATES[mode]
    d = 2 if bidirectional else 1
    h = state_size
    total = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else h * d
        total += d * (g * h * in_sz + g * h * h)  # weights
        total += d * (2 * g * h)  # biases
    return total


def _unpack(params, mode, input_size, state_size, num_layers, bidirectional):
    g = _GATES[mode]
    d = 2 if bidirectional else 1
    h = state_size
    ws, bs = [], []
    off = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else h * d
        lw = []
        for _ in range(d):
            wi = params[off:off + g * h * in_sz].reshape(g * h, in_sz)
            off += g * h * in_sz
            wh = params[off:off + g * h * h].reshape(g * h, h)
            off += g * h * h
            lw.append((wi, wh))
        ws.append(lw)
    for layer in range(num_layers):
        lb = []
        for _ in range(d):
            bi = params[off:off + g * h]
            off += g * h
            bh = params[off:off + g * h]
            off += g * h
            lb.append((bi, bh))
        bs.append(lb)
    return ws, bs


def _cell_step(mode, h_size):
    if mode == "lstm":
        def step(carry, gates_x, wh, bh):
            h, c = carry
            gates = gates_x + h @ wh.T + bh
            i, f, g, o = jnp.split(gates, 4, axis=-1)
            i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
            g = jnp.tanh(g)
            c2 = f * c + i * g
            h2 = o * jnp.tanh(c2)
            return (h2, c2), h2
    elif mode == "gru":
        def step(carry, gates_x, wh, bh):
            (h,) = carry
            gh = h @ wh.T + bh
            xr, xz, xn = jnp.split(gates_x, 3, axis=-1)
            hr, hz, hn = jnp.split(gh, 3, axis=-1)
            r = jax.nn.sigmoid(xr + hr)
            z = jax.nn.sigmoid(xz + hz)
            n = jnp.tanh(xn + r * hn)
            h2 = (1 - z) * n + z * h
            return (h2,), h2
    else:
        act = jnp.tanh if mode == "rnn_tanh" else (lambda x: jnp.maximum(x, 0))

        def step(carry, gates_x, wh, bh):
            (h,) = carry
            h2 = act(gates_x + h @ wh.T + bh)
            return (h2,), h2
    return step


def _scan_unroll(T):
    """Unroll factor for the recurrent scan. Short sequences unroll fully:
    each residual scan iteration is a while-loop step with a fixed cost
    per iteration. What that cost is on the attached chip is to be
    re-measured (ROADMAP S4): full unroll to T=128 buys compile time and
    may buy nothing. Long sequences unroll partially so compile time
    stays bounded. MXT_RNN_UNROLL overrides (0 = no unrolling)."""
    from .. import config as _config

    override = _config.get("MXT_RNN_UNROLL")
    if override is not None:
        return max(1, int(override)) if int(override) > 0 else 1
    if T <= 128:
        return T
    return 16


def _run_layer(x, mode, wi, wh, bi, bh, h0, c0, reverse=False):
    """x: (T, B, in) → (T, B, H). Pre-computes the input projection for the
    whole sequence as ONE big matmul (MXU-friendly), scanning only the
    recurrent part."""
    gates_x = jnp.einsum("tbi,gi->tbg", x, wi) + bi  # (T, B, G*H)
    step = _cell_step(mode, wh.shape[1])
    carry = (h0, c0) if mode == "lstm" else (h0,)

    def body(carry, gx):
        return step(carry, gx, wh, bh)

    carry, outs = jax.lax.scan(body, carry, gates_x, reverse=reverse,
                               unroll=_scan_unroll(x.shape[0]))
    return carry, outs


def _wavefront_lstm(x, ws, bs, state, state_cell, num_layers):
    """Multi-layer LSTM as a diagonal WAVEFRONT (MXT_RNN_WAVEFRONT=1).

    The standard path runs layer scans sequentially: the serial chain is
    num_layers * T small (B, H)@(H, 4H) matmuls, each latency-bound at
    small batch (PERF.md round-4 LSTM ceiling analysis). At diagonal step
    d, layer l processes t = d - l, so every active layer's recurrent
    gemm is INDEPENDENT — they batch into one (A, B, 2H)@(A, 2H, 4H)
    einsum per diagonal. Chain length drops from L*T to T + L - 1 at the
    cost of zero-padding layer 0's unused input half (latency-bound
    segments, so the padded FLOPs are ~free).

    Unidirectional, no inter-layer dropout, T small enough to unroll —
    the caller gates on that. Numerically equivalent to the sequential
    path up to FP reduction order (the fused [h,x]@[Wh;Wi] contraction
    sums over one axis); pinned at rtol 1e-6 by the
    tests/test_gluon_rnn.py equivalence test."""
    T, B, _ = x.shape
    L = num_layers
    H = ws[0][0][1].shape[1]

    # layer 0's input projection hoists into one big gemm, as before
    wi0, wh0 = ws[0][0]
    bi0, bh0 = bs[0][0]
    gates_x0 = jnp.einsum("tbi,gi->tbg", x, wi0) + bi0 + bh0  # (T, B, 4H)

    # per-layer stacked weights: operand is [h_prev, x_in] (B, 2H) ->
    # weight [Wh ; Wi] (4H, 2H); layer 0's x half is zero (its x term is
    # the precomputed gates_x0)
    wcat, bias = [], []
    for l in range(L):
        wi, wh = ws[l][0]
        bi, bh = bs[l][0]
        if l == 0:
            wcat.append(jnp.concatenate(
                [wh0, jnp.zeros((wh0.shape[0], H), wh0.dtype)], axis=1))
            bias.append(jnp.zeros_like(bi0))  # biases live in gates_x0
        else:
            wcat.append(jnp.concatenate([wh, wi], axis=1))
            bias.append(bi + bh)
    wcat = jnp.stack(wcat)          # (L, 4H, 2H)
    bias = jnp.stack(bias)          # (L, 4H)

    h = [state[l] for l in range(L)]
    c = [state_cell[l] for l in range(L)]
    outs = []
    for d in range(T + L - 1):
        lo, hi = max(0, d - T + 1), min(L - 1, d)
        # layer l's input at this diagonal is layer l-1's output from
        # the PREVIOUS diagonal — which is exactly h[l-1] right now
        ops = jnp.stack([
            jnp.concatenate(
                [h[l], h[l - 1] if l > 0 else jnp.zeros_like(h[0])],
                axis=-1)
            for l in range(lo, hi + 1)])             # (A, B, 2H)
        gates = jnp.einsum("abe,afe->abf", ops, wcat[lo:hi + 1]) \
            + bias[lo:hi + 1][:, None, :]            # (A, B, 4H)
        if lo == 0:  # layer 0 active at t = d: add its hoisted x gates
            gates = gates.at[0].add(gates_x0[d])
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        i, f, o = (jax.nn.sigmoid(i), jax.nn.sigmoid(f),
                   jax.nn.sigmoid(o))
        g = jnp.tanh(g)
        cs = jnp.stack([c[l] for l in range(lo, hi + 1)])
        c2 = f * cs + i * g
        h2 = o * jnp.tanh(c2)
        for a, l in enumerate(range(lo, hi + 1)):
            h[l], c[l] = h2[a], c2[a]
        if hi == L - 1:  # final layer produced y_{L-1, d-(L-1)}
            outs.append(h[L - 1])
    out = jnp.stack(outs)                            # (T, B, H)
    return out, jnp.stack(h), jnp.stack(c)


@register("RNN", num_outputs=3)
def rnn_op(data, parameters, state, state_cell=None, mode="lstm",
           state_size=0, num_layers=1, bidirectional=False, p=0.0,
           state_outputs=False, projection_size=None, lstm_state_clip_min=None,
           lstm_state_clip_max=None, lstm_state_clip_nan=False,
           use_sequence_length=False, train_mode=False):
    """Fused RNN (ref: src/operator/rnn.cc — RNNParam). data is (T, B, I);
    state is (L*D, B, H). Returns (out, h_n[, c_n])."""
    del projection_size, lstm_state_clip_min, lstm_state_clip_max
    del lstm_state_clip_nan, use_sequence_length
    d = 2 if bidirectional else 1
    h = state_size
    input_size = data.shape[2]
    ws, bs = _unpack(parameters, mode, input_size, h, num_layers, bidirectional)

    from .. import config as _config

    if (mode == "lstm" and d == 1 and num_layers >= 2
            and data.shape[0] <= 128 and (p == 0 or not train_mode)
            and _config.get("MXT_RNN_WAVEFRONT")):
        return _wavefront_lstm(data, ws, bs, state, state_cell, num_layers)

    x = data
    h_finals, c_finals = [], []
    for layer in range(num_layers):
        outs_dir = []
        for di in range(d):
            idx = layer * d + di
            h0 = state[idx]
            c0 = state_cell[idx] if mode == "lstm" else None
            wi, wh = ws[layer][di]
            bi, bh = bs[layer][di]
            carry, outs = _run_layer(
                x, mode, wi, wh, bi, bh, h0, c0, reverse=(di == 1)
            )
            outs_dir.append(outs)
            h_finals.append(carry[0])
            if mode == "lstm":
                c_finals.append(carry[1])
        x = outs_dir[0] if d == 1 else jnp.concatenate(outs_dir, axis=-1)
        if p > 0 and train_mode and layer < num_layers - 1:
            from .. import random as _random

            keep = 1.0 - p
            mask = jax.random.bernoulli(_random.new_key(), keep, x.shape)
            x = jnp.where(mask, x / keep, 0.0).astype(x.dtype)

    h_n = jnp.stack(h_finals, axis=0)
    if mode == "lstm":
        return (x, h_n, jnp.stack(c_finals, axis=0))
    return (x, h_n)
