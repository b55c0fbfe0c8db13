"""Plain reference: Granite 4.0-H's hybrid decoder (``model_type:
granitemoehybrid`` with ``num_local_experts`` 0; ibm-granite/granite-4.0-h-micro;
the sizes come from the configuration's file) with next-token cross-entropy, in
straightforward ``jax.numpy`` float32 with matmul precision "highest". No
kernels, no custom backward, no program code, nothing the program made. Written
from the layer equations (transformers' ``modeling_granitemoehybrid.py``; the
mixer's from Dao and Gu, "Transformers are SSMs", arXiv:2405.21060, whose
Listing 1 the scan follows: segment sums and four einsums, plain autodiff):

``h = embedding_multiplier * embed(ids)``; block i, pre-norm, RMSNorm with a
weight, no bias but the filter's: ``h' = h + r * mixer_i(RMSNorm(h))``, ``h'' =
h' + r * mlp(RMSNorm(h'))`` with ``r = residual_multiplier``; a last RMSNorm,
then the head, whose weight is the embedding's (one leaf, ``embed.w``, used
twice), its scores over ``logits_scaling``.

* ``mlp(u) = W_down (silu(W_gate u) * W_up u)`` (``shared_intermediate_size``
  wide; the publisher keeps ``W_gate`` over ``W_up`` as one matrix: two leaves
  here, the same function of the same entries).
* Mixer where ``layer_types[i]`` is ``attention``. ``q = W_q u`` as 32 heads of
  64, ``[k, v] = W_kv u`` as 8 + 8 heads of 64 (``W_k`` over ``W_v``, one leaf);
  no positions, no norm on a head; query head i reads K/V head ``i // 4``;
  causal softmax of the scores times ``attention_multiplier`` (1/64, not
  ``64 ** -0.5``); ``W_o`` of the heads side by side. Scores are materialised,
  a few heads at a time.
* Mixer where it is ``mamba`` (H heads of P, G groups, state N, K taps, chunk
  Q). ``[z, xBC, dt] = W_in u`` (``H P``, ``H P + 2 G N``, ``H`` columns, in that
  order); ``xBC = silu(filter(xBC) + bias)``, ``filter[t] = sum_j w[:, j]
  xBC[t - (K - 1) + j]`` as K shifted sums, zeros before the sequence (``Conv1d(
  groups=C, padding=K - 1)`` cut to the sequence); ``[x, B, C] = xBC``; ``dt =
  softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head; ``S_t = exp(dt_t A)
  S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t`` a head, a group's heads
  sharing its B and C, the state zero before the sequence, computed as Listing 1
  does: within a chunk ``C B^T`` under ``exp(segsum(dt A))``, each chunk's
  closing state, the states carried over the chunks by a second segment sum,
  each token's read of its chunk's opening state. ``y = RMSNorm(y * silu(z))``
  over all ``H P`` channels (one group), the gate first; ``W_out y``.
* Loss. Mean cross-entropy over every position of the sliced vocabulary.

Departures, all of them the configuration's ``assumed``: ``time_step_limit``
is (0, inf), so ``dt`` is not clamped; T is a whole number of chunks or is
padded to one with ``dt = 0``. So that float32 at the timed size fits beside
the follower's state, each block runs under ``jax.checkpoint``, the scan and
the attention a block of heads at a time (``reference.scan_head_block``,
``reference.head_block``: heads do not see each other) and the head and the
loss a block of rows at a time (``reference.loss_rows``).

Faults the reference can plant in itself, for the calibration tools and the
CPU tests, never in a configuration's file: ``carry_dropped`` (every chunk
opens on a zero state) and ``gate_after_norm`` (``RMSNorm(y) * silu(z)``), as
``quant`` of ``value_and_grad`` or as ``reference.planted``.
"""
from __future__ import annotations

import functools
from collections import OrderedDict

import jax
import jax.numpy as jnp

from harness import seeds
from harness.quant import operand

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
PLANTED = ("carry_dropped", "gate_after_norm")


def arch(config):
    """The sizes as run: the file's own keys."""
    a = {k: config[k] for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "shared_intermediate_size", "mamba_n_heads",
        "mamba_d_head", "mamba_n_groups", "mamba_d_state", "mamba_d_conv",
        "mamba_chunk_size", "rms_norm_eps", "embedding_multiplier",
        "attention_multiplier", "residual_multiplier", "logits_scaling", "vocab_size")}
    a["layer_types"] = tuple(config["layer_types"])
    a["head_dim"] = a["hidden_size"] // a["num_attention_heads"]
    if len(a["layer_types"]) != a["num_hidden_layers"]:
        raise ValueError("layer_types names %d layers of %d"
                         % (len(a["layer_types"]), a["num_hidden_layers"]))
    return a


def leaves(config):
    """{leaf: (shape, how it starts)}; weights as ``FullyConnected`` keeps
    them, (out, in), the taps (channels, K)."""
    a = arch(config)
    h, heads, kv, d = (a["hidden_size"], a["num_attention_heads"],
                       a["num_key_value_heads"], a["head_dim"])
    mh, inner = a["mamba_n_heads"], a["mamba_n_heads"] * a["mamba_d_head"]
    filtered = inner + 2 * a["mamba_n_groups"] * a["mamba_d_state"]
    width = a["shared_intermediate_size"]
    out = OrderedDict()
    out["embed.w"] = ((a["vocab_size"], h), "normal")
    for l, kind in enumerate(a["layer_types"]):
        p = "l%d." % l
        out[p + "in_norm.g"] = ((h,), "one")
        if kind == "mamba":
            out[p + "in.w"] = ((inner + filtered + mh, h), "normal")
            out[p + "conv.w"] = ((filtered, a["mamba_d_conv"]), "taps")
            out[p + "conv.bias"] = ((filtered,), "zero")
            out[p + "A_log"] = ((mh,), "A_log")
            out[p + "D"] = ((mh,), "one")
            out[p + "dt_bias"] = ((mh,), "dt_bias")
            out[p + "gate_norm.g"] = ((inner,), "one")
            out[p + "out.w"] = ((h, inner), "normal")
        elif kind == "attention":
            out[p + "q.w"] = ((heads * d, h), "normal")
            out[p + "kv.w"] = ((2 * kv * d, h), "normal")
            out[p + "o.w"] = ((h, heads * d), "normal")
        else:
            raise ValueError("no mixer named %r" % (kind,))
        out[p + "post_norm.g"] = ((h,), "one")
        out[p + "gate.w"], out[p + "up.w"] = ((width, h), "normal"), ((width, h), "normal")
        out[p + "down.w"] = ((h, width), "normal")
    out["norm.g"] = ((h,), "one")
    return out


def init(config, seed):
    """Seeded weights, all in the type they are trained in: N(0,
    ``weight_std``) (0.02 where the file does not say), norms and ``D`` 1, the filter's bias 0, and Mamba-2's own draws for what shapes
    the recurrence (``assumed``): the taps uniform within ``conv_tap_bound``
    (``K ** -0.5``, the ``Conv1d`` default its code leaves in place), ``A_log``
    the log of a uniform draw in ``A_range``, ``dt_bias`` the inverse softplus
    of a log-uniform draw in ``dt_range`` floored at ``dt_floor``."""
    spec = leaves(config)
    dtype = jnp.dtype(config["dtype"])
    assumed = config.get("assumed") or {}
    a_lo, a_hi = assumed.get("A_range", (1.0, 16.0))
    dt_lo, dt_hi = assumed.get("dt_range", (0.001, 0.1))
    dt_floor = assumed.get("dt_floor", 1e-4)
    tap_bound = assumed.get("conv_tap_bound", config["mamba_d_conv"] ** -0.5)
    std = assumed.get("weight_std", 0.02)

    @jax.jit
    def make(key):
        out = {}
        for n, (name, (shape, kind)) in enumerate(spec.items()):
            k = jax.random.fold_in(key, n)
            if kind == "one":
                v = jnp.ones(shape, F32)
            elif kind == "zero":
                v = jnp.zeros(shape, F32)
            elif kind == "taps":
                v = jax.random.uniform(k, shape, F32, -tap_bound, tap_bound)
            elif kind == "A_log":
                v = jnp.log(jax.random.uniform(k, shape, F32, a_lo, a_hi))
            elif kind == "dt_bias":
                dt = jnp.exp(jax.random.uniform(k, shape, F32, jnp.log(dt_lo),
                                                jnp.log(dt_hi)))
                dt = jnp.maximum(dt, dt_floor)
                v = dt + jnp.log(-jnp.expm1(-dt))  # softplus' inverse
            else:
                v = std * jax.random.normal(k, shape, F32)
            out[name] = v.astype(dtype)
        return out

    return make(seeds.key(seed, 1))


def batches(config, traffic, seed):
    """A pool of seeded batches: token ids uniform over the sliced vocabulary
    and, as targets, the next token of the same sequence; float32 as the Gluon
    loss takes labels."""
    n, b, t = traffic["pool"], traffic["batch"], traffic["sequence"]

    @jax.jit
    def make(key):
        ids = jax.random.randint(key, (n, b, t + 1), 0, config["vocab_size"])
        return ids[..., :-1].astype(F32), ids[..., 1:].astype(F32)

    x, y = make(seeds.key(seed, 2))
    return [(x[i], y[i]) for i in range(n)]


def dense(x, w, quant=None):
    return jnp.einsum("...c,oc->...o", operand(x, quant), operand(w, quant), precision=HI)


def product(spec, a, b, quant=None):
    return jnp.einsum(spec, operand(a, quant), operand(b, quant), precision=HI)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def swiglu(x, gate, up, down, quant=None):
    return dense(jax.nn.silu(dense(x, gate, quant)) * dense(x, up, quant), down, quant)


def by_head_block(fn, operands, heads, block):
    """``fn`` of ``operands`` (each (b, heads, ...)) a block of heads at a
    time, each block under a checkpoint; (b, heads, ...) again."""
    block = block or heads
    if heads % block:
        raise ValueError("%d heads are not whole blocks of %d" % (heads, block))

    def split(z):  # (b, heads, ...) -> (heads / block, b, block, ...)
        return jnp.moveaxis(z.reshape((z.shape[0], heads // block, block) + z.shape[2:]),
                            1, 0)

    out = jax.lax.map(jax.checkpoint(fn), tuple(split(z) for z in operands))
    out = jnp.moveaxis(out, 0, 1)
    return out.reshape((out.shape[0], heads) + out.shape[3:])


def attention(p, x, a, quant=None, head_block=None):
    """Causal grouped-query attention of (B, T, H), no positions."""
    b, t, _ = x.shape
    heads, kv, d = a["num_attention_heads"], a["num_key_value_heads"], a["head_dim"]
    q = jnp.moveaxis(dense(x, p["q.w"], quant).reshape(b, t, heads, d), 2, 1)
    kvs = jnp.moveaxis(dense(x, p["kv.w"], quant).reshape(b, t, 2 * kv, d), 2, 1)
    per_query = functools.partial(jnp.repeat, repeats=heads // kv, axis=1)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def some_heads(qkv):
        qh, kh, vh = qkv  # (b, g, t, d): the K/V head of each query head
        s = product("bhqd,bhkd->bhqk", qh, kh, quant) * a["attention_multiplier"]
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return product("bhqk,bhkd->bhqd", pr, vh, quant)

    o = by_head_block(some_heads, (q, per_query(kvs[:, :kv]), per_query(kvs[:, kv:])),
                      heads, head_block)
    return dense(jnp.moveaxis(o, 1, 2).reshape(b, t, heads * d), p["o.w"], quant)


def causal_filter(x, w, bias, quant=None):
    """``filter(x) + bias`` of (B, T, C) with taps ``w`` (C, K): the plain
    formula, the sequence padded with K - 1 zeros before its start."""
    t, k = x.shape[1], w.shape[1]
    padded = jnp.pad(operand(x, quant), ((0, 0), (k - 1, 0), (0, 0)))
    return sum(operand(w, quant)[:, j] * padded[:, j:j + t] for j in range(k)) + bias


def segsum(x):
    """Listing 1's segment sum: out[..., i, j] = sum of x[..., j + 1 .. i] for
    i >= j, -inf above the diagonal."""
    t = x.shape[-1]
    x = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), x[..., :, None], 0.0)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), jnp.cumsum(x, axis=-2), -jnp.inf)


def ssd(x, a, B, C, chunk, quant=None, carry=True):
    """Listing 1 of arXiv:2405.21060 for some heads of one group: ``x`` (b, h,
    t, p) already times dt, ``a = dt A`` (b, h, t), the group's ``B`` / ``C``
    (b, t, n), t whole chunks. Returns (b, h, t, p) without the ``D`` term."""
    b, h, t, p = x.shape
    c = t // chunk
    x, a = x.reshape(b, h, c, chunk, p), a.reshape(b, h, c, chunk)
    B, C = B.reshape(b, c, chunk, -1), C.reshape(b, c, chunk, -1)
    a_cum = jnp.cumsum(a, axis=-1)
    # 1. within each chunk
    scores = product("bcln,bcsn->bcls", C, B, quant)
    y = product("bhcls,bhcsp->bhclp", scores[:, None] * jnp.exp(segsum(a)), x, quant)
    if carry:
        # 2. each chunk's closing state from its own tokens
        to_end = jnp.exp(a_cum[..., -1:] - a_cum)
        states = product("bcln,bhclp->bhcpn", B, x * to_end[..., None], quant)
        # 3. the states at the chunks' boundaries
        states = jnp.concatenate([jnp.zeros_like(states[:, :, :1]), states], axis=2)
        across = jnp.exp(segsum(jnp.pad(a_cum[..., -1], ((0, 0), (0, 0), (1, 0)))))
        states = jnp.einsum("bhzc,bhcpn->bhzpn", across, states, precision=HI)[:, :, :-1]
        # 4. each token's read of the state its chunk began with
        y = y + product("bcln,bhcpn->bhclp", C, states, quant) * jnp.exp(a_cum)[..., None]
    return y.reshape(b, h, t, p)


def mamba(p, u, a, quant=None, head_block=None, planted=None):
    """The Mamba-2 mixer of (B, T, hidden)."""
    b, t, _ = u.shape
    heads, hd, groups, n = (a["mamba_n_heads"], a["mamba_d_head"], a["mamba_n_groups"],
                            a["mamba_d_state"])
    inner, chunk = heads * hd, a["mamba_chunk_size"]
    zxbcdt = dense(u, p["in.w"], quant)
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * groups * n], axis=-1)
    xbc = jax.nn.silu(causal_filter(xbc, p["conv.w"], p["conv.bias"], quant))
    x, B, C = jnp.split(xbc, [inner, inner + groups * n], axis=-1)
    x = x.reshape(b, t, heads, hd)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    decay = dt * -jnp.exp(p["A_log"])
    rows = ((0, 0), (0, -t % chunk))

    def by_head(z):  # (b, t, heads, w) -> (b, heads, t', w): whole chunks
        return jnp.moveaxis(jnp.pad(z, rows + ((0, 0), (0, 0))), 2, 1)

    def some_heads(group, operands):  # a block of one group's heads
        xh, ah = operands
        return ssd(xh, ah[..., 0], group[0], group[1], chunk, quant,
                   carry=planted != "carry_dropped")

    per = heads // groups
    B, C = (jnp.pad(z, rows + ((0, 0),)).reshape(b, -1, groups, n) for z in (B, C))
    xdt, decay = by_head(x * dt[..., None]), by_head(decay[..., None])
    y = jnp.concatenate([
        by_head_block(functools.partial(some_heads, (B[:, :, g], C[:, :, g])),
                      (xdt[:, g * per:(g + 1) * per], decay[:, g * per:(g + 1) * per]),
                      per, min(head_block or per, per))
        for g in range(groups)], axis=1)
    y = jnp.moveaxis(y, 1, 2)[:, :t] + p["D"][:, None] * x
    y = y.reshape(b, t, inner)
    if planted == "gate_after_norm":
        y = rms_norm(y, p["gate_norm.g"], a["rms_norm_eps"]) * jax.nn.silu(z)
    else:
        y = rms_norm(y * jax.nn.silu(z), p["gate_norm.g"], a["rms_norm_eps"])
    return dense(y, p["out.w"], quant)


def block(p, h, a, kind, quant=None, knobs=None, planted=None):
    knobs = knobs or {}
    x = rms_norm(h, p["in_norm.g"], a["rms_norm_eps"])
    if kind == "mamba":
        mixed = mamba(p, x, a, quant, knobs.get("scan_head_block"), planted)
    else:
        mixed = attention(p, x, a, quant, knobs.get("head_block"))
    h = h + a["residual_multiplier"] * mixed
    x = rms_norm(h, p["post_norm.g"], a["rms_norm_eps"])
    return h + a["residual_multiplier"] * swiglu(x, p["gate.w"], p["up.w"], p["down.w"],
                                                 quant)


def hidden(config, params, x, quant=None, planted=None):
    """The last norm's output (B, T, hidden) and the float32 leaves."""
    a = arch(config)
    p = {k: v.astype(F32) for k, v in params.items()}
    knobs = config.get("reference") or {}
    planted = planted or knobs.get("planted")
    h = a["embedding_multiplier"] * p["embed.w"][x.astype(jnp.int32)]
    for l, kind in enumerate(a["layer_types"]):
        pre = "l%d." % l
        lp = {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}
        h = jax.checkpoint(functools.partial(
            block, a=a, kind=kind, quant=quant, knobs=knobs, planted=planted))(lp, h)
    return rms_norm(h, p["norm.g"], a["rms_norm_eps"]), p


def logits(config, params, x, quant=None):
    h, p = hidden(config, params, x, quant)
    # the tied head: the embedding's rows are the scores' weights
    return dense(h, p["embed.w"], quant) / config["logits_scaling"]


def value_and_grad(config, params, x, y, quant=None):
    """Mean loss over every position of the batch, and its gradient. ``quant``
    is the control's precision or one of ``PLANTED``."""
    planted, quant = (quant, None) if quant in PLANTED else (None, quant)
    rows = (config.get("reference") or {}).get("loss_rows")

    def picked(h, w, targets):  # -log p(target) summed over some rows
        logp = jax.nn.log_softmax(dense(h, w, quant) / config["logits_scaling"], axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], axis=-1))

    def loss(p):
        h, p32 = hidden(config, p, x, quant, planted)
        h, targets = h.reshape(-1, h.shape[-1]), y.astype(jnp.int32).reshape(-1)
        n = h.shape[0]
        if not rows or n % rows:
            return picked(h, p32["embed.w"], targets) / n
        parts = jax.lax.map(
            lambda ht: jax.checkpoint(picked)(ht[0], p32["embed.w"], ht[1]),
            (h.reshape(n // rows, rows, -1), targets.reshape(n // rows, rows)))
        return jnp.sum(parts) / n

    return jax.value_and_grad(loss)(params)
