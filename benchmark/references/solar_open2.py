"""Plain reference: Solar Open 2's hybrid sparse decoder (``model_type:
solar_open2``; upstage/Solar-Open2-250B; the sizes come from the
configuration's file) with next-token cross-entropy, in straightforward
``jax.numpy`` float32 with matmul precision "highest". No kernels, no custom
backward, no chunks, no program code, nothing the program made. Written from
the layer equations (Kimi Team, "Kimi Linear", arXiv:2510.26692, section 3 and
its ``KimiDeltaAttention``, for the mixer; Qiu et al., "Gated Attention for
Large Language Models", arXiv:2505.06708, for the attention's gate; the
family's own ``modeling_solar_open.py``, which is GLM-4.5's, for the expert
layer):

``h = embed(ids)``; block i, pre-norm, RMSNorm with a weight, no bias but the
output gate's: ``h' = h + mixer_i(RMSNorm(h))``, ``h'' = h' +
moe(RMSNorm(h'))``; a last RMSNorm, then the untied head.

* Mixer where i is in ``gqa_layers``. ``q = W_q u`` as H heads of D, ``[k, v]
  = W_kv u`` as G + G heads of D (``W_k`` over ``W_v``, one leaf); no
  positions, no norm on a head; query head i reads K/V head ``i // (H / G)``;
  causal softmax of the scores times ``D ** -0.5``; ``W_o (attn *
  sigmoid(W_gate u))``, the gate an element of every head. Scores are
  materialised, a few heads at a time.
* Mixer elsewhere: Kimi's delta attention, H heads of K = V = ``head_dim``.
  ``[q, k, v] = silu(filter(W_qkv u))``, ``filter[t] = sum_j w[:, j] x[t - (taps
  - 1) + j]`` as shifted sums, zeros before the sequence, no bias; a head, ``q =
  q / sqrt(|q|^2 + 1e-6) * K ** -0.5``, ``k = k / sqrt(|k|^2 + 1e-6)``; ``g_t =
  -exp(A_log_h) * softplus(W_fb (W_fa u_t) + dt_bias)``, a log-decay a channel;
  ``beta_t = 2 * sigmoid(W_b u_t)_h``; the state S a (K, V) matrix a head, zero
  before the sequence, and **token by token** (a ``lax.scan`` over t, plain
  autodiff)::

      S' = Diag(exp(g_t)) S_{t-1}
      S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
      o_t = S_t^T q_t

  ``y_t = RMSNorm_K(o_t) * w * sigmoid(W_gb (W_ga u_t) + b_g)``, the norm a
  head and THEN the gate; ``W_o y``.
* ``moe(u)``: ``s = sigmoid(W_r u)`` over all the published experts; chosen:
  the ``num_experts_per_tok`` largest of ``s + bias`` (a buffer with no
  gradient); ``w_e = routed_scaling_factor * s_e / (sum of the chosen s +
  1e-20)``; ``y = sum over the chosen e of w_e SwiGLU_e(u) + SwiGLU_shared(u)``.
  No capacity, no dropped token. A loop over the experts HELD, one at a time
  (``experts_held`` of the configuration): the reference is given the same
  share as the program, and what the other experts would have added is left
  out of both.
* Loss. Mean cross-entropy over every position of the sliced vocabulary.

What the config's keys do not fix, all of it the configuration's ``assumed``:
the gate's form (``use_gqa_gate`` names it, not its shape: the element-wise
form is the one the cited paper recommends); the decay's and the output gate's
low-rank pairs of rank ``head_dim`` (``kda_use_full_proj: false``); sigmoid
scoring with a selection bias and SwiGLU experts (no ``scoring_func`` or
``hidden_act`` key: the family's earlier model's); the bias's update rule is
not run, it is seeded and fixed; where ``assumed`` says ``router_trained:
false`` no gradient passes through the chosen experts' weights. The heads are
the heads this chip holds (``num_attention_heads``, ``num_key_value_heads``,
``linear_attn_config.num_heads`` of the file): a head's work does not depend
on the other heads, and the sum of the shares' ``W_o`` results is the uncut
layer's (``benchmark/SHARES_solar_open2.md``). So that float32 at the timed
size fits beside the follower's state, each block runs under
``jax.checkpoint``, the recurrence and the attention a block of heads at a
time (``reference.kda_head_block``, ``reference.head_block``: heads do not see
each other), an expert under a checkpoint of its own, and the head and the
loss a block of rows at a time (``reference.loss_rows``).

Faults the reference can plant in itself, for the calibration tools and the
CPU tests, never in a configuration's file, as ``quant`` of ``value_and_grad``
or as ``reference.planted``: ``carry_dropped`` (the state is zero again every
``assumed.chunk`` tokens: a chunked program whose chunks each open on
nothing), ``beta_not_doubled`` (``kda_allow_neg_eigval`` ignored),
``gate_before_norm`` (``RMSNorm(o * sigmoid(gate))``), ``decay_head_mean`` (a
head's channels all decay by their mean) and ``no_gqa_gate``.
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
SUM_EPSILON = 1e-20  # the family's router: weights / (their sum + 1e-20)
NORM_EPSILON = 1e-6  # the unit length of q and k
PLANTED = ("carry_dropped", "beta_not_doubled", "gate_before_norm", "decay_head_mean",
           "no_gqa_gate")


def arch(config):
    """The sizes as run: the file's own keys, with the router as wide as
    published and this chip's share of the experts."""
    a = {k: config[k] for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "moe_intermediate_size",
        "n_shared_experts", "num_experts_per_tok", "routed_scaling_factor",
        "rms_norm_eps", "vocab_size")}
    lin = config["linear_attn_config"]
    a["kda_heads"], a["kda_dim"] = lin["num_heads"], lin["head_dim"]
    a["taps"] = lin["short_conv_kernel_size"]
    a["gqa_layers"] = tuple(config["gqa_layers"])
    a["router_width"] = config["published"]["n_routed_experts"]
    a["experts_held"] = tuple(config["experts_held"])
    a["router_trained"] = router_trained(config)
    a["beta_scale"] = 2.0 if config.get("kda_allow_neg_eigval") else 1.0
    a["gqa_gate"] = bool(config.get("use_gqa_gate"))
    a["chunk"] = (config.get("assumed") or {}).get("chunk", 64)
    if a["experts_held"][1] != config["n_routed_experts"]:
        raise ValueError("experts_held and n_routed_experts (held here) differ")
    return a


def router_trained(config):
    """Whether the routers' weights take their gradient: ``assumed``'s
    ``router_trained``, true where the file does not say."""
    return bool((config.get("assumed") or {}).get("router_trained", True))


def leaves(config):
    """{leaf: (shape, how it starts)}; weights as ``FullyConnected`` keeps
    them, (out, in), the experts stacked (held, in, out), the taps (channels,
    K)."""
    a = arch(config)
    h, heads, kv, d = (a["hidden_size"], a["num_attention_heads"],
                       a["num_key_value_heads"], a["head_dim"])
    kh, kd = a["kda_heads"], a["kda_dim"]
    inner = kh * kd
    held, width = a["experts_held"][1], a["moe_intermediate_size"]
    shared = a["n_shared_experts"] * width
    out = OrderedDict()
    out["embed.w"] = ((a["vocab_size"], h), "normal")
    for l in range(a["num_hidden_layers"]):
        p = "l%d." % l
        out[p + "in_norm.g"] = ((h,), "one")
        if l in a["gqa_layers"]:
            out[p + "q.w"] = ((heads * d, h), "normal")
            out[p + "kv.w"] = ((2 * kv * d, h), "normal")
            if a["gqa_gate"]:
                out[p + "gate.w"] = ((heads * d, h), "normal")
            out[p + "o.w"] = ((h, heads * d), "normal")
        else:
            out[p + "qkv.w"] = ((3 * inner, h), "normal")
            out[p + "conv.w"] = ((3 * inner, a["taps"]), "taps")
            out[p + "fa.w"], out[p + "fb.w"] = ((kd, h), "normal"), ((inner, kd), "normal")
            out[p + "A_log"] = ((kh,), "A_log")
            out[p + "dt_bias"] = ((inner,), "dt_bias")
            out[p + "b.w"] = ((kh, h), "normal")
            out[p + "ga.w"], out[p + "gb.w"] = ((kd, h), "normal"), ((inner, kd), "normal")
            out[p + "gb.bias"] = ((inner,), "zero")
            out[p + "o_norm.g"] = ((kd,), "one")
            out[p + "out.w"] = ((h, inner), "normal")
        out[p + "post_norm.g"] = ((h,), "one")
        out[p + "router.w"] = ((a["router_width"], h), "normal")
        out[p + "router.bias"] = ((a["router_width"],), "bias")
        out[p + "experts.gate"] = ((held, h, width), "normal")
        out[p + "experts.up"] = ((held, h, width), "normal")
        out[p + "experts.down"] = ((held, width, h), "normal")
        if shared:
            out[p + "shared.gate.w"] = ((shared, h), "normal")
            out[p + "shared.up.w"] = ((shared, h), "normal")
            out[p + "shared.down.w"] = ((h, shared), "normal")
    out["norm.g"] = ((h,), "one")
    out["head.w"] = ((a["vocab_size"], h), "normal")
    return out


def init(config, seed):
    """Seeded weights, all in the type they are trained in: N(0,
    ``weight_std``) (0.02 where the file does not say), norms 1, the output
    gate's bias 0, the selection bias N(0, ``expert_bias_std``), and the
    paper's code's draws for what shapes the recurrence (``assumed``): the taps
    uniform within ``conv_tap_bound`` (``taps ** -0.5``, the ``Conv1d``
    default), ``A_log`` the log of a uniform draw in ``A_range`` a head,
    ``dt_bias`` the inverse softplus of a log-uniform draw in ``dt_range`` a
    channel."""
    spec = leaves(config)
    dtype = jnp.dtype(config["dtype"])
    assumed = config.get("assumed") or {}
    a_lo, a_hi = assumed.get("A_range", (1.0, 16.0))
    dt_lo, dt_hi = assumed.get("dt_range", (0.001, 0.1))
    taps = config["linear_attn_config"]["short_conv_kernel_size"]
    tap_bound = assumed.get("conv_tap_bound", taps ** -0.5)
    std = assumed.get("weight_std", 0.02)
    bias_std = assumed.get("expert_bias_std", 0.001)

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
                v = dt + jnp.log(-jnp.expm1(-dt))  # softplus' inverse
            elif kind == "bias":
                v = bias_std * jax.random.normal(k, shape, F32)
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


def attention(p, x, a, quant=None, head_block=None, planted=None):
    """Causal grouped-query attention of (B, T, H), no positions, with the
    output gate where the leaves hold one."""
    b, t, _ = x.shape
    heads, kv, d = a["num_attention_heads"], a["num_key_value_heads"], a["head_dim"]
    q = jnp.moveaxis(dense(x, p["q.w"], quant).reshape(b, t, heads, d), 2, 1)
    kvs = jnp.moveaxis(dense(x, p["kv.w"], quant).reshape(b, t, 2 * kv, d), 2, 1)
    per_query = functools.partial(jnp.repeat, repeats=heads // kv, axis=1)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def some_heads(qkv):
        qh, kh, vh = qkv  # (b, g, t, d): the K/V head of each query head
        s = product("bhqd,bhkd->bhqk", qh, kh, quant) * d ** -0.5
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return product("bhqk,bhkd->bhqd", pr, vh, quant)

    o = by_head_block(some_heads, (q, per_query(kvs[:, :kv]), per_query(kvs[:, kv:])),
                      heads, head_block)
    o = jnp.moveaxis(o, 1, 2).reshape(b, t, heads * d)
    if "gate.w" in p and planted != "no_gqa_gate":
        o = o * jax.nn.sigmoid(dense(x, p["gate.w"], quant))
    return dense(o, p["o.w"], quant)


def causal_filter(x, w, quant=None):
    """``filter(x)`` of (B, T, C) with taps ``w`` (C, K): the plain formula,
    the sequence padded with K - 1 zeros before its start."""
    t, k = x.shape[1], w.shape[1]
    padded = jnp.pad(operand(x, quant), ((0, 0), (k - 1, 0), (0, 0)))
    return sum(operand(w, quant)[:, j] * padded[:, j:j + t] for j in range(k))


def delta_rule(q, k, v, g, beta, quant=None, reset_every=None):
    """The recurrence of the module's docstring, token by token, for some
    heads: ``q``, ``k``, ``g`` (b, h, t, K), ``v`` (b, h, t, V), ``beta`` (b, h,
    t). Returns (b, h, t, V). ``reset_every`` is the planted fault's: the state
    is zero again every so many tokens."""
    b, h, t, _ = q.shape

    def step(s, xs):
        qt, kt, vt, gt, bt, keep = xs
        s = jnp.exp(gt)[..., None] * s * keep
        seen = product("bhkv,bhk->bhv", s, kt, quant)
        s = s + product("bhk,bhv->bhkv", bt[..., None] * kt, vt - seen, quant)
        return s, product("bhkv,bhk->bhv", s, qt, quant)

    keep = jnp.ones((t,), F32)
    if reset_every:
        keep = (jnp.arange(t) % reset_every != 0).astype(F32)
    xs = tuple(jnp.moveaxis(z, 2, 0) for z in (q, k, v, g, beta)) + (keep,)
    _, o = jax.lax.scan(step, jnp.zeros((b, h, q.shape[-1], v.shape[-1]), F32), xs)
    return jnp.moveaxis(o, 0, 2)


def kda(p, u, a, quant=None, head_block=None, planted=None):
    """Kimi's delta attention of (B, T, hidden)."""
    b, t, _ = u.shape
    heads, d = a["kda_heads"], a["kda_dim"]
    inner = heads * d
    qkv = jax.nn.silu(causal_filter(dense(u, p["qkv.w"], quant), p["conv.w"], quant))

    def by_head(z):  # (b, t, heads * w) -> (b, heads, t, w)
        return jnp.moveaxis(z.reshape(b, t, heads, -1), 2, 1)

    q, k, v = (by_head(z) for z in jnp.split(qkv, 3, axis=-1))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + NORM_EPSILON) * d ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + NORM_EPSILON)
    step = jax.nn.softplus(dense(dense(u, p["fa.w"], quant), p["fb.w"], quant)
                           + p["dt_bias"])
    g = -jnp.exp(p["A_log"])[:, None, None] * by_head(step)
    if planted == "decay_head_mean":
        g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
    beta = jnp.moveaxis(jax.nn.sigmoid(dense(u, p["b.w"], quant)), 2, 1)
    if planted != "beta_not_doubled":
        beta = beta * a["beta_scale"]
    reset = a["chunk"] if planted == "carry_dropped" else None
    o = by_head_block(
        lambda xs: delta_rule(*xs, quant=quant, reset_every=reset),
        (q, k, v, g, beta), heads, head_block)
    o = jnp.moveaxis(o, 1, 2)  # (b, t, heads, d)
    gate = jax.nn.sigmoid(dense(dense(u, p["ga.w"], quant), p["gb.w"], quant)
                          + p["gb.bias"]).reshape(b, t, heads, d)
    if planted == "gate_before_norm":
        y = rms_norm(o * gate, p["o_norm.g"], a["rms_norm_eps"])
    else:
        y = rms_norm(o, p["o_norm.g"], a["rms_norm_eps"]) * gate
    return dense(y.reshape(b, t, inner), p["out.w"], quant)


def routing(p, x, a, quant=None):
    """(N, router_width) weights: 0 where an expert is not chosen. Where the
    configuration says ``router_trained: false`` they are constants of the
    loss: no gradient reaches the router's weights or ``x`` through them."""
    s = jax.nn.sigmoid(dense(x, p["router.w"], quant))
    choice = jax.lax.stop_gradient(s + p["router.bias"])
    kth = jnp.sort(choice, axis=-1)[:, -a["num_experts_per_tok"]][:, None]
    chosen = jnp.where(choice >= kth, s, 0.0)
    w = a["routed_scaling_factor"] * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + SUM_EPSILON)
    return w if a["router_trained"] else jax.lax.stop_gradient(w)


def moe(p, x, a, quant=None, experts_held=None, shared=True):
    """The expert layer of (N, H) tokens: the part of the result that the
    experts ``experts_held=(first, count)`` give, and (``shared``) the shared
    expert's. ``p["experts.*"]`` hold those experts alone."""
    first, count = experts_held or a["experts_held"]
    w = routing(p, x, a, quant)

    def one(y, expert):  # plain: every held expert sees every token
        gate, up, down, weight = expert
        return y + weight[:, None] * swiglu(x, gate.T, up.T, down.T, quant), None

    # a loop over the experts held, one at a time (``lax.scan`` and not
    # Python's ``for``: the chip's compiler then builds one expert)
    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(x), (
        p["experts.gate"], p["experts.up"], p["experts.down"],
        w[:, first:first + count].T))
    if shared and "shared.gate.w" in p:
        y = y + swiglu(x, p["shared.gate.w"], p["shared.up.w"], p["shared.down.w"], quant)
    return y


def block(p, h, a, gqa, quant=None, knobs=None, planted=None):
    knobs = knobs or {}
    x = rms_norm(h, p["in_norm.g"], a["rms_norm_eps"])
    if gqa:
        h = h + attention(p, x, a, quant, knobs.get("head_block"), planted)
    else:
        h = h + kda(p, x, a, quant, knobs.get("kda_head_block"), planted)
    x = rms_norm(h, p["post_norm.g"], a["rms_norm_eps"])
    return h + moe(p, x.reshape(-1, x.shape[-1]), a, quant).reshape(x.shape)


def hidden(config, params, x, quant=None, planted=None):
    """The last norm's output (B, T, hidden) and the float32 leaves."""
    a = arch(config)
    p = {k: v.astype(F32) for k, v in params.items()}
    knobs = config.get("reference") or {}
    planted = planted or knobs.get("planted")
    h = p["embed.w"][x.astype(jnp.int32)]
    for l in range(a["num_hidden_layers"]):
        pre = "l%d." % l
        lp = {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}
        h = jax.checkpoint(functools.partial(
            block, a=a, gqa=l in a["gqa_layers"], quant=quant, knobs=knobs,
            planted=planted))(lp, h)
    return rms_norm(h, p["norm.g"], a["rms_norm_eps"]), p


def logits(config, params, x, quant=None, planted=None):
    h, p = hidden(config, params, x, quant, planted)
    return dense(h, p["head.w"], quant)


def value_and_grad(config, params, x, y, quant=None):
    """Mean loss over every position of the batch, and its gradient. ``quant``
    is the control's precision or one of ``PLANTED``."""
    planted, quant = (quant, None) if quant in PLANTED else (None, quant)
    rows = (config.get("reference") or {}).get("loss_rows")

    def picked(h, w, targets):  # -log p(target) summed over some rows
        logp = jax.nn.log_softmax(dense(h, w, quant), axis=-1)
        return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], axis=-1))

    def loss(p):
        h, p32 = hidden(config, p, x, quant, planted)
        h, targets = h.reshape(-1, h.shape[-1]), y.astype(jnp.int32).reshape(-1)
        n = h.shape[0]
        if not rows or n % rows:
            return picked(h, p32["head.w"], targets) / n
        parts = jax.lax.map(
            lambda ht: jax.checkpoint(picked)(ht[0], p32["head.w"], ht[1]),
            (h.reshape(n // rows, rows, -1), targets.reshape(n // rows, rows)))
        return jnp.sum(parts) / n

    return jax.value_and_grad(loss)(params)
