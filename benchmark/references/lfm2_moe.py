"""Plain reference: LFM2's sparse decoder (``model_type: lfm2_moe``;
LiquidAI/LFM2-24B-A2B; the sizes come from the configuration's file) with
next-token cross-entropy, in straightforward ``jax.numpy`` float32 with matmul
precision "highest". No kernels, no program code, nothing the program made.
Written from the layer equations (LFM2 technical report; transformers'
``modeling_lfm2_moe.py``):

Block i, pre-norm, no bias anywhere: ``h' = h + mixer_i(RMSNorm(h))``,
``h'' = h' + ffn_i(RMSNorm(h'))``; a last RMSNorm, then the head, whose weight
is the embedding's (one leaf, ``embed.w``, used twice).

* Mixer where ``layer_types[i]`` is ``conv``. ``[Bg, Cg, X] = W_in x`` (3 x
  hidden, in that order); ``Z = Bg * X``; ``V[t] = sum_j w[:, j] Z[t - (K - 1)
  + j]`` over the ``K = conv_L_cache`` taps of one filter a channel, ``Z`` zero
  before the sequence's start (``Conv1d(groups=hidden, padding=K - 1)`` cut to
  the sequence's length), each sequence of the batch alone; ``Y = Cg * V``;
  ``W_out Y``. No activation.
* Mixer where it is ``full_attention``. ``q = W_q x`` as 32 heads of 64,
  ``[k, v] = W_kv x`` as 8 + 8 heads of 64 (``W_kv`` is ``W_k`` over ``W_v``:
  one leaf, the same function of the same entries); an RMSNorm over the 64 of
  each head of q and of k (one weight vector each); rotate-half rotary over
  the whole head; query head i reads K/V head ``i // 4``; causal softmax at
  scale ``64 ** -0.5``; ``W_o`` of the heads side by side. Scores are
  materialised, a few heads at a time.
* ffn for ``i < num_dense_layers``: SwiGLU ``W_2 (silu(W_1 x) * W_3 x)``.
  Otherwise the expert layer: ``s = sigmoid(W_g x)`` over all the published
  experts; chosen: the ``num_experts_per_tok`` largest of ``s + expert_bias``
  (a buffer with no gradient); ``w_e = routed_scaling_factor * s_e / (sum of
  the chosen s + 1e-6)``; ``y = sum over the chosen e of w_e SwiGLU_e(x)``. No
  shared expert, no capacity, no dropped token. A loop over the experts HELD,
  one at a time (``experts_held`` of the configuration): the reference is
  given the same share as the program, and what the other experts would have
  added is left out of both.
* Loss. Mean cross-entropy over every position of the sliced vocabulary.

Departures, all of them the configuration's ``assumed``: the bias's update
rule is not run, ``expert_bias`` is seeded and fixed; where ``assumed`` says
``router_trained: false``, no gradient passes through the chosen experts'
weights, to the router's weights or to the layer's input (a share of the
experts trained alone has only a part of that gradient). Each block runs under
``jax.checkpoint`` and an expert under a checkpoint of its own, so that float32
at the timed size fits beside the follower's state.
"""
from __future__ import annotations

import functools
import math
from collections import OrderedDict

import jax
import jax.numpy as jnp

from harness import seeds
from harness.quant import operand

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
SUM_EPSILON = 1e-6  # Lfm2MoeSparseMoeBlock: weights / (their sum + 1e-6)


def arch(config):
    """The sizes as run: the file's own keys, with the router as wide as
    published and this chip's share of the experts."""
    a = {k: config[k] for k in (
        "hidden_size", "num_hidden_layers", "num_dense_layers", "conv_L_cache",
        "num_attention_heads", "num_key_value_heads", "norm_eps",
        "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
        "routed_scaling_factor", "vocab_size")}
    a["layer_types"] = tuple(config["layer_types"])
    a["head_dim"] = a["hidden_size"] // a["num_attention_heads"]
    a["rope_theta"] = config["rope_parameters"]["rope_theta"]
    a["router_width"] = config["published"]["num_experts"]
    a["experts_held"] = tuple(config["experts_held"])
    a["router_trained"] = router_trained(config)
    if a["experts_held"][1] != config["num_experts"]:
        raise ValueError("experts_held and num_experts (held here) differ")
    if len(a["layer_types"]) != a["num_hidden_layers"]:
        raise ValueError("layer_types names %d layers of %d"
                         % (len(a["layer_types"]), a["num_hidden_layers"]))
    return a


def router_trained(config):
    """Whether the routers' weights take their gradient: ``assumed``'s
    ``router_trained``, true where the file does not say."""
    return bool((config.get("assumed") or {}).get("router_trained", True))


def leaves(config):
    """{leaf: (shape, how it starts)}; weights as ``FullyConnected`` keeps
    them, (out, in), the experts stacked (held, in, out), the taps (hidden,
    K)."""
    a = arch(config)
    h, heads, kv, d = (a["hidden_size"], a["num_attention_heads"],
                       a["num_key_value_heads"], a["head_dim"])
    held, width = a["experts_held"][1], a["moe_intermediate_size"]
    out = OrderedDict()
    out["embed.w"] = ((a["vocab_size"], h), "normal")
    for l, kind in enumerate(a["layer_types"]):
        p = "l%d." % l
        out[p + "op_norm.g"] = ((h,), "one")
        if kind == "conv":
            out[p + "in.w"] = ((3 * h, h), "normal")
            out[p + "conv.w"] = ((h, a["conv_L_cache"]), "taps")
            out[p + "out.w"] = ((h, h), "normal")
        elif kind == "full_attention":
            out[p + "q.w"] = ((heads * d, h), "normal")
            out[p + "kv.w"] = ((2 * kv * d, h), "normal")
            out[p + "q_norm.g"] = ((d,), "one")
            out[p + "k_norm.g"] = ((d,), "one")
            out[p + "o.w"] = ((h, heads * d), "normal")
        else:
            raise ValueError("no mixer named %r" % (kind,))
        out[p + "ffn_norm.g"] = ((h,), "one")
        if l < a["num_dense_layers"]:
            i = a["intermediate_size"]
            out[p + "gate.w"], out[p + "up.w"] = ((i, h), "normal"), ((i, h), "normal")
            out[p + "down.w"] = ((h, i), "normal")
            continue
        out[p + "router.w"] = ((a["router_width"], h), "normal")
        out[p + "router.bias"] = ((a["router_width"],), "bias")
        out[p + "experts.gate"] = ((held, h, width), "normal")
        out[p + "experts.up"] = ((held, h, width), "normal")
        out[p + "experts.down"] = ((held, width, h), "normal")
    out["norm.g"] = ((h,), "one")
    return out


def init(config, seed):
    """Seeded weights, all in the type they are trained in: N(0, 0.02), norms
    1, and from ``assumed`` where the file gives them the taps' ``conv_tap_std``
    and the selection bias's ``expert_bias_std`` (its values are then exact in
    the program's float32 buffer and in the follower's stored type)."""
    spec = leaves(config)
    dtype = jnp.dtype(config["dtype"])
    assumed = config.get("assumed") or {}
    stds = {"normal": 0.02, "taps": assumed.get("conv_tap_std", 0.02),
            "bias": assumed.get("expert_bias_std", 0.01)}

    @jax.jit
    def make(key):
        out = {}
        for n, (name, (shape, kind)) in enumerate(spec.items()):
            if kind == "one":
                v = jnp.ones(shape, F32)
            else:
                v = stds[kind] * jax.random.normal(jax.random.fold_in(key, n), shape, F32)
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


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * g


def swiglu(x, gate, up, down, quant=None):
    return dense(jax.nn.silu(dense(x, gate, quant)) * dense(x, up, quant), down, quant)


def rope(x, theta):
    """Rotary positions on the last axis of (..., T, D), position along the
    axis before it. HF's form: ``x * cos + rotate_half(x) * sin`` with the
    D/2 frequencies written twice."""
    t, d = x.shape[-2], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    freqs = jnp.arange(t, dtype=F32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


def gated_conv(bcx, w, quant=None):
    """``Cg * filter(Bg * X)`` of (B, T, 3C) with taps ``w`` (C, K): the plain
    formula, the sequence padded with K - 1 zeros before its start."""
    t, k = bcx.shape[1], w.shape[1]
    bg, cg, x = jnp.split(bcx, 3, axis=-1)
    z = operand(bg, quant) * operand(x, quant)
    padded = jnp.pad(operand(z, quant), ((0, 0), (k - 1, 0), (0, 0)))
    v = sum(operand(w, quant)[:, j] * padded[:, j:j + t] for j in range(k))
    return operand(cg, quant) * operand(v, quant)


def short_conv(p, x, quant=None):
    """The gated short convolution between its two projections."""
    return dense(gated_conv(dense(x, p["in.w"], quant), p["conv.w"], quant),
                 p["out.w"], quant)


def attention(p, x, a, quant=None, head_block=None):
    """Causal grouped-query attention of (B, T, H)."""
    b, t, _ = x.shape
    heads, kv, d = a["num_attention_heads"], a["num_key_value_heads"], a["head_dim"]
    group = heads // kv
    q = dense(x, p["q.w"], quant).reshape(b, t, heads, d)
    kvs = dense(x, p["kv.w"], quant).reshape(b, t, 2 * kv, d)
    k, v = kvs[:, :, :kv], kvs[:, :, kv:]
    q = rms_norm(q, p["q_norm.g"], a["norm_eps"])
    k = rms_norm(k, p["k_norm.g"], a["norm_eps"])
    q = rope(jnp.moveaxis(q, 2, 1), a["rope_theta"])  # (b, heads, t, d)
    k = rope(jnp.moveaxis(k, 2, 1), a["rope_theta"])  # (b, kv, t, d)
    v = jnp.moveaxis(v, 2, 1)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def some_heads(qkv):
        qh, kh, vh = qkv  # (b, g, t, d): the K/V head of each query head
        s = jnp.einsum("bhqd,bhkd->bhqk", operand(qh, quant), operand(kh, quant),
                       precision=HI) / math.sqrt(d)
        pr = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", operand(pr, quant), operand(vh, quant),
                          precision=HI)

    g = head_block or heads
    if heads % g:
        raise ValueError("%d heads are not whole blocks of %d" % (heads, g))

    def split(z):  # (b, heads, t, d) -> (heads / g, b, g, t, d)
        return jnp.moveaxis(z.reshape(b, heads // g, g, t, z.shape[-1]), 1, 0)

    per_query = functools.partial(jnp.repeat, repeats=group, axis=1)
    o = jax.lax.map(jax.checkpoint(some_heads),
                    (split(q), split(per_query(k)), split(per_query(v))))
    o = jnp.moveaxis(o, 0, 1).reshape(b, heads, t, d)
    return dense(jnp.moveaxis(o, 1, 2).reshape(b, t, heads * d), p["o.w"], quant)


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


def moe(p, x, a, quant=None, experts_held=None):
    """The expert layer of (N, H) tokens: the part of the result that the
    experts ``experts_held=(first, count)`` give. ``p["experts.*"]`` hold
    those experts alone."""
    first, count = experts_held or a["experts_held"]
    w = routing(p, x, a, quant)

    def one(y, expert):  # plain: every held expert sees every token
        gate, up, down, weight = expert
        return y + weight[:, None] * swiglu(x, gate.T, up.T, down.T, quant), None

    # a loop over the experts held, one at a time (``lax.scan`` and not
    # Python's ``for``: the chip's compiler then builds one expert, not 64)
    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(x), (
        p["experts.gate"], p["experts.up"], p["experts.down"],
        w[:, first:first + count].T))
    return y


def block(p, h, a, kind, dense_ffn, quant=None, head_block=None):
    x = rms_norm(h, p["op_norm.g"], a["norm_eps"])
    if kind == "conv":
        h = h + short_conv(p, x, quant)
    else:
        h = h + attention(p, x, a, quant, head_block)
    x = rms_norm(h, p["ffn_norm.g"], a["norm_eps"])
    if dense_ffn:
        return h + swiglu(x, p["gate.w"], p["up.w"], p["down.w"], quant)
    return h + moe(p, x.reshape(-1, x.shape[-1]), a, quant).reshape(x.shape)


def logits(config, params, x, quant=None):
    a = arch(config)
    p = {k: v.astype(F32) for k, v in params.items()}
    head_block = (config.get("reference") or {}).get("head_block")
    h = p["embed.w"][x.astype(jnp.int32)]
    for l, kind in enumerate(a["layer_types"]):
        pre = "l%d." % l
        lp = {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}
        h = jax.checkpoint(functools.partial(
            block, a=a, kind=kind, dense_ffn=l < a["num_dense_layers"], quant=quant,
            head_block=head_block))(lp, h)
    # the tied head: the embedding's rows are the scores' weights
    return dense(rms_norm(h, p["norm.g"], a["norm_eps"]), p["embed.w"], quant)


def value_and_grad(config, params, x, y, quant=None):
    """Mean loss over every position of the batch, and its gradient."""
    def loss(p):
        logp = jax.nn.log_softmax(logits(config, p, x, quant), axis=-1)
        picked = jnp.take_along_axis(logp, y.astype(jnp.int32)[..., None], axis=-1)
        return -jnp.mean(picked)

    return jax.value_and_grad(loss)(params)
