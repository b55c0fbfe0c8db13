"""Plain reference: SmallThinker's sparse decoder (PowerInfer,
SmallThinker-21BA3B-Instruct; the sizes come from the configuration's file) with
next-token cross-entropy, in straightforward ``jax.numpy`` float32 with matmul
precision "highest". No kernels, no program code, nothing the program made.
Written from the layer equations ("SmallThinker: A Family of Efficient Large
Language Models Natively Trained for Local Deployment", arXiv:2507.20984; the
family's ``modeling_smallthinker.py``):

Block l, pre-norm, no bias anywhere: ``x = RMSNorm(h)``, ``h' = h +
attention_l(x)``, ``h'' = h' + experts(RMSNorm(h'), routed by x)``; a last
RMSNorm, then the untied head.

* Attention. ``q = W_q x`` as 28 heads of 128, ``[k, v] = W_kv x`` as 4 + 4
  heads of 128 (``W_kv`` is ``W_k`` over ``W_v``: one leaf, the same function of
  the same entries); NO norm on q or k; where ``rope_layout[l]`` is 1
  rotate-half rotary over the whole head, where it is 0 no positions at all;
  query head i reads K/V head ``i // 7``; softmax at scale ``128 ** -0.5`` over
  the keys ``j <= i`` and, where ``sliding_window_layout[l]`` is 1, ``i - j <
  sliding_window_size`` (the window as a mask: a query sees itself and the
  4095 keys before it, transformers' convention); ``W_o`` of the heads side by
  side. Scores are materialised, a few heads at a time.
* Experts. The router reads the ATTENTION's input: ``r = W_r x`` over all the
  published experts, ``p = softmax(r)``; chosen: the
  ``moe_num_active_primary_experts`` largest; ``w_e = p_e / (sum of the chosen
  p)`` (``norm_topk_prob``: the same as a softmax over the chosen logits);
  ``m = sum over the chosen e of w_e W_down,e (relu(W_gate,e y) * W_up,e y)``
  with ``y = RMSNorm(h')``: ReGLU. No shared expert, no bias, no capacity, no
  dropped token. A loop over the experts HELD, one at a time
  (``experts_held`` of the configuration): the reference is given the same
  share as the program, and what the other experts would have added is left
  out of both.
* Loss. Mean cross-entropy over every position of the sliced vocabulary.

Departures, all of them the configuration's ``assumed``: where it says
``router_trained: false``, no gradient passes through the chosen experts'
weights, to the router's weights or to the attention's input (a share of the
experts trained alone has only a part of that gradient). Each block runs under
``jax.checkpoint`` and an expert under a checkpoint of its own, so that float32
at the timed size fits beside the follower's state. ``window_short`` (never in
a configuration's file: the CPU tests' planted fault) shortens every window by
that many keys.
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


def arch(config):
    """The sizes as run: the file's own keys, with the router as wide as
    published and this chip's share of the experts."""
    a = {k: config[k] for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta",
        "sliding_window_size", "moe_ffn_hidden_size",
        "moe_num_active_primary_experts", "vocab_size")}
    a["rope_layout"] = tuple(config["rope_layout"])
    a["sliding_window_layout"] = tuple(config["sliding_window_layout"])
    a["router_width"] = config["published"]["moe_num_primary_experts"]
    a["experts_held"] = tuple(config["experts_held"])
    a["router_trained"] = router_trained(config)
    if a["experts_held"][1] != config["moe_num_primary_experts"]:
        raise ValueError("experts_held and moe_num_primary_experts (held here) differ")
    for key in ("rope_layout", "sliding_window_layout"):
        if len(a[key]) != a["num_hidden_layers"]:
            raise ValueError("%s names %d layers of %d"
                             % (key, len(a[key]), a["num_hidden_layers"]))
    return a


def router_trained(config):
    """Whether the routers' weights take their gradient: ``assumed``'s
    ``router_trained``, true where the file does not say."""
    return bool((config.get("assumed") or {}).get("router_trained", True))


def leaves(config):
    """{leaf: (shape, how it starts)}; weights as ``FullyConnected`` keeps
    them, (out, in), the experts stacked (held, in, out)."""
    a = arch(config)
    h, heads, kv, d = (a["hidden_size"], a["num_attention_heads"],
                       a["num_key_value_heads"], a["head_dim"])
    held, width = a["experts_held"][1], a["moe_ffn_hidden_size"]
    out = OrderedDict()
    out["embed.w"] = ((a["vocab_size"], h), "embedding")
    for l in range(a["num_hidden_layers"]):
        p = "l%d." % l
        out[p + "attn_norm.g"] = ((h,), "one")
        out[p + "q.w"] = ((heads * d, h), "normal")
        out[p + "kv.w"] = ((2 * kv * d, h), "normal")
        out[p + "o.w"] = ((h, heads * d), "residual")
        out[p + "ffn_norm.g"] = ((h,), "one")
        out[p + "router.w"] = ((a["router_width"], h), "normal")
        out[p + "experts.gate"] = ((held, h, width), "normal")
        out[p + "experts.up"] = ((held, h, width), "normal")
        out[p + "experts.down"] = ((held, width, h), "residual")
    out["norm.g"] = ((h,), "one")
    out["head.w"] = ((a["vocab_size"], h), "normal")
    return out


def init(config, seed):
    """Seeded weights, all in the type they are trained in: N(0, 0.02), norms
    1; the embedding and the two projections that write to the residual
    stream (attention's output, the experts' down) take ``assumed``'s
    ``embedding_std`` / ``residual_projection_std`` where the file gives
    them."""
    spec = leaves(config)
    dtype = jnp.dtype(config["dtype"])
    assumed = config.get("assumed") or {}
    stds = {"normal": 0.02, "embedding": assumed.get("embedding_std", 0.02),
            "residual": assumed.get("residual_projection_std", 0.02)}

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


def reglu(x, gate, up, down, quant=None):
    return dense(jax.nn.relu(dense(x, gate, quant)) * dense(x, up, quant), down, quant)


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


def visible(t, window):
    """(T, T) booleans: key j for query i where ``j <= i`` and, under a
    window, ``i - j < window``."""
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    return seen if window is None else jnp.logical_and(seen, i - j < window)


def attention(p, x, a, rotary, window, quant=None, head_block=None):
    """Causal grouped-query attention of (B, T, H): ``rotary`` says whether
    q and k are turned, ``window`` how many keys a query sees (None: all)."""
    b, t, _ = x.shape
    heads, kv, d = a["num_attention_heads"], a["num_key_value_heads"], a["head_dim"]
    group = heads // kv
    q = jnp.moveaxis(dense(x, p["q.w"], quant).reshape(b, t, heads, d), 2, 1)
    kvs = dense(x, p["kv.w"], quant).reshape(b, t, 2 * kv, d)
    k, v = jnp.moveaxis(kvs[:, :, :kv], 2, 1), jnp.moveaxis(kvs[:, :, kv:], 2, 1)
    if rotary:
        q, k = rope(q, a["rope_theta"]), rope(k, a["rope_theta"])
    seen = visible(t, window)

    def some_heads(qkv):
        qh, kh, vh = qkv  # (b, g, t, d): the K/V head of each query head
        s = jnp.einsum("bhqd,bhkd->bhqk", operand(qh, quant), operand(kh, quant),
                       precision=HI) / math.sqrt(d)
        pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
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
    """(N, router_width) weights of the rows ``x`` the router reads (the
    attention's input): 0 where an expert is not chosen. Where the
    configuration says ``router_trained: false`` they are constants of the
    loss: no gradient reaches the router's weights or ``x`` through them."""
    s = jax.nn.softmax(dense(x, p["router.w"], quant), axis=-1)
    kth = jnp.sort(jax.lax.stop_gradient(s), axis=-1)[
        :, -a["moe_num_active_primary_experts"]][:, None]
    chosen = jnp.where(s >= kth, s, 0.0)
    w = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return w if a["router_trained"] else jax.lax.stop_gradient(w)


def moe(p, y, x, a, quant=None, experts_held=None):
    """The expert layer of (N, H) tokens ``y``, routed by the rows ``x``: the
    part of the result that the experts ``experts_held=(first, count)`` give.
    ``p["experts.*"]`` hold those experts alone."""
    first, count = experts_held or a["experts_held"]
    w = routing(p, x, a, quant)

    def one(total, expert):  # plain: every held expert sees every token
        gate, up, down, weight = expert
        return total + weight[:, None] * reglu(y, gate.T, up.T, down.T, quant), None

    # a loop over the experts held, one at a time (``lax.scan`` and not
    # Python's ``for``: the chip's compiler then builds one expert, not 64)
    total, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(y), (
        p["experts.gate"], p["experts.up"], p["experts.down"],
        w[:, first:first + count].T))
    return total


def block(p, h, a, rotary, window, quant=None, head_block=None):
    x = rms_norm(h, p["attn_norm.g"], a["rms_norm_eps"])
    h = h + attention(p, x, a, rotary, window, quant, head_block)
    y = rms_norm(h, p["ffn_norm.g"], a["rms_norm_eps"])
    flat = functools.partial(jnp.reshape, shape=(-1, y.shape[-1]))
    return h + moe(p, flat(y), flat(x), a, quant).reshape(y.shape)


def logits(config, params, x, quant=None):
    a = arch(config)
    p = {k: v.astype(F32) for k, v in params.items()}
    knobs = config.get("reference") or {}
    h = p["embed.w"][x.astype(jnp.int32)]
    for l in range(a["num_hidden_layers"]):
        pre = "l%d." % l
        lp = {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}
        window = None
        if a["sliding_window_layout"][l]:
            window = a["sliding_window_size"] - knobs.get("window_short", 0)
        h = jax.checkpoint(functools.partial(
            block, a=a, rotary=bool(a["rope_layout"][l]), window=window, quant=quant,
            head_block=knobs.get("head_block")))(lp, h)
    return dense(rms_norm(h, p["norm.g"], a["rms_norm_eps"]), p["head.w"], quant)


def value_and_grad(config, params, x, y, quant=None):
    """Mean loss over every position of the batch, and its gradient."""
    def loss(p):
        logp = jax.nn.log_softmax(logits(config, p, x, quant), axis=-1)
        picked = jnp.take_along_axis(logp, y.astype(jnp.int32)[..., None], axis=-1)
        return -jnp.mean(picked)

    return jax.value_and_grad(loss)(params)
