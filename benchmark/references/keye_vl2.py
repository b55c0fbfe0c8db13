"""Plain reference: the language decoder of Keye-VL-2.0 (``model_type:
KeyeVL2``; Kwai-Keye/Keye-VL-2.0-30B-A3B; the sizes come from the
configuration's file) with next-token cross-entropy, in straightforward
``jax.numpy`` float32 with matmul precision "highest". No kernels, no program
code, nothing the program made. Written from the layer equations:

Block l, pre-norm, no bias anywhere, every layer an expert layer:
``h' = h + Attn(x, S)`` with ``x = RMSNorm(h)`` and ``S = Indexer(x)``;
``h'' = h' + MoE(RMSNorm(h'))``; a last RMSNorm, then the untied head.

* Attention. ``q = W_q x`` as 32 heads of 128, ``[k, v] = W_kv x`` as 4 + 4
  heads of 128 (``W_kv`` is ``W_k`` over ``W_v``: one leaf); an RMSNorm over
  the 128 of each head of q and of k (one weight vector each); rotary
  positions, rotate-half over the whole head (``x cos + rotate_half(x) sin``;
  ``mrope_section`` with a text token's three equal position ids is this);
  query head i reads K/V head ``i // 8``; ``o[t, i] = sum over s in S_t of
  softmax_s(q[t, i] . k[s] / sqrt(128)) v[s]``, the softmax over ``S_t``
  alone; ``W_o`` of the heads side by side. Scores are materialised, a few
  heads at a time.
* Indexer (``sa_config``). ``qI = rope(W_qI x)`` as 16 heads of 64; ``kI =
  rope(LayerNorm(W_kI x))``, one head of 64; ``w = W_w x / sqrt(16 * 64)``;
  ``I[t, s] = sum_j w[t, j] ReLU(qI[t, j] . kI[s])`` for ``s <= t``. ``S_t``
  is all of ``0 .. t`` while ``t + 1 <= topk``, else the ``topk`` keys of
  largest ``I[t, .]``, a tie to the lower ``s``: the threshold is
  ``lax.top_k``'s last value, and the keys tied with it are taken in order
  until ``topk`` are chosen. ``S`` is a constant of the loss: no gradient
  passes through it, so the indexer's weights get none.
* Expert layer. ``p = softmax(W_r x)`` over all the published experts; the
  ``num_experts_per_tok`` largest; ``w_e = p_e / sum of the chosen p``; ``y =
  sum over the chosen e of w_e SwiGLU_e(x)``. No capacity, no dropped token,
  no shared expert. A loop over the experts HELD, one at a time
  (``experts_held`` of the configuration): the reference is given the same
  share as the program, and what the other experts would have added is left
  out of both.
* Loss. Mean cross-entropy over every position of the sliced vocabulary.

Departures, all of them the configuration's ``assumed``: the indexer's own
alignment loss is not run, so its weights are seeded and fixed; where
``assumed`` says ``router_trained: false``, no gradient passes through the
chosen experts' weights, to the router's weights or to the layer's input (a
share of the experts trained alone has only a part of that gradient). Each block runs under ``jax.checkpoint`` with its selection, made
once, as an input (so the backward pass sorts nothing again), so that float32
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


def arch(config):
    """The sizes as run: the file's own keys, with the router as wide as
    published and this chip's share of the experts."""
    a = {k: config[k] for k in (
        "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "rope_theta", "rms_norm_eps",
        "moe_intermediate_size", "num_experts_per_tok", "vocab_size")}
    sa = config["sa_config"]
    a.update(index_heads=sa["indexer_num_heads"], index_dim=sa["indexer_head_dim"],
             topk=sa["topk"])
    a["router_width"] = config["published"]["num_experts"]
    a["experts_held"] = tuple(config["experts_held"])
    a["router_trained"] = router_trained(config)
    if a["experts_held"][1] != config["num_experts"]:
        raise ValueError("experts_held and num_experts (held here) differ")
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
    ih, idim = a["index_heads"], a["index_dim"]
    held, width = a["experts_held"][1], a["moe_intermediate_size"]
    out = OrderedDict()
    out["embed.w"] = ((a["vocab_size"], h), "embedding")
    for l in range(a["num_hidden_layers"]):
        p = "l%d." % l
        out[p + "attn_norm.g"] = ((h,), "one")
        out[p + "q.w"] = ((heads * d, h), "normal")
        out[p + "kv.w"] = ((2 * kv * d, h), "normal")
        out[p + "q_norm.g"] = ((d,), "one")
        out[p + "k_norm.g"] = ((d,), "one")
        out[p + "o.w"] = ((h, heads * d), "residual")
        out[p + "index_q.w"] = ((ih * idim, h), "normal")
        out[p + "index_k.w"] = ((idim, h), "normal")
        out[p + "index_k_norm.g"] = ((idim,), "one")
        out[p + "index_k_norm.b"] = ((idim,), "zero")
        out[p + "index_w.w"] = ((ih, h), "normal")
        out[p + "ffn_norm.g"] = ((h,), "one")
        out[p + "router.w"] = ((a["router_width"], h), "normal")
        out[p + "experts.gate"] = ((held, h, width), "normal")
        out[p + "experts.up"] = ((held, h, width), "normal")
        out[p + "experts.down"] = ((held, width, h), "residual")
    out["norm.g"] = ((h,), "one")
    out["head.w"] = ((a["vocab_size"], h), "normal")
    return out


def init(config, seed):
    """Seeded weights N(0, 0.02), norms 1 (the LayerNorm's shift 0), all in
    the type they are trained in. The embedding and the two projections that
    write to the residual stream (attention's output, the experts' down) take
    ``assumed``'s ``embedding_std`` / ``residual_projection_std`` where the
    file gives them: at 0.02 throughout, attention's near-uniform average of
    the values is the same vector for every query, each layer doubles it, and
    by the second layer nine tokens in ten choose the same expert."""
    spec = leaves(config)
    dtype = jnp.dtype(config["dtype"])
    assumed = config.get("assumed") or {}
    stds = {"normal": 0.02, "embedding": assumed.get("embedding_std", 0.02),
            "residual": assumed.get("residual_projection_std", 0.02)}

    @jax.jit
    def make(key):
        out = {}
        for n, (name, (shape, kind)) in enumerate(spec.items()):
            if kind in stds:
                v = stds[kind] * jax.random.normal(jax.random.fold_in(key, n), shape, F32)
            else:
                v = jnp.full(shape, 1.0 if kind == "one" else 0.0, F32)
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


def layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * g + b


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


def selection(p, x, a, quant=None):
    """The indexer's choice for (B, T, H) normed hidden states: (B, T, T)
    booleans, true where key s is in ``S_t``. A constant: no gradient."""
    b, t, _ = x.shape
    ih, idim, topk = a["index_heads"], a["index_dim"], a["topk"]
    causal = jnp.tril(jnp.ones((t, t), bool))
    if t <= topk:
        return jnp.broadcast_to(causal, (b, t, t))
    x = jax.lax.stop_gradient(x)
    q = jnp.moveaxis(dense(x, p["index_q.w"], quant).reshape(b, t, ih, idim), 2, 1)
    q = rope(q, a["rope_theta"])  # (b, ih, t, idim)
    k = rope(layer_norm(dense(x, p["index_k.w"], quant), p["index_k_norm.g"],
                        p["index_k_norm.b"], a["rms_norm_eps"]), a["rope_theta"])
    w = dense(x, p["index_w.w"], quant) / math.sqrt(ih * idim)  # (b, t, ih)

    def one_sequence(qkw):
        qs, ks, ws = qkw
        score = jnp.zeros((t, t), F32)
        for j in range(ih):  # one head's (t, t) at a time
            s = jnp.einsum("qd,kd->qk", operand(qs[j], quant), operand(ks, quant),
                           precision=HI)
            score = score + ws[:, j, None] * jax.nn.relu(s)
        score = jnp.where(causal, score, -jnp.inf)
        kth = jax.lax.top_k(score, topk)[0][:, -1:]
        above, tied = score > kth, jnp.logical_and(score == kth, causal)
        need = topk - jnp.sum(above, axis=-1, keepdims=True, dtype=jnp.int32)
        chosen = jnp.logical_or(above, jnp.logical_and(
            tied, jnp.cumsum(tied, axis=-1, dtype=jnp.int32) <= need))
        few = jnp.arange(t)[:, None] < topk  # at most topk candidates: all
        return jnp.logical_and(jnp.logical_or(chosen, few), causal)

    return jax.lax.stop_gradient(jax.lax.map(one_sequence, (q, k, w)))


def attention(p, x, chosen, a, quant=None, head_block=None):
    """Grouped-query attention of (B, T, H) over the keys ``chosen``."""
    b, t, _ = x.shape
    heads, kv, d = a["num_attention_heads"], a["num_key_value_heads"], a["head_dim"]
    group = heads // kv
    q = dense(x, p["q.w"], quant).reshape(b, t, heads, d)
    kvs = dense(x, p["kv.w"], quant).reshape(b, t, 2 * kv, d)
    k, v = kvs[:, :, :kv], kvs[:, :, kv:]
    q = rms_norm(q, p["q_norm.g"], a["rms_norm_eps"])
    k = rms_norm(k, p["k_norm.g"], a["rms_norm_eps"])
    q = rope(jnp.moveaxis(q, 2, 1), a["rope_theta"])  # (b, heads, t, d)
    k = rope(jnp.moveaxis(k, 2, 1), a["rope_theta"])  # (b, kv, t, d)
    v = jnp.moveaxis(v, 2, 1)

    def some_heads(qkv):
        qh, kh, vh = qkv  # (b, g, t, d): the K/V head of each query head
        s = jnp.einsum("bhqd,bhkd->bhqk", operand(qh, quant), operand(kh, quant),
                       precision=HI) / math.sqrt(d)
        pr = jax.nn.softmax(jnp.where(chosen[:, None], s, -jnp.inf), axis=-1)
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
    s = jax.nn.softmax(dense(x, p["router.w"], quant), axis=-1)
    kth = jnp.sort(jax.lax.stop_gradient(s), axis=-1)[:, -a["num_experts_per_tok"]][:, None]
    chosen = jnp.where(s >= kth, s, 0.0)
    w = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
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
    # Python's ``for``: the chip's compiler then builds one expert, not 96)
    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(x), (
        p["experts.gate"], p["experts.up"], p["experts.down"],
        w[:, first:first + count].T))
    return y


def block(p, h, chosen, a, quant=None, head_block=None):
    x = rms_norm(h, p["attn_norm.g"], a["rms_norm_eps"])
    h = h + attention(p, x, chosen, a, quant, head_block)
    x = rms_norm(h, p["ffn_norm.g"], a["rms_norm_eps"])
    return h + moe(p, x.reshape(-1, x.shape[-1]), a, quant).reshape(x.shape)


def layer_leaves(p, l):
    pre = "l%d." % l
    return {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}


def logits(config, params, x, quant=None):
    a = arch(config)
    p = {k: v.astype(F32) for k, v in params.items()}
    head_block = (config.get("reference") or {}).get("head_block")
    h = p["embed.w"][x.astype(jnp.int32)]
    for l in range(a["num_hidden_layers"]):
        lp = layer_leaves(p, l)
        # the selection is made once, outside the checkpoint: a constant
        chosen = selection(lp, rms_norm(h, lp["attn_norm.g"], a["rms_norm_eps"]),
                           a, quant)
        h = jax.checkpoint(functools.partial(
            block, a=a, quant=quant, head_block=head_block))(lp, h, chosen)
    return dense(rms_norm(h, p["norm.g"], a["rms_norm_eps"]), p["head.w"], quant)


def first_selection(config, params, x, quant=None):
    """Layer 0's selection for token ids ``x`` (B, T): what the cell's
    ``selection_mismatch`` holds the program's against."""
    a = arch(config)
    p = {k: v.astype(F32) for k, v in params.items()
         if k == "embed.w" or k.startswith("l0.")}
    lp = layer_leaves(p, 0)
    h = p["embed.w"][x.astype(jnp.int32)]
    return selection(lp, rms_norm(h, lp["attn_norm.g"], a["rms_norm_eps"]), a, quant)


def value_and_grad(config, params, x, y, quant=None):
    """Mean loss over every position of the batch, and its gradient."""
    def loss(p):
        logp = jax.nn.log_softmax(logits(config, p, x, quant), axis=-1)
        picked = jnp.take_along_axis(logp, y.astype(jnp.int32)[..., None], axis=-1)
        return -jnp.mean(picked)

    return jax.value_and_grad(loss)(params)
