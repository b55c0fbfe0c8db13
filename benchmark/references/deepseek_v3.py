"""Plain reference: a DeepSeek-V3-style decoder (``model_type: deepseek_v3``;
DeepSeek-V3, arXiv:2412.19437; the sizes come from the configuration's file)
with next-token cross-entropy, in straightforward ``jax.numpy`` float32 with
matmul precision "highest". No kernels, no program code, nothing the program
made. Written from the layer equations:

Block l, pre-norm, no bias anywhere: ``h' = h + MLA(RMSNorm(h))``,
``h'' = h' + FFN_l(RMSNorm(h'))``; ``FFN_l`` is SwiGLU ``W_down(silu(W_gate x)
* W_up x)`` for the leading ``first_k_dense_replace`` layers and the expert
layer after them; a last RMSNorm, then the untied head.

* MLA. ``q = W_q x``, each head ``[q_nope, q_rope]``; ``[c, k_r] = W_kva x``;
  ``[k_nope_i, v_i] = W_kvb RMSNorm(c)`` for head i; RoPE on ``q_rope`` and on
  the one ``k_r`` all heads share, interleaved: entries (2j, 2j+1) are a pair,
  taken apart to ``[evens, odds]`` before the rotate-half form
  (``apply_rotary_pos_emb_interleave``); ``k_i = [k_nope_i, k_r]``;
  ``softmax_causal(q_i k_i^T / sqrt(nope + rope)) v_i``; ``W_o`` of the heads
  side by side. Scores are materialised, a few heads at a time.
* Expert layer. ``s = sigmoid(W_g x)`` over all the published experts; chosen:
  the top k of ``s + b`` (``b`` a buffer with no gradient; one group, so no
  group step); ``w_e = scaling * s_e / (sum of the chosen s + 1e-20)``;
  ``y = sum over the chosen e of w_e SwiGLU_e(x) + SwiGLU_shared(x)``, the
  shared experts one SwiGLU of ``n_shared_experts`` times the width. No
  capacity, no dropped token. A loop over the experts HELD, one at a time
  (``experts_held`` of the configuration): the reference is given the same
  share as the program, and what the other experts would have added is left
  out of both.
* Loss. Mean cross-entropy over every position of the sliced vocabulary.

Departures, all of them the configuration's ``assumed``: the bias's update
rule and the sequence-wise balance loss are not run; ``b`` is seeded and
fixed; no weight decay; where ``assumed`` says ``router_trained: false``, the
router's weights are not trained (a share of the experts trained alone has only
a part of their gradient). The batch is taken whole (the follower's float32
weights are then no loop's operand) and each block under ``jax.checkpoint``,
so that float32 at the timed size fits beside the follower's state.
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
        "hidden_size", "num_hidden_layers", "first_k_dense_replace",
        "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "rope_theta", "rms_norm_eps",
        "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
        "n_shared_experts", "routed_scaling_factor", "vocab_size")}
    a["router_width"] = config["published"]["n_routed_experts"]
    a["experts_held"] = tuple(config["experts_held"])
    a["router_trained"] = router_trained(config)
    if a["experts_held"][1] != config["n_routed_experts"]:
        raise ValueError("experts_held and n_routed_experts (held here) differ")
    return a


def router_trained(config):
    """Whether the routers' weights take their gradient: ``assumed``'s
    ``router_trained``, true where the file does not say."""
    return bool((config.get("assumed") or {}).get("router_trained", True))


def leaves(config):
    """{leaf: (shape, how it starts)}; weights as ``FullyConnected`` keeps
    them, (out, in), the experts stacked (held, in, out)."""
    a = arch(config)
    h, heads = a["hidden_size"], a["num_attention_heads"]
    nope, rope, dv, lora = (a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                            a["v_head_dim"], a["kv_lora_rank"])
    held, width = a["experts_held"][1], a["moe_intermediate_size"]
    shared = a["n_shared_experts"] * width
    out = OrderedDict()
    out["embed.w"] = ((a["vocab_size"], h), "normal")
    for l in range(a["num_hidden_layers"]):
        p = "l%d." % l
        out[p + "attn_norm.g"] = ((h,), "one")
        out[p + "q.w"] = ((heads * (nope + rope), h), "normal")
        out[p + "kv_a.w"] = ((lora + rope, h), "normal")
        out[p + "kv_a_norm.g"] = ((lora,), "one")
        out[p + "kv_b.w"] = ((heads * (nope + dv), lora), "normal")
        out[p + "o.w"] = ((h, heads * dv), "normal")
        out[p + "ffn_norm.g"] = ((h,), "one")
        if l < a["first_k_dense_replace"]:
            i = a["intermediate_size"]
            out[p + "gate.w"], out[p + "up.w"] = ((i, h), "normal"), ((i, h), "normal")
            out[p + "down.w"] = ((h, i), "normal")
            continue
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
    """Seeded weights N(0, 0.02), norms 1, the selection bias N(0,
    ``assumed.router_bias_std``), all in the type they are trained in (the
    bias too: its values are then exact in the program's float32 buffer and
    in the follower's stored type)."""
    spec = leaves(config)
    dtype = jnp.dtype(config["dtype"])
    bias_std = (config.get("assumed") or {}).get("router_bias_std", 0.1)

    @jax.jit
    def make(key):
        out = {}
        for n, (name, (shape, kind)) in enumerate(spec.items()):
            if kind == "one":
                v = jnp.ones(shape, F32)
            else:
                std = bias_std if kind == "bias" else 0.02
                v = std * jax.random.normal(jax.random.fold_in(key, n), shape, F32)
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


def rope(x, theta, interleaved=True):
    """Rotary positions on the last axis of (..., T, D), position along the
    axis before it. HF's form: ``x * cos + rotate_half(x) * sin`` with the
    D/2 frequencies written twice, after the pairs are taken apart."""
    t, d = x.shape[-2], x.shape[-1]
    if interleaved:
        x = jnp.swapaxes(x.reshape(x.shape[:-1] + (d // 2, 2)), -1, -2).reshape(x.shape)
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    freqs = jnp.arange(t, dtype=F32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    rotated = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * jnp.cos(emb) + rotated * jnp.sin(emb)


def mla(p, x, a, quant=None, head_block=None):
    """Latent attention of (B, T, H) with ``p`` the block's leaves."""
    b, t, _ = x.shape
    heads, nope, rp, dv = (a["num_attention_heads"], a["qk_nope_head_dim"],
                           a["qk_rope_head_dim"], a["v_head_dim"])
    q = dense(x, p["q.w"], quant).reshape(b, t, heads, nope + rp)
    q = jnp.moveaxis(q, 2, 1)  # (b, heads, t, nope + rope)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], a["rope_theta"])], axis=-1)
    ckr = dense(x, p["kv_a.w"], quant)
    c = rms_norm(ckr[..., :a["kv_lora_rank"]], p["kv_a_norm.g"], a["rms_norm_eps"])
    k_r = rope(ckr[..., a["kv_lora_rank"]:], a["rope_theta"])  # (b, t, rope)
    kv = jnp.moveaxis(dense(c, p["kv_b.w"], quant).reshape(b, t, heads, nope + dv), 2, 1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_r[:, None], (b, heads, t, rp))], axis=-1)
    v = kv[..., nope:]
    mask = jnp.tril(jnp.ones((t, t), bool))

    def some_heads(qkv):
        qh, kh, vh = qkv  # (b, g, t, d)
        s = jnp.einsum("bhqd,bhkd->bhqk", operand(qh, quant), operand(kh, quant),
                       precision=HI) / math.sqrt(nope + rp)
        pr = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", operand(pr, quant), operand(vh, quant),
                          precision=HI)

    g = head_block or heads
    if heads % g:
        raise ValueError("%d heads are not whole blocks of %d" % (heads, g))

    def split(z):  # (b, heads, t, d) -> (heads / g, b, g, t, d)
        return jnp.moveaxis(z.reshape(b, heads // g, g, t, z.shape[-1]), 1, 0)

    o = jax.lax.map(jax.checkpoint(some_heads), (split(q), split(k), split(v)))
    o = jnp.moveaxis(o, 0, 1).reshape(b, heads, t, dv)
    return dense(jnp.moveaxis(o, 1, 2).reshape(b, t, heads * dv), p["o.w"], quant)


def routing(p, x, a, quant=None):
    """(N, router_width) weights: 0 where an expert is not chosen."""
    s = jax.nn.sigmoid(dense(x, p["router.w"], quant))
    choice = jax.lax.stop_gradient(s + p["router.bias"])
    kth = jnp.sort(choice, axis=-1)[:, -a["num_experts_per_tok"]][:, None]
    chosen = jnp.where(choice >= kth, s, 0.0)
    return a["routed_scaling_factor"] * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def moe(p, x, a, quant=None, experts_held=None):
    """The expert layer of (N, H) tokens: the part of the result that the
    experts ``experts_held=(first, count)`` give, and the shared experts'.
    ``p["experts.*"]`` hold those experts alone."""
    first, count = experts_held or a["experts_held"]
    w = routing(p, x, a, quant)

    def one(y, expert):  # plain: every held expert sees every token
        gate, up, down, weight = expert
        return y + weight[:, None] * swiglu(x, gate.T, up.T, down.T, quant), None

    # a loop over the experts held, one at a time (``lax.scan`` and not
    # Python's ``for``: the chip's compiler then builds one expert, not 80)
    y, _ = jax.lax.scan(jax.checkpoint(one), jnp.zeros_like(x), (
        p["experts.gate"], p["experts.up"], p["experts.down"],
        w[:, first:first + count].T))
    if "shared.gate.w" in p:
        y = y + swiglu(x, p["shared.gate.w"], p["shared.up.w"], p["shared.down.w"], quant)
    return y


def block(p, h, a, dense_ffn, quant=None, head_block=None):
    h = h + mla(p, rms_norm(h, p["attn_norm.g"], a["rms_norm_eps"]), a, quant, head_block)
    x = rms_norm(h, p["ffn_norm.g"], a["rms_norm_eps"])
    if dense_ffn:
        return h + swiglu(x, p["gate.w"], p["up.w"], p["down.w"], quant)
    if not a["router_trained"]:
        # the configuration freezes the router (the gradient still reaches x)
        p = dict(p, **{"router.w": jax.lax.stop_gradient(p["router.w"])})
    return h + moe(p, x.reshape(-1, x.shape[-1]), a, quant).reshape(x.shape)


def logits(config, params, x, quant=None):
    a = arch(config)
    p = {k: v.astype(F32) for k, v in params.items()}
    head_block = (config.get("reference") or {}).get("head_block")
    h = p["embed.w"][x.astype(jnp.int32)]
    for l in range(a["num_hidden_layers"]):
        pre = "l%d." % l
        lp = {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}
        h = jax.checkpoint(functools.partial(
            block, a=a, dense_ffn=l < a["first_k_dense_replace"], quant=quant,
            head_block=head_block))(lp, h)
    return dense(rms_norm(h, p["norm.g"], a["rms_norm_eps"]), p["head.w"], quant)


def value_and_grad(config, params, x, y, quant=None):
    """Mean loss over every position of the batch, and its gradient."""
    def loss(p):
        logp = jax.nn.log_softmax(logits(config, p, x, quant), axis=-1)
        picked = jnp.take_along_axis(logp, y.astype(jnp.int32)[..., None], axis=-1)
        return -jnp.mean(picked)

    return jax.value_and_grad(loss)(params)
