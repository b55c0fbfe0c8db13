"""Plain reference: the BERT encoder with its masked-language-model head
(Devlin et al. 2018, arXiv:1810.04805; sizes of its appendix A.2), softmax
cross-entropy over the vocabulary, in straightforward ``jax.numpy`` float32
with matmul precision "highest". No kernels, no program code, nothing the
program made.

Post-layer-norm transformer layers; learned position embeddings; token type 0
everywhere; GELU in its tanh form (as the paper's released code computes it);
layer-norm eps 1e-12; the MLM head is dense -> GELU -> layer norm -> the word
embedding transposed plus a bias. Departures, all of them the configuration's
``assumed``: no next-sentence head, a score for every position and not the 15%
masked, no padding and so no attention mask, no dropout. The batch is taken in
blocks of rows, gradients summed over the blocks, so that float32 at the timed
batch fits the chip; rows do not interact, so the values are the batch's.
"""
from __future__ import annotations

import math
from collections import OrderedDict

import jax
import jax.numpy as jnp

from harness import seeds
from harness.quant import operand

F32 = jnp.float32
LN_EPS = 1e-12
HI = jax.lax.Precision.HIGHEST


def leaves(config):
    m = config["published"]
    h, i, v = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    out = OrderedDict()
    out["embed.word"] = ((v, h), "normal")
    out["embed.type"] = ((m["type_vocab_size"], h), "normal")
    out["embed.position"] = ((m["max_position_embeddings"], h), "normal")
    out["embed.ln.g"], out["embed.ln.b"] = ((h,), "one"), ((h,), "zero")
    for l in range(m["num_hidden_layers"]):
        p = "l%d." % l
        for name, o, c in (("qkv", 3 * h, h), ("proj", h, h), ("ffn1", i, h),
                           ("ffn2", h, i)):
            out[p + name + ".w"] = ((o, c), "normal")
            out[p + name + ".bias"] = ((o,), "zero")
        for ln in ("ln1", "ln2"):
            out[p + ln + ".g"], out[p + ln + ".b"] = ((h,), "one"), ((h,), "zero")
    out["mlm.dense.w"], out["mlm.dense.bias"] = ((h, h), "normal"), ((h,), "zero")
    out["mlm.ln.g"], out["mlm.ln.b"] = ((h,), "one"), ((h,), "zero")
    out["mlm.out.bias"] = ((v,), "zero")
    return out


def init(config, seed):
    """Seeded weights, normal with the paper's std 0.02, in the type they are
    trained in, one jitted call."""
    spec = leaves(config)
    dtype = jnp.dtype(config["dtype"])

    @jax.jit
    def make(key):
        out = {}
        for n, (name, (shape, kind)) in enumerate(spec.items()):
            if kind == "normal":
                v = 0.02 * jax.random.normal(jax.random.fold_in(key, n), shape, F32)
            else:
                v = jnp.full(shape, 1.0 if kind == "one" else 0.0, F32)
            out[name] = v.astype(dtype)
        return out

    return make(seeds.key(seed, 1))


def batches(config, traffic, seed):
    """A pool of seeded batches of token ids and target ids, uniform over the
    vocabulary, every row different; float32 as the Gluon loss takes labels."""
    m = config["published"]
    n, b, t = traffic["pool"], traffic["batch"], traffic["sequence"]

    @jax.jit
    def make(key):
        kx, ky = jax.random.split(key)
        x = jax.random.randint(kx, (n, b, t), 0, m["vocab_size"]).astype(F32)
        y = jax.random.randint(ky, (n, b, t), 0, m["vocab_size"]).astype(F32)
        return x, y

    x, y = make(seeds.key(seed, 2))
    return [(x[i], y[i]) for i in range(n)]


def _dense(x, w, b, quant):
    return jnp.einsum("...c,oc->...o", operand(x, quant), operand(w, quant),
                      precision=HI) + b


def _ln(x, g, b):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * g + b


def _gelu(x):
    return jax.nn.gelu(x, approximate=True)


def _layer(p, x, heads, quant):
    b, t, h = x.shape
    d = h // heads
    qkv = _dense(x, p["qkv.w"], p["qkv.bias"], quant).reshape(b, t, 3, heads, d)
    q, k, v = (jnp.moveaxis(qkv[:, :, j], 1, 2) for j in range(3))  # (b, heads, t, d)
    s = jnp.einsum("bhqd,bhkd->bhqk", operand(q, quant), operand(k, quant),
                   precision=HI) / math.sqrt(d)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", operand(a, quant), operand(v, quant), precision=HI)
    o = jnp.moveaxis(o, 1, 2).reshape(b, t, h)
    x = _ln(x + _dense(o, p["proj.w"], p["proj.bias"], quant), p["ln1.g"], p["ln1.b"])
    f = _dense(_gelu(_dense(x, p["ffn1.w"], p["ffn1.bias"], quant)),
               p["ffn2.w"], p["ffn2.bias"], quant)
    return _ln(x + f, p["ln2.g"], p["ln2.b"])


def loss_sum(config, params, x, y, quant=None):
    """Sum over the rows' positions of the cross-entropy, float32 throughout."""
    m = config["published"]
    p = {k: v.astype(F32) for k, v in params.items()}
    ids = x.astype(jnp.int32)
    t = ids.shape[1]
    h = p["embed.word"][ids] + p["embed.type"][0] + p["embed.position"][:t]
    h = _ln(h, p["embed.ln.g"], p["embed.ln.b"])
    for l in range(m["num_hidden_layers"]):
        pre = "l%d." % l
        lp = {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}
        h = jax.checkpoint(_layer, static_argnums=(2, 3))(
            lp, h, m["num_attention_heads"], quant)
    h = _ln(_gelu(_dense(h, p["mlm.dense.w"], p["mlm.dense.bias"], quant)),
            p["mlm.ln.g"], p["mlm.ln.b"])
    logits = _dense(h, p["embed.word"], p["mlm.out.bias"], quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, y.astype(jnp.int32)[..., None], axis=-1)
    return -jnp.sum(picked)


def value_and_grad(config, params, x, y, quant=None):
    """Mean loss over every position of the batch and its gradient, taken in
    blocks of rows."""
    rows = config["reference"]["row_block"]
    b, t = x.shape
    if b % rows:
        raise ValueError("batch %d is not a multiple of the row block %d" % (b, rows))
    xs, ys = x.reshape(b // rows, rows, t), y.reshape(b // rows, rows, t)
    vg = jax.value_and_grad(lambda p, xb, yb: loss_sum(config, p, xb, yb, quant))

    def body(carry, xy):
        total, grads = carry
        v, g = vg(params, xy[0], xy[1])
        return (total + v, jax.tree.map(jnp.add, grads, g)), None

    zero = jax.tree.map(lambda a: jnp.zeros(a.shape, F32), params)
    (total, grads), _ = jax.lax.scan(body, (jnp.zeros((), F32), zero), (xs, ys))
    n = b * t
    return total / n, jax.tree.map(lambda g: g / n, grads)
