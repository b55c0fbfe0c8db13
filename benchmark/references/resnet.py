"""Plain reference: ResNet v1 with bottleneck blocks (He et al. 2015,
arXiv:1512.03385, table 1), its softmax cross-entropy loss, in straightforward
``jax.numpy`` float32 with matmul precision "highest". No kernels, no program
code, nothing the program made.

Follows the paper's v1 block as MXNet's model zoo writes it: 1x1 (stride) ->
3x3 -> 1x1, batch norm after each convolution, ReLU after the first two and
after the sum; the 1x1 convolutions of a block carry a bias, the 3x3 and the
projection do not. Batch norm uses the batch's own biased variance, eps 1e-5.
Departures: the running statistics are not kept (they do not enter the
training loss); every block is rematerialised in backward so that float32 at
the timed batch fits the chip (``jax.checkpoint`` changes memory, not values).
"""
from __future__ import annotations

from collections import OrderedDict

import jax
import jax.numpy as jnp

from harness import seeds
from harness.quant import backward_only, operand

F32 = jnp.float32
BN_EPS = 1e-5


def leaves(config):
    """name -> (shape, kind) in construction order. kind: conv (OIHW), dense
    (out, in), one (gamma), zero (beta, bias)."""
    m = config["published"]
    out = OrderedDict()
    ch = m["channels"]
    out["stem.conv.w"] = ((ch[0], 3, 7, 7), "conv")
    out["stem.bn.g"], out["stem.bn.b"] = ((ch[0],), "one"), ((ch[0],), "zero")
    cin = ch[0]
    for s, (n, cout) in enumerate(zip(m["layers"], ch[1:]), start=1):
        mid = cout // 4
        for b in range(n):
            p = "s%d.b%d." % (s, b)
            for i, (o, c, k, bias) in enumerate(
                    [(mid, cin, 1, True), (mid, mid, 3, False), (cout, mid, 1, True)]):
                out[p + "conv%d.w" % i] = ((o, c, k, k), "conv")
                if bias:
                    out[p + "conv%d.bias" % i] = ((o,), "zero")
                # the last batch norm of a block starts small, so that a block
                # starts near the identity (see the configuration's "assumed")
                last = i == 2 and "residual_gamma" in config
                out[p + "bn%d.g" % i] = ((o,), "residual" if last else "one")
                out[p + "bn%d.b" % i] = ((o,), "zero")
            if b == 0:
                out[p + "down.w"] = ((cout, cin, 1, 1), "conv")
                out[p + "downbn.g"] = ((cout,), "one")
                out[p + "downbn.b"] = ((cout,), "zero")
            cin = cout
    out["fc.w"] = ((m["classes"], cin), "dense")
    out["fc.bias"] = ((m["classes"],), "zero")
    return out


def init(config, seed):
    """Seeded weights in the type they are trained in, one jitted call."""
    spec = leaves(config)
    dtype = jnp.dtype(config["dtype"])

    @jax.jit
    def make(key):
        out = {}
        for i, (name, (shape, kind)) in enumerate(spec.items()):
            if kind == "conv":  # He et al. 2015b: std = sqrt(2 / fan_in)
                std = (2.0 / (shape[1] * shape[2] * shape[3])) ** 0.5
                v = std * jax.random.normal(jax.random.fold_in(key, i), shape, F32)
            elif kind == "dense":
                v = 0.01 * jax.random.normal(jax.random.fold_in(key, i), shape, F32)
            else:
                fill = {"one": 1.0, "zero": 0.0, "residual": config.get("residual_gamma")}
                v = jnp.full(shape, fill[kind], F32)
            out[name] = v.astype(dtype)
        return out

    return make(seeds.key(seed, 1))


def batches(config, traffic, seed):
    """A pool of seeded device-resident batches: images uniform in (-1, 1) in
    the model's type, labels uniform over the classes, every row different."""
    m = config["published"]
    n, b, hw = traffic["pool"], traffic["batch"], m["image"]
    dtype = jnp.dtype(config["dtype"])

    @jax.jit
    def make(key):
        kx, ky = jax.random.split(key)
        x = jax.random.uniform(kx, (n, b, hw, hw, 3), F32, -1.0, 1.0).astype(dtype)
        y = jax.random.randint(ky, (n, b), 0, m["classes"]).astype(F32)
        return x, y

    x, y = make(seeds.key(seed, 2))
    return [(x[i], y[i]) for i in range(n)]


# planted faults (see ``_conv``): quant name -> what the branch's backward does
BRANCH_BACKWARD = {"fp8_branch_backward": "fp8", "negated_branch_backward": "negated"}


def _conv(x, w, stride, pad, quant, branch=False):
    """``quant`` rounds both operands (the control). A planted fault leaves
    every forward pass exact and touches only the backward pass of the
    convolutions inside a residual branch: computed in fp8, or with the
    weights' gradient of the wrong sign."""
    def conv(x, w):
        return jax.lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "OIHW", "NHWC"),
            precision=jax.lax.Precision.HIGHEST)

    if quant in BRANCH_BACKWARD:
        return backward_only(conv, BRANCH_BACKWARD[quant])(x, w) if branch else conv(x, w)
    return conv(operand(x, quant), operand(w, quant))


def _bn(x, g, b):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * jax.lax.rsqrt(var + BN_EPS) * g + b


def _block(p, x, stride, down, quant):
    y = _conv(x, p["conv0.w"], stride, 0, quant, True) + p["conv0.bias"]
    y = jax.nn.relu(_bn(y, p["bn0.g"], p["bn0.b"]))
    y = _conv(y, p["conv1.w"], 1, 1, quant, True)
    y = jax.nn.relu(_bn(y, p["bn1.g"], p["bn1.b"]))
    y = _conv(y, p["conv2.w"], 1, 0, quant, True) + p["conv2.bias"]
    y = _bn(y, p["bn2.g"], p["bn2.b"])
    if down:
        x = _bn(_conv(x, p["down.w"], stride, 0, quant), p["downbn.g"], p["downbn.b"])
    return jax.nn.relu(y + x)


def loss(config, params, x, y, quant=None):
    """Mean softmax cross-entropy of the batch, float32 throughout."""
    m = config["published"]
    p = {k: v.astype(F32) for k, v in params.items()}
    h = _conv(x.astype(F32), p["stem.conv.w"], 2, 3, quant)
    h = jax.nn.relu(_bn(h, p["stem.bn.g"], p["stem.bn.b"]))
    h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                              [(0, 0), (1, 1), (1, 1), (0, 0)])
    for s, n in enumerate(m["layers"], start=1):
        for b in range(n):
            pre = "s%d.b%d." % (s, b)
            bp = {k[len(pre):]: v for k, v in p.items() if k.startswith(pre)}
            stride = 2 if (b == 0 and s > 1) else 1
            h = jax.checkpoint(_block, static_argnums=(2, 3, 4))(
                bp, h, stride, b == 0, quant)
    h = jnp.mean(h, axis=(1, 2))
    fc_quant = None if quant in BRANCH_BACKWARD else quant
    logits = jnp.dot(operand(h, fc_quant), operand(p["fc.w"], fc_quant).T,
                     precision=jax.lax.Precision.HIGHEST) + p["fc.bias"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, y.astype(jnp.int32)[:, None], axis=-1)
    return -jnp.mean(picked)


def value_and_grad(config, params, x, y, quant=None):
    return jax.value_and_grad(lambda p: loss(config, p, x, y, quant))(params)
