"""The first steps of training as the plain reference takes them: the
family's float32 loss and gradient, and a plain optimizer that keeps weights
and state in the type the configuration states.

``first_steps`` gives what the comparison reads: each step's loss, the norm
of every leaf of the first gradient, its distance from the program's first
gradient (all leaves together, and leaf by leaf), and the norm of every
leaf's change after the last step.

What it keeps on the device, in bytes a parameter for a stored type of two
bytes under Adam: its own weights (2) and state (4), donated to every step,
so that old and new never live together; the first step's float32 gradient
(4), which later steps do not return; and the caller's seeded weights (2),
against which the change is taken. Float32 weights and state exist only
inside a step. The program's first gradient stays on the host and visits the
device one leaf at a time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _store(x, dtype):
    """x rounded to the stored type, kept in float32. ``reduce_precision``
    and not a pair of casts: under jit XLA drops a cast there and back."""
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp, mantissa_bits=info.nmant)


def optimizer_step(opt, dtype, w, g, state, t):
    """One update of one leaf, MXNet's formulas (``sgd_mom_update``,
    ``adam_update`` with the bias correction folded into the rate). ``w`` and
    ``state`` come and go in the stored type: ``_store`` has rounded every
    value that is kept, so the cast loses nothing."""
    w, state = w.astype(F32), [s.astype(F32) for s in state]
    if opt["name"] == "sgd":
        mom = _store(opt["momentum"] * state[0] - opt["learning_rate"] * g, dtype)
        new_w, new_state = _store(w + mom, dtype), (mom,)
    elif opt["name"] == "adam":
        b1, b2, eps = opt.get("beta1", 0.9), opt.get("beta2", 0.999), opt.get("epsilon", 1e-8)
        m = _store(b1 * state[0] + (1.0 - b1) * g, dtype)
        v = _store(b2 * state[1] + (1.0 - b2) * g * g, dtype)
        lr_t = opt["learning_rate"] * (1.0 - b2 ** t) ** 0.5 / (1.0 - b1 ** t)
        new_w, new_state = _store(w - lr_t * m / (jnp.sqrt(v) + eps), dtype), (m, v)
    else:
        raise ValueError("no plain optimizer named %r" % (opt["name"],))
    return new_w.astype(dtype), tuple(s.astype(dtype) for s in new_state)


def state_slots(opt):
    return {"sgd": 1, "adam": 2}[opt["name"]]


def gradient_from_state(opt, state_leaves):
    """The first gradient as the optimizer got it, from its state after one
    step from zero state: momentum = -lr g; Adam's mean = (1 - beta1) g."""
    if opt["name"] == "sgd":
        return -state_leaves[0].astype(F32) / opt["learning_rate"]
    if opt["name"] == "adam":
        return state_leaves[0].astype(F32) / (1.0 - opt.get("beta1", 0.9))
    raise ValueError("no plain optimizer named %r" % (opt["name"],))


def norms(tree):
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(F32)))) for k, v in tree.items()}


def effective_optimizer(config, traffic):
    """The configuration's optimizer at the cell's batch: a rate stated per
    so many samples (Goyal et al. 2017) scales with the global batch."""
    opt = dict(config["optimizer"])
    per = opt.pop("rate_per_batch", None)
    if per:
        opt["learning_rate"] = opt["learning_rate"] * traffic["batch"] / per
    return opt


@jax.jit
def _leaf_sums(got, want):
    return jnp.sum(jnp.square(got.astype(F32) - want)), jnp.sum(jnp.square(want))


def gradient_distance(got, want):
    """How far the gradient ``got`` lies from ``want``: the norm of the
    difference over the norm of ``want`` with all leaves together (which
    weights a leaf by its squared norm, so it stands for the whole gradient
    only where no leaf holds most of that), and the norm of the difference
    leaf by leaf. ``want`` is on the device; ``got`` may lie on the host, and
    is put beside ``want`` one leaf at a time, each gone before the next."""
    num = den = np.float32(0.0)
    leaf = {}
    for k in want:
        n, d = jax.device_get(_leaf_sums(jax.device_put(got[k], want[k].sharding), want[k]))
        num, den, leaf[k] = num + n, den + d, float(np.sqrt(n))
    return float(np.sqrt(num / den)), leaf


def _spread(devices, params, batches):
    """On several chips the reference's batch is split by rows over them, its
    weights copied to each: plain code, partitioned by where its inputs lie."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(list(devices), ("rows",))
    rows, whole = NamedSharding(mesh, P("rows")), NamedSharding(mesh, P())
    return (jax.device_put(params, whole),
            [(jax.device_put(x, rows), jax.device_put(y, rows)) for x, y in batches])


def follower_step(ref, config, opt, quant=None):
    """The jitted step of the follower: float32 loss and gradient of the
    weights it is given in the stored type, then the plain optimizer. It
    donates weights and state; the gradient comes back only where asked for."""
    dtype = jnp.dtype(config["dtype"])

    @functools.partial(jax.jit, static_argnames="with_gradient", donate_argnums=(0, 1))
    def step(p, state, x, y, t, with_gradient=False):
        with jax.default_matmul_precision("highest"):
            value, grads = ref.value_and_grad(
                config, {k: v.astype(F32) for k, v in p.items()}, x, y, quant)
        new_p, new_s = {}, {}
        for k in p:
            new_p[k], new_s[k] = optimizer_step(opt, dtype, p[k], grads[k], state[k], t)
        return value, new_p, new_s, grads if with_gradient else None

    return step


def first_steps(ref, config, opt, params, batches, steps=3, quant=None,
                program_gradient=None, keep_gradient=False, devices=None):
    """Follow the first ``steps`` batches from ``params`` (in the stored type,
    and left as they are). ``program_gradient`` is the program's first
    gradient, leaf by leaf, on the host or the device; the reading then
    carries its distance from the reference's."""
    start, batches = params, batches[:steps]
    if devices is not None and len(devices) > 1:
        start, batches = _spread(devices, params, batches)
    step = follower_step(ref, config, opt, quant)
    # a copy of its own: the step donates what it is given, and the caller's
    # weights are what the change is taken against
    p = jax.jit(lambda tree: jax.tree.map(jnp.copy, tree))(start)
    state = {k: tuple(jnp.zeros_like(v) for _ in range(state_slots(opt)))
             for k, v in p.items()}
    losses, out = [], {}
    for i in range(steps):
        x, y = batches[i]
        value, p, state, grads = step(p, state, x, y, float(i + 1), with_gradient=i == 0)
        losses.append(value)
        if i == 0:
            out["grad_norms"] = jax.jit(norms)(grads)
            if program_gradient is not None:
                out["grad_rel_diff"], out["grad_diff_norms"] = gradient_distance(
                    program_gradient, grads)
            if keep_gradient:
                out["first_gradient"] = grads
            del grads
    out["delta_norms"] = jax.jit(lambda a, b: norms(
        {k: a[k].astype(F32) - b[k].astype(F32) for k in a}))(p, start)
    out["losses"] = [float(v) for v in jax.device_get(losses)]
    for key in ("grad_norms", "delta_norms"):
        out[key] = {k: float(v) for k, v in jax.device_get(out[key]).items()}
    return out
