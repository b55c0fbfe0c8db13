"""The follower as it stood before PR 27, kept as a test's oracle and used by
nothing else: float32 arrays that hold the stored type's values, nothing
donated, a float32 copy of the start, the program's first gradient whole on
the device. ``test_follower.py`` holds ``harness/train_reference.py`` to its
numbers bit for bit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.train_reference import norms, state_slots

F32 = jnp.float32


def _store(x, dtype):
    """x rounded to the stored type, kept in float32. ``reduce_precision``
    and not a pair of casts: under jit XLA drops a cast there and back."""
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp, mantissa_bits=info.nmant)


def optimizer_step(opt, dtype, w, g, state, t):
    """One update of one leaf, MXNet's formulas (``sgd_mom_update``,
    ``adam_update`` with the bias correction folded into the rate)."""
    if opt["name"] == "sgd":
        mom = _store(opt["momentum"] * state[0] - opt["learning_rate"] * g, dtype)
        return _store(w + mom, dtype), (mom,)
    if opt["name"] == "adam":
        b1, b2, eps = opt.get("beta1", 0.9), opt.get("beta2", 0.999), opt.get("epsilon", 1e-8)
        m = _store(b1 * state[0] + (1.0 - b1) * g, dtype)
        v = _store(b2 * state[1] + (1.0 - b2) * g * g, dtype)
        lr_t = opt["learning_rate"] * (1.0 - b2 ** t) ** 0.5 / (1.0 - b1 ** t)
        return _store(w - lr_t * m / (jnp.sqrt(v) + eps), dtype), (m, v)
    raise ValueError("no plain optimizer named %r" % (opt["name"],))


def global_rel_diff(got, want):
    """Norm of the difference of two gradients over the norm of the second,
    all leaves together. It weights a leaf by its squared norm, so it stands
    for the whole gradient only where no leaf holds most of that."""
    num = sum(jnp.sum(jnp.square(got[k].astype(F32) - want[k])) for k in want)
    den = sum(jnp.sum(jnp.square(want[k])) for k in want)
    return jnp.sqrt(num / den)


def leaf_diff_norms(got, want):
    """Norm of the difference of two gradients, leaf by leaf."""
    return norms({k: got[k].astype(F32) - want[k] for k in want})


def _spread(devices, start, batches):
    """On several chips the reference's batch is split by rows over them, its
    weights copied to each: plain code, partitioned by where its inputs lie."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(list(devices), ("rows",))
    rows, whole = NamedSharding(mesh, P("rows")), NamedSharding(mesh, P())
    return (jax.device_put(start, whole),
            [(jax.device_put(x, rows), jax.device_put(y, rows)) for x, y in batches])


def first_steps(ref, config, opt, params, batches, steps=3, quant=None,
                program_gradient=None, keep_gradient=False, devices=None):
    """Follow the first ``steps`` batches from ``params`` (in the stored type).
    ``program_gradient`` is the program's first gradient, leaf by leaf; the
    reading then carries its distance from the reference's."""
    dtype = jnp.dtype(config["dtype"])
    start = {k: v.astype(F32) for k, v in params.items()}
    batches = batches[:steps]
    if devices is not None and len(devices) > 1:
        start, batches = _spread(devices, start, batches)
    slots = state_slots(opt)

    @jax.jit
    def step(p, state, x, y, t):
        with jax.default_matmul_precision("highest"):
            value, grads = ref.value_and_grad(config, p, x, y, quant)
        new_p, new_s = {}, {}
        for k in p:
            new_p[k], new_s[k] = optimizer_step(opt, dtype, p[k], grads[k], state[k], t)
        return value, new_p, new_s, grads

    p = start
    state = {k: tuple(jnp.zeros_like(v) for _ in range(slots)) for k, v in p.items()}
    losses, grad_norms, rel_diff, leaf_diffs, first_gradient = [], None, None, None, None
    for i in range(steps):
        x, y = batches[i]
        value, p, state, grads = step(p, state, x, y, float(i + 1))
        losses.append(value)
        if i == 0:
            grad_norms = jax.jit(norms)(grads)
            if program_gradient is not None:
                rel_diff = jax.jit(global_rel_diff)(program_gradient, grads)
                leaf_diffs = jax.jit(leaf_diff_norms)(program_gradient, grads)
            if keep_gradient:
                first_gradient = grads
        del grads
    delta = jax.jit(lambda a, b: norms({k: a[k] - b[k] for k in a}))(p, start)
    losses, grad_norms, delta, rel_diff, leaf_diffs = jax.device_get(
        (losses, grad_norms, delta, rel_diff, leaf_diffs))
    out = {"losses": [float(v) for v in losses],
           "grad_norms": {k: float(v) for k, v in grad_norms.items()},
           "delta_norms": {k: float(v) for k, v in delta.items()}}
    if rel_diff is not None:
        out["grad_rel_diff"] = float(rel_diff)
        out["grad_diff_norms"] = {k: float(v) for k, v in leaf_diffs.items()}
    if keep_gradient:
        out["first_gradient"] = first_gradient
    return out
