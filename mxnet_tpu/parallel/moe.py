"""Expert parallelism — Switch-style mixture-of-experts over a mesh
axis (new capability beyond the reference: SURVEY §2.4 strategy
inventory "Expert parallel / MoE: none in core").

Layout (the standard EP arrangement): the ``expert`` mesh axis carries
BOTH the token shards (data-parallel) and the experts — device e holds
1/E of the tokens and expert e. One `shard_map` does:

  gate (local) -> capacity-bounded one-hot dispatch (local einsum)
  -> `jax.lax.all_to_all` (tokens travel to their expert's device, ICI)
  -> expert_fn on the device's expert -> reverse all_to_all -> combine.

Everything is dense/static-shaped (the TPU-correct formulation: no
ragged gathers) and differentiable — gradients ride the reverse
all_to_alls. Tokens beyond an expert's capacity are dropped (their
combine weight is 0), exactly like Switch/GShard."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


from ..base import MXNetError

__all__ = ["moe_apply", "stack_expert_params", "switch_load_balance_loss"]


def stack_expert_params(per_expert_params):
    """Stacks identically-structured per-expert pytrees along a new
    leading axis (the ``expert``-sharded layout moe_apply expects)."""
    if not per_expert_params:
        raise MXNetError("need at least one expert")
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *per_expert_params)


def switch_load_balance_loss(gates, dispatch_mask):
    """Switch-Transformer aux loss: E * sum_e f_e * p_e where f_e is
    the fraction of tokens routed to expert e and p_e the mean gate
    probability (Fedus et al. 2101.03961)."""
    e = gates.shape[-1]
    f = dispatch_mask.sum(axis=tuple(range(dispatch_mask.ndim - 1)))
    f = f / jnp.maximum(dispatch_mask.sum(), 1.0)
    p = gates.mean(axis=tuple(range(gates.ndim - 1)))
    return e * jnp.sum(f * p)


def moe_apply(expert_fn, expert_params, gate_w, x, mesh, axis="expert",
              capacity_factor=1.25):
    """Routes tokens to experts over the ``axis`` mesh dimension.

    expert_fn(params_e, tokens) -> tokens' : one expert (a dense MLP in
    the standard Switch block), applied to a (capacity*E, D) slab.
    expert_params: pytree with leading axis E on every leaf.
    gate_w: (D, E) router weights.
    x: (N, D) tokens, N divisible by E (sharded over ``axis``).
    Returns (out (N, D), aux) with aux = (gates, dispatch_mask) for the
    load-balance loss.
    """
    if axis not in mesh.axis_names:
        raise MXNetError("mesh has no %r axis (axes: %s)"
                         % (axis, mesh.axis_names))
    n_exp = mesh.shape[axis]
    for leaf in jax.tree_util.tree_leaves(expert_params):
        if leaf.shape[0] != n_exp:
            raise MXNetError(
                "expert_params leading dim %d must equal the %r axis "
                "size %d (one expert per device)"
                % (leaf.shape[0], axis, n_exp))
    n = x.shape[0]
    if n % n_exp:
        raise MXNetError("token count %d not divisible by %d experts"
                         % (n, n_exp))
    n_local = n // n_exp
    # ceil so the factor always buys headroom (Switch/GShard rounding)
    capacity = max(1, -(-int(n_local * capacity_factor) // n_exp))

    def per_device(params, wg, xs):  # xs: (n_local, D); params (1,...)
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        gates = jax.nn.softmax(xs @ wg, axis=-1)       # (n, E)
        expert_idx = jnp.argmax(gates, axis=-1)        # top-1 routing
        onehot = jax.nn.one_hot(expert_idx, n_exp, dtype=xs.dtype)
        # position of each token within its expert's capacity;
        # one_hot is all-zero for positions >= capacity, which IS the
        # over-capacity drop
        pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot   # (n, E)
        pos_oh = jax.nn.one_hot(pos.sum(-1).astype(jnp.int32),
                                capacity, dtype=xs.dtype)
        dispatch = onehot[:, :, None] * pos_oh[:, None, :]  # (n, E, C)
        gate_val = (gates * onehot).sum(-1)            # (n,)

        slabs = jnp.einsum("nec,nd->ecd", dispatch, xs)  # (E, C, D)
        # tokens travel to their expert's device (one ICI all-to-all)
        recv = jax.lax.all_to_all(slabs, axis, split_axis=0,
                                  concat_axis=0, tiled=True)
        # this device's expert processes everyone's slab for expert e
        out = expert_fn(params, recv.reshape(-1, recv.shape[-1]))
        out = out.reshape(recv.shape[:-1] + (out.shape[-1],))
        back = jax.lax.all_to_all(out, axis, split_axis=0,
                                  concat_axis=0, tiled=True)
        combined = jnp.einsum("nec,ecd->nd", dispatch, back)
        combined = combined * gate_val[:, None]
        return (combined[None], gates[None],
                dispatch.sum(-1)[None])  # lead axis for out_specs

    sm = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P(axis),
                                         expert_params), P(), P(axis)),
        out_specs=(P(axis), P(axis), P(axis)))
    out, gates, mask = sm(expert_params, gate_w, x)
    return out.reshape(x.shape[0], -1), (
        gates.reshape(x.shape[0], -1), mask.reshape(x.shape[0], -1))
