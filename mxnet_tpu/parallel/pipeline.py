"""Pipeline parallelism — GPipe-style microbatched stages over a mesh
axis (new capability beyond the reference: its closest analog is manual
`group2ctx` layer placement, SURVEY §2.4 strategy inventory "Pipeline
parallel: none").

Design (the jax-native shape, not a scheduler translation):

- The mesh gets a ``pipe`` axis of S stages; each device holds ONE
  stage's parameters (stacked pytree, leading axis S, sharded over
  ``pipe``).
- One `lax.scan` runs S+M-1 ticks inside a `shard_map`. Each tick every
  stage applies itself to its in-flight activation and hands the result
  to the next stage via `jax.lax.ppermute` — a neighbor hop that rides
  ICI on real hardware.
- The whole schedule is one differentiable XLA program: the backward
  pipeline is jax autodiff of the scan (ppermute's VJP is the reverse
  ppermute), so grads flow stage-by-stage in reverse exactly like the
  1B1F schedule's backward wave — no hand-built backward scheduler.
- Bubbles (S-1 warmup + S-1 drain ticks) compute garbage that is never
  collected; their gradient contribution is exactly zero because the
  output gather only reads real microbatch slots.

Efficiency: pipeline utilization is M/(M+S-1) — pick
``num_microbatches`` >= 4*S to keep the bubble under ~20%.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


from ..base import MXNetError

__all__ = ["pipeline_apply", "stack_stage_params"]


def stack_stage_params(per_stage_params):
    """Stacks a list of identically-structured per-stage pytrees along a
    new leading axis (the ``pipe``-sharded layout pipeline_apply
    expects)."""
    if not per_stage_params:
        raise MXNetError("need at least one stage")
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *per_stage_params)


def pipeline_apply(stage_fn, stage_params, x, mesh, axis="pipe",
                   num_microbatches=None):
    """Runs ``stage_fn`` as an S-stage GPipe pipeline over ``mesh``
    axis ``axis``.

    stage_fn(params_i, h) -> h' : one stage (all stages share this
    structure — the homogeneous-blocks case, e.g. transformer layers).
    stage_params: pytree with leading axis S on every leaf (see
    stack_stage_params).
    x: (B, ...) batch; B must divide into ``num_microbatches``.
    Returns the last stage's output, (B, ...).

    Differentiable; call under jit/grad. Activations hop stages via
    ppermute (ICI neighbor traffic on hardware).
    """
    if axis not in mesh.axis_names:
        raise MXNetError("mesh has no %r axis (axes: %s)"
                         % (axis, mesh.axis_names))
    n_stages = mesh.shape[axis]
    for leaf in jax.tree_util.tree_leaves(stage_params):
        if leaf.shape[0] != n_stages:
            # a multiple would silently shard >1 stage per device and
            # drop all but the first — refuse loudly instead
            raise MXNetError(
                "stage_params leading dim %d must equal the %r axis "
                "size %d (one stage per device)"
                % (leaf.shape[0], axis, n_stages))
    m = num_microbatches or n_stages
    b = x.shape[0]
    if b % m:
        raise MXNetError("batch %d not divisible into %d microbatches"
                         % (b, m))
    mb = b // m

    def per_device(params, xs):  # params: leaves (1, ...); xs: full batch
        params = jax.tree_util.tree_map(lambda a: a[0], params)
        stage = jax.lax.axis_index(axis)
        x_mb = xs.reshape((m, mb) + xs.shape[1:])

        h0 = jnp.zeros((mb,) + xs.shape[1:], xs.dtype)
        out0 = jnp.zeros((m, mb) + xs.shape[1:], xs.dtype)
        # the loop makes the carry device-varying (ppermute); mark the
        # replicated zeros accordingly so scan's carry types line up
        # (jax builds without lax.pcast track varying-ness implicitly —
        # the no-op fallback keeps the schedule identical)
        _pcast = getattr(jax.lax, "pcast", None)
        if _pcast is not None:
            h0 = _pcast(h0, (axis,), to="varying")
            out0 = _pcast(out0, (axis,), to="varying")

        def tick(carry, t):
            h, outs = carry
            # receive the previous stage's activation (stage 0 receives
            # stage S-1's drain garbage and ignores it)
            recv = jax.lax.ppermute(
                h, axis, [(i, (i + 1) % n_stages)
                          for i in range(n_stages)])
            feed_t = jnp.clip(t, 0, m - 1)
            # one-hot select of microbatch feed_t (not x_mb[feed_t]): the
            # gather's transpose is a scatter/DUS that miscompiles under
            # spmd-partitioning on some backends (s64/s32 index compare);
            # the masked sum's VJP is a broadcast multiply instead
            feed_mask = (jnp.arange(m) == feed_t).reshape(
                (m,) + (1,) * (x_mb.ndim - 1))
            x_t = jnp.sum(jnp.where(feed_mask, x_mb, 0.0), axis=0)
            inp = jnp.where(stage == 0,
                            jnp.where(t < m, x_t, 0.0),
                            recv)
            h2 = stage_fn(params, inp)
            # last stage finishes microbatch t-(S-1) at tick t; masked
            # write (where, not cond — keeps shard_map's varying-axis
            # types uniform)
            slot = t - (n_stages - 1)
            write = (stage == n_stages - 1) & (slot >= 0)
            # one-hot masked write instead of dynamic_update_slice: the
            # DUS transpose under spmd-partitioning miscompiles on some
            # backends (s64/s32 index compare); the where-form is the
            # same masked store and keeps varying-axis types uniform
            onehot = jnp.arange(m) == jnp.clip(slot, 0, m - 1)
            mask = (onehot & write).reshape((m,) + (1,) * (outs.ndim - 1))
            outs = jnp.where(mask, h2[None].astype(outs.dtype), outs)
            return (h2, outs), None

        # python-unrolled ticks (the ring-attention treatment): a
        # lax.scan here stacks its carries with dynamic_update_slice,
        # which miscompiles under spmd-partitioning on some backends —
        # and the tick count m + S - 1 is small, so XLA still pipelines
        # the unrolled ppermutes against the stage matmuls
        carry = (h0, out0)
        for t in range(m + n_stages - 1):
            carry, _ = tick(carry, jnp.asarray(t, jnp.int32))
        outs = carry[1]
        return outs.reshape((b,) + xs.shape[1:])[None]  # (1, B, ...)

    spec_params = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    sm = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(spec_params, P()), out_specs=P(axis))
    # jit the schedule: eager shard_map dispatches the unrolled tick
    # body primitive-by-primitive through the mesh machinery (~100ms
    # per collective on the CPU mesh — 15s for an 8×8 schedule); one
    # compiled program runs it in milliseconds. Under an outer jit this
    # inlines.
    stacked = jax.jit(sm)(stage_params, x)  # (S, B, ...) — one real row
    # the last stage's output, WITHOUT stacked[-1]: that slice's
    # transpose is a dynamic_update_slice along the pipe-partitioned
    # dim, which miscompiles under spmd-partitioning on some backends
    # (s64 index vs s32 partition offset); the masked sum transposes to
    # a plain select
    last = jnp.arange(stacked.shape[0]) == stacked.shape[0] - 1
    mask = last.reshape((-1,) + (1,) * (stacked.ndim - 1))
    return jnp.sum(jnp.where(mask, stacked, 0.0), axis=0)


def pipeline_utilization(num_stages, num_microbatches):
    """The GPipe schedule's compute utilization M/(M+S-1)."""
    return num_microbatches / (num_microbatches + num_stages - 1)
