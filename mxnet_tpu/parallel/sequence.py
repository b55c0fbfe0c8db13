"""Sequence/context parallelism — ring attention + Ulysses all-to-all
(SURVEY §5: absent from the reference, a first-class new capability here).

Both are shard_map programs over a mesh sequence axis:

- **Ring attention**: Q stays put, K/V blocks rotate around the ring via
  ``ppermute`` (ICI neighbor exchange); each hop folds one KV block into the
  running online-softmax state. Peak memory per chip is O(T/n), enabling
  sequences n× longer than one chip's HBM would allow. Collective order:
  hop i holds the block originally on device (idx - i) mod n.

- **Ulysses**: ``all_to_all`` reshards (T-sharded, all heads) →
  (H-sharded, full T), runs dense local attention, reshards back. One
  collective pair instead of n hops — better when heads ≥ devices and T
  fits per-chip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops.attention import _attention_reference, _NEG_INF

__all__ = ["ring_attention", "ulysses_attention", "sequence_scope",
           "current_sequence_scope"]


def _ring_hop_scores(qf, k_cur, b_cur, idx, src, Tl, causal, sm_scale):
    """Masked score block for one ring hop: (B, H, Tl, Tl) in f32."""
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, k_cur.astype(jnp.float32),
                   preferred_element_type=jnp.float32) * sm_scale
    if b_cur is not None:
        s = s + b_cur.astype(jnp.float32)
    if causal:
        row = idx * Tl + jnp.arange(Tl)
        col = src * Tl + jnp.arange(Tl)
        mask = col[None, :] <= row[:, None]
        s = jnp.where(mask[None, None], s, _NEG_INF)
    return s


def _ring_fwd_pass(q_loc, k_loc, v_loc, bias_loc, axis_name, causal,
                   sm_scale, n_shards):
    """Per-device online-softmax ring. q_loc/k_loc/v_loc: (B, H, Tl, D);
    bias_loc: (B, 1, 1, Tl) additive key bias or None. Returns (out, lse)."""
    B, H, Tl, D = q_loc.shape
    idx = jax.lax.axis_index(axis_name)
    qf = q_loc.astype(jnp.float32)

    m0 = jnp.full((B, H, Tl), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Tl), jnp.float32)
    acc0 = jnp.zeros((B, H, Tl, D), jnp.float32)
    perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]

    def body(i, carry):
        k_cur, v_cur, b_cur, m, l, acc = carry
        src = (idx - i) % n_shards  # which global block k_cur is
        s = _ring_hop_scores(qf, k_cur, b_cur, idx, src, Tl, causal,
                             sm_scale)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_cur.astype(jnp.float32))
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        b_nxt = None if b_cur is None else jax.lax.ppermute(
            b_cur, axis_name, perm)
        return k_nxt, v_nxt, b_nxt, m_new, l_new, acc_new

    carry = (k_loc, v_loc, bias_loc, m0, l0, acc0)
    # n_shards hops: python loop keeps b_cur=None branch static; XLA still
    # pipelines the ppermutes against the matmuls
    for i in range(n_shards):
        carry = body(i, carry)
    _, _, _, m, l, acc = carry
    l = jnp.maximum(l, 1e-30)
    out = (acc / l[..., None]).astype(q_loc.dtype)
    return out, m + jnp.log(l)


# --------------------------------------------------------------------------
# custom VJP: the naive autodiff of the unrolled ring saves every hop's
# (B, H, Tl, Tl) probability block, making backward O(T^2/n) memory
# (round-1 ADVICE #1). Instead we save only out + lse — O(T/n) — and the
# backward re-runs the ring, recomputing each hop's scores from lse and
# rotating dk/dv accumulators along with their K/V blocks so every
# gradient lands back on the chip that owns the block.
# --------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _ring_core(q_loc, k_loc, v_loc, bias_loc, axis_name, causal, sm_scale,
               n_shards):
    out, _ = _ring_fwd_pass(q_loc, k_loc, v_loc, bias_loc, axis_name,
                            causal, sm_scale, n_shards)
    return out


def _ring_core_fwd(q_loc, k_loc, v_loc, bias_loc, axis_name, causal,
                   sm_scale, n_shards):
    out, lse = _ring_fwd_pass(q_loc, k_loc, v_loc, bias_loc, axis_name,
                              causal, sm_scale, n_shards)
    return out, (q_loc, k_loc, v_loc, bias_loc, out, lse)


def _ring_core_bwd(axis_name, causal, sm_scale, n_shards, res, do):
    q_loc, k_loc, v_loc, bias_loc, out, lse = res
    B, H, Tl, D = q_loc.shape
    idx = jax.lax.axis_index(axis_name)
    qf = q_loc.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    delta = jnp.sum(dof * out.astype(jnp.float32), axis=-1)  # (B, H, Tl)
    perm = [(j, (j + 1) % n_shards) for j in range(n_shards)]

    dq = jnp.zeros((B, H, Tl, D), jnp.float32)
    dk_acc = jnp.zeros((B, H, Tl, D), jnp.float32)
    dv_acc = jnp.zeros((B, H, Tl, D), jnp.float32)
    # accumulator matches bias's own shape so broadcast dims (e.g. a
    # (1, 1, 1, T) shared bias with B > 1) get summed, not silently
    # expanded to a wrong-shaped per-example grad
    db_acc = None if bias_loc is None else jnp.zeros(bias_loc.shape,
                                                     jnp.float32)

    k_cur, v_cur, b_cur = k_loc, v_loc, bias_loc
    for i in range(n_shards):
        src = (idx - i) % n_shards
        s = _ring_hop_scores(qf, k_cur, b_cur, idx, src, Tl, causal,
                             sm_scale)
        p = jnp.exp(s - lse[..., None])  # exact probs from saved lse
        dv_acc = dv_acc + jnp.einsum("bhqk,bhqd->bhkd", p, dof)
        dp = jnp.einsum("bhqd,bhkd->bhqk", dof,
                        v_cur.astype(jnp.float32))
        ds = p * (dp - delta[..., None])  # dL/ds_total (pre-scale)
        dq = dq + jnp.einsum("bhqk,bhkd->bhqd", ds,
                             k_cur.astype(jnp.float32)) * sm_scale
        dk_acc = dk_acc + jnp.einsum("bhqk,bhqd->bhkd", ds, qf) * sm_scale
        if b_cur is not None:
            # reduce ds (B, H, Tq, Tk) onto the bias's own shape: sum
            # exactly the axes the bias broadcasts over (H=1 shared
            # biases sum heads; per-head (B, H, 1, Tk) biases — ALiBi —
            # keep their head axis)
            db = ds
            for ax in range(db.ndim):
                if bias_loc.shape[ax] == 1 and db.shape[ax] != 1:
                    db = jnp.sum(db, axis=ax, keepdims=True)
            db_acc = db_acc + db
        # rotate the block with its accumulators; after n hops each dk/dv
        # (and db) lands back on the chip that owns its K/V block
        k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
        v_cur = jax.lax.ppermute(v_cur, axis_name, perm)
        dk_acc = jax.lax.ppermute(dk_acc, axis_name, perm)
        dv_acc = jax.lax.ppermute(dv_acc, axis_name, perm)
        if b_cur is not None:
            b_cur = jax.lax.ppermute(b_cur, axis_name, perm)
            db_acc = jax.lax.ppermute(db_acc, axis_name, perm)

    dbias = None if bias_loc is None else db_acc.astype(bias_loc.dtype)
    return (dq.astype(q_loc.dtype), dk_acc.astype(k_loc.dtype),
            dv_acc.astype(v_loc.dtype), dbias)


_ring_core.defvjp(_ring_core_fwd, _ring_core_bwd)


def ring_attention(q, k, v, bias=None, mesh=None, seq_axis="data",
                   causal=False, sm_scale=None):
    """Sequence-parallel attention with ring KV rotation.

    q/k/v: (B, H, T, D) with T sharded over ``mesh[seq_axis]``; bias:
    optional additive (B, 1, 1, T) key bias (sharded on its T too).
    Returns (B, H, T, D) sharded like q.
    """
    if mesh is None:
        raise ValueError("ring_attention requires mesh= (a jax Mesh with "
                         "a %r axis)" % (seq_axis,))
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    n_shards = mesh.shape[seq_axis]
    if q.shape[2] % n_shards:
        raise ValueError("sequence length %d not divisible by %d shards"
                         % (q.shape[2], n_shards))

    qkv_spec = P(None, None, seq_axis, None)
    scale = float(sm_scale)
    q, k, v = _commit_to_mesh(mesh, qkv_spec, q, k, v)
    if bias is not None:
        bias, = _commit_to_mesh(mesh, P(None, None, None, seq_axis),
                                bias)
        sm = _ring_callable(mesh, seq_axis, causal, scale, n_shards,
                            True)
        return sm(q, k, v, bias)
    sm = _ring_callable(mesh, seq_axis, causal, scale, n_shards, False)
    return sm(q, k, v)


def _commit_to_mesh(mesh, spec, *arrays):
    """device_put arrays onto the mesh sharding — inputs may live on one
    device while the mesh spans several (eager scope dispatch, or its
    vjp trace); under jit this lowers to a sharding constraint."""
    from jax.sharding import NamedSharding

    sh = NamedSharding(mesh, spec)
    return tuple(jax.device_put(a, sh) for a in arrays)


@functools.lru_cache(maxsize=64)
def _ring_callable(mesh, seq_axis, causal, scale, n_shards, has_bias):
    """Jitted shard_map program, cached by configuration — a fresh
    lambda per call would force a recompile per attention call (63 s/fwd
    for a 4-layer GPT before this cache; one compile per shape after)."""
    qkv_spec = P(None, None, seq_axis, None)
    if has_bias:
        sm = jax.shard_map(
            lambda q_, k_, v_, b_: _ring_core(q_, k_, v_, b_, seq_axis,
                                              causal, scale, n_shards),
            mesh=mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec,
                      P(None, None, None, seq_axis)),
            out_specs=qkv_spec,
        )
    else:
        sm = jax.shard_map(
            lambda q_, k_, v_: _ring_core(q_, k_, v_, None, seq_axis,
                                          causal, scale, n_shards),
            mesh=mesh,
            in_specs=(qkv_spec, qkv_spec, qkv_spec),
            out_specs=qkv_spec,
        )
    return jax.jit(sm)


def _ulysses_local(q_loc, k_loc, v_loc, *, axis_name, causal, sm_scale):
    """(B, H, Tl, D) T-sharded → all_to_all → (B, H/n, T, D) H-sharded →
    dense local attention → reshard back."""
    a2a = functools.partial(jax.lax.all_to_all, axis_name=axis_name,
                            tiled=True)
    q2 = a2a(q_loc, split_axis=1, concat_axis=2)
    k2 = a2a(k_loc, split_axis=1, concat_axis=2)
    v2 = a2a(v_loc, split_axis=1, concat_axis=2)
    out = _attention_reference(q2, k2, v2, None, causal, sm_scale)
    return a2a(out, split_axis=2, concat_axis=1)


def ulysses_attention(q, k, v, mesh=None, seq_axis="data", causal=False,
                      sm_scale=None):
    """All-to-all (DeepSpeed-Ulysses style) sequence parallelism. Heads must
    be divisible by the mesh axis size."""
    if mesh is None:
        raise ValueError("ulysses_attention requires mesh= (a jax Mesh "
                         "with a %r axis)" % (seq_axis,))
    if sm_scale is None:
        sm_scale = 1.0 / float(np.sqrt(q.shape[-1]))
    n_shards = mesh.shape[seq_axis]
    if q.shape[1] % n_shards:
        raise ValueError("num_heads %d not divisible by %d shards"
                         % (q.shape[1], n_shards))
    if q.shape[2] % n_shards:
        raise ValueError("sequence length %d not divisible by %d shards"
                         % (q.shape[2], n_shards))
    spec = P(None, None, seq_axis, None)
    q, k, v = _commit_to_mesh(mesh, spec, q, k, v)
    sm = _ulysses_callable(mesh, seq_axis, causal, float(sm_scale))
    return sm(q, k, v)


@functools.lru_cache(maxsize=64)
def _ulysses_callable(mesh, seq_axis, causal, sm_scale):
    """Jitted shard_map program, cached by configuration (same
    recompile-per-call hazard _ring_callable fixes for the ring)."""
    spec = P(None, None, seq_axis, None)
    sm = jax.shard_map(
        functools.partial(_ulysses_local, axis_name=seq_axis,
                          causal=causal, sm_scale=sm_scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
    )
    return jax.jit(sm)


# ---------------------------------------------------------------------------
# sequence-parallel scope: any flash_attention op called inside it (eager
# or traced — model zoo, gluon blocks, symbols) dispatches to a
# sequence-parallel schedule (ring, or Ulysses when eligible) with zero
# model changes
# ---------------------------------------------------------------------------
import contextlib as _contextlib
import threading as _threading

_SP_STATE = _threading.local()


@_contextlib.contextmanager
def sequence_scope(mesh, seq_axis="sp", schedule="ring"):
    """Route every flash_attention inside the scope through a
    sequence-parallel schedule over ``mesh[seq_axis]`` (the op reads
    this scope at call time — ops/attention.py flash_attention). The
    model code does not change; the sequence axis of q/k/v must divide
    by the axis size.

    schedule: "ring" (KV rotation; works with biases and any head
    count) or "ulysses" (head all-to-all; needs heads divisible by the
    axis size and no bias — falls back to ring when those don't hold).
    """
    if schedule not in ("ring", "ulysses"):
        raise ValueError("schedule must be 'ring' or 'ulysses', got %r"
                         % (schedule,))
    prev = getattr(_SP_STATE, "scope", None)
    _SP_STATE.scope = (mesh, seq_axis, schedule)
    try:
        yield
    finally:
        _SP_STATE.scope = prev


def current_sequence_scope():
    return getattr(_SP_STATE, "scope", None)
