"""Sparse expert layer without dropped tokens, for a chip that holds a share
of the experts (DeepSeek-V3's ``DeepseekV3MoE``; Shazeer et al. 2017 for the
layer, Gale et al. 2022, MegaBlocks, for computing it without a capacity).

One pure function, :func:`moe_ffn`:

* **router** — scores over ALL ``n_routed`` experts, the product in float32:
  ``sigmoid(x W_g^T)`` (``scoring="sigmoid"``, DeepSeek-V3's) or
  ``softmax(x W_g^T)`` over the experts (``scoring="softmax"``, the
  Mixtral / Qwen3-MoE form); the ``top_k`` of ``score + bias`` are chosen
  (the bias is a buffer with no gradient: the family's load balancing
  without an auxiliary loss; ``None`` where the model has none), and
  weighted by ``scaling * score / sum of the chosen scores`` (from the
  scores, not from ``score + bias``: ``norm_topk_prob``). A model whose
  router reads other rows than the experts do (the attention's input, one
  residual add earlier) hands the logits in ready-made
  (``router_logits``; op ``moe_router_logits`` is the same float32
  product);
* **dispatch** — the layer is told which experts it holds,
  ``experts_held=(first, count)``. Token-slots (token x chosen expert) are
  sorted by expert, held experts first; the rows of the slots held are
  gathered, at most a static bound of rows at a time
  (``default_slots_bound`` of the shapes): one XLA gather of the rows laid
  out, each moved once (``_take_rows``);
* **experts** — ``W_down (act(W_gate x) * W_up x)`` of each held expert over
  its own rows, ``act`` SiLU (SwiGLU, the default) or ReLU (ReGLU,
  ``activation="relu"``): three grouped
  matmuls (``ops/grouped_matmul.py``: on a TPU, at whole 128-lane widths, the
  program's own kernels, which visit only the row tiles a group holds;
  ``jax.lax.ragged_dot`` everywhere else);
* **combine** — every token sums its held slots, weighted; what the experts
  that are NOT held would have added is left out (it is computed where they
  live). ``_sum_rows``: on a TPU, at whole 128-lane widths, the program's own
  kernels (``ops/row_gather.py``), which read how many rows exist on the
  device and move only those, one DMA a row; everywhere else XLA's gather of
  a row for EVERY entry of (tokens, top_k), a zero row for the entries not
  held;
* **shared** — the shared expert's gated unit of every token is added.

Nothing is dropped: where more slots are held than that bound, the
rest are taken in further blocks of that many rows, so the result is exact
for every routing. How many further blocks run is read from the data: the
routed experts' part is one differentiable unit (``_routed``, a
``custom_vjp``) whose forward runs the first block and then a loop of
``ceil(slots held / bound) - 1`` trips, counted on the device, and whose
backward takes the first block's cotangents from its kept residuals and
then, in the same loop, recomputes each further block that ran and adds
its cotangents into them. A block that holds no row runs in neither pass
and writes nothing: no zero part, no zero residual, no zero cotangent. The
function returns the tokens each held expert got, the slots it did NOT
compute, which must read 0 (the slots held less the rows that the blocks
which ran handed to their grouped matmuls), and the further blocks it ran.

The two movements of rows are written so that no pass scatters: each is the
other's transpose (``_take_rows`` picks rows forward and is summed back
through the inverse permutation; ``_sum_rows`` the reverse), so forward and
backward are gathers alike: a layer runs each twice a step. What they move:
``take`` the rows laid out (``bound``, twice what an even routing holds: the
chip's gather of 4 KB rows runs at the memory's rate, 0.1-0.2 ms a call, and
neither a DMA a row nor chunks under the count beat it in a step), ``sum``
the rows held where the kernels take it and ``tokens * top_k`` rows where XLA
does (``rows_moved`` counts both from a call's own counts; which branch a
traced movement took is ``telemetry.row_movement_branches()``). A gather
costs the chip a row whatever the row's width, so nothing here gathers one
scalar a slot: the chosen scores (``_pick``) and the experts' first sorted
positions (``starts``) are comparisons and sums over the experts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import telemetry as _telemetry
from . import row_gather
from .grouped_matmul import ROW_TILE, grouped_matmul
from .registry import register

F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
_BOUND_TILE = ROW_TILE  # the bound on the rows is rounded up to this many


def _rows(x, idx):
    return x.at[idx].get(mode="promise_in_bounds")


@jax.custom_vjp
def _take_rows(x, slot, valid, back):
    """``x[slot // k]`` where ``valid``, else 0: (M, H) -> (C, H). Row c is
    copy ``slot[c] % k`` of row ``slot[c] // k`` of ``x``; ``back`` (M, k)
    holds, for every row of ``x``, where in 0..C-1 its copies went (C: no
    such copy)."""
    _telemetry.record_row_movement("take", "gather")
    return jnp.where(valid[:, None], _rows(x, slot // back.shape[1]), jnp.zeros((), x.dtype))


def _take_fwd(x, slot, valid, back):
    return _take_rows(x, slot, valid, back), (slot, valid, back)


def _take_bwd(res, ct):
    slot, valid, back = res
    return _sum_rows(ct, slot, valid, back), None, None, None


@jax.custom_vjp
def _sum_rows(o, slot, valid, back):
    """(C, H) -> (M, H): row m is the sum of ``o[back[m, j]]`` over j, a
    position C standing for a zero row. The transpose of ``_take_rows``.
    Where ``row_gather``'s rule takes the call, only the rows that hold a slot
    are moved (the ``valid`` ones, which lead: row c is entry ``slot[c]`` of
    ``back``); elsewhere XLA gathers a row for every entry of ``back``."""
    kernel = row_gather.kernel_takes(o.shape[0], *back.shape, o.shape[1], o.dtype)
    _telemetry.record_row_movement("sum", "kernel" if kernel else "gather")
    if kernel:
        return row_gather.sum_rows(o, slot, jnp.sum(valid, dtype=jnp.int32), *back.shape)
    return _gathered_sum(o, back)


def _gathered_sum(o, back):
    """``_sum_rows`` by XLA: a gather of a row for every entry of ``back``, a
    zero row for position C, float32 adds in column order, rounded once."""
    ext = jnp.concatenate([o, jnp.zeros((1,) + o.shape[1:], o.dtype)])
    total = _rows(ext, back[:, 0]).astype(F32)
    for j in range(1, back.shape[1]):
        total = total + _rows(ext, back[:, j]).astype(F32)
    return total.astype(o.dtype)


def _sum_fwd(o, slot, valid, back):
    return _sum_rows(o, slot, valid, back), (slot, valid, back)


def _sum_bwd(res, ct):
    slot, valid, back = res
    return _take_rows(ct, slot, valid, back), None, None, None


_take_rows.defvjp(_take_fwd, _take_bwd)
_sum_rows.defvjp(_sum_fwd, _sum_bwd)


def router_product(x, router_w):
    """(..., H) rows -> (..., n_routed) float32 logits, the product in
    float32 at the highest precision."""
    return jnp.dot(x.astype(F32), router_w.astype(F32).T, precision=_HI)


def _pick(scores, idx):
    """``scores[n, idx[n, j]]``, (N, E) and (N, k) -> (N, k), as a comparison
    with every expert's number and a sum over them, exact: one of the terms
    is not zero. Not ``take_along_axis``: a gather costs the chip a row
    whatever a row's width (0.50-0.67 ms a layer for 49-66 thousand scalars,
    PERF.md Findings, PR 42), and its transpose is a scatter."""
    hit = idx[:, :, None] == jnp.arange(scores.shape[-1], dtype=idx.dtype)
    return jnp.sum(jnp.where(hit, scores[:, None, :], 0.0), axis=-1)


def route(x, router_w, router_bias, top_k, scaling, scoring="sigmoid",
          sum_epsilon=1e-20, logits=None):
    """(N, H) tokens -> chosen experts (N, k) int32 and their weights (N, k)
    float32. ``scoring`` is ``"sigmoid"`` (each expert alone) or
    ``"softmax"`` (over all the experts); ``router_bias`` None is no bias;
    ``sum_epsilon`` is what the model's publisher adds to the chosen scores'
    sum before dividing by it (DeepSeek-V3 1e-20, LFM2 1e-6). ``logits``
    (N, n_routed) are the router's product where the caller made it, from
    whatever rows its router reads; ``x`` and ``router_w`` are then unread.
    The gradient reaches ``router_w`` and ``x`` (or ``logits``) through the
    weights; the choice and the bias carry none."""
    if logits is None:
        logits = router_product(x, router_w)
    logits = logits.astype(F32)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError("route: scoring=%r (sigmoid or softmax)" % (scoring,))
    choice = scores
    if router_bias is not None:
        choice = scores + jax.lax.stop_gradient(router_bias.astype(F32))
    _, idx = jax.lax.top_k(jax.lax.stop_gradient(choice), top_k)
    chosen = _pick(scores, idx)
    weights = scaling * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + sum_epsilon)
    return idx.astype(jnp.int32), weights


def default_slots_bound(tokens, top_k, n_routed, count):
    """Rows the experts' matmuls are laid out for: twice what an even
    routing sends to ``count`` of ``n_routed`` experts, in whole tiles, and
    never more than there are slots."""
    slots = tokens * top_k
    if count >= n_routed:
        return slots
    even = -(-slots * count // n_routed)
    return min(slots, -(-2 * even // _BOUND_TILE) * _BOUND_TILE)


def rows_moved(load, ran, tokens, hidden, dtype, top_k, n_routed):
    """int32 (2,): the rows of ``hidden`` that the two movements of one layer
    call moved, forward and backward, and the rows its blocks were laid out
    for, from what the call returned (``load`` (count,), the slots each held
    expert got; ``ran``, the blocks past the first). A block lays out ``bound``
    rows for ``take`` and ``tokens * top_k`` entries for ``sum``, in either
    pass, and XLA's gather reads a row for every one of them; where the
    ``row_gather`` kernels take the sum, it moves a row for every slot held."""
    slots = tokens * top_k
    bound = min(slots, default_slots_bound(tokens, top_k, n_routed, load.shape[0]))
    held, blocks = jnp.sum(load, dtype=jnp.int32), 1 + ran.astype(jnp.int32)
    laid = blocks * (bound + slots)
    kernel = row_gather.kernel_takes(bound, tokens, top_k, hidden, dtype)
    moved = blocks * bound + (held if kernel else blocks * slots)
    return 2 * jnp.stack([moved, laid]).astype(jnp.int32)


_ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _gate(activation):
    """The gate's function of a gated unit: SiLU (SwiGLU) or ReLU (ReGLU)."""
    if activation not in _ACTIVATIONS:
        raise ValueError("moe_ffn: activation=%r (silu or relu)" % (activation,))
    return _ACTIVATIONS[activation]


def _glu_rows(xs, sizes, w_gate, w_up, w_down, act):
    """The gated unit of every row by its group's expert: (C, H) -> (C, H)."""
    h = grouped_matmul(xs, w_gate, sizes)
    u = grouped_matmul(xs, w_up, sizes)
    a = (act(h.astype(F32)) * u.astype(F32)).astype(xs.dtype)
    return grouped_matmul(a, w_down, sizes)


def _block(c, x, wflat, experts, *, bound, act, order, inv, is_held, starts):
    """Rows ``c * bound ...`` of the sorted slots: gathered, through their
    experts, weighted and summed back to their tokens. Returns (N, H) and
    the rows its grouped matmuls were handed, int32 ()."""
    n, k = x.shape[0], inv.shape[0] // x.shape[0]
    lo = c * bound
    with jax.named_scope("dispatch"):
        pos = lo + jnp.arange(bound, dtype=jnp.int32)
        valid = pos < starts[-1]
        slot = jax.lax.dynamic_slice_in_dim(order, lo, bound)
        # where each slot's row sits in this block (bound: not in it)
        here = jnp.logical_and(is_held, jnp.logical_and(inv >= lo, inv < lo + bound))
        back = jnp.where(here, inv - lo, bound).astype(jnp.int32)
        sizes = jnp.clip(starts[1:], lo, lo + bound) - jnp.clip(starts[:-1], lo, lo + bound)
        xs = _take_rows(x, slot, valid, back.reshape(n, k))
    with jax.named_scope("experts"):
        o = _glu_rows(xs, sizes, *experts, act)
    with jax.named_scope("combine"):
        ws = _take_rows(wflat, slot, valid, back.reshape(n * k, 1))
        o = jnp.where(valid[:, None], o.astype(F32) * ws, 0.0).astype(x.dtype)
        y = _sum_rows(o, slot, valid, back.reshape(n, k))
    return y, jnp.sum(sizes, dtype=jnp.int32)


def _further_blocks(held, bound):
    """Blocks past the first that hold a row, int32 (): the trip count of the
    loops over them, read from the slots held."""
    return jnp.maximum((held + bound - 1) // bound - 1, 0)


def _routed_parts(bound, activation, order, inv, is_held, starts):
    """``_block`` of this routing, and how many further blocks it needs
    (None where the first block's rows are all the slots there are: such a
    layer never builds a loop)."""
    block = functools.partial(_block, bound=bound, act=_gate(activation),
                              order=order, inv=inv, is_held=is_held,
                              starts=starts)
    if order.shape[0] <= bound:
        return block, None
    return block, _further_blocks(starts[-1], bound)


def _further_forward(block, trips, x, wflat, experts, y, done):
    """Blocks 1..trips added to block 0's ``y`` and ``done``. No trip: both
    pass through untouched."""
    if trips is None:
        return y, done, jnp.zeros((), jnp.int32)

    def body(c, carry):
        part, rows = block(c, x, wflat, experts)
        return carry[0] + part, carry[1] + rows

    y, done = jax.lax.fori_loop(1, trips + 1, body, (y, done))
    return y, done, trips


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _routed(bound, activation, x, wflat, experts, order, inv, is_held, starts):
    """The routed experts' part of the layer, one differentiable unit: block
    0, then as many further blocks as hold a row (a loop whose trip count is
    read on the device). Returns (N, H), the rows handed to the grouped
    matmuls of the blocks that ran and the further blocks that ran, both
    int32 (). The backward keeps block 0's residuals, recomputes each further
    block that ran and adds its cotangents into block 0's: a block that does
    not run costs nothing in either pass."""
    block, trips = _routed_parts(bound, activation, order, inv, is_held, starts)
    y, done = block(0, x, wflat, experts)
    return _further_forward(block, trips, x, wflat, experts, y, done)


def _routed_fwd(bound, activation, x, wflat, experts, order, inv, is_held,
                starts):
    block, trips = _routed_parts(bound, activation, order, inv, is_held, starts)
    y, vjp0, done = jax.vjp(functools.partial(block, 0), x, wflat, experts,
                            has_aux=True)
    out = _further_forward(block, trips, x, wflat, experts, y, done)
    return out, (vjp0, x, wflat, experts, order, inv, is_held, starts)


def _routed_bwd(bound, activation, res, cts):
    vjp0, x, wflat, experts, order, inv, is_held, starts = res
    ct = cts[0]  # the counts carry no gradient
    block, trips = _routed_parts(bound, activation, order, inv, is_held, starts)
    # a hand-written rule names its own operations: device time is read by
    # scope (``moe``; ``dispatch`` / ``experts`` / ``combine`` come with the
    # blocks), in this pass as in the forward
    with jax.named_scope("moe"):
        grads = vjp0(ct)
        if trips is not None:
            def body(c, grads):
                _, vjp = jax.vjp(lambda *primals: block(c, *primals)[0],
                                 x, wflat, experts)
                return jax.tree_util.tree_map(jnp.add, grads, vjp(ct))

            grads = jax.lax.fori_loop(1, trips + 1, body, grads)
    return grads + (None,) * 4


_routed.defvjp(_routed_fwd, _routed_bwd)


def moe_ffn_raw(x, router_w, router_bias, w_gate, w_up, w_down,
                shared_gate, shared_up, shared_down, *, top_k, n_routed,
                experts_held, scaling=1.0, slots_bound=None,
                scoring="sigmoid", router_gradient=True, sum_epsilon=1e-20,
                router_logits=None, activation="silu"):
    """The expert layer on (N, H) tokens. ``w_gate`` / ``w_up`` are
    (count, H, I) and ``w_down`` (count, I, H): the experts held, expert
    ``first + i`` at row i. ``shared_*`` are the shared expert's weights as
    ``FullyConnected`` keeps them, (I_s, H), (I_s, H), (H, I_s), or None.
    ``router_logits`` (N, n_routed) stand in for ``x @ router_w.T`` where the
    model's router reads other rows; ``activation`` is the gated units' gate,
    ``"silu"`` or ``"relu"``.

    Returns ``(y, load, lost, ran)``: (N, H); int32 (count,) slots each held
    expert got; int32 () slots held and not computed: the slots held less
    the rows that the blocks which ran handed to their grouped matmuls (0:
    every block that holds a row runs); int32 () blocks past the first that
    ran. ``slots_bound`` is for tests: the layers take
    ``default_slots_bound`` of their shapes."""
    n, hidden = x.shape
    first, count = experts_held
    if w_gate.shape[0] != count:
        raise ValueError("experts_held says %d experts, the weights hold %d"
                         % (count, w_gate.shape[0]))
    slots = n * top_k
    bound = int(slots_bound or default_slots_bound(n, top_k, n_routed, count))
    bound = min(bound, slots)
    blocks = -(-slots // bound)
    with jax.named_scope("router"):
        idx, weights = route(x, router_w, router_bias, top_k, scaling, scoring,
                             sum_epsilon, router_logits)
        if not router_gradient:
            weights = jax.lax.stop_gradient(weights)
    with jax.named_scope("dispatch"):
        local = idx.reshape(slots) - first
        is_held = jnp.logical_and(local >= 0, local < count)
        key = jnp.where(is_held, local, count)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inv = jnp.argsort(order).astype(jnp.int32)
        # first sorted position of every held expert, and one past the last:
        # the slots of the experts before it, counted (the sorted keys would
        # be a gather of a scalar a slot, which costs what a row costs)
        starts = jnp.sum(key[None, :] < jnp.arange(count + 1, dtype=jnp.int32)[:, None],
                         axis=1, dtype=jnp.int32)
        load = starts[1:] - starts[:-1]
        order = jnp.pad(order, (0, blocks * bound - slots))
    y, done, ran = _routed(bound, activation, x, weights.reshape(slots, 1),
                           (w_gate, w_up, w_down), order, inv, is_held, starts)
    lost = starts[-1] - done
    if shared_gate is not None:
        with jax.named_scope("shared"):
            dot = functools.partial(jnp.einsum, "nc,oc->no")
            a = (_gate(activation)(dot(x, shared_gate).astype(F32))
                 * dot(x, shared_up).astype(F32)).astype(x.dtype)
            y = y + dot(a, shared_down)
    return y, load, lost, ran


@register("moe_router_logits")
def moe_router_logits(data, router_weight):
    """A router's float32 logits of ``data`` (..., H) under ``router_weight``
    (n_routed, H): what ``moe_ffn`` computes from its own rows, for a model
    whose router reads others (``moe_ffn(router_logits=...)``)."""
    with jax.named_scope("router"):
        return router_product(data, router_weight)


@register("moe_ffn", num_outputs=4, wrt=(0, 1, 3, 4, 5, 6, 7, 8, 9))
def moe_ffn(data, router_weight, router_bias, gate_weight, up_weight,
            down_weight, shared_gate_weight=None, shared_up_weight=None,
            shared_down_weight=None, router_logits=None, top_k=1,
            n_routed=None, experts_held=None, scaling=1.0, scoring="sigmoid",
            router_gradient=True, sum_epsilon=1e-20, activation="silu"):
    """The sparse expert layer of a chip that holds ``experts_held=(first,
    count)`` of ``n_routed`` experts, on ``data`` (..., H): see the module's
    docstring. ``scoring`` is the router's: ``"sigmoid"`` or ``"softmax"``
    over all ``n_routed`` logits; ``router_bias=None`` is a router without a
    selection bias; the weights of the chosen are ``scaling`` times their
    scores over the chosen scores' sum plus ``sum_epsilon`` (the model's own:
    DeepSeek-V3 adds 1e-20, LFM2 1e-6). ``router_gradient=False`` makes the
    chosen experts' weights constants of the loss: no gradient reaches the
    router's weights or the layer's input through them (what a strict share
    trained without the experts' exchange can say of that gradient is a part
    of a sum over all the chips, and applied alone the part pulls every
    token towards the experts held). ``router_logits`` (..., n_routed) are
    the router's product ready-made, where the model's router reads other
    rows than ``data`` (``router_weight`` is then unread here); ``activation``
    is the gate of the experts' units, ``"silu"`` (SwiGLU) or ``"relu"``
    (ReGLU). Returns ``(out, load, lost, ran)``; the three counts carry no
    gradient."""
    n_routed = int(n_routed or router_weight.shape[0])
    held = tuple(int(v) for v in (experts_held or (0, n_routed)))
    lead = data.shape[:-1]
    if router_logits is not None:
        router_logits = router_logits.reshape(-1, router_logits.shape[-1])
    with jax.named_scope("moe"):
        y, *counts = moe_ffn_raw(
            data.reshape(-1, data.shape[-1]), router_weight, router_bias,
            gate_weight, up_weight, down_weight, shared_gate_weight,
            shared_up_weight, shared_down_weight, top_k=int(top_k),
            n_routed=n_routed, experts_held=held, scaling=float(scaling),
            scoring=str(scoring), router_gradient=bool(router_gradient),
            sum_epsilon=float(sum_epsilon), router_logits=router_logits,
            activation=str(activation))
    return (y.reshape(lead + (y.shape[-1],)),
            *(jax.lax.stop_gradient(c) for c in counts))
