"""``ops/row_gather.py``: the expert layer's sum of rows back to their tokens
as the kernels that move only the rows that exist, in interpret mode on the
CPU, against XLA's gathers of every entry (``ops/moe.py``'s ``_sum_rows`` as
it runs wherever the rule refuses), forward and as the backward of
``_take_rows``.

The kernels copy rows and add exact zeros less, in the gathers' own column
order and float32, so every comparison here is for equality, in bfloat16 and
in float32, of values and of gradients.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import moe as M
from mxnet_tpu.ops import row_gather as RG

F32 = jnp.float32
WIDTH = 256  # the least a bfloat16 row may be: two whole 128-lane lengths of words


def _layout(seed, tokens, k, rows, held):
    """A block of ``rows`` rows laid out of which the first ``held`` hold a
    slot of (tokens, k), as ``moe._block`` hands it to the movements: the
    token of every row, which rows hold one, and where every slot's row is
    (``rows``: nowhere here)."""
    rs = np.random.RandomState(seed)
    slots = rs.permutation(tokens * k)[:held]  # the slot of row 0, 1, ...
    slot = np.concatenate([slots, rs.randint(0, tokens * k, rows - held)])
    back = np.full(tokens * k, rows)
    back[slots] = np.arange(held)
    return (jnp.asarray(slot, jnp.int32), jnp.arange(rows) < held,
            jnp.asarray(back.reshape(tokens, k), jnp.int32))


def _both_passes(move, src, idx, valid, back, ct):
    out, vjp = jax.vjp(lambda s: move(s, idx, valid, back), src)
    return out, vjp(ct)[0]


def _same(got, want):
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert bool(jnp.all(g == w))


HELD = {"no_row": 0, "one_row": 1, "a_tiles_edge": 128, "every_row": None}


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("held", sorted(HELD))
@pytest.mark.parametrize("k", [1, 4, 6, 8])
def test_both_movements_with_the_sum_by_the_kernels_equal_the_gathers(k, held, dtype,
                                                                      row_gather_kernel):
    """``sum`` forward and as the backward of ``take`` (which stays XLA's
    gather, forward and as the backward of ``sum``), for a block that holds no
    row, one, a tile's worth and every row it is laid out for; k = 1 is a
    layer that sends a token to one expert."""
    tokens, rows = 256, 256
    n_held = rows if HELD[held] is None else min(HELD[held], rows)
    idx, valid, back = _layout(k, tokens, k, rows, n_held)
    x = jax.random.normal(jax.random.PRNGKey(1), (tokens, WIDTH), F32).astype(dtype)
    o = jax.random.normal(jax.random.PRNGKey(2), (rows, WIDTH), F32).astype(dtype)
    want = (_both_passes(M._take_rows, x, idx, valid, back, o),
            _both_passes(M._sum_rows, o, idx, valid, back, x))
    before = telemetry.row_movement_branches()
    row_gather_kernel()
    got = (_both_passes(M._take_rows, x, idx, valid, back, o),
           _both_passes(M._sum_rows, o, idx, valid, back, x))
    _same(got, want)
    after = telemetry.row_movement_branches()
    # each movement once forward and once as the other's backward
    assert after["sum"]["kernel"] - before.get("sum", {}).get("kernel", 0) == 2
    assert after["take"]["gather"] - before["take"]["gather"] == 2
    assert "kernel" not in after["take"]


@pytest.mark.parametrize("k", [4, 6, 8])
def test_a_token_that_holds_all_its_slots_sums_them_in_column_order(k, row_gather_kernel):
    """One token's k slots are the only rows there are: float32 sums of k
    bfloat16 rows depend on the order, and the kernel's is the gathers'."""
    tokens, rows = 128, 256
    back = np.full((tokens, k), rows)
    back[5] = np.random.RandomState(k).permutation(k)
    slot = np.zeros(rows, np.int64)
    slot[back[5]] = 5 * k + np.arange(k)
    back, slot = jnp.asarray(back, jnp.int32), jnp.asarray(slot, jnp.int32)
    scale = jnp.logspace(-3, 3, rows, dtype=F32)[:, None]  # sums that round differently by order
    o = (scale * jax.random.normal(jax.random.PRNGKey(3), (rows, WIDTH), F32)).astype(jnp.bfloat16)
    valid = jnp.arange(rows) < k
    want = M._sum_rows(o, slot, valid, back)
    row_gather_kernel()
    got = M._sum_rows(o, slot, valid, back)
    _same(got, want)
    assert bool(jnp.any(got[5])) and not bool(jnp.any(got[:5])) and not bool(jnp.any(got[6:]))


def _layer(dtype, hidden=WIDTH, width=128, n_routed=8, count=4, tokens=384):
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    normal = lambda key, shape, std: (std * jax.random.normal(key, shape, F32))  # noqa: E731
    p = dict(router=normal(ks[0], (n_routed, hidden), 0.2),
             bias=jnp.zeros((n_routed,), F32).at[:count].add(10.0),  # every slot held
             gate=normal(ks[1], (count, hidden, width), 0.1),
             up=normal(ks[2], (count, hidden, width), 0.1),
             down=normal(ks[3], (count, width, hidden), 0.1))
    x, ct = normal(ks[4], (tokens, hidden), 1.0), normal(ks[5], (tokens, hidden), 1.0)
    return jax.tree_util.tree_map(lambda v: v.astype(dtype), (p, x, ct))


def _run_layer(p, x, ct, bound):
    def layer(p, x):
        return M.moe_ffn_raw(x, p["router"], p["bias"], p["gate"], p["up"], p["down"],
                             None, None, None, top_k=2, n_routed=8, experts_held=(0, 4),
                             slots_bound=bound)

    (y, load, lost, ran), vjp = jax.vjp(layer, p, x)
    zeros = tuple(jnp.zeros(c.shape, jax.dtypes.float0) for c in (load, lost, ran))
    return y, vjp((ct,) + zeros), int(lost), int(ran)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("bound", [None, 256], ids=["one_block", "further_blocks"])
def test_expert_layer_through_the_kernel_equals_the_gathers(bound, dtype, row_gather_kernel):
    """``moe_ffn`` and ``jax.grad`` of it with the sums by the kernels against
    the same layer by XLA's gathers: equal, the first block's kept residuals
    and the recomputed further blocks (``lo > 0``: the rows before a block are
    not in it either) alike."""
    p, x, ct = _layer(dtype)
    want, want_grads, _, _ = _run_layer(p, x, ct, bound)
    before = telemetry.row_movement_branches()
    row_gather_kernel()
    got, got_grads, lost, ran = _run_layer(p, x, ct, bound)
    after = telemetry.row_movement_branches()
    assert lost == 0 and ran == (0 if bound is None else 2)
    _same((got, got_grads), (want, want_grads))
    assert after["sum"]["kernel"] > before.get("sum", {}).get("kernel", 0)
    # the slots' weights are one column: XLA's, gathered forward and summed back
    assert after["sum"]["gather"] > before["sum"]["gather"]


CELLS = {"smallthinker": (24576, 6, 2560), "keye": (16384, 8, 2048),
         "kanana": (12288, 6, 2048), "lfm2": (16384, 4, 2048)}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_rule_takes_the_sum_at_a_cells_shape_on_a_tpu(cell, monkeypatch):
    bound, k, hidden = CELLS[cell]
    assert not RG.kernel_takes(bound, 8192, k, hidden, "bfloat16")  # this is the CPU
    monkeypatch.setattr(RG, "on_tpu", lambda: True)
    assert RG.kernel_takes(bound, 8192, k, hidden, "bfloat16")
    assert RG.kernel_takes(bound, 8192, k, hidden, "float32")


REFUSED = {"one_column_of_weights": (16384, 65536, 1, 1, "float32"),
           "odd_width": (16384, 8192, 6, 200, "float32"),
           "one_lane_length_of_bfloat16": (16384, 8192, 6, 128, "bfloat16"),
           "tokens_not_whole_tiles": (16384, 8200, 6, 2048, "bfloat16"),
           "rows_not_whole_steps": (16512, 8192, 6, 2048, "bfloat16"),
           "no_rows": (0, 8192, 6, 2048, "bfloat16"),
           "more_columns_than_a_listed_row_names": (16384, 8192, 17, 2048, "bfloat16"),
           "sixteen_bit_floats_of_another_kind": (16384, 8192, 6, 2048, "float16"),
           "integers": (16384, 8192, 6, 2048, "int32"),
           "more_rows_in_flight_than_vmem_holds": (16384, 8192, 16, 16384, "float32")}


@pytest.mark.parametrize("case", sorted(REFUSED))
def test_rule_refuses(case, monkeypatch):
    monkeypatch.setattr(RG, "on_tpu", lambda: True)
    assert not RG.kernel_takes(*REFUSED[case])


def test_movements_on_the_cpu_are_the_gathers_and_counted():
    slot, valid, back = _layout(0, 128, 4, 256, 100)
    x = jnp.ones((128, WIDTH), jnp.bfloat16)
    before = telemetry.row_movement_branches()
    text = str(jax.make_jaxpr(lambda x: M._sum_rows(M._take_rows(x, slot, valid, back),
                                                    slot, valid, back))(x))
    after = telemetry.row_movement_branches()
    assert "row_gather" not in text and "gather" in text
    for movement in ("take", "sum"):
        assert after[movement]["gather"] - before.get(movement, {}).get("gather", 0) == 1
    assert 'mxt_row_movement_total{movement="take",branch="gather"}' \
        in telemetry.render_prometheus()


@pytest.mark.parametrize("branch", ["gather", "kernel"])
def test_rows_moved_reads_the_slots_held_under_the_kernels(branch, monkeypatch):
    """Kanana's layer: 8192 tokens, 6 of 128 experts each, 16 held, 12288
    rows laid out. ``take`` moves the rows laid out in either pass; XLA's sum
    a row for every entry, the kernels' a row for every slot held."""
    if branch == "kernel":
        monkeypatch.setattr(RG, "on_tpu", lambda: True)
    load = jnp.full((16,), 460, jnp.int32)
    laid = 2 * (12288 + 8192 * 6)
    moved = 2 * (12288 + 16 * 460) if branch == "kernel" else laid
    got = M.rows_moved(load, jnp.int32(0), 8192, 2048, "bfloat16", 6, 128)
    assert got.dtype == jnp.int32 and [int(v) for v in got] == [moved, laid]
    got = M.rows_moved(load, jnp.int32(2), 8192, 2048, "bfloat16", 6, 128)
    moved = 2 * (3 * 12288 + 16 * 460) if branch == "kernel" else 3 * laid
    assert [int(v) for v in got] == [moved, 3 * laid]


ROUTERS = {"smallthinker": (64, 6), "keye": (128, 8), "lfm2": (64, 4), "two_of_eight": (8, 2)}


@pytest.mark.parametrize("scoring", ["sigmoid", "softmax"])
@pytest.mark.parametrize("router", sorted(ROUTERS))
def test_route_picks_the_chosen_scores_without_a_gather(router, scoring):
    """A gather of one scalar a slot costs the chip what a gather of rows
    costs: ``route`` compares and sums. Weights and their gradient equal
    ``take_along_axis``'s, and no gather or scatter is traced in either pass."""
    n_routed, k = ROUTERS[router]
    logits = jax.random.normal(jax.random.PRNGKey(k), (256, n_routed), F32)
    g = jax.random.normal(jax.random.PRNGKey(1), (256, k), F32)

    def weights(logits, pick):
        scores = jax.nn.sigmoid(logits) if scoring == "sigmoid" else jax.nn.softmax(logits, -1)
        _, idx = jax.lax.top_k(scores, k)
        chosen = pick(scores, idx)
        return 2.5 * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)

    routed = lambda logits: M.route(None, None, None, k, 2.5, scoring, logits=logits)[1]  # noqa: E731
    want, want_vjp = jax.vjp(lambda l: weights(l, lambda s, i: jnp.take_along_axis(s, i, -1)), logits)
    got, got_vjp = jax.vjp(routed, logits)
    _same((got, got_vjp(g)), (want, want_vjp(g)))
    text = str(jax.make_jaxpr(lambda l: jax.vjp(routed, l)[1](g))(logits))
    assert "gather" not in text and "scatter" not in text


@pytest.mark.parametrize("held", [(0, 4), (2, 4), (5, 3), (0, 8)], ids=str)
def test_starts_are_the_slots_of_the_experts_before_counted(held):
    """``load`` (the starts' differences) is the count of slots each held
    expert got, and nothing is lost, for a share that starts anywhere."""
    p, x, _ = _layer("float32")
    first, count = held
    experts = [jnp.concatenate([p[w], p[w]])[:count] for w in ("gate", "up", "down")]
    _, load, lost, _ = M.moe_ffn_raw(x, p["router"], None, *experts, None, None, None,
                                     top_k=2, n_routed=8, experts_held=held)
    idx, _ = M.route(x, p["router"], None, 2, 1.0)
    want = np.bincount(np.asarray(idx).reshape(-1), minlength=8)[first:first + count]
    assert [int(v) for v in load] == [int(v) for v in want] and int(lost) == 0
    text = str(jax.make_jaxpr(lambda x: M.moe_ffn_raw(
        x, p["router"], None, p["gate"], p["up"], p["down"], None, None, None, top_k=2,
        n_routed=8, experts_held=(0, 4))[1])(x))
    assert "searchsorted" not in text
