"""``ops/grouped_matmul.py``: the expert layer's grouped matmuls as the
program's own kernels, in interpret mode on the CPU against a per-group dense
product in float32 and against ``jax.lax.ragged_dot``; the rule that says
which call takes them; the counter that says which did."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import grouped_matmul as GM

F32 = jnp.float32
C, K, N, G = 1024, 256, 128, 5

# rows a group, by what the walk over the tiles has to get right
SIZES = {
    "tile_aligned": [256, 128, 128, 256, 128],
    "not_aligned": [100, 301, 7, 200, 150],
    "smaller_than_a_tile": [3, 5, 1, 2, 9],
    "empty_first": [0, 0, 300, 200, 100],
    "empty_last": [300, 200, 100, 0, 0],
    "empty_between": [300, 0, 0, 200, 100],
    "one_group_holds_all": [0, 0, 1024, 0, 0],
    "sum_at_the_rows": [200, 200, 200, 200, 224],
    "sum_at_half": [100, 100, 100, 100, 112],
    "sum_at_zero": [0, 0, 0, 0, 0],
}
PRODUCTS = ("fwd", "dx", "dw")
TOL = {"float32": 1e-5, "bfloat16": 1e-2}


def _operands(dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (C, K), F32).astype(dtype),
            jax.random.normal(ks[1], (G, K, N), F32).astype(dtype),
            jax.random.normal(ks[2], (C, N), F32).astype(dtype))


def _dense(product, x, w, dy, sizes):
    """The product group by group, plain float32 matmuls of the rows held."""
    x, w, dy = (np.asarray(v, np.float32) for v in (x, w, dy))
    out = {"fwd": np.zeros((C, N)), "dx": np.zeros((C, K)),
           "dw": np.zeros((G, K, N))}[product].astype(np.float32)
    lo = 0
    for g, n in enumerate(sizes):
        rows = slice(lo, lo + n)
        if product == "fwd":
            out[rows] = x[rows] @ w[g]
        elif product == "dx":
            out[rows] = dy[rows] @ w[g].T
        else:
            out[g] = x[rows].T @ dy[rows]
        lo += n
    return out


def _kernel(product, x, w, dy, sizes, **kw):
    return GM._kernel(product, x, w, dy, sizes, interpret=True, **kw)


def _ragged(product, x, w, dy, sizes):
    if product == "fwd":
        return jax.lax.ragged_dot(x, w, sizes)
    out, vjp = jax.vjp(lambda x, w: jax.lax.ragged_dot(x, w, sizes), x, w)
    return vjp(dy.astype(out.dtype))[0 if product == "dx" else 1]


def _close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    scale = max(float(np.abs(want).max()), 1.0)
    assert float(np.abs(got - want).max()) <= tol * scale


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("product", PRODUCTS)
@pytest.mark.parametrize("case", sorted(SIZES))
def test_kernel_equals_the_dense_product_and_ragged_dot(case, product, dtype):
    x, w, dy = _operands(jnp.dtype(dtype))
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    got = _kernel(product, x, w, dy, sizes)
    assert got.dtype == jnp.dtype(dtype)
    _close(got, _dense(product, x, w, dy, SIZES[case]), TOL[dtype])
    _close(got, _ragged(product, x, w, dy, sizes), TOL[dtype])


@pytest.mark.parametrize("tm", [128, 256, 512])
@pytest.mark.parametrize("product", PRODUCTS)
def test_every_row_tile_gives_the_same_product(product, tm):
    x, w, dy = _operands(F32, seed=1)
    sizes = SIZES["not_aligned"]
    got = _kernel(product, x, w, dy, jnp.asarray(sizes, jnp.int32), tm=tm)
    _close(got, _dense(product, x, w, dy, sizes), 1e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("product", PRODUCTS)
@pytest.mark.parametrize("case", ["not_aligned", "sum_at_half", "sum_at_zero"])
def test_rows_no_group_holds_read_zero_whatever_lies_there(case, product, dtype):
    """NaN planted in every operand row past ``sum(sizes)``: the result's rows
    there are exactly 0 (stored, not left as they were), and nothing of it
    reaches a group's rows or a weight gradient."""
    x, w, dy = _operands(jnp.dtype(dtype), seed=2)
    held = sum(SIZES[case])
    sizes = jnp.asarray(SIZES[case], jnp.int32)
    got = np.asarray(_kernel(product, x.at[held:].set(jnp.nan), w,
                             dy.at[held:].set(jnp.nan), sizes), np.float32)
    assert np.all(np.isfinite(got))
    if product != "dw":
        assert not np.any(got[held:])
    _close(got, _dense(product, x, w, dy, SIZES[case]), TOL[dtype])


@pytest.mark.parametrize("empty_visits", [0, 1])
@pytest.mark.parametrize("tm", [128, 512])
@pytest.mark.parametrize("case", sorted(SIZES))
def test_the_walk_visits_each_tile_a_group_holds_once_and_no_other(case, tm, empty_visits):
    sizes = SIZES[case]
    group, tile, starts, counts = (np.asarray(v) for v in GM._plan(
        jnp.asarray(sizes, jnp.int32), C, tm, empty_visits))
    assert len(group) == len(tile) == C // tm + G - 1
    assert list(starts) == [0] + list(np.cumsum(sizes))
    want, lo = [], 0
    for g, n in enumerate(sizes):
        if n:
            want += [(g, t) for t in range(lo // tm, -(-(lo + n) // tm))]
        elif empty_visits:
            want.append((g, min(lo // tm, C // tm - 1)))
        lo += n
    visits = int(counts[0])
    assert list(zip(group[:visits], tile[:visits])) == want
    assert np.all(np.diff(tile[:visits]) >= 0)  # a tile's visits are neighbours
    if empty_visits:  # the weight gradient's walk: no rows to zero past the last
        return
    # then the tiles past the rows held, each once; then nothing new
    tail = list(range(-(-lo // tm), C // tm))
    assert list(tile[visits:int(counts[1])]) == tail
    assert int(counts[1]) <= len(tile)
    assert len(set(zip(group[int(counts[1]):], tile[int(counts[1]):]))) <= 1
    assert np.all(np.diff(tile[:int(counts[1])]) >= 0)


SHAPES = {  # (rows, k, n, dtype) -> whether the kernels take the call on a TPU
    "kanana": ((12288, 2048, 768, "bfloat16"), True),
    "lfm2": ((16384, 2048, 1536, "bfloat16"), True),
    "lfm2_down": ((16384, 1536, 2048, "bfloat16"), True),
    "float32": ((1024, 256, 128, "float32"), True),
    "rows_not_whole_tiles": ((1280 + 128, 256, 128, "bfloat16"), False),
    "odd_width": ((1024, 256, 24, "bfloat16"), False),
    "odd_contraction": ((1024, 32, 128, "bfloat16"), False),
    "float16": ((1024, 256, 128, "float16"), False),
    "no_rows": ((0, 256, 128, "bfloat16"), False),
    "blocks_past_vmem": ((1024, 8192, 8192, "float32"), False),
}


@pytest.mark.parametrize("product", PRODUCTS)
@pytest.mark.parametrize("case", sorted(SHAPES))
def test_the_rule_reads_the_shapes_and_the_device(case, product, monkeypatch):
    (rows, k, n, dtype), want = SHAPES[case]
    assert not GM._kernel_takes(product, rows, k, n, dtype)  # here: a CPU
    monkeypatch.setattr(GM, "on_tpu", lambda: True)
    assert GM._kernel_takes(product, rows, k, n, dtype) == want


def _count(product, branch):
    return telemetry.grouped_matmul_branches().get(product, {}).get(branch, 0)


def test_on_the_cpu_the_call_is_ragged_dot_and_says_so():
    x, w, dy = _operands(F32, seed=3)
    sizes = jnp.asarray(SIZES["not_aligned"], jnp.int32)
    before = _count("fwd", "ragged_dot"), _count("fwd", "kernel")
    text = str(jax.make_jaxpr(jax.grad(
        lambda x, w: jnp.sum(GM.grouped_matmul(x, w, sizes) * dy), (0, 1)))(x, w))
    assert "ragged_dot" in text and "pallas_call" not in text and "custom_vjp" not in text
    assert (_count("fwd", "ragged_dot"), _count("fwd", "kernel")) == (before[0] + 1, before[1])
    assert 'mxt_grouped_matmul_total{product="fwd",branch="ragged_dot"}' \
        in telemetry.render_prometheus()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_value_and_both_gradients_through_the_custom_vjp(dtype, grouped_matmul_kernels):
    """The three products joined: what ``jax.grad`` of a loss through
    ``grouped_matmul`` gives equals what it gives through ``ragged_dot``, and
    each product counted once under ``kernel``."""
    x, w, dy = _operands(jnp.dtype(dtype), seed=4)
    sizes = jnp.asarray(SIZES["not_aligned"], jnp.int32)

    def loss(op):
        return lambda x, w: jnp.sum(op(x, w, sizes).astype(F32) * dy.astype(F32))

    want = jax.value_and_grad(loss(jax.lax.ragged_dot), (0, 1))(x, w)
    grouped_matmul_kernels()
    before = {p: _count(p, "kernel") for p in PRODUCTS}
    got = jax.value_and_grad(loss(GM.grouped_matmul), (0, 1))(x, w)
    assert {p: _count(p, "kernel") - before[p] for p in PRODUCTS} == dict.fromkeys(PRODUCTS, 1)
    for g, r in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert g.dtype == r.dtype
        _close(g, r, TOL[dtype])
    text = str(jax.make_jaxpr(jax.grad(loss(GM.grouped_matmul), (0, 1)))(x, w))
    assert "grouped_matmul_dw" in text and "ragged_dot" not in text


def test_operands_of_two_types_stay_with_ragged_dot(grouped_matmul_kernels):
    grouped_matmul_kernels()
    x, w, _ = _operands(F32, seed=5)
    sizes = jnp.asarray(SIZES["tile_aligned"], jnp.int32)
    text = str(jax.make_jaxpr(lambda x, w: GM.grouped_matmul(x, w, sizes))(
        x.astype(jnp.bfloat16), w))
    assert "ragged_dot" in text and "pallas_call" not in text
