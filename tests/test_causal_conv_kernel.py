"""``causal_conv_silu``'s two Pallas kernels (``ops/causal_conv_pallas.py``) on
the CPU in interpret mode, dispatched as on the chip: forward and all three
gradients against the ``jax.numpy`` branch of ``ops/gated_conv.py`` and against
the benchmark's plain reference (``benchmark/references/granite_hybrid.py:
causal_filter`` under plain autodiff), over sequences of three token tiles so
that a halo crosses every tile boundary, zeros before token 0 and after token
T - 1; that a token moves its own sequence's next K - 1 tokens only; what
``kernel_takes`` refuses, and that such a call still answers through XLA.

The chip's compiler sees the same kernels at the cell's shape in
``tests/test_aot_tpu_compile.py``.
"""
import importlib.util
import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import causal_conv_pallas as P
from mxnet_tpu.ops import gated_conv as G

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from harness import loader  # noqa: E402

ref = loader.load_module("references", "granite_hybrid")
F32 = jnp.float32
ROWS = 2048  # a tile of tokens (the Granite cell's one tile is 8192)
T = 3 * ROWS


@pytest.fixture
def kernels(monkeypatch):
    """``engage()``: from then on ``ops/causal_conv_pallas.py`` answers as on a
    TPU, its kernels in interpret mode (a test computes what the ``jax.numpy``
    branch gives first, then engages)."""
    def engage():
        fwd, bwd = P._fwd_pallas, P._bwd_pallas
        monkeypatch.setattr(P, "on_tpu", lambda: True)
        monkeypatch.setattr(P, "_fwd_pallas", lambda *a, interpret=False, **kw:
                            fwd(*a, interpret=True, **kw))
        monkeypatch.setattr(P, "_bwd_pallas", lambda *a, interpret=False, **kw:
                            bwd(*a, interpret=True, **kw))

    return engage


def _inputs(dtype, b=2, t=T, c=128, k=4, seed=5):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (b, t, c), F32).astype(dtype)
    w = jax.random.uniform(jax.random.fold_in(key, 1), (c, k), F32, -0.5, 0.5).astype(dtype)
    bias = (0.5 * jax.random.normal(jax.random.fold_in(key, 2), (c,), F32)).astype(dtype)
    g = jax.random.normal(jax.random.fold_in(key, 3), (b, t, c), F32).astype(dtype)
    return x, w, bias, g


def _op_and_grads(x, w, bias, g):
    y, pull = jax.vjp(G.causal_conv_silu, x, w, bias)
    return (y,) + pull(g)


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    scale = max(np.max(np.abs(want)), 1e-30)
    assert np.max(np.abs(got - want)) <= tol * scale, (np.max(np.abs(got - want)) / scale, tol)


@pytest.mark.parametrize("rows", [ROWS, P.TOKENS[-1]])
@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("k", [3, 4])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_kernels_match_the_xla_branch_and_the_reference(kernels, dtype, k, batch, rows):
    x, w, bias, g = _inputs(dtype, b=batch, t=3 * rows, k=k)
    assert P._tiles(3 * rows, 128, 0)[0] == (128, rows)  # three tiles, two edges between
    counts = telemetry.causal_conv_branches
    xla_before = counts().get("xla", 0)
    xla = _op_and_grads(x, w, bias, g)
    assert counts()["xla"] == xla_before + 1
    plain = jax.vjp(lambda *a: jax.nn.silu(ref.causal_filter(*a)),
                    *(a.astype(F32) for a in (x, w, bias)))
    plain = (plain[0],) + plain[1](g.astype(F32))
    kernel_before = counts().get("kernel", 0)
    kernels()
    got = _op_and_grads(x, w, bias, g)
    assert counts()["kernel"] == kernel_before + 1 and counts()["xla"] == xla_before + 1
    # float32: the same sums in another order; bfloat16: each rounded once from float32
    tol = 2e-5 if dtype == "float32" else 1e-2
    for a, b, c in zip(got, xla, plain):
        assert str(a.dtype) == dtype and a.shape == b.shape
        _close(a.astype(F32), b.astype(F32), tol)
        _close(a.astype(F32), c, tol)


@pytest.mark.parametrize("k", [1, P._MAX_TAPS])
def test_the_rules_ends_one_tap_and_as_many_as_are_written_out(kernels, k):
    x, w, bias, g = _inputs("float32", t=3 * P.TOKENS[-1], k=k)
    want = _op_and_grads(x, w, bias, g)
    kernels()
    assert P.kernel_takes(x.shape, k, "float32")
    for a, b in zip(_op_and_grads(x, w, bias, g), want):
        _close(a, b, 2e-5)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("begin", [0, 48, 256])
def test_columns_of_a_wider_array_are_read_in_place_and_the_rest_gets_a_zero_gradient(
        kernels, begin, dtype):
    """``columns=(begin, end)`` of a fused projection's result (its width no
    whole lane-length, as ``in_proj``'s 8512): the op of the slice, and a
    gradient of the wide array's shape that is zero outside the columns."""
    rows, c = P.TOKENS[-1], 256
    wide, w, bias, g = _inputs(dtype, b=2, t=3 * rows, c=begin + c + 80)
    w, bias, g = w[begin:begin + c], bias[begin:begin + c], g[..., :c]
    cut = lambda x: x[..., begin:begin + c]  # noqa: E731
    want = jax.vjp(lambda x, *a: G.causal_conv_silu(cut(x), *a), wide, w, bias)
    want = (want[0],) + want[1](g)
    kernels()
    before = telemetry.causal_conv_branches()["kernel"]
    got = jax.vjp(lambda *a: G.causal_conv_silu(*a, columns=(begin, begin + c)), wide, w, bias)
    got = (got[0],) + got[1](g)
    assert telemetry.causal_conv_branches()["kernel"] == before + 1
    assert got[1].shape == wide.shape and not np.asarray(got[1][..., :begin].astype(F32)).any()
    assert not np.asarray(got[1][..., begin + c:].astype(F32)).any()
    for a, b in zip(got, want):
        assert a.dtype == b.dtype
        _close(a.astype(F32), b.astype(F32), 2e-5 if dtype == "float32" else 1e-2)


@pytest.mark.parametrize("at", [0, ROWS - 2, ROWS - 1, ROWS, 2 * ROWS - 3, T - 2])
def test_a_token_moves_its_own_sequences_next_rows_only_across_tile_edges(kernels, at):
    x, w, bias, _ = _inputs("float32")
    k = w.shape[1]
    kernels()
    a, b = G.causal_conv_silu(x, w, bias), G.causal_conv_silu(x.at[1, at].add(1.0), w, bias)
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))  # the other sequence
    moved = np.abs(np.asarray(a[1]) - np.asarray(b[1])).sum(-1)
    assert not moved[:at].any() and not moved[at + k:].any()
    assert moved[at:at + k].all()


def test_the_gradient_of_data_reads_the_next_rows_of_g_and_zeros_past_the_end(kernels):
    """``d data[t]`` gathers ``dV`` on rows t .. t + K - 1 of its own sequence:
    a unit of ``g`` at a tile's first row reaches back over the edge, one at
    the sequence's last row reaches the last K rows and no other sequence."""
    x, w, bias, _ = _inputs("float32")
    k = w.shape[1]
    kernels()
    for at in (ROWS, T - 1):
        g = jnp.zeros(x.shape, F32).at[0, at].set(1.0)
        dx = np.abs(np.asarray(_op_and_grads(x, w, bias, g)[1])).sum(-1)
        assert not dx[1].any()
        assert dx[0, at - k + 1:at + 1].all()
        assert not dx[0, :at - k + 1].any() and not dx[0, at + 1:].any()


def test_the_kernels_run_twice_under_the_cells_recomputation(kernels):
    """The Granite cell trains under ``remat="dots_with_no_batch_dims_saveable"``:
    where what follows the op needs its result again (the scan does), the
    forward half runs a second time inside the backward."""
    x, w, bias, g = _inputs("bfloat16", b=1)
    kernels()

    def loss(x, w, bias):
        return jnp.sum(jnp.tanh(G.causal_conv_silu(x, w, bias).astype(F32)) * g.astype(F32))

    plain = jax.value_and_grad(loss, argnums=(0, 1, 2))(x, w, bias)
    again = jax.jit(jax.value_and_grad(jax.checkpoint(
        loss, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable),
        argnums=(0, 1, 2)))
    text = again.lower(x, w, bias).as_text()
    assert text.count("call @_fwd_pallas") == 2 and text.count("call @_bwd_pallas") == 1
    for a, b in zip(jax.tree.leaves(again(x, w, bias)), jax.tree.leaves(plain)):
        assert np.array_equal(np.asarray(a.astype(F32)), np.asarray(b.astype(F32)))


@pytest.mark.parametrize("shape,k,dtype", [
    ((1, 512, 100), 4, "bfloat16"),  # channels that are no whole tiles of 16
    ((1, 576, 128), 4, "bfloat16"),  # a sequence that is no whole tile
    ((1, 512, 128), 20, "float32"),  # more taps than are written out
    ((1, 512, 128), 4, "float16"),   # a type the kernels do not hold
])
def test_kernel_takes_refuses_and_the_call_answers_through_xla(kernels, shape, k, dtype):
    x, w, bias, g = _inputs(dtype, b=shape[0], t=shape[1], c=shape[2], k=k)
    want = _op_and_grads(x, w, bias, g)
    kernels()
    assert P.kernel_takes((1, 512, 128), 4, "bfloat16")  # the rule is awake
    assert not P.kernel_takes(shape, k, dtype)
    before = dict(telemetry.causal_conv_branches())
    got = _op_and_grads(x, w, bias, g)
    after = telemetry.causal_conv_branches()
    assert after["xla"] == before["xla"] + 1 and after.get("kernel") == before.get("kernel")
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a.astype(F32)), np.asarray(b.astype(F32)))


def test_kernel_takes_asks_for_a_tpu_and_whole_tiles():
    assert not P.kernel_takes((1, 8192, 4352), 4, "bfloat16")  # no TPU here
    assert P._tiles(8192, 4352, 4096) == ((256, 8192), (16, 2048))
    assert P._tiles(1536, 640, 0) == ((128, 512), (16, 512))
    assert P._tiles(1536, 512, 48) == ((16, 512), (16, 512))  # the columns' start in tiles too
    assert not P._tiles(8192, 100, 0) and not P._tiles(576, 128, 0)
    assert not P._tiles(8192, 256, 8)
    assert all(t % min(t, P._WALK[1]) == 0 for t in P.TOKENS)


def test_trace_cell_prints_the_branch_each_traced_filter_took(
        kernels, monkeypatch, capsys, tmp_path):
    """``tools/trace_cell.py`` prints ``causal_conv_branches`` on its ``scoped``
    line beside ``gated_conv_branches``: in the Granite cell ``kernel`` for
    every traced call and ``xla`` for none."""
    x, w, bias, _ = _inputs("bfloat16", b=1, t=P.TOKENS[-1])
    kernels()
    G.causal_conv_silu(x, w, bias)
    spec = importlib.util.spec_from_file_location(
        "trace_cell_under_test", os.path.join(os.path.dirname(BENCH), "tools", "trace_cell.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    args = types.SimpleNamespace(seed=0, seconds=1.0, trace=1, rehearse=True)

    def fake_run(argv):  # the run itself: an empty trace, and its clean-up
        ctx = tool.Context(args, {}, {}, {}, [])
        ctx._trace_dirs.append(str(tmp_path))
        ctx.cleanup()
        return 0

    monkeypatch.setattr(tool.bench, "main", fake_run)
    capsys.readouterr()
    assert tool.main([]) == 0
    line = [l for l in capsys.readouterr().out.splitlines() if l.startswith('{"scoped"')][-1]
    assert json.loads(line)["scoped"]["causal_conv_branches"]["kernel"] >= 1
