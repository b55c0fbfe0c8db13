"""Granite 4.0-H's hybrid decoder at a tiny size on the CPU: the op
``ssd_scan`` with its hand-written backward against the token-by-token
recurrence (a ``lax.scan`` over time, not a chunked form) and against the
benchmark's plain reference (``benchmark/references/granite_hybrid.py``: the
paper's Listing 1 under plain autodiff), ``causal_conv_silu`` against a direct
sum, ``GatedRMSNorm``, ``GroupedQueryAttention(sm_scale=...)``, the whole model
against the reference, the scopes and the counter, and the model's first steps
through ``ShardedTrainStep`` against the benchmark's follower, with the fp8
control and the two faults the reference plants in itself.

Tolerances: float32 throughout but for the ops' bfloat16 cases (float32 inside,
operands of the products bfloat16) and the model-level run in bfloat16, which
is held as the benchmark holds a cell.
"""
import copy
import functools
import hashlib
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, telemetry
from mxnet_tpu.gluon.model_zoo import granite_hybrid as zoo
from mxnet_tpu.gluon.model_zoo.keye import GroupedQueryAttention
from mxnet_tpu.ops import attention as A
from mxnet_tpu.ops import gated_conv as G
from mxnet_tpu.ops import ssd as S
from mxnet_tpu.ops import ssd_pallas as K

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from harness import compare, loader, train_reference  # noqa: E402

ref = loader.load_module("references", "granite_hybrid")
F32 = jnp.float32
CELL = "granite4_h_micro_train_s8192"
SSD_INPUTS = ("x", "dt", "A_log", "B", "C", "D", "dt_bias")


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(np.max(np.abs(want)), 1e-30)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= tol * scale, \
        (np.max(np.abs(got - want)) / scale, tol)


# -- the scan ----------------------------------------------------------------------
def _ssd_inputs(t, groups, dtype="float32", b=2, h=4, p=16, n=16, seed=3):
    k = jax.random.split(jax.random.PRNGKey(seed + t), 8)
    args = (jax.random.normal(k[0], (b, t, h, p), F32),
            jax.random.normal(k[1], (b, t, h), F32),
            jnp.log(jax.random.uniform(k[2], (h,), F32, 1.0, 16.0)),
            jax.random.normal(k[3], (b, t, groups, n), F32),
            jax.random.normal(k[4], (b, t, groups, n), F32),
            jax.random.normal(k[5], (h,), F32),
            jax.random.normal(k[6], (h,), F32) - 2.0)
    return (tuple(a.astype(dtype) for a in args),
            jax.random.normal(k[7], (b, t, h, p), F32).astype(dtype))


def _recurrence(x, dt, A_log, B, C, D, dt_bias):
    """The equations a token at a time: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t
    B_t^T``, ``y_t = S_t C_t + D x_t``, one (P, N) state a head."""
    x, dt, A_log, B, C, D, dt_bias = (a.astype(F32) for a in
                                      (x, dt, A_log, B, C, D, dt_bias))
    b, _, h, p = x.shape
    n, rep = B.shape[3], h // B.shape[2]
    dt, decay = jax.nn.softplus(dt + dt_bias), -jnp.exp(A_log)

    def one(state, now):
        xt, dtt, bt, ct = now
        state = jnp.exp(dtt * decay)[..., None, None] * state \
            + (dtt[..., None] * xt)[..., None] * bt[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct) + D[:, None] * xt

    by_time = (x, dt, jnp.repeat(B, rep, axis=2), jnp.repeat(C, rep, axis=2))
    _, y = jax.lax.scan(one, jnp.zeros((b, h, p, n), F32),
                        tuple(jnp.moveaxis(a, 1, 0) for a in by_time))
    return jnp.moveaxis(y, 0, 1)


def _all_gradients(fn, args, g):
    return jax.value_and_grad(lambda *a: jnp.sum(fn(*a).astype(F32) * g.astype(F32)),
                              argnums=tuple(range(len(args))))(*args)


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("t,what", [(32, "whole_chunks"), (29, "a_ragged_last_chunk"),
                                    (5, "shorter_than_a_chunk")])
def test_ssd_scan_forward_and_every_gradient_against_the_recurrence(t, what, groups):
    """Output and the gradient of all seven inputs, ``A_log``, ``D`` and
    ``dt_bias`` among them, against ``jax.grad`` of the recurrence written as a
    ``lax.scan`` over time: the chunks' masks, their closing states, the carry
    and its reverse walk, the padding."""
    args, g = _ssd_inputs(t, groups)
    want = _recurrence(*args)
    got = S.ssd_scan(*args, chunk=8)
    assert got.shape == want.shape and got.dtype == F32
    _close(got, want, 2e-5)
    _, want_grads = _all_gradients(_recurrence, args, g)
    _, got_grads = _all_gradients(lambda *a: S.ssd_scan(*a, chunk=8), args, g)
    for name, a, b in zip(SSD_INPUTS, got_grads, want_grads):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        _close(a, b, 1e-4)


@pytest.mark.parametrize("t", [32, 29])
def test_ssd_scan_against_the_references_chunked_form(t):
    """The reference's scan (Listing 1: segment sums, four einsums, plain
    autodiff, a head at a time) is the same function: both stand against the
    recurrence, and so against each other."""
    args, g = _ssd_inputs(t, 2)
    x, dt, A_log, B, C, D, dt_bias = args
    b, _, h, p = x.shape

    def listing(x, dt, A_log, B, C, D, dt_bias):
        dts = jax.nn.softplus(dt + dt_bias)
        rows = ((0, 0), (0, -t % 8))
        heads = lambda z: jnp.moveaxis(jnp.pad(z, rows + ((0, 0), (0, 0))), 2, 1)
        xdt, decay = heads(x * dts[..., None]), heads((dts * -jnp.exp(A_log))[..., None])
        B, C = (jnp.pad(z, rows + ((0, 0), (0, 0))) for z in (B, C))
        per = h // B.shape[2]
        y = jnp.concatenate([ref.ssd(xdt[:, g * per:(g + 1) * per],
                                     decay[:, g * per:(g + 1) * per, :, 0],
                                     B[:, :, g], C[:, :, g], 8)
                             for g in range(B.shape[2])], axis=1)
        return jnp.moveaxis(y, 1, 2)[:, :t] + D[:, None] * x

    with jax.default_matmul_precision("highest"):
        _close(S.ssd_scan(*args, chunk=8), listing(*args), 2e-5)
        _, want = _all_gradients(listing, args, g)
        _, got = _all_gradients(lambda *a: S.ssd_scan(*a, chunk=8), args, g)
    for a, b in zip(got, want):
        _close(a, b, 1e-4)


def test_ssd_scan_in_bfloat16_keeps_its_decays_and_states_in_float32():
    """bfloat16 operands, a bfloat16 result and gradients in the inputs' types,
    within the rounding of the four products' operands; the chunks' opening
    states the forward keeps are float32."""
    args, g = _ssd_inputs(32, 1, "bfloat16")
    got = S.ssd_scan(*args, chunk=8)
    assert got.dtype == jnp.bfloat16
    _close(got.astype(F32), _recurrence(*args), 2e-2)
    _, want = _all_gradients(_recurrence, tuple(a.astype(F32) for a in args), g)
    _, grads = _all_gradients(lambda *a: S.ssd_scan(*a, chunk=8), args, g)
    for name, a, b, arg in zip(SSD_INPUTS, grads, want, args):
        assert a.dtype == arg.dtype, name
        _close(a.astype(F32), b, 4e-2)
    _, res = S._ssd_fwd(8, *args)
    assert res[-1].dtype == F32 and res[-1].shape == (2, 4, 1, 4, 16, 16)


def test_the_backward_keeps_the_inputs_and_the_chunks_opening_states():
    """What the forward hands the backward: its seven operands as they came and
    one (P, N) state a head a chunk, the first of them zero; no mask."""
    args, _ = _ssd_inputs(29, 2)
    _, res = S._ssd_fwd(8, *args)
    assert len(res) == 8 and all(r is a for r, a in zip(res, args))
    assert res[7].shape == (2, 4, 2, 2, 16, 16) and not np.asarray(res[7][:, 0]).any()
    assert np.asarray(res[7][:, 1]).any()


@pytest.mark.parametrize("at", [0, 9, 28])
def test_ssd_causality_a_token_moves_no_earlier_output_and_no_other_sequence(at):
    args, _ = _ssd_inputs(29, 1)
    moved = (args[0].at[1, at].add(1.0),) + args[1:]
    a, b = S.ssd_scan(*args, chunk=8), S.ssd_scan(*moved, chunk=8)
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))            # the other sequence
    assert np.array_equal(np.asarray(a[1, :at]), np.asarray(b[1, :at]))  # the past
    later = np.abs(np.asarray(a[1, at:]) - np.asarray(b[1, at:])).sum((-2, -1))
    assert later[0] > 0 and (at > 12 or later[8:].any())  # and past its chunk's end


@pytest.mark.parametrize("mode", ["imperative", "hybridized", "symbolic"])
def test_the_operator_ssd_scan_through_nd_autograd_and_symbol(mode):
    from mxnet_tpu import autograd as ag

    args, g = _ssd_inputs(29, 1)
    want = _recurrence(*args)
    if mode == "symbolic":
        sym = mx.sym.ssd_scan(*[mx.sym.Variable(n) for n in SSD_INPUTS], chunk=8)
        exe = sym.bind(mx.cpu(), {n: nd.NDArray(a) for n, a in zip(SSD_INPUTS, args)})
        _close(exe.forward()[0].asnumpy(), want, 2e-5)
        return

    class Scan(mx.gluon.HybridBlock):
        def hybrid_forward(self, F, *a):
            return F.ssd_scan(*a, chunk=8)

    net = Scan()
    if mode == "hybridized":
        net.hybridize()
    arrays = [nd.NDArray(a) for a in args]
    for a in arrays:
        a.attach_grad()
    with ag.record():
        y = net(*arrays)
    y.backward(nd.NDArray(g))
    _close(y.asnumpy(), want, 2e-5)
    _, grads = _all_gradients(_recurrence, args, g)
    for a, b in zip(arrays, grads):
        _close(a.grad.asnumpy(), b, 1e-4)


def test_ssd_scan_refuses_shapes_that_are_not_its_own():
    (x, dt, A_log, B, C, D, dt_bias), _ = _ssd_inputs(16, 2)
    for bad in ((x, dt[:, :-1], A_log, B, C, D, dt_bias),
                (x, dt, A_log, B, C[..., :-1], D, dt_bias),
                (x, dt, A_log[:-1], B, C, D, dt_bias),
                (x[:, :, :3], dt[:, :, :3], A_log[:3], B, C, D[:3], dt_bias[:3]),
                (x[0], dt, A_log, B, C, D, dt_bias)):
        with pytest.raises(mx.base.MXNetError):
            S.ssd_scan(*bad, chunk=8)
    with pytest.raises(mx.base.MXNetError):
        S.ssd_scan(x, dt, A_log, B, C, D, dt_bias, chunk=0)


def test_ssd_counts_one_traced_call_by_branch():
    args, _ = _ssd_inputs(16, 1)
    before = telemetry.ssd_branches().get("xla", 0)
    f = jax.jit(lambda *a: S.ssd_scan(*a, chunk=8))
    f(*args), f(*args), f(*args)
    assert telemetry.ssd_branches()["xla"] == before + 1
    assert 'mxt_ssd_total{branch="xla"}' in telemetry.render_prometheus()


# -- the scan's kernels (ops/ssd_pallas.py), interpreted, dispatched as on the chip ----
@pytest.fixture
def ssd_kernels(monkeypatch):
    """``engage()``: from then on ``ops/ssd_pallas.py`` answers as on a TPU, its
    kernels in interpret mode (a test computes what the ``jax.numpy`` formula
    gives first, then engages)."""
    def engage():
        fwd, bwd = K._fwd_pallas, K._bwd_pallas
        monkeypatch.setattr(K, "on_tpu", lambda: True)
        monkeypatch.setattr(K, "_fwd_pallas", lambda *a, interpret=False, **kw:
                            fwd(*a, interpret=True, **kw))
        monkeypatch.setattr(K, "_bwd_pallas", lambda *a, interpret=False, **kw:
                            bwd(*a, interpret=True, **kw))

    return engage


def _kernel_inputs(t, groups, dtype="float32"):
    """Shapes ``kernel_takes`` accepts: 8 heads of 16 a group, state 128."""
    return _ssd_inputs(t, groups, dtype, h=8 * groups, n=128)


def _scan128(*a):
    return S.ssd_scan(*a, chunk=128)


@pytest.mark.parametrize("groups,t,chunk,what", [
    (1, 256, 128, "whole_chunks_of_one_tile"),
    (2, 300, 256, "a_ragged_last_chunk_two_groups_and_a_tile_under_the_diagonal")])
def test_ssd_kernels_forward_and_every_gradient_against_recurrence_and_formula(
        ssd_kernels, groups, t, chunk, what):
    """Both kernels, float32: the result and the gradient of all seven inputs
    against ``jax.grad`` of the token-by-token recurrence and against the
    ``jax.numpy`` formula of ``ops/ssd.py`` on the same inputs: the scores
    shared by a group's heads, the masks by tile, the carry in scratch both
    ways, the heads' sum of ``dscores``, the rows XLA finishes, the padding."""
    args, g = _kernel_inputs(t, groups)
    scan = functools.partial(S.ssd_scan, chunk=chunk)
    want = _recurrence(*args)
    _, want_grads = _all_gradients(_recurrence, args, g)
    formula = scan(*args)
    _, formula_grads = _all_gradients(scan, args, g)
    before = dict(telemetry.ssd_branches())
    ssd_kernels()
    got = scan(*args)
    assert got.shape == want.shape and got.dtype == F32
    _close(got, want, 2e-5)
    _close(got, formula, 2e-6)
    _, got_grads = _all_gradients(scan, args, g)
    for name, a, b, c in zip(SSD_INPUTS, got_grads, want_grads, formula_grads):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        # A_log's gradient is what is left of rows less columns: a thousandth
        _close(a, b, 2e-3 if name == "A_log" else 2e-4)
        _close(a, c, 2e-3 if name == "A_log" else 2e-4)
    after = telemetry.ssd_branches()
    assert after["kernel"] > before.get("kernel", 0) and after.get("xla") == before.get("xla")


def test_ssd_kernels_in_bfloat16_keep_decays_and_states_in_float32(ssd_kernels):
    """bfloat16 operands at the kernels' branch: a bfloat16 result and gradients
    in the inputs' types within the rounding of the products' operands,
    ``A_log``'s (the cancelling sums on the same rounded operands) among them;
    the formula's on the same inputs to a rounding of the result; the opening
    states the forward keeps float32, the first of them zero."""
    args, g = _kernel_inputs(256, 1, "bfloat16")
    _, want = _all_gradients(_recurrence, tuple(a.astype(F32) for a in args), g)
    formula = _scan128(*args)
    _, formula_grads = _all_gradients(_scan128, args, g)
    ssd_kernels()
    got = _scan128(*args)
    assert got.dtype == jnp.bfloat16
    _close(got.astype(F32), _recurrence(*args), 2e-2)
    _close(got.astype(F32), formula.astype(F32), 1e-2)
    _, grads = _all_gradients(_scan128, args, g)
    for name, a, b, c, arg in zip(SSD_INPUTS, grads, want, formula_grads, args):
        assert a.dtype == arg.dtype, name
        _close(a.astype(F32), b, 4e-2)
        _close(a.astype(F32), c.astype(F32), 2e-2)
    _, res = S._ssd_fwd(128, *args)
    assert res[-1].dtype == F32 and res[-1].shape == (2, 2, 1, 8, 16, 128)
    assert not np.asarray(res[-1][:, 0]).any() and np.asarray(res[-1][:, 1]).any()


@pytest.mark.parametrize("at", [0, 120, 299])
def test_ssd_kernels_causality_a_token_moves_no_earlier_output(ssd_kernels, at):
    args, _ = _kernel_inputs(300, 1)
    ssd_kernels()
    moved = (args[0].at[1, at].add(1.0),) + args[1:]
    a, b = _scan128(*args), _scan128(*moved)
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))            # the other sequence
    assert np.array_equal(np.asarray(a[1, :at]), np.asarray(b[1, :at]))  # the past
    later = np.abs(np.asarray(a[1, at:]) - np.asarray(b[1, at:])).sum((-2, -1))
    assert later[0] > 0 and (at != 120 or later[8:].any())  # and past its chunk's end


def test_which_scans_the_kernels_take_is_a_function_of_what_the_call_sees(monkeypatch):
    """``kernel_takes`` at the edges of its rule: a TPU, bfloat16 or float32, a
    chunk of whole lane tiles, P and N whole tiles, groups of whole blocks of
    heads, the tiles within the chip's VMEM (shrunk through ``ops/chip.py``);
    a call it refuses runs the formula and is counted ``xla``."""
    cell = ((1, 8192, 64, 64), (1, 8192, 1, 128), 256, "bfloat16")
    assert not K.kernel_takes(*cell)  # the CPU
    monkeypatch.setattr(K, "on_tpu", lambda: True)
    assert K.kernel_takes(*cell)
    assert K.kernel_takes((1, 8192, 64, 64), (1, 8192, 1, 128), 256, "float32")
    assert K.kernel_takes((2, 300, 16, 16), (2, 300, 2, 128), 128, "bfloat16")  # padded inside
    for x, b, chunk, dtype in (
            (cell[0], cell[1], 256, "float16"),                 # neither of the two types
            (cell[0], cell[1], 8, "bfloat16"),                  # the tests' chunk
            (cell[0], cell[1], 192, "bfloat16"),                # no whole lane tiles
            ((1, 8192, 64, 64), (1, 8192, 1, 64), 256, "bfloat16"),   # N half a tile
            ((1, 8192, 64, 8), (1, 8192, 1, 128), 256, "bfloat16"),   # P half a bfloat16 tile
            ((1, 8192, 12, 64), (1, 8192, 1, 128), 256, "bfloat16"),  # 12 heads: no blocks of 8
            ((1, 8192, 16, 64), (1, 8192, 4, 128), 256, "bfloat16"),  # 4 heads a group
            ((0, 8192, 64, 64), (0, 8192, 1, 128), 256, "bfloat16")):
        assert not K.kernel_takes(x, b, chunk, dtype), (x, b, chunk, dtype)
    assert K.kernel_takes((1, 8192, 64, 8), (1, 8192, 1, 128), 256, "float32")  # 8 rows: a tile
    need = K._vmem(64, 128, 256, 2, states=8)
    monkeypatch.setattr(K._chip, "VMEM_CEILING", need)
    assert K.kernel_takes(*cell)
    monkeypatch.setattr(K._chip, "VMEM_CEILING", need - 1)
    assert not K.kernel_takes(*cell)
    args, _ = _ssd_inputs(16, 1)  # on the shrunk chip the op still answers, through XLA
    before = dict(telemetry.ssd_branches())
    _close(S.ssd_scan(*args, chunk=8), _recurrence(*args), 2e-5)
    after = telemetry.ssd_branches()
    assert after["xla"] == before.get("xla", 0) + 1 and after.get("kernel") == before.get("kernel")


def test_ssd_counts_the_kernels_branch_once_a_trace(ssd_kernels):
    args, _ = _kernel_inputs(256, 1)
    ssd_kernels()
    before = dict(telemetry.ssd_branches())
    f = jax.jit(_scan128)
    f(*args), f(*args)
    after = telemetry.ssd_branches()
    assert after["kernel"] == before.get("kernel", 0) + 1 and after.get("xla") == before.get("xla")
    assert 'mxt_ssd_total{branch="kernel"}' in telemetry.render_prometheus()


# -- the filter --------------------------------------------------------------------
def _conv_inputs(dtype, b=2, t=12, c=8, k=4, seed=11):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (b, t, c), F32).astype(dtype)
    w = (0.5 * jax.random.normal(jax.random.fold_in(key, 1), (c, k), F32)).astype(dtype)
    bias = (0.5 * jax.random.normal(jax.random.fold_in(key, 2), (c,), F32)).astype(dtype)
    g = jax.random.normal(jax.random.fold_in(key, 3), (b, t, c), F32).astype(dtype)
    return x, w, bias, g


def _direct(x, w, bias):
    """The formula as a direct sum, with nothing of the op's: ``silu(sum_j w[:,
    j] x[t - (K - 1) + j] + bias)``, terms before the sequence left out."""
    x, w, bias = (a.astype(F32) for a in (x, w, bias))
    t, k = x.shape[1], w.shape[1]
    rows = []
    for i in range(t):
        v = bias
        for j in range(k):
            if i - (k - 1) + j >= 0:
                v = v + w[:, j] * x[:, i - (k - 1) + j]
        rows.append(v)
    return jax.nn.silu(jnp.stack(rows, axis=1))


class _Conv(mx.gluon.HybridBlock):
    def hybrid_forward(self, F, x, w, bias):
        return F.causal_conv_silu(x, w, bias)


@pytest.mark.parametrize("mode", ["imperative", "hybridized"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_causal_conv_silu_forward_and_all_three_gradients(dtype, mode):
    from mxnet_tpu import autograd as ag

    x, w, bias, g = _conv_inputs(dtype)
    tol = 1e-5 if dtype == "float32" else 1.5e-2
    want = _direct(x, w, bias)
    wants = jax.grad(lambda *a: jnp.sum(_direct(*a) * g.astype(F32)), argnums=(0, 1, 2))(
        x.astype(F32), w.astype(F32), bias.astype(F32))
    net = _Conv()
    if mode == "hybridized":
        net.hybridize()
    arrays = [nd.NDArray(a) for a in (x, w, bias)]
    for a in arrays:
        a.attach_grad()
    with ag.record():
        y = net(*arrays)
    y.backward(nd.NDArray(g))
    assert str(y.dtype) == dtype and y.shape == want.shape
    _close(y.asnumpy().astype(np.float32), want, tol)
    for a, b in zip(arrays, wants):
        assert str(a.grad.dtype) == dtype
        _close(a.grad.asnumpy().astype(np.float32), b, tol)


def test_causal_conv_silu_is_the_references_filter_keeps_data_alone_and_checks_shapes():
    x, w, bias, _ = _conv_inputs("float32")
    _close(G.causal_conv_silu(x, w, bias), jax.nn.silu(ref.causal_filter(x, w, bias)), 1e-6)
    sym = mx.sym.causal_conv_silu(*[mx.sym.Variable(n) for n in "xwb"])
    exe = sym.bind(mx.cpu(), {"x": nd.NDArray(x), "w": nd.NDArray(w), "b": nd.NDArray(bias)})
    _close(exe.forward()[0].asnumpy(), _direct(x, w, bias), 1e-5)
    _, res = G._conv_silu_fwd(x, w, bias, (0, x.shape[-1]))  # the operands, nothing computed
    assert len(res) == 3 and res[0] is x and res[1] is w and res[2] is bias
    for bad in ((x[..., :-1], w, bias), (x, w, bias[:-1]), (x[:, :3], w, bias), (x[0], w, bias)):
        with pytest.raises(mx.base.MXNetError):
            G.causal_conv_silu(*bad)
    # columns of a wider array: the op of the slice, and a gradient of the wide shape
    wide = jnp.concatenate([x[..., :3] + 1.0, x, x[..., :2] - 1.0], axis=-1)
    c = x.shape[-1]
    got, pull = jax.vjp(lambda d: G.causal_conv_silu(d, w, bias, columns=(3, 3 + c)), wide)
    want, pull_x = jax.vjp(lambda d: G.causal_conv_silu(d, w, bias), x)
    assert np.array_equal(np.asarray(got), np.asarray(want))
    dwide, dx = pull(want)[0], pull_x(want)[0]
    assert np.array_equal(np.asarray(dwide[..., 3:3 + c]), np.asarray(dx))
    assert not np.asarray(dwide[..., :3]).any() and not np.asarray(dwide[..., 3 + c:]).any()
    for columns in ((0, c + 1), (3, 2 + c), (6, 6 + c), (-1, c - 1)):
        with pytest.raises(mx.base.MXNetError):
            G.causal_conv_silu(wide, w, bias, columns=columns)


@pytest.mark.parametrize("at", [0, 5, 11])
def test_filter_causality_a_token_moves_no_earlier_output_and_at_most_three_later(at):
    x, w, bias, _ = _conv_inputs("float32")
    a, b = G.causal_conv_silu(x, w, bias), G.causal_conv_silu(x.at[1, at].add(1.0), w, bias)
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))
    assert np.array_equal(np.asarray(a[1, :at]), np.asarray(b[1, :at]))
    later = np.abs(np.asarray(a[1, at:]) - np.asarray(b[1, at:])).sum(-1)
    assert later[0] > 0 and not later[4:].any()


def test_causal_conv_counts_one_traced_call_by_branch():
    x, w, bias, _ = _conv_inputs("float32")
    before = telemetry.causal_conv_branches().get("xla", 0)
    f = jax.jit(G.causal_conv_silu)
    f(x, w, bias), f(x, w, bias), f(x, w, bias)
    assert telemetry.causal_conv_branches()["xla"] == before + 1  # 12 rows: no whole tile
    assert 'mxt_causal_conv_total{branch="xla"}' in telemetry.render_prometheus()
    assert {"record_causal_conv", "causal_conv_branches"} <= set(telemetry.__all__)


def _digest(jaxpr):
    return hashlib.sha256(re.sub(r" at 0x[0-9a-f]+", "", str(jaxpr)).encode()
                          ).hexdigest()[:16]


@pytest.mark.parametrize("half,digest", [("fwd", "592a8b863de3af48"),
                                         ("bwd", "66ce67b47a49ad76")])
def test_gated_short_conv_traces_to_the_program_it_was_at_lfm2s_shapes(half, digest):
    """The second op shares ``_filtered`` / ``_delay`` / ``_advance`` with
    ``gated_short_conv``; LFM2's call, (1, 8192, 3 x 2048) bfloat16 on three
    taps, traces to what it traced to before there was a second op (sha256 of
    the jaxpr at PR 42's tree, 2a819e4), letter for letter."""
    bcx = jax.ShapeDtypeStruct((1, 8192, 6144), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((2048, 3), jnp.bfloat16)
    if half == "fwd":
        jaxpr = jax.make_jaxpr(G.gated_short_conv)(bcx, w)
    else:
        g = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(
            lambda a, b, c: jax.vjp(G.gated_short_conv, a, b)[1](c))(bcx, w, g)
    assert _digest(jaxpr) == digest


# -- the gated norm ----------------------------------------------------------------
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gated_rms_norm_gates_first_then_norms(dtype):
    """``RMSNorm(y * silu(z)) * w`` against the plain formula under autodiff,
    through the Gluon block; the other order is another function."""
    from mxnet_tpu import autograd as ag

    k = jax.random.split(jax.random.PRNGKey(2), 4)
    y, z, g = (jax.random.normal(k[i], (2, 7, 24), F32).astype(dtype) for i in range(3))
    w = (1.0 + 0.3 * jax.random.normal(k[3], (24,), F32)).astype(dtype)
    plain = lambda y, z, w: ref.rms_norm(y * jax.nn.silu(z), w, 1e-5)
    f32 = tuple(a.astype(F32) for a in (y, z, w))
    want = plain(*f32)
    wants = jax.grad(lambda *a: jnp.sum(plain(*a) * g.astype(F32)), argnums=(0, 1, 2))(*f32)
    net = mx.gluon.nn.GatedRMSNorm(epsilon=1e-5, in_channels=24)
    net.initialize()
    net.cast(dtype)
    net.gamma.set_data(w)
    ys, zs = nd.NDArray(y), nd.NDArray(z)
    ys.attach_grad()
    zs.attach_grad()
    with ag.record():
        out = net(ys, zs)
    out.backward(nd.NDArray(g))
    tol = 1e-5 if dtype == "float32" else 2e-2
    assert str(out.dtype) == dtype
    _close(out.asnumpy().astype(np.float32), want, tol)
    for got, b in zip((ys.grad, zs.grad, net.gamma.grad()), wants):
        _close(got.asnumpy().astype(np.float32), b, tol)
    other = ref.rms_norm(f32[0], f32[2], 1e-5) * jax.nn.silu(f32[1])
    assert np.max(np.abs(np.asarray(other - want))) > 0.1


# -- the attention block's scale ---------------------------------------------------------
def _gqa_cases():
    k = loader.load_json("configs", "rehearse_keye_vl2")
    l = loader.load_json("configs", "rehearse_lfm2_moe")
    s = loader.load_json("configs", "rehearse_smallthinker")
    small = dict(units=s["hidden_size"], num_heads=s["num_attention_heads"],
                 num_kv_heads=s["num_key_value_heads"], head_dim=s["head_dim"],
                 head_norm=False)
    return {
        "keye": dict(units=k["hidden_size"], num_heads=k["num_attention_heads"],
                     num_kv_heads=k["num_key_value_heads"], head_dim=k["head_dim"],
                     rope_theta=k["rope_theta"], rms_norm_eps=k["rms_norm_eps"]),
        "lfm2": dict(units=l["hidden_size"], num_heads=l["num_attention_heads"],
                     num_kv_heads=l["num_key_value_heads"],
                     head_dim=l["hidden_size"] // l["num_attention_heads"],
                     rope_theta=l["rope_parameters"]["rope_theta"],
                     rms_norm_eps=l["norm_eps"]),
        "smallthinker_window": dict(small, rope_theta=s["rope_theta"],
                                    window=s["sliding_window_size"]),
        "smallthinker_full": dict(small, rope_theta=None),
    }


@pytest.mark.parametrize("family", sorted(_gqa_cases()))
def test_the_attention_blocks_default_scale_is_the_block_as_it_was_bit_for_bit(family):
    """Keye's, LFM2's and SmallThinker's blocks name no ``sm_scale``: the
    default block equals the one that states ``head_dim ** -0.5``, the scale
    that was written into the call, output and every gradient to the last
    bit, and traces to the same program letter for letter; another scale is
    another program."""
    from mxnet_tpu import autograd as ag

    kw = _gqa_cases()[family]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, kw["units"]), F32)
    g = jax.random.normal(jax.random.PRNGKey(4), (2, 24, kw["units"]), F32)

    def run(**more):
        blk = GroupedQueryAttention(prefix="gqa_", **kw, **more)
        blk.initialize(mx.init.Normal(0.3))
        for n, p in sorted(blk.collect_params().items()):
            if not n.endswith("gamma"):
                p.set_data(0.3 * jax.random.normal(jax.random.PRNGKey(len(n)), p.shape, F32))
        xs = nd.NDArray(x)
        xs.attach_grad()
        with ag.record():
            out = blk(xs)
        out.backward(nd.NDArray(g))
        grads = [p.grad().asnumpy() for _, p in sorted(blk.collect_params().items())]
        return [out.asnumpy(), xs.grad.asnumpy()] + grads, str(jax.make_jaxpr(
            lambda a: blk(nd.NDArray(a)).data)(x))

    default, program = run()
    stated, stated_program = run(sm_scale=kw["head_dim"] ** -0.5)
    assert len(default) >= 5
    for a, b in zip(default, stated):
        assert np.array_equal(a, b)
    assert re.sub(r"0x[0-9a-f]+", "", program) == re.sub(r"0x[0-9a-f]+", "", stated_program)
    other, _ = run(sm_scale=1.0 / kw["head_dim"])
    assert not np.array_equal(default[0], other[0])


@pytest.fixture
def flash_kernels(monkeypatch):
    """``ops/attention.py`` dispatches as on a TPU, both kernels in interpret
    mode at 128-row blocks, each noting the scale it was handed."""
    fwd, bwd = A._flash_forward_pallas, A._flash_backward_pallas
    seen = {}

    def forward(q, k, v, bias, causal, sm_scale, *a, interpret=False, **kw):
        seen["fwd"] = sm_scale
        return fwd(q, k, v, bias, causal, sm_scale, *a, interpret=True, **kw)

    def backward(q, k, v, bias, out, lse, do, causal, sm_scale, *a, interpret=False, **kw):
        seen["bwd"] = sm_scale
        return bwd(q, k, v, bias, out, lse, do, causal, sm_scale, *a, interpret=True, **kw)

    monkeypatch.setattr(A, "on_tpu", lambda: True)
    monkeypatch.setattr(A, "_tuned_config", lambda *a, **kw: {
        "backend": "pallas", "block_q": 128, "block_k": 128})
    monkeypatch.setattr(A, "_flash_forward_pallas", forward)
    monkeypatch.setattr(A, "_flash_backward_pallas", backward)
    return seen


def test_the_multiplier_reaches_both_flash_kernels_folded_into_q(flash_kernels):
    """``attention_multiplier`` 1/64 on heads of 64 through the block as on the
    chip: both kernels are handed 0.015625, a power of two, which they fold
    into the Q block exactly (``math.frexp(s)[0] == 0.5``), and give the
    reference's attention at that scale, not at 1/8."""
    import math

    from mxnet_tpu import autograd as ag

    a = ref.arch(dict(loader.load_json("configs", "rehearse_granite_hybrid"),
                      hidden_size=128, num_attention_heads=2, num_key_value_heads=1,
                      attention_multiplier=0.015625))
    blk = GroupedQueryAttention(128, 2, 1, 64, rope_theta=None, head_norm=False,
                                sm_scale=0.015625, prefix="gqa_")
    blk.initialize()
    k = jax.random.split(jax.random.PRNGKey(8), 5)
    p = {"q.w": jax.random.normal(k[0], (128, 128), F32), "kv.w": jax.random.normal(
        k[1], (128, 128), F32), "o.w": 0.1 * jax.random.normal(k[2], (128, 128), F32)}
    for leaf, name in (("q.w", "q_proj_weight"), ("kv.w", "kv_proj_weight"),
                       ("o.w", "o_proj_weight")):
        blk.collect_params()["gqa_" + name].set_data(p[leaf])
    x = jax.random.normal(k[3], (1, 200, 128), F32)
    g = jax.random.normal(k[4], (1, 200, 128), F32)
    with jax.default_matmul_precision("highest"):
        want, dx = jax.value_and_grad(lambda xx: jnp.sum(ref.attention(p, xx, a) * g))(x)
        xs = nd.NDArray(x)
        xs.attach_grad()
        with ag.record():
            out = blk(xs)
        out.backward(nd.NDArray(g))
        _close(jnp.sum(out.data * g), want, 1e-5)
        _close(xs.grad.asnumpy(), dx, 5e-5)
        eighth = jnp.sum(ref.attention(p, x, dict(a, attention_multiplier=0.125)) * g)
    assert flash_kernels == {"fwd": 0.015625, "bwd": 0.015625}
    assert math.frexp(flash_kernels["fwd"])[0] == 0.5
    assert abs(float(eighth - want)) > 1e-2 * abs(float(want))


# -- the model ---------------------------------------------------------------------------
def _arch(**over):
    config = loader.load_json("configs", "rehearse_granite_hybrid")
    return dict(config, dtype="float32", **over)


def _tiny_model(config=None):
    config = config or _arch()
    params = ref.init(config, 5)
    # biases and D off their seeded 0 and 1, so that a sign or a transpose shows
    params = {k: v + (0.1 * jax.random.normal(jax.random.PRNGKey(n), v.shape, F32)
                      if k.endswith(("conv.bias", ".D")) else 0.0)
              for n, (k, v) in enumerate(params.items())}
    net = zoo.GraniteHybridModel(config)
    net.initialize()
    net.cast(config["dtype"])
    model = loader.load_module("models", "granite_hybrid")
    names = model.leaf_names(config, net.prefix)
    model.common.set_parameters(net.collect_params(), names, params)
    return config, params, net, names


def _batch(t=29):
    ids = jax.random.randint(jax.random.PRNGKey(7), (2, t + 1), 0, 300)
    return ids[:, :-1].astype(F32), ids[:, 1:].astype(F32)


def _program(net, x, y):
    from mxnet_tpu import autograd as ag

    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    with jax.default_matmul_precision("highest"), ag.record():
        scores = net(nd.NDArray(x))
        loss = loss_fn(scores, nd.NDArray(y)).mean()
    loss.backward()
    return scores, loss


@pytest.mark.parametrize("groups", [1, 2])
def test_model_forward_and_every_leafs_gradient_against_the_reference(groups):
    """float32 on both sides, 29 tokens (three chunks of 8 and one of 5): the
    zoo's decoder (two Mamba-2 layers around a NoPE attention layer, the three
    multipliers, the head tied to the embedding and its scores scaled) and the
    plain reference give the same scores, the same loss and the same gradient
    of every leaf."""
    config, params, net, names = _tiny_model(_arch(mamba_n_groups=groups))
    x, y = _batch()
    with jax.default_matmul_precision("highest"):
        want, grads = ref.value_and_grad(config, params, x, y)
        logits = ref.logits(config, params, x)
    scores, loss = _program(net, x, y)
    net_params = net.collect_params()
    assert not any(name.endswith("head_weight") for name in net_params)  # tied
    _close(scores.asnumpy(), logits, 1e-5)
    _close(loss.asnumpy(), want, 1e-5)
    assert set(names) == set(grads) and len(names) == 2 * 13 + 8 + 2
    for leaf, name in names.items():
        _close(net_params[name].grad().asnumpy(), grads[leaf], 5e-4)


@pytest.mark.parametrize("fault", ["carry_dropped", "gate_after_norm",
                                   "scale_of_sqrt_head_dim", "no_residual_multiplier",
                                   "no_embedding_multiplier", "no_logits_scaling"])
def test_a_reference_with_the_layer_written_otherwise_is_not_the_program(fault):
    """The controls: every chunk opening on a zero state, the gate applied
    after the norm, scores at ``head_dim ** -0.5``, a multiplier left out. Each
    moves the gradient past the tolerance the sound comparison meets, so the
    test above tells them apart."""
    config, params, net, names = _tiny_model()
    x, y = _batch()
    other = copy.deepcopy(config)
    if fault in ref.PLANTED:
        other["reference"]["planted"] = fault
    elif fault == "scale_of_sqrt_head_dim":
        other["attention_multiplier"] = 16 ** -0.5
    else:
        key = {"no_residual_multiplier": "residual_multiplier",
               "no_embedding_multiplier": "embedding_multiplier",
               "no_logits_scaling": "logits_scaling"}[fault]
        other[key] = 1.0
    with jax.default_matmul_precision("highest"):
        _, grads = ref.value_and_grad(other, params, x, y)
        _, planted = ref.value_and_grad(config, params, x, y, quant=fault) \
            if fault in ref.PLANTED else (None, grads)
    _program(net, x, y)
    net_params = net.collect_params()
    worst = max(
        np.max(np.abs(net_params[name].grad().asnumpy() - np.asarray(grads[leaf])))
        / max(np.max(np.abs(np.asarray(grads[leaf]))), 1e-30)
        for leaf, name in names.items())
    assert worst > 20 * 5e-4, worst
    for leaf in grads:  # the calibration tools plant it as ``quant``: the same fault
        assert np.array_equal(np.asarray(grads[leaf]), np.asarray(planted[leaf]))


def test_the_config_is_checked_and_layers_take_their_kind_from_layer_types():
    for key, bad in (("num_local_experts", 8), ("mamba_n_groups", 3),
                     ("position_embedding_type", "rope"), ("tie_word_embeddings", False),
                     ("attention_bias", True), ("mamba_proj_bias", True),
                     ("mamba_conv_bias", False), ("mamba_expand", 2),
                     ("layer_types", ["mamba", "window", "mamba"]),
                     ("layer_types", ["mamba", "mamba"])):
        with pytest.raises(mx.base.MXNetError):
            zoo.GraniteHybridModel(dict(_arch(), **{key: bad}))
    net = zoo.GraniteHybridModel(_arch(layer_types=["attention", "mamba", "mamba"]))
    assert [type(b.mixer).__name__ for b in net.blocks] == [
        "GroupedQueryAttention", "Mamba2Mixer", "Mamba2Mixer"]
    assert all(type(b.mlp).__name__ == "SwiGLU" for b in net.blocks)
    attn = net.blocks[0].mixer
    assert attn._scale == 0.0625 and attn._theta is None and attn.qk_norm is None
    net.initialize()
    net.cast("bfloat16")
    mixer = net.blocks[1].mixer
    assert {str(p.dtype) for p in (mixer.A_log, mixer.D, mixer.dt_bias, mixer.conv_bias)} \
        == {"bfloat16"}
    assert mixer.in_proj.weight.shape == (64 + 64 + 2 * 16 + 4, 64)
    assert mixer.conv_weight.shape == (64 + 2 * 16, 4)


def test_the_published_sizes_hold_797_850_560_parameters():
    """The cell's configuration, from its leaves' shapes alone (nothing is
    allocated): ISSUE 43's count, layer by layer."""
    config = loader.load_json("configs", "granite4_h_micro_pp4")
    sizes = {k: int(np.prod(s)) for k, (s, _) in ref.leaves(config).items()}

    def layer(l):
        return sum(v for k, v in sizes.items() if k.startswith("l%d." % l))

    assert [layer(l) for l in range(10)] == [76182976] * 5 + [60821504] + [76182976] * 4
    assert sizes["embed.w"] == 25088 * 2048 and sum(sizes.values()) == 797850560
    assert config["assumed"]["recomputation"]["remat"] == "dots_with_no_batch_dims_saveable"
    for key in config["reduced"]:
        assert config[key] != config["published"][key]


def test_the_step_carries_the_scopes_of_both_mixers():
    """Device time is attributed by the names in the compiled step: a Mamba
    layer's projections, filter, scan and gated norm under ``mamba``, both
    halves of each op under a scope of its own, attention's under ``gqa``, with
    and without the recomputation the cell runs under."""
    from mxnet_tpu import parallel, profiler_trace

    _, _, net, _ = _tiny_model()
    x = jnp.zeros((1, 24), F32)
    mesh = parallel.make_mesh((1,), ("data",), devices=jax.devices()[:1])
    for remat in (None, "dots_with_no_batch_dims_saveable"):
        step = parallel.ShardedTrainStep(net, mx.gluon.loss.SoftmaxCrossEntropyLoss(),
                                         "adam", {"learning_rate": 1e-3}, mesh=mesh,
                                         remat=remat)
        step(nd.NDArray(x), nd.NDArray(x))
        common = loader.load_module("models", "gluon_common")
        prog = common.TrainProgram(step, {}, None, None, None, 1, {})
        prog.record_next_step()
        step(nd.NDArray(x), nd.NDArray(x))
        jitted, (args, kwargs) = prog._recorded
        text = jitted.lower(*args, **kwargs).as_text(debug_info=True)
        names, phases = set(), {}
        for name in re.findall(r'loc\("([^"]+)"', text):
            scopes = profiler_trace.scopes_of(name)
            names.update("/".join(scopes[i:j]) for i in range(len(scopes))
                         for j in range(i + 1, len(scopes) + 1))
            for s in scopes:
                phases.setdefault(s, set()).add(profiler_trace.phase_of("fusion", name))
        for want in ("mamba/in_proj", "mamba/causal_conv", "mamba/causal_conv_bwd",
                     "mamba/ssd", "mamba/ssd_bwd", "mamba/norm/gated_rmsnorm",
                     "mamba/norm/gated_rmsnorm_bwd", "mamba/out_proj", "gqa/q_proj",
                     "gqa/attention", "gqa/attention_bwd", "mlp/gate"):
            assert want in names, (remat, want)
        for scope in ("ssd_bwd", "causal_conv_bwd", "gated_rmsnorm_bwd"):
            assert phases[scope] == {"backward"}, (remat, scope)
        assert "forward" in phases["ssd"] and "forward" in phases["causal_conv"]
        if remat:  # the forward run again counts as backward, under the same scopes
            assert "backward" in phases["ssd"] and "backward" in phases["causal_conv"]


# -- the whole model through ShardedTrainStep, against the follower -------------------
def _first_steps(faults=(), sequence=None):
    c = loader.resolve_cell(CELL, rehearse=True)
    config = loader.load_json("configs", c["config"])
    traffic = loader.load_json("traffic", c["traffic"])
    if sequence:
        traffic = dict(traffic, sequence=sequence)
    model = loader.load_module("models", config["family"])
    runner = loader.load_module("runners", c["runner"])
    opt = train_reference.effective_optimizer(config, traffic)
    params, pool = ref.init(config, 5), ref.batches(config, traffic, 5)
    counted = dict(telemetry.ssd_branches())
    prog = model.build(config, traffic, params, jax.devices()[:1], opt)
    first, later = runner.first_steps(prog, [prog.batch(x, y) for x, y in pool], params,
                                      traffic)
    plain = train_reference.first_steps(ref, config, opt, params, pool,
                                        program_gradient=first["first_gradient"],
                                        keep_gradient=True)
    want = plain.pop("first_gradient")
    out = {"cell": c, "config": config, "program": compare.training_numbers(first, plain),
           "later": later, "published": prog.after_window(), "entry": prog.entry,
           "describe": prog.describe, "counted": counted}
    for fault in faults:
        low = train_reference.first_steps(ref, config, opt, params, pool, quant=fault,
                                          keep_gradient=True)
        rel, norms = train_reference.gradient_distance(low.pop("first_gradient"), want)
        out[fault] = compare.training_numbers(
            low, dict(plain, grad_rel_diff=rel, grad_diff_norms=norms))
    return out


@pytest.fixture(scope="module")
def first_steps():
    return _first_steps(faults=("fp8",) + ref.PLANTED)


def test_model_trains_through_sharded_step_like_the_follower(first_steps):
    rows = compare.judge(first_steps["program"], first_steps["cell"]["limits"])
    assert all(r["ok"] for r in rows), [r for r in rows if not r["ok"]]
    assert first_steps["later"] == 0  # nothing compiled after the first call
    assert getattr(first_steps["entry"], "fused", True)
    assert first_steps["describe"]["net"] == "GraniteHybridModel"
    assert first_steps["describe"]["remat"] is None  # the rehearsal's file names none


def test_a_sequence_that_is_no_whole_number_of_chunks_follows_the_follower_too():
    """29 tokens under chunks of 8, in bfloat16 through the compiled step: the
    padding inside the op changes nothing."""
    got = _first_steps(sequence=29)
    rows = compare.judge(got["program"], got["cell"]["limits"])
    assert all(r["ok"] for r in rows), [r for r in rows if not r["ok"]]
    assert got["published"]["ssd_chunks"] == 4 and got["later"] == 0


def test_the_adapter_publishes_the_chunks_and_the_branch_counter(first_steps):
    published = first_steps["published"]
    assert published["ssd_chunks"] == 4  # 32 tokens under chunks of 8
    # two Mamba layers traced once in the step: one count each, the XLA branch
    counted = first_steps["counted"]  # the process's counts before the model was built
    assert published["ssd_branches"]["xla"] >= counted.get("xla", 0) + 2
    assert published["ssd_branches"].get("kernel") == counted.get("kernel")  # none on the CPU
    for name in ("ssm_share.train", "ssd_scan_roofline.train", "causal_conv_roofline.train"):
        reader = loader.load_module("layer_metrics", name)
        assert reader.NAME == name and reader.read({"trace_dir": None}) is None


@pytest.mark.parametrize("fault", ("fp8",) + ref.PLANTED)
def test_a_control_fails_a_limit_the_program_meets(first_steps, fault):
    """The reference in fp8, or with a fault planted in its scan or its gated
    norm, put in the program's place: its first gradient lies several times
    farther from the sound reference's than the program's does, and a limit
    between the two readings passes the one and refuses the other."""
    def value(numbers, name):
        return next(v for n, v, _ in numbers if n == name)

    sound = value(first_steps["program"], "grad_rel_diff")
    control = value(first_steps[fault], "grad_rel_diff")
    assert control > 3 * sound, (sound, control)
    limits = dict(first_steps["cell"]["limits"], grad_rel_diff=(sound * control) ** 0.5)
    assert all(r["ok"] for r in compare.judge(first_steps["program"], limits))
    assert not all(r["ok"] for r in compare.judge(first_steps[fault], limits))
