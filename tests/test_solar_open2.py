"""Solar Open 2's hybrid sparse decoder at a tiny size on the CPU: the op
``gated_delta_rule`` with its own backward against the token-by-token
recurrence (a ``lax.scan`` over time, no chunk, plain autodiff), the strongest
decays of the initial draw over a full chunk among the cases; the head's
norm-then-gate; ``GroupedQueryAttention(output_gate=...)``; the whole model
against the benchmark's plain reference (``benchmark/references/solar_open2.py``),
five layers written otherwise, the shares of heads and of experts adding up to
the uncut layer, the config's checks, the scopes and the counter, and the
model's first steps through ``ShardedTrainStep`` against the benchmark's
follower, with the fp8 control and the faults the reference plants in itself.

Tolerances: float32 throughout but for the ops' bfloat16 cases (float32 inside,
operands of the products bfloat16) and the model-level run in bfloat16, which
is held as the benchmark holds a cell's rehearsal.
"""
import copy
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
from mxnet_tpu.gluon.model_zoo import solar_open2 as zoo
from mxnet_tpu.gluon.model_zoo.deepseek import DeepseekMoE
from mxnet_tpu.gluon.model_zoo.keye import GroupedQueryAttention
from mxnet_tpu.ops import chip as CHIP
from mxnet_tpu.ops import delta_rule as D
from mxnet_tpu.ops import delta_rule_pallas as K

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)
from harness import compare, loader, train_reference  # noqa: E402

ref = loader.load_module("references", "solar_open2")
F32 = jnp.float32
CELL = "solar_open2_train_s8192"
INPUTS = ("q", "k", "v", "g", "beta")
# what the chip's calibration reads (benchmark/tests/test_limits_solar_open2.py
# holds all five planted faults to the cell's limits in float32)
CONTROLS = ("fp8", "carry_dropped", "beta_not_doubled")


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(np.max(np.abs(want)), 1e-30)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= tol * scale, \
        (np.max(np.abs(got - want)) / scale, tol)


# -- the delta rule ----------------------------------------------------------------------
def _inputs(t, dtype="float32", b=2, h=2, k=16, v=16, strong=False, seed=3):
    """q, k, v before their unit length, log-decays a channel and beta in (0,
    2). ``strong``: the decays of the cell's initial draw at their strongest,
    ``A`` up to 16 times a step of up to 0.1, so that a channel's running sum
    passes -100 within 64 tokens beside channels that hardly decay."""
    r = jax.random.split(jax.random.PRNGKey(seed + t), 6)
    q, kk = (jax.random.normal(r[i], (b, t, h, k), F32) for i in (0, 1))
    vv = jax.random.normal(r[2], (b, t, h, v), F32)
    if strong:
        rate = jnp.exp(jnp.linspace(jnp.log(0.001), jnp.log(1.6), k))
        g = -rate * jax.random.uniform(r[3], (b, t, h, k), F32, 0.9, 1.0)
    else:
        g = -0.3 * jax.random.uniform(r[3], (b, t, h, k), F32)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(r[4], (b, t, h), F32))
    args = tuple(a.astype(dtype) for a in (q, kk, vv)) + (g, beta.astype(dtype))
    return args, jax.random.normal(r[5], (b, t, h, v), F32).astype(dtype)


def _recurrence(q, k, v, g, beta):
    """The equations a token at a time: ``S' = Diag(exp(g_t)) S``, ``S = S' +
    beta_t k_t (v_t - S'^T k_t)^T``, ``o_t = S^T q_t``, one (K, V) state a
    head; q and k at unit length first, q times ``K ** -0.5``."""
    q, k, v, g, beta = (a.astype(F32) for a in (q, k, v, g, beta))
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) * q.shape[-1] ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)

    def one(state, now):
        qt, kt, vt, gt, bt = now
        state = jnp.exp(gt)[..., None] * state
        seen = jnp.einsum("bhkv,bhk->bhv", state, kt)
        state = state + (bt[..., None] * kt)[..., None] * (vt - seen)[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, qt)

    first = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[3:], F32)
    _, o = jax.lax.scan(one, first, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1)


def _all_gradients(fn, args, dy):
    return jax.value_and_grad(lambda *a: jnp.sum(fn(*a).astype(F32) * dy.astype(F32)),
                              argnums=tuple(range(len(args))))(*args)


@pytest.mark.parametrize("t,what", [(32, "whole_chunks"), (37, "a_ragged_last_chunk"),
                                    (5, "shorter_than_a_chunk"),
                                    (40, "whole_chunks_of_several_sub_blocks")])
def test_gated_delta_rule_forward_and_every_gradient_against_the_recurrence(t, what):
    """Output and the gradient of all five inputs against ``jax.grad`` of the
    recurrence written as a ``lax.scan`` over time: the pairs under their
    decays, the solve, the carry and its reverse walk, the padding. The last
    case takes chunks of 20 in sub-blocks of 4, so the series over the blocks
    runs."""
    chunk = 20 if what.endswith("sub_blocks") else 8
    args, dy = _inputs(t)
    want = _recurrence(*args)
    got = D.gated_delta_rule(*args, chunk=chunk)
    assert got.shape == want.shape and got.dtype == F32
    _close(got, want, 2e-5)
    _, want_grads = _all_gradients(_recurrence, args, dy)
    _, got_grads = _all_gradients(lambda *a: D.gated_delta_rule(*a, chunk=chunk), args, dy)
    for name, a, b in zip(INPUTS, got_grads, want_grads):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        _close(a, b, 1e-4)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4), ("bfloat16", 4e-2)])
def test_the_strongest_decays_over_a_full_chunk_are_finite_and_the_recurrences(dtype, tol):
    """One full chunk of 64 tokens, the fastest channels' running
    sum at -100 (``exp(100)`` is past float32: a pair's decay split as ``exp(G_i)
    exp(-G_j)`` over the chunk is inf times zero): finite, and the recurrence's,
    output and every gradient."""
    args, dy = _inputs(64, dtype, b=1, h=1, k=32, v=32, strong=True)
    assert float(jnp.min(jnp.sum(args[3], axis=1))) < -95.0
    want = _recurrence(*args)
    got = D.gated_delta_rule(*args, chunk=64)
    assert str(got.dtype) == dtype and bool(jnp.all(jnp.isfinite(got.astype(F32))))
    _close(got.astype(F32), want, tol)
    _, wants = _all_gradients(_recurrence, tuple(a.astype(F32) for a in args), dy)
    _, grads = _all_gradients(lambda *a: D.gated_delta_rule(*a, chunk=64), args, dy)
    for name, a, b, arg in zip(INPUTS, grads, wants, args):
        assert a.dtype == arg.dtype and bool(jnp.all(jnp.isfinite(a.astype(F32)))), name
        _close(a.astype(F32), b, tol)


def test_gated_delta_rule_in_bfloat16_keeps_its_decays_and_states_in_float32():
    """bfloat16 operands over several chunks of 64, a bfloat16 result and
    gradients in the inputs' types within the rounding of the products'
    operands; the log-decays and their gradient stay float32, and so do the
    chunks' opening states the forward keeps."""
    args, dy = _inputs(150, "bfloat16", b=1, k=32, v=32)
    got = D.gated_delta_rule(*args, chunk=64)
    assert got.dtype == jnp.bfloat16
    _close(got.astype(F32), _recurrence(*args), 2e-2)
    _, wants = _all_gradients(_recurrence, tuple(a.astype(F32) for a in args), dy)
    _, grads = _all_gradients(lambda *a: D.gated_delta_rule(*a, chunk=64), args, dy)
    for name, a, b, arg in zip(INPUTS, grads, wants, args):
        assert a.dtype == arg.dtype, name
        _close(a.astype(F32), b, 4e-2)
    assert grads[3].dtype == F32
    _, res = D._delta_fwd(64, *args)
    assert res[-1].dtype == F32 and res[-1].shape == (1, 3, 2, 32, 32)


def test_the_backward_keeps_the_inputs_and_the_chunks_opening_states():
    """What the forward hands the backward: its five operands as they came and
    one (K, V) state a head a chunk, the first of them zero; no pair."""
    args, _ = _inputs(37)
    _, res = D._delta_fwd(8, *args)
    assert len(res) == 6 and all(r is a for r, a in zip(res, args))
    assert res[5].shape == (2, 5, 2, 16, 16) and not np.asarray(res[5][:, 0]).any()
    assert np.asarray(res[5][:, 1]).any()


def test_the_checkpointed_forward_under_plain_autodiff_is_the_same_function():
    """The other backward ISSUE 47 named, ``jax.checkpoint`` of the forward
    under plain autodiff (written here: the op does not keep it): the same
    gradients as the op's own."""
    args, dy = _inputs(37)
    _, own = _all_gradients(lambda *a: D.gated_delta_rule(*a, chunk=8), args, dy)
    checkpointed = jax.checkpoint(lambda *a: D._forward(8, *a)[0])
    _, other = _all_gradients(checkpointed, args, dy)
    for a, b in zip(own, other):
        _close(a, b, 1e-5)


@pytest.mark.parametrize("at", [0, 9, 36])
def test_causality_a_token_moves_no_earlier_output_and_no_other_sequence(at):
    args, _ = _inputs(37)
    moved = (args[0], args[1], args[2].at[1, at].add(1.0)) + args[3:]
    a, b = D.gated_delta_rule(*args, chunk=8), D.gated_delta_rule(*moved, chunk=8)
    assert np.array_equal(np.asarray(a[0]), np.asarray(b[0]))            # the other sequence
    assert np.array_equal(np.asarray(a[1, :at]), np.asarray(b[1, :at]))  # the past
    later = np.abs(np.asarray(a[1, at:]) - np.asarray(b[1, at:])).sum((-2, -1))
    assert later[0] > 0 and (at > 20 or later[8:].any())  # and past its chunk's end


@pytest.mark.parametrize("mode", ["imperative", "hybridized", "symbolic"])
def test_the_operator_through_nd_autograd_and_symbol(mode):
    from mxnet_tpu import autograd as ag

    args, dy = _inputs(37)
    want = _recurrence(*args)
    if mode == "symbolic":
        sym = mx.sym.gated_delta_rule(*[mx.sym.Variable(n) for n in INPUTS], chunk=8)
        exe = sym.bind(mx.cpu(), {n: nd.NDArray(a) for n, a in zip(INPUTS, args)})
        _close(exe.forward()[0].asnumpy(), want, 2e-5)
        return

    class Rule(mx.gluon.HybridBlock):
        def hybrid_forward(self, F, *a):
            return F.gated_delta_rule(*a, chunk=8)

    net = Rule()
    if mode == "hybridized":
        net.hybridize()
    arrays = [nd.NDArray(a) for a in args]
    for a in arrays:
        a.attach_grad()
    with ag.record():
        y = net(*arrays)
    y.backward(nd.NDArray(dy))
    _close(y.asnumpy(), want, 2e-5)
    _, grads = _all_gradients(_recurrence, args, dy)
    for a, b in zip(arrays, grads):
        _close(a.grad.asnumpy(), b, 1e-4)


def test_the_ops_refuse_shapes_that_are_not_their_own():
    (q, k, v, g, beta), _ = _inputs(16)
    for bad in ((q, k[:, :-1], v, g, beta), (q, k, v, g[..., :-1], beta),
                (q, k, v[:, :, :1], g, beta), (q, k, v, g, beta[..., :1]),
                (q[0], k[0], v[0], g[0], beta[0])):
        with pytest.raises(mx.base.MXNetError):
            D.gated_delta_rule(*bad, chunk=8)
    with pytest.raises(mx.base.MXNetError):
        D.gated_delta_rule(q, k, v, g, beta, chunk=0)
    f = jnp.zeros((2, 5, 12), F32)
    for bad in ((f, jnp.zeros((5,)), jnp.zeros((12,))), (f, jnp.zeros((3,)), jnp.zeros((6,))),
                (f[0], jnp.zeros((3,)), jnp.zeros((12,)))):
        with pytest.raises(mx.base.MXNetError):
            D.kda_log_decay(*bad)


def test_the_log_decays_are_float32_and_the_formulas():
    r = jax.random.split(jax.random.PRNGKey(1), 3)
    f = jax.random.normal(r[0], (2, 5, 12), F32).astype(jnp.bfloat16)
    a_log = jnp.log(jax.random.uniform(r[1], (3,), F32, 1.0, 16.0)).astype(jnp.bfloat16)
    bias = jax.random.normal(r[2], (12,), F32).astype(jnp.bfloat16)
    got = D.kda_log_decay(f, a_log, bias)
    want = -jnp.exp(a_log.astype(F32))[:, None] * jax.nn.softplus(
        f.astype(F32) + bias.astype(F32)).reshape(2, 5, 3, 4)
    assert got.dtype == F32 and got.shape == (2, 5, 3, 4) and float(jnp.max(got)) < 0
    _close(got, want, 1e-6)


def test_the_delta_rule_counts_one_traced_call_by_branch():
    args, _ = _inputs(16)
    before = telemetry.delta_rule_branches().get("xla", 0)
    f = jax.jit(lambda *a: D.gated_delta_rule(*a, chunk=8))
    f(*args), f(*args), f(*args)
    assert telemetry.delta_rule_branches()["xla"] == before + 1
    assert 'mxt_delta_rule_total{branch="xla"}' in telemetry.render_prometheus()
    assert {"record_delta_rule", "delta_rule_branches"} <= set(telemetry.__all__)


# -- the head's norm-then-gate ------------------------------------------------------------
# -- the rule's kernels (ops/delta_rule_pallas.py), interpreted, dispatched as on the chip
@pytest.fixture
def kda_kernels(monkeypatch):
    """``engage()``: from then on ``ops/delta_rule_pallas.py`` answers as on a TPU,
    its kernels in interpret mode (a test computes what the ``jax.numpy`` formula
    gives first, then engages)."""
    def engage():
        fwd, bwd = K._fwd_pallas, K._bwd_pallas
        monkeypatch.setattr(K, "on_tpu", lambda: True)
        monkeypatch.setattr(K, "_fwd_pallas", lambda *a, interpret=False, **kw:
                            fwd(*a, interpret=True, **kw))
        monkeypatch.setattr(K, "_bwd_pallas", lambda *a, interpret=False, **kw:
                            bwd(*a, interpret=True, **kw))

    return engage


def _rule64(*a):
    return D.gated_delta_rule(*a, chunk=64)


def _whole():
    """A jitted (result, what the forward keeps, the five gradients) of the op as
    it dispatches when first called: one trace and one compile a shape."""
    @jax.jit
    def whole(args, dy):
        o, res = D._delta_fwd(64, *args)
        return o, res[5], _all_gradients(_rule64, args, dy)[1]

    return whole


_FORMULA, _KERNELS = _whole(), _whole()  # a cache each: the dispatch is no part of a key


def _kernel_inputs(t, dtype="float32", h=8, case="plain"):
    """Shapes ``kernel_takes`` accepts, (1, t, h, 128). ``overflow``: log-decays
    of 1.44-1.6 a token in every channel, under -100 within a chunk. ``solve``:
    beta 1.99 and the same key on tokens 16-31 and 72-87 with hardly a decay, so
    ``A``'s entries there are near 2 and ``T``'s alternate in sign."""
    args, dy = _inputs(t, dtype, b=1, h=h, k=128, v=128)
    q, k, v, g, beta = args
    if case == "overflow":
        g = -1.6 * jax.random.uniform(jax.random.PRNGKey(t), g.shape, F32, 0.9, 1.0)
    elif case == "solve":
        for at in (16, 72):
            k = k.at[:, at:at + 16].set(k[:, at:at + 1])
        g, beta = g * 0.01, jnp.full_like(beta, 1.99)
    return (q, k, v, g, beta), dy


@pytest.mark.parametrize("dtype,t,h,case,tol", [
    ("float32", 256, 8, "plain", 2e-5),
    ("float32", 100, 16, "plain", 2e-5),  # a ragged last chunk, two blocks of heads
    ("float32", 256, 8, "overflow", 2e-5),
    ("float32", 256, 8, "solve", 2e-4),
    ("bfloat16", 256, 8, "plain", 2e-2),
    ("bfloat16", 256, 8, "overflow", 2e-2)])
def test_kda_kernels_result_states_and_every_gradient_against_the_formula(
        kda_kernels, dtype, t, h, case, tol):
    """Both kernels against ``ops/delta_rule.py``'s formula on the same inputs:
    the result, every chunk's opening state (the kernels keep a state
    transposed, and the chunk's ``T`` beside it) and the gradient of all five
    inputs, whose formula is ``jax.vjp`` of ``_within`` around the carry's
    transposes: the pairs by sub-block under their anchors, the substitution and
    the series, the carry in scratch both ways, ``dG``'s rows less columns and
    its reverse running sum, the norms, the padding, a second block of heads."""
    args, dy = _kernel_inputs(t, dtype, h, case)
    want, want_opening, want_grads = _FORMULA(args, dy)
    before = dict(telemetry.delta_rule_branches())
    kda_kernels()
    got, (opening, solved), got_grads = _KERNELS(args, dy)
    assert got.shape == want.shape and got.dtype == want.dtype
    assert bool(jnp.all(jnp.isfinite(got.astype(F32))))
    _close(got.astype(F32), want.astype(F32), tol)
    opening = jnp.swapaxes(opening, -1, -2)
    assert opening.dtype == solved.dtype == F32 and not np.any(np.asarray(opening[:, 0]))
    assert solved.shape == opening.shape[:3] + (64, 64)
    _close(opening, want_opening, tol)
    for name, a, b in zip(INPUTS, got_grads, want_grads):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert bool(jnp.all(jnp.isfinite(a.astype(F32)))), name
        _close(a.astype(F32), b.astype(F32), 5 * tol)
    # counted where traced: a case after the first of its shape runs the program it compiled
    after = telemetry.delta_rule_branches()
    assert after["kernel"] >= max(before.get("kernel", 0), 1)
    assert after.get("xla") == before.get("xla")


def test_kda_kernels_gradients_are_plain_autodiffs_of_the_formula(kda_kernels):
    """The backward kernel's five gradients against plain autodiff through the
    formula's forward (``jax.vjp`` of every line of it, no hand-written backward
    anywhere), float32."""
    args, dy = _kernel_inputs(256)
    _, plain = jax.jit(lambda *a: _all_gradients(lambda *b: D._forward(64, *b)[0], a, dy))(*args)
    kda_kernels()
    for name, a, b in zip(INPUTS, _KERNELS(args, dy)[2], plain):
        _close(a, b, 1e-4)


@pytest.mark.parametrize("what", ["the_cpu", "k_of_64", "a_chunk_of_24", "float16",
                                  "a_vmem_ceiling_shrunk"])
def test_kernel_takes_refuses_and_the_refused_call_is_the_formula(monkeypatch, what):
    """``kernel_takes`` reads the platform, the type, the shapes and the chip's
    VMEM: each call it refuses traces the ``jax.numpy`` formula, both halves
    (no kernel in the program), and counts ``xla``."""
    shape, chunk, dtype = (1, 128, 8, 128), 64, "bfloat16"
    monkeypatch.setattr(K, "on_tpu", lambda: what != "the_cpu")
    assert what == "the_cpu" or K.kernel_takes(shape, shape, chunk, dtype)
    if what == "k_of_64":
        shape = (1, 128, 8, 64)
    elif what == "a_chunk_of_24":
        chunk = 24
    elif what == "float16":
        dtype = "float16"
    elif what == "a_vmem_ceiling_shrunk":
        monkeypatch.setattr(CHIP, "VMEM_CEILING", 2 ** 20)
    assert not K.kernel_takes(shape, shape, chunk, dtype)
    assert not K.kernel_takes((1, 128, 4, 128), (1, 128, 4, 128), 64, "bfloat16")  # half a block
    args, dy = _inputs(shape[1], dtype, b=1, h=8, k=shape[3], v=shape[3])
    before = dict(telemetry.delta_rule_branches())
    program = str(jax.make_jaxpr(lambda *a: _all_gradients(
        lambda *b: D.gated_delta_rule(*b, chunk=chunk), a, dy))(*args))
    assert "pallas_call" not in program and "scan[" in program
    after = telemetry.delta_rule_branches()
    assert after["xla"] == before.get("xla", 0) + 1 and after.get("kernel") == before.get("kernel")


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_heads_norm_comes_first_and_the_sigmoid_gate_after_it(dtype):
    """``RMSNorm(o) * w * sigmoid(z)`` a head against the plain formula under
    autodiff, through the Gluon block on (B, T, H, K); the block's default is
    the other order under the other gate, another function."""
    from mxnet_tpu import autograd as ag

    r = jax.random.split(jax.random.PRNGKey(2), 4)
    o, z, dy = (jax.random.normal(r[i], (2, 7, 3, 8), F32).astype(dtype) for i in range(3))
    w = (1.0 + 0.3 * jax.random.normal(r[3], (8,), F32)).astype(dtype)
    plain = lambda o, z, w: ref.rms_norm(o, w, 1e-5) * jax.nn.sigmoid(z)
    f32 = tuple(a.astype(F32) for a in (o, z, w))
    want = plain(*f32)
    wants = jax.grad(lambda *a: jnp.sum(plain(*a) * dy.astype(F32)), argnums=(0, 1, 2))(*f32)
    net = mx.gluon.nn.RMSNormSigmoidGate(epsilon=1e-5, in_channels=8)
    net.initialize()
    net.cast(dtype)
    net.gamma.set_data(w)
    os_, zs = nd.NDArray(o), nd.NDArray(z)
    os_.attach_grad()
    zs.attach_grad()
    with ag.record():
        out = net(os_, zs)
    out.backward(nd.NDArray(dy))
    tol = 1e-5 if dtype == "float32" else 2e-2
    assert str(out.dtype) == dtype
    _close(out.asnumpy().astype(np.float32), want, tol)
    for got, b in zip((os_.grad, zs.grad, net.gamma.grad()), wants):
        _close(got.asnumpy().astype(np.float32), b, tol)
    first = mx.gluon.nn.GatedRMSNorm(epsilon=1e-5, in_channels=8)
    first.initialize()
    first.cast(dtype)
    first.gamma.set_data(w)
    other = first(nd.NDArray(o), nd.NDArray(z)).asnumpy().astype(np.float32)
    _close(other, ref.rms_norm(f32[0] * jax.nn.silu(f32[1]), f32[2], 1e-5), tol)
    assert np.max(np.abs(other - np.asarray(want))) > 0.1


# -- the attention block's gate -----------------------------------------------------------
def _gqa_cases():
    k = loader.load_json("configs", "rehearse_keye_vl2")
    l = loader.load_json("configs", "rehearse_lfm2_moe")
    s = loader.load_json("configs", "rehearse_smallthinker")
    g = loader.load_json("configs", "rehearse_granite_hybrid")
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
        "granite": dict(units=g["hidden_size"], num_heads=g["num_attention_heads"],
                        num_kv_heads=g["num_key_value_heads"],
                        head_dim=g["hidden_size"] // g["num_attention_heads"],
                        rope_theta=None, head_norm=False,
                        sm_scale=g["attention_multiplier"]),
    }


# sha256 (16 hex digits) of the block's traced forward at each family's tiny
# configuration as the tree BEFORE the gate was built (PR 46's, 46f77d7) traces
# it
_GQA_DIGESTS = {
    "granite": "b2b37959aafdd41d",
    "keye": "0e84345110358daa",
    "lfm2": "2ab3c79bd616e433",
    "smallthinker_full": "fe41eca71f22ba1c",
    "smallthinker_window": "97c530bb2c0b5fcc",
}


def _digest(jaxpr):
    return hashlib.sha256(re.sub(r" at 0x[0-9a-f]+", "", str(jaxpr)).encode()
                          ).hexdigest()[:16]


@pytest.mark.parametrize("family", sorted(_gqa_cases()))
def test_the_attention_blocks_default_is_the_block_without_a_gate_bit_for_bit(family):
    """Keye's, LFM2's, SmallThinker's and Granite's blocks name no
    ``output_gate``: the default block has the parameters it had, equals the
    one that states ``output_gate=False`` to the last bit, output and every
    gradient, and traces to the program it traced to before there was a gate,
    letter for letter; a gated block is another program."""
    from mxnet_tpu import autograd as ag

    kw = _gqa_cases()[family]
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 24, kw["units"]), F32)
    dy = jax.random.normal(jax.random.PRNGKey(4), (2, 24, kw["units"]), F32)

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
        out.backward(nd.NDArray(dy))
        params = sorted(blk.collect_params().items())
        grads = [p.grad().asnumpy() for _, p in params]
        return ([out.asnumpy(), xs.grad.asnumpy()] + grads, [n for n, _ in params],
                jax.make_jaxpr(lambda a: blk(nd.NDArray(a)).data)(x))

    default, names, program = run()
    stated, _, stated_program = run(output_gate=False)
    assert not any("gate" in n for n in names) and len(default) >= 5
    for a, b in zip(default, stated):
        assert np.array_equal(a, b)
    assert _digest(program) == _digest(stated_program) == _GQA_DIGESTS[family]
    gated, gated_names, gated_program = run(output_gate=True)
    assert "gqa_gate_proj_weight" in gated_names
    assert not np.array_equal(default[0], gated[0])
    assert _digest(gated_program) != _digest(program)


def test_the_gated_block_is_the_references_attention():
    """8 query heads on 1 K/V head as the cell cuts them, no positions, no
    head norm, the gate an element of every head before ``o_proj``."""
    from mxnet_tpu import autograd as ag

    config = dict(loader.load_json("configs", "rehearse_solar_open2"),
                  num_attention_heads=8, num_key_value_heads=1, head_dim=8)
    a = ref.arch(config)
    blk = GroupedQueryAttention(64, 8, 1, 8, rope_theta=None, head_norm=False,
                                output_gate=True, prefix="gqa_")
    blk.initialize()
    r = jax.random.split(jax.random.PRNGKey(8), 6)
    p = {"q.w": jax.random.normal(r[0], (64, 64), F32),
         "kv.w": jax.random.normal(r[1], (16, 64), F32),
         "gate.w": jax.random.normal(r[2], (64, 64), F32),
         "o.w": 0.1 * jax.random.normal(r[3], (64, 64), F32)}
    for leaf, name in (("q.w", "q_proj_weight"), ("kv.w", "kv_proj_weight"),
                       ("gate.w", "gate_proj_weight"), ("o.w", "o_proj_weight")):
        blk.collect_params()["gqa_" + name].set_data(p[leaf])
    x = jax.random.normal(r[4], (2, 40, 64), F32)
    dy = jax.random.normal(r[5], (2, 40, 64), F32)
    with jax.default_matmul_precision("highest"):
        want, dx = jax.value_and_grad(lambda xx: jnp.sum(ref.attention(p, xx, a) * dy))(x)
        xs = nd.NDArray(x)
        xs.attach_grad()
        with ag.record():
            out = blk(xs)
        out.backward(nd.NDArray(dy))
        _close(jnp.sum(out.data * dy), want, 1e-5)
        _close(xs.grad.asnumpy(), dx, 5e-5)
        bare = jnp.sum(ref.attention(p, x, a, planted="no_gqa_gate") * dy)
    assert abs(float(bare - want)) > 1e-2 * abs(float(want))


# -- the model ---------------------------------------------------------------------------
def _arch(**over):
    config = loader.load_json("configs", "rehearse_solar_open2")
    return dict(config, dtype="float32", **over)


def _set(block, prefix, values):
    params = block.collect_params()
    for name, value in values.items():
        params[prefix + name].set_data(value)


def _tiny_model(config=None):
    config = config or _arch()
    params = ref.init(config, 5)
    # the output gate's bias off its seeded 0, so that a sign or a transpose shows
    params = {k: v + (0.1 * jax.random.normal(jax.random.PRNGKey(n), v.shape, F32)
                      if k.endswith("gb.bias") else 0.0)
              for n, (k, v) in enumerate(params.items())}
    cfg = dict(config, n_routed_experts=config["published"]["n_routed_experts"])
    net = zoo.SolarOpen2Model(cfg, experts_held=tuple(config["experts_held"]),
                              chunk=config["assumed"]["chunk"])
    net.initialize()
    net.cast(config["dtype"])
    model = loader.load_module("models", "solar_open2")
    names = model.leaf_names(config, net.prefix)
    model.common.set_parameters(net.collect_params(), names, params)
    return config, params, net, names


def _batch(t=37):
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


def test_model_forward_and_every_leafs_gradient_against_the_reference():
    """float32 on both sides, 37 tokens (four chunks of 8 and one of 5): the
    zoo's decoder (a gated NoPE attention layer, then two delta-attention
    layers, each over the expert layer with its shared expert, an untied head)
    and the plain reference, whose delta rule is the token-by-token recurrence,
    give the same scores, the same loss and the same gradient of every leaf."""
    config, params, net, names = _tiny_model()
    x, y = _batch()
    with jax.default_matmul_precision("highest"):
        want, grads = ref.value_and_grad(config, params, x, y)
        logits = ref.logits(config, params, x)
    scores, loss = _program(net, x, y)
    net_params = net.collect_params()
    _close(scores.asnumpy(), logits, 1e-5)
    _close(loss.asnumpy(), want, 1e-5)
    assert set(names) == set(grads) and len(names) == 14 + 2 * 22 + 3
    for leaf, name in names.items():
        if leaf.endswith(("router.w", "router.bias")):  # a share held: no gradient
            assert not np.asarray(grads[leaf]).any()
            continue
        _close(net_params[name].grad().asnumpy(), grads[leaf], 5e-4)


@pytest.mark.parametrize("fault", ref.PLANTED)
def test_a_reference_with_the_layer_written_otherwise_is_not_the_program(fault):
    """The controls: every chunk opening on a zero state, beta not doubled, the
    gate before the norm, a head's channels all decaying by their mean, the
    attention's gate left out. Each moves the gradient past the tolerance the
    sound comparison meets, so the test above tells them apart."""
    config, params, net, names = _tiny_model()
    x, y = _batch()
    other = copy.deepcopy(config)
    other["reference"]["planted"] = fault
    with jax.default_matmul_precision("highest"):
        _, grads = ref.value_and_grad(other, params, x, y)
        _, planted = ref.value_and_grad(config, params, x, y, quant=fault)
    _program(net, x, y)
    net_params = net.collect_params()
    worst = max(
        np.max(np.abs(net_params[name].grad().asnumpy() - np.asarray(grads[leaf])))
        / max(np.max(np.abs(np.asarray(grads[leaf]))), 1e-30)
        for leaf, name in names.items() if not leaf.endswith(("router.w", "router.bias")))
    assert worst > 20 * 5e-4, worst
    for leaf in grads:  # the calibration tools plant it as ``quant``: the same fault
        assert np.array_equal(np.asarray(grads[leaf]), np.asarray(planted[leaf]))


# -- the shares add up --------------------------------------------------------------------
def _uncut():
    """One delta-attention layer, one attention layer and one expert layer at
    the rehearsal's widths, every head and every expert: 4 delta-rule heads, 4
    query heads on 2 K/V heads, 8 experts."""
    config = _arch(n_routed_experts=8, experts_held=[0, 8], gqa_layers=[0],
                   num_hidden_layers=2)
    params = ref.init(config, 11)
    x = jax.random.normal(jax.random.PRNGKey(12), (2, 21, 64), F32)
    return config, params, x


def _layer(params, l):
    pre = "l%d." % l
    return {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}


def _rows(w, heads, d, share, of):
    """The rows of ``w`` (heads * d, ...) that share ``share`` of ``of`` holds."""
    per = heads // of
    return w.reshape((heads, d) + w.shape[1:])[share * per:(share + 1) * per].reshape(
        (per * d,) + w.shape[1:])


def test_the_shares_of_the_delta_rule_heads_add_up_to_the_uncut_layer():
    """Two chips of two heads each: every chip's ``KimiDeltaAttention`` built at
    ITS head count from its rows of the uncut layer's leaves (the low-rank
    pairs' first halves and the head norm whole on both) gives a partial
    ``o_proj`` result, and the two sum to the uncut reference's layer."""
    config, params, x = _uncut()
    a, p = ref.arch(config), _layer(params, 1)
    with jax.default_matmul_precision("highest"):
        want = ref.kda(p, x, a)
        total = jnp.zeros_like(want)
        for share in range(2):
            rows = lambda w: _rows(w, 4, 16, share, 2)
            blk = zoo.KimiDeltaAttention(64, 2, 16, 4, 1e-5, True, 8, prefix="kda_")
            blk.initialize()
            qkv = jnp.concatenate([rows(part) for part in jnp.split(p["qkv.w"], 3)])
            taps = jnp.concatenate([rows(part) for part in jnp.split(p["conv.w"], 3)])
            _set(blk, "kda_", {
                "qkv_proj_weight": qkv, "conv_weight": taps,
                "f_a_proj_weight": p["fa.w"], "f_b_proj_weight": rows(p["fb.w"]),
                "A_log": p["A_log"][share * 2:(share + 1) * 2], "dt_bias": rows(p["dt_bias"]),
                "b_proj_weight": p["b.w"][share * 2:(share + 1) * 2],
                "g_a_proj_weight": p["ga.w"], "g_b_proj_weight": rows(p["gb.w"]),
                "g_b_proj_bias": rows(p["gb.bias"]), "o_norm_gamma": p["o_norm.g"],
                "o_proj_weight": rows(p["out.w"].T).T})
            total = total + blk(nd.NDArray(x)).data
    assert float(jnp.max(jnp.abs(want))) > 1e-2
    _close(total, want, 2e-5)


def test_the_shares_of_the_query_heads_add_up_to_the_uncut_layer():
    """Two chips of two query heads on one K/V head each (the cell's 8 on 1 at
    the rehearsal's size)."""
    config, params, x = _uncut()
    a, p = ref.arch(config), _layer(params, 0)
    with jax.default_matmul_precision("highest"):
        want = ref.attention(p, x, a)
        total = jnp.zeros_like(want)
        for share in range(2):
            blk = GroupedQueryAttention(64, 2, 1, 16, rope_theta=None, head_norm=False,
                                        output_gate=True, prefix="gqa_")
            blk.initialize()
            keys, values = jnp.split(p["kv.w"], 2)
            _set(blk, "gqa_", {
                "q_proj_weight": _rows(p["q.w"], 4, 16, share, 2),
                "kv_proj_weight": jnp.concatenate([_rows(keys, 2, 16, share, 2),
                                                   _rows(values, 2, 16, share, 2)]),
                "gate_proj_weight": _rows(p["gate.w"], 4, 16, share, 2),
                "o_proj_weight": _rows(p["o.w"].T, 4, 16, share, 2).T})
            total = total + blk(nd.NDArray(x)).data
    _close(total, want, 2e-5)


def test_the_shares_of_the_experts_add_up_with_the_shared_expert_counted_once():
    """Two chips of four experts each: every chip's ``DeepseekMoE`` routes over
    all eight, computes its own experts' part and the shared expert; the two
    results, less one shared expert, are the uncut reference's layer."""
    config, params, x = _uncut()
    a, p = ref.arch(config), _layer(params, 1)
    rows = x.reshape(-1, 64)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(p, rows, a)
        shared = want - ref.moe(p, rows, a, shared=False)
        total = -shared
        for share in range(2):
            held = (4 * share, 4)
            blk = DeepseekMoE(64, 32, 8, 2, 1, 1, held, scoring="sigmoid",
                              selection_bias=True, router_gradient=False, prefix="moe_")
            blk.initialize()
            _set(blk, "moe_", {
                "router_weight": p["router.w"], "router_bias": p["router.bias"],
                "gate_weight": p["experts.gate"][held[0]:held[0] + 4],
                "up_weight": p["experts.up"][held[0]:held[0] + 4],
                "down_weight": p["experts.down"][held[0]:held[0] + 4],
                "shared_gate_weight": p["shared.gate.w"],
                "shared_up_weight": p["shared.up.w"],
                "shared_down_weight": p["shared.down.w"]})
            total = total + blk(nd.NDArray(rows)).data
            part = ref.moe(p | {k: v[held[0]:held[0] + 4] for k, v in p.items()
                                if k.startswith("experts.")}, rows, a, experts_held=held)
            _close(blk(nd.NDArray(rows)).data, part, 2e-5)
    assert float(jnp.max(jnp.abs(shared))) > 1e-2
    _close(total, want, 2e-5)


# -- the config ---------------------------------------------------------------------------
def test_the_config_is_checked_and_layers_take_their_kind_from_gqa_layers():
    lin = _arch()["linear_attn_config"]
    for key, bad in (("use_rope", True), ("tie_word_embeddings", True),
                     ("first_k_dense_replace", 1), ("kda_use_full_proj", True),
                     ("norm_topk_prob", False), ("gqa_layers", [0, 3]),
                     ("linear_attn_config", dict(lin, num_kv_heads=2))):
        with pytest.raises(mx.base.MXNetError):
            zoo.SolarOpen2Model(dict(_arch(), **{key: bad}))
    zoo.SolarOpen2Model(dict(_arch(), linear_attn_config=dict(lin, num_kv_heads=4)))
    net = zoo.SolarOpen2Model(_arch(gqa_layers=[1], n_routed_experts=8), experts_held=(0, 4))
    assert [type(b.mixer).__name__ for b in net.blocks] == [
        "KimiDeltaAttention", "GroupedQueryAttention", "KimiDeltaAttention"]
    assert all(type(b.ffn).__name__ == "DeepseekMoE" for b in net.blocks)
    attn = net.blocks[1].mixer
    assert attn._theta is None and attn.qk_norm is None and attn.gate_proj is not None
    assert attn._scale == 16 ** -0.5
    plain = zoo.SolarOpen2Model(_arch(use_gqa_gate=False, kda_allow_neg_eigval=False))
    assert plain.blocks[0].mixer.gate_proj is None and plain.blocks[1].mixer._beta == 1.0
    net.initialize()
    net.cast("bfloat16")
    mixer, moe = net.blocks[0].mixer, net.blocks[0].ffn
    assert mixer._beta == 2.0 and mixer._chunk == 64
    assert {str(p.dtype) for p in (mixer.A_log, mixer.dt_bias, mixer.conv_weight)} \
        == {"bfloat16"}
    assert mixer.qkv_proj.weight.shape == (3 * 64, 64) and mixer.conv_weight.shape == (192, 4)
    assert mixer.f_b_proj.weight.shape == (64, 16) and mixer.g_b_proj.bias.shape == (64,)
    assert mixer.A_log.shape == (4,) and mixer.dt_bias.shape == (64,)
    assert not any(n.endswith("conv_bias") for n in net.collect_params())  # a constant
    assert moe.router_weight.shape == (8, 64) and moe.gate_weight.shape == (4, 64, 32)
    assert str(moe.router_bias.dtype) == "float32"
    assert moe._static["router_gradient"] is False and moe._static["sum_epsilon"] == 1e-20


def test_the_published_sizes_hold_840_875_672_parameters():
    """The cell's configuration, from its leaves' shapes alone (nothing is
    allocated): ISSUE 47's count, layer by layer, every published width in
    place and every reduced key beside its published value."""
    config = loader.load_json("configs", "solar_open2_250b_ep40_tp8")
    sizes = {k: int(np.prod(s)) for k, (s, _) in ref.leaves(config).items()}

    def layer(l, part=""):
        return sum(v for k, v in sizes.items() if k.startswith("l%d.%s" % (l, part)))

    mixers = [layer(l) - 142876992 for l in range(4)]  # less what lies beside a mixer
    assert mixers == [13631488, 18135176, 18135176, 18135176]
    assert [layer(l) for l in range(4)] == [156508480] + [161012168] * 3
    assert sizes["embed.w"] == sizes["head.w"] == 24576 * 4096
    assert sum(sizes.values()) == 840875672  # the selection biases' 4 x 320 among them
    assert "840,875,672 parameters" in config["deployment"]
    assert (config["hidden_size"], config["head_dim"], config["moe_intermediate_size"],
            config["num_experts_per_tok"], config["rms_norm_eps"]) == (4096, 128, 1280, 8, 1e-5)
    lin = config["linear_attn_config"]
    assert (lin["head_dim"], lin["short_conv_kernel_size"], lin["num_heads"]) == (128, 4, 8)
    assert config["published"]["n_routed_experts"] == 320 and config["experts_held"] == [0, 8]
    assert sorted(config["reduced"]) == sorted(config["published"])
    for key in config["reduced"]:
        assert config[key] != config["published"][key]
    assert config["assumed"]["recomputation"]["remat"] is None


# -- scopes -------------------------------------------------------------------------------
def test_the_step_carries_the_scopes_of_both_mixers_and_the_expert_layer():
    """Device time is attributed by the names in the compiled step: a
    delta-attention layer's projections, filter, log-decays, delta rule and
    norm-then-gate under ``kda``, both halves of each op under a scope of its
    own, attention's under ``gqa``, the expert layer's under ``moe``, with and
    without recomputation."""
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
        for want in ("kda/qkv_proj", "kda/causal_conv", "kda/causal_conv_bwd",
                     "kda/log_decay", "kda/delta_rule", "kda/delta_rule_bwd",
                     "kda/o_norm/rmsnorm_gate", "kda/o_norm/rmsnorm_gate_bwd",
                     "kda/o_proj", "gqa/q_proj", "gqa/gate_proj", "gqa/attention",
                     "gqa/attention_bwd", "moe"):
            assert want in names, (remat, want)
        for scope in ("delta_rule_bwd", "causal_conv_bwd", "rmsnorm_gate_bwd"):
            assert phases[scope] == {"backward"}, (remat, scope)
        assert "forward" in phases["delta_rule"] and "forward" in phases["causal_conv"]
        if remat:  # the forward run again counts as backward, under the same scopes
            assert "backward" in phases["delta_rule"]


# -- the whole model through ShardedTrainStep, against the follower -------------------
def _first_steps(faults=()):
    c = loader.resolve_cell(CELL, rehearse=True)
    config = loader.load_json("configs", c["config"])
    traffic = loader.load_json("traffic", c["traffic"])
    model = loader.load_module("models", config["family"])
    runner = loader.load_module("runners", c["runner"])
    opt = train_reference.effective_optimizer(config, traffic)
    params, pool = ref.init(config, 5), ref.batches(config, traffic, 5)
    counted = telemetry.delta_rule_branches().get("xla", 0)
    prog = model.build(config, traffic, params, jax.devices()[:1], opt)
    first, later = runner.first_steps(prog, [prog.batch(x, y) for x, y in pool], params,
                                      traffic)
    plain = train_reference.first_steps(ref, config, opt, params, pool,
                                        program_gradient=first["first_gradient"],
                                        keep_gradient=True)
    want = plain.pop("first_gradient")
    out = {"cell": c, "config": config, "program": compare.training_numbers(first, plain),
           "later": later, "published": prog.after_window(), "entry": prog.entry,
           "describe": prog.describe, "counted": counted, "traffic": traffic}
    for fault in faults:
        low = train_reference.first_steps(ref, config, opt, params, pool, quant=fault,
                                          keep_gradient=True)
        rel, norms = train_reference.gradient_distance(low.pop("first_gradient"), want)
        out[fault] = compare.training_numbers(
            low, dict(plain, grad_rel_diff=rel, grad_diff_norms=norms))
    return out


@pytest.fixture(scope="module")
def first_steps():
    return _first_steps(faults=CONTROLS)


def test_model_trains_through_sharded_step_like_the_follower(first_steps):
    """bfloat16 through the compiled step at the rehearsal's size: 44 tokens
    under chunks of 8, five whole chunks and a ragged sixth."""
    rows = compare.judge(first_steps["program"], first_steps["cell"]["limits"])
    assert all(r["ok"] for r in rows), [r for r in rows if not r["ok"]]
    assert first_steps["later"] == 0  # nothing compiled after the first call
    assert getattr(first_steps["entry"], "fused", True)
    assert first_steps["describe"]["net"] == "SolarOpen2Model"
    assert first_steps["describe"]["remat"] is None and first_steps["describe"]["chunk"] == 8
    assert first_steps["traffic"]["sequence"] % 8


def test_the_adapter_publishes_the_chunks_the_slots_and_the_branch_counter(first_steps):
    published = first_steps["published"]
    assert published["delta_rule_chunks"] == 6  # 44 tokens under chunks of 8
    # two delta-attention layers traced once in the step: one count each
    assert published["delta_rule_branches"]["xla"] >= first_steps["counted"] + 2
    # the CPU traces the formula; "kernel" is there only from this file's interpreted tests
    assert set(published["delta_rule_branches"]) <= {"xla", "kernel"}
    slots = published["expert_slots"]
    assert len(slots) == 3 and all(len(row) == 4 and sum(row) > 0 for row in slots)
    for name in ("linear_attention_share.train", "delta_rule_roofline.train",
                 "kda_conv_roofline.train"):
        reader = loader.load_module("layer_metrics", name)
        assert reader.NAME == name and reader.read({"trace_dir": None}) is None


@pytest.mark.parametrize("fault", CONTROLS)
def test_a_control_fails_a_limit_the_program_meets(first_steps, fault):
    """The reference in fp8, or with a fault planted in its delta rule, put
    in the program's place: its first gradient lies farther from the sound
    reference's than the program's does, and a limit between the two readings
    passes the one and refuses the other."""
    def value(numbers, name):
        return next(v for n, v, _ in numbers if n == name)

    sound = value(first_steps["program"], "grad_rel_diff")
    control = value(first_steps[fault], "grad_rel_diff")
    assert control > 2 * sound, (sound, control)
    limits = dict(first_steps["cell"]["limits"], grad_rel_diff=(sound * control) ** 0.5)
    assert all(r["ok"] for r in compare.judge(first_steps["program"], limits))
    assert not all(r["ok"] for r in compare.judge(first_steps[fault], limits))


def test_warmup_replays_the_delta_rules_recorded_signature(monkeypatch):
    """A traced call leaves its shapes in the tuning table, as the flash
    kernels' do, and ``tuning.warmup()``'s replay compiles forward and gradient
    of what was recorded."""
    import importlib

    from mxnet_tpu import tuning

    W = importlib.import_module("mxnet_tpu.tuning.warmup")  # the package names a function so

    seen = []
    monkeypatch.setattr(tuning, "record_signature", lambda kind, spec: seen.append((kind, spec)))
    args, _ = _inputs(16, "bfloat16")
    D.gated_delta_rule(*args, chunk=8)
    kind, spec = seen[-1]
    assert kind == "gated_delta_rule" and spec["q_shape"] == [2, 16, 2, 16]
    assert (spec["chunk"], spec["g_dtype"], spec["dtype"]) == (8, "float32", "bfloat16")
    calls = []
    core = D._delta_core
    monkeypatch.setattr(D, "_delta_core", lambda *a: calls.append(a) or core(*a))
    assert W._warm_delta_rule(spec) == "gated_delta_rule"
    chunk, q, k, v, g, beta = calls[0]
    assert chunk == 8 and q.shape == k.shape == g.shape == (2, 16, 2, 16)
    assert str(g.dtype) == "float32" and beta.shape == (2, 16, 2)
