"""Flash attention kernel + BERT model tests."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd as ag
from mxnet_tpu import gluon, nd
from mxnet_tpu.gluon import model_zoo
from mxnet_tpu.ops import attention as A
from mxnet_tpu.test_utils import assert_almost_equal, with_seed


_FWD_CASES = {
    # (Tq, Tk, D, Dv, dtype, block_q, block_k, sm_scale, tolerance)
    "f32_80": (80, 80, 32, 32, "float32", 32, 32, 0.125, 1e-5),
    # operands stay bf16 into both matmuls
    "bf16_80": (80, 80, 32, 32, "bfloat16", 32, 32, 0.125, 2e-2),
    # a scale that is no power of two is applied to the scores, not to Q
    "f32_scale_0.3": (80, 80, 32, 32, "float32", 32, 32, 0.3, 1e-5),
    "bf16_scale_0.3": (80, 80, 32, 32, "bfloat16", 32, 32, 0.3, 2e-2),
    # causal is bottom-right aligned: the diagonal starts 32 keys in
    "tk_gt_tq": (48, 80, 32, 32, "float32", 16, 32, 0.125, 1e-5),
    # a length that pads Q alone, and one that pads K/V alone (the split
    # loop's tail block)
    "pads_q": (80, 96, 32, 32, "float32", 32, 32, 0.125, 1e-5),
    "pads_kv": (64, 80, 32, 32, "float32", 32, 32, 0.125, 1e-5),
    # values narrower than keys
    "dv_lt_d": (80, 80, 32, 16, "float32", 32, 32, 0.125, 1e-5),
    # more K/V blocks than the kernel unrolls: its loop, first block peeled
    "ten_kv_blocks": (80, 80, 32, 32, "float32", 32, 8, 0.125, 1e-5),
    # one block a side, as BERT's forward at 128 and at 512
    "one_tile": (80, 80, 32, 32, "bfloat16", 128, 128, 0.125, 2e-2),
}


@with_seed()
@pytest.mark.parametrize("case", sorted(_FWD_CASES))
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("with_bias", [False, True])
def test_flash_kernel_vs_reference(causal, with_bias, case):
    """Pallas kernel (interpret mode) must match the O(T^2) reference:
    its output, its lse, and the backward that recomputes from that lse."""
    Tq, Tk, D, Dv, dtype, bq, bk, sm, tol = _FWD_CASES[case]
    rng = np.random.RandomState(0)
    B, H = 2, 3

    def arr(*shape):
        return jnp.asarray(rng.normal(size=shape).astype("f4")).astype(dtype)

    q, k, v, do = (arr(B, H, Tq, D), arr(B, H, Tk, D), arr(B, H, Tk, Dv),
                   arr(B, H, Tq, Dv))
    bias = A.make_padding_bias(jnp.asarray([Tk - 43, Tk]), Tk) if with_bias \
        else None
    f32 = jnp.float32
    ref = A._attention_reference(q.astype(f32), k.astype(f32), v.astype(f32),
                                 bias, causal, sm)
    out, lse = A._flash_forward_pallas(q, k, v, bias, causal, sm, bq, bk,
                                       interpret=True)
    assert out.dtype == q.dtype and lse.dtype == f32
    assert out.shape == (B, H, Tq, Dv) and lse.shape == (B, H, Tq)
    assert_almost_equal(np.asarray(out.astype(f32)), np.asarray(ref),
                        rtol=tol, atol=tol)
    # lse is the logsumexp of the reference's masked, biased scores
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(f32), k.astype(f32)) * sm
    if bias is not None:
        s = s + bias
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq), s,
                      A._NEG_INF)
    assert_almost_equal(np.asarray(lse),
                        np.asarray(jax.nn.logsumexp(s, axis=-1)),
                        rtol=1e-5, atol=1e-5)
    # lse-based backward must match autodiff-of-reference
    got = A._flash_bwd(causal, sm, (q, k, v, bias, None, out, lse), do)
    _assert_grads_close(got[:3], _ref_grads(q, k, v, do, bias, causal, sm)[:3],
                        max(tol, 1e-4))


@with_seed()
def test_flash_attention_op_and_grad():
    """Registered op works through nd + autograd."""
    rng = np.random.RandomState(1)
    q = nd.array(rng.normal(size=(2, 2, 16, 8)).astype("f4"))
    k = nd.array(rng.normal(size=(2, 2, 16, 8)).astype("f4"))
    v = nd.array(rng.normal(size=(2, 2, 16, 8)).astype("f4"))
    q.attach_grad()
    with ag.record():
        out = nd.flash_attention(q, k, v)
        loss = (out * out).sum()
    loss.backward()
    assert out.shape == (2, 2, 16, 8)
    assert float(np.abs(q.grad.asnumpy()).sum()) > 0


@with_seed()
def test_bert_forward_shapes():
    net = model_zoo.bert_3_64_2(dropout=0.0)
    net.initialize()
    B, T = 2, 12
    tokens = nd.array(np.random.RandomState(0).randint(0, 1000, (B, T)))
    types = nd.zeros((B, T))
    vl = nd.array([8, 12])
    seq, pooled = net(tokens, types, vl)
    assert seq.shape == (B, T, 64)
    assert pooled.shape == (B, 64)
    scores = net.decode_mlm(seq)
    assert scores.shape == (B, T, 1000)
    nsp = net.classify_nsp(pooled)
    assert nsp.shape == (B, 2)


@with_seed()
def test_bert_padding_invariance():
    """Tokens past valid_length must not affect valid positions."""
    net = model_zoo.bert_3_64_2(dropout=0.0)
    net.initialize()
    rng = np.random.RandomState(0)
    t1 = rng.randint(0, 1000, (1, 10))
    t2 = t1.copy()
    t2[0, 6:] = rng.randint(0, 1000, 4)  # change only padding region
    vl = nd.array([6])
    types = nd.zeros((1, 10))
    s1, _ = net(nd.array(t1), types, vl)
    s2, _ = net(nd.array(t2), types, vl)
    assert_almost_equal(s1.asnumpy()[:, :6], s2.asnumpy()[:, :6],
                        rtol=1e-4, atol=1e-5)


@with_seed()
def test_bert_mlm_training_step():
    """One hybridized MLM pretraining step decreases loss over iterations."""
    net = model_zoo.bert_3_64_2(dropout=0.0)
    net.initialize()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    params = net.collect_params()
    trainer = gluon.Trainer(params, "adam", {"learning_rate": 1e-3})
    rng = np.random.RandomState(0)
    B, T = 4, 16
    tokens = nd.array(rng.randint(0, 1000, (B, T)))
    types = nd.zeros((B, T))
    labels = nd.array(rng.randint(0, 1000, (B, T)))

    losses = []
    for _ in range(12):
        with ag.record():
            seq, pooled = net(tokens, types)
            scores = net.decode_mlm(seq)
            loss = loss_fn(scores.reshape((-1, 1000)),
                           labels.reshape((-1,)))
        loss.backward()
        trainer.step(B * T)
        losses.append(float(loss.mean().asnumpy()))
    assert losses[-1] < losses[0] * 0.8, losses


@with_seed()
def test_bert_hybridize_consistency():
    net = model_zoo.bert_3_64_2(dropout=0.0)
    net.initialize()
    tokens = nd.array(np.random.RandomState(0).randint(0, 1000, (2, 8)))
    types = nd.zeros((2, 8))
    s0, p0 = net(tokens, types)
    net.hybridize()
    s1, p1 = net(tokens, types)
    assert_almost_equal(s0.asnumpy(), s1.asnumpy(), rtol=1e-5, atol=1e-5)
    assert_almost_equal(p0.asnumpy(), p1.asnumpy(), rtol=1e-5, atol=1e-5)


@with_seed()
def test_causal_cross_length_alignment():
    """Tq != Tk causal must be bottom-right aligned in ALL paths."""
    rng = np.random.RandomState(2)
    B, H, Tq, Tk, D = 1, 1, 4, 12, 8
    q = jnp.asarray(rng.normal(size=(B, H, Tq, D)).astype("f4"))
    k = jnp.asarray(rng.normal(size=(B, H, Tk, D)).astype("f4"))
    v = jnp.asarray(rng.normal(size=(B, H, Tk, D)).astype("f4"))
    ref = A._attention_reference(q, k, v, None, True, 0.3)
    out_p, _ = A._flash_forward_pallas(q, k, v, None, True, 0.3, 4, 4,
                                       interpret=True)
    assert_almost_equal(np.asarray(out_p), np.asarray(ref), rtol=1e-5,
                        atol=1e-5)
    out_s, _ = A._attention_scan_fwd(q, k, v, None, True, 0.3, chunk=4)
    assert_almost_equal(np.asarray(out_s), np.asarray(ref), rtol=1e-5,
                        atol=1e-5)


@with_seed()
def test_long_sequence_chunked_path():
    """KV beyond the VMEM budget takes the scan path; fwd+bwd match ref."""
    rng = np.random.RandomState(3)
    B, H, T, D = 1, 1, 64, 16
    q = jnp.asarray(rng.normal(size=(B, H, T, D)).astype("f4"))
    k = jnp.asarray(rng.normal(size=(B, H, T, D)).astype("f4"))
    v = jnp.asarray(rng.normal(size=(B, H, T, D)).astype("f4"))
    bias = A.make_padding_bias(jnp.asarray([50]), T)
    out, lse = A._attention_scan_fwd(q, k, v, bias, False, 0.25, chunk=16)
    ref = A._attention_reference(q, k, v, bias, False, 0.25)
    assert_almost_equal(np.asarray(out), np.asarray(ref), rtol=1e-5,
                        atol=1e-5)
    do = jnp.asarray(rng.normal(size=(B, H, T, D)).astype("f4"))
    dq, dk, dv, db = A._bwd_chunked(q, k, v, bias, out, lse, do, False,
                                    0.25, chunk=16)
    g_ref = jax.grad(
        lambda q_, k_, v_, b_: jnp.sum(
            A._attention_reference(q_, k_, v_, b_, False, 0.25) * do),
        argnums=(0, 1, 2, 3))(q, k, v, bias)
    assert_almost_equal(np.asarray(dq), np.asarray(g_ref[0]), rtol=1e-4,
                        atol=1e-4)
    assert_almost_equal(np.asarray(dk), np.asarray(g_ref[1]), rtol=1e-4,
                        atol=1e-4)
    assert_almost_equal(np.asarray(dv), np.asarray(g_ref[2]), rtol=1e-4,
                        atol=1e-4)
    assert_almost_equal(np.asarray(db), np.asarray(g_ref[3]), rtol=1e-3,
                        atol=1e-3)


def _bwd_case(rng, B, H, Tq, Tk, D, dtype, with_bias):
    """Seeded q, k, v, do (and a key bias that also masks a tail) in
    ``dtype``, with the reference's float32 gradient."""
    def arr(*shape):
        return jnp.asarray(rng.normal(size=shape).astype("f4")).astype(dtype)

    q, do = arr(B, H, Tq, D), arr(B, H, Tq, D)
    k, v = arr(B, H, Tk, D), arr(B, H, Tk, D)
    bias = None
    if with_bias:
        bias = jnp.asarray(rng.normal(size=(B, 1, 1, Tk)).astype("f4")) \
            + A.make_padding_bias(jnp.asarray([Tk - 7] + [Tk] * (B - 1)), Tk)
    return q, k, v, do, bias


def _ref_grads(q, k, v, do, bias, causal, sm_scale):
    f32 = jnp.float32

    def loss(q_, k_, v_, b_):
        return jnp.sum(A._attention_reference(
            q_.astype(f32), k_.astype(f32), v_.astype(f32), b_, causal,
            sm_scale) * do.astype(f32))

    if bias is None:
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v, None) + (None,)
    return jax.grad(loss, argnums=(0, 1, 2, 3))(q, k, v, bias)


def _assert_grads_close(got, ref, tol):
    """Each gradient within ``tol`` of the reference's, in units of the
    reference's largest entry (bf16 rounds relative to the magnitude)."""
    for name, g, r in zip(("dq", "dk", "dv", "dbias"), got, ref):
        assert (g is None) == (r is None), name
        if r is None:
            continue
        assert g.shape == r.shape, name
        g = np.asarray(g.astype(jnp.float32))
        r = np.asarray(r.astype(jnp.float32))
        err = np.max(np.abs(g - r)) / (np.max(np.abs(r)) + 1e-30)
        assert err < tol, "%s: %.3g of the largest entry" % (name, err)


_BWD_SHAPES = {
    # (Tq, Tk, block_q, block_k)
    "block_multiple": (64, 64, 32, 32),
    "ragged_80": (80, 80, 32, 32),
    "tq_ne_tk": (48, 80, 32, 16),
}


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("shape", sorted(_BWD_SHAPES))
@pytest.mark.parametrize("with_bias", [False, True])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_bwd_kernel_vs_reference(causal, with_bias, shape, dtype, tol):
    """The backward kernel (interpret mode) against jax.grad of the
    reference: dq, dk, dv and dbias, from the forward kernel's out/lse."""
    Tq, Tk, bq, bk = _BWD_SHAPES[shape]
    rng = np.random.RandomState(7)
    q, k, v, do, bias = _bwd_case(rng, 2, 3, Tq, Tk, 32, dtype, with_bias)
    out, lse = A._flash_forward_pallas(q, k, v, bias, causal, 0.125,
                                       32, 32, interpret=True)
    got = A._flash_backward_pallas(q, k, v, bias, out, lse, do, causal,
                                   0.125, bq, bk, interpret=True)
    assert [g.dtype for g in got[:3]] == [q.dtype, k.dtype, v.dtype]
    _assert_grads_close(got, _ref_grads(q, k, v, do, bias, causal, 0.125),
                        tol)


def test_flash_bwd_kernel_tiles_of_the_dispatch():
    """At the blocks _bwd_blocks picks (128 lanes at least) ragged lengths
    are padded and masked: 300 queries and 330 keys in three tiles each."""
    assert A._bwd_blocks(512, 512) == (512, 512)
    assert A._bwd_blocks(200, 640) == (256, 128)
    assert A._bwd_blocks(80, 2048) == (128, 512)
    assert A._bwd_blocks(300, 330) == (128, 128)
    rng = np.random.RandomState(8)
    q, k, v, do, bias = _bwd_case(rng, 1, 2, 300, 330, 32, "float32", True)
    out, lse = A._flash_forward_pallas(q, k, v, bias, True, 0.125,
                                       128, 128, interpret=True)
    got = A._flash_backward_pallas(q, k, v, bias, out, lse, do, True, 0.125,
                                   128, 128, interpret=True)
    _assert_grads_close(got, _ref_grads(q, k, v, do, bias, True, 0.125),
                        1e-4)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("chunk", [16, 24])  # divides Tk = 64 / does not
@pytest.mark.parametrize("causal", [False, True])
def test_bwd_chunked_operand_dtype_and_chunk(causal, chunk, dtype, tol):
    """The XLA chunked backward with operands in the input dtype: a chunk
    that divides Tk and one that leaves a padded tail."""
    rng = np.random.RandomState(9)
    q, k, v, do, bias = _bwd_case(rng, 2, 2, 48, 64, 16, dtype, True)
    out, lse = A._attention_scan_fwd(q, k, v, bias, causal, 0.25, chunk=16)
    got = A._bwd_chunked(q, k, v, bias, out, lse, do, causal, 0.25,
                         chunk=chunk)
    assert [g.dtype for g in got[:3]] == [q.dtype, k.dtype, v.dtype]
    _assert_grads_close(got, _ref_grads(q, k, v, do, bias, causal, 0.25),
                        tol)


@pytest.mark.parametrize("shape,expect", [
    ((32, 12, 512, 512), 256),    # the BERT cell: two chunks, no padding
    ((32, 12, 512, 640), 256),    # five tiles in three chunks: one padded
    ((1, 8, 8192, 8192), 1024),
    ((64, 16, 4096, 100), 128),   # never under a tile
    ((1, 1, 64, 131072), 131072),  # the budget admits all of Tk: one chunk
])
def test_bwd_chunk_is_whole_tiles(shape, expect):
    B, H, Tq, Tk = shape
    chunk = A._bwd_chunk(B, H, Tq, Tk)
    assert chunk == expect and chunk % 128 == 0
    nchunks = -(-Tk // chunk)
    # under the score budget unless one tile is already over it
    assert chunk == 128 or B * H * Tq * chunk * 4 <= A._BWD_SCORE_BYTES
    assert nchunks * chunk - Tk < 128 * nchunks  # padding under a tile each
    if shape == (32, 12, 512, 512):
        assert nchunks * chunk == Tk  # not padded


def test_flash_bwd_branch_counter_and_no_kernel_on_cpu(monkeypatch):
    """_flash_bwd counts the branch it takes, and on the CPU never reaches
    the kernel, whatever the residuals say."""
    from mxnet_tpu import telemetry

    def no_kernel(*a, **kw):
        raise AssertionError("the backward kernel was reached on the CPU")

    monkeypatch.setattr(A, "_flash_backward_pallas", no_kernel)
    rng = np.random.RandomState(10)
    q, k, v, do, _ = _bwd_case(rng, 1, 2, 64, 64, 16, "float32", False)
    out, lse = A._flash_forward_pallas(q, k, v, None, False, 0.25,
                                       32, 32, interpret=True)
    ref = _ref_grads(q, k, v, do, None, False, 0.25)

    before = telemetry.flash_bwd_branches()
    got = A._flash_bwd(False, 0.25, (q, k, v, None, None, out, lse), do)
    _assert_grads_close(got, ref, 1e-4)
    # a score matrix over the budget: the chunked branch, in whole tiles
    monkeypatch.setattr(A, "_BWD_SCORE_BYTES", 1024)
    got = A._flash_bwd(False, 0.25, (q, k, v, None, None, out, lse), do)
    _assert_grads_close(got, ref, 1e-4)
    # through the op, differentiated under jit: one count a trace
    g = jax.jit(jax.grad(lambda q_: jnp.sum(
        A.flash_attention(q_, k, v, sm_scale=0.25) * do)))
    g(q), g(q)
    after = telemetry.flash_bwd_branches()
    delta = {b: after.get(b, 0) - before.get(b, 0) for b in after}
    assert delta == {"chunked": 2, "materialised": 1}
    assert "kernel" not in after


def test_flash_fwd_branch_counter(monkeypatch):
    """_flash_fwd counts the branch it takes: the reference on the CPU, the
    kernel where the device is a TPU and the table says so, the scan once a
    head's K/V are over _VMEM_KV_BYTES; one count a trace."""
    from mxnet_tpu import telemetry

    rng = np.random.RandomState(11)
    q, k, v, do, _ = _bwd_case(rng, 1, 2, 128, 128, 16, "float32", False)
    ref = A._attention_reference(q, k, v, None, False, 0.25)

    def delta(fn):
        before = telemetry.flash_fwd_branches()
        out = fn()
        after = telemetry.flash_fwd_branches()
        assert_almost_equal(np.asarray(out), np.asarray(ref), rtol=1e-5,
                            atol=1e-5)
        return {b: after[b] - before.get(b, 0) for b in after
                if after[b] != before.get(b, 0)}

    op = lambda q_: A.flash_attention(q_, k, v, sm_scale=0.25)  # noqa: E731
    assert delta(lambda: op(q)) == {"reference": 1}
    # under jit: one count a trace, none a call
    jitted = jax.jit(op)
    assert delta(lambda: (jitted(q), jitted(q))[1]) == {"reference": 1}
    # K/V that do not fit: the scan, whatever the device
    with monkeypatch.context() as m:
        m.setattr(A, "_VMEM_KV_BYTES", 1024)
        assert delta(lambda: op(q)) == {"scan": 1}
    # a TPU (the kernel in interpret mode here): the kernel
    fwd = A._flash_forward_pallas
    monkeypatch.setattr(A, "on_tpu", lambda: True)
    monkeypatch.setattr(A, "_flash_forward_pallas",
                        lambda *a, interpret=False, **kw: fwd(*a, interpret=True, **kw))
    assert delta(lambda: op(q)) == {"kernel": 1}


def _pallas_eqn(jaxpr):
    """The one kernel of a traced builder, where its call sits: in the
    jaxpr, or (several heads a grid step) in the jitted function the call
    sites of a shape share."""
    (eqn,) = [e for e in _inner_eqns(jaxpr) if e.primitive.name == "pallas_call"]
    return eqn


def _inner_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _inner_eqns(sub)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_fwd_kernel_lse_row_and_operand_dtype(causal):
    """What the forward kernel declares, read from its jaxpr so that neither
    can slide back unseen: lse leaves as a (B*H, 1, Tq) float32 row (4 bytes
    a query, no lane-broadcast copy), and for bf16 inputs both matmuls take
    bf16 operands and accumulate in float32."""
    B, H, T, D, Dv = 2, 3, 320, 32, 16  # 320 pads to 384 = 3 x 128
    q = jnp.zeros((B, H, T, D), jnp.bfloat16)
    v = jnp.zeros((B, H, T, Dv), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda q_, k_, v_: A._flash_forward_pallas(
        q_, k_, v_, None, causal, 0.125, 128, 128, interpret=True))(q, q, v)
    call = _pallas_eqn(jaxpr.jaxpr)
    out, lse = [x.aval for x in call.outvars]
    assert (out.shape, out.dtype) == ((B * H, 384, Dv), jnp.bfloat16)
    assert (lse.shape, lse.dtype) == ((B * H, 1, 384), jnp.float32)
    # nothing 128 lanes wide comes out, and nothing is sliced to a lane
    assert not [e for e in jaxpr.jaxpr.eqns
                for x in e.outvars if x.aval.shape[-1:] == (A._LSE_LANES,)]
    dots = [e for e in _inner_eqns(call.params["jaxpr"])
            if e.primitive.name == "dot_general"]
    assert len(dots) >= 2
    for e in dots:
        assert [x.aval.dtype for x in e.invars] == [jnp.bfloat16] * 2
        assert e.params["preferred_element_type"] == jnp.float32
    # no tile is upcast whole: float32 values come from the products alone
    casts = [e for e in _inner_eqns(call.params["jaxpr"])
             if e.primitive.name == "convert_element_type"
             and e.params["new_dtype"] == jnp.float32
             and e.invars[0].aval.shape[-2:] in ((128, D), (128, Dv))]
    # the Q block alone, once a grid step, for the exact scale
    assert len(casts) == 1


@with_seed()
def test_bert_mlm_weight_tying():
    net = model_zoo.bert_3_64_2(dropout=0.0)
    net.initialize()
    embed_w = net.word_embed.weight
    dec_w = net.mlm_decoder.weight
    assert embed_w is dec_w  # literally the same Parameter


@with_seed()
def test_bert_export_symbol_block(tmp_path):
    """BERT must trace symbolically (shape-free hybrid_forward)."""
    from mxnet_tpu import symbol as sym
    from mxnet_tpu import gluon

    net = model_zoo.bert_3_64_2(dropout=0.0)
    net.initialize()
    tokens = nd.array(np.random.RandomState(0).randint(0, 1000, (2, 8)))
    types = nd.zeros((2, 8))
    s0, p0 = net(tokens, types)
    data = sym.Variable("data")
    ttypes = sym.Variable("token_types")
    out = net(data, ttypes)  # symbolic trace
    g = sym.Group(list(out))
    args = g.list_arguments()
    assert "data" in args and "token_types" in args
    blk = gluon.SymbolBlock(g, [data, ttypes])
    for name, p in net.collect_params().items():
        if name in blk.params:
            blk.params[name].set_data(p.data())
    s1, p1 = blk(tokens, types)
    assert_almost_equal(s0.asnumpy(), s1.asnumpy(), rtol=1e-4, atol=1e-5)
    assert_almost_equal(p0.asnumpy(), p1.asnumpy(), rtol=1e-4, atol=1e-5)


# -- a selection mask, and grouped heads ------------------------------------------
def _grouped_case(group, masked, dtype="float32", B=2, Hkv=2, T=96, D=16):
    """q of ``group`` query heads a K/V head, and a mask that is data: half
    the pairs at random, every row keeping its own position."""
    rng = np.random.RandomState(11)

    def arr(*shape):
        return jnp.asarray(rng.normal(size=shape).astype("f4")).astype(dtype)

    q, do = arr(B, Hkv * group, T, D), arr(B, Hkv * group, T, D)
    k, v = arr(B, Hkv, T, D), arr(B, Hkv, T, D)
    mask = None
    if masked:
        mask = jnp.asarray(rng.rand(B, T, T) < 0.5) | jnp.eye(T, dtype=bool)[None]
        mask = mask.astype(jnp.int8)
    return q, k, v, do, mask


def _masked_ref_grads(q, k, v, do, mask, causal, sm):
    f32 = jnp.float32
    out, vjp = jax.vjp(lambda q_, k_, v_: A._attention_reference(
        q_, k_, v_, None, causal, sm, mask), *(a.astype(f32) for a in (q, k, v)))
    return out, vjp(do.astype(f32))


@pytest.mark.parametrize("branch", ["kernels", "scan_chunked", "materialised", "op"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("group,masked", [(1, True), (8, False), (8, True)])
def test_selection_mask_and_grouped_heads_in_every_branch(group, masked, causal,
                                                          branch, monkeypatch):
    """A mask for (query, key) pairs, shared by a sequence's heads, and 8
    query heads a K/V head: forward and all three gradients of the kernels
    (interpret mode) and of every XLA branch against the reference, dk / dv
    summed over the group."""
    q, k, v, do, mask = _grouped_case(group, masked)
    sm = 0.25
    want, grads = _masked_ref_grads(q, k, v, do, mask, causal, sm)
    if branch == "kernels":
        out, lse = A._flash_forward_pallas(q, k, v, None, causal, sm, 32, 32,
                                           interpret=True, mask=mask)
        got = A._flash_backward_pallas(q, k, v, None, out, lse, do, causal, sm,
                                       32, 32, interpret=True, mask=mask)[:3]
    elif branch == "scan_chunked":  # a chunk that does not divide Tk
        out, lse = A._attention_scan_fwd(q, k, v, None, causal, sm, chunk=40,
                                         mask=mask)
        got = A._bwd_chunked(q, k, v, None, out, lse, do, causal, sm, chunk=40,
                             mask=mask)[:3]
    elif branch == "materialised":
        out = A._attention_reference(q, k, v, None, causal, sm, mask)
        got = A._flash_bwd(causal, sm, (q, k, v, None, mask, out, None), do)
        assert got[3] is None and got[4] is None  # no bias, and the mask has none
        got = got[:3]
    else:  # the registered op, differentiated as a model differentiates it
        out, vjp = jax.vjp(lambda q_, k_, v_: A.flash_attention(
            q_, k_, v_, None, mask, causal=causal, sm_scale=sm), q, k, v)
        got = vjp(do)
    assert [g.shape for g in got] == [q.shape, k.shape, v.shape]
    assert_almost_equal(np.asarray(out), np.asarray(want), rtol=1e-5, atol=1e-5)
    _assert_grads_close(got, grads, 1e-4)


def test_grouped_heads_in_bfloat16_sum_the_group_in_float32():
    q, k, v, do, mask = _grouped_case(8, True, "bfloat16")
    want, grads = _masked_ref_grads(q, k, v, do, mask, True, 0.25)
    out, lse = A._flash_forward_pallas(q, k, v, None, True, 0.25, 32, 32,
                                       interpret=True, mask=mask)
    got = A._flash_backward_pallas(q, k, v, None, out, lse, do, True, 0.25, 32, 32,
                                   interpret=True, mask=mask)
    assert [g.dtype for g in got[:3]] == [q.dtype, k.dtype, v.dtype]
    _assert_grads_close(got[:3], grads, 2e-2)


def test_mask_and_heads_are_checked_and_a_sequence_scope_takes_neither():
    q, k, v, _, mask = _grouped_case(8, True)
    with pytest.raises(mx.base.MXNetError):
        A.flash_attention(q, k, v, None, mask[:, :, :-1])
    with pytest.raises(mx.base.MXNetError):
        A.flash_attention(q[:, :15], k, v)


def test_dense_equal_head_calls_trace_to_the_kernels_they_traced_to():
    """A call without a mask and with as many K/V heads as query heads lowers
    to the jaxpr it lowered to before either was built (``tests/data``: the
    text the parent commit gave, addresses and paths taken out): both
    kernels with a bias and with latent widths under ``causal``, and the op's
    gradient on the CPU (that one text re-recorded at PR 46: the XLA branches
    now compare indices for ``causal`` by the rule the kernels use,
    ``_visible``, where they built ``jnp.tril`` of ones; the four kernels'
    texts are the parent commit's still)."""
    import json
    import os
    import re

    def norm(txt):
        txt = re.sub(r"0x[0-9a-f]+", "0x", str(txt))
        txt = re.sub(r" at [^\s:]+\.py:\d+", " at FILE", txt)
        return re.sub(r"/[^\s'\"]+\.py(:\d+)?", "FILE", txt)

    bf, f32 = jnp.bfloat16, jnp.float32
    S = jax.ShapeDtypeStruct
    q, bias, lse = S((2, 3, 256, 64), bf), S((2, 1, 1, 256), f32), S((2, 3, 256), f32)
    ql, vl, lsel = S((1, 4, 1024, 192), bf), S((1, 4, 1024, 128), bf), S((1, 4, 1024), f32)
    qs = S((2, 2, 48, 16), f32)
    with jax.default_matmul_precision("default"):  # as the fixture was traced
        now = _dense_jaxprs(q, bias, lse, ql, vl, lsel, qs)
    path = os.path.join(os.path.dirname(__file__), "data", "flash_dense_jaxprs_pr31.json")
    with open(path) as f:
        before = json.load(f)
    assert set(now) == set(before)
    for name, jaxpr in now.items():
        assert norm(jaxpr) == before[name], name
    # and a mask or a group does change the kernels' programs
    m = S((2, 256, 256), jnp.int8)
    with jax.default_matmul_precision("default"):
        masked = jax.make_jaxpr(lambda q, k, v, b, m: A._flash_forward_pallas(
            q, k, v, b, False, 0.125, 128, 128, False, mask=m))(q, q, q, bias, m)
    assert norm(masked) != before["fwd_kernel_bias"]


def _dense_jaxprs(q, bias, lse, ql, vl, lsel, qs):
    return {
        "fwd_kernel_bias": jax.make_jaxpr(lambda q, k, v, b: A._flash_forward_pallas(
            q, k, v, b, False, 0.125, 128, 128, False))(q, q, q, bias),
        "fwd_kernel_causal_latent": jax.make_jaxpr(
            lambda q, k, v: A._flash_forward_pallas(
                q, k, v, None, True, 192 ** -0.5, 512, 512, False))(ql, ql, vl),
        "bwd_kernel_causal_latent": jax.make_jaxpr(
            lambda q, k, v, o, l, do: A._flash_backward_pallas(
                q, k, v, None, o, l, do, True, 192 ** -0.5, 512, 512, False))(
                    ql, ql, vl, vl, lsel, vl),
        "bwd_kernel_bias": jax.make_jaxpr(
            lambda q, k, v, b, o, l, do: A._flash_backward_pallas(
                q, k, v, b, o, l, do, False, 0.125, 256, 256, False))(
                    q, q, q, bias, q, lse, q),
        "op_grad_cpu": jax.make_jaxpr(jax.grad(
            lambda q, k, v: A.flash_attention(q, k, v, causal=True).sum(),
            argnums=(0, 1, 2)))(qs, qs, qs),
    }


def test_vmem_asked_for_follows_the_mask_and_the_group():
    # the cell's head: 8192 rows at 128 + 128; Q + dO are 4 MB, K + V 4 MB
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)  # noqa: E731
    q, kv = shape(1, 32, 8192, 128), shape(1, 4, 8192, 128)
    assert A._kv_fits_vmem(kv, kv) and A._qdo_fits_vmem(q, q)
    assert not A._qdo_fits_vmem(shape(1, 32, 8192 + 512, 128))
    dense = A._bwd_vmem_limit(8192, 128, 128, 512, 512, 2)
    masked = A._bwd_vmem_limit(8192, 128, 128, 512, 512, 2, mask=True, out_itemsize=4)
    assert 30e6 < dense < 32e6 and 41e6 < masked < 43e6
    assert masked - dense > 1.25 * 2 * 8192 * 512  # the mask's tiles, twice
    assert 29e6 < A._fwd_vmem_limit(8192, 128, 128, 512, 512, 2) < 31e6
    # what stood before is what it was
    assert A._bwd_vmem_limit(4096, 192, 128, 512, 512, 2) == 28508160
    assert A._bwd_vmem_limit(512, 64, 64, 512, 512, 2) is None


# -- several heads a grid step ----------------------------------------------------
_HEADS_SHAPES = {"s128": (4, 12, 128, 64), "s512": (2, 12, 512, 64)}
_one_head_cache = {}


def _heads_case(shape, dtype, with_bias):
    """Seeded inputs at a BERT cell's head shape (fewer sequences), the
    one-head kernels' outputs and the reference's, made once a case."""
    key = (shape, dtype, with_bias)
    if key not in _one_head_cache:
        B, H, T, D = _HEADS_SHAPES[shape]
        q, k, v, do, bias = _bwd_case(np.random.RandomState(12), B, H, T, T, D,
                                      dtype, with_bias)
        f32 = jnp.float32
        ref = A._attention_reference(q.astype(f32), k.astype(f32), v.astype(f32),
                                     bias, False, 0.125)
        out, lse = A._flash_forward_pallas(q, k, v, bias, False, 0.125, T, T,
                                           interpret=True, heads_per_step=1)
        grads = A._flash_backward_pallas(q, k, v, bias, out, lse, do, False, 0.125,
                                         T, T, interpret=True, heads_per_step=1)
        _one_head_cache[key] = (q, k, v, do, bias, ref,
                                _ref_grads(q, k, v, do, bias, False, 0.125),
                                out, lse, grads)
    return _one_head_cache[key]


def _cell_heads(shape):
    """Heads a step the rule gives the benchmark's cell of this head shape
    (forward, backward), read from the programs it traces to."""
    B = {"s128": 128, "s512": 32}[shape]
    return _traced_heads((B,) + _HEADS_SHAPES[shape][1:])


def _traced_heads(shape, causal=False, kv_heads=None, masked=False, bias=False,
                  dv=None, blocks=None):
    """(forward, backward) heads a grid step of the kernels a call of this
    shape traces to under the rule, with the blocks the dispatch gives it:
    the leading dimension of the Q block, checked against the grid."""
    B, H, T, D = shape
    bf = jnp.bfloat16
    S = jax.ShapeDtypeStruct
    q, kv = S(shape, bf), S((B, kv_heads or H, T, D), bf)
    v = S((B, kv_heads or H, T, dv or D), bf)
    o, lse = S((B, H, T, dv or D), bf), S((B, H, T), jnp.float32)
    b = S((B, 1, 1, T), jnp.float32) if bias else None
    m = S((B, T, T), jnp.int8) if masked else None
    from mxnet_tpu import tuning
    cfg = tuning.heuristic_attention(shape, T, "bfloat16", causal)
    bq, bk = blocks or (cfg["block_q"], cfg["block_k"])
    fwd = jax.make_jaxpr(lambda q, k, v, b, m: A._flash_forward_pallas(
        q, k, v, b, causal, D ** -0.5, bq, bk, False, mask=m))(q, kv, v, b, m)
    bq, bk = A._bwd_blocks(T, T)
    bwd = jax.make_jaxpr(lambda q, k, v, b, o, l, do, m: A._flash_backward_pallas(
        q, k, v, b, o, l, do, causal, D ** -0.5, bq, bk, False, mask=m))(
            q, kv, v, b, o, lse, o, m)
    heads = []
    for jaxpr in (fwd, bwd):
        call = _pallas_eqn(jaxpr.jaxpr)
        gm = call.params["grid_mapping"]
        g = gm.block_mappings[0].block_shape[0]
        g = getattr(g, "block_size", g)
        assert gm.grid[0] * g == B * H
        if g > 1:  # several heads live in what the compiler gives unasked
            params = dict(call.params["compiler_params"] or {})
            assert all(p.vmem_limit_bytes is None for p in params.values())
        heads.append(int(g))
    return tuple(heads)


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("with_bias", [False, True])
@pytest.mark.parametrize("heads", [2, 4, "cell"])
@pytest.mark.parametrize("shape", sorted(_HEADS_SHAPES))
def test_several_heads_a_grid_step_vs_reference(shape, heads, with_bias, dtype, tol):
    """Both kernels (interpret mode) with 2, 4 and the cell's own count of
    heads a grid step at BERT's two head shapes, with and without a key
    bias: against the reference and its gradient, and every output equal to
    the one-head kernel's to the last bit (a head's arithmetic is what it
    is alone), lse among them."""
    B, H, T, D = _HEADS_SHAPES[shape]
    q, k, v, do, bias, ref, ref_grads, out1, lse1, grads1 = _heads_case(
        shape, dtype, with_bias)
    gf, gb = _cell_heads(shape) if heads == "cell" else (heads, heads)
    assert gf > 1 and gb > 1 and B * H % gf == 0 and B * H % gb == 0
    out, lse = A._flash_forward_pallas(q, k, v, bias, False, 0.125, T, T,
                                       interpret=True, heads_per_step=gf)
    assert_almost_equal(np.asarray(out.astype(jnp.float32)), np.asarray(ref),
                        rtol=tol, atol=tol)
    assert np.array_equal(np.asarray(lse), np.asarray(lse1))
    assert np.array_equal(np.asarray(out.astype(jnp.float32)),
                          np.asarray(out1.astype(jnp.float32)))
    got = A._flash_backward_pallas(q, k, v, bias, out, lse, do, False, 0.125,
                                   T, T, interpret=True, heads_per_step=gb)
    _assert_grads_close(got, ref_grads, tol)
    for g, g1 in zip(got, grads1):
        assert (g is None) == (g1 is None)
        if g is not None:
            assert np.array_equal(np.asarray(g.astype(jnp.float32)),
                                  np.asarray(g1.astype(jnp.float32)))


@pytest.mark.parametrize("case,call,expect", [
    # the two BERT cells, without and with a padding bias
    ("bert_s128", dict(shape=(128, 12, 128, 64)), (16, 16)),
    ("bert_s128_bias", dict(shape=(128, 12, 128, 64), bias=True), (16, 16)),
    ("bert_s512", dict(shape=(32, 12, 512, 64)), (4, 2)),
    # the deferred-shape forward of either: 12 grid steps
    ("batch1_s128", dict(shape=(1, 12, 128, 64)), (1, 1)),
    ("batch1_s512", dict(shape=(1, 12, 512, 64)), (1, 1)),
    # just under the grid's threshold, and at it
    ("grid_252", dict(shape=(21, 12, 128, 64)), (1, 1)),
    ("grid_256", dict(shape=(64, 4, 128, 64)), (16, 16)),
    # a head count the larger powers of two do not divide
    ("grid_12x25", dict(shape=(25, 12, 128, 64)), (4, 4)),
    # Kanana's latent attention, Keye's selected grouped-query attention
    ("kanana", dict(shape=(2, 32, 4096, 192), causal=True, dv=128), (1, 1)),
    ("keye", dict(shape=(1, 32, 8192, 128), causal=True, kv_heads=4,
                  masked=True), (1, 1)),
    # LFM2's grouped-query attention: 64-wide heads at 8192 rows, no mask
    ("lfm2", dict(shape=(1, 32, 8192, 64), causal=True, kv_heads=8), (1, 1)),
    # each of the conditions alone, at a shape that else qualifies
    ("causal", dict(shape=(128, 12, 128, 64), causal=True), (1, 1)),
    ("masked", dict(shape=(128, 12, 128, 64), masked=True), (1, 1)),
    ("grouped", dict(shape=(128, 12, 128, 64), kv_heads=4), (1, 1)),
    ("padded_100", dict(shape=(128, 12, 100, 64)), (1, 1)),
    ("two_q_blocks", dict(shape=(128, 12, 256, 64), blocks=(128, 256)), (1, 8)),
    ("two_kv_blocks", dict(shape=(128, 12, 256, 64), blocks=(256, 128)), (1, 8)),
])
def test_heads_per_step_rule_as_a_table(case, call, expect):
    """``_heads_per_step`` by the programs it gives: (forward, backward)
    heads a grid step at the shapes the benchmark's cells send and at each
    condition that keeps the one-head program."""
    assert _traced_heads(**call) == expect


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_kernel_body_does_not_grow_with_the_heads(kernel):
    """The heads of a step are walked by a loop inside the kernel: its jaxpr
    holds as many equations at 16 and at 32 heads a step as at 2, and a
    head's matmuls ONCE, however many heads are in flight (the lowering
    writes those out; a Python loop over the heads wrote the body out once
    a head, and every start paid the tracing of it at every call site)."""
    B, H, T, D = 8, 12, 128, 64
    S = jax.ShapeDtypeStruct
    q, lse = S((B, H, T, D), jnp.bfloat16), S((B, H, T), jnp.float32)

    def body(heads):
        if kernel == "fwd":
            jaxpr = jax.make_jaxpr(lambda q, k, v: A._flash_forward_pallas(
                q, k, v, None, False, 0.125, T, T, False,
                heads_per_step=heads))(q, q, q)
        else:
            jaxpr = jax.make_jaxpr(lambda q, k, v, o, l, do: A._flash_backward_pallas(
                q, k, v, None, o, l, do, False, 0.125, T, T, False,
                heads_per_step=heads))(q, q, q, q, lse, q)
        eqns = list(_inner_eqns(_pallas_eqn(jaxpr.jaxpr).params["jaxpr"]))
        return len(eqns), sum(e.primitive.name == "dot_general" for e in eqns)

    counts = {g: body(g) for g in (1, 2, 4, 16, 32)}
    assert counts[2][0] == counts[4][0] == counts[16][0] == counts[32][0]
    assert A._HEADS_IN_FLIGHT[128] == 2  # at these 128 rows
    # the matmuls of a head: 2 forward, 5 backward
    assert {c[1] for c in counts.values()} == {{"fwd": 2, "bwd": 5}[kernel]}
    assert counts[1][0] < counts[2][0]  # the two loops themselves


def test_forced_heads_want_a_plain_tile():
    q = jnp.zeros((2, 4, 128, 16), jnp.float32)
    with pytest.raises(mx.base.MXNetError):  # causal: heads differ in their work
        A._flash_forward_pallas(q, q, q, None, True, 0.25, 128, 128, True,
                                heads_per_step=2)
    with pytest.raises(mx.base.MXNetError):  # 3 does not divide 8
        A._flash_forward_pallas(q, q, q, None, False, 0.25, 128, 128, True,
                                heads_per_step=3)


# -- the fused projection in place ------------------------------------------------
def _cell_rows(shape):
    """Batch elements a grid step of the in-place kernels holds in the
    benchmark's cell of this length (``_HEADS_SHAPES``'s key)."""
    return A._in_place_rows({"s128": 128, "s512": 32}[shape],
                            _HEADS_SHAPES[shape][2])


_IN_PLACE_CASES = {
    # (B, T, H, D, rows a grid step forward / backward)
    "d64_h2_t128": (2, 128, 2, 64, (1, 1)),
    "d64_h4_t128": (4, 128, 4, 64, (2, 4)),
    "d64_h12_t128_cell": (4, 128, 12, 64, "s128"),
    "d64_h4_t256": (2, 256, 4, 64, (2, 1)),
    "d64_h12_t512_cell": (2, 512, 12, 64, "s512"),
    "d128_h3_t128": (2, 128, 3, 128, (1, 2)),
}


def _qkv_reference(qkv, do, bias, heads, sm):
    """out, lse and the cotangents of ``qkv`` and the bias by the plain
    formula on the turned operands, in float32."""
    f32 = jnp.float32
    B, T, width3 = qkv.shape
    D = width3 // 3 // heads

    def fn(x, b):
        q, k, v = A._heads_major(x, heads, D)
        out = A._attention_reference(q, k, v, b, False, sm)
        return jnp.reshape(jnp.transpose(out, (0, 2, 1, 3)), (B, T, -1))

    q, k, _ = A._heads_major(qkv.astype(f32), heads, D)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * sm
    if bias is not None:
        s = s + bias
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    out, vjp = jax.vjp(fn, qkv.astype(f32), bias)
    return (out, lse) + tuple(vjp(do.astype(f32)))


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-4), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("with_bias", [False, True])
@pytest.mark.parametrize("case", sorted(_IN_PLACE_CASES))
def test_in_place_kernels_vs_reference(case, with_bias, dtype, tol):
    """Both in-place kernels (interpret mode) against the reference on the
    turned operands: the output where the output projection reads it, lse,
    and the ONE cotangent of the fused projection, at a pair of 64-wide
    heads a 128-lane block (2, 4 and 12 heads) and at a 128-wide head a
    block, with and without a key bias, at the cells' own cut of a grid
    step."""
    B, T, H, D, rows = _IN_PLACE_CASES[case]
    if isinstance(rows, str):
        rows = (_cell_rows(rows), _cell_rows(rows))
        assert B % rows[0] == 0 and B % rows[1] == 0
    rng = np.random.RandomState(13)
    qkv = jnp.asarray(rng.normal(size=(B, T, 3 * H * D)).astype("f4")).astype(dtype)
    do = jnp.asarray(rng.normal(size=(B, T, H * D)).astype("f4")).astype(dtype)
    bias = A.make_padding_bias(jnp.asarray([T - 43] + [T] * (B - 1)), T) \
        if with_bias else None
    sm = D ** -0.5
    ref_out, ref_lse, ref_dqkv, ref_db = _qkv_reference(qkv, do, bias, H, sm)
    out, lse = A._qkv_forward_pallas(qkv, bias, H, sm, interpret=True,
                                     rows=rows[0])
    assert out.shape == (B, T, H * D) and out.dtype == qkv.dtype
    assert lse.shape == (B * H, 1, T) and lse.dtype == jnp.float32
    assert_almost_equal(np.asarray(out.astype(jnp.float32)), np.asarray(ref_out),
                        rtol=tol, atol=tol)
    assert_almost_equal(np.asarray(lse).reshape(B, H, T), np.asarray(ref_lse),
                        rtol=tol, atol=tol)
    dqkv, db = A._qkv_backward_pallas(qkv, bias, out, lse, do, H, sm,
                                      interpret=True, rows=rows[1])
    assert dqkv.shape == qkv.shape and dqkv.dtype == qkv.dtype
    scale = float(jnp.max(jnp.abs(ref_dqkv))) or 1.0
    assert_almost_equal(np.asarray(dqkv.astype(jnp.float32)) / scale,
                        np.asarray(ref_dqkv) / scale, rtol=tol, atol=tol)
    assert (db is None) == (bias is None)
    if bias is not None:
        assert db.shape == bias.shape and db.dtype == bias.dtype
        scale = float(jnp.max(jnp.abs(ref_db))) or 1.0
        assert_almost_equal(np.asarray(db) / scale, np.asarray(ref_db) / scale,
                            rtol=tol, atol=tol)


def _norm_jaxpr(jaxpr):
    import re

    txt = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    txt = re.sub(r" at [^\s:]+\.py:\d+", " at FILE", txt)
    return re.sub(r"/[^\s'\"]+\.py(:\d+)?", "FILE", txt)


def _parent_formula(qkv, bias, heads, causal):
    """``BERTSelfAttention.hybrid_forward`` between its two projections as
    it stood before ``flash_attention_qkv``: the operators it called."""
    from mxnet_tpu.ops import matrix as M

    D = qkv.shape[2] // 3 // heads
    x = M.reshape(qkv, shape=(0, 0, 3, heads, D))
    q, k, v = M.split(x, num_outputs=3, axis=2, squeeze_axis=True)
    q, k, v = (M.transpose(t, axes=(0, 2, 1, 3)) for t in (q, k, v))
    out = A.flash_attention(q, k, v, bias, causal=causal, sm_scale=D ** -0.5)
    return M.reshape(M.transpose(out, axes=(0, 2, 1, 3)), shape=(0, 0, -1))


@pytest.mark.parametrize("case,call,tpu,in_place", [
    # the two BERT cells, without and with a padding bias
    ("bert_s128", dict(shape=(128, 128, 12, 64)), True, True),
    ("bert_s128_bias", dict(shape=(128, 128, 12, 64), bias=True), True, True),
    ("bert_s512", dict(shape=(32, 512, 12, 64)), True, True),
    ("s256", dict(shape=(64, 256, 12, 64)), True, True),
    # a 128-wide head is a block of its own: any head count
    ("d128_h3", dict(shape=(128, 128, 3, 128)), True, True),
    # each of the conditions alone, at a shape that else qualifies
    ("causal", dict(shape=(128, 128, 12, 64), causal=True), True, False),
    ("odd_heads_at_64", dict(shape=(128, 128, 3, 64)), True, False),
    ("t_100", dict(shape=(128, 100, 12, 64)), True, False),
    ("t_384_three_backward_blocks", dict(shape=(64, 384, 12, 64)), True, False),
    ("t_1024_two_blocks", dict(shape=(32, 1024, 12, 64)), True, False),
    ("batch_1", dict(shape=(1, 128, 12, 64)), True, False),
    ("grid_252", dict(shape=(21, 128, 12, 64)), True, False),
    ("d_32", dict(shape=(128, 128, 12, 32)), True, False),
    ("sequence_scope", dict(shape=(128, 128, 12, 64), scope=True), True, False),
    ("cpu", dict(shape=(128, 128, 12, 64)), False, False),
])
def test_in_place_rule_as_a_table(case, call, tpu, in_place, monkeypatch):
    """Which ``flash_attention_qkv`` calls run the in-place kernels, by the
    programs they trace to: the cells' shapes do (both ``pallas_call``s
    under the names the trace reads, one (B, T, 3 x H x D) cotangent out
    of the backward's, no transpose anywhere); each condition that does not
    hold falls back to the jaxpr that ``flash_attention`` on turned operands
    traces to, letter for letter, forward and gradient."""
    import contextlib

    from mxnet_tpu import parallel, telemetry

    B, T, H, D = call["shape"]
    causal = call.get("causal", False)
    S = jax.ShapeDtypeStruct
    qkv = S((B, T, 3 * H * D), jnp.bfloat16)
    bias = S((B, 1, 1, T), jnp.float32) if call.get("bias") else None
    if tpu:
        monkeypatch.setattr(A, "on_tpu", lambda: True)
    scope = contextlib.nullcontext()
    if call.get("scope"):
        mesh = parallel.make_mesh((1,), ("sp",), devices=jax.devices()[:1])
        scope = parallel.sequence_scope(mesh, "sp")

    def new(x, b):
        return A.flash_attention_qkv(x, b, num_heads=H, causal=causal)

    def grad_of(fn):
        return jax.grad(lambda x, b: fn(x, b).astype(jnp.float32).sum())

    before = telemetry.flash_layouts()
    with scope:
        said = []
        jax.eval_shape(lambda x, b: said.append(
            A._in_place(x, H, D, b, causal)), qkv, bias)
        assert said == [in_place]
        if call.get("scope"):
            return  # the dispatch under a scope: tests/test_sequence_scope.py
        fwd = jax.make_jaxpr(new)(qkv, bias)
        bwd = jax.make_jaxpr(grad_of(new))(qkv, bias)
    after = telemetry.flash_layouts()
    layout = "in_place" if in_place else "heads_major"
    delta = {(k, l): n - before.get(k, {}).get(l, 0)
             for k, d in after.items() for l, n in d.items()
             if n != before.get(k, {}).get(l, 0)}
    # the forward is traced by both programs, the backward by the gradient's
    assert delta == {("fwd", layout): 2, ("bwd", layout): 1}
    if not in_place:
        old = lambda x, b: _parent_formula(x, b, H, causal)  # noqa: E731
        assert _norm_jaxpr(fwd) == _norm_jaxpr(jax.make_jaxpr(old)(qkv, bias))
        assert _norm_jaxpr(bwd) == _norm_jaxpr(
            jax.make_jaxpr(grad_of(old))(qkv, bias))
        return
    for jaxpr, name, outs in ((fwd, "flash_attention_fwd", [(B, T, H * D), (B * H, 1, T)]),
                              (bwd, "flash_attention_bwd", [(B, T, 3 * H * D)])):
        calls = [e for e in _inner_eqns(jaxpr.jaxpr)
                 if e.primitive.name == "pallas_call"]
        assert [e.params["name"] for e in calls][-1] == name
        assert [tuple(x.aval.shape) for x in calls[-1].outvars][:len(outs)] == outs
        # no copy stands between the projection and the kernels
        assert not [e for e in _inner_eqns(jaxpr.jaxpr)
                    if e.primitive.name in ("transpose", "concatenate", "split")
                    and e not in list(_inner_eqns(calls[-1].params["jaxpr"]))
                    and all(e not in list(_inner_eqns(c.params["jaxpr"])) for c in calls)]


@pytest.mark.parametrize("kernel", ["fwd", "bwd"])
def test_in_place_body_does_not_grow_with_the_heads(kernel):
    """``test_kernel_body_does_not_grow_with_the_heads``'s measure on the
    in-place kernels: a head's body is traced ONCE whatever the heads and
    batch elements a grid step holds (2 matmuls forward, 5 backward; the two
    heads of a 128-lane pair are two trips of the loop the lowering writes
    out, not two copies of the body)."""
    T, D = 128, 64
    S = jax.ShapeDtypeStruct

    def body(B, H, rows):
        qkv, o = S((B, T, 3 * H * D), jnp.bfloat16), S((B, T, H * D), jnp.bfloat16)
        lse = S((B * H, 1, T), jnp.float32)
        if kernel == "fwd":
            jaxpr = jax.make_jaxpr(lambda x: A._qkv_forward_pallas(
                x, None, H, 0.125, False, rows=rows))(qkv)
        else:
            jaxpr = jax.make_jaxpr(lambda x, o, l, do: A._qkv_backward_pallas(
                x, None, o, l, do, H, 0.125, False, rows=rows))(qkv, o, lse, o)
        eqns = list(_inner_eqns(_pallas_eqn(jaxpr.jaxpr).params["jaxpr"]))
        return len(eqns), sum(e.primitive.name == "dot_general" for e in eqns)

    counts = {body(8, 2, 1), body(8, 4, 2), body(8, 12, 1), body(8, 12, 8)}
    assert len(counts) == 1
    assert counts.pop()[1] == {"fwd": 2, "bwd": 5}[kernel]


def test_in_place_vmem_asked_for():
    """What the in-place kernels ask of scoped VMEM at the cells' shapes:
    nothing at 128 tokens, what a step of whole rows holds and a quarter
    more at 512."""
    assert (_cell_rows("s128"), _cell_rows("s512")) == (2, 1)
    for kernel in ("fwd", "bwd"):
        assert A._in_place_vmem_limit(kernel, 2, 128, 768, 12, 2, None) is None
    fwd = A._in_place_vmem_limit("fwd", 1, 512, 768, 12, 2, None)
    bwd = A._in_place_vmem_limit("bwd", 1, 512, 768, 12, 2, None)
    assert 16e6 < fwd < 16.5e6 and 24e6 < bwd < 24.5e6


class _ParentSelfAttention(model_zoo.bert.BERTSelfAttention):
    """``BERTSelfAttention`` with the ``hybrid_forward`` it had before
    ``flash_attention_qkv``: the plain formula the block is held to."""

    def hybrid_forward(self, F, x, bias=None):
        import math

        H = self._num_heads
        D = self._units // H
        qkv = self.qkv(x)  # (B, T, 3C)
        qkv = F.reshape(qkv, shape=(0, 0, 3, H, D))
        q, k, v = F.split(qkv, num_outputs=3, axis=2, squeeze_axis=True)
        q = F.transpose(q, axes=(0, 2, 1, 3))  # (B, H, T, D)
        k = F.transpose(k, axes=(0, 2, 1, 3))
        v = F.transpose(v, axes=(0, 2, 1, 3))
        out = F.flash_attention(q, k, v, bias, causal=self._causal,
                                sm_scale=1.0 / math.sqrt(D))
        out = F.transpose(out, axes=(0, 2, 1, 3))  # (B, T, H, D)
        out = F.reshape(out, shape=(0, 0, -1))
        out = self.proj(out)
        if self.dropout is not None:
            out = self.dropout(out)
        return out


def _block_pair(causal, units=32, heads=4):
    """The block and the parent's formula over the SAME parameters."""
    blk = model_zoo.bert.BERTSelfAttention(units, heads, causal=causal,
                                           prefix="attn_")
    blk.initialize(mx.init.Normal(0.5))
    old = _ParentSelfAttention(units, heads, causal=causal, prefix="attn_",
                               params=blk.collect_params())
    return blk, old


def _out_and_grads(blk, x, bias):
    params = [p for _, p in sorted(blk.collect_params().items())]
    with ag.record():
        out = blk(x, bias) if bias is not None else blk(x)
        loss = (out * out).sum()
    loss.backward()
    return out.asnumpy(), [p.grad().asnumpy().copy() for p in params]


@with_seed()
@pytest.mark.parametrize("mode", ["eager", "hybridized"])
@pytest.mark.parametrize("with_bias", [False, True])
@pytest.mark.parametrize("causal", [False, True])
def test_self_attention_block_equals_the_parent_formula(causal, with_bias, mode):
    """``BERTSelfAttention`` over ``flash_attention_qkv`` against the block
    as it stood (same parameters): the output and the gradient of every
    parameter to the last bit on the CPU, eager and hybridized, with and
    without a padding bias; ``causal`` is ``model_zoo/gpt.py``'s block."""
    blk, old = _block_pair(causal)
    rng = np.random.RandomState(3)
    x = nd.array(rng.normal(size=(2, 16, 32)).astype("f4"))
    bias = nd.array(np.asarray(A.make_padding_bias(
        jnp.asarray([11, 16]), 16))) if with_bias else None
    if mode == "hybridized":
        blk.hybridize()
        old.hybridize()
    want, want_grads = _out_and_grads(old, x, bias)
    got, got_grads = _out_and_grads(blk, x, bias)
    assert np.array_equal(got, want)
    assert len(got_grads) == len(want_grads) == 4
    for g, w in zip(got_grads, want_grads):
        assert np.abs(w).max() > 0 and np.array_equal(g, w)


@with_seed()
def test_self_attention_block_through_export_and_imports(tmp_path):
    """The block traces as a Symbol (``flash_attention_qkv`` is shape-free:
    the head count is its attribute) and comes back through ``export`` /
    ``SymbolBlock.imports`` with the output it had."""
    blk, _ = _block_pair(False)
    x = nd.array(np.random.RandomState(4).normal(size=(2, 16, 32)).astype("f4"))
    blk.hybridize()
    want = blk(x).asnumpy()
    sym_file, param_file = blk.export(str(tmp_path / "attn"))
    with open(sym_file) as f:
        assert '"flash_attention_qkv"' in f.read()
    loaded = gluon.SymbolBlock.imports(sym_file, ["data"], param_file)
    assert_almost_equal(loaded(x).asnumpy(), want, rtol=1e-5, atol=1e-5)


@with_seed()
def test_gpt_block_is_the_parent_formula_to_the_last_bit():
    """``model_zoo/gpt.py`` builds on ``BERTSelfAttention(causal=True)``:
    the operator falls back inside, and a GPT forward and its parameters'
    gradients equal those of the same net with the parent's formula in
    every block."""
    from mxnet_tpu.gluon.model_zoo import gpt

    net = gpt.gpt_mini(dropout=0.0)
    net.initialize(mx.init.Normal(0.05))
    ids = nd.array(np.random.RandomState(5).randint(0, 100, (2, 16)))

    def run():
        params = [p for _, p in sorted(net.collect_params().items())
                  if p.grad_req != "null"]
        with ag.record():
            out = net(ids)
            loss = (out * out).mean()
        loss.backward()
        return out.asnumpy(), [p.grad().asnumpy().copy() for p in params]

    got, got_grads = run()
    for blk in net.blocks:  # the parent's formula over the same parameters
        blk.attn.__class__ = _ParentSelfAttention
    want, want_grads = run()
    assert np.array_equal(got, want)
    for g, w in zip(got_grads, want_grads):
        assert np.array_equal(g, w)


def _interpreted(kernel_call):
    """An in-place ``pallas_call`` builder with ``interpret`` forced on."""
    def call(*args, interpret=False, **kwargs):
        return kernel_call(*args, interpret=True, **kwargs)
    return call


@with_seed()
@pytest.mark.parametrize("with_bias", [False, True])
def test_self_attention_block_in_place_equals_the_parent_formula(with_bias, monkeypatch):
    """The block with the in-place kernels ENGAGED (a TPU's dispatch, the
    kernels in interpret mode here; heads of 64 at 128 tokens) against the
    parent's formula over the same parameters, hybridized: the output and
    every parameter's gradient, and the layout counter says which ran."""
    from mxnet_tpu import telemetry

    blk, old = _block_pair(False, units=128, heads=2)
    rng = np.random.RandomState(6)
    x = nd.array(rng.normal(size=(2, 128, 128)).astype("f4"))
    bias = nd.array(np.asarray(A.make_padding_bias(
        jnp.asarray([90, 128]), 128))) if with_bias else None
    old.hybridize()
    want, want_grads = _out_and_grads(old, x, bias)
    monkeypatch.setattr(A, "on_tpu", lambda: True)
    monkeypatch.setattr(A, "_HEAD_MIN_GRID", 4)
    for name in ("_qkv_forward_pallas", "_qkv_backward_pallas"):
        monkeypatch.setattr(A, name, _interpreted(getattr(A, name)))
    before = telemetry.flash_layouts()
    blk.hybridize()
    got, got_grads = _out_and_grads(blk, x, bias)
    after = telemetry.flash_layouts()
    assert after["fwd"]["in_place"] > before.get("fwd", {}).get("in_place", 0)
    assert after["bwd"]["in_place"] > before.get("bwd", {}).get("in_place", 0)
    assert_almost_equal(got, want, rtol=1e-4, atol=1e-4)
    for g, w in zip(got_grads, want_grads):
        scale = np.abs(w).max()
        assert scale > 0
        assert_almost_equal(g / scale, w / scale, rtol=1e-4, atol=1e-4)
