"""TPU hardware smoke lane (run: ``MXT_TEST_TPU=1 python -m pytest -m tpu``).

Every test here executes on the real chip — no interpret mode, no CPU
forcing. This lane exists because round 2 shipped a Pallas kernel that was
correct under ``interpret=True`` but failed Mosaic lowering on hardware
(invalid BlockSpec); hardware-only failure modes must have hardware tests.

Models the reference's GPU test tier (SURVEY §4: tests/python/gpu re-runs
the op suite under a GPU context) at smoke-test size: flash attention
fwd/bwd vs the XLA reference, one hybridized ResNet step, one BERT step,
fused RNN, fused optimizer updates, and async sync-point semantics.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.tpu


@pytest.fixture(autouse=True)
def _tpu_only():
    from mxnet_tpu.context import on_tpu
    if not on_tpu():
        pytest.skip("no TPU backend available (got %s)"
                    % jax.default_backend())


def _maxerr(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


# ---------------------------------------------------------------------------
# flash attention on hardware
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("causal", [False, True])
def test_flash_fwd_hardware(causal):
    from mxnet_tpu.ops import attention as A
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(s, (2, 4, 384, 64), jnp.bfloat16)
               for s in jax.random.split(key, 3))
    out, _ = A._flash_forward_pallas(q, k, v, None, causal, 0.125,
                                     128, 128, interpret=False)
    ref = A._attention_reference(q, k, v, None, causal, 0.125)
    assert _maxerr(out, ref) < 2e-2  # bf16 inputs, f32 accumulation


def test_flash_fwd_bias_hardware():
    from mxnet_tpu.ops import attention as A
    key = jax.random.PRNGKey(1)
    q, k, v = (jax.random.normal(s, (2, 4, 384, 64), jnp.bfloat16)
               for s in jax.random.split(key, 3))
    bias = A.make_padding_bias(jnp.asarray([300, 150]), max_len=384)
    out, _ = A._flash_forward_pallas(q, k, v, bias, True, 0.125,
                                     128, 128, interpret=False)
    ref = A._attention_reference(q, k, v, bias, True, 0.125)
    assert _maxerr(out, ref) < 2e-2


def test_flash_fwd_ragged_seqlen_hardware():
    """T=300 is not a block multiple — exercises the padding path."""
    from mxnet_tpu.ops import attention as A
    key = jax.random.PRNGKey(2)
    q, k, v = (jax.random.normal(s, (2, 2, 300, 64), jnp.bfloat16)
               for s in jax.random.split(key, 3))
    out, _ = A._flash_forward_pallas(q, k, v, None, True, 0.125,
                                     128, 128, interpret=False)
    ref = A._attention_reference(q, k, v, None, True, 0.125)
    assert _maxerr(out, ref) < 2e-2


def test_ragged_paged_attention_hardware():
    """The serving decode kernel on real Mosaic: mixed ragged lengths,
    shuffled page table, both head-block widths vs the gather+dense
    reference (the round-2 lesson: interpret-green is not
    Mosaic-green, so the paged kernel gets its own hardware gate)."""
    from mxnet_tpu.ops import attention as A
    key = jax.random.PRNGKey(5)
    B, H, D, S, P = 4, 4, 128, 16, 40
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, H, D), jnp.float32)
    k_pages = jax.random.normal(ks[1], (P, S, H, D), jnp.float32)
    v_pages = jax.random.normal(ks[2], (P, S, H, D), jnp.float32)
    pt = jnp.asarray(np.random.RandomState(0).permutation(P)[
        :B * 8].reshape(B, 8), jnp.int32)
    cl = jnp.array([1, 17, 100, 128], jnp.int32)
    # float32 pools: at the chip's default matmul precision the kernel's
    # MXU dots round their inputs to bf16 (a one-key sequence returns
    # bf16(v): 7e-3 off on the first chip run), so the sharp comparison
    # of the paging and masking logic runs both sides at full precision
    for precision, tol in (("highest", 1e-3), ("default", 2e-2)):
        with jax.default_matmul_precision(precision):
            ref = A._paged_gather_reference(q, k_pages, v_pages, pt, cl,
                                            0.125)
            for block_h in (1, 4):
                out = A._paged_decode_pallas(q, k_pages, v_pages, pt, cl,
                                             0.125, block_h,
                                             interpret=False)
                assert _maxerr(out, ref) < tol, \
                    "block_h=%d at %s precision" % (block_h, precision)


def test_flash_lse_hardware():
    from mxnet_tpu.ops import attention as A
    key = jax.random.PRNGKey(3)
    q, k, v = (jax.random.normal(s, (1, 2, 256, 64), jnp.float32)
               for s in jax.random.split(key, 3))
    _, lse = A._flash_forward_pallas(q, k, v, None, False, 0.125,
                                     128, 128, interpret=False)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 0.125
    ref_lse = jax.scipy.special.logsumexp(scores, axis=-1)
    assert _maxerr(lse, ref_lse) < 2e-2


def test_flash_grads_hardware():
    from mxnet_tpu.ops import attention as A
    key = jax.random.PRNGKey(4)
    q, k, v = (jax.random.normal(s, (2, 4, 384, 64), jnp.bfloat16)
               for s in jax.random.split(key, 3))
    bias = A.make_padding_bias(jnp.asarray([384, 200]), max_len=384)

    def loss(q, k, v):
        o = A.flash_attention(q, k, v, bias=bias, causal=True)
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        o = A._attention_reference(q, k, v, bias, True,
                                   1.0 / np.sqrt(q.shape[-1]))
        return jnp.sum(o.astype(jnp.float32) ** 2)

    g = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)
    gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g, gr):
        assert _maxerr(a, b) < 1e-1  # bf16 grads


@pytest.mark.parametrize("shape,causal,with_bias", [
    ((32, 12, 512, 64), False, False),  # the benchmark's BERT cell
    ((2, 4, 300, 64), True, True),      # ragged, causal, dbias
])
def test_flash_bwd_kernel_hardware(shape, causal, with_bias):
    """The compiled backward kernel within 2e-2 of the reference's
    gradient (in units of its largest entry), and _flash_bwd takes it."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops import attention as A
    B, H, T, D = shape
    q, k, v, do = (jax.random.normal(s, shape, jnp.bfloat16)
                   for s in jax.random.split(jax.random.PRNGKey(6), 4))
    bias = None
    if with_bias:
        bias = A.make_padding_bias(
            jnp.asarray(np.linspace(T // 2, T, B).astype(np.int32)), T)
    sm = D ** -0.5
    f32 = jnp.float32
    out, lse = A._flash_forward_pallas(q, k, v, bias, causal, sm,
                                       128, 128, interpret=False)
    before = telemetry.flash_bwd_branches().get("kernel", 0)
    got = jax.jit(lambda *a: A._flash_bwd(causal, sm, a[:4] + (None,) + a[4:6],
                                          a[6]))(q, k, v, bias, out, lse, do)
    assert telemetry.flash_bwd_branches().get("kernel", 0) == before + 1
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(jax.grad(lambda q_, k_, v_: jnp.sum(
            A._attention_reference(q_.astype(f32), k_.astype(f32),
                                   v_.astype(f32), bias, causal, sm)
            * do.astype(f32)), argnums=(0, 1, 2)))(q, k, v)
    for g, r in zip(got, ref):
        assert _maxerr(g, r) < 2e-2 * float(jnp.max(jnp.abs(r)))


def test_flash_long_seq_chunked_hardware():
    """T long enough that K/V exceed the VMEM budget → lax.scan path."""
    from mxnet_tpu.ops import attention as A
    key = jax.random.PRNGKey(5)
    T = 20480  # 2*20480*64*2B = 5.2 MB > _VMEM_KV_BYTES (4 MB)
    q, k, v = (jax.random.normal(s, (1, 1, T, 64), jnp.bfloat16)
               for s in jax.random.split(key, 3))
    assert not A._kv_fits_vmem(k)
    out = A.flash_attention(q, k, v, causal=True)
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))


# ---------------------------------------------------------------------------
# framework paths on hardware
# ---------------------------------------------------------------------------
def test_resnet18_train_step_hardware():
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon import model_zoo

    mx.random.seed(0)
    net = model_zoo.get_model("resnet18_v1", classes=10)
    net.initialize()
    net.cast("bfloat16")
    x = nd.array(np.random.RandomState(0)
                 .uniform(-1, 1, (8, 3, 64, 64)).astype("f4"))
    x = x.astype("bfloat16")
    y = nd.array(np.random.RandomState(1).randint(0, 10, (8,)).astype("f4"))
    net(x)
    step = parallel.ShardedTrainStep(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.02, "momentum": 0.9})
    losses = [float(step(x, y).asnumpy()) for _ in range(6)]
    assert all(np.isfinite(losses))
    # optimizing, not just running (early bf16 steps can overshoot, so
    # check the best later loss rather than strict monotonicity)
    assert min(losses[1:]) < losses[0]


def test_bert_mini_train_step_hardware():
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu import autograd as ag
    from mxnet_tpu.gluon import model_zoo

    mx.random.seed(0)
    bert = model_zoo.bert.bert_3_64_2(use_classifier=False, dropout=0.0)
    bert.initialize()
    trainer = mx.gluon.Trainer(bert.collect_params(), "adam",
                               {"learning_rate": 1e-4})
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    rng = np.random.RandomState(0)
    x = nd.array(rng.randint(0, 1000, (4, 48)).astype("f4"))
    y = nd.array(rng.randint(0, 1000, (4, 48)).astype("f4"))
    with ag.record():
        seq, _ = bert(x, nd.zeros_like(x))
        out = bert.decode_mlm(seq)
        loss = loss_fn(out.reshape((-1, out.shape[-1])), y.reshape((-1,)))
        loss = loss.mean()
    loss.backward()
    trainer.step(1)
    assert np.isfinite(float(loss.asnumpy()))


def test_fused_rnn_hardware():
    from mxnet_tpu import nd
    from mxnet_tpu import autograd as ag
    from mxnet_tpu.gluon import rnn

    layer = rnn.LSTM(hidden_size=32, num_layers=2)
    layer.initialize()
    x = nd.array(np.random.RandomState(0)
                 .normal(size=(20, 4, 16)).astype("f4"))
    x.attach_grad()
    with ag.record():
        out = layer(x)
        loss = (out * out).sum()
    loss.backward()
    assert np.all(np.isfinite(out.asnumpy()))
    assert np.all(np.isfinite(x.grad.asnumpy()))


def test_fused_optimizer_update_hardware():
    """Fused adam_update on device matches the CPU-side numpy recipe."""
    from mxnet_tpu import nd
    w = nd.array(np.linspace(-1, 1, 64).astype("f4"))
    g = nd.array(np.linspace(1, -1, 64).astype("f4"))
    m = nd.zeros((64,))
    v = nd.zeros((64,))
    out = nd.adam_update(w, g, m, v, lr=0.1, beta1=0.9, beta2=0.999,
                         epsilon=1e-8)
    wn, gn = np.linspace(-1, 1, 64, dtype="f4"), np.linspace(
        1, -1, 64, dtype="f4")
    mn = 0.1 * gn
    vn = 0.001 * gn * gn
    exp = wn - 0.1 * mn / (np.sqrt(vn) + 1e-8)
    np.testing.assert_allclose(out.asnumpy(), exp, rtol=1e-5, atol=1e-6)


def test_hybridize_jit_cache_hardware():
    """hybridize() compiles once and reuses the executable on hardware."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd

    net = mx.gluon.nn.HybridSequential()
    net.add(mx.gluon.nn.Dense(64, activation="relu"),
            mx.gluon.nn.Dense(10))
    net.initialize()
    net.hybridize()
    x = nd.array(np.random.RandomState(0).normal(size=(8, 32)).astype("f4"))
    out1 = net(x)
    out2 = net(x)
    np.testing.assert_allclose(out1.asnumpy(), out2.asnumpy(), rtol=1e-6)


def test_asnumpy_sync_point_hardware():
    """asnumpy() is the sync point and round-trips device data exactly."""
    from mxnet_tpu import nd
    a = nd.array(np.arange(1024, dtype="f4").reshape(32, 32))
    b = (a * 2 + 1).reshape((16, 64))
    expected = (np.arange(1024, dtype="f4") * 2 + 1).reshape(16, 64)
    np.testing.assert_array_equal(b.asnumpy(), expected)


def test_batchnorm_custom_vjp_hardware():
    """Fused BN kernel (custom VJP) matches numpy fwd + finite-diff bwd."""
    from mxnet_tpu import nd
    from mxnet_tpu import autograd as ag

    rng = np.random.RandomState(0)
    x = rng.randn(8, 16, 6, 6).astype("f4")
    g = (rng.rand(16) + 0.5).astype("f4")
    b = rng.randn(16).astype("f4")
    xa, ga, ba = nd.array(x), nd.array(g), nd.array(b)
    for a in (xa, ga, ba):
        a.attach_grad()
    with ag.record():
        out, _, _ = nd.BatchNorm(xa, ga, ba, nd.zeros((16,)),
                                 nd.ones((16,)), fix_gamma=False,
                                 train_mode=True)
        loss = (out * out).sum()
    loss.backward()
    mean = x.mean(axis=(0, 2, 3))
    var = x.var(axis=(0, 2, 3))
    xh = (x - mean[None, :, None, None]) / \
        np.sqrt(var + 1e-5)[None, :, None, None]
    ref = xh * g[None, :, None, None] + b[None, :, None, None]
    assert np.abs(out.asnumpy() - ref).max() < 1e-2
    # dL/dbeta = sum(2*out) per channel — closed form for this loss
    db_ref = (2 * ref).sum(axis=(0, 2, 3))
    np.testing.assert_allclose(ba.grad.asnumpy(), db_ref, rtol=1e-2,
                               atol=1e-2)


def test_layernorm_custom_vjp_hardware():
    """Fused LN kernel matches numpy forward on the chip."""
    from mxnet_tpu import nd

    rng = np.random.RandomState(1)
    x = (rng.randn(4, 12, 64) * 3 + 5).astype("f4")
    g = (rng.rand(64) + 0.5).astype("f4")
    b = rng.randn(64).astype("f4")
    out = nd.LayerNorm(nd.array(x), nd.array(g), nd.array(b))
    mean = x.mean(-1, keepdims=True)
    var = x.var(-1, keepdims=True)
    ref = (x - mean) / np.sqrt(var + 1e-5) * g + b
    assert np.abs(out.asnumpy() - ref).max() < 1e-2


def test_nhwc_resnet_train_step_hardware():
    """Channels-last resnet trains on the chip via the layout scope."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon import model_zoo, nn

    mx.random.seed(0)
    with nn.layout_scope("NHWC"):
        net = model_zoo.get_model("resnet18_v1", classes=10)
    net.initialize()
    net.cast("bfloat16")
    x = nd.array(np.random.RandomState(0)
                 .uniform(-1, 1, (8, 64, 64, 3)).astype("f4"))
    x = x.astype("bfloat16")
    y = nd.array(np.random.RandomState(1).randint(0, 10, (8,)).astype("f4"))
    net(x)
    step = parallel.ShardedTrainStep(
        net, mx.gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.02, "momentum": 0.9})
    losses = [float(step(x, y).asnumpy()) for _ in range(6)]
    assert all(np.isfinite(losses))
    assert min(losses[1:]) < losses[0]


def test_native_recordio_feeds_device_hardware():
    """Native C++ record pipeline -> device batch round-trip."""
    import tempfile

    from mxnet_tpu import nd, native, recordio

    if not native.available():
        pytest.skip("no native toolchain")
    d = tempfile.mkdtemp()
    p = d + "/t.rec"
    w = recordio.MXRecordIO(p, "w")
    rows = [np.arange(i, i + 8, dtype=np.float32) for i in range(32)]
    for arr in rows:
        w.write(arr.tobytes())
    w.close()
    r = native.NativeRecordReader(p)
    offs, lens = r.scan()
    pf = native.NativePrefetcher(p, offs, lens, np.arange(32),
                                 num_threads=2, capacity=8)
    batch = np.stack([np.frombuffer(b, np.float32) for b in pf])
    dev = nd.array(batch)
    out = (dev * 2).asnumpy()
    np.testing.assert_allclose(out, batch * 2)


# ---------------------------------------------------------------------------
# train-tier convergence on hardware (SURVEY §4 tests/python/train analog)
# ---------------------------------------------------------------------------
def test_mnist_convergence_hardware():
    """LeNet trained to >=0.95 val accuracy ON THE CHIP in bounded steps.

    Real MNIST files aren't shippable in this environment (zero egress),
    so the task is synthetic-but-learnable 'digits': 10 fixed random
    prototypes + Gaussian noise. A broken optimizer step, loss, BN/pool
    lowering, or sync-point semantics fails this; random labels can't
    pass it. Accuracy is printed so the TPU-lane artifact records it."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd as ag, nd
    from mxnet_tpu.gluon import Trainer, nn

    rng = np.random.RandomState(0)
    # smooth prototypes (coarse 7x7 upsampled): conv/pool-friendly spatial
    # structure — pure per-pixel noise patterns defeat pooling layers
    protos = np.repeat(np.repeat(rng.rand(10, 1, 7, 7), 4, axis=2),
                       4, axis=3).astype("f4")

    def make(n, seed):
        r = np.random.RandomState(seed)
        y = r.randint(0, 10, (n,))
        x = protos[y] + r.normal(0, 0.35, (n, 1, 28, 28))
        return x.astype("f4"), y.astype("f4")

    xtr, ytr = make(2048, 1)
    xva, yva = make(512, 2)

    mx.random.seed(0)
    net = nn.HybridSequential(prefix="conv_mnist_")
    with net.name_scope():
        net.add(nn.Conv2D(16, kernel_size=5, activation="relu"),
                nn.MaxPool2D(2, 2),
                nn.Conv2D(32, kernel_size=5, activation="relu"),
                nn.MaxPool2D(2, 2),
                nn.Flatten(),
                nn.Dense(128, activation="relu"),
                nn.Dense(10))
    net.initialize()
    net.hybridize()
    trainer = Trainer(net.collect_params(), "adam",
                      {"learning_rate": 1e-3})
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()

    batch = 256
    acc = 0.0
    for epoch in range(12):  # bounded: 12 * 8 = 96 steps max
        order = np.random.RandomState(10 + epoch).permutation(len(xtr))
        for i in range(0, len(xtr), batch):
            idx = order[i:i + batch]
            x = nd.array(xtr[idx])
            y = nd.array(ytr[idx])
            with ag.record():
                loss = loss_fn(net(x), y)  # per-sample; step() normalizes
            loss.backward()
            trainer.step(len(idx))
        preds = net(nd.array(xva)).asnumpy().argmax(axis=1)
        acc = float((preds == yva).mean())
        print("epoch %d val_acc %.4f" % (epoch, acc), flush=True)
        if acc >= 0.97:
            break
    assert acc >= 0.95, "val accuracy %.4f below the train-tier bar" % acc


# ---------------------------------------------------------------------------
# round-4 additions: CTC scan kernel + wavefront LSTM parity on hardware
# ---------------------------------------------------------------------------
def test_ctc_loss_hardware():
    """The lax.scan alpha recursion compiles and matches the CPU-verified
    torch-parity values on chip (scan + take_along_axis + masked
    logaddexp is exactly the op mix Mosaic has rejected before)."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd

    rng = np.random.RandomState(0)
    T, N, C = 12, 3, 6
    logits = rng.randn(T, N, C).astype(np.float32)
    labels = np.array([[1, 2, 3, 2], [2, 2, 0, 0], [4, 1, 5, 3]],
                      dtype=np.float32)
    x = mx.nd.array(logits)
    x.attach_grad()
    with autograd.record():
        loss = mx.nd.CTCLoss(x, mx.nd.array(labels), blank_label="first")
    loss.backward()
    vals = loss.asnumpy()
    # CPU-verified torch ground truth for this exact seed/config
    np.testing.assert_allclose(
        vals, [10.896658, 19.76711, 11.33562], rtol=1e-3)
    g = x.grad.asnumpy()
    assert np.isfinite(g).all() and np.abs(g).sum() > 0


def test_wavefront_lstm_parity_hardware():
    """MXT_RNN_WAVEFRONT batches all layers' recurrent gemms per
    diagonal; outputs must match the sequential path on chip."""
    import os

    from mxnet_tpu.ops.rnn import rnn_op, rnn_param_size

    T, B, I, H, L = 16, 8, 32, 32, 3
    key = jax.random.PRNGKey(1)
    k1, k2, k3 = jax.random.split(key, 3)
    data = jax.random.normal(k1, (T, B, I), jnp.float32)
    params = jax.random.normal(
        k2, (rnn_param_size("lstm", I, H, num_layers=L),),
        jnp.float32) * 0.1
    state = jnp.zeros((L, B, H), jnp.float32)
    cell = jnp.zeros((L, B, H), jnp.float32)

    old = os.environ.get("MXT_RNN_WAVEFRONT")
    try:
        os.environ["MXT_RNN_WAVEFRONT"] = "0"
        seq = rnn_op(data, params, state, cell, mode="lstm",
                     state_size=H, num_layers=L)
        os.environ["MXT_RNN_WAVEFRONT"] = "1"
        wave = rnn_op(data, params, state, cell, mode="lstm",
                      state_size=H, num_layers=L)
    finally:
        if old is None:
            os.environ.pop("MXT_RNN_WAVEFRONT", None)
        else:
            os.environ["MXT_RNN_WAVEFRONT"] = old
    assert _maxerr(jnp.asarray(seq[0]), jnp.asarray(wave[0])) < 1e-4
    assert _maxerr(jnp.asarray(seq[1]), jnp.asarray(wave[1])) < 1e-4
    assert _maxerr(jnp.asarray(seq[2]), jnp.asarray(wave[2])) < 1e-4


# ---------------------------------------------------------------------------
# sparse tier on hardware (the row_sparse push/pull +
# sparse-optimizer path must be exercised on the chip lane, not only the
# CPU suite; ref: SURVEY §2.2 sparse row + §2.4 PullRowSparse)
# ---------------------------------------------------------------------------
def test_embedding_sparse_grad_train_step_hardware():
    """Embedding(sparse_grad) fwd/bwd + lazy sparse SGD on the chip:
    the gather fwd, row_sparse grad extraction, and touched-rows-only
    update all ride device buffers."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd, autograd as ag

    mx.random.seed(3)
    net = mx.gluon.nn.Embedding(512, 32, sparse_grad=True)
    net.initialize()
    tr = mx.gluon.Trainer(net.collect_params(), "sgd",
                          {"learning_rate": 0.5})
    ids = np.random.RandomState(0).randint(0, 512, (8, 16)).astype("f4")
    x = nd.array(ids)
    w_before = net.weight.data().asnumpy().copy()
    with ag.record():
        out = net(x)
        loss = (out * out).sum()
    loss.backward()
    g = net.weight.grad()
    assert g.stype == "row_sparse"
    touched = set(int(i) for i in g.indices.asnumpy())
    assert touched == set(int(i) for i in np.unique(ids))
    tr.step(1)
    w_after = net.weight.data().asnumpy()
    untouched = sorted(set(range(512)) - touched)
    np.testing.assert_array_equal(w_after[untouched], w_before[untouched])
    assert not np.allclose(w_after[sorted(touched)],
                           w_before[sorted(touched)])


def test_kvstore_row_sparse_pull_hardware():
    """row_sparse_pull + sparse push through a server-side optimizer,
    with every buffer on the chip."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd, sparse

    kv = mx.kv.create("local")
    w = np.arange(256 * 8, dtype="f4").reshape(256, 8)
    kv.init("emb", nd.array(w))
    out = sparse.zeros("row_sparse", (256, 8))
    rows = nd.array(np.array([3.0, 77.0, 200.0], "f4"))
    kv.row_sparse_pull("emb", out=out, row_ids=rows)
    np.testing.assert_array_equal(out.indices.asnumpy(), [3, 77, 200])
    np.testing.assert_array_equal(out.data.asnumpy(), w[[3, 77, 200]])

    kv.set_optimizer(mx.optimizer.SGD(learning_rate=1.0))
    gvals = np.full((2, 8), 0.5, "f4")
    kv.push("emb", sparse.row_sparse_array(
        (gvals, np.array([3, 200], "i8")), shape=(256, 8)))
    pulled = nd.zeros((256, 8))
    kv.pull("emb", out=pulled)
    pn = pulled.asnumpy()
    np.testing.assert_array_equal(pn[77], w[77])        # untouched
    np.testing.assert_allclose(pn[[3, 200]], w[[3, 200]] - 0.5, rtol=1e-6)


def test_sparse_adam_lazy_update_hardware():
    """Sparse Adam on chip: touched rows match the dense update,
    untouched rows (weight AND optimizer state) stay put — the
    reference's lazy-update contract."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd, sparse

    shape, rows = (128, 16), [5, 44, 91]
    w_s = nd.array(np.ones(shape, "f4"))
    w_d = nd.array(np.ones(shape, "f4"))
    gd = np.zeros(shape, "f4")
    gd[rows] = 0.25
    opt_s, opt_d = (mx.optimizer.Adam(learning_rate=0.1) for _ in range(2))
    st_s = opt_s.create_state(0, w_s)
    st_d = opt_d.create_state(0, w_d)
    opt_s.update(0, w_s, sparse.row_sparse_array(gd), st_s)
    opt_d.update(0, w_d, nd.array(gd), st_d)
    np.testing.assert_allclose(w_s.asnumpy()[rows], w_d.asnumpy()[rows],
                               rtol=1e-5, atol=1e-6)
    other = sorted(set(range(shape[0])) - set(rows))
    np.testing.assert_array_equal(w_s.asnumpy()[other],
                                  np.ones(shape, "f4")[other])


def test_quantized_conv_fc_hardware():
    """s8xs8->s32 conv + matmul on the MXU (ops/quantization.py): the
    int8 path must lower and match the f32 reference on chip."""
    import mxnet_tpu as mx
    from mxnet_tpu import nd

    rs = np.random.RandomState(0)
    x = nd.array(rs.randn(8, 16, 28, 28).astype(np.float32))
    W = rs.randn(32, 16, 3, 3).astype(np.float32)
    b = rs.randn(32).astype(np.float32)
    qx, xmn, xmx = nd.contrib.quantize_v2(x)
    qw, wmn, wmx = nd.contrib.quantize_v2(nd.array(W))
    acc, omn, omx = nd.contrib.quantized_conv(
        qx, qw, nd.array(b), xmn, xmx, wmn, wmx,
        kernel=(3, 3), num_filter=32, pad=(1, 1))
    assert acc.dtype == np.int32
    out = nd.contrib.dequantize(acc, omn, omx).asnumpy()
    ref = nd.Convolution(x, nd.array(W), nd.array(b), kernel=(3, 3),
                         num_filter=32, pad=(1, 1)).asnumpy()
    denom = np.abs(ref).max()
    assert np.abs(out - ref).max() / denom < 0.05, \
        np.abs(out - ref).max() / denom

    xf = nd.array(rs.randn(64, 256).astype(np.float32))
    Wf = rs.randn(128, 256).astype(np.float32)
    qxf, fmn, fmx = nd.contrib.quantize_v2(xf)
    qwf, gmn, gmx = nd.contrib.quantize_v2(nd.array(Wf))
    accf, fomn, fomx = nd.contrib.quantized_fully_connected(
        qxf, qwf, None, fmn, fmx, gmn, gmx, num_hidden=128, no_bias=True)
    outf = nd.contrib.dequantize(accf, fomn, fomx).asnumpy()
    reff = xf.asnumpy() @ Wf.T
    assert np.abs(outf - reff).max() / np.abs(reff).max() < 0.05
