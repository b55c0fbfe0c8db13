"""int8 quantization (ref: tests/python/quantization/test_quantization.py;
ops in src/operator/quantization/*, API in contrib/quantization.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu import symbol as sym
from mxnet_tpu.contrib import quantization as qz
from mxnet_tpu.io import NDArrayIter
from mxnet_tpu.module import Module

RS = np.random.RandomState(7)


# ------------------------------------------------------------------- ops
def test_quantize_dequantize_roundtrip():
    x = nd.array(RS.randn(3, 17).astype(np.float32) * 4)
    q, mn, mx_ = nd.contrib.quantize_v2(x)
    assert q.dtype == np.int8
    back = nd.contrib.dequantize(q, mn, mx_)
    step = float(mx_.asnumpy()) / 127
    assert np.abs(back.asnumpy() - x.asnumpy()).max() <= step / 2 + 1e-7


def test_quantize_calibrated_range_clips():
    x = nd.array(np.array([[-10.0, 0.5, 3.0]], np.float32))
    q, mn, mx_ = nd.contrib.quantize_v2(x, min_calib_range=-4.0,
                                        max_calib_range=4.0)
    assert float(mn.asnumpy()) == -4.0 and float(mx_.asnumpy()) == 4.0
    assert q.asnumpy()[0, 0] == -127  # clipped, not wrapped


def test_quantized_fc_matches_f32():
    x = nd.array(RS.randn(5, 12).astype(np.float32))
    W = RS.randn(6, 12).astype(np.float32)
    b = RS.randn(6).astype(np.float32)
    qx, xmn, xmx = nd.contrib.quantize_v2(x)
    qw, wmn, wmx = nd.contrib.quantize_v2(nd.array(W))
    acc, omn, omx = nd.contrib.quantized_fully_connected(
        qx, qw, nd.array(b), xmn, xmx, wmn, wmx, num_hidden=6)
    assert acc.dtype == np.int32
    out = nd.contrib.dequantize(acc, omn, omx).asnumpy()
    ref = x.asnumpy() @ W.T + b
    assert np.abs(out - ref).max() / np.abs(ref).max() < 0.03


def test_quantized_conv_matches_f32():
    x = nd.array(RS.randn(2, 3, 8, 8).astype(np.float32))
    W = RS.randn(5, 3, 3, 3).astype(np.float32)
    b = RS.randn(5).astype(np.float32)
    qx, xmn, xmx = nd.contrib.quantize_v2(x)
    qw, wmn, wmx = nd.contrib.quantize_v2(nd.array(W))
    acc, omn, omx = nd.contrib.quantized_conv(
        qx, qw, nd.array(b), xmn, xmx, wmn, wmx,
        kernel=(3, 3), num_filter=5, pad=(1, 1))
    out = nd.contrib.dequantize(acc, omn, omx).asnumpy()
    ref_sym = nd.Convolution(x, nd.array(W), nd.array(b), kernel=(3, 3),
                             num_filter=5, pad=(1, 1)).asnumpy()
    assert np.abs(out - ref_sym).max() / np.abs(ref_sym).max() < 0.03


def test_requantize_to_calibrated_int8():
    x = nd.array(RS.randn(4, 9).astype(np.float32))
    qx, xmn, xmx = nd.contrib.quantize_v2(x)
    qw, wmn, wmx = nd.contrib.quantize_v2(nd.array(
        RS.randn(3, 9).astype(np.float32)))
    acc, amn, amx = nd.contrib.quantized_fully_connected(
        qx, qw, None, xmn, xmx, wmn, wmx, num_hidden=3, no_bias=True)
    ref = nd.contrib.dequantize(acc, amn, amx).asnumpy()
    cal = float(np.abs(ref).max())
    q8, rmn, rmx = nd.contrib.requantize(acc, amn, amx,
                                         min_calib_range=-cal,
                                         max_calib_range=cal)
    assert q8.dtype == np.int8
    out = nd.contrib.dequantize(q8, rmn, rmx).asnumpy()
    assert np.abs(out - ref).max() <= cal / 127 + 1e-6


def test_quantized_pooling_triple():
    x = nd.array(RS.randn(2, 4, 6, 6).astype(np.float32))
    q, mn, mx_ = nd.contrib.quantize_v2(x)
    p, pmn, pmx = nd.contrib.quantized_pooling(q, mn, mx_, kernel=(2, 2),
                                               stride=(2, 2),
                                               pool_type="max")
    assert p.dtype == np.int8 and p.shape == (2, 4, 3, 3)
    ref = nd.Pooling(nd.contrib.dequantize(q, mn, mx_), kernel=(2, 2),
                     stride=(2, 2), pool_type="max").asnumpy()
    out = nd.contrib.dequantize(p, pmn, pmx).asnumpy()
    np.testing.assert_allclose(out, ref, rtol=1e-5)


# ----------------------------------------------------------- graph level
def _lenet_symbol():
    data = sym.Variable("data")
    c1 = sym.Convolution(data, kernel=(3, 3), num_filter=8, pad=(1, 1),
                         name="conv1")
    a1 = sym.Activation(c1, act_type="relu", name="relu1")
    p1 = sym.Pooling(a1, kernel=(2, 2), stride=(2, 2), pool_type="max",
                     name="pool1")
    c2 = sym.Convolution(p1, kernel=(3, 3), num_filter=16, pad=(1, 1),
                         name="conv2")
    a2 = sym.Activation(c2, act_type="relu", name="relu2")
    p2 = sym.Pooling(a2, kernel=(2, 2), stride=(2, 2), pool_type="max",
                     name="pool2")
    f = sym.Flatten(p2, name="flat")
    fc1 = sym.FullyConnected(f, num_hidden=32, name="fc1")
    a3 = sym.Activation(fc1, act_type="relu", name="relu3")
    fc2 = sym.FullyConnected(a3, num_hidden=4, name="fc2")
    return sym.SoftmaxOutput(fc2, name="softmax")


def _proto_dataset(n, img=12, classes=4, noise=0.3, seed=42):
    """Learnable synthetic task: smooth, mutually-orthogonal per-class
    prototypes + noise (orthogonality guarantees separability, so the
    fp32 baseline trains to confident margins — without that, int8
    rounding collapses near-ties and the accuracy delta measures the
    task's noise, not the quantizer). Own RandomState: sharing the
    module-level RS made the data depend on test execution order."""
    coarse = np.linalg.qr(np.random.RandomState(0).randn(9, 9))[0][:classes]
    protos = []
    for c in range(classes):
        up = np.kron(coarse[c].reshape(3, 3) * 3.0,
                     np.ones((img // 3 + 1, img // 3 + 1)))
        protos.append(up[:img, :img])
    protos = np.stack(protos)
    r = np.random.RandomState(seed + n)
    y = r.randint(0, classes, n)
    x = protos[y] + noise * r.randn(n, img, img)
    return x[:, None].astype(np.float32), y.astype(np.float32)


def _train_fp32_lenet():
    # own seeds: the initialiser and the shuffle drew from the global streams,
    # so whether the baseline trained depended on which files ran before
    mx.random.seed(7)
    np.random.seed(7)
    X, y = _proto_dataset(768)
    it = NDArrayIter(X, y, batch_size=64, shuffle=True,
                     label_name="softmax_label")
    mod = Module(_lenet_symbol(), data_names=["data"],
                 label_names=["softmax_label"])
    mod.fit(it, num_epoch=4,
            optimizer="adam", optimizer_params={"learning_rate": 2e-3},
            eval_metric="acc")
    return mod


def _accuracy(symbol, args, auxs, X, y, batch=64):
    mod = Module(symbol, data_names=["data"], label_names=None)
    mod.bind(data_shapes=[("data", (batch,) + X.shape[1:])],
             for_training=False)
    mod.set_params(args, auxs, allow_missing=False)
    correct = 0
    for i in range(0, len(X) - batch + 1, batch):
        b = mx.io.DataBatch(data=[nd.array(X[i:i + batch])], label=None)
        mod.forward(b, is_train=False)
        pred = mod.get_outputs()[0].asnumpy().argmax(axis=1)
        correct += (pred == y[i:i + batch]).sum()
    return correct / (len(X) // batch * batch)


@pytest.fixture(scope="module")
def trained_lenet():
    mod = _train_fp32_lenet()
    arg, aux = mod.get_params()
    return mod._symbol, arg, aux


def test_quantize_model_accuracy_within_1pt(trained_lenet):
    symbol, arg, aux = trained_lenet
    Xv, yv = _proto_dataset(512)
    calib = NDArrayIter(Xv[:256], yv[:256], batch_size=64,
                        label_name="softmax_label")
    qsym, qarg, qaux = qz.quantize_model(
        symbol, arg, aux, calib_mode="naive", calib_data=calib,
        num_calib_examples=256, excluded_sym_names=())
    acc_f = _accuracy(symbol, arg, aux, Xv, yv)
    acc_q = _accuracy(qsym, qarg, qaux, Xv, yv)
    assert acc_f > 0.8, "fp32 baseline did not train (acc=%.3f)" % acc_f
    assert acc_f - acc_q <= 0.01 + 1e-9, (acc_f, acc_q)
    # the rewritten graph really runs int8 kernels
    ops = {n.op for n in qsym._topo_nodes() if not n.is_var()}
    assert "quantized_conv" in ops and "quantized_fully_connected" in ops
    assert "quantized_pooling" in ops  # pool rides the int8 triple


def test_quantize_model_entropy_calibration(trained_lenet):
    symbol, arg, aux = trained_lenet
    Xv, yv = _proto_dataset(320)
    calib = NDArrayIter(Xv[:192], yv[:192], batch_size=64,
                        label_name="softmax_label")
    qsym, qarg, qaux = qz.quantize_model(
        symbol, arg, aux, calib_mode="entropy", calib_data=calib,
        num_calib_examples=192)
    acc_f = _accuracy(symbol, arg, aux, Xv, yv)
    acc_q = _accuracy(qsym, qarg, qaux, Xv, yv)
    assert acc_f - acc_q <= 0.02 + 1e-9, (acc_f, acc_q)


def test_quantize_model_excluded_layer(trained_lenet):
    symbol, arg, aux = trained_lenet
    Xv, yv = _proto_dataset(128)
    calib = NDArrayIter(Xv, yv, batch_size=64,
                        label_name="softmax_label")
    qsym, qarg, qaux = qz.quantize_model(
        symbol, arg, aux, calib_mode="naive", calib_data=calib,
        excluded_sym_names=("fc2",))
    ops = [n for n in qsym._topo_nodes()
           if not n.is_var() and n.op == "FullyConnected"]
    assert len(ops) == 1 and ops[0].name == "fc2"
    assert "fc2_weight" in qarg  # stays f32


def test_quantized_symbol_json_roundtrip(trained_lenet, tmp_path):
    """A quantized graph survives Symbol JSON + binary params save/load
    (the deployment path)."""
    symbol, arg, aux = trained_lenet
    Xv, yv = _proto_dataset(128)
    calib = NDArrayIter(Xv, yv, batch_size=64, label_name="softmax_label")
    qsym, qarg, qaux = qz.quantize_model(
        symbol, arg, aux, calib_mode="naive", calib_data=calib)
    from mxnet_tpu.model import save_checkpoint, load_checkpoint
    save_checkpoint(str(tmp_path / "q"), 0, qsym, qarg, qaux)
    qsym2, qarg2, qaux2 = load_checkpoint(str(tmp_path / "q"), 0)
    a1 = _accuracy(qsym, qarg, qaux, Xv, yv)
    a2 = _accuracy(qsym2, qarg2, qaux2, Xv, yv)
    assert a1 == a2
    assert qarg2["conv1_weight_quantize"].dtype == np.int8


def test_dynamic_quantization_no_calib(trained_lenet):
    symbol, arg, aux = trained_lenet
    Xv, yv = _proto_dataset(128)
    qsym, qarg, qaux = qz.quantize_model(
        symbol, arg, aux, calib_mode="none")
    acc_f = _accuracy(symbol, arg, aux, Xv, yv)
    acc_q = _accuracy(qsym, qarg, qaux, Xv, yv)
    assert acc_f - acc_q <= 0.02 + 1e-9, (acc_f, acc_q)


def test_quantize_net_gluon_surface(tmp_path):
    """quantize_net: gluon block in, int8 SymbolBlock out
    (ref: contrib/quantization.py — quantize_net_v2)."""
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn as gnn

    net = gnn.HybridSequential()
    with net.name_scope():
        net.add(gnn.Conv2D(8, 3, padding=1, in_channels=1))
        net.add(gnn.Activation("relu"))
        net.add(gnn.MaxPool2D(2, 2))
        net.add(gnn.Flatten())
        net.add(gnn.Dense(4))
    net.initialize()
    X, y = _proto_dataset(128)
    net(mx.nd.array(X[:4]))  # shape init
    calib = NDArrayIter(X, y, batch_size=64, label_name="softmax_label")
    qnet = qz.quantize_net(net, calib_data=calib, calib_mode="naive",
                           num_calib_examples=128, tmpdir=str(tmp_path))
    ref = net(mx.nd.array(X[:64])).asnumpy()
    out = qnet(mx.nd.array(X[:64])).asnumpy()
    # int8 logits track the f32 block closely
    denom = np.abs(ref).max() or 1.0
    assert np.abs(out - ref).max() / denom < 0.05
    # the imported graph must actually carry int8 kernels — numeric
    # closeness alone would pass trivially for an unquantized graph
    kinds = {n.op for n in qnet._sb_symbol._topo_nodes()
             if not n.is_var()}
    assert "quantized_conv" in kinds
    assert "quantized_fully_connected" in kinds
