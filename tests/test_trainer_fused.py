"""Fused (one-launch, donated) Trainer.step vs the eager per-param path.

The canonical Gluon loop (ref: gluon/trainer.py — step) must produce
identical numerics whether Trainer.step runs the fused donated XLA program
or the eager per-parameter updates; these tests pin that equivalence and
the eligibility/fallback edges.
"""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd as ag
from mxnet_tpu import nd
from mxnet_tpu import profiler
from mxnet_tpu.gluon import CachedTrainStep, Trainer, nn, train_step
from mxnet_tpu.gluon.trainer import _FusedUpdate


def _make_net(seed=0):
    mx.random.seed(seed)
    net = nn.HybridSequential(prefix="fused_")
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8),
                nn.Dense(4, in_units=16))
    net.initialize()
    net.hybridize()
    return net


def _train(net, trainer, steps=4, seed=0):
    rng = np.random.RandomState(seed)
    losses = []
    for _ in range(steps):
        x = nd.array(rng.uniform(-1, 1, (8, 8)).astype(np.float32))
        y = nd.array(rng.uniform(-1, 1, (8, 4)).astype(np.float32))
        with ag.record():
            out = net(x)
            loss = ((out - y) ** 2).mean()
        loss.backward()
        trainer.step(8)
        losses.append(float(loss.asnumpy()))
    return losses


def _weights(net):
    return {k: v.data().asnumpy() for k, v in net.collect_params().items()}


@pytest.mark.parametrize("optimizer,opt_params", [
    ("sgd", {"learning_rate": 0.1}),
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}),
    ("nag", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 1e-2}),
    ("adamw", {"learning_rate": 1e-2, "wd": 1e-2}),
    ("rmsprop", {"learning_rate": 1e-3}),
    ("rmsprop", {"learning_rate": 1e-3, "centered": True}),
    ("adagrad", {"learning_rate": 0.05, "wd": 1e-4}),
])
def test_fused_matches_eager(monkeypatch, optimizer, opt_params):
    net_f = _make_net()
    tr_f = Trainer(net_f.collect_params(), optimizer, dict(opt_params))
    _train(net_f, tr_f)
    assert tr_f._fused, "fused path should be eligible here"

    monkeypatch.setenv("MXT_FUSED_TRAINER", "0")
    net_e = _make_net()
    tr_e = Trainer(net_e.collect_params(), optimizer, dict(opt_params))
    _train(net_e, tr_e)
    assert tr_e._fused is False

    wf, we = _weights(net_f), _weights(net_e)
    assert wf.keys() == we.keys()
    for k in wf:
        np.testing.assert_allclose(wf[k], we[k], rtol=1e-5, atol=1e-6,
                                   err_msg=k)
    # optimizer step counters advanced identically
    assert tr_f._optimizer.num_update == tr_e._optimizer.num_update == 4


def test_fused_with_lr_scheduler(monkeypatch):
    from mxnet_tpu.lr_scheduler import FactorScheduler

    def run(env):
        if env is not None:
            monkeypatch.setenv("MXT_FUSED_TRAINER", env)
        net = _make_net()
        tr = Trainer(net.collect_params(), "sgd",
                     {"learning_rate": 0.5, "momentum": 0.9,
                      "lr_scheduler": FactorScheduler(step=2, factor=0.5)})
        _train(net, tr, steps=5)
        return _weights(net), tr

    wf, tr_f = run(None)
    assert tr_f._fused
    we, _ = run("0")
    for k in wf:
        np.testing.assert_allclose(wf[k], we[k], rtol=1e-5, atol=1e-6)


def test_fused_lr_mult(monkeypatch):
    def run(env):
        if env is not None:
            monkeypatch.setenv("MXT_FUSED_TRAINER", env)
        net = _make_net()
        for name, p in net.collect_params().items():
            if name.endswith("bias"):
                p.lr_mult = 0.0  # frozen biases exercise the static fold
        tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.2})
        _train(net, tr)
        return _weights(net)

    wf = run(None)
    we = run("0")
    for k in wf:
        np.testing.assert_allclose(wf[k], we[k], rtol=1e-5, atol=1e-6)
    # the frozen biases really didn't move
    net0 = _make_net()
    w0 = _weights(net0)
    for k in wf:
        if k.endswith("bias"):
            np.testing.assert_array_equal(wf[k], w0[k])


def test_fused_save_load_states_roundtrip(tmp_path):
    net = _make_net()
    tr = Trainer(net.collect_params(), "adam", {"learning_rate": 1e-2})
    _train(net, tr, steps=3)
    assert tr._fused
    fname = str(tmp_path / "trainer.states")
    tr.save_states(fname)

    net2 = _make_net()
    tr2 = Trainer(net2.collect_params(), "adam", {"learning_rate": 1e-2})
    _train(net2, tr2, steps=1)  # materialize states
    tr2.load_states(fname)
    # the fused program closed over the pre-load optimizer — must rebuild
    assert tr2._fused is None
    # update counts resumed from the checkpoint, not the stale object
    assert tr2._optimizer.num_update == tr._optimizer.num_update == 3
    for i, s in tr._updaters[0].states.items():
        s2 = tr2._updaters[0].states[i]
        np.testing.assert_allclose(s[0].asnumpy(), s2[0].asnumpy(),
                                   rtol=1e-6)
        np.testing.assert_allclose(s[1].asnumpy(), s2[1].asnumpy(),
                                   rtol=1e-6)
    # training continues through the fused path after a state load
    _train(net2, tr2, steps=1)


def test_fused_ineligible_falls_back():
    net = _make_net()
    # adadelta has no fused builder — must run eager and still train
    tr = Trainer(net.collect_params(), "adadelta", {"learning_rate": 1.0})
    losses = _train(net, tr)
    assert tr._fused is False
    assert np.isfinite(losses[-1])


def test_fused_no_per_step_retrace(monkeypatch):
    """Dynamic scalars (t, lr, rescale) are traced arguments, so the jit
    cache must stop growing after step 1 (step 0 compiles once; step 1
    recompiles once when the donated outputs re-enter as inputs) — a
    growing cache would mean a compile per step."""
    net = _make_net()
    tr = Trainer(net.collect_params(), "adam", {"learning_rate": 1e-2})
    _train(net, tr, steps=2)
    fused = tr._fused
    assert isinstance(fused, _FusedUpdate)
    steady = fused._jit._cache_size()
    _train(net, tr, steps=3, seed=1)
    assert fused._jit._cache_size() == steady <= 2


def test_tied_parameters_survive_donation():
    """Weight tying (params=other.params, the BERT MLM-decoder pattern)
    must register the tied Parameter in the borrowing block's
    collect_params(), so CachedOp passes it as a live input rather than
    baking it in as a constant — which dies as soon as the fused trainer
    donates the buffer."""
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import Block

    mx.random.seed(0)

    class Tied(Block):
        def __init__(self):
            super().__init__(prefix="tied_")
            with self.name_scope():
                self.embed = nn.Embedding(20, 8)
                self.decoder = nn.Dense(20, flatten=False, in_units=8,
                                        params=self.embed.params)

        def forward(self, x):
            return self.decoder(self.embed(x))

    net = Tied()
    net.initialize()
    # the tied weight must appear in the BORROWING block's params too
    tied_name = net.embed.weight.name
    assert net.decoder.weight is net.embed.weight  # actually tied
    assert tied_name in net.decoder.collect_params()
    assert len(net.collect_params()) == 2  # tied weight + decoder bias
    net.embed.hybridize()
    net.decoder.hybridize()
    tr = Trainer(net.collect_params(), "adam", {"learning_rate": 1e-2})
    x = nd.array(np.arange(6).reshape(2, 3).astype("f4"))
    y = nd.array(np.ones((2, 3), "f4"))
    loss_fn = mx.gluon.loss.SoftmaxCrossEntropyLoss()
    for _ in range(3):  # step 2+ would hit the deleted donated buffer
        with ag.record():
            loss = loss_fn(net(x), y).mean()
        loss.backward()
        tr.step(2)
    assert np.isfinite(float(loss.asnumpy()))


def test_tied_parameter_shape_mismatch_raises():
    from mxnet_tpu.gluon import Block

    mx.random.seed(0)
    with pytest.raises(mx.MXNetError, match="tied parameter"):
        class Bad(Block):
            def __init__(self):
                super().__init__(prefix="badtied_")
                with self.name_scope():
                    self.embed = nn.Embedding(20, 8)
                    # in_units=9 conflicts with the tied (20, 8) weight
                    self.decoder = nn.Dense(20, in_units=9,
                                            params=self.embed.params)

        Bad()


# ---------------------------------------------------------------------------
# CachedTrainStep — the whole canonical loop as ONE donated launch
# (gluon/train_step.py). Numerics must match record/backward/step exactly,
# including optimizer state and BatchNorm running stats; ineligible configs
# must fall back to the eager loop with identical results.
# ---------------------------------------------------------------------------
def _make_bn_net(seed=0):
    mx.random.seed(seed)
    net = nn.HybridSequential(prefix="fstep_")
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8),
                nn.BatchNorm(),
                nn.Dense(4, in_units=16))
    net.initialize()
    net.hybridize()
    return net


def _batches(steps=5, seed=0):
    rng = np.random.RandomState(seed)
    return [(nd.array(rng.uniform(-1, 1, (8, 8)).astype(np.float32)),
             nd.array(rng.uniform(-1, 1, (8, 4)).astype(np.float32)))
            for _ in range(steps)]


def _eager_loop(net, trainer, loss_fn, data):
    losses = []
    for x, y in data:
        with ag.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(x.shape[0])
        losses.append(loss.asnumpy())
    return losses


def _states_np(trainer):
    out = {}
    for i, s in trainer._updaters[0].states.items():
        leaves = s if isinstance(s, tuple) else (() if s is None else (s,))
        out[i] = [l.asnumpy() for l in leaves]
    return out


@pytest.mark.parametrize("optimizer,opt_params", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}),
    ("adam", {"learning_rate": 1e-2}),
])
def test_cached_train_step_matches_eager(optimizer, opt_params):
    loss_fn = mx.gluon.loss.L2Loss()
    data = _batches()

    net_f = _make_bn_net()
    tr_f = Trainer(net_f.collect_params(), optimizer, dict(opt_params))
    step = tr_f.fuse_step(net_f, loss_fn)
    losses_f = [step(x, y).asnumpy() for x, y in data]
    assert step.fused and step.fallback_reason is None

    net_e = _make_bn_net()
    tr_e = Trainer(net_e.collect_params(), optimizer, dict(opt_params))
    losses_e = _eager_loop(net_e, tr_e, loss_fn, data)

    for lf, le in zip(losses_f, losses_e):
        np.testing.assert_allclose(lf, le, rtol=1e-6, atol=1e-6)
    wf, we = _weights(net_f), _weights(net_e)
    assert wf.keys() == we.keys()
    for k in wf:  # includes BatchNorm running_mean/var aux state
        np.testing.assert_allclose(wf[k], we[k], rtol=1e-6, atol=1e-6,
                                   err_msg=k)
    sf, se = _states_np(tr_f), _states_np(tr_e)
    assert sf.keys() == se.keys()
    for i in sf:
        for a, b in zip(sf[i], se[i]):
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    assert tr_f._optimizer.num_update == tr_e._optimizer.num_update == 5


def test_cached_train_step_single_launch_per_step():
    """Fused steady state = EXACTLY one compiled execution per training
    step (the whole point of whole-step fusion: every launch is a host
    dispatch)."""
    loss_fn = mx.gluon.loss.L2Loss()
    net = _make_bn_net()
    tr = Trainer(net.collect_params(), "adam", {"learning_rate": 1e-2})
    step = tr.fuse_step(net, loss_fn)
    data = _batches(steps=5)
    step(*data[0]).wait_to_read()  # build + compile + base-key draw
    step(*data[1]).wait_to_read()
    c0 = profiler.launch_count()
    for x, y in data[2:]:
        step(x, y).wait_to_read()
    assert profiler.launch_count() - c0 == 3
    # ...and the eager loop pays strictly more per step
    net_e = _make_bn_net()
    tr_e = Trainer(net_e.collect_params(), "adam", {"learning_rate": 1e-2})
    _eager_loop(net_e, tr_e, loss_fn, data[:1])
    c1 = profiler.launch_count()
    _eager_loop(net_e, tr_e, loss_fn, data[1:2])
    assert profiler.launch_count() - c1 > 1


def test_cached_train_step_no_per_step_retrace():
    """Dynamic scalars (t, lr via scheduler, wd, rescale) are traced 0-d
    args — the jit cache must stop growing after the donated outputs
    re-enter as inputs once."""
    from mxnet_tpu.lr_scheduler import FactorScheduler

    loss_fn = mx.gluon.loss.L2Loss()
    net = _make_bn_net()
    tr = Trainer(net.collect_params(), "sgd",
                 {"learning_rate": 0.5, "momentum": 0.9,
                  "lr_scheduler": FactorScheduler(step=2, factor=0.5)})
    step = tr.fuse_step(net, loss_fn)
    data = _batches(steps=8)
    for x, y in data:
        step(x, y)
    assert step._jit._cache_size() <= 2


def test_cached_train_step_ineligible_falls_back():
    """Unsupported optimizer: no exception, results identical to the
    hand-written eager loop."""
    loss_fn = mx.gluon.loss.L2Loss()
    data = _batches()
    net_a = _make_bn_net()
    tr_a = Trainer(net_a.collect_params(), "adadelta",
                   {"learning_rate": 1.0})
    step = train_step(net_a, loss_fn, tr_a)
    losses_a = [step(x, y).asnumpy() for x, y in data]
    assert step.fused is False
    assert "AdaDelta" in step.fallback_reason

    net_b = _make_bn_net()
    tr_b = Trainer(net_b.collect_params(), "adadelta",
                   {"learning_rate": 1.0})
    losses_b = _eager_loop(net_b, tr_b, loss_fn, data)
    for la, lb in zip(losses_a, losses_b):
        np.testing.assert_array_equal(la, lb)
    wf, we = _weights(net_a), _weights(net_b)
    for k in wf:
        np.testing.assert_array_equal(wf[k], we[k], err_msg=k)


def test_cached_train_step_flag_off(monkeypatch):
    monkeypatch.setenv("MXT_FUSED_STEP", "0")
    loss_fn = mx.gluon.loss.L2Loss()
    net = _make_bn_net()
    tr = Trainer(net.collect_params(), "adam", {"learning_rate": 1e-2})
    step = tr.fuse_step(net, loss_fn)
    data = _batches(steps=2)
    for x, y in data:
        step(x, y)
    assert step.fused is False
    assert step.fallback_reason == "MXT_FUSED_STEP=0"
    assert tr._optimizer.num_update == 2  # the eager loop really trained


def test_cached_train_step_return_outputs():
    loss_fn = mx.gluon.loss.L2Loss()
    net = _make_bn_net()
    tr = Trainer(net.collect_params(), "adam", {"learning_rate": 1e-2})
    step = tr.fuse_step(net, loss_fn, return_outputs=True)
    x, y = _batches(steps=1)[0]
    loss, out = step(x, y)
    assert loss.shape == (8,) and out.shape == (8, 4)
    # outputs are the pre-update forward: match a replayed forward on the
    # pre-step weights
    net_e = _make_bn_net()
    tr_e = Trainer(net_e.collect_params(), "adam", {"learning_rate": 1e-2})
    with ag.record():
        out_e = net_e(x)
        loss_e = loss_fn(out_e, y)
    np.testing.assert_allclose(out.asnumpy(), out_e.asnumpy(),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(loss.asnumpy(), loss_e.asnumpy(),
                               rtol=1e-6, atol=1e-6)


def test_module_fused_update_matches_eager(monkeypatch, tmp_path):
    """Module.update rides FusedApply (same machinery/numerics as the
    gluon fused step) — results must match the eager per-param loop."""
    import mxnet_tpu.symbol as sym
    from mxnet_tpu.io import NDArrayIter
    from mxnet_tpu.module import Module

    def run(env):
        if env is not None:
            monkeypatch.setenv("MXT_FUSED_STEP", env)
        else:
            monkeypatch.delenv("MXT_FUSED_STEP", raising=False)
        mx.random.seed(0)
        rng = np.random.RandomState(0)
        x = rng.uniform(-1, 1, (32, 8)).astype(np.float32)
        y = rng.randint(0, 4, (32,)).astype(np.float32)
        data = sym.var("data")
        net = sym.FullyConnected(data, num_hidden=16, name="fc1")
        net = sym.Activation(net, act_type="relu")
        net = sym.FullyConnected(net, num_hidden=4, name="fc2")
        net = sym.SoftmaxOutput(net, name="softmax")
        mod = Module(net, data_names=("data",),
                     label_names=("softmax_label",))
        it = NDArrayIter(x, y, batch_size=8)
        mod.bind(data_shapes=it.provide_data,
                 label_shapes=it.provide_label)
        mod.init_params(initializer=mx.init.Uniform(0.05))
        mod.init_optimizer(optimizer="sgd", optimizer_params=(
            ("learning_rate", 0.1), ("momentum", 0.9)))
        for _ in range(2):
            it.reset()
            for batch in it:
                mod.forward(batch, is_train=True)
                mod.backward()
                mod.update()
        arg, aux = mod.get_params()
        return {k: v.asnumpy() for k, v in arg.items()}, mod

    wf, mod_f = run(None)
    assert mod_f._fused_update, "fused Module.update should be eligible"
    we, mod_e = run("0")
    assert mod_e._fused_update is False
    assert wf.keys() == we.keys()
    for k in wf:
        np.testing.assert_allclose(wf[k], we[k], rtol=1e-6, atol=1e-6,
                                   err_msg=k)


# ---------------------------------------------------------------------------
# DataLoader prefetch (gluon/data/dataloader.py — _DevicePrefetcher):
# prefetched batches must equal non-prefetched ones in value AND order.
# ---------------------------------------------------------------------------
def test_dataloader_prefetch_matches():
    from mxnet_tpu.gluon import data as gdata

    rng = np.random.RandomState(0)
    npx = rng.uniform(0, 1, (37, 3)).astype(np.float32)
    npy = np.arange(37).astype(np.float32)
    ds = gdata.ArrayDataset(npx, npy)

    def collect(**kw):
        return [(bx.asnumpy(), by.asnumpy())
                for bx, by in gdata.DataLoader(ds, batch_size=5, **kw)]

    plain = collect()
    assert len(plain) == 8
    for kw in ({"prefetch": 2},                          # serial load-ahead
               {"prefetch": 3, "prefetch_to_device": True},
               {"num_workers": 2, "prefetch_to_device": True}):
        got = collect(**kw)
        assert len(got) == len(plain), kw
        for (ax, ay), (bx, by) in zip(plain, got):
            np.testing.assert_array_equal(ax, bx)
            np.testing.assert_array_equal(ay, by)


def test_dataloader_ndarray_samples_batched_read():
    """NDArray samples batchify through ONE stacked device op — values
    and dtypes must match the per-sample numpy stacking it replaced."""
    from mxnet_tpu.gluon import data as gdata

    rng = np.random.RandomState(0)
    npx = rng.uniform(0, 1, (10, 3)).astype(np.float32)
    ds = gdata.SimpleDataset(
        [(nd.array(npx[i]), nd.array([float(i)])) for i in range(10)])
    batches = list(gdata.DataLoader(ds, batch_size=4))
    assert len(batches) == 3
    bx, by = batches[0]
    assert bx.dtype == np.float32 and bx.shape == (4, 3)
    np.testing.assert_allclose(bx.asnumpy(), npx[:4], rtol=1e-7)
    np.testing.assert_array_equal(
        by.asnumpy().ravel(), np.arange(4, dtype=np.float32))


def test_bf16_batchnorm_step_compiles_once():
    """A bf16-cast BatchNorm net keeps float32 running statistics (the op
    returns them float32), and the fused step pins its donated buffers
    before the first dispatch — so step 2 neither retraces nor compiles.
    Found on the way to the chip: a ResNet-50 step compiled twice."""
    from mxnet_tpu import tuning

    net = _make_bn_net()
    net.cast("bfloat16")
    params = net.collect_params()
    for name, p in params.items():
        want = "float32" if "running_" in name else "bfloat16"
        assert np.dtype(p.dtype).name == want, (name, p.dtype)
    x, y = _batches(steps=1)[0]
    x, y = x.astype("bfloat16"), y.astype("bfloat16")
    net(x)  # resolve BatchNorm's deferred shape before the trainer looks
    tr = Trainer(params, "sgd", {"learning_rate": 1e-2, "momentum": 0.9})
    step = tr.fuse_step(net, mx.gluon.loss.L2Loss())
    step(x, y).wait_to_read()  # build + the one compile
    c0, l0 = tuning.compile_stats()["compiles"], profiler.launch_count()
    step(x, y).wait_to_read()
    assert step.fused
    assert tuning.compile_stats()["compiles"] == c0
    assert profiler.launch_count() - l0 == 1
    for name, p in params.items():
        want = "float32" if "running_" in name else "bfloat16"
        assert str(p.data().dtype) == want, (name, p.data().dtype)
