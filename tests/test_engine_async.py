"""Async dispatch engine (mxnet_tpu/engine.py): ThreadedEngine semantics
over XLA — K-deep in-flight fused steps, deferred host reads, waitall as
the drain barrier, and the static host-sync lint.

The load-bearing properties:

- numerics are bit-exact at ANY window depth (the non-finite skip is
  compiled on-device; only host *bookkeeping* is deferred);
- the fused-step hot path performs <= 1 host sync per K steps;
- ``nd.waitall()`` / ``CheckpointManager`` drain the window, so counters
  and snapshots are consistent at every barrier.
"""
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd as ag
from mxnet_tpu import engine, metric, nd, profiler, resilience
from mxnet_tpu.gluon import Trainer, nn
from mxnet_tpu.ndarray.pending import PendingValue

_loss_fn = mx.gluon.loss.L2Loss()


@pytest.fixture(autouse=True)
def _drained():
    """Leave no in-flight tokens behind for the next test."""
    yield
    engine.wait_all()


def _make(opt, opt_args, seed=11, prefix="asy_"):
    mx.random.seed(seed)
    net = nn.HybridSequential(prefix=prefix)
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8),
                nn.Dense(4, in_units=16))
    net.initialize()
    net.hybridize()
    tr = Trainer(net.collect_params(), opt, dict(opt_args))
    return net, tr


def _batches(n, nan_at=None, seed=0):
    rng = np.random.RandomState(seed)
    out = []
    for t in range(n):
        x = rng.uniform(-1, 1, (8, 8)).astype(np.float32)
        y = rng.uniform(-1, 1, (8, 4)).astype(np.float32)
        if t == nan_at:
            x[0, 0] = np.nan
        out.append((nd.array(x), nd.array(y)))
    return out


def _weights(net):
    return [p.data().asnumpy().copy()
            for _, p in sorted(net.collect_params().items())]


# ---------------------------------------------------------------------------
# bit-exactness: async vs sync
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("opt,args", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 1e-2}),
])
@pytest.mark.parametrize("guard", ["0", "1"])
def test_async_vs_sync_bitexact(monkeypatch, opt, args, guard):
    """5+ steps through the fused path at window K=1 vs K=4: losses and
    weights match bit-exactly, guard on and off (with a NaN batch when
    the guard is on, so the deferred skip path is exercised)."""
    monkeypatch.setenv("MXT_SKIP_NONFINITE", guard)
    data = _batches(6, nan_at=3 if guard == "1" else None)

    def run(k):
        net, tr = _make(opt, args)
        step = tr.fuse_step(net, _loss_fn)
        losses = []
        with engine.bulk(k):
            for x, y in data:
                losses.append(step(x, y))
            nd.waitall()
        assert step.fused
        return ([l.asnumpy() for l in losses], _weights(net),
                tr._optimizer.num_update)

    l1, w1, n1 = run(1)
    l4, w4, n4 = run(4)
    assert n1 == n4 == (5 if guard == "1" else 6)
    for a, b in zip(l1, l4):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(w1, w4):
        np.testing.assert_array_equal(a, b)


def test_trainer_step_guarded_async_bitexact(monkeypatch):
    """The canonical record/backward/trainer.step loop with the guard on:
    the fused in-program guard + deferred flag matches the synchronous
    window bit-exactly, including the skip."""
    monkeypatch.setenv("MXT_SKIP_NONFINITE", "1")
    data = _batches(5, nan_at=2, seed=3)

    def run(k):
        net, tr = _make("sgd", {"learning_rate": 0.1, "momentum": 0.9})
        with engine.bulk(k):
            for x, y in data:
                with ag.record():
                    loss = _loss_fn(net(x), y)
                loss.backward()
                tr.step(8)
            nd.waitall()
        return _weights(net), tr._optimizer.num_update

    w1, n1 = run(1)
    w4, n4 = run(4)
    assert n1 == n4 == 4  # one skipped
    for a, b in zip(w1, w4):
        np.testing.assert_array_equal(a, b)
    assert resilience.skipped_step_count() >= 2


# ---------------------------------------------------------------------------
# host-sync accounting
# ---------------------------------------------------------------------------
def test_at_most_one_host_sync_per_window(monkeypatch):
    """With the guard on and K=4, 8 fused steps cost at most 8/K = 2
    framework host reads before the drain (the host_syncs gauge is the
    benchmark's ``host_syncs_per_step.train`` source)."""
    monkeypatch.setenv("MXT_SKIP_NONFINITE", "1")
    net, tr = _make("adam", {"learning_rate": 1e-2})
    step = tr.fuse_step(net, _loss_fn)
    (x, y), = _batches(1)
    step(x, y)
    nd.waitall()  # build + compile + land the first flag
    with engine.bulk(4):
        h0 = profiler.host_sync_count()
        for _ in range(8):
            step(x, y)
        mid = profiler.host_sync_count() - h0
        nd.waitall()
    assert mid <= 2, "expected <= 8/K deferred reads, saw %d" % mid
    assert profiler.gauge_value("dispatch_depth") == 0  # drained


def test_waitall_drains_bookkeeping(monkeypatch):
    """Counters lag while steps are in flight; nd.waitall() is the
    barrier that lands them (the chaos_matrix.sh contract)."""
    monkeypatch.setenv("MXT_SKIP_NONFINITE", "1")
    net, tr = _make("adam", {"learning_rate": 1e-2})
    step = tr.fuse_step(net, _loss_fn)
    data = _batches(6)
    with engine.bulk(8):
        for x, y in data:
            step(x, y)
        assert engine.inflight_depth() > 0
        nd.waitall()
        assert engine.inflight_depth() == 0
        assert tr._optimizer.num_update == 6


def test_bulk_is_the_real_knob():
    """set_bulk_size returns the previous effective depth and bulk()
    scopes it (the reference API, now load-bearing)."""
    prev = engine.set_bulk_size(8)
    assert engine.max_inflight() == 8
    assert engine.set_bulk_size(prev) == 8
    with engine.bulk(1):
        assert engine.max_inflight() == 1
    with engine.bulk(64):
        assert engine.max_inflight() == 15  # clamped to the mask width


def test_pending_value_protocol():
    """PendingValue defers the read, fires callbacks once, and counts
    exactly one host sync per materialization."""
    import jax.numpy as jnp

    pv = PendingValue(jnp.float32(4.0) * 2)
    fired = []
    pv.on_ready(fired.append)
    assert not pv.materialized
    h0 = profiler.host_sync_count()
    assert float(pv) == 8.0
    assert float(pv) == 8.0  # second read is free
    assert profiler.host_sync_count() - h0 == 1
    assert len(fired) == 1 and float(fired[0]) == 8.0
    late = []
    pv.on_ready(late.append)  # after materialization: fires immediately
    assert len(late) == 1


# ---------------------------------------------------------------------------
# metrics accumulate on device
# ---------------------------------------------------------------------------
def test_metric_device_accumulation_no_per_batch_sync():
    rng = np.random.RandomState(0)
    preds = [rng.uniform(0, 1, (16, 10)).astype(np.float32)
             for _ in range(4)]
    labels = [rng.randint(0, 10, (16,)).astype(np.float32)
              for _ in range(4)]

    acc = metric.Accuracy()
    loss_m = metric.Loss()
    dp = [nd.array(p) for p in preds]
    dl = [nd.array(l) for l in labels]
    h0 = profiler.host_sync_count()
    for p, l in zip(dp, dl):
        acc.update([l], [p])
        loss_m.update(None, [p])
    assert profiler.host_sync_count() == h0  # zero reads during update
    name, val = acc.get()  # the ONE deferred read
    assert profiler.host_sync_count() > h0

    ref = metric.Accuracy()
    for p, l in zip(preds, labels):
        ref.update([l], [p])  # numpy host path
    assert val == ref.get()[1]
    want = sum(float(p.sum()) for p in preds) / \
        sum(p.size for p in preds)
    assert abs(loss_m.get()[1] - want) < 1e-5
    # reset clears the device accumulator too
    acc.reset()
    assert acc.get()[1] != acc.get()[1]  # nan


# ---------------------------------------------------------------------------
# checkpoint drains the window
# ---------------------------------------------------------------------------
def test_kill_mid_window_resume_bitexact(monkeypatch, tmp_path):
    """Save with 5 steps in flight (guard on, K=8): CheckpointManager
    drains before snapshotting, so a 'killed' run resumed into FRESH
    objects continues bit-identically with an uninterrupted sync run."""
    monkeypatch.setenv("MXT_SKIP_NONFINITE", "1")
    data = _batches(8, nan_at=2, seed=5)

    # uninterrupted synchronous reference
    net_r, tr_r = _make("adam", {"learning_rate": 1e-2})
    step_r = tr_r.fuse_step(net_r, _loss_fn)
    with engine.bulk(1):
        for x, y in data:
            step_r(x, y)
        nd.waitall()

    # async run killed after 5 steps — none of them observed yet
    net_a, tr_a = _make("adam", {"learning_rate": 1e-2})
    step_a = tr_a.fuse_step(net_a, _loss_fn)
    mgr = resilience.CheckpointManager(tmp_path, net=net_a, trainer=tr_a)
    with engine.bulk(8):
        for x, y in data[:5]:
            step_a(x, y)
        assert engine.inflight_depth() > 0
        mgr.save(step=5)  # must drain: counts/weights/opt-state coherent
    assert tr_a._optimizer.num_update == 4  # 5 dispatched, 1 skipped

    # "kill" + resume into fresh objects, finish the schedule async
    net_b, tr_b = _make("adam", {"learning_rate": 1e-2}, seed=99)
    mgr_b = resilience.CheckpointManager(tmp_path, net=net_b, trainer=tr_b)
    state = mgr_b.resume()
    assert state is not None and state.step == 5
    step_b = tr_b.fuse_step(net_b, _loss_fn)
    with engine.bulk(4):
        for x, y in data[5:]:
            step_b(x, y)
        nd.waitall()

    for a, b in zip(_weights(net_r), _weights(net_b)):
        np.testing.assert_array_equal(a, b)
    assert tr_b._optimizer.num_update == tr_r._optimizer.num_update == 7


# ---------------------------------------------------------------------------
# profiler thread-safety (counters bumped from deferred-read callbacks)
# ---------------------------------------------------------------------------
def test_profiler_counters_thread_safe():
    n_threads, per_thread = 8, 2000
    l0 = profiler.launch_count()
    h0 = profiler.host_sync_count()
    ctr = profiler.Counter(None, "ts_regression", 0)

    def hammer():
        for _ in range(per_thread):
            profiler.record_launch()
            profiler.record_host_sync()
            ctr.increment()

    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * per_thread
    assert profiler.launch_count() - l0 == total
    assert profiler.host_sync_count() - h0 == total
    assert profiler.counter_value("ts_regression") == total


# ---------------------------------------------------------------------------
# CI: no new hot-path sync points
# ---------------------------------------------------------------------------
def test_static_host_sync_pass():
    """tools/check_host_syncs.py is clean — a new unmarked asnumpy()/
    float()/np.asarray() in the fused-step hot path fails tier-1."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    tool = os.path.join(root, "tools", "check_host_syncs.py")
    r = subprocess.run([sys.executable, tool, root],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stdout + r.stderr
