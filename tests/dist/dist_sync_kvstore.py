"""Worker script for the multi-process dist_sync test (models
tests/nightly/dist_sync_kvstore.py — run via tools/launch.py, each worker
pushes distinct values and asserts every worker converges to the same
summed state).

Run: python tools/launch.py -n 2 --launcher local \
         python tests/dist/dist_sync_kvstore.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import nd  # noqa: E402


def main():
    mx.parallel.init_distributed()
    kv = mx.kv.create("dist_sync")
    rank, nw = kv.rank, kv.num_workers
    assert nw == int(os.environ["MXT_NUM_WORKERS"]), (nw, os.environ)

    # 1) push/pull sync: each worker pushes rank+1; all must pull the sum
    kv.init("a", nd.zeros((4, 3)))
    kv.push("a", nd.full((4, 3), rank + 1.0))
    out = nd.zeros((4, 3))
    kv.pull("a", out=out)
    expect = sum(r + 1.0 for r in range(nw))
    np.testing.assert_allclose(out.asnumpy(), expect)

    # 2) trainer-level: identical weights on every worker after a step on
    # different per-worker data
    from mxnet_tpu import autograd as ag
    mx.random.seed(7)  # same init on every worker
    net = mx.gluon.nn.Dense(2)
    net.initialize()
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1}, kvstore=kv)
    rng = np.random.RandomState(100 + rank)  # different data per worker
    x = nd.array(rng.normal(size=(8, 5)).astype("f4"))
    with ag.record():
        loss = (net(x) ** 2).mean()
    loss.backward()
    trainer.step(8)
    w = net.weight.data().asnumpy()
    # gather every worker's weights; all rows must match
    from mxnet_tpu.parallel.sharded import allreduce_across_processes
    mean_w = allreduce_across_processes(nd.array(w / nw)).asnumpy()
    np.testing.assert_allclose(w, mean_w, rtol=1e-5, atol=1e-6)

    # 3) 2-bit gradient compression with error feedback across the ring
    kv2 = mx.kv.create("dist_sync")
    kv2.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    kv2.init("c", nd.zeros((2, 2)))
    kv2.push("c", nd.full((2, 2), 0.3))  # below threshold -> all-zero push
    out = nd.zeros((2, 2))
    kv2.pull("c", out=out)
    np.testing.assert_allclose(out.asnumpy(), 0.0)
    kv2.push("c", nd.full((2, 2), 0.3))  # residual 0.6 crosses 0.5
    kv2.pull("c", out=out)
    np.testing.assert_allclose(out.asnumpy(), 0.5 * nw)

    # 4) row_sparse over the wire (models the reference nightly's sparse
    # section, ref: kvstore_dist — PullRowSparseImpl): each worker pushes
    # different rows; the reduced store must hold the union, and
    # row_sparse_pull must return any requested row subset of it.
    from mxnet_tpu import sparse
    shape = (nw + 2, 3)
    kv3 = mx.kv.create("dist_sync")
    kv3.init("rs", nd.zeros(shape))
    rows = np.array([rank, rank + 2], np.int64)  # overlaps neighbors
    vals = np.full((2, 3), rank + 1.0, "f4")
    kv3.push("rs", sparse.row_sparse_array((vals, rows), shape=shape))
    expect = np.zeros(shape, "f4")
    for r in range(nw):
        expect[[r, r + 2]] += r + 1.0
    dense_out = nd.zeros(shape)
    kv3.pull("rs", out=dense_out)
    np.testing.assert_allclose(dense_out.asnumpy(), expect, rtol=1e-6)
    # union of every worker's touched rows
    union = np.unique(np.concatenate(
        [np.array([r, r + 2]) for r in range(nw)]))
    rs_out = sparse.zeros("row_sparse", shape)
    kv3.row_sparse_pull("rs", out=rs_out, row_ids=nd.array(
        union.astype("f4")))
    np.testing.assert_array_equal(rs_out.indices.asnumpy(), union)
    np.testing.assert_allclose(rs_out.data.asnumpy(), expect[union],
                               rtol=1e-6)
    # a single worker's own-row view pulls just those rows
    rs_own = sparse.zeros("row_sparse", shape)
    kv3.row_sparse_pull("rs", out=rs_own, row_ids=nd.array(
        rows.astype("f4")))
    np.testing.assert_allclose(rs_own.data.asnumpy(), expect[rows],
                               rtol=1e-6)

    print("DIST_PASS rank=%d/%d" % (rank, nw), flush=True)


if __name__ == "__main__":
    main()
