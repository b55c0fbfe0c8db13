"""NDArray basics (modeled on tests/python/unittest/test_ndarray.py)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.test_utils import assert_almost_equal, with_seed


@with_seed()
def test_creation():
    a = nd.zeros((3, 4))
    assert a.shape == (3, 4)
    assert a.dtype == np.float32
    assert (a.asnumpy() == 0).all()
    b = nd.ones((2,), dtype="int32")
    assert b.dtype == np.int32
    c = nd.full((2, 2), 7.0)
    assert (c.asnumpy() == 7).all()
    d = nd.arange(0, 10, 2)
    assert_almost_equal(d, np.arange(0, 10, 2, dtype=np.float32))
    e = nd.array([[1, 2], [3, 4]])
    assert e.shape == (2, 2)


@with_seed()
def test_arithmetic():
    a = nd.array([[1.0, 2.0], [3.0, 4.0]])
    b = nd.array([[5.0, 6.0], [7.0, 8.0]])
    assert_almost_equal(a + b, np.array([[6, 8], [10, 12]]))
    assert_almost_equal(a - b, np.array([[-4, -4], [-4, -4]]))
    assert_almost_equal(a * b, np.array([[5, 12], [21, 32]]))
    assert_almost_equal(b / a, np.array([[5, 3], [7 / 3, 2]]))
    assert_almost_equal(a + 1, np.array([[2, 3], [4, 5]]))
    assert_almost_equal(1 - a, np.array([[0, -1], [-2, -3]]))
    assert_almost_equal(2 * a, np.array([[2, 4], [6, 8]]))
    assert_almost_equal(a ** 2, np.array([[1, 4], [9, 16]]))
    assert_almost_equal(-a, -a.asnumpy())
    assert_almost_equal(abs(-a), a.asnumpy())


@with_seed()
def test_broadcast_binary():
    a = nd.array(np.random.rand(3, 1))
    b = nd.array(np.random.rand(1, 4))
    assert (a + b).shape == (3, 4)
    assert_almost_equal(nd.broadcast_add(a, b), a.asnumpy() + b.asnumpy())
    assert_almost_equal(nd.broadcast_maximum(a, b),
                        np.maximum(a.asnumpy(), b.asnumpy()))


@with_seed()
def test_comparison_dtype():
    a = nd.array([1.0, 2.0, 3.0])
    b = nd.array([2.0, 2.0, 2.0])
    eq = (a == b)
    assert eq.dtype == np.float32  # reference returns input dtype, not bool
    assert_almost_equal(eq, np.array([0.0, 1.0, 0.0]))
    assert_almost_equal(a < b, np.array([1.0, 0.0, 0.0]))


@with_seed()
def test_mutation_and_views():
    a = nd.zeros((4, 4))
    a[1] = 1.0
    assert_almost_equal(a.asnumpy()[1], np.ones(4))
    a[2, 3] = 5.0
    assert a.asnumpy()[2, 3] == 5.0
    a[:, 0] = nd.array([9.0, 9.0, 9.0, 9.0])
    assert (a.asnumpy()[:, 0] == 9).all()
    # view read/write coherence (reference: slices share the Chunk)
    v = a[1:3]
    assert v.shape == (2, 4)
    a[1] = 7.0
    assert (v.asnumpy()[0] == 7).all()  # view sees base mutation
    v[0] = 3.0
    assert (a.asnumpy()[1] == 3).all()  # base sees view mutation


@with_seed()
def test_inplace_ops():
    a = nd.ones((2, 2))
    orig = a
    a += 1
    assert (a.asnumpy() == 2).all()
    assert orig is a
    a *= 3
    assert (a.asnumpy() == 6).all()
    a /= 2
    assert (a.asnumpy() == 3).all()


@with_seed()
def test_reshape_special_codes():
    a = nd.zeros((2, 3, 4))
    assert a.reshape((6, 4)).shape == (6, 4)
    assert a.reshape((-1,)).shape == (24,)
    assert a.reshape((0, -1)).shape == (2, 12)
    assert a.reshape((-2,)).shape == (2, 3, 4)
    assert a.reshape((-3, 4)).shape == (6, 4)
    b = nd.zeros((2, 8))
    assert b.reshape((0, -4, -1, 2)).shape == (2, 4, 2)
    assert b.reshape((0, -4, 2, 4)).shape == (2, 2, 4)


@with_seed()
def test_reduce():
    x = np.random.rand(2, 3, 4).astype(np.float32)
    a = nd.array(x)
    assert_almost_equal(nd.sum(a), x.sum())
    assert_almost_equal(nd.sum(a, axis=1), x.sum(axis=1))
    assert_almost_equal(nd.sum(a, axis=(0, 2), keepdims=True),
                        x.sum(axis=(0, 2), keepdims=True))
    assert_almost_equal(nd.sum(a, axis=1, exclude=True), x.sum(axis=(0, 2)))
    assert_almost_equal(nd.mean(a, axis=0), x.mean(axis=0))
    assert_almost_equal(nd.max(a, axis=2), x.max(axis=2))
    assert_almost_equal(a.sum(axis=1), x.sum(axis=1))  # method route


@with_seed()
def test_dot():
    x = np.random.rand(4, 5).astype(np.float32)
    y = np.random.rand(5, 6).astype(np.float32)
    assert_almost_equal(nd.dot(nd.array(x), nd.array(y)), x @ y, rtol=1e-4)
    assert_almost_equal(
        nd.dot(nd.array(x), nd.array(y.T), transpose_b=True), x @ y, rtol=1e-4
    )
    bx = np.random.rand(3, 4, 5).astype(np.float32)
    by = np.random.rand(3, 5, 2).astype(np.float32)
    assert_almost_equal(nd.batch_dot(nd.array(bx), nd.array(by)), bx @ by,
                        rtol=1e-4)


@with_seed()
def test_slicing_ops():
    x = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
    a = nd.array(x)
    assert_almost_equal(nd.slice(a, begin=(0, 1), end=(2, 3)), x[0:2, 1:3])
    assert_almost_equal(nd.slice_axis(a, axis=2, begin=1, end=3), x[:, :, 1:3])
    assert_almost_equal(nd.concat(a, a, dim=1), np.concatenate([x, x], 1))
    parts = nd.split(a, num_outputs=3, axis=1)
    assert len(parts) == 3
    assert_almost_equal(parts[1], x[:, 1:2, :])
    assert_almost_equal(nd.flip(a, axis=2), x[:, :, ::-1])
    assert_almost_equal(nd.tile(a, reps=(1, 2, 1)), np.tile(x, (1, 2, 1)))
    assert_almost_equal(nd.transpose(a, axes=(2, 0, 1)), x.transpose(2, 0, 1))
    assert_almost_equal(nd.expand_dims(a, axis=1), x[:, None])
    assert_almost_equal(a.flatten(), x.reshape(2, -1))


@with_seed()
def test_take_and_indexing_ops():
    x = np.random.rand(5, 3).astype(np.float32)
    a = nd.array(x)
    idx = nd.array([0, 4, 2], dtype="int32")
    assert_almost_equal(nd.take(a, idx), x[[0, 4, 2]])
    # clip mode
    idx2 = nd.array([-1, 10], dtype="int32")
    assert_almost_equal(nd.take(a, idx2), x[[0, 4]])
    oh = nd.one_hot(nd.array([0, 2], dtype="int32"), depth=3)
    assert_almost_equal(oh, np.eye(3, dtype=np.float32)[[0, 2]])
    p = nd.pick(a, nd.array([0, 1, 2, 0, 1]), axis=1)
    assert_almost_equal(p, x[np.arange(5), [0, 1, 2, 0, 1]])


@with_seed()
def test_ordering():
    x = np.random.rand(4, 6).astype(np.float32)
    a = nd.array(x)
    assert_almost_equal(nd.sort(a, axis=1), np.sort(x, axis=1))
    assert_almost_equal(nd.sort(a, axis=1, is_ascend=False),
                        -np.sort(-x, axis=1))
    tk = nd.topk(a, axis=1, k=2, ret_typ="value")
    assert_almost_equal(tk, -np.sort(-x, axis=1)[:, :2])


@with_seed()
def test_astype_and_cast():
    a = nd.array([1.5, 2.5])
    b = a.astype("int32")
    assert b.dtype == np.int32
    c = nd.cast(a, dtype="float64")
    assert c.dtype == np.float64
    d = a.astype("bfloat16")
    assert d.dtype.name.startswith("bfloat16") or d.dtype.itemsize == 2


@with_seed()
def test_save_load(tmp_path):
    fname = str(tmp_path / "arrays.bin")
    a = nd.array([1.0, 2.0])
    b = nd.array([[3.0]])
    nd.save(fname, [a, b])
    loaded = nd.load(fname)
    assert isinstance(loaded, list) and len(loaded) == 2
    assert_almost_equal(loaded[0], a.asnumpy())
    nd.save(fname, {"x": a, "y": b})
    loaded = nd.load(fname)
    assert set(loaded.keys()) == {"x", "y"}
    assert_almost_equal(loaded["y"], b.asnumpy())


@with_seed()
def test_random_basic():
    mx.random.seed(42)
    a = nd.random.uniform(0, 1, shape=(100,))
    assert a.shape == (100,)
    assert 0 <= a.asnumpy().min() and a.asnumpy().max() <= 1
    mx.random.seed(42)
    b = nd.random.uniform(0, 1, shape=(100,))
    assert_almost_equal(a, b)  # seeding reproduces
    n = nd.random.normal(0, 1, shape=(2000,))
    assert abs(float(n.asnumpy().mean())) < 0.15
    r = nd.random.randint(0, 10, shape=(50,))
    assert r.asnumpy().min() >= 0 and r.asnumpy().max() < 10


@with_seed()
def test_scalar_conversion():
    a = nd.array([3.5])
    assert float(a) == 3.5
    assert a.asscalar() == np.float32(3.5)
    with pytest.raises(ValueError):
        nd.zeros((2,)).asscalar()


@with_seed()
def test_context_and_copy():
    a = nd.ones((2, 2), ctx=mx.cpu())
    assert a.context.device_type == "cpu"
    b = a.copyto(mx.cpu(0))
    b[0, 0] = 5.0
    assert a.asnumpy()[0, 0] == 1.0  # copy, not alias
    c = a.as_in_context(mx.cpu(0))
    assert c is a  # same ctx returns self (reference behavior)


@with_seed()
def test_wait_and_waitall():
    a = nd.ones((8, 8))
    b = a * 2
    b.wait_to_read()
    nd.waitall()
    assert (b.asnumpy() == 2).all()


def test_wait_to_read_is_one_counted_block_until_ready(monkeypatch):
    """wait_to_read is jax.block_until_ready plus the host-sync counter —
    no jitted one-element read-back (one more compile per shape, and a
    gather on a sharded array)."""
    import jax

    from mxnet_tpu import profiler
    from mxnet_tpu.ndarray import ndarray as impl

    waited = []
    real = jax.block_until_ready
    monkeypatch.setattr(impl.jax, "block_until_ready",
                        lambda a: waited.append(a) or real(a))
    x = nd.ones((3, 5)) * 2
    s0 = profiler.host_sync_count()
    assert x.wait_to_read() is x
    assert profiler.host_sync_count() - s0 == 1
    assert len(waited) == 1 and waited[0] is x.data
    assert not hasattr(impl, "_sync_pick")
    s0 = profiler.host_sync_count()
    nd.zeros((0, 4)).wait_to_read()  # nothing to wait for, nothing counted
    assert profiler.host_sync_count() == s0
