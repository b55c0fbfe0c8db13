"""Native C++ RecordIO engine (mxnet_tpu/src/recordio.cc via native.py):
byte-format parity with the pure-Python reader, threaded prefetch order,
and the ImageRecordIter fast path. Skipped wholesale when no toolchain."""
import os

import numpy as np
import pytest

from mxnet_tpu import native, recordio

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native engine unavailable")


@pytest.fixture
def shard(tmp_path):
    p = str(tmp_path / "t.rec")
    rng = np.random.RandomState(0)
    payloads = [bytes(rng.randint(0, 256, rng.randint(1, 3000),
                                  dtype=np.uint8)) for _ in range(150)]
    w = recordio.MXRecordIO(p, "w")
    for pl in payloads:
        w.write(pl)
    w.close()
    return p, payloads


def test_scan_matches_python_walk(shard):
    p, payloads = shard
    r = native.NativeRecordReader(p)
    offs, lens = r.scan()
    assert len(offs) == len(payloads)
    assert list(lens) == [len(pl) for pl in payloads]
    # python reader sees records at offs - 8
    pr = recordio.MXRecordIO(p, "r")
    for i in (0, 1, 73, 149):
        pr.handle.seek(int(offs[i]) - 8)
        assert pr.read() == payloads[i]
    pr.close()


def test_random_and_sequential_reads(shard):
    p, payloads = shard
    r = native.NativeRecordReader(p)
    for i in (149, 0, 42):
        assert r.read(i) == payloads[i]
    r2 = native.NativeRecordReader(p)
    got = []
    while True:
        b = r2.read_next()
        if b is None:
            break
        got.append(b)
    assert got == payloads


def test_corrupt_magic_detected(tmp_path):
    p = str(tmp_path / "bad.rec")
    with open(p, "wb") as f:
        f.write(b"\x00" * 64)
    r = native.NativeRecordReader(p)
    with pytest.raises(RuntimeError, match="corrupt"):
        r.scan()


def test_prefetch_shuffled_order(shard):
    p, payloads = shard
    r = native.NativeRecordReader(p)
    offs, lens = r.scan()
    order = np.random.RandomState(1).permutation(len(payloads))
    pf = native.NativePrefetcher(p, offs, lens, order,
                                 num_threads=3, capacity=8)
    out = list(pf)
    assert [out[j] for j in range(len(order))] \
        == [payloads[i] for i in order]


def test_prefetch_early_stop(shard):
    p, payloads = shard
    r = native.NativeRecordReader(p)
    offs, lens = r.scan()
    pf = native.NativePrefetcher(p, offs, lens, np.arange(len(payloads)),
                                 num_threads=2, capacity=4)
    assert pf.pop() == payloads[0]
    pf.stop()  # must join workers without deadlock
    assert pf.pop() is None


def test_image_record_iter_uses_native(tmp_path):
    from mxnet_tpu.io import ImageRecordIter

    p = str(tmp_path / "img.rec")
    rng = np.random.RandomState(0)
    w = recordio.MXRecordIO(p, "w")
    for i in range(20):
        img = rng.randint(0, 255, (40, 40, 3), dtype=np.uint8)
        w.write(recordio.pack_img((0, float(i % 4), i, 0), img,
                                  img_fmt=".png"))
    w.close()

    it = ImageRecordIter(path_imgrec=p, data_shape=(3, 32, 32),
                         batch_size=5, shuffle=False,
                         preprocess_threads=3)
    assert it._native is not None  # fast path engaged
    batches = list(it)
    assert len(batches) == 4
    for b in batches:
        assert b.data[0].shape == (5, 3, 32, 32)
    labels = np.concatenate([b.label[0].asnumpy() for b in batches])
    np.testing.assert_array_equal(labels, np.arange(20) % 4)


def test_record_file_dataset_native_path(tmp_path):
    from mxnet_tpu.gluon.data import RecordFileDataset

    p = str(tmp_path / "ds.rec")
    rng = np.random.RandomState(3)
    payloads = [bytes(rng.randint(0, 256, 100 + i, dtype=np.uint8))
                for i in range(40)]
    w = recordio.MXIndexedRecordIO(str(tmp_path / "ds.idx"), p, "w")
    for i, pl in enumerate(payloads):
        w.write_idx(i, pl)
    w.close()

    ds = RecordFileDataset(p)
    assert ds._payload is not None  # native fast path engaged
    assert len(ds) == 40
    for i in (0, 17, 39):
        assert ds[i] == payloads[i]

    # threaded readers (DataLoader worker pattern) agree
    import concurrent.futures

    with concurrent.futures.ThreadPoolExecutor(4) as pool:
        got = list(pool.map(lambda i: ds[i], range(40)))
    assert got == payloads


def test_record_file_dataset_stale_idx_falls_back(tmp_path):
    from mxnet_tpu.gluon.data import RecordFileDataset

    p = str(tmp_path / "ds2.rec")
    idx = str(tmp_path / "ds2.idx")
    w = recordio.MXIndexedRecordIO(idx, p, "w")
    for i in range(5):
        w.write_idx(i, b"x" * (10 + i))
    w.close()
    # corrupt the sidecar offsets (regenerated .rec scenario)
    with open(idx, "w") as f:
        for i in range(5):
            f.write("%d\t%d\n" % (i, 1000 + i))
    ds = RecordFileDataset(p)
    assert ds._payload is None  # fell back to the python reader


def test_image_record_iter_prefetch_across_epochs(tmp_path):
    """Shuffled epochs through the native read-ahead ring stay correct:
    every epoch yields exactly the full label set, in the shuffled
    order's sequence, across resets."""
    from mxnet_tpu.io import ImageRecordIter

    p = str(tmp_path / "pf.rec")
    rng = np.random.RandomState(1)
    w = recordio.MXRecordIO(p, "w")
    for i in range(30):
        img = rng.randint(0, 255, (36, 36, 3), dtype=np.uint8)
        w.write(recordio.pack_img((0, float(i), i, 0), img,
                                  img_fmt=".png"))
    w.close()

    it = ImageRecordIter(path_imgrec=p, data_shape=(3, 32, 32),
                         batch_size=8, shuffle=True, seed=5,
                         preprocess_threads=2)
    assert it._prefetcher is not None
    for epoch in range(3):
        labels = []
        for batch in it:
            labels.extend(batch.label[0].asnumpy()
                          [:8 - batch.pad if batch.pad else 8])
        # round_batch wraps: first len-pad labels of the last batch are
        # the tail; the full multiset must be 0..29
        assert sorted(int(v) for v in labels) == list(range(30))
        it.reset()


def test_status_says_built_found_or_absent():
    """available() cannot tell a fresh build from a library copied in with
    the tree; status() says which (chip_smoke.py prints it)."""
    st = native.status()
    if native.available():
        assert st in ("built", "found")
    else:
        assert st.startswith("absent: ")
