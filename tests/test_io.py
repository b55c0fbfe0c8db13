"""Data pipeline tests (models tests/python/unittest/test_io.py,
test_recordio.py, and the gluon data portions of test_gluon_data.py)."""
import os
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, recordio
from mxnet_tpu.gluon import data as gdata
from mxnet_tpu.io import NDArrayIter, DataBatch, DataDesc, ResizeIter, \
    PrefetchingIter, ImageRecordIter
from mxnet_tpu.test_utils import assert_almost_equal


# ---------------------------------------------------------------------------
# RecordIO
# ---------------------------------------------------------------------------
def test_recordio_roundtrip(tmp_path):
    frec = str(tmp_path / "test.rec")
    N = 25
    writer = recordio.MXRecordIO(frec, "w")
    for i in range(N):
        writer.write(b"x" * i + b"payload%d" % i)
    writer.close()

    reader = recordio.MXRecordIO(frec, "r")
    for i in range(N):
        buf = reader.read()
        assert buf == b"x" * i + b"payload%d" % i
    assert reader.read() is None
    reader.close()


def test_indexed_recordio(tmp_path):
    frec = str(tmp_path / "test.rec")
    fidx = str(tmp_path / "test.idx")
    writer = recordio.MXIndexedRecordIO(fidx, frec, "w")
    for i in range(10):
        writer.write_idx(i, b"record_%d" % i)
    writer.close()

    reader = recordio.MXIndexedRecordIO(fidx, frec, "r")
    assert reader.keys == list(range(10))
    for i in (3, 7, 0, 9):
        assert reader.read_idx(i) == b"record_%d" % i
    reader.close()


def test_irheader_pack_unpack():
    header = recordio.IRHeader(0, 3.5, 42, 0)
    s = recordio.pack(header, b"imagebytes")
    h2, payload = recordio.unpack(s)
    assert h2.label == 3.5
    assert h2.id == 42
    assert payload == b"imagebytes"
    # multi-label path
    header = recordio.IRHeader(0, np.array([1.0, 2.0, 3.0]), 7, 0)
    s = recordio.pack(header, b"xyz")
    h3, payload = recordio.unpack(s)
    assert h3.flag == 3
    assert_almost_equal(h3.label, np.array([1.0, 2.0, 3.0]))
    assert payload == b"xyz"


def test_pack_img_unpack_img():
    img = (np.random.uniform(0, 255, (32, 24, 3))).astype(np.uint8)
    s = recordio.pack_img(recordio.IRHeader(0, 1.0, 0, 0), img,
                          img_fmt=".png")
    header, img2 = recordio.unpack_img(s)
    assert header.label == 1.0
    assert img2.shape == (32, 24, 3)
    assert np.array_equal(img, img2)  # png is lossless


# ---------------------------------------------------------------------------
# NDArrayIter
# ---------------------------------------------------------------------------
def test_ndarray_iter_basic():
    data = np.arange(40).reshape(10, 4).astype(np.float32)
    label = np.arange(10).astype(np.float32)
    it = NDArrayIter(data, label, batch_size=3, last_batch_handle="pad")
    batches = list(it)
    assert len(batches) == 4
    assert batches[0].data[0].shape == (3, 4)
    assert batches[-1].pad == 2
    assert_almost_equal(batches[0].data[0].asnumpy(), data[:3])

    it.reset()
    again = list(it)
    assert len(again) == 4


def test_ndarray_iter_discard_and_shuffle():
    data = np.arange(40).reshape(10, 4).astype(np.float32)
    it = NDArrayIter(data, None, batch_size=3, shuffle=True,
                     last_batch_handle="discard")
    batches = list(it)
    assert len(batches) == 3
    seen = np.concatenate([b.data[0].asnumpy() for b in batches])
    assert seen.shape == (9, 4)


def test_ndarray_iter_provide_data():
    data = np.zeros((8, 2, 3), dtype=np.float32)
    it = NDArrayIter(data, np.zeros(8), batch_size=4)
    d = it.provide_data[0]
    assert d.name == "data"
    assert d.shape == (4, 2, 3)
    assert it.provide_label[0].name == "softmax_label"


def test_resize_and_prefetch_iter():
    data = np.arange(24).reshape(12, 2).astype(np.float32)
    base = NDArrayIter(data, np.zeros(12), batch_size=4)
    r = ResizeIter(base, 5)
    assert len(list(r)) == 5

    base.reset()
    p = PrefetchingIter(NDArrayIter(data, np.zeros(12), batch_size=4))
    batches = list(p)
    assert len(batches) == 3
    assert batches[0].data[0].shape == (4, 2)


# ---------------------------------------------------------------------------
# ImageRecordIter over a generated .rec
# ---------------------------------------------------------------------------
def _make_rec(tmp_path, n=12, size=(20, 18)):
    frec = str(tmp_path / "imgs.rec")
    fidx = str(tmp_path / "imgs.idx")
    writer = recordio.MXIndexedRecordIO(fidx, frec, "w")
    rng = np.random.RandomState(0)
    for i in range(n):
        img = rng.randint(0, 255, size + (3,)).astype(np.uint8)
        writer.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, float(i % 3), i, 0), img, img_fmt=".png"))
    writer.close()
    return frec, fidx


def test_image_record_iter(tmp_path):
    frec, fidx = _make_rec(tmp_path)
    it = ImageRecordIter(path_imgrec=frec, path_imgidx=fidx,
                         data_shape=(3, 16, 16), batch_size=4,
                         shuffle=True, rand_crop=True, rand_mirror=True,
                         preprocess_threads=2)
    batches = list(it)
    assert len(batches) == 3
    assert batches[0].data[0].shape == (4, 3, 16, 16)
    assert batches[0].label[0].shape == (4,)
    labels = np.concatenate([b.label[0].asnumpy() for b in batches])
    assert set(labels.tolist()) <= {0.0, 1.0, 2.0}
    it.reset()
    assert len(list(it)) == 3


def test_image_det_record_iter(tmp_path):
    from mxnet_tpu.io import ImageDetRecordIter

    frec = str(tmp_path / "det.rec")
    fidx = str(tmp_path / "det.idx")
    writer = recordio.MXIndexedRecordIO(fidx, frec, "w")
    rng = np.random.RandomState(0)
    widths = []
    for i in range(8):
        img = rng.randint(0, 255, (20, 18, 3)).astype(np.uint8)
        n_obj = 1 + i % 3
        label = [2.0, 5.0]  # header_width, object_width
        for j in range(n_obj):
            label += [float(j % 4), 0.1 + 0.05 * j, 0.2, 0.6, 0.8]
        widths.append(len(label))
        writer.write_idx(i, recordio.pack_img(
            recordio.IRHeader(0, np.array(label, dtype=np.float32), i, 0),
            img, img_fmt=".png"))
    writer.close()

    it = ImageDetRecordIter(path_imgrec=frec, path_imgidx=fidx,
                            data_shape=(3, 16, 16), batch_size=4,
                            preprocess_threads=2)
    assert it.label_pad_width == max(widths)
    batches = list(it)
    assert len(batches) == 2
    assert batches[0].data[0].shape == (4, 3, 16, 16)
    assert batches[0].label[0].shape == (4, max(widths))
    lab = batches[0].label[0].asnumpy()
    np.testing.assert_allclose(lab[:, 0], 2.0)  # header width preserved
    np.testing.assert_allclose(lab[:, 1], 5.0)
    # single-object rows are padded with -1 past their boxes
    one_obj = lab[lab[:, 7] == -1.0]
    if len(one_obj):
        assert (one_obj[:, 7:] == -1.0).all()

    # mirror flips normalized x coords, boxes stay ordered/in-range
    it_m = ImageDetRecordIter(path_imgrec=frec, path_imgidx=fidx,
                              data_shape=(3, 16, 16), batch_size=8,
                              rand_mirror=True, seed=3,
                              preprocess_threads=1)
    b = next(iter(it_m))
    la = b.label[0].asnumpy()
    xmin, xmax = la[:, 3], la[:, 5]
    valid = la[:, 2] >= 0
    assert (xmin[valid] < xmax[valid]).all()
    assert (xmin[valid] >= 0).all() and (xmax[valid] <= 1.0).all()

    # rand_crop would shift boxes -> rejected loudly
    with pytest.raises(Exception, match="rand_crop"):
        ImageDetRecordIter(path_imgrec=frec, path_imgidx=fidx,
                           data_shape=(3, 16, 16), batch_size=4,
                           rand_crop=True)
    # too-narrow pad width surfaces the real error, not a thread crash
    it_bad = ImageDetRecordIter(path_imgrec=frec, path_imgidx=fidx,
                                data_shape=(3, 16, 16), batch_size=4,
                                label_pad_width=3)
    with pytest.raises(Exception, match="label_pad_width"):
        next(iter(it_bad))


def test_image_record_iter_sharded(tmp_path):
    frec, fidx = _make_rec(tmp_path)
    it0 = ImageRecordIter(path_imgrec=frec, path_imgidx=fidx,
                          data_shape=(3, 16, 16), batch_size=2,
                          part_index=0, num_parts=2)
    it1 = ImageRecordIter(path_imgrec=frec, path_imgidx=fidx,
                          data_shape=(3, 16, 16), batch_size=2,
                          part_index=1, num_parts=2)
    assert len(list(it0)) == 3
    assert len(list(it1)) == 3


# ---------------------------------------------------------------------------
# Gluon data
# ---------------------------------------------------------------------------
def test_array_dataset_and_loader():
    X = np.arange(20).reshape(10, 2).astype(np.float32)
    y = np.arange(10).astype(np.float32)
    ds = gdata.ArrayDataset(X, y)
    assert len(ds) == 10
    x0, y0 = ds[3]
    assert_almost_equal(x0, X[3])

    loader = gdata.DataLoader(ds, batch_size=4, shuffle=False,
                              last_batch="keep")
    batches = list(loader)
    assert len(batches) == 3
    assert batches[0][0].shape == (4, 2)
    assert batches[2][0].shape == (2, 2)

    loader2 = gdata.DataLoader(ds, batch_size=4, shuffle=True,
                               last_batch="discard", num_workers=2)
    batches2 = list(loader2)
    assert len(batches2) == 2


def test_dataset_transform():
    X = np.arange(10).astype(np.float32)
    ds = gdata.SimpleDataset(list(X)).transform(lambda x: x * 2)
    assert ds[3] == 6.0
    ds2 = gdata.ArrayDataset(X, X).transform_first(lambda x: x + 1)
    a, b = ds2[0]
    assert a == 1.0 and b == 0.0


def test_samplers():
    s = gdata.SequentialSampler(5)
    assert list(s) == [0, 1, 2, 3, 4]
    r = gdata.RandomSampler(5)
    assert sorted(list(r)) == [0, 1, 2, 3, 4]
    b = gdata.BatchSampler(gdata.SequentialSampler(7), 3, "keep")
    assert [len(x) for x in b] == [3, 3, 1]
    assert len(b) == 3
    b2 = gdata.BatchSampler(gdata.SequentialSampler(7), 3, "discard")
    assert [len(x) for x in b2] == [3, 3]
    b3 = gdata.BatchSampler(gdata.SequentialSampler(7), 3, "rollover")
    assert [len(x) for x in list(b3)] == [3, 3]
    assert [len(x) for x in list(b3)] == [3, 3]  # rolled-over 1 + 7 = 8 → 2x3


def test_record_file_dataset(tmp_path):
    frec, fidx = _make_rec(tmp_path, n=6)
    ds = gdata.vision.ImageRecordDataset(frec)
    assert len(ds) == 6
    img, label = ds[2]
    assert img.shape == (20, 18, 3)
    assert label == 2.0


def test_transforms():
    from mxnet_tpu.gluon.data.vision import transforms as T

    img = nd.array(np.random.randint(0, 255, (20, 16, 3)).astype(np.uint8))
    t = T.ToTensor()(img)
    assert t.shape == (3, 20, 16)
    assert float(t.max().asscalar()) <= 1.0

    n = T.Normalize(mean=(0.5, 0.5, 0.5), std=(2.0, 2.0, 2.0))(t)
    assert n.shape == (3, 20, 16)

    r = T.Resize((8, 10))(img)
    assert r.shape == (10, 8, 3)

    c = T.CenterCrop(8)(img)
    assert c.shape == (8, 8, 3)

    rc = T.RandomResizedCrop(8)(img)
    assert rc.shape == (8, 8, 3)

    comp = T.Compose([T.Resize(12), T.ToTensor()])
    out = comp(img)
    assert out.shape == (3, 12, 12)

    f = T.RandomFlipLeftRight()(img)
    assert f.shape == img.shape
    cj = T.RandomColorJitter(0.4, 0.4, 0.4)(img)
    assert cj.shape == img.shape
    rl = T.RandomLighting(0.1)(img)
    assert rl.shape == img.shape


def test_transforms_hue_crop_rotate():
    from mxnet_tpu.gluon.data.vision import transforms as T

    img = nd.array(np.random.randint(0, 255, (20, 16, 3)).astype(np.uint8))

    h = T.RandomHue(0.3)(img)
    assert h.shape == img.shape
    # hue=0 factor range collapses to 1.0 -> identity (up to clip/float)
    h0 = T.RandomHue(0.0)(img)
    np.testing.assert_allclose(h0.asnumpy(), img.asnumpy().astype(np.float32),
                               atol=1e-2)
    # jitter with hue enabled routes through RandomHue
    cj = T.RandomColorJitter(hue=0.2)(img)
    assert cj.shape == img.shape

    cr = T.CropResize(2, 4, 10, 12)(img)
    assert cr.shape == (12, 10, 3)
    cr2 = T.CropResize(2, 4, 10, 12, size=(6, 8))(img)
    assert cr2.shape == (8, 6, 3)
    import pytest as _pytest
    with _pytest.raises(Exception):
        T.CropResize(10, 10, 10, 12)(img)

    # 4x90-degree rotations of a square image compose to identity
    sq = nd.array(np.random.randint(0, 255, (16, 16, 3)).astype(np.uint8))
    r = sq
    for _ in range(4):
        r = T.Rotate(90)(r)
    np.testing.assert_allclose(r.asnumpy(), sq.asnumpy(), atol=1.0)
    assert T.Rotate(37, zoom_in=True)(sq).shape == (16, 16, 3)
    assert T.Rotate(37, zoom_out=True)(sq).shape == (16, 16, 3)
    # float images (mid-pipeline, after color jitter) must work too
    fsq = T.RandomBrightness(0.3)(sq)
    assert T.Rotate(20, zoom_in=True)(fsq).shape == (16, 16, 3)
    assert T.Rotate(20, zoom_out=True)(fsq).shape == (16, 16, 3)
    with _pytest.raises(Exception):  # negative origin must raise
        T.CropResize(-5, 0, 4, 4)(img)
    with _pytest.raises(Exception):  # non-positive dims must raise
        T.CropResize(0, 0, 0, 10)(img)
    # zoom_out on a non-square image: content scales uniformly (a square
    # marker stays square), no stretch
    rect = np.zeros((10, 30, 3), dtype=np.uint8)
    rect[3:7, 13:17] = 255  # 4x4 marker
    rot = T.Rotate(90, zoom_out=True)(nd.array(rect)).asnumpy()
    ys, xs = np.where(rot[:, :, 0] > 128)
    hspan, wspan = ys.max() - ys.min() + 1, xs.max() - xs.min() + 1
    assert abs(hspan - wspan) <= 1, (hspan, wspan)

    rr = T.RandomRotation((-30, 30))(sq)
    assert rr.shape == (16, 16, 3)
    # proba=0 -> identity
    rr0 = T.RandomRotation((-30, 30), rotate_with_proba=0.0)(sq)
    np.testing.assert_array_equal(rr0.asnumpy(), sq.asnumpy())
    with _pytest.raises(Exception):
        T.RandomRotation((30, -30))
    with _pytest.raises(Exception):
        T.Rotate(10, zoom_in=True, zoom_out=True)


def test_dataloader_with_transform_pipeline():
    from mxnet_tpu.gluon.data.vision import transforms as T

    imgs = [np.random.randint(0, 255, (20, 16, 3)).astype(np.uint8)
            for _ in range(8)]
    labels = list(range(8))
    ds = gdata.ArrayDataset(gdata.SimpleDataset(imgs),
                            gdata.SimpleDataset(labels))
    tds = ds.transform_first(
        T.Compose([T.Resize(12), T.ToTensor()]))
    loader = gdata.DataLoader(tds, batch_size=4)
    for x, y in loader:
        assert x.shape == (4, 3, 12, 12)
        assert y.shape == (4,)


def test_ndarray_iter_roll_over():
    """roll_over withholds the partial batch and rolls it into next epoch."""
    X = np.arange(10).astype(np.float32).reshape(10, 1)
    it = NDArrayIter(X, batch_size=4, last_batch_handle="roll_over")
    b1 = list(it)
    assert len(b1) == 2  # 8 samples; 2 leftover withheld
    assert all(b.pad == 0 for b in b1)
    it.reset()
    b2 = list(it)
    # next epoch leads with the 2 leftover samples: 2 + 10 = 12 → 3 batches
    assert len(b2) == 3
    first = b2[0].data[0].asnumpy().ravel()
    assert first[0] == 8.0 and first[1] == 9.0
    seen = np.concatenate([b.data[0].asnumpy().ravel() for b in b2])
    assert sorted(seen.tolist()) == sorted([8., 9.] + list(range(10)))


def test_image_record_iter_round_batch_false(tmp_path):
    frec, fidx = _make_rec(tmp_path, n=10)
    it = ImageRecordIter(path_imgrec=frec, path_imgidx=fidx,
                         data_shape=(3, 16, 16), batch_size=4,
                         round_batch=False)
    batches = list(it)
    assert len(batches) == 3
    assert batches[-1].data[0].shape[0] == 2  # short final batch, no wrap
    assert batches[-1].pad == 0
    # round_batch=True wraps and reports pad
    it2 = ImageRecordIter(path_imgrec=frec, path_imgidx=fidx,
                          data_shape=(3, 16, 16), batch_size=4)
    batches2 = list(it2)
    assert batches2[-1].data[0].shape[0] == 4
    assert batches2[-1].pad == 2


def test_record_file_dataset_threaded_reads(tmp_path):
    """Concurrent __getitem__ must not race the shared seek+read handle."""
    import threading as _threading

    frec, fidx = _make_rec(tmp_path, n=12)
    ds = gdata.vision.ImageRecordDataset(frec)
    errors = []

    def reader(tid):
        rng = np.random.RandomState(tid)
        try:
            for _ in range(40):
                i = int(rng.randint(0, 12))
                img, label = ds[i]
                assert label == float(i % 3)
                assert img.shape == (20, 18, 3)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [_threading.Thread(target=reader, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors


def test_prefetching_iter_reset_no_leak():
    X = np.arange(40).astype(np.float32).reshape(20, 2)
    base = NDArrayIter(X, batch_size=4)
    pf = PrefetchingIter(base)
    import threading as _threading

    n0 = _threading.active_count()
    for _ in range(5):
        batches = list(pf)
        assert len(batches) == 5
        pf.reset()
    assert _threading.active_count() <= n0 + 1  # no thread pile-up


# ---------------------------------------------------------------------------
# process-worker DataLoader (ref: gluon/data/dataloader.py fork workers +
# src/storage/cpu_shared_storage_manager.h — our redesign ships pickled
# numpy from forked children; see dataloader.py module docstring)
# ---------------------------------------------------------------------------
class _GilHeavyDataset(gdata.Dataset):
    """Pure-Python per-sample transform — holds the GIL (the workload the
    reference's fork workers exist for)."""

    def __init__(self, n=64, work=4000):
        self._n, self._work = n, work

    def __len__(self):
        return self._n

    def __getitem__(self, idx):
        acc = 0.0
        for i in range(self._work):  # GIL-bound Python loop
            acc += (idx * 31 + i) % 7
        return np.full((8,), np.float32(acc)), np.float32(idx)


class _FailingDataset(gdata.Dataset):
    def __len__(self):
        return 16

    def __getitem__(self, idx):
        if idx == 11:
            raise ValueError("poisoned sample 11")
        return np.zeros((2,), np.float32)


def test_process_workers_match_thread_workers():
    ds = _GilHeavyDataset(n=24, work=50)
    thr = list(gdata.DataLoader(ds, batch_size=8, num_workers=2,
                                thread_pool=True))
    prc = list(gdata.DataLoader(ds, batch_size=8, num_workers=2,
                                thread_pool=False))
    assert len(thr) == len(prc) == 3
    for (tx, ty), (px, py) in zip(thr, prc):
        assert_almost_equal(tx, px.asnumpy())
        assert_almost_equal(ty, py.asnumpy())


def test_process_workers_custom_batchify():
    ds = _GilHeavyDataset(n=16, work=10)

    def batchify(samples):
        xs = np.stack([s[0] for s in samples])
        return mx.nd.array(xs * 2.0)

    out = list(gdata.DataLoader(ds, batch_size=8, num_workers=2,
                                thread_pool=False, batchify_fn=batchify))
    ref = list(gdata.DataLoader(ds, batch_size=8, num_workers=0,
                                batchify_fn=batchify))
    for a, b in zip(out, ref):
        assert_almost_equal(a, b.asnumpy())


def test_process_worker_error_propagates():
    ds = _FailingDataset()
    loader = gdata.DataLoader(ds, batch_size=4, num_workers=2,
                              thread_pool=False)
    with pytest.raises(ValueError, match="poisoned sample 11"):
        list(loader)


def test_thread_worker_error_propagates():
    ds = _FailingDataset()
    loader = gdata.DataLoader(ds, batch_size=4, num_workers=2,
                              thread_pool=True)
    with pytest.raises(ValueError, match="poisoned sample 11"):
        list(loader)


class _WhoRanDataset(_GilHeavyDataset):
    """The GIL-bound transform, each sample stamped with the process and
    thread that ran it."""

    def __getitem__(self, idx):
        data, _ = super().__getitem__(idx)
        return data, np.array([idx, os.getpid(), threading.get_native_id()],
                              np.int32)


@pytest.mark.parametrize("thread_pool", [True, False])
def test_gil_heavy_transform_runs_in_the_worker_kind_asked_for(thread_pool):
    """The reason the escape hatch exists is that a GIL-bound transform
    serializes under threads and not under processes. What a shared CPU
    can hold the loader to is where the transform ran: with
    ``thread_pool=False`` no sample is transformed in this process, with
    threads every one is, off the consumer's thread; either way every
    record arrives once and in order."""
    ds = _WhoRanDataset(n=48, work=2000)
    stamps = np.concatenate([
        label.asnumpy() for _, label in gdata.DataLoader(
            ds, batch_size=8, num_workers=4, thread_pool=thread_pool)])
    assert stamps[:, 0].tolist() == list(range(48))
    in_parent = stamps[:, 1] == os.getpid()
    if thread_pool:
        assert in_parent.all()
        assert threading.get_native_id() not in set(stamps[:, 2].tolist())
    else:
        assert not in_parent.any()


def test_image_record_iter_nhwc_layout(tmp_path):
    """layout='NHWC' (TPU extension): channels-last batches, pixel-equal
    to the NCHW path transposed."""
    frec, fidx = _make_rec(tmp_path)
    common = dict(path_imgrec=frec, path_imgidx=fidx,
                  data_shape=(3, 16, 16), batch_size=4, shuffle=False,
                  mean_r=10.0, std_r=2.0,  # exercise normalization too
                  preprocess_threads=2)
    nchw = list(ImageRecordIter(**common))
    nhwc = list(ImageRecordIter(layout="NHWC", **common))
    it = ImageRecordIter(layout="NHWC", **common)
    assert it.provide_data[0].shape == (4, 16, 16, 3)
    assert it.provide_data[0].layout == "NHWC"
    for a, b in zip(nchw, nhwc):
        np.testing.assert_array_equal(
            a.data[0].asnumpy().transpose(0, 2, 3, 1),
            b.data[0].asnumpy())
        np.testing.assert_array_equal(a.label[0].asnumpy(),
                                      b.label[0].asnumpy())
    with pytest.raises(Exception):
        ImageRecordIter(layout="NCWH", **common)


def test_image_record_uint8_iter(tmp_path):
    """ImageRecordUInt8Iter (ref: iter_image_recordio_2.cc uint8
    registration): raw uint8 batches, device-side normalization."""
    from mxnet_tpu.io import ImageRecordUInt8Iter
    frec, fidx = _make_rec(tmp_path)
    it = ImageRecordUInt8Iter(path_imgrec=frec, path_imgidx=fidx,
                              data_shape=(3, 16, 16), batch_size=4,
                              shuffle=False, preprocess_threads=2)
    b = next(iter(it))
    assert b.data[0].dtype == np.uint8
    assert it.provide_data[0].dtype == np.dtype("uint8")
    # pixel-equal to the f32 path
    it_f = ImageRecordIter(path_imgrec=frec, path_imgidx=fidx,
                           data_shape=(3, 16, 16), batch_size=4,
                           shuffle=False, preprocess_threads=2)
    bf = next(iter(it_f))
    np.testing.assert_array_equal(b.data[0].asnumpy().astype(np.float32),
                                  bf.data[0].asnumpy())
    # mean/std are a device-side job in uint8 mode
    with pytest.raises(Exception, match="uint8"):
        ImageRecordUInt8Iter(path_imgrec=frec, path_imgidx=fidx,
                             data_shape=(3, 16, 16), batch_size=4,
                             mean_r=1.0)


def test_image_record_uint8_iter_rejects_conflicting_dtype(tmp_path):
    from mxnet_tpu.io import ImageRecordUInt8Iter
    frec, fidx = _make_rec(tmp_path)
    with pytest.raises(Exception, match="uint8 by definition"):
        ImageRecordUInt8Iter(path_imgrec=frec, path_imgidx=fidx,
                             data_shape=(3, 16, 16), batch_size=4,
                             dtype="float32")
