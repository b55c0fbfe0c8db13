"""mx.image pipeline + MXT_* config tier + AMP tests (models
tests/python/unittest/test_image.py and the contrib amp coverage)."""
import os
import re

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.base import MXNetError


def _png_bytes(h, w, seed=0):
    import io
    from PIL import Image

    arr = np.random.RandomState(seed).randint(0, 255, (h, w, 3), np.uint8)
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, format="PNG")
    return arr, buf.getvalue()


# ---------------------------------------------------------------------------
# mx.image
# ---------------------------------------------------------------------------
def test_imdecode_roundtrip():
    arr, png = _png_bytes(20, 30)
    img = mx.image.imdecode(png)
    assert img.shape == (20, 30, 3)
    np.testing.assert_array_equal(img.asnumpy(), arr)  # PNG is lossless
    gray = mx.image.imdecode(png, flag=0)
    assert gray.shape == (20, 30, 1)


def test_resize_and_crops():
    arr, png = _png_bytes(40, 60)
    img = mx.image.imdecode(png)
    r = mx.image.resize_short(img, 20)
    assert min(r.shape[:2]) == 20 and r.shape[1] == 30
    f = mx.image.imresize(img, 10, 14)
    assert f.shape == (14, 10, 3)
    c, (x0, y0, w, h) = mx.image.center_crop(img, (20, 20))
    assert c.shape == (20, 20, 3) and (w, h) == (20, 20)
    rc, _ = mx.image.random_crop(img, (16, 16))
    assert rc.shape == (16, 16, 3)
    norm = mx.image.color_normalize(img, mean=(1.0, 2.0, 3.0),
                                    std=(2.0, 2.0, 2.0))
    np.testing.assert_allclose(
        norm.asnumpy(), (arr.astype("f4") - [1, 2, 3]) / 2.0, rtol=1e-6)


def test_create_augmenter_pipeline():
    augs = mx.image.CreateAugmenter((3, 16, 16), resize=20, rand_crop=True,
                                    rand_mirror=True, mean=True, std=True,
                                    brightness=0.1, contrast=0.1,
                                    saturation=0.1)
    arr, png = _png_bytes(40, 50, seed=1)
    img = mx.image.imdecode(png)
    for aug in augs:
        img = aug(img)
    out = img.asnumpy()
    assert out.shape == (16, 16, 3)
    assert out.dtype == np.float32
    assert np.isfinite(out).all()


def test_image_iter_from_imglist(tmp_path):
    from PIL import Image

    rng = np.random.RandomState(0)
    imglist = []
    for i in range(5):
        arr = rng.randint(0, 255, (24 + i, 30, 3), np.uint8)
        fname = "img%d.png" % i
        Image.fromarray(arr).save(tmp_path / fname)
        imglist.append([float(i % 3), fname])
    it = mx.image.ImageIter(batch_size=2, data_shape=(3, 16, 16),
                            imglist=imglist, path_root=str(tmp_path),
                            shuffle=False)
    batch = it.next()
    assert batch.data[0].shape == (2, 3, 16, 16)
    assert batch.label[0].shape == (2,)
    np.testing.assert_array_equal(batch.label[0].asnumpy(), [0, 1])
    batches = [batch] + [b for b in iter(it.next, None)] \
        if False else None
    it.reset()
    n = 0
    while True:
        try:
            b = it.next()
        except StopIteration:
            break
        n += 1
    assert n == 3  # 5 images, batch 2 → 2 full + 1 padded
    del batches


def test_image_iter_from_rec(tmp_path):
    from mxnet_tpu import recordio

    rec_path = str(tmp_path / "data.rec")
    idx_path = str(tmp_path / "data.idx")
    rec = recordio.MXIndexedRecordIO(idx_path, rec_path, "w")
    rng = np.random.RandomState(1)
    for i in range(4):
        _, png = _png_bytes(20, 20, seed=i)
        header = recordio.IRHeader(0, float(i), i, 0)
        rec.write_idx(i, recordio.pack(header, png))
    rec.close()
    it = mx.image.ImageIter(batch_size=2, data_shape=(3, 12, 12),
                            path_imgrec=rec_path, path_imgidx=idx_path)
    b = it.next()
    assert b.data[0].shape == (2, 3, 12, 12)
    np.testing.assert_array_equal(b.label[0].asnumpy(), [0, 1])


# ---------------------------------------------------------------------------
# config tier
# ---------------------------------------------------------------------------
def test_config_env_precedence(monkeypatch):
    assert mx.config.get("MXT_NUM_WORKERS") >= 1
    monkeypatch.setenv("MXT_NUM_WORKERS", "7")
    assert mx.config.get("MXT_NUM_WORKERS") == 7
    monkeypatch.delenv("MXT_NUM_WORKERS")
    mx.config.set_default("MXT_NUM_WORKERS", 3)
    assert mx.config.get("MXT_NUM_WORKERS") == 3
    mx.config.set_default("MXT_NUM_WORKERS", 1)
    with pytest.raises(MXNetError):
        mx.config.get("MXT_NOT_A_VAR")
    monkeypatch.setenv("MXT_PROFILER_AUTOSTART", "true")
    assert mx.config.get("MXT_PROFILER_AUTOSTART") is True
    table = mx.config.describe()
    assert "MXT_PROFILER_AUTOSTART" in table


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_every_declared_variable_has_a_reader():
    """A declared ``MXT_*`` variable that nothing reads is a documented
    behaviour that does not exist: each name ``config.py`` declares is
    named by some other source file of the package, the tools, the
    benchmark or the examples (``MXT_TEST_TPU`` selects the lane of these
    tests, so its reader is ``tests/conftest.py``)."""
    with open(os.path.join(_REPO, "tests", "conftest.py")) as f:
        text = [f.read()]
    for top in ("mxnet_tpu", "tools", "benchmark", "examples"):
        for folder, _, files in os.walk(os.path.join(_REPO, top)):
            for name in files:
                path = os.path.join(folder, name)
                if name.endswith((".py", ".sh")) and path != os.path.join(
                        _REPO, "mxnet_tpu", "config.py"):
                    with open(path, encoding="utf-8") as f:
                        text.append(f.read())
    text = "\n".join(text)
    unread = [name for name in mx.config.variables()
              if not re.search(r"\b%s\b" % name, text)]
    assert unread == []


def test_declared_variables_only_go_down():
    """A ratchet on the options: ``config.py`` declares at most what the last
    PR that counted them left (PR 46: 76 -> 72). A PR that adds a variable
    edits the number, and names the two callers that need different values."""
    assert len(mx.config.variables()) <= 72


@pytest.mark.parametrize("doc", ["README.md", "MIGRATION.md"])
def test_documents_name_files_that_exist(doc):
    """Every back-quoted ``*.py`` / ``*.sh`` name in the document is a
    file, under the repo root, ``mxnet_tpu/`` or ``tools/``."""
    with open(os.path.join(_REPO, doc), encoding="utf-8") as f:
        names = set(re.findall(r"`([^`\s]+\.(?:py|sh))`", f.read()))
    assert names, "no file names found: the pattern rotted"
    missing = sorted(
        n for n in names
        if not any(os.path.exists(os.path.join(_REPO, base, n))
                   for base in ("", "mxnet_tpu", "tools")))
    assert missing == []


def test_config_naive_engine_runs_unjitted():
    import jax

    with mx.config.naive_engine():
        assert jax.config.jax_disable_jit
        out = (nd.ones((2, 2)) * 3).asnumpy()
    np.testing.assert_array_equal(out, 3)
    assert not jax.config.jax_disable_jit


# ---------------------------------------------------------------------------
# AMP
# ---------------------------------------------------------------------------
def test_amp_autocast_lists():
    import mxnet_tpu.amp as amp

    amp.init(target_dtype="bfloat16")
    try:
        a = nd.array(np.random.RandomState(0)
                     .normal(size=(4, 8)).astype("f4"))
        b = nd.array(np.random.RandomState(1)
                     .normal(size=(8, 2)).astype("f4"))
        out = nd.dot(a, b)
        assert out.dtype == np.dtype("bfloat16")  # MXU op ran low-precision
        sm = nd.softmax(a)
        assert sm.dtype == np.float32  # sensitive op stayed f32
        bf = a.astype("bfloat16")
        assert nd.softmax(bf).dtype == np.dtype("bfloat16")  # cast back
        with pytest.raises(MXNetError):
            amp.init(target_dtype="float16")  # conflicting re-init
    finally:
        amp._deinit_for_tests()


def test_amp_dynamic_loss_scaling():
    import mxnet_tpu.amp as amp
    from mxnet_tpu import autograd as ag

    net = mx.gluon.nn.Dense(4)
    net.initialize()
    trainer = mx.gluon.Trainer(net.collect_params(), "sgd",
                               {"learning_rate": 0.1})
    amp.init_trainer(trainer)
    scaler = trainer._amp_scaler
    scale0 = scaler.loss_scale
    x = nd.array(np.random.RandomState(0).normal(size=(2, 3)).astype("f4"))
    with ag.record():
        loss = (net(x) ** 2).mean()
        # reference usage: scale_loss + backward inside record()
        with amp.scale_loss(loss, trainer) as scaled:
            scaled.backward()
    w_before = net.weight.data().asnumpy().copy()
    trainer.step(2)
    assert not np.allclose(net.weight.data().asnumpy(), w_before)

    # overflow: grads forced to inf → step is SKIPPED, scale halves
    w_before = net.weight.data().asnumpy().copy()
    with ag.record():
        loss = (net(x) ** 2).mean()
    loss.backward()
    net.weight.data()._grad = nd.full(net.weight.shape, np.inf)
    trainer.step(2)
    np.testing.assert_array_equal(net.weight.data().asnumpy(), w_before)
    assert scaler.loss_scale == max(1.0, scale0 / 2.0)
