"""RecordIO + image pipeline tests.

Models the reference's ``tests/python/unittest/test_recordio.py`` and
``test_io.py`` ImageRecordIter coverage (SURVEY §4), plus an
end-to-end train-on-packed-records check.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import recordio as rio

cv2 = pytest.importorskip("cv2")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_recordio_roundtrip(tmp_path):
    path = str(tmp_path / "t.rec")
    recs = [b"hello", b"x" * 7, b"", b"\xce\xd7\x23\x0a" * 5, b"a" * 1025]
    w = rio.MXRecordIO(path, "w")
    for r in recs:
        w.write(r)
    w.close()
    r = rio.MXRecordIO(path, "r")
    out = []
    while True:
        b = r.read()
        if b is None:
            break
        out.append(b)
    r.close()
    assert out == recs
    assert len(rio.list_records(path)) == len(recs)


def test_recordio_native_python_identical_bytes(tmp_path):
    """The C++ writer and the Python fallback must produce identical files."""
    from mxnet_tpu import _native
    if _native.lib() is None:
        pytest.skip("native library unavailable")
    recs = [b"abc", b"1234", b"\x00" * 9]
    pn = str(tmp_path / "n.rec")
    w = rio.MXRecordIO(pn, "w")
    for r in recs:
        w.write(r)
    w.close()
    # force the python path
    pp = str(tmp_path / "p.rec")
    wp = rio.MXRecordIO.__new__(rio.MXRecordIO)
    wp.uri, wp.flag, wp.is_open = pp, "w", False
    wp._native, wp._fp = None, open(pp, "wb")
    wp.writable, wp.is_open = True, True
    for r in recs:
        wp.write(r)
    wp._fp.close()
    wp.is_open = False
    with open(pn, "rb") as a, open(pp, "rb") as b:
        assert a.read() == b.read()


def test_native_lib_from_a_tree_without_it(tmp_path, monkeypatch):
    """A checkout holds no ``mxnet_tpu/lib/`` (.gitignore hides it):
    the library is built there from ``native/recordio.cc``, and where
    it cannot be built ``lib()`` answers None and every consumer takes
    the Python path — nothing depends on a .so that happens to lie on
    the builder's disk."""
    from mxnet_tpu import _native

    so = tmp_path / "lib" / "libmxtpu_io.so"
    monkeypatch.setattr(_native, "_SO", str(so))
    monkeypatch.setattr(_native, "_lib", None)
    monkeypatch.setattr(_native, "_tried", False)
    built = _native.lib()
    if built is not None:          # a compiler is here: built from source
        assert so.exists()
        assert built.MXTPURecordIOScan is not None
    # no source, no library: None, and a writer/reader pair still works
    monkeypatch.setattr(_native, "_SO", str(tmp_path / "none" / "x.so"))
    monkeypatch.setattr(_native, "_SRC", str(tmp_path / "missing.cc"))
    monkeypatch.setattr(_native, "_lib", None)
    monkeypatch.setattr(_native, "_tried", False)
    assert _native.lib() is None
    path = str(tmp_path / "p.rec")
    w = rio.MXRecordIO(path, "w")
    assert w._native is None
    w.write(b"abc")
    w.close()
    r = rio.MXRecordIO(path, "r")
    assert r.read() == b"abc"
    r.close()


def test_indexed_recordio(tmp_path):
    prefix = str(tmp_path / "i")
    w = rio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    for i in range(20):
        w.write_idx(i, f"record-{i}".encode())
    w.close()
    r = rio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "r")
    assert r.keys == list(range(20))
    for i in (13, 0, 19, 7):
        assert r.read_idx(i) == f"record-{i}".encode()
    r.close()


def test_irheader_pack_unpack():
    h = rio.IRHeader(0, 2.5, 11, 0)
    hdr, body = rio.unpack(rio.pack(h, b"payload"))
    assert hdr.label == 2.5 and hdr.id == 11 and body == b"payload"
    # vector label goes through the flag field
    hdr, body = rio.unpack(rio.pack(rio.IRHeader(0, [1.0, 2.0], 3, 0), b"x"))
    assert hdr.flag == 2 and list(hdr.label) == [1.0, 2.0] and body == b"x"


def test_pack_img_roundtrip():
    img = (np.arange(40 * 60 * 3) % 255).astype(np.uint8).reshape(40, 60, 3)
    s = rio.pack_img(rio.IRHeader(0, 1.0, 7, 0), img, img_fmt=".png")
    hdr, img2 = rio.unpack_img(s)
    assert hdr.label == 1.0 and np.array_equal(img, img2)


def _make_color_dataset(tmp_path, n=40, size=36):
    """Two classes distinguishable by mean brightness."""
    prefix = str(tmp_path / "ds")
    rec = rio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    rng = np.random.RandomState(0)
    for i in range(n):
        label = i % 2
        base = 60 if label == 0 else 190
        img = np.clip(rng.randn(size, size, 3) * 15 + base, 0,
                      255).astype(np.uint8)
        rec.write_idx(i, rio.pack_img(rio.IRHeader(0, float(label), i, 0),
                                      img, img_fmt=".png"))
    rec.close()
    return prefix


def test_image_record_iter_shapes_and_epoch(tmp_path):
    prefix = _make_color_dataset(tmp_path, n=30)
    it = mx.io.ImageRecordIter(
        path_imgrec=prefix + ".rec", path_imgidx=prefix + ".idx",
        data_shape=(3, 32, 32), batch_size=8, shuffle=True, rand_crop=True,
        rand_mirror=True, preprocess_threads=2, seed=7)
    pads = [b.pad for b in it]
    assert len(pads) == 4 and pads == [0, 0, 0, 2]
    it.reset()
    b = next(iter(it))
    assert b.data[0].shape == (8, 3, 32, 32)
    assert b.label[0].shape == (8,)
    it.close()


def test_image_record_iter_sharding(tmp_path):
    prefix = _make_color_dataset(tmp_path, n=30)
    counts = []
    for pi in range(3):
        it = mx.io.ImageRecordIter(
            path_imgrec=prefix + ".rec", data_shape=(3, 32, 32),
            batch_size=5, num_parts=3, part_index=pi, preprocess_threads=1)
        counts.append(it.num_data)
        it.close()
    assert counts == [10, 10, 10]


def test_image_record_iter_mean_img_cache(tmp_path):
    prefix = _make_color_dataset(tmp_path, n=16)
    mean_path = str(tmp_path / "mean.bin")
    it = mx.io.ImageRecordIter(
        path_imgrec=prefix + ".rec", data_shape=(3, 32, 32), batch_size=8,
        mean_img=mean_path, preprocess_threads=1)
    assert os.path.isfile(mean_path)
    b = next(iter(it))
    assert abs(float(b.data[0].asnumpy().mean())) < 30  # roughly centered
    it.close()
    # second open loads the cached file
    it2 = mx.io.ImageRecordIter(
        path_imgrec=prefix + ".rec", data_shape=(3, 32, 32), batch_size=8,
        mean_img=mean_path, preprocess_threads=1)
    next(iter(it2))
    it2.close()


def test_train_on_image_records(tmp_path):
    """End-to-end: pack images -> ImageRecordIter -> Module.fit learns."""
    prefix = _make_color_dataset(tmp_path, n=40)
    it = mx.io.ImageRecordIter(
        path_imgrec=prefix + ".rec", data_shape=(3, 32, 32), batch_size=10,
        shuffle=True, rand_mirror=True, mean_r=123, mean_g=123, mean_b=123,
        scale=1.0 / 58.0, preprocess_threads=2, seed=3)
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=8, kernel=(3, 3), name="c1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(8, 8), stride=(8, 8), pool_type="avg")
    net = mx.sym.Flatten(net)
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=4, optimizer="sgd",
            optimizer_params={"learning_rate": 0.1},
            initializer=mx.initializer.Xavier(),
            eval_metric="acc", batch_end_callback=None)
    it.reset()
    score = mod.score(it, mx.metric.Accuracy())
    acc = dict(score)["accuracy"]
    assert acc > 0.9, f"accuracy {acc} too low — pipeline not learnable"


def test_im2rec_tool(tmp_path):
    """make_list + pack from an image directory, then read back."""
    root = tmp_path / "imgs"
    for cls in ("cat", "dog"):
        (root / cls).mkdir(parents=True)
        for i in range(4):
            img = np.full((20, 24, 3),
                          40 if cls == "cat" else 200, np.uint8)
            cv2.imwrite(str(root / cls / f"{i}.png"), img)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    lst = tmp_path / "data.lst"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "im2rec.py"),
         "--make-list", str(lst), str(root)],
        capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stderr
    assert len(open(lst).readlines()) == 8
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "im2rec.py"),
         str(lst), str(root), "--encoding", ".png", "--num-thread", "2"],
        capture_output=True, text=True, env=env)
    assert r.returncode == 0, r.stderr
    rec = rio.MXIndexedRecordIO(str(tmp_path / "data.idx"),
                                str(tmp_path / "data.rec"), "r")
    assert len(rec.keys) == 8
    hdr, img = rio.unpack_img(rec.read_idx(rec.keys[0]))
    assert img.shape == (20, 24, 3) and hdr.label in (0.0, 1.0)
    rec.close()


def test_recordio_empty_first_record(tmp_path):
    """Zero-length record at position 0 must not read as EOF (native path)."""
    path = str(tmp_path / "e.rec")
    w = rio.MXRecordIO(path, "w")
    w.write(b"")
    w.write(b"after-empty")
    w.close()
    r = rio.MXRecordIO(path, "r")
    assert r.read() == b""
    assert r.read() == b"after-empty"
    assert r.read() is None
    r.close()


def test_image_record_iter_tiny_shard_wrap(tmp_path):
    """batch_size > 2*num_data: round_batch must still emit full batches."""
    prefix = _make_color_dataset(tmp_path, n=3)
    it = mx.io.ImageRecordIter(
        path_imgrec=prefix + ".rec", data_shape=(3, 32, 32), batch_size=8,
        round_batch=True, preprocess_threads=1)
    b = next(iter(it))
    assert b.data[0].shape == (8, 3, 32, 32) and b.pad == 5
    it.close()


def test_image_record_iter_seed_reproducible(tmp_path):
    """Same seed -> identical augmented batches across fresh iterators."""
    prefix = _make_color_dataset(tmp_path, n=12)
    def run():
        it = mx.io.ImageRecordIter(
            path_imgrec=prefix + ".rec", data_shape=(3, 28, 28),
            batch_size=6, shuffle=True, rand_crop=True, rand_mirror=True,
            random_h=20, preprocess_threads=3, seed=5)
        out = np.concatenate([b.data[0].asnumpy() for b in it])
        it.close()
        return out
    a, b = run(), run()
    assert np.array_equal(a, b)


def test_image_record_iter_grayscale(tmp_path):
    """data_shape channel count drives decode: (1, H, W) yields 1-channel."""
    prefix = str(tmp_path / "g")
    rec = rio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    rng = np.random.RandomState(0)
    for i in range(12):
        img = (rng.rand(28, 28) * 255).astype(np.uint8)
        rec.write_idx(i, rio.pack_img(rio.IRHeader(0, float(i % 2), i, 0),
                                      img, img_fmt=".png"))
    rec.close()
    it = mx.io.ImageRecordIter(
        path_imgrec=prefix + ".rec", data_shape=(1, 28, 28), batch_size=4,
        preprocess_threads=1)
    b = next(iter(it))
    assert b.data[0].shape == (4, 1, 28, 28)
    it.close()


def test_image_record_iter_rejects_unknown_kwargs(tmp_path):
    prefix = _make_color_dataset(tmp_path, n=4)
    with pytest.raises(TypeError, match="rand_miror"):
        mx.io.ImageRecordIter(path_imgrec=prefix + ".rec",
                              data_shape=(3, 32, 32), batch_size=2,
                              rand_miror=True)


def test_image_record_iter_grayscale_resize(tmp_path):
    """cv2 ops drop the channel dim of (H,W,1); the pipeline must restore it."""
    prefix = str(tmp_path / "gr")
    rec = rio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    rng = np.random.RandomState(0)
    for i in range(8):
        img = (rng.rand(20, 20) * 255).astype(np.uint8)  # != data_shape
        rec.write_idx(i, rio.pack_img(rio.IRHeader(0, float(i % 2), i, 0),
                                      img, img_fmt=".png"))
    rec.close()
    it = mx.io.ImageRecordIter(
        path_imgrec=prefix + ".rec", data_shape=(1, 28, 28), batch_size=4,
        rand_crop=True, rand_mirror=True, max_rotate_angle=10,
        preprocess_threads=2)
    b = next(iter(it))
    assert b.data[0].shape == (4, 1, 28, 28)
    it.close()
    with pytest.raises(Exception, match="HSL"):
        mx.io.ImageRecordIter(path_imgrec=prefix + ".rec",
                              data_shape=(1, 28, 28), batch_size=4,
                              random_h=10)


def test_read_batch_native(tmp_path):
    """Batched native reads return the same payloads as sequential reads."""
    path = str(tmp_path / "b.rec")
    w = rio.MXRecordIO(path, "w")
    recs = [bytes([i]) * (i * 7 + 1) for i in range(20)]
    for r in recs:
        w.write(r)
    w.close()
    offsets = rio.list_records(path)
    # arbitrary order incl. duplicates
    order = [3, 0, 19, 7, 7, 12]
    out = rio.read_batch(path, [offsets[i] for i in order], threads=3)
    assert out == [recs[i] for i in order]
    with pytest.raises(Exception, match="corrupt|open"):
        rio.read_batch(path, [5], threads=1)  # misaligned offset


def test_read_batch_empty_records(tmp_path):
    path = str(tmp_path / "e2.rec")
    w = rio.MXRecordIO(path, "w")
    for r in (b"", b"", b"x"):
        w.write(r)
    w.close()
    offsets = rio.list_records(path)
    assert rio.read_batch(path, offsets[:2]) == [b"", b""]  # all-empty batch
    assert rio.read_batch(path, offsets) == [b"", b"", b"x"]


def test_image_record_iter_label_map(tmp_path):
    """path_imglist relabels records without repacking (reference:
    image_recordio.h:24-30)."""
    prefix = _make_color_dataset(tmp_path, n=8)
    lst = tmp_path / "relabel.lst"
    # flip every label: id i -> 1 - (i % 2)
    lst.write_text("".join(f"{i}\t{1 - (i % 2)}\t-\n" for i in range(8)))
    it = mx.io.ImageRecordIter(
        path_imgrec=prefix + ".rec", path_imglist=str(lst),
        data_shape=(3, 32, 32), batch_size=8, preprocess_threads=1)
    b = next(iter(it))
    idxs = b.index
    labels = b.label[0].asnumpy()
    for pos, i in enumerate(idxs):
        assert labels[pos] == 1 - (int(i) % 2)
    it.close()


def test_image_record_iter_label_map_missing_id(tmp_path):
    prefix = _make_color_dataset(tmp_path, n=4)
    lst = tmp_path / "partial.lst"
    lst.write_text("0\t1\t-\n")  # only id 0 remapped
    it = mx.io.ImageRecordIter(
        path_imgrec=prefix + ".rec", path_imglist=str(lst),
        data_shape=(3, 32, 32), batch_size=4, preprocess_threads=1)
    with pytest.raises(Exception, match="not found in path_imglist"):
        next(iter(it))
    it.close()


def test_image_record_iter_state_resume(tmp_path):
    """Mid-epoch restore reproduces the remaining batches bit-exactly —
    per-epoch shuffle order, the epoch-keyed augmentation RNG, and the
    cursor all travel in state_dict."""
    prefix = _make_color_dataset(tmp_path, n=24)
    kw = dict(path_imgrec=prefix + ".rec", path_imgidx=prefix + ".idx",
              data_shape=(3, 32, 32), batch_size=4, shuffle=True,
              rand_mirror=True, preprocess_threads=1, seed=13)
    it = mx.io.ImageRecordIter(**kw)
    it.reset()  # epoch 2: a reshuffle has happened
    for _ in range(2):
        next(it)
    state = it.state_dict()
    rest_ref = [(b.data[0].asnumpy(), b.label[0].asnumpy()) for b in it]
    assert len(rest_ref) == 4
    it.close()

    it2 = mx.io.ImageRecordIter(**dict(kw, seed=99))  # different seed!
    it2.set_state(state)
    rest = [(b.data[0].asnumpy(), b.label[0].asnumpy()) for b in it2]
    assert len(rest) == 4
    for (d1, l1), (d2, l2) in zip(rest_ref, rest):
        np.testing.assert_array_equal(d1, d2)
        np.testing.assert_array_equal(l1, l2)
    # the restored rng stream drives the NEXT epoch's reshuffle too
    it2.reset()
    b = next(it2)
    assert b.data[0].shape == (4, 3, 32, 32)
    it2.close()


def test_image_record_iter_state_resume_at_epoch_end(tmp_path):
    """Restoring a snapshot taken exactly at the epoch's end must NOT
    swallow the next epoch's reshuffle (review finding: a rewind latch
    leaked into the genuine epoch-advance reset)."""
    prefix = _make_color_dataset(tmp_path, n=16)
    kw = dict(path_imgrec=prefix + ".rec", data_shape=(3, 32, 32),
              batch_size=4, shuffle=True, preprocess_threads=1, seed=21)
    it = mx.io.ImageRecordIter(**kw)
    for _ in it:
        pass  # exhaust epoch 1 -> _seen_epoch_end
    state = it.state_dict()
    it.reset()
    epoch2_ref = [b.label[0].asnumpy() for b in it]
    it.close()

    it2 = mx.io.ImageRecordIter(**dict(kw, seed=5))
    it2.set_state(state)
    with np.testing.assert_raises(StopIteration):
        next(it2)  # restored position IS the epoch end
    it2.reset()  # a genuine epoch advance: must reshuffle like the ref
    epoch2 = [b.label[0].asnumpy() for b in it2]
    assert len(epoch2) == len(epoch2_ref)
    for a, b in zip(epoch2_ref, epoch2):
        np.testing.assert_array_equal(a, b)
    it2.close()
