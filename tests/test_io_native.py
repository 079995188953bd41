"""Native data plane + image pipeline tests (reference test_io.py /
test_recordio.py analogues, SURVEY §4.2)."""
import os
import struct
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import recordio


def _make_rec(tmp_path, n=12, size=(40, 48)):
    """Synthetic jpeg .rec with label = image index."""
    cv2 = pytest.importorskip("cv2")
    path = str(tmp_path / "data.rec")
    w = recordio.MXRecordIO(path, "w")
    rng = np.random.RandomState(0)
    for i in range(n):
        img = rng.randint(0, 255, size + (3,), dtype=np.uint8)
        header = recordio.IRHeader(0, float(i), i, 0)
        w.write(recordio.pack_img(header, img, quality=95))
    w.close()
    return path


def test_native_reader_matches_python(tmp_path):
    from mxnet_tpu.native import NativeRecordReader, available

    if not available():
        pytest.skip("native lib unavailable")
    path = _make_rec(tmp_path)
    py = recordio.MXRecordIO(path, "r")
    nat = NativeRecordReader(path)
    count = 0
    while True:
        a = py.read()
        b = nat.read()
        assert (a is None) == (b is None)
        if a is None:
            break
        assert a == b
        count += 1
    assert count == 12


def test_native_reader_sharding(tmp_path):
    from mxnet_tpu.native import NativeRecordReader, available

    if not available():
        pytest.skip("native lib unavailable")
    path = _make_rec(tmp_path)
    seen = []
    for part in range(3):
        r = NativeRecordReader(path, part_index=part, num_parts=3)
        while True:
            buf = r.read()
            if buf is None:
                break
            header, _ = recordio.unpack(buf)
            seen.append(int(header.label))
    assert sorted(seen) == list(range(12))


def test_image_record_iter(tmp_path):
    path = _make_rec(tmp_path, n=10, size=(40, 48))
    it = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                               batch_size=4, preprocess_threads=2)
    total = 0
    labels = []
    for batch in it:
        data = batch.data[0].asnumpy()
        assert data.shape == (4, 3, 32, 32)
        lab = batch.label[0].asnumpy()
        valid = 4 - batch.pad
        labels.extend(lab[:valid].astype(int).tolist())
        total += valid
    assert total == 10
    assert sorted(labels) == list(range(10))
    # pixel values in [0, 255] float
    assert 0 <= data.min() and data.max() <= 255.0
    it.reset()
    b2 = next(iter(it))
    assert b2.data[0].shape == (4, 3, 32, 32)


def test_image_record_iter_python_fallback(tmp_path, monkeypatch):
    import mxnet_tpu.native as native

    path = _make_rec(tmp_path, n=6)
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", True)  # force fallback
    it = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                               batch_size=3)
    total = sum(3 - b.pad for b in it)
    assert total == 6


def test_csv_iter(tmp_path):
    p = tmp_path / "d.csv"
    np.savetxt(p, np.arange(24).reshape(6, 4), delimiter=",")
    it = mx.io.CSVIter(data_csv=str(p), data_shape=(4,), batch_size=2)
    batches = list(it)
    assert len(batches) == 3
    np.testing.assert_allclose(batches[0].data[0].asnumpy(),
                               [[0, 1, 2, 3], [4, 5, 6, 7]])


def test_mnist_iter(tmp_path):
    # tiny synthetic idx files
    imgs = np.random.RandomState(0).randint(0, 255, (20, 28, 28),
                                            dtype=np.uint8)
    labs = np.arange(20, dtype=np.uint8) % 10
    with open(tmp_path / "img", "wb") as f:
        f.write(struct.pack(">I", 0x00000803) +
                struct.pack(">III", 20, 28, 28) + imgs.tobytes())
    with open(tmp_path / "lab", "wb") as f:
        f.write(struct.pack(">I", 0x00000801) +
                struct.pack(">I", 20) + labs.tobytes())
    it = mx.io.MNISTIter(image=str(tmp_path / "img"),
                         label=str(tmp_path / "lab"), batch_size=5)
    batches = list(it)
    assert len(batches) == 4
    assert batches[0].data[0].shape == (5, 1, 28, 28)
    np.testing.assert_allclose(batches[0].label[0].asnumpy(),
                               labs[:5].astype(np.float32))


def test_image_module(tmp_path):
    cv2 = pytest.importorskip("cv2")
    from mxnet_tpu import image

    rng = np.random.RandomState(1)
    img = rng.randint(0, 255, (50, 60, 3), dtype=np.uint8)
    ok, enc = cv2.imencode(".jpg", img)
    assert ok
    dec = image.imdecode(enc.tobytes())
    assert dec.shape == (50, 60, 3)
    small = image.resize_short(dec, 32)
    assert min(small.shape[:2]) == 32
    crop, _ = image.center_crop(dec, (32, 32))
    assert crop.shape == (32, 32, 3)
    augs = image.CreateAugmenter((3, 24, 24), rand_mirror=True)
    out = dec
    for a in augs:
        out = a(out)
    assert out.shape == (24, 24, 3)


def test_im2rec_tool(tmp_path):
    cv2 = pytest.importorskip("cv2")
    root = tmp_path / "imgs"
    for cls in ("cat", "dog"):
        (root / cls).mkdir(parents=True)
        for i in range(3):
            img = np.random.RandomState(i).randint(
                0, 255, (32, 32, 3), dtype=np.uint8)
            cv2.imwrite(str(root / cls / ("%d.jpg" % i)), img)
    prefix = str(tmp_path / "ds")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    subprocess.run([sys.executable, os.path.join(repo, "tools", "im2rec.py"),
                    prefix, str(root)], check=True, env=env,
                   capture_output=True)
    r = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "r")
    keys = list(r.keys)
    assert len(keys) == 6
    header, img = recordio.unpack(r.read_idx(keys[0]))
    assert header.label in (0.0, 1.0)


def _make_det_rec(tmp_path, n=10, size=(48, 56)):
    """Synthetic detection .rec: one box per image in the reference det
    label layout [header_width=2, object_width=5, header..., objects...]."""
    import cv2

    path = str(tmp_path / "det.rec")
    w = recordio.MXRecordIO(path, "w")
    rng = np.random.RandomState(0)
    for i in range(n):
        img = rng.randint(0, 255, (size[0], size[1], 3), np.uint8)
        cls = float(i % 3)
        box = np.array([0.1, 0.2, 0.6, 0.8], np.float32)
        label = np.concatenate([[2, 5], [cls], box]).astype(np.float32)
        header = recordio.IRHeader(0, label, i, 0)
        ok, enc = cv2.imencode(".jpg", img)
        assert ok
        w.write(recordio.pack(header, enc.tobytes()))
    w.close()
    return path


def test_image_det_record_iter(tmp_path):
    """ImageDetRecordIter: det data plane end-to-end (reference
    iter_image_recordio_2.cc:579 det variant)."""
    path = _make_det_rec(tmp_path)
    it = mx.io.ImageDetRecordIter(
        path_imgrec=path, data_shape=(3, 32, 32), batch_size=4,
        max_objs=3, rand_mirror=True, rand_crop=0.5, rand_pad=0.5,
        mean_r=127.0, mean_g=127.0, mean_b=127.0, std_r=64.0, std_g=64.0,
        std_b=64.0, seed=3)
    assert it.provide_label[0].shape == (4, 3, 5)
    total = 0
    for epoch in range(2):
        it.reset()
        for batch in it:
            d = batch.data[0].asnumpy()
            l = batch.label[0].asnumpy()
            assert d.shape == (4, 3, 32, 32)
            assert l.shape == (4, 3, 5)
            valid = 4 - batch.pad
            total += valid
            for b in range(valid):
                rows = l[b]
                real = rows[rows[:, 0] >= 0]
                assert len(real) >= 1  # the packed box survives augmentation
                # boxes stay normalized and ordered after the aug chain
                assert (real[:, 1:] >= -1e-4).all() and (real[:, 1:] <= 1 + 1e-4).all()
                assert (real[:, 3] > real[:, 1]).all() and (real[:, 4] > real[:, 2]).all()
    assert total == 20  # 10 records x 2 epochs


def test_image_det_record_iter_sharding(tmp_path):
    path = _make_det_rec(tmp_path, n=8)
    seen = []
    for part in range(2):
        it = mx.io.ImageDetRecordIter(
            path_imgrec=path, data_shape=(3, 16, 16), batch_size=2,
            max_objs=2, num_parts=2, part_index=part)
        for batch in it:
            lab = batch.label[0].asnumpy()
            seen.append(lab[:2 - batch.pad, 0, 0])
    classes = np.concatenate(seen)
    assert len(classes) == 8  # both shards together cover every record


def test_image_record_uint8_iter(tmp_path):
    """ImageRecordUInt8Iter: raw uint8 batches, no normalization
    (reference iter_image_recordio_2.cc uint8 registration) — the 4x-
    smaller wire format for device-side casting."""
    path = _make_rec(tmp_path, n=6)
    it = mx.io.ImageRecordUInt8Iter(path_imgrec=path, data_shape=(3, 24, 24),
                                    batch_size=3,
                                    mean_r=99.0, std_r=2.0)  # must be ignored
    batch = it.next()
    d = batch.data[0]
    assert str(d._data.dtype) == "uint8"
    v = d.asnumpy()
    assert v.shape == (3, 3, 24, 24)
    assert v.max() > 1  # raw pixel range, not normalized


# --- augmenter completeness (reference image_aug_default.cc:151-316 +
# python image.py ColorJitterAug/LightingAug) --------------------------------

def test_native_rotate_matches_python(tmp_path):
    """Golden: native RotateU8 vs cv2-based rotate_image (same reference
    affine formula, image_aug_default.cc:215-246)."""
    from mxnet_tpu import native
    from mxnet_tpu.image import rotate_image

    if not native.available():
        pytest.skip("native lib unavailable")
    rng = np.random.RandomState(2)
    img = rng.randint(0, 256, (40, 56, 3), np.uint8)
    for angle in (7.0, -23.0, 90.0):
        a = native.aug_rotate(img, angle, fill=128)
        b = rotate_image(img, angle, 128).asnumpy().astype(np.uint8)
        diff = np.abs(a.astype(int) - b.astype(int))
        # native keeps the reference's fixed-point warpAffine (1/1024-px
        # per-term rounding, 1/32-px taps, 15-bit coefficients), which
        # opencv 4 matched bit for bit. opencv 5 interpolates at full
        # precision, so on this white-noise image a tap quantised to 1/32
        # px may be off by up to 256/32 = 8 levels; most pixels stay
        # within 2
        assert (diff > 2).mean() < 0.05 and diff.max() <= 8, \
            (angle, diff.max(), (diff > 2).mean())


def test_native_hsl_matches_python():
    """Golden: native HslShiftU8 vs cv2 HLS round-trip (reference
    image_aug_default.cc:297-316 formula)."""
    from mxnet_tpu import native
    from mxnet_tpu.image import hsl_shift

    if not native.available():
        pytest.skip("native lib unavailable")
    pytest.importorskip("cv2")
    rng = np.random.RandomState(3)
    img = rng.randint(0, 256, (32, 48, 3), np.uint8)
    for dh, ds, dl in ((10, 0, 0), (0, -30, 0), (0, 0, 25), (8, 12, -17)):
        a = native.aug_hsl(img, dh, ds, dl)
        b = hsl_shift(img, dh, ds, dl).asnumpy().astype(np.uint8)
        diff = np.abs(a.astype(int) - b.astype(int))
        # different rounding orders: allow +-2 on a tiny fraction of pixels
        assert (diff > 2).mean() < 0.01 and diff.max() <= 8, \
            ((dh, ds, dl), diff.max(), (diff > 2).mean())


def test_hsl_shift_lightness_semantics():
    """Pure-L shift on a gray image raises every channel equally."""
    pytest.importorskip("cv2")
    from mxnet_tpu.image import hsl_shift

    img = np.full((8, 8, 3), 100, np.uint8)
    out = hsl_shift(img, 0, 0, 50).asnumpy()
    assert np.abs(out - 150).max() <= 2  # L +50/255 on gray
    out2 = hsl_shift(img, 25, 0, 0).asnumpy()  # pure-H shift leaves gray
    assert np.abs(out2.astype(int) - 100).max() <= 2  # (S=0: achromatic)


def test_contrast_saturation_formulas(monkeypatch):
    """ColorJitter formulas match the reference (image.py ColorJitterAug):
    contrast blends toward mean gray, saturation toward per-pixel gray."""
    from mxnet_tpu import image as im

    rng = np.random.RandomState(4)
    src = im.nd.array(rng.randint(0, 256, (6, 5, 3)).astype(np.float32))
    alpha = 1.3
    monkeypatch.setattr(im.pyrandom, "uniform", lambda a, b: alpha - 1.0)
    coef = np.array([0.299, 0.587, 0.114], np.float32)

    arr = src.asnumpy()
    got_c = im.ContrastJitterAug(0.5)(src).asnumpy()
    gray = (3.0 * (1.0 - alpha) / arr.size) * (arr * coef).sum()
    np.testing.assert_allclose(got_c, arr * alpha + gray, rtol=1e-5)

    got_s = im.SaturationJitterAug(0.5)(src).asnumpy()
    gray_px = (arr * coef).sum(axis=2, keepdims=True)
    np.testing.assert_allclose(got_s, arr * alpha + gray_px * (1.0 - alpha),
                               rtol=1e-5)


def test_create_augmenter_honors_every_arg():
    """Every documented CreateAugmenter arg produces its augmenter — the
    silent-drop bug (contrast/saturation accepted and ignored) stays dead."""
    from mxnet_tpu import image as im

    augs = im.CreateAugmenter((3, 24, 24), rand_crop=True, rand_resize=True,
                              rand_mirror=True, brightness=0.1, contrast=0.2,
                              saturation=0.3, pca_noise=0.1,
                              max_rotate_angle=10, random_h=18, random_s=20,
                              random_l=20, mean=True, std=True)
    kinds = [type(a).__name__ for a in augs]
    assert "RandomRotateAug" in kinds
    assert "RandomSizedCropAug" in kinds
    assert "HSLJitterAug" in kinds
    assert "RandomOrderAug" in kinds  # brightness/contrast/saturation
    assert "LightingAug" in kinds
    jitter = next(a for a in augs if type(a).__name__ == "RandomOrderAug")
    assert {type(t).__name__ for t in jitter.ts} == {
        "BrightnessJitterAug", "ContrastJitterAug", "SaturationJitterAug"}
    # HSL (uint8-space) must run before the float cast
    assert kinds.index("HSLJitterAug") < kinds.index("CastAug")
    # and the chain still runs end-to-end
    rng = np.random.RandomState(5)
    out = im.nd.array(rng.randint(0, 256, (40, 40, 3)).astype(np.uint8))
    for a in augs:
        out = a(out)
    assert out.shape == (24, 24, 3)


def test_record_iter_rotation_and_hsl(tmp_path, monkeypatch):
    """ImageRecordIter honors the native aug params: fixed rotate changes
    pixels deterministically, and the native path agrees with the Python
    fallback (same reference formula on both sides)."""
    import mxnet_tpu.native as native

    path = _make_rec(tmp_path, n=4, size=(32, 32))

    def batch_of(**kw):
        it = mx.io.ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                                   batch_size=4, preprocess_threads=1, **kw)
        return next(iter(it)).data[0].asnumpy()

    plain = batch_of()
    rot = batch_of(rotate=37)
    assert np.abs(plain - rot).max() > 1  # rotation moved pixels

    hsl = batch_of(random_l=40, seed=7)
    assert np.abs(plain - hsl).max() > 1  # jitter changed pixels
    assert hsl.min() >= 0 and hsl.max() <= 255

    if native.available():
        # deterministic fixed angle: Python fallback must reproduce the
        # native batch (bilinear rotate + constant fill on both sides)
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_tried", True)
        rot_py = batch_of(rotate=37)
        assert np.abs(rot - rot_py).mean() < 2.0


# --- pluggable record streams (reference dmlc::Stream s3/hdfs seam,
# make/config.mk:132-144) ----------------------------------------------------

def test_memory_stream_recordio_roundtrip():
    from mxnet_tpu import filesystem

    filesystem.memory_fs_clear()
    uri = "memory://fixtures/a.rec"
    w = recordio.MXRecordIO(uri, "w")
    payloads = [b"alpha", b"bravo" * 100, b"x"]
    for p in payloads:
        w.write(p)
    w.close()
    r = recordio.MXRecordIO(uri, "r")
    got = []
    while True:
        buf = r.read()
        if buf is None:
            break
        got.append(buf)
    assert got == payloads
    r.reset()  # reopen from the store, not a half-consumed buffer
    assert r.read() == payloads[0]


def test_image_record_iter_from_memory_uri(tmp_path):
    """ImageRecordIter reads a .rec living in the memory:// store —
    the native loader can't open non-file URIs, so this also proves the
    scheme-aware Python fallback engages transparently."""
    from mxnet_tpu import filesystem

    filesystem.memory_fs_clear()
    local = _make_rec(tmp_path, n=6, size=(32, 32))
    uri = "memory://fixtures/imgs.rec"
    with open(local, "rb") as f, filesystem.open_stream(uri, "wb") as out:
        out.write(f.read())
    it = mx.io.ImageRecordIter(path_imgrec=uri, data_shape=(3, 24, 24),
                               batch_size=3)
    labels = []
    for b in it:
        lab = b.label[0].asnumpy()
        labels.extend(lab[:3 - b.pad].astype(int).tolist())
    assert sorted(labels) == list(range(6))


def test_unknown_scheme_raises():
    from mxnet_tpu import filesystem
    from mxnet_tpu.base import MXNetError

    with pytest.raises(MXNetError, match="no stream opener"):
        filesystem.open_stream("weird://bucket/x.rec")
    # remote schemes route through fsspec; assert the clear error only
    # where the s3 backend is genuinely absent
    import importlib.util

    if importlib.util.find_spec("s3fs") is None:
        with pytest.raises(MXNetError, match="fsspec|backend"):
            filesystem.open_stream("s3://bucket/x.rec")


def _load_im2rec():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "im2rec", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "im2rec.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _write_test_images(root, n, size=24):
    cv2 = pytest.importorskip("cv2")
    rng = np.random.RandomState(0)
    paths = []
    for i in range(n):
        sub = os.path.join(root, "class%d" % (i % 3))
        os.makedirs(sub, exist_ok=True)
        img = (rng.rand(size + i, size, 3) * 255).astype(np.uint8)
        p = os.path.join(sub, "img%03d.jpg" % i)
        cv2.imwrite(p, img)
        paths.append(p)
    return paths


def test_native_im2rec_roundtrip(tmp_path):
    """The native multithreaded packer (mxio_im2rec ≡ the reference's
    C++ tools/im2rec.cc): .lst -> .rec/.idx whose records round-trip
    through recordio.unpack_img with the right keys/labels, whose .idx
    supports random access, and whose bytes are IDENTICAL for 1 vs 4
    worker threads (the ordered-writer contract)."""
    pytest.importorskip("cv2")
    from mxnet_tpu import native

    if not native.available() or not getattr(native.load(),
                                             "_mxtpu_has_im2rec", False):
        pytest.skip("native io library unavailable")
    root = str(tmp_path / "imgs")
    _write_test_images(root, 9)
    im2rec = _load_im2rec()
    prefix = str(tmp_path / "data")
    im2rec.make_list(prefix, root)

    n = native.im2rec_pack(prefix + ".lst", root, prefix + ".rec",
                           prefix + ".idx", nthreads=4)
    assert n == 9

    # determinism: single-thread pack must be byte-identical
    n1 = native.im2rec_pack(prefix + ".lst", root, prefix + "_1.rec",
                            prefix + "_1.idx", nthreads=1)
    assert n1 == 9
    with open(prefix + ".rec", "rb") as a, open(prefix + "_1.rec",
                                                "rb") as b:
        assert a.read() == b.read()
    with open(prefix + ".idx") as a, open(prefix + "_1.idx") as b:
        assert a.read() == b.read()

    # contents: headers + passthrough jpeg bytes match the .lst entries
    lst = {}
    with open(prefix + ".lst") as f:
        for line in f:
            k, lab, rel = line.strip().split("\t")
            lst[int(k)] = (float(lab), rel)
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "r")
    for key in sorted(lst):
        header, img = recordio.unpack_img(rec.read_idx(key))
        assert header.id == key
        assert header.label == lst[key][0]
        assert img is not None and img.ndim == 3
    rec.close()

    # the native threaded loader consumes the native-packed file
    from mxnet_tpu.native import NativeImageLoader
    loader = NativeImageLoader(prefix + ".rec", batch_size=4,
                               data_shape=(3, 16, 16), nthreads=2)
    got = loader.next_batch()
    assert got is not None and got[0].shape == (4, 3, 16, 16)
    loader.close()


def test_native_im2rec_multilabel(tmp_path):
    """A label_width>1 .lst line packs flag=k + k float32 labels
    (recordio.py pack() convention) — NOT just the first label with the
    rest silently dropped (the reference's im2rec.cc packs label_width
    extras with flag>0)."""
    pytest.importorskip("cv2")
    from mxnet_tpu import native

    if not native.available() or not getattr(native.load(),
                                             "_mxtpu_has_im2rec", False):
        pytest.skip("native io library unavailable")
    root = str(tmp_path / "imgs")
    paths = _write_test_images(root, 3)
    prefix = str(tmp_path / "data")
    labels = {0: [1.0], 1: [2.0, 0.25, -3.5], 2: [4.0, 5.0]}
    with open(prefix + ".lst", "w") as f:
        for i, p in enumerate(paths):
            rel = os.path.relpath(p, root)
            f.write("%d\t%s\t%s\n" % (
                i, "\t".join("%g" % v for v in labels[i]), rel))

    n = native.im2rec_pack(prefix + ".lst", root, prefix + ".rec",
                           prefix + ".idx", nthreads=2)
    assert n == 3
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "r")
    for key, want in labels.items():
        header, img = recordio.unpack_img(rec.read_idx(key))
        assert header.id == key and img is not None
        if len(want) == 1:
            assert header.flag == 0 and header.label == want[0]
        else:
            got = np.asarray(header.label, dtype=np.float32)
            assert got.shape == (len(want),)
            np.testing.assert_allclose(got, np.float32(want))
    rec.close()

    # flag==1 records (recordio.pack writes flag=label.size for ANY array
    # label, including size 1) must decode through the native loader: the
    # image offset is 24 + flag*4 for flag > 0, per unpack()'s convention
    # — a flag>1-only check made the loader hand label bytes to the JPEG
    # decoder and silently drop every such record
    import cv2 as _cv2
    w1 = recordio.MXRecordIO(prefix + "_f1.rec", "w")
    enc = _cv2.imencode(".jpg", (np.random.RandomState(1)
                                 .rand(20, 20, 3) * 255).astype(np.uint8))[1]
    w1.write(recordio.pack(recordio.IRHeader(0, np.float32([7.5]), 0, 0),
                           enc.tobytes()))
    w1.close()
    from mxnet_tpu.native import NativeImageLoader
    ld = NativeImageLoader(prefix + "_f1.rec", batch_size=1,
                           data_shape=(3, 16, 16), nthreads=1)
    got = ld.next_batch()
    assert got is not None and got[2] == 1
    assert got[1][0] == 7.5
    ld.close()

    # ImageRecordIter(label_width=k) reads the packed rows as (N, k) —
    # the native loader fills short rows with zeros, and flag==0 records
    # put their inline label in column 0
    import mxnet_tpu as mx
    it = mx.io.ImageRecordIter(path_imgrec=prefix + ".rec",
                               data_shape=(3, 16, 16), batch_size=3,
                               label_width=3)
    assert it.provide_label[0].shape == (3, 3)
    lab = it.next().label[0].asnumpy()
    rows = sorted(lab.tolist())
    want_rows = sorted([[1.0, 0.0, 0.0], [2.0, 0.25, -3.5],
                        [4.0, 5.0, 0.0]])
    np.testing.assert_allclose(rows, want_rows)


def test_native_im2rec_resize(tmp_path):
    """resize=K re-encodes with the shorter side scaled to K (aspect
    kept), decodable by the Python reader."""
    pytest.importorskip("cv2")
    from mxnet_tpu import native

    if not native.available() or not getattr(native.load(),
                                             "_mxtpu_has_im2rec", False):
        pytest.skip("native io library unavailable")
    root = str(tmp_path / "imgs")
    _write_test_images(root, 4, size=32)   # heights 32..35, width 32
    im2rec = _load_im2rec()
    prefix = str(tmp_path / "data")
    im2rec.make_list(prefix, root)
    n = native.im2rec_pack(prefix + ".lst", root, prefix + ".rec",
                           prefix + ".idx", resize=16, nthreads=2)
    assert n == 4
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "r")
    for key in (0, 1, 2, 3):
        _, img = recordio.unpack_img(rec.read_idx(key))
        assert min(img.shape[:2]) == 16, img.shape
        assert max(img.shape[:2]) >= 16
    rec.close()
