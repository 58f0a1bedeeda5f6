"""Input-pipeline tests: single-copy collation, shared-memory workers,
device prefetch (docs/perf.md "Input pipeline").

Parity contract: every transport (in-process, thread pool, spawn
shared-memory) and the DevicePrefetcher wrapper must deliver batches
element-wise IDENTICAL — values and order — to the legacy in-process
path, given the same sampler seed.
"""

import gc
import io as _io
import multiprocessing
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, recordio
from mxnet_tpu.gluon.data import (DataLoader, DataLoaderWorkerError,
                                  DevicePrefetcher)
from mxnet_tpu.gluon.data import _shm_worker
from mxnet_tpu.gluon.data.dataloader import default_batchify_fn


class FailingDataset:
    """Module-level (picklable for spawn) dataset that poisons one index."""

    def __init__(self, n=16, bad=13):
        self._n = n
        self._bad = bad

    def __len__(self):
        return self._n

    def __getitem__(self, i):
        if i == self._bad:
            raise ValueError(f"poisoned sample {i}")
        return np.full(3, i, np.float32)


class PlatformProbeDataset:
    """Module-level (picklable for spawn): each sample says whether the
    worker that built it was held to the host CPU."""

    def __len__(self):
        return 4

    def __getitem__(self, i):
        import os

        return np.float32(os.environ.get("JAX_PLATFORMS") == "cpu")


def sum_batchify(samples):
    """Module-level custom batchify (picklable for spawn workers)."""
    return np.asarray([float(np.sum(s[0])) for s in samples], np.float32)


def _as_np(batch):
    if isinstance(batch, (list, tuple)):
        return [_as_np(b) for b in batch]
    return batch.asnumpy()


def _assert_batches_equal(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        g, w = _as_np(g), _as_np(w)
        assert len(g) == len(w)
        for gc_, wc in zip(g, w):
            np.testing.assert_array_equal(gc_, wc)


def _float_ds(n=37, dim=4):
    rng = np.random.RandomState(0)
    return gluon.data.ArrayDataset(
        rng.rand(n, dim).astype(np.float32),
        np.arange(n, dtype=np.float32))


# -- collation -----------------------------------------------------------------

def test_collate_column_single_copy_matches_stack():
    rng = np.random.RandomState(1)
    col = [rng.rand(3, 5).astype(np.float32) for _ in range(8)]
    out = _shm_worker.collate_column(col)
    np.testing.assert_array_equal(out, np.stack(col))
    assert out.flags["C_CONTIGUOUS"]
    # preallocated output is written in place
    buf = np.empty((8, 3, 5), np.float32)
    assert _shm_worker.collate_column(col, out=buf) is buf
    np.testing.assert_array_equal(buf, np.stack(col))


def test_collate_column_mixed_dtype_falls_back_to_legacy_promotion():
    mixed = [np.arange(2, dtype=np.float32), np.arange(2, dtype=np.int64)]
    got = _shm_worker.collate_column(mixed)
    legacy = np.asarray([np.asarray(m) for m in mixed])
    assert got.dtype == legacy.dtype
    np.testing.assert_array_equal(got, legacy)
    # truly ragged shapes are an error on the legacy path too
    ragged = [np.zeros((2,), np.float32), np.zeros((3,), np.float32)]
    with pytest.raises(ValueError):
        _shm_worker.collate_column(ragged)


def test_default_batchify_parity_with_legacy_stack():
    import jax.numpy as jnp

    rng = np.random.RandomState(2)
    samples = [(rng.rand(4).astype(np.float32), np.float32(i))
               for i in range(6)]
    got = default_batchify_fn(samples)
    # the pre-optimization path: one jnp.asarray per sample + stack
    want_x = jnp.stack([jnp.asarray(s[0]) for s in samples])
    want_y = jnp.stack([jnp.asarray(s[1]) for s in samples])
    np.testing.assert_array_equal(got[0].asnumpy(), np.asarray(want_x))
    np.testing.assert_array_equal(got[1].asnumpy(), np.asarray(want_y))


def test_default_batchify_device_resident_samples():
    samples = [mx.nd.array(np.full((2, 2), i, np.float32))
               for i in range(4)]
    out = default_batchify_fn(samples)
    assert out.shape == (4, 2, 2)
    np.testing.assert_array_equal(
        out.asnumpy(), np.stack([s.asnumpy() for s in samples]))


# -- transport parity ----------------------------------------------------------

def test_loader_thread_workers_parity():
    ds = _float_ds()
    kw = dict(batch_size=5, shuffle=False, last_batch="keep")
    want = list(DataLoader(ds, **kw))
    got = list(DataLoader(ds, num_workers=2, **kw))
    _assert_batches_equal(got, want)


def test_loader_thread_workers_parity_shuffled():
    ds = _float_ds()
    np.random.seed(42)
    want = list(DataLoader(ds, batch_size=5, shuffle=True))
    np.random.seed(42)
    got = list(DataLoader(ds, batch_size=5, shuffle=True, num_workers=2))
    _assert_batches_equal(got, want)


def test_loader_shm_workers_parity():
    """Spawn + shared-memory ring transport: same values, same order.
    More batches than ring slots exercises slot recycling."""
    ds = _float_ds(n=48)
    want = list(DataLoader(ds, batch_size=4))
    loader = DataLoader(ds, batch_size=4, num_workers=2,
                        thread_pool=False)
    with iter(loader) as it:
        got = list(it)
    _assert_batches_equal(got, want)
    assert not [p for p in multiprocessing.active_children()
                if p.is_alive()]


def test_shm_workers_are_held_to_the_cpu(monkeypatch):
    """One process per chip: whatever platform the parent runs on, a
    spawned worker sees JAX_PLATFORMS=cpu, so a dataset that builds an
    NDArray there cannot claim the accelerator; the parent's own
    setting comes back once the workers are started."""
    import os

    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    loader = DataLoader(PlatformProbeDataset(), batch_size=4,
                        num_workers=1, thread_pool=False)
    with iter(loader) as it:
        (batch,) = list(it)
    assert os.environ["JAX_PLATFORMS"] == "tpu,cpu"
    np.testing.assert_array_equal(batch.asnumpy(), np.ones(4, np.float32))


def test_loader_shm_oversize_batch_pickle_fallback(monkeypatch):
    """A batch too big for a ring slot transparently takes the pickle
    path — identical results, merely slower."""
    monkeypatch.setenv("MXTPU_SHM_SLOT_MB", "0.00005")  # ~52 bytes
    rng = np.random.RandomState(3)
    ds = gluon.data.ArrayDataset(rng.rand(12, 64).astype(np.float32),
                                 np.arange(12, dtype=np.float32))
    want = list(DataLoader(ds, batch_size=4))
    loader = DataLoader(ds, batch_size=4, num_workers=1,
                        thread_pool=False)
    with iter(loader) as it:
        got = list(it)
    _assert_batches_equal(got, want)


def test_loader_shm_custom_batchify():
    ds = _float_ds(n=8, dim=3)
    want = [sum_batchify([ds[i] for i in range(b * 4, b * 4 + 4)])
            for b in range(2)]
    loader = DataLoader(ds, batch_size=4, num_workers=1,
                        thread_pool=False, batchify_fn=sum_batchify)
    with iter(loader) as it:
        got = list(it)
    assert len(got) == 2
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g.asnumpy(), w)


# -- worker failure context ----------------------------------------------------

def test_worker_error_context_threads():
    loader = DataLoader(FailingDataset(), batch_size=4, num_workers=2)
    it = iter(loader)
    got = [next(it), next(it), next(it)]  # batches 0..2 are fine
    assert len(got) == 3
    with pytest.raises(DataLoaderWorkerError) as exc:
        next(it)
    msg = str(exc.value)
    assert "batch 3" in msg and "13" in msg
    assert not [t for t in threading.enumerate()
                if t.name.startswith("ThreadPoolExecutor")
                and t.is_alive() and "loader" in repr(t)]


def test_worker_error_context_processes():
    loader = DataLoader(FailingDataset(), batch_size=4, num_workers=1,
                        thread_pool=False)
    it = iter(loader)
    for _ in range(3):
        next(it)
    with pytest.raises(DataLoaderWorkerError) as exc:
        next(it)
    msg = str(exc.value)
    assert "batch 3" in msg and "13" in msg
    assert "worker traceback" in msg and "poisoned sample 13" in msg
    assert not [p for p in multiprocessing.active_children()
                if p.is_alive()]


# -- resource cleanup ----------------------------------------------------------

def test_early_break_leaves_no_worker_threads():
    ds = _float_ds(n=64)
    before = set(threading.enumerate())
    loader = DataLoader(ds, batch_size=4, num_workers=2)
    it = iter(loader)
    next(it)
    it.close()
    leaked = [t for t in threading.enumerate()
              if t not in before and t.is_alive()]
    assert not leaked
    # __del__ path: abandoning the iterator mid-epoch also cleans up
    it2 = iter(loader)
    next(it2)
    del it2
    gc.collect()
    leaked = [t for t in threading.enumerate()
              if t not in before and t.is_alive()]
    assert not leaked


def test_early_break_leaves_no_worker_processes():
    ds = _float_ds(n=32)
    loader = DataLoader(ds, batch_size=4, num_workers=1,
                        thread_pool=False)
    it = iter(loader)
    next(it)
    del it
    gc.collect()
    assert not [p for p in multiprocessing.active_children()
                if p.is_alive()]


# -- last_batch semantics across epochs ----------------------------------------

@pytest.mark.parametrize("num_workers", [0, 2])
def test_last_batch_rollover_two_epochs(num_workers):
    ds = gluon.data.SimpleDataset(list(range(10)))
    loader = DataLoader(ds, batch_size=4, last_batch="rollover",
                        num_workers=num_workers)
    assert len(loader) == 2  # no carry yet
    ep1 = [b.asnumpy().tolist() for b in loader]
    assert ep1 == [[0, 1, 2, 3], [4, 5, 6, 7]]
    # the tail [8, 9] rolled over: it leads epoch 2, in order
    assert len(loader) == 3
    ep2 = [b.asnumpy().tolist() for b in loader]
    assert ep2 == [[8, 9, 0, 1], [2, 3, 4, 5], [6, 7, 8, 9]]
    assert len(loader) == 2  # nothing carried out of epoch 2


@pytest.mark.parametrize("num_workers", [0, 2])
def test_last_batch_discard_two_epochs(num_workers):
    ds = gluon.data.SimpleDataset(list(range(10)))
    loader = DataLoader(ds, batch_size=4, last_batch="discard",
                        num_workers=num_workers)
    for _ in range(2):  # identical epochs, ragged tail dropped
        assert len(loader) == 2
        ep = [b.asnumpy().tolist() for b in loader]
        assert ep == [[0, 1, 2, 3], [4, 5, 6, 7]]


def test_prefetch_defaulting():
    ds = _float_ds(n=16)
    assert DataLoader(ds, 4, num_workers=3)._prefetch == 6  # 2 * workers
    assert DataLoader(ds, 4, num_workers=3, prefetch=None)._prefetch == 6
    assert DataLoader(ds, 4, num_workers=2, prefetch=0)._prefetch == 0
    assert DataLoader(ds, 4, num_workers=2, prefetch=7)._prefetch == 7
    loader = DataLoader(ds, 4, num_workers=2, prefetch=0)
    it = iter(loader)
    assert it._depth == 1  # prefetch=0: at most one batch in flight
    it.close()


# -- DevicePrefetcher ----------------------------------------------------------

def test_device_prefetcher_parity_and_order():
    ds = _float_ds(n=20, dim=3)
    loader = DataLoader(ds, batch_size=5)
    want = list(loader)
    got = list(DevicePrefetcher(loader, depth=2))
    _assert_batches_equal(got, want)


def test_device_prefetcher_env_zero_is_synchronous(monkeypatch):
    monkeypatch.setenv("MXTPU_DEVICE_PREFETCH", "0")
    ds = _float_ds(n=12, dim=2)
    loader = DataLoader(ds, batch_size=4)
    pf = DevicePrefetcher(loader)
    assert pf._depth == 0
    want = list(loader)
    got = []
    for b in pf:
        got.append(b)
        assert not [t for t in threading.enumerate()
                    if t.name == "mxtpu-device-prefetch"]
    _assert_batches_equal(got, want)
    assert pf._thread is None  # no background thread was ever started


def test_device_prefetcher_databatch_and_reset():
    data = np.random.RandomState(0).rand(20, 3).astype(np.float32)
    it = mx.io.NDArrayIter(data, np.zeros(20, np.float32), batch_size=5)
    pf = DevicePrefetcher(it, depth=2)
    for _ in range(2):  # two epochs through reset()
        pf.reset()
        batches = list(pf)
        assert len(batches) == 4
        got = np.concatenate([b.data[0].asnumpy() for b in batches])
        np.testing.assert_array_equal(got, data)
        assert batches[0].pad == 0


def test_device_prefetcher_mesh_sharding():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from mxnet_tpu import parallel

    ndev = len(jax.devices())
    mesh = parallel.data_parallel_mesh(ndev)
    data = np.random.RandomState(1).rand(2 * ndev + 1, 3) \
        .astype(np.float32)
    it = DataLoader(gluon.data.ArrayDataset(data,
                                            np.zeros(len(data),
                                                     np.float32)),
                    batch_size=2 * ndev, last_batch="keep")
    batches = list(DevicePrefetcher(it, depth=2, mesh=mesh))
    full = batches[0][0]._data
    want = NamedSharding(mesh, PartitionSpec("dp"))
    assert full.sharding.is_equivalent_to(want, full.ndim)
    # ragged tail (1 row) can't shard the batch axis: replicated
    tail = batches[-1][0]._data
    repl = NamedSharding(mesh, PartitionSpec())
    assert tail.sharding.is_equivalent_to(repl, tail.ndim)
    # values survive placement
    got = np.concatenate([b[0].asnumpy() for b in batches])
    np.testing.assert_array_equal(got, data)


def test_device_prefetcher_early_break_stops_producer():
    def endless():
        i = 0
        while True:
            yield np.full((2, 2), i, np.float32)
            i += 1

    pf = DevicePrefetcher(endless(), depth=2)
    it = iter(pf)
    a = next(it)
    np.testing.assert_array_equal(a.asnumpy(), np.zeros((2, 2)))
    next(it)
    pf.close()
    assert not [t for t in threading.enumerate()
                if t.name == "mxtpu-device-prefetch" and t.is_alive()]


def test_device_prefetcher_forwards_source_exception():
    def boom():
        yield np.zeros((2,), np.float32)
        raise RuntimeError("source exploded")

    pf = DevicePrefetcher(boom(), depth=2)
    it = iter(pf)
    next(it)
    with pytest.raises(RuntimeError, match="source exploded"):
        next(it)


# -- batch-vectorized normalize/flip -------------------------------------------

def test_normalize_flip_batch_np_bit_parity():
    from mxnet_tpu import image as image_mod

    rng = np.random.RandomState(4)
    u8 = rng.randint(0, 256, (6, 9, 7, 3)).astype(np.uint8)
    mirror = np.array([1, 0, 1, 1, 0, 0], bool)
    scale = 1 / 255.0
    mean = np.array([0.2, 0.3, 0.4], np.float32).reshape(3, 1, 1)
    std = np.array([1.1, 0.9, 1.3], np.float32).reshape(3, 1, 1)
    # the per-sample reference op sequence, exactly as _decode_one had it
    ref = np.stack([
        ((arr[:, ::-1, :] if m else arr).astype(np.float32)
         .transpose(2, 0, 1) * scale - mean) / std
        for arr, m in zip(u8, mirror)])
    got = image_mod.normalize_flip_batch_np(u8.copy(), mirror, scale,
                                            mean, std)
    np.testing.assert_array_equal(got, ref)
    # preallocated output is honored
    out = np.empty((6, 3, 9, 7), np.float32)
    assert image_mod.normalize_flip_batch_np(
        u8.copy(), mirror, scale, mean, std, out=out) is out
    np.testing.assert_array_equal(out, ref)


def _write_rec(tmp_path, n, size):
    from PIL import Image

    path = str(tmp_path / "pipe.rec")
    rng = np.random.RandomState(0)
    w = recordio.MXRecordIO(path, "w")
    payloads = []
    for i in range(n):
        arr = rng.randint(0, 255, size + (3,)).astype(np.uint8)
        buf = _io.BytesIO()
        Image.fromarray(arr).save(buf, format="jpeg")
        payloads.append(buf.getvalue())
        w.write(recordio.pack(recordio.IRHeader(0, float(i), i, 0),
                              payloads[-1]))
    w.close()
    return path, payloads


def test_image_record_iter_python_batch_parity(tmp_path, monkeypatch):
    """The vectorized pure-python branch is bit-identical to the
    per-sample reference path, mirror flags included."""
    from mxnet_tpu import _native as native_mod
    from mxnet_tpu.io import io as io_mod

    path, payloads = _write_rec(tmp_path, 4, (40, 48))
    monkeypatch.setattr(native_mod, "has_jpeg", lambda: False)
    kw = dict(path_imgrec=path, data_shape=(3, 32, 32), batch_size=4,
              mean_r=0.5, std_g=1.2, scale=1 / 255.0, rand_mirror=True)
    it = io_mod.ImageRecordIter(**kw)
    np.random.seed(7)
    got = it.next().data[0].asnumpy()
    np.random.seed(7)
    mirror = np.random.rand(4) < 0.5
    ref = np.stack([it._decode_one(p, m)
                    for p, m in zip(payloads, mirror)])
    np.testing.assert_array_equal(got, ref)


def test_image_iter_vectorized_tail_parity(tmp_path):
    """ImageIter's hoisted flip/cast/normalize suffix matches running the
    full augmenter list per sample — same RNG stream, same pixels."""
    import random as _pyrandom

    from mxnet_tpu import image as image_mod

    path, payloads = _write_rec(tmp_path, 4, (36, 44))
    mean = np.array([100.0, 50.0, 25.0])
    std = np.array([2.0, 3.0, 4.0])

    def make_augs():
        return [image_mod.CenterCropAug((24, 24)),
                image_mod.HorizontalFlipAug(0.5),
                image_mod.CastAug(),
                image_mod.ColorNormalizeAug(mean, std)]

    it = image_mod.ImageIter(batch_size=4, data_shape=(3, 24, 24),
                             path_imgrec=path, aug_list=make_augs())
    assert len(it._aug_tail) == 3  # flip + cast + normalize hoisted
    _pyrandom.seed(11)
    got = it.next().data[0].asnumpy()

    # reference: the full per-sample pipeline, same RNG seed
    _pyrandom.seed(11)
    ref = np.empty((4, 3, 24, 24), np.float32)
    for i, payload in enumerate(payloads):
        arr = image_mod.imdecode_np(payload)
        arr = image_mod.center_crop_np(arr, (24, 24))
        if _pyrandom.random() < 0.5:
            arr = arr[:, ::-1, :]
        a = arr.astype(np.float32)          # CastAug
        a = (a - mean) / std                # ColorNormalizeAug (f64)
        ref[i] = a.astype(np.float32).transpose(2, 0, 1)
    np.testing.assert_array_equal(got, ref)


def test_image_iter_jitter_keeps_tail_minimal(tmp_path):
    """A non-hoistable aug (brightness jitter) between cast and normalize
    limits the hoisted suffix to the normalize alone."""
    from mxnet_tpu import image as image_mod

    path, _ = _write_rec(tmp_path, 4, (36, 44))
    it = image_mod.ImageIter(
        batch_size=2, data_shape=(3, 24, 24), path_imgrec=path,
        aug_list=image_mod.CreateAugmenter(
            data_shape=(3, 24, 24), rand_mirror=True, brightness=0.1,
            mean=np.array([1.0, 2.0, 3.0]), std=np.ones(3)))
    assert len(it._aug_tail) == 1
    assert isinstance(it._aug_tail[0], image_mod.ColorNormalizeAug)
    batch = it.next()
    assert batch.data[0].shape == (2, 3, 24, 24)
