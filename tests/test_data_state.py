"""Exactly-once resumable input pipeline (gluon/data/state.py).

Every test asserts the sample LEDGER, not just API plumbing: across a
checkpoint/restore, an elastic N→M reshape, or a quarantine replay, the
union of delivered sample sets must cover the epoch exactly once — zero
re-read, zero skipped.  Fault sites exercised here: ``worker_hang:K``
(receive watchdog) and ``data_skew:K`` (slow-but-alive workers must NOT
trip it).
"""

import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, telemetry
from mxnet_tpu import resilience
from mxnet_tpu.checkpoint import (AsyncCheckpointer, PeerSnapshotStore,
                                  _peer_unwrap, _peer_wrap)
from mxnet_tpu.gluon.data import (DataLoader, DataLoaderWorkerError,
                                  DataPipelineState, DevicePrefetcher,
                                  epoch_order)
from mxnet_tpu.numerics import DivergenceMonitor
from mxnet_tpu.resilience import (CheckpointCorrupt, LocalCheckpointer,
                                  run_resilient)
from mxnet_tpu.test_utils import cpu_child_env

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TRACE_REPORT = os.path.join(_REPO, "tools", "trace_report.py")


@pytest.fixture(autouse=True)
def _telemetry_clean(monkeypatch):
    monkeypatch.delenv("MXTPU_TELEMETRY_PATH", raising=False)
    monkeypatch.delenv("MXTPU_TELEMETRY", raising=False)
    telemetry.reset()
    yield
    telemetry.reset()


def _index_dataset(n):
    """Samples ARE their indices — a delivered batch names exactly which
    samples it carried, so tests can keep a ledger."""
    return gluon.data.SimpleDataset(np.arange(n, dtype=np.int64))


def _vals(batch):
    return [int(v) for v in np.asarray(batch.asnumpy()).ravel()]


def _drain(source):
    out = []
    for batch in source:
        out.extend(_vals(batch))
    return out


# -- epoch_order / DataPipelineState unit --------------------------------------

def test_epoch_order_pure_function_of_seed_and_epoch():
    a = epoch_order(7, 0, 100)
    assert np.array_equal(a, epoch_order(7, 0, 100))   # deterministic
    assert np.array_equal(np.sort(a), np.arange(100))  # a permutation
    assert not np.array_equal(a, epoch_order(7, 1, 100))
    assert not np.array_equal(a, epoch_order(8, 0, 100))
    assert np.array_equal(epoch_order(7, 0, 10, shuffle=False),
                          np.arange(10))


@pytest.mark.parametrize("world", [1, 2, 3, 5])
def test_shards_partition_the_remaining_epoch(world):
    """order[cursor:][r::w] over all ranks == the un-consumed sample
    set, exactly once, for any world size and any cursor."""
    n = 41   # deliberately ragged
    for cursor in (0, 7, 40):
        shards = []
        for r in range(world):
            st = DataPipelineState(n, seed=3, rank=r, world=world)
            st.cursor = cursor
            shards.extend(st.shard().tolist())
            assert st.shard_len() == len(st.shard())
        expect = epoch_order(3, 0, n)[cursor:]
        assert sorted(shards) == sorted(expect.tolist())


def test_state_dict_roundtrips_through_json_and_keeps_local_shard():
    st = DataPipelineState(100, seed=9, rank=1, world=3)
    st.advance(4)
    st.quarantine([(0, 7)])
    sd = json.loads(json.dumps(st.state_dict()))

    st2 = DataPipelineState(100, seed=0, rank=0, world=2)
    st2.load_state_dict(sd)
    assert (st2.rank, st2.world) == (0, 2)   # LOCAL: the N→M re-shard
    assert st2.seed == 9 and st2.cursor == st.cursor
    assert st2.samples_seen == st.samples_seen
    assert st2.is_quarantined(0, 7)

    with pytest.raises(ValueError):
        DataPipelineState(99, seed=0).load_state_dict(sd)   # length
    with pytest.raises(ValueError):
        DataPipelineState(100).load_state_dict(dict(sd, version=99))
    with pytest.raises(ValueError):
        DataPipelineState(100).load_state_dict(dict(sd, cursor=101))


def test_skip_moves_cursor_but_not_samples_seen():
    st = DataPipelineState(32, seed=0, shuffle=False)
    st.advance(4)
    st.skip(4)
    assert st.cursor == 8 and st.samples_seen == 4
    assert st.batch_idx == 2 and st.last_delivered == (0, 0)


# -- DataLoader: resume / reshape / quarantine ledgers -------------------------

@pytest.mark.parametrize("num_workers", [0, 2])
def test_loader_resume_is_exactly_once(num_workers):
    n, bs = 64, 8
    loader = DataLoader(_index_dataset(n), batch_size=bs, shuffle=True,
                        seed=5, num_workers=num_workers)
    it = iter(loader)
    first = []
    for _ in range(3):
        first.extend(_vals(next(it)))
    sd = loader.state_dict()
    assert sd["cursor"] == 24 and loader.samples_seen == 24
    close = getattr(it, "close", None)
    if close:
        close()

    fresh = DataLoader(_index_dataset(n), batch_size=bs, shuffle=True,
                       seed=0, num_workers=num_workers)
    fresh.load_state_dict(sd)
    rest = _drain(fresh)
    assert sorted(first + rest) == list(range(n))   # zero re-read/skip
    assert telemetry.event_counts().get("data_resume") == 1
    # next epoch reshuffles and covers the epoch again
    assert sorted(_drain(fresh)) == list(range(n))
    assert fresh.state_dict()["epoch"] == 2


def test_elastic_3_to_2_reshape_mid_epoch_is_exactly_once():
    n, bs = 96, 8
    mk = lambda r, w: DataLoader(_index_dataset(n), batch_size=bs,
                                 shuffle=True, seed=13, rank=r,
                                 world_size=w)
    old = [mk(r, 3) for r in range(3)]
    before = []
    for loader in old:   # 2 rounds each, then rank 2 "dies"
        it = iter(loader)
        for _ in range(2):
            before.extend(_vals(next(it)))
    states = [ld.state_dict() for ld in old]
    # the GLOBAL position is rank-agnostic (only rank/world are local)
    globals_ = [{k: v for k, v in s.items() if k not in ("rank", "world")}
                for s in states]
    assert globals_[0] == globals_[1] == globals_[2]

    survivors = [mk(r, 2) for r in range(2)]
    after = []
    for loader in survivors:
        loader.load_state_dict(states[0])
        after.extend(_drain(loader))
    assert sorted(before + after) == list(range(n))
    assert len(before) + len(after) == n


def test_quarantined_batch_skipped_loudly_with_one_event_each():
    n, bs = 40, 8
    loader = DataLoader(_index_dataset(n), batch_size=bs, shuffle=True,
                        seed=2)
    planned = _drain(DataLoader(_index_dataset(n), batch_size=bs,
                                shuffle=True, seed=2))
    loader.quarantine([(0, 1), (0, 3)])
    got = _drain(loader)
    poisoned = set(planned[bs:2 * bs]) | set(planned[3 * bs:4 * bs])
    assert sorted(got) == sorted(set(planned) - poisoned)
    assert telemetry.event_counts().get("batch_quarantined") == 2
    sd = loader.state_dict()
    assert sd["epoch"] == 1 and loader.samples_seen == n - 2 * bs


def test_loader_without_seed_rejects_state_api():
    loader = DataLoader(_index_dataset(8), batch_size=4)
    with pytest.raises(RuntimeError, match="seed="):
        loader.state_dict()
    with pytest.raises(ValueError, match="seed="):
        DataLoader(_index_dataset(8), batch_size=4, seed=1,
                   sampler=gluon.data.SequentialSampler(8))


# -- receive watchdog (worker_hang / data_skew fault sites) --------------------

@pytest.mark.faults
def test_worker_hang_trips_receive_watchdog(fault_inject, monkeypatch):
    monkeypatch.setenv("MXTPU_DATA_TIMEOUT", "0.2")
    monkeypatch.setenv("MXTPU_DATA_HANG_SECS", "1.5")
    fault_inject("worker_hang:1")
    loader = DataLoader(_index_dataset(32), batch_size=8, seed=0,
                        num_workers=2)
    with pytest.raises(DataLoaderWorkerError, match="batch 1"):
        _drain(loader)
    assert telemetry.event_counts().get("data_worker_timeout") == 1


@pytest.mark.faults
def test_data_skew_is_slow_but_alive(fault_inject, monkeypatch):
    """Skewed (straggler) workers delay batches without killing them —
    the watchdog must NOT fire and the ledger must stay exact."""
    monkeypatch.setenv("MXTPU_DATA_TIMEOUT", "30")
    fault_inject("data_skew:2")
    loader = DataLoader(_index_dataset(32), batch_size=8, seed=0,
                        num_workers=2)
    assert sorted(_drain(loader)) == list(range(32))
    assert not telemetry.event_counts().get("data_worker_timeout")


# -- DevicePrefetcher: deferred accounting -------------------------------------

def test_prefetcher_accounting_is_delivery_exact():
    """The producer thread runs ahead; the cursor must reflect only what
    the CONSUMER took, so a state_dict mid-epoch restores without
    re-reading the batches the producer had prefetched."""
    n, bs = 64, 8
    loader = DataLoader(_index_dataset(n), batch_size=bs, shuffle=True,
                        seed=4)
    pf = DevicePrefetcher(loader, depth=3)
    it = iter(pf)
    first = []
    for _ in range(3):
        first.extend(_vals(next(it)))
    sd = pf.state_dict()
    assert sd["cursor"] == 24   # not 24 + prefetched
    pf.close()   # discards in-flight batches; their tokens never commit
    assert loader.state_dict()["cursor"] == 24

    fresh_loader = DataLoader(_index_dataset(n), batch_size=bs,
                              shuffle=True, seed=4)
    fresh = DevicePrefetcher(fresh_loader, depth=3)
    fresh.load_state_dict(sd)
    rest = _drain(fresh)
    assert sorted(first + rest) == list(range(n))
    assert fresh.samples_seen == n and fresh.last_batch_id() == (0, 7)


# -- checkpoint path: stamp, sidecar, manifest, peer frames --------------------

def test_data_state_stamp_crc_fails_closed():
    sd = {"version": 1, "cursor": 8}
    stamp = resilience.data_state_stamp(sd)
    assert resilience.data_state_unstamp(stamp) == sd
    assert resilience.data_state_unstamp(None) is None   # lenient
    with pytest.raises(CheckpointCorrupt):
        resilience.data_state_unstamp(
            dict(stamp, state={"version": 1, "cursor": 9}))
    with pytest.raises(CheckpointCorrupt):
        resilience.data_state_unstamp(dict(stamp, version=99))
    with pytest.raises(CheckpointCorrupt):
        resilience.data_state_unstamp("junk")


def test_local_checkpointer_sidecar_roundtrip(tmp_path):
    ck = LocalCheckpointer(tmp_path)
    ck.save(5, {"w": np.arange(4.0)})
    assert ck.data_state(5) is None          # pre-data-state checkpoint
    ck.save(6, {"w": np.arange(4.0)}, data_state={"version": 1,
                                                  "cursor": 16})
    assert ck.data_state(6) == {"version": 1, "cursor": 16}
    assert ck.data_state() == {"version": 1, "cursor": 16}   # latest


@pytest.mark.parametrize("async_save", [False, True])
def test_async_manifest_carries_data_state(tmp_path, async_save):
    loader = DataLoader(_index_dataset(32), batch_size=8, seed=1)
    it = iter(loader)
    next(it)
    ck = AsyncCheckpointer(tmp_path, async_save=async_save, rank=0,
                           world_size=1)
    ck.save(1, {"w": np.arange(8.0)})                 # no data state
    ck.save(2, {"w": np.arange(8.0)},
            data_state=loader.state_dict())
    ck.wait()
    assert ck.data_state(1) is None                   # lenient absence
    assert ck.data_state(2) == loader.state_dict()
    assert ck.data_state() == loader.state_dict()     # latest
    np.testing.assert_array_equal(ck.restore(1)["w"], np.arange(8.0))

    # a reader process that never heard of data state still restores
    reader = AsyncCheckpointer(tmp_path, async_save=False, rank=0,
                               world_size=1)
    np.testing.assert_array_equal(reader.restore(2)["w"], np.arange(8.0))


def test_manifest_data_state_crc_fails_closed(tmp_path):
    ck = AsyncCheckpointer(tmp_path, async_save=False, rank=0,
                           world_size=1)
    ck.save(3, {"w": np.zeros(4)}, data_state={"version": 1, "cursor": 8})
    mpath = os.path.join(ck._step_dir(3), "MANIFEST.json")
    with open(mpath) as f:
        m = json.load(f)
    m["data_state"]["state"]["cursor"] = 9   # bit-rot the position
    with open(mpath, "w") as f:
        json.dump(m, f)
    with pytest.raises(CheckpointCorrupt):
        ck.data_state(3)


def test_peer_wrap_roundtrip_and_bare_compat(tmp_path):
    state = {"w": np.arange(4.0)}
    ds = {"version": 1, "cursor": 24}
    s, d = _peer_unwrap(_peer_wrap(state, ds))
    assert d == ds and s is state
    s, d = _peer_unwrap(state)          # pre-wrap snapshot
    assert s is state and d is None

    from mxnet_tpu import distributed
    kv = distributed.FileKV(str(tmp_path))
    store = PeerSnapshotStore(0, kv=kv).start()
    try:
        store.hold_own(4, _peer_wrap(state, ds))
        np.testing.assert_array_equal(store.own_at(4)["w"], state["w"])
        assert store.data_state_at(0, 4) == ds
        store.hold_own(5, state)        # bare: old writer, new reader
        np.testing.assert_array_equal(store.own_at(5)["w"], state["w"])
        assert store.data_state_at(0, 5) is None
        assert store.data_state_at(0, 99) is None
    finally:
        store.close()


def test_peer_only_step_serves_data_state_without_manifest(tmp_path):
    """Elastic recovery can restore from a peer-RAM step that never got
    a disk manifest — data_state() must fall through to the held wrap
    instead of raising on the missing MANIFEST.json."""
    from mxnet_tpu import distributed
    kv = distributed.FileKV(str(tmp_path / "kv"))
    store = PeerSnapshotStore(0, kv=kv).start()
    try:
        ck = AsyncCheckpointer(tmp_path / "ck", async_save=False, rank=0,
                               world_size=1).attach_peers(store, every=1)
        ds = {"version": 1, "cursor": 40}
        ck.save(7, {"w": np.zeros(2)}, data_state=ds)
        import shutil
        shutil.rmtree(ck._step_dir(7))
        assert ck.data_state(7) == ds    # from the peer wrap
    finally:
        store.close()


# -- run_resilient: lockstep rewind of trainer + sample stream -----------------

def test_run_resilient_rewinds_sample_stream_in_lockstep(tmp_path):
    n, bs, steps = 64, 8, 8
    loader = DataLoader(_index_dataset(n), batch_size=bs, shuffle=True,
                        seed=3)
    box = {"it": None}
    seen = {}          # step -> sample tuple; replay must match bitwise
    armed = {"crash": True}

    def step_fn(step):
        if box["it"] is None:
            box["it"] = iter(loader)
        vals = tuple(_vals(next(box["it"])))
        if step in seen:
            assert seen[step] == vals   # replay trains on SAME batch
        seen[step] = vals
        if armed["crash"] and step == 5:
            armed["crash"] = False
            raise RuntimeError("injected step failure")
        return 0.0

    def set_data_state(sd):
        loader.load_state_dict(sd)
        box["it"] = None

    report = run_resilient(
        step_fn, LocalCheckpointer(tmp_path), steps,
        get_state=lambda: {"w": 0.0}, set_state=lambda s: None,
        checkpoint_every=2, get_data_state=loader.state_dict,
        set_data_state=set_data_state)
    assert report.restarts == 1 and report.resumed_from == [0, 4]
    assert sorted(v for t in seen.values() for v in t) == list(range(n))
    # the restore rewound samples_seen along with the cursor, so the
    # replayed steps 4-5 don't double-count
    assert loader.samples_seen == n


# -- divergence rollback → quarantine → replay (bitwise parity) ----------------

def test_rollback_quarantine_replay_matches_clean_run_bitwise(tmp_path):
    """The e2e loop: a poisoned batch NaNs the loss, DivergenceMonitor
    rolls back, the pipeline rewinds + quarantines it, and the replay —
    which skips it loudly — lands on weights BITWISE equal to a run
    that never saw the batch."""
    n, bs, lr = 48, 8, 0.1
    rng = np.random.RandomState(0)
    x = rng.rand(n, 4).astype(np.float32)
    y = rng.rand(n, 1).astype(np.float32)
    x[16:24] = np.nan          # batch ordinal 2 under shuffle=False
    ds = gluon.data.ArrayDataset(x, y)
    w0 = rng.rand(4, 1).astype(np.float32)

    def sgd(w, batch):
        bx = np.asarray(batch[0].asnumpy(), np.float32)
        by = np.asarray(batch[1].asnumpy(), np.float32)
        err = bx @ w - by
        loss = float(np.mean(err ** 2))
        return w - lr * (2.0 / len(bx)) * (bx.T @ err), loss

    # faulty run: checkpoint at step 0, train until the NaN trips
    loader = DataLoader(ds, batch_size=bs, seed=11, shuffle=False)
    ck = LocalCheckpointer(tmp_path)
    box = {"w": w0.copy()}
    ck.save(1, {"w": box["w"]}, data_state=loader.state_dict())
    mon = DivergenceMonitor(checkpointer=ck, set_state=box.update,
                            max_bad_steps=1)
    mon.data_pipeline = loader   # what Trainer.attach_data_pipeline does
    rolled = False
    it = iter(loader)
    for step in range(n // bs):
        batch = next(it)
        w_next, loss = sgd(box["w"], batch)
        if mon.observe(step=step, loss=loss,
                       batch_indices=[loader.last_batch_id()]):
            rolled = True
            break          # restored: box["w"] back to w0, loader rewound
        box["w"] = w_next
    assert rolled and mon.quarantined == [(0, 2)]
    replay_losses = []
    for batch in loader:   # quarantine-honoring replay
        box["w"], loss = sgd(box["w"], batch)
        replay_losses.append(loss)
    assert telemetry.event_counts().get("batch_quarantined") == 1
    assert telemetry.event_counts().get("data_resume") == 1

    # oracle: same seed, never computes on the poisoned batch
    w = w0.copy()
    oracle_losses = []
    clean = DataLoader(ds, batch_size=bs, seed=11, shuffle=False)
    for i, batch in enumerate(clean):
        if i == 2:
            continue
        w, loss = sgd(w, batch)
        oracle_losses.append(loss)
    assert replay_losses == oracle_losses        # bitwise float equality
    assert np.array_equal(box["w"], w)


def test_trainer_attach_data_pipeline_wires_monitor():
    p = gluon.Parameter("p_weight", shape=(3,), dtype="float32")
    p.initialize(init=mx.init.Zero())
    trainer = gluon.Trainer([p], "sgd", {"learning_rate": 0.1},
                            kvstore=None)
    trainer.divergence_monitor = DivergenceMonitor(max_bad_steps=50)
    loader = DataLoader(_index_dataset(8), batch_size=4, seed=0)
    assert trainer.attach_data_pipeline(loader) is trainer
    assert trainer.divergence_monitor.data_pipeline is loader
    assert trainer._batch_ids() is None          # nothing delivered yet
    next(iter(loader))
    assert trainer._batch_ids() == [(0, 0)]


# -- io iterators --------------------------------------------------------------

def test_ndarray_iter_state_roundtrip_mid_epoch():
    data = np.arange(48).reshape(12, 4).astype(np.float32)
    label = np.arange(12).astype(np.float32)
    it = mx.io.NDArrayIter(data, label, batch_size=3, shuffle=True)
    first = [it.next() for _ in range(2)]
    sd = it.state_dict()

    it2 = mx.io.NDArrayIter(data, label, batch_size=3, shuffle=True)
    it2.load_state_dict(sd)
    rest_a = [b.data[0].asnumpy() for b in it]
    rest_b = [b.data[0].asnumpy() for b in it2]
    assert len(rest_a) == len(rest_b) == 2
    for a, b in zip(rest_a, rest_b):
        np.testing.assert_array_equal(a, b)
    covered = np.concatenate([first[0].data[0].asnumpy(),
                              first[1].data[0].asnumpy()] + rest_b)
    np.testing.assert_array_equal(
        np.sort(covered.ravel()), np.sort(data.ravel()))
    with pytest.raises(ValueError):
        it2.load_state_dict(dict(sd, idx=list(range(5))))


def test_prefetching_iter_refetches_in_flight_batch():
    data = np.arange(40).reshape(10, 4).astype(np.float32)

    def mk():
        return mx.io.NDArrayIter(data, np.zeros(10), batch_size=2)

    pre = mx.io.PrefetchingIter(mk())
    got = [pre.next().data[0].asnumpy() for _ in range(2)]
    sd = pre.state_dict()   # one batch sits fetched-but-undelivered

    pre2 = mx.io.PrefetchingIter(mk()).load_state_dict(sd)
    rest = [b.data[0].asnumpy() for b in pre2]
    covered = np.concatenate(got + rest)
    np.testing.assert_array_equal(covered, data)   # nothing skipped


# -- telemetry v7 / trace_report ----------------------------------------------

def test_step_record_samples_seen_validation():
    rec = {"type": "step", "run": "r", "t": 0.0,
           "v": telemetry.SCHEMA_VERSION, "step": 0, "path": "eager",
           "skipped": False, "wall_us": 1.0, "interval_us": 1.0,
           "breakdown_us": {k: 0.0 for k in telemetry._BREAKDOWN_KEYS},
           "shares": {k: 1.0 / len(telemetry._BREAKDOWN_KEYS)
                      for k in telemetry._BREAKDOWN_KEYS},
           "collective_bytes": 0, "collective_buckets": 0}
    telemetry.validate_record(dict(rec, samples_seen=128))
    telemetry.validate_record(rec)                  # absent is fine
    for bad in (-1, True, 1.5, "128"):
        with pytest.raises(ValueError, match="samples_seen"):
            telemetry.validate_record(dict(rec, samples_seen=bad))


def test_trace_report_renders_data_pipeline_section(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    evs = [
        {"event": "data_resume", "epoch": 1, "cursor": 24,
         "samples_seen": 88, "reread_samples": 0, "skipped_samples": 0,
         "world": 2, "loader_rank": 0},
        {"event": "batch_quarantined", "epoch": 1, "batch": 3,
         "samples": 8},
        {"event": "data_worker_timeout", "batch": 5},
    ]
    with open(path, "w") as f:
        for e in evs:
            rec = {"type": "event", "run": "r", "t": 0.0,
                   "v": telemetry.SCHEMA_VERSION}
            rec.update(e)
            f.write(json.dumps(rec) + "\n")
    r = subprocess.run(
        [sys.executable, _TRACE_REPORT, path, "--validate"],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "data pipeline:" in r.stdout
    assert "resumes: 1  re-read samples 0  skipped samples 0" in r.stdout
    assert "NOT exactly-once" not in r.stdout
    assert "quarantined batches skipped on replay: 1 (8 sample(s))" \
        in r.stdout
    assert "worker-hang timeouts: 1" in r.stdout

    with open(path, "a") as f:
        f.write(json.dumps({"type": "event", "run": "r", "t": 0.0,
                            "v": telemetry.SCHEMA_VERSION,
                            "event": "data_resume",
                            "reread_samples": 8,
                            "skipped_samples": 0}) + "\n")
    r = subprocess.run([sys.executable, _TRACE_REPORT, path],
                       capture_output=True, text=True, timeout=60)
    assert r.returncode == 0
    assert "** NOT exactly-once **" in r.stdout


# -- SIGKILL'd run resumes from the async manifest -----------------------------

_KILLED_CHILD = r"""
import json, os, signal, sys
import numpy as np
sys.path.insert(0, {repo!r})
from mxnet_tpu import gluon
from mxnet_tpu.checkpoint import AsyncCheckpointer

ckdir, outpath = sys.argv[1], sys.argv[2]
ds = gluon.data.SimpleDataset(np.arange(64, dtype=np.int64))
loader = gluon.data.DataLoader(ds, batch_size=8, seed=5, shuffle=True)
it = iter(loader)
delivered = []
for _ in range(3):
    delivered += [int(v) for v in np.asarray(next(it).asnumpy()).ravel()]
ck = AsyncCheckpointer(ckdir, async_save=True, rank=0, world_size=1)
ck.save(3, {{"w": np.arange(4.0)}}, data_state=loader.state_dict())
ck.wait()
with open(outpath, "w") as f:
    json.dump(delivered, f)
    f.flush(); os.fsync(f.fileno())
os.kill(os.getpid(), signal.SIGKILL)   # no cleanup, no atexit
"""


def test_sigkilled_run_resumes_exactly_once_from_async_manifest(tmp_path):
    script = str(tmp_path / "child.py")
    outpath = str(tmp_path / "delivered.json")
    ckdir = str(tmp_path / "ck")
    with open(script, "w") as f:
        f.write(_KILLED_CHILD.format(repo=_REPO))
    r = subprocess.run([sys.executable, script, ckdir, outpath],
                       env=cpu_child_env(), capture_output=True, text=True,
                       timeout=180)
    assert r.returncode == -signal.SIGKILL, (r.returncode, r.stderr)
    with open(outpath) as f:
        delivered = json.load(f)
    assert len(delivered) == 24

    ck = AsyncCheckpointer(ckdir, async_save=False, rank=0, world_size=1)
    sd = ck.data_state()
    assert sd is not None and sd["cursor"] == 24
    loader = gluon.data.DataLoader(
        _index_dataset(64), batch_size=8, seed=0, shuffle=True)
    loader.load_state_dict(sd)
    rest = _drain(loader)
    assert sorted(delivered + rest) == list(range(64))   # exactly once
