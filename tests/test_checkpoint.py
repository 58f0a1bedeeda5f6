"""Native async multi-host checkpoint engine tests
(mxnet_tpu/checkpoint.AsyncCheckpointer).

CPU-hermetic throughout: multi-rank commits are faked by constructing
one checkpointer per rank in a single process (``rank=``/``world_size=``
— no barrier), crashes come from the MXTPU_FAULT_INJECT harness killing
a subprocess mid-save, and the real 2-process gang (rendezvous, shard
barrier, rank-0 manifest commit, watchdog abort, launch.py restart) runs
in the slow tier.
"""

import json
import os
import signal
import subprocess
import sys
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import checkpoint, numerics, resilience
from mxnet_tpu.checkpoint import AsyncCheckpointer, make_checkpointer
from mxnet_tpu.resilience import CheckpointCorrupt
from mxnet_tpu.test_utils import cpu_child_env

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _state():
    return {
        "params": [np.arange(12, dtype=np.float32).reshape(3, 4),
                   np.full((2, 2), 2.5, np.float64)],
        "opt": ({"m": np.zeros(3, np.float32)},
                np.arange(5, dtype=np.int32)),
        "meta": {"lr": 0.1, "name": "toy", "flag": True, "none": None},
        "steps": [1, 2, 3],
    }


def _assert_state_equal(a, b):
    assert sorted(a) == sorted(b)
    for i in range(2):
        got, want = a["params"][i], b["params"][i]
        assert got.dtype == want.dtype and np.array_equal(got, want)
    assert isinstance(a["opt"], tuple)
    assert np.array_equal(a["opt"][0]["m"], b["opt"][0]["m"])
    assert np.array_equal(a["opt"][1], b["opt"][1])
    assert a["opt"][1].dtype == b["opt"][1].dtype
    assert a["meta"] == b["meta"]
    assert a["steps"] == b["steps"]


# -- roundtrip + snapshot semantics --------------------------------------------

@pytest.mark.parametrize("async_save", [False, True])
def test_roundtrip(tmp_path, async_save):
    ck = AsyncCheckpointer(tmp_path, async_save=async_save,
                           rank=0, world_size=1)
    ck.save(3, _state())
    ck.wait()
    assert ck.all_steps() == [3]
    _assert_state_equal(ck.restore(3), _state())
    _assert_state_equal(ck.restore(), _state())   # latest


def test_copy_on_snapshot_survives_mutation(tmp_path):
    """save() must host-copy before returning: mutating the state pytree
    in place afterwards (what a training loop does) cannot leak into the
    bytes the background writer serializes."""
    w = np.arange(1024, dtype=np.float32)
    ck = AsyncCheckpointer(tmp_path, async_save=True, rank=0,
                           world_size=1)
    ck.save(1, {"w": w})
    w *= -1.0   # the very next "training step", racing the writer
    ck.wait()
    restored = ck.restore(1)
    assert np.array_equal(restored["w"],
                          np.arange(1024, dtype=np.float32))


def test_backpressure_exactly_one_outstanding(tmp_path, monkeypatch):
    """A second save() blocks until the in-flight commit lands — never
    two writers racing, never an unbounded snapshot queue."""
    gate = threading.Event()
    real = checkpoint._write_shard

    def gated(path, payload):
        gate.wait(timeout=30)
        return real(path, payload)

    monkeypatch.setattr(checkpoint, "_write_shard", gated)
    ck = AsyncCheckpointer(tmp_path, async_save=True, rank=0,
                           world_size=1)
    ck.save(1, {"w": np.zeros(4)})
    assert ck.in_flight() and ck.pending_step == 1

    done = threading.Event()

    def second():
        ck.save(2, {"w": np.ones(4)})
        done.set()

    t = threading.Thread(target=second, daemon=True)
    t.start()
    assert not done.wait(timeout=0.3)   # blocked on save 1's commit
    assert ck.pending_step == 1
    gate.set()
    t.join(timeout=30)
    ck.wait()
    assert ck.all_steps() == [1, 2]


def test_writer_error_propagates(tmp_path, monkeypatch):
    """An error in the background writer surfaces at the NEXT
    save()/wait(), and the engine stays usable afterwards."""
    real = checkpoint._write_shard
    monkeypatch.setattr(
        checkpoint, "_write_shard",
        lambda *a: (_ for _ in ()).throw(OSError("disk gone")))
    ck = AsyncCheckpointer(tmp_path, async_save=True, rank=0,
                           world_size=1)
    ck.save(1, {"w": np.zeros(4)})   # returns fine; writer fails
    with pytest.raises(OSError, match="disk gone"):
        ck.wait()
    ck.save(2, {"w": np.zeros(4)})   # error was consumed: save starts
    with pytest.raises(OSError, match="disk gone"):
        ck.save(3, {"w": np.zeros(4)})   # save 2's failure lands here
    monkeypatch.setattr(checkpoint, "_write_shard", real)
    ck.save(3, {"w": np.ones(4)})    # disk "repaired": engine recovers
    ck.wait()
    assert ck.all_steps() == [3]
    assert np.array_equal(ck.restore(3)["w"], np.ones(4))


# -- crash consistency (1-process harness) -------------------------------------

_CRASH_WORKER = os.path.join(_REPO, "tests", "ckpt_crash_worker.py")


@pytest.mark.faults
@pytest.mark.parametrize("site,mode", [
    ("crash_during_save", "async"),
    ("crash_before_manifest", "async"),
    ("crash_during_save", "sync"),
])
def test_crash_leaves_previous_checkpoint(tmp_path, site, mode):
    """Kill the process mid-save (torn shard) or between the shard write
    and the manifest rename: restore must always yield the PREVIOUS
    fully-committed checkpoint, and the next save GCs the orphan."""
    proc = subprocess.run(
        [sys.executable, _CRASH_WORKER, str(tmp_path), site, mode],
        env=cpu_child_env(), capture_output=True, text=True, timeout=120)
    assert proc.returncode == resilience.CRASH_EXIT_CODE, \
        (proc.returncode, proc.stdout[-2000:], proc.stderr[-2000:])
    assert f"injected crash at {site}" in proc.stderr

    ck = AsyncCheckpointer(tmp_path, async_save=False, rank=0,
                           world_size=1)
    # the half-written step 20 is invisible; step 10 restores intact
    assert ck.all_steps() == [10]
    restored = []
    assert resilience.resume_latest(ck, restored.append) == 10
    assert np.array_equal(restored[0]["w"],
                          np.full((64, 64), 10.0, np.float32))
    orphan = os.path.join(str(tmp_path), "step_0000000020")
    assert os.path.isdir(orphan)   # crash leftovers linger until...
    ck.save(30, {"w": np.zeros(2)})
    ck.wait()
    assert not os.path.exists(orphan)   # ...the next save GCs them
    assert ck.all_steps() == [10, 30]


@pytest.mark.faults
def test_corrupt_shard_falls_back(tmp_path, fault_inject):
    """``corrupt_shard:K`` bit-rots a committed shard: restore fails
    closed on the CRC and resume_latest falls back a step."""
    ck = AsyncCheckpointer(tmp_path, async_save=False, rank=0,
                           world_size=1)
    ck.save(10, {"w": np.full(8, 10.0)})
    fault_inject("corrupt_shard:0")
    ck.save(20, {"w": np.full(8, 20.0)})
    with pytest.raises(CheckpointCorrupt, match="checksum"):
        ck.restore(20)
    restored = []
    assert resilience.resume_latest(ck, restored.append) == 10
    assert np.array_equal(restored[0]["w"], np.full(8, 10.0))


def test_manifest_validation(tmp_path):
    ck = AsyncCheckpointer(tmp_path, async_save=False, rank=0,
                           world_size=1)
    ck.save(5, {"w": np.zeros(4)})
    mpath = os.path.join(ck._step_dir(5), "MANIFEST.json")
    with open(mpath) as f:
        m = json.load(f)

    def rewrite(d):
        with open(mpath, "w") as f:
            json.dump(d, f)

    rewrite({**m, "magic": "NOPE"})
    with pytest.raises(CheckpointCorrupt, match="magic"):
        ck.restore(5)
    rewrite({**m, "version": 99})
    with pytest.raises(CheckpointCorrupt, match="version"):
        ck.restore(5)
    rewrite({**m, "shards": []})
    with pytest.raises(CheckpointCorrupt, match="shard entries"):
        ck.restore(5)
    rewrite(m)
    ck.restore(5)   # pristine manifest restores again

    # truncated shard: framing length check fails closed
    spath = os.path.join(ck._step_dir(5), "shard_00000.mxtckpt")
    blob = open(spath, "rb").read()
    with open(spath, "wb") as f:
        f.write(blob[:-3])
    with pytest.raises(CheckpointCorrupt, match="truncated"):
        ck.restore(5)


def test_uncommitted_step_is_invisible(tmp_path):
    ck = AsyncCheckpointer(tmp_path, async_save=False, rank=0,
                           world_size=1)
    ck.save(7, {"w": np.zeros(2)})
    orphan = os.path.join(str(tmp_path), "step_0000000099")
    os.makedirs(orphan)
    open(os.path.join(orphan, "shard_00000.mxtckpt"), "wb").close()
    assert ck.all_steps() == [7]
    assert ck.latest_step() == 7
    with pytest.raises(CheckpointCorrupt, match="no manifest"):
        ck.restore(99)


# -- fake multi-rank commit + elastic restore ----------------------------------

def _save_two_rank(tmp_path, step, state):
    """Commit one checkpoint as TWO fake ranks sharing a directory.
    Rank 1 first: with barriers off, rank 0's manifest pass must find
    every rank entry already durable."""
    for rank in (1, 0):
        ck = AsyncCheckpointer(tmp_path, async_save=False, rank=rank,
                               world_size=2)
        ck.save(step, state)
    return ck


def test_two_rank_commit_restores_anywhere(tmp_path):
    """A 2-rank checkpoint reassembles under a different world size from
    the manifest alone (host pytree — no template needed off-cluster)."""
    _save_two_rank(tmp_path, 4, _state())
    ck = AsyncCheckpointer(tmp_path, async_save=False, rank=0,
                           world_size=1)
    with open(os.path.join(ck._step_dir(4), "MANIFEST.json")) as f:
        m = json.load(f)
    assert m["world_size"] == 2 and len(m["shards"]) == 2
    # both shards carry a disjoint, non-empty slice of the leaves
    slices = [set(sh["leaves"]) for sh in m["shards"]]
    assert slices[0] and slices[1] and not (slices[0] & slices[1])
    _assert_state_equal(ck.restore(4), _state())


def test_rank0_aborts_commit_on_missing_entry(tmp_path):
    """Rank 0 alone (rank 1's entry missing) must abort the commit and
    leave no manifest — the previous checkpoint stays authoritative."""
    ck0 = AsyncCheckpointer(tmp_path, async_save=False, rank=0,
                            world_size=2)
    with pytest.raises(mx.MXNetError, match="commit aborted"):
        ck0.save(4, _state())
    assert ck0.all_steps() == []


def test_world_size_mismatch_is_hard_error(tmp_path):
    _save_two_rank(tmp_path, 4, _state())
    ck = AsyncCheckpointer(tmp_path, async_save=False, rank=0,
                           world_size=3)
    ck._use_barrier = True   # pretend this is a REAL 3-host job
    with pytest.raises(mx.MXNetError, match="pass template"):
        ck.restore(4)


def test_template_validation_errors(tmp_path):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:4]), ("dp",))
    repl = NamedSharding(mesh, PartitionSpec())
    ck = AsyncCheckpointer(tmp_path, async_save=False, rank=0,
                           world_size=1)
    ck.save(1, {"w": np.zeros((4, 2), np.float32), "b": np.zeros(3)})
    with pytest.raises(mx.MXNetError, match="keys differ"):
        ck.restore(1, template={"w": repl, "EXTRA": repl, "b": repl})
    with pytest.raises(mx.MXNetError, match="shape"):
        ck.restore(1, template={
            "w": jax.ShapeDtypeStruct((4, 999), np.float32,
                                      sharding=repl),
            "b": repl})
    with pytest.raises(mx.MXNetError, match="dtype"):
        ck.restore(1, template={
            "w": jax.ShapeDtypeStruct((4, 2), np.int32, sharding=repl),
            "b": repl})
    out = ck.restore(1, template={
        "w": NamedSharding(mesh, PartitionSpec("dp")), "b": repl})
    assert isinstance(out["w"], jax.Array)
    assert out["w"].sharding.spec == PartitionSpec("dp")


def test_elastic_trainer_restore_bitwise(tmp_path):
    """The acceptance bar: a ShardedTrainer checkpoint written under one
    world size restores BITWISE-identically under another via the
    trainer's sharding template — and the snapshot is immune to the
    trainer training on after the save (satellite: snapshot-safe
    trainer_state)."""
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon import nn

    net = nn.HybridSequential(prefix="ck_")
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu", in_units=8),
                nn.Dense(4, in_units=16))
    net.initialize(init=mx.init.Xavier())
    tr = parallel.ShardedTrainer(
        net, gluon.loss.L2Loss(), "adam", {"learning_rate": 1e-2},
        mesh=parallel.make_mesh(dp=8))
    rng = np.random.RandomState(0)
    x = rng.randn(16, 8).astype(np.float32)
    y = rng.randn(16, 4).astype(np.float32)
    tr.step(x, y)
    tr.step(x, y)

    st = checkpoint.trainer_state(tr)
    frozen = [np.array(p, copy=True) for p in st["params"]]
    tr.step(x, y)   # mutate the trainer AFTER the snapshot
    tr.step(x, y)
    for before, after in zip(frozen, st["params"]):
        assert np.array_equal(before, after)   # snapshot never aliased

    _save_two_rank(tmp_path, 2, st)            # "written by 2 hosts"

    ck = AsyncCheckpointer(tmp_path, async_save=False, rank=0,
                           world_size=1)       # "restored by 1"
    restored = ck.restore(2, template=tr.state_template())
    checkpoint.load_trainer_state(tr, restored)
    for got, want in zip(tr._param_vals, frozen):
        got = np.asarray(got)
        assert got.dtype == want.dtype
        assert np.array_equal(got, want)       # bitwise, pre-mutation
    assert tr._num_update == int(st["num_update"])
    tr.step(x, y)   # restored trainer still trains


def test_elastic_restore_dp4_onto_dp2_tp2_bitwise(tmp_path):
    """PR 9 satellite: a checkpoint written under a pure ``dp=4`` mesh
    restores BITWISE onto a ``dp=2,tp=2`` mesh with Megatron TP rules,
    through `AsyncCheckpointer`'s template path — the PR 5 elastic
    mechanism aimed at the new shardings."""
    import jax
    from jax.sharding import PartitionSpec

    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon import nn

    def build(prefix):
        net = nn.HybridSequential(prefix=prefix)
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu", in_units=8),
                    nn.Dense(4, in_units=16))
        net.initialize(init=mx.init.Xavier())
        return net

    # writer: dp=4 over half the devices
    mx.random.seed(3)
    src = parallel.ShardedTrainer(
        build("ckel_"), gluon.loss.L2Loss(), "adam",
        {"learning_rate": 1e-2},
        mesh=parallel.make_mesh(dp=4, devices=jax.devices()[:4]))
    rng = np.random.RandomState(0)
    x = rng.randn(16, 8).astype(np.float32)
    y = rng.randn(16, 4).astype(np.float32)
    src.step(x, y)
    src.step(x, y)
    st = checkpoint.trainer_state(src)
    frozen = [np.array(p, copy=True) for p in st["params"]]
    _save_two_rank(tmp_path, 5, st)

    # reader: dp=2,tp=2 with TP rules over dense weights
    mx.random.seed(99)  # different init — restore must overwrite it
    rules = parallel.ShardingRules(rules=[
        (r"dense0_weight$", ("tp", None)),
        (r"dense1_weight$", (None, "tp")),
    ])
    dst = parallel.ShardedTrainer(
        build("ckel2_"), gluon.loss.L2Loss(), "adam",
        {"learning_rate": 1e-2},
        mesh=parallel.make_mesh(dp=2, tp=2), rules=rules)
    dst.step(x, y)  # stage + one step of divergent training
    ck = AsyncCheckpointer(tmp_path, async_save=False, rank=0,
                           world_size=1)
    restored = ck.restore(5, template=dst.state_template())
    checkpoint.load_trainer_state(dst, restored)
    tp_specs = [sh.spec for sh in dst._param_shardings]
    assert PartitionSpec("tp", None) in tp_specs  # template was TP
    for got, want, sh in zip(dst._param_vals, frozen,
                             dst._param_shardings):
        assert got.sharding.is_equivalent_to(sh, got.ndim)
        assert np.array_equal(np.asarray(got), want)  # bitwise
    assert dst._num_update == int(st["num_update"])
    dst.step(x, y)  # restored trainer still trains on the new mesh


def test_elastic_restore_dp8_onto_tp2_pp2_dp2_bitwise(tmp_path):
    """PR 17 acceptance: a checkpoint written under a pure ``dp=8``
    mesh restores BITWISE onto the 3-axis ``tp=2×pp=2×dp=2`` layout —
    the scanned trunk's layer-stack dim lands on the pp axis
    (`pp_rules` composed over `TRANSFORMER_TP_RULES`), through the same
    PR 5/9 elastic template path."""
    import jax
    from jax.sharding import PartitionSpec

    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo.bert import ScanTransformerEncoder

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (forced-host) devices")

    def build(seed):
        mx.random.seed(seed)
        net = ScanTransformerEncoder(num_layers=2, units=16,
                                     num_heads=2, hidden_size=32,
                                     dropout=0.0)
        net.initialize(init=mx.init.Xavier())
        net.hybridize()
        return net

    rng = np.random.RandomState(0)
    x = rng.randn(8, 4, 16).astype(np.float32)
    y = rng.randn(8, 4, 16).astype(np.float32)

    # writer: pure data parallel over all 8 devices
    src = parallel.ShardedTrainer(
        build(3), gluon.loss.L2Loss(), "adam", {"learning_rate": 1e-2},
        mesh=parallel.make_mesh(dp=8))
    src.step(x, y)
    src.step(x, y)
    st = checkpoint.trainer_state(src)
    frozen = [np.array(p, copy=True) for p in st["params"]]
    _save_two_rank(tmp_path, 17, st)

    # reader: the 3-axis pipeline layout — different init, must be
    # overwritten bitwise by the restore
    mesh = parallel.make_mesh(axes={"tp": 2, "pp": 2, "dp": 2})
    rules = parallel.combined_rules(parallel.pp_rules(mesh),
                                    parallel.TRANSFORMER_TP_RULES)
    dst = parallel.ShardedTrainer(
        build(99), gluon.loss.L2Loss(), "adam",
        {"learning_rate": 1e-2}, mesh=mesh, rules=rules)
    dst.step(x, y)  # stage + one step of divergent training
    ck = AsyncCheckpointer(tmp_path, async_save=False, rank=0,
                           world_size=1)
    restored = ck.restore(17, template=dst.state_template())
    checkpoint.load_trainer_state(dst, restored)
    specs = [tuple(sh.spec) for sh in dst._param_shardings]
    assert any("pp" in s and "tp" in s for s in specs)  # 3-axis layout
    for got, want, sh in zip(dst._param_vals, frozen,
                             dst._param_shardings):
        assert got.sharding.is_equivalent_to(sh, got.ndim)
        assert np.array_equal(np.asarray(got), want)  # bitwise
    assert dst._num_update == int(st["num_update"])
    dst.step(x, y)  # restored trainer still trains on the new layout
    parallel.set_default_mesh(None)


def test_gluon_trainer_checkpoint_roundtrip_sharded(tmp_path):
    """The imperative gluon Trainer checkpoints through the SAME
    trainer_state/template/load surface (duck-typed): params + adam
    moments + update counters round-trip bitwise onto the captured
    path's sharded placements."""
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon import nn

    def build():
        mx.random.seed(7)
        net = nn.HybridSequential()
        with net.name_scope():
            net.add(nn.Dense(16, activation="relu", in_units=8),
                    nn.Dense(3, in_units=16))
        net.initialize(init=mx.init.Xavier())
        net.hybridize()
        return net

    mesh = parallel.make_mesh(dp=2, tp=4)
    net = build()
    parallel.shard_model(net, mesh, mode="fsdp", min_size=8)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 1e-2})
    rng = np.random.RandomState(1)
    batches = [(rng.randn(16, 8).astype(np.float32),
                rng.randint(0, 3, (16,)).astype(np.float32))
               for _ in range(4)]
    for x, y in batches[:2]:
        tr.train_step(net, loss_fn, mx.nd.array(x), mx.nd.array(y))
    st = checkpoint.trainer_state(tr)
    ck = AsyncCheckpointer(tmp_path, async_save=False, rank=0,
                           world_size=1)
    ck.save(2, st)
    # train on, then restore: must rewind bitwise
    for x, y in batches[2:]:
        tr.train_step(net, loss_fn, mx.nd.array(x), mx.nd.array(y))
    restored = ck.restore(2, template=checkpoint.trainer_state_template(tr))
    checkpoint.load_trainer_state(tr, restored)
    for p, want in zip(tr._params, st["params"]):
        assert np.array_equal(p.data().asnumpy(), want)
    assert tr._optimizer.num_update == int(st["num_update"])
    # the restored trainer still trains on the sharded placements
    for x, y in batches[2:]:
        tr.train_step(net, loss_fn, mx.nd.array(x), mx.nd.array(y))
    parallel.set_default_mesh(None)


def test_elastic_restore_row_sharded_table_bitwise(tmp_path):
    """PR 18 acceptance: a row-sharded `ShardedEmbedding` table trained
    on a ``dp=8`` mesh (6-row shards) restores BITWISE onto a
    ``dp=2,tp=2`` layout (24-row shards, replicated over tp) through
    the elastic template path — shard sizes differ across the layouts,
    the bytes must not."""
    import jax
    from jax.sharding import PartitionSpec

    from mxnet_tpu import embedding, gluon, parallel
    from mxnet_tpu.gluon import nn

    if len(jax.devices()) < 8:
        pytest.skip("needs 8 (forced-host) devices")

    def build(seed, prefix):
        mx.random.seed(seed)
        net = nn.HybridSequential(prefix=prefix)
        with net.name_scope():
            net.add(embedding.ShardedEmbedding(48, 8),
                    nn.Dense(3, in_units=8, flatten=False))
        net.initialize(init=mx.init.Xavier())
        net.hybridize()
        return net

    def table_of(tr):
        (i, p), = [(i, p) for i, p in enumerate(tr._params)
                   if p.name.endswith("embed_table")]
        return i, p

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    rng = np.random.RandomState(5)
    batches = [(rng.randint(0, 48, (16,)).astype(np.float32),
                rng.randint(0, 3, (16,)).astype(np.float32))
               for _ in range(4)]

    # writer: table rows sharded 48/8 = 6 per device
    src_net = build(11, "ckemb_")
    src = gluon.Trainer(src_net.collect_params(), "adam",
                        {"learning_rate": 1e-2})
    parallel.shard_model(src_net, parallel.make_mesh(dp=8),
                         mode="fsdp", min_size=1, trainer=src)
    for x, y in batches[:2]:
        src.train_step(src_net, loss_fn, mx.nd.array(x), mx.nd.array(y))
    _, src_table = table_of(src)
    src_jax = src_table.data()._data
    assert src_jax.sharding.spec == PartitionSpec("dp", None)
    assert src_jax.sharding.shard_shape(src_jax.shape) == (6, 8)
    st = checkpoint.trainer_state(src)
    frozen = [np.array(p, copy=True) for p in st["params"]]
    _save_two_rank(tmp_path, 18, st)

    # reader: different init + layout — 24-row shards over dp=2
    dst_net = build(97, "ckemb2_")
    dst = gluon.Trainer(dst_net.collect_params(), "adam",
                        {"learning_rate": 1e-2})
    parallel.shard_model(dst_net, parallel.make_mesh(dp=2, tp=2),
                         mode="fsdp", min_size=1, trainer=dst)
    x, y = batches[2]
    dst.train_step(dst_net, loss_fn, mx.nd.array(x), mx.nd.array(y))
    ck = AsyncCheckpointer(tmp_path, async_save=False, rank=0,
                           world_size=1)
    restored = ck.restore(
        18, template=checkpoint.trainer_state_template(dst))
    checkpoint.load_trainer_state(dst, restored)
    ti, dst_table = table_of(dst)
    dst_jax = dst_table.data()._data
    assert dst_jax.sharding.shard_shape(dst_jax.shape) == (24, 8)
    for p, want in zip(dst._params, frozen):
        assert np.array_equal(p.data().asnumpy(), want)  # bitwise
    assert dst._optimizer.num_update == int(st["num_update"])
    # the restored table still trains row-sparse on the new layout
    for x, y in batches[2:]:
        dst.train_step(dst_net, loss_fn, mx.nd.array(x), mx.nd.array(y))
    parallel.set_default_mesh(None)


# -- integration: rollback / preemption / run_resilient / factory --------------

def test_async_save_overlapped_with_rollback(tmp_path, monkeypatch):
    """DivergenceMonitor rollback while a save is STILL IN FLIGHT: the
    recovery path drains the commit first (flush_inflight inside
    resume_latest), so the rollback restores the just-committed step —
    never a half-observed one."""
    gate = threading.Event()
    real = checkpoint._write_shard

    def gated(path, payload):
        gate.wait(timeout=30)
        return real(path, payload)

    ck = AsyncCheckpointer(tmp_path, async_save=True, rank=0,
                           world_size=1)
    ck.save(10, {"w": np.full(8, 1.0)})
    ck.wait()
    monkeypatch.setattr(checkpoint, "_write_shard", gated)
    ck.save(20, {"w": np.full(8, 2.0)})
    assert ck.in_flight()

    restored = {}
    mon = numerics.DivergenceMonitor(
        checkpointer=ck, set_state=restored.update, max_bad_steps=2)
    threading.Timer(0.3, gate.set).start()
    assert mon.observe(step=21, loss=float("nan")) is False
    assert mon.observe(step=22, loss=float("nan")) is True
    assert mon.recoveries == 1
    assert ck.latest_step() == 20   # the in-flight save DID commit
    assert np.array_equal(restored["w"], np.full(8, 2.0))


def test_preemption_completes_pending_commit(tmp_path, monkeypatch):
    """SIGTERM with a save in flight: the grace window finishes THAT
    commit; no new save is started (get_state must never be called)."""
    gate = threading.Event()
    real = checkpoint._write_shard

    def gated(path, payload):
        gate.wait(timeout=30)
        return real(path, payload)

    monkeypatch.setattr(checkpoint, "_write_shard", gated)
    ck = AsyncCheckpointer(tmp_path, async_save=True, rank=0,
                           world_size=1)
    ck.save(7, {"w": np.full(4, 7.0)})
    assert ck.in_flight()

    def boom():
        raise AssertionError("a NEW save was started in the grace window")

    with checkpoint.PreemptionHandler(ck, get_state=boom,
                                      get_step=lambda: 99) as h:
        assert h.maybe_checkpoint() is False   # not preempted yet
        os.kill(os.getpid(), signal.SIGTERM)
        assert h.preempted.is_set()
        threading.Timer(0.3, gate.set).start()
        assert h.maybe_checkpoint() is True
    assert ck.latest_step() == 7
    assert np.array_equal(ck.restore(7)["w"], np.full(4, 7.0))


@pytest.mark.faults
def test_run_resilient_with_async_engine(tmp_path, fault_inject):
    """run_resilient on the async engine end-to-end, including an
    injected SIGTERM preemption: drain-at-recovery + final wait() give
    the same trajectory as an uninterrupted synchronous run."""
    fault_inject("sigterm_at_step:7")
    state = {"w": np.full(4, 10.0)}

    def step_fn(step):
        w = state["w"]
        loss = float((w ** 2).sum())
        state["w"] = w - 0.1 * 2 * w
        return loss

    ck = AsyncCheckpointer(tmp_path, async_save=True, rank=0,
                           world_size=1)
    report = resilience.run_resilient(
        step_fn, ck, 20,
        get_state=lambda: {"w": state["w"].copy()},
        set_state=lambda s: state.update(w=np.asarray(s["w"]).copy()),
        checkpoint_every=5, max_restarts=3)
    assert report.preempted and report.restarts == 1
    assert report.final_step == 20
    # the grace window either commits the step-7 save or completes the
    # in-flight step-5 one — both are consistent resume points (the
    # trajectory is a pure function of the restored state)
    assert report.resumed_from[0] == 0 and report.resumed_from[1] in (5, 7)
    assert not ck.in_flight()
    assert ck.latest_step() == 20
    np.testing.assert_allclose(ck.restore(20)["w"],
                               np.full(4, 10.0) * 0.8 ** 20)


def test_make_checkpointer_backends(tmp_path, monkeypatch):
    msgs = []

    class Log:
        def info(self, m):
            msgs.append(m)

    ck = make_checkpointer(tmp_path / "a", logger=Log())
    assert isinstance(ck, AsyncCheckpointer)
    assert any("native" in m for m in msgs)

    ck = make_checkpointer(tmp_path / "b", backend="local", logger=Log())
    assert isinstance(ck, resilience.LocalCheckpointer)

    # orbax requested but unavailable: clean fallback, logged
    monkeypatch.setitem(sys.modules, "orbax", None)
    msgs.clear()
    ck = make_checkpointer(tmp_path / "c", backend="orbax", logger=Log())
    assert isinstance(ck, AsyncCheckpointer)
    assert any("falling back" in m for m in msgs)

    monkeypatch.setenv("MXTPU_CKPT_BACKEND", "local")
    ck = make_checkpointer(tmp_path / "d", logger=Log())
    assert isinstance(ck, resilience.LocalCheckpointer)

    with pytest.raises(mx.MXNetError, match="unknown backend"):
        make_checkpointer(tmp_path / "e", backend="nope", logger=Log())


def test_fsync_dir_helper(tmp_path):
    resilience.fsync_dir(str(tmp_path))           # real dir: no error
    resilience.fsync_dir(str(tmp_path / "gone"))  # missing: tolerated


def test_max_to_keep_prunes(tmp_path):
    ck = AsyncCheckpointer(tmp_path, max_to_keep=2, async_save=False,
                           rank=0, world_size=1)
    for s in (1, 2, 3, 4):
        ck.save(s, {"w": np.zeros(2)})
    assert ck.all_steps() == [3, 4]


# -- 2-process gang: real barriers, real crash, real restart -------------------

_DIST_WORKER = os.path.join(_REPO, "tests", "ckpt_dist_worker.py")


def _serial_replay(num_steps):
    sys.path.insert(0, os.path.join(_REPO, "tests"))
    try:
        import ckpt_dist_worker as w
    finally:
        sys.path.pop(0)
    state = w.initial_state()
    for _ in range(num_steps):
        w.apply_step(state)
    return state


@pytest.mark.slow
@pytest.mark.faults
@pytest.mark.parametrize("site", ["crash_during_save",
                                  "crash_before_manifest"])
def test_two_process_crash_consistency(tmp_path, site):
    """The acceptance bar, 2-process edition: rank 0 dies mid-commit
    (torn shard, or after the shard barrier but before the manifest
    rename), the survivor's barrier is aborted by the collective
    watchdog, launch.py relaunches the gang, both ranks resume from the
    last COMMITTED step, and the final state matches a serial replay."""
    num_steps = 20
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "tools", "launch.py"),
         "-n", "2", "--launcher", "local", "--max-restarts", "1",
         "--port", str(port), "--",
         sys.executable, _DIST_WORKER, str(tmp_path), str(num_steps)],
        env={**cpu_child_env(),
             "MXTPU_COLLECTIVE_TIMEOUT": "8",
             "MXTPU_WATCHDOG_ACTION": "abort",
             "CKPT_CRASH_SITE": site,
             "CKPT_CRASH_RANK": "0",
             "CKPT_CRASH_STEP": "10"},
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, (proc.stdout[-2000:],
                                  proc.stderr[-2000:])
    assert f"injected crash at {site}" in proc.stderr
    assert "restarting gang" in proc.stderr
    expected = _serial_replay(num_steps)
    for rank in range(2):
        assert (f"worker {rank}: ckpt run done at step {num_steps} "
                f"w00={expected['w'][0, 0]:.9g}") in proc.stdout
        # the torn step-10 checkpoint is invisible: both ranks resume
        # from the last COMMITTED step
        assert f"worker {rank}: resumed from step 5" in proc.stdout

    # the final checkpoint: committed by 2 ranks, restorable by 1
    ck = AsyncCheckpointer(os.path.join(str(tmp_path), "ckpt"),
                           async_save=False, rank=0, world_size=1)
    assert ck.latest_step() == num_steps
    with open(os.path.join(ck._step_dir(num_steps),
                           "MANIFEST.json")) as f:
        assert json.load(f)["world_size"] == 2
    final = ck.restore(num_steps)
    assert np.array_equal(final["w"], expected["w"])
    assert np.array_equal(final["b"], expected["b"])


# -- epoch fencing on the durable commit (split-brain guard) -------------------

def test_manifest_carries_gang_epoch(tmp_path):
    """attach_gang stamps the gang epoch into every rank entry and into
    MANIFEST.json; manifests restore normally and verify() hands the
    stamp back (the serving reload gate reads it)."""
    ck = AsyncCheckpointer(tmp_path, async_save=False, rank=0,
                           world_size=1)
    assert ck.attach_gang(lambda: 7, lambda: 7) is ck
    ck.save(3, _state())
    ck.wait()
    with open(os.path.join(ck._step_dir(3), "MANIFEST.json")) as f:
        assert json.load(f)["gang_epoch"] == 7
    assert ck.verify(3)["gang_epoch"] == 7
    _assert_state_equal(ck.restore(3), _state())


def test_stale_epoch_manifest_commit_aborted(tmp_path, monkeypatch):
    """The tentpole abort path: the fence moved on while this rank was
    out to lunch (paused rank 0, partition minority).  The manifest
    rename must NOT happen — MXNetError, one ckpt_fenced event, no
    orphan .tmp, and the PREVIOUS manifest stays the restore point."""
    from mxnet_tpu import telemetry

    ev_path = str(tmp_path / "ev.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", ev_path)
    telemetry.reset()
    try:
        ckdir = tmp_path / "ckpt"
        ck = AsyncCheckpointer(ckdir, async_save=False, rank=0,
                               world_size=1)
        ck.attach_gang(lambda: 1, lambda: 1)
        ck.save(1, _state())
        ck.wait()
        assert checkpoint.latest_manifest_step(ckdir) == 1
        # a quorum elsewhere committed epoch 3: we are now a zombie
        ck.attach_gang(lambda: 1, lambda: 3)
        with pytest.raises(resilience.MXNetError, match="FENCED"):
            ck.save(2, _state())
        # the previous manifest remains the restore point
        assert checkpoint.latest_manifest_step(ckdir) == 1
        _assert_state_equal(ck.restore(), _state())
        # no half-published manifest anywhere
        orphans = [f for root, _dirs, files in os.walk(ckdir)
                   for f in files if f.endswith(".tmp")]
        assert orphans == []
    finally:
        telemetry.reset()
    with open(ev_path) as f:
        ev = [json.loads(ln) for ln in f if ln.strip()]
    fenced = [e for e in ev if e.get("event") == "ckpt_fenced"]
    assert len(fenced) == 1
    assert fenced[0]["step"] == 2
    assert fenced[0]["epoch"] == 1
    assert fenced[0]["committed"] == 3


def test_manifest_commit_fails_closed_on_unreachable_fence(tmp_path):
    """No fence answer -> no rename: a rank that cannot read the fence
    might BE the fenced minority, so the commit aborts rather than
    gambling on a stale restore point."""
    ck = AsyncCheckpointer(tmp_path, async_save=False, rank=0,
                           world_size=1)

    def down():
        raise OSError("gang kv unreachable")

    ck.attach_gang(lambda: 1, down)
    with pytest.raises(resilience.MXNetError, match="FENCED"):
        ck.save(1, _state())
    assert checkpoint.latest_manifest_step(tmp_path) is None


def test_unfenced_checkpointer_unchanged(tmp_path):
    """No attach_gang -> no stamp, no fence check: the pre-v8 surface
    is bitwise what it was."""
    ck = AsyncCheckpointer(tmp_path, async_save=False, rank=0,
                           world_size=1)
    ck.save(1, _state())
    ck.wait()
    with open(os.path.join(ck._step_dir(1), "MANIFEST.json")) as f:
        assert "gang_epoch" not in json.load(f)
