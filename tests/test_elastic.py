"""Elastic gang recovery (mxnet_tpu/resilience.ElasticGang): the health
plane (heartbeats, phi failure detector, straggler naming), the
peer-replicated RAM snapshot store, the epoch-consensus reshape
protocol, and the end-to-end surviving-a-SIGKILL paths — in-process
(threads over one FileKV) for tier-1, and real multi-process gangs
(tests/elastic_gang_worker.py, tools/launch.py --elastic) under
@slow."""

import json
import os
import signal
import socket
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from mxnet_tpu import distributed, resilience, telemetry
from mxnet_tpu.checkpoint import PeerSnapshotStore
from mxnet_tpu.test_utils import cpu_child_env

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_WORKER = os.path.join(_REPO, "tests", "elastic_gang_worker.py")
_LAUNCH = os.path.join(_REPO, "tools", "launch.py")
_TRACE_REPORT = os.path.join(_REPO, "tools", "trace_report.py")
_GANG_KV = os.path.join(_REPO, "tools", "gang_kv.py")


# -- the serial reference simulation -------------------------------------------

def _sim_losses(num_steps, phases, n=8):
    """Replicate elastic_gang_worker.py's arithmetic exactly.

    ``phases`` is [(start_step, members), ...]: the membership in force
    from that step on.  A reshape rolls the gang back to the common
    snapshot (= w at the TOP of the boundary step), so a straight serial
    run that switches membership at the boundary IS "the clean M-rank
    run from the same snapshot" the acceptance criterion names — the
    rolled-back executions only produced loss records the re-run
    overwrote.
    """
    w = np.full(n, 1.0, dtype=np.float64)
    losses = {}
    for step in range(num_steps):
        members = None
        for start, m in sorted(phases):
            if step >= start:
                members = m
        total = 0.0
        for r in sorted(members):
            total += float((r + 1) * float(w.sum()))
        loss = total / len(members)
        losses[step] = loss
        w = w * 0.99 - 0.01 * (loss / w.size)
    return losses, w


def _kv_allreduce(gang, kv, step, contribution):
    """The worker's lockstep KV mean (see elastic_gang_worker.py)."""
    epoch = gang.epoch
    kv.put_json(f"red/{epoch}/{step}/{gang.rank}",
                {"v": float(contribution)})
    gang.barrier(f"red{step}")
    total = 0.0
    for r in sorted(gang.members):
        total += float(kv.get_json(f"red/{epoch}/{step}/{r}")["v"])
    return total / len(gang.members)


# -- control plane units -------------------------------------------------------

@pytest.fixture(params=["file", "tcp"])
def kv_backend(request, tmp_path):
    """Both gang control planes behind the same get/put/scan/delete
    surface: FileKV on a tmp dir, TcpKV against an in-process
    GangKVServer (no filesystem at all).  Yields (mode, make) where
    ``make(rank)`` returns a fresh client — thread-gang tests give each
    rank its own connection, exactly like separate processes would."""
    if request.param == "file":
        kvdir = str(tmp_path / "kv")

        def make(rank=None):
            return distributed.FileKV(kvdir, rank=rank)

        yield request.param, make
    else:
        server = distributed.GangKVServer(lease_ttl=5.0).start()
        clients = []

        def make(rank=None):
            c = distributed.TcpKV(server.addr, rank=rank)
            clients.append(c)
            return c

        yield request.param, make
        for c in clients:
            try:
                c.close()
            except Exception:           # noqa: BLE001 — teardown
                pass
        server.stop()


def test_kv_roundtrip(kv_backend):
    _, make = kv_backend
    kv = make(rank=0)
    kv.put_json("epoch/current", {"epoch": 3, "members": [0, 2]})
    assert kv.get_json("epoch/current") == {"epoch": 3,
                                            "members": [0, 2]}
    for r in range(3):
        kv.put_json(f"hb/{r}", {"rank": r, "seq": 1})
    assert [k for k, _ in kv.scan("hb")] == ["hb/0", "hb/1", "hb/2"]
    kv.delete("hb/1")
    kv.delete("hb/1")                       # idempotent
    assert [k for k, _ in kv.scan("hb")] == ["hb/0", "hb/2"]
    assert kv.get_json("hb/1", default="gone") == "gone"
    with pytest.raises(ValueError):
        kv.put("../escape", b"nope")
    # float values must survive the JSON hop bitwise (the lockstep
    # allreduce in the elastic tests depends on it)
    v = 1.0 / 3.0 * 7.3
    kv.put_json("red/0/0/0", {"v": v})
    assert kv.get_json("red/0/0/0")["v"] == v


def test_kv_put_if_epoch_fencing(kv_backend):
    """The epoch fence (both planes): an epoch-stamped write at or
    above the highest committed epoch lands and advances the fence; a
    STALE one is rejected with FencedWrite and the stored value is
    untouched.  The fence is server-side state, visible to every
    client."""
    _, make = kv_backend
    kv = make(rank=0)
    assert kv.committed_epoch() == 0
    kv.put_if_epoch("a", b"one", 1)         # advances the fence
    assert kv.get("a") == b"one"
    assert kv.committed_epoch() == 1
    kv.put_if_epoch("a", b"two", 1)         # equal epoch: accepted
    kv.put_if_epoch("a", b"three", 3)       # newer: accepted + advances
    assert kv.committed_epoch() == 3
    with pytest.raises(distributed.FencedWrite):
        kv.put_if_epoch("a", b"stale", 2)
    assert kv.get("a") == b"three"          # rejected write left no trace
    kv.put("plain", b"ok")                  # un-stamped writes unaffected
    assert kv.get("plain") == b"ok"
    # a SECOND client sees the same fence — this is what stops a
    # resumed zombie that still believes in the old epoch
    kv2 = make(rank=1)
    assert kv2.committed_epoch() == 3
    with pytest.raises(distributed.FencedWrite):
        kv2.put_json_if_epoch("a", {"v": 1}, 0)
    assert kv2.get("a") == b"three"


@pytest.mark.faults
def test_tcpkv_fence_survives_coordinator_failover(fault_inject,
                                                   monkeypatch):
    """The fence is part of the coordinator's replicated state frame:
    after the daemon dies and a standby promotes itself, a stale-epoch
    write must STILL be rejected — a failover that forgot the fence
    would reopen the split-brain window at the worst possible
    moment."""
    monkeypatch.setenv("MXTPU_KV_FAILOVER_STAGGER", "0.1")
    server = distributed.GangKVServer(lease_ttl=2.0).start()
    c0 = c1 = None
    try:
        c0 = distributed.TcpKV(server.addr, rank=0, lease_ttl=2.0)
        c1 = distributed.TcpKV(server.addr, rank=1, lease_ttl=2.0)
        c0.put_if_epoch("epoch/marker", b"e3", 3)
        # committed_epoch doubles as a state-frame refresh: the fence
        # it reads is the fence a promotion will replay
        assert c0.committed_epoch() == 3
        assert c1.committed_epoch() == 3
        time.sleep(0.8)                 # a renewal refreshes the
        fault_inject("kill_coordinator")  # clients' state frames
        c0.put_json("arm", {"v": 0})    # mutation -> daemon dies mid-op
        assert server.died
        assert c0.failovers == 1
        # the promoted coordinator still enforces the fence
        assert c1.committed_epoch() == 3
        with pytest.raises(distributed.FencedWrite):
            c1.put_if_epoch("epoch/marker", b"stale", 2)
        assert c1.get("epoch/marker") == b"e3"
    finally:
        for c in (c1, c0):
            if c is not None:
                c.close()
        server.stop()


def test_failure_detector_confirms_silence(kv_backend):
    _, make = kv_backend
    kv = make(rank=0)
    hb = resilience.HeartbeatPublisher(kv, 1, interval=0.02)
    det = resilience.FailureDetector(kv, 0, [0, 1], timeout=0.3,
                                     check_interval=0.01)
    hb.publish_once()
    assert det.poll(force=True) == set()
    time.sleep(0.35)                        # silence beyond the timeout
    assert det.poll(force=True) == {1}
    hb.publish_once()                       # resurrection: seq moves on
    assert det.poll(force=True) == set()


@pytest.mark.faults
def test_heartbeat_loss_fault_looks_like_death(fault_inject, tmp_path):
    """heartbeat_loss:K — wedged-but-alive must be indistinguishable
    from death: publishes are suppressed, the detector confirms."""
    kv = distributed.FileKV(str(tmp_path))
    hb = resilience.HeartbeatPublisher(kv, 1, interval=0.02)
    det = resilience.FailureDetector(kv, 0, [0, 1], timeout=0.25,
                                     check_interval=0.01)
    hb.publish_once()
    assert det.poll(force=True) == set()
    seq = kv.get_json("hb/1")["seq"]
    fault_inject("heartbeat_loss:1")
    for _ in range(5):
        hb.publish_once()                   # all suppressed
    assert kv.get_json("hb/1")["seq"] == seq
    time.sleep(0.3)
    assert det.poll(force=True) == {1}


def test_straggler_monitor_names_laggard(tmp_path):
    kv = distributed.FileKV(str(tmp_path))
    det = resilience.FailureDetector(kv, 0, [0, 1, 2], timeout=60.0,
                                     check_interval=0.0)
    kv.put_json("hb/1", {"rank": 1, "seq": 1, "step": 3})
    kv.put_json("hb/2", {"rank": 2, "seq": 1, "step": 19})
    det.poll(force=True)
    mon = resilience.StragglerMonitor(det, window=3,
                                      share_threshold=0.5)
    assert mon.observe(20, 0.9) is None     # window not yet full
    assert mon.observe(21, 0.9) is None
    assert mon.observe(22, 0.9) == 1        # rank 1 is furthest behind
    assert mon.observe(23, 0.9) is None     # rate-limited to one/window


def test_peer_snapshot_roundtrip(tmp_path):
    kv = distributed.FileKV(str(tmp_path))
    s0 = PeerSnapshotStore(0, kv=kv).start()
    s1 = PeerSnapshotStore(1, kv=kv).start()
    try:
        state = {"w": np.arange(4.0), "opt": 3.5}
        s0.hold_own(4, state, epoch=0)
        assert s0.own_at(4)["opt"] == 3.5
        assert s0.send_to(1, 4, state, epoch=0)
        assert s1.held_steps(0) == [4]
        got = s0.fetch(1, 0, 4)             # over the socket
        np.testing.assert_array_equal(got["w"], state["w"])
        assert got["opt"] == 3.5
        assert s0.fetch(1, 0, 99) is None   # holder doesn't have it
        assert kv.get_json("held/1/0")["steps"] == [4]
    finally:
        s0.close()
        s1.close()


def test_peer_snapshot_retention_and_epoch_filter(tmp_path):
    kv = distributed.FileKV(str(tmp_path))
    # retain_s=0: pure count-based pruning
    s = PeerSnapshotStore(1, kv=kv, keep=2, retain_s=0.0)
    for step in (2, 4, 6):
        s._store(0, step, 0, b"x")
    assert s.held_steps(0) == [4, 6]
    # a large time floor overrides the count cap: everything inside the
    # detection window survives (the reshape needs a COMMON step)
    s2 = PeerSnapshotStore(2, kv=kv, keep=2, retain_s=3600.0)
    for step in (2, 4, 6, 8):
        s2._store(0, step, 0, b"x")
    assert s2.held_steps(0) == [2, 4, 6, 8]
    # epoch filtering: pre-reshape snapshots are never advertised as
    # restore points for the reshaped gang
    s2._store(0, 10, 1, b"x")
    assert s2.held_steps(0, epoch=1) == [10]
    assert kv.get_json("held/2/0") == {"steps": [10], "epoch": 1}


def test_peer_snapshot_fence_drops_stale_frames(tmp_path):
    """A receiver whose gang committed a newer epoch must DROP frames
    stamped with an older one — a fenced trainer's RAM replica must
    never survive as a restore point — while still ACKING the sender
    (containment, not a wedge: the zombie learns its fate from the
    epoch check, not from a hung socket)."""
    kv = distributed.FileKV(str(tmp_path))
    s0 = PeerSnapshotStore(0, kv=kv).start()
    s1 = PeerSnapshotStore(1, kv=kv).start()
    try:
        state = {"w": np.arange(4.0)}
        s1.fence(2)
        assert s0.send_to(1, 4, state, epoch=1)   # acked ...
        assert s1.held_steps(0, epoch=1) == []    # ... but NOT stored
        assert s0.send_to(1, 6, state, epoch=2)   # current epoch lands
        assert s1.held_steps(0, epoch=2) == [6]
        s1.fence(1)                               # the fence never moves
        assert s0.send_to(1, 8, state, epoch=1)   # backwards
        assert s1.held_steps(0, epoch=1) == []
    finally:
        s0.close()
        s1.close()


def test_buddy_ring(tmp_path):
    kv = distributed.FileKV(str(tmp_path))
    gang = resilience.ElasticGang(0, 4, kv=kv)
    assert gang.buddy_of(0) == 1
    assert gang.buddy_of(3) == 0
    assert gang.buddy_of(0, [0, 2]) == 2
    assert gang.buddy_of(2, [0, 2]) == 0


def test_join_fresh_gang_writes_epoch_record(kv_backend):
    """join() on a fresh gang must leave the epoch-0 record behind
    (it routes through start()), so later joiners have a record to
    read."""
    _, make = kv_backend
    kv = make(rank=0)
    gang = resilience.ElasticGang(0, 2, kv=kv,
                                  heartbeat_interval=0.05,
                                  heartbeat_timeout=1.0)
    try:
        assert gang.join() is None
        cur = kv.get_json("epoch/current")
        assert cur is not None
        assert cur["epoch"] == 0 and cur["members"] == [0, 1]
    finally:
        gang.stop()


# -- TcpKV specifics: leases, watches, failover, partition ---------------------

def test_tcpkv_lease_expiry_replaces_mtime_freshness():
    """Keys under the ephemeral prefixes ride the client's lease: when
    the client stops renewing (process death), the server expires them;
    durable keys survive."""
    server = distributed.GangKVServer(lease_ttl=0.3).start()
    c1 = None
    try:
        c0 = distributed.TcpKV(server.addr, rank=0)
        c1 = distributed.TcpKV(server.addr, rank=1)
        c0.put_json("hb/0", {"rank": 0, "seq": 1})
        c0.put_json("epoch/current", {"epoch": 0})
        assert c1.get_json("hb/0")["seq"] == 1
        c0.close()                      # renewals stop; lease expires
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline \
                and c1.get_json("hb/0") is not None:
            time.sleep(0.05)
        assert c1.get_json("hb/0") is None
        # the client's own failover advertisement is leased too
        assert c1.get_json("failover/0") is None
        assert c1.get_json("epoch/current") == {"epoch": 0}
    finally:
        if c1 is not None:
            c1.close()
        server.stop()


def test_tcpkv_watch_wakes_on_prefix_change():
    """watch(prefix) long-polls: it must block while nothing under the
    prefix changes and wake promptly on a put."""
    server = distributed.GangKVServer(lease_ttl=5.0).start()
    c0 = c1 = None
    try:
        c0 = distributed.TcpKV(server.addr, rank=0)
        c1 = distributed.TcpKV(server.addr, rank=1)
        got = {}

        def waiter():
            got["keys"] = c1.watch("leave/", timeout=10.0)

        t = threading.Thread(target=waiter, daemon=True)
        t.start()
        time.sleep(0.2)
        assert "keys" in got or t.is_alive()    # still blocked
        c0.put_json("leave/1", {"rank": 1, "at_step": 7})
        t.join(timeout=10)
        assert not t.is_alive(), "watch never woke"
        # an unrelated prefix does not satisfy a fresh watch
        t2 = threading.Thread(
            target=lambda: got.update(other=c1.watch("admit/",
                                                     timeout=0.3)),
            daemon=True)
        t2.start()
        c0.put_json("leave/2", {"rank": 2})
        t2.join(timeout=10)
        assert not t2.is_alive()
    finally:
        for c in (c0, c1):
            if c is not None:
                c.close()
        server.stop()


@pytest.mark.faults
def test_kill_coordinator_failover(fault_inject, monkeypatch):
    """kill_coordinator — the daemon drops dead mid-mutation, cutting
    every client off with no reply.  The lowest live rank must promote
    itself on its standby socket, replay the state frame, and the
    higher rank must adopt the new address and still see pre-death
    writes."""
    monkeypatch.setenv("MXTPU_KV_FAILOVER_STAGGER", "0.1")
    server = distributed.GangKVServer(lease_ttl=2.0).start()
    c0 = c1 = None
    try:
        c0 = distributed.TcpKV(server.addr, rank=0)
        c1 = distributed.TcpKV(server.addr, rank=1)
        c0.put_json("epoch/current", {"epoch": 0, "members": [0, 1]})
        c1.get_json("epoch/current")    # both have live connections
        time.sleep(0.8)                 # a renewal refreshes the
        fault_inject("kill_coordinator")  # clients' state frames
        c0.put_json("arm", {"v": 0})    # mutation -> daemon dies mid-op
        assert server.died
        # the very put that killed the server must have been retried
        # through the failover and landed
        assert c0.failovers == 1
        assert c0.get_json("arm") == {"v": 0}
        # pre-death state survived the replay, and the OTHER client
        # adopts the promoted coordinator transparently
        assert c1.get_json("epoch/current") == {"epoch": 0,
                                                "members": [0, 1]}
        c1.put_json("after/1", {"v": 1})
        assert c0.get_json("after/1") == {"v": 1}
    finally:
        for c in (c1, c0):
            if c is not None:
                c.close()
        server.stop()


@pytest.mark.faults
def test_net_partition_cuts_one_rank(fault_inject):
    """net_partition:K — rank K's client is cut off (every op raises
    GangKVError) while other ranks keep working."""
    server = distributed.GangKVServer(lease_ttl=5.0).start()
    c0 = c1 = None
    try:
        c0 = distributed.TcpKV(server.addr, rank=0)
        c1 = distributed.TcpKV(server.addr, rank=1)
        fault_inject("net_partition:1")
        with pytest.raises(distributed.GangKVError):
            c1.put_json("x", {"v": 1})
        with pytest.raises(distributed.GangKVError):
            c1.get_json("x")
        c0.put_json("y", {"v": 2})      # the un-partitioned rank
        assert c0.get_json("y") == {"v": 2}
    finally:
        for c in (c0, c1):
            if c is not None:
                try:
                    c.close()
                except Exception:       # noqa: BLE001 — teardown
                    pass
        server.stop()


# -- in-process gang: reshape, loss parity, report CLI -------------------------

def _run_thread_rank(rank, world, kv_make, num_steps, snap_every, die_at,
                     out):
    kv = kv_make(rank)
    gang = resilience.ElasticGang(rank, world, kv=kv,
                                  peer_snap_every=snap_every,
                                  heartbeat_interval=0.05,
                                  heartbeat_timeout=0.5)
    gang.start()
    state = {"w": np.full(8, 1.0, dtype=np.float64), "opt": 0.0}
    step, losses, infos = 0, {}, []
    try:
        while step < num_steps:
            if die_at is not None and step == die_at:
                gang.hb.stop()              # silent death: no heartbeat
                out[rank] = {"status": "died", "losses": losses,
                             "gang": gang}
                return
            try:
                gang.step_tick(step, state=state)
                loss = _kv_allreduce(
                    gang, kv, step,
                    (rank + 1) * float(state["w"].sum()))
            except resilience.RankFailure as rf:
                info = gang.recover(rf)
                st = info.shards[rank]
                state = {"w": np.array(st["w"], dtype=np.float64),
                         "opt": float(st["opt"])}
                step = info.snap_step
                infos.append(info)
                continue
            losses[step] = loss
            state["w"] = state["w"] * 0.99 - 0.01 * (loss /
                                                     state["w"].size)
            state["opt"] += loss
            step += 1
        out[rank] = {"status": "done", "losses": losses, "gang": gang,
                     "infos": infos, "w": state["w"]}
    except Exception as e:                  # noqa: BLE001 — surfaced
        out[rank] = {"status": "error", "error": repr(e), "gang": gang}


def test_thread_gang_survives_silent_death(kv_backend, tmp_path,
                                           monkeypatch):
    """3 ranks over one control plane (both backends — over TcpKV there
    is NO shared filesystem); rank 1 goes silent at step 6.  The
    survivors must reshape to world 2 from the newest COMMON peer
    snapshot (step 4: the buddy's copy of the dead rank lags one
    round), and the post-reshape loss trajectory must be bitwise equal
    to a clean 2-rank run from that snapshot.  The resulting event log
    must flow through the trace_report CLI."""
    _, kv_make = kv_backend
    ev_path = str(tmp_path / "ev.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", ev_path)
    telemetry.reset()
    num_steps, snap_every, die_at = 10, 2, 6
    out = {}
    threads = [threading.Thread(
        target=_run_thread_rank,
        args=(r, 3, kv_make, num_steps, snap_every,
              die_at if r == 1 else None, out))
        for r in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    try:
        assert not any(t.is_alive() for t in threads), "gang wedged"
        assert out[1]["status"] == "died"
        for r in (0, 2):
            assert out[r]["status"] == "done", out[r]
        for r in (0, 2):
            (info,) = out[r]["infos"]
            assert info.source == "peer"
            assert info.snap_step == 4
            assert info.members == [0, 2]
            assert info.epoch == 1
            assert info.dead == [1]
        # bitwise parity: pre-reshape with [0,1,2], post with [0,2]
        sim, sim_w = _sim_losses(num_steps, [(0, [0, 1, 2]),
                                             (4, [0, 2])])
        for r in (0, 2):
            assert out[r]["losses"] == sim
            np.testing.assert_array_equal(out[r]["w"], sim_w)
        # the dead rank's pre-death losses agree up to the rollback
        for s in range(4):
            assert out[1]["losses"][s] == sim[s]
    finally:
        for res in out.values():
            res["gang"].stop()
        telemetry.reset()                   # close the sink

    # injected-death log through the report CLI
    proc = subprocess.run(
        [sys.executable, _TRACE_REPORT, ev_path, "--validate"],
        env=cpu_child_env(), capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "resilience:" in proc.stdout
    assert "dead: rank 1" in proc.stdout
    assert "reshape: epoch 1 world 2" in proc.stdout
    assert "from peer" in proc.stdout


# -- planned drain / scheduled admit / scale policy ----------------------------

def _run_elastic_rank(rank, world, kv_make, num_steps, snap_every, out,
                      *, join=False, leave_after=None, step_s=0.0):
    """Thread rank with the full traffic-elastic surface: optional
    late join (scheduled admit) and optional planned departure
    (plan_leave at ``leave_after`` + drain_margin)."""
    kv = kv_make(rank)
    gang = resilience.ElasticGang(rank, world, kv=kv,
                                  peer_snap_every=snap_every,
                                  heartbeat_interval=0.05,
                                  heartbeat_timeout=2.0)
    state = {"w": np.full(8, 1.0, dtype=np.float64), "opt": 0.0}
    step, losses, infos = 0, {}, []
    planned_at = None

    def adopt(info):
        st = info.shards.get(rank)
        if st is None:                  # fresh joiner: any replica's w
            st = dict(next(iter(info.shards.values())))
            st["opt"] = 0.0
        return {"w": np.array(st["w"], dtype=np.float64),
                "opt": float(st["opt"])}

    try:
        if join:
            info = gang.join()
            assert info is not None
            state = adopt(info)
            step = info.snap_step
            infos.append(info)
        else:
            gang.start()
        while step < num_steps:
            if leave_after is not None and step == leave_after \
                    and planned_at is None:
                planned_at = gang.plan_leave(step + gang.drain_margin)
            try:
                gang.step_tick(step, state=state)
                loss = _kv_allreduce(
                    gang, kv, step,
                    (rank + 1) * float(state["w"].sum()))
            except resilience.RankFailure as rf:
                try:
                    info = gang.recover(rf)
                except resilience.GangEvicted:
                    out[rank] = {"status": "evicted", "losses": losses,
                                 "gang": gang, "at": step}
                    return
                state = adopt(info)
                step = info.snap_step
                infos.append(info)
                continue
            losses[step] = loss
            state["w"] = state["w"] * 0.99 - 0.01 * (loss /
                                                     state["w"].size)
            state["opt"] += loss
            step += 1
            if step_s:
                time.sleep(step_s)
        out[rank] = {"status": "done", "losses": losses, "gang": gang,
                     "infos": infos, "w": state["w"]}
    except Exception as e:                  # noqa: BLE001 — surfaced
        out[rank] = {"status": "error", "error": repr(e), "gang": gang}


def test_thread_gang_planned_drain_zero_lost_steps(kv_backend, tmp_path,
                                                   monkeypatch):
    """Preemption-aware drain: rank 1 announces at step 4 that it will
    leave at step 6 (drain_margin 2).  Every member snapshots at
    EXACTLY step 6 and reshapes with no detection window and no
    rollback — the leaver produced exactly 6 losses (zero lost steps)
    and the survivors' trajectory is bitwise equal to a clean run that
    switches membership at the boundary.  The event log must carry the
    planned markers through the trace_report fleet section."""
    _, kv_make = kv_backend
    ev_path = str(tmp_path / "ev.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", ev_path)
    telemetry.reset()
    num_steps, snap_every = 10, 2
    out = {}
    threads = [threading.Thread(
        target=_run_elastic_rank,
        args=(r, 3, kv_make, num_steps, snap_every, out),
        kwargs={"leave_after": 4 if r == 1 else None})
        for r in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    try:
        assert not any(t.is_alive() for t in threads), "gang wedged"
        assert out[1]["status"] == "evicted", out[1]
        for r in (0, 2):
            assert out[r]["status"] == "done", out[r]
            (info,) = out[r]["infos"]
            assert info.planned is True
            assert info.snap_step == 6      # at_step = 4 + margin(2)
            assert info.members == [0, 2]
            assert info.source == "peer"
        sim, sim_w = _sim_losses(num_steps, [(0, [0, 1, 2]),
                                             (6, [0, 2])])
        for r in (0, 2):
            assert out[r]["losses"] == sim
            np.testing.assert_array_equal(out[r]["w"], sim_w)
        # the leaver computed every step up to the boundary and NONE
        # was rolled back: zero lost steps
        assert sorted(out[1]["losses"]) == list(range(6))
        for s in range(6):
            assert out[1]["losses"][s] == sim[s]
    finally:
        for res in out.values():
            res["gang"].stop()
        telemetry.reset()

    with open(ev_path) as f:
        ev = [json.loads(ln) for ln in f if ln.strip()]
    drained = [e for e in ev if e.get("event") == "rank_drained"]
    assert any(e.get("rank") == 1 for e in drained)
    recs = [e for e in ev if e.get("event") == "elastic_recover"]
    assert recs and all(e.get("planned") for e in recs)
    sched = [e for e in ev
             if e.get("event") == "gang_drain_scheduled"]
    assert any(e.get("at_step") == 6 for e in sched)

    proc = subprocess.run(
        [sys.executable, _TRACE_REPORT, ev_path, "--validate"],
        env=cpu_child_env(), capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "fleet:" in proc.stdout
    assert "drained: rank 1" in proc.stdout
    assert "reshape latency: planned" in proc.stdout


def test_thread_gang_scheduled_admit_zero_lost_steps(kv_backend):
    """A joiner arriving mid-run is admitted at a SCHEDULED future step
    (join_req -> admit/plan), so the running ranks never roll back:
    they produce a loss for every step of the run, and all three ranks
    end bitwise identical."""
    _, kv_make = kv_backend
    num_steps, snap_every = 12, 2
    out = {}
    threads = [threading.Thread(
        target=_run_elastic_rank,
        args=(r, 2, kv_make, num_steps, snap_every, out),
        kwargs={"step_s": 0.08}) for r in range(2)]
    for t in threads:
        t.start()
    time.sleep(0.2)
    tj = threading.Thread(
        target=_run_elastic_rank,
        args=(2, 2, kv_make, num_steps, snap_every, out),
        kwargs={"join": True, "step_s": 0.08})
    tj.start()
    threads.append(tj)
    for t in threads:
        t.join(timeout=60)
    try:
        assert not any(t.is_alive() for t in threads), "gang wedged"
        for r in range(3):
            assert out[r]["status"] == "done", out.get(r)
        info0 = out[0]["infos"][0]
        admit_step = info0.snap_step
        assert info0.members == [0, 1, 2]
        assert info0.planned is True
        sim, sim_w = _sim_losses(num_steps, [(0, [0, 1]),
                                             (admit_step, [0, 1, 2])])
        for r in range(3):
            for s, v in out[r]["losses"].items():
                assert v == sim[s], (r, s)
            np.testing.assert_array_equal(out[r]["w"], sim_w)
        # zero lost steps: the base ranks computed EVERY step once
        for r in (0, 1):
            assert sorted(out[r]["losses"]) == list(range(num_steps))
    finally:
        for res in out.values():
            res["gang"].stop()


# -- split-brain: partition fencing + zombie containment -----------------------

def _run_partition_rank(rank, world, kv_make, num_steps, snap_every, out,
                        *, step_s=0.05):
    """Thread rank for the partition matrix.  On a KV cut (GangKVError
    mid-allreduce, or GangFenced out of step_tick/recover) the rank
    waits for the heal, probes the fence with a STALE-epoch write —
    which must be REJECTED: the zero-durable-writes pin — and rejoins
    via park_fenced."""
    kv = kv_make(rank)
    gang = resilience.ElasticGang(rank, world, kv=kv,
                                  peer_snap_every=snap_every,
                                  heartbeat_interval=0.05,
                                  heartbeat_timeout=0.5)
    gang.start()
    state = {"w": np.full(8, 1.0, dtype=np.float64), "opt": 0.0}
    step, losses, infos = 0, {}, []
    fenced = rejoined = False
    probe_rejected = probe_committed = 0

    def adopt(info):
        st = info.shards.get(rank)
        if st is None:                  # readmitted: any replica's w
            st = dict(next(iter(info.shards.values())))
            st["opt"] = 0.0
        return {"w": np.array(st["w"], dtype=np.float64),
                "opt": float(st["opt"])}

    try:
        while step < num_steps:
            try:
                gang.step_tick(step, state=state)
                loss = _kv_allreduce(
                    gang, kv, step,
                    (rank + 1) * float(state["w"].sum()))
            except (resilience.GangFenced, distributed.GangKVError):
                fenced = True
                stale = gang.epoch
                # wait until the cut heals AND the majority has
                # committed the next epoch — the fence the stale probe
                # below must bounce off
                t0 = time.monotonic()
                while time.monotonic() - t0 < 20:
                    try:
                        cur = kv.get_json("epoch/current")
                        if cur and int(cur.get("epoch", 0)) > stale:
                            break
                    except Exception:   # noqa: BLE001 — still cut
                        pass
                    time.sleep(0.05)
                try:
                    kv.put_if_epoch(f"zombie/{rank}", b"stale", stale)
                    probe_committed += 1
                except distributed.FencedWrite:
                    probe_rejected += 1
                info = gang.park_fenced(timeout=30.0)
                rejoined = True
                if info is not None:
                    state = adopt(info)
                    step = info.snap_step
                    infos.append(info)
                continue
            except resilience.RankFailure as rf:
                info = gang.recover(rf)
                state = adopt(info)
                step = info.snap_step
                infos.append(info)
                continue
            losses[step] = loss
            state["w"] = state["w"] * 0.99 - 0.01 * (loss /
                                                     state["w"].size)
            state["opt"] += loss
            if step_s:
                time.sleep(step_s)
            step += 1
        out[rank] = {"status": "done", "losses": losses, "gang": gang,
                     "infos": infos, "w": state["w"], "fenced": fenced,
                     "rejoined": rejoined,
                     "probe_rejected": probe_rejected,
                     "probe_committed": probe_committed}
    except Exception as e:                  # noqa: BLE001 — surfaced
        out[rank] = {"status": "error", "error": repr(e), "gang": gang}


@pytest.mark.faults
def test_thread_gang_partition_minority_fences_and_rejoins(
        kv_backend, fault_inject, tmp_path, monkeypatch):
    """The split-brain tentpole, end to end, over BOTH control planes:
    rank 2's side of an asymmetric partition is cut mid-run.  The
    majority (a strict quorum of the old epoch) commits the next epoch
    and continues BITWISE; the minority parks fenced with ZERO durable
    writes — its stale-epoch probe bounces off the fence — then
    rejoins after the heal and the world is restored to [0, 1, 2].
    The event log flows through the trace_report fencing section."""
    _, kv_make = kv_backend
    ev_path = str(tmp_path / "ev.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", ev_path)
    monkeypatch.setenv("MXTPU_PARTITION_SECS", "1.5")
    telemetry.reset()
    num_steps, snap_every = 70, 2
    out = {}
    threads = [threading.Thread(
        target=_run_partition_rank,
        args=(r, 3, kv_make, num_steps, snap_every, out))
        for r in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.8)                     # gang forms, snapshots exist
    fault_inject("partition_split:2")
    for t in threads:
        t.join(timeout=90)
    try:
        assert not any(t.is_alive() for t in threads), "gang wedged"
        for r in range(3):
            assert out.get(r, {}).get("status") == "done", out.get(r)
        # the minority: fenced, rejected, back in
        assert out[2]["fenced"], out[2]
        assert out[2]["rejoined"], out[2]
        assert out[2]["probe_committed"] == 0, \
            "a fenced rank's stale write LANDED — split-brain"
        assert out[2]["probe_rejected"] >= 1, out[2]
        # world restored after the heal
        for r in range(3):
            assert sorted(out[r]["gang"].members) == [0, 1, 2], out[r]
        # the majority continued BITWISE: replay the membership history
        # rank 0 actually lived (cut -> [0,1], readmit -> [0,1,2])
        # against the serial simulation
        infos0 = out[0]["infos"]
        assert len(infos0) >= 2, infos0
        assert infos0[0].members == [0, 1]
        phases = [(0, [0, 1, 2])]
        for info in infos0:
            phases.append((info.snap_step, list(info.members)))
        sim, sim_w = _sim_losses(num_steps, phases)
        for r in (0, 1):
            assert out[r]["losses"] == sim, f"rank {r} diverged"
            np.testing.assert_array_equal(out[r]["w"], sim_w)
        np.testing.assert_array_equal(out[2]["w"], sim_w)
    finally:
        for res in out.values():
            res["gang"].stop()
        telemetry.reset()

    with open(ev_path) as f:
        ev = [json.loads(ln) for ln in f if ln.strip()]
    kinds = {e.get("event") for e in ev}
    assert "gang_fenced" in kinds
    assert "fencing_rejected" in kinds
    assert "partition_healed" in kinds
    healed = [e for e in ev if e.get("event") == "partition_healed"]
    assert any(e.get("rank") == 2 and e.get("fenced_ms", 0) > 0
               for e in healed)

    proc = subprocess.run(
        [sys.executable, _TRACE_REPORT, ev_path, "--validate"],
        env=cpu_child_env(), capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "fencing:" in proc.stdout
    assert "rejected stale writes:" in proc.stdout
    assert "healed: rank 2" in proc.stdout
    assert "heal latency:" in proc.stdout


def test_zombie_rank_evicted_before_any_durable_write(kv_backend):
    """Zombie containment, distilled: while this rank was out to lunch
    a majority elsewhere committed an epoch that EXCLUDES it.  The very
    next step_tick must raise GangEvicted from the epoch check — which
    runs BEFORE the periodic snapshot — so no durable write of the
    zombie's ever lands."""
    _, make = kv_backend
    kv = make(rank=0)
    gang = resilience.ElasticGang(0, 1, kv=kv, peer_snap_every=1,
                                  heartbeat_interval=0.05,
                                  heartbeat_timeout=5.0)
    gang.start()
    try:
        state = {"w": np.ones(4), "opt": 0.0}
        gang.step_tick(0, state=state)
        assert kv.get_json("snap/0")["step"] == 0
        # the rest of the gang moved on without us (epoch 5, fence up)
        other = make(rank=1)
        other.put_json_if_epoch(
            "epoch/current", {"epoch": 5, "members": [1], "dead": [0]},
            5)
        with pytest.raises(resilience.GangEvicted):
            gang.step_tick(1, state=state)
        # containment: the snapshot advert was never refreshed
        assert kv.get_json("snap/0")["step"] == 0
        # and even a direct snapshot attempt is fenced into eviction,
        # leaving the stored advert untouched
        with pytest.raises(resilience.GangEvicted):
            gang.snapshot(1, state)
        assert kv.get_json("snap/0")["step"] == 0
    finally:
        gang.stop()


class _FakeGang:
    """Just enough gang surface for ScalePolicy unit tests."""

    def __init__(self, kv, members=(0, 1)):
        self.kv = kv
        self.rank = 0
        self.members = list(members)
        self.drain_margin = 2
        self.planned = []

    def plan_leave(self, at_step):
        self.planned.append(at_step)
        return at_step


def test_scale_policy_grow_window_cooldown_and_caps(tmp_path):
    kv = distributed.FileKV(str(tmp_path))
    gang = _FakeGang(kv)
    pol = resilience.ScalePolicy(gang, window=3, cooldown=100.0,
                                 max_world=4)
    # a cold queue resets the saturation window
    assert pol.observe(0, queue_depth=5.0) is None
    assert pol.observe(1, queue_depth=0.0) is None
    assert pol.observe(2, queue_depth=5.0) is None
    assert pol.observe(3, queue_depth=5.0) is None
    assert pol.observe(4, queue_depth=5.0) == "grow"
    req = kv.get_json("scale/req")
    assert req["want_world"] == 3
    assert req["reason"] == "input_saturated"
    # cooldown suppresses a second request even though the launcher
    # hasn't consumed the first
    for s in range(5, 12):
        assert pol.observe(s, queue_depth=5.0) is None
    assert pol.grow_requests == 1
    # data-bound saturation (high data-wait share) never grows: more
    # chips would only starve faster
    pol2 = resilience.ScalePolicy(gang, window=1, cooldown=0.0,
                                  max_world=4)
    kv.delete("scale/req")
    assert pol2.observe(0, queue_depth=5.0, data_share=0.9) is None
    # max_world caps the fleet
    gang.members = [0, 1, 2, 3]
    assert pol2.observe(1, queue_depth=5.0) is None
    assert kv.get_json("scale/req") is None


def test_scale_policy_preemption_drain_and_min_world(tmp_path):
    kv = distributed.FileKV(str(tmp_path))
    gang = _FakeGang(kv, members=(0, 1, 2))
    pol = resilience.ScalePolicy(gang, min_world=2)
    assert pol.on_preemption(7) == 9        # step + drain_margin
    assert gang.planned == [9]
    assert pol.drains == 1
    # at min_world the drain is refused: losing the rank would stall
    # the fleet harder than the preemption
    gang.members = [0, 1]
    assert pol.on_preemption(11) is None
    assert gang.planned == [9]


def test_announce_freed_chips_record(tmp_path):
    kv = distributed.FileKV(str(tmp_path))
    rec = resilience.announce_freed_chips(kv, 2, step=9, count=4,
                                          addr="10.0.0.2:8476")
    got = kv.get_json("chips/freed/2")
    assert got["rank"] == 2 and got["count"] == 4
    assert got["step"] == 9 and got["addr"] == "10.0.0.2:8476"
    assert rec["rank"] == 2


def test_step_tick_steady_state_overhead(tmp_path):
    """The health plane must cost ≤1% of a training step: budget the
    per-tick mechanism (heartbeat note + throttled detector poll +
    epoch check + periodic RAM snapshot) against a 50 ms step."""
    kv = distributed.FileKV(str(tmp_path))
    gang = resilience.ElasticGang(0, 1, kv=kv, peer_snap_every=5,
                                  heartbeat_interval=0.05,
                                  heartbeat_timeout=5.0)
    gang.start()
    try:
        # the fence bookkeeping must be LIVE while the budget is
        # measured: start() wired the committed epoch into the v8
        # telemetry stamp, so every tick below pays the real epoch-check
        # + stamping cost, not a fencing-disabled fast path
        assert telemetry._GANG_EPOCH == gang.epoch
        state = {"w": np.zeros(256, dtype=np.float32)}
        for step in range(20):              # warm caches
            gang.step_tick(step, state=state)
        # best of 3: the budget gates the mechanism's cost, not a
        # transient CPU-contention spike on a loaded CI host
        n, step, per_tick = 200, 20, float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for s in range(step, step + n):
                gang.step_tick(s, state=state)
            step += n
            per_tick = min(per_tick,
                           (time.perf_counter() - t0) / n)
    finally:
        gang.stop()
    assert per_tick < 0.01 * 0.050, \
        f"step_tick costs {per_tick * 1e6:.0f}us — over 1% of a 50ms " \
        f"step"


# -- multi-process gangs (slow) ------------------------------------------------

def _spawn_rank(rank, world, env, args):
    e = dict(env)
    e["MXTPU_WORKER_RANK"] = str(rank)
    e["MXTPU_NUM_WORKERS"] = str(world)
    return subprocess.Popen([sys.executable, _WORKER] + args, env=e,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)


def _parse_worker_output(text):
    results, losses, pids = {}, {}, []
    for ln in text.splitlines():
        if ln.startswith("RESULT "):
            rec = json.loads(ln[len("RESULT "):])
            results[rec["rank"]] = rec
        elif ln.startswith("LOSS "):
            _, r, _e, s, h = ln.split()
            losses[int(s)] = float.fromhex(h)
        elif ln.startswith("PID "):
            pids.append(int(ln.split()[2]))
    return results, losses, pids


def _start_kv_daemon(extra_env=None):
    """Spawn tools/gang_kv.py on an ephemeral port; returns (proc,
    addr) once LISTEN is printed."""
    env = cpu_child_env(**(extra_env or {}))
    proc = subprocess.Popen([sys.executable, _GANG_KV], env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
    line = proc.stdout.readline()
    assert line.startswith("LISTEN "), (line, proc.stderr.read())
    return proc, line.split()[1]


@pytest.mark.slow
@pytest.mark.faults
@pytest.mark.parametrize("backend", ["file", "tcp"])
def test_multiproc_kill_rank_elastic_reshape(tmp_path, backend):
    """Hermetic 3-rank gang; rank 1 is SIGKILLed at step 9.  Survivors
    must keep their pids, reshape to world 2 within the heartbeat
    timeout, restore from buddy RAM (disk restores = 0), and produce a
    loss trajectory bitwise equal to the clean 2-rank continuation.
    Over ``tcp`` the control plane is a gang_kv.py daemon — NO shared
    filesystem between the ranks' KV clients."""
    world, steps, snap_every, kill_step = 3, 14, 4, 9
    daemon = None
    if backend == "file":
        gang_dir = tmp_path / "gang"
        gang_dir.mkdir()
        plane = {"MXTPU_GANG_DIR": str(gang_dir)}
    else:
        daemon, addr = _start_kv_daemon()
        plane = {"MXTPU_GANG_KV": "tcp", "MXTPU_GANG_ADDR": addr}
    env = cpu_child_env(
        MXTPU_HEARTBEAT_INTERVAL="0.1",
        MXTPU_HEARTBEAT_TIMEOUT="1.0",
        MXTPU_FAULT_INJECT="kill_rank:1",
        MXTPU_KILL_AT_STEP=str(kill_step),
        **plane,
    )
    args = [str(tmp_path), str(steps), str(snap_every)]
    try:
        procs = {r: _spawn_rank(r, world, env, args)
                 for r in range(world)}
        outs = {r: p.communicate(timeout=120)
                for r, p in procs.items()}
    finally:
        if daemon is not None:
            daemon.terminate()
            daemon.communicate(timeout=30)
    assert procs[1].returncode == -signal.SIGKILL, outs[1]
    sim, sim_w = _sim_losses(steps, [(0, [0, 1, 2]), (8, [0, 2])])
    w0 = {}
    for r in (0, 2):
        assert procs[r].returncode == 0, outs[r]
        results, losses, pids = _parse_worker_output(outs[r][0])
        assert len(pids) == 1, "survivor pid must be stable"
        rec = results[r]
        assert rec["pid"] == pids[0]
        assert rec["final_step"] == steps
        assert rec["epoch"] == 1
        assert rec["members"] == [0, 2]
        assert rec["source"] == "peer"
        assert rec["disk_restores"] == 0
        assert rec["reshapes"] == 1
        assert losses == sim, f"rank {r} loss trajectory diverged"
        w0[r] = rec["w0"]
    assert w0[0] == w0[2] == float(sim_w[0]).hex()


@pytest.mark.slow
@pytest.mark.faults
def test_multiproc_dual_kill_falls_back_to_disk(tmp_path):
    """Ranks 1 AND 2 die at step 9 — rank 1's buddy (2) is gone too, so
    no common RAM snapshot can exist and the survivor must complete the
    run from its disk manifest.  MXTPU_QUORUM=0: one survivor of three
    can never form a strict majority of the old epoch, and this
    single-controller deployment explicitly opts out of the split-brain
    guard (the documented escape hatch for worlds that shrink below
    quorum)."""
    world, steps, snap_every, kill_step = 3, 14, 4, 9
    gang_dir = tmp_path / "gang"
    gang_dir.mkdir()
    env = cpu_child_env(
        MXTPU_GANG_DIR=str(gang_dir),
        MXTPU_HEARTBEAT_INTERVAL="0.1",
        MXTPU_HEARTBEAT_TIMEOUT="1.0",
        MXTPU_FAULT_INJECT="kill_rank:1,kill_rank:2",
        MXTPU_KILL_AT_STEP=str(kill_step),
        MXTPU_QUORUM="0",
    )
    args = [str(tmp_path), str(steps), str(snap_every)]
    procs = {r: _spawn_rank(r, world, env, args) for r in range(world)}
    outs = {r: p.communicate(timeout=120) for r, p in procs.items()}
    for r in (1, 2):
        assert procs[r].returncode == -signal.SIGKILL, outs[r]
    assert procs[0].returncode == 0, outs[0]
    results, losses, _ = _parse_worker_output(outs[0][0])
    rec = results[0]
    assert rec["final_step"] == steps
    assert rec["members"] == [0]
    assert rec["source"] == "disk"
    assert rec["disk_restores"] == 1
    sim, sim_w = _sim_losses(steps, [(0, [0, 1, 2]), (8, [0])])
    assert losses == sim
    assert rec["w0"] == float(sim_w[0]).hex()


@pytest.mark.slow
@pytest.mark.faults
@pytest.mark.parametrize("backend", ["file", "tcp"])
def test_multiproc_partition_minority_fences_and_rejoins(tmp_path,
                                                         backend):
    """Real processes, both control planes: rank 2's KV path is cut at
    its own step 6 (deferred arming — see elastic_gang_worker.py) and
    heals 2 s later.  The majority quorum-commits the next epoch and
    finishes; the minority prints FENCED, parks without stepping, and
    rejoins after the heal — every rank ends at the full world
    [0, 1, 2] with the same final step."""
    world, steps, snap_every = 3, 30, 4
    daemon = None
    if backend == "file":
        gang_dir = tmp_path / "gang"
        gang_dir.mkdir()
        plane = {"MXTPU_GANG_DIR": str(gang_dir)}
    else:
        daemon, addr = _start_kv_daemon()
        plane = {"MXTPU_GANG_KV": "tcp", "MXTPU_GANG_ADDR": addr}
    env = cpu_child_env(
        MXTPU_HEARTBEAT_INTERVAL="0.1",
        MXTPU_HEARTBEAT_TIMEOUT="1.0",
        MXTPU_FAULT_INJECT="partition_split:2",
        MXTPU_FAULT_AT_STEP="6",
        MXTPU_PARTITION_SECS="2.0",
        **plane,
    )
    args = [str(tmp_path), str(steps), str(snap_every), "100"]
    try:
        procs = {r: _spawn_rank(r, world, env, args)
                 for r in range(world)}
        outs = {r: p.communicate(timeout=180)
                for r, p in procs.items()}
    finally:
        if daemon is not None:
            daemon.terminate()
            daemon.communicate(timeout=30)
    for r in range(world):
        assert procs[r].returncode == 0, outs[r]
    for r in (0, 1):
        results, _losses, pids = _parse_worker_output(outs[r][0])
        rec = results[r]
        assert len(pids) == 1
        assert rec["final_step"] == steps
        assert rec["fenced"] == 0, "the MAJORITY must never fence"
        assert rec["members"] == [0, 1, 2]
        assert rec["reshapes"] >= 2        # cut out + readmit
    results, _losses, pids = _parse_worker_output(outs[2][0])
    rec = results[2]
    assert len(pids) == 1, "the fenced rank keeps its process"
    assert "FENCED 2" in outs[2][0]
    assert rec["fenced"] >= 1
    assert rec["rejoined"] >= 1
    assert rec["evictions"] == 0
    assert rec["final_step"] == steps
    assert rec["members"] == [0, 1, 2]


@pytest.mark.slow
@pytest.mark.faults
def test_multiproc_pause_rank_zombie_contained_and_readmitted(tmp_path):
    """pause_rank:2 — the rank is SIGSTOPped at step 6 for 3 s, long
    past the heartbeat timeout; the survivors declare it dead and
    commit the next epoch.  On SIGCONT the zombie's very next KV touch
    must learn the committed epoch and raise GangEvicted BEFORE any
    durable write; with MXTPU_REJOIN_ON_EVICT it then re-enters via a
    planned admission and the full world finishes together."""
    world, steps, snap_every = 3, 35, 4
    gang_dir = tmp_path / "gang"
    gang_dir.mkdir()
    env = cpu_child_env(
        MXTPU_GANG_DIR=str(gang_dir),
        MXTPU_HEARTBEAT_INTERVAL="0.1",
        MXTPU_HEARTBEAT_TIMEOUT="1.0",
        MXTPU_FAULT_INJECT="pause_rank:2",
        MXTPU_FAULT_AT_STEP="6",
        MXTPU_PAUSE_SECS="3.0",
        MXTPU_REJOIN_ON_EVICT="1",
    )
    args = [str(tmp_path), str(steps), str(snap_every), "100"]
    procs = {r: _spawn_rank(r, world, env, args) for r in range(world)}
    outs = {r: p.communicate(timeout=180) for r, p in procs.items()}
    for r in range(world):
        assert procs[r].returncode == 0, outs[r]
    for r in (0, 1):
        results, _losses, _pids = _parse_worker_output(outs[r][0])
        rec = results[r]
        assert rec["final_step"] == steps
        assert rec["members"] == [0, 1, 2]
        assert rec["evictions"] == 0
    results, _losses, pids = _parse_worker_output(outs[2][0])
    rec = results[2]
    assert len(pids) == 1, "the zombie keeps its process"
    assert "EVICTED 2" in outs[2][0]
    assert rec["evictions"] == 1
    assert rec["final_step"] == steps
    assert rec["members"] == [0, 1, 2]
    # containment: between SIGCONT and the eviction the zombie produced
    # no LOSS line — its step counter froze at the pause step until the
    # readmission rolled it to the majority's snapshot
    assert "[resilience] injected pause_rank" in outs[2][1]


@pytest.mark.slow
@pytest.mark.faults
def test_multiproc_kill_coordinator_failover(tmp_path):
    """The coordination daemon is fault-armed to drop dead mid-run.
    The gang must NOT reshape: rank 0's client promotes itself on its
    standby socket, replays the daemon's state, the other ranks adopt,
    and the run finishes at epoch 0 with bitwise loss parity — a
    coordinator death is an availability blip, never a training event.

    The kill is armed with a count normal traffic can't reach; once
    every rank has published step 6 (reads don't consume the counter)
    the test burns the remainder with its own puts, so the daemon dies
    at a point where all three failover candidacies are registered and
    every client's state frame is warm — deterministic, not a race
    against the heartbeat mutation rate."""
    world, steps, snap_every, burn_budget = 3, 30, 4, 5000
    daemon, addr = _start_kv_daemon(
        {"MXTPU_FAULT_INJECT": f"kill_coordinator:{burn_budget}"})
    env = cpu_child_env(
        MXTPU_GANG_KV="tcp",
        MXTPU_GANG_ADDR=addr,
        MXTPU_LEASE_TTL="1.0",          # state-frame refresh every ~0.3s
        MXTPU_HEARTBEAT_INTERVAL="0.25",
        MXTPU_HEARTBEAT_TIMEOUT="3.0",
        MXTPU_KV_FAILOVER_STAGGER="0.2",
    )
    host, _, port = addr.rpartition(":")
    args = [str(tmp_path), str(steps), str(snap_every), "60"]
    d_rc = None
    try:
        procs = {r: _spawn_rank(r, world, env, args)
                 for r in range(world)}
        conn = socket.create_connection((host, int(port)), timeout=5)
        try:
            # wait for every rank's step-6 contribution (gets are free)
            deadline = time.time() + 60
            want = [f"red/0/6/{r}" for r in range(world)]
            while want and time.time() < deadline:
                distributed._kv_send(conn, distributed._OP_GET,
                                     (want[0],))
                _code, val = distributed._kv_recv(conn)
                if val is not None:
                    want.pop(0)
                else:
                    time.sleep(0.05)
            assert not want, f"gang never reached step 6: {want}"
            # burn the fault counter: the daemon dies mid-put, now
            burned = 0
            try:
                while burned < 2 * burn_budget:
                    distributed._kv_send(
                        conn, distributed._OP_PUT,
                        (f"burn/{burned % 50}", b"x", None))
                    distributed._kv_recv(conn)
                    burned += 1
            except (ConnectionError, OSError, EOFError):
                pass
            assert burned < 2 * burn_budget, "daemon survived the burn"
        finally:
            conn.close()
        outs = {r: p.communicate(timeout=120)
                for r, p in procs.items()}
        d_out = daemon.communicate(timeout=30)
        d_rc = daemon.returncode
    finally:
        if d_rc is None:
            daemon.terminate()
            d_out = daemon.communicate(timeout=30)
    # the daemon really did die (clean exit after the injected kill)
    assert daemon.returncode == 0, d_out
    sim, sim_w = _sim_losses(steps, [(0, [0, 1, 2])])
    w0 = {}
    for r in range(world):
        assert procs[r].returncode == 0, outs[r]
        results, losses, pids = _parse_worker_output(outs[r][0])
        rec = results[r]
        assert len(pids) == 1, "no respawn on coordinator death"
        assert rec["final_step"] == steps
        assert rec["epoch"] == 0, "coordinator death must not reshape"
        assert rec["members"] == [0, 1, 2]
        assert rec["reshapes"] == 0
        assert rec["kv_failovers"] == 1, \
            f"rank {r} never failed over — the test proved nothing"
        assert losses == sim, f"rank {r} loss trajectory diverged"
        w0[r] = rec["w0"]
    assert w0[0] == w0[1] == w0[2] == float(sim_w[0]).hex()


@pytest.mark.slow
@pytest.mark.faults
def test_launch_elastic_respawns_dead_rank_and_rejoins(tmp_path):
    """tools/launch.py --elastic end to end: rank 1 dies, the gang
    absorbs it and keeps training; the launcher respawns ONLY rank 1
    (new pid, ranks 0/2 keep theirs), which disarms its kill via the
    marker file and rejoins through the join protocol.  Everyone
    finishes at epoch 2 with world 3 and bitwise-identical state."""
    gang_dir = tmp_path / "gang"
    gang_dir.mkdir()
    steps, snap_every, step_ms = 120, 4, 25
    env = cpu_child_env(
        MXTPU_HEARTBEAT_INTERVAL="0.1",
        MXTPU_HEARTBEAT_TIMEOUT="1.0",
        MXTPU_ELASTIC_RESPAWN_DELAY="2.0",
        MXTPU_FAULT_INJECT="kill_rank:1",
        MXTPU_KILL_AT_STEP="6",
    )
    proc = subprocess.run(
        [sys.executable, _LAUNCH, "-n", "3", "--elastic",
         "--gang-dir", str(gang_dir), "--max-restarts", "1", "--",
         sys.executable, _WORKER, str(tmp_path), str(steps),
         str(snap_every), str(step_ms)],
        env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, (proc.stdout[-4000:],
                                  proc.stderr[-4000:])
    results, _, _ = _parse_worker_output(proc.stdout)
    assert sorted(results) == [0, 1, 2], proc.stdout[-4000:]
    pids_by_rank = {}
    for ln in proc.stdout.splitlines():
        if ln.startswith("PID "):
            _, r, p = ln.split()
            pids_by_rank.setdefault(int(r), []).append(int(p))
    assert len(pids_by_rank[0]) == 1      # survivors: stable pids
    assert len(pids_by_rank[2]) == 1
    assert len(pids_by_rank[1]) == 2      # victim: respawned once
    for r in range(3):
        rec = results[r]
        assert rec["final_step"] == steps
        assert rec["epoch"] == 2          # shrink + rejoin
        assert rec["members"] == [0, 1, 2]
    assert results[1]["pid"] == pids_by_rank[1][1]
    assert results[0]["w0"] == results[1]["w0"] == results[2]["w0"]
    assert "respawning rank 1" in proc.stderr
