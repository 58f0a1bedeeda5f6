"""SDC integrity plane (mxnet_tpu/integrity.py): the fingerprint math
(device/host bitwise parity, single-bit sensitivity), tier-1
cross-replica attestation (majority vote over the gang KV), tier-2
shadow-replay audits (memory vs compute classification), the tier-3
hash-chained lineage ledger + checkpoint provenance, the quarantine →
elastic-reshape → grow-back path, the SDC fault sites
(bit_flip_param / bit_flip_grad / bad_core), charge-consumption
semantics (`resilience.consume_charges` / `consume_rank_fault`), the
fault-site coverage sweep (parser ⊆ docs ⊆ tests), and the telemetry
torn-tail strike-out.
"""

import json
import os
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import (checkpoint, distributed, gluon, integrity,
                       resilience, telemetry)
from mxnet_tpu.gluon import captured, nn
from mxnet_tpu.test_utils import cpu_child_env

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TRACE_REPORT = os.path.join(_REPO, "tools", "trace_report.py")


# -- fingerprint math ----------------------------------------------------------


def test_fingerprint_device_host_parity():
    """The in-program fingerprint (jit-traceable uint32 math) and the
    host mirror must agree bitwise across dtypes — the attestation
    compares one against the other."""
    import jax.numpy as jnp

    rng = np.random.RandomState(0)
    arrs = [
        jnp.asarray(rng.normal(size=(17,)).astype(np.float32)),
        jnp.asarray(rng.normal(size=(3, 5)).astype(np.float16)),
        jnp.asarray(rng.normal(size=(9,)).astype(np.float32),
                    dtype=jnp.bfloat16),
        jnp.asarray(rng.randint(-50, 50, size=(11,)).astype(np.int32)),
        jnp.asarray(rng.rand(8) > 0.5),
        jnp.asarray(rng.randint(0, 255, size=(6,)).astype(np.uint8)),
    ]
    dev = integrity.combine(np.asarray(integrity.fingerprint_arrays(arrs)))
    host = integrity.fingerprint_host([np.asarray(a) for a in arrs])
    assert dev == host
    assert integrity.fp_hex(host) == f"{host:016x}"


def test_fingerprint_single_bit_sensitivity():
    """Odd per-word weights: a single flipped bit — any bit position,
    any element — always changes the fingerprint."""
    base = np.linspace(-1.0, 1.0, 33, dtype=np.float32)
    fp0 = integrity.fingerprint_host([base])
    seen = {fp0}
    for bit in (0, 7, 20, 31):
        a = base.copy()
        integrity.bit_flip_host(a, bit=bit)
        fp = integrity.fingerprint_host([a])
        assert fp not in seen, f"bit {bit} collided"
        seen.add(fp)
    a = base.copy()
    a.view(np.uint32)[16] ^= 1          # element 16, not element 0
    assert integrity.fingerprint_host([a]) not in seen


def test_fingerprint_is_order_canonical():
    a = np.arange(4, dtype=np.float32)
    b = np.arange(4, 8, dtype=np.float32)
    assert integrity.fingerprint_host([a, b]) != \
        integrity.fingerprint_host([b, a])
    # pytree leaves are canonical (dict keys sort): same fp as the list
    assert integrity.fingerprint_host({"a": a, "b": b}) == \
        integrity.fingerprint_host([a, b])


def test_bit_flip_host_flips_exactly_one_bit():
    a = np.arange(16, dtype=np.float32)
    b = a.copy()
    integrity.bit_flip_host(b, bit=20)
    x = a.view(np.uint32) ^ b.view(np.uint32)
    assert np.unpackbits(x.view(np.uint8)).sum() == 1
    assert x[0] != 0 and not x[1:].any()


# -- captured-step attestation (tier 1, zero extra dispatches) -----------------


STEPS = 10


def _make_net(seed=7):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"))
        net.add(nn.Dense(3))
    net.initialize(init=mx.init.Xavier())
    net.hybridize()
    return net


def _batches(steps=STEPS, n=8, d=6, seed=42):
    rng = np.random.RandomState(seed)
    xs = [rng.normal(size=(n, d)).astype(np.float32) for _ in range(steps)]
    ys = [rng.randint(0, 3, size=(n,)).astype(np.float32)
          for _ in range(steps)]
    return xs, ys


def _train_captured(monkeypatch, tmp_path, steps=STEPS, every=None,
                    tag=""):
    """Run `steps` captured train steps; with ``every`` set, attach an
    IntegrityPlane (solo gang over a FileKV, private ledger)."""
    monkeypatch.setenv("MXTPU_CAPTURED_STEP", "1")
    if every is not None:
        monkeypatch.setenv("MXTPU_INTEGRITY", "1")
    else:
        monkeypatch.delenv("MXTPU_INTEGRITY", raising=False)
    net = _make_net()
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    loss_fn.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.01})
    plane = None
    if every is not None:
        plane = integrity.IntegrityPlane(
            rank=0, world=1,
            kv=distributed.FileKV(str(tmp_path / f"kv{tag}")),
            every=every,
            ledger=integrity.IntegrityLedger(
                str(tmp_path / f"led{tag}.jsonl")),
            run="test")
        trainer.attach_integrity(plane)
    xs, ys = _batches(steps)
    captured.reset_counters()
    losses = [trainer.train_step(net, loss_fn, mx.nd.array(xs[s]),
                                 mx.nd.array(ys[s])).asnumpy()
              for s in range(steps)]
    dispatches = captured.dispatch_count()
    weights = [p.data().asnumpy() for p in trainer._params]
    return {"losses": losses, "weights": weights,
            "dispatches": dispatches, "trainer": trainer,
            "plane": plane, "net": net, "loss_fn": loss_fn,
            "xs": xs, "ys": ys}


def test_captured_attestation_is_a_pure_observer(monkeypatch, tmp_path):
    """Attestation must not perturb training: same losses and bitwise
    identical weights with integrity on vs off, ONE dispatch per step
    either way (the fingerprint rides the step program), rounds firing
    exactly every `every` steps, and the attested fingerprint equal to
    the host fingerprint of the LIVE post-step params + optimizer
    state."""
    off = _train_captured(monkeypatch, tmp_path, every=None, tag="off")
    on = _train_captured(monkeypatch, tmp_path, every=5, tag="on")
    for s, (a, b) in enumerate(zip(off["losses"], on["losses"])):
        np.testing.assert_array_equal(a, b, err_msg=f"loss step {s}")
    for i, (a, b) in enumerate(zip(off["weights"], on["weights"])):
        np.testing.assert_array_equal(a, b, err_msg=f"weight {i}")
    assert off["dispatches"] == STEPS
    assert on["dispatches"] == STEPS      # zero extra dispatches
    plane = on["plane"]
    assert plane.attestations == STEPS // 5
    v = plane.last_verdict
    assert v["ok"] and v["step"] == STEPS and not v["corrupt"]
    # tier 3: one ledger entry per round, chained
    entries = plane.ledger.entries()
    assert [e["step"] for e in entries] == [5, 10]
    ok, why = plane.ledger.verify_chain()
    assert ok, why
    # the attested fp IS the live state: host-recompute it from the
    # captured step's own leaf order (new_train + flattened states)
    tr = on["trainer"]
    step = captured.get_step(tr, on["net"], on["loss_fn"],
                             mx.nd.array(on["xs"][0]),
                             mx.nd.array(on["ys"][0]), 1)
    assert step is not None               # cache hit
    leaves = [p.data().asnumpy() for _i, p in step._trained]
    for _gkey, items in step._groups.items():
        for _i, _w, _g, st, _d in items:
            leaves.extend(s.asnumpy() for s in st)
    assert integrity.fp_hex(integrity.fingerprint_host(leaves)) == v["fp"]


def test_bit_flip_param_fires_after_captured_commit(monkeypatch,
                                                    fault_inject):
    """bit_flip_param corrupts the live state AFTER the program commits:
    the step's loss is untouched, exactly one parameter differs from an
    uninjected twin, and by exactly one bit; the charge is one-shot."""
    monkeypatch.setenv("MXTPU_CAPTURED_STEP", "1")

    def run_once():
        net = _make_net()
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        loss_fn.hybridize()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1})
        xs, ys = _batches(steps=1)
        loss = tr.train_step(net, loss_fn, mx.nd.array(xs[0]),
                             mx.nd.array(ys[0])).asnumpy()
        return loss, [p.data().asnumpy() for p in tr._params]

    clean_loss, clean_w = run_once()
    fault_inject("bit_flip_param:0")
    flip_loss, flip_w = run_once()
    assert not resilience.fault_armed("bit_flip_param")   # consumed
    np.testing.assert_array_equal(clean_loss, flip_loss)
    diffs = [i for i, (a, b) in enumerate(zip(clean_w, flip_w))
             if not np.array_equal(a, b)]
    assert len(diffs) == 1
    x = clean_w[diffs[0]].view(np.uint32) ^ \
        flip_w[diffs[0]].view(np.uint32)
    assert np.unpackbits(x.view(np.uint8)).sum() == 1


def test_bit_flip_grad_routes_step_to_eager_oracle(monkeypatch,
                                                   fault_inject):
    """The captured program's gradients never materialize, so an armed
    bit_flip_grad must route that step to the eager oracle (where a
    gradient buffer exists to flip) and re-capture once the charge is
    spent."""
    monkeypatch.setenv("MXTPU_CAPTURED_STEP", "1")

    def run(steps=2):
        net = _make_net()
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        loss_fn.hybridize()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1})
        xs, ys = _batches(steps=steps)
        captured.reset_counters()
        for s in range(steps):
            tr.train_step(net, loss_fn, mx.nd.array(xs[s]),
                          mx.nd.array(ys[s]))
        return captured.dispatch_count(), \
            [p.data().asnumpy() for p in tr._params]

    clean_disp, clean_w = run()
    assert clean_disp == 2
    fault_inject("bit_flip_grad:0")
    flip_disp, flip_w = run()
    assert flip_disp == 1          # step 1 went eager, step 2 captured
    assert not resilience.fault_armed("bit_flip_grad")
    assert any(not np.array_equal(a, b)
               for a, b in zip(clean_w, flip_w))


# -- tier 1: cross-replica majority vote ---------------------------------------


def _mk_planes(tmp_path, n, every=1, timeout=10.0):
    return [integrity.IntegrityPlane(
        rank=r, world=n,
        kv=distributed.FileKV(str(tmp_path / "kv")),
        every=every, timeout=timeout,
        ledger=integrity.IntegrityLedger(
            str(tmp_path / f"led_{r}.jsonl")),
        run="test") for r in range(n)]


def test_attest_majority_names_corrupt_minority(tmp_path):
    telemetry.reset()
    planes = _mk_planes(tmp_path, 3)
    w = np.arange(64, dtype=np.float32) / 3.0
    states = [{"w": w.copy()} for _ in range(3)]
    integrity.bit_flip_host(states[2]["w"])
    fps = [integrity.fingerprint_host(s) for s in states]
    assert fps[0] == fps[1] != fps[2]
    planes[1].publish(0, fps[1])
    planes[2].publish(0, fps[2])
    v0 = planes[0].attest(0, fps[0])
    assert v0["ok"] is False and not v0["tie"]
    assert v0["corrupt"] == [2] and not v0["self_corrupt"]
    assert v0["absent"] == []
    v2 = planes[2].attest(0, fps[2])
    assert v2["self_corrupt"] and v2["corrupt"] == [2]
    # one announcer per verdict: rank 0 (lowest healthy), exactly once
    counts = telemetry.event_counts()
    assert counts.get("integrity_mismatch") == 1
    assert counts.get("sdc_detected") == 1
    assert planes[0].mismatches == 1 and planes[2].mismatches == 1
    telemetry.reset()


def test_attest_two_way_tie_names_nobody(tmp_path):
    telemetry.reset()
    planes = _mk_planes(tmp_path, 2)
    a = np.arange(8, dtype=np.float32)
    b = a.copy()
    integrity.bit_flip_host(b)
    planes[1].publish(0, integrity.fingerprint_host([b]))
    v = planes[0].attest(0, integrity.fingerprint_host([a]))
    assert v["ok"] is False and v["tie"] is True
    assert v["corrupt"] == [] and not v["self_corrupt"]
    # a tie names nobody — no mismatch announcement, no sdc event
    assert telemetry.event_counts().get("sdc_detected") is None
    telemetry.reset()


def test_attest_absent_peer_times_out_without_blocking(tmp_path):
    planes = _mk_planes(tmp_path, 3, timeout=0.3)
    fp = integrity.fingerprint_host([np.ones(4, np.float32)])
    planes[1].publish(0, fp)
    t0 = time.monotonic()
    v = planes[0].attest(0, fp)       # rank 2 never publishes
    assert time.monotonic() - t0 < 5
    assert v["absent"] == [2]
    assert v["ok"] is True and v["corrupt"] == []


# -- tier 2: shadow replay classification --------------------------------------


def test_replay_audit_classifies_memory_compute_clean(tmp_path):
    telemetry.reset()
    plane = integrity.IntegrityPlane(
        rank=1, world=1,
        ledger=integrity.IntegrityLedger(str(tmp_path / "led.jsonl")),
        run="test")

    def step_fn(state, lr):
        return {"w": state["w"] * (1.0 - lr)}

    pre = {"w": np.arange(16, dtype=np.float64) / 7.0}
    live = step_fn({"w": pre["w"].copy()}, 0.01)
    plane.retain(3, {"w": pre["w"].copy()}, inputs=0.01)
    rep = plane.audit(step_fn, integrity.fingerprint_host(live),
                      step=3, peers_agree=True)
    assert rep["kind"] == "clean"
    assert rep["replay_fp"] == rep["live_fp"]
    # memory: live state mutated after the step committed
    bad = {"w": live["w"].copy()}
    integrity.bit_flip_host(bad["w"])
    rep = plane.audit(step_fn, integrity.fingerprint_host(bad),
                      step=3, peers_agree=False)
    assert rep["kind"] == "memory"
    # compute: the WRONG input was recorded, so the replay reproduces
    # the wrong answer — replay == live while peers disagree
    live2 = step_fn({"w": pre["w"].copy()}, 0.02)
    plane.retain(4, {"w": pre["w"].copy()}, inputs=0.02)
    rep = plane.audit(step_fn, integrity.fingerprint_host(live2),
                      step=4, peers_agree=False)
    assert rep["kind"] == "compute"
    assert plane.audit(step_fn, 0, step=99) is None   # nothing retained
    counts = telemetry.event_counts()
    assert counts.get("replay_audit") == 3
    assert counts.get("sdc_detected") == 2            # memory + compute
    assert plane.replays == 3
    telemetry.reset()


def test_bad_core_perturbs_the_input_once(fault_inject):
    fault_inject("bad_core:0")
    x = np.arange(6, dtype=np.float32)
    y = integrity.maybe_bad_core(rank=0, value=x)
    assert y is not x and y[0] != x[0]
    np.testing.assert_array_equal(y[1:], x[1:])
    z = integrity.maybe_bad_core(rank=0, value=x)     # charge spent
    np.testing.assert_array_equal(z, x)
    assert not resilience.fault_armed("bad_core")


# -- tier 3: lineage ledger + checkpoint provenance ----------------------------


def test_ledger_chain_append_verify_tamper(tmp_path):
    path = str(tmp_path / "led.jsonl")
    led = integrity.IntegrityLedger(path)
    assert led.head() is None
    for s in (0, 50, 100):
        led.append(s, 0xDEADBEEF + s, rank=0, epoch=0, run="t")
    ok, why = led.verify_chain()
    assert ok, why
    entries = led.entries()
    assert [e["step"] for e in entries] == [0, 50, 100]
    assert led.has_hash(led.head())
    assert not led.has_hash("f" * 64)
    # tamper entry 1's fp but keep its hash: the chain must fail closed
    lines = open(path).read().splitlines()
    rec = json.loads(lines[1])
    rec["fp"] = "0" * 16
    lines[1] = json.dumps(rec)
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    led2 = integrity.IntegrityLedger(path)
    ok, why = led2.verify_chain()
    assert not ok and why


def test_checkpoint_provenance_stamp_and_fail_closed(tmp_path,
                                                     monkeypatch):
    """AsyncCheckpointer stamps the ledger head into MANIFEST.json;
    restore audits the stamp back to the chain — a tampered ledger
    fails closed, a missing ledger (fresh machine) stays lenient."""
    from mxnet_tpu.checkpoint import CheckpointCorrupt

    lpath = str(tmp_path / "ledger.jsonl")
    monkeypatch.setenv("MXTPU_INTEGRITY_LEDGER", lpath)
    integrity.reset()
    led = integrity.get_ledger()
    led.append(100, 0xABCD, rank=0, run="t")
    state = {"w": np.arange(8, dtype=np.float32)}
    ck = checkpoint.AsyncCheckpointer(str(tmp_path / "ck"), rank=0,
                                      world_size=1)
    try:
        ck.save(1, state)
        ck.wait()
        m = ck.verify(1)
        assert m["integrity"]["ledger_head"] == led.head()
        np.testing.assert_array_equal(ck.restore(1)["w"], state["w"])
        # unstamped manifests (pre-integrity writers) stay readable
        ok, why = integrity.verify_provenance({"step": 1})
        assert ok
        # tamper the ledger → chain invalid → provenance fails closed
        lines = open(lpath).read().splitlines()
        rec = json.loads(lines[0])
        rec["fp"] = "0" * 16
        with open(lpath, "w") as f:
            f.write(json.dumps(rec) + "\n")
        integrity.reset()
        with pytest.raises(CheckpointCorrupt, match="provenance"):
            ck.restore(1)
        # ledger gone entirely (checkpoint shipped to a fresh machine):
        # nothing to audit against — lenient
        os.remove(lpath)
        integrity.reset()
        np.testing.assert_array_equal(ck.restore(1)["w"], state["w"])
    finally:
        ck.close()
        integrity.reset()


# -- end-to-end: 3-rank gang, bit flip detected / audited / repaired -----------


def _sim_losses(num_steps, phases, n=8):
    """Serial oracle of the thread-gang arithmetic (test_elastic.py)."""
    w = np.full(n, 1.0, dtype=np.float64)
    losses = {}
    for step in range(num_steps):
        members = None
        for start, m in sorted(phases):
            if step >= start:
                members = m
        total = sum(float((r + 1) * float(w.sum()))
                    for r in sorted(members))
        loss = total / len(members)
        losses[step] = loss
        w = w * 0.99 - 0.01 * (loss / w.size)
    return losses, w


def _kv_allreduce(gang, kv, step, contribution):
    epoch = gang.epoch
    kv.put_json(f"red/{epoch}/{step}/{gang.rank}",
                {"v": float(contribution)})
    gang.barrier(f"red{step}")
    total = 0.0
    for r in sorted(gang.members):
        total += float(kv.get_json(f"red/{epoch}/{step}/{r}")["v"])
    return total / len(gang.members)


def _apply(pre, loss):
    return {"w": pre["w"] * 0.99 - 0.01 * (loss / pre["w"].size),
            "opt": pre["opt"] + loss}


@pytest.fixture(params=["file", "tcp"])
def kv_backend(request, tmp_path):
    """(mode, make) over both gang control planes — the same surface
    tests/test_elastic.py exercises."""
    if request.param == "file":
        kvdir = str(tmp_path / "kv")

        def make(rank=None):
            return distributed.FileKV(kvdir)

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


def _run_sdc_rank(rank, world, kv_make, root, num_steps, every,
                  flip_step, out):
    """Thread rank: lockstep KV allreduce + integrity plane.  A
    self-corrupt verdict triggers the shadow replay; kind "memory"
    means the replayed step IS the clean post-step state, so the rank
    repairs in place — zero lost steps, no reshape."""
    kv = kv_make(rank)
    gang = resilience.ElasticGang(rank, world, kv=kv, peer_snap_every=2,
                                  heartbeat_interval=0.05,
                                  heartbeat_timeout=5.0)
    gang.start()
    plane = integrity.IntegrityPlane(
        rank=rank, world=world, kv=kv, every=every, timeout=30.0,
        ledger=integrity.IntegrityLedger(
            os.path.join(root, f"led_{rank}.jsonl")),
        run="sdc-test")
    state = {"w": np.full(8, 1.0, dtype=np.float64), "opt": 0.0}
    step, losses, audits, repaired, last_ok = 0, {}, [], 0, None
    try:
        while step < num_steps:
            gang.step_tick(step, state=state)
            pre = {"w": state["w"].copy(), "opt": state["opt"]}
            loss = _kv_allreduce(gang, kv, step,
                                 (rank + 1) * float(state["w"].sum()))
            losses[step] = loss
            state = _apply(pre, loss)
            if step == flip_step and \
                    resilience.consume_rank_fault("bit_flip_param",
                                                  rank):
                integrity.bit_flip_host(state["w"])
            if plane.due(step):
                plane.retain(step, pre, inputs=loss)
                v = plane.attest(step,
                                 integrity.fingerprint_host(state))
                last_ok = v["ok"]
                if not v["ok"] and v["self_corrupt"]:
                    rep = plane.audit(
                        _apply, integrity.fingerprint_host(state),
                        step=step)
                    audits.append(rep)
                    if rep["kind"] == "memory":
                        state = _apply(pre, loss)
                        repaired += 1
            step += 1
        out[rank] = {"status": "done", "losses": losses,
                     "w": state["w"], "gang": gang, "audits": audits,
                     "repaired": repaired, "last_ok": last_ok,
                     "attestations": plane.attestations}
    except Exception as e:                  # noqa: BLE001 — surfaced
        out[rank] = {"status": "error", "error": repr(e), "gang": gang}


def test_gang_detects_audits_and_repairs_bit_flip(kv_backend, tmp_path,
                                                  monkeypatch,
                                                  fault_inject):
    """The ISSUE's acceptance run: 3 ranks, bit_flip_param:1 lands at
    step 6 (post-commit).  The very next attestation round (same step:
    within one interval) names rank 1, the shadow replay classifies it
    "memory", the rank repairs from the retained snapshot, and every
    rank's losses and final weights are BITWISE equal to the uninjected
    serial oracle.  The event log must flow through trace_report."""
    _, kv_make = kv_backend
    ev_path = str(tmp_path / "ev.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", ev_path)
    telemetry.reset()
    fault_inject("bit_flip_param:1")
    num_steps, every, flip_step = 12, 3, 6
    out = {}
    threads = [threading.Thread(
        target=_run_sdc_rank,
        args=(r, 3, kv_make, str(tmp_path), num_steps, every,
              flip_step, out)) for r in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=90)
    try:
        assert not any(t.is_alive() for t in threads), "gang wedged"
        for r in range(3):
            assert out[r]["status"] == "done", out.get(r)
            assert out[r]["last_ok"] is True          # clean re-attest
            assert out[r]["attestations"] == 4        # steps 0,3,6,9
        # detection within the SAME round the flip landed in
        (audit,) = out[1]["audits"]
        assert audit["step"] == flip_step
        assert audit["kind"] == "memory"
        assert audit["replay_fp"] != audit["live_fp"]
        assert out[1]["repaired"] == 1
        assert out[0]["audits"] == [] and out[2]["audits"] == []
        # post-recovery: bitwise equal to the uninjected run — the
        # corruption never escaped the detection interval
        sim, sim_w = _sim_losses(num_steps, [(0, [0, 1, 2])])
        for r in range(3):
            assert out[r]["losses"] == sim
            np.testing.assert_array_equal(out[r]["w"], sim_w)
        counts = telemetry.event_counts()
        assert counts.get("integrity_mismatch") == 1
        assert counts.get("replay_audit") == 1
        assert counts.get("sdc_detected", 0) >= 1
        # the victim is NAMED: rank 1, refined kind "memory"
        events = [json.loads(l) for l in open(ev_path)]
        sdc = [e for e in events if e.get("event") == "sdc_detected"]
        assert all(e["rank"] == 1 and e["step"] == flip_step
                   for e in sdc)
        assert any(e["kind"] == "memory" for e in sdc)
    finally:
        for res in out.values():
            res["gang"].stop()
        telemetry.reset()                   # close the sink

    proc = subprocess.run(
        [sys.executable, _TRACE_REPORT, ev_path, "--validate"],
        env=cpu_child_env(), capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert "integrity:" in proc.stdout
    assert "attestations:" in proc.stdout
    assert f"mismatch: step {flip_step}" in proc.stdout
    assert "sdc: rank 1" in proc.stdout
    assert "-> memory" in proc.stdout


# -- quarantine: evict the corrupt rank, reshape, grow back --------------------


def _run_quarantine_rank(rank, world, kv_make, root, num_steps, every,
                         flip_step, out, step_s=0.03):
    """Thread rank where a mismatch verdict quarantines instead of
    repairing: survivors turn the verdict into a RankFailure and
    reshape around the corrupt rank; the victim gets evicted, restarts
    its gang membership and `join`s back with clean state."""
    kv = kv_make(rank)
    gang = resilience.ElasticGang(rank, world, kv=kv, peer_snap_every=2,
                                  heartbeat_interval=0.05,
                                  heartbeat_timeout=5.0)
    gang.start()
    plane = integrity.IntegrityPlane(
        rank=rank, world=world, kv=kv, every=every, timeout=15.0,
        ledger=integrity.IntegrityLedger(
            os.path.join(root, f"qled_{rank}.jsonl")),
        run="q-test")
    state = {"w": np.full(8, 1.0, dtype=np.float64), "opt": 0.0}
    step, losses, infos, audits = 0, {}, [], []
    evicted_at = None

    def adopt(info):
        # fresh joiner: any replica's shard — ranks run in lockstep, so
        # EVERY field (opt included) is replica-identical; adopting a
        # partial state would fail the very next attestation
        st = info.shards.get(rank) or next(iter(info.shards.values()))
        return {"w": np.array(st["w"], dtype=np.float64),
                "opt": float(st["opt"])}

    def resync(info):
        infos.append(info)
        plane.peers = list(info.members)
        plane.epoch = info.epoch
        return adopt(info), info.snap_step

    def rejoin(at):
        # quarantined: come back as a fresh member with clean
        # (replica-restored) state, like a restarted process would
        nonlocal evicted_at, gang
        evicted_at = at
        gang.stop()
        gang = resilience.ElasticGang(
            rank, world, kv=kv_make(rank), peer_snap_every=2,
            heartbeat_interval=0.05, heartbeat_timeout=5.0)
        info = gang.join()
        assert info is not None
        return resync(info)

    try:
        while step < num_steps:
            try:
                gang.step_tick(step, state=state)
                pre = {"w": state["w"].copy(), "opt": state["opt"]}
                loss = _kv_allreduce(
                    gang, kv, step,
                    (rank + 1) * float(state["w"].sum()))
            except resilience.GangEvicted:
                state, step = rejoin(step)
                continue
            except resilience.RankFailure as rf:
                try:
                    info = gang.recover(rf)
                except resilience.GangEvicted:
                    state, step = rejoin(step)
                    continue
                state, step = resync(info)
                continue
            losses[step] = loss
            state = _apply(pre, loss)
            if step == flip_step and \
                    resilience.consume_rank_fault("bit_flip_param",
                                                  rank):
                integrity.bit_flip_host(state["w"])
            if plane.due(step) and gang.rank in gang.members:
                plane.retain(step, pre, inputs=loss)
                v = plane.attest(step,
                                 integrity.fingerprint_host(state))
                if not v["ok"] and not v["tie"] and v["corrupt"]:
                    if v["self_corrupt"]:
                        rep = plane.audit(
                            _apply,
                            integrity.fingerprint_host(state),
                            step=step)
                        audits.append(rep)
                        # no self-repair here: the gang evicts us
                    else:
                        rf = plane.quarantine(gang, v)
                        assert rf is not None
                        state, step = resync(gang.recover(rf))
                        continue
            step += 1
            if step_s:
                time.sleep(step_s)
        out[rank] = {"status": "done", "losses": losses,
                     "w": state["w"], "gang": gang, "infos": infos,
                     "audits": audits, "evicted_at": evicted_at}
    except Exception as e:                  # noqa: BLE001 — surfaced
        out[rank] = {"status": "error", "error": repr(e), "gang": gang}


def test_quarantine_evicts_corrupt_rank_and_grows_back(kv_backend,
                                                       tmp_path,
                                                       monkeypatch,
                                                       fault_inject):
    _, kv_make = kv_backend
    ev_path = str(tmp_path / "qev.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", ev_path)
    telemetry.reset()
    fault_inject("bit_flip_param:1")
    num_steps, every, flip_step = 26, 3, 6
    out = {}
    threads = [threading.Thread(
        target=_run_quarantine_rank,
        args=(r, 3, kv_make, str(tmp_path), num_steps, every,
              flip_step, out)) for r in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    try:
        assert not any(t.is_alive() for t in threads), "gang wedged"
        for r in range(3):
            assert out[r]["status"] == "done", out.get(r)
        # the victim was evicted, audited itself ("memory"), and rejoined
        assert out[1]["evicted_at"] is not None
        assert any(a["kind"] == "memory" for a in out[1]["audits"])
        rejoin = out[1]["infos"][-1]
        assert 1 in rejoin.members
        # survivors' first reshape excluded exactly the corrupt rank
        for r in (0, 2):
            first = out[r]["infos"][0]
            assert first.members == [0, 2]
            assert first.dead == [1]
        counts = telemetry.event_counts()
        assert counts.get("rank_quarantined", 0) >= 1
        assert counts.get("sdc_detected", 0) >= 1
        # grown back and converged: same weights on every rank, and the
        # post-rejoin trajectory agrees step for step
        np.testing.assert_array_equal(out[0]["w"], out[1]["w"])
        np.testing.assert_array_equal(out[0]["w"], out[2]["w"])
        for s in range(rejoin.snap_step, num_steps):
            assert out[0]["losses"][s] == out[1]["losses"][s] \
                == out[2]["losses"][s], f"step {s}"
    finally:
        for res in out.values():
            res["gang"].stop()
        telemetry.reset()


# -- charge consumption (resilience.consume_charges / consume_rank_fault) ------


def test_consume_charges_fire_on_last(fault_inject):
    """kill_coordinator discipline: N charges absorb N-1 triggers and
    fire on the LAST one (the Nth mutation kills the daemon)."""
    fault_inject("kill_coordinator:3")
    assert resilience.consume_charges("kill_coordinator") is False
    assert resilience.consume_charges("kill_coordinator") is False
    assert resilience.consume_charges("kill_coordinator") is True
    assert resilience.consume_charges("kill_coordinator") is False


def test_consume_charges_fire_on_each(fault_inject):
    """corrupt_ckpt_write discipline: every charge fires (bit-rot the
    next N files), then the site disarms."""
    fault_inject("corrupt_ckpt_write:2")
    assert resilience.consume_charges("corrupt_ckpt_write",
                                      on_last=False) is True
    assert resilience.consume_charges("corrupt_ckpt_write",
                                      on_last=False) is True
    assert resilience.consume_charges("corrupt_ckpt_write",
                                      on_last=False) is False


def test_consume_rank_fault_is_one_shot_per_rank(fault_inject):
    fault_inject("bit_flip_param:1,bit_flip_param:2,bad_core:0")
    assert tuple(resilience.fault_args("bit_flip_param")) == (1, 2)
    assert resilience.fault_armed("bit_flip_param")
    assert not resilience.consume_rank_fault("bit_flip_param", 0)
    assert resilience.consume_rank_fault("bit_flip_param", 1)
    assert not resilience.consume_rank_fault("bit_flip_param", 1)
    assert resilience.fault_armed("bit_flip_param")   # rank 2 pending
    assert resilience.consume_rank_fault("bit_flip_param", 2)
    assert not resilience.fault_armed("bit_flip_param")
    assert resilience.consume_rank_fault("bad_core", 0)
    assert not resilience.consume_rank_fault("bad_core", 0)


# -- fault-site coverage sweep -------------------------------------------------


def _parser_sites():
    import inspect

    src = inspect.getsource(resilience._FaultPlan.__init__)
    groups = re.findall(r"site in \(([^)]*)\)", src)
    sites = {m for g in groups for m in re.findall(r'"([a-z_]+)"', g)}
    sites.discard("stall")              # alias of stall_collective
    return sites


def test_every_fault_site_is_documented_and_tested():
    """Grep-driven sweep: every site MXTPU_FAULT_INJECT's parser
    accepts must (a) have a row in docs/env_vars.md's fault-site table
    and (b) be exercised by at least one test under tests/ — and the
    docs table must not carry stale rows the parser rejects."""
    sites = _parser_sites()
    assert len(sites) >= 25, sorted(sites)

    docs = open(os.path.join(_REPO, "docs", "env_vars.md")).read()
    assert "### Fault sites" in docs
    table = docs.split("### Fault sites")[1].split("\n## ")[0]
    doc_sites = set(re.findall(r"^\| `([a-z_]+)`", table, re.M))
    undocumented = sites - doc_sites
    assert not undocumented, f"sites missing from docs: {undocumented}"
    stale = doc_sites - sites
    assert not stale, f"docs rows the parser rejects: {stale}"

    tests_dir = os.path.join(_REPO, "tests")
    blob = "".join(
        open(os.path.join(tests_dir, name)).read()
        for name in sorted(os.listdir(tests_dir))
        if name.endswith(".py"))
    untested = {s for s in sites if s not in blob}
    assert not untested, f"sites no test exercises: {untested}"


# -- telemetry: integrity records + torn-tail strike-out -----------------------


def test_integrity_record_schema_validates(tmp_path, monkeypatch):
    path = str(tmp_path / "t.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", path)
    telemetry.reset()
    telemetry.integrity_record(step=50, fp="00ab", ok=False, epoch=1,
                               peers=3, corrupt=[1], kind="memory",
                               rank=0)
    telemetry.reset()                   # close the sink
    (rec,) = [json.loads(l) for l in open(path)]
    telemetry.validate_record(rec)
    assert rec["type"] == "integrity" and rec["corrupt"] == [1]
    with pytest.raises(ValueError, match="step"):
        telemetry.validate_record(dict(rec, step=-1))
    with pytest.raises(ValueError, match="kind"):
        telemetry.validate_record(dict(rec, kind="banana"))


def test_torn_tail_strikes_out_after_three_polls(tmp_path):
    """A tail that stays torn for MXTPU_TELEMETRY_TAIL_STRIKES polls
    (default 3) is a dead write, not an in-flight flush: skip it, emit
    ONE telemetry_torn_line, and keep reading what comes after."""
    telemetry.reset()
    path = str(tmp_path / "t.jsonl")
    with open(path, "w") as f:
        f.write('{"type": "event", "event": "resume", "step": 0}\n')
        f.write('{"type": "event", "ev')            # torn forever
    assert [r["step"] for r in telemetry.tail_records(path)] == [0]
    assert telemetry.tail_records(path) == []       # strike 2: held
    c0 = telemetry.event_counts().get("telemetry_torn_line", 0)
    assert telemetry.tail_records(path) == []       # strike 3: skipped
    assert telemetry.event_counts()["telemetry_torn_line"] == c0 + 1
    assert telemetry.tail_records(path) == []       # no repeat event
    assert telemetry.event_counts()["telemetry_torn_line"] == c0 + 1
    # the reader moved PAST the torn bytes: later complete lines flow
    with open(path, "a") as f:
        f.write('{"type": "event", "event": "resume", "step": 2}\n')
    assert [r["step"] for r in telemetry.tail_records(path)] == [2]
    telemetry.reset()


def test_torn_tail_growth_resets_the_strike_count(tmp_path):
    """A tail that GROWS between polls is an in-flight flush — the
    strike count restarts and the completed line is delivered intact."""
    telemetry.reset()
    path = str(tmp_path / "t.jsonl")
    with open(path, "w") as f:
        f.write('{"type": "event", "event": "resume", "step": 0}\n')
        f.write('{"type": "event", "ev')
    assert [r["step"] for r in telemetry.tail_records(path)] == [0]
    assert telemetry.tail_records(path) == []       # 2 strikes held
    with open(path, "a") as f:
        f.write('ent": "resu')                      # still torn, grew
    assert telemetry.tail_records(path) == []       # back to strike 1
    assert telemetry.tail_records(path) == []       # strike 2
    assert telemetry.event_counts().get("telemetry_torn_line", 0) == 0
    with open(path, "a") as f:
        f.write('me", "step": 7}\n')                # flush completes
    assert [r["step"] for r in telemetry.tail_records(path)] == [7]
    assert telemetry.event_counts().get("telemetry_torn_line", 0) == 0
    telemetry.reset()


def test_tail_strikes_env_override(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_TELEMETRY_TAIL_STRIKES", "2")
    telemetry.reset()
    path = str(tmp_path / "t.jsonl")
    with open(path, "w") as f:
        f.write('{"type": "event", "event": "resume", "step": 0}\n')
        f.write('{"type": "event", "ev')
    assert [r["step"] for r in telemetry.tail_records(path)] == [0]
    c0 = telemetry.event_counts().get("telemetry_torn_line", 0)
    assert telemetry.tail_records(path) == []       # strike 2: skipped
    assert telemetry.event_counts()["telemetry_torn_line"] == c0 + 1
    telemetry.reset()
