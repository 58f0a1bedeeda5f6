"""Fault-tolerance layer tests (mxnet_tpu/resilience.py).

All CPU-hermetic: every failure mode — flaky rendezvous, flaky IO,
stalled collectives, SIGTERM preemption, corrupt checkpoints — is
produced by the MXTPU_FAULT_INJECT harness or by hand-corrupting files,
never by real hardware.  No test may hang past its watchdog deadline.
"""

import io
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import resilience
from mxnet_tpu.resilience import (CheckpointCorrupt, InjectedFault,
                                  LocalCheckpointer, Watchdog,
                                  WatchdogExpired, retry_call,
                                  run_resilient)


# -- retry_call ----------------------------------------------------------------

def test_retry_call_succeeds_after_failures():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    assert retry_call(flaky, retries=4, backoff=0.001) == "ok"
    assert len(calls) == 3


def test_retry_call_exhausts_retries():
    def always():
        raise OSError("down")

    with pytest.raises(OSError):
        retry_call(always, retries=2, backoff=0.001)


def test_retry_call_deadline():
    def always():
        raise OSError("down")

    t0 = time.monotonic()
    with pytest.raises(mx.MXNetError, match="deadline"):
        retry_call(always, retries=100, backoff=0.05, jitter=0.0,
                   deadline=0.2)
    assert time.monotonic() - t0 < 2.0


def test_retry_call_non_retryable_immediate():
    calls = []

    def missing():
        calls.append(1)
        raise FileNotFoundError("gone")

    with pytest.raises(FileNotFoundError):
        retry_call(missing, retries=5, backoff=0.001,
                   retryable=(OSError,),
                   non_retryable=(FileNotFoundError,))
    assert len(calls) == 1


def test_retry_call_backoff_grows():
    sleeps = []

    def always():
        raise OSError("down")

    with pytest.raises(OSError):
        retry_call(always, retries=3, backoff=0.001, jitter=0.0,
                   on_retry=lambda a, e, s: sleeps.append(s))
    assert sleeps == sorted(sleeps) and len(sleeps) == 3
    assert sleeps[1] == pytest.approx(2 * sleeps[0])


# -- fault-injection harness ---------------------------------------------------

@pytest.mark.faults
def test_fault_spec_parsing(fault_inject):
    fault_inject("rendezvous:2,corrupt_record:7,stall_collective:9.5")
    assert resilience.fault_arg("corrupt_record") == 7
    assert resilience.fault_arg("stall_collective") == 9.5
    with pytest.raises(InjectedFault):
        resilience.inject_failure("rendezvous")
    with pytest.raises(InjectedFault):
        resilience.inject_failure("rendezvous")
    resilience.inject_failure("rendezvous")  # count exhausted: no-op
    assert resilience.consume_fault("corrupt_record")
    assert not resilience.consume_fault("corrupt_record")


@pytest.mark.faults
def test_fault_spec_unknown_site(fault_inject):
    fault_inject("warp_core_breach:1")
    with pytest.raises(mx.MXNetError, match="unknown site"):
        resilience.inject_failure("rendezvous")


@pytest.mark.faults
def test_io_retry_recovers(fault_inject, monkeypatch):
    monkeypatch.setenv("MXTPU_IO_RETRIES", "3")
    monkeypatch.setenv("MXTPU_IO_BACKOFF", "0.001")
    fault_inject("io_open:2")
    calls = []

    def opener():
        calls.append(1)
        return "handle"

    assert resilience.io_retry(opener) == "handle"
    assert len(calls) == 1  # two injected failures happened pre-open


@pytest.mark.faults
def test_io_retry_exhausted(fault_inject, monkeypatch):
    monkeypatch.setenv("MXTPU_IO_RETRIES", "1")
    monkeypatch.setenv("MXTPU_IO_BACKOFF", "0.001")
    fault_inject("io_open:5")
    with pytest.raises(InjectedFault):
        resilience.io_retry(lambda: "never")


# -- watchdog ------------------------------------------------------------------

def test_watchdog_interrupts_stall():
    stream = io.StringIO()
    t0 = time.monotonic()
    with pytest.raises(WatchdogExpired, match="deadline"):
        with Watchdog(0.3, name="stall-test", action="interrupt",
                      stream=stream):
            time.sleep(30)
    assert time.monotonic() - t0 < 5.0
    out = stream.getvalue()
    assert "watchdog 'stall-test' expired" in out
    assert "thread stack dump" in out
    assert "time.sleep(30)" in out  # the dump shows WHERE it was stuck


def test_watchdog_feed_extends_deadline():
    with Watchdog(0.4, name="fed", action="interrupt") as wd:
        for _ in range(4):
            time.sleep(0.2)
            wd.feed()
    assert not wd.expired


def test_watchdog_cancel_no_fire():
    wd = Watchdog(0.2, name="cancelled", action="interrupt")
    wd.start()
    wd.cancel()
    time.sleep(0.4)
    assert not wd.expired


def test_watchdog_none_action_runs_on_expire():
    fired = []
    with Watchdog(0.15, name="observer", action="none",
                  on_expire=lambda: fired.append(1),
                  stream=io.StringIO()) as wd:
        time.sleep(0.5)
    assert wd.expired and fired == [1]


def test_watchdog_abort_exits_process():
    """action='abort' is the only escape from a wedged C call: the
    process must die with the configured exit code AFTER dumping
    stacks.  Exercised in a subprocess (os._exit kills pytest too)."""
    code = ("import importlib.util, time\n"
            "spec = importlib.util.spec_from_file_location(\n"
            "    'res', 'mxnet_tpu/resilience.py')\n"
            "res = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(res)\n"
            "wd = res.Watchdog(0.3, name='wedge', action='abort',"
            " exit_code=42)\n"
            "wd.start()\n"
            "time.sleep(60)\n")
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=60,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 42, proc.stderr
    assert "thread stack dump" in proc.stderr
    assert "watchdog 'wedge' expired" in proc.stderr


def test_watchdog_rejects_unknown_action():
    with pytest.raises(mx.MXNetError, match="unknown action"):
        Watchdog(1.0, action="self-destruct")


# -- rendezvous retry ----------------------------------------------------------

@pytest.mark.faults
def test_rendezvous_retries_then_succeeds(fault_inject, monkeypatch):
    from mxnet_tpu import distributed

    attempts = []
    import jax

    monkeypatch.setattr(jax.distributed, "initialize",
                        lambda **kw: attempts.append(kw))
    monkeypatch.setenv("MXTPU_RENDEZVOUS_RETRIES", "3")
    monkeypatch.setenv("MXTPU_RENDEZVOUS_TIMEOUT", "30")
    monkeypatch.setattr(resilience.time, "sleep", lambda s: None)
    fault_inject("rendezvous:2")
    monkeypatch.setattr(distributed, "_INITIALIZED", False)
    distributed.initialize("127.0.0.1:1", 1, 0)
    # two injected failures burned two attempts; the third connected
    assert len(attempts) == 1
    assert attempts[0]["coordinator_address"] == "127.0.0.1:1"


@pytest.mark.faults
def test_rendezvous_retries_exhausted(fault_inject, monkeypatch):
    from mxnet_tpu import distributed

    import jax

    monkeypatch.setattr(jax.distributed, "initialize",
                        lambda **kw: None)
    monkeypatch.setenv("MXTPU_RENDEZVOUS_RETRIES", "1")
    monkeypatch.setattr(resilience.time, "sleep", lambda s: None)
    fault_inject("rendezvous:10")
    monkeypatch.setattr(distributed, "_INITIALIZED", False)
    with pytest.raises(InjectedFault):
        distributed.initialize("127.0.0.1:1", 1, 0)


# -- stalled collective --------------------------------------------------------

@pytest.mark.faults
def test_stalled_collective_hits_watchdog(fault_inject, monkeypatch):
    """A wedged runtime call, hermetic: a collective that stalls must
    be killed by MXTPU_COLLECTIVE_TIMEOUT, not hang the suite."""
    from mxnet_tpu import distributed

    monkeypatch.setenv("MXTPU_COLLECTIVE_TIMEOUT", "0.5")
    fault_inject("stall_collective:30")
    t0 = time.monotonic()
    with pytest.raises(WatchdogExpired):
        distributed.barrier("stall-test")
    assert time.monotonic() - t0 < 10.0


@pytest.mark.faults
def test_guarded_collective_passes_when_healthy(monkeypatch):
    from mxnet_tpu import distributed

    monkeypatch.setenv("MXTPU_COLLECTIVE_TIMEOUT", "30")
    distributed.barrier("healthy")  # single process: returns instantly


# -- local checkpointer --------------------------------------------------------

def test_local_checkpointer_roundtrip(tmp_path):
    ck = LocalCheckpointer(tmp_path)
    state = {"w": np.arange(6.0).reshape(2, 3), "step": 5,
             "nested": {"b": [1, 2, 3]}}
    ck.save(5, state)
    got = ck.restore(5)
    np.testing.assert_array_equal(got["w"], state["w"])
    assert got["nested"]["b"] == [1, 2, 3]
    assert ck.latest_step() == 5
    ck.verify(5)


def test_local_checkpointer_prunes(tmp_path):
    ck = LocalCheckpointer(tmp_path, max_to_keep=2)
    for s in (1, 2, 3, 4):
        ck.save(s, {"s": s})
    assert ck.all_steps() == [3, 4]


def test_local_checkpointer_detects_corruption(tmp_path):
    ck = LocalCheckpointer(tmp_path)
    ck.save(3, {"w": np.ones(8)})
    path = os.path.join(str(tmp_path), "ckpt_0000000003.mxtckpt")
    blob = open(path, "rb").read()
    with open(path, "wb") as f:        # flip payload bytes: crc mismatch
        f.write(blob[:-4] + b"\xde\xad\xbe\xef")
    with pytest.raises(CheckpointCorrupt, match="checksum"):
        ck.restore(3)
    with open(path, "wb") as f:        # truncate: length mismatch
        f.write(blob[:len(blob) // 2])
    with pytest.raises(CheckpointCorrupt, match="truncated"):
        ck.restore(3)
    with open(path, "wb") as f:        # stomp magic
        f.write(b"NOTCKPT!" + blob[8:])
    with pytest.raises(CheckpointCorrupt, match="magic"):
        ck.restore(3)


def test_resume_latest_falls_back_past_corrupt(tmp_path):
    ck = LocalCheckpointer(tmp_path)
    ck.save(10, {"v": 10})
    ck.save(20, {"v": 20})
    path = os.path.join(str(tmp_path), "ckpt_0000000020.mxtckpt")
    with open(path, "wb") as f:
        f.write(b"garbage")
    restored = []
    step = resilience.resume_latest(ck, restored.append)
    assert step == 10
    assert restored[0]["v"] == 10


def test_resume_latest_fresh_start(tmp_path):
    ck = LocalCheckpointer(tmp_path)
    assert resilience.resume_latest(ck, lambda s: None) == 0


# -- run_resilient: numpy model ------------------------------------------------

def _numpy_trainer():
    """Deterministic toy SGD on a quadratic — state is one weight
    vector, loss strictly decreases, trajectory is exactly replayable."""
    state = {"w": np.full(4, 10.0)}

    def step_fn(step):
        w = state["w"]
        loss = float((w ** 2).sum())
        state["w"] = w - 0.1 * 2 * w
        return loss

    return (step_fn, lambda: {"w": state["w"].copy()},
            lambda s: state.update(w=np.asarray(s["w"]).copy()))


def test_run_resilient_uninterrupted(tmp_path):
    step_fn, get_state, set_state = _numpy_trainer()
    report = run_resilient(step_fn, LocalCheckpointer(tmp_path), 20,
                           get_state=get_state, set_state=set_state,
                           checkpoint_every=5)
    assert report.final_step == 20
    assert report.restarts == 0 and not report.preempted
    assert sorted(report.losses) == list(range(20))
    losses = [report.losses[i] for i in range(20)]
    assert losses == sorted(losses, reverse=True)  # converging
    # final checkpoint written + valid
    ck = LocalCheckpointer(tmp_path)
    assert ck.latest_step() == 20
    ck.verify(20)


@pytest.mark.faults
def test_run_resilient_sigterm_preemption(tmp_path, fault_inject):
    """Injected SIGTERM mid-run: checkpoint at the preemption step,
    in-process restart, resume, identical final state."""
    fault_inject("sigterm_at_step:7")
    step_fn, get_state, set_state = _numpy_trainer()
    report = run_resilient(step_fn, LocalCheckpointer(tmp_path), 20,
                           get_state=get_state, set_state=set_state,
                           checkpoint_every=5, max_restarts=3)
    assert report.preempted
    assert report.restarts == 1
    assert report.final_step == 20
    assert report.resumed_from == [0, 7]  # preemption saved step 7
    # trajectory identical to an uninterrupted run
    base_step, base_get, base_set = _numpy_trainer()
    base = run_resilient(base_step, LocalCheckpointer(tmp_path / "b"),
                         20, get_state=base_get, set_state=base_set,
                         checkpoint_every=5)
    for s in range(20):
        assert report.losses[s] == pytest.approx(base.losses[s])
    np.testing.assert_allclose(get_state()["w"], base_get()["w"])


@pytest.mark.faults
def test_run_resilient_exit_on_preempt(tmp_path, fault_inject):
    fault_inject("sigterm_at_step:4")
    step_fn, get_state, set_state = _numpy_trainer()
    report = run_resilient(step_fn, LocalCheckpointer(tmp_path), 20,
                           get_state=get_state, set_state=set_state,
                           checkpoint_every=100, exit_on_preempt=True)
    assert report.preempted and report.final_step == 4
    # the grace-window checkpoint landed; a relaunch resumes from it
    step_fn2, get2, set2 = _numpy_trainer()
    report2 = run_resilient(step_fn2, LocalCheckpointer(tmp_path), 20,
                            get_state=get2, set_state=set2,
                            checkpoint_every=100)
    assert report2.resumed_from == [4]
    assert report2.final_step == 20


def test_run_resilient_step_failure_restart(tmp_path):
    step_fn, get_state, set_state = _numpy_trainer()
    boom = [True]

    def flaky_step(step):
        if step == 12 and boom[0]:
            boom[0] = False
            raise RuntimeError("device wedged")
        return step_fn(step)

    report = run_resilient(flaky_step, LocalCheckpointer(tmp_path), 20,
                           get_state=get_state, set_state=set_state,
                           checkpoint_every=5, max_restarts=2)
    assert report.final_step == 20
    assert report.restarts == 1
    assert report.resumed_from == [0, 10]  # replays from checkpoint 10


def test_run_resilient_max_restarts_exhausted(tmp_path):
    def always_fails(step):
        raise RuntimeError("permanently broken")

    with pytest.raises(RuntimeError, match="permanently broken"):
        run_resilient(always_fails, LocalCheckpointer(tmp_path), 20,
                      get_state=lambda: {}, set_state=lambda s: None,
                      checkpoint_every=5, max_restarts=2)


def test_run_resilient_corrupt_latest_falls_back(tmp_path):
    """Kill the latest checkpoint after a partial run: the next run must
    fall back to the previous checkpoint and still finish."""
    step_fn, get_state, set_state = _numpy_trainer()
    run_resilient(step_fn, LocalCheckpointer(tmp_path), 10,
                  get_state=get_state, set_state=set_state,
                  checkpoint_every=5)
    path = os.path.join(str(tmp_path), "ckpt_0000000010.mxtckpt")
    blob = open(path, "rb").read()
    with open(path, "wb") as f:
        f.write(blob[:len(blob) // 2])
    step_fn2, get2, set2 = _numpy_trainer()
    report = run_resilient(step_fn2, LocalCheckpointer(tmp_path), 15,
                           get_state=get2, set_state=set2,
                           checkpoint_every=5)
    assert report.resumed_from == [5]   # 10 was corrupt, fell back
    assert report.final_step == 15
    # identical trajectory to a clean run over the same steps
    base_step, base_get, base_set = _numpy_trainer()
    base = run_resilient(base_step, LocalCheckpointer(tmp_path / "b"),
                         15, get_state=base_get, set_state=base_set,
                         checkpoint_every=5)
    np.testing.assert_allclose(get2()["w"], base_get()["w"])


# -- run_resilient: real gluon model (the acceptance e2e) ----------------------

def _gluon_trainer():
    """Tiny deterministic gluon MLP + plain SGD (stateless optimizer so
    params ARE the full state), fixed batches."""
    from mxnet_tpu import gluon
    from mxnet_tpu.gluon import nn

    mx.random.seed(11)
    np.random.seed(11)
    rng = np.random.RandomState(11)
    data = rng.normal(size=(64, 8)).astype(np.float32)
    labels = rng.randint(0, 3, size=64).astype(np.float32)
    batches = [(mx.nd.array(data[i:i + 16]),
                mx.nd.array(labels[i:i + 16]))
               for i in range(0, 64, 16)]

    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(3))
    net.initialize(init=mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    params = net.collect_params()

    def step_fn(step):
        x, y = batches[step % len(batches)]
        with mx.autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        trainer.step(x.shape[0])
        return float(loss.asnumpy().mean())

    def get_state():
        return {k: p.data().asnumpy() for k, p in params.items()}

    def set_state(state):
        for k, v in state.items():
            params[k].set_data(mx.nd.array(v))

    return step_fn, get_state, set_state


@pytest.mark.faults
def test_e2e_gluon_crash_resume_matches_uninterrupted(tmp_path,
                                                      fault_inject):
    """THE acceptance test: a gluon training run SIGTERMed mid-epoch by
    fault injection restarts in-process, resumes from the preemption
    checkpoint, and reproduces the uninterrupted run's loss trajectory
    and final parameters exactly."""
    num_steps = 24

    # uninterrupted reference trajectory
    step_fn, get_state, set_state = _gluon_trainer()
    base = run_resilient(step_fn, LocalCheckpointer(tmp_path / "base"),
                         num_steps, get_state=get_state,
                         set_state=set_state, checkpoint_every=8)
    base_params = get_state()
    assert base.final_step == num_steps and base.restarts == 0

    # crashed-and-resumed run
    fault_inject("sigterm_at_step:13")
    step_fn2, get2, set2 = _gluon_trainer()
    report = run_resilient(step_fn2, LocalCheckpointer(tmp_path / "c"),
                           num_steps, get_state=get2, set_state=set2,
                           checkpoint_every=8, max_restarts=3)
    assert report.preempted and report.restarts == 1
    assert report.final_step == num_steps
    assert report.resumed_from == [0, 13]

    # same steps, same losses, same final parameters
    assert sorted(report.losses) == sorted(base.losses)
    for s in sorted(base.losses):
        assert report.losses[s] == pytest.approx(base.losses[s],
                                                 rel=1e-5), f"step {s}"
    # param names carry a per-net auto prefix (hybridsequential0_ vs
    # hybridsequential1_); pair them positionally in sorted order
    crashed_params = get2()
    for bk, ck in zip(sorted(base_params), sorted(crashed_params)):
        np.testing.assert_allclose(crashed_params[ck], base_params[bk],
                                   rtol=1e-5, atol=1e-6)


# -- PreemptionHandler ---------------------------------------------------------

def test_preemption_handler_chains_previous(tmp_path):
    from mxnet_tpu.checkpoint import PreemptionHandler

    outer = []
    prev = signal.signal(signal.SIGTERM,
                         lambda s, f: outer.append("outer"))
    try:
        ck = LocalCheckpointer(tmp_path)
        with PreemptionHandler(ck, lambda: {"x": 1}, lambda: 3) as h:
            os.kill(os.getpid(), signal.SIGTERM)
            time.sleep(0.05)
            assert h.preempted.is_set()
            assert outer == ["outer"]   # the previous handler STILL ran
            assert h.maybe_checkpoint()
        assert ck.restore(3) == {"x": 1}
        # context exit restored the outer handler
        assert signal.getsignal(signal.SIGTERM) is not h._on_sigterm
    finally:
        signal.signal(signal.SIGTERM, prev)


def test_preemption_handler_restore_idempotent(tmp_path):
    from mxnet_tpu.checkpoint import PreemptionHandler

    prev = signal.getsignal(signal.SIGTERM)
    h = PreemptionHandler(LocalCheckpointer(tmp_path),
                          lambda: {}, lambda: 0)
    h.restore_handler()
    h.restore_handler()
    assert signal.getsignal(signal.SIGTERM) == prev


def test_preemption_handler_no_preempt_no_save(tmp_path):
    from mxnet_tpu.checkpoint import PreemptionHandler

    ck = LocalCheckpointer(tmp_path)
    with PreemptionHandler(ck, lambda: {}, lambda: 0) as h:
        assert not h.maybe_checkpoint()
    assert ck.latest_step() is None

# -- decorrelated jitter (PR 8) ------------------------------------------------

def test_retry_call_decorrelated_jitter_bounds():
    """jitter=True (default): every sleep lands in [backoff,
    max_backoff] and depends on the PREVIOUS sleep (uniform up to 3x
    it), so lockstep retry herds spread out."""
    sleeps = []

    def always():
        raise OSError("down")

    with pytest.raises(OSError):
        retry_call(always, retries=20, backoff=0.001, max_backoff=0.004,
                   on_retry=lambda a, e, s: sleeps.append(s))
    assert len(sleeps) == 20
    for s in sleeps:
        assert 0.001 <= s <= 0.004
    # with a cap 4x the floor and 20 draws, identical values would mean
    # the jitter is not actually sampling
    assert len(set(sleeps)) > 1


def test_retry_call_legacy_proportional_jitter():
    sleeps = []

    def always():
        raise OSError("down")

    with pytest.raises(OSError):
        retry_call(always, retries=3, backoff=0.001, jitter=0.5,
                   on_retry=lambda a, e, s: sleeps.append(s))
    # exponential base with at most +50% proportional noise
    for i, s in enumerate(sleeps):
        base = 0.001 * (2 ** i)
        assert base <= s <= base * 1.5 + 1e-9


# -- verify-after-write rewrite path (PR 8) ------------------------------------

@pytest.mark.faults
def test_save_verified_rewrites_once_on_bitrot(fault_inject, tmp_path):
    """corrupt_ckpt_write:1 bit-rots the first committed file AFTER the
    rename; _save_verified's readback must catch it and the single
    rewrite must produce a restorable checkpoint."""
    fault_inject("corrupt_ckpt_write:1")
    ck = LocalCheckpointer(tmp_path)
    resilience._save_verified(ck, 5, {"w": [1.0, 2.0]})
    assert ck.restore(5) == {"w": [1.0, 2.0]}


@pytest.mark.faults
def test_save_verified_raises_on_persistent_bitrot(fault_inject,
                                                   tmp_path):
    """When the rewrite is corrupted too (corrupt_ckpt_write:2), the
    failure must surface as CheckpointCorrupt — never a silent bad
    checkpoint."""
    fault_inject("corrupt_ckpt_write:2")
    ck = LocalCheckpointer(tmp_path)
    with pytest.raises(CheckpointCorrupt):
        resilience._save_verified(ck, 5, {"w": [1.0, 2.0]})


# -- recovery decisions as telemetry events (PR 8) -----------------------------

def _read_events(path):
    import json

    with open(path) as f:
        return [json.loads(ln) for ln in f.read().splitlines() if ln]


def test_resume_latest_emits_ckpt_fallback_event(tmp_path, monkeypatch):
    from mxnet_tpu import telemetry

    ck = LocalCheckpointer(tmp_path / "ck")
    ck.save(3, {"x": 1})
    ck.save(6, {"x": 2})
    with open(ck._path(6), "r+b") as f:    # bit-rot the newest
        f.seek(-1, os.SEEK_END)
        last = f.read(1)
        f.seek(-1, os.SEEK_END)
        f.write(bytes([last[0] ^ 0xFF]))
    path = str(tmp_path / "ev.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", path)
    telemetry.reset()
    got = {}
    try:
        assert resilience.resume_latest(ck, got.update) == 3
    finally:
        telemetry.reset()                   # close the sink
    assert got == {"x": 1}
    events = [r for r in _read_events(path) if r.get("type") == "event"]
    assert [e["event"] for e in events] == ["ckpt_fallback"]
    assert events[0]["step"] == 6
    assert events[0]["reason"] == "CheckpointCorrupt"


def test_flush_inflight_emits_dropped_event(tmp_path, monkeypatch):
    from mxnet_tpu import telemetry

    class FailingAsync:
        pending_step = 11

        def wait(self):
            raise OSError("backing store went away")

    path = str(tmp_path / "ev.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", path)
    telemetry.reset()
    try:
        resilience.flush_inflight(FailingAsync())   # must not raise
    finally:
        telemetry.reset()
    events = [r for r in _read_events(path) if r.get("type") == "event"]
    assert [e["event"] for e in events] == ["inflight_save_dropped"]
    assert events[0]["step"] == 11
    assert events[0]["reason"] == "OSError"


# -- retry_call total-elapsed budget (PR 20) ------------------------------------

def test_retry_call_max_elapsed_caps_total_time():
    """Unlike ``deadline`` (which only vetoes the next SLEEP), a slow
    fn() burning the whole budget inside one attempt still stops at the
    next failure — the partition-era property: KV retries hand over to
    the fencing checks instead of retrying unboundedly."""
    calls = []

    def slow_always():
        calls.append(1)
        time.sleep(0.03)
        raise OSError("partitioned")

    t0 = time.monotonic()
    with pytest.raises(mx.MXNetError, match="retry budget"):
        retry_call(slow_always, retries=1000, backoff=0.001, jitter=0.0,
                   max_elapsed=0.05)
    assert time.monotonic() - t0 < 2.0
    assert 1 < len(calls) < 10


def test_retry_call_max_elapsed_off_by_default():
    calls = []

    def flaky():
        calls.append(1)
        time.sleep(0.02)
        if len(calls) < 4:
            raise OSError("transient")
        return "ok"

    # four slow attempts, no budget: must still succeed
    assert retry_call(flaky, retries=10, backoff=0.001) == "ok"


# -- partition_split / pause_rank fault sites (PR 20) ---------------------------

@pytest.mark.faults
def test_fault_spec_partition_split(fault_inject, monkeypatch):
    fault_inject("partition_split:1,partition_split:2")
    monkeypatch.delenv("MXTPU_PARTITION_SECS", raising=False)
    assert resilience.partition_blocked(1)
    assert resilience.partition_blocked(2)
    assert not resilience.partition_blocked(0)
    # persistent (no heal configured): still blocked on re-check
    assert resilience.partition_blocked(1)


@pytest.mark.faults
def test_partition_split_heals_after_deadline(fault_inject, monkeypatch):
    fault_inject("partition_split:1")
    monkeypatch.setenv("MXTPU_PARTITION_SECS", "0.15")
    assert resilience.partition_blocked(1)   # starts the heal timer
    deadline = time.monotonic() + 5.0
    while resilience.partition_blocked(1):
        assert time.monotonic() < deadline, "partition never healed"
        time.sleep(0.02)
    assert not resilience.partition_blocked(1)   # healed stays healed


@pytest.mark.faults
def test_fault_spec_pause_rank_parses_one_shot(fault_inject):
    fault_inject("pause_rank:3")
    plan = resilience._plan()
    assert 3 in plan.list_args["pause_rank"]
    # one-shot per listed rank, like the other SDC sites
    assert resilience.consume_rank_fault("pause_rank", 3)
    assert not resilience.consume_rank_fault("pause_rank", 3)
    assert not resilience.consume_rank_fault("pause_rank", 0)


# -- wall-clock-jump immunity (PR 20: monotonic freshness arithmetic) -----------

def test_wall_clock_jump_does_not_kill_detector(tmp_path, monkeypatch):
    """An NTP step (hours, either direction) must not fake a partition:
    heartbeat freshness and phi inter-arrival math run on
    time.monotonic(), never time.time()."""
    from mxnet_tpu import distributed

    kv = distributed.FileKV(str(tmp_path))
    hb = resilience.HeartbeatPublisher(kv, 1, interval=0.05).start()
    det = resilience.FailureDetector(kv, 0, [0, 1], timeout=5.0,
                                     check_interval=0.0)
    try:
        deadline = time.monotonic() + 5.0
        while not det.peer_steps() and time.monotonic() < deadline:
            det.poll(force=True)
            time.sleep(0.02)
        assert det.poll(force=True) == set()
        real_time = time.time
        monkeypatch.setattr(time, "time",
                            lambda: real_time() + 86400.0)
        for _ in range(10):     # a day forward: nobody dies
            assert det.poll(force=True) == set()
            time.sleep(0.02)
        monkeypatch.setattr(time, "time",
                            lambda: real_time() - 86400.0)
        for _ in range(10):     # two days backward: nobody dies
            assert det.poll(force=True) == set()
            time.sleep(0.02)
    finally:
        hb.stop()


def test_wall_clock_jump_does_not_expire_leases(monkeypatch):
    """GangKVServer lease deadlines are monotonic: a wall-clock jump
    while a client is connected must not mass-expire its ephemeral
    keys (heartbeats) and fake a gang-wide death."""
    from mxnet_tpu import distributed

    server = distributed.GangKVServer(lease_ttl=30.0).start()
    kv = distributed.TcpKV(server.addr, rank=0, lease_ttl=30.0)
    try:
        kv.put("hb/0", b"alive")        # ephemeral -> leased
        real_time = time.time
        monkeypatch.setattr(time, "time",
                            lambda: real_time() + 86400.0)
        time.sleep(0.3)                 # a few sweeper passes
        assert kv.get("hb/0") == b"alive"
    finally:
        monkeypatch.undo()
        kv.close()
        server.stop()
