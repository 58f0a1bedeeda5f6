"""Always-on telemetry (mxnet_tpu/telemetry.py): metrics registry,
per-step StepStats assembly, MFU accounting, crash-safe JSONL event log,
zero-extra-device-work regression, and the trace_report.py consumer."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, numerics, telemetry
from mxnet_tpu.gluon import captured, nn
from mxnet_tpu.test_utils import cpu_child_env

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CRASH_WORKER = os.path.join(_REPO, "tests", "telemetry_crash_worker.py")
_TRACE_REPORT = os.path.join(_REPO, "tools", "trace_report.py")


@pytest.fixture(autouse=True)
def _telemetry_clean(monkeypatch):
    """Each test starts from empty ring/registry and no sink."""
    monkeypatch.delenv("MXTPU_TELEMETRY_PATH", raising=False)
    monkeypatch.delenv("MXTPU_TELEMETRY", raising=False)
    telemetry.reset()
    telemetry.REGISTRY.reset()
    yield
    telemetry.reset()
    telemetry.REGISTRY.reset()


def _tiny(seed=0):
    net = nn.Dense(4, in_units=8)
    net.initialize()
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01})
    loss_fn = gluon.loss.L2Loss()
    rng = np.random.RandomState(seed)
    x = mx.nd.array(rng.rand(16, 8).astype("float32"))
    y = mx.nd.array(rng.rand(16, 4).astype("float32"))
    return net, loss_fn, trainer, x, y


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


# -- metrics registry ----------------------------------------------------------

def test_metrics_registry():
    reg = telemetry.MetricsRegistry()
    reg.counter("a").inc()
    reg.counter("a").inc(2)
    reg.gauge("g").set(7)
    h = reg.histogram("h")
    for v in (1.0, 3.0, 2.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["a"] == 3
    assert snap["g"] == 7
    assert snap["h"] == {"count": 3, "total": 6.0, "min": 1.0, "max": 3.0}
    # a name is ONE metric type forever — silent aliasing would corrupt
    # whichever consumer registered first
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("a")


def test_disabled_is_a_noop(monkeypatch):
    monkeypatch.setenv("MXTPU_TELEMETRY", "0")
    assert telemetry.step_begin() is None
    telemetry.count("x")
    telemetry.event("y", step=1)
    assert telemetry.recent_steps() == []
    assert telemetry.event_counts() == {}


# -- the acceptance pin: one captured step, one record, no extra work ----------

def test_captured_step_emits_one_complete_record():
    """ISSUE 7 acceptance: one healthy captured-path train step emits
    exactly one StepStats with non-null MFU, breakdown shares summing to
    ~1.0, and no device work beyond the step's own single dispatch +
    single guard readback."""
    net, loss_fn, trainer, x, y = _tiny()
    for _ in range(3):
        trainer.train_step(net, loss_fn, x, y)
    telemetry.reset()
    captured.reset_counters()
    numerics.reset_readback_count()

    trainer.train_step(net, loss_fn, x, y)

    recs = telemetry.recent_steps()
    assert len(recs) == 1
    rec = recs[0]
    telemetry.validate_record(rec)
    assert rec["path"] == "captured"
    assert rec["skipped"] is False
    assert rec["step"] == trainer._step_count
    assert rec["cache_hit"] is True
    assert rec["flops"] is not None and rec["flops"] > 0
    assert rec["mfu"] is not None and rec["mfu"] > 0
    assert abs(sum(rec["shares"].values()) - 1.0) < 0.02
    assert rec["breakdown_us"]["dispatch"] > 0
    assert rec["breakdown_us"]["readback"] > 0
    # the telemetry cost the step actually paid, in device terms: none
    assert captured.dispatch_count() == 1
    assert numerics.readback_count() == 1


def test_zero_extra_dispatch_readback_regression():
    """PR 6 pins: N captured steps = N dispatches, N guard readbacks,
    zero runtime retraces — telemetry (incl. the cost-analysis lowering
    behind MFU) must not move any of those counters."""
    net, loss_fn, trainer, x, y = _tiny()
    for _ in range(3):
        trainer.train_step(net, loss_fn, x, y)
    telemetry.reset()
    captured.reset_counters()
    numerics.reset_readback_count()
    n = 5
    for _ in range(n):
        trainer.train_step(net, loss_fn, x, y)
    assert captured.dispatch_count() == n
    assert captured.trace_count() == 0
    assert numerics.readback_count() == n
    assert len(telemetry.recent_steps(path="captured")) == n


def test_overhead_below_one_percent():
    """The <1% budget, pinned: the full per-record mechanism cost
    (step_begin + scope hooks + notes + step_end assembly into the
    ring) must stay under 1% of a representative captured step's wall
    time.  The model is deliberately NOT the 4-unit toy used elsewhere
    — the budget is relative to a step doing real work, and a
    microscopic step would pin Python dict overhead against XLA
    dispatch overhead, which bounds nothing."""
    net = nn.HybridSequential()
    net.add(nn.Dense(256, in_units=256, activation="relu"))
    net.add(nn.Dense(256, in_units=256))
    net.initialize()
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01})
    loss_fn = gluon.loss.L2Loss()
    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.rand(128, 256).astype("float32"))
    y = mx.nd.array(rng.rand(128, 256).astype("float32"))
    for _ in range(3):
        trainer.train_step(net, loss_fn, x, y)
    times = []
    for _ in range(10):
        t0 = time.perf_counter()
        trainer.train_step(net, loss_fn, x, y)
        times.append(time.perf_counter() - t0)
    step_s = sorted(times)[len(times) // 2]

    # the least of five repeats: a neighbour worker's burst lengthens
    # some repeats, never all of them, and the mechanism's own cost is
    # what the shortest one shows
    n, repeats = 300, []
    for _ in range(5):
        telemetry.reset()
        t0 = time.perf_counter()
        for i in range(n):
            acc = telemetry.step_begin(path="captured")
            telemetry.on_scope("captured_host_prep", 1e-4)
            telemetry.on_scope("captured_step", 2e-4)
            telemetry.on_scope("guard_readback", 1e-5)
            telemetry.note(flops=1e6, cache_hit=True, grad_norm=1.0)
            telemetry.step_end(acc, step=i)
        repeats.append((time.perf_counter() - t0) / n)
    mech_s = min(repeats)
    assert mech_s < 0.01 * step_s, \
        f"telemetry {mech_s * 1e6:.1f}us/record vs step " \
        f"{step_s * 1e6:.1f}us"


# -- JSONL sink: schema roundtrip and crash consistency ------------------------

def test_jsonl_schema_roundtrip(monkeypatch, tmp_path):
    path = str(tmp_path / "train_events.jsonl")
    net, loss_fn, trainer, x, y = _tiny()
    for _ in range(2):
        trainer.train_step(net, loss_fn, x, y)    # warm, unsunk
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", path)
    telemetry.reset()
    trainer.train_step(net, loss_fn, x, y)
    telemetry.event("marker", step=99, note="roundtrip")
    telemetry.reset()   # closes the sink handle

    recs = _read_jsonl(path)
    assert [r["type"] for r in recs] == ["step", "event"]
    for rec in recs:
        telemetry.validate_record(rec)
    step, ev = recs
    assert step["run"] == ev["run"] == telemetry.run_id()
    assert step["path"] == "captured"
    assert ev["event"] == "marker" and ev["step"] == 99


@pytest.mark.faults
def test_crash_mid_append_leaves_parseable_log(tmp_path):
    """telemetry_crash kills the process after HALF a line: every
    earlier line still parses and readers skip the truncated tail."""
    from mxnet_tpu import resilience

    path = str(tmp_path / "ev.jsonl")
    proc = subprocess.run(
        [sys.executable, _CRASH_WORKER, path],
        env=cpu_child_env(), capture_output=True, text=True, timeout=180)
    assert proc.returncode == resilience.CRASH_EXIT_CODE, \
        (proc.returncode, proc.stdout[-2000:], proc.stderr[-2000:])

    with open(path) as f:
        lines = f.read().splitlines()
    assert len(lines) == 4          # 3 whole lines + the torn tail
    good = [json.loads(ln) for ln in lines[:3]]
    assert [g["step"] for g in good] == [0, 1, 2]
    with pytest.raises(ValueError):
        json.loads(lines[3])

    r = subprocess.run(
        [sys.executable, _TRACE_REPORT, path, "--validate"],
        env=cpu_child_env(), capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "skipping unparseable line" in r.stderr
    assert "3 records validate" in r.stdout


# -- resilience events carry correct step ids ----------------------------------

@pytest.mark.faults
def test_skip_step_event(fault_inject, monkeypatch, tmp_path):
    path = str(tmp_path / "ev.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", path)
    params = []
    import jax.numpy as jnp
    for k, shape in enumerate([(5, 7), (3,)]):
        p = gluon.Parameter(f"p{k}_weight", shape=shape, dtype="float32")
        p.initialize(init=mx.init.Zero())
        p.data()._set_data(jnp.asarray(
            np.random.RandomState(k).standard_normal(shape)
            .astype("float32")))
        params.append(p)
    trainer = gluon.Trainer(params, "adam", {"learning_rate": 1e-3},
                            kvstore=None)

    def set_grads():
        for p in params:
            p.list_grad()[0]._set_data(
                jnp.ones(p.shape, jnp.float32))

    set_grads()
    trainer.step(2, ignore_stale_grad=True)       # healthy
    fault_inject("nan_grad:1")
    set_grads()
    trainer.step(2, ignore_stale_grad=True)       # poisoned -> skipped
    telemetry.reset()   # close sink so the file is complete

    assert len(trainer.skipped_steps) == 1
    recs = _read_jsonl(path)
    for rec in recs:
        telemetry.validate_record(rec)
    evs = [r for r in recs if r.get("type") == "event"
           and r["event"] == "step_skipped"]
    assert len(evs) == 1
    assert evs[0]["step"] == trainer.skipped_steps[0].step == 2
    steps = [r for r in recs if r.get("type") == "step"]
    assert [s["path"] for s in steps] == ["manual", "manual"]
    assert [s["skipped"] for s in steps] == [False, True]
    assert steps[1]["step"] == 2


def test_divergence_rollback_event(monkeypatch, tmp_path):
    from mxnet_tpu.resilience import LocalCheckpointer

    path = str(tmp_path / "ev.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", path)
    ck = LocalCheckpointer(tmp_path / "ck")
    ck.save(7, {"w": np.arange(4.0)})
    restored = {}
    mon = numerics.DivergenceMonitor(
        checkpointer=ck, set_state=restored.update, max_bad_steps=3)
    for i in range(2):
        assert not mon.observe(step=i, loss=float("nan"),
                               batch_indices=[i])
    assert mon.observe(step=2, loss=float("nan"), batch_indices=[2])
    assert telemetry.event_counts() == {"divergence_rollback": 1}
    telemetry.reset()   # close the sink before reading the file

    (ev,) = _read_jsonl(path)
    telemetry.validate_record(ev)
    assert ev["event"] == "divergence_rollback"
    assert ev["step"] == 7            # the step rolled back TO
    assert ev["last_step"] == 2       # the last observed bad step
    assert ev["bad_steps"] == 3
    assert ev["quarantined"] == 3


def test_watchdog_expired_event():
    from mxnet_tpu import resilience

    wd = resilience.Watchdog(0.05, name="telemetry_test", action="none",
                             dump_stacks=False)
    wd.start()
    deadline = time.time() + 10
    while not wd.expired and time.time() < deadline:
        time.sleep(0.01)
    wd.cancel()
    assert wd.expired
    assert telemetry.event_counts().get("watchdog_expired") == 1


# -- component counters --------------------------------------------------------

def test_prefetcher_counters():
    from mxnet_tpu.gluon.data.prefetcher import DevicePrefetcher

    data = [np.ones((4, 3), np.float32) for _ in range(5)]
    assert len(list(DevicePrefetcher(data, depth=2))) == 5
    assert telemetry.REGISTRY.counter("input.batches").value == 5
    assert telemetry.REGISTRY.gauge("input.queue_depth").value is not None
    assert len(list(DevicePrefetcher(data, depth=0))) == 5
    assert telemetry.REGISTRY.counter("input.batches").value == 10
    assert telemetry.REGISTRY.counter("input.wait_us").value >= 0


def test_collective_counters():
    from mxnet_tpu import kvstore as kvs

    kv = kvs.create("device")
    kv.init(0, mx.nd.array(np.ones((8,), np.float32)))
    g = mx.nd.array(np.full((8,), 2.0, np.float32))
    kv.bucketed_pushpull([0], [g], outs=[g])
    assert telemetry.REGISTRY.counter("collective.buckets").value == 1
    assert telemetry.REGISTRY.counter("collective.bytes").value == 32


def test_ckpt_counters(monkeypatch, tmp_path):
    from mxnet_tpu import checkpoint

    path = str(tmp_path / "ev.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", path)
    ck = checkpoint.AsyncCheckpointer(
        str(tmp_path / "ck"), async_save=False, rank=0, world_size=1)
    ck.save(3, {"w": np.arange(8.0)})
    assert telemetry.REGISTRY.counter("ckpt.saves").value == 1
    assert telemetry.REGISTRY.counter("ckpt.stall_us").value > 0
    assert telemetry.REGISTRY.counter("ckpt.commits").value == 1
    assert telemetry.event_counts().get("ckpt_commit") == 1
    telemetry.reset()
    evs = [r for r in _read_jsonl(path)
           if r.get("event") == "ckpt_commit"]
    assert len(evs) == 1 and evs[0]["step"] == 3


# -- profiler.scope enters a TraceAnnotation on every use ----------------------

def test_scope_enters_trace_annotation_whoever_traces(monkeypatch):
    """No gate on this module's own flags: a span must show in any live
    ``jax.profiler`` session, so the annotation is entered on every use
    (a no-op ``TraceMe`` when no session is live), attributes and all."""
    from mxnet_tpu import profiler

    seen = []

    class _Stub:
        def __init__(self, name, **attrs):
            seen.append(["made", name, attrs])

        def set_metadata(self, **attrs):
            seen.append(["set", attrs])

        def __enter__(self):
            seen.append(["enter"])
            return self

        def __exit__(self, *exc):
            seen.append(["exit"])
            return False

    monkeypatch.setattr(profiler, "_TraceMe", _Stub)
    with profiler.annotate("idle_scope"):
        pass
    assert seen == [["made", "idle_scope", {}], ["enter"], ["exit"]]
    del seen[:]
    profiler.set_state("run")
    try:
        with profiler.scope("hot_scope", step=3) as sp:
            sp.set(n=2)
    finally:
        profiler.set_state("stop")
    assert seen == [["made", "hot_scope", {"step": 3}], ["enter"],
                    ["set", {"n": 2}], ["exit"]]
    assert sp.t1 >= sp.t0


# -- satellite: CI smoke — one step, validate everything, run the CLI ----------

def test_smoke_one_step_validate_and_report(monkeypatch, tmp_path):
    path = str(tmp_path / "train_events.jsonl")
    net, loss_fn, trainer, x, y = _tiny()
    for _ in range(2):
        trainer.train_step(net, loss_fn, x, y)
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", path)
    telemetry.reset()
    trainer.train_step(net, loss_fn, x, y)
    telemetry.reset()

    recs = _read_jsonl(path)
    assert len([r for r in recs if r["type"] == "step"]) == 1
    for rec in recs:
        telemetry.validate_record(rec)

    r = subprocess.run(
        [sys.executable, _TRACE_REPORT, path, "--validate"],
        env=cpu_child_env(), capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "validate against schema" in r.stdout
    assert "1 step records" in r.stdout
    assert "breakdown" in r.stdout
    assert "mfu" in r.stdout
