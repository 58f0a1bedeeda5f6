"""Fleet observability plane (ISSUE 14): telemetry schema v3 identity
stamping, event-log rotation (size cap + torn-rotation crash safety),
O(new lines) incremental tailing, distributed request spans through the
serving path, the per-host collector + FleetView rollup aggregation,
the Prometheus exporter, on-demand profile capture, and the
tools/fleet_report.py consumer."""

import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from mxnet_tpu import distributed, obs, resilience, telemetry
from mxnet_tpu.obs.collector import (FleetView, HostCollector,
                                     request_profile)
from mxnet_tpu.obs.exporter import MetricsExporter, render_prometheus
from mxnet_tpu.obs.spans import Trace, render_tree
from mxnet_tpu.test_utils import cpu_child_env

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_PKG = os.path.join(_REPO, "mxnet_tpu")
_FLEET_REPORT = os.path.join(_REPO, "tools", "fleet_report.py")
_OBS_WORKER = os.path.join(_REPO, "tests", "obs_fleet_worker.py")


@pytest.fixture(autouse=True)
def _obs_clean(monkeypatch):
    """Each test starts with no sink, no identity, no cached tails, and
    nothing the bootstrap may have started."""
    for var in ("MXTPU_TELEMETRY_PATH", "MXTPU_TELEMETRY",
                "MXTPU_TELEMETRY_MAX_MB", "MXTPU_WORKER_RANK",
                "MXTPU_NUM_WORKERS", "MXTPU_METRICS_PORT",
                "MXTPU_OBS_COLLECTOR", "MXTPU_GANG_DIR"):
        monkeypatch.delenv(var, raising=False)
    telemetry.reset()
    telemetry.REGISTRY.reset()
    yield
    obs.shutdown()
    telemetry.reset()
    telemetry.REGISTRY.reset()


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def _emit_step(step=0, **over):
    """One synthetic-but-schema-valid step record via the real
    assembly path (step_begin/step_end)."""
    acc = telemetry.step_begin(path="captured")
    telemetry.on_scope("captured_step", 0.001)
    telemetry.note(flops=over.pop("flops", 1e9))
    return telemetry.step_end(acc, step=step, **over)


# -- schema v3: fleet identity -------------------------------------------------

def test_identity_from_env(tmp_path, monkeypatch):
    path = str(tmp_path / "t.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", path)
    monkeypatch.setenv("MXTPU_WORKER_RANK", "3")
    monkeypatch.setenv("MXTPU_NUM_WORKERS", "8")
    telemetry.reset()       # drop the cached (empty) identity
    telemetry.event("resume", step=1)
    _emit_step(step=1)
    for rec in _read_jsonl(path):
        assert rec["rank"] == 3 and rec["world"] == 8
        assert rec["v"] == telemetry.SCHEMA_VERSION == 8
        telemetry.validate_record(rec)


def test_set_identity_merges_and_explicit_fields_win(tmp_path,
                                                     monkeypatch):
    path = str(tmp_path / "t.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", path)
    telemetry.set_identity(rank=0, world=4)
    telemetry.set_identity(replica_id=2)            # merge, not replace
    assert telemetry.identity() == {"rank": 0, "world": 4,
                                    "replica_id": 2}
    # an event that NAMES a rank (straggler suspicion) must keep it:
    # identity is stamped with setdefault, never overwrites
    telemetry.event("straggler_suspected", rank=1, step=5,
                    mean_collective_share=0.8)
    rec = _read_jsonl(path)[-1]
    assert rec["rank"] == 1 and rec["world"] == 4
    assert rec["replica_id"] == 2
    telemetry.validate_record(rec)


def test_older_schema_versions_still_validate():
    base = {"type": "event", "event": "resume", "run": "r", "t": 1.0}
    for v in (1, 2, 3, 4, 5, 6, 7, 8):
        telemetry.validate_record(dict(base, v=v))
    with pytest.raises(ValueError, match="schema version"):
        telemetry.validate_record(dict(base, v=9))
    with pytest.raises(ValueError, match="rank"):
        telemetry.validate_record(dict(base, v=3, rank="zero"))
    with pytest.raises(ValueError, match="world"):
        telemetry.validate_record(dict(base, v=3, world=0))


def test_span_field_validation():
    req = {"type": "request", "v": 3, "run": "r", "t": 1.0,
           "queue_us": 1.0, "prefill_us": 2.0,
           "decode_us_per_token": 3.0, "bucket": [1, 8],
           "padded_fraction": 0.0}
    root = {"span_id": "a", "parent": None, "name": "frontdoor",
            "t0": 1.0, "dur_us": 10.0}
    kid = {"span_id": "b", "parent": "a", "name": "batcher",
           "t0": 1.0, "dur_us": 5.0}
    telemetry.validate_record(
        dict(req, trace_id="t1", spans=[root, kid]))
    for bad, msg in (
            ([kid], "root"),                          # no root
            ([root, dict(kid, parent=None)], "root"),  # two roots
            ([root, dict(kid, dur_us=None)], "dur_us"),
            ([root, dict(kid, parent="zz")], "parent"),
            ([root, dict(kid, span_id="a")], "duplicate"),
            ([], "empty")):
        with pytest.raises(ValueError, match=msg):
            telemetry.validate_record(
                dict(req, trace_id="t1", spans=bad))


# -- S1: size-capped rotation --------------------------------------------------

def test_rotation_size_cap_no_record_loss(tmp_path, monkeypatch):
    path = str(tmp_path / "t.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", path)
    monkeypatch.setenv("MXTPU_TELEMETRY_MAX_MB", "0.01")   # 10 kB
    telemetry.reset()
    n = 120                                  # ~110 B/line: one rotation
    for i in range(n):
        telemetry.event("resume", step=i)
    assert os.path.exists(path + ".1")
    assert not os.path.exists(path + ".2")
    recs = _read_jsonl(path + ".1") + _read_jsonl(path)
    assert [r["step"] for r in recs] == list(range(n))
    assert os.path.getsize(path) <= 10000


def test_torn_rotation_crash_is_recoverable(tmp_path):
    """telemetry_rotate kills the process BETWEEN the rename and the
    reopen; the rotated file must hold every record emitted so far and
    the readers must see them all."""
    path = str(tmp_path / "t.jsonl")
    prog = ("import mxnet_tpu.telemetry as t\n"
            "for i in range(200):\n"
            "    t.event('resume', step=i)\n")
    proc = subprocess.run(
        [sys.executable, "-c", prog],
        env=cpu_child_env(MXTPU_TELEMETRY_PATH=path,
                          MXTPU_TELEMETRY_MAX_MB="0.003",
                          MXTPU_FAULT_INJECT="telemetry_rotate:1"),
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == resilience.CRASH_EXIT_CODE, proc.stderr
    assert os.path.exists(path + ".1")
    assert not os.path.exists(path)          # torn: died before reopen
    rotated = _read_jsonl(path + ".1")
    steps = [r["step"] for r in rotated]
    assert steps == list(range(len(steps))) and steps  # no loss, no tear
    # both readers recover across the torn boundary
    assert [r["step"] for r in telemetry.tail_records(path)] == steps
    out = subprocess.run(
        [sys.executable, _FLEET_REPORT, path, "--validate"],
        env=cpu_child_env(), capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert f"{len(steps)} records" in out.stdout


# -- S2: incremental tail with a bytes-read pin --------------------------------

def test_tail_is_o_new_lines(tmp_path, monkeypatch):
    path = str(tmp_path / "t.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", path)
    for i in range(50):
        telemetry.event("resume", step=i)
    size0 = os.path.getsize(path)
    assert len(telemetry.tail_records(path)) == 50
    assert telemetry.tail_bytes_read() == size0
    # steady state: re-reading an unchanged log costs ZERO bytes
    assert telemetry.tail_records(path) == []
    assert telemetry.tail_bytes_read() == size0
    # two appended records cost exactly their own bytes
    telemetry.event("resume", step=50)
    telemetry.event("resume", step=51)
    new_bytes = os.path.getsize(path) - size0
    got = telemetry.tail_records(path)
    assert [r["step"] for r in got] == [50, 51]
    assert telemetry.tail_bytes_read() == size0 + new_bytes
    # recent_steps(jsonl=...) rides the same offset machinery
    _emit_step(step=52)
    steps = telemetry.recent_steps(jsonl=path)
    assert steps and steps[-1]["step"] == 52


def test_tail_survives_rotation(tmp_path, monkeypatch):
    path = str(tmp_path / "t.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", path)
    monkeypatch.setenv("MXTPU_TELEMETRY_MAX_MB", "0.002")  # ~13 lines
    telemetry.reset()
    seen = []
    for i in range(60):
        telemetry.event("resume", step=i)
        seen.extend(r["step"] for r in telemetry.tail_records(path))
    seen.extend(r["step"] for r in telemetry.tail_records(path))
    assert seen == list(range(60))           # nothing lost, nothing twice


def test_half_flushed_line_is_not_consumed(tmp_path):
    path = str(tmp_path / "t.jsonl")
    with open(path, "w") as f:
        f.write('{"type": "event", "event": "resume", "step": 0}\n')
        f.write('{"type": "event", "ev')            # torn tail
    recs = telemetry.tail_records(path)
    assert [r["step"] for r in recs] == [0]
    with open(path, "a") as f:                      # flush completes
        f.write('ent": "resume", "step": 1}\n')
    assert [r["step"] for r in telemetry.tail_records(path)] == [1]


# -- S3: every event emitter in the repo produces a valid record ---------------

def test_every_event_kind_in_repo_validates(tmp_path, monkeypatch):
    pat = re.compile(
        r"(?:telemetry\.event|_tel_event)\(\s*[\"']([a-z0-9_]+)[\"']")
    kinds = set()
    for root, _dirs, files in os.walk(_PKG):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(root, fn)) as f:
                kinds.update(pat.findall(f.read()))
    assert len(kinds) >= 15, f"emitter inventory shrank: {sorted(kinds)}"
    for probe in ("mesh_reshape", "straggler_suspected",
                  "profile_captured", "serving_reload", "resume"):
        assert probe in kinds
    path = str(tmp_path / "t.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", path)
    telemetry.reset()
    for kind in sorted(kinds):
        telemetry.event(kind, step=1, rank=0)
    recs = _read_jsonl(path)
    assert {r["event"] for r in recs} == kinds
    for rec in recs:
        telemetry.validate_record(rec)


# -- spans ---------------------------------------------------------------------

def test_trace_span_tree_lifecycle():
    tr = Trace()
    root = tr.begin("frontdoor", t0=100.0)
    child = tr.begin("batcher", parent=root, t0=100.1, replica_id=3)
    tr.begin("queue", parent=child, t0=100.1).close(dur_us=50.0)
    assert not tr.closed()                   # root + batcher still open
    child.close(dur_us=200.0)
    tr.close_open(t_end=100.2)
    assert tr.closed()
    fields = tr.to_fields()
    assert fields["trace_id"] == tr.trace_id
    assert len(fields["spans"]) == 3
    lines = render_tree(fields["spans"])
    assert lines[0].startswith("frontdoor")
    assert lines[1].strip().startswith("batcher")
    assert "replica_id=3" in lines[1]
    assert lines[2].strip().startswith("queue")
    # an abandoned open span (shed submit, never served) is dropped
    tr2 = Trace()
    tr2.begin("frontdoor", t0=1.0).close(dur_us=5.0)
    tr2.begin("batcher", parent=tr2.root(), t0=1.0)   # never closed
    assert [s["name"] for s in tr2.to_fields()["spans"]] == ["frontdoor"]


class _FakeEngine:
    """serve_group-compatible stand-in: real batcher/FrontDoor code
    path, no model, no compile — spans and records come out the same
    shape as the real engine's."""

    batch_buckets = (4,)

    def serve_group(self, prompts, max_new_tokens, temperature=None,
                    rng=None):
        now = time.time()
        outs = [list(range(int(m))) for m in max_new_tokens]
        timings = {"bucket": [len(prompts), 8], "generation": 0,
                   "prefill_us": 120.0, "decode_us_per_token": 30.0,
                   "padded_fraction": 0.25, "t_prefill0": now,
                   "t_decode0": now + 1e-4,
                   "decode_us": 30.0 * max(len(o) for o in outs)}
        return outs, timings


def test_request_spans_through_frontdoor(tmp_path, monkeypatch):
    from mxnet_tpu import serving

    path = str(tmp_path / "serve.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", path)
    telemetry.reset()
    replicas = [serving.ReplicaServer(_FakeEngine(), rank=r)
                for r in (0, 1)]
    door = serving.FrontDoor(replicas)
    try:
        futs = [door.submit(f"p{i}", max_new_tokens=4)
                for i in range(3)]
        for f in futs:
            assert f.result(timeout=30)["tokens"] == [0, 1, 2, 3]
    finally:
        door.close()
    recs = [r for r in _read_jsonl(path) if r["type"] == "request"]
    assert len(recs) == 3
    seen_replicas = set()
    for rec in recs:
        telemetry.validate_record(rec)
        spans = {s["name"]: s for s in rec["spans"]}
        assert set(spans) == {"frontdoor", "batcher", "queue",
                              "prefill", "decode"}
        assert spans["frontdoor"]["parent"] is None
        assert spans["batcher"]["parent"] == spans["frontdoor"]["span_id"]
        for leaf in ("queue", "prefill", "decode"):
            assert spans[leaf]["parent"] == spans["batcher"]["span_id"]
        assert all(s["dur_us"] >= 0 for s in rec["spans"])
        assert spans["decode"]["attrs"]["new_tokens"] == 4
        assert rec["replica_id"] == spans["batcher"]["attrs"]["replica_id"]
        seen_replicas.add(rec["replica_id"])
    assert seen_replicas <= {0, 1}


def test_direct_batcher_submit_roots_at_batcher(tmp_path, monkeypatch):
    from mxnet_tpu.serving.batcher import ContinuousBatcher

    path = str(tmp_path / "serve.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", path)
    telemetry.reset()
    b = ContinuousBatcher(_FakeEngine(), max_delay_ms=0.0)
    try:
        b.submit("p", max_new_tokens=2).result(timeout=30)
    finally:
        b.close()
    rec = [r for r in _read_jsonl(path) if r["type"] == "request"][0]
    telemetry.validate_record(rec)
    roots = [s for s in rec["spans"] if s["parent"] is None]
    assert len(roots) == 1 and roots[0]["name"] == "batcher"


# -- collector + FleetView -----------------------------------------------------

def _seed_rank_log(tmp_path, rank, interval_us, mfu, shares, events=()):
    path = str(tmp_path / f"rank{rank}.jsonl")
    with open(path, "w") as f:
        for i in range(10):
            f.write(json.dumps({
                "type": "step", "step": i, "interval_us": interval_us,
                "wall_us": interval_us * 0.9, "mfu": mfu,
                "shares": shares}) + "\n")
        for e in events:
            f.write(json.dumps(dict(
                {"type": "event", "t": time.time()}, **e)) + "\n")
    return path


def test_collector_rollup_and_fleet_view(tmp_path):
    kv = distributed.FileKV(str(tmp_path / "kv"))
    fast = {"data": 0.05, "host_prep": 0.05, "dispatch": 0.1,
            "readback": 0.0, "collective": 0.7, "other": 0.1}
    slow = {"data": 0.05, "host_prep": 0.05, "dispatch": 0.1,
            "readback": 0.0, "collective": 0.05, "other": 0.75}
    logs = {
        0: _seed_rank_log(tmp_path, 0, 1000.0, 0.30, fast, events=[
            {"event": "straggler_suspected", "rank": 1,
             "mean_collective_share": 0.8, "step": 7},
            {"event": "mesh_reshape", "epoch": 1, "world": 3}]),
        1: _seed_rank_log(tmp_path, 1, 2000.0, 0.15, slow),
        2: _seed_rank_log(tmp_path, 2, 1000.0, 0.30, fast),
    }
    for rank, path in logs.items():
        c = HostCollector(path=path, kv=kv, rank=rank, world=3)
        c.poll_once()
        roll = c.rollup()
        assert roll["steps_total"] == roll["steps_window"] == 10
        assert roll["interval_us_mean"] == pytest.approx(
            1000.0 if rank != 1 else 2000.0)
    view = FleetView(kv)
    view.refresh()
    s = view.summary()
    assert s["ranks"] == [0, 1, 2] and s["world"] == 3
    assert s["steps_total"] == 30
    assert s["fleet_mfu"] == pytest.approx(0.25)      # (0.3+0.15+0.3)/3
    assert s["slowest_rank"] == 1
    assert s["interval_skew"] == pytest.approx(2.0)
    (straggler,) = s["stragglers"]
    assert straggler["rank"] == 1 and straggler["suspected_by"] == 0
    assert straggler["stall_bucket"] == "other"
    assert straggler["stall_share"] == pytest.approx(0.75)
    assert straggler["slowdown_vs_median"] == pytest.approx(2.0)
    kinds = [e["event"] for e in s["timeline"]]
    assert "mesh_reshape" in kinds and "straggler_suspected" in kinds


def test_collector_thread_stays_off_train_thread(tmp_path, monkeypatch):
    path = str(tmp_path / "t.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", path)
    telemetry.reset()
    kv = distributed.FileKV(str(tmp_path / "kv"))
    c = HostCollector(path=path, kv=kv, rank=0, world=1,
                      period_s=0.05).start()
    try:
        main_tid = threading.get_ident()
        assert c._thread.ident != main_tid
        for i in range(5):
            telemetry.event("resume", step=i)
        deadline = time.monotonic() + 10
        roll = None
        while time.monotonic() < deadline:
            roll = kv.get_json("obs/rollup/0")
            if roll is not None:
                break
            time.sleep(0.02)
        assert roll is not None and roll["rank"] == 0
    finally:
        c.close()


# -- on-demand profiling -------------------------------------------------------

def test_profile_request_roundtrip(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_PROFILE_BUDGET_S", "5.0")
    path = str(tmp_path / "t.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", path)
    telemetry.reset()
    with open(path, "w") as f:
        f.write(json.dumps({"type": "step", "step": 0}) + "\n")
    kv = distributed.FileKV(str(tmp_path / "kv"))
    c = HostCollector(path=path, kv=kv, rank=0, world=1,
                      hlo_provider=lambda: "HloModule step")
    c.poll_once()                            # folds step 0, no request
    logdir = str(tmp_path / "prof")
    req_id = request_profile(kv, 0, steps=1, logdir=logdir)
    # a step landing mid-capture releases the bounded wait
    t = threading.Timer(0.2, lambda: open(path, "a").write(
        json.dumps({"type": "step", "step": 1}) + "\n"))
    t.start()
    try:
        c.poll_once()
    finally:
        t.cancel()
    assert c.profiles_captured == 1
    done = kv.get_json("profile/done/0")
    assert done["id"] == req_id and done["steps"] >= 1
    assert kv.get_json("profile/req") is None          # consumed
    with open(os.path.join(logdir, "step_hlo.txt")) as f:
        assert "HloModule" in f.read()
    events = [r for r in _read_jsonl(path)
              if r.get("event") == "profile_captured"]
    assert len(events) == 1 and events[0]["rank"] == 0
    assert events[0]["hlo"] is True
    telemetry.validate_record(events[0])
    c.poll_once()                            # no re-trigger: req gone
    assert c.profiles_captured == 1


def test_profile_request_ignored_for_other_rank(tmp_path):
    kv = distributed.FileKV(str(tmp_path / "kv"))
    c = HostCollector(path=None, kv=kv, rank=0, world=2)
    request_profile(kv, 1, steps=1)
    c.poll_once()
    assert c.profiles_captured == 0
    assert kv.get_json("profile/req")["rank"] == 1     # left for rank 1


# -- S5: exporter scrape + fleet_report CLI ------------------------------------

_METRIC_LINE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z0-9_]+="[^"]*"'
    r'(,[a-zA-Z0-9_]+="[^"]*")*\})? [-+]?[0-9.eE+-]+$')
_META_LINE = re.compile(r"^# (TYPE [a-zA-Z_:][a-zA-Z0-9_:]* "
                        r"(counter|gauge)|HELP .+)$")


def test_exporter_scrapes_prometheus_text(tmp_path):
    telemetry.REGISTRY.counter("collective.bytes").inc(4096)
    telemetry.REGISTRY.gauge("input.queue_depth").set(3)
    h = telemetry.REGISTRY.histogram("serve.queue_us")
    for v in (10.0, 30.0):
        h.observe(v)
    kv = distributed.FileKV(str(tmp_path / "kv"))
    kv.put_json("obs/rollup/0", {
        "rank": 0, "world": 2, "t": time.time(), "run": "r",
        "steps_total": 10, "steps_window": 10, "skipped_total": 0,
        "last_step": 9, "interval_us_mean": 1000.0,
        "wall_us_mean": 900.0, "mfu_mean": 0.25, "shares": {},
        "requests_total": 0, "request_queue_us_mean": None,
        "events": []})
    exporter = MetricsExporter(port=0,
                               fleet=FleetView(kv))  # ephemeral port
    try:
        url = f"http://127.0.0.1:{exporter.port}/metrics"
        with urllib.request.urlopen(url, timeout=10) as resp:
            assert resp.status == 200
            assert resp.headers["Content-Type"].startswith("text/plain")
            body = resp.read().decode()
        lines = [ln for ln in body.splitlines() if ln]
        assert lines, body
        for ln in lines:                     # the full line grammar
            assert _METRIC_LINE.match(ln) or _META_LINE.match(ln), ln
        assert "mxtpu_collective_bytes 4096" in body
        assert "# TYPE mxtpu_collective_bytes counter" in body
        assert "mxtpu_input_queue_depth 3" in body
        assert "mxtpu_serve_queue_us_count 2" in body
        assert "mxtpu_serve_queue_us_sum 40" in body
        assert "mxtpu_fleet_mfu 0.25" in body
        assert 'mxtpu_fleet_rank_interval_us{rank="0"} 1000' in body
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(
                f"http://127.0.0.1:{exporter.port}/other", timeout=10)
        assert exc.value.code == 404
    finally:
        exporter.close()


def test_ensure_from_env_bootstrap(tmp_path, monkeypatch):
    # no env: a no-op
    assert obs.ensure_from_env() == (None, None)
    obs.shutdown()
    path = str(tmp_path / "t.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", path)
    monkeypatch.setenv("MXTPU_METRICS_PORT", "0")
    telemetry.reset()
    collector, exporter = obs.ensure_from_env()
    try:
        assert collector is not None and exporter is not None
        # idempotent: the Trainer may construct many times
        assert obs.ensure_from_env() == (collector, exporter)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{exporter.port}/metrics",
                timeout=10) as resp:
            assert resp.status == 200
    finally:
        obs.shutdown()


def _valid_rank_log(tmp_path, monkeypatch, rank, name=None,
                    interval_s=0.001, events=(), requests=0):
    """Write a fully schema-valid per-rank JSONL through the real
    telemetry pipeline."""
    path = str(tmp_path / (name or f"rank{rank}.jsonl"))
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", path)
    monkeypatch.setenv("MXTPU_PEAK_FLOPS", "1e12")
    telemetry.reset()
    telemetry.set_identity(rank=rank, world=3)
    for i in range(4):
        acc = telemetry.step_begin(path="captured")
        time.sleep(interval_s)
        telemetry.on_scope("captured_step", interval_s)
        telemetry.note(flops=1e9)
        telemetry.step_end(acc, step=i)
    for e in events:
        telemetry.event(e.pop("event"), **e)
    for _ in range(requests):
        tr = Trace()
        tr.begin("frontdoor", t0=time.time()).close(dur_us=500.0)
        tr.begin("batcher", parent=tr.root(), t0=time.time(),
                 replica_id=0).close(dur_us=400.0)
        telemetry.request_record(
            queue_us=100.0, prefill_us=200.0, decode_us_per_token=50.0,
            bucket=[1, 8], padded_fraction=0.0, new_tokens=4,
            generation=0, replica_id=0, **tr.to_fields())
    telemetry.reset()
    return path


def test_fleet_report_cli_on_three_rank_logs(tmp_path, monkeypatch):
    logdir = tmp_path / "logs"
    logdir.mkdir()
    _valid_rank_log(logdir, monkeypatch, 0, events=[
        {"event": "straggler_suspected", "rank": 1, "step": 3,
         "mean_collective_share": 0.8},
        {"event": "mesh_reshape", "epoch": 1, "world": 3,
         "members": [0, 1, 2]}])
    _valid_rank_log(logdir, monkeypatch, 1, interval_s=0.004)
    _valid_rank_log(logdir, monkeypatch, 2, requests=2)
    monkeypatch.delenv("MXTPU_TELEMETRY_PATH")
    proc = subprocess.run(
        [sys.executable, _FLEET_REPORT, str(logdir), "--validate"],
        env=cpu_child_env(), capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr + proc.stdout
    out = proc.stdout
    assert "validate (schema + span completeness)" in out
    assert "fleet: 3 rank(s), world 3" in out
    assert "fleet mfu (step-weighted):" in out
    assert "straggler: rank 1 suspected" in out
    assert "mesh_reshape" in out
    assert "frontdoor" in out and "batcher" in out
    assert re.search(r"step-time skew: \d+\.\d+x \(slowest rank 1",
                     out)


def test_fleet_report_validate_catches_broken_spans(tmp_path):
    path = str(tmp_path / "bad.jsonl")
    rec = {"type": "request", "v": 3, "run": "r", "t": 1.0,
           "queue_us": 1.0, "prefill_us": 2.0,
           "decode_us_per_token": 3.0, "bucket": [1, 8],
           "padded_fraction": 0.0, "trace_id": "t1",
           "spans": [{"span_id": "a", "parent": "missing",
                      "name": "batcher", "t0": 1.0, "dur_us": None}]}
    with open(path, "w") as f:
        f.write(json.dumps(rec) + "\n")
    proc = subprocess.run(
        [sys.executable, _FLEET_REPORT, path, "--validate"],
        env=cpu_child_env(), capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    assert "violation" in proc.stderr


# -- the acceptance run: 3-rank elastic fleet + serving, one report ------------

@pytest.mark.slow
def test_fleet_observability_end_to_end(tmp_path, monkeypatch):
    """ISSUE 14 acceptance: a 3-rank elastic run with an injected
    slow_rank and a mid-run silent death, plus a 2-replica serving run,
    merge through tools/fleet_report.py into ONE fleet view: fleet MFU,
    the slow rank named with its stall share, the reshape timeline, and
    a complete FrontDoor→batcher→prefill/decode span tree."""
    work = tmp_path / "fleet"
    work.mkdir()
    gang_dir = work / "kv"
    gang_dir.mkdir()
    num_steps = 18
    base = dict(
        MXTPU_NUM_WORKERS="3",
        MXTPU_GANG_DIR=str(gang_dir),
        MXTPU_HEARTBEAT_INTERVAL="0.05",
        MXTPU_HEARTBEAT_TIMEOUT="1.5",
        MXTPU_STRAGGLER_WINDOW="4",
        MXTPU_STRAGGLER_SHARE="0.3",
        MXTPU_PEAK_FLOPS="1e12",
        MXTPU_OBS_ROLLUP_SECS="0.15",
        PYTHONUNBUFFERED="1",
    )
    per_rank = {
        0: {},
        1: {"MXTPU_FAULT_INJECT": "slow_rank:1",
            "MXTPU_SLOW_RANK_SECS": "0.25"},
        2: {"MXTPU_OBS_EXIT_RANK": "2", "MXTPU_OBS_EXIT_STEP": "12"},
    }
    procs = {}
    for rank in (0, 1, 2):
        env = cpu_child_env(**base, **per_rank[rank],
                            MXTPU_WORKER_RANK=str(rank),
                            MXTPU_TELEMETRY_PATH=str(
                                work / f"rank{rank}.jsonl"))
        procs[rank] = subprocess.Popen(
            [sys.executable, _OBS_WORKER, str(work), str(num_steps),
             "20"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
    outs = {r: p.communicate(timeout=300) for r, p in procs.items()}
    results = {}
    for rank in (0, 1):
        stdout, stderr = outs[rank]
        assert procs[rank].returncode == 0, (rank, stdout, stderr)
        for line in stdout.splitlines():
            if line.startswith("RESULT "):
                results[rank] = json.loads(line[len("RESULT "):])
    assert set(results) == {0, 1}
    for rank, res in results.items():
        assert res["final_step"] == num_steps
        assert res["members"] == [0, 1]      # rank 2's death adopted
        assert res["reshapes"] >= 1

    # serving half: two replicas behind one FrontDoor, real span path
    serve_log = str(work / "serving.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", serve_log)
    telemetry.reset()
    from mxnet_tpu import serving

    replicas = [serving.ReplicaServer(_FakeEngine(), rank=r)
                for r in (0, 1)]
    door = serving.FrontDoor(replicas)
    try:
        futs = [door.submit(f"prompt {i}", max_new_tokens=4)
                for i in range(4)]
        for f in futs:
            f.result(timeout=60)
    finally:
        door.close()
    monkeypatch.delenv("MXTPU_TELEMETRY_PATH")
    telemetry.reset()

    proc = subprocess.run(
        [sys.executable, _FLEET_REPORT, str(work), "--validate"],
        env=cpu_child_env(), capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr + proc.stdout
    out = proc.stdout
    assert "fleet mfu (step-weighted):" in out
    # the injected slow rank is named, with its own stall attribution
    assert re.search(r"straggler: rank 1 suspected.*its own time:",
                     out, re.S)
    # the reshape after rank 2's silent death is on the timeline
    assert "mesh_reshape" in out and "rank_dead" in out
    # at least one request renders as a complete causal tree
    assert "frontdoor" in out and "prefill" in out and "decode" in out
