"""Self-tuning performance layer (mxnet_tpu/autotune/).

The acceptance loop, end to end: ``MXTPU_AUTOTUNE=search`` finds a
config no slower than the defaults within ``MXTPU_TUNE_BUDGET`` trials
(OOM candidates score infeasible, never crash) and persists it to the
CRC'd tuning DB; a second run in ``replay`` mode starts at the tuned
point with ZERO trials (``tune_db_hit`` event) and a loss trajectory
bitwise-identical to defaults — every searchable knob is
numerics-preserving, including all MXTPU_REMAT policies over the
captured step.  Plus: corrupt-DB fallback (``corrupt_tune_db`` fault),
telemetry schema v2, the trace_report autotune section, and
MXTPU_GROUP_MAX_ITEMS bitwise group splitting.
"""

import math
import os
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, telemetry
from mxnet_tpu.autotune import db, runner, search, space
from mxnet_tpu.gluon import nn
from mxnet_tpu.optimizer import grouped

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_TRACE_REPORT = os.path.join(_REPO, "tools", "trace_report.py")

#: every env var the tuner may set (apply_config writes os.environ
#: directly, outside monkeypatch's view) plus the driver's own knobs.
_TUNE_ENVS = [k.env for k in space.KNOBS.values()] + [
    "MXTPU_AUTOTUNE", "MXTPU_TUNE_DB", "MXTPU_TUNE_BUDGET",
    "MXTPU_TUNE_STEPS", "MXTPU_TUNE_SEMANTICS", "MXTPU_FAULT_INJECT",
]


@pytest.fixture(autouse=True)
def _tune_clean():
    """apply_config / the search mutate os.environ directly; scrub the
    whole tuner env and the telemetry trial state around every test."""
    saved = {e: os.environ.pop(e, None) for e in _TUNE_ENVS}
    telemetry.reset()
    yield
    for e in _TUNE_ENVS:
        os.environ.pop(e, None)
    for e, v in saved.items():
        if v is not None:
            os.environ[e] = v
    telemetry.reset()


def _make_net(seed=7):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"))
        net.add(nn.Dense(3))
    net.initialize(init=mx.init.Xavier())
    net.hybridize()
    return net


def _train(steps=4, seed=7, opt="adam"):
    """Fresh net + trainer, `steps` train_step calls; returns (losses,
    weights) as numpy."""
    net = _make_net(seed=seed)
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    loss_fn.hybridize()
    trainer = gluon.Trainer(net.collect_params(), opt,
                            {"learning_rate": 0.01})
    rs = np.random.RandomState(42)
    xs = [rs.normal(size=(8, 6)).astype(np.float32) for _ in range(steps)]
    ys = [rs.randint(0, 3, size=(8,)).astype(np.float32)
          for _ in range(steps)]
    losses = [trainer.train_step(net, loss_fn, mx.nd.array(xs[s]),
                                 mx.nd.array(ys[s])).asnumpy()
              for s in range(steps)]
    weights = [p.data().asnumpy() for p in trainer._params]
    return losses, weights


# -- knob space ----------------------------------------------------------------

def test_knob_space_declaration():
    """Every knob: default in domain, env-backed `current`, adjacent
    `neighbors`, out-of-domain `validate` raises."""
    for knob in space.KNOBS.values():
        assert knob.default in knob.domain
        assert knob.current() == knob.default    # env scrubbed by fixture
        for v in knob.domain:
            nbrs = knob.neighbors(v)
            assert nbrs and all(n in knob.domain for n in nbrs)
            assert v not in nbrs
        with pytest.raises(mx.MXNetError):
            knob.validate("definitely-not-a-value")
    # fingerprint is order-independent and value-sensitive
    cfg = space.default_config()
    assert space.fingerprint(dict(reversed(list(cfg.items())))) \
        == space.fingerprint(cfg)
    cfg["remat"] = "dots"
    assert space.fingerprint(cfg) != space.fingerprint(
        space.default_config())


def test_semantics_changing_knobs_gated(monkeypatch):
    """grad_accum is searched and applied ONLY behind
    MXTPU_TUNE_SEMANTICS=1 — not even a stored DB entry applies it
    silently."""
    names = [k.name for k in space.searchable_knobs()]
    assert "grad_accum" not in names
    prev = space.apply_config({**space.default_config(),
                               "grad_accum": "4"})
    assert os.environ.get("MXTPU_GRAD_ACCUM") is None
    space.restore_env(prev)

    monkeypatch.setenv("MXTPU_TUNE_SEMANTICS", "1")
    assert "grad_accum" in [k.name for k in space.searchable_knobs()]
    prev = space.apply_config({**space.default_config(),
                               "grad_accum": "4"})
    assert os.environ.get("MXTPU_GRAD_ACCUM") == "4"
    space.restore_env(prev)
    assert os.environ.get("MXTPU_GRAD_ACCUM") is None


def test_mode_parsing(monkeypatch):
    assert search.mode() == "replay"            # the default
    for raw, want in (("off", "off"), ("0", "off"), ("false", "off"),
                      ("replay", "replay"), ("SEARCH", "search")):
        monkeypatch.setenv("MXTPU_AUTOTUNE", raw)
        assert search.mode() == want
    monkeypatch.setenv("MXTPU_AUTOTUNE", "bogus")
    with pytest.raises(mx.MXNetError):
        search.mode()


# -- trial runner --------------------------------------------------------------

def test_oom_trial_is_infeasible_not_a_crash(fault_inject):
    """tune_oom fault (hermetic RESOURCE_EXHAUSTED): the trial returns
    an infeasible result, emits tune_infeasible, and restores the
    env."""
    fault_inject("tune_oom:1")
    cfg = dict(space.default_config(), remat="dots")
    res = runner.run_trial(lambda: None, cfg, steps=2)
    assert not res.feasible
    assert res.score_us == math.inf
    assert "RESOURCE_EXHAUSTED" in res.error
    assert telemetry.event_counts().get("tune_infeasible") == 1
    assert os.environ.get("MXTPU_REMAT") is None   # trial env undone


def test_search_survives_oom_candidate(fault_inject):
    """One OOM candidate mid-search: the winner is still a feasible
    config and the infeasible one is never kept in the pool."""
    fault_inject("tune_oom:1")                  # first trial (= base) OOMs
    winner, results = search.successive_halving(
        lambda: None, total_budget=4, rung_steps=1)
    assert winner.feasible
    assert sum(1 for r in results if not r.feasible) == 1
    assert telemetry.event_counts().get("tune_infeasible") == 1
    assert telemetry.event_counts().get("tune_search_start") == 1


def test_search_budget_respected(monkeypatch):
    monkeypatch.setenv("MXTPU_TUNE_BUDGET", "3")
    _, results = search.successive_halving(lambda: None, rung_steps=1)
    assert len(results) == 3


# -- tuning DB -----------------------------------------------------------------

def test_db_roundtrip_and_key(tmp_path, monkeypatch):
    path = str(tmp_path / "tune_db.jsonl")
    monkeypatch.setenv("MXTPU_TUNE_DB", path)
    key = db.entry_key("abcd1234", "cpu", (("data", 8),))
    assert key == "abcd1234|cpu|data=8"
    assert db.entry_key("abcd1234", "cpu", None).endswith("|single")
    cfg = space.default_config()
    entry = db.record(key, cfg, 123.4, mfu=0.1, trials=5,
                      default_score_us=150.0)
    got = db.lookup(key)
    assert got == entry
    assert got["config"] == cfg
    assert got["fingerprint"] == space.fingerprint(cfg)
    assert got["db_version"] == db.DB_VERSION
    # later write for the same key wins
    db.record(key, dict(cfg, remat="dots"), 99.0)
    assert db.lookup(key)["config"]["remat"] == "dots"
    assert telemetry.event_counts().get("tune_db_write") == 2


def test_db_path_is_its_own_variable(tmp_path, monkeypatch):
    """The tuning DB is placed by MXTPU_TUNE_DB alone: the compile
    cache's location (JAX_COMPILATION_CACHE_DIR) does not move it."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert db.tune_db_path() is None            # no persistence configured
    monkeypatch.setenv("MXTPU_TUNE_DB", str(tmp_path / "elsewhere.jsonl"))
    assert db.tune_db_path() == str(tmp_path / "elsewhere.jsonl")


@pytest.mark.faults
def test_corrupt_db_falls_back_and_gcs(fault_inject, tmp_path,
                                       monkeypatch):
    """A corrupt entry (injected bit-rot via corrupt_tune_db) reads as
    absent with a tune_db_fallback event — never a crash — and the next
    write GCs it along with stale-version entries."""
    path = str(tmp_path / "tune_db.jsonl")
    monkeypatch.setenv("MXTPU_TUNE_DB", path)
    cfg = space.default_config()
    fault_inject("corrupt_tune_db:1")
    db.record("k1|cpu|single", cfg, 123.0)      # line lands corrupted
    assert db.lookup("k1|cpu|single") is None   # CRC catches it
    counts = telemetry.event_counts()
    assert counts.get("tune_db_fallback", 0) >= 1
    # a stale-schema entry (valid CRC, old db_version) is also skipped
    stale = {"db_version": db.DB_VERSION - 1, "key": "old",
             "config": cfg, "fingerprint": "x", "score_us": 1.0, "t": 0}
    with open(path, "a", encoding="utf-8") as f:
        f.write(db._encode(stale))
    assert db.lookup("old") is None
    # the next clean write GCs both: only the new entry survives, and
    # loading the rewritten file emits no further fallback
    db.record("k2|cpu|single", cfg, 50.0)
    before = telemetry.event_counts().get("tune_db_fallback", 0)
    entries = db.load(path)
    assert set(entries) == {"k2|cpu|single"}
    assert telemetry.event_counts().get("tune_db_fallback", 0) == before


def test_torn_tail_is_skipped(tmp_path, monkeypatch):
    """A half-written last line (crash mid-append) must not poison the
    file."""
    path = str(tmp_path / "tune_db.jsonl")
    monkeypatch.setenv("MXTPU_TUNE_DB", path)
    db.record("good|cpu|single", space.default_config(), 10.0)
    with open(path, "a", encoding="utf-8") as f:
        f.write('{"crc": 123, "payl')            # torn
    entries = db.load(path)
    assert set(entries) == {"good|cpu|single"}


# -- telemetry schema v2 -------------------------------------------------------

def test_telemetry_trial_marking_and_v2_schema():
    acc = telemetry.step_begin(path="manual")
    telemetry.step_end(acc, step=0)
    telemetry.trial_begin("abc123def456")
    acc = telemetry.step_begin(path="manual")
    telemetry.step_end(acc, step=1)
    telemetry.trial_end()
    telemetry.set_config_fingerprint("feedc0ffee12")
    acc = telemetry.step_begin(path="manual")
    telemetry.step_end(acc, step=2)

    every = telemetry.recent_steps(include_trials=True)
    steady = telemetry.recent_steps()
    assert len(every) == 3
    assert [r["step"] for r in steady] == [0, 2]   # trial excluded
    assert every[1]["tuning_trial"] is True
    assert every[1]["config_fingerprint"] == "abc123def456"
    assert "tuning_trial" not in every[0]
    assert every[2]["config_fingerprint"] == "feedc0ffee12"
    for rec in every:
        assert rec["v"] == telemetry.SCHEMA_VERSION == 8   # v8: fencing
        telemetry.validate_record(rec)
    v1 = dict(every[0])
    v1["v"] = 1                                  # v1 records stay valid
    telemetry.validate_record(v1)
    bad = dict(every[1])
    bad["tuning_trial"] = "yes"
    with pytest.raises(ValueError):
        telemetry.validate_record(bad)
    bad = dict(every[1])
    bad["config_fingerprint"] = ""
    with pytest.raises(ValueError):
        telemetry.validate_record(bad)


# -- the acceptance loop: search -> persist -> replay --------------------------

def test_search_persist_replay_end_to_end(tmp_path):
    """search mode finds a winner within budget and persists it; a
    fresh replay run applies it with ZERO trials (tune_db_hit) and a
    loss trajectory bitwise-identical to MXTPU_AUTOTUNE=off — every
    searchable knob is numerics-preserving."""
    path = str(tmp_path / "tune_db.jsonl")
    os.environ["MXTPU_TUNE_DB"] = path
    os.environ["MXTPU_TUNE_STEPS"] = "1"
    os.environ["MXTPU_TUNE_BUDGET"] = "5"

    # 1) search: trials run on the live trainer, winner persisted
    os.environ["MXTPU_AUTOTUNE"] = "search"
    telemetry.reset()
    _train()
    counts = telemetry.event_counts()
    assert counts.get("tune_search_start") == 1
    assert counts.get("tune_trial") == 5         # the whole budget
    assert counts.get("tune_winner") == 1
    assert counts.get("tune_db_write") == 1
    (entry,) = db.load(path).values()
    assert entry["score_us"] < math.inf
    assert entry["trials"] == 5
    # the measured winner is never slower than the measured defaults
    if entry.get("default_score_us") is not None:
        assert entry["score_us"] <= entry["default_score_us"]
    # trial steps are marked: steady-state view saw only the 4 real steps
    assert len(telemetry.recent_steps()) == 4
    for k in space.KNOBS.values():               # winner's env, scrubbed
        os.environ.pop(k.env, None)

    # 2) baseline at defaults (fresh net, same seed), tuner off
    os.environ["MXTPU_AUTOTUNE"] = "off"
    telemetry.reset()
    losses_off, weights_off = _train()
    # no `tune_*` event of any kind (a step that compiles is a
    # `compile` event of its own: engine.watch_compiles)
    assert not [k for k in telemetry.event_counts() if k.startswith("tune_")]

    # 3) replay: fresh net, same seed — DB hit, zero trials, bitwise
    os.environ["MXTPU_AUTOTUNE"] = "replay"
    telemetry.reset()
    losses_rep, weights_rep = _train()
    counts = telemetry.event_counts()
    assert counts.get("tune_db_hit") == 1
    assert "tune_trial" not in counts            # ZERO trials on restart
    assert "tune_search_start" not in counts
    assert not [r for r in telemetry.recent_steps(include_trials=True)
                if r.get("tuning_trial")]
    for s, (a, b) in enumerate(zip(losses_rep, losses_off)):
        np.testing.assert_array_equal(a, b, err_msg=f"loss step {s}")
    for i, (a, b) in enumerate(zip(weights_rep, weights_off)):
        np.testing.assert_array_equal(a, b, err_msg=f"weight {i}")
    # steady-state records carry the tuned config's fingerprint
    fps = {r.get("config_fingerprint")
           for r in telemetry.recent_steps()}
    assert fps == {entry["fingerprint"]}


def test_replay_is_noop_without_db():
    """Default mode (replay) with no DB configured: no events, no
    trials, just training."""
    telemetry.reset()
    losses_a, _ = _train()
    assert not [k for k in telemetry.event_counts() if k.startswith("tune_")]
    os.environ["MXTPU_AUTOTUNE"] = "off"
    telemetry.reset()
    losses_b, _ = _train()
    for a, b in zip(losses_a, losses_b):
        np.testing.assert_array_equal(a, b)


# -- remat policy registry (bitwise over the captured step) --------------------

def _train_scan_encoder(policy, steps=3):
    """Captured ScanTransformerEncoder training under one MXTPU_REMAT
    policy; returns (losses, weights, peak_bytes, captured?)."""
    from mxnet_tpu.gluon.model_zoo import bert as bz

    if policy:
        os.environ["MXTPU_REMAT"] = policy
    else:
        os.environ.pop("MXTPU_REMAT", None)
    os.environ["MXTPU_AUTOTUNE"] = "off"
    mx.random.seed(11)
    net = bz.ScanTransformerEncoder(4, 32, 4, dropout=0.0)
    net.initialize(init=mx.init.Xavier())
    net.hybridize()
    loss_fn = gluon.loss.L2Loss()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-3})
    rs = np.random.RandomState(0)
    x = mx.nd.array(rs.randn(4, 8, 32).astype("float32"))
    y = mx.nd.array(rs.randn(4, 8, 32).astype("float32"))
    losses = [trainer.train_step(net, loss_fn, x, y).asnumpy()
              for _ in range(steps)]
    cache = getattr(trainer, "_captured_cache", {})
    step = next(iter(cache.values())) if cache else None
    peak = step.memory_high_water() if step is not None else None
    # key by counter-stripped name: each net instantiation bumps the
    # gluon auto-name counter ("scantransformerencoder9_..." vs "...10_")
    import re

    weights = {re.sub(r"\d+", "", n): p.data().asnumpy()
               for n, p in net.collect_params().items()}
    return losses, weights, peak, step is not None


def test_remat_registry_parsing():
    from mxnet_tpu import remat

    assert set(remat.names()) >= {"full", "dots", "dots_no_batch"}
    assert remat.canonical(True) == "full"
    assert remat.canonical("all") == "full"
    assert remat.canonical("none") is None
    assert remat.canonical(None) is None
    assert remat.parse_save_every("save_every_k:2") == 2
    assert remat.parse_save_every("dots") is None
    with pytest.raises(mx.MXNetError):
        remat.canonical("bogus_policy")
    with pytest.raises(mx.MXNetError):
        remat.parse_save_every("save_every_k:0")


def test_remat_env_precedence(monkeypatch):
    from mxnet_tpu import remat

    assert remat.env_default(None) is None
    monkeypatch.setenv("MXNET_BACKWARD_DO_MIRROR", "1")
    assert remat.env_default(None) == "full"     # reference-compat alias
    monkeypatch.setenv("MXTPU_REMAT", "dots")
    assert remat.env_default(None) == "dots"     # MXTPU_REMAT wins
    assert remat.env_default("save_every_k:2") == "save_every_k:2"


@pytest.mark.parametrize("policy", ["none", "full", "dots",
                                    "dots_no_batch", "save_every_k:2"])
def test_remat_policy_bitwise_parity(policy):
    """Every named policy over the captured ScanTransformerEncoder step
    is a pure recompute schedule: losses AND weights bitwise-identical
    to the unremat'd capture."""
    base_l, base_w, _, base_cap = _train_scan_encoder(None)
    assert base_cap, "baseline must take the captured path"
    l, w, _, cap = _train_scan_encoder(policy)
    assert cap, f"policy {policy} must stay capture-eligible"
    for s, (a, b) in enumerate(zip(l, base_l)):
        np.testing.assert_array_equal(a, b,
                                      err_msg=f"{policy} loss step {s}")
    assert set(w) == set(base_w)
    for n in w:
        np.testing.assert_array_equal(w[n], base_w[n],
                                      err_msg=f"{policy} weight {n}")


def test_remat_save_every_k_lowers_high_water():
    """The measured activation-memory delta: chunked trunk remat
    (save_every_k:2 over the L=4 scanned stack) must lower the step
    program's high-water mark below the unremat'd capture."""
    _, _, peak_none, _ = _train_scan_encoder(None)
    _, _, peak_k2, _ = _train_scan_encoder("save_every_k:2")
    assert peak_none is not None and peak_k2 is not None, \
        "memory_analysis unavailable on this jax build"
    assert peak_k2 < peak_none, (peak_k2, peak_none)


# -- optimizer group splitting (MXTPU_GROUP_MAX_ITEMS) -------------------------

def test_group_max_items_split_is_bitwise(monkeypatch):
    """Capping fused-group size re-plans into more groups (one eager
    dispatch per chunk) without changing a single bit of the update
    math, on both the eager and captured paths."""
    os.environ["MXTPU_AUTOTUNE"] = "off"
    # eager: 4 adam params = 1 fused dispatch/step; cap 1 -> 4
    monkeypatch.setenv("MXTPU_CAPTURED_STEP", "0")
    grouped.reset_dispatch_count()
    base_l, base_w = _train(steps=2)
    assert grouped.dispatch_count() == 2         # one group, two steps
    monkeypatch.setenv("MXTPU_GROUP_MAX_ITEMS", "1")
    grouped.reset_dispatch_count()
    split_l, split_w = _train(steps=2)
    assert grouped.dispatch_count() == 8         # four chunks, two steps
    for a, b in zip(split_l, base_l):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(split_w, base_w):
        np.testing.assert_array_equal(a, b)
    # captured path under the same cap stays bitwise too
    monkeypatch.setenv("MXTPU_CAPTURED_STEP", "1")
    cap_l, cap_w = _train(steps=2)
    for a, b in zip(cap_l, base_l):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(cap_w, base_w):
        np.testing.assert_array_equal(a, b)


# -- trace_report autotune section ---------------------------------------------

def test_trace_report_autotune_section(tmp_path, monkeypatch):
    """A tuning run's event log flows through the trace_report CLI: the
    autotune section shows trials, the winner + improvement, and DB
    activity; trial steps are split out of the steady-state header."""
    path = str(tmp_path / "ev.jsonl")
    monkeypatch.setenv("MXTPU_TELEMETRY_PATH", path)
    monkeypatch.setenv("MXTPU_TUNE_DB", str(tmp_path / "db.jsonl"))
    telemetry.reset()
    telemetry.event("tune_search_start", candidates=3, budget=4)
    telemetry.trial_begin("aaaabbbbcccc")
    acc = telemetry.step_begin(path="manual")
    telemetry.step_end(acc, step=0)
    telemetry.trial_end()
    telemetry.event("tune_trial", fingerprint="aaaabbbbcccc", steps=1,
                    score_us=120.0)
    telemetry.event("tune_infeasible", fingerprint="ddddeeeeffff",
                    error="RESOURCE_EXHAUSTED: injected")
    db.record("sig|cpu|single", space.default_config(), 100.0,
              default_score_us=120.0)
    telemetry.event("tune_winner", key="sig|cpu|single",
                    fingerprint="aaaabbbbcccc", score_us=100.0,
                    default_score_us=120.0, improvement=1.2, trials=4)
    telemetry.event("tune_db_hit", key="sig|cpu|single",
                    fingerprint="aaaabbbbcccc", score_us=100.0)
    acc = telemetry.step_begin(path="manual")
    telemetry.step_end(acc, step=1)
    telemetry.reset()                            # close the sink

    env = dict(os.environ)
    env.pop("MXTPU_TELEMETRY_PATH", None)
    proc = subprocess.run(
        [sys.executable, _TRACE_REPORT, path, "--validate"],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    out = proc.stdout
    assert "1 step records (+1 tuning trials)" in out
    assert "autotune:" in out
    assert "trials: 1 scored, 1 infeasible (OOM)" in out
    assert "winner: aaaabbbbcccc at 100.0 us/step" in out
    assert "1.200x vs default 120.0 us" in out
    assert "db hits (replayed with zero trials): 1" in out
    assert "db writes: 1" in out
