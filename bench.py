#!/usr/bin/env python
"""Benchmark entry point (driver contract: prints ONE JSON line).

Headline metric: ResNet-50 training throughput in samples/sec/chip
(BASELINE.md metric #1).  The same JSON line also carries BERT-base
pretraining tokens/sec/chip (BASELINE.md metric #2) measured by a second
worker, plus an in-run matmul calibration so every number can be sanity
checked against what the chip actually sustains.

Round-3 honesty hardening: round 2's bench timed dispatch, not compute,
and recorded an impossible 429% MFU (on the backend of that round
``jax.block_until_ready()`` did not synchronize before the first host
readback; chip_smoke.py's sync phase re-checks that on the installed
runtime, see docs/perf.md).  Every timing loop here therefore:

  1. ends warmup with a forced host readback (``np.asarray``), and
  2. ends the timed region with a forced host readback of the last
     output, and
  3. passes through a sanity gate: if the implied MFU exceeds
     ``_MFU_GATE`` the measurement is discarded and re-taken with a
     readback after EVERY step (strictly correct, slightly pessimistic);
     an impossible number is never printed.

The orchestrator process never imports jax; workers run in subprocesses
with time budgets and a persistent XLA compile cache (.jax_cache/).

vs_baseline is null: BASELINE.json.published is {} (reference mount was
empty — see BASELINE.md provenance note).
"""

import json
import os
import subprocess
import sys
import time

_HOSTILE_ENV_PREFIXES = ("TPU_", "LIBTPU")

# bf16 peak FLOP/s per chip by device kind substring (public specs)
_PEAK_FLOPS = [
    ("v6e", 918e12), ("v6", 918e12),
    ("v5p", 459e12), ("v5e", 197e12), ("v5 lite", 197e12),
    ("v4", 275e12), ("v3", 123e12), ("v2", 45e12),
]

# ResNet-50 @224: ~4.09e9 MACs fwd => 8.2e9 FLOPs; training ~= 3x fwd
_RESNET50_TRAIN_FLOPS_224 = 3.0 * 2 * 4.089e9

# Measurements above this implied MFU are discarded and re-taken with a
# readback per step.  0.95 not 1.0: anything near peak on a full training
# step is itself evidence of a sync bug.
_MFU_GATE = 0.95


def _load_resilience():
    """Load mxnet_tpu/resilience.py WITHOUT importing the package — the
    orchestrator must stay jax-free (module contract above)."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "mxnet_tpu", "resilience.py")
    spec = importlib.util.spec_from_file_location("_mxtpu_resilience",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _probe_backend():
    """Cheap backend-liveness probe.

    A backend that cannot be reached can hang ``jax.devices()``; burning
    the full worker budgets on it is how round 3 ended as ``rc: 124``
    with no JSON at all.  A resilience.Watchdog supervises the probe
    subprocess (round 5's ad-hoc 90s timeout, structured): on expiry it
    dumps the orchestrator's thread stacks to stderr, kills the wedged
    child, and the JSON line carries a structured ``tpu_probe`` error
    instead of a bare timeout string.

    The trajectory has been refused-CPU since r03 on exactly this
    timeout, so the policy is now tunable and self-healing: the budget
    comes from ``MXTPU_PROBE_TIMEOUT`` (legacy ``BENCH_PROBE_TIMEOUT``
    still honored), a wedged first probe gets ONE decorrelated-jitter
    retry via ``resilience.retry_call`` (a killed probe sometimes
    frees the device for the second), and the returned
    record carries the probe ``rc`` + stderr tail so the
    ``on_chip_unavailable`` trajectory point tells the next on-chip
    session exactly what the chip said.
    """
    timeout = int(os.environ.get(
        "MXTPU_PROBE_TIMEOUT", os.environ.get("BENCH_PROBE_TIMEOUT", 90)))
    code = ("import jax, json; d = jax.devices(); "
            "print(json.dumps({'platform': d[0].platform, "
            "'kind': getattr(d[0], 'device_kind', '')}))")
    resilience = _load_resilience()

    def attempt():
        proc = subprocess.Popen([sys.executable, "-c", code],
                                stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, text=True)
        wd = resilience.Watchdog(timeout, name="tpu_probe", action="none",
                                 on_expire=proc.kill)
        with wd:
            out, err = proc.communicate()
        tail = (err or "").strip()[-200:]
        if wd.expired:
            raise TimeoutError(
                f"tpu_probe watchdog expired after {timeout}s "
                f"(backend wedged?); probe killed, thread stacks "
                f"dumped to stderr")
        if proc.returncode != 0:
            return {"ok": False, "rc": proc.returncode,
                    "stderr_tail": tail,
                    "reason": f"probe rc={proc.returncode}: {tail}"}
        for ln in reversed(out.strip().splitlines()):
            try:
                obj = json.loads(ln)
            except (ValueError, TypeError):
                continue
            if isinstance(obj, dict) and "platform" in obj:
                obj["ok"] = obj["platform"] != "cpu"
                obj["rc"] = 0
                obj["stderr_tail"] = tail
                if not obj["ok"]:
                    obj["reason"] = "probe saw CPU only"
                return obj
        return {"ok": False, "rc": 0, "stderr_tail": tail,
                "reason": "probe produced no parseable output"}

    try:
        return resilience.retry_call(
            attempt, retries=1, backoff=2.0, max_backoff=8.0,
            jitter=True, retryable=(TimeoutError,),
            description="tpu_probe")
    except TimeoutError as exc:
        return {"ok": False, "rc": None, "stderr_tail": "",
                "reason": f"{exc} (after 1 retry, "
                          f"MXTPU_PROBE_TIMEOUT={timeout})"}


def _attempts(tpu_ok):
    steps = int(os.environ.get("BENCH_STEPS", 20))
    # 1000s not 560s: a COLD compile of the b256 step was measured >560s
    # in round 5 — one generously-budgeted attempt beats two that both
    # die mid-compile (each kill also risks leaving the chip claimed).
    # A warm compile cache makes the attempt finish in ~2 min regardless
    # of this budget.
    budget = int(os.environ.get("BENCH_BUDGET", 1000))
    layout = os.environ.get("BENCH_LAYOUT", "NCHW")
    tpu_attempts = [] if not tpu_ok else [
        (None, {"model": "resnet50",
                "batch": int(os.environ.get("BENCH_BATCH", 256)),
                "image": int(os.environ.get("BENCH_IMAGE", 224)),
                "steps": steps, "backend": "tpu", "layout": layout},
         budget),
        # reached only if the b256 attempt failed FAST (OOM / compile
        # error): a timeout skips straight to CPU (same cold-compile
        # wall, and another kill risks leaving the chip claimed)
        (None, {"model": "resnet50", "batch": 64, "image": 224,
                "steps": 10, "backend": "tpu", "layout": layout},
         min(600, budget)),
    ]
    return tpu_attempts + [
        ({"JAX_PLATFORMS": "cpu"},
         {"model": "resnet50", "batch": 8, "image": 32, "steps": 3,
          "backend": "cpu"}, 240),
    ]


def _bert_attempts(tpu_ok):
    steps = int(os.environ.get("BENCH_BERT_STEPS", 12))
    # 900s default for the same cold-compile reason as _attempts
    budget = int(os.environ.get("BENCH_BERT_BUDGET", 900))
    if not tpu_ok:
        return [({"JAX_PLATFORMS": "cpu"},
                 {"model": "bert", "batch": 2, "seq": 128, "steps": 2,
                  "backend": "cpu", "attn": "dense"}, 240)]
    return [
        (None, {"model": "bert",
                "batch": int(os.environ.get("BENCH_BERT_BATCH", 32)),
                "seq": int(os.environ.get("BENCH_BERT_SEQ", 512)),
                "steps": steps, "backend": "tpu", "attn": "flash"},
         budget),
        # dense-attention fallback: a Pallas/Mosaic compile failure must
        # not cost the whole metric
        (None, {"model": "bert", "batch": 16, "seq": 512, "steps": 6,
                "backend": "tpu", "attn": "dense"}, min(420, budget)),
        # a flash TIMEOUT skips the dense TPU attempt (same cold-compile
        # wall) — this CPU entry keeps the metric non-null even then
        ({"JAX_PLATFORMS": "cpu"},
         {"model": "bert", "batch": 2, "seq": 128, "steps": 2,
          "backend": "cpu", "attn": "dense"}, 240),
    ]


def _trainer_attempts(tpu_ok):
    steps = int(os.environ.get("BENCH_TRAINER_STEPS", 30))
    nparams = int(os.environ.get("BENCH_TRAINER_PARAMS", 160))
    cfg = {"model": "trainer_step", "params": nparams, "batch": nparams,
           "steps": steps}
    # persistent compile cache shared across worker processes: the
    # orchestrator runs this bench TWICE and reports the second run's
    # first_step_ms as restart-to-first-step (trace + cache hit instead
    # of trace + compile)
    cache = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".jax_cache", "trainer")
    attempts = []
    if tpu_ok:
        attempts.append((None, dict(cfg, backend="tpu"), 240))
    attempts.append(({"JAX_PLATFORMS": "cpu",
                      "JAX_COMPILATION_CACHE_DIR": cache},
                     dict(cfg, backend="cpu"), 240))
    return attempts


def _embedding_attempts(tpu_ok):
    cfg = {"model": "embedding",
           "vocab": int(os.environ.get("BENCH_EMB_VOCAB", 4096)),
           "dim": int(os.environ.get("BENCH_EMB_DIM", 64)),
           "batch": int(os.environ.get("BENCH_EMB_BATCH", 512)),
           "steps": int(os.environ.get("BENCH_EMB_STEPS", 20))}
    attempts = []
    if tpu_ok:
        attempts.append((None, dict(cfg, backend="tpu"), 240))
    # the captured-vs-eager ratio gate is meaningful on any backend;
    # CPU numbers survive only under embedding_on_chip_unavailable
    # tagging
    attempts.append(({"JAX_PLATFORMS": "cpu"},
                     dict(cfg, backend="cpu"), 240))
    return attempts


def _sharded_attempts(tpu_ok):
    steps = int(os.environ.get("BENCH_SHARDED_STEPS", 10))
    cfg = {"model": "sharded_step", "batch": 8, "steps": steps}
    attempts = []
    if tpu_ok:
        attempts.append((None, dict(cfg, backend="tpu"), 300))
    # forced-host 8-device mesh: the SAME sharded program shapes (TP
    # collectives, FSDP gathers) compile and run on any box; the
    # orchestrator tags the numbers sharded_on_chip_unavailable
    attempts.append((
        {"JAX_PLATFORMS": "cpu",
         "XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
        dict(cfg, backend="cpu"), 300))
    return attempts


def _pp_attempts(tpu_ok):
    steps = int(os.environ.get("BENCH_PP_STEPS", 10))
    cfg = {"model": "pp_step", "batch": 8, "steps": steps}
    attempts = []
    if tpu_ok:
        attempts.append((None, dict(cfg, backend="tpu"), 300))
    # forced-host 8-device mesh: the SAME 3-axis program (tp
    # collectives, pp stage hand-offs, dp reduce) compiles and runs on
    # any box; the orchestrator tags the numbers pp_on_chip_unavailable
    attempts.append((
        {"JAX_PLATFORMS": "cpu",
         "XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
        dict(cfg, backend="cpu"), 300))
    return attempts


def _autotune_attempts(tpu_ok):
    steps = int(os.environ.get("BENCH_TUNE_TIMED_STEPS", 20))
    cfg = {"model": "autotune", "batch": 8, "steps": steps}
    attempts = []
    if tpu_ok:
        attempts.append((None, dict(cfg, backend="tpu"), 420))
    # the 8-device test mesh: the tuner's knobs (bucket MB, FSDP floor,
    # remat, group split) exercise real collective/sharding paths here;
    # numbers survive only under autotune_on_chip_unavailable tagging
    attempts.append((
        {"JAX_PLATFORMS": "cpu",
         "XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
        dict(cfg, backend="cpu"), 420))
    return attempts


def _serving_attempts(tpu_ok):
    cfg = {"model": "serving",
           "batch": int(os.environ.get("BENCH_SERVE_BATCH", 8)),
           "clients": int(os.environ.get("BENCH_SERVE_CLIENTS", 8)),
           "requests": int(os.environ.get("BENCH_SERVE_REQUESTS", 24)),
           "new_tokens": int(os.environ.get("BENCH_SERVE_TOKENS", 8))}
    attempts = []
    if tpu_ok:
        attempts.append((None, dict(cfg, backend="tpu"), 300))
    # the bucketed AOT programs compile and serve on any backend; CPU
    # numbers survive only under serving_on_chip_unavailable tagging
    attempts.append(({"JAX_PLATFORMS": "cpu"},
                     dict(cfg, backend="cpu"), 300))
    return attempts


def _obs_attempts(tpu_ok):
    cfg = {"model": "obs",
           "steps": int(os.environ.get("BENCH_OBS_STEPS", 300)),
           "batch": int(os.environ.get("BENCH_OBS_BATCH", 512)),
           "requests": int(os.environ.get("BENCH_OBS_REQUESTS", 8)),
           "new_tokens": int(os.environ.get("BENCH_OBS_TOKENS", 4))}
    attempts = []
    if tpu_ok:
        attempts.append((None, dict(cfg, backend="tpu"), 420))
    # the obs plane (JSONL tail + rollup + HTTP scrape) is host-side,
    # so the overhead RATIO is meaningful on any backend; CPU numbers
    # survive only under obs_on_chip_unavailable tagging
    attempts.append(({"JAX_PLATFORMS": "cpu"},
                     dict(cfg, backend="cpu"), 420))
    return attempts


def _integrity_attempts(tpu_ok):
    steps = int(os.environ.get("BENCH_INTEGRITY_STEPS", 40))
    every = int(os.environ.get("BENCH_INTEGRITY_EVERY", 10))
    cfg = {"model": "integrity", "params": 64, "batch": 64,
           "steps": steps, "every": every}
    attempts = []
    if tpu_ok:
        attempts.append((None, dict(cfg, backend="tpu"), 240))
    # the attestation overhead is a RATIO (fingerprint program on vs
    # off, same box), so it is meaningful on any backend; CPU numbers
    # survive only under integrity_on_chip_unavailable tagging
    attempts.append(({"JAX_PLATFORMS": "cpu"},
                     dict(cfg, backend="cpu"), 240))
    return attempts


def _pipeline_attempts():
    # pure host work (decode/augment/collate) + device_put: always runs
    # on CPU so it never touches the chip and never needs a TPU probe
    return [
        ({"JAX_PLATFORMS": "cpu"},
         {"model": "input_pipeline",
          "n": int(os.environ.get("BENCH_PIPE_N", 1024)),
          "batch": int(os.environ.get("BENCH_PIPE_BATCH", 64)),
          "image": int(os.environ.get("BENCH_PIPE_IMAGE", 32)),
          "workers": int(os.environ.get("BENCH_PIPE_WORKERS", 2)),
          "backend": "cpu"}, 300),
    ]


def _ckpt_attempts():
    # pure host work (snapshot + pickle + fsync): always CPU, no probe
    return [
        ({"JAX_PLATFORMS": "cpu"},
         {"model": "ckpt",
          "mb": int(os.environ.get("BENCH_CKPT_MB", 64)),
          "reps": int(os.environ.get("BENCH_CKPT_REPS", 5)),
          "batch": 0,
          "backend": "cpu"}, 300),
    ]


def _recovery_cfg():
    return {
        "world": 3,
        # enough steps AFTER the kill that the survivors are still
        # mid-run when the heartbeat timeout confirms the death — a gang
        # that finishes first never needs to reshape
        "steps": int(os.environ.get("BENCH_RECOVERY_STEPS", 60)),
        "snap_every": 5,
        # NOT a snapshot multiple: the victim must have shipped its
        # shard to the buddy before dying for the peer-RAM path
        "kill_step": 12,
        "step_ms": 20.0,
        "n": 1 << 15,
        "hb_interval": "0.04",
        "hb_timeout": "0.4",
        "budget": int(os.environ.get("BENCH_RECOVERY_BUDGET", 120)),
    }


def _gang_env(extra):
    """Worker env for the recovery gangs: hostile accelerator claims and
    stale gang/fault knobs stripped, then the scenario's own knobs."""
    drop = ("MXTPU_FAULT_INJECT", "MXTPU_KILL_AT_STEP", "MXTPU_GANG_DIR",
            "MXTPU_HEARTBEAT_INTERVAL", "MXTPU_HEARTBEAT_TIMEOUT",
            "MXTPU_PEER_SNAP_EVERY", "MXTPU_ELASTIC")
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(_HOSTILE_ENV_PREFIXES) and k not in drop}
    env.update(extra)
    return env


def _spawn_gang_worker(cfg, extra_env):
    return subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--gang-worker",
         json.dumps(cfg)],
        env=_gang_env(extra_env), stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)


def _last_json(text):
    for ln in reversed((text or "").strip().splitlines()):
        try:
            obj = json.loads(ln)
        except (ValueError, TypeError):
            continue
        if isinstance(obj, dict):
            return obj
    return None


def _recovery_elastic(cfg, base, errors):
    """3-rank elastic gang, rank 1 SIGKILLed mid-run: survivors detect,
    reshape to 2, restore from the buddy's RAM snapshot, and finish.
    Reports rank 0's in-process recovery latency (the span of
    ElasticGang.recover — consensus + acks + shard assembly)."""
    gang_dir = os.path.join(base, "gang")
    os.makedirs(gang_dir)
    extra = {"MXTPU_GANG_DIR": gang_dir,
             "MXTPU_HEARTBEAT_INTERVAL": cfg["hb_interval"],
             "MXTPU_HEARTBEAT_TIMEOUT": cfg["hb_timeout"],
             "MXTPU_FAULT_INJECT": "kill_rank:1",
             "MXTPU_KILL_AT_STEP": str(cfg["kill_step"])}
    procs = [_spawn_gang_worker(
        dict(cfg, mode="elastic", rank=r, gang_dir=gang_dir,
             dir=os.path.join(base, "ck_elastic")), extra)
        for r in range(cfg["world"])]
    deadline = time.monotonic() + cfg["budget"]
    while time.monotonic() < deadline \
            and any(p.poll() is None for p in procs):
        time.sleep(0.1)
    for p in procs:
        if p.poll() is None:
            p.kill()
    outs = [p.communicate() for p in procs]
    if procs[0].returncode != 0:
        tail = (outs[0][1] or "").strip()[-200:]
        errors.append(f"recovery/elastic rank0 "
                      f"rc={procs[0].returncode}: {tail}")
        return None
    obj = _last_json(outs[0][0])
    if not obj or obj.get("recovery_ms") is None:
        errors.append("recovery/elastic: rank0 reported no recovery")
        return None
    if obj.get("final_step") != cfg["steps"]:
        errors.append(f"recovery/elastic: rank0 stopped at "
                      f"{obj.get('final_step')}/{cfg['steps']}")
        return None
    return {"elastic_recovery_ms": round(float(obj["recovery_ms"]), 1),
            "elastic_recovery_source": obj.get("recovery_source"),
            "elastic_disk_restores": obj.get("disk_restores")}


def _recovery_restart(cfg, base, errors):
    """The same failure under classic gang fate-sharing supervision
    (tools/launch.py default mode, inlined so the measurement hooks are
    orchestrator-local): rank 1's death tears the gang down, a FULL gang
    is respawned, every rank resumes from its disk checkpoint.
    full_restart_ms = death observed -> restarted rank 0 completes its
    first post-resume step (process spawn + interpreter + restore are
    all on the clock, exactly the cost elastic recovery avoids)."""
    ckdir = os.path.join(base, "ck_restart")
    marker = os.path.join(base, "resumed")
    extra = {"MXTPU_FAULT_INJECT": "kill_rank:1",
             "MXTPU_KILL_AT_STEP": str(cfg["kill_step"])}

    def wcfg(r):
        return dict(cfg, mode="restart", rank=r, dir=ckdir,
                    marker=marker)

    procs = [_spawn_gang_worker(wcfg(r), extra)
             for r in range(cfg["world"])]
    deadline = time.monotonic() + cfg["budget"]
    t_detect = None
    while time.monotonic() < deadline:
        codes = [p.poll() for p in procs]
        if any(c not in (None, 0) for c in codes):
            t_detect = time.monotonic()
            break
        if all(c == 0 for c in codes):
            break
        time.sleep(0.05)
    if t_detect is None:
        errors.append("recovery/restart: no worker death observed")
        for p in procs:
            p.kill()
            p.communicate()
        return None
    for p in procs:                       # gang fate-sharing teardown
        if p.poll() is None:
            p.terminate()
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()
        p.communicate()
    procs2 = [_spawn_gang_worker(wcfg(r), {})
              for r in range(cfg["world"])]
    marker0 = marker + ".rank0"
    t_first = None
    while time.monotonic() < deadline:
        if os.path.exists(marker0):
            t_first = time.monotonic()
            break
        if procs2[0].poll() is not None:
            break
        time.sleep(0.01)
    while time.monotonic() < deadline \
            and any(p.poll() is None for p in procs2):
        time.sleep(0.1)
    for p in procs2:
        if p.poll() is None:
            p.kill()
        p.communicate()
    if t_first is None:
        errors.append("recovery/restart: restarted gang never reached "
                      "a resumed step")
        return None
    return {"full_restart_ms": round((t_first - t_detect) * 1e3, 1)}


def bench_recovery(errors):
    """elastic_recovery_ms vs full_restart_ms for the SAME injected
    failure (rank 1 of 3 SIGKILLed mid-run) — the headline claim of the
    elastic gang work.  Orchestrator-side and jax-free end to end: the
    gang workers are hermetic ``bench.py --gang-worker`` subprocesses
    (numpy state, FileKV control plane), so this scenario never touches
    the chip and runs identically on any host."""
    import shutil
    import tempfile

    cfg = _recovery_cfg()
    base = tempfile.mkdtemp(prefix="bench_recovery_")
    out = {}
    try:
        out.update(_recovery_elastic(cfg, base, errors) or {})
        out.update(_recovery_restart(cfg, base, errors) or {})
    finally:
        shutil.rmtree(base, ignore_errors=True)
    e_ms = out.get("elastic_recovery_ms")
    f_ms = out.get("full_restart_ms")
    if e_ms is not None and f_ms is not None:
        out["elastic_recovery_speedup"] = round(f_ms / e_ms, 2) \
            if e_ms else None
        out["elastic_faster_than_restart"] = e_ms < f_ms
    return out or None


# -- resumable-input-pipeline bench (gluon/data/state.py) ----------------------

def _load_data_state():
    """Load gluon/data/state.py WITHOUT importing the package (numpy +
    stdlib only by contract) — the orchestrator stays jax-free."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "mxnet_tpu", "gluon", "data", "state.py")
    spec = importlib.util.spec_from_file_location("_mxtpu_data_state",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _drain_epoch(states, batch, ledger):
    """Drive every rank's state to the end of the in-flight epoch,
    recording each delivered sample index in ``ledger`` (a dict
    index -> times delivered).  Mirrors the ResumableSampler contract:
    the shard is computed ONCE from the current cursor, then the cursor
    advances at delivery time."""
    shards = [st.shard().tolist() for st in states]
    for st, shard in zip(states, shards):
        for i in range(0, len(shard), batch):
            chunk = shard[i:i + batch]
            for s in chunk:
                ledger[s] = ledger.get(s, 0) + 1
            st.advance(len(chunk))


def bench_data_resume(errors):
    """Exactly-once resume ledger + accounting overhead, orchestrator-
    side and jax-free (pure host work, like bench_recovery — the
    numbers carry no device claim so they need no on-chip tag).

    Scenario A (kill/resume): 3 ranks consume part of an epoch, rank
    state is checkpointed at a delivery boundary (exactly what
    ``AsyncCheckpointer.save(..., data_state=)`` stamps), the processes
    "die", fresh states adopt the checkpoint and finish the epoch.
    Scenario B (elastic 3->2): mid-epoch the global state is reloaded
    by TWO survivors which re-shard the remaining sample space.  Both
    gate on the sample ledger: every index delivered exactly once —
    zero re-read, zero skipped.

    Overhead: per-batch delivery accounting + a periodic state_dict()
    vs the identical loop without any of it, on real batch copies —
    gated at <= 1%."""
    try:
        import numpy as np

        ds = _load_data_state()
        n, batch = 4096, 64
        out = {}

        # -- A: kill/resume mid-epoch --------------------------------
        ledger = {}
        states = [ds.DataPipelineState(n, seed=7, rank=r, world=3)
                  for r in range(3)]
        # each rank delivers 10 batches, then the job is killed; the
        # checkpoint is the state AT the delivery boundary
        for st in states:
            shard = st.shard().tolist()
            for i in range(0, 10 * batch, batch):
                chunk = shard[i:i + batch]
                for s in chunk:
                    ledger[s] = ledger.get(s, 0) + 1
                st.advance(len(chunk))
        saved = states[0].state_dict()          # global fields
        resumed = []
        for r in range(3):                      # fresh processes
            st = ds.DataPipelineState(n, seed=7, rank=r, world=3)
            st.load_state_dict(saved)
            resumed.append(st)
        _drain_epoch(resumed, batch, ledger)
        reread = sum(1 for c in ledger.values() if c > 1)
        skipped = n - len(ledger)
        out["data_resume_reread_samples"] = int(reread)
        out["data_resume_skipped_samples"] = int(skipped)

        # -- B: elastic 3 -> 2 reshape mid-epoch ---------------------
        ledger2 = {}
        states = [ds.DataPipelineState(n, seed=11, rank=r, world=3)
                  for r in range(3)]
        for st in states:
            shard = st.shard().tolist()
            for i in range(0, 8 * batch, batch):
                chunk = shard[i:i + batch]
                for s in chunk:
                    ledger2[s] = ledger2.get(s, 0) + 1
                st.advance(len(chunk))
        saved = states[1].state_dict()          # any survivor's copy
        survivors = []
        for r in range(2):                      # rank 2 is gone
            st = ds.DataPipelineState(n, seed=11, rank=r, world=2)
            st.load_state_dict(saved)
            survivors.append(st)
        _drain_epoch(survivors, batch, ledger2)
        out["data_reshape_reread_samples"] = int(
            sum(1 for c in ledger2.values() if c > 1))
        out["data_reshape_skipped_samples"] = int(n - len(ledger2))

        # -- accounting overhead vs a non-checkpointed loop ----------
        # both loops pay the REAL DataLoader's per-batch work — one
        # dataset __getitem__ per sample plus the np.stack batchify —
        # so the gate compares accounting against what a loader
        # actually does, not against a single fancy-index
        data = np.random.default_rng(0).standard_normal(
            (n, 2048)).astype(np.float32)
        reps = int(os.environ.get("BENCH_DATA_RESUME_REPS", 3))

        def batchify(idxs):
            return np.stack([data[int(j)] for j in idxs])

        def run_plain():
            order = ds.epoch_order(7, 0, n)
            t0 = time.perf_counter()
            for i in range(0, n, batch):
                batchify(order[i:i + batch])
            return time.perf_counter() - t0

        def run_resumable():
            st = ds.DataPipelineState(n, seed=7)
            shard = st.shard()
            t0 = time.perf_counter()
            for k, i in enumerate(range(0, n, batch)):
                batchify(shard[i:i + batch])
                st.advance(min(batch, n - i))
                if k % 10 == 0:
                    st.state_dict()             # checkpoint cadence
            return time.perf_counter() - t0

        run_plain(), run_resumable()            # warm the page cache
        t_plain = min(run_plain() for _ in range(reps))
        t_res = min(run_resumable() for _ in range(reps))
        overhead = (t_res - t_plain) / t_plain if t_plain > 0 else 0.0
        out["data_resume_overhead_pct"] = round(100.0 * overhead, 3)

        gates = {
            "zero_reread_samples":
                out["data_resume_reread_samples"] == 0
                and out["data_reshape_reread_samples"] == 0,
            "zero_skipped_samples":
                out["data_resume_skipped_samples"] == 0
                and out["data_reshape_skipped_samples"] == 0,
            "resume_overhead_le_1pct": overhead <= 0.01,
        }
        out["data_resume_gates"] = gates
        out["data_resume_gates_ok"] = all(gates.values())
        return out
    except Exception as e:      # noqa: BLE001 — bench must print JSON
        errors.append(f"data_resume: {type(e).__name__}: {e}")
        return None


# -- fleet bench (traffic-elastic control plane) -------------------------------

def _fleet_gang_thread(res, dist, np, server, rank, world, num_steps,
                       snap_every, out, *, hb_timeout=5.0, step_s=0.0,
                       join=False, die_at=None, leave_after=None,
                       preempt_after=None, policy_kw=None):
    """One in-process rank of a fleet-bench thread gang over TcpKV.

    Measurement hooks: ``reshape_ms`` is the wall-clock from the
    attempt that raised RankFailure to recover() returning — for a
    planned drain that is pure reshape cost, for a silent death it
    includes the detection window, which is exactly the comparison the
    drain protocol exists to win.  ``computed`` counts loss
    computations, so ``computed - len(losses)`` is the redone-step bill
    of each reshape (zero for a planned one)."""
    import threading
    kv = None
    gang = None
    try:
        kv = dist.TcpKV(server.addr, rank=rank)
        gang = res.ElasticGang(rank, world, kv=kv,
                               peer_snap_every=snap_every,
                               heartbeat_interval=0.05,
                               heartbeat_timeout=hb_timeout)
        policy = res.ScalePolicy(gang, **policy_kw) if policy_kw else None
        state = {"w": np.full(8, 1.0, dtype=np.float64), "opt": 0.0}
        step, losses, computed = 0, {}, 0
        reshapes, reshape_ms = 0, []
        planned_at = preempt_trigger = None
        rec = {"rank": rank, "gang": gang, "kv": kv, "policy": policy,
               "losses": losses, "reshape_ms": reshape_ms}
        if join:
            info = gang.join()
            st = info.shards.get(rank)
            if st is None:              # fresh joiner: adopt a replica
                st = dict(next(iter(info.shards.values())))
                st["opt"] = 0.0
            state = {"w": np.array(st["w"], dtype=np.float64),
                     "opt": float(st["opt"])}
            step = info.snap_step
            if preempt_after is not None:
                preempt_trigger = step + preempt_after
        else:
            gang.start()
        while step < num_steps:
            if die_at is not None and step == die_at:
                gang.hb.stop()          # silent death: no heartbeat
                out[rank] = dict(rec, status="died", computed=computed)
                return
            if leave_after is not None and step == leave_after \
                    and planned_at is None:
                planned_at = gang.plan_leave(step + gang.drain_margin)
            if preempt_trigger is not None and step == preempt_trigger:
                res.ScalePolicy(gang, min_world=2).on_preemption(step)
                preempt_trigger = None
            t_try = time.monotonic()
            try:
                gang.step_tick(step, state=state)
                epoch = gang.epoch
                kv.put_json(f"red/{epoch}/{step}/{rank}",
                            {"v": (rank + 1) * float(state["w"].sum())})
                gang.barrier(f"red{step}")
                total = sum(
                    float(kv.get_json(f"red/{epoch}/{step}/{r}")["v"])
                    for r in sorted(gang.members))
                loss = total / len(gang.members)
                computed += 1
            except res.RankFailure as rf:
                try:
                    info = gang.recover(rf)
                except res.GangEvicted:
                    gang.stop()
                    res.announce_freed_chips(kv, rank, step=step)
                    out[rank] = dict(rec, status="evicted",
                                     computed=computed)
                    return
                reshape_ms.append((time.monotonic() - t_try) * 1e3)
                st = info.shards.get(rank)
                if st is None:
                    st = dict(next(iter(info.shards.values())))
                    st["opt"] = 0.0
                state = {"w": np.array(st["w"], dtype=np.float64),
                         "opt": float(st["opt"])}
                step = info.snap_step
                reshapes += 1
                continue
            if policy is not None:
                policy.observe(step, queue_depth=4.0, data_share=0.0)
            losses[step] = loss
            state["w"] = state["w"] * 0.99 - 0.01 * (loss /
                                                     state["w"].size)
            state["opt"] += loss
            step += 1
            if step_s:
                time.sleep(step_s)
        out[rank] = dict(rec, status="done", computed=computed,
                         reshapes=reshapes)
    except Exception as e:              # noqa: BLE001 — surfaced
        out[rank] = {"rank": rank, "status": "error", "error": repr(e),
                     "gang": gang, "kv": kv, "losses": {},
                     "reshape_ms": []}


def _fleet_teardown(out, server):
    for v in out.values():
        g = v.get("gang")
        if g is not None:
            try:
                g.stop()
            except Exception:           # noqa: BLE001 — teardown
                pass
        c = v.get("kv")
        if c is not None:
            try:
                c.close()
            except Exception:           # noqa: BLE001 — teardown
                pass
    server.stop()


def _fleet_reshape(res, dist, np, mode, errors):
    """One 3-rank TcpKV thread gang losing rank 1 at step 5 — either as
    a planned drain (``plan_leave``, no detection window, no redone
    steps) or as a silent death (heartbeat-timeout detection + rollback
    to the newest common snapshot).  Returns (mean reshape ms across
    survivors, redone steps)."""
    import threading
    server = dist.GangKVServer(lease_ttl=5.0).start()
    num_steps, snap_every, event_step = 12, 2, 5
    out = {}
    threads = [threading.Thread(
        target=_fleet_gang_thread,
        args=(res, dist, np, server, r, 3, num_steps, snap_every, out),
        kwargs={"hb_timeout": 0.6 if mode == "detect" else 5.0,
                "die_at": event_step if (mode == "detect" and r == 1)
                else None,
                "leave_after": event_step if (mode == "drain" and r == 1)
                else None},
        daemon=True) for r in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    try:
        if any(t.is_alive() for t in threads):
            errors.append(f"fleet/{mode}: gang wedged")
            return None, None
        if out.get(1, {}).get("status") not in ("died", "evicted"):
            errors.append(f"fleet/{mode}: rank1 {out.get(1)}")
            return None, None
        ms, redone = [], 0
        for r in (0, 2):
            v = out.get(r)
            if not v or v.get("status") != "done":
                errors.append(f"fleet/{mode}: rank{r} {v and v.get('error')}")
                return None, None
            ms.extend(v["reshape_ms"])
            redone += v["computed"] - len(v["losses"])
        if not ms:
            errors.append(f"fleet/{mode}: no reshape observed")
            return None, None
        return sum(ms) / len(ms), redone
    finally:
        _fleet_teardown(out, server)


def _fleet_scale_cycle(res, dist, np, errors):
    """Forced grow→shrink→grow driven by ScalePolicy over TcpKV: rank
    0's policy sees a saturated input queue and publishes ``scale/req``;
    a launcher thread consumes it and spawns a joiner (scheduled admit);
    the joiner is then "preempted" — graceful drain + freed-chip
    announcement — and the policy grows the gang again.  The bar is
    zero lost steps on the base ranks across the whole cycle."""
    import threading
    server = dist.GangKVServer(lease_ttl=5.0).start()
    num_steps, snap_every, step_s = 26, 2, 0.06
    out = {}
    policy_kw = {"min_world": 2, "max_world": 3, "window": 3,
                 "cooldown": 0.5}
    threads = [threading.Thread(
        target=_fleet_gang_thread,
        args=(res, dist, np, server, r, 2, num_steps, snap_every, out),
        kwargs={"step_s": step_s,
                "policy_kw": policy_kw if r == 0 else None},
        daemon=True) for r in range(2)]
    stop_launcher = threading.Event()

    def launcher():
        lkv = dist.TcpKV(server.addr, standby=False)
        next_rank = 2
        try:
            while not stop_launcher.is_set() and next_rank <= 3:
                req = lkv.get_json("scale/req")
                if isinstance(req, dict):
                    lkv.delete("scale/req")
                    r = next_rank
                    next_rank += 1
                    t = threading.Thread(
                        target=_fleet_gang_thread,
                        args=(res, dist, np, server, r, 2, num_steps,
                              snap_every, out),
                        kwargs={"step_s": step_s, "join": True,
                                "preempt_after": 4 if r == 2 else None},
                        daemon=True)
                    t.start()
                    threads.append(t)
                time.sleep(0.05)
        finally:
            try:
                lkv.close()
            except Exception:           # noqa: BLE001 — teardown
                pass

    lt = threading.Thread(target=launcher, daemon=True)
    for t in threads:
        t.start()
    lt.start()
    deadline = time.monotonic() + 90
    for t in list(threads):
        t.join(timeout=max(0.1, deadline - time.monotonic()))
    # the second joiner's thread is appended mid-run; join stragglers
    for t in list(threads):
        t.join(timeout=max(0.1, deadline - time.monotonic()))
    stop_launcher.set()
    lt.join(timeout=10)
    try:
        if any(t.is_alive() for t in threads):
            errors.append("fleet/cycle: gang wedged")
            return None
        lost = 0
        for r in (0, 1):
            v = out.get(r)
            if not v or v.get("status") != "done":
                errors.append(f"fleet/cycle: rank{r} "
                              f"{v and (v.get('status'), v.get('error'))}")
                return None
            if sorted(v["losses"]) != list(range(num_steps)):
                errors.append(f"fleet/cycle: rank{r} missed steps")
                return None
            lost += v["computed"] - len(v["losses"])
        pol = out[0].get("policy")
        freed = [k for k, _ in out[0]["kv"].scan("chips/freed")]
        evicted = out.get(2, {}).get("status") == "evicted"
        joined2 = out.get(3, {}).get("status") == "done"
        return {"fleet_cycle_lost_steps": lost,
                "fleet_cycle_grow_requests":
                    pol.grow_requests if pol else None,
                "fleet_cycle_drained": evicted,
                "fleet_cycle_regrown": joined2,
                "fleet_cycle_chips_freed": len(freed),
                "fleet_cycle_final_world": len(out[0]["gang"].members)}
    finally:
        _fleet_teardown(out, server)


def _fleet_failover(res, dist, errors):
    """Coordinator death mid-run: rank 0's client promotes itself on
    its standby socket, replays the state frame, rank 1 adopts — the
    measured span is die() → the next successful mutation."""
    stagger = os.environ.get("MXTPU_KV_FAILOVER_STAGGER")
    os.environ["MXTPU_KV_FAILOVER_STAGGER"] = "0.1"
    server = dist.GangKVServer(lease_ttl=1.0).start()
    c0 = c1 = None
    try:
        c0 = dist.TcpKV(server.addr, rank=0)
        c1 = dist.TcpKV(server.addr, rank=1)
        c0.put_json("fleet/seed", {"v": 42})
        c1.get_json("fleet/seed")
        time.sleep(0.5)                 # a lease renewal refreshes the
        server.die()                    # clients' failover state frames
        t0 = time.monotonic()
        c0.put_json("fleet/after", {"v": 1})
        ms = (time.monotonic() - t0) * 1e3
        if (c1.get_json("fleet/seed") or {}).get("v") != 42:
            errors.append("fleet/failover: replayed state lost a write")
            return None
        if not c0.failovers:
            errors.append("fleet/failover: no failover recorded")
            return None
        return round(ms, 1)
    except Exception as e:              # noqa: BLE001 — surfaced
        errors.append(f"fleet/failover: {e!r}")
        return None
    finally:
        for c in (c1, c0):
            if c is not None:
                try:
                    c.close()
                except Exception:       # noqa: BLE001 — teardown
                    pass
        server.stop()
        if stagger is None:
            os.environ.pop("MXTPU_KV_FAILOVER_STAGGER", None)
        else:
            os.environ["MXTPU_KV_FAILOVER_STAGGER"] = stagger


def _fleet_shed(errors):
    """Bounded admission vs unbounded queueing at 2x the engine's
    service rate: same stub engine, same offered load; the bounded
    batcher sheds (ServerOverloaded) and keeps the p99 of ADMITTED
    requests flat, the unbounded one lets the backlog grow and the p99
    walk off with it."""
    import threading
    batcher_mod = _import_batcher()

    class _StubEngine:
        batch_buckets = (1, 2, 4)

        def serve_group(self, prompts, maxes, temperature=None,
                        rng=None):
            time.sleep(0.01)            # 4-wide groups -> ~400 req/s
            outs = [[1, 2, 3] for _ in prompts]
            return outs, {"prefill_us": 10.0,
                          "decode_us_per_token": 1.0,
                          "bucket": [max(len(prompts), 1), 8],
                          "padded_fraction": 0.0, "generation": 0}

    def drive(max_queue):
        b = batcher_mod.ContinuousBatcher(_StubEngine(),
                                          max_delay_ms=0.5,
                                          max_queue=max_queue)
        lats, lock = [], threading.Lock()
        shed = 0
        interval, duration = 1.0 / 800.0, 0.5   # 2x capacity
        t_end = time.monotonic() + duration
        nxt = time.monotonic()
        try:
            while time.monotonic() < t_end:
                t_sub = time.monotonic()
                try:
                    f = b.submit("p", 3)
                except batcher_mod.ServerOverloaded:
                    shed += 1
                else:
                    def done(fut, t=t_sub):
                        with lock:
                            lats.append(time.monotonic() - t)
                    f.add_done_callback(done)
                nxt += interval
                delay = nxt - time.monotonic()
                if delay > 0:
                    time.sleep(delay)
        finally:
            try:
                b.close(timeout=30)
            except Exception:           # noqa: BLE001 — teardown
                pass
        with lock:
            done_lats = sorted(lats)
        if not done_lats:
            return None, shed
        p99 = done_lats[int(0.99 * (len(done_lats) - 1))] * 1e3
        return round(p99, 1), shed

    bounded_p99, shed = drive(batcher_mod.max_queue_from_env(default=8))
    unbounded_p99, _ = drive(4096)
    if bounded_p99 is None or unbounded_p99 is None:
        errors.append("fleet/shed: no completed requests")
        return None
    if not shed:
        errors.append("fleet/shed: bounded run shed nothing at 2x load")
    return {"serve_shed_p99_ms": bounded_p99,
            "serve_unbounded_p99_ms": unbounded_p99,
            "serve_shed_count": shed,
            "serve_shed_bounded": bounded_p99 < unbounded_p99}


def bench_fleet(errors):
    """Traffic-elastic fleet numbers (all jax-free, in-process thread
    gangs over a real GangKVServer — no shared filesystem anywhere):

    - fleet_drain_ms vs fleet_detected_ms: the SAME rank loss as a
      planned drain vs a silent death.  The drain must be cheaper (no
      detection window) and redo zero steps.
    - fleet_cycle_*: a forced grow→shrink→grow ScalePolicy cycle with
      zero lost steps on the base ranks.
    - fleet_failover_ms: coordinator death → next successful mutation.
    - serve_shed_*: bounded vs unbounded admission at 2x overload.
    """
    res, dist = _import_elastic()
    import numpy as np

    out = {}
    drain_ms, drain_redone = _fleet_reshape(res, dist, np, "drain",
                                            errors)
    det_ms, det_redone = _fleet_reshape(res, dist, np, "detect", errors)
    if drain_ms is not None:
        out["fleet_drain_ms"] = round(drain_ms, 1)
        out["fleet_drain_redone_steps"] = drain_redone
    if det_ms is not None:
        out["fleet_detected_ms"] = round(det_ms, 1)
        out["fleet_detected_redone_steps"] = det_redone
    if drain_ms is not None and det_ms is not None:
        out["fleet_drain_cheaper"] = drain_ms < det_ms
        out["fleet_drain_speedup"] = round(det_ms / drain_ms, 2) \
            if drain_ms else None
    fo = _fleet_failover(res, dist, errors)
    if fo is not None:
        out["fleet_failover_ms"] = fo
    cycle = _fleet_scale_cycle(res, dist, np, errors)
    if cycle:
        out.update(cycle)
    shed = _fleet_shed(errors)
    if shed:
        out.update(shed)
    return out or None


def _partition_gang_thread(res, dist, np, server, rank, world,
                           num_steps, snap_every, out, *, hb_timeout,
                           step_s):
    """One rank of the partition bench (PR 20 fencing): same KV-plane
    allreduce loop as `_fleet_gang_thread`, plus the GangFenced path —
    a partitioned rank parks, probes a STALE durable write against the
    healed KV (must be rejected by the fence), then rejoins via
    `park_fenced`."""
    kv = gang = None
    try:
        kv = dist.TcpKV(server.addr, rank=rank)
        gang = res.ElasticGang(rank, world, kv=kv,
                               peer_snap_every=snap_every,
                               heartbeat_interval=0.05,
                               heartbeat_timeout=hb_timeout)
        gang.start()
        state = {"w": np.full(8, 1.0, dtype=np.float64), "opt": 0.0}
        step, losses, computed = 0, {}, 0
        reshape_ms, fenced, rejoined = [], False, False
        probe_rejected = probe_committed = 0
        fenced_ms = None
        rec = {"rank": rank, "gang": gang, "kv": kv, "losses": losses,
               "reshape_ms": reshape_ms}
        while step < num_steps:
            t_try = time.monotonic()
            try:
                gang.step_tick(step, state=state)
                epoch = gang.epoch
                kv.put_json(f"red/{epoch}/{step}/{rank}",
                            {"v": (rank + 1) * float(state["w"].sum())})
                gang.barrier(f"red{step}")
                total = sum(
                    float(kv.get_json(f"red/{epoch}/{step}/{r}")["v"])
                    for r in sorted(gang.members))
                loss = total / len(gang.members)
                computed += 1
            except (res.GangFenced, dist.GangKVError):
                fenced = True
                stale_epoch = gang.epoch
                # wait out the partition with read-only probes, then
                # attempt ONE stale durable write: the fence must
                # reject it (FencedWrite) — that rejection IS the
                # minority_zero_durable_writes evidence
                t_f = time.monotonic()
                while time.monotonic() - t_f < 20.0:
                    try:
                        kv.get_json("epoch/current")
                        break
                    except (dist.GangKVError, OSError):
                        time.sleep(0.1)
                try:
                    kv.put_if_epoch(f"zombie/{rank}", b"stale",
                                    stale_epoch)
                    probe_committed += 1
                except dist.FencedWrite:
                    probe_rejected += 1
                except (dist.GangKVError, res.MXNetError, OSError):
                    pass
                try:
                    info = gang.park_fenced(timeout=20.0)
                except res.MXNetError:
                    break               # heal/rejoin window missed
                fenced_ms = (time.monotonic() - t_f) * 1e3
                rejoined = True
                if info is not None:
                    st = info.shards.get(rank) if info.shards else None
                    if st is None and info.shards:
                        st = dict(next(iter(info.shards.values())))
                        st["opt"] = 0.0
                    if st is not None:
                        state = {"w": np.array(st["w"],
                                               dtype=np.float64),
                                 "opt": float(st["opt"])}
                    step = info.snap_step
                continue
            except res.RankFailure as rf:
                try:
                    info = gang.recover(rf)
                except res.GangEvicted:
                    gang.stop()
                    out[rank] = dict(rec, status="evicted",
                                     computed=computed)
                    return
                reshape_ms.append((time.monotonic() - t_try) * 1e3)
                st = info.shards.get(rank)
                if st is None:
                    st = dict(next(iter(info.shards.values())))
                    st["opt"] = 0.0
                state = {"w": np.array(st["w"], dtype=np.float64),
                         "opt": float(st["opt"])}
                step = info.snap_step
                continue
            losses[step] = loss
            state["w"] = state["w"] * 0.99 - 0.01 * (loss /
                                                     state["w"].size)
            state["opt"] += loss
            step += 1
            if step_s:
                time.sleep(step_s)
        out[rank] = dict(rec, status="done", computed=computed,
                         fenced=fenced, rejoined=rejoined,
                         fenced_ms=fenced_ms,
                         probe_rejected=probe_rejected,
                         probe_committed=probe_committed,
                         members=list(gang.members))
    except Exception as e:              # noqa: BLE001 — surfaced
        out[rank] = {"rank": rank, "status": "error", "error": repr(e),
                     "gang": gang, "kv": kv, "losses": {},
                     "reshape_ms": []}


def bench_partition(errors):
    """Split-brain fencing numbers (PR 20, jax-free thread gang over
    TcpKV): rank 2 is cut off from the coordinator mid-run
    (``partition_split:2``), the majority detects it and commits a
    quorum-gated reshape (``partition_majority_continue_ms`` — compare
    with ``elastic_recovery_ms``), the minority fences and its stale
    write probe is REJECTED (gate ``minority_zero_durable_writes``),
    and after ``MXTPU_PARTITION_SECS`` the partition heals and the
    fenced rank rejoins (``partition_heal_ms``,
    ``partition_world_restored``)."""
    import threading
    res, dist = _import_elastic()
    import numpy as np

    server = dist.GangKVServer(lease_ttl=5.0).start()
    num_steps, snap_every, step_s = 70, 2, 0.06
    run_out = {}
    saved = {k: os.environ.get(k)
             for k in ("MXTPU_FAULT_INJECT", "MXTPU_PARTITION_SECS")}
    threads = [threading.Thread(
        target=_partition_gang_thread,
        args=(res, dist, np, server, r, 3, num_steps, snap_every,
              run_out),
        kwargs={"hb_timeout": 0.5, "step_s": step_s},
        daemon=True) for r in range(3)]
    try:
        for t in threads:
            t.start()
        time.sleep(0.8)     # let the gang reach steady state first
        os.environ["MXTPU_PARTITION_SECS"] = "1.5"
        os.environ["MXTPU_FAULT_INJECT"] = "partition_split:2"
        for t in threads:
            t.join(timeout=90)
        if any(t.is_alive() for t in threads):
            errors.append("partition: gang wedged")
            return None
        out = {}
        ms = []
        for r in (0, 1):
            v = run_out.get(r)
            if not v or v.get("status") != "done":
                errors.append(
                    f"partition: rank{r} {v and v.get('error')}")
                return None
            ms.extend(v["reshape_ms"])
        v2 = run_out.get(2) or {}
        if not v2.get("fenced"):
            errors.append("partition: rank2 never fenced")
            return None
        if ms:
            out["partition_majority_continue_ms"] = \
                round(sum(ms) / len(ms), 1)
        out["minority_zero_durable_writes"] = \
            v2.get("probe_committed", 1) == 0 and \
            v2.get("probe_rejected", 0) >= 1
        out["partition_world_restored"] = \
            v2.get("status") == "done" and v2.get("rejoined") and \
            sorted(v2.get("members", ())) == [0, 1, 2]
        if v2.get("fenced_ms") is not None:
            out["partition_heal_ms"] = round(v2["fenced_ms"], 1)
        return out
    finally:
        for k, old in saved.items():
            if old is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = old
        res.reset_faults()
        _fleet_teardown(run_out, server)


def _run_worker(env_over, cfg, budget, errors, timed_out=None):
    env = dict(os.environ)
    if env_over is not None:
        # CPU fallback: strip anything that could claim the chip
        env = {k: v for k, v in env.items()
               if not k.startswith(_HOSTILE_ENV_PREFIXES)}
        env.update(env_over)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--worker",
             json.dumps(cfg)],
            env=env, timeout=budget, capture_output=True, text=True)
    except subprocess.TimeoutExpired:
        errors.append(f"{cfg['model']}/{cfg['backend']} "
                      f"b{cfg['batch']}: timeout {budget}s")
        if timed_out is not None:
            timed_out.append(cfg)
        return None
    for ln in reversed(proc.stdout.strip().splitlines()):
        try:
            obj = json.loads(ln)
        except (ValueError, TypeError):
            continue
        if isinstance(obj, dict) and "metric" in obj:
            if proc.returncode == 0:
                return obj
            break
    tail = (proc.stderr or proc.stdout or "").strip()[-300:]
    errors.append(f"{cfg['model']}/{cfg['backend']} b{cfg['batch']}: rc="
                  f"{proc.returncode} "
                  f"{tail.splitlines()[-1] if tail else ''}")
    return None


def orchestrate():
    errors = []
    if os.environ.get("BENCH_SKIP_TPU"):
        probe = {}
        tpu_ok, probe_note = False, "BENCH_SKIP_TPU set"
    else:
        probe = _probe_backend()
        tpu_ok = probe.get("ok", False)
        probe_note = ("ok: " + probe.get("kind", "?")) if tpu_ok \
            else probe.get("reason", "?")
        if not tpu_ok:
            errors.append(f"tpu skipped ({probe_note})")
    headline = None
    timed_out = []
    for env_over, cfg, budget in _attempts(tpu_ok):
        if timed_out and cfg.get("backend") == "tpu":
            continue  # cold-compile wall: don't re-kill on the chip
        headline = _run_worker(env_over, cfg, budget, errors, timed_out)
        if headline is not None:
            break
    bert = None
    bert_errors = []
    if headline is not None and not os.environ.get("BENCH_SKIP_BERT"):
        bert_timed_out = []
        for env_over, cfg, budget in _bert_attempts(tpu_ok):
            if bert_timed_out and cfg.get("backend") == "tpu":
                continue
            bert = _run_worker(env_over, cfg, budget, bert_errors,
                               bert_timed_out)
            if bert is not None:
                break
    trainer_bench = None
    trainer_restart = None
    trainer_errors = []
    if headline is not None and not os.environ.get("BENCH_SKIP_TRAINER"):
        for env_over, cfg, budget in _trainer_attempts(tpu_ok):
            trainer_bench = _run_worker(env_over, cfg, budget,
                                        trainer_errors)
            if trainer_bench is not None:
                # same config again in a FRESH process: its
                # first_step_ms is restart-to-first-step (trace +
                # persistent compile-cache hit instead of full compile)
                trainer_restart = _run_worker(env_over, cfg, budget,
                                              trainer_errors)
                break
    emb = None
    emb_errors = []
    if headline is not None \
            and not os.environ.get("BENCH_SKIP_EMBEDDING"):
        for env_over, cfg, budget in _embedding_attempts(tpu_ok):
            emb = _run_worker(env_over, cfg, budget, emb_errors)
            if emb is not None:
                break
    pipe = None
    pipe_errors = []
    if headline is not None and not os.environ.get("BENCH_SKIP_PIPELINE"):
        for env_over, cfg, budget in _pipeline_attempts():
            pipe = _run_worker(env_over, cfg, budget, pipe_errors)
            if pipe is not None:
                break
    ckpt = None
    ckpt_errors = []
    if headline is not None and not os.environ.get("BENCH_SKIP_CKPT"):
        for env_over, cfg, budget in _ckpt_attempts():
            ckpt = _run_worker(env_over, cfg, budget, ckpt_errors)
            if ckpt is not None:
                break
    sharded = None
    sharded_errors = []
    if headline is not None and not os.environ.get("BENCH_SKIP_SHARDED"):
        for env_over, cfg, budget in _sharded_attempts(tpu_ok):
            sharded = _run_worker(env_over, cfg, budget, sharded_errors)
            if sharded is not None:
                break
    pp = None
    pp_errors = []
    if headline is not None and not os.environ.get("BENCH_SKIP_PP"):
        for env_over, cfg, budget in _pp_attempts(tpu_ok):
            pp = _run_worker(env_over, cfg, budget, pp_errors)
            if pp is not None:
                break
    autotune = None
    autotune_errors = []
    if headline is not None \
            and not os.environ.get("BENCH_SKIP_AUTOTUNE"):
        for env_over, cfg, budget in _autotune_attempts(tpu_ok):
            autotune = _run_worker(env_over, cfg, budget,
                                   autotune_errors)
            if autotune is not None:
                break
    serving = None
    serving_errors = []
    if headline is not None and not os.environ.get("BENCH_SKIP_SERVING"):
        for env_over, cfg, budget in _serving_attempts(tpu_ok):
            serving = _run_worker(env_over, cfg, budget, serving_errors)
            if serving is not None:
                break
    obs = None
    obs_errors = []
    if headline is not None and not os.environ.get("BENCH_SKIP_OBS"):
        for env_over, cfg, budget in _obs_attempts(tpu_ok):
            obs = _run_worker(env_over, cfg, budget, obs_errors)
            if obs is not None:
                break
    integ = None
    integ_errors = []
    if headline is not None \
            and not os.environ.get("BENCH_SKIP_INTEGRITY"):
        for env_over, cfg, budget in _integrity_attempts(tpu_ok):
            integ = _run_worker(env_over, cfg, budget, integ_errors)
            if integ is not None:
                break
    recovery = None
    recovery_errors = []
    if headline is not None \
            and not os.environ.get("BENCH_SKIP_RECOVERY"):
        recovery = bench_recovery(recovery_errors)
    data_resume = None
    data_resume_errors = []
    if headline is not None \
            and not os.environ.get("BENCH_SKIP_DATA_RESUME"):
        data_resume = bench_data_resume(data_resume_errors)
    fleet = None
    fleet_errors = []
    if headline is not None \
            and not os.environ.get("BENCH_SKIP_FLEET"):
        fleet = bench_fleet(fleet_errors)
    partition = None
    partition_errors = []
    if headline is not None \
            and not os.environ.get("BENCH_SKIP_PARTITION"):
        partition = bench_partition(partition_errors)
    if headline is None:
        print(json.dumps({
            "metric": "resnet50_train_samples_per_sec_per_chip",
            "value": 0.0, "unit": "samples/sec/chip", "vs_baseline": None,
            "tpu_probe": probe_note,
            "on_chip_unavailable": {
                "reason": probe_note,
                "fallback_backend": None,
                "numbers_are_cpu": False,
                "probe_rc": probe.get("rc"),
                "probe_stderr_tail": probe.get("stderr_tail"),
            },
            "error": "; ".join(errors)[-500:],
        }))
        return 0
    headline["tpu_probe"] = probe_note
    # structured tag when the numbers did NOT come from a TPU: the probe
    # failed (or a TPU attempt died and the CPU fallback produced the
    # metric).  Downstream readers keep the CPU numbers but must not
    # compare them against on-chip baselines.
    if not tpu_ok or headline.get("backend") == "cpu":
        headline["on_chip_unavailable"] = {
            "reason": probe_note if not tpu_ok
            else "tpu attempts failed; cpu fallback produced the metric",
            "fallback_backend": headline.get("backend", "cpu"),
            "numbers_are_cpu": headline.get("backend") == "cpu",
            # probe forensics so the next on-chip session can
            # recalibrate without re-reproducing the wedge
            "probe_rc": probe.get("rc"),
            "probe_stderr_tail": probe.get("stderr_tail"),
        }
    if bert is not None:
        headline["bert_tokens_per_sec_per_chip"] = bert["value"]
        headline["bert_mfu"] = bert.get("mfu")
        headline["bert_batch"] = bert.get("batch")
        headline["bert_seq"] = bert.get("seq")
        # attribution: which attention path and trunk produced the number
        headline["bert_attn"] = bert.get("attn")
        headline["bert_scan_layers"] = bert.get("scan_layers")
    elif bert_errors:
        headline["bert_error"] = "; ".join(bert_errors)[-300:]
    if trainer_bench is not None:
        headline["trainer_step_us"] = trainer_bench["value"]
        headline["trainer_step_us_grouped"] = \
            trainer_bench.get("grouped_us")
        headline["trainer_step_us_legacy"] = trainer_bench.get("legacy_us")
        headline["trainer_step_speedup"] = trainer_bench.get("speedup")
        headline["trainer_step_speedup_vs_grouped"] = \
            trainer_bench.get("speedup_vs_grouped")
        headline["trainer_captured_le_grouped"] = \
            trainer_bench.get("captured_le_grouped")
        headline["trainer_step_params"] = trainer_bench.get("params")
        headline["trainer_cache_hits"] = trainer_bench.get("cache_hits")
        headline["trainer_cache_misses"] = \
            trainer_bench.get("cache_misses")
        headline["trainer_first_step_ms"] = \
            trainer_bench.get("first_step_ms")
        headline["trainer_step_breakdown_us"] = \
            trainer_bench.get("breakdown_us")
        if trainer_restart is not None:
            headline["trainer_restart_first_step_ms"] = \
                trainer_restart.get("first_step_ms")
        headline["guard_overhead_us"] = \
            trainer_bench.get("guard_overhead_us")
        headline["guard_overhead_pct"] = \
            trainer_bench.get("guard_overhead_pct")
        headline["guard_ok"] = trainer_bench.get("guard_ok")
        headline["trainer_mfu"] = trainer_bench.get("mfu")
        headline["trainer_stall_share"] = trainer_bench.get("stall_share")
        # ratio gates (ISSUE 7): pass/fail on ratios the telemetry layer
        # computed, never on absolute CPU samples/sec
        gates = {
            "one_dispatch_per_step":
                trainer_bench.get("dispatches") ==
                trainer_bench.get("steps_timed")
                and bool(trainer_bench.get("steps_timed")),
            "mfu_nonnull": trainer_bench.get("mfu") is not None,
            "stall_share_le_half":
                trainer_bench.get("stall_share") is not None
                and trainer_bench["stall_share"] <= 0.5,
            "captured_le_grouped":
                bool(trainer_bench.get("captured_le_grouped")),
        }
        headline["trainer_gates"] = gates
        headline["trainer_gates_ok"] = all(gates.values())
    elif trainer_errors:
        headline["trainer_error"] = "; ".join(trainer_errors)[-300:]
    if emb is not None:
        headline["embedding_ids_per_sec"] = emb["value"]
        headline["embedding_captured_step_us"] = emb.get("captured_us")
        headline["embedding_eager_step_us"] = emb.get("eager_us")
        headline["embedding_speedup_vs_eager"] = \
            emb.get("speedup_vs_eager")
        headline["embedding_lookup_stall_share"] = \
            emb.get("lookup_stall_share")
        headline["embedding_unique_fraction"] = \
            emb.get("unique_fraction")
        # ratio gates (trainer_gates discipline): the captured sparse
        # step must not lose to its own eager oracle, and must keep the
        # one-dispatch-per-step contract
        emb_gates = {
            "sparse_captured_le_eager":
                bool(emb.get("sparse_captured_le_eager")),
            "one_dispatch_per_step":
                emb.get("dispatches") == emb.get("steps_timed")
                and bool(emb.get("steps_timed")),
        }
        headline["embedding_gates"] = emb_gates
        headline["embedding_gates_ok"] = all(emb_gates.values())
        # forced-host numbers survive only tagged, never as an on-chip
        # result (sharded_on_chip_unavailable discipline)
        if emb.get("backend") == "cpu":
            headline["embedding_on_chip_unavailable"] = {
                "reason": probe_note if not tpu_ok
                else "tpu attempts failed; cpu fallback produced the "
                     "embedding numbers",
                "fallback_backend": "cpu",
                "numbers_are_cpu": True,
            }
    elif emb_errors:
        headline["embedding_error"] = "; ".join(emb_errors)[-300:]
    if pipe is not None:
        headline["input_pipeline_imgs_per_sec"] = pipe["value"]
        headline["input_pipeline_imgs_per_sec_legacy"] = \
            pipe.get("legacy_ips")
        headline["input_pipeline_speedup"] = pipe.get("speedup")
        headline["input_pipeline_stall_share_prefetch"] = \
            pipe.get("stall_share_prefetch")
        headline["input_pipeline_stall_share_sync"] = \
            pipe.get("stall_share_sync")
    elif pipe_errors:
        headline["input_pipeline_error"] = "; ".join(pipe_errors)[-300:]
    if ckpt is not None:
        headline["ckpt_stall_us"] = ckpt["value"]
        headline["ckpt_stall_us_sync"] = ckpt.get("sync_stall_us")
        headline["ckpt_stall_speedup"] = ckpt.get("speedup")
        headline["ckpt_async_commit_ms"] = ckpt.get("async_commit_ms")
        headline["ckpt_state_mb"] = ckpt.get("state_mb")
    elif ckpt_errors:
        headline["ckpt_error"] = "; ".join(ckpt_errors)[-300:]
    if sharded is not None:
        headline["tp_step_us"] = sharded["value"]
        headline["fsdp_step_us"] = sharded.get("fsdp_step_us")
        headline["tp_device_peak_bytes"] = \
            sharded.get("tp_device_peak_bytes")
        headline["fsdp_device_peak_bytes"] = \
            sharded.get("fsdp_device_peak_bytes")
        headline["tp_collective_bytes_by_axis"] = \
            sharded.get("tp_collective_bytes_by_axis")
        headline["fsdp_collective_bytes_by_axis"] = \
            sharded.get("fsdp_collective_bytes_by_axis")
        headline["tp_mesh"] = sharded.get("tp_mesh")
        headline["fsdp_mesh"] = sharded.get("fsdp_mesh")
        # same discipline as the headline: a forced-host mesh number
        # may only survive tagged, never as an on-chip result
        if sharded.get("backend") == "cpu":
            headline["sharded_on_chip_unavailable"] = {
                "reason": probe_note if not tpu_ok
                else "tpu attempts failed; cpu fallback produced the "
                     "sharded numbers",
                "fallback_backend": "cpu",
                "numbers_are_cpu": True,
            }
    elif sharded_errors:
        headline["sharded_error"] = "; ".join(sharded_errors)[-300:]
    if pp is not None:
        headline["pp_step_us"] = pp["value"]
        headline["pp_tp_only_step_us"] = pp.get("tp_only_step_us")
        headline["pp_bubble_fraction"] = pp.get("bubble_fraction")
        headline["pp_collective_bytes_by_axis"] = \
            pp.get("pp_collective_bytes_by_axis")
        headline["pp_mesh"] = pp.get("pp_mesh")
        headline["pp_gates"] = pp.get("pp_gates")
        headline["pp_gates_ok"] = pp.get("pp_gates_ok")
        # forced-host mesh numbers survive only tagged, never as an
        # on-chip result (sharded_on_chip_unavailable discipline)
        if pp.get("backend") == "cpu":
            headline["pp_on_chip_unavailable"] = {
                "reason": probe_note if not tpu_ok
                else "tpu attempts failed; cpu fallback produced the "
                     "pipeline numbers",
                "fallback_backend": "cpu",
                "numbers_are_cpu": True,
            }
    elif pp_errors:
        headline["pp_error"] = "; ".join(pp_errors)[-300:]
    if autotune is not None:
        headline["autotune_tuned_step_us"] = autotune["value"]
        headline["autotune_default_step_us"] = autotune.get("default_us")
        headline["autotune_improvement"] = autotune.get("improvement")
        headline["autotune_tuned_mfu"] = autotune.get("tuned_mfu")
        headline["autotune_default_mfu"] = autotune.get("default_mfu")
        headline["autotune_trials"] = autotune.get("trials")
        headline["autotune_infeasible"] = autotune.get("infeasible")
        headline["autotune_winner_fingerprint"] = \
            autotune.get("winner_fingerprint")
        # ratio gates (trainer_gates discipline): the tuned config must
        # not lose to the defaults as measured by the search itself, and
        # a restart must replay from the DB without a single trial
        autotune_gates = {
            "tuned_le_default": bool(autotune.get("tuned_le_default")),
            "replay_zero_trials":
                bool(autotune.get("replay_zero_trials")),
        }
        headline["autotune_gates"] = autotune_gates
        headline["autotune_gates_ok"] = all(autotune_gates.values())
        if autotune.get("backend") == "cpu":
            headline["autotune_on_chip_unavailable"] = {
                "reason": probe_note if not tpu_ok
                else "tpu attempts failed; cpu fallback produced the "
                     "autotune numbers",
                "fallback_backend": "cpu",
                "numbers_are_cpu": True,
            }
    elif autotune_errors:
        headline["autotune_error"] = "; ".join(autotune_errors)[-300:]
    if serving is not None:
        headline["serving_p50_us"] = serving["value"]
        headline["serving_p99_us"] = serving.get("p99_us")
        headline["serving_tokens_per_sec"] = \
            serving.get("tokens_per_sec")
        headline["serving_tokens_per_sec_unbatched"] = \
            serving.get("tokens_per_sec_unbatched")
        headline["serving_batched_throughput_ratio"] = \
            serving.get("batched_ratio")
        headline["serving_clients"] = serving.get("clients")
        headline["serving_mean_padded_fraction"] = \
            serving.get("mean_padded_fraction")
        # ratio gates (same discipline as trainer_gates): batched must
        # beat unbatched at N clients, and the request path must be
        # retrace-free after warmup
        serving_gates = {
            "batched_ge_unbatched":
                serving.get("batched_ratio") is not None
                and serving["batched_ratio"] >= 1.0,
            "zero_retraces_after_warmup":
                serving.get("retraces_after_warmup") == 0,
        }
        headline["serving_gates"] = serving_gates
        headline["serving_gates_ok"] = all(serving_gates.values())
        if serving.get("backend") == "cpu":
            headline["serving_on_chip_unavailable"] = {
                "reason": probe_note if not tpu_ok
                else "tpu attempts failed; cpu fallback produced the "
                     "serving numbers",
                "fallback_backend": "cpu",
                "numbers_are_cpu": True,
            }
    elif serving_errors:
        headline["serving_error"] = "; ".join(serving_errors)[-300:]
    if obs is not None:
        headline["obs_overhead_pct"] = obs["value"]
        headline["obs_overhead_ratio"] = obs.get("obs_overhead_ratio")
        headline["obs_step_us_base"] = obs.get("obs_step_us_base")
        headline["obs_step_us_with"] = obs.get("obs_step_us_with")
        headline["obs_exporter_scrapes_ok"] = \
            obs.get("exporter_scrapes_ok")
        headline["obs_spans_total"] = obs.get("spans_total")
        headline["obs_spans_complete"] = obs.get("spans_complete")
        # ratio gates (trainer_gates discipline): the live obs plane —
        # collector tail + rollup publish + HTTP scrapes — must cost
        # under 1% of the captured step, and every served request must
        # render as ONE closed frontdoor→…→decode span tree
        obs_gates = {
            "obs_overhead_le_1pct":
                obs.get("obs_overhead_ratio") is not None
                and obs["obs_overhead_ratio"] <= 1.01,
            "spans_complete":
                bool(obs.get("spans_total"))
                and obs.get("spans_complete") == obs.get("spans_total"),
        }
        headline["obs_gates"] = obs_gates
        headline["obs_gates_ok"] = all(obs_gates.values())
        if obs.get("backend") == "cpu":
            headline["obs_on_chip_unavailable"] = {
                "reason": probe_note if not tpu_ok
                else "tpu attempts failed; cpu fallback produced the "
                     "obs numbers",
                "fallback_backend": "cpu",
                "numbers_are_cpu": True,
            }
    elif obs_errors:
        headline["obs_error"] = "; ".join(obs_errors)[-300:]
    if integ is not None:
        headline["integrity_overhead_pct"] = integ["value"]
        headline["integrity_step_us_base"] = integ.get("base_us")
        headline["integrity_step_us_with"] = integ.get("integrity_us")
        headline["integrity_attest_round_us"] = \
            integ.get("attest_round_us")
        headline["integrity_attest_amortized_pct"] = \
            integ.get("attest_amortized_pct")
        headline["sdc_detect_ms"] = integ.get("sdc_detect_ms")
        headline["sdc_detect_to_recovery_ms"] = \
            integ.get("sdc_detect_to_recovery_ms")
        # ratio gates (trainer_gates discipline): the always-on
        # fingerprint program must cost under 1% of the plain captured
        # step, and the injected flip must be named, classified and
        # survived end to end
        integrity_gates = {
            "integrity_overhead_le_1pct":
                integ.get("overhead_ratio") is not None
                and integ["overhead_ratio"] <= 1.01,
            "sdc_detected_names_rank":
                integ.get("sdc_rank_named") is not None
                and integ.get("sdc_rank_named") ==
                integ.get("sdc_injected_rank"),
            "replay_kind_memory": integ.get("sdc_kind") == "memory",
            "reattest_clean_after_restore":
                bool(integ.get("sdc_reattest_ok")),
        }
        headline["integrity_gates"] = integrity_gates
        headline["integrity_gates_ok"] = all(integrity_gates.values())
        if integ.get("backend") == "cpu":
            headline["integrity_on_chip_unavailable"] = {
                "reason": probe_note if not tpu_ok
                else "tpu attempts failed; cpu fallback produced the "
                     "integrity numbers",
                "fallback_backend": "cpu",
                "numbers_are_cpu": True,
            }
    elif integ_errors:
        headline["integrity_error"] = "; ".join(integ_errors)[-300:]
    if recovery:
        headline.update(recovery)
        e_ms = headline.get("elastic_recovery_ms")
        s_ms = headline.get("sdc_detect_to_recovery_ms")
        if e_ms and s_ms is not None:
            # SDC path vs the PR-8 elastic floor: detection is an
            # attestation vote, not a heartbeat timeout, so it should
            # undercut the elastic number by a wide margin
            headline["sdc_recovery_vs_elastic"] = round(s_ms / e_ms, 3)
            headline["sdc_recovery_lt_elastic"] = s_ms < e_ms
    if recovery_errors:
        headline["recovery_error"] = "; ".join(recovery_errors)[-300:]
    if data_resume:
        headline.update(data_resume)
    if data_resume_errors:
        headline["data_resume_error"] = \
            "; ".join(data_resume_errors)[-300:]
    if fleet:
        headline.update(fleet)
    if fleet_errors:
        headline["fleet_error"] = "; ".join(fleet_errors)[-300:]
    if partition:
        headline.update(partition)
        p_ms = headline.get("partition_majority_continue_ms")
        e_ms = headline.get("elastic_recovery_ms")
        if p_ms and e_ms:
            # majority-side continue vs the plain single-death elastic
            # floor: the quorum gate rides the same detection window,
            # so the ratio is the price of split-brain safety
            headline["partition_vs_elastic"] = round(p_ms / e_ms, 3)
    if partition_errors:
        headline["partition_error"] = \
            "; ".join(partition_errors)[-300:]
    _seal_trajectory_point(headline)
    print(json.dumps(headline))
    return 0


def _seal_trajectory_point(headline):
    """Refuse an untagged CPU-fallback trajectory point (ROADMAP "Perf
    truth"): a number measured on the CPU fallback may only survive when
    it carries the structured ``on_chip_unavailable`` record with
    ``numbers_are_cpu: true`` and a reason — anything else is zeroed so
    a silent CPU proxy can never be read as an on-chip result."""
    if headline.get("backend") != "cpu":
        return
    tag = headline.get("on_chip_unavailable")
    if isinstance(tag, dict) and tag.get("numbers_are_cpu") is True \
            and tag.get("reason"):
        return
    headline["refused_cpu_point"] = True
    headline["value"] = 0.0
    prior = headline.get("error")
    msg = ("cpu-backend measurement without a complete "
           "on_chip_unavailable tag: trajectory point refused")
    headline["error"] = f"{prior}; {msg}" if prior else msg


# -- recovery gang worker (jax-free) -------------------------------------------

def _import_elastic():
    """Import the elastic-recovery stack WITHOUT executing the package
    __init__ (which pulls the jax array frontend in): install a bare
    package shell for ``mxnet_tpu`` and load the submodules — they only
    lazy-import jax, so a numpy-state gang stays jax-free and its
    process spawn stays cheap."""
    import importlib
    import types

    root = os.path.dirname(os.path.abspath(__file__))
    if "mxnet_tpu" not in sys.modules:
        pkg = types.ModuleType("mxnet_tpu")
        pkg.__path__ = [os.path.join(root, "mxnet_tpu")]
        sys.modules["mxnet_tpu"] = pkg
    res = importlib.import_module("mxnet_tpu.resilience")
    dist = importlib.import_module("mxnet_tpu.distributed")
    return res, dist


def _import_batcher():
    """Same bare-shell trick one level down: the serving batcher is
    stdlib-only, but the ``mxnet_tpu.serving`` __init__ drags the jax
    engine in — install a shell for the subpackage too and import the
    batcher module directly."""
    import importlib
    import types

    _import_elastic()                    # installs the mxnet_tpu shell
    root = os.path.dirname(os.path.abspath(__file__))
    if "mxnet_tpu.serving" not in sys.modules:
        spkg = types.ModuleType("mxnet_tpu.serving")
        spkg.__path__ = [os.path.join(root, "mxnet_tpu", "serving")]
        sys.modules["mxnet_tpu.serving"] = spkg
    return importlib.import_module("mxnet_tpu.serving.batcher")


def gang_worker(cfg):
    """One rank of the hermetic recovery-bench gang.

    State is a replicated numpy vector with a deterministic
    rank-independent update, so any peer's shard (or any rank's disk
    checkpoint) is a full restore — the bench measures recovery
    latency, not resharding math (the elastic tests cover that).
    """
    import numpy as np

    res, dist = _import_elastic()
    rank, world = cfg["rank"], cfg["world"]
    steps, snap_every = cfg["steps"], cfg["snap_every"]
    step_s = cfg["step_ms"] / 1e3
    state = {"w": np.full(cfg["n"], 1.0, np.float64), "step": 0}

    def work(step):
        state["w"] *= 0.9999
        state["step"] = step
        time.sleep(step_s)

    recov = {"ms": None, "source": None, "disk_restores": 0}
    if cfg["mode"] == "elastic":
        kv = dist.FileKV(cfg["gang_dir"])
        ck = res.LocalCheckpointer(
            os.path.join(cfg["dir"], f"rank{rank}"))
        gang = res.ElasticGang(rank, world, kv=kv, checkpointer=ck,
                               peer_snap_every=snap_every)
        gang.start()
        step = 0
        while step < steps:
            try:
                gang.step_tick(step, state=state)
            except res.RankFailure as rf:
                info = gang.recover(rf)
                state = (next(iter(info.shards.values()))
                         if info.shards else info.full_state)
                step = info.snap_step
                recov["ms"] = info.recovery_ms
                recov["source"] = info.source
                if info.source == "disk":
                    recov["disk_restores"] += 1
                continue
            except res.GangEvicted:
                sys.exit(0)
            work(step)
            step += 1
        gang.stop()
    else:                                    # full-restart mode
        ck = res.LocalCheckpointer(
            os.path.join(cfg["dir"], f"rank{rank}"))
        start = res.resume_latest(ck, state.update)
        step = start
        resumed = start > 0
        while step < steps:
            res.maybe_kill_rank(rank, step)
            work(step)
            if resumed:
                # restart-latency marker: first step COMPLETED after
                # the disk resume
                with open(f"{cfg['marker']}.rank{rank}", "w") as f:
                    f.write(str(step))
                resumed = False
            step += 1
            if step % snap_every == 0:
                ck.save(step, state)
    print(json.dumps({"rank": rank, "final_step": step,
                      "loss": float(state["w"][0]),
                      "recovery_ms": recov["ms"],
                      "recovery_source": recov["source"],
                      "disk_restores": recov["disk_restores"]}))


# -- worker-side helpers -------------------------------------------------------

def _readback(nd):
    """Force a host readback — a sync on any runtime (ROADMAP S1 keeps
    or drops it on chip_smoke.py's sync result, docs/perf.md)."""
    import numpy as np

    arr = getattr(nd, "_data", nd)
    return np.asarray(arr)


def _timed_loop(step, steps, per_step_readback=False):
    """Time `steps` invocations of `step()`; always readback-terminated."""
    out = None
    t0 = time.perf_counter()
    if per_step_readback:
        for _ in range(steps):
            out = _readback(step())
    else:
        for _ in range(steps):
            out = step()
        out = _readback(out)
    dt = time.perf_counter() - t0
    return dt, out


def _measure(step, steps, flops_per_step, peak):
    """warmup + timed loop + MFU sanity gate (remeasure on violation)."""
    # warmup / compile; readback ends each warmup step so the first timed
    # step starts from a drained device queue
    _readback(step())
    _readback(step())
    dt, out = _timed_loop(step, steps)
    mfu = (flops_per_step * steps / dt / peak) if peak else None
    gated = False
    if mfu is not None and mfu > _MFU_GATE:
        # impossible (or suspiciously perfect) number: the async queue
        # must have leaked past the readback — retime strictly
        gated = True
        dt, out = _timed_loop(step, steps, per_step_readback=True)
        mfu = flops_per_step * steps / dt / peak
    return dt, mfu, gated, out


def _calibrate(peak):
    """Time a known bf16 matmul chain with readback; returns TFLOP/s.

    An in-run reference point: if the model MFU were ever to exceed
    calib/peak something is wrong with the timing, not the model.  The
    chain reduces to a SCALAR before readback and runs enough FLOPs
    (~17.6 TFLOP) that the readback's round trip (~30ms in round 3) is
    noise, not signal.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    n, chain, iters = 8192, 8, 2

    @jax.jit
    def f(a, b):
        for _ in range(chain):
            a = a @ b
        return jnp.sum(a.astype(jnp.float32))

    a = jnp.asarray(np.random.RandomState(0).standard_normal((n, n)),
                    jnp.bfloat16)
    b = jnp.asarray(np.random.RandomState(1).standard_normal((n, n)),
                    jnp.bfloat16)
    _readback(f(a, b))  # compile + drain
    t0 = time.perf_counter()
    out = [f(a, b) for _ in range(iters)][-1]
    np.asarray(out)
    dt = time.perf_counter() - t0
    tflops = iters * chain * 2 * n ** 3 / dt / 1e12
    if peak and tflops > peak / 1e12:
        # still raced dispatch somehow; retime strictly per-call
        t0 = time.perf_counter()
        for _ in range(iters):
            np.asarray(f(b, a))
        dt = time.perf_counter() - t0
        tflops = iters * chain * 2 * n ** 3 / dt / 1e12
    return round(tflops, 1)


def _peak_for(device):
    kind = getattr(device, "device_kind", "") or ""
    for key, val in _PEAK_FLOPS:
        if key in kind.lower():
            return kind, val
    return kind, None


def worker(cfg):
    import jax

    # backend init guard: one retry, then a distinct rc for the parent
    devices = None
    for attempt in range(2):
        try:
            devices = jax.devices()
            break
        except RuntimeError as e:
            sys.stderr.write(f"backend init failed ({e}); "
                             f"attempt {attempt}\n")
            time.sleep(8)
    if devices is None:
        sys.exit(3)
    if cfg["backend"] != "cpu" and devices[0].platform == "cpu":
        # jax fell back to CPU on a chip-less host: don't burn the TPU
        # attempt's budget — bail so the parent moves to the CPU config
        sys.stderr.write("requested TPU but only CPU available\n")
        sys.exit(4)

    from mxnet_tpu import engine

    engine.ensure_compile_cache()

    if cfg["model"] == "bert":
        bench_bert(cfg, devices)
    elif cfg["model"] == "trainer_step":
        bench_trainer(cfg, devices)
    elif cfg["model"] == "input_pipeline":
        bench_input_pipeline(cfg, devices)
    elif cfg["model"] == "ckpt":
        bench_ckpt(cfg, devices)
    elif cfg["model"] == "embedding":
        bench_embedding(cfg, devices)
    elif cfg["model"] == "sharded_step":
        bench_sharded(cfg, devices)
    elif cfg["model"] == "pp_step":
        bench_pp(cfg, devices)
    elif cfg["model"] == "autotune":
        bench_autotune(cfg, devices)
    elif cfg["model"] == "serving":
        bench_serving(cfg, devices)
    elif cfg["model"] == "obs":
        bench_obs(cfg, devices)
    elif cfg["model"] == "integrity":
        bench_integrity(cfg, devices)
    else:
        bench_resnet(cfg, devices)


def bench_resnet(cfg, devices):
    import numpy as np

    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel
    from mxnet_tpu.gluon.model_zoo import vision

    n_chips = max(1, len(devices))
    batch_size, image_size, steps = cfg["batch"], cfg["image"], cfg["steps"]
    layout = cfg.get("layout", "NCHW")

    net = vision.resnet50_v1(classes=1000, layout=layout)
    net.initialize(init=mx.init.Xavier())
    net.cast("bfloat16")

    mesh = parallel.data_parallel_mesh(n_chips)
    trainer = parallel.ShardedTrainer(
        net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
        {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}, mesh=mesh)

    rng = np.random.RandomState(0)
    xshape = ((batch_size, 3, image_size, image_size) if layout == "NCHW"
              else (batch_size, image_size, image_size, 3))
    x = jnp.asarray(rng.standard_normal(xshape), dtype=jnp.bfloat16)
    y = jnp.asarray(rng.randint(0, 1000, batch_size).astype("float32"))

    kind, peak = _peak_for(devices[0])
    calib_tflops = (_calibrate(peak)
                    if devices[0].platform != "cpu" else None)

    flops_per_step = (_RESNET50_TRAIN_FLOPS_224
                      * (image_size / 224.0) ** 2) * batch_size
    # flops_per_step covers the GLOBAL batch: peak scales with chips so
    # mfu stays per-chip utilization
    total_peak = peak * n_chips if peak else None

    dt, mfu, gated, loss_val = _measure(
        lambda: trainer.step(x, y), steps, flops_per_step, total_peak)

    loss = float(np.asarray(loss_val, dtype=np.float32))
    if not np.isfinite(loss):
        sys.stderr.write(f"non-finite loss {loss}\n")
        sys.exit(5)

    # data-stall share: the SAME compiled step driven by a synthetic host
    # pipeline (batch-vectorized normalize + bf16 cast per batch — real
    # loader-shaped host work), with device prefetch on vs off.  Stall =
    # time blocked waiting for the next batch / wall time.
    from mxnet_tpu import image as image_mod
    from mxnet_tpu.gluon.data.prefetcher import DevicePrefetcher

    u8 = rng.randint(0, 256, (batch_size, image_size, image_size, 3),
                     dtype=np.uint8)
    _mean = np.zeros((3, 1, 1), np.float32)
    _std = np.ones((3, 1, 1), np.float32)
    nst = max(4, steps // 2)

    def host_batches(nb):
        for _ in range(nb):
            xb = image_mod.normalize_flip_batch_np(
                u8, None, 1.0 / 255, _mean, _std)
            if layout != "NCHW":
                xb = np.ascontiguousarray(xb.transpose(0, 2, 3, 1))
            yield xb.astype(jnp.bfloat16), y

    def stall_share(depth):
        it = iter(DevicePrefetcher(host_batches(nst), depth=depth,
                                   mesh=mesh))
        stall = 0.0
        t0 = time.perf_counter()
        for _ in range(nst):
            ts = time.perf_counter()
            xb, yb = next(it)
            stall += time.perf_counter() - ts
            _readback(trainer.step(xb, yb))
        return round(stall / (time.perf_counter() - t0), 3)

    stall_prefetch = stall_share(2)
    stall_sync = stall_share(0)

    per_chip = batch_size * steps / dt / n_chips
    print(json.dumps({
        "metric": "resnet50_train_samples_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "samples/sec/chip",
        "vs_baseline": None,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "mfu_gated_remeasure": gated,
        "calib_tflops": calib_tflops,
        "loss": round(loss, 4),
        "data_stall_share": stall_prefetch,
        "data_stall_share_sync": stall_sync,
        "device_kind": kind,
        "backend": devices[0].platform,
        "batch": batch_size,
        "image": cfg["image"],
        "layout": layout,
    }))


def _epoch_stats(loader, step_fn=None):
    """Iterate one epoch; return (imgs/sec, data-stall share).

    Stall = time blocked in ``next()`` waiting for a batch; with a
    step_fn in the loop and prefetch working, the loader hides its host
    work behind the step and the share drops toward zero."""
    import numpy as np  # noqa: F401  (readback helper)

    it = iter(loader)
    imgs, stall, last = 0, 0.0, None
    t0 = time.perf_counter()
    while True:
        ts = time.perf_counter()
        try:
            batch = next(it)
        except StopIteration:
            break
        stall += time.perf_counter() - ts
        last = batch
        imgs += int(batch[0].shape[0])
        if step_fn is not None:
            step_fn(batch)
    if last is not None:
        _readback(last[0])
    total = time.perf_counter() - t0
    return imgs / total, stall / total


def bench_input_pipeline(cfg, devices):
    """input_pipeline_imgs_per_sec: end-to-end loader throughput —
    decode + augment(crop) + collate + device_put — on synthetic
    in-memory JPEGs.  'new' is the single-copy collation DataLoader
    wrapped in DevicePrefetcher; 'legacy' is the same loader driven by
    the pre-optimization batchify (one jnp.asarray per SAMPLE plus a
    device-side stack), same worker count, so the delta isolates the
    transport/collation change.  Stall shares come from a loop with a
    small jitted step in it, prefetch on vs off."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from mxnet_tpu import image as image_mod
    from mxnet_tpu.gluon.data import DataLoader, DevicePrefetcher
    from mxnet_tpu.gluon.data.dataset import Dataset
    from mxnet_tpu.ndarray.ndarray import _from_jax

    n, batch = cfg["n"], cfg["batch"]
    size, workers = cfg["image"], cfg["workers"]

    rng = np.random.RandomState(0)
    n_unique = 32
    payloads = [
        image_mod.imencode(
            rng.randint(0, 256, (size + 8, size + 8, 3))
            .astype(np.uint8), quality=85, img_fmt=".jpg")
        for _ in range(n_unique)]

    class _JpegDataset(Dataset):
        def __len__(self):
            return n

        def __getitem__(self, i):
            arr = image_mod.imdecode_np(payloads[i % n_unique])
            arr = image_mod.center_crop_np(arr, (size, size))
            return arr, np.float32(i % 10)

    ds = _JpegDataset()

    def legacy_batchify(samples):
        cols = list(zip(*samples))
        return [_from_jax(jnp.stack([jnp.asarray(s) for s in col]))
                for col in cols]

    legacy = DataLoader(ds, batch, num_workers=workers,
                        batchify_fn=legacy_batchify)
    new = DataLoader(ds, batch, num_workers=workers)
    prefetched = DevicePrefetcher(new, depth=2)

    @jax.jit
    def _compute(a):
        return (a.astype(jnp.float32) ** 2).sum()

    def step_fn(b):
        _readback(_compute(getattr(b[0], "_data", b[0])))

    # throughput: warm epoch (jit/stack compile, PIL init), then timed
    _epoch_stats(legacy)
    legacy_ips, _ = _epoch_stats(legacy)
    _epoch_stats(prefetched)
    new_ips, _ = _epoch_stats(prefetched)
    # stall share with a step in the loop: prefetch on vs off
    _, stall_pf = _epoch_stats(prefetched, step_fn)
    _, stall_sync = _epoch_stats(DevicePrefetcher(new, depth=0), step_fn)

    print(json.dumps({
        "metric": "input_pipeline_imgs_per_sec",
        "value": round(new_ips, 1),
        "unit": "imgs/sec",
        "vs_baseline": None,
        "legacy_ips": round(legacy_ips, 1),
        "speedup": round(new_ips / legacy_ips, 2) if legacy_ips else None,
        "stall_share_prefetch": round(stall_pf, 3),
        "stall_share_sync": round(stall_sync, 3),
        "n": n, "batch": batch, "image": size, "workers": workers,
        "backend": devices[0].platform,
    }))


def bench_ckpt(cfg, devices):
    """ckpt_stall_us: train-thread stall per checkpoint save() — how long
    ``save()`` blocks the caller before training can continue.  'async'
    is the native AsyncCheckpointer (copy-on-snapshot, then a background
    writer serializes/fsyncs/commits); 'sync' is the SAME engine with
    ``async_save=False`` (the whole pickle+fsync+commit inline).  Same
    ~cfg['mb'] MB state and directory layout for both, so the delta is
    exactly the work moved off the critical path.  ``async_commit_ms``
    (save->wait latency) is reported for context: the stall win is only
    real while the commit also finishes well inside a checkpoint
    interval."""
    import shutil
    import tempfile

    import numpy as np

    from mxnet_tpu import checkpoint

    mb, reps = cfg["mb"], cfg["reps"]
    n_arr = 8
    per = max(1, (mb << 20) // (4 * n_arr))
    state = {"params": [np.random.RandomState(i).rand(per)
                        .astype(np.float32) for i in range(n_arr)],
             "step": 0}

    def run(async_save):
        d = tempfile.mkdtemp(prefix="bench_ckpt_")
        ck = checkpoint.AsyncCheckpointer(
            d, max_to_keep=2, async_save=async_save, rank=0,
            world_size=1)
        ck.save(0, state)    # warm: page cache, allocator, thread path
        ck.wait()
        stalls, commit = [], 0.0
        for r in range(1, reps + 1):
            t0 = time.perf_counter()
            ck.save(r, state)
            stalls.append(time.perf_counter() - t0)
            t1 = time.perf_counter()
            ck.wait()
            commit += time.perf_counter() - t1
        shutil.rmtree(d, ignore_errors=True)
        return (1e6 * sorted(stalls)[len(stalls) // 2],   # median us
                1e3 * commit / reps)                      # mean ms

    async_us, commit_ms = run(True)
    sync_us, _ = run(False)

    print(json.dumps({
        "metric": "ckpt_stall_us",
        "value": round(async_us, 1),
        "unit": "us/save",
        "vs_baseline": None,
        "sync_stall_us": round(sync_us, 1),
        "speedup": round(sync_us / async_us, 2) if async_us else None,
        "async_commit_ms": round(commit_ms, 1),
        "state_mb": mb, "reps": reps,
        "backend": devices[0].platform,
    }))


def bench_trainer(cfg, devices):
    """trainer_step_us: FULL imperative train-step latency — forward +
    loss + backward + health guard + optimizer update — on a
    many-small-parameter model (~cfg['params'] tensors), three ways:

    - captured (the reported value): the whole step runs as ONE donated
      jit program (gluon/captured.py) with a single deferred health
      readback per step;
    - grouped: MXTPU_CAPTURED_STEP=0 — eager per-op dispatch chain with
      the fused GroupedUpdater update (the bitwise oracle the captured
      program is checked against);
    - legacy: additionally MXTPU_FUSED_STEP=0 — one eager op chain per
      parameter inside the update loop (fewer steps; slow on purpose).

    Tensors are tiny on purpose: the metric is dispatch/host overhead,
    not FLOPs.  Also reported: first_step_ms (model built → first loss
    readback, i.e. trace + compile + dispatch — the orchestrator reruns
    this bench with the same JAX_COMPILATION_CACHE_DIR to turn it into a
    restart-to-first-step number), captured-cache hit/miss + retrace
    counts, and a per-step breakdown (data staging / host prep /
    dispatch / guard readback / collective / other) plus MFU and data
    stall share, all sourced from the telemetry StepStats records the
    timed loop emits (mxnet_tpu/telemetry.py)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, telemetry
    from mxnet_tpu.gluon import captured, nn

    n_params, steps = cfg["params"], cfg["steps"]
    n_layers = max(1, n_params // 2)  # Dense = weight + bias

    net = nn.HybridSequential(prefix="bench_")
    with net.name_scope():
        for _ in range(n_layers):
            net.add(nn.Dense(32, in_units=32, flatten=False))
    net.initialize(init=mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-3})

    def loss_fn(out):
        return (out ** 2).sum()

    x = mx.nd.array(np.random.RandomState(0)
                    .standard_normal((8, 32)).astype("float32"))

    def step():
        return trainer.train_step(net, loss_fn, x, batch_size=8)

    t0 = time.perf_counter()
    _readback(step())
    first_step_ms = (time.perf_counter() - t0) * 1e3

    _readback(step())
    captured.reset_counters()
    telemetry.reset()
    dt, _ = _timed_loop(step, steps, per_step_readback=True)
    captured_us = dt / steps * 1e6
    stats = captured.cache_stats()
    traces = captured.trace_count()
    dispatches = captured.dispatch_count()

    # breakdown / MFU / stall share from the telemetry StepStats records
    # the timed loop just emitted — the always-on accounting IS the
    # bench's source now, not a separately-profiled segment
    recs = [r for r in telemetry.recent_steps()
            if r.get("path") == "captured"][-steps:]

    def _mean(key, sub=None):
        vals = [(r[key].get(sub) if sub else r.get(key)) for r in recs]
        vals = [v for v in vals if v is not None]
        return sum(vals) / len(vals) if vals else None

    breakdown = mfu = stall_share = None
    skipped = 0
    if recs:
        breakdown = {
            "data_stall_us": round(_mean("breakdown_us", "data"), 1),
            "host_prep_us": round(_mean("breakdown_us", "host_prep"), 1),
            "dispatch_us": round(_mean("breakdown_us", "dispatch"), 1),
            "readback_us": round(_mean("breakdown_us", "readback"), 1),
            "collective_us": round(_mean("breakdown_us", "collective"),
                                   1),
            "other_us": round(_mean("breakdown_us", "other"), 1),
        }
        m = _mean("mfu")
        mfu = round(m, 6) if m is not None else None
        stall_share = round(_mean("shares", "data"), 3)
        skipped = sum(1 for r in recs if r.get("skipped"))

    # guard_overhead_us: health guard on (captured_us above paid for
    # it) vs MXTPU_GRAD_GUARD=0 — a different capture signature, so the
    # warmup steps absorb the retrace.  Target <5% (guard_ok;
    # informational on CPU where dispatch overhead dominates).
    os.environ["MXTPU_GRAD_GUARD"] = "0"
    try:
        _readback(step())
        _readback(step())
        dt3, _ = _timed_loop(step, steps, per_step_readback=True)
        noguard_us = dt3 / steps * 1e6
    finally:
        os.environ.pop("MXTPU_GRAD_GUARD", None)
    guard_overhead_us = captured_us - noguard_us
    guard_overhead_pct = guard_overhead_us / noguard_us * 100 \
        if noguard_us else None

    # grouped eager oracle, same process (the flag is read per step)
    os.environ["MXTPU_CAPTURED_STEP"] = "0"
    try:
        _readback(step())
        _readback(step())
        dt2, _ = _timed_loop(step, steps, per_step_readback=True)
        grouped_us = dt2 / steps * 1e6

        # legacy per-parameter update loop under the eager step
        os.environ["MXTPU_FUSED_STEP"] = "0"
        try:
            _readback(step())
            legacy_steps = max(3, steps // 5)
            dt4, _ = _timed_loop(step, legacy_steps,
                                 per_step_readback=True)
            legacy_us = dt4 / legacy_steps * 1e6
        finally:
            os.environ.pop("MXTPU_FUSED_STEP", None)
    finally:
        os.environ.pop("MXTPU_CAPTURED_STEP", None)

    actual = sum(1 for p in net.collect_params().values()
                 if p.grad_req != "null")
    print(json.dumps({
        "metric": "trainer_step_us",
        "value": round(captured_us, 1),
        "unit": "us/step",
        "vs_baseline": None,
        "grouped_us": round(grouped_us, 1),
        "legacy_us": round(legacy_us, 1),
        "speedup": round(legacy_us / captured_us, 2)
        if captured_us else None,
        "speedup_vs_grouped": round(grouped_us / captured_us, 2)
        if captured_us else None,
        "captured_le_grouped": captured_us <= grouped_us,
        "first_step_ms": round(first_step_ms, 1),
        "cache_hits": stats["hits"],
        "cache_misses": stats["misses"],
        "traces": traces,
        "dispatches": dispatches,
        "breakdown_us": breakdown,
        "mfu": mfu,
        "stall_share": stall_share,
        "steps_timed": len(recs),
        "skipped_steps": skipped,
        "guard_overhead_us": round(guard_overhead_us, 1),
        "guard_overhead_pct": round(guard_overhead_pct, 1)
        if guard_overhead_pct is not None else None,
        "guard_ok": guard_overhead_pct is not None
        and guard_overhead_pct < 5.0,
        "params": actual,
        "batch": n_params,
        "backend": devices[0].platform,
    }))


def bench_embedding(cfg, devices):
    """embeddings_per_sec: the recommender workload — a row-sparse
    `ShardedEmbedding` table + dense head trained end to end, two ways
    on the same model:

    - captured (the reported value): host unique/inverse id prep, the
      in-program padded gather, segment-sum scatter-add row update —
      one dispatch + one readback per step (gluon/captured.py +
      embedding/prep.py);
    - eager (MXTPU_SPARSE_CAPTURED=0): the RowSparseNDArray op-by-op
      oracle the captured program is bitwise-checked against
      (tests/test_embedding.py).

    Ids are zipf-skewed (hot head + long tail, like real id traffic).
    Also reported: lookup-stall share (host id-prep time / step time,
    from the schema-v6 ``lookup_us`` StepStats field), the mean
    ``unique_fraction``, and the ``sparse_captured_le_eager`` ratio
    gate — a ratio on the same box, so meaningful on any backend."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import embedding, gluon, telemetry
    from mxnet_tpu.gluon import captured, nn

    vocab, dim = cfg["vocab"], cfg["dim"]
    batch, steps = cfg["batch"], cfg["steps"]

    net = nn.HybridSequential(prefix="benchemb_")
    with net.name_scope():
        net.add(embedding.ShardedEmbedding(vocab, dim),
                nn.Dense(1, in_units=dim, flatten=False))
    net.initialize(init=mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-3})

    def loss_fn(out):
        return (out ** 2).sum()

    rng = np.random.RandomState(0)
    ids = rng.zipf(1.3, size=(steps + 8, batch)) % vocab
    xs = [mx.nd.array(b.astype("float32")) for b in ids]
    cursor = [0]

    def step():
        x = xs[cursor[0] % len(xs)]
        cursor[0] += 1
        return trainer.train_step(net, loss_fn, x, batch_size=batch)

    # warmup: trace + compile every unique-count bucket the id stream
    # hits (pow-2 buckets, so a handful at most)
    for _ in range(4):
        _readback(step())
    captured.reset_counters()
    telemetry.reset()
    dt, _ = _timed_loop(step, steps, per_step_readback=True)
    captured_us = dt / steps * 1e6
    embeddings_per_sec = batch * steps / dt
    stats = captured.cache_stats()
    traces = captured.trace_count()
    dispatches = captured.dispatch_count()

    recs = [r for r in telemetry.recent_steps()
            if r.get("path") == "captured"][-steps:]

    def _mean(key):
        vals = [r.get(key) for r in recs]
        vals = [v for v in vals if v is not None]
        return sum(vals) / len(vals) if vals else None

    lookup_us = _mean("lookup_us")
    unique_fraction = _mean("unique_fraction")
    lookup_stall_share = lookup_us / captured_us \
        if lookup_us is not None and captured_us else None

    # eager row-sparse oracle, same process (the flag is read per step)
    os.environ["MXTPU_SPARSE_CAPTURED"] = "0"
    try:
        _readback(step())
        _readback(step())
        dt2, _ = _timed_loop(step, steps, per_step_readback=True)
        eager_us = dt2 / steps * 1e6
    finally:
        os.environ.pop("MXTPU_SPARSE_CAPTURED", None)

    print(json.dumps({
        "metric": "embeddings_per_sec",
        "value": round(embeddings_per_sec, 1),
        "unit": "ids/sec",
        "vs_baseline": None,
        "captured_us": round(captured_us, 1),
        "eager_us": round(eager_us, 1),
        "speedup_vs_eager": round(eager_us / captured_us, 2)
        if captured_us else None,
        "sparse_captured_le_eager": captured_us <= eager_us,
        "lookup_us": round(lookup_us, 1)
        if lookup_us is not None else None,
        "lookup_stall_share": round(lookup_stall_share, 4)
        if lookup_stall_share is not None else None,
        "unique_fraction": round(unique_fraction, 4)
        if unique_fraction is not None else None,
        "cache_hits": stats["hits"],
        "cache_misses": stats["misses"],
        "traces": traces,
        "dispatches": dispatches,
        "steps_timed": len(recs),
        "vocab": vocab, "dim": dim, "batch": batch,
        "backend": devices[0].platform,
    }))


def bench_integrity(cfg, devices):
    """integrity_overhead_pct: steady-state cost of the SDC integrity
    plane (mxnet_tpu/integrity.py) on the captured train step, three
    timings on the same model:

    - base_us: MXTPU_INTEGRITY off — the plain captured step;
    - integrity_us (the reported ratio): fingerprint program compiled
      in (MXTPU_INTEGRITY=1) but no attestation due inside the timed
      window — the per-step tax EVERY step pays for the lax.cond'd
      fingerprint branch plus the extra (2,)uint32 word riding the
      step's single readback.  Gate: <=1% of base
      (integrity_overhead_le_1pct);
    - attest_round_us: marginal host cost of one attestation round
      (ledger append + KV publish + vote), attributed by re-timing
      with rounds firing every cfg['every'] steps — same compiled
      program, the attest flag is a traced scalar — and dividing the
      delta by the rounds observed; also reported amortized at the
      default MXTPU_INTEGRITY_EVERY=50 cadence.

    Also measured, host-side in the same process: detection-to-recovery
    for an injected single-bit flip (_integrity_sdc_scenario) — the
    orchestrator compares sdc_detect_to_recovery_ms against the PR-8
    elastic_recovery_ms floor."""
    import shutil
    import tempfile

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import distributed, gluon, integrity
    from mxnet_tpu.gluon import nn

    n_params, steps, every = cfg["params"], cfg["steps"], cfg["every"]
    n_layers = max(1, n_params // 2)

    net = nn.HybridSequential(prefix="bench_integ_")
    with net.name_scope():
        for _ in range(n_layers):
            net.add(nn.Dense(32, in_units=32, flatten=False))
    net.initialize(init=mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 1e-3})

    def loss_fn(out):
        return (out ** 2).sum()

    x = mx.nd.array(np.random.RandomState(0)
                    .standard_normal((8, 32)).astype("float32"))

    def step():
        return trainer.train_step(net, loss_fn, x, batch_size=8)

    base = tempfile.mkdtemp(prefix="bench_integrity_")
    try:
        # phase 1: integrity off
        _readback(step())
        _readback(step())
        dt, _ = _timed_loop(step, steps, per_step_readback=True)
        base_us = dt / steps * 1e6

        # phase 2: fingerprint program on, no round due in the window —
        # a different capture signature, so the warmup absorbs the
        # retrace
        os.environ["MXTPU_INTEGRITY"] = "1"
        os.environ["MXTPU_INTEGRITY_LEDGER"] = os.path.join(
            base, "ledger.jsonl")
        integrity.reset()
        kv = distributed.FileKV(os.path.join(base, "kv"))
        plane = integrity.IntegrityPlane(rank=0, world=1, kv=kv,
                                         every=10 ** 9, run="bench")
        trainer.attach_integrity(plane)
        try:
            _readback(step())
            _readback(step())
            dt2, _ = _timed_loop(step, steps, per_step_readback=True)
            integrity_us = dt2 / steps * 1e6

            # phase 3: rounds actually firing every cfg['every'] steps
            # — warm through one full interval so the attest-step
            # specialization's one-time trace+compile lands outside the
            # timed window
            plane.every = max(1, int(every))
            for _ in range(plane.every):
                _readback(step())
            before = plane.attestations
            dt3, _ = _timed_loop(step, steps, per_step_readback=True)
            rounds = plane.attestations - before
            with_attest_us = dt3 / steps * 1e6
        finally:
            trainer.attach_integrity(None)
            os.environ.pop("MXTPU_INTEGRITY", None)
            os.environ.pop("MXTPU_INTEGRITY_LEDGER", None)
            integrity.reset()

        overhead_pct = (integrity_us - base_us) / base_us * 100 \
            if base_us else None
        overhead_ratio = integrity_us / base_us if base_us else None
        attest_round_us = (dt3 - dt2) / rounds * 1e6 if rounds else None
        attest_amortized_pct = \
            attest_round_us / 50 / base_us * 100 \
            if attest_round_us is not None and base_us else None

        sdc = _integrity_sdc_scenario(np, distributed, integrity,
                                      os.path.join(base, "sdc"))
    finally:
        shutil.rmtree(base, ignore_errors=True)

    out = {
        "metric": "integrity_overhead_pct",
        "value": round(overhead_pct, 2)
        if overhead_pct is not None else None,
        "unit": "%",
        "vs_baseline": None,
        "base_us": round(base_us, 1),
        "integrity_us": round(integrity_us, 1),
        "with_attest_us": round(with_attest_us, 1),
        "overhead_ratio": round(overhead_ratio, 4)
        if overhead_ratio is not None else None,
        "attest_rounds": rounds,
        "attest_round_us": round(attest_round_us, 1)
        if attest_round_us is not None else None,
        "attest_amortized_pct": round(attest_amortized_pct, 3)
        if attest_amortized_pct is not None else None,
        "backend": devices[0].platform,
    }
    out.update(sdc)
    print(json.dumps(out))


def _integrity_sdc_scenario(np, distributed, integrity, root):
    """Detection-to-recovery micro-scenario, pure host work: three
    replica planes vote over one FileKV; rank 1's state takes a
    single-bit flip AFTER its step committed (in-HBM corruption, the
    bit_flip_param site's semantics).  The clock runs from the flip:
    the attestation round names rank 1 (detect), the shadow replay on
    the named rank classifies the corruption as kind="memory" (replay
    of the retained pre-step snapshot disagrees with the live state),
    the state is restored from a healthy replica and the next round
    attests clean (recover)."""
    kv = distributed.FileKV(root)
    world = 3

    def step_fn(state):
        return {"w": state["w"] * 0.999 + 0.001}

    pre = {"w": np.arange(256, dtype=np.float32) / 7.0}
    planes, states = [], []
    for r in range(world):
        led = integrity.IntegrityLedger(
            os.path.join(root, f"ledger_{r}.jsonl"))
        p = integrity.IntegrityPlane(rank=r, world=world, kv=kv,
                                     every=1, timeout=2.0, ledger=led,
                                     run="bench")
        p.retain(0, {"w": pre["w"].copy()})
        planes.append(p)
        states.append(step_fn({"w": pre["w"].copy()}))

    t0 = time.perf_counter()
    integrity.bit_flip_host(states[1]["w"])

    fps = [integrity.fingerprint_host(s) for s in states]
    # healthy peers publish first so the victim's vote resolves without
    # a gather poll
    for r in (0, 2):
        planes[r].publish(0, fps[r])
    verdict = planes[1].attest(0, fps[1])
    t_detect = time.perf_counter()
    audit = planes[1].audit(step_fn, fps[1], step=0)
    # recover: adopt a healthy replica's state (the buddy-snapshot
    # path), then re-attest clean
    states[1] = {"w": states[0]["w"].copy()}
    fps[1] = integrity.fingerprint_host(states[1])
    for r in (0, 2):
        planes[r].publish(1, fps[r])
    verdict2 = planes[1].attest(1, fps[1])
    t_recover = time.perf_counter()

    return {
        "sdc_injected_rank": 1,
        "sdc_rank_named": (verdict.get("corrupt") or [None])[0],
        "sdc_kind": (audit or {}).get("kind"),
        "sdc_detect_ms": round((t_detect - t0) * 1e3, 2),
        "sdc_detect_to_recovery_ms": round((t_recover - t0) * 1e3, 2),
        "sdc_reattest_ok": bool(verdict2.get("ok")),
    }


def bench_sharded(cfg, devices):
    """tp_step_us / fsdp_step_us: full sharded train-step latency on a
    small transformer with the model-parallel collectives fused into
    the ONE donated jit program (parallel/sharding.py shard_model +
    gluon/captured.py), two modes on the same mesh abstraction:

    - tp: Megatron-style tensor parallelism over the ``tp`` axis
      (column/row weight splits + activation constraints);
    - fsdp: params sharded over the data axis, gathered per-layer
      inside the program.

    Per mode, also reported: per-device memory high-water
    (compiled.memory_analysis) and per-axis collective bytes the HLO
    actually issues (telemetry.collective_bytes_by_axis) — both read
    from the telemetry StepStats records the timed loop emits, the
    same always-on accounting the trainer bench uses."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel, telemetry
    from mxnet_tpu.gluon import captured
    from mxnet_tpu.gluon.model_zoo.bert import TransformerEncoder

    steps = cfg["steps"]
    n = max(1, len(devices))
    tp = 4 if n % 4 == 0 else (2 if n % 2 == 0 else 1)
    dp = n // tp
    units, hidden, layers, batch, t = 64, 256, 2, cfg["batch"], 6

    rng = np.random.RandomState(0)
    x_np = rng.normal(size=(batch, t, units)).astype(np.float32)
    y_np = rng.randint(0, units, size=(batch, t)).astype(np.float32)

    def _run_mode(mode, mesh_axes):
        mesh = parallel.make_mesh(**mesh_axes)
        mx.random.seed(7)
        net = TransformerEncoder(num_layers=layers, units=units,
                                 num_heads=4, hidden_size=hidden,
                                 dropout=0.0)
        net.initialize(init=mx.init.Xavier())
        net.hybridize()
        parallel.shard_model(net, mesh, mode=mode)
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        loss_fn.hybridize()
        tr = gluon.Trainer(net.collect_params(), "adam",
                           {"learning_rate": 1e-3})

        def step():
            return tr.train_step(net, loss_fn, mx.nd.array(x_np),
                                 mx.nd.array(y_np))

        _readback(step())
        _readback(step())
        captured.reset_counters()
        telemetry.reset()
        dt, _ = _timed_loop(step, steps, per_step_readback=True)
        recs = [r for r in telemetry.recent_steps()
                if r.get("path") == "captured"][-steps:]
        peak = coll = None
        for r in reversed(recs):
            if peak is None and r.get("device_peak_bytes") is not None:
                peak = r["device_peak_bytes"]
            if coll is None and r.get("collective_bytes_by_axis"):
                coll = r["collective_bytes_by_axis"]
        out = {
            "step_us": round(dt / steps * 1e6, 1),
            "device_peak_bytes": peak,
            "collective_bytes_by_axis": coll,
            "dispatches": captured.dispatch_count(),
            "mesh": dict(mesh_axes),
        }
        parallel.set_default_mesh(None)
        return out

    tp_out = _run_mode("tp", {"dp": dp, "tp": tp})
    fsdp_out = _run_mode("fsdp", {"dp": n})

    print(json.dumps({
        "metric": "tp_step_us",
        "value": tp_out["step_us"],
        "unit": "us/step",
        "vs_baseline": None,
        "fsdp_step_us": fsdp_out["step_us"],
        "tp_device_peak_bytes": tp_out["device_peak_bytes"],
        "fsdp_device_peak_bytes": fsdp_out["device_peak_bytes"],
        "tp_collective_bytes_by_axis": tp_out["collective_bytes_by_axis"],
        "fsdp_collective_bytes_by_axis":
            fsdp_out["collective_bytes_by_axis"],
        "tp_mesh": tp_out["mesh"],
        "fsdp_mesh": fsdp_out["mesh"],
        "tp_dispatches": tp_out["dispatches"],
        "fsdp_dispatches": fsdp_out["dispatches"],
        "steps": steps,
        "batch": batch,
        "backend": devices[0].platform,
    }))


def bench_pp(cfg, devices):
    """pp_step_us: 3-axis (tp×pp×dp) vs tp-only full train-step latency
    at EQUAL global batch on a scanned-trunk transformer, with the 1F1B
    microbatch schedule fused into the ONE donated whole-step program
    (gluon/captured.py; docs/parallel.md "Pipeline parallelism on the
    captured step").  Per point: the measured ``bubble_fraction`` from
    the StepStats records the timed loop emits and per-axis collective
    bytes (the ``pp`` row is the stage grad hand-off).  Gates, same
    discipline as trainer_gates:

    - pp_zero_retrace: the schedule lives INSIDE the cached program —
      the timed loop must be all cache hits, zero retraces, one
      dispatch per step;
    - bubble_share_reported: the schedule accounts for its own bubble
      in telemetry (docs/observability.md, schema v5)."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel, telemetry
    from mxnet_tpu.gluon import captured
    from mxnet_tpu.gluon.model_zoo.bert import ScanTransformerEncoder

    steps = cfg["steps"]
    n = max(1, len(devices))
    if n % 4 != 0:
        raise RuntimeError(
            "pp bench needs a device count divisible by 4 for the "
            "tp=2 x pp=2 x dp mesh, got %d" % n)
    units, hidden, layers, batch, t = 64, 256, 4, cfg["batch"], 6

    rng = np.random.RandomState(0)
    x_np = rng.normal(size=(batch, t, units)).astype(np.float32)
    y_np = rng.randint(0, units, size=(batch, t)).astype(np.float32)

    def _run_mode(mode, mesh_axes):
        mesh = parallel.make_mesh(axes=dict(mesh_axes))
        mx.random.seed(7)
        net = ScanTransformerEncoder(num_layers=layers, units=units,
                                     num_heads=4, hidden_size=hidden,
                                     dropout=0.0)
        net.initialize(init=mx.init.Xavier())
        net.hybridize()
        parallel.shard_model(net, mesh, mode=mode)
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        loss_fn.hybridize()
        tr = gluon.Trainer(net.collect_params(), "adam",
                           {"learning_rate": 1e-3})

        def step():
            return tr.train_step(net, loss_fn, mx.nd.array(x_np),
                                 mx.nd.array(y_np))

        _readback(step())
        _readback(step())
        captured.reset_counters()
        telemetry.reset()
        dt, _ = _timed_loop(step, steps, per_step_readback=True)
        recs = [r for r in telemetry.recent_steps()
                if r.get("path") == "captured"][-steps:]
        bubble = coll = None
        for r in reversed(recs):
            if bubble is None and r.get("bubble_fraction") is not None:
                bubble = r["bubble_fraction"]
            if coll is None and r.get("collective_bytes_by_axis"):
                coll = r["collective_bytes_by_axis"]
        cache = captured.cache_stats()
        out = {
            "step_us": round(dt / steps * 1e6, 1),
            "bubble_fraction": bubble,
            "collective_bytes_by_axis": coll,
            "dispatches": captured.dispatch_count(),
            "traces": captured.trace_count(),
            "cache_misses": cache.get("misses"),
            "mesh": dict(mesh_axes),
        }
        parallel.set_default_mesh(None)
        return out

    tp_out = _run_mode("tp", {"dp": n // 2, "tp": 2})
    pp_out = _run_mode("tp_pp", {"tp": 2, "pp": 2, "dp": n // 4})

    bubble = pp_out["bubble_fraction"]
    gates = {
        "pp_zero_retrace": pp_out["traces"] == 0
        and pp_out["cache_misses"] == 0
        and pp_out["dispatches"] == steps,
        "bubble_share_reported": bubble is not None
        and 0 <= bubble < 1,
    }
    print(json.dumps({
        "metric": "pp_step_us",
        "value": pp_out["step_us"],
        "unit": "us/step",
        "vs_baseline": None,
        "tp_only_step_us": tp_out["step_us"],
        "bubble_fraction": bubble,
        "pp_collective_bytes_by_axis":
            pp_out["collective_bytes_by_axis"],
        "tp_collective_bytes_by_axis":
            tp_out["collective_bytes_by_axis"],
        "pp_mesh": pp_out["mesh"],
        "tp_mesh": tp_out["mesh"],
        "pp_dispatches": pp_out["dispatches"],
        "pp_gates": gates,
        "pp_gates_ok": all(gates.values()),
        "steps": steps,
        "batch": batch,
        "backend": devices[0].platform,
    }))


def bench_autotune(cfg, devices):
    """autotune_tuned_step_us: tuned vs default full-step time and MFU
    (mxnet_tpu/autotune/) on the test mesh — an FSDP-sharded
    transformer trained three ways in one process:

    - default: MXTPU_AUTOTUNE=off, knobs at their declared defaults;
    - search: MXTPU_AUTOTUNE=search against a fresh tuning DB — the
      successive-halving trials run inside the first train_step, then
      the timed loop measures the tuned steady state (trial steps are
      stamped ``tuning_trial`` and never enter the aggregates);
    - replay: a FRESH trainer in the same process re-consults the DB —
      the gate demands a ``tune_db_hit`` with ZERO trials.

    Gates (trainer_gates discipline, ratios not absolutes):
    ``tuned_le_default`` — the persisted winner's searched score beats
    or ties the base config's searched score (the search measures both
    on the same warm trainer, so this holds regardless of host noise);
    ``replay_zero_trials`` — restart starts at the tuned point for
    free.  Steady-state tuned vs default wall time and MFU are reported
    alongside as the observed (noisier) numbers."""
    import tempfile

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import gluon, parallel, telemetry
    from mxnet_tpu.autotune import db as tune_db
    from mxnet_tpu.autotune import space as tune_space
    from mxnet_tpu.gluon.model_zoo.bert import TransformerEncoder

    steps = cfg["steps"]
    n = max(1, len(devices))
    units, hidden, layers, batch, t = 64, 256, 2, cfg["batch"], 6
    rng = np.random.RandomState(0)
    x_np = rng.normal(size=(batch, t, units)).astype(np.float32)
    y_np = rng.randint(0, units, size=(batch, t)).astype(np.float32)

    db_path = os.path.join(tempfile.mkdtemp(prefix="mxtpu_bench_tune_"),
                           "tune_db.jsonl")
    os.environ["MXTPU_TUNE_DB"] = db_path
    os.environ["MXTPU_TUNE_STEPS"] = \
        os.environ.get("BENCH_TUNE_STEPS", "2")
    os.environ["MXTPU_TUNE_BUDGET"] = \
        os.environ.get("BENCH_TUNE_BUDGET", "6")

    def _run(mode):
        os.environ["MXTPU_AUTOTUNE"] = mode
        mesh = parallel.make_mesh(dp=n) if n > 1 else None
        mx.random.seed(7)
        net = TransformerEncoder(num_layers=layers, units=units,
                                 num_heads=4, hidden_size=hidden,
                                 dropout=0.0)
        net.initialize(init=mx.init.Xavier())
        net.hybridize()
        if mesh is not None:
            parallel.shard_model(net, mesh, mode="fsdp")
        loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
        loss_fn.hybridize()
        tr = gluon.Trainer(net.collect_params(), "adam",
                           {"learning_rate": 1e-3})

        def step():
            return tr.train_step(net, loss_fn, mx.nd.array(x_np),
                                 mx.nd.array(y_np))

        telemetry.reset()
        _readback(step())   # search/replay happens inside this call
        _readback(step())
        counts = telemetry.event_counts()
        telemetry.reset(close_sink=False)
        dt, _ = _timed_loop(step, steps, per_step_readback=True)
        recs = telemetry.recent_steps()[-steps:]   # trials excluded
        mfus = [r["mfu"] for r in recs if r.get("mfu") is not None]
        out = {
            "step_us": round(dt / steps * 1e6, 1),
            "mfu": round(sum(mfus) / len(mfus), 6) if mfus else None,
            "events": counts,
        }
        parallel.set_default_mesh(None)
        # the applied winner's env must not leak into the next phase
        for knob in tune_space.KNOBS.values():
            os.environ.pop(knob.env, None)
        return out

    default_out = _run("off")
    search_out = _run("search")
    replay_out = _run("replay")
    os.environ.pop("MXTPU_AUTOTUNE", None)
    os.environ.pop("MXTPU_TUNE_DB", None)

    entries = list(tune_db.load(db_path).values())
    entry = entries[0] if entries else None
    searched_score = entry.get("score_us") if entry else None
    searched_default = entry.get("default_score_us") if entry else None
    tuned_us = search_out["step_us"]
    default_us = default_out["step_us"]
    print(json.dumps({
        "metric": "autotune_tuned_step_us",
        "value": tuned_us,
        "unit": "us/step",
        "vs_baseline": None,
        "default_us": default_us,
        "improvement": round(default_us / tuned_us, 3)
        if tuned_us else None,
        "tuned_mfu": search_out["mfu"],
        "default_mfu": default_out["mfu"],
        "searched_score_us": searched_score,
        "searched_default_us": searched_default,
        "trials": search_out["events"].get("tune_trial", 0),
        "infeasible": search_out["events"].get("tune_infeasible", 0),
        "winner_fingerprint": entry.get("fingerprint") if entry
        else None,
        "tuned_le_default": searched_score is not None
        and (searched_default is None
             or searched_score <= searched_default),
        "replay_zero_trials":
            replay_out["events"].get("tune_db_hit", 0) == 1
            and replay_out["events"].get("tune_trial", 0) == 0,
        "replay_step_us": replay_out["step_us"],
        "steps": steps,
        "batch": batch,
        "mesh_devices": n,
        "backend": devices[0].platform,
    }))


def bench_serving(cfg, devices):
    """serving_p50_us / p99_us / tokens_per_sec: the full request path
    (queue → coalesce → bucketed AOT prefill → KV-cache decode) under N
    simulated closed-loop clients, vs the same requests served
    unbatched one-by-one.  The ratio gate is the point: continuous
    batching must BUY throughput at N clients, or the batcher is just
    latency.  Also pins retraces-after-warmup, the claim that makes the
    p99 trustworthy."""
    import threading

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.gluon.model_zoo import gpt

    clients = cfg["clients"]
    n_requests = cfg["requests"]
    new_tokens = cfg["new_tokens"]
    max_bucket = cfg["batch"]

    np.random.seed(0)
    mx.random.seed(0)
    net = gpt.gpt_tiny(scan_layers=True)
    net.initialize(init=mx.init.Xavier())
    net(mx.nd.array(np.random.randint(0, 128, (1, 8)).astype(np.float32)))

    buckets = tuple(sorted({1, 2, max(1, max_bucket // 2), max_bucket}))
    engine = serving.ServingEngine(net, batch_buckets=buckets)
    engine.warmup()
    traces_at_warmup = serving.trace_count()

    rng = np.random.RandomState(1)
    prompts = [rng.randint(0, 128, rng.randint(4, 17)).tolist()
               for _ in range(n_requests)]

    # unbatched: the same requests strictly one-by-one (bucket B=1)
    t0 = time.perf_counter()
    solo_lat = []
    for p in prompts:
        t1 = time.perf_counter()
        engine.serve_group([p], new_tokens)
        solo_lat.append((time.perf_counter() - t1) * 1e6)
    solo_dt = time.perf_counter() - t0
    tokens_total = n_requests * new_tokens
    solo_tps = tokens_total / solo_dt

    # batched: N closed-loop clients through the continuous batcher
    batcher = serving.ContinuousBatcher(engine, max_delay_ms=2.0,
                                        max_batch=max_bucket)
    lat_lock = threading.Lock()
    batched_lat = []
    padded = []

    def client(idx):
        for j in range(idx, n_requests, clients):
            t1 = time.perf_counter()
            rec = batcher.submit(prompts[j], new_tokens).result(
                timeout=240)
            with lat_lock:
                batched_lat.append((time.perf_counter() - t1) * 1e6)
                padded.append(rec["padded_fraction"])

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(i,))
               for i in range(clients)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    batched_dt = time.perf_counter() - t0
    batcher.close()
    batched_tps = tokens_total / batched_dt

    lat = np.sort(np.asarray(batched_lat))
    p50 = float(np.percentile(lat, 50))
    p99 = float(np.percentile(lat, 99))
    print(json.dumps({
        "metric": "serving_p50_us",
        "value": round(p50, 1),
        "unit": "us/request",
        "vs_baseline": None,
        "p99_us": round(p99, 1),
        "tokens_per_sec": round(batched_tps, 1),
        "tokens_per_sec_unbatched": round(solo_tps, 1),
        "batched_ratio": round(batched_tps / solo_tps, 3)
        if solo_tps else None,
        "unbatched_p50_us": round(float(np.percentile(
            np.asarray(solo_lat), 50)), 1),
        "retraces_after_warmup":
            serving.trace_count() - traces_at_warmup,
        "programs": engine.program_count(),
        "mean_padded_fraction": round(float(np.mean(padded)), 4)
        if padded else None,
        "clients": clients,
        "requests": n_requests,
        "new_tokens": new_tokens,
        "batch": max_bucket,
        "backend": devices[0].platform,
    }))


def bench_obs(cfg, devices):
    """obs_overhead_pct: the fleet observability plane must be free at
    the train loop's timescale.  The SAME captured-step run (Dense-256
    model, JSONL sink on for both halves — the sink itself is already
    pinned <1% by the telemetry tests) is timed with the full plane
    live — a HostCollector tailing the sink off the train thread and
    publishing rollups on a FileKV, plus a MetricsExporter being
    scraped over HTTP for the whole run — bracketed by a bare baseline
    run on each side.  The median-step ratio vs the slower baseline is
    the ``obs_overhead_le_1pct`` gate.  Second
    half: N requests through FrontDoor → batcher → a real bucketed
    engine must EACH yield exactly one closed span tree covering
    frontdoor/batcher/prefill/decode — the span-completeness gate that
    makes the fleet report's request view trustworthy end to end."""
    import tempfile
    import threading
    import urllib.request

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import distributed, gluon, serving, telemetry
    from mxnet_tpu.gluon import nn
    from mxnet_tpu.gluon.model_zoo import gpt
    from mxnet_tpu.obs.collector import FleetView, HostCollector
    from mxnet_tpu.obs.exporter import MetricsExporter

    steps, batch = cfg["steps"], cfg["batch"]
    work = tempfile.mkdtemp(prefix="bench_obs_")
    os.environ["MXTPU_TELEMETRY_PATH"] = os.path.join(
        work, "train_events.jsonl")
    telemetry.reset()
    telemetry.set_identity(rank=0, world=1)

    # ~10ms steps on the CPU fallback: the record RATE (not the record
    # cost) is what the collector pays for, so a microscopic step would
    # feed it telemetry 100x faster than any real workload and pin the
    # parse cost against nothing
    units = 384
    net = nn.HybridSequential()
    net.add(nn.Dense(units, in_units=units, activation="relu"))
    net.add(nn.Dense(units, in_units=units))
    net.initialize(init=mx.init.Xavier())
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.01})
    loss_fn = gluon.loss.L2Loss()
    rng = np.random.RandomState(0)
    x = mx.nd.array(rng.rand(batch, units).astype("float32"))
    y = mx.nd.array(rng.rand(batch, units).astype("float32"))

    def run(n):
        times = []
        for _ in range(n):
            t0 = time.perf_counter()
            trainer.train_step(net, loss_fn, x, y)
            times.append(time.perf_counter() - t0)
        return times

    run(5)                                 # warm: trace + compile
    base_before = sorted(run(steps))[steps // 2]

    kv = distributed.FileKV(os.path.join(work, "kv"))
    collector = HostCollector(kv=kv, rank=0, world=1,
                              period_s=0.5).start()
    exporter = MetricsExporter(port=0, fleet=FleetView(kv))
    scrapes = {"n": 0, "ok": 0}
    stop = threading.Event()

    def scrape_loop():
        url = f"http://127.0.0.1:{exporter.port}/metrics"
        while not stop.is_set():
            try:
                body = urllib.request.urlopen(url, timeout=5).read()
                scrapes["ok"] += int(b"mxtpu_" in body)
            except Exception:
                pass
            scrapes["n"] += 1
            stop.wait(1.0)

    scraper = threading.Thread(target=scrape_loop, daemon=True)
    scraper.start()
    run(5)                                 # settle with the plane live
    withs = sorted(run(steps))[steps // 2]
    stop.set()
    scraper.join(timeout=5)
    collector.poll_once()
    rollup = kv.get_json("obs/rollup/0") or {}
    collector.close()
    exporter.close()
    # bracketing baseline: on a shared host, run-to-run drift exceeds
    # the true plane cost — a baseline on EACH side of the obs run
    # (gate vs the slower one) keeps the gate about the plane, not the
    # machine, while still catching anything train-thread-bounded
    base_after = sorted(run(steps))[steps // 2]
    base = max(base_before, base_after)
    ratio = withs / base if base > 0 else None

    # -- span completeness: the full ingress→decode request path -------------
    np.random.seed(0)
    mx.random.seed(0)
    lm = gpt.gpt_tiny(scan_layers=True)
    lm.initialize(init=mx.init.Xavier())
    lm(mx.nd.array(np.random.randint(0, 128, (1, 8))
                   .astype(np.float32)))
    engine = serving.ServingEngine(lm, batch_buckets=(1, 2))
    engine.warmup()
    replica = serving.ReplicaServer(engine, max_delay_ms=2.0,
                                    max_batch=2)
    door = serving.FrontDoor([replica])
    prng = np.random.RandomState(1)
    futs = [door.submit(prng.randint(0, 128,
                                     prng.randint(4, 9)).tolist(),
                        cfg["new_tokens"])
            for _ in range(cfg["requests"])]
    for fut in futs:
        fut.result(timeout=240)
    replica.close()

    need = {"frontdoor", "batcher", "prefill", "decode"}
    recs = telemetry.recent_requests()
    spans_total = spans_complete = 0
    for rec in recs:
        spans_total += 1
        spans = rec.get("spans") or []
        roots = [s for s in spans if s.get("parent") is None]
        closed = bool(spans) and all(
            isinstance(s.get("dur_us"), (int, float))
            and s["dur_us"] >= 0 for s in spans)
        ok = (len(roots) == 1 and closed
              and need <= {s.get("name") for s in spans})
        try:
            telemetry.validate_record(rec)
        except Exception:
            ok = False
        spans_complete += int(ok)

    print(json.dumps({
        "metric": "obs_overhead_pct",
        "value": round((ratio - 1.0) * 100.0, 3)
        if ratio is not None else None,
        "unit": "% captured-step overhead",
        "vs_baseline": None,
        "obs_step_us_base": round(base * 1e6, 1),
        "obs_step_us_base_before": round(base_before * 1e6, 1),
        "obs_step_us_base_after": round(base_after * 1e6, 1),
        "obs_step_us_with": round(withs * 1e6, 1),
        "obs_overhead_ratio": round(ratio, 4)
        if ratio is not None else None,
        "collector_polls": collector.polls,
        "rollup_steps_total": rollup.get("steps_total"),
        "exporter_scrapes": scrapes["n"],
        "exporter_scrapes_ok": scrapes["ok"],
        "spans_total": spans_total,
        "spans_complete": spans_complete,
        "requests": cfg["requests"],
        "new_tokens": cfg["new_tokens"],
        "steps": steps,
        "backend": devices[0].platform,
    }))


def bench_bert(cfg, devices):
    import numpy as np

    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon.model_zoo import bert as bert_zoo

    n_chips = max(1, len(devices))
    batch_size, seq_len, steps = cfg["batch"], cfg["seq"], cfg["steps"]

    # scan_layers: the 12-layer trunk compiles as ONE scanned layer —
    # unrolled, the whole-step compile takes tens of minutes and blows
    # the worker budget.  The attention impl runs as named or raises
    # (ops/pallas_attention.py), so what was asked is what was used.
    attn = cfg.get("attn", "dense")
    net = bert_zoo.bert_base(dropout=0.0, max_length=seq_len,
                             scan_layers=True,
                             attention_impl=attn)
    net.initialize(init=mx.init.Xavier())
    net.cast("bfloat16")

    mesh = parallel.data_parallel_mesh(n_chips)
    trainer = parallel.ShardedTrainer(
        net, bert_zoo.BERTPretrainLoss(), "adamw",
        {"learning_rate": 1e-4, "wd": 0.01}, mesh=mesh)

    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 30000, (batch_size, seq_len)),
                         jnp.int32)
    mlm_labels = np.full((batch_size, seq_len), -1, np.int32)
    mask_pos = rng.rand(batch_size, seq_len) < 0.15
    mlm_labels[mask_pos] = rng.randint(
        0, 30000, int(mask_pos.sum()))
    mlm_labels = jnp.asarray(mlm_labels)
    nsp_labels = jnp.asarray(rng.randint(0, 2, (batch_size,)), jnp.int32)

    kind, peak = _peak_for(devices[0])

    # BERT-base FLOPs/token (matmuls only): 12 layers ×
    # (qkv 3*768*768*2 + attn 2*2*768*T... ) — use the standard 6*N
    # approximation with N=110e6 params plus attention quadratic term
    n_params = 110e6
    attn_flops = 12 * 2 * 2 * seq_len * 768  # per token: QK^T + AV
    flops_per_token = 3.0 * (2 * n_params + attn_flops)
    flops_per_step = flops_per_token * batch_size * seq_len
    total_peak = peak * n_chips if peak else None

    dt, mfu, gated, loss_val = _measure(
        lambda: trainer.step(tokens, (mlm_labels, nsp_labels)),
        steps, flops_per_step, total_peak)

    loss = float(np.asarray(loss_val, dtype=np.float32))
    if not np.isfinite(loss):
        sys.stderr.write(f"non-finite loss {loss}\n")
        sys.exit(5)

    per_chip = batch_size * seq_len * steps / dt / n_chips
    print(json.dumps({
        "metric": "bert_base_pretrain_tokens_per_sec_per_chip",
        "value": round(per_chip, 2),
        "unit": "tokens/sec/chip",
        "vs_baseline": None,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "mfu_gated_remeasure": gated,
        "loss": round(loss, 4),
        "device_kind": kind,
        "backend": devices[0].platform,
        "batch": batch_size,
        "seq": seq_len,
        # the path that actually RAN, not the one requested:
        # flash_attention silently dispatches dense when T is not
        # lane-aligned on TPU (ops/pallas_attention.py)
        "attn": attn,
        "scan_layers": True,
    }))


if __name__ == "__main__":
    if len(sys.argv) > 2 and sys.argv[1] == "--worker":
        worker(json.loads(sys.argv[2]))
    elif len(sys.argv) > 2 and sys.argv[1] == "--gang-worker":
        gang_worker(json.loads(sys.argv[2]))
    else:
        sys.exit(orchestrate())
