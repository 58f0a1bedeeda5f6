#!/usr/bin/env python
"""Human consumer of the telemetry event log (mxnet_tpu/telemetry.py).

Reads a ``train_events.jsonl`` and prints, per run id: the step-time
breakdown table (mean microseconds + share of the step interval), MFU
statistics, and a summary of the discrete resilience events — skipped
steps (with step ids), restarts, divergence rollbacks, watchdog
expiries, checkpoint commits.  Elastic gang events (rank_dead /
mesh_reshape / rank_rejoin / elastic_recover, see
mxnet_tpu/resilience.py) get their own narrative section: who died at
which step, what each new mesh epoch looks like, and how long each
recovery took and from which source (peer RAM vs disk).

The ``compile`` events (mxnet_tpu/engine.py `watch_compiles`) become a
start-up section: the programs by backend-compile seconds, each with
whether the persistent cache gave it.  In the process itself,
``report_startup(events, out, spans=telemetry.startup_spans())`` puts
the categories of the start-up timeline above them.

Stdlib-only on purpose: it must run on a machine with neither jax nor
the package installed (pull the JSONL off a pod, read it anywhere).
``--validate`` additionally loads ``mxnet_tpu/telemetry.py`` standalone
(importlib, no package import) and runs every record through
``validate_record`` — the schema's executable spec.

Usage:
    python tools/trace_report.py train_events.jsonl [--validate]
"""

import argparse
import json
import os
import sys

BREAKDOWN_KEYS = ("data", "host_prep", "dispatch", "readback",
                  "collective", "other")


def read_records(path):
    """Parse one JSONL log; when a rotated predecessor ``<path>.1``
    exists (MXTPU_TELEMETRY_MAX_MB size cap) it is read FIRST, so the
    report spans the rotation boundary.  A truncated tail (crash
    mid-append) is skipped with a warning, never a crash."""
    records, bad = [], 0
    rotated = path + ".1"
    if os.path.exists(rotated):
        recs, b = _read_one(rotated)
        records.extend(recs)
        bad += b
    if os.path.exists(path):
        recs, b = _read_one(path)
        records.extend(recs)
        bad += b
    return records, bad


def _read_one(path):
    records, bad = [], 0
    with open(path, "r") as f:
        for ln, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                bad += 1
                sys.stderr.write(
                    f"warning: skipping unparseable line {ln} "
                    f"(truncated append?)\n")
                continue
            if isinstance(rec, dict):
                records.append(rec)
    return records, bad


def _mean(vals):
    vals = [v for v in vals if v is not None]
    return sum(vals) / len(vals) if vals else None


def _pctl(vals, q):
    """Nearest-rank percentile over a non-empty list (stdlib-only)."""
    vals = sorted(v for v in vals if v is not None)
    if not vals:
        return None
    idx = min(len(vals) - 1, max(0, int(round(q / 100.0 * len(vals))) - 1))
    return vals[idx]


def _fmt(v, nd=1):
    return "-" if v is None else f"{v:.{nd}f}"


def report_run(run, records, out):
    all_steps = [r for r in records if r.get("type") == "step"]
    # autotune trial steps time candidate configs, not the run: every
    # steady-state aggregate below excludes them (they get their own
    # section)
    trials = [s for s in all_steps if s.get("tuning_trial")]
    steps = [s for s in all_steps if not s.get("tuning_trial")]
    events = [r for r in records if r.get("type") == "event"]
    requests = [r for r in records if r.get("type") == "request"]
    attestations = [r for r in records if r.get("type") == "integrity"]
    out.write(f"run {run}: {len(steps)} step records"
              + (f" (+{len(trials)} tuning trials)" if trials else "")
              + f", {len(events)} events, {len(requests)} requests"
              + (f", {len(attestations)} attestations"
                 if attestations else "")
              + "\n")
    if requests:
        report_requests(requests, out)
    report_memory([e for e in events if e.get("event") == "program_memory"],
                  requests, out)
    if steps:
        wall = _mean([s.get("wall_us") for s in steps])
        interval = _mean([s.get("interval_us") for s in steps])
        out.write(f"  steps/s {1e6 / interval:.1f}  "
                  f"wall {_fmt(wall)} us  interval {_fmt(interval)} us\n")
        out.write("  breakdown (mean):\n")
        out.write(f"    {'stage':<12}{'us':>12}{'share':>9}\n")
        for key in BREAKDOWN_KEYS:
            us = _mean([s.get("breakdown_us", {}).get(key)
                        for s in steps])
            share = _mean([s.get("shares", {}).get(key) for s in steps])
            out.write(f"    {key:<12}{_fmt(us):>12}"
                      f"{_fmt(share, 3):>9}\n")
        mfus = [s.get("mfu") for s in steps if s.get("mfu") is not None]
        if mfus:
            out.write(f"  mfu: mean {sum(mfus) / len(mfus):.5f}  "
                      f"min {min(mfus):.5f}  max {max(mfus):.5f}\n")
        else:
            out.write("  mfu: unavailable (no cost analysis / unknown "
                      "device peak)\n")
        cbytes = sum(s.get("collective_bytes") or 0 for s in steps)
        cbuckets = sum(s.get("collective_buckets") or 0 for s in steps)
        if cbuckets:
            out.write(f"  collectives: {cbytes} bytes in {cbuckets} "
                      f"buckets\n")
        skipped = [s for s in steps if s.get("skipped")]
        if skipped:
            ids = [s.get("step") for s in skipped]
            out.write(f"  skipped steps: {len(skipped)} "
                      f"(ids {ids})\n")
        report_pipeline(steps, out)
    kinds = {}
    for e in events:
        kinds.setdefault(e.get("event", "?"), []).append(e)
    report_embeddings(steps, kinds, out)
    if events:
        out.write("  events:\n")
        for kind in sorted(kinds):
            group = kinds[kind]
            ids = [e["step"] for e in group if "step" in e]
            at = f" at steps {ids}" if ids else ""
            out.write(f"    {kind}: {len(group)}{at}\n")
        report_startup(kinds.get("compile", []), out)
        report_resilience(kinds, out)
        report_fencing(kinds, out)
        report_data(kinds, out)
        report_integrity(kinds, attestations, out)
        report_fleet(kinds, requests, out)
        report_autotune(kinds, trials, out)
    else:
        if attestations:
            report_integrity({}, attestations, out)
        if trials:
            report_autotune({}, trials, out)


#: the start-up timeline's categories and the spans of each (a name
#: ending in ``.`` or ``_`` is a prefix).  The one list: the benchmark's
#: ``setup_<category>_s`` metric files are tested against it
#: (benchmark/tests/test_startup.py), docs/observability.md shows it
STARTUP_CATEGORIES = (
    ("before_import", ("startup.before_import",)),
    ("import", ("startup.import",)),
    ("params", ("startup.params", "startup.engine", "startup.trainer",
                "startup.optimizer", "startup.backend")),
    ("trace_lower", ("compile.trace", "compile.lower", "serve.compile",
                     "train.compile")),
    ("compile", ("compile.backend",)),
    ("warm_run", ("serve.prefill.", "serve.decode.", "serve.group",
                  "serve.collect", "serve.finish", "train_step",
                  "captured_", "guard_readback")),
)


def startup_seconds(spans, thread=None, until=None):
    """{category: seconds} of a start-up timeline
    (``telemetry.startup_spans()``): of ``thread``'s spans (default: the
    thread of the first, the importing one) that closed by ``until``
    (default: all), each instant belongs to the innermost span open
    then, and a span's seconds go to the category that lists it.
    ``unattributed`` is the rest of first start to ``until`` (or the
    last end)."""
    if not spans:
        return {}
    thread = spans[0][3] if thread is None else thread
    mine = sorted((s for s in spans if s[3] == thread
                   and (until is None or s[2] <= until)),
                  key=lambda s: (s[1], -s[2]))
    if not mine:
        return {}
    out = {name: 0.0 for name, _ in STARTUP_CATEGORIES}

    def category(name):
        for cat, listed in STARTUP_CATEGORIES:
            if any(name == w or (w[-1] in "._" and name.startswith(w))
                   for w in listed):
                return cat
        return None

    stack = []                          # [end, category, children's time]

    def close():
        start, end, cat, inner = stack.pop()
        if cat is not None:
            out[cat] += (end - start) - inner
        if stack:
            stack[-1][3] += end - start

    for name, t0, t1, _thread, _attrs in mine:
        while stack and stack[-1][1] <= t0:
            close()
        if stack:                       # an overhang ends with its parent
            t1 = min(t1, stack[-1][1])
        stack.append([t0, t1, category(name), 0.0])
    while stack:
        close()
    end = max(s[2] for s in mine) if until is None else until
    out["unattributed"] = (end - mine[0][1]) - sum(out.values())
    return out


def report_startup(events, out, spans=None, until=None):
    """The start-up section: from a timeline (in process) the seconds
    by category; from ``compile`` events the programs by backend-compile
    seconds, each with ``hit``, ``miss`` or ``off``."""
    if spans:
        secs = startup_seconds(spans, until=until)
        out.write(f"  start-up ({len(spans)} spans kept):\n")
        for cat, v in secs.items():
            out.write(f"    {cat:<14}{v:>10.3f} s\n")
        out.write(f"    {'sum':<14}{sum(secs.values()):>10.3f} s\n")
        if not events:
            events = [dict(s[4], secs=s[2] - s[1]) for s in spans
                      if s[0] == "compile.backend"]
    if not events:
        return
    by_program = {}
    for e in events:
        key = (e.get("program", "?"), e.get("cache", "?"))
        n, total = by_program.get(key, (0, 0.0))
        by_program[key] = (n + 1, total + float(e.get("secs", 0.0)))
    how = {c: sum(n for (_p, cache), (n, _t) in by_program.items()
                  if cache == c) for c in ("hit", "miss", "off")}
    out.write(f"  compiles: {len(events)} backend compiles or cache "
              f"loads, {sum(t for _n, t in by_program.values()):.3f} s "
              f"({how['hit']} hit, {how['miss']} miss, {how['off']} "
              f"off)\n")
    out.write(f"    {'program':<44}{'n':>5}{'s':>10}  cache\n")
    ranked = sorted(by_program.items(), key=lambda kv: -kv[1][1])
    for (program, cache), (n, total) in ranked[:12]:
        out.write(f"    {program:<44}{n:>5}{total:>10.3f}  {cache}\n")
    if len(ranked) > 12:
        rest = ranked[12:]
        out.write(f"    {'(' + str(len(rest)) + ' more)':<44}"
                  f"{sum(n for _k, (n, _t) in rest):>5}"
                  f"{sum(t for _k, (_n, t) in rest):>10.3f}\n")


def report_pipeline(steps, out):
    """Pipeline-schedule section (docs/parallel.md "Pipeline
    parallelism on the captured step"): the bubble share the 1F1B
    microbatch schedule paid, aggregated over the run's steps, plus
    the per-device bytes the stage grad hand-off moved on the ``pp``
    mesh axis.  Prints nothing for unpipelined runs — no step record
    carries ``bubble_fraction`` (schema v5)."""
    bubbles = [s.get("bubble_fraction") for s in steps
               if s.get("bubble_fraction") is not None]
    if not bubbles:
        return
    out.write("  pipeline:\n")
    out.write(f"    bubble_fraction: mean {_mean(bubbles):.4f}  "
              f"min {min(bubbles):.4f}  max {max(bubbles):.4f} "
              f"over {len(bubbles)} step(s)\n")
    pp_bytes = [s["collective_bytes_by_axis"]["pp"] for s in steps
                if isinstance(s.get("collective_bytes_by_axis"), dict)
                and s["collective_bytes_by_axis"].get("pp")]
    if pp_bytes:
        out.write(f"    pp hand-off: mean {_mean(pp_bytes):.0f} "
                  f"bytes/step/device\n")


def report_embeddings(steps, kinds, out):
    """Sparse-embedding section (docs/perf.md "Sharded embeddings"):
    host id-prep time and unique-id fraction of the captured sparse
    steps (schema v6 ``lookup_us``/``unique_fraction`` fields), plus
    every ``sparse_fallback`` event with its reason — a sparse model
    landing on the eager oracle is a performance cliff and never
    silent.  Prints nothing for dense runs."""
    lookups = [s.get("lookup_us") for s in steps
               if s.get("lookup_us") is not None]
    fallbacks = kinds.get("sparse_fallback", ())
    if not lookups and not fallbacks:
        return
    out.write("  embeddings:\n")
    if lookups:
        out.write(f"    lookup_us: mean {_mean(lookups):.1f}  "
                  f"p50 {_pctl(lookups, 50):.1f}  "
                  f"p99 {_pctl(lookups, 99):.1f} "
                  f"over {len(lookups)} step(s)\n")
        shares = [s["lookup_us"] / s["wall_us"] for s in steps
                  if s.get("lookup_us") is not None
                  and s.get("wall_us")]
        if shares:
            out.write(f"    lookup stall share: mean "
                      f"{_mean(shares):.4f} of step wall time\n")
        fracs = [s.get("unique_fraction") for s in steps
                 if s.get("unique_fraction") is not None]
        if fracs:
            out.write(f"    unique_fraction: mean {_mean(fracs):.4f}  "
                      f"min {min(fracs):.4f}  max {max(fracs):.4f}\n")
    if fallbacks:
        reasons = {}
        for e in fallbacks:
            reasons[e.get("reason", "?")] = \
                reasons.get(e.get("reason", "?"), 0) + 1
        out.write(f"    sparse fallbacks: {len(fallbacks)} step(s) ran "
                  f"the eager oracle\n")
        for reason in sorted(reasons):
            out.write(f"      {reasons[reason]}x {reason}\n")


def report_integrity(kinds, attestations, out):
    """Integrity-plane section: attestation rounds, cross-replica
    mismatches, replay-audit verdicts, and quarantines.  Prints
    nothing when the run never attested and saw no SDC events."""
    integ_kinds = ("sdc_detected", "integrity_mismatch", "replay_audit",
                   "rank_quarantined", "serving_reload_rejected")
    if not attestations and not any(k in kinds for k in integ_kinds):
        return
    out.write("  integrity:\n")
    if attestations:
        bad = [a for a in attestations if not a.get("ok")]
        out.write(f"    attestations: {len(attestations)} "
                  f"({len(bad)} mismatched)\n")
    for e in kinds.get("integrity_mismatch", ()):
        out.write(f"    mismatch: step {e.get('step', '?')} corrupt "
                  f"rank(s) {e.get('corrupt', '?')} "
                  f"({e.get('votes', '?')} votes)\n")
    for e in kinds.get("sdc_detected", ()):
        out.write(f"    sdc: rank {e.get('rank', '?')} at step "
                  f"{e.get('step', '?')} kind "
                  f"{e.get('kind', '?')}\n")
    for e in kinds.get("replay_audit", ()):
        out.write(f"    replay audit: rank {e.get('rank', '?')} step "
                  f"{e.get('step', '?')} -> {e.get('kind', '?')}\n")
    for e in kinds.get("rank_quarantined", ()):
        out.write(f"    quarantined: rank {e.get('rank', '?')} "
                  f"(epoch {e.get('epoch', '?')}, step "
                  f"{e.get('step', '?')})\n")
    for e in kinds.get("serving_reload_rejected", ()):
        out.write(f"    serving reload rejected: step "
                  f"{e.get('step', '?')} ({e.get('reason', '?')})\n")


def report_autotune(kinds, trials, out):
    """Autotune section: trials run (with infeasible count), the
    winning config and its measured improvement over defaults, and DB
    activity — hits on restart (the zero-trial replay path), writes,
    and corrupt-entry fallbacks.  Prints nothing when the run never
    tuned."""
    tune_kinds = ("tune_search_start", "tune_trial", "tune_infeasible",
                  "tune_winner", "tune_db_hit", "tune_db_write",
                  "tune_db_fallback")
    if not any(k in kinds for k in tune_kinds) and not trials:
        return
    out.write("  autotune:\n")
    n_trials = len(kinds.get("tune_trial", ())) or len(trials)
    n_infeasible = len(kinds.get("tune_infeasible", ()))
    if n_trials or n_infeasible:
        out.write(f"    trials: {n_trials} scored, "
                  f"{n_infeasible} infeasible (OOM)\n")
    for e in kinds.get("tune_winner", ()):
        imp = e.get("improvement")
        vs = "" if imp is None else \
            f"  ({imp:.3f}x vs default {_fmt(e.get('default_score_us'))}" \
            f" us)"
        out.write(f"    winner: {e.get('fingerprint', '?')} at "
                  f"{_fmt(e.get('score_us'))} us/step{vs}\n")
    hits = kinds.get("tune_db_hit", ())
    if hits:
        fps = sorted({e.get("fingerprint", "?") for e in hits})
        out.write(f"    db hits (replayed with zero trials): "
                  f"{len(hits)} ({', '.join(fps)})\n")
    writes = len(kinds.get("tune_db_write", ()))
    if writes:
        out.write(f"    db writes: {writes}\n")
    for e in kinds.get("tune_db_fallback", ()):
        why = e.get("reason") or (
            f"{e.get('corrupt_entries', 0)} corrupt, "
            f"{e.get('stale_entries', 0)} stale entries")
        out.write(f"    db fallback: {why} -> defaults kept\n")


def report_requests(requests, out):
    """Per-request serving section: latency percentiles for each stage
    of the request path plus the padding overhead the bucket policy
    cost (schema: the 'request' record in docs/observability.md)."""
    out.write("  serving requests:\n")
    out.write(f"    {'stage':<22}{'p50 us':>12}{'p99 us':>12}\n")
    for key, label in (("queue_us", "queue"),
                       ("collect_us", "collect (group)"),
                       ("prefill_us", "prefill"),
                       ("decode_us_per_token", "decode/token"),
                       ("decode_host_us_per_step", "  host part/step")):
        vals = [r.get(key) for r in requests]
        if key in ("collect_us", "decode_host_us_per_step") \
                and all(v is None for v in vals):
            continue        # a log from before these fields
        out.write(f"    {label:<22}{_fmt(_pctl(vals, 50)):>12}"
                  f"{_fmt(_pctl(vals, 99)):>12}\n")
    pf = _mean([r.get("padded_fraction") for r in requests])
    out.write(f"    mean padded_fraction {_fmt(pf, 4)}\n")
    buckets = {}
    for r in requests:
        b = r.get("bucket")
        if isinstance(b, list) and len(b) == 2:
            key = f"{b[0]}x{b[1]}"
            buckets[key] = buckets.get(key, 0) + 1
    if buckets:
        hist = "  ".join(f"{k}:{buckets[k]}" for k in sorted(buckets))
        out.write(f"    buckets (batch x seq): {hist}\n")
    gens = sorted({r["generation"] for r in requests
                   if r.get("generation") is not None})
    if len(gens) > 1:
        out.write(f"    weight generations served: {gens} "
                  f"(hot reload mid-run)\n")


def _gb(n):
    return "-" if n is None else f"{n / 1e9:.3f} GB"


def report_memory(events, requests, out, spans=None):
    """The serving engine's memory ledger (docs/observability.md,
    "Device memory"): from the ``request`` records the weights, the
    cache a bucket reserves by kind and the share of it the groups
    wrote, what the chip held at a group's end and the part of it the
    ledger does not name; from the ``program_memory`` events what each
    compiled program needs beside its arguments; and the phase that
    made the process's peak: the first reading, in order, at which the
    runtime's high-water mark stood where it ended (``spans``: the
    process's own ``telemetry.startup_spans()``, whose ``startup.engine``
    says what was there before the engine)."""
    held = [r for r in requests if "weights_bytes" in r]
    if not events and not held:
        return
    out.write("  memory:\n")
    if held:
        out.write(f"    weights {_gb(held[-1]['weights_bytes'])} in "
                  f"{held[-1].get('weights_leaves', '?')} leaves\n")
    by_bucket = {}
    for r in held:
        if "cache_bytes_reserved" in r:
            by_bucket.setdefault(tuple(r.get("bucket", ())), []).append(r)
    for bucket, recs in sorted(by_bucket.items()):
        r = recs[-1]
        # the requests of one group carry the group's own sums, and its
        # two clock readings tell it from every other
        groups = {(x.get("prefill_us"), x.get("collect_us")):
                  x["cache_bytes_written"] for x in recs
                  if "cache_bytes_written" in x}
        written = sum(groups.values())
        used = 100.0 * written / (len(groups) * r["cache_bytes_reserved"]) \
            if groups else None
        out.write(f"    cache of bucket {'x'.join(map(str, bucket))}: "
                  f"{_gb(r['cache_bytes_reserved'])} reserved (stacks "
                  f"{_gb(r.get('cache_stack_bytes'))}, states "
                  f"{_gb(r.get('cache_state_bytes'))}, counters "
                  f"{r.get('cache_counter_bytes', '-')} B), "
                  f"{_fmt(used, 2)} % of it written over "
                  f"{len(groups)} groups\n")
    if events:
        out.write(f"    {'program':<18}{'temporaries':>14}{'arguments':>14}"
                  f"{'aliased':>14}{'outputs':>14}{'code':>12}\n")
        for e in events:
            name = f"{e.get('program', '?')} {e.get('B')}x{e.get('S')}"
            out.write(f"    {name:<18}"
                      f"{_gb(e.get('temp_size_in_bytes')):>14}"
                      f"{_gb(e.get('argument_size_in_bytes')):>14}"
                      f"{_gb(e.get('alias_size_in_bytes')):>14}"
                      f"{_gb(e.get('output_size_in_bytes')):>14}"
                      f"{_gb(e.get('generated_code_size_in_bytes')):>12}\n")
    ends = [r for r in held if "memory_in_use_bytes" in r]
    if ends:
        r = ends[-1]
        unnamed = r.get("memory_unaccounted_bytes")
        share = 100.0 * unnamed / r["memory_in_use_bytes"] \
            if unnamed is not None and r["memory_in_use_bytes"] else None
        out.write(f"    at a group's end: in use "
                  f"{_gb(r['memory_in_use_bytes'])} of "
                  f"{_gb(r.get('memory_limit_bytes'))}, of it unaccounted "
                  f"{_gb(unnamed)} ({_fmt(share, 2)} %), largest free "
                  f"block {_gb(r.get('memory_largest_free_block_bytes'))}\n")
    # the high-water mark only rises: the phase that made it is the
    # first reading that shows it where it ended
    readings = []
    for name, _t0, _t1, _thread, attrs in spans or ():
        if name == "startup.engine" and attrs:
            readings.append(("before the engine", attrs.get("peak_before")))
            readings.append(("startup.engine", attrs.get("peak")))
    later = [(e.get("t", 0.0), f"the compile of {e.get('program')} "
              f"{e.get('B')}x{e.get('S')}", e.get("peak")) for e in events]
    later += [(r.get("t", 0.0), "a group", r.get("memory_peak_bytes"))
              for r in ends]
    readings += [(label, peak) for _t, label, peak in sorted(
        later, key=lambda x: x[0])]
    readings = [(label, peak) for label, peak in readings
                if peak is not None]
    if readings:
        top = max(peak for _label, peak in readings)
        first = next(label for label, peak in readings if peak == top)
        out.write(f"    peak {_gb(top)}, first read at the end of: "
                  f"{first}\n")


def report_resilience(kinds, out):
    """Narrative summary of the elastic-gang events in one run.

    ``kinds`` is the {event kind: [records]} map built by report_run.
    Prints nothing when the run had no elastic activity.
    """
    elastic_kinds = ("rank_suspected", "straggler_suspected", "rank_dead",
                     "rank_rejoin", "mesh_reshape", "elastic_recover",
                     "ckpt_fallback", "inflight_save_dropped")
    if not any(k in kinds for k in elastic_kinds):
        return
    out.write("  resilience:\n")
    for e in kinds.get("rank_suspected", ()):
        out.write(f"    suspected: rank {e.get('rank', '?')} silent "
                  f"{_fmt(e.get('silence_s'), 2)} s "
                  f"(phi {_fmt(e.get('phi'), 1)})\n")
    for e in kinds.get("straggler_suspected", ()):
        out.write(f"    straggler: rank {e.get('rank', '?')} at step "
                  f"{e.get('step', '?')} (mean collective share "
                  f"{_fmt(e.get('mean_collective_share'), 3)})\n")
    for e in kinds.get("rank_dead", ()):
        out.write(f"    dead: rank {e.get('rank', '?')} "
                  f"(epoch {e.get('epoch', '?')}, "
                  f"detected at step {e.get('step', '?')})\n")
    for e in kinds.get("rank_rejoin", ()):
        out.write(f"    rejoin: rank {e.get('rank', '?')} "
                  f"(epoch {e.get('epoch', '?')})\n")
    for e in kinds.get("mesh_reshape", ()):
        out.write(f"    reshape: epoch {e.get('epoch', '?')} world "
                  f"{e.get('world', '?')} members "
                  f"{e.get('members', '?')} at step "
                  f"{e.get('step', '?')}\n")
    recovers = kinds.get("elastic_recover", ())
    for e in recovers:
        out.write(f"    recover: epoch {e.get('epoch', '?')} from "
                  f"{e.get('source', '?')} at step {e.get('step', '?')} "
                  f"in {_fmt(e.get('recovery_ms'))} ms\n")
    lat = [e.get("recovery_ms") for e in recovers
           if e.get("recovery_ms") is not None]
    if lat:
        out.write(f"    recovery latency: mean "
                  f"{sum(lat) / len(lat):.1f} ms  max {max(lat):.1f} ms "
                  f"over {len(lat)} recover(ies)\n")
    for e in kinds.get("ckpt_fallback", ()):
        out.write(f"    ckpt fallback: step {e.get('step', '?')} "
                  f"unreadable ({e.get('reason', '?')})\n")
    for e in kinds.get("inflight_save_dropped", ()):
        out.write(f"    inflight save dropped: step "
                  f"{e.get('step', '?')} ({e.get('reason', '?')})\n")


def report_fencing(kinds, out):
    """Split-brain fencing section (schema v8): which ranks fenced and
    why, every rejected stale write by kind (kv / peer_frame /
    checkpoint manifest), and partition heal latency.  Prints nothing
    for runs with no fencing activity."""
    fence_kinds = ("gang_fenced", "fencing_rejected", "ckpt_fenced",
                   "partition_healed")
    if not any(k in kinds for k in fence_kinds):
        return
    out.write("  fencing:\n")
    fenced = kinds.get("gang_fenced", ())
    for e in fenced:
        out.write(f"    fenced: rank {e.get('rank', '?')} at epoch "
                  f"{e.get('epoch', '?')} ({e.get('reason', '?')})\n")
    rejected = kinds.get("fencing_rejected", ())
    if rejected:
        by_kind = {}
        for e in rejected:
            by_kind.setdefault(e.get("kind", "?"), []).append(e)
        parts = ", ".join(f"{k}: {len(v)}"
                          for k, v in sorted(by_kind.items()))
        out.write(f"    rejected stale writes: {len(rejected)} "
                  f"({parts})\n")
        for e in rejected:
            out.write(f"      {e.get('kind', '?')}: rank "
                      f"{e.get('rank', '?')} epoch "
                      f"{e.get('epoch', '?')} < committed "
                      f"{e.get('committed', '?')}\n")
    for e in kinds.get("ckpt_fenced", ()):
        out.write(f"    ckpt commit aborted: rank {e.get('rank', '?')} "
                  f"step {e.get('step', '?')} epoch "
                  f"{e.get('epoch', '?')} ({e.get('reason', '?')})\n")
    healed = kinds.get("partition_healed", ())
    for e in healed:
        out.write(f"    healed: rank {e.get('rank', '?')} fenced for "
                  f"{_fmt(e.get('fenced_ms'))} ms before rejoin\n")
    lat = [e.get("fenced_ms") for e in healed
           if e.get("fenced_ms") is not None]
    if lat:
        out.write(f"    heal latency: mean {sum(lat) / len(lat):.1f} ms"
                  f"  max {max(lat):.1f} ms over {len(lat)} "
                  f"partition(s)\n")


def report_data(kinds, out):
    """Input-pipeline section (docs/resilience.md "Data-pipeline
    state"): every exactly-once resume with its sample ledger — the
    re-read and skipped counts MUST both be 0, anything else is
    flagged — plus the quarantine census (which poisoned batches the
    post-rollback replay refused, one ``batch_quarantined`` event
    each) and hung-worker timeouts.  Prints nothing for runs without
    a resumable pipeline."""
    data_kinds = ("data_resume", "batch_quarantined",
                  "data_worker_timeout")
    if not any(k in kinds for k in data_kinds):
        return
    out.write("  data pipeline:\n")
    resumes = kinds.get("data_resume", ())
    if resumes:
        reread = sum(e.get("reread_samples") or 0 for e in resumes)
        skipped = sum(e.get("skipped_samples") or 0 for e in resumes)
        flag = "" if reread == 0 and skipped == 0 else \
            "  ** NOT exactly-once **"
        out.write(f"    resumes: {len(resumes)}  re-read samples "
                  f"{reread}  skipped samples {skipped}{flag}\n")
        for e in resumes:
            out.write(f"      epoch {e.get('epoch', '?')} cursor "
                      f"{e.get('cursor', '?')} (samples_seen "
                      f"{e.get('samples_seen', '?')}, world "
                      f"{e.get('world', '?')})\n")
    quarantined = kinds.get("batch_quarantined", ())
    if quarantined:
        ids = [(e.get("epoch", "?"), e.get("batch", "?"))
               for e in quarantined]
        samples = sum(e.get("samples") or 0 for e in quarantined)
        out.write(f"    quarantined batches skipped on replay: "
                  f"{len(quarantined)} ({samples} sample(s)): "
                  f"{ids}\n")
    timeouts = kinds.get("data_worker_timeout", ())
    if timeouts:
        batches = [e.get("batch", "?") for e in timeouts]
        out.write(f"    worker-hang timeouts: {len(timeouts)} "
                  f"(batches {batches})\n")


def report_fleet(kinds, requests, out):
    """Traffic-elastic fleet section: scale events, planned-vs-detected
    reshape latency, coordinator failovers, and the serving admission
    counters (shed / deadline-exceeded requests).  Prints nothing when
    the run had no fleet activity."""
    fleet_kinds = ("scale_up", "scale_down", "gang_drain_scheduled",
                   "rank_drained", "chips_freed",
                   "serving_replica_spawned", "coordinator_failover",
                   "coordinator_reconnect", "queue_full",
                   "serving_request_shed")
    deadline = sum(1 for r in requests if r.get("deadline_exceeded"))
    if not any(k in kinds for k in fleet_kinds) and not deadline:
        return
    out.write("  fleet:\n")
    for e in kinds.get("scale_up", ()):
        out.write(f"    scale up: rank {e.get('rank', '?')} requested "
                  f"world {e.get('world', '?')} -> "
                  f"{e.get('want_world', '?')} at step "
                  f"{e.get('step', '?')} (queue depth "
                  f"{_fmt(e.get('queue_depth'))})\n")
    for e in kinds.get("scale_down", ()):
        out.write(f"    scale down: rank {e.get('rank', '?')} drains at "
                  f"step {e.get('at_step', '?')} (world "
                  f"{e.get('world', '?')}, planned)\n")
    for e in kinds.get("rank_drained", ()):
        out.write(f"    drained: rank {e.get('rank', '?')} left cleanly "
                  f"(epoch {e.get('epoch', '?')})\n")
    for e in kinds.get("chips_freed", ()):
        out.write(f"    chips freed: rank {e.get('rank', '?')} "
                  f"({e.get('count', '?')} chip(s))\n")
    for e in kinds.get("serving_replica_spawned", ()):
        out.write(f"    replica spawned on freed chips of rank "
                  f"{e.get('rank', '?')}\n")
    recovers = kinds.get("elastic_recover", ())
    planned = [e.get("recovery_ms") for e in recovers
               if e.get("planned") and e.get("recovery_ms") is not None]
    detected = [e.get("recovery_ms") for e in recovers
                if not e.get("planned")
                and e.get("recovery_ms") is not None]
    if planned or detected:
        def _stats(vals):
            return (f"mean {sum(vals) / len(vals):.1f} ms over "
                    f"{len(vals)}") if vals else "none"
        out.write(f"    reshape latency: planned {_stats(planned)}  "
                  f"detected {_stats(detected)}\n")
    failovers = kinds.get("coordinator_failover", ())
    if failovers:
        by = [f"rank {e.get('rank', '?')}" for e in failovers]
        out.write(f"    coordinator failovers: {len(failovers)} "
                  f"(promoted: {', '.join(by)})\n")
    reconnects = len(kinds.get("coordinator_reconnect", ()))
    if reconnects:
        out.write(f"    coordinator reconnects: {reconnects}\n")
    shed_batcher = len(kinds.get("queue_full", ()))
    shed_front = len(kinds.get("serving_request_shed", ()))
    if shed_batcher or shed_front:
        out.write(f"    shed requests: {shed_batcher} queue-full "
                  f"(front door retried {shed_front})\n")
    if deadline:
        out.write(f"    deadline-exceeded requests: {deadline}\n")


def validate_all(records):
    """Run every record through the package's validate_record without
    importing the package (and without needing jax installed)."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "mxnet_tpu", "telemetry.py")
    spec = importlib.util.spec_from_file_location("_mxtpu_telemetry",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    errors = []
    for i, rec in enumerate(records):
        try:
            mod.validate_record(rec)
        except ValueError as e:
            errors.append(f"record {i}: {e}")
    return errors


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Summarize a mxnet_tpu train_events.jsonl")
    ap.add_argument("path", help="path to the JSONL event log")
    ap.add_argument("--validate", action="store_true",
                    help="validate every record against the schema")
    args = ap.parse_args(argv)
    if not os.path.exists(args.path) \
            and not os.path.exists(args.path + ".1"):
        sys.stderr.write(f"error: no such file: {args.path}\n")
        return 2
    records, bad = read_records(args.path)
    if not records:
        sys.stderr.write("error: no parseable records\n")
        return 2
    if args.validate:
        errors = validate_all(records)
        if errors:
            for err in errors:
                sys.stderr.write(f"schema violation: {err}\n")
            return 1
        print(f"{len(records)} records validate against schema "
              f"v{records[0].get('v', '?')}")
    runs = {}
    for rec in records:
        runs.setdefault(rec.get("run", "?"), []).append(rec)
    for run in runs:
        report_run(run, runs[run], sys.stdout)
    if bad:
        print(f"({bad} unparseable line(s) skipped)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
