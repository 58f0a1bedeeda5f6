#!/usr/bin/env python3
"""benchmark/run.py — one run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

One process: build the cell's system from the seed, warm its shapes
(set-up), drive its traffic for ``--seconds`` under the one
admission-and-rate rule (`window.py`), then compare what the window
produced with the configuration's plain reference.  The last line of
stdout is the result object; everything else a run has to say goes on
earlier lines or into files under ``benchmark_out/``, named there.

``__main__`` demands platform ``tpu`` with as many chips as the cell
asks for, and a ``device_kind`` that ``peaks.json`` holds; the tests
drive `run_cell` tiny on the CPU.  README.md says how a cell, a
configuration, a traffic mix, a traffic kind and a per-layer metric are
added as files.
"""

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse     # noqa: E402
import gc           # noqa: E402
import json         # noqa: E402
import os           # noqa: E402
import sys          # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import trace as tracing      # noqa: E402
from benchmark.cells import Cells           # noqa: E402
from benchmark.window import Window         # noqa: E402

COMPILES = []       # seconds of every backend compile or cache load


def _watch_compiles():
    import jax

    def on_event(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            COMPILES.append(secs)

    if not getattr(_watch_compiles, "on", False):
        _watch_compiles.on = True
        jax.monitoring.register_event_duration_secs_listener(on_event)


def say(msg):
    print(f"[bench] {msg}", flush=True)


class NoChip(RuntimeError):
    """JAX does not see the device this run demands."""


def lapper(log, start):
    """``lap(what)`` logs the seconds since the last lap: where set-up
    goes, for PERF.md's list of what only the program can shorten."""
    last = [start]

    def lap(what):
        now = time.perf_counter()
        log(f"set-up: {now - last[0]:6.2f}s {what}")
        last[0] = now

    return lap


def device_info(platform, chips):
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != platform:
        raise NoChip(f"JAX runs on {info['platform']!r}; {platform!r} is "
                     "required")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chip(s), JAX sees "
                     f"{len(devs)}")
    return info, devs[:chips]


def memory_peak(devices):
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else 0


def _jsonable(obj):
    import numpy as np

    if isinstance(obj, dict):
        return {k: _jsonable(v) for k, v in obj.items()}
    if isinstance(obj, (list, tuple)):
        return [_jsonable(v) for v in obj]
    if isinstance(obj, np.ndarray):
        return obj.tolist()
    if isinstance(obj, np.generic):
        return obj.item()
    return obj


def run_cell(cells, workload, seed, seconds, trace, platform="tpu",
             out_root=None, t_process=None, log=say, hold=None):
    """One run; returns the result object (a dict).  ``hold`` (a dict)
    receives the kind's state and result for a caller that goes on to
    read the control in the same process; the caller then closes."""
    t_process = time.perf_counter() if t_process is None else t_process
    cell = cells.cell(workload)
    # the package first, and the client's start inside its own span
    # (``startup.backend``): jax's import and the client are then read as
    # ``setup_import_s`` and ``setup_params_s``, not as time before the
    # program's first line; the same work in another order
    from mxnet_tpu import engine

    cache = engine.ensure_compile_cache()
    info, devices = device_info(platform, cell["chips"])
    peaks = cells.data(".", "peaks")
    if info["kind"] not in peaks:
        raise NoChip(f"peaks.json holds no device kind {info['kind']!r}")
    _watch_compiles()
    out_dir = os.path.join(out_root or os.path.join(cells.root,
                                                    "benchmark_out"),
                           workload, f"seed{seed}-trace{int(trace)}")
    os.makedirs(out_dir, exist_ok=True)
    log(f"{workload} seed={seed} seconds={seconds} trace={int(trace)} on "
        f"{info['count']} x {info['kind']!r}; compile cache {cache}; "
        f"files under {out_dir}")
    kind, traffic = cell["kind"], cell["traffic"]
    ctx = {"cell": cell, "seed": int(seed), "seconds": float(seconds),
           "platform": platform, "devices": devices, "log": log,
           "peaks": peaks[info["kind"]], "lap": lapper(log, t_process)}
    ctx["lap"]("imports, device, compile cache")
    state = kind.setup(ctx)
    try:
        # a full pass of the cyclic collector that set-up leaves due
        # stops the interpreter for tens of milliseconds somewhere in the
        # window; where it falls is drawn by what set-up happened to
        # allocate, not by the program under test.  One pass now, inside
        # setup_s; the window itself keeps the interpreter's own collector
        gc.collect()
        window = Window(traffic["trace_seconds"] if trace else seconds)
        setup_s = time.perf_counter() - t_process
        log(f"set-up {setup_s:.2f}s ({len(COMPILES)} compiles or cache "
            f"loads, {sum(COMPILES):.1f}s)")
        compiles0 = len(COMPILES)
        xplane = None
        if trace:
            def traced():
                t_open = time.perf_counter()
                res = kind.drive(state, window, ctx)
                return res, time.perf_counter() - t_open

            (result, window_s), xplane = tracing.record(
                os.path.join(out_dir, "trace"), traced)
        else:
            result = kind.drive(state, window, ctx)
        late = len(COMPILES) - compiles0
        off = [type(a).__name__ for a in kind.arrays(state)
               if not hasattr(a, "devices")
               or any(d.platform != platform for d in a.devices())]
        if off:
            log(f"arrays off the {platform}: {off[:3]}")
        # counts that have to be 0: the kind's own and the harness's
        faults = dict(result["faults"],
                      compiled_in_window=late,
                      arrays_off_device=len(off),
                      requests_failed=result["failed"])
        peak = memory_peak(devices)
        rate = window.rate()
        log(f"window: {len(window.completed)} pieces, {window.work} "
            f"{traffic['work_unit']} in {window.elapsed:.3f}s "
            f"(asked {window.seconds}s): {rate:.4f} {traffic['work_unit']}"
            f"/s; device peak {peak} bytes")
        t0 = time.perf_counter()
        comparisons = kind.verify(state, result, ctx)
        log(f"reference comparison took {time.perf_counter() - t0:.1f}s")
        if hold is not None:
            hold.update(state=state, result=result, ctx=ctx,
                        comparisons=comparisons)
    finally:
        if hold is None:
            kind.close(state)
    for c in comparisons:
        c["ok"] = bool(c["value"] <= c["limit"])
        log(f"compared {c['name']}: {c['value']:.6g} (limit "
            f"{c['limit']:.6g}) {'ok' if c['ok'] else 'NOT OK'}")
    for name, count in faults.items():
        if count:
            log(f"fault: {name} {count} (limit 0)")
    correct = all(c["ok"] for c in comparisons) \
        and not any(faults.values())
    # what `correct` was decided from, each number beside its limit, and
    # after them what the kind watches and holds no run to (limit null)
    checks = {c["name"]: [c["value"], c["limit"]] for c in comparisons}
    checks.update((name, [count, 0]) for name, count in faults.items())
    checks.update((name, [count, None])
                  for name, count in result.get("watched", {}).items())
    device = dict(info, memory_peak_bytes=peak)
    out = {"correct": correct, "attempted": result["attempted"],
           "failed": result["failed"], "metrics": {}, "device": device}
    with open(os.path.join(out_dir, "records.json"), "w") as f:
        json.dump(_jsonable({
            "workload": workload, "seed": seed, "setup_s": setup_s,
            "rate": rate, "elapsed_s": window.elapsed,
            "completed": window.completed, "comparisons": comparisons,
            "checks": checks, "compile_seconds": COMPILES,
            "records": [{k: v for k, v in r.items() if k != "prompt"}
                        for r in result["records"]]}), f)
    if not trace:
        for m in cells.metrics("end_to_end", workload):
            value = setup_s if m["name"] == "setup_s" else rate
            if m["name"] not in ("setup_s", traffic["rate_metric"]):
                raise RuntimeError(
                    f"{workload}: end-to-end metric {m['name']!r} is "
                    f"neither setup_s nor the traffic's rate_metric")
            out["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
        out["checks"] = checks          # the line's last key
        return out
    t0 = time.perf_counter()
    reduced = tracing.reduce(xplane)
    log(f"trace reduction took {time.perf_counter() - t0:.1f}s")
    with open(os.path.join(out_dir, "trace_reduced.json"), "w") as f:
        json.dump(reduced, f)
    log(f"trace {xplane} reduced to {out_dir}/trace_reduced.json")
    device["busy_s"] = reduced["busy_s"]
    device["window_s"] = window_s
    run = {"cell": cell, "records": result["records"], "trace": reduced,
           "window": window, "rate": rate, "peaks": ctx["peaks"],
           "window_s": device["window_s"], "result": result}
    for m in cells.metrics("per_layer", workload):
        desc, read = cells.reader(m["name"])
        value = read(run, desc.get("params", {}))
        if value is not None:
            out["metrics"][m["name"]] = {"value": float(value),
                                         "unit": m["unit"]}
    for note in run.get("notes", []):
        log(note)
    out["breakdown"] = {
        "device_ops": [[tracing.clean(k), v]
                       for k, v in reduced["device_ops"]],
        "idle_gaps": [[tracing.clean(k), v]
                      for k, v in reduced["idle_gaps"]]}
    out["checks"] = checks              # the line's last key
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run_cell(Cells(ROOT), args.workload, args.seed, args.seconds,
                       bool(args.trace), t_process=T_PROCESS)
    except NoChip as exc:
        print(f"[bench] no chip: {exc}", file=sys.stderr, flush=True)
        return 3
    sys.stdout.flush()
    for name, (value, limit) in out["checks"].items():
        print(f"[bench] check {name}: {value} (limit {limit})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(_jsonable(out)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
