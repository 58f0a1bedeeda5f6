"""Seconds of the run's set-up under some of the program's start-up
spans (``mxnet_tpu.telemetry.startup_spans()``: the first spans the
process closed, each with both ``perf_counter`` reads and its thread).

Of the main thread's kept spans that closed before the window opened
(``run["window"].t0``), cut to where the run's ``setup_s`` starts
counting (`counted_from`: the program's first span opens at the
process's start, the interpreter's own 0.2 s before ``run.py``'s first
line), each instant belongs to the innermost span open then, the rule of `spans.py`'s idle split: a compile inside
``serve.prefill.dispatch`` counts as compile, and a span's own time is
its duration less its children's.

params: ``spans``, a list of span names (a name ending in ``.`` or ``_``
is a prefix, as in `span_idle`), or the string ``"unattributed"``: the
run's own ``setup_s`` less the time under the spans of every other
metric file of this reader (`listed`), so that the ``setup_*_s`` metrics
sum to ``setup_s`` whatever files there are.  ``setup_s`` is read from the
``records.json`` the run has just written, found by the layout
``run.py`` writes as `spans.find` finds the trace (or ``run["setup_s"]``
where a caller gives it).

None where the program keeps no such store (the parent of the PR that
brought it), where the store filled before the window opened (it holds
``STARTUP_SPANS`` spans and the last closed before the window: later
ones were not kept, and a sum would fall short), or where ``setup_s`` is asked for
and not found.  Once a run, ``run["notes"]`` gains a line with the
backend compiles or cache loads of set-up and the five programs that
took most of them, each with ``hit`` or ``miss``."""

import glob
import json
import os
import sys
import threading

from benchmark import spans


def counted_from(run):
    """``perf_counter``'s reading from which the run's ``setup_s`` is
    counted: ``run.py``'s ``T_PROCESS``, which it hands to `run_cell`
    and to no reader, so it is read off the process's main module (as
    the driver runs a cell, ``run.py``), or ``run["t_process"]`` where a
    caller gives it; None under any other main module (nothing is cut)."""
    return run.get("t_process", getattr(sys.modules.get("__main__"),
                                        "T_PROCESS", None))


def kept(run):
    """The main thread's spans that closed before the window opened and
    after ``setup_s`` started counting, as the program kept them (one
    that was open then starts there), or None (module doc)."""
    try:
        from mxnet_tpu import telemetry

        timeline = telemetry.startup_spans()
        room = telemetry.STARTUP_SPANS
    except (ImportError, AttributeError):
        return None
    t_open = run["window"].t0
    if t_open is None:
        return None
    if len(timeline) >= room and timeline[-1][2] < t_open:
        return None
    main = threading.main_thread().ident
    t_from = counted_from(run)
    t_from = float("-inf") if t_from is None else t_from
    return [(name, max(t0, t_from), t1, thread, attrs)
            for name, t0, t1, thread, attrs in timeline
            if thread == main and t_from < t1 <= t_open]


def own_seconds(timeline):
    """{span name: seconds in which it is the innermost open span}."""
    events = sorted(((t0, t1, name) for name, t0, t1, _, _ in timeline),
                    key=lambda ev: (ev[0], -ev[1]))
    return spans._self_seconds(events)


def matches(name, wanted):
    return any(name == w or (w[-1] in "._" and name.startswith(w))
               for w in wanted)


def listed(metrics=os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "metrics")):
    """The span names of every metric file of this reader that lists
    some."""
    names = []
    for path in sorted(glob.glob(os.path.join(metrics, "*.json"))):
        with open(path) as f:
            desc = json.load(f)
        if desc.get("reader") == "startup_spans" \
                and isinstance(desc["params"]["spans"], list):
            names += desc["params"]["spans"]
    return names


def setup_seconds(run, root=spans.ROOT):
    if "setup_s" in run:
        return run["setup_s"]
    files = glob.glob(os.path.join(
        root, "benchmark_out", run["cell"]["name"], "seed*-trace1",
        "records.json"))
    born = spans._process_start()
    files = [f for f in files if os.path.getmtime(f) >= born]
    if not files:
        return None
    with open(max(files, key=os.path.getmtime)) as f:
        return json.load(f)["setup_s"]


def note(timeline):
    backend = [s for s in timeline if s[0] == "compile.backend"]
    by_program = {}
    for _, t0, t1, _, attrs in backend:
        key = (attrs["program"], attrs["cache"])
        by_program[key] = by_program.get(key, 0.0) + (t1 - t0)
    most = sorted(by_program.items(), key=lambda kv: -kv[1])[:5]
    how = {c: sum(s[4]["cache"] == c for s in backend)
           for c in ("hit", "miss", "off")}
    return (f"start-up: {len(timeline)} spans kept on the main thread; "
            f"{len(backend)} backend compiles or cache loads, "
            f"{sum(s[2] - s[1] for s in backend):.2f}s ({how['hit']} hit, "
            f"{how['miss']} miss, {how['off']} off); most: "
            + ", ".join(f"{p} {secs:.2f}s {c}" for (p, c), secs in most))


def read(run, params):
    timeline = kept(run)
    if timeline is None:
        return None
    line = note(timeline)
    if line not in run.setdefault("notes", []):
        run["notes"].append(line)
    own = own_seconds(timeline)
    if params["spans"] != "unattributed":
        return sum(v for k, v in own.items() if matches(k, params["spans"]))
    total = setup_seconds(run)
    if total is None:
        return None
    named = listed()
    return total - sum(v for k, v in own.items() if matches(k, named))
