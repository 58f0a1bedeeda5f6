"""The reader `startup_spans` and the ``setup_*_s`` metric files, on a
hand-made start-up timeline (seconds on the window's clock; the window
opens at 30.0 and ``setup_s`` is 31.5, the process having started 1.5
before the clock's zero):

    main     startup.before_import -1.5-2
             startup.import 2-6
             startup.params 6-9 > compile.backend 7-8 (an eager op's)
             startup.engine 9-11
             serve.prefill.dispatch 12-20 > serve.compile 12.5-18
                 > compile.trace 13-14, compile.lower 14-15,
                   compile.backend 15-17.5
             serve.prefill.readback 20-24
             serve.decode.dispatch 24-25
             some.other.span 26-27 > compile.backend 26.25-26.5
             serve.decode.readback 31-32        after the window opened
    another  startup.import.pallas 3-5, compile.backend 3.5-4.5
"""

import json
import os
import threading

import pytest

from benchmark.cells import Cells
from benchmark.window import Window

from conftest import ROOT

MAIN = threading.main_thread().ident
OTHER = MAIN + 1
FILES = ["setup_before_import_s", "setup_import_s", "setup_params_s", "setup_trace_lower_s",
         "setup_compile_s", "setup_warm_run_s", "setup_unattributed_s"]
WANT = {"setup_before_import_s": 3.5,
        "setup_import_s": 4.0,
        "setup_params_s": 2.0 + 2.0,            # less the eager compile
        "setup_trace_lower_s": 2.0 + 1.0,       # serve.compile's own: 1.0
        "setup_compile_s": 1.0 + 2.5 + 0.25,
        "setup_warm_run_s": 2.5 + 4.0 + 1.0,    # dispatch's own: 2.5
        "setup_unattributed_s": 31.5 - 25.75}   # some.other.span's 0.75 too


def _backend(t0, t1, program, cache, thread=MAIN):
    return ("compile.backend", t0, t1, thread,
            {"program": program, "cache": cache})


TIMELINE = [
    ("startup.before_import", -1.5, 2.0, MAIN, None),
    ("startup.import", 2.0, 6.0, MAIN, None),
    ("startup.import.pallas", 3.0, 5.0, OTHER, None),
    _backend(3.5, 4.5, "jit_elsewhere", "miss", OTHER),
    _backend(7.0, 8.0, "jit_convert_element_type", "hit"),
    ("startup.params", 6.0, 9.0, MAIN, {"what": "cast"}),
    ("startup.engine", 9.0, 11.0, MAIN, None),
    ("compile.trace", 13.0, 14.0, MAIN, {"program": "serve_prefill"}),
    ("compile.lower", 14.0, 15.0, MAIN, {"program": "jit_serve_prefill"}),
    _backend(15.0, 17.5, "jit_serve_prefill", "miss"),
    ("serve.compile", 12.5, 18.0, MAIN, {"B": 4, "S": 16}),
    ("serve.prefill.dispatch", 12.0, 20.0, MAIN, None),
    ("serve.prefill.readback", 20.0, 24.0, MAIN, None),
    ("serve.decode.dispatch", 24.0, 25.0, MAIN, {"step": 0}),
    _backend(26.25, 26.5, "jit_other", "off"),
    ("some.other.span", 26.0, 27.0, MAIN, None),
    ("serve.decode.readback", 31.0, 32.0, MAIN, {"step": 0}),
]


@pytest.fixture
def cells():
    return Cells(ROOT)


@pytest.fixture
def timeline(monkeypatch):
    """The program's store holding TIMELINE (skipped where the program
    has no store: the reader then has nothing to read)."""
    telemetry = pytest.importorskip("mxnet_tpu.telemetry")
    if not hasattr(telemetry, "startup_spans"):
        pytest.skip("a program without the start-up timeline")
    monkeypatch.setattr(telemetry, "startup_spans", lambda: list(TIMELINE))
    return telemetry


def _run(t_open=30.0, **over):
    window = Window(1.0, clock=lambda: t_open)
    if t_open is not None:
        window.submit(0)
    run = {"cell": {"name": "no-such-cell"}, "window": window,
           "setup_s": 31.5}
    run.update(over)
    return run


def _read(cells, name, run):
    desc, read = cells.reader(name)
    return read(run, desc["params"])


@pytest.mark.parametrize("name", FILES)
def test_each_metric_file_loads_and_reads_its_category(cells, timeline,
                                                       name):
    desc, _ = cells.reader(name)
    assert (desc["unit"], desc["better"], desc["source"], desc["layer"],
            desc["moves"], desc["reader"]) == (
        "s", "lower", "program_span", "process start-up", "setup_s",
        "startup_spans")
    # entered for every cell; the entry's ``workloads`` is the one list
    bench = cells.bench
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    assert "cells" not in desc
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    assert _read(cells, name, _run()) == pytest.approx(WANT[name])


def test_the_metrics_sum_to_setup_s_to_the_cent(cells, timeline):
    run = _run()
    got = {name: _read(cells, name, run) for name in FILES}
    assert all(v >= 0.0 for v in got.values())
    assert round(sum(got.values()), 2) == 31.5


def test_unattributed_is_what_no_other_file_of_the_reader_lists(
        cells, timeline, tmp_path):
    mod = cells.module("readers", "startup_spans")
    # the files beside the reader: every span of every list, once
    listed = mod.listed()
    assert len(set(listed)) == len(listed)
    assert sorted(listed) == sorted(
        w for name in FILES[:-1]
        for w in cells.reader(name)[0]["params"]["spans"])
    assert cells.reader(FILES[-1])[0]["params"] == {"spans": "unattributed"}
    # one more file of the reader takes its span out of the rest; a file
    # of another reader, and the rest's own, take nothing
    for name, reader, spans in (("a", "startup_spans", ["some.other.span"]),
                                ("b", "span_idle", ["serve.prefill."]),
                                ("c", "startup_spans", "unattributed")):
        (tmp_path / f"{name}.json").write_text(json.dumps(
            {"reader": reader, "params": {"spans": spans}}))
    assert mod.listed(str(tmp_path)) == ["some.other.span"]


def test_nesting_self_time_and_the_prefix_rule(cells, timeline):
    _, read = cells.reader("setup_compile_s")
    run = _run()
    # a span's own time is its duration less its children's
    assert read(run, {"spans": ["serve.prefill.dispatch"]}) \
        == pytest.approx(8.0 - 5.5)
    assert read(run, {"spans": ["serve.compile"]}) \
        == pytest.approx(5.5 - 4.5)
    # a name ending in ``.`` or ``_`` is a prefix; a bare name is not
    assert read(run, {"spans": ["serve.prefill."]}) \
        == pytest.approx(2.5 + 4.0)
    assert read(run, {"spans": ["serve.prefill"]}) == 0.0
    assert read(run, {"spans": ["compile."]}) == pytest.approx(5.75)
    assert read(run, {"spans": ["startup.import"]}) == pytest.approx(4.0)
    # a span no metric lists keeps its own time out of the five; the
    # compile inside it still counts as compile
    assert read(run, {"spans": ["some.other.span"]}) == pytest.approx(0.75)


def test_spans_after_the_window_and_other_threads_are_ignored(cells,
                                                              timeline):
    _, read = cells.reader("setup_warm_run_s")
    assert read(_run(), {"spans": ["serve.decode.readback"]}) == 0.0
    assert read(_run(t_open=40.0), {"spans": ["serve.decode.readback"]}) \
        == pytest.approx(1.0)
    # another thread's import and compile reach no metric
    assert read(_run(), {"spans": ["startup.import."]}) == 0.0
    assert read(_run(), {"spans": ["compile.backend"]}) \
        == pytest.approx(3.75)
    # a window that opens mid-way: only what closed before it
    assert read(_run(t_open=19.0), {"spans": ["serve.", "compile."]}) \
        == pytest.approx(1.0 + 1.0 + 4.5)


def test_the_note_names_the_programs_once(cells, timeline):
    run = _run()
    for name in FILES:
        _read(cells, name, run)
    (note,) = run["notes"]
    assert "14 spans kept on the main thread" in note
    assert "3 backend compiles or cache loads, 3.75s " \
           "(1 hit, 1 miss, 1 off)" in note
    assert note.endswith("most: jit_serve_prefill 2.50s miss, "
                         "jit_convert_element_type 1.00s hit, "
                         "jit_other 0.25s off")


def test_none_without_a_store_after_drops_or_without_setup_s(
        cells, timeline, monkeypatch, tmp_path):
    _, read = cells.reader("setup_import_s")
    want = {"spans": ["startup.import"]}
    rest = cells.reader("setup_unattributed_s")[0]["params"]
    assert read(_run(), want) == pytest.approx(4.0)
    # the window never opened
    assert read(_run(t_open=None), want) is None
    # no records.json of this process under the layout: no setup_s
    run = _run()
    del run["setup_s"]
    assert read(run, rest) is None and read(run, want) == pytest.approx(4.0)
    # the store filled before the window opened: spans were dropped
    monkeypatch.setattr(timeline, "STARTUP_SPANS", len(TIMELINE))
    assert read(_run(t_open=40.0), want) is None
    assert read(_run(), want) == pytest.approx(4.0)     # filled inside it
    # a program without the store (the parent of the PR that brought it)
    monkeypatch.delattr(timeline, "startup_spans")
    assert read(_run(), want) is None and read(_run(), rest) is None


def test_spans_are_cut_to_where_setup_s_starts_counting(cells, timeline,
                                                        monkeypatch):
    """The program's first span opens at the process's start, before
    ``run.py``'s first line: what ``setup_s`` does not count is cut off,
    so the rest is what no span covers and never less than nothing."""
    import sys

    names = FILES
    got = {n: _read(cells, n, _run(t_process=-1.0)) for n in names}
    assert got["setup_before_import_s"] == pytest.approx(3.0)
    assert got["setup_unattributed_s"] == pytest.approx(
        WANT["setup_unattributed_s"] + 0.5)
    assert all(got[n] == pytest.approx(WANT[n]) for n in names[1:-1])
    # as the driver runs a cell, run.py is the main module and holds it
    monkeypatch.setattr(sys.modules["__main__"], "T_PROCESS", 4.0,
                        raising=False)
    assert _read(cells, "setup_before_import_s", _run()) == 0.0
    assert _read(cells, "setup_import_s", _run()) == pytest.approx(2.0)
    assert _read(cells, "setup_import_s", _run(t_process=2.0)) \
        == pytest.approx(4.0)


def test_setup_s_is_found_by_the_layout_run_py_writes(cells, timeline,
                                                      tmp_path):
    mod = cells.module("readers", "startup_spans")
    out = tmp_path / "benchmark_out" / "a-cell" / "seed7-trace1"
    out.mkdir(parents=True)
    (out / "records.json").write_text(json.dumps({"setup_s": 12.25}))
    plain = tmp_path / "benchmark_out" / "a-cell" / "seed7-trace0"
    plain.mkdir()
    (plain / "records.json").write_text(json.dumps({"setup_s": 99.0}))
    run = {"cell": {"name": "a-cell"}}
    assert mod.setup_seconds(run, root=str(tmp_path)) == 12.25
    assert mod.setup_seconds({"cell": {"name": "b-cell"}},
                             root=str(tmp_path)) is None


def test_the_files_list_what_the_programs_own_report_lists(cells):
    """tools/trace_report.py prints the same categories in process and
    holds the one list of them: where the program's tree has it, every
    category has its file, with that list."""
    import importlib.util

    path = os.path.join(ROOT, "tools", "trace_report.py")
    spec = importlib.util.spec_from_file_location("trace_report", path)
    report = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(report)
    if not hasattr(report, "STARTUP_CATEGORIES"):
        pytest.skip("a program without the start-up report")
    for cat, listed in report.STARTUP_CATEGORIES:
        desc, _ = cells.reader(f"setup_{cat}_s")
        assert desc["params"]["spans"] == list(listed)
    assert [f"setup_{cat}_s" for cat, _ in report.STARTUP_CATEGORIES] \
        == FILES[:-1]
