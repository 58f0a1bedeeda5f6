"""Every name and unit of BENCHMARK.json and of metrics/ holds only the
characters the contract allows, and the files agree with each other.

An entry's ``workloads`` in BENCHMARK.json is the one list of a metric's
cells (PR 45): a cell joins a metric that exists by its name there and
by no edit under ``benchmark/``; a metric file lists ``cells`` only
while no entry names it (it waits for a later PR).  No two entered
metrics may be the same reading under two names."""

import glob
import json
import os
import re

import pytest

from benchmark.cells import HERE, Cells

from conftest import ROOT, TINY_GPT, TINY_SERVE, write_bench

PER_LAYER_MAX = 128     # the contract's limit on ``per_layer``
# what makes a metric the reading it is; two entered files that agree in
# all of it are one metric under two names
READING = ("reader", "params", "unit", "better", "source", "layer", "moves")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_and_units(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in bench[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"),
                          entry["name"]))
    assert len(set(names)) == len(names)
    for w in bench["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for c in bench["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
    assert len(json.dumps(bench)) < 64 * 1024


def test_metric_files_agree_with_the_entries(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for path in glob.glob(os.path.join(HERE, "metrics", "*.json")):
        with open(path) as f:
            desc = json.load(f)
        assert os.path.basename(path) == desc["name"] + ".json"
        assert NAME.match(desc["name"]) and UNIT.match(desc["unit"])
        assert os.path.isfile(os.path.join(HERE, "readers",
                                           desc["reader"] + ".py"))
        entry = by_name.get(desc["name"])
        if entry is None:       # its cells are kept for a later PR
            continue
        for key in ("unit", "better", "source", "layer", "moves"):
            assert entry[key] == desc[key], (desc["name"], key)
        assert entry["moves"] in e2e and entry["workloads"]
        # an entered file lists no cells of its own; one that still does
        # (a test's own) holds the entry to them
        assert set(entry["workloads"]) <= set(desc.get("cells",
                                                       entry["workloads"]))


def _entered():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [m["name"] for m in json.load(f)["per_layer"]]


@pytest.fixture(scope="module")
def described(bench):
    """{entered metric: its file's description}, each file read once."""
    out = {}
    for m in bench["per_layer"]:
        with open(os.path.join(HERE, "metrics", m["name"] + ".json")) as f:
            out[m["name"]] = json.load(f)
    return out


@pytest.mark.parametrize("name", _entered())
def test_no_entered_metric_is_another_under_a_second_name(bench, described,
                                                          name):
    """A cell that wants a reading some entry already gives joins that
    entry's ``workloads``; a copy of the file under a suffix is refused
    here, so the 40 twins that filled ``per_layer`` cannot come back."""
    assert len(bench["per_layer"]) <= PER_LAYER_MAX
    assert "cells" not in described[name], \
        "an entered metric's cells are its entry's workloads"

    def reading(desc):
        return [desc.get(k, {}) for k in READING]

    assert [n for n, desc in described.items()
            if reading(desc) == reading(described[name])] == [name]


def test_a_cell_joins_a_metric_by_its_name_in_workloads_alone(tmp_path,
                                                              quiet):
    """A benchmark of a test's own: its cell reports
    ``decode_ms_per_step_p50`` because the entry names it, with the
    harness's own metric file, of which no byte says the cell's name."""
    from benchmark import run

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = dict(next(m for m in json.load(f)["per_layer"]
                          if m["name"] == "decode_ms_per_step_p50"),
                     workloads=["scratch-cell"])
    write_bench(str(tmp_path), {"tiny": TINY_GPT}, {"tiny-serve": TINY_SERVE},
                [{"name": "scratch-cell", "config": "tiny",
                  "traffic": "tiny-serve", "chips": 1, "why": "a test"}],
                [entry])
    assert not os.listdir(os.path.join(str(tmp_path), "tb", "metrics"))
    cells = Cells(str(tmp_path))
    assert [m["name"] for m in cells.metrics("per_layer", "scratch-cell")] \
        == ["decode_ms_per_step_p50"]
    desc, read = cells.reader("decode_ms_per_step_p50")
    with open(os.path.join(HERE, "metrics",
                           "decode_ms_per_step_p50.json")) as f:
        assert "scratch-cell" not in f.read()
    assert read({"records": [{"t_decode0": 1.0,
                              "decode_us_per_token": 2500.0}]},
                desc["params"]) == pytest.approx(2.5)
    # and the three tests of this file's first half pass on it as they
    # stand: names, the entry against its file, the cell's metrics
    with open(os.path.join(str(tmp_path), "BENCHMARK.json")) as f:
        scratch = json.load(f)
    test_names_and_units(scratch)
    test_metric_files_agree_with_the_entries(scratch)
    traced = run.run_cell(cells, "scratch-cell", 2 ** 31 + 45, 0.3, True,
                          platform="cpu", log=quiet[1])
    assert traced["correct"] is True, quiet[0]
    assert traced["metrics"]["decode_ms_per_step_p50"]["value"] > 0


def test_every_cell_has_its_files_and_metrics(bench):
    cells = Cells(ROOT)
    for w in bench["workloads"]:
        cell = cells.cell(w["name"])
        rate = cell["traffic"]["rate_metric"]
        ends = [m["name"] for m in cells.metrics("end_to_end", w["name"])]
        assert sorted(ends) == sorted([rate, "setup_s"])
        layers = cells.metrics("per_layer", w["name"])
        assert layers and all(m["moves"] in (rate, "setup_s")
                              for m in layers)
        assert any(m["moves"] == rate for m in layers)
        for m in layers:
            cells.reader(m["name"])


def test_files_under_paths_are_named_from_the_allowed_characters(bench):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for p in bench["paths"]:
        for dirpath, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
                assert ok.match(rel), rel
