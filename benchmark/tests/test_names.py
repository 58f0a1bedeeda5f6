"""Every name and unit of BENCHMARK.json and of metrics/ holds only the
characters the contract allows, and the files agree with each other."""

import glob
import json
import os
import re

import pytest

from benchmark.cells import HERE, Cells

from conftest import ROOT

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
        assert entry["moves"] in e2e
        assert set(entry["workloads"]) <= set(desc["cells"])


def test_every_cell_has_its_files_and_metrics(bench):
    cells = Cells(ROOT)
    for w in bench["workloads"]:
        cell = cells.cell(w["name"])
        rate = cell["traffic"]["rate_metric"]
        ends = [m["name"] for m in cells.metrics("end_to_end", w["name"])]
        assert sorted(ends) == sorted([rate, "setup_s"])
        layers = cells.metrics("per_layer", w["name"])
        assert layers and all(m["moves"] == rate for m in layers)
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
