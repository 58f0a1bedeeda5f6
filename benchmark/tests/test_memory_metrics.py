"""`cache_bytes_used_pct` and `memory_unaccounted_pct.serve` (PR 51):
the serving engine's memory ledger as two per-layer metrics, files
alone.  Both read through `record_share` from fields the engine puts
into every group's records (`cache_bytes_written` over
`cache_bytes_reserved`; `memory_unaccounted_bytes` over
`memory_in_use_bytes`), each group once; the entries list the serving
cells.  On the CPU, whose ``memory_stats()`` is None, a tiny cell
reports the first and leaves the second out of its line."""

import json
import os

import pytest

from benchmark import run
from benchmark.cells import HERE, Cells

from conftest import ROOT, TINY_GPT, TINY_SERVE, write_bench

USED, UNACCOUNTED = "cache_bytes_used_pct", "memory_unaccounted_pct.serve"
FIELDS = {USED: ("cache_bytes_written", "cache_bytes_reserved"),
          UNACCOUNTED: ("memory_unaccounted_bytes", "memory_in_use_bytes")}


@pytest.mark.parametrize("name, better", [(USED, "higher"),
                                          (UNACCOUNTED, "lower")])
def test_the_files_load_and_the_entries_list_the_serving_cells(name, better):
    cells = Cells(ROOT)
    desc, read = cells.reader(name)
    above, below = FIELDS[name]
    assert desc["name"] == name and "cells" not in desc
    assert desc["reader"] == "record_share" and desc["params"] == {
        "field": above, "per": below, "once_per": "t_decode0"}
    assert above in desc["what"] and below in desc["what"]
    entry = next(m for m in cells.bench["per_layer"] if m["name"] == name)
    assert {k: desc[k] for k in ("unit", "better", "source", "layer",
                                 "moves")} \
        == {k: entry[k] for k in ("unit", "better", "source", "layer",
                                  "moves")} \
        == {"unit": "%", "better": better, "source": "program_counter",
            "layer": "serving.ServingEngine",
            "moves": "serve_tokens_per_s"}
    serving = [w["name"] for w in cells.bench["workloads"]
               if cells.cell(w["name"])["traffic"]["rate_metric"]
               == "serve_tokens_per_s"]
    assert entry["workloads"] == serving


def test_the_second_says_where_it_is_absent_and_room_is_left():
    """No count of entries is held here: a later entry breaks no pin."""
    cells = Cells(ROOT)
    desc, _ = cells.reader(UNACCOUNTED)
    assert "absent off the chip" in desc["what"].lower()
    assert len(cells.bench["per_layer"]) <= 128


def test_the_reader_by_hand():
    """Three records of two groups: a group's fields count once."""
    cells = Cells(ROOT)
    first = dict(t_decode0=1.0, cache_bytes_written=300,
                 cache_bytes_reserved=1000, memory_unaccounted_bytes=50,
                 memory_in_use_bytes=4000)
    second = dict(t_decode0=2.0, cache_bytes_written=500,
                  cache_bytes_reserved=1000, memory_unaccounted_bytes=150,
                  memory_in_use_bytes=4000)
    records = {"records": [first, dict(first), second]}
    spec, read = cells.reader(USED)
    assert read(records, spec["params"]) == pytest.approx(
        100.0 * (300 + 500) / 2000)
    spec, read = cells.reader(UNACCOUNTED)
    assert read(records, spec["params"]) == pytest.approx(
        100.0 * (50 + 150) / 8000)
    # off the chip the engine reports neither memory field
    cpu = {"records": [{k: v for k, v in r.items()
                        if not k.startswith("memory_")}
                       for r in (first, second)]}
    assert read(cpu, spec["params"]) is None
    spec, read = cells.reader(USED)
    assert read(cpu, spec["params"]) == pytest.approx(40.0)


def test_a_tiny_cell_on_the_cpu_reports_the_first_and_not_the_second(
        tmp_path, quiet):
    entries = [dict(m, workloads=["scratch-cell"])
               for m in Cells(ROOT).bench["per_layer"]
               if m["name"] in (USED, UNACCOUNTED)]
    write_bench(str(tmp_path), {"tiny": TINY_GPT}, {"tiny-serve": TINY_SERVE},
                [{"name": "scratch-cell", "config": "tiny",
                  "traffic": "tiny-serve", "chips": 1, "why": "a test"}],
                entries)
    cells = Cells(str(tmp_path))
    traced = run.run_cell(cells, "scratch-cell", 2 ** 31 + 51, 0.3, True,
                          platform="cpu", out_root=str(tmp_path / "out"),
                          log=quiet[1])
    assert traced["correct"] is True, quiet[0]
    assert 0 < traced["metrics"][USED]["value"] <= 100
    assert UNACCOUNTED not in traced["metrics"]
    with open(os.path.join(str(tmp_path), "out", "scratch-cell",
                           f"seed{2 ** 31 + 51}-trace1",
                           "records.json")) as f:
        records = json.load(f)["records"]
    # the bucket's cache: two stacks of (2, 4, 2, 16, 64) bfloat16
    assert records and all(
        r["cache_bytes_reserved"] == 2 * 2 * 4 * 2 * 16 * 64 * 2
        and 0 < r["cache_bytes_written"] <= r["cache_bytes_reserved"]
        and not [k for k in r if k.startswith("memory_")]
        for r in records)
    with open(os.path.join(HERE, "metrics", USED + ".json")) as f:
        assert "scratch-cell" not in f.read()
