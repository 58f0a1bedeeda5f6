"""`prefill_tokens_padded_pct` (PR 47): the positions the prefill's
token-wise tiles worked that hold no token, from the counters a packed
prefill leaves in the records (`prefill_positions_worked`,
`prefill_positions_padded`).  Its file names a reader and an entry that
lists the cell; it reads by hand and from a tiny copy of the cell run on
the CPU, as `test_jamba_cell.py` has `ssm_scan_padded_pct` read; and a
program without the counters (the parent of the PR, GPT-2) leaves it out
of the line and raises nothing."""

import json
import os

import pytest

from benchmark import run
from benchmark.cells import HERE, Cells

from conftest import ROOT, TINY_GPT, write_bench
from test_jamba_cell import (CELL, TINY, TINY_CHAT, TINY_JAMBA, _layer,
                             _run)

NAME = "prefill_tokens_padded_pct"


def test_the_metric_file_names_its_reader_and_the_entry_lists_the_cell():
    cells = Cells(ROOT)
    with open(os.path.join(HERE, "metrics", NAME + ".json")) as f:
        desc = json.load(f)
    entry = next(m for m in cells.bench["per_layer"] if m["name"] == NAME)
    assert desc["name"] == NAME and "cells" not in desc
    assert desc["reader"] == "record_share" and desc["params"] == {
        "field": "prefill_positions_padded",
        "per": "prefill_positions_worked", "once_per": "t_decode0"}
    assert {k: desc[k] for k in ("unit", "better", "source", "layer",
                                 "moves")} \
        == {k: entry[k] for k in ("unit", "better", "source", "layer",
                                  "moves")} \
        == {"unit": "%", "better": "lower", "source": "program_counter",
            "layer": "model step and kernels",
            "moves": "serve_tokens_per_s"}
    # no family in the name: the next prefill that packs joins the list
    assert entry["workloads"] == [CELL]
    assert cells.bench["per_layer"][-1] is entry
    assert NAME in [m["name"] for m in cells.metrics("per_layer", CELL)]


def test_the_reader_by_hand():
    """Each group's counters once, summed over the window's groups."""
    spec, read = Cells(ROOT).reader(NAME)
    group = lambda t0, **c: [dict(c, t_decode0=t0, tokens=[1, 2])] * 3
    recs = group(1.0, prefill_positions_worked=4096,
                 prefill_positions_padded=512) \
        + group(2.0, prefill_positions_worked=3072,
                prefill_positions_padded=180)
    assert read(_run(recs), spec["params"]) == pytest.approx(
        100.0 * (512 + 180) / (4096 + 3072))
    assert read(_run([{"t_decode0": 1.0, "tokens": [1]}]),
                spec["params"]) is None


def _cells(tmp_path, config):
    names = TINY + [NAME]
    extra = []
    for n in names:
        with open(os.path.join(HERE, "metrics", n + ".json")) as f:
            extra.append((f"metrics/{n}.json", f.read()))
    write_bench(str(tmp_path), {"tiny": config}, {"tiny-chat": TINY_CHAT},
                [{"name": "jamba-cell", "config": "tiny",
                  "traffic": "tiny-chat", "chips": 1, "why": "a test"}],
                [_layer(n) for n in names], extra)
    return Cells(str(tmp_path))


def test_it_reads_from_a_tiny_cells_records_and_not_from_gpt2s(tmp_path,
                                                               quiet):
    """The block of 4 x 64 is one tile at the family's own tile and is
    worked whole: 256 positions, of which 33 + 36 + 41 + 52 are real."""
    lines, log = quiet
    traced = run.run_cell(_cells(tmp_path / "jamba", TINY_JAMBA),
                          "jamba-cell", 7, 0.3, True, platform="cpu",
                          log=log)
    assert traced["correct"] is True and traced["failed"] == 0, lines
    assert traced["metrics"][NAME]["value"] == pytest.approx(
        100.0 * (1 - 162 / 256))
    with open(os.path.join(str(tmp_path), "jamba", "benchmark_out",
                           "jamba-cell", "seed7-trace1",
                           "records.json")) as f:
        records = json.load(f)["records"]
    for rec in records:
        assert rec["prefill_positions"] == 162
        assert rec["prefill_positions_worked"] == 256
        assert rec["prefill_positions_padded"] == 94
    other = run.run_cell(_cells(tmp_path / "gpt", TINY_GPT), "jamba-cell", 7,
                         0.3, True, platform="cpu", log=log)
    assert NAME not in other["metrics"]
    assert "decode_ms_per_step_p50" in other["metrics"]
