"""The Ouro-2.6B cell's files load, its traffic tables follow their
stated rule, a tiny copy of the cell runs through `run_cell` on the CPU
as the others do and reads `correct` (and its float8 control does not
pass), its new per-layer metrics are read where there is something to
read, and left out (never raised) where there is not: a CPU trace, or a
program without the counters."""

import json
import math
import os
import statistics

import pytest

from benchmark import control, flops_ouro as flops, run, spans
from benchmark.cells import HERE, Cells

from conftest import (ROOT, SERVING, SERVING_ON_THE_CPU, SETUP, TINY_GPT,
                      write_bench)

CELL = "ouro26-serve-reason8"

KW = {"vocab_size": 96, "units": 64, "num_layers": 2, "num_heads": 4,
      "kv_heads": 4, "head_dim": 16, "hidden_size": 96, "loop_steps": 3,
      "exit_threshold": 1.0, "max_length": 64, "dtype": "float32",
      "grad_req": "null"}

TINY_OURO = {
    "name": "tiny-ouro", "source": "a test's own", "model_type": "ouro",
    "hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "intermediate_size": 96,
    "total_ut_steps": 3, "early_exit_threshold": 1, "vocab_size": 96,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000, "rope_scaling": None,
    "hidden_act": "silu", "tie_word_embeddings": False,
    "use_sliding_window": False, "initializer_range": 0.1,
    "seeded": {"embed_weight": "normal:1.0"},
    "n_positions": 64, "reduced": [], "reference": "ouro",
    "program": {
        "constructor": "mxnet_tpu.gluon.model_zoo.ouro.OuroModel",
        "kwargs": KW, "dtype": "float32"}}

# one prefill bucket (64) for all four prompts; the cell's own batcher
# delay.  In float32 the served tokens lie 1e-5 under the reference's
# best; the float8 control's lie 0.1 and more under it
TINY_REASON = {
    "kind": "serve_closed", "clients": 4, "batch_buckets": [4],
    "prompt_lengths": [33, 36, 41, 52], "output_lengths": [2, 3, 5, 8],
    "rate_metric": "serve_tokens_per_s", "work_unit": "tokens",
    "trace_seconds": 0.01, "check_tokens": 20,
    "batcher": {"max_delay_ms": 200.0},
    "limits": {"served_token_logit_gap_max": 0.01}}

OWN = ["decode_cache_write_pct.ouro", "decode_attn_pct.ouro",
       "decode_mlp_pct.ouro", "decode_unscoped_pct.ouro",
       "prefill_unscoped_pct.ouro", "prefill_attn_full_pct.ouro",
       "decode_step_roofline.ouro", "decode_attn_roofline.ouro",
       "prefill_attn_full_roofline.ouro", "decode_loop_exit_pct",
       "loop_passes_per_token"]
# the cell's entries in BENCHMARK.json's order; a tiny run leaves the
# start-up metrics out (they read the process's own start)
TINY = SERVING + OWN
NAMES = TINY + SETUP
READ_ON_THE_CPU = SERVING_ON_THE_CPU + ["loop_passes_per_token"]
SCOPES = ["serve.embed", "serve.attn_qkv", "serve.cache_write",
          "serve.attn", "serve.attn_full", "serve.attn_out", "serve.mlp",
          "serve.loop_norm", "serve.exit", "serve.head", "serve.sample"]


def _quantiles(median, sigma, lo, hi, n=8):
    inv = statistics.NormalDist().inv_cdf
    return [min(max(math.floor(median * math.exp(
        sigma * inv((i + 0.5) / n))), lo), hi) for i in range(n)]


def test_the_cells_files_load():
    import numpy as np

    cells = Cells(ROOT)
    cell = cells.cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    assert cell["chips"] == 1 and cell["kind"].__name__.endswith(
        "serve_closed")
    spec = cell["reference"].param_spec(config)
    total = sum(int(np.prod(s)) for _, s, _ in spec)
    # the configuration's table: 48 layers, embedding and head, the final
    # gain and the gate
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert flops.layer_params(config) == layer == 51_388_416
    assert total == 48 * layer + 2 * 49152 * 2048 + 2048 + 2048 + 1 \
        == 2_667_974_657
    assert flops.step_params(config) == 4 * (48 * layer + 2 * 2048 + 1) \
        + 49152 * 2048
    # 1.5 MiB a cached position; the cell's cache
    assert flops.slots(config) == 192
    assert flops.slot_bytes(config, 2) == 8192
    assert 192 * 8192 == 1_572_864
    assert 8 * config["n_positions"] * 192 * 8192 == 6_442_450_944
    # the traffic is what the issue names, number for number
    assert traffic["clients"] == 8 and traffic["batch_buckets"] == [8]
    assert traffic["prompt_lengths"] == _quantiles(128, 0.6, 48, 320) \
        == [50, 75, 95, 116, 140, 171, 217, 320]
    assert traffic["output_lengths"] == _quantiles(96, 0.6, 32, 192) \
        == [38, 56, 71, 87, 105, 128, 163, 192]
    assert sum(traffic["prompt_lengths"]) == 1184
    assert sum(traffic["output_lengths"]) == 840
    assert traffic["batcher"] == {"max_delay_ms": 200.0}
    assert traffic["check_tokens"] == 300 and traffic["trace_seconds"] == 2
    assert max(traffic["prompt_lengths"]) + max(traffic["output_lengths"]) \
        == config["n_positions"] == 512
    for key in ("deployment", "assumed", "reduced_why"):
        assert key in config
    for key in ("n_positions", "bias", "norms", "loop_input", "exit_rule",
                "rope_pairing", "initializer_range", "weights", "unused"):
        assert key in config["assumed"], key
    inits = {name: init for name, _, init in spec}
    assert config["seeded"] and all(inits[k] == v for k, v
                                    in config["seeded"].items())
    assert inits["o_weight"] == f"normal:{config['initializer_range']}"
    assert inits["exit_bias"] == "zeros" and inits["ln2_gamma"] == "ones"
    with pytest.raises(ValueError, match="no leaf"):
        cell["reference"].param_spec(dict(config, seeded={"nope": "ones"}))
    with pytest.raises(ValueError, match="full attention"):
        cell["reference"].sizes(dict(config, use_sliding_window=True))
    assert [m["name"] for m in cells.metrics("per_layer", CELL)] == NAMES
    # the catalog's numbers, every one, and nothing reduced
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    source = next(r for r in rows if r["name"] == "Ouro-2.6B")
    assert config["source"] == source["source_url"]
    assert {k for k, v in source["config"].items()
            if k not in config or config[k] != v} == set()
    assert config["reduced"] == []
    # the program is built at the same sizes
    kw = config["program"]["kwargs"]
    assert (kw["units"], kw["num_layers"], kw["num_heads"], kw["kv_heads"],
            kw["head_dim"], kw["hidden_size"], kw["loop_steps"],
            kw["vocab_size"], kw["max_length"], kw["exit_threshold"],
            kw["rope_theta"], kw["eps"]) == (
        config["hidden_size"], config["num_hidden_layers"],
        config["num_attention_heads"], config["num_key_value_heads"],
        config["head_dim"], config["intermediate_size"],
        config["total_ut_steps"], config["vocab_size"],
        config["n_positions"], config["early_exit_threshold"],
        config["rope_theta"], config["rms_norm_eps"])


def test_each_metric_file_names_a_reader_and_the_cell():
    entries = {m["name"]: m for m in Cells(ROOT).bench["per_layer"]}
    for n in NAMES:
        with open(os.path.join(HERE, "metrics", n + ".json")) as f:
            desc = json.load(f)
        # the entry's ``workloads`` is the one list of a metric's cells
        assert desc["name"] == n and "cells" not in desc
        assert CELL in entries[n]["workloads"]
        assert desc["moves"] == ("setup_s" if n in SETUP
                                 else "serve_tokens_per_s")
        assert os.path.isfile(os.path.join(HERE, "readers",
                                           desc["reader"] + ".py"))
        scopes = desc.get("params", {}).get("scopes")
        assert scopes is None or scopes == SCOPES, n
        for s in desc.get("params", {}).get("under", []):
            assert s in SCOPES


def test_the_programs_scopes_are_the_metric_files():
    """Every scope the metric files name is in the traced step, prefill
    or decode, and the step names no other."""
    import re

    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.gluon.model_zoo import ouro

    net = ouro.OuroModel(**KW)
    net.initialize(init=mx.init.Zero())
    eng = serving.ServingEngine(net, batch_buckets=(2,))
    found = set()
    for S in (8, 1):
        text = jax.jit(eng._step["decode" if S == 1 else "prefill"]).lower(
            eng._weights, eng.init_cache(2), np.zeros(2, np.int32),
            np.zeros(2, np.int32), np.zeros((2, S), np.int32)
        ).as_text(debug_info=True)
        found |= set(re.findall(r"serve\.[a-z_.]+", text))
    assert found == set(SCOPES), found


def _layer(name):
    return {"name": name, "unit": "%", "better": "lower",
            "source": "program_counter", "layer": "model step and kernels",
            "moves": "serve_tokens_per_s"}


def _cells(tmp_path, config):
    extra = []
    for n in TINY:
        with open(os.path.join(HERE, "metrics", n + ".json")) as f:
            extra.append((f"metrics/{n}.json", f.read()))
    write_bench(str(tmp_path), {"tiny": config}, {"tiny-reason": TINY_REASON},
                [{"name": "ouro-cell", "config": "tiny",
                  "traffic": "tiny-reason", "chips": 1, "why": "a test"}],
                [_layer(n) for n in TINY], extra)
    return Cells(str(tmp_path))


def test_the_cell_runs_tiny_through_run_cell(tmp_path, quiet):
    lines, log = quiet
    cells = _cells(tmp_path, TINY_OURO)
    out = run.run_cell(cells, "ouro-cell", 2 ** 31 + 11, 0.3, False,
                       platform="cpu", log=log)
    assert out["correct"] is True and out["failed"] == 0, lines
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0
    traced = run.run_cell(cells, "ouro-cell", 7, 0.3, True, platform="cpu",
                          log=log)
    got = traced["metrics"]
    # counters and host spans are read on the CPU too; what needs a
    # device plane is left out of the line
    assert sorted(got) == sorted(READ_ON_THE_CPU)
    assert got["decode_ms_per_step_p50"]["value"] > 0
    assert 0.0 < got["decode_rows_useful_pct"]["value"] <= 100.0
    # every loop step runs for every decoded token
    assert got["loop_passes_per_token"]["value"] == 3.0
    with open(os.path.join(str(tmp_path), "benchmark_out", "ouro-cell",
                           "seed7-trace1", "records.json")) as f:
        records = json.load(f)["records"]
    for rec in records:
        assert rec["loop_exit_step_prefill"] == [0, 0, 4]
        assert rec["loop_exit_step_decode"][:2] == [0, 0]
        assert rec["loop_passes_prefill"] == 3


def test_verify_passes_sound_and_fails_the_control(tmp_path, quiet):
    """`control.py`'s two readings on the tiny cell: the served tokens
    lie under the limit, the float8 reference's own tokens do not."""
    cells = _cells(tmp_path, TINY_OURO)
    out = control.read(cells, "ouro-cell", [3, 2 ** 31 + 5], 0.3,
                       platform="cpu", log=quiet[1])
    limit = TINY_REASON["limits"]["served_token_logit_gap_max"]
    assert out["correct"] == [True, True]
    assert max(out["sound"]["served_token_logit_gap_max"]) < limit / 10
    assert min(out["control"]["served_token_logit_gap_max"]) > 5 * limit


def test_a_program_without_the_counters_reads_nothing(tmp_path, quiet):
    """The same metrics over GPT-2's records (as the parent of this PR
    would give them for a cell it can run): left out, not raised."""
    cells = _cells(tmp_path, TINY_GPT)
    traced = run.run_cell(cells, "ouro-cell", 7, 0.3, True, platform="cpu",
                          log=quiet[1])
    assert "decode_step_roofline.ouro" not in traced["metrics"]
    assert "decode_attn_roofline.ouro" not in traced["metrics"]
    assert "loop_passes_per_token" not in traced["metrics"]
    assert "decode_ms_per_step_p50" in traced["metrics"]


def _run(records, modules=None):
    return {"records": records, "cell": {"name": "x", "config": TINY_OURO},
            "trace": {"modules": modules or {}},
            "peaks": {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}}


def _group(t0, lengths, **counters):
    return [dict(counters, t_decode0=t0, prompt=list(range(20)),
                 tokens=list(range(n))) for n in lengths]


def test_readers_by_hand(monkeypatch):
    cells = Cells(ROOT)
    counters = dict(attn_positions_decode=999, attn_positions_prefill=1000,
                    loop_passes_decode=6, loop_passes_prefill=3,
                    decode_steps_fed_on_device=2)
    recs = _group(1.0, (3, 2), **counters) + _group(2.0, (3, 3), **counters)
    roof = cells.module("readers", "decode_roofline_ouro").read
    run_ = _run(recs, {"jit_serve_decode(1)": [2e-3, 4e-3, 9e-3],
                       "jit_serve_prefill(2)": [1.0]})
    # two steps a group; the second row of the first group is done
    # after one
    lives = [[21, 21], [22], [21, 21], [22, 22]]
    need = statistics.mean(
        flops.decode_step_bytes(TINY_OURO, 2, live) for live in lives)
    assert roof(run_, {"itemsize_of": "float16"}) == pytest.approx(
        100.0 * need / 1e9 / 5e-3)        # the mean execution, not 4e-3
    assert roof(_run(recs), {}) is None          # no trace of the program
    assert roof(_run([{"t_decode0": 1.0, "tokens": [1]}],
                     {"jit_serve_decode": [1.0]}), {}) is None
    ratio = cells.module("readers", "record_ratio").read
    per_token = {"field": "loop_passes_decode",
                 "per": "decode_steps_fed_on_device",
                 "once_per": "t_decode0"}
    assert ratio(_run(recs), per_token) == 3.0      # each group once
    assert ratio(_run([{"t_decode0": 1.0}]), per_token) is None

    tr = {"programs": {"jit_serve_prefill": {"a": 0.5, "d": 0.2},
                       "jit_serve_decode": {"b": 0.25, "c": 0.05,
                                            "e": 0.1, "f": 0.1}},
          "paths": {"jit_serve_prefill": {
              "a": "jit(serve_prefill)/while/body/while/body/"
                   "serve.attn_full/call",
              "d": "jit(serve_prefill)/serve.head/dot"},
                    "jit_serve_decode": {
              "b": "jit(serve_decode)/while/body/while/body/serve.attn/call",
              "c": "jit(serve_decode)/while/body/serve.loop_norm/mul",
              "e": "jit(serve_decode)/while/body/serve.exit/dot",
              "f": "jit(serve_decode)/serve.head/dot"}}}
    monkeypatch.setattr(spans, "of_run", lambda run: tr)
    share = cells.module("readers", "attn_roofline_ouro").read
    full = {"program": "jit_serve_prefill", "scopes": SCOPES,
            "phase": "prefill", "under": ["serve.attn_full"]}
    # each group's counter once (it counts every slot already)
    assert share(_run(recs), full) == pytest.approx(
        100.0 * flops.attn_flops(TINY_OURO, 2000) / 1e12 / 0.5)
    attn = {"program": "jit_serve_decode", "scopes": SCOPES,
            "phase": "decode", "under": ["serve.attn"]}
    # the rows that still want a token, 3 x 2 slots: not the counter
    positions = 6 * sum(sum(live) for live in lives)
    t_bytes = positions * 2 * 4 * 16 * 2 / 1e9
    t_flops = 2 * positions * 4 * 32 / 1e12
    assert flops.decode_attn_bytes(TINY_OURO, positions, 2) / 1e9 == t_bytes
    assert flops.attn_flops(TINY_OURO, positions) / 1e12 == t_flops
    assert share(_run(recs), attn) == pytest.approx(
        100.0 * max(t_bytes, t_flops) / 0.25)
    assert share(_run([{"t_decode0": 1.0}]), attn) is None
    both = cells.module("readers", "scope_share_sum").read
    assert both(_run([]), dict(attn, under=[
        "serve.loop_norm", "serve.exit"])) == pytest.approx(
        100.0 * 0.15 / 0.5)
    one = cells.module("readers", "scope_share").read
    assert one(_run([]), dict(attn, scope="serve.attn")) == pytest.approx(
        50.0)
    monkeypatch.setattr(spans, "of_run", lambda run: None)
    assert share(_run(recs), full) is None
    assert both(_run([]), attn) is None


def test_the_counting_functions_by_hand():
    c = TINY_OURO
    # a layer: q, k and v of 4 heads of 16 each from 64, W_o, a SwiGLU of
    # 96, four gains
    layer = 3 * 64 * 64 + 64 * 64 + 3 * 64 * 96 + 4 * 64
    assert flops.layer_params(c) == layer
    assert flops.slots(c) == 6 and flops.slot_bytes(c, 2) == 2 * 4 * 16 * 2
    # a pass: two layers, the final gain, the gate and its bias
    assert flops.pass_params(c) == 2 * layer + 64 + 64 + 1
    assert flops.step_params(c) == 3 * flops.pass_params(c) + 96 * 64
    # rows of 5 and 30 positions read 35 positions in each of 6 slots
    assert flops.decode_step_bytes(c, 2, [5, 30]) == \
        2 * flops.step_params(c) + 35 * 6 * 256
    assert flops.attn_flops(c, 10) == 2 * 10 * 4 * 32
    assert flops.token_flops(c) == 2 * (6 * (layer - 256) + 96 * 64)
    assert flops.decode_step_flops(c, 2, 210) == \
        2 * flops.token_flops(c) + flops.attn_flops(c, 210)
    assert flops.prefill_flops(c, 7, 28) == \
        2 * 7 * 6 * (layer - 256) + flops.attn_flops(c, 28)
    # the published sizes: 8,192 B a position and slot; a decode step of
    # 8 rows of 220 positions reads 19.93 GB of weights and 2.77 GB of
    # cache: 27.7 ms at 819 GB/s, 88 % of it weights; its operations
    # (8 x 19.9 GFLOP) would take 0.9 ms
    big = Cells(ROOT).cell(CELL)["config"]
    assert flops.decode_attn_bytes(big, 1, 2) == 8192
    assert flops.attn_flops(big, 1) == 16 * 256 * 2
    weights = flops.step_params(big) * 2
    assert round(weights / 1e9, 2) == 19.93
    step = flops.decode_step_bytes(big, 2, [220] * 8)
    assert round((step - weights) / 1e9, 2) == 2.77
    assert round(step / 819e9 * 1e3, 1) == 27.7
    assert round(flops.token_flops(big) / 1e9, 1) == 19.9
    assert round(flops.prefill_flops(big, 4096, 0) / 1e12, 1) == 80.8
