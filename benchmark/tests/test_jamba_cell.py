"""The Jamba2-3B cell's files load and say what the issue names (the
parameter count from the configuration file, the traffic tables' law),
`flops_jamba`'s counts equal hand figures, a tiny copy of the cell runs
through `run_cell` on the CPU as the others do and reads `correct` (and
its float8 control does not pass), its per-layer metrics are read where
there is something to read, and left out (never raised) where there is
not: a CPU trace, or a program without the counters."""

import json
import math
import os
import statistics

import pytest

from benchmark import control, flops_jamba as flops, run, spans
from benchmark.cells import HERE, Cells

from conftest import (ROOT, SERVING, SERVING_ON_THE_CPU, SETUP, TINY_GPT,
                      write_bench)

CELL = "jamba2-serve-chat128"

KW = {"vocab_size": 96, "units": 64, "num_layers": 14, "num_heads": 4,
      "kv_heads": 1, "hidden_size": 96, "attn_period": 14, "attn_offset": 7,
      "d_state": 16, "d_conv": 4, "dt_rank": 4, "expand": 2,
      "max_length": 64, "dtype": "float32", "grad_req": "null"}

TINY_JAMBA = {
    "name": "tiny-jamba", "source": "a test's own", "model_type": "jamba",
    "hidden_size": 64, "num_hidden_layers": 14, "num_attention_heads": 4,
    "num_key_value_heads": 1, "intermediate_size": 96, "vocab_size": 96,
    "rms_norm_eps": 1e-6, "hidden_act": "silu", "tie_word_embeddings": True,
    "mamba_expand": 2, "mamba_d_state": 16, "mamba_dt_rank": 4,
    "mamba_d_conv": 4, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "attn_layer_period": 14, "attn_layer_offset": 7, "num_experts": 1,
    "initializer_range": 0.1,
    "seeded": {"a_log_weight": "uniform:5", "conv_weight": "uniform:0.5"},
    "n_positions": 64, "reduced": [], "reference": "jamba",
    "program": {
        "constructor": "mxnet_tpu.gluon.model_zoo.jamba.JambaModel",
        "kwargs": KW, "dtype": "float32"}}

# one prefill bucket (64) for all four prompts.  In float32 the served
# tokens lie 1e-5 under the reference's best; the float8 control's lie
# 0.1 and more under it
TINY_CHAT = {
    "kind": "serve_closed", "clients": 4, "batch_buckets": [4],
    "prompt_lengths": [33, 36, 41, 52], "output_lengths": [2, 3, 5, 8],
    "rate_metric": "serve_tokens_per_s", "work_unit": "tokens",
    "trace_seconds": 0.01, "check_tokens": 20,
    "batcher": {"max_delay_ms": 200.0},
    "limits": {"served_token_logit_gap_max": 0.01}}

OWN = ["prefill_ssm_scan_pct", "prefill_ssm_proj_pct", "prefill_ssm_conv_pct",
       "prefill_mlp_pct.jamba", "prefill_attn_full_pct.jamba",
       "prefill_unscoped_pct.jamba", "prefill_ssm_scan_roofline",
       "decode_ssm_update_pct", "decode_ssm_proj_pct", "decode_ssm_conv_pct",
       "decode_mlp_pct.jamba", "decode_attn_pct.jamba",
       "decode_unscoped_pct.jamba", "decode_ssm_update_roofline",
       "decode_step_roofline.jamba", "ssm_scan_padded_pct"]
# the cell's entries in BENCHMARK.json's order: the generic ones, the
# start-up ones, and behind them this PR's own, appended
NAMES = SERVING + SETUP + OWN
TINY = SERVING + OWN
READ_ON_THE_CPU = SERVING_ON_THE_CPU + ["ssm_scan_padded_pct"]
SCOPES = ["serve.embed", "serve.ssm_in", "serve.ssm_conv", "serve.ssm_x",
          "serve.ssm_scan", "serve.ssm_update", "serve.ssm_out",
          "serve.attn_qkv", "serve.cache_write", "serve.attn_full",
          "serve.attn", "serve.attn_out", "serve.mlp", "serve.head",
          "serve.sample"]


def _quantiles(median, sigma, lo, hi, n=128):
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
    # the parameter count, from the configuration file: the reference's
    # leaves and `flops_jamba` each derive the issue's figure
    spec = cell["reference"].param_spec(config)
    total = sum(int(np.prod(s)) for _, s, _ in spec)
    assert flops.ssm_mixer_params(config) == 41_241_792
    assert flops.attn_mixer_params(config) == 13_762_560
    assert flops.mlp_params(config) == 62_914_560 + 5_120
    assert (flops.ssm_layers(config), flops.attn_layers(config)) == (26, 2)
    assert [i for i, k in enumerate(flops.kinds(config)) if k == "attn"] \
        == [7, 21] == [i for i, k in enumerate(
            cell["reference"].kinds(config)) if k == "attn"]
    assert total == flops.total_params(config) == 3_029_337_472 \
        == 26 * 104_161_472 + 2 * 76_682_240 + 65_536 * 2_560 + 2_560
    # what a row holds: 1,024 B a cached position, 9.32 MB of states
    # and tails whatever its length
    assert flops.position_bytes(config, 2) == 1_024
    assert flops.row_state_bytes(config, 2) == 26 * (16 * 5120 * 4
                                                     + 3 * 5120 * 2) \
        == 9_318_400
    # the traffic is what the issue names, number for number
    assert traffic["clients"] == 128 and traffic["batch_buckets"] == [128]
    assert traffic["prompt_lengths"] == _quantiles(160, 0.8, 32, 512)
    assert traffic["output_lengths"] == _quantiles(48, 0.9, 8, 192)
    assert sum(traffic["prompt_lengths"]) == 25_712
    assert sum(traffic["output_lengths"]) == 8_274
    assert traffic["batcher"] == {"max_delay_ms": 200.0}
    assert traffic["check_tokens"] == 300 and traffic["trace_seconds"] == 2
    assert max(traffic["prompt_lengths"]) + max(traffic["output_lengths"]) \
        == config["n_positions"] == 704
    for key in ("deployment", "assumed", "reduced_why"):
        assert key in config
    for key in ("n_positions", "layer_order", "dense", "in_proj_order",
                "x_proj_order", "inner_norms", "bias", "positions",
                "state_precision", "layouts", "initializer_range", "weights",
                "unused"):
        assert key in config["assumed"], key
    inits = {name: init for name, _, init in spec}
    assert config["seeded"] and all(inits[k] == v for k, v
                                    in config["seeded"].items())
    assert inits["in_weight"] == f"normal:{config['initializer_range']}"
    assert inits["d_weight"] == "ones" and inits["dt_bias"] == "zeros"
    assert [m["name"] for m in cells.metrics("per_layer", CELL)] == NAMES
    assert "moe_rows_padded_pct" not in NAMES
    # the catalog's numbers, every one, and nothing reduced
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    source = next(r for r in rows if r["name"] == "AI21-Jamba2-3B")
    assert config["source"] == source["source_url"]
    assert {k for k, v in source["config"].items()
            if k not in config or config[k] != v} == set()
    assert config["reduced"] == []
    # the program is built at the same sizes
    kw = config["program"]["kwargs"]
    assert (kw["units"], kw["num_layers"], kw["num_heads"], kw["kv_heads"],
            kw["hidden_size"], kw["attn_period"], kw["attn_offset"],
            kw["d_state"], kw["d_conv"], kw["dt_rank"], kw["expand"],
            kw["vocab_size"], kw["max_length"], kw["eps"]) == (
        config["hidden_size"], config["num_hidden_layers"],
        config["num_attention_heads"], config["num_key_value_heads"],
        config["intermediate_size"], config["attn_layer_period"],
        config["attn_layer_offset"], config["mamba_d_state"],
        config["mamba_d_conv"], config["mamba_dt_rank"],
        config["mamba_expand"], config["vocab_size"], config["n_positions"],
        config["rms_norm_eps"])


def test_each_metric_file_names_a_reader_and_the_cell():
    entries = {m["name"]: m for m in Cells(ROOT).bench["per_layer"]}
    for n in NAMES:
        with open(os.path.join(HERE, "metrics", n + ".json")) as f:
            desc = json.load(f)
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
    # this PR's own are the cell's alone, and `per_layer` has room left
    for n in OWN:
        assert entries[n]["workloads"] == [CELL]
    assert len(entries) == 111


def test_the_programs_scopes_are_the_metric_files():
    """Every scope the metric files name is in the traced step, prefill
    or decode, and the step names no other."""
    import re

    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.gluon.model_zoo import jamba

    net = jamba.JambaModel(**KW)
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
    write_bench(str(tmp_path), {"tiny": config}, {"tiny-chat": TINY_CHAT},
                [{"name": "jamba-cell", "config": "tiny",
                  "traffic": "tiny-chat", "chips": 1, "why": "a test"}],
                [_layer(n) for n in TINY], extra)
    return Cells(str(tmp_path))


def test_the_cell_runs_tiny_and_the_control_fails(tmp_path, quiet):
    """Through `run_cell`, plain and traced, and `control.py`'s two
    readings: the served tokens lie under the limit, the float8
    reference's own tokens do not."""
    lines, log = quiet
    cells = _cells(tmp_path, TINY_JAMBA)
    traced = run.run_cell(cells, "jamba-cell", 7, 0.3, True, platform="cpu",
                          log=log)
    assert traced["correct"] is True and traced["failed"] == 0, lines
    got = traced["metrics"]
    # counters and host spans are read on the CPU too; what needs a
    # device plane is left out of the line
    assert sorted(got) == sorted(READ_ON_THE_CPU)
    # the plain scan walks the bucket whole: 4 x 64 positions a layer
    # of which 33 + 36 + 41 + 52 are real
    assert got["ssm_scan_padded_pct"]["value"] == pytest.approx(
        100.0 * (1 - 162 / 256))
    with open(os.path.join(str(tmp_path), "benchmark_out", "jamba-cell",
                           "seed7-trace1", "records.json")) as f:
        records = json.load(f)["records"]
    for rec in records:
        assert rec["ssm_positions_prefill"] == 13 * 162
        assert rec["ssm_positions_scanned_prefill"] == 13 * 256
        assert rec["decode_state_update_kernel_share"] == 0.0
    out = control.read(cells, "jamba-cell", [2 ** 31 + 5], 0.3,
                       platform="cpu", log=log)
    limit = TINY_CHAT["limits"]["served_token_logit_gap_max"]
    assert out["correct"] == [True]
    assert max(out["sound"]["served_token_logit_gap_max"]) < limit / 10
    assert min(out["control"]["served_token_logit_gap_max"]) > 5 * limit


def test_a_program_without_the_counters_reads_nothing(tmp_path, quiet):
    """The same metrics over GPT-2's records (as the parent of this PR
    would give them for a cell it can run): left out, not raised."""
    cells = _cells(tmp_path, TINY_GPT)
    traced = run.run_cell(cells, "jamba-cell", 7, 0.3, True, platform="cpu",
                          log=quiet[1])
    for n in OWN:
        assert n not in traced["metrics"]
    assert "decode_ms_per_step_p50" in traced["metrics"]


def _run(records, modules=None):
    return {"records": records, "cell": {"name": "x", "config": TINY_JAMBA},
            "trace": {"modules": modules or {}},
            "peaks": {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}}


def _group(t0, lengths, **counters):
    return [dict(counters, t_decode0=t0, prompt=list(range(20)),
                 tokens=list(range(n))) for n in lengths]


def test_readers_by_hand(monkeypatch):
    cells = Cells(ROOT)
    c = TINY_JAMBA
    counters = dict(ssm_positions_prefill=1000, ssm_row_updates_decode=39,
                    ssm_positions_scanned_prefill=1600,
                    ssm_positions_padded_prefill=600)
    recs = _group(1.0, (3, 2), **counters) + _group(2.0, (3, 3), **counters)
    roof = cells.module("readers", "decode_roofline_jamba").read
    run_ = _run(recs, {"jit_serve_decode(1)": [2e-3, 4e-3, 9e-3],
                       "jit_serve_prefill(2)": [1.0]})
    # two steps a group; the second row of the first group is done
    # after one
    lives = [[21, 21], [22], [21, 21], [22, 22]]
    need = statistics.mean(
        flops.decode_step_bytes(c, 2, live) for live in lives)
    assert roof(run_, {"itemsize_of": "float16"}) == pytest.approx(
        100.0 * need / 1e9 / 5e-3)        # the mean execution, not 4e-3
    assert roof(_run(recs), {}) is None          # no trace of the program
    assert roof(_run([{"t_decode0": 1.0, "tokens": [1]}],
                     {"jit_serve_decode": [1.0]}), {}) is None
    padded = cells.reader("ssm_scan_padded_pct")
    assert padded[1](_run(recs), padded[0]["params"]) == pytest.approx(37.5)
    assert padded[1](_run([{"t_decode0": 1.0}]), padded[0]["params"]) is None

    tr = {"programs": {"jit_serve_prefill": {"a": 0.5, "d": 0.2, "g": 0.3},
                       "jit_serve_decode": {"b": 0.25, "c": 0.05,
                                            "e": 0.1, "f": 0.1}},
          "paths": {"jit_serve_prefill": {
              "a": "jit(serve_prefill)/while/body/serve.ssm_scan/call",
              "d": "jit(serve_prefill)/serve.head/dot",
              "g": "jit(serve_prefill)/while/body/serve.ssm_x/dot"},
                    "jit_serve_decode": {
              "b": "jit(serve_decode)/serve.ssm_update/call",
              "c": "jit(serve_decode)/serve.ssm_in/dot",
              "e": "jit(serve_decode)/serve.ssm_out/dot",
              "f": "jit(serve_decode)/serve.head/dot"}}}
    monkeypatch.setattr(spans, "of_run", lambda run: tr)
    scan = cells.reader("prefill_ssm_scan_roofline")
    # each group's counter once: 2,000 (position, layer) pairs
    t_bytes = flops.scan_bytes(c, 2000, 2) / 1e9
    t_flops = flops.scan_ops(c, 2000) / 1e12
    assert t_bytes > t_flops
    assert scan[1](_run(recs), scan[0]["params"]) == pytest.approx(
        100.0 * t_bytes / 0.5)
    update = cells.reader("decode_ssm_update_roofline")
    assert update[1](_run(recs), update[0]["params"]) == pytest.approx(
        100.0 * flops.update_bytes(c, 78, 2) / 1e9 / 0.25)
    assert update[1](_run([{"t_decode0": 1.0}]), update[0]["params"]) is None
    proj = cells.reader("decode_ssm_proj_pct")
    assert proj[1](_run([]), proj[0]["params"]) == pytest.approx(
        100.0 * 0.15 / 0.5)
    one = cells.reader("prefill_ssm_scan_pct")
    assert one[1](_run([]), one[0]["params"]) == pytest.approx(50.0)
    monkeypatch.setattr(spans, "of_run", lambda run: None)
    assert scan[1](_run(recs), scan[0]["params"]) is None
    assert proj[1](_run([]), proj[0]["params"]) is None


def test_the_counting_functions_by_hand():
    c = TINY_JAMBA
    C, E, N, R, k, F, V = 64, 128, 16, 4, 4, 96, 96
    mixer = (C * 2 * E + E * k + E + E * (R + 2 * N) + R * E + E + E * N + E
             + R + 2 * N + E * C)
    assert flops.ssm_mixer_params(c) == mixer
    attn = 2 * 64 * 64 + 2 * 16 * 64
    assert flops.attn_mixer_params(c) == attn
    mlp = 3 * C * F + 2 * C
    assert flops.mlp_params(c) == mlp
    assert flops.total_params(c) == 13 * (mixer + mlp) + attn + mlp + V * C + C
    products = C * 2 * E + E * (R + 2 * N) + R * E + E * C
    assert flops.token_flops(c) == 2 * (13 * (products + 3 * C * F)
                                        + attn + 3 * C * F + V * C)
    assert flops.position_bytes(c, 2) == 2 * 16 * 2
    assert flops.row_state_bytes(c, 2) == 13 * (N * E * 4 + 3 * E * 2)
    # rows of 5 and 30 positions: both rows' states and tails in and
    # out, 35 cached positions in
    assert flops.decode_step_bytes(c, 2, [5, 30]) == \
        2 * flops.total_params(c) + 2 * 2 * flops.row_state_bytes(c, 2) \
        + 35 * 64
    assert flops.scan_ops(c, 10) == 10 * E * N * 6
    assert flops.scan_bytes(c, 10, 2) == 10 * (3 * E + 2 * N) * 2
    assert flops.update_bytes(c, 10, 2) == 10 * 2 * N * E * 4 \
        + flops.scan_bytes(c, 10, 2)
    assert flops.attn_flops(c, 10) == 2 * 10 * 2 * C
    assert flops.decode_step_flops(c, [5, 30]) == 2 * flops.token_flops(c) \
        + flops.scan_ops(c, 26) + flops.attn_flops(c, 35)
    assert flops.prefill_flops(c, 7, 28) == \
        7 * (flops.token_flops(c) - 2 * V * C) + flops.scan_ops(c, 7 * 13) \
        + flops.attn_flops(c, 28)
    # the published sizes: a decode step of 128 live rows of 300
    # positions moves 6.06 GB of weights, 2.39 GB of states and tails
    # and 0.04 GB of cache, 10.4 ms at 819 GB/s; its operations (128 x
    # 6.1 GFLOP, 5.7 of them below the head, + the updates') would take
    # 3.9 ms; the Mamba mixers' weights and states are 53 % of its bytes
    big = Cells(ROOT).cell(CELL)["config"]
    weights = flops.total_params(big) * 2
    assert round(weights / 1e9, 2) == 6.06
    step = flops.decode_step_bytes(big, 2, [300] * 128)
    states = 128 * 2 * flops.row_state_bytes(big, 2)
    assert round(states / 1e9, 2) == 2.39
    assert round((step - weights - states) / 1e9, 2) == 0.04
    assert round(step / 819e9 * 1e3, 1) == 10.4
    assert round((26 * flops.ssm_mixer_params(big) * 2 + states) / step, 2) \
        == 0.53
    assert round(flops.token_flops(big) / 1e9, 1) == 6.1
    assert round((flops.token_flops(big) - 2 * 65_536 * 2_560) / 1e9, 1) \
        == 5.7
    assert round(flops.decode_step_flops(big, [300] * 128) / 197e12 * 1e3,
                 1) == 3.9
    # a round's prefill: the scan's element updates over 65,536 padded
    # positions are 1.4e11; over the 25,712 real ones its operands are
    # 20.6 GB, 25 ms
    assert round(65_536 * 26 * 5120 * 16 / 1e11, 1) == 1.4
    assert round(flops.scan_bytes(big, 25_712 * 26, 2) / 1e9, 1) == 20.6
