"""The Kimi-K2.6 cell's files load, its traffic tables follow their
stated rule, a tiny copy of the cell runs through `run_cell` on the CPU
as the others do and reads `correct`, its new per-layer metrics are read
where there is something to read, and left out (never raised) where
there is not: a CPU trace, or a program without the counters."""

import json
import math
import os
import statistics

import pytest

from benchmark import flops_kimi_k2 as flops, run, spans
from benchmark.cells import HERE, Cells

from conftest import (PADDED, ROOT, SERVING, SERVING_ON_THE_CPU, SETUP,
                      TINY_GPT, write_bench)

CELL = "kimi26-serve-doc16k"

KW = {"vocab_size": 96, "units": 64, "num_layers": 3, "num_heads": 4,
      "q_rank": 24, "kv_rank": 16, "nope_dim": 16, "rope_dim": 8,
      "v_dim": 12, "hidden_size": 96, "expert_hidden": 32,
      "router_experts": 8, "experts_per_token": 2, "experts_held": [2, 4],
      "route_scale": 2.5, "rope_theta": 50000.0, "rope_factor": 8.0,
      "rope_original_length": 8, "mscale": 1.0, "mscale_all_dim": 1.0,
      "max_length": 64, "attn_block": 16, "token_chunk": 16,
      "prefill_chunk_tokens": 128, "dtype": "float32", "grad_req": "null"}

TINY_KIMI = {
    "name": "tiny-kimi", "source": "a test's own", "model_type": "kimi_k2",
    "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 12, "intermediate_size": 96,
    "moe_intermediate_size": 32, "n_shared_experts": 1,
    "n_routed_experts": 4, "router_experts": 8, "experts_held": [2, 4],
    "num_experts_per_tok": 2, "routed_scaling_factor": 2.5,
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "n_group": 1,
    "topk_group": 1, "scoring_func": "sigmoid", "norm_topk_prob": True,
    "rope_theta": 50000.0,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 8, "type": "yarn"},
    "vocab_size": 96, "rms_norm_eps": 1e-5, "initializer_range": 0.2,
    "n_positions": 64, "reduced": [], "reference": "kimi_k2",
    "program": {
        "constructor": "mxnet_tpu.gluon.model_zoo.kimi_k2.KimiK2Model",
        "kwargs": KW, "dtype": "float32"}}

# every prompt several times the original length and in one prefill
# bucket (64), two rows a chunk; the cells' own batcher delay
TINY_DOC = {
    "kind": "serve_closed", "clients": 4, "batch_buckets": [4],
    "prompt_lengths": [33, 36, 41, 52], "output_lengths": [2, 3, 5, 8],
    "rate_metric": "serve_tokens_per_s", "work_unit": "tokens",
    "trace_seconds": 0.01, "check_tokens": 20,
    "batcher": {"max_delay_ms": 200.0},
    "limits": {"served_token_logit_gap_max": 0.01}}

OWN = ["decode_cache_write_pct.kimi", "decode_moe_experts_pct.kimi",
       "prefill_moe_experts_pct.kimi", "decode_unscoped_pct.kimi",
       "prefill_unscoped_pct.kimi", "decode_attn_latent_pct",
       "decode_attn_proj_pct", "decode_moe_shared_pct",
       "decode_mlp_pct.kimi", "prefill_attn_full_pct.kimi",
       "prefill_attn_proj_pct", "decode_attn_latent_roofline",
       "decode_step_roofline.kimi", "prefill_attn_full_roofline.kimi"]
# the cell's entries in BENCHMARK.json's order; a tiny run leaves the
# start-up metrics out (they read the process's own start)
TINY = SERVING + [PADDED] + OWN
NAMES = TINY + SETUP
READ_ON_THE_CPU = SERVING_ON_THE_CPU + [PADDED]
SCOPES = ["serve.embed", "serve.attn_down", "serve.attn_q_up",
          "serve.attn_kv_up", "serve.cache_write", "serve.attn_latent",
          "serve.attn_full", "serve.attn_out", "serve.mlp",
          "serve.moe.route", "serve.moe.shared", "serve.moe.experts",
          "serve.head", "serve.sample"]


def _quantiles(median, sigma, lo, hi, n=8):
    inv = statistics.NormalDist().inv_cdf
    return [min(max(math.floor(median * math.exp(
        sigma * inv((i + 0.5) / n))), lo), hi) for i in range(n)]


def test_the_cells_files_load():
    cells = Cells(ROOT)
    cell = cells.cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    assert cell["chips"] == 1 and cell["kind"].__name__.endswith(
        "serve_closed")
    spec = cell["reference"].param_spec(config)
    total = sum(int(__import__("numpy").prod(s)) for _, s, _ in spec)
    # the configuration's table: layer 0, 4 expert layers, embedding and
    # head (the table's millions round each; the gains are counted here)
    attention, expert = 101_124_096, 44_040_192
    assert flops.attention_params(config) == attention
    assert flops.expert_params(config) == expert
    assert total == (5 * (attention + 2 * 7168) + 9 * expert
                     + 4 * (384 * 7168 + 384 + 13 * expert)
                     + 2 * 20480 * 7168 + 7168) == 3_496_763_904
    assert flops.non_expert_params(config) + 4 * 12 * expert \
        + 20480 * 7168 == total
    # the traffic is what the issue names, number for number
    assert traffic["clients"] == 8 and traffic["batch_buckets"] == [8]
    assert traffic["prompt_lengths"] == _quantiles(8192, 0.5, 4096, 15872) \
        == [4096, 5257, 6415, 7572, 8862, 10459, 12765, 15872]
    assert traffic["output_lengths"] == _quantiles(128, 0.8, 32, 384) \
        == [37, 62, 86, 112, 145, 189, 260, 384]
    assert sum(traffic["prompt_lengths"]) == 71298
    assert sum(traffic["output_lengths"]) == 1275
    assert traffic["batcher"] == {"max_delay_ms": 200.0}
    assert max(traffic["prompt_lengths"]) + max(traffic["output_lengths"]) \
        <= config["n_positions"]
    # every context lies past the length YaRN stretches from
    assert min(traffic["prompt_lengths"]) >= config["rope_scaling"][
        "original_max_position_embeddings"]
    for key in ("published", "deployment", "assumed", "reduced_why"):
        assert key in config
    inits = {name: init for name, _, init in spec}
    assert config["seeded"] and all(inits[k] == v for k, v
                                    in config["seeded"].items())
    assert inits["q_up_weight"] == f"normal:{config['initializer_range']}"
    assert inits["router_bias"] == "zeros"
    with pytest.raises(ValueError, match="no leaf"):
        cell["reference"].param_spec(dict(config, seeded={"nope": "ones"}))
    assert [m["name"] for m in cells.metrics("per_layer", CELL)] == NAMES
    # the catalog's numbers, unchanged but for the three reduced keys
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    source = next(r for r in rows if r["name"] == "Kimi-K2.6")
    assert config["source"] == source["source_url"]
    changed = {k for k, v in source["config"].items() if config[k] != v}
    assert changed == set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert config["published"] == {k: source["config"][k] for k in changed}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 12, 163840 // 8)


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


def _layer(name):
    return {"name": name, "unit": "%", "better": "lower",
            "source": "program_counter", "layer": "model step and kernels",
            "moves": "serve_tokens_per_s"}


def _cells(tmp_path, config):
    extra = []
    for n in TINY:
        with open(os.path.join(HERE, "metrics", n + ".json")) as f:
            extra.append((f"metrics/{n}.json", f.read()))
    write_bench(str(tmp_path), {"tiny": config}, {"tiny-doc": TINY_DOC},
                [{"name": "kimi-cell", "config": "tiny",
                  "traffic": "tiny-doc", "chips": 1, "why": "a test"}],
                [_layer(n) for n in TINY], extra)
    return Cells(str(tmp_path))


def test_the_cell_runs_tiny_through_run_cell(tmp_path, quiet):
    lines, log = quiet
    cells = _cells(tmp_path, TINY_KIMI)
    out = run.run_cell(cells, "kimi-cell", 2 ** 31 + 11, 0.3, False,
                       platform="cpu", log=log)
    assert out["correct"] is True and out["failed"] == 0, lines
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0
    traced = run.run_cell(cells, "kimi-cell", 7, 0.3, True, platform="cpu",
                          log=log)
    got = traced["metrics"]
    # counters and host spans are read on the CPU too; what needs a
    # device plane is left out of the line
    assert sorted(got) == sorted(READ_ON_THE_CPU)
    assert got["decode_ms_per_step_p50"]["value"] > 0
    assert 0.0 < got["decode_rows_useful_pct"]["value"] <= 100.0
    assert 0.0 <= got["moe_rows_padded_pct"]["value"] < 100.0


def test_a_program_without_the_counters_reads_nothing(tmp_path, quiet):
    """The same metrics over GPT-2's records (as the parent of this PR
    would give them for a cell it can run): left out, not raised."""
    cells = _cells(tmp_path, TINY_GPT)
    traced = run.run_cell(cells, "kimi-cell", 7, 0.3, True, platform="cpu",
                          log=quiet[1])
    assert "decode_step_roofline.kimi" not in traced["metrics"]
    assert "decode_attn_latent_roofline" not in traced["metrics"]
    assert "moe_rows_padded_pct" not in traced["metrics"]
    assert "decode_ms_per_step_p50" in traced["metrics"]


def _run(records, modules=None):
    return {"records": records, "cell": {"name": "x", "config": TINY_KIMI},
            "trace": {"modules": modules or {}},
            "peaks": {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}}


def _group(t0, lengths, **counters):
    return [dict(counters, t_decode0=t0, prompt=list(range(20)),
                 tokens=list(range(n))) for n in lengths]


def test_readers_by_hand(monkeypatch):
    cells = Cells(ROOT)
    counters = dict(attn_latent_positions_decode=999,
                    attn_latent_positions_prefill=1000, moe_pairs_decode=10,
                    moe_experts_hit_per_step=2.0)
    recs = _group(1.0, (3, 2), **counters) + _group(2.0, (3, 3), **counters)
    roof = cells.module("readers", "decode_roofline_kimi_k2").read
    run_ = _run(recs, {"jit_serve_decode(1)": [2e-3, 4e-3, 9e-3],
                       "jit_serve_prefill(2)": [1.0]})
    # two steps a group; the second row of the first group is done
    # after one
    lives = [[21, 21], [22], [21, 21], [22, 22]]
    need = statistics.mean(
        flops.decode_step_bytes(TINY_KIMI, 2, live, 2.0) for live in lives)
    assert roof(run_, {"itemsize_of": "float16"}) == pytest.approx(
        100.0 * need / 1e9 / 5e-3)        # the mean execution, not 4e-3
    assert roof(_run(recs), {}) is None          # no trace of the program
    assert roof(_run([{"t_decode0": 1.0, "tokens": [1]}],
                     {"jit_serve_decode": [1.0]}), {}) is None

    tr = {"programs": {"jit_serve_prefill": {"a": 0.5, "d": 0.2},
                       "jit_serve_decode": {"b": 0.25, "c": 0.05,
                                            "e": 0.1, "f": 0.1}},
          "paths": {"jit_serve_prefill": {
              "a": "jit(serve_prefill)/while/body/serve.attn_full/dot",
              "d": "jit(serve_prefill)/serve.head/dot"},
                    "jit_serve_decode": {
              "b": "jit(serve_decode)/while/body/serve.attn_latent/call",
              "c": "jit(serve_decode)/while/body/serve.attn_down/dot",
              "e": "jit(serve_decode)/while/body/serve.attn_out/dot",
              "f": "jit(serve_decode)/serve.head/dot"}}}
    monkeypatch.setattr(spans, "of_run", lambda run: tr)
    share = cells.module("readers", "attn_roofline_kimi_k2").read
    full = {"program": "jit_serve_prefill", "scopes": SCOPES,
            "phase": "prefill", "under": ["serve.attn_full"]}
    # each group's counter once: 2,000 pairs
    assert share(_run(recs), full) == pytest.approx(
        100.0 * flops.attn_full_flops(TINY_KIMI, 2000) / 1e12 / 0.5)
    latent = {"program": "jit_serve_decode", "scopes": SCOPES,
              "phase": "decode", "under": ["serve.attn_latent"]}
    # the rows that still want a token, 3 layers: not the counter
    positions = 3 * sum(sum(live) for live in lives)
    t_bytes = positions * 24 * 2 / 1e9
    t_flops = 2 * positions * 4 * (24 + 16) / 1e12
    assert flops.latent_attn_bytes(TINY_KIMI, positions, 2) / 1e9 == t_bytes
    assert flops.latent_attn_flops(TINY_KIMI, positions) / 1e12 == t_flops
    assert share(_run(recs), latent) == pytest.approx(
        100.0 * max(t_bytes, t_flops) / 0.25)
    assert share(_run([{"t_decode0": 1.0}]), latent) is None
    both = cells.module("readers", "scope_share_sum").read
    assert both(_run([]), dict(latent, under=[
        "serve.attn_down", "serve.attn_q_up", "serve.attn_out"])) \
        == pytest.approx(100.0 * 0.15 / 0.5)
    monkeypatch.setattr(spans, "of_run", lambda run: None)
    assert share(_run(recs), full) is None
    assert both(_run([]), latent) is None


def test_the_counting_functions_by_hand():
    c = TINY_KIMI
    # attention: W_dq 64 x 24 + its gain, W_uq 24 x 4 x 24, W_dkv
    # 64 x 24 + the latent's gain, W_ukv 16 x 4 x 28, W_o 4 x 12 x 64
    attention = (64 * 24 + 24) + 24 * 96 + (64 * 24 + 16) + 16 * 112 \
        + 48 * 64
    assert flops.attention_params(c) == attention
    assert flops.expert_params(c) == 3 * 64 * 32
    assert flops.latent_width(c) == 24
    # three layers' attention and gains, a dense layer of 96, two expert
    # layers' router of 8 with its bias and shared expert, the final
    # gain, the head
    non_expert = 3 * (attention + 128) + 3 * 64 * 96 \
        + 2 * (8 * 64 + 8 + 3 * 64 * 32) + 64 + 96 * 64
    assert flops.non_expert_params(c) == non_expert
    # rows of 5 and 30 positions read 35 entries of 24 a layer
    assert flops.decode_step_bytes(c, 2, [5, 30], 1.5) == 2 * (
        non_expert + 2 * 1.5 * flops.expert_params(c) + 3 * 35 * 24)
    assert flops.latent_attn_flops(c, 10) == 2 * 10 * 4 * (24 + 16)
    assert flops.attn_full_flops(c, 10) == 2 * 10 * 4 * (16 + 8 + 12)
    assert flops.decode_step_flops(c, 2, 3, 105) == 2 * (
        2 * (non_expert - 64 - 3 * 128) + 3 * flops.expert_params(c)) \
        + flops.latent_attn_flops(c, 105)
    # the published sizes: 1,152 B a position, 64 x 1,088 x 2 operations
    # a pair absorbed and 64 x 320 x 2 expanded, 121 operations a byte
    big = Cells(ROOT).cell(CELL)["config"]
    assert flops.latent_attn_bytes(big, 1, 2) == 1152
    assert flops.latent_attn_flops(big, 1) == 64 * 1088 * 2
    assert flops.attn_full_flops(big, 1) == 64 * 320 * 2
    assert round(flops.latent_attn_flops(big, 1) / 1152) == 121
