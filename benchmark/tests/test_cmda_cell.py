"""The Command A+ cell's files load, its traffic tables sum as stated, a
tiny copy of the cell runs through `run_cell` on the CPU as the others
do and reads `correct`, its new per-layer metrics are read where there
is something to read, and left out (never raised) where there is not: a
CPU trace, or a program without the counters; `flops_cohere2_moe.py`'s
counts equal a hand count at the tiny size."""

import json
import os
import statistics

import pytest

from benchmark import flops_cohere2_moe as flops, run, spans
from benchmark.cells import HERE, Cells

from conftest import (PADDED, ROOT, SERVING, SERVING_ON_THE_CPU, SETUP,
                      TINY_GPT, write_bench)

CELL = "cmda-serve-rag16k"

KW = {"vocab_size": 96, "units": 64,
      "layer_types": ["window", "window", "window", "full"], "num_heads": 8,
      "kv_heads": 2, "head_dim": 16, "window": 8, "expert_hidden": 32,
      "router_experts": 8, "experts_per_token": 2, "experts_held": [2, 4],
      "shared_experts": 2, "rope_theta": 50000.0, "max_length": 64,
      "token_chunk": 16, "prefill_chunk_tokens": 128, "dtype": "float32",
      "grad_req": "null"}

TINY_CMDA = {
    "name": "tiny-cmda", "source": "a test's own",
    "model_type": "cohere2_moe", "hidden_size": 64, "num_hidden_layers": 4,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
    "sliding_window": 8, "rope_theta": 50000.0, "rotary_pct": 1,
    "position_embedding_type": "rope_gptj", "intermediate_size": 32,
    "num_experts": 4, "router_experts": 8, "experts_held": [2, 4],
    "num_experts_per_tok": 2, "num_shared_experts": 2,
    "shared_expert_combination_strategy": "average",
    "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
    "first_k_dense_replace": 0, "use_parallel_block": True,
    "use_qk_norm": False, "attention_bias": False,
    "use_gated_activation": True, "hidden_act": "silu",
    "tie_word_embeddings": True, "logit_scale": 1, "layer_norm_eps": 1e-5,
    "vocab_size": 96, "initializer_range": 0.2, "n_positions": 64,
    "reduced": [], "reference": "cohere2_moe",
    "program": {
        "constructor":
            "mxnet_tpu.gluon.model_zoo.cohere2_moe.Cohere2MoeModel",
        "kwargs": KW, "dtype": "float32"}}

# every prompt several times the window of 8 and in one prefill bucket
# (64), two rows a chunk; the cells' own batcher delay
TINY_RAG = {
    "kind": "serve_closed", "clients": 4, "batch_buckets": [4],
    "prompt_lengths": [33, 36, 41, 52], "output_lengths": [2, 3, 5, 8],
    "rate_metric": "serve_tokens_per_s", "work_unit": "tokens",
    "trace_seconds": 0.01, "check_tokens": 20,
    "batcher": {"max_delay_ms": 200.0},
    "limits": {"served_token_logit_gap_max": 0.01}}

OWN = ["decode_attn_window_pct.cmda", "decode_attn_full_pct.cmda",
       "decode_attn_proj_pct.cmda", "decode_moe_shared_pct.cmda",
         "decode_moe_experts_pct.cmda", "decode_cache_write_pct.cmda",
         "decode_unscoped_pct.cmda", "prefill_attn_window_pct.cmda",
         "prefill_attn_full_pct.cmda", "prefill_moe_shared_pct.cmda",
         "prefill_unscoped_pct.cmda", "attn_window_pairs_kept_pct",
         "prefill_attn_window_roofline", "prefill_attn_full_roofline.cmda",
         "decode_attn_window_roofline", "decode_step_roofline.cmda"]
# the cell's entries in BENCHMARK.json's order; a tiny run leaves the
# start-up metrics out (they read the process's own start)
TINY = SERVING + [PADDED] + OWN
NAMES = TINY + SETUP
READ_ON_THE_CPU = SERVING_ON_THE_CPU + [PADDED, "attn_window_pairs_kept_pct"]
SCOPES = ["serve.embed", "serve.norm", "serve.attn_qkv", "serve.cache_write",
          "serve.attn_window", "serve.attn_full", "serve.attn_out",
          "serve.moe.route", "serve.moe.shared", "serve.moe.experts",
          "serve.head", "serve.sample"]


def test_the_cells_files_load():
    cells = Cells(ROOT)
    cell = cells.cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    assert cell["chips"] == 1 and cell["kind"].__name__.endswith(
        "serve_closed")
    spec = cell["reference"].param_spec(config)
    total = sum(int(__import__("numpy").prod(s)) for _, s, _ in spec)
    # the configuration's table: four layers and the tied slice (the
    # table's millions round each; the gains are counted here)
    attention, expert = 142_606_336, 50_331_648
    assert flops.attention_params(config) == attention
    assert flops.expert_params(config) == expert
    layer = attention + 128 * 4096 + 4 * expert + 8 * expert + 4096
    assert total == 4 * layer + 32768 * 4096 + 4096 == 3_122_679_808
    assert flops.non_expert_params(config) + 4 * 8 * expert == total
    # the whole model by the same counts: 218B-A25B to the digit
    whole = 32 * (layer + 120 * expert) + 262144 * 4096 + 4096
    assert round(whole / 1e9, 1) == 218.3
    assert round((whole - 32 * 120 * expert) / 1e9, 1) == 25.0
    # the traffic is what the issue names, number for number
    assert traffic["clients"] == 8 and traffic["batch_buckets"] == [8]
    assert traffic["prompt_lengths"] == [4096, 5257, 6415, 7572, 8862,
                                         10459, 12765, 15872]
    assert traffic["output_lengths"] == [37, 62, 86, 112, 145, 189, 260,
                                         384]
    assert sum(traffic["prompt_lengths"]) == 71298
    assert sum(traffic["output_lengths"]) == 1275
    assert traffic["batcher"] == {"max_delay_ms": 200.0}
    assert traffic["rate_metric"] == "serve_tokens_per_s"
    assert max(traffic["prompt_lengths"]) + max(traffic["output_lengths"]) \
        <= config["n_positions"]
    # the tables are Kimi's cell's: the same lengths through other
    # attention
    doc = cells.data("traffic", "serve-doc-closed8")
    assert traffic["prompt_lengths"] == doc["prompt_lengths"]
    assert traffic["output_lengths"] == doc["output_lengths"]
    # every context is 1-4 times the window, and the band keeps 60 %
    W = config["sliding_window"]
    assert all(W <= n <= 4 * W for n in traffic["prompt_lengths"])
    causal = sum(n * (n + 1) // 2 for n in traffic["prompt_lengths"])
    band = sum(W * (W + 1) // 2 + (n - W) * W
               for n in traffic["prompt_lengths"])
    assert (causal, band) == (372_881_653, 224_944_128)
    for key in ("published", "deployment", "assumed", "reduced_why"):
        assert key in config
    for key in ("nope", "average", "shared_width", "router", "window",
                "left_out", "n_positions"):
        assert key in config["assumed"], key
    inits = {name: init for name, _, init in spec}
    assert config["seeded"] and all(
        inits[f"l{i}_{k}"] == v for k, v in config["seeded"].items()
        for i in range(4))
    assert inits["l0_q_weight"] == f"normal:{config['initializer_range']}"
    assert inits["l3_ln_gamma"] == "ones"
    with pytest.raises(ValueError, match="no leaf"):
        cell["reference"].param_spec(dict(config, seeded={"nope": "ones"}))
    assert [m["name"] for m in cells.metrics("per_layer", CELL)] == NAMES
    assert CELL in next(m for m in cells.bench["end_to_end"]
                        if m["name"] == "serve_tokens_per_s")["workloads"]
    # the catalog's numbers, unchanged but for the four reduced keys
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    source = next(r for r in rows if r["name"] == "command-a-plus-05-2026")
    assert config["source"] == source["source_url"]
    changed = {k for k, v in source["config"].items() if config[k] != v}
    assert changed == set(config["reduced"]) == {
        "num_hidden_layers", "layer_types", "num_experts", "vocab_size"}
    assert config["published"] == {k: source["config"][k] for k in changed}
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (4, 8, 262144 // 8)
    assert config["layer_types"] == source["config"]["layer_types"][:4]
    kw = config["program"]["kwargs"]
    assert (kw["experts_held"], kw["router_experts"], kw["max_length"]) == (
        config["experts_held"], 128, config["n_positions"])


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
    write_bench(str(tmp_path), {"tiny": config}, {"tiny-rag": TINY_RAG},
                [{"name": "cmda-cell", "config": "tiny",
                  "traffic": "tiny-rag", "chips": 1, "why": "a test"}],
                [_layer(n) for n in TINY], extra)
    return Cells(str(tmp_path))


def test_the_cell_runs_tiny_through_run_cell(tmp_path, quiet):
    lines, log = quiet
    cells = _cells(tmp_path, TINY_CMDA)
    out = run.run_cell(cells, "cmda-cell", 2 ** 31 + 11, 0.3, False,
                       platform="cpu", log=log)
    assert out["correct"] is True and out["failed"] == 0, lines
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0
    traced = run.run_cell(cells, "cmda-cell", 7, 0.3, True, platform="cpu",
                          log=log)
    got = traced["metrics"]
    # counters and host spans are read on the CPU too; what needs a
    # device plane is left out of the line
    assert sorted(got) == sorted(READ_ON_THE_CPU)
    assert got["decode_ms_per_step_p50"]["value"] > 0
    assert 0.0 < got["decode_rows_useful_pct"]["value"] <= 100.0
    assert 0.0 <= got["moe_rows_padded_pct"]["value"] < 100.0
    # prompts of 33-52 positions under a window of 8
    band = sum(36 + (n - 8) * 8 for n in TINY_RAG["prompt_lengths"])
    causal = sum(n * (n + 1) // 2 for n in TINY_RAG["prompt_lengths"])
    assert got["attn_window_pairs_kept_pct"]["value"] == pytest.approx(
        100.0 * band / causal)


def test_a_program_without_the_counters_reads_nothing(tmp_path, quiet):
    """The same metrics over GPT-2's records (as the parent of this PR
    would give them for a cell it can run): left out, not raised."""
    cells = _cells(tmp_path, TINY_GPT)
    traced = run.run_cell(cells, "cmda-cell", 7, 0.3, True, platform="cpu",
                          log=quiet[1])
    for name in ("decode_step_roofline.cmda", "decode_attn_window_roofline",
                 "prefill_attn_window_roofline", "moe_rows_padded_pct",
                 "attn_window_pairs_kept_pct"):
        assert name not in traced["metrics"]
    assert "decode_ms_per_step_p50" in traced["metrics"]


def _run(records, modules=None):
    return {"records": records, "cell": {"name": "x", "config": TINY_CMDA},
            "trace": {"modules": modules or {}},
            "peaks": {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}}


def _group(t0, lengths, **counters):
    return [dict(counters, t_decode0=t0, prompt=list(range(20)),
                 tokens=list(range(n))) for n in lengths]


def test_readers_by_hand(monkeypatch):
    cells = Cells(ROOT)
    counters = dict(attn_window_pairs_decode=600,
                    attn_window_pairs_prefill=1000,
                    attn_window_pairs_causal_prefill=4000,
                    attn_full_pairs_prefill=1500, moe_pairs_decode=10,
                    moe_experts_hit_per_step=2.0)
    recs = _group(1.0, (3, 2), **counters) + _group(2.0, (3, 3), **counters)
    roof = cells.module("readers", "decode_roofline_cohere2_moe").read
    run_ = _run(recs, {"jit_serve_decode(1)": [2e-3, 4e-3, 9e-3],
                       "jit_serve_prefill(2)": [1.0]})
    # two steps a group; the second row of the first group is done
    # after one
    lives = [[21, 21], [22], [21, 21], [22, 22]]
    need = statistics.mean(
        flops.decode_step_bytes(TINY_CMDA, 2, live, 2.0) for live in lives)
    assert roof(run_, {"itemsize_of": "float16"}) == pytest.approx(
        100.0 * need / 1e9 / 5e-3)        # the mean execution, not 4e-3
    assert roof(_run(recs), {}) is None          # no trace of the program
    assert roof(_run([{"t_decode0": 1.0, "tokens": [1]}],
                     {"jit_serve_decode": [1.0]}), {}) is None
    kept = cells.module("readers", "record_share").read
    assert kept(_run(recs), {"field": "attn_window_pairs_prefill",
                             "per": "attn_window_pairs_causal_prefill",
                             "once_per": "t_decode0"}) == 25.0
    assert kept(_run([{"t_decode0": 1.0}]), {
        "field": "attn_window_pairs_prefill",
        "per": "attn_window_pairs_causal_prefill",
        "once_per": "t_decode0"}) is None

    tr = {"programs": {"jit_serve_prefill": {"a": 0.5, "d": 0.2, "g": 0.1},
                       "jit_serve_decode": {"b": 0.25, "c": 0.05,
                                            "e": 0.1, "f": 0.1}},
          "paths": {"jit_serve_prefill": {
              "a": "jit(serve_prefill)/while/body/serve.attn_window/call",
              "g": "jit(serve_prefill)/while/body/serve.attn_full/call",
              "d": "jit(serve_prefill)/serve.head/dot"},
                    "jit_serve_decode": {
              "b": "jit(serve_decode)/serve.attn_window/call",
              "c": "jit(serve_decode)/serve.attn_qkv/dot",
              "e": "jit(serve_decode)/serve.attn_out/dot",
              "f": "jit(serve_decode)/serve.head/dot"}}}
    monkeypatch.setattr(spans, "of_run", lambda run: tr)
    share = cells.module("readers", "attn_roofline_cohere2_moe").read
    window = {"program": "jit_serve_prefill", "scopes": SCOPES,
              "phase": "prefill", "kind": "window",
              "under": ["serve.attn_window"]}
    # each group's counter once: the band's 2,000 pairs, not the 8,000
    assert share(_run(recs), window) == pytest.approx(
        100.0 * flops.attn_flops(TINY_CMDA, 2000) / 1e12 / 0.5)
    assert share(_run(recs), dict(window, kind="full",
                                  under=["serve.attn_full"])) \
        == pytest.approx(100.0 * flops.attn_flops(TINY_CMDA, 3000) / 1e12
                         / 0.1)
    ring = {"program": "jit_serve_decode", "scopes": SCOPES,
            "phase": "decode", "kind": "window",
            "under": ["serve.attn_window"]}
    t_bytes = 1200 * 2 * 2 * 16 * 2 / 1e9
    t_flops = 2 * 1200 * 8 * 2 * 16 / 1e12
    assert flops.attn_bytes(TINY_CMDA, 1200, 2) / 1e9 == t_bytes
    assert flops.attn_flops(TINY_CMDA, 1200) / 1e12 == t_flops
    assert share(_run(recs), ring) == pytest.approx(
        100.0 * max(t_bytes, t_flops) / 0.25)
    assert share(_run([{"t_decode0": 1.0}]), ring) is None
    both = cells.module("readers", "scope_share_sum").read
    assert both(_run([]), dict(ring, under=[
        "serve.attn_qkv", "serve.attn_out"])) \
        == pytest.approx(100.0 * 0.15 / 0.5)
    monkeypatch.setattr(spans, "of_run", lambda run: None)
    assert share(_run(recs), window) is None
    assert both(_run([]), ring) is None


def test_the_counting_functions_by_hand():
    c = TINY_CMDA
    # attention: W_q 64 x 128, W_k and W_v 64 x 32, W_o 128 x 64
    attention = 64 * 128 + 2 * 64 * 32 + 128 * 64
    assert flops.attention_params(c) == attention
    assert flops.expert_params(c) == 3 * 64 * 32
    assert flops.position_width(c) == 2 * 2 * 16
    assert flops.windows(c) == [8, 8, 8, None]
    # four layers' attention, gain, router of 8 and two shared experts;
    # the final gain; the tied head
    non_expert = 4 * (attention + 64 + 8 * 64 + 2 * 3 * 64 * 32) + 64 \
        + 96 * 64
    assert flops.non_expert_params(c) == non_expert
    # a row of 5 positions reads 5 of each layer, one of 30 three rings
    # of 8 and 30 of the full layer
    assert flops.positions_read(c, 5) == 20
    assert flops.positions_read(c, 30) == 54
    assert flops.decode_step_bytes(c, 2, [5, 30], 1.5) == 2 * (
        non_expert + 4 * 1.5 * flops.expert_params(c) + 74 * 64)
    assert flops.attn_flops(c, 10) == 2 * 10 * 8 * 32
    assert flops.attn_bytes(c, 10, 2) == 10 * 64 * 2
    assert flops.decode_step_flops(c, 2, 3, 74) == 2 * (
        2 * (non_expert - 5 * 64) + 3 * flops.expert_params(c)) \
        + flops.attn_flops(c, 74)
    # the published sizes: 4,096 B a position and layer, 128 heads x 256
    # x 2 operations a pair, 16 operations a byte
    big = Cells(ROOT).cell(CELL)["config"]
    assert flops.attn_bytes(big, 1, 2) == 4096
    assert flops.attn_flops(big, 1) == 128 * 256 * 2
    # a live row at 12k reads 24k positions a step where four full
    # layers would read 48k
    assert flops.positions_read(big, 12288) == 3 * 4096 + 12288
