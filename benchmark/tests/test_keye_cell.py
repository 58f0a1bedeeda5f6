"""The Keye-VL-2.0 cell's files load, a tiny copy of the cell runs through
`run_cell` on the CPU as the others do and reads `correct`, its new
per-layer metrics are read where there is something to read, and left
out (never raised) where there is not: a CPU trace, or a program without
the counters."""

import json
import os

import pytest

from benchmark import flops_keye_vl2 as flops, run, spans
from benchmark.cells import HERE, Cells

from conftest import PADDED, ROOT, SERVING, SETUP, TINY_GPT, write_bench

KW = {"vocab_size": 96, "units": 64, "num_layers": 3, "num_heads": 4,
      "kv_heads": 2, "head_dim": 16, "index_heads": 2, "index_dim": 8,
      "topk": 8, "expert_hidden": 32, "router_experts": 8,
      "experts_per_token": 2, "experts_held": [2, 4], "rope_theta": 1e7,
      "eps": 1e-6, "max_length": 64, "dtype": "float32",
      "grad_req": "null"}

TINY_KEYE = {
    "name": "tiny-keye", "source": "a test's own", "model_type": "KeyeVL2",
    "hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "rope_theta": 1e7,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                  "indexer_num_kv_heads": 1, "topk": 8,
                  "q_chunk_size": 512, "kv_chunk_size": 512},
    "mlp_only_layers": [], "decoder_sparse_step": 1,
    "moe_intermediate_size": 32, "num_experts": 4, "router_experts": 8,
    "experts_held": [2, 4], "num_experts_per_tok": 2, "vocab_size": 96,
    "rms_norm_eps": 1e-6, "initializer_range": 0.2, "n_positions": 64,
    "reduced": [], "reference": "keye_vl2",
    "program": {
        "constructor": "mxnet_tpu.gluon.model_zoo.keye_vl2.KeyeVL2Model",
        "kwargs": KW, "dtype": "float32"}}

# every prompt several times topk and in one prefill bucket (64), so that
# a round that splits under load compiles nothing; the cells' own batcher
# delay (PERF.md, Open questions)
TINY_VIDEO = {
    "kind": "serve_closed", "clients": 4, "batch_buckets": [4],
    "prompt_lengths": [33, 36, 41, 52], "output_lengths": [2, 3, 5, 8],
    "rate_metric": "serve_tokens_per_s", "work_unit": "tokens",
    "trace_seconds": 0.01, "check_tokens": 20,
    "batcher": {"max_delay_ms": 200.0},
    "limits": {"served_token_logit_gap_max": 0.01}}

OWN = ["decode_attn_index_pct", "decode_attn_select_pct",
       "decode_attn_sparse_pct", "prefill_attn_index_pct",
       "prefill_attn_select_pct", "prefill_attn_sparse_pct",
       "decode_moe_experts_pct.keye", "attn_keys_selected_pct",
       "decode_step_roofline.keye", "prefill_attn_sparse_roofline",
       "prefill_attn_index_roofline",
       # scope shares whose lists of scopes are this family's own
       "prefill_moe_experts_pct.keye", "prefill_moe_experts_roofline.keye",
       "decode_cache_write_pct.keye", "decode_unscoped_pct.keye",
       "prefill_unscoped_pct.keye", "prefill_attn_qkv_pct.keye"]
# the cell's entries in BENCHMARK.json's order; a tiny run leaves the
# start-up metrics out (they read the process's own start)
TINY = SERVING + [PADDED] + OWN
NAMES = TINY + SETUP
NEEDS_A_DEVICE_TRACE = [n for n in TINY if n.endswith("roofline")
                        or n.endswith("roofline.keye")
                        or "_attn_" in n or "moe_experts" in n
                        or "unscoped" in n or "cache_write" in n
                        or "idle_" in n]


def test_the_cells_files_load():
    cells = Cells(ROOT)
    cell = cells.cell("keye2-serve-video16k")
    config, traffic = cell["config"], cell["traffic"]
    assert cell["chips"] == 1 and cell["kind"].__name__.endswith(
        "serve_closed")
    spec = cell["reference"].param_spec(config)
    total = sum(int(__import__("numpy").prod(s)) for _, s, _ in spec)
    # the configuration's table: 6 layers of 96.9M, embedding and head
    assert abs(total - (6 * 96.899456e6 + 77.791232e6 + 2048)) < 1
    assert flops.non_expert_params(config) + 6 * 16 * \
        flops.expert_params(config) + 18992 * 2048 == total
    assert max(traffic["prompt_lengths"]) + max(traffic["output_lengths"]) \
        <= config["n_positions"]
    assert min(traffic["prompt_lengths"]) >= 2 * config["sa_config"]["topk"]
    for key in ("published", "deployment", "assumed", "reduced_why"):
        assert key in config
    # the leaves the configuration seeds otherwise reach the list both
    # sides draw from; a name that is no leaf is an error, not ignored
    inits = {name: init for name, _, init in spec}
    assert config["seeded"] and all(inits[k] == v for k, v
                                    in config["seeded"].items())
    assert inits["q_weight"] == f"normal:{config['initializer_range']}"
    with pytest.raises(ValueError, match="no leaf"):
        cell["reference"].param_spec(dict(config, seeded={"nope": "ones"}))
    got = [m["name"] for m in cells.metrics("per_layer",
                                            "keye2-serve-video16k")]
    assert got == NAMES
    # the catalog's numbers, unchanged but for the four reduced keys
    assert config["hidden_size"] == 2048 and config["head_dim"] == 128
    assert config["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert config["published"] == {
        "num_hidden_layers": 48, "num_experts": 128,
        "num_local_experts": 128, "vocab_size": 151936}


def _layer(name):
    return {"name": name, "unit": "%", "better": "lower",
            "source": "program_counter", "layer": "model step and kernels",
            "moves": "serve_tokens_per_s"}


def _cells(tmp_path, config):
    extra = []
    for n in TINY:
        with open(os.path.join(HERE, "metrics", n + ".json")) as f:
            extra.append((f"metrics/{n}.json", f.read()))
    write_bench(str(tmp_path), {"tiny": config}, {"tiny-video": TINY_VIDEO},
                [{"name": "keye-cell", "config": "tiny",
                  "traffic": "tiny-video", "chips": 1, "why": "a test"}],
                [_layer(n) for n in TINY], extra)
    return Cells(str(tmp_path))


def test_the_cell_runs_tiny_through_run_cell(tmp_path, quiet):
    lines, log = quiet
    cells = _cells(tmp_path, TINY_KEYE)
    out = run.run_cell(cells, "keye-cell", 2 ** 31 + 11, 0.3, False,
                       platform="cpu", log=log)
    assert out["correct"] is True and out["failed"] == 0, lines
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0
    traced = run.run_cell(cells, "keye-cell", 7, 0.3, True, platform="cpu",
                          log=log)
    got = traced["metrics"]
    # counters and host spans are read on the CPU too; what needs a
    # device plane is left out of the line
    assert 0.0 < got["attn_keys_selected_pct"]["value"] < 100.0
    assert got["decode_ms_per_step_p50"]["value"] > 0
    assert got["serve_ttft_ms_p50"]["value"] > 0
    assert 0.0 < got["decode_rows_useful_pct"]["value"] <= 100.0
    assert 0.0 <= got["moe_rows_padded_pct"]["value"] < 100.0
    assert got["serve_token_gap_ms_p95"]["value"] > 0
    for name in NEEDS_A_DEVICE_TRACE:
        assert name not in got


def test_a_program_without_the_counters_reads_nothing(tmp_path, quiet):
    """The same metrics over GPT-2's records (as the parent of this PR
    would give them for a cell it can run): left out, not raised."""
    cells = _cells(tmp_path, TINY_GPT)
    traced = run.run_cell(cells, "keye-cell", 7, 0.3, True, platform="cpu",
                          log=quiet[1])
    assert "attn_keys_selected_pct" not in traced["metrics"]
    assert "decode_step_roofline.keye" not in traced["metrics"]
    assert "decode_ms_per_step_p50" in traced["metrics"]


def _run(records, modules=None):
    return {"records": records, "cell": {"name": "x", "config": TINY_KEYE},
            "trace": {"modules": modules or {}},
            "peaks": {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}}


def _group(t0, n, **counters):
    return [dict(counters, t_decode0=t0, prompt=list(range(20)),
                 tokens=list(range(n))) for _ in range(2)]


def test_readers_by_hand(monkeypatch):
    cells = Cells(ROOT)
    recs = _group(1.0, 3, attn_keys_live_decode=400,
                  attn_keys_selected_decode=100,
                  attn_keys_live_prefill=1000,
                  attn_keys_selected_prefill=300, moe_pairs_decode=10,
                  moe_experts_hit_per_step=2.0) \
        + _group(2.0, 3, attn_keys_live_decode=600,
                 attn_keys_selected_decode=300,
                 attn_keys_live_prefill=1000,
                 attn_keys_selected_prefill=500, moe_pairs_decode=10,
                 moe_experts_hit_per_step=2.0)
    selected = cells.module("readers", "attn_keys_selected").read
    # each group once: 400 of 1000
    assert selected(_run(recs), {"phase": "decode"}) == pytest.approx(40.0)
    assert selected(_run(recs), {"phase": "prefill"}) == pytest.approx(40.0)
    assert selected(_run([{"t_decode0": 1.0}]), {}) is None

    roof = cells.module("readers", "decode_roofline_keye_vl2").read
    run_ = _run(recs, {"jit_serve_decode(1)": [2e-3, 4e-3, 9e-3],
                       "jit_serve_prefill(2)": [1.0]})
    # two steps a group, both rows live at lengths 21 and 22
    need = sum(flops.decode_step_bytes(TINY_KEYE, 2, [n, n], 2.0)
               for n in (21, 22)) / 2
    assert roof(run_, {"itemsize_of": "float16"}) == pytest.approx(
        100.0 * need / 1e9 / 5e-3)        # the mean execution, not 4e-3
    assert roof(_run(recs), {}) is None          # no trace of the program

    tr = {"programs": {"jit_serve_prefill": {
              "a": 0.05, "b": 0.25, "c": 0.5, "d": 0.2}},
          "paths": {"jit_serve_prefill": {
              "a": "jit(serve_prefill)/while/body/serve.attn_index/dot",
              "b": "jit(serve_prefill)/while/body/serve.attn_select/call",
              "c": "jit(serve_prefill)/while/body/serve.attn_sparse/call",
              "d": "jit(serve_prefill)/serve.head/dot"}}}
    monkeypatch.setattr(spans, "of_run", lambda run: tr)
    share = cells.module("readers", "attn_roofline_keye_vl2").read
    scopes = ["serve.attn_index", "serve.attn_select", "serve.attn_sparse",
              "serve.head"]
    sparse = {"program": "jit_serve_prefill", "scopes": scopes,
              "counter": "attn_keys_selected_prefill",
              "flops": "attn_sparse_flops", "under": ["serve.attn_sparse"]}
    assert share(_run(recs), sparse) == pytest.approx(
        100.0 * flops.attn_sparse_flops(TINY_KEYE, 800) / 1e12 / 0.5)
    index = dict(sparse, counter="attn_keys_live_prefill",
                 flops="attn_index_flops",
                 under=["serve.attn_index", "serve.attn_select"])
    assert share(_run(recs), index) == pytest.approx(
        100.0 * flops.attn_index_flops(TINY_KEYE, 2000) / 1e12 / 0.3)
    assert share(_run([{"t_decode0": 1.0}]), sparse) is None
    monkeypatch.setattr(spans, "of_run", lambda run: None)
    assert share(_run(recs), sparse) is None


def test_a_programs_shares_and_its_rest_sum_to_100(monkeypatch):
    """`scope_rest_ops`: the time under no scope less the unnamed
    grouped products that `scope_share_ops` counts to the experts."""
    cells = Cells(ROOT)
    tr = {"programs": {"jit_serve_decode": {
              "a": 0.2, "b": 0.1, "ragged-dot-none.1": 0.3, "copy.7": 0.4}},
          "paths": {"jit_serve_decode": {
              "a": "jit(serve_decode)/while/body/serve.attn_sparse/dot",
              "b": "jit(serve_decode)/while/body/serve.moe.experts/add"}}}
    monkeypatch.setattr(spans, "of_run", lambda run: tr)
    params = {"program": "jit_serve_decode", "ops": ["ragged-dot"],
              "scopes": ["serve.attn_sparse", "serve.moe.experts"]}
    rest = cells.module("readers", "scope_rest_ops").read(_run([]), params)
    assert rest == pytest.approx(40.0)
    experts = cells.module("readers", "scope_share_ops").read(
        _run([]), dict(params, scope="serve.moe.experts"))
    sparse = cells.module("readers", "scope_share").read(
        _run([]), dict(params, scope="serve.attn_sparse"))
    assert rest + experts + sparse == pytest.approx(100.0)
    monkeypatch.setattr(spans, "of_run", lambda run: None)
    assert cells.module("readers", "scope_rest_ops").read(
        _run([]), params) is None


def test_the_counting_functions_by_hand():
    c = TINY_KEYE
    # a layer: attention 64 x (4 + 2 x 2) x 16 + 4 x 16 x 64 + 2 x 16,
    # indexer 64 x (2 x 8 + 8 + 2) + 2 x 8, two gains, a router of 8
    layer = (64 * 8 * 16 + 64 * 64 + 32) + (64 * 26 + 16) + 128 + 8 * 64
    assert flops.layer_params(c) == layer
    assert flops.non_expert_params(c) == 3 * layer + 64 + 96 * 64
    assert flops.expert_params(c) == 3 * 64 * 32
    # a row of length 5 reads 5 indexer keys and 5 positions' keys and
    # values; one of length 30 reads 30 and topk = 8
    rows = 3 * ((5 * 8 + 5 * 64) + (30 * 8 + 8 * 64))
    assert flops.decode_step_bytes(c, 2, [5, 30], 1.5) == 2 * (
        flops.non_expert_params(c) + 3 * 1.5 * flops.expert_params(c)
        + rows)
    assert flops.attn_sparse_flops(c, 10) == 4 * 10 * 4 * 16
    assert flops.attn_index_flops(c, 10) == 2 * 10 * 2 * 8
    assert flops.decode_step_flops(c, 2, 3) == 2 * (
        2 * (flops.non_expert_params(c) - 64)
        + 3 * flops.expert_params(c))
