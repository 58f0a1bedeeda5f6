"""The Granite 4.0-H cell's files load and say what the issue names (the
parameter count from the configuration file, the catalog's numbers, the
traffic tables), `flops_granite_hybrid`'s counts equal hand figures, the
traced programs' scopes are the metric files', a tiny copy of the cell
runs through `run_cell` on the CPU as the others do and reads `correct`
(and its float8 control does not pass), its per-layer metrics are read
where there is something to read, and left out (never raised) where
there is not: a CPU trace, or a program without the counters; and the
two readers that take a `flops` module by name read what Jamba's and
MiMo's own readers read."""

import json
import os
import statistics

import pytest

from benchmark import control, flops_granite_hybrid as flops, run, spans
from benchmark.cells import HERE, Cells

from conftest import (PADDED, ROOT, SERVING, SERVING_ON_THE_CPU, SETUP,
                      TINY_GPT, write_bench)

CELL = "granite4h-serve-chat128"

TYPES = ["mamba", "mamba", "attention", "mamba", "mamba"]
KW = {"vocab_size": 96, "units": 64, "layer_types": TYPES, "num_heads": 4,
      "kv_heads": 2, "ssm_heads": 4, "ssm_head_dim": 8, "d_state": 16,
      "d_conv": 4, "expert_hidden": 24, "shared_hidden": 48,
      "router_experts": 8, "experts_per_token": 3, "experts_held": [0, 2],
      "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
      "attention_multiplier": 0.0625, "logits_scaling": 4.0, "eps": 1e-5,
      "max_length": 64, "dtype": "float32", "grad_req": "null"}

TINY_GRANITE = {
    "name": "tiny-granite", "source": "a test's own",
    "model_type": "granitemoehybrid", "hidden_size": 64,
    "num_hidden_layers": 5, "layer_types": TYPES, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 24,
    "shared_intermediate_size": 48, "num_local_experts": 2,
    "router_experts": 8, "experts_held": [0, 2], "num_experts_per_tok": 3,
    "vocab_size": 96, "rms_norm_eps": 1e-5, "hidden_act": "silu",
    "tie_word_embeddings": True, "mamba_expand": 0.5, "mamba_n_heads": 4,
    "mamba_d_head": 8, "mamba_d_state": 16, "mamba_n_groups": 1,
    "mamba_d_conv": 4, "mamba_conv_bias": True, "mamba_proj_bias": False,
    "attention_bias": False, "position_embedding_type": "nope",
    "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
    "attention_multiplier": 0.0625, "logits_scaling": 4.0,
    "initializer_range": 0.1,
    "seeded": {"a_log_weight": "uniform:6", "conv_weight": "uniform:0.5",
               "embed_weight": "normal:0.02", "q_weight": "normal:0.5",
               "k_weight": "normal:0.5", "v_weight": "normal:0.3",
               "experts_down_weight": "normal:0.5",
               "shared_down_weight": "normal:0.3"},
    "n_positions": 64, "reduced": [], "reference": "granite_hybrid",
    "program": {
        "constructor":
            "mxnet_tpu.gluon.model_zoo.granite_hybrid.GraniteHybridModel",
        "kwargs": KW, "dtype": "float32"}}

# one prefill bucket (64) for all four prompts.  In float32 the served
# tokens lie 1e-6 under the reference's best (logits that spread by
# 0.04); the float8 control's lie 0.003 and more under it
TINY_CHAT = {
    "kind": "serve_closed", "clients": 4, "batch_buckets": [4],
    "prompt_lengths": [33, 36, 41, 52], "output_lengths": [2, 3, 5, 8],
    "rate_metric": "serve_tokens_per_s", "work_unit": "tokens",
    "trace_seconds": 0.01, "check_tokens": 20,
    "batcher": {"max_delay_ms": 200.0},
    "limits": {"served_token_logit_gap_max": 0.0005}}

OWN = ["prefill_ssd_scan_pct", "prefill_ssd_scan_roofline",
       "prefill_ssm_proj_pct.granite", "prefill_moe_experts_pct.granite",
       "prefill_moe_experts_roofline.granite", "prefill_unscoped_pct.granite",
       "decode_ssd_update_pct", "decode_ssd_update_roofline",
       "decode_ssm_proj_pct.granite", "decode_moe_experts_pct.granite",
       "decode_moe_shared_pct.granite", "decode_unscoped_pct.granite",
       "decode_step_roofline.granite"]
# the counters' metrics the cell joins by its name: the experts' padding
# (MiMo's), the scan's and the packed prefill's (Jamba's)
JOINED = [PADDED, "ssm_scan_padded_pct", "prefill_tokens_padded_pct"]
TINY = SERVING + JOINED + OWN
READ_ON_THE_CPU = SERVING_ON_THE_CPU + JOINED
SCOPES = ["serve.embed", "serve.ssm_in", "serve.ssm_conv", "serve.ssd_scan",
          "serve.ssd_update", "serve.ssm_out", "serve.attn_qkv",
          "serve.cache_write", "serve.attn_full", "serve.attn",
          "serve.attn_out", "serve.moe.route", "serve.moe.shared",
          "serve.moe.experts", "serve.head", "serve.sample"]


def test_the_cells_files_load():
    import numpy as np

    cells = Cells(ROOT)
    cell = cells.cell(CELL)
    config, traffic = cell["config"], cell["traffic"]
    assert cell["chips"] == 1 and cell["kind"].__name__.endswith(
        "serve_closed")
    # the parameter count, from the configuration file: the reference's
    # leaves and `flops_granite_hybrid` each derive the issue's figure
    spec = cell["reference"].param_spec(config)
    total = sum(int(np.prod(s)) for _, s, _ in spec)
    assert flops.ssm_mixer_params(config) == 102_286_976
    assert flops.attn_mixer_params(config) == 41_943_040
    assert flops.expert_params(config) == 9_437_184
    assert flops.layer_rest_params(config) == 18_874_368 + 294_912 + 8_192
    assert (flops.ssm_layers(config), flops.attn_layers(config)) == (9, 1)
    assert [i for i, k in enumerate(flops.kinds(config)) if k == "attn"] \
        == [5] == [i for i, k in enumerate(
            cell["reference"].kinds(config)) if k == "attn"]
    assert total == flops.total_params(config) == 2_955_758_208 \
        == 9 * 291_333_760 + 230_989_824 + 25_088 * 4_096 + 4_096
    # what a row holds: 4,096 B a cached position, 38.2 MB of states and
    # tails whatever its length
    assert flops.position_bytes(config, 2) == 4_096
    assert flops.row_state_bytes(config, 2) == 9 * (
        128 * 64 * 128 * 4 + 3 * 8_448 * 2) == 37_748_736 + 456_192
    # the traffic is serve-chat-closed128's, table for table, with its
    # own limits and why
    other = cells.data("traffic", "serve-chat-closed128")
    for key in ("kind", "clients", "batch_buckets", "sampling",
                "prompt_lengths", "output_lengths", "check_tokens",
                "trace_seconds", "batcher", "rate_metric", "work_unit"):
        assert traffic[key] == other[key], key
    assert sum(traffic["prompt_lengths"]) == 25_712
    assert sum(traffic["output_lengths"]) == 8_274
    assert traffic["why"] != other["why"] and traffic["limits_from"]
    assert max(traffic["prompt_lengths"]) + max(traffic["output_lengths"]) \
        == config["n_positions"] == 704
    for key in ("deployment", "assumed", "reduced_why", "published"):
        assert key in config
    for key in ("n_positions", "expert_width", "router_experts",
                "experts_held", "routing", "in_proj_order", "conv_order",
                "gated_norm", "bias", "positions", "attention_multiplier",
                "mamba_chunk_size", "state_precision", "layouts",
                "initializer_range", "weights", "unused"):
        assert key in config["assumed"], key
    inits = {name: init for name, _, init in spec}
    assert config["seeded"] and all(inits[k] == v for k, v
                                    in config["seeded"].items())
    assert inits["in_weight"] == f"normal:{config['initializer_range']}"
    assert inits["d_weight"] == "ones" and inits["dt_bias"] == "zeros"
    names = [m["name"] for m in cells.metrics("per_layer", CELL)]
    assert sorted(names) == sorted(SERVING + JOINED + SETUP + OWN)
    assert names[-len(OWN):] == OWN          # this PR's own, appended
    # the catalog's numbers, every one but the four reduced keys, which
    # `published` keeps
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    source = next(r for r in rows if r["name"] == "granite-4.0-h-small")
    entry = next(c for c in cells.bench["configs"]
                 if c["name"] == cell["config_name"])
    assert config["source"] == entry["source"] == source["source_url"]
    assert {k for k, v in source["config"].items()
            if k not in config or config[k] != v} == set(config["reduced"]) \
        == set(entry["reduced"]) == {"num_hidden_layers", "layer_types",
                                     "num_local_experts", "vocab_size"}
    assert config["published"] == {k: source["config"][k]
                                   for k in config["reduced"]}
    assert config["layer_types"] == source["config"]["layer_types"][:10]
    assert config["vocab_size"] * 4 == source["config"]["vocab_size"]
    assert config["num_local_experts"] * 4 == config["router_experts"] \
        == source["config"]["num_local_experts"]
    # the program is built at the same sizes
    kw = config["program"]["kwargs"]
    assert (kw["units"], kw["layer_types"], kw["num_heads"], kw["kv_heads"],
            kw["ssm_heads"], kw["ssm_head_dim"], kw["d_state"], kw["d_conv"],
            kw["expert_hidden"], kw["shared_hidden"], kw["router_experts"],
            kw["experts_per_token"], kw["experts_held"], kw["vocab_size"],
            kw["max_length"], kw["eps"], kw["embedding_multiplier"],
            kw["residual_multiplier"], kw["attention_multiplier"],
            kw["logits_scaling"]) == (
        config["hidden_size"], config["layer_types"],
        config["num_attention_heads"], config["num_key_value_heads"],
        config["mamba_n_heads"], config["mamba_d_head"],
        config["mamba_d_state"], config["mamba_d_conv"],
        config["intermediate_size"], config["shared_intermediate_size"],
        config["router_experts"], config["num_experts_per_tok"],
        config["experts_held"], config["vocab_size"], config["n_positions"],
        config["rms_norm_eps"], config["embedding_multiplier"],
        config["residual_multiplier"], config["attention_multiplier"],
        config["logits_scaling"])
    assert kw["ssm_heads"] * kw["ssm_head_dim"] \
        == config["mamba_expand"] * config["hidden_size"]


def test_each_metric_file_names_a_reader_and_the_cell():
    entries = {m["name"]: m for m in Cells(ROOT).bench["per_layer"]}
    for n in SERVING + JOINED + SETUP + OWN:
        with open(os.path.join(HERE, "metrics", n + ".json")) as f:
            desc = json.load(f)
        assert desc["name"] == n and "cells" not in desc
        assert CELL in entries[n]["workloads"]
        assert desc["moves"] == ("setup_s" if n in SETUP
                                 else "serve_tokens_per_s")
        assert os.path.isfile(os.path.join(HERE, "readers",
                                           desc["reader"] + ".py"))
    # this PR's own are the cell's alone, name the family's scopes and
    # its `flops` module, and `per_layer` stays within its limit
    for n in OWN:
        with open(os.path.join(HERE, "metrics", n + ".json")) as f:
            params = json.load(f)["params"]
        assert entries[n]["workloads"] == [CELL]
        assert params.get("scopes", SCOPES) == SCOPES, n
        for s in params.get("under", []) + [params.get("scope", SCOPES[0])]:
            assert s in SCOPES + ["unscoped"]
        assert params.get("flops", "flops_granite_hybrid") \
            == "flops_granite_hybrid"
    assert len(entries) <= 128


def test_the_programs_scopes_are_the_metric_files():
    """Every scope the metric files name is in the traced step, prefill
    or decode, and the step names no other but ``serve.pack``, which
    `prefill_unscoped_pct.granite` reads."""
    import re

    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.gluon.model_zoo import granite_hybrid

    net = granite_hybrid.GraniteHybridModel(**KW)
    net.initialize(init=mx.init.Zero())
    eng = serving.ServingEngine(net, batch_buckets=(2,))
    found = set()
    for S in (8, 1):
        text = jax.jit(eng._step["decode" if S == 1 else "prefill"]).lower(
            eng._weights, eng.init_cache(2), np.zeros(2, np.int32),
            np.zeros(2, np.int32), np.zeros((2, S), np.int32)
        ).as_text(debug_info=True)
        found |= set(re.findall(r"serve\.[a-z_.]+", text))
    assert found == set(SCOPES) | {"serve.pack"}, found


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
                [{"name": "granite-cell", "config": "tiny",
                  "traffic": "tiny-chat", "chips": 1, "why": "a test"}],
                [_layer(n) for n in TINY], extra)
    return Cells(str(tmp_path))


def test_the_cell_runs_tiny_and_the_control_fails(tmp_path, quiet):
    """Through `run_cell`, plain and traced, and `control.py`'s two
    readings: the served tokens lie under the limit, the float8
    reference's own tokens do not."""
    lines, log = quiet
    cells = _cells(tmp_path, TINY_GRANITE)
    traced = run.run_cell(cells, "granite-cell", 7, 0.3, True, platform="cpu",
                          log=log)
    assert traced["correct"] is True and traced["failed"] == 0, lines
    got = traced["metrics"]
    # counters and host spans are read on the CPU too; what needs a
    # device plane is left out of the line
    assert sorted(got) == sorted(READ_ON_THE_CPU)
    # the plain scan walks the bucket whole: 4 x 64 positions a layer
    # of which 33 + 36 + 41 + 52 are real; one tile holds the block
    assert got["ssm_scan_padded_pct"]["value"] == pytest.approx(
        100.0 * (1 - 162 / 256))
    assert got["prefill_tokens_padded_pct"]["value"] == pytest.approx(
        100.0 * (1 - 162 / 256))
    assert 0.0 < got[PADDED]["value"] < 100.0
    with open(os.path.join(str(tmp_path), "benchmark_out", "granite-cell",
                           "seed7-trace1", "records.json")) as f:
        records = json.load(f)["records"]
    for rec in records:
        assert rec["ssm_positions_prefill"] == 4 * 162
        assert rec["ssm_positions_scanned_prefill"] == 4 * 256
        assert 0 < rec["moe_pairs_prefill"] <= 5 * 162 * 2
        assert rec["decode_state_update_kernel_share"] == 0.0
    out = control.read(cells, "granite-cell", [2 ** 31 + 5], 0.3,
                       platform="cpu", log=log)
    limit = TINY_CHAT["limits"]["served_token_logit_gap_max"]
    assert out["correct"] == [True]
    assert max(out["sound"]["served_token_logit_gap_max"]) < limit / 10
    assert min(out["control"]["served_token_logit_gap_max"]) > 5 * limit


def test_a_program_without_the_counters_reads_nothing(tmp_path, quiet):
    """The same metrics over GPT-2's records (as the parent of this PR
    would give them for a cell it can run): left out, not raised."""
    cells = _cells(tmp_path, TINY_GPT)
    traced = run.run_cell(cells, "granite-cell", 7, 0.3, True, platform="cpu",
                          log=quiet[1])
    for n in OWN + JOINED:
        assert n not in traced["metrics"]
    assert "decode_ms_per_step_p50" in traced["metrics"]


def _run(records, modules=None, config=TINY_GRANITE):
    return {"records": records, "cell": {"name": "x", "config": config},
            "trace": {"modules": modules or {}},
            "peaks": {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}}


def _group(t0, lengths, **counters):
    return [dict(counters, t_decode0=t0, prompt=list(range(20)),
                 tokens=list(range(n))) for n in lengths]


def test_readers_by_hand(monkeypatch):
    cells = Cells(ROOT)
    c = TINY_GRANITE
    counters = dict(ssm_positions_prefill=1000, ssm_row_updates_decode=39,
                    moe_pairs_prefill=500, moe_pairs_decode=14,
                    moe_experts_hit_per_step=1.5)
    recs = _group(1.0, (3, 2), **counters) + _group(2.0, (3, 3), **counters)
    step = cells.reader("decode_step_roofline.granite")
    run_ = _run(recs, {"jit_serve_decode(1)": [2e-3, 4e-3, 9e-3],
                       "jit_serve_prefill(2)": [1.0]})
    # two steps a group; the second row of the first group is done
    # after one
    lives = [[21, 21], [22], [21, 21], [22, 22]]
    of_group = dict(counters, decode_steps=2)
    need = statistics.mean(
        flops.decode_step_bytes(c, 2, live, of_group) for live in lives)
    assert need / 1e9 > statistics.mean(
        flops.decode_step_flops(c, live, of_group) for live in lives) / 1e12
    assert step[1](run_, step[0]["params"]) == pytest.approx(
        100.0 * need / 1e9 / 5e-3)        # the mean execution, not 4e-3
    assert step[1](_run(recs), step[0]["params"]) is None    # no trace
    assert step[1](_run([{"t_decode0": 1.0, "tokens": [1]}],
                        {"jit_serve_decode": [1.0]}),
                   step[0]["params"]) is None

    tr = {"programs": {"jit_serve_prefill": {"a": 0.5, "d": 0.2, "g": 0.3,
                                             "ragged-dot-none.1": 0.25,
                                             "h": 0.05},
                       "jit_serve_decode": {"b": 0.25, "c": 0.05,
                                            "e": 0.1, "f": 0.1}},
          "paths": {"jit_serve_prefill": {
              "a": "jit(serve_prefill)/while/body/serve.ssd_scan/call",
              "d": "jit(serve_prefill)/serve.head/dot",
              "g": "jit(serve_prefill)/while/body/serve.ssm_in/dot",
              "h": "jit(serve_prefill)/while/body/serve.moe.experts/sort"},
                    "jit_serve_decode": {
              "b": "jit(serve_decode)/serve.ssd_update/call",
              "c": "jit(serve_decode)/serve.ssm_in/dot",
              "e": "jit(serve_decode)/serve.ssm_out/dot",
              "f": "jit(serve_decode)/serve.head/dot"}}}
    monkeypatch.setattr(spans, "of_run", lambda run: tr)
    scan = cells.reader("prefill_ssd_scan_roofline")
    # each group's counter once: 2,000 (position, layer) pairs; the
    # longer of the two bounds
    t_bytes = flops.scan_bytes(c, 2000, 2) / 1e9
    t_flops = flops.scan_ops(c, 2000) / 1e12
    assert t_bytes > t_flops
    assert scan[1](_run(recs), scan[0]["params"]) == pytest.approx(
        100.0 * t_bytes / 0.5)
    update = cells.reader("decode_ssd_update_roofline")
    assert update[1](_run(recs), update[0]["params"]) == pytest.approx(
        100.0 * flops.update_bytes(c, 78, 2) / 1e9 / 0.25)
    assert update[1](_run([{"t_decode0": 1.0}]), update[0]["params"]) is None
    # the experts': the scope's time with the unnamed grouped products
    experts = cells.reader("prefill_moe_experts_roofline.granite")
    assert experts[1](_run(recs), experts[0]["params"]) == pytest.approx(
        100.0 * flops.expert_flops(c, 1000) / 1e12 / 0.30)
    share = cells.reader("prefill_moe_experts_pct.granite")
    assert share[1](_run([]), share[0]["params"]) == pytest.approx(
        100.0 * 0.30 / 1.3)
    proj = cells.reader("decode_ssm_proj_pct.granite")
    assert proj[1](_run([]), proj[0]["params"]) == pytest.approx(
        100.0 * 0.15 / 0.5)
    one = cells.reader("prefill_ssd_scan_pct")
    assert one[1](_run([]), one[0]["params"]) == pytest.approx(
        100.0 * 0.5 / 1.3)
    monkeypatch.setattr(spans, "of_run", lambda run: None)
    assert scan[1](_run(recs), scan[0]["params"]) is None
    assert experts[1](_run(recs), experts[0]["params"]) is None


def test_the_readers_by_module_read_what_the_families_own_read(monkeypatch):
    """`counter_roofline` handed `flops_jamba` and `flops_mimo_v2` is
    `ssm_roofline` and `moe_experts_roofline`: the next family brings a
    module and no reader."""
    from test_jamba_cell import TINY_JAMBA

    cells = Cells(ROOT)
    by_module = cells.module("readers", "counter_roofline").read
    tr = {"programs": {"jit_serve_prefill": {"a": 0.5, "d": 0.2,
                                             "ragged-dot-none.1": 0.25}},
          "paths": {"jit_serve_prefill": {
              "a": "jit(serve_prefill)/while/body/serve.ssm_scan/call",
              "d": "jit(serve_prefill)/serve.moe.experts/sort"}}}
    monkeypatch.setattr(spans, "of_run", lambda run: tr)
    recs = _group(1.0, (3, 2), ssm_positions_prefill=1000,
                  moe_pairs_prefill=70)
    own = cells.reader("prefill_ssm_scan_roofline")
    assert by_module(_run(recs, config=TINY_JAMBA),
                     dict(own[0]["params"], flops="flops_jamba")) \
        == own[1](_run(recs, config=TINY_JAMBA), own[0]["params"])
    mimo = cells.cell("mimo25-serve-chat64")["config"]
    own = cells.reader("prefill_moe_experts_roofline")
    p = own[0]["params"]
    assert by_module(_run(recs, config=mimo), {
        "flops": "flops_mimo_v2", "program": p["program"],
        "scopes": p["scopes"], "under": [p["scope"]], "unnamed": p["ops"],
        "counter": "moe_pairs_prefill", "ops": "expert_flops"}) \
        == pytest.approx(own[1](_run(recs, config=mimo), p))


def test_the_counting_functions_by_hand():
    c = TINY_GRANITE
    C, E, W, H, N, k, V = 64, 32, 64, 4, 16, 4, 96
    mixer = C * (E + W + H) + W * k + W + 3 * H + E + E * C
    assert flops.ssm_mixer_params(c) == mixer
    attn = 2 * 64 * 64 + 2 * 2 * 16 * 64
    assert flops.attn_mixer_params(c) == attn
    rest = 3 * C * 48 + 8 * C + 2 * C
    assert flops.layer_rest_params(c) == rest
    assert flops.expert_params(c) == 3 * C * 24
    assert flops.non_expert_params(c) == 4 * mixer + attn + 5 * rest \
        + V * C + C
    assert flops.total_params(c) == flops.non_expert_params(c) \
        + 5 * 2 * 3 * C * 24
    assert flops.position_bytes(c, 2) == 2 * 2 * 16 * 2
    assert flops.row_state_bytes(c, 2) == 4 * (E * N * 4 + 3 * W * 2)
    assert flops.scan_ops(c, 10) == 10 * E * N * 5
    assert flops.scan_bytes(c, 10, 2) == 10 * (2 * E + 2 * N + H) * 2
    assert flops.update_bytes(c, 10, 2) == 10 * 2 * E * N * 4 \
        + flops.scan_bytes(c, 10, 2)
    assert flops.expert_flops(c, 7) == 2 * 7 * 3 * C * 24
    assert flops.attn_flops(c, 10) == 2 * 10 * 2 * C
    # rows of 5 and 30 positions, 1.5 held experts hit a layer, 6 pairs
    # over 2 steps
    counters = {"moe_experts_hit_per_step": 1.5, "moe_pairs_decode": 6,
                "decode_steps": 2}
    assert flops.decode_step_bytes(c, 2, [5, 30], counters) == \
        2 * (flops.non_expert_params(c) + 5 * 1.5 * 3 * C * 24) \
        + 2 * 2 * flops.row_state_bytes(c, 2) + 35 * 128
    assert flops.decode_step_flops(c, [5, 30], counters) == \
        2 * 2 * flops.non_expert_params(c) + flops.expert_flops(c, 3) \
        + flops.scan_ops(c, 8) + flops.attn_flops(c, 35)
    # the published sizes: a decode step of 128 live rows of 300
    # positions moves 2.51 GB of non-expert weights, 3.40 GB of held
    # experts (all 18 a layer are hit), 9.78 GB of states and tails and
    # 0.16 GB of cache: 19.4 ms at 819 GB/s, 62 % of it states; its
    # operations would take 2.0 ms
    big = Cells(ROOT).cell(CELL)["config"]
    hit = {"moe_experts_hit_per_step": 18.0, "moe_pairs_decode": 3200,
           "decode_steps": 1}
    assert round(flops.non_expert_params(big) * 2 / 1e9, 2) == 2.51
    assert round(10 * 18 * flops.expert_params(big) * 2 / 1e9, 2) == 3.40
    states = 128 * 2 * flops.row_state_bytes(big, 2)
    assert round(states / 1e9, 2) == 9.78
    step = flops.decode_step_bytes(big, 2, [300] * 128, hit)
    assert round((step - states) / 1e9 - 2.51 - 3.40, 2) == 0.16
    assert round(step / 819e9 * 1e3, 1) == 19.4
    assert round(states / step, 2) == 0.62
    assert round(flops.decode_step_flops(big, [300] * 128, hit) / 197e12
                 * 1e3, 1) == 2.0
    # a round's prefill: the recurrence over the 25,712 real tokens'
    # 231,408 (position, layer) pairs is 1.21 TFLOP (6.2 ms at the
    # peak) and 7.8 GB of operands (9.5 ms); the held experts' 2.5 pairs
    # a token and layer are 12 TFLOP
    pairs = 25_712 * 9
    assert round(flops.scan_ops(big, pairs) / 1e12, 2) == 1.21
    assert round(flops.scan_bytes(big, pairs, 2) / 819e9 * 1e3, 1) == 9.5
    assert round(flops.expert_flops(big, 25_712 * 10 * 2.5) / 1e12) == 12
