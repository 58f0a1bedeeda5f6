"""The MiMo-V2 cell runs tiny on the CPU through `run_cell` as the
others do, its new per-layer metrics are read where there is something
to read, and left out (never raised) where there is not: a CPU trace,
or a program without the expert counters."""

import pytest

from benchmark import run, spans
from benchmark.cells import Cells

from conftest import (PADDED, ROOT, SERVING, SETUP, TINY_GPT, TINY_SERVE,
                      write_bench)

KW = {"vocab_size": 96, "units": 64,
      "layer_types": ["full", "window", "window", "window", "window",
                      "window", "full"],
      "moe_layers": [0, 1, 1, 1, 1, 1, 1], "num_heads": 4, "kv_heads": 1,
      "swa_kv_heads": 2, "qk_dim": 24, "v_dim": 16, "rotary_dim": 8,
      "window": 4, "rope_theta": 1e7, "swa_rope_theta": 1e4,
      "hidden_size": 96, "expert_hidden": 32, "router_experts": 8,
      "experts_per_token": 2, "experts_held": [2, 4], "value_scale": 0.707,
      "max_length": 64, "attn_block": 4, "dtype": "float32",
      "grad_req": "null"}

TINY_MIMO = {
    "name": "tiny-mimo", "source": "a test's own", "model_type": "mimo_v2",
    "hidden_size": 64, "num_hidden_layers": 7,
    "hybrid_layer_pattern": [0, 1, 1, 1, 1, 1, 0],
    "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1], "num_attention_heads": 4,
    "num_key_value_heads": 1, "swa_num_key_value_heads": 2,
    "rope_theta": 1e7, "swa_rope_theta": 1e4, "head_dim": 24,
    "v_head_dim": 16, "partial_rotary_factor": 0.334, "sliding_window": 4,
    "attention_value_scale": 0.707, "intermediate_size": 96,
    "moe_intermediate_size": 32, "n_routed_experts": 4,
    "router_experts": 8, "experts_held": [2, 4], "num_experts_per_tok": 2,
    "vocab_size": 96, "layernorm_epsilon": 1e-5, "initializer_range": 0.2,
    "n_positions": 64, "reduced": [], "reference": "mimo_v2",
    "program": {
        "constructor": "mxnet_tpu.gluon.model_zoo.mimo_v2.MiMoV2Model",
        "kwargs": KW, "dtype": "float32"}}

SCOPES = ["serve.attn_full", "serve.moe.experts"]


def test_the_cells_entries_are_the_generic_ones_and_its_own():
    """In BENCHMARK.json's order: what every serving cell reports under
    one name, the family's own, the start-up metrics of every cell."""
    got = [m["name"] for m in Cells(ROOT).metrics("per_layer",
                                                  "mimo25-serve-chat64")]
    assert got == SERVING + [
        "decode_moe_experts_pct", "decode_attn_window_pct",
        "decode_attn_full_pct", "prefill_moe_experts_pct", PADDED,
        "decode_step_roofline.moe", "prefill_moe_experts_roofline"] + SETUP


def _layer(name, reader=None):
    return {"name": name, "unit": "%", "better": "lower",
            "source": "program_counter", "layer": "model step and kernels",
            "moves": "serve_tokens_per_s"}


def _cells(tmp_path, config):
    import json
    import os

    from benchmark.cells import HERE

    names = ["moe_rows_padded_pct", "decode_step_roofline.moe",
             "prefill_moe_experts_roofline", "decode_moe_experts_pct",
             "decode_ms_per_step_p50"]
    extra = []
    for n in names:
        with open(os.path.join(HERE, "metrics", n + ".json")) as f:
            extra.append((f"metrics/{n}.json", f.read()))
    write_bench(str(tmp_path), {"tiny": config}, {"tiny-serve": TINY_SERVE},
                [{"name": "moe-cell", "config": "tiny",
                  "traffic": "tiny-serve", "chips": 1, "why": "a test"}],
                [_layer(n) for n in names], extra)
    return Cells(str(tmp_path))


def test_the_cell_runs_tiny_through_run_cell(tmp_path, quiet):
    lines, log = quiet
    cells = _cells(tmp_path, TINY_MIMO)
    out = run.run_cell(cells, "moe-cell", 2 ** 31 + 11, 0.3, False,
                       platform="cpu", log=log)
    assert out["correct"] is True and out["failed"] == 0, lines
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0
    traced = run.run_cell(cells, "moe-cell", 7, 0.3, True, platform="cpu",
                          log=log)
    got = traced["metrics"]
    # counters and host spans are read on the CPU too; what needs a
    # device plane is left out of the line
    assert 0.0 <= got["moe_rows_padded_pct"]["value"] < 100.0
    assert got["decode_ms_per_step_p50"]["value"] > 0
    for name in ("decode_step_roofline.moe", "prefill_moe_experts_roofline",
                 "decode_moe_experts_pct"):
        assert name not in got


def test_a_program_without_the_counters_reads_nothing(tmp_path, quiet):
    """The same metrics over GPT-2's records (as the parent of the PR
    that brought the counters would give them): left out, not raised."""
    cells = _cells(tmp_path, TINY_GPT)
    traced = run.run_cell(cells, "moe-cell", 7, 0.3, True, platform="cpu",
                          log=quiet[1])
    assert "moe_rows_padded_pct" not in traced["metrics"]
    assert "decode_step_roofline.moe" not in traced["metrics"]
    assert "decode_ms_per_step_p50" in traced["metrics"]


def _run(records, modules=None):
    return {"records": records, "cell": {"name": "x", "config": TINY_MIMO},
            "trace": {"modules": modules or {}},
            "peaks": {"hbm_bytes_per_s": 1e9, "bf16_flops_per_s": 1e12}}


def _group(t0, n, **counters):
    return [dict(counters, t_decode0=t0, prompt=list(range(5)),
                 tokens=list(range(n))) for _ in range(2)]


def test_readers_by_hand(monkeypatch):
    cells = Cells(ROOT)
    recs = _group(1.0, 3, moe_pairs_prefill=30, moe_pairs_decode=10,
                  moe_rows_computed_prefill=64, moe_rows_computed_decode=16,
                  moe_experts_hit_per_step=2.0) \
        + _group(2.0, 3, moe_pairs_prefill=10, moe_pairs_decode=10,
                 moe_rows_computed_prefill=32, moe_rows_computed_decode=8,
                 moe_experts_hit_per_step=2.0)
    # each group once: 1 - 60 / 120
    padded = cells.module("readers", "moe_rows_padded").read
    assert padded(_run(recs), {}) == pytest.approx(50.0)
    assert padded(_run([{"t_decode0": 1.0}]), {}) is None

    from benchmark import flops_mimo_v2 as flops

    roof = cells.module("readers", "decode_roofline_mimo_v2").read
    run_ = _run(recs, {"jit_serve_decode(1)": [2e-3, 4e-3, 9e-3],
                       "jit_serve_prefill(2)": [1.0]})
    # two steps a group, both rows live at lengths 6 and 7
    need = sum(flops.decode_step_bytes(TINY_MIMO, 2, [n, n], 2.0)
               for n in (6, 7)) / 2
    assert roof(run_, {"itemsize_of": "float16"}) == pytest.approx(
        100.0 * need / 1e9 / 5e-3)        # the mean execution, not 4e-3
    assert roof(_run(recs), {}) is None          # no trace of the program

    # XLA:TPU's ragged-dot custom calls carry no op_name: counted by
    # kind, under the scope's name only where they have no path
    tr = {"programs": {"jit_serve_prefill": {
              "a": 0.05, "b": 0.75, "ragged-dot-none.3": 0.2,
              "ragged-dot-none.4": 0.5}},
          "paths": {"jit_serve_prefill": {
              "a": "jit(serve_prefill)/serve.moe.experts/sort",
              "b": "jit(serve_prefill)/while/body/serve.attn_full/dot",
              "ragged-dot-none.4":
                  "jit(serve_prefill)/serve.attn_full/named_after_all"}}}
    monkeypatch.setattr(spans, "of_run", lambda run: tr)
    share = cells.module("readers", "moe_experts_roofline").read
    params = {"program": "jit_serve_prefill", "phase": "prefill",
              "scope": "serve.moe.experts", "scopes": SCOPES,
              "ops": ["ragged-dot"]}
    assert share(_run(recs), params) == pytest.approx(
        100.0 * flops.expert_flops(TINY_MIMO, 40) / 1e12 / 0.25)
    pct = cells.module("readers", "scope_share_ops").read
    assert pct(_run(recs), params) == pytest.approx(100.0 * 0.25 / 1.5)
    monkeypatch.setattr(spans, "of_run", lambda run: None)
    assert share(_run(recs), params) is None
