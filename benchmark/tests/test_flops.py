"""`flops.py` and `peaks.json`: the yardstick has no defaults."""

import json
import os

import pytest

from benchmark import flops
from benchmark.cells import HERE

GPT2M = {"n_embd": 1024, "n_layer": 24, "vocab_size": 50257,
         "reference": "gpt2"}


def test_gpt2_medium_counts():
    # 24 x (24 C^2 + 2 T C) + 2 V C per token forward, three times that
    per_token = 24 * (24 * 1024 ** 2 + 2 * 1024 * 1024) + 2 * 50257 * 1024
    assert flops.gpt2_forward_flops_per_token(GPT2M, 1024) == per_token
    assert flops.train_flops_per_unit(GPT2M, {"seq_len": 1024}) \
        == 3 * per_token
    # 354.8M parameters without the position table, in bf16
    assert flops.gpt2_weight_bytes(GPT2M, 2) == pytest.approx(
        2 * 353.77e6, rel=1e-3)


def test_decode_bytes_count_live_rows_only():
    none = flops.gpt2_decode_step_bytes(GPT2M, 2, [])
    one = flops.gpt2_decode_step_bytes(GPT2M, 2, [100])
    assert none == flops.gpt2_weight_bytes(GPT2M, 2)
    assert one - none == 2 * 24 * 100 * 1024 * 2


def test_resnet50_is_the_papers_count():
    cfg = {"image_size": 224, "num_classes": 1000,
           "reference": "resnet50_v1"}
    # He et al. give 3.8e9 multiply-adds
    macs = flops.resnet50_v1_forward_flops_per_image(cfg) / 2
    assert 3.7e9 < macs < 3.95e9
    assert flops.train_flops_per_unit(cfg, {}) == pytest.approx(6 * macs)


def test_unknown_family_and_kind_raise():
    with pytest.raises(KeyError):
        flops.train_flops_per_unit({"reference": "mystery"}, {})
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["source"]
    assert "cpu" not in peaks


def test_run_refuses_a_kind_peaks_does_not_hold(tmp_path):
    from benchmark import run
    from benchmark.cells import Cells

    from conftest import TINY_GPT, TINY_SERVE, write_bench

    write_bench(str(tmp_path), {"tiny-gpt": TINY_GPT},
                {"tiny-serve": TINY_SERVE},
                [{"name": "t", "config": "tiny-gpt",
                  "traffic": "tiny-serve", "chips": 1, "why": "t"}])
    with open(tmp_path / "tb" / "peaks.json", "w") as f:
        json.dump({"TPU v9": {}}, f)
    with pytest.raises(run.NoChip, match="peaks.json"):
        run.run_cell(Cells(str(tmp_path)), "t", 1, 0.1, False,
                     platform="cpu", log=lambda m: None)
    with pytest.raises(run.NoChip, match="required"):
        run.run_cell(Cells(str(tmp_path)), "t", 1, 0.1, False,
                     platform="tpu", log=lambda m: None)
