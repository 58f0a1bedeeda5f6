"""`flops_mimo_v2.py`: the counts behind `decode_step_roofline.moe` and
`prefill_moe_experts_roofline`, checked against the configuration's own
list of leaves and by hand."""

import json
import os

import numpy as np
import pytest

from benchmark import flops_mimo_v2 as flops
from benchmark.references import mimo_v2 as ref

from conftest import ROOT


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "mimo-v2.5-ep16.json")) as f:
        return json.load(f)


def test_the_parts_add_up_to_the_leaves(config):
    """Non-expert weights + the held experts + the embedding are every
    leaf of the reference's list: 3.43B parameters."""
    leaves = sum(int(np.prod(s)) for _, s, _ in ref.param_spec(config))
    held = 6 * 16 * flops.expert_params(config)
    embedding = config["vocab_size"] * config["hidden_size"]
    assert flops.non_expert_params(config) + held + embedding == leaves
    assert leaves == pytest.approx(3.43e9, rel=2e-3)
    # ISSUE.md's table: full and window attention, one expert
    assert flops.attention_params(config, 4) == pytest.approx(89.1e6, rel=1e-3)
    assert flops.attention_params(config, 8) == pytest.approx(94.4e6, rel=1e-3)
    assert flops.expert_params(config) == 3 * 4096 * 2048


def test_decode_bytes_by_hand(config):
    base = flops.decode_step_bytes(config, 2, [], 0)
    assert base == 2 * flops.non_expert_params(config)
    # every held expert of the six expert layers hit: 4.83 GB of experts
    full = flops.decode_step_bytes(config, 2, [], 16)
    assert full - base == 2 * 6 * 16 * 3 * 4096 * 2048
    assert (full - base) / 1e9 == pytest.approx(4.83, rel=2e-3)
    # a row of 100 positions: 2 full layers x 4 heads and 5 window
    # layers x 8 heads, 320 values a head and position
    row = flops.decode_step_bytes(config, 2, [100], 0) - base
    assert row == 2 * 320 * (2 * 4 * 100 + 5 * 8 * 100)
    # past the window a window layer reads 128 positions
    row = flops.decode_step_bytes(config, 2, [1000], 0) - base
    assert row == 2 * 320 * (2 * 4 * 1000 + 5 * 8 * 128)


def test_expert_operations(config):
    assert flops.expert_flops(config, 1) == 6 * 4096 * 2048
    # 64 rows and no assignment: twice the weights a row multiplies
    # (embedding and gains left out), so less than twice all of them
    ops = flops.decode_step_flops(config, 64, 0)
    assert ops < 2 * 64 * flops.non_expert_params(config)
    assert flops.decode_step_flops(config, 64, 10) - ops \
        == flops.expert_flops(config, 10)
