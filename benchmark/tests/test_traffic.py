"""The stratified closed-loop generator of `kinds/serve_closed.py`."""

import numpy as np
import pytest

from benchmark.cells import Cells

from conftest import ROOT

SEEDS = [0, 1, 7, 12345, 2 ** 31 - 1, 2 ** 31, 2 ** 31 + 11, 99, 4242,
         31337, 2 ** 32 + 5, 600613]


@pytest.fixture(scope="module")
def cell():
    return Cells(ROOT).cell("gpt2m-serve-chat")


@pytest.mark.parametrize("seed", SEEDS)
def test_every_round_holds_each_entry_once(cell, seed):
    traffic, kind = cell["traffic"], cell["kind"]
    plan = kind.schedule(traffic, seed, rounds=6)
    assert plan.shape == (6, traffic["clients"], 2)
    for r in range(6):
        assert sorted(plan[r, :, 0]) == sorted(traffic["prompt_lengths"])
        assert sorted(plan[r, :, 1]) == sorted(traffic["output_lengths"])


def test_useful_tokens_a_round_do_not_depend_on_the_seed(cell):
    traffic, kind = cell["traffic"], cell["kind"]
    per_round = {int(kind.schedule(traffic, s, 3)[r, :, 1].sum())
                 for s in SEEDS for r in range(3)}
    assert per_round == {sum(traffic["output_lengths"])} == {1281}


def test_the_seed_moves_who_asks_what(cell):
    traffic, kind = cell["traffic"], cell["kind"]
    plans = [kind.schedule(traffic, s, 2) for s in SEEDS]
    assert any(not np.array_equal(plans[0], p) for p in plans[1:])
    assert np.array_equal(kind.schedule(traffic, 5, 2),
                          kind.schedule(traffic, 5, 2))
    a = kind.prompt_ids(5, 3, 0, 40, 50257)
    assert np.array_equal(a, kind.prompt_ids(5, 3, 0, 40, 50257))
    assert not np.array_equal(a, kind.prompt_ids(6, 3, 0, 40, 50257))
    assert a.min() >= 0 and a.max() < 50257


def test_prompt_plus_answer_fits_the_window(cell):
    traffic = cell["traffic"]
    assert max(traffic["prompt_lengths"]) + max(traffic["output_lengths"]) \
        <= cell["config"]["n_positions"]


def test_tables_are_the_mid_quantiles_the_file_says(cell):
    """(i + 0.5)/16 quantiles of the two lognormals, clipped, rounded down."""
    from statistics import NormalDist

    def table(median, sigma, lo, hi):
        return [int(min(hi, max(lo, median * np.exp(
            sigma * NormalDist().inv_cdf((i + 0.5) / 16)))))
            for i in range(16)]

    traffic = cell["traffic"]
    assert traffic["prompt_lengths"] == table(128, 0.8, 16, 512)
    assert traffic["output_lengths"] == table(56, 1.0, 8, 256)


def test_training_rows_all_differ():
    from benchmark import data

    ids, _ = data.permutation_corpus(2 ** 31 + 3, 3, 4,
                                     {"seq_len": 16}, {"vocab_size": 97})
    rows = ids.reshape(-1, 16)
    assert len({tuple(r) for r in rows}) == len(rows)
    assert ids.min() >= 0 and ids.max() < 97
