"""`correct` is a comparison that has been shown to fail.

Tiny, on the CPU: (1) the control, the reference in float8 in the
program's place, comes out not correct; (2) a run driven through the
harness with the timed path broken underneath comes out not correct.  On
the chip the same two are read at the cells' own sizes by
`benchmark/control.py`; PERF.md has those readings.
"""

import pytest

from benchmark import control, run
from benchmark.cells import Cells

from conftest import TINY_GPT, TINY_SERVE, TINY_TRAIN, write_bench

# limits for the tiny sizes, set as the contract sets the real ones:
# above the sound runs' largest over a dozen seeds, below the control's
# smallest (readings in the assertions' messages when they move)
SERVE_LIMITS = {"served_token_logit_gap_max": 3e-4}
TRAIN_LIMITS = {"loss_gap_max": 3e-4, "first_gradient_norm_gap_max": 0.1,
                "parameter_change_norm_gap_max": 0.3,
                "first_gradient_error_max": 0.05}


def _cells(tmp_path):
    write_bench(str(tmp_path), {"tiny-gpt": TINY_GPT},
                {"tiny-serve": dict(TINY_SERVE, limits=SERVE_LIMITS,
                                    output_lengths=[8, 16, 24, 40],
                                    check_tokens=1500),
                 "tiny-train": dict(TINY_TRAIN, limits=TRAIN_LIMITS)},
                [{"name": "serve", "config": "tiny-gpt",
                  "traffic": "tiny-serve", "chips": 1, "why": "t"},
                 {"name": "train", "config": "tiny-gpt",
                  "traffic": "tiny-train", "chips": 1, "why": "t"}])
    return Cells(str(tmp_path))


@pytest.mark.parametrize("workload", ["serve", "train"])
def test_sound_runs_pass_and_the_float8_control_fails(tmp_path, workload):
    out = control.read(_cells(tmp_path), workload, [11, 2 ** 31 + 5, 77],
                       0.5, platform="cpu", log=lambda m: None)
    assert out["correct"] == [True, True, True], out
    limits = SERVE_LIMITS if workload == "serve" else TRAIN_LIMITS
    for seed_idx in range(3):
        failed = [n for n, vals in out["control"].items()
                  if vals[seed_idx] > limits[n]]
        assert failed, (f"the control passed every limit on seed "
                        f"{seed_idx}: {out}")


def test_a_token_altered_where_it_is_produced_is_not_correct(
        tmp_path, monkeypatch):
    from mxnet_tpu.serving import engine

    real = engine._sample

    def off_by_one(last, temperature, rng):
        return (real(last, temperature, rng) + 1) % last.shape[-1]

    monkeypatch.setattr(engine, "_sample", off_by_one)
    out = run.run_cell(_cells(tmp_path), "serve", 5, 0.2, False,
                       platform="cpu", log=lambda m: None)
    assert out["correct"] is False and out["failed"] == 0


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        tmp_path, monkeypatch):
    from mxnet_tpu import gluon

    real = gluon.Trainer.train_step

    def no_update(self, block, loss_fn, data, label=None, **kw):
        before = [(p, p.data()._data + 0) for p in self._params]
        loss = real(self, block, loss_fn, data, label, **kw)
        for p, value in before:
            p.set_data(value)
        return loss

    monkeypatch.setattr(gluon.Trainer, "train_step", no_update)
    lines = []
    out = run.run_cell(_cells(tmp_path), "train", 5, 0.2, False,
                       platform="cpu", log=lines.append)
    assert out["correct"] is False
    assert any("parameter_change_norm_gap_max" in ln and "NOT OK" in ln
               for ln in lines)


def test_part_of_the_batch_left_out_is_not_correct(tmp_path, monkeypatch):
    from mxnet_tpu import gluon

    real = gluon.Trainer.train_step

    def half_batch(self, block, loss_fn, data, label=None, **kw):
        half = data.shape[0] // 2
        return real(self, block, loss_fn, data[:half], label[:half], **kw)

    monkeypatch.setattr(gluon.Trainer, "train_step", half_batch)
    out = run.run_cell(_cells(tmp_path), "train", 5, 0.2, False,
                       platform="cpu", log=lambda m: None)
    assert out["correct"] is False
