"""Model FLOP/s utilisation of a training cell: the operations the
forward and backward passes require per unit of work (`flops.py`, no
recomputation counted) times the traced run's rate, over chips times the
chip's bf16 peak."""

from benchmark import flops


def read(run, params):
    cell = run["cell"]
    per_unit = flops.train_flops_per_unit(cell["config"], cell["traffic"])
    peak = cell["chips"] * run["peaks"]["bf16_flops_per_s"]
    return 100.0 * per_unit * run["rate"] / peak
