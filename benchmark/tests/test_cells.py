"""A cell made only of new files is found and run with no edit to the
harness: a configuration, a traffic mix, a traffic kind, a per-layer
metric and its reader, all under a temporary directory."""

import json

import pytest

from benchmark import run
from benchmark.cells import Cells

from conftest import TINY_GPT, TINY_SERVE, write_bench

NEW_READER = '''
def read(run, params):
    asked = [r["asked"] for r in run["records"]]
    return params["scale"] * sum(asked) / len(asked) if asked else None
'''

NEW_KIND = '''
"""A kind of a test's own: the closed loop, with every answer cut to
two tokens."""
import importlib.util, os

_spec = importlib.util.spec_from_file_location(
    "_closed", os.path.join(r"%s", "kinds", "serve_closed.py"))
_closed = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_closed)
setup, close, verify, arrays = (_closed.setup, _closed.close,
                                _closed.verify, _closed.arrays)


def drive(state, window, ctx):
    state["plan"][:, :, 1] = 2
    return _closed.drive(state, window, ctx)
'''


def _bench(tmp_path, traffic, extra=()):
    per_layer = [
        {"name": "serve_queue_ms_p50", "unit": "ms", "better": "lower",
         "source": "program_span", "layer": "serving.ContinuousBatcher",
         "moves": "serve_tokens_per_s"},
        {"name": "asked_tokens_mean", "unit": "tokens", "better": "higher",
         "source": "program_counter", "layer": "traffic",
         "moves": "serve_tokens_per_s", "workloads": ["new-cell"]},
        {"name": "decode_step_roofline", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "model step and kernels",
         "moves": "serve_tokens_per_s"}]
    metric = {"name": "asked_tokens_mean", "unit": "tokens",
              "reader": "mean_asked", "params": {"scale": 1.0}}
    write_bench(str(tmp_path), {"tiny-gpt": TINY_GPT},
                {"tiny-serve": traffic},
                [{"name": "new-cell", "config": "tiny-gpt",
                  "traffic": "tiny-serve", "chips": 1, "why": "a test"}],
                per_layer,
                [("metrics/asked_tokens_mean.json", json.dumps(metric)),
                 ("readers/mean_asked.py", NEW_READER)] + list(extra))
    return Cells(str(tmp_path))


def test_new_cell_config_traffic_metric_and_reader(tmp_path, quiet):
    lines, log = quiet
    cells = _bench(tmp_path, TINY_SERVE)
    out = run.run_cell(cells, "new-cell", 2 ** 31 + 7, 0.3, False,
                       platform="cpu", log=log)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert out["metrics"]["serve_tokens_per_s"]["value"] > 0
    assert out["device"]["platform"] == "cpu"
    assert "breakdown" not in out
    assert any("served_token_logit_gap_max" in ln and "limit" in ln
               for ln in lines)

    traced = run.run_cell(cells, "new-cell", 5, 0.3, True,
                          platform="cpu", log=log)
    # the new metric through its new reader; a reader with nothing to
    # read (no device plane in a CPU trace) is left out of the line
    assert traced["metrics"]["asked_tokens_mean"]["value"] == 4.5
    assert "serve_queue_ms_p50" in traced["metrics"]
    assert "decode_step_roofline" not in traced["metrics"]
    assert "serve_tokens_per_s" not in traced["metrics"]
    assert set(traced["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"busy_s", "window_s"} <= set(traced["device"])
    assert traced["attempted"] == 4          # the traced window: a round


def test_new_traffic_kind_is_a_file(tmp_path, quiet):
    from benchmark.cells import HERE

    traffic = dict(TINY_SERVE, kind="serve_two")
    cells = _bench(tmp_path, traffic,
                   [("kinds/serve_two.py", NEW_KIND % HERE)])
    out = run.run_cell(cells, "new-cell", 3, 0.2, False, platform="cpu",
                       log=quiet[1])
    assert out["correct"] is True
    rec = json.load(open(tmp_path / "benchmark_out" / "new-cell" /
                         "seed3-trace0" / "records.json"))
    assert {len(r["tokens"]) for r in rec["records"]} == {2}
    # the rate is the work of whole requests over first submit to last
    # completion
    assert rec["rate"] * rec["elapsed_s"] == pytest.approx(
        2 * len(rec["records"]))


def test_sharded_training_cell_on_four_virtual_devices(tmp_path, quiet):
    """`train_steps` under ``parallel`` (shard_model fsdp over 4 devices)
    agrees with the single-device reference."""
    import jax

    from conftest import TINY_TRAIN

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    traffic = dict(TINY_TRAIN, parallel={"axes": {"dp": 4}, "mode": "fsdp"})
    write_bench(str(tmp_path), {"tiny-gpt": TINY_GPT},
                {"tiny-fsdp": traffic},
                [{"name": "fsdp", "config": "tiny-gpt",
                  "traffic": "tiny-fsdp", "chips": 4, "why": "t"}])
    out = run.run_cell(Cells(str(tmp_path)), "fsdp", 9, 0.2, False,
                       platform="cpu", log=quiet[1])
    assert out["correct"] is True and out["device"]["count"] >= 4
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0


def test_resnet_reference_names_every_parameter_of_the_model_zoo():
    """The unproved ResNet cell's files stay true to the program: every
    parameter of ``resnet50_v1`` gets a seeded value from the
    reference's list of leaves (names and known shapes are checked)."""
    from benchmark import program
    from conftest import ROOT

    cells = Cells(ROOT)
    config = cells.data("configs", "resnet50-v1")
    cell = {"config": dict(config, num_classes=10,
                           program=dict(config["program"],
                                        kwargs={"classes": 10})),
            "reference": cells.module("references", config["reference"])}
    net, leaves = program.build_net(cell, 3, "cpu")
    assert len(leaves) == len(net.collect_params()) == 299
    assert leaves["stage4_conv9_weight"].shape == (2048, 512, 1, 1)
