"""The benchmark's own tests: tiny, on the CPU, through the functions a
chip run goes through.  They are not part of tier-1.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os
import sys

# four virtual CPU devices, for the sharded kind's test; set before JAX
# is first imported
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=4")

import pytest       # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# what every serving cell reports under one name (a cell joins a metric by
# its name in the entry's ``workloads``, no file of its own), the experts'
# padding that the four expert cells share, and the start-up metrics of
# every cell: each in BENCHMARK.json's order, where the generic entries
# come before any family's own and the start-up entries last
SERVING = ["device_idle_pct.serve", "decode_rows_useful_pct",
           "serve_ttft_ms_p50", "decode_ms_per_step_p50",
           "serve_token_gap_ms_p95", "idle_readback_pct.serve",
           "idle_host_pct.serve", "idle_collect_pct.serve",
           "idle_unattributed_pct.serve"]
PADDED = "moe_rows_padded_pct"
SETUP = ["setup_before_import_s", "setup_import_s", "setup_params_s",
         "setup_trace_lower_s", "setup_compile_s", "setup_warm_run_s",
         "setup_unattributed_s"]
# of SERVING, what a CPU run reads (the others need a device plane)
SERVING_ON_THE_CPU = ["decode_rows_useful_pct", "serve_ttft_ms_p50",
                      "decode_ms_per_step_p50", "serve_token_gap_ms_p95"]

TINY_GPT = {
    "name": "tiny-gpt", "source": "a test's own", "model_type": "gpt2",
    "n_embd": 32, "n_layer": 2, "n_head": 2, "n_positions": 64,
    "vocab_size": 128, "layer_norm_epsilon": 1e-05,
    "initializer_range": 0.02, "reduced": [], "reference": "gpt2",
    "program": {
        "constructor": "mxnet_tpu.gluon.model_zoo.gpt.GPTModel",
        "kwargs": {"vocab_size": 128, "units": 32, "num_layers": 2,
                   "num_heads": 2, "max_length": 64, "dropout": 0.0,
                   "scan_layers": True, "attention_impl": "dense"},
        "dtype": "bfloat16",
        "loss": "mxnet_tpu.gluon.model_zoo.gpt.GPTLMLoss"}}

TINY_SERVE = {
    "kind": "serve_closed", "clients": 4, "batch_buckets": [4],
    "prompt_lengths": [3, 5, 9, 16], "output_lengths": [2, 3, 5, 8],
    "rate_metric": "serve_tokens_per_s", "work_unit": "tokens",
    "trace_seconds": 0.01, "check_tokens": 20,
    "limits": {"served_token_logit_gap_max": 0.05}}

TINY_TRAIN = {
    "kind": "train_steps", "batch": 4, "seq_len": 64, "batches": 3,
    "data": "permutation_corpus", "optimizer": "adamw",
    "optimizer_params": {"learning_rate": 0.001},
    "train_step_batch_size": 1, "reference_rows": 2,
    "rate_metric": "train_tokens_per_s", "work_unit": "tokens",
    "work_per_step": 256, "trace_seconds": 0.01,
    "limits": {"loss_gap_max": 0.05, "first_gradient_norm_gap_max": 0.1,
               "parameter_change_norm_gap_max": 0.3,
               "first_gradient_error_max": 0.05}}


def _e2e(name, unit, cells):
    return {"name": name, "unit": unit, "better": "higher", "bound": 0.05,
            "source": "host_clock", "workloads": cells}


def write_bench(root, configs, traffics, workloads, per_layer=(),
                extra_files=()):
    """A benchmark of a test's own under ``root``: BENCHMARK.json and
    its files in ``root/tb``; kinds, readers and references come from
    the harness's own directory unless ``extra_files`` adds them."""
    tb = os.path.join(root, "tb")
    for group in ("configs", "traffic", "metrics", "readers", "kinds"):
        os.makedirs(os.path.join(tb, group), exist_ok=True)
    for name, body in configs.items():
        with open(os.path.join(tb, "configs", name + ".json"), "w") as f:
            json.dump(body, f)
    for name, body in traffics.items():
        with open(os.path.join(tb, "traffic", name + ".json"), "w") as f:
            json.dump(body, f)
    for rel, text in extra_files:
        with open(os.path.join(tb, rel), "w") as f:
            f.write(text)
    with open(os.path.join(tb, "peaks.json"), "w") as f:
        json.dump({"cpu": {"bf16_flops_per_s": 1e12,
                           "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10,
                           "source": "a test's own"}}, f)
    rates = {}
    for w in workloads:
        rates.setdefault(traffics[w["traffic"]]["rate_metric"],
                         []).append(w["name"])
    bench = {
        "command": ["python3", "benchmark/run.py"], "paths": ["tb"],
        "run_seconds": 1,
        "configs": [{"name": n, "source": "test",
                     "file": f"tb/configs/{n}.json", "reduced": [],
                     "why": "test"} for n in configs],
        "workloads": workloads,
        "end_to_end": [_e2e(n, "x/s", c) for n, c in rates.items()] + [
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.1, "source": "host_clock"}],
        "per_layer": list(per_layer)}
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return bench


@pytest.fixture
def quiet():
    lines = []
    return lines, lines.append
