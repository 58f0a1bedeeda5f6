"""The Jamba decode step's share of its roofline: the least time the
chip could take for one step (the larger of required bytes over HBM
bandwidth and required operations over the bf16 peak, `flops_jamba.py`)
over the decode program's device time in the trace (the mean execution
of ``jit_serve_decode``).  Bytes: every parameter once, and for each row
that still wants a token its states and tails in and out (26 layers'
whatever the row's length) and its cached positions in the attention
layers.  Mean over the traced groups' steps.  None where the records
carry no state counters (a program without them) or there is no
trace."""

import statistics

import numpy as np

from benchmark import flops_jamba as flops


def read(run, params):
    modules = run["trace"]["modules"]
    times = [t for k, v in modules.items()
             if k.startswith(params.get("program", "jit_serve_decode"))
             for t in v]
    if not times:
        return None
    device_s = statistics.mean(times)
    median_s = statistics.median(times)
    config = run["cell"]["config"]
    itemsize = np.dtype(params.get("itemsize_of", "float16")).itemsize
    groups = {}
    for rec in run["records"]:
        if "t_decode0" in rec and "ssm_row_updates_decode" in rec:
            groups.setdefault(rec["t_decode0"], []).append(rec)
    need_bytes, need_flops = [], []
    for recs in groups.values():
        steps = max(len(r["tokens"]) for r in recs) - 1
        for j in range(steps):
            live = [len(r["prompt"]) + j + 1 for r in recs
                    if len(r["tokens"]) > j + 1]
            need_bytes.append(flops.decode_step_bytes(config, itemsize,
                                                      live))
            need_flops.append(flops.decode_step_flops(config, live))
    if not need_bytes or device_s <= 0:
        return None
    t_bytes = statistics.mean(need_bytes) / run["peaks"]["hbm_bytes_per_s"]
    t_flops = statistics.mean(need_flops) / run["peaks"]["bf16_flops_per_s"]
    run.setdefault("notes", []).append(
        f"decode step: bound by {'bytes' if t_bytes >= t_flops else 'flops'}"
        f" ({t_bytes * 1e3:.3f} ms against {t_flops * 1e3:.3f} ms), "
        f"jit_serve_decode took {device_s * 1e3:.3f} ms on the device (the "
        f"mean execution; by the median, {median_s * 1e3:.3f} ms, the share "
        f"would read {100.0 * max(t_bytes, t_flops) / median_s:.4f})")
    return 100.0 * max(t_bytes, t_flops) / device_s
