"""A decode step's share of its roofline, by the ``flops`` module the
metric names: the least time the chip could take for one step (the
larger of required bytes over HBM bandwidth and required operations
over the bf16 peak) over the decode program's device time in the trace
(the mean execution of ``jit_serve_decode``).  The six
`decode_roofline*` readers with the family's module a parameter.

For each step of each traced group the module is asked
``decode_step_bytes(config, itemsize, context_lengths, counters)`` and
``decode_step_flops(config, context_lengths, counters)``:
``context_lengths`` the positions of the rows that still wanted a token,
``counters`` the group's own (a record of it: the engine's timings) with
``decode_steps``.  Mean over the steps.

params: ``flops``, ``needs`` (the records' fields without which the
program is another family's: None then), ``program``, ``itemsize_of``.
None too where there is no trace."""

import importlib
import statistics

import numpy as np


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
        if "t_decode0" in rec and all(f in rec for f in params["needs"]):
            groups.setdefault(rec["t_decode0"], []).append(rec)
    if not groups or device_s <= 0:
        return None
    flops = importlib.import_module("benchmark." + params["flops"])
    need_bytes, need_flops = [], []
    for recs in groups.values():
        steps = max(len(r["tokens"]) for r in recs) - 1
        counters = dict(recs[0], decode_steps=steps)
        for j in range(steps):
            live = [len(r["prompt"]) + j + 1 for r in recs
                    if len(r["tokens"]) > j + 1]
            need_bytes.append(flops.decode_step_bytes(config, itemsize,
                                                      live, counters))
            need_flops.append(flops.decode_step_flops(config, live,
                                                      counters))
    if not need_bytes:
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
