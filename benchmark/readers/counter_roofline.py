"""A share of the chip's roofline for a part of one program whose work
the program counts: the least time the chip could take for what the
counter says was needed (the larger of its bytes over HBM bandwidth and
its operations over the bf16 peak, by the functions of the ``flops``
module the metric names) over the device time under the scopes
``under`` in the trace.  `ssm_roofline` and `moe_experts_roofline` with
the family's module a parameter, so that a family brings a `flops_*`
module and metric files and no reader.

params: ``flops`` (a module of ``benchmark/``, by name), ``program``,
``counter`` (the records' field that counts the work, summed over the
traced groups, each once), ``ops`` and ``bytes`` (the module's functions
of that count, ``(config, count)`` and ``(config, count, itemsize)``;
either may be left out), ``under`` (the scopes whose time is summed),
``scopes`` (every scope the program names) and optionally ``unnamed``:
prefixes of operations that belong under ``under`` though the compiler
left them without an ``op_name`` (`scope_share_ops`: the grouped
products' custom calls).  None where there is no trace, no such scope in
it, or no counter in the records (a program without it)."""

import importlib

import numpy as np

from benchmark import spans


def read(run, params):
    tr = spans.of_run(run)
    found = tr and spans.scope_seconds(tr, params["program"],
                                       params["scopes"])
    if not found:
        return None
    seconds = sum(found[0][s] for s in params["under"])
    if params.get("unnamed"):
        paths = tr["paths"].get(params["program"], {})
        seconds += sum(
            secs for op, secs in tr["programs"][params["program"]].items()
            if not paths.get(op)
            and any(op.startswith(p) for p in params["unnamed"]))
    count, seen = 0, set()
    for rec in run["records"]:
        if params["counter"] in rec and rec.get("t_decode0") not in seen:
            seen.add(rec.get("t_decode0"))
            count += rec[params["counter"]]
    if not count or seconds <= 0:
        return None
    flops = importlib.import_module("benchmark." + params["flops"])
    config, peaks = run["cell"]["config"], run["peaks"]
    itemsize = np.dtype(params.get("itemsize_of", "float16")).itemsize
    t_flops = getattr(flops, params["ops"])(config, count) \
        / peaks["bf16_flops_per_s"] if params.get("ops") else 0.0
    t_bytes = getattr(flops, params["bytes"])(config, count, itemsize) \
        / peaks["hbm_bytes_per_s"] if params.get("bytes") else 0.0
    run.setdefault("notes", []).append(
        f"{params['program']}: {count} {params['counter']} need "
        f"{t_flops * 1e3:.2f} ms of operations at the bf16 peak and "
        f"{t_bytes * 1e3:.2f} ms of bytes; {seconds * 1e3:.1f} ms under "
        f"{' + '.join(params['under'])}")
    return 100.0 * max(t_flops, t_bytes) / seconds
