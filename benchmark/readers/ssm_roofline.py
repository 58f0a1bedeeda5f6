"""A share of the chip's roofline for a state-space recurrence in one
program: the least time the chip could take for what the program's
counter says was needed (`flops_jamba.py`: the larger of its bytes over
HBM bandwidth and its operations over the bf16 peak) over the device
time under the scopes ``under`` in the trace.

``counter`` names the records' field that counts the work, summed over
the traced groups, each once: ``ssm_positions_prefill`` ((real
position, Mamba layer) pairs of a prefill's scans) or
``ssm_row_updates_decode`` ((live row, Mamba layer) pairs of the decode
steps).  ``ops`` and ``bytes`` name the `flops_jamba` functions of that
count.  ``peaks.json`` has no rate for the vector unit or for
exponentials, so the bound is by bytes and a recurrence held up by its
exponentials reads low; the note says what each bound gives.

params: ``program``, ``counter``, ``ops``, ``bytes``, ``under`` (the
scopes whose time is summed) and ``scopes`` (every scope the program
names).  None where there is no trace, no such scope in it, or no
counter in the records (a program without it)."""

import numpy as np

from benchmark import flops_jamba as flops, spans


def read(run, params):
    tr = spans.of_run(run)
    found = tr and spans.scope_seconds(tr, params["program"],
                                       params["scopes"])
    if not found:
        return None
    seconds = sum(found[0][s] for s in params["under"])
    count, seen = 0, set()
    for rec in run["records"]:
        if params["counter"] in rec and rec.get("t_decode0") not in seen:
            seen.add(rec.get("t_decode0"))
            count += rec[params["counter"]]
    if not count or seconds <= 0:
        return None
    config, peaks = run["cell"]["config"], run["peaks"]
    itemsize = np.dtype(params.get("itemsize_of", "float16")).itemsize
    t_flops = getattr(flops, params["ops"])(config, count) \
        / peaks["bf16_flops_per_s"]
    t_bytes = getattr(flops, params["bytes"])(config, count, itemsize) \
        / peaks["hbm_bytes_per_s"]
    run.setdefault("notes", []).append(
        f"{params['program']}: {count} {params['counter']} need "
        f"{t_flops * 1e3:.2f} ms of operations at the bf16 peak and "
        f"{t_bytes * 1e3:.2f} ms of bytes; {seconds * 1e3:.1f} ms under "
        f"{' + '.join(params['under'])}")
    return 100.0 * max(t_flops, t_bytes) / seconds
