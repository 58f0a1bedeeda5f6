"""A share of the chip's bf16 peak for one part of Keye-VL-2.0's
attention in one program: the operations its real tokens need
(`flops_keye_vl2.py`'s function ``flops`` of the counter ``counter``,
summed over the traced groups, each once) over the peak, over the device
time under the scopes ``under`` in the trace.

params: ``program`` (``jit_serve_prefill``), ``counter``
(``attn_keys_selected_prefill``), ``flops`` (``attn_sparse_flops``),
``under`` (the scopes whose time is summed) and ``scopes`` (every scope
the program names).  None where there is no trace, no such scope in it,
or no counter in the records."""

from benchmark import flops_keye_vl2 as flops, spans


def read(run, params):
    tr = spans.of_run(run)
    found = tr and spans.scope_seconds(tr, params["program"],
                                       params["scopes"])
    if not found:
        return None
    seconds = sum(found[0][s] for s in params["under"])
    pairs, seen = 0, set()
    for rec in run["records"]:
        if params["counter"] in rec and rec.get("t_decode0") not in seen:
            seen.add(rec.get("t_decode0"))
            pairs += rec[params["counter"]]
    if not pairs or seconds <= 0:
        return None
    need = getattr(flops, params["flops"])(run["cell"]["config"], pairs)
    run.setdefault("notes", []).append(
        f"{params['program']}: {pairs} pairs of {params['counter']} need "
        f"{need / 1e12:.2f} TFLOP; {seconds * 1e3:.1f} ms under "
        f"{' + '.join(params['under'])}")
    return 100.0 * need / run["peaks"]["bf16_flops_per_s"] / seconds
