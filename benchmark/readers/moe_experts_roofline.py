"""The held experts' share of the chip's bf16 peak in one program: the
operations the assignments need (``moe_pairs_<phase>`` of each traced
group x three products of hidden x expert width, `flops_mimo_v2.py`)
over the peak, over the device time under the program's
``serve.moe.experts`` scope in the trace, the grouped products' unnamed
custom calls included (`scope_share_ops`).

params: ``program`` (``jit_serve_prefill``), ``phase`` (``prefill``),
``scope``, ``scopes`` and ``ops`` as `scope_share_ops`.  None where there is no
trace, no such scope in it, or no counter in the records."""

from benchmark import flops_mimo_v2 as flops, spans
from benchmark.readers import scope_share_ops


def read(run, params):
    found = scope_share_ops.seconds(spans.of_run(run), params)
    if found is None:
        return None
    seconds = found[0]
    field = "moe_pairs_" + params["phase"]
    pairs, seen = 0, set()
    for rec in run["records"]:
        if field in rec and rec.get("t_decode0") not in seen:
            seen.add(rec.get("t_decode0"))
            pairs += rec[field]
    if not pairs or seconds <= 0:
        return None
    need = flops.expert_flops(run["cell"]["config"], pairs)
    run.setdefault("notes", []).append(
        f"{params['program']}: {pairs} assignments to held experts need "
        f"{need / 1e12:.2f} TFLOP; {seconds * 1e3:.1f} ms under "
        f"{params['scope']}")
    return 100.0 * need / run["peaks"]["bf16_flops_per_s"] / seconds
