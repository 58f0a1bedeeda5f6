"""A share of the chip's roofline for Command A+'s attention in one
program: the least time the chip could take for the query-key pairs the
layers of one kind were asked to score (`flops_cohere2_moe.py`) over the
device time under the scopes ``under`` in the trace.

The need is the program's own counter ``attn_<kind>_pairs_<phase>``
(live rows only; a window layer's pairs are the band's, never the
causal triangle's), summed over the traced groups, each once.
``phase`` ``prefill``: the flash forward kernel, operations over the
bf16 peak.  ``phase`` ``decode``: the per-row kernel over the caches,
the larger of the positions' bytes over HBM bandwidth and the
operations over the peak.

params: ``program``, ``phase``, ``kind`` (``window`` or ``full``),
``under`` (the scopes whose time is summed) and ``scopes`` (every scope
the program names).  None where there is no trace, no such scope in it,
or no counter in the records (a program without them)."""

from benchmark import flops_cohere2_moe as flops, spans


def _pairs(run, field):
    total, seen = 0, set()
    for rec in run["records"]:
        if field in rec and rec.get("t_decode0") not in seen:
            seen.add(rec.get("t_decode0"))
            total += rec[field]
    return total


def read(run, params):
    tr = spans.of_run(run)
    found = tr and spans.scope_seconds(tr, params["program"],
                                       params["scopes"])
    if not found:
        return None
    seconds = sum(found[0][s] for s in params["under"])
    pairs = _pairs(run, f"attn_{params['kind']}_pairs_{params['phase']}")
    if not pairs or seconds <= 0:
        return None
    config, peaks = run["cell"]["config"], run["peaks"]
    t_flops = flops.attn_flops(config, pairs) / peaks["bf16_flops_per_s"]
    t_bytes = 0.0 if params["phase"] == "prefill" else \
        flops.attn_bytes(config, pairs, 2) / peaks["hbm_bytes_per_s"]
    run.setdefault("notes", []).append(
        f"{params['program']}: {pairs} {params['kind']} pairs need "
        f"{t_flops * 1e3:.1f} ms of operations and {t_bytes * 1e3:.1f} ms "
        f"of reads; {seconds * 1e3:.1f} ms under "
        f"{' + '.join(params['under'])}")
    return 100.0 * max(t_flops, t_bytes) / seconds
