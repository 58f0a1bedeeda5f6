"""A share of the chip's roofline for Ouro's attention in one program:
the least time the chip could take for what the rows that still want a
token need (`flops_ouro.py`) over the device time under the scopes
``under`` in the trace.

``phase`` ``prefill``: attention inside the block.  Every prefilled row
wants a token, so the need is the counter ``attn_positions_prefill``
(each row's n (n + 1) / 2 pairs, in every (loop step, layer) slot)
summed over the traced groups, each once, as operations over the bf16
peak.  ``phase`` ``decode``: attention over the caches.  The program
runs every row of a group to the group's longest answer; the need
counts, from the records, only the steps of rows that still wanted a
token (a row of prompt n at step j reads n + j + 1 positions a slot), as
the larger of bytes over HBM bandwidth and operations over the peak.

params: ``program``, ``phase``, ``under`` (the scopes whose time is
summed) and ``scopes`` (every scope the program names).  None where
there is no trace, no such scope in it, or no counter in the records (a
program without them)."""

from benchmark import flops_ouro as flops, spans


def _positions(run, phase):
    config = run["cell"]["config"]
    field = "attn_positions_" + phase
    total, seen = 0, set()
    for rec in run["records"]:
        if field not in rec:
            continue
        if phase == "decode":
            total += flops.slots(config) * sum(
                len(rec["prompt"]) + j + 1
                for j in range(len(rec["tokens"]) - 1))
        elif rec.get("t_decode0") not in seen:
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
    positions = _positions(run, params["phase"])
    if not positions or seconds <= 0:
        return None
    config, peaks = run["cell"]["config"], run["peaks"]
    t_flops = flops.attn_flops(config, positions) / peaks["bf16_flops_per_s"]
    t_bytes = 0.0 if params["phase"] == "prefill" else \
        flops.decode_attn_bytes(config, positions, 2) \
        / peaks["hbm_bytes_per_s"]
    run.setdefault("notes", []).append(
        f"{params['program']}: {positions} attended positions need "
        f"{t_flops * 1e3:.1f} ms of operations and {t_bytes * 1e3:.1f} ms "
        f"of reads; {seconds * 1e3:.1f} ms under "
        f"{' + '.join(params['under'])}")
    return 100.0 * max(t_flops, t_bytes) / seconds
