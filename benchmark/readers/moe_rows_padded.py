"""The share of the grouped product's rows that held no assignment:
1 - ``moe_pairs`` / ``moe_rows_computed`` (prefill and decode together),
over the groups of the window, each counted once.  The rows are the
buffer the product was given (`ops/moe.py::held_experts_ffn`: passes x
``pass_rows``).  None where the records carry no such counters."""

FIELDS = ("moe_pairs_prefill", "moe_pairs_decode",
          "moe_rows_computed_prefill", "moe_rows_computed_decode")


def read(run, params):
    pairs = rows = 0
    seen = set()
    for rec in run["records"]:
        if not all(f in rec for f in FIELDS) \
                or rec.get("t_decode0") in seen:
            continue
        seen.add(rec.get("t_decode0"))
        pairs += rec[FIELDS[0]] + rec[FIELDS[1]]
        rows += rec[FIELDS[2]] + rec[FIELDS[3]]
    if not rows:
        return None
    return 100.0 * (1.0 - pairs / rows)
