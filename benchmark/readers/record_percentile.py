"""A percentile of a sum of the records' fields.

params: ``fields`` (summed per record), ``q`` (0..100), ``scale``
(multiplies the result), and optionally ``once_per`` (a field: records
that share its value count once, as the requests of one group share
their group's timings)."""

import statistics


def read(run, params):
    values, seen = [], set()
    for rec in run["records"]:
        if not all(f in rec for f in params["fields"]):
            continue
        if "once_per" in params:
            key = rec.get(params["once_per"])
            if key in seen:
                continue
            seen.add(key)
        values.append(sum(float(rec[f]) for f in params["fields"]))
    if not values:
        return None
    values.sort()
    if params["q"] == 50:
        out = statistics.median(values)
    else:
        out = values[min(len(values) - 1,
                         int(len(values) * params["q"] / 100.0))]
    return out * params.get("scale", 1.0)
