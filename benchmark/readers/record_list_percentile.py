"""A percentile over the gaps between consecutive entries of a list
field of the records (``token_t_us``: when each token of a group was
emitted, so the gaps are what a streaming caller waits between tokens).

params: ``field``, ``q`` (0..100), ``scale`` and optionally ``once_per``
(as `record_percentile`: the requests of one group share its list).
None where no record has the field with two entries or more."""

from benchmark.readers import record_percentile


def read(run, params):
    gaps, seen = [], set()
    for rec in run["records"]:
        values = rec.get(params["field"])
        if values is None or len(values) < 2:
            continue
        if "once_per" in params:
            key = rec.get(params["once_per"])
            if key in seen:
                continue
            seen.add(key)
        gaps.extend(b - a for a, b in zip(values, values[1:]))
    # one definition of a percentile: `record_percentile`'s
    return record_percentile.read(
        {"records": [{"gap": g} for g in gaps]},
        {"fields": ["gap"], "q": params["q"],
         "scale": params.get("scale", 1.0)})
