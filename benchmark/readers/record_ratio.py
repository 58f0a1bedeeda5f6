"""The ratio of two of the records' fields, each summed over the groups
of the window (records that share ``once_per``'s value count once, as
the requests of one group share their group's counters).

params: ``field`` (summed above the line), ``per`` (the field summed
below it) and ``once_per``.  None where no record
carries both fields (a program without the counter) or the lower sum is
zero."""


def read(run, params):
    above = below = 0.0
    seen = set()
    for rec in run["records"]:
        if params["field"] not in rec or params["per"] not in rec:
            continue
        key = rec.get(params["once_per"])
        if key in seen:
            continue
        seen.add(key)
        above += float(rec[params["field"]])
        below += float(rec[params["per"]])
    if not below:
        return None
    return above / below
