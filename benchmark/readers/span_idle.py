"""The device's idle share of the traced window under some of the
program's spans (`spans.py`: each idle instant belongs to the innermost
span open then).

params: ``spans``, a list of span names (a name ending in ``.`` or ``_``
is a prefix), or the string ``"unattributed"``: the window's idle total
(what `device_idle` reports) less the part under every span, so that the
cell's ``idle_*_pct`` metrics sum to its ``device_idle_pct``.  None
where the run has no trace of its own or the trace holds no span of the
program (the parent of the PR that brought the spans)."""

from benchmark import spans


def read(run, params):
    tr = spans.of_run(run)
    if tr is None or not tr["idle"] or run["window_s"] <= 0:
        return None
    want = params["spans"]
    if want == "unattributed":
        idle = run["window_s"] - tr["busy_s"] - sum(tr["idle"].values())
    else:
        idle = sum(v for k, v in tr["idle"].items()
                   if any(k == w or (w[-1] in "._" and k.startswith(w))
                          for w in want))
    return 100.0 * idle / run["window_s"]
