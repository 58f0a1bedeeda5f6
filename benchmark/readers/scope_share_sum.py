"""`scope_share` over several scopes: the share of a compiled program's
device time that runs under any of the scopes ``under`` (a part of a
layer that the program names in pieces: MLA's projections).

params: ``program``, ``scopes`` (every scope the program names) and
``under``: those whose time is summed.  None where `scope_share` gives
None."""

from benchmark import spans


def read(run, params):
    tr = spans.of_run(run)
    found = tr and spans.scope_seconds(tr, params["program"],
                                       params["scopes"])
    if not found:
        return None
    by_scope, none, total = found
    if total <= 0 or none >= total:
        return None
    return 100.0 * sum(by_scope[s] for s in params["under"]) / total
