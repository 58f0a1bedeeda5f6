"""The share of a compiled program's device time under none of its
scopes, less the unnamed operations that `scope_share_ops` counts into a
scope by their kind: what the compiler made (copies, slices of scanned
weights, loop overhead).  With it a program's shares by scope, read by
`scope_share` and `scope_share_ops`, and this rest sum to 100.

params: ``program``, ``scopes`` and ``ops`` as `scope_share_ops` (no
``scope``: the rest has none).  None where `scope_share` gives None."""

from benchmark import spans


def read(run, params):
    tr = spans.of_run(run)
    found = tr and spans.scope_seconds(tr, params["program"],
                                       params["scopes"])
    if not found:
        return None
    _, none, total = found
    if total <= 0 or none >= total:
        return None
    paths = tr["paths"].get(params["program"], {})
    unnamed = sum(
        secs for op, secs in tr["programs"][params["program"]].items()
        if not paths.get(op)
        and any(op.startswith(p) for p in params.get("ops", ())))
    return 100.0 * (none - unnamed) / total
