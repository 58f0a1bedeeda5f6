"""`scope_share` for a scope whose heaviest operations the compiler
leaves without an ``op_name``: XLA:TPU rewrites ``lax.ragged_dot`` into
custom calls (``ragged-dot-none.N``) that carry no metadata, so no
named scope reaches them.  They are found by their kind instead.

params: as `scope_share` (``program``, ``scopes``, ``scope``), and
``ops``: prefixes of operation names that belong to ``scope`` when they
have no ``op_name`` of their own (one that has is counted by its scopes
alone, never twice).  None where `scope_share` gives None."""

from benchmark import spans


def seconds(tr, params):
    """(seconds of ``scope`` with its unnamed operations, total) of the
    program's device time, or None."""
    found = tr and spans.scope_seconds(tr, params["program"],
                                       params["scopes"])
    if not found:
        return None
    by_scope, none, total = found
    if total <= 0 or none >= total:
        return None
    paths = tr["paths"].get(params["program"], {})
    unnamed = sum(
        secs for op, secs in tr["programs"][params["program"]].items()
        if not paths.get(op)
        and any(op.startswith(p) for p in params.get("ops", ())))
    return by_scope[params["scope"]] + unnamed, total


def read(run, params):
    found = seconds(spans.of_run(run), params)
    return None if found is None else 100.0 * found[0] / found[1]
