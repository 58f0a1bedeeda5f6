"""The share of a compiled program's device time that runs under one of
its named scopes (`spans.py`: operations are found by the program's name
on the ``XLA Modules`` line and by the ``jax.named_scope`` names in
their HLO ``op_name``, never by an operation's number).

params: ``program`` (``jit_serve_decode``), ``scopes`` (every scope the
program names) and ``scope``: one of them, or ``"unscoped"`` for the
time under none (what the compiler made: copies, loop overhead).  None
where there is no trace, no such program in it, or no operation of it
under any scope (a program without the names)."""

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
    part = none if params["scope"] == "unscoped" \
        else by_scope[params["scope"]]
    return 100.0 * part / total
