"""Device milliseconds a run of one program (``match``) spends in one
scope group (``group``) of a groups table (``groups``, a
``module:ATTRIBUTE``) that need not be the program's first: what
``scope_device_ms`` reads, with one reduction kept a table (that reader
keeps one a program, so a second table over the same program would read
the first's groups)."""

from benchmarks.programs import obs


def by_scope(run, spec):
    """``obs.step_by_scope``'s reduction for ``spec``'s own table; None
    where the program has no such table, scopes or trace."""
    trace = run.get("trace")
    if trace is None or not trace.ops:
        return None
    kept = run.setdefault("_by_scope_of", {})
    key = (spec["match"], spec["groups"])
    if key not in kept:
        kept[key] = obs._step_by_scope(trace, spec["match"], spec["groups"])
    return kept[key]


def read(run, spec):
    by = by_scope(run, spec)
    if by is None or spec["group"] not in by["groups"] or not by["runs"]:
        return None
    return 1e3 * by["groups"][spec["group"]]["seconds"] / by["runs"]
