"""Device milliseconds a run of one program (``match``) spends in one
scope group (``group``: a part of the model, by the ``jax.named_scope``
path of each operation), forward and backward together: the program's
scope table joined with the traced ``XLA Ops`` events. A fusion counts
whole for the group of its root."""

from benchmarks.programs import obs


def read(run, spec):
    by = obs.step_by_scope(run, spec)
    if by is None or spec["group"] not in by["groups"]:
        return None
    return 1e3 * by["groups"][spec["group"]]["seconds"] / by["runs"]
