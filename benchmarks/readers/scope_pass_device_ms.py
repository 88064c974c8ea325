"""Device milliseconds a run of one program (``match``) spends in one
pass of the step (``pass``: ``forward``, ``recompute``, the forward
that block remat runs again inside the backward, ``backward`` without
it, or ``other``, what differentiation never touched), of the whole
program or of one scope group (``group``): the pass is read off each
operation's ``op_name`` path by the program's own rule
(``obs/programs.pass_of``: ``rematted_computation``, ``transpose(jvp(``,
``jvp(``), over the reduction that the ``scope_device_ms`` metrics of
the program share. 0.0 where the table is sound and holds nothing of
the pass (a step without remat recomputes nothing); nothing where the
program's reduction does not tell the passes apart."""

from benchmarks.programs import obs


def read(run, spec):
    by = obs.step_by_scope(run, spec)
    if by is None:
        return None
    which = spec["pass"]
    if "group" not in spec:
        seconds = by.get("by_pass", {}).get(which)
    else:
        g = by["groups"].get(spec["group"], {})
        if "recompute_s" not in g:  # no such group, or a reduction from before the passes
            return None
        seconds = {
            "forward": g["forward_s"],
            "recompute": g["recompute_s"],
            # a group's backward_s is everything under transpose(jvp(:
            # the recomputed forward is replayed in there
            "backward": g["backward_s"] - g["recompute_s"],
            "other": g["seconds"] - g["forward_s"] - g["backward_s"],
        }[which]
    return None if seconds is None else 1e3 * seconds / by["runs"]
