"""Seconds under one of the program's ``obs`` spans (``span``) over the
whole process, set-up included: the bus's cumulative totals, which do
not age as its ring does."""

from benchmarks.programs import obs


def read(run, spec):  # noqa: ARG001
    tot = obs.total(spec["span"])
    return tot["sum"] if tot else None
