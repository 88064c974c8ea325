"""The mean value of one of the program's ``obs`` counters (``counter``)
over the whole process: the bus's cumulative totals, sum over count.
For a counter that reports a reading each time it is emitted (the train
step's own metrics at a log sync), not an increment."""

from benchmarks.programs import obs


def mean(name):
    tot = obs.total(name)
    return tot["sum"] / tot["count"] if tot and tot["count"] else None


def read(run, spec):  # noqa: ARG001
    return mean(spec["counter"])
