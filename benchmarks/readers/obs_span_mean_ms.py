"""The mean duration, in milliseconds, of one of the program's ``obs``
spans (``span``) over the events the bus's ring still holds from inside
the window (the ring is 512 events deep: the window's last hundred
steps or so)."""

from benchmarks.programs import obs


def read(run, spec):
    xs = [e["dur"] for e in obs.ring_events(spec["span"], "span", run["window"])]
    return 1e3 * sum(xs) / len(xs) if xs else None
