"""The mean increment, in MiB, of one of the program's ``obs`` byte
counters (``counter``; one increment a staged batch, so a step) over
the events the bus's ring still holds from inside the window."""

from benchmarks.programs import obs


def read(run, spec):
    xs = [e["value"] for e in obs.ring_events(spec["counter"], "counter", run["window"])]
    return sum(xs) / len(xs) / 2**20 if xs else None
