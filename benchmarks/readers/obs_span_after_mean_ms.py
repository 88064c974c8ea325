"""The mean duration, in milliseconds, of the ``first`` spans ``span``
that follow each span ``after`` (both the program's ``obs`` spans),
over the events the bus's ring still holds from inside the window: one
kind of work timed where another has just left the system in a known
state."""

from benchmarks.programs import obs


def read(run, spec):
    xs = [
        e["dur"] for e in obs.ring_spans_after(
            spec["span"], spec["after"], int(spec["first"]), run["window"]
        )
    ]
    return 1e3 * sum(xs) / len(xs) if xs else None
