"""A percentile of the durations of one of the program's ``obs`` spans
(``span``) inside the window, in milliseconds."""

from benchmarks import stats


def read(run, spec):
    xs = [
        1e3 * e["dur"] for e in run.get("events", [])
        if e.get("name") == spec["span"] and e.get("dur") is not None
    ]
    return stats.percentile(xs, float(spec["q"])) if xs else None
