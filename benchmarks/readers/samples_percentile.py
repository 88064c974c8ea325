"""A percentile of host-clock samples the runner took (``samples``: which
list; ``q``: which percentile). Nothing where the list is empty."""

from benchmarks import stats


def read(run, spec):
    xs = run.get("samples", {}).get(spec["samples"]) or []
    return stats.percentile(xs, float(spec["q"])) if xs else None
