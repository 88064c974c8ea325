"""Mean device time of the programs whose name starts with ``match``
(the trace's ``XLA Modules`` line), in milliseconds."""

from benchmarks import tracing


def read(run, spec):
    if run.get("trace") is None:
        return None
    xs = tracing.program_seconds(run["trace"], spec["match"])
    return 1e3 * sum(xs) / len(xs) if xs else None
