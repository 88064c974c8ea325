"""The device's idle share of the traced window, in percent."""

from benchmarks import tracing


def read(run, spec):  # noqa: ARG001
    if run.get("trace") is None:
        return None
    return 100.0 * tracing.idle_share(run["trace"])
