"""The share, in percent, of one program's (``match``) device time
whose operations fall in no scope group: what the by-part metrics of
``scope_device_ms`` leave out."""

from benchmarks.programs import obs


def read(run, spec):
    by = obs.step_by_scope(run, spec)
    if by is None or by["total_s"] <= 0.0:
        return None
    return 100.0 * by["unscoped_s"] / by["total_s"]
