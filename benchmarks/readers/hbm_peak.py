"""Peak device memory on the fullest chip: the runtime's peak plus the
largest temporary of the compiled programs the window ran."""


def read(run, spec):  # noqa: ARG001
    return run["memory_peak_bytes"] / 2**30
