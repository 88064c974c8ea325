"""Host time the feed spent making a batch, per step of the window."""


def read(run, spec):  # noqa: ARG001
    if not run.get("steps"):
        return None
    return 1e3 * run["input_host_s"] / run["steps"]
