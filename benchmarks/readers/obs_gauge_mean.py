"""The mean of one of the program's ``obs`` gauges (``gauge``) inside the
window, times the run's ``scale_by`` field where the file names one (slot
occupancy is a share of the slots)."""


def read(run, spec):
    xs = [
        float(e["value"]) for e in run.get("events", [])
        if e.get("kind") == "gauge" and e.get("name") == spec["gauge"]
    ]
    if not xs:
        return None
    scale = float(run[spec["scale_by"]]) if spec.get("scale_by") else 1.0
    return scale * sum(xs) / len(xs)
