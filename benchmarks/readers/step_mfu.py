"""The whole step's share of the chips' peak: FLOPs that the traced
window's work needs (from the configuration's shapes, by the family's
functions; recomputation and padding not counted) over the traced
seconds times chips times the peak."""

from benchmarks import peaks


def read(run, spec):  # noqa: ARG001
    if not run.get("useful_flops_in_trace") or not run.get("trace_seconds"):
        return None
    cell = run["cell"]
    peak = float(peaks.peaks_for(run["device"]["kind"])["flops_per_s"])
    return 100.0 * run["useful_flops_in_trace"] / (
        run["trace_seconds"] * cell.chips * peak
    )
