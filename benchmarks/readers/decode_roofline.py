"""The decode program's share of its roofline: the least time one chip
could take for a decode step over the traced ticks' live rows and cached
tokens (weights once, live cache once; the larger of FLOPs over peak and
bytes over peak bandwidth), over the decode program's mean device time."""

from benchmarks import peaks, tracing


def read(run, spec):
    if run.get("trace") is None or not run.get("trace_ticks"):
        return None
    took = tracing.program_seconds(run["trace"], spec["match"])
    if not took:
        return None
    cell = run["cell"]
    ref = cell.family("references")
    kind = run["device"]["kind"]
    least = [
        peaks.roofline_seconds(c["flops"], c["bytes"], kind)["seconds"]
        for c in (
            ref.decode_step_cost(cell.config, k["rows"], k["live_tokens"])
            for k in run["trace_ticks"]
        )
    ]
    return 100.0 * (sum(least) / len(least)) / (sum(took) / len(took))
