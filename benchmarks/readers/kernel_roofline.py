"""A model part's share of its roofline over a train step, in percent:
the least time the chip could take for the part's work (the larger of
operations over peak FLOP/s and bytes over peak bytes/s,
``peaks.roofline_seconds``), over the device time of the part's scope
group (``scope_group_device_ms``). The work is counted from shapes by
the reference family's ``cost`` function, whatever implements it:
``attn_core_cost(cfg, seq_len, rows)``, or ``expert_cost(cfg, pairs)``
with the pairs a layer that the program's counter (``pairs_counter``)
reports on the held experts."""

from benchmarks import peaks
from benchmarks.readers import obs_counter_mean, scope_group_device_ms


def read(run, spec):
    ms = scope_group_device_ms.read(run, spec)
    if not ms:
        return None
    cell = run["cell"]
    cost = getattr(cell.family("references"), spec["cost"], None)
    if cost is None:
        return None
    if spec.get("pairs_counter"):
        pairs = obs_counter_mean.mean(spec["pairs_counter"])
        if pairs is None:
            return None
        work = cost(cell.config, pairs)
    else:
        work = cost(
            cell.config, int(cell.traffic["seq_len"]),
            int(cell.traffic["batch_per_chip"]),
        )
    least = peaks.roofline_seconds(
        work["flops"], work["bytes"], run["device"]["kind"]
    )["seconds"]
    return 100.0 * least / (1e-3 * ms)
