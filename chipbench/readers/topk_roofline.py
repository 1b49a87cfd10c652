"""Least time for what one top-k search call needs (the family's `topk_work`
for the traffic's queries: the larger of its FLOP at the bf16 peak and one
read of the items at the HBM peak, over the chips), over the device time a
call of the ops named by `prefixes`. None where the trace carries no such op,
or the family counts no such work."""
from . import op_union_ms_per_fit


def read(run, prefixes):
    busy_ms = op_union_ms_per_fit.read(run, prefixes)
    if not busy_ms or not hasattr(run.family, "topk_work"):
        return None
    work = run.family.topk_work(run.config, int(run.traffic["queries"]))
    least_s = max(work["flops"] / run.peaks["flops_per_s"], work["bytes"] / run.peaks["bytes_per_s"]) / run.chips
    return 100.0 * least_s / (1e-3 * busy_ms)
