"""The whole solve against the chip's peak: the larger of FLOP / peak FLOP/s
and bytes / peak bytes/s that the configuration's fit needs, over all chips,
over the solve span."""
from . import span_mean


def read(run):
    solve = span_mean.read(run, "fit/solve", "window")
    if solve is None:
        return None
    least = 0.0
    for o in run.outputs:
        work = run.family.fit_work(run.config, run.family.iterations(o))
        least += max(work["flops"] / run.peaks["flops_per_s"], work["bytes"] / run.peaks["bytes_per_s"]) / run.chips
    return 100.0 * least / len(run.outputs) / solve
