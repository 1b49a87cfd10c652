"""The whole call against the chip's peak: the larger of FLOP / peak FLOP/s
and bytes / peak bytes/s that one call needs (the family's `topk_work` for the
traffic's queries), over all chips, over the mean wall of the program's
top-level `span` a window call. None where the window has no such span."""
from . import span_mean


def read(run, span: str):
    wall = span_mean.read(run, span, "window")
    if not wall or not hasattr(run.family, "topk_work"):
        return None
    work = run.family.topk_work(run.config, int(run.traffic["queries"]))
    least_s = max(work["flops"] / run.peaks["flops_per_s"], work["bytes"] / run.peaks["bytes_per_s"]) / run.chips
    return 100.0 * least_s / wall
