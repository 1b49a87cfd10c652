"""Least time for the reads the histogram accumulate needs (the family's
`hist_bytes` for the levels the window's fits grew, over the chips at the HBM
peak: memory-bound), over the device time of the accumulate's named ops. None
where the trace carries no such op, or the family counts no such bytes."""
from . import op_union_ms_per_fit


def read(run, prefixes):
    busy_ms = op_union_ms_per_fit.read(run, prefixes)
    if not busy_ms or not run.outputs or not hasattr(run.family, "hist_bytes"):
        return None
    levels = sum(run.family.iterations(o) for o in run.outputs) / len(run.outputs)
    least_s = run.family.hist_bytes(run.config, levels) / run.chips / run.peaks["bytes_per_s"]
    return 100.0 * least_s / (1e-3 * busy_ms)
