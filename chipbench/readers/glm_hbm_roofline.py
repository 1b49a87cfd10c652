"""One read of float32 X per iteration at the HBM peak (memory-bound), over
the device's busy time inside the window's `fit/solve` spans."""
from .. import trace as tracing


def read(run):
    solve = tracing.union((a, b) for a, b, n in run.trace_data.host if n == "fit/solve")
    busy = tracing.busy_inside(run.trace_data, solve)
    if busy <= 0:
        return None
    bytes_needed = sum(run.family.fit_work(run.config, run.family.iterations(o))["bytes"] for o in run.outputs)
    return 100.0 * bytes_needed / run.chips / run.peaks["bytes_per_s"] / busy
