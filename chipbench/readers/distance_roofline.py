"""Least time for the assignment the algorithm needs (2*n*k*d FLOP per pass
per chip's rows at the bf16 peak: compute-bound), over the Pallas kernels'
device time. Passes: the Lloyd iterations and the final inertia pass."""
from .. import trace as tracing


def read(run):
    kernel = tracing.op_s(run.trace_data, tracing.is_kernel)  # per chip, whole window
    if kernel <= 0:
        return None
    passes = sum(run.family.iterations(o) + 1 for o in run.outputs)
    least = run.family.assign_flops(run.config) / run.chips * passes / run.peaks["flops_per_s"]
    return 100.0 * least / kernel
