"""Device time of the Pallas kernels (Mosaic custom calls) in the window, per
chip, per Lloyd iteration. The final inertia pass's kernels are in the sum."""
from .. import trace as tracing


def read(run):
    iters = sum(run.family.iterations(o) for o in run.outputs)
    kernel = tracing.op_s(run.trace_data, tracing.is_kernel)
    return 1e3 * kernel / iters if kernel > 0 else None
