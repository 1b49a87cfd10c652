"""Least time for the gram the algorithm needs (the family's `gram_flops`, 2 n d^2
a fit, over the chips at the bf16 peak: compute-bound), over the device's busy
time inside the window's `fit/solve/gram` spans."""
from . import span_device_ms_per_call


def read(run):
    busy_ms = span_device_ms_per_call.read(run, "fit/solve/gram")
    if not busy_ms:
        return None
    least_s = run.family.gram_flops(run.config) / run.chips / run.peaks["flops_per_s"]
    return 100.0 * least_s / (1e-3 * busy_ms)
