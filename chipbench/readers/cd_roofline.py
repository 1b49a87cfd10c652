"""Least time for the reads the coordinate descent needs (the family's
`cd_bytes`: the float32 standardized gram once a sweep, over the chips at the
HBM peak: memory-bound), over the device's busy time inside the window's
`fit/solve/cd` spans. None where the trace carries no such span, or the family
counts no such bytes: a program without the descent has nothing to read."""
from . import span_device_ms_per_call


def read(run):
    busy_ms = span_device_ms_per_call.read(run, "fit/solve/cd")
    if not busy_ms or not run.outputs or not hasattr(run.family, "cd_bytes"):
        return None
    sweeps = sum(run.family.iterations(o) for o in run.outputs) / len(run.outputs)
    least_s = run.family.cd_bytes(run.config, sweeps) / run.chips / run.peaks["bytes_per_s"]
    return 100.0 * least_s / (1e-3 * busy_ms)
