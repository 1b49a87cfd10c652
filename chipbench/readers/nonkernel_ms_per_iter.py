"""Device time that is neither a Pallas kernel (Mosaic custom call) nor a
collective, per chip per solver iteration: each op's own time, nested ops'
time taken out of their parents (per tile today: the slice, the relayout copy
and the row norms). The final pass's share is in the sum, as it is in
`kernel.distance_ms_per_iter`."""
from .. import trace as tracing


def read(run):
    iters = sum(run.family.iterations(o) for o in run.outputs)
    devices = run.trace_data.devices
    other = sum(s for dev in devices for n, s in tracing.self_times(dev).items()
                if not tracing.is_kernel(n) and not tracing.is_collective(n)) / len(devices)
    return 1e3 * other / iters if iters and other > 0 else None
