"""Collective time with no other op running on that chip, over the window."""
from .. import trace as tracing


def read(run):
    if not any(tracing.is_collective(n) for dev in run.trace_data.devices for _, _, n in dev):
        return None
    return 100.0 * tracing.exposed_collective_s(run.trace_data) / run.trace_data.window_s
