"""Device busy time inside one program span's intervals over their wall time."""
from .. import trace as tracing


def read(run, span: str):
    inside = tracing.union((a, b) for a, b, n in run.trace_data.host if n == span)
    wall = tracing.length(inside)
    return 100.0 * tracing.busy_inside(run.trace_data, inside) / wall if wall > 0 else None
