"""Device busy time inside one program span's intervals, per chip, per window
call, in ms. None where the trace carries no such span: a program without the
span has nothing to read, which is not 0 ms."""
from .. import trace as tracing


def read(run, span: str):
    inside = tracing.union((a, b) for a, b, n in run.trace_data.host if n == span)
    if not inside or not run.window.calls:
        return None
    return 1e3 * tracing.busy_inside(run.trace_data, inside) / run.window.calls
