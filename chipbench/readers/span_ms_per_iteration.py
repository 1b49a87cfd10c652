"""Mean wall time of one program span a window call over the solver's
iterations of that call (the family's `iterations`), in ms. None where the
window has no such span."""
from . import iterations, span_mean


def read(run, span: str):
    wall = span_mean.read(run, span, "window")
    units = iterations.read(run) if run.outputs else 0
    return None if wall is None or not units else 1e3 * wall / units
