"""The solve span over the solver's iterations."""
from . import iterations, span_mean


def read(run):
    solve = span_mean.read(run, "fit/solve", "window")
    return None if solve is None else 1e3 * solve / iterations.read(run)
