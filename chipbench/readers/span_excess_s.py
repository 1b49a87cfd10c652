"""Seconds the window lost to calls far beyond their usual time: the sum,
over the spans of one path whose wall is over `ratio` times the median wall,
of wall - median. 0 where none is. None as `span_max_over_median`."""
import statistics

from .span_max_over_median import walls


def read(run, span: str, ratio: float = 3.0):
    found = walls(run, span)
    if found is None:
        return None
    median = statistics.median(found)
    return sum(w - median for w in found if w > ratio * median)
