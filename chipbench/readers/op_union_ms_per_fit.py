"""Device time of the ops whose name starts with one of `prefixes`, per chip,
per window fit, in ms: the union of their intervals, so that a named op inside
a named loop is counted once. None where no op carries such a name: a program
without them has nothing to read, which is not 0 ms."""
from .. import trace as tracing


def read(run, prefixes):
    prefixes = tuple(prefixes)
    merged = [tracing.union((a, b) for a, b, n in dev if n.startswith(prefixes)) for dev in run.trace_data.devices]
    if not any(merged) or not run.window.calls:
        return None
    return 1e3 * sum(tracing.length(m) for m in merged) / len(merged) / run.window.calls
