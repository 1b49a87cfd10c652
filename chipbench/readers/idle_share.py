"""1 - the union of the device's op intervals over the traced window."""
from .. import trace as tracing


def read(run):
    return 100.0 * tracing.idle_share(run.trace_data)
