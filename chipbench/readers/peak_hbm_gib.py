"""`peak_bytes_in_use` of the fullest device, read after the window."""


def read(run):
    return run.peak_bytes / 2**30 if run.peak_bytes else None
