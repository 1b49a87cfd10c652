"""Persistent-cache hits over the compile requests made during set-up."""


def read(run):
    at_window = run.setup_totals
    return 100.0 * at_window["hits"] / at_window["requests"] if at_window["requests"] else None
