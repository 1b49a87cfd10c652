"""A program counter's rise inside the window over the window's calls."""


def read(run, counter: str):
    return run.window.telemetry["counters"].get(counter, 0.0) / run.window.calls
