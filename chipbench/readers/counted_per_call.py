"""A program counter's rise inside the window over the window's calls, where
the program counts it at all: None where it rose neither in the set-up nor in
the window (a program without the counter has nothing to read, which is not 0)."""


def read(run, counter: str):
    window = run.window.telemetry["counters"]
    if counter not in window and counter not in run.setup.get("counters", {}):
        return None
    return window.get(counter, 0.0) / run.window.calls
