"""One program counter's rise inside the window over another's. None where
the second did not rise: a program without the counters has nothing to read."""


def read(run, counter: str, over: str):
    counters = run.window.telemetry["counters"]
    return counters.get(counter, 0.0) / counters[over] if counters.get(over) else None
