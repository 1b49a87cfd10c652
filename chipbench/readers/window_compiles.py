"""Backend compiles inside the window (none expected)."""


def read(run):
    return run.window.compiles["compiles"]
