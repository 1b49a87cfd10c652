"""Solver iterations per window fit, as the fitted models report them."""


def read(run):
    return sum(run.family.iterations(o) for o in run.outputs) / len(run.outputs)
