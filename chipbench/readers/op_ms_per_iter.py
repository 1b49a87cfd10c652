"""Device time of the ops whose name starts with one of `prefixes` (the
program's stable kernel names, `srml_<kernel>_<mode>`), per chip: per solver
iteration of the window's fits (`per` = "iteration"), or per fit ("fit").
None where no op carries such a name: a program without the names has
nothing to read, which is not 0 ms."""
from .. import trace as tracing


def read(run, prefixes, per: str = "iteration"):
    prefixes = tuple(prefixes)
    named = lambda n: n.startswith(prefixes)
    if not any(named(n) for dev in run.trace_data.devices for _, _, n in dev):
        return None
    units = sum(run.family.iterations(o) for o in run.outputs) if per == "iteration" else len(run.outputs)
    return 1e3 * tracing.op_s(run.trace_data, named) / units if units else None
