"""A seconds attribute summed over one program span and every span under it,
over the summed wall of that span, in %: `wait_s` under `fit` is the share of
the fits' wall that the host spent blocked on the runtime. None where the
registry dropped spans of this window, or where no span carries the attribute
(a program that does not record it)."""


def read(run, span: str, attr: str):
    tele = run.window.telemetry
    if tele.get("spans_dropped", 0):
        return None
    under = [s for s in tele["spans"] if s["path"] == span or s["path"].startswith(span + "/")]
    wall = sum(s["wall_s"] for s in under if s["path"] == span)
    if wall <= 0 or not any(attr in s for s in under):
        return None
    return 100.0 * sum(s.get(attr, 0.0) for s in under) / wall
