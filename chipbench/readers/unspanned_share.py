"""The share of the window's wall time in which the caller was inside no
top-level program span: 1 - (wall of the spans whose path has no parent) /
window seconds. None where the registry dropped spans of this window (the
sum would be over a cut list)."""


def read(run):
    tele = run.window.telemetry
    if tele.get("spans_dropped", 0) or run.window.seconds <= 0:
        return None
    top = [s["wall_s"] for s in tele["spans"] if "/" not in s["path"]]
    return 100.0 * (1.0 - sum(top) / run.window.seconds) if top else None
