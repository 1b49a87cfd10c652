"""Mean wall time of one program span: per window call, or the set-up's."""


def read(run, span: str, scope: str):
    if scope == "setup":
        spans = [s["wall_s"] for s in run.setup["spans"] if s["path"] == span]
        return sum(spans) if spans else None
    spans = [s["wall_s"] for s in run.window.telemetry["spans"] if s["path"] == span]
    return sum(spans) / run.window.calls if spans else None
