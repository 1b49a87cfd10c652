"""The largest wall of one program span in the window over the median wall:
1.0-1.2 where every call took its usual time, about 9 where one fit of 0.34 s
took 3 s (a window of one call reads 1.0). None where the registry dropped
spans of this window, or where the window holds no such span."""
import statistics


def walls(run, span: str):
    tele = run.window.telemetry
    if tele.get("spans_dropped", 0):
        return None
    found = [s["wall_s"] for s in tele["spans"] if s["path"] == span]
    return found or None


def read(run, span: str):
    found = walls(run, span)
    return None if found is None else max(found) / statistics.median(found)
