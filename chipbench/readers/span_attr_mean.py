"""An attribute of the program spans of the given paths, summed over the
window and taken per window call: what a call's top-level spans say the
process spent (`cpu_s`, `minor_faults`, `invol_switches`). None where the
registry dropped spans of this window, or where no such span carries the
attribute (a program that does not record it)."""
from typing import List


def read(run, spans: List[str], attr: str):
    tele = run.window.telemetry
    if tele.get("spans_dropped", 0) or not run.window.calls:
        return None
    found = [s[attr] for s in tele["spans"] if s["path"] in spans and attr in s]
    return sum(found) / run.window.calls if found else None
