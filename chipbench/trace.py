"""From a profiler trace to numbers: the one reduction every PR shares.

`load` turns an `.xplane.pb` into plain lists (`events`), `Trace` holds the
part the metrics read, and the functions below are the arithmetic: busy
union, idle share, op time, exposed collective time, the breakdown. All times
are seconds on the trace's own clock. `chipbench/tests/test_trace.py` checks
each against a recorded trace with hand-computed answers.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass
from typing import Callable, Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float, str]  # start_s, end_s, name

WINDOW = "chipbench/window"
_COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|collective-broadcast")
# ops that only hold other ops: their time is their children's
_CONTAINER = re.compile(r"_(while|conditional|call)$")
_OPCODE = re.compile(r"\b([a-z][a-z\-]*[a-z])\(")


def short_name(text: str) -> str:
    """The TPU trace names an op by its whole HLO line. Keep `<result>_<opcode>`
    (`_tile_accum_1dev.3_tpu_custom_call`, `copy.3_copy`, `all-reduce.2_all-reduce`);
    Mosaic's custom call, which is how a Pallas kernel appears, gets its target
    as opcode."""
    if " = " not in text:
        return text
    result, rest = text.split(" = ", 1)
    m = _OPCODE.search(rest)
    opcode = m.group(1) if m else "op"
    if opcode == "custom-call" and 'custom_call_target="tpu_custom_call"' in rest:
        opcode = "tpu_custom_call"
    return f"{result.lstrip('%')}_{opcode}"


def load(trace_dir: str) -> dict:
    """The newest `.xplane.pb` under `trace_dir` as {"planes": [{"name", "lines":
    [{"name", "events": [[name, start_ns, duration_ns], ...]}]}]}."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    planes = []
    for plane in ProfileData.from_file(files[-1]).planes:
        lines = []
        for line in plane.lines:
            on_host_device = plane.name.startswith("/host:")
            events = []
            for e in line.events:
                if on_host_device and e.name.startswith(("ThreadpoolListener", "end: ")):
                    continue
                ev = [short_name(e.name), float(e.start_ns), float(e.duration_ns)]
                if on_host_device and any(k == "hlo_op" for k, _ in e.stats):
                    ev.append("hlo_op")  # the CPU backend runs its ops on host threads
                events.append(ev)
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


@dataclass
class Trace:
    devices: List[List[Interval]]  # per chip: the ops line, sorted by start
    host: List[Interval]  # the driving thread's annotations, sorted by start
    w0: float
    w1: float

    @property
    def window_s(self) -> float:
        return self.w1 - self.w0


def reduce(events: dict) -> Trace:
    """Pick the device op lines and the driving host thread, cut to the window
    that the `chipbench/window` annotation marks."""
    devices, host_lines = [], []
    for plane in events["planes"]:
        if plane["name"].startswith("/device:") and "TPU" in plane["name"]:
            for line in plane["lines"]:
                if line["name"] == "XLA Ops":
                    devices.append([(s * 1e-9, (s + d) * 1e-9, n) for n, s, d, *_ in line["events"]])
        elif plane["name"].startswith("/host:"):
            host_lines += [line["events"] for line in plane["lines"]]
    driving = [ev for ev in host_lines if any(e[0] == WINDOW for e in ev)]
    if not driving:
        raise ValueError(f"no host thread carries the {WINDOW!r} annotation")
    host = sorted((e[1] * 1e-9, (e[1] + e[2]) * 1e-9, e[0]) for e in driving[0] if len(e) == 3)
    w0, w1 = next((a, b) for a, b, n in host if n == WINDOW)
    if not devices:  # CPU rehearsal: one pseudo device out of every thread's hlo ops
        ops = [(e[1] * 1e-9, (e[1] + e[2]) * 1e-9, e[0]) for ev in host_lines for e in ev if len(e) == 4]
        devices = [ops]
    devices = [sorted((max(a, w0), min(b, w1), n) for a, b, n in dev if b > w0 and a < w1) for dev in devices]
    host = [(max(a, w0), min(b, w1), n) for a, b, n in host if b > w0 and a < w1 and n != WINDOW]
    return Trace(devices, host, w0, w1)


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(merged: Sequence[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in merged)


def busy_s(trace: Trace) -> float:
    """Seconds in which an op ran on the device, averaged over the chips."""
    return sum(length(union((a, b) for a, b, _ in dev)) for dev in trace.devices) / len(trace.devices)


def busy_inside(trace: Trace, inside: Sequence[Tuple[float, float]]) -> float:
    """Seconds of the merged intervals `inside` in which an op ran, averaged over the chips."""
    wall = length(inside)
    return sum(wall - length(subtract(inside, union((a, b) for a, b, _ in dev))) for dev in trace.devices) / len(trace.devices)


def idle_share(trace: Trace) -> float:
    return 1.0 - busy_s(trace) / trace.window_s


def op_s(trace: Trace, match: Callable[[str], bool]) -> float:
    """Device time of the ops whose name matches, averaged over the chips."""
    return sum(b - a for dev in trace.devices for a, b, n in dev if match(n)) / len(trace.devices)


def is_collective(name: str) -> bool:
    return bool(_COLLECTIVE.search(name))


def is_kernel(name: str) -> bool:
    """A Pallas kernel: Mosaic's custom call, whatever jit named the program."""
    return name.endswith("_tpu_custom_call")


def subtract(a: Sequence[Tuple[float, float]], b: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """The part of merged intervals `a` that no interval of merged `b` covers."""
    out, j = [], 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k, at = j, lo
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > at:
                out.append((at, b[k][0]))
            at = max(at, b[k][1])
            k += 1
        if at < hi:
            out.append((at, hi))
    return out


def exposed_collective_s(trace: Trace) -> float:
    """Collective time during which no other op ran on that chip, per chip."""
    total = 0.0
    for dev in trace.devices:
        coll = union((a, b) for a, b, n in dev if is_collective(n))
        work = union((a, b) for a, b, n in dev if not is_collective(n) and not _CONTAINER.match(n))
        total += length(subtract(coll, work))
    return total / len(trace.devices)


def self_times(dev: Sequence[Interval]) -> Dict[str, float]:
    """Time per op name with nested ops' time taken out of their parents."""
    out: Dict[str, float] = {}
    stack: List[List] = []  # [end, name, self]

    def close(until: float) -> None:
        while stack and stack[-1][0] <= until:
            _, name, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(own, 0.0)

    for a, b, n in sorted(dev, key=lambda e: (e[0], -e[1])):
        close(a)
        if stack:
            stack[-1][2] -= b - a
        stack.append([b, n, b - a])
    close(float("inf"))
    return out


def innermost(host: Sequence[Interval]) -> List[Interval]:
    """Flatten nested host annotations into segments named by the innermost."""
    out: List[Interval] = []
    stack: List[Tuple[float, str]] = []  # (end, name)
    at = None

    def emit(until: float) -> None:
        nonlocal at
        if stack and at is not None and until > at:
            out.append((at, until, stack[-1][1]))
        at = until

    for a, b, n in sorted(host, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= a:
            emit(stack[-1][0])
            stack.pop()
        emit(a)
        stack.append((b, n))
        at = a
    while stack:
        emit(stack[-1][0])
        stack.pop()
    return out


def breakdown(trace: Trace, top: int = 10) -> dict:
    """The device ops that took most time (own time, per chip) and the idle
    gaps of chip 0 by what the driving host thread was in at the time."""
    ops: Dict[str, float] = {}
    for dev in trace.devices:
        for name, s in self_times(dev).items():
            ops[name] = ops.get(name, 0.0) + s / len(trace.devices)
    busy = union((a, b) for a, b, _ in trace.devices[0])
    gaps = subtract([(trace.w0, trace.w1)], busy)
    segments = innermost(trace.host)
    named = union((a, b) for a, b, _ in segments)
    idle: Dict[str, float] = {"outside_any_span": length(subtract(gaps, named))}
    j = 0  # both lists are sorted and disjoint: one sweep (a traced refit has 1e5 of each)
    for a, b, n in segments:
        while j < len(gaps) and gaps[j][1] <= a:
            j += 1
        k = j
        while k < len(gaps) and gaps[k][0] < b:
            idle[n] = idle.get(n, 0.0) + min(b, gaps[k][1]) - max(a, gaps[k][0])
            k += 1
    rank = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top] if v > 0]
    return {"device_ops": rank(ops), "idle_gaps": rank(idle)}
