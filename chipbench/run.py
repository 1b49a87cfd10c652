"""One run of one cell of the benchmark.

    python3 -m chipbench.run --workload <config>.<traffic> --seed <n> --seconds <s> --trace <0|1>

One process that holds the cell's chips. The cell is found by name in
`BENCHMARK.json`; its configuration, traffic mix, driver, estimator family and
per-layer metrics are files of their own under `chipbench/`, found by name
(see README.md), so this file holds no table of them. The last line of
standard output is the result the builder's contract fixes.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse
import contextlib
import gc
import importlib
import json
import math
import os
import sys
import tempfile
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


def load_json(*parts: str) -> dict:
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_bench() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json")


def cell_files(bench: dict, name: str):
    """A cell's entry, configuration and traffic mix, each from its own file;
    the runtime settings the configuration states go into the environment
    (before jax loads)."""
    cell = find_cell(bench, name)
    config = load_json("configs", cell["config"] + ".json")
    traffic = load_json("traffic", cell["traffic"] + ".json")
    os.environ.update(config.get("env", {}))
    return cell, config, traffic


def cell_metrics(bench: dict, cell_name: str) -> List[str]:
    """The per-layer metrics that BENCHMARK.json lists for this cell."""
    return [m["name"] for m in bench["per_layer"] if cell_name in m["workloads"]]


class Compiles:
    """Backend compiles and persistent-cache traffic, from jax.monitoring
    (the listener of `chip_smoke.py`, copied)."""

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.compiles = self.requests = self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name: str, **_: Any) -> None:
        if name.endswith("/compile_requests_use_cache"):
            self.requests += 1
        elif name.endswith("/cache_hits"):
            self.hits += 1
        elif name.endswith("/cache_misses"):
            self.misses += 1

    def _duration(self, name: str, secs: float, **_: Any) -> None:
        if name.endswith("/backend_compile_duration"):
            self.seconds += secs
            self.compiles += 1

    def mark(self) -> Dict[str, float]:
        return {"seconds": self.seconds, "compiles": self.compiles, "requests": self.requests,
                "hits": self.hits, "misses": self.misses}

    def since(self, mark: Dict[str, float]) -> Dict[str, float]:
        return {k: v - mark[k] for k, v in self.mark().items()}


@dataclass
class Window:
    """What the measured window left behind."""

    t0: float = 0.0
    t1: float = 0.0
    calls: int = 0
    failed: int = 0
    telemetry: dict = field(default_factory=dict)  # registry delta: counters, spans
    compiles: dict = field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


@dataclass
class Run:
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    rehearse: bool
    family: Any
    devices: List[Any]
    compiles: Compiles
    data: Any = None
    setup: dict = field(default_factory=dict)  # registry delta of the set-up's cold fit
    setup_totals: dict = field(default_factory=dict)  # compile counts from process start to the window
    setup_s: float = 0.0
    window: Window = field(default_factory=Window)
    outputs: List[dict] = field(default_factory=list)  # what each window call answered
    model_outputs: Optional[dict] = None  # the set-up's model, where the window's calls use one
    e2e: Dict[str, float] = field(default_factory=dict)
    trace_dir: Optional[str] = None
    trace_data: Any = None
    peak_bytes: int = 0
    peaks: Optional[dict] = None
    notes: List[str] = field(default_factory=list)  # where set-up's seconds went, for stderr

    def note(self, what: str) -> None:
        self.notes.append(f"{time.perf_counter() - _T_START:8.2f}s {what}")

    @property
    def chips(self) -> int:
        return int(self.cell["chips"])

    def registry(self):
        from spark_rapids_ml_tpu import telemetry

        return telemetry.registry()

    def annotate(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def before_window(self) -> None:
        """Set-up ends here: nothing the configuration switches off has run."""
        measured = self.registry().snapshot()["counters"].get("autotune.measurements", 0)
        if measured:
            raise RuntimeError(f"the autotuner measured {measured} tilings although the configuration switches it off")
        gc.collect()

    @contextlib.contextmanager
    def measure(self):
        """The measured window: marks, the trace where asked for, the clock."""
        import jax

        self.before_window()
        self.note("set-up done, window opens")
        reg, win = self.registry(), self.window
        if self.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        mark, cmark = reg.mark(), self.compiles.mark()
        self.setup_totals = cmark
        try:
            with self.annotate("chipbench/window"):
                win.t0 = time.perf_counter()
                self.setup_s = win.t0 - _T_START
                yield win
        finally:
            if self.trace:
                jax.profiler.stop_trace()
        win.telemetry, win.compiles = reg.delta(mark), self.compiles.since(cmark)


def peak_bytes(devices: List[Any]) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices)


def read_per_layer(run: Run, names: List[str]) -> Dict[str, dict]:
    """Each metric by the reader its own file names; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for name in names:
        m = load_json("metrics", name + ".json")
        reader = importlib.import_module(f"chipbench.readers.{m['reader']}")
        value = reader.read(run, **m.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def execute(cell: dict, config: dict, traffic: dict, seed: int, seconds: float, trace: bool,
            per_layer: List[str] = (), rehearse: bool = False, control: bool = False) -> dict:
    """Set-up, window, check, metrics: the result as a dict. `rehearse` is the
    tests' way past the look for a chip; `control` also puts the lower
    precision in the program's place and judges it by the same limits, under
    `result["control"]`: its `correct` has to come out false."""
    import jax

    from spark_rapids_ml_tpu import core, telemetry
    from spark_rapids_ml_tpu.parallel import default_devices, ensure_compilation_cache

    from . import checks, datagen, trace as tracing

    chips = int(cell["chips"])
    devices = list(default_devices())
    platform, kind = devices[0].platform, devices[0].device_kind
    table = load_json("peaks.json")
    peaks = table.get(kind)
    if rehearse:  # shares of a peak are then arithmetic to be tested, not measurements
        peaks = peaks or table["TPU v5 lite"]
    else:
        if platform != "tpu" or len(devices) < chips:
            raise SystemExit(f"chipbench: {cell['name']} needs {chips} TPU chip(s), found {len(devices)} x {platform}")
        if peaks is None:
            raise SystemExit(f"chipbench: device kind {kind!r} is not in chipbench/peaks.json")
    devices = devices[:chips]

    compiles = Compiles()
    ensure_compilation_cache()
    telemetry.enable()
    core.config.update(config["program_config"])
    family = importlib.import_module(f"chipbench.families.{config['family']}")
    driver = importlib.import_module(f"chipbench.drivers.{traffic['driver']}")
    run = Run(cell, config, traffic, int(seed), float(seconds), bool(trace), rehearse, family, devices, compiles,
              peaks=peaks)
    with tempfile.TemporaryDirectory(prefix="chipbench-trace-") as trace_dir:
        run.trace_dir = trace_dir
        run.note("jax and the program imported, devices found")
        run.data = datagen.make(config, run.seed)
        run.note(f"data made: {run.data.X.shape} float32 from seed {run.seed} {run.data.timing}")
        driver.run(run)  # set-up, then the window inside run.measure()
        run.note(f"window closed: {run.window.calls} calls in {run.window.seconds:.3f}s")
        for s in run.setup.get("spans", []):
            run.notes.append(f"    set-up span {s['path']}: {s['wall_s']:.3f}s")
        run.peak_bytes = peak_bytes(devices)
        if trace:
            events = tracing.load(trace_dir)
            run.trace_data = tracing.reduce(events)
    gc.collect()

    checked = driver.check(run, control=control)  # name -> (value, limit); the program's state is freed by now
    correct = checks.correct(checked["compared"])
    metrics = dict(run.e2e)
    metrics["setup_s"] = run.setup_s
    units = {**driver.UNITS, "setup_s": "s"}
    result = {
        "correct": bool(correct),
        "attempted": run.window.calls,
        "failed": run.window.failed,
        "metrics": {k: {"value": float(v), "unit": units[k]} for k, v in metrics.items()},
        "device": {"platform": platform, "kind": kind, "count": len(devices), "memory_peak_bytes": run.peak_bytes},
    }
    if trace:
        result["metrics"] = read_per_layer(run, list(per_layer))
        result["device"]["busy_s"] = tracing.busy_s(run.trace_data)
        result["device"]["window_s"] = run.trace_data.window_s
        result["breakdown"] = tracing.breakdown(run.trace_data)
    result["window"] = {"seconds": run.window.seconds, "calls": run.window.calls, "compiles": run.window.compiles}
    run.note("compared with the reference")
    result["notes"] = run.notes
    result["read"] = {k: number(v) for k, v in checked["read"].items()}
    if control:
        result["control"] = {"correct": checks.correct(checked["control"]), "compared": beside(checked["control"])}
        result["faults"] = {f: {k: number(v) for k, v in read.items()} for f, read in (checked["faults"] or {}).items()}
    result["compared"] = beside(checked["compared"])
    return result


def number(v: float) -> Optional[float]:
    """A reading that is not a number goes as null, so that the line stays JSON."""
    return float(v) if math.isfinite(v) else None


def beside(compared: Dict[str, tuple]) -> Dict[str, dict]:
    return {k: {"value": number(v), "limit": lim} for k, (v, lim) in compared.items()}


def main(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_bench()
    cell, config, traffic = cell_files(bench, args.workload)
    result = execute(cell, config, traffic, args.seed, args.seconds, bool(args.trace),
                     per_layer=cell_metrics(bench, cell["name"]))
    for line in result.pop("notes"):
        print(line, file=sys.stderr)
    for name, c in result["compared"].items():
        value = "not a number" if c["value"] is None else f"{c['value']:.6g}"
        print(f"compared {name}: {value} (limit {c['limit']:.6g})", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
