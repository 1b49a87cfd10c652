#
# Structured telemetry: spans, counters/gauges/histograms, and sinks.
#
# The observability substrate for the whole hot path (ingest -> layout ->
# solve -> transform). The reference's story here is NVTX ranges in the Scala
# plugin plus ad-hoc wall-clock logs in the Python tier (SURVEY.md §5); the
# TPU-native answer is:
#
#   * `span("stage", **attrs)` — a nestable context manager that records wall
#     time into the registry, emits a `jax.profiler.TraceAnnotation` so the
#     stage lines up inside xprof traces (the NVTX-range analog), and logs the
#     stage timing at a caller-provided logger (the old `verbose` prints).
#   * `MetricsRegistry` — a process-global store of counters (bytes ingested,
#     device_put calls, rendezvous rounds), gauges (HBM watermark, solver
#     objective), histograms (rendezvous latency), span aggregates, and
#     per-iteration solver convergence traces.
#   * sinks — a JSONL file (`SRML_METRICS_PATH`) receiving one record per
#     span plus one snapshot record per fit, and an in-process `snapshot()`
#     dict that bench.py embeds into BENCH_* emission and `fit` attaches to
#     models as `model._fit_metrics`.
#
# Contracts:
#   * ZERO-COST WHEN DISABLED: `span()` returns a shared no-op object and
#     every record method is behind one flag check — a disabled fit does no
#     timing, no allocation, no I/O.
#   * SPMD-SAFE: records are rank-tagged, the JSONL sink writes to a per-rank
#     file (rank 0 owns the bare path), and nothing here performs a
#     collective of its own.
#   * Per-iteration convergence traces from jitted solvers use
#     `jax.debug.callback` and are gated SEPARATELY (`SRML_TRACE_CONVERGENCE`
#     / `enable(convergence=True)`): a host callback per L-BFGS iteration
#     stalls the device program on the host every iteration, so it never
#     rides along with plain counter telemetry. The gate is read at
#     TRACE time — toggling it after a solver shape has compiled does not
#     retrace that shape.
#
from __future__ import annotations

import atexit
import collections
import contextlib
import gc
import json
import os
import re
import resource
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from .utils import lockcheck

__all__ = [
    "enabled",
    "enable",
    "disable",
    "convergence_trace_enabled",
    "span",
    "registry",
    "MetricsRegistry",
    "snapshot",
    "summary",
    "fit_scope",
    "record_device_memory",
    "record_solver_result",
    "record_convergence_point",
    "quantile_of",
    "summarize_histogram",
    "tenant_metric",
    "merge_counters",
    "merge_gauges",
    "merge_histograms",
    "merge_windows",
    "MergedWindows",
]

# Span records kept in-process (the JSONL sink receives every record; the
# in-memory list is for snapshot()/summary() and stays bounded). A window of
# `MetricsRegistry.delta` has to fit: a several-piece `model.transform` call
# records about fifty (six a piece), and a caller makes seven such calls a
# second (a 20 s window of them: 6.6 k spans).
_MAX_SPAN_RECORDS = 32768
# The slow-call rule (docs/observability.md "Slow calls"): per top-level path a
# ring of the last `_SLOW_RING` walls; once a path has been called
# `_SLOW_AFTER` times, a call whose wall is over `_SLOW_RATIO` times the ring's
# median AND over the median plus `_SLOW_EXCESS_S` is a slow call. The last
# `_MAX_SLOW_CALLS` records are kept, each with at most `_MAX_SLOW_CALL_SPANS`
# of the spans under it (the longest), and a table of the process's threads is
# taken at most once in `_THREAD_TABLE_EVERY_S` (and for no more than
# `_THREAD_TABLE_SHARE` of the process's time: the next one waits five
# thousand times what the last one took) so that a slow call's own table has
# a neighbour to be subtracted from.
_SLOW_RING = 16
_SLOW_AFTER = 8
_SLOW_RATIO = 3.0
_SLOW_EXCESS_S = 0.25
_MAX_SLOW_CALLS = 8
_MAX_SLOW_CALL_SPANS = 32
_THREAD_TABLE_EVERY_S = 5.0
_THREAD_TABLE_SHARE = 2e-4  # of the process's time at most: a table of 170 threads takes 10 ms on a v5e's host
_MAX_CONVERGENCE_POINTS = 10_000
# Most-recent observations retained per histogram for quantile() estimation
# (serving latency p50/p99); the count/sum/min/max summary sees EVERY
# observation — only the quantile view is windowed.
_MAX_HIST_SAMPLES = 1024
# Per-bucket sample retention for the TIME-windowed quantile view (the ops
# plane's rolling windows): bounded so a traffic burst cannot grow the ring —
# a bucket past the cap keeps its count/sum exact and its quantiles
# approximate (computed over the retained samples).
_MAX_BUCKET_SAMPLES = 256


class _State:
    __slots__ = ("on", "sink_path", "convergence")

    def __init__(self) -> None:
        self.sink_path: Optional[str] = os.environ.get("SRML_METRICS_PATH") or None
        self.on: bool = bool(self.sink_path) or bool(os.environ.get("SRML_TELEMETRY"))
        self.convergence: bool = bool(os.environ.get("SRML_TRACE_CONVERGENCE"))


_STATE = _State()
_LOCAL = threading.local()  # per-thread stack of the open `_Span`s (nesting -> paths, waits -> the innermost)

# Cached handle to the diagnostics module (trace tags + flight recorder).
# Lazy: diagnostics never imports telemetry at module level and vice versa,
# so whichever loads first wins without a cycle.
_DIAG: Any = None


def _diag():
    global _DIAG
    if _DIAG is None:
        from . import diagnostics

        _DIAG = diagnostics
    return _DIAG


def enabled() -> bool:
    """Whether telemetry recording is on (one branch — THE hot-path check)."""
    return _STATE.on


def convergence_trace_enabled() -> bool:
    """Whether jitted solvers should bake per-iteration host callbacks in.
    Read at trace time; see the module header for the compile-cache caveat."""
    return _STATE.on and _STATE.convergence


def enable(sink_path: Optional[str] = None, *, convergence: Optional[bool] = None) -> None:
    """Turn telemetry on, optionally pointing the JSONL sink at `sink_path`
    and/or toggling per-iteration convergence tracing. Re-pointing the sink
    closes the previous file handles (no fd accumulation across jobs)."""
    _STATE.on = True
    if sink_path is not None:
        if sink_path != _STATE.sink_path:
            _close_sinks()
        _STATE.sink_path = sink_path
    if convergence is not None:
        _STATE.convergence = bool(convergence)
    # opt-in live scrape surface (docs/observability.md "Ops plane"): when
    # SRML_METRICS_PORT names a port, enabling telemetry also stands up the
    # exporter thread. Best-effort — a busy port degrades to no server, never
    # to a failed fit.
    if os.environ.get("SRML_METRICS_PORT"):
        try:
            from . import ops_plane

            ops_plane.ensure_server()
        except Exception:  # pragma: no cover - exporter must never break enable
            pass


def disable() -> None:
    """Turn telemetry off (records already taken stay in the registry) and
    close any open sink files."""
    _STATE.on = False
    _close_sinks()


def _rank() -> int:
    """This process's rank for record tagging and per-rank sink naming.
    Delegates to diagnostics (active TpuContext > set_process_rank >
    SRML_RANK env > 0) so telemetry records and flight-recorder dumps agree
    on rank identity. Control-plane only — never touches the XLA backend
    (jax.process_index() would initialize it)."""
    return _diag()._rank()


# --------------------------------------------------------- rolling windows --
#
# Time-bucketed ring aggregation (docs/observability.md "Ops plane"): every
# counter gets `rate()` and every histogram gets `window_quantile()` over a
# configurable recent horizon (bucket width x bucket count,
# `config["metrics_bucket_seconds"]` x `config["metrics_bucket_count"]`,
# default 10s x 18 = 3 minutes) ALONGSIDE the cumulative views — a long-lived
# serving process answers "what is the error rate NOW", not since boot.
# Window updates ride the same single `_STATE.on` check as every recorder
# (zero-cost when telemetry is disabled, the PR-2 contract); window params are
# resolved lazily at first record after construction/reset, so tests that
# shrink the bucket width set config and call `registry().reset()`.


def _window_params() -> Tuple[float, int]:
    """(bucket_seconds, bucket_count) from core.config, via sys.modules like
    diagnostics.flightrec_dir — telemetry must never pay core's import chain
    (and an uncustomized process cannot have customized the knobs)."""
    bucket_s, n = 10.0, 18
    core = sys.modules.get(__package__ + ".core")
    if core is not None:
        try:
            bucket_s = float(core.config.get("metrics_bucket_seconds") or 10.0)
            n = int(core.config.get("metrics_bucket_count") or 18)
        except Exception:  # pragma: no cover - malformed knob keeps defaults
            pass
    return max(0.001, bucket_s), max(2, n)


class _CounterRing:
    """Per-counter ring of per-bucket increment sums."""

    __slots__ = ("bucket_s", "n", "vals", "head")

    def __init__(self, bucket_s: float, n: int) -> None:
        self.bucket_s = bucket_s
        self.n = n
        self.vals = [0.0] * n
        self.head: Optional[int] = None  # absolute index of the newest bucket

    def _advance(self, b: int) -> None:
        if self.head is None or b - self.head >= self.n:
            self.vals = [0.0] * self.n
            self.head = b
            return
        while self.head < b:
            self.head += 1
            self.vals[self.head % self.n] = 0.0

    def add(self, now: float, v: float) -> None:
        b = int(now // self.bucket_s)
        if self.head is None or b > self.head:
            self._advance(b)
        # a clock reading from just before the head bucket opened lands in
        # the head bucket rather than rewriting history
        self.vals[(self.head if b < (self.head or 0) else b) % self.n] += v

    def window_sum(self, now: float, window_s: Optional[float]) -> Tuple[float, float]:
        """(sum over the window, window span seconds). The span is clamped to
        the ring horizon — asking for 1h over a 3min ring reads 3min."""
        b = int(now // self.bucket_s)
        if self.head is None or b > self.head:
            self._advance(b)
        horizon = self.n * self.bucket_s
        span = horizon if window_s is None else min(max(float(window_s), self.bucket_s), horizon)
        k = max(1, min(self.n, int(round(span / self.bucket_s))))
        assert self.head is not None
        return sum(self.vals[(self.head - i) % self.n] for i in range(k)), k * self.bucket_s


class _HistRing:
    """Per-histogram ring of per-bucket (count, sum, bounded samples)."""

    __slots__ = ("bucket_s", "n", "counts", "sums", "samples", "head")

    def __init__(self, bucket_s: float, n: int) -> None:
        self.bucket_s = bucket_s
        self.n = n
        self.counts = [0.0] * n
        self.sums = [0.0] * n
        self.samples: List[List[float]] = [[] for _ in range(n)]
        self.head: Optional[int] = None

    def _advance(self, b: int) -> None:
        if self.head is None or b - self.head >= self.n:
            self.counts = [0.0] * self.n
            self.sums = [0.0] * self.n
            self.samples = [[] for _ in range(self.n)]
            self.head = b
            return
        while self.head < b:
            self.head += 1
            i = self.head % self.n
            self.counts[i] = 0.0
            self.sums[i] = 0.0
            self.samples[i] = []

    def add(self, now: float, v: float) -> None:
        b = int(now // self.bucket_s)
        if self.head is None or b > self.head:
            self._advance(b)
        i = (self.head if b < (self.head or 0) else b) % self.n
        self.counts[i] += 1.0
        self.sums[i] += v
        if len(self.samples[i]) < _MAX_BUCKET_SAMPLES:
            self.samples[i].append(v)

    def _slots(self, now: float, window_s: Optional[float]) -> List[int]:
        b = int(now // self.bucket_s)
        if self.head is None or b > self.head:
            self._advance(b)
        horizon = self.n * self.bucket_s
        span = horizon if window_s is None else min(max(float(window_s), self.bucket_s), horizon)
        k = max(1, min(self.n, int(round(span / self.bucket_s))))
        assert self.head is not None
        return [(self.head - i) % self.n for i in range(k)]

    def window_samples(self, now: float, window_s: Optional[float]) -> List[float]:
        out: List[float] = []
        for i in self._slots(now, window_s):
            out.extend(self.samples[i])
        return out

    def window_count(self, now: float, window_s: Optional[float]) -> float:
        return sum(self.counts[i] for i in self._slots(now, window_s))


def quantile_of(values: List[float], q: float) -> Optional[float]:
    """Nearest-rank quantile over a (possibly unsorted) sample list — THE one
    quantile-extraction implementation (ScoringEngine.stats,
    FitScheduler.stats, the registry's quantile views, and the bench lanes
    all delegate here, so they cannot drift). None on an empty list."""
    if not values:
        return None
    ordered = sorted(values)
    q = min(max(float(q), 0.0), 1.0)
    idx = min(len(ordered) - 1, max(0, int(round(q * (len(ordered) - 1)))))
    return float(ordered[idx])


# --------------------------------------------------------- merge semantics --
#
# THE cross-rank merge definitions (docs/observability.md "Fleet plane") —
# both fleet transports (the live ops round and the offline snapshot merge)
# delegate here so they cannot drift: counters SUM; gauges keep every
# per-rank value plus min/max/sum (averaging a watermark would lie); window
# histograms merge per-bucket with exact counts/sums preserved and sample
# multisets concatenated (bounded at `_MAX_BUCKET_SAMPLES` per bucket PER
# RANK — quantiles over the merged window are approximate past the cap,
# exactly as approximate as each rank's own view). Merging is associative
# and rank-order-independent, and merging a single rank is the identity
# (pinned in tests/test_fleet.py).


def merge_counters(per_rank: List[Dict[str, float]]) -> Dict[str, float]:
    """Sum counter dicts across ranks (missing names = 0 contribution)."""
    out: Dict[str, float] = {}
    for counters in per_rank:
        for name, v in (counters or {}).items():
            out[name] = out.get(name, 0.0) + float(v)
    return out


def merge_gauges(per_rank: Dict[Any, Dict[str, float]]) -> Dict[str, Dict[str, Any]]:
    """Merge gauge dicts keyed by rank: each name keeps the full per-rank
    map plus min/max/sum rollups. Rank keys may be ints or their JSON string
    round-trips; the merged `by_rank` map is keyed by int rank."""
    out: Dict[str, Dict[str, Any]] = {}
    for rank in sorted(per_rank, key=lambda r: int(r)):
        for name, v in (per_rank[rank] or {}).items():
            e = out.setdefault(
                name,
                {"by_rank": {}, "min": float("inf"), "max": float("-inf"), "sum": 0.0},
            )
            v = float(v)
            e["by_rank"][int(rank)] = v
            e["min"] = min(e["min"], v)
            e["max"] = max(e["max"], v)
            e["sum"] += v
    return out


def merge_histograms(
    per_rank: List[Dict[str, Dict[str, float]]]
) -> Dict[str, Dict[str, float]]:
    """Merge cumulative histogram summaries: counts/sums add, min/max fold."""
    out: Dict[str, Dict[str, float]] = {}
    for hists in per_rank:
        for name, h in (hists or {}).items():
            e = out.setdefault(
                name,
                {"count": 0.0, "sum": 0.0, "min": float("inf"), "max": float("-inf")},
            )
            e["count"] += float(h.get("count", 0.0))
            e["sum"] += float(h.get("sum", 0.0))
            e["min"] = min(e["min"], float(h.get("min", float("inf"))))
            e["max"] = max(e["max"], float(h.get("max", float("-inf"))))
    return out


def merge_windows(exports: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Merge `windows_export()` payloads from several ranks, aligned by
    bucket AGE (newest first). Exports must share one bucket width — a
    heterogeneous fleet has no meaningful common window and raises
    ValueError (the fleet plane treats that rank's payload as unusable, it
    never averages misaligned buckets). Per-bucket counts and sums stay
    exact; merged sample lists are the sorted concatenation."""
    exports = [e for e in exports if e]
    if not exports:
        return {"bucket_seconds": None, "bucket_count": 0, "counters": {}, "hists": {}, "ranks": 0}
    bucket_s = float(exports[0]["bucket_seconds"])
    for e in exports[1:]:
        if abs(float(e["bucket_seconds"]) - bucket_s) > 1e-9:
            raise ValueError(
                "merge_windows: mismatched bucket_seconds "
                f"({e['bucket_seconds']} vs {bucket_s}) — ranks must share "
                "metrics_bucket_seconds for their windows to align"
            )
    n = max(int(e["bucket_count"]) for e in exports)
    counters: Dict[str, List[float]] = {}
    hists: Dict[str, Dict[str, List[Any]]] = {}
    for e in exports:
        for name, vals in (e.get("counters") or {}).items():
            acc = counters.setdefault(name, [0.0] * n)
            for i, v in enumerate(vals[:n]):
                acc[i] += float(v)
        for name, h in (e.get("hists") or {}).items():
            hacc = hists.setdefault(
                name,
                {
                    "counts": [0.0] * n,
                    "sums": [0.0] * n,
                    "samples": [[] for _ in range(n)],
                },
            )
            m = min(n, len(h["counts"]))
            for i in range(m):
                hacc["counts"][i] += float(h["counts"][i])
                hacc["sums"][i] += float(h["sums"][i])
                hacc["samples"][i].extend(h["samples"][i])
    for h in hists.values():
        h["samples"] = [sorted(s) for s in h["samples"]]
    return {
        "bucket_seconds": bucket_s,
        "bucket_count": n,
        "counters": counters,
        "hists": hists,
        "ranks": len(exports),
    }


class MergedWindows:
    """Read-side view over a `merge_windows()` result that duck-types the
    registry's windowed readers (`rate` / `window_count` / `window_quantile`
    / `window_fraction_over` / `snapshot()["gauges"]`) so the SLO evaluator
    runs unchanged over a CLUSTER window (ops_plane.slo.evaluate_reader).
    The merged export is a static snapshot: "now" is the newest bucket, and
    a `window_s` selects the newest ``round(window_s / bucket)`` buckets."""

    def __init__(
        self,
        merged: Optional[Dict[str, Any]],
        gauges: Optional[Dict[str, float]] = None,
    ) -> None:
        self._m = merged or {
            "bucket_seconds": None,
            "bucket_count": 0,
            "counters": {},
            "hists": {},
        }
        # cluster gauge view for gauge_ceiling specs: name -> the value the
        # ceiling should judge (the fleet plane passes per-rank MAX — a
        # ceiling breached anywhere is breached)
        self._gauges = dict(gauges or {})

    def _k(self, window_s: Optional[float]) -> int:
        bucket_s = self._m.get("bucket_seconds") or 0.0
        n = int(self._m.get("bucket_count") or 0)
        if not bucket_s or not n:
            return 0
        horizon = bucket_s * n
        span = horizon if window_s is None else min(max(float(window_s), bucket_s), horizon)
        return max(1, min(n, int(round(span / bucket_s))))

    def bucket_seconds(self) -> float:
        return float(self._m.get("bucket_seconds") or 0.0)

    def window_horizon_s(self) -> float:
        return self.bucket_seconds() * int(self._m.get("bucket_count") or 0)

    def rate(self, name: str, window_s: Optional[float] = None) -> Optional[float]:
        vals = (self._m.get("counters") or {}).get(name)
        k = self._k(window_s)
        if vals is None or not k:
            return None
        span = k * float(self._m["bucket_seconds"])
        return sum(vals[:k]) / span if span > 0 else None

    def window_samples(self, name: str, window_s: Optional[float] = None) -> List[float]:
        h = (self._m.get("hists") or {}).get(name)
        if h is None:
            return []
        out: List[float] = []
        for i in range(min(self._k(window_s), len(h["samples"]))):
            out.extend(h["samples"][i])
        return out

    def window_count(self, name: str, window_s: Optional[float] = None) -> float:
        h = (self._m.get("hists") or {}).get(name)
        if h is None:
            return 0.0
        return float(sum(h["counts"][: self._k(window_s)]))

    def window_quantile(
        self, name: str, q: float, window_s: Optional[float] = None
    ) -> Optional[float]:
        return quantile_of(self.window_samples(name, window_s), q)

    def window_fraction_over(
        self, name: str, threshold: float, window_s: Optional[float] = None
    ) -> Optional[Tuple[float, int]]:
        samples = self.window_samples(name, window_s)
        if not samples:
            return None
        bad = sum(1 for s in samples if s > threshold)
        return bad / len(samples), len(samples)

    def snapshot(self) -> Dict[str, Any]:
        return {"gauges": dict(self._gauges)}


# ---------------------------------------------------------------- registry --


class MetricsRegistry:
    """Process-global metrics store. All methods are thread-safe; all record
    methods are no-ops while telemetry is disabled (callers may skip the call
    entirely with `enabled()` — both layers check)."""

    def __init__(self) -> None:
        self._lock = lockcheck.make_lock("telemetry.MetricsRegistry._lock")
        self._counters: Dict[str, float] = {}  # guarded-by: _lock
        self._gauges: Dict[str, float] = {}  # guarded-by: _lock
        self._hists: Dict[str, Dict[str, float]] = {}  # guarded-by: _lock
        # per-histogram ring of the most recent observations (quantile())
        self._hist_samples: Dict[str, List[float]] = {}  # guarded-by: _lock
        self._spans: List[Dict[str, Any]] = []  # guarded-by: _lock
        # monotone count of ALL spans ever recorded — `_spans` is trimmed to a
        # bound, so marks must not be absolute list indices
        self._spans_total: int = 0  # guarded-by: _lock
        self._convergence: Dict[str, List[List[float]]] = {}  # guarded-by: _lock
        # rolling windows (ops plane): params resolved at first record after
        # construction/reset, one ring per counter/histogram
        self._win_cfg: Optional[Tuple[float, int]] = None  # guarded-by: _lock
        self._win_counters: Dict[str, _CounterRing] = {}  # guarded-by: _lock
        self._win_hists: Dict[str, _HistRing] = {}  # guarded-by: _lock
        # slow calls: per top-level path the last _SLOW_RING walls
        self._calls: Dict[str, "collections.deque[float]"] = {}  # guarded-by: _lock
        self._slow_calls: List[Dict[str, Any]] = []  # guarded-by: _lock
        self._slow_total: int = 0  # guarded-by: _lock
        self._threads: Optional[Dict[str, Any]] = None  # the last thread table  # guarded-by: _lock
        self._threads_due: float = float("-inf")  # monotonic: no table before  # guarded-by: _lock
        # the flight recorders' overwrites already in `flightrec.events_dropped`
        self._flightrec_seen: int = 0  # guarded-by: _lock

    def _win(self) -> Tuple[float, int]:
        """Window params, resolved once per construction/reset (caller holds
        the lock)."""
        if self._win_cfg is None:
            self._win_cfg = _window_params()
        return self._win_cfg

    # -- record ------------------------------------------------------------
    def inc(self, name: str, value: float = 1.0) -> None:
        if not _STATE.on:
            return
        now = time.monotonic()
        with self._lock:
            self._counters[name] = self._counters.get(name, 0.0) + value
            ring = self._win_counters.get(name)
            if ring is None:
                bucket_s, n = self._win()
                ring = self._win_counters[name] = _CounterRing(bucket_s, n)
            ring.add(now, value)

    def gauge(self, name: str, value: float) -> None:
        if not _STATE.on:
            return
        with self._lock:
            self._gauges[name] = float(value)

    def gauge_max(self, name: str, value: float) -> None:
        """Watermark gauge: keep the maximum ever seen (HBM peaks)."""
        if not _STATE.on:
            return
        with self._lock:
            self._gauges[name] = max(self._gauges.get(name, float("-inf")), float(value))

    def observe(self, name: str, value: float) -> None:
        """Histogram observation (count/sum/min/max summary, not buckets)."""
        if not _STATE.on:
            return
        now = time.monotonic()
        with self._lock:
            h = self._hists.setdefault(
                name, {"count": 0.0, "sum": 0.0, "min": float("inf"), "max": float("-inf")}
            )
            h["count"] += 1.0
            h["sum"] += value
            h["min"] = min(h["min"], value)
            h["max"] = max(h["max"], value)
            samples = self._hist_samples.setdefault(name, [])
            samples.append(float(value))
            if len(samples) > _MAX_HIST_SAMPLES:
                del samples[: -_MAX_HIST_SAMPLES // 2]
            ring = self._win_hists.get(name)
            if ring is None:
                bucket_s, n = self._win()
                ring = self._win_hists[name] = _HistRing(bucket_s, n)
            ring.add(now, float(value))

    def record_span(
        self,
        name: str,
        path: str,
        wall_s: float,
        attrs: Dict[str, Any],
        t0: Optional[float] = None,
        rank: Optional[int] = None,
        tags: Optional[Dict[str, Any]] = None,
    ) -> Optional[Dict[str, Any]]:
        """One span's record (returned). `rank` and `tags` are this
        process's rank and the trace tags where the caller has resolved them
        already (a `_Span` does, once for its record and its two
        flight-recorder events)."""
        if not _STATE.on:
            return None
        rec = {"kind": "span", "name": name, "path": path, "wall_s": wall_s,
               "rank": _rank() if rank is None else rank,
               **(_diag().trace_tags() if tags is None else tags), **attrs}
        if t0 is not None:
            # wall-clock start: what lets trace_merge place this span on a
            # cross-rank timeline (perf_counter has no cross-process meaning)
            rec["t0"] = t0
        with self._lock:
            self._spans.append(rec)
            self._spans_total += 1
            if len(self._spans) > _MAX_SPAN_RECORDS:
                del self._spans[: -_MAX_SPAN_RECORDS // 2]
        self.observe(f"span.{path}", wall_s)
        _sink_write(rec)
        return rec

    # -- slow calls ----------------------------------------------------------
    def open_call(self) -> Tuple[int, float, List[int]]:
        """What a top-level span reads as it opens, for the slow-call record
        (`close_call`): the count of spans recorded so far (those after it
        are the call's), the compile ledger's seconds and the collections of
        each generation."""
        with self._lock:
            h = self._hists.get("compile.wall_s")
            return self._spans_total, h["sum"] if h else 0.0, [s["collections"] for s in gc.get_stats()]

    def close_call(self, rec: Dict[str, Any], opened: Tuple[int, float, List[int]]) -> None:
        """A top-level span's record `rec` against its path's last walls:
        O(1) unless the call is slow or a thread table is due."""
        path, wall = rec["path"], rec["wall_s"]
        now = time.monotonic()
        with self._lock:
            ring = self._calls.get(path)
            if ring is None:
                ring = self._calls[path] = collections.deque(maxlen=_SLOW_RING)
            median = None
            if len(ring) >= _SLOW_AFTER and wall > _SLOW_EXCESS_S:
                median = quantile_of(list(ring), 0.5)
            ring.append(wall)
            slow = median is not None and wall > _SLOW_RATIO * median and wall > median + _SLOW_EXCESS_S
            if not slow and now < self._threads_due:
                return
        table = _thread_table()
        took = time.monotonic() - now
        with self._lock:
            self._threads_due = now + max(_THREAD_TABLE_EVERY_S, took / _THREAD_TABLE_SHARE)
            before, self._threads = self._threads, table or self._threads
            if not slow:
                return
            since = min(len(self._spans), self._spans_total - opened[0])
            under = [r for r in self._spans[len(self._spans) - since:] if r["path"].startswith(path + "/")]
            usual = {}
            for r in under:  # each path's mean wall over its other records, for `_excess_span`
                h = self._hists.get("span." + r["path"])
                n = h["count"] - 1.0 if h else 0.0
                usual[r["path"]] = (h["sum"] - r["wall_s"]) / n if n > 0 else 0.0
            h = self._hists.get("compile.wall_s")
            compile_s = (h["sum"] if h else 0.0) - opened[1]
        kept = sorted(under, key=lambda r: -r["wall_s"])[:_MAX_SLOW_CALL_SPANS]
        kept.sort(key=lambda r: r.get("t0", 0.0))
        record = {
            "path": path,
            "wall_s": wall,
            "median_s": median,
            "span": dict(rec),
            "spans": [{k: r[k] for k in ("path", "t0", "wall_s", "wait_s", "waits") if k in r} for r in kept],
            "spans_left_out": len(under) - len(kept),
            "excess_in": _excess_span(under, usual, wall - median),
            "gc": {"count": list(gc.get_count()),
                   "collections": [s["collections"] - c0 for s, c0 in zip(gc.get_stats(), opened[2])]},
            "compile_s": compile_s,
            "threads": table,
            "threads_before": before,
        }
        with self._lock:
            self._slow_calls.append(record)
            del self._slow_calls[:-_MAX_SLOW_CALLS]
            self._slow_total += 1
        self.inc("telemetry.slow_calls")
        diag = _diag()
        diag.record_event("slow_call", **record)
        from .utils import get_logger

        get_logger("telemetry").warning(_slow_call_line(record))
        diag.flight_recorder().dump(reason=f"slow call: {path} {wall:.2f} s against a median of {median:.2f} s")

    def _sync_flightrec(self) -> None:
        """Bring `flightrec.events_dropped` up to the flight recorders' own
        count of overwrites: called where a snapshot, a mark or a delta is
        taken, so that no overwritten event pays for a counter."""
        overwritten = _diag().FlightRecorder.overwritten
        with self._lock:
            new, self._flightrec_seen = overwritten - self._flightrec_seen, overwritten
        if new > 0:
            self.inc("flightrec.events_dropped", float(new))

    def record_convergence(self, solver: str, iteration: int, value: float) -> None:
        if not _STATE.on:
            return
        with self._lock:
            pts = self._convergence.setdefault(solver, [])
            if len(pts) >= _MAX_CONVERGENCE_POINTS:
                # ring-buffer semantics: drop the OLDEST point so `last` (and
                # the tail a long-lived process cares about) stays current;
                # surface the truncation instead of silently losing data
                pts.pop(0)
                self._counters[f"{solver}.convergence_points_dropped"] = (
                    self._counters.get(f"{solver}.convergence_points_dropped", 0.0) + 1.0
                )
            pts.append([int(iteration), float(value)])

    # -- read --------------------------------------------------------------
    def quantile(self, name: str, q: float) -> Optional[float]:
        """Quantile estimate over histogram `name`'s retained sample window
        (the most recent ``_MAX_HIST_SAMPLES`` observations — a long-lived
        serving process reads CURRENT latency, not all-time). None when no
        observations exist. Nearest-rank on the sorted window."""
        with self._lock:
            samples = list(self._hist_samples.get(name) or ())
        return quantile_of(samples, q)

    # -- windowed reads (ops plane) ----------------------------------------
    def window_horizon_s(self) -> float:
        """The rolling-window horizon (bucket width x bucket count)."""
        with self._lock:
            bucket_s, n = self._win()
        return bucket_s * n

    def bucket_seconds(self) -> float:
        with self._lock:
            return self._win()[0]

    def rate(self, name: str, window_s: Optional[float] = None) -> Optional[float]:
        """Counter increments per second over the most recent `window_s`
        (None = the whole ring horizon; any window clamps to it). None for a
        counter never incremented since the last reset — a never-seen metric
        has no rate, which is different from a zero one."""
        with self._lock:
            ring = self._win_counters.get(name)
            if ring is None:
                return None
            total, span = ring.window_sum(time.monotonic(), window_s)
        return total / span if span > 0 else None

    def window_count(self, name: str, window_s: Optional[float] = None) -> float:
        """Observations recorded into histogram `name` within the window."""
        with self._lock:
            ring = self._win_hists.get(name)
            if ring is None:
                return 0.0
            return float(ring.window_count(time.monotonic(), window_s))

    def window_quantile(
        self, name: str, q: float, window_s: Optional[float] = None
    ) -> Optional[float]:
        """Quantile over histogram `name`'s observations within the most
        recent `window_s` (clamped to the ring horizon). Approximate past
        ``_MAX_BUCKET_SAMPLES`` observations per bucket; None when the window
        holds no samples."""
        with self._lock:
            ring = self._win_hists.get(name)
            if ring is None:
                return None
            samples = ring.window_samples(time.monotonic(), window_s)
        return quantile_of(samples, q)

    def window_fraction_over(
        self, name: str, threshold: float, window_s: Optional[float] = None
    ) -> Optional[Tuple[float, int]]:
        """(fraction of windowed observations strictly above `threshold`,
        sample count) — the SLO burn-rate numerator. None when the window is
        empty (no traffic is not a violation)."""
        with self._lock:
            ring = self._win_hists.get(name)
            if ring is None:
                return None
            samples = ring.window_samples(time.monotonic(), window_s)
        if not samples:
            return None
        bad = sum(1 for s in samples if s > threshold)
        return bad / len(samples), len(samples)

    def windows_snapshot(self) -> Dict[str, Any]:
        """Machine-readable rolling-window view — what the exporters and
        `ops_plane.report()` serve: per-counter rates over the fast window
        (60s, clamped to the horizon) AND the full horizon, and per-histogram
        p50/p99/count over the full horizon. Taken under ONE lock hold at ONE
        clock instant, so every metric in the snapshot describes the same
        window — and a scrape costs one lock round-trip, not O(metrics)."""
        now = time.monotonic()
        with self._lock:
            bucket_s, n = self._win()
            horizon = bucket_s * n
            fast = min(60.0, horizon)
            rates: Dict[str, Any] = {}
            for name, ring in self._win_counters.items():
                fsum, fspan = ring.window_sum(now, fast)
                hsum, hspan = ring.window_sum(now, None)
                rates[name] = {
                    "fast_per_s": fsum / fspan if fspan > 0 else None,
                    "horizon_per_s": hsum / hspan if hspan > 0 else None,
                }
            quantiles: Dict[str, Any] = {}
            for name, ring in self._win_hists.items():
                samples = ring.window_samples(now, None)
                quantiles[name] = {
                    "p50": quantile_of(samples, 0.5),
                    "p99": quantile_of(samples, 0.99),
                    "count": float(ring.window_count(now, None)),
                }
        return {
            "bucket_seconds": bucket_s,
            "bucket_count": n,
            "horizon_s": horizon,
            "rates": rates,
            "quantiles": quantiles,
        }

    def windows_export(self) -> Dict[str, Any]:
        """Merge-form export of the rolling windows (docs/observability.md
        "Fleet plane"): per-counter per-bucket increment sums and
        per-histogram per-bucket (count, sum, sorted samples), all indexed by
        bucket AGE (newest first). Ring heads are per-process
        ``time.monotonic()`` bucket indices with no cross-process meaning, so
        age is the only alignment the fleet merger can use — cross-rank skew
        is bounded by one bucket width. Samples are sorted here so the merge
        is canonical: merging one export is the identity, and merge order
        cannot change the result. Taken under one lock hold at one clock
        instant, like `windows_snapshot`."""
        now = time.monotonic()
        with self._lock:
            bucket_s, n = self._win()
            counters: Dict[str, List[float]] = {}
            for name, ring in self._win_counters.items():
                b = int(now // ring.bucket_s)
                if ring.head is None or b > ring.head:
                    ring._advance(b)
                assert ring.head is not None
                counters[name] = [
                    float(ring.vals[(ring.head - i) % ring.n]) for i in range(ring.n)
                ]
            hists: Dict[str, Dict[str, List[Any]]] = {}
            for name, hring in self._win_hists.items():
                b = int(now // hring.bucket_s)
                if hring.head is None or b > hring.head:
                    hring._advance(b)
                assert hring.head is not None
                idx = [(hring.head - i) % hring.n for i in range(hring.n)]
                hists[name] = {
                    "counts": [float(hring.counts[i]) for i in idx],
                    "sums": [float(hring.sums[i]) for i in idx],
                    "samples": [sorted(hring.samples[i]) for i in idx],
                }
        return {
            "bucket_seconds": bucket_s,
            "bucket_count": n,
            "counters": counters,
            "hists": hists,
        }

    def convergence_trace(self, solver: str) -> List[List[float]]:
        """[(iteration, value), ...] points recorded for `solver`."""
        with self._lock:
            return [list(p) for p in self._convergence.get(solver, [])]

    def snapshot(self) -> Dict[str, Any]:
        """Machine-readable state: counters, gauges, histogram summaries, and
        per-path span aggregates. Safe to json.dumps. Span aggregates come
        from the `span.<path>` histograms, which see EVERY span — the raw
        record list is trimmed to a bound and would under-count.
        `slow_calls`: the records of the last slow calls (`close_call`)."""
        self._sync_flightrec()
        with self._lock:
            spans: Dict[str, Dict[str, float]] = {}
            for hname, h in self._hists.items():
                if hname.startswith("span."):
                    spans[hname[len("span."):]] = {
                        "count": h["count"],
                        "total_s": h["sum"],
                        "min_s": h["min"],
                        "max_s": h["max"],
                    }
            snap = {
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "histograms": {k: dict(v) for k, v in self._hists.items()},
                "spans": spans,
                "convergence": {
                    k: {"points": len(v), "last": v[-1] if v else None}
                    for k, v in self._convergence.items()
                },
                "slow_calls": [dict(r) for r in self._slow_calls],
            }
        # flight-recorder health rides the snapshot (and therefore the bench
        # JSON "telemetry" embedding) — outside the lock: the recorder has its
        # own and never calls back into the registry while holding it
        snap["flightrec"] = _diag().flight_recorder().stats()
        return snap

    class _Mark:
        __slots__ = ("counters", "hists", "spans_total", "slow_total")

    def mark(self) -> "MetricsRegistry._Mark":
        """Cheap position marker for `delta()` (fit-scoped metrics)."""
        self._sync_flightrec()
        m = MetricsRegistry._Mark()
        with self._lock:
            m.counters = dict(self._counters)
            m.hists = {k: dict(v) for k, v in self._hists.items()}
            m.spans_total = self._spans_total
            m.slow_total = self._slow_total
        return m

    def delta(self, m: "MetricsRegistry._Mark") -> Dict[str, Any]:
        """Counters/histograms accumulated SINCE `m`, spans recorded since
        `m`, and current gauges — the per-fit view attached to models.
        `spans_dropped` is how many of the spans recorded since `m` the trim
        no longer holds (exact): a reader that sums or averages `spans`
        must refuse a non-zero value rather than report over a cut list.
        `slow_calls`: the records of the slow calls since `m` that are
        still kept (a fit's own, in its model's `_fit_metrics`)."""
        self._sync_flightrec()
        with self._lock:
            counters = {
                k: v - m.counters.get(k, 0.0)
                for k, v in self._counters.items()
                if v != m.counters.get(k, 0.0)
            }
            hists = {}
            for k, v in self._hists.items():
                prev = m.hists.get(k)
                count = v["count"] - (prev["count"] if prev else 0.0)
                if count:
                    hists[k] = {
                        "count": count,
                        "sum": v["sum"] - (prev["sum"] if prev else 0.0),
                    }
            # spans recorded since the mark, bounded by what the trim kept:
            # the count since the mark is exact (monotone counter); if more
            # than the retained window were recorded, only the tail survives
            since = max(0, self._spans_total - m.spans_total)
            kept = min(since, len(self._spans))
            spans = [dict(r) for r in self._spans[len(self._spans) - kept:]] if kept else []
            # copy gauges UNDER the lock: the copy used to happen in the
            # return expression after releasing it, so a concurrent gauge()
            # could resize the dict mid-iteration (found by the
            # guard-discipline rule)
            gauges = dict(self._gauges)
            slow = min(len(self._slow_calls), self._slow_total - m.slow_total)
            slow_calls = [dict(r) for r in self._slow_calls[len(self._slow_calls) - slow:]] if slow > 0 else []
        return {
            "counters": counters,
            "gauges": gauges,
            "histograms": hists,
            "spans": spans,
            "spans_dropped": since - kept,
            "slow_calls": slow_calls,
        }

    def reset(self) -> None:
        overwritten = _diag().FlightRecorder.overwritten
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()
            self._hist_samples.clear()
            self._spans.clear()
            self._convergence.clear()
            # window rings rebuild against the CURRENT config on next record —
            # this is how tests (and reconfiguring operators) apply new
            # bucket params
            self._win_cfg = None
            self._win_counters.clear()
            self._win_hists.clear()
            self._calls.clear()
            self._slow_calls.clear()
            self._threads, self._threads_due = None, float("-inf")
            self._flightrec_seen = overwritten


def _thread_table() -> Optional[Dict[str, Any]]:
    """The process's threads by name with the CPU seconds (user + system)
    each name has burnt, from `/proc/self/task/*/stat`; None where `/proc`
    is not there. Two tables subtracted say which thread was busy between
    them."""
    try:
        tids = os.listdir("/proc/self/task")
    except OSError:
        return None
    tick = os.sysconf("SC_CLK_TCK")
    cpu: Dict[str, float] = {}
    for tid in tids:
        try:
            fd = os.open(f"/proc/self/task/{tid}/stat", os.O_RDONLY)
        except OSError:  # the thread ended between the listing and the read
            continue
        try:
            head, _, rest = os.read(fd, 1024).decode("ascii", "replace").rpartition(")")
        finally:
            os.close(fd)
        fields = rest.split()  # from field 3 (state): utime and stime are fields 14 and 15
        name = head.partition("(")[2]
        cpu[name] = cpu.get(name, 0.0) + (int(fields[11]) + int(fields[12])) / tick
    return {"t": time.time(), "threads": len(tids), "cpu_s": cpu}


def _excess_span(under: List[Dict[str, Any]], usual: Dict[str, float], excess: float) -> Optional[str]:
    """The path of the deepest span under a slow call that holds at least
    half of the call's excess (its wall less its path's usual wall); None
    where no span does (the excess lies in the call's own code)."""
    held = [r for r in under if r["wall_s"] - usual.get(r["path"], 0.0) >= 0.5 * excess]
    if not held:
        return None
    return max(held, key=lambda r: (r["path"].count("/"), r["wall_s"]))["path"]


def _slow_call_line(record: Dict[str, Any]) -> str:
    """The one WARNING line of a slow call."""
    span = record["span"]
    line = f"slow call: {record['path']} {record['wall_s']:.2f} s against a median of {record['median_s']:.2f} s"
    inside = next((r for r in record["spans"] if r["path"] == record["excess_in"]), None)
    if inside is not None:
        line += f": {inside['path']} {inside['wall_s']:.2f} s of which waiting {inside.get('wait_s', 0.0):.2f} s"
    line += (
        f"; process cpu {span.get('cpu_s', 0.0):.2f} s, {span.get('minor_faults', 0)} minor faults, "
        f"{span.get('invol_switches', 0)} involuntary switches"
    )
    now, before = record["threads"], record["threads_before"]
    if now and before:
        rise = {n: c - before["cpu_s"].get(n, 0.0) for n, c in now["cpu_s"].items()}
        name = max(rise, key=rise.get)
        line += f"; busiest thread in the {now['t'] - before['t']:.1f} s before: {name} {rise[name]:.2f} s cpu"
    return line


_REGISTRY = MetricsRegistry()


def registry() -> MetricsRegistry:
    return _REGISTRY


def snapshot() -> Dict[str, Any]:
    return _REGISTRY.snapshot()


def summary() -> str:
    """One-line-per-stage human summary of the current registry state:
    ``print(telemetry.summary())`` after any fit. Ends with a flight-recorder
    health line (events recorded/dropped for this rank) — ring truncation is
    never silent (docs/observability.md "no silent caps")."""
    snap = _REGISTRY.snapshot()
    lines = []
    for path, agg in sorted(snap["spans"].items()):
        lines.append(
            f"{path}: {agg['total_s']:.3f}s total / {int(agg['count'])} call(s)"
        )
    for name, v in sorted(snap["counters"].items()):
        lines.append(f"{name}: {v:,.0f}")
    for name, v in sorted(snap["gauges"].items()):
        lines.append(f"{name}: {v:,.6g}")
    fr = snap["flightrec"]  # snapshot() already embeds the recorder stats
    if fr["enabled"]:
        lines.append(
            f"flightrec rank{_rank()}: {fr['recorded']} events recorded / "
            f"{fr['dropped']} dropped (capacity {fr['capacity']})"
        )
    else:
        lines.append("flightrec: disabled (SRML_FLIGHTREC=0)")
    return "\n".join(lines)


def summarize_histogram(name: str, *, window_s: Optional[float] = None) -> Dict[str, Optional[float]]:
    """One histogram's summary view: cumulative count/sum/mean/min/max plus
    p50/p99 — over the retained cumulative sample window by default, over the
    most recent `window_s` of the rolling ring when given. THE shared p50/p99
    extraction (`ScoringEngine.stats`, `FitScheduler.stats`, and the ops
    plane all delegate here — hand-rolled copies would silently diverge now
    that windowed quantiles exist). All values None when nothing was
    observed."""
    reg = _REGISTRY
    with reg._lock:
        h = reg._hists.get(name)
        cum = dict(h) if h else None
    out: Dict[str, Optional[float]] = {
        "count": cum["count"] if cum else None,
        "sum": cum["sum"] if cum else None,
        "mean": (cum["sum"] / cum["count"]) if cum and cum["count"] else None,
        "min": cum["min"] if cum else None,
        "max": cum["max"] if cum else None,
    }
    if window_s is None:
        out["p50"] = reg.quantile(name, 0.5)
        out["p99"] = reg.quantile(name, 0.99)
    else:
        out["p50"] = reg.window_quantile(name, 0.5, window_s)
        out["p99"] = reg.window_quantile(name, 0.99, window_s)
        out["window_count"] = reg.window_count(name, window_s)
    return out


def tenant_metric(base: str, tenant: str) -> str:
    """THE per-tenant metric naming contract: ``<base>.<tenant>`` with the
    tenant sanitized to the metric-name alphabet (every run of characters
    outside ``[A-Za-z0-9_.:-]`` collapses to one ``_``). The serving plane
    records per-tenant siblings of its global surfaces
    (``serve.queue_wait_s.<tenant>``, ``serve.e2e_s.<tenant>``,
    ``serve.rows.<tenant>``) through this one helper — the overload
    controller and the ops report read the SAME names back, so the contract
    lives here, not duplicated at each call site
    (docs/observability.md "Serving plane")."""
    safe = re.sub(r"[^A-Za-z0-9_.:\-]+", "_", tenant) or "_"
    return f"{base}.{safe}"


# ------------------------------------------------------------------- sinks --

_SINK_LOCK = lockcheck.make_lock("telemetry._SINK_LOCK")
_SINK_FILES: Dict[str, Any] = {}


def _close_sinks() -> None:
    """Close every cached sink handle (disable() and interpreter exit) so
    re-pointing the sink per job never accumulates open fds."""
    with _SINK_LOCK:
        for f in _SINK_FILES.values():
            try:
                f.close()
            except OSError:  # pragma: no cover
                pass
        _SINK_FILES.clear()


atexit.register(_close_sinks)


def _sink_path() -> Optional[str]:
    """Per-rank JSONL path: rank 0 owns the configured path, other ranks get
    `<path>.rank<r>` so SPMD processes on a shared filesystem never interleave
    writes in one file."""
    path = _STATE.sink_path
    if not path:
        return None
    r = _rank()
    return path if r == 0 else f"{path}.rank{r}"


def _sink_write(rec: Dict[str, Any]) -> None:
    path = _sink_path()
    if path is None:
        return
    line = json.dumps(rec, default=_json_default) + "\n"
    with _SINK_LOCK:  # held-ok: the sink lock exists to serialize exactly this local append (open-once + write + flush); no other lock is ever taken under it
        f = _SINK_FILES.get(path)
        if f is None or f.closed:
            try:
                f = open(path, "a")
            except OSError:
                return
            _SINK_FILES[path] = f
        f.write(line)
        f.flush()


def _json_default(o: Any):
    try:
        import numpy as np

        if isinstance(o, np.generic):
            return o.item()
        if isinstance(o, np.ndarray):
            return o.tolist()
    except ImportError:  # pragma: no cover
        pass
    return str(o)


# ------------------------------------------------------------------- spans --


class _NoopSpan:
    """Shared do-nothing span — what `span()` returns while disabled."""

    __slots__ = ()
    wall_s: Optional[float] = None

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False

    def set(self, **attrs: Any) -> None:
        pass


_NOOP_SPAN = _NoopSpan()


class _Span:
    __slots__ = ("name", "attrs", "logger", "path", "wall_s", "wait_s", "waits",
                 "_t0", "_w0", "_ta", "_rank", "_tags", "_opened")

    def __init__(self, name: str, logger: Any, attrs: Dict[str, Any]) -> None:
        self.name = name
        self.logger = logger
        self.attrs = attrs
        self.wall_s: Optional[float] = None
        # what `device_wait` adds while this span is the innermost open one
        self.wait_s = 0.0
        self.waits = 0

    def __enter__(self) -> "_Span":
        stack = getattr(_LOCAL, "stack", None)
        if stack is None:
            stack = _LOCAL.stack = []
        self.path = f"{stack[-1].path}/{self.name}" if stack else self.name
        top = not stack and _STATE.on
        stack.append(self)
        # xprof alignment: TraceAnnotation is the NVTX-range analog — it tags
        # this wall-clock interval in any ACTIVE jax.profiler trace and is
        # near-free when no trace is running. Spans must never break when the
        # profiler is inactive, so failures here are swallowed.
        self._ta = None
        try:
            import jax

            self._ta = jax.profiler.TraceAnnotation(self.path)
            self._ta.__enter__()
        except Exception:
            self._ta = None
        self._w0 = time.time()  # wall clock, for cross-rank trace merging
        # rank and trace tags resolved once, for the two flight-recorder
        # events and the record
        diag = _diag()
        self._rank, self._tags = diag._rank(), diag.trace_tags()
        diag.flight_recorder().record_as(
            self._rank, self._tags, "span_begin", {"name": self.name, "path": self.path}
        )
        self._t0 = time.perf_counter()
        # a top-level span carries what the process spent while its clock ran
        # (`_spent`). The readings lie inside its wall: the span pays for
        # them, not the caller's time between spans; nested spans take none
        self._opened = None
        if top:
            self._opened = (resource.getrusage(resource.RUSAGE_SELF), time.thread_time(), _REGISTRY.open_call())
        return self

    def __exit__(self, exc_type: Any, exc_val: Any, exc_tb: Any) -> bool:
        spent = self._spent() if self._opened is not None and exc_type is None else None
        self.wall_s = time.perf_counter() - self._t0
        if self._ta is not None:
            try:
                self._ta.__exit__(exc_type, exc_val, exc_tb)
            except Exception:
                pass
        stack = _LOCAL.stack
        if stack and stack[-1] is self:
            stack.pop()
        recorder = _diag().flight_recorder()
        if exc_type is not None:
            recorder.record_as(self._rank, self._tags, "span_fail",
                               {"name": self.name, "path": self.path, "error": exc_type.__name__})
            return False
        recorder.record_as(self._rank, self._tags, "span_end",
                           {"name": self.name, "path": self.path, "wall_s": self.wall_s})
        if self.waits:
            self.attrs.update(wait_s=self.wait_s, waits=self.waits)
        if spent is not None:
            self.attrs.update(spent)
        rec = _REGISTRY.record_span(self.name, self.path, self.wall_s, self.attrs,
                                    t0=self._w0, rank=self._rank, tags=self._tags)
        if rec is not None and self._opened is not None:
            _REGISTRY.close_call(rec, self._opened[2])
        if self.logger is not None:
            self.logger.info("stage %s: %.3fs", self.path, self.wall_s)
        return False

    def _spent(self) -> Dict[str, Any]:
        """What the process spent while this top-level span was open:
        `cpu_s` (user + system, all threads), `thread_cpu_s` (the calling
        thread), page faults and context switches."""
        ru0, thread0, _ = self._opened
        ru = resource.getrusage(resource.RUSAGE_SELF)
        return {
            "cpu_s": (ru.ru_utime - ru0.ru_utime) + (ru.ru_stime - ru0.ru_stime),
            "thread_cpu_s": time.thread_time() - thread0,
            "minor_faults": ru.ru_minflt - ru0.ru_minflt,
            "major_faults": ru.ru_majflt - ru0.ru_majflt,
            "vol_switches": ru.ru_nvcsw - ru0.ru_nvcsw,
            "invol_switches": ru.ru_nivcsw - ru0.ru_nivcsw,
        }

    def set(self, **attrs: Any) -> None:
        """Attributes known only inside the span (the rung a batch padded to,
        the bytes an extraction returned): host metadata only, never a value
        that has to be fetched from the device."""
        self.attrs.update(attrs)


class _Wait:
    """`device_wait` inside a span: the wait's wall goes onto the innermost
    open span (`wait_s`, `waits`) and, where an efficiency scope is open,
    into that scope through `timer`."""

    __slots__ = ("_span", "_timer", "_t0")

    def __init__(self, span: _Span, timer: Any) -> None:
        self._span = span
        self._timer = timer

    def __enter__(self) -> "_Wait":
        if self._timer is not None:
            self._timer.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: Any) -> bool:
        self._span.wait_s += time.perf_counter() - self._t0
        self._span.waits += 1
        if self._timer is not None:
            self._timer.__exit__(*exc)
        return False


def span(name: str, *, logger: Any = None, **attrs: Any):
    """Nestable timing span.

    ``with telemetry.span("solve", index=0): ...`` records wall time (and the
    nesting path, e.g. ``fit/solve``) into the registry + JSONL sink, tags the
    interval in any active `jax.profiler` trace, and — when `logger` is passed
    (the estimator `verbose` path) — logs ``stage <path>: <t>s``. Returns a
    shared no-op object when telemetry is disabled and no logger wants the
    timing, so the disabled cost is one branch."""
    if not _STATE.on and logger is None:
        return _NOOP_SPAN
    return _Span(name, logger, attrs)


# ------------------------------------------------------ efficiency hooks ----
#
# The profiling hook layer for the efficiency attribution plane
# (ops_plane/efficiency.py, docs/observability.md "Efficiency plane").
# Instrumented call sites stay one cheap call away from telemetry — they
# never import the ops_plane package themselves — and the disabled path is
# one `_STATE.on` branch returning a shared no-op (the same identity
# contract `span()` pins). Timers only ever wrap a host fetch the caller
# already performs; they add no syncs of their own.


class _NoopCompileEvent:
    """Shared do-nothing compile event — what `compile_event()` returns
    while disabled (`cache_hit` stays False)."""

    __slots__ = ()
    cache_hit = False

    def __enter__(self) -> "_NoopCompileEvent":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False


_NOOP_COMPILE_EVENT = _NoopCompileEvent()


def _efficiency():
    """sys.modules probe for the efficiency plane (the `_window_params`
    idiom): attribution scopes are only ever opened through code that
    imported the module, so an absent module means no scope can be active
    and the hook can bail without importing anything."""
    return sys.modules.get(
        (__package__ or "spark_rapids_ml_tpu") + ".ops_plane.efficiency"
    )


def device_wait(stage: str):
    """Time a `block_until_ready`/`np.asarray` wait at a boundary that
    ALREADY host-fetches: the wall goes to the active attribution scope's
    `execute` kind under `stage`, and onto the innermost open span of this
    thread as `wait_s` / `waits`, so that a span's `wall_s - wait_s` is the
    host's own time in it. Shared no-op when telemetry is disabled or
    neither a scope nor a span is open on this thread."""
    if not _STATE.on:
        return _NOOP_SPAN
    eff = _efficiency()
    timer = eff.device_wait_timer(stage) if eff is not None and eff.active() else None
    stack = getattr(_LOCAL, "stack", None)
    if not stack:
        return _NOOP_SPAN if timer is None else timer
    return _Wait(stack[-1], timer)


def host_section(stage: str):
    """Time host-side boundary work (checkpoint serialization, response
    slicing) into the active scope's `host` kind. Same no-op contract as
    `device_wait`."""
    if not _STATE.on:
        return _NOOP_SPAN
    eff = _efficiency()
    if eff is None or not eff.active():
        return _NOOP_SPAN
    return eff.host_section_timer(stage)


def compile_event(program: str, shape_key: Any):
    """Ledger one jit entry-point execution keyed (program, shape-class) —
    first sighting records the body's wall as compile time, later sightings
    count as cache hits (`cm.cache_hit`). Process-wide: records with or
    without an attribution scope. Shared no-op when disabled."""
    if not _STATE.on:
        return _NOOP_COMPILE_EVENT
    from .ops_plane import efficiency

    return efficiency.compile_event(program, str(shape_key))


def note_flops(flops: float, *, chips: int = 1) -> None:
    """Record the active attribution scope's analytic FLOP estimate (the
    `_solver_flop_estimate` hooks) — the roofline/MFU numerator. No-op when
    disabled or outside a scope."""
    if not _STATE.on:
        return
    eff = _efficiency()
    if eff is not None and eff.active():
        eff.note_flops(flops, chips=chips)


def attribution(label: str, *, tenant: Any = None):
    """Open an efficiency attribution window outside the fit path (the
    serving engine opens one per dispatch group). Shared no-op span when
    telemetry is disabled; fits get theirs through `fit_scope`."""
    if not _STATE.on:
        return _NOOP_SPAN
    from .ops_plane import efficiency

    return efficiency.attribution_scope(label, tenant=tenant)


# ------------------------------------------------------- derived recorders --


def record_device_memory() -> None:
    """Sample per-device memory stats into HBM watermark gauges, where the
    backend exposes them (`Device.memory_stats()` — TPU/GPU yes, CPU None).
    Callers invoke this only where the backend is already live (inside fit);
    it never initializes a backend on its own."""
    if not _STATE.on:
        return
    try:
        import jax

        devices = jax.local_devices()
    except Exception:
        return
    peak = in_use = 0
    seen = False
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if not stats:
            continue
        seen = True
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        in_use = max(in_use, int(stats.get("bytes_in_use", 0)))
    if seen:
        _REGISTRY.gauge_max("device.peak_bytes_in_use", peak)
        _REGISTRY.gauge("device.bytes_in_use", in_use)


def record_solver_result(
    solver: str,
    *,
    n_iter: int,
    objective: Optional[float] = None,
    stalled: bool = False,
) -> None:
    """Host-side record of a completed iterative solve: iteration counter,
    final objective gauge, and a final convergence point."""
    if not _STATE.on:
        return
    _REGISTRY.inc(f"{solver}.fits")
    _REGISTRY.inc(f"{solver}.iterations", float(n_iter))
    if stalled:
        _REGISTRY.inc(f"{solver}.line_search_stalls")
    if objective is not None:
        _REGISTRY.gauge(f"{solver}.objective", float(objective))
        _REGISTRY.record_convergence(solver, int(n_iter), float(objective))
    _diag().record_event(
        "solver_result", solver=solver, n_iter=int(n_iter),
        objective=float(objective) if objective is not None else None,
    )


def record_convergence_point(solver: str, iteration: Any, value: Any) -> None:
    """Per-iteration convergence sample. Shaped for `jax.debug.callback`
    (iteration/value arrive as device scalars); also callable from host loops
    (KMeans passes plain floats)."""
    if not _STATE.on:
        return
    import numpy as np

    it, val = int(np.asarray(iteration)), float(np.asarray(value))
    _REGISTRY.record_convergence(solver, it, val)
    _diag().record_event("solver_tick", solver=solver, iteration=it, value=val)


# --------------------------------------------------------------- fit scope --


@contextlib.contextmanager
def fit_scope(label: str):
    """Fit-scoped metrics view. Yields a dict whose ``metrics`` key is filled
    at exit with the registry DELTA accumulated during the fit (counters,
    per-fit spans, histogram deltas, current gauges) — what `core` attaches
    to models as ``_fit_metrics`` — and writes one ``{"kind": "fit"}``
    snapshot record to the JSONL sink."""
    scope: Dict[str, Any] = {"metrics": {}}
    if not _STATE.on:
        yield scope
        return
    m = _REGISTRY.mark()
    # the efficiency attribution scope rides the fit scope: one window per
    # top-level fit (nested fits attribute into the outer window — the
    # scope itself refuses to nest)
    from .ops_plane import efficiency

    eff_cm = efficiency.attribution_scope(label)
    try:
        with eff_cm:
            yield scope
    finally:
        delta = _REGISTRY.delta(m)
        scope["metrics"] = delta
        eff_summary = getattr(eff_cm, "summary", None)
        if eff_summary:
            scope["efficiency"] = eff_summary
        _sink_write(
            {
                "kind": "fit",
                "estimator": label,
                "rank": _rank(),
                **_diag().trace_tags(),
                "counters": delta["counters"],
                "gauges": delta["gauges"],
                "histograms": delta["histograms"],
            }
        )
