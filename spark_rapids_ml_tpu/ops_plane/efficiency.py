#
# Efficiency attribution plane: per-tenant device-time accounting, the
# compile ledger, and roofline/MFU gauges (docs/observability.md
# "Efficiency plane").
#
# PR 13's ledger answers "who held how many bytes for how long"; nothing
# answered "what were the chips DOING during those seconds" — a chip-second
# spent 95%-idle in a host-sync stall was billed identically to one
# saturating the MXU. This module splits attributed wall time into four
# kinds per tenant:
#
#   execute  — measured `block_until_ready` waits at boundaries that ALREADY
#              host-fetch (solver cadence points, `run_segmented_while`
#              segments, streaming chunk partials, serving response
#              assembly). A LOWER bound on device-busy time: compute that
#              overlapped host work before the wait is not seen here.
#   compile  — first-sighting walls from the compile ledger (below). An
#              UPPER bound: a miss wall includes the first execution.
#   host     — measured host-side sections at the same boundaries
#              (checkpoint serialization, response slicing).
#   idle     — the residual: scope wall minus the three measured kinds,
#              clamped at zero. Unattributed python/dispatch overhead lands
#              here, which is exactly the on-call question ("where did the
#              wall go that no stage accounts for").
#
# By construction execute + compile + host + idle == wall for every scope,
# so the roll-up attributes 100% of fit wall time to named kinds; per-stage
# idle is the scope idle distributed proportionally to each stage's
# pre-boundary gap (the window in which the device may have starved).
#
# Contracts:
#   * zero-cost when telemetry is disabled: `attribution_scope` returns a
#     shared no-op, and the telemetry.py hooks (`device_wait`,
#     `host_section`, `compile_event`) bail on one `_STATE.on` check before
#     this module is even imported. No extra syncs, ever: every timer wraps
#     a fetch the caller already performed.
#   * the compile ledger is ALWAYS process-wide (prewarm runs outside any
#     fit scope); scope attribution is layered on top when a scope is
#     active on the calling thread.
#   * nested timers never double-count: the outermost attribution wins
#     (a compile miss wrapping a solve swallows the solve's inner waits).
#
from __future__ import annotations

import contextvars
import time
from typing import Any, Dict, Optional, Tuple

from ..utils import lockcheck

__all__ = [
    "attribution_scope",
    "active",
    "compile_event",
    "compile_stats",
    "note_flops",
    "peak_flops",
    "summary",
    "tenant_time_splits",
    "reset",
]

_KINDS = ("execute_s", "compile_s", "host_s", "idle_s")

_LOCK = lockcheck.make_lock("ops_plane.efficiency._LOCK")
# tenant -> {execute_s, compile_s, host_s, idle_s, wall_s, scopes}  # guarded-by: _LOCK
_TENANTS: Dict[str, Dict[str, float]] = {}
# tenant -> stage -> {execute_s, host_s, idle_s, events}  # guarded-by: _LOCK
_STAGES: Dict[str, Dict[str, Dict[str, float]]] = {}

_COMPILE_LOCK = lockcheck.make_lock("ops_plane.efficiency._COMPILE_LOCK")
# (program, shape_key) -> {misses, hits, wall_s}  # guarded-by: _COMPILE_LOCK
_COMPILE: Dict[Tuple[str, str], Dict[str, float]] = {}

_SCOPE: "contextvars.ContextVar[Optional[_Scope]]" = contextvars.ContextVar(
    "srml_efficiency_scope", default=None
)


def _registry():
    from .. import telemetry

    return telemetry.registry() if telemetry.enabled() else None


# ------------------------------------------------------------ peak spec ----


def parse_peak_spec(spec: Any) -> Optional[float]:
    """Peak-spec grammar (docs/observability.md "Efficiency plane"): a
    number with an optional K/M/G/T/P suffix — ``"14T"``, ``"275e12"``,
    ``900e9`` — in FLOP/s per device. None/empty/unparseable = no peak
    (gauges omitted, never guessed)."""
    if spec is None:
        return None
    if isinstance(spec, (int, float)):
        return float(spec) if spec > 0 else None
    s = str(spec).strip()
    if not s:
        return None
    mult = 1.0
    suffix = {"k": 1e3, "m": 1e6, "g": 1e9, "t": 1e12, "p": 1e15}
    if s[-1].lower() in suffix:
        mult = suffix[s[-1].lower()]
        s = s[:-1]
    try:
        v = float(s) * mult
    except ValueError:
        return None
    return v if v > 0 else None


def peak_flops() -> Optional[float]:
    """The configured per-device peak (`config["device_peak_flops"]`,
    seeded from `SRML_DEVICE_PEAK_FLOPS`), parsed; None when unset."""
    try:
        from ..core import config
    except Exception:
        return None
    return parse_peak_spec(config.get("device_peak_flops"))


# ------------------------------------------------------- attribution scope --


class _Scope:
    """One attribution window (a fit, or one serving dispatch group):
    accumulates measured seconds by (kind, stage) on the opening thread,
    then folds into the per-tenant module totals at close."""

    __slots__ = (
        "label", "tenant", "trace_id", "t0", "mark", "depth",
        "kinds", "stages", "flops", "chips", "compile_hits",
        "compile_misses", "closed", "_token",
    )

    def __init__(self, label: str, tenant: str, trace_id: Optional[str]):
        self.label = label
        self.tenant = tenant
        self.trace_id = trace_id
        self.t0 = time.perf_counter()
        self.mark = self.t0  # last boundary exit (gap accounting)
        self.depth = 0  # >0 while an attribution timer is open
        self.kinds = {"execute_s": 0.0, "compile_s": 0.0, "host_s": 0.0}
        # stage -> {execute_s, host_s, gap_s, events}
        self.stages: Dict[str, Dict[str, float]] = {}
        self.flops = 0.0
        self.chips = 1
        self.compile_hits = 0
        self.compile_misses = 0
        self.closed = False
        self._token = None

    # -- accumulation (single-threaded: the scope's opening thread) --------
    def _stage(self, stage: str) -> Dict[str, float]:
        st = self.stages.get(stage)
        if st is None:
            st = self.stages[stage] = {
                "execute_s": 0.0, "host_s": 0.0, "gap_s": 0.0, "events": 0.0,
            }
        return st

    def note(self, kind: str, stage: str, seconds: float, gap: float) -> None:
        # kind is "execute_s" or "host_s" (compile attributes directly from
        # the ledger event, which has no stage of its own)
        self.kinds[kind] += seconds
        st = self._stage(stage)
        st[kind] += seconds
        st["gap_s"] += gap
        st["events"] += 1

    # -- close -------------------------------------------------------------
    def summary_dict(self) -> Dict[str, Any]:
        wall = max(0.0, time.perf_counter() - self.t0)
        accounted = sum(self.kinds.values())
        idle = max(0.0, wall - accounted)
        total_gap = sum(st["gap_s"] for st in self.stages.values())
        stages: Dict[str, Dict[str, float]] = {}
        top_idle, top_idle_s = None, -1.0
        for name, st in self.stages.items():
            stage_idle = idle * (st["gap_s"] / total_gap) if total_gap > 0 else 0.0
            stages[name] = {
                "execute_s": st["execute_s"],
                "host_s": st["host_s"],
                "idle_s": stage_idle,
                "events": int(st["events"]),
            }
            if stage_idle > top_idle_s:
                top_idle, top_idle_s = name, stage_idle
        out: Dict[str, Any] = {
            "wall_s": wall,
            "execute_s": self.kinds["execute_s"],
            "compile_s": self.kinds["compile_s"],
            "host_s": self.kinds["host_s"],
            "idle_s": idle,
            "stages": stages,
            "top_idle_stage": top_idle,
            "compile": {"hits": self.compile_hits, "misses": self.compile_misses},
        }
        peak = peak_flops()
        if peak is not None and self.flops > 0 and wall > 0:
            out["mfu"] = self.flops / (wall * peak * max(1, self.chips))
            out["flops"] = self.flops
        return out

    def close(self) -> Dict[str, Any]:
        if self.closed:
            return {}
        self.closed = True
        out = self.summary_dict()
        with _LOCK:
            t = _TENANTS.setdefault(self.tenant, {
                "execute_s": 0.0, "compile_s": 0.0, "host_s": 0.0,
                "idle_s": 0.0, "wall_s": 0.0, "scopes": 0.0,
            })
            for k in _KINDS:
                t[k] += out[k]
            t["wall_s"] += out["wall_s"]
            t["scopes"] += 1
            stages = _STAGES.setdefault(self.tenant, {})
            for name, st in out["stages"].items():
                agg = stages.setdefault(name, {
                    "execute_s": 0.0, "host_s": 0.0, "idle_s": 0.0, "events": 0.0,
                })
                agg["execute_s"] += st["execute_s"]
                agg["host_s"] += st["host_s"]
                agg["idle_s"] += st["idle_s"]
                agg["events"] += st["events"]
        reg = _registry()
        if reg is not None:
            reg.observe("efficiency.execute_s", out["execute_s"])
            reg.observe("efficiency.compile_s", out["compile_s"])
            reg.observe("efficiency.host_s", out["host_s"])
            reg.observe("efficiency.idle_s", out["idle_s"])
            if "mfu" in out:
                # serving windows gauge apart from fits: a scoring burst must
                # not overwrite the last fit's roofline reading
                if self.label.startswith("serve"):
                    reg.gauge("efficiency.serve_mfu", out["mfu"])
                else:
                    reg.gauge("efficiency.mfu", out["mfu"])
        return out


class _NoopScope:
    """Shared do-nothing scope: the disabled-telemetry path holds this one
    instance (identity-pinned by tests, like telemetry._NOOP_SPAN)."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def close(self):
        return {}

    summary = None


_NOOP_SCOPE = _NoopScope()


class _ScopeCM:
    """Context manager wrapping one `_Scope`: sets the contextvar on entry,
    closes + restores on exit, and exposes the close summary as
    ``cm.summary`` for the caller's metrics stamp."""

    __slots__ = ("_scope", "summary")

    def __init__(self, scope: "_Scope"):
        self._scope = scope
        self.summary: Dict[str, Any] = {}

    def __enter__(self):
        self._scope._token = _SCOPE.set(self._scope)
        return self

    def __exit__(self, *exc):
        self.summary = self._scope.close()
        if self._scope._token is not None:
            _SCOPE.reset(self._scope._token)
        return False


def attribution_scope(
    label: str,
    *,
    tenant: Optional[str] = None,
    trace_id: Optional[str] = None,
):
    """Open one attribution window on this thread. Disabled telemetry (or a
    scope already active — scopes never nest) returns the shared no-op."""
    from .. import telemetry

    if not telemetry.enabled() or _SCOPE.get() is not None:
        return _NOOP_SCOPE
    if tenant is None:
        from ..scheduler.ledger import _current_tenant

        tenant = _current_tenant()
    return _ScopeCM(_Scope(label, str(tenant), trace_id))


def active() -> bool:
    """True when an attribution scope is open on this thread (the
    telemetry.py hooks probe this before building a timer)."""
    return _SCOPE.get() is not None


def note_flops(flops: float, *, chips: int = 1) -> None:
    """Record the active scope's analytic FLOP estimate (the
    `_solver_flop_estimate` hooks, docs/observability.md) — the MFU gauge's
    numerator. No-op outside a scope."""
    sc = _SCOPE.get()
    if sc is not None and flops and flops > 0:
        sc.flops += float(flops)
        sc.chips = max(sc.chips, int(chips))


# --------------------------------------------------------------- timers ----


class _Timer:
    """Times its body and attributes the wall to (kind, stage) on the
    active scope. Outermost-wins: nested timers attribute nothing."""

    __slots__ = ("kind", "stage", "_sc", "_t0", "_gap")

    def __init__(self, kind: str, stage: str):
        self.kind = kind
        self.stage = stage
        self._sc: Optional[_Scope] = None
        self._t0 = 0.0
        self._gap = 0.0

    def __enter__(self):
        sc = _SCOPE.get()
        if sc is not None and sc.depth == 0:
            self._sc = sc
            sc.depth += 1
            now = time.perf_counter()
            self._gap = max(0.0, now - sc.mark)
            self._t0 = now
        return self

    def __exit__(self, *exc):
        sc = self._sc
        if sc is not None:
            now = time.perf_counter()
            sc.depth -= 1
            sc.note(self.kind, self.stage, max(0.0, now - self._t0), self._gap)
            sc.mark = now
        return False


def device_wait_timer(stage: str) -> _Timer:
    return _Timer("execute_s", stage)


def host_section_timer(stage: str) -> _Timer:
    return _Timer("host_s", stage)


# -------------------------------------------------------- compile ledger ---


class _CompileEvent:
    """One jit entry-point execution, keyed (program, shape_key). First
    sighting = miss: the body's wall is recorded as compile time (known
    bias: it includes the first execution) and attributed to the active
    scope's compile kind. Later sightings = hit: counted, nothing timed.
    The ledger is process-wide — prewarm records with no scope active.
    ``cache_hit`` is readable after entry."""

    __slots__ = ("program", "shape_key", "cache_hit", "_t0", "_sc")

    def __init__(self, program: str, shape_key: str):
        self.program = program
        self.shape_key = str(shape_key)
        self.cache_hit = False
        self._t0 = 0.0
        self._sc: Optional[_Scope] = None

    def __enter__(self):
        key = (self.program, self.shape_key)
        with _COMPILE_LOCK:
            ent = _COMPILE.get(key)
            if ent is None:
                _COMPILE[key] = {"misses": 0.0, "hits": 0.0, "wall_s": 0.0}
                self.cache_hit = False
            else:
                self.cache_hit = True
        sc = _SCOPE.get()
        if self.cache_hit:
            if sc is not None:
                sc.compile_hits += 1
        else:
            self._t0 = time.perf_counter()
            if sc is not None and sc.depth == 0:
                self._sc = sc
                sc.depth += 1  # swallow inner waits: the miss wall wins
        return self

    def __exit__(self, *exc):
        key = (self.program, self.shape_key)
        reg = _registry()
        if self.cache_hit:
            with _COMPILE_LOCK:
                _COMPILE[key]["hits"] += 1
            if reg is not None:
                reg.inc("compile.hits")
            return False
        wall = max(0.0, time.perf_counter() - self._t0)
        with _COMPILE_LOCK:
            ent = _COMPILE[key]
            ent["misses"] += 1
            ent["wall_s"] += wall
        sc = self._sc
        if sc is not None:
            sc.depth -= 1
            sc.kinds["compile_s"] += wall
            sc.mark = time.perf_counter()
        cur = _SCOPE.get()
        if cur is not None:
            cur.compile_misses += 1
        if reg is not None:
            reg.inc("compile.misses")
            reg.observe("compile.wall_s", wall)
        return False


class _NoopCompileEvent:
    __slots__ = ()
    cache_hit = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP_COMPILE = _NoopCompileEvent()


def compile_event(program: str, shape_key: str):
    """Ledger a jit entry point (fit solve, PredictProgram prewarm rung,
    first-dispatch bucket). Returns the shared no-op when telemetry is
    disabled."""
    from .. import telemetry

    if not telemetry.enabled():
        return _NOOP_COMPILE
    return _CompileEvent(program, shape_key)


def compile_stats() -> Dict[str, Any]:
    """The compile ledger rolled up: totals + per-(program, shape) entries."""
    with _COMPILE_LOCK:
        entries = [
            {
                "program": prog, "shape_key": shape,
                "misses": int(ent["misses"]), "hits": int(ent["hits"]),
                "wall_s": ent["wall_s"],
            }
            for (prog, shape), ent in _COMPILE.items()
        ]
    return {
        "programs": len(entries),
        "misses": sum(e["misses"] for e in entries),
        "hits": sum(e["hits"] for e in entries),
        "wall_s": sum(e["wall_s"] for e in entries),
        "entries": entries,
    }


# --------------------------------------------------------------- roll-up ---


def tenant_time_splits() -> Dict[str, Dict[str, float]]:
    """Per-tenant device-time splits for `HbmLedger.tenant_usage()`'s
    merge (the sys.modules probe in scheduler/ledger.py): tenant ->
    {execute_s, compile_s, host_s, idle_s, wall_s, scopes}."""
    with _LOCK:
        return {t: dict(v) for t, v in _TENANTS.items()}


def summary() -> Dict[str, Any]:
    """The efficiency plane as one JSON-able dict (`ops_plane.report()
    ["efficiency"]`): per-tenant kind splits with per-stage detail and the
    top idle-time stage, plus the compile ledger and the configured peak."""
    with _LOCK:
        tenants: Dict[str, Any] = {}
        for name, totals in _TENANTS.items():
            stages = {
                s: dict(v) for s, v in (_STAGES.get(name) or {}).items()
            }
            top = None
            if stages:
                top = max(stages, key=lambda s: stages[s]["idle_s"])
            tenants[name] = dict(totals)
            tenants[name]["stages"] = stages
            tenants[name]["top_idle_stage"] = top
    return {
        "tenants": tenants,
        "compile": compile_stats(),
        "device_peak_flops": peak_flops(),
    }


def reset() -> None:
    """Drop all accumulated state (test isolation)."""
    with _LOCK:
        _TENANTS.clear()
        _STAGES.clear()
    with _COMPILE_LOCK:
        _COMPILE.clear()
