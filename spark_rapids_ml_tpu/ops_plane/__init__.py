#
# Ops plane: the live operability layer over the telemetry registry
# (docs/observability.md "Ops plane").
#
# PRs 11-12 made the library a resident service (serving plane, fit
# scheduler); the PR-2/PR-5 telemetry stack was still batch-shaped —
# cumulative counters, sinks read after the run. This package is the
# other half: answers WHILE the process is up.
#
#   * rolling windows  — telemetry.MetricsRegistry's time-bucketed rings
#                        (rate()/window_quantile(); configured by
#                        `config["metrics_bucket_seconds"]` x
#                        `config["metrics_bucket_count"]`);
#   * export           — Prometheus/JSON scrape surface + /healthz on an
#                        opt-in `SRML_METRICS_PORT` http thread, and
#                        rotating on-disk snapshots for headless runs;
#   * slo              — declarative `config["slo"]` specs evaluated by
#                        multi-window burn rate, feeding /healthz and the
#                        flight recorder;
#   * audit            — the bounded per-tenant decision log (every
#                        admission/demotion/preemption/eviction verdict);
#   * drift            — per-column ingest feature stats + PSI-vs-baseline
#                        (ROADMAP item 5's observability half);
#   * efficiency       — the attribution plane: per-tenant device-time
#                        splits (execute/compile/host/idle), the jit
#                        compile ledger, and roofline/MFU gauges
#                        (docs/observability.md "Efficiency plane").
#
# `report()` is the one-call roll-up — live (`ops_plane.report()`), scraped
# (`GET /snapshot`), or archived (`export.write_snapshot()` ->
# `python -m benchmark.opsreport <file>`).
#
from __future__ import annotations

import os
import socket
import time
from typing import Any, Dict, Optional

from . import audit, drift, efficiency, export, fleet, slo
from .export import ensure_server, start_server, stop_server, write_snapshot

__all__ = [
    "audit",
    "drift",
    "efficiency",
    "export",
    "fleet",
    "slo",
    "report",
    "ensure_server",
    "start_server",
    "stop_server",
    "write_snapshot",
]


def _serving_section() -> Dict[str, Any]:
    """The serving plane's per-tenant overload view (backpressure ladder
    levels, refusal counters, tenant latency summaries) — every live
    ScoringEngine's controller, via `serving.overload.serving_report`."""
    try:
        from ..serving.overload import serving_report

        return serving_report()
    except Exception:  # pragma: no cover - the report never fails a scrape
        return {"tenants": {}}


def report(
    *,
    tenant: Optional[str] = None,
    trace_id: Optional[str] = None,
    decision_limit: int = 256,
    cluster: bool = False,
) -> Dict[str, Any]:
    """The full ops-plane state as one JSON-able dict: health + SLO verdicts
    (evaluated fresh), rolling-window rates/quantiles, the decision log
    (optionally filtered to one tenant / trace), per-tenant HBM accounting
    from the shared ledger, drift stats, and the registry snapshot. The
    `meta` header (rank/host/pid/t/trace id) and `windows_detail` (the
    age-indexed window export) are what the fleet plane's offline merger
    keys on — staleness, dead-rank detection, and cross-rank window
    alignment (docs/observability.md "Fleet plane"). `cluster=True` adds
    the last merged LIVE cluster view (`fleet.cluster_report()`)."""
    from .. import diagnostics, telemetry
    from ..scheduler.ledger import global_ledger

    reg = telemetry.registry()
    health = slo.health(fresh=True)
    rank = diagnostics._rank()
    now = time.time()
    rep = {
        "t": now,
        "meta": {
            "rank": rank,
            "hostname": socket.gethostname(),
            "pid": os.getpid(),
            "t": now,
            "trace_id": diagnostics.trace_tags().get("trace_id"),
        },
        "health": {k: health[k] for k in ("healthy", "failing", "specs")},
        "slo": health["verdicts"],
        "windows": reg.windows_snapshot(),
        "windows_detail": reg.windows_export(),
        "decisions": audit.decisions(
            tenant=tenant, trace_id=trace_id, limit=decision_limit
        ),
        "decision_log": audit.stats(),
        "tenants": global_ledger().tenant_usage(),
        "drift": drift.last_stats(),
        "serving": _serving_section(),
        "efficiency": efficiency.summary(),
        "telemetry": reg.snapshot(),
    }
    if cluster:
        rep["cluster"] = fleet.cluster_report()
    return rep
