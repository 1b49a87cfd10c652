#
# opsreport: render an ops-plane report — live from this process, or from a
# snapshot file written by `ops_plane.export.write_snapshot()` (the rotating
# `ops_snapshot.json` a headless run leaves behind, or the per-rank
# `ops_snapshot_rank_<r>.json` a flight-recorder dump rides with).
#
#   python -m benchmark.opsreport /path/ops_snapshot.json
#   python -m benchmark.opsreport snap.json --tenant tenant3
#   python -m benchmark.opsreport snap.json --trace-id ab12... --json
#   python -m benchmark.opsreport --write /tmp/ops_snapshot.json  # archive
#
# The human rendering answers the on-call question directly: which SLO is
# violated (burn rates and windows), which tenants are holding/holding-up
# HBM (byte-seconds, chip-seconds), and the decision-log entries — tenant,
# verdict, reason — for the filtered tenant/trace
# (docs/observability.md "Ops plane").
#
# Cluster mode (docs/observability.md "Fleet plane"):
#
#   python -m benchmark.opsreport --cluster /path/snapshot_dir --nranks 3
#   python -m benchmark.opsreport --cluster            # live merged view
#
# merges the per-rank `ops_snapshot*.json` files (dropping stale dead-rank
# data by their `meta` headers) and renders the cluster verdict, straggler
# lags, and the fleet tenant rollup — NAMING missing/stale ranks.
#
# Exit codes: 0 = healthy (or no SLOs configured), 1 = at least one SLO
# failing, 2 = snapshot unreadable, 3 = PARTIAL cluster (healthy so far as
# visible, but some rank snapshots missing or stale — a half-dead fleet is
# not a healthy one, and not an unreadable one either).
#
from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Optional

EXIT_HEALTHY = 0
EXIT_FAILING = 1
EXIT_UNREADABLE = 2
EXIT_PARTIAL = 3


def _fmt_burn(v: Optional[float]) -> str:
    return "-" if v is None else f"{v:.2f}"


def _fmt_bytes(v: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if abs(v) < 1024.0:
            return f"{v:,.1f}{unit}"
        v /= 1024.0
    return f"{v:,.1f}TiB"


def render(
    report: Dict[str, Any],
    *,
    tenant: Optional[str] = None,
    trace_id: Optional[str] = None,
    decision_limit: int = 20,
) -> str:
    lines: List[str] = []
    health = report.get("health") or {}
    verdicts = report.get("slo") or []
    ok = bool(health.get("healthy", True))
    lines.append(
        f"health: {'OK' if ok else 'FAILING'} "
        f"({health.get('specs', 0)} SLO spec(s))"
    )
    for v in verdicts:
        mark = "FAIL" if v.get("failing") else "ok"
        extra = ""
        if v.get("kind") == "latency":
            extra = f" threshold={v.get('threshold_s')}s objective={v.get('objective')}"
        elif v.get("kind") == "error_rate":
            extra = f" threshold={v.get('threshold')}"
        elif v.get("kind") == "gauge_ceiling":
            extra = f" value={v.get('value')} ceiling={v.get('ceiling')}"
        lines.append(
            f"  [{mark:>4}] {v.get('name')} ({v.get('kind')}): "
            f"burn fast={_fmt_burn(v.get('fast_burn'))}"
            f"/{v.get('fast_burn_threshold')} "
            f"({v.get('fast_window_s'):g}s), "
            f"slow={_fmt_burn(v.get('slow_burn'))}"
            f"/{v.get('slow_burn_threshold')} "
            f"({v.get('slow_window_s'):g}s){extra}"
        )
    tenants = report.get("tenants") or {}
    if tenants:
        lines.append("tenant HBM accounting:")
        for name in sorted(tenants):
            if tenant is not None and name != tenant:
                continue
            u = tenants[name]
            live = (
                f", live {_fmt_bytes(u['live_bytes'])} "
                f"across {int(u.get('live_reservations', 0))} claim(s)"
                if u.get("live_bytes")
                else ""
            )
            lines.append(
                f"  {name}: {_fmt_bytes(u.get('byte_seconds', 0.0))}·s, "
                f"{u.get('chip_seconds', 0.0):.3f} chip·s over "
                f"{int(u.get('reservations', 0))} reservation(s){live}"
            )
            dt = u.get("device_time")
            if dt:
                lines.append(
                    f"    device time: execute={dt.get('execute_s', 0.0):.3f}s "
                    f"compile={dt.get('compile_s', 0.0):.3f}s "
                    f"host={dt.get('host_s', 0.0):.3f}s "
                    f"idle={dt.get('idle_s', 0.0):.3f}s"
                )
    decisions = report.get("decisions") or []
    if tenant is not None:
        decisions = [d for d in decisions if d.get("tenant") == tenant]
    if trace_id is not None:
        decisions = [d for d in decisions if d.get("trace_id") == trace_id]
    scope = ""
    if tenant is not None:
        scope += f" tenant={tenant}"
    if trace_id is not None:
        scope += f" trace={trace_id}"
    lines.append(f"decision log{scope}: {len(decisions)} entr(ies)")
    for d in decisions[-max(0, decision_limit):]:
        reason = f" — {d['reason']}" if d.get("reason") else ""
        tid = f" trace={d['trace_id']}" if d.get("trace_id") else ""
        lines.append(
            f"  [{d.get('kind')}/{d.get('subsystem')}] "
            f"tenant={d.get('tenant')} {d.get('subject')}: "
            f"{d.get('verdict')}{reason}{tid}"
        )
    serving = (report.get("serving") or {}).get("tenants") or {}
    if serving:
        lines.append("serving overload (backpressure ladder):")
        for name in sorted(serving):
            if tenant is not None and name != tenant:
                continue
            s = serving[name]
            burn = s.get("burn")
            burn_s = f", burn={burn:.2f}" if burn is not None else ""
            p99 = s.get("e2e_p99_s")
            p99_s = f", e2e p99={p99 * 1e3:.1f}ms" if p99 is not None else ""
            lines.append(
                f"  {name}: level={s.get('level')}{burn_s}{p99_s} — "
                f"shed={int(s.get('shed_requests', 0))} "
                f"throttled={int(s.get('throttled_requests', 0))} "
                f"degraded={int(s.get('degraded_requests', 0))} over "
                f"{int(s.get('transitions', 0))} transition(s)"
            )
    drift = report.get("drift")
    if drift:
        psi = (
            f", psi_max={drift['psi_max']:.4f}" if "psi_max" in drift else ""
        )
        lines.append(
            f"ingest drift: {drift.get('rows', 0)} row(s) over "
            f"{len(drift.get('columns', []))} column(s){psi}"
        )
    eff = report.get("efficiency") or {}
    eff_tenants = eff.get("tenants") or {}
    if eff_tenants:
        lines.append("efficiency (attributed device time):")
        for name in sorted(eff_tenants):
            if tenant is not None and name != tenant:
                continue
            t = eff_tenants[name]
            wall = t.get("wall_s", 0.0)
            mfu = f", mfu={t['mfu']:.3f}" if t.get("mfu") is not None else ""
            top = t.get("top_idle_stage")
            top_s = f", top idle stage: {top}" if top else ""
            lines.append(
                f"  {name}: wall={wall:.3f}s "
                f"execute={t.get('execute_s', 0.0):.3f}s "
                f"compile={t.get('compile_s', 0.0):.3f}s "
                f"host={t.get('host_s', 0.0):.3f}s "
                f"idle={t.get('idle_s', 0.0):.3f}s{mfu}{top_s}"
            )
    comp = eff.get("compile") or {}
    if comp.get("programs"):
        lines.append(
            f"compile ledger: {comp.get('programs', 0)} program/shape "
            f"entr(ies), {comp.get('misses', 0)} miss(es) totalling "
            f"{comp.get('wall_s', 0.0):.3f}s, {comp.get('hits', 0)} hit(s)"
        )
    return "\n".join(lines)


def render_cluster(view: Dict[str, Any], issues: Dict[str, Any]) -> str:
    lines: List[str] = []
    n = view.get("nranks") or issues.get("nranks") or 0
    lines.append(
        f"cluster: {view.get('ranks_reporting', 0)}/{n} rank(s) reporting"
    )
    for key, label in (("missing", "missing"), ("stale", "stale"), ("unreadable", "unreadable")):
        bad = issues.get(key) or []
        if bad:
            lines.append(f"  {label} rank(s): {', '.join(str(r) for r in bad)}")
    for r in sorted(view.get("ranks") or {}):
        meta = view["ranks"][r]
        host = meta.get("host") or "?"
        lines.append(f"  rank {r}: host={host} pid={meta.get('pid')}")
    health = view.get("health") or {}
    ok = bool(health.get("healthy", True))
    lines.append(
        f"cluster health: {'OK' if ok else 'FAILING'} "
        f"({health.get('specs', 0)} SLO spec(s) over the merged window)"
    )
    for v in health.get("verdicts") or []:
        mark = "FAIL" if v.get("failing") else "ok"
        lines.append(
            f"  [{mark:>4}] {v.get('name')} ({v.get('kind')}): "
            f"burn fast={_fmt_burn(v.get('fast_burn'))}"
            f"/{v.get('fast_burn_threshold')}, "
            f"slow={_fmt_burn(v.get('slow_burn'))}"
            f"/{v.get('slow_burn_threshold')}"
        )
    strag = view.get("straggler") or {}
    lags = strag.get("lags_s") or {}
    if lags:
        lag_s = ", ".join(
            f"rank {r}={lags[r]*1e3:.1f}ms" for r in sorted(lags, key=lambda x: int(x))
        )
        slowest = strag.get("slowest")
        tail = f" (slowest: rank {slowest})" if slowest is not None else ""
        lines.append(f"straggler lags: {lag_s}{tail}")
    tenants = view.get("tenants") or {}
    pool = tenants.get("_pool") or {}
    if pool:
        lines.append(
            f"fleet chips: busy={pool.get('chips_busy', 0.0):g} "
            f"idle={pool.get('chips_idle', 0.0):g} "
            f"total={pool.get('chips_total', 0.0):g}"
        )
    named = {t: u for t, u in tenants.items() if t != "_pool"}
    if named:
        lines.append("fleet tenant rollup:")
        for name in sorted(named):
            u = named[name]
            lines.append(
                f"  {name}: {_fmt_bytes(u.get('byte_seconds', 0.0))}·s, "
                f"{u.get('chip_seconds', 0.0):.3f} chip·s, "
                f"chips_busy={u.get('chips_busy', 0.0):g}"
            )
            dt = u.get("device_time")
            if dt:
                lines.append(
                    f"    device time: execute={dt.get('execute_s', 0.0):.3f}s "
                    f"compile={dt.get('compile_s', 0.0):.3f}s "
                    f"host={dt.get('host_s', 0.0):.3f}s "
                    f"idle={dt.get('idle_s', 0.0):.3f}s"
                )
    if view.get("windows_error"):
        lines.append(f"window merge degraded: {view['windows_error']}")
    return "\n".join(lines)


def _cluster_main(args: Any) -> int:
    from spark_rapids_ml_tpu.ops_plane import fleet

    if args.snapshot is None:
        live = fleet.cluster_report()
        if not live.get("available"):
            print(
                "opsreport: no live cluster view (no ops round has merged yet)",
                file=sys.stderr,
            )
            return EXIT_UNREADABLE
        view = live
        issues: Dict[str, Any] = {
            "missing": view.get("missing") or [],
            "stale": [],
            "unreadable": [],
            "nranks": view.get("nranks"),
        }
    else:
        reports, issues = fleet.read_rank_snapshots(args.snapshot, nranks=args.nranks)
        if not reports:
            named = issues.get("stale") or issues.get("unreadable") or "none found"
            print(
                f"opsreport: no usable rank snapshots in {args.snapshot} "
                f"(stale/unreadable: {named})",
                file=sys.stderr,
            )
            return EXIT_UNREADABLE
        view = fleet.merge_reports(
            reports, expected=issues.get("nranks") or args.nranks
        )
    if args.write:
        with open(args.write, "w") as f:
            json.dump({"cluster": view, "issues": issues}, f, indent=2, default=str)
    if args.json:
        print(json.dumps({"cluster": view, "issues": issues}, default=str))
    else:
        print(render_cluster(view, issues))
    if not (view.get("health") or {}).get("healthy", True):
        return EXIT_FAILING
    partial = (
        (issues.get("missing") or [])
        or (issues.get("stale") or [])
        or (issues.get("unreadable") or [])
        or (view.get("missing") or [])
    )
    return EXIT_PARTIAL if partial else EXIT_HEALTHY


def main(argv: Optional[List[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="opsreport",
        description="render an ops-plane report (live, or from a snapshot file)",
    )
    p.add_argument("snapshot", nargs="?", default=None,
                   help="ops_snapshot.json path (omitted = this process's live state)")
    p.add_argument("--tenant", default=None, help="filter decisions/accounting to one tenant")
    p.add_argument("--trace-id", default=None, help="filter decisions to one trace")
    p.add_argument("--json", action="store_true", help="emit the raw report dict")
    p.add_argument("--decisions", type=int, default=20, help="decision-log entries rendered")
    p.add_argument("--write", default=None, metavar="PATH",
                   help="also archive the report as a rotating snapshot at PATH")
    p.add_argument("--write-efficiency", default=None, metavar="PATH",
                   help="archive just the efficiency section (attribution "
                        "splits + compile ledger) as JSON at PATH")
    p.add_argument("--cluster", action="store_true",
                   help="fleet mode: treat SNAPSHOT as a DIRECTORY of per-rank "
                        "ops_snapshot*.json files and render the merged "
                        "cluster view (omitted = this process's live merged "
                        "view); exit 3 names a partial cluster")
    p.add_argument("--nranks", type=int, default=None,
                   help="expected rank count for --cluster (missing ranks "
                        "are named; default: inferred from the snapshots)")
    args = p.parse_args(argv)

    if args.cluster:
        return _cluster_main(args)
    if args.snapshot is not None:
        try:
            with open(args.snapshot) as f:
                report = json.load(f)
        except (OSError, ValueError) as e:
            print(f"opsreport: cannot read {args.snapshot}: {e}", file=sys.stderr)
            return 2
    else:
        from spark_rapids_ml_tpu import ops_plane

        report = ops_plane.report(tenant=args.tenant, trace_id=args.trace_id)
        if args.write:
            from spark_rapids_ml_tpu.ops_plane import export

            export.write_snapshot(args.write)
    if args.write_efficiency:
        eff_doc = {
            "t": report.get("t"),
            "efficiency": report.get("efficiency") or {},
        }
        with open(args.write_efficiency, "w") as f:
            json.dump(eff_doc, f, indent=2, default=str)
    if args.json:
        print(json.dumps(report, default=str))
    else:
        print(render(report, tenant=args.tenant, trace_id=args.trace_id,
                     decision_limit=args.decisions))
    return 0 if (report.get("health") or {}).get("healthy", True) else 1


if __name__ == "__main__":
    sys.exit(main())
