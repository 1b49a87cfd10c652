#
# Perf-regression gate over the BENCH trajectory (docs/observability.md
# "Regression gate").
#
# The trajectory is a set of BENCH_r<NN>.json artifacts (bench.py's one-line
# JSON, bare or wrapped under a "parsed" key). No such artifact is checked
# in at present — the pre-ledger ones were retired with the machine that
# produced them — so the gate's verdict on this repo is "no-data" until
# records from the current machine exist. A collected trajectory that is
# never CHECKED lets a slowdown ship silently, and a cache regression that
# doubles ingest work can hide entirely inside unchanged wall time. This
# gate closes both holes:
#
#   * WALL-TIME LANE — the headline throughput geomean of the newest complete
#     run must stay within `--min-ratio` (default 0.8) of the trajectory
#     reference (median of prior complete runs WITH THE SAME lane
#     composition — a round that adds lanes to the geomean starts a new
#     geomean trajectory instead of being gated on the mix).
#   * PER-ALGO WALL LANES — records embedding per-lane values ("lanes",
#     added when kmeans_scale/knn joined the geomean) are also gated lane by
#     lane against each lane's OWN history; the first artifact carrying a
#     lane is that lane's trajectory start (skipped, never a false fail
#     against rounds that predate it).
#   * COUNTER LANES — telemetry counters embedded in the BENCH snapshot
#     (ingest/layout/placement/solve counts) are lower-is-better efficiency
#     invariants: the newest run failing `current <= tolerance * reference`
#     fails the lane even when wall time looks fine.
#   * LATENCY LANES — records embedding `latency_lanes` (serving p50/p99 ms,
#     added with the persistent serving plane) gate each value as a
#     LOWER-IS-BETTER lane against the median of its own trajectory at
#     `--max-latency-ratio` (default 1.5): a p99 blowup fails even when the
#     throughput lanes hide it. Same trajectory-start rule as the per-algo
#     wall lanes — the first artifact carrying a latency lane is skipped.
#
# Infra honesty: a run that did not finish (value 0.0 / INCOMPLETE — bench.py
# itself exited non-zero) carries no perf signal — those runs are excluded
# from the reference and, when the NEWEST run is incomplete, the verdict is
# "no-data" (exit 0): a failed run is not a perf regression. A lane with no
# reference data reports "skipped".
#
# Output: one machine-readable JSON verdict on stdout
#   {"verdict": "pass"|"fail"|"no-data", "lanes": [...], ...}
# Exit code: 1 on "fail" unless --report-only (the ci/ lane runs report-only
# until the trajectory carries enough telemetry-bearing rounds to be strict).
#
from __future__ import annotations

import argparse
import glob
import json
import os
import re
import statistics
import sys
from typing import Any, Dict, List, Optional, Tuple

# Counter lanes: (counter name, lower-is-better tolerance ratio). Chosen for
# work-amount invariants the multi-fit engine and ingest cache guarantee —
# the "cache regression doubles ingests" class. Tolerances are loose enough
# to absorb lane additions (a new bench lane adds real work) but a 2x blowup
# always fails.
DEFAULT_COUNTER_LANES: List[Tuple[str, float]] = [
    ("ingest.rows", 1.5),
    ("ingest.datasets", 1.5),
    ("ingest.chunks", 1.5),
    ("placement.device_put_calls", 1.5),
    ("sparse.csr_to_ell_calls", 1.5),
    ("fit.solves_sequential", 1.5),
    ("rendezvous.rounds", 1.5),
]


def load_bench_record(path: str) -> Dict[str, Any]:
    """A BENCH artifact's inner record: the round driver wraps bench.py's
    stdout line under "parsed"; accept the bare record (or a JSONL file whose
    last parseable line is the record) too, so fixtures and ad-hoc runs work."""
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except ValueError:
        doc = None
        for line in reversed(text.splitlines()):
            line = line.strip()
            if not line:
                continue
            try:
                doc = json.loads(line)
                break
            except ValueError:
                continue
        if doc is None:
            return {}
    if not isinstance(doc, dict):
        return {}
    inner = doc.get("parsed")
    if isinstance(inner, dict) and "value" in inner:
        return inner
    return doc if "value" in doc else {}


def is_complete(rec: Dict[str, Any]) -> bool:
    """A run carries perf signal only when it finished: positive value and
    not flagged INCOMPLETE (lanes missing from the emission)."""
    try:
        value = float(rec.get("value") or 0.0)
    except (TypeError, ValueError):
        return False
    return value > 0.0 and "INCOMPLETE" not in str(rec.get("unit", ""))


def _counters(rec: Dict[str, Any]) -> Dict[str, float]:
    tel = rec.get("telemetry")
    if isinstance(tel, dict) and isinstance(tel.get("counters"), dict):
        return {k: float(v) for k, v in tel["counters"].items()
                if isinstance(v, (int, float))}
    return {}


def _lanes(rec: Dict[str, Any]) -> Dict[str, float]:
    """Per-algo throughput values embedded in the record ("lanes", added
    when kmeans_scale/knn entered the geomean). Empty for older artifacts —
    which is exactly how the gate knows a lane's trajectory starts here."""
    lanes = rec.get("lanes")
    if isinstance(lanes, dict):
        return {k: float(v) for k, v in lanes.items()
                if isinstance(v, (int, float)) and float(v) > 0.0}
    return {}


def _latency_lanes(rec: Dict[str, Any]) -> Dict[str, float]:
    """Lower-is-better latency values embedded in the record
    ("latency_lanes", added when the serving lane joined — p50/p99 ms).
    Empty for older artifacts, which is how the gate knows a latency lane's
    trajectory starts here."""
    lanes = rec.get("latency_lanes")
    if isinstance(lanes, dict):
        return {k: float(v) for k, v in lanes.items()
                if isinstance(v, (int, float)) and float(v) > 0.0}
    return {}


def _lower_better_lane(
    name: str, kind: str, cur: float, ref: Optional[float], tolerance: float,
    skip_note: str = "counter absent on one side",
) -> Dict[str, Any]:
    """One lower-is-better lane verdict — the counter-lane machinery,
    generalized so latency lanes gate through the exact same rule
    (`current <= tolerance * reference`)."""
    if cur is None or ref is None or ref <= 0:
        return {
            "lane": name, "kind": kind, "status": "skipped",
            "current": cur, "reference": ref, "note": skip_note,
        }
    ratio = cur / ref
    return {
        "lane": name,
        "kind": kind,
        "direction": "lower-better",
        "current": cur,
        "reference": ref,
        "ratio": round(ratio, 4),
        "threshold": tolerance,
        "status": "pass" if ratio <= tolerance else "fail",
    }


def _geomean_lanes(rec: Dict[str, Any]) -> frozenset:
    """The lane names whose values entered the record's headline geomean —
    the COMPARABILITY key for the wall lane. bench.py embeds it explicitly
    ("geomean_lanes"); records without it (incl. the pre-lanes era) fall
    back to every embedded lane, and lane-less legacy records compare as
    the empty set (i.e. with each other), preserving pre-lane behavior.
    Keying on the embedded lane dict alone would let an OPTIONAL extra lane
    (BENCH_SPARSE/BENCH_OOCORE toggled on for one round) silently skip the
    headline gate even though the geomean composition never changed."""
    gl = rec.get("geomean_lanes")
    if isinstance(gl, (list, tuple)):
        return frozenset(str(x) for x in gl)
    return frozenset(_lanes(rec).keys())


def discover_trajectory(root: str, pattern: str = "BENCH_r*.json") -> List[str]:
    """BENCH artifacts in round order (numeric suffix sort, not lexical —
    r2 < r10)."""
    def round_key(p: str):
        m = re.search(r"_r(\d+)", os.path.basename(p))
        return (int(m.group(1)) if m else -1, p)

    return sorted(glob.glob(os.path.join(root, pattern)), key=round_key)


def run_gate(
    current: Dict[str, Any],
    history: List[Dict[str, Any]],
    *,
    min_ratio: float = 0.8,
    counter_lanes: Optional[List[Tuple[str, float]]] = None,
    max_latency_ratio: float = 1.5,
) -> Dict[str, Any]:
    """Compare `current` against the completed runs in `history`. Pure
    function of its inputs (the CLI wires files in); returns the verdict
    dict described in the module header."""
    if counter_lanes is None:
        counter_lanes = DEFAULT_COUNTER_LANES
    lanes: List[Dict[str, Any]] = []
    complete_hist = [r for r in history if is_complete(r)]

    if not is_complete(current):
        return {
            "verdict": "no-data",
            "reason": "newest run is incomplete (infra outage, not a perf signal)",
            "current_value": current.get("value"),
            "reference_runs": len(complete_hist),
            "lanes": [],
        }

    # -- wall-time lane: throughput geomean, higher is better --------------
    # The geomean is only comparable between runs with the SAME lane
    # composition: when a round ADDS lanes to the headline (kmeans_scale/knn
    # joining with the tiled distance core), its geomean is a different
    # statistic, and gating it against the old composition's median would
    # false-fail (or false-pass) on the mix, not on performance. Runs that
    # predate the "lanes" embed have no composition info — treated as
    # matching only other lane-less runs.
    cur_value = float(current["value"])
    cur_lanes = _lanes(current)
    comparable = [
        r for r in complete_hist
        if _geomean_lanes(r) == _geomean_lanes(current)
    ]
    if comparable:
        ref_value = statistics.median(float(r["value"]) for r in comparable)
        ratio = cur_value / ref_value if ref_value > 0 else float("inf")
        lanes.append({
            "lane": "throughput_geomean",
            "kind": "wall",
            "direction": "higher-better",
            "current": cur_value,
            "reference": ref_value,
            "ratio": round(ratio, 4),
            "threshold": min_ratio,
            "status": "pass" if ratio >= min_ratio else "fail",
        })
    elif complete_hist:
        lanes.append({
            "lane": "throughput_geomean",
            "kind": "wall",
            "current": cur_value,
            "reference": None,
            "status": "skipped",
            "note": "lane composition changed — this artifact starts the new "
                    "geomean trajectory; the per-lane gates carry the signal",
        })
    else:
        lanes.append({
            "lane": "throughput_geomean",
            "kind": "wall",
            "current": cur_value,
            "reference": None,
            "status": "skipped",
            "note": "no complete historical run to compare against",
        })

    # -- per-algo wall lanes: each lane gates against ITS OWN trajectory ---
    # A lane absent from every historical run starts its trajectory at the
    # current artifact (status "skipped", never a false fail against rounds
    # that predate the lane — e.g. kmeans_scale/knn joining at round N).
    for lane_name in sorted(cur_lanes):
        refs = [
            _lanes(r)[lane_name] for r in complete_hist if lane_name in _lanes(r)
        ]
        if not refs:
            lanes.append({
                "lane": f"lane:{lane_name}",
                "kind": "wall",
                "current": cur_lanes[lane_name],
                "reference": None,
                "status": "skipped",
                "note": "trajectory start: no historical run carries this lane",
            })
            continue
        ref_value = statistics.median(refs)
        ratio = cur_lanes[lane_name] / ref_value if ref_value > 0 else float("inf")
        lanes.append({
            "lane": f"lane:{lane_name}",
            "kind": "wall",
            "direction": "higher-better",
            "current": cur_lanes[lane_name],
            "reference": ref_value,
            "ratio": round(ratio, 4),
            "threshold": min_ratio,
            "status": "pass" if ratio >= min_ratio else "fail",
        })

    # -- latency lanes: p50/p99 upper bounds, lower is better --------------
    # Same machinery as the counter lanes (`current <= tolerance * ref`),
    # with the per-algo wall lanes' trajectory rule: each latency value
    # gates against the median of ITS OWN history, and the first artifact
    # carrying a lane starts that lane's trajectory (skipped).
    cur_lat = _latency_lanes(current)
    for lane_name in sorted(cur_lat):
        refs = [
            _latency_lanes(r)[lane_name]
            for r in complete_hist
            if lane_name in _latency_lanes(r)
        ]
        lanes.append(_lower_better_lane(
            f"latency:{lane_name}", "latency", cur_lat[lane_name],
            statistics.median(refs) if refs else None, max_latency_ratio,
            skip_note="trajectory start: no historical run carries this lane",
        ))

    # -- counter lanes: work-amount invariants, lower is better ------------
    # Reference = the NEWEST complete run that embedded a telemetry
    # snapshot, taken as one coherent set. Never assembled per-key across
    # runs: a counter that stopped being emitted two rounds ago would then
    # gate today's run against a stale reference while the wall lane
    # compares against the current median.
    cur_counters = _counters(current)
    ref_counters: Dict[str, float] = {}
    for r in reversed(complete_hist):
        if _counters(r):
            ref_counters = _counters(r)
            break
    for name, tolerance in counter_lanes:
        lanes.append(_lower_better_lane(
            name, "counter", cur_counters.get(name), ref_counters.get(name),
            tolerance,
        ))

    checked = [ln for ln in lanes if ln["status"] in ("pass", "fail")]
    failed = [ln for ln in lanes if ln["status"] == "fail"]
    verdict = "fail" if failed else ("pass" if checked else "no-data")
    return {
        "verdict": verdict,
        "current_value": cur_value,
        "reference_runs": len(complete_hist),
        "failed_lanes": [ln["lane"] for ln in failed],
        "lanes": lanes,
    }


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--root", default=None,
                    help="repo root holding BENCH_r*.json (default: this repo)")
    ap.add_argument("--pattern", default="BENCH_r*.json",
                    help="glob for trajectory artifacts under --root")
    ap.add_argument("--current", default=None,
                    help="explicit newest artifact (default: highest round in the trajectory)")
    ap.add_argument("--min-ratio", type=float, default=0.8,
                    help="wall lane: fail when current/reference drops below this")
    ap.add_argument("--counter-tolerance", type=float, default=None,
                    help="override every counter lane's tolerance ratio")
    ap.add_argument("--max-latency-ratio", type=float, default=1.5,
                    help="latency lanes: fail when current/reference exceeds this")
    ap.add_argument("--report-only", action="store_true",
                    help="always exit 0 (CI report lane); the verdict JSON still says fail")
    ap.add_argument("--out", default=None, help="also write the verdict JSON here")
    args = ap.parse_args(argv)

    root = args.root or os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = discover_trajectory(root, args.pattern)
    if args.current:
        current_path = args.current
        history_paths = [p for p in paths if os.path.abspath(p) != os.path.abspath(current_path)]
    elif paths:
        current_path, history_paths = paths[-1], paths[:-1]
    else:
        verdict = {"verdict": "no-data", "reason": f"no artifacts match {args.pattern} under {root}",
                   "lanes": []}
        out = json.dumps(verdict, indent=2)
        print(out)
        if args.out:
            with open(args.out, "w") as f:
                f.write(out + "\n")
        return 0

    lanes = DEFAULT_COUNTER_LANES
    if args.counter_tolerance is not None:
        lanes = [(name, args.counter_tolerance) for name, _ in lanes]
    verdict = run_gate(
        load_bench_record(current_path),
        [load_bench_record(p) for p in history_paths],
        min_ratio=args.min_ratio,
        counter_lanes=lanes,
        max_latency_ratio=args.max_latency_ratio,
    )
    verdict["current_artifact"] = os.path.basename(current_path)
    verdict["history_artifacts"] = [os.path.basename(p) for p in history_paths]
    out = json.dumps(verdict, indent=2)
    print(out)
    if args.out:
        with open(args.out, "w") as f:
            f.write(out + "\n")
    if verdict["verdict"] == "fail" and not args.report_only:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
