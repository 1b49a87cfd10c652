#!/usr/bin/env bash
#
# CI entry point (the reference's ci/test.sh analog: pre-merge fast suite vs
# nightly --runslow, ci/test.sh:20-57). Usage:
#   ci/test.sh            # pre-merge: lint + fast tests
#   ci/test.sh --nightly  # adds the large-scale --runslow tests
#   ci/test.sh --spark    # Spark barrier-stage integration lane (needs a
#                         # pyspark install; tests self-skip without one)
#
set -euo pipefail
cd "$(dirname "$0")/.."

# CI artifacts (analysis + regression verdicts) land side by side here
ARTIFACTS="${CI_ARTIFACT_DIR:-/tmp/srml_ci_artifacts}"
mkdir -p "$ARTIFACTS"

echo "== static analysis (AST lint: ci/analysis — compile, invariants, registries, lock discipline, imports)"
# the gate prints its own wall time against --time-budget; the verdict JSON
# (incl. wall_s + cache hit count) lands next to the regression verdict
python -m ci.analysis --json-out "$ARTIFACTS/analysis_verdict.json" --time-budget 60

echo "== perf regression gate (report-only; no BENCH_r*.json is checked in, so the verdict is no-data)"
python -m benchmark.regression --report-only --out "$ARTIFACTS/regression_verdict.json"

echo "== ops snapshot artifact (SLO verdicts + decision log + tenant accounting + efficiency attribution)"
python -m benchmark.opsreport --json --write "$ARTIFACTS/ops_snapshot.json" \
  --write-efficiency "$ARTIFACTS/efficiency_report.json" > /dev/null

echo "== fleet aggregation smoke (3-rank LocalRendezvous ops round; merged counters must equal the per-rank sum)"
# archives the merged cluster snapshot next to the verdict JSONs
# (docs/observability.md "Fleet plane")
python -m benchmark.bench_fleet --smoke --nranks 3 \
  --write "$ARTIFACTS/cluster_snapshot.json"

echo "== chaos smoke (kill one rank mid-solve; survivors must recover + post-mortem must name it)"
python ci/chaos_smoke.py

echo "== concurrency sanitizer lanes (SRML_LOCKCHECK=1 over the threaded families; report archived)"
SRML_LOCKCHECK=1 SRML_LOCKCHECK_REPORT="$ARTIFACTS/lockcheck_report.json" \
  python -m pytest tests/test_chaos.py tests/test_scheduler.py tests/test_serving.py \
    tests/test_ops_plane.py tests/test_lockcheck.py -q
python - "$ARTIFACTS/lockcheck_report.json" <<'PY'
import json, sys
rep = json.load(open(sys.argv[1]))
print(f"lockcheck: {len(rep['locks'])} locks, {len(rep['edges'])} edges, "
      f"{len(rep['inversions'])} inversion(s), {len(rep['long_holds'])} long hold(s)")
sys.exit(1 if rep["inversions"] else 0)  # zero-inversion acceptance gate
PY

echo "== numerics sanitizer lanes (SRML_NUMCHECK=1 over the solver/streaming/serving/segmented families; report archived)"
# test_recovery drives run_segmented_while, so the segment.* checkpoint
# boundary is exercised by the gate (test_numcheck's own segment trips are
# deliberately discarded by its snapshot/restore fixture); test_precision
# runs every bf16 solver family under the sanitizer (the mixed-precision
# acceptance: zero trips, no bf16 solver-state watermark)
SRML_NUMCHECK=1 SRML_NUMCHECK_REPORT="$ARTIFACTS/numcheck_report.json" \
  python -m pytest tests/test_kmeans.py tests/test_oocore.py tests/test_serving.py \
    tests/test_recovery.py tests/test_numcheck.py tests/test_precision.py -q
python - "$ARTIFACTS/numcheck_report.json" <<'PY'
import json, sys
rep = json.load(open(sys.argv[1]))
print(f"numcheck: {rep['checks']} boundary checks, {len(rep['trips'])} trip(s), "
      f"{len(rep['watermarks'])} watermarked stage(s)")
if rep["checks"] == 0:
    print("numcheck: 0 checks — the instrumented lanes did not exercise the hook")
    sys.exit(1)
sys.exit(1 if rep["trips"] else 0)  # zero-trip acceptance gate
PY

if [[ "${1:-}" == "--nightly" ]]; then
  echo "== nightly: full suite incl. large-scale slow tests"
  python -m pytest tests/ -q --runslow
  echo "== nightly: multichip dryrun"
  python __graft_entry__.py
elif [[ "${1:-}" == "--spark" ]]; then
  echo "== spark integration lane (real local[N] barrier stage)"
  python -c "import pyspark" 2>/dev/null || {
    echo "pyspark not installed - the pyspark lane will self-skip"; }
  python -m pytest tests/test_spark.py -q
else
  echo "== unit/parity tests (virtual 8-device CPU mesh)"
  python -m pytest tests/ -q
fi
echo "CI OK"
