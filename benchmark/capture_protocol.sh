#!/usr/bin/env bash
#
# One-command protocol capture on the chip.
# Usage: benchmark/capture_protocol.sh [tag]   (writes PROTOCOL_<tag>.csv)
#
# Runs the full 10-config protocol with per-config process isolation and a
# time limit (benchmark_runner --isolate), then walks the RandomForest
# ladder: the protocol config (50 trees / depth 13 / 128 bins at 1M x 3k)
# first, then decreasing depths until one completes — recording the deepest
# completing config. Exits non-zero when any protocol config failed or no
# RandomForest depth completed. One process holds the chip at a time: this
# script never touches it itself.
#
set -uo pipefail
cd "$(dirname "$0")/.."

TAG="${1:-capture}"
CSV="PROTOCOL_${TAG}.csv"
export BENCH_TIME_LIMIT="${BENCH_TIME_LIMIT:-2400}"
rc=0

echo "== protocol sweep -> ${CSV}"
python -m benchmark.benchmark_runner protocol --isolate --report "${CSV}" || rc=1

echo "== RF protocol ladder (classification 50 trees, 128 bins, 1M x 3k)"
rf_done=0
for depth in 13 12 11 10; do
  echo "== RF depth ${depth}"
  if timeout "${BENCH_TIME_LIMIT}" python -m benchmark.benchmark_runner \
      random_forest --task classification --num_rows 1000000 --num_cols 3000 \
      --numTrees 50 --maxDepth "${depth}" --maxBins 128 --report "${CSV}"; then
    echo "== RF depth ${depth} COMPLETED"
    rf_done=1
    break
  fi
  echo "== RF depth ${depth} failed; stepping down"
done
[ "${rf_done}" = 1 ] || rc=1

echo "== done (rc=${rc}); rows:"
cat "${CSV}"
exit "${rc}"
