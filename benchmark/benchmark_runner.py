#
# Benchmark runner — dispatch to the per-algorithm benchmarks (the reference's
# benchmark_runner.py:38-50 registry shape).
#
#   python -m benchmark.benchmark_runner <algo> [--num_rows N --num_cols D ...]
#   python -m benchmark.benchmark_runner protocol --report out.csv
#
# `protocol` runs every algorithm at its reference-protocol config (BASELINE.md)
# scaled by --num_rows/--num_cols (defaults to the full 1M x 3k for the dense
# solvers; DBSCAN/UMAP/kNN run their own protocol sizes).
#
from __future__ import annotations

import sys

from .bench_approximate_nearest_neighbors import BenchmarkApproximateNearestNeighbors
from .bench_cv import BenchmarkCV
from .bench_dbscan import BenchmarkDBSCAN
from .bench_ingest import BenchmarkIngest
from .bench_kmeans import BenchmarkKMeans
from .bench_linear_regression import BenchmarkLinearRegression
from .bench_logistic_regression import BenchmarkLogisticRegression
from .bench_nearest_neighbors import BenchmarkNearestNeighbors
from .bench_oocore import BenchmarkOOCore
from .bench_pca import BenchmarkPCA
from .bench_random_forest import BenchmarkRandomForest
from .bench_scheduler import BenchmarkScheduler
from .bench_serving import BenchmarkServing
from .bench_umap import BenchmarkUMAP
from .utils import log

ALGORITHMS = {
    "cv": BenchmarkCV,
    "ingest": BenchmarkIngest,
    "oocore": BenchmarkOOCore,
    "scheduler": BenchmarkScheduler,
    "serving": BenchmarkServing,
    "pca": BenchmarkPCA,
    "kmeans": BenchmarkKMeans,
    "linear_regression": BenchmarkLinearRegression,
    "logistic_regression": BenchmarkLogisticRegression,
    "random_forest": BenchmarkRandomForest,
    "random_forest_classifier": BenchmarkRandomForest,
    "random_forest_regressor": BenchmarkRandomForest,
    "knn": BenchmarkNearestNeighbors,
    "nearest_neighbors": BenchmarkNearestNeighbors,
    "approximate_nearest_neighbors": BenchmarkApproximateNearestNeighbors,
    "dbscan": BenchmarkDBSCAN,
    "umap": BenchmarkUMAP,
}

# The full reference protocol (BASELINE.md): (algo, extra argv). Sizes come
# from --num_rows/--num_cols so the same list runs scaled-down smoke tests.
PROTOCOL = [
    ("pca", ["--k", "3"]),
    ("kmeans", ["--k", "1000", "--maxIter", "30"]),
    ("linear_regression", ["--config", "all"]),
    ("logistic_regression", ["--maxIter", "200", "--reg", "1e-5"]),
    ("random_forest", ["--task", "classification"]),
    ("random_forest", ["--task", "regression"]),
    ("nearest_neighbors", []),
    ("approximate_nearest_neighbors", []),
    ("approximate_nearest_neighbors", ["--algorithm", "cagra"]),
    ("dbscan", ["--num_rows", "40000", "--num_cols", "64"]),
    ("umap", ["--num_rows", "20000", "--num_cols", "64"]),
    ("umap", ["--num_rows", "100000", "--num_cols", "64"]),
]


def _refuse_off_tpu() -> None:
    """The protocol is a chip measurement: fail before any config runs when
    the framework's devices are not TPUs."""
    from spark_rapids_ml_tpu.parallel import device_platforms

    found = device_platforms()
    if found != ["tpu"]:
        raise SystemExit(
            f"benchmark_runner protocol: refusing to run on platform(s) {found}; "
            "the protocol measures the TPU"
        )


def _protocol_child(name: str, argv) -> None:
    """Body of one `protocol --isolate` child: this process owns the chip
    for one config."""
    _refuse_off_tpu()
    ALGORITHMS[name]().run(list(argv))


_PROTOCOL_CHILD = (
    "import sys; from benchmark.benchmark_runner import _protocol_child; "
    "_protocol_child(sys.argv[1], sys.argv[2:])"
)


def _run_protocol(rest) -> int:
    """Run every protocol config; returns the number that failed (the
    process exit code is non-zero when any did).

    Default: all configs in THIS process, which holds the chip throughout.
    With --isolate each config runs as its own child process under a time
    limit, so one that faults or hangs cannot take the rest down — the
    reference's time-limited per-algo loop (databricks/run_benchmark.sh:
    33-47). A chip belongs to one process at a time, so the isolating parent
    must never initialise a JAX backend: it imports the benchmark modules
    (numpy/argparse only at import) and nothing here touches jax — each
    child refuses a non-TPU platform itself. Keep it so."""
    import os
    import subprocess
    import time

    isolate = "--isolate" in rest
    rest = [a for a in rest if a != "--isolate"]
    n_failed = 0
    if not isolate:
        _refuse_off_tpu()
        for name, extra in PROTOCOL:
            log(f"=== protocol: {name} {' '.join(extra)}")
            # later flags win in argparse, so per-algo sizes in `extra`
            # override the shared scale flags passed on the command line
            try:
                ALGORITHMS[name]().run(rest + extra)
            except Exception as e:
                n_failed += 1
                log(f"=== protocol: {name} FAILED {type(e).__name__}: {e}")
        return n_failed
    time_limit = float(os.environ.get("BENCH_TIME_LIMIT", 3600))
    for name, extra in PROTOCOL:
        log(f"=== protocol: {name} {' '.join(extra)}")
        t0 = time.monotonic()
        try:
            rc = subprocess.run(
                [sys.executable, "-c", _PROTOCOL_CHILD, name, *rest, *extra],
                timeout=time_limit,
            ).returncode
        except subprocess.TimeoutExpired:
            n_failed += 1
            log(f"=== protocol: {name} TIMED OUT after {time_limit:.0f}s")
            continue
        if rc != 0:
            n_failed += 1
            log(f"=== protocol: {name} FAILED rc={rc} after {time.monotonic() - t0:.0f}s")
    return n_failed


def main(argv=None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] in ("-h", "--help"):
        print(f"usage: benchmark_runner <{'|'.join(sorted(set(ALGORITHMS)))}|protocol> [args]")
        return
    algo, rest = argv[0], argv[1:]
    if algo == "protocol":
        n_failed = _run_protocol(rest)
        if n_failed:
            raise SystemExit(f"benchmark_runner protocol: {n_failed} config(s) failed")
        return
    if algo not in ALGORITHMS:
        raise SystemExit(f"unknown algorithm {algo!r}; one of {sorted(set(ALGORITHMS))}")
    if algo == "random_forest_classifier":
        rest = ["--task", "classification"] + rest
    elif algo == "random_forest_regressor":
        rest = ["--task", "regression"] + rest
    ALGORITHMS[algo]().run(rest)


if __name__ == "__main__":
    main()
