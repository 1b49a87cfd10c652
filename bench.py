#
# Round benchmark: the reference protocol's three headline fit configs
# (BASELINE.md — PCA k=3, KMeans k=1000 maxIter=30, LogisticRegression
# maxIter=200 reg=1e-5) at the TRUE protocol scale 1M x 3k, on the real TPU.
#
# Prints ONE JSON line on stdout:
#   {"metric", "value", "unit", "vs_baseline"}
# value = geometric mean of fit throughput (rows/sec/chip) across the three
# algos; per-algo detail goes to stderr. The full 10-config suite lives in
# benchmark/ (python -m benchmark.benchmark_runner protocol).
#
# ONE process runs every lane on the chip it holds (a chip belongs to one
# process at a time; nothing here starts a child). The run refuses a
# non-TPU platform, names its device in the record, and exits non-zero when
# any lane failed — a dead run is a failed run, never `{"value": 0.0}` rc 0.
#
# Memory: X is 1M x 3000 f32 = 11.2 GiB, generated tile-wise DIRECTLY into a
# row-sharded HBM buffer (benchmark/gen_data.py) — peak = X + one 64k-row tile,
# inside a single v5e chip's 16 GB.
#
# Baseline normalization: the reference publishes a protocol + bar chart, no
# numbers (SURVEY.md §6). We normalize against A100-class per-algo assumptions
# on the 1M x 3k configs (2 workers): PCA 10 s, KMeans 60 s, LogReg 40 s
# => per-chip baselines {pca: 50k, kmeans: 8.3k, logreg: 12.5k} rows/sec/chip.
# vs_baseline = geomean(measured/baseline) — >1 beats the A100-class estimate.
#
from __future__ import annotations

import json
import os
import sys
import time
from typing import Optional

import numpy as np

N_ROWS = int(os.environ.get("BENCH_ROWS", 1_000_000))
N_COLS = int(os.environ.get("BENCH_COLS", 3000))
# kmeans_scale / knn joined the headline geomean with the shared tiled
# distance core (docs/performance.md "Tiled distance core"): the r01->r03
# KMeans scaling cliff lived exactly in these lanes and the gate could not
# see it while they carried no baseline. A100-class per-algo assumptions on
# the same 1M x 3k shape (2 workers), like the original three:
#   kmeans_scale = ONE fused assignment+accumulate pass at k=1000
#     (~2 s/pass on A100-class: the 60 s / 30-iteration KMeans assumption)
#     => 1M / (2 s x 2 chips) = 250k rows/sec/chip;
#   knn = exact kNN of 4096 queries against the 1M items at k=64
#     (NearestNeighborsMG-class ~25 s on 2 workers)
#     => 1M / (25 s x 2 chips) = 20k rows/sec/chip (item-scan throughput).
# serving joined the headline geomean with the persistent serving plane
# (docs/serving.md): mixed-size concurrent predict requests against a
# resident k=1000 model at the protocol width, coalesced up the bucket
# ladder by the ScoringEngine. Baseline: the reference serves through a
# pandas_udf re-dispatched per query batch — Arrow serialization + Python
# re-entry per micro-batch caps an A100-class chip well below its one-pass
# assignment rate (250k rows/s); at the protocol's mixed 1-512 row request
# sizes we assume ~1/5 of it => 50k rows/sec/chip scored.
BASELINES = {
    "pca": 50_000.0,
    "kmeans": 8_333.0,
    "logreg": 12_500.0,
    "kmeans_scale": 250_000.0,
    "knn": 20_000.0,
    "serving": 50_000.0,
    # mixed-precision solver lanes (docs/performance.md "Mixed-precision
    # solvers"): the solver_precision="bf16" contract measured end-to-end.
    # Baselines reuse the f32 siblings' A100-reference rates — the reference
    # has no bf16 solver mode, so the speedup shows up as a higher vs_baseline
    # ratio on the same yardstick.
    "kmeans_bf16": 8_333.0,
    "logreg_bf16": 12_500.0,
}
# the serving lanes run FIRST: they build their own small resident models
# and must not coexist with the ~12 GiB dense protocol block on a single
# v5e. serving_saturation leads — it retunes the telemetry window buckets
# for its fast closed loop and resets the registry on exit, so running it
# before every other lane keeps their counters out of the blast radius.
ALGOS = (
    "serving_saturation", "serving", "pca", "logreg", "logreg_bf16",
    "kmeans", "kmeans_bf16", "kmeans_scale", "knn",
)
# lanes that run on ONE local device by construction (the serving plane's
# registry/engine are single-device): their rows/sec is already per-chip —
# dividing by the mesh size would underreport them n_chips-fold on
# multi-chip rounds and false-fail the lane gate vs single-chip history
SINGLE_DEVICE_LANES = {
    "serving", "serving_saturation", "sched_contention", "fleet_scale",
}
KNN_QUERIES = int(os.environ.get("BENCH_KNN_QUERIES", 4096))
KNN_K = int(os.environ.get("BENCH_KNN_K", 64))
SERVE_REQUESTS = int(os.environ.get("BENCH_SERVE_REQUESTS", 256))
SERVE_K = int(os.environ.get("BENCH_SERVE_K", 1000))
SERVE_CONCURRENCY = int(os.environ.get("BENCH_SERVE_CONCURRENCY", 8))

# Optional sparse lane (BENCH_SPARSE=1): the reference tests_large scale shape
# (1e7 x 2200 at 0.1% density) streamed partition-parallel from
# benchmark/gen_data_distributed.py into padded ELL — the full CSR is never
# materialized driver-side. Reported as its own lane; NOT part of the
# headline geomean (BASELINES has no entry for it).
SPARSE_ALGO = "sparse_logreg"
SPARSE_ROWS = int(os.environ.get("BENCH_SPARSE_ROWS", 10_000_000))
SPARSE_COLS = int(os.environ.get("BENCH_SPARSE_COLS", 2200))
SPARSE_DENSITY = float(os.environ.get("BENCH_SPARSE_DENSITY", 0.001))

# Optional CV grid-sweep lane (BENCH_CV=1): a numFolds x grid CrossValidator
# fit through the multi-fit engine (benchmark/bench_cv.py) — reports
# solves/sec and ingest-count-per-CV-fit (1 under the engine). Own lane;
# NOT part of the headline geomean (no BASELINES entry).
CV_ALGO = "cv_sweep"
CV_ROWS = int(os.environ.get("BENCH_CV_ROWS", 200_000))
CV_COLS = int(os.environ.get("BENCH_CV_COLS", 500))
CV_FOLDS = int(os.environ.get("BENCH_CV_FOLDS", 3))
CV_GRID = int(os.environ.get("BENCH_CV_GRID", 4))

# Optional out-of-core streaming lane (BENCH_OOCORE=1): the same dataset fit
# resident and demoted to the streaming path (benchmark/bench_oocore.py) —
# reports streaming rows/sec, the streaming/resident ratio, and the measured
# ingest.overlap_fraction (the double-buffer acceptance gauge). Own lane;
# NOT part of the headline geomean until the lane history stabilizes
# (no BASELINES entry).
OOCORE_ALGO = "oocore_stream"
OOCORE_ROWS = int(os.environ.get("BENCH_OOCORE_ROWS", 400_000))
OOCORE_COLS = int(os.environ.get("BENCH_OOCORE_COLS", 500))
OOCORE_CHUNK = int(os.environ.get("BENCH_OOCORE_CHUNK", 65_536))

# Optional multi-tenant scheduler contention lane (BENCH_SCHED=1): N tenants
# with adversarial job sizes through one FitScheduler over the shared HBM
# ledger (benchmark/bench_scheduler.py, docs/scheduling.md) — reports ledger
# utilization, per-tenant queue-wait p50/p99, and preemption counts. Own
# lane; NOT part of the headline geomean until the lane history
# stabilizes (no BASELINES entry).
SCHED_ALGO = "sched_contention"
SCHED_TENANTS = int(os.environ.get("BENCH_SCHED_TENANTS", 4))
SCHED_ROWS = int(os.environ.get("BENCH_SCHED_ROWS", 60_000))
SCHED_COLS = int(os.environ.get("BENCH_SCHED_COLS", 32))

# Co-admission utilization lane (rides BENCH_SCHED=1): the same two
# half-mesh fits co-admitted onto disjoint chip windows by the 2-D ledger
# vs time-sliced (benchmark/bench_scheduler.run_coadmission_bench,
# docs/scheduling.md "2-D placement") — reports the aggregate rows/sec
# ratio and the chip-occupancy integral of both phases. Own lane;
# NOT part of the headline geomean until the lane history stabilizes (no
# BASELINES entry — the PR-10 per-lane trajectory gate picks it up).
SCHED_COADMIT_ALGO = "sched_coadmit"
SCHED_COADMIT_ROWS = int(os.environ.get("BENCH_SCHED_COADMIT_ROWS", 40_000))

# Optional fleet observability lane (BENCH_FLEET=1): the multi-host scaling
# sweep on the CPU SPMD harness — N LocalRendezvous ranks streaming work
# through lockstep rounds WITH periodic fleet ops rounds riding the control
# plane (benchmark/bench_fleet.py, docs/observability.md "Fleet plane").
# Reports aggregate rows/sec at the widest rank count (`fleet_scale`), the
# per-count curve as `fleet_scale_<n>` sub-lanes, and pool utilization vs
# tenant count as `fleet_util`. Own lanes; NOT part of the headline
# geomean until the lane history stabilizes (no BASELINES entry — the PR-10
# per-lane trajectory gate picks each lane up at its first artifact).
FLEET_ALGO = "fleet_scale"
FLEET_RANKS = tuple(
    int(n) for n in os.environ.get("BENCH_FLEET_RANKS", "1,2,3").split(",") if n
)
FLEET_ROWS = int(os.environ.get("BENCH_FLEET_ROWS", 50_000))


def bench_algos() -> tuple:
    extra: tuple = ()
    if os.environ.get("BENCH_SPARSE"):
        # sparse FIRST: its ELL tensors are freed when its runner returns,
        # BEFORE the ~12 GiB dense protocol block is generated — running it
        # last would stack both datasets on the chip and OOM a single v5e
        extra += (SPARSE_ALGO,)
    if os.environ.get("BENCH_CV"):
        # CV lane also ahead of the dense block, for the same HBM reason
        extra += (CV_ALGO,)
    if os.environ.get("BENCH_OOCORE"):
        # streaming lane ahead of the dense block too: its resident baseline
        # fit is freed before the protocol X lands
        extra += (OOCORE_ALGO,)
    if os.environ.get("BENCH_SCHED"):
        # contention lane ahead of the dense block for the same HBM reason
        # (its per-tenant datasets are freed when the scheduler drains)
        extra += (SCHED_ALGO, SCHED_COADMIT_ALGO)
    if os.environ.get("BENCH_FLEET"):
        # fleet lane first: pure host-side harness (numpy + thread barriers),
        # no device state to collide with anything that follows
        extra = (FLEET_ALGO,) + extra
    return extra + ALGOS

def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ----------------------------------------------------------------- lanes ----


def _time_fit(run, fetch, repeats=2) -> float:
    """Best-of-`repeats` wall-clock around `run` plus a device->host fetch of
    one result leaf (the completion fence)."""
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = run()
        np.asarray(fetch(out))
        best = min(best, time.perf_counter() - t0)
    return best


def bench_pca(X, w, mesh) -> float:
    from spark_rapids_ml_tpu.ops.pca import pca_fit, record_pca_fit

    fit = lambda X, w: pca_fit(X, w, k=3)  # noqa: E731  (its own programs: the gram, then the eigensolve)
    state = fit(X, w)
    np.asarray(state["components_"])  # compile + warm
    fit_s = _time_fit(lambda: fit(X, w), lambda s: s["components_"])
    record_pca_fit(state, k=3)  # outside the timer
    _log(f"pca: {fit_s:.2f}s fit")
    return N_ROWS / fit_s


def bench_kmeans(X, w, mesh) -> float:
    import jax

    from spark_rapids_ml_tpu.ops.kmeans import kmeans_fit

    k = 1000
    # random-row init (initMode=random protocol config). The rows are iid BY
    # CONSTRUCTION (gen_classification_device draws every row from the same
    # mixture, in tile order independent of label), so ONE contiguous k-row
    # block at a random offset is an equally random sample. Do NOT point this
    # at ordered/clustered data (e.g. a parquet dataset sorted by label) —
    # there a contiguous block is a degenerate init; sample rows instead.
    # (A fancy-index gather program on the 11 GiB X makes XLA materialize a
    # full copy — measured OOM.)
    rng = np.random.default_rng(1)
    r0 = int(rng.integers(0, max(1, X.shape[0] - k + 1)))
    centers0 = jax.jit(lambda X: jax.lax.dynamic_slice_in_dim(X, r0, k, 0))(X)
    np.asarray(centers0[:1])

    def run():
        # KMeans precision policy: 3-pass bf16 MXU (parallel/mesh.py dtype_scope)
        with jax.default_matmul_precision("BF16_BF16_F32_X3"):
            return kmeans_fit(
                X, w, centers0, mesh=mesh, max_iter=30, tol=1e-20, batch_rows=65536
            )

    np.asarray(run()["cluster_centers_"])  # compile + warm
    fit_s = _time_fit(run, lambda s: s["cluster_centers_"], repeats=1)
    _log(f"kmeans: {fit_s:.2f}s fit (k={k}, maxIter=30)")
    return N_ROWS / fit_s


def bench_kmeans_bf16(X, w, mesh) -> float:
    """The solver_precision="bf16" k-means lane, measured exactly as a user
    gets it: one-pass bf16-compute/f32-accumulate assignment + accumulation
    (distance-core fast path), final inertia at full precision — no ambient
    matmul-precision override. Distinct from the `kmeans` lane, which wraps
    its fit in the estimator's 3-pass-bf16 dtype_scope policy."""
    import jax

    from spark_rapids_ml_tpu.ops.kmeans import kmeans_fit

    k = 1000
    rng = np.random.default_rng(1)  # same init block as the kmeans lane
    r0 = int(rng.integers(0, max(1, X.shape[0] - k + 1)))
    centers0 = jax.jit(lambda X: jax.lax.dynamic_slice_in_dim(X, r0, k, 0))(X)
    np.asarray(centers0[:1])

    def run():
        return kmeans_fit(
            X, w, centers0, mesh=mesh, max_iter=30, tol=1e-20,
            batch_rows=65536, precision_mode="fast",
        )

    np.asarray(run()["cluster_centers_"])  # compile + warm
    fit_s = _time_fit(run, lambda s: s["cluster_centers_"], repeats=1)
    _log(f"kmeans_bf16: {fit_s:.2f}s fit (k={k}, maxIter=30, solver_precision=bf16)")
    return N_ROWS / fit_s


def bench_kmeans_scale(X, w, mesh) -> float:
    """The distance-core lane: ONE fused assignment + accumulate pass over
    the full 1M x 3k block against k=1000 centers — the exact shape of the
    r01->r03 scaling cliff, now measured in isolation so the regression gate
    sees the tiled core's contribution separately from init/convergence."""
    import jax

    from spark_rapids_ml_tpu.ops.kmeans import kmeans_fit

    k = 1000
    rng = np.random.default_rng(7)
    r0 = int(rng.integers(0, max(1, X.shape[0] - k + 1)))
    centers0 = jax.jit(lambda X: jax.lax.dynamic_slice_in_dim(X, r0, k, 0))(X)
    np.asarray(centers0[:1])

    def run():
        with jax.default_matmul_precision("BF16_BF16_F32_X3"):
            # max_iter=1, no final inertia pass: one assignment+accumulate
            # sweep + the center update, nothing else
            return kmeans_fit(
                X, w, centers0, mesh=mesh, max_iter=1, tol=1e-20,
                batch_rows=65536, final_inertia=False,
            )

    np.asarray(run()["cluster_centers_"])  # compile + warm
    fit_s = _time_fit(run, lambda s: s["cluster_centers_"], repeats=2)
    _log(f"kmeans_scale: {fit_s:.2f}s one-pass assignment (k={k})")
    return N_ROWS / fit_s


def bench_knn(X, w, mesh) -> float:
    """Exact kNN lane: 4096 replicated queries against the row-sharded 1M
    items at k=64 — the NearestNeighborsMG workload on the shared tiled
    top-k core. Reported as item-scan throughput (items / second / chip),
    the same normalization as the fit lanes."""
    import jax

    from spark_rapids_ml_tpu.ops.knn import exact_knn

    Q = jax.jit(lambda X: jax.lax.dynamic_slice_in_dim(X, 0, KNN_QUERIES, 0))(X)
    np.asarray(Q[:1])

    def run():
        return exact_knn(X, w > 0, Q, mesh=mesh, k=KNN_K)

    np.asarray(run()[0])  # compile + warm
    search_s = _time_fit(run, lambda out: out[0], repeats=2)
    _log(f"knn: {search_s:.2f}s kneighbors ({KNN_QUERIES} queries, k={KNN_K})")
    return N_ROWS / search_s


def bench_logreg(X, w, y_idx) -> float:
    from spark_rapids_ml_tpu import telemetry
    from spark_rapids_ml_tpu.ops.logistic import logistic_fit

    run = lambda: logistic_fit(  # noqa: E731
        X, y_idx, w, k=2, multinomial=False, lam_l2=1e-5,
        fit_intercept=True, standardize=True, max_iter=200, tol=1e-30,
    )
    state = run()
    np.asarray(state["coef_"])  # compile + warm
    fit_s = _time_fit(lambda: run(), lambda s: s["coef_"], repeats=1)
    telemetry.record_solver_result(  # outside the timer
        "logistic", n_iter=int(state["n_iter_"]), objective=float(state["objective_"])
    )
    _log(f"logreg: {fit_s:.2f}s fit (maxIter=200, tol=1e-30)")
    return N_ROWS / fit_s


def bench_logreg_bf16(X, w, y_idx) -> float:
    """The solver_precision="bf16" GLM lane: X·β / Xᵀr matvecs bf16-in with
    f32 accumulation (ops/logistic._dense_ops), L-BFGS state + line search +
    convergence scalars full precision — same protocol config as `logreg`."""
    from spark_rapids_ml_tpu import telemetry
    from spark_rapids_ml_tpu.ops.logistic import logistic_fit

    run = lambda: logistic_fit(  # noqa: E731
        X, y_idx, w, k=2, multinomial=False, lam_l2=1e-5,
        fit_intercept=True, standardize=True, max_iter=200, tol=1e-30,
        fast=True,
    )
    state = run()
    np.asarray(state["coef_"])  # compile + warm
    fit_s = _time_fit(lambda: run(), lambda s: s["coef_"], repeats=1)
    telemetry.record_solver_result(  # outside the timer
        "logistic", n_iter=int(state["n_iter_"]), objective=float(state["objective_"])
    )
    _log(f"logreg_bf16: {fit_s:.2f}s fit (maxIter=200, solver_precision=bf16)")
    return N_ROWS / fit_s


def bench_sparse_logreg(mesh) -> float:
    """Sparse scale-shape fit: stream gen_data_distributed partitions into
    ELL (chunked, no full-CSR materialization), binarize the target, fit the
    certified tests_large config (scale-only standardization, maxIter=60)."""
    from benchmark.gen_data_distributed import sparse_classification_ell
    from spark_rapids_ml_tpu.ops.logistic import logistic_fit_ell

    t0 = time.perf_counter()
    data = sparse_classification_ell(SPARSE_ROWS, SPARSE_COLS, SPARSE_DENSITY, 0, mesh)
    np.asarray(data["w"][:1])
    _log(f"sparse datagen+ingest: {time.perf_counter() - t0:.1f}s (k_max={data['k_max']})")

    run = lambda: logistic_fit_ell(  # noqa: E731
        data["values"], data["indices"], data["y"], data["w"],
        d=SPARSE_COLS, k=2, multinomial=False, lam_l2=1e-6,
        fit_intercept=True, standardize=True, max_iter=60, tol=1e-12,
    )
    state = run()
    np.asarray(state["coef_"])  # compile + warm
    fit_s = _time_fit(run, lambda s: s["coef_"], repeats=1)
    from spark_rapids_ml_tpu import telemetry

    telemetry.record_solver_result(  # outside the timer
        "sparse_logistic", n_iter=int(state["n_iter_"]), objective=float(state["objective_"])
    )
    _log(f"sparse_logreg: {fit_s:.2f}s fit ({SPARSE_ROWS}x{SPARSE_COLS} @ {SPARSE_DENSITY})")
    return SPARSE_ROWS / fit_s


def bench_cv_lane() -> float:
    """CrossValidator grid sweep through the multi-fit engine: one ingest +
    one layout for numFolds x grid solves (+ the refit). Reports rows
    processed across all solves per second; the engine counters go to
    stderr and ride the record's telemetry snapshot."""
    from benchmark.bench_cv import run_cv_fit

    out = run_cv_fit(CV_ROWS, CV_COLS, num_folds=CV_FOLDS, grid_size=CV_GRID)
    _log(
        f"cv_sweep: {out['fit']:.2f}s for {int(out['solves'])} solves "
        f"({out['solves_per_sec']:.2f} solves/s, {int(out['ingests'])} ingest(s), "
        f"{int(out['solves_batched'])} batched / "
        f"{int(out['solves_sequential'])} sequential)"
    )
    return out["solves"] * CV_ROWS / out["fit"]


def bench_oocore_lane() -> float:
    """Streaming-vs-resident fit over one host dataset: reports streaming
    rows/sec (the lane metric), the throughput ratio, the double-buffer
    overlap fraction, and the live parity delta (~1e-9). Counters ride the
    record's telemetry snapshot."""
    from benchmark.bench_oocore import run_oocore_fit

    out = run_oocore_fit(OOCORE_ROWS, OOCORE_COLS, chunk_rows=OOCORE_CHUNK)
    _log(
        f"oocore_stream: {out['stream_s']:.2f}s streamed vs "
        f"{out['resident_s']:.2f}s resident "
        f"(ratio {out['stream_vs_resident']:.2f}, "
        f"overlap {out['overlap_fraction']:.2f} over "
        f"{int(out['stream_chunks'])} chunks, "
        f"max_rel_diff {out['max_rel_diff']:.2e})"
    )
    return out["stream_rows_per_sec"]


def bench_scheduler_lane() -> float:
    """Multi-tenant contention lane (docs/scheduling.md): N tenants with
    adversarial sizes through one FitScheduler over the shared HBM ledger.
    Reports ledger utilization, per-tenant queue-wait p50/p99, and
    preemption/demotion counts; over-budget admissions are a correctness
    failure, not a slow lane. The lane metric is total fit rows/sec."""
    from benchmark.bench_scheduler import run_scheduler_bench

    out = run_scheduler_bench(SCHED_TENANTS, SCHED_ROWS, SCHED_COLS)
    _log(
        f"sched_contention: {out['wall_s']:.2f}s for {int(out['jobs'])} jobs "
        f"({out['rows_per_sec']:,.0f} rows/s, utilization "
        f"{out['utilization']:.2f}, queue-wait p50 {out['queue_wait_p50_s']*1e3:.1f}ms "
        f"/ p99 {out['queue_wait_p99_s']*1e3:.1f}ms, "
        f"{int(out['preemptions'])} preemption(s), "
        f"{int(out['demotions'])} demotion(s))"
    )
    if out["ledger_over_budget_admissions"]:
        raise RuntimeError(
            "sched_contention lane: ledger exceeded the budget at "
            f"{int(out['ledger_over_budget_admissions'])} admission(s)"
        )
    # report-only ops embed (SLO verdict + per-tenant byte-seconds): rides
    # the BENCH record's "ops" key, never the gated geomean
    return out["rows_per_sec"], None, {
        "slo": out.get("slo", {}),
        "tenant_byte_seconds": out.get("tenant_byte_seconds", {}),
    }


def bench_sched_coadmit_lane() -> tuple:
    """Co-admission utilization lane (docs/scheduling.md "2-D placement"):
    two half-mesh fits co-admitted onto disjoint chip windows vs the same
    fits time-sliced. The lane metric is concurrent aggregate fit rows/sec;
    the rows/sec ratio and the chip-occupancy integrals ride the record's
    report-only `ops` embed. Cross-placement result divergence is a
    correctness failure, not a slow lane."""
    from benchmark.bench_scheduler import run_coadmission_bench

    out = run_coadmission_bench(SCHED_COADMIT_ROWS, SCHED_COLS)
    _log(
        f"sched_coadmit: {out['wall_concurrent_s']:.2f}s concurrent vs "
        f"{out['wall_sliced_s']:.2f}s time-sliced "
        f"(rows/s ratio {out['rows_per_sec_ratio']:.2f}, occupancy "
        f"{out['avg_chips_concurrent']:.1f} vs {out['avg_chips_sliced']:.1f} "
        f"avg chips of {int(out['pool_chips'])}, "
        f"max_abs_diff {out['max_abs_diff']:.1e})"
    )
    if out["max_abs_diff"] != 0.0:
        raise RuntimeError(
            "sched_coadmit lane: co-admitted results differ from time-sliced "
            f"(max_abs_diff={out['max_abs_diff']})"
        )
    return out["rows_per_sec_concurrent"], None, {
        "rows_per_sec_ratio": round(out["rows_per_sec_ratio"], 3),
        "rows_per_sec_sliced": round(out["rows_per_sec_sliced"], 1),
        "occupancy": {
            "pool_chips": out["pool_chips"],
            "avg_chips_concurrent": round(out["avg_chips_concurrent"], 2),
            "avg_chips_sliced": round(out["avg_chips_sliced"], 2),
            "peak_chips_concurrent": out["peak_chips_concurrent"],
            "peak_chips_sliced": out["peak_chips_sliced"],
            "chip_seconds_concurrent": round(out["chip_seconds_concurrent"], 3),
            "chip_seconds_sliced": round(out["chip_seconds_sliced"], 3),
            "ratio": round(out["occupancy_ratio"], 3),
        },
    }


def bench_fleet_lane() -> tuple:
    """Fleet observability lane (docs/observability.md "Fleet plane"): the
    multi-host scaling sweep — aggregate rows/sec with the piggybacked ops
    rounds riding the control plane — plus the utilization-vs-tenants sweep
    over the 2-D ledger rollup. The lane metric is rows/sec at the widest
    rank count; each rank count's value and the utilization number ride
    their own lanes so the per-lane trajectory gate sees the curve.
    A failed ops round here is a correctness failure, not a slow lane: the
    plane's whole contract is that aggregation never breaks the fit."""
    from benchmark.bench_fleet import (
        run_fleet_scaling_bench,
        run_fleet_utilization_bench,
    )

    out = run_fleet_scaling_bench(FLEET_RANKS, FLEET_ROWS)
    util = run_fleet_utilization_bench()
    _log(
        f"fleet_scale: {out['rows_per_sec']:,.0f} rows/s aggregate at "
        f"{int(out['nranks'])} ranks (curve "
        + ", ".join(f"n={k}: {v:,.0f}" for k, v in out["scale"].items())
        + f"), {int(out['ops_rounds'])} ops round(s), "
        f"{int(out['ops_rounds_failed'])} failed; utilization "
        f"{util['utilization']:.2f} at {int(util['tenants'])} tenants over "
        f"{int(util['pool_chips'])} chips"
    )
    if out["ops_rounds_failed"]:
        raise RuntimeError(
            f"fleet_scale lane: {int(out['ops_rounds_failed'])} ops round(s) "
            "failed on a healthy harness"
        )
    # per-count scaling curve + pool utilization: own higher-better
    # trajectory lanes (no BASELINES entries — never in the geomean)
    sub_lanes = {f"fleet_scale_{n}": v for n, v in out["scale"].items()}
    sub_lanes["fleet_util"] = util["utilization"]
    return out["rows_per_sec"], None, {
        "sub_lanes": sub_lanes,
        "ops_rounds": out["ops_rounds"],
        "ranks_reporting": out.get("ranks_reporting"),
        "cluster_healthy": out.get("cluster_healthy"),
        "utilization": util["sweep"],
    }


def bench_serving_lane() -> tuple:
    """Serving-plane lane (docs/serving.md): mixed-size concurrent predict
    requests against a resident k=SERVE_K model at the protocol width through
    the ScoringEngine (admission + ladder prewarm + coalescing). Returns
    (rows scored per second, {p50/p99 latency ms}) — the latency dict rides
    the BENCH record's `latency_lanes` embed, which benchmark/regression.py
    gates as LOWER-IS-BETTER lanes (a p99 blowup fails even when throughput
    hides it)."""
    from benchmark.bench_serving import run_serving_bench

    out = run_serving_bench(
        n_cols=N_COLS, k=SERVE_K,
        n_requests=SERVE_REQUESTS, concurrency=SERVE_CONCURRENCY,
    )
    _log(
        f"serving: {out['qps']:.1f} qps, p50 {out['p50_ms']:.2f}ms / "
        f"p99 {out['p99_ms']:.2f}ms, {out['rows_per_sec']:,.0f} rows/s "
        f"({int(out['coalesced_batches'])}/{int(out['batches'])} batches "
        f"coalesced, {int(out['prewarmed_programs'])} rungs prewarmed, "
        f"max_abs_diff {out['max_abs_diff']:.1e})"
    )
    if out["max_abs_diff"] != 0.0:
        # coalesced != solo is a correctness failure, not a slow lane
        raise RuntimeError(
            f"serving lane: coalesced responses differ from solo predicts "
            f"(max_abs_diff={out['max_abs_diff']})"
        )
    # QPS rides the record's "lanes" as its own higher-better trajectory
    # lane (no BASELINES entry — not in the geomean; rows/sec is the
    # headline serving value, QPS the request-rate view of the same run)
    return out["rows_per_sec"], {
        "serving_p50_ms": round(out["p50_ms"], 3),
        "serving_p99_ms": round(out["p99_ms"], 3),
    }, {
        "sub_lanes": {"serving_qps": out["qps"]},
        # report-only ops embed, same contract as the scheduler lane's
        "slo": out.get("slo", {}),
        "tenant_byte_seconds": out.get("tenant_byte_seconds", {}),
    }


def bench_saturation_lane() -> tuple:
    """Serving saturation lane (docs/serving.md "Overload & backpressure"):
    a chaos `burst:stage=serve` plan ramps offered load past the measured
    plateau and the closed loop — deadline admission, bounded queue, the
    per-tenant backpressure ladder, adaptive batching — must degrade
    gracefully. The runner's hard gates (zero over-deadline dispatches,
    deadline-bounded served p99, goodput within a factor of the plateau,
    every ladder transition audited) raise here, so a graceful-overload
    regression is a FAILED lane, not a slower number. Lane value: rows/sec
    of goodput sustained UNDER the burst; the served p99 rides the record's
    `latency_lanes` embed (lower-is-better gate)."""
    from benchmark.bench_saturation import run_saturation_bench

    out = run_saturation_bench()
    _log(
        f"serving_saturation: plateau {out['plateau_rows_per_sec']:,.0f} rows/s, "
        f"burst offered {out['burst_offered_rows_per_sec']:,.0f} -> served "
        f"{out['burst_rows_per_sec']:,.0f} rows/s (p99 {out['burst_p99_ms']:.0f}ms, "
        f"deadline {out['deadline_ms']:.0f}ms), recovered to "
        f"{out['recover_rows_per_sec']:,.0f} rows/s at level "
        f"{out['final_level']!r} in {out['recover_wait_s']:.1f}s; "
        f"{int(out['shed_requests'])} shed / {int(out['throttled_requests'])} "
        f"throttled / {int(out['rejected_requests'])} rejected / "
        f"{int(out['expired_requests'])} expired, {int(out['transitions'])} "
        f"audited transition(s) [{', '.join(out['audited_verdicts'])}]"
    )
    failed = [n for n, g in out["gates"].items() if not g["ok"]]
    if failed:
        raise RuntimeError(
            "serving_saturation gates failed: "
            + "; ".join(f"{n}: {out['gates'][n]['detail']}" for n in failed)
        )
    return out["burst_rows_per_sec"], {
        "saturation_p99_ms": round(out["burst_p99_ms"], 3),
    }, {
        # report-only ops embed: the gate verdicts + ladder evidence
        "gates": {n: g["ok"] for n, g in out["gates"].items()},
        "audited_verdicts": out["audited_verdicts"],
        "transitions": out["transitions"],
    }


def run_lanes(device: dict) -> int:
    """Generate data once, run every lane in THIS process, print the one JSON
    record. A lane that raises is logged and the rest still run, but the
    return code is then non-zero — a failed lane fails the run."""
    from benchmark.gen_data import gen_classification_device
    from spark_rapids_ml_tpu import telemetry
    from spark_rapids_ml_tpu.parallel import get_mesh

    # Registry telemetry (counters/gauges/span aggregates) is host-side and
    # cheap — enable it so the BENCH emission carries the per-stage snapshot.
    # Per-iteration convergence tracing stays OFF unless the env asks: a host
    # callback per solver iteration stalls the device program every
    # iteration and would poison the timings.
    telemetry.enable()

    mesh = get_mesh()
    n_chips = int(mesh.devices.size)

    dense: dict = {}

    def dense_data() -> dict:
        """Generate the dense protocol block LAZILY, on the first dense
        runner — so the sparse lane (which runs first) never coexists with
        the ~12 GiB dense X on the chip."""
        if not dense:
            t0 = time.perf_counter()
            _log(f"generating {N_ROWS}x{N_COLS} dataset tile-wise ON DEVICE...")
            # single chip: plain (uncommitted-sharding) arrays — a committed
            # NamedSharding makes Shardy insert a full input-resharding copy of
            # X in downstream programs (11 GiB here), while GSPMD on a 1-device
            # mesh needs no sharding annotations at all
            X, y_idx, w = gen_classification_device(
                N_ROWS, N_COLS, n_classes=2, mesh=mesh if n_chips > 1 else None
            )
            np.asarray(w[:1])  # force materialization for honest phase timing
            _log(f"datagen: {time.perf_counter() - t0:.1f}s")
            dense.update(X=X, y_idx=y_idx, w=w)
        return dense

    runners = {
        SPARSE_ALGO: lambda: bench_sparse_logreg(mesh),
        CV_ALGO: lambda: bench_cv_lane(),
        OOCORE_ALGO: lambda: bench_oocore_lane(),
        SCHED_ALGO: lambda: bench_scheduler_lane(),
        SCHED_COADMIT_ALGO: lambda: bench_sched_coadmit_lane(),
        FLEET_ALGO: lambda: bench_fleet_lane(),
        "serving_saturation": lambda: bench_saturation_lane(),
        "serving": lambda: bench_serving_lane(),
        "pca": lambda: bench_pca(dense_data()["X"], dense_data()["w"], mesh),
        "logreg": lambda: bench_logreg(
            dense_data()["X"], dense_data()["w"], dense_data()["y_idx"]
        ),
        "logreg_bf16": lambda: bench_logreg_bf16(
            dense_data()["X"], dense_data()["w"], dense_data()["y_idx"]
        ),
        "kmeans": lambda: bench_kmeans(dense_data()["X"], dense_data()["w"], mesh),
        "kmeans_bf16": lambda: bench_kmeans_bf16(
            dense_data()["X"], dense_data()["w"], mesh
        ),
        "kmeans_scale": lambda: bench_kmeans_scale(
            dense_data()["X"], dense_data()["w"], mesh
        ),
        "knn": lambda: bench_knn(dense_data()["X"], dense_data()["w"], mesh),
    }
    from spark_rapids_ml_tpu.ops_plane import efficiency as _eff

    def _eff_totals() -> dict:
        # process-cumulative attribution totals (all tenants) + the compile
        # ledger — per-lane deltas of these ride the BENCH record
        tot = {"execute_s": 0.0, "compile_s": 0.0, "host_s": 0.0, "idle_s": 0.0}
        for split in _eff.tenant_time_splits().values():
            for k in tot:
                tot[k] += float(split.get(k, 0.0))
        comp = _eff.compile_stats()
        tot["compile_misses"] = float(comp["misses"])
        tot["compile_hits"] = float(comp["hits"])
        tot["compile_wall_s"] = float(comp["wall_s"])
        return tot

    results: dict = {}
    latency_lanes: dict = {}
    ops_lanes: dict = {}
    failed: list = []
    for name in bench_algos():
        _log(f"bench lane {name}: start")
        try:
            eff_before = _eff_totals()
            out = runners[name]()
        except Exception as e:  # the remaining lanes still run; the run FAILS
            failed.append(name)
            _log(f"bench[{name}] FAILED: {type(e).__name__}: {e}")
            continue
        # a lane may return (value, latency_dict[, ops_dict]): latency values
        # ride the record's `latency_lanes` embed; the ops dict (SLO verdict,
        # per-tenant byte-seconds, extra `sub_lanes`) rides report-only
        # under `ops`
        latency = ops = None
        if isinstance(out, tuple):
            out, latency, ops = (out + (None,))[:3]
        results[name] = float(out if name in SINGLE_DEVICE_LANES else out / n_chips)
        if latency:
            latency_lanes.update({k: float(v) for k, v in latency.items()})
        ops = dict(ops or {})
        # a lane's extra higher-better trajectory lanes (serving_qps, the
        # fleet curve): no BASELINES entries — never in the geomean
        results.update({k: float(v) for k, v in ops.pop("sub_lanes", {}).items()})
        # the lane's efficiency delta (execute/compile/host/idle split plus
        # compile-ledger movement), report-only under `ops` — regression.py
        # never reads it. MFU rides along when a peak spec is configured
        # (last attributed scope's gauge).
        eff_after = _eff_totals()
        eff_delta = {k: eff_after[k] - eff_before[k] for k in eff_after}
        if any(v_ > 0 for v_ in eff_delta.values()):
            if _eff.peak_flops() is not None:
                gauges = telemetry.snapshot().get("gauges", {})
                for g in ("efficiency.mfu", "efficiency.serve_mfu"):
                    if g in gauges:
                        eff_delta[g.split(".", 1)[1]] = gauges[g]
            ops["efficiency"] = eff_delta
        if ops:
            ops_lanes[name] = ops
        _log(f"bench lane {name}: end")
    # per-stage telemetry snapshot (HBM watermark, solver iterations, span
    # aggregates) embedded in the BENCH JSON line
    telemetry.record_device_memory()
    snap = telemetry.snapshot()
    # precision provenance: which distance kernel actually ran and the
    # session's solver_precision default — embedded so every BENCH record is
    # interpretable without the stderr log
    from spark_rapids_ml_tpu.core import config as _srml_config
    from spark_rapids_ml_tpu.ops.distance import kernel_mode as _kernel_mode

    snap["precision"] = {
        "distance_kernel_mode": _kernel_mode(),
        "solver_precision": _srml_config["solver_precision"],
    }
    emit(results, snap, latency_lanes, ops_lanes, device=device, failed=failed)
    return 1 if failed else 0


def emit(
    results: dict,
    telemetry_snap: Optional[dict] = None,
    latency_lanes: Optional[dict] = None,
    ops_lanes: Optional[dict] = None,
    device: Optional[dict] = None,
    failed: Optional[list] = None,
) -> None:
    """The one stdout JSON line.
    The headline BASELINES algos enter the geomean; extra lanes
    (sparse_logreg, cv_sweep, oocore_stream) are logged to stderr and still
    ride the record's "lanes" embed, which carries EVERY finite per-lane
    value for benchmark/regression.py's per-lane gates ("geomean_lanes"
    names the subset that formed the geomean — the gate's comparability
    key). `telemetry_snap` is the same counters/gauges/span-aggregate dict
    `telemetry.snapshot()` returns in-process (docs/observability.md).
    `device` names what the run executed on (platform, kind, count) and
    `failed` the lanes that raised — a record with failed lanes comes from a
    run that exited non-zero."""
    for name, v in results.items():
        if name not in BASELINES and v and np.isfinite(v):
            _log(f"{name}: {v:,.0f} rows/sec/chip (no baseline; excluded from geomean)")
    ok = {k: v for k, v in results.items() if k in BASELINES and v and np.isfinite(v)}
    if ok:
        geo = float(np.exp(np.mean([np.log(v) for v in ok.values()])))
        geo_vs = float(np.exp(np.mean([np.log(ok[k] / BASELINES[k]) for k in ok])))
    else:
        geo, geo_vs = 0.0, 0.0
    missing = [a for a in ALGOS if a not in ok]
    unit = (
        f"rows/sec/chip (geomean of PCA k=3 / KMeans k=1000 / LogReg maxIter=200 / "
        f"their solver_precision=bf16 lanes / "
        f"KMeans-scale 1-pass k=1000 / kNN q={KNN_QUERIES} k={KNN_K} / "
        f"Serving {SERVE_REQUESTS}req k={SERVE_K} "
        f"on {N_ROWS // 1000}k x {N_COLS}, f32"
        + (f"; INCOMPLETE, missing {'+'.join(missing)}" if missing else "")
        + ")"
    )
    for name, v in ok.items():
        _log(f"{name}: {v:,.0f} rows/sec/chip (baseline {BASELINES[name]:,.0f}; {v / BASELINES[name]:.1f}x)")
    record = {
        "metric": "classical_ml_fit_throughput_geomean",
        "value": round(geo, 1),
        "unit": unit,
        "vs_baseline": round(geo_vs, 3),
        # per-lane values (baseline lanes AND extras): benchmark/regression.py
        # gates each lane against ITS OWN trajectory — the first artifact
        # carrying a lane starts that lane's history instead of false-failing
        # against rounds that predate it
        "lanes": {
            name: round(v, 1)
            for name, v in results.items()
            if v and np.isfinite(v)
        },
        # which of those lanes entered the headline geomean: the regression
        # gate keys geomean COMPARABILITY on this set, so toggling an
        # optional extra lane (BENCH_SPARSE/BENCH_OOCORE) cannot silently
        # skip the headline gate
        "geomean_lanes": sorted(ok),
    }
    if device:
        record["device"] = device
    if failed:
        record["failed"] = list(failed)
    if latency_lanes:
        # p50/p99 serving latencies: benchmark/regression.py gates each as a
        # LOWER-IS-BETTER lane against its own trajectory, so a p99 blowup
        # fails even when the throughput lanes look fine
        record["latency_lanes"] = {k: float(v) for k, v in latency_lanes.items()}
    if ops_lanes:
        # per-lane ops embeds (end-of-run SLO verdict + per-tenant
        # byte-seconds): REPORT-ONLY — the regression gate never reads them
        record["ops"] = ops_lanes
    if telemetry_snap:
        record["telemetry"] = telemetry_snap
    print(json.dumps(record), flush=True)


def main() -> int:
    from spark_rapids_ml_tpu.parallel import default_devices, device_platforms

    devices = default_devices()
    found = device_platforms()
    if found != ["tpu"]:
        _log(
            f"bench: refusing to run — every lane is a TPU measurement, found "
            f"platform(s) {found} ({devices[0].device_kind!r} x {len(devices)})"
        )
        return 2
    return run_lanes({
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    })


if __name__ == "__main__":
    sys.exit(main())
