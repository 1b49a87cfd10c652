#
# Core execution framework: everything shared by all algorithms.
#
# This is the TPU-native re-design of the reference's L5 (reference core.py, 1661
# LoC): `_CumlCaller`/`_CumlEstimator`/`_CumlModel`. The reference's shape —
# driver builds a barrier RDD of pandas UDF tasks, one per GPU, each task
# bootstraps NCCL and calls a cuML MG solver — collapses on TPU into a
# single-controller SPMD program: the features are laid out once as a row-sharded
# global `jax.Array` over a device `Mesh`, and the solver is a jitted function
# whose collectives (`psum` etc.) XLA lowers onto ICI. The estimator/model
# contracts, param flow, persistence format, fitMultiple single-pass semantics,
# and transform batching all mirror the reference 1:1 so the API stays drop-in.
#
# Reference call-stack parity (SURVEY.md §3.1): fit(df) -> _fit_internal ->
# _call_fit_func -> [extract cols (core.py:458-557) -> partition/pad
# (core.py:452-456) -> process-group context (core.py:768-774) ->
# per-algo fit closure (core.py:781)] -> _create_model (core.py:1040-1052).
#
from __future__ import annotations

import contextvars
import json
import os
import shutil
import threading
import time
from abc import abstractmethod
from collections import namedtuple
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Tuple, Union

import numpy as np

from .data import DenseRows, ExtractedData, as_pandas, extract_dataset, vectors_to_pandas_column
from .params import Param, Params, _TpuParams
from .utils import get_logger, lockcheck


def _env_float(name: str, default: float) -> float:
    """Env-seeded float config value; a typo'd value falls back to the
    default instead of crashing package import (audit._capacity precedent)."""
    try:
        return float(os.environ.get(name) or default)
    except ValueError:
        return default


_DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".srml_cache"
)

# Global framework configuration — the analog of the reference's Spark-conf tier
# (`spark.sql.execution.arrow.maxRecordsPerBatch`, `spark.rapids.ml.uvm.enabled`;
# reference core.py:660-665, clustering.py:775-779).
config: Dict[str, Any] = {
    "max_records_per_batch": 1 << 16,  # rows per transform batch (PER DEVICE on the mesh path)
    "broadcast_chunk_bytes": 8 << 30,  # 8GB broadcast chunking parity (clustering.py:1013-1091)
    # transform batches at or above this row count are row-sharded over the
    # whole mesh (model state replicated) instead of running on one device —
    # the reference's transform is parallel across all GPUs (core.py:1531-1635)
    "distributed_transform_min_rows": 1 << 15,
    # host-side ingest chunking: per-row feature columns are converted
    # column -> contiguous block (and CSR -> ELL) in row chunks of at most
    # this many bytes, so ingest temporaries stay bounded instead of scaling
    # with the dataset (the streaming analog of the reference's Arrow
    # maxRecordsPerBatch-bounded batch loop, reference core.py:698-760)
    "ingest_chunk_bytes": 128 << 20,
    # rows per tile of the shared distance/top-k core (ops/distance.py,
    # docs/performance.md "Tiled distance core"): the outer row-tile every
    # neighbor-family scan shares — kNN query tiles, kmeans_predict
    # assignment tiles, the kernel block planner's input. Bounds the live
    # [tile, k] reduction footprint on the fallback path and the per-tile
    # VMEM working set on the Pallas path.
    "distance_tile_rows": 4096,
    # --- fault-tolerant control plane (docs/robustness.md) ---------------
    # per-round rendezvous deadline: a round with ranks still missing raises
    # RendezvousTimeoutError (transient, retryable) when this elapses —
    # Spark's spark.barrier.sync.timeout analog
    "rendezvous_timeout_s": 300.0,
    # liveness-file cadence for FileRendezvous; a peer whose heartbeat goes
    # stale by 1.5x this raises RankFailedError on survivors, so a killed
    # rank surfaces within 2x the interval instead of the full round deadline
    "heartbeat_interval_s": 5.0,
    # success-path TpuContext teardown barrier bound: a peer that already
    # exited must not hang teardown — timing out here logs a warning only
    "teardown_timeout_s": 15.0,
    # retryable_stage policy: transient failures (rendezvous timeout,
    # distributed-init race — errors.is_transient) are retried up to this
    # many times with exponential backoff from this base, capped at
    # fit_retry_backoff_max_s (uncapped base * 2^N sleeps for minutes before
    # the final attempt of a high fit_max_retries budget)
    "fit_max_retries": 2,
    "fit_retry_backoff_s": 0.5,
    "fit_retry_backoff_max_s": 30.0,
    # --- elastic recovery (docs/robustness.md "Elastic recovery") ---------
    # solver-checkpoint cadence in inner iterations: at each boundary the
    # solver state is host-fetched so an interrupted fit resumes from the
    # last checkpoint instead of from scratch. 0 disables (default — no
    # extra host sync is ever added to an un-checkpointed fit).
    "checkpoint_every_iters": 0,
    # how many rank losses one fit may absorb through survivor re-meshing
    # (recovery epochs) before degrading to the typed RankFailedError
    "recovery_max_rank_losses": 1,
    # minimum membership window a reform round stays open, so a respawned
    # rank relaunched promptly after a kill can rejoin at the epoch boundary
    # (0 = close as soon as all known-live ranks have voted)
    "recovery_rejoin_grace_s": 0.0,
    # how many times a CrossValidator/TrainValidationSplit sweep may resume
    # after a mid-flight failure; the completion ledger (tuning.SweepLedger)
    # guarantees finished (fold, paramMap) fits are never redone
    "sweep_max_resumes": 1,
    # opt-in NaN/Inf scan over ingested feature/label/weight columns
    # (chunked under ingest_chunk_bytes); raises IngestValidationError
    # naming the column instead of feeding NaNs to a solver
    "validate_ingest": False,
    # --- memory safety (docs/robustness.md "Memory safety") ---------------
    # per-device HBM capacity override for the admission budgeter
    # (spark_rapids_ml_tpu/memory.py). None = use the device-reported
    # bytes_limit where the backend exposes it (TPU/GPU); CPU has none, so
    # fits stay unbudgeted there unless this is set.
    "hbm_budget_bytes": None,
    # fraction of the capacity RESERVED (not budgeted) for the transform
    # bucket ladder, compiled-program scratch, and allocator fragmentation:
    # the admission budget is capacity * (1 - this)
    "hbm_headroom_fraction": 0.1,
    # rows per out-of-core streaming chunk (the double-buffered host->HBM
    # pipeline's unit). 0 = auto: sized so two in-flight chunks + the solver
    # workspace fit the budget (floor 256 rows; 65536 when no capacity
    # information bounds it).
    "stream_chunk_rows": 0,
    # --- multi-fit execution engine (docs/performance.md) ----------------
    # XLA persistent compilation cache directory: compiled programs survive
    # process restarts — a cold d=3000 PCA compile alone is minutes. Where
    # JAX_COMPILATION_CACHE_DIR is set, that directory is used and no other
    # is ever configured; otherwise ONE fixed, git-ignored directory in the
    # checkout — the path is part of the cache key, so it must not move
    # between processes.
    "compilation_cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR")
    or _DEFAULT_COMPILE_CACHE_DIR,
    # smallest rung of the transform bucket ladder: serving batches pad up a
    # geometric (x2) ladder of row counts starting here, so `predict`
    # compiles once per rung instead of once per distinct tail shape
    "transform_bucket_min_rows": 256,
    # max DeviceDatasets (HBM placements + pinned host datasets) a
    # device_dataset_scope retains at once; least-recently-used entries are
    # evicted beyond this, so a scope wrapped around a loop over FRESH
    # dataset objects cannot stack placements until HBM OOMs
    "device_dataset_cache_entries": 2,
    # --- multi-tenant fit scheduler (docs/scheduling.md) -----------------
    # preemptions one job may absorb before the scheduler demotes it to the
    # out-of-core streaming path (a floor-chunk footprint that packs into
    # almost any budget — degraded-mode service instead of starvation);
    # estimators without a streaming path become non-preemptible instead
    "sched_max_preemptions": 2,
    # co-admitted jobs running concurrently at most, regardless of how many
    # bin-pack into the ledger — bounds worker threads and per-job compile
    # pressure (a fairness/safety knob, docs/scheduling.md)
    "sched_max_concurrent": 4,
    # 2-D placement mode (docs/scheduling.md "2-D placement"): scheduler
    # claims name WHICH chips (contiguous first-fit runs over the pool) and
    # each job runs pinned to its claimed set via parallel.mesh.chip_scope,
    # so jobs of disjoint widths co-admit onto disjoint chip sets and run
    # concurrently instead of time-slicing the whole mesh. False keeps the
    # 1-D bytes-only book.
    "sched_chip_placement": False,
    # hierarchical mesh topology for parallel.mesh.build_mesh: None = flat
    # 1-D `rows` mesh; a dict like {"dcn": 2, "rows": 4} composes a DCN
    # (cross-process) axis with an ICI (in-process) axis — either axis may
    # be 0/absent to auto-derive from the process grouping
    "mesh_topology": None,
    # --- serving plane (docs/serving.md) ---------------------------------
    # how long the ScoringEngine holds a dispatched request open for
    # same-model coalescing (micro-batching up the bucket ladder): the
    # latency/throughput knob — 0 disables coalescing entirely
    "serve_coalesce_window_ms": 2.0,
    # row cap of one coalesced serving batch (and of a resident model's
    # PredictProgram bucket ladder); larger requests split across dispatches
    "serve_max_batch_rows": 8192,
    # model-load prewarm: every bucket-ladder rung up to this many rows is
    # compiled (through the persistent compile cache) AT LOAD TIME, so a
    # resident model's first query is compile-free; 0 disables prewarm
    "serve_prewarm_rows": 4096,
    # --- serving overload control (docs/serving.md "Overload &
    # backpressure") ------------------------------------------------------
    # server-side deadline applied to every submit() that does not pass its
    # own deadline_ms: an expired request NEVER dispatches (typed
    # RequestTimeoutError), and admission refuses a request whose deadline
    # the live queue-wait p99 predicts unmeetable (typed ServeOverloadError).
    # Monotonic-clock only. 0 disables the default deadline.
    "serve_default_deadline_ms": 30000.0,
    # bounded request queue: total rows queued in the ScoringEngine at most;
    # a submit that would exceed it is refused at admission instead of
    # growing an unbounded backlog
    "serve_max_queue_rows": 262144,
    # adaptive micro-batching: when True the coalesce window/row target
    # self-tune from the windowed arrival rate and queue-wait p99 (bounded
    # by the floor/ceiling below) — saturation grows batches instead of
    # queues. Uncongested traffic (queue-wait p99 at or under the static
    # window) behaves exactly like the static window, and
    # serve_coalesce_window_ms=0 still disables coalescing entirely.
    "serve_adaptive_batching": True,
    "serve_coalesce_window_floor_ms": 0.5,
    "serve_coalesce_window_ceiling_ms": 20.0,
    # backpressure ladder hysteresis: minimum dwell (seconds) between a
    # tenant's ladder transitions (throttle -> degrade -> shed and every
    # restore step), so a burn flap cannot flap the ladder
    "serve_overload_hold_s": 30.0,
    # per-tenant token-bucket rate while a tenant is at the throttle rung,
    # in rows/second; 0 = auto (half the tenant's recent admitted row rate)
    "serve_throttle_rows_per_s": 0.0,
    # opt-in degraded serving rung: a serve dtype (e.g. "bf16") the registry
    # builds as a SECOND resident program (its bytes honestly admitted
    # against the HBM budget) for models whose `_serve_dtypes` allow it —
    # the backpressure ladder routes a burning tenant's traffic there before
    # shedding. None disables the rung (the ladder skips degrade).
    "serve_degraded_dtype": None,
    # --- distributed diagnostics (docs/observability.md) -----------------
    # directory for flight-recorder dumps (`flightrec_rank_<r>.jsonl`) on
    # SrmlError / abort publication; seeded from SRML_FLIGHTREC_DIR. None ->
    # exception tails still attach, but no dump files are written.
    "flightrec_dir": os.environ.get("SRML_FLIGHTREC_DIR") or None,
    # --- live ops plane (docs/observability.md "Ops plane") ---------------
    # rolling-window ring geometry for the telemetry registry: every counter
    # gets rate() and every histogram gets window_quantile() over the most
    # recent bucket_seconds x bucket_count horizon (default 10s x 18 = 3min).
    # Resolved when a ring is first written — change before recording, or
    # call telemetry.registry().reset() to apply.
    "metrics_bucket_seconds": 10.0,
    "metrics_bucket_count": 18,
    # declarative SLO specs evaluated by multi-window burn rate
    # (ops_plane.slo; grammar in docs/observability.md "SLO specs"): a list
    # of dicts naming a latency histogram / error-rate counter pair / gauge
    # ceiling plus thresholds. None or [] disables the monitors entirely.
    "slo": None,
    # directory for rotating ops-plane snapshots (`ops_snapshot.json` +
    # bounded .1/.2/... generations, ops_plane.export.write_snapshot) — the
    # headless-run analog of the SRML_METRICS_PORT scrape surface; seeded
    # from SRML_OPS_SNAPSHOT_DIR. None -> no files.
    "ops_snapshot_dir": os.environ.get("SRML_OPS_SNAPSHOT_DIR") or None,
    # --- runtime lock-order sanitizer (docs/robustness.md "Threading
    # model") -------------------------------------------------------------
    # hold duration (ms) above which the SRML_LOCKCHECK=1 sanitizer records
    # a `lockcheck.long_hold` violation for a framework lock — the runtime
    # face of the static blocking-under-lock rule. Seeded from
    # SRML_LOCKCHECK_LONG_HOLD_MS; only read while the sanitizer is on. A
    # typo'd value falls back to the default — it must not crash package
    # import (utils.lockcheck.long_hold_threshold_s guards the same way).
    "lockcheck_long_hold_ms": _env_float("SRML_LOCKCHECK_LONG_HOLD_MS", 500.0),
    # --- mixed-precision solver contract (docs/performance.md
    # "Mixed-precision solvers") ------------------------------------------
    # default precision for the SANCTIONED hot contractions of every solver
    # fit: "f32" (default) keeps all fit arithmetic at the ambient input
    # precision; "bf16" routes the per-solver hot paths (k-means
    # assign+accumulate, GLM X·β / Xᵀr matvecs, linear/PCA sufficient-stat
    # einsums) through bf16 inputs with f32 accumulators. Convergence
    # scalars, L-BFGS state, and all REPORTED metrics stay full precision in
    # both modes. Per-estimator override via the `solver_precision` solver
    # param; seeded from SRML_SOLVER_PRECISION.
    "solver_precision": os.environ.get("SRML_SOLVER_PRECISION") or "f32",
    # --- efficiency attribution plane (ops_plane/efficiency.py,
    # docs/observability.md "Efficiency plane") ---------------------------
    # per-device peak FLOP/s for the roofline/MFU gauges — the peak-spec
    # grammar is a number with an optional K/M/G/T/P suffix ("14T",
    # "275e12"). Unset (default) = the `efficiency.mfu` gauges are OMITTED,
    # never guessed from the device model. Seeded from
    # SRML_DEVICE_PEAK_FLOPS.
    "device_peak_flops": os.environ.get("SRML_DEVICE_PEAK_FLOPS") or None,
    # --- fleet observability plane (ops_plane/fleet.py,
    # docs/observability.md "Fleet plane") --------------------------------
    # minimum seconds between live ops rounds (the throttled cross-rank
    # window exchange piggybacked on the rendezvous control plane). None
    # (default) = one metrics bucket width (metrics_bucket_seconds) — the
    # finest cadence at which a new exchange can carry new window data.
    "fleet_ops_round_seconds": None,
    # consecutive ops rounds a rank must be the slowest round-exiter (by at
    # least fleet_straggler_min_lag_s) before the straggler detector fires a
    # flight-recorder event + audit entry naming it
    "fleet_straggler_windows": 3,
    # lag floor (seconds behind the fastest rank's round exit) below which a
    # rank is never counted as straggling — jitter under this is noise
    "fleet_straggler_min_lag_s": 0.05,
    # per-rank ops snapshots older than this (by their meta.t header) are
    # dropped from the offline cluster merge as stale dead-rank data and
    # named in the `opsreport --cluster` partial verdict
    "fleet_stale_snapshot_s": 600.0,
}


def resolve_solver_precision(params: Optional[Dict[str, Any]] = None) -> str:
    """Effective solver precision for ONE fit: the estimator's
    ``solver_precision`` solver-param when set (per-estimator override),
    else ``config["solver_precision"]``. Returns "f32" or "bf16"; anything
    else raises ValueError naming the knob. The choice is counted
    (`fit.precision_f32` / `fit.precision_bf16`) so the BENCH/ops artifacts
    can audit which precision every fit actually ran at."""
    value = params.get("solver_precision") if params else None
    if value is None:
        value = config.get("solver_precision") or "f32"
    value = str(value).lower()
    if value not in ("f32", "bf16"):
        raise ValueError(
            f"solver_precision must be 'f32' or 'bf16', got {value!r}"
        )
    from . import telemetry

    if telemetry.enabled():
        telemetry.registry().inc(
            "fit.precision_bf16" if value == "bf16" else "fit.precision_f32"
        )
    return value

def evaluator_label_column(params_obj: Any, evaluator: Any) -> str:
    """The label column an evaluator scores against: its own ``labelCol``
    when it defines one, else the estimator/model's. The ONE resolution
    shared by the fused transform-evaluate paths and the tuning layer's
    held-out scoring, so they cannot drift."""
    if hasattr(evaluator, "hasParam") and evaluator.hasParam("labelCol"):
        return evaluator.getOrDefault("labelCol")
    return params_obj.getOrDefault("labelCol")


# Output-column naming contract shared by all predictive models
# (reference core.py:146-160 `pred` namedtuple).
pred = namedtuple("pred", ("prediction", "probability", "raw_prediction", "model_index"))(
    "prediction", "probability", "rawPrediction", "model_index"
)

# Internal column aliases used during pre-processing (reference core.py:123-144).
alias = namedtuple("alias", ("data", "label", "weight", "row_number"))(
    "tpu_values", "tpu_label", "tpu_weight", "unique_id"
)


@dataclass
class StreamPlan:
    """Out-of-core execution plan attached to a demoted fit's `FitInputs`
    (docs/robustness.md "Memory safety"): the host-retained extracted blocks
    plus the ADMITTED chunk size. Streaming solver drivers (ops/streaming.py)
    cut row chunks from `extracted`, validate them per block when
    ``config["validate_ingest"]`` asked for it, and feed them through the
    double-buffered host->HBM pipeline. Mutable bookkeeping: `validated_rows`
    (per-block validation watermark — later passes over scanned rows are
    free) and the once-per-fit CSR->ELL block cache."""

    extracted: Any  # host ExtractedData (dense np block or scipy CSR)
    chunk_rows: int
    validate: bool = False
    admission: Any = None  # the memory.AdmissionDecision that demoted the fit
    validated_rows: int = 0
    ell_blocks: Any = None  # once-per-fit CSR->ELL host blocks (global k_max)
    ell_k_max: int = 0


@dataclass
class FitInputs:
    """Device-resident inputs handed to every algorithm's fit function.

    The analog of the reference MG calling convention `(parts, m, n,
    parts_rank_size, rank)` + raft handle (reference feature.py:234-241): here the
    "handle" is the mesh, and the ragged partition layout is replaced by
    pad-to-equal row blocks with zero weights on padding (SURVEY.md §7 hard parts).
    """

    mesh: Any  # jax.sharding.Mesh
    X: Any  # row-sharded jax.Array [n_pad, d], or None when sparse
    y: Any  # row-sharded jax.Array [n_pad] or None
    w: Any  # row-sharded jax.Array [n_pad]; 0.0 on padding rows
    n_valid: int  # GLOBAL valid row count (sum over processes under SPMD)
    n_cols: int
    desc: Any  # PartitionDescriptor
    dtype: Any
    X_sparse: Any = None  # host scipy CSR when the sparse path is active
    ctx: Any = None  # the TpuContext the fit runs under (rendezvous access)
    local_rows_target: Any = None  # per-process padded local rows (SPMD mode)
    # host-side boolean over the VALID rows naming which participate in this
    # fit (None = all). Set by `with_row_mask`; fit funcs that derive host
    # statistics from raw columns (label class sets) must respect it.
    host_mask: Any = None
    # out-of-core execution plan (a demoted fit): X is NOT placed — y/w are
    # HOST arrays and solvers stream row chunks via ops/streaming.py
    stream: Optional["StreamPlan"] = None
    extra: Dict[str, Any] = field(default_factory=dict)

    def put_rows(self, host_rows: np.ndarray, weights: Optional[np.ndarray] = None) -> Any:
        """Lay an additional per-row host array out on the mesh with the SAME
        row layout/padding as X (labels, per-row stats, ...). Under SPMD every
        process passes its local slice; padding matches X's so row i of the
        result still corresponds to row i of X."""
        from .parallel import make_global_rows

        arr, _, _ = make_global_rows(
            self.mesh, host_rows, weights=weights, local_rows_target=self.local_rows_target
        )
        return arr

    def allgather_host(self, payload: str) -> List[str]:
        """Control-plane allgather of small strings across ranks (host-side
        statistics merging: class sets, bin edges, init centers). Identity in
        single-controller mode."""
        if self.ctx is not None and self.ctx.is_spmd:
            return self.ctx.rendezvous.allgather(payload)
        return [payload]

    def ell_rows(self):
        """Device-resident padded-ELL form of `X_sparse` (ops/sparse.py),
        laid out with the SAME row layout/padding as the dense path:
        returns (values, indices) row-sharded jax.Arrays. Under SPMD the pad
        width k_max is the rendezvous-agreed GLOBAL widest row so all ranks
        trace identical shapes.

        MEMOIZED on `extra` (which `with_row_mask`'s shallow replace shares
        across fold variants): the ELL tensors depend only on the data,
        dtype, and layout — never on weights or hyperparameters — so a CV
        grid over a sparse dataset converts and places them ONCE, not once
        per solve (the sparse half of the one-placement contract)."""
        cached = self.extra.get("_ell_rows")
        if cached is not None:
            return cached
        from .ops.sparse import csr_to_ell

        assert self.X_sparse is not None, "ell_rows() requires a sparse fit input"
        local_kmax = (
            int(np.diff(self.X_sparse.indptr).max()) if self.X_sparse.shape[0] else 0
        )
        k_max = max(int(g) for g in self.allgather_host(str(local_kmax)))
        idx_h, val_h, _ = csr_to_ell(self.X_sparse, k_max=k_max, dtype=self.dtype)
        out = (self.put_rows(val_h), self.put_rows(idx_h))
        self.extra["_ell_rows"] = out
        return out

    def with_row_mask(self, mask: np.ndarray) -> "FitInputs":
        """These inputs with the rows where ``mask == 0`` neutralized:
        ``w -> w * mask``. The solvers already treat ``w == 0`` rows as
        padding, so a masked fit over the FULL placed dataset computes
        exactly the fit over the mask's rows — this is how CrossValidator
        realizes a fold without re-ingesting or re-laying-out anything
        (one HBM placement serves every fold). The placed X/y are shared
        untouched; only the tiny weight vector is re-derived per fold.

        Under multi-process SPMD the mask names THIS RANK's local valid
        rows (`n_valid` is the global sum): each rank masks its own slice
        and `put_rows` pads it out to the rendezvous-agreed local target,
        so one fold is the union of every rank's local train rows."""
        import dataclasses

        m = np.ascontiguousarray(np.asarray(mask), dtype=self.dtype)
        spmd_local = self.local_rows_target is not None and m.shape[0] != self.n_valid
        if spmd_local:
            if m.shape[0] > int(self.local_rows_target):
                raise ValueError(
                    f"row mask has {m.shape[0]} entries for a local row "
                    f"target of {int(self.local_rows_target)}"
                )
        elif m.shape[0] != self.n_valid:
            raise ValueError(
                f"row mask has {m.shape[0]} entries for {self.n_valid} rows"
            )
        if self.X_sparse is not None or self.stream is not None:
            # sparse and streaming paths carry host weights
            w_masked = np.asarray(self.w) * m
        else:
            w_masked = self.w * self.put_rows(m)  # padding rows stay 0
        return dataclasses.replace(self, w=w_masked, host_mask=m > 0)

    def allgather_array(self, arr: np.ndarray) -> np.ndarray:
        """Control-plane allgather of a host numpy block, concatenated in rank
        order along axis 0. Identity in single-controller mode. Used to merge
        host-side per-rank samples (KMeans init candidates, RF quantile-sketch
        rows) — the reference's BarrierTaskContext.allGather of base64 payloads
        (e.g. tree.py:343, classification.py:1006-1012)."""
        if self.ctx is None or not self.ctx.is_spmd:
            return arr
        from .parallel.context import allgather_ndarray

        return np.concatenate(
            allgather_ndarray(self.ctx.rendezvous, arr), axis=0
        )


def retryable_stage(
    fn: Callable[[int], Any],
    *,
    stage: str,
    rendezvous: Any = None,
    logger: Any = None,
    max_retries: Optional[int] = None,
    backoff_s: Optional[float] = None,
) -> Any:
    """Run ``fn(attempt)`` with bounded retries on TRANSIENT failures — the
    in-process analog of Spark's lineage-based stage re-execution (the crash
    recovery the reference inherits for free; Zaharia et al., NSDI 2012).

    Transient means `errors.is_transient`: rendezvous round timeouts (which
    fire symmetrically, so every SPMD rank unwinds and re-enters together)
    and the distributed-init race. Permanent failures — RankFailedError (a
    peer is dead), SolverDivergedError, user errors — propagate immediately.

    Before each retry: exponential backoff from ``config["fit_retry_backoff_s"]``
    (attempt N sleeps base * 2^(N-1), capped at
    ``config["fit_retry_backoff_max_s"]``), and `rendezvous.begin_epoch(attempt)`
    re-namespaces the control plane so the retry never reads the failed
    attempt's stale rounds. Every retry increments the ``fit.retries``
    telemetry counter, which lands in ``model._fit_metrics`` and the bench
    snapshot. The chaos hook (`parallel.chaos.maybe_fail_stage`) runs at the
    top of every attempt so fault plans can inject the transient path.

    A `checkpoint.CheckpointStore` is active for all attempts (adopting the
    enclosing `recoverable_stage`'s store when present): solvers that
    checkpoint (``config["checkpoint_every_iters"]``) resume a transient
    retry from the last checkpoint instead of from scratch."""
    from . import checkpoint as _checkpoint
    from . import diagnostics, telemetry
    from .errors import is_transient
    from .parallel import chaos

    if max_retries is None:
        max_retries = int(config.get("fit_max_retries", 2))
    if backoff_s is None:
        backoff_s = float(config.get("fit_retry_backoff_s", 0.5))
    backoff_max_s = float(config.get("fit_retry_backoff_max_s", 30.0))
    if logger is None:
        logger = get_logger("retryable_stage")
    with _checkpoint.ensure_scope():
        for attempt in range(max_retries + 1):
            try:
                chaos.maybe_fail_stage(stage, attempt)
                return fn(attempt)
            except Exception as e:
                if not is_transient(e) or attempt >= max_retries:
                    raise
                telemetry.registry().inc("fit.retries")
                diagnostics.record_event(
                    "retry", stage=stage, attempt=attempt + 1,
                    error=type(e).__name__,
                )
                sleep_s = min(backoff_s * (2 ** attempt), backoff_max_s)
                logger.warning(
                    "stage %s attempt %d/%d failed transiently (%s: %s); "
                    "retrying in %.2fs",
                    stage, attempt + 1, max_retries + 1, type(e).__name__, e, sleep_s,
                )
                time.sleep(sleep_s)  # sleep-ok: capped retry backoff (the one backoff owner)
                if rendezvous is not None:
                    rendezvous.begin_epoch(attempt + 1)
    raise AssertionError("unreachable")  # pragma: no cover


def recoverable_stage(
    fn: Callable[[int], Any],
    *,
    stage: str,
    ctx: Any = None,
    rendezvous: Any = None,
    on_recover: Optional[Callable[[Any, int, set], None]] = None,
    logger: Any = None,
    max_rank_losses: Optional[int] = None,
    max_retries: Optional[int] = None,
    backoff_s: Optional[float] = None,
) -> Any:
    """Elastic outer layer over `retryable_stage`: grow abort-and-retry into
    survivor re-meshing (docs/robustness.md "Elastic recovery").

    Transient failures retry as before. A `RankFailedError` — previously
    always terminal — now opens a RECOVERY EPOCH when the rendezvous
    substrate supports membership reform (so does an exhausted
    `RendezvousTimeoutError` that names missing ranks: a peer dead before
    first contact never heartbeats, so it can only surface as a timeout;
    the reform is evidence-based — no dead hint — so a merely-slow rank
    that votes late is re-admitted): survivors agree on the live rank
    set (`rendezvous.reform`, which also admits a respawned rank rejoining
    at the epoch boundary), the context adopts the reformed group
    (`ctx.adopt_reform`: new rank/nranks + the mesh rebuilt over survivors),
    and the stage re-enters — solvers resume from the last checkpoint in the
    shared `CheckpointStore` rather than from scratch. Bounded by
    ``config["recovery_max_rank_losses"]``; exhaustion (or a substrate
    without reform) degrades to today's typed failure.

    `on_recover(new_rendezvous, generation, dead_original_ranks)` lets
    context-free callers (the chaos harness) swap their rendezvous handle.
    Recovery epochs are counted (``fit.recoveries`` / ``recovery.epochs`` /
    ``recovery.rank_losses``) and flight-recorded, and the ring is dumped
    after each successful reform so post-mortems show the epoch."""
    from . import checkpoint as _checkpoint
    from . import diagnostics, telemetry
    from .errors import RankFailedError, RendezvousTimeoutError

    if rendezvous is None and ctx is not None:
        rendezvous = getattr(ctx, "rendezvous", None)
    if max_rank_losses is None:
        max_rank_losses = int(config.get("recovery_max_rank_losses", 1))
    if logger is None:
        logger = get_logger("recoverable_stage")
    losses = 0
    with _checkpoint.ensure_scope():
        while True:  # blocking-ok: every epoch charges the recovery budget; exhaustion raises
            try:
                return retryable_stage(
                    fn, stage=stage, rendezvous=rendezvous, logger=logger,
                    max_retries=max_retries, backoff_s=backoff_s,
                )
            except (RankFailedError, RendezvousTimeoutError) as e:
                if isinstance(e, RendezvousTimeoutError) and not getattr(
                    e, "missing_ranks", None
                ):
                    # a timeout naming NO missing ranks carries no liveness
                    # evidence to reform around (e.g. a desync, a chaos
                    # `fail` injection) — that stays retryable_stage's
                    # territory, and it already exhausted its budget
                    raise
                if (
                    rendezvous is None
                    or not getattr(rendezvous, "can_reform", False)
                    or losses >= max_rank_losses
                ):
                    # stamp how far recovery got before degrading to the
                    # typed failure (0 = never opened an epoch), so callers
                    # and post-mortems distinguish "unreformable substrate"
                    # from "budget exhausted"
                    e.recovery_exhausted = losses > 0
                    e.recovery_generations = losses
                    raise
                live = list(getattr(rendezvous, "live_ranks", range(rendezvous.nranks)))
                dead = set()
                failed_rank = getattr(e, "failed_rank", None)
                if (
                    isinstance(e, RankFailedError)
                    and failed_rank is not None
                    and 0 <= failed_rank < len(live)
                ):
                    dead.add(live[failed_rank])
                # an exhausted TIMEOUT (a peer dead before it ever made
                # contact — no abort file, no heartbeat file to go stale)
                # seeds NO dead hint: the reform round's own evidence
                # (votes, abort files, heartbeat staleness) decides who is
                # gone, so a merely-slow rank that votes late is re-admitted
                # instead of excluded on circumstantial missing_ranks
                generation = int(getattr(rendezvous, "reform_generation", 0)) + 1
                reg = telemetry.registry()
                reg.inc("fit.recoveries")
                reg.inc("recovery.epochs")
                diagnostics.record_event(
                    "recovery_epoch_begin", stage=stage, generation=generation,
                    failed_rank=failed_rank,
                    dead_ranks=sorted(dead),
                )
                logger.warning(
                    "stage %s: rank failure (%s) — entering recovery epoch %d "
                    "over the survivor set", stage, e, generation,
                )
                new_rdv = rendezvous.reform(dead_ranks=dead, generation=generation)  # spmd-ok: recovery rendezvous — every survivor observes the same failure (heartbeat/abort scan) and enters reform, which carries its own deadline
                lost = len(live) - len(getattr(new_rdv, "live_ranks", range(new_rdv.nranks)))
                losses += max(1, lost)
                reg.inc("recovery.rank_losses", max(1, lost))
                if ctx is not None and hasattr(ctx, "adopt_reform"):
                    ctx.adopt_reform(new_rdv)
                if on_recover is not None:
                    on_recover(new_rdv, generation, dead)
                rendezvous = new_rdv
                diagnostics.record_event(
                    "recovery_epoch", stage=stage, generation=generation,
                    survivors=list(getattr(new_rdv, "live_ranks", range(new_rdv.nranks))),
                )
                # dump the ring so the post-mortem timeline NAMES the epoch
                # even when the fit then completes cleanly
                diagnostics.flight_recorder().dump(
                    reason=f"recovery epoch {generation}"
                )


# ---------------------------------------------------------------------------
# DeviceDataset: one ingest + layout, many fits (docs/performance.md
# "Multi-fit engine"). The reference's fitMultiple already reuses the placed
# data WITHIN one fit call (core.py:877-911); DeviceDataset extends that
# across fit calls — CV folds, sweep re-fits, and the best-model refit all
# hit the same HBM placement.
# ---------------------------------------------------------------------------


@dataclass
class DeviceDataset:
    """A dataset after ingest + layout, resident in HBM and reusable across
    fits. `key` is the cache key: (dataset identity fingerprint, extraction
    columns, dtype, mesh shape) — see `_TpuCaller._device_dataset_key`.
    `extracted` keeps the host-side blocks (features/label) so held-out
    scoring can slice rows without a pandas round-trip. `source` pins the
    ORIGINAL dataset object for the entry's lifetime: the fingerprint is
    `id()`-based, and without a strong reference CPython could recycle a
    garbage-collected dataset's id onto a new object of the same shape —
    a silent false cache hit training on the wrong data."""

    key: Optional[tuple]
    extracted: ExtractedData
    inputs: FitInputs
    source: Any = None
    # the memory.AdmissionDecision that admitted this placement — re-stamped
    # on fits served from the scope cache, so every fit's model carries its
    # verdict, not just the cache-miss one
    admission: Any = None


class DeviceDatasetScope:
    """Caching scope for DeviceDatasets. Fits inside the scope reuse a
    placed dataset when the key matches; the outermost scope exit drops the
    cache (releasing the HBM references). `last` is the dataset most
    recently built or reused — the tuning layer reads its host blocks for
    held-out scoring."""

    __slots__ = ("cache", "lock", "last")

    def __init__(self) -> None:
        self.cache: Dict[tuple, DeviceDataset] = {}  # guarded-by: lock
        self.lock = lockcheck.make_lock("core.DeviceDatasetScope.lock")
        self.last: Optional[DeviceDataset] = None


# Context-local (NOT process-global): concurrent scopes on different threads
# must neither share a cache nor clobber each other's enter/exit bookkeeping
# — with a bare global, interleaved exits across threads could resurrect an
# already-cleared scope with no owner left to release its HBM references.
# Threads spawned inside a scope start from a fresh context and simply do not
# see it (their fits ingest normally — correct, just uncached).
_DDS_SCOPE: "contextvars.ContextVar[Optional[DeviceDatasetScope]]" = contextvars.ContextVar(
    "srml_device_dataset_scope", default=None
)


def device_dataset_scope():
    """Context manager enabling DeviceDataset reuse for its dynamic extent.

    >>> with core.device_dataset_scope():
    ...     est.fit(df)            # ingest + layout + solve
    ...     est.copy(pm).fit(df)   # SAME placement, one more solve

    Nested scopes share the outermost cache; the scope is context-local, so
    fits running on OTHER threads neither see nor disturb it. Caching is
    identity-fingerprint based (cheap — the data is never hashed), so
    mutating the same dataset object in place between fits inside one scope
    is not detected; pass a new object instead."""
    import contextlib

    @contextlib.contextmanager
    def _scope():
        outer = _DDS_SCOPE.get()
        scope = outer if outer is not None else DeviceDatasetScope()
        token = _DDS_SCOPE.set(scope)
        try:
            yield scope
        finally:
            _DDS_SCOPE.reset(token)
            if outer is None:
                with scope.lock:
                    scope.cache.clear()  # free the HBM references

    return _scope()


# A fit function maps (inputs, solver_params) -> model-attribute dict.
FitFunc = Callable[[FitInputs, Dict[str, Any]], Dict[str, Any]]
# A transform triple: (construct_state, predict(state, X_batch), optional evaluate)
# mirroring the reference's (construct, transform, evaluate) closures
# (reference core.py:1434-1488).
TransformFuncs = Tuple[Callable[[], Any], Callable[[Any, np.ndarray], Any], Optional[Callable]]


class _TpuCommon(_TpuParams):
    """Input pre-processing shared by estimators (fit side) and models
    (transform side) — reference core.py:458-557 and 1205-1328 respectively."""

    _supports_sparse_input: bool = False
    _supervised: bool = False
    _use_weight_col: bool = True
    # Per-solver MXU precision policy (see parallel/mesh.py dtype_scope):
    # "float32" unless the solver's numeric contract tolerates fewer passes.
    _matmul_precision: str = "float32"

    def _pre_process_data(
        self,
        dataset: Any,
        for_fit: bool = True,
        defer_validation: bool = False,
        dense_rows: bool = False,
    ) -> ExtractedData:
        """Column selection + dense/CSR extraction (reference core.py:458-557).

        ``dense_rows=True`` leaves a dense object column as `data.DenseRows`
        for a caller that fills its own buffers (`extract_dataset`).

        ``defer_validation=True`` skips the eager opt-in NaN/Inf scan — the
        fit driver must run it itself (`data.run_deferred_validation`): full
        scan before a RESIDENT layout, per row-block on the STREAMING path
        (where re-materializing the dataset just to validate it would defeat
        the memory budget)."""
        input_col, input_cols = self._get_input_columns()
        label_col = None
        if for_fit and self._supervised:
            label_col = self.getOrDefault("labelCol")
        weight_col = None
        if (
            for_fit
            and self._use_weight_col
            and self.hasParam("weightCol")
            and self.isDefined("weightCol")
        ):
            weight_col = self.getOrDefault("weightCol")
        id_col = None
        if self.hasParam("idCol") and self.isDefined("idCol"):
            id_col = self.getOrDefault("idCol")
        sparse_optim = (
            self.getOrDefault("enable_sparse_data_optim")
            if self.hasParam("enable_sparse_data_optim")
            else None
        )
        if sparse_optim is None and not self._supports_sparse_input:
            sparse_optim = False  # densify for algorithms without a CSR path
        extracted = extract_dataset(
            dataset,
            input_col=input_col,
            input_cols=input_cols,
            label_col=label_col,
            weight_col=weight_col,
            id_col=id_col,
            float32_inputs=self._float32_inputs,
            enable_sparse_data_optim=sparse_optim,
            validate=not defer_validation,
            dense_rows=dense_rows,
        )
        if for_fit and extracted.n_rows == 0:
            # reference raises the same way when a rank gets no rows (core.py:762-765)
            raise RuntimeError("Dataset is empty — cannot fit")
        return extracted


class _TpuCaller(_TpuCommon):
    """Shared fit-orchestration machinery (reference `_CumlCaller`, core.py:430-806)."""

    # Whether this estimator's fit function is correct under multi-process SPMD
    # (all host-side statistics either rendezvous-merged or absent). Estimators
    # flip this as they are proven by the multiprocess test harness.
    _supports_multiprocess: bool = False

    # Whether this estimator's fit function can run OUT-OF-CORE (an
    # inputs.stream plan routed to ops/streaming.py). Estimators whose solver
    # state is accumulable over row chunks (linear/PCA sufficient stats,
    # logistic full-batch gradients, k-means center sums) flip this; for the
    # rest an over-budget fit raises HbmBudgetError instead of demoting.
    _supports_streaming_fit: bool = False

    # The layout a resident fit's X is placed in (parallel/mesh.py
    # `make_global_rows`): "default" leaves it to the device; "row_major" is
    # for a solver that feeds row tiles of X to the Pallas distance kernels,
    # whose operands are row-major (KMeans). The solver family decides, not
    # the shape: the GLM matvecs read the same [n, 3000] block best as the
    # device lays it out (docs/performance.md "Tiled distance core").
    _x_layout: str = "default"

    # the memory.AdmissionDecision of the most recent fit attempt (stamped
    # onto model._fit_metrics by _call_fit_func)
    _last_admission: Any = None

    # this fit's live claim in the shared HBM ledger (scheduler.HbmLedger,
    # docs/scheduling.md): one reservation spanning admission -> fit end,
    # swapped on every re-admission (retry/recovery/OOM-demotion) and
    # released in _call_fit_func's finally. None inside a scheduler job
    # (the job's own reservation is resized instead) and between fits.
    _fit_reservation: Any = None

    # portable warm-start payload for the NEXT fit call (set by
    # _TpuEstimator.fit(..., warm_start_from=...), consumed by the
    # per-estimator fit closures, cleared in fit's finally)
    _warm_start: Any = None

    def _adopt_reservation(self, reservation: Any) -> None:
        """Swap this fit's ledger claim: release the previous one (a retry's
        or a prior fit's leftover — idempotent) and hold the new. Decisions
        hand their reservation over here so the SHARED AdmissionDecision
        objects cached on DeviceDatasets never carry a live claim."""
        from .scheduler.ledger import global_ledger

        old = self._fit_reservation
        if old is not None:
            global_ledger().release(old)
        self._fit_reservation = reservation

    def _solver_workspace_terms(
        self, rows_per_device: int, n_cols: int, params: Dict[str, Any], itemsize: int
    ) -> Dict[str, int]:
        """Per-solver HBM workspace estimate hook for the admission budgeter
        (spark_rapids_ml_tpu/memory.py): named byte terms BEYOND the data
        placement — gram/covariance blocks, GLM logits + L-BFGS history,
        k-means tile buffers. Per device; {} (default) = no modeled
        workspace. Formulas are pinned by tests/test_memory.py."""
        return {}

    def _solver_flop_estimate(
        self, n_rows: int, n_cols: int
    ) -> Optional[float]:
        """Analytic FLOP estimate for ONE solve of this estimator — the
        `_solver_workspace_terms` sibling feeding the roofline/MFU gauges
        (ops_plane/efficiency.py): achieved fraction of the configured
        `config["device_peak_flops"]` peak. None (default) = no model; the
        MFU gauge is simply omitted for this estimator."""
        return None

    def _build_fit_inputs(self, extracted: ExtractedData, ctx: Any) -> FitInputs:
        """Lay the host blocks out on the mesh (pad-and-mask; SURVEY.md §7).

        Under multi-process SPMD (`ctx.is_spmd`) `extracted` is this PROCESS's
        local row block: the global layout is agreed through the rendezvous
        (PartitionDescriptor allgather — the reference's utils.py:192-210) and
        every process pads its block to the common per-process size before
        global-array assembly.
        """
        import jax

        from .parallel import PartitionDescriptor, make_global_rows

        mesh = ctx.mesh
        n_dev = mesh.devices.size
        dtype = np.float32 if self._float32_inputs else np.float64
        spmd = ctx.is_spmd

        local_rows_target = None
        if spmd:
            desc = PartitionDescriptor.build(
                [extracted.n_rows], extracted.n_cols, rank=ctx.rank, rendezvous=ctx.rendezvous
            )
            n_local_dev = jax.local_device_count()
            max_rows = max(r for _, r in desc.parts_rank_size)
            local_rows_target = -(-max_rows // n_local_dev) * n_local_dev
        else:
            desc = PartitionDescriptor.build(
                [extracted.n_rows // n_dev + (1 if i < extracted.n_rows % n_dev else 0) for i in range(n_dev)],
                extracted.n_cols,
            )

        weights = extracted.weight
        if extracted.is_sparse:
            X = None
            X_sparse = extracted.features
            import numpy as _np

            w_np = weights if weights is not None else _np.ones(extracted.n_rows, dtype=dtype)
            w = w_np
            y = extracted.label
            return FitInputs(
                mesh=mesh, X=None, y=y, w=w, n_valid=desc.m, n_cols=extracted.n_cols,
                desc=desc, dtype=dtype, X_sparse=X_sparse, ctx=ctx,
                local_rows_target=local_rows_target,
            )

        X, w, _ = make_global_rows(
            mesh, extracted.features.astype(dtype, copy=False), weights=weights,
            local_rows_target=local_rows_target, x_layout=self._x_layout,
        )
        y = None
        if extracted.label is not None:
            y, _, _ = make_global_rows(
                mesh, extracted.label.astype(dtype, copy=False),
                local_rows_target=local_rows_target,
            )
        return FitInputs(
            mesh=mesh, X=X, y=y, w=w, n_valid=desc.m, n_cols=extracted.n_cols,
            desc=desc, dtype=dtype, ctx=ctx, local_rows_target=local_rows_target,
        )

    @abstractmethod
    def _get_tpu_fit_func(self, extracted: ExtractedData) -> FitFunc:
        """Per-algorithm fit closure factory (reference `_get_cuml_fit_func`)."""
        raise NotImplementedError

    def _get_tpu_batched_fit_func(
        self, extracted: ExtractedData
    ) -> Optional[Callable[[FitInputs, List[Dict[str, Any]]], Optional[List[Dict[str, Any]]]]]:
        """Optional batched-sweep closure: ``f(inputs, param_sets)`` solves a
        whole hyperparameter group in ONE compiled program and returns one
        attribute dict per set — or None to decline at runtime (the caller
        falls back to the sequential loop). Estimators whose solvers take
        the swept hyperparameters as traced scalars override this."""
        return None

    def _batch_group_key(self, solver_params: Dict[str, Any]):
        """Hashable signature of everything that changes the PROGRAM (static
        shape/structure) for this estimator's solver — param sets with equal
        keys can solve as one batched program over the remaining (traced)
        hyperparameters. None (default) = this estimator never batches."""
        return None

    def _device_dataset_key(self, dataset: Any, ctx: Any) -> tuple:
        """(dataset identity fingerprint, columns, (dtype, sparse mode, X's
        layout), mesh shape) — what must match for a cached placement to be
        reusable by this fit."""
        from .data import dataset_fingerprint

        input_col, input_cols = self._get_input_columns()
        label_col = self.getOrDefault("labelCol") if self._supervised else None
        weight_col = (
            self.getOrDefault("weightCol")
            if self._use_weight_col and self.hasParam("weightCol") and self.isDefined("weightCol")
            else None
        )
        sparse_optim = (
            self.getOrDefault("enable_sparse_data_optim")
            if self.hasParam("enable_sparse_data_optim")
            else None
        )
        if sparse_optim is None and not self._supports_sparse_input:
            sparse_optim = False  # mirrors _pre_process_data's densify default
        id_col = (
            self.getOrDefault("idCol")
            if self.hasParam("idCol") and self.isDefined("idCol")
            else None
        )
        return (
            dataset_fingerprint(dataset),
            (
                input_col,
                tuple(input_cols) if input_cols else None,
                label_col,
                weight_col,
                id_col,
            ),
            (
                np.dtype(np.float32 if self._float32_inputs else np.float64).name,
                sparse_optim,
                self._x_layout,
            ),
            tuple(int(d.id) for d in ctx.mesh.devices.flatten()),
        )

    def _admit_and_layout(
        self,
        extracted: ExtractedData,
        ctx: Any,
        stage_logger: Any,
        force_stream: bool = False,
        key: Optional[tuple] = None,
        source: Any = None,
        attempt: int = 0,
    ) -> DeviceDataset:
        """Admission verdict + the matching data plane (docs/robustness.md
        "Memory safety"): RESIDENT fits validate eagerly and lay out in HBM
        as before; an over-budget fit DEMOTES to the streaming plan
        (`fit.demotions`, reason logged and stamped on ``model._fit_metrics``)
        with per-block validation deferred to the pipeline; even-streaming-
        doesn't-fit raises the typed `HbmBudgetError` from `memory.admit_fit`.
        Streamed datasets return with ``key=None`` — NON-cacheable: there is
        no HBM placement to reuse, and a later attempt must re-budget."""
        from . import memory as _memory
        from . import telemetry
        from .data import run_deferred_validation
        from .parallel import chaos

        # hand back this fit call's PREVIOUS claim before re-admitting: a
        # retry/recovery/OOM-demotion re-entry still holds the failed
        # attempt's reservation, and the fresh admission must not count the
        # fit's own doomed bytes against itself (a resident fit at ~0.9x
        # budget would otherwise spuriously demote — or refuse — on retry)
        self._adopt_reservation(None)
        adm = _memory.admit_fit(self, extracted, ctx, force_stream=force_stream)  # ledger-ok: THE fit-side admission entry — reserves through the shared ledger
        self._last_admission = adm
        # the admission's shared-ledger claim now belongs to THIS fit call
        # (released in _call_fit_func's finally); the decision object itself
        # may be cached on the DeviceDataset and must not carry a live claim
        self._adopt_reservation(adm.reservation)
        adm.reservation = None
        if adm.verdict == _memory.STREAM:
            if telemetry.enabled():
                reg = telemetry.registry()
                reg.inc("memory.admission_stream")
                reg.inc("fit.demotions")
            get_logger(type(self)).warning(
                "fit demoted RESIDENT -> STREAM: %s (chunk_rows=%d)",
                adm.reason, adm.chunk_rows,
            )
            plan = StreamPlan(
                extracted=extracted,
                chunk_rows=adm.chunk_rows,
                validate=bool(config.get("validate_ingest", False)),
                admission=adm,
            )
            inputs = self._build_stream_inputs(extracted, ctx, plan)
            return DeviceDataset(
                key=None, extracted=extracted, inputs=inputs, source=source,
                admission=adm,
            )
        if telemetry.enabled():
            telemetry.registry().inc("memory.admission_resident")
        # the deferred opt-in NaN/Inf scan runs eagerly (full, chunked) before
        # any placement — resident semantics unchanged
        run_deferred_validation(extracted)
        # index = the retry/recovery attempt: `oom:stage=placement:round=1`
        # targets the RE-placement of a recovery attempt, not the first layout
        chaos.maybe_fail_oom("placement", attempt)
        with telemetry.span("layout", logger=stage_logger):
            inputs = self._build_fit_inputs(extracted, ctx)
        telemetry.record_device_memory()  # HBM watermark after placement
        return DeviceDataset(
            key=key, extracted=extracted, inputs=inputs, source=source,
            admission=adm,
        )

    def _build_stream_inputs(
        self, extracted: ExtractedData, ctx: Any, plan: StreamPlan
    ) -> FitInputs:
        """`FitInputs` for an out-of-core fit: NOTHING is placed — X is None,
        y/w are the HOST columns, and `stream` carries the plan the streaming
        solver drivers consume. Solvers treat host w == 0 rows as padding,
        so `with_row_mask` fold reuse works unchanged."""
        from .parallel import PartitionDescriptor

        mesh = ctx.mesh
        n_dev = mesh.devices.size
        dtype = np.float32 if self._float32_inputs else np.float64
        desc = PartitionDescriptor.build(
            [
                extracted.n_rows // n_dev + (1 if i < extracted.n_rows % n_dev else 0)
                for i in range(n_dev)
            ],
            extracted.n_cols,
        )
        w = extracted.weight
        w_np = (
            np.asarray(w, dtype=dtype)
            if w is not None
            else np.ones(extracted.n_rows, dtype=dtype)
        )
        return FitInputs(
            mesh=mesh,
            X=None,
            y=extracted.label,
            w=w_np,
            n_valid=desc.m,
            n_cols=extracted.n_cols,
            desc=desc,
            dtype=dtype,
            X_sparse=extracted.features if extracted.is_sparse else None,
            ctx=ctx,
            stream=plan,
        )

    def _device_dataset(
        self,
        dataset: Any,
        ctx: Any,
        stage_logger: Any,
        force_stream: bool = False,
        attempt: int = 0,
    ) -> DeviceDataset:
        """Ingest + admission + layout, or a cache hit inside an active
        `device_dataset_scope` — the ingest/layout spans (and their cost)
        exist only on a miss, which is how a numFolds x paramMaps
        CrossValidator fit performs exactly ONE ingest and ONE layout.
        Streamed (demoted) datasets are never cached; a cached entry is by
        construction a RESIDENT placement that already passed admission."""
        from . import memory as _memory
        from . import telemetry

        scope = _DDS_SCOPE.get()
        if scope is None or force_stream:
            with telemetry.span("ingest", logger=stage_logger):
                extracted = self._pre_process_data(
                    dataset, for_fit=True, defer_validation=True
                )
            return self._admit_and_layout(
                extracted, ctx, stage_logger, force_stream, attempt=attempt
            )
        key = self._device_dataset_key(dataset, ctx)
        allow_hit = True
        if ctx.is_spmd:
            # placement-fingerprint agreement, ONE rendezvous round: the
            # cache-hit branch below runs no collectives while the miss
            # branch runs the layout allgather, so hit/miss MUST be
            # symmetric across ranks. Every rank votes its have-bit; the
            # cache is used only when ALL ranks hold the exact entry —
            # otherwise every rank takes the rebuild branch together (a
            # rank that does hold the entry re-lands on the host-retained
            # path: same identity, ingest skipped, symmetric layout).
            with scope.lock:
                have = key in scope.cache
            votes = ctx.rendezvous.allgather(f"dds-have:{int(have)}")
            allow_hit = all(v == "dds-have:1" for v in votes)
            telemetry.registry().inc("fit.device_dataset_spmd_rounds")
            # a rank that holds the entry while others miss takes the
            # host-retained path below (`same_ingest_identity` is reflexive):
            # its ingest is skipped but admission + layout re-run, keeping
            # every rank's collective schedule identical
        # one builder per scope: a cache-miss build is never duplicated by a
        # concurrent fit sharing the scope
        with scope.lock:  # held-ok: the scope (and its lock) is context-local — each SPMD rank holds only its own — and the partition-build allgather below is symmetric across ranks: the pre-lock fingerprint round guarantees every rank enters the same branch
            dds = scope.cache.get(key) if allow_hit else None
            if dds is not None:
                scope.cache[key] = scope.cache.pop(key)  # LRU: move to newest
                telemetry.registry().inc("fit.device_dataset_reuses")
                if dds.admission is not None:
                    # a cache hit skipped _admit_and_layout: re-stamp the
                    # verdict that admitted the reused placement, and
                    # re-reserve its bytes in the shared ledger (the
                    # placement is physically held; serving loads and other
                    # tenants must see it — docs/scheduling.md)
                    self._last_admission = dds.admission
                    self._adopt_reservation(
                        _memory.rereserve_admission(dds.admission)
                    )
            else:
                # host-retained re-placement (docs/robustness.md "Elastic
                # recovery"): a cached entry for the SAME data on a DIFFERENT
                # mesh — the survivor re-mesh shape, where the device set
                # changed under one fit — still holds the right host blocks.
                # Reuse them: the ingest pass is skipped entirely and only
                # the admission + layout run against the new mesh (fewer
                # chips shrink the budget: a resident fit may legitimately
                # RESUME AS A STREAMING FIT here).
                from .data import same_ingest_identity

                retained = next(
                    (e for ek, e in scope.cache.items() if same_ingest_identity(ek, key)),
                    None,
                )
                if retained is not None:
                    extracted = retained.extracted
                    reg = telemetry.registry()
                    reg.inc("recovery.replacements")
                    reg.inc("recovery.rows_replaced", int(extracted.n_rows))
                    dds = self._admit_and_layout(
                        extracted, ctx, stage_logger, key=key,
                        source=retained.source, attempt=attempt,
                    )
                else:
                    with telemetry.span("ingest", logger=stage_logger):
                        extracted = self._pre_process_data(
                            dataset, for_fit=True, defer_validation=True
                        )
                    # `source=dataset` pins the object so its id() — the
                    # heart of the cache key — cannot be recycled while the
                    # entry lives
                    dds = self._admit_and_layout(
                        extracted, ctx, stage_logger, key=key, source=dataset,
                        attempt=attempt,
                    )
                    if dds.key is not None:
                        telemetry.registry().inc("fit.device_dataset_builds")
                if dds.key is not None:  # streamed datasets are non-cacheable
                    scope.cache[key] = dds
                # bounded retention: a scope around a loop over FRESH dataset
                # objects (per-fold slices on a non-engine path) must not
                # stack HBM placements — evict least-recently-used entries
                # (in-flight fits keep their own references; eviction only
                # drops the cache's pin)
                cap = max(1, int(config.get("device_dataset_cache_entries", 2)))
                while len(scope.cache) > cap:
                    evicted = next(iter(scope.cache))
                    del scope.cache[evicted]
                    telemetry.registry().inc("fit.device_dataset_evictions")
            scope.last = dds
        return dds

    def _call_fit_func(
        self,
        dataset: Any,
        param_maps: Optional[List[Dict[Param, Any]]],
        row_mask: Optional[np.ndarray] = None,
    ) -> List[Dict[str, Any]]:
        """Run the (possibly multi-model) fit: ONE data layout, N solver calls.

        Parity with the reference's single-pass `fitMultiple` (core.py:877-911):
        the feature block is placed in HBM once; each param-map's solver call
        reuses it. Returns one model-attribute dict per param map (or a single
        one when param_maps is None).

        Stage timing rides on `telemetry.span` (ingest/layout/solve): spans
        feed the metrics registry + JSONL sink when telemetry is on and log
        the old ``stage <name>: <t>s`` lines when `verbose` is set — one
        mechanism instead of parallel hand-rolled timing. The per-fit
        registry delta lands on models as ``_fit_metrics``
        (see `_TpuEstimator._fit_internal`).
        """
        import contextlib

        from . import telemetry

        logger = get_logger(type(self))
        self._last_admission = None  # per-fit; stamped onto _fit_metrics below
        verbose = bool(self._solver_params.get("verbose"))
        stage_logger = logger if verbose else None
        # Opt-in tracing (the NVTX/xprof analog, SURVEY.md §5): when
        # SRML_PROFILE_DIR is set, the whole fit runs under a jax.profiler
        # trace viewable in xprof/tensorboard. The trace must begin BEFORE the
        # fit/ingest spans open — a TraceAnnotation entered outside an active
        # trace is not captured, and docs/observability.md promises every
        # stage span as an xprof annotation.
        profile_dir = os.environ.get("SRML_PROFILE_DIR")
        profile_cm: Any = contextlib.nullcontext()
        if profile_dir:
            import jax

            profile_cm = jax.profiler.trace(profile_dir)  # profiler-ok: the opt-in SRML_PROFILE_DIR xprof hook — this IS the sanctioned whole-fit trace entry point
        from . import diagnostics
        from .parallel import TpuContext

        active = TpuContext.current()
        # trace identity OUTERMOST: every span/metric/flight-recorder record
        # of this fit (including the fit_scope snapshot) carries the same
        # trace_id + fit_id on every rank — under SPMD, rank 0 mints the id
        # and propagates it through one rendezvous round (docs/observability.md
        # "Trace correlation")
        try:
            with diagnostics.trace_scope(
                type(self).__name__, active
            ), profile_cm, telemetry.fit_scope(
                type(self).__name__
            ) as tele_scope, telemetry.span(
                "fit", logger=stage_logger, estimator=type(self).__name__
            ):
                # the whole traced fit (ingest -> layout -> solve) is ONE
                # recoverable stage: a transient retry re-derives its state from
                # the immutable dataset (bit-identical to an unfaulted fit —
                # pinned by tests/test_chaos.py), and a rank loss on a
                # reform-capable rendezvous opens a recovery epoch — the
                # survivor mesh re-ingests from host-retained chunks and the
                # solvers resume from the checkpoint store
                rows = recoverable_stage(
                    lambda attempt: self._call_fit_func_traced(
                        dataset, param_maps, logger, stage_logger, row_mask,
                        attempt=attempt,
                    ),
                    stage="fit",
                    ctx=active,
                    logger=logger,
                )
        finally:
            # the fit's shared-ledger claim ends with the fit — success,
            # failure, or preemption (the workspace is gone; a scope-cached
            # placement re-reserves on its next cache hit)
            self._adopt_reservation(None)
        self._last_fit_metrics = tele_scope["metrics"]
        eff = tele_scope.get("efficiency")
        if eff and isinstance(self._last_fit_metrics, dict):
            # the fit's device-time attribution (execute/compile/host/idle
            # split + per-stage detail) and its compile-ledger delta ride the
            # per-fit metrics, mirroring the admission stamp below
            self._last_fit_metrics = dict(self._last_fit_metrics)
            self._last_fit_metrics["efficiency"] = eff
            self._last_fit_metrics["compile"] = eff.get("compile", {})
        adm = getattr(self, "_last_admission", None)
        if (
            adm is not None
            and isinstance(self._last_fit_metrics, dict)
            and (telemetry.enabled() or adm.demoted)
        ):
            # stamp the admission verdict (and a demotion's reason) onto the
            # per-fit metrics so models carry WHY they streamed. A DEMOTED
            # fit stamps even with telemetry off — the reason a fit streamed
            # is robustness state, not a metric — while a plain resident fit
            # keeps the disabled-telemetry contract: _fit_metrics == {}
            self._last_fit_metrics = dict(self._last_fit_metrics)
            self._last_fit_metrics["admission"] = adm.stamp()
        return rows

    def _call_fit_func_traced(
        self,
        dataset: Any,
        param_maps: Optional[List[Dict[Param, Any]]],
        logger: Any,
        stage_logger: Any,
        row_mask: Optional[np.ndarray] = None,
        attempt: int = 0,
    ) -> List[Dict[str, Any]]:
        """One recoverable attempt, with the OOM conversion ladder wrapped
        around it: a REAL backend out-of-memory failure at placement or solve
        (XLA RESOURCE_EXHAUSTED — or the chaos `oom` injection shaped like
        one) is converted to the typed `HbmBudgetError` and retried ONCE on
        the out-of-core streaming path. The retry re-ingests and streams; if
        it OOMs too (or the estimator has no streaming path / runs SPMD), the
        typed error propagates — a raw XLA error never does. `attempt` is the
        retry/recovery attempt index — the chaos `oom:stage=placement` index,
        so a plan can target the RE-placement of a recovery attempt
        (`round=1`) rather than the first layout."""
        from . import memory as _memory
        from . import telemetry
        from .parallel import TpuContext

        try:
            return self._call_fit_func_attempt(
                dataset, param_maps, logger, stage_logger, row_mask,
                attempt=attempt,
            )
        except Exception as e:
            if not _memory.is_oom_error(e):
                raise
            if telemetry.enabled():
                telemetry.registry().inc("memory.oom_caught")
            active = TpuContext.current()
            if not getattr(self, "_supports_streaming_fit", False) or (
                active is not None and active.is_spmd
            ):
                raise _memory.as_hbm_budget_error(e) from e
            logger.warning(
                "backend out-of-memory during fit (%s); converting to "
                "HbmBudgetError and retrying ONCE on the out-of-core "
                "streaming path", e,
            )
        # the retry runs OUTSIDE the except handler: the handler's traceback
        # pins the failed attempt's frames — and with them the dead resident
        # placement's device arrays — for as long as `e` lives; Python drops
        # `e` at handler exit, so by here that HBM is release-able. Any
        # placements cached by an enclosing device_dataset_scope are evicted
        # too: under a real allocation failure, a cache hit is worth less
        # than the streaming retry having room to run.
        scope = _DDS_SCOPE.get()
        if scope is not None:
            with scope.lock:
                n_evicted = len(scope.cache)
                scope.cache.clear()
            if n_evicted and telemetry.enabled():
                telemetry.registry().inc("fit.device_dataset_evictions", n_evicted)
        try:
            return self._call_fit_func_attempt(
                dataset, param_maps, logger, stage_logger, row_mask,
                attempt=attempt, force_stream=True,
            )
        except Exception as e2:
            if _memory.is_oom_error(e2):
                raise _memory.as_hbm_budget_error(e2) from e2
            raise

    def _call_fit_func_attempt(
        self,
        dataset: Any,
        param_maps: Optional[List[Dict[Param, Any]]],
        logger: Any,
        stage_logger: Any,
        row_mask: Optional[np.ndarray] = None,
        attempt: int = 0,
        force_stream: bool = False,
    ) -> List[Dict[str, Any]]:
        import contextlib

        from . import telemetry
        from .parallel import TpuContext
        from .parallel.mesh import dtype_scope, ensure_compilation_cache

        ensure_compilation_cache()

        # Route through the caller's process group when one is active (the
        # reference's train-UDF-inside-CumlContext shape, core.py:768-781);
        # otherwise stand up the single-controller context ourselves.
        active = TpuContext.current()
        if active is not None:
            if active.is_spmd and not self._supports_multiprocess:
                raise NotImplementedError(
                    f"{type(self).__name__} does not support multi-process SPMD fit yet; "
                    "run it single-controller (one process driving all devices)"
                )
            ctx_mgr: Any = contextlib.nullcontext(active)
        else:
            from .parallel.mesh import default_devices

            ctx_mgr = TpuContext(
                0, 1, num_devices=min(self.num_workers, len(default_devices()))
            )

        with ctx_mgr as ctx, dtype_scope(
            np.float32 if self._float32_inputs else np.float64, self._matmul_precision
        ):
            dds = self._device_dataset(
                dataset, ctx, stage_logger, force_stream=force_stream,
                attempt=attempt,
            )
            extracted, inputs = dds.extracted, dds.inputs
            fit_func = self._get_tpu_fit_func(extracted)
            if row_mask is not None:
                # under SPMD each rank passes its LOCAL fold mask; the fold
                # is the union of per-rank train rows (with_row_mask pads to
                # the agreed local target, so shapes stay symmetric)
                inputs = inputs.with_row_mask(row_mask)
            logger.info(
                "fit: %d rows x %d cols on %d-device mesh (%s)%s",
                inputs.n_valid, inputs.n_cols, inputs.mesh.devices.size,
                "sparse" if inputs.X_sparse is not None else "dense",
                f" [SPMD rank {ctx.rank}/{ctx.nranks}]" if ctx.is_spmd else "",
            )
            if param_maps is None:
                solver_param_sets = [dict(self._solver_params)]
            else:
                solver_param_sets = []
                for pm in param_maps:
                    est = self.copy(pm)
                    # re-sync spark params -> solver params for overridden entries
                    mapping = est._param_mapping()
                    for p, v in pm.items():
                        name = p.name if isinstance(p, Param) else p
                        mapped = mapping.get(name, None)
                        if mapped:
                            est._set_solver_param(mapped, v, silent=True)
                    solver_param_sets.append(dict(est._solver_params))
            rows, solve_times = self._dispatch_solves(
                inputs, extracted, fit_func, solver_param_sets, stage_logger
            )
            # compile-vs-execute first-call probe: valid ONLY when the solver
            # param sets are identical SEQUENTIAL re-runs of one program —
            # different maps change the work itself (e.g. a maxIter grid),
            # and after sweep batching a whole grid is ONE solve, leaving a
            # single time with nothing to difference against
            if len(solve_times) > 1 and all(
                sp == solver_param_sets[0] for sp in solver_param_sets[1:]
            ):
                telemetry.registry().gauge(
                    "fit.compile_overhead_s_est", solve_times[0] - min(solve_times[1:])
                )
            if solve_times:
                # first-call wall time under the persistent compilation cache:
                # across bench rounds this gauge falling toward the repeat
                # solve time IS the cache working (docs/observability.md)
                telemetry.registry().gauge("fit.compile_cache_hit", solve_times[0])
            telemetry.record_device_memory()  # HBM watermark after solve
            if telemetry.enabled():
                # analytic FLOP estimate (the `_solver_workspace_terms`
                # sibling hook) feeds the MFU gauge's numerator — per solve,
                # so a sweep's N param sets scale it N-fold
                fhook = getattr(self, "_solver_flop_estimate", None)
                if fhook is not None:
                    try:
                        flops = fhook(int(inputs.n_valid), int(inputs.n_cols))
                    except Exception:
                        flops = None
                    if flops:
                        telemetry.note_flops(
                            float(flops) * max(1, len(solver_param_sets)),
                            chips=int(inputs.mesh.devices.size),
                        )
        return rows

    def _dispatch_solves(
        self,
        inputs: FitInputs,
        extracted: ExtractedData,
        fit_func: FitFunc,
        solver_param_sets: List[Dict[str, Any]],
        stage_logger: Any,
    ) -> Tuple[List[Dict[str, Any]], List[float]]:
        """Run every solver param set, batching where possible.

        Param sets whose `_batch_group_key` signatures match differ only in
        hyperparameters the solver takes as TRACED scalars — those groups
        solve as ONE compiled program (`_get_tpu_batched_fit_func`); sets
        that change program structure (maxIter, k, solver selection) run the
        classic sequential loop. `fit.solves_batched` / `fit.solves_sequential`
        count how each param set was dispatched."""
        from . import telemetry
        from .parallel import chaos

        chaos.maybe_fail_oom("solve")  # round-less `oom:stage=solve` plans
        n_sets = len(solver_param_sets)
        rows: List[Optional[Dict[str, Any]]] = [None] * n_sets
        solve_times: List[float] = []
        # streaming fits solve sequentially: the batched sweeps are compiled
        # over the RESIDENT placement (inputs.X / one placed ELL set)
        batched_fn = (
            self._get_tpu_batched_fit_func(extracted)
            if n_sets > 1 and inputs.stream is None
            else None
        )

        groups: Dict[Any, List[int]] = {}
        order: List[Any] = []
        for i, sp in enumerate(solver_param_sets):
            key = self._batch_group_key(sp) if batched_fn is not None else None
            gid = ("seq", i) if key is None else ("batch", key)
            if gid not in groups:
                groups[gid] = []
                order.append(gid)
            groups[gid].append(i)

        # compile-ledger shape-class: coarse on purpose — what the jit cache
        # keys on that the OUTSIDE can see (padded dims, layout, mesh width).
        # Hyperparameters that re-trace (maxIter grids) are a documented bias
        # of the ledger, not part of the key (docs/observability.md).
        shape_class = (
            f"{inputs.n_valid}x{inputs.n_cols}"
            f":{'sparse' if inputs.X_sparse is not None else 'dense'}"
            f":{'stream' if inputs.stream is not None else 'resident'}"
            f":mesh{int(inputs.mesh.devices.size)}"
        )
        for gid in order:
            idxs = groups[gid]
            if batched_fn is not None and gid[0] == "batch" and len(idxs) > 1:
                with telemetry.span(
                    "solve", logger=stage_logger, batched=len(idxs), of=n_sets
                ) as solve_span, telemetry.compile_event(
                    f"fit.{type(self).__name__}.batched",
                    f"{shape_class}:n{len(idxs)}",
                ):
                    out = batched_fn(inputs, [solver_param_sets[i] for i in idxs])
                if out is not None:
                    if len(out) != len(idxs):  # fail at the contract breach,
                        # not as a far-away TypeError on a None attrs dict
                        raise RuntimeError(
                            f"{type(self).__name__} batched fit returned "
                            f"{len(out)} results for {len(idxs)} param sets"
                        )
                    if solve_span.wall_s is not None:
                        solve_times.append(solve_span.wall_s)
                    telemetry.registry().inc("fit.solves_batched", len(idxs))
                    for i, attrs in zip(idxs, out):
                        rows[i] = attrs
                    continue
                # declined at runtime (degenerate data, convergence tracing
                # active): fall through to the sequential loop below
            for i in idxs:
                with telemetry.span(
                    "solve", logger=stage_logger, index=i, of=n_sets
                ) as solve_span, telemetry.compile_event(
                    f"fit.{type(self).__name__}", shape_class
                ):
                    rows[i] = fit_func(inputs, solver_param_sets[i])
                if solve_span.wall_s is not None:
                    solve_times.append(solve_span.wall_s)
                telemetry.registry().inc("fit.solves_sequential")
        return rows, solve_times


class _TpuEstimator(_TpuCaller):
    """Estimator base (reference `_CumlEstimator`, core.py:853-1074)."""

    def fit(
        self,
        dataset: Any,
        params: Optional[Union[Dict, List[Dict]]] = None,
        warm_start_from: Any = None,
    ):
        """Fit on `dataset`. `warm_start_from` seeds the solver from a
        previous result's PORTABLE iterate (docs/scheduling.md "Warm
        starts") instead of a cold init: a fitted model of the same
        estimator family (k-means centers, the GLM coefficient iterate) or a
        `checkpoint.SolverCheckpoint` (the PR-6 portable subset — what a
        preempted/recovered fit resumes from, now a public API). Estimators
        whose solvers have no iterate to seed (closed-form linear/PCA,
        DBSCAN/UMAP) raise `NotImplementedError`; a shape-mismatched donor
        raises `ValueError`. Adoption is counted (``fit.warm_starts``) along
        with the donor's already-paid iterations
        (``fit.warm_start_iterations_saved``)."""
        if isinstance(params, (list, tuple)):
            if warm_start_from is not None:
                raise ValueError(
                    "warm_start_from is a single-fit seed; combine it with "
                    "one param dict, not a param-map list"
                )
            return [m for _, m in sorted(self.fitMultiple(dataset, list(params)))]
        if isinstance(params, dict) and params:
            return self.copy(params).fit(dataset, warm_start_from=warm_start_from)
        if warm_start_from is not None:
            self._warm_start = self._resolve_warm_start(warm_start_from)
        try:
            models = self._fit_internal(dataset, None)
        finally:
            self._warm_start = None
        return models[0]

    def _resolve_warm_start(self, source: Any) -> Dict[str, Any]:
        """Per-estimator hook: extract the portable warm-start payload from
        `source` (a fitted model or a `SolverCheckpoint`). Overridden by the
        iterative estimators (KMeans, LogisticRegression); the default names
        the gap instead of silently cold-starting."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support warm_start_from: its "
            "solver has no portable iterate to seed (closed-form or "
            "non-iterative fit)"
        )

    def fitMultiple(self, dataset: Any, paramMaps: Sequence[Dict[Param, Any]]) -> "_FitMultipleIterator":
        """Train all param maps in ONE pass over the data (reference core.py:877-911)."""

        def fitMultipleModels() -> List["_TpuModel"]:
            return self._fit_internal(dataset, list(paramMaps))

        return _FitMultipleIterator(fitMultipleModels, len(paramMaps))

    def _fit_internal(
        self,
        dataset: Any,
        paramMaps: Optional[List[Dict[Param, Any]]],
        row_mask: Optional[np.ndarray] = None,
    ) -> List["_TpuModel"]:
        attr_rows = self._call_fit_func(dataset, paramMaps, row_mask)
        fit_metrics = getattr(self, "_last_fit_metrics", {})
        models = []
        for i, attrs in enumerate(attr_rows):
            model = self._create_model(attrs)
            model._model_attributes = attrs
            model._fit_metrics = fit_metrics
            self._copyValues(model, paramMaps[i] if paramMaps else None)
            self._copy_solver_params(model)
            if paramMaps:
                est = self.copy(paramMaps[i])
                est._copy_solver_params(model)
                model._solver_params.update(
                    {k: v for k, v in est._solver_params.items()}
                )
            models.append(model)
        return models

    @abstractmethod
    def _create_model(self, attrs: Dict[str, Any]) -> "_TpuModel":
        raise NotImplementedError

    def _supportsTransformEvaluate(self, evaluator: Any) -> bool:
        """Whether CrossValidator can use the fused multi-model evaluate path
        (reference `_CumlEstimator._supportsTransformEvaluate`)."""
        return False

    # persistence ---------------------------------------------------------
    def write(self) -> "_TpuWriter":
        return _TpuWriter(self)

    def save(self, path: str) -> None:
        self.write().save(path)

    @classmethod
    def read(cls) -> "_TpuReader":
        return _TpuReader(cls)

    @classmethod
    def load(cls, path: str):
        return cls.read().load(path)


class _TpuEstimatorSupervised(_TpuEstimator):
    """Adds label handling (reference `_CumlEstimatorSupervised`, core.py:1075-1114)."""

    _supervised = True


class _FitMultipleIterator:
    """Thread-safe (index, model) iterator; ALL models come from one fit pass
    (reference `_FitMultipleIterator`, core.py:808-850)."""

    def __init__(self, fitMultipleModels: Callable[[], List["_TpuModel"]], numModels: int):
        self.fitMultipleModels = fitMultipleModels
        self.numModels = numModels
        self.counter = 0  # guarded-by: lock
        self.lock = lockcheck.make_lock("core._FitMultipleIterator.lock")
        # written once by the index-0 claimant, then published through
        # `_materialized`; readers wait on the event, never the lock
        self.models: Optional[List["_TpuModel"]] = None
        self._materialized = threading.Event()
        self._fit_error: Optional[BaseException] = None

    def __iter__(self) -> Iterator[Tuple[int, "_TpuModel"]]:
        return self

    def __next__(self) -> Tuple[int, "_TpuModel"]:
        # the lock covers ONLY index claiming: the single fit pass used to
        # run inside it, which held the iterator lock across rendezvous
        # rounds and sink I/O (ci/analysis `blocking-under-lock`) — every
        # concurrent consumer was blocked on the MUTEX instead of on the
        # models being ready
        with self.lock:
            index = self.counter
            if index >= self.numModels:
                raise StopIteration()
            self.counter += 1
        if index == 0:
            try:
                self.models = self.fitMultipleModels()
            except BaseException as e:
                self._fit_error = e
                raise
            finally:
                self._materialized.set()
        else:
            self._materialized.wait()  # blocking-ok: bounded by the claimant's fit, which owns the retry/rendezvous deadlines (core.retryable_stage)
            if self._fit_error is not None:
                raise RuntimeError(
                    "the fit pass materializing this iterator's models failed"
                ) from self._fit_error
        return index, self.models[index]

    next = __next__


class _TpuModel(_TpuCommon):
    """Model base (reference `_CumlModel`, core.py:1117-1488)."""

    def __init__(self, **model_attrs: Any) -> None:
        super().__init__()
        self._model_attributes: Dict[str, Any] = model_attrs
        # per-fit telemetry delta (counters/spans/gauges captured during the
        # fit that produced this model); {} when telemetry was disabled
        self._fit_metrics: Dict[str, Any] = {}
        # serving-plane state stamped by serving.ModelRegistry (docs/serving.md):
        # the admission verdict that loaded (or refused/evicted) this model,
        # mirroring the fit-side _fit_metrics["admission"] stamp
        self._serve_metrics: Dict[str, Any] = {}

    @property
    def hasSummary(self) -> bool:
        return False

    def transform(self, dataset: Any):
        raise NotImplementedError

    def _transform_evaluate(self, dataset: Any, evaluator: Any) -> List[float]:
        raise NotImplementedError(f"{type(self).__name__} does not support transform-evaluate")

    @classmethod
    def _transformEvaluate_supported(cls, evaluator: Any) -> bool:
        return False

    def _combine(self, models: List["_TpuModel"]) -> "_TpuModel":
        raise NotImplementedError

    # serving hooks (docs/serving.md) -------------------------------------
    # The per-estimator surface the serving plane composes: a resident
    # PredictProgram factory, plus the placement / per-bucket workspace byte
    # terms the admission budgeter (memory.admit_model_load) charges — the
    # serve-side analog of the fit-side `_solver_workspace_terms` hook.

    # serving dtypes this model accepts; the distance-core models extend
    # with "bf16" (their fast-bf16 scoring is parity-tested)
    _serve_dtypes: tuple = (None, "float32", "float64")

    def _serve_program(
        self, serve_dtype: Optional[str] = None, *, cap: Optional[int] = None
    ) -> "PredictProgram":
        """Resident predict handle for the serving plane. Models without a
        batched predict surface (DBSCAN's fused fit-transform, UMAP's
        fit-embedding) have nothing to keep resident."""
        raise NotImplementedError(
            f"{type(self).__name__} has no serving hook (no batched predict "
            "surface to keep resident)"
        )

    def _serve_check(self, serve_dtype: Optional[str] = None) -> None:
        """Cheap serveability preflight: raises exactly what `_serve_program`
        would, WITHOUT placing anything on device. The registry runs this
        before its admission/eviction loop, so a load that can never succeed
        (no hook, bad serve_dtype, unbound item set) cannot evict resident
        models as a side effect."""
        if type(self)._serve_program is _TpuModel._serve_program:
            self._serve_program(serve_dtype)  # the standard NotImplementedError
        if serve_dtype not in self._serve_dtypes:
            raise ValueError(
                f"{type(self).__name__} serves at its fit dtype; "
                f"serve_dtype={serve_dtype!r} is only available on the "
                "distance-core models (docs/serving.md)"
            )
        self._serve_n_cols()

    def _serve_n_cols(self) -> int:
        """Feature width the serving plane prewarms/validates against."""
        n = int(getattr(self, "n_cols", 0) or 0)
        if n <= 0:
            raise ValueError(
                f"{type(self).__name__} does not know its feature width; "
                "cannot prewarm the serving ladder"
            )
        return n

    def _serve_placement_terms(self) -> Dict[str, int]:
        """Per-device HBM bytes of this model's RESIDENT state (the arrays
        `construct()` places), as named terms for the admission budgeter.
        Default: every array model attribute at the serving working dtype —
        model state is replicated, so per-device cost is the full size."""
        itemsize = 4 if self._float32_inputs else 8
        total = 0
        for v in self._model_attributes.values():
            if isinstance(v, np.ndarray):
                total += int(v.size) * itemsize
            elif isinstance(v, (list, tuple)) and v and isinstance(v[0], np.ndarray):
                total += sum(int(a.size) for a in v) * itemsize
        return {"placement.params": total}

    def _serve_workspace_terms(
        self, bucket_rows_count: int, itemsize: int
    ) -> Dict[str, int]:
        """Per-bucket predict workspace estimate: bytes live during ONE
        dispatched batch of `bucket_rows_count` rows beyond the model state
        and the input block itself. {} (default) = no modeled workspace."""
        return {}

    def _record_bucket(self, xp: np.ndarray, on_mesh: bool) -> None:
        """Bucket-ladder telemetry: via a process-wide set of (model class,
        bucketed shape, dtype, placement) signatures, a
        `transform.bucket_programs` counter that advances only when a NEW
        bucketed shape reaches `predict` (the rows a batch was padded by are
        the `transform/pad` span's `rung` less its `rows`). The shape set
        deliberately survives `registry().reset()`: it mirrors the
        process-wide jit cache, which a registry reset does not clear — a
        shape seen before genuinely compiles nothing, so re-counting it
        would overstate compile work. Readers wanting per-window numbers take counter
        DELTAS. Asserting the counter stays at the ladder size while batch
        sizes vary freely is the test-side proof that serving compiles per
        bucket, not per tail shape."""
        from . import telemetry

        if not telemetry.enabled():
            return
        reg = telemetry.registry()
        sig = (type(self).__name__, tuple(xp.shape), str(xp.dtype), on_mesh)
        with _BUCKET_LOCK:
            if sig not in _BUCKET_SHAPES:
                _BUCKET_SHAPES.add(sig)
                reg.inc("transform.bucket_programs")

    # Spark JVM interop: name of the `spark_interop` converter for this model
    # class (None = the reference has no `.cpu()` for it either)
    _spark_converter: Optional[str] = None

    def cpu(self):
        """Equivalent GENUINE pyspark.ml JVM model built via py4j, usable in
        existing Spark pipelines and JVM serving (the reference's `.cpu()`
        capability: tree.py:524-569 + utils.py:311-481 for forests,
        feature.py:365-379 PCA, regression.py:658-672, classification.py:
        1301-1323). Requires pyspark and an active SparkSession; cached after
        the first conversion."""
        if self._spark_converter is None:
            raise NotImplementedError(
                f"{type(self).__name__} has no Spark-ML JVM equivalent (reference parity)"
            )
        if getattr(self, "_spark_model", None) is None:
            from . import spark_interop

            self._spark_model = getattr(spark_interop, self._spark_converter)(self)
        return self._spark_model

    # persistence ---------------------------------------------------------
    def write(self) -> "_TpuWriter":
        return _TpuWriter(self)

    def save(self, path: str) -> None:
        self.write().save(path)

    @classmethod
    def read(cls) -> "_TpuReader":
        return _TpuReader(cls)

    @classmethod
    def load(cls, path: str):
        return cls.read().load(path)


class _PieceRing:
    """The piece buffers a model keeps between its several-piece `transform`
    calls. Fresh pages are what an extraction costs on a host without
    transparent hugepages (a first write into a new 96 MB buffer takes ten
    times the copy itself: PERF.md section 7), so a call does not make its
    buffers anew: it TAKES them out of here (they are its own while it runs —
    a concurrent call finds none and makes its own) and hands them back after
    its last result was fetched, when nothing reads them any more. A call
    that raises hands nothing back. At most `KEEP` are kept; `deque` makes
    `take` and `give` atomic, so there is no lock to hold."""

    KEEP = 2  # buffers a call fills in turn: one leaves the host while the other is filled

    def __init__(self) -> None:
        import collections

        self._free: Any = collections.deque(maxlen=self.KEEP)

    def take(self, shape: Tuple[int, int], dtype: Any) -> List[np.ndarray]:
        bufs: List[np.ndarray] = []
        for _ in range(self.KEEP):
            try:
                buf = self._free.pop()
            except IndexError:
                buf = None
            if buf is None or buf.shape != shape or buf.dtype != dtype:
                buf = np.empty(shape, dtype=dtype)
            bufs.append(buf)
        return bufs

    def give(self, bufs: List[np.ndarray]) -> None:
        self._free.extend(bufs)


class _Pieces:
    """The rows of one transform call as host pieces of `piece_rows` rows, in
    order: row views of a block (CSR rows densified), or, with a `ring`, a
    `DenseRows` column filled piece by piece into the ring's buffers in turn
    (`filled`). `host(i)` of a filled piece overwrites the buffer piece
    ``i - len(ring)`` had: the caller has waited for that piece's result.
    Asked for the piece it returned last, it returns that again (`transform`
    fills piece 0 inside its own span, the loop takes it from here)."""

    def __init__(self, features: Any, piece_rows: int, ring: Optional[_PieceRing] = None) -> None:
        n = int(features.shape[0])
        self.features = features
        self.piece_rows = int(piece_rows)
        self.bounds = [(lo, min(lo + piece_rows, n)) for lo in range(0, n, piece_rows)] or [(0, 0)]
        self.filled = ring is not None
        self._pool = ring
        self.ring: List[np.ndarray] = (
            ring.take((self.piece_rows, int(features.shape[1])), features.dtype) if ring else []
        )
        self._last: Tuple[int, Any] = (-1, None)

    def host(self, i: int) -> np.ndarray:
        if i == self._last[0]:
            return self._last[1]
        lo, hi = self.bounds[i]
        if self.filled:
            xb = self.ring[i % len(self.ring)][: hi - lo]
            self.features.fill(xb, lo, hi)
        else:
            xb = self.features[lo:hi]
            if hasattr(xb, "todense"):
                xb = np.asarray(xb.todense())
        self._last = (i, xb)
        return xb

    def release(self) -> None:
        """The ring back to the model: only after the call's last fetch."""
        if self._pool is not None:
            self._pool.give(self.ring)
            self.ring = []


# Process-wide record of bucketed shapes already handed to a `predict`
# program (see `_TpuModel._record_bucket`).
_BUCKET_LOCK = lockcheck.make_lock("core._BUCKET_LOCK")
_BUCKET_SHAPES: set = set()  # guarded-by: _BUCKET_LOCK


class PredictProgram:
    """Resident, reusable predict handle — the internals of
    `_TpuModelWithColumns._transform_arrays` (construct the device state once,
    bucket-pad every batch up the geometric ladder, run the jitted `predict`,
    slice outputs back) exposed as ONE object with a lifetime.

    Two consumers share it so they cannot drift: `_transform_arrays` builds a
    short-lived one per transform call, and the serving plane
    (`spark_rapids_ml_tpu/serving/`, docs/serving.md) holds one per RESIDENT
    model for the model's whole registry lifetime — which is what makes a
    long-lived scoring service compile-free after load-time prewarm.

    The async contract (enforced by the ci/analysis `serve-dispatch` rule):

      * `dispatch(xb)` pads a host batch UP the bucket ladder
        (`mesh.bucket_rows`) and runs `predict` WITHOUT any host fetch — the
        returned device arrays are in flight when it returns;
      * `fetch(result, n_valid)` is the one device→host sync point, slicing
        every output back to the valid rows;
      * `prewarm(...)` dispatches zeros through every ladder rung (through
        the persistent compile cache, `mesh.ensure_compilation_cache`) so a
        resident model's first query pays dispatch, never compile.
    """

    def __init__(
        self,
        model: "_TpuModel",
        *,
        construct: Optional[Callable[[], Any]] = None,
        predict: Optional[Callable[[Any, Any], Any]] = None,
        cap: Optional[int] = None,
        mesh: Any = None,
    ) -> None:
        import jax

        from .parallel.mesh import default_local_device, replicated

        if construct is None or predict is None:
            c0, p0, _ = model._get_transform_func()
            construct = construct or c0
            predict = predict or p0
        self.model = model
        self.predict_fn = predict
        self.mesh = mesh
        # where `launch` places a batch off the mesh path: the device every
        # `construct` puts the model's state on
        self.device = default_local_device()
        self.multiple = int(mesh.devices.size) if mesh is not None else 1
        self.cap = int(cap) if cap else int(config["max_records_per_batch"]) * self.multiple
        self.bucket_min = int(config["transform_bucket_min_rows"])
        self.dtype = np.float32 if model._float32_inputs else np.float64
        state = construct()
        if mesh is not None:
            state = jax.tree.map(
                lambda a: jax.device_put(a, replicated(mesh))
                if isinstance(a, (np.ndarray, jax.Array))
                else a,
                state,
            )
        self.state = state
        # per-program record of bucketed shapes already dispatched — what the
        # serving engine's `serve.bucket_hits` counter reads (independent of
        # the telemetry-gated process-wide `transform.bucket_programs` set)
        self._shapes_seen: set = set()
        self.last_dispatch_new_shape: bool = False

    def ladder(self, max_rows: Optional[int] = None) -> List[int]:
        """The rung sizes (rows) batches of 1..max_rows pad up to — exactly
        what `prewarm` compiles (`mesh.bucket_ladder`)."""
        from .parallel.mesh import bucket_ladder

        return bucket_ladder(
            min(int(max_rows), self.cap) if max_rows else self.cap,
            multiple=self.multiple,
            min_rows=self.bucket_min,
            cap=self.cap,
        )

    def dispatch(self, xb: np.ndarray) -> Tuple[Any, int]:
        """Pad one host batch up its bucket rung and run `predict` — NO host
        fetch; returns (in-flight result, valid row count). A zero-row batch
        still dispatches one bucket-padded rung so multi-output models yield
        one correctly-shaped empty array per output at `fetch`."""
        xp, n_valid = self.pad(xb)
        return self.launch(xp), n_valid

    def pad(self, xb: np.ndarray) -> Tuple[np.ndarray, int]:
        """`dispatch`'s host half: the batch padded up to its rung (a copy
        only where the rung is larger); returns (padded, valid row count)."""
        from .parallel.mesh import bucket_rows

        return bucket_rows(
            np.asarray(xb), multiple=self.multiple, min_rows=self.bucket_min, cap=self.cap
        )

    def launch(self, xp: np.ndarray) -> Any:
        """`dispatch`'s device half: place a padded batch and call `predict`
        with the device array, up to its asynchronous return — NO host fetch.
        The placement is explicit, so `predict`'s `xb.astype(dtype)` is the
        device's (nothing, for a batch of the model's dtype) and never a host
        copy of the batch; the runtime may read `xp` until the result is
        ready, so a caller that reuses the buffer waits for that first."""
        import jax

        from .parallel.mesh import row_sharding

        self.model._record_bucket(xp, self.mesh is not None)
        sig = (tuple(xp.shape), str(xp.dtype))
        self.last_dispatch_new_shape = sig not in self._shapes_seen
        self._shapes_seen.add(sig)
        where = self.device if self.mesh is None else row_sharding(self.mesh, xp.ndim)
        return self.predict_fn(self.state, jax.device_put(xp, where))

    def fetch(self, result: Any, n_valid: int) -> Any:
        """THE device→host sync point: materialize the in-flight result and
        slice every output back to the valid rows."""
        from . import telemetry

        with telemetry.device_wait("predict_fetch"):
            if isinstance(result, tuple):
                return tuple(np.asarray(r)[:n_valid] for r in result)
            return np.asarray(result)[:n_valid]

    def prewarm(self, n_cols: int, *, max_rows: Optional[int] = None) -> int:
        """Compile every ladder rung up to `max_rows` rows by dispatching a
        zeros batch per rung and blocking on it (the compile must complete at
        LOAD time, not at the first query). With a persistent compile cache
        configured the programs come off disk. Returns the rung count.

        Each rung is one compile-ledger entry (`telemetry.compile_event`):
        the load-time compile wall lands in `compile.*` instead of hiding in
        `serve_load`'s span."""
        from . import telemetry

        rungs = self.ladder(max_rows)
        for r in rungs:
            with telemetry.compile_event(
                f"predict.{type(self.model).__name__}", f"{r}x{int(n_cols)}"
            ):
                result, _ = self.dispatch(
                    np.zeros((r, int(n_cols)), dtype=self.dtype)
                )
                self.fetch(result, 0)
        return len(rungs)


class _TpuModelWithColumns(_TpuModel):
    """Transform = append prediction column(s), batched over rows
    (reference `_CumlModelWithColumns`, core.py:1490-1649).

    The per-batch loop is the analog of the reference's pandas_udf Arrow-batch
    loop (core.py:1562-1572); `construct` runs once (model attrs -> device
    arrays), `predict` is jitted and reused across batches.
    """

    @abstractmethod
    def _get_transform_func(self) -> TransformFuncs:
        raise NotImplementedError

    def _serve_program(
        self, serve_dtype: Optional[str] = None, *, cap: Optional[int] = None
    ) -> PredictProgram:
        """Default serving hook: the model's own (construct, predict) pair as
        a resident PredictProgram. `serve_dtype` outside `_serve_dtypes` is
        rejected — the bf16 query path exists only on the distance-core
        models (KMeansModel, NearestNeighborsModel), whose fast-bf16 scoring
        is parity-tested in ops/distance.py (docs/serving.md "bf16 serving")."""
        self._serve_check(serve_dtype)
        return PredictProgram(self, cap=cap)

    def _out_column_names(self) -> List[str]:
        """Names of appended columns; single-entry list for plain predictors."""
        return [self.getOrDefault("outputCol") if self.hasParam("outputCol") and self.isDefined("outputCol") else pred.prediction]

    def _transform_plan(self, n_rows: int, row_bytes: int) -> Tuple[int, Any, int]:
        """(batch rows, mesh or None, piece rows) of a call over `n_rows` rows
        of `row_bytes` each.

        Small blocks run on one device (the reference's one-task-per-batch
        pandas_udf shape). At ``config["distributed_transform_min_rows"]`` rows
        and up, each batch is row-sharded over the full mesh with the model
        state replicated — every per-algo `predict` is a row-parallel jitted
        program, so GSPMD partitions it with zero collectives (the reference's
        all-GPU parallel transform, core.py:1531-1635).

        A piece is what one `predict` launch takes: the largest rung of the
        bucket ladder whose bytes fit ``config["ingest_chunk_bytes"]``, the
        batch at most; on the mesh path one row-sharded batch."""
        import jax

        from .parallel.mesh import bucket_ladder, default_devices, get_mesh

        batch = int(config["max_records_per_batch"])
        n_dev = min(self.num_workers, len(default_devices()))
        # multi-process SPMD transforms rank-LOCAL batches: stay on local
        # devices (sharding a local batch over the global mesh would mix
        # ranks' unrelated rows and target non-addressable devices)
        if (
            n_rows >= int(config["distributed_transform_min_rows"])
            and n_dev > 1
            and jax.process_count() == 1
        ):
            # per-device batch budget stays constant
            return batch * n_dev, get_mesh(n_dev), batch * n_dev
        rungs = bucket_ladder(
            batch, min_rows=int(config["transform_bucket_min_rows"]), cap=batch
        )
        fit = [r for r in rungs if r * row_bytes <= int(config["ingest_chunk_bytes"])]
        return batch, None, min(batch, fit[-1] if fit else rungs[0])

    def _transform_arrays(self, features: Any) -> Any:
        """Batched predict over a host feature block (an array or a CSR
        matrix), a piece (`_transform_plan`) at a time: each piece is a row
        view, padded, placed, predicted and fetched in turn. The per-algo
        `predict` may return one array or a tuple of arrays (multi-output
        models); each output is concatenated across pieces."""
        n, d = features.shape
        batch, mesh, piece_rows = self._transform_plan(
            n, d * np.dtype(features.dtype).itemsize
        )
        return self._transform_pieces(_Pieces(features, piece_rows), batch, mesh)

    def _transform_pieces(self, pieces: "_Pieces", batch: int, mesh: Any) -> Any:
        """`predict` over the pieces of one call, in order.

        Every piece is padded UP to a geometric ladder of row buckets
        (`mesh.bucket_rows`) and the outputs sliced back to the valid rows —
        serving traffic with ragged batch sizes compiles one `predict`
        program per bucket instead of one per distinct tail shape (and with
        ``config["compilation_cache_dir"]`` set, those programs survive
        process restarts). `predict` is row-parallel by contract, so padding
        rows cannot influence valid rows' outputs, and predicting a piece at
        a time gives the whole batch's answers.

        The pad/dispatch/slice mechanics live in `PredictProgram` — the same
        handle the serving plane keeps resident per model (docs/serving.md) —
        so batch transform and long-lived serving cannot drift.

        Row views go in lockstep (pad, dispatch, fetch a piece, under one
        `transform` span). Pieces that are FILLED (`_Pieces.filled`: a dense
        object column, several pieces) are pipelined on this one thread:
        placement and `predict` return before the bytes have left the host,
        so piece i leaves and runs while piece i + 1 is filled. A fill is the
        top-level span `transform.extract`, so `transform` closes before it
        and opens again after (one `transform` span a piece; the top-level
        spans of a call never overlap), `transform/stall` is the wait for the
        ring buffer the next fill takes (the result of the piece that held it:
        until that is ready the runtime may still read the buffer), and the
        results are fetched once the last piece is in flight. Piece 0 is the
        caller's to fill, inside the call's first `transform.extract`."""
        import contextlib

        import jax

        from . import telemetry
        from .parallel.mesh import dtype_scope, ensure_compilation_cache

        ensure_compilation_cache()
        bounds, piped = pieces.bounds, pieces.filled
        n = bounds[-1][1]
        attrs: Dict[str, Any] = {"model": type(self).__name__}
        if piped:
            attrs.update(pieces=len(bounds), piece_rows=pieces.piece_rows)
        program = None
        outs: List[Any] = []
        in_flight: List[Tuple[Any, int]] = []
        with dtype_scope(
            np.float32 if self._float32_inputs else np.float64, self._matmul_precision
        ), contextlib.ExitStack() as call_span:
            # a zero-row block still runs ONE (bucket-padded) piece: the
            # output arity/shape comes from `predict` itself, so multi-output
            # models return one correctly-shaped empty array PER output —
            # never a single bare zeros((0,)) that `_split_output` would
            # mis-map across its columns
            for i, (lo, hi) in enumerate(bounds):
                if piped and i:
                    call_span.close()
                    with telemetry.span("transform.extract", rows=hi - lo) as sp:
                        xb = pieces.host(i)
                        sp.set(bytes=int(xb.nbytes))
                    telemetry.registry().inc("transform.bytes_extracted", xb.nbytes)
                else:
                    xb = pieces.host(i)
                if i == 0 or piped:
                    call_span.enter_context(
                        telemetry.span("transform", rows=hi - lo if piped else n, **attrs)
                    )
                if program is None:
                    with telemetry.span("construct", model=type(self).__name__):
                        program = PredictProgram(self, cap=batch, mesh=mesh)
                    if telemetry.enabled():
                        reg = telemetry.registry()
                        reg.inc("transform.rows", n)
                        reg.inc("transform.batches", -(-n // batch) if n else 1)
                        if piped:
                            reg.inc("transform.pieces", len(bounds))
                # the spans go round the program's calls, not inside them:
                # the serving engine calls `dispatch`/`fetch` per request group
                with telemetry.span("pad", rows=hi - lo) as sp:
                    xp, n_valid = program.pad(xb)
                    sp.set(rung=int(xp.shape[0]))
                with telemetry.span(
                    "dispatch", rows=int(xp.shape[0]), bytes=int(xp.nbytes)
                ) as sp:
                    result = program.launch(xp)
                    sp.set(new_shape=program.last_dispatch_new_shape)
                if not piped:
                    with telemetry.span("fetch", rows=n_valid):
                        outs.append(program.fetch(result, n_valid))
                    continue
                in_flight.append((result, n_valid))
                with telemetry.span("stall", piece=i):
                    held_by = i + 1 - len(pieces.ring)
                    if 0 <= held_by and i + 1 < len(bounds):
                        with telemetry.device_wait("transform_stall"):
                            jax.block_until_ready(in_flight[held_by][0])
            for result, n_valid in in_flight:
                with telemetry.span("fetch", rows=n_valid):
                    outs.append(program.fetch(result, n_valid))
        pieces.release()
        if isinstance(outs[0], tuple):
            return tuple(np.concatenate(parts, axis=0) for parts in zip(*outs))
        return np.concatenate(outs, axis=0)

    def transform(self, dataset: Any):
        from . import telemetry

        # `transform.extract` / `transform.assemble` are top-level spans on
        # either side of `transform` (whose extent `_transform_pieces` keeps):
        # the pandas column to host rows, and the answer frame. A dense
        # object column whose batch is larger than an ingest chunk (wide
        # rows: the bytes are worth overlapping) is not made one block: it
        # goes through the call in pieces, each filled into one of a small
        # ring of buffers the model keeps, and only piece 0 is filled here.
        with telemetry.span("transform.extract") as sp:
            pdf = as_pandas(dataset)
            extracted = self._pre_process_data(dataset, for_fit=False, dense_rows=True)
            feats = extracted.features
            n, d = extracted.n_rows, extracted.n_cols
            batch, mesh, piece_rows = self._transform_plan(
                n, d * np.dtype(feats.dtype).itemsize
            )
            piped = isinstance(feats, DenseRows) and piece_rows < min(batch, n)
            if isinstance(feats, DenseRows) and not piped:
                feats = extracted.features = feats.block()
            ring = self.__dict__.setdefault("_piece_ring", _PieceRing()) if piped else None
            pieces = _Pieces(feats, piece_rows, ring)
            got = pieces.host(0) if piped else (feats.data if extracted.is_sparse else feats)
            if telemetry.enabled():
                sp.set(rows=len(got) if piped else n, cols=d,
                       feature_kind=extracted.feature_kind, bytes=int(got.nbytes))
                telemetry.registry().inc("transform.bytes_extracted", int(got.nbytes))
        result = self._transform_pieces(pieces, batch, mesh)
        names = self._out_column_names()
        with telemetry.span("transform.assemble", rows=len(pdf), columns=len(names)):
            out = pdf.copy(deep=False)
            values_by_col = self._split_output(result, names, extracted)
            for name, vals in values_by_col.items():
                out[name] = vals
            # the extraction's host array goes here, inside the span: left
            # to the frame's teardown, handing its pages back is time of the
            # call that no span covers
            del extracted, feats, pieces, got
        return out

    def _split_output(
        self, result: Any, names: List[str], extracted: ExtractedData
    ) -> Dict[str, Any]:
        """Map raw predict output to output columns. Default: single column;
        2-D output becomes a vector column when the input was vectors
        (core.py:1577-1593 parity)."""
        name = names[0]
        if result.ndim > 1:
            if extracted.feature_kind == "vector":
                return {name: vectors_to_pandas_column(result)}
            return {name: list(result)}
        return {name: result}


# ---------------------------------------------------------------------------
# Persistence (reference core.py:253-340): metadata JSON + npz array sidecar.
# ---------------------------------------------------------------------------


def _prepare_save_path(path: str, overwrite: bool) -> None:
    """Shared exists/overwrite/mkdir preamble for every writer (incl. the
    composite writers below)."""
    if os.path.exists(path):
        if not overwrite:
            raise FileExistsError(f"Path {path} already exists; use write().overwrite().save()")
        shutil.rmtree(path)
    os.makedirs(path)


class CompositeWriter:
    """Writer for models made of OTHER models (CrossValidatorModel,
    TrainValidationSplitModel, PipelineModel): one metadata.json carrying the
    class + caller-provided fields, plus nested per-child sub-saves in each
    child's own format. One implementation so the save protocol (overwrite
    semantics, metadata shape, child layout) cannot drift between the
    composite model types.

    build_meta(instance) -> dict of extra metadata fields;
    iter_children(instance) -> iterable of (relative_subdir, child_model).
    """

    def __init__(self, instance: Any, build_meta, iter_children) -> None:
        self.instance = instance
        self._build_meta = build_meta
        self._iter_children = iter_children
        self._overwrite = False

    def overwrite(self) -> "CompositeWriter":
        self._overwrite = True
        return self

    def save(self, path: str) -> None:
        inst = self.instance
        _prepare_save_path(path, self._overwrite)
        meta = {
            "class": f"{type(inst).__module__}.{type(inst).__qualname__}",
            **self._build_meta(inst),
        }
        with open(os.path.join(path, "metadata.json"), "w") as f:
            json.dump(meta, f, indent=2)
        for rel, child in self._iter_children(inst):
            child.write().overwrite().save(os.path.join(path, rel))


class _TpuWriter:
    def __init__(self, instance: Union[_TpuEstimator, _TpuModel]):
        self.instance = instance
        self._overwrite = False

    def overwrite(self) -> "_TpuWriter":
        self._overwrite = True
        return self

    def save(self, path: str) -> None:
        inst = self.instance
        _prepare_save_path(path, self._overwrite)
        metadata = {
            "class": f"{type(inst).__module__}.{type(inst).__qualname__}",
            "uid": inst.uid,
            "paramMap": {p.name: v for p, v in inst._paramMap.items() if _jsonable(v)},
            "defaultParamMap": {p.name: v for p, v in inst._defaultParamMap.items() if _jsonable(v)},
            "solver_params": {k: v for k, v in inst._solver_params.items() if _jsonable(v)},
            "num_workers": inst._num_workers,
            "float32_inputs": inst._float32_inputs,
            "is_model": isinstance(inst, _TpuModel),
        }
        with open(os.path.join(path, "metadata.json"), "w") as f:
            json.dump(metadata, f, indent=2)
        if isinstance(inst, _TpuModel):
            self._write_model_attributes(inst, path)

    def _write_model_attributes(self, inst: "_TpuModel", path: str) -> None:
        """Array-serialization hook: npz bundle + JSON scalars by default;
        subclasses may use a different sidecar format (UMAP's .npy layout)."""
        arrays = {}
        scalars = {}
        for k, v in inst._model_attributes.items():
            if isinstance(v, np.ndarray):
                arrays[k] = v
            elif isinstance(v, (list, tuple)) and len(v) and isinstance(v[0], np.ndarray):
                for i, a in enumerate(v):
                    arrays[f"{k}__list{i}"] = a
                scalars[f"{k}__listlen"] = len(v)
            else:
                scalars[k] = v
        np.savez(os.path.join(path, "arrays.npz"), **arrays)
        with open(os.path.join(path, "attributes.json"), "w") as f:
            json.dump(scalars, f, default=_np_default)


def load_instance(path: str):
    """Load any saved estimator/model by the class recorded in its metadata —
    the analog of pyspark.ml's DefaultParamsReader class dispatch. Composite
    writers (CrossValidatorModel) use this to restore nested models without
    knowing their concrete type."""
    import importlib

    with open(os.path.join(path, "metadata.json")) as f:
        qualname = json.load(f)["class"]
    module, _, name = qualname.rpartition(".")
    obj: Any = importlib.import_module(module)
    for part in name.split("."):
        obj = getattr(obj, part)
    return obj.load(path)


class _TpuReader:
    def __init__(self, cls: type):
        self.cls = cls

    def load(self, path: str):
        with open(os.path.join(path, "metadata.json")) as f:
            metadata = json.load(f)
        cls = self.cls
        if metadata["is_model"]:
            attrs = self._read_model_attributes(path)
            inst = cls(**attrs)  # reference `_from_row` pattern (core.py:1150-1157)
            inst._model_attributes = attrs
        else:
            inst = cls()
        self._restore_params(inst, metadata)
        return inst

    def _read_model_attributes(self, path: str) -> Dict[str, Any]:
        """Inverse of `_TpuWriter._write_model_attributes` (hook for sidecar
        format variants)."""
        scalars: Dict[str, Any] = {}
        attrs_path = os.path.join(path, "attributes.json")
        if os.path.exists(attrs_path):
            with open(attrs_path) as f:
                scalars = json.load(f)
        arrays_path = os.path.join(path, "arrays.npz")
        attrs: Dict[str, Any] = {}
        if os.path.exists(arrays_path):
            with np.load(arrays_path, allow_pickle=False) as npz:
                attrs.update({k: npz[k] for k in npz.files})
        # reassemble list-of-array attributes
        list_lens = {k[: -len("__listlen")]: v for k, v in scalars.items() if k.endswith("__listlen")}
        for base, ln in list_lens.items():
            attrs[base] = [attrs.pop(f"{base}__list{i}") for i in range(ln)]
            scalars.pop(f"{base}__listlen")
        attrs.update(scalars)
        return attrs

    def _restore_params(self, inst: Any, metadata: Dict[str, Any]) -> None:
        for name, v in metadata["defaultParamMap"].items():
            if inst.hasParam(name):
                inst._defaultParamMap[inst.getParam(name)] = v
        for name, v in metadata["paramMap"].items():
            if inst.hasParam(name):
                inst._paramMap[inst.getParam(name)] = v
        inst._solver_params.update(metadata["solver_params"])
        inst._num_workers = metadata["num_workers"]
        inst._float32_inputs = metadata["float32_inputs"]
        inst.uid = metadata["uid"]
        return inst


def _jsonable(v: Any) -> bool:
    try:
        json.dumps(v, default=_np_default)
        return True
    except (TypeError, ValueError):
        return False


def _np_default(o: Any):
    if isinstance(o, np.generic):
        return o.item()
    if isinstance(o, np.ndarray):
        return o.tolist()
    raise TypeError(f"not JSON serializable: {type(o)}")
