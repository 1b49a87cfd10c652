#
# HBM admission control: the memory-safety plane (docs/robustness.md
# "Memory safety").
#
# The reference inherits cuML MG's full-device-residency assumption (PAPER.md
# L3): a dataset over HBM is an uncatchable XLA RESOURCE_EXHAUSTED crash that
# under SPMD tears down the whole clique. This module makes memory a BUDGETED
# resource instead: every fit entering `core._call_fit_func` gets a preflight
# ADMISSION VERDICT —
#
#   RESIDENT  the placement + solver working set fits the per-device budget:
#             lay the dataset out in HBM as before;
#   STREAM    the resident working set does not fit, but the out-of-core one
#             (double-buffered row chunks + solver workspace) does: the fit
#             demotes to the streaming solvers (ops/streaming.py) and the
#             `fit.demotions` counter advances;
#   raise     even streaming cannot fit — a typed `HbmBudgetError` carrying
#             the estimate, the capacity, and the LARGEST term, so the failure
#             names what doesn't fit instead of surfacing as a raw XLA error.
#
# Estimates are deliberately simple, exact formulas (pinned by
# tests/test_memory.py against analytic byte counts): per-device placement
# bytes for the dense and CSR->ELL (incl. padding) layouts, plus per-solver
# workspace from the estimator hook `_solver_workspace_terms` (GLM logits +
# L-BFGS history, k-means tile buffers AND its predict-side assignment tile
# — `config["distance_tile_rows"]` rows through the shared distance core,
# so an admitted fit cannot OOM at transform — PCA/linear X'X). A fraction of the
# capacity (`config["hbm_headroom_fraction"]`) is reserved as headroom for the
# transform bucket ladder, compiled-program scratch, and allocator
# fragmentation — the budget is capacity * (1 - headroom).
#
# Capacity resolution order: a chaos-injected budget (`oom:budget=` faults,
# parallel/chaos.py) > `config["hbm_budget_bytes"]` > the minimum
# `Device.memory_stats()["bytes_limit"]` over the mesh where the backend
# exposes it (TPU/GPU yes, CPU None). No capacity information means no
# budgeting: the verdict is RESIDENT, exactly the pre-PR behavior.
#
# This module (and telemetry.py's watermark sampler) is the one sanctioned
# `memory_stats()` owner — the ci/analysis gate forbids direct calls elsewhere in the
# framework (`# hbm-ok` waiver).
#
# SHARED LEDGER (docs/scheduling.md "The shared ledger"): both admission
# controllers here — `admit_fit` and `admit_model_load` — charge against the
# budget MINUS what the process-wide `scheduler.HbmLedger` already holds, and
# every admission reserves its estimate there. A fit running next to resident
# serving models (or other co-admitted fits) can no longer jointly overshoot
# HBM: the fit sees the models' reserved bytes and demotes/refuses
# accordingly, and vice versa. The companion ci/analysis rule `ledger-bypass`
# keeps capacity math in this module and `scheduler/` (`# ledger-ok` waiver
# at the two sanctioned call sites).
#
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import numpy as np

from .errors import HbmBudgetError

RESIDENT = "resident"
STREAM = "stream"

# floor for auto-derived streaming chunk rows: chunks smaller than this spend
# more wall time on dispatch than transfer
MIN_STREAM_CHUNK_ROWS = 256
# auto chunk size when no capacity information bounds it
DEFAULT_STREAM_CHUNK_ROWS = 65536


@dataclass
class MemoryEstimate:
    """A per-device byte estimate as named terms (placement.X, workspace.gram,
    ...) so failures and logs can name the dominant line item."""

    terms: Dict[str, int] = field(default_factory=dict)

    def total(self) -> int:
        return int(sum(self.terms.values()))

    def largest(self) -> Tuple[str, int]:
        if not self.terms:
            return ("", 0)
        name = max(self.terms, key=lambda k: self.terms[k])
        return (name, int(self.terms[name]))


@dataclass
class AdmissionDecision:
    """The verdict `core` applies at fit entry. `estimate` is the per-device
    working set backing the verdict (the RESIDENT one for resident fits, the
    STREAMING one for demoted fits); `chunk_rows` is the admitted streaming
    chunk size (0 on the resident path); `demoted` marks a fit that ASKED for
    residency and was demoted (budget, or an OOM-retry force)."""

    verdict: str
    estimate: MemoryEstimate
    capacity_bytes: Optional[int] = None
    budget_bytes: Optional[int] = None
    chunk_rows: int = 0
    reason: str = ""
    demoted: bool = False
    # devices the admitted working set spans — the chip-seconds multiplier
    # for the ledger's per-tenant accounting (a cache-hit re-reserve must
    # charge the same chips the original admission did)
    chips: int = 1
    # the shared-ledger claim backing this admission (scheduler.HbmReservation),
    # or None when a scheduler job owns the claim (the job's reservation was
    # RESIZED instead — the scheduler releases it at job end). Fit-side claims
    # are released by the fit driver's finally (core._call_fit_func); serving
    # claims by ModelRegistry eviction.
    reservation: Any = None

    def stamp(self) -> Dict[str, Any]:
        """The JSON-able summary `core` stamps onto ``model._fit_metrics``."""
        name, nbytes = self.estimate.largest()
        return {
            "verdict": self.verdict,
            "reason": self.reason,
            "estimate_bytes": self.estimate.total(),
            "capacity_bytes": self.capacity_bytes,
            "budget_bytes": self.budget_bytes,
            "chunk_rows": self.chunk_rows,
            "largest_term": name,
            "largest_term_bytes": nbytes,
        }


def rows_per_device(n_rows: int, n_devices: int) -> int:
    """Padded per-device row count of the mesh layout: rows are padded to a
    multiple of the device count (mesh.shard_row_slices semantics)."""
    n_devices = max(1, int(n_devices))
    n_pad = -(-max(0, int(n_rows)) // n_devices) * n_devices
    return n_pad // n_devices


def ell_k_max(csr: Any) -> int:
    """Widest-row nnz of a scipy CSR — the padded-ELL row width (min 1,
    mirroring ops/sparse.csr_to_ell)."""
    if csr.shape[0] == 0:
        return 1
    return max(1, int(np.diff(csr.indptr).max()))


def placement_terms(
    extracted: Any, dtype: Any, n_devices: int, x_layout: str = "default"
) -> Dict[str, int]:
    """Per-device HBM bytes of the resident placement of `extracted`.

    Dense: the row-sharded [n_pad, d] block (rows padded to a multiple of the
    device count); placed row-major (`x_layout`, the estimator's
    `_x_layout`) every row is padded to whole 128-lane tiles, which the
    block then really occupies (2.4 % at d = 3,000, nothing at 3,072).
    Sparse: the CSR->ELL conversion's values [n_pad, k_max] +
    int32 indices [n_pad, k_max] — the padding cells are REAL placed bytes,
    which is exactly why a skewed k_max can blow the budget. The label column
    (when supervised data carries one) and the weight vector ride along as one
    scalar per row each. Pinned against analytic byte counts by
    tests/test_memory.py."""
    itemsize = int(np.dtype(dtype).itemsize)
    rows_dev = rows_per_device(extracted.n_rows, n_devices)
    terms: Dict[str, int] = {}
    if extracted.is_sparse:
        k_max = ell_k_max(extracted.features)
        terms["placement.ell_values"] = rows_dev * k_max * itemsize
        terms["placement.ell_indices"] = rows_dev * k_max * 4  # int32
    else:
        n_cols = int(extracted.n_cols)
        if x_layout == "row_major":
            n_cols = -(-n_cols // 128) * 128
        terms["placement.X"] = rows_dev * n_cols * itemsize
    if extracted.label is not None:
        terms["placement.y"] = rows_dev * itemsize
    terms["placement.w"] = rows_dev * itemsize
    return terms


def row_bytes(extracted: Any, dtype: Any) -> int:
    """Placed bytes of ONE row (features + label + weight) — the streaming
    chunk sizing unit. ELL rows cost k_max * (4 + itemsize)."""
    itemsize = int(np.dtype(dtype).itemsize)
    if extracted.is_sparse:
        per_row = ell_k_max(extracted.features) * (4 + itemsize)
    else:
        per_row = int(extracted.n_cols) * itemsize
    if extracted.label is not None:
        per_row += itemsize
    return per_row + itemsize  # + weight


def workspace_estimate(
    estimator: Any, extracted: Any, n_devices: int, rows_dev: Optional[int] = None
) -> MemoryEstimate:
    """Per-solver workspace terms from the estimator hook
    (`_solver_workspace_terms`), prefixed ``workspace.``.

    `rows_dev` is the per-device row count ROW-SCALING terms are evaluated
    at: the full padded shard for a resident fit (default), the CHUNK shard
    for a streaming one — out-of-core solvers only ever hold one chunk's
    logits / tile buffers on device (accumulators, gram blocks, and L-BFGS
    history are row-count independent and unaffected)."""
    dtype = np.float32 if getattr(estimator, "_float32_inputs", True) else np.float64
    itemsize = int(np.dtype(dtype).itemsize)
    if rows_dev is None:
        rows_dev = rows_per_device(extracted.n_rows, n_devices)
    hook = getattr(estimator, "_solver_workspace_terms", None)
    terms: Dict[str, int] = {}
    if hook is not None:
        raw = hook(rows_dev, int(extracted.n_cols), dict(estimator._solver_params), itemsize)
        for name, nbytes in (raw or {}).items():
            key = name if name.startswith("workspace.") else f"workspace.{name}"
            terms[key] = int(nbytes)
    return MemoryEstimate(terms)


def resident_estimate(
    estimator: Any, extracted: Any, n_devices: int
) -> MemoryEstimate:
    """Full resident working set: placement + solver workspace, per device."""
    dtype = np.float32 if getattr(estimator, "_float32_inputs", True) else np.float64
    x_layout = getattr(estimator, "_x_layout", "default")
    est = MemoryEstimate(dict(placement_terms(extracted, dtype, n_devices, x_layout)))
    est.terms.update(workspace_estimate(estimator, extracted, n_devices).terms)
    return est


def streaming_estimate(
    estimator: Any, extracted: Any, n_devices: int, chunk_rows: int
) -> MemoryEstimate:
    """Streaming working set: TWO chunks resident at once (the double buffer
    — chunk N computing while chunk N+1's transfer is in flight) plus the
    solver workspace with its row-scaling terms (per-row logits, assignment
    tile buffers) evaluated at the CHUNK shard — out-of-core solvers never
    hold more than one chunk's row-proportional state on device."""
    dtype = np.float32 if getattr(estimator, "_float32_inputs", True) else np.float64
    rb = row_bytes(extracted, dtype)
    # per-device: each device holds its shard of BOTH in-flight chunks
    chunk_dev = rows_per_device(chunk_rows, n_devices)
    full_dev = rows_per_device(extracted.n_rows, n_devices)
    est = MemoryEstimate({"stream.chunk_buffers": 2 * chunk_dev * rb})
    est.terms.update(
        workspace_estimate(
            estimator, extracted, n_devices, rows_dev=min(chunk_dev, full_dev)
        ).terms
    )
    return est


def device_capacity_bytes(
    mesh: Any = None, devices: Any = None, *, consume_chaos: bool = True
) -> Optional[int]:
    """Per-device HBM capacity the admission check budgets against.

    Resolution order: chaos-injected budget (`oom:budget=` fault — the
    shrunken-budget injection that makes the whole demotion ladder testable
    without a real TPU) > ``config["hbm_budget_bytes"]`` > the minimum
    ``Device.memory_stats()['bytes_limit']`` over the mesh devices (or the
    explicit `devices` list — the serving plane budgets its one local device
    without standing up a mesh). Returns None when nothing is known (CPU
    backend, no override) — no budgeting. ``consume_chaos=False`` skips the
    injected-budget probe WITHOUT spending a plan firing — the scheduler's
    bin-packing passes read capacity many times per admission, and each
    `oom:budget=` entry must demote exactly `times` FIT admissions."""
    from .core import config
    from .parallel import chaos

    if consume_chaos:
        injected = chaos.injected_hbm_budget()
        if injected is not None:
            return int(injected)
    override = config.get("hbm_budget_bytes")
    if override:
        return int(override)
    if devices is None:
        if mesh is None:
            return None
        devices = list(mesh.devices.flatten())
    limit: Optional[int] = None
    for d in devices:
        try:
            stats = d.memory_stats()  # hbm-ok: memory.py is the budget owner
        except Exception:
            stats = None
        if not stats:
            continue
        cap = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
        if cap:
            limit = int(cap) if limit is None else min(limit, int(cap))
    return limit


def headroom_fraction() -> float:
    from .core import config

    try:
        f = float(config.get("hbm_headroom_fraction", 0.1))
    except (TypeError, ValueError):
        return 0.1
    return min(max(f, 0.0), 0.9)


def _configured_chunk_rows() -> int:
    from .core import config

    try:
        return max(0, int(config.get("stream_chunk_rows", 0)))
    except (TypeError, ValueError):
        return 0


def _claimed_chips(job_res: Any = None) -> Optional[Tuple[int, ...]]:
    """The chip set this admission is scoped to, if any: the enclosing
    scheduler job's PLACED reservation (2-D co-admission), else the ambient
    `parallel.mesh.chip_scope` pin (a sweep shard or test carving a
    sub-mesh by hand). None means the legacy whole-pool contract."""
    if job_res is not None and getattr(job_res, "chip_ids", None) is not None:
        return tuple(job_res.chip_ids)
    from .parallel.mesh import current_chip_scope

    scoped = current_chip_scope()
    if scoped is None:
        return None
    return tuple(int(getattr(d, "id", i)) for i, d in enumerate(scoped))


def admit_fit(
    estimator: Any,
    extracted: Any,
    ctx: Any,
    *,
    force_stream: bool = False,
) -> AdmissionDecision:
    """Issue the admission verdict for one fit (see module docstring).

    Budgets against the capacity MINUS what the shared `scheduler.HbmLedger`
    already holds (resident serving models, co-admitted fits), and reserves
    the admitted estimate there — under the ledger's admission lock, so
    concurrent admissions cannot both claim the same free bytes. Inside a
    scheduler job (`scheduler.context.current_job`) the job's queue-time
    reservation is RESIZED instead of duplicated, and a job demoted after
    repeated preemption is force-streamed.

    Raises `HbmBudgetError` — naming the largest term — when even the
    streaming working set exceeds the remaining budget, when the estimator
    has no out-of-core path, or when the fit runs under multi-process SPMD
    (the streaming pipeline is single-controller; an SPMD over-budget fit
    must fail typed rather than OOM the clique). `force_stream` is the
    OOM-retry entry: skip the resident check and admit the streaming path
    (capacity may be unknown — a real allocation failure is evidence
    enough)."""
    from . import telemetry
    from .ops_plane import audit as _audit
    from .scheduler import context as _sched_ctx
    from .scheduler.ledger import global_ledger

    mesh = ctx.mesh
    n_devices = int(mesh.devices.size)
    capacity = device_capacity_bytes(mesh)
    budget = (
        None if capacity is None else int(capacity * (1.0 - headroom_fraction()))
    )
    if telemetry.enabled() and capacity is not None:
        telemetry.registry().gauge("memory.capacity_bytes", capacity)

    led = global_ledger()
    job = _sched_ctx.current_job()
    sched_demoted = job is not None and getattr(job, "demote_to_stream", False)
    if sched_demoted:
        force_stream = True
    job_res = getattr(job, "reservation", None) if job is not None else None
    my_chips = _claimed_chips(job_res)

    with led.admission():
        if budget is None:
            held = 0
        elif my_chips:
            # 2-D placement: a chip-scoped fit budgets against ITS chips'
            # byte book — bytes held by a co-admitted job on DISJOINT chips
            # must not shrink this fit's budget, while whole-pool claims
            # (chip_ids=None) still count everywhere
            held = max(
                led.reserved_bytes_on(c, exclude=job_res) for c in my_chips
            )
        else:
            held = led.reserved_bytes(exclude=job_res)
        avail = None if budget is None else max(0, budget - held)
        held_note = (
            f" ({held} bytes/device already reserved in the shared ledger "
            "by other fits/serving models"
            + (" on this fit's chip set" if my_chips else "")
            + ")"
            if held
            else ""
        )

        def _grant(est_obj, verdict, chunk_rows=0, reason="", demoted=False):
            """Record the admitted claim in the shared ledger and build the
            decision. Job-owned claims resize; standalone fits reserve."""
            if job_res is not None:
                led.resize(job_res, est_obj.total())
                reservation = None  # the scheduler releases the job's claim
            else:
                reservation = led.reserve(
                    f"fit:{type(estimator).__name__}", "fit", est_obj.total(),
                    chips=n_devices, chip_ids=my_chips,
                )
            led.note_admission(budget)
            # one audit-trail record per admission verdict — the queryable
            # side of the _fit_metrics["admission"] stamp (ops_plane.audit)
            _audit.record_decision(
                "demotion" if demoted else "admission", "fit", verdict,
                subject=type(estimator).__name__, reason=reason,
                estimate_bytes=est_obj.total(), budget_bytes=budget,
                chunk_rows=int(chunk_rows),
            )
            return AdmissionDecision(
                verdict=verdict,
                estimate=est_obj,
                capacity_bytes=capacity,
                budget_bytes=budget,
                chunk_rows=int(chunk_rows),
                reason=reason,
                demoted=demoted,
                chips=n_devices,
                reservation=reservation,
            )

        def _refuse(exc):
            led.note_admission(budget)  # refusals fire the admission hooks too
            _audit.record_decision(
                "admission", "fit", "refused",
                subject=type(estimator).__name__, reason=str(exc),
                estimate_bytes=getattr(exc, "estimate_bytes", None),
                budget_bytes=budget,
            )
            raise exc

        res = resident_estimate(estimator, extracted, n_devices)
        if not force_stream:
            if telemetry.enabled():
                telemetry.registry().gauge("memory.estimate_bytes", res.total())
            if avail is None or res.total() <= avail:
                return _grant(
                    res, RESIDENT,
                    reason="fits" if budget is not None else "no capacity information",
                )
            reason = (
                f"resident working set {res.total()} bytes/device exceeds the "
                f"{budget}-byte budget{held_note}"
            )
        elif sched_demoted:
            reason = (
                "scheduler demotion: preempted "
                f"{getattr(job, 'preemptions', 0)} time(s) "
                "(config['sched_max_preemptions'])"
            )
        else:
            reason = "backend OOM caught; retrying out-of-core"

        # ---- the streaming side of the ladder ----------------------------
        if not getattr(estimator, "_supports_streaming_fit", False):
            name, nbytes = res.largest()
            _refuse(HbmBudgetError(
                f"{type(estimator).__name__} fit does not fit device memory "
                f"and has no out-of-core streaming path{held_note}",
                estimate_bytes=res.total(),
                capacity_bytes=budget,
                largest_term=name,
                largest_term_bytes=nbytes,
                terms=res.terms,
            ))
        if ctx is not None and getattr(ctx, "is_spmd", False):
            name, nbytes = res.largest()
            _refuse(HbmBudgetError(
                f"{type(estimator).__name__} fit does not fit device memory; "
                "the out-of-core streaming path is single-controller only "
                "(multi-process SPMD fits must fit resident)",
                estimate_bytes=res.total(),
                capacity_bytes=budget,
                largest_term=name,
                largest_term_bytes=nbytes,
                terms=res.terms,
            ))

        dtype = np.float32 if getattr(estimator, "_float32_inputs", True) else np.float64
        rb = row_bytes(extracted, dtype)
        chunk_rows = _configured_chunk_rows()
        if chunk_rows <= 0:
            if avail is None:
                chunk_rows = DEFAULT_STREAM_CHUNK_ROWS
            else:
                # size against the floor-chunk workspace (row-scaling
                # workspace terms grow with the chunk; the post-sizing check
                # below shrinks back toward the floor if the chosen chunk's
                # full estimate overshoots)
                floor_dev = rows_per_device(
                    min(MIN_STREAM_CHUNK_ROWS, max(1, int(extracted.n_rows))), n_devices
                )
                ws = workspace_estimate(
                    estimator, extracted, n_devices, rows_dev=floor_dev
                ).total()
                room = avail - ws
                # two in-flight chunks per device; chunk rows are a whole-chunk
                # (all-devices) count, so a device holds chunk_rows/n_devices rows
                chunk_rows = max(
                    MIN_STREAM_CHUNK_ROWS,
                    (room // (2 * rb)) * n_devices if room > 0 else 0,
                )
        chunk_rows = max(1, min(int(chunk_rows), max(1, int(extracted.n_rows))))

        stream = streaming_estimate(estimator, extracted, n_devices, chunk_rows)
        if avail is not None and stream.total() > avail:
            # shrink toward the floor before giving up: the chunk size is the
            # only knob the admission controller owns
            floor = min(MIN_STREAM_CHUNK_ROWS, chunk_rows)
            stream_floor = streaming_estimate(estimator, extracted, n_devices, floor)
            if stream_floor.total() > avail:
                name, nbytes = stream_floor.largest()
                _refuse(HbmBudgetError(
                    f"{type(estimator).__name__} fit does not fit device "
                    "memory even on the out-of-core streaming "
                    f"path{held_note}",
                    estimate_bytes=stream_floor.total(),
                    capacity_bytes=budget,
                    largest_term=name,
                    largest_term_bytes=nbytes,
                    terms=stream_floor.terms,
                ))
            chunk_rows, stream = floor, stream_floor
        if telemetry.enabled():
            telemetry.registry().gauge("memory.estimate_bytes", stream.total())
        return _grant(
            stream, STREAM, chunk_rows=chunk_rows, reason=reason, demoted=True
        )


# ------------------------------------------------------- serving plane ------


def model_serve_estimate(model: Any, bucket_rows_count: int) -> MemoryEstimate:
    """Per-device working set of a RESIDENT serving model: the placement of
    its state arrays (`_serve_placement_terms` — replicated, so per-device =
    full size) plus the per-bucket predict workspace
    (`_serve_workspace_terms` at the ladder cap), exactly the fit-side
    placement + workspace split (module docstring)."""
    dtype = np.float32 if getattr(model, "_float32_inputs", True) else np.float64
    itemsize = int(np.dtype(dtype).itemsize)
    terms: Dict[str, int] = {}
    hook = getattr(model, "_serve_placement_terms", None)
    for name, nbytes in ((hook() if hook is not None else None) or {}).items():
        key = name if name.startswith("placement.") else f"placement.{name}"
        terms[key] = int(nbytes)
    whook = getattr(model, "_serve_workspace_terms", None)
    raw = whook(int(bucket_rows_count), itemsize) if whook is not None else None
    for name, nbytes in (raw or {}).items():
        key = name if name.startswith("workspace.") else f"workspace.{name}"
        terms[key] = int(nbytes)
    return MemoryEstimate(terms)


def admit_model_load(
    model: Any,
    *,
    resident_bytes: int = 0,
    bucket_rows_count: Optional[int] = None,
    devices: Any = None,
    tenant: Optional[str] = None,
    chip_ids: Any = None,
) -> AdmissionDecision:
    """Admission verdict for loading a fitted model into the serving plane
    (docs/serving.md): params get a placement estimate and a per-bucket
    predict workspace term, exactly like fits. `resident_bytes` is what the
    registry's already-resident models hold — the load is admitted against
    the REMAINING budget. There is no streaming demotion for serving (a
    model either resides or the load is refused typed), so the two verdicts
    are RESIDENT or a raised `HbmBudgetError` naming the largest term; the
    caller (serving.ModelRegistry) may evict LRU residents and retry.

    Charges against the budget MINUS the shared ledger's held bytes — a
    concurrently running fit's placement + workspace now counts against a
    model load exactly as resident models count against fits (the
    shared-ledger contract, docs/scheduling.md) — and reserves the admitted
    estimate there (kind "serve", released by the registry on eviction).
    `resident_bytes` remains for callers outside the registry that account
    residents themselves; the registry passes 0 (its residents already hold
    ledger reservations).

    `chip_ids` places the replica on an explicit chip set (2-D book,
    docs/scheduling.md "2-D placement"): the byte check runs against those
    chips' book only, and the reservation claims them EXCLUSIVELY — a
    4-chip serving replica co-admits beside a 4-chip fit on the other half
    of the mesh instead of serializing against it. Defaults to the ambient
    `chip_scope` pin when one is active, else the legacy whole-pool claim."""
    from . import telemetry
    from .core import config
    from .ops_plane import audit as _audit
    from .scheduler.ledger import global_ledger

    if bucket_rows_count is None:
        bucket_rows_count = int(config.get("serve_max_batch_rows", 8192))
    if tenant is None:
        # per-model serving tenants ("serving:<name>") so tenant_usage() and
        # eviction can weigh actual per-model byte-seconds instead of one
        # undifferentiated "serving" bucket; type name is the fallback when
        # the caller has no registry name for the model
        tenant = f"serving:{type(model).__name__}"
    capacity = device_capacity_bytes(devices=devices)
    budget = (
        None if capacity is None else int(capacity * (1.0 - headroom_fraction()))
    )
    led = global_ledger()
    if chip_ids is None:
        chip_ids = _claimed_chips()
    else:
        chip_ids = tuple(int(c) for c in chip_ids)
    with led.admission():
        if budget is None:
            held = 0
        elif chip_ids:
            held = max(led.reserved_bytes_on(c) for c in chip_ids)
        else:
            held = led.reserved_bytes()
        est = model_serve_estimate(model, bucket_rows_count)
        if telemetry.enabled():
            telemetry.registry().gauge("memory.serve_estimate_bytes", est.total())
        if budget is None or est.total() + int(resident_bytes) + held <= budget:
            # serving residents are shared infrastructure, accounted to a
            # per-model "serving:<name>" tenant (not whichever tenant's
            # thread loaded them)
            reservation = led.reserve(
                f"serve:{type(model).__name__}", "serve", est.total(),
                tenant=tenant, chip_ids=chip_ids,
            )
            led.note_admission(budget)
            _audit.record_decision(
                "admission", "serving", RESIDENT,
                subject=type(model).__name__, tenant=tenant,
                estimate_bytes=est.total(), budget_bytes=budget,
            )
            return AdmissionDecision(
                verdict=RESIDENT,
                estimate=est,
                capacity_bytes=capacity,
                budget_bytes=budget,
                reason="fits" if budget is not None else "no capacity information",
                reservation=reservation,
            )
        led.note_admission(budget)
        name, nbytes = est.largest()
        _audit.record_decision(
            "admission", "serving", "refused",
            subject=type(model).__name__, tenant=tenant,
            reason="over budget", estimate_bytes=est.total(),
            budget_bytes=budget, largest_term=name,
        )
        raise HbmBudgetError(
            f"{type(model).__name__} load does not fit the serving budget "
            f"({int(resident_bytes)} bytes already resident, {held} "
            "bytes/device held in the shared ledger)",
            estimate_bytes=est.total(),
            capacity_bytes=budget,
            largest_term=name,
            largest_term_bytes=nbytes,
            terms=est.terms,
        )


def release_admission(adm: Optional[AdmissionDecision]) -> None:
    """Return an admission's shared-ledger claim (idempotent; None-safe for
    `finally` blocks). No-op for job-owned admissions (their `reservation`
    is None — the scheduler releases the job's claim at job end)."""
    if adm is None or adm.reservation is None:
        return
    from .scheduler.ledger import global_ledger

    global_ledger().release(adm.reservation)
    adm.reservation = None


def rereserve_admission(adm: AdmissionDecision, owner: str = "fit:cache-hit"):
    """Shared-ledger claim for a fit served from the device-dataset scope
    CACHE (the placement physically exists; a cache hit skips `admit_fit`).
    Bookkeeping-only — no budget check: the bytes are already held, so the
    honest move is to record them, and later admissions will see them.
    Inside a scheduler job the job's reservation is resized instead and
    None is returned (job-owned)."""
    from .scheduler import context as _sched_ctx
    from .scheduler.ledger import global_ledger

    led = global_ledger()
    job = _sched_ctx.current_job()
    job_res = getattr(job, "reservation", None) if job is not None else None
    if job_res is not None:
        led.resize(job_res, adm.estimate.total())
        return None
    return led.reserve(
        owner, "fit", adm.estimate.total(), chips=getattr(adm, "chips", 1),
        chip_ids=_claimed_chips(),
    )


# ------------------------------------------------------------------ OOM -----


def is_oom_error(exc: BaseException) -> bool:
    """Whether `exc` is a backend out-of-memory failure the fit driver should
    convert to `HbmBudgetError` (and retry once out-of-core). Matches XLA's
    RESOURCE_EXHAUSTED surface (jaxlib raises it as a RuntimeError subclass)
    and plain MemoryError; an already-typed `HbmBudgetError` is NOT matched —
    it must propagate, not re-enter the conversion. Neither is a kernel that
    does not COMPILE: Mosaic reports a scoped-VMEM overflow as
    RESOURCE_EXHAUSTED too ("Ran out of memory in memory space vmem"), but
    streaming the rows runs the same kernel into the same refusal — it must
    surface as itself, once."""
    if isinstance(exc, HbmBudgetError):
        return False
    if isinstance(exc, MemoryError):
        return True
    if not isinstance(exc, RuntimeError):
        return False
    msg = str(exc)
    if "memory space vmem" in msg or "Mosaic" in msg:
        return False
    return "RESOURCE_EXHAUSTED" in msg or "out of memory" in msg.lower()


def as_hbm_budget_error(exc: BaseException) -> HbmBudgetError:
    """Wrap a caught backend OOM as the typed, permanent `HbmBudgetError`
    (no estimate attached — the backend, not the preflight, made the call)."""
    return HbmBudgetError(f"backend out-of-memory during fit: {exc}")
