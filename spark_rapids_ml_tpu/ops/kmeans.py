#
# Distributed KMeans solver — the in-tree replacement for
# `cuml.cluster.kmeans_mg.KMeansMG` (consumed by reference clustering.py:353).
#
# Lloyd iterations as an explicit SPMD program (`shard_map` over the rows axis):
# each device scans its row block in fixed-size tiles (the reference's
# `max_samples_per_batch` memory knob, clustering.py:110-121) through the
# SHARED tiled distance core (ops/distance.py — fused assignment + one-hot
# accumulation, Pallas-k-tiled on TPU); partial (k,d) sums/counts/inertia are
# `psum`'d across devices — the NCCL allreduce the cuML MG solver does
# internally. The outer loop is a `lax.while_loop` on center movement +
# max_iter, so the whole fit is ONE XLA program: no per-iteration host
# round-trips.
#
from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from .. import telemetry
from ..parallel.mesh import ROWS_AXIS, lies_row_major, x_layout_of
from .distance import (
    argmin_assign,
    assign_accumulate,
    assign_accumulate_rows,
    block_plan,
    min_d2_update,
    row_sq,
    shard_map_check_vma,
    tile_assign_accumulate as _tile_assign_accumulate,
)

# jitted once per shape: the seeding paths dispatch these eagerly per round
_min_d2_update = jax.jit(min_d2_update)


def _finish_centers(sums, counts, inertia, centers):
    # empty clusters keep their previous center (cuML behavior)
    new_centers = jnp.where(
        counts[:, None] > 0, sums / jnp.maximum(counts, 1e-30)[:, None], centers
    )
    shift = jnp.sum((new_centers - centers) ** 2)
    return new_centers, inertia, shift


_finish_centers_jit = jax.jit(_finish_centers)


# |x|^2 of every row, made ONCE a fit (X does not change within one): one
# read of X, and every Lloyd step, the float32 final pass included, slices
# its tile's 128 KB out of the result
_row_norms = jax.jit(row_sq)


@partial(jax.jit, static_argnames=("mesh", "batch_rows", "fast", "in_place"))
def _lloyd_step(X, w, centers, x_sq, *, mesh, batch_rows, fast=False, in_place=False):
    """One Lloyd iteration as a TOP-LEVEL XLA program: per-shard tiled
    assignment + accumulation, psum'd (k,d) sums/counts/inertia, center update.

    Kept out of a `lax.while_loop` deliberately: XLA duplicates an array that
    a SLICE inside nested loops consumes (the tile scan inside a while body
    costs +1 full copy of X — 11 GiB at the 1M x 3k benchmark shape, an OOM on
    one chip; an X that the kernels index `in_place` is not duplicated by a
    loop round them, and whether that holds two loops deep is not tried). The
    iteration loop lives on the host instead; each step is one dispatch (~ms)
    against seconds of compute, and the convergence scalar is a replicated
    global value so every SPMD rank steps identically."""

    def local(Xl, wl, ql):
        sums, counts, inertia = _tile_assign_accumulate(
            Xl, wl, centers, ql, batch_rows, fast, in_place
        )
        sums = jax.lax.psum(sums, ROWS_AXIS)
        counts = jax.lax.psum(counts, ROWS_AXIS)
        inertia = jax.lax.psum(inertia, ROWS_AXIS)
        return sums, counts, inertia

    sums, counts, inertia = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(ROWS_AXIS, None), P(ROWS_AXIS), P(ROWS_AXIS)),
        out_specs=(P(), P(), P()),
        check_vma=shard_map_check_vma(),
    )(X, w, x_sq)
    return _finish_centers(sums, counts, inertia, centers)


@partial(jax.jit, static_argnames=("batch_rows", "fast", "in_place"))
def _lloyd_step_fused_1dev(X, w, centers, x_sq, *, batch_rows, fast=False, in_place=False):
    """One Lloyd iteration as ONE local program (no mesh, no collectives):
    the in-program tile scan of `_tile_assign_accumulate` plus the center
    update. This is the small-dataset single-device path — it must NOT touch
    a Mesh: under multi-process SPMD a 1-device `get_mesh(1)` holds GLOBAL
    device 0, which other ranks cannot address, while per-rank local fits
    (e.g. each rank's ANN coarse quantizer) run on the rank's own default
    device. Where the tiles are sliced the in-program scan may double-buffer
    X (see _tile_accum_1dev) — affordable below _ONE_DISPATCH_MAX_BYTES,
    where this path is used."""
    sums, counts, inertia = _tile_assign_accumulate(
        X, w, centers, x_sq, batch_rows, fast, in_place
    )
    return _finish_centers(sums, counts, inertia, centers)


@partial(jax.jit, static_argnames=("size", "fast", "in_place"), donate_argnums=(4, 5, 6))
def _tile_accum_1dev(
    X, w, centers, x_sq, sums, counts, inertia, start, *, size, fast=False, in_place=False
):
    """Single-device tile accumulation: one tile a program, at the PROGRAM
    TOP LEVEL (no in-program loop over X at all). XLA's choice to duplicate
    an operand that a slice inside a loop consumes is size-dependent — at
    the 1M x 3k benchmark shape even the fori_loop-of-dynamic_slice form
    gets a full X copy — so on one device the tile loop lives on the host
    and the (k,d) accumulators are DONATED device buffers updated in place.
    The per-tile math is the shared core's fused assign+accumulate
    (ops/distance.py `assign_accumulate_rows`).

    The program is compiled for the layout the committed X has, which the
    placement chose (parallel/mesh.py `make_global_rows`; KMeans asks for
    row-major through `_x_layout`), and `kmeans_fit` says `in_place` where
    that is row-major: the kernels then fetch their row blocks out of X by
    `start`, and the compiled program holds no value of the tile's shape and
    no copy (so the host loop is no longer what keeps X single-buffered:
    folding it into one program is ROADMAP D3). Sliced, a row-major tile is
    written out once by the `dynamic_slice`; in a TPU's default for
    [n, 3000] (column-major) it is sliced and turned by a tile-sized
    `copy`, 12 times an iteration at the benchmark shape
    (tests/test_distance.py pins the three texts)."""
    s, c, i = assign_accumulate_rows(
        X, w, centers, x_sq, start, size, fast=fast, in_place=in_place
    )
    return sums + s, counts + c, inertia + i


def _lloyd_step_1dev(X, w, centers, x_sq, batch_rows, fast=False, in_place=False):
    """Host-tiled Lloyd iteration for a 1-device mesh (see _tile_accum_1dev);
    the ragged tail is sliced whatever `in_place` says of the full tiles."""
    import numpy as np

    n, d = X.shape
    k = centers.shape[0]
    dtype = X.dtype
    batch_rows = min(batch_rows, n)
    sums = jnp.zeros((k, d), dtype)
    counts = jnp.zeros((k,), dtype)
    inertia = jnp.zeros((), dtype)
    n_full = (n // batch_rows) * batch_rows
    for start in range(0, n_full, batch_rows):
        sums, counts, inertia = _tile_accum_1dev(
            X, w, centers, x_sq, sums, counts, inertia, np.int32(start),
            size=batch_rows, fast=fast, in_place=in_place,
        )
    if n - n_full:
        sums, counts, inertia = _tile_accum_1dev(
            X, w, centers, x_sq, sums, counts, inertia, np.int32(n_full),
            size=n - n_full, fast=fast,
        )
    return _finish_centers_jit(sums, counts, inertia, centers)


# Below this size a 1-device fit takes the SAME one-dispatch-per-iteration
# program as the mesh path (fori_loop of tiles inside one program). The
# host-tiled `_lloyd_step_1dev` exists to keep the big-X regime
# single-buffered (XLA copies an X that a slice inside a loop consumes at
# the 1M×3k protocol shape; not one that the kernels index `in_place`, so
# for a row-major X the split has lost its reason: ROADMAP D3), but it costs
# one dispatch PER TILE; below this cap the in-program
# X copy is affordable and one dispatch per iteration is the simpler form.
# The iteration loop itself stays on the host (see `_lloyd_step`). Where the
# cap belongs on the current machine is not measured (ROADMAP D12).
_ONE_DISPATCH_MAX_BYTES = 2 << 30


@partial(jax.jit, static_argnames=("fast",))
def block_assign_accumulate(
    xb: jax.Array, wb: jax.Array, centers: jax.Array, fast: bool = False
):
    """One streaming chunk's Lloyd contribution: (sums [k,d], counts [k],
    inertia) — the shared core's fused assign+accumulate
    (ops/distance.py), over ONE placed row block. The out-of-core driver
    (ops/streaming.py) sums these per-chunk partials across the
    double-buffered pipeline; padding rows carry zero weight, so they
    contribute nothing — exactly the resident pad contract. `fast` runs the
    chunk's distance matmuls in the parity-tested fast-bf16 mode; the
    streaming driver keeps its final inertia pass at full precision."""
    return assign_accumulate(xb, wb, centers, fast=fast)


def kmeans_ckpt_key(init_centers, max_iter: int, tol: float) -> str:
    """Trajectory-identifying checkpoint key shared by the resident and
    streaming Lloyd loops: init-centers digest + shape + loop statics. ONE
    format for both, so a resident fit's checkpoint resumes a streaming
    retry (the OOM-demotion ladder) and vice versa — centers are replicated,
    fully portable state."""
    import hashlib

    import numpy as np

    init_digest = hashlib.sha1(
        np.ascontiguousarray(np.asarray(init_centers)).tobytes()
    ).hexdigest()[:12]
    shape = tuple(np.shape(init_centers))
    return f"kmeans:{shape}:{init_digest}:{max_iter}:{tol}"


def _raise_diverged(iteration: int, last_good_centers, detail: str) -> None:
    """Typed divergence error off the already-fetched per-iteration shift:
    carries the iterate that ENTERED the diverging update (still finite)."""
    import numpy as np

    from ..errors import SolverDivergedError

    telemetry.registry().inc("solver.divergence")
    telemetry.registry().inc("kmeans.divergence")
    raise SolverDivergedError(
        "kmeans",
        iteration,
        last_good={"cluster_centers_": np.asarray(last_good_centers)},
        detail=detail,
    )


def kmeans_fit(
    X: jax.Array,
    w: jax.Array,
    init_centers: jax.Array,
    *,
    mesh,
    max_iter: int = 20,
    tol: float = 1e-4,
    batch_rows: int = 32768,
    precision_mode: str = "fast",
    final_inertia: bool = True,
    to_host: Optional[Callable[[Dict[str, jax.Array]], Any]] = None,
) -> Dict[str, jax.Array]:
    """Lloyd's algorithm on a row-sharded global X. Returns
    cluster_centers_ [k,d], inertia_, n_iter_.

    Two once-per-fit telemetry spans (children of the caller's `fit/solve`):
    `loop` — the row norms' one program and the iterations, dispatched up to
    the last shift fetch, with the path taken, the block plan, the layout X
    has on the device (`x_layout`) and how the kernels get at a tile of it
    (`tile_access`: `in_place` or `sliced`) as attributes — and `finish` — the final
    inertia pass and, through `to_host` (the estimator's conversion of the
    returned state, run inside the span), the model's attributes brought to
    the host. Neither adds a device synchronisation.

    Convergence: squared center movement <= tol (sklearn/cuML semantics; the
    reference maps Spark's `tol` straight through, clustering.py:96-108).
    Host-stepped loop, one step an iteration (see `_lloyd_step`'s docstring
    for why the loop is not a `lax.while_loop`): on a mesh the `shard_map`
    program `_lloyd_step`; on one device `_lloyd_step_fused_1dev`, one program
    an iteration, while X is under `_ONE_DISPATCH_MAX_BYTES`, and above it
    the host-tiled `_lloyd_step_1dev`, one program a tile. In all three the
    kernels' blocks are `distance.block_plan`'s.

    The deferred (pipelined) convergence check means `n_iter_` can be ONE
    HIGHER than sklearn/cuML would report for the same tol crossing — the
    extra iteration runs at the converged fixpoint, so centers match. With
    ``final_inertia=False`` no trustworthy inertia exists (the in-loop value
    is a stale, possibly-bf16 partial) and `inertia_` is returned as NaN.

    precision_mode: "fast" (default for f32) runs the IN-LOOP distance and
    center-update matmuls in one-pass bf16 (see distance._mm — 1.6× per iteration at
    the protocol shape, true inertia agrees to ~1e-5); "high" keeps the
    ambient (3-pass-bf16 "f32") precision everywhere. f64 inputs always run
    "high". The final reported inertia is high-precision in both modes."""
    import numpy as np

    from .. import checkpoint as _ckpt

    centers = jnp.asarray(init_centers)
    fast = precision_mode == "fast" and X.dtype == jnp.float32
    inertia = jnp.zeros((), X.dtype)
    n_iter = 0
    one_dev = mesh.devices.size == 1
    host_tiled = one_dev and X.size * X.dtype.itemsize > _ONE_DISPATCH_MAX_BYTES

    rows_dev = max(1, -(-X.shape[0] // mesh.devices.size))
    tile = min(batch_rows, rows_dev)
    # resolved OUTSIDE any trace: the plan the tile programs will take, per
    # precision mode (None = the jnp form; the float32 final pass plans its own
    # blocks), which also settles `distance.kernel_mode()` eagerly
    plans = {
        f: block_plan(tile, centers.shape[0], X.shape[1], X.dtype, f) for f in {fast, False}
    }
    block_rows, block_k = plans[fast] or (None, None)
    # ... and how the kernels get at a tile: where X lies row-major on its
    # device (read here from the committed array; no trace sees a layout) and
    # the tile is whole row blocks they index X itself, else the tile is sliced
    # out first. A column-major X (the TPU's default at d = 3,000: every
    # caller that did not place it row-major) must stay sliced: handed whole
    # to a Mosaic operand it would be turned whole in every tile program.
    row_major = lies_row_major(X)
    in_place = {
        f: row_major and plan is not None and tile % plan[0] == 0 for f, plan in plans.items()
    }

    def step(c, x_sq, f):
        if host_tiled:
            return _lloyd_step_1dev(X, w, c, x_sq, batch_rows, fast=f, in_place=in_place[f])
        if one_dev:  # meshless local program (see _lloyd_step_fused_1dev)
            return _lloyd_step_fused_1dev(
                X, w, c, x_sq, batch_rows=batch_rows, fast=f, in_place=in_place[f]
            )
        return _lloyd_step(
            X, w, c, x_sq, mesh=mesh, batch_rows=batch_rows, fast=f, in_place=in_place[f]
        )

    # convergence is tested one iteration LATE: fetching the shift scalar
    # synchronizes with the device; checking the PREVIOUS iteration's shift
    # overlaps the fetch with the current step's compute. At most one extra
    # Lloyd iteration runs after the tol crossing (same fixpoint).
    # Convergence trace + divergence guard: the shift scalar for iteration
    # i-1 is fetched here ANYWAY (the deferred check), so both the telemetry
    # point and the NaN/Inf check cost no extra device synchronization.
    prev_shift = None
    last_good = centers  # iterate entering the step that produced prev_shift
    # runtime numerics sanitizer (SRML_NUMCHECK=1): resolved ONCE per solve;
    # disabled = a None local, one `is not None` test per boundary
    from ..utils import numcheck

    _nc = numcheck.hook()
    # Solver checkpoints (docs/robustness.md "Elastic recovery"): the host
    # loop already fetches the shift scalar every iteration, so host-fetching
    # the centers at the configured cadence is near-free. Centers are
    # REPLICATED state — fully portable across meshes — so a resume after a
    # transient retry or a survivor re-mesh restarts Lloyd from the
    # checkpointed iterate: bit-identical on the same mesh (the host
    # round-trip is lossless and each step depends only on (X, w, centers)),
    # deterministic given the survivor set on a degraded one.
    ckpt_store = _ckpt.active_store()
    ckpt_every = _ckpt.every_iters()
    ckpt_key = None
    if ckpt_store is not None and ckpt_every > 0:
        # the key must identify THIS solve's trajectory, not just its shape:
        # sequential param sets in one fit stage (a maxIter/tol sweep, or a
        # different init seed) share the store, and a shape-only key would
        # resume solve N from solve N-1's converged state. The init-centers
        # fingerprint (one tiny host fetch, once per fit) plus the loop
        # statics pin the trajectory; tol/maxIter only move the STOP point
        # on it, but keying them too keeps the entries disjoint and cheap.
        # The fast flag is part of the trajectory too (bf16 assignments walk
        # a different path), so bf16 keys apart — same suffix on the
        # streaming driver, preserving the resident<->streaming sharing.
        ckpt_key = kmeans_ckpt_key(init_centers, max_iter, tol)
        if fast:
            ckpt_key = ckpt_key + ":bf16"
        saved = ckpt_store.load(ckpt_key)
        if saved is not None and tuple(saved.state["centers"].shape) == tuple(
            jnp.shape(centers)
        ):
            centers = jnp.asarray(saved.state["centers"], dtype=X.dtype)
            # last_good is the iterate ENTERING the step that produced
            # prev_shift — one step BEHIND the checkpointed centers. Restore
            # it too, so a divergence detected right after resume reports the
            # same last-good iterate an uninterrupted run would.
            lg = saved.state.get("last_good")
            last_good = centers if lg is None else jnp.asarray(lg, dtype=X.dtype)
            n_iter = int(saved.iteration)
            ps = saved.state.get("prev_shift")
            prev_shift = None if ps is None else float(ps)
    with telemetry.span(
        "loop",
        solver_path="host_tiled" if host_tiled else "fused_1dev" if one_dev else "shard_map",
        tiles_per_iter=-(-rows_dev // tile),
        block_rows=block_rows,
        block_k=block_k,
        x_layout=x_layout_of(X),
        tile_access="in_place" if in_place[fast] else "sliced",
    ):
        x_sq = _row_norms(X)  # one asynchronous program a fit; nothing waits for it here
        while n_iter < max_iter:
            step_in = centers
            centers, inertia, shift = step(centers, x_sq, fast)
            n_iter += 1
            if prev_shift is not None:
                # the deferred shift fetch is Lloyd's per-iteration sync — the
                # efficiency attributor times the wait as `execute` (this IS the
                # solver cadence point; no sync added)
                with telemetry.device_wait("kmeans_shift"):
                    shift_host = float(prev_shift)  # host-fetch-ok: the DEFERRED convergence fetch (documented above) — overlapped with the current step's compute
                if not math.isfinite(shift_host):
                    _raise_diverged(n_iter - 1, last_good, f"center shift = {shift_host}")
                if _nc is not None:
                    # AFTER the divergence guard (typed SolverDivergedError owns
                    # non-finite shifts); sweeps the already-fetched scalar and
                    # records the iterate's dtype watermark without a new fetch
                    _nc("kmeans.iterate", solver="kmeans", iteration=n_iter - 1,
                        watermark=centers.dtype, shift=shift_host)
                if telemetry.enabled():
                    telemetry.record_convergence_point("kmeans.shift", n_iter - 1, shift_host)
                if shift_host <= tol:
                    break
            prev_shift = shift
            last_good = step_in
            if ckpt_store is not None and ckpt_every > 0 and n_iter % ckpt_every == 0:
                # the cadence fetch of prev_shift syncs with the device — the
                # documented checkpoint overhead; the float survives the
                # round-trip exactly, so the resumed convergence pipeline sees
                # the same value the uninterrupted run would
                with telemetry.device_wait("kmeans_checkpoint"):
                    prev_shift = float(prev_shift)  # host-fetch-ok: checkpoint-cadence boundary (config["checkpoint_every_iters"])
                    centers_host = np.asarray(centers)  # host-fetch-ok: the checkpoint itself — replicated centers must land on host to survive
                if _nc is not None:
                    # the checkpoint already fetched the full iterate: sweep it
                    # (a non-finite checkpoint would poison every later resume)
                    _nc("kmeans.checkpoint", solver="kmeans", iteration=n_iter,
                        centers=centers_host)
                with telemetry.host_section("kmeans_checkpoint"):
                    ckpt_store.save(ckpt_key, _ckpt.SolverCheckpoint(
                        solver="kmeans", iteration=n_iter,
                        state={
                            "centers": centers_host,
                            "prev_shift": prev_shift,
                            # the divergence-fallback iterate (one step behind)
                            "last_good": np.asarray(last_good),  # host-fetch-ok: checkpoint payload (one step behind, for divergence fallback)
                        },
                    ))
                # mid-solve fault injection points (`fail:stage=solve` and
                # `oom:stage=solve` plans): both fire AFTER the boundary
                # checkpoint landed, so a retried fit — bounded transient retry
                # or the OOM demotion to the streaming path — provably resumes
                # instead of restarting Lloyd from scratch
                from ..parallel import chaos

                chaos.maybe_fail_oom("solve", n_iter)
                chaos.maybe_fail_stage("solve", n_iter)
                # cooperative scheduler preemption (docs/scheduling.md): checked
                # where the loop already host-fetched (the cadence shift fetch
                # above), AFTER the boundary checkpoint landed — a preempted
                # fit resumes from exactly this iterate
                from ..scheduler.context import preemption_point

                preemption_point("kmeans", n_iter)
    if telemetry.enabled():
        telemetry.record_solver_result("kmeans", n_iter=n_iter)
    with telemetry.span("finish"):
        # inertia reported is one iteration stale; recompute once with final
        # centers — always at high precision. Callers that don't consume inertia
        # (e.g. the IVF coarse quantizer) skip the pass: the high-precision
        # program is a separate ~79s compile in a fresh process. The stale value
        # must not leak to them either — return NaN so accidental consumption is
        # loud instead of subtly wrong.
        if final_inertia:
            _, inertia, _ = step(centers, x_sq, False)
            with telemetry.device_wait("kmeans_inertia"):
                inertia_host = float(inertia)
            if not math.isfinite(inertia_host):
                # the loop's deferred check trails by one fetch: a divergence on
                # the FINAL step (or a 1-iteration fit) is caught here, on the
                # inertia scalar the caller fetches anyway
                _raise_diverged(n_iter, last_good, f"final inertia = {inertia_host}")
        else:
            inertia = jnp.full((), jnp.nan, X.dtype)
        state = {
            "cluster_centers_": centers,
            "inertia_": inertia,
            "n_iter_": jnp.asarray(n_iter, jnp.int32),
        }
        if to_host is None:
            return state
        with telemetry.device_wait("kmeans_finish"):
            return to_host(state)


@partial(jax.jit, static_argnames=("mesh",))
def kmeans_predict(X: jax.Array, centers: jax.Array, mesh=None) -> jax.Array:
    """Nearest-center assignment for a batch of rows (transform path).

    Row-tiled through the shared core (`distance.argmin_assign`,
    `config["distance_tile_rows"]` rows per tile): the full [n, k] distance
    matrix never materializes, so a fit the HBM admission controller
    approved cannot OOM at PREDICT — the predict-side tile is a budgeted
    workspace term (memory.py / KMeans._solver_workspace_terms).

    `mesh`: the mesh a row-sharded X lives on (the distributed transform,
    core._transform_arrays). The row parallelism is then STATED as a
    shard_map — each device assigns its own rows against the replicated
    centers — because GSPMD refuses to partition a Mosaic kernel."""
    if mesh is None:
        return argmin_assign(X, centers)
    return shard_map(
        argmin_assign,
        mesh=mesh,
        in_specs=(P(ROWS_AXIS, None), P()),
        out_specs=P(ROWS_AXIS),
        check_vma=shard_map_check_vma(),
    )(X, centers)


_INIT_SAMPLE_CAP = 262_144  # rows used for seeding (both init paths)


def _init_subsample(x_host, sample_weight, rng):
    """Bounded (row, weight) subsample shared by both seeding paths."""
    import numpy as np

    n = x_host.shape[0]
    if n > _INIT_SAMPLE_CAP:
        idx = np.sort(rng.choice(n, _INIT_SAMPLE_CAP, replace=False))
        x = np.ascontiguousarray(np.asarray(x_host[idx], dtype=np.float64))
        sw = None if sample_weight is None else np.asarray(sample_weight[idx], dtype=np.float64)
    else:
        x = np.ascontiguousarray(np.asarray(x_host, dtype=np.float64))
        sw = None if sample_weight is None else np.asarray(sample_weight, dtype=np.float64)
    if sw is None:
        sw = np.ones(x.shape[0])
    return x, sw


# nearest-candidate assignment for the seeding paths: the shared row-tiled
# core (never a full [n, k] distance matrix), jitted once per shape
_assign_nearest = jax.jit(argmin_assign)


@partial(jax.jit, static_argnames=("k",))
def _kmeanspp_device(x, sw, seed, *, k: int):
    """Classic k-means++ as ONE device program (fori_loop over the k sequential
    draws; categorical sampling by inverse-CDF). The host numpy loop this
    replaces costs ~50 ms per draw at 10k×512 — 51 s for the ANN coarse
    quantizer's k=1024 reduce; here the whole reduce is a single dispatch."""
    n, d = x.shape
    x_sq = jnp.sum(x * x, axis=1)

    def sample(key, probs):
        c = jnp.cumsum(probs)
        u = jax.random.uniform(key, dtype=c.dtype) * c[-1]
        return jnp.clip(jnp.searchsorted(c, u), 0, n - 1)

    key = jax.random.PRNGKey(seed)
    key, k0 = jax.random.split(key)
    i0 = sample(k0, sw)
    centers0 = jnp.zeros((k, d), x.dtype).at[0].set(x[i0])

    def body(i, carry):
        centers, closest, key = carry
        prev = jax.lax.dynamic_slice_in_dim(centers, i - 1, 1, 0)[0]
        d2 = x_sq - 2.0 * (x @ prev) + jnp.sum(prev * prev)
        closest = jnp.minimum(closest, jnp.maximum(d2, 0.0))
        probs = closest * sw
        s = jnp.sum(probs)
        probs = jnp.where(s > 0, probs, sw)  # degenerate: all points covered
        key, kk = jax.random.split(key)
        idx = sample(kk, probs)
        return centers.at[i].set(x[idx]), closest, key

    centers, _, _ = jax.lax.fori_loop(
        1, k, body, (centers0, jnp.full((n,), jnp.inf, x.dtype), key)
    )
    return centers


def scalable_kmeans_init(x_host, k: int, seed: int, sample_weight=None, rounds: int = 5):
    """k-means|| (Bahmani et al.) seeding — the reference's
    'scalable-k-means++' (cuML KMeansMG init). Device-assisted: each round
    computes distances to ONLY the new candidates (one incremental matmul
    program), samples ~2k further candidates with probability ∝ d², then the
    ~2k·rounds candidate set is weighted by assignment counts and reduced to k
    with classic k-means++ on the host — O(rounds) device passes instead of
    the O(k) sequential host passes of plain k-means++ (minutes at the
    protocol's k=1000)."""
    import numpy as np

    rng = np.random.default_rng(seed)
    x, sw = _init_subsample(x_host, sample_weight, rng)
    x = x.astype(np.float32)
    n_sub = x.shape[0]
    l = max(1, 2 * k)  # oversampling factor per round

    xd = jax.device_put(x)
    # every candidate block is PADDED to exactly l rows (repeating one row —
    # duplicates never change a running min-distance): all `_min_d2_update`
    # calls then share ONE compiled shape instead of one compile per block
    # size.
    first = np.broadcast_to(x[rng.choice(n_sub, p=sw / sw.sum())], (l, x.shape[1]))
    cand_list = [np.ascontiguousarray(first)]
    min_d2 = _min_d2_update(xd, jax.device_put(cand_list[0]), jnp.full((n_sub,), np.inf, jnp.float32))
    for _ in range(rounds):
        with telemetry.device_wait("kmeans_init"):
            probs = np.maximum(np.asarray(min_d2), 0.0) * sw  # host-fetch-ok: one fetch per k-means|| seeding ROUND (host does the ∝d² sampling); rounds is small and fixed
        s = probs.sum()
        # without-replacement sampling needs enough nonzero-probability rows
        n_new = min(l, n_sub, int(np.count_nonzero(probs)))
        if s <= 0 or n_new == 0:
            break
        new_idx = rng.choice(n_sub, size=n_new, replace=False, p=probs / s)
        new = x[np.sort(new_idx)]
        if n_new < l:  # pad to the fixed block shape
            new = np.concatenate([new, np.broadcast_to(new[0], (l - n_new, new.shape[1]))])
        cand_list.append(new)
        min_d2 = _min_d2_update(xd, jax.device_put(new), min_d2)
    cand = np.concatenate(cand_list, axis=0)
    # weight candidates by how many points they own (one assignment pass);
    # duplicate (padding) rows lose every argmin tie, so they get weight 0
    assign = _assign_nearest(xd, jax.device_put(cand))
    with telemetry.device_wait("kmeans_init"):
        assign = np.asarray(assign)
    weights = np.bincount(assign, weights=sw, minlength=len(cand)).astype(np.float32)
    # reduce the small weighted candidate set to k with k-means++ ON DEVICE
    # (one dispatch; the host loop costs ~50s at the ANN build's k=1024)
    centers = _kmeanspp_device(
        jax.device_put(cand.astype(np.float32)),
        jax.device_put(np.maximum(weights, 1e-12)),
        seed + 1, k=k,
    )
    with telemetry.device_wait("kmeans_init"):
        return np.asarray(centers, dtype=np.float64)


def kmeans_plus_plus_init(x_host, k: int, seed: int, sample_weight=None):
    """k-means++ seeding on the host (numpy), optionally on a subsample.

    Used for Spark's default ``k-means||`` init mode: the reference delegates to
    cuML's scalable-k-means++; here we seed with classic k-means++ over a
    bounded subsample (equivalent quality for the benchmark regime), then let
    the distributed Lloyd loop refine.
    """
    import numpy as np

    rng = np.random.default_rng(seed)
    x, sw = _init_subsample(x_host, sample_weight, rng)
    centers = np.empty((k, x.shape[1]), dtype=np.float64)
    p = sw / sw.sum()
    centers[0] = x[rng.choice(x.shape[0], p=p)]
    closest = np.full(x.shape[0], np.inf)
    for i in range(1, k):
        d2 = np.sum((x - centers[i - 1]) ** 2, axis=1)
        closest = np.minimum(closest, d2)
        probs = closest * sw
        s = probs.sum()
        if s <= 0:
            centers[i] = x[rng.choice(x.shape[0], p=p)]
        else:
            centers[i] = x[rng.choice(x.shape[0], p=probs / s)]
    return centers


def random_init(x_host, k: int, seed: int):
    """Sample k distinct rows as initial centers (initMode='random')."""
    import numpy as np

    rng = np.random.default_rng(seed)
    n = x_host.shape[0]
    if k > n:
        raise ValueError(f"k={k} exceeds number of rows {n}")
    idx = rng.choice(n, k, replace=False)
    return np.asarray(x_host[idx], dtype=np.float64)


@partial(jax.jit, static_argnames=("l",), donate_argnums=(2,))
def _kmeanspar_round(xd, cand_prev, min_d2, sw, key, *, l: int):
    """One k-means|| round fully on device: update min-d² against the
    previous candidate block, then draw the next `l` candidates WITHOUT
    replacement with probability ∝ d²·w via Gumbel-top-k (keys
    log p + Gumbel(0,1); the top-l keys are exactly a weighted
    without-replacement sample). Returns (new candidate block [l, d],
    updated min_d2)."""
    min_d2 = min_d2_update(xd, cand_prev, min_d2)
    probs = min_d2 * sw
    total = jnp.sum(probs)
    # degenerate (all points covered): fall back to uniform-by-weight
    probs = jnp.where(total > 0, probs, sw)
    gumbel = -jnp.log(-jnp.log(
        jax.random.uniform(key, (xd.shape[0],), minval=1e-20, maxval=1.0)
    ))
    keys = jnp.where(probs > 0, jnp.log(probs) + gumbel, -jnp.inf)
    _, idx = jax.lax.top_k(keys, l)
    return xd[idx], min_d2


def scalable_kmeans_init_device(
    xd: jax.Array, k: int, seed: int, sample_weight=None, rounds: int = 5
) -> jax.Array:
    """k-means|| seeding with every step device-resident — for data that
    already lives in HBM (the ANN index builds). No candidate rows, distance
    vectors or weights ever cross the host boundary: each round is one
    fused program (_kmeanspar_round), the candidate weighting is a device
    scatter-add, and the final reduce-to-k is `_kmeanspp_device`. Returns
    [k, d] f32 centers ON DEVICE.

    Equivalent in distribution to `scalable_kmeans_init` (Bahmani et al.
    k-means||); the without-replacement sampling uses Gumbel-top-k instead
    of host `rng.choice`.

    Size bound: the per-round `xd[idx]` candidate gather is the fancy-index
    pattern XLA may answer with a full temporary copy of xd at very large
    shapes (see the 1-device KMeans notes) — callers keep xd below a few GB
    (the ANN index builds, whose per-partition data is well under that)."""
    n, d = xd.shape
    l = max(1, min(2 * k, n))  # top_k sample size cannot exceed n
    sw = (
        jnp.ones((n,), jnp.float32)
        if sample_weight is None
        else jnp.asarray(sample_weight, jnp.float32)
    )
    key = jax.random.PRNGKey(seed)
    k0, key = jax.random.split(key)
    i0 = jax.random.categorical(k0, jnp.log(jnp.maximum(sw, 1e-30)))
    cand = jnp.broadcast_to(xd[i0], (l, d))
    min_d2 = jnp.full((n,), jnp.inf, jnp.float32)
    blocks = [cand]
    for r in range(rounds):
        key, kr = jax.random.split(key)
        cand, min_d2 = _kmeanspar_round(xd, blocks[-1], min_d2, sw, kr, l=l)
        blocks.append(cand)
    cand_all = jnp.concatenate(blocks, axis=0)
    assign = _assign_nearest(xd, cand_all)
    weights = jnp.zeros((cand_all.shape[0],), jnp.float32).at[assign].add(sw)
    return _kmeanspp_device(
        cand_all, jnp.maximum(weights, 1e-12), seed + 1, k=min(k, cand_all.shape[0])
    )
