#
# Out-of-core solver drivers: fits for datasets whose resident placement does
# not fit HBM (docs/robustness.md "Memory safety", ROADMAP item 2).
#
# Every driver here consumes a `FitInputs` whose `stream` field carries a
# `core.StreamPlan` (host-resident extracted blocks + admitted chunk size) and
# feeds row chunks through the double-buffered host->HBM pipeline
# (`parallel.mesh.stream_place_blocks`: chunk N+1's `device_put` in flight
# while chunk N computes). The solvers are restructured around ACCUMULABLE
# state, so only two chunks are ever device-resident:
#
#   linear / PCA   sufficient statistics (X'WX, X'Wy / mean+covariance)
#                  summed over chunks, then the SAME replicated (d, d) solve
#                  as the resident path (ops/linear._solve_from_stats /
#                  ops/pca._pca_finish) — identical finish kernels, so
#                  streaming matches resident to summation rounding;
#   logistic       the GLM quasi-Newton loop of ops/logistic._glm_qn_setup
#                  re-expressed with streamed reductions: per iteration, ONE
#                  chunked pass evaluates the line-search logits z_d and the
#                  batched-Armijo candidate losses, and ONE chunked pass
#                  accumulates the analytic gradient — the same two
#                  data-reads-per-iteration the resident program performs.
#                  Logits (n x k_out, tiny next to X) stay on host between
#                  passes;
#   k-means        per-chunk assignment + center accumulation
#                  (ops/kmeans.block_assign_accumulate) inside the SAME
#                  deferred-convergence host loop as the resident fit, with
#                  the SAME checkpoint key (ops/kmeans.kmeans_ckpt_key) — a
#                  resident fit's checkpoint resumes a streaming retry.
#
# Math parity: every formula mirrors its resident counterpart term by term;
# only the summation ORDER differs (per-chunk partials instead of one fused
# reduction), so streaming results match resident fits to accumulation
# rounding — pinned at rtol 1e-9 in float64 by tests/test_oocore.py.
#
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .. import telemetry
from ..parallel.mesh import stream_place_blocks
from .linalg import centered_gram, centered_moments
from ..utils import numcheck


def _ranges(n: int, chunk_rows: int) -> List[Tuple[int, int]]:
    step = max(1, int(chunk_rows))
    return [(lo, min(lo + step, n)) for lo in range(0, max(0, int(n)), step)]


def _maybe_validate(plan: Any, lo: int, hi: int) -> None:
    """Per-row-block NaN/Inf scan (``config["validate_ingest"]``): validation
    rides the stream — the dataset is never host-materialized a second time
    just to validate it, and later passes over already-scanned rows are
    free."""
    if not getattr(plan, "validate", False) or lo < plan.validated_rows:
        return
    from ..data import run_deferred_validation

    run_deferred_validation(plan.extracted, lo=lo, hi=hi)
    plan.validated_rows = hi


def _ell_host_blocks(inputs: Any) -> List[Tuple[int, int, np.ndarray, np.ndarray]]:
    """CSR row slices converted ONCE per fit to padded-ELL host blocks at the
    GLOBAL k_max (every pass then re-places the same host arrays). Cached on
    the plan; per-block validation happens at conversion.

    The cache trades host memory (a full padded-ELL copy of the dataset
    alongside the CSR — up to `k_max / mean_nnz` times its size on skewed
    data) for conversion work, which the streamed GLM loop would otherwise
    redo three passes per iteration. Single-pass consumers must NOT build
    it — they go through `_ell_block_iter(cache=False)`, which converts one
    chunk at a time and retains nothing."""
    plan = inputs.stream
    if plan.ell_blocks is None:
        from .sparse import csr_to_ell

        csr = inputs.X_sparse
        k_max = (
            max(1, int(np.diff(csr.indptr).max())) if csr.shape[0] else 1
        )
        blocks = []
        for lo, hi in _ranges(inputs.n_valid, plan.chunk_rows):
            _maybe_validate(plan, lo, hi)
            idx, val, _ = csr_to_ell(csr[lo:hi], k_max=k_max, dtype=inputs.dtype)
            blocks.append((lo, hi, val, idx))
        plan.ell_blocks = blocks
        plan.ell_k_max = k_max
    return plan.ell_blocks


def _dense_block_iter(inputs: Any, extras: Dict[str, np.ndarray], per_block=None):
    """Host dicts for one dense pass: the features slice + aligned slices of
    `extras` (+ optional per-block arrays, e.g. the host-retained logits)."""
    plan = inputs.stream
    feats = plan.extracted.features
    dtype = inputs.dtype
    for bi, (lo, hi) in enumerate(_ranges(inputs.n_valid, plan.chunk_rows)):
        _maybe_validate(plan, lo, hi)
        blk = {"X": np.asarray(feats[lo:hi], dtype=dtype)}
        for name, arr in extras.items():
            blk[name] = arr[lo:hi]
        if per_block is not None:
            for name, arrs in per_block.items():
                blk[name] = arrs[bi]
        yield blk


def _ell_block_iter(
    inputs: Any, extras: Dict[str, np.ndarray], per_block=None, cache: bool = True
):
    plan = inputs.stream
    if not cache and plan.ell_blocks is None:
        # single-pass consumer: convert chunk by chunk, retain nothing — a
        # dataset streamed for device-memory pressure must not grow a second
        # full host copy just to be read once
        from .sparse import csr_to_ell

        csr = inputs.X_sparse
        if not plan.ell_k_max:
            plan.ell_k_max = (
                max(1, int(np.diff(csr.indptr).max())) if csr.shape[0] else 1
            )
        for lo, hi in _ranges(inputs.n_valid, plan.chunk_rows):
            _maybe_validate(plan, lo, hi)
            idx, val, _ = csr_to_ell(csr[lo:hi], k_max=plan.ell_k_max, dtype=inputs.dtype)
            blk = {"val": val, "idx": idx}
            for name, arr in extras.items():
                blk[name] = arr[lo:hi]
            yield blk
        return
    for bi, (lo, hi, val, idx) in enumerate(_ell_host_blocks(inputs)):
        blk = {"val": val, "idx": idx}
        for name, arr in extras.items():
            blk[name] = arr[lo:hi]
        if per_block is not None:
            for name, arrs in per_block.items():
                blk[name] = arrs[bi]
        yield blk


# ------------------------------------------------------- linear / PCA -------


@jax.jit
def _xy_means_block(xb, yb, wb):
    """Per-chunk weighted first moments: (Σw, Σw·x [d], Σw·y)."""
    return jnp.sum(wb), jnp.einsum("n,nd->d", wb, xb), jnp.sum(wb * yb)


@partial(jax.jit, static_argnames=("fast",))
def _shifted_sums_block(xb, yb, wb, x_shift, y_shift, fast: bool = False):
    """Per-chunk raw sums of the rows less a given shift, the resident fit's
    own tiles (`linalg.centered_moments`): (Σw, Σw(x-s) [d], Σw(y-t),
    Σw(x-s)(x-s)ᵀ [d, d], Σw(x-s)(y-t) [d], Σw(y-t)²)."""
    gram, xy, yy = centered_moments(xb, yb, wb, x_shift, y_shift, fast=fast)
    return (jnp.sum(wb), jnp.einsum("n,nd->d", wb, xb - x_shift), jnp.sum(wb * (yb - y_shift)),
            gram, xy, yy)


def linear_streaming_stats(
    inputs: Any, fit_intercept: bool = True, fast: bool = False
) -> Tuple[np.ndarray, ...]:
    """The statistics of a linear fit (ops/linear `_STATS_NAMES`) from ONE
    streamed pass: each chunk's raw sums (dense: the resident fit's tiled
    contraction about zero; padded-ELL: the scatter-add), added up on the
    host at full precision and centred on the [d, d] side (ops/linear
    `_centred_from_raw`). Padding rows carry zero weight and zero features,
    so per-chunk partials sum to exactly the resident statistics (up to
    summation rounding).

    ``fast`` runs each dense chunk's gram bf16-in / f32-accumulate, and what
    it rounds has to be what the resident fit rounds, the CENTRED rows: so a
    dense ``fast`` fit with an intercept streams the means first and takes
    the chunks' sums about them (the [d, d] side then takes out only what
    rounding left of the means)."""
    from .linear import _centred_from_raw, _ell_raw_jit

    dtype = inputs.dtype
    extras = {"y": np.asarray(inputs.y, dtype=dtype), "w": np.asarray(inputs.w, dtype=dtype)}
    raw_names = ("sw", "sx", "sy", "G", "c", "syy")
    _nc = numcheck.hook()  # SRML_NUMCHECK=1: sweep per-chunk host partials

    def summed(chunks, part_of, names=raw_names):
        acc: Optional[List[np.ndarray]] = None
        for blk in stream_place_blocks(inputs.mesh, chunks):
            part = part_of(blk)
            # per-chunk partial fetch = the streaming pipeline's existing
            # sync; the efficiency attributor times the wait as `execute`
            with telemetry.device_wait("stream_chunk"):
                part = [np.asarray(p) for p in part]
            if _nc is not None:
                _nc("linear_stream.chunk", solver="linear_stream", **dict(zip(names, part)))
            acc = part if acc is None else [a + b for a, b in zip(acc, part)]
        assert acc is not None, "streaming stats over an empty dataset"
        return acc

    if inputs.X_sparse is not None:
        return tuple(np.asarray(v) for v in _centred_from_raw(summed(
            _ell_block_iter(inputs, extras, cache=False),
            lambda blk: _ell_raw_jit(
                blk["val"], blk["idx"], blk["y"], blk["w"], d=inputs.n_cols, tile=8192, fast=fast),
        ), fit_intercept))
    x_shift, y_shift = np.zeros(inputs.n_cols, dtype), np.zeros((), dtype)
    if fast and fit_intercept:
        sw, sx, sy = summed(
            _dense_block_iter(inputs, extras),
            lambda blk: _xy_means_block(blk["X"], blk["y"], blk["w"]), raw_names[:3],
        )
        x_shift, y_shift = sx / sw, sy / sw
    xs_dev, ys_dev = jnp.asarray(x_shift, dtype), jnp.asarray(y_shift, dtype)
    sw, xm, ym, Gc, cc, syc = _centred_from_raw(summed(
        _dense_block_iter(inputs, extras),
        lambda blk: _shifted_sums_block(blk["X"], blk["y"], blk["w"], xs_dev, ys_dev, fast=fast),
    ), fit_intercept)
    if fit_intercept:
        xm, ym = x_shift + xm, y_shift + ym
    return tuple(np.asarray(v) for v in (sw, xm, ym, Gc, cc, syc))


def linear_fit_streaming(
    inputs: Any,
    *,
    alpha: float,
    l1_ratio: float,
    fit_intercept: bool = True,
    standardize: bool = True,
    use_cd: bool = False,
    max_iter: int = 1000,
    tol: float = 1e-6,
    fast: bool = False,
) -> Dict[str, jax.Array]:
    """Out-of-core linear regression: the streamed statistics feed the SAME
    replicated (d, d) solve as the resident path. The statistics are
    retained in the active `CheckpointStore` (when one is installed), so a
    transient retry — or every further param set of a sequential sweep —
    skips the data passes, exactly like the resident checkpointed fit
    (keyed apart by what they are taken about and by `fast`)."""
    from ..parallel.mesh import X_DEFAULT
    from .linear import _fit_from_retained_stats, _gram_span, stats_ckpt_key

    dtype = inputs.dtype
    sparse = inputs.X_sparse is not None

    def compute():
        with _gram_span(int(inputs.n_valid), int(inputs.n_cols), fast, "ell" if sparse else X_DEFAULT):
            return linear_streaming_stats(inputs, fit_intercept=fit_intercept, fast=fast)

    return _fit_from_retained_stats(
        compute, dtype,
        alpha=alpha, l1_ratio=l1_ratio, fit_intercept=fit_intercept,
        standardize=standardize, use_cd=use_cd, max_iter=max_iter, tol=tol,
        ckpt_key=stats_ckpt_key(
            "linear_stats_stream" + ("_ell" if sparse else ""), fit_intercept=fit_intercept, fast=fast),
        placement_key=("stream", int(inputs.n_valid), int(inputs.n_cols), np.dtype(dtype).name),
    )


@jax.jit
def _moments_block(xb, wb):
    """Per-chunk weighted raw moments: (Σw, Σw·x [d], Σw·x² [d])."""
    return (
        jnp.sum(wb),
        jnp.einsum("n,nd->d", wb, xb),
        jnp.einsum("n,nd->d", wb, xb * xb),
    )


@partial(jax.jit, static_argnames=("fast",))
def _cov_block(xb, wb, mean, fast: bool = False):
    """Per-chunk CENTERED outer-product sum: Σ w (x-μ)(x-μ)ᵀ. Padding rows
    contribute (0-μ) terms scaled by w=0 — nothing. The resident fit's own
    contraction (linalg.centered_gram: row tiles, ``fast`` bf16-in /
    f32-accumulate with the weights applied at full precision first)."""
    return centered_gram(xb, wb, mean, fast=fast)


def pca_fit_streaming(inputs: Any, *, k: int, fast: bool = False) -> Dict[str, jax.Array]:
    """Out-of-core PCA: two streamed passes — weighted mean, then the
    CENTERED covariance (the same ``Σw(x-μ)(x-μ)ᵀ/(Σw-1)`` formula as
    linalg.weighted_cov, never the cancellation-prone uncentered form) — and
    the SAME finish kernel as the resident fit. Statistics retained through
    the checkpoint store like the resident checkpointed path. ``fast``
    applies to each chunk's covariance contraction only; the mean pass and
    the eigendecomposition stay full precision."""
    from .. import checkpoint as _ckpt
    from ..parallel import chaos
    from ..parallel.mesh import X_DEFAULT
    from .pca import _gram_span, _pca_finish

    dtype = inputs.dtype
    w = np.asarray(inputs.w, dtype=dtype)

    def compute() -> Dict[str, np.ndarray]:
        with _gram_span(int(inputs.n_valid), int(inputs.n_cols), fast, X_DEFAULT):  # both streamed passes
            return stream_stats()

    def stream_stats() -> Dict[str, np.ndarray]:
        sw = None
        sx = None
        _nc = numcheck.hook()  # SRML_NUMCHECK=1: sweep per-chunk host partials
        for blk in stream_place_blocks(inputs.mesh, _dense_block_iter(inputs, {"w": w})):
            b_sw, b_sx, _ = _moments_block(blk["X"], blk["w"])
            with telemetry.device_wait("stream_chunk"):
                b_sw, b_sx = np.asarray(b_sw), np.asarray(b_sx)  # host-fetch-ok: out-of-core by design — per-CHUNK moment partials accumulate on host (tiny [d]-sized payloads)
            if _nc is not None:
                _nc("pca_stream.chunk", solver="pca_stream", sum_w=b_sw, sum_x=b_sx)
            sw = b_sw if sw is None else sw + b_sw
            sx = b_sx if sx is None else sx + b_sx
        assert sw is not None
        mean = sx / sw
        mean_dev = jnp.asarray(mean, dtype)
        cov_sum = None
        for blk in stream_place_blocks(inputs.mesh, _dense_block_iter(inputs, {"w": w})):
            with telemetry.device_wait("stream_chunk"):
                part = np.asarray(_cov_block(blk["X"], blk["w"], mean_dev, fast=fast))  # host-fetch-ok: out-of-core by design — per-CHUNK [d,d] covariance partial accumulates on host
            if _nc is not None:
                _nc("pca_stream.chunk", solver="pca_stream", cov_partial=part)
            cov_sum = part if cov_sum is None else cov_sum + part
        cov = cov_sum / (sw - 1.0)
        if _nc is not None:
            _nc("pca_stream.stats", solver="pca_stream", mean=mean, cov=cov)
        return {"total_w": np.asarray(sw), "mean": np.asarray(mean), "cov": cov}

    store = _ckpt.active_store()
    # bf16 statistics are keyed apart from full-precision ones
    stats_key = "pca_stats_stream" + (":bf16" if fast else "")
    pkey = ("stream", int(inputs.n_valid), int(inputs.n_cols), np.dtype(dtype).name)
    if store is not None:
        state = store.get_or_compute(
            stats_key, compute, solver="pca", placement_key=pkey
        )
    else:
        state = compute()
    chaos.maybe_fail_stage("solve", 0)
    return _pca_finish(
        jnp.asarray(state["total_w"], dtype),
        jnp.asarray(state["mean"], dtype),
        jnp.asarray(state["cov"], dtype),
        k=k,
    )


# ------------------------------------------------------------- k-means ------


def kmeans_fit_streaming(
    inputs: Any,
    init_centers: np.ndarray,
    *,
    max_iter: int = 20,
    tol: float = 1e-4,
    final_inertia: bool = True,
    precision_mode: str = "high",
) -> Dict[str, jax.Array]:
    """Out-of-core Lloyd: each iteration streams the row chunks through the
    double-buffered pipeline, accumulating (sums, counts, inertia) per chunk.
    The host loop — deferred convergence check, last-good tracking,
    divergence guard, final high-precision inertia, checkpoint cadence — is
    the resident `kmeans_fit` loop verbatim, and the checkpoint key is
    SHARED with it (`kmeans_ckpt_key`), so a resident fit interrupted by an
    OOM resumes on this path from its own checkpoint (centers are replicated
    state: fully portable).

    precision_mode: "high" (default) keeps every chunk at the ambient
    precision; "fast" (solver_precision="bf16", f32 inputs only) runs the
    IN-LOOP chunk assignment matmuls in one-pass bf16 — the final inertia
    pass always reruns at full precision, resident-contract parity."""
    from .. import checkpoint as _ckpt
    from ..parallel import chaos
    from .kmeans import (
        _finish_centers_jit,
        _raise_diverged,
        block_assign_accumulate,
        kmeans_ckpt_key,
    )

    dtype = inputs.dtype
    fast = precision_mode == "fast" and dtype == jnp.float32
    w = np.asarray(inputs.w, dtype=dtype)
    centers = jnp.asarray(np.asarray(init_centers), dtype=dtype)
    _nc = numcheck.hook()  # SRML_NUMCHECK=1: chunk partials + iterate boundary

    def step(c, f=False):
        sums = counts = inertia = None
        for blk in stream_place_blocks(inputs.mesh, _dense_block_iter(inputs, {"w": w})):
            s, n_, i_ = block_assign_accumulate(blk["X"], blk["w"], c, fast=f)
            with telemetry.device_wait("stream_chunk"):
                s, n_, i_ = np.asarray(s), np.asarray(n_), np.asarray(i_)  # host-fetch-ok: out-of-core by design — per-CHUNK [k,d] assignment partials accumulate on host
            if _nc is not None:
                _nc("kmeans_stream.chunk", solver="kmeans_stream",
                    sums=s, inertia=i_)
            if sums is None:
                sums, counts, inertia = s, n_, i_
            else:
                sums, counts, inertia = sums + s, counts + n_, inertia + i_
        return _finish_centers_jit(
            jnp.asarray(sums, dtype), jnp.asarray(counts, dtype),
            jnp.asarray(inertia, dtype), c,
        )

    inertia = jnp.zeros((), dtype)
    n_iter = 0
    prev_shift = None
    last_good = centers
    ckpt_store = _ckpt.active_store()
    ckpt_every = _ckpt.every_iters()
    ckpt_key = None
    if ckpt_store is not None and ckpt_every > 0:
        ckpt_key = kmeans_ckpt_key(init_centers, max_iter, tol)
        if fast:  # bf16 trajectories key apart (same suffix as the resident loop)
            ckpt_key = ckpt_key + ":bf16"
        saved = ckpt_store.load(ckpt_key)
        if saved is not None and tuple(saved.state["centers"].shape) == tuple(
            jnp.shape(centers)
        ):
            centers = jnp.asarray(saved.state["centers"], dtype=dtype)
            lg = saved.state.get("last_good")
            last_good = centers if lg is None else jnp.asarray(lg, dtype=dtype)
            n_iter = int(saved.iteration)
            ps = saved.state.get("prev_shift")
            prev_shift = None if ps is None else float(ps)
    while n_iter < max_iter:
        step_in = centers
        centers, inertia, shift = step(centers, fast)
        n_iter += 1
        if prev_shift is not None:
            with telemetry.device_wait("kmeans_shift"):
                shift_host = float(prev_shift)  # host-fetch-ok: the DEFERRED convergence fetch (resident-loop parity) — overlapped with the current step's compute
            if not math.isfinite(shift_host):
                _raise_diverged(n_iter - 1, last_good, f"center shift = {shift_host}")
            if _nc is not None:
                # after the divergence guard (resident-loop parity)
                _nc("kmeans_stream.iterate", solver="kmeans_stream",
                    iteration=n_iter - 1, watermark=centers.dtype,
                    shift=shift_host)
            if telemetry.enabled():
                telemetry.record_convergence_point("kmeans.shift", n_iter - 1, shift_host)
            if shift_host <= tol:
                break
        prev_shift = shift
        last_good = step_in
        if ckpt_store is not None and ckpt_every > 0 and n_iter % ckpt_every == 0:
            prev_shift = float(prev_shift)  # host-fetch-ok: checkpoint-cadence boundary (config["checkpoint_every_iters"])
            ckpt_store.save(ckpt_key, _ckpt.SolverCheckpoint(
                solver="kmeans", iteration=n_iter,
                state={
                    "centers": np.asarray(centers),  # host-fetch-ok: the checkpoint itself — centers must land on host to survive
                    "prev_shift": prev_shift,
                    "last_good": np.asarray(last_good),  # host-fetch-ok: checkpoint payload (divergence-fallback iterate)
                },
            ))
            chaos.maybe_fail_oom("solve", n_iter)
            chaos.maybe_fail_stage("solve", n_iter)
            # cooperative scheduler preemption — post-checkpoint, like the
            # resident loop (a demoted job can still yield to higher priority)
            from ..scheduler.context import preemption_point

            preemption_point("kmeans_stream", n_iter)
    if telemetry.enabled():
        telemetry.record_solver_result("kmeans", n_iter=n_iter)
    if final_inertia:
        # always at full precision: the REPORTED inertia (and the divergence
        # guard on it) must never see bf16 rounding, resident-loop parity
        _, inertia, _ = step(centers, False)
        inertia_host = float(inertia)
        if not math.isfinite(inertia_host):
            _raise_diverged(n_iter, last_good, f"final inertia = {inertia_host}")
    else:
        inertia = jnp.full((), jnp.nan, dtype)
    return {
        "cluster_centers_": centers,
        "inertia_": inertia,
        "n_iter_": jnp.asarray(n_iter, jnp.int32),
    }


# ------------------------------------------------------------ logistic ------
#
# Streamed GLM quasi-Newton (the ops/logistic._glm_qn_setup algorithm with
# chunked reductions). Per-chunk kernels below are the per-row math of the
# resident objective closures, returning UNNORMALIZED partial sums the driver
# divides by total_w once — same per-row formulas, chunked summation order.


@partial(jax.jit, static_argnames=("multinomial",))
def _glm_loss_block(zb, yb, wb, *, multinomial):
    if multinomial:
        z_true = jnp.take_along_axis(zb, yb[:, None], axis=1)[:, 0]
        return jnp.sum(wb * (jax.nn.logsumexp(zb, axis=1) - z_true))
    y = yb.astype(zb.dtype)
    z0 = zb[:, 0]
    return jnp.sum(wb * (jax.nn.softplus(z0) - y * z0))


def _glm_residual(zb, yb, wb, total_w, k: int, multinomial: bool):
    if multinomial:
        p = jax.nn.softmax(zb, axis=1)
        return wb[:, None] * (p - jax.nn.one_hot(yb, k, dtype=zb.dtype)) / total_w
    p = jax.nn.sigmoid(zb[:, 0])
    return ((wb * (p - yb.astype(zb.dtype))) / total_w)[:, None]


def _search_losses(zb, z_d, yb, wb, alphas, multinomial: bool):
    if multinomial:
        z = zb[:, None, :] + alphas[None, :, None] * z_d[:, None, :]
        idx = jnp.broadcast_to(yb[:, None, None], (z.shape[0], alphas.shape[0], 1))
        z_true = jnp.take_along_axis(z, idx, axis=2)[..., 0]
        return jnp.einsum("n,ns->s", wb, jax.nn.logsumexp(z, axis=2) - z_true)
    yf = yb.astype(zb.dtype)
    z = zb[:, :1] + alphas[None, :] * z_d[:, :1]
    return jnp.einsum("n,ns->s", wb, jax.nn.softplus(z) - yf[:, None] * z)


def _fdot(a, b, fast: bool):
    """a @ b, optionally on the bf16-compute / f32-accumulate contract
    (``solver_precision="bf16"``): both operands rounded to bf16 so the MXU
    runs its native-width pass, `preferred_element_type` pins the f32
    accumulator, result cast back to the working dtype. Mirrors
    ops/logistic._dense_ops for the resident solver."""
    if not fast:
        return a @ b
    return jax.lax.dot(
        a.astype(jnp.bfloat16),
        b.astype(jnp.bfloat16),
        precision=jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32,
    ).astype(a.dtype)


@partial(jax.jit, static_argnames=("k", "multinomial", "fast"))
def _glm_eval_block_dense(xb, yb, wb, Beff, offset, total_w, *, k, multinomial, fast=False):
    """z + loss + gradient partials for one dense chunk (the init/warm pass)."""
    z = _fdot(xb, Beff, fast) + offset[None, :]
    loss = _glm_loss_block(z, yb, wb, multinomial=multinomial)
    r = _glm_residual(z, yb, wb, total_w, k, multinomial)
    return z, loss, _fdot(xb.T, r, fast), jnp.sum(r, axis=0)


@partial(jax.jit, static_argnames=("multinomial", "fast"))
def _glm_search_block_dense(xb, zb, yb, wb, Beff_d, offset_d, alphas, *, multinomial, fast=False):
    """Line-search pass: the direction's logits z_d (ONE data read) and the
    batched-Armijo candidate losses for all step sizes from it."""
    z_d = _fdot(xb, Beff_d, fast) + offset_d[None, :]
    return z_d, _search_losses(zb, z_d, yb, wb, alphas, multinomial)


@partial(jax.jit, static_argnames=("k", "multinomial", "fast"))
def _glm_grad_block_dense(xb, zb, yb, wb, total_w, *, k, multinomial, fast=False):
    """Gradient pass: analytic Xᵀ·residual from the accepted logits."""
    r = _glm_residual(zb, yb, wb, total_w, k, multinomial)
    return _fdot(xb.T, r, fast), jnp.sum(r, axis=0)


def _ell_fast_values(val, fast: bool):
    """ELL gather/scatter has no MXU contraction to cast — the honest bf16
    analog (resident ops/logistic._ell_ops parity) rounds the stored values
    once; index arithmetic and accumulation stay full precision."""
    return val.astype(jnp.bfloat16).astype(val.dtype) if fast else val


@partial(jax.jit, static_argnames=("d", "k", "multinomial", "fast"))
def _glm_eval_block_ell(val, idx, yb, wb, Beff, offset, total_w, *, d, k, multinomial, fast=False):
    from .sparse import ell_matmul, ell_rmatvec

    gv = _ell_fast_values(val, fast)
    z = ell_matmul(gv, idx, Beff) + offset[None, :]
    loss = _glm_loss_block(z, yb, wb, multinomial=multinomial)
    r = _glm_residual(z, yb, wb, total_w, k, multinomial)
    g = jnp.stack(
        [ell_rmatvec(gv, idx, r[:, j], d) for j in range(r.shape[1])], axis=1
    )
    return z, loss, g, jnp.sum(r, axis=0)


@partial(jax.jit, static_argnames=("multinomial", "fast"))
def _glm_search_block_ell(val, idx, zb, yb, wb, Beff_d, offset_d, alphas, *, multinomial, fast=False):
    from .sparse import ell_matmul

    z_d = ell_matmul(_ell_fast_values(val, fast), idx, Beff_d) + offset_d[None, :]
    return z_d, _search_losses(zb, z_d, yb, wb, alphas, multinomial)


@partial(jax.jit, static_argnames=("d", "k", "multinomial", "fast"))
def _glm_grad_block_ell(val, idx, zb, yb, wb, total_w, *, d, k, multinomial, fast=False):
    from .sparse import ell_rmatvec

    gv = _ell_fast_values(val, fast)
    r = _glm_residual(zb, yb, wb, total_w, k, multinomial)
    g = jnp.stack(
        [ell_rmatvec(gv, idx, r[:, j], d) for j in range(r.shape[1])], axis=1
    )
    return g, jnp.sum(r, axis=0)


@partial(jax.jit, static_argnames=("d",))
def _ell_moments_block(val, idx, wb, *, d):
    """Per-chunk scale-only standardization partials (ops/sparse.
    ell_col_moments accumulables): (Σw, Σw·x [d] scatter, Σw·x² [d] scatter)."""
    sw = jnp.sum(wb)
    wv = val * wb[:, None]
    s1 = jnp.zeros((d,), val.dtype).at[idx.ravel()].add(wv.ravel())
    s2 = jnp.zeros((d,), val.dtype).at[idx.ravel()].add((wv * val).ravel())
    return sw, s1, s2


def _streaming_scaling(inputs, w_host, standardize: bool, fit_intercept: bool):
    """(mu, d_scale, total_w) matching ops/logistic._make_scaling (dense) /
    _ell_scaling (sparse, scale-only), accumulated over streamed chunks."""
    dtype = inputs.dtype
    d = inputs.n_cols
    sparse = inputs.X_sparse is not None
    if not standardize:
        total_w = np.asarray(np.sum(w_host, dtype=dtype))
        return (
            np.zeros((d,), dtype),
            np.ones((d,), dtype),
            total_w,
        )
    sw = s1 = s2 = None
    if sparse:
        for blk in stream_place_blocks(inputs.mesh, _ell_block_iter(inputs, {"w": w_host})):
            p = _ell_moments_block(blk["val"], blk["idx"], blk["w"], d=d)
            p = [np.asarray(x) for x in p]
            sw, s1, s2 = (
                (p[0], p[1], p[2]) if sw is None else (sw + p[0], s1 + p[1], s2 + p[2])
            )
        mean = s1 / sw
        var = s2 / sw - mean * mean  # ell_col_moments: population, no clamp
    else:
        for blk in stream_place_blocks(inputs.mesh, _dense_block_iter(inputs, {"w": w_host})):
            p = _moments_block(blk["X"], blk["w"])
            p = [np.asarray(x) for x in p]
            sw, s1, s2 = (
                (p[0], p[1], p[2]) if sw is None else (sw + p[0], s1 + p[1], s2 + p[2])
            )
        mean = s1 / sw
        var = np.maximum(s2 / sw - mean * mean, 0.0)  # weighted_moments clamp
    sigma = np.sqrt(var * (sw / np.maximum(sw - 1.0, 1.0)))
    with np.errstate(invalid="ignore", divide="ignore"):
        d_scale = np.where(sigma > 0, 1.0 / np.maximum(sigma, 1e-30), 0.0)
    if sparse:
        mu = np.zeros((d,), dtype)  # scale-only: sparse data is never centered
    else:
        mu = mean if fit_intercept else np.zeros((d,), dtype)
    return (
        np.asarray(mu, dtype),
        np.asarray(d_scale, dtype),
        np.asarray(sw, dtype),
    )


def logistic_fit_streaming(
    inputs: Any,
    y_idx_host: np.ndarray,
    *,
    k: int,
    multinomial: bool,
    lam_l2: float,
    fit_intercept: bool = True,
    standardize: bool = True,
    max_iter: int = 100,
    tol: float = 1e-6,
    lbfgs_memory: int = 10,
    n_alphas: int = 12,
    c1: float = 1e-4,
    fast: bool = False,
    ckpt_key: str = "logistic_stream",
) -> Dict[str, jax.Array]:
    """Out-of-core logistic regression (smooth L2 path; the L1/elastic-net
    OWL-QN solver has no streaming form — callers gate on it).

    The ops/logistic._glm_qn_setup loop with streamed reductions: per
    iteration, one chunked pass computes the direction's logits + batched
    Armijo candidates and one chunked pass the analytic gradient — the same
    two data reads per iteration as the resident program. The per-row logits
    (n x k_out) are retained on HOST between passes; the accepted point's
    logits are the free linear update z_p + a·z_d, never a third data read.
    Checkpoints (``config["checkpoint_every_iters"]``) save the iterate +
    L-BFGS memory — placement-independent state, so a resume re-derives the
    logits from the iterate with one pass and continues exactly."""
    from .. import checkpoint as _ckpt
    from ..parallel import chaos
    from .logistic import _finish_glm
    from .owlqn import lbfgs_two_loop

    if fast:
        # bf16 iterates/logits are keyed apart: a bf16 run must never resume
        # from (or serve) a full-precision checkpoint
        ckpt_key = ckpt_key + ":bf16"
    dtype = np.dtype(inputs.dtype)
    d = int(inputs.n_cols)
    k_out = k if multinomial else 1
    n_flat = d * k_out + k_out
    m = int(lbfgs_memory)
    sparse = inputs.X_sparse is not None
    mesh = inputs.mesh

    w_host = np.asarray(inputs.w, dtype=dtype)
    y_host = np.asarray(y_idx_host, dtype=np.int32)
    extras = {"y": y_host, "w": w_host}

    mu, d_scale, total_w = _streaming_scaling(
        inputs, w_host, standardize, fit_intercept
    )
    total_w_f = dtype.type(total_w)

    def unflatten(xf: np.ndarray):
        return xf[: d * k_out].reshape(d, k_out), xf[d * k_out :]

    def beff_offset(xf: np.ndarray):
        B, b0 = unflatten(xf)
        Beff = B * d_scale[:, None]
        off = (b0 - mu @ Beff) if fit_intercept else -(mu @ Beff)
        return jnp.asarray(Beff), jnp.asarray(np.asarray(off, dtype))

    def penalty_terms(xf: np.ndarray, dv: np.ndarray):
        Bx, Bd = xf[: d * k_out], dv[: d * k_out]
        return (
            0.5 * lam_l2 * float(np.sum(Bx * Bx)),
            lam_l2 * float(np.dot(Bx, Bd)),
            0.5 * lam_l2 * float(np.sum(Bd * Bd)),
        )

    def assemble_grad(xf: np.ndarray, g_beff: np.ndarray, sum_r: np.ndarray):
        B, _ = unflatten(xf)
        g_b = g_beff - mu[:, None] * sum_r[None, :]
        dB = g_b * d_scale[:, None] + lam_l2 * B
        db0 = sum_r if fit_intercept else np.zeros((k_out,), dtype)
        return np.concatenate([dB.ravel(), db0]).astype(dtype)

    def blocks(per_block=None):
        return (
            _ell_block_iter(inputs, extras, per_block)
            if sparse
            else _dense_block_iter(inputs, extras, per_block)
        )

    # placed blocks are row-padded to the mesh multiple: fetched logits must
    # be TRIMMED back to each chunk's valid rows before they re-enter a later
    # pass as host arrays (the placer re-pads them consistently)
    row_counts = [hi - lo for lo, hi in _ranges(inputs.n_valid, inputs.stream.chunk_rows)]

    def eval_pass(xf: np.ndarray):
        """z blocks + loss + gradient at `xf` (init / resume re-derivation)."""
        Beff, off = beff_offset(xf)
        z_blocks: List[np.ndarray] = []
        loss = 0.0
        g_beff = np.zeros((d, k_out), dtype)
        sum_r = np.zeros((k_out,), dtype)
        for bi, blk in enumerate(stream_place_blocks(mesh, blocks())):
            if sparse:
                z, l_, g, sr = _glm_eval_block_ell(
                    blk["val"], blk["idx"], blk["y"], blk["w"], Beff, off,
                    total_w_f, d=d, k=k, multinomial=multinomial, fast=fast,
                )
            else:
                z, l_, g, sr = _glm_eval_block_dense(
                    blk["X"], blk["y"], blk["w"], Beff, off, total_w_f,
                    k=k, multinomial=multinomial, fast=fast,
                )
            z_blocks.append(np.asarray(z)[: row_counts[bi]])  # host-fetch-ok: out-of-core by design — per-CHUNK logits retained on host (z-block reuse saves an X pass per line search)
            loss += float(l_)  # host-fetch-ok: per-CHUNK scalar loss partial, accumulated on host
            g_beff = g_beff + np.asarray(g)  # host-fetch-ok: per-CHUNK [d,k] gradient partial, accumulated on host
            sum_r = sum_r + np.asarray(sr)  # host-fetch-ok: per-CHUNK residual-sum partial, accumulated on host
        return z_blocks, loss / float(total_w), g_beff, sum_r

    # --- state (host numpy, the working dtype throughout) -----------------
    x = np.zeros((n_flat,), dtype)
    S = np.zeros((m, n_flat), dtype)
    Y = np.zeros((m, n_flat), dtype)
    rho = np.zeros((m,), dtype)
    count = pos = 0
    it = 0
    stalled = False
    f_prev = np.inf

    store = _ckpt.active_store()
    every = _ckpt.every_iters()
    use_ckpt = store is not None and every > 0
    restored = False
    if use_ckpt:
        saved = store.peek(ckpt_key)
        if saved is not None and np.shape(saved.state.get("x")) == (n_flat,):
            st = saved.state
            x = np.asarray(st["x"], dtype)
            S = np.asarray(st["S"], dtype)
            Y = np.asarray(st["Y"], dtype)
            rho = np.asarray(st["rho"], dtype)
            count, pos = int(st["count"]), int(st["pos"])
            f_prev = float(st["f_prev"])
            it = int(saved.iteration)
            store.load(ckpt_key)  # count the restore + flight-recorder event
            restored = True

    z_blocks, loss, g_beff, sum_r = eval_pass(x)
    p0_x, _, _ = penalty_terms(x, np.zeros_like(x))
    f_cur = loss + p0_x
    if restored:
        # the saved f_cur is the exact continuation value (the re-derived one
        # equals it up to rounding; prefer the saved scalar so the resumed
        # convergence test sees precisely what the uninterrupted run would)
        f_cur = float(saved.state["f_cur"])
    g = assemble_grad(x, g_beff, sum_r)

    alphas_np = np.asarray(
        [2.0] + [0.5 ** i for i in range(n_alphas - 1)], np.float32
    ).astype(dtype)
    alphas_dev = jnp.asarray(alphas_np)
    _two_loop = jax.jit(lbfgs_two_loop, static_argnums=(6,))

    trace_convergence = telemetry.convergence_trace_enabled()
    _nc = numcheck.hook()  # SRML_NUMCHECK=1: outer-iteration boundary sweep
    while it < max_iter and not stalled:
        rel = abs(f_prev - f_cur) / max(abs(f_cur), 1.0)
        if not rel > tol:
            break
        d_dir = np.asarray(  # host-fetch-ok: ONE direction fetch per outer L-BFGS iteration — the host-stepped streaming solver's step size, not an inner-loop sync
            _two_loop(
                jnp.asarray(g), jnp.asarray(S), jnp.asarray(Y), jnp.asarray(rho),
                jnp.asarray(count, jnp.int32), jnp.asarray(pos, jnp.int32), m,
            ),
            dtype,
        )
        gd = float(np.dot(g, d_dir))
        if not gd < 0:  # steepest-descent fallback (resident parity)
            d_dir = -g
            gd = -float(np.dot(g, g))
        Beff_d, off_d = beff_offset(d_dir)
        loss_cand = np.zeros((len(alphas_np),), dtype)
        z_d_blocks: List[np.ndarray] = []
        for bi, blk in enumerate(
            stream_place_blocks(mesh, blocks(per_block={"z": z_blocks}))
        ):
            if sparse:
                z_d, part = _glm_search_block_ell(
                    blk["val"], blk["idx"], blk["z"], blk["y"], blk["w"],
                    Beff_d, off_d, alphas_dev, multinomial=multinomial, fast=fast,
                )
            else:
                z_d, part = _glm_search_block_dense(
                    blk["X"], blk["z"], blk["y"], blk["w"], Beff_d, off_d,
                    alphas_dev, multinomial=multinomial, fast=fast,
                )
            z_d_blocks.append(np.asarray(z_d)[: row_counts[bi]])  # host-fetch-ok: out-of-core by design — per-CHUNK direction logits retained on host
            loss_cand = loss_cand + np.asarray(part)  # host-fetch-ok: per-CHUNK batched-Armijo loss partials, accumulated on host
        p0, p1, p2 = penalty_terms(x, d_dir)
        a = alphas_np
        f_cand = loss_cand / float(total_w) + p0 + a * p1 + a * a * p2
        ok_mask = f_cand <= f_cur + c1 * a * gd
        ok = bool(ok_mask.any())
        if not ok:
            # no acceptable step: the batched-Armijo stall (resident parity —
            # the loop ends with `stalled` set, iterate unchanged)
            stalled = True
            f_prev = f_cur
            it += 1
            if trace_convergence:
                telemetry.record_convergence_point("glm_qn", it - 1, f_cur)
            break
        first_ok = int(np.argmax(ok_mask))
        a_sel = dtype.type(a[first_ok])
        f_new = float(f_cand[first_ok])
        xn = (x + a_sel * d_dir).astype(dtype)
        z_n_blocks = [zp + a_sel * zd for zp, zd in zip(z_blocks, z_d_blocks)]
        g_beff = np.zeros((d, k_out), dtype)
        sum_r = np.zeros((k_out,), dtype)
        for blk in stream_place_blocks(mesh, blocks(per_block={"z": z_n_blocks})):
            if sparse:
                gb, sr = _glm_grad_block_ell(
                    blk["val"], blk["idx"], blk["z"], blk["y"], blk["w"],
                    total_w_f, d=d, k=k, multinomial=multinomial, fast=fast,
                )
            else:
                gb, sr = _glm_grad_block_dense(
                    blk["X"], blk["z"], blk["y"], blk["w"], total_w_f,
                    k=k, multinomial=multinomial, fast=fast,
                )
            g_beff = g_beff + np.asarray(gb)  # host-fetch-ok: per-CHUNK gradient partial at the accepted point, accumulated on host
            sum_r = sum_r + np.asarray(sr)  # host-fetch-ok: per-CHUNK residual-sum partial, accumulated on host
        gn = assemble_grad(xn, g_beff, sum_r)
        s = xn - x
        yv = gn - g
        sy = float(np.dot(s, yv))
        if sy > 1e-10:
            S[pos] = s
            Y[pos] = yv
            rho[pos] = 1.0 / max(sy, 1e-30)
            count = min(count + 1, m)
            pos = (pos + 1) % m
        x, z_blocks, g = xn, z_n_blocks, gn
        f_prev, f_cur = f_cur, f_new
        it += 1
        if _nc is not None:
            # objective, iterate, and gradient are host state already —
            # the outer L-BFGS iteration IS the host boundary here
            _nc("glm_stream.iterate", solver="glm_qn_stream", iteration=it - 1,
                objective=f_cur, iterate=x, gradient=g)
        if trace_convergence:
            telemetry.record_convergence_point("glm_qn", it - 1, f_cur)
        if use_ckpt and it % every == 0:
            store.save(ckpt_key, _ckpt.SolverCheckpoint(
                solver="glm_qn_stream", iteration=it,
                state={
                    "x": x.copy(), "S": S.copy(), "Y": Y.copy(),
                    "rho": rho.copy(), "count": count, "pos": pos,
                    "f_prev": f_prev, "f_cur": f_cur,
                },
                portable={"x": x.copy()},
            ))
            chaos.maybe_fail_oom("solve", it)
            chaos.maybe_fail_stage("solve", it)
            # cooperative scheduler preemption — post-checkpoint boundary
            from ..scheduler.context import preemption_point

            preemption_point("glm_qn_stream", it)

    def unflat_jnp(xf):
        return xf[: d * k_out].reshape(d, k_out), xf[d * k_out :]

    return _finish_glm(
        jnp.asarray(x), jnp.asarray(f_cur, dtype), jnp.asarray(it, jnp.int32),
        jnp.asarray(stalled), unflat_jnp, jnp.asarray(d_scale), jnp.asarray(mu),
        fit_intercept=fit_intercept, multinomial=multinomial,
    )
