#
# Distributed PCA solver — the in-tree replacement for `cuml.decomposition.
# pca_mg.PCAMG` (consumed by reference feature.py:220-241).
#
# Algorithm (single pass + local eig, the same math cuML MG runs):
#   1. weighted mean + covariance of the row-sharded X — one fused MXU
#      contraction per shard, GSPMD psum across the `rows` mesh axis
#      (the NCCL-allreduce-of-covariance equivalent);
#   2. replicated top-k symmetric eigensolve, descending (linalg.topk_eigh:
#      a block subspace iteration, the full decomposition where it must);
#   3. sign canonicalization (reference signFlip kernel parity,
#      rapidsml_jni.cu:35-61).
#
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

from .. import telemetry
from ..parallel.mesh import x_layout_of
from .linalg import gram_panels, sign_flip, topk_eigh, weighted_cov


def check_pca_state(state: Dict, *, k: int) -> Dict:
    """Divergence guard on a HOST-fetched PCA state (callers pass the state
    after model-attribute conversion, so no extra device sync): the one-shot
    eigendecomposition has no iterations, but non-finite input rows surface
    as NaN covariance -> NaN components/variances. Raises SolverDivergedError
    (iteration 0 — `n_iter_` is absent from a single-shot solver's state)
    keeping the finite attributes as the last-good payload; returns `state`
    untouched otherwise. One shared guard implementation for every solver
    family (ops/owlqn.check_solver_state)."""
    from .owlqn import check_solver_state

    return check_solver_state(
        "pca", state,
        scalars=(),
        arrays=("components_", "explained_variance_", "mean_"),
    )


def record_pca_fit(state: Dict[str, jax.Array], *, k: int) -> None:
    """Host-side telemetry for a completed `pca_fit` (the solver itself is one
    jitted program — no iterations to trace): fit counter plus the captured
    variance ratio, the solver's single convergence-quality scalar. Callers
    pass the state AFTER fetching it to host (model-attribute conversion), so
    this forces no extra device sync."""
    if not telemetry.enabled():
        return
    import numpy as np

    reg = telemetry.registry()
    reg.inc("pca.fits")
    reg.gauge("pca.n_components", k)
    reg.gauge(
        "pca.explained_variance_ratio_sum",
        float(np.sum(np.asarray(state["explained_variance_ratio_"]))),
    )


def pca_fit(X: jax.Array, w: jax.Array, *, k: int, fast: bool = False, mesh=None) -> Dict[str, jax.Array]:
    """Fit PCA on a row-sharded global X with padding/sample weights w
    (`mesh`: the mesh it is sharded over, see linalg.weighted_cov).

    Returns the model-state dict matching the reference's model attributes
    (reference feature.py:250-257): mean_, components_, explained_variance_,
    explained_variance_ratio_, singular_values_. `components_` rows are always
    unit-norm (cuML/sklearn store unwhitened components; whitening is applied
    at transform time). `fast` runs the covariance contraction bf16-in /
    f32-accumulate (linalg.weighted_cov); the eigendecomposition and every
    reported variance stay full precision.

    Two programs run in turn, each under a span of its own (`gram`, then
    `eig` inside the finish the checkpointed and the streaming fit share), so
    that each span's wall is its program's.
    """
    return _pca_finish(*_gram_pass(X, w, fast=fast, mesh=mesh), k=k)


@partial(jax.jit, static_argnames=("fast", "mesh"))
def _pca_stats(X: jax.Array, w: jax.Array, fast: bool = False, mesh=None):
    return weighted_cov(X, w, ddof=1, fast=fast, mesh=mesh)


def _gram_span(rows: int, d: int, fast: bool, x_layout: str):
    """The `gram` span of one pass over X (a child of the caller's
    `fit/solve`), and the count of it: a fit that reuses retained statistics
    opens none and adds nothing to `pca.gram_passes`. `panels` and
    `panel_cols` say how the contraction is split (`linalg.gram_panels`:
    1 = the whole contraction, more = the block upper triangle only)."""
    telemetry.registry().inc("pca.gram_passes")
    panels, panel_cols = gram_panels(d, fast)
    return telemetry.span(
        "gram", rows=rows, d=d, precision="bf16" if fast else "f32", x_layout=x_layout,
        panels=panels, panel_cols=panel_cols,
    )


def _gram_pass(X: jax.Array, w: jax.Array, *, fast: bool, mesh=None):
    """The one pass over a resident X: (total_w, mean, cov), ready."""
    with _gram_span(int(X.shape[0]), int(X.shape[1]), fast, x_layout_of(X)):
        stats = _pca_stats(X, w, fast=fast, mesh=mesh)
        with telemetry.device_wait("gram"):
            return jax.block_until_ready(stats)


@jax.jit
def _pca_attrs(total_w, mean, cov, evals, comps) -> Dict[str, jax.Array]:
    evals = jnp.maximum(evals, 0.0)
    comps = sign_flip(comps)
    total_var = jnp.trace(cov)
    ratio = evals / total_var
    singular_values = jnp.sqrt(evals * (total_w - 1.0))
    return {
        "mean_": mean,
        "components_": comps,
        "explained_variance_": evals,
        "explained_variance_ratio_": ratio,
        "singular_values_": singular_values,
    }


def _pca_finish(total_w, mean, cov, *, k: int) -> Dict[str, jax.Array]:
    """Statistics -> model state, shared by the resident, the checkpointed
    and the streaming fit: the top-k eigensolve (`linalg.topk_eigh`: the
    block iteration, or the full decomposition where that cannot answer)
    under the `eig` span, then the model's attributes (one small program)."""
    with telemetry.span("eig") as sp:
        evals, comps, ran = topk_eigh(cov, k)
        sp.set(**ran)
    reg = telemetry.registry()
    reg.inc("pca.eig_iterations", ran["iterations"])
    if ran["eig_path"] == "full":
        reg.inc("pca.eig_full")
    return _pca_attrs(total_w, mean, cov, evals, comps)


def pca_fit_checkpointed(
    X: jax.Array, w: jax.Array, *, k: int, fast: bool = False,
    ckpt_key: str = "pca_stats", placement_key=None, mesh=None,
) -> Dict[str, jax.Array]:
    """`pca_fit` with the sufficient statistics — weighted (total_w, mean,
    covariance), the output of the ONE distributed data pass — retained on
    host in the active `CheckpointStore` (docs/robustness.md "Elastic
    recovery"). A transient retry (or a k sweep in the same fit stage)
    re-runs only the replicated d×d eigendecomposition from the retained
    statistics; the data pass is never repeated (``checkpoint.stats_reuses``).
    Identical math to `pca_fit`: same stats kernel, same finish kernel."""
    import numpy as np

    from .. import checkpoint as _ckpt
    from ..parallel import chaos

    store = _ckpt.active_store()
    if fast:
        # bf16 statistics are keyed apart: a bf16 pass must never be
        # resumed from (or serve) a full-precision one
        ckpt_key = ckpt_key + ":bf16"

    def compute() -> Dict:
        total_w, mean, cov = _gram_pass(X, w, fast=fast, mesh=mesh)
        return {
            "total_w": np.asarray(total_w),
            "mean": np.asarray(mean),
            "cov": np.asarray(cov),
        }

    if store is not None:
        state = store.get_or_compute(
            ckpt_key, compute, solver="pca", placement_key=placement_key
        )
    else:
        state = compute()
    chaos.maybe_fail_stage("solve", 0)  # after retention: retries reuse stats
    dtype = X.dtype
    return _pca_finish(
        jnp.asarray(state["total_w"], dtype),
        jnp.asarray(state["mean"], dtype),
        jnp.asarray(state["cov"], dtype),
        k=k,
    )


@partial(jax.jit, static_argnames=("whiten",))
def pca_transform(
    X: jax.Array, components: jax.Array, explained_variance: jax.Array, *, whiten: bool = False
) -> jax.Array:
    """Project rows onto the principal axes WITHOUT mean-centering.

    Spark ML's PCA.transform does not center; cuML's does, and the reference
    undoes cuML's centering by adding the mean back (reference
    feature.py:426-438). Net effect there — and the contract here — is
    ``X @ componentsᵀ`` (scaled by 1/√eigenvalue when whitening).
    """
    T = X @ components.T
    if whiten:
        T = T * jax.lax.rsqrt(jnp.maximum(explained_variance, 1e-30))
    return T
