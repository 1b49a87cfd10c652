#
# Distributed logistic regression solver — the in-tree replacement for
# `cuml.linear_model.logistic_regression_mg.LogisticRegressionMG` (the L-BFGS
# "qn" solver consumed by reference classification.py:1051-1057).
#
# Design: the whole fit is ONE jitted program over the row-sharded X:
#  * standardization stats (weighted mean/var) are psum'd in-graph — the
#    reference's hand-rolled CuPy allgather pre-standardization
#    (classification.py:984-1089) collapses into two einsum+psum lines, and the
#    scaling is folded INTO the coefficients (logits = X @ (D·B) + (b0 − μᵀD·B))
#    so no standardized copy of X is ever materialized in HBM;
#  * L-BFGS (memory=10, zoom linesearch — optax) runs inside a lax.while_loop;
#    each objective/gradient evaluation is a fused MXU matmul + psum over the
#    mesh, the NCCL-allreduce-per-iteration of the reference;
#  * binomial (sigmoid, coef [1,d]) and multinomial (softmax, coef [k,d]) with
#    Spark's multinomial intercept centering (classification.py:1077-1089).
#
# Objective (Spark semantics): (Σ wᵢ·logloss_i)/Σw + λ·[(1−α)/2·‖B_std‖² +
# α·‖B_std‖₁] with the penalty applied in standardized space when
# standardization=True and never to intercepts. The smooth part (logloss + L2)
# goes through optax L-BFGS when α·λ=0 and through the in-tree OWL-QN solver
# (ops/owlqn.py — the same Andrew & Gao 2007 algorithm behind cuML's qn
# `penalty='l1'/'elasticnet'`, reference classification.py:1051-1057) when the
# L1 term is active.
#
from __future__ import annotations

from functools import partial
from typing import Any, Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp
import optax

from .. import telemetry
from .linalg import weighted_moments


def _make_scaling(X, w, standardize: bool, fit_intercept: bool):
    """Returns (mu [d], d_scale [d]): logits use Beff = d_scale·B, offset −μ·Beff."""
    total_w, mean, var = weighted_moments(X, w)
    if not standardize:
        return jnp.zeros_like(mean), jnp.ones_like(mean), total_w
    sigma = jnp.sqrt(var * (total_w / jnp.maximum(total_w - 1.0, 1.0)))  # unbiased, Spark summarizer
    d_scale = jnp.where(sigma > 0, 1.0 / jnp.maximum(sigma, 1e-30), 0.0)
    mu = mean if fit_intercept else jnp.zeros_like(mean)
    return mu, d_scale, total_w


# Step candidates whose gradient the fused pass forms beside the direction's
# logits (`_glm_step`): the first c entries of `_glm_qn_setup`'s `alphas`.
# On a v5e at 393,216 x 3,000 the pass takes 6.3 ms at c = 1, 2 and 4 alike
# (the read of X sets its pace) and the cell's fits accept one of the first
# 1 / 2 / 4 in 18 / 22 / 25 of 25 iterations (PERF.md section 5, PR 37).
GLM_SPECULATED = 4

# How the L-BFGS loop of a dense fit reads X (`glm_pass_of`; the
# `fit/solve/loop` span's `glm_pass` says "fused" for both fused forms).
GLM_TWO_PRODUCTS = "two_products"
GLM_FUSED = "fused"
GLM_FUSED_INTERPRET = "fused_interpret"  # the same kernel through the Pallas interpreter (CI)


def _glm_qn_setup(
    z_of, rowloss, rowloss_alphas, grad_from_z, z_shape, n_flat: int, dtype,
    penalty_terms, max_iter: int, tol: float, memory: int = 10,
    n_alphas: int = 12, c1: float = 1e-4, x0=None, step_of=None,
):
    """L-BFGS specialized to GLM objectives: loss(p) = rowloss(z_of(p)) +
    penalty(p) with z LINEAR in p. Builds and returns the loop triple
    ``(cond, body, state0)`` — shared verbatim by the one-program
    `_glm_qn_minimize` path and the host-segmented checkpointing driver
    (`glm_qn_minimize_segmented`). `x0` warm-starts the iterate (the
    degraded-mesh portable resume; z0/g0/f0 are re-derived from it).

    Two structural exploits of linearity keep every iteration at two passes
    over the data matrix, the forward X·D and the gradient Xᵀ·r:
      1. Line search: along direction D the logits are z(p + a·D) = z_p + a·z_D,
         so ALL candidate step sizes are scored elementwise from one new matmul
         result (z_D) — no inner while_loop touches X. cuML's qn does the same;
         it also avoids the XLA pattern where a loss evaluated inside a NESTED
         while loop costs a full copy of X (11 GiB at 1M x 3k, measured).
      2. Gradients: z at the accepted point is z_p + a·z_D (free), and the
         gradient is computed ANALYTICALLY from it as Xᵀ·(∂loss/∂z) via the
         caller's `grad_from_z` — autodiff re-evaluating the forward would
         re-read X twice more per iteration.

    With `step_of` (the fused pass, `_glm_step`) an iteration is ONE pass.
    The residual of a row tile at the ACCEPTED step depends on every tile
    (the search sums over all rows before it picks), so no pass can form the
    gradient it will need; what is local to a tile is its residual at a
    GIVEN step, and the candidates are a fixed list. So the pass that forms
    z_D also forms Xᵀ·r at a window of `GLM_SPECULATED` candidates, the first
    ones on an iteration's first trip. Where the search picks inside the
    window (a hit), that candidate's product IS the gradient at the accepted
    point. Where it does not (a miss), nothing is accepted and the loop makes
    one more trip on the unchanged state with the window moved to the pick:
    the same direction, the same z_D, the same pick, now a hit. X has one
    reader in the loop either way, `it` counts accepted iterations only, and
    the accepted step is the one the two-pass loop accepts. The state tuple
    then carries two more scalars (the window's start, the first-trip hits).

    Interfaces (all jax-traceable):
      z_of(flat_params [F]) -> z [n, k_out]             (linear)
      rowloss(z) -> scalar                               (data term)
      rowloss_alphas(z_p, z_d, alphas [S]) -> [S]        (data term at p + a·d)
      grad_from_z(flat_p, z[, xr]) -> flat grad [F]      (incl. penalty grad;
                                                          `xr`: Xᵀ·r at z, given)
      penalty_terms(flat_p, flat_d) -> (p0, p1, p2)      (penalty(p + a·d) =
                                                          p0 + a·p1 + a²·p2)
      step_of(flat_d, z_p, a [c]) -> (z_d, xr [c, ...])  (optional: z_of(d), and
                                                          Xᵀ·r at z_p + a_j·z_d)
    Returns (flat_params, objective, n_iter, stalled) — `stalled` is True when
    the run ended because the batched Armijo check found NO acceptable step
    (see the KNOWN LIMIT note below), not because tol/maxIter was reached.
    """
    m = memory
    # step candidates: one growth step, unit step, then geometric backtracking.
    # KNOWN LIMIT (documented, matches the reference's practical envelope): on
    # badly-scaled UNSTANDARDIZED problems whose minimizer sits at |coef|>>1
    # (e.g. raw 0.1%-density features), per-step objective improvements fall
    # below the f32 mean-loss reduction noise at ~1e6+ rows and the Armijo
    # stall check fires early. Spark/cuML standardize by default, and the
    # sparse path's scale-only standardization restores conditioning without
    # densifying — certified by tests/test_large_sparse.py at 1e7 x 2200.
    alphas = jnp.asarray([2.0] + [0.5 ** i for i in range(n_alphas - 1)], jnp.float32)

    from .owlqn import freeze_when_done, lbfgs_two_loop

    # Per-iteration convergence trace (telemetry): gated at TRACE time — a
    # host callback per L-BFGS iteration stalls the device program on the
    # host every iteration, so it only exists in programs traced while
    # SRML_TRACE_CONVERGENCE / enable(convergence=True) was active.
    trace_convergence = telemetry.convergence_trace_enabled()  # traced-ok: the TRACE-TIME gate by design — callbacks exist only in programs traced while convergence tracing was on (docs/observability.md)

    def cond(state):
        f_prev, f_cur, it, stalled = state[7:11]
        rel = jnp.abs(f_prev - f_cur) / jnp.maximum(jnp.abs(f_cur), 1.0)
        return jnp.logical_and(jnp.logical_and(it < max_iter, rel > tol), ~stalled)

    def body(state):
        x, z_p, g, S, Y, rho, meta, f_prev, f_cur, it, _ = state[:11]
        count, pos = meta
        d = lbfgs_two_loop(g, S, Y, rho, count, pos, m)
        # fall back to steepest descent if the direction isn't a descent one
        gd = jnp.dot(g, d)
        d = jnp.where(gd < 0, d, -g)
        # true directional derivative: g·d when the L-BFGS direction is kept,
        # -g·g only in the steepest-descent fallback branch
        gd = jnp.where(gd < 0, gd, -jnp.dot(g, g))
        a = alphas.astype(x.dtype)
        # batched Armijo over all candidates from ONE new logit evaluation
        if step_of is None:
            z_d = z_of(d)  # linear => z(x + a d) = z_p + a z_d     [X read 1]
        else:
            window, hits = state[11:]
            c = GLM_SPECULATED
            # past the list's end the window holds the step 0, which the search never sees
            a_win = jax.lax.dynamic_slice(jnp.pad(a, (0, c - 1)), (window,), (c,))
            z_d, xr_win = step_of(d, z_p, a_win)  # and Xᵀ·r at the window  [the X read]
        p0, p1, p2 = penalty_terms(x, d)
        f_cand = rowloss_alphas(z_p, z_d, a) + p0 + a * p1 + a * a * p2
        ok_mask = f_cand <= f_cur + c1 * a * gd
        # LARGEST passing step (alphas sorted descending)
        first_ok = jnp.argmax(ok_mask)
        ok = jnp.any(ok_mask)
        a_sel = a[first_ok]
        f_new = f_cand[first_ok]
        xn = x + a_sel * d
        z_n = z_p + a_sel * z_d  # logits at the accepted point, no X pass
        if step_of is None:
            gn = grad_from_z(xn, z_n)  # analytic Xᵀ·residual          [X read 2]
            accept = ok
        else:
            at = first_ok.astype(window.dtype) - window
            hit = (at >= 0) & (at < c)
            gn = grad_from_z(xn, z_n, xr_win[jnp.clip(at, 0, c - 1)])
            accept = ok & hit
            miss = ok & ~hit  # this trip accepts nothing: the next one's window starts at the pick
        s = xn - x
        yv = gn - g
        sy = jnp.dot(s, yv)
        do_update = accept & (sy > 1e-10)
        S = jnp.where(do_update, S.at[pos].set(s), S)
        Y = jnp.where(do_update, Y.at[pos].set(yv), Y)
        rho = jnp.where(do_update, rho.at[pos].set(1.0 / jnp.maximum(sy, 1e-30)), rho)
        count = jnp.where(do_update, jnp.minimum(count + 1, m), count)
        pos = jnp.where(do_update, (pos + 1) % m, pos)
        x = jnp.where(accept, xn, x)
        z_p = jnp.where(accept, z_n, z_p)
        g = jnp.where(accept, gn, g)
        f_out = jnp.where(accept, f_new, f_cur)
        if trace_convergence:  # a miss's trip repeats its iteration's point
            jax.debug.callback(
                partial(telemetry.record_convergence_point, "glm_qn"), it, f_out
            )
        if step_of is None:
            return x, z_p, g, S, Y, rho, (count, pos), f_cur, f_out, it + 1, ~ok
        return (
            x, z_p, g, S, Y, rho, (count, pos),
            jnp.where(miss, f_prev, f_cur), f_out, it + (~miss).astype(it.dtype), ~ok,
            jnp.where(miss, first_ok.astype(window.dtype), 0),
            hits + (accept & (window == 0)).astype(hits.dtype),
        )

    if x0 is None:
        x0 = jnp.zeros((n_flat,), dtype)
        z0 = jnp.zeros(z_shape, dtype)  # z_of(0) == 0: z is linear with no constant
    else:
        x0 = jnp.asarray(x0, dtype)
        z0 = z_of(x0)
    g0 = grad_from_z(x0, z0)
    p00, _, _ = penalty_terms(x0, jnp.zeros_like(x0))
    f0 = rowloss(z0) + p00
    state0 = (
        x0, z0, g0,
        jnp.zeros((m, n_flat), x0.dtype), jnp.zeros((m, n_flat), x0.dtype),
        jnp.zeros((m,), x0.dtype),
        (jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32)),
        jnp.asarray(jnp.inf, x0.dtype), f0, jnp.asarray(0, jnp.int32), jnp.asarray(False),
    )
    if step_of is not None:
        state0 += (jnp.asarray(0, jnp.int32), jnp.asarray(0, jnp.int32))
    return cond, body, state0


def _glm_qn_minimize(
    z_of, rowloss, rowloss_alphas, grad_from_z, z_shape, n_flat: int, dtype,
    penalty_terms, max_iter: int, tol: float, memory: int = 10,
    n_alphas: int = 12, c1: float = 1e-4, x0=None, step_of=None,
):
    """One-program GLM quasi-Newton minimization (see `_glm_qn_setup` for
    the algorithm and its structural exploits of linearity). `x0`
    warm-starts the iterate (the public warm_start_from API). Returns
    (flat_params, objective, n_iter, stalled, fused_hits): the last is None
    without `step_of`, else the iterations whose first trip was a hit."""
    from .owlqn import freeze_when_done

    cond, body, state0 = _glm_qn_setup(
        z_of, rowloss, rowloss_alphas, grad_from_z, z_shape, n_flat, dtype,
        penalty_terms, max_iter, tol, memory, n_alphas, c1, x0=x0, step_of=step_of,
    )
    # freeze_when_done makes the loop vmap-safe: batched hyperparameter
    # sweeps (vmap over lam_l2/lam_l1) step until the SLOWEST grid element
    # converges, and converged elements must hold their iterate exactly
    state = jax.lax.while_loop(cond, freeze_when_done(cond, body), state0)
    x, obj, n_iter, stalled = state[0], state[8], state[9], state[10]
    return x, obj, n_iter, stalled, (None if step_of is None else state[12])


def glm_qn_minimize_segmented(
    z_of, rowloss, rowloss_alphas, grad_from_z, z_shape, n_flat: int, dtype,
    penalty_terms, max_iter: int, tol: float, memory: int = 10,
    n_alphas: int = 12, c1: float = 1e-4, *,
    ckpt_key: str = "glm_qn", placement_key=None, x0=None,
):
    """`_glm_qn_minimize` with the one big ``lax.while_loop`` segmented into
    outer HOST segments of ``config["checkpoint_every_iters"]`` inner
    iterations: each boundary host-fetches the full solver state — the
    iterate x, its logits z_p, the gradient, the circular L-BFGS (S, Y, rho)
    memory, and n_iter — into the active `CheckpointStore` so an interrupted
    fit resumes there instead of from scratch. The segment body is the SAME
    traced body and the boundary round-trip is lossless, so a same-mesh
    resume is bit-identical to an uninterrupted segmented run (pinned by
    tests/test_recovery.py). When a checkpoint's shapes no longer match (a
    survivor re-mesh changed n), the PORTABLE subset — the iterate x — warm-
    starts a fresh loop with re-derived logits/gradient: deterministic given
    the survivor set."""
    import numpy as np

    from .. import checkpoint as _ckpt

    store = _ckpt.active_store()
    x_warm = x0  # user warm start (warm_start_from); checkpoints override
    if store is not None:
        saved = store.peek(ckpt_key)
        if saved is not None and saved.placement_key != placement_key:
            # degraded-mesh resume: leaf shapes changed with the data, but
            # the iterate is mesh-independent — warm-start from it
            x_saved = saved.portable.get("x")
            if x_saved is not None and np.shape(x_saved) == (n_flat,):
                x_warm = x_saved
                store.load(ckpt_key)  # count the (portable) restore
    cond, body, state0 = _glm_qn_setup(
        z_of, rowloss, rowloss_alphas, grad_from_z, z_shape, n_flat, dtype,
        penalty_terms, max_iter, tol, memory, n_alphas, c1, x0=x_warm,
    )
    every = _ckpt.every_iters() or max_iter

    def _save_portable(state):  # ride the generic driver's save with x
        return {"x": np.asarray(state[0])}

    state = _ckpt.run_segmented_while(
        cond, body, state0,
        it_of=lambda s: s[9],  # (x, z_p, g, S, Y, rho, meta, f_prev, f_cur, IT, stalled)
        every=every,
        store=store,
        key=ckpt_key,
        solver="glm_qn",
        placement_key=placement_key,
        max_iter=max_iter,
        portable_of=_save_portable,
    )
    x, _, _, _, _, _, _, _, obj, n_iter, stalled = state
    return x, obj, n_iter, stalled


def check_glm_result(state: Dict, *, solver: str = "logistic") -> Dict:
    """Divergence guard for a fetched GLM fit state: piggybacks on the final
    objective/coef scalars the model layer converts to host anyway (the
    jitted while_loop exposes no per-iteration scalar to watch). Raises
    `SolverDivergedError` (with iteration count and the finite remainder of
    the state as last-good) on NaN/Inf; returns `state` otherwise. Shared by
    the dense and ELL fit call sites (models/classification.py)."""
    from .owlqn import check_solver_state

    return check_solver_state(solver, state)


def warn_if_early_stall(state: Dict, *, standardize: bool, max_iter: int, logger=None) -> bool:
    """Host-side signal for the KNOWN LIMIT above: when the Armijo stall check
    ended an UNSTANDARDIZED fit well before maxIter/tol, the returned model is
    silently under-converged — warn and point at standardization=True (the
    sparse path's scale-only standardization restores conditioning without
    densifying). Returns whether the warning fired; shared by the dense and
    ELL fit wrappers' callers (models/classification.py)."""
    stalled = bool(np.asarray(state.get("stalled_", False)))
    n_iter = int(np.asarray(state.get("n_iter_", 0)))
    if not stalled or standardize or n_iter >= max_iter:
        return False
    if logger is None:
        from ..utils import get_logger

        logger = get_logger("LogisticRegression")
    logger.warning(
        "L-BFGS line search stalled after %d/%d iterations on an "
        "unstandardized fit — the model may be under-converged. Badly scaled "
        "features shrink per-step objective improvements below f32 noise; "
        "set standardization=True (sparse fits standardize scale-only, "
        "preserving sparsity).",
        n_iter, max_iter,
    )
    return True


def _lbfgs_minimize(loss, params0, max_iter: int, tol: float, memory: int = 10):
    """L-BFGS in a lax.while_loop; converges on relative objective decrease
    (the qn-solver criterion the reference relies on)."""
    import optax.tree_utils as otu

    opt = optax.lbfgs(memory_size=memory)
    value_and_grad = optax.value_and_grad_from_state(loss)

    def cond(carry):
        _, _, prev, cur, it = carry
        rel = jnp.abs(prev - cur) / jnp.maximum(jnp.abs(cur), 1.0)
        return jnp.logical_and(it < max_iter, rel > tol)

    def body(carry):
        params, state, _, cur, it = carry
        value, grad = value_and_grad(params, state=state)
        updates, state = opt.update(
            grad, state, params, value=value, grad=grad, value_fn=loss
        )
        params = optax.apply_updates(params, updates)
        # the zoom linesearch evaluated the loss at the NEW params; read it from
        # the optimizer state so the convergence check compares new vs old
        new_value = otu.tree_get(state, "value")
        return params, state, cur, new_value, it + 1

    state0 = opt.init(params0)
    v0 = loss(params0)
    params, state, _, obj, n_iter = jax.lax.while_loop(
        cond, body, (params0, state0, jnp.inf, v0, jnp.array(0, jnp.int32))
    )
    return params, obj, n_iter


@partial(
    jax.jit,
    static_argnames=(
        "k", "fit_intercept", "standardize", "max_iter", "lbfgs_memory", "multinomial", "use_l1",
        "fast", "glm_pass",
    ),
)
def logistic_fit(
    X: jax.Array,
    y_idx: jax.Array,  # int32 class indices in [0, k)
    w: jax.Array,
    *,
    k: int,
    multinomial: bool,
    lam_l2: float,
    lam_l1: float = 0.0,
    use_l1: bool = False,  # static solver choice; lam_l1/lam_l2 stay traced so
    # hyperparameter sweeps (fitMultiple/CV) never recompile
    fit_intercept: bool = True,
    standardize: bool = True,
    max_iter: int = 100,
    tol: float = 1e-6,
    lbfgs_memory: int = 10,
    fast: bool = False,
    warm_start=None,  # (coef [k_out, d], intercept [k_out]) original-space seed
    glm_pass: str = GLM_TWO_PRODUCTS,  # `glm_pass_of(X, ...)`, asked on the host
) -> Dict[str, jax.Array]:
    """Returns coef_ [k_out, d] and intercept_ [k_out] in ORIGINAL feature space
    (standardization folded out), plus objective_ and n_iter_. `warm_start`
    seeds the iterate from a previous model's coefficients (the public
    warm_start_from API, docs/scheduling.md "Warm starts"). `fast` runs the
    per-iteration matvecs bf16-in / f32-accumulate (`_dense_ops`). With
    `glm_pass` "fused" / "fused_interpret" the L-BFGS loop reads X once an
    iteration (`_glm_step`) and the result carries `fused_hits_`."""
    d = X.shape[1]
    mu, d_scale, total_w = _make_scaling(X, w, standardize, fit_intercept)
    matvec, rmat = _dense_ops(X, fast)
    step = None
    if glm_pass != GLM_TWO_PRODUCTS:
        step = partial(_glm_step, X, interpret=glm_pass == GLM_FUSED_INTERPRET)
    return _fit_common(
        matvec, rmat, X.shape[0],
        X.dtype, d, y_idx, w, mu, d_scale, total_w,
        k=k, multinomial=multinomial, lam_l2=lam_l2, lam_l1=lam_l1, use_l1=use_l1,
        fit_intercept=fit_intercept, max_iter=max_iter, tol=tol, lbfgs_memory=lbfgs_memory,
        warm_start=warm_start, step=step,
    )


@partial(
    jax.jit,
    static_argnames=(
        "d", "k", "fit_intercept", "standardize", "max_iter", "lbfgs_memory", "multinomial",
        "use_l1", "fast",
    ),
)
def logistic_fit_ell(
    values: jax.Array,  # [n, k_max] ELL values (ops/sparse.py)
    indices: jax.Array,  # [n, k_max] int32 column indices
    y_idx: jax.Array,
    w: jax.Array,
    *,
    d: int,
    k: int,
    multinomial: bool,
    lam_l2: float,
    lam_l1: float = 0.0,
    use_l1: bool = False,
    fit_intercept: bool = True,
    standardize: bool = True,
    max_iter: int = 100,
    tol: float = 1e-6,
    lbfgs_memory: int = 10,
    fast: bool = False,
    warm_start=None,
) -> Dict[str, jax.Array]:
    """Sparse (padded-ELL) logistic fit. Standardization is SCALE-ONLY — the
    data is divided by the per-column std but never centered, preserving
    sparsity (the reference's sparse trick, classification.py:975-1098: cuML qn
    standardizes sparse input without mean subtraction). Coefficients return in
    original space; no mu offset is folded into the intercept."""
    mu, d_scale, total_w = _ell_scaling(values, indices, w, d, standardize)
    matvec, rmat = _ell_ops(values, indices, d, fast)
    return _fit_common(
        matvec, rmat, values.shape[0],
        values.dtype, d, y_idx, w, mu, d_scale, total_w,
        k=k, multinomial=multinomial, lam_l2=lam_l2, lam_l1=lam_l1, use_l1=use_l1,
        fit_intercept=fit_intercept, max_iter=max_iter, tol=tol, lbfgs_memory=lbfgs_memory,
        warm_start=warm_start,
    )


def _ell_scaling(values, indices, w, d: int, standardize: bool):
    """Scale-only standardization statistics for the padded-ELL layout:
    returns (mu=0, d_scale [d], total_w) — sparse data is never centered."""
    from .sparse import ell_col_moments

    if standardize:
        total_w, _, var = ell_col_moments(values, indices, w, d)
        sigma = jnp.sqrt(var * (total_w / jnp.maximum(total_w - 1.0, 1.0)))
        d_scale = jnp.where(sigma > 0, 1.0 / jnp.maximum(sigma, 1e-30), 0.0)
    else:
        total_w = jnp.sum(w)
        d_scale = jnp.ones((d,), values.dtype)
    mu = jnp.zeros((d,), values.dtype)  # scale-only: never centered
    return mu, d_scale, total_w


def _dense_ops(X, fast: bool = False):
    """(matvec, rmat) closures over dense X for `_fit_common`. ``fast``
    (solver_precision="bf16") runs the X·β forward and Xᵀr gradient matvecs
    — the two O(n·d) contractions every L-BFGS iteration pays twice — with
    bf16 inputs and f32 accumulation on the MXU; the L-BFGS state, line
    search, and convergence scalars downstream stay at the ambient
    precision (docs/performance.md "Mixed-precision solvers"; parity pinned
    by tests/test_precision.py)."""
    if not fast:
        return (lambda Beff: X @ Beff), (lambda r: X.T @ r)
    bX = X.astype(jnp.bfloat16)

    def matvec(Beff):
        return jax.lax.dot(
            bX, Beff.astype(jnp.bfloat16),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32,
        ).astype(X.dtype)

    def rmat(r):
        return jax.lax.dot(
            bX.T, r.astype(jnp.bfloat16),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32,
        ).astype(X.dtype)

    return matvec, rmat


_LANES, _SUBLANES = 128, 8  # a float32 vector register: (8, 128)
_GLM_TILE_ROWS = 1024  # rows of X a visit of the fused pass holds, at most: 12.3 MB at d = 3,000


def _glm_tile_rows(n: int, d: int):
    """Rows of X a visit of `_glm_step` holds: the most, of `_GLM_TILE_ROWS`
    halved down to one register of lanes, that n has and whose VMEM (two
    tiles in flight, the candidates' lane partials and d_eff twice each)
    stays inside three quarters of the limit the kernels declare (1,024 up
    to d = 3,780 on a v5e); None where none does."""
    from . import distance

    tn = _GLM_TILE_ROWS
    while tn >= _LANES:
        held = 4 * d * 2 * (tn + (GLM_SPECULATED + 1) * _LANES)
        if tn <= n and held <= 3 * distance.vmem_limit_bytes() // 4:
            return tn
        tn //= 2
    return None


def glm_pass_of(X, *, multinomial: bool, use_l1: bool, fast: bool) -> str:
    """How `logistic_fit` should read this X in its L-BFGS loop, decided from
    what the committed array shows (asked on the host, outside any trace:
    `kernel_mode` may run its self-test, and no layout is visible in a
    trace). The fused pass (`_glm_step`) where the fit is the binomial
    float32 L-BFGS at full precision, X lies on ONE device (GSPMD cannot
    partition a Mosaic kernel; the `shard_map` form is not written) with its
    ROWS ON THE LANES, as a TPU lays a float32 [n, 3000] block out (d not a
    multiple of 128, n one: `parallel/mesh.py`), whole registers of columns
    (d a multiple of 8) and a tile of rows that fits VMEM (`_glm_tile_rows`);
    the interpreter takes any layout (it runs where CI asks for the real
    kernel). Everything else keeps the two products: a row-major X wants a
    second kernel body (its forward reduces over lanes), the multinomial
    forward is a real matmul."""
    from . import distance
    from ..parallel.mesh import lies_row_major

    mode = distance.kernel_mode()
    if (
        mode == "jnp" or multinomial or use_l1 or fast
        or not isinstance(X, jax.Array) or X.ndim != 2 or X.dtype != jnp.float32
        or len(X.devices()) != 1
        or X.shape[1] % _SUBLANES or _glm_tile_rows(*X.shape) is None
    ):
        return GLM_TWO_PRODUCTS
    if mode == "interpret":
        return GLM_FUSED_INTERPRET
    return GLM_TWO_PRODUCTS if lies_row_major(X) else GLM_FUSED


def _glm_step_kernel(
    scal_ref, xt_ref, db_ref, zp_ref, y_ref, ws_ref, zd_ref, g_ref, *, n: int, tn: int, c: int,
):
    """One visit of a [d, tn] tile of Xᵀ (tn rows of X on the lanes), a
    register of columns (8 sublanes) a loop step, one (8, 128) register of X
    an operation. Phase 1: z_d = Σ over sublanes of x·d_eff (`db_ref`: d_eff
    along every lane) + the offset. Phase 2: for each of the c candidates
    the tile's residual row r_j = ws·(σ(z_p + a_j·z_d) − y), and G_j += x·r_j
    summed over the tile's registers of lanes to [d, 128] lane partials
    (`g_ref`, in VMEM for the whole grid). Float32 products and sums on the
    VPU; the tile is read from VMEM twice, from HBM once. `scal_ref` (SMEM):
    the offset, then a_0..a_{c-1}. Lanes past row n (the last tile of a
    ragged n) hold whatever the fetch left: they are zeroed in x and r
    before they can reach a partial. (Whole [8, tn] operands with d_eff a
    column broadcast along the lanes read shorter and ran 14 to 36 ms a
    pass where this runs 6.3; a [1, 128] slice of a wider value does not
    broadcast over sublanes in Mosaic, hence the slices of the refs:
    PERF.md, PR 37.)"""
    from jax.experimental import pallas as pl

    i = pl.program_id(0)
    d = xt_ref.shape[0]
    lanes = [slice(k, k + _LANES) for k in range(0, tn, _LANES)]

    @pl.when(i == 0)
    def _():
        g_ref[...] = jnp.zeros(g_ref.shape, g_ref.dtype)

    def visit(ragged: bool):
        def in_rows(sublanes, at):  # lanes of the tile that are rows of X
            return i * tn + at.start + jax.lax.broadcasted_iota(jnp.int32, (sublanes, _LANES), 1) < n

        def registers(s):
            rows = pl.ds(pl.multiple_of(s * _SUBLANES, _SUBLANES), _SUBLANES)
            x = [xt_ref[rows, at] for at in lanes]
            return rows, ([jnp.where(ok, v, 0.0) for ok, v in zip(valid_x, x)] if ragged else x)

        if ragged:
            valid_x = [in_rows(_SUBLANES, at) for at in lanes]

        def forward(s, acc):
            rows, x = registers(s)
            db = db_ref[rows, :]
            return [a + v * db for a, v in zip(acc, x)]

        zero = jnp.zeros((_SUBLANES, _LANES), jnp.float32)
        acc = jax.lax.fori_loop(0, d // _SUBLANES, forward, [zero] * len(lanes))
        over_sublanes = [[] for _ in range(c)]  # r_j of each register of lanes, the same in its eight sublanes
        for a, at in zip(acc, lanes):
            z_d = jnp.sum(a, axis=0, keepdims=True) + scal_ref[0, 0]
            zd_ref[:, at] = z_d
            z_p, y, ws = zp_ref[:, at], y_ref[:, at], ws_ref[:, at]
            for j in range(c):
                r = ws * (jax.nn.sigmoid(z_p + scal_ref[0, 1 + j] * z_d) - y)
                if ragged:
                    r = jnp.where(in_rows(1, at), r, 0.0)
                over_sublanes[j].append(jnp.broadcast_to(r, (_SUBLANES, _LANES)))

        def gradient(s, carry):
            rows, x = registers(s)
            for j in range(c):
                g_ref[j, rows, :] += sum(v * r for v, r in zip(x, over_sublanes[j]))
            return carry

        jax.lax.fori_loop(0, d // _SUBLANES, gradient, 0)

    if n % tn == 0:
        visit(False)
    else:
        last = pl.num_programs(0) - 1
        pl.when(i < last)(lambda: visit(False))
        pl.when(i == last)(lambda: visit(True))


def _glm_step(X, d_eff, offset, z_p, y, w_share, a, *, interpret: bool):
    """The fused pass of a binomial L-BFGS iteration, `srml_glm_step_f32`:
    ONE read of X gives z_d = X·d_eff + offset [n] and, for each candidate
    step a_j, Xᵀ·r_j [c, d] with r_j = w_share·(σ(z_p + a_j·z_d) − y): the
    gradient's data term at the point z_p + a_j·z_d. The kernel takes X as
    Xᵀ, [d, n] with the rows of X on the lanes: for an X that lies
    column-major (a v5e's choice at d = 3,000) that is the same bytes, and
    no copy is made. `glm_pass_of` says which X this is written for."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from . import distance

    n, d = X.shape
    c = a.shape[0]
    f32 = jnp.float32
    tn = _glm_tile_rows(n, d)

    def rows(v):
        return v.astype(f32)[None, :]

    row_tile = pl.BlockSpec((1, tn), lambda i: (0, i))
    z_d, partials = pl.pallas_call(
        partial(_glm_step_kernel, n=n, tn=tn, c=c),
        grid=(pl.cdiv(n, tn),),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((d, tn), lambda i: (0, i)),
            pl.BlockSpec((d, _LANES), lambda i: (0, 0)),
            row_tile, row_tile, row_tile,
        ],
        out_specs=[row_tile, pl.BlockSpec((c, d, _LANES), lambda i: (0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, n), f32), jax.ShapeDtypeStruct((c, d, _LANES), f32)],
        name=distance.kernel_name("glm_step", False), **distance._call_params(interpret),
    )(
        jnp.concatenate([jnp.reshape(offset, (1,)), a]).astype(f32)[None, :],
        X.T, jnp.broadcast_to(d_eff.astype(f32)[:, None], (d, _LANES)),
        rows(z_p), rows(y), rows(w_share),
    )
    return z_d[0], jnp.sum(partials, axis=2)


def _ell_ops(values, indices, d: int, fast: bool = False):
    """(matvec, rmat) closures over the ELL layout for `_fit_common`.
    ``fast`` is the scatter-path analog of `_dense_ops`' bf16 contract:
    no MXU dot to cast, so the stored values are ROUNDED through bf16 once
    (bf16 inputs) while all accumulation stays at the ambient precision."""
    from .sparse import ell_matmul, ell_rmatvec

    gv = values.astype(jnp.bfloat16).astype(values.dtype) if fast else values

    def rmat(r):  # Xᵀ r via per-column ELL scatter
        return jnp.stack(
            [ell_rmatvec(gv, indices, r[:, j], d) for j in range(r.shape[1])],
            axis=1,
        )

    return (lambda Beff: ell_matmul(gv, indices, Beff)), rmat


@partial(
    jax.jit,
    static_argnames=(
        "k", "fit_intercept", "standardize", "max_iter", "lbfgs_memory", "multinomial", "use_l1",
        "fast",
    ),
)
def logistic_fit_batched(
    X: jax.Array,
    y_idx: jax.Array,
    w: jax.Array,
    lam_l2s: jax.Array,  # [S] per-grid-point L2 strengths
    lam_l1s: jax.Array,  # [S] per-grid-point L1 strengths
    *,
    k: int,
    multinomial: bool,
    use_l1: bool = False,
    fit_intercept: bool = True,
    standardize: bool = True,
    max_iter: int = 100,
    tol: float = 1e-6,
    lbfgs_memory: int = 10,
    fast: bool = False,
) -> Dict[str, jax.Array]:
    """ONE compiled program that solves a whole (lam_l2, lam_l1) grid.

    The regularization strengths are traced scalars of the objective, so the
    grid vmaps over them: XLA fuses the S per-model logit matmuls into one
    wider matmul per L-BFGS iteration — X is read TWICE PER ITERATION FOR THE
    WHOLE GRID instead of twice per iteration per model, and the grid pays
    max(iters) loop steps instead of sum(iters). Converged grid elements
    freeze exactly (`freeze_when_done`), so each returned model matches its
    sequential `logistic_fit` counterpart. Statics (use_l1, max_iter, ...)
    must be uniform across the grid — the model layer groups param sets by
    that signature and falls back to sequential solves otherwise.

    Returns the `logistic_fit` dict with a leading [S] axis on every entry."""
    d = X.shape[1]
    mu, d_scale, total_w = _make_scaling(X, w, standardize, fit_intercept)
    matvec, rmat = _dense_ops(X, fast)

    def fit_one(lam_l2, lam_l1):
        return _fit_common(
            matvec, rmat, X.shape[0],
            X.dtype, d, y_idx, w, mu, d_scale, total_w,
            k=k, multinomial=multinomial, lam_l2=lam_l2, lam_l1=lam_l1, use_l1=use_l1,
            fit_intercept=fit_intercept, max_iter=max_iter, tol=tol,
            lbfgs_memory=lbfgs_memory,
        )

    return jax.vmap(fit_one)(lam_l2s, lam_l1s)


@partial(
    jax.jit,
    static_argnames=(
        "d", "k", "fit_intercept", "standardize", "max_iter", "lbfgs_memory", "multinomial",
        "use_l1", "fast",
    ),
)
def logistic_fit_ell_batched(
    values: jax.Array,
    indices: jax.Array,
    y_idx: jax.Array,
    w: jax.Array,
    lam_l2s: jax.Array,
    lam_l1s: jax.Array,
    *,
    d: int,
    k: int,
    multinomial: bool,
    use_l1: bool = False,
    fit_intercept: bool = True,
    standardize: bool = True,
    max_iter: int = 100,
    tol: float = 1e-6,
    lbfgs_memory: int = 10,
    fast: bool = False,
) -> Dict[str, jax.Array]:
    """Sparse (padded-ELL) analog of `logistic_fit_batched`: one program for
    the whole grid, scale-only standardization computed once and shared."""
    mu, d_scale, total_w = _ell_scaling(values, indices, w, d, standardize)
    matvec, rmat = _ell_ops(values, indices, d, fast)

    def fit_one(lam_l2, lam_l1):
        return _fit_common(
            matvec, rmat, values.shape[0],
            values.dtype, d, y_idx, w, mu, d_scale, total_w,
            k=k, multinomial=multinomial, lam_l2=lam_l2, lam_l1=lam_l1, use_l1=use_l1,
            fit_intercept=fit_intercept, max_iter=max_iter, tol=tol,
            lbfgs_memory=lbfgs_memory,
        )

    return jax.vmap(fit_one)(lam_l2s, lam_l1s)


def _build_glm_problem(
    matvec, rmat, dtype, d, y_idx, w, mu, d_scale, total_w,
    *, k, multinomial, lam_l2, fit_intercept, step=None,
) -> Dict[str, Any]:
    """The GLM objective closures — z_of / rowloss / rowloss_alphas /
    penalty_terms / grad_from_z plus the flat-parameter geometry — shared by
    the one-program `_fit_common` path and the host-segmented checkpointing
    driver (`logistic_fit_checkpointed`), so both trace the identical math.
    With `step` (binomial only: `_glm_step` over the fit's X) also `step_of`,
    the fused pass in the solver's terms."""
    k_out = k if multinomial else 1
    n_flat = d * k_out + k_out

    def unflatten(xf):
        return xf[: d * k_out].reshape(d, k_out), xf[d * k_out :]

    def effective(xf):
        B, b0 = unflatten(xf)
        Beff = B * d_scale[:, None]
        return Beff, (b0 - mu @ Beff) if fit_intercept else -(mu @ Beff)

    def z_of(xf):
        Beff, offset = effective(xf)
        return matvec(Beff) + offset[None, :]  # LINEAR in (B, b0)

    if multinomial:
        def rowloss(z):
            z_true = jnp.take_along_axis(z, y_idx[:, None], axis=1)[:, 0]
            return jnp.sum(w * (jax.nn.logsumexp(z, axis=1) - z_true)) / total_w

        def rowloss_alphas(z_p, z_d, a):
            z = z_p[:, None, :] + a[None, :, None] * z_d[:, None, :]  # [n, S, k]
            idx = jnp.broadcast_to(y_idx[:, None, None], (z.shape[0], a.shape[0], 1))
            z_true = jnp.take_along_axis(z, idx, axis=2)[..., 0]  # [n, S]
            return jnp.einsum("n,ns->s", w, jax.nn.logsumexp(z, axis=2) - z_true) / total_w
    else:
        y = y_idx.astype(dtype)

        def rowloss(z):
            z0 = z[:, 0]
            return jnp.sum(w * (jax.nn.softplus(z0) - y * z0)) / total_w

        def rowloss_alphas(z_p, z_d, a):
            z = z_p[:, :1] + a[None, :] * z_d[:, :1]  # [n, S]
            return jnp.einsum(
                "n,ns->s", w, jax.nn.softplus(z) - y[:, None] * z
            ) / total_w

    def penalty_terms(xf, df_):
        Bx, Bd = xf[: d * k_out], df_[: d * k_out]
        return (
            0.5 * lam_l2 * jnp.sum(Bx * Bx),
            lam_l2 * jnp.dot(Bx, Bd),
            0.5 * lam_l2 * jnp.sum(Bd * Bd),
        )

    def grad_from_z(xf, z, xr=None):
        """Analytic gradient from the logits: ∂loss/∂z is the GLM residual,
        the chain through z = matvec(B·d_scale) + (b0 − mu·Beff) is one
        transposed data pass (rmat; or its result `xr` [d, k_out], where a
        fused pass has formed it at these logits) plus tiny vector algebra."""
        B, _ = unflatten(xf)
        if multinomial:
            p = jax.nn.softmax(z, axis=1)
            r = w[:, None] * (p - jax.nn.one_hot(y_idx, k, dtype=dtype)) / total_w
        else:
            p = jax.nn.sigmoid(z[:, 0])
            r = ((w * (p - y)) / total_w)[:, None]  # [n, 1]
        if xr is None:
            xr = rmat(r)
        g_beff = xr - mu[:, None] * jnp.sum(r, axis=0)[None, :]  # [d, k_out]
        dB = g_beff * d_scale[:, None] + lam_l2 * B
        db0 = jnp.sum(r, axis=0) if fit_intercept else jnp.zeros((k_out,), dtype)
        return jnp.concatenate([dB.ravel(), db0])

    step_of = None
    if step is not None:
        w_share = w / total_w

        def step_of(df_, z_p, a):
            Beff, offset = effective(df_)
            z_d, xr = step(Beff[:, 0], offset[0], z_p[:, 0], y, w_share, a)
            return z_d[:, None], xr[:, :, None]  # [n, 1], [c, d, 1]

    return dict(
        k_out=k_out, n_flat=n_flat, unflatten=unflatten, z_of=z_of,
        rowloss=rowloss, rowloss_alphas=rowloss_alphas,
        penalty_terms=penalty_terms, grad_from_z=grad_from_z, step_of=step_of,
    )


def _finish_glm(
    xf, obj, n_iter, stalled, unflatten, d_scale, mu, *, fit_intercept, multinomial,
) -> Dict[str, jax.Array]:
    """Flat iterate -> model-attribute dict in ORIGINAL feature space
    (standardization folded out, Spark multinomial intercept centering)."""
    B, b0 = unflatten(xf)
    coef = (B * d_scale[:, None]).T  # [k_out, d] original space
    intercept = b0 - coef @ mu if fit_intercept else jnp.zeros_like(b0)
    if multinomial:
        # softmax shift invariance: center intercepts (Spark parity,
        # reference classification.py:1077-1089)
        intercept = intercept - jnp.mean(intercept)
    return {
        "coef_": coef, "intercept_": intercept, "objective_": obj,
        "n_iter_": n_iter, "stalled_": stalled,
    }


def _warm_x0(warm_start, d, k_out, mu, d_scale, fit_intercept, dtype):
    """ORIGINAL-space (coef [k_out, d], intercept [k_out]) -> the flat
    STANDARDIZED iterate the solvers walk — the exact inverse of
    `_finish_glm`'s fold-out, so seeding from a converged model restarts the
    solver AT that model (docs/scheduling.md "Warm starts"). Columns whose
    d_scale is 0 (constant features) carry zero coefficient either way."""
    coef, intercept = warm_start
    coef = jnp.asarray(coef, dtype).reshape(k_out, d)
    intercept = jnp.asarray(intercept, dtype).reshape(k_out)
    scale = d_scale[:, None]
    B = jnp.where(scale != 0, coef.T / jnp.where(scale == 0, 1.0, scale), 0.0)
    b0 = (intercept + coef @ mu) if fit_intercept else jnp.zeros((k_out,), dtype)
    return jnp.concatenate([B.ravel(), b0])


def _fit_common(
    matvec, rmat, n_rows, dtype, d, y_idx, w, mu, d_scale, total_w,
    *, k, multinomial, lam_l2, lam_l1, use_l1, fit_intercept, max_iter, tol, lbfgs_memory,
    warm_start=None, step=None,
) -> Dict[str, jax.Array]:
    prob = _build_glm_problem(
        matvec, rmat, dtype, d, y_idx, w, mu, d_scale, total_w,
        k=k, multinomial=multinomial, lam_l2=lam_l2, fit_intercept=fit_intercept, step=step,
    )
    k_out, n_flat, unflatten = prob["k_out"], prob["n_flat"], prob["unflatten"]
    z_of, rowloss, rowloss_alphas = prob["z_of"], prob["rowloss"], prob["rowloss_alphas"]
    penalty_terms, grad_from_z = prob["penalty_terms"], prob["grad_from_z"]
    x_warm = (
        _warm_x0(warm_start, d, k_out, mu, d_scale, fit_intercept, dtype)
        if warm_start is not None
        else None
    )

    if use_l1:
        # L1/ElasticNet: OWL-QN over the flattened (B, b0) with the L1 mask
        # covering coefficients only (intercepts are never penalized — Spark
        # semantics; reference classification.py:1051-1057 `penalty='elasticnet'`)
        from .owlqn import owlqn_minimize

        def flat_loss(xf):
            p0, _, _ = penalty_terms(xf, jnp.zeros_like(xf))
            return rowloss(z_of(xf)) + p0

        l1_mask = jnp.concatenate(
            [jnp.ones((d * k_out,), dtype), jnp.zeros((k_out,), dtype)]
        )
        x0 = x_warm if x_warm is not None else jnp.zeros((n_flat,), dtype)
        xf, obj, n_iter = owlqn_minimize(
            flat_loss, x0, l1_mask, lam_l1,
            max_iter=max_iter, tol=tol, memory=lbfgs_memory,
        )
        stalled, fused_hits = jnp.asarray(False), None
    else:
        xf, obj, n_iter, stalled, fused_hits = _glm_qn_minimize(
            z_of, rowloss, rowloss_alphas, grad_from_z, (n_rows, k_out), n_flat,
            dtype, penalty_terms, max_iter=max_iter, tol=tol, memory=lbfgs_memory,
            x0=x_warm, step_of=prob["step_of"],
        )
    state = _finish_glm(
        xf, obj, n_iter, stalled, unflatten, d_scale, mu,
        fit_intercept=fit_intercept, multinomial=multinomial,
    )
    if fused_hits is not None:
        state["fused_hits_"] = fused_hits
    return state


def _fit_common_checkpointed(
    matvec, rmat, n_rows, dtype, d, y_idx, w, mu, d_scale, total_w,
    *, k, multinomial, lam_l2, lam_l1, use_l1, fit_intercept, max_iter, tol,
    lbfgs_memory, ckpt_key, placement_key, warm_start=None,
) -> Dict[str, jax.Array]:
    """`_fit_common` with the solver loop segmented for checkpointing
    (docs/robustness.md "Elastic recovery"): the IDENTICAL objective closures
    (`_build_glm_problem`) drive the host-segmented OWL-QN / GLM-QN loops
    instead of the one-program `lax.while_loop`, so an interrupted fit
    resumes from the last segment boundary. Runs eagerly (the segments are
    jitted; the glue is host code) — callers gate on
    `checkpoint.solver_checkpoints_active()`."""
    prob = _build_glm_problem(
        matvec, rmat, dtype, d, y_idx, w, mu, d_scale, total_w,
        k=k, multinomial=multinomial, lam_l2=lam_l2, fit_intercept=fit_intercept,
    )
    k_out, n_flat, unflatten = prob["k_out"], prob["n_flat"], prob["unflatten"]
    z_of, rowloss, rowloss_alphas = prob["z_of"], prob["rowloss"], prob["rowloss_alphas"]
    penalty_terms, grad_from_z = prob["penalty_terms"], prob["grad_from_z"]
    x_warm = (
        _warm_x0(warm_start, d, k_out, mu, d_scale, fit_intercept, dtype)
        if warm_start is not None
        else None
    )

    if use_l1:
        from .owlqn import owlqn_minimize_segmented

        def flat_loss(xf):
            p0, _, _ = penalty_terms(xf, jnp.zeros_like(xf))
            return rowloss(z_of(xf)) + p0

        l1_mask = jnp.concatenate(
            [jnp.ones((d * k_out,), dtype), jnp.zeros((k_out,), dtype)]
        )
        x0 = x_warm if x_warm is not None else jnp.zeros((n_flat,), dtype)
        xf, obj, n_iter = owlqn_minimize_segmented(
            flat_loss, x0, l1_mask, lam_l1,
            max_iter=max_iter, tol=tol, memory=lbfgs_memory,
            ckpt_key=ckpt_key + ":owlqn", placement_key=placement_key,
        )
        stalled = jnp.asarray(False)
    else:
        xf, obj, n_iter, stalled = glm_qn_minimize_segmented(
            z_of, rowloss, rowloss_alphas, grad_from_z, (n_rows, k_out), n_flat,
            dtype, penalty_terms, max_iter=max_iter, tol=tol, memory=lbfgs_memory,
            ckpt_key=ckpt_key, placement_key=placement_key, x0=x_warm,
        )
    return _finish_glm(
        xf, obj, n_iter, stalled, unflatten, d_scale, mu,
        fit_intercept=fit_intercept, multinomial=multinomial,
    )


def logistic_fit_checkpointed(
    X: jax.Array,
    y_idx: jax.Array,
    w: jax.Array,
    *,
    k: int,
    multinomial: bool,
    lam_l2: float,
    lam_l1: float = 0.0,
    use_l1: bool = False,
    fit_intercept: bool = True,
    standardize: bool = True,
    max_iter: int = 100,
    tol: float = 1e-6,
    lbfgs_memory: int = 10,
    fast: bool = False,
    ckpt_key: str = "logistic",
    placement_key=None,
    warm_start=None,
) -> Dict[str, jax.Array]:
    """`logistic_fit` with solver checkpoints: same returns, same math
    (shared closures), segmented loop. The model layer routes here when
    ``config["checkpoint_every_iters"]`` > 0 and a `CheckpointStore` is
    active; a same-placement resume is bit-identical to an uninterrupted
    checkpointed fit (pinned by tests/test_recovery.py). `fast` trajectories
    are keyed apart — a bf16 solve must never resume a full-precision one."""
    d = X.shape[1]
    mu, d_scale, total_w = _make_scaling(X, w, standardize, fit_intercept)
    if fast:
        ckpt_key = ckpt_key + ":bf16"
    matvec, rmat = _dense_ops(X, fast)
    return _fit_common_checkpointed(
        matvec, rmat, X.shape[0],
        X.dtype, d, y_idx, w, mu, d_scale, total_w,
        k=k, multinomial=multinomial, lam_l2=lam_l2, lam_l1=lam_l1, use_l1=use_l1,
        fit_intercept=fit_intercept, max_iter=max_iter, tol=tol,
        lbfgs_memory=lbfgs_memory, ckpt_key=ckpt_key, placement_key=placement_key,
        warm_start=warm_start,
    )


def logistic_fit_ell_checkpointed(
    values: jax.Array,
    indices: jax.Array,
    y_idx: jax.Array,
    w: jax.Array,
    *,
    d: int,
    k: int,
    multinomial: bool,
    lam_l2: float,
    lam_l1: float = 0.0,
    use_l1: bool = False,
    fit_intercept: bool = True,
    standardize: bool = True,
    max_iter: int = 100,
    tol: float = 1e-6,
    lbfgs_memory: int = 10,
    fast: bool = False,
    ckpt_key: str = "logistic_ell",
    placement_key=None,
    warm_start=None,
) -> Dict[str, jax.Array]:
    """Sparse (padded-ELL) analog of `logistic_fit_checkpointed` — scale-only
    standardization, same closures as `logistic_fit_ell`, segmented loop."""
    mu, d_scale, total_w = _ell_scaling(values, indices, w, d, standardize)
    if fast:
        ckpt_key = ckpt_key + ":bf16"
    matvec, rmat = _ell_ops(values, indices, d, fast)
    return _fit_common_checkpointed(
        matvec, rmat, values.shape[0],
        values.dtype, d, y_idx, w, mu, d_scale, total_w,
        k=k, multinomial=multinomial, lam_l2=lam_l2, lam_l1=lam_l1, use_l1=use_l1,
        fit_intercept=fit_intercept, max_iter=max_iter, tol=tol,
        lbfgs_memory=lbfgs_memory, ckpt_key=ckpt_key, placement_key=placement_key,
        warm_start=warm_start,
    )


@partial(jax.jit, static_argnames=("multinomial",))
def logistic_predict(
    X: jax.Array, coef: jax.Array, intercept: jax.Array, *, multinomial: bool
) -> Tuple[jax.Array, jax.Array]:
    """Returns (raw [n, k], prob [n, k]) — Spark's rawPrediction/probability.

    Binary: raw = [-m, m] with m the margin (Spark convention)."""
    if multinomial:
        raw = X @ coef.T + intercept[None, :]
        prob = jax.nn.softmax(raw, axis=1)
    else:
        m = X @ coef[0] + intercept[0]
        raw = jnp.stack([-m, m], axis=1)
        p1 = jax.nn.sigmoid(m)
        prob = jnp.stack([1.0 - p1, p1], axis=1)
    return raw, prob
