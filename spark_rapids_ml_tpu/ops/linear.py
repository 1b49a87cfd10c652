#
# Distributed linear regression solvers — in-tree replacements for
# `cuml.linear_model.{linear_regression_mg.LinearRegressionMG, ridge_mg.RidgeMG,
# cd_mg.CDMG}` (selected by reg params in reference regression.py:510-548).
#
# Design: ALL paths take the normal-equation statistics first (for dense rows
# `linalg.weighted_xy_moments`: PCA's mean pass and its tiled centred
# contraction, Σw(x-x̄)(x-x̄)ᵀ, with Σw(x-x̄)(y-ȳ) and Σw(y-ȳ)² taken in the
# same tiles — MXU contractions per row shard + psum, the NCCL allreduce
# equivalent; never the uncentred form, nothing of X's size written), then
# solve locally on replicated (d,d) data, a program of its own:
#   * reg=0            → weighted OLS solve               (OLS-eig analog)
#   * l1=0, reg>0      → ridge with alpha scaled by Σw    (reference parity
#                        trick, regression.py:536-542: Spark's 1/(2n)·RSS+λ/2‖b‖²
#                        ⇔ RSS+nλ‖b‖²)
#   * l1>0             → coordinate descent ON THE GRAM with incremental
#                        q=A·b updates — O(d²) per sweep, no further passes
#                        over the data (CDMG analog; sklearn/Spark objective
#                        1/(2n)·RSS + λα‖b‖₁ + λ(1-α)/2‖b‖²)
#
# `standardization=True` (Spark default) scales the penalty space by feature
# std and unscales afterward, penalizing the intercept never.
#
from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp

from .. import telemetry
from ..parallel.mesh import x_layout_of
from .linalg import gram_panels, weighted_xy_moments

# A fit's statistics, whoever made them: Σw, the means the sums are taken
# about (x̄ [d] and ȳ; zeros for a fit without an intercept), and about them
# Σw(x-x̄)(x-x̄)ᵀ [d, d], Σw(x-x̄)(y-ȳ) [d], Σw(y-ȳ)². The names of the
# host-retained checkpoint payload, in the tuple's order.
_STATS_NAMES = ("sw", "xm", "ym", "Gc", "cc", "syc")


@partial(jax.jit, static_argnames=("fit_intercept", "fast", "mesh"))
def _dense_stats(X, y, w, *, fit_intercept: bool = True, fast: bool = False, mesh=None):
    """The one pass over a dense (X, y): `linalg.weighted_xy_moments`, PCA's
    mean pass and tiled centred contraction with the y-moments taken in the
    same tiles. ``fast`` (solver_precision="bf16") is `centered_gram`'s fast
    arm: the O(n·d²) gram bf16-in / f32-accumulate, everything else at full
    precision (docs/performance.md "Mixed-precision solvers")."""
    return weighted_xy_moments(X, y, w, center=fit_intercept, fast=fast, mesh=mesh)


def _gram_span(rows: int, d: int, fast: bool, x_layout: str):
    """The `gram` span of one pass over the data (a child of the caller's
    `fit/solve`; PCA's name, so that one set of metrics reads both), and the
    count of it: a fit from retained statistics opens none and adds nothing
    to `linear.gram_passes`. `panels` and `panel_cols`: PCA's (the padded-ELL
    pass scatter-adds its gram and has one panel)."""
    telemetry.registry().inc("linear.gram_passes")
    panels, panel_cols = (1, d) if x_layout == "ell" else gram_panels(d, fast)
    return telemetry.span(
        "gram", rows=rows, d=d, precision="bf16" if fast else "f32", x_layout=x_layout, targets=1,
        panels=panels, panel_cols=panel_cols,
    )


def _gram_pass(X, y, w, *, fit_intercept: bool, fast: bool, mesh=None):
    """The statistics of a resident dense (X, y), ready, under their span."""
    with _gram_span(int(X.shape[0]), int(X.shape[1]), fast, x_layout_of(X)):
        stats = _dense_stats(X, y, w, fit_intercept=fit_intercept, fast=fast, mesh=mesh)
        with telemetry.device_wait("gram"):
            return jax.block_until_ready(stats)


def _cd_elastic_net(A, r, lam, l1_ratio, max_iter, tol, kernel=None):
    """Coordinate descent on normalized gram A=G/n, r=c/n.

    Soft-threshold updates with incremental q = A·b maintenance; converges when
    the max coefficient change in a sweep is <= tol. Returns (b, sweeps run,
    the last sweep's max change).

    A sweep is d dependent steps of a few hundred flops each, so what a step
    costs is what it takes to START: as a chain of XLA ops a step is launch
    latency (4.4 us of 5 scalar reads, a column slice, an axpy and a
    one-element update at d = 3,000 on a v5e; 2.6 us as the one op below),
    and 30,000 steps a fit put more ops into a profiler's window than its
    host can hold (PERF.md, PR 34). So a step is written as one update of
    the state (b; q; the sweep's largest change): the coordinate's scalars
    are masked sums over the lanes (exact: one term is not zero), and q
    takes row j of the symmetrized gram (contiguous; a column of a row-tiled
    array is not). ``kernel`` ("pallas" / "interpret"; `_cd_kernel_mode`)
    runs a sweep's steps inside one Mosaic kernel that holds the gram in VMEM
    (`_cd_sweep_kernel`), bit for bit the same arithmetic; None is the XLA
    loop, which every dtype, width and `vmap` takes. Every coordinate's
    arithmetic and the cyclic order are the same in all three."""
    d = A.shape[0]
    l1 = lam * l1_ratio
    l2 = lam * (1.0 - l1_ratio)
    A = 0.5 * (A + A.T)
    diag = jnp.diag(A)
    denom = jnp.maximum(diag + l2, 1e-30)
    if kernel is not None:
        return _cd_sweeps_in_kernel(A, r, diag, denom, l1, max_iter, tol, interpret=kernel == "interpret")
    lane = jnp.arange(d)

    def sweep(b_q):
        def coord(j, state):
            b, q, max_delta = state
            at = lane == j

            def pick(v):
                return jnp.sum(jnp.where(at, v, 0.0))

            b_j = pick(b)
            rho = pick(r - q + diag * b)
            bj = jnp.sign(rho) * jnp.maximum(jnp.abs(rho) - l1, 0.0) / pick(denom)
            delta = bj - b_j
            row = jax.lax.dynamic_index_in_dim(A, j, 0, keepdims=False)
            return jnp.stack([jnp.where(at, bj, b), q + row * delta, jnp.maximum(max_delta, jnp.abs(delta))])

        start = jnp.stack([*b_q, jnp.zeros((d,), A.dtype)])
        b, q, max_delta = jax.lax.fori_loop(0, d, coord, start)
        return (b, q), max_delta[0]

    def cond(state):
        (_, _), it, max_delta = state
        return jnp.logical_and(it < max_iter, max_delta > tol)

    def body(state):
        b_q, it, _ = state
        b_q, max_delta = sweep(b_q)
        return b_q, it + 1, max_delta

    from .owlqn import freeze_when_done

    b0 = jnp.zeros((d,), A.dtype)
    q0 = jnp.zeros((d,), A.dtype)
    # freeze_when_done: vmap-safe for batched (alpha, l1_ratio) grids — a
    # converged grid element must stop sweeping while slower ones finish
    (b, _), n_iter, max_delta = jax.lax.while_loop(
        cond, freeze_when_done(cond, body), ((b0, q0), 0, jnp.array(jnp.inf, A.dtype))
    )
    return b, n_iter, max_delta


# ------------------------------------------------- the sweep as a kernel ----

_LANES, _SUBLANES = 128, 8


def _padded(d: int):
    """(rows, lanes) of the gram as the kernel holds it: whole (8, 128) tiles."""
    return -(-d // _SUBLANES) * _SUBLANES, -(-d // _LANES) * _LANES


def _cd_kernel_mode(gram: jax.Array):
    """How a fit on this [d, d] gram descends in this process: "pallas" or
    "interpret" (the distance core's `kernel_mode`, which a TPU answers with
    "pallas" and CI's interpreter runs with "interpret") where the gram is
    float32, lies on one device (GSPMD cannot partition a Mosaic kernel, and
    a gram replicated over a mesh would ask it to) and, padded, fits three
    quarters of the scoped-VMEM limit the kernels declare (d up to 3,400 on
    a v5e); else None: the XLA loop. Asked outside any trace (`kernel_mode`
    may run its self-test)."""
    from . import distance

    mode = distance.kernel_mode()
    rows, lanes = _padded(int(gram.shape[-1]))
    held = 4 * lanes * (rows + 3 * _SUBLANES)  # the gram, and three (8, lanes) arrays
    one_device = len(getattr(gram, "devices", lambda: (None,))()) == 1
    if (mode == "jnp" or gram.dtype != jnp.float32 or not one_device
            or held > 3 * distance.vmem_limit_bytes() // 4):
        return None
    return mode


def _cd_sweep_kernel(l1_ref, A_ref, consts_ref, state_ref, out_ref, *, d: int):
    """One cyclic sweep, d dependent steps inside one kernel. `A_ref`: the
    symmetrized gram, zero-padded, whole in VMEM; `consts_ref` rows r, diag,
    guarded denominator; `state_ref` / `out_ref` rows b, q and (out) the
    sweep's largest change in every lane. Scalars are (1, 1) arrays: a
    masked lane sum picks them, broadcasting puts them back."""
    from jax.experimental import pallas as pl

    lanes = A_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    r, diag, denom = consts_ref[0:1, :], consts_ref[1:2, :], consts_ref[2:3, :]
    l1 = l1_ref[0, 0]

    def coord(j, state):
        b, q, max_delta = state
        at = lane == j

        def pick(v):
            return jnp.sum(jnp.where(at, v, 0.0), axis=1, keepdims=True)

        b_j = pick(b)
        rho = pick(r - q + diag * b)
        bj = jnp.sign(rho) * jnp.maximum(jnp.abs(rho) - l1, 0.0) / pick(denom)
        delta = bj - b_j
        row = A_ref[pl.ds(j, 1), :]
        return jnp.where(at, bj, b), q + row * delta, jnp.maximum(max_delta, jnp.abs(delta))

    start = (state_ref[0:1, :], state_ref[1:2, :], jnp.zeros((1, 1), jnp.float32))
    b, q, max_delta = jax.lax.fori_loop(0, d, coord, start)
    out_ref[0:1, :] = b
    out_ref[1:2, :] = q
    out_ref[2:3, :] = jnp.broadcast_to(max_delta, (1, lanes))
    out_ref[3:, :] = jnp.zeros((_SUBLANES - 3, lanes), jnp.float32)


def _cd_sweeps_in_kernel(A, r, diag, denom, l1, max_iter, tol, *, interpret: bool):
    """`_cd_elastic_net`'s loop with each sweep one `srml_cd_sweep_f32`
    kernel call: the padded gram goes to VMEM once a sweep (37 MB in 46 us
    at the HBM peak), the steps never leave the core."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from . import distance

    d = A.shape[0]
    rows, lanes = _padded(d)
    f32 = jnp.float32
    gram = jnp.zeros((rows, lanes), f32).at[:d, :d].set(A)
    consts = jnp.zeros((_SUBLANES, lanes), f32).at[0, :d].set(r).at[1, :d].set(diag).at[2].set(1.0).at[2, :d].set(denom)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    sweep = pl.pallas_call(
        partial(_cd_sweep_kernel, d=d),
        out_shape=jax.ShapeDtypeStruct((_SUBLANES, lanes), f32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), vmem, vmem, vmem], out_specs=vmem,
        name=distance.kernel_name("cd_sweep", False), **distance._call_params(interpret),
    )
    l1 = jnp.reshape(l1, (1, 1)).astype(f32)

    def cond(state_it):
        state, it = state_it
        return jnp.logical_and(it < max_iter, state[2, 0] > tol)

    def body(state_it):
        state, it = state_it
        return sweep(l1, gram, consts, state), it + 1

    state, n_iter = jax.lax.while_loop(cond, body, (jnp.zeros((_SUBLANES, lanes), f32).at[2].set(jnp.inf), 0))
    return state[0, :d], n_iter, state[2, 0]


def linear_fit(
    X: jax.Array,
    y: jax.Array,
    w: jax.Array,
    *,
    alpha: float,
    l1_ratio: float,
    fit_intercept: bool = True,
    standardize: bool = True,
    use_cd: bool = False,
    max_iter: int = 1000,
    tol: float = 1e-6,
    fast: bool = False,
    mesh=None,
) -> Dict[str, jax.Array]:
    """Weighted linear regression on row-sharded global (X, y) (`mesh`: the
    mesh it is sharded over, see linalg.weighted_cov).

    `alpha` is Spark's regParam (per-sample-normalized objective); the Σw
    scaling for the ridge path happens inside. `fast` runs the gram
    contraction bf16-in / f32-accumulate (`_dense_stats`).

    Two programs run in turn, each under a span of its own (`gram`, then
    `cd` or `normal`), so that each span's wall is its program's: call it,
    do not wrap it in `jax.jit`."""
    stats = _gram_pass(X, y, w, fit_intercept=fit_intercept, fast=fast, mesh=mesh)
    return _solve(
        stats, alpha=alpha, l1_ratio=l1_ratio, fit_intercept=fit_intercept,
        standardize=standardize, use_cd=use_cd, max_iter=max_iter, tol=tol,
    )


def _ell_pass(values, indices, y, w, *, d: int, tile: int, fit_intercept: bool, fast: bool):
    """The statistics of padded-ELL rows, ready, under the `gram` span."""
    with _gram_span(int(values.shape[0]), int(d), fast, "ell"):
        stats = _ell_stats(
            values, indices, y, w, d=d, tile=min(tile, values.shape[0]),
            fit_intercept=fit_intercept, fast=fast,
        )
        with telemetry.device_wait("gram"):
            return jax.block_until_ready(stats)


def linear_fit_ell(
    values: jax.Array,  # [n, k_max] padded-ELL (ops/sparse.py)
    indices: jax.Array,  # [n, k_max] int32
    y: jax.Array,
    w: jax.Array,
    *,
    d: int,
    alpha: float,
    l1_ratio: float,
    fit_intercept: bool = True,
    standardize: bool = True,
    use_cd: bool = False,
    max_iter: int = 1000,
    tol: float = 1e-6,
    tile: int = 8192,
    fast: bool = False,
) -> Dict[str, jax.Array]:
    """Sparse linear regression: identical math to `linear_fit` — the gram and
    moment sufficient statistics are accumulated from the ELL layout by
    scatter-adding per-row outer products (tiled over `tile`-row blocks to
    bound the [tile, k_max, k_max] intermediate), then the SAME replicated
    (d, d) solve runs. Centering/standardization operate on the statistics,
    never the data, so sparsity is preserved AND full dense-parity holds
    (unlike the logistic path, no scale-only compromise is needed)."""
    stats = _ell_pass(values, indices, y, w, d=d, tile=tile, fit_intercept=fit_intercept, fast=fast)
    return _solve(
        stats, alpha=alpha, l1_ratio=l1_ratio, fit_intercept=fit_intercept,
        standardize=standardize, use_cd=use_cd, max_iter=max_iter, tol=tol,
    )


def _ell_raw_sums(values, indices, y, w, d: int, tile: int, fast: bool = False):
    """The raw sums of padded-ELL rows, scatter-added from the stored
    entries: (Σw, Σwx [d], Σwy, XᵀWX [d, d], XᵀWy [d], Σwy²). They add up
    over chunks; `_centred_from_raw` makes a fit's statistics of them.

    ``fast`` is the scatter-add analog of the dense bf16 contract: there is
    no MXU dot to cast here, so the stored values feeding the gram and the
    XᵀWy correlation are ROUNDED through bf16 once (bf16 inputs) while all
    accumulation stays at full precision — same contract shape, parity
    pinned by tests/test_precision.py."""
    from .sparse import ell_rmatvec

    dtype = values.dtype
    gv = values.astype(jnp.bfloat16).astype(dtype) if fast else values
    sw = jnp.sum(w)
    sy = jnp.sum(w * y)
    syy = jnp.sum(w * y * y)
    sx = ell_rmatvec(values, indices, w, d)
    c = ell_rmatvec(gv, indices, w * y, d)

    # tiled gram accumulation: scan a reshape of the full-tile prefix (free,
    # contiguous view) + one direct tail step — never jnp.pad the whole block
    # (that would materialize a second ELL-sized buffer)
    n = values.shape[0]
    k_max = values.shape[1]
    tile = min(tile, n)
    n_full = (n // tile) * tile

    def add_tile(G, args):
        v, i, wt = args  # [b, k_max] ...
        contrib = jnp.einsum("nk,n,nl->nkl", v, wt, v)
        ii = jnp.broadcast_to(i[:, :, None], contrib.shape)
        jj = jnp.broadcast_to(i[:, None, :], contrib.shape)
        G = G.at[ii.ravel(), jj.ravel()].add(contrib.ravel())
        return G, None

    G = jnp.zeros((d, d), dtype)
    if n_full:
        G, _ = jax.lax.scan(
            add_tile,
            G,
            (
                gv[:n_full].reshape(-1, tile, k_max),
                indices[:n_full].reshape(-1, tile, k_max),
                w[:n_full].reshape(-1, tile),
            ),
        )
    if n - n_full:
        G, _ = add_tile(G, (gv[n_full:], indices[n_full:], w[n_full:]))
    return sw, sx, sy, G, c, syy


def _centred_from_raw(raw, fit_intercept: bool):
    """Raw sums -> a fit's statistics (`_STATS_NAMES`), centred on the
    [d, d] side: what the ELL rows must do (centring the rows would fill
    them in). Device or host arrays alike."""
    sw, sx, sy, G, c, syy = raw
    if not fit_intercept:
        return sw, sx * 0, sy * 0, G, c, syy
    xm, ym = sx / sw, sy / sw
    return sw, xm, ym, G - sw * xm[:, None] * xm[None, :], c - sx * ym, syy - sy * ym


_ell_raw_jit = jax.jit(_ell_raw_sums, static_argnames=("d", "tile", "fast"))


@partial(jax.jit, static_argnames=("d", "tile", "fit_intercept", "fast"))
def _ell_stats(values, indices, y, w, *, d: int, tile: int, fit_intercept: bool = True, fast: bool = False):
    return _centred_from_raw(_ell_raw_sums(values, indices, y, w, d, tile, fast), fit_intercept)


def linear_fit_batched(
    X: jax.Array,
    y: jax.Array,
    w: jax.Array,
    alphas: jax.Array,  # [S] Spark regParam grid
    l1_ratios: jax.Array,  # [S] elasticNetParam grid
    *,
    fit_intercept: bool = True,
    standardize: bool = True,
    use_cd: bool = False,
    max_iter: int = 1000,
    tol: float = 1e-6,
    fast: bool = False,
    mesh=None,
) -> Dict[str, jax.Array]:
    """A whole (alpha, l1_ratio) grid from ONE pass over the data: the
    statistics program runs once and one vmapped program solves every grid
    point on the replicated (d, d) gram — grid size adds zero passes.
    `use_cd` is a static of the solve (it selects the solver), so the model
    layer groups grids by it. Converged CD elements freeze exactly
    (`_cd_elastic_net`), so every grid point matches its sequential
    counterpart.

    Returns the `linear_fit` dict with a leading [S] axis on every entry."""
    stats = _gram_pass(X, y, w, fit_intercept=fit_intercept, fast=fast, mesh=mesh)
    return _solve(
        stats, alpha=alphas, l1_ratio=l1_ratios, fit_intercept=fit_intercept,
        standardize=standardize, use_cd=use_cd, max_iter=max_iter, tol=tol, grid=True,
    )


def linear_fit_ell_batched(
    values: jax.Array,
    indices: jax.Array,
    y: jax.Array,
    w: jax.Array,
    alphas: jax.Array,
    l1_ratios: jax.Array,
    *,
    d: int,
    fit_intercept: bool = True,
    standardize: bool = True,
    use_cd: bool = False,
    max_iter: int = 1000,
    tol: float = 1e-6,
    tile: int = 8192,
    fast: bool = False,
) -> Dict[str, jax.Array]:
    """Sparse (padded-ELL) analog of `linear_fit_batched`: one tiled gram
    accumulation feeds the whole grid's solves."""
    stats = _ell_pass(values, indices, y, w, d=d, tile=tile, fit_intercept=fit_intercept, fast=fast)
    return _solve(
        stats, alpha=alphas, l1_ratio=l1_ratios, fit_intercept=fit_intercept,
        standardize=standardize, use_cd=use_cd, max_iter=max_iter, tol=tol, grid=True,
    )


def _solve_from_stats(
    stats, *, alpha, l1_ratio, fit_intercept, standardize, use_cd, max_iter, tol, cd_kernel=None
) -> Dict[str, jax.Array]:
    """Statistics (`_STATS_NAMES`) -> the model's state, on replicated
    (d, d) data."""
    sw, xm, ym, Gc, cc, syc = stats
    dtype = Gc.dtype

    var = jnp.maximum(jnp.diag(Gc) / sw, 0.0)
    if standardize:
        sigma = jnp.sqrt(var)
        d_scale = jnp.where(sigma > 0, 1.0 / jnp.maximum(sigma, 1e-30), 0.0)
    else:
        d_scale = jnp.ones_like(var)

    Gs = Gc * d_scale[:, None] * d_scale[None, :]
    cs = cc * d_scale

    alpha = jnp.asarray(alpha, dtype)
    state = {}
    if use_cd:
        A = Gs / sw
        r = cs / sw
        b_s, n_iter, state["max_delta_"] = _cd_elastic_net(
            A, r, alpha, jnp.asarray(l1_ratio, dtype), max_iter, tol, kernel=cd_kernel
        )
    else:
        # ridge normal equations; alpha==0 degenerates to OLS (+ tiny jitter for
        # numerical safety on singular grams)
        eye = jnp.eye(Gs.shape[0], dtype=dtype)
        ridge_term = alpha * sw + jnp.asarray(1e-10, dtype) * jnp.trace(Gs) / Gs.shape[0]
        b_s = jnp.linalg.solve(Gs + ridge_term * eye, cs)
        n_iter = jnp.array(1, jnp.int32)

    coef = b_s * d_scale
    intercept = jnp.where(fit_intercept, ym - jnp.dot(xm, coef), jnp.zeros((), dtype))

    # training summary stats (RegressionMetrics inputs): the residual of row i
    # is (y_i - ȳ) - (x_i - x̄)·coef about the means the sums were taken about
    rss = syc - 2.0 * jnp.dot(coef, cc) + jnp.dot(coef, Gc @ coef)
    return {**state, "coef_": coef, "intercept_": intercept, "n_iter_": n_iter,
            "rss_": jnp.maximum(rss, 0.0), "sw_": sw}


@partial(jax.jit, static_argnames=("fit_intercept", "standardize", "max_iter", "use_cd", "grid", "cd_kernel"))
def _solve_stats_jit(
    stats, *, alpha, l1_ratio, fit_intercept, standardize, use_cd, max_iter, tol, grid=False, cd_kernel=None
):
    """The solve as a program of its own; ``grid``: `alpha` and `l1_ratio`
    are [S] arrays and the solve is vmapped over them (the XLA descent)."""
    solve = lambda a, l1: _solve_from_stats(  # noqa: E731
        stats, alpha=a, l1_ratio=l1, fit_intercept=fit_intercept,
        standardize=standardize, use_cd=use_cd, max_iter=max_iter, tol=tol, cd_kernel=cd_kernel,
    )
    return jax.vmap(solve)(alpha, l1_ratio) if grid else solve(alpha, l1_ratio)


def _solve(stats, *, alpha, l1_ratio, use_cd, max_iter, tol, grid=False, **statics) -> Dict[str, jax.Array]:
    """The solve under its span, ready before the span closes: `cd` where the
    parameters take the coordinate descent (what ran is fetched with the
    sweeps: two scalars, or two [S] vectors of a grid), `normal` where they
    take the dense solve."""
    import numpy as np

    d = int(stats[3].shape[-1])
    cd_kernel = _cd_kernel_mode(stats[3]) if use_cd and not grid else None
    with telemetry.span("cd" if use_cd else "normal", d=d) as sp:
        state = _solve_stats_jit(
            stats, alpha=alpha, l1_ratio=l1_ratio, use_cd=use_cd, max_iter=int(max_iter),
            tol=tol, grid=grid, cd_kernel=cd_kernel, **statics,
        )
        if not use_cd:
            with telemetry.device_wait("normal"):
                return jax.block_until_ready(state)
        with telemetry.device_wait("cd"):
            sweeps, max_delta = (np.asarray(v) for v in jax.device_get((state["n_iter_"], state.pop("max_delta_"))))
        ran_out = bool(np.any((sweeps >= int(max_iter)) & (max_delta > tol)))
        l1 = np.asarray(alpha, np.float64) * np.asarray(l1_ratio, np.float64)
        sp.set(
            sweeps=int(sweeps.max()), stopped_by="max_iter" if ran_out else "tol",
            max_delta=float(max_delta.max()), l1=float(l1.max()),
            l2=float((np.asarray(alpha, np.float64) - l1).max()), descent=cd_kernel or "xla",
        )
        telemetry.registry().inc("linear.cd_sweeps", int(sweeps.sum()))
    return state


def _fit_from_retained_stats(
    compute_stats, dtype, *, alpha, l1_ratio, fit_intercept, standardize,
    use_cd, max_iter, tol, ckpt_key, placement_key,
) -> Dict[str, jax.Array]:
    """Linear-family fit through host-RETAINED sufficient statistics
    (docs/robustness.md "Elastic recovery"): the one distributed data pass
    lands its (d,d)-sized outputs in the active `CheckpointStore`, so a
    transient retry — and every further param set of a sequential sweep in
    the same fit stage — solves from the retained statistics WITHOUT another
    pass over the data (``checkpoint.stats_reuses``). The replicated solve
    is deterministic given the statistics, so a resumed fit is bit-identical
    to an uninterrupted one."""
    import numpy as np

    from .. import checkpoint as _ckpt
    from ..parallel import chaos

    store = _ckpt.active_store()

    def compute() -> Dict:
        return {n: np.asarray(v) for n, v in zip(_STATS_NAMES, compute_stats())}

    if store is not None:
        state = store.get_or_compute(
            ckpt_key, compute, solver="linear", placement_key=placement_key
        )
    else:
        state = compute()
    # mid-solve fault injection point: `fail:stage=solve` fires after the
    # stats were retained, so the retried attempt provably reuses them
    chaos.maybe_fail_stage("solve", 0)
    return _solve(
        tuple(jnp.asarray(state[n], dtype) for n in _STATS_NAMES),
        alpha=alpha, l1_ratio=l1_ratio, fit_intercept=fit_intercept,
        standardize=standardize, use_cd=use_cd, max_iter=max_iter, tol=tol,
    )


def stats_ckpt_key(base: str, *, fit_intercept: bool, fast: bool) -> str:
    """Statistics taken about zero (no intercept) or from bf16 operands are
    keyed apart: neither may be resumed from, or serve, the other kind."""
    return base + ("" if fit_intercept else ":nointercept") + (":bf16" if fast else "")


def linear_fit_checkpointed(
    X: jax.Array,
    y: jax.Array,
    w: jax.Array,
    *,
    alpha: float,
    l1_ratio: float,
    fit_intercept: bool = True,
    standardize: bool = True,
    use_cd: bool = False,
    max_iter: int = 1000,
    tol: float = 1e-6,
    fast: bool = False,
    mesh=None,
    ckpt_key: str = "linear_stats",
    placement_key=None,
) -> Dict[str, jax.Array]:
    """`linear_fit` with the sufficient statistics retained on host (see
    `_fit_from_retained_stats`). The statistics depend only on (X, y, w) and
    on what they are taken about — never on alpha/l1_ratio — so one retained
    pass serves a whole sequential hyperparameter sweep AND any bounded-retry
    resume (`stats_ckpt_key`)."""
    return _fit_from_retained_stats(
        lambda: _gram_pass(X, y, w, fit_intercept=fit_intercept, fast=fast, mesh=mesh), X.dtype,
        alpha=alpha, l1_ratio=l1_ratio, fit_intercept=fit_intercept,
        standardize=standardize, use_cd=use_cd, max_iter=max_iter, tol=tol,
        ckpt_key=stats_ckpt_key(ckpt_key, fit_intercept=fit_intercept, fast=fast),
        placement_key=placement_key,
    )


def linear_fit_ell_checkpointed(
    values: jax.Array,
    indices: jax.Array,
    y: jax.Array,
    w: jax.Array,
    *,
    d: int,
    alpha: float,
    l1_ratio: float,
    fit_intercept: bool = True,
    standardize: bool = True,
    use_cd: bool = False,
    max_iter: int = 1000,
    tol: float = 1e-6,
    tile: int = 8192,
    fast: bool = False,
    ckpt_key: str = "linear_stats_ell",
    placement_key=None,
) -> Dict[str, jax.Array]:
    """Sparse (padded-ELL) analog of `linear_fit_checkpointed`: the tiled
    gram accumulation is the retained pass."""
    return _fit_from_retained_stats(
        lambda: _ell_pass(values, indices, y, w, d=d, tile=tile, fit_intercept=fit_intercept, fast=fast),
        values.dtype,
        alpha=alpha, l1_ratio=l1_ratio, fit_intercept=fit_intercept,
        standardize=standardize, use_cd=use_cd, max_iter=max_iter, tol=tol,
        ckpt_key=stats_ckpt_key(ckpt_key, fit_intercept=fit_intercept, fast=fast),
        placement_key=placement_key,
    )


@jax.jit
def linear_predict(X: jax.Array, coef: jax.Array, intercept: jax.Array) -> jax.Array:
    return X @ coef + intercept
