#
# Distributed dense linear-algebra primitives shared by PCA / linear models:
# weighted mean/covariance/gram with cross-chip reduction, symmetric eigensolve,
# and eigenvector sign canonicalization.
#
# Replaces the cuML/RAFT pieces the reference calls through `PCAMG` /
# `LinearRegressionMG` (local cov gemm + NCCL allreduce + eig; see reference
# feature.py:220-241 and the JNI path rapidsml_jni.cu:109-127 `dgemmCov`,
# :215-269 `calSVD`). Design: inputs are row-sharded global arrays; the
# `einsum` contractions below hit the MXU per shard and GSPMD inserts the
# `psum` for the row (sharded) dimension — the NCCL allreduce equivalent.
#
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from .. import telemetry


def weighted_moments(X: jax.Array, w: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (total_weight, mean [d], var [d]) with padding rows zero-weighted."""
    total_w = jnp.sum(w)
    mean = jnp.einsum("n,nd->d", w, X) / total_w
    sq = jnp.einsum("n,nd->d", w, X * X) / total_w
    var = jnp.maximum(sq - mean * mean, 0.0)
    return total_w, mean, var


# Rows of one tile of the float32 centred sum below. On a v5e the MXU's
# multi-pass float32 contraction over K rows carries a SYSTEMATIC relative
# error that grows with K: one `highest` contraction over 393,216 rows reads
# every entry 2.2e-5 low (the top eigenvalues 1.5e-5 low: worse than the same
# contraction from bf16 inputs, whose one pass is unbiased and reads them
# 6e-6 off), tiles of 32,768 rows 1.4e-6 high, tiles of 8,192 rows 1e-7 (my
# chip runs, PERF.md PR 29). The tiles are added in float32 on the vector
# unit; the d x d accumulator's traffic makes the gram 2.9 % slower at
# d = 3,000 (0.2503 -> 0.2576 s), which is what float32 costs here.
GRAM_TILE_ROWS = 8192


# Columns of one panel of a tile's contraction. The matrix is symmetric, so a
# tile's sum is taken as column panels that cover the block upper triangle
# only (panel s: rows s..s+c of the result from column s on), the panels are
# what the tile loop carries and adds, and the full [d, d] is assembled once
# after the loop (`_mirror`): its strictly-lower blocks are COPIES, the
# transposes of the upper ones. Every entry kept is the contraction it always
# was (the same rows, the same MXU passes, the same float32 tile adds); the
# others are not computed. Up to one panel's width, and under `fast` at any
# (`gram_panels`), the function is the whole contraction it was.
# The width, from the statistics pass of both cells that run it at
# [393,216, 3,000] on a v5e (PCA's / the linear fits', seconds a pass; my chip
# runs, PERF.md PR 35): whole 0.2578 / 0.2645; 1,024 columns (0.667 of the
# work) 0.1702 / 0.1712; 768 (0.625) 0.1615 / 0.1627; 512 (0.584) 0.1489 /
# 0.1502; 384 (0.563) 0.1480 / 0.1467; 256 (0.542) 0.1430 / 0.1443; 128
# (0.521) 0.1363 / 0.1375. A panel's convolution runs nearer the MXU's peak
# than the whole one did (96 % at 512 columns against 88 %), so the time
# follows the work down to the narrowest width tried. What narrower panels
# cost is the program: one convolution a panel, and again for the first tile,
# each its own code on the device (2.4 MiB whole, 13.4 MiB at 512 columns,
# 20.6 MiB at 256, which the fit's peak shows: 4.434 / 4.445 / 4.452 GiB)
# and its own compile (6 / 12 / 15-19 s from an empty cache), both growing
# with d. 512 columns take 95 % of what 256 take and half the program.
GRAM_PANEL_COLS = 512


def gram_panels(d: int, fast: bool = False) -> Tuple[int, int]:
    """(panels, panel_cols) of a [d, d] gram: how `centered_gram` splits it
    (1 and d: the whole contraction)."""
    if fast or d <= GRAM_PANEL_COLS:
        return 1, d
    return -(-d // GRAM_PANEL_COLS), GRAM_PANEL_COLS


def _gram_panels(xc, wb, fast: bool, dtype):
    """One tile's ``Σ w xc xcᵀ`` from rows already centred, as the tuple of
    its upper panels: panel i is rows s..s+c of the result from column s on,
    [c, d - s] with s = i·c (one [d, d] panel where d is one panel wide)."""
    if fast:
        # weights applied at FULL precision first — a mixed-dtype einsum
        # would promote the bf16 operand straight back to f32 and defeat
        # the cast; the bf16 dot accumulates in f32 on the MXU
        xcw = xc * wb[:, None]
        return (jnp.einsum(
            "nd,ne->de", xcw.astype(jnp.bfloat16), xc.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        ).astype(dtype),)
    d = xc.shape[1]
    panels, c = gram_panels(d)
    if panels == 1:
        return (jnp.einsum("nd,n,ne->de", xc, wb, xc),)
    return tuple(jnp.einsum("nd,n,ne->de", xc[:, s : s + c], wb, xc[:, s:]) for s in range(0, d, c))


def _mirror(panels):
    """The full symmetric [d, d] from its upper panels: each panel written
    where it lies, and what hangs over its diagonal block transposed into
    the strictly-lower blocks beneath that block."""
    if len(panels) == 1:
        return panels[0]
    c, d = panels[0].shape
    full = jnp.zeros((d, d), panels[0].dtype)
    for i, p in enumerate(panels):
        s = i * c
        full = full.at[s : s + c, s:].set(p)
        full = full.at[s + c :, s : s + c].set(p[:, c:].T)
    return full


def _sum_over_row_tiles(tile, arrays, *, at_once: bool):
    """``tile(*rows)`` (an array or a tuple of arrays) summed over tiles of
    `GRAM_TILE_ROWS` rows of every array in `arrays`, in float32 on the
    vector unit; one call where the rows fit one tile, or `at_once`."""
    n, tile_rows = arrays[0].shape[0], GRAM_TILE_ROWS
    if at_once or n <= tile_rows:
        return tile(*arrays)
    whole = n // tile_rows

    def body(i, acc):
        rows = [jax.lax.dynamic_slice_in_dim(a, i * tile_rows, tile_rows, axis=0) for a in arrays]
        return jax.tree.map(jnp.add, acc, tile(*rows))

    # from the first tile's sum, not from zeros: under `shard_map` the carry is then typed as its updates are
    sums = jax.lax.fori_loop(1, whole, body, tile(*(a[:tile_rows] for a in arrays)))
    if n % tile_rows:
        sums = jax.tree.map(jnp.add, sums, tile(*(a[whole * tile_rows :] for a in arrays)))
    return sums


def centered_gram(
    X: jax.Array, w: jax.Array, mean: jax.Array, *, fast: bool = False
) -> jax.Array:
    """``Σ w_i (x_i-μ)(x_i-μ)ᵀ`` [d, d], accumulated over row tiles inside
    the caller's one program: the mean is given (first pass), the centring
    and the weighting are fused into each tile's contraction, so nothing of
    X's size is written (the peak is X plus a few tiles). Never the
    uncentred form. Wider than `GRAM_PANEL_COLS` only the block upper
    triangle is contracted and the rest mirrored after the loop. Up to
    `GRAM_TILE_ROWS` rows it is one contraction a panel, and so it is at any
    size under ``fast``: bf16 operands (weights applied at full precision
    first), one unbiased MXU pass with f32 accumulation, which tiles would
    only slow (0.0525 -> 0.0612 s)."""
    return _mirror(_sum_over_row_tiles(
        lambda xb, wb: _gram_panels(xb - mean, wb, fast, X.dtype), (X, w), at_once=fast
    ))


def centered_moments(
    X: jax.Array, y: jax.Array, w: jax.Array, x_mean: jax.Array, y_mean: jax.Array,
    *, fast: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """What a linear fit needs of (X, y) about given means, from the tiles of
    `centered_gram` (its panels, its `fast` arm): ``Σw(x-μ)(x-μ)ᵀ``
    [d, d], and in the same tiles ``Σw(x-μ)(y-ȳ)`` [d] and ``Σw(y-ȳ)²`` at
    full precision. Means of zero give the uncentred sums a fit without an
    intercept solves from."""

    def tile(xb, yb, wb):
        xc, yc = xb - x_mean, yb - y_mean
        wy = wb * yc
        return _gram_panels(xc, wb, fast, X.dtype), jnp.einsum("nd,n->d", xc, wy), jnp.sum(wy * yc)

    panels, xy, yy = _sum_over_row_tiles(tile, (X, y, w), at_once=fast)
    return _mirror(panels), xy, yy


def _over_row_shards(local, arrays, *, mesh, fast: bool):
    """``local(total, *arrays)`` where `total` adds a partial sum over the
    row shards. Where a shard of a float32 contraction has more rows than one
    tile, each device runs `local` (and its tile loop) over its own rows
    under `shard_map` and `total` is a `psum` (a tile loop over the global
    array would slice across shards); smaller shards, and callers that pass
    no mesh, leave the one contraction and its psum to GSPMD."""
    shards = 1 if mesh is None else int(mesh.devices.size)
    if shards > 1 and not fast and arrays[0].shape[0] // shards > GRAM_TILE_ROWS:
        from jax import shard_map
        from jax.sharding import PartitionSpec as P

        from ..parallel.mesh import ROWS_AXIS

        out_shapes = jax.eval_shape(partial(local, lambda v: v), *arrays)
        return shard_map(
            partial(local, lambda v: jax.lax.psum(v, ROWS_AXIS)), mesh=mesh,
            in_specs=tuple(P(ROWS_AXIS, *(None,) * (a.ndim - 1)) for a in arrays),
            out_specs=jax.tree.map(lambda _: P(), out_shapes),
        )(*arrays)
    return local(lambda v: v, *arrays)


def weighted_cov(
    X: jax.Array, w: jax.Array, ddof: int = 1, fast: bool = False, mesh=None
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Weighted covariance: returns (total_weight, mean [d], cov [d, d]).

    ``cov = Σ w_i (x_i-μ)(x_i-μ)ᵀ / (Σw - ddof)`` — matches the reference's
    sample covariance (cuML PCA divides by n-1). Two passes over X in one
    program: the mean, then the centred sum (`centered_gram`: MXU
    contractions over row tiles).

    ``mesh``: the mesh a row-sharded X lives on (`_over_row_shards`).

    ``fast`` runs the big contraction bf16-in / f32-accumulate (the
    solver_precision="bf16" contract, docs/performance.md "Mixed-precision
    solvers"): weighting and centering stay at full precision, only the
    [n,d]x[n,d] outer product is cast. Parity vs the full-precision cov is
    pinned by tests/test_precision.py.
    """

    def local(total, Xl, wl):
        total_w = total(jnp.sum(wl))
        mean = total(jnp.einsum("n,nd->d", wl, Xl)) / total_w
        return total_w, mean, total(centered_gram(Xl, wl, mean, fast=fast))

    total_w, mean, gram = _over_row_shards(local, (X, w), mesh=mesh, fast=fast)
    return total_w, mean, gram / (total_w - ddof)


def weighted_xy_moments(
    X: jax.Array, y: jax.Array, w: jax.Array, *, center: bool = True, fast: bool = False, mesh=None
):
    """The statistics of a weighted linear fit, on `weighted_cov`'s two
    passes: (Σw, x̄ [d], ȳ, Σw(x-x̄)(x-x̄)ᵀ [d, d], Σw(x-x̄)(y-ȳ) [d],
    Σw(y-ȳ)²). ``center=False`` (a fit without an intercept) is the same
    tile loop with both means given as zero, and no pass for them."""

    def local(total, Xl, yl, wl):
        total_w = total(jnp.sum(wl))
        if center:
            x_mean = total(jnp.einsum("n,nd->d", wl, Xl)) / total_w
            y_mean = total(jnp.sum(wl * yl)) / total_w
        else:
            x_mean, y_mean = jnp.zeros(Xl.shape[1:], Xl.dtype), jnp.zeros((), Xl.dtype)
        gram, xy, yy = centered_moments(Xl, yl, wl, x_mean, y_mean, fast=fast)
        return total_w, x_mean, y_mean, total(gram), total(xy), total(yy)

    return _over_row_shards(local, (X, y, w), mesh=mesh, fast=fast)


def sign_flip(components: jax.Array) -> jax.Array:
    """Canonicalize eigenvector signs: the max-|value| element of each component
    row is made positive — the exact semantics of the reference's thrust
    `signFlip` kernel (reference jvm/native/src/rapidsml_jni.cu:35-61) and of
    cuML MG PCA, so component outputs are comparable bit-for-sign."""
    idx = jnp.argmax(jnp.abs(components), axis=1)
    signs = jnp.sign(components[jnp.arange(components.shape[0]), idx])
    signs = jnp.where(signs == 0, 1.0, signs)
    return components * signs[:, None]


def topk_eigh_desc(sym: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Full symmetric eigendecomposition, top-k in descending eigenvalue order.

    Mirrors the reference JNI `calSVD` post-processing (eigDC + column/row
    reverse, rapidsml_jni.cu:215-269): LAPACK/XLA return ascending order, the
    framework contract is descending. Returns (eigvals [k], eigvecs [k, d]).
    The `full` path of `topk_eigh`: on a TPU it decomposes the whole matrix
    (at d = 3,000 that one program compiled for 268 s, PERF.md PR 29), so
    `topk_eigh` takes it only where the block iteration below cannot answer.
    """
    evals, evecs = jnp.linalg.eigh(sym)  # ascending
    evals = evals[::-1][:k]
    comps = evecs.T[::-1][:k]
    return evals, comps


# ---------------------------------------------------- top-k eigensolver -----
#
# The k largest eigenpairs of a symmetric d x d matrix without decomposing
# the whole of it: subspace iteration on an oversampled block of k + p
# columns with a Rayleigh-Ritz step every iteration. Per iteration one
# [d, d] x [d, b] product, a QR of [d, b] and a b x b eigh: at d = 3,000,
# k = 3 that is 16 columns, against the QDWH divide-and-conquer of all 3,000.

EIG_BUDGET = 48  # iterations; a spectrum the block cannot split by then takes `full`
_HIGHEST = jax.lax.Precision.HIGHEST


def subspace_block(d: int, k: int):
    """Columns of the iteration's block for the top k of d: k + p with
    p = max(k, 8), up to a multiple of 8 (the block converges at the rate
    lambda_{b+1} / lambda_k, so the oversampling is what makes near-equal
    neighbours of lambda_k cheap). None where the block is over a quarter of
    d: there it buys nothing over the full decomposition."""
    block = -(-(k + max(k, 8)) // 8) * 8
    return block if 4 * block <= d else None


def eig_tolerance(dtype) -> float:
    """A kept pair counts as converged at |C v - lambda v| <= this * lambda_1:
    16 ulp. The iteration's floor in float32 reads 1e-7 to 8e-7 (the Ritz
    values carry a few ulp of lambda_1 from the b x b projection; CPU and
    v5e alike, PERF.md PR 29), so this is twice the worst floor seen."""
    return 16.0 * float(jnp.finfo(dtype).eps)


def _ritz(sym, Q):
    """Rayleigh-Ritz on span(Q), Q orthonormal, from the one product C Q:
    Ritz values descending, Ritz vectors V, C V, and each pair's residual."""
    Y = jnp.dot(sym, Q, precision=_HIGHEST)
    T = jnp.dot(Q.T, Y, precision=_HIGHEST)
    theta, U = jnp.linalg.eigh(0.5 * (T + T.T))  # b x b, ascending
    theta, U = theta[::-1], U[:, ::-1]
    V = jnp.dot(Q, U, precision=_HIGHEST)
    CV = jnp.dot(Y, U, precision=_HIGHEST)
    return theta, V, CV, jnp.linalg.norm(CV - V * theta, axis=0)


@partial(jax.jit, static_argnames=("k", "block"))
def topk_eigh_subspace(sym: jax.Array, *, k: int, block: int):
    """Block subspace iteration from a fixed start block (no seed: the same
    matrix gives the same answer), every product at `highest` precision,
    until each kept pair's residual is within `eig_tolerance` of lambda_1 and
    a further step no longer halves it (the floor), or `EIG_BUDGET` iterations
    have run. An iteration is one product with the
    matrix: the next block is the orthonormalised C V that the last
    Rayleigh-Ritz step already holds. Returns (eigvals [k] descending,
    eigvecs [k, d], iterations run, max kept residual / lambda_1): the caller
    decides from the last whether this is the answer (`topk_eigh`)."""
    d = sym.shape[0]
    tol = eig_tolerance(sym.dtype)
    start = jax.random.normal(jax.random.PRNGKey(0), (d, block), sym.dtype)

    def step(Y):
        Q, _ = jnp.linalg.qr(Y)
        theta, V, CV, res = _ritz(sym, Q)
        scale = jnp.maximum(jnp.abs(theta[0]), jnp.finfo(sym.dtype).tiny)
        return theta, V, CV, jnp.max(res[:k]) / scale

    def cond(state):
        i, _, _, _, residual, before = state
        # on while over the tolerance, and under it for as long as a step still halves the
        # residual: the steps that take it from the tolerance down to the floor are the cheapest
        # accuracy there is (the eigenvectors' error is the residual over the gap). A residual
        # that is not a number ends the loop too.
        return (i < EIG_BUDGET) & ((residual > tol) | (residual < 0.5 * before))

    def body(state):
        i, _, _, CV, residual, _ = state
        return (i + 1, *step(CV), residual)

    first = (jnp.int32(0), *step(start), jnp.asarray(jnp.inf, sym.dtype))
    iterations, theta, V, _, residual, _ = jax.lax.while_loop(cond, body, first)
    return theta[:k], V[:, :k].T, iterations, residual


@partial(jax.jit, static_argnames=("k",))
def _topk_eigh_full(sym: jax.Array, *, k: int):
    evals, comps = topk_eigh_desc(sym, k)
    R = jnp.dot(comps, sym, precision=_HIGHEST) - comps * evals[:, None]
    scale = jnp.maximum(jnp.max(jnp.abs(evals)), jnp.finfo(sym.dtype).tiny)
    return evals, comps, jnp.max(jnp.linalg.norm(R, axis=1)) / scale


def topk_eigh(sym: jax.Array, k: int) -> Tuple[jax.Array, jax.Array, Dict[str, Any]]:
    """The k largest eigenpairs of symmetric `sym`, descending: (eigvals [k],
    eigvecs [k, d], what ran). Two programs and a choice made on the host
    from what it observes: the block iteration where `subspace_block` gives a
    block, and the full decomposition where it gives none or where the
    iteration's residual is still over `eig_tolerance` when its budget is
    spent (more near-equal eigenvalues at the top than the block has
    columns). The same answer either way, to float32's floor; the full
    program is compiled only by a fit that needs it. What ran: `eig_path`
    ("topk" / "full"), `block`, `iterations`, `residual_max` (fetched: two
    scalars)."""
    block = subspace_block(int(sym.shape[0]), int(k))
    iterations = 0
    if block is not None:
        evals, comps, iterations, residual = topk_eigh_subspace(sym, k=int(k), block=block)
        with telemetry.device_wait("eig"):
            iterations, residual = (v.item() for v in jax.device_get((iterations, residual)))  # one fetch
        # a residual that is not a number comes from a matrix that is not one: no
        # decomposition repairs it, the caller's divergence guard names it
        if residual <= eig_tolerance(sym.dtype) or math.isnan(residual):
            return evals, comps, {"eig_path": "topk", "block": block, "iterations": iterations,
                                  "residual_max": residual}
    evals, comps, residual = _topk_eigh_full(sym, k=int(k))
    with telemetry.device_wait("eig"):
        residual = float(residual)
    return evals, comps, {"eig_path": "full", "block": block or 0, "iterations": iterations,
                          "residual_max": residual}
