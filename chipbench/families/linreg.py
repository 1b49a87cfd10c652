"""LinearRegression with an elastic net: estimator builder, work counts, the
plain reference and what is compared.

What program and reference both compute (w is 1 on real rows, 0 on padding;
n = sum w): the means x_bar, y_bar; about them G = sum w (x - x_bar)(x - x_bar)^T,
c = sum w (x - x_bar)(y - y_bar), s = sum w (y - y_bar)^2; the population standard
deviation sigma_j = sqrt(G_jj / n) (Spark's `standardization=True`, the
estimator's default); on the standardized gram A = G / (n sigma sigma^T),
r = c / (n sigma), from b = 0, `maxIter` cyclic sweeps j = 0..d-1 of

    rho = r_j - (A b)_j + A_jj b_j
    b_j = sign(rho) max(|rho| - lambda alpha, 0) / (A_jj + lambda (1 - alpha))

(A b kept up to date after every coordinate; a sweep whose largest |change| is
<= tol ends the descent), coefficients b / sigma, intercept y_bar - x_bar . coef.
That descends Spark's objective 1/(2n) RSS + lambda alpha |b|_1 +
lambda (1 - alpha)/2 |b|^2 on the standardized coefficients, the intercept
never penalized (`ops/linear.py`'s header). The comparison is of the same ten
sweeps, not of two optima: with tol = 1e-30 none has converged.

The reference makes two passes over row blocks on the device in float32 at
`highest` matmul precision (the means, then the centred sums in pieces of 4,096
rows added pairwise: never the uncentred form; its own copy of what
`families/pca.py` does), adds the chips' partial sums on the host in float64,
and descends on the host in float64 numpy. It imports nothing of the program;
the check below only asks whether the program is one that can run the
configuration at all.

Departures from the protocol's two solvers, each by design of the row:
- Spark's LinearRegression takes OWL-QN on this row (elasticNetParam > 0) and
  runs it to its optimum or maxIter = 10 quasi-Newton steps; cuML's `CDMG`
  descends on the rows (a pass over X a coordinate). Program and reference
  descend on the gram (one pass over X a fit), which visits the same iterates
  as `CDMG`'s cyclic order would in exact arithmetic.
- cuML shuffles no coordinates here either (`selection="cyclic"`); neither do we.
- Spark standardizes the label too and scales lambda by its deviation; the
  reference, as the program and cuML, leaves the label as it is.
- The stop is on the largest coefficient change of a sweep (cuML's), not on
  OWL-QN's relative objective decrease.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
SEEDED = False  # the answer does not depend on the estimator seed


def _program_has_the_statistics_pass() -> None:
    """The configuration names a tiled, centred statistics pass for the linear
    fits. A program without one (the parent of PR 34: one untiled, uncentred
    contraction and a second array of X's size beside X) is not the program
    the configuration describes, and says so before anything is made or placed."""
    from spark_rapids_ml_tpu.ops import linalg

    if not hasattr(linalg, "weighted_xy_moments"):
        raise ImportError("chipbench.families.linreg: this program has no spark_rapids_ml_tpu.ops.linalg."
                          "weighted_xy_moments; the linreg-p3k configuration cannot run on it")


_program_has_the_statistics_pass()


# ------------------------------------------------------------- the program ---


def estimator(config: dict, seed: int, overrides: dict | None = None):
    """`seed` is the loop's: LinearRegression takes none."""
    from spark_rapids_ml_tpu.models.regression import LinearRegression

    est = LinearRegression(**config["estimator"], num_workers=int(config["num_workers"]), **(overrides or {}))
    return est.setFeaturesCol("features").setLabelCol("label")


def _cd_span(model) -> Dict[str, Any]:
    spans = (getattr(model, "_fit_metrics", None) or {}).get("spans", [])
    return next((s for s in spans if s["path"] == "fit/solve/cd"), {})


def outputs(model) -> Dict[str, Any]:
    cd = _cd_span(model)
    return {
        "coef": np.asarray(model.coef_, np.float64).reshape(-1),
        "intercept": float(model.intercept_),
        "n_iter": int(model.n_iter_),
        "rss": float(model.rss_),
        "sw": float(model.sw_),
        "sweeps": int(cd.get("sweeps", 0)),
        "stopped_by": cd.get("stopped_by"),
    }


def iterations(out: Dict[str, Any]) -> int:
    return out["n_iter"]


def before_fit(rehearse: bool) -> None:
    """A refit that reused retained statistics would skip the pass over X and
    time the sweeps alone: solver checkpoints have to be off."""
    from spark_rapids_ml_tpu import checkpoint

    if checkpoint.solver_checkpoints_active():
        raise RuntimeError("solver checkpoints are on: a LinearRegression refit would reuse its statistics and skip the gram")


def assert_path(model) -> None:
    """Admitted resident, one pass over X in this fit, the sweeps the
    configuration asks for, all of them (a descent that stopped early under
    tol = 1e-30 is a fault here, not a faster answer), and descended as the
    kernel where the process has kernels (the XLA loop at this width is
    another program with other numbers)."""
    metrics = getattr(model, "_fit_metrics", None) or {}
    adm = metrics.get("admission")
    if adm is None or adm.get("verdict") != "resident":
        raise RuntimeError(f"linreg fit was not admitted resident: admission={adm}")
    passes = metrics.get("counters", {}).get("linear.gram_passes", 0)
    if passes != 1:
        raise RuntimeError(f"linreg fit made {passes} passes over X (linear.gram_passes), the cell names one a fit")
    cd, want = _cd_span(model), int(model.getOrDefault("maxIter"))
    if cd.get("sweeps") != want or cd.get("stopped_by") != "max_iter":
        raise RuntimeError(f"linreg fit's fit/solve/cd span does not show {want} sweeps stopped by max_iter: {cd}")
    from spark_rapids_ml_tpu.ops import distance

    mode = distance.kernel_mode()  # "pallas" on a TPU: the sweeps run as the kernel there, at this width
    if mode != "jnp" and cd.get("descent") != mode:
        raise RuntimeError(f"linreg fit descended as {cd.get('descent')!r} where the process runs kernels as {mode!r}: {cd}")


# ------------------------------------------------------------ work counts ---


def gram_flops(config: dict) -> float:
    """The centred sum of outer products, over all chips: 2 n d^2."""
    return 2.0 * int(config["rows"]) * int(config["d"]) ** 2


def cd_bytes(config: dict, sweeps: float) -> float:
    """What the descent has to read: the standardized gram once a sweep,
    4 d^2 bytes in float32 (each coordinate reads its own row of it)."""
    return 4.0 * int(config["d"]) ** 2 * sweeps


def fit_work(config: dict, n_iter: float) -> Dict[str, float]:
    """What the algorithm needs for one fit, over all chips: the gram, X^T y,
    a multiply-add an entry of the gram a sweep; one read of float32 X (a fused
    pass would take the means and the centred sums from one read; the program
    reads X twice, as the reference does) and the gram once a sweep."""
    n, d = int(config["rows"]), int(config["d"])
    return {"flops": gram_flops(config) + 2.0 * n * d + 2.0 * d * d * n_iter,
            "bytes": 4.0 * n * d + cd_bytes(config, n_iter)}


# -------------------------------------------------------------- reference ---


@jax.jit
def _sum_block(xb):
    return jnp.sum(xb, axis=0)


PIECE_ROWS = 4096


def _pairwise(parts: list):
    while len(parts) > 1:
        parts = [a + b for a, b in zip(parts[::2], parts[1::2])] + ([parts[-1]] if len(parts) % 2 else [])
    return parts[0]


@jax.jit
def _centred_block(xb, yc, mu):
    """A block's centred sums, (x - mu)^T (x - mu) and (x - mu)^T yc, from
    pieces of 4,096 rows added pairwise in float32 (one `highest` contraction
    over K rows reads low by a share that grows with K on a v5e: PERF.md PR 29)."""
    xc = xb - mu
    cuts = range(0, xc.shape[0], PIECE_ROWS)
    gram = _pairwise([jnp.dot(xc[i : i + PIECE_ROWS].T, xc[i : i + PIECE_ROWS], precision=HIGHEST) for i in cuts])
    xy = _pairwise([jnp.dot(xc[i : i + PIECE_ROWS].T, yc[i : i + PIECE_ROWS], precision=HIGHEST) for i in cuts])
    return gram, xy


def _per_chip(blocks: Sequence[Any], fn) -> list:
    """fn(block index, block, device) summed on each chip in float32; one float64 total a chip."""
    acc: Dict[Any, Any] = {}
    for i, xb in enumerate(blocks):
        dev = list(xb.devices())[0]
        part = fn(i, xb, dev)
        acc[dev] = part if dev not in acc else jax.tree.map(jnp.add, acc[dev], part)
    return [jax.tree.map(lambda a: np.asarray(a, np.float64), v) for v in acc.values()]


def _total(parts: list):
    return jax.tree.map(lambda *a: np.sum(a, axis=0), *parts)


def statistics(blocks: Sequence[Any], y_host: np.ndarray) -> Dict[str, Any]:
    """The standardized statistics of the blocks given, in float64."""
    rows = blocks[0].shape[0]
    n = len(blocks) * rows
    y = np.asarray(y_host[:n], np.float64)
    mu = _total(_per_chip(blocks, lambda i, xb, dev: _sum_block(xb))) / n
    y_bar = float(y.mean())
    yc = y - y_bar
    placed: Dict[Any, Any] = {}

    def centred(i, xb, dev):
        if dev not in placed:
            placed[dev] = jax.device_put(mu.astype(np.float32), dev)
        return _centred_block(xb, jax.device_put(yc[i * rows : (i + 1) * rows].astype(np.float32), dev), placed[dev])

    G, c = _total(_per_chip(blocks, centred))
    G = 0.5 * (G + G.T)
    sigma = np.sqrt(np.maximum(np.diag(G) / n, 0.0))
    scale = np.where(sigma > 0, 1.0 / np.maximum(sigma, 1e-300), 0.0)
    return {"n": n, "mu": mu, "y_bar": y_bar, "sigma": sigma, "scale": scale,
            "A": G * scale[:, None] * scale[None, :] / n, "r": c * scale / n, "s": float(yc @ yc) / n}


def descend(A: np.ndarray, r: np.ndarray, l1: float, l2: float, sweeps: int, tol: float):
    """Cyclic coordinate descent from zero in float64: (b, sweeps run)."""
    d = len(r)
    b, q = np.zeros(d), np.zeros(d)
    diag = np.diag(A).copy()
    denom = np.maximum(diag + l2, 1e-30)
    ran = 0
    for _ in range(sweeps):
        biggest = 0.0
        for j in range(d):
            rho = r[j] - q[j] + diag[j] * b[j]
            bj = np.sign(rho) * max(abs(rho) - l1, 0.0) / denom[j]
            delta = bj - b[j]
            if delta != 0.0:
                q += A[j] * delta
                b[j] = bj
            biggest = max(biggest, abs(delta))
        ran += 1
        if biggest <= tol:
            break
    return b, ran


def penalties(config: dict):
    est = config["estimator"]
    lam, alpha = float(est["regParam"]), float(est["elasticNetParam"])
    return lam * alpha, lam * (1.0 - alpha)


def mean_square_residual(st: Dict[str, Any], coef: np.ndarray, intercept: float | None = None) -> float:
    """RSS / n of a model on the reference's statistics, in float64; with no
    intercept given, the best one for these coefficients."""
    b = coef * st["sigma"]
    ms = st["s"] - 2.0 * float(b @ st["r"]) + float(b @ (st["A"] @ b))
    if intercept is not None:
        ms += (intercept - (st["y_bar"] - float(st["mu"] @ coef))) ** 2
    return max(ms, 0.0)


def objective(config: dict, st: Dict[str, Any], coef: np.ndarray) -> float:
    """Spark's objective at these coefficients, on the reference's statistics."""
    l1, l2 = penalties(config)
    b = coef * st["sigma"]
    return 0.5 * mean_square_residual(st, coef) + l1 * float(np.abs(b).sum()) + 0.5 * l2 * float(b @ b)


def as_outputs(st: Dict[str, Any], b: np.ndarray, n_iter: int) -> Dict[str, Any]:
    """Standardized coefficients in the shape of `outputs`."""
    coef = b * st["scale"]
    intercept = st["y_bar"] - float(st["mu"] @ coef)
    return {"coef": coef, "intercept": intercept, "n_iter": int(n_iter), "sweeps": int(n_iter), "stopped_by": "reference",
            "rss": st["n"] * mean_square_residual(st, coef, intercept), "sw": float(st["n"])}


def fit_from(config: dict, st: Dict[str, Any], sweeps: int | None = None) -> Dict[str, Any]:
    """The descent on given statistics: `maxIter` sweeps, or as many as asked."""
    est = config["estimator"]
    l1, l2 = penalties(config)
    b, ran = descend(st["A"], st["r"], l1, l2, int(est["maxIter"]) if sweeps is None else sweeps, float(est["tol"]))
    ref = as_outputs(st, b, ran)
    return {**ref, "stats": st, "objective": objective(config, st, ref["coef"])}


def reference_fit(config: dict, data, blocks: Sequence[Any], seed: int = 0, sweeps: int | None = None) -> Dict[str, Any]:
    # the blocks given: all of them, or a prefix (a planted fault)
    return fit_from(config, statistics(blocks, data.y), sweeps)


def control_fit(run, blocks: Sequence[Any], seed: int) -> Dict[str, Any]:
    """The program has a lower precision of its own (the gram from bfloat16
    inputs): the program with that path switched on is the control."""
    from spark_rapids_ml_tpu import core

    saved = {k: core.config[k] for k in run.config["control_program_config"]}
    core.config.update(run.config["control_program_config"])
    try:
        return outputs(estimator(run.config, seed).fit(run.data.frame))
    finally:
        core.config.update(saved)


def fault_fits(config: dict, data, blocks: Sequence[Any], seed: int, chips: int) -> Dict[str, Dict[str, Any]]:
    """The faults a fit can have, planted in the reference put in the
    program's place (for reading a fault at the cell's own size)."""
    sweeps = int(config["estimator"]["maxIter"])
    st = statistics(blocks, data.y)
    faults = {
        # the descent returned its start: zero coefficients, the intercept y_bar
        "state_unchanged": as_outputs(st, np.zeros(blocks[0].shape[1]), sweeps),
        "half_left_out": reference_fit(config, data, blocks[: len(blocks) // 2]),
        # a sweep left out, the count reported as asked: the other numbers have to catch it
        "a_sweep_left_out": {**fit_from(config, st, sweeps - 1), "n_iter": sweeps, "sweeps": sweeps},
    }
    if chips > 1:  # each chip keeps its own sums: the answer is chip 0's
        faults["exchange_left_out"] = reference_fit(config, data, blocks[: len(blocks) // chips])
    return faults


NUMBERS = ("coef_gap", "intercept_gap", "objective_gap", "rmse_gap", "sweeps_gap")


def compare_fit(config: dict, out: Dict[str, Any], ref: Dict[str, Any], data, blocks: Sequence[Any]) -> Dict[str, float]:
    """The numbers a fit is judged by (each is 0 for a perfect answer; an
    answer that is not a number makes every one NaN, which no limit admits)."""
    answer = (out["coef"], out["intercept"], out["rss"], out["sw"])
    if not all(np.isfinite(a).all() for a in answer) or out["sw"] <= 0:
        return {name: float("nan") for name in NUMBERS}
    st, sweeps = ref["stats"], int(config["estimator"]["maxIter"])
    rmse_ref = np.sqrt(mean_square_residual(st, ref["coef"], ref["intercept"]))
    rmse_said = np.sqrt(max(out["rss"], 0.0) / out["sw"])  # the training summary's
    rmse_has = np.sqrt(mean_square_residual(st, out["coef"], out["intercept"]))  # the model's, on the reference's statistics
    return {
        "coef_gap": float(np.max(np.abs(out["coef"] - ref["coef"])) / np.max(np.abs(ref["coef"]))),
        "intercept_gap": abs(out["intercept"] - ref["intercept"]) / max(abs(ref["intercept"]), 1e-30),
        # the objective in float64 on the reference's statistics at the answer's coefficients
        "objective_gap": abs(objective(config, st, out["coef"]) - ref["objective"]) / ref["objective"],
        # the protocol's quality score, as the summary states it and as the coefficients give it
        "rmse_gap": float(max(abs(rmse_said - rmse_ref), abs(rmse_has - rmse_ref)) / rmse_ref),
        "sweeps_gap": float(max(abs(out["n_iter"] - sweeps), abs(out["sweeps"] - sweeps))),  # exact
    }
