"""Binomial LogisticRegression: estimator builder, work counts, the plain
reference and what is compared.

The objective is Spark's: mean log-loss plus regParam/2 * |B|^2 over the
coefficients of the standardized features (unbiased variance), the intercept
unpenalized. The reference minimizes it by Newton's method (IRLS), which owes
nothing to the program's L-BFGS: row blocks on the device in float32 at
`highest` matmul precision, sums and the (d+1)^2 solve on the host in float64.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
NEWTON_STEPS = 14
SEEDED = False  # the optimum does not depend on the estimator seed


# ------------------------------------------------------------- the program ---


def estimator(config: dict, seed: int, overrides: dict | None = None):
    from spark_rapids_ml_tpu.models.classification import LogisticRegression

    est = LogisticRegression(**config["estimator"], num_workers=int(config["num_workers"]), **(overrides or {}))
    return est.setFeaturesCol("features").setLabelCol("label")


def outputs(model) -> Dict[str, Any]:
    return {
        "coef": np.asarray(model.coef_, np.float64).reshape(-1),
        "intercept": float(np.asarray(model.intercept_).reshape(-1)[0]),
        "objective": float(model.objective_),
        "n_iter": int(model.n_iter_),
    }


def iterations(out: Dict[str, Any]) -> int:
    return out["n_iter"]


def before_fit(rehearse: bool) -> None:
    """Nothing to resolve: the GLM path has no kernel of its own."""


def assert_path(model) -> None:
    adm = (getattr(model, "_fit_metrics", None) or {}).get("admission")
    if adm is None or adm.get("verdict") != "resident":
        raise RuntimeError(f"logreg fit was not admitted resident: admission={adm}")


# ------------------------------------------------------------ work counts ---


def fit_work(config: dict, n_iter: float) -> Dict[str, float]:
    """What the algorithm needs for one fit, over all chips: each iteration
    one forward X.b and one gradient X^T.r (2*n*d multiply-adds each) and one
    read of float32 X, the floor a fused forward+gradient pass could reach."""
    n, d = int(config["rows"]), int(config["d"])
    return {"flops": 4.0 * n * d * n_iter, "bytes": 4.0 * n * d * n_iter}


# -------------------------------------------------------------- reference ---


@jax.jit
def _sums(xb, shift):
    c = xb - shift
    return jnp.sum(c, axis=0), jnp.sum(c * c, axis=0)


@jax.jit
def _newton_block(xb, yb, mu, scale, B, b0):
    xs = (xb - mu) * scale
    z = jnp.dot(xs, B, precision=HIGHEST) + b0
    p = jax.nn.sigmoid(z)
    w = p * (1.0 - p)
    loss = jnp.sum(jax.nn.softplus(z) - yb * z)
    g = jnp.concatenate([jnp.dot(xs.T, p - yb, precision=HIGHEST), jnp.sum(p - yb)[None]])
    hb = jnp.dot(xs.T, w, precision=HIGHEST)
    H = jnp.dot(xs.T * w[None, :], xs, precision=HIGHEST)
    H = jnp.block([[H, hb[:, None]], [hb[None, :], jnp.sum(w)[None, None]]])
    return loss, g, H


@jax.jit
def _loss_block(xb, yb, coef, b):
    z = jnp.dot(xb, coef, precision=HIGHEST) + b
    return jnp.sum(jax.nn.softplus(z) - yb * z)


def _put(a, dev):
    return jax.device_put(np.asarray(a, np.float32), dev)


def _per_chip(blocks: Sequence[Any], fn) -> list:
    """fn(block index, block, device) summed on each chip; one total a chip."""
    acc: Dict[Any, Any] = {}
    for i, xb in enumerate(blocks):
        dev = list(xb.devices())[0]
        part = fn(i, xb, dev)
        acc[dev] = part if dev not in acc else jax.tree.map(jnp.add, acc[dev], part)
    return [jax.tree.map(lambda a: np.asarray(a, np.float64), v) for v in acc.values()]


def _total(parts: list):
    return jax.tree.map(lambda *a: np.sum(a, axis=0), *parts)


def _moments(blocks: Sequence[Any], n: int):
    d = blocks[0].shape[1]
    s, _ = _total(_per_chip(blocks, lambda i, xb, dev: _sums(xb, _put(np.zeros(d), dev))))
    mu = s / n
    s1, s2 = _total(_per_chip(blocks, lambda i, xb, dev: _sums(xb, _put(mu, dev))))
    mu = mu + s1 / n
    var = (s2 - s1 * s1 / n) / (n - 1)  # unbiased, as Spark's summarizer
    return mu, np.sqrt(var)


def reference_fit(config: dict, data, blocks: Sequence[Any], seed: int = 0) -> Dict[str, Any]:
    d, rows = blocks[0].shape[1], blocks[0].shape[0]
    n = len(blocks) * rows  # the blocks given: all of them, or a prefix (a planted fault)
    y_host = data.y[:n]
    lam = float(config["estimator"]["regParam"])
    mu, sigma = _moments(blocks, n)
    scale = np.where(sigma > 0, 1.0 / np.maximum(sigma, 1e-30), 0.0)
    pen = np.concatenate([np.full(d, lam), [0.0]])

    def evaluate(theta):
        loss, g, H = _total(_per_chip(blocks, lambda i, xb, dev: _newton_block(
            xb, _put(y_host[i * rows : (i + 1) * rows], dev), _put(mu, dev), _put(scale, dev),
            _put(theta[:d], dev), _put(theta[d], dev))))
        f = loss / n + 0.5 * float(np.sum(pen * theta * theta))
        return f, g / n + pen * theta, H / n + np.diag(pen)

    theta = np.zeros(d + 1)
    f, g, H = evaluate(theta)
    for _ in range(NEWTON_STEPS):
        step = np.linalg.solve(H, g)
        if float(g @ step) < 1e-13 * max(f, 1e-30):  # Newton decrement: converged to float32's floor
            break
        t = 1.0
        while True:
            f_new, g_new, H_new = evaluate(theta - t * step)
            if f_new <= f or t < 1e-3:
                break
            t *= 0.5
        theta, f, g, H = theta - t * step, f_new, g_new, H_new
    coef = theta[:d] * scale
    return {"coef": coef, "intercept": float(theta[d] - coef @ mu), "objective": float(f),
            "sigma": sigma}


def objective_at(config: dict, out: Dict[str, Any], sigma: np.ndarray, y_host: np.ndarray,
                 blocks: Sequence[Any]) -> float:
    """The reference's objective at an answer's coefficients."""
    rows = blocks[0].shape[0]
    loss = _total(_per_chip(blocks, lambda i, xb, dev: _loss_block(
        xb, _put(y_host[i * rows : (i + 1) * rows], dev), _put(out["coef"], dev), _put(out["intercept"], dev))))
    return float(loss) / y_host.shape[0] + 0.5 * float(config["estimator"]["regParam"]) * float(
        np.sum((out["coef"] * sigma) ** 2))


def control_fit(run, blocks: Sequence[Any], seed: int) -> Dict[str, Any]:
    """The program has a lower precision of its own (bfloat16 matvecs): the
    program with that path switched on is the control."""
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
    d = blocks[0].shape[1]
    faults = {
        "state_unchanged": {"coef": np.zeros(d), "intercept": 0.0, "objective": float(np.log(2.0)), "n_iter": 0},
        "half_left_out": reference_fit(config, data, blocks[: len(blocks) // 2]),
    }
    if chips > 1:
        faults["exchange_left_out"] = reference_fit(config, data, blocks[: len(blocks) // chips])
    return faults


def compare_fit(config: dict, out: Dict[str, Any], ref: Dict[str, Any], data, blocks: Sequence[Any]) -> Dict[str, float]:
    """The numbers a fit is judged by (each is 0 for a perfect answer)."""
    y_host = data.y
    own = objective_at(config, out, ref["sigma"], y_host, blocks)
    best = ref["objective"]
    diff = (out["coef"] - ref["coef"]) * ref["sigma"]
    return {
        "objective_self_gap": abs(out["objective"] - own) / own,
        "objective_gap": abs(own - best) / best,
        "coef_gap": float(np.linalg.norm(diff) / np.linalg.norm(ref["coef"] * ref["sigma"])),
        "intercept_gap": abs(out["intercept"] - ref["intercept"]) / max(abs(ref["intercept"]), 1.0),
    }
