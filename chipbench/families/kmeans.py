"""KMeans: how to build the estimator from a configuration, the work a fit
needs, the plain reference, and what is compared.

The reference is Lloyd's algorithm as the configuration states it (k distinct
rows drawn by numpy's generator from the estimator's seed, `maxIter`
iterations, an empty cluster keeps its centre), in `jax.numpy` at float32 and
`highest` matmul precision, over row blocks that the benchmark makes itself.
It imports nothing of the program.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..checks import worse

HIGHEST = jax.lax.Precision.HIGHEST
SEEDED = True  # the answer depends on the estimator seed (the random init)


# ------------------------------------------------------------- the program ---


def estimator(config: dict, seed: int, overrides: dict | None = None):
    from spark_rapids_ml_tpu.models.clustering import KMeans

    est = KMeans(**config["estimator"], seed=int(seed), num_workers=int(config["num_workers"]), **(overrides or {}))
    return est.setFeaturesCol("features")


def outputs(model) -> Dict[str, Any]:
    return {
        "centers": np.asarray(model.cluster_centers_, np.float32),
        "inertia": float(model.inertia_),
        "n_iter": int(model.n_iter_),
    }


def iterations(out: Dict[str, Any]) -> int:
    return out["n_iter"]


def before_fit(rehearse: bool) -> None:
    """The cell names the Pallas kernels. Resolved here, outside any trace, as
    `chip_smoke.py` does: with the autotuner off nothing else asks before the
    first jitted Lloyd tile, and the kernel self-test cannot run under a trace."""
    from spark_rapids_ml_tpu.ops import distance

    want = "interpret" if rehearse else "pallas"
    if distance.kernel_mode() != want:
        raise RuntimeError(f"distance core runs {distance.kernel_mode()!r}, the cell names {want!r}")


def assert_path(model) -> None:
    """The fit ran the path the cell names: admitted resident, not streamed."""
    adm = (getattr(model, "_fit_metrics", None) or {}).get("admission")
    if adm is None or adm.get("verdict") != "resident":
        raise RuntimeError(f"kmeans fit was not admitted resident: admission={adm}")


# ------------------------------------------------------------ work counts ---


def fit_work(config: dict, n_iter: float) -> Dict[str, float]:
    """What the algorithm needs for one fit, over all chips: the assignment's
    n*k*d multiply-adds for each Lloyd iteration and for the final inertia
    pass, and one read of X for each. The update's one-hot matmul is one
    implementation's choice and is not counted."""
    n, d, k = int(config["rows"]), int(config["d"]), int(config["estimator"]["k"])
    passes = n_iter + 1
    return {"flops": 2.0 * n * k * d * passes, "bytes": 4.0 * n * d * passes}


def assign_flops(config: dict) -> float:
    """One Lloyd iteration's assignment, over all chips."""
    return 2.0 * int(config["rows"]) * int(config["estimator"]["k"]) * int(config["d"])


# -------------------------------------------------------------- reference ---


def _round(a, dtype):
    return a if dtype is None else a.astype(dtype).astype(jnp.float32)


@partial(jax.jit, static_argnames=("dtype",))
def _d2(xb, centers, dtype=None):
    """Squared distances of a row block to every centre; `dtype` rounds the
    contraction's inputs (the lower-precision control), None is float32."""
    cross = jnp.dot(_round(xb, dtype), _round(centers, dtype).T, precision=HIGHEST)
    return jnp.sum(xb * xb, axis=1)[:, None] - 2.0 * cross + jnp.sum(centers * centers, axis=1)[None, :]


@partial(jax.jit, static_argnames=("dtype",))
def _block_step(xb, centers, dtype=None):
    d2 = _d2(xb, centers, dtype)
    best = jnp.argmin(d2, axis=1)
    onehot = jax.nn.one_hot(best, centers.shape[0], dtype=jnp.float32)
    sums = jnp.einsum("nk,nd->kd", onehot, _round(xb, dtype), precision=HIGHEST)
    return sums, jnp.sum(onehot, axis=0), jnp.sum(jnp.min(d2, axis=1))


@jax.jit
def _regret(xb, centers, pred):
    d2 = _d2(xb, centers)
    return jnp.take_along_axis(d2, pred[:, None], axis=1)[:, 0] - jnp.min(d2, axis=1), jnp.min(d2, axis=1)


def _sweep(blocks: Sequence[Any], centers: np.ndarray, dtype):
    """One pass over all blocks: each chip sums the blocks it holds, the host
    adds the chips' sums in float64."""
    per_chip: Dict[Any, Any] = {}
    for xb in blocks:
        dev = list(xb.devices())[0]
        if dev not in per_chip:
            per_chip[dev] = [jax.device_put(centers, dev), None]
        c, acc = per_chip[dev]
        part = _block_step(xb, c, dtype)
        per_chip[dev][1] = part if acc is None else jax.tree.map(jnp.add, acc, part)
    parts = [acc for _, acc in per_chip.values()]
    sums = np.sum([np.asarray(p[0], np.float64) for p in parts], axis=0)
    counts = np.sum([np.asarray(p[1], np.float64) for p in parts], axis=0)
    return sums, counts, float(np.sum([np.asarray(p[2], np.float64) for p in parts]))


def init_rows(rows: int, k: int, seed: int) -> np.ndarray:
    """initMode='random' as stated: k distinct rows by numpy's generator."""
    return np.random.default_rng(int(seed)).choice(rows, k, replace=False)


def reference_fit(config: dict, data, blocks: Sequence[Any], seed: int, lower: bool = False) -> Dict[str, Any]:
    """Lloyd from the stated init. `lower` is the control: the in-loop
    contractions (distances and the centre sums) from fp8 (e4m3) inputs where
    the configuration states bfloat16, the final inertia pass from bfloat16
    inputs where it states float32."""
    est = config["estimator"]
    centers = np.asarray(data.X[init_rows(data.rows, int(est["k"]), seed)], np.float32)
    for _ in range(int(est["maxIter"])):
        sums, counts, _ = _sweep(blocks, centers, jnp.float8_e4m3fn if lower else None)
        mean = (sums / np.maximum(counts, 1.0)[:, None]).astype(np.float32)
        centers = np.where(counts[:, None] > 0, mean, centers)
    _, _, inertia = _sweep(blocks, centers, jnp.bfloat16 if lower else None)
    return {"centers": centers, "inertia": inertia, "n_iter": int(est["maxIter"])}


def control_fit(run, blocks: Sequence[Any], seed: int) -> Dict[str, Any]:
    """The program has no precision below the stated one: the reference in
    lower precision stands in the program's place."""
    return reference_fit(run.config, run.data, blocks, seed, lower=True)


def fault_fits(config: dict, data, blocks: Sequence[Any], seed: int, chips: int) -> Dict[str, Dict[str, Any]]:
    """The faults a fit can have, planted in the reference put in the
    program's place (for reading a fault at the cell's own size)."""
    est = config["estimator"]
    init = np.asarray(data.X[init_rows(data.rows, int(est["k"]), seed)], np.float32)
    faults = {
        "state_unchanged": {"centers": init, "inertia": _sweep(blocks, init, None)[2], "n_iter": int(est["maxIter"])},
        "half_left_out": reference_fit(config, data, blocks[: len(blocks) // 2], seed),
    }
    if chips > 1:  # each chip keeps its own sums: the answer is chip 0's
        faults["exchange_left_out"] = reference_fit(config, data, blocks[: len(blocks) // chips], seed)
    return faults


def compare_fit(config: dict, out: Dict[str, Any], ref: Dict[str, Any], data, blocks: Sequence[Any]) -> Dict[str, float]:
    """The numbers a fit is judged by (each is 0 for a perfect answer; a centre
    that is not a number makes every gap NaN, which no limit admits)."""
    _, _, own = _sweep(blocks, out["centers"], None)  # the reference's inertia at the answer's centres
    gap = np.linalg.norm(out["centers"] - ref["centers"], axis=1) / np.linalg.norm(ref["centers"], axis=1)
    finite = out["centers"][np.isfinite(out["centers"]).all(axis=1)]
    return {
        "n_iter_gap": float(abs(out["n_iter"] - int(config["estimator"]["maxIter"]))),
        "inertia_self_gap": abs(out["inertia"] - own) / own,
        "inertia_gap": abs(own - ref["inertia"]) / ref["inertia"],
        "centers_median_gap": float(np.median(gap)),
        "centers_moved_share": float(np.mean(~(gap <= 1e-3))),
        "centers_worst_gap": float(np.max(gap)) if np.isfinite(gap).all() else float("nan"),
        "centers_degenerate": float(len(out["centers"]) - len(np.unique(finite, axis=0))),
    }


def compare_transform(centers: np.ndarray, calls: List[dict], blocks: Dict[int, Any], block_rows: int,
                      lower: bool = False) -> Dict[str, float]:
    """Every call's predictions against the reference's distances: by how
    much the chosen centre is farther than the nearest, over the typical
    squared distance. `lower` puts a bfloat16 argmin in the program's place."""
    worst, total, rows, scale = 0.0, 0.0, 0, []
    for call in calls:
        for j, b in enumerate(range(call["lo"] // block_rows, call["hi"] // block_rows)):
            xb = blocks[b]
            c = jax.device_put(centers, list(xb.devices())[0])
            pred = call["prediction"][j * block_rows : (j + 1) * block_rows]
            if lower:
                pred = jnp.argmin(_d2(xb, c, jnp.bfloat16), axis=1)
            regret, nearest = _regret(xb, c, jnp.asarray(pred, jnp.int32))
            regret = np.asarray(regret, np.float64)
            worst, total, rows = worse(worst, float(regret.max())), total + float(regret.sum()), rows + regret.size
            scale.append(float(np.mean(np.asarray(nearest))))
    typical = float(np.mean(scale))
    return {"regret_max": worst / typical, "regret_mean": total / rows / typical}
