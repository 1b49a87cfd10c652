"""PCA: estimator builder, work counts, the plain reference and what is compared.

What program and reference both compute (w is 1 on real rows, 0 on padding):
mu = sum w x / sum w; C = sum w (x - mu)(x - mu)^T / (sum w - 1); the k largest
eigenpairs of C, descending, each vector of unit norm with its largest-magnitude
entry positive; explained_variance_ = lambda, explained_variance_ratio_ =
lambda / tr C, singular_values_ = sqrt(lambda (sum w - 1)), mean_ = mu.

The reference makes two passes over row blocks on the device in float32 at
`highest` matmul precision (the mean, then the centred sum in pieces of 4,096
rows: never the uncentred form), adds the chips' partial sums on the host in
float64, and decomposes the whole of C with `numpy.linalg.eigh` in float64. It
imports nothing of the program; the check below only asks whether the program
is one that can run the configuration at all.
"""
from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
SEEDED = False  # the answer does not depend on the estimator seed


def _program_has_the_eigensolver() -> None:
    """The configuration names a top-k eigensolver. A program without one (the
    parent of PR 29: its whole-matrix `eigh` compiles for 268 s at d = 3,000,
    past any run's time limit) cannot run it, and says so before anything is
    made or placed."""
    from spark_rapids_ml_tpu.ops import linalg

    if not hasattr(linalg, "topk_eigh"):
        raise ImportError("chipbench.families.pca: this program has no spark_rapids_ml_tpu.ops.linalg.topk_eigh; "
                          "the pca-p3k configuration cannot run on it")


_program_has_the_eigensolver()


# ------------------------------------------------------------- the program ---


def estimator(config: dict, seed: int, overrides: dict | None = None):
    """`seed` is the loop's: PCA takes none."""
    from spark_rapids_ml_tpu.models.feature import PCA

    est = PCA(**config["estimator"], num_workers=int(config["num_workers"]), **(overrides or {}))
    return est.setInputCol("features")


def _eig_span(model) -> Dict[str, Any]:
    spans = (getattr(model, "_fit_metrics", None) or {}).get("spans", [])
    return next((s for s in spans if s["path"] == "fit/solve/eig"), {})


def outputs(model) -> Dict[str, Any]:
    eig = _eig_span(model)
    return {
        "mean": np.asarray(model.mean_, np.float64),
        "components": np.asarray(model.components_, np.float64),
        "explained_variance": np.asarray(model.explained_variance_, np.float64),
        "explained_variance_ratio": np.asarray(model.explained_variance_ratio_, np.float64),
        "singular_values": np.asarray(model.singular_values_, np.float64),
        "eig_path": eig.get("eig_path"),
        "block": int(eig.get("block", 0)),
        "eig_iterations": int(eig.get("iterations", 0)),
    }


def iterations(out: Dict[str, Any]) -> int:
    return out["eig_iterations"]


def before_fit(rehearse: bool) -> None:
    """A refit that reused retained statistics would skip the pass over X and
    time nothing: solver checkpoints have to be off."""
    from spark_rapids_ml_tpu import checkpoint

    if checkpoint.solver_checkpoints_active():
        raise RuntimeError("solver checkpoints are on: a PCA refit would reuse its statistics and skip the gram")


def assert_path(model) -> None:
    """Admitted resident, one pass over X in this fit, and the eigensolver
    that the shape calls for (an iteration that ran out of its budget and took
    the full decomposition is a fault here, not a slower answer)."""
    from spark_rapids_ml_tpu.ops import linalg

    metrics = getattr(model, "_fit_metrics", None) or {}
    adm = metrics.get("admission")
    if adm is None or adm.get("verdict") != "resident":
        raise RuntimeError(f"pca fit was not admitted resident: admission={adm}")
    passes = metrics.get("counters", {}).get("pca.gram_passes", 0)
    if passes != 1:
        raise RuntimeError(f"pca fit made {passes} passes over X (pca.gram_passes), the cell names one a fit")
    k, d = model.components_.shape
    want = "topk" if linalg.subspace_block(d, k) is not None else "full"
    eig = _eig_span(model)
    if eig.get("eig_path") != want:
        raise RuntimeError(f"pca eigensolve took {eig.get('eig_path')!r}, the shape d={d}, k={k} calls for {want!r}: {eig}")


# ------------------------------------------------------------ work counts ---


def gram_flops(config: dict) -> float:
    """The centred sum of outer products, over all chips: 2 n d^2."""
    return 2.0 * int(config["rows"]) * int(config["d"]) ** 2


def eig_flops(config: dict, n_iter: float, block: int = 16) -> float:
    """The eigensolve as the block iteration needs it: one [d, d] x [d, block]
    product for the start block and one an iteration (the small block-sized
    products are not counted; `block` is 16 for k = 3)."""
    return 2.0 * int(config["d"]) ** 2 * block * (n_iter + 1)


def fit_work(config: dict, n_iter: float) -> Dict[str, float]:
    """What the algorithm needs for one fit, over all chips: the gram and the
    eigensolve's products, and one read of float32 X (a fused pass would take
    the mean and the centred sum from one read with a rank-one correction; the
    program reads X twice, as the reference does)."""
    n, d = int(config["rows"]), int(config["d"])
    return {"flops": gram_flops(config) + eig_flops(config, n_iter), "bytes": 4.0 * n * d}


# -------------------------------------------------------------- reference ---


@jax.jit
def _sum_block(xb):
    return jnp.sum(xb, axis=0)


PIECE_ROWS = 4096


@jax.jit
def _centred_block(xb, mu):
    """A block's centred sum of outer products, from pieces of 4,096 rows added
    pairwise in float32: one `highest` contraction over K rows reads low by a
    share that grows with K on a v5e (32,768 rows at once: the top eigenvalues
    1e-7 off and single entries 2e-6; in these pieces 3e-10 and 1e-7, against
    float64 on the host: my chip run, PR 29)."""
    xc = xb - mu
    parts = [jnp.dot(xc[i : i + PIECE_ROWS].T, xc[i : i + PIECE_ROWS], precision=HIGHEST)
             for i in range(0, xc.shape[0], PIECE_ROWS)]
    while len(parts) > 1:
        parts = [a + b for a, b in zip(parts[::2], parts[1::2])] + ([parts[-1]] if len(parts) % 2 else [])
    return parts[0]


def _per_chip(blocks: Sequence[Any], fn) -> list:
    """fn(block, device) summed on each chip in float32; one float64 total a chip."""
    acc: Dict[Any, Any] = {}
    for xb in blocks:
        dev = list(xb.devices())[0]
        part = fn(xb, dev)
        acc[dev] = part if dev not in acc else acc[dev] + part
    return [np.asarray(v, np.float64) for v in acc.values()]


def as_outputs(mean, evals, comps, trace: float, n: int) -> Dict[str, Any]:
    """A decomposition in the shape of `outputs`, the sign convention applied."""
    comps = np.asarray(comps, np.float64)
    lead = comps[np.arange(len(comps)), np.argmax(np.abs(comps), axis=1)]
    comps = comps * np.where(lead < 0, -1.0, 1.0)[:, None]
    evals = np.maximum(np.asarray(evals, np.float64), 0.0)
    return {"mean": np.asarray(mean, np.float64), "components": comps, "explained_variance": evals,
            "explained_variance_ratio": evals / trace if trace > 0 else np.zeros_like(evals),
            "singular_values": np.sqrt(evals * (n - 1)), "eig_path": "reference", "block": 0, "eig_iterations": 0}


def reference_fit(config: dict, data, blocks: Sequence[Any], seed: int = 0) -> Dict[str, Any]:
    k = int(config["estimator"]["k"])
    n = len(blocks) * blocks[0].shape[0]  # the blocks given: all of them, or a prefix (a planted fault)
    mu = np.sum(_per_chip(blocks, lambda xb, dev: _sum_block(xb)), axis=0) / n
    placed: Dict[Any, Any] = {}

    def centred(xb, dev):
        if dev not in placed:
            placed[dev] = jax.device_put(mu.astype(np.float32), dev)
        return _centred_block(xb, placed[dev])

    cov = np.sum(_per_chip(blocks, centred), axis=0) / (n - 1)
    cov = 0.5 * (cov + cov.T)
    evals, evecs = np.linalg.eigh(cov)  # float64, ascending
    trace = float(np.trace(cov))
    ref = as_outputs(mu, evals[::-1][:k], evecs[:, ::-1][:, :k].T, trace, n)
    return {**ref, "cov": cov, "lambda_1": float(evals[-1]), "trace": trace, "n": n}


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
    k, d = int(config["estimator"]["k"]), blocks[0].shape[1]
    faults = {
        # the eigensolver returned its start block (any fixed one: here the first k axes) and no variance
        "state_unchanged": as_outputs(np.zeros(d), np.zeros(k), np.eye(k, d), 1.0, data.rows),
        "half_left_out": reference_fit(config, data, blocks[: len(blocks) // 2]),
    }
    if chips > 1:  # each chip keeps its own sums: the answer is chip 0's
        faults["exchange_left_out"] = reference_fit(config, data, blocks[: len(blocks) // chips])
    return faults


NUMBERS = ("subspace_gap", "variance_gap", "ratio_gap", "mean_gap", "orthonormality", "residual", "sign_gap")


def compare_fit(config: dict, out: Dict[str, Any], ref: Dict[str, Any], data, blocks: Sequence[Any]) -> Dict[str, float]:
    """The numbers a fit is judged by (each is 0 for a perfect answer; an
    answer that is not a number makes every one NaN, which no limit admits)."""
    V, lam = out["components"], out["explained_variance"]
    answer = (V, lam, out["explained_variance_ratio"], out["singular_values"], out["mean"])
    if not all(np.isfinite(a).all() for a in answer):
        return {name: float("nan") for name in NUMBERS}
    R, n = ref["components"], ref["n"]
    rel = lambda a, b: float(np.max(np.abs(a - b) / np.abs(b)))
    outside = V.T - R.T @ (R @ V.T)  # what of the answer's span lies outside the reference's
    lead = V[np.arange(len(V)), np.argmax(np.abs(V), axis=1)]
    return {
        # sine of the largest principal angle between the two spans
        "subspace_gap": float(np.linalg.norm(outside, 2) / np.linalg.norm(V, 2)),
        # lambda, and lambda again as the singular values state it (needs sum w right)
        "variance_gap": max(rel(lam, ref["explained_variance"]),
                            rel(out["singular_values"] ** 2 / (n - 1), ref["explained_variance"])),
        "ratio_gap": rel(out["explained_variance_ratio"], ref["explained_variance_ratio"]),  # needs tr C right
        # against a coordinate's typical spread (the mean itself is near 0 on these rows)
        "mean_gap": float(np.max(np.abs(out["mean"] - ref["mean"])) / np.sqrt(ref["trace"] / len(ref["mean"]))),
        "orthonormality": float(np.max(np.abs(np.eye(len(V)) - V @ V.T))),  # bench_pca.py's quality score
        # an eigensolver that has not converged fails here whatever the gaps
        "residual": float(np.max(np.linalg.norm(ref["cov"] @ V.T - V.T * lam, axis=0)) / ref["lambda_1"]),
        "sign_gap": float(np.sum(~(lead > 0))),  # components whose largest-magnitude entry is not positive
    }
