"""Exact k-nearest neighbours: how to build the estimator from a
configuration, the queries a call asks, the work a call needs, the plain
reference, and what is compared.

The reference is exact kNN by brute force in float64 numpy over row blocks of
the host copy of the items: every item's squared euclidean distance to each
sampled query, the k smallest kept, ties to the lower item index. It imports
nothing of the program.
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

from .. import datagen

SEEDED = False
REF_BLOCK_ROWS = 262144  # item rows a reference block: 200 MB of float64 at d = 96
# Two items whose float64 squared distances lie within this share of the k-th
# of each other are a tie to a float32 search: its distances carry about
# eps32 (||x||^2 + ||q||^2 + 2 |q.x|) / d2, 1e-6 of d2 at this data's scale,
# and a one-pass bfloat16 contraction's 3e-3 and more (PERF.md section 6)
TIE_TOL = 1e-4


# ------------------------------------------------------------- the program ---


def estimator(config: dict):
    from spark_rapids_ml_tpu.models.knn import NearestNeighbors

    est = NearestNeighbors(**config["estimator"], num_workers=int(config["num_workers"]))
    return est.setInputCol("features")


def before_fit(rehearse: bool) -> None:
    """The cell names the fused top-k kernel on resident items. Resolved
    here, outside any trace, before the item set is ingested: a program
    without that path fails here, at once."""
    from spark_rapids_ml_tpu.models.knn import NearestNeighborsModel
    from spark_rapids_ml_tpu.ops import distance

    want = "interpret" if rehearse else "pallas"
    if distance.kernel_mode() != want:
        raise RuntimeError(f"distance core runs {distance.kernel_mode()!r}, the cell names {want!r}")
    if not hasattr(distance, "topk_plan") or not hasattr(NearestNeighborsModel, "_resident_items"):
        raise RuntimeError("this program has no fused top-k kernel and keeps no resident item set: "
                           "it cannot run the cell")


def assert_path(model, setup: dict) -> None:
    """After the set-up's two calls, before the window: the items are held on
    the device, placed once and reused once, and the search took the kernel."""
    counters = setup["counters"]
    placed, reused = counters.get("knn.item_placements", 0), counters.get("knn.item_reuses", 0)
    if model.__dict__.get("_resident") is None or placed != 1 or reused < 1:
        raise RuntimeError(f"items not resident: {placed} placements, {reused} reuses in set-up")
    paths = {s.get("topk_path") for s in setup["spans"] if s["path"] == "kneighbors/search"}
    if paths != {"kernel"}:
        raise RuntimeError(f"the set-up's searches took {sorted(map(str, paths))}, the cell names the kernel")


def outputs(knn_df) -> Dict[str, Any]:
    return {"indices": np.stack(knn_df["indices"].to_numpy()), "distances": np.stack(knn_df["distances"].to_numpy())}


# ---------------------------------------------------------------- traffic ---


def queries(config: dict, data, n: int, index: int) -> np.ndarray:
    """Query set `index` of `n` rows: rows of the items' own recipe (the same
    blob centres and planted directions, from the run's seed), from a block
    stream past the items' blocks, so that no query is an item."""
    spec = config["data"]
    aux = datagen._aux(data.seed, data.d, int(spec["blobs"]))
    out = np.empty((n, data.d), np.float32)
    datagen._block(data.seed, data.n_blocks + index, n, aux, out, np.empty(n, np.float32), None)
    return out


# ------------------------------------------------------------ work counts ---


def topk_work(config: dict, n_queries: int) -> Dict[str, float]:
    """What one call needs, over all chips: the n_queries x items contraction
    (2 n_q n d FLOP) and one read of the float32 items. The norms, the
    selection and the answer's few bytes are left out."""
    n, d = int(config["rows"]), int(config["d"])
    return {"flops": 2.0 * n_queries * n * d, "bytes": 4.0 * n * d}


# -------------------------------------------------------------- reference ---


def reference_knn(X: np.ndarray, q: np.ndarray, k: int, lower: bool = False) -> Dict[str, np.ndarray]:
    """Exact kNN of the queries `q` over all rows of X: squared distances
    [nq, k] and item indices [nq, k], ascending, ties to the lower index.
    float64 throughout; `lower` is the control: the cross term as one
    bfloat16 pass with float32 accumulation (the `fast` contraction), the
    norms float32. The first block's k best seed each row's list; a later
    block offers only the items at or under its row's k-th distance."""
    nq = q.shape[0]
    if lower:
        import ml_dtypes

        qb = q.astype(ml_dtypes.bfloat16).astype(np.float32)
        q_sq = np.sum(q * q, axis=1, dtype=np.float32)[:, None]
    else:
        q64 = q.astype(np.float64)
        q_sq = np.sum(q64 * q64, axis=1)[:, None]
    best_d = best_i = None
    for lo in range(0, X.shape[0], REF_BLOCK_ROWS):
        xb = X[lo : lo + REF_BLOCK_ROWS]
        # part = ||x||^2 - 2 q.x, made in place; d2 = part + ||q||^2
        if lower:
            part = qb @ xb.astype(ml_dtypes.bfloat16).astype(np.float32).T
            part *= -2.0
            part += np.einsum("ij,ij->i", xb, xb)[None, :]
            part = part.astype(np.float64)
        else:
            x64 = xb.astype(np.float64)
            part = q64 @ x64.T
            part *= -2.0
            part += np.einsum("ij,ij->i", x64, x64)[None, :]
        if best_d is None:
            take = np.argpartition(part, min(k, part.shape[1]) - 1, axis=1)[:, :k]
            rows, cols = np.repeat(np.arange(nq), take.shape[1]), take.ravel()
            best_d, best_i = np.empty((nq, 0)), np.empty((nq, 0), np.int64)
        else:
            rows, cols = np.nonzero(part <= best_d[:, -1:] - q_sq)
        cat_r = np.concatenate([np.repeat(np.arange(nq), best_d.shape[1]), rows])
        cat_d = np.concatenate([best_d.ravel(), part[rows, cols] + q_sq[rows, 0]])
        cat_i = np.concatenate([best_i.ravel(), lo + cols])
        order = np.lexsort((cat_i, cat_d, cat_r))
        first = np.searchsorted(cat_r[order], np.arange(nq))
        pick = order[first[:, None] + np.arange(min(k, len(order) // nq))]
        best_d, best_i = cat_d[pick], cat_i[pick]
    return {"d2": best_d, "indices": best_i}


def exact_d2(X: np.ndarray, q: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """float64 squared distance of each query to each of its returned items."""
    diff = X[np.clip(ids, 0, X.shape[0] - 1)].astype(np.float64) - q.astype(np.float64)[:, None, :]
    return np.sum(diff * diff, axis=2)


def compare(X: np.ndarray, q: np.ndarray, out: Dict[str, np.ndarray], ref: Dict[str, np.ndarray]) -> Dict[str, float]:
    """The numbers a call's sampled rows are judged by, each 0 for a perfect
    answer (a distance that is not a number makes its number NaN, which no
    limit admits):

    * `id_gap_max`: how far past the exact k-th squared distance a returned
      item lies, over that distance; near-ties that float32 rounding swaps
      read ~1e-7, a wrong neighbour far more;
    * `recall_loss_max`: 1 - recall@k against the exact ids, worst row, an
      item within `TIE_TOL` of the k-th distance counting as found;
    * `dist_rel_err_max`: returned distance against the float64 distance of
      the same item, relative;
    * `unsorted_rows`, `bad_ids`: rows not ascending; ids out of range or
      repeated within a row."""
    ids, dist = out["indices"], out["distances"]
    n, k = X.shape[0], ids.shape[1]
    d2 = exact_d2(X, q, ids)
    kth = ref["d2"][:, -1:]
    gap = np.max((d2 - kth) / np.maximum(kth, 1e-30))
    tie = d2 <= kth * (1.0 + TIE_TOL)
    recall = [np.mean([i in exact or t for i, t in zip(row.tolist(), ties)])
              for row, ties, exact in zip(ids, tie.tolist(), (set(r.tolist()) for r in ref["indices"]))]
    true = np.sqrt(np.maximum(d2, 0.0))
    err = np.abs(dist - true) / np.maximum(true, 1e-30)
    bad = np.sum((ids < 0) | (ids >= n)) + sum(k - len(set(row.tolist())) for row in ids)
    return {
        "id_gap_max": float(max(gap, 0.0)) if np.isfinite(gap) else float("nan"),
        "recall_loss_max": 1.0 - float(min(recall)),
        "dist_rel_err_max": float(np.max(err)) if np.isfinite(err).all() else float("nan"),
        "unsorted_rows": float(np.sum(np.any(np.diff(dist, axis=1) < 0, axis=1))),
        "bad_ids": float(bad),
    }


def control_answer(X: np.ndarray, q: np.ndarray, k: int) -> Dict[str, np.ndarray]:
    """The program has no precision below the stated one in kneighbors: the
    reference with a one-pass bfloat16 contraction stands in its place."""
    low = reference_knn(X, q, k, lower=True)
    return {"indices": low["indices"], "distances": np.sqrt(np.maximum(low["d2"], 0.0))}


def judge(X: np.ndarray, sample: Sequence[Dict[str, Any]], k: int, control: bool):
    """(read, control read) over the sampled calls, each a dict with the
    queries `q` and the answer's rows `out`: one reference pass over the
    items for all of them (and one for the control)."""
    q = np.concatenate([s["q"] for s in sample])
    out = {key: np.concatenate([s["out"][key] for s in sample]) for key in ("indices", "distances")}
    ref = reference_knn(X, q, k)
    read = compare(X, q, out, ref)
    ctrl = compare(X, q, control_answer(X, q, k), ref) if control else None
    return read, ctrl


def sampled(outputs_: List[Dict[str, Any]], sets: List[np.ndarray], rows: np.ndarray) -> List[Dict[str, Any]]:
    """The sampled rows of each call: its queries and its answer's rows."""
    return [{"q": sets[o["set"]][rows], "out": {k: v[rows] for k, v in o.items() if k != "set"}} for o in outputs_]
