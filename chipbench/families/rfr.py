"""RandomForestRegressor: estimator builder, work counts, the plain reference
and what is compared.

What program and reference both compute. *Edges*, *bins* and *draws* are
`families/rfc.py`'s, imported from there (the same sketch, the same uint8
bins, the same threefry bootstrap and feature subsets); a node takes
m = d // 3 features (featureSubsetStrategy "auto" for regression: 1,000 of
3,000). *Statistics*: a row counted c times by its tree's bootstrap adds
(c, c y, c y^2) to its node; y is the configuration's continuous target
(`drivers/fit_loop_target.py`). *Growth*, level-wise in the full binary
layout: for each node with rows, (w, wy) over (feature of its subset, bin);
prefix sums over bins give the split `bin <= b` its left side, the node's
totals less it the right; its gain is the variance it removes, (ss_p - ss_l
- ss_r) / w_p with ss = wy^2-sum - wy^2 / w, computed as the same number
w_l w_r / w_p (mu_l - mu_r)^2 / w_p (the wy^2 sums cancel); valid where both
sides hold `minInstancesPerNode` and b is not the last bin; the node splits
at the first largest valid gain (in (subset position, bin) order) if it is
over `minInfoGain`, with the threshold edges[feature, b]; else it is a leaf.
A node no row reached reports its parent's statistics.

The reference is numpy in float64 on the host (the bins are taken on the
device by `rfc.bin_rows`' own searchsorted). It imports nothing of the
program; `_plans_no_scatter` only asks the program's level plan whether it can
run the configuration at all. As `rfc.py` does, `compare_fit` FOLLOWS the
program's trees and re-derives every node of them from the rows the program's
own splits route there, with their bootstrap counts: the node's (w, wy, wy^2)
and its best split over its 1,000 features x 127 thresholds. A level's
histogram is one weighted `np.bincount` a (node, feature, statistic) over the
node's rows (`rfr_hist.py`), on spawned worker processes over the bins in
shared memory where a level is large, in this process otherwise; the levels
the reference's own forest grew are not grown again where the program's tree
routes the same rows there.
"""
from __future__ import annotations

import math
import os
import time
import weakref
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from . import rfc, rfr_hist
from .rfc import _span, assert_path as _assert_resident, bin_rows, bootstrap_counts, node_features, quantile_edges, tree_place

SEEDED = True  # the answer depends on the estimator seed (bootstrap and feature subsets)
S = 3  # (w, wy, wy^2)
STAT_PIECES = 3  # the bfloat16 pieces a float32 statistic goes in as (the span's `stat_pieces`)


# ------------------------------------------------------------- the program ---


def estimator(config: dict, seed: int, overrides: dict | None = None):
    from spark_rapids_ml_tpu.models.regression import RandomForestRegressor

    _plans_no_scatter(config)
    est = RandomForestRegressor(**config["estimator"], seed=int(seed), num_workers=int(config["num_workers"]),
                                **(overrides or {}))
    return est.setFeaturesCol("features").setLabelCol("label")


def _plans_no_scatter(config: dict) -> None:
    """The configuration names a fit whose float statistics no level sends to
    the scatter (`segment_sum`: 6 to 9 s a pass at 1,000 features a node, some
    minutes a fit). A program whose level plan sends any level there (the
    parent of PR 40: every level of a regressor) is not that program, and the
    run ends here, before anything is placed, with an exit code of its own.
    Asked of the program's own plan (`ops.trees.level_plan` at the fit's
    depth, features a node, bins and three statistics, the arguments every
    version of it takes), not of a name inside its solver."""
    from spark_rapids_ml_tpu.ops.trees import level_plan

    est = config["estimator"]
    plan = level_plan(int(est["maxDepth"]), features_per_node(config), int(est["maxBins"]), S)
    scattered = [lv["depth"] for lv in plan if lv["accumulate"] == "scatter"]
    if scattered:
        raise SystemExit(f"chipbench.families.rfr: this program's forest plans the scatter for a regressor's float "
                         f"statistics at levels {scattered}; the rfr-p3k configuration cannot run on it")


def outputs(model) -> Dict[str, Any]:
    grow = _span(model, "fit/solve/grow")
    return {
        "feature": np.asarray(model.feature, np.int64),
        "threshold": np.asarray(model.threshold, np.float64),
        "node_stats": np.asarray(model.node_stats, np.float64),
        "said": {k: grow.get(k) for k in ("trees", "depth", "bins", "features_per_node", "passes_per_tree", "accumulate",
                                          "stat_pieces")},
    }


def iterations(out: Dict[str, Any]) -> int:
    """Levels grown a fit: trees x depth."""
    return rfc.iterations(out)


def before_fit(rehearse: bool) -> None:
    pass


def assert_path(model) -> None:
    """`rfc.assert_path`'s (admitted resident, a refit bins nothing), and no
    pass over the rows took the scatter."""
    _assert_resident(model)
    scattered = (getattr(model, "_fit_metrics", None) or {}).get("counters", {}).get("forest.scatter_passes", 0)
    if scattered:
        raise RuntimeError(f"regression forest fit sent {scattered} passes to the scatter")


# ------------------------------------------------------------ work counts ---


def features_per_node(config: dict) -> int:
    return max(1, int(config["d"]) // 3)  # featureSubsetStrategy "auto", regression


def hist_bytes(config: dict, levels: Optional[float] = None) -> float:
    """What the accumulate has to read, whatever implements it: a tree's level
    reads, for each row, its m bin ids (a byte each), its node id (4), its
    three float32 statistics (12) and its flag (1), once. `levels`: levels
    grown a fit (trees x depth by default)."""
    est = config["estimator"]
    if levels is None:
        levels = int(est["numTrees"]) * int(est["maxDepth"])
    return float(levels) * int(config["rows"]) * (features_per_node(config) + 4 + 4 * S + 1)


def fit_work(config: dict, n_iter: float) -> Dict[str, float]:
    """What one fit needs, over all chips: the accumulate's reads (`n_iter`
    levels); its sums are one add a (row, feature, statistic) cell, counted as
    the FLOP they are. Memory-bound by its count."""
    return {"flops": float(n_iter) * int(config["rows"]) * features_per_node(config) * S,
            "bytes": hist_bytes(config, n_iter)}


# ------------------------------------------------------------- the growth ---


def _bf16(a: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return a.astype(ml_dtypes.bfloat16).astype(np.float64)


def row_stats(y: np.ndarray, cnt: np.ndarray, rows: np.ndarray, rnd=None) -> np.ndarray:
    """[rows, 3] float64 (c, c y, c y^2) of the rows; `rnd` rounds the two
    float ones (the control's single bfloat16 piece; c is an integer)."""
    c = cnt[rows].astype(np.float64)
    v = np.stack([c, c * y[rows], c * y[rows] ** 2], axis=1)
    if rnd is not None:
        v[:, 1:] = rnd(v[:, 1:])
    return v


class Bins:
    """The reference's uint8 bins feature-major, [features, rows], in a file
    of the temporary directory that the worker processes map too (a plain
    array where no file can be had). Made once a dataset; removed with it."""

    def __init__(self, Xb: np.ndarray, block_rows: int = 1024):
        import tempfile

        n, d = Xb.shape
        self.shape, self.path = (d, n), None
        try:
            fd, self.path = tempfile.mkstemp(prefix="rfr-bins-", suffix=".u8")
            os.close(fd)
            weakref.finalize(self, os.unlink, self.path)
            self.T = np.memmap(self.path, np.uint8, mode="w+", shape=self.shape)
        except OSError:
            self.path, self.T = None, np.empty(self.shape, np.uint8)
        for r0 in range(0, n, block_rows):  # a block's transpose stays in cache: 1.6 s at the cell's size, 7.6 whole
            self.T[:, r0 : r0 + block_rows] = Xb[r0 : r0 + block_rows].T
        if self.path:
            self.T.flush()

    def at(self, rows: np.ndarray, feats: np.ndarray) -> np.ndarray:
        """Each row's bin at its own feature."""
        return self.T[feats, rows]


POOL_CELLS = 20_000_000  # a level of more (row, feature) cells goes to the worker processes
FEATURE_BLOCK = 128  # features a task
_POOL = None


def _pool():
    """Spawned workers (never forked: this process holds the chip's runtime), kept for the process's life."""
    global _POOL
    if _POOL is None:
        import multiprocessing
        from concurrent.futures import ProcessPoolExecutor

        _POOL = ProcessPoolExecutor(max_workers=max(1, min(12, (os.cpu_count() or 2) - 1)),
                                    mp_context=multiprocessing.get_context("spawn"))
    return _POOL


def level_stats(binsT: Bins, v: np.ndarray, local: np.ndarray, rows: np.ndarray, fids: np.ndarray, bins: int):
    """(w, wy) [C, m, bins, 2] over each node's feature subset and the
    nodes' (w, wy, wy^2) [C, 3] of the rows `rows` (global ids, each at node
    `local` of the level, with statistics `v`): a weighted `np.bincount` a
    (node, feature, statistic), float64, by blocks of `FEATURE_BLOCK`
    features on the worker processes where the level is large."""
    C, m = fids.shape
    order = np.argsort(local, kind="stable")
    bounds = np.concatenate([[0], np.cumsum(np.bincount(local, minlength=C))])
    totals = np.stack([np.bincount(local, weights=v[:, s], minlength=C) for s in range(S)], axis=1)
    h = np.zeros((C, m, bins, 2))
    tasks = [(c, j0, order[bounds[c] : bounds[c + 1]]) for c in range(C) for j0 in range(0, m, FEATURE_BLOCK)
             if bounds[c + 1] > bounds[c]]
    args = [(rows[at], v[at, :2], fids[c, j0 : j0 + FEATURE_BLOCK]) for c, j0, at in tasks]
    if binsT.path and rows.size * m > POOL_CELLS:
        got = _pool().map(rfr_hist.mapped_block, *zip(*[(binsT.path, binsT.shape, *a, bins) for a in args]))
    else:
        got = (rfr_hist.block(binsT.T, *a, bins) for a in args)
    for (c, j0, _), part in zip(tasks, got):
        h[c, j0 : j0 + part.shape[0]] = part
    return h, totals


def split_gains(h: np.ndarray, totals: np.ndarray, min_instances: float):
    """gain [C, m, bins] (-inf where the split is not valid) and the nodes'
    impurity (variance) [C], float64."""
    left = np.cumsum(h, axis=2)
    w_l, wy_l = left[..., 0], left[..., 1]
    w_p, wy_p, wyy_p = (totals[:, s][:, None, None] for s in range(S))
    w_r, wy_r = w_p - w_l, wy_p - wy_l
    with np.errstate(invalid="ignore", divide="ignore"):
        mu_gap = wy_l / np.maximum(w_l, 1e-300) - wy_r / np.maximum(w_r, 1e-300)
        gain = (w_l * w_r / np.maximum(w_p, 1e-300)) * mu_gap * mu_gap / np.maximum(w_p, 1e-300)
    valid = (w_l >= min_instances) & (w_r >= min_instances)
    valid[:, :, -1] = False  # the last bin means "everything left"
    w, wy, wyy = totals.T
    imp = np.maximum(wyy - wy * wy / np.maximum(w, 1e-300), 0.0) / np.maximum(w, 1e-300)
    return np.where(valid, gain, -np.inf), imp


def _fill(stats: np.ndarray) -> np.ndarray:
    """A node no row reached reports its parent's statistics."""
    out = stats.copy()
    for i in range(1, out.shape[0]):
        if out[i, 0] == 0:
            out[i] = out[(i - 1) // 2]
    return out


def _last_level(stats, node, rows, v_of, depth: int) -> None:
    C, off = 2**depth, 2**depth - 1
    v = v_of(rows)
    stats[off : off + C] = np.stack([np.bincount(node[rows] - off, weights=v[:, s], minlength=C) for s in range(S)], axis=1)


def grow_tree(binsT: Bins, lo: int, y, cnt, edges, seed: int, tree: int, *, depth: int, levels: int, m: int, bins: int,
              min_instances: float = 1.0, min_info_gain: float = 0.0, take: Optional[int] = None,
              round_stats=None, memo: Optional[dict] = None) -> Dict[str, np.ndarray]:
    """One tree free-running from the stated draws on the rows lo .. lo + len(cnt)
    (the worker's): arrays of a depth-`depth` layout of which `levels` levels
    are grown (the rest leaves). The hooks plant faults: `take` features of
    each subset, `round_stats` (the rows' float statistics rounded: the
    control's lower precision). `memo` keeps each level's histogram for
    `follow_tree`."""
    d, n = binsT.shape[0], len(cnt)
    M = 2 ** (depth + 1) - 1
    feature, threshold, stats = np.full(M, -1, np.int64), np.full(M, np.inf), np.zeros((M, S))
    node, rows = np.zeros(n, np.int64), np.flatnonzero(cnt > 0)
    v_of = lambda r: row_stats(y, cnt, r, round_stats)
    for level in range(levels):
        C, off = 2**level, 2**level - 1
        fids = node_features(seed, tree, level, d, m)[:, : (take or m)]
        local = node[rows] - off
        h, totals = level_stats(binsT, v_of(rows), local, rows + lo, fids, bins)
        if memo is not None:
            memo[level] = (rows, local, h, totals)
        gain, _ = split_gains(h, totals, min_instances)
        stats[off : off + C] = totals
        flat = gain.reshape(C, -1)
        best = flat.argmax(axis=1)
        split = flat[np.arange(C), best] > min_info_gain
        f, b = fids[np.arange(C), best // bins], best % bins
        feature[off : off + C] = np.where(split, f, -1)
        threshold[off : off + C] = np.where(split, edges[f, np.minimum(b, edges.shape[1] - 1)], np.inf)
        at = node[rows] - off
        rows = rows[split[at]]  # rows of leaves stay where they are
        at = node[rows] - off
        node[rows] = 2 * node[rows] + np.where(binsT.at(rows + lo, f[at]) <= b[at], 1, 2)
    _last_level(stats, node, rows, v_of, levels)
    return {"feature": feature, "threshold": threshold, "node_stats": _fill(stats)}


def _stack(trees: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    return {k: np.stack([t[k] for t in trees]) for k in ("feature", "threshold", "node_stats")}


def predict(forest: Dict[str, np.ndarray], X: np.ndarray, depth: int) -> np.ndarray:
    """The mean over trees of wy / w at the node a row reaches in the first
    `depth` levels; the reference's own traversal: `x <= float32(threshold)`
    goes left, as the bins do."""
    n = X.shape[0]
    thr32 = forest["threshold"].astype(np.float32)
    out = np.zeros(n)
    every = np.arange(n)
    for t in range(forest["feature"].shape[0]):
        node = np.zeros(n, np.int64)
        for _ in range(depth):
            f = forest["feature"][t, node]
            child = 2 * node + np.where(X[every, np.maximum(f, 0)] <= thr32[t, node], 1, 2)
            node = np.where(f >= 0, child, node)
        s = forest["node_stats"][t, node]
        out += s[:, 1] / np.maximum(s[:, 0], 1e-300)
    return out / forest["feature"].shape[0]


def r2(forest: Dict[str, np.ndarray], X: np.ndarray, y: np.ndarray, depth: int) -> float:
    """The protocol's quality score on the training rows (`bench_random_forest.py`): R^2."""
    resid = y - predict(forest, X, depth)
    return float(1.0 - resid @ resid / np.sum((y - y.mean()) ** 2))


# -------------------------------------------------------------- reference ---


def _sizes(config: dict, d: int):
    est = config["estimator"]
    workers = int(config["num_workers"])
    return {"depth": int(est["maxDepth"]), "bins": int(est["maxBins"]), "trees": int(est["numTrees"]),
            "workers": workers, "trees_per_worker": -(-int(est["numTrees"]) // workers), "m": max(1, d // 3),
            "min_instances": float(est.get("minInstancesPerNode", 1)), "min_info_gain": float(est.get("minInfoGain", 0.0))}


def prepared(config: dict, data, blocks: Sequence[Any]) -> Dict[str, Any]:
    """What does not depend on the estimator seed, made once a dataset: the
    reference's edges, its bins (feature-major, `Bins`), and the target in
    float64."""
    kept = getattr(data, "_rfr_prepared", None)
    bins, n = int(config["estimator"]["maxBins"]), len(blocks) * blocks[0].shape[0]
    if kept is None or kept["bins"] != bins or kept["n"] != n:
        X = data.X[:n]
        t0 = time.perf_counter()
        edges = quantile_edges(X, bins)
        _note("edges", t0)
        t0 = time.perf_counter()
        kept = {"bins": bins, "edges": edges, "binsT": Bins(bin_rows(blocks, edges)), "X": X, "y": np.asarray(data.y[:n], np.float64), "n": n}
        _note("bins", t0)
        data._rfr_prepared = kept
    return kept


def _note(what: str, t0: float) -> None:
    import sys

    print(f"    rfr reference: {what} {time.perf_counter() - t0:.2f}s", file=sys.stderr, flush=True)


def checked_trees(config: dict, data, seed: int) -> List[int]:
    """The trees whose every node is re-derived, drawn from the run's seed."""
    return rfc.checked_trees(config, data, seed)


def reference_fit(config: dict, data, blocks: Sequence[Any], seed: int = 0) -> Dict[str, Any]:
    """The reference's own forest free-running from the stated draws, to the
    depth `check.r2_depth` (every tree), with its R^2 on the rows; and what
    `compare_fit` needs to follow the program's trees."""
    prep = prepared(config, data, blocks)
    sz = _sizes(config, prep["binsT"].shape[0])
    kept = getattr(data, "_rfr_refs", {})
    if (seed, sz["depth"], sz["trees"], sz["bins"]) in kept:  # the control and the planted faults ask again
        return kept[(seed, sz["depth"], sz["trees"], sz["bins"])]
    shallow = min(int(config["check"]["r2_depth"]), sz["depth"])
    counts, memo = {}, {}
    for t in range(sz["trees"]):
        key, lo, hi = tree_place(sz, t, prep["n"])
        counts[t] = bootstrap_counts(seed, key, hi - lo)
    t0 = time.perf_counter()
    free = _stack([_grow(prep, sz, counts[t], seed, t, levels=shallow, memo=memo.setdefault(t, {})) for t in range(sz["trees"])])
    ref = {"prep": prep, "sizes": sz, "seed": int(seed), "counts": counts, "free": free, "shallow": shallow, "memo": memo,
           "r2_free": r2(free, prep["X"], prep["y"], shallow), "check": checked_trees(config, data, seed)}
    _note(f"its own forest to depth {shallow}", t0)
    data._rfr_refs = {(seed, sz["depth"], sz["trees"], sz["bins"]): ref}  # the last seed's only
    return ref


def _grow(prep, sz, cnt, seed: int, t: int, levels: int, **hooks) -> Dict[str, np.ndarray]:
    key, lo, hi = tree_place(sz, t, prep["n"])
    return grow_tree(prep["binsT"], lo, prep["y"][lo:hi], cnt, prep["edges"], seed, key, depth=sz["depth"], levels=levels,
                     m=sz["m"], bins=sz["bins"], min_instances=sz["min_instances"], min_info_gain=sz["min_info_gain"], **hooks)


def _planted(config: dict, data, blocks, seed: int, **hooks) -> Dict[str, Any]:
    """A forest in the shape of `outputs`: the checked trees grown free-running
    to the full depth with the hooks' fault, the others the reference's
    shallow trees (their deeper levels leaves)."""
    ref = reference_fit(config, data, blocks, seed)
    prep, sz = ref["prep"], ref["sizes"]
    levels = hooks.pop("levels", sz["depth"])
    counts = hooks.pop("counts", ref["counts"])
    every = hooks.pop("every_tree", False)
    trees = []
    for t in range(sz["trees"]):
        if every or t in ref["check"]:
            trees.append(_grow(prep, sz, counts[t], seed, t, levels=levels, **hooks))
        else:
            trees.append({k: v[t] for k, v in ref["free"].items()})
    # the span says what was asked for: the arrays have to show the fault
    said = {"trees": sz["trees"], "depth": sz["depth"], "bins": sz["bins"], "features_per_node": sz["m"], "stat_pieces": STAT_PIECES}
    return {**_stack(trees), "said": said}


def control_fit(run, blocks: Sequence[Any], seed: int) -> Dict[str, Any]:
    """The lower precision put in the program's place: each row's float
    statistics as ONE bfloat16 piece (the classifier's one-hot form applied to
    a regressor's (c y, c y^2); c is exact), summed and compared as the
    program's would be."""
    return _planted(run.config, run.data, blocks, seed, round_stats=_bf16)


def fault_fits(config: dict, data, blocks: Sequence[Any], seed: int, chips: int) -> Dict[str, Dict[str, Any]]:
    """The faults a fit can have, planted in the reference put in the
    program's place (for reading a fault at the cell's own size)."""
    sz = _sizes(config, data.d)
    n = len(blocks) * blocks[0].shape[0]
    return {
        # every tree is its root: the state a fit starts from
        "not_grown": _planted(config, data, blocks, seed, levels=0, every_tree=True),
        "a_level_left_out": _planted(config, data, blocks, seed, levels=sz["depth"] - 1),
        "half_the_features": _planted(config, data, blocks, seed, take=max(1, sz["m"] // 2)),
        "no_bootstrap": _planted(config, data, blocks, seed,
                                 counts={t: np.ones(n // sz["workers"], np.int64) for t in range(sz["trees"])}),
    }


NUMBERS = ("weight_gap", "stats_gap", "gain_gap", "threshold_gap", "shape_gap", "r2_gap")


def follow_tree(ref: Dict[str, Any], out: Dict[str, Any], t: int) -> Dict[str, float]:
    """Every node of the program's tree t re-derived: the statistics of the
    rows its own splits route there, and the best split of the node's subset."""
    prep, sz = ref["prep"], ref["sizes"]
    key, lo, hi = tree_place(sz, t, prep["n"])
    binsT, y, edges, cnt = prep["binsT"], prep["y"][lo:hi], prep["edges"], ref["counts"][t]
    depth, m, bins = sz["depth"], sz["m"], sz["bins"]
    feature, threshold = out["feature"][t], out["threshold"][t]
    stats = np.zeros((feature.shape[0], S))
    node, rows = np.zeros(hi - lo, np.int64), np.flatnonzero(cnt > 0)
    v_of = lambda r: row_stats(y, cnt, r)
    gain_gap = threshold_gap = 0.0
    for level in range(depth):
        C, off = 2**level, 2**level - 1
        fids = node_features(ref["seed"], key, level, binsT.shape[0], m)
        local = node[rows] - off
        seen = ref["memo"].get(t, {}).get(level)
        if seen is not None and np.array_equal(seen[0], rows) and np.array_equal(seen[1], local):
            h, totals = seen[2:]  # the reference's own tree routed the same rows here
        else:
            h, totals = level_stats(binsT, v_of(rows), local, rows + lo, fids, bins)
        gain, imp = split_gains(h, totals, sz["min_instances"])
        stats[off : off + C] = totals
        f = feature[off : off + C]
        split, held = f >= 0, totals[:, 0] > 0
        # the program's split: its position in the node's subset, its bin by its threshold among the reference's edges
        pos = np.argmax(fids == f[:, None], axis=1)
        in_subset = (fids[np.arange(C), pos] == f) & split
        e = edges[np.maximum(f, 0)]
        b = np.minimum((e < threshold[off : off + C, None]).sum(axis=1), bins - 2)
        threshold_gap = max(threshold_gap, float(np.max(np.abs(np.where(split, e[np.arange(C), b] - threshold[off : off + C], 0.0)))))
        best = gain.reshape(C, -1).max(axis=1)
        took = gain[np.arange(C), pos, b]
        scale = np.maximum(imp, 1e-300)
        with np.errstate(invalid="ignore"):  # a node without a valid split has best = took = -inf
            gap = np.where(split, np.where(in_subset & held & np.isfinite(took), (best - took) / scale, 1.0),
                           np.where(held & (best > sz["min_info_gain"]), best / scale, 0.0))
        gain_gap = max(gain_gap, float(gap.max()))
        at = node[rows] - off
        rows = rows[split[at]]
        at = node[rows] - off
        node[rows] = 2 * node[rows] + np.where(binsT.at(rows + lo, np.maximum(f, 0)[at]) <= b[at], 1, 2)
    _last_level(stats, node, rows, v_of, depth)
    want, got = _fill(stats), out["node_stats"][t]
    reached = want[:, 0] > 0
    weight_gap = float(np.max(np.abs(want[:, 0] - got[:, 0])))
    # wy against its natural scale sqrt(w wy^2-sum) >= |wy| (never 0 where a row with y != 0 is), wy^2 against itself
    scale_wy = np.sqrt(np.maximum(want[:, 0] * want[:, 2], 1e-300))
    with np.errstate(invalid="ignore"):
        stats_gap = float(np.max(np.where(reached, np.maximum(np.abs(got[:, 1] - want[:, 1]) / scale_wy,
                                                               np.abs(got[:, 2] - want[:, 2]) / np.maximum(want[:, 2], 1e-300)), 0.0)))
    return {"weight_gap": weight_gap, "stats_gap": stats_gap, "gain_gap": gain_gap, "threshold_gap": threshold_gap}


def levels_grown(out: Dict[str, Any]) -> int:
    """The deepest level at which some node of some tree holds rows below a split."""
    nodes = out["feature"].shape[1]
    parent_split = out["feature"][:, (np.arange(1, nodes) - 1) // 2] >= 0
    reached = np.flatnonzero((parent_split & (out["node_stats"][:, 1:, 0] > 0)).any(axis=0)) + 1
    return int(math.floor(math.log2(reached.max() + 1))) if reached.size else 0


def compare_fit(config: dict, out: Dict[str, Any], ref: Dict[str, Any], data, blocks: Sequence[Any]) -> Dict[str, float]:
    """The numbers a fit is judged by (each is 0 for a perfect answer; an
    answer that is not a number makes every one NaN, which no limit admits)."""
    sz = ref["sizes"]
    nodes = 2 ** (sz["depth"] + 1) - 1
    if out["feature"].ndim != 2 or out["feature"].shape[1] != nodes or out["node_stats"].shape[2] != S:
        return {**{name: float("nan") for name in NUMBERS}, "shape_gap": float(abs(out["feature"].shape[-1] - nodes) + 1)}
    if not np.isfinite(out["node_stats"]).all() or np.isnan(out["threshold"]).any():
        return {name: float("nan") for name in NUMBERS}
    read = {"weight_gap": 0.0, "stats_gap": 0.0, "gain_gap": 0.0, "threshold_gap": 0.0}
    t0 = time.perf_counter()
    for t in [t for t in ref["check"] if t < out["feature"].shape[0]]:
        for k, v in follow_tree(ref, out, t).items():
            read[k] = max(read[k], v)
    _note(f"trees {ref['check']} followed", t0)
    t0 = time.perf_counter()
    said = out["said"]
    read["shape_gap"] = float(max(
        abs(out["feature"].shape[0] - sz["trees"]), abs(levels_grown(out) - sz["depth"]),
        abs((said.get("trees") or 0) - sz["trees"]), abs((said.get("depth") or 0) - sz["depth"]),
        abs((said.get("features_per_node") or 0) - sz["m"]), abs((said.get("bins") or 0) - sz["bins"]),
        abs((said.get("stat_pieces") or 0) - STAT_PIECES),
    ))
    prep = ref["prep"]
    forest = {k: out[k] for k in ("feature", "threshold", "node_stats")}
    # the protocol's quality score on the training rows: the forest cut at the reference's depth against the
    # reference's own forest there (the same forest but for ties), and the whole forest no worse than that
    shallow = r2(forest, prep["X"], prep["y"], ref["shallow"])
    whole = r2(forest, prep["X"], prep["y"], sz["depth"])
    read["r2_gap"] = max(abs(shallow - ref["r2_free"]), ref["r2_free"] - whole)
    _note("R^2", t0)
    return read
