"""RandomForestClassifier: estimator builder, work counts, the plain reference
and what is compared.

What program and reference both compute. *Edges*: of the placed rows, all up
to 100,000, else 100,000 without replacement from `default_rng(0)` (a fixed
stream: never the estimator seed), sorted by index; per feature the
`k / maxBins` quantiles, k = 1 .. maxBins - 1, by `np.quantile` in float64
(method "linear"). *Bins*: `searchsorted(float32(edges[f]), x[:, f], "left")`,
uint8: bin b holds edges[b - 1] < x <= edges[b]. *Draws* (stated, as
`families/kmeans.init_rows` states `initMode="random"`; `jax.random`'s
threefry bits are the same on every backend, and everything after them is
integer arithmetic or a stable sort): tree t has the key
`fold_in(PRNGKey(seed), t)`; its bootstrap is n int32 draws `randint(split(key)[0],
(n,), 0, n)` over the n placed rows, a row's count how often it was drawn;
the 2^L nodes of level L take the first m = int(sqrt(d)) columns of a stable
`argsort` of float32 `uniform(fold_in(key, 7919 + L), (2^L, d))`. *Growth*,
level-wise in the full binary layout (node i's children 2i + 1, 2i + 2): for
each node with rows, class counts (bootstrap counts summed) over (feature of
its subset, bin); prefix sums over bins give the split `bin <= b` its left
and right counts; its gain is gini(node) - (n_l gini(left) + n_r gini(right))
/ n; valid where both sides hold `minInstancesPerNode` rows and b is not the
last bin; the node splits at the first largest valid gain (in (subset
position, bin) order) if it is over `minInfoGain`, with the threshold
edges[feature, b]; otherwise it is a leaf. Rows of a split node go left where
`bin <= b`. Depth `maxDepth`: the nodes of the last level are leaves. A node
no row reached reports its parent's counts (`_fill`).

The reference is numpy on the host (only the edges' bins are taken over the
row blocks on the device, by its own `searchsorted`). It imports nothing of
the program; `_priced_at_admission` only asks the program's admission whether
it is one that can run the configuration at all. Exact agreement of two free-running forests
breaks at the first tie in gain and cascades down a subtree, so `compare_fit`
FOLLOWS the program's tree and re-derives every node of it: for the rows (with
their bootstrap counts) that the program's own splits route to a node, its own
counts and its own best split.

Departures from the protocol's two solvers, each by design of the row:
- Spark's `RandomForest.findSplits` takes its candidate thresholds from a
  sample of `max(maxBins^2, 10000)` rows with `approxQuantile`; cuML's builder
  from per-column quantiles of its rows. Here: exact quantiles of a fixed
  100,000-row sample.
- Spark and cuML draw the feature subset of a node from one stream a tree in
  node order; here a level's subsets come from one draw a level.
- Spark bins a continuous feature so that `x <= threshold` goes left, as here;
  cuML splits on `x <= quantile` too. The last bin never splits (Spark's too).
- Spark's bootstrap is a Poisson(subsamplingRate) count a row; here (and in
  cuML) exactly n draws with replacement.
- The ensemble is split over the devices as the reference splits it over its
  workers (`_estimators_per_worker`): a device grows its trees on ITS rows.
"""
from __future__ import annotations

import math
import sys
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

SEEDED = True  # the answer depends on the estimator seed (bootstrap and feature subsets)
SKETCH_ROWS = 100_000
SKETCH_STREAM = 0


# ------------------------------------------------------------- the program ---


def estimator(config: dict, seed: int, overrides: dict | None = None):
    from spark_rapids_ml_tpu.models.classification import RandomForestClassifier

    est = RandomForestClassifier(**config["estimator"], seed=int(seed), num_workers=int(config["num_workers"]),
                                 **(overrides or {}))
    _priced_at_admission(est, config)
    return est.setFeaturesCol("features").setLabelCol("label")


def _priced_at_admission(est, config: dict) -> None:
    """The configuration names a fit that is admitted `resident` with its
    binned X priced, because the bins stay with the placement. A program
    whose forest estimator prices no binned X (the parent of PR 36: a sketch
    and a binning pass in every fit, 39 passes over the rows a tree; it did
    not return from one fit at this shape in ten minutes) is not that
    program, and the run ends here, before anything is placed, with an exit
    code of its own. Asked of the program's admission (`memory.
    resident_estimate`, as `assert_path` asks the verdict), not of a name
    inside its solver."""
    from spark_rapids_ml_tpu import memory

    class Shape:  # what admission reads of the rows
        n_rows, n_cols, is_sparse, label, weight = int(config["rows"]), int(config["d"]), False, np.zeros(1), None

    if not memory.resident_estimate(est, Shape, int(config["num_workers"])).terms.get("workspace.binned_X"):
        raise SystemExit("chipbench.families.rfc: this program's forest fit prices no binned X at admission "
                         "(no workspace.binned_X term); the rfc-p3k configuration cannot run on it")


def _span(model, path: str) -> Dict[str, Any]:
    spans = (getattr(model, "_fit_metrics", None) or {}).get("spans", [])
    return next((s for s in spans if s["path"] == path), {})


def outputs(model) -> Dict[str, Any]:
    grow = _span(model, "fit/solve/grow")
    return {
        "feature": np.asarray(model.feature, np.int64),
        "threshold": np.asarray(model.threshold, np.float64),
        "node_stats": np.asarray(model.node_stats, np.float64),
        "classes": np.asarray(model.classes_, np.float64),
        "said": {k: grow.get(k) for k in ("trees", "depth", "bins", "features_per_node", "passes_per_tree", "accumulate")},
    }


def iterations(out: Dict[str, Any]) -> int:
    """Levels grown a fit: trees x depth."""
    trees, nodes = out["feature"].shape
    return int(trees * (int(math.log2(nodes + 1)) - 1))


def before_fit(rehearse: bool) -> None:
    pass


def assert_path(model) -> None:
    """Admitted resident, and a fit inside the scope after the first bins
    nothing: the edges and the uint8 X are the placement's. (The set-up's cold
    fit bins once: `forest.bin_passes` 1 and `reused` false there.)"""
    metrics = getattr(model, "_fit_metrics", None) or {}
    adm = metrics.get("admission")
    if adm is None or adm.get("verdict") != "resident":
        raise RuntimeError(f"forest fit was not admitted resident: admission={adm}")
    binned = metrics.get("counters", {}).get("forest.bin_passes", 0)
    reuses = metrics.get("counters", {}).get("fit.device_dataset_reuses", 0)
    bin_span = _span(model, "fit/solve/bin")
    if "reused" not in bin_span or _span(model, "fit/solve/grow").get("trees") != model.num_trees:
        raise RuntimeError(f"forest fit shows no fit/solve/bin and fit/solve/grow spans of its own: {bin_span}")
    if reuses and (binned or not bin_span["reused"]):
        raise RuntimeError(f"forest refit on a kept placement binned X again: forest.bin_passes={binned}, bin span {bin_span}")


# ------------------------------------------------------------ work counts ---


def features_per_node(config: dict) -> int:
    return max(1, int(math.sqrt(int(config["d"]))))  # featureSubsetStrategy "auto", classification


def hist_bytes(config: dict, levels: Optional[float] = None) -> float:
    """What the accumulate has to read, whatever implements it: a tree's level
    reads, for each row, its m bin ids (a byte each), its node id (4), its S
    class statistics (4 each) and its flag (1), once. `levels`: levels grown
    a fit (trees x depth by default)."""
    est = config["estimator"]
    if levels is None:
        levels = int(est["numTrees"]) * int(est["maxDepth"])
    S = int(config["classes"])
    return float(levels) * int(config["rows"]) * (features_per_node(config) + 4 + 4 * S + 1)


def fit_work(config: dict, n_iter: float) -> Dict[str, float]:
    """What one fit needs, over all chips: the accumulate's reads (`n_iter`
    levels); its sums are one add a (row, feature, class) cell, counted as the
    FLOP they are (no multiply is needed). Memory-bound by its count."""
    S = int(config["classes"])
    return {"flops": float(n_iter) * int(config["rows"]) * features_per_node(config) * S,
            "bytes": hist_bytes(config, n_iter)}


# ------------------------------------------------------------------ draws ---


def _seed32(seed: int):
    return np.uint32(int(seed) & 0xFFFFFFFF)


def tree_key(seed: int, tree: int):
    return jax.random.fold_in(jax.random.PRNGKey(_seed32(seed)), tree)


def bootstrap_counts(seed: int, tree: int, n: int) -> np.ndarray:
    """How often each of the n placed rows was drawn for tree `tree`."""
    k1, _ = jax.random.split(tree_key(seed, tree))
    return np.bincount(np.asarray(jax.random.randint(k1, (n,), 0, n, dtype=jnp.int32)), minlength=n).astype(np.int64)


def node_features(seed: int, tree: int, level: int, d: int, m: int) -> np.ndarray:
    """The feature subsets of the 2^level nodes of a level: [2^level, m]."""
    if m >= d:
        return np.broadcast_to(np.arange(d), (2**level, d))
    u = np.asarray(jax.random.uniform(jax.random.fold_in(tree_key(seed, tree), 7919 + level), (2**level, d), dtype=jnp.float32))
    return np.argsort(u, axis=1, kind="stable")[:, :m]


# ------------------------------------------------------- edges and bins ---


def sketch_rows(n: int) -> np.ndarray:
    if n <= SKETCH_ROWS:
        return np.arange(n)
    return np.sort(np.random.default_rng(SKETCH_STREAM).choice(n, SKETCH_ROWS, replace=False))


def quantile_edges(X: np.ndarray, bins: int) -> np.ndarray:
    """[d, bins - 1] float64. The columns are sorted first (float32, exact) so
    that `np.quantile` finds its ranks in order: the values are those of
    `np.quantile(X[sample].astype(float64), qs, axis=0)`."""
    sample = np.sort(np.ascontiguousarray(X[sketch_rows(X.shape[0])].T), axis=1)
    qs = np.linspace(0, 1, bins + 1)[1:-1]
    pieces = [sample[lo : lo + 64] for lo in range(0, sample.shape[0], 64)]
    return np.concatenate(_threads(lambda piece: np.quantile(piece.astype(np.float64), qs, axis=1).T, pieces))


def _note(what: str, t0: float) -> None:
    """Where the comparison's seconds go, on standard error beside the run's own notes."""
    print(f"    rfc reference: {what} {time.perf_counter() - t0:.2f}s", file=sys.stderr, flush=True)


def _threads(fn, items: Sequence[Any]) -> List[Any]:
    """fn over the items on a few threads (numpy lets go of the interpreter in its loops), in order."""
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=min(8, max(1, len(items)))) as pool:
        return list(pool.map(fn, items))


@jax.jit
def _bin_block(xb, e32):
    """`searchsorted(e32[f], x[:, f], "left")`: how many of a column's edges lie below x."""
    return jnp.sum(e32[None, :, :] < xb[:, :, None], axis=2, dtype=jnp.int32).astype(jnp.uint8)


def bin_rows(blocks: Sequence[Any], edges: np.ndarray, piece: int = 8192) -> np.ndarray:
    """uint8 bins of the row blocks, a piece at a time on the block's device."""
    e32 = edges.astype(np.float32)
    out, placed = [], {}
    for xb in blocks:
        dev = list(xb.devices())[0]
        if dev not in placed:
            placed[dev] = jax.device_put(e32, dev)
        for lo in range(0, xb.shape[0], piece):
            out.append(np.asarray(_bin_block(xb[lo : lo + piece], placed[dev])))
    return np.concatenate(out)


# ----------------------------------------------------------- the growth ---


def _bf16(a: np.ndarray) -> np.ndarray:
    import ml_dtypes

    return a.astype(ml_dtypes.bfloat16).astype(np.float64)


def level_counts(Xb, cls, cnt, rows, local, fids, bins: int, S: int) -> np.ndarray:
    """Class counts [C, m, bins, S] (float64, integers) of the rows `rows`
    (each at node `local` of the level, counted `cnt` times) over their node's
    feature subset."""
    C, m = fids.shape
    sub = Xb[rows[:, None], fids[local]]  # [r, m] the row's bins at ITS node's features
    idx = ((local[:, None] * m + np.arange(m)[None, :]) * bins + sub) * S + cls[rows][:, None]
    h = np.bincount(idx.ravel(), weights=np.repeat(cnt[rows].astype(np.float64), m), minlength=C * m * bins * S)
    return h.astype(np.float64).reshape(C, m, bins, S)  # (numpy counts no rows as integers, weights or not)


def _exact(a: np.ndarray) -> np.ndarray:
    return a


def _gini(counts: np.ndarray, rnd=_exact):
    n = counts.sum(axis=-1)
    p = rnd(np.divide(counts, n[..., None], out=np.zeros_like(counts), where=n[..., None] > 0))
    return rnd(1.0 - rnd(rnd(p * p).sum(axis=-1))), n


NODE_PIECE = 64  # nodes whose histograms (7 MB at 54 x 128 x 2) and their temporaries stay in a cache


def split_gains(h: np.ndarray, min_instances: float, rnd=_exact):
    """gain [C, m, bins] (-inf where the split is not valid), the nodes' class
    counts [C, S] and impurity [C]; the nodes a piece at a time. `rnd` rounds
    every step of the gini arithmetic (the control's lower precision)."""
    parts = [_split_gains(h[lo : lo + NODE_PIECE], min_instances, rnd) for lo in range(0, h.shape[0], NODE_PIECE)]
    return tuple(np.concatenate([p[i] for p in parts]) for i in range(3))


def _split_gains(h: np.ndarray, min_instances: float, rnd):
    left = np.cumsum(h, axis=2)
    total = left[:, 0, -1, :]
    right = total[:, None, None, :] - left
    imp_l, n_l = _gini(left, rnd)
    imp_r, n_r = _gini(right, rnd)
    imp, n = _gini(total, rnd)
    children = rnd(rnd(rnd(n_l * imp_l) + rnd(n_r * imp_r)) / np.maximum(n, 1.0)[:, None, None])
    gain = rnd(imp[:, None, None] - children)
    valid = (n_l >= min_instances) & (n_r >= min_instances)
    valid[:, :, -1] = False  # the last bin means "everything left"
    return np.where(valid, gain, -np.inf), total, imp


def _fill(feature: np.ndarray, stats: np.ndarray) -> np.ndarray:
    """A node no row reached reports its parent's counts."""
    out = stats.copy()
    for i in range(1, out.shape[0]):
        if out[i].sum() == 0:
            out[i] = out[(i - 1) // 2]
    return out


def grow_tree(Xb, cls, cnt, edges, seed: int, tree: int, *, depth: int, levels: int, m: int, bins: int, S: int,
              min_instances: float = 1.0, min_info_gain: float = 0.0, take: Optional[int] = None,
              halve_bins: bool = False, round_hist=None, stale_counts: bool = False) -> Dict[str, np.ndarray]:
    """One tree free-running from the stated draws: arrays of a depth-`depth`
    layout of which `levels` levels are grown (the rest leaves). The hooks
    plant faults: `take` features of each subset, `halve_bins` (bins merged in
    pairs), `round_hist` (a lower precision: the sums and every step of the gini
    arithmetic rounded), `stale_counts` (a level's
    node counts are those of the level above)."""
    n, d = Xb.shape
    M = 2 ** (depth + 1) - 1
    feature, threshold, stats = np.full(M, -1, np.int64), np.full(M, np.inf), np.zeros((M, S))
    node, rows = np.zeros(n, np.int64), np.flatnonzero(cnt > 0)
    if halve_bins:
        Xb, edges, bins = Xb // 2, edges[:, 1::2], bins // 2
    for level in range(levels):
        C, off = 2**level, 2**level - 1
        fids = node_features(seed, tree, level, d, m)[:, : (take or m)]
        h = level_counts(Xb, cls, cnt, rows, node[rows] - off, fids, bins, S)
        if round_hist is not None:
            h = round_hist(h)
        gain, total, _ = split_gains(h, min_instances, round_hist or _exact)
        stats[off : off + C] = stats[(np.arange(off, off + C) - 1) // 2] if stale_counts and level else total
        flat = gain.reshape(C, -1)
        best = flat.argmax(axis=1)
        split = flat[np.arange(C), best] > min_info_gain
        f, b = fids[np.arange(C), best // bins], best % bins
        feature[off : off + C] = np.where(split, f, -1)
        threshold[off : off + C] = np.where(split, edges[f, np.minimum(b, edges.shape[1] - 1)], np.inf)
        at = node[rows] - off
        rows = rows[split[at]]  # rows of leaves stay where they are
        at = node[rows] - off
        node[rows] = 2 * node[rows] + np.where(Xb[rows, f[at]] <= b[at], 1, 2)
    C, off = 2**levels, 2**levels - 1
    last = np.bincount((node[rows] - off) * S + cls[rows], weights=cnt[rows].astype(np.float64), minlength=C * S).astype(np.float64).reshape(C, S)
    stats[off : off + C] = round_hist(last) if round_hist is not None else last
    return {"feature": feature, "threshold": threshold, "node_stats": _fill(feature, stats)}


def _stack(trees: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    return {k: np.stack([t[k] for t in trees]) for k in ("feature", "threshold", "node_stats")}


def accuracy(forest: Dict[str, np.ndarray], X: np.ndarray, cls: np.ndarray, depth: int) -> float:
    """Share of the rows whose class the forest's first `depth` levels name:
    the mean over trees of the class shares at the node a row reaches, the
    first largest. The reference's own traversal: `x <= float32(threshold)`
    goes left, as the bins do."""
    n = X.shape[0]
    thr32 = forest["threshold"].astype(np.float32)
    votes = np.zeros((n, forest["node_stats"].shape[2]))
    every = np.arange(n)
    for t in range(forest["feature"].shape[0]):
        node = np.zeros(n, np.int64)
        for _ in range(depth):
            f = forest["feature"][t, node]
            child = 2 * node + np.where(X[every, np.maximum(f, 0)] <= thr32[t, node], 1, 2)
            node = np.where(f >= 0, child, node)
        s = forest["node_stats"][t, node]
        votes += s / np.maximum(s.sum(axis=1), 1e-300)[:, None]
    return float(np.mean(votes.argmax(axis=1) == cls))


# -------------------------------------------------------------- reference ---


def _sizes(config: dict, d: int):
    est = config["estimator"]
    workers = int(config["num_workers"])
    return {"depth": int(est["maxDepth"]), "bins": int(est["maxBins"]), "trees": int(est["numTrees"]),
            "workers": workers, "trees_per_worker": -(-int(est["numTrees"]) // workers),
            "m": max(1, int(math.sqrt(d))), "S": int(config["classes"]),
            "min_instances": float(est.get("minInstancesPerNode", 1)), "min_info_gain": float(est.get("minInfoGain", 0.0))}


def prepared(config: dict, data, blocks: Sequence[Any]) -> Dict[str, Any]:
    """What does not depend on the estimator seed, made once a dataset: the
    reference's edges, its bins and the rows' classes."""
    kept = getattr(data, "_rfc_prepared", None)
    bins, n = int(config["estimator"]["maxBins"]), len(blocks) * blocks[0].shape[0]
    if kept is None or kept["bins"] != bins or kept["Xb"].shape[0] != n:
        X = data.X[:n]
        t0 = time.perf_counter()
        edges = quantile_edges(X, bins)
        _note("edges", t0)
        t0 = time.perf_counter()
        classes = np.unique(data.y[:n])
        kept = {"bins": bins, "edges": edges, "Xb": bin_rows(blocks, edges), "X": X,
                "classes": classes, "cls": np.searchsorted(classes, data.y[:n]).astype(np.int64)}
        _note("bins", t0)
        data._rfc_prepared = kept
    return kept


def checked_trees(config: dict, data, seed: int) -> List[int]:
    """The trees whose every node is re-derived, drawn from the run's seed."""
    trees = int(config["estimator"]["numTrees"])
    rng = np.random.default_rng([int(data.seed), int(seed)])
    return sorted(rng.choice(trees, min(int(config["check"]["trees"]), trees), replace=False).tolist())


def reference_fit(config: dict, data, blocks: Sequence[Any], seed: int = 0) -> Dict[str, Any]:
    """The reference's own forest free-running from the stated draws, to the
    depth `check.accuracy_depth` (every tree), with its accuracy on the rows;
    and what `compare_fit` needs to follow the program's tree."""
    prep = prepared(config, data, blocks)
    sz = _sizes(config, prep["Xb"].shape[1])
    shallow = min(int(config["check"]["accuracy_depth"]), sz["depth"])
    n = prep["Xb"].shape[0]
    counts = {}
    for t in range(sz["trees"]):
        key, lo, hi = tree_place(sz, t, n)
        counts[t] = bootstrap_counts(seed, key, hi - lo)
    t0 = time.perf_counter()
    free = _stack(_threads(lambda t: _grow(prep, sz, counts[t], seed, t, levels=shallow), range(sz["trees"])))
    ref = {"prep": prep, "sizes": sz, "seed": int(seed), "counts": counts, "free": free, "shallow": shallow,
           "accuracy_free": accuracy(free, prep["X"], prep["cls"], shallow), "check": checked_trees(config, data, seed)}
    _note(f"its own forest to depth {shallow}", t0)
    return ref


def tree_place(sz: Dict[str, Any], t: int, n: int):
    """Tree t of the returned forest (round-major: round t // workers of
    worker t % workers): its key's tree number and its worker's rows."""
    worker, rnd = t % sz["workers"], t // sz["workers"]
    per = n // sz["workers"]
    return worker * sz["trees_per_worker"] + rnd, worker * per, (worker + 1) * per


def _grow(prep, sz, cnt, seed: int, t: int, levels: int, **hooks) -> Dict[str, np.ndarray]:
    key, lo, hi = tree_place(sz, t, prep["Xb"].shape[0])
    return grow_tree(prep["Xb"][lo:hi], prep["cls"][lo:hi], cnt, prep["edges"], seed, key, depth=sz["depth"], levels=levels,
                     m=sz["m"], bins=sz["bins"], S=sz["S"], min_instances=sz["min_instances"],
                     min_info_gain=sz["min_info_gain"], **hooks)


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
    said = {"trees": sz["trees"], "depth": sz["depth"], "bins": sz["bins"], "features_per_node": sz["m"]}
    return {**_stack(trees), "classes": prep["classes"], "said": said}


def control_fit(run, blocks: Sequence[Any], seed: int) -> Dict[str, Any]:
    """The program has no lower precision of its own, so the reference with
    one is put in its place: every histogram's sums rounded to bfloat16 (counts
    over 256 round) and the gini arithmetic on them in bfloat16, every step
    rounded (a gain is the difference of two near-equal numbers of eight bits:
    near-equal splits tie, and the first of them wins)."""
    return _planted(run.config, run.data, blocks, seed, round_hist=_bf16)


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
        "half_the_bins": _planted(config, data, blocks, seed, halve_bins=True),
        "no_bootstrap": _planted(config, data, blocks, seed,
                                 counts={t: np.ones(n // sz["workers"], np.int64) for t in range(sz["trees"])}),
        "counts_from_the_level_above": _planted(config, data, blocks, seed, stale_counts=True),
    }


NUMBERS = ("counts_gap", "gain_gap", "threshold_gap", "shape_gap", "accuracy_gap")


def follow_tree(ref: Dict[str, Any], out: Dict[str, Any], t: int) -> Dict[str, float]:
    """Every node of the program's tree t re-derived: the counts of the rows
    its own splits route there, and the best split of the node's subset."""
    prep, sz = ref["prep"], ref["sizes"]
    key, lo, hi = tree_place(sz, t, prep["Xb"].shape[0])
    Xb, cls, edges, cnt = prep["Xb"][lo:hi], prep["cls"][lo:hi], prep["edges"], ref["counts"][t]
    depth, m, bins, S = sz["depth"], sz["m"], sz["bins"], sz["S"]
    feature, threshold = out["feature"][t], out["threshold"][t]
    stats = np.zeros((feature.shape[0], S))
    node, rows = np.zeros(Xb.shape[0], np.int64), np.flatnonzero(cnt > 0)
    gain_gap = threshold_gap = 0.0
    for level in range(depth):
        C, off = 2**level, 2**level - 1
        fids = node_features(ref["seed"], key, level, Xb.shape[1], m)
        gain, total, imp = split_gains(level_counts(Xb, cls, cnt, rows, node[rows] - off, fids, bins, S), sz["min_instances"])
        stats[off : off + C] = total
        f = feature[off : off + C]
        split, held = f >= 0, total.sum(axis=1) > 0
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
        node[rows] = 2 * node[rows] + np.where(Xb[rows, np.maximum(f, 0)[at]] <= b[at], 1, 2)
    C, off = 2**depth, 2**depth - 1
    stats[off : off + C] = np.bincount((node[rows] - off) * S + cls[rows], weights=cnt[rows].astype(np.float64), minlength=C * S).reshape(C, S)
    counts_gap = float(np.max(np.abs(_fill(feature, stats) - out["node_stats"][t])))
    return {"counts_gap": counts_gap, "gain_gap": gain_gap, "threshold_gap": threshold_gap}


def levels_grown(out: Dict[str, Any]) -> int:
    """The deepest level at which some node of some tree holds rows below a split."""
    nodes = out["feature"].shape[1]
    parent_split = out["feature"][:, (np.arange(1, nodes) - 1) // 2] >= 0
    reached = np.flatnonzero((parent_split & (out["node_stats"][:, 1:].sum(axis=2) > 0)).any(axis=0)) + 1
    return int(math.floor(math.log2(reached.max() + 1))) if reached.size else 0


def compare_fit(config: dict, out: Dict[str, Any], ref: Dict[str, Any], data, blocks: Sequence[Any]) -> Dict[str, float]:
    """The numbers a fit is judged by (each is 0 for a perfect answer; an
    answer that is not a number makes every one NaN, which no limit admits)."""
    sz = ref["sizes"]
    nodes = 2 ** (sz["depth"] + 1) - 1
    if out["feature"].ndim != 2 or out["feature"].shape[1] != nodes or out["node_stats"].shape[2] != sz["S"]:
        return {**{name: float("nan") for name in NUMBERS}, "shape_gap": float(abs(out["feature"].shape[-1] - nodes) + 1)}
    if not np.isfinite(out["node_stats"]).all() or np.isnan(out["threshold"]).any():
        return {name: float("nan") for name in NUMBERS}
    read = {"counts_gap": 0.0, "gain_gap": 0.0, "threshold_gap": 0.0}
    t0 = time.perf_counter()
    for followed in _threads(lambda t: follow_tree(ref, out, t), [t for t in ref["check"] if t < out["feature"].shape[0]]):
        for k, v in followed.items():
            read[k] = max(read[k], v)
    _note(f"trees {ref['check']} followed", t0)
    t0 = time.perf_counter()
    said = out["said"]
    read["shape_gap"] = float(max(
        abs(out["feature"].shape[0] - sz["trees"]), abs(levels_grown(out) - sz["depth"]),
        abs((said.get("trees") or 0) - sz["trees"]), abs((said.get("depth") or 0) - sz["depth"]),
        abs((said.get("features_per_node") or 0) - sz["m"]), abs((said.get("bins") or 0) - sz["bins"]),
    ))
    prep = ref["prep"]
    forest = {k: out[k] for k in ("feature", "threshold", "node_stats")}
    # the protocol's quality score on the training rows: the forest cut at the reference's depth against the
    # reference's own forest there (the same forest but for ties), and the whole forest no worse than that
    shallow = accuracy(forest, prep["X"], prep["cls"], ref["shallow"])
    whole = accuracy(forest, prep["X"], prep["cls"], sz["depth"])
    read["accuracy_gap"] = max(abs(shallow - ref["accuracy_free"]), ref["accuracy_free"] - whole)
    _note("accuracies", t0)
    return read
