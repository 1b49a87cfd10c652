#
# Shared random-forest machinery (reference tree.py, 636 LoC): params common to
# classifier/regressor, the ensemble-split fit orchestration, and the
# array-forest model base. Subclasses live in classification.py/regression.py,
# mirroring the reference layout.
#
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional

import numpy as np

from ..core import FitInputs, _TpuEstimatorSupervised, _TpuModelWithColumns
from ..data import ExtractedData
from ..params import (
    HasFeaturesCol,
    HasFeaturesCols,
    HasLabelCol,
    HasPredictionCol,
    HasSeed,
    HasWeightCol,
    Param,
    TypeConverters,
)


def resolve_max_features(strategy: str, d: int, is_classification: bool) -> int:
    """featureSubsetStrategy -> number of features per split (Spark semantics)."""
    s = str(strategy).lower()
    if s == "auto":
        return max(1, int(math.sqrt(d))) if is_classification else max(1, d // 3)
    if s == "all":
        return d
    if s == "sqrt":
        return max(1, int(math.sqrt(d)))
    if s == "log2":
        return max(1, int(math.log2(d)))
    if s == "onethird":
        return max(1, d // 3)
    import re

    # Spark's grammar: "^[1-9]\d*$" is a feature COUNT; "(0.0, 1.0]" decimals
    # are a fraction — so "1.0" means ALL features, "1" means one feature
    if re.fullmatch(r"[1-9]\d*", s):
        return min(d, int(s))
    try:
        v = float(s)
        if 0 < v <= 1:
            return max(1, int(v * d))
    except ValueError:
        pass
    raise ValueError(f"Unsupported featureSubsetStrategy: {strategy!r}")


class _RandomForestParams(
    HasFeaturesCol, HasFeaturesCols, HasLabelCol, HasPredictionCol, HasSeed, HasWeightCol
):
    numTrees = Param("numTrees", "number of trees in the forest", TypeConverters.toInt)
    maxDepth = Param("maxDepth", "maximum tree depth", TypeConverters.toInt)
    maxBins = Param("maxBins", "maximum number of feature histogram bins", TypeConverters.toInt)
    minInstancesPerNode = Param(
        "minInstancesPerNode", "minimum number of instances each child must have", TypeConverters.toInt
    )
    minInfoGain = Param("minInfoGain", "minimum information gain for a split", TypeConverters.toFloat)
    featureSubsetStrategy = Param(
        "featureSubsetStrategy",
        "number of features per split: auto|all|sqrt|log2|onethird|n|fraction",
        TypeConverters.toString,
    )
    subsamplingRate = Param("subsamplingRate", "fraction of rows sampled per tree", TypeConverters.toFloat)
    bootstrap = Param("bootstrap", "whether bootstrap samples are used", TypeConverters.toBoolean)
    impurity = Param("impurity", "split criterion", TypeConverters.toString)
    # accepted-and-ignored Spark knobs (reference maps these to "" the same way)
    checkpointInterval = Param("checkpointInterval", "ignored", TypeConverters.toInt)
    cacheNodeIds = Param("cacheNodeIds", "ignored", TypeConverters.toBoolean)
    maxMemoryInMB = Param("maxMemoryInMB", "ignored", TypeConverters.toInt)

    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        # mirrors reference tree.py param mapping
        return {
            "numTrees": "n_estimators",
            "maxDepth": "max_depth",
            "maxBins": "n_bins",
            "minInstancesPerNode": "min_samples_leaf",
            "minInfoGain": "min_impurity_decrease",
            "featureSubsetStrategy": "max_features",
            "subsamplingRate": "max_samples",
            "bootstrap": "bootstrap",
            "impurity": "split_criterion",
            "seed": "random_state",
            "checkpointInterval": "",
            "cacheNodeIds": "",
            "maxMemoryInMB": "",
            "weightCol": "",
        }

    def _get_solver_params_default(self) -> Dict[str, Any]:
        return {
            "n_estimators": 20,
            "max_depth": 5,
            "n_bins": 32,
            "min_samples_leaf": 1,
            "min_impurity_decrease": 0.0,
            "max_features": "auto",
            "max_samples": 1.0,
            "bootstrap": True,
            "split_criterion": None,  # set by subclass default
            "random_state": 0,
            "node_chunk": 0,  # nodes a histogram pass: 0 = as many as ops.trees.SEGMENT_BUDGET holds
            "verbose": False,
        }

    def getNumTrees(self) -> int:
        return self.getOrDefault("numTrees")

    def getMaxDepth(self) -> int:
        return self.getOrDefault("maxDepth")


class _RandomForestEstimator(_RandomForestParams, _TpuEstimatorSupervised):
    """Shared fit orchestration (reference tree.py:240-431)."""

    _is_classification: bool = False
    # ensemble-split growth is per-device-local by design; the host-side state
    # (class set, quantile bin edges) is rendezvous-merged in _get_tpu_fit_func.
    # Like the reference's cuRF, the exact trees depend on the partition layout
    # (bootstrap draws are keyed per device) — parity across rank counts is
    # statistical, not bitwise.
    _supports_multiprocess = True

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._setDefault(
            numTrees=20, maxDepth=5, maxBins=32, minInstancesPerNode=1, minInfoGain=0.0,
            featureSubsetStrategy="auto", subsamplingRate=1.0, bootstrap=True, seed=0,
        )
        self._set_params(**kwargs)

    # common setters (each subclass also exposes them through this base)
    def setNumTrees(self, value: int):
        return self._set_params(numTrees=value)

    def setMaxDepth(self, value: int):
        return self._set_params(maxDepth=value)

    def setMaxBins(self, value: int):
        return self._set_params(maxBins=value)

    def setFeatureSubsetStrategy(self, value: str):
        return self._set_params(featureSubsetStrategy=value)

    def setImpurity(self, value: str):
        return self._set_params(impurity=value)

    def setSeed(self, value: int):
        return self._set_params(seed=value)

    def setFeaturesCol(self, value):
        return self._set_params(featuresCol=value) if isinstance(value, str) else self._set_params(featuresCols=value)

    def setLabelCol(self, value: str):
        return self._set_params(labelCol=value)

    def setPredictionCol(self, value: str):
        return self._set_params(predictionCol=value)

    def _row_stats(self, labels: np.ndarray, classes: np.ndarray) -> np.ndarray:
        """Per-row stat contributions: class one-hot (clf) or (1, y, y²) (reg)."""
        if self._is_classification:
            idx = np.searchsorted(classes, labels)
            stats = np.zeros((len(labels), len(classes)), np.float32)
            stats[np.arange(len(labels)), idx] = 1.0
            return stats
        y = labels.astype(np.float64)
        return np.stack([np.ones_like(y), y, y * y], axis=1).astype(np.float32)

    def _solver_workspace_terms(
        self, rows_per_device: int, n_cols: int, params: Dict[str, Any], itemsize: int
    ) -> Dict[str, int]:
        # what a forest fit holds beside the placement (tests/test_memory.py
        # pins each): the binned X kept with the placement; the searchsorted
        # temporaries of one binning tile (five full-shape 4-byte arrays);
        # the deepest pass's histogram with its prefix sums, right-hand
        # statistics and gains (four arrays of its size); a row's node id,
        # flag, statistics and bootstrapped statistics. A classifier is
        # priced at two classes: the labels are not read before admission.
        # A regressor's float32 statistics in pieces (`onehot_split`) add the
        # deepest pass's picked bin ids (bfloat16, feature-major), the rows'
        # statistic pieces, and the kernel's blocks of piece sums twice: as
        # the kernel writes them and rearranged to [piece, node, feature, bin].
        from ..ops import histogram
        from ..ops.trees import BIN_TILE_CELLS, STAT_PIECES, binned_cols, level_plan, plan_summary

        bins, S = int(params["n_bins"]), (2 if self._is_classification else 3)
        m = resolve_max_features(params["max_features"], n_cols, self._is_classification)
        integer = self._is_classification and not self.isSet("weightCol")  # as `_fit` decides it from the rows
        plan = level_plan(int(params["max_depth"]), m, bins, S, int(params["node_chunk"]), integer_stats=integer,
                          split_stats=itemsize == 4)
        summary = plan_summary(plan)
        tile_rows = min(rows_per_device, max(1024, BIN_TILE_CELLS // max(n_cols, 1)))
        terms = {
            "binned_X": rows_per_device * binned_cols(n_cols) * (1 if bins <= 256 else 4),
            "bin_tile": 5 * tile_rows * n_cols * 4,
            "histogram": 4 * S * summary["deepest_chunk"] * m * bins * 4,
            "row_state": rows_per_device * (4 + 1 + 2 * S * itemsize),
        }
        if summary["split_passes"]:
            P, m_pad = STAT_PIECES * S, histogram.split_features(m)
            groups = -(-summary["deepest_chunk"] // histogram._group_nodes(P))
            terms["split_accumulate"] = (rows_per_device * (2 * m_pad + 2 * P * 4)
                                         + 2 * groups * m_pad * histogram._bin_lanes(bins) * 128 * 4)
        return terms

    def _placement_bins(self, inputs: FitInputs, extracted: ExtractedData, max_bins: int) -> Dict[str, Any]:
        """What a forest fit needs of the placement and nothing of the fit's
        own parameters but `maxBins`: the bin edges and the binned X (of the
        features and `maxBins`), the class set and the per-row statistics (of
        the labels and the kind of forest). MEMOIZED on `inputs.extra`, the
        bins under a key that holds `maxBins`, the label's part under one
        that holds the kind: a classifier and a regressor fitted on one
        placement share the bins and not the statistics (as `FitInputs.ell_rows`
        keeps the ELL tensors): inside a `device_dataset_scope` the second
        fit builds none of them, fold masks (`with_row_mask` shares `extra`)
        share them, and leaving the scope frees them with X. The sketch's
        sample comes from a fixed stream (`ops.trees.sketch_rows`), never the
        estimator seed."""
        from .. import telemetry
        from ..ops.trees import SKETCH_ROWS, bin_features, quantile_bins, sketch_rows

        rows_key = ("_forest_rows", "classes" if self._is_classification else "moments")
        rows = inputs.extra.get(rows_key)
        if rows is None:
            labels_host = extracted.label
            if self._is_classification:
                # class set must be GLOBAL (a rank may hold a label subset)
                import json

                local_classes = np.unique(labels_host).astype(np.float64)
                gathered = inputs.allgather_host(json.dumps(local_classes.tolist()))
                classes = np.unique(np.concatenate([np.asarray(json.loads(g)) for g in gathered]))
            else:
                classes = np.zeros(0)
            rows = {"classes": classes, "stats": inputs.put_rows(self._row_stats(labels_host, classes))}
            inputs.extra[rows_key] = rows
        bins_key = ("_forest_bins", int(max_bins))
        kept = inputs.extra.get(bins_key)
        if kept is not None:
            return {**kept, **rows, "reused": True}
        x_host = extracted.features
        # quantile sketch rows must be GLOBAL too: each rank contributes a
        # bounded sample, all ranks derive IDENTICAL bin edges from the
        # union (cuRF's distributed quantile computation analog)
        x_sketch = x_host
        if inputs.ctx is not None and inputs.ctx.is_spmd:
            sel = sketch_rows(x_host.shape[0], SKETCH_ROWS // inputs.ctx.nranks, rank=inputs.ctx.rank)
            x_sketch = inputs.allgather_array(np.asarray(x_host[sel], dtype=np.float64))
        edges_host = quantile_bins(x_sketch, max_bins)
        # bin the ALREADY device-resident features (inputs.X carries the
        # padding; its rows are zero-weighted in inputs.w)
        import jax

        binned = bin_features(inputs.X, edges_host.astype(np.float32))
        with telemetry.device_wait("bin"):
            kept = {"edges": edges_host, "Xb": jax.block_until_ready(binned)}
        inputs.extra[bins_key] = kept
        telemetry.registry().inc("forest.bin_passes")
        return {**kept, **rows, "reused": False}

    def _get_tpu_fit_func(self, extracted: ExtractedData):
        from .. import telemetry
        from ..ops.trees import forest_fit, plan_summary, split_bins_to_thresholds

        def _fit(inputs: FitInputs, params: Dict[str, Any]) -> Dict[str, Any]:
            import jax

            d = inputs.n_cols
            max_bins = int(params["n_bins"])
            max_depth = int(params["max_depth"])
            n_trees = int(params["n_estimators"])
            m = resolve_max_features(params["max_features"], d, self._is_classification)
            # once-per-fit child spans of `fit/solve` (docs/observability.md):
            # `bin` (edges and the binned X, ready before it closes; `reused`
            # where the placement had them), `grow` (every tree, the last
            # level's program ready before it closes), `finish` (the one fetch
            # of the forest and the host's fill)
            with telemetry.span("bin", rows=int(inputs.X.shape[0]), d=d, bins=max_bins) as sp:
                kept = self._placement_bins(inputs, extracted, max_bins)
                sp.set(reused=kept["reused"])
            # user weights scale each row's histogram contribution and the
            # bootstrap draw inside forest_fit multiplies on top
            w = inputs.w
            with telemetry.span("grow", trees=n_trees, depth=max_depth, bins=max_bins, features_per_node=m) as sp:
                state = forest_fit(
                    kept["Xb"],
                    kept["stats"] * w[:, None],
                    w,
                    int(params["random_state"] or 0),
                    mesh=inputs.mesh,
                    n_features=d,
                    n_trees=n_trees,
                    max_depth=max_depth,
                    max_bins=max_bins,
                    max_features=m,
                    impurity=params["split_criterion"],
                    node_chunk=int(params["node_chunk"]),
                    bootstrap=bool(params["bootstrap"]),
                    subsample_rate=float(params["max_samples"]),
                    min_instances=float(params["min_samples_leaf"]),
                    min_info_gain=float(params["min_impurity_decrease"]),
                    # class counts times bootstrap counts are small integers
                    # unless rows carry weights of their own
                    integer_stats=self._is_classification and extracted.weight is None,
                )
                plan = state.pop("plan")
                with telemetry.device_wait("grow"):
                    jax.block_until_ready(state)
                summary = plan_summary(plan)
                grown = int(state["feature"].shape[0])  # whole rounds: trees_per_dev x devices
                sp.set(
                    passes_per_tree=summary["passes_per_tree"], accumulate=summary["accumulate"],
                    level_programs=(grown // inputs.mesh.devices.size) * len(plan), trees_grown=grown,
                    sorted_levels=summary["sorted_levels"], kernel_levels=summary["kernel_levels"],
                    stat_pieces=summary["stat_pieces"], advance=summary["advance"],
                )
                reg = telemetry.registry()
                reg.inc("forest.trees", grown)
                reg.inc("forest.levels", grown * len(plan))
                reg.inc("forest.row_passes", grown * summary["passes_per_tree"])
                # the passes `ops.histogram`'s kernels ran (0 where XLA's forms did: a CPU, over 256 bins)
                reg.inc("forest.kernel_passes", grown * summary["kernel_levels"])
                # the levels whose row advance read the row's bin id by a masked reduce over X, no per-row gather
                reg.inc("forest.masked_advances", grown * summary["masked_advances"])
                # float32 statistics in exact bfloat16 pieces, and the passes left to the scatter (float64, > 256 bins)
                reg.inc("forest.split_stat_passes", grown * summary["split_passes"])
                reg.inc("forest.scatter_passes", grown * summary["scatter_passes"])
            with telemetry.span("finish"):  # ONE fetch: the forest's three arrays
                with telemetry.device_wait("finish"):
                    out = jax.device_get(state)
                feature = np.asarray(out["feature"])[:n_trees]
                split_bin = np.asarray(out["split_bin"])[:n_trees]
                node_stats = np.asarray(out["node_stats"], dtype=np.float64)[:n_trees]
                threshold = split_bins_to_thresholds(feature, split_bin, kept["edges"])
                node_stats = _fill_empty_nodes(feature, node_stats)
            return {
                "feature": feature.astype(np.int32),
                "threshold": threshold,
                "node_stats": node_stats,
                "classes_": kept["classes"],
                "num_trees": n_trees,
                "max_depth": max_depth,
                "n_cols": d,
                "dtype": np.dtype(inputs.dtype).name,
            }

        return _fit


def _fill_empty_nodes(feature: np.ndarray, node_stats: np.ndarray) -> np.ndarray:
    """Propagate parent stats into empty nodes so predict-time rows landing in a
    training-empty branch fall back to the parent distribution. A level at a
    time (a node's parent lies on the level above, already filled): 0.12 s of
    a 7-tree depth-13 fit's 3.8 s went node by node (PERF.md, PR 36)."""
    T, M, S = node_stats.shape
    out = node_stats.copy()
    first = 1
    while first < M:
        nodes = np.arange(first, min(2 * first + 1, M))
        empty = out[:, nodes, :].sum(axis=2) == 0
        out[:, nodes, :] = np.where(empty[:, :, None], out[:, (nodes - 1) // 2, :], out[:, nodes, :])
        first = 2 * first + 1
    return out


class _RandomForestModel(_RandomForestParams, _TpuModelWithColumns):
    """Array-forest model base (reference tree.py:433-636)."""

    _is_classification: bool = False

    def __init__(
        self,
        feature: Optional[np.ndarray] = None,
        threshold: Optional[np.ndarray] = None,
        node_stats: Optional[np.ndarray] = None,
        classes_: Optional[np.ndarray] = None,
        num_trees: int = 0,
        max_depth: int = 0,
        n_cols: int = 0,
        dtype: str = "float32",
        **kwargs: Any,
    ) -> None:
        super().__init__(
            feature=feature, threshold=threshold, node_stats=node_stats, classes_=classes_,
            num_trees=num_trees, max_depth=max_depth, n_cols=n_cols, dtype=dtype,
        )
        self.feature = np.asarray(feature)
        self.threshold = np.asarray(threshold)
        self.node_stats = np.asarray(node_stats)
        self.classes_ = np.asarray(classes_)
        self.num_trees = int(num_trees)
        self.max_depth = int(max_depth)
        self.n_cols = int(n_cols)
        self.dtype = dtype

    @property
    def getNumTrees(self) -> int:  # Spark model exposes this as a property
        return self.num_trees

    @property
    def numFeatures(self) -> int:
        return self.n_cols

    @property
    def totalNumNodes(self) -> int:
        return int(np.sum(self.feature >= 0) * 2 + self.num_trees)

    def setFeaturesCol(self, value):
        return self._set_params(featuresCol=value) if isinstance(value, str) else self._set_params(featuresCols=value)

    def setPredictionCol(self, value: str):
        return self._set_params(predictionCol=value)

    def _leaf_values(self) -> np.ndarray:
        """Per-node output values fed to the traversal (subclass defines)."""
        raise NotImplementedError

    # -- Spark-interop surface (reference tree.py:524-569, utils.py:311-481:
    # featureImportances, per-tree JSON, debug dump) ------------------------

    def _node_impurity_weight(self, stats: np.ndarray):
        """(impurity [..., M], weight [..., M]) from node stats.

        Classification stats are per-class counts (gini/entropy from the
        distribution); regression stats are (n, Σy, Σy²) (variance)."""
        if self._is_classification:
            tot = stats.sum(axis=-1)
            p = stats / np.maximum(tot[..., None], 1e-30)
            if str(self._solver_params.get("split_criterion")) == "entropy":
                with np.errstate(divide="ignore", invalid="ignore"):
                    plogp = np.where(p > 0, p * np.log2(np.maximum(p, 1e-30)), 0.0)
                imp = -plogp.sum(axis=-1)
            else:  # gini
                imp = 1.0 - (p * p).sum(axis=-1)
            return imp, tot
        n = stats[..., 0]
        mean = stats[..., 1] / np.maximum(n, 1e-30)
        var = stats[..., 2] / np.maximum(n, 1e-30) - mean * mean
        return np.maximum(var, 0.0), n

    @property
    def featureImportances(self):
        """Impurity-gain feature importances, Spark semantics: per-node gain
        = w·imp − w_l·imp_l − w_r·imp_r accumulated by split feature,
        normalized per tree, averaged over trees, normalized again."""
        from ..linalg import DenseVector

        T, M = self.feature.shape
        imp, w = self._node_impurity_weight(self.node_stats.astype(np.float64))
        total = np.zeros(self.n_cols, dtype=np.float64)
        for t in range(T):
            per_tree = np.zeros(self.n_cols, dtype=np.float64)
            for i in range(M):
                f = int(self.feature[t, i])
                l, r = 2 * i + 1, 2 * i + 2
                if f < 0 or r >= M:
                    continue
                gain = w[t, i] * imp[t, i] - w[t, l] * imp[t, l] - w[t, r] * imp[t, r]
                per_tree[f] += max(gain, 0.0)
            s = per_tree.sum()
            if s > 0:
                total += per_tree / s
        s = total.sum()
        return DenseVector(total / s if s > 0 else total)

    def _tree_to_dict(self, t: int, i: int = 0, leaves: Optional[np.ndarray] = None):
        """Nested-dict form of tree `t` (the per-tree JSON parity of the
        reference's cuML model_json -> Spark tree translation). `leaves` is
        computed once per forest and threaded through the recursion."""
        if leaves is None:
            leaves = self._leaf_values()
        M = self.feature.shape[1]
        f = int(self.feature[t, i])
        if f < 0 or 2 * i + 2 >= M:
            value = leaves[t, i]
            return {"leaf_value": [float(v) for v in np.atleast_1d(value)]}
        return {
            "split_feature": f,
            "threshold": float(self.threshold[t, i]),
            "yes": self._tree_to_dict(t, 2 * i + 1, leaves),  # feature <= threshold
            "no": self._tree_to_dict(t, 2 * i + 2, leaves),
        }

    @property
    def trees(self):
        """List of per-tree nested dicts (portable serialization surface)."""
        leaves = self._leaf_values()
        return [self._tree_to_dict(t, 0, leaves) for t in range(self.num_trees)]

    def treesToJson(self) -> List[str]:
        import json

        return [json.dumps(t) for t in self.trees]

    # `.cpu()` (base `_TpuModel.cpu`): array forest -> genuine JVM
    # RandomForest model (reference tree.py:524-569 _convert_to_java_trees)
    _spark_converter = "rf_to_spark"

    def predictLeaf(self, value) -> float:
        """Leaf indices for a feature vector, via the converted JVM model —
        the reference delegates to `.cpu()` identically (tree.py:513-518).
        Accepts any row representation (numpy, list, framework or pyspark
        Vector) — py4j cannot marshal numpy arrays directly."""
        from ..spark_interop import to_spark_vector

        return self.cpu().predictLeaf(to_spark_vector(value))

    def toDebugString(self) -> str:
        """Spark-style textual dump of the forest."""
        lines = [
            f"{type(self).__name__}: numTrees={self.num_trees}, "
            f"numFeatures={self.n_cols}, totalNumNodes={self.totalNumNodes}"
        ]

        def walk(node, indent):
            pad = " " * indent
            if "leaf_value" in node:
                vals = node["leaf_value"]
                pretty = vals[0] if len(vals) == 1 else vals
                lines.append(f"{pad}Predict: {pretty}")
                return
            f, thr = node["split_feature"], node["threshold"]
            lines.append(f"{pad}If (feature {f} <= {thr})")
            walk(node["yes"], indent + 1)
            lines.append(f"{pad}Else (feature {f} > {thr})")
            walk(node["no"], indent + 1)

        for t, tree in enumerate(self.trees):
            lines.append(f"  Tree {t} (weight 1.0):")
            walk(tree, 4)
        return "\n".join(lines)

    def _raw_forest_output(self, features) -> np.ndarray:
        """Batched mean-of-leaf-values [n, S] through the shared batching."""
        return self._transform_arrays(features)

    def _get_transform_func(self):
        import jax

        from ..ops.trees import forest_raw_predict
        from ..parallel.mesh import default_local_device

        feature = self.feature
        threshold = self.threshold
        leaves = self._leaf_values()
        max_depth = self.max_depth
        dtype = np.float32 if self._float32_inputs else np.float64

        def construct():
            dev = default_local_device()
            return (
                jax.device_put(feature, dev),
                jax.device_put(threshold.astype(dtype), dev),
                jax.device_put(leaves.astype(dtype), dev),
            )

        def predict(state, xb):
            f, t, lv = state
            return forest_raw_predict(xb.astype(dtype), f, t, lv, max_depth=max_depth)

        return construct, predict, None
