#
# RandomForest benchmark — the protocol's two configs (reference
# databricks/run_benchmark.sh:107-129): classifier 50 trees / depth 13 /
# 128 bins, regressor 30 trees / depth 6 / 128 bins, both on 1M x 3k.
# Quality = training accuracy (clf) / R² (reg) on a row subsample.
#
from __future__ import annotations

import numpy as np

from .base import BenchmarkBase, fetch
from .gen_data import gen_classification_device, gen_regression_device
from .utils import with_benchmark


class BenchmarkRandomForest(BenchmarkBase):
    name = "random_forest"
    extra_args = {
        "task": (str, "classification", "classification (50xd13) | regression (30xd6)"),
        "numTrees": (int, 0, "override protocol tree count"),
        "maxDepth": (int, 0, "override protocol depth"),
        "maxBins": (int, 128, "histogram bins (protocol: 128)"),
        "node_chunk": (int, 0, "nodes processed per histogram pass (HBM knob; 0 = what SEGMENT_BUDGET holds)"),
    }

    def gen_dataset(self, args, mesh):
        if args.cpu_comparison:
            from .gen_data import gen_classification_host, gen_regression_host

            if args.task == "classification":
                Xh, yh = gen_classification_host(
                    args.num_rows, args.num_cols, 2, args.seed
                )
            else:
                Xh, yh, _ = gen_regression_host(
                    args.num_rows, args.num_cols, seed=args.seed
                )
            return self.dataset_from_arrays(Xh, yh, args, mesh)
        if args.task == "classification":
            X, y, w = gen_classification_device(
                args.num_rows, args.num_cols, n_classes=2, seed=args.seed, mesh=mesh
            )
            data = {"X": X, "y": y, "w": w}
        else:
            X, y, w, _ = gen_regression_device(
                args.num_rows, args.num_cols, seed=args.seed, mesh=mesh
            )
            data = {"X": X, "y": y, "w": w}
        fetch(w[:1])
        return data

    def dataset_from_arrays(self, X, y, args, mesh):
        from spark_rapids_ml_tpu.parallel import make_global_rows

        if y is None:
            raise ValueError("random_forest dataset needs a label column")
        Xh = np.asarray(X, dtype=np.float32)
        yh = np.asarray(y, dtype=np.float32)
        Xd, w, _ = make_global_rows(mesh, Xh)  # pad + row-shard like the gens
        yd, _, _ = make_global_rows(mesh, yh)
        return {
            "X": Xd,
            "y": yd,
            "w": w,
            "X_host": Xh,
            "y_host": yh,
        }

    def run_cpu(self, args, data):
        import time

        from sklearn.ensemble import RandomForestClassifier as SkRFC
        from sklearn.ensemble import RandomForestRegressor as SkRFR

        clf = args.task == "classification"
        n_trees = args.numTrees or (50 if clf else 30)
        depth = args.maxDepth or (13 if clf else 6)
        est = (SkRFC if clf else SkRFR)(
            n_estimators=n_trees, max_depth=depth, n_jobs=-1, random_state=0
        )
        t0 = time.perf_counter()
        est.fit(data["X_host"], data["y_host"])
        return {"cpu_fit": time.perf_counter() - t0}

    def run_once(self, args, data, mesh):
        import jax

        from spark_rapids_ml_tpu.ops.trees import bin_features, forest_fit, quantile_bins

        clf = args.task == "classification"
        n_trees = args.numTrees or (50 if clf else 30)
        depth = args.maxDepth or (13 if clf else 6)

        if data.get("X") is None:
            # a previous run released the raw matrix (see below); the device
            # generators are deterministic in the seed, so regenerate
            # identically (datagen, not fit — outside the timer)
            if data.get("X_host") is not None:
                data["X"] = jax.device_put(data["X_host"])
            elif clf:
                data["X"], _, _ = gen_classification_device(
                    args.num_rows, args.num_cols, n_classes=2, seed=args.seed, mesh=mesh
                )
            else:
                data["X"], _, _, _ = gen_regression_device(
                    args.num_rows, args.num_cols, seed=args.seed, mesh=mesh
                )
        # raw row sample fetched ONCE: quantile edges (fit) + quality eval
        n_sample = min(args.num_rows, 65536)
        if "X_sample" not in data:
            data["X_sample"] = np.asarray(data["X"][:n_sample], dtype=np.float32)
        xs = data["X_sample"]
        release_raw = args.num_rows * args.num_cols >= 500_000_000

        def run():
            # quantile sketch from the row subsample (binning is part of the
            # fit, like cuRF's quantile computation)
            edges = quantile_bins(xs, args.maxBins).astype(np.float32)
            Xb = bin_features(data["X"], edges)
            if release_raw:
                # the forest consumes ONLY the binned matrix; at protocol
                # scale the idle raw X (11.2 GB) plus Xb plus histogram
                # buffers exceed one chip's HBM — release X for the growth
                # phase (regenerated above if another run follows). The tiny
                # fetch is the reliable completion fence on this platform.
                np.asarray(Xb[:1, :1])
                data["X"].delete()
                data["X"] = None
            y_host = np.asarray(data["y"])
            if clf:
                stats = np.zeros((len(y_host), 2), np.float32)
                stats[np.arange(len(y_host)), y_host.astype(int)] = 1.0
            else:
                stats = np.stack(
                    [np.ones_like(y_host), y_host, y_host * y_host], axis=1
                ).astype(np.float32)
            from spark_rapids_ml_tpu.parallel.mesh import row_sharding

            stats_dev = jax.device_put(stats, row_sharding(mesh, 2))
            w = data["w"]
            return forest_fit(
                Xb, stats_dev * w[:, None], w, args.seed, mesh=mesh, n_features=args.num_cols,
                n_trees=n_trees, max_depth=depth, max_bins=args.maxBins,
                max_features=max(1, int(np.sqrt(args.num_cols))) if clf else max(1, args.num_cols // 3),
                impurity="gini" if clf else "variance",
                node_chunk=args.node_chunk, bootstrap=True, subsample_rate=1.0,
                min_instances=1.0, min_info_gain=0.0, integer_stats=clf,
            )

        state = {}

        def timed():
            s = run()
            fetch(s["feature"])
            state.update(s)
            return s

        _, sec = with_benchmark(f"random_forest[{args.task}] fit", timed)
        self._state = {k: np.asarray(v)[:n_trees] for k, v in state.items() if k != "plan"}  # whole rounds cut to the trees asked for
        self._clf = clf
        self._depth = depth
        return {"fit": sec}

    def quality(self, args, data):
        from spark_rapids_ml_tpu.ops.trees import forest_raw_predict, split_bins_to_thresholds
        from spark_rapids_ml_tpu.models.tree import _fill_empty_nodes

        n_eval = min(args.num_rows, 32768)
        # the raw matrix may have been released during the fit (HBM budget);
        # the stashed host sample covers both eval rows and the edge sketch
        X = data["X_sample"][:n_eval]
        y = np.asarray(data["y"][:n_eval])
        feature = self._state["feature"]
        node_stats = _fill_empty_nodes(feature, self._state["node_stats"].astype(np.float64))
        from spark_rapids_ml_tpu.ops.trees import quantile_bins

        edges = quantile_bins(data["X_sample"], args.maxBins)
        threshold = split_bins_to_thresholds(feature, self._state["split_bin"], edges)
        if self._clf:
            leaves = node_stats / np.maximum(node_stats.sum(axis=2, keepdims=True), 1e-30)
            dist = np.asarray(
                forest_raw_predict(
                    X, feature, threshold.astype(np.float32), leaves.astype(np.float32),
                    max_depth=self._depth,
                )
            )
            pred = np.argmax(dist, axis=1)
            return {"accuracy": float((pred == y).mean())}
        w = node_stats[..., 0]
        leaves = (node_stats[..., 1] / np.maximum(w, 1e-30))[..., None]
        pred = np.asarray(
            forest_raw_predict(
                X, feature, threshold.astype(np.float32), leaves.astype(np.float32),
                max_depth=self._depth,
            )
        )[:, 0]
        ss_res = float(((pred - y) ** 2).sum())
        ss_tot = float(((y - y.mean()) ** 2).sum())
        return {"r2": 1.0 - ss_res / max(ss_tot, 1e-30)}


if __name__ == "__main__":
    BenchmarkRandomForest().run()
