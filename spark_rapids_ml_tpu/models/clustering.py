#
# Clustering algorithms: KMeans (DBSCAN lands in this module too — reference
# clustering.py holds both).
#
# API-parity target: reference clustering.py:67-499 (`KMeans`/`KMeansModel`),
# drop-in for `pyspark.ml.clustering.KMeans`. Distributed strategy identical in
# math (row data-parallel Lloyd with center allreduce, SURVEY.md §2.2) but as
# one jitted while_loop program instead of per-iteration cuML MG calls.
#
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from ..core import FitInputs, _TpuEstimator, _TpuModel, _TpuModelWithColumns, pred
from ..data import ExtractedData
from ..params import (
    HasFeaturesCol,
    HasFeaturesCols,
    HasIDCol,
    HasPredictionCol,
    HasSeed,
    HasTol,
    HasMaxIter,
    HasWeightCol,
    Param,
    TypeConverters,
)


class _KMeansParams(
    HasFeaturesCol, HasFeaturesCols, HasPredictionCol, HasSeed, HasTol, HasMaxIter, HasWeightCol
):
    k = Param("k", "the number of clusters to create", TypeConverters.toInt)
    initMode = Param(
        "initMode", "the initialization algorithm: 'k-means||' or 'random'", TypeConverters.toString
    )
    initSteps = Param("initSteps", "the number of steps for k-means|| initialization", TypeConverters.toInt)
    distanceMeasure = Param("distanceMeasure", "the distance measure (euclidean only)", TypeConverters.toString)

    def getK(self) -> int:
        return self.getOrDefault("k")

    def getInitMode(self) -> str:
        return self.getOrDefault("initMode")

    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        # mirrors reference clustering.py param mapping (Spark -> cuml kwargs)
        return {
            "k": "n_clusters",
            "maxIter": "max_iter",
            "tol": "tol",
            "seed": "random_state",
            "initMode": "init",
            "initSteps": "",  # accepted, ignored (cuML has no analog; reference does the same)
            "distanceMeasure": None,  # only 'euclidean'; validated in _set_params
            "weightCol": "",
        }

    @classmethod
    def _param_value_mapping(cls):
        return {"tol": lambda v: 1e-16 if v == 0 else v}  # reference clustering.py:96-108 tol=0 remap

    def _get_solver_params_default(self) -> Dict[str, Any]:
        return {
            "n_clusters": 8,
            "max_iter": 300,
            "tol": 1e-4,
            "random_state": 1,
            "init": "scalable-k-means++",
            "max_samples_per_batch": 32768,
            "oversampling_factor": 2.0,
            "verbose": False,
            # "fast" = one-pass bf16 in-loop matmuls (f32 accumulate); the
            # reported inertia is always re-evaluated at high precision.
            # Measured at the protocol shape: 1.6x per iteration, true
            # inertia agrees to ~1e-5 (ops/kmeans.py _mm). "high" restores
            # the 3-pass-bf16 in-loop matmuls.
            "distance_precision": "fast",
            # per-estimator override of config["solver_precision"]; "bf16"
            # forces the fast in-loop path on BOTH the resident and the
            # streaming fit (streaming otherwise runs full precision)
            "solver_precision": None,
        }


class KMeans(_KMeansParams, _TpuEstimator):
    """KMeans estimator, drop-in for ``pyspark.ml.clustering.KMeans``.

    Fit is a single XLA program: `lax.while_loop` of Lloyd iterations over the
    row-sharded mesh, each iteration scanning row tiles of
    ``max_samples_per_batch`` rows (HBM-bounded) and psum-reducing (k,d) center
    sums — the TPU-native equivalent of `KMeansMG.fit` (reference
    clustering.py:339-384).
    """

    # Lloyd's argmin assignment tolerates the 3-pass MXU mode; the center-update
    # reductions are plain f32 sums — see dtype_scope (parallel/mesh.py) policy.
    _matmul_precision = "BF16_BF16_F32_X3"

    # the Lloyd loop is one pure SPMD program; the only host-side state — the
    # init centers — is computed from a rendezvous-gathered row sample below
    _supports_multiprocess = True
    # per-chunk assignment + center accumulation: an over-HBM dataset demotes
    # to ops/streaming.kmeans_fit_streaming (same host loop, same checkpoints)
    _supports_streaming_fit = True
    # every Lloyd iteration hands row tiles of X to the distance kernels
    # (ops/distance.py), which read them row-major
    _x_layout = "row_major"

    def _solver_workspace_terms(
        self, rows_per_device: int, n_cols: int, params: Dict[str, Any], itemsize: int
    ) -> Dict[str, int]:
        # per-device tile buffers of the assignment scan: the [b, k] distance
        # + one-hot blocks for batch_rows-row tiles, plus the (k, d) centers
        # and sums (replicated), plus the PREDICT-side assignment tile — the
        # transform path row-tiles through the shared distance core at
        # config["distance_tile_rows"] rows (ops/distance.argmin_assign), so
        # an admission-approved fit cannot OOM at predict; its [tile, k]
        # block is budgeted here like the fit-side tiles
        from ..ops.distance import tile_rows

        k = int(params.get("n_clusters", 8))
        b = min(int(params.get("max_samples_per_batch", 32768)), max(1, rows_per_device))
        predict_rows = min(tile_rows(), max(1, rows_per_device))
        return {
            "tile_buffers": 2 * b * k * itemsize,
            "centers": 2 * k * n_cols * itemsize,
            "predict_tile": predict_rows * k * itemsize,
        }

    def _solver_flop_estimate(self, n_rows: int, n_cols: int) -> Optional[float]:
        # Lloyd roofline model (ops_plane/efficiency.py): per iteration the
        # x·cᵀ term of the ‖x−c‖² expansion (2·n·k·d) plus the one-hot
        # center accumulation (≤ 2·n·k·d). maxIter bounds iterations from
        # above, so the MFU derived from this is an upper bound.
        k = int(self._solver_params.get("n_clusters", 8))
        iters = int(self._solver_params.get("max_iter", 300))
        return 4.0 * n_rows * k * n_cols * iters

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._setDefault(k=2, initMode="k-means||", initSteps=2, maxIter=20, tol=1e-4, seed=1,
                         distanceMeasure="euclidean")
        self._set_params(**kwargs)

    def _set_params(self, **kwargs):
        if "distanceMeasure" in kwargs and kwargs["distanceMeasure"] != "euclidean":
            raise ValueError("Only distanceMeasure='euclidean' is supported")
        kwargs.pop("distanceMeasure", None)
        return super()._set_params(**kwargs)

    def setK(self, value: int) -> "KMeans":
        return self._set_params(k=value)

    def setMaxIter(self, value: int) -> "KMeans":
        return self._set_params(maxIter=value)

    def setTol(self, value: float) -> "KMeans":
        return self._set_params(tol=value)

    def setSeed(self, value: int) -> "KMeans":
        return self._set_params(seed=value)

    def setInitMode(self, value: str) -> "KMeans":
        return self._set_params(initMode=value)

    def setFeaturesCol(self, value) -> "KMeans":
        return self._set_params(featuresCol=value) if isinstance(value, str) else self._set_params(featuresCols=value)

    def setPredictionCol(self, value: str) -> "KMeans":
        return self._set_params(predictionCol=value)

    def setWeightCol(self, value: str) -> "KMeans":
        return self._set_params(weightCol=value)

    def _resolve_warm_start(self, source: Any) -> Dict[str, Any]:
        """Warm-start payload for `fit(..., warm_start_from=...)`: a fitted
        `KMeansModel`'s centers, or a `SolverCheckpoint`'s portable center
        subset (the PR-6 elastic-recovery iterate, public API here)."""
        from .. import checkpoint as _ckpt

        if isinstance(source, _ckpt.SolverCheckpoint):
            centers = (source.portable or {}).get(
                "centers", (source.state or {}).get("centers")
            )
            if centers is None:
                raise ValueError(
                    "SolverCheckpoint warm start for KMeans needs a "
                    "'centers' payload (k-means checkpoints carry one)"
                )
            return {
                "cluster_centers_": np.asarray(centers),
                "n_iter_": int(source.iteration),
            }
        centers = getattr(source, "cluster_centers_", None)
        if centers is None:
            raise TypeError(
                f"cannot warm-start KMeans from {type(source).__name__}: "
                "expected a fitted KMeansModel or a SolverCheckpoint"
            )
        return {
            "cluster_centers_": np.asarray(centers),
            "n_iter_": int(getattr(source, "n_iter_", 0) or 0),
        }

    def _get_tpu_fit_func(self, extracted: ExtractedData):
        from .. import telemetry
        from ..ops.kmeans import (
            kmeans_fit,
            kmeans_plus_plus_init,
            random_init,
            scalable_kmeans_init,
        )

        x_host = extracted.features
        w_host = extracted.weight

        def _fit(inputs: FitInputs, params: Dict[str, Any]) -> Dict[str, Any]:
            k = int(params["n_clusters"])
            if k > inputs.n_valid:
                raise ValueError(f"k={k} exceeds number of rows {inputs.n_valid}")
            init_mode = params.get("init", "scalable-k-means++")
            seed = int(params.get("random_state", 1) or 1)
            # public warm start (fit(..., warm_start_from=model_or_checkpoint),
            # docs/scheduling.md "Warm starts"): the donor's centers ARE the
            # init — the seeding passes below are skipped entirely, and Lloyd
            # continues the donor's trajectory (adoption + the donor's
            # already-paid iterations are counted)
            warm = getattr(self, "_warm_start", None)
            warm_centers = None
            if warm is not None:
                c0 = np.asarray(warm["cluster_centers_"])
                if tuple(c0.shape) != (k, int(inputs.n_cols)):
                    raise ValueError(
                        f"warm-start centers shape {tuple(c0.shape)} does not "
                        f"match this fit (k={k}, d={inputs.n_cols})"
                    )
                from .. import telemetry as _telemetry

                if _telemetry.enabled():
                    reg = _telemetry.registry()
                    reg.inc("fit.warm_starts")
                    reg.inc(
                        "fit.warm_start_iterations_saved",
                        int(warm.get("n_iter_", 0) or 0),
                    )
                warm_centers = c0
            # once-per-fit child spans of `fit/solve`: `init` here, `loop` and
            # `finish` inside `kmeans_fit` (docs/observability.md)
            with telemetry.span(
                "init", init_mode="warm_start" if warm_centers is not None else init_mode
            ):
                # under multi-process SPMD the init must be computed from GLOBAL
                # rows: every rank contributes a bounded sample (the whole local
                # block when small), the rendezvous concatenates them in rank
                # order, and every rank runs the SAME seeded init on the union —
                # so all ranks enter the Lloyd loop with identical centers (the
                # reference's distributed k-means|| init runs inside KMeansMG)
                x_init, w_init = x_host, w_host
                if warm_centers is None and inputs.ctx is not None and inputs.ctx.is_spmd:
                    cap = max(4 * k, 262_144 // inputs.ctx.nranks)
                    n_loc = x_host.shape[0]
                    if n_loc > cap:
                        rs = np.random.default_rng(seed * 100_003 + inputs.ctx.rank)  # prng-ok: deliberate per-rank sampling of LOCAL rows; the allgather below hands every rank the identical union, so the seeded init agrees
                        sel = np.sort(rs.choice(n_loc, cap, replace=False))
                        xs = np.asarray(x_host[sel], dtype=np.float64)
                        ws = None if w_host is None else np.asarray(w_host[sel], dtype=np.float64)
                    else:
                        xs = np.asarray(x_host, dtype=np.float64)
                        ws = None if w_host is None else np.asarray(w_host, dtype=np.float64)
                    x_init = inputs.allgather_array(xs)
                    w_init = None if ws is None else inputs.allgather_array(ws)
                if warm_centers is not None:
                    centers0 = warm_centers  # the donor's iterate IS the init
                elif init_mode == "random":
                    centers0 = random_init(x_init, k, seed)
                elif k >= 64:
                    # true k-means|| for large k: O(rounds) device passes instead
                    # of k sequential host passes (minutes at the protocol k=1000)
                    centers0 = scalable_kmeans_init(x_init, k, seed, w_init)
                else:  # small k: classic k-means++ (exactness-friendly for tests)
                    centers0 = kmeans_plus_plus_init(x_init, k, seed, w_init)
                centers0 = centers0.astype(inputs.dtype)
            # `solver_precision="bf16"` (per-estimator or config-wide) forces
            # the bf16-compute/f32-accumulate in-loop path on both fit modes;
            # the legacy `distance_precision` knob keeps governing the
            # resident loop when solver_precision stays at its "f32" default
            from ..core import resolve_solver_precision

            solver_precision = resolve_solver_precision(params)

            def attributes(state: Dict[str, Any]) -> Dict[str, Any]:
                return {
                    "cluster_centers_": np.asarray(state["cluster_centers_"]),
                    "inertia_": float(state["inertia_"]),
                    "n_iter_": int(state["n_iter_"]),
                    "n_cols": inputs.n_cols,
                    "dtype": np.dtype(inputs.dtype).name,
                }

            if inputs.stream is not None:
                # out-of-core: per-chunk assignment + center accumulation
                # under the SAME deferred-convergence host loop and the SAME
                # checkpoint key as the resident fit. In-loop chunk matmuls
                # honor solver_precision ("bf16" -> distance core fast path);
                # the reported inertia is always re-evaluated full precision.
                from ..ops.streaming import kmeans_fit_streaming

                # the streaming kernel materializes its [chunk_dev, k]
                # distance/one-hot buffers UNTILED, while the workspace
                # estimate charges tiles of at most max_samples_per_batch
                # rows — clamp the chunk so the per-device slice never
                # exceeds the tile the admission verdict budgeted for
                # (smaller chunks only shrink the admitted working set)
                b = int(params.get("max_samples_per_batch", 32768))
                n_dev = int(inputs.mesh.devices.size)
                inputs.stream.chunk_rows = max(
                    1, min(int(inputs.stream.chunk_rows), b * n_dev)
                )
                return attributes(kmeans_fit_streaming(
                    inputs,
                    centers0,
                    max_iter=int(params["max_iter"]),
                    tol=float(params["tol"]),
                    precision_mode="fast" if solver_precision == "bf16" else "high",
                ))
            return kmeans_fit(
                inputs.X,
                inputs.w,
                centers0,
                mesh=inputs.mesh,
                max_iter=int(params["max_iter"]),
                tol=float(params["tol"]),
                batch_rows=int(params.get("max_samples_per_batch", 32768)),
                precision_mode=(
                    "fast"
                    if solver_precision == "bf16"
                    else str(params.get("distance_precision", "fast"))
                ),
                to_host=attributes,  # runs inside the solver's `finish` span
            )

        return _fit

    def _create_model(self, attrs: Dict[str, Any]) -> "KMeansModel":
        return KMeansModel(**attrs)


class KMeansModel(_KMeansParams, _TpuModelWithColumns):
    """Fitted KMeans model (reference clustering.py:386-499)."""

    _matmul_precision = "BF16_BF16_F32_X3"
    _spark_converter = "kmeans_to_spark"  # `.cpu()` (reference clustering.py:422-443)

    def __init__(
        self,
        cluster_centers_: Optional[np.ndarray] = None,
        inertia_: float = 0.0,
        n_iter_: int = 0,
        n_cols: int = 0,
        dtype: str = "float32",
        **kwargs: Any,
    ) -> None:
        super().__init__(
            cluster_centers_=cluster_centers_,
            inertia_=inertia_,
            n_iter_=n_iter_,
            n_cols=n_cols,
            dtype=dtype,
        )
        self.cluster_centers_ = np.asarray(cluster_centers_)
        self.inertia_ = float(inertia_)
        self.n_iter_ = int(n_iter_)
        self.n_cols = int(n_cols)
        self.dtype = dtype
        self._setDefault(k=int(self.cluster_centers_.shape[0]) if cluster_centers_ is not None else 2)

    def clusterCenters(self) -> List[np.ndarray]:
        """Spark ML surface: list of center vectors."""
        return [c for c in self.cluster_centers_]

    @property
    def numClusters(self) -> int:
        return self.cluster_centers_.shape[0]

    def predict(self, value) -> int:
        """Single-vector predict (Spark ML model surface)."""
        from ..linalg import Vector

        v = value.toArray() if isinstance(value, Vector) else np.asarray(value)
        d2 = np.sum((self.cluster_centers_ - v[None, :]) ** 2, axis=1)
        return int(np.argmin(d2))

    def setFeaturesCol(self, value) -> "KMeansModel":
        return self._set_params(featuresCol=value) if isinstance(value, str) else self._set_params(featuresCols=value)

    def setPredictionCol(self, value: str) -> "KMeansModel":
        return self._set_params(predictionCol=value)

    def _out_column_names(self) -> List[str]:
        return [self.getOrDefault("predictionCol")]

    def _get_transform_func(self):
        import jax

        from ..ops.kmeans import kmeans_predict
        from ..parallel.mesh import default_local_device

        centers = self.cluster_centers_
        dtype = np.float32 if self._float32_inputs else np.float64

        def construct():
            return jax.device_put(centers.astype(dtype), default_local_device())

        def predict(state, xb):
            # a batch the distributed transform row-sharded over a mesh
            # (core.PredictProgram.dispatch) is assigned shard by shard
            sharding = getattr(xb, "sharding", None)
            mesh = sharding.mesh if len(getattr(sharding, "device_set", ())) > 1 else None
            return kmeans_predict(xb.astype(dtype), state, mesh=mesh)

        return construct, predict, None

    # serving hooks (docs/serving.md) -------------------------------------

    _serve_dtypes = (None, "float32", "float64", "bf16")

    def _serve_program(self, serve_dtype=None, *, cap=None):
        """KMeans serving hook: `serve_dtype="bf16"` routes assignment
        through the distance core's parity-tested fast-bf16 mode (one-pass
        bf16 MXU matmuls, f32 accumulation) — assignment flips only for
        near-tied rows (docs/serving.md "bf16 serving" accuracy contract)."""
        if serve_dtype != "bf16":
            return super()._serve_program(serve_dtype, cap=cap)
        self._serve_check(serve_dtype)
        import jax

        from ..core import PredictProgram
        from ..ops.distance import argmin_assign
        from ..parallel.mesh import default_local_device

        centers = self.cluster_centers_
        dtype = np.float32 if self._float32_inputs else np.float64

        def construct():
            return jax.device_put(centers.astype(dtype), default_local_device())

        def predict(state, xb):
            return argmin_assign(xb.astype(dtype), state, fast=True)

        return PredictProgram(self, construct=construct, predict=predict, cap=cap)

    def _serve_workspace_terms(self, bucket_rows_count, itemsize):
        # the predict-side assignment tile: a [tile, k] distance block per
        # dispatched bucket, row-tiled through the shared distance core at
        # config["distance_tile_rows"] rows — the same term the fit-side
        # budgeter charges as `predict_tile`
        from ..ops.distance import tile_rows

        k = int(self.cluster_centers_.shape[0])
        tile = min(tile_rows(), max(1, int(bucket_rows_count)))
        return {"predict_tile": tile * k * itemsize}

    def _serve_flop_estimate(self, n_rows, n_cols):
        # roofline numerator: the [n, k] squared-distance block (~3*n*k*d for
        # the expanded |x|^2 - 2 x.c + |c|^2 form); argmin epilogue omitted
        k = max(1, int(self.cluster_centers_.shape[0]))
        return 3.0 * n_rows * k * n_cols


class _DBSCANParams(HasFeaturesCol, HasFeaturesCols, HasPredictionCol, HasIDCol):
    """Param surface of the reference's DBSCAN (reference clustering.py:522-639):
    solver knobs are first-class Params (there is no pyspark DBSCAN to map from)."""

    eps = Param(
        "eps",
        "maximum distance between 2 points such they reside in the same neighborhood",
        TypeConverters.toFloat,
    )
    min_samples = Param(
        "min_samples",
        "number of samples in a neighborhood for a point to be a core point (incl. itself)",
        TypeConverters.toInt,
    )
    metric = Param("metric", "distance metric: 'euclidean' or 'cosine'", TypeConverters.toString)
    algorithm = Param("algorithm", "neighbor computation algorithm: 'brute' or 'rbc'", TypeConverters.toString)
    max_mbytes_per_batch = Param(
        "max_mbytes_per_batch",
        "memory budget (MB) for each pairwise-distance tile — trades runtime for memory "
        "on the N^2 distance computation",
        TypeConverters.identity,
    )
    calc_core_sample_indices = Param(
        "calc_core_sample_indices", "whether to compute core sample indices", TypeConverters.toBoolean
    )

    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        # identity mapping: the Param names ARE the solver kwargs (no pyspark
        # class exists to translate from; reference clustering.py:503-505 has
        # an empty mapping for the same reason but syncs via shared names)
        return {
            "eps": "eps",
            "min_samples": "min_samples",
            "metric": "metric",
            "algorithm": "algorithm",
            "max_mbytes_per_batch": "max_mbytes_per_batch",
            "calc_core_sample_indices": "calc_core_sample_indices",
        }

    def __init__(self, *args: Any, **kwargs: Any) -> None:
        super().__init__(*args, **kwargs)
        # Param-tier defaults live HERE so a directly-constructed model resolves
        # them too. calc_core_sample_indices follows the reference's Param tier
        # (True, clustering.py:526-533) — its cuml tier says False but the Param
        # default wins there as well.
        self._setDefault(
            eps=0.5, min_samples=5, metric="euclidean", algorithm="brute",
            max_mbytes_per_batch=None, calc_core_sample_indices=True,
        )

    def _get_solver_params_default(self) -> Dict[str, Any]:
        # reference clustering.py:508-515 defaults (Param tier overrides above)
        return {
            "eps": 0.5,
            "min_samples": 5,
            "metric": "euclidean",
            "algorithm": "brute",
            "verbose": False,
            "max_mbytes_per_batch": None,
            "calc_core_sample_indices": False,  # cuml-tier default (reference clustering.py:513); Param tier above wins
        }

    def getEps(self) -> float:
        return self.getOrDefault("eps")

    def setEps(self, value: float):
        return self._set_params(eps=value)

    def getMinSamples(self) -> int:
        return self.getOrDefault("min_samples")

    def setMinSamples(self, value: int):
        return self._set_params(min_samples=value)

    def getMetric(self) -> str:
        return self.getOrDefault("metric")

    def setMetric(self, value: str):
        return self._set_params(metric=value)

    def setMaxMbytesPerBatch(self, value):
        return self._set_params(max_mbytes_per_batch=value)

    def getMaxMbytesPerBatch(self):
        return self.getOrDefault("max_mbytes_per_batch")

    def getAlgorithm(self) -> str:
        return self.getOrDefault("algorithm")

    def setAlgorithm(self, value: str):
        return self._set_params(algorithm=value)

    def getCalcCoreSampleIndices(self) -> bool:
        return self.getOrDefault("calc_core_sample_indices")

    def setCalcCoreSampleIndices(self, value: bool):
        return self._set_params(calc_core_sample_indices=value)

    def setFeaturesCol(self, value):
        return self._set_params(featuresCol=value) if isinstance(value, str) else self._set_params(featuresCols=value)

    def setPredictionCol(self, value: str):
        return self._set_params(predictionCol=value)

    def setIdCol(self, value: str):
        return self._set_params(idCol=value)


class DBSCAN(_DBSCANParams, _TpuEstimator):
    """DBSCAN estimator (reference clustering.py:641-849).

    Like the reference, ``fit`` is a no-op returning a parameter-copied model —
    the clustering itself runs in ``model.transform`` because DBSCAN has no
    train/inference split (reference clustering.py:820-833).

    >>> model = DBSCAN(eps=0.5, min_samples=5).setFeaturesCol("features").fit(df)
    >>> out = model.transform(df)   # df + prediction column, noise = -1

    Distributed strategy: the dataset is replicated to every device and the N²
    pairwise-distance work is row-sliced across the mesh (the reference's
    broadcast + rank-sliced DBSCANMG, clustering.py:1013-1091) in three tiled
    MXU passes — core mask, core-graph components by min-label propagation with
    pointer jumping, border adoption. `max_mbytes_per_batch` bounds each
    distance tile.
    """

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._set_params(**kwargs)

    def _set_params(self, **kwargs):
        if "metric" in kwargs and kwargs["metric"] not in ("euclidean", "cosine", "precomputed"):
            raise ValueError(
                f"metric must be 'euclidean', 'cosine' or 'precomputed', got {kwargs['metric']!r}"
            )
        if "algorithm" in kwargs and kwargs["algorithm"] not in ("brute", "rbc"):
            raise ValueError(f"algorithm must be 'brute' or 'rbc', got {kwargs['algorithm']!r}")
        return super()._set_params(**kwargs)

    def _get_tpu_fit_func(self, extracted: ExtractedData):  # pragma: no cover
        raise NotImplementedError("DBSCAN does not fit and generate model (reference parity)")

    def _fit_internal(self, dataset: Any, paramMaps):
        # parameter-copied model(s), no data touched (reference
        # clustering.py:820-833); one model per param map for fitMultiple
        sources = [self.copy(pm) for pm in paramMaps] if paramMaps else [self]
        models = []
        for src in sources:
            model = DBSCANModel(n_cols=0, dtype="")
            src._copyValues(model)
            src._copy_solver_params(model)
            models.append(model)
        return models

    def _create_model(self, attrs):  # pragma: no cover - _fit_internal overridden
        return DBSCANModel(**attrs)


class DBSCANModel(_DBSCANParams, _TpuModel):
    """DBSCAN 'model': runs the clustering inside transform and appends the
    label column (reference clustering.py:852-1100).

    `idCol` is accepted for API compatibility with the reference, which needs
    an id join because Spark rows are unordered; the pandas path preserves row
    order, so labels are attached positionally and the id column is left
    untouched."""

    def __init__(self, n_cols: int = 0, dtype: str = "", **kwargs: Any) -> None:
        super().__init__(n_cols=n_cols, dtype=dtype)
        self.n_cols = int(n_cols)
        self.dtype = dtype
        self.core_sample_indices_: Optional[np.ndarray] = None

    def transform(self, dataset: Any):
        from ..data import as_pandas
        from ..ops.dbscan import dbscan_fit
        from ..parallel import TpuContext, get_mesh
        from ..parallel.context import allgather_concat
        from ..parallel.mesh import default_devices, dtype_scope

        active = TpuContext.current()
        spmd = active is not None and active.is_spmd
        pdf = as_pandas(dataset)
        extracted = self._pre_process_data(dataset, for_fit=False)
        feats = extracted.features
        if hasattr(feats, "todense"):
            feats = np.asarray(feats.todense())
        feats = np.asarray(feats, dtype=np.float32)
        row_offset, n_local = 0, feats.shape[0]
        if spmd:
            # replicated-data strategy (reference clustering.py:1013-1091): the
            # whole dataset is rendezvous-gathered to every rank (chunked by
            # config["broadcast_chunk_bytes"]), the N² passes run cooperatively
            # over the GLOBAL mesh, and each rank keeps its own rows' labels
            feats, row_offset = allgather_concat(active.rendezvous, feats)
            mesh = active.mesh
        else:
            mesh = get_mesh(min(self.num_workers, len(default_devices())))
        with dtype_scope(np.float32):
            labels, core_idx = dbscan_fit(
                feats,
                mesh=mesh,
                eps=float(self.getOrDefault("eps")),
                min_samples=int(self.getOrDefault("min_samples")),
                metric=self.getOrDefault("metric"),
                max_mbytes_per_batch=self.getOrDefault("max_mbytes_per_batch"),
                calc_core_sample_indices=bool(self.getOrDefault("calc_core_sample_indices")),
            )
        if spmd:
            # labels are GLOBAL; keep this rank's slice (core_sample_indices_
            # stay global row positions, like the reference's idCol join space)
            labels = labels[row_offset : row_offset + n_local]
        # labels attach positionally: _pre_process_data must not drop/reorder rows
        assert len(labels) == len(pdf), (
            f"row count mismatch: {len(labels)} labels vs {len(pdf)} input rows"
        )
        # most-recent-transform state, mirroring cuML's fit_predict attribute;
        # concurrent transforms of one model should each use their own copy()
        self.core_sample_indices_ = core_idx
        out = pdf.copy(deep=False)
        out[self.getOrDefault("predictionCol")] = labels.astype(np.int64)
        return out
