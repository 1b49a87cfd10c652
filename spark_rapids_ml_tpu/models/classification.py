#
# Classification algorithms: LogisticRegression (RandomForestClassifier joins
# this module when the tree family lands — reference classification.py hosts
# both).
#
# API-parity target: reference classification.py:665-1581, drop-in for
# `pyspark.ml.classification.LogisticRegression`: binomial + multinomial,
# standardization, intercept centering, single-class degenerate handling,
# rawPrediction/probability/prediction output columns, threshold(s).
#
from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

from ..core import FitInputs, _TpuEstimatorSupervised, _TpuModelWithColumns, pred
from ..data import ExtractedData, as_pandas, vectors_to_pandas_column
from ..params import (
    HasElasticNetParam,
    HasEnableSparseDataOptim,
    HasFeaturesCol,
    HasFeaturesCols,
    HasFitIntercept,
    HasLabelCol,
    HasMaxIter,
    HasPredictionCol,
    HasProbabilityCol,
    HasRawPredictionCol,
    HasRegParam,
    HasStandardization,
    HasTol,
    HasWeightCol,
    Param,
    TypeConverters,
)


from .tree import _RandomForestEstimator, _RandomForestModel


class RandomForestClassifier(HasProbabilityCol, HasRawPredictionCol, _RandomForestEstimator):
    """RandomForestClassifier, drop-in for
    ``pyspark.ml.classification.RandomForestClassifier``.

    Ensemble-split fit (reference tree.py:270-281 strategy): each mesh device
    grows its share of the forest on its row shard with level-wise histogram
    tree building (ops/trees.py); tree arrays are gathered at the end (the
    Treelite-concat analog). Impurity: gini (default) or entropy.
    """

    _is_classification = True

    def __init__(self, **kwargs: Any) -> None:
        super().__init__(**kwargs)
        self._setDefault(impurity="gini")
        if self._solver_params.get("split_criterion") is None:
            self._solver_params["split_criterion"] = "gini"

    def _set_params(self, **kwargs):
        if "impurity" in kwargs and kwargs["impurity"] not in ("gini", "entropy"):
            raise ValueError("impurity must be 'gini' or 'entropy' for classification")
        return super()._set_params(**kwargs)

    def setProbabilityCol(self, value: str) -> "RandomForestClassifier":
        return self._set_params(probabilityCol=value)

    def setRawPredictionCol(self, value: str) -> "RandomForestClassifier":
        return self._set_params(rawPredictionCol=value)

    def _create_model(self, attrs: Dict[str, Any]) -> "RandomForestClassificationModel":
        return RandomForestClassificationModel(**attrs)


class RandomForestClassificationModel(HasProbabilityCol, HasRawPredictionCol, _RandomForestModel):
    """Fitted RF classification model (reference classification.py:302-662)."""

    _is_classification = True

    @property
    def numClasses(self) -> int:
        return len(self.classes_)

    def _leaf_values(self) -> np.ndarray:
        # normalized per-node class distribution (Spark averages leaf distributions)
        totals = self.node_stats.sum(axis=2, keepdims=True)
        return self.node_stats / np.maximum(totals, 1e-30)

    def setProbabilityCol(self, value: str) -> "RandomForestClassificationModel":
        return self._set_params(probabilityCol=value)

    def setRawPredictionCol(self, value: str) -> "RandomForestClassificationModel":
        return self._set_params(rawPredictionCol=value)

    def _out_column_names(self) -> List[str]:
        return [
            self.getOrDefault("rawPredictionCol"),
            self.getOrDefault("probabilityCol"),
            self.getOrDefault("predictionCol"),
        ]

    def _split_output(self, result, names, extracted) -> Dict[str, Any]:
        mean_dist = np.asarray(result, dtype=np.float64)
        prob = mean_dist / np.maximum(mean_dist.sum(axis=1, keepdims=True), 1e-30)
        raw = mean_dist * self.num_trees  # Spark raw = summed tree votes
        prediction = self.classes_[np.argmax(prob, axis=1)].astype(np.float64)
        as_vec = extracted.feature_kind == "vector"
        return {
            names[0]: vectors_to_pandas_column(raw) if as_vec else list(raw),
            names[1]: vectors_to_pandas_column(prob) if as_vec else list(prob),
            names[2]: prediction,
        }

    def predict(self, value) -> float:
        from ..linalg import Vector

        v = value.toArray() if isinstance(value, Vector) else np.asarray(value)
        dist = np.asarray(self._raw_forest_output(v[None, :]), dtype=np.float64)[0]
        return float(self.classes_[int(np.argmax(dist))])

    def predictRaw(self, value):
        """Summed per-tree normalized votes (Spark's RF raw prediction;
        computed natively — the reference delegates to .cpu())."""
        from ..linalg import DenseVector, Vector

        v = value.toArray() if isinstance(value, Vector) else np.asarray(value)
        dist = np.asarray(self._raw_forest_output(v[None, :]), dtype=np.float64)[0]
        return DenseVector(dist * self.num_trees)

    def predictProbability(self, value):
        from ..linalg import DenseVector, Vector

        v = value.toArray() if isinstance(value, Vector) else np.asarray(value)
        dist = np.asarray(self._raw_forest_output(v[None, :]), dtype=np.float64)[0]
        return DenseVector(dist / max(dist.sum(), 1e-30))

    def evaluate(self, dataset):
        """Evaluate on a dataset via the converted JVM model's summary
        (reference classification.py:604-662). Accepts framework datasets
        (pandas/arrow/dict) or a Spark DataFrame."""
        from ..spark_interop import as_spark_df

        return self.cpu().evaluate(as_spark_df(dataset))


class _LogisticRegressionParams(
    HasEnableSparseDataOptim,
    HasFeaturesCol,
    HasFeaturesCols,
    HasLabelCol,
    HasPredictionCol,
    HasProbabilityCol,
    HasRawPredictionCol,
    HasMaxIter,
    HasTol,
    HasRegParam,
    HasElasticNetParam,
    HasFitIntercept,
    HasStandardization,
    HasWeightCol,
):
    family = Param("family", "label distribution: 'auto', 'binomial' or 'multinomial'", TypeConverters.toString)
    threshold = Param("threshold", "binary prediction threshold in [0, 1]", TypeConverters.toFloat)
    thresholds = Param(
        "thresholds",
        "multiclass thresholds: predict argmax(p/threshold)",
        TypeConverters.toListFloat,
    )

    def getFamily(self) -> str:
        return self.getOrDefault("family")

    def getThreshold(self) -> float:
        return self.getOrDefault("threshold")

    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        # mirrors reference classification.py param mapping for LogisticRegression
        return {
            "maxIter": "max_iter",
            "regParam": "alpha",
            "elasticNetParam": "l1_ratio",
            "tol": "tol",
            "fitIntercept": "fit_intercept",
            "standardization": "standardization",
            "family": "",  # resolved from the label cardinality at fit time
            "threshold": "",
            "thresholds": "",
            "weightCol": "",
        }

    def _get_solver_params_default(self) -> Dict[str, Any]:
        return {
            "alpha": 0.0,
            "l1_ratio": 0.0,
            "max_iter": 100,
            "tol": 1e-6,
            "fit_intercept": True,
            "standardization": True,
            "lbfgs_memory": 10,  # reference parity: lbfgs_memory=10 (classification.py:1056-1057)
            "verbose": False,
            # per-estimator override of config["solver_precision"]; "bf16"
            # runs the X·β / Xᵀr matvecs bf16-in/f32-accumulate while the
            # L-BFGS state, line search, and convergence scalars stay full
            # precision (docs/performance.md "Mixed-precision solvers")
            "solver_precision": None,
        }


class LogisticRegression(_LogisticRegressionParams, _TpuEstimatorSupervised):
    """LogisticRegression estimator, drop-in for
    ``pyspark.ml.classification.LogisticRegression``.

    Distributed L-BFGS where every objective/gradient evaluation is one fused
    MXU matmul + psum over the rows mesh; standardization statistics are
    computed in-graph and folded into the coefficients (no standardized copy of
    the data) — the TPU-native form of the reference's CuPy pre-standardization
    + `LogisticRegressionMG` path (classification.py:984-1089).
    """

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._setDefault(
            maxIter=100, regParam=0.0, elasticNetParam=0.0, tol=1e-6, fitIntercept=True,
            standardization=True, family="auto", threshold=0.5,
        )
        self._set_params(**kwargs)

    def _set_params(self, **kwargs):
        if "family" in kwargs and kwargs["family"] not in ("auto", "binomial", "multinomial"):
            raise ValueError(
                f"family must be 'auto', 'binomial' or 'multinomial', got {kwargs['family']!r}"
            )
        return super()._set_params(**kwargs)

    def setMaxIter(self, value: int) -> "LogisticRegression":
        return self._set_params(maxIter=value)

    def setRegParam(self, value: float) -> "LogisticRegression":
        return self._set_params(regParam=value)

    def setElasticNetParam(self, value: float) -> "LogisticRegression":
        return self._set_params(elasticNetParam=value)

    def setTol(self, value: float) -> "LogisticRegression":
        return self._set_params(tol=value)

    def setFitIntercept(self, value: bool) -> "LogisticRegression":
        return self._set_params(fitIntercept=value)

    def setStandardization(self, value: bool) -> "LogisticRegression":
        return self._set_params(standardization=value)

    def setFamily(self, value: str) -> "LogisticRegression":
        return self._set_params(family=value)

    def setThreshold(self, value: float) -> "LogisticRegression":
        return self._set_params(threshold=value)

    def setThresholds(self, value: List[float]) -> "LogisticRegression":
        return self._set_params(thresholds=value)

    def setFeaturesCol(self, value) -> "LogisticRegression":
        return self._set_params(featuresCol=value) if isinstance(value, str) else self._set_params(featuresCols=value)

    def setLabelCol(self, value: str) -> "LogisticRegression":
        return self._set_params(labelCol=value)

    def setPredictionCol(self, value: str) -> "LogisticRegression":
        return self._set_params(predictionCol=value)

    def setProbabilityCol(self, value: str) -> "LogisticRegression":
        return self._set_params(probabilityCol=value)

    def setRawPredictionCol(self, value: str) -> "LogisticRegression":
        return self._set_params(rawPredictionCol=value)

    def setWeightCol(self, value: str) -> "LogisticRegression":
        return self._set_params(weightCol=value)

    # host-side class discovery is rendezvous-merged below; everything else is
    # one pure SPMD program — correct under multi-process
    _supports_multiprocess = True
    # CSR input fits via the padded-ELL sparse program (ops/sparse.py) without
    # densifying — the reference's sparse qn path (classification.py:975-1098)
    _supports_sparse_input = True
    # full-batch gradients accumulate over row chunks: an over-HBM dataset
    # demotes to ops/streaming.logistic_fit_streaming (smooth L2 path; the
    # L1/elastic-net OWL-QN solver has no out-of-core form and raises the
    # typed HbmBudgetError instead — docs/robustness.md "Memory safety")
    _supports_streaming_fit = True

    def _solver_workspace_terms(
        self, rows_per_device: int, n_cols: int, params: Dict[str, Any], itemsize: int
    ) -> Dict[str, int]:
        # GLM working set: the per-row logits held TWICE (z at the iterate +
        # z along the search direction) and the circular L-BFGS (S, Y)
        # history over the flat parameter vector. Class count is unknown
        # before the fit sees labels: binomial/auto estimate with k_out=1,
        # an explicit multinomial family with a documented floor of 2.
        # (`family` is a Spark param, not a solver param — query it directly.)
        try:
            family = self.getOrDefault("family")
        except Exception:
            family = "auto"
        k_out = 2 if family == "multinomial" else 1
        n_flat = n_cols * k_out + k_out
        mem = int(params.get("lbfgs_memory", 10))
        return {
            "glm_logits": 2 * rows_per_device * k_out * itemsize,
            "lbfgs_history": 2 * mem * n_flat * itemsize,
        }

    def _solver_flop_estimate(self, n_rows: int, n_cols: int) -> Optional[float]:
        # GLM roofline model (ops_plane/efficiency.py): each L-BFGS
        # iteration is dominated by the X·B forward matvec and the Xᵀr
        # gradient matvec, 2·n·d·k_out FLOPs each; pointwise link terms are
        # O(n·k) and omitted. max_iter is an UPPER bound on iterations, so
        # MFU from this estimate is an upper bound too (documented bias).
        try:
            family = self.getOrDefault("family")
        except Exception:
            family = "auto"
        k_out = 2 if family == "multinomial" else 1
        iters = int(self._solver_params.get("max_iter", 100))
        return 4.0 * n_rows * n_cols * k_out * iters

    def _fit_streaming(
        self, inputs: FitInputs, params: Dict[str, Any], classes, labels_host,
        alpha: float, l1_ratio: float,
    ) -> Dict[str, Any]:
        """Out-of-core logistic fit (docs/robustness.md "Memory safety"):
        streamed full-batch GLM quasi-Newton. L1/elastic-net has no
        out-of-core path — OWL-QN's pseudo-gradient projection is not a
        chunk-accumulable reduction — so a demoted L1 fit fails typed."""
        from ..errors import HbmBudgetError
        from ..ops.streaming import logistic_fit_streaming

        if alpha * l1_ratio > 0:
            raise HbmBudgetError(
                "logistic L1/elastic-net fit does not fit device memory and "
                "the OWL-QN solver has no out-of-core streaming path "
                "(set elasticNetParam=0 or raise the budget)",
                largest_term="solver.owlqn",
            )
        multinomial, y_idx_host = self._fit_geometry_host(classes, labels_host)
        statics = self._solver_statics(params)
        common = dict(
            k=len(classes),
            multinomial=multinomial,
            lam_l2=alpha,
            lam_l1=0.0,
            use_l1=False,
            **statics,
        )
        state = logistic_fit_streaming(
            inputs, y_idx_host,
            k=len(classes), multinomial=multinomial, lam_l2=alpha,
            fit_intercept=statics["fit_intercept"],
            standardize=statics["standardize"],
            max_iter=statics["max_iter"], tol=statics["tol"],
            lbfgs_memory=statics["lbfgs_memory"],
            fast=statics["fast"],
            # param-identifying key, mirroring the resident checkpointed
            # fit's "logistic:<params>" — a static key would let sequential
            # param sets of one demoted sweep resume EACH OTHER'S trajectories
            ckpt_key="logistic_stream:" + repr(sorted(common.items())),
        )
        state = {k_: np.asarray(v) for k_, v in state.items()}
        return self._finalize_state(state, classes, inputs, common)

    def _resolve_classes(self, labels_host: np.ndarray, inputs: FitInputs) -> np.ndarray:
        """Sorted global class values for THIS fit's rows. Honors a fold's
        row mask (a weight-masked CV fold must discover classes from its
        TRAIN rows only — physical-split parity) and merges across ranks
        under SPMD (the reference gets this for free because cuML's qn fit
        allgathers label cardinality internally)."""
        import json

        lbl = labels_host if inputs.host_mask is None else labels_host[inputs.host_mask]
        local_classes = np.unique(lbl).astype(np.float64)
        gathered = inputs.allgather_host(json.dumps(local_classes.tolist()))
        return np.unique(np.concatenate([np.asarray(json.loads(g)) for g in gathered]))

    def _degenerate_single_class(self, classes: np.ndarray, inputs: FitInputs) -> Dict[str, Any]:
        # degenerate single-class fit: P(class)=1 (Spark parity,
        # reference classification.py:1122-1135)
        return {
            "coef_": np.zeros((1, inputs.n_cols)),
            "intercept_": np.array([np.inf if classes[0] == 1.0 else -np.inf]),
            "classes_": classes,
            "n_iter_": 0,
            "objective_": 0.0,
            "n_cols": inputs.n_cols,
            "dtype": np.dtype(inputs.dtype).name,
        }

    def _fit_geometry_host(self, classes: np.ndarray, labels_host: np.ndarray):
        """(multinomial, y_idx HOST array) — the label geometry both the
        resident paths (which place y_idx) and the streaming path (which
        slices it per chunk) derive from."""
        family = self.getOrDefault("family")
        k = len(classes)
        multinomial = family == "multinomial" or (family == "auto" and k > 2)
        if family == "binomial" and k > 2:
            raise ValueError(f"family='binomial' but found {k} classes")
        # Under a fold mask, held-out rows may carry labels OUTSIDE the
        # fold's class set; their weight is 0 so they contribute nothing,
        # but the index must stay in [0, k) for the traced gather — clip
        # (exact for every in-set label: classes is sorted unique)
        y_idx_host = np.clip(
            np.searchsorted(classes, labels_host), 0, k - 1
        ).astype(np.int32)
        return multinomial, y_idx_host

    def _fit_geometry(self, classes: np.ndarray, labels_host: np.ndarray, inputs: FitInputs):
        """(multinomial, y_idx device array) shared by the sequential and
        batched solve paths."""
        multinomial, y_idx_host = self._fit_geometry_host(classes, labels_host)
        return multinomial, inputs.put_rows(y_idx_host)

    @staticmethod
    def _finalize_state(state: Dict[str, Any], classes, inputs: FitInputs, common) -> Dict[str, Any]:
        """Host-fetched solver state -> model attribute dict, running the
        shared divergence guard / stall warning / telemetry record."""
        from .. import telemetry
        from ..ops.logistic import check_glm_result, warn_if_early_stall

        check_glm_result(state)
        warn_if_early_stall(
            state, standardize=common["standardize"], max_iter=common["max_iter"]
        )
        if telemetry.enabled():  # gate: the arg fetches sync with the device
            telemetry.record_solver_result(
                "logistic",
                n_iter=int(state["n_iter_"]),
                objective=float(state["objective_"]),
                stalled=bool(np.asarray(state.get("stalled_", False))),
            )
            if "fused_hits_" in state:  # a fit whose loop read X once an iteration
                telemetry.registry().inc("logistic.fused_hits", float(state["fused_hits_"]))
        return {
            "coef_": np.asarray(state["coef_"], dtype=np.float64),
            "intercept_": np.asarray(state["intercept_"], dtype=np.float64),
            "classes_": classes,
            "n_iter_": int(state["n_iter_"]),
            "objective_": float(state["objective_"]),
            "n_cols": inputs.n_cols,
            "dtype": np.dtype(inputs.dtype).name,
        }

    @staticmethod
    def _solver_statics(params: Dict[str, Any]) -> Dict[str, Any]:
        from ..core import resolve_solver_precision

        return dict(
            fit_intercept=bool(params["fit_intercept"]),
            standardize=bool(params["standardization"]),
            max_iter=int(params["max_iter"]),
            tol=float(params["tol"]),
            lbfgs_memory=int(params["lbfgs_memory"]),
            # static of every GLM entry point; also part of the checkpoint
            # key repr, so bf16 and f32 trajectories can never cross-resume
            fast=resolve_solver_precision(params) == "bf16",
        )

    def _resolve_warm_start(self, source: Any) -> Dict[str, Any]:
        """Warm-start payload for `fit(..., warm_start_from=...)`: a fitted
        `LogisticRegressionModel`'s original-space (coef_, intercept_)
        iterate, or a `SolverCheckpoint` carrying one. GLM segment
        checkpoints store the STANDARDIZED flat iterate — dataset-specific
        scaling, not portable across fits — so those are rejected with a
        pointer at the model route (the scheduler resumes them through the
        checkpoint store instead, where the placement is pinned equal)."""
        from .. import checkpoint as _ckpt

        if isinstance(source, _ckpt.SolverCheckpoint):
            st = dict(source.portable or {})
            st.update({k: v for k, v in (source.state or {}).items() if k not in st})
            if "coef_" not in st:
                raise ValueError(
                    "SolverCheckpoint warm start for LogisticRegression needs "
                    "an original-space 'coef_' payload; GLM segment "
                    "checkpoints carry the standardized iterate (dataset-"
                    "specific) — warm-start from the fitted model instead"
                )
            coef = np.asarray(st["coef_"])
            return {
                "coef_": coef,
                "intercept_": np.asarray(
                    st.get("intercept_", np.zeros(coef.shape[0], coef.dtype))
                ),
                "n_iter_": int(st.get("n_iter_", source.iteration) or 0),
            }
        coef = getattr(source, "coef_", None)
        if coef is None:
            raise TypeError(
                f"cannot warm-start LogisticRegression from "
                f"{type(source).__name__}: expected a fitted "
                "LogisticRegressionModel or a SolverCheckpoint"
            )
        coef = np.asarray(coef)
        return {
            "coef_": coef,
            "intercept_": np.asarray(
                getattr(source, "intercept_", np.zeros(coef.shape[0], coef.dtype))
            ),
            "n_iter_": int(np.max(getattr(source, "n_iter_", 0)) or 0),
        }

    def _get_tpu_fit_func(self, extracted: ExtractedData):
        from functools import partial

        import jax

        from .. import checkpoint as _ckpt
        from .. import telemetry
        from ..ops.logistic import (
            GLM_SPECULATED,
            GLM_TWO_PRODUCTS,
            glm_pass_of,
            logistic_fit,
            logistic_fit_checkpointed,
            logistic_fit_ell,
            logistic_fit_ell_checkpointed,
        )

        labels_host = extracted.label

        def _fit(inputs: FitInputs, params: Dict[str, Any]) -> Dict[str, Any]:
            alpha = float(params["alpha"])
            l1_ratio = float(params["l1_ratio"])
            # once-per-fit child spans of `fit/solve` (docs/observability.md):
            # `init` is the host's preparation, `loop` the solver's dispatch
            # (one asynchronous program here), `finish` the wait for it and
            # the model's attributes brought to the host
            with telemetry.span("init"):
                classes = self._resolve_classes(labels_host, inputs)
                if len(classes) > 1 and inputs.stream is None:
                    multinomial, y_idx = self._fit_geometry(classes, labels_host, inputs)
                    common = dict(
                        k=len(classes),
                        multinomial=multinomial,
                        lam_l2=alpha * (1.0 - l1_ratio),
                        lam_l1=alpha * l1_ratio,
                        use_l1=alpha * l1_ratio > 0,
                        **self._solver_statics(params),
                    )
                    # public warm start (fit(..., warm_start_from=...),
                    # docs/scheduling.md "Warm starts"): seed the L-BFGS/OWL-QN
                    # iterate from the donor's original-space coefficients — the
                    # solver rebuilds the standardized flat iterate via the exact
                    # inverse of its own fold-out (ops/logistic._warm_x0)
                    warm_tuple = None
                    _warm = getattr(self, "_warm_start", None)
                    if _warm is not None:
                        k_out = len(classes) if multinomial else 1
                        wcoef = np.asarray(_warm["coef_"])
                        if tuple(wcoef.shape) != (k_out, int(inputs.n_cols)):
                            raise ValueError(
                                f"warm-start coef shape {tuple(wcoef.shape)} does not "
                                f"match this fit (k_out={k_out}, d={inputs.n_cols})"
                            )
                        from .. import telemetry as _telemetry

                        if _telemetry.enabled():
                            reg = _telemetry.registry()
                            reg.inc("fit.warm_starts")
                            reg.inc(
                                "fit.warm_start_iterations_saved",
                                int(_warm.get("n_iter_", 0) or 0),
                            )
                        warm_tuple = (
                            wcoef.astype(inputs.dtype),
                            np.asarray(_warm["intercept_"]).astype(inputs.dtype),
                        )
                    # elastic recovery: with a checkpoint cadence configured and a
                    # store installed by the enclosing recoverable stage, the solver
                    # loop runs host-segmented so an interrupted fit resumes from
                    # the last boundary. Single-controller only: the segment
                    # boundary host-fetches globally-sharded state, which a
                    # multi-process rank cannot address alone.
                    use_ckpt = _ckpt.solver_checkpoints_active() and (
                        inputs.ctx is None or not inputs.ctx.is_spmd
                    )
                    ckpt_common = (
                        dict(
                            ckpt_key="logistic:" + repr(sorted(common.items())),
                            placement_key=_ckpt.placement_key_of(inputs),
                        )
                        if use_ckpt
                        else {}
                    )
            if len(classes) == 1:
                return self._degenerate_single_class(classes, inputs)
            if inputs.stream is not None:
                return self._fit_streaming(
                    inputs, params, classes, labels_host, alpha, l1_ratio
                )
            layout = "ell" if inputs.X_sparse is not None else "dense"
            # one read of X an iteration where this X and this fit allow it
            # (`glm_pass_of`); the checkpointed driver keeps the two products
            glm_pass = GLM_TWO_PRODUCTS
            if layout == "dense" and not use_ckpt:
                glm_pass = glm_pass_of(
                    inputs.X, multinomial=multinomial, use_l1=common["use_l1"], fast=common["fast"]
                )
            fused = glm_pass != GLM_TWO_PRODUCTS
            with telemetry.span(
                "loop", solver_path=layout + ("_checkpointed" if use_ckpt else ""),
                glm_pass="fused" if fused else GLM_TWO_PRODUCTS,
                speculated=GLM_SPECULATED if fused else 0,
            ):
                if inputs.X_sparse is not None:
                    ell_val, ell_idx = inputs.ell_rows()
                    w_dev = inputs.put_rows(np.asarray(inputs.w, dtype=inputs.dtype))
                    fit_fn = logistic_fit_ell_checkpointed if use_ckpt else logistic_fit_ell
                    state = fit_fn(
                        ell_val, ell_idx, y_idx, w_dev, d=inputs.n_cols,
                        warm_start=warm_tuple, **common, **ckpt_common,
                    )
                else:
                    if use_ckpt:
                        fit_fn = logistic_fit_checkpointed
                    else:
                        fit_fn = partial(logistic_fit, glm_pass=glm_pass)
                    state = fit_fn(
                        inputs.X, y_idx, inputs.w, warm_start=warm_tuple,
                        **common, **ckpt_common,
                    )
            with telemetry.span("finish"):
                # ONE device->host fetch of the whole result (every array's copy
                # started before the first is waited for: a wait apiece cost 0.9 ms
                # on a v5e), then the divergence guard runs on the already-fetched
                # scalars (no extra sync)
                with telemetry.device_wait("finish"):
                    state = jax.device_get(state)
                return self._finalize_state(state, classes, inputs, common)

        return _fit

    def _batch_group_key(self, sp: Dict[str, Any]):
        # regParam (alpha) and elasticNetParam (l1_ratio) are TRACED scalars
        # of the solver — a grid over them is one compiled program. The L1
        # solver choice is a derived STATIC (use_l1), so grids mixing
        # L1-on/off split into one batched program per side. Everything else
        # in the solver param dict changes program structure.
        use_l1 = float(sp["alpha"]) * float(sp["l1_ratio"]) > 0
        rest = tuple(sorted((k, repr(v)) for k, v in sp.items() if k not in ("alpha", "l1_ratio")))
        return (use_l1, rest)

    def _get_tpu_batched_fit_func(self, extracted: ExtractedData):
        from .. import telemetry
        from ..ops.logistic import logistic_fit_batched, logistic_fit_ell_batched

        labels_host = extracted.label

        def _fit_batch(inputs: FitInputs, param_sets) -> Optional[list]:
            if telemetry.convergence_trace_enabled():
                # per-iteration host callbacks receive per-grid-point scalars;
                # under vmap they would see batched values — trace sequentially
                return None
            classes = self._resolve_classes(labels_host, inputs)
            if len(classes) == 1:
                return [self._degenerate_single_class(classes, inputs) for _ in param_sets]
            multinomial, y_idx = self._fit_geometry(classes, labels_host, inputs)
            alphas = np.asarray([float(sp["alpha"]) for sp in param_sets])
            l1rs = np.asarray([float(sp["l1_ratio"]) for sp in param_sets])
            lam_l2s = (alphas * (1.0 - l1rs)).astype(inputs.dtype)
            lam_l1s = (alphas * l1rs).astype(inputs.dtype)
            statics = self._solver_statics(param_sets[0])  # uniform per group key
            common = dict(
                k=len(classes),
                multinomial=multinomial,
                use_l1=bool((lam_l1s > 0).any()),
                **statics,
            )
            if inputs.X_sparse is not None:
                ell_val, ell_idx = inputs.ell_rows()
                w_dev = inputs.put_rows(np.asarray(inputs.w, dtype=inputs.dtype))
                stacked = logistic_fit_ell_batched(
                    ell_val, ell_idx, y_idx, w_dev, lam_l2s, lam_l1s,
                    d=inputs.n_cols, **common,
                )
            else:
                stacked = logistic_fit_batched(
                    inputs.X, y_idx, inputs.w, lam_l2s, lam_l1s, **common
                )
            stacked = {k: np.asarray(v) for k, v in stacked.items()}  # ONE fetch
            return [
                self._finalize_state(
                    {k: v[i] for k, v in stacked.items()}, classes, inputs, common
                )
                for i in range(len(param_sets))
            ]

        return _fit_batch

    def _create_model(self, attrs: Dict[str, Any]) -> "LogisticRegressionModel":
        return LogisticRegressionModel(**attrs)

    def _supportsTransformEvaluate(self, evaluator: Any) -> bool:
        if not hasattr(evaluator, "getMetricName"):
            return False
        from ..metrics import MulticlassMetrics

        if evaluator.getMetricName() not in MulticlassMetrics.SUPPORTED_MULTI_CLASS_METRIC_NAMES:
            return False
        if evaluator.hasParam("weightCol") and evaluator.isDefined("weightCol"):
            return False
        return True


class LogisticRegressionModel(_LogisticRegressionParams, _TpuModelWithColumns):
    """Fitted logistic regression model (reference classification.py:1159-1581)."""

    def __init__(
        self,
        coef_: Optional[np.ndarray] = None,
        intercept_: Optional[np.ndarray] = None,
        classes_: Optional[np.ndarray] = None,
        n_iter_: int = 0,
        objective_: float = 0.0,
        n_cols: int = 0,
        dtype: str = "float32",
        **kwargs: Any,
    ) -> None:
        super().__init__(
            coef_=coef_, intercept_=intercept_, classes_=classes_, n_iter_=n_iter_,
            objective_=objective_, n_cols=n_cols, dtype=dtype,
        )
        self.coef_ = np.atleast_2d(np.asarray(coef_))
        self.intercept_ = np.atleast_1d(np.asarray(intercept_))
        self.classes_ = np.asarray(classes_)
        self.n_iter_ = int(n_iter_)
        self.objective_ = float(objective_)
        self.n_cols = int(n_cols)
        self.dtype = dtype

    # -- Spark ML model surface -------------------------------------------
    @property
    def numClasses(self) -> int:
        return len(self.classes_)

    @property
    def numFeatures(self) -> int:
        return self.n_cols

    @property
    def _is_multinomial(self) -> bool:
        return self.coef_.shape[0] > 1

    @property
    def coefficients(self):
        from ..linalg import DenseVector

        if self._is_multinomial:
            raise Exception(
                "Multinomial models contain a matrix of coefficients, use coefficientMatrix instead."
            )
        return DenseVector(self.coef_[0])

    @property
    def intercept(self) -> float:
        if self._is_multinomial:
            raise Exception(
                "Multinomial models contain a vector of intercepts, use interceptVector instead."
            )
        return float(self.intercept_[0])

    @property
    def coefficientMatrix(self) -> np.ndarray:
        return self.coef_

    @property
    def interceptVector(self):
        from ..linalg import DenseVector

        return DenseVector(self.intercept_)

    _spark_converter = "logreg_to_spark"  # `.cpu()` (reference classification.py:1301-1323)

    def setFeaturesCol(self, value) -> "LogisticRegressionModel":
        return self._set_params(featuresCol=value) if isinstance(value, str) else self._set_params(featuresCols=value)

    def setThreshold(self, value: float) -> "LogisticRegressionModel":
        return self._set_params(threshold=value)

    def setProbabilityCol(self, value: str) -> "LogisticRegressionModel":
        return self._set_params(probabilityCol=value)

    def setRawPredictionCol(self, value: str) -> "LogisticRegressionModel":
        return self._set_params(rawPredictionCol=value)

    def setPredictionCol(self, value: str) -> "LogisticRegressionModel":
        return self._set_params(predictionCol=value)

    # -- prediction machinery ---------------------------------------------
    def _get_transform_func(self):
        import jax

        from ..ops.logistic import logistic_predict
        from ..parallel.mesh import default_local_device

        coef_np, intercept_np = self.coef_, self.intercept_
        multinomial = self._is_multinomial
        dtype = np.float32 if self._float32_inputs else np.float64

        def construct():
            dev = default_local_device()
            return (
                jax.device_put(coef_np.astype(dtype), dev),
                jax.device_put(intercept_np.astype(dtype), dev),
            )

        def predict(state, xb):
            coef, b = state
            return logistic_predict(xb.astype(dtype), coef, b, multinomial=multinomial)

        return construct, predict, None

    def _serve_workspace_terms(self, bucket_rows_count, itemsize):
        # per-bucket predict workspace (docs/serving.md): the raw-margin and
        # probability blocks logistic_predict materializes, [bucket, k] each
        k_out = max(2, int(np.asarray(self.coef_).shape[0]))
        return {"logits": 2 * int(bucket_rows_count) * k_out * itemsize}

    def _serve_flop_estimate(self, n_rows, n_cols):
        # roofline numerator per dispatched bucket: the X @ coef.T matmul
        # (2*n*d*k) dominates; softmax/sigmoid epilogue omitted (lower bound)
        k_out = max(1, int(np.asarray(self.coef_).shape[0]))
        return 2.0 * n_rows * n_cols * k_out

    def _raw_prob(self, features) -> tuple:
        """Batched (raw, prob) arrays for a host feature block."""
        if np.isinf(self.intercept_).any():
            # degenerate single-class model
            n = features.shape[0]
            return np.tile(self.intercept_, (n, 1)), np.ones((n, 1))
        raw, prob = self._transform_arrays(features)
        return raw.astype(np.float64), prob.astype(np.float64)

    def _predict_from_prob(self, prob: np.ndarray) -> np.ndarray:
        if self.numClasses == 1:
            return np.full(prob.shape[0], float(self.classes_[0]))
        if self.isDefined("thresholds"):
            t = np.asarray(self.getOrDefault("thresholds"))
            idx = np.argmax(prob / t[None, :], axis=1)
        elif not self._is_multinomial and self.numClasses == 2:
            idx = (prob[:, 1] > self.getThreshold()).astype(int)
        else:
            idx = np.argmax(prob, axis=1)
        return self.classes_[idx].astype(np.float64)

    def transform(self, dataset: Any):
        pdf = as_pandas(dataset)
        extracted = self._pre_process_data(dataset, for_fit=False)
        raw, prob = self._raw_prob(extracted.features)
        out = pdf.copy(deep=False)
        as_vec = extracted.feature_kind == "vector"
        raw_col = vectors_to_pandas_column(raw) if as_vec else list(raw)
        prob_col = vectors_to_pandas_column(prob) if as_vec else list(prob)
        out[self.getOrDefault("rawPredictionCol")] = raw_col
        out[self.getOrDefault("probabilityCol")] = prob_col
        out[self.getOrDefault("predictionCol")] = self._predict_from_prob(prob)
        return out

    def predict(self, value) -> float:
        """Single-vector predict (Spark ML model surface)."""
        from ..linalg import Vector

        v = value.toArray() if isinstance(value, Vector) else np.asarray(value)
        _, prob = self._raw_prob(v[None, :])
        return float(self._predict_from_prob(prob)[0])

    def predictProbability(self, value):
        from ..linalg import DenseVector, Vector

        v = value.toArray() if isinstance(value, Vector) else np.asarray(value)
        _, prob = self._raw_prob(v[None, :])
        return DenseVector(prob[0])

    def predictRaw(self, value):
        """Raw margin scores per class (Spark surface; computed natively —
        the reference delegates to .cpu(), classification.py:1559-1576)."""
        from ..linalg import DenseVector, Vector

        v = value.toArray() if isinstance(value, Vector) else np.asarray(value)
        raw, _ = self._raw_prob(v[None, :])
        return DenseVector(raw[0])

    def evaluate(self, dataset):
        """Evaluate on a dataset via the converted JVM model's summary (the
        reference's exact behavior, classification.py:1592-1599). Accepts
        framework datasets (pandas/arrow/dict) or a Spark DataFrame."""
        from ..spark_interop import as_spark_df

        return self.cpu().evaluate(as_spark_df(dataset))

    @property
    def summary(self):
        """No training summary is retained (reference parity,
        classification.py:1550-1557)."""
        raise RuntimeError(
            f"No training summary available for this {type(self).__name__}"
        )

    # -- fused CV path ------------------------------------------------------
    def _combine(self, models: List["LogisticRegressionModel"]) -> "LogisticRegressionModel":
        combined = LogisticRegressionModel(
            coef_=self.coef_, intercept_=self.intercept_, classes_=self.classes_,
            n_iter_=self.n_iter_, objective_=self.objective_, n_cols=self.n_cols, dtype=self.dtype,
        )
        combined._sub_models = list(models)
        self._copyValues(combined)
        self._copy_solver_params(combined)
        return combined

    def _transform_evaluate(self, dataset: Any, evaluator: Any) -> List[float]:
        """Score ALL packed models in one pass over a DATASET (extracts the
        feature block, then delegates to `_transform_evaluate_arrays`)."""
        from ..core import evaluator_label_column

        pdf = as_pandas(dataset)
        label = pdf[evaluator_label_column(self, evaluator)].to_numpy(dtype=np.float64)
        extracted = self._pre_process_data(dataset, for_fit=False)
        return self._transform_evaluate_arrays(extracted.features, label, evaluator)

    def _transform_evaluate_arrays(
        self, features: Any, label: np.ndarray, evaluator: Any
    ) -> List[float]:
        """Score ALL packed models over already-extracted blocks — the array
        entry point CrossValidator uses to score held-out rows by slicing
        the one ingested block (no pandas round-trip)."""
        from ..metrics import MulticlassMetrics

        assert hasattr(self, "_sub_models"), "call _combine first"
        want_logloss = evaluator.getMetricName() == "logLoss"
        eps = evaluator.getOrDefault("eps") if evaluator.hasParam("eps") else 1e-15
        scores = []
        for m in self._sub_models:
            _, prob = m._raw_prob(features)
            prediction = m._predict_from_prob(prob)
            pairs = np.stack([label, prediction], axis=1)
            uniq, inverse = np.unique(pairs, axis=0, return_inverse=True)
            counts = np.bincount(inverse, minlength=len(uniq)).astype(np.float64)
            confusion = {
                (float(uniq[i, 0]), float(uniq[i, 1])): float(counts[i]) for i in range(len(uniq))
            }
            log_loss = None
            if want_logloss:
                # exact class membership: labels unseen by this fold's model get
                # probability eps (the model assigns them ~0 mass)
                cls_idx = np.searchsorted(m.classes_, label)
                cls_idx_safe = np.clip(cls_idx, 0, len(m.classes_) - 1)
                known = m.classes_[cls_idx_safe] == label
                p_raw = prob[np.arange(len(label)), cls_idx_safe]
                p_true = np.clip(np.where(known, p_raw, 0.0), eps, 1 - eps)
                log_loss = float(np.sum(-np.log(p_true)))
            scores.append(MulticlassMetrics.from_confusion(confusion, log_loss).evaluate(evaluator))
        return scores
