#
# Regression algorithms: LinearRegression (+Ridge/Lasso/ElasticNet via params).
# RandomForestRegressor joins this module when the tree family lands
# (mirroring reference regression.py which hosts both).
#
# API-parity target: reference regression.py:176-797, drop-in for
# `pyspark.ml.regression.LinearRegression`. Solver selection by reg params
# matches the reference (regression.py:510-548): OLS / Ridge(alpha·m) / CD.
#
from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import numpy as np

from .. import telemetry
from ..core import FitInputs, _TpuEstimatorSupervised, _TpuModelWithColumns, pred
from ..data import ExtractedData
from ..params import (
    HasElasticNetParam,
    HasEnableSparseDataOptim,
    HasFeaturesCol,
    HasFeaturesCols,
    HasFitIntercept,
    HasLabelCol,
    HasMaxIter,
    HasPredictionCol,
    HasRegParam,
    HasStandardization,
    HasTol,
    HasWeightCol,
    Param,
    TypeConverters,
)


from .tree import _RandomForestEstimator, _RandomForestModel


class RandomForestRegressor(_RandomForestEstimator):
    """RandomForestRegressor, drop-in for
    ``pyspark.ml.regression.RandomForestRegressor`` (reference
    regression.py:799-1080). Variance split criterion; ensemble split across
    the mesh like the classifier."""

    _is_classification = False

    def __init__(self, **kwargs: Any) -> None:
        super().__init__(**kwargs)
        self._setDefault(impurity="variance")
        if self._solver_params.get("split_criterion") is None:
            self._solver_params["split_criterion"] = "variance"

    def _set_params(self, **kwargs):
        if "impurity" in kwargs and kwargs["impurity"] != "variance":
            raise ValueError("impurity must be 'variance' for regression")
        return super()._set_params(**kwargs)

    def _create_model(self, attrs: Dict[str, Any]) -> "RandomForestRegressionModel":
        return RandomForestRegressionModel(**attrs)


class RandomForestRegressionModel(_RandomForestModel):
    """Fitted RF regression model."""

    _is_classification = False

    def _leaf_values(self) -> np.ndarray:
        # node mean: Σwy / Σw, kept as [M, 1]
        w = self.node_stats[..., 0]
        wy = self.node_stats[..., 1]
        return (wy / np.maximum(w, 1e-30))[..., None]

    def _out_column_names(self) -> List[str]:
        return [self.getOrDefault("predictionCol")]

    def _split_output(self, result, names, extracted):
        return {names[0]: np.asarray(result)[:, 0]}

    def predict(self, value) -> float:
        from ..linalg import Vector

        v = value.toArray() if isinstance(value, Vector) else np.asarray(value)
        return float(np.asarray(self._raw_forest_output(v[None, :]))[0, 0])


class _LinearRegressionParams(
    HasEnableSparseDataOptim,
    HasFeaturesCol,
    HasFeaturesCols,
    HasLabelCol,
    HasPredictionCol,
    HasMaxIter,
    HasTol,
    HasRegParam,
    HasElasticNetParam,
    HasFitIntercept,
    HasStandardization,
    HasWeightCol,
):
    solver = Param("solver", "solver algorithm: 'auto', 'normal' or 'eig'", TypeConverters.toString)
    loss = Param("loss", "loss function: only 'squaredError'", TypeConverters.toString)

    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        # mirrors reference regression.py param mapping
        return {
            "maxIter": "max_iter",
            "regParam": "alpha",
            "elasticNetParam": "l1_ratio",
            "tol": "tol",
            "fitIntercept": "fit_intercept",
            "standardization": "normalize",
            "solver": "solver",
            "loss": "loss",
            "weightCol": "",
        }

    @classmethod
    def _param_value_mapping(cls):
        def _solver(v):
            return {"auto": "eig", "normal": "eig", "eig": "eig"}.get(v)

        def _loss(v):
            return "squared_loss" if v in ("squaredError", "squared_loss") else None

        return {"solver": _solver, "loss": _loss}

    def _get_solver_params_default(self) -> Dict[str, Any]:
        return {
            "alpha": 0.0001,
            "l1_ratio": 0.0,
            "fit_intercept": True,
            "normalize": False,
            "max_iter": 1000,
            "tol": 1e-3,
            "solver": "eig",
            "loss": "squared_loss",
            "verbose": False,
            # per-estimator override of config["solver_precision"]; "bf16"
            # runs the sufficient-statistics gram contraction bf16-in /
            # f32-accumulate; the replicated solve stays full precision
            "solver_precision": None,
        }


def _model_attrs(out: Dict[str, Any], inputs: FitInputs) -> Dict[str, Any]:
    """A host-fetched solve state (ops/linear `_solve_from_stats`) as the
    model's attributes; `rss_` and `sw_` are the training summary's sums."""
    return {
        "coef_": np.asarray(out["coef_"]),
        "intercept_": float(out["intercept_"]),
        "n_iter_": int(out["n_iter_"]),
        "rss_": float(out["rss_"]),
        "sw_": float(out["sw_"]),
        "n_cols": inputs.n_cols,
        "dtype": np.dtype(inputs.dtype).name,
    }


class LinearRegression(_LinearRegressionParams, _TpuEstimatorSupervised):
    """LinearRegression estimator, drop-in for ``pyspark.ml.regression.LinearRegression``.

    One distributed pass builds the normal-equation sufficient statistics
    (XᵀWX/XᵀWy psum across the rows mesh); OLS/Ridge solve locally, L1/EN runs
    gram-space coordinate descent — no further passes over the data. The Ridge
    path scales alpha by Σw for Spark objective parity (reference
    regression.py:536-542).
    """

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._setDefault(
            maxIter=100, regParam=0.0, elasticNetParam=0.0, tol=1e-6,
            fitIntercept=True, standardization=True, solver="auto", loss="squaredError",
        )
        self._set_params(**kwargs)

    def setMaxIter(self, value: int) -> "LinearRegression":
        return self._set_params(maxIter=value)

    def setRegParam(self, value: float) -> "LinearRegression":
        return self._set_params(regParam=value)

    def setElasticNetParam(self, value: float) -> "LinearRegression":
        return self._set_params(elasticNetParam=value)

    def setTol(self, value: float) -> "LinearRegression":
        return self._set_params(tol=value)

    def setFitIntercept(self, value: bool) -> "LinearRegression":
        return self._set_params(fitIntercept=value)

    def setStandardization(self, value: bool) -> "LinearRegression":
        return self._set_params(standardization=value)

    def setLoss(self, value: str) -> "LinearRegression":
        return self._set_params(loss=value)

    def setFeaturesCol(self, value) -> "LinearRegression":
        return self._set_params(featuresCol=value) if isinstance(value, str) else self._set_params(featuresCols=value)

    def setLabelCol(self, value: str) -> "LinearRegression":
        return self._set_params(labelCol=value)

    def setPredictionCol(self, value: str) -> "LinearRegression":
        return self._set_params(predictionCol=value)

    def setWeightCol(self, value: str) -> "LinearRegression":
        return self._set_params(weightCol=value)

    # fit is one pure SPMD program over (X, y, w): correct under multi-process
    _supports_multiprocess = True
    # CSR fits via the padded-ELL gram accumulation (ops/linear.py
    # linear_fit_ell) with full dense parity — centering happens on the
    # sufficient statistics, never the data
    _supports_sparse_input = True
    # sufficient statistics are accumulable over row chunks: an over-HBM
    # dataset demotes to ops/streaming.linear_fit_streaming (dense + ELL)
    _supports_streaming_fit = True

    def _solver_workspace_terms(
        self, rows_per_device: int, n_cols: int, params: Dict[str, Any], itemsize: int
    ) -> Dict[str, int]:
        # the replicated normal-equation solve: gram (d,d) + the handful of
        # d-vectors of the sufficient-statistics tuple (sx, c, scale, coef)
        return {
            "gram": n_cols * n_cols * itemsize,
            "vectors": 4 * n_cols * itemsize,
        }

    def _solver_flop_estimate(self, n_rows: int, n_cols: int) -> Optional[float]:
        # normal-equation roofline model (ops_plane/efficiency.py): the
        # XᵀX gram accumulation (2·n·d²) plus Xᵀy (2·n·d); the (d,d) solve
        # and any elastic-net CD sweeps over the gram are O(d²·iters) and
        # omitted — with n ≫ d this is a tight lower bound on the work.
        return 2.0 * n_rows * n_cols * (n_cols + 1)

    def _get_tpu_fit_func(self, extracted: ExtractedData):
        from .. import checkpoint as _ckpt
        from ..ops.linear import (
            linear_fit,
            linear_fit_checkpointed,
            linear_fit_ell,
            linear_fit_ell_checkpointed,
        )

        def _fit(inputs: FitInputs, params: Dict[str, Any]) -> Dict[str, Any]:
            alpha = float(params["alpha"])
            l1_ratio = float(params["l1_ratio"])
            use_cd = bool(alpha > 0 and l1_ratio > 0)
            from ..core import resolve_solver_precision

            common = dict(
                alpha=alpha,
                l1_ratio=l1_ratio,
                fit_intercept=bool(params["fit_intercept"]),
                standardize=bool(params.get("normalize", False)),
                use_cd=use_cd,
                max_iter=int(params["max_iter"]),
                tol=float(params["tol"]),
                # static of every linear entry point (and of the retained-
                # statistics checkpoint key: bf16 stats are keyed apart)
                fast=resolve_solver_precision(params) == "bf16",
            )
            # elastic recovery: retain the sufficient statistics (the one
            # data pass) on host so a transient retry — and every further
            # sequential param set in this fit stage — solves without
            # another pass over the data. The stats never depend on
            # alpha/l1_ratio, so one key serves the whole sweep.
            use_ckpt = _ckpt.solver_checkpoints_active() and (
                inputs.ctx is None or not inputs.ctx.is_spmd
            )
            ckpt_common = (
                dict(placement_key=_ckpt.placement_key_of(inputs))
                if use_ckpt
                else {}
            )
            if inputs.stream is not None:
                # out-of-core: streamed statistics passes, same replicated
                # solve (docs/robustness.md "Memory safety")
                from ..ops.streaming import linear_fit_streaming

                state = linear_fit_streaming(inputs, **common)
            elif inputs.X_sparse is not None:
                ell_val, ell_idx = inputs.ell_rows()
                fit_fn = linear_fit_ell_checkpointed if use_ckpt else linear_fit_ell
                state = fit_fn(
                    ell_val,
                    ell_idx,
                    inputs.put_rows(np.asarray(inputs.y, dtype=inputs.dtype)),
                    inputs.put_rows(np.asarray(inputs.w, dtype=inputs.dtype)),
                    d=inputs.n_cols,
                    **common,
                    **ckpt_common,
                )
            else:
                fit_fn = linear_fit_checkpointed if use_ckpt else linear_fit
                state = fit_fn(inputs.X, inputs.y, inputs.w, mesh=inputs.mesh, **common, **ckpt_common)
            # once-per-fit child spans of `fit/solve` (docs/observability.md):
            # `gram` and `cd` / `normal` inside the calls above, `finish` the
            # fetch of the model's attributes
            with telemetry.span("finish"), telemetry.device_wait("finish"):  # the five attributes in one fetch
                out = jax.device_get(state)
            return _model_attrs(out, inputs)

        return _fit

    def _batch_group_key(self, sp: Dict[str, Any]):
        # regParam (alpha) and elasticNetParam (l1_ratio) are TRACED scalars
        # of the normal-equation / gram-CD solve; the solver choice use_cd is
        # a derived STATIC, so grids mixing elastic-net and ridge/OLS points
        # split into one batched program per solver. A whole batched grid
        # costs ONE sufficient-statistics pass over the data.
        use_cd = float(sp["alpha"]) > 0 and float(sp["l1_ratio"]) > 0
        rest = tuple(sorted((k, repr(v)) for k, v in sp.items() if k not in ("alpha", "l1_ratio")))
        return (use_cd, rest)

    def _get_tpu_batched_fit_func(self, extracted: ExtractedData):
        from ..ops.linear import linear_fit_batched, linear_fit_ell_batched

        def _fit_batch(inputs: FitInputs, param_sets) -> Optional[list]:
            alphas = np.asarray([float(sp["alpha"]) for sp in param_sets], dtype=inputs.dtype)
            l1rs = np.asarray([float(sp["l1_ratio"]) for sp in param_sets], dtype=inputs.dtype)
            p0 = param_sets[0]  # statics are uniform per group key
            from ..core import resolve_solver_precision

            common = dict(
                fit_intercept=bool(p0["fit_intercept"]),
                standardize=bool(p0.get("normalize", False)),
                use_cd=bool(alphas[0] > 0 and l1rs[0] > 0),
                max_iter=int(p0["max_iter"]),
                tol=float(p0["tol"]),
                fast=resolve_solver_precision(p0) == "bf16",
            )
            if inputs.X_sparse is not None:
                ell_val, ell_idx = inputs.ell_rows()
                stacked = linear_fit_ell_batched(
                    ell_val,
                    ell_idx,
                    inputs.put_rows(np.asarray(inputs.y, dtype=inputs.dtype)),
                    inputs.put_rows(np.asarray(inputs.w, dtype=inputs.dtype)),
                    alphas, l1rs, d=inputs.n_cols, **common,
                )
            else:
                stacked = linear_fit_batched(
                    inputs.X, inputs.y, inputs.w, alphas, l1rs, mesh=inputs.mesh, **common
                )
            with telemetry.span("finish"), telemetry.device_wait("finish"):  # ONE fetch
                stacked = jax.device_get(stacked)
            return [
                _model_attrs({k: v[i] for k, v in stacked.items()}, inputs)
                for i in range(len(param_sets))
            ]

        return _fit_batch

    def _create_model(self, attrs: Dict[str, Any]) -> "LinearRegressionModel":
        return LinearRegressionModel(**attrs)

    def _supportsTransformEvaluate(self, evaluator: Any) -> bool:
        if not hasattr(evaluator, "getMetricName"):
            return False
        if evaluator.getMetricName() not in ("rmse", "mse", "r2", "mae", "var"):
            return False
        # weighted evaluation must take the fallback path (the fused pass
        # produces unweighted sufficient stats)
        if evaluator.hasParam("weightCol") and evaluator.isDefined("weightCol"):
            return False
        return True


class LinearRegressionModel(_LinearRegressionParams, _TpuModelWithColumns):
    """Fitted linear regression model (reference regression.py:616-797)."""

    def __init__(
        self,
        coef_: Optional[np.ndarray] = None,
        intercept_: float = 0.0,
        n_iter_: int = 0,
        n_cols: int = 0,
        dtype: str = "float32",
        rss_: float = float("nan"),
        sw_: float = 0.0,
        **kwargs: Any,
    ) -> None:
        super().__init__(
            coef_=coef_, intercept_=intercept_, n_iter_=n_iter_, n_cols=n_cols, dtype=dtype,
            rss_=rss_, sw_=sw_,
        )
        self.coef_ = np.asarray(coef_)
        self.intercept_ = float(intercept_)
        self.n_iter_ = int(n_iter_)
        # the training summary's sums: weighted residual sum of squares and
        # Σw (rmse = sqrt(rss_ / sw_)); nan / 0 for a model built without a fit
        self.rss_ = float(rss_)
        self.sw_ = float(sw_)
        self.n_cols = int(n_cols)
        self.dtype = dtype

    # -- Spark ML model surface -------------------------------------------
    @property
    def coefficients(self):
        from ..linalg import DenseVector

        return DenseVector(self.coef_)

    @property
    def intercept(self) -> float:
        return self.intercept_

    @property
    def numFeatures(self) -> int:
        return self.n_cols

    @property
    def hasSummary(self) -> bool:
        return False

    @property
    def scale(self) -> float:
        """Huber loss is unsupported (squaredError only); 1.0 for API
        compatibility (reference regression.py:699-703)."""
        return 1.0

    def evaluate(self, dataset):
        """Evaluate on a dataset via the converted JVM model's summary
        (reference regression.py:711-715). Accepts framework datasets
        (pandas/arrow/dict) or a Spark DataFrame."""
        from ..spark_interop import as_spark_df

        return self.cpu().evaluate(as_spark_df(dataset))

    def setFeaturesCol(self, value) -> "LinearRegressionModel":
        return self._set_params(featuresCol=value) if isinstance(value, str) else self._set_params(featuresCols=value)

    def setPredictionCol(self, value: str) -> "LinearRegressionModel":
        return self._set_params(predictionCol=value)

    def predict(self, value) -> float:
        """Single-vector predict (Spark ML model surface)."""
        from ..linalg import Vector

        v = value.toArray() if isinstance(value, Vector) else np.asarray(value)
        return float(v @ self.coef_ + self.intercept_)

    _spark_converter = "linreg_to_spark"  # `.cpu()` (reference regression.py:658-672)

    def _out_column_names(self) -> List[str]:
        return [self.getOrDefault("predictionCol")]

    # -- fused CV path (reference regression.py:762-785, 90-142) -----------
    def _combine(self, models: List["LinearRegressionModel"]) -> "LinearRegressionModel":
        """Pack N fitted models into one multi-model (coef_ stacked [m, d])."""
        combined = LinearRegressionModel(
            coef_=np.stack([m.coef_ for m in models]),
            intercept_=0.0,
            n_iter_=self.n_iter_,
            n_cols=self.n_cols,
            dtype=self.dtype,
        )
        combined._intercepts = np.asarray([m.intercept_ for m in models])
        self._copyValues(combined)
        self._copy_solver_params(combined)
        return combined

    def _transform_evaluate(self, dataset: Any, evaluator: Any) -> List[float]:
        """Score ALL packed models in one pass over a DATASET (extracts the
        feature block, then delegates to `_transform_evaluate_arrays`)."""
        from ..core import evaluator_label_column
        from ..data import as_pandas

        extracted = self._pre_process_data(dataset, for_fit=False)
        # the evaluator's labelCol governs scoring (it may differ from the model's)
        label = as_pandas(dataset)[evaluator_label_column(self, evaluator)].to_numpy(
            dtype=np.float64
        )
        return self._transform_evaluate_arrays(extracted.features, label, evaluator)

    def _transform_evaluate_arrays(
        self, features: Any, label: np.ndarray, evaluator: Any
    ) -> List[float]:
        """Score ALL packed models over already-extracted blocks: predictions
        [n, m] via a single MXU matmul, then per-model regression sufficient
        stats. The array entry point exists so CrossValidator can score a
        held-out fold by SLICING the one ingested block instead of
        round-tripping the fold through pandas and re-extracting it."""
        from ..metrics import RegressionMetrics

        assert self.coef_.ndim == 2 and hasattr(self, "_intercepts"), "call _combine first"
        feats = features
        if hasattr(feats, "todense"):
            feats = np.asarray(feats.todense())
        preds = np.asarray(feats, dtype=np.float64) @ self.coef_.T + self._intercepts[None, :]  # [n, m]
        return [
            RegressionMetrics.from_values(label, preds[:, j]).evaluate(evaluator)
            for j in range(preds.shape[1])
        ]

    def _get_transform_func(self):
        import jax

        from ..ops.linear import linear_predict
        from ..parallel.mesh import default_local_device

        coef = self.coef_
        intercept = self.intercept_
        dtype = np.float32 if self._float32_inputs else np.float64

        def construct():
            dev = default_local_device()
            return (
                jax.device_put(coef.astype(dtype), dev),
                jax.device_put(np.asarray(intercept, dtype=dtype), dev),
            )

        def predict(state, xb):
            c, b = state
            return linear_predict(xb.astype(dtype), c, b)

        return construct, predict, None

    def _serve_workspace_terms(self, bucket_rows_count, itemsize):
        # per-bucket predict workspace (docs/serving.md): one prediction
        # scalar per row
        return {"pred": int(bucket_rows_count) * itemsize}

    def _serve_flop_estimate(self, n_rows, n_cols):
        # roofline numerator: the X @ coef dot per row (2*n*d)
        return 2.0 * n_rows * n_cols
