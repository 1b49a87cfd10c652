#
# Feature algorithms: PCA.
#
# API-parity target: reference feature.py:106-447 (`PCA`/`PCAModel`), itself a
# drop-in for `pyspark.ml.feature.PCA`. The distributed strategy is identical in
# math (rank-local covariance contribution + allreduce + eig; SURVEY.md §2.2),
# but executed as one SPMD jit program over the rows mesh instead of a barrier
# stage of cuML MG calls.
#
from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import numpy as np

from .. import telemetry
from ..core import FitInputs, _TpuEstimator, _TpuModelWithColumns
from ..data import ExtractedData
from ..params import (
    HasFeaturesCol,
    HasFeaturesCols,
    HasInputCol,
    HasInputCols,
    HasOutputCol,
    Param,
    TypeConverters,
)


class _PCAParams(HasInputCol, HasInputCols, HasFeaturesCol, HasFeaturesCols, HasOutputCol):
    k = Param("k", "the number of principal components", TypeConverters.toInt)

    def getK(self) -> int:
        return self.getOrDefault("k")

    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        # reference feature.py param mapping: Spark `k` -> cuml `n_components`
        return {"k": "n_components"}

    def _get_solver_params_default(self) -> Dict[str, Any]:
        # mirrors cuML PCA(MG) kwargs the reference exposes via cuml_params
        return {
            "n_components": 1,
            "svd_solver": "auto",
            "whiten": False,
            "verbose": False,
            # per-estimator override of config["solver_precision"]; "bf16"
            # runs the covariance contraction bf16-in/f32-accumulate; the
            # eigendecomposition and reported variances stay full precision
            "solver_precision": None,
        }


class PCA(_PCAParams, _TpuEstimator):
    """PCA estimator, drop-in for ``pyspark.ml.feature.PCA``.

    >>> PCA(k=2, inputCol="features").fit(df).transform(df)

    Distributed fit: single pass computing the weighted mean + d×d covariance
    with an MXU contraction per row shard and a GSPMD psum across chips, then a
    replicated top-k symmetric eig (a block subspace iteration on k + p
    columns; the full decomposition only where the block cannot answer:
    ops/linalg.topk_eigh) with sign canonicalization — the TPU-native
    equivalent of the reference's `PCAMG.fit(parts, m, n, parts_rank_size, rank)`
    (reference feature.py:222-241).
    """

    # fit is one pure SPMD program over (X, w): correct under multi-process
    _supports_multiprocess = True
    # the (mean, covariance) statistics are accumulable over row chunks: an
    # over-HBM dataset demotes to ops/streaming.pca_fit_streaming
    _supports_streaming_fit = True

    def _solver_workspace_terms(
        self, rows_per_device: int, n_cols: int, params: Dict[str, Any], itemsize: int
    ) -> Dict[str, int]:
        # the replicated d x d covariance, the mean d-vector, and what the
        # eigensolver that runs holds (ops/linalg.topk_eigh): the block
        # iteration a handful of [d, block] arrays, the full decomposition a
        # d x d eigenvector output beside its input
        from ..ops.linalg import subspace_block

        block = subspace_block(n_cols, int(params.get("n_components") or 1))
        eig = 6 * n_cols * block if block is not None else n_cols * n_cols
        return {
            "covariance": n_cols * n_cols * itemsize,
            "eigensolve": eig * itemsize,
            "vectors": 2 * n_cols * itemsize,
        }

    def _solver_flop_estimate(self, n_rows: int, n_cols: int) -> Optional[float]:
        # PCA roofline model (ops_plane/efficiency.py): the covariance
        # einsum (2·n·d²) dominates. The eigensolve is omitted: the block
        # iteration is ~10 products of 2·d²·16, five orders below at the
        # protocol's shape; the full decomposition (~9·d³), taken only where
        # the block cannot answer, is negligible at n ≫ d.
        return 2.0 * n_rows * n_cols * n_cols

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._setDefault(k=1)
        self._set_params(**kwargs)

    def setK(self, value: int) -> "PCA":
        return self._set_params(k=value)

    def setInputCol(self, value: str) -> "PCA":
        return self._set_params(inputCol=value) if isinstance(value, str) else self._set_params(inputCols=value)

    def setInputCols(self, value: List[str]) -> "PCA":
        return self._set_params(inputCols=value)

    def setOutputCol(self, value: str) -> "PCA":
        return self._set_params(outputCol=value)

    def _get_tpu_fit_func(self, extracted: ExtractedData):
        from .. import checkpoint as _ckpt
        from ..ops.pca import (
            check_pca_state,
            pca_fit,
            pca_fit_checkpointed,
            record_pca_fit,
        )

        def _fit(inputs: FitInputs, params: Dict[str, Any]) -> Dict[str, Any]:
            from ..core import resolve_solver_precision

            k = int(params["n_components"])
            fast = resolve_solver_precision(params) == "bf16"
            if k < 1:
                raise ValueError(f"k must be >= 1, got {k}")
            if k > inputs.n_cols:
                raise ValueError(f"k={k} exceeds the number of features {inputs.n_cols}")
            if inputs.stream is not None:
                # out-of-core: two streamed passes (mean, then centered
                # covariance), same finish kernel as the resident fit
                from ..ops.streaming import pca_fit_streaming

                state = pca_fit_streaming(inputs, k=k, fast=fast)
            # elastic recovery: retain the (mean, covariance) statistics so a
            # transient retry (or a k sweep in this stage) skips the data pass
            elif _ckpt.solver_checkpoints_active() and (
                inputs.ctx is None or not inputs.ctx.is_spmd
            ):
                state = pca_fit_checkpointed(
                    inputs.X, inputs.w, k=k, fast=fast,
                    placement_key=_ckpt.placement_key_of(inputs), mesh=inputs.mesh,
                )
            else:
                state = pca_fit(inputs.X, inputs.w, k=k, fast=fast, mesh=inputs.mesh)
            # once-per-fit child spans of `fit/solve` (docs/observability.md):
            # `gram` and `eig` inside the calls above, `finish` the fetch of
            # the model's attributes
            with telemetry.span("finish"), telemetry.device_wait("finish"):  # the five attributes in one fetch
                out = {name: np.asarray(v) for name, v in jax.device_get(state).items()}
            check_pca_state(out, k=k)  # guard on the host-fetched attributes
            record_pca_fit(out, k=k)
            out["n_cols"] = inputs.n_cols
            out["dtype"] = np.dtype(inputs.dtype).name
            return out

        return _fit

    def _create_model(self, attrs: Dict[str, Any]) -> "PCAModel":
        return PCAModel(**attrs)


class PCAModel(_PCAParams, _TpuModelWithColumns):
    """Fitted PCA model (reference feature.py:281-447 `PCAModel`).

    Exposes both the Spark ML surface (``pc``, ``explainedVariance``, ``mean``)
    and the solver-native attributes (``components_`` etc.).
    """

    def __init__(
        self,
        mean_: Optional[np.ndarray] = None,
        components_: Optional[np.ndarray] = None,
        explained_variance_: Optional[np.ndarray] = None,
        explained_variance_ratio_: Optional[np.ndarray] = None,
        singular_values_: Optional[np.ndarray] = None,
        n_cols: int = 0,
        dtype: str = "float32",
        **kwargs: Any,
    ) -> None:
        super().__init__(
            mean_=mean_,
            components_=components_,
            explained_variance_=explained_variance_,
            explained_variance_ratio_=explained_variance_ratio_,
            singular_values_=singular_values_,
            n_cols=n_cols,
            dtype=dtype,
        )
        self.mean_ = np.asarray(mean_)
        self.components_ = np.asarray(components_)
        self.explained_variance_ = np.asarray(explained_variance_)
        self.explained_variance_ratio_ = np.asarray(explained_variance_ratio_)
        self.singular_values_ = np.asarray(singular_values_)
        self.n_cols = int(n_cols)
        self.dtype = dtype
        self._setDefault(k=int(self.components_.shape[0]) if components_ is not None else 1)

    # -- Spark ML model surface -------------------------------------------
    @property
    def mean(self) -> List[float]:
        return self.mean_.tolist()

    @property
    def pc(self) -> np.ndarray:
        """Principal components as a d×k column matrix (Spark's DenseMatrix layout)."""
        return self.components_.T

    @property
    def explainedVariance(self) -> np.ndarray:
        """Variance ratio per component (Spark parity: ratio, not raw variance)."""
        return self.explained_variance_ratio_

    _spark_converter = "pca_to_spark"  # `.cpu()` (reference feature.py:365-379)

    def setInputCol(self, value: str) -> "PCAModel":
        return self._set_params(inputCol=value) if isinstance(value, str) else self._set_params(inputCols=value)

    def setOutputCol(self, value: str) -> "PCAModel":
        return self._set_params(outputCol=value)

    def _out_column_names(self) -> List[str]:
        if self.hasParam("outputCol") and self.isDefined("outputCol"):
            return [self.getOrDefault("outputCol")]
        return [f"{self.uid}__output"]

    def _get_transform_func(self):
        from ..ops.pca import pca_transform
        from ..parallel.mesh import default_local_device

        components = self.components_
        explained_variance = self.explained_variance_
        whiten = bool(self._solver_params.get("whiten", False))
        dtype = np.float32 if self._float32_inputs else np.float64

        def construct():
            dev = default_local_device()
            return (
                jax.device_put(components.astype(dtype), dev),
                jax.device_put(explained_variance.astype(dtype), dev),
            )

        def predict(state, xb):
            comps, ev = state
            return pca_transform(xb.astype(dtype), comps, ev, whiten=whiten)

        return construct, predict, None

    def _serve_workspace_terms(self, bucket_rows_count, itemsize):
        # per-bucket predict workspace (docs/serving.md): the [bucket, k]
        # projection block
        k = int(np.asarray(self.components_).shape[0])
        return {"proj": int(bucket_rows_count) * k * itemsize}

    def _serve_flop_estimate(self, n_rows, n_cols):
        # roofline numerator: the (X - mean) @ components.T projection matmul
        k = max(1, int(np.asarray(self.components_).shape[0]))
        return 2.0 * n_rows * n_cols * k
