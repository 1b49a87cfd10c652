#
# Data plane: DataFrame-like input -> contiguous numpy dense / scipy CSR blocks,
# ready for HBM placement as sharded `jax.Array`s.
#
# Mirrors the reference's L2 ingest (reference core.py:458-557 input pre-processing,
# core.py:205-250 sparse-vector decode, core.py:698-760 Arrow-batch -> numpy/CSR
# loop), re-designed for the TPU build: instead of per-batch pandas conversion
# inside a Spark UDF, the ingest produces one contiguous (row-major) feature block
# per partition that the parallel layer pads and lays out on the device mesh.
#
# Accepted dataset types: pandas.DataFrame, pyarrow.Table, dict[str, array-like],
# and (when pyspark is installed) pyspark.sql.DataFrame via collection to Arrow.
#
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, List, Optional, Sequence, Tuple, Union

import numpy as np

from .linalg import DenseVector, SparseVector

try:  # scipy is available in this image; used for the CSR ingest path
    import scipy.sparse as _sp
except Exception:  # pragma: no cover
    _sp = None


@dataclass
class ExtractedData:
    """Columnar view of a dataset after ingest."""

    features: Any  # np.ndarray [n, d] or scipy.sparse.csr_matrix
    label: Optional[np.ndarray] = None
    weight: Optional[np.ndarray] = None
    row_id: Optional[np.ndarray] = None
    feature_kind: str = "array"  # "vector" | "array" | "multi_cols"
    feature_names: List[str] = field(default_factory=list)
    # source column names for validation error attribution (the streaming
    # path validates per row-block long after extraction, so the names must
    # ride along with the data)
    label_name: Optional[str] = None
    weight_name: Optional[str] = None

    @property
    def n_rows(self) -> int:
        return int(self.features.shape[0])

    @property
    def n_cols(self) -> int:
        return int(self.features.shape[1])

    @property
    def is_sparse(self) -> bool:
        return _sp is not None and _sp.issparse(self.features)


def as_pandas(dataset: Any):
    """Normalize any accepted dataset type to a pandas DataFrame (zero-copy where possible)."""
    import pandas as pd

    if isinstance(dataset, pd.DataFrame):
        return dataset
    try:
        import pyarrow as pa

        if isinstance(dataset, pa.Table):
            return dataset.to_pandas()
    except ImportError:  # pragma: no cover
        pass
    if isinstance(dataset, dict):
        return pd.DataFrame({k: (list(v) if getattr(v, "ndim", 1) > 1 else v) for k, v in dataset.items()})
    # pyspark.sql.DataFrame (optional dependency)
    if hasattr(dataset, "toPandas") and hasattr(dataset, "sparkSession"):
        return dataset.toPandas()
    raise TypeError(f"Unsupported dataset type {type(dataset)}; expected pandas/pyarrow/dict")


def dataset_fingerprint(dataset: Any) -> tuple:
    """Identity fingerprint of a dataset object, for DeviceDataset cache keys
    (core.device_dataset_scope).

    Identity-based BY DESIGN: it never hashes the data (a content hash of a
    multi-GiB block would cost a full host pass per fit — more than the
    ingest it is meant to skip), so it is exact for the reuse it serves —
    repeated fits over the SAME object inside one scope (CV folds, sweep
    refits). The id() is only stable while the object is alive, so every
    cache entry PINS its source object (`DeviceDataset.source`) — without
    that, a recycled id on a new same-shaped object would be a silent false
    hit. Shape/columns ride along as defense in depth. An in-place mutation
    of the same object between fits inside one scope is not detected
    (documented in docs/performance.md)."""
    if isinstance(dataset, dict):
        shapes = tuple(
            (str(k), tuple(getattr(v, "shape", ())) or (len(v) if hasattr(v, "__len__") else None))
            for k, v in dataset.items()
        )
        return (id(dataset), type(dataset).__name__, shapes)
    cols = getattr(dataset, "columns", None)
    cols_t = tuple(map(str, cols)) if cols is not None else None
    shape = getattr(dataset, "shape", None)
    if shape is None and hasattr(dataset, "__len__"):
        shape = (len(dataset),)
    return (id(dataset), type(dataset).__name__, cols_t, tuple(shape) if shape else None)


def same_ingest_identity(key_a: Any, key_b: Any) -> bool:
    """Whether two DeviceDataset cache keys name the SAME ingested data —
    dataset fingerprint, extraction columns, dtype/sparse mode — regardless
    of the MESH they were placed on (the key's final component). This is the
    host-retained re-placement predicate for elastic recovery
    (docs/robustness.md): after a survivor re-mesh changes the device set,
    the stale placement's `extracted` host blocks are still the right data —
    only the layout must be redone on the new mesh."""
    return (
        key_a is not None
        and key_b is not None
        and len(key_a) == len(key_b) == 4
        and key_a[:3] == key_b[:3]
    )


def ingest_chunk_rows(row_bytes: int) -> int:
    """Rows per ingest chunk under ``core.config["ingest_chunk_bytes"]``."""
    from .core import config  # lazy: core imports this module at load time

    chunk_bytes = int(config.get("ingest_chunk_bytes", 128 << 20))
    return max(1, chunk_bytes // max(1, int(row_bytes)))


def _first_nonfinite_row(block: np.ndarray, lo: int) -> int:
    """Row index (absolute, given chunk offset `lo`) of the first non-finite
    entry in a dense chunk."""
    finite_rows = np.isfinite(block).all(axis=tuple(range(1, block.ndim)))
    return lo + int(np.argmin(finite_rows))


def validate_extracted(
    extracted: "ExtractedData",
    label_col=None,
    weight_col=None,
    lo: int = 0,
    hi: Optional[int] = None,
) -> None:
    """NaN/Inf scan over rows ``[lo, hi)`` of the ingested blocks.

    Chunked under the same ``ingest_chunk_bytes`` bound as the ingest itself,
    so validation temporaries (the per-chunk finite mask) never scale with
    the dataset. Raises `IngestValidationError` NAMING the offending column
    and the ABSOLUTE first bad row — the alternative is a NaN surfacing
    iterations later inside a solver as a divergence with no pointer back to
    the data. The full-range call is the eager fit-entry scan; the streaming
    fit path calls it PER ROW-BLOCK as chunks enter the pipeline, so the
    dataset is never host-materialized a second time just to validate it."""
    from .core import config
    from .errors import IngestValidationError

    feats = extracted.features
    n = extracted.n_rows
    hi = n if hi is None else min(int(hi), n)
    lo = max(0, int(lo))
    if extracted.is_sparse:
        # CSR: only the stored values can be non-finite; chunk the row range's
        # data slice and map the first bad element back to its ABSOLUTE row
        # through indptr
        indptr = feats.indptr
        e_lo, e_hi = int(indptr[lo]), int(indptr[hi])
        data = feats.data
        step = max(1, int(config.get("ingest_chunk_bytes", 128 << 20)) // max(1, data.itemsize))
        for elo in range(e_lo, e_hi, step):
            chunk = data[elo : min(elo + step, e_hi)]
            if not np.isfinite(chunk).all():
                elem = elo + int(np.argmin(np.isfinite(chunk)))
                row = int(np.searchsorted(indptr, elem, side="right") - 1)
                raise IngestValidationError(extracted.feature_names[0], row)
    else:
        # drift seedling (ops_plane.drift, docs/observability.md "Ops
        # plane"): per-column moments + PSI bins accumulate off this SAME
        # pass — zero extra data reads; stats for a failing chunk are taken
        # BEFORE the raise (partial stats are never published). None (and
        # zero cost) while telemetry is off or the block is sparse.
        from .ops_plane import drift as _drift

        acc = _drift.accumulator_for(extracted)
        row_bytes = feats.shape[1] * feats.itemsize if feats.ndim > 1 else feats.itemsize
        step = ingest_chunk_rows(row_bytes)
        for clo in range(lo, hi, step):
            chunk = np.asarray(feats[clo : min(clo + step, hi)])
            if acc is not None:
                acc.update(chunk)
            if np.isfinite(chunk).all():
                continue
            if extracted.feature_kind == "multi_cols" and chunk.ndim > 1:
                # name the exact offending source column, not the block
                bad_cols = ~np.isfinite(chunk).all(axis=0)
                name = extracted.feature_names[int(np.argmax(bad_cols))]
                col = chunk[:, int(np.argmax(bad_cols))]
                raise IngestValidationError(name, clo + int(np.argmin(np.isfinite(col))))
            raise IngestValidationError(
                extracted.feature_names[0], _first_nonfinite_row(chunk, clo)
            )
        if acc is not None and acc.rows >= n:
            # the whole dataset has been scanned (eagerly, or as the last of
            # the streaming path's per-row-block calls): publish the
            # ingest.feature.* gauges (+ PSI when a baseline is registered)
            acc.publish()
    for name, arr in ((label_col, extracted.label), (weight_col, extracted.weight)):
        if arr is None:
            continue
        part = arr[lo:hi]
        if not np.isfinite(part).all():
            raise IngestValidationError(
                str(name), lo + int(np.argmin(np.isfinite(part)))
            )


def run_deferred_validation(
    extracted: "ExtractedData", lo: int = 0, hi: Optional[int] = None
) -> None:
    """`validate_extracted` gated on ``config["validate_ingest"]``, with the
    column names taken from the extraction record — the entry point for the
    fit driver (eager full scan on the resident path) and the streaming
    pipeline (per row-block)."""
    from .core import config

    if not config.get("validate_ingest", False):
        return
    validate_extracted(
        extracted, extracted.label_name, extracted.weight_name, lo=lo, hi=hi
    )


def _scans_at_extraction(validate: bool) -> bool:
    """Whether this extraction runs the opt-in eager NaN/Inf scan
    (``config["validate_ingest"]``, not deferred by the caller)."""
    from .core import config

    return bool(validate and config.get("validate_ingest", False))


def _validate_ingest(
    extracted: "ExtractedData", label_col=None, weight_col=None
) -> None:
    """Opt-in eager NaN/Inf scan at extraction (``config["validate_ingest"]``)."""
    if _scans_at_extraction(True):
        validate_extracted(extracted, label_col, weight_col)


def _record_ingest(
    extracted: "ExtractedData", label_col=None, weight_col=None, validate: bool = True
) -> "ExtractedData":
    """Validation (opt-in, deferrable) + telemetry counters for a completed
    extraction: rows and host bytes staged (CSR counts its data+index
    arrays). The telemetry half is a flag-checked no-op when disabled.
    ``validate=False`` DEFERS the NaN/Inf scan to the caller (the fit driver:
    eager full scan on the resident path, per row-block on the streaming
    path — `run_deferred_validation`)."""
    from . import telemetry

    extracted.label_name = None if label_col is None else str(label_col)
    extracted.weight_name = None if weight_col is None else str(weight_col)
    if validate:
        _validate_ingest(extracted, label_col=label_col, weight_col=weight_col)
    if telemetry.enabled():
        feats = extracted.features
        if extracted.is_sparse:
            nbytes = feats.data.nbytes + feats.indices.nbytes + feats.indptr.nbytes
        else:
            nbytes = feats.nbytes
        for aux in (extracted.label, extracted.weight, extracted.row_id):
            if aux is not None:
                nbytes += aux.nbytes
        reg = telemetry.registry()
        reg.inc("ingest.rows", extracted.n_rows)
        reg.inc("ingest.bytes", nbytes)
        reg.inc("ingest.datasets")
    return extracted


class DenseRows:
    """A dense object column (one vector, array or list a row) that is not a
    block yet: `fill` copies a row range into a buffer the caller keeps,
    `block` makes the whole `[n, n_cols]` array (the fit's ingest).

    `model.transform` walks such a column piece by piece through a small ring
    of reused buffers (core.py `_TpuModelWithColumns.transform`), so no block
    of the whole partition is made there. `to_row` turns one cell into its
    1-D row (`DenseVector.toArray`); None where the cells are the rows."""

    def __init__(self, values: np.ndarray, n_cols: int, dtype, to_row=None) -> None:
        self.values = values
        self.shape = (len(values), int(n_cols))
        self.dtype = np.dtype(dtype)
        self.to_row = to_row

    @property
    def nbytes(self) -> int:
        return self.shape[0] * self.shape[1] * self.dtype.itemsize

    def fill(self, out: np.ndarray, lo: int, hi: int) -> None:
        """Rows ``[lo, hi)`` into the C-contiguous ``out`` (``[hi - lo,
        n_cols]``), cast on the way: one pass, each row copied from the
        object it is straight to its place (no list-to-array temporary)."""
        rows = self.values[lo:hi]
        if self.to_row is not None:
            rows = [self.to_row(v) for v in rows]
        n_cols = self.shape[1]
        if out.shape != (hi - lo, n_cols) or not out.flags.c_contiguous:
            raise ValueError(f"fill buffer {out.shape} is not a contiguous [{hi - lo}, {n_cols}] block")
        if set(map(len, rows)) - {n_cols}:
            bad = next(i for i, r in enumerate(rows) if len(r) != n_cols)
            raise ValueError(
                f"feature row {lo + bad} has {len(rows[bad])} entries where the column's first row has {n_cols}"
            )
        if len(rows):
            np.concatenate(rows, out=out.reshape(-1), casting="unsafe")

    def block(self) -> np.ndarray:
        """The whole column as one preallocated block, filled a row-chunk at
        a time (``core.config["ingest_chunk_bytes"]``; one `ingest.chunks`
        count each)."""
        from . import telemetry

        n, n_cols = self.shape
        out = np.empty(self.shape, dtype=self.dtype)
        step = ingest_chunk_rows(n_cols * self.dtype.itemsize)
        for lo in range(0, n, step):
            hi = min(lo + step, n)
            self.fill(out[lo:hi], lo, hi)
            telemetry.registry().inc("ingest.chunks")
        return out


def _column_to_matrix(col, dtype) -> Tuple[Any, str]:
    """Convert a single feature column (vectors / arrays / lists) to its rows.

    Returns (rows, kind) where kind is 'vector' when the column held
    Dense/SparseVector objects (so transform can emit vectors back) else 'array'.
    Sparse rows produce a scipy CSR matrix: the nnz are counted first and the
    preallocated CSR arrays filled in place (no second full-nnz copy). Dense
    rows come back as `DenseRows`, whose `block()` is the 2-D array.
    """
    values = col.to_numpy() if hasattr(col, "to_numpy") else np.asarray(col, dtype=object)
    if len(values) == 0:
        raise ValueError("empty feature column")
    first = values[0]
    if isinstance(first, (DenseVector, SparseVector)) or (
        _sp is not None and _sp.issparse(first)
    ):
        any_sparse = any(
            isinstance(v, SparseVector) or (_sp is not None and _sp.issparse(v)) for v in values
        )
        if any_sparse:
            size = first.size if isinstance(first, (DenseVector, SparseVector)) else first.shape[1]
            n = len(values)

            def _row_parts(v):
                if isinstance(v, SparseVector):
                    return v.indices, v.values
                if isinstance(v, DenseVector):
                    idx = np.nonzero(v.values)[0].astype(np.int32)
                    return idx, v.values[idx]
                v = v.tocsr()  # scipy sparse row
                return v.indices, v.data

            # decode each row ONCE (SparseVector rows contribute pure
            # references to their own index/value arrays — no copy), size the
            # CSR arrays from the decoded lengths, then fill in place, freeing
            # the decoded Dense/scipy-row copies as they are consumed — no
            # second full-nnz concatenate copy ever exists
            parts = [_row_parts(v) for v in values]
            indptr = np.zeros(n + 1, dtype=np.int64)
            for i, (idx, _) in enumerate(parts):
                indptr[i + 1] = indptr[i] + len(idx)
            data = np.empty(int(indptr[-1]), dtype=dtype)
            indices = np.empty(int(indptr[-1]), dtype=np.int32)
            for i in range(n):
                idx, val = parts[i]
                parts[i] = None  # free decode copies as they are copied in
                lo, hi = indptr[i], indptr[i + 1]
                indices[lo:hi] = idx
                data[lo:hi] = val  # cast to dtype on assignment
            mat = _sp.csr_matrix(
                (data, indices, indptr), shape=(n, size), dtype=dtype
            )
            return mat, "vector"
        return DenseRows(values, first.size, dtype, lambda v: v.toArray()), "vector"
    # plain array/list rows
    if (isinstance(first, np.ndarray) and first.ndim == 1) or isinstance(first, (list, tuple)):
        return DenseRows(values, len(first), dtype), "array"
    raise TypeError(f"Unsupported feature cell type {type(first)} in feature column")


def extract_dataset(
    dataset: Any,
    *,
    input_col: Optional[str] = None,
    input_cols: Optional[Sequence[str]] = None,
    label_col: Optional[str] = None,
    weight_col: Optional[str] = None,
    id_col: Optional[str] = None,
    float32_inputs: bool = True,
    enable_sparse_data_optim: Optional[bool] = None,
    validate: bool = True,
    dense_rows: bool = False,
) -> ExtractedData:
    """Extract features (+label/weight/id) as contiguous blocks.

    ``enable_sparse_data_optim``: None autodetects (CSR kept sparse); True requires
    a sparse input (raises otherwise); False densifies (reference params.py:44-65).
    ``validate=False`` defers the opt-in NaN/Inf scan to the caller (see
    `_record_ingest`). ``dense_rows=True`` leaves a dense object column as
    `DenseRows` (the caller fills its own buffers from it, piece by piece)
    unless the opt-in scan is on, which reads the whole block here.
    """
    dtype = np.float32 if float32_inputs else np.float64

    # Fast path for dict datasets whose feature entry is ALREADY a 2-D block
    # (ndarray or scipy CSR): skip the per-row object column entirely. This is
    # the at-scale ingest used by the benchmark suite — the reference reads
    # parquet into whole Arrow batches the same way (core.py:724-760) rather
    # than per-row vectors.
    if (
        isinstance(dataset, dict)
        and input_col is not None
        and input_col in dataset
        and (
            (isinstance(dataset[input_col], np.ndarray) and dataset[input_col].ndim == 2)
            or (_sp is not None and _sp.issparse(dataset[input_col]))
        )
    ):
        features = dataset[input_col]
        if _sp is not None and _sp.issparse(features):
            features = features.tocsr()
            if enable_sparse_data_optim is False:
                features = np.asarray(features.todense(), dtype=dtype)
            kind = "vector"
        else:
            features = np.ascontiguousarray(features, dtype=dtype)
            kind = "array"
            if enable_sparse_data_optim is True:
                raise ValueError("enable_sparse_data_optim=True requires sparse input")

        def _dict_scalar(colname, dt):
            if colname is None or colname == "":
                return None
            if colname not in dataset:
                raise ValueError(f"column {colname!r} not in dataset")
            return np.asarray(dataset[colname], dtype=dt)

        return _record_ingest(ExtractedData(
            features=features,
            label=_dict_scalar(label_col, dtype),
            weight=_dict_scalar(weight_col, dtype),
            row_id=_dict_scalar(id_col, np.int64),
            feature_kind=kind,
            feature_names=[input_col],
        ), label_col=label_col, weight_col=weight_col, validate=validate)

    pdf = as_pandas(dataset)

    if input_cols is not None:
        missing = [c for c in input_cols if c not in pdf.columns]
        if missing:
            raise ValueError(f"feature columns not in dataset: {missing}")
        names = list(input_cols)
        # chunked column->block conversion: the whole-frame to_numpy holds a
        # second full copy in flight; filling a preallocated block per
        # row-chunk bounds the temporary at one chunk
        n = len(pdf)
        features = np.empty((n, len(names)), dtype=dtype)
        step = ingest_chunk_rows(len(names) * np.dtype(dtype).itemsize)
        sub = pdf[names]
        for lo in range(0, n, step):
            hi = min(lo + step, n)
            features[lo:hi] = sub.iloc[lo:hi].to_numpy(dtype=dtype)
        kind = "multi_cols"
    else:
        assert input_col is not None
        if input_col not in pdf.columns:
            raise ValueError(f"feature column {input_col!r} not in dataset")
        features, kind = _column_to_matrix(pdf[input_col], dtype)
        # the opt-in scan reads the whole block here, so it is made here
        if isinstance(features, DenseRows) and (not dense_rows or _scans_at_extraction(validate)):
            features = features.block()
        names = [input_col]

    if _sp is not None and _sp.issparse(features):
        if enable_sparse_data_optim is False:
            features = np.asarray(features.todense(), dtype=dtype)
    elif enable_sparse_data_optim is True:
        raise ValueError("enable_sparse_data_optim=True requires sparse vector input")

    def _scalar(colname: Optional[str], dt) -> Optional[np.ndarray]:
        if colname is None or colname == "":
            return None
        if colname not in pdf.columns:
            raise ValueError(f"column {colname!r} not in dataset")
        return pdf[colname].to_numpy(dtype=dt)

    return _record_ingest(ExtractedData(
        features=features,
        label=_scalar(label_col, dtype),
        weight=_scalar(weight_col, dtype),
        row_id=_scalar(id_col, np.int64),
        feature_kind=kind,
        feature_names=names,
    ), label_col=label_col, weight_col=weight_col, validate=validate)


def vectors_to_pandas_column(matrix: np.ndarray) -> list:
    """Dense 2-D block -> list of DenseVector for a vector-typed output column."""
    return [DenseVector(row) for row in np.asarray(matrix)]


def attach_column(dataset: Any, pdf_out, name: str, values) -> Any:
    """Append a column to the (pandas-normalized) dataset, preserving pandas type."""
    out = pdf_out.copy(deep=False)
    out[name] = list(values) if getattr(values, "ndim", 1) > 1 else values
    return out
