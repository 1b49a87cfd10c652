#
# Exact + approximate nearest-neighbor estimators.
#
# API-parity target: reference knn.py (`NearestNeighbors` :74-785,
# `ApproximateNearestNeighbors` :787-1544): fit() registers the item set,
# `kneighbors(query_df)` returns (item_df, query_df, knn_df) with knn_df =
# (query_id, indices, distances); `exactNearestNeighborsJoin` /
# `approxSimilarityJoin` explode the pairs. Neither supports persistence
# (reference knn.py:370-394 raises the same way).
#
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from ..core import FitInputs, _TpuEstimator, _TpuModel, alias
from ..data import ExtractedData, as_pandas
from ..params import (
    HasFeaturesCol,
    HasFeaturesCols,
    HasIDCol,
    HasInputCol,
    HasInputCols,
    HasLabelCol,
    Param,
    TypeConverters,
)


class _KNNParams(HasInputCol, HasInputCols, HasFeaturesCol, HasFeaturesCols, HasIDCol, HasLabelCol):
    k = Param("k", "the number of nearest neighbors to retrieve", TypeConverters.toInt)

    def getK(self) -> int:
        return self.getOrDefault("k")

    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        return {"k": "n_neighbors"}

    def _get_solver_params_default(self) -> Dict[str, Any]:
        # batch_queries 0 = config["distance_tile_rows"] (the shared tiled
        # distance core's row-tile, docs/performance.md "Tiled distance
        # core"); a nonzero value overrides per estimator
        return {"n_neighbors": 5, "batch_queries": 0, "verbose": False}


class NearestNeighbors(_KNNParams, _TpuEstimator):
    """Exact kNN estimator (reference knn.py:74-447).

    >>> gnn = NearestNeighbors(k=2).setInputCol("features").setIdCol("id")
    >>> model = gnn.fit(item_df)
    >>> item_out, query_out, knn_df = model.kneighbors(query_df)

    Distributed strategy: items row-sharded on the mesh, queries replicated;
    per-shard MXU distance tiles + top-k, then an all-gather of the [k·nq]
    candidates and one final top-k — replacing the reference's UCX all-to-all
    item/query shuffle (knn.py:712-723) with one small ICI collective.
    CSR item sets search via tile-densify with a running top-k (never fully
    densified — the reference's cupyx-CSR kNN capability).
    """

    _supports_sparse_input = True

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._setDefault(k=5)
        self._set_params(**kwargs)

    def setK(self, value: int) -> "NearestNeighbors":
        return self._set_params(k=value)

    def setInputCol(self, value) -> "NearestNeighbors":
        return self._set_params(inputCol=value) if isinstance(value, str) else self._set_params(inputCols=value)

    def setIdCol(self, value: str) -> "NearestNeighbors":
        return self._set_params(idCol=value)

    def _get_tpu_fit_func(self, extracted: ExtractedData):
        def _fit(inputs: FitInputs, params: Dict[str, Any]) -> Dict[str, Any]:
            return {"n_cols": inputs.n_cols, "dtype": np.dtype(inputs.dtype).name}

        return _fit

    def _fit_internal(self, dataset: Any, paramMaps):
        # fit just registers the (host) item set; the heavy work happens in
        # kneighbors — mirroring the reference where fit returns a model bound
        # to the item dataframe (knn.py:333-368)
        pdf = as_pandas(dataset)
        extracted = self._pre_process_data(dataset, for_fit=True)
        model = NearestNeighborsModel(
            n_cols=extracted.n_cols, dtype="float32" if self._float32_inputs else "float64"
        )
        self._copyValues(model)
        self._copy_solver_params(model)
        model._item_pdf = pdf
        model._item_extracted = extracted
        return [model]

    def _create_model(self, attrs):  # pragma: no cover - _fit_internal overridden
        return NearestNeighborsModel(**attrs)

    def write(self):
        raise NotImplementedError("NearestNeighbors does not support saving (reference parity)")


class NearestNeighborsModel(_KNNParams, _TpuModel):
    _supports_sparse_input = True

    def __init__(self, n_cols: int = 0, dtype: str = "float32", **kwargs: Any) -> None:
        super().__init__(n_cols=n_cols, dtype=dtype)
        self.n_cols = int(n_cols)
        self.dtype = dtype
        self._item_pdf = None
        self._item_extracted: Optional[ExtractedData] = None

    def _ensure_id(self, pdf, extracted) -> np.ndarray:
        if extracted.row_id is not None:
            return extracted.row_id
        return np.arange(len(pdf), dtype=np.int64)

    def kneighbors(self, query_df: Any) -> Tuple[Any, Any, Any]:
        """Returns (item_df, query_df, knn_df) — knn_df has columns
        (query_id, indices, distances), indices being item id values.

        Under multi-process SPMD (an active ``TpuContext`` with nranks > 1):
        each rank holds LOCAL item and query blocks; items are laid out
        globally on the mesh, query blocks are rendezvous-replicated (the
        reference allgathers sizes/ids for the UCX shuffle the same way,
        knn.py:689-700), every rank computes the full result, and returns the
        rows for ITS OWN queries."""
        import pandas as pd

        from .. import telemetry
        from ..parallel import TpuContext
        from ..parallel.mesh import dtype_scope

        assert self._item_pdf is not None, "model is not bound to an item dataframe"
        k = int(self._solver_params["n_neighbors"])
        item_ex = self._item_extracted
        query_pdf = as_pandas(query_df)
        active0 = TpuContext.current()
        if len(query_pdf) == 0 and (active0 is None or not active0.is_spmd):
            # 0-row query frame: nothing to search (ingest can't infer a width
            # from an empty column). SPMD ranks still run the full path — an
            # empty LOCAL block must participate in the collective gathers.
            item_ids = self._ensure_id(self._item_pdf, item_ex)
            id_col = self.getOrDefault("idCol") if self.isDefined("idCol") else alias.row_number
            item_out = self._item_pdf.copy(deep=False)
            if id_col not in item_out.columns:
                item_out[id_col] = item_ids
            query_out = query_pdf.copy(deep=False)
            if id_col not in query_out.columns:
                query_out[id_col] = np.zeros(0, dtype=np.int64)
            knn_df = pd.DataFrame(
                {"query_id": np.zeros(0, dtype=np.int64), "indices": [], "distances": []}
            )
            return item_out, query_out, knn_df
        with telemetry.span("kneighbors", model=type(self).__name__, rows=len(query_pdf)):
            with telemetry.span("extract", rows=len(query_pdf)):
                query_ex = self._pre_process_data(query_df, for_fit=False)
                query_ids = self._ensure_id(query_pdf, query_ex)
                queries = query_ex.features
                if hasattr(queries, "todense"):
                    queries = np.asarray(queries.todense())
                np_dtype = np.float32 if self._float32_inputs else np.float64
                queries = np.asarray(queries, dtype=np_dtype)
            active = TpuContext.current()
            with dtype_scope(np_dtype):
                if active is not None and active.is_spmd:
                    dist, indices, item_ids, query_ids = self._search_spmd(
                        queries, query_ex, query_ids, k, np_dtype, active
                    )
                else:
                    dist, indices, item_ids = self._search_local(queries, k, np_dtype)
        with telemetry.span("kneighbors.assemble", rows=len(query_pdf)):
            knn_df = pd.DataFrame(
                {
                    "query_id": query_ids,
                    "indices": list(indices),
                    "distances": list(dist),
                }
            )
            id_col = self.getOrDefault("idCol") if self.isDefined("idCol") else alias.row_number
            item_out = self._item_frame(id_col, item_ids)
            query_out = query_pdf.copy(deep=False)
            if id_col not in query_out.columns:
                query_out[id_col] = query_ids
        return item_out, query_out, knn_df

    def _item_frame(self, id_col: str, item_ids: np.ndarray):
        """The item frame `kneighbors` returns: the fitted frame with its id
        column, made once for the held id map (10,000,000 ids are 80 MB to
        assign) and handed out as a shallow copy (copy-on-write: what a
        caller does to it stays with the caller)."""
        held = self.__dict__.get("_item_out")
        if held is None or held[0] != id_col or held[1] is not item_ids:
            frame = self._item_pdf.copy(deep=False)
            if id_col not in frame.columns:
                frame[id_col] = item_ids
            held = self._item_out = (id_col, item_ids, frame)
        return held[2].copy(deep=False)

    def _resident_items(self, n_dev: int, np_dtype) -> Dict[str, Any]:
        """The item set on the device, placed at the first search and kept
        while the model lives: X (row-major, the layout the top-k kernel
        reads where it lies), the validity mask, the item norms and the map
        from positions to ids. Placed again only for another worker count or
        dtype. Counted: `knn.item_placements`, `knn.item_reuses`."""
        import jax

        from .. import telemetry
        from ..ops.knn import _row_sq
        from ..parallel import get_mesh, make_global_rows
        from ..parallel.mesh import X_ROW_MAJOR

        key = (int(n_dev), np.dtype(np_dtype).name)
        held = self.__dict__.get("_resident")
        if held is not None and held["key"] == key:
            telemetry.registry().inc("knn.item_reuses")
            return held
        self._resident = None  # the old placement goes before the new one comes
        item_ex = self._item_extracted
        with telemetry.span("place", rows=int(item_ex.n_rows), cols=int(item_ex.n_cols), workers=int(n_dev)):
            items = item_ex.features
            if hasattr(items, "todense"):
                items = np.asarray(items.todense())
            mesh = get_mesh(n_dev)
            X, w, _ = make_global_rows(mesh, np.asarray(items, dtype=np_dtype), x_layout=X_ROW_MAJOR)
            held = {
                "key": key, "mesh": mesh, "X": X, "valid": w > 0, "item_sq": _row_sq(X),
                "ids": self._ensure_id(self._item_pdf, item_ex),
            }
            with telemetry.device_wait("knn_place"):
                jax.block_until_ready((held["valid"], held["item_sq"]))
        self._resident = held
        telemetry.registry().inc("knn.item_placements")
        return held

    def _search_local(self, queries: np.ndarray, k: int, np_dtype):
        """Single controller: (euclidean distances, item ids, item id column).
        Dense items search where they are held (`_resident_items`): one
        device through the host-looped query tiles, several through the
        sharded search; CSR items tile-densify with a running top-k (never
        fully densified — the reference's sparse kNN capability)."""
        import jax

        from .. import telemetry
        from ..ops import knn as ops_knn
        from ..ops.distance import topk_chunk_steps, topk_plan
        from ..parallel.mesh import default_devices

        item_ex = self._item_extracted
        if k > item_ex.n_rows:
            raise ValueError(f"k={k} exceeds the number of item rows {item_ex.n_rows}")
        if item_ex.is_sparse:
            with telemetry.span("search", queries=len(queries), items=int(item_ex.n_rows), k=k, topk_path="sparse"):
                d_np, gidx_np = ops_knn.exact_knn_sparse(item_ex.features, queries, k)
            item_ids = self._ensure_id(self._item_pdf, item_ex)
            return np.asarray(d_np, dtype=np.float64), item_ids[np.maximum(np.asarray(gidx_np), 0)], item_ids
        n_dev = min(self.num_workers, len(default_devices()))
        held = self._resident_items(n_dev, np_dtype)
        X, valid = held["X"], held["valid"]
        nq, (n_pad, d) = len(queries), X.shape
        kk = min(k, n_pad // n_dev)
        batch = int(self._solver_params["batch_queries"]) or None  # 0: config["distance_tile_rows"]
        tile, path = ops_knn.search_tile(nq, n_pad // n_dev, d, kk, np_dtype, batch)
        blocks = topk_plan(tile, n_pad // n_dev, d, kk, np_dtype, False) or (None, None)
        attrs = dict(queries=nq, items=int(item_ex.n_rows), d=int(d), k=k, block_rows=blocks[0],
                     block_items=blocks[1], topk_path=path, precision=np.dtype(np_dtype).name)
        with telemetry.span("search", **attrs):
            Q = jax.device_put(queries)
            if n_dev == 1:
                out = ops_knn.dispatch_1dev(X, valid, Q, k, batch, item_sq=held["item_sq"])
                programs = len(out)
            else:
                out = ops_knn._exact_knn_sharded(X, valid, Q, mesh=held["mesh"], k=k, batch_queries=batch)
                programs = 1
            with telemetry.device_wait("knn_search"):
                jax.block_until_ready(out)
        if path == "kernel":
            telemetry.registry().inc("knn.kernel_calls", programs)
        telemetry.registry().inc("knn.candidates", nq * int(item_ex.n_rows))
        with telemetry.span("fetch", rows=nq):
            if n_dev == 1:
                dist, gidx, merged = ops_knn.fetch_1dev(out, k, np_dtype)
            else:
                dist, gidx = np.asarray(out[0]), np.asarray(out[1])  # host-fetch-ok: the search's one result fetch
            dist = dist.astype(np.float64)
        if path == "kernel" and n_dev == 1:  # the sharded search fetches no count
            telemetry.registry().inc("knn.merged_pairs", merged)
            telemetry.registry().inc("knn.flag_pairs", programs * topk_chunk_steps(tile, n_pad, *blocks))
        return dist, held["ids"][gidx], held["ids"]

    def _search_spmd(self, queries, query_ex, query_ids, k, np_dtype, active):
        """Multi-process SPMD: (distances, item ids, item id column, query
        ids) of this rank's queries. Every rank holds LOCAL item and query
        blocks; items are laid out globally on the mesh, query blocks are
        rendezvous-replicated, every rank computes the full result and keeps
        the rows of its own queries."""
        import jax

        from .. import telemetry
        from ..ops.knn import exact_knn, exact_knn_sparse
        from ..parallel import PartitionDescriptor, make_global_rows
        from ..parallel.context import allgather_concat, allgather_ndarray
        from ..parallel.mesh import X_ROW_MAJOR

        item_ex = self._item_extracted
        items = item_ex.features
        item_ids = self._ensure_id(self._item_pdf, item_ex)
        rdv = active.rendezvous
        if item_ex.is_sparse:
            # each rank runs the exact tile-densify search on its LOCAL CSR
            # block for ALL queries, then the per-rank exact top-k sets are
            # merged on the control plane — the union of exact local results
            # IS the exact global result
            counts = [int(c) for c in rdv.allgather(str(item_ex.n_rows))]
            if k > sum(counts):
                raise ValueError(f"k={k} exceeds the number of item rows {sum(counts)}")
            if item_ex.row_id is None:
                item_ids = item_ids + sum(counts[: active.rank])
            if query_ex.row_id is None:
                qcounts = [int(c) for c in rdv.allgather(str(len(query_ids)))]
                query_ids = query_ids + sum(qcounts[: active.rank])
            queries_global, q_offset = allgather_concat(rdv, queries)
            nq_local = len(queries)
            with telemetry.span("search", queries=len(queries_global), items=sum(counts), k=k, topk_path="sparse"):
                d_np, lidx = exact_knn_sparse(items, queries_global, k)
            local_user_ids = np.where(
                np.asarray(lidx) >= 0, item_ids[np.maximum(np.asarray(lidx), 0)], -1
            )
            d_all = np.concatenate(allgather_ndarray(rdv, np.asarray(d_np, dtype=np.float64)), axis=1)
            i_all = np.concatenate(allgather_ndarray(rdv, local_user_ids.astype(np.int64)), axis=1)
            order = np.argsort(d_all, axis=1, kind="stable")[:, :k]
            dist = np.take_along_axis(d_all, order, axis=1)[q_offset : q_offset + nq_local]
            indices = np.take_along_axis(i_all, order, axis=1)[q_offset : q_offset + nq_local]
            return dist, indices, item_ids, query_ids
        if hasattr(items, "todense"):
            items = np.asarray(items.todense())
        mesh = active.mesh
        # agree on the global item layout (ragged local blocks -> common
        # padded per-process size), like _build_fit_inputs
        desc = PartitionDescriptor.build(
            [items.shape[0]], item_ex.n_cols, rank=active.rank, rendezvous=rdv,
        )
        if k > desc.m:
            raise ValueError(f"k={k} exceeds the number of item rows {desc.m}")
        # default row-number ids are rank-local — offset by the lower-rank
        # row counts so they're globally unique (same rule as the sparse-SPMD
        # and ANN-SPMD branches)
        if item_ex.row_id is None:
            item_ids = item_ids + desc.row_offset_of(active.rank)
        n_local_dev = jax.local_device_count()
        max_rows = max(r for _, r in desc.parts_rank_size)
        local_rows_target = -(-max_rows // n_local_dev) * n_local_dev
        with telemetry.span("place", rows=int(items.shape[0]), cols=int(item_ex.n_cols), workers=mesh.devices.size):
            X, w, _ = make_global_rows(
                mesh, np.asarray(items, dtype=np_dtype), local_rows_target=local_rows_target,
                x_layout=X_ROW_MAJOR,
            )
        telemetry.registry().inc("knn.item_placements")
        # global padded-position -> user item id map (pad with -1)
        ids_padded = np.full(local_rows_target, -1, np.int64)
        ids_padded[: len(item_ids)] = item_ids
        global_item_ids = np.concatenate(allgather_ndarray(rdv, ids_padded))
        # replicate the query blocks; remember this rank's slice
        q_blocks = allgather_ndarray(rdv, queries)
        q_offset = sum(len(b) for b in q_blocks[: active.rank])
        if query_ex.row_id is None:
            query_ids = query_ids + q_offset
        queries_global = np.concatenate(q_blocks, axis=0)
        with telemetry.span("search", queries=len(queries_global), items=int(desc.m), k=k, topk_path="sharded"):
            d_dev, gidx_dev = exact_knn(
                X, w > 0, jax.device_put(queries_global), mesh=mesh, k=k,
                # 0 -> None: resolves config["distance_tile_rows"]
                batch_queries=int(self._solver_params["batch_queries"]) or None,
            )
            with telemetry.device_wait("knn_search"):
                jax.block_until_ready((d_dev, gidx_dev))
        with telemetry.span("fetch", rows=len(queries)):
            rows = slice(q_offset, q_offset + len(queries))
            dist = np.asarray(d_dev, dtype=np.float64)[rows]
            gidx = np.asarray(gidx_dev)[rows]
        return dist, global_item_ids[gidx], item_ids, query_ids

    def exactNearestNeighborsJoin(self, query_df: Any, distCol: str = "distCol") -> Any:
        """Exploded (item, query, distance) join (reference knn.py:421-468).

        Single-controller only: under multi-process SPMD the neighbor ids
        returned by ``kneighbors`` routinely live on OTHER ranks, and the item
        attribute join is a data-plane operation (the reference performs it as
        a Spark dataframe join over the distributed item set, knn.py:421-468) —
        join the per-rank ``knn_df`` outputs against the full item table in the
        caller's data layer instead."""
        import pandas as pd

        from ..parallel import TpuContext

        active = TpuContext.current()
        if active is not None and active.is_spmd:
            raise NotImplementedError(
                "exactNearestNeighborsJoin/approxSimilarityJoin need the full item "
                "table on one node; under multi-process SPMD use kneighbors() and "
                "join the returned ids against your distributed item dataframe"
            )
        item_out, query_out, knn_df = self.kneighbors(query_df)
        id_col = self.getOrDefault("idCol") if self.isDefined("idCol") else alias.row_number
        item_by_id = item_out.set_index(id_col)
        query_by_id = query_out.set_index(id_col)
        # vectorized explode of the [nq, k] neighbor lists; ANN search pads
        # under-filled probe results with +inf distance — those aren't real
        # neighbors, drop them (a real hit always has finite distance)
        if len(knn_df):
            indices = np.stack(knn_df["indices"].to_numpy())
            dists = np.stack(knn_df["distances"].to_numpy())
        else:  # 0-row query frame: np.stack rejects an empty list
            indices = np.zeros((0, 1), dtype=np.int64)
            dists = np.zeros((0, 1), dtype=np.float64)
        k = indices.shape[1]
        flat_q = np.repeat(knn_df["query_id"].to_numpy(), k)
        flat_i = indices.ravel()
        flat_d = dists.ravel()
        finite = np.isfinite(flat_d)
        pairs = pd.DataFrame(
            {"_query_id": flat_q[finite], "_item_id": flat_i[finite], distCol: flat_d[finite]}
        )
        item_side = item_by_id.loc[pairs["_item_id"]].reset_index()
        item_side.columns = [f"item_{c}" if c != id_col else f"item_{id_col}" for c in item_side.columns]
        query_side = query_by_id.loc[pairs["_query_id"]].reset_index()
        query_side.columns = [f"query_{c}" if c != id_col else f"query_{id_col}" for c in query_side.columns]
        out = pd.concat(
            [item_side.reset_index(drop=True), query_side.reset_index(drop=True), pairs[[distCol]]],
            axis=1,
        )
        return out

    def transform(self, dataset: Any):
        raise NotImplementedError("use kneighbors()/exactNearestNeighborsJoin() (reference parity)")

    # serving hooks (docs/serving.md) -------------------------------------

    _serve_dtypes = (None, "float32", "float64", "bf16")

    def _serve_n_cols(self) -> int:
        if self._item_extracted is None:
            raise ValueError(
                "NearestNeighborsModel is not bound to an item dataframe; "
                "fit it before loading into the serving plane"
            )
        return int(self._item_extracted.n_cols)

    def _serve_placement_terms(self) -> Dict[str, int]:
        # the resident state is the ITEM BLOCK (plus its row norms and the
        # int64 id map), not the tiny param surface; where the top-k kernel
        # runs compiled, the block is row-major and every row fills whole
        # 128-lane tiles (`_serve_program`'s construct)
        from ..ops import distance as dist

        itemsize = 4 if self._float32_inputs else 8
        n = int(self._item_extracted.n_rows) if self._item_extracted is not None else 0
        d = self._serve_n_cols()
        if dist.kernel_mode() == "pallas":
            d = -(-d // 128) * 128
        return {
            "items": n * d * itemsize,
            "item_sq": n * itemsize,
            "item_ids": n * 8,
        }

    def _serve_workspace_terms(self, bucket_rows_count, itemsize) -> Dict[str, int]:
        # what one dispatched bucket adds in HBM. The fused top-k kernel
        # (`distance.topk_plan`) keeps its distance blocks in VMEM: the
        # -2 q copy padded to its query block, the +inf-padded norms row,
        # the zero-filled ragged item block, its [rows, TOPK_LANES] lists
        # (distances and int32 ids), its [8, 128] int32 count of merged
        # chunks a query block and the sorted [bucket, k] answer. The
        # jnp form holds the one-matmul [bucket, n_items] block and the
        # [bucket, k] best list x2 (d2 + index)
        from ..ops import distance as dist

        n_items = max(1, int(self._item_extracted.n_rows) if self._item_extracted is not None else 0)
        d = self._serve_n_cols()
        kk = min(int(self._solver_params["n_neighbors"]), n_items)
        b = max(1, int(bucket_rows_count))
        plan = dist.topk_plan(b, n_items, d, kk, np.float32 if itemsize == 4 else np.float64, False)
        if plan is None:
            return {"topk_block": b * n_items * itemsize, "topk_carry": 2 * b * kk * itemsize}
        br, bk = plan
        rows = -(-b // br) * br
        blocks = -(-n_items // bk)
        return {
            "topk_queries": rows * d * itemsize,
            "topk_norms": blocks * bk * itemsize,
            "topk_tail": bk * d * itemsize if n_items % bk else 0,
            "topk_lists": rows * dist.TOPK_LANES * (itemsize + 4),
            "topk_merged": rows // br * 8 * dist.TOPK_LANES * 4,
            "topk_carry": b * kk * (itemsize + 4),
        }

    def _serve_flop_estimate(self, n_rows, n_cols):
        # roofline numerator: the [queries, items] contraction the search
        # makes (2*n*m*d); the norms and the top-k selection are left out
        n_items = int(self._item_extracted.n_rows) if self._item_extracted is not None else 0
        return 2.0 * n_rows * max(1, n_items) * n_cols

    def _serve_program(self, serve_dtype=None, *, cap=None):
        """kNN serving hook: queries route through the tiled distance core
        (`ops/distance.topk_tile`, the fused top-k kernel where it plans
        one) so no `[batch, n_items]` distance block lands in HBM on the
        kernel path. Returns per query row
        (euclidean distances [B, k], USER item ids [B, k]) — the same values
        `kneighbors`' knn_df carries. `serve_dtype="bf16"` scores through the
        core's parity-tested fast-bf16 mode (docs/serving.md "bf16 serving")."""
        import jax
        import jax.numpy as jnp

        from ..core import PredictProgram
        from ..ops import distance as dist
        from ..parallel.mesh import X_ROW_MAJOR, default_local_device, place_on_device

        self._serve_check(serve_dtype)  # dtype surface + bound item set
        fast = serve_dtype == "bf16"
        dtype = np.float32 if self._float32_inputs else np.float64
        items = self._item_extracted.features
        if hasattr(items, "todense"):
            items = np.asarray(items.todense())
        items_np = np.ascontiguousarray(np.asarray(items, dtype=dtype))
        ids_np = np.asarray(
            self._ensure_id(self._item_pdf, self._item_extracted), dtype=np.int64
        )
        k = min(int(self._solver_params["n_neighbors"]), items_np.shape[0])

        def construct():
            dev = default_local_device()
            it = place_on_device(items_np, dev, X_ROW_MAJOR)  # the top-k kernel's layout
            return (it, dist.row_sq(it), jax.device_put(ids_np, dev))

        @jax.jit
        def predict(state, qb):
            it, it_sq, ids = state
            q = qb.astype(dtype)
            d2, idx = dist.topk_tile(q, it, None, k, item_sq=it_sq, fast=fast)
            d = jnp.sqrt(jnp.maximum(d2 + dist.row_sq(q)[:, None], 0.0))
            return d, ids[idx]

        return PredictProgram(self, construct=construct, predict=predict, cap=cap)

    def write(self):
        raise NotImplementedError("NearestNeighborsModel does not support saving (reference parity)")


class _ANNParams(_KNNParams):
    algorithm = Param("algorithm", "ANN algorithm: 'ivfflat', 'ivfpq' or 'cagra'", TypeConverters.toString)
    algoParams = Param("algoParams", "algorithm-specific parameters dict", TypeConverters.identity)
    metric = Param("metric", "distance metric: euclidean | sqeuclidean | cosine", TypeConverters.toString)

    @classmethod
    def _param_mapping(cls) -> Dict[str, Optional[str]]:
        return {"k": "n_neighbors", "metric": "metric"}

    def _get_solver_params_default(self) -> Dict[str, Any]:
        return {
            "metric": "euclidean",
            "n_neighbors": 5,
            "batch_queries": 1024,
            "n_lists": 64,
            "n_probes": 8,
            "pq_m": 8,       # cuML algoParams key "M": subquantizer count
            "pq_n_bits": 8,  # cuML algoParams key "n_bits": bits per PQ code
            # ivfpq retrieves k*refine_ratio ADC candidates, then re-ranks them
            # with exact distances (the cuVS refine step) — raw ADC ordering
            # alone caps recall well below the probe ceiling
            "refine_ratio": 4,
            # cagra index params (reference knn.py:927-931 IndexParams)
            "build_algo": "ivf_pq",
            "graph_degree": 64,
            "intermediate_graph_degree": 128,
            # cagra build knobs beyond the reference surface (ops/cagra.py):
            # seeding reps / max descent rounds / cuVS-style update-rate
            # termination / bf16 candidate scoring
            "cluster_reps": 8,
            "nn_descent_niter": 0,
            "termination_threshold": 0.003,
            "fast_score": True,
            # cagra search params (reference knn.py:933-938 SearchParams)
            "itopk_size": 64,
            "search_width": 1,
            "max_iterations": 0,
            "min_iterations": 0,
            "num_random_samplings": 1,
            "verbose": False,
        }


class ApproximateNearestNeighbors(_ANNParams, _TpuEstimator):
    """Approximate kNN via IVFFlat, IVFPQ or CAGRA (reference
    knn.py:787-1544; algorithm set knn.py:1089-1094).

    Local-index strategy like the reference: a coarse KMeans quantizer with
    padded inverted lists; queries probe `n_probes` lists. IVFPQ additionally
    product-quantizes the residuals and searches via ADC lookup tables.
    CAGRA builds a fixed-degree kNN graph by tiled NN-descent and answers
    queries with a batched greedy graph search (ops/cagra.py).
    `algoParams` accepts the cuML/cuVS-style keys {"nlist", "nprobe", "M",
    "n_bits"} and the cagra keys {"build_algo", "graph_degree",
    "intermediate_graph_degree", "itopk_size", "search_width",
    "max_iterations", "min_iterations", "num_random_samplings"} plus the
    TPU-build knobs {"cluster_reps", "nn_descent_niter",
    "termination_threshold", "fast_score"} (ops/cagra.py build_cagra).
    """

    def __init__(self, **kwargs: Any) -> None:
        super().__init__()
        self._setDefault(k=5, algorithm="ivfflat")
        self._set_params(**kwargs)

    def _set_params(self, **kwargs):
        if "algorithm" in kwargs and kwargs["algorithm"] not in (
            "ivfflat", "ivfpq", "cagra",
        ):
            raise ValueError(
                f"algorithm {kwargs['algorithm']!r} not supported"
                " (ivfflat | ivfpq | cagra)"
            )
        if "metric" in kwargs and kwargs["metric"] not in (
            "euclidean", "sqeuclidean", "cosine",
        ):
            raise ValueError(
                f"metric {kwargs['metric']!r} not supported"
                " (euclidean | sqeuclidean | cosine)"
            )
        if "algoParams" in kwargs:
            ap = kwargs.pop("algoParams") or {}
            if "compression" in ap:
                raise ValueError(
                    "cagra 'compression' is not supported by the TPU backend"
                )
            mapped = {
                "nlist": "n_lists", "nprobe": "n_probes", "M": "pq_m",
                "n_bits": "pq_n_bits", "refine_ratio": "refine_ratio",
            }
            # REPLACE semantics (reference setAlgoParams resets the whole
            # Param dict): keys a previous algoParams set revert to their
            # defaults first, so config sweeps don't inherit stale knobs
            defaults = self._get_solver_params_default()
            for prev in getattr(self, "_algo_params_keys", ()):  # type: ignore[attr-defined]
                if prev in defaults:
                    self._solver_params[prev] = defaults[prev]
                else:
                    self._solver_params.pop(prev, None)
            applied = set()
            for key, v in ap.items():
                solver_key = mapped.get(key, key)
                self._solver_params[solver_key] = v
                applied.add(solver_key)
            self._algo_params_keys = applied
        return super()._set_params(**kwargs)

    def setK(self, value: int) -> "ApproximateNearestNeighbors":
        return self._set_params(k=value)

    def setInputCol(self, value) -> "ApproximateNearestNeighbors":
        return self._set_params(inputCol=value) if isinstance(value, str) else self._set_params(inputCols=value)

    def setIdCol(self, value: str) -> "ApproximateNearestNeighbors":
        return self._set_params(idCol=value)

    # reference accessor surface (knn.py:850-888)
    def setAlgorithm(self, value: str) -> "ApproximateNearestNeighbors":
        return self._set_params(algorithm=value)

    def getAlgorithm(self) -> str:
        return self.getOrDefault("algorithm")

    def setAlgoParams(self, value: Dict[str, Any]) -> "ApproximateNearestNeighbors":
        return self._set_params(algoParams=value)

    def setMetric(self, value: str) -> "ApproximateNearestNeighbors":
        return self._set_params(metric=value)

    def getMetric(self) -> str:
        return str(self._solver_params["metric"])

    def _get_tpu_fit_func(self, extracted):  # pragma: no cover - _fit_internal overridden
        raise NotImplementedError

    def _fit_internal(self, dataset: Any, paramMaps):
        from ..ops.knn import build_ivfflat, build_ivfpq
        from ..parallel.mesh import dtype_scope

        pdf = as_pandas(dataset)
        extracted = self._pre_process_data(dataset, for_fit=True)
        feats = extracted.features
        if hasattr(feats, "todense"):
            feats = np.asarray(feats.todense())
        if str(self._solver_params["metric"]) == "cosine":
            # cosine rides the euclidean kernels on unit vectors (identical
            # ranking); stored index vectors are normalized, searches
            # normalize queries and convert distances (kneighbors)
            from ..utils import unit_rows

            feats = unit_rows(feats)
        algo = self.getOrDefault("algorithm")
        # index BUILD must not run at raw TPU bf16 (1-pass, ~3 digits — wrecks
        # quantizer training and recall), but the 3-pass mode's ~1e-6 relative
        # error is far below quantization error, at ~2x the f32 throughput
        with dtype_scope(np.float32, "BF16_BF16_F32_X3"):
            if algo == "ivfpq":
                index = build_ivfpq(
                    feats, int(self._solver_params["n_lists"]),
                    M=int(self._solver_params["pq_m"]),
                    n_bits=int(self._solver_params["pq_n_bits"]),
                    seed=0,
                )
            elif algo == "cagra":
                from ..ops.cagra import build_cagra

                # cuVS validates itopk_size >= k up front (knn.py:1286-1297);
                # fail at fit like the reference does at first use
                itopk = int(self._solver_params.get("itopk_size", 64))
                internal = -(-itopk // 32) * 32
                if internal < int(self._solver_params["n_neighbors"]):
                    raise ValueError(
                        f"cagra rounds itopk_size up to a multiple of 32"
                        f" ({internal}) and requires it >= k"
                        f" ({int(self._solver_params['n_neighbors'])})"
                    )
                index = build_cagra(
                    feats,
                    graph_degree=int(self._solver_params["graph_degree"]),
                    intermediate_graph_degree=int(
                        self._solver_params["intermediate_graph_degree"]
                    ),
                    build_algo=str(self._solver_params["build_algo"]),
                    nn_descent_niter=int(self._solver_params["nn_descent_niter"]),
                    cluster_reps=int(self._solver_params["cluster_reps"]),
                    termination_threshold=float(
                        self._solver_params["termination_threshold"]
                    ),
                    fast_score=bool(self._solver_params["fast_score"]),
                    seed=0,
                )
            else:
                index = build_ivfflat(feats, int(self._solver_params["n_lists"]), seed=0)
        model = ApproximateNearestNeighborsModel(
            n_cols=extracted.n_cols, dtype="float32" if self._float32_inputs else "float64"
        )
        self._copyValues(model)
        self._copy_solver_params(model)
        model._item_pdf = pdf
        model._item_extracted = extracted
        model._index = index
        model._algorithm = algo
        return [model]

    def _create_model(self, attrs):  # pragma: no cover
        return ApproximateNearestNeighborsModel(**attrs)

    def write(self):
        raise NotImplementedError("ApproximateNearestNeighbors does not support saving")


class ApproximateNearestNeighborsModel(NearestNeighborsModel):
    def __init__(self, **kwargs: Any) -> None:
        super().__init__(**kwargs)
        self._index = None
        self._algorithm = "ivfflat"

    def _refine_exact(self, queries: np.ndarray, cand_idx: np.ndarray, k: int):
        """Exact re-rank of ADC candidates (cuVS refine): gather the candidate
        item vectors and score true euclidean distances; −1 pads stay last.
        Under metric='cosine' both sides are unit-normalized (queries arrive
        normalized from kneighbors; the stored item vectors are raw)."""
        items = self._item_extracted.features
        if hasattr(items, "todense"):
            items = np.asarray(items.todense())
        items = np.asarray(items, dtype=np.float64)
        if str(self._solver_params["metric"]) == "cosine":
            from ..utils import unit_rows

            items = np.asarray(unit_rows(items), dtype=np.float64)
        q = np.asarray(queries, dtype=np.float64)
        safe = np.maximum(cand_idx, 0)
        cand = items[safe]  # [nq, k_adc, d]
        d2 = ((cand - q[:, None, :]) ** 2).sum(axis=2)
        d2 = np.where(cand_idx >= 0, d2, np.inf)
        order = np.argsort(d2, axis=1)[:, :k]
        dist = np.sqrt(np.take_along_axis(d2, order, axis=1))
        idx = np.take_along_axis(cand_idx, order, axis=1)
        return dist, idx

    def _get_solver_params_default(self) -> Dict[str, Any]:
        return _ANNParams._get_solver_params_default(self)

    # the reference mixes the accessor surface into the model too (knn.py
    # params class shared by estimator and model)
    def getAlgorithm(self) -> str:
        return self._algorithm

    def getMetric(self) -> str:
        return str(self._solver_params["metric"])

    def kneighbors(self, query_df: Any) -> Tuple[Any, Any, Any]:
        """Under multi-process SPMD this is the reference's local-index +
        broadcast-query + global top-k merge (knn.py:1189-1261): each rank
        built an index over ITS item partition at fit time; query blocks are
        rendezvous-replicated, every rank searches its local index for ALL
        queries, the per-rank top-k candidate sets are allgathered and merged
        by distance, and each rank keeps its own queries' rows."""
        import jax
        import pandas as pd

        from ..parallel import TpuContext
        from ..parallel.context import allgather_concat, allgather_ndarray
        from ..ops.knn import ivfflat_search, ivfpq_search
        from ..parallel.mesh import dtype_scope

        assert self._index is not None and self._item_pdf is not None
        k = int(self._solver_params["n_neighbors"])
        item_ex = self._item_extracted
        query_pdf = as_pandas(query_df)
        query_ex = self._pre_process_data(query_df, for_fit=False)
        item_ids = self._ensure_id(self._item_pdf, item_ex)
        query_ids = self._ensure_id(query_pdf, query_ex)

        active = TpuContext.current()
        spmd = active is not None and active.is_spmd
        q_offset, nq_local = 0, len(query_pdf)
        if spmd:
            rdv = active.rendezvous
            # default row-number ids must be GLOBAL: offset by the rows held
            # on lower ranks (an explicit idCol is used as-is) — item AND
            # query ids, so per-rank result frames concatenate unambiguously
            if item_ex.row_id is None:
                counts = [int(c) for c in rdv.allgather(str(len(item_ids)))]
                item_ids = item_ids + sum(counts[: active.rank])
            if query_ex.row_id is None:
                qcounts = [int(c) for c in rdv.allgather(str(len(query_ids)))]
                query_ids = query_ids + sum(qcounts[: active.rank])

        metric = str(self._solver_params["metric"])
        with dtype_scope(np.float32):
            queries = query_ex.features
            if hasattr(queries, "todense"):
                queries = np.asarray(queries.todense())
            if metric == "cosine":
                from ..utils import unit_rows

                queries = unit_rows(queries)
            if spmd:
                queries, q_offset = allgather_concat(
                    active.rendezvous, np.asarray(queries, dtype=np.float32)
                )
            if self._algorithm == "ivfpq":
                refine = max(1, int(self._solver_params.get("refine_ratio", 4)))
                k_adc = min(k * refine, item_ex.n_rows)
                dist, idx = ivfpq_search(
                    jax.device_put(queries.astype(np.float32)),
                    self._index,
                    k=k_adc,
                    n_probes=int(self._solver_params["n_probes"]),
                    batch_queries=int(self._solver_params["batch_queries"]),
                )
                if k_adc > k:
                    dist, idx = self._refine_exact(np.asarray(queries), np.asarray(idx), k)
            elif self._algorithm == "cagra":
                from ..ops.cagra import cagra_search

                sp = self._solver_params
                idx, d2 = cagra_search(
                    np.asarray(queries, dtype=np.float32),
                    self._index,
                    k=min(k, item_ex.n_rows),
                    itopk_size=int(sp["itopk_size"]),
                    search_width=int(sp["search_width"]),
                    max_iterations=int(sp["max_iterations"]),
                    min_iterations=int(sp["min_iterations"]),
                    num_random_samplings=int(sp["num_random_samplings"]),
                    batch_queries=int(sp["batch_queries"]),
                )
                # framework-wide convention: euclidean distances (the
                # reference returns squared L2 for its ANN algorithms —
                # documented deviation, docs/compatibility.md)
                dist = np.sqrt(np.maximum(d2, 0.0))
                if k > item_ex.n_rows:  # pad like the ivf paths
                    padw = k - item_ex.n_rows
                    idx = np.concatenate(
                        [idx, np.full((len(idx), padw), -1, idx.dtype)], axis=1
                    )
                    dist = np.concatenate(
                        [dist, np.full((len(dist), padw), np.inf, dist.dtype)], axis=1
                    )
            else:
                dist, idx = ivfflat_search(
                    jax.device_put(queries.astype(np.float32)),
                    jax.device_put(self._index["centroids"].astype(np.float32)),
                    jax.device_put(self._index["buckets"]),
                    jax.device_put(self._index["bucket_ids"]),
                    k=k,
                    n_probes=int(self._solver_params["n_probes"]),
                    batch_queries=int(self._solver_params["batch_queries"]),
                )
        dist = np.asarray(dist, dtype=np.float64)
        # metric output conversion (monotone — safe before the SPMD merge):
        # the kernels produce euclidean distances (on unit vectors for cosine)
        if metric == "sqeuclidean":
            dist = dist * dist
        elif metric == "cosine":
            dist = (dist * dist) / 2.0  # unit vectors: 1 - cosθ; inf pads stay inf
        idx = np.asarray(idx)
        indices = np.where(idx >= 0, item_ids[np.maximum(idx, 0)], -1)
        if spmd:
            # global top-k merge of the per-rank candidate sets (the
            # reference's _agg_topk groupBy, knn.py:1221-1261), then keep this
            # rank's own queries
            d_all = np.concatenate(
                allgather_ndarray(active.rendezvous, dist), axis=1
            )  # [nq_global, R*k]
            i_all = np.concatenate(
                allgather_ndarray(active.rendezvous, indices.astype(np.int64)), axis=1
            )
            order = np.argsort(d_all, axis=1, kind="stable")[:, :k]
            dist = np.take_along_axis(d_all, order, axis=1)
            indices = np.take_along_axis(i_all, order, axis=1)
            dist = dist[q_offset : q_offset + nq_local]
            indices = indices[q_offset : q_offset + nq_local]
        knn_df = pd.DataFrame(
            {"query_id": query_ids, "indices": list(indices), "distances": list(dist)}
        )
        id_col = self.getOrDefault("idCol") if self.isDefined("idCol") else alias.row_number
        item_out = self._item_pdf.copy(deep=False)
        if id_col not in item_out.columns:
            item_out[id_col] = item_ids
        query_out = query_pdf.copy(deep=False)
        if id_col not in query_out.columns:
            query_out[id_col] = query_ids
        return item_out, query_out, knn_df

    def approxSimilarityJoin(self, query_df: Any, distCol: str = "distCol") -> Any:
        return self.exactNearestNeighborsJoin(query_df, distCol)
