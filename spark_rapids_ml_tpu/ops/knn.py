#
# Distributed k-nearest-neighbors solvers — in-tree replacements for
# `cuml.neighbors.nearest_neighbors_mg.NearestNeighborsMG` (exact, reference
# knn.py:649) and the local-index ANN path (`cuml.neighbors.NearestNeighbors`
# IVFFlat, reference knn.py:1393-1404).
#
# Exact kNN, TPU-native shape: instead of the reference's UCX all-to-all
# (query blocks shuffled between ranks), ITEMS stay row-sharded and QUERIES are
# replicated: every device computes a [q_tile, n_local] distance tile on the
# MXU, takes a per-shard top-k, and the [n_dev, nq, k] candidates are gathered
# and merged with one final top-k — an all-gather of k·nq scalars instead of an
# item shuffle, which is the right trade on ICI (SURVEY.md §2.4 all-to-all row).
#
# ANN IVFFlat: per-shard KMeans coarse quantizer + PADDED cluster buckets
# (fixed list length -> static shapes); queries probe the nprobe closest
# centroids and search only those buckets via gather — the TPU analog of the
# IVF list scan.
#
from __future__ import annotations

from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import ROWS_AXIS
from .distance import (
    argmin_assign,
    pairwise_d2,
    row_sq,
    shard_map_check_vma,
    tile_topk,
    topk_tile,
)

# row-tiled nearest-centroid assignment (shared core), compiled once per shape
_assign_rows = jax.jit(argmin_assign)

# the per-device search is the SHARED core's (ops/distance.py): on the
# kernel path one fused distance + top-k kernel over all queries, so no
# [queries, n_loc] distance block reaches HBM; the jnp form scans query tiles
# of config["distance_tile_rows"] rows
_tile_topk = tile_topk


@jax.jit
def _row_sq(x):
    return row_sq(x)


@partial(jax.jit, static_argnames=("kk",))
def _topk_tile_1dev(items, valid, item_sq, q, *, kk):
    """One compiled query-tile program over the shared core (the host-looped
    single-device path below), with the kernel's merged pairs."""
    d2, idx, merged = topk_tile(q, items, valid, kk, item_sq=item_sq, count_merges=True)
    return d2 + row_sq(q)[:, None], idx, merged


# On the kernel path one program takes up to this many `distance_tile_rows`
# of queries: the fused kernel walks the query blocks itself and holds no
# [tile, items] block, so the items are read once a query block, not once a
# tile per query block.
_KERNEL_TILES = 8


def search_tile(nq: int, n_items: int, d: int, kk: int, dtype, batch_queries=None):
    """(query rows a program, top-k path) of the one-device search:
    `batch_queries` (default `config["distance_tile_rows"]`) on the jnp
    path; on the kernel path (`distance.topk_plan`) every query, up to
    `_KERNEL_TILES` tiles, in one program."""
    from .distance import tile_rows, topk_plan

    tile = batch_queries or tile_rows()
    if topk_plan(min(nq, tile), n_items, d, kk, dtype, False) is not None:
        return max(1, min(nq, _KERNEL_TILES * tile)), "kernel"
    return max(1, min(nq, tile)), "jnp"


def dispatch_1dev(items, valid, queries, k, batch_queries=None, item_sq=None):
    """Launch the one-device search and return what `fetch_1dev` takes: the
    device results of each query tile with the rows it adds, not waited
    for. A HOST loop over query tiles, each one top-level program over the
    shared core (distance.topk_tile), every tile the same shape (the last
    clamped back, its overlap dropped at the fetch) so the program compiles
    once. The shard_map/in-program tiling form costs a full copy of the
    item matrix at benchmark scale (measured +11 GiB at 1M x 3k -> OOM),
    same XLA behavior as the KMeans tile loop."""
    nq = queries.shape[0]
    kk = min(k, items.shape[0])
    tile, _ = search_tile(nq, items.shape[0], items.shape[1], kk, queries.dtype, batch_queries)
    if item_sq is None:
        item_sq = _row_sq(items)
    parts = []
    for start in range(0, nq, tile):
        s0 = min(start, nq - tile)
        d2, idx, merged = _topk_tile_1dev(items, valid, item_sq, queries[s0 : s0 + tile], kk=kk)
        parts.append((d2, idx, merged, start - s0))
    return parts


def fetch_1dev(parts, k, dtype):
    """Host (euclidean distances [nq, k], item positions [nq, k], merged
    pairs) of `dispatch_1dev`'s tiles, in one fetch; k past the items is
    padded with +inf. The merged pairs (the fused kernel's count, 0 on the
    jnp form) are summed over the tiles, the clamped last tile's in full."""
    import numpy as np

    if not parts:
        return np.zeros((0, k), dtype=dtype), np.zeros((0, k), dtype=np.int32), 0
    # results stay HOST numpy: every caller consumes numpy
    host = jax.device_get([part[:3] for part in parts])  # host-fetch-ok: the search's one result fetch
    d2 = np.concatenate([d[fresh:] for (d, _, _), (*_, fresh) in zip(host, parts)], axis=0)
    idx = np.concatenate([i[fresh:] for (_, i, _), (*_, fresh) in zip(host, parts)], axis=0)
    kk = d2.shape[1]
    if kk < k:
        d2 = np.pad(d2, ((0, 0), (0, k - kk)), constant_values=np.inf)
        idx = np.pad(idx, ((0, 0), (0, k - kk)))
    return np.sqrt(np.maximum(d2, 0.0)), idx, sum(int(m) for _, _, m in host)


def _exact_knn_1dev(items, valid, queries, k, batch_queries):
    """Single-device exact kNN: `dispatch_1dev`, then `fetch_1dev`."""
    return fetch_1dev(dispatch_1dev(items, valid, queries, k, batch_queries), k, queries.dtype)[:2]


@partial(jax.jit, static_argnames=())
def _sparse_tile_merge(xt, q, best_d2, best_i, tile_ids, fresh):
    """Merge one densified item tile into the running top-k: d² tile vs all
    queries (one shared-core distance tile, ops/distance.py), concat with
    the carried best, re-top-k. `fresh` masks rows already merged by a
    previous tile (the clamped last tile overlaps — a duplicate candidate
    would otherwise occupy two slots)."""
    d2 = pairwise_d2(q, xt)  # [nq, bt]
    d2 = jnp.where(fresh[None, :], d2, jnp.inf)
    cat_d = jnp.concatenate([best_d2, d2], axis=1)
    cat_i = jnp.concatenate([best_i, jnp.broadcast_to(tile_ids[None, :], d2.shape)], axis=1)
    neg_d, pos = jax.lax.top_k(-cat_d, best_d2.shape[1])
    return -neg_d, jnp.take_along_axis(cat_i, pos, axis=1)


def exact_knn_sparse(items_csr, queries, k: int, batch_items: int = 65536):
    """Exact kNN with SPARSE (scipy CSR) items: item tiles are densified one at
    a time on device and merged into a running top-k — CSR never fully
    densifies in memory (the reference's sparse kNN capability,
    cuML NearestNeighborsMG on cupyx CSR). Queries are dense [nq, d].

    Returns host (distances [nq, k] euclidean, item row indices [nq, k])."""
    import numpy as np

    n, d = items_csr.shape
    nq = queries.shape[0]
    kk = min(k, n)
    batch_items = min(batch_items, n)
    dtype = queries.dtype if queries.dtype in (np.float32, np.float64) else np.float32
    if nq == 0:
        return np.zeros((0, k), dtype=dtype), np.zeros((0, k), dtype=np.int32)
    q_dev = jax.device_put(np.ascontiguousarray(queries, dtype=dtype))
    best_d2 = jnp.full((nq, kk), jnp.inf, dtype)
    best_i = jnp.full((nq, kk), -1, jnp.int32)
    for start in range(0, n, batch_items):
        # clamp the last tile back so every tile has the same shape (single
        # compile); `fresh` masks the re-visited overlap rows
        s0 = min(start, max(0, n - batch_items))
        stop = s0 + batch_items
        xt = np.asarray(items_csr[s0:stop].todense(), dtype=dtype)
        tile_ids = jnp.arange(s0, stop, dtype=jnp.int32)
        fresh = tile_ids >= start
        best_d2, best_i = _sparse_tile_merge(
            xt, q_dev, best_d2, best_i, tile_ids, fresh
        )
    dist = np.sqrt(np.maximum(np.asarray(best_d2), 0.0))
    idx = np.asarray(best_i)
    if kk < k:
        dist = np.pad(dist, ((0, 0), (0, k - kk)), constant_values=np.inf)
        idx = np.pad(idx, ((0, 0), (0, k - kk)), constant_values=-1)
    return dist, idx


def exact_knn(
    items: jax.Array,  # [n_pad, d] row-sharded
    valid: jax.Array,  # [n_pad] bool (False on padding)
    queries: jax.Array,  # [nq, d] replicated
    *,
    mesh,
    k: int,
    batch_queries: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Global exact kNN: returns (distances [nq, k], GLOBAL item indices [nq, k])
    sorted ascending by distance. Distances are euclidean (not squared), Spark/
    cuML convention. `batch_queries` defaults to
    ``config["distance_tile_rows"]`` (the shared core's row-tile knob)."""
    if mesh.devices.size == 1:
        return _exact_knn_1dev(items, valid, queries, k, batch_queries)
    return _exact_knn_sharded(
        items, valid, queries, mesh=mesh, k=k, batch_queries=batch_queries
    )


@partial(jax.jit, static_argnames=("mesh", "k", "batch_queries"))
def _exact_knn_sharded(
    items: jax.Array,
    valid: jax.Array,
    queries: jax.Array,
    *,
    mesh,
    k: int,
    batch_queries: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    n_dev = mesh.devices.size
    n_loc = items.shape[0] // n_dev

    def local(items_l, valid_l):
        rank = jax.lax.axis_index(ROWS_AXIS)
        d2, idx = _tile_topk(items_l, queries, valid_l, k, batch_queries)
        gidx = idx + rank * n_loc
        return d2, gidx

    # per-shard candidates come back stacked over the mesh axis ([n_dev*nq, k]);
    # the merge below is a tiny [nq, n_dev*k] top-k that XLA gathers itself —
    # an all-gather of k·nq scalars, not an item shuffle
    d2_all, gidx_all = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(ROWS_AXIS, None), P(ROWS_AXIS)),
        out_specs=(P(ROWS_AXIS, None), P(ROWS_AXIS, None)),
        check_vma=shard_map_check_vma(),
    )(items, valid)
    nq = queries.shape[0]
    d2_cat = jnp.moveaxis(d2_all.reshape(n_dev, nq, k), 0, 1).reshape(nq, -1)
    gidx_cat = jnp.moveaxis(gidx_all.reshape(n_dev, nq, k), 0, 1).reshape(nq, -1)
    neg_d, pos = jax.lax.top_k(-d2_cat, k)
    final_idx = jnp.take_along_axis(gidx_cat, pos, axis=1)
    d2_final = jnp.maximum(-neg_d, 0.0)
    # replicate the [nq, k] result so every process can fetch it whole under
    # multi-process SPMD (each rank then slices its own queries' rows)
    from jax.sharding import NamedSharding

    rep = NamedSharding(mesh, P())
    return (
        jax.lax.with_sharding_constraint(jnp.sqrt(d2_final), rep),
        jax.lax.with_sharding_constraint(final_idx, rep),
    )


# ---------------------------------------------------------------------------
# IVFFlat approximate kNN (single-shard index; the estimator runs one per
# partition like the reference's local-index design)
# ---------------------------------------------------------------------------


def build_ivfflat(x, n_lists: int, seed: int = 0, kmeans_iters: int = 10):
    """Build an IVFFlat index: returns dict with centroids [n_lists, d],
    buckets [n_lists, L, d], bucket_ids [n_lists, L] (−1 pad) — centroids and
    buckets are DEVICE arrays (the search consumes them in HBM; only the tiny
    id layout is host-built).

    Bucket fill is one device gather through the host-computed padded id
    layout — the item matrix itself never crosses back to the host."""
    import numpy as np

    xd, centroids, assign, sorted_assign, order, offsets, n_lists, L = _coarse_quantizer(
        x, n_lists, seed, kmeans_iters
    )
    n, d = xd.shape
    bucket_ids = np.full((n_lists, L), -1, np.int64)
    bucket_ids[sorted_assign, offsets] = order
    idsj = jax.device_put(bucket_ids)
    buckets = _gather_buckets(xd, idsj)
    return {"centroids": centroids, "buckets": buckets, "bucket_ids": idsj}


@jax.jit
def _gather_buckets(X, I):
    """Padded bucket layout via one device gather (pad ids −1 -> zero row)."""
    n = X.shape[0]
    return jnp.where((I >= 0)[:, :, None], X[jnp.clip(I, 0, n - 1)], 0.0)


def _coarse_quantizer(x, n_lists: int, seed: int, kmeans_iters: int = 10):
    """Shared IVF coarse step: KMeans centroids + per-row assignment + the
    sorted-fill layout (order, offsets, counts, L).

    Accepts a host array OR a device-resident jax.Array (benchmark datagen
    produces the latter). Every heavy step — k-means|| seeding, Lloyd
    iterations, assignment — is device-resident; only the [n] int32
    assignment vector is fetched for the host-side bucket layout."""
    import numpy as np

    from .kmeans import _kmeanspp_device, kmeans_fit, scalable_kmeans_init_device
    from ..parallel.mesh import get_mesh

    if isinstance(x, jax.Array):
        xd = x if x.dtype == jnp.float32 else x.astype(jnp.float32)
    else:
        xd = jax.device_put(np.ascontiguousarray(np.asarray(x, dtype=np.float32)))
    n, d = xd.shape
    n_lists = min(n_lists, n)
    ones = jnp.ones((n,), jnp.float32)
    if n_lists >= 64:
        centers0 = scalable_kmeans_init_device(xd, n_lists, seed)
    else:
        # bound the k-means++ scan: one contiguous slice (ordering bias is
        # washed out by the full-data Lloyd refinement below)
        n_pp = min(n, 262_144)
        xs = jax.lax.dynamic_slice_in_dim(xd, 0, n_pp, 0) if n_pp < n else xd
        centers0 = _kmeanspp_device(
            xs, jnp.ones((n_pp,), jnp.float32), seed, k=n_lists
        )
    # no final high-precision inertia pass: nothing consumes it, and its
    # program is a separate ~79s compile in a fresh process
    state = kmeans_fit(
        xd, ones, centers0,
        mesh=get_mesh(1), max_iter=kmeans_iters, tol=1e-6, final_inertia=False,
    )
    centroids_dev = state["cluster_centers_"].astype(jnp.float32)
    assign = np.asarray(_assign_rows(xd, centroids_dev))
    counts = np.bincount(assign, minlength=n_lists)
    L = max(1, int(counts.max()))
    order = np.argsort(assign, kind="stable")
    sorted_assign = assign[order]
    offsets = np.arange(n) - (np.cumsum(counts) - counts)[sorted_assign]
    return xd, centroids_dev, assign, sorted_assign, order, offsets, n_lists, L


def build_ivfpq(
    x, n_lists: int, *, M: int = 8, n_bits: int = 8, seed: int = 0,
    kmeans_iters: int = 10, pq_iters: int = 10, train_cap: int = 65536,
):
    """Build an IVFPQ index: coarse quantizer + per-subspace product
    quantization of the RESIDUALS (x − centroid), ADC-searchable.

    `algoParams` naming follows cuML ({"M": subquantizers, "n_bits": bits per
    code}, reference knn.py:1393-1404). Returns dict with centroids
    [C, d], codebooks [M, K, dsub] (K = 2^n_bits), code_buckets [C, L, M] uint8,
    bucket_ids [C, L] (−1 pad).
    """
    import numpy as np

    from .kmeans import _kmeanspp_device, kmeans_fit
    from ..parallel.mesh import get_mesh

    xd, centroids, assign, sorted_assign, order, offsets, n_lists, L = _coarse_quantizer(
        x, n_lists, seed, kmeans_iters
    )
    n, d = xd.shape
    if d % M:
        raise ValueError(f"M={M} must divide the feature dimension d={d}")
    dsub = d // M
    K = 1 << n_bits

    # train per-subspace codebooks on a RESIDUAL subsample built from a few
    # contiguous row blocks at random offsets: no full [n, d] residual matrix
    # is ever materialized (that doubles HBM at large shapes), and no
    # fancy-index gather touches the big x (the pattern XLA answers with a
    # full device copy)
    rs = np.random.default_rng(seed)
    cap = min(n, train_cap)
    n_blocks = min(16, max(1, cap // 1024)) if cap < n else 1
    bs = cap // n_blocks
    assign_dev = jax.device_put(assign)
    blocks = []
    for b in range(n_blocks):
        off = int(rs.integers(0, max(1, n - bs + 1))) if cap < n else b * bs
        blocks.append(_residual_block(xd, centroids, assign_dev, off, size=bs))
    train = jnp.concatenate(blocks, axis=0) if len(blocks) > 1 else blocks[0]
    codebooks = np.zeros((M, K, dsub), np.float32)
    mesh1 = get_mesh(1)
    for m in range(M):
        sub = train[:, m * dsub : (m + 1) * dsub]
        sub_w = jnp.ones((sub.shape[0],), jnp.float32)
        k_eff = min(K, sub.shape[0])
        c0 = _kmeanspp_device(  # one dispatch; shared shape across all M
            sub, sub_w, seed + m, k=k_eff,
        )
        st = kmeans_fit(
            sub, sub_w, c0,
            mesh=mesh1, max_iter=pq_iters, tol=1e-6, final_inertia=False,
        )
        codebooks[m, :k_eff] = np.asarray(st["cluster_centers_"])  # host-fetch-ok: one codebook fetch per PQ subspace (M is small and fixed), landing in the host codebook table
        if k_eff < K:  # degenerate tiny datasets: repeat the first centroid
            codebooks[m, k_eff:] = codebooks[m, 0]

    # encode all points: residual + nearest codeword per subspace, TILED over
    # rows inside one program — the per-tile residual is transient, so peak
    # HBM stays x + one tile; only the [n, M] code matrix crosses to host
    codes = np.asarray(
        _encode_residuals(xd, centroids, assign_dev, jax.device_put(codebooks))
    ).astype(np.uint8 if n_bits <= 8 else np.int32)

    code_buckets = np.zeros((n_lists, L, M), codes.dtype)
    bucket_ids = np.full((n_lists, L), -1, np.int64)
    code_buckets[sorted_assign, offsets] = codes[order]
    bucket_ids[sorted_assign, offsets] = order
    return {
        "centroids": centroids,
        "codebooks": codebooks,
        "code_buckets": code_buckets,
        "bucket_ids": bucket_ids,
    }


@partial(jax.jit, static_argnames=("size",))
def _residual_block(X, C, A, off, *, size):
    """Residuals of one contiguous row block: X[off:off+size] − C[A[...]]."""
    xb = jax.lax.dynamic_slice_in_dim(X, off, size, 0)
    ab = jax.lax.dynamic_slice_in_dim(A, off, size, 0)
    return (xb - C[ab]).astype(jnp.float32)


@jax.jit
def _encode_residuals(X, C, A, CB):
    """PQ-encode every row: nearest codeword per subspace of (x − centroid),
    tiled over rows so the residual never exists in full. CB [M, K, dsub]."""
    n, d = X.shape
    M, K, dsub = CB.shape
    tile = max(256, min(n, 4_000_000 // max(d, 1)))
    n_tiles = -(-n // tile)
    cb_sq = jnp.sum(CB * CB, axis=2)  # [M, K]

    def body(ti, out):
        r0 = jnp.minimum(ti * tile, n - tile)
        xb = jax.lax.dynamic_slice(X, (r0, 0), (tile, d))
        ab = jax.lax.dynamic_slice(A, (r0,), (tile,))
        R = (xb - C[ab]).reshape(tile, M, dsub)
        d2 = cb_sq[None] - 2.0 * jnp.einsum("nmd,mkd->nmk", R, CB)
        codes_t = jnp.argmin(d2, axis=2).astype(jnp.int32)  # distance-ok: PQ nearest-codeword argmin over [tile, M, K] per-SUBSPACE residual distances — M parallel tiny codebooks, not the row-tile x·cᵀ shape the core owns
        return jax.lax.dynamic_update_slice(out, codes_t, (r0, 0))

    if n <= tile:
        R = (X - C[A]).reshape(n, M, dsub)
        d2 = cb_sq[None] - 2.0 * jnp.einsum("nmd,mkd->nmk", R, CB)
        return jnp.argmin(d2, axis=2).astype(jnp.int32)  # distance-ok: same per-subspace PQ codeword argmin as the tiled branch above
    return jax.lax.fori_loop(
        0, n_tiles, body, jnp.zeros((n, M), jnp.int32)
    )


@partial(jax.jit, static_argnames=("k", "n_probes", "batch_queries"))
def _ivfpq_search_impl(
    queries, centroids, codebooks, code_buckets, bucket_ids,
    *, k: int, n_probes: int, batch_queries: int,
):
    nq, d = queries.shape
    C, L, M = code_buckets.shape
    K = codebooks.shape[1]
    dsub = d // M
    n_probes = min(n_probes, C)
    n_tiles = max(1, -(-nq // batch_queries))
    pad = n_tiles * batch_queries - nq
    qp = jnp.pad(queries, ((0, pad), (0, 0)))
    cb_sq = jnp.sum(codebooks * codebooks, axis=2)  # [M, K]

    def one_tile(q):  # [B, d]
        B = q.shape[0]
        # coarse probe through the shared core (identical ranking: the
        # ||q||^2 term is constant per row)
        _, probe = topk_tile(q, centroids, None, n_probes)  # [B, P]
        # residual per probed list, split into subspaces
        q_res = q[:, None, :] - centroids[probe]  # [B, P, d]
        q_res = q_res.reshape(B, n_probes, M, dsub)
        # ADC lookup table: ||q_res_m − cb_mk||² (the einsum rides the MXU)
        lut = (
            jnp.sum(q_res * q_res, axis=3)[..., None]      # [B, P, M, 1]
            - 2.0 * jnp.einsum("bpmd,mkd->bpmk", q_res, codebooks)
            + cb_sq[None, None, :, :]
        )  # [B, P, M, K]
        cand_codes = code_buckets[probe].astype(jnp.int32)  # [B, P, L, M]
        cand_ids = bucket_ids[probe]  # [B, P, L]
        # dist[b,p,l] = Σ_m lut[b,p,m,codes[b,p,l,m]] — index the K axis
        # directly with codes transposed to [B, P, M, L]; broadcasting lut to
        # a [B,P,L,M,K] intermediate would materialize tens of GB
        codes_t = jnp.swapaxes(cand_codes, 2, 3)  # [B, P, M, L]
        picked = jnp.take_along_axis(lut, codes_t, axis=3)  # [B, P, M, L]
        dist = jnp.sum(picked, axis=2)  # [B, P, L]
        dist = jnp.where(cand_ids >= 0, dist, jnp.inf)
        dist = dist.reshape(B, n_probes * L)
        ids = cand_ids.reshape(B, n_probes * L)
        kk = min(k, n_probes * L)
        neg_d, pos = jax.lax.top_k(-dist, kk)
        out_ids = jnp.take_along_axis(ids, pos, axis=1)
        out_d = jnp.maximum(-neg_d, 0.0)
        if kk < k:
            out_d = jnp.pad(out_d, ((0, 0), (0, k - kk)), constant_values=jnp.inf)
            out_ids = jnp.pad(out_ids, ((0, 0), (0, k - kk)), constant_values=-1)
        return jnp.sqrt(out_d), out_ids

    qt = qp.reshape(n_tiles, batch_queries, d)
    dists, idxs = jax.lax.map(one_tile, qt)
    return dists.reshape(-1, k)[:nq], idxs.reshape(-1, k)[:nq]


def ivfpq_search(queries, index, *, k: int, n_probes: int, batch_queries: int = 256):
    """ADC search over an IVFPQ index (see build_ivfpq). Returns (approximate
    euclidean distances [nq, k], item ids [nq, k], −1 where short)."""
    return _ivfpq_search_impl(
        queries,
        jax.device_put(jnp.asarray(index["centroids"], jnp.float32)),
        jax.device_put(jnp.asarray(index["codebooks"], jnp.float32)),
        jax.device_put(jnp.asarray(index["code_buckets"])),
        jax.device_put(jnp.asarray(index["bucket_ids"])),
        k=k, n_probes=n_probes, batch_queries=batch_queries,
    )


@partial(jax.jit, static_argnames=("k", "n_probes", "batch_queries"))
def ivfflat_search(
    queries: jax.Array,  # [nq, d]
    centroids: jax.Array,  # [C, d]
    buckets: jax.Array,  # [C, L, d]
    bucket_ids: jax.Array,  # [C, L]
    *,
    k: int,
    n_probes: int,
    batch_queries: int = 1024,
) -> Tuple[jax.Array, jax.Array]:
    """Probe the n_probes nearest lists per query; returns (sqrt distances,
    item ids) [nq, k] (id −1 where fewer than k candidates).

    Lists are scanned ONE PROBE AT A TIME with a running top-k: gathering all
    probed buckets at once is [B, P, L, d] — hundreds of GB at benchmark
    scale. The query-tile width additionally adapts so the per-probe gather
    [B, L, d] stays under ~1 GB."""
    nq, d = queries.shape
    C, L, _ = buckets.shape
    n_probes = min(n_probes, C)
    # bound the per-probe gather to ~1 GB of f32
    b_mem = max(16, int(1e9 / max(1, 4 * L * d)))
    batch_queries = max(16, min(batch_queries, b_mem))
    n_tiles = max(1, -(-nq // batch_queries))
    pad = n_tiles * batch_queries - nq
    qp = jnp.pad(queries, ((0, pad), (0, 0)))
    kk = min(k, n_probes * L)

    def one_tile(q):  # [B, d]
        B = q.shape[0]
        # coarse probe through the shared core (ranking-identical, see above)
        _, probe = topk_tile(q, centroids, None, n_probes)  # [B, n_probes]
        q_sq = jnp.sum(q * q, axis=1)  # [B]

        def probe_body(p_i, carry):
            best_d, best_i = carry  # [B, kk]
            pb = probe[:, p_i]  # [B]
            bucket = buckets[pb]  # [B, L, d] — the bounded gather
            ids = bucket_ids[pb]  # [B, L]
            # ||q − x||² = ||q||² − 2 q·x + ||x||²; q·x via batched matmul
            d2 = (
                q_sq[:, None]
                - 2.0 * jnp.einsum("bld,bd->bl", bucket, q)
                + jnp.sum(bucket * bucket, axis=2)
            )
            d2 = jnp.where(ids >= 0, d2, jnp.inf)
            cat_d = jnp.concatenate([best_d, d2], axis=1)
            cat_i = jnp.concatenate([best_i, ids], axis=1)
            neg_d, pos = jax.lax.top_k(-cat_d, kk)  # distance-ok: IVF bucket scan — per-query GATHERED buckets ([B, L, d] batched einsum), not the shared row-tile x·cᵀ shape; the running kk-merge is the memory bound here
            return -neg_d, jnp.take_along_axis(cat_i, pos, axis=1)

        init = (
            jnp.full((B, kk), jnp.inf, queries.dtype),
            jnp.full((B, kk), -1, bucket_ids.dtype),
        )
        best_d, best_i = jax.lax.fori_loop(0, n_probes, probe_body, init)
        dist = jnp.maximum(best_d, 0.0)
        if kk < k:  # fewer candidates than k: pad
            dist = jnp.pad(dist, ((0, 0), (0, k - kk)), constant_values=jnp.inf)
            best_i = jnp.pad(best_i, ((0, 0), (0, k - kk)), constant_values=-1)
        return jnp.sqrt(dist), best_i

    qt = qp.reshape(n_tiles, batch_queries, d)
    dists, idxs = jax.lax.map(one_tile, qt)
    return dists.reshape(-1, k)[:nq], idxs.reshape(-1, k)[:nq]
