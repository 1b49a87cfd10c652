#
# Distributed DBSCAN solver — the in-tree replacement for
# `cuml.cluster.dbscan_mg.DBSCANMG` (consumed by reference
# clustering.py:944-1006).
#
# TPU-native design. The reference replicates the dataset to every rank and
# rank-slices the N² pairwise-distance problem (reference
# clustering.py:1013-1091); here the same shape becomes three tiled SPMD
# passes over a `shard_map` row-sliced mesh, each an MXU distance contraction:
#
#   1. CORE pass: per-point eps-neighbor counts -> core mask
#      (one tiled N x N pass, rows sliced across devices).
#   2. EXPANSION: connected components of the core-core eps-graph by
#      min-label propagation with pointer jumping (host-compacted core
#      subset, so each round is nc x nc, not N x N; rounds ~ O(log n)).
#   3. BORDER pass: non-core points adopt the min-labeled core neighbor;
#      no core neighbor -> noise (-1).
#
# Labels match sklearn/cuML: clusters numbered by ascending first-core-point
# index (min-label propagation's fixpoint root IS the cluster's minimum core
# index), noise = -1. Border points attach to their minimum-labeled core
# neighbor — deterministic where sklearn's is scan-order dependent.
#
# The `max_mbytes_per_batch` knob bounds each device's distance-tile footprint
# exactly like the reference's DBSCANMG batching (clustering.py:570-579).
#
from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import ROWS_AXIS
from .distance import pairwise_d2 as _pairwise_d2


def _tile_rows_for_budget(n: int, max_mbytes: Optional[int], default: int = 8192) -> int:
    """Rows per distance tile so one [tile, n] f32 tile fits the budget."""
    if not max_mbytes:
        return default
    rows = int(max_mbytes * 1e6 / (4 * max(n, 1)))
    return max(64, min(rows, max(n, 64)))


def _replicate_out(mesh, x):
    """Outputs of the rank-sliced passes come back rows-sharded; replicate the
    (small, [n]-sized) result so every SPMD process can fetch it whole."""
    from jax.sharding import NamedSharding

    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))


# the distance tile is the SHARED core's (distance.pairwise_d2, imported
# above): squared euclidean / cosine / precomputed pass-through — dbscan_fit
# pre-normalizes cosine rows and hands "precomputed" passes the matching
# column slice of the user's distance matrix, so the tile is just `q` there


def _map_row_tiles(fn, rows, tile_rows: int, extra=None):
    """Scan `fn` over row tiles of the per-device slice: pad the [n_loc, ...]
    leading axis to a tile multiple, `lax.map` over [tiles, tile_rows, ...]
    (bounding the live distance-tile footprint), and slice the padding back
    off. `extra` is a second per-row array carried alongside the rows."""
    n_loc = rows.shape[0]
    tiles = max(1, -(-n_loc // tile_rows))
    pad = tiles * tile_rows - n_loc
    qp = jnp.pad(rows, [(0, pad)] + [(0, 0)] * (rows.ndim - 1))
    qt = qp.reshape((tiles, tile_rows) + rows.shape[1:])
    if extra is not None:
        ep = jnp.pad(extra, (0, pad)).reshape(tiles, tile_rows)
        out = jax.lax.map(fn, (qt, ep))
    else:
        out = jax.lax.map(fn, qt)
    return out.reshape(-1)[: n_loc]


@partial(jax.jit, static_argnames=("mesh", "metric", "tile_rows"))
def core_mask(
    X: jax.Array,  # [n, d] REPLICATED
    valid: jax.Array,  # [n] bool
    eps2: float,
    min_samples: int,
    *,
    mesh,
    metric: str = "euclidean",
    tile_rows: int = 8192,
) -> jax.Array:
    """Per-point eps-neighborhood size (incl. self) >= min_samples: bool [n].

    Each device counts neighbors for ITS row slice (replicated data,
    rank-sliced N² — SURVEY.md §2.4 'replicated-data parallelism')."""
    n, d = X.shape
    n_dev = mesh.devices.size
    n_loc = n // n_dev

    def local(Xl, X_all, valid_all):  # Xl: [n_loc, d] this device's row slice
        def one_tile(q):
            d2 = _pairwise_d2(q, X_all, metric)
            neigh = (d2 <= eps2) & valid_all[None, :]
            return jnp.sum(neigh, axis=1)

        return _map_row_tiles(one_tile, Xl, tile_rows)

    counts = shard_map(
        local, mesh=mesh,
        in_specs=(P(ROWS_AXIS, None), P(None, None), P(None)),
        out_specs=P(ROWS_AXIS),
    )(X, X, valid)
    return _replicate_out(mesh, (counts >= min_samples) & valid)


@partial(jax.jit, static_argnames=("mesh", "metric", "tile_rows"))
def core_components(
    Xc: jax.Array,  # [nc_pad, d] core points, REPLICATED
    valid: jax.Array,  # [nc_pad] bool
    eps2: float,
    *,
    mesh,
    metric: str = "euclidean",
    tile_rows: int = 8192,
) -> jax.Array:
    """Connected components of the core-core eps-graph.

    Returns per-core root index [nc_pad]: the minimum core index of its
    component. Min-label propagation (one tiled nc x nc pass per round) plus
    two pointer-jumping hops per round -> rounds grow with log(component
    diameter), not diameter."""
    nc, d = Xc.shape
    n_dev = mesh.devices.size
    n_loc = nc // n_dev
    idx = jnp.arange(nc, dtype=jnp.int32)

    def propagate(labels):
        def local(Xl, idx_l, X_all, valid_all, labels_all):
            def one_tile(args):
                q, qi = args
                d2 = _pairwise_d2(q, X_all, metric)
                neigh = (d2 <= eps2) & valid_all[None, :]
                m = jnp.min(jnp.where(neigh, labels_all[None, :], nc), axis=1)
                return jnp.minimum(m.astype(jnp.int32), labels_all[qi])

            return _map_row_tiles(one_tile, Xl, tile_rows, extra=idx_l)

        return _replicate_out(mesh, shard_map(
            local, mesh=mesh,
            in_specs=(P(ROWS_AXIS, None), P(ROWS_AXIS), P(None, None), P(None), P(None)),
            out_specs=P(ROWS_AXIS),
        )(Xc, idx, Xc, valid, labels))

    labels0 = jnp.where(valid, idx, jnp.int32(nc))

    def cond(state):
        labels, prev, it = state
        return jnp.logical_and(jnp.any(labels != prev), it < nc)

    def body(state):
        labels, _, it = state
        new = propagate(labels)
        # pointer jumping: hop each label to its label's label (path halving)
        safe = jnp.minimum(new, nc - 1)
        new = jnp.where(valid, jnp.minimum(new, new[safe]), nc)
        safe = jnp.minimum(new, nc - 1)
        new = jnp.where(valid, jnp.minimum(new, new[safe]), nc)
        return new, labels, it + 1

    labels, _, _ = jax.lax.while_loop(
        cond, body, (labels0, jnp.full((nc,), -1, jnp.int32), jnp.int32(0))
    )
    return labels


@partial(jax.jit, static_argnames=("mesh", "metric", "tile_rows"))
def border_assign(
    X: jax.Array,  # [n, d] all points, REPLICATED
    valid: jax.Array,  # [n] bool
    Xc: jax.Array,  # [nc_pad, d] core points
    core_valid: jax.Array,  # [nc_pad] bool
    core_labels: jax.Array,  # [nc_pad] int32 cluster ids of core points
    eps2: float,
    *,
    mesh,
    metric: str = "euclidean",
    tile_rows: int = 8192,
) -> jax.Array:
    """For every point: the minimum cluster id among eps-neighboring core
    points, or -1 (noise) if none. Core points are their own neighbors."""
    n, d = X.shape
    n_dev = mesh.devices.size
    n_loc = n // n_dev
    big = jnp.int32(2**30)

    def local(Xl, Xc_all, cvalid_all, clabels_all):
        def one_tile(q):
            d2 = _pairwise_d2(q, Xc_all, metric)
            neigh = (d2 <= eps2) & cvalid_all[None, :]
            return jnp.min(jnp.where(neigh, clabels_all[None, :], big), axis=1)

        return _map_row_tiles(one_tile, Xl, tile_rows)

    m = shard_map(
        local, mesh=mesh,
        in_specs=(P(ROWS_AXIS, None), P(None, None), P(None), P(None)),
        out_specs=P(ROWS_AXIS),
    )(X, Xc, core_valid, core_labels)
    return _replicate_out(mesh, jnp.where((m < big) & valid, m, -1))


def dbscan_fit(
    x_host: np.ndarray,
    *,
    mesh,
    eps: float,
    min_samples: int,
    metric: str = "euclidean",
    max_mbytes_per_batch: Optional[int] = None,
    calc_core_sample_indices: bool = False,
) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Full DBSCAN: returns (labels [n] int32 with -1 noise, optional core
    sample indices). Orchestrates the three jitted passes; the host round-trip
    between passes compacts the core subset so expansion is nc², not N².

    metric="precomputed": `x_host` is the [n, n] distance matrix (sklearn/cuML
    convention, raw distances vs `eps`). Each pass receives the matching
    column slice of the matrix — the N² "distance" tiles become free reads
    (see _pairwise_d2) and everything else is unchanged.
    """
    n, d = x_host.shape
    n_dev = mesh.devices.size
    x = np.ascontiguousarray(x_host, dtype=np.float32)
    precomputed = metric == "precomputed"
    if precomputed:
        if n != d:
            raise ValueError(f"precomputed metric needs a square distance matrix, got {n}x{d}")
        eps2 = float(eps)
    elif metric == "cosine":
        norms = np.linalg.norm(x, axis=1, keepdims=True)
        x = x / np.maximum(norms, 1e-12)
        eps2 = float(eps)
    elif metric == "euclidean":
        eps2 = float(eps) ** 2
    else:
        raise ValueError(
            f"metric must be 'euclidean', 'cosine' or 'precomputed', got {metric!r}"
        )

    def pad_repl(a, multiple, fill=0.0):
        rem = (-a.shape[0]) % multiple
        if rem:
            a = np.pad(a, [(0, rem)] + [(0, 0)] * (a.ndim - 1), constant_values=fill)
        return a

    def pad_cols(a, width, fill=np.float32(1e30)):
        # precomputed slices must stay column-aligned with the passes' valid
        # masks; padded columns are masked, the fill is belt-and-braces
        if a.shape[1] < width:
            a = np.pad(a, [(0, 0), (0, width - a.shape[1])], constant_values=fill)
        return a

    tile = _tile_rows_for_budget(n, max_mbytes_per_batch)
    # replicated placement: under multi-process SPMD every rank passes the SAME
    # host array and the explicit replicated NamedSharding makes it one global
    # array over the full mesh (single-process device_put suffices otherwise)
    if jax.process_count() > 1:
        from ..parallel.mesh import replicated

        rep = replicated(mesh)
        put = lambda a: jax.device_put(a, rep)  # noqa: E731
    else:
        put = jax.device_put
    xp = pad_repl(x, n_dev)
    if precomputed:
        xp = pad_cols(xp, xp.shape[0])  # square: columns align with `valid`
    validp = np.arange(xp.shape[0]) < n
    X = put(xp)  # replicated
    valid = put(validp)

    core = np.asarray(core_mask(X, valid, eps2, min_samples, mesh=mesh, metric=metric, tile_rows=tile))
    core = core[:n]
    core_idx = np.flatnonzero(core)
    nc = len(core_idx)
    if nc == 0:
        labels = np.full(n, -1, np.int32)
        return labels, (core_idx if calc_core_sample_indices else None)

    xc = pad_repl(x[np.ix_(core_idx, core_idx)] if precomputed else x[core_idx], n_dev)
    if precomputed:
        xc = pad_cols(xc, xc.shape[0])
    cvalidp = np.arange(xc.shape[0]) < nc
    Xc = put(xc)
    cvalid = put(cvalidp)
    tile_c = _tile_rows_for_budget(xc.shape[0], max_mbytes_per_batch)

    roots = np.asarray(
        core_components(Xc, cvalid, eps2, mesh=mesh, metric=metric, tile_rows=tile_c)
    )[:nc]
    # sklearn/cuML numbering: clusters ordered by ascending first (minimum)
    # core index — exactly the propagation roots, ranked
    uniq_roots = np.unique(roots)
    core_cluster = np.searchsorted(uniq_roots, roots).astype(np.int32)

    core_labels_p = np.full(xc.shape[0], -1, np.int32)
    core_labels_p[:nc] = core_cluster
    if precomputed:
        # border pass rows must carry point-to-CORE distances, column-aligned
        # with the (padded) core axis
        xb = pad_cols(pad_repl(x[:, core_idx], n_dev), xc.shape[0])
        X_border = put(xb)
    else:
        X_border = X
    labels = np.asarray(
        border_assign(
            X_border, valid, Xc, cvalid, put(core_labels_p), eps2,
            mesh=mesh, metric=metric, tile_rows=tile,
        )
    )[:n].astype(np.int32)
    return labels, (core_idx if calc_core_sample_indices else None)
