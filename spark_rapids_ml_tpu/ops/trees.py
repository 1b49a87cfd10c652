#
# Distributed random-forest solver — the in-tree replacement for
# `cuml.RandomForestClassifier/Regressor` + Treelite concat (consumed by
# reference tree.py:324-378).
#
# TPU-native design (no CUDA-style per-node kernels):
#  * features are QUANTILE-BINNED once a placement (maxBins edges from a host
#    sample of the placed rows — the same sketch-then-bin scheme Spark ML
#    uses), so tree growth only touches compact bin ids (uint8 at <=256 bins,
#    the columns rounded up to the 128-lane tile: `binned_cols`);
#  * trees grow LEVEL-WISE in a full binary-array layout: one pass over the
#    rows a level builds the (node, feature, bin, stat) histogram for every
#    active row at once (`_level_histogram`: a one-hot contraction on the MXU
#    where the statistics are small integers, over the rows SORTED BY NODE
#    beyond `WINDOW_NODES` nodes a pass so that a row tile meets a few nodes
#    and not all of them; float32 statistics go in as three bfloat16 pieces
#    each (`stat_pieces`) over the sorted rows at every level; a `segment_sum`
#    scatter where neither holds), prefix sums over
#    bins give every candidate split's left/right stats, and the best
#    (feature, bin) per node is an argmax — all static shapes, fully jittable;
#  * a level whose histogram would outgrow `SEGMENT_BUDGET` is processed in
#    node CHUNKS, each a pass over the rows (the `max_batch_size` idea of
#    cuML's RF builder);
#  * the ensemble is split across the mesh exactly like the reference
#    (_estimators_per_worker, tree.py:270-281): each device grows its share of
#    trees on ITS row shard via shard_map (no collectives during growth), and
#    the stacked tree arrays are gathered at the end — the Treelite-concat
#    analog with arrays instead of serialized C++ objects.
#
# The draws are part of what a fit computes (`chipbench/families/rfc.py` holds
# its own copy of these few lines and re-derives every split from them): tree
# t of the forest (t = rank * trees_per_dev + round) has the key
# `fold_in(PRNGKey(seed), t)`; its bootstrap is `n_l` draws (the shard's rows)
# `randint(split(key)[0], 0, valid rows)` (int32) mapped to the r-th valid row; the
# nodes of level L take the first m columns of a stable `argsort` of
# `uniform(fold_in(key, 7919 + L), (2^L, d))` (float32). All integer or bit-exact
# float arithmetic: the same on every backend.
#
# A forest is a dict of arrays (n_trees leading axis):
#   feature   [T, M] int32   (-1 = leaf)           M = 2^(max_depth+1) - 1
#   threshold [T, M] f32     (split: x <= thr -> left child 2i+1)
#   leaf      [T, M, S] f32  (class counts / (w, wy) stats per node)
#
from __future__ import annotations

from functools import lru_cache, partial
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import distance, histogram

# ---------------------------------------------------------------------------
# Binning
# ---------------------------------------------------------------------------

SKETCH_ROWS = 100_000  # rows of the quantile sketch's sample
SKETCH_STREAM = 0  # the sample's fixed stream: edges depend on the rows and maxBins alone


def sketch_rows(n: int, sample_cap: int = SKETCH_ROWS, rank: int = 0) -> np.ndarray:
    """The sorted row indices of the quantile sketch's sample: all rows up to
    `sample_cap`, else `sample_cap` of them without replacement from a FIXED
    stream (per rank under SPMD) — never the estimator's seed, so every fit
    on one placement derives the same edges (the reference's solver takes
    its quantiles from the worker's rows with no seed either)."""
    if n <= sample_cap:
        return np.arange(n)
    stream = SKETCH_STREAM if rank == 0 else [SKETCH_STREAM, rank]
    rs = np.random.default_rng(stream)  # prng-ok: a fixed stream by design — the sketch must not depend on the estimator seed
    return np.sort(rs.choice(n, sample_cap, replace=False))


def quantile_bins(x_host: np.ndarray, max_bins: int, sample_cap: int = SKETCH_ROWS) -> np.ndarray:
    """Per-feature quantile bin edges from a host sample: [d, max_bins-1],
    float64. A function of the rows and `max_bins` alone (`sketch_rows`).

    Mirrors Spark ML's approxQuantile-based continuous-feature binning. The
    values are `np.quantile(sample.astype(float64), k / max_bins, axis=0)`'s,
    bit for bit (method "linear"), computed from one float32 sort a column
    and numpy's own interpolation of the two neighbouring order statistics:
    14x faster than `np.quantile` at 100,000 x 3,000, which selects each of
    the 127 ranks in turn. tests/test_forest_reference.py holds the two equal."""
    sample = np.ascontiguousarray(np.asarray(x_host)[sketch_rows(x_host.shape[0], sample_cap)].T)
    sample.sort(axis=1)  # [d, rows], each column's order statistics
    qs = np.linspace(0, 1, max_bins + 1)[1:-1]
    at = (sample.shape[1] - 1) * qs
    lo = np.floor(at).astype(np.int64)
    t = at - lo
    a = sample[:, lo].astype(np.float64)
    b = sample[:, np.minimum(lo + 1, sample.shape[1] - 1)].astype(np.float64)
    diff = b - a  # numpy's `_lerp`: a + (b - a) t, and from b's side for t >= 0.5
    return np.ascontiguousarray(np.where(t >= 0.5, b - diff * (1 - t), a + diff * t))


def _bin_dtype(edges):
    return jnp.uint8 if edges.shape[1] + 1 <= 256 else jnp.int32


BIN_LANES = 128  # the binned X has a multiple of this many columns


def binned_cols(d: int) -> int:
    """Columns of the binned X of d features: d rounded up to the 128-lane
    tile, the extra ones 0 and never read. A TPU lays a [rows, 3,000] array
    out column-major and a [rows, 3,072] one row-major, each by its own
    choice: a pass that fetches WHOLE ROWS in another order
    (`_sorted_histogram`) needs them contiguous, and a copy of the 1.18 GB
    into that layout in every level program was 33 ms by the compiler's own
    estimate (PERF.md, PR 36). The tiles pad 3,000 to 3,072 in memory
    either way."""
    return -(-d // BIN_LANES) * BIN_LANES


def _bin_impl(X: jax.Array, edges: jax.Array) -> jax.Array:
    """searchsorted-left of each column in its own edges, as a count: the bin
    of x is how many edges lie below it. One fused compare-and-sum over the
    edges (127 compares a cell on the vector unit) where the binary search is
    seven dependent gathers a cell: 24 tiles of 16,666 x 3,000 took 72 s as
    the search on a v5e (PERF.md, PR 36)."""
    below = edges[None, :, :] < X[:, :, None]  # [n, d, bins - 1], never materialized: the sum fuses it
    return jnp.sum(below, axis=2, dtype=jnp.int32).astype(_bin_dtype(edges))


@jax.jit
def _bin_all(X, edges):
    return jnp.pad(_bin_impl(X, edges), ((0, 0), (0, binned_cols(X.shape[1]) - X.shape[1])))


@partial(jax.jit, static_argnames=("size",), donate_argnums=(2,))
def _bin_tile(X, edges, out, start, *, size):
    xb = jax.lax.dynamic_slice(X, (start, 0), (size, X.shape[1]))
    return jax.lax.dynamic_update_slice(out, _bin_impl(xb, edges), (start, 0))


BIN_TILE_CELLS = 50_000_000  # cells a binning tile: priced as five 4-byte temporaries of its shape, 1 GB


def bin_features(X: jax.Array, edges: jax.Array, batch_rows: int = 0) -> jax.Array:
    """X [n, d] -> bin ids [n, `binned_cols(d)`] via per-feature searchsorted
    (the columns past d are 0).

    Stored uint8 when max_bins <= 256 (the protocol's 128-bin config halves the
    persistent binned-matrix footprint vs int32 — 3 GiB instead of 12 GiB at
    1M x 3k); consumers upcast at the arithmetic sites.

    Large single-device inputs are binned in row tiles (host loop of
    dynamic_slice programs into one donated output buffer), which bounds the
    program's temporaries to a tile's (~1 GB at the default). Sharded inputs
    keep the one-program path (per-shard size is what matters there)."""
    n, d = X.shape
    if not batch_rows:
        batch_rows = max(1024, int(BIN_TILE_CELLS // max(d, 1)))
    one_dev = not hasattr(X, "devices") or len(X.devices()) == 1
    if not one_dev or n <= 2 * batch_rows:
        return _bin_all(X, edges)
    out = jnp.zeros((n, binned_cols(d)), _bin_dtype(edges))
    n_full = (n // batch_rows) * batch_rows
    for start in range(0, n_full, batch_rows):
        out = _bin_tile(X, edges, out, np.int32(start), size=batch_rows)
    if n - n_full:
        out = _bin_tile(X, edges, out, np.int32(n_full), size=n - n_full)
    return out


# ---------------------------------------------------------------------------
# Impurity / split evaluation
# ---------------------------------------------------------------------------


def _split_gains(hist: jax.Array, impurity: str, min_instances: float):
    """hist: [S, C, d, B] per-node histograms (STAT-MAJOR layout: the bin axis
    B sits in the 128-lane tile dimension — a stat-minor [C, d, B, S] layout
    pads S=2 up to 128 lanes, 64x the memory: 14 GB for the protocol's deepest
    level where this layout takes 226 MB). Returns (gain [C, d, B], total [C, S]) where
    gain[c, f, b] is the impurity decrease of splitting node c on feature f at
    bin <= b."""
    left = jnp.cumsum(hist, axis=3)  # [S, C, d, B]
    total_s = left[:, :, 0, -1]  # [S, C] (any feature's full sum)
    right = total_s[:, :, None, None] - left

    if impurity in ("gini", "entropy"):
        def node_impurity(stats):  # stats [S, ...] class counts
            cnt = jnp.sum(stats, axis=0)
            p = stats / jnp.maximum(cnt, 1e-30)[None]
            if impurity == "gini":
                return 1.0 - jnp.sum(p * p, axis=0), cnt
            return -jnp.sum(jnp.where(p > 0, p * jnp.log2(p), 0.0), axis=0), cnt

        imp_l, cnt_l = node_impurity(left)
        imp_r, cnt_r = node_impurity(right)
        imp_p, cnt_p = node_impurity(total_s)  # [C], [C]
        cnt_p_b = cnt_p[:, None, None]
        weighted_child = (cnt_l * imp_l + cnt_r * imp_r) / jnp.maximum(cnt_p_b, 1e-30)
        gain = imp_p[:, None, None] - weighted_child
    else:  # variance (regression): S = (w, wy, wyy)
        w_l, wy_l = left[0], left[1]
        w_r, wy_r = right[0], right[1]
        w_p = jnp.maximum(total_s[0][:, None, None], 1e-30)
        # the variance a split removes, (ss_p − ss_l − ss_r) / w_p with ss = Σwy² − (Σwy)²/Σw, as the
        # between-sides sum of squares w_l w_r / w_p · (μ_l − μ_r)²: the same number without the float32
        # difference of two Σwy² of the node's size (a mean far from 0 cancelled most of its digits)
        mu_gap = wy_l / jnp.maximum(w_l, 1e-30) - wy_r / jnp.maximum(w_r, 1e-30)
        gain = (w_l * w_r / w_p) * mu_gap * mu_gap / w_p
        cnt_l, cnt_r = w_l, w_r

    valid = (cnt_l >= min_instances) & (cnt_r >= min_instances)
    # the last bin means "everything left" — never a real split
    valid = valid & (jnp.arange(hist.shape[3])[None, None, :] < hist.shape[3] - 1)
    return jnp.where(valid, gain, -jnp.inf), total_s.T


_SORT_MIN_ROWS = 1024


def _feature_subset_ids(key, n_nodes: int, d: int, m: int):
    """Exact-m random feature subset per node: int32 ids [n_nodes, m].

    The subset is applied WHERE THE WORK IS: histogram accumulation only
    touches the m chosen features per node (seg space chunk·m·B), so
    featureSubsetStrategy="auto" (√d for classification, d/3 for regression —
    Spark semantics) cuts the dominant accumulate work by d/m (~54× at the
    protocol's 3000-feature classification config), instead of masking gains
    after a full-d histogram pass."""
    if m >= d:
        return jnp.broadcast_to(jnp.arange(d, dtype=jnp.int32), (n_nodes, d))
    u = jax.random.uniform(key, (n_nodes, d), dtype=jnp.float32)
    # rows sort independently; a sort of fewer than ~512 rows of 3,000 compiles
    # for a v5e in 16-19 s against 3 s for 1,024 (PERF.md, PR 36), so a small
    # level's draw is sorted among rows of zeros
    u = jnp.pad(u, ((0, max(0, _SORT_MIN_ROWS - n_nodes)), (0, 0)))
    return jnp.argsort(u, axis=1, stable=True)[:n_nodes, :m].astype(jnp.int32)


def tree_key(seed, tree):
    """The key of tree `tree` of the forest: `fold_in(PRNGKey(seed), tree)`
    (`seed` the estimator's, as uint32; traced or not)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed), tree)


def bootstrap_draws(key, valid: jax.Array, n_draws: int) -> jax.Array:
    """`n_draws` draws with replacement, uniform over the valid (non-padding,
    unmasked) rows: int32 [n_draws], a draw r naming the r-th valid row.
    Integer arithmetic only, so every backend draws the same rows. Its own
    program: compiled for a v5e ahead of time, these draws and
    `bootstrap_counts`' scatter take 2 and 9 s apart and 41 s as one."""
    k1, _ = jax.random.split(key)
    return jax.random.randint(k1, (n_draws,), 0, jnp.maximum(jnp.sum(valid, dtype=jnp.int32), 1), dtype=jnp.int32)


def bootstrap_counts(draws: jax.Array, valid: jax.Array) -> jax.Array:
    """How often each row was drawn (`bootstrap_draws`): int32 [n]. The draws
    are counted by r, and a valid row reads the count at its own rank among
    the valid rows."""
    below = jnp.cumsum(valid.astype(jnp.int32))  # a valid row's rank among them, plus one
    per_rank = jnp.zeros(valid.shape, jnp.int32).at[draws].add(1)  # histogram-ok: counts the draws a rank got (n scalars), not a (node, feature, bin) histogram
    return jnp.where(valid, per_rank[jnp.maximum(below - 1, 0)], 0)


# ---------------------------------------------------------------------------
# The level plan and the histogram accumulate
# ---------------------------------------------------------------------------

# Largest histogram a pass builds, in (node, feature, bin) cells a statistic:
# 2^27 float32 cells are 512 MiB a statistic beside the prefix sums and gains
# of the same size. The protocol's deepest level (4,096 nodes x 54 features x
# 128 bins = 28.3 M cells, 226 MB for two classes) is one pass; a level over
# the budget goes in node chunks, each a pass over the rows.
SEGMENT_BUDGET = 1 << 27
# The one-hot contraction costs 2 · rows · (nodes · S) · (m · bins) FLOP a
# level whatever the rows hold; the scatter costs rows · m · S updates. On a
# v5e at 393,216 rows x 54 features x 2 classes the contraction takes 6 ms up
# to 64 nodes and 283 ms at 4,096 (80 % of the MXU's peak), the scatter 345 to
# 474 ms whatever the nodes (PERF.md, PR 36): up to this many histogram rows
# (nodes · S) the contraction is the faster, and the scatter compiles in 44 to
# 97 s a level where the contraction takes 2 to 10.
ONEHOT_MAX_ROWS = 8192
HIST_TILE_ROWS = 8192  # rows a tile of the accumulate: its [rows, m · bins] one-hot operand and its selection are a tile's
# A row's m bin ids lie at ITS node's features: a per-element gather, 12.7 ns
# an element on a v5e (269 ms a pass at 21.2 M elements, whatever the nodes).
# Up to this many nodes a pass the gather goes as a contraction instead: the
# tile times the nodes' [d, nodes · m] selection matrix (bin ids under 256 are
# exact in bfloat16), then each row's own node block: 3.6 ms at one node, 60
# at 64, 2 · rows · d · nodes · m FLOP (PERF.md, PR 36).
MATMUL_GATHER_MAX_NODES = 128
# Beyond this many nodes a pass the one-hot form visits the rows SORTED BY
# NODE (`_sorted_histogram`): a tile of `SORTED_TILE_ROWS` sorted rows holds
# the rows of a few consecutive nodes, so both contractions go over windows of
# this many nodes (a window's selection matrix is [columns, WINDOW_NODES · m])
# and their cost follows the rows, not rows x nodes: at most tiles + nodes /
# WINDOW_NODES windows a pass. Up to it the tile meets every node anyway and
# the rows stay where they lie. On a v5e at 393,216 rows of which a bootstrap
# drew 63 %: the ordering 2 ms, a pass 26 ms at 32 and at 256 nodes and 43 ms
# at 4,096, of which the fetch of whole uint8 rows is 17 ms (43 ns a row),
# where the rows in place took 43 to 626 ms a level from 32 nodes on; tiles of
# 512 or 2,048 rows and windows of 8 or 32 nodes read within a fifth of it
# (PERF.md, PR 36).
WINDOW_NODES = 16
# A window's selection matrix has at most this many columns where a node has more features than
# `WINDOW_NODES` nodes' worth of them fill (one node a window at the regressor's 1,000)
WINDOW_COLUMNS = 1024
SORTED_TILE_ROWS = 1024
HIST_SCOPE = "srml_hist_accumulate"  # the accumulate's ops carry this scope in a trace's metadata
# A float32 statistic is the exact sum of this many bfloat16 pieces (`stat_pieces`): 8 + 8 + 8
# significant bits hold its 24
STAT_PIECES = 3
# The row advance (`advance_rows`) at 393,216 rows x 3,072 uint8 columns on a v5e: the three per-row gathers
# 10.5-10.7 ms a level (the node's feature 3.4, its split bin 3.4, the row's bin id 4.9-5.1); the masked
# reduces 2.0 ms at 1 and 32 nodes, 2.6 at 1,024, 4.5 at 4,096 (PERF.md section 6). The reduce over the nodes
# grows with them: beyond this many the node tables are gathered (8.3 ms a level at 8,192 nodes)
MASKED_ADVANCE_NODES = 4096


def level_plan(
    max_depth: int, max_features: int, max_bins: int, n_stats: int,
    node_chunk: int = 0, integer_stats: bool = False, split_stats: bool = True,
) -> List[Dict[str, Any]]:
    """What each level of a tree runs: its nodes, the node chunk of a pass,
    the passes over the rows, the accumulate's form and the bfloat16 pieces
    a statistic goes in as. `node_chunk` > 0 is the caller's cap on a pass's
    nodes; 0 takes as many as `SEGMENT_BUDGET` holds. The one-hot form takes
    statistics that bfloat16 holds exactly (`integer_stats`: class counts
    times bootstrap counts, no row weights) as they are; float32 statistics
    (`split_stats`: a regressor's (w, wy, wy²), rows with weights) as
    `STAT_PIECES` pieces each, `onehot_split`, over the rows sorted by node
    at every level (a node's features need not fit a window of several
    nodes). The scatter is left for what neither holds: float64 statistics,
    over 256 bins, a level in node chunks of float statistics."""
    cap = max(1, SEGMENT_BUDGET // max(max_features * max_bins, 1))
    if node_chunk > 0:
        cap = min(cap, int(node_chunk))
    plan = []
    for depth in range(max_depth):
        nodes = 1 << depth
        chunk = min(nodes, cap)
        # bin ids of a uint8 X are exact in bfloat16: the sorted form picks them by contraction alone
        # (a level of one pass: a pass of a node chunk visits the rows where they lie)
        whole = max_bins <= 256 and chunk == nodes
        split = not integer_stats and split_stats and whole
        by_node = split or (integer_stats and whole and chunk > WINDOW_NODES)
        onehot = integer_stats and (by_node or chunk * n_stats <= ONEHOT_MAX_ROWS)
        plan.append({
            "depth": depth, "nodes": nodes, "chunk": chunk, "passes": -(-nodes // chunk),
            "accumulate": "onehot_split" if split else "onehot" if onehot else "scatter",
            "rows": "sorted" if by_node else "in_place", "stat_pieces": STAT_PIECES if split else 1,
            # the row advance (`advance_rows`) reads a uint8 X whole; an int32 one (over 256 bins) is four times the bytes
            "advance": "masked" if max_bins <= 256 else "gather",
        })
    return plan


def plan_summary(plan: List[Dict[str, Any]]) -> Dict[str, Any]:
    forms = {lv["accumulate"] for lv in plan}
    return {
        "masked_advances": sum(lv["advance"] == "masked" for lv in plan),
        "advance": plan[0]["advance"] if plan else "gather",
        "passes_per_tree": sum(lv["passes"] for lv in plan),
        "sorted_levels": sum(lv["rows"] == "sorted" for lv in plan),
        "kernel_levels": sum(bool(lv.get("kernel")) for lv in plan),
        "accumulate": forms.pop() if len(forms) == 1 else "mixed",
        "deepest_chunk": max((lv["chunk"] for lv in plan), default=1),
        "stat_pieces": max((lv["stat_pieces"] for lv in plan), default=1),
        "split_passes": sum(lv["passes"] for lv in plan if lv["accumulate"] == "onehot_split"),
        "scatter_passes": sum(lv["passes"] for lv in plan if lv["accumulate"] == "scatter"),
    }


def stat_pieces(stats: jax.Array) -> jax.Array:
    """[S, n] float32 -> [STAT_PIECES · S, n] float32 (piece-major: all
    statistics' first pieces, then the second, then the third), each value
    exact in bfloat16 and the pieces of a statistic summing to it exactly:
    the first keeps the top 8 significant bits (the low 16 bits of the word
    cleared), the second the top 8 of what is left, the third the rest (at
    most 8 bits). Cleared bits, not a rounding convert, so that no compiler
    may keep a piece in float32 (XLA's excess precision)."""
    def top8(x):
        return jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000), jnp.float32)

    x = stats.astype(jnp.float32)
    hi = top8(x)
    mid = top8(x - hi)
    return jnp.concatenate([hi, mid, x - hi - mid])


def joined_pieces(hist: jax.Array) -> jax.Array:
    """`stat_pieces`' inverse on sums: [STAT_PIECES · S, ...] -> [S, ...]."""
    hi, mid, lo = jnp.split(hist, STAT_PIECES)
    return (hi + mid) + lo


def _level_histogram(
    Xb: jax.Array,  # [n, d] bin ids
    stats: jax.Array,  # [S, n] per-row statistics (weights and bootstrap counts applied)
    node_id: jax.Array,  # [n]
    active: jax.Array,  # [n]
    fids: jax.Array,  # [chunk, m] the chunk's feature subsets
    c0,  # level-order id of the chunk's first node
    *,
    bins: int,
    form: str,
    ordered: Tuple[jax.Array, ...] = (),  # `order_rows`' results for a pass over the sorted rows
    kernel: str = "",  # the level's `kernel` of `_forest_programs`' plan: the sorted pass as `ops.histogram`'s kernel
) -> jax.Array:
    """One pass over the rows: hist[s, c, j, b] = the sum of stats[s] over the
    active rows at node c0 + c whose feature fids[c, j] lies in bin b.
    [S, chunk, m, bins] in the statistics' dtype. With `ordered` it is
    `_sorted_histogram`'s pass, or, where the plan says `kernel`, the Mosaic
    kernel's (`ops.histogram.sorted_histogram`: the same integers); the form
    `onehot_split` is `_split_sorted_histogram`'s. Else rows
    go a tile at a time where they lie;
    each row's m bin ids are picked at ITS node's subset (a contraction with
    the nodes' selection matrix up to `MATMUL_GATHER_MAX_NODES` nodes a pass,
    a per-element gather beyond). `onehot`: the tile's
    (statistic, node) one-hot times its (feature, bin) one-hot on the MXU,
    0/1 and small-integer operands in bfloat16 with float32 sums (exact under
    2^24). `scatter`: one 1-D `segment_sum` a statistic (a [rows, S] operand
    would pad S to the 128-lane tile)."""
    from ..parallel.mesh import ROWS_AXIS

    if ordered:
        if form == "onehot_split":
            return _split_sorted_histogram(Xb, *ordered, fids, bins=bins, kernel=kernel)
        if kernel:
            return histogram.sorted_histogram(Xb, *ordered, fids, bins=bins, interpret=kernel == "interpret")
        return _sorted_histogram(Xb, *ordered, fids, bins=bins)
    n, d = Xb.shape
    S = stats.shape[0]
    chunk, m = fids.shape
    n_seg = chunk * m * bins
    tile_rows = min(n, HIST_TILE_ROWS)
    n_tiles = -(-n // tile_rows)

    by_matmul = chunk <= MATMUL_GATHER_MAX_NODES and Xb.dtype == jnp.uint8
    if by_matmul:
        select = jax.nn.one_hot(fids.reshape(-1), d, dtype=jnp.bfloat16).T  # [d, chunk · m]

    def tile_body(ti, hist):
        # clamp the last tile back and mask rows already covered
        r0 = jnp.minimum(ti * tile_rows, n - tile_rows)
        fresh = (r0 + jnp.arange(tile_rows)) >= ti * tile_rows
        xb_t = jax.lax.dynamic_slice(Xb, (r0, 0), (tile_rows, d))
        nid_t = jax.lax.dynamic_slice(node_id, (r0,), (tile_rows,))
        act_t = jax.lax.dynamic_slice(active, (r0,), (tile_rows,))
        st_t = jax.lax.dynamic_slice(stats, (0, r0), (S, tile_rows))
        local = nid_t - c0
        ok = act_t & (local >= 0) & (local < chunk) & fresh
        local = jnp.clip(local, 0, chunk - 1)
        st_t = jnp.where(ok[None, :], st_t, 0.0)  # rows of other nodes add nothing
        # each row's bins at ITS node's feature subset: [rows, m]
        if by_matmul:
            picked = jnp.dot(xb_t.astype(jnp.bfloat16), select, preferred_element_type=jnp.float32)
            own = jax.nn.one_hot(local, chunk, dtype=jnp.float32)[:, :, None]
            xb_sub = jnp.sum(picked.reshape(tile_rows, chunk, m) * own, axis=1).astype(jnp.int32)
        else:
            xb_sub = jnp.take_along_axis(xb_t, fids[local], axis=1)
        if form == "onehot":
            node_hot = jax.nn.one_hot(local, chunk, dtype=jnp.bfloat16)  # [rows, chunk]
            lhs = (st_t.astype(jnp.bfloat16)[:, :, None] * node_hot[None]).transpose(1, 0, 2)
            rhs = jax.nn.one_hot(xb_sub, bins, dtype=jnp.bfloat16).reshape(tile_rows, m * bins)
            part = jax.lax.dot_general(
                lhs.reshape(tile_rows, S * chunk), rhs, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [S · chunk, m · bins]
            return hist + part.reshape(S, n_seg).astype(hist.dtype)
        # flat segment id: (node_local * m + j) * B + bin
        seg = ((local[:, None] * m + jnp.arange(m)[None, :]) * bins + xb_sub.astype(jnp.int32)).reshape(-1)
        return hist + jnp.stack([
            jax.ops.segment_sum(
                jnp.broadcast_to(st_t[s_i][:, None], (tile_rows, m)).reshape(-1), seg, num_segments=n_seg,
            )
            for s_i in range(S)
        ])

    with jax.named_scope(HIST_SCOPE):
        # the carry accumulates per-shard values: type it as varying over
        # the mesh axis (shard_map vma typing, like the KMeans carry)
        hist0 = jax.lax.pcast(jnp.zeros((S, n_seg), stats.dtype), ROWS_AXIS, to="varying")
        hist = tile_body(0, hist0) if n_tiles == 1 else jax.lax.fori_loop(0, n_tiles, tile_body, hist0)
    return hist.reshape(S, chunk, m, bins)


def order_rows(stats: jax.Array, node_id: jax.Array, active: jax.Array, c0, nodes):
    """The rows in the order `_sorted_histogram` visits them: those that
    count at a level (active, at one of its `nodes` nodes from level-order id
    `c0` on, drawn by the bootstrap: some statistic not 0) sorted by their
    node, the others last. Returns (each row's node of the level, or `nodes`
    for a row that does not count, sorted [n]; the rows' ids in that order
    [n]; their statistics in that order [S, n]; how many count). `c0` and
    `nodes` are traced, so one program a shape serves every level: a TPU sort
    of 393,216 keys with three operands carried along compiles in 29 s on the chip (PERF.md, PR 36)."""
    local = node_id - c0
    counts = active & (local >= 0) & (local < nodes) & jnp.any(stats != 0, axis=0)
    key = jnp.where(counts, local, nodes).astype(jnp.int32)
    # one sort carries the row ids and the statistics along: no per-element gather
    key_s, order, *st_s = jax.lax.sort((key, jnp.arange(key.shape[0], dtype=jnp.int32), *stats), num_keys=1)
    return key_s, order, jnp.stack(st_s), jnp.sum(counts, dtype=jnp.int32)


def _sorted_histogram(Xb, key_s, order, st_s, n_counted, fids, *, bins: int) -> jax.Array:
    """`_level_histogram`'s one-hot pass over the rows SORTED BY NODE
    (`order_rows`), for a pass of many nodes. `_picked_sorted` gives each
    sorted row's m bin ids at ITS node's features (a tile of
    `SORTED_TILE_ROWS` sorted rows fetched once, one small selection
    contraction a window of its nodes); then, a tile at a time, for each
    window of `window_nodes` nodes among the tile's, the tile's (statistic,
    node of the window) one-hot times its (feature, bin) one-hot adds the
    window's rows of the histogram. At most tiles + nodes / `WINDOW_NODES`
    windows a pass, each the same small contraction, where the rows in place
    cost rows x nodes: the per-element gather of 21.2 M bin ids (269 ms a pass
    on a v5e) and the [rows, nodes · S] one-hot operand (283 ms at 4,096
    nodes) are both gone (PERF.md, PR 36). Sums of small integers in float32:
    the same histogram, bit for bit, whatever the order."""
    from ..parallel.mesh import ROWS_AXIS

    n = Xb.shape[0]
    S = st_s.shape[0]
    chunk, m = fids.shape
    K = window_nodes(m)
    T = min(n, SORTED_TILE_ROWS)
    chunk_pad = -(-chunk // K) * K  # windows start at multiples of K: the last one may pass the chunk's end
    zero = jnp.int32(0)
    picked = _picked_sorted(Xb, key_s, order, n_counted, fids, m)  # [m, n]

    with jax.named_scope(HIST_SCOPE):
        n_tiles = (n_counted + T - 1) // T  # the rows that count come first

        def tile_body(ti, hist):
            # clamp the last tile back and mask rows already covered
            r0 = jnp.minimum(ti * T, n - T)
            fresh = (r0 + jnp.arange(T)) >= ti * T
            k_t = jax.lax.dynamic_slice(key_s, (r0,), (T,))
            ok = (k_t < chunk) & fresh
            st_t = jnp.where(ok[None, :], jax.lax.dynamic_slice(st_s, (zero, r0), (S, T)), 0).astype(jnp.bfloat16)
            xb_sub = jax.lax.dynamic_slice(picked, (zero, r0), (m, T)).astype(jnp.int32).T  # [T, m]
            rhs = jax.nn.one_hot(xb_sub, bins, dtype=jnp.bfloat16).reshape(T, m * bins)
            lo = jnp.min(jnp.where(ok, k_t, chunk))
            hi = jnp.max(jnp.where(ok, k_t, -1))

            def window_body(carry):
                w0, hist = carry
                own = ((k_t - w0)[:, None] == jnp.arange(K)[None, :]) & ok[:, None]  # [T, K]: the row's node in the window
                lhs = (st_t[:, :, None] * own.astype(jnp.bfloat16)[None]).transpose(1, 0, 2).reshape(T, S * K)
                part = jax.lax.dot_general(lhs, rhs, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
                at = (zero, w0, zero)
                seen = jax.lax.dynamic_slice(hist, at, (S, K, m * bins))
                return w0 + K, jax.lax.dynamic_update_slice(hist, seen + part.reshape(S, K, m * bins).astype(hist.dtype), at)

            return jax.lax.while_loop(lambda c: c[0] <= hi, window_body, ((lo // K) * K, hist))[1]

        hist0 = jax.lax.pcast(jnp.zeros((S, chunk_pad, m * bins), st_s.dtype), ROWS_AXIS, to="varying")
        hist = jax.lax.fori_loop(0, n_tiles, tile_body, hist0)
    return hist[:, :chunk].reshape(S, chunk, m, bins)


def window_nodes(m: int) -> int:
    """Nodes a window of the sorted form: `WINDOW_NODES`, fewer where their
    features would pass `WINDOW_COLUMNS` (at least one)."""
    return max(1, min(WINDOW_NODES, WINDOW_COLUMNS // max(m, 1)))


def _split_sorted_histogram(Xb, key_s, order, st_s, n_counted, fids, *, bins: int, kernel: str = "") -> jax.Array:
    """The sorted pass of float32 statistics, `onehot_split`: each statistic
    goes in as its `stat_pieces`, 0/1 and bfloat16 operands whose products
    are exact, float32 sums; the pieces' sums are joined after. [S, chunk, m,
    bins] float32: a float32 sum of each statistic's exact values, in the
    order the contraction takes them. With `kernel`, the rows' bin ids at
    their node's features are picked by `_picked_sorted` and accumulated by
    `ops.histogram.split_histogram`; else by `_sorted_histogram`."""
    pieces = stat_pieces(st_s)
    if kernel:
        chunk, m = fids.shape
        picked = _picked_sorted(Xb, key_s, order, n_counted, fids, histogram.split_features(m))
        hist = histogram.split_histogram(picked, key_s, pieces, chunk, m, bins=bins, interpret=kernel == "interpret")
    else:
        hist = _sorted_histogram(Xb, key_s, order, pieces, n_counted, fids, bins=bins)
    return joined_pieces(hist)


def _picked_sorted(Xb, key_s, order, n_counted, fids, m_pad: int) -> jax.Array:
    """Each sorted row's m bin ids at ITS node's features, feature-major:
    [m_pad, n] bfloat16 (ids under 256 are exact; the features past m and
    the rows that do not count are 0): the picking of XLA's sorted form
    (`_sorted_histogram`) and of the float statistics' kernel
    (`ops.histogram.split_histogram`). A tile of `SORTED_TILE_ROWS` sorted
    rows is fetched whole once, and each window of its nodes (`window_nodes`,
    one at 1,000 features) contracts the window's [window · m_pad, columns]
    selection matrix with it, so that the cost follows the rows. What is
    left, the (feature, bin) one-hot of 128,000 columns a row, is the
    accumulate's: in the kernel it never leaves VMEM."""
    from ..parallel.mesh import ROWS_AXIS

    n, cols = Xb.shape
    chunk, m = fids.shape
    K = window_nodes(m_pad)
    T = min(n, SORTED_TILE_ROWS)
    chunk_pad = -(-chunk // K) * K
    fids_pad = jnp.pad(fids, ((0, chunk_pad - chunk), (0, m_pad - m)), constant_values=-1)
    columns = jnp.arange(cols, dtype=jnp.int32)
    zero = jnp.int32(0)

    with jax.named_scope(HIST_SCOPE):
        n_tiles = (n_counted + T - 1) // T  # the rows that count come first

        def tile_body(ti, picked):
            r0 = jnp.minimum(ti * T, n - T)  # the last tile clamped back: its first rows are picked again, alike
            k_t = jax.lax.dynamic_slice(key_s, (r0,), (T,))
            ok = k_t < chunk
            xb_t = Xb[jax.lax.dynamic_slice(order, (r0,), (T,))].astype(jnp.bfloat16)  # [T, columns]: whole rows
            lo = jnp.min(jnp.where(ok, k_t, chunk))
            hi = jnp.max(jnp.where(ok, k_t, -1))

            def window_body(carry):
                w0, ids = carry
                f_w = jax.lax.dynamic_slice(fids_pad, (w0, zero), (K, m_pad)).reshape(-1)
                select = (f_w[:, None] == columns[None, :]).astype(jnp.bfloat16)  # [K · m_pad, columns]
                got = jax.lax.dot_general(select, xb_t, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
                own = ((k_t - w0)[None, :] == jnp.arange(K)[:, None]) & ok[None, :]  # [K, T]
                return w0 + K, ids + jnp.sum(got.reshape(K, m_pad, T) * own[:, None, :], axis=0)

            ids0 = jax.lax.pcast(jnp.zeros((m_pad, T), jnp.float32), ROWS_AXIS, to="varying")
            ids = jax.lax.while_loop(lambda c: c[0] <= hi, window_body, ((lo // K) * K, ids0))[1]
            return jax.lax.dynamic_update_slice(picked, ids.astype(jnp.bfloat16), (zero, r0))

        picked0 = jax.lax.pcast(jnp.zeros((m_pad, n), jnp.bfloat16), ROWS_AXIS, to="varying")
        return jax.lax.fori_loop(0, n_tiles, tile_body, picked0)


# ---------------------------------------------------------------------------
# Single-tree growth (level-wise, full binary layout)
# ---------------------------------------------------------------------------


def _tree_level(
    key,
    Xb: jax.Array,  # [n, d] bin ids (uint8 at <=256 bins; upcast at arithmetic sites)
    stats: jax.Array,  # [S, n] per-row stat contributions (already w-weighted)
    node_id: jax.Array,  # [n] current node per row (level-order id)
    active: jax.Array,  # [n] row not yet in a leaf
    feature: jax.Array,  # [M] chosen feature per node (−1 = leaf)
    split_bin: jax.Array,  # [M]
    node_stats: jax.Array,  # [M, S]
    params: Dict,
    level: Dict[str, Any],  # this level's entry of `level_plan`
    ordered: Tuple[jax.Array, ...] = (),  # `order_rows`' results where the level's rows are "sorted"
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array, jax.Array]:
    """Grow ONE level of one tree: histograms (a pass a node chunk) + split
    selection + row advance. Returns (node_id, active, feature, split_bin,
    node_stats)."""
    d = params["n_features"]  # the features, not the binned X's padded columns
    B = params["max_bins"]
    m = min(params["max_features"], d)
    level_size, chunk, n_chunks = level["nodes"], level["chunk"], level["passes"]
    offset = level_size - 1
    fids_level = _feature_subset_ids(key, level_size, d, m)  # [level, m]

    def chunk_body(ci, carry):
        feature, split_bin, node_stats = carry
        # the last chunk of a level that `chunk` does not divide is clamped back: its first nodes are selected twice, alike
        lo = jnp.minimum(ci * chunk, level_size - chunk)
        c0 = offset + lo
        fids = jax.lax.dynamic_slice_in_dim(fids_level, lo, chunk, 0)  # [chunk, m]
        hist = _level_histogram(
            Xb, stats, node_id, active, fids, c0, bins=B, form=level["accumulate"], ordered=ordered,
            kernel=level.get("kernel", ""),
        )
        gain, total = _split_gains(hist, params["impurity"], params["min_instances"])
        flat_best = jnp.argmax(gain.reshape(chunk, -1), axis=1)
        best_gain = jnp.take_along_axis(gain.reshape(chunk, -1), flat_best[:, None], 1)[:, 0]
        best_j = (flat_best // B).astype(jnp.int32)
        best_f = jnp.take_along_axis(fids, best_j[:, None], axis=1)[:, 0].astype(jnp.int32)
        best_b = (flat_best % B).astype(jnp.int32)

        is_split = best_gain > params["min_info_gain"]
        feature = jax.lax.dynamic_update_slice_in_dim(feature, jnp.where(is_split, best_f, -1), c0, 0)
        split_bin = jax.lax.dynamic_update_slice_in_dim(split_bin, jnp.where(is_split, best_b, 0), c0, 0)
        node_stats = jax.lax.dynamic_update_slice(node_stats, total, (c0, 0))
        return feature, split_bin, node_stats

    # several chunks iterate in a fori_loop: one rolled body a level keeps the
    # program linear in depth
    if n_chunks == 1:
        feature, split_bin, node_stats = chunk_body(0, (feature, split_bin, node_stats))
    else:
        feature, split_bin, node_stats = jax.lax.fori_loop(
            0, n_chunks, chunk_body, (feature, split_bin, node_stats)
        )

    # advance rows: split nodes send rows to children; leaf rows deactivate
    node_id, went_split = advance_rows(Xb, node_id, active, feature, split_bin, offset, level_size,
                                       masked=level["advance"] == "masked")
    return node_id, went_split, feature, split_bin, node_stats


def advance_rows(Xb, node_id, active, feature, split_bin, offset: int, nodes: int, *, masked: bool):
    """The row advance after a level of `nodes` nodes from level-order id
    `offset` on: each row at a split node moves to the child its bin id at
    the node's feature picks (left where it is at most the split bin); a row
    at a leaf deactivates. (node_id, went_split). `masked` (uint8 bin ids,
    `level_plan`'s `advance`) reads the row's bin id by a masked reduce over
    the columns, one pass over X where it lies, and the node's feature and
    split bin by one over the level's nodes up to `MASKED_ADVANCE_NODES`;
    else per-row gathers, 12.7 ns an element on a v5e (PERF.md section 6). Every
    row of a grown tree is at one of the level's nodes or inactive: there
    the two agree bit for bit."""
    if masked and nodes <= MASKED_ADVANCE_NODES:
        own = (node_id - offset)[:, None] == jnp.arange(nodes, dtype=jnp.int32)[None, :]  # [n, nodes]: none outside the level

        def of_node(table, none):
            return jnp.max(jnp.where(own, jax.lax.dynamic_slice_in_dim(table, offset, nodes)[None, :], none), axis=1)

        node_f, node_bin = of_node(feature, -1), of_node(split_bin, 0)
    else:
        node_f, node_bin = feature[node_id], split_bin[node_id]
    went_split = active & (node_f >= 0)
    if masked:
        columns = jnp.arange(Xb.shape[1], dtype=jnp.int32)
        row_bin = jnp.max(jnp.where(columns[None, :] == node_f[:, None], Xb, jnp.zeros((), Xb.dtype)), axis=1)
    else:
        row_bin = jnp.take_along_axis(Xb, jnp.maximum(node_f, 0)[:, None], axis=1)[:, 0]
    go_left = row_bin.astype(jnp.int32) <= node_bin
    return jnp.where(went_split, 2 * node_id + jnp.where(go_left, 1, 2), node_id), went_split


def _tree_final_level(stats, node_id, active, node_stats, max_depth: int):
    """Record stats for rows that reached the last level (remaining leaves)."""
    S = stats.shape[0]
    level_size = 2**max_depth
    offset = level_size - 1
    local = node_id - offset
    in_level = active & (local >= 0)
    seg = jnp.where(in_level, local, level_size)
    last_stats = jnp.stack(
        [
            jax.ops.segment_sum(stats[s_i], seg, num_segments=level_size + 1)[:-1]
            for s_i in range(S)
        ],
        axis=1,
    )
    return jax.lax.dynamic_update_slice(node_stats, last_stats, (offset, 0))


# ---------------------------------------------------------------------------
# Forest over the mesh
# ---------------------------------------------------------------------------


@lru_cache(maxsize=16)
def _forest_programs(
    mesh, n_rows: int, n_features: int, n_stats: int, dtype: str, trees_per_dev: int, max_depth: int, max_bins: int,
    max_features: int, impurity: str, node_chunk: int, integer_stats: bool, bootstrap: bool,
    subsample_rate: float, min_instances: float, min_info_gain: float, kernel_mode: str = "jnp",
):
    """The jitted programs of a forest fit on one mesh at one shape, built
    once and kept: the estimator seed and the round are traced arguments, so
    a refit with another seed compiles nothing. `kernel_mode` is
    `distance.kernel_mode()`'s answer, resolved by `forest_fit` outside any
    trace and part of the key: where it is not `jnp`, a sorted level whose
    shard `ops.histogram` takes runs the accumulate as its kernel (the plan's
    entry says `kernel`)."""
    from jax import shard_map
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from ..parallel.mesh import ROWS_AXIS

    n_dev = mesh.devices.size
    S = n_stats
    M = 2 ** (max_depth + 1) - 1
    # float32 statistics that are not small integers go in as bfloat16 pieces; float64 ones keep the scatter
    plan = level_plan(max_depth, max_features, max_bins, S, node_chunk, integer_stats, split_stats=dtype == "float32")
    m = min(max_features, n_features)
    if kernel_mode != "jnp":
        takes = {
            "onehot": histogram.takes(n_rows // n_dev, binned_cols(n_features), S, m, max_bins),
            "onehot_split": histogram.takes_split(STAT_PIECES * S, max_bins),
        }
        plan = [dict(lv, kernel=kernel_mode) if lv["rows"] == "sorted" and takes[lv["accumulate"]] else lv for lv in plan]
    params = {
        "n_features": n_features, "max_depth": max_depth, "max_bins": max_bins, "max_features": max_features,
        "impurity": impurity, "min_instances": min_instances, "min_info_gain": min_info_gain,
    }
    rows_spec = P(ROWS_AXIS)
    stat_major = P(None, ROWS_AXIS)

    def this_tree_key(seed, tree_i):
        rank = jax.lax.axis_index(ROWS_AXIS)
        return tree_key(seed, rank * trees_per_dev + tree_i)

    n_draws = int(max(1, round(subsample_rate * (n_rows // n_dev))))

    def draw_fn(w_l, seed, tree_i):
        # draw UNIFORMLY over valid (non-padding) rows; the user weights
        # already scale the statistics, so weighting the draw too would apply
        # them twice (w² effective weighting)
        return bootstrap_draws(this_tree_key(seed, tree_i), w_l > 0, n_draws)

    draw_step = jax.jit(shard_map(draw_fn, mesh=mesh, in_specs=(rows_spec, P(), P()), out_specs=rows_spec))

    def boot_fn(stats_l, w_l, seed, tree_i, *draws_l):
        # per-device bootstrap weighting for THIS round's tree (`draw_step`'s
        # draws where it bootstraps); the result is stat-major [S, n_l] (the
        # row axis in the lanes)
        n_l = stats_l.shape[0]
        if bootstrap:
            wb = bootstrap_counts(draws_l[0], w_l > 0).astype(stats_l.dtype)
        elif subsample_rate < 1.0:
            # subsample without replacement (Spark bootstrap=False semantics);
            # padding rows drawn here contribute nothing (stats are w-scaled)
            k1, _ = jax.random.split(this_tree_key(seed, tree_i))
            idx = jax.random.choice(k1, n_l, (n_draws,), replace=False)
            wb = jnp.zeros((n_l,), stats_l.dtype).at[idx].set(1.0)
        else:
            wb = jnp.ones((n_l,), stats_l.dtype)
        return (stats_l * wb[:, None]).T

    boot_step = jax.jit(shard_map(
        boot_fn, mesh=mesh,
        in_specs=(P(ROWS_AXIS, None), rows_spec, P(), P(), *((rows_spec,) if bootstrap else ())),
        out_specs=stat_major,
    ))

    ordered_specs = (rows_spec, rows_spec, stat_major, rows_spec)  # `order_rows`' results, a device's own

    def order_fn(stw_l, nid_l, act_l, c0, nodes):
        key_s, order, st_s, n_counted = order_rows(stw_l, nid_l, act_l, c0, nodes)
        return key_s, order, st_s, n_counted[None]

    order_step = jax.jit(shard_map(
        order_fn, mesh=mesh, in_specs=(stat_major, rows_spec, rows_spec, P(), P()), out_specs=ordered_specs,
    ))

    def make_level_step(level):
        by_node = level["rows"] == "sorted"

        def fn(Xb_l, stw_l, nid_l, act_l, feat_b, bin_b, nst_b, seed, tree_i, *ordered):
            kf = jax.random.fold_in(this_tree_key(seed, tree_i), 7919 + level["depth"])  # per-level stream
            if by_node:
                ordered = (*ordered[:3], ordered[3][0])
            nid, act, f, b, s = _tree_level(
                kf, Xb_l, stw_l, nid_l, act_l, feat_b[0], bin_b[0], nst_b[0], params, level, ordered,
            )
            return nid, act, f[None], b[None], s[None]

        return jax.jit(shard_map(
            fn, mesh=mesh,
            in_specs=(
                P(ROWS_AXIS, None), stat_major, rows_spec, rows_spec,
                P(ROWS_AXIS, None), P(ROWS_AXIS, None), P(ROWS_AXIS, None, None),
                P(), P(), *(ordered_specs if by_node else ()),
            ),
            out_specs=(
                rows_spec, rows_spec,
                P(ROWS_AXIS, None), P(ROWS_AXIS, None), P(ROWS_AXIS, None, None),
            ),
            # the kernel's body mixes a shard's values with loop indices, program ids and iotas: typed as
            # varying over the mesh axis they would need a `pvary` each, which Mosaic lowers no more than
            # the interpreter evaluates (`distance.shard_map_check_vma`). A level has no collective to check
            check_vma=not level.get("kernel"),
        ))

    def final_fn(stw_l, nid_l, act_l, nst_b):
        return _tree_final_level(stw_l, nid_l, act_l, nst_b[0], max_depth)[None]

    final_step = jax.jit(shard_map(
        final_fn, mesh=mesh,
        in_specs=(stat_major, rows_spec, rows_spec, P(ROWS_AXIS, None, None)),
        out_specs=P(ROWS_AXIS, None, None),
    ))

    tree_init = jax.jit(
        lambda: (
            jnp.zeros((n_rows,), jnp.int32),
            jnp.ones((n_rows,), bool),
            jnp.full((n_dev, M), -1, jnp.int32),
            jnp.zeros((n_dev, M), jnp.int32),
            jnp.zeros((n_dev, M, S), jnp.dtype(dtype)),
        ),
        out_shardings=(
            NamedSharding(mesh, rows_spec),
            NamedSharding(mesh, rows_spec),
            NamedSharding(mesh, P(ROWS_AXIS, None)),
            NamedSharding(mesh, P(ROWS_AXIS, None)),
            NamedSharding(mesh, P(ROWS_AXIS, None, None)),
        ),
    )

    # every round's (small) tree arrays in one replicated stack, so that every
    # process can fetch the full forest under multi-process SPMD — the in-graph
    # form of the reference's serialized-tree allGather + concat
    # (tree.py:333-378). Tree order is ROUND-major ([round0: dev0..devN,
    # round1: ...]) — forest aggregation is order-invariant.
    rep = NamedSharding(mesh, P())
    stack = jax.jit(
        lambda fs, bs, ss: (jnp.concatenate(fs), jnp.concatenate(bs), jnp.concatenate(ss)),
        out_shardings=(rep, rep, rep),
    )
    return {
        "plan": plan, "draw": draw_step if bootstrap else None, "boot": boot_step, "order": order_step, "levels": [make_level_step(lv) for lv in plan],
        "final": final_step, "init": tree_init, "stack": stack,
    }


# NOT jitted: forest_fit is a HOST orchestrator — it dispatches one compact
# jitted program per (tree round, level), none of which waits for another on
# the host: the whole fit is queued, and the caller's one fetch ends it.
def forest_fit(
    Xb: jax.Array,  # [n_pad, binned_cols(d)] bin ids (`bin_features`; row-sharded; uint8 at <=256 bins)
    stats_row: jax.Array,  # [n_pad, S] per-row stats, zero on padding
    w: jax.Array,  # [n_pad] weights (> 0 marks the rows a bootstrap draws from)
    seed: int,
    *,
    mesh,
    n_features: int,
    n_trees: int,
    max_depth: int,
    max_bins: int,
    max_features: int,
    impurity: str,
    node_chunk: int = 0,
    bootstrap: bool = True,
    subsample_rate: float = 1.0,
    min_instances: float = 1.0,
    min_info_gain: float = 0.0,
    integer_stats: bool = False,
) -> Dict[str, Any]:
    """Ensemble-split forest fit: device i grows trees [i*t0, (i+1)*t0) on its
    row shard. Returns the stacked forest ON THE DEVICE, replicated
    (feature [T, M], split_bin [T, M], node_stats [T, M, S], T = t0 · devices
    in round-major order), not waited for, and `plan`: what `level_plan` gave
    each level. `integer_stats` promises statistics that bfloat16 holds
    exactly (class counts, no row weights), which admits the one-hot
    accumulate as they are; float32 statistics otherwise take it in pieces
    (`onehot_split`)."""
    n_dev = mesh.devices.size
    trees_per_dev = -(-n_trees // n_dev)  # reference _estimators_per_worker
    progs = _forest_programs(
        mesh, int(Xb.shape[0]), int(n_features), int(stats_row.shape[1]), jnp.dtype(stats_row.dtype).name, trees_per_dev, int(max_depth), int(max_bins),
        int(max_features), str(impurity), int(node_chunk), bool(integer_stats), bool(bootstrap),
        float(subsample_rate), float(min_instances), float(min_info_gain), distance.kernel_mode(),
    )
    seed32 = np.uint32(int(seed) & 0xFFFFFFFF)
    rounds = []
    for t_i in range(trees_per_dev):
        ti = np.int32(t_i)
        draws = (progs["draw"](w, seed32, ti),) if progs["draw"] else ()
        stw = progs["boot"](stats_row, w, seed32, ti, *draws)
        nid, act, feat_b, bin_b, nst_b = progs["init"]()
        for lv, level_step in zip(progs["plan"], progs["levels"]):
            ordered = ()
            if lv["rows"] == "sorted":  # one program for every such level: the level's first node and size are arguments
                ordered = progs["order"](stw, nid, act, np.int32(lv["nodes"] - 1), np.int32(lv["nodes"]))
            nid, act, feat_b, bin_b, nst_b = level_step(Xb, stw, nid, act, feat_b, bin_b, nst_b, seed32, ti, *ordered)
        rounds.append((feat_b, bin_b, progs["final"](stw, nid, act, nst_b)))
    feats, bins_, nstats = progs["stack"](*(tuple(r[i] for r in rounds) for i in range(3)))
    return {"feature": feats, "split_bin": bins_, "node_stats": nstats, "plan": progs["plan"]}


# ---------------------------------------------------------------------------
# Prediction
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("max_depth",))
def forest_raw_predict(
    X: jax.Array,  # [n, d] float
    feature: jax.Array,  # [T, M]
    threshold: jax.Array,  # [T, M] real-valued thresholds
    leaf_value: jax.Array,  # [T, M, S]
    *,
    max_depth: int,
) -> jax.Array:
    """Average of per-tree leaf values: [n, S]. Traversal is a fixed-depth
    gather loop (vectorized oblivious descent, SURVEY.md §7 architecture map)."""

    def one_tree(feat, thr, leaves):
        def step(_, node):
            f = feat[node]
            is_split = f >= 0
            xv = jnp.take_along_axis(X, jnp.maximum(f, 0)[:, None], axis=1)[:, 0]
            child = 2 * node + jnp.where(xv <= thr[node], 1, 2)
            return jnp.where(is_split, child, node)

        node = jax.lax.fori_loop(0, max_depth, step, jnp.zeros(X.shape[0], jnp.int32))
        return leaves[node]  # [n, S]

    per_tree = jax.vmap(one_tree)(feature, threshold, leaf_value)  # [T, n, S]
    return jnp.mean(per_tree, axis=0)


def split_bins_to_thresholds(
    feature: np.ndarray, split_bin: np.ndarray, edges: np.ndarray
) -> np.ndarray:
    """Convert bin-id splits to real thresholds using the bin edges.

    Split 'bin <= b' corresponds to 'x <= edges[f, b]' (searchsorted-left)."""
    f = np.maximum(feature, 0)
    b = np.minimum(split_bin, edges.shape[1] - 1)
    thr = edges[f, b]
    return np.where(feature >= 0, thr, np.inf).astype(np.float64)
