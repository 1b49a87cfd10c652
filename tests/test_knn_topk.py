#
# The fused distance + top-k kernel (ops/distance.py `_pl_knn_topk`) and the
# kNN model that keeps its items on the device (models/knn.py): the kernel run
# through the Pallas interpreter and the jnp form, each against a plain full
# sort, across ties, ragged item blocks, padding rows, k past a shard's rows
# and the 8-device mesh; the model's one placement, its spans and counters;
# and the KMeans block planner, which the kernel's own VMEM budget leaves as
# it was.
#
import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu import telemetry
from spark_rapids_ml_tpu.ops import distance


@pytest.fixture
def kernel_mode(request):
    """`interpret` runs the real kernel through the Pallas interpreter,
    `jnp` the XLA form; the probed mode comes back after."""
    saved = distance._MODE
    distance._MODE = request.param
    yield request.param
    distance._MODE = saved


@pytest.fixture
def interpret_mode():
    saved = distance._MODE
    distance._MODE = "interpret"
    yield
    distance._MODE = saved


@pytest.fixture
def tele():
    telemetry.registry().reset()
    telemetry.enable()
    yield telemetry.registry()
    telemetry.registry().reset()
    telemetry.disable()


def _full_sort(q, items, kk, valid=None):
    """The plain reference: every squared distance (no ||q||^2 term, as
    `topk_tile` returns them), then a stable sort: ties to the lower index."""
    q, items = np.asarray(q, np.float64), np.asarray(items, np.float64)
    d2 = np.sum(items * items, axis=1)[None, :] - 2.0 * q @ items.T
    if valid is not None:
        d2 = np.where(np.asarray(valid)[None, :], d2, np.inf)
    order = np.argsort(d2, axis=1, kind="stable")[:, :kk]
    return np.take_along_axis(d2, order, axis=1), order


@pytest.mark.parametrize("kernel_mode", ["interpret", "jnp"], indirect=True)
def test_topk_matches_a_full_sort_at_96_columns(kernel_mode):
    # 5,000 x 96 items, 300 queries, k = 10: the plan's blocks (304 rows, a
    # whole block of items and a ragged second) on the kernel path
    rng = np.random.default_rng(0)
    items = rng.normal(size=(5000, 96))
    q = rng.normal(size=(300, 96))
    d2, idx = jax.jit(lambda q, x: distance.topk_tile(q, x, None, 10))(q, items)
    d2r, idxr = _full_sort(q, items, 10)
    np.testing.assert_array_equal(np.asarray(idx), idxr)
    np.testing.assert_allclose(np.asarray(d2), d2r, rtol=1e-9, atol=1e-9)
    if kernel_mode == "interpret":
        assert distance.topk_plan(300, 5000, 96, 10, np.float64, False) == (304, 4096)


@pytest.mark.parametrize("n", [7, 128, 129, 300, 1000])
def test_ragged_item_blocks_count_each_item_once(interpret_mode, n):
    # 128-item blocks: none whole (7), exactly whole (128, no tail), one item
    # past a block (129), a ragged tail (300, 1000); the tail block is the only
    # copy and its fill rows are never taken, nor is an item taken twice
    rng = np.random.default_rng(n)
    items = rng.normal(size=(n, 16))
    q = rng.normal(size=(37, 16))
    kk = min(10, n)
    d2, idx = distance.topk_tile(q, items, None, kk, k_tile=128, block_rows=16)
    d2r, idxr = _full_sort(q, items, kk)
    np.testing.assert_array_equal(np.asarray(idx), idxr)
    np.testing.assert_allclose(np.asarray(d2), d2r, rtol=1e-9, atol=1e-9)
    assert all(len(set(row)) == kk for row in np.asarray(idx).tolist())


@pytest.mark.parametrize("kernel_mode", ["interpret", "jnp"], indirect=True)
def test_exact_ties_go_to_the_lower_index_across_blocks_and_chunks(kernel_mode):
    # integer rows: every distance is exact whatever the contraction's shape,
    # so duplicated rows tie bit for bit; the duplicates lie in later item
    # blocks than their originals, and 40 queries make five 8-row chunks
    rng = np.random.default_rng(1)
    base = rng.integers(-3, 4, size=(300, 8)).astype(np.float64)
    items = np.concatenate([base, base[::-1][:200], base[:50]])
    q = rng.integers(-3, 4, size=(40, 8)).astype(np.float64)
    d2, idx = distance.topk_tile(q, items, None, 12, k_tile=128, block_rows=40)
    d2r, idxr = _full_sort(q, items, 12)
    np.testing.assert_array_equal(np.asarray(d2), d2r)
    np.testing.assert_array_equal(np.asarray(idx), idxr)
    ref = jax.lax.top_k(-(jnp.sum(items * items, 1)[None, :] - 2.0 * (q @ items.T)), 12)[1]
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref))


def test_padding_rows_are_never_chosen(interpret_mode):
    # the padding rows are the queries themselves (distance 0): still never
    # taken; with fewer valid items than k the unfilled slots read +inf
    rng = np.random.default_rng(2)
    q = rng.normal(size=(9, 6))
    items = np.concatenate([rng.normal(size=(5, 6)), q[:4], rng.normal(size=(140, 6))])
    valid = np.ones(len(items), bool)
    valid[5:9] = False
    d2, idx = distance.topk_tile(q, items, valid, 10, k_tile=128, block_rows=8)
    d2r, idxr = _full_sort(q, items, 10, valid)
    np.testing.assert_array_equal(np.asarray(idx), idxr)
    assert not np.isin(np.asarray(idx), np.arange(5, 9)).any()
    few = np.zeros(len(items), bool)
    few[:3] = True
    d2, idx = distance.topk_tile(q, items, few, 5, k_tile=128, block_rows=8)
    assert np.isinf(np.asarray(d2)[:, 3:]).all() and np.isfinite(np.asarray(d2)[:, :3]).all()
    assert (np.sort(np.asarray(idx)[:, :3], axis=1) == [0, 1, 2]).all()


def test_an_item_that_is_not_a_number_blanks_nothing_else(interpret_mode):
    # one NaN item counts as +inf: the other items of its block are still taken
    rng = np.random.default_rng(3)
    items = rng.normal(size=(256, 8))
    items[70, 2] = np.nan
    q = rng.normal(size=(8, 8))
    d2, idx = distance.topk_tile(q, items, None, 5, k_tile=128, block_rows=8)
    keep = np.ones(256, bool)
    keep[70] = False
    d2r, idxr = _full_sort(q, np.nan_to_num(items), 5, keep)
    np.testing.assert_array_equal(np.asarray(idx), idxr)


@pytest.mark.parametrize("kernel_mode", ["interpret", "jnp"], indirect=True)
def test_tile_topk_pads_k_past_a_shards_rows(kernel_mode):
    rng = np.random.default_rng(4)
    items = rng.normal(size=(6, 5))
    q = rng.normal(size=(11, 5))
    dist, idx = distance.tile_topk(jnp.asarray(items), jnp.asarray(q), jnp.ones(6, bool), 9)
    d2r, idxr = _full_sort(q, items, 6)
    np.testing.assert_array_equal(np.asarray(idx)[:, :6], idxr)
    np.testing.assert_allclose(np.asarray(dist)[:, :6], d2r + np.sum(q * q, 1)[:, None], rtol=1e-9)
    assert np.isinf(np.asarray(dist)[:, 6:]).all()


def test_the_sharded_search_takes_the_kernel_on_the_8_device_mesh(interpret_mode):
    # the kernel under shard_map, each shard 100 items (k = 12 past none, the
    # padding of 803 rows to 808 on the last shard): the one-device answer
    from spark_rapids_ml_tpu.ops.knn import exact_knn
    from spark_rapids_ml_tpu.parallel import get_mesh, make_global_rows

    rng = np.random.default_rng(5)
    items = rng.normal(size=(803, 12)).astype(np.float32)
    q = rng.normal(size=(33, 12)).astype(np.float32)
    mesh8 = get_mesh(8)
    X8, w8, _ = make_global_rows(mesh8, items)
    d8, i8 = exact_knn(X8, w8 > 0, jax.device_put(q), mesh=mesh8, k=12)
    _, idxr = _full_sort(q, items, 12)
    np.testing.assert_array_equal(np.asarray(i8), idxr)
    mesh1 = get_mesh(1)
    X1, w1, _ = make_global_rows(mesh1, items)
    d1, i1 = exact_knn(X1, w1 > 0, jax.device_put(q), mesh=mesh1, k=12)
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i8))
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d8), rtol=1e-5, atol=1e-5)


# ------------------------------------------ the selection: a take, or a merge --

# (query rows, items) a step: one 8-row chunk, two, and five chunks of a step
_BLOCKS = [(8, 128), (16, 256), (40, 512)]


def _far_items(rng, n, d, block_items):
    """Integer items far from every query, each item block 1,000 farther
    than the one before, so that a row's best list fills in the first block
    and only planted items improve it after; integers keep every distance
    exact, so ties are bit for bit."""
    items = rng.integers(40, 60, size=(n, d)).astype(np.float64)
    items[:, 0] += 1000.0 * (np.arange(n) // block_items)
    return items


def _check(q, items, kk, block_rows, block_items, valid=None):
    d2, idx = distance.topk_tile(q, items, valid, kk, k_tile=block_items, block_rows=block_rows)
    d2r, idxr = _full_sort(q, items, kk, valid)
    np.testing.assert_array_equal(np.asarray(idx), idxr)
    np.testing.assert_array_equal(np.asarray(d2), d2r)
    assert all(len(set(row)) == kk for row in np.asarray(idx).tolist())
    return np.asarray(idx)


@pytest.mark.parametrize("block_rows,block_items", _BLOCKS)
def test_a_rows_only_improving_item_in_its_blocks_last_lane_is_taken(interpret_mode, block_rows, block_items):
    # each row's one better item sits in the last lane of the last whole
    # block: the step's single take, at the block's last index
    rng = np.random.default_rng(10)
    q = rng.integers(-2, 3, size=(block_rows, 8)).astype(np.float64)
    items = _far_items(rng, 3 * block_items, 8, block_items)
    at = 3 * block_items - 1
    items[at] = q[0]
    idx = _check(q, items, 4, block_rows, block_items)
    assert idx[0, 0] == at


@pytest.mark.parametrize("block_rows,block_items", _BLOCKS)
def test_ties_across_a_block_boundary_go_to_the_lower_index(interpret_mode, block_rows, block_items):
    # the same item in the last lane of block 1 and the first of block 2, for
    # every query: one takes it from each block alone (a take a block), the
    # rest of the list full of far items; k = 1 keeps the lower index only,
    # k = 2 keeps both, the lower first
    rng = np.random.default_rng(11)
    q = rng.integers(-2, 3, size=(block_rows, 8)).astype(np.float64)
    items = _far_items(rng, 3 * block_items, 8, block_items)
    planted = rng.integers(-2, 3, size=8).astype(np.float64)
    items[2 * block_items - 1] = items[2 * block_items] = planted
    for kk in (1, 2):
        idx = _check(q, items, kk, block_rows, block_items)
        assert (idx[:, 0] == 2 * block_items - 1).all()


@pytest.mark.parametrize("block_rows,block_items", _BLOCKS)
def test_ties_inside_a_block_go_to_the_lower_index_through_the_merge(interpret_mode, block_rows, block_items):
    # three copies of one item in one block, under every row's worst: the
    # rows merge, and of the copies the lower indices are kept first
    rng = np.random.default_rng(12)
    q = rng.integers(-2, 3, size=(block_rows, 8)).astype(np.float64)
    items = _far_items(rng, 2 * block_items + 37, 8, block_items)
    planted = rng.integers(-2, 3, size=8).astype(np.float64)
    copies = [block_items + 5, block_items + 70, 2 * block_items + 36]
    items[copies] = planted
    idx = _check(q, items, 2, block_rows, block_items)
    assert (idx == copies[:2]).all()


@pytest.mark.parametrize("block_rows,block_items", _BLOCKS)
def test_an_item_that_is_not_a_number_blanks_no_other_block(interpret_mode, block_rows, block_items):
    # a NaN item in block 1 beside each row's better item there (a take), and
    # two better items a row in block 2 (a merge): all of them are kept
    rng = np.random.default_rng(13)
    q = rng.integers(-2, 3, size=(block_rows, 8)).astype(np.float64)
    items = _far_items(rng, 3 * block_items, 8, block_items)
    items[block_items + 3, 1] = np.nan
    items[block_items + 9] = q[0]
    items[[2 * block_items + 1, 3 * block_items - 2]] = q[-1]
    keep = np.ones(len(items), bool)
    keep[block_items + 3] = False
    d2, idx = distance.topk_tile(q, items, None, 3, k_tile=block_items, block_rows=block_rows)
    d2r, idxr = _full_sort(q, np.nan_to_num(items), 3, keep)
    np.testing.assert_array_equal(np.asarray(idx), idxr)
    assert not (np.asarray(idx) == block_items + 3).any()


@pytest.mark.parametrize("block_rows,block_items", _BLOCKS)
def test_a_ragged_tail_takes_its_items_and_never_its_fill(interpret_mode, block_rows, block_items):
    # the last block holds 3 items and fill: a take from its last real item,
    # and a merge of its first two for another row
    rng = np.random.default_rng(14)
    q = rng.integers(-2, 3, size=(block_rows, 8)).astype(np.float64)
    n = 2 * block_items + 3
    items = _far_items(rng, n, 8, block_items)
    items[n - 1] = q[0]
    items[[n - 3, n - 2]] = q[-1]
    idx = _check(q, items, 5, block_rows, block_items)
    assert idx[0, 0] == n - 1 and (idx.max() < n)


@pytest.mark.parametrize("block_rows,block_items", _BLOCKS)
def test_a_take_and_a_merge_in_one_chunk_take_each_item_once(interpret_mode, block_rows, block_items):
    # in one 8-row chunk, row 0 has one better item in block 1 (taken with
    # the contraction) and row 1 has four (merged): the merge passes row 0
    # by, so its item is not taken twice
    rng = np.random.default_rng(15)
    q = rng.integers(-2, 3, size=(block_rows, 8)).astype(np.float64)
    q[1] = q[0] + 1
    items = _far_items(rng, 2 * block_items, 8, block_items)
    items[block_items + 2] = q[0]
    items[[block_items + 4, block_items + 6, block_items + 8, block_items + 10]] = q[1]
    idx = _check(q, items, 6, block_rows, block_items)
    assert idx[0, 0] == block_items + 2 and (np.sort(idx[1, :4]) == block_items + np.array([4, 6, 8, 10])).all()


@pytest.mark.parametrize("block_rows,block_items", _BLOCKS)
def test_merged_chunks_are_counted(interpret_mode, block_rows, block_items):
    # every chunk merges in the first block, where its lists fill; in block 1
    # each row has one better item (a take, no merge), in block 2 three
    # (every chunk merges again)
    rng = np.random.default_rng(16)
    q = rng.integers(-2, 3, size=(block_rows, 8)).astype(np.float64)
    items = _far_items(rng, 3 * block_items, 8, block_items)
    items[block_items + 1] = q[0]
    items[[2 * block_items, 2 * block_items + 1, 2 * block_items + 2]] = q[0]
    d2, idx, merged = distance.topk_tile(q, items, None, 5, k_tile=block_items, block_rows=block_rows,
                                         count_merges=True)
    chunks = block_rows // 8
    assert distance.topk_chunk_steps(block_rows, len(items), block_rows, block_items) == 3 * chunks
    assert int(merged) == 2 * chunks
    _, idxr = _full_sort(q, items, 5)
    np.testing.assert_array_equal(np.asarray(idx), idxr)


def _frames(rng, n_items, n_queries, d):
    items = rng.normal(size=(n_items, d)).astype(np.float32)
    queries = [rng.normal(size=(n_queries, d)).astype(np.float32) for _ in range(2)]
    return items, pd.DataFrame({"features": list(items)}), [pd.DataFrame({"features": list(q)}) for q in queries], queries


def test_kneighbors_places_the_items_once_and_reports_the_kernel(interpret_mode, tele):
    from spark_rapids_ml_tpu.models.knn import NearestNeighbors

    rng = np.random.default_rng(6)
    items, item_df, query_dfs, queries = _frames(rng, 700, 50, 24)
    model = NearestNeighbors(k=10, num_workers=1).setInputCol("features").fit(item_df)
    mark = tele.mark()
    answers = [model.kneighbors(df)[2] for df in query_dfs]
    delta = tele.delta(mark)
    counters, spans = delta["counters"], delta["spans"]
    assert counters["knn.item_placements"] == 1 and counters["knn.item_reuses"] == 1
    assert counters["knn.kernel_calls"] == 2 and counters["knn.candidates"] == 2 * 50 * 700
    paths = [s["path"] for s in spans]
    for path in ("kneighbors", "kneighbors/extract", "kneighbors/search", "kneighbors/fetch", "kneighbors.assemble"):
        assert paths.count(path) == 2, path
    assert paths.count("kneighbors/place") == 1
    search = next(s for s in spans if s["path"] == "kneighbors/search")
    assert {k: search[k] for k in ("queries", "items", "d", "k", "topk_path", "precision")} == {
        "queries": 50, "items": 700, "d": 24, "k": 10, "topk_path": "kernel", "precision": "float32"}
    assert (search["block_rows"], search["block_items"]) == distance.plan_topk(50, 700, 24)
    for q, knn_df in zip(queries, answers):
        _, idxr = _full_sort(q, items, 10)
        np.testing.assert_array_equal(np.stack(knn_df["indices"].to_numpy()), idxr)
        ref = np.sqrt(np.maximum(_full_sort(q, items, 10)[0] + np.sum(q.astype(np.float64) ** 2, 1)[:, None], 0))
        np.testing.assert_allclose(np.stack(knn_df["distances"].to_numpy()), ref, rtol=1e-4, atol=1e-4)


def test_kneighbors_counts_the_chunks_that_merged(interpret_mode, tele):
    # 5,000 items: one whole block of 4,096 and a ragged tail; 30 queries: one
    # block of 32 rows, four chunks. Every chunk merges in the first block
    from spark_rapids_ml_tpu.models.knn import NearestNeighbors

    rng = np.random.default_rng(9)
    items, item_df, query_dfs, queries = _frames(rng, 5000, 30, 8)
    model = NearestNeighbors(k=10, num_workers=1).setInputCol("features").fit(item_df)
    for df in query_dfs:
        model.kneighbors(df)
    counters = tele.snapshot()["counters"]
    assert distance.plan_topk(30, 5000, 8) == (32, 4096)
    assert counters["knn.flag_pairs"] == 2 * distance.topk_chunk_steps(30, 5000, 32, 4096) == 2 * 8
    assert 2 * 4 <= counters["knn.merged_pairs"] <= counters["knn.flag_pairs"]


def test_the_sharded_search_leaves_the_merge_counters_alone(interpret_mode, tele):
    from spark_rapids_ml_tpu.models.knn import NearestNeighbors

    rng = np.random.default_rng(17)
    items, item_df, query_dfs, queries = _frames(rng, 300, 9, 8)
    model = NearestNeighbors(k=3, num_workers=4).setInputCol("features").fit(item_df)
    _, _, knn_df = model.kneighbors(query_dfs[0])
    counters = tele.snapshot()["counters"]
    assert "knn.merged_pairs" not in counters and "knn.flag_pairs" not in counters
    np.testing.assert_array_equal(np.stack(knn_df["indices"].to_numpy()), _full_sort(queries[0], items, 3)[1])


def test_a_new_worker_count_places_the_items_again(tele):
    from spark_rapids_ml_tpu.models.knn import NearestNeighbors

    rng = np.random.default_rng(7)
    items, item_df, query_dfs, _ = _frames(rng, 90, 7, 4)
    model = NearestNeighbors(k=3, num_workers=1).setInputCol("features").fit(item_df)
    first = model.kneighbors(query_dfs[0])[2]
    model.num_workers = 4
    second = model.kneighbors(query_dfs[0])[2]
    counters = tele.snapshot()["counters"]
    assert counters["knn.item_placements"] == 2 and counters.get("knn.item_reuses", 0) == 0
    np.testing.assert_array_equal(np.stack(first["indices"].to_numpy()), np.stack(second["indices"].to_numpy()))


def test_the_serving_program_takes_the_kernel(interpret_mode, tele):
    from spark_rapids_ml_tpu.models.knn import NearestNeighbors

    rng = np.random.default_rng(8)
    items, item_df, query_dfs, queries = _frames(rng, 300, 20, 8)
    model = NearestNeighbors(k=4).setInputCol("features").fit(item_df)
    program = model._serve_program()
    xp, n_valid = program.pad(queries[0])
    dist, ids = program.fetch(program.launch(xp), n_valid)
    _, _, knn_df = model.kneighbors(query_dfs[0])
    np.testing.assert_array_equal(np.asarray(ids), np.stack(knn_df["indices"].to_numpy()))
    np.testing.assert_allclose(np.asarray(dist), np.stack(knn_df["distances"].to_numpy()), rtol=1e-5)
    assert model._serve_workspace_terms(xp.shape[0], 4)["topk_lists"] > 0  # the kernel's terms


# ------------------------------------------------- the planners, side by side --

# (rows, k, d, fast) -> (block_rows, block_k) as the KMeans cells and the
# serving rungs plan them; the top-k kernel has a budget of its own
_KMEANS_PLANS = [
    ((32768, 1000, 3000, True), (512, 512)),
    ((32768, 1000, 3000, False), (512, 512)),
    ((4096, 1000, 3000, False), (512, 512)),
    ((65536, 1024, 3072, False), (512, 512)),
    ((4096, 4096, 7168, False), (256, 128)),
    ((4096, 4096, 7168, True), (512, 256)),
]


@pytest.mark.parametrize("shape,plan", _KMEANS_PLANS)
def test_the_kmeans_block_plan_is_untouched(shape, plan):
    rows, k, d, fast = shape
    assert distance.plan_blocks(rows, k, d, jnp.float32, fast) == plan
    assert distance.block_vmem_bytes(512, 512, 3000, jnp.float32, False) == 63_438_848


@pytest.mark.parametrize("d,plan", [(96, (512, 4096)), (3000, (128, 512))])
def test_the_topk_plan_fits_its_own_budget(d, plan):
    got = distance.plan_topk(10_000, 10_000_000, d)
    assert got == plan
    assert distance.topk_vmem_bytes(*got, d, jnp.float32, False) <= distance.vmem_limit_bytes()
    # the [rows, 1] int32 column of rows that merge, one 128-lane tile wide, and
    # the [8, 128] int32 count block, double-buffered, over the operands, the
    # contraction and the lists
    br, bk = got
    dp = -(-d // 128) * 128
    rest = 2 * (br + 2 * bk) * dp * 4 + (br + bk) * dp * 17 + 2 * br * bk * 4 + 2 * 8 * bk * 4 + 2 * br * 128 * 8
    assert distance.topk_vmem_bytes(br, bk, d, jnp.float32, False) - rest == br * 128 * 4 + 2 * 8 * 128 * 4
    assert distance.plan_topk(37, 300, d)[0] == 40  # rows cut to the call, a multiple of 8
