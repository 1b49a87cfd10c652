#
# Parity suite for the shared tiled distance/top-k core (ops/distance.py):
# the Pallas kernels (run through the interpreter — CPU CI's way of
# executing real kernel code) against the bit-compatible pure-jnp fallback,
# swept across tile boundaries (rows/k/d = block±1), f32/f64,
# weighted/zero-weight padding rows, the `fast` bf16 precision mode, and
# top-k tie ordering against a full-matrix `jax.lax.top_k` reference.
# Plus the compile-count invariant: a KMeans fit compiles ONE distance
# program across all its Lloyd iterations (the distance.* counters tick at
# TRACE time by design).
#
import json
import pathlib
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu import telemetry
from spark_rapids_ml_tpu.core import config
from spark_rapids_ml_tpu.ops import distance


@pytest.fixture
def interpret_mode():
    """Force the REAL kernels through the Pallas interpreter for this test;
    restore the probed mode after."""
    saved = distance._MODE
    distance._MODE = "interpret"
    yield
    distance._MODE = saved


@pytest.fixture
def jnp_mode():
    saved = distance._MODE
    distance._MODE = "jnp"
    yield
    distance._MODE = saved


def _data(n, k, d, dtype, seed=0, dup_rows=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(dtype)
    if dup_rows:  # deliberate exact ties for the tie-ordering tests
        X[-dup_rows:] = X[:dup_rows]
    C = rng.normal(size=(k, d)).astype(dtype)
    w = rng.uniform(0.5, 2.0, size=n).astype(dtype)
    return jnp.asarray(X), jnp.asarray(C), jnp.asarray(w)


def _fallback_assign_accumulate(X, w, C):
    d2 = jnp.sum(C * C, 1)[None, :] - 2.0 * (X @ C.T)
    assign = jnp.argmin(d2, axis=1)
    min_d2 = jnp.min(d2, axis=1) + jnp.sum(X * X, axis=1)
    oh = jax.nn.one_hot(assign, C.shape[0], dtype=X.dtype) * w[:, None]
    return oh.T @ X, jnp.sum(oh, axis=0), jnp.sum(jnp.maximum(min_d2, 0.0) * w)


# ------------------------------------------------- assign/accumulate parity --


@pytest.mark.parametrize("n", [7, 8, 9])
@pytest.mark.parametrize("k", [3, 4, 5])
@pytest.mark.parametrize("d", [5, 8])
def test_assign_accumulate_kernel_parity_f64(interpret_mode, n, k, d):
    # blocks of (8, 4): every (n, k) combination crosses a boundary or a
    # ragged tail on at least one axis
    X, C, w = _data(n, k, d, np.float64, seed=n * 100 + k * 10 + d)
    s, c, i = distance.assign_accumulate(X, w, C, block_rows=8, block_k=4)
    sr, cr, ir = _fallback_assign_accumulate(X, w, C)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-9)
    np.testing.assert_allclose(np.asarray(c), np.asarray(cr), rtol=1e-9)
    np.testing.assert_allclose(float(i), float(ir), rtol=1e-9)


@pytest.mark.parametrize("n,k", [(9, 5), (16, 4), (33, 7)])
def test_assignments_exact_f32(interpret_mode, n, k):
    X, C, _ = _data(n, k, 6, np.float32, seed=n)
    _, a = distance.assign_argmin(X, C, block_rows=8, block_k=4)
    ref = jnp.argmin(jnp.sum(C * C, 1)[None, :] - 2.0 * (X @ C.T), axis=1)
    assert (np.asarray(a) == np.asarray(ref)).all()


def test_assignments_exact_f32_fast_mode(interpret_mode):
    # `fast` (one-pass bf16, f32 accumulation) must round IDENTICALLY on the
    # kernel and fallback paths — assignments are compared exactly
    X, C, w = _data(33, 5, 8, np.float32, seed=3)
    s, c, i = distance.assign_accumulate(X, w, C, fast=True, block_rows=8, block_k=4)
    distance._MODE = "jnp"
    sr, cr, ir = distance.assign_accumulate(X, w, C, fast=True)
    distance._MODE = "interpret"
    np.testing.assert_allclose(np.asarray(c), np.asarray(cr), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(i), float(ir), rtol=1e-5)


def test_zero_weight_padding_rows_contribute_nothing(interpret_mode):
    # the resident pad contract: rows with w == 0 change NOTHING, on both
    # paths, including when they land in a ragged kernel block
    X, C, w = _data(11, 4, 5, np.float64, seed=7)
    Xp = jnp.concatenate([X, jnp.ones((5, 5), X.dtype) * 1e6])
    wp = jnp.concatenate([w, jnp.zeros((5,), X.dtype)])
    s, c, i = distance.assign_accumulate(Xp, wp, C, block_rows=8, block_k=4)
    sr, cr, ir = _fallback_assign_accumulate(X, w, C)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-9)
    np.testing.assert_allclose(np.asarray(c), np.asarray(cr), rtol=1e-9)
    np.testing.assert_allclose(float(i), float(ir), rtol=1e-9)


def test_argmin_assign_ragged_tiles_match_bruteforce(jnp_mode):
    # row-tiled predict path: clamp-back tiles recompute overlap rows
    # idempotently — assignments equal the untiled argmin
    X, C, _ = _data(37, 6, 5, np.float64, seed=11)
    a = distance.argmin_assign(X, C, batch_rows=8)
    ref = jnp.argmin(jnp.sum(C * C, 1)[None, :] - 2.0 * (X @ C.T), axis=1)
    assert (np.asarray(a) == np.asarray(ref)).all()
    assert a.dtype == jnp.int32


# --------------------------------------------- a tile read where it lies -----
#
# `assign_accumulate(..., tile=(start, rows))`: the kernels fetch the tile's
# row blocks out of the whole X by a prefetched block offset instead of being
# handed a slice. Same grid, same blocks, same body: every output is the
# sliced form's bit for bit.


def _tile_case(start, fast, n=96, rows=32):
    X, C, w = _data(n, 5, 9, np.float32, seed=7 + start + int(fast))
    w = w.at[start + 3].set(0.0).at[start + rows - 1].set(0.0)  # padding rows inside the tile
    return X, C, w, distance.row_sq(X), slice(start, start + rows)


@pytest.mark.parametrize("fast", [False, True])
@pytest.mark.parametrize("start", [0, 32, 64])  # the first, a middle and the last tile of 96 rows
def test_tile_read_in_place_is_bit_identical_to_the_slice(interpret_mode, fast, start):
    X, C, w, x_sq, rows = _tile_case(start, fast)
    blocks = dict(fast=fast, block_rows=8, block_k=4)
    sliced = distance.assign_accumulate(X[rows], w[rows], C, **blocks)
    whole = distance.assign_accumulate(
        X, w[rows], C, x_sq=x_sq[rows], tile=(jnp.int32(start), 32), **blocks
    )
    for a, b in zip(sliced, whole):  # sums, counts, inertia
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # minima and assignments, from the argmin kernel itself
    cp, _ = distance._pad_rows_multiple(C, 4)
    csq = jnp.pad(distance._c_sq(C), (0, cp.shape[0] - C.shape[0]), constant_values=jnp.inf)
    kernel = dict(block_rows=8, block_k=4, fast=fast, interpret=True)
    mind, best = distance._pl_argmin(X[rows], cp, csq, **kernel)
    mind_w, best_w = distance._pl_argmin(X, cp, csq, tile=(jnp.int32(start), 32), **kernel)
    np.testing.assert_array_equal(np.asarray(mind), np.asarray(mind_w))
    np.testing.assert_array_equal(np.asarray(best), np.asarray(best_w))
    ref = jnp.argmin(jnp.sum(C * C, 1)[None, :] - 2.0 * distance._mm(X[rows], C.T, fast), axis=1)
    assert (np.asarray(best_w) == np.asarray(ref)).all()


@pytest.mark.parametrize("fast", [False, True])
def test_traced_tile_offset_matches_the_slice(interpret_mode, fast):
    # a tile scan step's form: ONE jitted program, the offset an argument

    @partial(jax.jit, static_argnames=("in_place",))
    def program(X, w, C, x_sq, start, *, in_place):
        xb, wb, qb, tile = distance._tile_operands(X, w, x_sq, start, 32, in_place)
        return distance.assign_accumulate(xb, wb, C, fast=fast, x_sq=qb, tile=tile)

    X, C, w, x_sq, _ = _tile_case(0, fast)
    for start in (0, 32, 64):
        args = (X, w, C, x_sq, np.int32(start))
        sliced = program(*args, in_place=False)
        whole = program(*args, in_place=True)
        for a, b in zip(sliced, whole):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("n", [96, 100, 131])
def test_shard_scan_in_place_keeps_the_ragged_tail_sliced(interpret_mode, n):
    # 32-row tiles read in place, then a tail of 0, 4 or 3 rows (no multiple of
    # the 32-row blocks the plan takes): sliced, and the whole scan agrees with
    # the all-sliced scan bit for bit and with brute force
    X, C, w, x_sq, _ = _tile_case(0, False, n=n)
    sliced = distance.tile_assign_accumulate(X, w, C, x_sq, 32)
    whole = distance.tile_assign_accumulate(X, w, C, x_sq, 32, in_place=True)
    for a, b in zip(sliced, whole):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(whole, _fallback_assign_accumulate(X, w, C)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=1e-5)


def test_a_tile_that_cannot_be_read_in_place_is_refused(interpret_mode):
    X, C, w, x_sq, rows = _tile_case(0, False)
    with pytest.raises(ValueError, match="slice the tile"):  # 30 rows are no whole 8-row blocks
        distance.assign_accumulate(X, w[:30], C, x_sq=x_sq[:30], tile=(0, 30), block_rows=8, block_k=4)
    with pytest.raises(ValueError, match="slice the tile"):  # no row norms to go with it
        distance.assign_accumulate(X, w[rows], C, tile=(0, 32), block_rows=8, block_k=4)
    distance._MODE = "jnp"  # (the fixture restores the mode)
    with pytest.raises(ValueError, match="slice the tile"):  # no kernels
        distance.assign_accumulate(X, w[rows], C, x_sq=x_sq[rows], tile=(0, 32))


# ------------------------------------------------------------ top-k parity --


def _topk_reference(q, items, valid, kk):
    d2 = jnp.sum(items * items, 1)[None, :] - 2.0 * (q @ items.T)
    if valid is not None:
        d2 = jnp.where(valid[None, :], d2, jnp.inf)
    neg_d, idx = jax.lax.top_k(-d2, kk)
    return -neg_d, idx


@pytest.mark.parametrize("mode_fixture", ["interpret_mode", "jnp_mode"])
@pytest.mark.parametrize("n", [7, 8, 9, 20])
def test_topk_tile_boundary_parity(request, mode_fixture, n):
    request.getfixturevalue(mode_fixture)
    rng = np.random.default_rng(n)
    q = jnp.asarray(rng.normal(size=(5, 6)))
    items = jnp.asarray(rng.normal(size=(n, 6)))
    kk = min(4, n)
    d2, idx = distance.topk_tile(q, items, None, kk, k_tile=4, block_rows=8)
    d2r, idxr = _topk_reference(q, items, None, kk)
    np.testing.assert_allclose(np.asarray(d2), np.asarray(d2r), rtol=1e-9)
    assert (np.asarray(idx) == np.asarray(idxr)).all()


def test_topk_tie_ordering_matches_lax_top_k(jnp_mode):
    # duplicated item rows produce EXACTLY tied distances; the k-tiled
    # running merge must resolve them like one full-matrix lax.top_k
    # (lower index first) even when the tie straddles a tile boundary
    rng = np.random.default_rng(0)
    base = rng.integers(-3, 4, size=(6, 5)).astype(np.float64)
    items = jnp.asarray(np.concatenate([base, base[:3]]))  # ids 6,7,8 == 0,1,2
    q = jnp.asarray(rng.integers(-3, 4, size=(4, 5)).astype(np.float64))
    d2, idx = distance.topk_tile(q, items, None, 6, k_tile=4)
    d2r, idxr = _topk_reference(q, items, None, 6)
    np.testing.assert_array_equal(np.asarray(d2), np.asarray(d2r))
    assert (np.asarray(idx) == np.asarray(idxr)).all()


def test_topk_tie_ordering_kernel_path(interpret_mode):
    # INTEGER-valued rows: every dot product is exact in f64 regardless of
    # tiling/summation order, so duplicated rows are bitwise ties on both
    # paths — the only fair way to compare tie ordering across matmul
    # shapes (float matmuls of different shapes are not bitwise
    # reproducible even within one backend)
    rng = np.random.default_rng(1)
    base = rng.integers(-3, 4, size=(6, 5)).astype(np.float64)
    items = jnp.asarray(np.concatenate([base, base[:3]]))  # ids 6,7,8 == 0,1,2
    q = jnp.asarray(rng.integers(-3, 4, size=(4, 5)).astype(np.float64))
    d2, idx = distance.topk_tile(q, items, None, 6, k_tile=4, block_rows=4)
    d2r, idxr = _topk_reference(q, items, None, 6)
    np.testing.assert_array_equal(np.asarray(d2), np.asarray(d2r))
    assert (np.asarray(idx) == np.asarray(idxr)).all()


def test_topk_invalid_items_masked(interpret_mode):
    # padding items (valid=False) must never appear among finite neighbors
    rng = np.random.default_rng(2)
    items = jnp.asarray(rng.normal(size=(9, 4)))
    valid = jnp.asarray(np.array([True] * 6 + [False] * 3))
    q = jnp.asarray(rng.normal(size=(3, 4)))
    d2, idx = distance.topk_tile(q, items, valid, 6, k_tile=4, block_rows=4)
    finite = np.isfinite(np.asarray(d2))
    assert finite[:, :6].sum() == 3 * 6  # all six real items found
    assert (np.asarray(idx)[finite] < 6).all()


def test_tile_topk_routes_batch_queries_through_config():
    # satellite: the query scan's hardcoded 4096 became
    # config["distance_tile_rows"] — a small knob value must still produce
    # exact results (more, smaller tiles), proving the knob is live
    saved = config["distance_tile_rows"]
    config["distance_tile_rows"] = 8
    try:
        assert distance.tile_rows() == 8
        rng = np.random.default_rng(5)
        items = jnp.asarray(rng.normal(size=(30, 4)))
        valid = jnp.asarray(np.ones(30, dtype=bool))
        q = jnp.asarray(rng.normal(size=(21, 4)))  # 3 tiles of 8 (ragged)
        dist, idx = distance.tile_topk(items, q, valid, 5)
        d2r, idxr = _topk_reference(q, items, valid, 5)
        ref = np.asarray(d2r) + np.sum(np.asarray(q) ** 2, axis=1)[:, None]
        np.testing.assert_allclose(np.asarray(dist), ref, rtol=1e-9)
        assert (np.asarray(idx) == np.asarray(idxr)).all()
    finally:
        config["distance_tile_rows"] = saved


# ------------------------------------------------------- compile invariant --


def test_kmeans_fit_compiles_one_distance_program():
    # the distance.* counters tick once per TRACE: across 3 and then 8 Lloyd
    # iterations of identical shape, the assign program is traced for the
    # first fit only — no per-iteration (or per-fit) recompile
    from spark_rapids_ml_tpu.ops.kmeans import kmeans_fit
    from spark_rapids_ml_tpu.parallel import get_mesh

    rng = np.random.default_rng(9)
    # unique shape so no other test's cached program hides the first trace
    X = jnp.asarray(rng.normal(size=(257, 13)))
    w = jnp.ones((257,), X.dtype)
    c0 = jnp.asarray(rng.normal(size=(6, 13)))
    telemetry.enable()
    try:
        telemetry.registry().reset()
        kmeans_fit(X, w, c0, mesh=get_mesh(1), max_iter=3, tol=0.0)
        first = telemetry.snapshot()["counters"].get("distance.assign_programs", 0)
        assert first > 0  # the fit really went through the shared core
        kmeans_fit(X, w, c0, mesh=get_mesh(1), max_iter=8, tol=0.0)
        second = telemetry.snapshot()["counters"].get("distance.assign_programs", 0)
        assert second == first  # 8 iterations + a second fit: zero retraces
    finally:
        telemetry.registry().reset()
        telemetry.disable()


def test_kernel_mode_probe_is_jnp_on_cpu(monkeypatch):
    monkeypatch.delenv("SRML_DISTANCE_KERNEL", raising=False)
    saved = distance._MODE
    distance._MODE = None
    try:
        assert distance.kernel_mode() == "jnp"  # CPU backend -> fallback
    finally:
        distance._MODE = saved


def test_kernel_mode_env_override(monkeypatch):
    saved = distance._MODE
    try:
        monkeypatch.setenv("SRML_DISTANCE_KERNEL", "interpret")
        distance._MODE = None
        assert distance.kernel_mode() == "interpret"
        # explicit `pallas` really FORCES the kernel path (no silent
        # self-test fallback — docs/configuration.md contract)
        monkeypatch.setenv("SRML_DISTANCE_KERNEL", "pallas")
        distance._MODE = None
        assert distance.kernel_mode() == "pallas"
    finally:
        distance._MODE = saved


def test_plan_blocks_fits_budget_and_floors():
    br, bk = distance.plan_blocks(4096, 1000, 3000)
    assert (
        distance.block_vmem_bytes(br, bk, 3000, np.float32, False)
        <= distance.vmem_limit_bytes()
    )
    assert br >= 8 and bk >= 128
    # absurd depth: nothing fits -> None (callers fall back to jnp)
    assert distance.plan_blocks(4096, 1000, 50_000_000) is None


# ------------------------------------------------------ one block planner --
#
# `plan_blocks` is the only author of (block_rows, block_k), and `block_plan`
# is what a dispatch takes of it in this process (docs/performance.md "Tiled
# distance core"). Until PR 31 a measured table (`srml_autotune.json` beside
# the compile cache, on by default) stood between the two; these hold what
# its removal promised: such a file is never opened, the benchmark files'
# leftover switch moves nothing, the cells' dispatches plan (512, 512), and
# the kNN serving budget and the top-k program take one item block.

CONFIGS = pathlib.Path(__file__).resolve().parents[1] / "chipbench" / "configs"


def _table_key(n_rows, k_side, d, fast):
    """The deleted table's key for a float32 dispatch: rows and k-side
    rounded up to a power of two, the depth exact, the mode spelled out."""
    up = lambda v: 1 << (max(1, int(v)) - 1).bit_length()
    return f"r{up(n_rows)}:k{up(k_side)}:d{d}:float32:{'fast' if fast else 'full'}"


@pytest.fixture
def planted_table(tmp_path, monkeypatch):
    """A table as the deleted autotuner persisted it, naming (128, 128) for
    every dispatch these tests plan, beside the compile cache this process
    would use."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)  # it would win
    monkeypatch.setitem(config, "compilation_cache_dir", str(tmp_path))
    shapes = [(32768, 1000, 3000), (300, 5, 16)] + [(b, 640, 3000) for b in (128, 1024, 8192)]
    entries = {_table_key(*shape, fast): [128, 128] for shape in shapes for fast in (True, False)}
    (tmp_path / "srml_autotune.json").write_text(json.dumps({"version": 1, "entries": entries}))
    return entries


def test_block_vmem_bytes_counts_stored_dtype_and_double_buffers():
    d = 3072  # lane-aligned, so the terms are exact
    full = distance.block_vmem_bytes(512, 512, d, jnp.float32, fast=False)
    fast = distance.block_vmem_bytes(512, 512, d, jnp.float32, fast=True)
    # the two [512, d] f32 blocks, each double-buffered, are the floor —
    # exactly the 24 MiB Mosaic reports for this shape on a v5e
    assert 2 * (512 + 512) * d * 4 == 24 << 20 < fast
    # both modes hold the SAME f32 blocks; the fast path adds a bf16 copy of
    # both, the fp32 contraction the row block's bf16 splits and residuals
    assert fast - (512 + 512) * d * 2 == full - 512 * d * 17
    # a non-128-multiple depth occupies whole lane tiles
    assert distance.block_vmem_bytes(512, 512, 3000, jnp.float32, False) == full
    # f64 blocks are twice as wide
    assert distance.block_vmem_bytes(512, 512, d, jnp.float64, False) > full


def test_full_precision_plan_never_outgrows_the_fast_plan(interpret_mode):
    # a VMEM-tight depth: both modes must shrink, full precision at least
    # as much (it peels 17 bytes per row-block element, fast 2 per element)
    d = 7168
    full = distance.plan_blocks(4096, 4096, d, jnp.float32, False)
    fast = distance.plan_blocks(4096, 4096, d, jnp.float32, True)
    assert full is not None and fast is not None
    assert fast != (512, 512)
    assert full[0] * full[1] <= fast[0] * fast[1]
    # block_plan threads the same accounting
    assert distance.block_plan(4096, 4096, d, jnp.float32, False) == full
    assert distance.block_plan(4096, 4096, d, jnp.float32, True) == fast


@pytest.mark.parametrize("env", [None, "1"])
@pytest.mark.parametrize("fast", [True, False])
def test_a_persisted_table_is_never_opened(interpret_mode, planted_table, monkeypatch, fast, env):
    """Neither the plan at the cells' Lloyd tile nor the blocks a fit reports
    on its `loop` span follow a table left beside the compile cache, with the
    deleted switch's environment variable set or not."""
    from spark_rapids_ml_tpu.ops.kmeans import kmeans_fit
    from spark_rapids_ml_tpu.parallel import get_mesh

    if env is None:
        monkeypatch.delenv("SRML_AUTOTUNE", raising=False)
    else:
        monkeypatch.setenv("SRML_AUTOTUNE", env)
    assert _table_key(32768, 1000, 3000, fast) in planted_table
    assert distance.block_plan(32768, 1000, 3000, jnp.float32, fast) == (512, 512)
    rng = np.random.default_rng(31)
    X = jnp.asarray(rng.normal(size=(300, 16)).astype(np.float32))
    telemetry.enable()
    try:
        mark = telemetry.registry().mark()
        kmeans_fit(
            X, jnp.ones((300,), X.dtype), X[:5], mesh=get_mesh(1), max_iter=2, tol=0.0,
            precision_mode="fast" if fast else "high",
        )
        loop = next(s for s in telemetry.registry().delta(mark)["spans"] if s["name"] == "loop")
    finally:
        telemetry.registry().reset()
        telemetry.disable()
    assert _table_key(300, 5, 16, fast) in planted_table
    assert (loop["block_rows"], loop["block_k"]) == (300, 5) == distance.plan_blocks(300, 5, 16, jnp.float32, fast)


def _cell_dispatch(name, dispatch):
    """(rows, k, d, fast) of one kernel dispatch of a benchmark cell, from its
    configuration file and the estimator's own defaults."""
    from spark_rapids_ml_tpu.models.clustering import KMeans

    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    k, d = cfg["estimator"]["k"], cfg["d"]
    if dispatch == "predict":  # a 65,536-row batch goes through the core in row tiles
        return distance.tile_rows(), k, d, False
    rows_dev = cfg["rows"] // cfg.get("num_workers", 1)
    tile = min(KMeans()._solver_params["max_samples_per_batch"], rows_dev)
    fast = dispatch == "loop" and cfg["precision"]["distance_precision"] == "fast"
    return tile, k, d, fast


@pytest.mark.parametrize(
    "name,dispatch",
    [
        ("kmeans-p3k", "loop"),  # kmeans-p3k.refit: the bf16 Lloyd tile
        ("kmeans-p3k", "final_pass"),  # ... and its float32 inertia pass
        ("kmeans-p3k", "predict"),  # kmeans-p3k.transform
        ("kmeans-p3k-host4", "loop"),  # one chip's tile of the four-chip shard
    ],
)
def test_the_cells_dispatches_plan_512_by_512(monkeypatch, name, dispatch):
    monkeypatch.setattr(distance, "_MODE", "pallas")  # the compiled path's answer
    rows, k, d, fast = _cell_dispatch(name, dispatch)
    assert (rows, k, d) in {(32768, 1000, 3000), (4096, 1000, 3000)}
    assert distance.block_plan(rows, k, d, jnp.float32, fast) == (512, 512)
    assert rows % 512 == 0  # whole row blocks: a tile can be read in place
    assert distance.block_vmem_bytes(512, 512, d, jnp.float32, fast) <= distance.vmem_limit_bytes()


def test_the_benchmark_files_autotune_switch_is_inert(interpret_mode):
    """`core.config` has no autotune key; the switch the benchmark's
    configuration files carried at PR 31, and whatever `program_config` they
    carry now, lands in it as `chipbench/run.py` does it (a plain
    `dict.update`) and changes no plan."""
    assert not [key for key in config if key.startswith("autotune")]
    shapes = [(32768, 1000, 3000, jnp.float32, f) for f in (True, False)] + [(4096, 1000, 3000, jnp.float32, False)]
    before = [distance.block_plan(*shape) for shape in shapes]
    landed = {"autotune_enabled": False}
    for path in CONFIGS.glob("*.json"):
        landed.update(json.loads(path.read_text()).get("program_config", {}))
    landed = {key: value for key, value in landed.items() if key not in config}
    config.update(landed)
    try:
        assert [distance.block_plan(*shape) for shape in shapes] == before == [(512, 512)] * 3
    finally:
        for key in landed:
            del config[key]


@pytest.mark.parametrize("bucket", [128, 1024, 8192])
def test_knn_serving_budget_and_topk_program_take_one_item_block(interpret_mode, planted_table, monkeypatch, bucket):
    """`_serve_workspace_terms` budgets the blocks of the program
    `_serve_program` runs: the query rows and item block `topk_tile` hands
    the fused top-k kernel are the ones the budget pads the bucket to and
    counts the norms row in, table or no table."""
    import pandas as pd

    from spark_rapids_ml_tpu.models.knn import NearestNeighbors

    n_items, d, kk = 640, 3000, 8
    items = np.random.default_rng(5).normal(size=(n_items, d)).astype(np.float32)
    model = NearestNeighbors(k=kk).setInputCol("features").fit(pd.DataFrame({"features": list(items)}))
    terms = model._serve_workspace_terms(bucket, 4)
    taken = []

    def knn_topk(qm2, items, tail, xsq, **kw):
        taken.append((qm2.shape[0], kw["block_rows"], kw["block_items"], xsq.shape[1]))
        lists = (qm2.shape[0], distance.TOPK_LANES)
        merged = (qm2.shape[0] // kw["block_rows"] * 8, distance.TOPK_LANES)
        return jnp.zeros(lists, qm2.dtype), jnp.zeros(lists, jnp.int32), jnp.zeros(merged, jnp.int32)

    monkeypatch.setattr(distance, "_pl_knn_topk", knn_topk)
    struct = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    jax.eval_shape(lambda q, it: distance.topk_tile(q, it, None, kk), struct(bucket, d), struct(n_items, d))
    assert _table_key(bucket, n_items, d, False) in planted_table
    (rows, br, bk, norms), = set(taken)
    assert (br, bk) == distance.plan_topk(bucket, n_items, d) and rows % br == 0
    assert terms["topk_lists"] == rows * distance.TOPK_LANES * 8
    assert terms["topk_merged"] == rows // br * 8 * distance.TOPK_LANES * 4
    assert terms["topk_queries"] == rows * d * 4
    assert terms["topk_norms"] == norms * 4 and norms % bk == 0
    assert distance.topk_vmem_bytes(br, bk, d, jnp.float32, False) <= distance.vmem_limit_bytes()


@pytest.mark.parametrize("site", ["assign_argmin", "assign_accumulate", "topk_tile"])
def test_float64_rows_take_the_jnp_form_on_the_compiled_path(monkeypatch, site):
    """Mosaic holds no f64: with the kernels compiled (not interpreted),
    every entry point answers float64 rows with the jnp form, and float32
    rows still reach its kernel. `plan_blocks` itself plans f64 blocks (what
    the interpreter runs, and what the kNN serving term budgets from)."""
    def refuse(*a, **kw):
        raise AssertionError("a kernel was reached")

    for kernel in ("_pl_argmin", "_pl_accumulate", "_pl_knn_topk"):
        monkeypatch.setattr(distance, kernel, refuse)
    X, C, w = _data(40, 6, 8, np.float64)
    call = {
        "assign_argmin": lambda x, c, w: distance.assign_argmin(x, c),
        "assign_accumulate": lambda x, c, w: distance.assign_accumulate(x, w, c),
        "topk_tile": lambda x, c, w: distance.topk_tile(c, x, None, 3),
    }[site]
    monkeypatch.setattr(distance, "_MODE", "jnp")
    want = call(X, C, w)
    monkeypatch.setattr(distance, "_MODE", "pallas")
    assert distance.block_plan(40, 6, 8, np.float64, False) is None
    assert distance.plan_blocks(40, 6, 8, np.float64, False) == (40, 6)
    for got, ref in zip(call(X, C, w), want):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    with pytest.raises(AssertionError, match="a kernel was reached"):
        call(X.astype(jnp.float32), C.astype(jnp.float32), w.astype(jnp.float32))


# ------------------------------------------------- stable kernel names -----
#
# docs/observability.md "Kernel names": every Pallas kernel of the core goes
# to `pallas_call` as `srml_<kernel>_<mode>`, which is what XLA's TPU compiler
# names the Mosaic custom call after — the same on one chip, under shard_map
# and in the predict program. chipbench's per-kernel metrics match on it.


def test_kernel_name_contract():
    assert distance.kernel_name("argmin", True) == "srml_argmin_bf16"
    assert distance.kernel_name("accumulate", False) == "srml_accumulate_f32"
    assert distance.kernel_name("d2_block", False) == "srml_d2_block_f32"
    assert distance.kernel_name("knn_topk", False) == "srml_knn_topk_f32"


def test_kernels_pass_their_names_to_pallas_call(interpret_mode, monkeypatch):
    """Where no TPU compiler is at hand: the names `pallas_call` is given,
    through the interpreter, for the Lloyd tile (both modes), the final
    inertia pass, predict, top-k."""
    from jax.experimental import pallas as pl

    names = []
    real = pl.pallas_call

    def recording(*args, **kwargs):
        names.append(kwargs.get("name"))
        return real(*args, **kwargs)

    monkeypatch.setattr(pl, "pallas_call", recording)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(70, 9)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(5, 9)), jnp.float32)
    w = jnp.ones((70,), jnp.float32)
    distance.assign_accumulate(x, w, c, fast=True)
    assert names == ["srml_argmin_bf16", "srml_accumulate_bf16"]
    del names[:]
    distance.assign_accumulate(x, w, c, fast=False)  # a `high`-precision loop's mode
    assert names == ["srml_argmin_f32", "srml_accumulate_f32"]
    del names[:]
    distance.assign_inertia(x, w, c)  # the final inertia pass: the argmin alone
    assert names == ["srml_argmin_f32"]
    del names[:]
    distance.argmin_assign(x, c)  # the predict program's call
    assert names == ["srml_argmin_f32"]
    del names[:]
    distance.tile_topk(x, x[:7], jnp.ones((70,), jnp.bool_), 3)
    assert names and set(names) == {"srml_knn_topk_f32"}


def test_compiled_kernels_carry_their_names_on_one_chip_and_under_shard_map(monkeypatch):
    """The op names a device trace will show, read from programs compiled
    ahead of time for a v5e (no chip needed): `%srml_<kernel>_<mode>.<n> =
    ... custom-call`, in the one-chip Lloyd step, the predict program and
    the four-chip `shard_map` Lloyd step."""
    import re

    monkeypatch.setenv("TPU_SKIP_MDS_QUERY", "1")
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

    from spark_rapids_ml_tpu.ops.kmeans import _lloyd_step, kmeans_predict
    from spark_rapids_ml_tpu.parallel import mesh as mesh_mod

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    except Exception as e:  # no libtpu on this machine: the test above holds the contract
        pytest.skip(f"no TPU compiler available ahead of time: {type(e).__name__}: {e}")
    monkeypatch.setattr(distance, "_MODE", "pallas")
    dev = topo.devices[0]

    def kernels(compiled):
        return sorted(set(re.findall(r"%(srml_\w+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"", compiled.as_text())))

    def one(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=SingleDeviceSharding(dev))

    with mesh_mod.chip_scope([dev]), jax.enable_x64(False):
        operands = (one((1024, 256)), one((1024,)), one((40, 256)), one((1024,)))
        for fast, mode in ((True, "bf16"), (False, "f32")):
            for in_place in (False, True):  # the kernels keep their names whichever way they get at the tile
                step = _lloyd_step.lower(*operands, mesh=None, batch_rows=512, fast=fast, in_place=in_place).compile()
                assert kernels(step) == [f"srml_accumulate_{mode}", f"srml_argmin_{mode}"]
        assert kernels(kmeans_predict.lower(one((1024, 256)), one((40, 256))).compile()) == ["srml_argmin_f32"]
        mesh = Mesh(np.asarray(topo.devices), (mesh_mod.ROWS_AXIS,))
        rows, rep = P(mesh_mod.ROWS_AXIS), P()
        sh = lambda shape, spec: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=NamedSharding(mesh, spec))
        for in_place in (False, True):
            step = _lloyd_step.lower(sh((4096, 256), rows), sh((4096,), rows), sh((40, 256), rep), sh((4096,), rows),
                                     mesh=mesh, batch_rows=512, fast=True, in_place=in_place).compile()
            assert kernels(step) == ["srml_accumulate_bf16", "srml_argmin_bf16"]


# ------------------------------------------- X's layout under the kernels -----
#
# docs/performance.md "Tiled distance core": the kernels' operands are
# row-major, and a float32 [n, 3000] block is column-major on a TPU unless the
# placement says otherwise (parallel/mesh.py `row_major_format`, asked for by
# KMeans). These compile, for a v5e and with no chip, the Lloyd programs the
# way `kmeans_fit` calls them on an X placed by that path, and read the text.


@pytest.fixture(scope="module")
def v5e():
    """The described v5e:2x2 topology (this module is the one test file that
    loads the TPU's compiler), or a skip where it cannot be described."""
    from jax.experimental import topologies

    env = pytest.MonkeyPatch()
    env.setenv("TPU_SKIP_MDS_QUERY", "1")
    try:
        yield topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    except Exception as e:
        pytest.skip(f"no TPU compiler available ahead of time: {type(e).__name__}: {e}")
    finally:
        env.undo()


@pytest.fixture(scope="module")
def programs():
    """The Lloyd programs' (text, temporary bytes) by what was asked of
    `_lloyd_program_text`: each compiled once for the module's tests."""
    return {}


def _lloyd_program_text(v5e, programs, program, d, x_layout, in_place=False, fast=True, final_pass=False):
    """Compiled text of one Lloyd program at the benchmark cells' shapes
    (`kmeans-p3k`: 393,216 rows on one chip, `tile_1dev`, through the
    meshless `_lloyd_step` and its tile scan; `kmeans-p3k-host4`: 1,048,576
    rows over four chips through the `shard_map` `_lloyd_step`), or with
    `final_pass` of the fit's final pass (`_inertia_pass`, placed the same
    way), with X's struct in the layout `make_global_rows` gives it for
    `x_layout` and the tile access `kmeans_fit` would state for it; the
    tile's and one device's X shape, and the program's temporary bytes."""
    from jax.experimental.layout import Format
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

    from spark_rapids_ml_tpu.ops.kmeans import _inertia_pass, _lloyd_step
    from spark_rapids_ml_tpu.parallel import mesh as mesh_mod

    k, tile = 1000, 32768
    devices = list(v5e.devices) if program == "lloyd_step_4chips" else [v5e.devices[0]]
    rows_dev = 262144 if program == "lloyd_step_4chips" else 393216
    key = (program, d, x_layout, in_place, fast, final_pass)
    if key not in programs:
        placed = None  # what `_place_blocks` asks of each device for its [rows_dev, d] block
        if x_layout == mesh_mod.X_ROW_MAJOR:
            placed = mesh_mod.row_major_format((rows_dev, d), np.float32, devices[0])
        with mesh_mod.chip_scope(devices), jax.enable_x64(False):
            if program == "tile_1dev":
                mesh, one = None, SingleDeviceSharding(devices[0])
                s = lambda shape, sharding=one: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)
                operands = (s((rows_dev, d), placed or one), s((rows_dev,)), s((k, d)), s((rows_dev,)))
            else:
                mesh = Mesh(np.asarray(devices), (mesh_mod.ROWS_AXIS,))
                rows, rep = NamedSharding(mesh, P(mesh_mod.ROWS_AXIS)), NamedSharding(mesh, P())
                x_sharding = rows if placed is None else Format(placed.layout, rows)
                s = lambda shape, sharding: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)
                operands = (s((4 * rows_dev, d), x_sharding), s((4 * rows_dev,), rows), s((k, d), rep), s((4 * rows_dev,), rows))
            if final_pass:
                lowered = _inertia_pass.lower(*operands, mesh=mesh, batch_rows=tile, in_place=in_place)
            else:
                lowered = _lloyd_step.lower(*operands, mesh=mesh, batch_rows=tile, fast=fast, in_place=in_place)
            compiled = lowered.compile()
            programs[key] = compiled.as_text(), compiled.memory_analysis().temp_size_in_bytes
    text, temp_bytes = programs[key]
    return text, (tile, d), (rows_dev, d), temp_bytes


def _copied_shapes(text):
    import re

    return {(int(r), int(c)) for r, c in re.findall(r"= f32\[(\d+),(\d+)\]\{[^}]*\} copy\(", text)}


def _x_entry_layout(text, x_shape):
    import re

    m = re.search(r"entry_computation_layout=\{\(f32\[%d,%d\]\{([\d,]+)" % x_shape, text)
    assert m, "X is not the program's first parameter"
    return m.group(1)


def _kernel_calls(text):
    """(kernel name, operand names) of every Mosaic custom call in `text`."""
    import re

    return re.findall(r"%(srml_\w+?)(?:\.\d+)? = [^\n]*? custom-call\(([^)]*)\), custom_call_target=\"tpu_custom_call\"", text)


@pytest.mark.parametrize("program", ["tile_1dev", "lloyd_step_4chips"])
@pytest.mark.parametrize("d", [3000, 3072])
def test_row_major_x_reaches_the_kernels_without_a_tile_copy(v5e, programs, monkeypatch, program, d):
    """Placed as KMeans asks, X enters the program row-major ({1,0}) and the
    kernels index it where it lies (`tile_access` `in_place`): no value of the
    tile's shape exists in the compiled program, bf16 and float32 loop steps
    alike, no `copy` has X's shape, and both custom calls take as
    their X operand a value of one device's whole X. Sliced (what a ragged
    tile keeps), the row-major tile is written out once and still never
    turned. The control: at d = 3,000 the default layout is column-major and
    its sliced lowering turns every tile (so this test reads the right text)
    and never copies X whole; at d = 3,072 row-major is the default and
    nothing has to be asked."""
    import re

    from spark_rapids_ml_tpu.parallel import mesh as mesh_mod

    monkeypatch.setattr(distance, "_MODE", "pallas")
    for fast, mode in ((True, "bf16"), (False, "f32")):
        text, tile_shape, x_shape, _ = _lloyd_program_text(v5e, programs, program, d, mesh_mod.X_ROW_MAJOR, in_place=True, fast=fast)
        assert _x_entry_layout(text, x_shape) == "1,0"
        assert "f32[%d,%d]" % tile_shape not in text
        assert not _copied_shapes(text) & {tile_shape, x_shape}
        calls = dict(_kernel_calls(text))
        assert sorted(calls) == [f"srml_accumulate_{mode}", f"srml_argmin_{mode}"]
        for operands in calls.values():
            # (prefetched block offset, X, ...): X is the program's parameter on
            # one chip, the tile loop's pass-through of it under shard_map
            x_operand = operands.split(", ")[1]
            assert re.search(r"%s = f32\[%d,%d\]\{1,0[^}]*\} (parameter|get-tuple-element)\(" % ((re.escape(x_operand),) + x_shape), text), x_operand
    sliced, _, _, _ = _lloyd_program_text(v5e, programs, program, d, mesh_mod.X_ROW_MAJOR)
    assert _x_entry_layout(sliced, x_shape) == "1,0"
    assert "f32[%d,%d]" % tile_shape in sliced
    assert not _copied_shapes(sliced) & {tile_shape, x_shape}
    assert "srml_argmin_bf16" in sliced and "srml_accumulate_bf16" in sliced
    asks = mesh_mod.row_major_format(x_shape, np.float32, v5e.devices[0]) is not None
    assert asks == (d % 128 != 0)
    if asks:
        control, _, _, _ = _lloyd_program_text(v5e, programs, program, d, mesh_mod.X_DEFAULT)
        assert _x_entry_layout(control, x_shape) == "0,1"
        assert tile_shape in _copied_shapes(control) and x_shape not in _copied_shapes(control)


@pytest.mark.parametrize("program", ["tile_1dev", "lloyd_step_4chips"])
@pytest.mark.parametrize("d", [3000, 3072])
def test_the_final_pass_runs_the_float32_argmin_alone(v5e, programs, monkeypatch, program, d):
    """The fit's final pass, as the one-chip program and as the four-chip
    `shard_map` program: one Mosaic call, `srml_argmin_f32`, no
    accumulate; it takes X where X lies (row-major, the program's parameter
    or the tile loop's pass-through of it), and no `copy` has the tile's or
    X's shape. On four chips the one value all-reduced is the scalar."""
    import re

    from spark_rapids_ml_tpu.parallel import mesh as mesh_mod

    monkeypatch.setattr(distance, "_MODE", "pallas")
    text, tile_shape, x_shape, _ = _lloyd_program_text(v5e, programs, program, d, mesh_mod.X_ROW_MAJOR, in_place=True, final_pass=True)
    assert _x_entry_layout(text, x_shape) == "1,0"
    assert "f32[%d,%d]" % tile_shape not in text
    assert not _copied_shapes(text) & {tile_shape, x_shape}
    calls = _kernel_calls(text)
    assert [name for name, _ in calls] == ["srml_argmin_f32"]
    x_operand = calls[0][1].split(", ")[1]
    assert re.search(r"%s = f32\[%d,%d\]\{1,0[^}]*\} (parameter|get-tuple-element)\(" % ((re.escape(x_operand),) + x_shape), text), x_operand
    reduced = re.findall(r"= (\w+\[[\d,]*\])\S* all-reduce(?:-start)?\(", text)
    assert reduced == ([] if program == "tile_1dev" else ["f32[]"])


@pytest.mark.parametrize("x_layout", ["row_major", "default"])
@pytest.mark.parametrize("program, fast, final_pass", [
    pytest.param("tile_1dev", True, False, id="step_bf16_1dev"),
    pytest.param("tile_1dev", False, False, id="step_f32_1dev"),
    pytest.param("tile_1dev", False, True, id="final_pass_1dev"),
    pytest.param("lloyd_step_4chips", True, False, id="step_bf16_4chips"),
])
def test_the_lloyd_programs_hold_no_second_x_at_the_cells_shapes(v5e, programs, monkeypatch, program, fast, final_pass, x_layout):
    """The in-program tile scan at the cells' shapes (393,216 x 3,000 float32
    on one device, 4 x 262,144 x 3,000 on four; k = 1,000, 32,768-row
    tiles), X placed row-major as KMeans asks (tiles read in place) or in
    the device's default layout (tiles sliced and turned): no `copy` has
    X's shape, and the program's temporary buffers are under half of one
    device's X. Compiled for a v5e they read 0.016 GiB row-major and 0.742
    GiB in the default layout (one sliced tile and its turned copy), against
    4.39 GiB of X on one device and 2.93 GiB on each of four; a second X
    would read 100 % or more."""
    from spark_rapids_ml_tpu.parallel import mesh as mesh_mod

    monkeypatch.setattr(distance, "_MODE", "pallas")
    in_place = x_layout == mesh_mod.X_ROW_MAJOR
    text, _, x_shape, temp_bytes = _lloyd_program_text(
        v5e, programs, program, 3000, x_layout, in_place=in_place, fast=fast, final_pass=final_pass
    )
    assert _x_entry_layout(text, x_shape) == ("1,0" if in_place else "0,1")
    assert x_shape not in _copied_shapes(text)
    assert temp_bytes < x_shape[0] * x_shape[1] * 4 / 2
