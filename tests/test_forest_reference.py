#
# The forest solver against the plain reference of `chipbench/families/rfc.py`
# (numpy on the host, which imports nothing of the program and re-derives
# every node of the program's own trees), by the numbers the benchmark's cell
# `rfc-p3k.refit` is judged by; the planted faults and the lower-precision
# control failing those numbers; the chip's share of the ensemble tied to the
# whole; what a placement keeps (edges, the binned X) and what a refit skips;
# the spans, counters and admission terms of a forest fit.
#
import gc
import os
import sys
import weakref

import jax
import numpy as np
import pandas as pd
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import checks  # noqa: E402
from chipbench.families import rfc  # noqa: E402
from spark_rapids_ml_tpu import core, memory, telemetry  # noqa: E402
from spark_rapids_ml_tpu.models.classification import RandomForestClassifier  # noqa: E402
from spark_rapids_ml_tpu.models.regression import RandomForestRegressor  # noqa: E402
from spark_rapids_ml_tpu.ops import distance, histogram, trees  # noqa: E402

LIMITS = checks.limits("rfc-p3k.refit.tiny")
ROWS, D = 4096, 64


class Data:
    """Seeded rows in the shape the family reads (`chipbench.datagen.Data`'s
    fields): blobs, and a label planted on a few columns so that depth pays."""

    def __init__(self, seed, rows=ROWS, d=D):
        rng = np.random.default_rng(seed)
        centers = rng.standard_normal((8, d)).astype(np.float32) * 2
        self.X = (centers[rng.integers(0, 8, rows)] + rng.standard_normal((rows, d))).astype(np.float32)
        margin = self.X[:, :6] @ rng.standard_normal(6) + 0.5 * rng.standard_normal(rows)
        self.y = (margin > np.median(margin)).astype(np.float64)
        self.seed, self.rows, self.d = seed, rows, d
        self.frame = pd.DataFrame({"features": list(self.X), "label": self.y})


def config(workers=1, **estimator):
    est = {"numTrees": 3, "maxDepth": 6, "maxBins": 16, **estimator}
    return {"rows": ROWS, "d": D, "classes": 2, "num_workers": workers, "estimator": est,
            "check": {"trees": 2, "accuracy_depth": 3}}


def blocks_of(data, rows=1024):
    dev = jax.devices()[0]
    return [jax.device_put(data.X[i : i + rows], dev) for i in range(0, data.rows, rows)]


@pytest.fixture
def telemetry_on():
    was = telemetry.enabled()
    telemetry.enable()
    yield telemetry.registry()
    if not was:
        telemetry.disable()


def within(read):
    return checks.correct({k: (read[k], LIMITS[k]) for k in LIMITS})


def fit_and_compare(cfg, data, seed, **overrides):
    model = rfc.estimator(cfg, seed, overrides or None).fit(data.frame)
    blocks = blocks_of(data)
    ref = rfc.reference_fit(cfg, data, blocks, seed)
    return model, rfc.compare_fit(cfg, rfc.outputs(model), ref, data, blocks), (ref, blocks)


@pytest.mark.parametrize("workers,trees,mode", [(1, 3, "jnp"), (8, 16, "jnp"), (1, 3, "interpret"), (8, 16, "interpret")],
                         ids=["one_device", "eight_devices", "one_device_kernel", "eight_devices_kernel"])
def test_estimator_against_the_reference(telemetry_on, monkeypatch, workers, trees, mode):
    """Every node of two trees re-derived: the counts exact, the split the
    best of its node's subset, the thresholds the reference's edges. In
    `interpret` mode the deepest level's accumulate is `ops.histogram`'s
    kernel, on the eight-device mesh a device's own under `shard_map`."""
    monkeypatch.setattr(distance, "_MODE", mode)
    data = Data(11)
    cfg = config(workers, numTrees=trees)
    for seed in (5, 2**31 + 9):
        model, read, _ = fit_and_compare(cfg, data, seed)
        assert within(read), read
        assert read["counts_gap"] == 0 and read["threshold_gap"] == 0 and read["shape_gap"] == 0
        assert model.feature.shape == (trees, 2**7 - 1)
        grow = rfc._span(model, "fit/solve/grow")
        assert (grow["sorted_levels"], grow["kernel_levels"]) == (1, int(mode == "interpret"))


@pytest.mark.parametrize("node_chunk,passes", [(8, 4 + 2 + 4), (3, 1 + 1 + 2 + 3 + 6 + 11)])
def test_node_chunks_engage_and_change_nothing(telemetry_on, node_chunk, passes):
    """A cap on a pass's nodes: deep levels take several passes over the rows
    (a last chunk that does not divide the level is clamped back), and the
    forest is the one-pass forest."""
    data = Data(12)
    cfg = config()
    whole = rfc.estimator(cfg, 7).fit(data.frame)
    model, read, _ = fit_and_compare(cfg, data, 7, node_chunk=node_chunk)
    assert within(read), read
    grow = rfc._span(model, "fit/solve/grow")
    assert grow["passes_per_tree"] == passes and rfc._span(whole, "fit/solve/grow")["passes_per_tree"] == 6
    np.testing.assert_array_equal(model.feature, whole.feature)
    np.testing.assert_array_equal(model.node_stats, whole.node_stats)


def test_scatter_and_onehot_accumulate_agree(telemetry_on, monkeypatch):
    """The forms of the accumulate give the same forest (integer counts are
    exact in each); rows with weights of their own take the float32
    statistics' exact pieces (`onehot_split`), float64 statistics the scatter."""
    data = Data(13)
    cfg = config()
    onehot = rfc.estimator(cfg, 3).fit(data.frame)
    assert rfc._span(onehot, "fit/solve/grow")["accumulate"] == "onehot"
    monkeypatch.setattr(trees, "ONEHOT_MAX_ROWS", 8)
    trees._forest_programs.cache_clear()
    mixed = rfc.estimator(cfg, 3).fit(data.frame)
    assert rfc._span(mixed, "fit/solve/grow")["accumulate"] == "mixed"
    np.testing.assert_array_equal(mixed.feature, onehot.feature)
    np.testing.assert_array_equal(mixed.node_stats, onehot.node_stats)
    trees._forest_programs.cache_clear()
    weighted = data.frame.assign(w=1.0)
    split = rfc.estimator(cfg, 3, {"weightCol": "w"}).fit(weighted)
    assert rfc._span(split, "fit/solve/grow")["accumulate"] == "onehot_split"
    np.testing.assert_array_equal(split.feature, onehot.feature)
    np.testing.assert_array_equal(split.node_stats, onehot.node_stats)
    trees._forest_programs.cache_clear()
    scatter = rfc.estimator(cfg, 3, {"weightCol": "w", "float32_inputs": False}).fit(weighted)
    assert rfc._span(scatter, "fit/solve/grow")["accumulate"] == "scatter"
    np.testing.assert_array_equal(scatter.feature, onehot.feature)


@pytest.mark.parametrize("window,tile,sorted_levels,mode", [
    (16, 1024, 1, "jnp"), (2, 1000, 4, "jnp"), (5, 256, 3, "jnp"),
    (16, 1024, 1, "interpret"), (2, 512, 4, "interpret"), (5, 256, 3, "interpret"),
])
def test_rows_sorted_by_node_give_the_same_forest(telemetry_on, monkeypatch, window, tile, sorted_levels, mode):
    """Beyond `WINDOW_NODES` nodes a pass the one-hot accumulate visits the
    rows sorted by node, a window of nodes at a time (a tile that spans
    several windows, a last tile clamped back, a window past the level's
    end), in `interpret` mode as `ops.histogram`'s kernel (tiles of `tile`
    rows in chunks of an eighth): the forest is the forest of the rows in
    place, bit for bit, and the reference re-derives every node of it."""
    data = Data(19)
    cfg = config()
    monkeypatch.setattr(distance, "_MODE", mode)
    monkeypatch.setattr(trees, "WINDOW_NODES", 1 << 20)
    trees._forest_programs.cache_clear()
    in_place = rfc.estimator(cfg, 4).fit(data.frame)
    assert rfc._span(in_place, "fit/solve/grow")["sorted_levels"] == 0
    monkeypatch.setattr(trees, "WINDOW_NODES", window)
    monkeypatch.setattr(trees, "SORTED_TILE_ROWS", tile)
    monkeypatch.setattr(histogram, "TILE_ROWS", tile)
    monkeypatch.setattr(histogram, "CHUNK_ROWS", tile // 8)
    trees._forest_programs.cache_clear()
    try:
        model, read, _ = fit_and_compare(cfg, data, 4)
    finally:
        trees._forest_programs.cache_clear()
    grow = rfc._span(model, "fit/solve/grow")
    assert grow["sorted_levels"] == sorted_levels and grow["accumulate"] == "onehot" and grow["passes_per_tree"] == 6
    assert grow["kernel_levels"] == (sorted_levels if mode == "interpret" else 0)
    assert within(read) and read["counts_gap"] == 0, read
    np.testing.assert_array_equal(model.feature, in_place.feature)
    np.testing.assert_array_equal(model.threshold, in_place.threshold)
    np.testing.assert_array_equal(model.node_stats, in_place.node_stats)


FAULTS = ["not_grown", "a_level_left_out", "half_the_features", "half_the_bins", "no_bootstrap", "counts_from_the_level_above"]


@pytest.fixture(scope="module")
def planted():
    """The control and the planted faults, read once (8 bins: root counts over 256, where bfloat16 rounds)."""
    data = Data(14)
    cfg = config(maxBins=8)
    blocks = blocks_of(data)
    ref = rfc.reference_fit(cfg, data, blocks, 21)
    run = type("Run", (), {"config": cfg, "data": data})()
    outs = {"control": rfc.control_fit(run, blocks, 21), **rfc.fault_fits(cfg, data, blocks, 21, 1)}
    return {name: rfc.compare_fit(cfg, out, ref, data, blocks) for name, out in outs.items()}, (cfg, data, ref, blocks)


@pytest.mark.parametrize("fault", ["control"] + FAULTS)
def test_control_and_planted_faults_fail_the_numbers(planted, fault):
    read = planted[0][fault]
    assert not within(read), (fault, read)


def test_what_catches_each_fault(planted):
    reads = planted[0]
    assert set(reads) == {"control", *FAULTS}
    assert reads["control"]["counts_gap"] > 0  # counts over 256 round in bfloat16
    assert reads["a_level_left_out"]["shape_gap"] == 1  # no node of the last level holds rows
    assert reads["half_the_features"]["gain_gap"] > LIMITS["gain_gap"] and reads["half_the_features"]["counts_gap"] == 0
    assert reads["half_the_bins"]["gain_gap"] > LIMITS["gain_gap"] and reads["half_the_bins"]["threshold_gap"] == 0
    assert reads["no_bootstrap"]["counts_gap"] > 0 and reads["counts_from_the_level_above"]["counts_gap"] > 0


def test_the_program_in_the_faults_place_is_sound(planted, telemetry_on):
    cfg, data, _, blocks = planted[1]
    model = rfc.estimator(cfg, 21).fit(data.frame)
    read = rfc.compare_fit(cfg, rfc.outputs(model), rfc.reference_fit(cfg, data, blocks, 21), data, blocks)
    assert within(read), read


def test_an_answer_that_is_not_a_number_or_the_wrong_shape(planted):
    cfg, data, ref, blocks = planted[1]
    out = rfc.fault_fits(cfg, data, blocks, 21, 1)["no_bootstrap"]
    nan = {**out, "node_stats": out["node_stats"] * np.nan}
    assert all(np.isnan(v) for v in rfc.compare_fit(cfg, nan, ref, data, blocks).values())
    short = {**out, "feature": out["feature"][:, :63], "threshold": out["threshold"][:, :63], "node_stats": out["node_stats"][:, :63]}
    assert not within(rfc.compare_fit(cfg, short, ref, data, blocks))
    fewer = {k: (v[:2] if k in ("feature", "threshold", "node_stats") else v) for k, v in out.items()}
    assert rfc.compare_fit(cfg, fewer, ref, data, blocks)["shape_gap"] >= 1


def test_the_share_ties_to_the_deployment(telemetry_on):
    """On an 8-device mesh `numTrees=50` gives device 0 seven trees on its row
    shard, keyed rank * trees_per_dev + round; a one-device fit of 7 trees on
    those rows returns the same seven; the 56 grown are cut to 50."""
    from spark_rapids_ml_tpu.parallel import TpuContext

    data = Data(15)
    edges = trees.quantile_bins(data.X, 16)
    stats = np.zeros((ROWS, 2), np.float32)
    stats[np.arange(ROWS), data.y.astype(int)] = 1.0
    common = dict(n_features=D, max_depth=6, max_bins=16, max_features=8, impurity="gini", integer_stats=True)

    def grow(rows, n_dev, n_trees):
        with TpuContext(0, 1, num_devices=n_dev) as ctx:
            from spark_rapids_ml_tpu.parallel import make_global_rows

            X, w, _ = make_global_rows(ctx.mesh, data.X[:rows])
            st, _, _ = make_global_rows(ctx.mesh, stats[:rows])
            Xb = trees.bin_features(X, edges.astype(np.float32))
            out = trees.forest_fit(Xb, st, w, 77, mesh=ctx.mesh, n_trees=n_trees, **common)
            out.pop("plan")
            return jax.device_get(out)

    whole = grow(ROWS, 8, 50)
    assert whole["feature"].shape[0] == 56  # whole rounds: ceil(50 / 8) = 7 trees a device
    share = grow(ROWS // 8, 1, 7)
    for k in ("feature", "split_bin", "node_stats"):
        np.testing.assert_array_equal(whole[k][0::8], share[k])  # round-major: device 0's trees are 0, 8, 16, ...
    model = RandomForestClassifier(numTrees=50, maxDepth=3, maxBins=8, num_workers=8, seed=1).fit(data.frame)
    assert model.feature.shape[0] == 50 and rfc._span(model, "fit/solve/grow")["trees_grown"] == 56


def test_a_placement_keeps_its_bins(telemetry_on):
    """Inside a scope the second fit bins nothing, another `maxBins` bins
    again, and leaving the scope frees the uint8 X."""
    data = Data(16)
    reg = telemetry_on
    est = lambda seed, bins=16: RandomForestClassifier(numTrees=2, maxDepth=4, maxBins=bins, seed=seed, num_workers=1)
    kept = []
    real = RandomForestClassifier._placement_bins

    def spy(self, inputs, extracted, max_bins):
        out = real(self, inputs, extracted, max_bins)
        kept.append(weakref.ref(out["Xb"]))
        return out

    RandomForestClassifier._placement_bins = spy
    try:
        with core.device_dataset_scope():
            first = est(1).fit(data.frame)
            mark = reg.mark()
            second = est(2).fit(data.frame)
            counters = reg.delta(mark)["counters"]
            assert counters.get("forest.bin_passes", 0) == 0 and counters["fit.device_dataset_reuses"] == 1
            assert rfc._span(first, "fit/solve/bin")["reused"] is False and rfc._span(second, "fit/solve/bin")["reused"] is True
            assert first._fit_metrics["counters"]["forest.bin_passes"] == 1
            rfc.assert_path(first), rfc.assert_path(second)
            other = est(3, bins=8).fit(data.frame)
            assert rfc._span(other, "fit/solve/bin")["reused"] is False and other._fit_metrics["counters"]["forest.bin_passes"] == 1
            assert kept[0]() is kept[1]() and kept[0]() is not kept[2]()
            assert kept[0]().dtype == np.uint8 and kept[0]().shape == (ROWS, trees.binned_cols(D)) == (ROWS, 128)
        del first, second, other
        gc.collect()
        assert all(ref() is None for ref in kept)  # the scope's end freed them with X
        # outside any scope every fit builds its own
        again = est(1).fit(data.frame)
        assert rfc._span(again, "fit/solve/bin")["reused"] is False
    finally:
        RandomForestClassifier._placement_bins = real


@pytest.mark.parametrize("first", ["classifier", "regressor"])
def test_both_kinds_of_forest_on_one_placement(telemetry_on, first):
    """A classifier and a regressor fitted in one scope on one frame, label
    column and `maxBins` share the placement: the bins are the one's and the
    other's, the class set and the per-row statistics are each kind's own,
    in either order."""
    data = Data(21, rows=2048)
    clf = lambda: RandomForestClassifier(numTrees=2, maxDepth=4, maxBins=16, seed=3, num_workers=1).fit(data.frame)
    reg_ = lambda: RandomForestRegressor(numTrees=2, maxDepth=4, maxBins=16, seed=3, num_workers=1).fit(data.frame)
    alone = {"classifier": clf(), "regressor": reg_()}
    with core.device_dataset_scope():
        order = [("classifier", clf), ("regressor", reg_)] if first == "classifier" else [("regressor", reg_), ("classifier", clf)]
        shared = {}
        for kind, fit in order:
            mark = telemetry_on.mark()
            shared[kind] = fit()
            binned = telemetry_on.delta(mark)["counters"].get("forest.bin_passes", 0)
            assert binned == (1 if kind == first else 0)  # the second kind finds the first one's bins
    for kind, model in shared.items():
        np.testing.assert_array_equal(model.feature, alone[kind].feature)
        np.testing.assert_array_equal(model.threshold, alone[kind].threshold)
        np.testing.assert_array_equal(model.node_stats, alone[kind].node_stats)
    assert shared["classifier"].node_stats.shape[2] == 2 and list(shared["classifier"].classes_) == [0.0, 1.0]
    assert shared["regressor"].node_stats.shape[2] == 3 and len(shared["regressor"].classes_) == 0


def test_two_seeds_get_the_same_edges(telemetry_on):
    """The edges are a function of the placed rows and `maxBins` alone: every
    threshold of two fits with two seeds is one of the same edges, which are
    `np.quantile`'s of the fixed sample, bit for bit."""
    data = Data(17, rows=2048)
    edges = trees.quantile_bins(data.X, 16)
    assert edges.dtype == np.float64 and edges.shape == (D, 15)
    np.testing.assert_array_equal(edges, rfc.quantile_edges(data.X, 16))
    qs = np.linspace(0, 1, 17)[1:-1]
    np.testing.assert_array_equal(edges, np.quantile(data.X.astype(np.float64), qs, axis=0).T)
    for seed in (1, 2):
        m = RandomForestClassifier(numTrees=2, maxDepth=4, maxBins=16, seed=seed, num_workers=1).fit(data.frame)
        split = m.feature >= 0
        assert split.sum() > 10
        assert all(thr in edges[f] for f, thr in zip(m.feature[split], m.threshold[split]))
    # a sample is drawn only past 100,000 rows, from a stream that is fixed
    assert np.array_equal(trees.sketch_rows(50), np.arange(50))
    a, b = trees.sketch_rows(300_000), rfc.sketch_rows(300_000)
    assert len(a) == 100_000 and np.array_equal(a, b) and np.all(np.diff(a) > 0)


def test_the_draws_are_the_family_s(telemetry_on):
    """The program's bootstrap counts and feature subsets, drawn on the device,
    are the family's copy's."""
    valid = np.ones(1000, bool)
    for seed, tree in ((3, 0), (2**31 + 5, 6)):
        key = trees.tree_key(np.uint32(seed & 0xFFFFFFFF), tree)
        draws = trees.bootstrap_draws(key, jax.numpy.asarray(valid), 1000)
        np.testing.assert_array_equal(np.asarray(trees.bootstrap_counts(draws, jax.numpy.asarray(valid))),
                                      rfc.bootstrap_counts(seed, tree, 1000))
        fids = trees._feature_subset_ids(jax.random.fold_in(key, 7919 + 3), 8, 200, 14)
        np.testing.assert_array_equal(np.asarray(fids), rfc.node_features(seed, tree, 3, 200, 14))
    # rows without weight are never drawn, and every draw lands on a row
    valid[::3] = False
    counts = np.asarray(trees.bootstrap_counts(trees.bootstrap_draws(key, jax.numpy.asarray(valid), 1000), jax.numpy.asarray(valid)))
    assert counts.sum() == 1000 and not counts[::3].any()


@pytest.mark.parametrize("mode,window,kernel_levels", [("jnp", 16, 0), ("jnp", 4, 0), ("interpret", 4, 2)])
def test_spans_counters_and_one_fetch(telemetry_on, monkeypatch, mode, window, kernel_levels):
    """`forest.kernel_passes` counts the passes `ops.histogram`'s kernel ran:
    none in `jnp` mode whatever the levels that go sorted, trees x sorted
    levels where a kernel mode is on."""
    monkeypatch.setattr(distance, "_MODE", mode)
    monkeypatch.setattr(trees, "WINDOW_NODES", window)
    trees._forest_programs.cache_clear()
    data = Data(18)
    fetches = []
    real_get = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: (fetches.append(1), real_get(x))[1])
    model = RandomForestClassifier(numTrees=3, maxDepth=5, maxBins=16, seed=4, num_workers=1).fit(data.frame)
    assert len(fetches) == 1  # the forest's three arrays in one fetch a fit
    spans = {s["path"]: s for s in model._fit_metrics["spans"]}
    assert {"fit/solve/bin", "fit/solve/grow", "fit/solve/finish"} <= set(spans)
    assert {k: spans["fit/solve/bin"][k] for k in ("rows", "d", "bins", "reused")} == {"rows": ROWS, "d": D, "bins": 16, "reused": False}
    grow = spans["fit/solve/grow"]
    assert {k: grow[k] for k in ("trees", "depth", "bins", "features_per_node", "passes_per_tree", "level_programs", "accumulate")} == \
        {"trees": 3, "depth": 5, "bins": 16, "features_per_node": 8, "passes_per_tree": 5, "level_programs": 15, "accumulate": "onehot"}
    counters = model._fit_metrics["counters"]
    assert (counters["forest.bin_passes"], counters["forest.trees"], counters["forest.levels"], counters["forest.row_passes"]) == (1, 3, 15, 15)
    assert (grow["sorted_levels"], grow["kernel_levels"]) == (2 * (window == 4), kernel_levels)
    assert counters.get("forest.kernel_passes", 0) == 3 * kernel_levels
    trees._forest_programs.cache_clear()
    parts = sum(spans[p]["wall_s"] for p in ("fit/solve/bin", "fit/solve/grow", "fit/solve/finish"))
    assert 0.8 * spans["fit/solve"]["wall_s"] < parts <= spans["fit/solve"]["wall_s"]


def test_a_refit_with_another_seed_compiles_nothing(telemetry_on):
    """The estimator seed is a traced argument of the kept programs."""
    data = Data(19, rows=1024)
    est = lambda seed: RandomForestClassifier(numTrees=2, maxDepth=4, maxBins=16, seed=seed, num_workers=1)
    compiled = []
    listening = [False]
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **_: compiled.append(name) if listening[0] and name.endswith("/backend_compile_duration") else None)
    with core.device_dataset_scope():
        a = est(1).fit(data.frame)
        before = trees._forest_programs.cache_info()
        listening[0] = True
        b = est(2).fit(data.frame)
        listening[0] = False
        after = trees._forest_programs.cache_info()
    assert after.misses == before.misses and after.hits == before.hits + 1 and not compiled
    assert not np.array_equal(a.feature, b.feature)


def test_forest_workspace_terms_analytic():
    # the protocol's classifier on one chip: 393,216 x 3,000 float32, depth 13, 128 bins, 54 features a node
    est = RandomForestClassifier(numTrees=7, maxDepth=13, maxBins=128)
    terms = est._solver_workspace_terms(393216, 3000, dict(est._solver_params), 4)
    assert terms == {
        "binned_X": 393216 * 3072,  # uint8, the columns rounded up to the 128-lane tile
        "bin_tile": 5 * 16666 * 3000 * 4,  # 50,000,000 // 3,000 rows a tile, five 4-byte temporaries
        "histogram": 4 * 2 * 4096 * 54 * 128 * 4,  # the deepest level whole: 226 MB, four arrays of its size
        "row_state": 393216 * (4 + 1 + 2 * 2 * 4),
    }
    # more than 256 bins are int32 ids; a cap on a pass's nodes caps the histogram
    wide = RandomForestClassifier(maxDepth=8, maxBins=300, node_chunk=16)
    t = wide._solver_workspace_terms(1000, 100, dict(wide._solver_params), 8)
    assert t["binned_X"] == 1000 * 128 * 4 and t["histogram"] == 4 * 2 * 16 * 10 * 300 * 4 and t["bin_tile"] == 5 * 1000 * 100 * 4
    # the regressor: three statistics, a third of the features
    reg = RandomForestRegressor(maxDepth=6, maxBins=128)
    t = reg._solver_workspace_terms(1000, 90, dict(reg._solver_params), 4)
    assert t["histogram"] == 4 * 3 * 32 * 30 * 128 * 4 and t["row_state"] == 1000 * (4 + 1 + 2 * 3 * 4)


def test_the_cells_fit_is_admitted_resident():
    """The protocol's shape against one v5e chip's memory: X, its uint8 bins and the deepest histogram fit."""
    from spark_rapids_ml_tpu.data import ExtractedData

    class Shape:  # the shapes admission reads, without the 4.7 GB
        n_rows, n_cols, is_sparse, label, weight = 393216, 3000, False, np.zeros(1), None

    est = RandomForestClassifier(numTrees=7, maxDepth=13, maxBins=128)
    res = memory.resident_estimate(est, Shape, 1)
    assert res.terms["placement.X"] == 393216 * 3000 * 4 and res.terms["workspace.binned_X"] == 393216 * 3072
    budget = int(15.75 * 2**30 * (1.0 - memory.headroom_fraction()))
    assert 6.9e9 < res.total() < budget
    assert ExtractedData  # the real class has these fields
