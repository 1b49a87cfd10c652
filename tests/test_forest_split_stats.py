#
# A regression forest's float32 statistics accumulated exactly: each (w, wy,
# wy²) goes into the one-hot contraction as three bfloat16 pieces
# (`ops.trees.stat_pieces`, `onehot_split`), over the rows sorted by node at
# every level, through XLA's form (`_sorted_histogram`) or the Mosaic kernel
# `srml_hist_accumulate_split_bf16` (`ops/histogram.py`, the Pallas
# interpreter here). Against float64 numpy to float32 rounding; one bfloat16
# piece visibly worse; the plan at the cell `rfr-p3k.refit`'s shape (1,000
# features a node, no scatter); a whole `RandomForestRegressor.fit` against
# `chipbench/families/rfr.py`'s float64 reference node by node; the
# classifier's plan and histograms as they were.
#
import os
import sys

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pandas as pd
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.families import rfr  # noqa: E402
from spark_rapids_ml_tpu import memory, telemetry  # noqa: E402
from spark_rapids_ml_tpu.models.classification import RandomForestClassifier  # noqa: E402
from spark_rapids_ml_tpu.models.regression import RandomForestRegressor  # noqa: E402
from spark_rapids_ml_tpu.ops import distance, histogram, trees  # noqa: E402

ROWS, D = 4096, 512  # m = 512 // 3 = 170 features a node: more than one 128-feature step of the kernel


@pytest.fixture
def telemetry_on():
    was = telemetry.enabled()
    telemetry.enable()
    yield telemetry.registry()
    if not was:
        telemetry.disable()


def operands(n, d, chunk, m, bins, *, seed=0, mean=0.3):
    """A level's operands as `order_rows` hands them over, with a
    regressor's statistics (c, c y, c y²) of a continuous y."""
    rng = np.random.default_rng(seed)
    Xb = np.zeros((n, trees.binned_cols(d)), np.uint8)
    Xb[:, :d] = rng.integers(0, bins, (n, d))
    node = rng.integers(0, chunk, n).astype(np.int32)
    cnt = rng.integers(1, 4, n) * (rng.random(n) < 0.63)
    y = (rng.standard_normal(n) * 1.7 + mean).astype(np.float32)
    stats = np.stack([cnt, cnt * y, cnt * y * y]).astype(np.float32)
    fids = np.stack([rng.permutation(d)[:m] for _ in range(chunk)]).astype(np.int32)
    ordered = jax.jit(trees.order_rows)(jnp.asarray(stats), jnp.asarray(node), jnp.ones(n, bool), 0, chunk)
    return Xb, node, stats, fids, ordered


def float64_histogram(Xb, node, stats, fids, bins):
    chunk, m = fids.shape
    ref = np.zeros((stats.shape[0], chunk, m, bins))
    for c in range(chunk):
        r = np.flatnonzero((node == c) & (stats[0] > 0))
        sub = Xb[r][:, fids[c]]
        for s in range(stats.shape[0]):
            for j in range(m):
                ref[s, c, j] = np.bincount(sub[:, j], weights=stats[s, r].astype(np.float64), minlength=bins)
    return ref


def worst(got, ref):
    """The largest error over a statistic's largest |sum|: float32 rounding reads about 1e-7."""
    return float(np.max(np.abs(got - ref) / np.abs(ref).max(axis=(1, 2, 3), keepdims=True)))


@pytest.mark.parametrize("chunk,bins,mode", [(1, 32, "jnp"), (20, 32, "jnp"), (3, 128, "jnp"), (1, 32, "interpret"), (20, 16, "interpret")],
                         ids=["root", "twenty_nodes", "bins_128", "root_kernel", "three_groups_kernel"])
def test_the_split_form_is_a_float32_sum_of_exact_values(monkeypatch, chunk, bins, mode):
    monkeypatch.setattr(histogram, "TILE_ROWS", 512)
    n, m = 2048, 170
    Xb, node, stats, fids, ordered = operands(n, 400, chunk, m, bins)
    got = np.asarray(jax.jit(lambda X, f, *o: trees._split_sorted_histogram(X, *o, f, bins=bins, kernel="" if mode == "jnp" else mode))(
        jnp.asarray(Xb), jnp.asarray(fids), *ordered))
    assert got.shape == (3, chunk, m, bins) and got.dtype == np.float32
    ref = float64_histogram(Xb, node, stats, fids, bins)
    assert np.array_equal(got[0], ref[0])  # the weights are integers: exact
    assert worst(got, ref) < 2e-6
    # one bfloat16 piece a statistic (the classifier's form) reads the rows' values rounded to 8 bits
    one = np.asarray(jax.jit(lambda X, f, *o: trees._sorted_histogram(X, *o, f, bins=bins))(jnp.asarray(Xb), jnp.asarray(fids), *ordered))
    assert worst(one, ref) > 100 * worst(got, ref) and worst(one, ref) > 1e-4


def test_kernel_and_xla_forms_agree_over_feature_steps(monkeypatch):
    """300 features a node: three of the kernel's 128-feature grid steps, the last one partial."""
    monkeypatch.setattr(histogram, "TILE_ROWS", 512)
    Xb, node, stats, fids, ordered = operands(1536, 700, 9, 300, 16, seed=3)
    args = (jnp.asarray(Xb), jnp.asarray(fids), *ordered)
    xla = np.asarray(jax.jit(lambda X, f, *o: trees._split_sorted_histogram(X, *o, f, bins=16))(*args))
    kernel = np.asarray(jax.jit(lambda X, f, *o: trees._split_sorted_histogram(X, *o, f, bins=16, kernel="interpret"))(*args))
    assert histogram.split_features(300) == 384
    np.testing.assert_allclose(kernel, xla, rtol=0, atol=1e-6 * np.abs(xla).max())
    assert np.array_equal(kernel[0], xla[0])


def test_stat_pieces_are_exact_in_bfloat16_and_sum_back():
    rng = np.random.default_rng(1)
    x = np.concatenate([rng.standard_normal(5000) * 10.0 ** rng.integers(-6, 7, 5000), [0.0, -0.0, 1.0, -3.0, 2.0**-100, 7.0e30]])
    x = x.astype(np.float32)[None, :]
    pieces = np.asarray(trees.stat_pieces(jnp.asarray(x)))
    assert pieces.shape == (3, x.shape[1])
    assert np.array_equal(pieces.astype(ml_dtypes.bfloat16).astype(np.float32), pieces)
    assert np.array_equal(pieces.astype(np.float64).sum(axis=0), x[0].astype(np.float64))
    assert np.array_equal(np.asarray(trees.joined_pieces(jnp.asarray(pieces))), x)


def test_the_cells_plan_takes_no_scatter():
    """1,000 of 3,000 features a node, three statistics, depth 6: every level
    sorted and in pieces, one pass a level; the span's summary says so."""
    plan = trees.level_plan(6, 1000, 128, 3)
    assert [(lv["accumulate"], lv["rows"], lv["stat_pieces"], lv["passes"]) for lv in plan] == [("onehot_split", "sorted", 3, 1)] * 6
    summary = trees.plan_summary(plan)
    assert (summary["stat_pieces"], summary["split_passes"], summary["scatter_passes"], summary["accumulate"]) == (3, 6, 0, "onehot_split")
    assert histogram.takes_split(9, 128) and not histogram.takes_split(9, 256)
    assert trees.window_nodes(histogram.split_features(1000)) == 1
    # what still takes the scatter: float64 statistics, over 256 bins, a level in node chunks
    assert {lv["accumulate"] for lv in trees.level_plan(6, 1000, 128, 3, split_stats=False)} == {"scatter"}
    assert {lv["accumulate"] for lv in trees.level_plan(6, 100, 300, 3)} == {"scatter"}
    assert trees.level_plan(6, 100, 128, 3, node_chunk=8)[5]["accumulate"] == "scatter"


def test_the_classifiers_plan_is_as_it_was():
    """The classifier at `rfc-p3k`'s shape: five levels in place, eight sorted, one-hot of whole counts."""
    plan = trees.level_plan(13, 54, 128, 2, integer_stats=True)
    assert [(lv["accumulate"], lv["rows"], lv["stat_pieces"]) for lv in plan] == \
        [("onehot", "in_place", 1)] * 5 + [("onehot", "sorted", 1)] * 8
    assert trees.plan_summary(plan)["split_passes"] == trees.plan_summary(plan)["scatter_passes"] == 0
    assert trees.window_nodes(54) == trees.WINDOW_NODES == 16


def test_the_final_level_sums_float32_values():
    """The last level's leaves take the rows' float32 statistics as they are
    (a float32 `segment_sum`: the sum of their exact values, as the pieces'
    sums are), whatever form the levels above took."""
    rng = np.random.default_rng(2)
    n, depth = 3000, 3
    y = (rng.standard_normal(n) * 4 + 50).astype(np.float32)  # a mean far from 0
    cnt = rng.integers(0, 3, n).astype(np.float32)
    stats = np.stack([cnt, cnt * y, cnt * y * y]).astype(np.float32)
    node = rng.integers(2**depth - 1, 2 ** (depth + 1) - 1, n).astype(np.int32)
    active = rng.random(n) < 0.8
    nst = jnp.zeros((2 ** (depth + 1) - 1, 3), jnp.float32)
    final = np.asarray(trees._tree_final_level(jnp.asarray(stats), jnp.asarray(node), jnp.asarray(active), nst, depth))
    want = np.zeros((2**depth, 3))
    for c in range(2**depth):
        r = (node == 2**depth - 1 + c) & active
        want[c] = stats[:, r].astype(np.float64).sum(axis=1)
    got = final[2**depth - 1 :]
    assert np.array_equal(got[:, 0], want[:, 0])
    assert np.max(np.abs(got - want) / np.abs(want).max(axis=0)) < 1e-6


@pytest.mark.parametrize("depth", [14, 20])
def test_the_final_level_holds_nothing_of_rows_times_leaves(depth):
    """No array of the last level's program grows with rows x 2**depth: at
    depth 20 such an array over 393,216 rows would be hundreds of GB."""
    n = 4096
    args = (jnp.zeros((3, n), jnp.float32), jnp.zeros(n, jnp.int32), jnp.ones(n, bool),
            jnp.zeros((2 ** (depth + 1) - 1, 3), jnp.float32))
    jaxpr = jax.make_jaxpr(lambda s, nid, act, nst: trees._tree_final_level(s, nid, act, nst, depth))(*args)
    sizes = [int(np.prod(v.aval.shape)) for eqn in jaxpr.jaxpr.eqns for v in eqn.outvars]
    assert max(sizes) <= 3 * 2 ** (depth + 1), max(sizes)


class Data:
    """Seeded rows in the shape the family reads, with a continuous target."""

    def __init__(self, seed, rows=ROWS, d=D):
        rng = np.random.default_rng(seed)
        centers = rng.standard_normal((8, d)).astype(np.float32) * 2
        self.X = (centers[rng.integers(0, 8, rows)] + rng.standard_normal((rows, d))).astype(np.float32)
        coef = (rng.standard_normal(d) / np.sqrt(d)).astype(np.float32)
        self.y = (self.X @ coef + 0.1 * rng.standard_normal(rows)).astype(np.float32).astype(np.float64)
        self.seed, self.rows, self.d = seed, rows, d
        self.frame = pd.DataFrame({"features": list(self.X), "label": self.y})


def config(workers=1, **estimator):
    return {"rows": ROWS, "d": D, "num_workers": workers, "estimator": {"numTrees": 2, "maxDepth": 4, "maxBins": 32, **estimator},
            "check": {"trees": 2, "r2_depth": 2}}


@pytest.mark.parametrize("mode,workers", [("jnp", 1), ("interpret", 1), ("jnp", 4)], ids=["one_device", "one_device_kernel", "four_devices"])
def test_a_regressor_fit_against_the_float64_reference(telemetry_on, monkeypatch, mode, workers):
    """Every node of both checked trees re-derived in float64 from the rows
    the program's splits route there: weights exact, (wy, wy²) to float32
    rounding, each split the reference's best but for near-ties, thresholds
    exact; no pass took the scatter. On four devices each grows its tree on
    its own quarter of the rows, as the reference splits the ensemble."""
    monkeypatch.setattr(distance, "_MODE", mode)
    monkeypatch.setattr(histogram, "TILE_ROWS", 512)
    trees._forest_programs.cache_clear()
    data, cfg = Data(5), config(workers, numTrees=2 * workers)
    model = rfr.estimator(cfg, 11).fit(data.frame)
    trees._forest_programs.cache_clear()
    blocks = [jax.device_put(data.X[i : i + 1024], jax.devices()[0]) for i in range(0, ROWS, 1024)]
    ref = rfr.reference_fit(cfg, data, blocks, 11)
    read = rfr.compare_fit(cfg, rfr.outputs(model), ref, data, blocks)
    assert read["weight_gap"] == read["threshold_gap"] == read["shape_gap"] == 0, read
    # R² over every tree at depth 2: a near-tie at a shallow node of a tree not followed moves it by up to 4e-4
    # at 1,024 rows a device (four_devices)
    assert read["stats_gap"] < 1e-5 and read["gain_gap"] < 1e-5 and read["r2_gap"] < 1e-3, read
    grow = rfr._span(model, "fit/solve/grow")
    assert (grow["accumulate"], grow["stat_pieces"], grow["features_per_node"], grow["kernel_levels"]) == \
        ("onehot_split", 3, 170, 4 if mode == "interpret" else 0)
    counters = model._fit_metrics["counters"]
    assert counters["forest.split_stat_passes"] == counters["forest.row_passes"] == 8 * workers
    assert counters.get("forest.scatter_passes", 0) == 0
    # the control: one bfloat16 piece a statistic in the program's place reads worse by far
    bf16 = rfr.compare_fit(cfg, rfr._planted(cfg, data, blocks, 11, round_stats=rfr._bf16), ref, data, blocks)
    assert bf16["stats_gap"] > 30 * max(read["stats_gap"], 1e-7), (bf16, read)


def test_a_regressor_whose_deep_levels_chunk(telemetry_on, monkeypatch):
    """A plan that mixes the pieces and the scatter: levels 0 and 1 whole and
    in pieces, levels 2 and 3 in chunks of two nodes and scattered (the form
    a deep regressor's levels take past `SEGMENT_BUDGET`: depth 11 and on at
    1,000 features a node). Both forms feed the same trees: against the
    float64 reference node by node, and the counters count each form's
    passes."""
    monkeypatch.setattr(distance, "_MODE", "jnp")
    trees._forest_programs.cache_clear()
    data, cfg = Data(8), config()
    model = rfr.estimator(cfg, 13, {"node_chunk": 2}).fit(data.frame)
    trees._forest_programs.cache_clear()
    blocks = [jax.device_put(data.X[i : i + 1024], jax.devices()[0]) for i in range(0, ROWS, 1024)]
    read = rfr.compare_fit(cfg, rfr.outputs(model), rfr.reference_fit(cfg, data, blocks, 13), data, blocks)
    assert read["weight_gap"] == read["threshold_gap"] == read["shape_gap"] == 0, read
    assert read["stats_gap"] < 1e-5 and read["gain_gap"] < 1e-5 and read["r2_gap"] < 1e-3, read
    assert rfr._span(model, "fit/solve/grow")["accumulate"] == "mixed"
    counters = model._fit_metrics["counters"]
    assert (counters["forest.split_stat_passes"], counters["forest.scatter_passes"]) == (2 * 2, 2 * (2 + 4))
    assert trees.plan_summary(trees.level_plan(14, 1000, 128, 3))["accumulate"] == "mixed"


def test_rows_with_weights_take_the_pieces(telemetry_on):
    data = Data(6, rows=2048)
    est = lambda **kw: RandomForestRegressor(numTrees=2, maxDepth=3, maxBins=16, seed=2, num_workers=1, **kw)
    plain = est().fit(data.frame)
    weighted = est(weightCol="w").fit(data.frame.assign(w=1.0))
    assert rfr._span(weighted, "fit/solve/grow")["accumulate"] == "onehot_split"
    np.testing.assert_array_equal(weighted.feature, plain.feature)
    np.testing.assert_array_equal(weighted.node_stats, plain.node_stats)


def test_a_classifier_fit_takes_no_pieces(telemetry_on):
    rng = np.random.default_rng(7)
    X = rng.standard_normal((2048, 64)).astype(np.float32)
    frame = pd.DataFrame({"features": list(X), "label": (X[:, 0] > 0).astype(float)})
    model = RandomForestClassifier(numTrees=2, maxDepth=5, maxBins=16, seed=2, num_workers=1).fit(frame)
    grow = rfr._span(model, "fit/solve/grow")
    assert (grow["accumulate"], grow["stat_pieces"]) == ("onehot", 1)
    assert model._fit_metrics["counters"].get("forest.split_stat_passes", 0) == 0


def test_the_cells_fit_is_admitted_resident_with_its_pieces():
    class Shape:  # the shapes admission reads, without the 4.7 GB
        n_rows, n_cols, is_sparse, label, weight = 393216, 3000, False, np.zeros(1), None

    est = RandomForestRegressor(numTrees=4, maxDepth=6, maxBins=128)
    terms = est._solver_workspace_terms(393216, 3000, dict(est._solver_params), 4)
    # picked ids [1,024, rows] bfloat16, two copies of nine pieces a row, the deepest level's 4 groups of piece
    # sums as the kernel writes them and rearranged
    assert terms["split_accumulate"] == 393216 * (2 * 1024 + 2 * 9 * 4) + 2 * 4 * 1024 * 128 * 128 * 4
    assert terms["histogram"] == 4 * 3 * 32 * 1000 * 128 * 4
    res = memory.resident_estimate(est, Shape, 1)
    assert res.terms["workspace.split_accumulate"] == terms["split_accumulate"]
    budget = int(15.75 * 2**30 * (1.0 - memory.headroom_fraction()))
    assert res.total() < budget
    # the classifier prices none
    clf = RandomForestClassifier(numTrees=7, maxDepth=13, maxBins=128)
    assert "split_accumulate" not in clf._solver_workspace_terms(393216, 3000, dict(clf._solver_params), 4)


def test_the_regressors_programs_compile_for_a_v5e(monkeypatch):
    """The cell's level programs (393,216 rows of 3,072 uint8 columns, 1,000
    features a node, nine pieces) compiled for a v5e as a TPU process builds
    them: the accumulate is ONE Mosaic call `srml_hist_accumulate_split_bf16`
    beside the picking's loops, every loop of which carries the scope
    `srml_hist_accumulate`, and the row advance takes no per-row gather;
    the temporaries are the picked ids and the piece sums."""
    import re

    monkeypatch.setenv("TPU_SKIP_MDS_QUERY", "1")
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from spark_rapids_ml_tpu.parallel.mesh import ROWS_AXIS

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    except Exception as e:  # no libtpu on this machine: nothing to compile with
        pytest.skip(f"no TPU compiler available ahead of time: {type(e).__name__}: {e}")
    mesh = Mesh(np.asarray(topo.devices[:1]), (ROWS_AXIS,))
    n, d, m, bins, S, depth = 393_216, 3000, 1000, 128, 3, 6
    nodes = 2 ** (depth + 1) - 1

    def struct(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, P(*spec)))

    scalar = lambda dtype: jax.ShapeDtypeStruct((), dtype)
    rows = (struct((n,), jnp.int32, ROWS_AXIS), struct((n,), jnp.bool_, ROWS_AXIS))
    stw = struct((S, n), jnp.float32, None, ROWS_AXIS)
    level_args = (
        struct((n, trees.binned_cols(d)), jnp.uint8, ROWS_AXIS, None), stw, *rows,
        struct((1, nodes), jnp.int32, ROWS_AXIS, None), struct((1, nodes), jnp.int32, ROWS_AXIS, None),
        struct((1, nodes, S), jnp.float32, ROWS_AXIS, None, None), scalar(jnp.uint32), scalar(jnp.int32),
        struct((n,), jnp.int32, ROWS_AXIS), struct((n,), jnp.int32, ROWS_AXIS), stw, struct((1,), jnp.int32, ROWS_AXIS),
    )
    kernel = distance.kernel_name("hist_accumulate_split", True)
    assert kernel == "srml_hist_accumulate_split_bf16" and kernel.startswith(trees.HIST_SCOPE)
    with jax.enable_x64(False), jax.default_matmul_precision("float32"):
        progs = trees._forest_programs(mesh, n, d, S, "float32", 4, depth, bins, m, "variance", 0, False, True, 1.0, 1.0, 0.0, "pallas")
        assert [lv.get("kernel") for lv in progs["plan"]] == ["pallas"] * 6
        compiled = progs["levels"][5].lower(*level_args).compile()
        final = progs["final"].lower(stw, *rows, struct((1, nodes, S), jnp.float32, ROWS_AXIS, None, None)).compile()
    text = compiled.as_text()
    loops = re.findall(r'= [^\n]* while\([^\n]*op_name="([^"]*)"', text)
    calls = re.findall(r'(%[\w.\-]+) = [^\n]* custom-call\([^\n]*custom_call_target="tpu_custom_call"', text)
    assert loops and all(f"/{trees.HIST_SCOPE}/" in name for name in loops), loops
    assert len(calls) == 1 and calls[0].lstrip("%").startswith(kernel), calls
    assert not re.search(rf"= \S+\[{n}\]\S* gather\(", text)  # the row advance: masked reduces over X, no gather a row
    # picked ids 768 MiB, the piece sums 256 MiB, the histogram's few arrays: under 1.5 GiB
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5 * 2**30
    assert final.memory_analysis().temp_size_in_bytes < 64 * 2**20  # the last level: a segment_sum, no [rows, leaves]
