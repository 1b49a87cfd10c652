#
# The forest's row advance (`ops.trees.advance_rows`): the masked reduces
# over the binned X that every uint8 level runs, against the per-row gathers
# that a level over 256 bins keeps: the rows' children and flags BIT FOR BIT.
#
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu import telemetry
from spark_rapids_ml_tpu.models.classification import RandomForestClassifier
from spark_rapids_ml_tpu.models.regression import RandomForestRegressor
from spark_rapids_ml_tpu.ops import trees

DEPTH = 13
M = 2 ** (DEPTH + 1) - 1


def a_level(n, cols, level, *, seed=0, leaves=0.1, inactive=0.2, padding=0):
    """A grown tree's state before the advance of `level`: the nodes above
    split, a share of the level's nodes leaves (`feature` -1), a share of
    the rows inactive (each at a leaf of a level above), the last `padding`
    rows zero bins at the level's first node, as a device's padding rows
    are (they stay active: their statistics are 0)."""
    rng = np.random.default_rng(seed)
    d = cols - 7  # the binned X's columns past the features are 0 and never picked
    Xb = np.zeros((n, cols), np.uint8)
    Xb[:, :d] = rng.integers(0, 256, (n, d))
    nodes, offset = 1 << level, (1 << level) - 1
    feature, split_bin = np.full(M, -1, np.int32), np.zeros(M, np.int32)
    feature[:offset] = rng.integers(0, d, offset)
    feature[offset:offset + nodes] = np.where(rng.random(nodes) < leaves, -1, rng.integers(0, d, nodes))
    split_bin[:offset + nodes] = rng.integers(0, 256, offset + nodes)
    node_id = (offset + rng.integers(0, nodes, n)).astype(np.int32)
    active = rng.random(n) >= inactive
    if offset:
        leaf = rng.integers(0, offset, n)
        feature[np.unique(leaf[~active])] = -1
        node_id = np.where(active, node_id, leaf).astype(np.int32)
    if padding:
        Xb[n - padding:] = 0
        node_id[n - padding:], active[n - padding:] = offset, True
    return [jnp.asarray(a) for a in (Xb, node_id, active, feature, split_bin)], offset, nodes


def gathers(Xb, node_id, active, feature, split_bin, offset, nodes):
    return trees.advance_rows(Xb, node_id, active, feature, split_bin, offset, nodes, masked=False)


def masked(Xb, node_id, active, feature, split_bin, offset, nodes):
    return trees.advance_rows(Xb, node_id, active, feature, split_bin, offset, nodes, masked=True)


CASES = {
    # name: (rows, columns, level, a_level's keywords)
    "one_node": (2048, 384, 0, {}),
    "32_nodes": (2048, 384, 5, {}),
    "4096_nodes": (4096, 256, 12, {}),
    "3072_columns": (1024, 3072, 5, {}),
    "every_node_a_leaf": (1024, 256, 5, {"leaves": 1.0}),
    "no_row_active": (1024, 256, 5, {"inactive": 1.0}),
    "padding_rows": (1024, 256, 5, {"padding": 200}),
    "an_odd_row_count": (1203, 256, 5, {}),
    "8192_nodes_tables_gathered": (8 * 1024, 128, 13, {}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_masked_advance_gives_the_gathers_children_bit_for_bit(case):
    n, cols, level, kw = CASES[case]
    args, offset, nodes = a_level(n, cols, level, **kw)
    assert (nodes <= trees.MASKED_ADVANCE_NODES) == (case != "8192_nodes_tables_gathered")
    want = jax.jit(lambda *a: gathers(*a, offset, nodes))(*args)
    got = jax.jit(lambda *a: masked(*a, offset, nodes))(*args)
    assert got[0].dtype == jnp.int32 and got[1].dtype == jnp.bool_
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])
    node_id, active = np.asarray(args[1]), np.asarray(args[2])
    went = np.asarray(want[1])
    assert not went[~active].any() and (np.asarray(want[0])[~went] == node_id[~went]).all()
    if case in ("every_node_a_leaf", "no_row_active"):
        assert not went.any()
    else:
        assert went.any() and set(np.asarray(want[0])[went] - 2 * node_id[went]) == {1, 2}  # both children reached


def test_the_masked_advance_under_shard_map_on_four_devices():
    """A device's own rows, its operands typed as the level programs type
    them (`check_vma` on): the children of the whole."""
    from jax import shard_map
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from spark_rapids_ml_tpu.parallel.mesh import ROWS_AXIS

    args, offset, nodes = a_level(4 * 1216, 256, 6, padding=64)
    mesh = Mesh(np.asarray(jax.devices()[:4]), (ROWS_AXIS,))
    rows = P(ROWS_AXIS)
    step = jax.jit(shard_map(
        lambda *a: masked(*a, offset, nodes), mesh=mesh,
        in_specs=(P(ROWS_AXIS, None), rows, rows, P(), P()), out_specs=(rows, rows),
    ))
    got, want = step(*args), gathers(*args, offset, nodes)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


def test_which_levels_take_the_masked_advance():
    """`level_plan` says `masked` wherever the bin ids are uint8 (at most 256
    bins), at every depth; over 256 bins (an int32 X, four times the bytes)
    the gathers."""
    assert {lv["advance"] for lv in trees.level_plan(13, 54, 128, 2, integer_stats=True)} == {"masked"}
    assert {lv["advance"] for lv in trees.level_plan(20, 54, 256, 3)} == {"masked"}
    assert {lv["advance"] for lv in trees.level_plan(6, 54, 257, 2, integer_stats=True)} == {"gather"}
    summary = trees.plan_summary(trees.level_plan(6, 1000, 128, 3))
    assert (summary["advance"], summary["masked_advances"]) == ("masked", 6)
    assert trees.plan_summary(trees.level_plan(6, 54, 300, 3))["masked_advances"] == 0


@pytest.fixture
def telemetry_on():
    was = telemetry.enabled()
    telemetry.enable()
    yield telemetry.registry()
    if not was:
        telemetry.disable()


def _frame(seed=23, rows=2048, d=40):
    import pandas as pd

    rng = np.random.default_rng(seed)
    X = (rng.standard_normal((8, d))[rng.integers(0, 8, rows)] * 2 + rng.standard_normal((rows, d))).astype(np.float32)
    y = X[:, :5] @ rng.standard_normal(5)
    return pd.DataFrame({"features": list(X), "label": (y > np.median(y)).astype(np.float64), "target": y})


@pytest.mark.parametrize("kind", ["classifier", "regressor"])
def test_a_forest_grown_with_the_masked_advance_is_the_gathers_forest(telemetry_on, monkeypatch, kind):
    """A whole CPU fit two ways: the advance as the masked reduces (what a
    uint8 X takes), as the gathers (the plan's `advance` set to them, all
    else alike): the same trees, array for array. The `grow` span's
    `advance` and the counter `forest.masked_advances` (trees x levels) say
    which ran."""
    frame = _frame()
    depth, n_trees = 6, 2
    plan_of = trees.level_plan

    def fit(form):
        monkeypatch.setattr(trees, "level_plan", lambda *a, **k: [dict(lv, advance=form) for lv in plan_of(*a, **k)])
        trees._forest_programs.cache_clear()
        cls, label = (RandomForestClassifier, "label") if kind == "classifier" else (RandomForestRegressor, "target")
        est = cls(numTrees=n_trees, maxDepth=depth, maxBins=32, seed=7, num_workers=1)
        try:
            model = est.setFeaturesCol("features").setLabelCol(label).fit(frame)
        finally:
            trees._forest_programs.cache_clear()
        grow = next(s for s in model._fit_metrics["spans"] if s["path"] == "fit/solve/grow")
        return model, grow["advance"], model._fit_metrics["counters"].get("forest.masked_advances", 0)

    model, form, advances = fit("masked")
    assert (form, advances) == ("masked", n_trees * depth)
    other, form, advances = fit("gather")
    assert (form, advances) == ("gather", 0)
    np.testing.assert_array_equal(other.feature, model.feature)
    np.testing.assert_array_equal(other.threshold, model.threshold)
    np.testing.assert_array_equal(other.node_stats, model.node_stats)
    assert (model.feature >= 0).sum() > n_trees * 8  # trees that grew past their first levels
