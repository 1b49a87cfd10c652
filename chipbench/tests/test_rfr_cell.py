"""The cell `rfr-p3k.refit` rehearsed on the CPU at `tiny.py`'s size (4,096 x
32: 10 features a node; once with the configuration's own estimator, 4 trees
to depth 6 over 128 bins, otherwise 2 trees to depth 4 over 16 bins): correct,
traced and untraced, with every metric it declares but those that need the
chip; the one-piece bfloat16 control and each planted fault not correct
through the run's own `correct`; faults planted under a whole rehearsed run;
the driver's continuous target; the work counts against hand arithmetic; a
program whose plan sends a regressor's levels to the scatter refused before
anything is placed."""
import json
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import checks
from chipbench.drivers import fit_loop_target
from chipbench.families import rfr

from . import tiny
from .test_faults import break_fit

CELL = "rfr-p3k.refit"
SMALL = {"numTrees": 2, "maxDepth": 4, "maxBins": 16}
NEEDS_THE_CHIP = {"device.peak_hbm_gib", "compile.cache_hit_share"}
FOREST = {"solver.bin_s", "solver.bin_passes_per_fit", "solver.grow_s", "solver.level_ms", "solver.row_passes_per_tree",
          "kernel.hist_ms_per_fit", "hist_roofline", "solver.hist_kernel_share", "solver.split_stat_share"}


def regressor_class():
    from spark_rapids_ml_tpu.models.regression import RandomForestRegressor

    return RandomForestRegressor


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(trace):
    """The configuration's own estimator: every level to depth 6 of two of the four trees re-derived."""
    res = tiny.execute(CELL, seed=2**31 + 11, trace=trace, seconds=0.3)
    json.dumps(res)
    assert res["correct"], res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0 and res["device"]["count"] == 1
    assert res["read"]["weight_gap"] == 0 and res["read"]["threshold_gap"] == 0 and res["read"]["shape_gap"] == 0
    if trace:
        declared = set(tiny.cell_metrics(CELL))
        assert FOREST <= declared
        # on the CPU a trace need not name the accumulate's loops as a TPU's does: the two that read them may be left out
        assert declared - set(res["metrics"]) <= NEEDS_THE_CHIP | {"kernel.hist_ms_per_fit", "hist_roofline"}
        assert set(res["metrics"]) <= declared
        m = {k: v["value"] for k, v in res["metrics"].items()}
        assert m["solver.bin_passes_per_fit"] == 0 and m["api.ingests_per_fit"] == 0
        assert m["solver.row_passes_per_tree"] == 6 and m["compile.window_compiles"] == 0
        assert m["solver.split_stat_share"] == 1.0 and m["solver.hist_kernel_share"] == 1.0  # the kernel through the interpreter
        assert m["solver.level_ms"] == pytest.approx(1e3 * m["solver.grow_s"] / (4 * 6))
        assert 0 < m["solve_mfu"] <= 100
    else:
        assert set(res["metrics"]) == {"fit_s", "setup_s"} and all(v["value"] > 0 for v in res["metrics"].values())


def test_control_and_planted_faults_are_not_correct():
    res = tiny.execute(CELL, seed=29, control=True, estimator=SMALL)
    assert res["correct"], res["compared"]
    assert not res["control"]["correct"], res["control"]["compared"]
    assert res["control"]["compared"]["stats_gap"]["value"] > 10 * res["compared"]["stats_gap"]["value"]
    assert set(res["faults"]) == {"not_grown", "a_level_left_out", "half_the_features", "no_bootstrap"}
    for name, read in res["faults"].items():  # the faults planted in the reference, by the same limits
        assert not checks.correct({k: (v, res["compared"][k]["limit"]) for k, v in read.items()}), (name, read)
    assert res["faults"]["a_level_left_out"]["shape_gap"] == 1 and res["faults"]["not_grown"]["shape_gap"] == 4


def fewer_features(fit, inputs, params):
    """Two features a node where ten are asked for."""
    return fit(inputs, {**params, "max_features": "2"})


def stats_altered(fit, inputs, params):
    """Each node's wy off by a part in 10^4 of its scale: a float sum that lost digits."""
    attrs = dict(fit(inputs, params))
    s = attrs["node_stats"].copy()
    s[..., 1] += 1e-4 * np.sqrt(s[..., 0] * s[..., 2])
    return {**attrs, "node_stats": s}


def a_threshold_moved(fit, inputs, params):
    attrs = dict(fit(inputs, params))
    attrs["threshold"] = np.where(np.isfinite(attrs["threshold"]), np.nextafter(attrs["threshold"], np.inf), attrs["threshold"])
    return attrs


def nan_stats(fit, inputs, params):
    attrs = dict(fit(inputs, params))
    return {**attrs, "node_stats": attrs["node_stats"] * np.nan}


@pytest.mark.parametrize("fault", [fewer_features, stats_altered, a_threshold_moved, nan_stats], ids=lambda f: f.__name__)
def test_fit_fault_is_not_correct(monkeypatch, fault):
    break_fit(monkeypatch, regressor_class(), fault)
    res = tiny.execute(CELL, seed=28, estimator=SMALL)
    assert not res["correct"], res["compared"]


def test_a_program_that_plans_the_scatter_is_refused_before_anything_is_placed(monkeypatch):
    """The parent of PR 40 sends every level of a regressor to the scatter: the family's builder ends the run."""
    from spark_rapids_ml_tpu.ops import trees

    real = trees.level_plan
    monkeypatch.setattr(trees, "level_plan", lambda *a, **kw: [dict(lv, accumulate="scatter") for lv in real(*a, **kw)])
    with pytest.raises(SystemExit, match="scatter"):
        tiny.execute(CELL, seed=28, estimator=SMALL)


def test_the_drivers_target():
    """gen_data.py's form on the rows: continuous, from --seed, the same for the same seed, in the frame too."""
    cell, config, traffic = tiny.cell_files(CELL)
    assert traffic["driver"] == "fit_loop_target" and config["data"]["target"] == {**config["data"]["target"], "recipe": "linear", "noise": 0.1}
    rng = np.random.default_rng(0)
    X = rng.standard_normal((3000, 40)).astype(np.float32)
    data = SimpleNamespace(seed=2**31 + 77, d=40, rows=3000, X=X)
    y = fit_loop_target.target(config, data)
    assert y.dtype == np.float64 and np.array_equal(y, y.astype(np.float32))
    assert np.array_equal(y, fit_loop_target.target(config, data))
    assert not np.array_equal(y, fit_loop_target.target(config, SimpleNamespace(**{**vars(data), "seed": 5})))
    assert len(np.unique(y)) == 3000  # not a label of a few values
    # y = X coef + 0.1 noise with |coef|^2 about 1: the rows' variance, and a tenth of noise
    coef = np.linalg.lstsq(X.astype(np.float64), y, rcond=None)[0]
    assert 0.07 < np.std(y - X @ coef) < 0.13 and 0.6 < np.sum(coef**2) < 1.5
    # the run replaces the label of the frame the fit reads, before the set-up's cold fit
    ran = []
    frame = {"label": np.zeros(3000)}
    run = SimpleNamespace(config=config, data=SimpleNamespace(**vars(data), y=np.zeros(3000), frame=frame), note=lambda s: None)
    real = fit_loop_target.fit_loop.run
    try:
        fit_loop_target.fit_loop.run = lambda r: ran.append(np.array(r.data.frame["label"]))
        fit_loop_target.run(run)
    finally:
        fit_loop_target.fit_loop.run = real
    assert np.array_equal(ran[0], y) and np.array_equal(run.data.y, y)


def test_rfr_work():
    cfg = {"rows": 393216, "d": 3000, "estimator": {"numTrees": 4, "maxDepth": 6, "maxBins": 128}}
    assert rfr.features_per_node(cfg) == 1000  # 3000 // 3
    # a row of a level: 1,000 bin ids, a node id, three float32 statistics, a flag = 1,000 + 4 + 12 + 1 = 1,017 bytes
    assert rfr.hist_bytes(cfg) == 24 * 393216 * 1017 == 9597616128
    work = rfr.fit_work(cfg, 24)
    assert work["bytes"] == 9597616128 and work["flops"] == 24 * 393216 * 1000 * 3
    # memory-bound by its count on a v5e: 11.7 ms of reads against 0.14 ms of adds
    assert work["bytes"] / 819e9 == pytest.approx(11.7187e-3, rel=1e-4)
    assert rfr.iterations({"feature": np.zeros((4, 2**7 - 1))}) == 24
