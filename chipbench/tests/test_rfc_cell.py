"""The cell `rfc-p3k.refit` rehearsed on the CPU at `tiny.py`'s size (4,096 x
32; once with the configuration's own estimator: 7 trees to depth 13 over 128
bins, otherwise 3 trees to depth 6 over 8 bins, where root counts pass 256):
correct, traced and untraced, with every metric it declares but those that
need the chip; the bfloat16 control and each planted fault not correct through
the run's own `correct`; faults planted under a whole rehearsed run; a refit
that bins again failing the run; the work counts against hand arithmetic; the
accumulate's readers on a recorded trace."""
import json
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import checks, trace as tracing
from chipbench.families import rfc
from chipbench.readers import counter_ratio, hist_roofline, op_union_ms_per_fit, span_ms_per_iteration

from . import tiny
from .test_faults import break_fit, half_left_out

CELL = "rfc-p3k.refit"
SMALL = {"numTrees": 3, "maxDepth": 6, "maxBins": 8}
# on the CPU the devices report no memory statistics and the program keeps CPU pools out of the persistent cache
NEEDS_THE_CHIP = {"device.peak_hbm_gib", "compile.cache_hit_share"}
NEW = {"solver.bin_s", "solver.bin_passes_per_fit", "solver.grow_s", "solver.level_ms", "solver.row_passes_per_tree",
       "kernel.hist_ms_per_fit", "hist_roofline"}
SHARED = {"api.solve_s", "api.ingests_per_fit", "api.cold_fit_s", "api.ingest_s", "api.unspanned_share.fit", "solver.finish_s",
          "solve_mfu", "compile.window_compiles", "compile.cache_hit_share", "device.idle_share.fit", "device.peak_hbm_gib"}


def forest_class():
    from spark_rapids_ml_tpu.models.classification import RandomForestClassifier

    return RandomForestClassifier


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(trace):
    """The configuration's own estimator: every level to depth 13 of two of the seven trees re-derived."""
    res = tiny.execute(CELL, seed=2**31 + 11, trace=trace, seconds=0.3)
    json.dumps(res)
    assert res["correct"], res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0 and res["device"]["count"] == 1
    assert res["read"]["counts_gap"] == 0 and res["read"]["threshold_gap"] == 0 and res["read"]["shape_gap"] == 0
    if trace:
        declared = set(tiny.cell_metrics(CELL))
        assert NEW | SHARED == declared
        # on the CPU a trace need not name the accumulate's loops as a TPU's does: the two that read them may be left out
        assert declared - set(res["metrics"]) <= NEEDS_THE_CHIP | {"kernel.hist_ms_per_fit", "hist_roofline"}
        assert set(res["metrics"]) <= declared
        m = {k: v["value"] for k, v in res["metrics"].items()}
        assert m["solver.bin_passes_per_fit"] == 0 and m["solver.bin_s"] < 1e-3 and m["api.ingests_per_fit"] == 0
        assert m["solver.row_passes_per_tree"] == 13 and m["compile.window_compiles"] == 0
        assert m["solver.level_ms"] == pytest.approx(1e3 * m["solver.grow_s"] / (7 * 13))
        assert 0 < m["solve_mfu"] <= 100
        parts = m["solver.bin_s"] + m["solver.grow_s"] + m["solver.finish_s"]
        assert 0.8 * m["api.solve_s"] < parts <= m["api.solve_s"]
    else:
        assert set(res["metrics"]) == {"fit_s", "setup_s"} and all(v["value"] > 0 for v in res["metrics"].values())


def test_control_and_planted_faults_are_not_correct():
    res = tiny.execute(CELL, seed=29, control=True, estimator=SMALL)
    assert res["correct"], res["compared"]
    assert not res["control"]["correct"], res["control"]["compared"]
    assert res["control"]["compared"]["counts_gap"]["value"] > 0  # counts over 256 round in bfloat16
    assert set(res["faults"]) == {"not_grown", "a_level_left_out", "half_the_features", "half_the_bins", "no_bootstrap", "counts_from_the_level_above"}
    for name, read in res["faults"].items():  # the faults planted in the reference, by the same limits
        assert not checks.correct({k: (v, res["compared"][k]["limit"]) for k, v in read.items()}), (name, read)
    # a span that says what was asked for does not hide them: the arrays and the re-derived nodes show each
    assert res["faults"]["a_level_left_out"]["shape_gap"] == 1
    assert res["faults"]["half_the_features"]["shape_gap"] == 0 and res["faults"]["half_the_bins"]["shape_gap"] == 0


def fewer_features(fit, inputs, params):
    """Two features a node where five are asked for."""
    return fit(inputs, {**params, "max_features": "2"})


def a_tree_left_out(fit, inputs, params):
    attrs = dict(fit(inputs, params))
    for k in ("feature", "threshold", "node_stats"):
        attrs[k] = attrs[k][:-1]
    return {**attrs, "num_trees": attrs["num_trees"] - 1}


def counts_altered(fit, inputs, params):
    attrs = dict(fit(inputs, params))
    attrs["node_stats"] = attrs["node_stats"].copy()
    attrs["node_stats"][:, 1, 0] += 1.0
    return attrs


def a_threshold_moved(fit, inputs, params):
    """Every split one ulp off its edge: the model no longer routes as the bins did."""
    attrs = dict(fit(inputs, params))
    attrs["threshold"] = np.where(np.isfinite(attrs["threshold"]), np.nextafter(attrs["threshold"], np.inf), attrs["threshold"])
    return attrs


def the_deepest_level_cut(fit, inputs, params):
    """The last level's splits taken back: its nodes are leaves, nothing lies below them."""
    attrs = dict(fit(inputs, params))
    nodes = attrs["feature"].shape[1]
    last = (nodes + 1) // 4 - 1  # first node of the last level that splits
    attrs["feature"] = attrs["feature"].copy()
    attrs["feature"][:, last:] = -1
    attrs["node_stats"] = attrs["node_stats"].copy()
    parents = (np.arange((nodes - 1) // 2, nodes) - 1) // 2
    attrs["node_stats"][:, (nodes - 1) // 2 :] = attrs["node_stats"][:, parents]  # as `_fill_empty_nodes` leaves them
    return attrs


def nan_counts(fit, inputs, params):
    attrs = dict(fit(inputs, params))
    attrs["node_stats"] = attrs["node_stats"] * np.nan
    return attrs


@pytest.mark.parametrize("fault", [half_left_out, fewer_features, a_tree_left_out, counts_altered, a_threshold_moved,
                                   the_deepest_level_cut, nan_counts], ids=lambda f: f.__name__)
def test_fit_fault_is_not_correct(monkeypatch, fault):
    break_fit(monkeypatch, forest_class(), fault)
    monkeypatch.setattr(rfc, "assert_path", lambda model: None)  # the comparison has to catch it, not the path's check
    res = tiny.execute(CELL, seed=28, estimator=SMALL)
    assert not res["correct"], res["compared"]


def test_a_refit_that_bins_again_fails_the_run(monkeypatch):
    """A program that kept nothing with the placement: the family's path check refuses the window's fit."""
    from spark_rapids_ml_tpu import core

    real = core.FitInputs.__init__

    def forgetful(self, *a, **kw):
        real(self, *a, **kw)
        self.extra = type("Forgets", (dict,), {"__setitem__": lambda s, k, v: None})()

    monkeypatch.setattr(core.FitInputs, "__init__", forgetful)
    with pytest.raises(RuntimeError, match="binned X again"):
        tiny.execute(CELL, seed=28, estimator=SMALL)


def test_rfc_work():
    cfg = {"rows": 393216, "d": 3000, "classes": 2, "estimator": {"numTrees": 7, "maxDepth": 13, "maxBins": 128}}
    assert rfc.features_per_node(cfg) == 54  # int(sqrt(3000))
    # a row of a level: 54 bin ids, a node id, two class statistics, a flag = 54 + 4 + 8 + 1 = 67 bytes
    # 7 trees x 13 levels x 393,216 rows x 67 bytes
    assert rfc.hist_bytes(cfg) == 91 * 393216 * 67 == 2397437952
    assert rfc.hist_bytes(cfg, 13) == 13 * 393216 * 67  # one tree
    work = rfc.fit_work(cfg, 91)
    assert work["bytes"] == 2397437952 and work["flops"] == 91 * 393216 * 54 * 2
    # memory-bound by its count on a v5e: 2.93 ms of reads against 0.02 ms of adds
    assert work["bytes"] / 819e9 == pytest.approx(2.9273e-3, rel=1e-3)
    assert work["bytes"] / 819e9 > 100 * work["flops"] / 197e12
    out = {"feature": np.zeros((7, 2**14 - 1))}
    assert rfc.iterations(out) == 91


MS = 1e6  # ns


def a_run_with_a_trace(device_events, host_spans=(), levels=91, calls=2, counters=None):
    ev = lambda n, a, b: [n, a * MS, (b - a) * MS]
    events = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [ev(*e) for e in device_events]}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [ev("chipbench/window", 0, 100)] + [ev(*s) for s in host_spans]}]},
    ]}
    cfg = {"rows": 393216, "d": 3000, "classes": 2, "estimator": {"numTrees": 7, "maxDepth": 13, "maxBins": 128}}
    window = SimpleNamespace(calls=calls, telemetry={"counters": counters or {}, "spans": [
        {"path": n, "wall_s": (b - a) * 1e-3} for n, a, b in host_spans]})
    return SimpleNamespace(trace_data=tracing.reduce(events), family=rfc, config=cfg, chips=1, window=window,
                           outputs=[{"feature": np.zeros((levels // 13, 2**14 - 1))}] * calls,
                           peaks={"bytes_per_s": 819e9, "flops_per_s": 197e12})


PREFIXES = ["srml_hist_accumulate", "while"]


def test_the_accumulates_readers_on_a_recorded_trace():
    """One chip, a 100 ms window, two fits: the accumulate's row-tile loops
    10-30 and 50-75 with a kernel inside the second (60-70, counted once), a
    sort and a fusion outside them."""
    device = [("sort.3_sort", 0, 10), ("while.7_while", 10, 30), ("fusion.4_fusion", 30, 40),
              ("while.7_while", 50, 75), ("srml_hist_accumulate_bf16.2_tpu_custom_call", 60, 70)]
    run = a_run_with_a_trace(device)
    # the union: 20 + 25 ms over 2 fits
    assert op_union_ms_per_fit.read(run, PREFIXES) == pytest.approx(22.5)
    # the least: 2,397,437,952 B / 819e9 B/s = 2.92727 ms
    assert hist_roofline.read(run, PREFIXES) == pytest.approx(100 * 2.92727 / 22.5, rel=1e-4)
    # a fit of one tree needs a seventh of the reads
    assert hist_roofline.read(a_run_with_a_trace(device, levels=13), PREFIXES) == pytest.approx(100 * 2.92727 / 7 / 22.5, rel=1e-4)
    # a program without such ops, or a family that counts no such bytes: nothing to read, not 0
    bare = a_run_with_a_trace([("sort.3_sort", 0, 10), ("fusion.4_fusion", 30, 40)])
    assert op_union_ms_per_fit.read(bare, PREFIXES) is None and hist_roofline.read(bare, PREFIXES) is None
    other = a_run_with_a_trace(device)
    other.family = SimpleNamespace(iterations=rfc.iterations)
    assert hist_roofline.read(other, PREFIXES) is None


def test_the_span_and_counter_readers():
    spans = [("fit/solve/grow", 0, 26), ("fit/solve/grow", 50, 76)]
    run = a_run_with_a_trace([("while.7_while", 10, 30)], spans, counters={"forest.row_passes": 182.0, "forest.trees": 14.0})
    assert span_ms_per_iteration.read(run, "fit/solve/grow") == pytest.approx(26 / 91)  # 26 ms a fit of 91 levels
    assert counter_ratio.read(run, "forest.row_passes", "forest.trees") == 13
    none = a_run_with_a_trace([("while.7_while", 10, 30)])
    assert span_ms_per_iteration.read(none, "fit/solve/grow") is None and counter_ratio.read(none, "forest.row_passes", "forest.trees") is None
