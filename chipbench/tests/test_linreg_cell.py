"""The cell `linreg-p3k.refit` rehearsed on the CPU at `tiny.py`'s size (4,096 x
32, all ten sweeps): correct, traced and untraced, with every metric it declares
but those that need the chip; the bf16 control and each planted fault not
correct through the run's own `correct`; the work counts against hand
arithmetic; `cd_roofline`'s reader on a recorded span."""
import json
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import checks, trace as tracing
from chipbench.families import linreg
from chipbench.readers import cd_roofline

from . import tiny
from .test_faults import answer_altered, break_fit, half_left_out, nan_answer

CELL = "linreg-p3k.refit"
# on the CPU the devices report no memory statistics and the program keeps CPU pools out of the persistent cache
NEEDS_THE_CHIP = {"device.peak_hbm_gib", "compile.cache_hit_share"}
NEW = {"solver.cd_s", "solver.cd_sweeps", "solver.gram_passes_per_fit", "kernel.cd_ms_per_fit", "cd_roofline"}
SHARED_WITH_PCA = {"solver.gram_s", "kernel.gram_ms_per_fit", "gram_roofline", "solver.finish_s"}


def linreg_class():
    from spark_rapids_ml_tpu.models.regression import LinearRegression

    return LinearRegression


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(trace):
    res = tiny.execute(CELL, seed=2**31 + 11, trace=trace, seconds=0.3)
    json.dumps(res)
    assert res["correct"], res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0 and res["device"]["count"] == 1
    if trace:
        declared = set(tiny.cell_metrics(CELL))
        assert NEW | SHARED_WITH_PCA <= declared
        assert declared - set(res["metrics"]) <= NEEDS_THE_CHIP
        assert set(res["metrics"]) <= declared
        m = {k: v["value"] for k, v in res["metrics"].items()}
        assert 0 < m["gram_roofline"] <= 100 and 0 < m["cd_roofline"] <= 100
        assert m["kernel.gram_ms_per_fit"] > 0 and m["kernel.cd_ms_per_fit"] > 0
        assert m["solver.cd_sweeps"] == 10 and m["solver.gram_passes_per_fit"] == 1 and m["api.ingests_per_fit"] == 0
        assert m["compile.window_compiles"] == 0
        parts = m["solver.gram_s"] + m["solver.cd_s"] + m["solver.finish_s"]
        assert 0.5 * m["api.solve_s"] < parts <= m["api.solve_s"]  # at this size the host steps between the spans show
    else:
        assert set(res["metrics"]) == {"fit_s", "setup_s"} and all(v["value"] > 0 for v in res["metrics"].values())


def test_control_and_planted_faults_are_not_correct():
    res = tiny.execute(CELL, seed=29, control=True)
    assert res["correct"], res["compared"]
    assert not res["control"]["correct"], res["control"]["compared"]
    assert set(res["faults"]) == {"state_unchanged", "half_left_out", "a_sweep_left_out"}
    for name, read in res["faults"].items():  # the faults planted in the reference, by the same limits
        assert not checks.correct({k: (v, res["compared"][k]["limit"]) for k, v in read.items()}), (name, read)
    # the count of a left-out sweep is reported as asked: the other numbers catch it
    assert res["faults"]["a_sweep_left_out"]["sweeps_gap"] == 0


def state_unchanged(fit, inputs, params):
    """The descent returned its start: zero coefficients, the intercept y_bar, the count as asked."""
    attrs = dict(fit(inputs, params))
    attrs["coef_"] = np.zeros_like(attrs["coef_"])
    attrs["intercept_"] = float(np.average(np.asarray(inputs.y), weights=np.asarray(inputs.w)))
    return attrs


def a_sweep_left_out(fit, inputs, params):
    """Nine sweeps, reported as ten."""
    return {**fit(inputs, {**params, "max_iter": int(params["max_iter"]) - 1}), "n_iter_": int(params["max_iter"])}


def a_sweep_left_out_that_says_so(fit, inputs, params):
    return fit(inputs, {**params, "max_iter": int(params["max_iter"]) - 1})


def summary_altered(fit, inputs, params):
    attrs = dict(fit(inputs, params))
    attrs["rss_"] = attrs["rss_"] * 1.001
    return attrs


@pytest.mark.parametrize("fault", [state_unchanged, half_left_out, a_sweep_left_out, answer_altered, summary_altered, nan_answer],
                         ids=lambda f: f.__name__)
def test_fit_fault_is_not_correct(monkeypatch, fault):
    break_fit(monkeypatch, linreg_class(), fault)
    monkeypatch.setattr(linreg, "assert_path", lambda model: None)  # the comparison has to catch it, not the path's check
    res = tiny.execute(CELL, seed=28)
    assert not res["correct"], res["compared"]


def test_a_descent_that_stops_short_fails_the_run(monkeypatch):
    """Nine sweeps that say so: the family's path check refuses the fit."""
    break_fit(monkeypatch, linreg_class(), a_sweep_left_out_that_says_so)
    with pytest.raises(RuntimeError, match="does not show 10 sweeps"):
        tiny.execute(CELL, seed=28)


def test_a_refit_that_skips_the_gram_fails_the_run(monkeypatch):
    """Retained statistics on: the family refuses before the first fit."""
    from spark_rapids_ml_tpu import checkpoint

    monkeypatch.setattr(checkpoint, "solver_checkpoints_active", lambda: True)
    with pytest.raises(RuntimeError, match="solver checkpoints are on"):
        tiny.execute(CELL, seed=28)


def test_linreg_work():
    cfg = {"rows": 393216, "d": 3000}
    # 2 * 393,216 * 3,000^2 = 7.077888e12 FLOP for the gram
    assert linreg.gram_flops(cfg) == 7.077888e12
    # ten sweeps, each one read of the 3,000 x 3,000 float32 gram: 10 * 3.6e7 bytes = 360 MB
    assert linreg.cd_bytes(cfg, 10) == 3.6e8
    work = linreg.fit_work(cfg, 10)
    # the gram, X^T y (2 * 393,216 * 3,000 = 2.359296e9) and a multiply-add an entry a sweep (10 * 1.8e7)
    assert work["flops"] == 7.077888e12 + 2.359296e9 + 1.8e8
    assert work["bytes"] == 393216 * 3000 * 4 + 3.6e8  # one read of float32 X: 4.718592e9 bytes, and the gram ten times
    # compute-bound on a v5e: 35.9 ms of bf16 FLOP against 6.2 ms for the reads
    assert work["flops"] / 197e12 > 5 * work["bytes"] / 819e9
    # the sweeps' reads alone are 0.44 ms at the HBM peak
    assert linreg.cd_bytes(cfg, 10) / 819e9 == pytest.approx(0.4396e-3, rel=1e-3)


MS = 1e6  # ns


def a_run_with_cd_spans(device_events, host_spans, sweeps=(10, 10)):
    ev = lambda n, a, b: [n, a * MS, (b - a) * MS]
    events = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [ev(*e) for e in device_events]}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [ev("chipbench/window", 0, 100)] + [ev(*s) for s in host_spans]}]},
    ]}
    return SimpleNamespace(trace_data=tracing.reduce(events), family=linreg, config={"rows": 393216, "d": 3000}, chips=1,
                           outputs=[{"n_iter": s} for s in sweeps], window=SimpleNamespace(calls=len(sweeps)),
                           peaks={"bytes_per_s": 819e9, "flops_per_s": 197e12})


def test_cd_roofline_on_a_recorded_span():
    """One chip, a 100 ms window, two fits: a gram fusion 0-20 and 50-70 under
    `fit/solve/gram`, the descent's while 21-41 and 71-95 under `fit/solve/cd`
    (the second span closes at 93: 2 ms of its while lie outside it)."""
    device = [("fusion.23_fusion", 0, 20), ("while.4_while", 21, 41), ("fusion.23_fusion", 50, 70), ("while.4_while", 71, 95)]
    host = [("fit/solve/gram", 0, 20.5), ("fit/solve/cd", 20.5, 42), ("fit/solve/gram", 50, 70.5), ("fit/solve/cd", 70.5, 93)]
    run = a_run_with_cd_spans(device, host)
    # busy inside the cd spans: 20 + 22 = 42 ms over 2 fits = 21 ms; the least: 3.6e8 B / 819e9 B/s = 0.43956 ms
    assert cd_roofline.read(run) == pytest.approx(100 * 0.43956 / 21, rel=1e-4)
    # a fit of five sweeps needs half the reads
    assert cd_roofline.read(a_run_with_cd_spans(device, host, sweeps=(5, 5))) == pytest.approx(100 * 0.21978 / 21, rel=1e-4)
    # a program without the span (the parent), or a family that counts no such bytes: nothing to read, not 0 %
    assert cd_roofline.read(a_run_with_cd_spans(device, [s for s in host if s[0] != "fit/solve/cd"])) is None
    other = a_run_with_cd_spans(device, host)
    other.family = SimpleNamespace(iterations=linreg.iterations)
    assert cd_roofline.read(other) is None
