"""The cell `pca-p3k.refit` rehearsed on the CPU at `tiny.py`'s size (4,096 x 32:
the full decomposition is the path that shape calls for): correct, traced and
untraced, with every metric it declares but those that need the chip; the bf16
control and each planted fault not correct through the run's own `correct`;
the work counts against hand arithmetic."""
import json

import numpy as np
import pytest

from chipbench import checks
from chipbench.families import pca

from . import tiny
from .test_faults import break_fit, half_left_out

CELL = "pca-p3k.refit"
# on the CPU the devices report no memory statistics and the program keeps CPU pools out of the persistent cache
NEEDS_THE_CHIP = {"device.peak_hbm_gib", "compile.cache_hit_share"}
NEW = {"solver.gram_s", "solver.eig_s", "solver.eig_iterations", "solver.eig_full_per_fit", "kernel.gram_ms_per_fit",
       "kernel.eig_ms_per_fit", "gram_roofline"}


def pca_class():
    from spark_rapids_ml_tpu.models.feature import PCA

    return PCA


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(trace):
    res = tiny.execute(CELL, seed=2**31 + 11, trace=trace, seconds=0.3)
    json.dumps(res)
    assert res["correct"], res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0 and res["device"]["count"] == 1
    if trace:
        declared = set(tiny.cell_metrics(CELL))
        assert NEW <= declared
        assert declared - set(res["metrics"]) <= NEEDS_THE_CHIP
        assert set(res["metrics"]) <= declared
        m = {k: v["value"] for k, v in res["metrics"].items()}
        assert 0 < m["gram_roofline"] <= 100 and m["kernel.gram_ms_per_fit"] > 0 and m["kernel.eig_ms_per_fit"] > 0
        assert m["solver.eig_full_per_fit"] == 1 and m["solver.eig_iterations"] == 0  # d = 32: the full path, by shape
        assert m["api.ingests_per_fit"] == 0
        parts = m["solver.gram_s"] + m["solver.eig_s"] + m["solver.finish_s"]
        assert 0.5 * m["api.solve_s"] < parts <= m["api.solve_s"]  # at this size 0.4 ms of host steps between the spans show
    else:
        assert set(res["metrics"]) == {"fit_s", "setup_s"} and all(v["value"] > 0 for v in res["metrics"].values())


def test_the_topk_path_at_a_width_that_calls_for_it():
    """d = 64: the block iteration, with its counters read by the cell's metrics."""
    res = tiny.execute(CELL, seed=25, trace=True, d=64)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert res["correct"] and m["solver.eig_full_per_fit"] == 0 and 0 < m["solver.eig_iterations"] < 20


def test_control_is_not_correct():
    res = tiny.execute(CELL, seed=29, control=True)
    assert res["correct"], res["compared"]
    assert not res["control"]["correct"], res["control"]["compared"]
    for name, read in res["faults"].items():  # the faults planted in the reference, by the same limits
        assert not checks.correct({k: (v, res["compared"][k]["limit"]) for k, v in read.items()}), (name, read)


def state_unchanged(fit, inputs, params):
    """The eigensolver returned its start block (here the first k axes) and no variance."""
    attrs = dict(fit(inputs, params))
    k, d = np.asarray(attrs["components_"]).shape
    attrs["components_"] = np.eye(k, d, dtype=np.float32)
    for key in ("explained_variance_", "explained_variance_ratio_", "singular_values_"):
        attrs[key] = np.zeros(k, np.float32)
    return attrs


def answer_altered(fit, inputs, params):
    attrs = dict(fit(inputs, params))
    attrs["components_"] = np.asarray(attrs["components_"]) * 1.05
    return attrs


def variances_altered(fit, inputs, params):
    attrs = dict(fit(inputs, params))
    attrs["explained_variance_"] = np.asarray(attrs["explained_variance_"]) * 1.001
    return attrs


def sign_flipped(fit, inputs, params):
    attrs = dict(fit(inputs, params))
    attrs["components_"] = -np.asarray(attrs["components_"])
    return attrs


def nan_answer(fit, inputs, params):
    attrs = dict(fit(inputs, params))
    attrs["explained_variance_"] = np.asarray(attrs["explained_variance_"]) * np.nan
    return attrs


@pytest.mark.parametrize("fault", [state_unchanged, half_left_out, answer_altered, variances_altered, sign_flipped, nan_answer],
                         ids=lambda f: f.__name__)
def test_fit_fault_is_not_correct(monkeypatch, fault):
    break_fit(monkeypatch, pca_class(), fault)
    res = tiny.execute(CELL, seed=28)
    assert not res["correct"], res["compared"]


def test_a_refit_that_skips_the_gram_fails_the_run(monkeypatch):
    """Retained statistics on: the family refuses before the first fit."""
    from spark_rapids_ml_tpu import checkpoint

    monkeypatch.setattr(checkpoint, "solver_checkpoints_active", lambda: True)
    with pytest.raises(RuntimeError, match="solver checkpoints are on"):
        tiny.execute(CELL, seed=28)


def test_pca_work():
    cfg = {"rows": 393216, "d": 3000, "estimator": {"k": 3}}
    # 2 * 393,216 * 3,000^2 = 7.077888e12 FLOP for the gram
    assert pca.gram_flops(cfg) == 7.077888e12
    # ten iterations and the start block: 11 products of [3000, 3000] x [3000, 16] = 11 * 2.88e8 FLOP
    assert pca.eig_flops(cfg, 10) == 11 * 2.88e8
    work = pca.fit_work(cfg, 10)
    assert work["flops"] == 7.077888e12 + 3.168e9
    assert work["bytes"] == 393216 * 3000 * 4  # one read of float32 X: 4.718592e9 bytes
    # compute-bound on a v5e: 35.9 ms of bf16 FLOP against 5.8 ms for the read
    assert work["flops"] / 197e12 > 6 * work["bytes"] / 819e9
