"""The comparison that decides `correct`, shown to fail: the control (the
lower precision in the program's place) and, with the timed path broken
underneath a whole rehearsed run, each fault a cell can have."""
import dataclasses

import jax
import numpy as np
import pytest

from . import tiny

FIT_CELLS = [c for c in tiny.CELLS if c.endswith(".refit")]
TRANSFORM_CELLS = [c for c in tiny.CELLS if c.endswith(".transform")]


def estimator_class(name):
    family = tiny.cell_files(name)[1]["family"]
    if family == "kmeans":
        from spark_rapids_ml_tpu.models.clustering import KMeans as cls
    else:
        from spark_rapids_ml_tpu.models.classification import LogisticRegression as cls
    return cls, family


def break_fit(monkeypatch, cls, fault):
    """Wrap the estimator's solver call: fault(fit, inputs, params) -> attrs."""
    real = cls._get_tpu_fit_func

    def patched(self, extracted):
        fit = real(self, extracted)
        return lambda inputs, params: fault(fit, inputs, params)

    monkeypatch.setattr(cls, "_get_tpu_fit_func", patched)


def state_unchanged(fit, inputs, params):
    """Every step returns its state: the init comes back, the count as asked."""
    attrs = fit(inputs, {**params, "max_iter": 0})
    return {**attrs, "n_iter_": int(params["max_iter"])}


def half_left_out(fit, inputs, params):
    """Half of the rows carry no weight: sums and means over the rest."""
    n = inputs.w.shape[0]
    keep = (np.arange(n) < n // 2).astype(np.asarray(inputs.w).dtype)
    w = jax.device_put(np.asarray(inputs.w) * keep, inputs.w.sharding)
    return fit(dataclasses.replace(inputs, w=w), params)


def answer_altered(fit, inputs, params):
    attrs = dict(fit(inputs, params))
    key = "cluster_centers_" if "cluster_centers_" in attrs else "coef_"
    attrs[key] = np.asarray(attrs[key]) * 1.05
    return attrs


def nan_answer(fit, inputs, params):
    """An overflow or a division by nought somewhere in the solve: the answer is not a number."""
    attrs = dict(fit(inputs, params))
    for key in ("cluster_centers_", "coef_", "inertia_", "objective_"):
        if key in attrs:
            attrs[key] = np.asarray(attrs[key]) * np.nan
    return attrs


def test_a_reading_that_is_not_a_number_is_never_within_its_limit():
    from chipbench import checks

    assert checks.worse(0.0, float("nan")) != checks.worse(0.0, float("nan"))  # NaN survives the fold
    assert checks.worse(float("nan"), 1.0) != checks.worse(float("nan"), 1.0)
    assert checks.worse(0.5, 0.2) == 0.5 and checks.worse(0.2, 0.5) == 0.5
    assert not checks.correct({"a": (float("nan"), 1.0)}) and not checks.correct({"a": (float("inf"), 1.0)})
    assert checks.correct({"a": (0.0, 0.0), "b": (0.5, 1.0)}) and not checks.correct({"a": (1e-9, 0.0)})


def test_sound_runs_are_correct():
    for name in tiny.CELLS:
        res = tiny.execute(name, seed=25)
        assert res["correct"], (name, res["compared"])


@pytest.mark.parametrize("name", tiny.CELLS)
def test_control_is_not_correct(name):
    """The control in the program's place, judged by the harness's own `correct`."""
    res = tiny.execute(name, seed=29, control=True)
    assert res["correct"], res["compared"]
    assert not res["control"]["correct"], res["control"]["compared"]


@pytest.mark.parametrize("fault", [state_unchanged, half_left_out, answer_altered, nan_answer], ids=lambda f: f.__name__)
@pytest.mark.parametrize("name", FIT_CELLS)
def test_fit_fault_is_not_correct(monkeypatch, name, fault):
    cls, _ = estimator_class(name)
    break_fit(monkeypatch, cls, fault)
    res = tiny.execute(name, seed=28)
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("name", [c for c in FIT_CELLS if tiny.find_cell(c)["chips"] > 1])
def test_exchange_left_out_is_not_correct(monkeypatch, name):
    """The all-reduce of the sharded Lloyd step left out: each chip keeps its own sums."""
    jax.clear_caches()
    monkeypatch.setattr(jax.lax, "psum", lambda x, axis_name, **kw: x)
    try:
        res = tiny.execute(name, seed=29)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert not res["correct"], res["compared"]


@pytest.mark.parametrize("fault", [nan_answer, answer_altered], ids=lambda f: f.__name__)
@pytest.mark.parametrize("name", TRANSFORM_CELLS)
def test_transform_with_a_broken_model_is_not_correct(monkeypatch, name, fault):
    """The set-up's fit is at fault: the window scores with centres that are
    not numbers, or that are not the reference's."""
    cls, _ = estimator_class(name)
    break_fit(monkeypatch, cls, fault)
    res = tiny.execute(name, seed=29)
    assert not res["correct"], res["compared"]


def test_transform_with_collapsed_centres_is_not_correct(monkeypatch):
    """All centres equal: every row's regret is 0, so the model has to be held too."""
    name = TRANSFORM_CELLS[0]
    cls, _ = estimator_class(name)

    def collapsed(fit, inputs, params):
        attrs = dict(fit(inputs, params))
        c = np.asarray(attrs["cluster_centers_"])
        attrs["cluster_centers_"] = np.broadcast_to(c.mean(axis=0), c.shape).copy()
        return attrs

    break_fit(monkeypatch, cls, collapsed)
    res = tiny.execute(name, seed=29)
    assert not res["correct"], res["compared"]
    assert res["compared"]["regret_max"]["value"] == 0.0 and res["compared"]["centers_degenerate"]["value"] > 0


@pytest.mark.parametrize("fault", ["altered", "half_left_out"])
@pytest.mark.parametrize("name", TRANSFORM_CELLS)
def test_transform_fault_is_not_correct(monkeypatch, name, fault):
    from spark_rapids_ml_tpu.models.clustering import KMeansModel

    real = KMeansModel.transform

    def broken(self, dataset, *a, **kw):
        out = real(self, dataset, *a, **kw).copy()
        pred = out["prediction"].to_numpy().copy()
        if fault == "altered":
            pred[::97] = (pred[::97] + 1) % self.cluster_centers_.shape[0]
        else:
            pred[len(pred) // 2:] = 0  # the second half of the batch never scored
        out["prediction"] = pred
        return out

    monkeypatch.setattr(KMeansModel, "transform", broken)
    res = tiny.execute(name, seed=29)
    assert not res["correct"], res["compared"]
