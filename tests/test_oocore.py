#
# Out-of-core streaming fit tests: the memory-safety acceptance suite
# (docs/robustness.md "Memory safety"). Streaming fits must MATCH resident
# fits to rtol 1e-9 (dense + padded-ELL, all four out-of-core solvers), the
# double-buffer overlap must be telemetry-visible, demotion must be counted
# and stamped, and the whole OOM conversion ladder — injected budget, fake
# RESOURCE_EXHAUSTED at placement/solve, resume-from-checkpoint on the
# streaming path — must end in a completed fit or a typed HbmBudgetError,
# never a raw backend error.
#
import json
import os
import subprocess
import sys

import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu import core as core_mod
from spark_rapids_ml_tpu import telemetry
from spark_rapids_ml_tpu.errors import HbmBudgetError, IngestValidationError
from spark_rapids_ml_tpu.linalg import SparseVector
from spark_rapids_ml_tpu.models.classification import LogisticRegression
from spark_rapids_ml_tpu.models.clustering import KMeans
from spark_rapids_ml_tpu.models.feature import PCA
from spark_rapids_ml_tpu.models.regression import LinearRegression
from spark_rapids_ml_tpu.parallel import chaos

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

_MEM_KEYS = (
    "hbm_budget_bytes", "hbm_headroom_fraction", "stream_chunk_rows",
    "checkpoint_every_iters", "validate_ingest",
)


@pytest.fixture
def tele():
    telemetry.enable()
    telemetry.registry().reset()
    saved = {k: core_mod.config[k] for k in _MEM_KEYS}
    yield telemetry
    core_mod.config.update(saved)
    chaos.clear_fault_plan()
    telemetry.disable()
    telemetry.registry().reset()


def _budget(budget, chunk=512):
    core_mod.config["hbm_budget_bytes"] = budget
    core_mod.config["stream_chunk_rows"] = chunk if budget else 0


def _reg_df(rng, n=2000, d=6):
    x = rng.normal(size=(n, d))
    y = x @ rng.normal(size=d) + 0.5 + 0.05 * rng.normal(size=n)
    return pd.DataFrame({"features": list(x), "label": y})


def _cls_df(rng, n=2000, d=6, k=2):
    x = rng.normal(size=(n, d))
    if k == 2:
        y = (x @ rng.normal(size=d) > 0).astype(np.float64)
    else:
        y = rng.integers(0, k, size=n).astype(np.float64)
    return pd.DataFrame({"features": list(x), "label": y})


def _sparse_rows(rng, n=1500, d=20):
    x = rng.normal(size=(n, d))
    x = np.where(np.abs(x) > 1.0, x, 0.0)
    rows = [
        SparseVector(d, np.nonzero(r)[0].astype(np.int32), r[np.nonzero(r)[0]])
        for r in x
    ]
    return x, rows


def _assert_streamed(model, counters):
    adm = model._fit_metrics["admission"]
    assert adm["verdict"] == "stream"
    assert adm["chunk_rows"] >= 1 and adm["reason"]
    assert counters.get("fit.demotions") == 1
    return adm


# ----------------------------------------------------- parity: dense --------


def test_linear_streaming_matches_resident(tele, rng):
    df = _reg_df(rng)
    est = lambda: LinearRegression(regParam=0.001, float32_inputs=False).setFeaturesCol("features")  # noqa: E731
    _budget(None)
    res = est().fit(df)
    tele.registry().reset()
    _budget(12_000)
    stream = est().fit(df)
    snap = tele.snapshot()
    _assert_streamed(stream, snap["counters"])
    np.testing.assert_allclose(stream.coef_, res.coef_, rtol=1e-9)
    np.testing.assert_allclose(stream.intercept_, res.intercept_, rtol=1e-9)
    # the double-buffer overlap acceptance: 2000 rows / 512-row chunks = 4
    # chunks, 3 of which were dispatched during a predecessor's compute
    assert snap["gauges"]["ingest.overlap_fraction"] == pytest.approx(0.75)
    assert snap["counters"]["ingest.stream_chunks"] >= 4


@pytest.mark.parametrize("family_k", [2, 3], ids=["binomial", "multinomial"])
def test_logistic_streaming_matches_resident(tele, rng, family_k):
    df = _cls_df(rng, k=family_k)
    est = lambda: LogisticRegression(regParam=0.01, float32_inputs=False).setFeaturesCol("features")  # noqa: E731
    _budget(None)
    res = est().fit(df)
    tele.registry().reset()
    _budget(12_000)
    stream = est().fit(df)
    _assert_streamed(stream, tele.snapshot()["counters"])
    np.testing.assert_allclose(
        np.asarray(stream.coef_), np.asarray(res.coef_), rtol=1e-9
    )
    np.testing.assert_allclose(
        np.asarray(stream.intercept_), np.asarray(res.intercept_), rtol=1e-9
    )


def test_pca_streaming_matches_resident(tele, rng):
    df = pd.DataFrame({"features": list(rng.normal(size=(2000, 6)))})
    est = lambda: PCA(k=3, float32_inputs=False).setInputCol("features")  # noqa: E731
    _budget(None)
    res = est().fit(df)
    tele.registry().reset()
    _budget(12_000)
    stream = est().fit(df)
    _assert_streamed(stream, tele.snapshot()["counters"])
    np.testing.assert_allclose(
        np.asarray(stream.components_), np.asarray(res.components_), rtol=1e-9
    )
    np.testing.assert_allclose(
        np.asarray(stream.explained_variance_),
        np.asarray(res.explained_variance_),
        rtol=1e-9,
    )


def test_kmeans_streaming_matches_resident(tele, rng):
    df = pd.DataFrame({"features": list(rng.normal(size=(2000, 6)))})
    est = lambda: KMeans(k=4, seed=7, maxIter=15, float32_inputs=False).setFeaturesCol("features")  # noqa: E731
    _budget(None)
    res = est().fit(df)
    tele.registry().reset()
    _budget(16_000)
    stream = est().fit(df)
    _assert_streamed(stream, tele.snapshot()["counters"])
    np.testing.assert_allclose(stream.cluster_centers_, res.cluster_centers_, rtol=1e-9)


# ------------------------------------------------- parity: padded ELL -------


def test_linear_streaming_matches_resident_ell(tele, rng):
    x, rows = _sparse_rows(rng)
    y = x @ rng.normal(size=x.shape[1]) + 0.1 * rng.normal(size=len(x))
    df = pd.DataFrame({"features": rows, "label": y})
    est = lambda: LinearRegression(  # noqa: E731
        regParam=0.001, float32_inputs=False, enable_sparse_data_optim=True
    ).setFeaturesCol("features")
    _budget(None)
    res = est().fit(df)
    tele.registry().reset()
    _budget(30_000)
    stream = est().fit(df)
    _assert_streamed(stream, tele.snapshot()["counters"])
    np.testing.assert_allclose(stream.coef_, res.coef_, rtol=1e-9)
    np.testing.assert_allclose(stream.intercept_, res.intercept_, rtol=1e-9)


def test_logistic_streaming_matches_resident_ell(tele, rng):
    x, rows = _sparse_rows(rng)
    y = (x @ rng.normal(size=x.shape[1]) > 0).astype(np.float64)
    df = pd.DataFrame({"features": rows, "label": y})
    est = lambda: LogisticRegression(  # noqa: E731
        regParam=0.01, float32_inputs=False, enable_sparse_data_optim=True
    ).setFeaturesCol("features")
    _budget(None)
    res = est().fit(df)
    tele.registry().reset()
    _budget(30_000)
    stream = est().fit(df)
    _assert_streamed(stream, tele.snapshot()["counters"])
    np.testing.assert_allclose(
        np.asarray(stream.coef_), np.asarray(res.coef_), rtol=1e-9
    )
    np.testing.assert_allclose(
        np.asarray(stream.intercept_), np.asarray(res.intercept_), rtol=1e-9
    )


# ----------------------------------------------------- typed failures -------


def test_overbudget_even_streaming_raises_typed_error(tele, rng):
    _budget(1_000)
    with pytest.raises(HbmBudgetError) as ei:
        LinearRegression(float32_inputs=False).setFeaturesCol("features").fit(
            _reg_df(rng)
        )
    # the failure names WHAT doesn't fit — never a raw XLA error
    assert ei.value.largest_term == "stream.chunk_buffers"
    assert "stream.chunk_buffers" in str(ei.value)
    assert ei.value.estimate_bytes > ei.value.capacity_bytes


def test_l1_logistic_demotion_refuses_typed(tele, rng):
    # OWL-QN has no out-of-core form: a demoted L1 fit fails TYPED at the
    # solver gate, not with a shape/attribute error from a half-built path
    _budget(12_000)
    with pytest.raises(HbmBudgetError, match="OWL-QN"):
        LogisticRegression(
            regParam=0.01, elasticNetParam=1.0, float32_inputs=False
        ).setFeaturesCol("features").fit(_cls_df(rng))


# ------------------------------------------------------- OOM ladder ---------


def test_oom_at_placement_converts_and_streams(tele, rng):
    df = _reg_df(rng)
    base = LinearRegression(regParam=0.001, float32_inputs=False).setFeaturesCol(
        "features"
    ).fit(df)
    tele.registry().reset()
    core_mod.config["stream_chunk_rows"] = 512
    chaos.set_fault_plan("oom:stage=placement")
    model = LinearRegression(regParam=0.001, float32_inputs=False).setFeaturesCol(
        "features"
    ).fit(df)
    snap = tele.snapshot()
    assert model._fit_metrics["admission"]["verdict"] == "stream"
    assert model._fit_metrics["admission"]["reason"].startswith("backend OOM")
    assert snap["counters"]["memory.oom_caught"] == 1
    np.testing.assert_allclose(model.coef_, base.coef_, rtol=1e-9)


def test_oom_mid_solve_resumes_on_streaming_path(tele, rng):
    # a RESOURCE_EXHAUSTED at a solver checkpoint boundary: the conversion
    # ladder must finish the fit on the streaming path FROM THE CHECKPOINT
    # (restores == 1), matching an uninterrupted fit to rtol 1e-9
    df = pd.DataFrame({"features": list(rng.normal(size=(2000, 6)))})
    est = lambda: KMeans(  # noqa: E731
        k=4, seed=7, maxIter=12, tol=1e-12, float32_inputs=False
    ).setFeaturesCol("features")
    base = est().fit(df)
    tele.registry().reset()
    core_mod.config["stream_chunk_rows"] = 512
    core_mod.config["checkpoint_every_iters"] = 3
    chaos.set_fault_plan("oom:stage=solve:round=6")
    model = est().fit(df)
    snap = tele.snapshot()
    assert model._fit_metrics["admission"]["verdict"] == "stream"
    assert snap["counters"]["memory.oom_caught"] == 1
    assert snap["counters"]["checkpoint.restores"] == 1
    np.testing.assert_allclose(model.cluster_centers_, base.cluster_centers_, rtol=1e-9)


def test_unstreamable_estimator_oom_raises_typed(tele, rng):
    # an estimator with no out-of-core path: the caught backend OOM becomes
    # the typed permanent error (no silent second resident attempt)
    df = _reg_df(rng)
    chaos.set_fault_plan("oom:stage=placement")
    est = LinearRegression(float32_inputs=False).setFeaturesCol("features")
    est._supports_streaming_fit = False
    with pytest.raises(HbmBudgetError, match="backend out-of-memory"):
        est.fit(df)


# ------------------------------------------------ streaming semantics -------


def test_streamed_dataset_not_cached_in_scope(tele, rng):
    # a demoted fit has no HBM placement to reuse: the DeviceDataset cache
    # must not retain it, and a later fit re-budgets from scratch
    df = _reg_df(rng)
    _budget(12_000)
    with core_mod.device_dataset_scope():
        LinearRegression(regParam=0.001, float32_inputs=False).setFeaturesCol(
            "features"
        ).fit(df)
        snap = tele.snapshot()["counters"]
        assert snap.get("fit.device_dataset_builds") is None
        LinearRegression(regParam=0.002, float32_inputs=False).setFeaturesCol(
            "features"
        ).fit(df)
        snap = tele.snapshot()["counters"]
        assert snap.get("fit.device_dataset_reuses") is None
        assert snap.get("fit.demotions") == 2


def test_streaming_validation_names_column_and_row(tele, rng):
    # the per-row-block NaN scan: the bad row is named with its ABSOLUTE
    # index even though validation ran chunk by chunk inside the pipeline
    df = _reg_df(rng)
    feats = np.stack(df["features"].to_numpy())
    feats[1400, 2] = np.nan
    df = pd.DataFrame({"features": list(feats), "label": df["label"]})
    _budget(12_000)
    core_mod.config["validate_ingest"] = True
    with pytest.raises(IngestValidationError) as ei:
        LinearRegression(float32_inputs=False).setFeaturesCol("features").fit(df)
    assert "features" in str(ei.value)
    assert "1400" in str(ei.value)


def test_resident_validation_still_eager(tele, rng):
    # the resident path keeps the fit-entry full scan (deferral is an
    # implementation detail of the driver, not a behavior change)
    df = _reg_df(rng, n=300)
    feats = np.stack(df["features"].to_numpy())
    feats[42, 0] = np.inf
    df = pd.DataFrame({"features": list(feats), "label": df["label"]})
    core_mod.config["validate_ingest"] = True
    with pytest.raises(IngestValidationError, match="42"):
        LinearRegression(float32_inputs=False).setFeaturesCol("features").fit(df)


def test_memory_watermark_sampled_at_chunk_boundaries(tele, rng):
    # stream_place_blocks samples record_device_memory() once per chunk
    # boundary; on CPU there are no stats, so the pinned contract here is
    # the counter pair every streamed pass must leave behind
    df = _reg_df(rng)
    _budget(12_000)
    LinearRegression(regParam=0.001, float32_inputs=False).setFeaturesCol(
        "features"
    ).fit(df)
    counters = tele.snapshot()["counters"]
    assert counters["ingest.stream_chunks"] == 4
    assert counters["ingest.stream_rows"] == 2000


# ------------------------------------------- subprocess harness (env) -------


def _run_worker(mode, tmp_path, plan):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["SRML_FAULT_PLAN"] = plan
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    out = str(tmp_path / f"{mode}.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "oom_worker.py"), mode, out],
        env=env, capture_output=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stdout.decode() + proc.stderr.decode()
    with open(out) as f:
        return json.load(f)


def test_subprocess_oom_injection_demotes_at_fit_entry(tmp_path):
    # THE acceptance scenario: a chaos `oom` budget injection at fit entry
    # completes the fit via demotion with fit.demotions == 1, and the model
    # matches the clean resident fit the same process runs once the plan is
    # spent
    result = _run_worker("demote", tmp_path, "oom:budget=16000")
    assert result["error"] is None, result
    assert result["admission_faulted"]["verdict"] == "stream"
    assert result["admission_clean"]["verdict"] == "resident"
    assert result["counters"]["fit.demotions"] == 1
    assert result["max_rel_center_diff"] < 1e-9
    assert result["gauges"]["ingest.overlap_fraction"] > 0


def test_subprocess_oom_mid_recovery_resumes_streaming(tmp_path):
    # THE mid-recovery acceptance scenario: attempt 0 checkpoints and dies on
    # a transient; the recovery attempt's RE-placement OOMs (round=1 = the
    # retry attempt index) — the fit must still complete, resumed from the
    # attempt-0 checkpoint ON THE STREAMING PATH, matching an uninterrupted
    # fit
    result = _run_worker(
        "midrecovery", tmp_path, "fail:stage=solve;oom:stage=placement:round=1"
    )
    assert result["error"] is None, result
    assert result["admission_faulted"]["verdict"] == "stream"
    assert result["admission_faulted"]["reason"].startswith("backend OOM")
    c = result["counters"]
    assert c["fit.retries"] == 1
    assert c["memory.oom_caught"] == 1
    assert c["checkpoint.restores"] >= 1
    assert c["fit.demotions"] == 1
    assert result["max_rel_center_diff"] < 1e-9
