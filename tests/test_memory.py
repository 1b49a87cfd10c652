#
# HBM admission-budgeter unit tests (spark_rapids_ml_tpu/memory.py): every
# estimate formula pinned against an ANALYTICALLY computed byte count — the
# budgeter's contract is exact, simple arithmetic, so the tests do the same
# arithmetic independently and demand equality, not tolerance. CPU backend
# throughout (no capacity information -> the verdict ladder is driven by the
# `hbm_budget_bytes` override / chaos-injected budgets, exactly as documented).
#
import numpy as np
import pytest
import scipy.sparse as sp

from spark_rapids_ml_tpu import core as core_mod
from spark_rapids_ml_tpu import memory
from spark_rapids_ml_tpu.data import ExtractedData
from spark_rapids_ml_tpu.errors import HbmBudgetError
from spark_rapids_ml_tpu.models.classification import LogisticRegression
from spark_rapids_ml_tpu.models.clustering import KMeans
from spark_rapids_ml_tpu.models.feature import PCA
from spark_rapids_ml_tpu.models.regression import LinearRegression


@pytest.fixture
def clean_config():
    keys = ("hbm_budget_bytes", "hbm_headroom_fraction", "stream_chunk_rows")
    saved = {k: core_mod.config[k] for k in keys}
    yield core_mod.config
    core_mod.config.update(saved)


def _dense_extracted(n=1000, d=12, label=True, dtype=np.float64):
    rng = np.random.default_rng(0)
    return ExtractedData(
        features=rng.normal(size=(n, d)).astype(dtype),
        label=rng.normal(size=n).astype(dtype) if label else None,
        feature_names=["features"],
    )


def _sparse_extracted(n=600, d=40, label=True, dtype=np.float64):
    rng = np.random.default_rng(1)
    csr = sp.random(n, d, density=0.1, format="csr", random_state=2, dtype=dtype)
    return ExtractedData(
        features=csr,
        label=rng.normal(size=n).astype(dtype) if label else None,
        feature_names=["features"],
    )


# ------------------------------------------------------------- formulas -----


def test_rows_per_device_pads_to_multiple():
    assert memory.rows_per_device(1000, 8) == 125
    assert memory.rows_per_device(1001, 8) == 126  # 1001 -> 1008 pad
    assert memory.rows_per_device(7, 8) == 1
    assert memory.rows_per_device(0, 8) == 0
    assert memory.rows_per_device(5, 1) == 5


def test_dense_placement_terms_analytic():
    ex = _dense_extracted(n=1000, d=12)
    terms = memory.placement_terms(ex, np.float64, 8)
    rows_dev = 125
    assert terms["placement.X"] == rows_dev * 12 * 8
    assert terms["placement.y"] == rows_dev * 8
    assert terms["placement.w"] == rows_dev * 8
    assert set(terms) == {"placement.X", "placement.y", "placement.w"}


def test_dense_placement_terms_unsupervised_no_label():
    ex = _dense_extracted(n=1000, d=12, label=False)
    terms = memory.placement_terms(ex, np.float64, 8)
    assert "placement.y" not in terms


def test_ell_placement_terms_include_padding():
    ex = _sparse_extracted(n=600, d=40)
    csr = ex.features
    k_max = int(np.diff(csr.indptr).max())
    assert k_max >= 2  # the padded-ELL point of the test
    terms = memory.placement_terms(ex, np.float64, 8)
    rows_dev = memory.rows_per_device(600, 8)
    # the padding cells are REAL placed bytes: rows_dev * k_max, not nnz
    assert terms["placement.ell_values"] == rows_dev * k_max * 8
    assert terms["placement.ell_indices"] == rows_dev * k_max * 4
    assert terms["placement.y"] == rows_dev * 8
    assert terms["placement.w"] == rows_dev * 8


def test_row_bytes_dense_and_ell():
    ex = _dense_extracted(n=100, d=12)
    # d feature doubles + label + weight
    assert memory.row_bytes(ex, np.float64) == 12 * 8 + 8 + 8
    exs = _sparse_extracted()
    k_max = int(np.diff(exs.features.indptr).max())
    assert memory.row_bytes(exs, np.float64) == k_max * (4 + 8) + 8 + 8


def test_memory_estimate_largest_names_dominant_term():
    est = memory.MemoryEstimate({"a": 10, "b": 300, "c": 2})
    assert est.total() == 312
    assert est.largest() == ("b", 300)
    assert memory.MemoryEstimate({}).largest() == ("", 0)


# ---------------------------------------------------- workspace hooks -------


def test_linear_workspace_terms_analytic():
    est = LinearRegression(float32_inputs=False)
    terms = est._solver_workspace_terms(125, 12, dict(est._solver_params), 8)
    assert terms == {"gram": 12 * 12 * 8, "vectors": 4 * 12 * 8}


@pytest.mark.parametrize("d,eigensolve", [
    (12, 12 * 12),  # k + p = 16 columns is over d / 4: the full decomposition's d x d eigenvector output
    (3000, 6 * 3000 * 16),  # the block iteration: a handful of [d, 16] arrays, no d x d output
])
def test_pca_workspace_terms_analytic(d, eigensolve):
    est = PCA(k=3, float32_inputs=False)
    terms = est._solver_workspace_terms(125, d, dict(est._solver_params), 8)
    assert terms == {"covariance": d * d * 8, "eigensolve": eigensolve * 8, "vectors": 2 * d * 8}


def test_kmeans_workspace_terms_analytic():
    est = KMeans(k=5, float32_inputs=False)
    terms = est._solver_workspace_terms(125, 12, dict(est._solver_params), 8)
    # b = min(max_samples_per_batch, rows_dev) = 125; the predict-side
    # assignment tile is min(distance_tile_rows, rows_dev) = 125 rows
    assert terms == {
        "tile_buffers": 2 * 125 * 5 * 8,
        "centers": 2 * 5 * 12 * 8,
        "predict_tile": 125 * 5 * 8,
    }
    # huge shard: the fit tile caps at max_samples_per_batch, the predict
    # tile at config["distance_tile_rows"] (default 4096)
    terms = est._solver_workspace_terms(10**6, 12, dict(est._solver_params), 8)
    assert terms["tile_buffers"] == 2 * 32768 * 5 * 8
    assert terms["predict_tile"] == 4096 * 5 * 8


def test_kmeans_predict_tile_term_tracks_config():
    # the predict-side term follows the distance_tile_rows knob — the
    # admission estimate and the transform-path tiling cannot drift apart
    saved = core_mod.config["distance_tile_rows"]
    core_mod.config["distance_tile_rows"] = 512
    try:
        est = KMeans(k=5, float32_inputs=False)
        terms = est._solver_workspace_terms(10**6, 12, dict(est._solver_params), 8)
        assert terms["predict_tile"] == 512 * 5 * 8
    finally:
        core_mod.config["distance_tile_rows"] = saved


def test_logistic_workspace_terms_analytic():
    est = LogisticRegression(float32_inputs=False)
    terms = est._solver_workspace_terms(125, 12, dict(est._solver_params), 8)
    n_flat = 12 * 1 + 1
    assert terms == {
        "glm_logits": 2 * 125 * 1 * 8,
        "lbfgs_history": 2 * 10 * n_flat * 8,
    }
    # explicit multinomial family: documented k_out floor of 2
    est_m = LogisticRegression(family="multinomial", float32_inputs=False)
    terms_m = est_m._solver_workspace_terms(125, 12, dict(est_m._solver_params), 8)
    assert terms_m["glm_logits"] == 2 * 125 * 2 * 8
    assert terms_m["lbfgs_history"] == 2 * 10 * (12 * 2 + 2) * 8


def test_workspace_estimate_prefixes_and_streaming_rows():
    ex = _dense_extracted(n=1000, d=12)
    est = LogisticRegression(float32_inputs=False)
    ws = memory.workspace_estimate(est, ex, 8)
    assert set(ws.terms) == {"workspace.glm_logits", "workspace.lbfgs_history"}
    assert ws.terms["workspace.glm_logits"] == 2 * 125 * 8
    # streaming evaluates row-scaling terms at the CHUNK shard
    stream = memory.streaming_estimate(est, ex, 8, chunk_rows=256)
    chunk_dev = memory.rows_per_device(256, 8)
    rb = memory.row_bytes(ex, np.float64)
    assert stream.terms["stream.chunk_buffers"] == 2 * chunk_dev * rb
    assert stream.terms["workspace.glm_logits"] == 2 * chunk_dev * 8
    # ...while the history term is row-count independent
    assert (
        stream.terms["workspace.lbfgs_history"]
        == ws.terms["workspace.lbfgs_history"]
    )


def test_resident_estimate_is_placement_plus_workspace():
    ex = _dense_extracted(n=1000, d=12)
    est = LinearRegression(float32_inputs=False)
    res = memory.resident_estimate(est, ex, 8)
    placement = memory.placement_terms(ex, np.float64, 8)
    ws = memory.workspace_estimate(est, ex, 8)
    assert res.total() == sum(placement.values()) + ws.total()


# ------------------------------------------------------------ admission -----


class _FakeDevice:
    def __init__(self, ids):
        import numpy as _np

        self.devices = _np.array(ids)


class _FakeCtx:
    def __init__(self, n_dev=8, is_spmd=False):
        self.mesh = _FakeDevice(list(range(n_dev)))
        self.is_spmd = is_spmd


def test_admit_resident_when_no_capacity_information(clean_config):
    ex = _dense_extracted()
    dec = memory.admit_fit(LinearRegression(float32_inputs=False), ex, _FakeCtx())
    assert dec.verdict == memory.RESIDENT
    assert dec.budget_bytes is None
    assert dec.reason == "no capacity information"


def test_admit_applies_headroom_fraction(clean_config):
    ex = _dense_extracted(n=1000, d=12)
    est = LinearRegression(float32_inputs=False)
    need = memory.resident_estimate(est, ex, 8).total()
    clean_config["hbm_headroom_fraction"] = 0.25
    # budget = cap * 0.75: a capacity of need/0.75 + eps admits, below demotes
    clean_config["hbm_budget_bytes"] = int(need / 0.75) + 8
    dec = memory.admit_fit(est, ex, _FakeCtx())
    assert dec.verdict == memory.RESIDENT
    # hand the first admission's shared-ledger claim back (core's fit driver
    # does this in its finally) so the second admission sees a clean book
    memory.release_admission(dec)
    clean_config["hbm_budget_bytes"] = int(need / 0.75) - 8
    assert memory.admit_fit(est, ex, _FakeCtx()).verdict == memory.STREAM


def test_admit_demotes_and_sizes_chunks(clean_config):
    ex = _dense_extracted(n=1000, d=12)
    est = LinearRegression(float32_inputs=False)
    need = memory.resident_estimate(est, ex, 8).total()
    clean_config["hbm_budget_bytes"] = need  # headroom 0.1 -> budget < need
    dec = memory.admit_fit(est, ex, _FakeCtx())
    assert dec.verdict == memory.STREAM and dec.demoted
    assert dec.chunk_rows >= 1
    assert dec.estimate.total() <= dec.budget_bytes
    stamp = dec.stamp()
    assert stamp["verdict"] == "stream" and stamp["chunk_rows"] == dec.chunk_rows


def test_admit_honors_configured_chunk_rows(clean_config):
    ex = _dense_extracted(n=1000, d=12)
    est = LinearRegression(float32_inputs=False)
    clean_config["hbm_budget_bytes"] = memory.resident_estimate(est, ex, 8).total()
    clean_config["stream_chunk_rows"] = 300
    assert memory.admit_fit(est, ex, _FakeCtx()).chunk_rows == 300


def test_admit_raises_typed_when_even_streaming_cannot_fit(clean_config):
    ex = _dense_extracted(n=1000, d=12)
    est = LinearRegression(float32_inputs=False)
    clean_config["hbm_budget_bytes"] = 1000
    with pytest.raises(HbmBudgetError) as ei:
        memory.admit_fit(est, ex, _FakeCtx())
    e = ei.value
    assert e.largest_term == "stream.chunk_buffers"
    assert e.largest_term in str(e) and "streaming" in str(e)
    assert e.estimate_bytes and e.terms


def test_admit_refuses_streaming_without_estimator_support(clean_config):
    ex = _dense_extracted(n=1000, d=12)
    est = LinearRegression(float32_inputs=False)
    est._supports_streaming_fit = False
    clean_config["hbm_budget_bytes"] = 10_000
    with pytest.raises(HbmBudgetError, match="no out-of-core streaming path"):
        memory.admit_fit(est, ex, _FakeCtx())


def test_admit_refuses_streaming_under_spmd(clean_config):
    ex = _dense_extracted(n=1000, d=12)
    clean_config["hbm_budget_bytes"] = 10_000
    with pytest.raises(HbmBudgetError, match="single-controller"):
        memory.admit_fit(
            LinearRegression(float32_inputs=False), ex, _FakeCtx(is_spmd=True)
        )


def test_force_stream_skips_resident_check(clean_config):
    # the OOM-retry entry: no capacity information at all, still streams
    ex = _dense_extracted(n=1000, d=12)
    dec = memory.admit_fit(
        LinearRegression(float32_inputs=False), ex, _FakeCtx(), force_stream=True
    )
    assert dec.verdict == memory.STREAM and dec.demoted
    assert dec.chunk_rows == min(memory.DEFAULT_STREAM_CHUNK_ROWS, 1000)


# ------------------------------------------------------------ OOM match -----


def test_is_oom_error_matches_backend_shapes():
    assert memory.is_oom_error(RuntimeError("RESOURCE_EXHAUSTED: out of memory"))
    assert memory.is_oom_error(RuntimeError("Out of memory allocating 1234 bytes"))
    assert memory.is_oom_error(MemoryError("boom"))
    assert not memory.is_oom_error(RuntimeError("some other failure"))
    assert not memory.is_oom_error(ValueError("RESOURCE_EXHAUSTED"))
    # an already-typed budget error must PROPAGATE, never re-enter conversion
    assert not memory.is_oom_error(HbmBudgetError("x"))
    # a kernel that does not COMPILE is not an HBM OOM, although Mosaic
    # spells its scoped-VMEM overflow RESOURCE_EXHAUSTED too (the message
    # the v5e compiler gives for the parent's 512x512 fast plan at d=3000)
    assert not memory.is_oom_error(RuntimeError(
        "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem while "
        "allocating on stack for %pallas_call. Scoped allocation with size "
        "16.07M and limit 16.00M exceeded scoped vmem limit by 72.0K."
    ))
    assert not memory.is_oom_error(RuntimeError(
        "INTERNAL: Mosaic failed to compile TPU kernel: Bad lhs type"
    ))
    # the real thing, as the v5e reports it
    assert memory.is_oom_error(RuntimeError(
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out of "
        "memory in memory space hbm. Used 26.49G of 15.75G hbm."
    ))


def test_as_hbm_budget_error_wraps_message():
    e = memory.as_hbm_budget_error(RuntimeError("RESOURCE_EXHAUSTED: 42"))
    assert isinstance(e, HbmBudgetError)
    assert "RESOURCE_EXHAUSTED: 42" in str(e)


def test_hbm_budget_error_is_permanent_memoryerror():
    from spark_rapids_ml_tpu.errors import is_transient

    e = HbmBudgetError("x", estimate_bytes=10, capacity_bytes=5,
                       largest_term="placement.X", largest_term_bytes=9)
    assert isinstance(e, MemoryError)
    assert not is_transient(e)
    assert "placement.X" in str(e) and "9" in str(e)


# ------------------------------------------- estimate vs memory_stats -------


@pytest.mark.slow
def test_estimate_vs_memory_stats_watermark(rng):
    """Where the backend DOES expose memory_stats (TPU/GPU), the resident
    estimate must bound the post-layout watermark growth within tolerance.
    On CPU jax exposes no stats — the test then only asserts the sampler's
    no-op contract (no gauges, no crash), keeping the lane green everywhere
    while pinning real numbers on chip runs."""
    import pandas as pd

    import jax

    from spark_rapids_ml_tpu import telemetry

    stats_available = any(
        (lambda d: (lambda s: bool(s))(d.memory_stats() if hasattr(d, "memory_stats") else None))(d)
        for d in jax.local_devices()
        if hasattr(d, "memory_stats")
    )
    telemetry.enable()
    telemetry.registry().reset()
    try:
        n, d = 4096, 16
        x = rng.normal(size=(n, d))
        y = x @ rng.normal(size=d)
        df = pd.DataFrame({"features": list(x), "label": y})
        est = LinearRegression(float32_inputs=False).setFeaturesCol("features")
        model = est.fit(df)
        gauges = telemetry.registry().snapshot().get("gauges", {})
        if not stats_available:
            assert "device.peak_bytes_in_use" not in gauges
            return
        ex = _dense_extracted(n=n, d=d)
        estimate = memory.resident_estimate(est, ex, jax.local_device_count())
        peak = gauges["device.peak_bytes_in_use"]
        # the estimate models the placement exactly; allocator rounding and
        # compiled-program scratch may add real bytes on top — the headroom
        # fraction exists for those. 2x is the documented tolerance.
        assert peak >= estimate.total() * 0.1
        assert estimate.total() <= peak * 2.0
        assert model.coef_ is not None
    finally:
        telemetry.disable()
        telemetry.registry().reset()


# ------------------------------------------------- shared HBM ledger --------
# The split-brain bugfix (docs/scheduling.md "The shared ledger"): fits and
# serving loads used to budget independently against FULL capacity, so a
# concurrent fit plus resident served models could jointly overshoot HBM.
# Both admission controllers now charge against capacity minus what the
# process-global scheduler.HbmLedger already holds.


class _FakeServeModel:
    """Minimal serving-hook surface for admit_model_load."""

    _float32_inputs = True

    def __init__(self, nbytes):
        self._nbytes = int(nbytes)

    def _serve_placement_terms(self):
        return {"params": self._nbytes}


def test_fit_admission_subtracts_resident_serving_bytes(clean_config):
    # THE satellite pin: a large model resident in the serving plane, then a
    # fit that would fit an EMPTY budget must demote to STREAM because the
    # model's bytes are already spoken for in the shared ledger.
    from spark_rapids_ml_tpu.scheduler.ledger import global_ledger

    ex = _dense_extracted(n=1000, d=12)
    est = LinearRegression(float32_inputs=False)
    need = memory.resident_estimate(est, ex, 8).total()
    # budget comfortably fits the fit alone (2x) — no model, RESIDENT
    clean_config["hbm_budget_bytes"] = int(2 * need / 0.9)
    dec = memory.admit_fit(est, ex, _FakeCtx())
    assert dec.verdict == memory.RESIDENT
    memory.release_admission(dec)

    # a "large model" load takes 1.25x the fit's bytes out of the budget:
    # what remains (~0.75x) no longer fits the fit resident, but DOES fit
    # its streaming working set — the demotion, not a refusal
    load = memory.admit_model_load(
        _FakeServeModel(int(1.25 * need)), bucket_rows_count=0
    )
    assert load.verdict == memory.RESIDENT
    assert global_ledger().reserved_bytes(kind="serve") >= 1.25 * need

    dec2 = memory.admit_fit(est, ex, _FakeCtx())
    assert dec2.verdict == memory.STREAM and dec2.demoted
    assert "already reserved" in dec2.reason  # the reason NAMES the ledger
    memory.release_admission(dec2)
    # evicting the model (releasing its claim) restores residency
    memory.release_admission(load)
    dec3 = memory.admit_fit(est, ex, _FakeCtx())
    assert dec3.verdict == memory.RESIDENT
    memory.release_admission(dec3)


def test_model_load_admission_subtracts_fit_reservations(clean_config):
    # ...and vice versa: a running fit's reservation counts against a model
    # load, which refuses typed instead of jointly overshooting
    ex = _dense_extracted(n=1000, d=12)
    est = LinearRegression(float32_inputs=False)
    need = memory.resident_estimate(est, ex, 8).total()
    clean_config["hbm_budget_bytes"] = int(2 * need / 0.9)
    fit_dec = memory.admit_fit(est, ex, _FakeCtx())  # holds `need` bytes
    assert fit_dec.verdict == memory.RESIDENT
    with pytest.raises(HbmBudgetError, match="held in the shared ledger"):
        memory.admit_model_load(_FakeServeModel(int(1.5 * need)), bucket_rows_count=0)
    # the fit completing frees the budget; the same load then admits
    memory.release_admission(fit_dec)
    load = memory.admit_model_load(_FakeServeModel(int(1.5 * need)), bucket_rows_count=0)
    assert load.verdict == memory.RESIDENT
    memory.release_admission(load)


def test_release_admission_is_idempotent_and_none_safe(clean_config):
    from spark_rapids_ml_tpu.scheduler.ledger import global_ledger

    ex = _dense_extracted(n=200, d=4)
    dec = memory.admit_fit(LinearRegression(float32_inputs=False), ex, _FakeCtx())
    assert global_ledger().reserved_bytes() > 0
    memory.release_admission(dec)
    assert global_ledger().reserved_bytes() == 0
    memory.release_admission(dec)  # double release: no-op, never a credit
    memory.release_admission(None)
    assert global_ledger().reserved_bytes() == 0
