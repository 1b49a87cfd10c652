#
# Efficiency attribution plane tests (docs/observability.md "Efficiency
# plane"): the zero-cost disabled path (shared no-op identity + the <1%
# overhead micro-bench, mirroring PR 2's pin), the attribution acceptance
# (execute/compile/host/idle sum ≈ scope wall, ≥95% of fit wall attributed
# to named kinds — on a real CV sweep over the virtual 8-device mesh), the
# compile ledger (miss on first sighting, hit on the second, per-fit
# `_fit_metrics["compile"]` stamp), the peak-spec grammar and
# omitted-unless-configured MFU gauges, the per-tenant `device_time` merge
# into `HbmLedger.tenant_usage()` and the ops-plane report/exporters, the
# per-model serving tenant default, and exporter rendering of
# `efficiency.*`/`compile.*` under concurrent scrape. All without a TPU.
#
import threading
import time

import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu import core, ops_plane, telemetry
from spark_rapids_ml_tpu.models.classification import LogisticRegression
from spark_rapids_ml_tpu.models.regression import LinearRegression
from spark_rapids_ml_tpu.ops_plane import efficiency, export

pytestmark = pytest.mark.filterwarnings("ignore::DeprecationWarning")


@pytest.fixture
def tele():
    """Fresh enabled registry + fresh efficiency state; restore after."""
    telemetry.registry().reset()
    efficiency.reset()
    telemetry.enable()
    yield telemetry.registry()
    telemetry.disable()
    telemetry.registry().reset()
    efficiency.reset()


@pytest.fixture
def peak_1g():
    saved = core.config.get("device_peak_flops")
    core.config["device_peak_flops"] = "1G"
    yield
    core.config["device_peak_flops"] = saved


def _binary_df(rng, n=256, d=6):
    x = rng.normal(size=(n, d))
    y = (x @ rng.normal(size=d) > 0).astype(np.float64)
    return pd.DataFrame({"features": list(x), "label": y})


# ------------------------------------------------------------- peak spec ----


def test_parse_peak_spec_grammar():
    assert efficiency.parse_peak_spec("1G") == 1e9
    assert efficiency.parse_peak_spec("275T") == 275e12
    assert efficiency.parse_peak_spec("1.5k") == 1.5e3
    assert efficiency.parse_peak_spec("2.75e14") == 2.75e14
    assert efficiency.parse_peak_spec(9e12) == 9e12
    # unset/empty/garbage/non-positive = no peak — gauges omitted, never
    # guessed (the documented contract)
    for bad in (None, "", "   ", "fast", "-3T", 0, -1.0):
        assert efficiency.parse_peak_spec(bad) is None


# ------------------------------------------------------- zero-cost pins -----


def test_disabled_hooks_are_shared_noops():
    telemetry.disable()
    efficiency.reset()  # process-wide state — earlier test files attribute
    # identity, not just behavior: the disabled path allocates NOTHING per
    # call (the PR-2 `_NOOP_SPAN` contract extended to the new hooks)
    assert telemetry.device_wait("a") is telemetry._NOOP_SPAN
    assert telemetry.device_wait("b") is telemetry.host_section("c")
    assert telemetry.compile_event("p", "s") is telemetry._NOOP_COMPILE_EVENT
    assert telemetry.attribution("l") is telemetry._NOOP_SPAN
    assert telemetry.note_flops(1e9) is None
    # usable as context managers, recording nothing
    with telemetry.device_wait("x"), telemetry.compile_event("p", "s") as ce:
        assert ce.cache_hit is False
    assert efficiency.tenant_time_splits() == {}
    assert efficiency.compile_stats()["programs"] == 0


def test_disabled_overhead_micro_bench(rng):
    """The <1% pin: per-boundary hook cost on the disabled path, scaled to
    a generous per-fit boundary count, must stay under 1% of a real
    logistic fit's wall (mirrors PR 2's zero-cost acceptance)."""
    telemetry.disable()
    t0 = time.monotonic()
    LogisticRegression(maxIter=10).setFeaturesCol("features").fit(_binary_df(rng))
    fit_wall = time.monotonic() - t0

    n = 20_000
    t0 = time.monotonic()
    for _ in range(n):
        with telemetry.device_wait("s"):
            pass
        with telemetry.compile_event("p", "k"):
            pass
        telemetry.note_flops(1.0)
    hook_wall = time.monotonic() - t0
    # a fit crosses a few hundred instrumented boundaries at most; charge
    # 1000 of each hook against the measured fit wall
    per_fit_cost = hook_wall / n * 1000
    assert per_fit_cost < 0.01 * fit_wall, (
        f"disabled hook path costs {per_fit_cost:.6f}s per 1000 boundaries "
        f"vs fit wall {fit_wall:.3f}s"
    )


# ------------------------------------------------- attribution acceptance ---


def test_fit_stamp_attribution_sums_to_wall(tele, rng):
    model = (
        LogisticRegression(maxIter=10).setFeaturesCol("features").fit(_binary_df(rng))
    )
    eff = model._fit_metrics.get("efficiency")
    assert eff, "fit must stamp _fit_metrics['efficiency']"
    wall = eff["wall_s"]
    accounted = eff["execute_s"] + eff["compile_s"] + eff["host_s"] + eff["idle_s"]
    assert wall > 0
    # the acceptance: >=95% of fit wall attributed to named kinds (by
    # construction idle is the residual, so this is ~exact)
    assert accounted >= 0.95 * wall
    assert accounted <= wall * 1.001 + 1e-6
    # the compile stamp rides next to it
    assert model._fit_metrics["compile"]["misses"] >= 1
    # the registry saw the kind histograms
    snap = tele.snapshot()
    for name in (
        "efficiency.execute_s",
        "efficiency.compile_s",
        "efficiency.host_s",
        "efficiency.idle_s",
    ):
        assert name in snap["histograms"]


def test_cv_sweep_attribution_acceptance(tele, rng):
    """The ISSUE acceptance scenario: an instrumented CV sweep on the
    virtual 8-device mesh attributes >=95% of its wall to named kinds, and
    the nested fold fits fold into ONE outer scope (scopes never nest)."""
    from spark_rapids_ml_tpu.evaluation import RegressionEvaluator
    from spark_rapids_ml_tpu.tuning import CrossValidator, ParamGridBuilder

    x = rng.normal(size=(300, 5))
    coef = np.array([1.0, -2.0, 0.0, 0.5, 3.0])
    y = x @ coef + 0.1 * rng.normal(size=300)
    df = pd.DataFrame({"features": list(x), "label": y})
    lr = LinearRegression(standardization=False, float32_inputs=False)
    grid = ParamGridBuilder().addGrid(lr.getParam("regParam"), [0.0, 1.0]).build()
    ev = RegressionEvaluator(metricName="rmse")
    cv = CrossValidator(
        estimator=lr, estimatorParamMaps=grid, evaluator=ev, numFolds=2, seed=3
    )
    t0 = time.monotonic()
    cv.fit(df)
    sweep_wall = time.monotonic() - t0

    splits = efficiency.tenant_time_splits()
    assert splits, "the sweep must attribute under some tenant"
    total_wall = sum(s["wall_s"] for s in splits.values())
    total_accounted = sum(
        s["execute_s"] + s["compile_s"] + s["host_s"] + s["idle_s"]
        for s in splits.values()
    )
    assert total_accounted >= 0.95 * total_wall
    # scope walls never exceed the sweep's own wall: the inner fold fits
    # attributed into outer windows instead of stacking their own
    assert total_wall <= sweep_wall * 1.05 + 0.1
    # the report names a top idle stage per tenant once stages were seen
    rep = ops_plane.report()["efficiency"]
    assert set(rep["tenants"]) == set(splits)


# --------------------------------------------------------- compile ledger ---


def test_compile_ledger_miss_then_hit_across_identical_fits(tele, rng):
    df = _binary_df(rng)
    est = LogisticRegression(maxIter=5).setFeaturesCol("features")
    m1 = est.fit(df)
    stamp1 = m1._fit_metrics["compile"]
    assert stamp1["misses"] >= 1
    m2 = est.fit(df)
    stamp2 = m2._fit_metrics["compile"]
    # identical (program, shape-class): the second fit is all hits
    assert stamp2["misses"] == 0
    assert stamp2["hits"] >= 1
    stats = efficiency.compile_stats()
    assert stats["misses"] >= 1 and stats["hits"] >= 1
    assert stats["wall_s"] > 0
    assert any(e["program"].startswith("fit.") for e in stats["entries"])
    snap = tele.snapshot()
    assert snap["counters"]["compile.misses"] >= 1
    assert snap["counters"]["compile.hits"] >= 1
    assert "compile.wall_s" in snap["histograms"]


def test_compile_event_scope_less_and_shape_keyed(tele):
    # prewarm records with NO scope active — ledger is process-wide
    with telemetry.compile_event("prewarm.M", "128x4") as ce:
        assert ce.cache_hit is False
        time.sleep(0.01)
    with telemetry.compile_event("prewarm.M", "128x4") as ce:
        assert ce.cache_hit is True
    # a different shape class is its own entry (a new compile)
    with telemetry.compile_event("prewarm.M", "256x4") as ce:
        assert ce.cache_hit is False
    stats = efficiency.compile_stats()
    assert stats["programs"] == 2
    assert stats["misses"] == 2 and stats["hits"] == 1
    assert stats["wall_s"] >= 0.01


# ------------------------------------------------------------ MFU gauges ----


def test_mfu_gauge_present_only_with_peak_spec(tele, peak_1g, rng):
    model = (
        LogisticRegression(maxIter=5).setFeaturesCol("features").fit(_binary_df(rng))
    )
    eff = model._fit_metrics["efficiency"]
    assert "mfu" in eff and 0 < eff["mfu"] < 1
    assert tele.snapshot()["gauges"].get("efficiency.mfu") == pytest.approx(
        eff["mfu"]
    )


def test_mfu_gauge_omitted_without_peak_spec(tele, rng):
    saved = core.config.get("device_peak_flops")
    core.config["device_peak_flops"] = None
    try:
        model = (
            LogisticRegression(maxIter=5)
            .setFeaturesCol("features")
            .fit(_binary_df(rng))
        )
        assert "mfu" not in model._fit_metrics["efficiency"]
        assert "efficiency.mfu" not in tele.snapshot()["gauges"]
    finally:
        core.config["device_peak_flops"] = saved


def test_solver_flop_estimates_exist():
    # every headline solver publishes a roofline numerator (the
    # _solver_workspace_terms sibling); serving models the per-bucket hook
    from spark_rapids_ml_tpu.models.clustering import KMeans
    from spark_rapids_ml_tpu.models.feature import PCA

    assert LogisticRegression(maxIter=3)._solver_flop_estimate(100, 10) > 0
    assert LinearRegression()._solver_flop_estimate(100, 10) > 0
    assert KMeans(n_clusters=4)._solver_flop_estimate(100, 10) > 0
    assert PCA(k=2)._solver_flop_estimate(100, 10) > 0


# --------------------------------------------------- tenant_usage / report --


def test_tenant_usage_merges_device_time(tele, rng):
    from spark_rapids_ml_tpu.scheduler.ledger import global_ledger

    LogisticRegression(maxIter=5).setFeaturesCol("features").fit(_binary_df(rng))
    usage = global_ledger().tenant_usage()
    assert "default" in usage
    dt = usage["default"].get("device_time")
    assert dt is not None
    assert set(dt) >= {"execute_s", "compile_s", "host_s", "idle_s", "wall_s"}
    # the same split flows through the scheduler's stats surface
    from spark_rapids_ml_tpu.scheduler import FitScheduler

    sched = FitScheduler(max_concurrent=1)
    try:
        assert "device_time" in sched.stats()["tenant_usage"]["default"]
    finally:
        sched.shutdown()


def test_report_and_snapshot_carry_efficiency(tele, tmp_path, rng):
    import json

    LogisticRegression(maxIter=5).setFeaturesCol("features").fit(_binary_df(rng))
    rep = ops_plane.report()
    assert "default" in rep["efficiency"]["tenants"]
    assert rep["efficiency"]["compile"]["misses"] >= 1
    # the block planner keeps no table and no counters: nothing to report
    assert "autotune" not in rep
    # the archived snapshot (what /snapshot serves) carries the section
    path = str(tmp_path / "snap.json")
    export.write_snapshot(path)
    with open(path) as f:
        snap = json.load(f)
    assert "efficiency" in snap and "autotune" not in snap
    assert "default" in snap["efficiency"]["tenants"]
    # opsreport renders the efficiency section + the standalone archive
    from benchmark.opsreport import main, render

    out = render(snap)
    assert "efficiency (attributed device time)" in out
    assert "compile ledger:" in out
    eff_path = str(tmp_path / "efficiency_report.json")
    assert main(["--write-efficiency", eff_path, "--json"]) in (0, 1)
    with open(eff_path) as f:
        eff_doc = json.load(f)
    assert set(eff_doc) == {"t", "efficiency"}


def test_admit_model_load_defaults_per_model_serving_tenant(tele, rng):
    from spark_rapids_ml_tpu import memory
    from spark_rapids_ml_tpu.scheduler.ledger import global_ledger

    model = (
        LogisticRegression(maxIter=3).setFeaturesCol("features").fit(_binary_df(rng))
    )
    adm = memory.admit_model_load(model)  # ledger-ok: exercising the admission entry itself
    try:
        tenants = {r.tenant for r in global_ledger().reservations()}
        # keyed by model identity, not the old literal "serving" bucket
        assert "serving:LogisticRegressionModel" in tenants
        assert "serving" not in tenants
    finally:
        memory.release_admission(adm)


# ---------------------------------------------------- concurrent scrape -----


def test_exporter_renders_efficiency_under_concurrent_scrape(tele):
    """Writers run attribution scopes + compile events while readers render
    Prometheus text and report() — no exceptions, and the new metric
    families appear in the exposition."""
    errors = []
    stop = threading.Event()

    def writer(tid):
        try:
            for i in range(40):
                with telemetry.attribution(f"fit_{tid}", tenant=f"t{tid}"):
                    with telemetry.device_wait("solve"):
                        time.sleep(0.0005)
                    with telemetry.compile_event(f"p{tid}", str(i % 4)):
                        pass
                    telemetry.note_flops(1e6)
        except Exception as e:  # pragma: no cover
            errors.append(e)

    def reader():
        try:
            while not stop.is_set():
                export.render_prometheus()
                ops_plane.report()
                efficiency.summary()
        except Exception as e:  # pragma: no cover
            errors.append(e)

    writers = [threading.Thread(target=writer, args=(t,)) for t in range(4)]
    readers = [threading.Thread(target=reader) for _ in range(2)]
    for t in readers + writers:
        t.start()
    for t in writers:
        t.join()
    stop.set()
    for t in readers:
        t.join()
    assert not errors
    text = export.render_prometheus()
    assert "efficiency_execute_s" in text or "efficiency.execute_s" in text
    assert "compile_misses" in text or "compile.misses" in text
    splits = efficiency.tenant_time_splits()
    assert {f"t{t}" for t in range(4)} <= set(splits)
    for s in splits.values():
        accounted = s["execute_s"] + s["compile_s"] + s["host_s"] + s["idle_s"]
        assert accounted >= 0.95 * s["wall_s"]
