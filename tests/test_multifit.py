#
# Multi-fit execution engine tests (docs/performance.md "Multi-fit engine"):
# DeviceDataset reuse across fits, CrossValidator weight-masked folds
# (one ingest + one layout per CV fit, fold metrics bit-identical to a
# physical split), batched hyperparameter sweeps vs sequential solves, the
# transform bucket ladder (one predict program per bucket, never per tail
# shape), and the zero-row multi-output transform fix.
#
import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu import core, telemetry
from spark_rapids_ml_tpu.evaluation import (
    MulticlassClassificationEvaluator,
    RegressionEvaluator,
)
from spark_rapids_ml_tpu.linalg import SparseVector
from spark_rapids_ml_tpu.models.classification import LogisticRegression
from spark_rapids_ml_tpu.models.regression import LinearRegression
from spark_rapids_ml_tpu.tuning import CrossValidator, ParamGridBuilder


@pytest.fixture
def tele():
    """Enable telemetry with a fresh registry; restore after."""
    telemetry.registry().reset()
    telemetry.enable()
    yield telemetry.registry()
    telemetry.disable()
    telemetry.registry().reset()


def _reg_df(rng, n=200, d=5):
    x = rng.normal(size=(n, d))
    coef = np.array([1.0, -2.0, 0.0, 0.0, 3.0])
    y = x @ coef + 0.5 + 0.2 * rng.normal(size=n)
    return pd.DataFrame({"features": list(x), "label": y})


def _cls_df(rng, n=200, d=4, sparse=False):
    x = rng.normal(size=(n, d))
    y = (x @ rng.normal(size=d) > 0).astype(np.float64)
    if sparse:
        x = np.where(np.abs(x) > 0.8, x, 0.0)  # sparsify but keep signal
        rows = [
            SparseVector(d, np.nonzero(r)[0].astype(np.int32), r[np.nonzero(r)[0]])
            for r in x
        ]
        return pd.DataFrame({"features": rows, "label": y})
    return pd.DataFrame({"features": list(x), "label": y})


# ------------------------------------------------------------ DeviceDataset --


def test_device_dataset_scope_single_ingest(tele, rng):
    df = _reg_df(rng)
    lr = LinearRegression(float32_inputs=False).setFeaturesCol("features")
    with core.device_dataset_scope():
        m1 = lr.fit(df)
        m2 = lr.copy({lr.getParam("regParam"): 0.5}).fit(df)
    snap = telemetry.snapshot()
    assert snap["counters"]["ingest.datasets"] == 1
    assert snap["counters"]["fit.device_dataset_builds"] == 1
    assert snap["counters"]["fit.device_dataset_reuses"] == 1
    assert snap["spans"]["fit/ingest"]["count"] == 1
    assert snap["spans"]["fit/layout"]["count"] == 1
    # the reused placement still produces the right models
    assert not np.allclose(m1.coef_, m2.coef_)  # different regParam really fit
    # outside a scope, every fit ingests
    lr.fit(df)
    assert telemetry.snapshot()["counters"]["ingest.datasets"] == 2


def test_device_dataset_no_stale_reuse_after_gc(tele, rng):
    # the cache key is id()-based: every entry must PIN its source object,
    # or a gc'd dataset's recycled id on a new same-shaped object would be a
    # silent false hit (model trained on the WRONG data)
    import gc

    lr = LinearRegression(float32_inputs=False).setFeaturesCol("features")
    with core.device_dataset_scope():
        m1 = lr.fit(_reg_df(rng))  # temporary df: unreferenced after the call
        gc.collect()
        m2 = lr.fit(_reg_df(rng))  # same shape/columns, DIFFERENT data
    snap = telemetry.snapshot()
    assert snap["counters"]["fit.device_dataset_builds"] == 2
    assert "fit.device_dataset_reuses" not in snap["counters"]
    assert not np.allclose(m1.coef_, m2.coef_)  # really fit on the new draw


def test_device_dataset_scope_distinct_datasets(tele, rng):
    df1, df2 = _reg_df(rng), _reg_df(rng, n=100)
    lr = LinearRegression(float32_inputs=False).setFeaturesCol("features")
    with core.device_dataset_scope():
        lr.fit(df1)
        lr.fit(df2)  # different object/shape: its own placement
    snap = telemetry.snapshot()
    assert snap["counters"]["ingest.datasets"] == 2
    assert snap["counters"]["fit.device_dataset_builds"] == 2
    assert "fit.device_dataset_reuses" not in snap["counters"]


def test_device_dataset_scope_bounded_lru(tele, rng):
    # a scope around a loop over FRESH dataset objects must not stack HBM
    # placements: retention is bounded by config["device_dataset_cache_entries"]
    dfs = [_reg_df(rng, n=60 + i) for i in range(3)]
    lr = LinearRegression(float32_inputs=False).setFeaturesCol("features")
    old = core.config["device_dataset_cache_entries"]
    core.config["device_dataset_cache_entries"] = 2
    try:
        with core.device_dataset_scope() as scope:
            for df in dfs:
                lr.fit(df)
            assert len(scope.cache) == 2  # oldest evicted
            lr.fit(dfs[2])  # newest still cached
            snap = telemetry.snapshot()
            assert snap["counters"]["fit.device_dataset_builds"] == 3
            assert snap["counters"]["fit.device_dataset_evictions"] == 1
            assert snap["counters"]["fit.device_dataset_reuses"] == 1
            lr.fit(dfs[0])  # evicted: must re-ingest, never stale-hit
            assert telemetry.snapshot()["counters"]["fit.device_dataset_builds"] == 4
    finally:
        core.config["device_dataset_cache_entries"] = old


# ------------------------------------------- CV: one placement, every fit --


def test_cv_telemetry_one_ingest_one_layout(tele, rng):
    # ISSUE acceptance: a numFolds=3 x 4-param-map CrossValidator fit
    # performs exactly 1 ingest and 1 layout (vs numFolds before), with the
    # whole grid dispatched as batched solves per fold + 1 sequential refit
    df = _reg_df(rng, n=240)
    lr = LinearRegression(float32_inputs=False).setFeaturesCol("features")
    grid = ParamGridBuilder().addGrid(
        lr.getParam("regParam"), [0.0, 0.01, 0.1, 1.0]
    ).build()
    cv = CrossValidator(
        estimator=lr, estimatorParamMaps=grid,
        evaluator=RegressionEvaluator(metricName="rmse"), numFolds=3, seed=1,
    )
    cv.fit(df)
    snap = telemetry.snapshot()
    assert snap["counters"]["ingest.datasets"] == 1
    assert snap["spans"]["fit/ingest"]["count"] == 1
    assert snap["spans"]["fit/layout"]["count"] == 1
    assert snap["counters"]["fit.device_dataset_builds"] == 1
    assert snap["counters"]["fit.device_dataset_reuses"] == 3  # 2 folds + refit
    assert snap["counters"]["fit.solves_batched"] == 12  # 3 folds x 4 maps
    assert snap["counters"]["fit.solves_sequential"] == 1  # best-model refit


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "csr"])
def test_cv_fold_metrics_bit_identical_logistic(rng, sparse):
    _fold_bit_identity_check(
        _cls_df(rng, n=180, sparse=sparse),
        LogisticRegression(
            maxIter=40, float32_inputs=False,
            **({"enable_sparse_data_optim": True} if sparse else {}),
        ).setFeaturesCol("features"),
        MulticlassClassificationEvaluator(metricName="accuracy"),
        [0.01, 0.1],
    )


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "csr"])
def test_cv_fold_metrics_bit_identical_linear(rng, sparse):
    df = _reg_df(rng, n=180)
    if sparse:
        x = np.stack(df["features"].to_numpy())
        x = np.where(np.abs(x) > 0.5, x, 0.0)
        d = x.shape[1]
        df = pd.DataFrame({
            "features": [
                SparseVector(d, np.nonzero(r)[0].astype(np.int32), r[np.nonzero(r)[0]])
                for r in x
            ],
            "label": df["label"],
        })
    _fold_bit_identity_check(
        df,
        LinearRegression(
            float32_inputs=False,
            **({"enable_sparse_data_optim": True} if sparse else {}),
        ).setFeaturesCol("features"),
        RegressionEvaluator(metricName="rmse"),
        [0.0, 0.1],
    )


def _fold_bit_identity_check(df, est, eva, reg_grid):
    """The engine's weight-masked fold fits vs a PHYSICAL representation of
    the same split: the fold mask written into the dataset as an explicit
    weight column (the framework's documented padding semantics — w == 0
    rows are absent from the objective) and fitted through the ordinary
    per-fold fitMultiple path with its own ingest. Same rows, same layout,
    same programs => fold metrics must be BIT-identical. A second check
    compares against the literal row-subset fit (different reduction
    groupings, so exact-arithmetic equality only): tight allclose."""
    grid = ParamGridBuilder().addGrid(est.getParam("regParam"), reg_grid).build()
    num_folds = 2
    cv = CrossValidator(
        estimator=est, estimatorParamMaps=grid, evaluator=eva,
        numFolds=num_folds, seed=5,
    )
    engine_avg = np.asarray(cv.fit(df).avgMetrics)

    n = len(df)
    folds = cv._kfold_indices(n, df)
    feats_full = est._pre_process_data(df, for_fit=False).features
    labels = df["label"].to_numpy(dtype=np.float64)

    baseline = np.zeros((num_folds, len(grid)))
    subset = np.zeros_like(baseline)
    for f, (train_idx, valid_idx) in enumerate(folds):
        mask = np.zeros(n)
        mask[train_idx] = 1.0
        df_w = df.copy()
        df_w["w_"] = mask
        est_w = est.copy()._set_params(weightCol="w_")
        models = [m for _, m in sorted(est_w.fitMultiple(df_w, grid))]
        combined = models[0]._combine(models)
        baseline[f] = combined._transform_evaluate_arrays(
            feats_full[valid_idx], labels[valid_idx], eva
        )
        # literal physical split (row subset, its own layout): exact math,
        # different float reduction groupings
        train = df.iloc[train_idx].reset_index(drop=True)
        sub_models = [m for _, m in sorted(est.fitMultiple(train, grid))]
        sub_combined = sub_models[0]._combine(sub_models)
        subset[f] = sub_combined._transform_evaluate_arrays(
            feats_full[valid_idx], labels[valid_idx], eva
        )
    np.testing.assert_array_equal(engine_avg, baseline.mean(axis=0))
    np.testing.assert_allclose(engine_avg, subset.mean(axis=0), rtol=1e-6, atol=1e-9)


def test_sparse_cv_converts_and_places_ell_once(tele, rng):
    # the sparse half of the one-placement contract: a CV grid over CSR data
    # converts CSR->ELL and places the ELL tensors ONCE (FitInputs.ell_rows
    # is memoized across fold masks and solves), not once per solve
    df = _cls_df(rng, n=120, sparse=True)
    lr = LogisticRegression(
        maxIter=10, float32_inputs=False, enable_sparse_data_optim=True
    ).setFeaturesCol("features")
    grid = ParamGridBuilder().addGrid(lr.getParam("regParam"), [0.0, 0.1]).build()
    cv = CrossValidator(
        estimator=lr, estimatorParamMaps=grid,
        evaluator=MulticlassClassificationEvaluator(metricName="accuracy"),
        numFolds=2, seed=2,
    )
    cv.fit(df)
    snap = telemetry.snapshot()
    assert snap["counters"]["ingest.datasets"] == 1
    assert snap["counters"]["sparse.csr_to_ell_calls"] == 1


def test_cv_masked_fold_respects_train_classes(rng):
    # a fold whose TRAIN rows miss a class must behave like the physical
    # split (class discovery honors the mask, not the full dataset)
    n = 30
    x = rng.normal(size=(n, 3))
    y = np.zeros(n)
    y[-3:] = 1.0  # the rare class sits in 3 rows
    df = pd.DataFrame({"features": list(x), "label": y, "fold": [0] * (n - 3) + [1] * 3})
    lr = LogisticRegression(maxIter=10, float32_inputs=False).setFeaturesCol("features")
    grid = ParamGridBuilder().addGrid(lr.getParam("regParam"), [0.0]).build()
    cv = CrossValidator(
        estimator=lr, estimatorParamMaps=grid,
        evaluator=MulticlassClassificationEvaluator(metricName="accuracy"),
        numFolds=2, foldCol="fold",
    )
    m = cv.fit(df)  # fold 1 trains on class-0 rows only: degenerate fit path
    assert np.isfinite(m.avgMetrics[0])


# ----------------------------------------------------------- batched sweeps --


def test_batched_sweep_matches_sequential_logistic(rng):
    df = _cls_df(rng, n=150)
    lr = LogisticRegression(maxIter=40, float32_inputs=False).setFeaturesCol("features")
    grid = ParamGridBuilder().addGrid(
        lr.getParam("regParam"), [1e-4, 1e-2, 1.0]
    ).build()
    swept = [m for _, m in sorted(lr.fitMultiple(df, grid))]  # batched dispatch
    for pm, m_b in zip(grid, swept):
        m_s = lr.copy(pm).fit(df)  # single fit: sequential solver
        np.testing.assert_allclose(m_b.coef_, m_s.coef_, rtol=1e-9, atol=1e-12)
        np.testing.assert_allclose(m_b.intercept_, m_s.intercept_, rtol=1e-9, atol=1e-12)
        assert m_b.n_iter_ == m_s.n_iter_  # frozen loops: same trajectory


def test_batched_sweep_groups_by_program_structure(tele, rng):
    # use_l1 is a STATIC of the traced program: a grid mixing L1-on/off
    # splits into one batched solve per side; a maxIter grid (program
    # structure) falls back to sequential solves entirely
    df = _cls_df(rng, n=120)
    lr = LogisticRegression(maxIter=30, float32_inputs=False).setFeaturesCol("features")
    grid = (
        ParamGridBuilder()
        .addGrid(lr.getParam("regParam"), [0.01, 0.1])
        .addGrid(lr.getParam("elasticNetParam"), [0.0, 0.5])
        .build()
    )
    swept = [m for _, m in sorted(lr.fitMultiple(df, grid))]
    snap = telemetry.snapshot()
    assert snap["counters"]["fit.solves_batched"] == 4  # 2 groups of 2
    assert "fit.solves_sequential" not in snap["counters"]
    for pm, m_b in zip(grid, swept):
        m_s = lr.copy(pm).fit(df)
        np.testing.assert_allclose(m_b.coef_, m_s.coef_, rtol=1e-8, atol=1e-10)

    telemetry.registry().reset()
    grid_iter = ParamGridBuilder().addGrid(lr.getParam("maxIter"), [5, 10]).build()
    list(lr.fitMultiple(df, grid_iter))
    snap = telemetry.snapshot()
    assert snap["counters"]["fit.solves_sequential"] == 2
    assert "fit.solves_batched" not in snap["counters"]


def test_batched_sweep_matches_sequential_linear_cd(rng):
    df = _reg_df(rng, n=150)
    lr = LinearRegression(float32_inputs=False).setFeaturesCol("features")
    grid = (
        ParamGridBuilder()
        .addGrid(lr.getParam("regParam"), [0.01, 0.1, 1.0])
        .addGrid(lr.getParam("elasticNetParam"), [0.5])
        .build()
    )
    swept = [m for _, m in sorted(lr.fitMultiple(df, grid))]
    for pm, m_b in zip(grid, swept):
        m_s = lr.copy(pm).fit(df)
        np.testing.assert_allclose(m_b.coef_, m_s.coef_, rtol=1e-10, atol=1e-13)
        assert m_b.n_iter_ == m_s.n_iter_


# --------------------------------------------------------- bucketed serving --


def test_transform_bucket_ladder_compiles_per_bucket(tele, rng):
    from spark_rapids_ml_tpu.ops.linear import linear_predict

    df = _reg_df(rng, n=64, d=5)
    model = LinearRegression(float32_inputs=False).setFeaturesCol("features").fit(df)
    old_min = core.config["transform_bucket_min_rows"]
    core.config["transform_bucket_min_rows"] = 8
    try:
        cache_before = (
            linear_predict._cache_size() if hasattr(linear_predict, "_cache_size") else None
        )
        programs_before = telemetry.snapshot()["counters"].get("transform.bucket_programs", 0)
        sizes = [1, 2, 3, 5, 7, 8, 9, 11, 13, 17, 19, 23, 29, 31, 33, 40, 47, 55, 63]
        for n in sizes:
            out = model._transform_arrays(rng.normal(size=(n, 5)))
            assert out.shape == (n,)  # outputs sliced back to the valid rows
        new_programs = (
            telemetry.snapshot()["counters"].get("transform.bucket_programs", 0)
            - programs_before
        )
        # 19 distinct batch sizes, ladder rungs 8/16/32/64 only
        assert new_programs <= 4, f"expected <=4 bucket programs, saw {new_programs}"
        if cache_before is not None:
            compiled = linear_predict._cache_size() - cache_before
            assert compiled <= 4, f"predict compiled {compiled} times for 19 shapes"
    finally:
        core.config["transform_bucket_min_rows"] = old_min


def test_transform_bucket_values_unchanged(rng):
    # bucket padding must not leak into valid rows' outputs
    df = _reg_df(rng, n=50, d=5)
    model = LinearRegression(float32_inputs=False).setFeaturesCol("features").fit(df)
    x = rng.normal(size=(37, 5))
    expect = x @ model.coef_ + model.intercept_
    np.testing.assert_allclose(model._transform_arrays(x), expect, rtol=1e-12)


# --------------------------------------------------- zero-row transform fix --


def test_transform_zero_rows_multi_output(rng):
    # ISSUE satellite: a zero-row block through a MULTI-output predict must
    # yield one correctly-shaped empty array PER output, not one bare
    # np.zeros((0,)) that _split_output would mis-map across columns
    df = _cls_df(rng, n=80)
    model = LogisticRegression(maxIter=10, float32_inputs=False).setFeaturesCol("features").fit(df)
    out = model._transform_arrays(np.zeros((0, 4)))
    assert isinstance(out, tuple) and len(out) == 2
    raw, prob = out
    assert raw.shape == (0, 2) and prob.shape == (0, 2)
    # and through the full transform surface
    empty = model.transform({"features": np.zeros((0, 4)), "label": np.zeros(0)})
    assert len(empty) == 0
    for col in ("rawPrediction", "probability", "prediction"):
        assert col in empty.columns

    # single-output model: empty 1-D prediction block
    df_r = _reg_df(rng, n=60, d=5)
    lin = LinearRegression(float32_inputs=False).setFeaturesCol("features").fit(df_r)
    out_r = lin._transform_arrays(np.zeros((0, 5)))
    assert out_r.shape == (0,)


# -------------------------------------------------- persistent compile cache --


def test_first_solve_gauge_and_cpu_pool_leaves_jax_cache_alone(tele, rng):
    import jax

    before = jax.config.jax_compilation_cache_dir
    df = _reg_df(rng, n=60)
    LinearRegression(float32_inputs=False).setFeaturesCol("features").fit(df)
    snap = telemetry.snapshot()
    # first-call wall time is recorded for cross-run cache-efficacy tracking
    assert snap["gauges"]["fit.compile_cache_hit"] > 0
    # the cache directory is resolved (never None), but a CPU device pool is
    # not wired into it: jax stays as the environment configured it (the
    # resolution itself is pinned by tests/test_chip_bringup.py)
    assert core.config["compilation_cache_dir"]
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_probe_guarded_after_batching(tele, rng):
    # identical param maps batch into ONE solve — the compile-overhead probe
    # must not fire on a single solve time (nothing to difference against)
    df = _reg_df(rng, n=80)
    lr = LinearRegression(float32_inputs=False).setFeaturesCol("features")
    grid = ParamGridBuilder().addGrid(lr.getParam("regParam"), [0.1, 0.1, 0.1]).build()
    list(lr.fitMultiple(df, grid))
    snap = telemetry.snapshot()
    assert snap["counters"]["fit.solves_batched"] == 3
    assert "fit.compile_overhead_s_est" not in snap["gauges"]


# --------------------------------------------- SPMD placement agreement -----
#
# Under multi-process SPMD the DeviceDataset cache-hit branch runs no
# collectives while the miss branch runs the layout allgather — so hit/miss
# must be SYMMETRIC across ranks. `_device_dataset` agrees placement
# fingerprints over ONE rendezvous round (every rank votes its have-bit;
# the cache is used only when ALL ranks hold the entry). These tests drive
# the agreement protocol directly with thread ranks + LocalRendezvous and
# stubbed ingest/layout (real cross-process XLA is exercised by
# tests/sweep_worker.py where the backend supports it).


def _dds_worker(rank, rendezvous, key, steps, counts, errors):
    """One thread-rank running the scripted `_device_dataset` sequence."""
    from types import SimpleNamespace

    from spark_rapids_ml_tpu.models.clustering import KMeans

    try:
        est = KMeans(k=2)
        est._pre_process_data = lambda dataset, **kw: (
            counts[rank].__setitem__("ingest", counts[rank]["ingest"] + 1),
            SimpleNamespace(n_rows=10),
        )[1]

        def _layout(extracted, ctx, stage_logger, force_stream=False,
                    key=None, source=None, attempt=0):
            counts[rank]["layout"] += 1
            return core.DeviceDataset(
                key=key, extracted=extracted, inputs=None, source=source
            )

        est._admit_and_layout = _layout
        est._device_dataset_key = lambda dataset, ctx: key
        ctx = SimpleNamespace(
            is_spmd=True, rank=rank, nranks=2, rendezvous=rendezvous
        )
        with core.device_dataset_scope():
            scope = core._DDS_SCOPE.get()
            for step in steps:
                if step == "fit":
                    est._device_dataset(object(), ctx, None)
                elif step == "evict-rank1":
                    # lockstep mutation: barrier, rank 1 drops its entry,
                    # barrier — so the next fit sees a split cache state
                    rendezvous.allgather("sync-a")
                    if rank == 1:
                        scope.cache.pop(key)
                    rendezvous.allgather("sync-b")
    except BaseException as e:  # surfaced by the parent; threads must not die silently
        errors[rank] = e


def test_spmd_placement_agreement_hits_only_when_all_ranks_have(tele):
    import threading

    from spark_rapids_ml_tpu.parallel import LocalRendezvous

    key = ("fp", ("features", None, None, None, None), ("float32", False), (0, 1))
    rvs = LocalRendezvous.create(2, timeout_s=20.0)
    counts = [
        {"ingest": 0, "layout": 0},
        {"ingest": 0, "layout": 0},
    ]
    errors = [None, None]
    steps = ["fit", "fit", "evict-rank1", "fit"]
    threads = [
        threading.Thread(
            target=_dds_worker, args=(r, rvs[r], key, steps, counts, errors)
        )
        for r in range(2)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    # symmetry is the whole point: an asymmetric hit/miss would deadlock one
    # rank in the layout allgather — both threads must come back
    assert not any(t.is_alive() for t in threads)
    assert errors == [None, None]

    # fit 1: both miss -> both ingest + layout and cache the entry
    # fit 2: both have -> pure cache hit, NO ingest/layout anywhere
    # fit 3: rank 1 evicted -> the vote fails, BOTH ranks rebuild together:
    #        rank 0 still holds the exact entry, so it takes the
    #        host-retained path (ingest skipped, layout re-run); rank 1
    #        re-ingests + lays out
    assert counts[0] == {"ingest": 1, "layout": 2}
    assert counts[1] == {"ingest": 2, "layout": 2}
    snap = tele.snapshot()["counters"]
    assert snap["fit.device_dataset_spmd_rounds"] == 6  # 3 fits x 2 ranks
    assert snap["fit.device_dataset_reuses"] == 2  # fit 2 only
    assert snap["fit.device_dataset_builds"] == 3  # fit 1 (x2) + fit 3 rank 1
    assert snap["recovery.replacements"] == 1  # fit 3 rank 0 host-retained


def test_spmd_agreement_skipped_off_spmd(tele, rng):
    # single-process fits must not pay (or count) any rendezvous round
    df = _reg_df(rng)
    lr = LinearRegression(float32_inputs=False).setFeaturesCol("features")
    with core.device_dataset_scope():
        lr.fit(df)
        lr.fit(df)
    snap = tele.snapshot()["counters"]
    assert "fit.device_dataset_spmd_rounds" not in snap
    assert snap["fit.device_dataset_reuses"] == 1
