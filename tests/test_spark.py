#
# Spark barrier-stage integration lane (reference core.py:698-797 runs every
# fit inside `mapInPandas(...).rdd.barrier()` tasks; its communicator is built
# from `BarrierTaskContext` — cuml_context.py:80-103, conftest.py:44-70).
#
# Two lanes:
#   * test_simulated_barrier_stage_fit — ALWAYS runs: N real OS processes,
#     each wrapping a `BarrierTaskContext`-shaped object (cross-process
#     file-backed allGather) in BarrierRendezvous + TpuContext — the exact
#     production wiring for a Spark task body, minus the JVM.
#   * test_pyspark_barrier_stage_fit — runs when pyspark is importable
#     (`ci/test.sh --spark`); skipped otherwise since this image ships no
#     pyspark. Drives the same fit from inside a REAL local[N] barrier stage.
#
import os
import subprocess
import sys
import uuid

import numpy as np
import pandas as pd
import pytest

HERE = os.path.dirname(__file__)
REPO = os.path.dirname(HERE)
NRANKS = 3


def _reference_models():
    from tests.mp_worker import make_dataset

    from spark_rapids_ml_tpu.models.classification import LogisticRegression
    from spark_rapids_ml_tpu.models.feature import PCA

    X, y_log, _ = make_dataset()
    df = pd.DataFrame({"features": list(X), "label": y_log})
    pca = PCA(k=3, inputCol="features", float32_inputs=False).fit(df)
    lr = (
        LogisticRegression(maxIter=100, regParam=0.1, tol=1e-10, float32_inputs=False)
        .setFeaturesCol("features")
        .fit(df)
    )
    return pca, lr


def test_simulated_barrier_stage_fit(tmp_path):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["JAX_ENABLE_X64"] = "1"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    rdv_dir = str(tmp_path / "rdv")
    out_dir = str(tmp_path / "out")
    os.makedirs(out_dir, exist_ok=True)
    run_id = uuid.uuid4().hex
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.join(HERE, "spark_barrier_worker.py"),
             str(r), str(NRANKS), rdv_dir, out_dir, run_id],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        for r in range(NRANKS)
    ]
    outputs = [p.communicate(timeout=300)[0].decode() for p in procs]
    for r, (p, out) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"

    pca_ref, lr_ref = _reference_models()
    results = [
        np.load(os.path.join(out_dir, f"rank_{r}.npz")) for r in range(NRANKS)
    ]
    for r, res in enumerate(results):
        # every rank must hold the SAME global model, equal to the
        # single-process fit on the concatenated data
        np.testing.assert_allclose(res["pc"], np.asarray(pca_ref.pc), rtol=1e-8, atol=1e-10)
        np.testing.assert_allclose(res["mean"], np.asarray(pca_ref.mean), rtol=1e-8, atol=1e-10)
        np.testing.assert_allclose(
            res["coef"], np.asarray(lr_ref.coefficients), rtol=1e-6, atol=1e-8
        )
        np.testing.assert_allclose(
            res["intercept"], [lr_ref.intercept], rtol=1e-6, atol=1e-8
        )


def _spark_train_body(it):
    """Barrier-task body: the reference's train UDF shape (core.py:698-797) —
    get the BarrierTaskContext, wrap it, build the communicator, fit, emit
    rank 0's model."""
    from pyspark import BarrierTaskContext

    rows = list(it)
    import numpy as np
    import pandas as pd

    from spark_rapids_ml_tpu.models.feature import PCA
    from spark_rapids_ml_tpu.parallel import BarrierRendezvous, TpuContext

    ctx = BarrierTaskContext.get()
    rdv = BarrierRendezvous(ctx)
    feats = np.asarray([r["features"] for r in rows], dtype=np.float64)
    df = pd.DataFrame({"features": list(feats)})
    with TpuContext(rdv.rank, rdv.nranks, rdv, require_distributed=True):
        pca = PCA(k=3, inputCol="features", float32_inputs=False).fit(df)
    if rdv.rank == 0:
        yield {
            "pc": np.asarray(pca.pc).ravel().tolist(),
            "mean": np.asarray(pca.mean).tolist(),
        }


# -- Spark JVM model interop (`.cpu()`): reference utils.py:311-481 /
# -- tree.py:524-569 / feature.py:365-379 parity -----------------------------


def _rf_training_data(seed=0, n=300, d=6, classification=True):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d))
    if classification:
        y = ((x[:, 0] + 0.5 * x[:, 1] > 0).astype(int) + (x[:, 2] > 1.0)).astype(float)
    else:
        y = x[:, 0] * 2.0 - x[:, 3] + 0.1 * rng.normal(size=n)
    return pd.DataFrame({"features": list(x), "label": y}), x


def test_tree_spec_pure_layer():
    """The py4j-free node-spec layer: structure and stats must be consistent
    with the model's own predictions — runs WITHOUT pyspark."""
    from spark_rapids_ml_tpu.models.classification import RandomForestClassifier
    from spark_rapids_ml_tpu.models.regression import RandomForestRegressor
    from spark_rapids_ml_tpu.spark_interop import forest_specs

    df, x = _rf_training_data(classification=True)
    clf = RandomForestClassifier(
        numTrees=3, maxDepth=4, seed=7, float32_inputs=False
    ).setFeaturesCol("features").fit(df)
    specs = forest_specs(clf)
    assert len(specs) == clf.num_trees

    def walk(node, depth=0):
        assert depth <= clf.max_depth
        assert node["impurity"] >= 0 and node["instance_count"] > 0
        assert len(node["stats"]) == clf.numClasses
        assert node["prediction"] == float(np.argmax(node["stats"]))
        if "split_feature" in node:
            assert 0 <= node["split_feature"] < clf.n_cols
            assert np.isfinite(node["threshold"])
            # children partition the parent's instances
            assert (
                node["left"]["instance_count"] + node["right"]["instance_count"]
                == node["instance_count"]
            )
            walk(node["left"], depth + 1)
            walk(node["right"], depth + 1)

    for spec in specs:
        walk(spec)

    # single-tree spec traversal must reproduce the model's own prediction
    def spec_predict(node, row):
        while "split_feature" in node:
            node = node["left"] if row[node["split_feature"]] <= node["threshold"] else node["right"]
        return node["prediction"]

    votes = np.zeros((len(x), clf.numClasses))
    for spec in specs:
        for i, row in enumerate(x):
            node = spec
            while "split_feature" in node:
                node = node["left"] if row[node["split_feature"]] <= node["threshold"] else node["right"]
            s = np.asarray(node["stats"])
            votes[i] += s / s.sum()
    got = clf.classes_[np.argmax(votes, axis=1)]
    want = clf.transform(df)["prediction"].to_numpy()
    np.testing.assert_array_equal(got.astype(float), want)

    # regression: leaf prediction = node mean; forest mean matches transform
    dfr, xr = _rf_training_data(classification=False)
    reg = RandomForestRegressor(
        numTrees=3, maxDepth=4, seed=7, float32_inputs=False
    ).setFeaturesCol("features").fit(dfr)
    preds = np.zeros(len(xr))
    for spec in forest_specs(reg):
        preds += np.asarray([spec_predict(spec, row) for row in xr])
    preds /= reg.num_trees
    np.testing.assert_allclose(
        preds, reg.transform(dfr)["prediction"].to_numpy(), rtol=1e-8, atol=1e-10
    )


def test_tree_spec_root_leaf():
    # a forest whose gain bar blocks every split must convert to single
    # LeafNode trees (the degenerate case the py4j builder must survive)
    from spark_rapids_ml_tpu.models.classification import RandomForestClassifier
    from spark_rapids_ml_tpu.spark_interop import forest_specs

    df, _ = _rf_training_data(n=120)
    clf = RandomForestClassifier(
        numTrees=2, maxDepth=3, minInfoGain=1e9, seed=1, float32_inputs=False
    ).setFeaturesCol("features").fit(df)
    for spec in forest_specs(clf):
        assert "split_feature" not in spec  # root is a leaf
        assert spec["instance_count"] > 0
        assert spec["prediction"] == float(np.argmax(spec["stats"]))


def test_cpu_requires_pyspark_message():
    """Without pyspark, .cpu() must raise a clear ImportError (not crash deep
    in py4j)."""
    try:
        import pyspark  # noqa: F401

        pytest.skip("pyspark installed; the gated parity tests cover .cpu()")
    except ImportError:
        pass
    from spark_rapids_ml_tpu.models.feature import PCA

    df, _ = _rf_training_data()
    model = PCA(k=2, inputCol="features", float32_inputs=False).fit(df)
    with pytest.raises(ImportError, match="pyspark"):
        model.cpu()


@pytest.fixture(scope="module")
def spark_session():
    pytest.importorskip("pyspark")
    from pyspark.sql import SparkSession

    spark = (
        SparkSession.builder.master("local[2]")
        .appName("srml-tpu-cpu-interop")
        .getOrCreate()
    )
    yield spark
    spark.stop()


def _spark_predictions(spark, spark_model, x, cols):
    from pyspark.ml.linalg import Vectors as SparkVectors

    sdf = spark.createDataFrame(
        [(SparkVectors.dense([float(v) for v in row]),) for row in x], ["features"]
    )
    rows = spark_model.transform(sdf).collect()
    return {c: np.asarray([_to_np(r[c]) for r in rows]) for c in cols}


def _to_np(v):
    return v.toArray() if hasattr(v, "toArray") else v


def test_rf_to_spark_model(spark_session):
    """Fitted TPU RF -> genuine JVM RandomForestClassificationModel with
    matching predictions (VERDICT round-4 item 4; reference tree.py:524-569)."""
    from spark_rapids_ml_tpu.models.classification import RandomForestClassifier

    df, x = _rf_training_data(classification=True)
    model = RandomForestClassifier(
        numTrees=5, maxDepth=5, seed=3, float32_inputs=False
    ).setFeaturesCol("features").fit(df)
    spark_model = model.cpu()
    assert spark_model.getNumTrees == model.num_trees
    assert spark_model.numFeatures == model.n_cols
    assert spark_model.numClasses == model.numClasses

    ours = model.transform(df)
    got = _spark_predictions(
        spark_session, spark_model, x, ["prediction", "probability"]
    )
    np.testing.assert_allclose(
        got["prediction"], ours["prediction"].to_numpy(), atol=1e-12
    )
    np.testing.assert_allclose(
        got["probability"], np.stack(ours["probability"].to_list()), atol=1e-6
    )
    # predictLeaf delegates through the JVM model (reference tree.py:513-518)
    leaves = model.predictLeaf(x[0])
    assert np.asarray(leaves.toArray() if hasattr(leaves, "toArray") else leaves).shape[-1] == model.num_trees


def test_rf_regression_to_spark_model(spark_session):
    from spark_rapids_ml_tpu.models.regression import RandomForestRegressor

    df, x = _rf_training_data(classification=False)
    model = RandomForestRegressor(
        numTrees=5, maxDepth=5, seed=3, float32_inputs=False
    ).setFeaturesCol("features").fit(df)
    spark_model = model.cpu()
    got = _spark_predictions(spark_session, spark_model, x, ["prediction"])
    np.testing.assert_allclose(
        got["prediction"], model.transform(df)["prediction"].to_numpy(), rtol=1e-6
    )


def test_pca_to_spark_model(spark_session):
    """PCA -> JVM PCAModel: pc/explainedVariance carried exactly; projections
    agree on centered inputs (Spark PCAModel does not mean-center)."""
    from spark_rapids_ml_tpu.models.feature import PCA

    df, x = _rf_training_data()
    model = PCA(k=3, inputCol="features", outputCol="pca_out", float32_inputs=False).fit(df)
    spark_model = model.cpu()
    np.testing.assert_allclose(
        np.asarray(spark_model.pc.toArray()), np.asarray(model.pc), rtol=1e-10
    )
    np.testing.assert_allclose(
        np.asarray(spark_model.explainedVariance.toArray()),
        np.asarray(model.explainedVariance),
        rtol=1e-10,
    )
    xc = x - np.asarray(model.mean)[None, :]
    got = _spark_predictions(spark_session, spark_model, xc, ["pca_out"])
    np.testing.assert_allclose(got["pca_out"], xc @ np.asarray(model.pc), atol=1e-8)


def test_kmeans_to_spark_model(spark_session):
    from spark_rapids_ml_tpu.models.clustering import KMeans

    df, x = _rf_training_data(classification=False)
    model = KMeans(k=4, seed=2, maxIter=20, float32_inputs=False).setFeaturesCol("features").fit(df)
    spark_model = model.cpu()
    got_centers = np.stack([np.asarray(c) for c in spark_model.clusterCenters()])
    np.testing.assert_allclose(got_centers, np.asarray(model.cluster_centers_), rtol=1e-10)
    got = _spark_predictions(spark_session, spark_model, x, ["prediction"])
    np.testing.assert_array_equal(
        got["prediction"], model.transform(df)["prediction"].to_numpy()
    )


def test_linear_models_to_spark(spark_session):
    from spark_rapids_ml_tpu.models.classification import LogisticRegression
    from spark_rapids_ml_tpu.models.regression import LinearRegression

    df, x = _rf_training_data(classification=False)
    lin = LinearRegression(float32_inputs=False).setFeaturesCol("features").fit(df)
    got = _spark_predictions(spark_session, lin.cpu(), x, ["prediction"])
    np.testing.assert_allclose(
        got["prediction"], lin.transform(df)["prediction"].to_numpy(), rtol=1e-6
    )

    dfc, xc = _rf_training_data(classification=True)
    dfc["label"] = (dfc["label"] > 0).astype(float)  # binary 0/1
    log = (
        LogisticRegression(maxIter=200, tol=1e-12, float32_inputs=False)
        .setFeaturesCol("features")
        .fit(dfc)
    )
    got = _spark_predictions(spark_session, log.cpu(), xc, ["prediction", "probability"])
    ours = log.transform(dfc)
    np.testing.assert_allclose(got["prediction"], ours["prediction"].to_numpy(), atol=1e-12)
    np.testing.assert_allclose(
        got["probability"], np.stack(ours["probability"].to_list()), atol=1e-6
    )


def test_pyspark_barrier_stage_fit(tmp_path):
    pyspark = pytest.importorskip("pyspark")
    from pyspark.sql import SparkSession

    from tests.mp_worker import make_dataset, split_bounds

    spark = (
        SparkSession.builder.master(f"local[{NRANKS}]")
        .appName("srml-tpu-barrier-it")
        .config("spark.default.parallelism", str(NRANKS))
        .config("spark.python.worker.reuse", "false")
        .getOrCreate()
    )
    try:
        X, _, _ = make_dataset()
        bounds = split_bounds(len(X), NRANKS)
        rows = [
            {"part": r, "features": X[i].tolist()}
            for r in range(NRANKS)
            for i in range(bounds[r], bounds[r + 1])
        ]
        rdd = (
            spark.sparkContext.parallelize(rows, NRANKS)
            .barrier()
            .mapPartitions(_spark_train_body)
        )
        out = rdd.collect()
        assert len(out) == 1  # one model row, from rank 0
        pca_ref, _ = _reference_models()
        got_pc = np.asarray(out[0]["pc"]).reshape(np.asarray(pca_ref.pc).shape)
        np.testing.assert_allclose(got_pc, np.asarray(pca_ref.pc), rtol=1e-6, atol=1e-8)
        np.testing.assert_allclose(
            np.asarray(out[0]["mean"]), np.asarray(pca_ref.mean), rtol=1e-6, atol=1e-8
        )
    finally:
        spark.stop()


def test_as_spark_df_probes_first_non_null():
    # the column-kind probe must skip leading None/NaN cells (ADVICE round 5):
    # a vector column whose row 0 is null is still a vector column — runs
    # WITHOUT pyspark (pure pandas helper layer)
    from spark_rapids_ml_tpu.spark_interop import _first_non_null

    pdf = pd.DataFrame(
        {
            "vec_leading_none": [None, np.array([1.0, 2.0]), np.array([3.0, 4.0])],
            "vec_leading_nan": [np.nan, [1.0, 2.0], [3.0, 4.0]],
            "scalar_leading_nan": [np.nan, 1.5, 2.5],
            "all_null": [None, None, None],
        }
    )
    probed = _first_non_null(pdf["vec_leading_none"])
    assert isinstance(probed, np.ndarray)
    np.testing.assert_array_equal(probed, [1.0, 2.0])
    assert _first_non_null(pdf["vec_leading_nan"]) == [1.0, 2.0]
    assert _first_non_null(pdf["scalar_leading_nan"]) == 1.5
    assert _first_non_null(pdf["all_null"]) is None
    assert _first_non_null(pd.Series([], dtype=object)) is None

    # null cells of a vector column map to None (a bare NaN in a VectorUDT
    # column breaks Spark's serializer); non-null branches need pyspark and
    # are covered by the --spark lane
    from spark_rapids_ml_tpu.spark_interop import _vector_cell_or_none

    assert _vector_cell_or_none(None) is None
    assert _vector_cell_or_none(float("nan")) is None
    assert _vector_cell_or_none(np.float64("nan")) is None
