#
# Multi-process SPMD fit tests: N real OS processes, each holding a ragged
# local row block, fit cooperatively through TpuContext(require_distributed=
# True) over a FileRendezvous — the runtime analog of the reference's barrier
# stage of one-task-per-GPU NCCL ranks (reference core.py:698-791 +
# cuml_context.py:36-148). Results must match a single-process fit on the
# concatenated dataset.
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


def _launch_workers(nranks, tmp_path, local_devices=2, script="mp_worker.py"):
    env = dict(os.environ)
    # subprocesses must NOT inherit the parent's 8-device CPU forcing: plain
    # CPU backend with `local_devices` devices each
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={local_devices}"
    env["JAX_ENABLE_X64"] = "1"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    rdv_dir = str(tmp_path / "rdv")
    out_dir = str(tmp_path / "out")
    os.makedirs(out_dir, exist_ok=True)
    run_id = uuid.uuid4().hex  # launcher-minted nonce guards against stale rounds
    procs = [
        subprocess.Popen(
            [sys.executable, os.path.join(HERE, script),
             str(r), str(nranks), rdv_dir, out_dir, run_id],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        )
        for r in range(nranks)
    ]
    outputs = [p.communicate(timeout=300)[0].decode() for p in procs]
    for r, (p, out) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out}"
    return out_dir


def _single_process_reference():
    from tests.mp_worker import make_dataset

    from spark_rapids_ml_tpu.models.classification import LogisticRegression
    from spark_rapids_ml_tpu.models.clustering import KMeans
    from spark_rapids_ml_tpu.models.feature import PCA
    from spark_rapids_ml_tpu.models.knn import NearestNeighbors
    from spark_rapids_ml_tpu.models.regression import LinearRegression

    X, y_log, y_lin = make_dataset()
    df = pd.DataFrame(
        {"features": list(X), "label": y_log, "target": y_lin,
         "id": np.arange(len(X), dtype=np.int64)}
    )
    pca = PCA(k=3, inputCol="features", float32_inputs=False).fit(df)
    lin = (
        LinearRegression(regParam=0.0, float32_inputs=False, labelCol="target")
        .setFeaturesCol("features")
        .fit(df)
    )
    lr = (
        LogisticRegression(maxIter=100, regParam=0.1, tol=1e-10, float32_inputs=False)
        .setFeaturesCol("features")
        .fit(df)
    )
    km = KMeans(k=4, maxIter=15, seed=3, float32_inputs=False).setFeaturesCol("features").fit(df)
    gnn = (
        NearestNeighbors(k=3, float32_inputs=False).setInputCol("features").setIdCol("id").fit(df)
    )
    return pca, lin, lr, km, gnn, df


@pytest.mark.parametrize("nranks", [2, 3])
def test_multiprocess_fit_matches_single_process(nranks, tmp_path):
    out_dir = _launch_workers(nranks, tmp_path)
    pca, lin, lr, km, gnn, full_df = _single_process_reference()
    from tests.mp_worker import make_dataset, split_bounds

    X, _, _ = make_dataset()
    bounds = split_bounds(len(X), nranks)

    for r in range(nranks):
        got = np.load(os.path.join(out_dir, f"rank{r}.npz"))
        np.testing.assert_allclose(got["pca_components"], pca.components_, rtol=1e-6, atol=1e-8)
        np.testing.assert_allclose(got["pca_mean"], pca.mean_, rtol=1e-6, atol=1e-10)
        np.testing.assert_allclose(
            got["pca_var_ratio"], pca.explained_variance_ratio_, rtol=1e-6
        )
        np.testing.assert_allclose(got["lin_coef"], lin.coef_, rtol=1e-6, atol=1e-8)
        np.testing.assert_allclose(got["lin_intercept"], lin.intercept_, rtol=1e-6, atol=1e-8)
        # the SORTED labels mean later ranks hold a single class locally — the
        # rendezvous class-merge must still find both classes globally
        np.testing.assert_array_equal(got["lr_classes"], lr.classes_)
        np.testing.assert_allclose(got["lr_coef"], lr.coef_, rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(got["lr_intercept"], lr.intercept_, rtol=1e-4, atol=1e-6)
        # KMeans: identical rendezvous-gathered init -> same Lloyd trajectory
        np.testing.assert_allclose(got["km_centers"], km.cluster_centers_, rtol=1e-6, atol=1e-8)
        np.testing.assert_allclose(
            float(got["km_inertia"]), km.inertia_, rtol=1e-6
        )
        # RF: tree growth is partition-layout-dependent (like cuRF) — require
        # the distributed forest to actually FIT its local slice
        # each device grows trees on its own small row shard here (~36 rows),
        # so the bar is "clearly fitted" (far above the ~0 of noise), not
        # "strongly converged" — realizations across RNG-stream changes have
        # landed between 0.52 and 0.75
        corr = np.corrcoef(got["rf_pred"], got["rf_target"])[0, 1]
        assert corr > 0.5, f"rank {r} RF pred/target correlation {corr}"
        # kNN: each rank queried its first 5 local rows against the GLOBAL
        # items; must match the single-process result for those query rows
        lo = bounds[r]
        q_rows = full_df.iloc[lo : lo + 5]
        _, _, knn_ref = gnn.kneighbors(q_rows)
        np.testing.assert_array_equal(got["knn_query_ids"], knn_ref["query_id"].to_numpy())
        np.testing.assert_array_equal(
            got["knn_indices"], np.stack(knn_ref["indices"].to_numpy())
        )
        np.testing.assert_allclose(
            got["knn_distances"], np.stack(knn_ref["distances"].to_numpy()),
            rtol=1e-7, atol=1e-6,  # self-distances are 0 ± sqrt-expansion noise
        )
        # sparse SPMD kNN (local exact + merged top-k) equals the dense result
        np.testing.assert_array_equal(
            got["knn_sp_indices"], np.stack(knn_ref["indices"].to_numpy())
        )
        np.testing.assert_allclose(
            got["knn_sp_distances"], np.stack(knn_ref["distances"].to_numpy()),
            rtol=1e-7, atol=1e-6,
        )
        # DBSCAN: replicated-data SPMD labels equal the single-process labels
        # for this rank's rows (deterministic: same full data, same program)
        from spark_rapids_ml_tpu.models.clustering import DBSCAN

        db_ref = (
            DBSCAN(eps=1.5, min_samples=3).setFeaturesCol("features").fit(full_df)
            .transform(full_df)["prediction"].to_numpy()
        )
        np.testing.assert_array_equal(got["db_labels"], db_ref[bounds[r] : bounds[r + 1]])
        # UMAP: every rank fit the same gathered data with the same seed ->
        # identical embeddings across ranks; finite and right-shaped
        emb = got["um_emb"]
        assert emb.shape == (len(X), 2) and np.isfinite(emb).all()
        if r > 0:
            ref0 = np.load(os.path.join(out_dir, "rank0.npz"))["um_emb"]
            np.testing.assert_allclose(emb, ref0, rtol=1e-6, atol=1e-7)
        # ANN with nprobe == nlist: local searches are exhaustive, so the
        # merged global top-k equals brute force (compare neighbor id sets —
        # equidistant neighbors may order differently)
        q = X[bounds[r] : bounds[r] + 5]
        d2 = ((q[:, None, :] - X[None, :, :]) ** 2).sum(-1)
        brute = np.argsort(d2, axis=1, kind="stable")[:, :3]
        for qi in range(5):
            assert set(got["ann_indices"][qi]) == set(brute[qi]), (
                f"rank {r} q{qi}: {got['ann_indices'][qi]} vs {brute[qi]}"
            )


def test_multiprocess_default_is_opt_in(tmp_path):
    # estimators without rendezvous-merged host stats must refuse SPMD fits
    from spark_rapids_ml_tpu.core import _TpuCaller
    from spark_rapids_ml_tpu.models.clustering import KMeans
    from spark_rapids_ml_tpu.models.tree import _RandomForestEstimator

    assert KMeans._supports_multiprocess  # rendezvous-merged init centers
    assert _RandomForestEstimator._supports_multiprocess  # merged classes/bins
    assert not _TpuCaller._supports_multiprocess  # default is opt-in


def test_multirank_context_requires_rendezvous():
    from spark_rapids_ml_tpu.parallel import TpuContext

    with pytest.raises(RuntimeError, match="rendezvous"):
        with TpuContext(0, 2):
            pass


def test_spmd_sweep_single_ingest_and_agreed_winner(tmp_path):
    # ISSUE 19 acceptance: a CrossValidator sweep under multi-process SPMD
    # runs through the multi-fit engine (no per-fold fallback) — each rank
    # asserts ONE ingest + ONE layout for the whole sweep in-process
    # (tests/sweep_worker.py), and the gathered held-out scoring makes the
    # metric grid and the winning param map IDENTICAL across ranks
    out_dir = _launch_workers(2, tmp_path, script="sweep_worker.py")
    got = [
        np.load(os.path.join(out_dir, f"rank{r}.npz")) for r in range(2)
    ]
    assert got[0]["avg_metrics"].shape == (3,)
    assert np.isfinite(got[0]["avg_metrics"]).all()
    # bit-identical agreement: every rank scored the SAME globalized
    # validation rows, so metrics, winner, and refit coefficients all match
    np.testing.assert_array_equal(got[0]["avg_metrics"], got[1]["avg_metrics"])
    np.testing.assert_array_equal(got[0]["best_reg"], got[1]["best_reg"])
    np.testing.assert_array_equal(got[0]["best_coef"], got[1]["best_coef"])
    assert int(got[0]["spmd_rounds"]) >= 4  # one agreement round per fit
