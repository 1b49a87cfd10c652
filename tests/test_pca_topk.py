#
# PCA at the protocol's shape, as far as a CPU can hold it (ISSUE 29):
#
#   (a) the estimator against the benchmark's plain reference
#       (chipbench/families/pca.py: float32 row blocks at `highest`, float64
#       sums and `numpy.linalg.eigh`) on seeded rows with zero-weight padding,
#       on one device and on the 8-device mesh, by the seven numbers that
#       decide the cell's `correct` — tight enough that the bf16 gram fails;
#   (b) the top-k eigensolver alone against float64 `numpy.linalg.eigh` on
#       planted spectra, with both of its paths taken;
#   (c) the spans and counters of a fit, for both paths and for a refit from
#       retained statistics.
#
# The compile-time guard (no whole-matrix eigh in the d = 3,000 finish
# program) sits with the other ahead-of-time compiles, tests/test_chip_bringup.py.
#
import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp

from chipbench.families import pca as reference
from spark_rapids_ml_tpu import checkpoint, core, telemetry
from spark_rapids_ml_tpu.models.feature import PCA
from spark_rapids_ml_tpu.ops import linalg

CONFIG = {"estimator": {"k": 3}, "num_workers": 1}

# Each tolerance with its reason (float32 ulp = 1.19e-7; the rows below have
# eigenvalues 145, 82, 37 over a unit bulk, so the smallest kept gap is 45 of
# lambda_1 = 145). Readings, 3 seeds x {1, 8} devices: float32 / the bf16 gram.
TOLERANCES = {
    # eigenvector error ~ (gram rounding + residual) * lambda_1 / gap: 7.4e-7..1.5e-6 / 8.1e-5..9.0e-5
    "subspace_gap": 1e-5,
    # a few ulp from the gram's float32 sums and the Ritz values: 2.2e-7..7.3e-7 / 2.5e-5..7.7e-5
    "variance_gap": 5e-6,
    # as variance_gap, with tr C's rounding: 1.7e-7..6.3e-7 / 1.6e-5..3.1e-5
    "ratio_gap": 5e-6,
    # float32 mean of 4,001 rows against float64, over a coordinate's spread: 1.6e-7..1.5e-6 (the bf16 gram leaves it alone)
    "mean_gap": 5e-6,
    # unit rows from a QR in float32: 2.2e-7..7.1e-7 (the bf16 gram leaves it alone)
    "orthonormality": 1e-5,
    # |C_ref v - lambda v| / lambda_1; the stop is 16 ulp = 1.9e-6 on the program's own C: 2.4e-7..5.6e-7 / 4.9e-5..6.3e-5
    "residual": 1e-5,
    "sign_gap": 0.0,  # exact
}


def planted_rows(seed, n=4001, d=64):
    """Unit noise, three planted directions of scale 12, 9, 6 (the benchmark's
    recipe without its blobs), a mean away from 0; n odd, so that every
    mesh pads."""
    rng = np.random.default_rng(seed)
    axes = np.linalg.qr(rng.standard_normal((d, 3)))[0].T
    X = rng.standard_normal((n, d)) + (rng.standard_normal((n, 3)) * [12.0, 9.0, 6.0]) @ axes + rng.standard_normal(d)
    return X.astype(np.float32)


def fit_numbers(X, workers, **params):
    df = pd.DataFrame({"features": list(X)})
    model = PCA(k=3, num_workers=workers, **params).setInputCol("features").fit(df)
    with jax.enable_x64(False):  # the reference's float32 blocks, as on the chip
        ref = reference.reference_fit(CONFIG, None, [jnp.asarray(X)])
        return reference.compare_fit(CONFIG, reference.outputs(model), ref, None, None), model


@pytest.mark.parametrize("workers", [1, 8])
@pytest.mark.parametrize("seed", [3, 4, 5])
def test_estimator_against_the_plain_reference(seed, workers):
    X = planted_rows(seed)
    read, model = fit_numbers(X, workers)
    assert {k: v for k, v in read.items() if not v <= TOLERANCES[k]} == {}, read
    assert model.components_.shape == (3, 64) and model.mean_.shape == (64,)
    np.testing.assert_allclose(model.singular_values_, np.sqrt(model.explained_variance_ * (len(X) - 1)), rtol=1e-6)


@pytest.mark.parametrize("workers", [1, 8])
def test_the_bf16_gram_fails_the_same_tolerances(workers):
    read, _ = fit_numbers(planted_rows(3), workers, solver_precision="bf16")
    failed = {k for k, v in read.items() if not v <= TOLERANCES[k]}
    assert {"subspace_gap", "variance_gap", "ratio_gap", "residual"} <= failed, read
    assert not failed & {"mean_gap", "orthonormality", "sign_gap"}, read  # only the gram is bf16


def test_eight_devices_equal_one():
    X = planted_rows(6)
    (_, one), (_, eight) = fit_numbers(X, 1), fit_numbers(X, 8)
    # eight partial float32 sums against one: a few ulp of the largest entry (the mean's are up to 2)
    np.testing.assert_allclose(eight.components_, one.components_, atol=2e-6)
    np.testing.assert_allclose(eight.explained_variance_, one.explained_variance_, rtol=2e-6)
    np.testing.assert_allclose(eight.mean_, one.mean_, atol=5e-6)


# --------------------------------------------------- (b) the eigensolver ----


def planted_matrix(spectrum, seed=0):
    d = len(spectrum)
    Q = np.linalg.qr(np.random.default_rng(seed).standard_normal((d, d)))[0]
    C = (Q * np.asarray(spectrum, np.float64)) @ Q.T
    return 0.5 * (C + C.T)


def low_rank_profile(d):
    """sklearn's make_low_rank_matrix at its defaults (effective_rank 10,
    tail_strength 0.5), which the protocol's recipe follows: singular values
    0.5 exp(-(i/10)^2) + 0.5 exp(-i/100); the covariance has their squares, so
    the third and fourth eigenvalues are 6 % apart."""
    i = np.arange(d)
    return (0.5 * np.exp(-((i / 10.0) ** 2)) + 0.5 * np.exp(-i / 100.0)) ** 2


SPECTRA = {
    # name: (spectrum, k, path expected)
    "well_separated": (np.concatenate([[100.0, 50.0, 25.0], np.ones(253)]), 3, "topk"),
    "low_rank_matrix": (low_rank_profile(256), 3, "topk"),
    # 40 eigenvalues within 0.4 % at the top: more than the block's 16 columns can split in the budget
    "cluster_wider_than_the_block": (np.concatenate([1.0 - 1e-4 * np.arange(40), np.linspace(0.5, 0.01, 216)]), 3, "full"),
    "rank_below_k": (np.concatenate([[5.0, 3.0], np.zeros(254)]), 3, "topk"),
    "k_equals_d": (np.linspace(4.0, 1.0, 12), 12, "full"),
    "block_over_a_quarter_of_d": (np.linspace(9.0, 1.0, 48), 3, "full"),  # k + p = 16 > 48 / 4
}


@pytest.mark.parametrize("name", list(SPECTRA))
def test_topk_eigh_against_float64(name):
    spectrum, k, path = SPECTRA[name]
    C = planted_matrix(spectrum)
    want = np.sort(np.linalg.eigvalsh(C))[::-1]
    evals, comps, ran = linalg.topk_eigh(jnp.asarray(C, jnp.float32), k)
    assert ran["eig_path"] == path, ran
    evals, V = np.asarray(evals, np.float64), np.asarray(comps, np.float64)
    assert np.isfinite(evals).all() and np.isfinite(V).all()
    assert (np.diff(evals) <= 1e-6 * want[0]).all()  # descending
    # the same answer on either path, to float32's floor: values to a few ulp of lambda_1,
    # vectors by their float64 residual (inside a cluster the vectors themselves are free)
    np.testing.assert_allclose(evals, want[:k], atol=2e-6 * want[0])
    residual = np.linalg.norm(C @ V.T - V.T * evals, axis=0).max() / want[0]
    assert residual <= 4e-6, (residual, ran)
    np.testing.assert_allclose(V @ V.T, np.eye(k), atol=1e-5)
    assert ran["residual_max"] <= 4e-6 and ran["block"] == (linalg.subspace_block(len(spectrum), k) or 0)
    if path == "topk":
        assert 0 < ran["iterations"] < 20  # a few tens of steps at most: the oversampled block at work
        # the eigenvectors themselves, where the gap defines them
        gaps = want[:k] - want[1 : k + 1]
        for j in np.flatnonzero(gaps > 0.03 * want[0]):
            ref = np.linalg.eigh(C)[1][:, ::-1][:, j]
            assert abs(V[j] @ ref) > 1 - 1e-6, (name, j)
    elif linalg.subspace_block(len(spectrum), k) is not None:
        assert ran["iterations"] == linalg.EIG_BUDGET  # the budget ran out first


def test_rank_below_k_through_the_estimator_is_clamped_and_finite():
    rng = np.random.default_rng(9)
    X = (rng.standard_normal((500, 2)) @ rng.standard_normal((2, 64))).astype(np.float32)  # rank 2, d = 64
    model = PCA(k=3).setInputCol("features").fit(pd.DataFrame({"features": list(X)}))
    assert np.isfinite(model.components_).all() and (model.explained_variance_ >= 0).all()
    assert model.explained_variance_[2] <= 1e-5 * model.explained_variance_[0]
    assert np.isfinite(model.singular_values_).all() and np.isfinite(model.explained_variance_ratio_).all()


def test_a_matrix_that_is_not_a_number_does_not_reach_the_full_decomposition():
    C = np.full((64, 64), np.nan, np.float32)
    _, _, ran = linalg.topk_eigh(jnp.asarray(C), 3)
    assert ran["eig_path"] == "topk" and np.isnan(ran["residual_max"])  # the caller's divergence guard names it


# ------------------------------------------------ (c) spans and counters ----


@pytest.fixture
def telemetry_on():
    telemetry.registry().reset()
    telemetry.enable()
    yield telemetry.registry()
    telemetry.disable()


@pytest.mark.parametrize("d,path,panel_cols", [(64, "topk", None), (32, "full", None), (64, "topk", 24)])
def test_spans_and_counters_of_a_fit(telemetry_on, gram_constants, d, path, panel_cols):
    if panel_cols:  # the gram's panel width patched under d
        gram_constants(panel_cols=panel_cols)
    X = planted_rows(7, n=600, d=d)
    model = PCA(k=3).setInputCol("features").fit(pd.DataFrame({"features": list(X)}))
    metrics = model._fit_metrics
    spans = {s["path"]: s for s in metrics["spans"]}
    assert {"fit/solve/gram", "fit/solve/eig", "fit/solve/finish"} <= set(spans)
    gram, eig = spans["fit/solve/gram"], spans["fit/solve/eig"]
    assert (gram["d"], gram["precision"], gram["x_layout"]) == (d, "f32", "default") and gram["rows"] >= 600
    # one panel (the whole contraction) under the real constant; three with it patched to 24 columns
    assert (gram["panels"], gram["panel_cols"]) == ((3, 24) if panel_cols else (1, d))
    assert eig["eig_path"] == path and eig["block"] == (16 if path == "topk" else 0)
    assert eig["residual_max"] <= 4e-6
    assert (eig["iterations"] > 0) == (path == "topk")
    counters = metrics["counters"]
    assert counters["pca.gram_passes"] == 1
    assert counters.get("pca.eig_iterations", 0) == eig["iterations"]
    assert counters.get("pca.eig_full", 0) == (1 if path == "full" else 0)
    parts = sum(spans[p]["wall_s"] for p in ("fit/solve/gram", "fit/solve/eig", "fit/solve/finish"))
    assert parts <= spans["fit/solve"]["wall_s"]


def test_a_refit_from_retained_statistics_adds_no_gram_pass(telemetry_on, monkeypatch):
    df = pd.DataFrame({"features": list(planted_rows(8, n=600))})
    reg = telemetry_on

    def passes(fit):
        mark = reg.mark()
        fit()
        delta = reg.delta(mark)
        return delta["counters"].get("pca.gram_passes", 0), [s["path"] for s in delta["spans"]]

    fit = lambda: PCA(k=3).setInputCol("features").fit(df)  # noqa: E731
    with core.device_dataset_scope():
        assert passes(fit)[0] == 1 and passes(fit)[0] == 1  # no retained statistics: one pass a fit
        monkeypatch.setitem(core.config, "checkpoint_every_iters", 1)
        with checkpoint.checkpoint_scope():
            first, second = passes(fit), passes(fit)
    assert first[0] == 1 and "fit/solve/gram" in first[1]
    assert second[0] == 0 and "fit/solve/gram" not in second[1] and "fit/solve/eig" in second[1]


# ------------------------------------------ (d) the gram over row tiles -----


@pytest.mark.parametrize("fast", [False, True])
@pytest.mark.parametrize("workers", [1, 8])
def test_the_tiled_gram_equals_the_one_contraction(monkeypatch, workers, fast):
    """More rows a shard than one tile: the float32 gram is the tile loop
    (one device), and the tile loop under `shard_map` with its psum (the
    mesh); from bf16 inputs it stays the one contraction. Same statistics as
    the one contraction, padding rows of weight 0 and a ragged last tile
    included."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.ops import pca
    from spark_rapids_ml_tpu.parallel import mesh as mesh_mod

    X = planted_rows(11, n=1208 + workers, d=24)  # a shape of its own: fresh traces under the patched tile
    n = 1216 + 8 * workers  # padded: a multiple of the mesh, rows of weight 0 at the end
    Xp = np.zeros((n, 24), np.float32)
    Xp[: len(X)] = X
    w = (np.arange(n) < len(X)).astype(np.float32)
    mesh = mesh_mod.get_mesh(workers)
    Xd = jax.device_put(Xp, NamedSharding(mesh, P(mesh_mod.ROWS_AXIS, None)))
    wd = jax.device_put(w, NamedSharding(mesh, P(mesh_mod.ROWS_AXIS)))
    with jax.enable_x64(False):
        whole = pca._pca_stats(Xd, wd, fast=fast, mesh=mesh)  # 154 rows a shard at most: under the tile
        monkeypatch.setattr(linalg, "GRAM_TILE_ROWS", 40)
        jax.clear_caches()
        tiled = pca._pca_stats(Xd, wd, fast=fast, mesh=mesh)
        text = pca._pca_stats.lower(Xd, wd, fast=fast, mesh=mesh).as_text()
    jax.clear_caches()
    # float32: the tile loop, under shard_map on the mesh; bf16 inputs: the one unbiased pass at any size
    assert ("while" in text) == (not fast) and ("shard_map" in text or "manual" in text) == (workers > 1 and not fast)
    for a, b in zip(tiled, whole):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-2 if fast else 2e-5, atol=2e-2 if fast else 2e-5)
    ref = np.cov(X.astype(np.float64), rowvar=False)
    np.testing.assert_allclose(np.asarray(tiled[2]), ref, atol=(3e-1 if fast else 2e-4))
    assert float(tiled[0]) == len(X)
