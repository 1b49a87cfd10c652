#
# X's layout on the device (docs/performance.md "Tiled distance core"): KMeans
# asks the placement for row-major rows, the layout the Pallas distance
# kernels read; everything else keeps the device's default. On CPU (and at d a
# multiple of 128) that IS row-major, so the placement must be exactly
# today's call; the piecewise row-major path is driven here by handing
# `row_major_format` a CPU `Format`, which this backend takes. What the chip's
# compiler makes of it is pinned in tests/test_distance.py.
#
import numpy as np
import pandas as pd
import pytest

import jax

from spark_rapids_ml_tpu import core, memory, telemetry
from spark_rapids_ml_tpu.data import ExtractedData
from spark_rapids_ml_tpu.models.classification import LogisticRegression
from spark_rapids_ml_tpu.models.clustering import KMeans
from spark_rapids_ml_tpu.models.feature import PCA
from spark_rapids_ml_tpu.models.regression import LinearRegression
from spark_rapids_ml_tpu.ops import distance
from spark_rapids_ml_tpu.parallel import get_mesh
from spark_rapids_ml_tpu.parallel import mesh as mesh_mod


@pytest.fixture
def tele():
    telemetry.registry().reset()
    telemetry.enable()
    yield telemetry.registry()
    telemetry.disable()
    telemetry.registry().reset()


@pytest.fixture
def forced_row_major(monkeypatch):
    """Make `row_major_format` answer as a TPU would at d = 3,000: a `Format`
    for every 2-D block, in small pieces, so the piecewise path runs here."""
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    def fmt(shape, dtype, device):
        return Format(Layout(major_to_minor=(0, 1)), SingleDeviceSharding(device))

    monkeypatch.setattr(mesh_mod, "row_major_format", fmt)
    monkeypatch.setattr(mesh_mod, "_ROW_MAJOR_PIECE_BYTES", 2048)


def _df(rng, n=240, d=6, label=False):
    x = rng.normal(size=(n, d)).astype(np.float32)
    cols = {"features": list(x)}
    if label:
        cols["label"] = (x[:, 0] > 0).astype(np.float64)
    return pd.DataFrame(cols)


# ------------------------------------------------------------ who asks -----


def test_kmeans_asks_for_row_major():
    assert KMeans._x_layout == mesh_mod.X_ROW_MAJOR == "row_major"
    assert core._TpuEstimator._x_layout == mesh_mod.X_DEFAULT == "default"


@pytest.mark.parametrize("estimator", [LogisticRegression, LinearRegression, PCA])
def test_other_estimators_keep_the_default(estimator):
    assert estimator._x_layout == "default"


def test_unknown_layout_is_refused(rng):
    x = rng.normal(size=(16, 3)).astype(np.float32)
    with pytest.raises(ValueError, match="x_layout"):
        mesh_mod.make_global_rows(get_mesh(1), x, x_layout="column_major")


# ------------------------------------------------- the placement's call -----


@pytest.mark.parametrize("n_dev", [1, 4])
def test_cpu_placement_is_todays_call(monkeypatch, rng, n_dev):
    """Off the TPU an ask for row-major changes nothing: `device_put` is
    handed plain devices, and no `Format` is constructed at all."""
    from jax.experimental import layout as jax_layout

    def no_format(*args, **kwargs):
        raise AssertionError("a Format was constructed on the CPU placement path")

    monkeypatch.setattr(jax_layout, "Format", no_format)
    targets = []
    real = jax.device_put

    def recording(x, device=None, **kwargs):
        targets.append(device)
        return real(x, device, **kwargs)

    monkeypatch.setattr(jax, "device_put", recording)
    x = rng.normal(size=(103, 7)).astype(np.float32)
    mesh = get_mesh(n_dev)
    X, w, n_valid = mesh_mod.make_global_rows(mesh, x, x_layout="row_major")
    flat = [d for t in targets for d in (t if isinstance(t, (list, tuple)) else [t])]
    assert flat and all(isinstance(d, jax.Device) for d in flat)
    assert n_valid == 103
    np.testing.assert_array_equal(np.asarray(X)[:103], x)
    assert mesh_mod.x_layout_of(X) == "default"


@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("rows", [5, 96, 1003])
def test_piecewise_row_major_placement_matches_the_default(forced_row_major, tele, rng, n_dev, rows):
    """The rows written piece by piece into a buffer made on the device are
    the rows `device_put` would have placed: same values, shape, sharding,
    also where the last piece is moved back over rows already written."""
    x = rng.normal(size=(rows, 37)).astype(np.float32)
    mesh = get_mesh(n_dev)
    X0, w0, _ = mesh_mod.make_global_rows(mesh, x)
    assert "placement.row_major" not in tele.snapshot()["counters"]
    X1, w1, _ = mesh_mod.make_global_rows(mesh, x, x_layout="row_major")
    assert tele.snapshot()["counters"]["placement.row_major"] == 1
    assert X1.shape == X0.shape and X1.sharding == X0.sharding and X1.dtype == X0.dtype
    np.testing.assert_array_equal(np.asarray(X1), np.asarray(X0))
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w0))
    for a, b in zip(X0.addressable_shards, X1.addressable_shards):
        assert a.device == b.device and a.index == b.index


@pytest.mark.parametrize("n_dev", [1, 4])
def test_local_rows_assembly_matches_multihost_utils(forced_row_major, rng, n_dev):
    """The multi-process arm of `place_rows`, run in this one process: the
    global array assembled from per-device row-major shards is the one
    `host_local_array_to_global_array` builds."""
    from jax.experimental import multihost_utils
    from jax.sharding import PartitionSpec as P

    mesh = get_mesh(n_dev)
    xp = rng.normal(size=(n_dev * 24, 5)).astype(np.float32)
    want = multihost_utils.host_local_array_to_global_array(xp, mesh, P(mesh_mod.ROWS_AXIS))
    got = mesh_mod._place_local_rows(mesh, xp, "row_major")
    assert got.shape == want.shape and got.sharding == want.sharding
    for a, b in zip(want.addressable_shards, got.addressable_shards):
        assert a.device == b.device and a.index == b.index
        np.testing.assert_array_equal(np.asarray(a.data), np.asarray(b.data))


def test_x_layout_of_names_a_non_default_row_major_array(monkeypatch, rng):
    X = jax.device_put(rng.normal(size=(64, 9)).astype(np.float32), jax.devices()[0])
    assert mesh_mod.x_layout_of(X) == "default"  # row-major is this backend's own choice
    monkeypatch.setattr(mesh_mod, "_default_is_row_major", lambda shape, dtype, device: False)
    assert mesh_mod.x_layout_of(X) == "row_major"
    assert mesh_mod.x_layout_of(X[0]) == "default"  # not a 2-D block


# --------------------------------------------------- what shares a placement -----


def test_scope_keeps_placements_of_different_layouts_apart(tele, rng):
    """Two KMeans fits in one scope share one placement; a fit that does not
    ask for row-major never gets KMeans's (LogisticRegression, and PCA, which
    reads the very same columns)."""
    df = _df(rng, label=True)
    km = KMeans(k=3, maxIter=2, seed=1, initMode="random").setFeaturesCol("features")
    layouts = []
    with core.device_dataset_scope() as scope:
        km.fit(df)
        km.copy().setSeed(2).fit(df)
        counters = tele.snapshot()["counters"]
        assert counters["fit.device_dataset_builds"] == 1
        assert counters["fit.device_dataset_reuses"] == 1
        layouts.append(scope.last.key[2][2])
        LogisticRegression(maxIter=2).setFeaturesCol("features").fit(df)
        assert tele.snapshot()["counters"]["fit.device_dataset_builds"] == 2
        layouts.append(scope.last.key[2][2])
        PCA(k=2).setInputCol("features").fit(df)
        assert tele.snapshot()["counters"]["fit.device_dataset_builds"] == 3
        layouts.append(scope.last.key[2][2])
    assert layouts == ["row_major", "default", "default"]


def test_device_dataset_key_carries_the_layout(rng):
    from types import SimpleNamespace

    df = _df(rng)
    ctx = SimpleNamespace(mesh=get_mesh(1))
    km_key = KMeans(k=2).setFeaturesCol("features")._device_dataset_key(df, ctx)
    pca_key = PCA(k=2).setInputCol("features")._device_dataset_key(df, ctx)
    assert km_key[2] == ("float32", False, "row_major")
    assert pca_key[2] == ("float32", False, "default")
    assert km_key[:2] == pca_key[:2] and km_key[3] == pca_key[3]


# ------------------------------------------------------------ admission -----


@pytest.mark.parametrize("d, padded", [(3000, 3072), (3072, 3072), (128, 128), (5, 128)])
def test_admission_counts_the_lane_padding_of_a_row_major_x(d, padded):
    ex = ExtractedData(
        features=np.zeros((64, d), np.float32), label=None, feature_names=["features"]
    )
    default = memory.placement_terms(ex, np.float32, 4)
    asked = memory.placement_terms(ex, np.float32, 4, "row_major")
    assert default["placement.X"] == 16 * d * 4
    assert asked["placement.X"] == 16 * padded * 4
    assert {k: v for k, v in asked.items() if k != "placement.X"} == {
        k: v for k, v in default.items() if k != "placement.X"
    }
    km = memory.resident_estimate(KMeans(k=2), ex, 4).terms["placement.X"]
    pca = memory.resident_estimate(PCA(k=2), ex, 4).terms["placement.X"]
    assert (km, pca) == (16 * padded * 4, 16 * d * 4)


# ------------------------------------------------------ the fit itself -----


def test_loop_span_says_what_layout_it_steps_over(tele, rng):
    model = KMeans(k=3, maxIter=2, seed=1, num_workers=1).setFeaturesCol("features").fit(_df(rng))
    loops = [s for s in model._fit_metrics["spans"] if s["path"].endswith("solve/loop")]
    assert len(loops) == 1
    assert loops[0]["x_layout"] == "default"  # CPU: nothing but the default exists
    assert loops[0]["solver_path"] == "fused_1dev"


@pytest.mark.parametrize("num_workers", [1, 4])
def test_centres_do_not_depend_on_who_placed_x(forced_row_major, tele, monkeypatch, rng, num_workers):
    """Kernels through the interpreter: a fit on the piecewise row-major
    placement gives, bit for bit, the centres of the fit with the ask
    switched off."""
    monkeypatch.setattr(distance, "_MODE", "interpret")
    df = _df(rng, n=300, d=9)

    def fit():
        est = KMeans(k=4, maxIter=4, seed=3, initMode="random", num_workers=num_workers)
        return est.setFeaturesCol("features").fit(df)

    asked = fit()
    assert tele.snapshot()["counters"]["placement.row_major"] == 1
    monkeypatch.setattr(KMeans, "_x_layout", "default")
    plain = fit()
    assert tele.snapshot()["counters"]["placement.row_major"] == 1
    np.testing.assert_array_equal(asked.cluster_centers_, plain.cluster_centers_)
    assert asked.inertia_ == plain.inertia_ and asked.n_iter_ == plain.n_iter_


# -------------------------------------------- how the kernels get at a tile -----
#
# Where X lies row-major, the kernels are on and a tile is whole row blocks,
# `kmeans_fit` has the kernels index X where it lies (`tile_access`
# `in_place`: no tile of X is copied); anything else slices the tile out first.


def _loop_span(model):
    (loop,) = [s for s in model._fit_metrics["spans"] if s["path"].endswith("solve/loop")]
    return loop


@pytest.mark.parametrize("solver_path, num_workers", [("fused_1dev", 1), ("shard_map", 4)])
def test_centres_do_not_depend_on_how_the_kernels_get_at_a_tile(tele, monkeypatch, rng, solver_path, num_workers):
    """Kernels through the interpreter, 10 iterations, four 64-row tiles a
    device: read in place, the fit gives the sliced fit's centres bit for
    bit on every Lloyd path (the row norms alone are summed elsewhere)."""
    from spark_rapids_ml_tpu.ops import kmeans as kmeans_ops

    monkeypatch.setattr(distance, "_MODE", "interpret")
    df = _df(rng, n=256 * num_workers, d=9)

    def fit():
        est = KMeans(k=5, maxIter=10, tol=0.0, seed=3, initMode="random",
                     num_workers=num_workers, max_samples_per_batch=64)
        return est.setFeaturesCol("features").fit(df)

    whole = fit()
    assert _loop_span(whole)["tile_access"] == "in_place"
    assert _loop_span(whole)["solver_path"] == solver_path and _loop_span(whole)["tiles_per_iter"] == 4
    monkeypatch.setattr(kmeans_ops, "lies_row_major", lambda x: False)  # what a column-major X reads
    sliced = fit()
    assert _loop_span(sliced)["tile_access"] == "sliced"
    np.testing.assert_array_equal(whole.cluster_centers_, sliced.cluster_centers_)
    assert whole.n_iter_ == sliced.n_iter_ == 10
    assert whole.inertia_ == pytest.approx(sliced.inertia_, rel=1e-6)


@pytest.mark.parametrize("mode, rows, batch, expected", [
    ("interpret", 256, 64, "in_place"),
    ("interpret", 256, 32768, "in_place"),  # one tile: all of X
    ("interpret", 700, 32768, "sliced"),  # 700 rows are no whole 512-row blocks
    ("interpret", 250, 64, "in_place"),  # full tiles in place, the 58-row tail sliced
    ("jnp", 256, 64, "sliced"),
])
def test_loop_span_says_how_the_kernels_get_at_a_tile(tele, monkeypatch, rng, mode, rows, batch, expected):
    monkeypatch.setattr(distance, "_MODE", mode)
    est = KMeans(k=3, maxIter=2, seed=1, initMode="random", num_workers=1, max_samples_per_batch=batch)
    model = est.setFeaturesCol("features").fit(_df(rng, n=rows, d=6))
    assert _loop_span(model)["tile_access"] == expected
    assert _loop_span(model)["x_layout"] == "default"  # row-major by the CPU's own choice
    assert np.isfinite(model.inertia_)


def test_row_norms_are_made_once_a_fit(tele, monkeypatch, rng):
    """One program a fit computes |x|^2, whatever the iteration count, and
    the step programs take its result (the float32 final pass included)."""
    from spark_rapids_ml_tpu.ops import kmeans as kmeans_ops

    calls = []
    real = kmeans_ops._row_norms

    def counting(x):
        calls.append(x.shape)
        return real(x)

    monkeypatch.setattr(kmeans_ops, "_row_norms", counting)
    df = _df(rng, n=200, d=6)
    for iters in (1, 7):
        KMeans(k=3, maxIter=iters, tol=0.0, seed=1, initMode="random", num_workers=1).setFeaturesCol("features").fit(df)
    assert calls == [(200, 6), (200, 6)]


def test_lies_row_major_reads_the_committed_layout(rng):
    X = jax.device_put(rng.normal(size=(64, 9)).astype(np.float32), jax.devices()[0])
    assert mesh_mod.lies_row_major(X)  # this backend's own choice
    assert not mesh_mod.lies_row_major(X[0])  # not a 2-D block
    assert not mesh_mod.lies_row_major(np.asarray(X))  # not on a device
    Xs, _, _ = mesh_mod.make_global_rows(get_mesh(4), np.asarray(X))
    assert mesh_mod.lies_row_major(Xs)
    assert not mesh_mod.lies_row_major(_column_major(np.asarray(X)))


def _column_major(x):
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    return jax.device_put(x, Format(Layout(major_to_minor=(1, 0)), SingleDeviceSharding(jax.devices()[0])))


def test_a_column_major_x_keeps_its_tiles_sliced(tele, monkeypatch, rng):
    """What a TPU holds at d = 3,000 when nobody asked for row-major (the ANN
    coarse quantizer's local fits): `kmeans_fit` reads the layout off the
    array, slices, and gives the row-major fit's centres."""
    from spark_rapids_ml_tpu.ops.kmeans import kmeans_fit

    monkeypatch.setattr(distance, "_MODE", "interpret")
    x = rng.normal(size=(256, 9)).astype(np.float32)
    w = jax.numpy.ones((256,), np.float32)
    centres = {}
    for name, X in (("in_place", jax.device_put(x, jax.devices()[0])), ("sliced", _column_major(x))):
        mark = tele.mark()
        state = kmeans_fit(X, w, x[:4], mesh=get_mesh(1), max_iter=5, tol=0.0, batch_rows=64)
        (loop,) = [s for s in tele.delta(mark)["spans"] if s["path"].endswith("loop")]
        assert loop["tile_access"] == name
        centres[name] = np.asarray(state["cluster_centers_"])
    np.testing.assert_array_equal(centres["in_place"], centres["sliced"])


# ------------------------------------------- the persistent compile cache -----


def test_row_major_buffers_are_made_by_programs_compiled_in_process(forced_row_major, monkeypatch, rng):
    """An executable read back from the persistent cache reports the default
    layout for its outputs (found on the chip, PR 28), so the programs that
    make a row-major buffer compile with the cache off, and the switch is put
    back as it was."""
    seen = []
    real = jax.jit
    before = jax.config.jax_enable_compilation_cache

    def recording(fn, *args, **kwargs):
        jitted = real(fn, *args, **kwargs)

        def call(*a, **k):
            seen.append(jax.config.jax_enable_compilation_cache)
            return jitted(*a, **k)

        return call

    monkeypatch.setattr(mesh_mod.jax, "jit", recording)
    mesh_mod._row_major_zeros.cache_clear()
    mesh_mod._row_major_write.cache_clear()
    try:
        for was in (True, False):
            jax.config.update("jax_enable_compilation_cache", was)
            x = rng.normal(size=(40, 5)).astype(np.float32)
            X, _, _ = mesh_mod.make_global_rows(get_mesh(1), x, x_layout="row_major")
            np.testing.assert_array_equal(np.asarray(X), x)
            assert jax.config.jax_enable_compilation_cache is was
        assert seen and not any(seen)
    finally:
        mesh_mod._row_major_zeros.cache_clear()  # drop the recording wrappers
        mesh_mod._row_major_write.cache_clear()
        jax.config.update("jax_enable_compilation_cache", before)
