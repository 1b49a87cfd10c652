#
# A KMeans fit's final pass (ops/kmeans.py `_inertia_pass`, the out-of-core
# driver's `block_inertia`): the float32 inertia of the final centres through
# the argmin kernel alone, with no one-hot accumulate. The reported inertia
# must be, bit for bit, the one the whole float32 Lloyd step over the same
# centres gives, on every path: the one-device program, the `shard_map` step
# and the streaming chunks.
#
from functools import partial

import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu import core as core_mod
from spark_rapids_ml_tpu import telemetry
from spark_rapids_ml_tpu.models.clustering import KMeans
from spark_rapids_ml_tpu.ops import distance
from spark_rapids_ml_tpu.ops import kmeans as kmeans_ops

# resident path -> worker count; each takes `_inertia_pass`, which stands for
# the float32 `_lloyd_step`, with a mesh or none
PATHS = {"fused_1dev": 1, "shard_map": 4}
# (path, kernel mode, how the kernels get at a tile): the streaming chunks
# are placed blocks of their own, so no tile of theirs is read in place
CASES = [
    (path, mode, tiles)
    for path in [*PATHS, "streaming"]
    for mode, tiles in (("interpret", "in_place"), ("interpret", "sliced"), ("jnp", "sliced"))
    if (path, tiles) != ("streaming", "in_place")
]


@pytest.fixture
def tele():
    telemetry.registry().reset()
    telemetry.enable()
    yield telemetry.registry()
    telemetry.disable()
    telemetry.registry().reset()


def _df(n_dev):
    """197 rows a device, less three: three full 64-row tiles and a ragged
    tail each, non-uniform weights, five rows of weight 0 and, on four
    devices, the placement's three zero-weight padding rows."""
    rng = np.random.default_rng(11)
    x = rng.normal(size=(200 * n_dev - 3, 9)).astype(np.float32)
    weight = rng.uniform(0.25, 4.0, size=len(x))
    weight[-5:] = 0.0
    return pd.DataFrame({"features": list(x), "weight": weight})


def _fit(df, n_dev, precision):
    est = KMeans(k=5, maxIter=3, tol=0.0, seed=3, initMode="random", num_workers=n_dev,
                 max_samples_per_batch=64, distance_precision=precision)
    return est.setFeaturesCol("features").setWeightCol("weight").fit(df)


def _whole_step(lloyd_step, seen):
    """The final pass as the whole float32 Lloyd step over the final centres,
    of which only the inertia is kept."""

    def final_pass(X, w, centers, x_sq, *, batch_rows, in_place, **mesh):
        seen.append(in_place)
        return lloyd_step(X, w, centers, x_sq, batch_rows=batch_rows, fast=False, in_place=in_place, **mesh)[1]

    return final_pass


def _recording_pallas_calls(monkeypatch, passes):
    """[(inside the final pass, kernel name)] of every `pallas_call` traced
    from here on; the programs are traced again (caches cleared)."""
    import jax
    from jax.experimental import pallas as pl

    calls, depth = [], []
    real = pl.pallas_call

    def recording(*args, **kwargs):
        calls.append((bool(depth), kwargs.get("name")))
        return real(*args, **kwargs)

    def marked(fn):
        def call(*args, **kwargs):
            depth.append(1)
            try:
                return fn(*args, **kwargs)
            finally:
                depth.pop()

        return call

    monkeypatch.setattr(pl, "pallas_call", recording)
    for name in passes:
        monkeypatch.setattr(kmeans_ops, name, marked(getattr(kmeans_ops, name)))
    jax.clear_caches()
    return calls


def _finish_span(model):
    (finish,) = [s for s in model._fit_metrics["spans"] if s["path"].endswith("solve/finish")]
    return finish


@pytest.mark.parametrize("precision", ["fast", "high"])
@pytest.mark.parametrize("path, mode, tiles", CASES)
def test_the_final_pass_gives_the_whole_steps_inertia(tele, monkeypatch, path, mode, tiles, precision):
    """`inertia_` equals the whole float32 step's inertia bit for bit, and
    the centres and iteration count are those of that fit; the pass runs
    the float32 argmin kernel and nothing else, is counted once a fit and
    named on the `finish` span."""
    monkeypatch.setattr(distance, "_MODE", mode)
    if tiles == "sliced":
        monkeypatch.setattr(kmeans_ops, "lies_row_major", lambda x: False)  # what a column-major X reads
    if path == "streaming":
        monkeypatch.setitem(core_mod.config, "hbm_budget_bytes", 12_000)
        monkeypatch.setitem(core_mod.config, "stream_chunk_rows", 64)
        if precision == "fast":
            monkeypatch.setitem(core_mod.config, "solver_precision", "bf16")
        n_dev, passes = 1, ["block_inertia"]
    else:
        n_dev, passes = PATHS[path], ["_inertia_pass"]
    df = _df(n_dev)

    calls = _recording_pallas_calls(monkeypatch, passes)
    model = _fit(df, n_dev, precision)
    counters = tele.snapshot()["counters"]
    assert counters["kmeans.inertia_passes"] == 1
    if path == "streaming":
        assert model._fit_metrics["admission"]["verdict"] == "stream"
    else:
        assert _finish_span(model)["final_pass"] == "inertia"
        (loop,) = [s for s in model._fit_metrics["spans"] if s["path"].endswith("solve/loop")]
        assert loop["solver_path"] == path and loop["tile_access"] == tiles
    final = [name for inside, name in calls if inside]
    assert final == (["srml_argmin_f32"] * len(final) if mode == "interpret" else [])
    assert final or mode == "jnp"

    seen = []
    if path == "streaming":
        monkeypatch.setattr(kmeans_ops, "block_inertia", lambda xb, wb, c: kmeans_ops.block_assign_accumulate(xb, wb, c)[2])
    else:
        monkeypatch.setattr(kmeans_ops, "_inertia_pass", _whole_step(kmeans_ops._lloyd_step, seen))
    whole = _fit(df, n_dev, precision)
    assert tele.snapshot()["counters"]["kmeans.inertia_passes"] == 2  # one a fit
    assert seen == ([] if path == "streaming" else [tiles == "in_place"])
    assert np.float32(model.inertia_).tobytes() == np.float32(whole.inertia_).tobytes()
    assert np.isfinite(model.inertia_) and model.inertia_ > 0
    np.testing.assert_array_equal(model.cluster_centers_, whole.cluster_centers_)
    assert model.n_iter_ == whole.n_iter_ == 3


@pytest.mark.parametrize("path", list(PATHS))
def test_no_final_pass_without_final_inertia(tele, monkeypatch, path):
    """`final_inertia=False` (the ANN quantizers): NaN, no pass, not counted,
    `final_pass` `none`."""
    n_dev = PATHS[path]

    def refused(*args, **kwargs):
        raise AssertionError("a final pass ran for a fit that asked for no inertia")

    monkeypatch.setattr(kmeans_ops, "_inertia_pass", refused)
    monkeypatch.setattr(kmeans_ops, "kmeans_fit", partial(kmeans_ops.kmeans_fit, final_inertia=False))
    model = _fit(_df(n_dev), n_dev, "fast")
    assert np.isnan(model.inertia_)
    assert "kmeans.inertia_passes" not in tele.snapshot()["counters"]
    assert _finish_span(model)["final_pass"] == "none"

