#
# `model.transform` in pieces (docs/performance.md "Transform in pieces"): a
# dense object column whose batch is wider than an ingest chunk is never made
# one [n, d] block. It goes through the call piece by piece: rows filled into
# a small ring of buffers the model keeps, each piece placed and predicted
# while the next is filled. These tests force several pieces with a small
# `ingest_chunk_bytes` and hold the answers to the whole-batch path's bit for
# bit, then the spans, the counters, the memory and the ring's discipline.
#
import gc
import sys
import threading
import tracemalloc
import weakref

import numpy as np
import pandas as pd
import pytest
import scipy.sparse as sp

from spark_rapids_ml_tpu import core, data, telemetry
from spark_rapids_ml_tpu.errors import IngestValidationError
from spark_rapids_ml_tpu.linalg import Vectors
from spark_rapids_ml_tpu.models.classification import LogisticRegression, RandomForestClassifier
from spark_rapids_ml_tpu.models.clustering import KMeans
from spark_rapids_ml_tpu.models.feature import PCA
from spark_rapids_ml_tpu.models.regression import LinearRegression

D = 12
PIECE = 256  # the ladder's first rung: the smallest piece a call can have
ROW_COUNTS = [0, 1, PIECE - 1, PIECE, PIECE + 1, 3 * PIECE + 37]
TOP_LEVEL = ("transform.extract", "transform", "transform.assemble")


def _x(n, seed=5):
    # every row differs from every other, so a piece answered from another
    # piece's rows (a buffer refilled too early) shows in the predictions
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(n, D)) + np.arange(n)[:, None] % 7).astype(np.float32)


def _frame(x, rows="views"):
    if rows == "views":
        col = list(x)
    elif rows == "separate":
        col = [r.copy() for r in x]  # objects of their own, as a Spark partition's rows
    elif rows == "lists":
        col = [r.tolist() for r in x]
    else:
        col = [Vectors.dense(r) for r in x]
    y = (x[:, 0] > 3).astype(np.float64)
    return pd.DataFrame({"features": col, "label": y})


def _fit(name):
    df = _frame(_x(700, seed=1))
    if name == "kmeans":
        return KMeans(k=5, maxIter=3, initMode="random", seed=3).setFeaturesCol("features").fit(df), ["prediction"]
    if name == "pca":
        return PCA(k=3).setInputCol("features").setOutputCol("proj").fit(df), ["proj"]
    if name == "linreg":
        return LinearRegression().setFeaturesCol("features").setLabelCol("label").fit(df), ["prediction"]
    if name == "forest":
        est = RandomForestClassifier(numTrees=3, maxDepth=3, seed=2)
        return est.setFeaturesCol("features").setLabelCol("label").fit(df), ["rawPrediction", "probability", "prediction"]
    est = LogisticRegression(maxIter=5).setFeaturesCol("features").setLabelCol("label")
    return est.fit(df), ["rawPrediction", "probability", "prediction"]


@pytest.fixture(scope="module")
def models():
    return {name: _fit(name) for name in ("kmeans", "pca", "linreg", "forest", "logreg")}


@pytest.fixture
def small_pieces(monkeypatch):
    """`ingest_chunk_bytes` of one 256-row piece at d = 12."""
    monkeypatch.setitem(core.config, "ingest_chunk_bytes", PIECE * D * 4)


@pytest.fixture
def tele():
    telemetry.registry().reset()
    telemetry.enable()
    yield telemetry.registry()
    telemetry.disable()
    telemetry.registry().reset()


def _columns(out, names):
    return [np.stack([np.asarray(getattr(v, "values", v)) for v in out[c]]) if out[c].dtype == object
            else out[c].to_numpy() for c in names]


def _same(a, b):
    assert len(a) == len(b)
    for u, v in zip(a, b):
        assert u.dtype == v.dtype and u.shape == v.shape
        assert np.array_equal(u, v)


# ---------------------------------------------------------------- answers ---


@pytest.mark.parametrize("n", ROW_COUNTS)
@pytest.mark.parametrize("name", ["kmeans", "pca", "linreg", "forest", "logreg"])
def test_pieces_give_the_whole_batch_answers_bit_for_bit(models, monkeypatch, name, n):
    model, names = models[name]
    x = _x(n)
    if n == 0:  # an empty frame has no feature column to read: the zero-row block
        whole = model._transform_arrays(x)
        monkeypatch.setitem(core.config, "ingest_chunk_bytes", PIECE * D * 4)
        pieces = model._transform_arrays(x)
        whole, pieces = (list(r) if isinstance(r, tuple) else [r] for r in (whole, pieces))
        assert all(len(r) == 0 for r in pieces)
        return _same(whole, pieces)
    df = _frame(x)
    whole = _columns(model.transform(df), names)
    monkeypatch.setitem(core.config, "ingest_chunk_bytes", PIECE * D * 4)
    _same(whole, _columns(model.transform(df), names))


@pytest.mark.parametrize("rows", ["views", "separate", "lists", "dense_vector"])
def test_rows_are_copied_as_the_objects_they_are(models, monkeypatch, rows):
    model, names = models["kmeans"]
    x = _x(2 * PIECE + 11)
    whole = _columns(model.transform(_frame(x, "views")), names)
    monkeypatch.setitem(core.config, "ingest_chunk_bytes", PIECE * D * 4)
    _same(whole, _columns(model.transform(_frame(x, rows)), names))


@pytest.mark.parametrize("kind", ["csr", "multi_cols", "block"])
def test_csr_multi_cols_and_blocks_keep_their_extraction(models, tele, small_pieces, kind):
    x = _x(2 * PIECE + 11)
    if kind == "multi_cols":
        cols = [f"c{j}" for j in range(D)]
        df = pd.DataFrame(x, columns=cols)
        model = KMeans(k=4, maxIter=2, seed=1).setFeaturesCols(cols).fit(df)
    else:
        model = models["kmeans"][0]
        df = {"features": sp.csr_matrix(x) if kind == "csr" else x}
    mark = tele.mark()
    got = np.asarray(model.transform(df)["prediction"])
    delta = tele.delta(mark)
    paths = [s["path"] for s in delta["spans"]]
    # one extraction, three row views in lockstep under one `transform` span, no ring
    assert paths == ["transform.extract", "transform/construct"] + [
        "transform/pad", "transform/dispatch", "transform/fetch"] * 3 + ["transform", "transform.assemble"]
    assert "transform.pieces" not in delta["counters"]
    want = np.asarray(model._transform_arrays(x))
    assert np.array_equal(got, want)


def test_the_opt_in_scan_reads_the_whole_block_and_names_the_row(models, small_pieces, monkeypatch):
    model, names = models["kmeans"]
    x = _x(3 * PIECE)
    monkeypatch.setitem(core.config, "validate_ingest", True)
    want = _columns(model.transform(_frame(x)), names)
    monkeypatch.setitem(core.config, "validate_ingest", False)
    _same(want, _columns(model.transform(_frame(x)), names))
    monkeypatch.setitem(core.config, "validate_ingest", True)
    x[2 * PIECE + 5, 3] = np.nan
    with pytest.raises(IngestValidationError, match=rf"row {2 * PIECE + 5}"):
        model.transform(_frame(x))


# ------------------------------------------------------------- the ring ---


def test_a_wrong_length_row_in_the_third_piece_raises_and_leaves_nothing(models, small_pieces, monkeypatch):
    model, _ = models["kmeans"]
    model.__dict__.pop("_piece_ring", None)
    taken = []
    real_take = core._PieceRing.take

    def take(self, shape, dtype):
        bufs = real_take(self, shape, dtype)
        taken.extend(weakref.ref(b) for b in bufs)
        return bufs

    monkeypatch.setattr(core._PieceRing, "take", take)
    col = list(_x(4 * PIECE))
    col[2 * PIECE + 9] = np.zeros(D + 1, np.float32)
    threads = threading.active_count()
    with pytest.raises(ValueError, match=rf"row {2 * PIECE + 9} has {D + 1} entries"):
        model.transform(pd.DataFrame({"features": col}))
    assert threading.active_count() == threads
    gc.collect()
    assert len(taken) == core._PieceRing.KEEP and all(ref() is None for ref in taken)
    assert len(model._piece_ring._free) == 0  # a call that raised hands nothing back
    # today's error where the whole block is made
    monkeypatch.setitem(core.config, "ingest_chunk_bytes", 128 << 20)
    with pytest.raises(ValueError):
        model.transform(pd.DataFrame({"features": col}))


def test_a_buffer_is_refilled_only_after_its_piece_is_ready(models, small_pieces, monkeypatch):
    import jax

    model, names = models["pca"]
    x = _x(6 * PIECE + 3)
    whole_bytes = x.nbytes
    result_of = {}  # ring buffer -> the result of the piece it last held
    checked = []
    real_launch, real_fill = core.PredictProgram.launch, data.DenseRows.fill

    def launch(self, xp):
        result = real_launch(self, xp)
        base = xp if xp.base is None else xp.base
        result_of[id(base)] = result
        return result

    def fill(self, out, lo, hi):
        held = result_of.get(id(out if out.base is None else out.base))
        if held is not None:
            checked.append(all(leaf.is_ready() for leaf in jax.tree.leaves(held)))
        return real_fill(self, out, lo, hi)

    monkeypatch.setattr(core.PredictProgram, "launch", launch)
    monkeypatch.setattr(data.DenseRows, "fill", fill)
    got = _columns(model.transform(_frame(x)), names)
    assert len(checked) == 7 - core._PieceRing.KEEP and all(checked)
    monkeypatch.undo()
    monkeypatch.setitem(core.config, "ingest_chunk_bytes", 2 * whole_bytes)
    _same(_columns(model.transform(_frame(x)), names), got)


def test_the_model_keeps_its_ring_between_calls(models, small_pieces, monkeypatch):
    model, _ = models["kmeans"]
    model.__dict__.pop("_piece_ring", None)
    df = _frame(_x(3 * PIECE))
    model.transform(df)
    kept = [id(b) for b in model._piece_ring._free]
    assert len(kept) == core._PieceRing.KEEP
    model.transform(df)
    assert sorted(id(b) for b in model._piece_ring._free) == sorted(kept)  # no buffer was made anew
    # a piece of another shape gets buffers of its own, and at most KEEP are kept
    monkeypatch.setitem(core.config, "ingest_chunk_bytes", 2 * PIECE * D * 4)
    model.transform(df)
    free = list(model._piece_ring._free)
    assert len(free) == core._PieceRing.KEEP and all(b.shape == (2 * PIECE, D) for b in free)


def test_two_calls_at_once_on_one_model_agree_with_the_calls_in_turn(models, small_pieces):
    model, names = models["kmeans"]
    frames = [_frame(_x(3 * PIECE + 10 * t, seed=t), "separate") for t in range(6)]
    want = [_columns(model.transform(df), names) for df in frames]
    got, errors = {}, []

    def call(t):
        try:
            for _ in range(3):
                got[t] = _columns(model.transform(frames[t]), names)
        except Exception as e:  # read below: a thread's exception must fail the test
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=call, args=(t,)) for t in range(6)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not errors and not any(th.is_alive() for th in threads)
    for t in range(6):
        _same(want[t], got[t])
    assert len(model._piece_ring._free) <= core._PieceRing.KEEP


# --------------------------------------------------- spans and counters ---


def test_a_several_piece_call_records_its_pieces(models, tele, small_pieces):
    model, _ = models["kmeans"]
    n = 3 * PIECE + 37
    df = _frame(_x(n))
    model.transform(df)  # the ring's buffers exist after this one
    mark = tele.mark()
    model.transform(df)
    delta = tele.delta(mark)
    spans = delta["spans"]
    paths = [s["path"] for s in spans]
    piece = ["transform/pad", "transform/dispatch", "transform/stall", "transform"]
    assert paths == (
        ["transform.extract", "transform/construct"] + piece
        + (["transform.extract"] + piece) * 2
        + ["transform.extract", "transform/pad", "transform/dispatch", "transform/stall"]
        + ["transform/fetch"] * 4 + ["transform", "transform.assemble"]
    )
    rows = [PIECE, PIECE, PIECE, 37]
    by = lambda path: [s for s in spans if s["path"] == path]  # noqa: E731
    assert [s["rows"] for s in by("transform.extract")] == rows
    assert [s["bytes"] for s in by("transform.extract")] == [r * D * 4 for r in rows]
    assert by("transform.extract")[0]["cols"] == D and by("transform.extract")[0]["feature_kind"] == "array"
    assert [(s["rows"], s["pieces"], s["piece_rows"], s["model"]) for s in by("transform")] == [
        (r, 4, PIECE, "KMeansModel") for r in rows]
    assert [(s["rows"], s["rung"]) for s in by("transform/pad")] == [(PIECE, PIECE)] * 3 + [(37, 256)]
    assert [s["piece"] for s in by("transform/stall")] == [0, 1, 2, 3]  # one a piece
    assert [s["rows"] for s in by("transform/fetch")] == rows
    assert len(by("transform/construct")) == 1
    counters = delta["counters"]
    assert counters["transform.pieces"] == 4 and counters["transform.batches"] == 1
    assert counters["transform.rows"] == n and counters["transform.bytes_extracted"] == n * D * 4
    assert delta["spans_dropped"] == 0
    # no two top-level spans of the call overlap, so their walls add up to at most the call
    top = sorted((s for s in spans if s["path"] in TOP_LEVEL), key=lambda s: s["t0"])
    assert len(top) == 4 + 4 + 1
    for a, b in zip(top, top[1:]):
        assert a["t0"] + a["wall_s"] <= b["t0"] + 2e-3  # t0 is the wall clock's, wall_s perf_counter's
    children = sum(s["wall_s"] for s in spans if s["path"].startswith("transform/"))
    assert children <= sum(s["wall_s"] for s in by("transform"))


def test_a_one_piece_call_has_no_ring_and_no_stall(models, tele, small_pieces):
    model, _ = models["kmeans"]
    model.__dict__.pop("_piece_ring", None)
    mark = tele.mark()
    model.transform(_frame(_x(PIECE)))
    delta = tele.delta(mark)
    assert [s["path"] for s in delta["spans"]] == [
        "transform.extract", "transform/construct", "transform/pad", "transform/dispatch", "transform/fetch",
        "transform", "transform.assemble"]
    assert "transform.pieces" not in delta["counters"] and "_piece_ring" not in model.__dict__


def test_telemetry_off_a_several_piece_call_records_nothing(models, small_pieces):
    telemetry.disable()
    telemetry.registry().reset()
    model, names = models["kmeans"]
    out = model.transform(_frame(_x(2 * PIECE + 1)))
    assert len(out) == 2 * PIECE + 1
    snap = telemetry.snapshot()
    assert snap["spans"] == {} and not [k for k in snap["counters"] if k.startswith("transform")]


# ------------------------------------------------ no host copy of the batch ---


def test_no_block_of_the_whole_partition_is_allocated(monkeypatch):
    d, piece, n_pieces = 64, 256, 16
    x = np.random.default_rng(0).normal(size=(n_pieces * piece, d)).astype(np.float32)
    km = KMeans(k=4, maxIter=2, seed=1).setFeaturesCol("features").fit(pd.DataFrame({"features": list(x[:600])}))
    df = pd.DataFrame({"features": list(x)})
    monkeypatch.setitem(core.config, "ingest_chunk_bytes", piece * d * 4)
    km.transform(df)  # compiled, and the ring's buffers made
    piece_bytes = piece * d * 4
    gc.collect()
    tracemalloc.start()
    try:
        tracemalloc.reset_peak()
        before = tracemalloc.get_traced_memory()[0]
        out = km.transform(df)
        peak = tracemalloc.get_traced_memory()[1] - before
    finally:
        tracemalloc.stop()
    assert len(out) == n_pieces * piece
    assert peak < 4 * piece_bytes, (peak, piece_bytes)  # the whole block is 16 pieces


@pytest.mark.parametrize("several", [False, True])
def test_predict_is_handed_a_device_array(models, monkeypatch, several):
    import jax

    model, _ = models["linreg"]
    seen = []
    construct, predict, extra = model._get_transform_func()

    def watching(state, xb):
        seen.append(type(xb))
        return predict(state, xb)

    monkeypatch.setattr(model, "_get_transform_func", lambda: (construct, watching, extra))
    if several:
        monkeypatch.setitem(core.config, "ingest_chunk_bytes", PIECE * D * 4)
    model.transform(_frame(_x(2 * PIECE + 5)))
    assert len(seen) == (3 if several else 1)
    assert all(issubclass(t, jax.Array) for t in seen)  # `xb.astype(dtype)` is the device's, never a host copy


# ---------------------------------------------------- the plan and the fill ---


@pytest.mark.parametrize("d, batch, chunk, want", [
    (3000, 1 << 16, 128 << 20, 8192),  # the protocol's width: 96 MB pieces, eight a 65,536-row call
    (8, 1 << 16, 128 << 20, 1 << 16),  # small rows: the piece is the batch
    (8, 256, 128 << 20, 256),
    (3000, 1 << 16, 1, 256),  # never under the ladder's first rung
    (3000, 4096, 128 << 20, 4096),  # never more than the batch
])
def test_the_piece_is_the_largest_rung_under_an_ingest_chunk(models, monkeypatch, d, batch, chunk, want):
    monkeypatch.setitem(core.config, "max_records_per_batch", batch)
    monkeypatch.setitem(core.config, "ingest_chunk_bytes", chunk)
    assert models["kmeans"][0]._transform_plan(100, d * 4) == (batch, None, want)


def test_the_mesh_path_keeps_one_row_sharded_batch_a_piece(monkeypatch):
    x = _x(600)
    model = KMeans(k=4, maxIter=2, seed=1, num_workers=4).setFeaturesCol("features").fit(_frame(x))
    monkeypatch.setitem(core.config, "distributed_transform_min_rows", 512)
    monkeypatch.setitem(core.config, "max_records_per_batch", 64)
    monkeypatch.setitem(core.config, "ingest_chunk_bytes", 1)
    batch, mesh, piece = model._transform_plan(600, D * 4)
    assert (batch, piece, mesh.devices.size) == (256, 256, 4)
    got = model.transform(_frame(x))["prediction"].to_numpy()
    monkeypatch.setitem(core.config, "distributed_transform_min_rows", 1 << 30)
    monkeypatch.setitem(core.config, "max_records_per_batch", 1 << 16)
    assert np.array_equal(got, model.transform(_frame(x))["prediction"].to_numpy())


@pytest.mark.parametrize("rows", ["views", "separate", "lists", "dense_vector"])
def test_fill_copies_a_row_range_into_the_callers_buffer(rows):
    x = _x(40)
    col, kind = data._column_to_matrix(_frame(x, rows)["features"], np.float32)
    assert isinstance(col, data.DenseRows) and col.shape == (40, D) and col.nbytes == x.nbytes
    assert kind == ("vector" if rows == "dense_vector" else "array")
    buf = np.full((16, D), -1, np.float32)
    col.fill(buf[:9], 30, 39)
    assert np.array_equal(buf[:9], x[30:39]) and (buf[9:] == -1).all()
    col.fill(buf[:0], 5, 5)
    assert np.array_equal(col.block(), x)


def test_fill_refuses_a_buffer_it_cannot_fill_in_place():
    # `+ [None]`: numpy would make a 2-D object array of eight equal rows
    col = data.DenseRows(np.array(list(_x(8)) + [None], dtype=object)[:8], D, np.float32)
    with pytest.raises(ValueError, match="contiguous"):
        col.fill(np.empty((8, 2 * D), np.float32)[:, :D], 0, 8)
    with pytest.raises(ValueError, match="contiguous"):
        col.fill(np.empty((7, D), np.float32), 0, 8)
    col.values[3] = np.zeros(D - 1, np.float32)
    col.values[4] = np.zeros(D + 1, np.float32)  # the two lengths add up: still refused, by row
    with pytest.raises(ValueError, match=rf"row 3 has {D - 1} entries"):
        col.fill(np.empty((8, D), np.float32), 0, 8)


def test_the_fit_ingest_goes_through_the_same_fill(tele, monkeypatch):
    x = _x(1000)
    monkeypatch.setitem(core.config, "ingest_chunk_bytes", 300 * D * 4)
    mark = tele.mark()
    ex = data.extract_dataset(_frame(x, "separate"), input_col="features")
    assert isinstance(ex.features, np.ndarray) and np.array_equal(ex.features, x)
    assert tele.delta(mark)["counters"]["ingest.chunks"] == 4
    rows = data.extract_dataset(_frame(x), input_col="features", dense_rows=True)
    assert isinstance(rows.features, data.DenseRows) and rows.n_rows == 1000 and rows.n_cols == D
