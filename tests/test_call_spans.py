#
# The spans inside `model.transform` and inside `fit/solve` (docs/observability.md
# "Spans"): which paths a call records and in what order, their attributes,
# what they cost while telemetry is off (the shared no-op span, nothing
# recorded), that their call sites add no host fetch to the solver layer, and
# `MetricsRegistry.delta`'s `spans_dropped`.
#
import pathlib

import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu import core, telemetry
from spark_rapids_ml_tpu.models.classification import LogisticRegression
from spark_rapids_ml_tpu.models.clustering import KMeans

ROOT = pathlib.Path(__file__).resolve().parents[1]

TRANSFORM_PATHS = [
    "transform.extract",
    "transform/construct",
    "transform/pad",
    "transform/dispatch",
    "transform/fetch",
    "transform",
    "transform.assemble",
]
SOLVE_CHILDREN = ["fit/solve/init", "fit/solve/loop", "fit/solve/finish"]
# what the process spent, on a top-level span's record (tests/test_slow_call.py holds them)
SPENT_KEYS = {"cpu_s", "thread_cpu_s", "minor_faults", "major_faults", "vol_switches", "invol_switches"}
_RECORD_KEYS = {"kind", "name", "path", "wall_s", "rank", "t0", "trace_id", "fit_id", "wait_s", "waits"} | SPENT_KEYS


@pytest.fixture
def tele():
    telemetry.registry().reset()
    telemetry.enable()
    yield telemetry.registry()
    telemetry.disable()
    telemetry.registry().reset()


def _frame(rng, n=600, d=8):
    x = rng.normal(size=(n, d)).astype(np.float32)
    return pd.DataFrame({"features": list(x), "label": (x[:, 0] > 0).astype(np.float64)})


def _attrs(span):
    return {k: v for k, v in span.items() if k not in _RECORD_KEYS}


def _kmeans(df, **kw):
    return KMeans(k=4, maxIter=3, initMode="random", seed=7, **kw).setFeaturesCol("features").fit(df)


def test_transform_records_its_steps_in_order(tele, rng):
    df = _frame(rng)
    model = _kmeans(df)
    mark = tele.mark()
    out = model.transform(df)
    delta = tele.delta(mark)
    spans = delta["spans"]
    assert [s["path"] for s in spans] == TRANSFORM_PATHS
    by_path = {s["path"]: _attrs(s) for s in spans}
    # the span that was there keeps its path and attributes exactly
    assert by_path["transform"] == {"model": "KMeansModel", "rows": 600}
    assert by_path["transform.extract"] == {"rows": 600, "cols": 8, "feature_kind": "array", "bytes": 600 * 8 * 4}
    assert by_path["transform/construct"] == {"model": "KMeansModel"}
    assert by_path["transform/pad"] == {"rows": 600, "rung": 1024}
    assert by_path["transform/dispatch"]["rows"] == 1024 and by_path["transform/dispatch"]["bytes"] == 1024 * 8 * 4
    assert isinstance(by_path["transform/dispatch"]["new_shape"], bool)
    assert by_path["transform/fetch"] == {"rows": 600}
    assert by_path["transform.assemble"] == {"rows": 600, "columns": 1}
    # the one wait of a one-piece call is the fetch's; the three top-level spans say what the process spent
    assert {s["path"]: s["waits"] for s in spans if "waits" in s} == {"transform/fetch": 1}
    assert [s["path"] for s in spans if SPENT_KEYS <= set(s)] == ["transform.extract", "transform", "transform.assemble"]
    assert delta["counters"]["transform.bytes_extracted"] == 600 * 8 * 4
    assert delta["counters"]["transform.rows"] == 600 and delta["counters"]["transform.batches"] == 1
    assert delta["spans_dropped"] == 0
    # the children lie inside `transform`, the two top-level steps on either side of it
    wall = {s["path"]: s["wall_s"] for s in spans}
    assert sum(wall[p] for p in TRANSFORM_PATHS[1:5]) <= wall["transform"]
    assert len(out) == 600 and "prediction" in out


def test_transform_batches_record_pad_dispatch_fetch_each(tele, rng, monkeypatch):
    df = _frame(rng)
    model = _kmeans(df)
    monkeypatch.setitem(core.config, "max_records_per_batch", 256)
    mark = tele.mark()
    model.transform(df)
    paths = [s["path"] for s in tele.delta(mark)["spans"]]
    batch = ["transform/pad", "transform/dispatch", "transform/fetch"]
    assert paths == TRANSFORM_PATHS[:2] + batch * 3 + TRANSFORM_PATHS[5:]


@pytest.mark.parametrize("workers, path, init_mode", [(1, "fused_1dev", "random"), (4, "shard_map", "k-means||")])
def test_kmeans_fit_records_init_loop_finish_under_solve(tele, rng, workers, path, init_mode):
    df = _frame(rng)
    mark = tele.mark()
    KMeans(k=4, maxIter=3, initMode=init_mode, seed=7, num_workers=workers).setFeaturesCol("features").fit(df)
    spans = tele.delta(mark)["spans"]
    paths = [s["path"] for s in spans]
    at = paths.index("fit/solve/init")
    assert paths[at : at + 4] == SOLVE_CHILDREN + ["fit/solve"]  # recorded at exit: children first
    assert [p for p in paths if p.startswith("fit/solve/")] == SOLVE_CHILDREN  # once per fit
    by_path = {s["path"]: _attrs(s) for s in spans}
    assert by_path["fit/solve/init"] == {"init_mode": init_mode}
    # 600 rows in one tile per device; the jnp form (CPU) has no block plan, no layout but the default,
    # and no kernel to read a tile where it lies
    assert by_path["fit/solve/loop"] == {
        "solver_path": path, "tiles_per_iter": 1, "block_rows": None, "block_k": None, "x_layout": "default",
        "tile_access": "sliced",
    }
    assert by_path["fit/solve/finish"] == {}
    # the waits that were there, each on the span that holds it: the deferred shift of iterations 2 and 3,
    # the final inertia and the model's attributes
    assert {s["path"]: s["waits"] for s in spans if "waits" in s} == {"fit/solve/loop": 2, "fit/solve/finish": 2}
    wall = {s["path"]: s["wall_s"] for s in spans}
    assert sum(wall[p] for p in SOLVE_CHILDREN) <= wall["fit/solve"]


def test_kmeans_loop_span_reports_the_block_plan_on_the_kernel_path(tele, rng, monkeypatch):
    from spark_rapids_ml_tpu.ops import distance

    monkeypatch.setattr(distance, "_MODE", "interpret")
    df = _frame(rng, n=300, d=16)
    mark = tele.mark()
    KMeans(k=5, maxIter=2, initMode="random", seed=3, num_workers=1).setFeaturesCol("features").fit(df)
    loop = next(s for s in tele.delta(mark)["spans"] if s["path"] == "fit/solve/loop")
    assert (loop["block_rows"], loop["block_k"]) == distance.plan_blocks(300, 5, 16, np.float32, True)
    assert loop["tile_access"] == "in_place"  # one 300-row tile in one 300-row block


def test_logistic_fit_records_init_loop_finish_under_solve(tele, rng):
    df = _frame(rng)
    mark = tele.mark()
    LogisticRegression(maxIter=5).setFeaturesCol("features").setLabelCol("label").fit(df)
    spans = tele.delta(mark)["spans"]
    paths = [s["path"] for s in spans]
    at = paths.index("fit/solve/init")
    assert paths[at : at + 4] == SOLVE_CHILDREN + ["fit/solve"]
    by_path = {s["path"]: _attrs(s) for s in spans}
    # the jnp form (CPU): the two products, nothing speculated (tests/test_logistic_fused.py)
    assert by_path["fit/solve/loop"] == {"solver_path": "dense", "glm_pass": "two_products", "speculated": 0}
    assert by_path["fit/solve/init"] == {} and by_path["fit/solve/finish"] == {}
    # one asynchronous program launched in `loop`, waited for in `finish`
    assert {s["path"]: s["waits"] for s in spans if "waits" in s} == {"fit/solve/finish": 1}


def test_telemetry_off_every_call_site_gets_the_shared_noop_span(rng, monkeypatch):
    telemetry.disable()
    telemetry.registry().reset()
    df = _frame(rng)
    handed = []
    real = telemetry.span

    def watching(name, **kw):
        sp = real(name, **kw)
        handed.append((name, sp))
        return sp

    monkeypatch.setattr(telemetry, "span", watching)
    waits = []
    real_wait = telemetry.device_wait
    monkeypatch.setattr(telemetry, "device_wait", lambda stage: waits.append((stage, real_wait(stage))) or waits[-1][1])
    model = _kmeans(df)
    model.transform(df)
    LogisticRegression(maxIter=3).setFeaturesCol("features").setLabelCol("label").fit(df)
    names = [n for n, _ in handed]
    # the waits the spans hold (tests/test_slow_call.py) cost a disabled call the same nothing
    assert {"kmeans_shift", "kmeans_inertia", "kmeans_finish", "predict_fetch", "finish"} <= {stage for stage, _ in waits}
    assert all(w is telemetry._NOOP_SPAN for _, w in waits)
    for name in ("transform.extract", "construct", "pad", "dispatch", "fetch", "transform.assemble"):
        assert names.count(name) == 1, name
    for name in ("init", "loop", "finish"):
        assert names.count(name) == 2, name  # the KMeans fit and the LogisticRegression fit
    assert all(sp is telemetry._NOOP_SPAN for _, sp in handed)
    telemetry._NOOP_SPAN.set(rows=1)  # attributes set inside a span cost nothing either
    snap = telemetry.snapshot()
    assert snap["spans"] == {} and "transform.bytes_extracted" not in snap["counters"]
    assert snap["slow_calls"] == [] and "telemetry.slow_calls" not in snap["counters"]


def test_span_set_adds_attributes_known_inside(tele):
    mark = tele.mark()
    with telemetry.span("outer", a=1) as sp:
        sp.set(b=2)
    (rec,) = tele.delta(mark)["spans"]
    assert (rec["path"], rec["a"], rec["b"]) == ("outer", 1, 2)


def test_span_call_sites_add_no_host_fetch_to_the_solver_layer():
    """The `hostsync` rule over the solver files the spans went into: no
    finding, and no waiver beyond the five `ops/kmeans.py` had."""
    import sys

    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from ci.analysis import analyze_source
    from ci.analysis.rules import HostSyncRule

    waivers = {}
    for rel in ("spark_rapids_ml_tpu/ops/kmeans.py", "spark_rapids_ml_tpu/ops/distance.py"):
        src = (ROOT / rel).read_text()
        assert analyze_source(src, relpath=rel, rules=[HostSyncRule()]) == []
        waivers[rel] = src.count("host-fetch-ok")
    assert waivers == {"spark_rapids_ml_tpu/ops/kmeans.py": 5, "spark_rapids_ml_tpu/ops/distance.py": 0}


def test_delta_counts_the_spans_it_dropped(tele):
    mark = tele.mark()
    with telemetry.span("kept"):
        pass
    assert tele.delta(mark)["spans_dropped"] == 0
    total = telemetry._MAX_SPAN_RECORDS + 10
    for _ in range(total):
        tele.record_span("s", "s", 0.0, {})
    delta = tele.delta(mark)
    assert delta["spans_dropped"] > 0
    assert len(delta["spans"]) + delta["spans_dropped"] == total + 1  # exact: none lost silently
