#
# What a slow call leaves behind (docs/observability.md "Slow calls"): the
# waits a span holds (`wait_s` / `waits`, from `telemetry.device_wait`), what
# the process spent on a top-level span's record, the slow-call rule and its
# record, log line, flight-recorder event and dump, and what the span's own
# bookkeeping became: rank and trace tags resolved once, the flight
# recorder's overwrites counted by the recorder alone.
#
import collections
import json
import logging
import time

import jax
import numpy as np
import pandas as pd
import pytest

from spark_rapids_ml_tpu import diagnostics, telemetry
from spark_rapids_ml_tpu.models.regression import LinearRegression
from spark_rapids_ml_tpu.ops import linear

from test_call_spans import SPENT_KEYS

SLEEP = 0.5


@pytest.fixture
def tele():
    telemetry.registry().reset()
    telemetry.enable()
    yield telemetry.registry()
    telemetry.disable()
    telemetry.registry().reset()


@pytest.fixture
def recorder():
    """The process flight recorder, emptied around the test."""
    rec = diagnostics.flight_recorder()
    rec.reset()
    yield rec
    rec.reset()


@pytest.fixture
def df(rng):
    x = rng.normal(size=(400, 6)).astype(np.float32)
    return pd.DataFrame({"features": list(x), "label": x @ np.arange(1.0, 7.0) + 0.5})


def _fit(df):
    return LinearRegression(regParam=0.01, num_workers=1).setFeaturesCol("features").setLabelCol("label").fit(df)


def _sleeps_when_armed(monkeypatch, owner, name):
    """`owner.name` sleeps SLEEP seconds before its next call once the
    returned flag is set, and clears the flag."""
    real, armed = getattr(owner, name), [False]

    def slowed(*a, **kw):
        if armed[0]:
            armed[0] = False
            time.sleep(SLEEP)
        return real(*a, **kw)

    monkeypatch.setattr(owner, name, slowed)
    return armed


# where the sleep goes -> which side of the `gram` span it has to land on: the
# statistics' launch (the host's own time) or the wait for them
PATCHES = {"launch": (linear, "_dense_stats"), "wait": (jax, "block_until_ready")}


@pytest.mark.parametrize("where", sorted(PATCHES))
def test_one_slow_fit_of_twelve_is_counted_once_and_says_where(tele, recorder, df, monkeypatch, where, tmp_path):
    monkeypatch.setenv("SRML_FLIGHTREC_DIR", str(tmp_path))
    handler_lines = []
    handler = logging.Handler()
    handler.emit = lambda rec: handler_lines.append(rec.getMessage())
    from spark_rapids_ml_tpu.utils import get_logger

    get_logger("telemetry").addHandler(handler)
    try:
        armed, models = _sleeps_when_armed(monkeypatch, *PATCHES[where]), []
        for i in range(12):  # the first compiles
            armed[0] = i == 10
            models.append(_fit(df))
    finally:
        get_logger("telemetry").removeHandler(handler)
    snap = telemetry.snapshot()
    assert snap["counters"]["telemetry.slow_calls"] == 1
    (record,) = snap["slow_calls"]
    assert record["path"] == "fit" and record["wall_s"] > SLEEP and record["median_s"] < 0.2
    # the child that held the excess, and which side of its wait the excess fell on
    assert record["excess_in"] == "fit/solve/gram"
    gram = next(s for s in record["spans"] if s["path"] == "fit/solve/gram")
    host_s = gram["wall_s"] - gram["wait_s"]
    if where == "wait":
        assert gram["wait_s"] >= SLEEP and host_s < 0.2 and gram["waits"] == 1
    else:
        assert host_s >= SLEEP and gram["wait_s"] < 0.2
    # the call's own record with what the process spent: asleep, so little CPU
    assert SPENT_KEYS <= set(record["span"]) and record["span"]["path"] == "fit"
    assert record["span"]["cpu_s"] < SLEEP and record["span"]["thread_cpu_s"] <= record["span"]["cpu_s"] + 0.01
    assert {s["path"] for s in record["spans"]} >= {"fit/solve", "fit/solve/gram", "fit/solve/normal", "fit/solve/finish"}
    assert all(s["path"].startswith("fit/") for s in record["spans"]) and record["spans_left_out"] == 0
    assert len(record["gc"]["count"]) == 3 and len(record["gc"]["collections"]) == 3 and record["compile_s"] == 0.0
    if record["threads"] is not None:  # where /proc is there
        assert record["threads"]["threads"] >= 1 and record["threads_before"]["t"] <= record["threads"]["t"]
    # the slow fit's model carries its own record, the others none
    carried = [m._fit_metrics["slow_calls"] for m in models]
    assert [len(c) for c in carried] == [0] * 10 + [1, 0] and carried[10][0]["wall_s"] == record["wall_s"]
    # one WARNING line, one flight-recorder event, one dump where a directory is configured
    (line,) = handler_lines
    assert line.startswith("slow call: fit ") and "fit/solve/gram" in line and "of which waiting" in line and "minor faults" in line
    dumped = [json.loads(l) for l in open(tmp_path / "flightrec_rank_0.jsonl")]
    (event,) = [e for e in dumped if e["kind"] == "slow_call"]
    assert event["excess_in"] == "fit/solve/gram" and event["span"]["cpu_s"] == record["span"]["cpu_s"]
    assert dumped[-1]["kind"] == "flightrec_dump" and dumped[-1]["reason"].startswith("slow call: fit")
    json.dumps(snap["slow_calls"])  # the record is plain data


def _calls(walls):
    for w in walls:
        with telemetry.span("call"):
            time.sleep(w)


@pytest.mark.parametrize("before, counted", [(7, 0), (8, 1), (12, 1)])
def test_no_slow_call_in_a_paths_first_eight_calls(tele, before, counted):
    _calls([0.0] * before + [0.3])
    assert telemetry.snapshot()["counters"].get("telemetry.slow_calls", 0) == counted
    assert len(telemetry.snapshot()["slow_calls"]) == counted


def test_a_call_has_to_pass_both_thresholds_and_paths_are_judged_apart(tele):
    _calls([0.0] * 8 + [0.05])  # many times the median, under the median plus 0.25 s
    with telemetry.span("other"):  # its own path's first call
        time.sleep(0.3)
    assert "telemetry.slow_calls" not in telemetry.snapshot()["counters"]
    _calls([0.3])
    record = telemetry.snapshot()["slow_calls"][0]
    assert record["path"] == "call" and record["spans"] == [] and record["excess_in"] is None  # the excess is the call's own


def test_the_last_eight_slow_calls_are_kept(tele):
    mark = tele.mark()
    for i in range(telemetry._MAX_SLOW_CALLS + 2):
        tele._calls["call"] = collections.deque([0.0] * telemetry._SLOW_RING)  # a path with sixteen instant calls behind it
        tele.close_call({"path": "call", "wall_s": 1.0 + i}, tele.open_call())
    kept = telemetry.snapshot()["slow_calls"]
    assert [r["wall_s"] for r in kept] == [3.0 + i for i in range(telemetry._MAX_SLOW_CALLS)]
    assert telemetry.snapshot()["counters"]["telemetry.slow_calls"] == telemetry._MAX_SLOW_CALLS + 2
    assert [r["wall_s"] for r in tele.delta(mark)["slow_calls"]] == [r["wall_s"] for r in kept]
    assert tele.delta(tele.mark())["slow_calls"] == []


def test_waits_go_to_the_innermost_open_span_once(tele):
    mark = tele.mark()
    with telemetry.span("fit"):
        with telemetry.device_wait("direct"):
            time.sleep(0.01)
        with telemetry.span("solve"):
            with telemetry.span("gram"):
                for _ in range(2):
                    with telemetry.device_wait("gram"):
                        time.sleep(0.01)
            with telemetry.span("eig"):
                pass
    by_path = {s["path"]: s for s in tele.delta(mark)["spans"]}
    assert by_path["fit/solve/gram"]["waits"] == 2 and 0.02 <= by_path["fit/solve/gram"]["wait_s"] <= by_path["fit/solve/gram"]["wall_s"]
    assert by_path["fit"]["waits"] == 1 and 0.01 <= by_path["fit"]["wait_s"] < 0.02  # its own wait, not its children's
    for path in ("fit/solve", "fit/solve/eig"):  # no wait of their own: no attribute
        assert "wait_s" not in by_path[path] and "waits" not in by_path[path]
    assert sum(s.get("waits", 0) for s in by_path.values()) == 3


def test_a_fits_waits_are_each_counted_once(tele, df):
    _fit(df)
    mark = tele.mark()
    _fit(df)
    spans = tele.delta(mark)["spans"]
    waits = {s["path"]: s["waits"] for s in spans if "waits" in s}
    assert waits == {"fit/solve/gram": 1, "fit/solve/normal": 1, "fit/solve/finish": 1}
    fit = next(s for s in spans if s["path"] == "fit")
    assert 0 < sum(s.get("wait_s", 0.0) for s in spans) < fit["wall_s"]


def test_device_wait_outside_any_span_and_scope_changes_nothing(tele):
    before = telemetry.snapshot()
    wait = telemetry.device_wait("nowhere")
    assert wait is telemetry._NOOP_SPAN
    with wait:
        pass
    after = telemetry.snapshot()
    assert {k: after[k] for k in ("counters", "spans", "histograms", "slow_calls")} == {
        k: before[k] for k in ("counters", "spans", "histograms", "slow_calls")}


def test_only_top_level_spans_carry_what_the_process_spent(tele):
    mark = tele.mark()
    with telemetry.span("transform.extract"):
        sum(range(200_000))
    with telemetry.span("transform"):
        with telemetry.span("dispatch"):
            pass
    top, outer, nested = tele.delta(mark)["spans"][0], tele.delta(mark)["spans"][2], tele.delta(mark)["spans"][1]
    assert (top["path"], nested["path"], outer["path"]) == ("transform.extract", "transform/dispatch", "transform")
    assert SPENT_KEYS <= set(top) and SPENT_KEYS <= set(outer) and not SPENT_KEYS & set(nested)
    assert top["cpu_s"] > 0 and top["thread_cpu_s"] > 0 and top["minor_faults"] >= 0 and top["invol_switches"] >= 0


def test_a_spans_record_and_events_still_carry_rank_and_trace_tags(tele, recorder, df):
    mark = tele.mark()
    _fit(df)
    spans = tele.delta(mark)["spans"]
    assert spans and all(s["rank"] == 0 and s["fit_id"].startswith("fit-") and s["trace_id"] for s in spans)
    assert len({(s["trace_id"], s["fit_id"]) for s in spans}) == 1
    events = [e for e in recorder.events() if e["kind"] in ("span_begin", "span_end")]
    assert len(events) == 2 * len(spans)
    assert {(e["rank"], e["trace_id"], e["fit_id"]) for e in events} == {(0, spans[0]["trace_id"], spans[0]["fit_id"])}
    with telemetry.span("bare") as sp:  # outside a trace scope: no tags, the rank still
        pass
    bare = tele.delta(mark)["spans"][-1]
    assert bare["path"] == "bare" and bare["rank"] == 0 and "trace_id" not in bare and sp.wall_s == bare["wall_s"]


def test_events_dropped_follows_the_recorders_own_count(tele, recorder):
    for _ in range(recorder.capacity // 2 + 5):  # two events a span
        with telemetry.span("s"):
            pass
    dropped = recorder.stats()["dropped"]
    assert dropped == 10
    assert telemetry.snapshot()["counters"]["flightrec.events_dropped"] == dropped
    mark = tele.mark()
    with telemetry.span("s"):
        pass
    assert tele.delta(mark)["counters"]["flightrec.events_dropped"] == 2
    assert telemetry.snapshot()["counters"]["flightrec.events_dropped"] == recorder.stats()["dropped"] == 12


UNDER = [
    {"path": "fit/solve/init", "wall_s": 0.006}, {"path": "fit/solve/loop", "wall_s": 1.0},
    {"path": "fit/solve/finish", "wall_s": 0.7, "wait_s": 0.69, "waits": 1}, {"path": "fit/solve", "wall_s": 1.71},
]
USUAL = {"fit/solve/init": 0.006, "fit/solve/loop": 1.0, "fit/solve/finish": 0.17, "fit/solve": 1.18}


@pytest.mark.parametrize("under, usual, excess, named", [
    (UNDER, USUAL, 0.53, "fit/solve/finish"),  # not the longest span (`loop` is as long as ever): the one that grew
    (UNDER, {**USUAL, "fit/solve/finish": 0.69, "fit/solve": 1.70}, 0.53, None),  # nothing under the call grew: the call's own code
    (UNDER[3:], USUAL, 0.53, "fit/solve"),  # the children were not recorded: the deepest that holds it
    ([], {}, 0.5, None),
])
def test_excess_is_named_by_growth_over_the_paths_usual_wall(under, usual, excess, named):
    assert telemetry._excess_span(under, usual, excess) == named


def test_the_log_line_says_span_wait_and_what_the_process_spent():
    record = {
        "path": "fit", "wall_s": 3.03, "median_s": 0.34, "excess_in": "fit/solve/gram",
        "span": {"cpu_s": 0.12, "minor_faults": 41, "invol_switches": 3},
        "spans": [{"path": "fit/solve/gram", "wall_s": 2.91, "wait_s": 2.90, "waits": 1}],
        "threads": {"t": 104.2, "threads": 3, "cpu_s": {"python3": 9.0, "tpu_worker": 1.02}},
        "threads_before": {"t": 100.0, "threads": 3, "cpu_s": {"python3": 8.99, "tpu_worker": 1.0}},
    }
    assert telemetry._slow_call_line(record) == (
        "slow call: fit 3.03 s against a median of 0.34 s: fit/solve/gram 2.91 s of which waiting 2.90 s; "
        "process cpu 0.12 s, 41 minor faults, 3 involuntary switches; busiest thread in the 4.2 s before: tpu_worker 0.02 s cpu")
    bare = {**record, "excess_in": None, "threads": None}
    assert telemetry._slow_call_line(bare) == (
        "slow call: fit 3.03 s against a median of 0.34 s; process cpu 0.12 s, 41 minor faults, 3 involuntary switches")


def test_thread_table_adds_up_the_threads_of_a_name():
    table = telemetry._thread_table()
    if table is None:
        pytest.skip("no /proc here")
    assert table["threads"] >= 1 and sum(table["cpu_s"].values()) > 0
    assert all(isinstance(n, str) and c >= 0 for n, c in table["cpu_s"].items())
    time.sleep(0.01)
    assert telemetry._thread_table()["t"] > table["t"]
