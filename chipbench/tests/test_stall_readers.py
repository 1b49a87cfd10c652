"""The readers ISSUE 38 added, against hand-made windows with hand-computed
answers, and the CPU rehearsal of one refit cell and of the transform cell
reporting every metric that reads them."""
from types import SimpleNamespace

import pytest

from chipbench import trace as tracing
from chipbench.readers import (
    counter_per_call,
    span_attr_mean,
    span_attr_share,
    span_excess_s,
    span_max_over_median,
    trace_covered_share,
)

from . import tiny


def a_run(spans, calls=None, dropped=0, counters=None):
    fits = sum(1 for s in spans if s["path"] == "fit")
    telemetry = {"spans": list(spans), "spans_dropped": dropped, "counters": counters or {}}
    return SimpleNamespace(window=SimpleNamespace(calls=fits if calls is None else calls, telemetry=telemetry))


def fits(walls):
    return [{"path": "fit", "wall_s": w} for w in walls]


# nine fits of 0.30-0.38 s and one of 3.0 s: the median of the ten is (0.34 + 0.35) / 2 = 0.345
STALLED = [0.30, 0.31, 0.32, 0.33, 0.34, 3.0, 0.35, 0.36, 0.37, 0.38]
# as `rfc-p3k.refit` has: six fits of 3.33 s
SIX = [3.331, 3.334, 3.332, 3.335, 3.333, 3.336]


def test_slowest_ratio_and_stall_seconds_of_a_window_with_one_stall():
    run = a_run(fits(STALLED) + [{"path": "fit/solve", "wall_s": 9.0}])  # another path's wall is not a fit's
    assert span_max_over_median.read(run, "fit") == pytest.approx(3.0 / 0.345)
    assert span_excess_s.read(run, "fit") == pytest.approx(3.0 - 0.345)
    assert span_excess_s.read(run, "fit", ratio=10.0) == 0.0  # 3.0 is under ten medians
    clean = a_run(fits(SIX))
    assert span_max_over_median.read(clean, "fit") == pytest.approx(3.336 / 3.3335)
    assert span_excess_s.read(clean, "fit") == 0.0


def test_a_cut_list_or_no_span_gives_no_number():
    assert span_max_over_median.read(a_run(fits(STALLED), dropped=1), "fit") is None
    assert span_excess_s.read(a_run(fits(STALLED), dropped=1), "fit") is None
    assert span_max_over_median.read(a_run([]), "fit") is None and span_excess_s.read(a_run([]), "fit") is None
    # a window of one fit (a tiny rehearsal's) is its own median
    assert span_max_over_median.read(a_run(fits([0.3])), "fit") == 1.0 and span_excess_s.read(a_run(fits([0.3])), "fit") == 0.0


WAITED = [
    {"path": "fit", "wall_s": 0.200, "cpu_s": 0.05, "minor_faults": 4, "invol_switches": 1},
    {"path": "fit/solve", "wall_s": 0.190},  # no wait of its own: the attribute is missing
    {"path": "fit/solve/gram", "wall_s": 0.150, "wait_s": 0.140, "waits": 1},
    {"path": "fit/solve/finish", "wall_s": 0.030, "wait_s": 0.020, "waits": 1},
    {"path": "fit", "wall_s": 0.300, "wait_s": 0.010, "waits": 1, "cpu_s": 0.07, "minor_faults": 0, "invol_switches": 2},
    {"path": "fit/solve/gram", "wall_s": 0.250, "wait_s": 0.240, "waits": 1},
    {"path": "fitted", "wall_s": 5.0, "wait_s": 5.0},  # not under `fit`
]


def test_wait_share_sums_every_span_under_the_call_once():
    # (0.140 + 0.020 + 0.010 + 0.240) / (0.200 + 0.300)
    assert span_attr_share.read(a_run(WAITED), "fit", "wait_s") == pytest.approx(100 * 0.410 / 0.500)
    assert span_attr_share.read(a_run(WAITED, dropped=1), "fit", "wait_s") is None
    parent = [{k: v for k, v in s.items() if k not in ("wait_s", "waits")} for s in WAITED]
    assert span_attr_share.read(a_run(parent), "fit", "wait_s") is None  # a program that records no waits
    assert span_attr_share.read(a_run([]), "fit", "wait_s") is None


def test_attr_mean_is_per_window_call_over_the_named_top_level_spans():
    run = a_run(WAITED)
    assert span_attr_mean.read(run, ["fit"], "cpu_s") == pytest.approx(0.06)
    assert span_attr_mean.read(run, ["fit"], "minor_faults") == pytest.approx(2.0)
    assert span_attr_mean.read(run, ["fit"], "invol_switches") == pytest.approx(1.5)
    assert span_attr_mean.read(run, ["fit"], "major_faults") is None  # no span carries it
    assert span_attr_mean.read(a_run(WAITED, dropped=1), ["fit"], "cpu_s") is None
    # a transform call's three top-level paths, two calls of two pieces each
    call = [{"path": "transform.extract", "wall_s": 0.01, "minor_faults": 3}, {"path": "transform", "wall_s": 0.02, "minor_faults": 1},
            {"path": "transform/fetch", "wall_s": 0.01}, {"path": "transform.assemble", "wall_s": 0.01, "minor_faults": 2}]
    tops = ["transform.extract", "transform", "transform.assemble"]
    assert span_attr_mean.read(a_run(call * 4, calls=2), tops, "minor_faults") == pytest.approx(12.0)
    # the counter of the program's own detector, per fit
    assert counter_per_call.read(a_run(fits(STALLED), counters={"telemetry.slow_calls": 1.0}), "telemetry.slow_calls") == pytest.approx(0.1)
    assert counter_per_call.read(a_run(fits(STALLED)), "telemetry.slow_calls") == 0.0


def traced(last_ends_ms, window_ms=100):
    ev = lambda n, a, b: [n, a * 1e6, (b - a) * 1e6]
    planes = [{"name": f"/device:TPU:{i}", "lines": [{"name": "XLA Ops", "events": [ev("fusion.1_fusion", 0, 10), ev("fusion.2_fusion", 20, end)]}]}
              for i, end in enumerate(last_ends_ms)]
    planes.append({"name": "/host:CPU", "lines": [{"name": "main", "events": [ev("chipbench/window", 0, window_ms)]}]})
    return SimpleNamespace(trace_data=tracing.reduce({"planes": planes}))


def test_covered_share_is_where_the_last_device_op_ends():
    assert trace_covered_share.read(traced([99.5])) == pytest.approx(99.5)
    assert trace_covered_share.read(traced([50.0])) == pytest.approx(50.0)  # a trace that lost half its window
    assert trace_covered_share.read(traced([99.0, 48.0])) == pytest.approx(48.0)  # the chip whose ops end first
    assert trace_covered_share.read(traced([140.0])) == pytest.approx(100.0)  # ops are cut to the window
    no_ops = traced([99.0])
    no_ops.trace_data.devices[0].clear()
    assert trace_covered_share.read(no_ops) is None


FIT_METRICS = {"api.slowest_fit_ratio", "api.stall_s", "api.slow_calls_per_fit", "api.wait_share.fit", "api.cpu_s_per_fit",
               "api.minor_faults_per_fit", "api.invol_switches_per_fit", "trace.covered_share.fit"}
TRANSFORM_METRICS = {"transform.minor_faults_per_call", "trace.covered_share.transform"}


@pytest.mark.parametrize("name, metrics", [("linreg-p3k.refit", FIT_METRICS), ("kmeans-p3k.transform", TRANSFORM_METRICS)])
def test_cell_rehearsal_reports_the_new_metrics(name, metrics):
    assert metrics <= set(tiny.cell_metrics(name))
    res = tiny.execute(name, seed=2**31 + 12, trace=True, seconds=0.5)
    assert res["failed"] == 0 and metrics <= set(res["metrics"])
    values = {m: res["metrics"][m]["value"] for m in metrics}
    assert all(v >= 0 for v in values.values()), values
    if name.endswith(".refit"):
        # a tiny fit on a shared CPU may well take three times its median: only the chip's windows expect 0 stalled seconds
        assert values["api.slowest_fit_ratio"] >= 1.0
        assert 0 < values["api.wait_share.fit"] < 100 and values["api.cpu_s_per_fit"] > 0
    assert 0 < values["trace.covered_share." + ("fit" if name.endswith(".refit") else "transform")] <= 100


@pytest.mark.parametrize("metric", sorted(FIT_METRICS | TRANSFORM_METRICS))
def test_metric_file_lists_the_cells_that_benchmark_json_lists(metric):
    entry = next(m for m in tiny.BENCH["per_layer"] if m["name"] == metric)
    own = tiny.run.load_json("metrics", metric + ".json")
    assert own["workloads"] == entry["workloads"]
    assert {k: own[k] for k in ("unit", "better", "source", "layer", "moves")} == {k: entry[k] for k in ("unit", "better", "source", "layer", "moves")}
