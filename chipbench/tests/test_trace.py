"""The trace reducer against hand-computed answers: a hand-made trace whose
idle share is small (an inverted share fails), and a trace recorded on the v5e."""
import json
import os
import time

import pytest

from chipbench import trace as tracing

MS = 1e6  # ns


def hand_made():
    """Two chips, a 100 ms window. Chip 0: ops over 0-40, 40-90 (a while that
    holds 45-60 and 60-85), all-reduce 90-96; idle 96-100 -> busy 96 ms.
    Chip 1: op 0-50, all-reduce 50-70 with a fusion over 55-65 beside it, op
    70-98 -> busy 98 ms."""
    ev = lambda n, a, b: [n, a * MS, (b - a) * MS]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [ev("fusion.1_fusion", 0, 40), ev("while.2_while", 40, 90), ev("k.3_tpu_custom_call", 45, 60),
                                           ev("copy.4_copy", 60, 85), ev("all-reduce.5_all-reduce", 90, 96)]},
            {"name": "XLA Modules", "events": [ev("jit_step", 0, 96)]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [ev("fusion.1_fusion", 0, 50), ev("all-reduce.5_all-reduce", 50, 70), ev("fusion.9_fusion", 55, 65),
                                           ev("k.3_tpu_custom_call", 70, 98)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [ev("chipbench/window", 0, 100), ev("chipbench/fit", 1, 99), ev("fit", 2, 98),
                                        ev("fit/solve", 10, 97)]},
            {"name": "other", "events": [ev("noise", 0, 100)]}]},
    ]}


def test_hand_made_trace():
    t = tracing.reduce(hand_made())
    assert t.window_s == pytest.approx(0.100)
    assert tracing.busy_s(t) == pytest.approx((0.096 + 0.098) / 2)
    assert tracing.idle_share(t) == pytest.approx(0.03)  # small: 1 - 0.97, not 0.97
    assert tracing.op_s(t, tracing.is_kernel) == pytest.approx((0.015 + 0.028) / 2)
    # chip 0: the whole all-reduce is exposed (6 ms); chip 1: 20 ms less the 10 ms a fusion runs beside it
    assert tracing.exposed_collective_s(t) == pytest.approx((0.006 + 0.010) / 2)
    own = tracing.self_times(t.devices[0])
    assert own["while.2_while"] == pytest.approx(0.050 - 0.015 - 0.025) and own["k.3_tpu_custom_call"] == pytest.approx(0.015)
    b = tracing.breakdown(t)
    assert b["device_ops"][0][0] == "fusion.1_fusion" and b["device_ops"][0][1] == pytest.approx(0.045)
    idle = dict(b["idle_gaps"])  # chip 0 idles 96-100: 1 ms in fit/solve, 1 in fit, 1 in chipbench/fit, 1 outside
    assert idle["fit/solve"] == pytest.approx(0.001) and idle["fit"] == pytest.approx(0.001)
    assert idle["chipbench/fit"] == pytest.approx(0.001) and idle["outside_any_span"] == pytest.approx(0.001)


def test_short_name():
    line = ('%_tile_accum_1dev.3 = (f32[1024,3000]{1,0:T(8,128)S(1)}, f32[1024,1]{1,0:T(8,128)S(1)}) '
            'custom-call(f32[32768,3000]{1,0:T(8,128)} %copy.3), custom_call_target="tpu_custom_call", x={}')
    assert tracing.short_name(line) == "_tile_accum_1dev.3_tpu_custom_call"
    assert tracing.short_name("%copy.3 = f32[32768,3000]{1,0:T(8,128)} copy(f32[32768,3000]{0,1:T(8,128)} %f)") == "copy.3_copy"
    assert tracing.short_name("%all-reduce.2 = f32[1000]{0:T(1024)} all-reduce(f32[1000]{0} %x), to_apply=%add") == "all-reduce.2_all-reduce"
    assert tracing.short_name("dot_general.1") == "dot_general.1"
    assert tracing.is_kernel("_tile_accum_1dev.3_tpu_custom_call") and not tracing.is_kernel("copy.3_copy")
    assert tracing.is_collective("all-reduce.2_all-reduce") and not tracing.is_collective("copy.3_copy")


def test_interval_arithmetic():
    assert tracing.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert tracing.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [(0, 1), (2, 4), (6, 9)]
    assert tracing.innermost([(0, 10, "a"), (2, 5, "b"), (3, 4, "c")]) == [
        (0, 2, "a"), (2, 3, "b"), (3, 4, "c"), (4, 5, "b"), (5, 10, "a")]


def test_breakdown_of_a_dense_trace_is_one_sweep():
    """A traced 20 s refit has 1e5 ops and as many host segments: the idle gaps are shared out in one sweep
    (a scan of all ops for each segment took 210 s of such a run's 360) and still add up."""
    n, us = 30_000, 1e-6
    dev = [(i * 100 * us, (i * 100 + 99) * us, f"op{i % 3}") for i in range(n)]  # 1 us idle after every op
    host = [(i * 50 * us, (i * 50 + 50) * us, f"span{i % 2}") for i in range(2 * n)]
    t0 = time.perf_counter()
    b = tracing.breakdown(tracing.Trace([dev], host, 0.0, n * 100 * us))
    assert time.perf_counter() - t0 < 10.0  # the scan took minutes here
    idle = dict(b["idle_gaps"])
    assert idle["span1"] == pytest.approx(n * us) and "span0" not in idle and "outside_any_span" not in idle
    assert sum(v for _, v in b["device_ops"]) == pytest.approx(n * 99 * us)


RECORDED = os.path.join(os.path.dirname(__file__), "fixtures", "kmeans_refit_v5e.json")


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_v5e_trace():
    with open(RECORDED) as f:
        fixture = json.load(f)
    t = tracing.reduce(fixture["events"])
    want = fixture["hand_computed"]
    assert t.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert tracing.busy_s(t) == pytest.approx(want["busy_s"], rel=1e-9)
    assert tracing.idle_share(t) == pytest.approx(want["idle_share"], abs=1e-9)
    assert tracing.idle_share(t) < 0.5  # the chip is busy in this window: an inverted share fails
    assert tracing.op_s(t, tracing.is_kernel) == pytest.approx(want["kernel_s"], rel=1e-9)
    assert tracing.exposed_collective_s(t) == pytest.approx(want["exposed_collective_s"], abs=1e-12)
