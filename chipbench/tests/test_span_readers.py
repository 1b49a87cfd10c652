"""The readers ISSUE 27 added, against hand-made event lists with
hand-computed answers, and the CPU rehearsal of every cell reporting every
metric that reads a new span."""
from types import SimpleNamespace

import pytest

from chipbench import trace as tracing
from chipbench.readers import nonkernel_ms_per_iter, op_ms_per_iter, unspanned_share

from . import tiny

MS = 1e6  # ns


def named_trace(names=("srml_argmin_bf16.3", "srml_accumulate_bf16.4", "srml_argmin_f32.3", "srml_accumulate_f32.4")):
    """Two chips, a 100 ms window, two fits of 3 iterations each. Chip 0: a
    while over 0-90 that holds argmin 0-12, a copy 12-20, accumulate 20-36, a
    fusion 36-40, the float32 pair 40-50 and 50-62, then an all-reduce 90-95.
    Chip 1: argmin 0-16, accumulate 16-36, a copy 36-56, the float32 pair
    56-64 and 64-70, an all-reduce 70-75."""
    ev = lambda n, a, b: [n, a * MS, (b - a) * MS]
    am, ac, fm, fc = (n + "_tpu_custom_call" for n in names)
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ev("while.2_while", 0, 90), ev(am, 0, 12), ev("copy.7_copy", 12, 20), ev(ac, 20, 36),
            ev("fusion.8_fusion", 36, 40), ev(fm, 40, 50), ev(fc, 50, 62), ev("all-reduce.5_all-reduce", 90, 95)]}]},
        {"name": "/device:TPU:1", "lines": [{"name": "XLA Ops", "events": [
            ev(am, 0, 16), ev(ac, 16, 36), ev("copy.7_copy", 36, 56), ev(fm, 56, 64), ev(fc, 64, 70),
            ev("all-reduce.5_all-reduce", 70, 75)]}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [ev("chipbench/window", 0, 100), ev("fit", 1, 99)]}]},
    ]}


def a_run(events, spans=(), seconds=0.1, dropped=0):
    family = SimpleNamespace(iterations=lambda o: o["n_iter"])
    window = SimpleNamespace(seconds=seconds, calls=2, telemetry={"spans": list(spans), "spans_dropped": dropped})
    return SimpleNamespace(trace_data=tracing.reduce(events), family=family, outputs=[{"n_iter": 3}, {"n_iter": 3}],
                           window=window)


def test_op_ms_reads_each_kernel_by_its_prefix():
    run = a_run(named_trace())
    # argmin bf16: (12 + 16) / 2 chips = 14 ms over 6 iterations
    assert op_ms_per_iter.read(run, ["srml_argmin_bf16"]) == pytest.approx(14 / 6)
    # accumulate bf16: (16 + 20) / 2 = 18 ms over 6 iterations
    assert op_ms_per_iter.read(run, ["srml_accumulate_bf16"], per="iteration") == pytest.approx(18 / 6)
    # the float32 pair: (10 + 12 + 8 + 6) / 2 = 18 ms over 2 fits
    assert op_ms_per_iter.read(run, ["srml_argmin_f32", "srml_accumulate_f32"], per="fit") == pytest.approx(9.0)
    # the three add up to what `kernel.distance_ms_per_iter` sums: every Mosaic custom call
    assert tracing.op_s(run.trace_data, tracing.is_kernel) * 1e3 == pytest.approx(14 + 18 + 18)


def test_op_ms_of_a_program_without_the_names_is_none_not_zero():
    parent = a_run(named_trace(names=("_tile_accum_1dev.2", "_tile_accum_1dev.3", "_closed_call.21", "_closed_call.22")))
    assert op_ms_per_iter.read(parent, ["srml_argmin_bf16"]) is None
    assert op_ms_per_iter.read(parent, ["srml_argmin_f32", "srml_accumulate_f32"], per="fit") is None
    assert op_ms_per_iter.read(a_run(named_trace()), ["srml_d2_block_f32"]) is None  # a kernel this window never ran


def test_nonkernel_ms_is_own_time_that_is_neither_kernel_nor_collective():
    run = a_run(named_trace())
    # chip 0: the while's own time 90 - 62 of children = 28, copy 8, fusion 4 -> 40 ms; chip 1: copy 20 ms
    assert nonkernel_ms_per_iter.read(run) == pytest.approx((40 + 20) / 2 / 6)
    only_kernels = {"planes": [p if not p["name"].startswith("/device") else
                               {**p, "lines": [{**l, "events": [e for e in l["events"] if "custom_call" in e[0]]} for l in p["lines"]]}
                               for p in named_trace()["planes"]]}
    assert nonkernel_ms_per_iter.read(a_run(only_kernels)) is None


def test_unspanned_share_counts_top_level_spans_only():
    spans = [{"path": "transform.extract", "wall_s": 0.020}, {"path": "transform", "wall_s": 0.050},
             {"path": "transform/fetch", "wall_s": 0.030}, {"path": "transform.assemble", "wall_s": 0.025}]
    assert unspanned_share.read(a_run(named_trace(), spans)) == pytest.approx(5.0)  # 1 - 95 ms / 100 ms
    assert unspanned_share.read(a_run(named_trace(), spans[1:3])) == pytest.approx(50.0)  # the parent's one span
    assert unspanned_share.read(a_run(named_trace(), spans, dropped=3)) is None  # a cut list: no number
    assert unspanned_share.read(a_run(named_trace(), [])) is None
    legacy = a_run(named_trace(), spans)
    del legacy.window.telemetry["spans_dropped"]  # a program whose delta does not count them yet
    assert unspanned_share.read(legacy) == pytest.approx(5.0)


# the spans' metrics are read on the CPU too; the kernels' names exist only in a TPU program
SPAN_METRICS = {"transform.extract_s", "transform.construct_s", "transform.pad_s", "transform.dispatch_s",
                "transform.fetch_s", "transform.assemble_s", "api.unspanned_share.fit", "api.unspanned_share.transform",
                "solver.init_s", "solver.finish_s", "solver.nonkernel_ms_per_iter"}
KERNEL_NAMES_NEED_THE_CHIP = {"kernel.argmin_ms_per_iter", "kernel.accumulate_ms_per_iter", "kernel.final_pass_ms_per_fit"}


@pytest.mark.parametrize("name", tiny.CELLS)
def test_cell_rehearsal_reports_every_span_metric(name):
    declared = set(tiny.cell_metrics(name))
    assert declared & SPAN_METRICS, name
    res = tiny.execute(name, seed=2**31 + 12, trace=True, seconds=0.3)
    assert res["failed"] == 0  # `correct` at the tests' size depends on the seed: test_faults.py holds it
    assert declared & SPAN_METRICS <= set(res["metrics"])
    assert not KERNEL_NAMES_NEED_THE_CHIP & set(res["metrics"])  # interpreted kernels carry no op name
    for m in declared & SPAN_METRICS:
        assert res["metrics"][m]["value"] >= 0, (m, res["metrics"][m])
    if "transform.extract_s" in declared:
        steps = sum(res["metrics"][f"transform.{s}_s"]["value"] for s in ("extract", "construct", "pad", "dispatch", "fetch", "assemble"))
        assert steps <= res["window"]["seconds"] / res["window"]["calls"]
        assert res["metrics"]["api.unspanned_share.transform"]["value"] < 50
    idle = dict(res["breakdown"]["idle_gaps"])
    assert any(k.startswith(("transform", "fit/solve/")) for k in idle), idle


def test_every_cell_of_the_benchmark_is_rehearsed():
    assert tiny.LATER == [] and len(tiny.CELLS) == 4
    assert tiny.find_cell("kmeans-p3k-host4.refit")["chips"] == 4
