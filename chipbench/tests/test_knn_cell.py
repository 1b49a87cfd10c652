"""The cell `knn-deep10m.kneighbors` rehearsed on the CPU at `tiny.py`'s size
(4,096 x 32 items, the kernel through the Pallas interpreter): correct, traced
and untraced, with every metric it declares but those that need the chip; the
bfloat16 control not correct through the run's own `correct`; the set-up's
`assert_path` refusing a program that placed the items twice or searched
without the kernel; the reference and the work count against hand answers."""
import json

import numpy as np
import pytest

from chipbench.families import knn

from . import tiny

CELL = "knn-deep10m.kneighbors"
# on the CPU the devices report no memory statistics and the program keeps CPU pools out of the persistent cache
NEEDS_THE_CHIP = {"device.peak_hbm_gib", "compile.cache_hit_share"}
NEW = {"knn.search_s", "knn.placements_per_call", "kernel.topk_ms_per_call", "topk_roofline", "kneighbors_mfu"}


@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(trace):
    res = tiny.execute(CELL, seed=2**31 + 11, trace=trace, seconds=0.3)
    json.dumps(res)
    assert res["correct"], res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0 and res["device"]["count"] == 1
    if trace:
        declared = set(tiny.cell_metrics(CELL))
        assert NEW <= declared
        assert declared - set(res["metrics"]) <= NEEDS_THE_CHIP
        m = {k: v["value"] for k, v in res["metrics"].items()}
        assert m["knn.placements_per_call"] == 0 and m["knn.search_s"] > 0 and m["kernel.topk_ms_per_call"] > 0
        assert 0 < m["topk_roofline"] <= 100 and 0 < m["kneighbors_mfu"] <= 100
    else:
        assert set(res["metrics"]) == {"transform_rows_per_s", "setup_s"}
        assert all(v["value"] > 0 for v in res["metrics"].values())


def test_control_is_not_correct():
    res = tiny.execute(CELL, seed=29, control=True)
    assert res["correct"], res["compared"]
    assert not res["control"]["correct"], res["control"]


def _setup(placements=1, reuses=1, path="kernel"):
    return {"counters": {"knn.item_placements": placements, "knn.item_reuses": reuses},
            "spans": [{"path": "kneighbors/search", "topk_path": path}] * 2}


class _Model:
    def __init__(self, resident):
        self._resident = resident


def test_assert_path_refuses_what_the_cell_does_not_name():
    knn.assert_path(_Model({"X": 0}), _setup())
    for model, setup in [(_Model(None), _setup()), (_Model({}), _setup(placements=2)),
                         (_Model({}), _setup(reuses=0)), (_Model({}), _setup(path="jnp"))]:
        with pytest.raises(RuntimeError):
            knn.assert_path(model, setup)


def test_reference_and_work_count():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(1000, 8)).astype(np.float32)
    X[500] = X[3]  # an exact tie: the lower index first
    q = np.concatenate([X[3:4] + 1e-3, rng.normal(size=(5, 8)).astype(np.float32)])
    ref = knn.reference_knn(X, q, 4)
    d2 = ((X[None].astype(np.float64) - q[:, None].astype(np.float64)) ** 2).sum(-1)
    order = np.argsort(d2, axis=1, kind="stable")[:, :4]
    np.testing.assert_array_equal(ref["indices"], order)
    assert ref["indices"][0, 0] == 3 and ref["indices"][0, 1] == 500
    out = {"indices": order, "distances": np.sqrt(np.take_along_axis(d2, order, axis=1))}
    read = knn.compare(X, q, out, ref)
    assert read["id_gap_max"] < 1e-12  # the reference's expanded form against the difference form: rounding only
    assert {k: read[k] for k in ("recall_loss_max", "dist_rel_err_max", "unsorted_rows", "bad_ids")} == \
        {"recall_loss_max": 0.0, "dist_rel_err_max": 0.0, "unsorted_rows": 0.0, "bad_ids": 0.0}
    swapped = {"indices": order[:, ::-1].copy(), "distances": out["distances"][:, ::-1].copy()}
    bad = knn.compare(X, q, swapped, ref)
    assert bad["unsorted_rows"] == len(q) and bad["recall_loss_max"] == 0.0
    work = knn.topk_work({"rows": 10_000_000, "d": 96}, 10_000)
    assert work == {"flops": 1.92e13, "bytes": 3.84e9}  # 97.5 ms at 197 TFLOP/s, 4.7 ms at 819 GB/s
