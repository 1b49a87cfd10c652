"""A CPU rehearsal of `chipbench.run` for each cell at a tiny size: the result
has the contract's keys and every metric the cell declares."""
import json

import pytest

from . import tiny

# on the CPU the kernels run through the interpreter (no Mosaic custom call to time), the
# devices report no memory statistics, and the program keeps CPU pools out of the persistent cache
NEEDS_THE_CHIP = {"kernel.distance_ms_per_iter", "distance_roofline", "device.peak_hbm_gib", "compile.cache_hit_share"}


@pytest.mark.parametrize("name", tiny.CELLS)
@pytest.mark.parametrize("trace", [False, True])
def test_cell_rehearsal(name, trace):
    res = tiny.execute(name, seed=2**31 + 11, trace=trace, seconds=0.3)
    json.dumps(res)  # the last line is JSON
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "compared" and res["compared"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(res["device"])
    cell = tiny.find_cell(name)
    if trace:
        declared = set(tiny.cell_metrics(name))
        assert declared - set(res["metrics"]) <= NEEDS_THE_CHIP
        assert set(res["metrics"]) <= declared
        assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
        assert res["breakdown"]["device_ops"] and len(res["breakdown"]["device_ops"]) <= 10
        for name_, m in res["metrics"].items():
            if m["unit"] == "%":
                assert 0 <= m["value"] <= 105, (name_, m)
    else:
        listed = {m["name"] for m in tiny.BENCH["end_to_end"] if name in m.get("workloads", [name])}
        assert "setup_s" in res["metrics"] and len(res["metrics"]) >= 2
        if cell in tiny.BENCH["workloads"]:
            assert set(res["metrics"]) == listed
        assert all(m["value"] > 0 for m in res["metrics"].values())
    assert res["device"]["count"] == cell["chips"]


def test_benchmark_json_matches_the_files():
    bench = tiny.BENCH
    for m in bench["per_layer"]:
        f = tiny.run.load_json("metrics", m["name"] + ".json")
        assert {k: f[k] for k in ("name", "unit", "better", "source", "layer", "moves")} == \
            {k: m[k] for k in ("name", "unit", "better", "source", "layer", "moves")}
        assert set(m["workloads"]) <= set(f["workloads"])
    for c in bench["configs"]:
        f = json.load(open(c["file"]))
        assert f["source"] == c["source"] and f["reduced"] == c["reduced"]
        assert f["program_config"]["autotune_enabled"] is False and f["program_config_why"]
    for w in bench["workloads"]:
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        tiny.run.load_json("limits", w["name"] + ".json")
