"""Every cell of BENCHMARK.json at a size a test run can hold: the published
shapes cut in every direction (tests only; a cell on the chip cuts no width)."""
from __future__ import annotations

import copy
import glob
import json
import os

from chipbench import run

BENCH = run.load_bench()
# cells whose files are in chipbench/ but which BENCHMARK.json does not list yet (PERF.md, Open
# questions): rehearsed all the same, with the metrics that their files say they would report
LATER = [{"name": "kmeans-p3k-host4.refit", "config": "kmeans-p3k-host4", "traffic": "refit", "chips": 4}]
LATER = [c for c in LATER if c["name"] not in {w["name"] for w in BENCH["workloads"]}]
CELLS = [c["name"] for c in BENCH["workloads"] + LATER]


def find_cell(name: str) -> dict:
    return next(c for c in BENCH["workloads"] + LATER if c["name"] == name)


def cell_metrics(name: str):
    if any(c["name"] == name for c in LATER):
        files = sorted(glob.glob(os.path.join(run.HERE, "metrics", "*.json")))
        return [m["name"] for m in (json.load(open(f)) for f in files) if name in m["workloads"]]
    return run.cell_metrics(BENCH, name)


def cell_files(name: str):
    cell = find_cell(name)
    config = run.load_json("configs", cell["config"] + ".json")
    traffic = run.load_json("traffic", cell["traffic"] + ".json")
    config.update(rows=2048, d=512)
    config["data"].update(blobs=8, block_rows=512)
    if config["family"] == "kmeans":
        config["estimator"].update(k=8, maxIter=6)
        if traffic["driver"] == "transform_loop":
            # 8 centres split one blob of many rows in few columns: rows near a boundary, as a cell's split blobs have
            config.update(rows=4096, d=32)
            config["data"].update(blobs=1, block_rows=512)
    else:
        config.update(rows=4096, d=32)  # few enough columns for the configuration's few iterations
        config["data"]["block_rows"] = 1024
    traffic = {**traffic, "part_rows": 2048 if config["rows"] == 4096 else 512}
    return cell, config, traffic


# The tests run seeds 25, 28 and 29: estimator seeds at which the tiny k-means problem has no blob
# split between two centres (there a few boundary rows decide half of the 8 centres, which a cell's
# 1,000 do not feel).


def execute(name: str, seed: int = 25, trace: bool = False, control: bool = False, seconds: float = 0.0, **changes):
    """`seconds=0`: the window is one call, so the sample compared is that call.
    Where the tests' size has readings of its own, its limits are the file
    `chipbench/limits/<cell>.tiny.json`, found by that name like any cell's."""
    cell, config, traffic = cell_files(name)
    config = {**copy.deepcopy(config), **changes}
    if os.path.exists(os.path.join(run.HERE, "limits", name + ".tiny.json")):
        cell = {**cell, "name": name + ".tiny"}
    return run.execute(cell, config, traffic, seed, seconds, trace,
                       per_layer=cell_metrics(name), rehearse=True, control=control)
