"""Closed loop of one caller: `model.transform(part)` over `part_rows`-row
pandas partitions of the dataset in turn, predictions back as pandas. Reports
`transform_rows_per_s`: all rows returned in the window over the whole window."""
from __future__ import annotations

import time
from typing import Dict

from .. import checks

UNITS = {"transform_rows_per_s": "rows/s"}


def run(run) -> None:
    fam, df, reg = run.family, run.data.frame, run.registry()
    part_rows = min(int(run.traffic["part_rows"]), run.data.rows)
    fam.before_fit(run.rehearse)
    mark = reg.mark()
    model_seed = run.seed % (2**31 - 1) + 1
    with run.annotate("chipbench/cold_fit"):
        model = fam.estimator(run.config, model_seed).fit(df)
    run.setup = reg.delta(mark)
    fam.assert_path(model)
    run.note("cold fit from host rows done")
    run.model_outputs = {**fam.outputs(model), "seed": model_seed}
    bounds = [(lo, lo + part_rows) for lo in range(0, run.data.rows - part_rows + 1, part_rows)]
    parts = [df.iloc[lo:hi] for lo, hi in bounds]
    for w in range(int(run.traffic["warm_calls"])):
        model.transform(parts[w % len(parts)])
    rows = 0
    with run.measure() as win:
        while True:
            lo, hi = bounds[win.calls % len(parts)]
            with run.annotate("chipbench/transform"):
                pred = model.transform(parts[win.calls % len(parts)])["prediction"].to_numpy()
            run.outputs.append({"lo": lo, "hi": hi, "prediction": pred})
            rows += len(pred)
            win.calls += 1
            win.t1 = time.perf_counter()
            if win.t1 - win.t0 >= run.seconds:
                break
    del model, parts
    run.e2e = {"transform_rows_per_s": rows / run.window.seconds}


def check(run, control: bool = False) -> Dict[str, dict]:
    """The set-up's model, and every call of the window row by row, against the reference."""
    return checks.transforms(run, control)
