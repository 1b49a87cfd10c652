"""Closed loop of one caller: `Estimator.fit(df)` again and again on one
placed dataset inside `core.device_dataset_scope()`, a new estimator seed each
call. Reports `fit_s`: the whole window over the fits completed in it."""
from __future__ import annotations

import time
from typing import Dict

import numpy as np

from .. import checks

UNITS = {"fit_s": "s"}


def fit_seed(run, i: int) -> int:
    """Estimator seed of window fit i (-1: the set-up's cold fit); positive int32."""
    return (run.seed + 7919 * (i + 2)) % (2**31 - 1) + 1


def run(run) -> None:
    from spark_rapids_ml_tpu import core

    fam, df, reg = run.family, run.data.frame, run.registry()
    with core.device_dataset_scope():
        fam.before_fit(run.rehearse)
        mark = reg.mark()
        with run.annotate("chipbench/cold_fit"):
            model = fam.estimator(run.config, fit_seed(run, -1)).fit(df)
        run.setup = reg.delta(mark)
        fam.assert_path(model)
        run.note("cold fit from host rows done")
        for w in range(int(run.traffic["warm_fits"])):
            fam.estimator(run.config, fit_seed(run, -1)).fit(df)
        del model
        with run.measure() as win:
            while True:
                with run.annotate("chipbench/fit"):
                    model = fam.estimator(run.config, fit_seed(run, win.calls)).fit(df)
                run.outputs.append(fam.outputs(model))
                win.calls += 1
                win.t1 = time.perf_counter()
                if win.t1 - win.t0 >= run.seconds:
                    break
        fam.assert_path(model)
        del model
    counters = run.window.telemetry["counters"]
    reuses, builds = counters.get("fit.device_dataset_reuses", 0), counters.get("fit.device_dataset_builds", 0)
    if reuses != run.window.calls or builds:
        raise RuntimeError(f"refit window: {run.window.calls} fits, {reuses} placement reuses, {builds} builds")
    run.e2e = {"fit_s": run.window.seconds / run.window.calls}


def check(run, control: bool = False) -> Dict[str, dict]:
    """A sample of the window's fits, drawn from the seed, against the
    reference's own fit from the same stated init."""
    rng = np.random.default_rng(run.seed)
    n = min(int(run.traffic["check_fits"]), run.window.calls)
    sample = sorted(rng.choice(run.window.calls, n, replace=False).tolist())
    return checks.fits(run, {i: fit_seed(run, i) for i in sample}, control)
