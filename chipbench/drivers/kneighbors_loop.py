"""Closed loop of one caller: `model.kneighbors(query_df)` on one fitted exact
kNN model, each call a fresh query set of `queries` rows (`query_sets` sets
made from the seed in set-up, taken in turn). The set-up fits the model from
the item frame and makes one cold call, which places the items, and one warm
call. Reports `transform_rows_per_s`: the query rows answered in the window
over the window."""
from __future__ import annotations

import time
from typing import Dict

import numpy as np

from .. import checks

UNITS = {"transform_rows_per_s": "rows/s"}


def run(run) -> None:
    import pandas as pd

    fam, reg = run.family, run.registry()
    fam.before_fit(run.rehearse)
    n_q, n_sets = int(run.traffic["queries"]), int(run.traffic["query_sets"])
    sets = [fam.queries(run.config, run.data, n_q, i) for i in range(n_sets)]
    frames = [pd.DataFrame({"features": list(q)}) for q in sets]
    run.note(f"{n_sets} query sets of {n_q} rows made")
    mark = reg.mark()
    with run.annotate("chipbench/cold_fit"):
        model = fam.estimator(run.config).fit(run.data.frame)
        model.kneighbors(frames[0])
    run.note("fit and cold call (placement) done")
    _, _, warm = model.kneighbors(frames[1])
    run.setup = reg.delta(mark)
    fam.assert_path(model, run.setup)
    run.model_outputs = {"set": 1, **fam.outputs(warm)}
    answers, rows = [], 0
    with run.measure() as win:
        while True:
            i = (win.calls + 2) % n_sets
            with run.annotate("chipbench/kneighbors"):
                _, _, knn_df = model.kneighbors(frames[i])
            answers.append((i, knn_df))
            rows += len(knn_df)
            win.calls += 1
            win.t1 = time.perf_counter()
            if win.t1 - win.t0 >= run.seconds:
                break
    del model
    # the first and last window calls are compared; each call's rows counted
    run.outputs = [{"set": i, **fam.outputs(df)} for i, df in (answers[0], answers[-1])]
    run.query_sets = sets
    run.e2e = {"transform_rows_per_s": rows / run.window.seconds}


def check(run, control: bool = False) -> Dict[str, dict]:
    """`check_rows` query rows drawn from the seed, in the set-up's warm call
    and the first and last window calls, against the float64 reference; the
    control is the reference with a one-pass bfloat16 contraction."""
    fam, traffic = run.family, run.traffic
    n_q = int(traffic["queries"])
    rows = np.sort(np.random.default_rng(run.seed).choice(n_q, min(int(traffic["check_rows"]), n_q), replace=False))
    calls = [run.model_outputs] + run.outputs
    sample = fam.sampled(calls, run.query_sets, rows)
    read, ctrl = fam.judge(run.data.X, sample, int(run.config["estimator"]["k"]), control)
    return {
        "compared": checks.beside_limits(run, read),
        "read": read,
        "control": None if ctrl is None else checks.beside_limits(run, ctrl),
        "faults": None,
    }
