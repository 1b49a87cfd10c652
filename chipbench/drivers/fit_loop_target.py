"""`fit_loop` on a continuous target: before anything is placed, the rows'
label (`run.data.y` and the frame's `label`) is replaced by the configuration's
`data.target` recipe, made once on the host from `--seed`; then `fit_loop`'s
own `run` and `check`, unchanged. `datagen.make` makes one planted {0, 1}
label, under which a regressor's (w, wy, wy^2) are integers; nothing in the
harness hands a family the frame before the set-up's cold fit, hence this
driver."""
from __future__ import annotations

import numpy as np

from . import fit_loop

UNITS = fit_loop.UNITS
TARGET_STREAM = 40  # the target's own stream beside --seed (datagen's rows draw from the seed alone)


def target(config: dict, data) -> np.ndarray:
    """`benchmark/gen_data.py` `gen_regression_host`'s form on datagen's rows:
    y = X coef + noise N(0, 1), coef ~ N(0, 1) / sqrt(d), float32 as there,
    returned as float64 (exactly those values)."""
    spec = config["data"]["target"]
    if spec["recipe"] != "linear":
        raise ValueError(f"fit_loop_target: target recipe {spec['recipe']!r}")
    rng = np.random.default_rng([int(data.seed), TARGET_STREAM])
    coef = (rng.standard_normal(data.d) / np.sqrt(data.d)).astype(np.float32)
    y = data.X @ coef + np.float32(spec["noise"]) * rng.standard_normal(data.rows, dtype=np.float32)
    return y.astype(np.float32).astype(np.float64)


def run(run) -> None:
    y = target(run.config, run.data)
    run.data.y = y
    run.data.frame["label"] = y
    run.note(f"continuous target made: y = X coef + {run.config['data']['target']['noise']} noise, var {float(np.var(y)):.4g}")
    fit_loop.run(run)


check = fit_loop.check
