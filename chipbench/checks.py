"""What decides `correct`: the window's own answers against the plain
reference of the cell's family, each number beside the limit that
`chipbench/limits/<cell>.json` gives it. Runs once the window has closed, the
peak has been read and the program's state is freed; the reference places the
host rows again in blocks. A reading that is not a number (NaN, infinite) is
never within its limit."""
from __future__ import annotations

import json
import math
import os
from typing import Dict, Optional, Tuple

from . import datagen

HERE = os.path.dirname(os.path.abspath(__file__))
Compared = Dict[str, Tuple[float, float]]  # name -> (value, limit)


def limits(cell_name: str) -> Dict[str, float]:
    with open(os.path.join(HERE, "limits", cell_name + ".json")) as f:
        return {k: float(v["limit"]) for k, v in json.load(f)["numbers"].items()}


def within(value: float, limit: float) -> bool:
    return math.isfinite(value) and value <= limit


def correct(compared: Compared) -> bool:
    return all(within(v, lim) for v, lim in compared.values())


def beside_limits(run, read: Dict[str, float]) -> Compared:
    lim = limits(run.cell["name"])
    missing = sorted(set(lim) - set(read))
    if missing:
        raise RuntimeError(f"limits name numbers the comparison did not read: {missing}")
    return {k: (read[k], lim[k]) for k in lim}


def _judge(run, read: Dict[str, float], control: Optional[Dict[str, float]], faults: Optional[dict] = None) -> dict:
    return {
        "compared": beside_limits(run, read),
        "read": read,
        "control": None if control is None else beside_limits(run, control),
        "faults": faults,
    }


def worse(a: float, b: float) -> float:
    """The larger of two readings, and NaN if either is (`max` would drop it)."""
    return b if math.isnan(b) or b > a else a


def _worst(into: Dict[str, float], numbers: Dict[str, float]) -> None:
    for k, v in numbers.items():
        into[k] = worse(into.get(k, 0.0), float(v))


def fits(run, sample: Dict[int, int], control: bool) -> dict:
    """`sample`: window fit index -> the estimator seed it was given."""
    fam, cfg = run.family, run.config
    blocks = datagen.blocks(run.data, run.devices)
    read: Dict[str, float] = {}
    ctrl: Optional[Dict[str, float]] = {} if control else None
    ref, faults = None, None
    for i, seed in sample.items():
        if ref is None or fam.SEEDED:
            ref = fam.reference_fit(cfg, run.data, blocks, seed)
        _worst(read, fam.compare_fit(cfg, run.outputs[i], ref, run.data, blocks))
        if control and (not ctrl or fam.SEEDED):
            _worst(ctrl, fam.compare_fit(cfg, fam.control_fit(run, blocks, seed), ref, run.data, blocks))
        if control and faults is None:  # the planted faults, read once
            faults = {name: fam.compare_fit(cfg, out, ref, run.data, blocks)
                      for name, out in fam.fault_fits(cfg, run.data, blocks, seed, run.chips).items()}
    return _judge(run, read, ctrl, faults)


def transforms(run, control: bool) -> dict:
    """The set-up's model against the reference's own fit (the window's calls
    score with it, so it is part of what they answer), then every call's
    predictions against the reference's distances to that model's centres."""
    fam, cfg = run.family, run.config
    held = datagen.blocks(run.data, run.devices)
    model = run.model_outputs
    ref = fam.reference_fit(cfg, run.data, held, model["seed"])
    fit_read = fam.compare_fit(cfg, model, ref, run.data, held)
    blocks = dict(enumerate(held))
    read = {**fit_read, **fam.compare_transform(model["centers"], run.outputs, blocks, run.data.block_rows)}
    ctrl = None
    if control:  # the lower precision in the predict's place; the model is the program's in both
        ctrl = {**fit_read, **fam.compare_transform(model["centers"], run.outputs, blocks, run.data.block_rows, lower=True)}
    return _judge(run, read, ctrl)
