#
# Timing + report helpers (reference benchmark/utils.py `with_benchmark` and
# base.py:241-270 csv report).
#
from __future__ import annotations

import csv
import os
import sys
import time
from typing import Any, Callable, Dict, Tuple


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def with_benchmark(name: str, fn: Callable[[], Any]) -> Tuple[Any, float]:
    """Run fn, log '<name> took N sec', return (result, seconds).

    The caller's fn must wait for its outputs inside (base.fetch of a result
    leaf, or `jax.block_until_ready`): dispatch is asynchronous.
    """
    t0 = time.perf_counter()
    out = fn()
    sec = time.perf_counter() - t0
    log(f"{name} took: {sec:.4g} sec")
    return out, sec


# Schema-stable shared columns; algorithm-specific keys (quality scores,
# per-config timings) go into one JSON `extra` column so rows from different
# algorithms never land under mismatched headers.
_REPORT_COLUMNS = [
    "algo", "num_rows", "num_cols", "num_devices",
    "gen_sec", "fit_sec", "fit_rows_per_sec", "extra",
]


def append_report(
    path: str,
    algo: str,
    rows: Dict[str, Any],
) -> None:
    """Append one result row to a CSV report (header written on first use) —
    the reference's report_row shape (base.py:269-270)."""
    import json

    if not path:
        return
    exists = os.path.exists(path)
    shared = {k: rows[k] for k in _REPORT_COLUMNS if k in rows}
    extra = {k: v for k, v in rows.items() if k not in _REPORT_COLUMNS}
    with open(path, "a", newline="") as f:
        writer = csv.DictWriter(f, fieldnames=_REPORT_COLUMNS, restval="")
        if not exists:
            writer.writeheader()
        writer.writerow({"algo": algo, **shared, "extra": json.dumps(extra, sort_keys=True)})


def pretty_dict(d: Dict[str, Any]) -> str:
    return ", ".join(f"{k}={v:.4g}" if isinstance(v, float) else f"{k}={v}" for k, v in d.items())
