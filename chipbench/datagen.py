"""Seeded data, made in bulk on the host in row blocks by a few threads.

The recipe is PR 21's (`chip_smoke.make_data`): `blobs` gaussian blobs with
unit noise, three strong planted directions and a planted linear label. Each
block of `block_rows` rows has a generator of its own, seeded by (seed, block),
so the same seed gives the same rows whatever the threads do. Made on the
device the rows took 1 s, and fetching them 16 s (my chip run, PR 26): the
host makes them faster than it can fetch them. The reference gets its row
blocks by placing slices of the host copy.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, List, Sequence

import numpy as np

PLANTED_SCALES = (12.0, 9.0, 6.0)
THREADS = 8


@dataclass
class Data:
    seed: int
    rows: int
    d: int
    block_rows: int
    X: np.ndarray  # [rows, d] float32, host
    y: np.ndarray  # [rows] float64 in {0, 1}, host
    frame: Any  # pandas: features (one array per row), label
    timing: Any = None

    @property
    def n_blocks(self) -> int:
        return self.rows // self.block_rows


def _aux(seed: int, d: int, blobs: int):
    rng = np.random.default_rng(int(seed))
    centers = rng.standard_normal((blobs, d), dtype=np.float32)
    planted = np.linalg.qr(rng.standard_normal((d, len(PLANTED_SCALES))))[0].T.astype(np.float32)
    w_true = (rng.standard_normal(d) / np.sqrt(d)).astype(np.float32)
    return centers, planted, w_true


def _block(seed: int, index: int, rows: int, aux, out: np.ndarray, margin: np.ndarray, order_seed) -> None:
    centers, planted, w_true = aux
    rng = np.random.default_rng([int(seed), int(index)])
    blob = rng.integers(0, centers.shape[0], size=rows)
    z = rng.standard_normal((rows, planted.shape[0]), dtype=np.float32) * np.asarray(PLANTED_SCALES, np.float32)
    rng.standard_normal(out=out, dtype=np.float32)
    step = 4096  # the gathered centres in pieces that stay in cache
    for lo in range(0, rows, step):
        out[lo : lo + step] += centers[blob[lo : lo + step]]
        out[lo : lo + step] += z[lo : lo + step] @ planted
    margin[:] = out @ w_true + 0.5 * rng.standard_normal(rows, dtype=np.float32)
    if order_seed is not None:  # the same rows for every seed, in the seed's order
        order = np.random.default_rng([int(order_seed), int(index)]).permutation(rows)
        out[:] = out[order]
        margin[:] = margin[order]


def blocks(data: Data, devices: Sequence[Any]) -> List[Any]:
    """Row blocks of X placed on the device for the reference, block i on device i mod n."""
    import jax

    b = data.block_rows
    return [jax.device_put(data.X[i * b : (i + 1) * b], devices[i % len(devices)]) for i in range(data.n_blocks)]


def make(config: dict, seed: int) -> Data:
    import pandas as pd

    rows, d = int(config["rows"]), int(config["d"])
    spec = config["data"]
    block_rows = min(int(spec["block_rows"]), rows)
    if spec["recipe"] != "blobs" or rows % block_rows:
        raise ValueError(f"datagen: recipe {spec['recipe']!r} / rows {rows} not a multiple of {block_rows}")
    # a configuration whose work depends on the rows fixes them (`data.seed`): every --seed then
    # gets the same rows in another order, blocks and rows within a block both permuted
    fixed = "seed" in spec
    data_seed = int(spec["seed"]) if fixed else int(seed)
    aux = _aux(data_seed, d, int(spec["blobs"]))
    data = Data(int(seed), rows, d, block_rows, np.empty((rows, d), np.float32), np.empty(rows, np.float64), None)
    n = data.n_blocks
    source = np.random.default_rng(int(seed)).permutation(n) if fixed else np.arange(n)
    margin = np.empty(rows, np.float32)
    t0 = time.perf_counter()
    with ThreadPoolExecutor(max_workers=min(THREADS, n)) as pool:
        jobs = [pool.submit(_block, data_seed, int(source[i]), block_rows, aux,
                            data.X[i * block_rows : (i + 1) * block_rows],
                            margin[i * block_rows : (i + 1) * block_rows], seed if fixed else None)
                for i in range(n)]
        for j in jobs:
            j.result()
    t1 = time.perf_counter()
    data.y[:] = margin > float(np.median(margin))
    data.frame = pd.DataFrame({"features": list(data.X), "label": data.y})
    data.timing = {"rows_made_s": round(t1 - t0, 3), "frame_s": round(time.perf_counter() - t1, 3)}
    return data
