"""The float64 reference's level histograms (`families/rfr.py`), a block of a
node's features at a time, on worker processes or in the caller's.

Pure numpy: it imports nothing of the program and no jax, so a spawned worker
starts in about a second and never touches the chip. `np.bincount` holds the
interpreter's lock, so threads do not share its work (one level at the cell's
size, 248,000 rows x 1,000 features x two statistics, is 3 to 8 s alone);
processes do. The uint8 bins are feature-major [features, rows] in a file
that every process maps (the page cache holds it once; a container's
/dev/shm is often too small for 1.2 GB): a feature's column of a node's rows
is one `take` from a row of bytes that stays in cache."""
from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

_MAPPED: Dict[str, np.ndarray] = {}


def columns(path: str, shape: Tuple[int, int]) -> np.ndarray:
    """The mapped [features, rows] uint8 bins, mapped once a process."""
    if path not in _MAPPED:
        _MAPPED[path] = np.memmap(path, np.uint8, mode="r", shape=shape)
    return _MAPPED[path]


def block(binsT: np.ndarray, rows: np.ndarray, v: np.ndarray, fids: np.ndarray, bins: int) -> np.ndarray:
    """(w, wy) [len(fids), bins, 2] of the rows (global ids, statistics `v`
    [rows, >= 2]) over the features `fids`, float64."""
    h = np.empty((len(fids), bins, 2))
    for j, f in enumerate(fids):
        col = binsT[f].take(rows)
        h[j, :, 0] = np.bincount(col, weights=v[:, 0], minlength=bins)
        h[j, :, 1] = np.bincount(col, weights=v[:, 1], minlength=bins)
    return h


def mapped_block(path: str, shape: Tuple[int, int], rows, v, fids, bins: int) -> np.ndarray:
    return block(columns(path, shape), rows, v, fids, bins)
