#
# Measured kernel autotuner for the tiled distance core's block planner
# (docs/performance.md "Kernel autotuner").
#
# The static `plan_blocks` heuristic (ops/distance.py) takes the largest
# blocks that fit the declared scoped-VMEM limit and is a fine cold-start
# default, but the best (block_rows, block_k) tiling is a property of the
# part and the shape, not of a fixed budget. This module measures it: on
# first TPU contact per (shape-class, dtype, fast-flag) it times a small
# candidate grid of tilings ON DEVICE, picks the winner, and persists the
# table as JSON beside the XLA compile cache
# (`parallel.mesh.compilation_cache_dir()`) so later PROCESSES reuse the
# measurement instead of redoing it — the same amortization contract as the
# compile cache itself.
#
# Degradation contract (pinned by tests/test_autotune.py and the
# ci/analysis fixture pair): a missing, malformed, stale-version, or
# unwritable table NEVER fails a fit — every failure path returns "no
# entry" and the caller falls back to the heuristic. A candidate tiling the
# compiler refuses is infeasible and skipped; the session goes on with the
# rest. `SRML_AUTOTUNE=0` (config["autotune_enabled"]) disables lookup and
# measurement entirely; off-TPU (kernel_mode() != "pallas") nothing is ever
# measured, so CPU/CI behavior is byte-identical to the heuristic-only
# planner.
#
# `lookup` runs at TRACE time (the block planner is called while tracing
# the jitted assignment programs); `ensure` — the actual measurement — is
# HOST-side only, called eagerly by solver drivers before their loop with
# host-known shapes. Counters follow the distance.* trace-time idiom.
#
from __future__ import annotations

import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from .. import telemetry

# Persisted-table schema version: a table written by an incompatible older
# build is STALE — discarded wholesale (degrade to heuristic), not patched.
_TABLE_VERSION = 1

_TABLE_BASENAME = "srml_autotune.json"

# candidate (block_rows, block_k) grid; filtered per shape by the VMEM-fit
# predicate before timing, and the heuristic's own pick is always included
_CANDIDATE_BR = (128, 256, 512)
_CANDIDATE_BK = (128, 256, 512)

_LOCK = threading.Lock()
_TABLE: Optional[Dict[str, Any]] = None  # guarded-by: _LOCK (lazy-loaded)
_STATS = {"hits": 0, "misses": 0, "measurements": 0, "table_errors": 0}  # guarded-by: _LOCK


def enabled() -> bool:
    """Autotuner opt-out: `config["autotune_enabled"]`, seeded from
    SRML_AUTOTUNE (docs/configuration.md)."""
    from ..core import config

    return bool(config.get("autotune_enabled", True))


def shape_class(n_rows: int, k_side: int, d: int, dtype: Any, fast: bool) -> str:
    """Bucketed table key: rows/k-side round UP to the next power of two
    (one measurement covers the whole bucket — tile shapes inside a bucket
    share a winner), the feature depth stays exact (d decides how many
    full-depth blocks fit VMEM, the quantity being tuned)."""
    import numpy as np

    def _bucket(v: int) -> int:
        v = max(1, int(v))
        return 1 << (v - 1).bit_length()

    mode = "fast" if fast else "full"
    return f"r{_bucket(n_rows)}:k{_bucket(k_side)}:d{int(d)}:{np.dtype(dtype).name}:{mode}"


def table_path() -> str:
    """Where the measured table persists: beside the XLA compile cache."""
    from ..parallel.mesh import compilation_cache_dir

    return os.path.join(compilation_cache_dir(), _TABLE_BASENAME)


def _count(name: str, key: str) -> None:
    # guarded-by: _LOCK (callers hold it)
    _STATS[key] += 1
    if telemetry.enabled():  # traced-ok: autotune.* counters tick at trace time by design — lookup runs while tracing the assignment programs, one tick per planned program (docs/observability.md)
        telemetry.registry().inc(name)  # traced-ok: see line above (deliberate trace-time tick)


def _load_table_locked() -> Dict[str, Any]:
    """Lazy-load the persisted table ONCE per process; every failure mode
    (unreadable, malformed JSON, wrong shape, stale version) degrades to an
    empty table — the heuristic keeps planning, a fit never fails here."""
    global _TABLE
    if _TABLE is not None:
        return _TABLE
    entries: Dict[str, Any] = {}
    path = table_path()
    if os.path.exists(path):
        try:
            with open(path, "r", encoding="utf-8") as f:
                raw = json.load(f)
            if (
                isinstance(raw, dict)
                and raw.get("version") == _TABLE_VERSION
                and isinstance(raw.get("entries"), dict)
            ):
                for key, val in raw["entries"].items():
                    if (
                        isinstance(val, (list, tuple))
                        and len(val) == 2
                        and all(isinstance(v, int) and v > 0 for v in val)
                    ):
                        entries[str(key)] = [int(val[0]), int(val[1])]
                    else:
                        _count("autotune.table_errors", "table_errors")
            else:
                _count("autotune.table_errors", "table_errors")
        except (OSError, ValueError):
            _count("autotune.table_errors", "table_errors")
    _TABLE = entries
    return _TABLE


def _persist_locked() -> None:
    """Atomic write-through (tmp + os.replace — the numcheck.write_report
    discipline); persistence failure is silent: the in-memory table still
    serves this process."""
    path = table_path()
    if _TABLE is None:
        return
    tmp = f"{path}.tmp{os.getpid()}"
    try:
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump({"version": _TABLE_VERSION, "entries": _TABLE}, f, indent=2)
            f.write("\n")
        os.replace(tmp, path)
    except OSError:  # pragma: no cover - persistence is best-effort
        try:
            os.unlink(tmp)
        except OSError:
            pass


def lookup(
    n_rows: int, k_side: int, d: int, dtype: Any, fast: bool
) -> Optional[Tuple[int, int]]:
    """Persisted winner for this shape class, or None (caller falls back to
    the heuristic). Trace-time safe: pure host dict read + counter tick."""
    if not enabled():
        return None
    key = shape_class(n_rows, k_side, d, dtype, fast)
    with _LOCK:  # held-ok: the table lock exists to serialize exactly this one-shot lazy load of a tiny JSON (+ dict read); no other lock is ever taken under it
        entry = _load_table_locked().get(key)
        if entry is None:
            _count("autotune.misses", "misses")
            return None
        _count("autotune.hits", "hits")
        return int(entry[0]), int(entry[1])


def record(
    n_rows: int, k_side: int, d: int, dtype: Any, fast: bool, plan: Tuple[int, int]
) -> None:
    """Store one measured winner and write the table through to disk."""
    key = shape_class(n_rows, k_side, d, dtype, fast)
    with _LOCK:  # held-ok: the table lock exists to serialize exactly this load+mutate+atomic-rewrite of a tiny JSON; no other lock is ever taken under it
        table = _load_table_locked()
        table[key] = [int(plan[0]), int(plan[1])]
        _persist_locked()


def _candidates(n_rows: int, k_side: int, d: int, dtype: Any, fast: bool) -> List[Tuple[int, int]]:
    """VMEM-feasible candidate tilings for this shape, heuristic pick
    included (the tuner can only match or beat the static planner)."""
    from .distance import block_vmem_bytes, plan_blocks, vmem_limit_bytes

    limit = vmem_limit_bytes()
    out: List[Tuple[int, int]] = []
    heuristic = plan_blocks(n_rows, k_side, d, dtype, fast)
    if heuristic is not None:
        out.append(heuristic)
    for br in _CANDIDATE_BR:
        for bk in _CANDIDATE_BK:
            # same VMEM-fit predicate the static planner budgets against
            if block_vmem_bytes(br, bk, d, dtype, fast) > limit:
                continue
            cand = (min(br, max(1, n_rows)), min(bk, max(1, k_side)))
            if cand not in out:
                out.append(cand)
    return out


def _default_timer(n_rows: int, k_side: int, d: int, dtype: Any, fast: bool) -> Callable[[int, int], float]:
    """On-device timing closure over the REAL argmin kernel at (a capped
    version of) the call shape: best-of-`config["autotune_repeats"]` wall
    time per candidate, first call per candidate excluded (compile)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from ..core import config
    from .distance import assign_argmin

    rows = int(min(max(1, n_rows), 4096))
    k = int(min(max(1, k_side), 2048))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((rows, d)), dtype=dtype)
    c = jnp.asarray(rng.standard_normal((k, d)), dtype=dtype)
    try:
        repeats = max(1, int(config.get("autotune_repeats", 3)))
    except (TypeError, ValueError):
        repeats = 3

    def timer(br: int, bk: int) -> float:
        # through assign_argmin, so rows and centers are padded to whole
        # blocks and every candidate scans ALL k centers
        fn = jax.jit(
            lambda x, c: assign_argmin(x, c, fast=fast, block_rows=br, block_k=bk)
        )

        def run() -> None:
            jax.block_until_ready(fn(x, c))

        run()  # compile + warm
        best_t = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()  # telemetry-ok: the measurement ITSELF — the tuner compares raw candidate wall times; a span here would recursively meter the meter
            run()
            best_t = min(best_t, time.perf_counter() - t0)  # telemetry-ok: see line above
        return best_t

    return timer


def ensure(
    n_rows: int,
    k_side: int,
    d: int,
    dtype: Any,
    fast: bool,
    timer: Optional[Callable[[int, int], float]] = None,
) -> Optional[Tuple[int, int]]:
    """HOST-side measurement entry: make sure a winner exists for this shape
    class, measuring the candidate grid on first contact. Returns the table
    entry (existing or just measured) or None when nothing can be tuned —
    disabled, off-TPU without an injected timer, or no feasible candidates.
    Solver drivers call this eagerly BEFORE their jitted loop, where shapes
    are host-known; the traced planner then hits the table via `lookup`.
    A candidate whose timer raises is skipped; when EVERY candidate raises
    (the heuristic's pick included) the fit's own kernels cannot run either,
    and that is raised with the last cause."""
    if not enabled():
        return None
    key = shape_class(n_rows, k_side, d, dtype, fast)
    with _LOCK:  # held-ok: the table lock exists to serialize exactly this one-shot lazy load of a tiny JSON (+ dict read); no other lock is ever taken under it
        existing = _load_table_locked().get(key)
    if existing is not None:
        return int(existing[0]), int(existing[1])
    if timer is None:
        from .distance import kernel_mode

        if kernel_mode() != "pallas":
            return None  # nothing to measure off-TPU: heuristic is the contract
        timer = _default_timer(n_rows, k_side, d, dtype, fast)
    candidates = _candidates(n_rows, k_side, d, dtype, fast)
    if not candidates:
        return None
    best: Optional[Tuple[int, int]] = None
    best_t = float("inf")
    refused: Optional[Exception] = None
    # the whole measurement session is one compile-ledger entry: every
    # candidate run compiles its own kernel variant, and the efficiency
    # plane should see the session's wall as compile time, not idle
    with telemetry.compile_event("autotune.measure", key):
        for br, bk in candidates:
            try:
                t = float(timer(br, bk))
            except Exception as e:
                # a tiling the compiler (or the part) refuses is INFEASIBLE,
                # not a reason to drop the candidates that do run
                from ..utils import get_logger

                get_logger("autotune").warning(
                    "candidate blocks (%d, %d) for %s skipped: %s: %s",
                    br, bk, key, type(e).__name__, e,
                )
                refused = e
                continue
            if t < best_t:
                best_t, best = t, (br, bk)
    if best is None:
        # every tiling was refused, the heuristic's own pick among them: the
        # fit's kernels cannot run either, so say so here with the cause
        raise RuntimeError(
            f"no candidate tiling for {key} compiles and runs "
            f"(tried {candidates})"
        ) from refused
    with _LOCK:  # held-ok: the table lock exists to serialize exactly this load+mutate+atomic-rewrite of a tiny JSON; no other lock is ever taken under it
        _count("autotune.measurements", "measurements")
        table = _load_table_locked()
        table[key] = [int(best[0]), int(best[1])]
        _persist_locked()
    return best


def stats() -> Dict[str, int]:
    """Counter snapshot for the BENCH artifact embed (bench.py)."""
    with _LOCK:
        out = dict(_STATS)
        out["entries"] = len(_TABLE) if _TABLE is not None else 0
        return out


def reset() -> None:
    """Forget the in-memory table cache and counters (test isolation); the
    persisted file is untouched — the next lookup lazily reloads it."""
    global _TABLE
    with _LOCK:
        _TABLE = None
        for k in _STATS:
            _STATS[k] = 0
