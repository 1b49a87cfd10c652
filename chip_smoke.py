#!/usr/bin/env python3
#
# chip_smoke.py — the quickest proof that the system still starts on the chip.
#
# ONE process drives fit -> transform -> serve through the public API only
# (`Estimator.fit`, `model.transform`, `ModelRegistry.load` +
# `ScoringEngine.score`) at the full protocol width (d=3000, KMeans k=1000),
# on data generated from a seed, and checks every result against a plain
# float32 numpy reference on the host, outside any timed region. Depth is
# cut (a few Lloyd / L-BFGS iterations, 262,144 rows), the width never is.
#
#   python3 chip_smoke.py                one chip: KMeans fit/transform/serve,
#                                        LogisticRegression, exact kNN, PCA
#   python3 chip_smoke.py --four-chips   also KMeans / LogReg / kNN with
#                                        num_workers=4, compared with one chip
#   python3 chip_smoke.py --rehearse-cpu control-flow rehearsal at a tiny size
#                                        on CPU, kernels in interpret mode;
#                                        prints no result line
#
# It REFUSES to run without a TPU (non-zero exit naming what it found), and
# any failed check or phase makes the exit code non-zero: no per-phase
# try/except that carries on. The last stdout line of a passing chip run is
#   {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
# The per-phase seconds it prints are smoke output, not benchmark numbers.
#
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Tuple

SEED = 20260926
REQUEST_ROWS = (1, 16, 128, 512)


@dataclass(frozen=True)
class Sizes:
    rows: int
    d: int
    k: int
    kmeans_iters: int
    logreg_iters: int
    knn_queries: int
    knn_k: int


# 262,144 x 3,000 f32 = 3.1 GB: one chip runs the one-program Lloyd step at the
# protocol's width (d = 3,000, k = 1,000) and its 32,768-row tiles
FULL = Sizes(rows=262_144, d=3000, k=1000, kmeans_iters=4, logreg_iters=10,
             knn_queries=256, knn_k=64)
REHEARSAL = Sizes(rows=2048, d=200, k=64, kmeans_iters=3, logreg_iters=10,
                  knn_queries=32, knn_k=8)


class Check(Exception):
    """A numeric check missed its band, or a path was not the one named."""


def require(cond: bool, what: str) -> None:
    if not cond:
        raise Check(what)
    print(f"    ok: {what}", flush=True)


# ------------------------------------------------------------ measurement ---


class Compiles:
    """XLA compile seconds and persistent-cache traffic, from jax.monitoring."""

    def __init__(self) -> None:
        import jax

        self.seconds = 0.0
        self.requests = self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name: str, **_: Any) -> None:
        if name.endswith("/compile_requests_use_cache"):
            self.requests += 1
        elif name.endswith("/cache_hits"):
            self.hits += 1
        elif name.endswith("/cache_misses"):
            self.misses += 1

    def _duration(self, name: str, secs: float, **_: Any) -> None:
        if name.endswith("/backend_compile_duration"):
            self.seconds += secs

    def mark(self) -> Tuple[float, int, int, int]:
        return (self.seconds, self.requests, self.hits, self.misses)


def peak_bytes() -> int:
    import jax

    return max(
        int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
        for d in jax.local_devices()
    )


def timed(compiles: Compiles, name: str, fn: Callable[[], Any], repeat: bool = True):
    """Run `fn` once (first call: trace + compile + run) and, with `repeat`,
    once more (programs now compiled). Both end in `jax.block_until_ready`;
    the first call's result is returned."""
    import jax

    def once():
        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        return out, time.perf_counter() - t0

    c0 = compiles.mark()
    out, first_s = once()
    c1 = compiles.mark()
    line = (
        f"phase {name}: first_call {first_s:.2f}s (xla compile {c1[0] - c0[0]:.2f}s, "
        f"persistent cache {c1[2] - c0[2]} hit / {c1[3] - c0[3]} miss "
        f"of {c1[1] - c0[1]} requests)"
    )
    if repeat:
        _, repeat_s = once()
        c2 = compiles.mark()
        line += f", repeat {repeat_s:.2f}s ({c2[1] - c1[1]} new compile requests)"
    print(f"{line}, peak_bytes_in_use {peak_bytes() / 2**30:.2f} GiB", flush=True)
    return out


def fence_section(rehearsal: bool) -> None:
    """Is `jax.block_until_ready` a usable completion fence here? Dispatch a
    program whose roofline time is known, and compare three clocks: dispatch
    returned, block_until_ready returned, a host fetch after that returned."""
    import jax
    import jax.numpy as jnp

    n, reps = (256, 4) if rehearsal else (8192, 64)
    x = jnp.ones((n, n), jnp.bfloat16)

    @jax.jit
    def chain(a):
        y = jax.lax.fori_loop(0, reps, lambda _, y: ((y @ a) * (1.0 / n)).astype(a.dtype), a)
        return y, y[0, 0]  # the scalar: a fetch that needs no program of its own

    jax.block_until_ready(chain(x))  # compile + warm
    t0 = time.perf_counter()
    y, corner = chain(x)
    t_dispatch = time.perf_counter() - t0
    jax.block_until_ready((y, corner))
    t_ready = time.perf_counter() - t0
    value = float(corner)
    t_fetch = time.perf_counter() - t0
    floor_s = 2.0 * n**3 * reps / 197e12  # v5e bf16 peak: no chip finishes sooner
    print(f"phase fence: dispatch returned {t_dispatch * 1e3:.1f} ms, block_until_ready {t_ready * 1e3:.1f} ms, "
          f"fetch after it {(t_fetch - t_ready) * 1e3:.1f} ms (roofline floor {floor_s * 1e3:.1f} ms)", flush=True)
    require(value == 1.0, "fence program result is right")
    if not rehearsal:
        require(t_ready >= floor_s, "block_until_ready returned no sooner than the program's roofline time: it waits for the device")
        require(t_fetch - t_ready < 0.25 * t_ready, "a fetch after block_until_ready is immediate: the work was done")


# ------------------------------------------------------------------ data ----


def make_data(s: Sizes):
    """Seeded mixture of k gaussian blobs plus three strong planted
    directions (so PCA has a right answer) and a planted linear label."""
    import numpy as np

    rng = np.random.default_rng(SEED)
    centers = rng.standard_normal((s.k, s.d), dtype=np.float32)
    blob = rng.integers(0, s.k, size=s.rows)
    planted = np.linalg.qr(rng.standard_normal((s.d, 3)))[0].T.astype(np.float32)
    scales = np.asarray([12.0, 9.0, 6.0], np.float32)
    X = rng.standard_normal((s.rows, s.d), dtype=np.float32)
    step = 32768
    for lo in range(0, s.rows, step):
        hi = min(lo + step, s.rows)
        X[lo:hi] += centers[blob[lo:hi]]
        z = rng.standard_normal((hi - lo, 3), dtype=np.float32) * scales
        X[lo:hi] += z @ planted
    w_true = (rng.standard_normal(s.d) / np.sqrt(s.d)).astype(np.float32)
    margin = X @ w_true + 0.5 * rng.standard_normal(s.rows).astype(np.float32)
    y = (margin > np.median(margin)).astype(np.float64)
    return X, y, planted


def frame(X, **cols):
    import pandas as pd

    return pd.DataFrame({"features": list(X), **cols})


def fit_was_resident(model: Any, what: str) -> None:
    adm = (getattr(model, "_fit_metrics", None) or {}).get("admission")
    require(
        adm is not None and adm.get("verdict") == "resident",
        f"{what} fit was admitted resident, not demoted to streaming (admission={adm})",
    )


# --------------------------------------------------------------- sections ---


def nearest_centers(X, C):
    """float32 numpy reference: (argmin, min d2, gap to the runner-up)."""
    import numpy as np

    c_sq = np.sum(C * C, axis=1)
    best = np.empty(X.shape[0], np.int64)
    mind = np.empty(X.shape[0], np.float64)
    gap = np.empty(X.shape[0], np.float64)
    for lo in range(0, X.shape[0], 32768):
        xb = X[lo : lo + 32768]
        d2 = np.sum(xb * xb, axis=1)[:, None] - 2.0 * (xb @ C.T) + c_sq[None, :]
        two = np.partition(d2, 1, axis=1)[:, :2]
        best[lo : lo + 32768] = np.argmin(d2, axis=1)
        mind[lo : lo + 32768] = two[:, 0]
        gap[lo : lo + 32768] = two[:, 1] - two[:, 0]
    return best, mind, gap


def kmeans_section(compiles, s: Sizes, X, df, workers: int, repeat: bool):
    import numpy as np

    from spark_rapids_ml_tpu.models.clustering import KMeans

    tag = f"kmeans[{workers}]"
    est = KMeans(k=s.k, maxIter=s.kmeans_iters, tol=0.0, seed=7, num_workers=workers)
    est.setFeaturesCol("features")
    model = timed(compiles, f"{tag}.fit", lambda: est.fit(df), repeat)
    out = timed(compiles, f"{tag}.transform", lambda: model.transform(df), repeat)
    assign = out["prediction"].to_numpy()

    fit_was_resident(model, tag)
    C = np.asarray(model.cluster_centers_, np.float32)
    require(C.shape == (s.k, s.d) and np.isfinite(C).all(), f"{tag} centers finite, shape {C.shape}")
    ref_assign, ref_mind, gap = nearest_centers(X, C)
    # a row whose two nearest centers are closer than 0.5 in d2 (of a typical
    # d2 ~ d) is a tie at float32: either answer is right
    decided = gap > 0.5
    require(decided.mean() > 0.95, f"{tag} {(~decided).sum()} of {s.rows} rows are float32 ties (< 5%)")
    require(
        np.array_equal(assign[decided], ref_assign[decided]),
        f"{tag} transform assignments equal numpy argmin on all {int(decided.sum())} decided rows",
    )
    ref_inertia = float(np.maximum(ref_mind, 0.0).sum())
    rel = abs(model.inertia_ - ref_inertia) / ref_inertia
    require(rel < 1e-3, f"{tag} inertia {model.inertia_:.6g} within 1e-3 of numpy {ref_inertia:.6g} (rel {rel:.1e})")
    require(model.n_iter_ == s.kmeans_iters, f"{tag} ran {model.n_iter_} Lloyd iterations")
    return model, C


def serving_section(compiles, s: Sizes, X, model):
    import numpy as np

    from spark_rapids_ml_tpu.serving import ModelRegistry, ScoringEngine

    rng = np.random.default_rng(SEED + 1)
    requests = [X[rng.integers(0, s.rows, size=n)] for n in REQUEST_ROWS]
    registry = ModelRegistry()
    timed(compiles, "serve.load", lambda: registry.load("clusters", model), repeat=False)

    def burst(engine):
        # four at once, so they coalesce; deeper than that, a cold engine's
        # deadline admission prices the backlog at its idle service rate
        futures = [engine.submit("clusters", q) for q in requests]
        return [np.asarray(f.result(timeout=600)) for f in futures]

    with ScoringEngine(registry) as engine:
        one = timed(compiles, "serve.score", lambda: np.asarray(engine.score("clusters", requests[0])))
        served = timed(compiles, "serve.burst", lambda: burst(engine))
        stats = engine.stats()
    registry.clear()
    solo = [model.transform(frame(q))["prediction"].to_numpy() for q in requests]
    require(np.array_equal(one, solo[0]), "served single request equals solo transform")
    require(
        all(np.array_equal(a, b) for a, b in zip(served, solo)),
        f"{len(requests)} mixed-size served responses (rows {REQUEST_ROWS}) identical to solo transform",
    )
    print(f"    serving stats: {json.dumps({k: v for k, v in stats.items() if isinstance(v, (int, float))})}", flush=True)


def logreg_section(compiles, s: Sizes, X, y, df, workers: int, repeat: bool):
    import numpy as np

    from spark_rapids_ml_tpu.models.classification import LogisticRegression

    tag = f"logreg[{workers}]"
    est = LogisticRegression(maxIter=s.logreg_iters, num_workers=workers)
    est.setFeaturesCol("features")
    model = timed(compiles, f"{tag}.fit", lambda: est.fit(df), repeat)
    out = timed(compiles, f"{tag}.transform", lambda: model.transform(df), repeat)

    fit_was_resident(model, tag)
    coef = np.asarray(model.coef_, np.float32).reshape(-1)
    b = float(np.asarray(model.intercept_).reshape(-1)[0])
    z = (X @ coef + b).astype(np.float64)
    ref_obj = float(np.mean(np.logaddexp(0.0, -np.where(y > 0, z, -z))))
    require(np.isfinite(model.objective_), f"{tag} objective finite ({model.objective_:.6f})")
    require(
        model.objective_ < np.log(2.0),
        f"{tag} objective {model.objective_:.6f} decreased from log 2 at the zero start in {model.n_iter_} iterations",
    )
    rel = abs(model.objective_ - ref_obj) / ref_obj
    require(rel < 1e-3, f"{tag} objective within 1e-3 of numpy log-loss {ref_obj:.6f} (rel {rel:.1e})")
    pred = out["prediction"].to_numpy()
    confident = np.abs(z) > 1e-3
    require(
        np.array_equal(pred[confident], (z[confident] > 0).astype(pred.dtype)),
        f"{tag} transform predictions equal sign(x.w+b) on all {int(confident.sum())} rows off the boundary",
    )
    return coef, b


def knn_section(compiles, s: Sizes, X, df, workers: int, repeat: bool):
    import numpy as np

    from spark_rapids_ml_tpu.models.knn import NearestNeighbors

    tag = f"knn[{workers}]"
    est = NearestNeighbors(k=s.knn_k, num_workers=workers).setInputCol("features").setIdCol("id")
    model = est.fit(df)
    queries = df.iloc[: s.knn_queries]
    _, _, knn_df = timed(compiles, f"{tag}.kneighbors", lambda: model.kneighbors(queries), repeat)
    idx = np.stack(knn_df["indices"].to_list())
    dist = np.stack(knn_df["distances"].to_list())

    Q = X[: s.knn_queries]
    d2 = np.sum(Q * Q, axis=1)[:, None] - 2.0 * (Q @ X.T) + np.sum(X * X, axis=1)[None, :]
    part = np.argpartition(d2, s.knn_k, axis=1)[:, : s.knn_k]
    order = np.argsort(np.take_along_axis(d2, part, axis=1), axis=1, kind="stable")
    ref_idx = np.take_along_axis(part, order, axis=1)
    ref_dist = np.sqrt(np.maximum(np.take_along_axis(d2, ref_idx, axis=1), 0.0))
    require(idx.shape == (s.knn_queries, s.knn_k) and np.isfinite(dist).all(), f"{tag} shape {idx.shape}, finite")
    require(np.array_equal(idx[:, 0], np.arange(s.knn_queries)), f"{tag} every query's nearest item is itself")
    # ||q||^2 - 2 q.x + ||x||^2 cancels to ~1e-7 * ||q||^2 at float32
    typical = float(np.median(ref_dist[:, 1]))
    require(
        float(dist[:, 0].max()) < 0.02 * typical,
        f"{tag} self distance {dist[:, 0].max():.3g} ~ 0 (typical neighbour at {typical:.3g})",
    )
    recall = np.mean([len(set(a) & set(b)) / s.knn_k for a, b in zip(idx, ref_idx)])
    require(recall >= 0.99, f"{tag} recall@{s.knn_k} vs numpy {recall:.4f}")
    err = float(np.max(np.abs(dist[:, 1:] - ref_dist[:, 1:]) / ref_dist[:, 1:]))
    require(err < 1e-3, f"{tag} neighbour distances within 1e-3 of numpy (max rel {err:.1e})")
    return idx, dist


def pca_section(compiles, s: Sizes, X, df, planted):
    import numpy as np

    from spark_rapids_ml_tpu.models.feature import PCA

    est = PCA(k=3, inputCol="features", outputCol="pca")
    model = timed(compiles, "pca.fit", lambda: est.fit(df))
    out = timed(compiles, "pca.transform", lambda: model.transform(df))

    fit_was_resident(model, "pca")
    P = np.asarray(model.components_, np.float32).reshape(3, s.d)
    require(float(np.max(np.abs(P @ P.T - np.eye(3)))) < 1e-4, "pca components orthonormal to 1e-4")
    overlap = np.linalg.svd(P @ planted.T, compute_uv=False)
    require(float(overlap.min()) > 0.99, f"pca subspace is the planted one (min cosine {overlap.min():.4f})")
    Xc = X - X.mean(axis=0, dtype=np.float64).astype(np.float32)
    var = np.var((Xc @ P.T).astype(np.float64), axis=0, ddof=1)
    rel = float(np.max(np.abs(np.asarray(model.explained_variance_).reshape(-1) - var) / var))
    require(rel < 1e-3, f"pca explained variance within 1e-3 of numpy (max rel {rel:.1e})")
    proj = np.stack(out["pca"].to_list())
    require(proj.shape == (s.rows, 3) and np.isfinite(proj).all(), f"pca transform finite, shape {proj.shape}")


def four_chip_section(compiles, s: Sizes, X, y, df, one_chip: Dict[str, Any]) -> None:
    """The same KMeans / LogReg / kNN path with num_workers=4, single
    controller: placement spread over four devices, results inside the
    parity band of the one-chip run."""
    import jax
    import numpy as np

    import spark_rapids_ml_tpu as srml
    from spark_rapids_ml_tpu.models.clustering import KMeans

    def in_use(d) -> int:  # the CPU rehearsal has no allocator statistics
        return int((d.memory_stats() or {}).get("bytes_in_use", 0))

    devices = jax.local_devices()[:4]
    whole = s.rows * s.d * 4
    gc.collect()
    before = [in_use(d) for d in devices]
    with srml.device_dataset_scope() as scope:
        est = KMeans(k=s.k, maxIter=s.kmeans_iters, tol=0.0, seed=7, num_workers=4)
        est.setFeaturesCol("features")
        timed(compiles, "kmeans[4].fit (placement kept)", lambda: est.fit(df), repeat=False)
        Xd = scope.last.inputs.X
        gc.collect()
        after = [in_use(d) for d in devices]
        placed = {sh.device: sh.data.shape for sh in Xd.addressable_shards}
        require(
            len(placed) == 4 and set(placed.values()) == {(s.rows // 4, s.d)},
            f"placed X spans 4 devices, one {(s.rows // 4, s.d)} shard each",
        )
        rose = [a - b for a, b in zip(after, before)]
        if devices[0].platform == "cpu":
            print("    (bytes_in_use is not reported on cpu: the per-device rise is not checked)", flush=True)
        else:
            require(
                all(whole // 4 <= r < whole // 2 for r in rose),
                "each device's bytes_in_use rose by about its shard "
                f"({[round(r / 2**20) for r in rose]} MiB; shard {whole // 4 >> 20} MiB, whole block {whole >> 20} MiB)",
            )
    _, C4 = kmeans_section(compiles, s, X, df, workers=4, repeat=True)
    coef4, b4 = logreg_section(compiles, s, X, y, df, workers=4, repeat=True)
    idx4, dist4 = knn_section(compiles, s, X, df, workers=4, repeat=True)

    C1, (coef1, b1), (idx1, dist1) = one_chip["centers"], one_chip["logreg"], one_chip["knn"]
    dc = float(np.max(np.abs(C4 - C1)))
    require(dc < 0.1, f"4-chip centers within 0.1 (noise sigma 1) of 1-chip: max abs {dc:.2e}")
    dw = float(np.max(np.abs(coef4 - coef1)) / np.max(np.abs(coef1)))
    require(dw < 1e-2 and abs(b4 - b1) < 1e-2, f"4-chip coefficients within 1e-2 of 1-chip (rel {dw:.1e}, intercept {abs(b4 - b1):.1e})")
    same = float(np.mean(idx4 == idx1))
    dd = float(np.max(np.abs(dist4[:, 1:] - dist1[:, 1:]) / dist1[:, 1:]))
    require(same > 0.999 and dd < 1e-4, f"4-chip neighbours match 1-chip ({same:.5f} of ids equal, distances rel {dd:.1e})")


# ------------------------------------------------------------------ main ----


def main(argv: List[str]) -> int:
    ap = argparse.ArgumentParser(description="fit -> transform -> serve on the chip")
    ap.add_argument("--four-chips", action="store_true",
                    help="also run KMeans/LogReg/kNN with num_workers=4 and compare with one chip")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="rehearse the control flow on CPU at a tiny size (interpret-mode kernels); prints no result line")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:  # explicit, never inferred: set before jax loads
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
        os.environ["SRML_DISTANCE_KERNEL"] = "interpret"

    import importlib.metadata as md

    import jax
    import numpy as np

    from spark_rapids_ml_tpu import core, memory, telemetry
    from spark_rapids_ml_tpu.ops import distance
    from spark_rapids_ml_tpu.parallel import (
        default_devices,
        device_platforms,
        ensure_compilation_cache,
        get_mesh,
    )

    want = "cpu" if args.rehearse_cpu else "tpu"
    devices = default_devices()
    found = device_platforms()
    if found != [want]:
        print(f"chip_smoke: refusing to run: needs platform {want!r}, found {found} "
              f"(jax.default_backend()={jax.default_backend()!r}, devices={devices})", file=sys.stderr)
        return 2
    if args.four_chips and len(devices) < 4:
        print(f"chip_smoke: --four-chips needs 4 devices, found {len(devices)}", file=sys.stderr)
        return 2
    s = REHEARSAL if args.rehearse_cpu else FULL
    device = {"platform": jax.devices()[0].platform, "kind": jax.devices()[0].device_kind,
              "count": len(jax.devices())}

    compiles = Compiles()
    ensure_compilation_cache()  # before the first compile (the kernel self-test)
    telemetry.enable()  # resident fits stamp their admission verdict too
    capacity = memory.device_capacity_bytes(get_mesh(len(devices)))
    versions = {p: md.version(p) for p in ("jax", "jaxlib", "libtpu", "numpy", "pandas")}
    print(f"device: {json.dumps(device)}", flush=True)
    print(f"versions: {json.dumps(versions)}", flush=True)
    print(f"compile cache: {core.config['compilation_cache_dir']} "
          f"(JAX_COMPILATION_CACHE_DIR={os.environ.get('JAX_COMPILATION_CACHE_DIR')!r}, "
          f"jax_compilation_cache_dir={jax.config.jax_compilation_cache_dir!r})", flush=True)
    print(f"distance.kernel_mode(): {distance.kernel_mode()}", flush=True)
    print(f"memory.device_capacity_bytes(): {capacity}", flush=True)
    print(f"sizes: {s}", flush=True)
    if not args.rehearse_cpu:
        require(distance.kernel_mode() == "pallas", "the distance core runs the Pallas kernels")
        require(capacity is not None, "the device reports its HBM capacity (admission is on)")

    fence_section(args.rehearse_cpu)
    t_start = time.perf_counter()
    X, y, planted = make_data(s)
    df = frame(X, label=y, id=np.arange(s.rows, dtype=np.int64))
    print(f"data: {X.shape} float32 generated from seed {SEED} in {time.perf_counter() - t_start:.1f}s", flush=True)

    full = not args.four_chips  # the four-chip run spends its time on the comparison
    model, C1 = kmeans_section(compiles, s, X, df, workers=1, repeat=full)
    if full:
        serving_section(compiles, s, X, model)
    lr1 = logreg_section(compiles, s, X, y, df, workers=1, repeat=full)
    knn1 = knn_section(compiles, s, X, df, workers=1, repeat=full)
    if full:
        pca_section(compiles, s, X, df, planted)
    if args.four_chips:
        four_chip_section(compiles, s, X, y, df, {"centers": C1, "logreg": lr1, "knn": knn1})

    print(f"total: {time.perf_counter() - t_start:.1f}s wall, xla compile {compiles.seconds:.1f}s, "
          f"persistent cache {compiles.hits} hit / {compiles.misses} miss of {compiles.requests} requests, "
          f"peak_bytes_in_use {peak_bytes() / 2**30:.2f} GiB", flush=True)
    if args.rehearse_cpu:
        print("rehearsal passed (no result line: this was not a chip run)", flush=True)
        return 0
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
