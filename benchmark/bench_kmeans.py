#
# KMeans benchmark — protocol config k=1000, maxIter=30, tol=1e-20,
# initMode=random on the 1M x 3k dataset (reference
# databricks/run_benchmark.sh:50-60; quality = inertia, bench_kmeans.py).
#
from __future__ import annotations

import numpy as np

from .base import BenchmarkBase, fetch
from .gen_data import gen_low_rank_device
from .utils import with_benchmark


class BenchmarkKMeans(BenchmarkBase):
    name = "kmeans"
    extra_args = {
        "k": (int, 1000, "number of clusters (protocol: 1000)"),
        "maxIter": (int, 30, "Lloyd iterations (protocol: 30)"),
        "batch_rows": (
            int, 16384,
            "rows per assignment tile (HBM knob); the per-tile assignment + "
            "accumulation runs on the shared tiled distance core "
            "(ops/distance.py, docs/performance.md 'Tiled distance core')",
        ),
    }

    def gen_dataset(self, args, mesh):
        import jax

        if args.cpu_comparison:
            from .gen_data import gen_low_rank_host

            Xh = gen_low_rank_host(args.num_rows, args.num_cols, seed=args.seed)
            return self.dataset_from_arrays(Xh, None, args, mesh)
        n_dev = int(mesh.devices.size)
        X, w = gen_low_rank_device(
            args.num_rows, args.num_cols, seed=args.seed,
            mesh=mesh if n_dev > 1 else None,  # plain on 1 device (no Shardy copy)
        )
        # random-row init (initMode=random protocol config). The dataset rows
        # are iid, so ONE contiguous k-row block at a random offset is an
        # equally random sample — one dynamic_slice program, no per-row
        # device round trips, and no fancy-index gather on X (which materializes a second copy of
        # it — OOM at the 1M x 3k protocol shape).
        rng = np.random.default_rng(args.seed + 1)
        r0 = int(rng.integers(0, max(1, args.num_rows - args.k + 1)))
        centers0 = jax.jit(
            lambda X: jax.lax.dynamic_slice_in_dim(X, r0, args.k, 0)
        )(X)
        fetch(centers0[:1])
        fetch(w[:1])
        return {"X": X, "w": w, "centers0": centers0}

    def dataset_from_arrays(self, X, y, args, mesh):
        import jax

        from spark_rapids_ml_tpu.parallel import make_global_rows

        Xh = np.asarray(X, dtype=np.float32)
        rng = np.random.default_rng(args.seed + 1)
        # TRUE random-row init here: external datasets may be ordered (e.g.
        # written grouped by label), so a contiguous block is NOT a random
        # sample — and the rows are on host, so host fancy-indexing is free
        # (the contiguous-block trick in gen_dataset exists only for
        # device-resident iid generated data)
        idx = np.sort(rng.choice(len(Xh), min(args.k, len(Xh)), replace=False))
        c0 = np.ascontiguousarray(Xh[idx])
        Xd, w, _ = make_global_rows(mesh, Xh)  # pad + row-shard like the gens
        return {
            "X": Xd,
            "w": w,
            "centers0": jax.device_put(c0),
            "X_host": Xh,
            "centers0_host": c0,
        }

    def run_cpu(self, args, data):
        import time

        from sklearn.cluster import KMeans as SkKMeans

        t0 = time.perf_counter()
        SkKMeans(
            n_clusters=args.k, init=data["centers0_host"], n_init=1,
            max_iter=args.maxIter, tol=1e-20, algorithm="lloyd",
        ).fit(data["X_host"])
        return {"cpu_fit": time.perf_counter() - t0}

    def run_once(self, args, data, mesh):
        from jax import default_matmul_precision

        from spark_rapids_ml_tpu.ops.kmeans import kmeans_fit
        def run():
            # KMeans precision policy: 3-pass bf16 MXU (see parallel/mesh.py)
            with default_matmul_precision("BF16_BF16_F32_X3"):
                return kmeans_fit(
                    data["X"], data["w"], data["centers0"], mesh=mesh,
                    max_iter=args.maxIter, tol=1e-20, batch_rows=args.batch_rows,
                )

        fetch(run()["cluster_centers_"])  # compile outside timing
        state = {}

        def timed():
            s = run()
            fetch(s["cluster_centers_"])
            state.update(s)
            return s

        _, sec = with_benchmark("kmeans fit", timed)
        self._inertia = float(np.asarray(state["inertia_"]))
        return {"fit": sec}

    def quality(self, args, data):
        return {"inertia": self._inertia}


if __name__ == "__main__":
    BenchmarkKMeans().run()
