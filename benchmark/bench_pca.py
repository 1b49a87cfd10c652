#
# PCA benchmark — protocol config k=3 on the 1M x 3k low-rank matrix
# (reference bench_pca.py; quality score = orthonormality max|I − PPᵀ| +
# Σ explained variance, bench_pca.py:86-110).
#
from __future__ import annotations

import numpy as np

from .base import BenchmarkBase, fetch
from .gen_data import gen_low_rank_device
from .utils import with_benchmark


class BenchmarkPCA(BenchmarkBase):
    name = "pca"
    extra_args = {
        "k": (int, 3, "number of components (protocol: 3)"),
    }

    def gen_dataset(self, args, mesh):
        if args.cpu_comparison:
            # host-generated so the sklearn arm sees the same rows
            from .gen_data import gen_low_rank_host

            Xh = gen_low_rank_host(args.num_rows, args.num_cols, seed=args.seed)
            return self.dataset_from_arrays(Xh, None, args, mesh)
        X, w = gen_low_rank_device(args.num_rows, args.num_cols, seed=args.seed, mesh=mesh)
        fetch(w[:1])
        return {"X": X, "w": w}

    def dataset_from_arrays(self, X, y, args, mesh):
        from spark_rapids_ml_tpu.parallel import make_global_rows

        Xh = np.asarray(X, dtype=np.float32)
        # mesh-aware layout (pad + row-shard), exactly like the generator path
        Xd, w, _ = make_global_rows(mesh, Xh)
        return {"X": Xd, "w": w, "X_host": Xh}

    def run_cpu(self, args, data):
        import time

        from sklearn.decomposition import PCA as SkPCA

        t0 = time.perf_counter()
        SkPCA(n_components=args.k, svd_solver="randomized", random_state=0).fit(
            data["X_host"]
        )
        return {"cpu_fit": time.perf_counter() - t0}

    def run_once(self, args, data, mesh):
        from spark_rapids_ml_tpu.ops.pca import pca_fit

        fit = lambda X, w: pca_fit(X, w, k=args.k)  # noqa: E731  (its own programs: the gram, then the eigensolve)
        fetch(fit(data["X"], data["w"])["components_"])  # compile outside timing
        state, sec = with_benchmark(
            "pca fit", lambda: fetch(fit(data["X"], data["w"])["components_"])
        )
        self._components = state
        return {"fit": sec}

    def quality(self, args, data):
        P = np.asarray(self._components, dtype=np.float64)
        ortho = float(np.abs(np.eye(P.shape[0]) - P @ P.T).max())
        return {"orthonormality_err": ortho}


if __name__ == "__main__":
    BenchmarkPCA().run()
