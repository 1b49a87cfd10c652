#
# Partition-parallel dataset generation — the TPU-native rebuild of the
# reference's `gen_data_distributed.py` (1177 LoC: DataGenBase subclasses that
# generate each Spark partition independently inside `mapInPandas`, incl.
# `SparseRegressionDataGen`:581). No Spark here: a partition is a row range
# whose content is a PURE FUNCTION of (seed, kind, partition index), so any
# process — or any number of processes — can generate any partition and the
# bytes are identical. The multi-process driver is a plain multiprocessing
# pool over partition blocks (each worker writes its own part files, the
# reference's one-task-per-partition write), and the streaming consumers
# (`iter_partitions`, `partitions_to_ell`) hand partitions to ingest one at a
# time so the full dataset is never materialized driver-side.
#
# Determinism contract (tested in tests/test_gen_distributed.py):
#   gen.gen_partition(i) depends ONLY on the generator's params + i
#   => generate()/write() output is bit-identical for any n_processes.
#
from __future__ import annotations

import argparse
import glob
import os
from typing import Dict, Iterator, Optional, Tuple

import numpy as np

from .gen_data import random_csr

# Stable per-kind seed tags: keep each generator's RNG streams disjoint even
# for the same (seed, partition) pair.
_KIND_TAGS = {
    "blobs": 1,
    "low_rank": 2,
    "regression": 3,
    "classification": 4,
    "sparse_regression": 5,
}
_SHARED_STREAM = 0  # per-run shared state (coef/centers/V)
_PARTITION_STREAM = 1  # per-partition row content


class DataGenBase:
    """One dataset kind, generated partition-by-partition.

    Subclasses define `kind`, optional extra params (captured in `self.params`),
    `_shared(rng)` (per-run state every partition needs: coefficient vectors,
    cluster centers, the low-rank factor) and `gen_partition(i)`.
    """

    kind: str = ""
    sparse: bool = False

    def __init__(
        self,
        n_rows: int,
        n_cols: int,
        *,
        seed: int = 0,
        n_partitions: Optional[int] = None,
        **params,
    ) -> None:
        if n_rows <= 0 or n_cols <= 0:
            raise ValueError(f"invalid shape {n_rows}x{n_cols}")
        self.n_rows = int(n_rows)
        self.n_cols = int(n_cols)
        self.seed = int(seed)
        if n_partitions is None:
            # ~1M rows per partition by default (the reference's Spark default
            # parallelism analog), at least one per generator
            n_partitions = max(1, -(-self.n_rows // 1_000_000))
        self.n_partitions = max(1, min(int(n_partitions), self.n_rows))
        self.params = params
        self._shared_cache = None

    # -- determinism plumbing ---------------------------------------------
    def _rng(self, stream: int, part_idx: int = 0) -> np.random.Generator:
        return np.random.default_rng(
            np.random.SeedSequence(
                [self.seed, _KIND_TAGS[self.kind], int(stream), int(part_idx)]
            )
        )

    def partition_bounds(self, i: int) -> Tuple[int, int]:
        """Row range [lo, hi) of partition `i`: even split, remainder spread
        over the first partitions (PartitionDescriptor convention)."""
        base, rem = divmod(self.n_rows, self.n_partitions)
        lo = i * base + min(i, rem)
        return lo, lo + base + (1 if i < rem else 0)

    @property
    def shared(self):
        """Per-run state derived from the seed alone — recomputed identically
        in every worker process (no pickling/broadcast needed)."""
        if self._shared_cache is None:
            self._shared_cache = self._shared(self._rng(_SHARED_STREAM))
        return self._shared_cache

    def _shared(self, rng) -> Dict[str, np.ndarray]:
        return {}

    # -- subclass surface --------------------------------------------------
    def gen_partition(self, i: int):
        """Generate partition `i`: (X [rows, d] f32 | CSR, y [rows] | None)."""
        raise NotImplementedError

    # -- drivers -----------------------------------------------------------
    def iter_partitions(self) -> Iterator[Tuple[int, Tuple]]:
        """Stream (i, (X, y)) one partition at a time — the ingest-facing API:
        consumers see one partition of host memory, never the whole set."""
        for i in range(self.n_partitions):
            yield i, self.gen_partition(i)

    def generate(self) -> Tuple:
        """Materialize the full dataset (small shapes / tests). Bit-identical
        to concatenating any multi-process run's partition outputs."""
        xs, ys = [], []
        for _, (x, y) in self.iter_partitions():
            xs.append(x)
            ys.append(y)
        if self.sparse:
            import scipy.sparse as sp

            X = sp.vstack(xs, format="csr") if len(xs) > 1 else xs[0]
        else:
            X = np.concatenate(xs, axis=0) if len(xs) > 1 else xs[0]
        y = None if ys[0] is None else np.concatenate(ys)
        return X, y

    def write_partition(self, i: int, out_dir: str) -> str:
        """Write partition `i` as its own part file (parquet for dense, npz
        CSR triple for sparse) — the per-task write of the reference's
        partition-parallel generators."""
        x, y = self.gen_partition(i)
        if self.sparse:
            path = os.path.join(out_dir, f"part-{i:05d}.npz")
            np.savez(
                path, data=x.data, indices=x.indices, indptr=x.indptr,
                shape=np.asarray(x.shape), **({} if y is None else {"y": y}),
            )
        else:
            from .dataset_io import write_parquet_part

            path = os.path.join(out_dir, f"part-{i:05d}.parquet")
            write_parquet_part(path, x, y)
        return path

    def write(self, out_dir: str, n_processes: int = 1) -> int:
        """Write every partition under `out_dir`, `n_processes`-parallel.

        Output is bit-identical for any `n_processes` (each part file is a
        pure function of params + partition index). Returns files written.
        """
        os.makedirs(out_dir, exist_ok=True)
        n_processes = max(1, min(int(n_processes), self.n_partitions))
        if n_processes == 1:
            for i in range(self.n_partitions):
                self.write_partition(i, out_dir)
            return self.n_partitions
        import multiprocessing as mp

        spec = self.to_spec()
        blocks = [
            list(range(r, self.n_partitions, n_processes)) for r in range(n_processes)
        ]
        # spawn, not fork: the calling process usually has a live multithreaded
        # JAX runtime, and forking it is a documented deadlock hazard. Workers
        # only import numpy/pyarrow (every jax import in this module is lazy),
        # so spawn startup is cheap — and they never touch jax, so they never
        # contend for the chip the caller holds (one process per chip).
        ctx = mp.get_context("spawn")
        with ctx.Pool(n_processes) as pool:
            pool.map(
                _write_partition_block,
                [(spec, block, out_dir) for block in blocks if block],
            )
        return self.n_partitions

    # -- multiprocessing (re)construction ---------------------------------
    def to_spec(self) -> Dict:
        return {
            "kind": self.kind,
            "n_rows": self.n_rows,
            "n_cols": self.n_cols,
            "seed": self.seed,
            "n_partitions": self.n_partitions,
            "params": dict(self.params),
        }

    @staticmethod
    def from_spec(spec: Dict) -> "DataGenBase":
        cls = GENERATORS[spec["kind"]]
        return cls(
            spec["n_rows"], spec["n_cols"], seed=spec["seed"],
            n_partitions=spec["n_partitions"], **spec["params"],
        )


def _write_partition_block(args) -> None:
    """Pool worker: rebuild the generator from its spec and write a block of
    partitions (module-level for picklability)."""
    spec, part_ids, out_dir = args
    gen = DataGenBase.from_spec(spec)
    for i in part_ids:
        gen.write_partition(i, out_dir)


class LowRankMatrixDataGen(DataGenBase):
    """Low-rank + noise features (reference LowRankMatrixDataGen analog):
    shared factor V [rank, d]; each partition draws its own U rows."""

    kind = "low_rank"

    def _shared(self, rng):
        rank = int(self.params.get("rank", 16))
        return {"V": rng.normal(size=(rank, self.n_cols)).astype(np.float32)}

    def gen_partition(self, i: int):
        lo, hi = self.partition_bounds(i)
        rng = self._rng(_PARTITION_STREAM, i)
        V = self.shared["V"]
        noise = float(self.params.get("noise", 0.1))
        U = rng.normal(size=(hi - lo, V.shape[0])).astype(np.float32)
        X = U @ V + noise * rng.normal(size=(hi - lo, self.n_cols)).astype(np.float32)
        return X, None


class RegressionDataGen(LowRankMatrixDataGen):
    """Low-rank features + shared linear target (reference RegressionDataGen)."""

    kind = "regression"

    def _shared(self, rng):
        state = super()._shared(rng)
        state["coef"] = (
            rng.normal(size=self.n_cols) / np.sqrt(self.n_cols)
        ).astype(np.float32)
        return state

    def gen_partition(self, i: int):
        X, _ = super().gen_partition(i)
        rng = self._rng(_PARTITION_STREAM + 1, i)  # label noise stream
        noise = float(self.params.get("noise", 0.1))
        y = X @ self.shared["coef"] + noise * rng.normal(size=len(X)).astype(np.float32)
        return X, y.astype(np.float32)


class ClassificationDataGen(LowRankMatrixDataGen):
    """Low-rank features + linear-margin labels (reference ClassificationDataGen)."""

    kind = "classification"

    def _shared(self, rng):
        state = super()._shared(rng)
        n_classes = int(self.params.get("n_classes", 2))
        state["coef"] = (
            rng.normal(size=(self.n_cols, max(1, n_classes - 1))) / np.sqrt(self.n_cols)
        ).astype(np.float32)
        return state

    def gen_partition(self, i: int):
        X, _ = super().gen_partition(i)
        rng = self._rng(_PARTITION_STREAM + 1, i)
        margins = X @ self.shared["coef"]
        z = np.concatenate(
            [np.zeros((len(X), 1), np.float32),
             margins + 0.5 * rng.normal(size=margins.shape).astype(np.float32)],
            axis=1,
        )
        return X, np.argmax(z, axis=1).astype(np.int64)


class BlobsDataGen(DataGenBase):
    """Gaussian blobs around shared centers (reference BlobsDataGen)."""

    kind = "blobs"

    def _shared(self, rng):
        centers = int(self.params.get("centers", 10))
        return {"C": 10.0 * rng.normal(size=(centers, self.n_cols)).astype(np.float32)}

    def gen_partition(self, i: int):
        lo, hi = self.partition_bounds(i)
        rng = self._rng(_PARTITION_STREAM, i)
        C = self.shared["C"]
        std = float(self.params.get("cluster_std", 1.0))
        assign = rng.integers(0, len(C), size=hi - lo)
        X = C[assign] + std * rng.normal(size=(hi - lo, self.n_cols)).astype(np.float32)
        return X.astype(np.float32), assign.astype(np.int64)


class SparseRegressionDataGen(DataGenBase):
    """Sparse CSR regression partitions (reference SparseRegressionDataGen:581):
    O(nnz) per-partition CSR via the shared `random_csr` generator, shared
    sparse-support coefficient, per-partition label noise. The 1e7 x 2200
    scale shape generates partition-parallel with ~nnz/partition peak memory.
    """

    kind = "sparse_regression"
    sparse = True

    def _shared(self, rng):
        # coef_support: fraction of columns carrying signal. The default
        # (1/40, gen_data.gen_sparse_regression_host parity) leaves most
        # ultra-sparse rows signal-free; classification consumers that score
        # accuracy want coef_support=1.0 (the tests/test_large_sparse.py
        # design: dense support, every nonzero row carries signal).
        coef = np.zeros(self.n_cols, dtype=np.float32)
        support = float(self.params.get("coef_support", 1.0 / 40.0))
        scale = float(self.params.get("coef_scale", 1.0))
        k = max(1, int(self.n_cols * support))
        coef[:k] = scale * rng.normal(size=k)
        return {"coef": coef}

    def gen_partition(self, i: int):
        lo, hi = self.partition_bounds(i)
        rng = self._rng(_PARTITION_STREAM, i)
        density = float(self.params.get("density", 0.001))
        noise = float(self.params.get("noise", 0.01))
        x = random_csr(rng, hi - lo, self.n_cols, density)
        y = np.asarray(x @ self.shared["coef"]).ravel()
        y = y + noise * rng.normal(size=hi - lo).astype(np.float32)
        return x, y.astype(np.float32)


GENERATORS = {
    "blobs": BlobsDataGen,
    "low_rank": LowRankMatrixDataGen,
    "regression": RegressionDataGen,
    "classification": ClassificationDataGen,
    "sparse_regression": SparseRegressionDataGen,
}


# ---------------------------------------------------------------------------
# streaming consumers
# ---------------------------------------------------------------------------


def partitions_to_ell(gen: DataGenBase, dtype=np.float32):
    """Stream a sparse generator's partitions straight into padded-ELL arrays.

    Two passes over the (pure, replayable) partition stream: pass 1 counts
    rows and finds the global widest-row k_max without keeping anything;
    pass 2 converts each partition and writes it into the preallocated
    output. Peak host memory is the ELL output + ONE partition of CSR+ELL —
    the full-dataset CSR is never materialized, and no second full-ELL
    accumulation exists (regenerating a partition costs seconds at the
    1e7x2200 scale shape; holding a second ELL copy costs a gigabyte).
    Returns ``(indices [n, k_max] int32, values [n, k_max], k_max, y)``.
    """
    from spark_rapids_ml_tpu.ops.sparse import csr_to_ell

    n, k_max, have_y = 0, 1, False
    for _, (x, y) in gen.iter_partitions():
        n += x.shape[0]
        if x.nnz:
            k_max = max(k_max, int(np.diff(x.indptr).max()))
        have_y = y is not None
    indices = np.zeros((n, k_max), np.int32)
    values = np.zeros((n, k_max), dtype)
    y_out = np.empty((n,), np.float32) if have_y else None
    lo = 0
    for _, (x, y) in gen.iter_partitions():
        idx, val, _ = csr_to_ell(x, k_max=k_max, dtype=dtype)
        hi = lo + idx.shape[0]
        indices[lo:hi] = idx
        values[lo:hi] = val
        if y_out is not None:
            y_out[lo:hi] = y
        lo = hi
    return indices, values, k_max, y_out


def sparse_classification_ell(n_rows: int, n_cols: int, density: float, seed: int, mesh):
    """The certified sparse classification lane shared by `bench.py` and
    `bench_logistic_regression`: dense-support scale-4 coefficient (the
    tests/test_large_sparse.py design — every nonzero row carries signal,
    accuracy ceiling ~0.94 at 0.1% density), streamed partition-by-partition
    into padded ELL, target binarized at 0, ELL tensors + labels row-sharded
    on `mesh` with ONE shared weight vector (ELL zero-padding rows carry
    w == 0 and index 0 / value 0, both neutral).

    Returns {"values", "indices", "y", "w", "k_max"} device-resident.
    """
    from spark_rapids_ml_tpu.parallel import make_global_rows, place_rows

    gen = SparseRegressionDataGen(
        n_rows, n_cols, seed=seed, density=density,
        coef_support=1.0, coef_scale=4.0, noise=0.25,
    )
    indices, values, k_max, y = partitions_to_ell(gen)
    y_idx = (y > 0).astype(np.int32)
    Xv, w, _ = make_global_rows(mesh, values)
    Xi = place_rows(mesh, indices)
    yd = place_rows(mesh, y_idx)
    return {"values": Xv, "indices": Xi, "y": yd, "w": w, "k_max": k_max}


def read_sparse_npz_dataset(path: str):
    """Load a sparse part-*.npz directory back into one CSR (+ y). Streaming
    consumers should prefer `iter_sparse_npz_dataset`."""
    import scipy.sparse as sp

    xs, ys = [], []
    for x, y in iter_sparse_npz_dataset(path):
        xs.append(x)
        ys.append(y)
    X = sp.vstack(xs, format="csr") if len(xs) > 1 else xs[0]
    y = None if ys[0] is None else np.concatenate(ys)
    return X, y


def iter_sparse_npz_dataset(path: str):
    """Yield (CSR, y|None) per part file, in partition order."""
    import scipy.sparse as sp

    files = sorted(glob.glob(os.path.join(path, "part-*.npz")))
    if not files:
        raise FileNotFoundError(f"no part-*.npz files under {path}")
    for fp in files:
        with np.load(fp) as z:
            x = sp.csr_matrix(
                (z["data"], z["indices"], z["indptr"]), shape=tuple(z["shape"])
            )
            yield x, (z["y"] if "y" in z.files else None)


def main(argv=None) -> None:
    p = argparse.ArgumentParser(
        description="partition-parallel dataset generator (reference "
        "gen_data_distributed.py analog)"
    )
    p.add_argument("kind", choices=sorted(GENERATORS))
    p.add_argument("--num_rows", type=int, default=1_000_000)
    p.add_argument("--num_cols", type=int, default=300)
    p.add_argument("--n_classes", type=int, default=2)
    p.add_argument("--centers", type=int, default=10)
    p.add_argument("--density", type=float, default=0.001)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--n_partitions", type=int, default=0, help="0 = auto (~1M rows each)")
    p.add_argument("--n_processes", type=int, default=1, help="parallel writer processes")
    p.add_argument("--output", required=True, help="output directory")
    args = p.parse_args(argv)

    extra: Dict = {}
    if args.kind == "classification":
        extra["n_classes"] = args.n_classes
    elif args.kind == "blobs":
        extra["centers"] = args.centers
    elif args.kind == "sparse_regression":
        extra["density"] = args.density
    gen = GENERATORS[args.kind](
        args.num_rows, args.num_cols, seed=args.seed,
        n_partitions=args.n_partitions or None, **extra,
    )
    n = gen.write(args.output, n_processes=args.n_processes)
    print(f"wrote {n} part files under {args.output}")


if __name__ == "__main__":
    main()
