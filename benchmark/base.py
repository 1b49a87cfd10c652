#
# Benchmark base — the reference's `benchmark/base.py` (283 LoC: argparse from
# the estimator's supported params, fit/transform timing, csv report) rebuilt
# for the TPU framework. No Spark cluster: datasets are generated on device
# (gen_data) and the estimators run on the local chip/mesh.
#
from __future__ import annotations

import argparse
from abc import ABC, abstractmethod
from typing import Any, Dict, Optional

import numpy as np

from .utils import append_report, log, pretty_dict, with_benchmark


class BenchmarkBase(ABC):
    """One algorithm benchmark: parse args -> gen data -> time fit (+transform)
    -> quality score -> report row."""

    name: str = ""
    # argparse spec: {flag: (type, default, help)}
    extra_args: Dict[str, tuple] = {}

    def __init__(self) -> None:
        self.parser = argparse.ArgumentParser(prog=f"benchmark {self.name}")
        self.parser.add_argument("--num_rows", type=int, default=100_000)
        self.parser.add_argument("--num_cols", type=int, default=300)
        self.parser.add_argument("--num_runs", type=int, default=1,
                                 help="timed runs; the best is reported (3 in the reference protocol)")
        self.parser.add_argument("--report", type=str, default="",
                                 help="CSV file to append the result row to")
        self.parser.add_argument("--num_workers", type=int, default=0,
                                 help="devices in the mesh (0 = all visible)")
        self.parser.add_argument("--seed", type=int, default=0)
        self.parser.add_argument("--dataset_path", type=str, default="",
                                 help="read the dataset from this parquet directory/file"
                                      " (the reference's shared multi-file parquet"
                                      " layout) instead of generating it")
        self.parser.add_argument("--cpu_comparison", action="store_true",
                                 help="also run the sklearn CPU equivalent and report"
                                      " cpu_fit_sec + speedup_vs_cpu (the reference"
                                      " protocol's accelerated-vs-CPU arm,"
                                      " ref base.py:50-61)")
        for flag, (typ, default, help_) in self.extra_args.items():
            self.parser.add_argument(f"--{flag}", type=typ, default=default, help=help_)

    # -- subclass surface --------------------------------------------------
    @abstractmethod
    def gen_dataset(self, args, mesh) -> Dict[str, Any]:
        """Generate the dataset (device-resident where possible)."""

    def dataset_from_arrays(self, X, y, args, mesh) -> Dict[str, Any]:
        """Build the run_once data dict from host arrays loaded off parquet
        (--dataset_path). Benches that support external datasets override."""
        raise NotImplementedError(
            f"{self.name} does not support --dataset_path yet"
        )

    def run_cpu(self, args, data: Dict[str, Any]) -> Dict[str, float]:
        """One CPU (sklearn) fit on the host copy of the dataset; returns
        {'cpu_fit': sec, ...}. Benches that support --cpu_comparison override.
        Host arrays are stashed by gen_dataset when args.cpu_comparison (or
        provided by dataset_from_arrays)."""
        raise NotImplementedError(
            f"{self.name} does not support --cpu_comparison yet"
        )

    @abstractmethod
    def run_once(self, args, data: Dict[str, Any], mesh) -> Dict[str, float]:
        """One timed fit(+transform); returns {'fit': sec, ...} timings."""

    def quality(self, args, data: Dict[str, Any]) -> Dict[str, float]:
        """Post-run quality scores (uses state stashed by run_once)."""
        return {}

    # -- driver ------------------------------------------------------------
    def run(self, argv=None) -> Dict[str, Any]:
        from spark_rapids_ml_tpu.parallel import default_devices, get_mesh

        args = self.parser.parse_args(argv)
        n_dev = args.num_workers or len(default_devices())
        mesh = get_mesh(min(n_dev, len(default_devices())))

        if args.cpu_comparison and type(self).run_cpu is BenchmarkBase.run_cpu:
            # fail BEFORE datagen/timed runs, not after minutes of work
            raise SystemExit(
                f"{self.name} does not support --cpu_comparison"
            )

        if args.dataset_path:
            from .dataset_io import read_parquet_dataset

            def load():
                X, y = read_parquet_dataset(args.dataset_path)
                args.num_rows, args.num_cols = X.shape
                return self.dataset_from_arrays(X, y, args, mesh)

            log(f"[{self.name}] dataset from {args.dataset_path}"
                f" on {mesh.devices.size} device(s)")
            data, gen_s = with_benchmark(f"{self.name} load_dataset", load)
        else:
            log(f"[{self.name}] {args.num_rows}x{args.num_cols}"
                f" on {mesh.devices.size} device(s)")
            data, gen_s = with_benchmark(
                f"{self.name} gen_dataset", lambda: self.gen_dataset(args, mesh)
            )

        timings: Dict[str, float] = {}
        for i in range(max(1, args.num_runs)):
            t = self.run_once(args, data, mesh)
            for k, v in t.items():
                timings[k] = min(timings.get(k, float("inf")), v)
            log(f"[{self.name}] run {i}: {pretty_dict(t)}")

        cpu_t: Dict[str, float] = {}
        if args.cpu_comparison:
            cpu_t, cpu_s = with_benchmark(
                f"{self.name} cpu arm", lambda: self.run_cpu(args, data)
            )
            log(f"[{self.name}] cpu arm: {pretty_dict(cpu_t)} ({cpu_s:.1f}s total)")

        q = self.quality(args, data)
        row = {
            "num_rows": args.num_rows,
            "num_cols": args.num_cols,
            "num_devices": int(mesh.devices.size),
            "gen_sec": round(gen_s, 4),
            **{f"{k}_sec": round(v, 4) for k, v in timings.items()},
            **{k: round(float(v), 6) for k, v in q.items()},
        }
        for k, v in cpu_t.items():
            row[f"{k}_sec"] = round(v, 4)
        if "fit" in timings:
            row["fit_rows_per_sec"] = round(args.num_rows / timings["fit"], 1)
            if cpu_t.get("cpu_fit"):
                row["speedup_vs_cpu"] = round(cpu_t["cpu_fit"] / timings["fit"], 2)
        log(f"[{self.name}] RESULT {pretty_dict(row)}")
        append_report(args.report, self.name, row)
        return row


def fetch(x) -> np.ndarray:
    """Force device->host materialization: a completion fence that also
    lands the value on the host."""
    return np.asarray(x)
