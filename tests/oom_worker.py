#
# Worker for the OOM-chaos subprocess harness (launched by
# tests/test_oocore.py; the non-test prefix keeps pytest from collecting it).
#
# The memory-safety acceptance scenarios need a REAL fit driver consuming a
# REAL `SRML_FAULT_PLAN` from the environment — exactly how an operator
# would chaos-test a deployment — so they run in a clean subprocess: the
# fault plan is process-global state, and the parity reference fit must see
# the plan SPENT, not absent.
#
# Modes (argv[1]; argv[2] = output JSON path):
#
#   demote       `oom:budget=<bytes>` plan: fit 1 enters admission against the
#                injected shrunken budget and must DEMOTE to streaming
#                (fit.demotions == 1); fit 2 (plan spent) runs resident. The
#                worker reports both verdicts, the counters, and the relative
#                coefficient difference — parity is judged here, in-process,
#                where both models share one backend.
#
#   midrecovery  `fail:stage=solve;oom:stage=placement:round=1` plan with
#                solver checkpoints on: attempt 0 runs RESIDENT, checkpoints
#                at the cadence boundary, and dies there on the injected
#                transient; the retry's RE-placement OOMs (round=1 = the
#                recovery attempt), converts to the typed budget error, and
#                the fit must complete on the STREAMING path RESUMED from the
#                attempt-0 checkpoint (checkpoint.restores >= 1) — the
#                "OOM mid-recovery" acceptance ladder end to end.
#
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_X64", "1")


def _dataset():
    import numpy as np
    import pandas as pd

    rng = np.random.default_rng(7)
    k, d = 3, 5
    offsets = rng.normal(scale=8.0, size=(k, d))
    x = np.concatenate(
        [rng.normal(size=(600, d)) + offsets[c] for c in range(k)]
    )
    return pd.DataFrame({"features": list(x)})


def main() -> None:
    mode = sys.argv[1]
    out_path = sys.argv[2]

    import numpy as np

    from spark_rapids_ml_tpu import core, telemetry
    from spark_rapids_ml_tpu.models.clustering import KMeans

    telemetry.enable()
    df = _dataset()
    core.config["stream_chunk_rows"] = 256  # multi-chunk: overlap measurable
    if mode == "midrecovery":
        core.config["checkpoint_every_iters"] = 2

    def fit():
        return KMeans(k=3, seed=11, maxIter=12, float32_inputs=False).setFeaturesCol(
            "features"
        ).fit(df)

    result = {"mode": mode, "error": None}
    try:
        faulted = fit()
        snap = telemetry.snapshot()
        result["counters"] = snap.get("counters", {})
        result["gauges"] = snap.get("gauges", {})
        result["admission_faulted"] = faulted._fit_metrics.get("admission")
        # reference fit: the plan is SPENT, so this runs clean + resident
        telemetry.registry().reset()
        clean = fit()
        result["admission_clean"] = clean._fit_metrics.get("admission")
        denom = np.maximum(np.abs(clean.cluster_centers_), 1e-30)
        result["max_rel_center_diff"] = float(
            np.max(np.abs(faulted.cluster_centers_ - clean.cluster_centers_) / denom)
        )
        result["n_iter_faulted"] = int(faulted._fit_metrics.get("n_iter", -1)) if isinstance(
            faulted._fit_metrics.get("n_iter"), (int, float)
        ) else None
    except Exception as e:  # noqa: BLE001 - the typed class IS the result
        result["error"] = type(e).__name__
        result["detail"] = str(e)
    with open(out_path + ".tmp", "w") as f:
        json.dump(result, f)
    os.replace(out_path + ".tmp", out_path)


if __name__ == "__main__":
    main()
