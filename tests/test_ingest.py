#
# Streaming-ingest tests: per-shard placement equivalence against the old
# monolithic pad+device_put path, chunked column->block extraction equality,
# chunked CSR->ELL equality, and the peak-host-memory regression contract
# (chunked ingest+placement stays ~1x dataset bytes of extra host memory
# where the monolithic path held ~2x extra / ~3x total).
#
import os
import subprocess
import sys

import numpy as np
import pandas as pd
import pytest

import jax

from spark_rapids_ml_tpu import core as core_mod
from spark_rapids_ml_tpu.parallel import (
    get_mesh,
    make_global_rows,
    pad_rows,
    place_row_shards,
    row_sharding,
    shard_row_slices,
)

HERE = os.path.dirname(__file__)
REPO = os.path.dirname(HERE)


@pytest.fixture
def tiny_chunks():
    """Run the body under a pathologically small ingest_chunk_bytes so every
    chunk boundary is exercised, restoring the default afterwards."""
    saved = core_mod.config["ingest_chunk_bytes"]
    core_mod.config["ingest_chunk_bytes"] = 256
    yield
    core_mod.config["ingest_chunk_bytes"] = saved


# ---------------------------------------------------------------------------
# placement equivalence (tentpole acceptance: every dtype/sharding)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.int32])
@pytest.mark.parametrize("shape", [(13, 3), (16, 4), (3, 2), (29,), (8,)])
def test_place_row_shards_matches_monolithic(mesh8, dtype, shape):
    # the chunked per-shard path must produce arrays numerically identical to
    # the old monolithic pad+device_put placement, same sharding included
    x = (np.arange(int(np.prod(shape))) % 17).reshape(shape).astype(dtype)
    X = place_row_shards(mesh8, x)
    xp, _ = pad_rows(x, 8)
    ref = jax.device_put(xp, row_sharding(mesh8, x.ndim))
    assert X.sharding == ref.sharding
    assert X.dtype == ref.dtype
    np.testing.assert_array_equal(np.asarray(X), np.asarray(ref))


def test_shard_row_slices_views_and_tail_pad():
    x = np.arange(26, dtype=np.float32).reshape(13, 2)
    pieces, n_pad = shard_row_slices(x, 4)
    assert n_pad == 16 and len(pieces) == 4
    # all but the tail shard are zero-copy views of x
    for p in pieces[:3]:
        assert np.shares_memory(p, x)
    assert not np.shares_memory(pieces[3], x)  # tail is the one padded copy
    np.testing.assert_array_equal(np.concatenate(pieces)[:13], x)
    np.testing.assert_array_equal(np.concatenate(pieces)[13:], 0)


def test_make_global_rows_matches_monolithic_f64(mesh8):
    x = np.linspace(0, 1, 21 * 5, dtype=np.float64).reshape(21, 5)
    w_in = np.arange(21, dtype=np.float64) + 1
    X, w, n_valid = make_global_rows(mesh8, x, weights=w_in)
    xp, _ = pad_rows(x, 8)
    wp, _ = pad_rows(w_in, 8)
    np.testing.assert_array_equal(np.asarray(X), xp)
    np.testing.assert_array_equal(np.asarray(w), wp)
    assert n_valid == 21


def test_single_device_mesh_placement_unchanged():
    mesh1 = get_mesh(1)
    x = np.arange(12, dtype=np.float32).reshape(6, 2)
    X, w, n_valid = make_global_rows(mesh1, x)
    assert n_valid == 6 and X.shape == (6, 2)
    np.testing.assert_array_equal(np.asarray(X), x)
    # 1-device placement stays UNCOMMITTED-sharding (plain device_put): a
    # committed NamedSharding would re-stage X in consumer programs
    assert len(X.sharding.device_set) == 1


def test_sparse_fit_invariant_to_chunk_size(rng):
    # end-to-end: CSR input through chunked CSR->ELL and per-shard placement
    # must produce bit-identical coefficients at any chunk size
    from benchmark.gen_data import random_csr
    from spark_rapids_ml_tpu.models.classification import LogisticRegression

    x = random_csr(rng, 600, 24, 0.15)
    s = np.asarray(x.sum(axis=1)).ravel()  # plain ndarray (scipy sum yields np.matrix)
    y = (s > np.median(s)).astype(np.float64)
    df = {"features": x, "label": y}

    def fit_coef():
        m = (
            LogisticRegression(maxIter=25, regParam=0.01, standardization=True)
            .setFeaturesCol("features")
            .fit(df)
        )
        return np.asarray(m.coef_)

    c_default = fit_coef()
    saved = core_mod.config["ingest_chunk_bytes"]
    try:
        core_mod.config["ingest_chunk_bytes"] = 512
        c_chunked = fit_coef()
    finally:
        core_mod.config["ingest_chunk_bytes"] = saved
    np.testing.assert_array_equal(c_default, c_chunked)


# ---------------------------------------------------------------------------
# chunked extraction equality
# ---------------------------------------------------------------------------


def test_chunked_extraction_bit_identical(rng, tiny_chunks):
    from spark_rapids_ml_tpu.data import extract_dataset
    from spark_rapids_ml_tpu.linalg import DenseVector, SparseVector

    n, d = 257, 6
    X = rng.normal(size=(n, d))
    saved = core_mod.config["ingest_chunk_bytes"]
    core_mod.config["ingest_chunk_bytes"] = 1 << 30
    try:
        ref_arr = extract_dataset({"f": list(X)}, input_col="f").features
        ref_vec = extract_dataset(
            pd.DataFrame({"f": [DenseVector(r) for r in X]}), input_col="f"
        ).features
        ref_cols = extract_dataset(
            pd.DataFrame({f"c{i}": X[:, i] for i in range(d)}),
            input_cols=[f"c{i}" for i in range(d)],
        ).features
        sv = [
            SparseVector(d, np.sort(rng.choice(d, 2, replace=False)).astype(np.int32),
                         rng.normal(size=2))
            for _ in range(n)
        ]
        ref_sp = extract_dataset(
            pd.DataFrame({"f": sv}), input_col="f", enable_sparse_data_optim=True
        ).features
    finally:
        core_mod.config["ingest_chunk_bytes"] = saved  # fixture value (tiny)

    got_arr = extract_dataset({"f": list(X)}, input_col="f").features
    got_vec = extract_dataset(
        pd.DataFrame({"f": [DenseVector(r) for r in X]}), input_col="f"
    ).features
    got_cols = extract_dataset(
        pd.DataFrame({f"c{i}": X[:, i] for i in range(d)}),
        input_cols=[f"c{i}" for i in range(d)],
    ).features
    got_sp = extract_dataset(
        pd.DataFrame({"f": sv}), input_col="f", enable_sparse_data_optim=True
    ).features
    np.testing.assert_array_equal(got_arr, ref_arr)
    np.testing.assert_array_equal(got_vec, ref_vec)
    np.testing.assert_array_equal(got_cols, ref_cols)
    assert (got_sp != ref_sp).nnz == 0
    np.testing.assert_array_equal(got_sp.indptr, ref_sp.indptr)


def test_csr_to_ell_chunked_bit_identical(rng, tiny_chunks):
    from benchmark.gen_data import random_csr
    from spark_rapids_ml_tpu.ops.sparse import csr_to_ell

    x = random_csr(rng, 311, 40, 0.12)
    saved = core_mod.config["ingest_chunk_bytes"]
    core_mod.config["ingest_chunk_bytes"] = 1 << 30
    try:
        i_ref, v_ref, k_ref = csr_to_ell(x, dtype=np.float32)
    finally:
        core_mod.config["ingest_chunk_bytes"] = saved
    i_got, v_got, k_got = csr_to_ell(x, dtype=np.float32)
    assert k_got == k_ref
    np.testing.assert_array_equal(i_got, i_ref)
    np.testing.assert_array_equal(v_got, v_ref)


# ---------------------------------------------------------------------------
# the unit_rows zero-row convention (satellite; ADVICE round 5)
# ---------------------------------------------------------------------------


def test_unit_rows_zero_row_convention():
    from spark_rapids_ml_tpu.utils import unit_rows

    x = np.array([[3.0, 4.0], [0.0, 0.0], [0.0, 2.0]], np.float32)
    u = unit_rows(x)
    np.testing.assert_allclose(np.linalg.norm(u[[0, 2]], axis=1), 1.0, rtol=1e-6)
    np.testing.assert_array_equal(u[1], 0.0)  # zero rows stay zero
    # through the cosine kernels' d²/2 conversion (models/knn.py) a zero row
    # is at distance 0.5 from EVERY unit vector — equidistant (ranking-
    # neutral) but not sklearn's 1.0 convention; this pins the documented value
    d2 = ((u[1] - u[0]) ** 2).sum()
    assert d2 / 2.0 == pytest.approx(0.5, abs=1e-6)
    d2b = ((u[1] - u[2]) ** 2).sum()
    assert d2b / 2.0 == pytest.approx(0.5, abs=1e-6)


# ---------------------------------------------------------------------------
# peak-host-memory regression (tentpole acceptance)
# ---------------------------------------------------------------------------

_MEM_PROBE = r"""
import os, sys, threading, time
import numpy as np
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from spark_rapids_ml_tpu.parallel import get_mesh, make_global_rows, set_devices
from spark_rapids_ml_tpu.parallel.mesh import pad_rows, row_sharding
set_devices("cpu")

mode, n, d = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
mesh = get_mesh(8)
# warm the CPU PJRT client + placement machinery before the baseline
_ = np.asarray(jax.device_put(np.ones((16, d), np.float32), row_sharding(mesh, 2)))

x = np.full((n, d), 0.5, np.float32)  # touched pages: truly resident
page = os.sysconf("SC_PAGE_SIZE")

def rss():
    with open("/proc/self/statm") as f:
        return int(f.read().split()[1]) * page

peak = [0]
stop = threading.Event()

def sampler():
    while not stop.is_set():
        r = rss()
        if r > peak[0]:
            peak[0] = r
        time.sleep(0.001)

base = rss()
t = threading.Thread(target=sampler, daemon=True)
t.start()
if mode == "chunked":
    X, w, _ = make_global_rows(mesh, x)
else:  # the old monolithic path: whole-block pad copy + one giant device_put
    xp, _ = pad_rows(x, 8)
    X = jax.device_put(xp, row_sharding(mesh, 2))
    w = jax.device_put(np.ones(xp.shape[0], np.float32), row_sharding(mesh, 1))
jax.block_until_ready(X)
final = rss()
stop.set(); t.join()
print(max(peak[0], final) - base)
"""


def _measure_extra_bytes(mode: str, n: int, d: int) -> int:
    """Peak RSS growth of ingest+placement of an [n, d] f32 block, measured in
    a fresh subprocess (clean allocator high-water mark per measurement)."""
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", _MEM_PROBE, mode, str(n), str(d)],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    return int(out.stdout.strip().splitlines()[-1])


def test_ingest_peak_host_memory_small():
    # 128 MiB block, n NOT divisible by the mesh so the old path really pads:
    # chunked placement must stay ~1x extra (device shard buffers only);
    # the monolithic path holds pad copy + device buffers (~2x extra)
    n, d = 8 * 4096 + 5, 1024
    dataset_bytes = n * d * 4
    chunked = _measure_extra_bytes("chunked", n, d)
    mono = _measure_extra_bytes("monolithic", n, d)
    assert chunked <= 1.3 * dataset_bytes, (
        f"chunked ingest used {chunked / dataset_bytes:.2f}x dataset bytes"
    )
    assert mono >= chunked + 0.5 * dataset_bytes, (
        f"expected the monolithic path to hold a full pad copy: "
        f"mono={mono / dataset_bytes:.2f}x chunked={chunked / dataset_bytes:.2f}x"
    )


@pytest.mark.slow
def test_ingest_peak_host_memory_1gib():
    # the tentpole acceptance shape: >= 1 GiB dense block, <= ~1.3x extra
    n, d = 8 * 8192 * 4 + 3, 1024  # 262147 x 1024 f32 = 1.00 GiB
    dataset_bytes = n * d * 4
    assert dataset_bytes >= 1 << 30
    chunked = _measure_extra_bytes("chunked", n, d)
    assert chunked <= 1.3 * dataset_bytes, (
        f"chunked ingest used {chunked / dataset_bytes:.2f}x dataset bytes"
    )


# ------------------------------------------------- opt-in ingest validation --


@pytest.fixture
def validate_on():
    saved = core_mod.config["validate_ingest"]
    core_mod.config["validate_ingest"] = True
    yield
    core_mod.config["validate_ingest"] = saved


def test_validate_ingest_names_the_feature_column(validate_on, tiny_chunks):
    from spark_rapids_ml_tpu.data import extract_dataset
    from spark_rapids_ml_tpu.errors import IngestValidationError

    x = np.arange(400, dtype=np.float64).reshape(100, 4)
    x[37, 2] = np.nan  # lands several 256-byte chunks in
    with pytest.raises(IngestValidationError, match=r"'feat'.*row 37") as ei:
        extract_dataset({"feat": x}, input_col="feat")
    assert isinstance(ei.value, ValueError)  # satellite contract: a clear ValueError
    assert ei.value.column == "feat" and ei.value.row == 37


def test_validate_ingest_names_the_exact_multi_col(validate_on):
    from spark_rapids_ml_tpu.data import extract_dataset
    from spark_rapids_ml_tpu.errors import IngestValidationError

    df = pd.DataFrame(
        {"a": np.ones(50), "b": np.ones(50), "c": np.ones(50), "label": np.zeros(50)}
    )
    df.loc[11, "b"] = np.inf
    with pytest.raises(IngestValidationError) as ei:
        extract_dataset(df, input_cols=["a", "b", "c"], label_col="label")
    assert ei.value.column == "b" and ei.value.row == 11


def test_validate_ingest_checks_label_and_weight(validate_on):
    from spark_rapids_ml_tpu.data import extract_dataset
    from spark_rapids_ml_tpu.errors import IngestValidationError

    x = np.ones((20, 3))
    lab = np.zeros(20)
    lab[4] = np.nan
    with pytest.raises(IngestValidationError) as ei:
        extract_dataset(
            {"f": x, "y": lab}, input_col="f", label_col="y"
        )
    assert ei.value.column == "y" and ei.value.row == 4
    w = np.ones(20)
    w[9] = -np.inf
    with pytest.raises(IngestValidationError) as ei:
        extract_dataset(
            {"f": x, "y": np.zeros(20), "w": w},
            input_col="f", label_col="y", weight_col="w",
        )
    assert ei.value.column == "w" and ei.value.row == 9


def test_validate_ingest_sparse_maps_back_to_the_row(validate_on):
    import scipy.sparse as sp

    from spark_rapids_ml_tpu.data import extract_dataset
    from spark_rapids_ml_tpu.errors import IngestValidationError

    m = sp.random(60, 10, density=0.2, random_state=0, format="csr")
    bad_row = 23
    m[bad_row, m[bad_row].indices[0] if m[bad_row].nnz else 0] = np.nan
    m = m.tocsr()
    with pytest.raises(IngestValidationError) as ei:
        extract_dataset({"f": m}, input_col="f")
    assert ei.value.column == "f" and ei.value.row == bad_row


def test_validate_ingest_off_by_default_and_clean_data_passes(validate_on):
    from spark_rapids_ml_tpu.data import extract_dataset

    x = np.ones((10, 2))
    out = extract_dataset({"f": x}, input_col="f")
    assert out.n_rows == 10  # clean data passes with validation ON
    core_mod.config["validate_ingest"] = False
    x_bad = x.copy()
    x_bad[0, 0] = np.nan
    out = extract_dataset({"f": x_bad}, input_col="f")  # default: no scan, no raise
    assert np.isnan(out.features[0, 0])
