#
# Test harness: run every test on a virtual 8-device CPU mesh so the real
# multi-chip SPMD code paths (sharding, psum, ppermute) execute on one machine —
# the analog of the reference's Spark local[N]-with-real-GPUs harness
# (reference tests/conftest.py:44-70): multi-"node" behavior without a cluster.
#
# The env vars MUST be set before jax is imported anywhere in the process:
# the suite runs on CPU whatever accelerator the machine has, and workers the
# tests spawn inherit the same pin. Chip runs use chip_smoke.py, not pytest.
#
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")  # f64 parity (float32_inputs=False path)

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

from spark_rapids_ml_tpu.parallel import set_devices  # noqa: E402

set_devices("cpu")  # all framework work on the virtual 8-device CPU mesh

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption("--runslow", action="store_true", default=False, help="run slow tests")


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: mark test as slow (nightly only)")
    config.addinivalue_line("markers", "compat: Spark-ML output-parity test")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow"):
        return
    skip_slow = pytest.mark.skip(reason="need --runslow option to run")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)


@pytest.fixture(autouse=True)
def _fresh_hbm_ledger():
    # every HBM admission (fit, serving load, scheduler job) reserves in the
    # process-global shared ledger (docs/scheduling.md); a test that admits
    # without releasing (direct admit_* calls, un-evicted registries) must
    # not shrink every later test's budget
    from spark_rapids_ml_tpu.scheduler.ledger import reset_global_ledger

    reset_global_ledger()
    yield
    reset_global_ledger()


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def mesh8():
    from spark_rapids_ml_tpu.parallel import default_devices, get_mesh

    assert len(default_devices()) >= 8, "conftest must provide 8 CPU devices"
    return get_mesh(8)


@pytest.fixture
def gram_constants(monkeypatch):
    """A function that patches `ops/linalg.py`'s `GRAM_TILE_ROWS` and
    `GRAM_PANEL_COLS` (`tile_rows=`, `panel_cols=`) for the test. The jitted
    statistics passes read them while tracing, so every patch, and the end of
    the test, drops the traces."""
    import jax

    from spark_rapids_ml_tpu.ops import linalg

    names = {"tile_rows": "GRAM_TILE_ROWS", "panel_cols": "GRAM_PANEL_COLS"}
    patched = []

    def patch(**constants):
        for name, value in constants.items():
            monkeypatch.setattr(linalg, names[name], value)
        jax.clear_caches()
        patched.append(constants)

    yield patch
    if patched:
        jax.clear_caches()

