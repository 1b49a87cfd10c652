#
# What the chip run (chip_smoke.py) depends on, as far as a CPU can check it:
#
#   (a) the kernel path under `shard_map`: KMeans / NearestNeighbors / DBSCAN
#       through the ESTIMATORS on the 4- and 8-device mesh with the kernels
#       interpreted — jax rejects a `pallas_call` whose out_shape has no
#       `vma` there, which no ops-level kernel test can see;
#   (b) where the persistent compile cache lives;
#   (c) a TPU whose kernel self-test fails RAISES — it never becomes "jnp";
#   (d) a kernel that does not compile surfaces as itself, once: not an HBM
#       OOM, no streaming retry, no HbmBudgetError;
#   (e) the block planner's accounted VMEM stays under the limit it declares
#       for every shape the issue lists — and those kernels really compile
#       for a v5e (ahead of time, with the installed libtpu, no chip).
#
import os

import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu import core, memory
from spark_rapids_ml_tpu.errors import HbmBudgetError
from spark_rapids_ml_tpu.ops import distance
from spark_rapids_ml_tpu.parallel import mesh as mesh_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _FakeTpu:
    platform = "tpu"
    device_kind = "TPU v5 lite"
    process_index = 0


@pytest.fixture
def kernel_calls(monkeypatch):
    """Interpreted kernels for this test, plus a count of the `pallas_call`s
    it traced: a jit cache hit on an earlier jnp-mode trace of the same
    shapes would make the test pass without touching a kernel."""
    calls = []
    real = distance._call_params

    def counting(interpret):
        calls.append(interpret)
        return real(interpret)

    monkeypatch.setattr(distance, "_call_params", counting)
    monkeypatch.setattr(distance, "_MODE", "interpret")
    return calls


# ------------------------------------------ (a) kernels under shard_map -----


@pytest.mark.parametrize("workers", [4, 8])
def test_kmeans_estimator_kernel_path_on_mesh(kernel_calls, workers):
    from spark_rapids_ml_tpu.models.clustering import KMeans

    rng = np.random.default_rng(11)
    # odd sizes: fresh jit shapes, and rows that do not divide the mesh
    X = (rng.normal(size=(8, 13)) * 6)[rng.integers(0, 8, 263)] + rng.normal(size=(263, 13))
    df = pd.DataFrame({"features": list(X.astype(np.float32))})

    def fit(n):
        est = KMeans(k=8, maxIter=3, tol=0.0, seed=3, num_workers=n)
        model = est.setFeaturesCol("features").fit(df)
        return model, model.transform(df)["prediction"].to_numpy()

    one, assign_one = fit(1)
    many, assign_many = fit(workers)
    assert kernel_calls and all(kernel_calls)  # interpreted kernels were traced
    np.testing.assert_allclose(many.cluster_centers_, one.cluster_centers_, rtol=1e-4, atol=1e-4)
    assert many.inertia_ == pytest.approx(one.inertia_, rel=1e-4)
    np.testing.assert_array_equal(assign_many, assign_one)


@pytest.mark.parametrize("workers", [4, 8])
def test_knn_estimator_kernel_path_on_mesh(kernel_calls, workers):
    from spark_rapids_ml_tpu.models.knn import NearestNeighbors

    rng = np.random.default_rng(12)
    X = rng.normal(size=(301, 11)).astype(np.float32)
    df = pd.DataFrame({"features": list(X), "id": np.arange(301, dtype=np.int64)})

    def search(n):
        est = NearestNeighbors(k=5, num_workers=n).setInputCol("features").setIdCol("id")
        _, _, knn_df = est.fit(df).kneighbors(df.iloc[:17])
        return np.stack(knn_df["indices"].to_list()), np.stack(knn_df["distances"].to_list())

    idx_one, dist_one = search(1)
    idx_many, dist_many = search(workers)
    assert kernel_calls and all(kernel_calls)
    np.testing.assert_array_equal(idx_many, idx_one)
    np.testing.assert_array_equal(idx_many[:, 0], np.arange(17))  # self first
    np.testing.assert_allclose(dist_many[:, 1:], dist_one[:, 1:], rtol=1e-5)
    # ||q||^2 - 2 q.x + ||x||^2 cancels to rounding, in a shard-dependent order
    assert max(dist_many[:, 0].max(), dist_one[:, 0].max()) < 5e-3


@pytest.mark.parametrize("workers", [4, 8])
def test_dbscan_estimator_on_mesh_with_kernel_mode_on(kernel_calls, workers):
    # DBSCAN's passes materialize their distance tile (ops/distance.pairwise_d2
    # — plain XLA by design), so no kernel is traced; what is pinned is that
    # the mesh fit runs with the kernel mode on and equals one device
    from sklearn.datasets import make_blobs

    from spark_rapids_ml_tpu.models.clustering import DBSCAN

    x, _ = make_blobs(n_samples=203, centers=3, cluster_std=0.4, random_state=5)
    df = pd.DataFrame({"features": list(x.astype(np.float64))})

    def labels(n):
        model = DBSCAN(eps=0.7, min_samples=4, num_workers=n).setFeaturesCol("features").fit(df)
        return model.transform(df)["prediction"].to_numpy()

    np.testing.assert_array_equal(labels(workers), labels(1))


# ------------------------------------------ (b) compile cache resolution ----


@pytest.fixture
def jax_cache_restored():
    """Tests that really wire a directory into jax put it back."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_compilation_cache_dir
    saved = (
        jax.config.jax_persistent_cache_min_compile_time_secs,
        jax.config.jax_persistent_cache_min_entry_size_bytes,
    )
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[0])
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", saved[1])
    compilation_cache.reset_cache()
    mesh_mod._COMPILE_CACHE_DIR = None


def test_compile_cache_default_is_one_fixed_git_ignored_checkout_dir(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    # the retired variable is not read any more
    monkeypatch.setenv("SRML_COMPILE_CACHE_DIR", "/nonexistent/elsewhere")
    expected = os.path.join(REPO, ".srml_cache")
    assert core._DEFAULT_COMPILE_CACHE_DIR == expected
    assert mesh_mod.compilation_cache_dir() == core.config["compilation_cache_dir"] == expected
    # a None config is never "no cache": it resolves to the fixed default
    monkeypatch.setitem(core.config, "compilation_cache_dir", None)
    assert mesh_mod.compilation_cache_dir() == expected
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".srml_cache/" in f.read().split()
    # a fresh process resolves the very same path: nothing of pid or time
    import subprocess
    import sys

    code = "from spark_rapids_ml_tpu.core import config; print(config['compilation_cache_dir'])"
    env = {k: v for k, v in os.environ.items() if k != "JAX_COMPILATION_CACHE_DIR"}
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.stdout.strip() == expected, out.stderr


def test_compile_cache_env_is_respected_and_never_overridden(
    monkeypatch, tmp_path, jax_cache_restored
):
    env_dir = str(tmp_path / "from_env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    monkeypatch.setitem(core.config, "compilation_cache_dir", str(tmp_path / "from_config"))
    assert mesh_mod.compilation_cache_dir() == env_dir
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (updates.append((k, v)), real_update(k, v))[1],
    )
    with mesh_mod.chip_scope([_FakeTpu()]):  # an accelerator pool gets wired
        mesh_mod.ensure_compilation_cache()
    dirs = [v for k, v in updates if k == "jax_compilation_cache_dir"]
    assert dirs == [env_dir]  # that directory, and no other, ever
    assert jax.config.jax_compilation_cache_dir == env_dir
    # the zero thresholds: sub-second serving programs persist too
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0


def test_compile_cache_config_dir_used_when_env_unset(monkeypatch, tmp_path, jax_cache_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setitem(core.config, "compilation_cache_dir", str(tmp_path / "from_config"))
    with mesh_mod.chip_scope([_FakeTpu()]):
        mesh_mod.ensure_compilation_cache()
    assert jax.config.jax_compilation_cache_dir == str(tmp_path / "from_config")


# ------------------------------------------ (c) the probe never hides a TPU --


@pytest.fixture
def unprobed(monkeypatch):
    monkeypatch.delenv("SRML_DISTANCE_KERNEL", raising=False)
    monkeypatch.setattr(distance, "_MODE", None)
    monkeypatch.setattr(jax, "device_put", lambda x, dev=None: jnp.asarray(x))


def test_kernel_mode_raises_when_the_tpu_self_test_does_not_compile(unprobed, monkeypatch):
    def refuse(*a, **k):
        raise RuntimeError("INTERNAL: Mosaic failed to compile TPU kernel: Bad lhs type")

    monkeypatch.setattr(distance, "_pl_argmin", refuse)
    with mesh_mod.chip_scope([_FakeTpu()]):
        with pytest.raises(RuntimeError, match="Mosaic failed to compile") as ei:
            distance.kernel_mode()
        assert "TPU v5 lite" in str(ei.value)
        assert distance._MODE is None  # nothing cached: the next call raises too
        with pytest.raises(RuntimeError, match="do not compile/run"):
            distance.kernel_mode()


def test_kernel_mode_raises_when_the_tpu_self_test_is_wrong(unprobed, monkeypatch):
    wrong = lambda x, c, c_sq, **k: (jnp.zeros((8,)), jnp.zeros((8,), jnp.int32))  # noqa: E731
    monkeypatch.setattr(distance, "_pl_argmin", wrong)
    with mesh_mod.chip_scope([_FakeTpu()]):
        with pytest.raises(RuntimeError, match="disagrees with the jnp formula"):
            distance.kernel_mode()


def test_kernel_mode_follows_the_framework_devices_not_the_default_backend(unprobed, monkeypatch):
    # jax's default backend here is cpu; the FRAMEWORK pool says tpu: the
    # probe runs the self-test (interpreted stand-in) and answers "pallas"
    real = distance._pl_argmin
    monkeypatch.setattr(
        distance, "_pl_argmin", lambda *a, **k: real(*a, **{**k, "interpret": True})
    )
    with mesh_mod.chip_scope([_FakeTpu()]):
        assert distance.kernel_mode() == "pallas"


def test_unknown_device_kind_has_no_vmem_limit():
    class Other(_FakeTpu):
        device_kind = "TPU v9 imaginary"

    with mesh_mod.chip_scope([Other()]):
        with pytest.raises(RuntimeError, match="TPU v9 imaginary"):
            distance.vmem_limit_bytes()
        with pytest.raises(RuntimeError, match="_VMEM_LIMIT_BYTES"):
            distance.plan_blocks(4096, 1000, 3000)


# ------------------------- (d) a compile failure is not an OOM ---------------

_VMEM_REFUSAL = (
    "RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem while allocating "
    "on stack for %pallas_call. Scoped allocation with size 19.10M and limit "
    "16.00M exceeded scoped vmem limit by 3.10M."
)


def test_kernel_compile_failure_surfaces_once_with_no_streaming_retry(monkeypatch):
    from spark_rapids_ml_tpu.models.clustering import KMeans
    from spark_rapids_ml_tpu.ops import kmeans as ops_kmeans
    from spark_rapids_ml_tpu.ops import streaming

    calls = {"resident": 0, "streaming": 0}

    def resident(*a, **k):
        calls["resident"] += 1
        raise RuntimeError(_VMEM_REFUSAL)

    def streamed(*a, **k):
        calls["streaming"] += 1
        raise AssertionError("a compile failure must not be retried out-of-core")

    monkeypatch.setattr(ops_kmeans, "kmeans_fit", resident)
    monkeypatch.setattr(streaming, "kmeans_fit_streaming", streamed)
    rng = np.random.default_rng(0)
    df = pd.DataFrame({"features": list(rng.normal(size=(64, 4)).astype(np.float32))})
    with pytest.raises(RuntimeError, match="memory space vmem") as ei:
        KMeans(k=2, maxIter=2).setFeaturesCol("features").fit(df)
    assert not isinstance(ei.value, HbmBudgetError)
    assert calls == {"resident": 1, "streaming": 0}
    # the classifier itself, and its real-OOM side, are pinned in test_memory
    assert not memory.is_oom_error(ei.value)


# ------------------------- (e) the planner vs the declared limit -------------

# (rows per kernel dispatch, k side, depth): the Lloyd row tiles, the serving
# ladder rungs (clamped to config["distance_tile_rows"]), the kNN item scan
_ISSUE_SHAPES = (
    [(rows, 1000, 3000) for rows in (32768, 65536)]
    + [(rows, 1024, 3072) for rows in (32768, 65536)]
    + [(rung, k, d) for rung in (256, 512, 1024, 2048, 4096) for k, d in ((1000, 3000), (1024, 3072))]
    + [(256, 262_144, 3000), (4096, 1_000_000, 3000)]
    + [(4096, 4096, 7168), (4096, 1000, 2999), (4096, 1000, 50)]
)


@pytest.mark.parametrize("fast", [True, False])
def test_planned_blocks_fit_the_declared_vmem_limit(fast):
    limit = distance.vmem_limit_bytes()
    assert limit == distance._VMEM_LIMIT_BYTES["TPU v5 lite"]  # CI plans like the v5e
    for rows, k, d in _ISSUE_SHAPES:
        plan = distance.plan_blocks(rows, k, d, jnp.float32, fast)
        assert plan is not None, (rows, k, d)
        assert distance.block_vmem_bytes(*plan, d, jnp.float32, fast) <= limit, (rows, k, d, plan)
    # the protocol width keeps the full 512x512 blocks in both modes
    assert distance.plan_blocks(32768, 1000, 3000, jnp.float32, fast) == (512, 512)


def test_distance_kernels_compile_for_a_v5e_ahead_of_time(monkeypatch):
    """The parent's default KMeans fit did not compile at the protocol width
    (Mosaic: 16.07M / 19.10M against a 16.00M scoped-VMEM limit). The
    installed libtpu compiles for a v5e topology without a chip."""
    monkeypatch.setenv("TPU_SKIP_MDS_QUERY", "1")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    except Exception as e:  # no libtpu on this machine: nothing to compile with
        pytest.skip(f"no TPU compiler available ahead of time: {type(e).__name__}: {e}")
    dev = topo.devices[0]
    assert dev.device_kind in distance._VMEM_LIMIT_BYTES
    monkeypatch.setattr(distance, "_MODE", "pallas")

    def struct(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=SingleDeviceSharding(dev))

    # the suite runs under x64 (conftest); the f32 path the chip runs does not
    with mesh_mod.chip_scope([dev]), jax.enable_x64(False):
        # the Lloyd kernels at both row tiles and both widths the issue names
        jax.jit(lambda x, w, c: distance.assign_accumulate(x, w, c, fast=True)).lower(
            struct((32768, 3000)), struct((32768,)), struct((1000, 3000))
        ).compile()
        jax.jit(lambda x, w, c: distance.assign_accumulate(x, w, c, fast=False)).lower(
            struct((65536, 3072)), struct((65536,)), struct((1024, 3072))
        ).compile()
        jax.jit(
            lambda items, q, valid: distance.tile_topk(items, q, valid, 64)
        ).lower(struct((262_144, 3000)), struct((256, 3000)), struct((262_144,), jnp.bool_)).compile()
        # the estimator's own ambient precision must not reach the kernel's
        # dot: Mosaic lowers no algorithm preset
        with jax.default_matmul_precision("BF16_BF16_F32_X3"):
            jax.jit(lambda x, c: distance.argmin_assign(x, c)).lower(
                struct((8192, 3000)), struct((1000, 3000))
            )
        # and under shard_map WITH the vma check on (the compiled path keeps
        # it): the four-chip Lloyd step, the row-sharded predict GSPMD cannot
        # partition, and the sharded top-k scan (whose multi-tile carry only
        # exists on the kernel path) all trace and lower
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from spark_rapids_ml_tpu.ops.kmeans import _lloyd_step, kmeans_predict
        from spark_rapids_ml_tpu.ops.knn import _exact_knn_sharded

        mesh = Mesh(np.asarray(topo.devices), (mesh_mod.ROWS_AXIS,))
        rows, rep = P(mesh_mod.ROWS_AXIS), P()
        assert distance.shard_map_check_vma()

        def sharded(shape, spec, dtype=jnp.float32):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, spec))

        X, C = sharded((262_144, 3000), rows), sharded((1000, 3000), rep)
        w = sharded((262_144,), rows)
        for in_place in (False, True):  # the tile sliced out of X, and indexed where it lies
            _lloyd_step.lower(X, w, C, w, mesh=mesh, batch_rows=32768, fast=True, in_place=in_place)
        kmeans_predict.lower(X, C, mesh=mesh)
        _exact_knn_sharded.lower(
            X, sharded((262_144,), rows, jnp.bool_), sharded((256, 3000), rep), mesh=mesh, k=64
        )


def test_the_knn_search_at_the_deep10m_shape_is_one_kernel_on_items_where_they_lie(monkeypatch):
    """The program `kneighbors` dispatches for the cell `knn-deep10m.kneighbors`
    (10,000 queries against 10,000,000 x 96 float32 items held row-major, k =
    10), compiled for a v5e: one Mosaic call named `srml_knn_topk_f32` whose
    item operand is the program's parameter itself and whose outputs are the
    lists and the count of merged chunks, no `copy` of the items, and nothing
    of [queries, items] anywhere in the program."""
    import re

    monkeypatch.setenv("TPU_SKIP_MDS_QUERY", "1")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from spark_rapids_ml_tpu.ops.knn import _topk_tile_1dev

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    except Exception as e:  # no libtpu on this machine: nothing to compile with
        pytest.skip(f"no TPU compiler available ahead of time: {type(e).__name__}: {e}")
    dev = topo.devices[0]
    monkeypatch.setattr(distance, "_MODE", "pallas")
    n, d, nq = 10_000_000, 96, 10_000
    one = SingleDeviceSharding(dev)
    placed = mesh_mod.row_major_format((n, d), np.float32, dev)
    assert placed is not None  # at d = 96 the device alone would lie the items column-major
    s = lambda shape, dtype=jnp.float32, sharding=one: jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    with mesh_mod.chip_scope([dev]), jax.enable_x64(False):
        assert distance.topk_plan(nq, n, d, 10, jnp.float32, False) == (512, 4096)
        text = _topk_tile_1dev.lower(
            s((n, d), sharding=placed), s((n,), jnp.bool_), s((n,)), s((nq, d)), kk=10
        ).compile().as_text()
    calls = re.findall(r"%(srml_\w+?)(?:\.\d+)? = [^\n]*? custom-call\(([^)]*)\), custom_call_target=\"tpu_custom_call\"", text)
    assert [name for name, _ in calls] == ["srml_knn_topk_f32"]
    # its outputs: the two [10,240, 128] lists and the merged chunks, one
    # [8, 128] int32 block for each of the 20 query blocks
    (head,) = re.findall(r"%srml_knn_topk_f32(?:\.\d+)? = (.*?) custom-call\(", text)
    assert re.findall(r"(f32|s32)\[(\d+),(\d+)\]", head) == [
        ("f32", "10240", "128"), ("s32", "10240", "128"), ("s32", "160", "128")]
    items = calls[0][1].split(", ")[1]
    assert re.search(r"%s = f32\[%d,%d\]\{1,0[^}]*\} parameter\(" % (re.escape(items), n, d), text), items
    assert not re.findall(r"= f32\[%d,%d\]\{[^}]*\} copy\(" % (n, d), text)
    assert "[10000,10000000]" not in text and "[10240,4096]" not in text


def test_the_pca_finish_at_d3000_decomposes_no_whole_matrix(monkeypatch):
    """The parent's finish program called `eigh` on the whole 3,000 x 3,000
    covariance to keep three eigenpairs: on a TPU that lowers to a QDWH
    divide-and-conquer down to `@Eigh` custom calls on 256 x 256 blocks, and
    the one program compiled for 268 s on a v5e (PERF.md, PR 29). The programs
    a PCA(k=3) fit runs after its gram now hold no `@Eigh` wider than the
    iteration's 16-column block, and compile for a v5e in seconds."""
    import re
    import time

    monkeypatch.setenv("TPU_SKIP_MDS_QUERY", "1")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from spark_rapids_ml_tpu.ops import linalg, pca

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    except Exception as e:  # no libtpu on this machine: nothing to compile with
        pytest.skip(f"no TPU compiler available ahead of time: {type(e).__name__}: {e}")
    one_chip = SingleDeviceSharding(topo.devices[0])

    def struct(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def eigh_widths(lowered):
        return [int(n) for n in re.findall(r"custom_call @Eigh\(.*?\(tensor<(\d+)x\d+xf32>\)", lowered.as_text())]

    d, k = 3000, 3
    block = linalg.subspace_block(d, k)
    assert block == 16
    with jax.enable_x64(False):
        # what the guard looks for is there to be found: the full path's program has the 256-wide base case
        assert max(eigh_widths(linalg._topk_eigh_full.lower(struct(d, d), k=k))) > block
        eig = linalg.topk_eigh_subspace.lower(struct(d, d), k=k, block=block)
        attrs = pca._pca_attrs.lower(struct(), struct(d), struct(d, d), struct(k), struct(k, d))
        assert set(eigh_widths(eig)) == {block} and eigh_widths(attrs) == []
        t0 = time.perf_counter()
        eig.compile()
        attrs.compile()
        assert time.perf_counter() - t0 < 60  # 5 s here; the issue's bound for any program of the fit


def test_the_gram_at_d3000_compiles_as_panels_that_copy_nothing(monkeypatch):
    """`_pca_stats` at the cells' shape compiled for a v5e: the float32 arm is
    one loop whose body holds one convolution a panel of the block upper
    triangle, each with its slice, centring and weighting fused in (its
    temporaries stay under three tiles: nothing of X's size); the bf16 arm
    is its one contraction with no temporaries. (Sliced into panels the bf16
    arm's untiled operands were held as two bf16 copies of X, 4.4 GiB, which
    is why `gram_panels` gives it one panel: PERF.md, PR 35.)"""
    import re

    monkeypatch.setenv("TPU_SKIP_MDS_QUERY", "1")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from spark_rapids_ml_tpu.ops import linalg, pca

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    except Exception as e:  # no libtpu on this machine: nothing to compile with
        pytest.skip(f"no TPU compiler available ahead of time: {type(e).__name__}: {e}")
    one_chip = SingleDeviceSharding(topo.devices[0])
    n, d = 393_216, 3000
    X = jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    panels, _ = linalg.gram_panels(d)
    assert panels > 1 and linalg.gram_panels(d, fast=True) == (1, d)
    tile_bytes = linalg.GRAM_TILE_ROWS * d * 4
    with jax.enable_x64(False), jax.default_matmul_precision("float32"):
        f32 = pca._pca_stats.lower(X, w).compile()
        bf16 = pca._pca_stats.lower(X, w, fast=True).compile()
    text = f32.as_text()
    assert len(re.findall(r" while\(", text)) == 1
    assert len(re.findall(r" convolution\(", text)) == 2 * panels  # the first tile's and the loop body's
    assert f32.memory_analysis().temp_size_in_bytes < 3 * tile_bytes
    assert len(re.findall(r" convolution\(", bf16.as_text())) == 1
    assert bf16.memory_analysis().temp_size_in_bytes < tile_bytes


def test_the_logistic_loop_at_d3000_reads_x_once_where_it_lies(monkeypatch):
    """`logistic_fit` at the cell's shape ([393216, 3000] float32, which a
    v5e lays out column-major) compiled for a v5e with the fused pass: the
    L-BFGS loop's body holds ONE op that reads an X-sized operand and it is
    the Mosaic kernel `srml_glm_step_f32`, fed X through a bitcast (Xᵀ with
    the rows on the lanes is the same bytes); nowhere in the module is an
    X-sized buffer copied, turned or converted, and the temporaries are the
    logits, the candidates' lane partials and the L-BFGS history."""
    import re

    monkeypatch.setenv("TPU_SKIP_MDS_QUERY", "1")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from spark_rapids_ml_tpu.ops import logistic

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    except Exception as e:  # no libtpu on this machine: nothing to compile with
        pytest.skip(f"no TPU compiler available ahead of time: {type(e).__name__}: {e}")
    dev = topo.devices[0]
    one_chip = SingleDeviceSharding(dev)
    n, d = 393_216, 3000
    X = jax.ShapeDtypeStruct((n, d), jnp.float32, sharding=one_chip)
    y = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    with mesh_mod.chip_scope([dev]), jax.enable_x64(False):
        fit = logistic.logistic_fit.lower(
            X, y, w, k=2, multinomial=False, lam_l2=1e-5, max_iter=25, tol=1e-30,
            glm_pass=logistic.GLM_FUSED,
        ).compile()
        # and the kernel alone where the last tile of rows is ragged (its masked body)
        ragged, vec = 100_000, lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
        jax.jit(lambda *a: logistic._glm_step(*a, interpret=False)).lower(
            vec(ragged, d), vec(d), vec(), vec(ragged), vec(ragged), vec(ragged), vec(logistic.GLM_SPECULATED)
        ).compile()
    text = fit.as_text()
    assert f"f32[{n},{d}]{{0,1:" in text.splitlines()[0]  # the entry's X: column-major, the device's choice
    x_sized = re.compile(rf"f32\[({n},{d}|{d},{n})\]")
    computations, entry = {}, None
    for line in text.splitlines():
        if line and not line.startswith(" ") and "{" in line:
            name = line.split()[1] if line.startswith("ENTRY") else line.split()[0]
            entry = name if line.startswith("ENTRY") else entry
            computations[name] = body = []
        elif line.startswith(" "):
            body.append(line)
    # the L-BFGS loop is the entry's `while` (the two-loop recursion's are inside its body)
    (loop,) = [m for ln in computations[entry] for m in re.findall(r" while\(.*body=(%[\w.\-]+)", ln)]
    ops = [re.match(r"\s+(?:ROOT )?(%[\w.\-]+) = (.*)", ln).groups() for ln in computations[loop]]
    x_names = {name for name, rhs in ops if x_sized.match(rhs)}
    assert x_names  # X rides the loop's state
    readers = [
        rhs for _, rhs in ops
        if x_names & set(re.findall(r"%[\w.\-]+", rhs))
        and not re.search(r" (get-tuple-element|bitcast|tuple)\(", rhs)
    ]
    assert len(readers) == 1 and "srml_glm_step_f32" in readers[0] and "tpu_custom_call" in readers[0], readers
    moved = [ln for ln in text.splitlines() if re.search(r" = f32\[(%d,%d|%d,%d)\]\S* (copy|transpose|convert)\(" % (n, d, d, n), ln)]
    assert not moved, moved
    assert fit.memory_analysis().temp_size_in_bytes < 0.3 * 2**30


@pytest.mark.parametrize("program", ["boot", "level_4_in_place", "level_12_sorted", "level_12_sorted_four_chips"])
def test_a_forests_programs_at_the_protocols_shape_loop_only_in_the_accumulate(monkeypatch, program):
    """The programs of the `rfc-p3k` fit (393,216 rows of 3,072 uint8 columns,
    depth 13, 54 of 3,000 features a node, two classes) compiled for a v5e as
    a TPU process builds them (`kernel_mode` "pallas"), each in seconds (as it
    stood a deep level's program compiled in 12 to 70 s). EVERY `while` of a
    level program carries the scope `srml_hist_accumulate` in its metadata,
    and the bootstrap's program holds none: a trace's op names do not carry
    the scope, so `kernel.hist_ms_per_fit` finds the accumulate as the loops
    of the fit's programs (and the kernel by its name), and a loop that is not
    the accumulate's must not come into one of them without this test saying
    so. A sorted level's accumulate is ONE Mosaic custom call named
    `srml_hist_accumulate_bf16` (`ops/histogram.py`), a device's own under
    `shard_map` on the four chips of a host too, and no loop is left in its
    program; every level's row advance is masked reduces over the binned X,
    no per-row gather; both read the binned X where it lies (row-major by the
    device's own choice at 3,072 columns): the temporaries stay under the
    deepest histogram's few arrays, nothing of the binned X's size. The
    bootstrap is two programs, the draws and their counts: as one (a draw
    bounded by a traced row count, then a scatter of it) they compiled in
    41 s on an idle 8-core host and in 77 s beside the suite's workers."""
    import re
    import time

    monkeypatch.setenv("TPU_SKIP_MDS_QUERY", "1")
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from spark_rapids_ml_tpu.ops import distance, trees
    from spark_rapids_ml_tpu.parallel.mesh import ROWS_AXIS

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    except Exception as e:  # no libtpu on this machine: nothing to compile with
        pytest.skip(f"no TPU compiler available ahead of time: {type(e).__name__}: {e}")
    n_dev = 4 if program.endswith("four_chips") else 1
    mesh = Mesh(np.asarray(topo.devices[:n_dev]), (ROWS_AXIS,))
    n, d, m, bins, S, depth = 393_216 * n_dev, 3000, 54, 128, 2, 13
    nodes = 2 ** (depth + 1) - 1

    def struct(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(mesh, P(*spec)))

    scalar = lambda dtype: jax.ShapeDtypeStruct((), dtype)
    rows = (struct((n,), jnp.int32, ROWS_AXIS), struct((n,), jnp.bool_, ROWS_AXIS))
    stw = struct((S, n), jnp.float32, None, ROWS_AXIS)
    level_args = (
        struct((n, trees.binned_cols(d)), jnp.uint8, ROWS_AXIS, None), stw, *rows,
        struct((n_dev, nodes), jnp.int32, ROWS_AXIS, None), struct((n_dev, nodes), jnp.int32, ROWS_AXIS, None),
        struct((n_dev, nodes, S), jnp.float32, ROWS_AXIS, None, None), scalar(jnp.uint32), scalar(jnp.int32),
    )
    ordered = (struct((n,), jnp.int32, ROWS_AXIS), struct((n,), jnp.int32, ROWS_AXIS), stw, struct((n_dev,), jnp.int32, ROWS_AXIS))
    assert trees.binned_cols(d) == 3072
    kernel = distance.kernel_name("hist_accumulate", True)
    assert kernel == "srml_hist_accumulate_bf16" and kernel.startswith(trees.HIST_SCOPE)  # the prefix the benchmark's metrics read
    # as a fit traces them: under `mesh.dtype_scope`'s matmul precision, which Mosaic takes for a kernel's
    # contractions too where they state none (the kernel states one pass: "Bad lhs type" for bfloat16 otherwise)
    with jax.enable_x64(False), jax.default_matmul_precision("float32"):
        progs = trees._forest_programs(mesh, n, d, S, "float32", 7, depth, bins, m, "gini", 0, True, True, 1.0, 1.0, 0.0, "pallas")
        plan = progs["plan"]
        assert [lv["rows"] for lv in plan] == ["in_place"] * 5 + ["sorted"] * 8 and all(lv["passes"] == 1 for lv in plan)
        assert [lv.get("kernel", "") for lv in plan] == [""] * 5 + ["pallas"] * 8
        assert [lv["advance"] for lv in plan] == ["masked"] * 13
        w = struct((n,), jnp.float32, ROWS_AXIS)
        if program == "boot":
            draws = struct((n,), jnp.int32, ROWS_AXIS)
            lowered = [progs["draw"].lower(w, scalar(jnp.uint32), scalar(jnp.int32)),
                       progs["boot"].lower(struct((n, S), jnp.float32, ROWS_AXIS, None), w, scalar(jnp.uint32),
                                           scalar(jnp.int32), draws)]
        elif program == "level_4_in_place":
            lowered = [progs["levels"][4].lower(*level_args)]
        else:
            lowered = [progs["levels"][12].lower(*level_args, *ordered)]
        for one in lowered:
            t0 = time.perf_counter()
            compiled = one.compile()
            assert time.perf_counter() - t0 < 60  # 2 to 9 s here
    text = compiled.as_text()
    loops = re.findall(r'= [^\n]* while\([^\n]*op_name="([^"]*)"', text)
    calls = re.findall(r'(%[\w.\-]+) = [^\n]* custom-call\([^\n]*custom_call_target="tpu_custom_call"', text)
    if program == "boot":
        assert loops == [] and calls == []
        assert "custom-call" not in lowered[0].compile().as_text()  # the draws' program
    elif program == "level_4_in_place":
        assert loops and all(f"/{trees.HIST_SCOPE}/" in name for name in loops), loops
        assert len(loops) == 1 and calls == []  # the row tiles
    else:
        assert loops == [], loops  # the sorted tiles and their windows went into the kernel
        assert len(calls) == 1 and calls[0].lstrip("%").startswith(kernel), calls  # one call a device
    if program != "boot":
        assert not re.search(rf"= \S+\[{n // n_dev}\]\S* gather\(", text)  # the row advance: no gather a row
        deepest = S * 4096 * m * bins * 4
        assert compiled.memory_analysis().temp_size_in_bytes < 4 * deepest < n // n_dev * trees.binned_cols(d)


@pytest.mark.parametrize("x64", [False, True])
def test_the_row_advance_compiles_for_a_v5e_in_either_x64_mode(monkeypatch, x64):
    """`ops.trees.advance_rows` at the forest cells' shape (393,216 rows of
    3,072 uint8 columns, the deepest level's 4,096 nodes) compiled for a
    v5e, under the x64 mode a float64 fit traces in too: masked reduces that
    read the binned X where it lies (no copy of it, no temporary of its size
    or of rows x nodes), no per-row gather."""
    import re

    monkeypatch.setenv("TPU_SKIP_MDS_QUERY", "1")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from spark_rapids_ml_tpu.ops import trees

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu")
    except Exception as e:  # no libtpu on this machine: nothing to compile with
        pytest.skip(f"no TPU compiler available ahead of time: {type(e).__name__}: {e}")
    dev = topo.devices[0]
    one_chip = SingleDeviceSharding(dev)
    n, cols, nodes, M = 393_216, 3072, 4096, 2**14 - 1
    assert nodes <= trees.MASKED_ADVANCE_NODES

    def struct(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    with mesh_mod.chip_scope([dev]), jax.enable_x64(x64):
        compiled = jax.jit(lambda *a: trees.advance_rows(*a, nodes - 1, nodes, masked=True)).lower(
            struct((n, cols), jnp.uint8), struct((n,), jnp.int32), struct((n,), jnp.bool_),
            struct((M,), jnp.int32), struct((M,), jnp.int32),
        ).compile()
    text = compiled.as_text()
    assert " gather(" not in text and "custom-call(" not in text
    assert not re.search(rf" = u8\[{n},{cols}\]\S* (copy|transpose)\(", text)
    assert compiled.memory_analysis().temp_size_in_bytes < n * 16
