#
# Runtime/communicator layer tests — the analog of the reference's transport
# test (reference tests/test_ucx.py:36-99: build the communicator clique for
# 1..N ranks and assert a live allGather). Here: mesh construction, pad-and-mask
# global array assembly, PartitionDescriptor allgather through the rendezvous,
# and a live psum over the 8-device mesh via shard_map.
#
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from spark_rapids_ml_tpu.parallel import (
    ROWS_AXIS,
    LocalRendezvous,
    PartitionDescriptor,
    TpuContext,
    get_mesh,
    make_global_rows,
    pad_rows,
)


def test_pad_rows():
    x = np.arange(10, dtype=np.float32).reshape(5, 2)
    xp, n = pad_rows(x, 4)
    assert n == 5
    assert xp.shape == (8, 2)
    np.testing.assert_array_equal(xp[5:], 0)
    xp2, n2 = pad_rows(x, 5)
    assert xp2.shape == (5, 2) and n2 == 5


def test_make_global_rows_weights_mask_padding(mesh8):
    x = np.ones((13, 3), dtype=np.float32)
    X, w, n_valid = make_global_rows(mesh8, x)
    assert n_valid == 13
    assert X.shape[0] % 8 == 0
    # weighted row count sees only valid rows
    assert float(jnp.sum(w)) == 13.0
    # weighted column sums ignore padding
    np.testing.assert_allclose(np.asarray(jnp.sum(X * w[:, None], axis=0)), [13, 13, 13])


def test_live_psum_over_mesh(mesh8):
    from jax import shard_map

    x = np.arange(16, dtype=np.float32).reshape(16, 1)
    X, w, _ = make_global_rows(mesh8, x)

    @jax.jit
    def global_sum(X, w):
        def body(xb, wb):
            local = jnp.sum(xb * wb[:, None])
            return jnp.reshape(jax.lax.psum(local, ROWS_AXIS), (1,))

        return shard_map(
            body, mesh=mesh8, in_specs=(P(ROWS_AXIS, None), P(ROWS_AXIS)),
            out_specs=P(ROWS_AXIS),
        )(X, w)

    out = np.asarray(global_sum(X, w))
    np.testing.assert_allclose(out, np.full(8, x.sum()))


@pytest.mark.parametrize("nranks", [1, 2, 4])
def test_local_rendezvous_allgather(nranks):
    rvs = LocalRendezvous.create(nranks)
    results = [None] * nranks

    def work(r):
        results[r] = rvs[r].allgather(f"rank{r}")

    threads = [threading.Thread(target=work, args=(r,)) for r in range(nranks)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    for r in range(nranks):
        assert results[r] == [f"rank{i}" for i in range(nranks)]


def test_partition_descriptor_via_rendezvous():
    rvs = LocalRendezvous.create(2)
    out = [None, None]

    def work(r):
        out[r] = PartitionDescriptor.build([10 + r], total_cols=5, rank=r, rendezvous=rvs[r])

    threads = [threading.Thread(target=work, args=(r,)) for r in range(2)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    for r in range(2):
        assert out[r].m == 21
        assert out[r].n == 5
        assert out[r].parts_rank_size == [(0, 10), (1, 11)]
    assert out[0].rows_of(1) == 11
    assert out[1].row_offset_of(1) == 10


def test_partition_descriptor_single_controller():
    d = PartitionDescriptor.build([4, 4, 5], total_cols=3)
    assert d.m == 13 and d.n == 3
    assert d.rows_of(2) == 5 and d.row_offset_of(2) == 8


def test_tpu_context_single_process():
    with TpuContext(0, 1) as ctx:
        assert ctx.mesh is not None
        assert ctx.mesh.devices.size >= 1


def test_distributed_transform_matches_single_device(rng):
    # >= distributed_transform_min_rows rows: the batch is row-sharded over the
    # 8-device mesh with replicated model state; result must equal the
    # single-device path bit-for-bit (row-parallel programs, no reductions)
    import pandas as pd

    from spark_rapids_ml_tpu import core as core_mod
    from spark_rapids_ml_tpu.models.classification import LogisticRegression

    n, d = 40000, 8
    x = rng.normal(size=(n, d)).astype(np.float64)
    y = (x[:, 0] > 0).astype(np.float64)
    df = pd.DataFrame({"features": list(x), "label": y})
    m = LogisticRegression(maxIter=30, float32_inputs=False).setFeaturesCol("features").fit(df)

    assert n >= core_mod.config["distributed_transform_min_rows"]
    out_mesh = m.transform(df)
    saved = core_mod.config["distributed_transform_min_rows"]
    try:
        core_mod.config["distributed_transform_min_rows"] = 1 << 60  # force single-device
        out_single = m.transform(df)
    finally:
        core_mod.config["distributed_transform_min_rows"] = saved
    np.testing.assert_array_equal(
        np.asarray(out_mesh["prediction"]), np.asarray(out_single["prediction"])
    )
    def _mat(col):
        return np.stack([v.toArray() if hasattr(v, "toArray") else np.asarray(v) for v in col])

    pm = _mat(out_mesh["probability"])
    ps = _mat(out_single["probability"])
    np.testing.assert_allclose(pm, ps, rtol=1e-12, atol=1e-15)


def test_distributed_transform_rf_and_kmeans(rng):
    import pandas as pd

    from spark_rapids_ml_tpu import core as core_mod
    from spark_rapids_ml_tpu.models.clustering import KMeans
    from spark_rapids_ml_tpu.models.regression import RandomForestRegressor

    n, d = 33000, 6
    x = rng.normal(size=(n, d)).astype(np.float64)
    y = x[:, 0] * 2 + rng.normal(size=n) * 0.1
    df = pd.DataFrame({"features": list(x), "label": y})

    km = KMeans(k=5, maxIter=5, seed=1).setFeaturesCol("features").fit(df)
    rf = (
        RandomForestRegressor(numTrees=4, maxDepth=4, seed=1)
        .setFeaturesCol("features")
        .fit(df)
    )
    saved = core_mod.config["distributed_transform_min_rows"]
    out_km_mesh = km.transform(df)
    out_rf_mesh = rf.transform(df)
    try:
        core_mod.config["distributed_transform_min_rows"] = 1 << 60
        out_km_single = km.transform(df)
        out_rf_single = rf.transform(df)
    finally:
        core_mod.config["distributed_transform_min_rows"] = saved
    np.testing.assert_array_equal(
        np.asarray(out_km_mesh["prediction"]), np.asarray(out_km_single["prediction"])
    )
    np.testing.assert_allclose(
        np.asarray(out_rf_mesh["prediction"]),
        np.asarray(out_rf_single["prediction"]),
        rtol=1e-12,
    )


def test_barrier_rendezvous_adapter():
    # duck-typed BarrierTaskContext: the adapter exposes the framework's
    # allgather contract over Spark's allGather (reference cuml_context.py:80-103)
    from spark_rapids_ml_tpu.parallel import BarrierRendezvous

    class FakeBarrierCtx:
        def __init__(self):
            self.sent = []

        def partitionId(self):
            return 2

        def getTaskInfos(self):
            return [object()] * 4

        def allGather(self, payload):
            self.sent.append(payload)
            return [f"r{i}:{payload}" for i in range(4)]

    ctx = FakeBarrierCtx()
    rdv = BarrierRendezvous(ctx)
    assert rdv.rank == 2 and rdv.nranks == 4
    out = rdv.allgather("hello")
    assert out == ["r0:hello", "r1:hello", "r2:hello", "r3:hello"]
    rdv.barrier()
    assert ctx.sent == ["hello", ""]


@pytest.mark.parametrize("rows", [[5, 17, 2], [0, 3], [4, 0, 0]])
def test_allgather_ndarray_ragged_row_counts(rows):
    # ragged per-rank row counts force the chunk-count AGREEMENT round to do
    # real work (every rank must adopt the max), and zero-row ranks must
    # still complete every round — all under the new per-round deadline
    # (timeout_s set, so a desynced rank would fail typed, not hang)
    from spark_rapids_ml_tpu.parallel.context import allgather_ndarray

    nranks = len(rows)
    rvs = LocalRendezvous.create(nranks, timeout_s=30.0)
    arrs = [
        (np.arange(r * 4, dtype=np.float64).reshape(r, 4) + 1000.0 * i)
        for i, r in enumerate(rows)
    ]
    results = [None] * nranks

    def work(r):
        # chunk_bytes=64 -> 2 rows per chunk: the 17-row rank needs 9 rounds
        results[r] = allgather_ndarray(rvs[r], arrs[r], chunk_bytes=64)

    threads = [threading.Thread(target=work, args=(r,)) for r in range(nranks)]
    [t.start() for t in threads]
    [t.join(timeout=60) for t in threads]
    assert not any(t.is_alive() for t in threads)
    for r in range(nranks):
        assert results[r] is not None, f"rank {r} did not finish"
        assert len(results[r]) == nranks
        for i in range(nranks):
            assert results[r][i].shape == (rows[i], 4)
            np.testing.assert_array_equal(results[r][i], arrs[i])


def test_allgather_ndarray_zero_row_rank_chunk_agreement():
    # the zero-row rank's local chunk count is 1; it must still participate
    # in all 5 of the big rank's chunk rounds or every peer would hang —
    # regression pin for the chunk-count agreement round
    from spark_rapids_ml_tpu.parallel.context import allgather_ndarray

    rvs = LocalRendezvous.create(2, timeout_s=20.0)
    arrs = [np.zeros((0, 8)), np.arange(80, dtype=np.float64).reshape(10, 8)]
    results = [None, None]

    def work(r):
        results[r] = allgather_ndarray(rvs[r], arrs[r], chunk_bytes=128)  # 2 rows/chunk

    threads = [threading.Thread(target=work, args=(r,)) for r in range(2)]
    [t.start() for t in threads]
    [t.join(timeout=30) for t in threads]
    assert not any(t.is_alive() for t in threads)
    for r in range(2):
        assert results[r][0].shape == (0, 8)
        np.testing.assert_array_equal(results[r][1], arrs[1])
    # both ranks ran the same number of rounds (agreement + 5 chunk rounds each)
    assert rvs[0]._round == rvs[1]._round


def test_allgather_ndarray_chunked(tmp_path):
    # broadcast_chunk_bytes bounds each control-plane round's payload; the
    # reassembled arrays must be identical to the unchunked gather
    import uuid

    from spark_rapids_ml_tpu.parallel import FileRendezvous
    from spark_rapids_ml_tpu.parallel.context import allgather_ndarray

    # single-rank rendezvous keeps this a unit test (chunk logic is rank-local)
    rdv = FileRendezvous(0, 1, str(tmp_path), run_id=uuid.uuid4().hex)
    arr = np.arange(1000, dtype=np.float64).reshape(100, 10)
    out = allgather_ndarray(rdv, arr, chunk_bytes=800)  # ~10 rows per chunk
    assert len(out) == 1
    np.testing.assert_array_equal(out[0], arr)
    # round counter advanced by more than one round (it actually chunked)
    assert rdv._round > 3


# ------------------------------------------------ hierarchical / sub-mesh ---
#
# The sub-mesh placement substrate (docs/scheduling.md "2-D placement"):
# build_mesh composes an ICI `rows` axis with a DCN axis across process
# groups; submesh carves contiguous chip runs; survivor_mesh composes with
# both so a sweep shard that loses a host re-meshes its OWN carve.


class _FakeDev:
    """Stand-in device for topology-only mesh math (jax.sharding.Mesh takes
    any object; no program ever runs on these)."""

    def __init__(self, did, process_index):
        self.id = did
        self.process_index = process_index

    def __repr__(self):  # pragma: no cover - debug aid
        return f"fake(d{self.id}@p{self.process_index})"


def _fake_pool(n_procs, per_proc):
    return [
        _FakeDev(p * per_proc + i, p) for p in range(n_procs) for i in range(per_proc)
    ]


def test_get_mesh_divisibility_is_typed_and_names_both_sides():
    from spark_rapids_ml_tpu.errors import MeshTopologyError, SrmlError

    with pytest.raises(MeshTopologyError) as ei:
        get_mesh(3)  # 8-device pool: 3 does not divide it
    assert isinstance(ei.value, SrmlError)
    assert ei.value.requested == 3
    assert ei.value.available == 8
    assert "num_workers=3" in str(ei.value) and "8-device" in str(ei.value)
    with pytest.raises(MeshTopologyError):
        get_mesh(0)
    with pytest.raises(MeshTopologyError):
        get_mesh(16)
    assert get_mesh(4).devices.size == 4  # divisors still build


def test_build_mesh_flat_default_and_2d_topology():
    from spark_rapids_ml_tpu.parallel import DCN_AXIS, build_mesh

    flat = build_mesh()
    assert flat.axis_names == (ROWS_AXIS,)
    assert flat.devices.size == 8

    pool = _fake_pool(n_procs=2, per_proc=4)
    m = build_mesh({"dcn": 2, "rows": 4}, devices=pool)
    assert m.axis_names == (DCN_AXIS, ROWS_AXIS)
    assert m.devices.shape == (2, 4)
    # each DCN row is ONE process group's ICI-connected chips
    for row in m.devices:
        assert len({d.process_index for d in row}) == 1

    # "auto" axes: dcn defaults to the process-group count
    auto = build_mesh({"dcn": 0}, devices=pool)
    assert auto.devices.shape == (2, 4)
    rows_only = build_mesh({"rows": 2}, devices=pool)
    assert rows_only.devices.shape == (4, 2)


def test_build_mesh_rejects_bad_topologies():
    from spark_rapids_ml_tpu.errors import MeshTopologyError
    from spark_rapids_ml_tpu.parallel import build_mesh

    pool = _fake_pool(n_procs=2, per_proc=4)
    with pytest.raises(MeshTopologyError) as ei:
        build_mesh({"dcn": 3, "rows": 4}, devices=pool)  # 12 != 8
    assert ei.value.available == 8
    assert ei.value.topology == {"dcn": 3, "rows": 4}
    with pytest.raises(MeshTopologyError):
        build_mesh({"ici": 8}, devices=pool)  # unknown axis name


def test_build_mesh_reads_config_topology_knob():
    from spark_rapids_ml_tpu import core as core_mod
    from spark_rapids_ml_tpu.parallel import DCN_AXIS, build_mesh

    saved = core_mod.config["mesh_topology"]
    core_mod.config["mesh_topology"] = {"dcn": 2, "rows": 4}
    try:
        m = build_mesh()  # deployment-wide default from config
        assert m.axis_names == (DCN_AXIS, ROWS_AXIS)
        assert m.devices.shape == (2, 4)
        flat = build_mesh({})  # an explicit empty topology wins over config
        assert flat.axis_names == (ROWS_AXIS,)
    finally:
        core_mod.config["mesh_topology"] = saved


def test_submesh_carves_contiguous_runs_only():
    from spark_rapids_ml_tpu.errors import MeshTopologyError
    from spark_rapids_ml_tpu.parallel import submesh

    mesh = get_mesh(8)
    flat = list(mesh.devices.flatten())

    first4 = submesh(mesh, 4)
    assert first4.axis_names == (ROWS_AXIS,)
    assert list(first4.devices.flatten()) == flat[:4]

    right = submesh(mesh, [4, 5, 6, 7])
    assert list(right.devices.flatten()) == flat[4:]
    by_dev = submesh(mesh, flat[2:5])  # device objects work too
    assert list(by_dev.devices.flatten()) == flat[2:5]

    with pytest.raises(MeshTopologyError):
        submesh(mesh, [0, 2])  # gapped: ICI run broken
    with pytest.raises(MeshTopologyError):
        submesh(mesh, [6, 7, 8])  # out of range
    with pytest.raises(MeshTopologyError):
        submesh(mesh, 9)  # wider than the pool
    with pytest.raises(MeshTopologyError):
        submesh(mesh, [])  # empty carve


def test_submesh_of_hierarchical_mesh_and_survivor_composition():
    from spark_rapids_ml_tpu.parallel import DCN_AXIS, build_mesh, submesh

    pool = _fake_pool(n_procs=2, per_proc=4)
    m2d = build_mesh({"dcn": 2, "rows": 4}, devices=pool)

    # carve one DCN row (one host's chips) as a 1-D rows sub-mesh
    row0 = submesh(m2d, 4)
    assert row0.axis_names == (ROWS_AXIS,)
    assert [d.process_index for d in row0.devices.flatten()] == [0] * 4

    # PR-6 recovery composes with the carve: losing a fictional process
    # keeps the carve; losing the carve's own host raises (nothing left)
    from spark_rapids_ml_tpu.errors import MeshTopologyError
    from spark_rapids_ml_tpu.parallel import survivor_mesh

    same = survivor_mesh(row0, {9})
    assert list(same.devices.flatten()) == list(row0.devices.flatten())
    with pytest.raises(MeshTopologyError):
        survivor_mesh(row0, {0})

    # 2-D mesh, whole DCN row dies: hierarchy survives intact
    kept = survivor_mesh(m2d, {1})
    assert kept.axis_names == (DCN_AXIS, ROWS_AXIS)
    assert kept.devices.shape == (1, 4)
    assert all(d.process_index == 0 for d in kept.devices.flatten())

    # partial row death degrades to the flat 1-D survivors (a ragged 2-D
    # grid is not a mesh): each DCN row here spans TWO processes, so losing
    # one process leaves its row half-alive
    ragged_pool = _fake_pool(n_procs=4, per_proc=2)
    m24 = build_mesh({"dcn": 2, "rows": 4}, devices=ragged_pool)
    flatd = survivor_mesh(m24, {3})
    assert flatd.axis_names == (ROWS_AXIS,)
    assert flatd.devices.size == 6


def test_chip_scope_pins_default_devices_context_locally():
    from spark_rapids_ml_tpu.parallel import (
        chip_scope,
        current_chip_scope,
        default_devices,
    )

    pool = default_devices()
    seen = {}

    def worker():
        # a sibling thread must NOT see the main thread's pin
        seen["other"] = list(default_devices())

    with chip_scope(pool[4:]):
        assert current_chip_scope() == tuple(pool[4:])
        assert default_devices() == pool[4:]
        assert get_mesh().devices.size == 4  # downstream mesh calls follow
        t = threading.Thread(target=worker)
        t.start()
        t.join(timeout=10)
    assert seen["other"] == pool
    assert current_chip_scope() is None
    assert default_devices() == pool


def test_shard_map_fold_grid_on_carved_submesh(mesh8):
    # the SPMD-batched sweep substrate: a vmapped fold grid under shard_map
    # over a CARVED sub-mesh computes exactly what plain numpy does on the
    # same rows — folds batch INSIDE the shard body, collectives stay on the
    # sub-mesh's own `rows` axis
    from jax.sharding import NamedSharding

    from spark_rapids_ml_tpu.parallel import submesh
    from jax import shard_map

    from spark_rapids_ml_tpu.parallel.mesh import row_sharding

    sub = submesh(mesh8, 4)
    n_rows = sub.devices.size * 2
    x = np.arange(n_rows * 3, dtype=np.float32).reshape(n_rows, 3)
    masks = np.stack([
        np.tile(np.array([1.0, 0.0], np.float32), n_rows // 2),
        np.tile(np.array([0.0, 1.0], np.float32), n_rows // 2),
    ])  # (2 folds, n_rows)

    X = jax.device_put(x, row_sharding(sub, 2))
    M = jax.device_put(masks, NamedSharding(sub, P(None, ROWS_AXIS)))

    def body(xs, ms):
        def one_fold(m):  # xs: (local_rows, 3), m: (local_rows,)
            return jax.lax.psum(jnp.sum(xs * m[:, None]), ROWS_AXIS)

        return jax.vmap(one_fold)(ms)

    got = np.asarray(
        shard_map(
            body, mesh=sub,
            in_specs=(P(ROWS_AXIS, None), P(None, ROWS_AXIS)),
            out_specs=P(),
        )(X, M)
    )
    want = (x[None, :, :] * masks[:, :, None]).sum(axis=(1, 2))
    np.testing.assert_allclose(got, want, rtol=1e-6)
