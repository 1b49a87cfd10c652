#
# Parallel runtime: device mesh management, row-sharded global-array assembly,
# partition bookkeeping, and the distributed process-group context.
#
# This is the TPU-native replacement for the reference's L4 communicator stack
# (reference common/cuml_context.py NCCL/UCX clique + utils.py PartitionDescriptor):
# collectives are XLA `psum`/`all_gather`/`ppermute` over a `jax.sharding.Mesh`
# (ICI within a slice, DCN across), and the rendezvous/control plane is an
# `allgather`-of-strings abstraction that maps onto Spark's
# `BarrierTaskContext.allGather` when running under Spark, or a no-op in
# single-controller mode.
#
from .mesh import (  # noqa: F401
    DCN_AXIS,
    ROWS_AXIS,
    bucket_rows,
    bucket_size,
    build_mesh,
    chip_scope,
    current_chip_scope,
    default_devices,
    device_platforms,
    ensure_compilation_cache,
    get_mesh,
    make_global_rows,
    pad_rows,
    place_row_shards,
    place_rows,
    replicated,
    row_sharding,
    set_devices,
    shard_row_slices,
    submesh,
    survivor_mesh,
)
from .partition import PartitionDescriptor  # noqa: F401
from .context import (  # noqa: F401
    BarrierRendezvous,
    FileRendezvous,
    LocalRendezvous,
    Rendezvous,
    TpuContext,
    allgather_ndarray,
)
from .chaos import ChaosRendezvous  # noqa: F401
