#
# Device-mesh helpers: the substrate every solver runs on.
#
# Design: all solvers are SPMD programs over a 1-D mesh axis `rows` (data
# parallelism over row blocks — the reference's only data-plane parallelism, see
# SURVEY.md §2.4). Row counts are padded to a multiple of the mesh size and the
# padding is neutralized with zero sample-weights, which unifies the reference's
# ragged `parts_rank_size` handling (cuML MG accepts ragged blocks; SPMD XLA
# wants equal ones) with `weightCol` support.
#
from __future__ import annotations

import collections
import contextlib
import contextvars
import functools
from typing import Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import telemetry
from ..errors import MeshTopologyError

ROWS_AXIS = "rows"
# outer axis of a hierarchical mesh: one step per jax.distributed process
# group (DCN hops cross process boundaries; ICI stays inside one group)
DCN_AXIS = "dcn"


# Device-resolution hook: which devices the framework runs on. Overridable for
# tests (virtual multi-device CPU mesh while a real TPU backend is registered)
# and for pinning a subset of chips. Resolution order: explicit override ->
# SRML_PLATFORM env var -> jax.devices().
_DEVICE_OVERRIDE: Optional[list] = None


def set_devices(devices_or_platform: Union[str, list, None]) -> None:
    """Override the framework's device pool ('cpu', 'tpu', a device list, or None)."""
    global _DEVICE_OVERRIDE
    if devices_or_platform is None:
        _DEVICE_OVERRIDE = None
    elif isinstance(devices_or_platform, str):
        _DEVICE_OVERRIDE = list(jax.devices(devices_or_platform))
    else:
        _DEVICE_OVERRIDE = list(devices_or_platform)


# Context-local chip pinning: the sub-mesh placement engine runs co-admitted
# jobs on DISJOINT chip sets concurrently, so the pin must be per
# thread/task — `set_devices` is process-global and would race. The scope is
# consulted FIRST by `default_devices()`: a job inside `chip_scope(chips)`
# sees only its claimed chips, so every downstream mesh/placement/capacity
# call lands on the claimed sub-mesh without threading a device list.
_CHIP_SCOPE: "contextvars.ContextVar[Optional[tuple]]" = contextvars.ContextVar(
    "srml_chip_scope", default=None
)


@contextlib.contextmanager
def chip_scope(devices: Sequence):
    """Pin `default_devices()` to an explicit chip set for the duration of
    the with-block, context-locally (threads/tasks see their own pin). The
    scheduler wraps each co-admitted job's fit in the job's claimed chip
    set; tests use it to emulate a carved sub-mesh."""
    token = _CHIP_SCOPE.set(tuple(devices))
    try:
        yield
    finally:
        _CHIP_SCOPE.reset(token)


def current_chip_scope() -> Optional[Tuple]:
    """The enclosing `chip_scope` pin, or None (whole pool)."""
    return _CHIP_SCOPE.get()


def default_devices() -> list:
    import os

    scoped = _CHIP_SCOPE.get()
    if scoped is not None:
        return list(scoped)
    if _DEVICE_OVERRIDE is not None:
        return _DEVICE_OVERRIDE
    platform = os.environ.get("SRML_PLATFORM")
    if platform:
        return list(jax.devices(platform))
    return list(jax.devices())


def device_platforms() -> list:
    """Every platform a run would touch: those of the framework's device
    pool plus jax's default backend. A chip measurement (chip_smoke.py,
    bench.py, the protocol runner) runs only when this is exactly
    ``["tpu"]`` — never on whatever happens to be there."""
    return sorted({d.platform for d in default_devices()} | {jax.default_backend()})


def default_local_device():
    """First framework device ADDRESSABLE by this process. Transform of a
    process-local batch must never target another process's device (under
    multi-process SPMD `default_devices()[0]` is rank 0's device — placing
    there from rank 1 deadlocks)."""
    local = [d for d in default_devices() if d.process_index == jax.process_index()]
    return local[0] if local else jax.local_devices()[0]


def get_mesh(num_workers: Optional[int] = None, devices=None) -> Mesh:
    """Build a 1-D `rows` mesh over the first `num_workers` visible devices.

    In multi-process (multi-host) runs `jax.devices()` is the global device list,
    so the same call yields the global mesh on every process — the direct analog
    of the reference's NCCL clique of `num_workers` ranks
    (reference common/cuml_context.py:36-148).
    """
    if devices is None:
        devices = default_devices()
    if num_workers is None:
        num_workers = len(devices)
    num_workers = int(num_workers)
    if num_workers <= 0:
        raise MeshTopologyError(
            f"num_workers={num_workers} must be positive",
            requested=num_workers, available=len(devices),
        )
    if num_workers > len(devices):
        raise MeshTopologyError(
            f"num_workers={num_workers} exceeds visible devices "
            f"({len(devices)}); set num_workers or start more processes",
            requested=num_workers, available=len(devices),
        )
    if len(devices) % num_workers != 0:
        # an uneven split used to surface as an opaque numpy reshape error
        # deep inside row padding; refuse typed at mesh construction instead
        raise MeshTopologyError(
            f"num_workers={num_workers} does not divide the "
            f"{len(devices)}-device pool evenly; pick a worker count that "
            "divides the device count (or carve an explicit sub-mesh with "
            "submesh()/chip_scope())",
            requested=num_workers, available=len(devices),
        )
    return Mesh(np.asarray(devices[:num_workers]), (ROWS_AXIS,))


def build_mesh(
    topology: Optional[Dict[str, int]] = None, devices=None
) -> Mesh:
    """Build the framework mesh, hierarchically when asked.

    ``topology=None`` (default) is the flat 1-D `rows` mesh over every
    visible device — exactly `get_mesh()`. A topology dict composes an ICI
    axis with a DCN axis: ``{"dcn": D, "rows": R}`` builds a 2-D
    ``(dcn, rows)`` `jax.sharding.Mesh` whose outer axis steps across
    `jax.distributed` process groups (devices are stably grouped by
    `process_index`, so each DCN row is one host's ICI-connected chips) and
    whose inner axis is the per-group chip count. Either axis may be 0/absent
    ("auto"): `dcn` defaults to the process-group count, `rows` to the
    remaining factor. The axis product must cover the pool exactly — a
    mismatch raises the typed `MeshTopologyError` naming both sides.

    Fold grids vmap under `shard_map` over the inner `rows` axis of the
    result (or of a `submesh()` carved from it); collectives along `dcn`
    cross the data-center network and stay in the control plane."""
    if topology is None:
        # the config knob is the deployment-wide default; an explicit
        # argument (even {}) wins
        from ..core import config

        topology = config.get("mesh_topology")
    if devices is None:
        devices = default_devices()
    devices = list(devices)
    if not topology:
        return get_mesh(len(devices), devices)
    unknown = set(topology) - {DCN_AXIS, ROWS_AXIS}
    if unknown:
        raise MeshTopologyError(
            f"unknown topology axes {sorted(unknown)}; expected "
            f"{DCN_AXIS!r} and/or {ROWS_AXIS!r}",
            topology={k: int(v) for k, v in topology.items()},
        )
    # stable process grouping: jax.devices() is process-ordered already, but
    # an explicit device list may not be — sort stably so each DCN row holds
    # one process group's ICI-connected chips
    devices.sort(key=lambda d: int(getattr(d, "process_index", 0)))
    n_groups = len({int(getattr(d, "process_index", 0)) for d in devices})
    dcn = int(topology.get(DCN_AXIS) or 0)
    rows = int(topology.get(ROWS_AXIS) or 0)
    if dcn <= 0 and rows <= 0:
        dcn = max(1, n_groups)
    if dcn <= 0:
        dcn = len(devices) // rows if rows and len(devices) % rows == 0 else 0
    if rows <= 0:
        rows = len(devices) // dcn if dcn and len(devices) % dcn == 0 else 0
    if dcn <= 0 or rows <= 0 or dcn * rows != len(devices):
        raise MeshTopologyError(
            "topology axis product must cover the device pool exactly",
            requested=(dcn * rows) if dcn > 0 and rows > 0 else None,
            available=len(devices),
            topology={DCN_AXIS: dcn, ROWS_AXIS: rows},
        )
    if telemetry.enabled():
        telemetry.registry().inc("mesh.hierarchical_builds")
    grid = np.empty((dcn, rows), dtype=object)
    for i, d in enumerate(devices):
        grid[i // rows, i % rows] = d
    return Mesh(grid, (DCN_AXIS, ROWS_AXIS))


def submesh(mesh: Mesh, chips: Union[int, Sequence]) -> Mesh:
    """Carve a CONTIGUOUS chip subset out of `mesh` as a 1-D `rows`
    sub-mesh — the unit the 2-D scheduler places fits, serving replicas,
    and sweep shards on, so disjoint carves own disjoint chips concurrently.

    `chips` is an int (the first N chips in mesh order) or an explicit
    sequence of mesh-order indices / device objects. Contiguity (in the
    parent's flattened order, i.e. ICI-neighbor runs within a DCN row) is
    enforced: a gapped carve raises `MeshTopologyError` — scattered chips
    would silently route ICI collectives over DCN."""
    flat = list(mesh.devices.flatten())
    if isinstance(chips, (int, np.integer)):
        n = int(chips)
        if n <= 0 or n > len(flat):
            raise MeshTopologyError(
                f"submesh: cannot carve {n} chips from a "
                f"{len(flat)}-chip mesh",
                requested=n, available=len(flat),
            )
        picked = flat[:n]
    else:
        by_id = {id(d): i for i, d in enumerate(flat)}
        idx = []
        for c in chips:
            if isinstance(c, (int, np.integer)):
                i = int(c)
                if i < 0 or i >= len(flat):
                    raise MeshTopologyError(
                        f"submesh: chip index {i} out of range",
                        requested=i, available=len(flat),
                    )
            else:
                if id(c) not in by_id:
                    raise MeshTopologyError(
                        f"submesh: device {c} is not part of the parent mesh",
                        available=len(flat),
                    )
                i = by_id[id(c)]
            idx.append(i)
        if not idx:
            raise MeshTopologyError(
                "submesh: empty chip set", requested=0, available=len(flat)
            )
        idx.sort()
        if len(set(idx)) != len(idx) or idx[-1] - idx[0] + 1 != len(idx):
            raise MeshTopologyError(
                f"submesh: chip set {idx} is not a contiguous run in the "
                "parent mesh order",
                requested=len(idx), available=len(flat),
            )
        picked = [flat[i] for i in idx]
    if telemetry.enabled():
        telemetry.registry().inc("mesh.submesh_carves")
    return Mesh(np.asarray(picked), (ROWS_AXIS,))


def survivor_mesh(mesh: Mesh, dead_process_indices) -> Mesh:
    """Rebuild a mesh over the devices NOT owned by the dead processes — the
    re-sharding half of elastic recovery: under GSPMD a rank loss is a mesh +
    placement change, not a solver rewrite (docs/robustness.md "Elastic
    recovery"). Raises when no devices survive.

    Composes with the hierarchical/sub-mesh substrate: a 1-D mesh (whole
    pool OR a `submesh()` carve — a sweep shard that loses a host re-meshes
    its own sub-mesh, not the whole pool) survives as a 1-D `rows` mesh over
    the remaining chips; a 2-D `(dcn, rows)` mesh keeps its hierarchy when
    whole DCN rows die, and degrades to the flat 1-D survivors otherwise
    (a ragged 2-D grid is not a mesh)."""
    dead = {int(p) for p in dead_process_indices}
    devices = [d for d in mesh.devices.flatten() if int(d.process_index) not in dead]
    if not devices:
        raise MeshTopologyError(
            "survivor_mesh: no devices remain after excluding processes "
            f"{sorted(dead)}",
            requested=0, available=0,
        )
    if telemetry.enabled():
        telemetry.registry().inc("recovery.mesh_rebuilds")
    if mesh.devices.ndim == 2:
        rows = [
            list(row)
            for row in mesh.devices
            if all(int(d.process_index) not in dead for d in row)
        ]
        if rows and len(rows) * len(rows[0]) == len(devices):
            # only whole DCN rows died: the hierarchy survives intact
            grid = np.empty((len(rows), len(rows[0])), dtype=object)
            for i, row in enumerate(rows):
                for j, d in enumerate(row):
                    grid[i, j] = d
            return Mesh(grid, mesh.axis_names)
    return Mesh(np.asarray(devices), (ROWS_AXIS,))


def row_sharding(mesh: Mesh, ndim: int = 2) -> NamedSharding:
    """NamedSharding that shards axis 0 over `rows` and replicates the rest."""
    return NamedSharding(mesh, P(ROWS_AXIS, *([None] * (ndim - 1))))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


_COMPILE_CACHE_DIR: Optional[str] = None  # dir currently wired into jax, if any


def compilation_cache_dir() -> str:
    """The ONE persistent-cache directory of this process (XLA programs):
    `JAX_COMPILATION_CACHE_DIR` where it is set — the program then never
    configures another — else ``core.config["compilation_cache_dir"]``, whose
    default is a fixed git-ignored directory in the checkout."""
    import os

    from ..core import _DEFAULT_COMPILE_CACHE_DIR, config

    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        config.get("compilation_cache_dir") or _DEFAULT_COMPILE_CACHE_DIR
    )


def ensure_compilation_cache() -> None:
    """Point XLA's PERSISTENT compilation cache at `compilation_cache_dir()`,
    so compiled programs survive process restarts — a transform fleet's
    bucket-ladder programs and a sweep's batched solver compile once per
    cluster, not once per process. Called from the fit, transform and
    serving-load entry points; re-pointing the config dir takes effect on
    the next call. A CPU device pool is left as the environment configured
    it: XLA:CPU programs compile in moments, and this jaxlib's CPU loader
    logs a machine-feature warning for every cached entry it reads back."""
    global _COMPILE_CACHE_DIR
    path = compilation_cache_dir()
    if path == _COMPILE_CACHE_DIR or default_devices()[0].platform == "cpu":
        return
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_compilation_cache_dir", path)
    # default thresholds skip sub-second programs — the dispatch-bound
    # serving shapes this cache exists for; persist everything
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # jax latches "is the cache in use" at the process's FIRST compile; one
    # that ran before this call (a probe, user code) latched the old answer
    compilation_cache.reset_cache()
    _COMPILE_CACHE_DIR = path


@contextlib.contextmanager
def dtype_scope(dtype, matmul_precision: str = "float32"):
    """Numerics context for the framework's own computations: real f64 when
    asked for, and a PER-SOLVER matmul precision.

    - JAX's default `jax_enable_x64=False` silently downcasts f64 to f32; a user
      who passed ``float32_inputs=False`` asked for double precision (the
      reference supports f64 end-to-end; SURVEY.md §7 'float64 parity'). The
      flag is enabled via the scoped context so the user's own JAX code keeps
      its default semantics.
    - TPU matmuls default to one-pass bf16 on the MXU (~3 decimal digits) —
      fine for neural nets, wrong for most classical ML. Each solver picks the
      cheapest precision that preserves its numeric contract via the estimator's
      `_matmul_precision` attribute (plumbed here by core._call_fit_func):

        * ``"float32"`` (default, 6-pass MXU): CPU-equivalent f32 accuracy.
          Required by kNN/DBSCAN distance expansions (sklearn-exact parity
          asserted in tests; raw bf16 shows ~2% distance error on a v5e chip)
          and used for covariance/gram/L-BFGS solvers where parity tolerances
          are tight.
        * ``"BF16_BF16_F32_X3"`` (3-pass MXU, ~2x the f32 throughput): used by
          KMeans — Lloyd's argmin assignment tolerates the ~1e-6 relative
          error of the 3-pass expansion, and the center-update reductions are
          plain f32 sums (no matmul), so inertia/center parity holds while the
          dominant distance matmul runs twice as fast.

      CPU/GPU backends ignore the hint (always full f32), so test parity on the
      virtual CPU mesh is unaffected either way.
    """
    with contextlib.ExitStack() as stack:
        if np.dtype(dtype) == np.float64 and not jax.config.jax_enable_x64:
            stack.enter_context(jax.enable_x64(True))  # scoped x64
        if np.dtype(dtype) == np.float64:
            matmul_precision = "float32"  # f64 runs don't want a reduced-pass MXU mode
        stack.enter_context(jax.default_matmul_precision(matmul_precision))
        yield


def pad_rows(x: np.ndarray, multiple: int) -> Tuple[np.ndarray, int]:
    """Zero-pad axis 0 of `x` to a multiple of `multiple`; returns (padded, n_valid)."""
    n = x.shape[0]
    rem = (-n) % multiple
    if rem == 0:
        return x, n
    pad_widths = [(0, rem)] + [(0, 0)] * (x.ndim - 1)
    return np.pad(x, pad_widths), n


def bucket_size(n: int, *, multiple: int = 1, min_rows: int = 256, cap: Optional[int] = None) -> int:
    """Row count of the bucket that batch size `n` pads up to.

    Serving pads every transform batch to a small GEOMETRIC ladder of row
    buckets (min_rows, 2·min_rows, 4·min_rows, ...) instead of running the
    exact batch shape: a jitted `predict` then compiles once per BUCKET, not
    once per distinct tail shape — on a TPU backend each avoided compile is
    tens of seconds. Every rung is rounded up to `multiple` (the mesh shard
    count on the distributed path), and the ladder is capped at `cap`
    (aligned up) so a near-full tail batch reuses the full-batch program
    instead of minting one more rung."""
    if multiple < 1:
        multiple = 1
    b = max(min_rows, multiple)
    b = -(-b // multiple) * multiple
    cap_aligned = None
    if cap is not None:
        cap_aligned = -(-max(cap, multiple) // multiple) * multiple
        if n >= cap_aligned:
            return cap_aligned
    while b < n:
        b = -(-(b * 2) // multiple) * multiple
    if cap_aligned is not None:
        b = min(b, cap_aligned)
    return b


def bucket_ladder(
    max_rows: int, *, multiple: int = 1, min_rows: int = 256, cap: Optional[int] = None
) -> list:
    """Every distinct rung `bucket_size` can return for batch sizes
    1..max_rows — the set of predict-program shapes serving traffic in that
    range can ever dispatch, and therefore exactly what the serving plane's
    load-time prewarm compiles (docs/serving.md). Derived by WALKING
    `bucket_size` itself (next probe = previous rung + 1), so the ladder can
    never drift from the padding function that defines it."""
    max_rows = max(1, int(max_rows))
    rungs: list = []
    n = 1
    while True:  # blocking-ok: pure arithmetic walk — rungs strictly grow until max_rows/cap, no waiting
        b = bucket_size(n, multiple=multiple, min_rows=min_rows, cap=cap)
        if rungs and b <= rungs[-1]:
            break  # the cap rung repeats for every larger n — ladder is done
        rungs.append(b)
        if b >= max_rows:
            break
        n = b + 1
    return rungs


def bucket_rows(
    x: np.ndarray, *, multiple: int = 1, min_rows: int = 256, cap: Optional[int] = None
) -> Tuple[np.ndarray, int]:
    """Zero-pad axis 0 of `x` up to its `bucket_size` rung; returns
    (padded, n_valid). THE one sanctioned padding entry point for
    transform/serving code (the ci/analysis gate forbids raw `pad_rows` there): callers
    slice every output back to `n_valid` rows."""
    b = bucket_size(x.shape[0], multiple=multiple, min_rows=min_rows, cap=cap)
    n = x.shape[0]
    if b == n:
        return x, n
    pad_widths = [(0, b - n)] + [(0, 0)] * (x.ndim - 1)
    return np.pad(x, pad_widths), n


# ---------------------------------------------------------------------------
# X's layout on the device (docs/performance.md "Tiled distance core").
#
# `jax.device_put(x, device)` leaves the layout to the device. A TPU picks, of
# {1,0} (rows contiguous) and {0,1}, the one that pads the (8, 128) tiles
# less: a float32 [393216, 3000] block comes out COLUMN-major (3,000 is not a
# multiple of the 128 lanes, 393,216 is). The Pallas distance kernels state
# {1,0} for their operands, so XLA turned every row tile of every Lloyd
# iteration before them (half of a busy chip's time at that shape). An
# estimator whose solver feeds row tiles of X to those kernels asks for
# `X_ROW_MAJOR` (`_TpuCaller._x_layout`) and the placement below obliges; a
# jitted step called with the committed array compiles for the layout it has.
# ---------------------------------------------------------------------------

X_DEFAULT = "default"  # whatever the device chooses: every placement but the one below
X_ROW_MAJOR = "row_major"  # {1,0:T(8,128)}: the distance kernels' operand layout

_ROW_MAJOR = (0, 1)  # `Layout.major_to_minor` of a [rows, d] block with rows contiguous

# Rows reach a row-major buffer in pieces of about this many bytes (see
# `_place_row_major`): two of them in flight bound what the placement holds
# beyond X itself.
_ROW_MAJOR_PIECE_BYTES = 48 << 20


def _default_is_row_major(shape, dtype, device) -> bool:
    from jax.experimental.layout import Layout

    default = device.client.get_default_layout(np.dtype(dtype), tuple(shape), device)
    return tuple(Layout.from_pjrt_layout(default).major_to_minor) == _ROW_MAJOR


def row_major_format(shape, dtype, device):
    """The `Format` that lays a [rows, d] block of `shape` out row-major on
    `device`, or None where there is nothing to ask for: off the TPU (no
    `Format` reaches a backend that may refuse one), for anything but a 2-D
    block, and where row-major is the device's own choice for the shape (d a
    multiple of 128). `shape` is what ONE device holds."""
    if device.platform != "tpu" or len(shape) != 2 or _default_is_row_major(shape, dtype, device):
        return None
    from jax.experimental.layout import Format, Layout
    from jax.sharding import SingleDeviceSharding

    return Format(Layout(major_to_minor=_ROW_MAJOR), SingleDeviceSharding(device))


def lies_row_major(x) -> bool:
    """Whether the 2-D device array `x` lies row-major on its devices, by
    anyone's choice: a placement below, or the device's own (CPU; a TPU at d
    a multiple of 128). Read from the committed array on the host: inside a
    trace no layout is visible."""
    layout = getattr(getattr(x, "format", None), "layout", None)
    return layout is not None and x.ndim == 2 and tuple(layout.major_to_minor) == _ROW_MAJOR


def x_layout_of(x) -> str:
    """`X_ROW_MAJOR` where the 2-D device array `x` lies row-major although
    its device would have chosen otherwise for a shard of that shape — i.e.
    where a placement below engaged — and `X_DEFAULT` for every other array
    (on CPU, and at d a multiple of 128, row-major IS the default)."""
    if not lies_row_major(x):
        return X_DEFAULT
    device = next(iter(x.sharding.addressable_devices))
    if _default_is_row_major(x.sharding.shard_shape(x.shape), x.dtype, device):
        return X_DEFAULT
    return X_ROW_MAJOR


@contextlib.contextmanager
def _no_persistent_compile_cache():
    """Compile what runs inside in this process, whatever the persistent
    cache holds. An executable that jax 0.9.0 / libtpu 0.0.34 reads back
    from the cache has lost its OUTPUT layouts: the buffers it returns are
    laid out as compiled, but report the device's default, and the next
    program is then compiled for a layout its argument does not have
    (`INVALID_ARGUMENT: ... expected parameter 0 of size ...`; my chip run,
    PR 28). So the two small programs that MAKE a row-major buffer are never
    read back; the programs that consume one are (a parameter's layout is
    part of the compiled program and survives). jax latches whether the
    cache is in use, hence the resets; a compile on another thread in the
    meantime goes uncached, nothing else."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@functools.lru_cache(maxsize=None)
def _row_major_zeros(fmt, shape, dtype):
    """The jitted program that makes one device's row-major buffer (kept a
    process long, like `_row_major_write`, so each compiles once a shape)."""
    import jax.numpy as jnp

    return jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=fmt)


@functools.lru_cache(maxsize=None)
def _row_major_write(fmt):
    """`write(buf, piece, start)`: puts `piece` at row `start` of the
    row-major `buf` IN PLACE (the buffer is donated and keeps its layout) and
    returns the buffer with a scalar that is ready when the write is done."""

    def write(buf, piece, start):
        return jax.lax.dynamic_update_slice_in_dim(buf, piece, start, 0), start

    return jax.jit(write, out_shardings=(fmt, None), donate_argnums=0)


def _place_row_major(blocks: Sequence[np.ndarray], formats: Sequence) -> List[jax.Array]:
    """One row-major device buffer per host block (equal shapes, one block
    and one `row_major_format` a device).

    jax 0.9.0 has no transfer INTO a chosen layout: `device_put(x, Format)`
    places x in the default layout and relayouts it in one program, which
    holds two X at once (8.9 GiB for the benchmark's 4.4; my chip run, PR
    28) — the published 12.6 GB shape would no longer fit the one chip it
    fits today. So each buffer is made on its device in the wanted layout
    and the rows follow in pieces: a piece is placed as ever (all devices'
    pieces in one batched `device_put`, so their transfers overlap) and a
    donating program writes it into its rows of the buffer, turning 48 MB
    instead of X. At most two pieces a device are alive, the one being
    written and the one in transfer: peak = X + two pieces. Every piece has
    the same row count (the last one is moved back over rows already
    written rather than cut short), so one write program is compiled a
    device — in this process: `_no_persistent_compile_cache`."""
    rows, d = blocks[0].shape
    dtype = blocks[0].dtype
    step = max(8, _ROW_MAJOR_PIECE_BYTES // max(1, d * dtype.itemsize) // 8 * 8)
    step = min(step, rows)
    devices = [fmt.sharding for fmt in formats]
    in_flight: collections.deque = collections.deque()
    with _no_persistent_compile_cache():
        bufs = [_row_major_zeros(fmt, (rows, d), dtype.name)() for fmt in formats]
        for lo in range(0, rows, step):
            lo = min(lo, rows - step)
            pieces = jax.device_put([b[lo : lo + step] for b in blocks], devices)
            written = []
            for i, fmt in enumerate(formats):
                bufs[i], done = _row_major_write(fmt)(bufs[i], pieces[i], np.int32(lo))
                written.append(done)
            del pieces
            in_flight.append(written)
            if len(in_flight) > 1:
                with telemetry.device_wait("row_major_piece"):
                    jax.block_until_ready(in_flight.popleft())
    return bufs


def _place_blocks(blocks: Sequence[np.ndarray], devices: Sequence, x_layout: str) -> List[jax.Array]:
    """One device array per host block: today's one batched `device_put`,
    unless `x_layout` asks for row-major and `row_major_format` says the
    devices would not give it (then `_place_row_major`, counted)."""
    if x_layout not in (X_DEFAULT, X_ROW_MAJOR):
        raise ValueError(f"x_layout={x_layout!r}: expected {X_DEFAULT!r} or {X_ROW_MAJOR!r}")
    if x_layout == X_ROW_MAJOR and blocks[0].shape[0]:
        formats = [row_major_format(b.shape, b.dtype, dev) for b, dev in zip(blocks, devices)]
        if all(f is not None for f in formats):
            telemetry.registry().inc("placement.row_major")
            return _place_row_major(blocks, formats)
    return jax.device_put(list(blocks), list(devices))


def place_on_device(x: np.ndarray, device, x_layout: str = X_DEFAULT) -> jax.Array:
    """One host block on one device, in the layout `x_layout` asks for
    (`_place_blocks`): a model's resident state outside a mesh."""
    return _place_blocks([np.ascontiguousarray(x)], [device], x_layout)[0]


def shard_row_slices(x: np.ndarray, n_dev: int) -> Tuple[list, int]:
    """Cut a host row block into `n_dev` equal per-shard pieces.

    Returns ``(pieces, n_pad)``: `n_dev` arrays of ``n_pad // n_dev`` rows
    each, where all but the tail shard are ZERO-COPY views of `x` — only the
    shard that crosses the valid-row boundary is padded (one small copy)
    instead of re-materializing the whole padded block the way
    ``pad_rows`` + monolithic placement did (~1x dataset bytes saved).
    """
    n = x.shape[0]
    n_pad = -(-n // n_dev) * n_dev  # 0 rows stay 0 rows (pad_rows parity)
    per = n_pad // n_dev
    pieces = []
    for i in range(n_dev):
        lo = i * per
        hi = max(lo, min(lo + per, n))
        piece = x[lo:hi]
        if piece.shape[0] < per:  # tail shard (or pure padding when n < n_pad)
            piece = np.pad(piece, [(0, per - piece.shape[0])] + [(0, 0)] * (x.ndim - 1))
        pieces.append(piece)
    return pieces, n_pad


def place_row_shards(mesh: Mesh, x: np.ndarray, x_layout: str = X_DEFAULT) -> jax.Array:
    """Place a host row block on the mesh shard-by-shard, each shard in the
    device's default layout or, for `x_layout=X_ROW_MAJOR`, row-major
    (`_place_blocks`).

    The old path padded the whole block (full host copy) and handed one
    monolithic buffer to `jax.device_put`, staging a third copy and
    serializing the H2D transfer. Here each device's row range is sliced as a
    view, only the tail shard is padded, and ONE batched `device_put` call
    dispatches all per-device transfers back-to-back so they overlap; the
    global array is assembled with `jax.make_array_from_single_device_arrays`
    — numerically identical to the monolithic placement (equality asserted in
    tests/test_ingest.py) at ~1/3 the peak host footprint.
    """
    devices = list(mesh.devices.flatten())
    pieces, n_pad = shard_row_slices(x, len(devices))
    if telemetry.enabled():
        reg = telemetry.registry()
        reg.inc("placement.device_put_calls")
        reg.inc("placement.shards", len(pieces))
        reg.inc("placement.bytes", sum(p.nbytes for p in pieces))
        reg.inc("placement.rows_padded", n_pad - x.shape[0])
    shards = _place_blocks(pieces, devices, x_layout)
    return jax.make_array_from_single_device_arrays(
        (n_pad,) + x.shape[1:], row_sharding(mesh, x.ndim), shards
    )


def place_rows(
    mesh: Mesh,
    x: np.ndarray,
    *,
    local_rows_target: Optional[int] = None,
    x_layout: str = X_DEFAULT,
) -> jax.Array:
    """X-only `make_global_rows`: identical row layout/padding (and the same
    `x_layout`), no weight vector built or placed — for callers laying out
    SEVERAL per-row arrays that share one weight vector (ELL
    values+indices+labels)."""
    x = np.ascontiguousarray(x)
    if jax.process_count() > 1:  # multi-process SPMD: x is this rank's block
        from jax.experimental import multihost_utils

        n_local_dev = jax.local_device_count()
        if local_rows_target is None:
            local_rows_target = -(-x.shape[0] // n_local_dev) * n_local_dev
        if local_rows_target < x.shape[0] or local_rows_target % n_local_dev:
            raise ValueError(
                f"local_rows_target={local_rows_target} must cover the {x.shape[0]} local "
                f"rows and divide by the {n_local_dev} local devices"
            )
        xp = np.pad(
            x, [(0, local_rows_target - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
        )
        if telemetry.enabled():
            reg = telemetry.registry()
            # this branch performs no jax.device_put of its own — the
            # multihost assembly owns the transfer, counted separately
            reg.inc("placement.global_assembly_calls")
            reg.inc("placement.bytes", xp.nbytes)
            reg.inc("placement.rows_padded", local_rows_target - x.shape[0])
        if x_layout != X_DEFAULT:
            return _place_local_rows(mesh, xp, x_layout)
        return multihost_utils.host_local_array_to_global_array(xp, mesh, P(ROWS_AXIS))
    if mesh.devices.size == 1:
        if telemetry.enabled():
            reg = telemetry.registry()
            reg.inc("placement.device_put_calls")
            reg.inc("placement.bytes", x.nbytes)
        # a plain device, not a 1-device NamedSharding: see make_global_rows
        return _place_blocks([x], [mesh.devices.flatten()[0]], x_layout)[0]
    return place_row_shards(mesh, x, x_layout)


def _place_local_rows(mesh: Mesh, xp: np.ndarray, x_layout: str) -> jax.Array:
    """`host_local_array_to_global_array(xp, mesh, P(ROWS_AXIS))` with the
    local shards placed by `_place_blocks`: this process's padded block goes
    to its own devices in the order the local mesh's row sharding gives, and
    the global array is assembled from every process's shards."""
    local_mesh = mesh.local_mesh
    local_index = NamedSharding(local_mesh, P(ROWS_AXIS)).devices_indices_map(xp.shape)
    devices = list(local_index)
    shards = _place_blocks([xp[local_index[dev]] for dev in devices], devices, x_layout)
    groups = mesh.shape[ROWS_AXIS] // local_mesh.shape[ROWS_AXIS]
    return jax.make_array_from_single_device_arrays(
        (xp.shape[0] * groups,) + xp.shape[1:], NamedSharding(mesh, P(ROWS_AXIS)), shards
    )


def stream_place_blocks(mesh: Mesh, host_blocks):
    """Double-buffered host->HBM chunk pipeline — the out-of-core fits'
    transfer engine (docs/robustness.md "Memory safety").

    `host_blocks` is an iterator of dicts of SAME-row-count host arrays (one
    streaming chunk: features + labels + weights + ...); each is placed
    row-sharded over `mesh` via `place_rows` (numpy's zero tail-padding makes
    padded weight rows weightless for free) and yielded as the same-keyed
    dict of device arrays. The pipeline dispatches chunk N+1's `device_put`
    BEFORE yielding chunk N, so the H2D transfer of the next chunk is in
    flight while the caller computes on the current one — two chunks resident
    at once, never the dataset.

    Telemetry (per drained pass): `ingest.stream_chunks`/`ingest.stream_rows`
    counters, a `device.{peak_,}bytes_in_use` watermark sample at every chunk
    boundary (so out-of-core peaks are visible, not just post-layout/post-
    solve ones), and the `ingest.overlap_fraction` gauge — the fraction of
    prefetched chunks whose transfer had COMPLETED by the time the caller
    finished computing on the previous chunk, probed via `Array.is_ready`
    where the backend exposes it (dispatch-order fallback otherwise: the
    transfer was at least in flight during the compute). (n-1)/n when fully
    pipelined; the acceptance assertion is simply > 0 on any multi-chunk
    fit, and ~0 there means the transfer is slower than the compute — a
    broken (serialized) pipeline, or chunks too small to amortize."""
    it = iter(host_blocks)

    def _place(d: dict) -> dict:
        return {k: place_rows(mesh, np.ascontiguousarray(v)) for k, v in d.items()}

    def _transfer_done(placed: dict) -> bool:
        try:
            return all(bool(a.is_ready()) for a in placed.values())
        except Exception:
            return True  # no is_ready on this backend: dispatch-order fallback

    try:
        cur_host = next(it)
    except StopIteration:
        return
    total = overlapped = 0
    rows = 0
    cur = _place(cur_host)
    rows += next(iter(cur_host.values())).shape[0]
    for nxt_host in it:
        # dispatch N+1 BEFORE handing N to the caller: the generator resumes
        # after the yield only once the caller finished computing on chunk N,
        # so the prefetched transfer runs concurrently with that compute
        nxt = _place(nxt_host)
        rows += next(iter(nxt_host.values())).shape[0]
        total += 1
        telemetry.record_device_memory()  # out-of-core watermark sample
        yield cur
        if _transfer_done(nxt):  # finished while the caller computed
            overlapped += 1
        cur = nxt
    total += 1
    telemetry.record_device_memory()
    yield cur
    if telemetry.enabled():
        reg = telemetry.registry()
        reg.inc("ingest.stream_chunks", total)
        reg.inc("ingest.stream_rows", rows)
        reg.gauge("ingest.overlap_fraction", overlapped / total)


def make_global_rows(
    mesh: Mesh,
    x: np.ndarray,
    *,
    weights: Optional[np.ndarray] = None,
    local_rows_target: Optional[int] = None,
    x_layout: str = X_DEFAULT,
) -> Tuple[jax.Array, jax.Array, int]:
    """Place a host row-block on the mesh as a row-sharded global array.

    What layout X has on the device is the caller's to say: `X_DEFAULT`
    leaves it to the device (a TPU stores a float32 [n, 3000] block with n a
    multiple of 128 column-major), `X_ROW_MAJOR` — asked by an estimator
    whose solver feeds row tiles of X to the distance kernels
    (`_TpuCaller._x_layout`; KMeans) — has every device's rows contiguous,
    the layout those kernels read, so no tile is turned before them. Off the
    TPU, and where row-major is the device's default anyway, both are the
    same call (`row_major_format`). `w` always takes the default.

    Pads rows and returns ``(X, w, n_valid)`` where `w` is a row-weight vector
    with zeros on padding rows (and the user's sample weights elsewhere).
    Solvers MUST use `w` for any per-row reduction so padding never
    contaminates results.

    Single-controller path: the host block is cut into per-device row ranges
    (zero-copy views, tail shard padded) and placed shard-by-shard
    (`place_row_shards`) — transfers dispatch back-to-back and no whole-block
    padded copy is ever made. Under multi-process SPMD, `x` is this PROCESS's
    local block; every process
    pads its block to `local_rows_target` rows (the rendezvous-agreed common
    local size — processes hold ragged row counts, SPMD XLA wants equal
    shards) and the global array is assembled from the per-process shards.
    """
    n_dev = mesh.devices.size
    x = np.ascontiguousarray(x)
    if weights is None:
        weights = np.ones(x.shape[0], dtype=x.dtype if x.dtype.kind == "f" else np.float32)
    weights = np.asarray(weights)

    if jax.process_count() == 1:
        n_valid = x.shape[0]
        w_host = np.asarray(weights, dtype=x.dtype if x.dtype.kind == "f" else np.float32)
        if n_dev == 1:
            # plain placement: a committed 1-device NamedSharding makes Shardy
            # insert a full input-resharding copy of X in consumer programs
            # (measured 11 GiB at the 1M x 3k benchmark shape)
            dev = mesh.devices.flatten()[0]
            if telemetry.enabled():
                reg = telemetry.registry()
                reg.inc("placement.device_put_calls", 2)
                reg.inc("placement.bytes", x.nbytes + w_host.nbytes)
            X = _place_blocks([x], [dev], x_layout)[0]
            w = jax.device_put(w_host, dev)
        else:
            X = place_row_shards(mesh, x, x_layout)
            w = place_row_shards(mesh, w_host)
    else:  # multi-process: x is this process's local block
        n_local_dev = jax.local_device_count()
        if local_rows_target is None:
            local_rows_target = -(-x.shape[0] // n_local_dev) * n_local_dev
        n_valid = x.shape[0]
        X = place_rows(mesh, x, local_rows_target=local_rows_target, x_layout=x_layout)
        w = place_rows(
            mesh,
            np.asarray(weights, dtype=x.dtype if x.dtype.kind == "f" else np.float32),
            local_rows_target=local_rows_target,
        )
    return X, w, n_valid
