#
# The ONE tiled distance / argmin / top-k core shared by the whole neighbor
# family (docs/performance.md "Tiled distance core").
#
# Every neighbor-shaped estimator reduces to the same inner loop: a
# `[rows_tile, d] x [k_side, d]` distance contraction followed by a running
# reduction (argmin for KMeans assignment, top-k for kNN/UMAP/CAGRA, an
# eps-threshold count for DBSCAN). Before this module each of
# kmeans/knn/dbscan/umap/cagra hand-rolled that loop — and the hand-rolled
# KMeans form fell ~2.2x going from 400k to 1M rows (pre-ledger rounds r01
# ~226k -> r03 ~100k rows/sec/chip at k=1000): at k=1000 the un-k-tiled `[batch, k]`
# distance block plus its one-hot twin stop fitting close to the compute and
# the MXU starves. This module is the single owner of that loop:
#
#   * a Pallas-TPU kernel path: the distance block is computed in
#     `[block_rows, d] x [block_k, d]` VMEM tiles (the grid pipeline
#     double-buffers the HBM->VMEM tile fetches), with the argmin merged
#     IN-KERNEL across k tiles — a `[rows_tile, k]` matrix never exists in
#     HBM, which is exactly the r01->r03 cliff;
#   * a bit-compatible pure-jnp form: the same formulas as one XLA program
#     (what CPU runs); parity between the two is pinned by
#     tests/test_distance.py (rtol 1e-9 f64, exact assignments f32) across
#     tile boundaries, ragged tails, weights, and the `fast` precision mode;
#   * the backend probe (`kernel_mode`) that picks between them once per
#     process from the FRAMEWORK's devices: on a TPU the kernels are the
#     contract — a tiny end-to-end self-test runs once and a compile or
#     parity failure RAISES with the compiler's message, it never demotes to
#     jnp; `SRML_DISTANCE_KERNEL` overrides (`pallas` | `jnp` | `interpret`
#     — the interpret form runs the REAL kernels through the Pallas
#     interpreter, which is how CPU CI exercises kernel code paths at all).
#
# The ci/analysis `raw-distance` rule forbids re-growing private copies:
# `jnp.argmin` / `lax.top_k` over a locally-built `x @ c.T`-shaped operand
# anywhere in the framework outside this file is a finding
# (`# distance-ok: <reason>` waives a deliberate exception).
#
# `distance.*` counters (docs/observability.md) count PROGRAM TRACES, not
# executions — they increment at trace time by design, so "a KMeans fit
# compiles ONE distance program across its iterations" is a testable
# invariant instead of folklore.
#
from __future__ import annotations

import math
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import telemetry

# Outer row-tile default when config["distance_tile_rows"] is missing or
# invalid (the config default matches this).
_DEFAULT_TILE_ROWS = 4096

# Scoped-VMEM limit the kernels DECLARE to Mosaic (`pltpu.CompilerParams
# (vmem_limit_bytes=...)`) and the block planner fits, keyed by the device's
# `device_kind`. Mosaic's default scoped limit on a v5e is 16 MiB, which the
# double-buffered f32 [512, d] blocks of the protocol width (d=3000) exceed;
# 64 MiB is half of the v5e core's 128 MiB VMEM, leaving the rest to XLA's
# own fusions. A kind that is not listed is an error, never the v5e value:
# a part with less VMEM would compile blocks it cannot hold. "cpu" is the
# Pallas INTERPRETER (CI parity runs): no VMEM exists there, the entry only
# makes CI plan the blocks the v5e would.
_VMEM_LIMIT_BYTES = {
    "TPU v5 lite": 64 << 20,
    "cpu": 64 << 20,
}

_LANES = 128  # the last block dim occupies whole 128-lane tiles in VMEM

_MODE: Optional[str] = None  # kernel_mode() cache: "pallas" | "interpret" | "jnp"


# ----------------------------------------------------------- tile planning --


def tile_rows() -> int:
    """Outer row-tile size shared by every consumer's query/row scan —
    `config["distance_tile_rows"]` (docs/configuration.md)."""
    from ..core import config

    try:
        v = int(config.get("distance_tile_rows", _DEFAULT_TILE_ROWS))
    except (TypeError, ValueError):
        return _DEFAULT_TILE_ROWS
    return v if v > 0 else _DEFAULT_TILE_ROWS


def vmem_limit_bytes() -> int:
    """The scoped-VMEM limit for the framework's device kind (see
    `_VMEM_LIMIT_BYTES`); raises for a kind nobody has sized."""
    from ..parallel.mesh import default_devices

    kind = default_devices()[0].device_kind
    try:
        return _VMEM_LIMIT_BYTES[kind]
    except KeyError:
        raise RuntimeError(
            f"no scoped-VMEM limit is recorded for device kind {kind!r}; add "
            "it to ops/distance.py _VMEM_LIMIT_BYTES (known: "
            f"{sorted(_VMEM_LIMIT_BYTES)})"
        ) from None


def block_vmem_bytes(br: int, bk: int, d: int, dtype, fast: bool) -> int:
    """Upper bound on the VMEM one grid step of the widest kernel holds at
    blocks (br, bk) — what the planner fits under `vmem_limit_bytes()`:

      * the two [block, d] operand/result blocks at their STORED dtype (the
        bf16 cast of the fast path happens after the load), each
        double-buffered by the grid pipeline;
      * what the MXU contraction peels off them: the fast path's bf16 copies
        of both blocks, or — `_kdot` asks Mosaic for an fp32 contraction —
        the hi/mid/lo bf16 splits of the [br, d] row block and the f32
        residuals they are taken from, 17 bytes per element as measured;
      * `_pl_accumulate`'s [bk, d] f32 dot result before it lands in the
        output block;
      * the [br, bk] f32 distance / one-hot block, its bf16 or int32 twin,
        and a third [br, bk] block (the unfused top-k's output, gone since
        the fused kernel has a budget of its own: kept, so that no plan
        moves);
      * the lane-sparse (n, 1) column blocks (weights, assignments, minima,
        counts): one 128-lane tile per 8 rows, double-buffered.

    Calibrated against Mosaic itself: for each kernel, blocks from 128 to
    512 and d in {3072, 7168}, the smallest `vmem_limit_bytes` an AOT
    compile for a v5e accepts stays below this bound (e.g. f32 argmin at
    512x256, d=7168 needs 99 MiB, 111 MiB accounted here; fast accumulate at
    512x512, d=3072 needs 32 MiB, 41 MiB accounted)."""
    item = jnp.dtype(dtype).itemsize
    dp = -(-d // _LANES) * _LANES
    blocks = 2 * (br + bk) * dp * item
    peeled = (br + bk) * dp * 2 if fast else br * dp * 17
    dot_out = bk * dp * 4
    tile = 3 * br * bk * 4
    columns = 2 * (3 * br + bk) * _LANES * 4
    return blocks + peeled + dot_out + tile + columns


def plan_blocks(
    n_rows: int, k_side: int, d: int, dtype=jnp.float32, fast: bool = False
) -> Optional[Tuple[int, int]]:
    """Kernel-internal (block_rows, block_k): the largest blocks whose
    `block_vmem_bytes` fit the declared scoped-VMEM limit. Returns None when
    even the floor blocks don't fit (enormous d) — callers take the jnp
    form then."""
    limit = vmem_limit_bytes()
    br, bk = 512, 512
    while block_vmem_bytes(br, bk, d, dtype, fast) > limit and (br > 8 or bk > 128):
        if bk > 128:
            bk //= 2
        elif br > 8:
            br //= 2
    if block_vmem_bytes(br, bk, d, dtype, fast) > limit:
        return None
    return min(br, max(1, n_rows)), min(bk, max(1, k_side))


# The fused top-k kernel (`_pl_knn_topk`) keeps each query row's best list in
# one 128-lane row: k up to this takes the kernel, a larger k the jnp form.
TOPK_LANES = 128
_TOPK_CHUNK = 8  # rows the fused top-k merges at once: one float32 sublane tile
_TOPK_BLOCKS = (512, 4096)  # (query rows, items) a grid step, before VMEM shrinks them
_TOPK_FLAG_UNROLL = 8  # chunks a step of the branch-free flag loop, whose vector-to-scalar waits overlap


def topk_vmem_bytes(br: int, bk: int, d: int, dtype, fast: bool) -> int:
    """Upper bound on the VMEM one grid step of `_pl_knn_topk` holds at
    blocks (br query rows, bk items), its own budget beside
    `block_vmem_bytes`: the [br, d] query block and the [bk, d] item and
    tail blocks at their stored dtype, double-buffered; what the MXU
    contraction peels off both operands (bf16 copies on the fast path, the
    hi/mid/lo splits and residuals, 17 bytes per element, otherwise); the
    [br, bk] contraction result and the scratch it lands in; the [1, bk]
    norms row (one 8-sublane tile); the two [br, TOPK_LANES] best lists
    (distances and int32 ids), double-buffered; the [br, 1] int32 column
    of rows that merge (one 128-lane tile wide); and the [8, 128] int32
    count of merged chunks, double-buffered. The 8-row merge chunk lives in
    vector registers."""
    item = jnp.dtype(dtype).itemsize
    dp = -(-d // _LANES) * _LANES
    blocks = 2 * (br + 2 * bk) * dp * item
    peeled = (br + bk) * dp * (2 if fast else 17)
    dots = 2 * br * bk * item
    norms = 2 * 8 * bk * item
    lists = 2 * br * TOPK_LANES * (item + 4)
    merging = br * _LANES * 4
    count = 2 * 8 * _LANES * 4
    return blocks + peeled + dots + norms + lists + merging + count


def plan_topk(
    n_queries: int, n_items: int, d: int, dtype=jnp.float32, fast: bool = False
) -> Optional[Tuple[int, int]]:
    """(block_rows, block_items) of the fused top-k kernel: `_TOPK_BLOCKS`
    shrunk (items first, to 512, then rows, then items again) until
    `topk_vmem_bytes` fits the declared scoped-VMEM limit, then cut to the
    call (rows to a multiple of 8, items to one of 128). None where even the
    floor blocks do not fit."""
    limit = vmem_limit_bytes()
    br, bk = _TOPK_BLOCKS
    while topk_vmem_bytes(br, bk, d, dtype, fast) > limit and (br > 8 or bk > 128):
        if bk > 512:
            bk //= 2
        elif br > 8:
            br //= 2
        else:
            bk //= 2
    if topk_vmem_bytes(br, bk, d, dtype, fast) > limit:
        return None
    up = lambda v, m: -(-max(1, v) // m) * m
    return min(br, up(n_queries, 8)), min(bk, up(n_items, 128))


def topk_chunk_steps(n_queries: int, n_items: int, block_rows: int, block_items: int) -> int:
    """The (8-row chunk, item block) pairs of one `_pl_knn_topk` call at
    these blocks, each of which may merge: every chunk of the padded query
    rows against every item block, the ragged last one included."""
    chunks = -(-n_queries // block_rows) * (block_rows // math.gcd(block_rows, _TOPK_CHUNK))
    return chunks * -(-n_items // block_items)


def topk_plan(
    n_queries: int, n_items: int, d: int, kk: int, dtype, fast: bool
) -> Optional[Tuple[int, int]]:
    """The blocks a top-k dispatch of this shape takes in this process —
    `plan_topk`'s answer — or None where it takes the jnp form: no kernel
    mode, more than `TOPK_LANES` neighbours a row, no plan fits, or float64
    on the compiled path (as `block_plan`). The first call resolves
    `kernel_mode()`: make it outside a trace."""
    if not _use_kernel() or kk > TOPK_LANES or (not _interpret() and jnp.dtype(dtype).itemsize > 4):
        return None
    return plan_topk(n_queries, n_items, d, dtype, fast)


def kernel_name(kernel: str, fast: bool) -> str:
    """The name a kernel carries onto the device: `srml_<kernel>_<mode>`,
    mode `bf16` (one-pass `fast` contraction) or `f32`. XLA's TPU compiler
    names a Mosaic custom call after `pallas_call`'s `name=` (without one,
    after the enclosing jit plus a uniquifier that moves with the program),
    so a device trace tells argmin from accumulate, and the in-loop bf16
    calls from the float32 final pass and predict, by this prefix: on one
    chip, under `shard_map` and in the predict program alike. A contract
    (docs/observability.md "Kernel names"): the benchmark's per-kernel
    metrics match on it."""
    return f"srml_{kernel}_{'bf16' if fast else 'f32'}"


def block_plan(
    n_rows: int, k_side: int, d: int, dtype, fast: bool
) -> Optional[Tuple[int, int]]:
    """The (block_rows, block_k) a kernel dispatch of this tile shape takes
    in this process — `plan_blocks`' answer — or None where it takes the jnp
    form: no kernel mode, no plan fits (enormous d), or float64 rows on the
    compiled path — Mosaic holds no f64 (and lowers no int64 index under the
    x64 mode f64 fits run in); XLA emulates f64 on the TPU, the interpreter
    runs it as is. The kernel entry points below ask here, and so does a
    caller that reports the plan (the `fit/solve/loop` span). The first call
    resolves `kernel_mode()`: make it outside a trace."""
    if not _use_kernel() or (not _interpret() and jnp.dtype(dtype).itemsize > 4):
        return None
    return plan_blocks(n_rows, k_side, d, dtype, fast)


def _call_params(interpret: bool) -> dict:
    """The `pallas_call` keywords every kernel here shares: interpreted, or
    compiled with the scoped-VMEM limit the planner budgeted against stated
    to Mosaic."""
    if interpret:
        return {"interpret": True}
    from jax.experimental.pallas import tpu as pltpu

    return {
        "compiler_params": pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes())
    }


def _grid_call(kernel, *, grid, in_specs, out_specs, out_shape, name, interpret,
               block_offset=()):
    """The `pallas_call` of one kernel of this file. With `block_offset` (a
    1-tuple holding an int32 [1] array, traced: `_tile_of`) the call takes it
    first as a PREFETCHED SCALAR: every index map receives its ref as a
    trailing argument (`_row_block` adds it to the row block index), the
    kernel body does not see it. That is how a kernel reads its row blocks
    out of an array larger than the tile it works on, where the array lies.
    Without it: the plain call."""
    from jax.experimental import pallas as pl

    if not block_offset:
        return pl.pallas_call(
            kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
            out_shape=out_shape, name=name, **_call_params(interpret),
        )
    from jax.experimental.pallas import tpu as pltpu

    call = pl.pallas_call(
        lambda _off_ref, *refs: kernel(*refs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs, out_specs=out_specs,
        ),
        out_shape=out_shape, name=name, **_call_params(interpret),
    )
    return lambda *operands: call(*block_offset, *operands)


def _row_block(r, off):
    """Row block index of X for grid row `r`: `off` is what the index map got
    after the grid indices, () or the prefetched block offset's ref."""
    return (off[0][0] + r if off else r, 0)


def _tile_of(x, tile, block_rows):
    """(rows, block offset) of a kernel's work: all of `x` and (), or, for
    `tile = (start, rows)`, `rows` and the 1-tuple `_grid_call` takes: row
    `start`, a multiple of `block_rows`, counted in blocks."""
    if tile is None:
        return x.shape[0], ()
    start, rows = tile
    return rows, ((jnp.asarray(start, jnp.int32) // block_rows).reshape(1),)


def shard_map_check_vma() -> bool:
    """`check_vma` for a `shard_map` whose body runs these kernels: on —
    the compiled `pallas_call`s state how their outputs vary (`_vary_alike`)
    — except while the kernels are INTERPRETED. The interpreter evaluates
    the kernel body under the caller's shard_map trace, and in jax 0.9.0 the
    vma check rejects its first constant ("Primitive mul requires varying
    manual axes to match"); the TPU-specific interpreter passes the check
    but deadlocks on the 8-device CPU mesh."""
    return not _interpret()


def _vary_alike(*operands):
    """Under `shard_map` (check_vma) a `pallas_call` needs its operands typed
    as varying over the SAME mesh axes — the kernel body contracts a
    per-shard row block with replicated centers — and its out_shape structs
    must state how the outputs vary, or the trace is rejected. Returns
    (vma, operands) with every operand cast to the union; outside a
    shard_map the set is empty and nothing happens."""
    vma = frozenset().union(*(jax.typeof(o).vma for o in operands))
    cast = tuple(
        jax.lax.pcast(o, tuple(vma - jax.typeof(o).vma), to="varying")
        if vma - jax.typeof(o).vma else o
        for o in operands
    )
    return vma, cast


def _vary_like(ref, *values) -> tuple:
    """`values` typed as varying like `ref`: a loop carry initialised from
    constants must vary like the per-shard data folded into it."""
    return _vary_alike(ref, *values)[1][1:]


# ---------------------------------------------------------- backend probe ---


def kernel_mode() -> str:
    """Which inner-loop implementation this process runs: "pallas" (TPU
    devices, kernels verified by a tiny self-test), "interpret" (the real
    kernels through the Pallas interpreter — CI parity testing), or "jnp"
    (the bit-compatible XLA form; what CPU runs). Resolved once;
    `SRML_DISTANCE_KERNEL` overrides. On a TPU a failing self-test raises —
    it is never answered with "jnp"."""
    global _MODE
    if _MODE is None:
        _MODE = _probe()
        if telemetry.enabled():  # traced-ok: one-shot probe-result gauge — resolves once per process, trace-time reads return the cached string
            telemetry.registry().gauge(  # traced-ok: same one-shot probe gauge (see line above)
                "distance.kernel_pallas", 1.0 if _MODE != "jnp" else 0.0
            )
    return _MODE


def _probe() -> str:
    env = os.environ.get("SRML_DISTANCE_KERNEL", "").strip().lower()
    if env in ("jnp", "fallback", "off"):
        return "jnp"
    if env == "interpret":
        return "interpret"
    if env == "pallas":
        # explicit override: no self-test — an operator debugging a kernel
        # failure needs it to surface at the kernel call
        return "pallas"
    from ..parallel.mesh import default_devices

    dev = default_devices()[0]
    if dev.platform != "tpu":
        return "jnp"
    _self_test(dev)
    return "pallas"


def _self_test(dev) -> None:
    """Compile and run `_pl_argmin` once on `dev` against the jnp formula.
    On a TPU the kernel path is the contract: a Mosaic compile error or a
    wrong answer raises here, with the compiler's message, instead of
    quietly running every neighbor-family fit as a different program."""
    import numpy as np

    x = jax.device_put(np.arange(64, dtype=np.float32).reshape(8, 8) / 64.0, dev)
    c = jax.device_put(np.arange(32, dtype=np.float32).reshape(4, 8) / 32.0, dev)
    try:
        mind, best = _pl_argmin(x, c, _c_sq(c), block_rows=8, block_k=4,
                                fast=False, interpret=False)
        mind, best = np.asarray(mind), np.asarray(best)
    except Exception as e:
        raise RuntimeError(
            f"the Pallas distance kernels do not compile/run on {dev.device_kind!r}: "
            f"{type(e).__name__}: {e}"
        ) from e
    ref_d2 = np.asarray(_c_sq(c)[None, :] - 2.0 * (x @ c.T))
    if not (
        np.allclose(mind, ref_d2.min(axis=1), rtol=1e-5)
        and np.array_equal(best, ref_d2.argmin(axis=1))
    ):
        raise RuntimeError(
            f"the Pallas argmin kernel disagrees with the jnp formula on "
            f"{dev.device_kind!r}: min {mind.tolist()} vs {ref_d2.min(axis=1).tolist()}, "
            f"argmin {best.tolist()} vs {ref_d2.argmin(axis=1).tolist()}"
        )


def _use_kernel() -> bool:
    return kernel_mode() != "jnp"


def _interpret() -> bool:
    return kernel_mode() == "interpret"


# --------------------------------------------------------------- helpers ----


def row_sq(x: jax.Array) -> jax.Array:
    return jnp.sum(x * x, axis=1)


def _c_sq(c: jax.Array) -> jax.Array:
    return jnp.sum(c * c, axis=1)


def _mm(a: jax.Array, b: jax.Array, fast: bool) -> jax.Array:
    """Matmul at the neighbor-family loop precision. `fast` = one-pass bf16
    on the MXU with f32 accumulation (explicit casts, so CPU tests see the
    same rounding). Measured at the protocol shape (1M x 3k, k=1000, v5e):
    in-loop bf16 drops 331 -> 208 ms/iter while the TRUE inertia (recomputed
    at 3-pass-bf16 "f32" precision with the final centers) agrees to 7e-6
    relative — assignment flips only for near-tied rows, which contribute
    equally either way."""
    if fast:
        return jax.lax.dot(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32,
        ).astype(a.dtype)
    return a @ b


def _note(name: str) -> None:
    """Trace-time program counter (see module docstring): one tick per
    compiled distance program, NOT per execution."""
    if telemetry.enabled():  # traced-ok: distance.* counters count program TRACES by design — one tick per compile is the invariant tests/test_distance.py pins
        telemetry.registry().inc(name)  # traced-ok: see line above (deliberate trace-time tick, docs/observability.md "Tiled distance core")


# ---------------------------------------------------------- Pallas kernels --
#
# Kernels never tile the feature axis: blocks are [block_rows, d] and
# [block_k, d] with full-depth dots, so each distance entry is ONE dot
# reduction — bitwise identical to the fallback's single big matmul slice-
# for-slice (the parity suite leans on this). The block planner refuses
# (-> jnp form) when full-depth blocks cannot fit VMEM.


def _kdot(a: jax.Array, b: jax.Array, fast: bool) -> jax.Array:
    """The in-kernel MXU contraction at a STATED precision. Mosaic lowers
    only DEFAULT and HIGHEST, so the calling solver's ambient
    `jax.default_matmul_precision` (KMeans runs under the
    "BF16_BF16_F32_X3" preset, kNN under "float32") must not reach the
    kernel's dot: `fast` is one bf16 pass with f32 accumulation, otherwise
    the full-f32 contraction the jnp form gets on CPU."""
    if fast:
        return jnp.dot(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32,
        ).astype(a.dtype)
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def _kdot_nt(a: jax.Array, b: jax.Array, fast: bool) -> jax.Array:
    """`_kdot(a, b.T, fast)` as one contraction over both operands' last
    axis, so that Mosaic feeds the [bk, d] block to the MXU as it lies
    instead of turning it first."""
    dims = (((1,), (1,)), ((), ()))
    if fast:
        return jax.lax.dot_general(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), dims,
            precision=jax.lax.Precision.DEFAULT, preferred_element_type=jnp.float32,
        ).astype(a.dtype)
    return jax.lax.dot_general(a, b, dims, precision=jax.lax.Precision.HIGHEST)


def _pl_argmin(
    x: jax.Array,  # [B, d] row tile, or with `tile` the whole [n, d] block it lies in
    c_pad: jax.Array,  # [kp, d] centers, padded to a block_k multiple
    c_sq_pad: jax.Array,  # [kp] (+inf on padding rows)
    *,
    block_rows: int,
    block_k: int,
    fast: bool,
    interpret: bool,
    tile: Optional[Tuple[jax.Array, int]] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused distance + running argmin: returns (min d2 [B] WITHOUT the
    ||x||^2 term, argmin index [B] int32). Grid = (row blocks, k blocks)
    with the k axis innermost: each step computes one [br, bk] distance
    block in VMEM and merges it into the carried per-row minimum — the full
    [B, k] matrix never exists.

    `tile = (start, rows)`: the B = `rows` rows of `x` from the (traced) row
    `start` on, both multiples of `block_rows`, fetched block by block from
    where they lie in `x` (`_grid_call`): the same grid, blocks and body as
    on a slice of those rows, and no buffer of the tile's shape."""
    from jax.experimental import pallas as pl

    d = x.shape[1]
    B, off = _tile_of(x, tile, block_rows)
    kp = c_pad.shape[0]
    n_rb = B // block_rows
    n_kb = kp // block_k
    dtype = x.dtype
    vma, (x, c_pad, c_sq_pad, *off) = _vary_alike(x, c_pad, c_sq_pad, *off)

    def kernel(x_ref, c_ref, csq_ref, mind_ref, best_ref):
        kb = pl.program_id(1)
        d2 = csq_ref[...] - 2.0 * _kdot(x_ref[...], c_ref[...].T, fast)  # [br, bk]
        blk_min = jnp.min(d2, axis=1, keepdims=True)
        # index dtype pinned: under x64 `jnp.argmin` asks for int64 indices,
        # which Mosaic does not lower
        blk_arg = jax.lax.argmin(d2, 1, jnp.int32)[:, None] + kb * block_k

        @pl.when(kb == 0)
        def _init():
            mind_ref[...] = blk_min
            best_ref[...] = blk_arg

        @pl.when(kb > 0)
        def _merge():
            cur = mind_ref[...]
            take = blk_min < cur  # strict: first-k-block wins ties, like argmin
            mind_ref[...] = jnp.where(take, blk_min, cur)
            best_ref[...] = jnp.where(take, blk_arg, best_ref[...])

    mind, best = _grid_call(
        kernel,
        grid=(n_rb, n_kb),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda r, k, *off: _row_block(r, off)),
            pl.BlockSpec((block_k, d), lambda r, k, *_: (k, 0)),
            pl.BlockSpec((1, block_k), lambda r, k, *_: (0, k)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, 1), lambda r, k, *_: (r, 0)),
            pl.BlockSpec((block_rows, 1), lambda r, k, *_: (r, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1), dtype, vma=vma),
            jax.ShapeDtypeStruct((B, 1), jnp.int32, vma=vma),
        ],
        name=kernel_name("argmin", fast),
        interpret=interpret,
        block_offset=off,
    )(x, c_pad, c_sq_pad[None, :])
    return mind[:, 0], best[:, 0]


def _pl_accumulate(
    x: jax.Array,  # [B, d], or with `tile` the whole [n, d] block the B rows lie in
    w: jax.Array,  # [B]
    assign: jax.Array,  # [B] int32
    kp: int,  # padded center count (block_k multiple)
    *,
    block_rows: int,
    block_k: int,
    fast: bool,
    interpret: bool,
    tile: Optional[Tuple[jax.Array, int]] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Weighted one-hot accumulation: (sums [kp, d], counts [kp]). Grid =
    (k blocks, row blocks) with rows innermost: each step builds one
    [br, bk] one-hot block and accumulates its [bk, d] contribution — the
    full [B, k] one-hot matrix never exists. `tile`: as in `_pl_argmin`
    (`w` and `assign` are the tile's own B entries either way)."""
    from jax.experimental import pallas as pl

    d = x.shape[1]
    B, off = _tile_of(x, tile, block_rows)
    n_rb = B // block_rows
    n_kb = kp // block_k
    dtype = x.dtype
    vma, (x, w, assign, *off) = _vary_alike(x, w, assign, *off)

    def kernel(x_ref, w_ref, a_ref, sums_ref, counts_ref):
        kb = pl.program_id(0)
        rb = pl.program_id(1)
        xb = x_ref[...]
        wb = w_ref[...]  # [br, 1]
        ab = a_ref[...]  # [br, 1]
        ids = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        oh = jnp.where(ab == ids, wb, jnp.zeros((), dtype))  # [br, bk]
        contrib = _kdot(oh.T, xb, fast)

        @pl.when(rb == 0)
        def _init():
            sums_ref[...] = contrib
            counts_ref[...] = jnp.sum(oh, axis=0)[:, None]

        @pl.when(rb > 0)
        def _acc():
            sums_ref[...] += contrib
            counts_ref[...] += jnp.sum(oh, axis=0)[:, None]

    sums, counts = _grid_call(
        kernel,
        grid=(n_kb, n_rb),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda k, r, *off: _row_block(r, off)),
            pl.BlockSpec((block_rows, 1), lambda k, r, *_: (r, 0)),
            pl.BlockSpec((block_rows, 1), lambda k, r, *_: (r, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_k, d), lambda k, r, *_: (k, 0)),
            pl.BlockSpec((block_k, 1), lambda k, r, *_: (k, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, d), dtype, vma=vma),
            jax.ShapeDtypeStruct((kp, 1), dtype, vma=vma),
        ],
        name=kernel_name("accumulate", fast),
        interpret=interpret,
        block_offset=off,
    )(x, w[:, None], assign[:, None].astype(jnp.int32))
    return sums, counts[:, 0]


def _pl_knn_topk(
    qm2: jax.Array,  # [B, d] query rows times -2, B a multiple of block_rows
    items: Optional[jax.Array],  # [n, d]: its first n_full item blocks are read where they lie
    tail: Optional[jax.Array],  # [block_items, d]: the items after those blocks, zero-filled
    xsq: jax.Array,  # [1, n_blocks * block_items]: ||x||^2, +inf where no valid item is
    *,
    kk: int,
    block_rows: int,
    block_items: int,
    n_full: int,
    fast: bool,
    interpret: bool,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Fused distance + running top-kk: (d2 [B, TOPK_LANES] WITHOUT the
    ||q||^2 term, item index [B, TOPK_LANES] int32, merged chunks [B /
    block_rows * 8, 128] int32), each row's kk best in lanes [0, kk),
    UNSORTED; an unfilled slot holds +inf and a negative index. Grid =
    (query blocks, item blocks), items innermost: each step contracts one
    [block_rows, d] x [block_items, d] pair into a VMEM scratch and merges
    it into the per-row lists held in the output blocks. Only the lists
    (and the counts) ever reach HBM.

    The selection reads the contraction's value as it lands: for each row,
    the lowest and highest index of the block's items under its worst kept
    distance (the step's first), and the least such distance. A row with
    exactly one such item takes it there, every row at once, in place of
    its worst entry (the highest index among equal worst distances). Only
    where a row has two or more (one wait a step says whether any has) do
    the 8-row chunks that hold one merge: a branch-free pass (unrolled by
    `_TOPK_FLAG_UNROLL`: each chunk's flag is a vector-to-scalar move, a
    long serial wait unless several overlap) flags them in SMEM, then each
    flagged chunk's rows of two or more take the block's items out one at a
    time, lowest index first among equal distances, each replacing the
    row's worst entry while it is strictly better. Item blocks come in
    ascending index order and a row's takes in a block in ascending
    (distance, index) order, so a kept entry is never displaced by an equal
    distance of a later item: the kept set is `lax.top_k`'s. Each query
    block counts its merged chunks, in every element of its [8, 128] count
    block.

    The item blocks past `n_full` are `tail` (the ragged end, one block);
    `xsq` is +inf on padding, invalid and fill rows, so those are never
    taken. A distance that is not a number is never under a worst, and
    counts as +inf."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, d = qm2.shape
    bk, dtype = block_items, qm2.dtype
    n_blocks = n_full + (tail is not None)
    lanes, rows = TOPK_LANES, math.gcd(block_rows, _TOPK_CHUNK)
    operands = [o for o in (qm2, items if n_full else None, tail, xsq) if o is not None]
    vma, operands = _vary_alike(*operands)

    in_specs = [pl.BlockSpec((block_rows, d), lambda i, j: (i, 0))]
    if n_full:  # the tail's step asks for the last whole block again: no fetch
        in_specs.append(pl.BlockSpec((bk, d), lambda i, j: (jnp.minimum(j, n_full - 1), 0)))
    if tail is not None:
        in_specs.append(pl.BlockSpec((bk, d), lambda i, j: (0, 0)))
    in_specs.append(pl.BlockSpec((1, bk), lambda i, j: (0, j)))

    def kernel(*refs):
        q_ref, *x_refs, s_ref, bd_ref, bi_ref, n_ref, dot_ref, multi_ref, flags_ref, count_ref = refs
        j = pl.program_id(1)
        base = j * bk
        inf = jnp.asarray(jnp.inf, dtype)
        lowest = jnp.iinfo(jnp.int32).min

        @pl.when(j == 0)
        def _init():
            lane = jax.lax.broadcasted_iota(jnp.int32, (block_rows, lanes), 1)
            bd_ref[...] = jnp.where(lane < kk, jnp.inf, -jnp.inf).astype(dtype)
            bi_ref[...] = -1 - lane
            count_ref[0] = jnp.int32(0)

        worst0 = jnp.max(bd_ref[...], axis=1, keepdims=True)  # each row's worst, the step's first
        lane_k = jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)

        def contract(x_ref):
            dots = _kdot_nt(q_ref[...], x_ref[...], fast)
            dot_ref[...] = dots
            dc = s_ref[...] + dots
            under = dc < worst0
            first = jnp.min(jnp.where(under, lane_k, bk), axis=1, keepdims=True)
            last = jnp.max(jnp.where(under, lane_k, -1), axis=1, keepdims=True)
            least = jnp.min(jnp.where(under, dc, inf), axis=1, keepdims=True)
            # the lists read after the contraction, not held across it (v5e:
            # 80 ms a call of the Deep1B 10M shape's 1.08 s when held)
            bd0, bi0 = bd_ref[...], bi_ref[...]
            at_worst = bd0 == worst0
            slot = at_worst & (bi0 == jnp.max(jnp.where(at_worst, bi0, lowest), axis=1, keepdims=True))
            put = slot & (first == last)  # one item under the worst: taken here, the worst entry evicted
            bd_ref[...] = jnp.where(put, least, bd0)
            bi_ref[...] = jnp.where(put, base + first, bi0)
            multi_ref[...] = jnp.where(first < last, jnp.int32(1), jnp.int32(0))

        if len(x_refs) == 2:
            pl.when(j < n_full)(lambda: contract(x_refs[0]))
            pl.when(j == n_full)(lambda: contract(x_refs[1]))
        else:
            contract(x_refs[0])

        lane_b = jax.lax.broadcasted_iota(jnp.int32, (rows, bk), 1)

        def more(state):
            _, _, _, m, worst = state
            return jnp.max(jnp.where(m < worst, jnp.int32(1), jnp.int32(0))) > 0  # int32 under x64 too

        def take_one(state):
            dc, bd, bi, m, worst = state
            arg = jnp.min(jnp.where(dc == m, lane_b, bk), axis=1, keepdims=True)
            at_worst = bd == worst
            evict = jnp.max(jnp.where(at_worst, bi, lowest), axis=1, keepdims=True)
            put = at_worst & (bi == evict) & (m < worst)
            bd = jnp.where(put, m, bd)
            bi = jnp.where(put, base + arg, bi)
            dc = jnp.where(lane_b == arg, inf, dc)
            return dc, bd, bi, jnp.min(dc, axis=1, keepdims=True), jnp.max(bd, axis=1, keepdims=True)

        def flag(c, carry):
            # branch-free, so that the chunks' reductions overlap: whether a
            # row of the chunk has two or more items under its worst
            at = pl.ds(pl.multiple_of(c * rows, rows), rows)
            flags_ref[c] = jnp.max(multi_ref[at, :])
            return carry

        def merge(c, carry):
            @pl.when(flags_ref[c] > 0)
            def _flagged():
                at = pl.ds(pl.multiple_of(c * rows, rows), rows)
                dc = s_ref[...] + dot_ref[at, :]
                # only its rows of two or more: a row of one took it above; a
                # distance that is not a number is never taken
                dc = jnp.where((multi_ref[at, :] > 0) & (dc < inf), dc, inf)
                # through an op, as the loop's own values come: under shard_map
                # a value loaded from an output block is typed as varying, one
                # computed in the kernel is not, and the loop's carry must agree
                bd, bi = bd_ref[at, :] * 1, bi_ref[at, :] * 1
                state = (dc, bd, bi, jnp.min(dc, axis=1, keepdims=True), jnp.max(bd, axis=1, keepdims=True))
                # a flagged row takes: the first take waits on no scalar answer
                _, bd2, bi2, _, _ = jax.lax.while_loop(more, take_one, take_one(state))
                bd_ref[at, :] = bd2
                bi_ref[at, :] = bi2
                count_ref[0] += 1

            return carry

        chunks = block_rows // rows
        unroll = math.gcd(chunks, _TOPK_FLAG_UNROLL)  # Mosaic lowers no partial unroll: by hand

        def flags(g, carry):
            for u in range(unroll):  # independent chunks, so that their latencies overlap
                flag(g * unroll + u, carry)
            return carry

        @pl.when(jnp.max(multi_ref[...]) > 0)
        def _merges():
            jax.lax.fori_loop(0, chunks // unroll, flags, 0)
            jax.lax.fori_loop(0, chunks, merge, 0)

        @pl.when(j == n_blocks - 1)
        def _count():
            n_ref[...] = jnp.full((8, _LANES), count_ref[0], jnp.int32)

    out = [pl.BlockSpec((block_rows, lanes), lambda i, j: (i, 0))] * 2
    out.append(pl.BlockSpec((8, _LANES), lambda i, j: (i, 0)))
    bd, bi, merged = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(B // block_rows, n_blocks), in_specs=in_specs,
            out_specs=out,
            scratch_shapes=[
                pltpu.VMEM((block_rows, bk), dtype), pltpu.VMEM((block_rows, 1), jnp.int32),
                pltpu.SMEM((block_rows // rows,), jnp.int32), pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, lanes), dtype, vma=vma),
            jax.ShapeDtypeStruct((B, lanes), jnp.int32, vma=vma),
            jax.ShapeDtypeStruct((B // block_rows * 8, _LANES), jnp.int32, vma=vma),
        ],
        name=kernel_name("knn_topk", fast),
        **_call_params(interpret),
    )(*operands)
    return bd, bi, merged


def _pad_rows_multiple(a: jax.Array, mult: int) -> Tuple[jax.Array, int]:
    n = a.shape[0]
    pad = (-n) % mult
    if pad:
        a = jnp.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1))
    return a, n


# ----------------------------------------------------- fused assign (KMeans) --


def assign_argmin(
    xb: jax.Array,  # [B, d] one row tile
    centers: jax.Array,  # [k, d]
    *,
    fast: bool = False,
    block_rows: Optional[int] = None,
    block_k: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Nearest-center reduction for one row tile: (min d2 [B] WITHOUT the
    ||x||^2 term, assignment [B] int32). The k-tiled kernel and the one-shot
    fallback share the exact `c_sq - 2 x.c^T` formula; first-index argmin
    ties are preserved across k blocks by the kernel's strict-< merge."""
    k, d = centers.shape
    c_sq = _c_sq(centers)
    plan = block_plan(xb.shape[0], k, d, xb.dtype, fast)
    if plan is None:
        d2 = c_sq[None, :] - 2.0 * _mm(xb, centers.T, fast)
        return jnp.min(d2, axis=1), jnp.argmin(d2, axis=1).astype(jnp.int32)
    br, bk = block_rows or plan[0], block_k or plan[1]
    xp, n = _pad_rows_multiple(xb, br)
    cp, _ = _pad_rows_multiple(centers, bk)
    csq_p = jnp.pad(c_sq, (0, cp.shape[0] - k), constant_values=jnp.inf)
    mind, best = _pl_argmin(
        xp, cp, csq_p, block_rows=br, block_k=min(bk, cp.shape[0]),
        fast=fast, interpret=_interpret(),
    )
    return mind[:n], best[:n]


def _nearest(xb, centers, fast, block_rows, block_k, x_sq, tile):
    """The head `assign_accumulate` and `assign_inertia` share, for one row
    tile: (min d2 [B] WITH the ||x||^2 term, assignment [B], kernel), where
    `kernel` is None on the jnp form and on the kernel path the rest of
    what the accumulate takes: (the rows, padded unless read in place,
    block rows, block k, the padded center count). Arguments as
    `assign_accumulate` takes them, refused as there."""
    k, d = centers.shape
    n = xb.shape[0] if tile is None else tile[1]
    plan = block_plan(n, k, d, xb.dtype, fast)
    br, bk = (block_rows or plan[0], block_k or plan[1]) if plan else (None, None)
    if tile is not None and (x_sq is None or plan is None or n % br):
        raise ValueError(
            f"a {n}-row tile read in place needs its row norms, the kernels and "
            f"whole row blocks (plan {plan}): slice the tile instead"
        )
    if x_sq is None:
        x_sq = row_sq(xb)
    if plan is None:
        d2 = _c_sq(centers)[None, :] - 2.0 * _mm(xb, centers.T, fast)
        return jnp.min(d2, axis=1) + x_sq, jnp.argmin(d2, axis=1), None
    xp = xb if tile is not None else _pad_rows_multiple(xb, br)[0]
    cp, _ = _pad_rows_multiple(centers, bk)
    bk = min(bk, cp.shape[0])
    csq_p = jnp.pad(_c_sq(centers), (0, cp.shape[0] - k), constant_values=jnp.inf)
    mind, best = _pl_argmin(
        xp, cp, csq_p, block_rows=br, block_k=bk, fast=fast,
        interpret=_interpret(), tile=tile,
    )
    return mind[:n] + x_sq, best, (xp, br, bk, cp.shape[0])


def assign_accumulate(
    xb: jax.Array,  # [B, d] one row tile, or with `tile` the whole [n, d] block it lies in
    wb: jax.Array,  # [B] weights (0 on padding rows — they contribute nothing)
    centers: jax.Array,  # [k, d]
    *,
    fast: bool = False,
    block_rows: Optional[int] = None,
    block_k: Optional[int] = None,
    x_sq: Optional[jax.Array] = None,
    tile: Optional[Tuple[jax.Array, int]] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One row tile's fused Lloyd contribution: (sums [k, d], counts [k],
    inertia scalar). THE kmeans inner loop: assignment (k-tiled argmin) plus
    the weighted one-hot accumulation, never materializing [B, k] on the
    kernel path.

    `x_sq`: the tile's [B] squared row norms where the caller keeps them (a
    KMeans fit makes them once); computed from `xb` otherwise.
    `tile = (start, rows)`: the kernels read the B = `rows` rows from the
    (traced) row `start` on out of `xb` where it lies (`_pl_argmin`), `wb`
    and `x_sq` being the tile's own. For a caller that knows `xb` lies
    row-major (no trace sees a layout: ops/kmeans.py `kmeans_fit` reads it
    from the committed array), the kernels are on and `rows` is a multiple
    of the plan's block rows (`block_plan`); anything else is the caller's
    error."""
    k = centers.shape[0]
    min_d2, assign, kernel = _nearest(xb, centers, fast, block_rows, block_k, x_sq, tile)
    inertia = jnp.sum(jnp.maximum(min_d2, 0.0) * wb)
    if kernel is None:
        oh = jax.nn.one_hot(assign, k, dtype=xb.dtype) * wb[:, None]
        return _mm(oh.T, xb, fast), jnp.sum(oh, axis=0), inertia
    xp, br, bk, kp = kernel
    wp = wb if tile is not None else _pad_rows_multiple(wb, br)[0]
    sums_p, counts_p = _pl_accumulate(
        xp, wp, assign, kp, block_rows=br, block_k=bk, fast=fast,
        interpret=_interpret(), tile=tile,
    )
    return sums_p[:k], counts_p[:k], inertia


def assign_inertia(
    xb: jax.Array,  # [B, d] one row tile, or with `tile` the whole [n, d] block it lies in
    wb: jax.Array,  # [B] weights (0 on padding rows)
    centers: jax.Array,  # [k, d]
    *,
    x_sq: Optional[jax.Array] = None,
    tile: Optional[Tuple[jax.Array, int]] = None,
) -> jax.Array:
    """`assign_accumulate(xb, wb, centers, x_sq=x_sq, tile=tile)[2]`, the
    float32 inertia of one row tile, without the sums and counts: the
    float32 argmin kernel alone (`srml_argmin_f32`) on the kernel path. The
    argmin and the sum are `assign_accumulate`'s own (`_nearest`), so the
    scalar is its third output bit for bit. `x_sq` and `tile` as there,
    refused as there."""
    min_d2, _, _ = _nearest(xb, centers, False, None, None, x_sq, tile)
    return jnp.sum(jnp.maximum(min_d2, 0.0) * wb)


def _tile_operands(X, w, x_sq, start, rows: int, in_place: bool):
    """(x, w, x_sq, tile) of rows [start, start + rows) of X, as
    `_scan_tiles` hands them to a step: `w` and `x_sq` sliced (1-D, 128 KB a
    tile at 32,768 rows); X whole with `tile` where the kernels read the
    rows `in_place`, else sliced with no `tile`, which makes the tile a
    buffer of its own (a Mosaic operand is a whole array)."""
    wb = jax.lax.dynamic_slice_in_dim(w, start, rows, 0)
    qb = jax.lax.dynamic_slice_in_dim(x_sq, start, rows, 0)
    if in_place:
        return X, wb, qb, (start, rows)
    return jax.lax.dynamic_slice_in_dim(X, start, rows, 0), wb, qb, None


def _scan_tiles(step, init, Xl, wl, x_sq, batch_rows: int, in_place: bool):
    """The in-program tile loop of the `tile_assign_*` scans: `step(carry,
    x, w, x_sq, tile)` on each tile's operands (`_tile_operands`), over the
    full tiles in a fori_loop, as `in_place` says, then once over the
    ragged tail, sliced."""
    nl = Xl.shape[0]
    batch_rows = min(batch_rows, nl)
    n_full = (nl // batch_rows) * batch_rows

    def at(carry, start, rows, tile_in_place):
        return step(carry, *_tile_operands(Xl, wl, x_sq, start, rows, tile_in_place))

    carry = jax.lax.fori_loop(
        0, n_full // batch_rows,
        lambda i, carry: at(carry, i * batch_rows, batch_rows, in_place),
        init,
    )
    if nl - n_full:
        carry = at(carry, n_full, nl - n_full, False)
    return carry


def tile_assign_accumulate(
    Xl: jax.Array, wl: jax.Array, centers: jax.Array, x_sq: jax.Array,
    batch_rows: int, fast: bool = False, in_place: bool = False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Scan one device's rows in tiles; returns (sums [k,d], counts [k],
    inertia) — the whole-device Lloyd accumulation of ops/kmeans.py
    `_lloyd_step`, on one device and under `shard_map` alike. `x_sq` holds
    the rows' squared norms (made once a fit, not once a tile).

    The full tiles are one fori_loop. `in_place` (the caller's statement
    that Xl lies row-major and a full tile is whole row blocks: see
    `assign_accumulate`'s `tile`): the kernels of each full tile index Xl
    itself by the loop's tile offset, and the compiled step holds no value
    of the tile's shape and no copy of Xl (tests/test_distance.py pins the
    text for a v5e). Otherwise each tile is cut out of Xl by a
    `dynamic_slice` inside the loop, one tile-sized buffer written and read
    back a tile; for an Xl that a TPU keeps column-major (its default at
    d = 3,000) the slice is also turned by a `copy` before the kernels
    (docs/performance.md "Tiled distance core"). The ragged tail is one
    sliced step after the loop either way. The shard is neither padded nor
    reshaped: either would make a second buffer of X's size."""
    _note("distance.assign_programs")
    nl, d = Xl.shape
    k = centers.shape[0]

    def step(carry, xb, wb, qb, tile):
        s, c, i = assign_accumulate(xb, wb, centers, fast=fast, x_sq=qb, tile=tile)
        return carry[0] + s, carry[1] + c, carry[2] + i

    # under a shard_map the carry varies like the per-shard accumulators
    # (vma typing); the meshless 1-device program has no axis to cast over
    init = _vary_like(
        Xl,
        jnp.zeros((k, d), Xl.dtype),
        jnp.zeros((k,), Xl.dtype),
        jnp.zeros((), Xl.dtype),
    )
    return _scan_tiles(step, init, Xl, wl, x_sq, batch_rows, in_place)


def tile_assign_inertia(
    Xl: jax.Array, wl: jax.Array, centers: jax.Array, x_sq: jax.Array,
    batch_rows: int, in_place: bool = False,
) -> jax.Array:
    """One device's inertia: `tile_assign_accumulate(...)[2]` at full
    precision, over the same tiles in the same order (the final pass of a
    KMeans fit)."""
    _note("distance.assign_programs")

    def step(inertia, xb, wb, qb, tile):
        return inertia + assign_inertia(xb, wb, centers, x_sq=qb, tile=tile)

    (init,) = _vary_like(Xl, jnp.zeros((), Xl.dtype))
    return _scan_tiles(step, init, Xl, wl, x_sq, batch_rows, in_place)


# ---------------------------------------------------- row-tiled assignment --


def argmin_assign(
    X: jax.Array, centers: jax.Array, *, batch_rows: Optional[int] = None,
    fast: bool = False,
) -> jax.Array:
    """Nearest-center assignment over ALL rows, row-tiled through the core:
    int32 [n]. The predict-side entry (kmeans transform, k-means|| candidate
    weighting, IVF/CAGRA anchor assignment, the serving plane's bf16 query
    path) — an admission-approved fit must not OOM at predict because the
    full [n, k] distance matrix materialized (docs/performance.md "Tiled
    distance core"). `fast` runs the distance matmuls in the parity-tested
    fast-bf16 mode (docs/serving.md "bf16 serving"). Tiles are clamped back
    at the ragged tail (overlap rows recompute the same assignment — writes
    are idempotent), so no padded copy of X is ever made."""
    n = X.shape[0]
    tr = min(batch_rows or tile_rows(), max(n, 1))
    if n <= tr:
        return assign_argmin(X, centers, fast=fast)[1]
    n_tiles = -(-n // tr)

    def body(i, out):
        s0 = jnp.minimum(i * tr, n - tr)
        xb = jax.lax.dynamic_slice_in_dim(X, s0, tr, 0)
        a = assign_argmin(xb, centers, fast=fast)[1]
        return jax.lax.dynamic_update_slice(out, a, (s0,))

    # under a shard_map the carry varies like the rows it is computed from
    (out0,) = _vary_like(X, jnp.zeros((n,), jnp.int32))
    return jax.lax.fori_loop(0, n_tiles, body, out0)


# ----------------------------------------------------------- top-k (kNN) ----


def topk_tile(
    q: jax.Array,  # [B, d] one query tile
    items: jax.Array,  # [n, d]
    valid: Optional[jax.Array],  # [n] bool, or None for all-valid
    kk: int,
    *,
    item_sq: Optional[jax.Array] = None,
    fast: bool = False,
    k_tile: Optional[int] = None,
    block_rows: Optional[int] = None,
    count_merges: bool = False,
) -> Tuple[jax.Array, ...]:
    """Running top-kk of one query tile against ALL items: (d2 [B, kk]
    WITHOUT the ||q||^2 term, item index [B, kk] int32), ascending by
    distance with `jax.lax.top_k` tie semantics (lower index first — pinned
    vs a full-matrix top_k by tests/test_distance.py and
    tests/test_knn_topk.py). With `count_merges` a third value: the
    (8-row chunk, item block) pairs that merged on the kernel path (int32
    scalar; 0 on the jnp form), for a caller that fetches it with the
    lists.

    Where `topk_plan` gives blocks, ONE fused kernel (`_pl_knn_topk`) forms
    the distances block by block in VMEM and keeps each row's best list
    there: only [B, kk] reaches HBM. `block_rows` / `k_tile` override the
    plan's (query rows, items) blocks. The items are read where they lie,
    so a caller that holds them row-major (`make_global_rows(...,
    x_layout=X_ROW_MAJOR)`) has them copied by nothing; the ragged last
    block is one zero-filled copy of at most `k_tile` rows.

    The jnp form scans the item axis in `k_tile` blocks (default: one block,
    the one-matmul shape right for CPU) with the [B, kk] best list as the
    loop carry; the last block is clamped back and its overlap columns
    masked +inf (already merged)."""
    _note("distance.topk_programs")
    n, d = items.shape
    kk = min(kk, n)
    if item_sq is None:
        item_sq = row_sq(items)
    plan = topk_plan(q.shape[0], n, d, kk, q.dtype, fast)
    if plan is not None:
        d2, idx, merged = _fused_topk(q, items, valid, item_sq, kk, block_rows or plan[0],
                                      k_tile or plan[1], fast)
        return (d2, idx, merged) if count_merges else (d2, idx)
    d2, idx = _scan_topk(q, items, valid, item_sq, kk, k_tile, fast)
    return (d2, idx, jnp.int32(0)) if count_merges else (d2, idx)


def _scan_topk(q, items, valid, item_sq, kk, k_tile, fast):
    """`topk_tile`'s jnp form."""
    n = items.shape[0]
    kt = min(k_tile or n, n)
    big = jnp.asarray(jnp.inf, items.dtype)

    def masked_block(start):
        s0 = jnp.minimum(start, n - kt)
        xt = jax.lax.dynamic_slice_in_dim(items, s0, kt, 0)
        sq = jax.lax.dynamic_slice_in_dim(item_sq, s0, kt, 0)
        ids = s0 + jnp.arange(kt, dtype=jnp.int32)
        d2 = sq[None, :] - 2.0 * _mm(q, xt.T, fast)
        keep = ids >= start  # clamp-back overlap: already merged columns
        if valid is not None:
            keep = keep & jax.lax.dynamic_slice_in_dim(valid, s0, kt, 0)
        return jnp.where(keep[None, :], d2, big), ids

    if kt >= n:  # single block: exactly the one-shot top_k
        d2, ids = masked_block(jnp.int32(0))
        neg_d, pos = jax.lax.top_k(-d2, kk)
        return -neg_d, jnp.take_along_axis(
            jnp.broadcast_to(ids[None, :], d2.shape), pos, axis=1
        )

    n_tiles = -(-n // kt)

    def body(i, carry):
        best_d2, best_i = carry
        d2, ids = masked_block(i * kt)
        cat_d = jnp.concatenate([best_d2, d2], axis=1)
        cat_i = jnp.concatenate(
            [best_i, jnp.broadcast_to(ids[None, :], d2.shape)], axis=1
        )
        neg_d, pos = jax.lax.top_k(-cat_d, kk)
        return -neg_d, jnp.take_along_axis(cat_i, pos, axis=1)

    # under a shard_map the best lists vary like the item shard they scan
    init = _vary_like(
        items,
        jnp.full((q.shape[0], kk), jnp.inf, items.dtype),
        jnp.zeros((q.shape[0], kk), jnp.int32),
    )
    return jax.lax.fori_loop(0, n_tiles, body, init)


def _fused_topk(q, items, valid, item_sq, kk, block_rows, block_items, fast):
    """`topk_tile` on the kernel path: the operands `_pl_knn_topk` takes,
    the call, and its lists cut to kk and sorted by (distance, index) — the
    order `lax.top_k` gives — with the call's merged chunks (int32 scalar).
    An unfilled slot (fewer valid items than kk) reads +inf at index 0."""
    n = items.shape[0]
    nq = q.shape[0]
    n_full, rem = divmod(n, block_items)
    sq = item_sq if valid is None else jnp.where(valid, item_sq, jnp.inf)
    xsq = jnp.pad(sq.astype(q.dtype), (0, (n_full + bool(rem)) * block_items - n),
                  constant_values=jnp.inf)[None, :]
    tail = None
    if rem:
        tail = jnp.pad(items[n_full * block_items:], ((0, block_items - rem), (0, 0)))
    # -2 q, exact in every precision: the kernel adds ||x||^2 to its contraction
    qm2, _ = _pad_rows_multiple(q * -2.0, block_rows)
    bd, bi, merged = _pl_knn_topk(
        qm2, items, tail, xsq, kk=kk, block_rows=block_rows, block_items=block_items,
        n_full=n_full, fast=fast, interpret=_interpret(),
    )
    d2, idx = jax.lax.sort((bd[:nq, :kk], bi[:nq, :kk]), dimension=1, num_keys=2)
    return d2, jnp.maximum(idx, 0), jnp.sum(merged[::8, 0], dtype=jnp.int32)


def tile_topk(
    items: jax.Array,  # [n_loc, d]
    queries: jax.Array,  # [nq, d]
    valid: jax.Array,  # [n_loc] bool (False on padding)
    k: int,
    batch_queries: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Exact top-k of every query against one device's items: (dist [nq, k]
    SQUARED incl. the ||q||^2 term, idx [nq, k] local). On the kernel path
    (`topk_plan`) all queries go to one fused kernel, whose grid walks the
    query blocks itself; the jnp form scans query tiles of `batch_queries`
    rows (default `config["distance_tile_rows"]`). Padding items get +inf
    distance; k past the shard's row count is padded with +inf so a global
    merge never selects it."""
    n_loc, d = items.shape
    nq = queries.shape[0]
    item_sq = row_sq(items)
    kk = min(k, n_loc)

    def one_tile(q):
        d2, idx = topk_tile(q, items, valid, kk, item_sq=item_sq)
        d_out = d2 + row_sq(q)[:, None]
        if kk < k:
            d_out = jnp.pad(d_out, ((0, 0), (0, k - kk)), constant_values=jnp.inf)
            idx = jnp.pad(idx, ((0, 0), (0, k - kk)))
        return d_out, idx

    if nq and topk_plan(nq, n_loc, d, kk, queries.dtype, False) is not None:
        return one_tile(queries)
    bq = batch_queries or tile_rows()
    n_tiles = max(1, -(-nq // bq))
    pad = n_tiles * bq - nq
    qp = jnp.pad(queries, ((0, pad), (0, 0)))
    qt = qp.reshape(n_tiles, bq, d)
    dists, idxs = jax.lax.map(one_tile, qt)
    return dists.reshape(-1, k)[:nq], idxs.reshape(-1, k)[:nq]


# ------------------------------------------------------ distance tiles ------


def pairwise_d2(q: jax.Array, x: jax.Array, metric: str = "euclidean") -> jax.Array:
    """One dense distance tile [tq, n]: squared euclidean, or cosine
    distance. The tile IS the intended output here (DBSCAN's threshold
    passes, running-min merges), so it stays a single MXU contraction — the
    Pallas path exists for the fused argmin/top-k reductions above, where
    NOT materializing the tile is the win.

    Inputs are pre-normalized for cosine by the caller, so cosine distance
    is 1 - q.x^T — both metrics ride the MXU. For "precomputed" the rows ARE
    distances already (DBSCAN hands each pass the matching column slice of
    the user's distance matrix), so the tile is just `q` — no compute."""
    _note("distance.pairwise_programs")
    if metric == "precomputed":
        return q
    if metric == "cosine":
        return 1.0 - q @ x.T
    return row_sq(q)[:, None] - 2.0 * (q @ x.T) + row_sq(x)[None, :]


def min_d2_update(x: jax.Array, cand: jax.Array, min_d2: jax.Array) -> jax.Array:
    """min(min_d2, min distance^2 to the NEW candidate block) — the k-means||
    seeding round's incremental matmul (one tile, running min)."""
    d2 = pairwise_d2(x, cand)
    return jnp.minimum(min_d2, jnp.maximum(jnp.min(d2, axis=1), 0.0))


def score_candidates(
    q_rows: jax.Array, cand: jax.Array, x: jax.Array, x_sq: jax.Array,
    fast: bool = False,
) -> jax.Array:
    """d2[t, c] = ||q_rows[t] - x[cand[t, c]]||^2 (squared L2, >= 0); the
    [T, C, d] gather feeds one batched einsum (the MXU side of a graph-ANN
    round). fast=True runs the einsum with bf16 inputs and f32 accumulation
    (the KMeans fast-path policy): CAGRA's BUILD only uses these distances
    to RANK candidate edges, so the ~1e-3 relative rounding is absorbed by
    the descent's redundancy, while the one-pass MXU einsum runs ~2.6x the
    f32-highest rate on a v5e. Searches keep exact f32 scoring (their
    distances are returned to the user)."""
    xc = x[cand]  # [T, C, d]
    if fast:
        dots = jnp.einsum(
            "td,tcd->tc",
            q_rows.astype(jnp.bfloat16),
            xc.astype(jnp.bfloat16),
            preferred_element_type=jnp.float32,
        )
    else:
        dots = jnp.einsum("td,tcd->tc", q_rows, xc)
    d2 = row_sq(q_rows)[:, None] + x_sq[cand] - 2.0 * dots
    return jnp.maximum(d2, 0.0)


def batched_self_topk(
    xb: jax.Array, ids_b: jax.Array, *, kk: int
) -> Tuple[jax.Array, jax.Array]:
    """Exact kNN inside padded buckets: xb [Cb, L, d], ids_b [Cb, L] global
    ids (-1 pad). One batched [Cb, L, L] distance matmul on the MXU + top-k
    — CAGRA's clustered brute-force seeding unit. Returns (d2 [Cb, L, kk],
    neighbor ids [Cb, L, kk])."""
    big = jnp.asarray(jnp.inf, jnp.float32)
    sq = jnp.sum(xb * xb, axis=2)  # [Cb, L]
    G = jnp.einsum("cld,cmd->clm", xb, xb)
    d2 = sq[:, :, None] + sq[:, None, :] - 2.0 * G
    valid = ids_b >= 0
    mask = valid[:, None, :] & valid[:, :, None]
    eye = jnp.eye(xb.shape[1], dtype=bool)[None]
    d2 = jnp.where(mask & ~eye, jnp.maximum(d2, 0.0), big)
    nd2, pos = jax.lax.top_k(-d2, kk)
    nid = jnp.take_along_axis(
        jnp.broadcast_to(ids_b[:, None, :], d2.shape), pos, axis=2
    )
    return -nd2, nid
