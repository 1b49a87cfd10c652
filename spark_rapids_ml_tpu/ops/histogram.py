#
# The forest's histogram accumulate over rows SORTED BY NODE as ONE Mosaic
# kernel, `srml_hist_accumulate_bf16` — `ops/trees.py` `_sorted_histogram`'s
# pass behind the same operands, the same integers out (that function stays
# the `jnp` form and the tests' reference).
#
# What a pass does, a chunk of `CHUNK_ROWS` sorted rows at a time:
#  * FETCH. The uint8 X stays in HBM. A TPU lays [rows, 3,072] uint8 out in
#    (8, 128) tiles with four rows packed into each 32-bit word, so the least
#    a DMA can address of a row is its 8-row stripe: 24 KB, contiguous
#    (Mosaic refuses a one-row slice: "must be aligned to tiling"). A chunk's
#    stripes are copied by row id (`order`, a tile's ids in SMEM) into one of
#    two VMEM buffers as 32-bit words, the next chunk's copies started before
#    the present chunk is worked on and waited for after: the fetch runs
#    under the arithmetic. The row's own bytes come out of its words on the
#    vector units (which of the stripe's two word rows, which byte of the
#    word: `row % 8`).
#  * SELECT, node by node. The rows are sorted, so a chunk holds the rows of
#    a few consecutive nodes (`chunk_lo` .. `chunk_hi`, prefetched scalars).
#    For a window of `128 // lanes-a-node` consecutive nodes (two at 54
#    features a node) the chunk times the window's [columns, 128] selection
#    matrix, built in VMEM from the window's feature ids by an iota compare,
#    picks each row's m bin ids at ITS node's features: MXU work
#    2 · rows · columns · 128 a window, where the XLA form pays 16 nodes'
#    worth of columns (864) for every row. (A lane gather in each column
#    tile does the same with no MXU work and read 0.5 to 0.8 ms a pass
#    slower on a v5e: PERF.md, PR 39.)
#  * ACCUMULATE. The chunk's picked ids are transposed (features on the
#    sublanes, rows on the lanes), so that a feature's (bin, row) one-hot is
#    a sublane iota against a row of ids, and the histogram of a GROUP of
#    `128 // S` consecutive nodes is one contraction a chunk,
#    [m · bins, rows] x [rows, (statistic, node of the group)], whose result
#    fills the MXU's 128 lanes. A group's sums are carried in a float32 VMEM
#    block across chunks, tiles and grid steps and written to HBM ONCE, when
#    the sorted rows leave the group. Groups no counted row reached are
#    never written: the output starts as zeros (`input_output_aliases`).
#
# Operands 0/1, bin ids under 256 and bootstrap counts under 256 in bfloat16,
# float32 sums of integers under 2^24: exact in any order.
#
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import distance

TILE_ROWS = 1024  # sorted rows a grid step: their ids are one SMEM block (a TPU lays a 1-D int32 array out in tiles of 1,024)
CHUNK_ROWS = 128  # sorted rows fetched, selected and contracted at a time: two stripe buffers of 3 MB at 3,072 columns
STRIPE_ROWS = 8  # rows of the binned X a DMA fetches for one: a uint8 (8, 128) tile row
_LANES = 128
# stated at each contraction: Mosaic takes the ambient `jax.default_matmul_precision` (a fit may run under "highest") for
# bfloat16 operands' too and refuses it ("Bad lhs type"); one bfloat16 pass is exact for 0/1 and integers under 256
_ONE_PASS = jax.lax.Precision.DEFAULT
_ACC_MAX_BYTES = 8 << 20  # the carried [m · bins, 128] float32 block of a group


def _node_lanes(m: int) -> int:
    """Lanes a node's m picked ids take in a window's 128: the power of two
    from 16 up that holds m."""
    lanes = 16
    while lanes < m:
        lanes *= 2
    return lanes


def _group_nodes(n_stats: int) -> int:
    """Nodes a group: the power of two whose (statistic, node) pairs fill
    the 128 lanes of the contraction's result (64 for two classes)."""
    nodes = 1
    while 2 * nodes * n_stats <= _LANES:
        nodes *= 2
    return nodes


def _bin_lanes(bins: int) -> int:
    return -(-bins // _LANES) * _LANES


def takes(rows: int, cols: int, n_stats: int, m: int, bins: int) -> bool:
    """Whether the kernel takes a sorted pass over a device's [rows, cols]
    uint8 bin ids (the mode is the caller's to ask: `distance.kernel_mode()`):
    whole 128-lane columns and whole 8-row stripes, a node's m features in
    one 128-lane window, statistics that leave a group 8 nodes or more, and
    a carried block that VMEM holds. Everything else takes
    `_sorted_histogram`."""
    if bins > 256 or cols % _LANES or rows % STRIPE_ROWS or m > _LANES or _group_nodes(n_stats) < 8:
        return False
    return m * _bin_lanes(bins) * _LANES * 4 <= _ACC_MAX_BYTES


def _kernel(
    lo_ref, hi_ref,  # prefetched: each chunk's first and last node that counts (lo > hi: none)
    ids_ref, ids_next_ref,  # SMEM [tile]: this grid step's row ids, and the next one's
    code_ref,  # [1, tile]: node · 8 + row % 8, the rows on the lanes
    st_ref,  # [S, tile] float32
    fids_ref,  # [windows, 128] int32: a window's feature ids, -1 where a node has none
    xb_ref,  # HBM [rows / 8, 8, columns] uint8
    _zeros_ref,  # HBM: the output's buffer, zeros
    out_ref,  # HBM [groups, m · bin lanes, 128] float32: a group's (statistic, node) pairs on the lanes
    buf, xblk, hot, acc, group, sem, out_sem,
    *, n_chunks: int, S: int, m: int, node_lanes: int, bin_lanes: int,
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R = CHUNK_ROWS
    per_tile = ids_ref.shape[0] // R
    cols = xblk.shape[1]
    G = _LANES // node_lanes  # nodes a window
    NG = _group_nodes(S)  # nodes a group
    t = pl.program_id(0)

    def live(q):
        q = jnp.minimum(q, n_chunks - 1)
        return lo_ref[q] <= hi_ref[q]

    def stripe_copy(stripe, slot, r):
        return pltpu.make_async_copy(xb_ref.at[stripe].bitcast(jnp.int32), buf.at[slot, r], sem.at[slot])

    def start_fetch(ids, base, slot):
        def eight(i):  # unrolled by hand: Mosaic's loops take no partial unroll
            for r in range(8):
                stripe_copy(ids[base + i * 8 + r] // STRIPE_ROWS, slot, i * 8 + r).start()

        _loop(0, R // 8, eight)

    def wait_fetch(slot):
        def eight(i):
            for r in range(8):
                stripe_copy(0, slot, i * 8 + r).wait()  # blocking-ok: a DMA semaphore inside the kernel, on the device: no peer, no host thread

        _loop(0, R // 8, eight)

    def flush():
        copy = pltpu.make_async_copy(acc, out_ref.at[group[0]], out_sem.at[0])
        copy.start()
        copy.wait()  # blocking-ok: a DMA semaphore inside the kernel, on the device

    @pl.when(t == 0)
    def _():
        group[0] = -1

        @pl.when(live(0))
        def _():
            start_fetch(ids_ref, 0, 0)

    def window(w, node_col, ids):
        """`ids` with the rows of window w's nodes set: each row's m bin ids at ITS node's features."""
        f = fids_ref[pl.ds(w, 1), :]  # [1, 128]: G nodes' feature ids
        select = _hot(jax.lax.broadcasted_iota(jnp.int32, (cols, _LANES), 0) == f)  # [columns, 128], built here
        picked = jnp.dot(xblk[...], select, preferred_element_type=jnp.float32, precision=_ONE_PASS).astype(jnp.int32)
        rel = node_col - w * G  # [R, 1]
        for k in range(G):  # the row's own node's lanes, moved to the front
            ids = jnp.where(rel == k, pltpu.roll(picked, (_LANES - k * node_lanes) % _LANES, 1) if k else picked, ids)
        return ids

    def accumulate(g, r0):
        """The chunk's rows at group g's nodes, added to the carried block."""
        @pl.when(g != group[0])
        def _():
            @pl.when(group[0] >= 0)
            def _():
                flush()

            acc[...] = jnp.zeros_like(acc)
            group[0] = g

        # the (statistic, node of the group) one-hot of the chunk's rows, the rows on the lanes
        rel = (code_ref[:, pl.ds(r0, R)] >> 3) - g * NG  # [1, R]
        at = jax.lax.broadcasted_iota(jnp.int32, (_LANES, R), 0)
        lhs = jnp.zeros((_LANES, R), jnp.float32)
        for s in range(S):
            lhs = jnp.where((at == rel + s * NG) & (rel >= 0) & (rel < NG), st_ref[s:s + 1, pl.ds(r0, R)], lhs)
        acc[...] += jax.lax.dot_general(
            hot[...], lhs.astype(jnp.bfloat16), (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, precision=_ONE_PASS,
        )  # [m · bins, rows] x [128, rows]ᵀ

    def chunk(u):
        q = t * per_tile + u
        slot = u % 2  # a tile holds an even number of chunks
        nxt = q + 1

        @pl.when((u + 1 < per_tile) & live(nxt))
        def _():
            start_fetch(ids_ref, (u + 1) * R, 1 - slot)

        @pl.when((u + 1 == per_tile) & (nxt < n_chunks) & live(nxt))
        def _():
            start_fetch(ids_next_ref, 0, 1 - slot)

        @pl.when(live(q))
        def _():
            wait_fetch(slot)
            r0 = pl.multiple_of(u * R, R)
            code = jnp.broadcast_to(code_ref[:, pl.ds(r0, R)], (_LANES, R)).T[:, 0:1]  # [R, 1]: the rows on the sublanes
            sub = code & (STRIPE_ROWS - 1)
            # the row's bytes out of its stripe's words: the word row, then the byte
            words = jnp.where(sub >= 4, buf[slot, :, 1, :], buf[slot, :, 0, :])  # [R, columns] int32
            own = jax.lax.shift_right_logical(words, (sub & 3) * 8) & 255
            xblk[...] = own.astype(jnp.float32).astype(jnp.bfloat16)
            lo, hi = lo_ref[q], hi_ref[q]
            node_col = code >> 3
            ids = jax.lax.fori_loop(
                lo // G, hi // G + 1, lambda w, ids: window(w, node_col, ids), jnp.zeros((R, _LANES), jnp.int32),
            )
            # the (feature, bin) one-hot with the rows on the lanes: a feature's bins a sublane block
            ids_t = ids.T  # [128, R]
            bin_at = jax.lax.broadcasted_iota(jnp.int32, (bin_lanes, R), 0)
            for j in range(m):
                hot[j * bin_lanes:(j + 1) * bin_lanes, :] = _hot(bin_at == ids_t[j:j + 1, :])
            _loop(lo // NG, hi // NG + 1, lambda g: accumulate(g, r0))

    _loop(0, per_tile, chunk)

    @pl.when((t == pl.num_programs(0) - 1) & (group[0] >= 0))
    def _():
        flush()


def sorted_histogram(Xb, key_s, order, st_s, n_counted, fids, *, bins: int, interpret: bool = False) -> jax.Array:
    """`ops.trees._sorted_histogram` as the kernel: the same operands
    (`n_counted` is not read: the rows that do not count carry the key
    `chunk` and come last), the same [S, chunk, m, bins] histogram in the
    statistics' dtype, bit for bit. Call it where `takes` says so."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    del n_counted
    n, cols = Xb.shape
    S = st_s.shape[0]
    chunk, m = fids.shape
    T, R = TILE_ROWS, CHUNK_ROWS
    n_pad = -(-n // T) * T
    n_tiles, n_chunks = n_pad // T, n_pad // R
    node_lanes, bin_lanes = _node_lanes(m), _bin_lanes(bins)
    G, NG = _LANES // node_lanes, _group_nodes(S)
    n_groups = -(-chunk // NG)

    # the padding rows do not count: the key `chunk`, row 0's stripe, no statistics
    key_p = jnp.pad(key_s, (0, n_pad - n), constant_values=chunk)
    order_p = jnp.pad(order, (0, n_pad - n))
    st_p = jnp.pad(st_s.astype(jnp.float32), ((0, 0), (0, n_pad - n)))
    by_chunk = key_p.reshape(n_chunks, R)  # sorted: a chunk's first and last row bound its nodes
    chunk_lo, chunk_hi = by_chunk[:, 0], jnp.minimum(by_chunk[:, -1], chunk - 1)
    code = (key_p * STRIPE_ROWS + order_p % STRIPE_ROWS)[None, :]
    windows = -(-chunk // G)
    fids_w = jnp.pad(fids, ((0, windows * G - chunk), (0, node_lanes - m)), constant_values=-1).reshape(windows, _LANES)
    last = n_tiles - 1
    call = pl.pallas_call(
        partial(_kernel, n_chunks=n_chunks, S=S, m=m, node_lanes=node_lanes, bin_lanes=bin_lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((T,), lambda t, *_: (t,), memory_space=pltpu.SMEM),
                pl.BlockSpec((T,), lambda t, *_: (jnp.minimum(t + 1, last),), memory_space=pltpu.SMEM),
                pl.BlockSpec((1, T), lambda t, *_: (0, t)),
                pl.BlockSpec((S, T), lambda t, *_: (0, t)),
                pl.BlockSpec(fids_w.shape, lambda t, *_: (0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((2, R, STRIPE_ROWS // 4, cols), jnp.int32),  # two chunks' stripes, as words
                pltpu.VMEM((R, cols), jnp.bfloat16),  # the chunk's own rows
                pltpu.VMEM((m * bin_lanes, R), jnp.bfloat16),  # the (feature, bin) one-hot, the rows on the lanes
                pltpu.VMEM((m * bin_lanes, _LANES), jnp.float32),  # a group's sums
                pltpu.SMEM((1,), jnp.int32),  # the group they belong to
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SemaphoreType.DMA((1,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_groups, m * bin_lanes, _LANES), jnp.float32),
        input_output_aliases={8: 0},  # the zeros below are the output's buffer
        name=distance.kernel_name("hist_accumulate", True),
        **distance._call_params(interpret),
    )
    # under `shard_map` the level runs with `check_vma=False` (`ops.trees._forest_programs`): no operand is typed
    hist = call(
        chunk_lo, chunk_hi, order_p, order_p, code, st_p, fids_w,
        Xb.reshape(n // STRIPE_ROWS, STRIPE_ROWS, cols), jnp.zeros((n_groups, m * bin_lanes, _LANES), jnp.float32),
    )
    # [group, feature, bin, (statistic, node of the group)] -> [statistic, node, feature, bin]
    hist = hist.reshape(n_groups, m, bin_lanes, _LANES)[:, :, :bins, :S * NG]
    hist = hist.reshape(n_groups, m, bins, S, NG).transpose(3, 0, 4, 1, 2).reshape(S, n_groups * NG, m, bins)
    return hist[:, :chunk].astype(st_s.dtype)


# ---------------------------------------------------------------------------
# Float32 statistics in pieces over many features a node: `srml_hist_accumulate_split_bf16`
# ---------------------------------------------------------------------------
#
# A regressor's (w, wy, wy²) are not small integers and its node takes 1,000
# of 3,000 features: the statistics go in as their three exact bfloat16 pieces
# each (`ops.trees.stat_pieces`), and a node's bin ids at its features are
# picked before the kernel (`ops.trees._picked_sorted`: XLA's whole-row fetch
# and one selection contraction a node a tile) into a feature-major [m_pad,
# rows] operand that the kernel reads in blocks where it lies. The grid goes
# over pieces of `SPLIT_FEATURES` features, then over the sorted rows: a
# chunk's (feature, bin) one-hot of the piece is built in VMEM, contracted
# with the chunk's (piece of a statistic, node of the group) one-hot, and the
# group's float32 sums are carried in VMEM and written once when the sorted
# rows leave the group, as `sorted_histogram` carries them. Products of a 0/1
# and a piece are exact and the sums are float32: a float32 sum of each
# statistic's exact values.

SPLIT_FEATURES = 128  # features a grid step: the carried [128 · 128 bins, 128] float32 block is 8 MiB


def split_features(m: int) -> int:
    """The picked ids' rows: m rounded up to whole grid steps of features."""
    return -(-m // SPLIT_FEATURES) * SPLIT_FEATURES


def takes_split(n_pieces: int, bins: int) -> bool:
    """Whether `split_histogram` takes a pass of `n_pieces` statistic pieces
    (three a float32 statistic) over `bins` bins: a bin one-hot of one lane
    tile and a group of one node at least. Anything else takes
    `ops.trees._sorted_histogram` with the pieces."""
    return bins <= _LANES and n_pieces <= _LANES


def _split_kernel(
    lo_ref, hi_ref,  # prefetched: each chunk's first and last node that counts (lo > hi: none)
    picked_ref,  # [SPLIT_FEATURES, tile] bfloat16: the piece's features' bin ids of the tile's rows
    key_ref,  # [1, tile] int32: the row's node (the chunk's size for a row that does not count)
    st_ref,  # [pieces, tile] float32: values exact in bfloat16
    _zeros_ref,  # HBM: the output's buffer, zeros
    out_ref,  # HBM [feature steps, groups, SPLIT_FEATURES · bin lanes, 128] float32
    hot, acc, group, out_sem,
    *, P: int, bin_lanes: int,
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R = CHUNK_ROWS
    per_tile = key_ref.shape[1] // R
    F = picked_ref.shape[0]
    NG = _group_nodes(P)
    p, t = pl.program_id(0), pl.program_id(1)

    def flush():
        copy = pltpu.make_async_copy(acc, out_ref.at[p, group[0]], out_sem.at[0])
        copy.start()
        copy.wait()  # blocking-ok: a DMA semaphore inside the kernel, on the device: no peer, no host thread

    @pl.when(t == 0)
    def _():
        group[0] = -1

    def accumulate(g, r0):
        """The chunk's rows at group g's nodes, added to the carried block."""
        @pl.when(g != group[0])
        def _():
            @pl.when(group[0] >= 0)
            def _():
                flush()

            acc[...] = jnp.zeros_like(acc)
            group[0] = g

        rel = key_ref[:, pl.ds(r0, R)] - g * NG  # [1, R]
        at = jax.lax.broadcasted_iota(jnp.int32, (_LANES, R), 0)
        lhs = jnp.zeros((_LANES, R), jnp.float32)
        for s in range(P):
            lhs = jnp.where((at == rel + s * NG) & (rel >= 0) & (rel < NG), st_ref[s:s + 1, pl.ds(r0, R)], lhs)
        acc[...] += jax.lax.dot_general(
            hot[...], lhs.astype(jnp.bfloat16), (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32, precision=_ONE_PASS,
        )  # [F · bins, rows] x [128, rows]ᵀ

    def chunk(u):
        q = t * per_tile + u

        @pl.when(lo_ref[q] <= hi_ref[q])
        def _():
            r0 = pl.multiple_of(u * R, R)
            ids = picked_ref[:, pl.ds(r0, R)].astype(jnp.float32).astype(jnp.int32)  # [F, R]: features on the sublanes
            bin_at = jax.lax.broadcasted_iota(jnp.int32, (bin_lanes, R), 0)
            for j in range(F):
                hot[j * bin_lanes:(j + 1) * bin_lanes, :] = _hot(bin_at == ids[j:j + 1, :])
            _loop(lo_ref[q] // NG, hi_ref[q] // NG + 1, lambda g: accumulate(g, r0))

    _loop(0, per_tile, chunk)

    @pl.when((t == pl.num_programs(1) - 1) & (group[0] >= 0))
    def _():
        flush()


def split_histogram(picked, key_s, pieces, chunk: int, m: int, *, bins: int, interpret: bool = False) -> jax.Array:
    """[pieces, chunk, m, bins] float32: the sums of each statistic piece of
    the sorted rows (`ops.trees.order_rows`' keys and order; `pieces` in that
    order, 0 for a row that does not count) at each node's (feature, bin),
    from `ops.trees._picked_sorted`'s [m_pad, rows] ids. Call it where
    `takes_split` says so."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m_pad, n = picked.shape
    P = pieces.shape[0]
    T, R, F = TILE_ROWS, CHUNK_ROWS, SPLIT_FEATURES
    n_pad = -(-n // T) * T
    n_tiles, n_chunks, steps = n_pad // T, n_pad // R, m_pad // F
    bin_lanes, NG = _bin_lanes(bins), _group_nodes(P)
    n_groups = -(-chunk // NG)

    key_p = jnp.pad(key_s, (0, n_pad - n), constant_values=chunk)
    by_chunk = key_p.reshape(n_chunks, R)  # sorted: a chunk's first and last row bound its nodes
    chunk_lo, chunk_hi = by_chunk[:, 0], jnp.minimum(by_chunk[:, -1], chunk - 1)
    out_shape = (steps, n_groups, F * bin_lanes, _LANES)
    call = pl.pallas_call(
        partial(_split_kernel, P=P, bin_lanes=bin_lanes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(steps, n_tiles),
            in_specs=[
                pl.BlockSpec((F, T), lambda p, t, *_: (p, t)),
                pl.BlockSpec((1, T), lambda p, t, *_: (0, t)),
                pl.BlockSpec((P, T), lambda p, t, *_: (0, t)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM((F * bin_lanes, R), jnp.bfloat16),  # the piece's (feature, bin) one-hot, the rows on the lanes
                pltpu.VMEM((F * bin_lanes, _LANES), jnp.float32),  # a group's sums
                pltpu.SMEM((1,), jnp.int32),  # the group they belong to
                pltpu.SemaphoreType.DMA((1,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        input_output_aliases={5: 0},  # the zeros below are the output's buffer
        name=distance.kernel_name("hist_accumulate_split", True),
        **distance._call_params(interpret),
    )
    hist = call(
        chunk_lo, chunk_hi, jnp.pad(picked, ((0, 0), (0, n_pad - n))), key_p[None, :],
        jnp.pad(pieces.astype(jnp.float32), ((0, 0), (0, n_pad - n))), jnp.zeros(out_shape, jnp.float32),
    )
    # [step, group, feature of the step, bin, (piece, node of the group)] -> [piece, node, feature, bin]
    hist = hist.reshape(steps, n_groups, F, bin_lanes, _LANES)[:, :, :, :bins, :P * NG]
    hist = hist.reshape(steps, n_groups, F, bins, P, NG).transpose(4, 1, 5, 0, 2, 3)
    return hist.reshape(P, n_groups * NG, m_pad, bins)[:, :chunk, :m]


def _loop(lo, hi, body) -> None:
    """`body(i)` for i in [lo, hi), the index int32 whatever the x64 mode (Mosaic lowers no int64)."""
    jax.lax.fori_loop(jnp.int32(lo), jnp.int32(hi), lambda i, c: (body(i), c)[1], jnp.int32(0))


def _hot(mask) -> jax.Array:
    return jnp.where(mask, jnp.float32(1), jnp.float32(0)).astype(jnp.bfloat16)
