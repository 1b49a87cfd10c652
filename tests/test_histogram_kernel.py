#
# The forest's sorted accumulate as a Mosaic kernel (`ops/histogram.py`,
# `srml_hist_accumulate_bf16`) against `ops.trees._sorted_histogram`, the
# `jnp` form it replaces where a kernel mode is on: the same operands, the
# same histogram BIT FOR BIT (sums of small integers in float32 are exact in
# any order). Run through the Pallas interpreter on the CPU: the row copies
# by id, their semaphores, the prefetched scalars and the carried block are
# the kernel's own code.
#
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_ml_tpu.ops import histogram, trees


def operands(n, d, chunk, m, bins, S, *, seed=0, drawn=0.63, max_count=3, nodes_of=None):
    """A level's operands as `order_rows` hands them over: `n` rows of `d`
    features in `bins` bins, each at one of `chunk` nodes (`nodes_of(rng, n)`
    where the case places them), of one of `S` classes, counted 0 to
    `max_count` times by the bootstrap (`drawn`: the share drawn at all)."""
    rng = np.random.default_rng(seed)
    Xb = np.zeros((n, trees.binned_cols(d)), np.uint8)
    Xb[:, :d] = rng.integers(0, bins, (n, d))
    node = (nodes_of(rng, n) if nodes_of else rng.integers(0, chunk, n)).astype(np.int32)
    counts = rng.integers(1, max_count + 1, n) * (rng.random(n) < drawn)
    stats = np.zeros((S, n), np.float32)
    stats[rng.integers(0, S, n), np.arange(n)] = counts
    fids = np.stack([rng.permutation(d)[:m] for _ in range(chunk)]).astype(np.int32)
    ordered = jax.jit(trees.order_rows)(jnp.asarray(stats), jnp.asarray(node), jnp.ones(n, bool), 0, chunk)
    return jnp.asarray(Xb), jnp.asarray(fids), ordered


def one_long_node(rng, n):
    """Node 5 holds 200 rows in a row of the sorted order: three tiles of 64."""
    node = rng.integers(0, 32, n)
    node[node == 5] = 6
    node[rng.permutation(n)[:200]] = 5
    return node


CASES = {
    # name: (n, d, chunk, m, bins, S, tile rows, chunk rows, operands' keywords)
    "a_node_with_no_counted_row": (640, 300, 32, 17, 32, 2, 256, 32, {"nodes_of": lambda rng, n: np.where((k := rng.integers(0, 32, n)) == 9, 10, k)}),
    "a_segment_over_three_tiles": (640, 300, 32, 17, 32, 2, 64, 16, {"nodes_of": one_long_node, "drawn": 1.0}),
    "a_last_tile_clamped_back": (1000, 300, 32, 17, 32, 2, 256, 32, {}),
    "no_row_counts": (512, 300, 32, 17, 32, 2, 256, 32, {"drawn": 0.0}),
    "every_row_counts": (512, 300, 32, 17, 32, 2, 256, 32, {"drawn": 1.0}),
    "chunk_32": (768, 300, 32, 17, 128, 2, 256, 32, {}),
    "chunk_48_not_a_multiple_of_16": (768, 300, 48, 17, 128, 2, 256, 32, {}),
    "chunk_4096_a_few_rows_a_node": (8192, 300, 4096, 17, 16, 2, 1024, 128, {}),
    "three_statistics": (768, 300, 32, 17, 64, 3, 256, 32, {}),
    "bootstrap_counts_up_to_255": (768, 300, 32, 17, 32, 2, 256, 32, {"max_count": 255}),
    "d_300_binned_columns_384": (512, 300, 64, 17, 128, 2, 256, 64, {}),
    "d_3000_binned_columns_3072": (512, 3000, 64, 54, 128, 2, 256, 128, {}),
    "one_node_a_window_over_64_features": (512, 300, 32, 100, 16, 2, 256, 32, {}),
    "bins_over_128": (512, 300, 32, 17, 200, 2, 256, 32, {}),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_kernel_gives_the_jnp_forms_histogram_bit_for_bit(monkeypatch, case):
    n, d, chunk, m, bins, S, tile, rows, kw = CASES[case]
    monkeypatch.setattr(histogram, "TILE_ROWS", tile)
    monkeypatch.setattr(histogram, "CHUNK_ROWS", rows)
    Xb, fids, ordered = operands(n, d, chunk, m, bins, S, **kw)
    assert Xb.shape[1] == {300: 384, 3000: 3072}[d] and histogram.takes(n, Xb.shape[1], S, m, bins)
    n_counted = int(ordered[3])
    if case == "no_row_counts":
        assert n_counted == 0
    if case == "every_row_counts":
        assert n_counted == n
    if case == "a_segment_over_three_tiles":
        assert int(jnp.sum(ordered[0] == 5)) == 200 > 3 * tile
    if case == "bootstrap_counts_up_to_255":
        assert float(jnp.max(ordered[2])) > 200
    want = np.asarray(jax.jit(lambda X, f, *o: trees._sorted_histogram(X, *o, f, bins=bins))(Xb, fids, *ordered))
    got = np.asarray(jax.jit(lambda X, f, *o: histogram.sorted_histogram(X, *o, f, bins=bins, interpret=True))(Xb, fids, *ordered))
    assert got.shape == (S, chunk, m, bins) and got.dtype == want.dtype
    assert np.array_equal(got, want)
    if case == "a_node_with_no_counted_row":
        assert not got[:, 9].any() and got[:, 10].any()
    # every counted row is in it once a feature of its node
    assert got.sum() == float(jnp.sum(ordered[2])) * m


def test_what_the_kernel_takes():
    """Whole stripes and lane tiles of uint8 bin ids, a node's features in
    one window, a carried block that VMEM holds; anything else is the jnp
    form's."""
    assert histogram.takes(393_216, 3072, 2, 54, 128)  # the protocol's shape
    assert not histogram.takes(393_216, 3000, 2, 54, 128)  # columns not in whole lane tiles
    assert not histogram.takes(393_215, 3072, 2, 54, 128)  # rows not in whole stripes
    assert not histogram.takes(393_216, 3072, 2, 54, 512)  # uint16 bins
    assert not histogram.takes(393_216, 3072, 2, 3000, 128)  # every feature a node: no window holds them
    assert not histogram.takes(393_216, 3072, 100, 54, 256)  # a hundred classes' block over the budget
    assert [histogram._node_lanes(m) for m in (1, 16, 17, 54, 64, 65, 128)] == [16, 16, 32, 64, 64, 128, 128]
