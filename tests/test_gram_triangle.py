#
# The float32 gram as panels of its block upper triangle (ISSUE 35):
# `ops/linalg.py` `_gram_panels`, `_mirror`, `gram_panels`, under
# `centered_gram` and `centered_moments`.
#
#   (a) both against float64 numpy and against the one-contraction form, over
#       d (not a multiple of the panel width, equal to it, under it), one tile
#       / the tile loop / a ragged last tile, centred or about zero, `fast` on
#       and off, weights with zero-weight padding rows;
#   (b) the row-sharded `shard_map` arm on the CPU mesh gives the one-device
#       answer;
#   (c) the program at the cells' shape, traced abstractly: the contraction
#       FLOP, one loop, nothing of X's size written;
#   (d) up to one panel's width, and under `fast` at any width, the lowered
#       program is the single contraction's, character for character;
#   (e) the strictly-lower blocks are bit for bit the transposes of the upper.
#
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu.ops import linalg, linear, pca

TILE, PANEL = 40, 24


@pytest.fixture
def small_tiles(gram_constants):
    """`GRAM_TILE_ROWS` 40 and `GRAM_PANEL_COLS` 24; gives the patcher."""
    gram_constants(tile_rows=TILE, panel_cols=PANEL)
    return gram_constants


def rows(seed, n, d, padding=5):
    """n rows of which the last `padding` are zero rows of weight 0; every
    column correlated with every other, a mean away from 0."""
    rng = np.random.default_rng(seed)
    X = (rng.standard_normal((n, d)) + 3.0 * rng.standard_normal((n, 1)) + rng.standard_normal(d)).astype(np.float32)
    y = (X @ rng.standard_normal(d) / np.sqrt(d) + rng.standard_normal(n)).astype(np.float32)
    w = (0.5 + rng.random(n)).astype(np.float32)
    X[n - padding :], y[n - padding :], w[n - padding :] = 0.0, 0.0, 0.0
    return X, y, w


def float64_moments(X, y, w, x_mean, y_mean):
    X, y, w = (a.astype(np.float64) for a in (X, y, w))
    xc, yc = X - np.asarray(x_mean, np.float64), y - float(y_mean)
    return np.einsum("nd,n,ne->de", xc, w, xc), np.einsum("nd,n->d", xc, w * yc), np.sum(w * yc * yc)


def blocks_mirrored(G, c):
    """Every strictly-lower block of G equals, bit for bit, the transpose of
    the upper block across the diagonal."""
    d = G.shape[0]
    return all(
        np.array_equal(G[j : j + c, i : i + c], G[i : i + c, j : j + c].T)
        for i in range(0, d, c) for j in range(i + c, d, c)
    )


# ------------------------------------------- (a) against float64, and whole -


@pytest.mark.parametrize("fast", [False, True], ids=["f32", "bf16"])
@pytest.mark.parametrize("center", [True, False], ids=["centred", "about0"])
@pytest.mark.parametrize("n", [TILE, 3 * TILE, 3 * TILE + 10], ids=["one_tile", "tile_loop", "ragged_tile"])
@pytest.mark.parametrize("d", [70, 48, PANEL, 20], ids=["d70", "d48", "d24", "d20"])
def test_panels_against_float64_and_the_one_contraction(small_tiles, d, n, center, fast):
    X, y, w = rows(d + n, n, d)
    x_mean = (w @ X / w.sum()).astype(np.float32) if center else np.zeros(d, np.float32)
    y_mean = np.float32(w @ y / w.sum()) if center else np.float32(0)
    want = float64_moments(X, y, w, x_mean, y_mean)

    def both():
        with jax.enable_x64(False):
            gram = jax.jit(lambda *a: linalg.centered_gram(*a, fast=fast))(X, w, x_mean)
            moments = jax.jit(lambda *a: linalg.centered_moments(*a, fast=fast))(X, y, w, x_mean, y_mean)
        return np.asarray(gram), [np.asarray(m) for m in moments]

    panels, cols = linalg.gram_panels(d, fast)
    assert (panels, cols) == ((1, d) if fast or d <= PANEL else (-(-d // PANEL), PANEL))
    gram, moments = both()
    small_tiles(panel_cols=4096)  # one panel: the whole contraction, the same tiles
    assert linalg.gram_panels(d, fast) == (1, d)
    whole_gram, whole_moments = both()

    scale = np.max(np.abs(want[0]))
    tol = 2e-2 if fast else 2e-6  # bf16 inputs: 8 bits; float32: a few ulp of the largest entry over 130 rows
    assert gram.shape == (d, d) and np.array_equal(gram, moments[0])  # one function under both
    np.testing.assert_allclose(gram, want[0], rtol=0, atol=tol * scale)
    np.testing.assert_allclose(moments[1], want[1], rtol=0, atol=2e-6 * np.max(np.abs(want[1])))
    np.testing.assert_allclose(moments[2], want[2], rtol=2e-6)
    # the entries kept are the whole contraction's (a panel's contraction is the same rows of the same sum)
    np.testing.assert_allclose(gram, whole_gram, rtol=0, atol=(0 if panels == 1 else 5e-7 * scale))
    np.testing.assert_array_equal(moments[1], whole_moments[1])
    np.testing.assert_array_equal(moments[2], whole_moments[2])
    assert blocks_mirrored(gram, cols)


# --------------------------------------------------- (b) under shard_map ----


@pytest.mark.parametrize("center", [True, False], ids=["centred", "about0"])
def test_the_row_sharded_arm_gives_the_one_device_answer(small_tiles, center):
    """Eight shards of 130 rows: each device runs the tile loop over its own
    panels, the mirrored [d, d] is what the `psum` adds."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from spark_rapids_ml_tpu.parallel import mesh as mesh_mod

    workers, d = 8, 70
    X, y, w = rows(3, workers * (3 * TILE + 10), d, padding=13)
    mesh = mesh_mod.get_mesh(workers)
    rows_on = NamedSharding(mesh, P(mesh_mod.ROWS_AXIS))
    Xd = jax.device_put(X, NamedSharding(mesh, P(mesh_mod.ROWS_AXIS, None)))
    yd, wd = jax.device_put(y, rows_on), jax.device_put(w, rows_on)
    with jax.enable_x64(False):
        sharded = linear._dense_stats(Xd, yd, wd, fit_intercept=center, mesh=mesh)
        text = linear._dense_stats.lower(Xd, yd, wd, fit_intercept=center, mesh=mesh).as_text()
        cov_sharded = pca._pca_stats(Xd, wd, mesh=mesh)
        one = linear._dense_stats(X, y, w, fit_intercept=center)
        cov_one = pca._pca_stats(X, w)
    assert ("shard_map" in text or "manual" in text) and "while" in text
    scale = float(np.max(np.abs(one[3])))
    for name, a, b in zip(linear._STATS_NAMES, sharded, one):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6 * scale, err_msg=name)
    for a, b in zip(cov_sharded, cov_one):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-6 * scale / len(X))
    assert blocks_mirrored(np.asarray(sharded[3]), PANEL) and blocks_mirrored(np.asarray(cov_sharded[2]), PANEL)
    want = float64_moments(X, y, w, one[1], one[2])
    np.testing.assert_allclose(np.asarray(sharded[3]), want[0], rtol=0, atol=2e-6 * scale)


# ------------------------------------- (c) the program at the cells' shape --


def _walk(jaxpr, times=1):
    """(equation, how often it runs) over a jaxpr and what it calls; a `scan`
    runs its body `length` times (a `fori_loop` with static bounds is one)."""
    for eqn in jaxpr.eqns:
        yield eqn, times
        inner = times * eqn.params["length"] if eqn.primitive.name == "scan" else times
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk(sub, inner)


def _contraction_flop(eqn):
    contract = eqn.params["dimension_numbers"][0][0]  # the left operand's contracted axes
    rows_contracted = np.prod([eqn.invars[0].aval.shape[a] for a in contract], dtype=np.int64)
    return 2 * int(rows_contracted) * int(np.prod(eqn.outvars[0].aval.shape, dtype=np.int64))


@pytest.mark.parametrize("program", ["pca", "linreg"])
def test_the_cells_program_contracts_the_triangle_in_one_loop(program):
    """`_pca_stats` and `_dense_stats` traced abstractly at [393,216, 3,000]
    float32 with the real constants: the matrix-matrix contractions do under
    0.70 of 2nd² FLOP (0.584 at 512 columns), inside one loop, and no value
    of X's size is made."""
    n, d = 393216, 3000
    X, v = jax.ShapeDtypeStruct((n, d), jnp.float32), jax.ShapeDtypeStruct((n,), jnp.float32)
    with jax.enable_x64(False):
        if program == "pca":
            jaxpr = jax.make_jaxpr(lambda X, w: pca._pca_stats(X, w))(X, v)
            text = pca._pca_stats.lower(X, v).as_text()
        else:
            jaxpr = jax.make_jaxpr(lambda X, y, w: linear._dense_stats(X, y, w))(X, v, v)
            text = linear._dense_stats.lower(X, v, v).as_text()
    panels, cols = linalg.gram_panels(d)
    assert panels > 1 and panels == -(-d // cols)
    flop, loops, largest = 0, 0, 0
    for eqn, times in _walk(jaxpr.jaxpr):
        loops += eqn.primitive.name in ("scan", "while")
        largest = max([largest] + [int(np.prod(o.aval.shape, dtype=np.int64)) for o in eqn.outvars])
        if eqn.primitive.name == "dot_general" and min(len(i.aval.shape) for i in eqn.invars) == 2:
            flop += times * _contraction_flop(eqn)
    share = flop / (2 * n * d * d)
    work = sum(min(cols, d - s) * (d - s) for s in range(0, d, cols)) / d**2
    assert share == pytest.approx(work, rel=1e-9) and 0.5 < share < 0.70, (share, work)
    assert loops == 1 and text.count("stablehlo.while") == 1
    assert largest < n * d // 8, largest  # a tile's centred rows at most: nothing of X's size


# ------------------------------ (d) the narrow and the bf16 program stay ----


def _parent_gram(X, w, mean, fast):
    """`centered_gram` as it was before the panels: one contraction a tile."""

    def tile(xb, wb):
        xc = xb - mean
        if fast:
            xcw = xc * wb[:, None]
            return jnp.einsum(
                "nd,ne->de", xcw.astype(jnp.bfloat16), xc.astype(jnp.bfloat16), preferred_element_type=jnp.float32,
            ).astype(X.dtype)
        return jnp.einsum("nd,n,ne->de", xc, wb, xc)

    return linalg._sum_over_row_tiles(tile, (X, w), at_once=fast)


@pytest.mark.parametrize(
    "n,d,fast", [(TILE, PANEL, False), (3 * TILE + 10, PANEL, False), (3 * TILE + 10, 20, False), (3 * TILE + 10, 70, True)],
    ids=["one_tile", "tile_loop_d24", "tile_loop_d20", "bf16_d70"],
)
def test_up_to_one_panel_the_lowered_program_is_the_single_einsum(small_tiles, n, d, fast):
    X, w, mean = (jax.ShapeDtypeStruct(s, jnp.float32) for s in ((n, d), (n,), (d,)))

    def gram(X, w, mean):
        return linalg.centered_gram(X, w, mean, fast=fast)

    now = jax.jit(gram).lower(X, w, mean).as_text()

    def gram(X, w, mean):  # noqa: F811 - the same name, so that the two modules are named alike
        return _parent_gram(X, w, mean, fast)

    assert now == jax.jit(gram).lower(X, w, mean).as_text()
    assert ("while" in now) == (n > TILE and not fast)


def test_wider_than_a_panel_the_program_differs(small_tiles):
    """The test above can tell: at d = 70 the float32 program is not the
    single contraction's (three panels a tile)."""
    X, w, mean = (jax.ShapeDtypeStruct(s, jnp.float32) for s in ((130, 70), (130,), (70,)))
    now = jax.jit(lambda X, w, mean: linalg.centered_gram(X, w, mean)).lower(X, w, mean).as_text()
    was = jax.jit(lambda X, w, mean: _parent_gram(X, w, mean, False)).lower(X, w, mean).as_text()
    assert now != was and now.count("dot_general") == 3 * was.count("dot_general")


# ------------------------------------------------- (e) the lower triangle ---


@pytest.mark.parametrize("d", [70, 48, 25])
def test_mirror_writes_each_panel_where_it_lies(d):
    """`_mirror` alone on panels of distinct numbers: the upper blocks are the
    panels, the strictly-lower blocks their transposes, nothing is left 0."""
    c = PANEL
    full = np.arange(1, d * d + 1, dtype=np.float32).reshape(d, d)
    panels = tuple(jnp.asarray(full[s : s + c, s:]) for s in range(0, d, c))
    got = np.asarray(linalg._mirror(panels))
    upper = np.triu(np.ones((d, d), bool))
    for s in range(0, d, c):
        upper[s : s + c, s : s + c] = True  # the diagonal blocks are kept whole
    assert np.array_equal(got[upper], full[upper])
    assert np.array_equal(got[~upper], full.T[~upper])
    assert blocks_mirrored(got, c) and np.all(got > 0)
    whole = jnp.asarray(full)
    assert linalg._mirror((whole,)) is whole  # one panel is the matrix
