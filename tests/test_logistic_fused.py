#
# The fused pass of the binomial L-BFGS loop (ops/logistic.py `_glm_step`,
# `srml_glm_step_f32`): one visit of each tile of X gives the direction's
# logits and Xᵀ·r at the first `GLM_SPECULATED` step candidates.
#
#   (a) with the kernel interpreted, the fused fit accepts the step the
#       two-product fit accepts in every iteration, counts the same
#       iterations and agrees in its answers to float32 rounding; a miss
#       (the search picks past the speculated window) costs one more trip and
#       no iteration; `fused_hits_` counts the first-trip hits; a search in
#       which nothing passes still ends `stalled_`;
#   (b) one pass against `X @ d` and `X.T @ R` written out, at shapes that
#       are no multiple of the tile or of 128, and the shapes it leaves to
#       the two products;
#   (c) the paths that must not engage keep the two products and their
#       results.
#
import numpy as np
import pandas as pd
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_ml_tpu import checkpoint as ckpt
from spark_rapids_ml_tpu import core, telemetry
from spark_rapids_ml_tpu.models.classification import LogisticRegression
from spark_rapids_ml_tpu.ops import distance
from spark_rapids_ml_tpu.ops import logistic as L
from spark_rapids_ml_tpu.ops.owlqn import lbfgs_two_loop

C = L.GLM_SPECULATED
PROBLEMS = ["plain", "unscaled_no_intercept", "far_backoff", "unstandardized", "row_weights"]
MISS_FIRST = ("unscaled_no_intercept", "far_backoff")
ALPHAS = [2.0] + [0.5 ** i for i in range(11)]  # `_glm_qn_setup`'s candidates
ITERATIONS = 8  # while the steps still mean something: near float32's floor a search follows rounding


@pytest.fixture
def kernel_calls(monkeypatch):
    """Interpreted kernels for this test, plus a count of the `pallas_call`s
    it traced: a jit cache hit on an earlier two-product trace of the same
    shapes would make a test pass without touching the kernel."""
    calls = []
    real = distance._call_params

    def counting(interpret):
        calls.append(interpret)
        return real(interpret)

    monkeypatch.setattr(distance, "_call_params", counting)
    monkeypatch.setattr(distance, "_MODE", "interpret")
    L.logistic_fit.clear_cache()  # a cached trace of either kind traces nothing
    return calls


@pytest.fixture
def tele():
    telemetry.registry().reset()
    telemetry.enable()
    yield telemetry.registry()
    telemetry.disable()
    telemetry.registry().reset()


# ------------------------------------------------------------- problems -----


def _problem(name):
    """(X [n, d] f32, y [n] int32, w [n] f32, statics) of a binomial fit.
    n = 700 is no multiple of the tile (512 rows: two visits, the second
    ragged) and d no multiple of 128."""
    rng = np.random.default_rng(11)
    n, d = 700, 24
    x = rng.normal(size=(n, d)).astype(np.float32)
    beta = rng.normal(size=d)
    y = (x @ beta + rng.normal(size=n) > 0).astype(np.int32)
    w = np.ones(n, np.float32)
    statics = dict(fit_intercept=True, standardize=True)
    if name == "unscaled_no_intercept":
        # steepest descent from 0 on columns 40 times too wide: the first
        # search backs off to 1/16, past the speculated window; the later ones do not
        x *= 40.0
        statics = dict(fit_intercept=False, standardize=False)
    elif name == "far_backoff":
        # 300 times: the first search accepts the LAST candidate (2^-10), so
        # the second trip's window runs past the list's end
        x *= 300.0
        statics = dict(fit_intercept=True, standardize=False)
    elif name == "unstandardized":
        x[:, ::2] *= 3.0
        statics = dict(fit_intercept=True, standardize=False)
    elif name == "row_weights":
        w = rng.uniform(0.0, 2.0, size=n).astype(np.float32)
        w[::7] = 0.0
    else:
        assert name == "plain"
    return jnp.asarray(x), jnp.asarray(y), jnp.asarray(w), statics


def _trajectory(X, y, w, *, fused, fit_intercept, standardize, lam_l2=1e-3, max_iter=ITERATIONS, tol=0.0):
    """`logistic_fit`'s loop stepped from the host: the accepted step of
    every iteration (read back from the iterate's move along the direction
    the state gives), the trips the loop made, and the final state."""
    d = X.shape[1]
    mu, d_scale, total_w = L._make_scaling(X, w, standardize, fit_intercept)
    matvec, rmat = L._dense_ops(X)
    step = (lambda *a: L._glm_step(X, *a, interpret=True)) if fused else None
    prob = L._build_glm_problem(
        matvec, rmat, X.dtype, d, y, w, mu, d_scale, total_w,
        k=2, multinomial=False, lam_l2=lam_l2, fit_intercept=fit_intercept, step=step,
    )
    cond, body, state = L._glm_qn_setup(
        prob["z_of"], prob["rowloss"], prob["rowloss_alphas"], prob["grad_from_z"],
        (X.shape[0], 1), prob["n_flat"], X.dtype, prob["penalty_terms"], max_iter, tol,
        step_of=prob["step_of"],
    )
    body = jax.jit(body)
    steps, trips = [], 0
    while bool(cond(state)):
        x, _, g, S, Y, rho, (count, pos) = state[:7]
        new = body(state)
        trips += 1
        if int(new[9]) > int(state[9]) and not bool(new[10]):
            dirn = lbfgs_two_loop(g, S, Y, rho, count, pos, 10)
            dirn = jnp.where(jnp.dot(g, dirn) < 0, dirn, -g)
            a = float(jnp.dot(new[0] - x, dirn) / jnp.dot(dirn, dirn))
            steps.append(min(ALPHAS, key=lambda c: abs(c - a)))
            assert abs(steps[-1] - a) < 1e-3 * steps[-1]
        state = new
    return steps, trips, state


@pytest.mark.parametrize("name", PROBLEMS)
def test_the_fused_loop_accepts_the_steps_the_two_products_accept(kernel_calls, name):
    X, y, w, statics = _problem(name)
    steps, trips, two = _trajectory(X, y, w, fused=False, **statics)
    assert not kernel_calls
    f_steps, f_trips, fused = _trajectory(X, y, w, fused=True, **statics)
    assert kernel_calls  # the loop's body traced the kernel
    assert f_steps == steps and len(steps) == ITERATIONS
    assert int(fused[9]) == int(two[9]) == ITERATIONS  # `it`: accepted iterations only
    speculated = [a in ALPHAS[:C] for a in steps]
    assert int(fused[12]) == sum(speculated)  # first-trip hits
    assert f_trips == trips + speculated.count(False)  # a miss: one more trip, no iteration
    if name in MISS_FIRST:
        assert not speculated[0] and all(speculated[1:])  # misses first, hits later
        assert steps[0] == {"unscaled_no_intercept": 0.5 ** 4, "far_backoff": ALPHAS[-1]}[name]
    else:
        assert all(speculated)
    np.testing.assert_allclose(fused[0], two[0], rtol=1e-5, atol=1e-6)  # the iterate
    np.testing.assert_allclose(fused[8], two[8], rtol=1e-5)  # the objective


@pytest.mark.parametrize("name", PROBLEMS)
def test_the_fused_fit_returns_what_the_two_product_fit_returns(kernel_calls, name):
    X, y, w, statics = _problem(name)
    kw = dict(k=2, multinomial=False, lam_l2=1e-3, max_iter=ITERATIONS, tol=0.0, **statics)
    two = L.logistic_fit(X, y, w, **kw)
    assert not kernel_calls and "fused_hits_" not in two
    fused = L.logistic_fit(X, y, w, glm_pass=L.GLM_FUSED_INTERPRET, **kw)
    assert kernel_calls
    assert int(fused["n_iter_"]) == int(two["n_iter_"]) == ITERATIONS
    assert int(fused["fused_hits_"]) == ITERATIONS - (name in MISS_FIRST)
    scale = float(jnp.abs(two["coef_"]).max())
    np.testing.assert_allclose(fused["coef_"], two["coef_"], rtol=1e-5, atol=1e-5 * scale)
    np.testing.assert_allclose(fused["intercept_"], two["intercept_"], rtol=1e-5, atol=1e-5 * scale)
    np.testing.assert_allclose(fused["objective_"], two["objective_"], rtol=1e-5)


def test_a_search_in_which_no_candidate_passes_still_ends_stalled(kernel_calls):
    """Columns 3,000 times too wide and unstandardized (the KNOWN LIMIT of
    `_glm_qn_setup`): steepest descent from 0 wants a step far under the
    smallest candidate, nothing passes, and the fit ends where it began, on
    both paths, with no hit and no second trip."""
    X, y, w, _ = _problem("plain")
    kw = dict(k=2, multinomial=False, lam_l2=1e-3, max_iter=ITERATIONS, tol=0.0,
              fit_intercept=True, standardize=False)
    two = L.logistic_fit(X * 3000.0, y, w, **kw)
    fused = L.logistic_fit(X * 3000.0, y, w, glm_pass=L.GLM_FUSED_INTERPRET, **kw)
    assert kernel_calls
    for state in (two, fused):
        assert bool(state["stalled_"]) and int(state["n_iter_"]) == 1
        assert not np.asarray(state["coef_"]).any() and float(state["objective_"]) == pytest.approx(np.log(2.0))
    assert int(fused["fused_hits_"]) == 0


# ---------------------------------------------------------- (b) one pass ----


@pytest.mark.parametrize("n, d", [(300, 40), (1100, 200), (1024, 24), (2500, 8)])
def test_one_pass_gives_the_logits_and_every_candidates_product(kernel_calls, n, d):
    rng = np.random.default_rng(n + d)
    X = rng.normal(size=(n, d)).astype(np.float32)
    dv = (rng.normal(size=d) / np.sqrt(d)).astype(np.float32)
    z_p = rng.normal(size=n).astype(np.float32)
    y = (rng.uniform(size=n) > 0.5).astype(np.float32)
    ws = (rng.uniform(0.0, 2.0, size=n) / n).astype(np.float32)
    a = np.asarray(ALPHAS[3 : 3 + C], np.float32)  # a window that starts past the first candidate
    assert L.glm_pass_of(jnp.asarray(X), multinomial=False, use_l1=False, fast=False) == L.GLM_FUSED_INTERPRET
    z_d, G = L._glm_step(*map(jnp.asarray, (X, dv, np.float32(0.25), z_p, y, ws, a)), interpret=True)
    assert kernel_calls == [True] and z_d.shape == (n,) and G.shape == (C, d)
    X64 = X.astype(np.float64)
    want_z = X64 @ dv + 0.25
    R = ws[:, None] * (1.0 / (1.0 + np.exp(-(z_p[:, None] + a[None, :] * want_z[:, None]))) - y[:, None])
    want_G = (X64.T @ R).T
    assert np.abs(np.asarray(z_d) - want_z).max() <= 1e-6 * np.abs(want_z).max()
    for j in range(C):
        assert np.abs(np.asarray(G[j]) - want_G[j]).max() <= 1e-6 * np.abs(want_G[j]).max(), j


@pytest.mark.parametrize(
    "n, d, why",
    [(300, 30, "d is no whole register of columns"), (100, 40, "fewer rows than one register of lanes"),
     (300, 40000, "no tile of rows fits VMEM beside the candidates' partials")],
)
def test_shapes_the_kernel_does_not_take_go_to_the_two_products(kernel_calls, n, d, why):
    X = jnp.zeros((n, d), jnp.float32)
    assert L.glm_pass_of(X, multinomial=False, use_l1=False, fast=False) == L.GLM_TWO_PRODUCTS, why
    assert L.glm_pass_of(X.astype(jnp.float64), multinomial=False, use_l1=False, fast=False) == L.GLM_TWO_PRODUCTS


def test_a_tile_of_rows_is_the_widest_that_fits(kernel_calls):
    assert L._glm_tile_rows(393_216, 3000) == 1024  # the cell's
    assert L._glm_tile_rows(700, 24) == 512 and L._glm_tile_rows(300, 40) == 256
    assert L._glm_tile_rows(393_216, 3780) == 1024 and L._glm_tile_rows(393_216, 6000) == 256
    assert L._glm_tile_rows(127, 8) is None


# -------------------------------------------- (c) paths that keep two products


def _frame(rng, n=400, d=16, classes=2):
    x = rng.normal(size=(n, d)).astype(np.float32)
    score = x @ rng.normal(size=(d, classes))
    label = (score[:, 0] > 0) if classes == 2 else score.argmax(axis=1)
    return pd.DataFrame({"features": list(x), "label": label.astype(np.float64)})


def _sparse_frame(rng, n=400, d=16):
    from spark_rapids_ml_tpu.linalg import Vectors

    x = rng.normal(size=(n, d)) * (rng.uniform(size=(n, d)) < 0.2)
    label = (x @ rng.normal(size=d) > 0).astype(np.float64)
    rows = [Vectors.sparse(d, np.flatnonzero(r).tolist(), r[np.flatnonzero(r)].tolist()) for r in x]
    return pd.DataFrame({"features": rows, "label": label})


def _estimator_fit(df, num_workers=1, **kw):
    return LogisticRegression(maxIter=8, regParam=1e-3, num_workers=num_workers, **kw).setFeaturesCol("features").setLabelCol("label").fit(df)


def _loop_span(model):
    (loop,) = [s for s in model._fit_metrics["spans"] if s["path"] == "fit/solve/loop"]
    return loop


def _through_the_estimator(rng, monkeypatch, path):
    if path == "multinomial":
        return lambda: _estimator_fit(_frame(rng, classes=3))
    if path == "ell":
        df = _sparse_frame(rng)
        return lambda: _estimator_fit(df)
    if path == "fast":
        monkeypatch.setitem(core.config, "solver_precision", "bf16")
        return lambda: _estimator_fit(_frame(rng))
    if path == "sharded":
        return lambda: _estimator_fit(_frame(rng), num_workers=4)
    if path == "elastic_net":
        return lambda: _estimator_fit(_frame(rng), elasticNetParam=0.5)
    assert path == "checkpointed"
    monkeypatch.setitem(core.config, "checkpoint_every_iters", 3)

    def fit():
        with ckpt.checkpoint_scope():
            return _estimator_fit(_frame(rng))

    return fit


@pytest.mark.parametrize("path", ["multinomial", "ell", "fast", "sharded", "elastic_net", "checkpointed"])
def test_paths_that_must_not_engage_keep_the_two_products(kernel_calls, tele, monkeypatch, path):
    """With the kernels on, each of these fits says `two_products`, traces no
    kernel, and returns bit for bit what it returns with the kernels off
    (which is the parent's program: nothing else reads the kernel mode)."""
    fit = _through_the_estimator(np.random.default_rng(5), monkeypatch, path)
    on = fit()
    loop = _loop_span(on)
    assert (loop["glm_pass"], loop["speculated"]) == ("two_products", 0)
    assert not kernel_calls and "logistic.fused_hits" not in on._fit_metrics["counters"]
    monkeypatch.setattr(distance, "_MODE", "jnp")
    off = _through_the_estimator(np.random.default_rng(5), monkeypatch, path)()
    np.testing.assert_array_equal(on.coef_, off.coef_)
    np.testing.assert_array_equal(on.intercept_, off.intercept_)
    assert on.n_iter_ == off.n_iter_ and on.objective_ == off.objective_


def test_the_batched_grid_keeps_the_two_products(kernel_calls):
    X, y, w, statics = _problem("plain")
    lams = jnp.asarray([1e-3, 1e-2], jnp.float32)
    kw = dict(k=2, multinomial=False, max_iter=6, tol=0.0, **statics)
    grid = L.logistic_fit_batched(X, y, w, lams, jnp.zeros_like(lams), **kw)
    assert not kernel_calls and "fused_hits_" not in grid
    one = L.logistic_fit(X, y, w, lam_l2=lams[1], **kw)
    np.testing.assert_allclose(grid["coef_"][1], one["coef_"], rtol=1e-5, atol=1e-6)


def test_the_binomial_fit_on_one_device_engages_and_counts_its_hits(kernel_calls, tele, monkeypatch):
    model = _estimator_fit(_frame(np.random.default_rng(5)))
    loop = _loop_span(model)
    assert (loop["glm_pass"], loop["speculated"], loop["solver_path"]) == ("fused", C, "dense")
    assert kernel_calls
    counters = model._fit_metrics["counters"]
    assert counters["logistic.iterations"] == model.n_iter_ == 8
    assert 0 < counters["logistic.fused_hits"] <= 8
    monkeypatch.setattr(distance, "_MODE", "jnp")
    plain = _estimator_fit(_frame(np.random.default_rng(5)))
    assert _loop_span(plain)["glm_pass"] == "two_products"
    np.testing.assert_allclose(model.coef_, plain.coef_, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(model.objective_, plain.objective_, rtol=1e-5)
