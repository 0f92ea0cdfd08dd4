"""GP substrate tests: posterior math, masked LML, padding exactness,
hyperparameter fit sanity, property-based invariants."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.gp.fit import fit_gp, standardize
from repro.gp.gpr import (GPState, fit_gram, log_marginal_likelihood,
                          log_marginal_likelihood_masked, pad_gp, predict)
from repro.gp.kernels import KernelParams, gram, init_params, matern52


def _data(n=24, d=3, seed=0):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.uniform(0, 1, (n, d)))
    y = jnp.sin(3 * X).sum(1) + 0.05 * jnp.asarray(
        rng.standard_normal(n))
    return X, y


def test_gram_spd_and_symmetric():
    X, _ = _data()
    p = init_params(X.shape[1])
    K = gram(X, p)
    np.testing.assert_allclose(K, K.T, atol=1e-12)
    w = np.linalg.eigvalsh(np.asarray(K))
    assert w.min() > 0


def test_posterior_interpolates_noiseless():
    X, y = _data(16)
    p = init_params(X.shape[1])._replace(
        log_noise=jnp.asarray(-14.0))
    gp = fit_gram(X, y, p)
    mean, var = predict(gp, X)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(y), atol=1e-4)
    assert float(jnp.max(var)) < 1e-4


def test_posterior_reverts_to_prior_far_away():
    X, y = _data(16)
    p = init_params(X.shape[1])
    gp = fit_gram(X, y, p)
    far = jnp.full((1, X.shape[1]), 100.0)
    mean, var = predict(gp, far)
    np.testing.assert_allclose(float(mean[0]), 0.0, atol=1e-8)
    np.testing.assert_allclose(float(var[0]), float(p.amplitude),
                               rtol=1e-6)


def test_masked_lml_equals_exact():
    X, y = _data(20)
    p = init_params(X.shape[1])
    exact = log_marginal_likelihood(X, y, p)
    n_pad = 12
    Xp = jnp.concatenate([X, jnp.full((n_pad, X.shape[1]), 1e6)
                          + jnp.arange(n_pad)[:, None]], 0)
    yp = jnp.concatenate([y, jnp.zeros(n_pad)])
    valid = jnp.arange(20 + n_pad) < 20
    masked = log_marginal_likelihood_masked(Xp, yp, valid, p)
    np.testing.assert_allclose(float(masked), float(exact), rtol=1e-10)


def test_padded_fit_predict_exact():
    """fit_gp's padded GPState predicts identically to an unpadded fit."""
    X, y = _data(21)          # deliberately not a bucket multiple
    gp_pad = fit_gp(X, y, n_restarts=1, pad_bucket=32)
    gp_exact = fit_gram(X, y, gp_pad.params)
    Xq = jnp.asarray(np.random.default_rng(1).uniform(0, 1, (7, 3)))
    m1, v1 = predict(gp_pad, Xq)
    m2, v2 = predict(gp_exact, Xq)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), atol=1e-10)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-10)


def test_pad_gp_utility_exact():
    X, y = _data(18)
    p = init_params(X.shape[1])
    gp = fit_gram(X, y, p)
    gpp = pad_gp(gp, 32)
    Xq = jnp.asarray(np.random.default_rng(2).uniform(0, 1, (5, 3)))
    m1, v1 = predict(gp, Xq)
    m2, v2 = predict(gpp, Xq)
    np.testing.assert_allclose(np.asarray(m1), np.asarray(m2), atol=1e-10)
    np.testing.assert_allclose(np.asarray(v1), np.asarray(v2), atol=1e-10)


def test_fit_improves_lml():
    X, y = _data(32, seed=3)
    init = init_params(X.shape[1])
    gp = fit_gp(X, y, n_restarts=2)
    lml_init = log_marginal_likelihood(X, y, init)
    lml_fit = log_marginal_likelihood(X, y, gp.params)
    assert float(lml_fit) > float(lml_init)


def test_standardize():
    y = jnp.asarray([1.0, 2.0, 3.0, 10.0])
    ys, mu, sd = standardize(y)
    np.testing.assert_allclose(float(jnp.mean(ys)), 0.0, atol=1e-12)
    np.testing.assert_allclose(float(jnp.std(ys)), 1.0, atol=1e-12)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 1000), n=st.integers(3, 30))
def test_property_variance_nonnegative_and_bounded(seed, n):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.uniform(-2, 2, (n, 2)))
    y = jnp.asarray(rng.standard_normal(n))
    gp = fit_gram(X, y, init_params(2))
    Xq = jnp.asarray(rng.uniform(-3, 3, (16, 2)))
    _, var = predict(gp, Xq)
    assert float(jnp.min(var)) >= 0.0
    assert float(jnp.max(var)) <= float(gp.params.amplitude) + 1e-6


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 1000))
def test_property_matern_kernel_bounds(seed):
    """0 < k(x,x') ≤ σ², k(x,x) == σ²."""
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.uniform(-5, 5, (10, 4)))
    p = init_params(4)
    K = matern52(X, X, p)
    amp = float(p.amplitude)
    assert float(jnp.min(K)) > 0.0
    assert float(jnp.max(K)) <= amp * (1 + 1e-9)
    np.testing.assert_allclose(np.asarray(jnp.diagonal(K)), amp, rtol=1e-6)
