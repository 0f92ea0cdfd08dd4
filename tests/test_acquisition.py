"""LogEI stability tests (Ament et al. 2023 numerics) + properties."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st
from scipy.special import erfcx
from scipy.stats import norm

from repro.core.acquisition import ei, log_ei, log_h


def h_ref(z):
    """φ(z) + zΦ(z) with scipy (float64 reference)."""
    return norm.pdf(z) + z * norm.cdf(z)


def test_log_h_matches_reference_moderate():
    z = jnp.linspace(-8, 6, 200, dtype=jnp.float64)
    ours = np.asarray(log_h(z))
    ref = np.log(h_ref(np.asarray(z)))
    np.testing.assert_allclose(ours, ref, rtol=1e-6)


def test_log_h_extreme_negative_finite():
    """Direct evaluation underflows long before z=-30; log_h must not."""
    z = jnp.asarray([-10.0, -20.0, -50.0, -100.0, -1000.0], jnp.float64)
    out = np.asarray(log_h(z))
    assert np.all(np.isfinite(out))
    # asymptotic: log h(z) ≈ -z²/2 - log√(2π) - 2 log|z|
    approx = -z**2 / 2 - 0.5 * np.log(2 * np.pi) - 2 * np.log(-z)
    np.testing.assert_allclose(out, np.asarray(approx), rtol=1e-3)


def test_log_h_matches_erfcx_reference_across_branch():
    """Both branches of log_h, and the switch between them at z = -10,
    against a float64 reference that does not cancel catastrophically:
    h(z) = φ(z)·(1 − |z|·√(π/2)·erfcx(|z|/√2)), and d log h/dz = Φ/h."""
    z = np.concatenate([np.linspace(-25.0, -9.0, 161),
                        [-10.0 - 1e-7, -10.0 + 1e-7]])
    e = erfcx(np.abs(z) / np.sqrt(2.0))
    rest = 1.0 - np.abs(z) * np.sqrt(np.pi / 2.0) * e
    ref = -0.5 * z * z - 0.5 * np.log(2.0 * np.pi) + np.log(rest)
    grad_ref = np.sqrt(np.pi / 2.0) * e / rest
    zj = jnp.asarray(z, jnp.float64)
    # log h to 1e-10 absolute is h to 1e-10 relative
    np.testing.assert_allclose(np.asarray(log_h(zj)), ref, rtol=0,
                               atol=1e-10)
    np.testing.assert_allclose(
        np.asarray(jax.vmap(jax.grad(log_h))(zj)), grad_ref, rtol=1e-10)


def test_log_h_gradient_finite_everywhere():
    g = jax.vmap(jax.grad(log_h))(jnp.asarray(
        [-100.0, -6.0, -5.9999, -1.0, 0.0, 3.0], jnp.float64))
    assert np.all(np.isfinite(np.asarray(g)))


def test_logei_consistent_with_ei():
    mean = jnp.asarray([0.0, 0.5, -0.5, 2.0], jnp.float64)
    var = jnp.asarray([1.0, 0.25, 4.0, 0.01], jnp.float64)
    best = jnp.asarray(0.3, jnp.float64)
    np.testing.assert_allclose(
        np.asarray(jnp.exp(log_ei(mean, var, best))),
        np.asarray(ei(mean, var, best)), rtol=1e-6)


@settings(max_examples=30, deadline=None)
@given(mu=st.floats(-5, 5), best=st.floats(-5, 5),
       var=st.floats(1e-4, 10.0))
def test_property_logei_monotone_in_mean(mu, best, var):
    """LogEI increases with the posterior mean (all else equal)."""
    lo = log_ei(jnp.asarray(mu, jnp.float64), jnp.asarray(var, jnp.float64),
                jnp.asarray(best, jnp.float64))
    hi = log_ei(jnp.asarray(mu + 0.1, jnp.float64),
                jnp.asarray(var, jnp.float64),
                jnp.asarray(best, jnp.float64))
    assert float(hi) >= float(lo)


@settings(max_examples=30, deadline=None)
@given(mu=st.floats(-50, 50), best=st.floats(-50, 50),
       var=st.floats(1e-6, 100.0))
def test_property_logei_finite(mu, best, var):
    v = log_ei(jnp.asarray(mu, jnp.float64), jnp.asarray(var, jnp.float64),
               jnp.asarray(best, jnp.float64))
    assert np.isfinite(float(v))
