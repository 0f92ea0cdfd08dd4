"""Acquisition functions — numerically stable LogEI (Ament et al. 2023),
EI, and UCB — plus the batched-evaluation closure used by every MSO
strategy.

The paper's experiment setting (§5): LogEI over a GP with Matérn-5/2,
optimized by L-BFGS-B MSO.  ``make_logei`` returns the `(k, D) → (k,)`
batched acquisition the MSO drivers consume.
"""
from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp

from repro.gp.gpr import GPState, predict, predict_joint

Array = jax.Array

_C1 = 0.5 * math.log(2.0 * math.pi)          # log √(2π)
_INV_SQRT2PI = 1.0 / math.sqrt(2.0 * math.pi)


def _log_phi(z):
    return -0.5 * z * z - _C1


# Below this the direct branch would need φ(z) < 1e-22.  A TPU emulates
# float64 with float32's exponent range, where φ underflows below z ≈ -13
# and the direct branch returns -inf with NaN gradients.
_BRANCH = -10.0
# (−1)ᵏ(2k+1)!! for k = 1..16: the asymptotic series of h(z)·z²/φ(z) − 1
_ASYM = tuple((-1) ** k * math.prod(range(1, 2 * k + 2, 2))
              for k in range(1, 17))


def log_h(z: Array) -> Array:
    """log(φ(z) + z·Φ(z)) — the LogEI kernel, stable over all z.

    Branches (double-where guarded so gradients stay finite):
      z > -10  : direct  log(φ(z) + zΦ(z)) — the cancellation amplifies
                 erfc's rounding by φ/h ≈ z², to ~2e-12 relative near
                 z=-10 (f64);
      z ≤ -10  : asymptotic from Φ(z) ~ φ(z)/(−z)·Σ(−1)ᵏ(2k−1)!!/z²ᵏ:
                 log h = log φ − 2·log|z| + log1p(Σₖ (−1)ᵏ(2k+1)!! uᵏ),
                 u = 1/z², 16 terms (the next, 35!!·u¹⁷, is ≤1e-13 at
                 the branch point).
    """
    direct_side = z > _BRANCH
    z_safe_hi = jnp.maximum(z, _BRANCH)         # direct-branch input
    phi = jnp.exp(_log_phi(z_safe_hi))
    # erfc keeps Φ relatively accurate in the far tail (0.5·(1+erf) has
    # only absolute accuracy there, which the φ+zΦ cancellation amplifies)
    Phi = 0.5 * jax.lax.erfc(-z_safe_hi / jnp.sqrt(2.0).astype(z.dtype))
    direct_arg = jnp.maximum(phi + z_safe_hi * Phi, 1e-300)
    direct = jnp.log(direct_arg)

    # where, not minimum: at z == _BRANCH minimum splits the gradient
    # between z and the constant, and the asymptotic branch taken there
    # would get half of it
    z_safe_lo = jnp.where(direct_side, _BRANCH, z)   # asymptotic input
    u = 1.0 / (z_safe_lo * z_safe_lo)
    series = jnp.zeros_like(u)
    for c in reversed(_ASYM):                   # Horner: u·(c₁ + u·(c₂ …))
        series = u * (c + series)
    asym = (_log_phi(z_safe_lo) - 2.0 * jnp.log(-z_safe_lo)
            + jnp.log1p(series))
    return jnp.where(direct_side, direct, asym)


def log_ei(mean: Array, var: Array, best: Array) -> Array:
    """log E[max(0, μ − best)] under N(μ, σ²) — maximization convention."""
    sigma = jnp.sqrt(var)
    z = (mean - best) / sigma
    return log_h(z) + 0.5 * jnp.log(var)


def ei(mean: Array, var: Array, best: Array) -> Array:
    sigma = jnp.sqrt(var)
    z = (mean - best) / sigma
    phi = jnp.exp(_log_phi(z))
    Phi = 0.5 * jax.lax.erfc(-z / jnp.sqrt(2.0).astype(z.dtype))
    return sigma * (phi + z * Phi)


def ucb(mean: Array, var: Array, beta: float = 2.0) -> Array:
    return mean + beta * jnp.sqrt(var)


AcqBatched = Callable[[Array], Array]   # (k, D) -> (k,)


def logei_acq(state, xb: Array) -> Array:
    """State-form LogEI for the MSO layer: ``state = (GPState, best)``.

    Module-level pure function ⇒ jit caches key on shapes only; the fitted
    GP flows through as a traced pytree (no per-trial recompilation).
    """
    gp, best = state
    mean, var = predict(gp, xb)
    return log_ei(mean, var, best)


def ucb_acq(state, xb: Array) -> Array:
    """State-form UCB: ``state = (GPState, beta)``."""
    gp, beta = state
    mean, var = predict(gp, xb)
    return mean + beta * jnp.sqrt(var)


def _log_softplus(x: Array) -> Array:
    """log(softplus(x)), stable over all x (→ x for x ≪ 0)."""
    sp = jax.nn.softplus(jnp.maximum(x, -30.0))
    return jnp.where(x < -30.0, x, jnp.log(sp + 1e-300))


def qlogei_acq(state, xb: Array, *, tau_max: float = 1e-2,
               tau_relu: float = 1e-3) -> Array:
    """Joint q-batch LogEI: ``state = (GPState, best, eps)``, xb (k, q, D).

    MC qLogEI in the smoothed formulation of Ament et al. 2023: for each
    candidate block the joint posterior over its q points is sampled with
    *fixed* base draws ``eps`` (S, q) — common random numbers keep the
    surface deterministic and differentiable for the QN optimizers — and
    the max over the q points / relu are softened by ``logsumexp`` /
    ``softplus`` so gradients reach every batch element:

        qLogEI ≈ log E_s[ τ_r·softplus( τ_m·logsumexp((f_s − best)/τ_m) / τ_r ) ]

    Module-level pure function (paired with per-call ``eps`` passed inside
    ``state``) ⇒ the engine's jit cache keys on shapes only.
    """
    gp, best, eps = state

    def one(xq):                                   # (q, D) -> ()
        mean, cov = predict_joint(gp, xq)
        Lc = jnp.linalg.cholesky(cov)
        samples = mean[None, :] + eps @ Lc.T       # (S, q)
        z = samples - best
        smax = tau_max * jax.scipy.special.logsumexp(z / tau_max, axis=-1)
        log_ei_s = jnp.log(tau_relu) + _log_softplus(smax / tau_relu)
        S = eps.shape[0]
        return jax.scipy.special.logsumexp(log_ei_s) - jnp.log(float(S))

    return jax.vmap(one)(xb)


def qlogei_state(gp: GPState, best, q: int, *, n_samples: int = 64,
                 seed: int = 0):
    """Build the ``(gp, best, eps)`` state tuple for ``qlogei_acq``."""
    eps = jax.random.normal(jax.random.PRNGKey(seed), (n_samples, q),
                            gp.y_train.dtype)
    return (gp, jnp.asarray(best, gp.y_train.dtype), eps)


def make_logei(gp: GPState, best: float) -> AcqBatched:
    """LogEI closure over a fitted GP (y standardized, maximization scale)."""
    best = jnp.asarray(best, gp.y_train.dtype)

    def acq(xb: Array) -> Array:
        mean, var = predict(gp, xb)
        return log_ei(mean, var, best)

    return acq


def make_ucb(gp: GPState, beta: float = 2.0) -> AcqBatched:
    def acq(xb: Array) -> Array:
        mean, var = predict(gp, xb)
        return ucb(mean, var, beta)

    return acq
