"""The plain reference the benchmark holds the served path to.

Host NumPy/SciPy, imports nothing of the program.  For one suggestion it
rebuilds the exact GP that the service's suggest saw (the study's live
observations on the unit cube, the minimized y standardized and negated,
Matérn-5/2 with ARD at hyperparameters θ) and evaluates LogEI there:

    K = k(X, X) + (σ_n² + 1e-8) I,     μ(x) = k(x, X) K⁻¹ y,
    σ²(x) = max(σ_f² − k(x, X) K⁻¹ k(X, x), 1e-16),
    LogEI(x) = log h((μ − y*) / σ) + log σ,   h(z) = φ(z) + z Φ(z),

with y* the best standardized observation.  θ is packed as in the
service's journal: D log lengthscales, log σ_f², log σ_n².

``dtype`` is float64 for the reference.  The control runs the same code
in float32, the next precision below what the deployment states.

Three judgements of what the program made, by the reference alone:

- :func:`projected_grad_inf`: the infinity norm of the projected
  gradient of −LogEI at a suggestion, the quantity the MSO's own
  stopping test (``pgtol``) bounds: an acquisition optimizer that
  stopped early leaves it large;
- :func:`logei_regret`: how much more LogEI the reference's own
  multistart L-BFGS-B finds than the suggestion has: one that ran too
  few restarts leaves it large on average;
- :func:`map_polish`: how much the MAP objective of the GP fit,

      −log p(y | X, θ) + ½‖ℓ/2‖² + ½(log σ_f²/2)² + ½((log σ_n² + 4)/2)²,

  over the box of ``THETA_BOUNDS``, still falls when SciPy's L-BFGS-B
  starts from the θ the program fitted: a refit that returned a stale or
  unfitted θ leaves it large.

Also here: a reader of the service's write-ahead journal (CRC-32 and a
sequence number per JSON line), used to read acknowledged asks and tells
back.
"""
from __future__ import annotations

import json
import math
import zlib
from typing import Dict, Iterator, List

import numpy as np
from scipy.linalg import cho_solve, cholesky, solve_triangular
from scipy.optimize import minimize
from scipy.special import erfcx, ndtr

SQRT5 = math.sqrt(5.0)
JITTER = 1e-8
VAR_FLOOR = 1e-16
# below this z, log h takes its asymptotic series; above, erfcx
Z_ASYMPTOTIC = -40.0
# (log lengthscale, log σ_f², log σ_n²) bounds of the GP fit
THETA_BOUNDS = ((-4.0, 4.0), (-6.0, 6.0), (-10.0, 2.0))
# central-difference step of the LogEI gradient, on the unit cube
FD_STEP = 1e-6


def matern52(x1: np.ndarray, x2: np.ndarray, log_ls: np.ndarray,
             amp) -> np.ndarray:
    """σ_f² (1 + √5 r + 5r²/3) exp(−√5 r), r = ‖(x − x')/ℓ‖."""
    inv = np.exp(-log_ls)
    diff = (x1[:, None, :] - x2[None, :, :]) * inv
    d2 = np.sum(diff * diff, axis=-1)
    r = np.sqrt(d2)
    return amp * (1.0 + SQRT5 * r + (5.0 / 3.0) * d2) * np.exp(-SQRT5 * r)


def log_h(z: np.ndarray) -> np.ndarray:
    """log(φ(z) + z Φ(z)).  For z < 0 through Φ(z) = φ(z)·√(π/2)·
    erfcx(|z|/√2), so h = φ(z)·(1 − |z|·√(π/2)·erfcx(|z|/√2)); below
    ``Z_ASYMPTOTIC`` the bracket's cancellation grows like z²·u, and the
    asymptotic series h ~ φ(z)/z²·(1 − 3/z² + 15/z⁴ − 105/z⁶) takes over
    (next term 945/z⁸ < 1e-9 there)."""
    z = np.asarray(z)
    dt = z.dtype.type
    log_phi = -0.5 * z * z - dt(0.5 * math.log(2.0 * math.pi))
    out = np.empty_like(z)
    pos = z >= 0
    zp = z[pos]
    out[pos] = np.log(np.exp(log_phi[pos]) + zp * ndtr(zp))
    mid = (z < 0) & (z >= Z_ASYMPTOTIC)
    a = -z[mid]
    out[mid] = log_phi[mid] + np.log1p(
        -a * dt(math.sqrt(math.pi / 2.0)) * erfcx(a / dt(math.sqrt(2.0))))
    far = z < Z_ASYMPTOTIC
    u = 1.0 / (z[far] * z[far])
    out[far] = (log_phi[far] + np.log(u)
                + np.log1p(u * (-3.0 + u * (15.0 - 105.0 * u))))
    return out


def unpack(theta, dim: int, dtype):
    theta = np.asarray(theta, dtype)
    return theta[:dim], np.exp(theta[dim]), np.exp(theta[dim + 1])


def standardized(y_obs, dtype=np.float64) -> np.ndarray:
    """The minimized y negated and standardized (population moments)."""
    y = -np.asarray(y_obs, dtype)
    sd = max(np.std(y), dtype(1e-10))
    return (y - np.mean(y)) / sd


def logei_fn(x_obs: np.ndarray, y_obs: np.ndarray, theta,
             dtype=np.float64):
    """LogEI of the exact GP of ``(x_obs, y_obs)`` (unit cube, raw
    minimized y) at θ, as a function of (q, D) query points; nats."""
    x_obs = np.asarray(x_obs, dtype)
    y_std = standardized(y_obs, dtype)
    log_ls, amp, noise = unpack(theta, x_obs.shape[1], dtype)
    K = matern52(x_obs, x_obs, log_ls, amp)
    K[np.diag_indices_from(K)] += noise + dtype(JITTER)
    L = cholesky(K, lower=True)
    alpha = cho_solve((L, True), y_std)
    best = np.max(y_std)

    def logei(x_query) -> np.ndarray:
        xq = np.atleast_2d(np.asarray(x_query, dtype))
        ks = matern52(xq, x_obs, log_ls, amp)
        mean = ks @ alpha
        v = solve_triangular(L, ks.T, lower=True)
        var = np.maximum(amp - np.sum(v * v, axis=0), dtype(VAR_FLOOR))
        z = (mean - best) / np.sqrt(var)
        return log_h(z) + 0.5 * np.log(var)
    return logei


def logei_at(x_obs: np.ndarray, y_obs: np.ndarray, theta, x_query,
             dtype=np.float64) -> np.ndarray:
    """LogEI of the exact GP of ``(x_obs, y_obs)`` at θ, at the (q, D)
    points ``x_query``; nats."""
    return logei_fn(x_obs, y_obs, theta, dtype)(x_query)


def _logei_value_grad(logei, x: np.ndarray):
    """LogEI at x and its gradient on the unit cube, by central
    differences whose points stay inside the cube."""
    d = x.shape[0]
    up = np.minimum(x + FD_STEP, 1.0)
    dn = np.maximum(x - FD_STEP, 0.0)
    pts = np.concatenate([x[None], np.tile(x, (2 * d, 1))])
    pts[1 + np.arange(d), np.arange(d)] = up
    pts[1 + d + np.arange(d), np.arange(d)] = dn
    f = logei(pts)
    return f[0], (f[1:1 + d] - f[1 + d:]) / (up - dn)


def projected_grad_inf(x_obs: np.ndarray, y_obs: np.ndarray, theta,
                       x_query) -> float:
    """For one suggestion ``x_query`` (unit cube): the infinity norm of
    the projected gradient of −LogEI there, ``x − P(x − ∇(−LogEI))``
    over [0, 1]^D, as L-BFGS-B's ``pgtol`` test reads it."""
    logei = logei_fn(x_obs, y_obs, theta)
    x0 = np.clip(np.asarray(x_query, np.float64), 0.0, 1.0)
    _, g = _logei_value_grad(logei, x0)
    return float(np.max(np.abs(x0 - np.clip(x0 + g, 0.0, 1.0))))


def _maximize(logei, x0: np.ndarray) -> float:
    """The LogEI that SciPy's L-BFGS-B over [0, 1]^D reaches from x0."""
    def neg(x):
        f, g = _logei_value_grad(logei, x)
        return -f, -g
    res = minimize(neg, x0, jac=True, method="L-BFGS-B",
                   bounds=[(0.0, 1.0)] * x0.shape[0],
                   options={"maxiter": 500, "ftol": 1e-15, "gtol": 1e-10})
    return -float(res.fun)


def logei_regret(x_obs: np.ndarray, y_obs: np.ndarray, theta, x_query,
                 starts: np.ndarray) -> float:
    """LogEI the reference's own multistart L-BFGS-B finds (from the
    suggestion, the best observation and ``starts``, all on the unit
    cube) above its LogEI at the suggestion ``x_query``; nats, ≥ 0."""
    logei = logei_fn(x_obs, y_obs, theta)
    xq = np.clip(np.asarray(x_query, np.float64), 0.0, 1.0)
    f0 = float(logei(xq)[0])
    x_obs = np.asarray(x_obs, np.float64)
    inc = x_obs[np.argmin(np.asarray(y_obs))]
    best = max(_maximize(logei, x0) for x0 in
               np.concatenate([xq[None], inc[None], starts]))
    return max(best - f0, 0.0)


def neg_log_posterior(theta, x_obs: np.ndarray, y_std: np.ndarray):
    """The GP fit's MAP objective at θ and its gradient in θ, for
    standardized targets ``y_std`` (see the module's docstring)."""
    theta = np.asarray(theta, np.float64)
    n, d = x_obs.shape
    log_ls, amp, noise = unpack(theta, d, np.float64)
    diff = (x_obs[:, None, :] - x_obs[None, :, :]) * np.exp(-log_ls)
    sq = diff * diff
    d2 = np.sum(sq, axis=-1)
    r = np.sqrt(d2)
    e = np.exp(-SQRT5 * r)
    kern = amp * (1.0 + SQRT5 * r + (5.0 / 3.0) * d2) * e
    K = kern.copy()
    K[np.diag_indices_from(K)] += noise + JITTER
    L = cholesky(K, lower=True)
    alpha = cho_solve((L, True), y_std)
    lml = (-0.5 * y_std @ alpha - np.sum(np.log(np.diag(L)))
           - 0.5 * n * math.log(2.0 * math.pi))
    prior = (-0.5 * np.sum((log_ls / 2.0) ** 2)
             - 0.5 * (theta[d] / 2.0) ** 2
             - 0.5 * ((theta[d + 1] + 4.0) / 2.0) ** 2)
    # d lml / dθ_j = ½ tr((α αᵀ − K⁻¹) ∂K/∂θ_j)
    W = np.outer(alpha, alpha) - cho_solve((L, True), np.eye(n))
    dk_dls = (amp * (5.0 / 3.0) * (1.0 + SQRT5 * r) * e)[..., None] * sq
    g_lml = np.concatenate([
        0.5 * np.einsum("ij,ijk->k", W, dk_dls),
        [0.5 * np.sum(W * kern), 0.5 * noise * np.trace(W)]])
    g_prior = np.concatenate([-log_ls / 4.0, [-theta[d] / 4.0,
                                              -(theta[d + 1] + 4.0) / 4.0]])
    return -(lml + prior), -(g_lml + g_prior)


def map_polish(x_obs: np.ndarray, y_obs: np.ndarray, theta) -> float:
    """How far, in nats, SciPy's L-BFGS-B still lowers the MAP objective
    of the GP of ``(x_obs, y_obs)`` (unit cube, raw minimized y) from
    the fitted θ, inside the fit's bounds."""
    x_obs = np.asarray(x_obs, np.float64)
    y_std = standardized(y_obs)
    d = x_obs.shape[1]
    bounds = [THETA_BOUNDS[0]] * d + [THETA_BOUNDS[1], THETA_BOUNDS[2]]
    lo, hi = np.array(bounds).T
    th0 = np.clip(np.asarray(theta, np.float64), lo, hi)
    f0, _ = neg_log_posterior(th0, x_obs, y_std)
    res = minimize(neg_log_posterior, th0, args=(x_obs, y_std), jac=True,
                   method="L-BFGS-B", bounds=bounds,
                   options={"maxiter": 1000, "ftol": 1e-15, "gtol": 1e-9})
    return max(float(f0) - float(res.fun), 0.0)


def read_journal(path: str) -> List[Dict]:
    """Every intact record of a write-ahead journal, in order.  A line is
    ``<crc32 hex> <json>`` with a ``seq`` that counts from 0; reading
    stops at the first line that fails either check (a torn tail)."""
    with open(path, "rb") as f:
        data = f.read()
    return list(_records(data))


def _records(data: bytes) -> Iterator[Dict]:
    seq = 0
    for line in data.split(b"\n"):
        head, sep, payload = line.partition(b" ")
        if not sep:
            return
        try:
            if int(head, 16) != zlib.crc32(payload):
                return
            rec = json.loads(payload)
        except ValueError:
            return
        if rec.get("seq") != seq:
            return
        seq += 1
        yield rec
