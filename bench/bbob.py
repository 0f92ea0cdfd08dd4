"""BBOB objectives of the benchmark's traffic: the workers' black boxes.

A copy of the COCO/BBOB definitions (T_osz, T_asy, Λ^α, seeded random
rotations) for f1 sphere, f6 attractive sector, f7 step ellipsoidal and
f15 rotated Rastrigin on [-5, 5]^D, the paper's §5 set.  The benchmark
keeps its own copy so that the traffic does not move when the program's
``repro.bo.objectives`` does.
"""
from __future__ import annotations

from typing import Callable, Dict

import numpy as np


def _rotation(rng: np.random.Generator, d: int) -> np.ndarray:
    q, r = np.linalg.qr(rng.standard_normal((d, d)))
    return q * np.sign(np.diag(r))


def _t_osz(x: np.ndarray) -> np.ndarray:
    xhat = np.where(x != 0, np.log(np.abs(x) + 1e-300), 0.0)
    c1 = np.where(x > 0, 10.0, 5.5)
    c2 = np.where(x > 0, 7.9, 3.1)
    return np.sign(x) * np.exp(
        xhat + 0.049 * (np.sin(c1 * xhat) + np.sin(c2 * xhat)))


def _t_asy(x: np.ndarray, beta: float) -> np.ndarray:
    d = x.shape[-1]
    i = np.arange(d) / max(d - 1, 1)
    expo = 1.0 + beta * i * np.sqrt(np.maximum(x, 0.0))
    return np.where(x > 0, np.power(np.maximum(x, 0.0), expo), x)


def _lam(alpha: float, d: int) -> np.ndarray:
    i = np.arange(d) / max(d - 1, 1)
    return np.power(alpha, 0.5 * i)


class BBOBFunction:
    """One seeded instance: optimum and rotations drawn from ``seed``."""

    def __init__(self, name: str, dim: int, seed: int):
        self.name = name
        self.dim = dim
        rng = np.random.default_rng([seed, dim])
        self.x_opt = rng.uniform(-4.0, 4.0, dim)
        self._R = _rotation(rng, dim)
        self._Q = _rotation(rng, dim)
        self._fn = FUNCTIONS[name]

    def __call__(self, x: np.ndarray) -> float:
        return float(self._fn(self, np.asarray(x, np.float64)))


def _sphere(f: BBOBFunction, x):
    z = x - f.x_opt
    return np.sum(z * z)


def _attractive_sector(f: BBOBFunction, x):
    z = f._Q @ (_lam(10.0, f.dim) * (f._R @ (x - f.x_opt)))
    s = np.where(z * f.x_opt > 0, 100.0, 1.0)
    val = np.sum((s * z) ** 2)
    return float(_t_osz(np.asarray([val]))[0]) ** 0.9


def _step_ellipsoidal(f: BBOBFunction, x):
    zhat = _lam(10.0, f.dim) * (f._R @ (x - f.x_opt))
    ztilde = np.where(np.abs(zhat) > 0.5, np.floor(0.5 + zhat),
                      np.floor(0.5 + 10.0 * zhat) / 10.0)
    z = f._Q @ ztilde
    i = np.arange(f.dim) / max(f.dim - 1, 1)
    val = np.sum(np.power(10.0, 2.0 * i) * z * z)
    return 0.1 * max(np.abs(zhat[0]) / 1e4, val)


def _rastrigin(f: BBOBFunction, x):
    z = f._R @ (x - f.x_opt)
    z = _t_asy(_t_osz(z), 0.2)
    z = f._R @ (_lam(10.0, f.dim) * (f._Q @ z))
    return 10.0 * (f.dim - np.sum(np.cos(2 * np.pi * z))) + np.sum(z * z)


FUNCTIONS: Dict[str, Callable] = {
    "sphere": _sphere,
    "attractive_sector": _attractive_sector,
    "step_ellipsoidal": _step_ellipsoidal,
    "rastrigin": _rastrigin,
}
