"""Posterior backends for the evaluation engine.

The dominant per-round cost of MSO is the batched GP posterior (paper §4:
one (k, n) cross-gram + triangular solves per evaluation round).  This
module routes that hot path:

* ``"xla"``     — the classic Cholesky-solve ``gp.gpr.predict`` (exact,
                  differentiable, runs anywhere);
* ``"pallas"``  — the fused cross-gram + mean/variance Pallas kernel
                  (``kernels.matern``): the (k, n) slab never round-trips
                  through HBM; gradients route through a custom VJP;
* ``"pallas_interpret"`` — same kernel in interpreter mode (CPU
                  validation / CI);
* ``"auto"``    — xla, on every platform (see ``resolve_backend``).

The fused path needs a Matérn-5/2 ``GPState`` carrying ``kinv`` (see
``gp.gpr.with_kinv``).  A Pallas backend handed any other state raises:
the kernel-or-Cholesky choice is made once, where the sampler resolves
its backend, and never silently here.
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.acquisition import log_ei
from repro.gp.gpr import GPState, predict
from repro.kernels.matern.ops import matern52_posterior_op

Array = jax.Array

BACKENDS = ("auto", "xla", "pallas", "pallas_interpret")


def resolve_backend(backend: str = "auto") -> str:
    if backend not in BACKENDS:
        raise ValueError(f"backend must be one of {BACKENDS}")
    if backend == "auto":
        # The fused kernel computes in float32, and its variance
        # amp - kᵀK⁻¹k cancels to rounding noise near the data once
        # amp/noise reaches ~1e4: on a v5e it was off by 1e-2·amp where
        # the true variance is 6e-6·amp, and LogEI then steers
        # suggestions by that noise.  So ``auto`` never picks it; the
        # kernel runs only when asked for by name.
        return "xla"
    return backend


def posterior(gp: GPState, xb: Array, *, backend: str = "auto"
              ) -> Tuple[Array, Array]:
    """Batched posterior ((k,) mean, (k,) var) via the chosen backend."""
    backend = resolve_backend(backend)
    if backend == "xla":
        return predict(gp, xb)
    if gp.kernel != "matern52" or gp.kinv is None:
        raise ValueError(
            f"posterior backend {backend!r} runs the fused Matérn-5/2 "
            f"kernel and needs a GPState with kinv; got kernel="
            f"{gp.kernel!r}, kinv={'set' if gp.kinv is not None else None}"
            f" (use gp.gpr.with_kinv, or the 'xla' backend)")
    inv_ls = jnp.exp(-gp.params.log_lengthscale)
    return matern52_posterior_op(
        xb, gp.x_train, gp.alpha, gp.kinv, inv_ls, gp.params.amplitude,
        backend="pallas", interpret=(backend == "pallas_interpret"))


# one acq function object per backend: the engine's jit caches key on
# function identity, so these must be stable across calls
_LOGEI_CACHE: Dict[str, Callable] = {}


def fused_logei_acq(backend: str = "auto") -> Callable:
    """State-form LogEI (``state = (GPState, best)``) over the chosen
    posterior backend — drop-in for ``core.acquisition.logei_acq``."""
    backend = resolve_backend(backend)
    fn = _LOGEI_CACHE.get(backend)
    if fn is None:
        def acq(state, xb, _backend=backend):
            gp, best = state
            mean, var = posterior(gp, xb, backend=_backend)
            return log_ei(mean, var, best)
        acq.__name__ = f"logei_acq_{backend}"
        _LOGEI_CACHE[backend] = fn = acq
    return fn
