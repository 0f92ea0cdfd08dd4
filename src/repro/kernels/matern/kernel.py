"""Matérn-5/2 gram matrix as a Pallas TPU kernel.

Hot spot: the O(n²D) gram construction inside every GP fit step (the fit's
L-BFGS-B evaluates the marginal likelihood dozens of times) and the (q, n)
cross-gram inside every batched acquisition evaluation — the cost the
paper's §4 model says dominates MSO.

TPU mapping: tiles of (TILE_M, TILE_N) outputs are produced per grid step;
each step loads an (TILE_M, D) and (TILE_N, D) slab of pre-scaled points
into VMEM and forms -2·a·bᵀ on the MXU, then applies the Matérn polynomial
on the VPU.  D is kept whole per block (BO dims are small); M/N tiles are
128-aligned for lane efficiency.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

SQRT5 = 2.2360679774997896

TILE_M = 128
TILE_N = 128

# Constant block index for the index maps.  Grid indices are int32; a bare
# Python 0 becomes an int64 constant under jax_enable_x64, and Mosaic then
# refuses the kernel ("failed to legalize operation 'func.return'").
_0 = np.int32(0)

# The MXU's default pass rounds float32 operands to bfloat16; measured on a
# v5e, that put the posterior variance off by up to 44x the amplitude.
# HIGHEST keeps the float32 products every kernel here is written for
# (and that interpret mode computes).
_F32 = jax.lax.Precision.HIGHEST


def _matern_kernel(a_ref, b_ref, asq_ref, bsq_ref, amp_ref, out_ref):
    """One (TILE_M, TILE_N) block of the gram matrix.

    a_ref: (TILE_M, D) pre-scaled rows; b_ref: (TILE_N, D);
    asq_ref/bsq_ref: (TILE_M, 1)/(TILE_N, 1) squared norms; amp_ref: (1, 1).
    """
    a = a_ref[...]
    b = b_ref[...]
    # MXU: (M, D) @ (D, N)
    ab = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                             precision=_F32,
                             preferred_element_type=jnp.float32)
    d2 = asq_ref[...] + bsq_ref[...].T - 2.0 * ab
    d2 = jnp.maximum(d2, 0.0)
    r = jnp.sqrt(d2 + 1e-36)
    poly = 1.0 + SQRT5 * r + (5.0 / 3.0) * d2
    out_ref[...] = (amp_ref[0, 0] * poly * jnp.exp(-SQRT5 * r)
                    ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def matern52_gram(x1: jax.Array, x2: jax.Array, inv_lengthscale: jax.Array,
                  amplitude: jax.Array, *, interpret: bool = False
                  ) -> jax.Array:
    """Pallas Matérn-5/2 cross gram, padded to tile multiples.

    Returns (n1, n2) in x1.dtype.  Use ``interpret=True`` off-TPU.
    """
    n1, d = x1.shape
    n2 = x2.shape[0]
    dtype = x1.dtype

    a = (x1 * inv_lengthscale).astype(jnp.float32)
    b = (x2 * inv_lengthscale).astype(jnp.float32)

    m_pad = (-n1) % TILE_M
    n_pad = (-n2) % TILE_N
    a = jnp.pad(a, ((0, m_pad), (0, 0)))
    b = jnp.pad(b, ((0, n_pad), (0, 0)))
    asq = jnp.sum(a * a, -1, keepdims=True)                 # (M, 1)
    bsq = jnp.sum(b * b, -1, keepdims=True)                 # (N, 1)
    amp = jnp.asarray(amplitude, jnp.float32).reshape(1, 1)

    M, N = a.shape[0], b.shape[0]
    grid = (M // TILE_M, N // TILE_N)

    out = pl.pallas_call(
        _matern_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_M, d), lambda i, j: (i, _0)),
            pl.BlockSpec((TILE_N, d), lambda i, j: (j, _0)),
            pl.BlockSpec((TILE_M, 1), lambda i, j: (i, _0)),
            pl.BlockSpec((TILE_N, 1), lambda i, j: (j, _0)),
            pl.BlockSpec((1, 1), lambda i, j: (_0, _0)),
        ],
        out_specs=pl.BlockSpec((TILE_M, TILE_N), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(a, b, asq, bsq, amp)

    return out[:n1, :n2].astype(dtype)


# ---------------------------------------------------------------------------
# fused posterior: cross-gram + mean/variance epilogue
# ---------------------------------------------------------------------------

VAR_FLOOR = 1e-16           # matches gpr.predict's variance clamp

# K⁻¹ (N², f32) must fit VMEM alongside the tile.  1280 is the largest
# training size at which the v5e compile (x64 on, as every entry point
# runs) accepts the kernel both alone and under a 16-slot vmap, as the
# fleet calls it; at the next tile (1408) the vmapped kernel runs out of
# VMEM.  tests/test_tpu_compile.py compiles the kernel at this bound.
MAX_TRAIN = 1280


def _posterior_kernel(a_ref, b_ref, asq_ref, bsq_ref, alpha_ref, kinv_ref,
                      amp_ref, mean_ref, var_ref):
    """One (TILE_Q,) slab of posterior mean/variance.

    a_ref: (TILE_Q, D) pre-scaled queries; b_ref: (N, D) the WHOLE
    pre-scaled training set (BO training sets are small — N ≤ MAX_TRAIN —
    so K⁻¹ fits VMEM and the cross-gram row never round-trips to HBM);
    alpha_ref: (N, 1) K⁻¹y; kinv_ref: (N, N).

    The (TILE_Q, N) cross-gram slab is built once on MXU+VPU and feeds
    both epilogues in-register:
      mean = K α                (MXU, (TILE_Q, 1))
      var  = σ_f² − rowsum((K K⁻¹) ∘ K)   (MXU + VPU)
    """
    a = a_ref[...]
    b = b_ref[...]
    ab = jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                             precision=_F32,
                             preferred_element_type=jnp.float32)
    d2 = asq_ref[...] + bsq_ref[...].T - 2.0 * ab
    d2 = jnp.maximum(d2, 0.0)
    r = jnp.sqrt(d2 + 1e-36)
    k = amp_ref[0, 0] * (1.0 + SQRT5 * r + (5.0 / 3.0) * d2) * \
        jnp.exp(-SQRT5 * r)                                  # (TILE_Q, N)

    mean_ref[...] = jnp.dot(k, alpha_ref[...], precision=_F32,
                            preferred_element_type=jnp.float32)  # (TILE_Q, 1)
    t = jax.lax.dot_general(k, kinv_ref[...], (((1,), (0,)), ((), ())),
                            precision=_F32,
                            preferred_element_type=jnp.float32)
    quad = jnp.sum(t * k, axis=-1, keepdims=True)             # (TILE_Q, 1)
    var_ref[...] = jnp.maximum(amp_ref[0, 0] - quad, VAR_FLOOR)


@functools.partial(jax.jit, static_argnames=("interpret",))
def matern52_posterior(xq: jax.Array, xt: jax.Array, alpha: jax.Array,
                       kinv: jax.Array, inv_lengthscale: jax.Array,
                       amplitude: jax.Array, *, interpret: bool = False):
    """Pallas-fused GP posterior: ((q,) mean, (q,) variance).

    Forward-only (see ``ops.matern52_posterior_op`` for the differentiable
    wrapper).  Queries are padded to TILE_M multiples; training rows to
    TILE_N multiples with zero-padded α and K⁻¹ (padded rows therefore
    contribute exactly nothing to either epilogue).
    """
    nq, d = xq.shape
    nt = xt.shape[0]
    if nt > MAX_TRAIN:
        raise ValueError(
            f"fused posterior holds K⁻¹ in VMEM; n={nt} exceeds "
            f"MAX_TRAIN={MAX_TRAIN} — use the xla backend")
    dtype = xq.dtype

    a = (xq * inv_lengthscale).astype(jnp.float32)
    b = (xt * inv_lengthscale).astype(jnp.float32)
    q_pad = (-nq) % TILE_M
    n_pad = (-nt) % TILE_N
    a = jnp.pad(a, ((0, q_pad), (0, 0)))
    b = jnp.pad(b, ((0, n_pad), (0, 0)))
    al = jnp.pad(alpha.astype(jnp.float32), (0, n_pad)).reshape(-1, 1)
    ki = jnp.pad(kinv.astype(jnp.float32), ((0, n_pad), (0, n_pad)))
    asq = jnp.sum(a * a, -1, keepdims=True)
    bsq = jnp.sum(b * b, -1, keepdims=True)
    amp = jnp.asarray(amplitude, jnp.float32).reshape(1, 1)

    Q, N = a.shape[0], b.shape[0]
    grid = (Q // TILE_M,)

    mean, var = pl.pallas_call(
        _posterior_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_M, d), lambda i: (i, _0)),
            pl.BlockSpec((N, d), lambda i: (_0, _0)),
            pl.BlockSpec((TILE_M, 1), lambda i: (i, _0)),
            pl.BlockSpec((N, 1), lambda i: (_0, _0)),
            pl.BlockSpec((N, 1), lambda i: (_0, _0)),
            pl.BlockSpec((N, N), lambda i: (_0, _0)),
            pl.BlockSpec((1, 1), lambda i: (_0, _0)),
        ],
        out_specs=[
            pl.BlockSpec((TILE_M, 1), lambda i: (i, _0)),
            pl.BlockSpec((TILE_M, 1), lambda i: (i, _0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, 1), jnp.float32),
            jax.ShapeDtypeStruct((Q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(a, b, asq, bsq, al, ki, amp)

    return mean[:nq, 0].astype(dtype), var[:nq, 0].astype(dtype)
