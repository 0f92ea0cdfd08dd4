"""Paper Figures 1, 3, 4 — off-diagonal artifacts in the QN inverse-Hessian.

Setup (paper §3): Rosenbrock, D=5, x ∈ [0,3]^D, B restarts.  Optimize with
(a) SEQ. OPT. (per-restart solver) and (b) C-BE (one solver over the
flattened B·D vector of the summed objective), then compare the solver's
final inverse-Hessian approximation against the true inverse Hessian:

  e_rel(H)     = ||H - H_true||_F / ||H_true||_F        (figure subtitles)
  offdiag_mass = ||offdiag-blocks(H)||_F / ||H||_F      (the artifact)

SEQ's H is block-diagonal by construction (mass ≡ 0); the paper's claim is
that C-BE's is not, for both L-BFGS-B (m=10) and full BFGS.
"""
import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp           # noqa: E402
import numpy as np                # noqa: E402
from scipy.optimize import minimize  # noqa: E402


def rosen_np(x):
    return float(np.sum(100.0 * (x[1:] - x[:-1] ** 2) ** 2
                        + (1.0 - x[:-1]) ** 2))


def rosen_grad_np(x):
    g = np.zeros_like(x)
    xm = x[1:-1]
    g[1:-1] = (200 * (xm - x[:-2] ** 2) - 400 * xm * (x[2:] - xm ** 2)
               - 2 * (1 - xm))
    g[0] = -400 * x[0] * (x[1] - x[0] ** 2) - 2 * (1 - x[0])
    g[-1] = 200 * (x[-1] - x[-2] ** 2)
    return g


def _sum_obj(z, B, D):
    X = z.reshape(B, D)
    return float(sum(rosen_np(X[b]) for b in range(B)))


def _sum_grad(z, B, D):
    X = z.reshape(B, D)
    return np.concatenate([rosen_grad_np(X[b]) for b in range(B)])


def true_inverse_hessian(X):
    """Block-diagonal inverse Hessian of the summed Rosenbrock at X."""
    B, D = X.shape

    def rosen_jnp(x):
        return jnp.sum(100.0 * (x[1:] - x[:-1] ** 2) ** 2
                       + (1.0 - x[:-1]) ** 2)

    H = np.zeros((B * D, B * D))
    for b in range(B):
        Hb = np.asarray(jax.hessian(rosen_jnp)(jnp.asarray(X[b])))
        H[b * D:(b + 1) * D, b * D:(b + 1) * D] = np.linalg.inv(Hb)
    return H


def offdiag_mass(H, B, D):
    mask = np.ones_like(H)
    for b in range(B):
        mask[b * D:(b + 1) * D, b * D:(b + 1) * D] = 0.0
    return float(np.linalg.norm(H * mask) / max(np.linalg.norm(H), 1e-30))


def run(B=3, D=5, method="L-BFGS-B", seed=0, maxiter=500):
    rng = np.random.default_rng(seed)
    X0 = rng.uniform(0.0, 3.0, (B, D))
    bounds = [(0.0, 3.0)] * D
    opts = dict(maxiter=maxiter)
    if method == "L-BFGS-B":
        opts.update(maxcor=10, gtol=1e-10, ftol=0.0)

    # SEQ. OPT.: independent solvers → assemble block-diagonal H
    H_seq = np.zeros((B * D, B * D))
    X_fin = np.zeros_like(X0)
    for b in range(B):
        r = minimize(rosen_np, X0[b], jac=rosen_grad_np, method=method,
                     bounds=bounds if method == "L-BFGS-B" else None,
                     options=opts)
        X_fin[b] = r.x
        hb = r.hess_inv.todense() if method == "L-BFGS-B" else r.hess_inv
        H_seq[b * D:(b + 1) * D, b * D:(b + 1) * D] = hb

    # C-BE: one solver over the flattened summed objective
    r = minimize(lambda z: _sum_obj(z, B, D), X0.reshape(-1),
                 jac=lambda z: _sum_grad(z, B, D), method=method,
                 bounds=bounds * B if method == "L-BFGS-B" else None,
                 options=opts)
    H_cbe = r.hess_inv.todense() if method == "L-BFGS-B" else r.hess_inv
    X_cbe = r.x.reshape(B, D)

    H_true_seq = true_inverse_hessian(X_fin)
    H_true_cbe = true_inverse_hessian(X_cbe)

    def e_rel(H, Ht):
        return float(np.linalg.norm(H - Ht) / np.linalg.norm(Ht))

    return {
        "method": method, "B": B, "D": D,
        "e_rel_seq": e_rel(H_seq, H_true_seq),
        "e_rel_cbe": e_rel(np.asarray(H_cbe), H_true_cbe),
        "offdiag_seq": offdiag_mass(H_seq, B, D),
        "offdiag_cbe": offdiag_mass(np.asarray(H_cbe), B, D),
        "offdiag_true": offdiag_mass(H_true_cbe, B, D),
    }


def main(full=False):
    rows = []
    cases = [("L-BFGS-B", 3), ("BFGS", 3), ("BFGS", 10)]   # Fig 1, 3, 4
    for method, B in cases:
        r = run(B=B, method=method)
        rows.append(r)
        print(f"offdiag,{method},B={r['B']},"
              f"e_rel_seq={r['e_rel_seq']:.3f},"
              f"e_rel_cbe={r['e_rel_cbe']:.3f},"
              f"offdiag_seq={r['offdiag_seq']:.4f},"
              f"offdiag_cbe={r['offdiag_cbe']:.4f}")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
