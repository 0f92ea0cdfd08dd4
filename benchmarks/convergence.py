"""Paper Figures 2, 5 — C-BE convergence slowdown as B grows.

Rosenbrock (D=5, x ∈ [0,3]^D), L-BFGS-B m=10 (Fig 2) or BFGS (Fig 5).
For each B ∈ {1, 2, 5, 10}: run C-BE from random starts, record the mean
objective across the B points at every QN iteration, and report the median
iteration count to reach 1e-6 / 1e-12.  B=1 is SEQ. OPT. by definition;
the paper's observation is ~30 iters at B=1 vs >120 at B=10 for 1e-12.
"""
import jax

jax.config.update("jax_enable_x64", True)

import numpy as np                     # noqa: E402
from scipy.optimize import minimize    # noqa: E402

from benchmarks.offdiag import rosen_np, rosen_grad_np, _sum_obj, _sum_grad  # noqa: E402


def _traj_cbe(B, D, x0, method, maxiter=400):
    """Mean-objective trajectory of one C-BE run."""
    traj = []

    def cb(z):
        X = z.reshape(B, D) if not hasattr(z, "x") else z.x.reshape(B, D)
        traj.append(np.mean([rosen_np(X[b]) for b in range(B)]))

    opts = dict(maxiter=maxiter)
    kw = {}
    if method == "L-BFGS-B":
        opts.update(maxcor=10, gtol=1e-14, ftol=0.0)
        kw["bounds"] = [(0.0, 3.0)] * (B * D)
    else:
        opts.update(gtol=1e-14)
    minimize(lambda z: _sum_obj(z, B, D), x0.reshape(-1),
             jac=lambda z: _sum_grad(z, B, D), method=method,
             callback=cb, options=opts, **kw)
    return np.asarray(traj)


def iters_to(traj, tol):
    idx = np.nonzero(traj <= tol)[0]
    return int(idx[0]) + 1 if idx.size else len(traj) + 1


def run(method="L-BFGS-B", D=5, Bs=(1, 2, 5, 10), total_runs=64, seed=0,
        maxiter=400):
    rng = np.random.default_rng(seed)
    rows = []
    for B in Bs:
        reps = max(total_runs // B, 3)
        it6, it12 = [], []
        for _ in range(reps):
            x0 = rng.uniform(0.0, 3.0, (B, D))
            traj = _traj_cbe(B, D, x0, method, maxiter)
            it6.append(iters_to(traj, 1e-6))
            it12.append(iters_to(traj, 1e-12))
        rows.append({
            "method": method, "B": B, "reps": reps,
            "iters_to_1e-6": float(np.median(it6)),
            "iters_to_1e-12": float(np.median(it12)),
        })
    return rows


def main(full=False):
    total = 256 if full else 48
    out = []
    for method in ("L-BFGS-B", "BFGS"):
        for r in run(method=method, total_runs=total):
            out.append(r)
            print(f"convergence,{method},B={r['B']},"
                  f"iters@1e-6={r['iters_to_1e-6']:.1f},"
                  f"iters@1e-12={r['iters_to_1e-12']:.1f}")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
