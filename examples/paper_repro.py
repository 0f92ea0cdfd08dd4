"""Reproduce the paper's core phenomena in one run:

1. C3 — D-BE per-restart trajectories are IDENTICAL to SEQ. OPT.
2. C2 — C-BE's off-diagonal artifacts inflate L-BFGS-B iterations.
3. wall-clock — D-BE < C-BE < SEQ. OPT. on batched-evaluation objectives.

    PYTHONPATH=src python examples/paper_repro.py
"""
import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp   # noqa: E402
import numpy as np        # noqa: E402

from repro.core.mso import MsoOptions, maximize_acqf   # noqa: E402


def neg_rosen(state, X):
    del state
    return -jax.vmap(lambda x: jnp.sum(
        100.0 * (x[1:] - x[:-1] ** 2) ** 2
        + (1.0 - x[:-1]) ** 2))(X)


def main():
    B, D = 10, 5
    x0 = np.random.default_rng(0).uniform(0, 3, (B, D))
    opts = MsoOptions(m=10, maxiter=200, pgtol=1e-8)

    results = {}
    for s in ("seq", "dbe", "cbe", "dbe_vec"):
        r = maximize_acqf(neg_rosen, x0, 0.0, 3.0, acq_state=None,
                          strategy=s, options=opts)
        results[s] = r
        print(f"{s:8s} best={r.best_acq:+.3e} "
              f"iters(med)={np.median(r.n_iters):6.1f} "
              f"eval_rounds={r.n_rounds:4d} wall={r.wall_time:.2f}s")

    same = np.array_equal(results["seq"].x, results["dbe"].x)
    print(f"\nC3  D-BE trajectories identical to SEQ. OPT.: {same}")
    infl = (np.median(results['cbe'].n_iters)
            / np.median(results['dbe'].n_iters))
    print(f"C2  C-BE iteration inflation vs D-BE: {infl:.1f}x")
    print(f"    D-BE eval rounds vs SEQ: {results['seq'].n_rounds} -> "
          f"{results['dbe'].n_rounds} "
          f"({results['seq'].n_rounds / results['dbe'].n_rounds:.1f}x fewer)")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
