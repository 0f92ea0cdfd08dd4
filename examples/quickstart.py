"""Quickstart: Bayesian optimization with D-BE acquisition optimization.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402

from repro.bo.objectives import make_objective     # noqa: E402
from repro.bo.sampler import GPSampler             # noqa: E402
from repro.bo.space import BoxSpace                # noqa: E402
from repro.core.mso import MsoOptions              # noqa: E402


def main():
    D = 5
    obj = make_objective("rastrigin", D, seed=1)
    space = BoxSpace.cube(D, *obj.bounds)

    sampler = GPSampler(
        space,
        strategy="dbe",               # the paper's coroutine D-BE
        n_startup_trials=10,
        n_restarts=10,                # B=10 multi-start (paper setting)
        mso_options=MsoOptions(m=10, maxiter=200, pgtol=1e-2),
        seed=0,
    )
    best = sampler.optimize(obj, n_trials=40)
    print(f"best value: {best.y:.4f} at x = {np.round(best.x, 3)}")
    print(f"GP fits: {sampler.stats.n_gp_fits}, "
          f"acqf time: {sampler.stats.acqf_time:.1f}s, "
          f"median L-BFGS-B iters/trial: "
          f"{np.median(sampler.stats.acqf_iters):.1f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
