"""Paper Tables 1 & 2 — end-to-end BO benchmark.

BO with GPSampler (Matérn-5/2 + LogEI), L-BFGS-B m=10, B=10 restarts,
termination 200 iters or ||∇α||_inf ≤ 1e-2, objectives Sphere / Attractive
Sector / Step Ellipsoidal / Rastrigin at D ∈ {5,10,20,40}, strategies
SEQ. OPT. / C-BE / D-BE (+ our D-BE-vectorized).

Reported per (objective, D, strategy): median best-value, median BO
wall-clock, median acqf wall-clock, median per-trial L-BFGS-B iterations —
the paper's three columns plus the acqf-only time.

Paper scale (--full): 300 trials × 20 seeds.  CPU-reduced default:
60 trials × 3 seeds × D ∈ {5,10} × {rastrigin, sphere}.
"""
import jax

jax.config.update("jax_enable_x64", True)

import time                        # noqa: E402

import numpy as np                 # noqa: E402

from repro.bo.objectives import make_objective      # noqa: E402
from repro.bo.sampler import GPSampler               # noqa: E402
from repro.bo.space import BoxSpace                  # noqa: E402
from repro.core.mso import MsoOptions                # noqa: E402


def run_one(objective: str, D: int, strategy: str, seed: int,
            n_trials: int, B: int = 10):
    obj = make_objective(objective, D, seed=1)   # same instance ∀ seeds
    space = BoxSpace.cube(D, *obj.bounds)
    sampler = GPSampler(
        space, strategy=strategy, seed=seed, n_startup_trials=10,
        n_restarts=B,
        mso_options=MsoOptions(m=10, maxiter=200, pgtol=1e-2))
    t0 = time.perf_counter()
    best = sampler.optimize(obj, n_trials)
    wall = time.perf_counter() - t0
    return {
        "objective": objective, "D": D, "strategy": strategy, "seed": seed,
        "best_value": best.y,
        "runtime_s": wall,
        "acqf_s": sampler.stats.acqf_time,
        "fit_s": sampler.stats.fit_time,
        "med_iters": float(np.median(sampler.stats.acqf_iters))
        if sampler.stats.acqf_iters else 0.0,
    }


def run_table(objectives, dims, strategies, seeds, n_trials):
    rows = []
    for objective in objectives:
        for D in dims:
            base = None
            for strategy in strategies:
                per_seed = [run_one(objective, D, strategy, s, n_trials)
                            for s in range(seeds)]
                med = {k: float(np.median([r[k] for r in per_seed]))
                       for k in ("best_value", "runtime_s", "acqf_s",
                                 "fit_s", "med_iters")}
                row = {"objective": objective, "D": D,
                       "strategy": strategy, "seeds": seeds,
                       "trials": n_trials, **med}
                if strategy == "seq":
                    base = med
                if base:
                    row["acqf_speedup_vs_seq"] = \
                        base["acqf_s"] / max(med["acqf_s"], 1e-12)
                rows.append(row)
                print(f"bo,{objective},D={D},{strategy},"
                      f"best={med['best_value']:.4g},"
                      f"runtime={med['runtime_s']:.1f}s,"
                      f"acqf={med['acqf_s']:.1f}s,"
                      f"iters={med['med_iters']:.1f}", flush=True)
    return rows


def main(full=False):
    if full:
        return run_table(
            ("sphere", "attractive_sector", "step_ellipsoidal",
             "rastrigin"),
            (5, 10, 20, 40), ("seq", "cbe", "dbe", "dbe_vec"), 20, 300)
    return run_table(("rastrigin", "sphere"), (5, 10),
                     ("seq", "cbe", "dbe", "dbe_vec"), 3, 60)


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import sys
    main(full="--full" in sys.argv)
