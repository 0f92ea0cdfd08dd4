"""Paper §5 wall-clock claim + §4 cost model — MSO micro-benchmark.

Fixes a fitted GP (n training points) and times ONE acquisition
optimization (B restarts, LogEI) per strategy, all four strategies running
through the shared evaluation engine.  Validates:

* C5 (cost model): batched eval cost O(B(n²+nD)) dominates the O(BmD) QN
  update when n ≫ m — measured as eval-time share.
* the 1.5×(vs SEQ.) / 1.1×(vs C-BE) wall-clock speedups of D-BE, and the
  beyond-paper D-BE-vectorized device-resident variant.
* the engine's compile economy: evaluation rounds per strategy plus the
  engine's exact compile counters land in BENCH_mso.json so the perf
  trajectory accumulates across PRs.

Usage:
  python benchmarks/mso_walltime.py [--full] [--tiny] [--backend xla|
      pallas|pallas_interpret] [--out BENCH_mso.json]
"""
import argparse
import json
import platform
import time

import jax

jax.config.update("jax_enable_x64", True)

import jax.numpy as jnp           # noqa: E402
import numpy as np                # noqa: E402

from repro.core.acquisition import logei_acq          # noqa: E402
from repro.core.mso import (MsoOptions, STRATEGIES,   # noqa: E402
                            maximize_acqf)
from repro.engine import EvalEngine, fused_logei_acq  # noqa: E402
from repro.gp.fit import fit_gp, standardize          # noqa: E402
from repro.gp.gpr import with_kinv                    # noqa: E402


def setup_gp(n: int, D: int, seed: int = 0, backend: str = "xla"):
    rng = np.random.default_rng(seed)
    X = rng.uniform(0, 1, (n, D))
    # high-frequency target -> short fitted lengthscales -> a wiggly,
    # multi-modal LogEI surface that makes the QN solvers actually work
    y = np.sin(8 * X).sum(1) + 0.3 * np.cos(13 * X[:, 0]) \
        + 0.05 * rng.standard_normal(n)
    y_std, _, _ = standardize(jnp.asarray(-y))
    gp = fit_gp(jnp.asarray(X), y_std, n_restarts=2, pad_bucket=32)
    if backend != "xla":
        gp = with_kinv(gp)
    return gp, float(jnp.max(y_std))


def bench(n: int, D: int, B: int = 10, reps: int = 5, seed: int = 0,
          backend: str = "xla"):
    gp, best = setup_gp(n, D, seed, backend)
    state = (gp, jnp.asarray(best))
    acq_fn = logei_acq if backend == "xla" else fused_logei_acq(backend)
    rng = np.random.default_rng(seed + 1)
    opts = MsoOptions(m=10, maxiter=200, pgtol=1e-5)
    rows = []
    for strategy in STRATEGIES:
        # fresh engine per strategy: compile counts are attributable
        engine = EvalEngine(acq_fn)
        walls, iters, rounds, evals = [], [], [], []
        for r in range(reps + 1):
            x0 = rng.uniform(0, 1, (B, D))
            res = maximize_acqf(acq_fn, x0, 0.0, 1.0, acq_state=state,
                                strategy=strategy, options=opts,
                                engine=engine)
            if r == 0:
                continue          # warm-up (jit compile)
            walls.append(res.wall_time)
            iters.append(float(np.median(res.n_iters)))
            rounds.append(res.n_rounds)
            evals.append(float(np.sum(res.n_evals)))
        es = engine.stats_snapshot()
        rows.append({
            "n": n, "D": D, "B": B, "strategy": strategy,
            "backend": backend,
            "wall_ms": 1e3 * float(np.median(walls)),
            "med_iters": float(np.median(iters)),
            "rounds": float(np.median(rounds)),
            # per-run solver totals (dbe_vec included: run_lockstep now
            # surfaces LbfgsbResult.rounds/n_evals into EngineStats)
            "evals_per_run": float(np.median(evals)),
            "eval_rounds_total": es["n_rounds"],
            "points_evaluated": es["n_points"],
            "points_padded": es["n_padded"],
            "engine_compiles": es["n_compiles"],
            "bucket_rounds": es["bucket_rounds"],
        })
    base = rows[0]["wall_ms"]
    cbe = rows[1]["wall_ms"]
    for r in rows:
        r["speedup_vs_seq"] = base / r["wall_ms"]
        r["speedup_vs_cbe"] = cbe / r["wall_ms"]
        print(f"mso,n={r['n']},D={r['D']},{r['strategy']},"
              f"wall={r['wall_ms']:.1f}ms,iters={r['med_iters']:.1f},"
              f"rounds={r['rounds']:.0f},"
              f"compiles={r['engine_compiles']},"
              f"vs_seq={r['speedup_vs_seq']:.2f}x", flush=True)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: tiny B/D, 1 rep")
    ap.add_argument("--backend", default="xla",
                    choices=("xla", "pallas", "pallas_interpret"))
    ap.add_argument("--out", default="BENCH_mso.json")
    args = ap.parse_args(argv)

    if args.tiny:
        cases, B, reps = [(32, 3)], 4, 1
    elif args.full:
        cases, B, reps = [(64, 5), (128, 10), (192, 20), (288, 40)], 10, 5
    else:
        cases, B, reps = [(64, 5), (192, 5), (192, 20)], 10, 5

    out = []
    for n, D in cases:
        out.extend(bench(n, D, B=B, reps=reps, backend=args.backend))

    # headline scalars, one per (case, strategy) — dashboards and PR
    # diffs read these without walking the row arrays
    summary = {}
    for r in out:
        key = f"n{r['n']}_D{r['D']}_{r['strategy']}"
        summary[f"{key}_wall_ms"] = r["wall_ms"]
        summary[f"{key}_speedup_vs_seq"] = r["speedup_vs_seq"]

    record = {
        "bench": "mso_walltime",
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "device": jax.devices()[0].device_kind,
        "jax_backend": jax.default_backend(),
        "python": platform.python_version(),
        "mode": ("tiny" if args.tiny else "full" if args.full
                 else "default"),
        "posterior_backend": args.backend,
        "summary": summary,
        "rows": out,
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    print(f"wrote {args.out} ({len(out)} rows)")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
