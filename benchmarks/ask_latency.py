"""Per-trial ask() latency: fused one-program suggest vs the host pipeline.

Runs full GPSampler BO loops (strategy=dbe_vec) and times every `ask()`:

* **unfused** (PR 1 host pipeline): from-scratch multi-start MAP `fit_gp`
  + host restart sampling + `run_lockstep` — per-trial O(n³) refit cost;
* **fused** (`engine/ask.py`): one compiled program per GP size bucket,
  rank-one incremental refits between `refit_interval`-spaced full MAP
  refits — steady-state trials skip both the O(n³) refactorization and
  the MAP optimization entirely.

Emits BENCH_ask.json: per-trial ask-latency trajectories, per-trial
refit kinds, steady-state medians, and exact compile counts (must stay
O(#size-buckets), not O(trials) — asserted with --check-compiles).

Steady-state definition (apples-to-apples): suggest trials that pay no
XLA trace and no bucket migration — for the fused run additionally the
trials that take the incremental (O(n²)) program, which is the
steady-state the fused pipeline is designed around.

--trace enables the obs span tracer for the whole run (off by default —
the obs contract): per-phase breakdowns land in the summary block, the
full Chrome-trace JSON in --trace-out, and --check-compiles still
asserts the O(#buckets) compile economy WITH tracing on (instrumentation
must never add traces).  --debug-nans arms the runtime FiniteGuard on
the two fused AskEngine programs.

Usage:
  python benchmarks/ask_latency.py [--tiny] [--trials N]
      [--backends xla pallas_interpret ...] [--check-compiles]
      [--trace] [--trace-out BENCH_ask_trace.json] [--debug-nans]
      [--out BENCH_ask.json]
"""
import argparse
import json
import platform
import time

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np                                     # noqa: E402

from repro.analysis.runtime import install_nan_guard, nan_guard_stats  # noqa: E402
from repro.bo.objectives import make_objective         # noqa: E402
from repro.bo.sampler import GPSampler                 # noqa: E402
from repro.bo.space import BoxSpace                    # noqa: E402
from repro.core.mso import MsoOptions                  # noqa: E402
from repro.gp.fit import pad_bucket_for                # noqa: E402
from repro.obs import export as obs_export             # noqa: E402
from repro.obs import trace as obs_trace               # noqa: E402


def run_bo(*, fused: bool, backend: str, trials: int, D: int, B: int,
           pad: int, refit_interval: int, n_startup: int, seed: int = 0,
           debug_nans: bool = False):
    obj = make_objective("sphere", D, seed=seed)
    space = BoxSpace.cube(D, *obj.bounds)
    s = GPSampler(space, strategy="dbe_vec", seed=seed,
                  n_startup_trials=n_startup, n_restarts=B,
                  pad_multiple=pad, posterior_backend=backend,
                  fused=fused, refit_interval=refit_interval,
                  mso_options=MsoOptions())
    ask_ms, kinds, buckets = [], [], []
    prev_bucket = 0
    for i in range(trials):
        if debug_nans and fused and s._ask is not None:
            install_nan_guard(s._ask)   # idempotent; engine is lazy-built
        n_done = sum(t.state == "complete" for t in s.trials)
        suggest = n_done >= n_startup
        bucket = pad_bucket_for(n_done, pad) if suggest else 0
        t0 = time.perf_counter()
        t = s.ask()
        ask_ms.append(1e3 * (time.perf_counter() - t0))
        if not suggest:
            kinds.append("startup")
        elif fused:
            kinds.append(s.last_ask_info.kind)
        else:
            kinds.append("host_fit" if bucket == prev_bucket
                         else "host_fit_newbucket")
        if suggest:
            buckets.append(bucket)
            prev_bucket = bucket
        s.tell(t.trial_id, obj(t.x))
    return s, ask_ms, kinds, sorted(set(buckets))


def steady_mask(kinds, fused: bool):
    """Steady-state trials: no trace, no bucket migration; for fused runs
    the incremental-program trials (its designed steady state)."""
    if fused:
        return [k == "incremental" for k in kinds]
    # host pipeline: same-bucket fit trials; bucket-migration trials pay
    # the fresh per-bucket traces and are excluded on both sides
    return [k == "host_fit" for k in kinds]


def bench_backend(backend: str, args) -> list:
    rows = []
    for fused in (False, True):
        s, ask_ms, kinds, buckets = run_bo(
            fused=fused, backend=backend, trials=args.trials, D=args.D,
            B=args.B, pad=args.pad, refit_interval=args.refit_interval,
            n_startup=args.n_startup, debug_nans=args.debug_nans)
        suggest_ms = [m for m, k in zip(ask_ms, kinds) if k != "startup"]
        sm = [m for m, keep in zip(ask_ms, steady_mask(kinds, fused))
              if keep]
        engine = s.stats.engine or {}
        row = {
            "backend": backend, "fused": fused, "trials": args.trials,
            "n_startup": args.n_startup, "D": args.D, "B": args.B,
            "pad": args.pad, "refit_interval": args.refit_interval,
            "gp_buckets": buckets,
            "ask_ms": [round(m, 3) for m in ask_ms],
            "kinds": kinds,
            "median_suggest_ms": float(np.median(suggest_ms)),
            "steady_ms": float(np.median(sm)) if sm else None,
            "n_steady_trials": len(sm),
            "best_y": s.best().y,
            "retrace_causes": (engine.get("retraces") or {}).get("causes"),
        }
        if fused:
            row["ask_stats"] = {k: engine.get(k) for k in
                                ("n_full_refits", "n_incremental",
                                 "n_fallbacks", "n_full_compiles",
                                 "n_incr_compiles", "n_ask_compiles")}
            if args.debug_nans and s._ask is not None:
                row["nan_guard"] = nan_guard_stats(s._ask)
        else:
            row["engine_compiles"] = engine.get("n_compiles")
            row["eval_rounds_total"] = engine.get("n_rounds")
            row["points_evaluated"] = engine.get("n_points")
        rows.append(row)
        steady = (f"{row['steady_ms']:.1f}ms" if row["steady_ms"]
                  is not None else "n/a")
        print(f"ask,{backend},fused={fused},"
              f"median={row['median_suggest_ms']:.1f}ms,"
              f"steady={steady},"
              f"buckets={len(buckets)}", flush=True)

    unf, fus = rows
    # too few trials for a steady state (e.g. --trials barely past
    # startup) ⇒ no steady speedup to report
    have_steady = (unf["steady_ms"] is not None
                   and fus["steady_ms"] is not None)
    speed = {
        "backend": backend,
        "speedup_steady": (unf["steady_ms"] / fus["steady_ms"]
                           if have_steady else None),
        "speedup_median": (unf["median_suggest_ms"]
                           / fus["median_suggest_ms"]),
    }
    if have_steady:
        print(f"ask,{backend},steady speedup "
              f"{speed['speedup_steady']:.2f}x, median speedup "
              f"{speed['speedup_median']:.2f}x", flush=True)
    else:
        print(f"ask,{backend},median speedup "
              f"{speed['speedup_median']:.2f}x (no steady-state trials)",
              flush=True)

    if args.check_compiles:
        n_buckets = len(fus["gp_buckets"])
        compiles = fus["ask_stats"]["n_ask_compiles"]
        n_suggests = args.trials - args.n_startup
        assert compiles <= 2 * n_buckets, \
            f"fused ask compiled {compiles}x for {n_buckets} buckets " \
            f"(must be <= 2/bucket, not O(trials)={n_suggests}); " \
            f"retrace causes: {fus['retrace_causes']}"
        # O(trials) sanity only meaningful once suggests outnumber the
        # per-bucket trace budget
        assert n_suggests <= 2 * n_buckets or compiles < n_suggests, \
            f"fused ask compiles {compiles} not < suggests {n_suggests}"
        print(f"ask,{backend},compile check OK "
              f"({compiles} traces / {n_buckets} buckets / "
              f"{n_suggests} suggests)", flush=True)
    return rows + [speed]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: few trials, small GP buckets")
    ap.add_argument("--trials", type=int, default=None)
    ap.add_argument("--backends", nargs="+", default=None,
                    choices=("xla", "pallas", "pallas_interpret"))
    ap.add_argument("--check-compiles", action="store_true")
    ap.add_argument("--trace", action="store_true",
                    help="enable the obs span tracer (off by default); "
                    "adds a per-phase breakdown to the summary and "
                    "writes the Chrome-trace JSON to --trace-out")
    ap.add_argument("--trace-out", default="BENCH_ask_trace.json")
    ap.add_argument("--debug-nans", action="store_true",
                    help="wrap the two fused AskEngine programs in a "
                    "finite-guard: every float leaf entering/leaving "
                    "them is checked (one host sync per call)")
    ap.add_argument("--out", default="BENCH_ask.json")
    args = ap.parse_args(argv)

    if args.tiny:
        args.trials = args.trials or 26
        args.D, args.B, args.pad = 3, 6, 8
        args.refit_interval, args.n_startup = 4, 6
        args.backends = args.backends or ["xla"]
    else:
        args.trials = args.trials or 150
        args.D, args.B, args.pad = 6, 10, 32
        args.refit_interval, args.n_startup = 8, 10
        args.backends = args.backends or ["xla", "pallas_interpret"]

    if args.trace:
        obs_trace.enable()

    out = []
    for backend in args.backends:
        out.extend(bench_backend(backend, args))

    # headline scalars, one per configuration (the speed rows carry no
    # "fused" key; per-run rows do)
    summary = {}
    if args.trace:
        events = obs_trace.get().events()
        summary["phase_breakdown"] = obs_export.phase_breakdown(events)
        obs_export.write_chrome_trace(
            args.trace_out, events, process_name="ask_latency",
            meta={"bench": "ask_latency"})
        print(f"wrote {args.trace_out} ({len(events)} trace events)")
    for r in out:
        if "fused" in r:
            tag = f"{r['backend']}_{'fused' if r['fused'] else 'unfused'}"
            summary[f"{tag}_median_suggest_ms"] = r["median_suggest_ms"]
            if r["steady_ms"] is not None:
                summary[f"{tag}_steady_ms"] = r["steady_ms"]
            if r["retrace_causes"] is not None:
                summary[f"{tag}_retrace_causes"] = r["retrace_causes"]
        else:
            summary[f"{r['backend']}_speedup_median"] = r["speedup_median"]
            if r["speedup_steady"] is not None:
                summary[f"{r['backend']}_speedup_steady"] = \
                    r["speedup_steady"]

    record = {
        "bench": "ask_latency",
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "device": jax.devices()[0].device_kind,
        "jax_backend": jax.default_backend(),
        "python": platform.python_version(),
        "mode": "tiny" if args.tiny else "default",
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
