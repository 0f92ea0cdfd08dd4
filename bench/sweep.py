#!/usr/bin/env python3
"""The sweep that fixes an open-loop cell's rate: its mix at several rates.

    python3 bench/sweep.py --workload <cell> --seed <n> RATE [RATE ...]

For each rate (asks a second, all tenants together), one run of the
cell's configuration under its open mix at that rate, with ``arrivals``
the rate times ``--schedule-s`` (40 s), in one process, so that only the
first run compiles.  One JSON line a rate: every ask served or not, the
median latency over the schedule's first and last thirds, p50 and p95,
asks per step and the window's length.  A rate is sustained where every
ask is served and the last third's median is at most 1.1 times the
first's; the cell runs at 4/5 of the highest rate sustained.  No check
against the reference: the benchmark's own runs make it.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--schedule-s", type=float, default=40.0)
    ap.add_argument("rates", type=float, nargs="+")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    from bench import harness, stats
    from bench.compile_meter import CompileMeter

    cell = harness.load_cell(args.workload, harness.load_spec())
    if cell["mix"]["loop"] != "open":
        print("sweep: the cell's mix is not an open loop", file=sys.stderr)
        return 2
    jax.config.update("jax_enable_x64", True)
    harness.use_compile_cache()
    try:
        harness.check_device(int(cell["cell"]["chips"]))
    except harness.BenchError as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    run_dir = harness.RUNS / f"{args.workload}.sweep"
    t_process = T_PROCESS
    with CompileMeter() as meter:
        for rate in args.rates:
            mix = dict(cell["mix"], rate_per_s=rate,
                       arrivals=round(rate * args.schedule_s))
            try:
                run = harness.run_cell(cell["config"], mix, seed=args.seed,
                                       trace=False, meter=meter,
                                       t_process=t_process, run_dir=run_dir)
            finally:
                shutil.rmtree(run_dir, ignore_errors=True)
            asks = sorted(run.asks, key=lambda a: a.due)
            third = len(asks) // 3

            def median(part):
                lat = [a.done - a.due for a in part if a.done is not None]
                return stats.percentile(lat, 50.0) if lat else None
            first, last = median(asks[:third]), median(asks[-third:])
            lat = run.latencies
            print(json.dumps({
                "workload": args.workload, "seed": args.seed,
                "rate_per_s": rate, "arrivals": mix["arrivals"],
                "unserved": len(asks) - run.n_completed,
                "p50_first_third_s": first, "p50_last_third_s": last,
                "sustained": bool(run.n_completed == len(asks)
                                  and last <= 1.1 * first),
                "ask_p50_s": stats.percentile(lat, 50.0),
                "ask_p95_s": stats.percentile(lat, 95.0),
                "asks_per_step": run.n_completed / max(run.n_steps, 1),
                "window_s": run.t_end - run.t_start,
                "setup_s": run.setup_s,
                "window_compiles": run.window_compiles}), flush=True)
            t_process = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
