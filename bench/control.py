#!/usr/bin/env python3
"""Readings of the check's numbers, for the program and for its control.

    python3 bench/control.py --workload <cell> SEED [SEED ...]

For each seed, one run of the cell on the chip at its own size (set-up and
its window's fixed work), then ``check_numbers`` twice over what that
window served: once for the program, and once for the control, which is
the reference in float32 put in the program's place (the next precision
below the float64 the deployment states).  One JSON line per seed; the
seeds share the process, so only the first compiles.  The benchmark's
own runs never run the control.  Limits are set from these readings, as
``PERF.md`` records.
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
    ap.add_argument("seeds", type=int, nargs="+")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import numpy as np
    import jax
    from bench import harness
    from bench.compile_meter import CompileMeter

    cell = harness.load_cell(args.workload, harness.load_spec())
    jax.config.update("jax_enable_x64", True)
    harness.use_compile_cache()
    try:
        harness.check_device(int(cell["cell"]["chips"]))
    except harness.BenchError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    run_dir = harness.RUNS / f"{args.workload}.control"
    t_process = T_PROCESS
    with CompileMeter() as meter:
        for seed in args.seeds:
            try:
                run = harness.run_cell(cell["config"], cell["mix"],
                                       seed=seed, trace=False, meter=meter,
                                       t_process=t_process, run_dir=run_dir)
                program = harness.check_numbers(run)
                control = harness.check_numbers(run, dtype=np.float32)
            finally:
                shutil.rmtree(run_dir, ignore_errors=True)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "asks": run.n_completed,
                              "setup_s": run.setup_s, "program": program,
                              "control": control,
                              "limits": cell["config"]["limits"]}),
                  flush=True)
            t_process = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
