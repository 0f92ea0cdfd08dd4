"""Benchmark entry point — one section per paper table/figure.

``PYTHONPATH=src python -m benchmarks.run [--full]``

Prints ``name,us_per_call,derived`` CSV-style lines per section (reduced
CPU-scale settings by default; --full reproduces the paper's scale).
"""
import argparse
import sys
import time


def _section(title):
    print(f"\n# === {title} ===", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--skip-bo", action="store_true",
                    help="skip the end-to-end BO table (slowest section)")
    args, _ = ap.parse_known_args()

    import jax
    jax.config.update("jax_enable_x64", True)

    t0 = time.time()

    _section("Fig 1/3/4: off-diagonal artifacts (e_rel, offdiag mass)")
    from benchmarks import offdiag
    offdiag.main(full=args.full)

    _section("Fig 2/5: C-BE convergence slowdown vs B")
    from benchmarks import convergence
    convergence.main(full=args.full)

    _section("§5 cost model + wall-clock: MSO micro-benchmark")
    from benchmarks import mso_walltime
    mso_walltime.main(full=args.full)

    _section("kernels: Pallas interpret-mode correctness + XLA timing")
    from benchmarks import kernels
    kernels.main(full=args.full)

    if not args.skip_bo:
        _section("Table 1/2: end-to-end BO (reduced scale by default)")
        from benchmarks import bo_table
        bo_table.main(full=args.full)

    _section("roofline (from results/dryrun, if present)")
    import glob
    if glob.glob("results/dryrun/*.json"):
        from benchmarks import roofline
        sys.argv = ["roofline"]
        roofline.main()
    else:
        print("roofline,skipped,no results/dryrun jsons (run "
              "repro.launch.dryrun --sweep first)")

    print(f"\n# total benchmark wall time: {time.time() - t0:.0f}s")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
