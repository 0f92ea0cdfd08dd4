#!/usr/bin/env python3
"""Run one cell of the BO service's chip benchmark, once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` at the checkout's root.  The
run builds the cell's deployment from ``--seed``, sets it up, measures
a fixed amount of its traffic on the chip (the configuration sizes it to
fit ``--seconds``) and checks what the window served against a float64
host reference.  Its last stdout line is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``; ``checks`` last); its last stderr
lines give each compared number beside its limit.  Without a TPU, or
outside a checkout of the repository, it exits non-zero and prints no
result.
"""
import time

T_PROCESS = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: the system under test is missing: no {src}/repro",
              file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, src]
    from bench.harness import main as harness_main
    return harness_main(t_process=T_PROCESS)


if __name__ == "__main__":
    sys.exit(main())
