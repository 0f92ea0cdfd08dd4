"""The fleet MSO program (``fleet.program.mso``, the lockstep L-BFGS-B of
``core/lbfgsb.py``): its spans, which run to ``block_until_ready``,
summed per step that served asks, over the steps that start after the
profiler's stop has returned."""
from bench.tracing import spans_named, total


def read(run):
    spans, n = run.clean_steps()
    progs = spans_named(spans, "fleet.program.mso")
    if not progs or not n:
        return None
    return 1e-3 * total(progs) / n
