"""Fleet scheduler on the host (``engine/fleet.py``): the program's
``fleet.step`` spans minus the ``fleet.program.*`` spans inside them, per
step that served asks, over the steps that start after the profiler's
stop has returned."""
from bench.tracing import self_time, spans_named


def read(run):
    spans, n = run.clean_steps()
    steps = spans_named(spans, "fleet.step")
    if not steps or not n:
        return None
    return 1e-3 * self_time(steps, spans_named(spans, "fleet.program")) / n
