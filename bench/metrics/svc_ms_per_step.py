"""Service layer (``serve/bo_service.py``): the harness's span around each
``service_step`` minus the program's ``fleet.step`` span inside it, per
step that served asks, over the steps that start after the profiler's
stop has returned.  Covers DRR, the overload ladder, dispatch, and the
journal appends of dispatch and delivery."""
from bench.tracing import self_time, spans_named


def read(run):
    spans, n = run.clean_steps()
    steps = spans_named(spans, "bench.service_step")
    if not steps or not n:
        return None
    return 1e-3 * self_time(steps, spans_named(spans, "fleet.step")) / n
