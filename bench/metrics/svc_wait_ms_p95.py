"""Queueing in the service (``serve/bo_service.py``): the 95th percentile
of how long an ask waited from its submit to its last dispatch, from the
``svc.request`` span each request records when it ends (``ts`` is the
submit, ``dispatch_us`` the dispatch, both on the tracer's clock), over
the requests dispatched after the profiler's stop has returned."""
from bench.stats import percentile


def read(run):
    waits = []
    for sp in run.spans:
        if sp.get("name") != "svc.request" or sp.get("ph") != "X":
            continue
        at = sp.get("args", {}).get("dispatch_us")
        if at is not None and at >= run.span_from_us:
            waits.append(at - sp["ts"])
    return 1e-3 * percentile(waits, 95.0) if waits else None
