"""Asks served per service step that served asks: the batch that DRR
forms from what arrived while the last step ran.  The ``served`` count
that ``service_step`` returned, from the harness's span around each
step, over the steps that start after the profiler's stop has
returned.  A count."""


def read(run):
    spans, n = run.clean_steps()
    if not n:
        return None
    return sum(sp.get("args", {}).get("served", 0) for sp in spans
               if sp.get("name") == "bench.service_step") / n
