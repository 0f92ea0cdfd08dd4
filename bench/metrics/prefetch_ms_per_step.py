"""The fleet sampler's prefetch stage (``bo/sampler.py``, inside
``fleet.ask_batch``): its ``fleet.prefetch`` spans, which cover each
study's observation sync and suggest request before the fleet step,
summed per step that served asks, over the steps that start after the
profiler's stop has returned."""
from bench.tracing import spans_named, total


def read(run):
    spans, n = run.clean_steps()
    pre = spans_named(spans, "fleet.prefetch")
    if not pre or not n:
        return None
    return 1e-3 * total(pre) / n
