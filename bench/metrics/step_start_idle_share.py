"""Share of the start of a window step in which no operation ran on the
device: 1 − (union of the device's op intervals / traced window), in
percent, from the JAX profiler's trace of the window's first
``TRACE_SECONDS``.  That stretch is the first step's host-side
``ask_batch`` prefetch and dispatch and the start of its MSO; it is not
a whole step's idle share."""
from bench.tracing import device_busy


def read(run):
    if run.trace is None:
        return None
    window = run.trace_hi_ns - run.trace_lo_ns
    busy = device_busy(run.trace, run.trace_lo_ns, run.trace_hi_ns)
    if busy is None or window <= 0:
        return None
    return 100.0 * (1.0 - busy / (1e-9 * window))
