"""95th percentile of ask latency over every ask completed in the window,
from when the ask was due to when its suggestion came back (host clock)."""
from bench.stats import percentile


def read(run):
    lat = run.latencies
    return percentile(lat, 95.0) if lat else None
