"""Suggestions completed in the window over the window's whole length,
from its start to its last completion (host clock)."""
from bench.stats import window_rate


def read(run):
    return window_rate(run.n_completed, run.t_start, run.t_end) \
        if run.n_completed else None
