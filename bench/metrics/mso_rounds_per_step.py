"""Lockstep rounds of the fleet MSO (the paper's D-BE loop) per fleet
step in the window: the program's ``n_rounds`` and ``n_steps`` counters
from ``stats_snapshot()``, end of window minus start.  A count."""


def read(run):
    a, b = run.counters_start, run.counters_end
    steps = b["n_steps"] - a["n_steps"]
    if steps <= 0:
        return None
    return (b["n_rounds"] - a["n_rounds"]) / steps
