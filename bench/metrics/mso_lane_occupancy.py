"""Share of the fleet MSO's lane evaluations that were live: a requesting
study's restart still running, against the rows the lockstep loop
evaluates and throws away (restarts that stopped, idle slots, studies
that did not ask).  The program's ``n_points`` and ``n_padded`` counters
from ``stats_snapshot()``, end of window minus start, in percent."""


def read(run):
    a, b = run.counters_start, run.counters_end
    keys = ("n_points", "n_padded")
    if any(k not in c for c in (a, b) for k in keys):
        return None
    live = b["n_points"] - a["n_points"]
    lanes = live + b["n_padded"] - a["n_padded"]
    if lanes <= 0:
        return None
    return 100.0 * live / lanes
