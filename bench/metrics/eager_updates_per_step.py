"""Eager device updates of the fleet's slot blocks per fleet step, outside
its three programs: the scatters that ``observe`` (two per new
observation), admission, eviction and quarantine issue one at a time.
The program's ``n_eager_updates`` over ``n_steps`` from
``stats_snapshot()``, end of window minus start.  A count."""


def read(run):
    a, b = run.counters_start, run.counters_end
    keys = ("n_eager_updates", "n_steps")
    if any(k not in c for c in (a, b) for k in keys):
        return None
    steps = b["n_steps"] - a["n_steps"]
    if steps <= 0:
        return None
    return (b["n_eager_updates"] - a["n_eager_updates"]) / steps
