"""Requesting restarts per fleet step that the MSO stopped at its
``maxiter`` or on a failed line search, not at its tolerance: the
program's ``n_mso_capped_lanes`` over ``n_steps`` from
``stats_snapshot()``, end of window minus start.  A count."""


def read(run):
    a, b = run.counters_start, run.counters_end
    keys = ("n_mso_capped_lanes", "n_steps")
    if any(k not in c for c in (a, b) for k in keys):
        return None
    steps = b["n_steps"] - a["n_steps"]
    if steps <= 0:
        return None
    return (b["n_mso_capped_lanes"] - a["n_mso_capped_lanes"]) / steps
