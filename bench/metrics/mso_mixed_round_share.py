"""Share of the fleet MSO's evaluation rounds in which one live lane
evaluated a line-search retry while another live lane evaluated the first
trial of a new iteration: rounds that a solver whose lanes all finish an
iteration's line search before any starts the next cannot have.  The
program's ``n_mso_mixed_rounds`` over ``n_rounds`` from
``stats_snapshot()``, end of window minus start, in percent."""


def read(run):
    a, b = run.counters_start, run.counters_end
    keys = ("n_mso_mixed_rounds", "n_rounds")
    if any(k not in c for c in (a, b) for k in keys):
        return None
    rounds = b["n_rounds"] - a["n_rounds"]
    if rounds <= 0:
        return None
    return 100.0 * (b["n_mso_mixed_rounds"] - a["n_mso_mixed_rounds"]) / rounds
