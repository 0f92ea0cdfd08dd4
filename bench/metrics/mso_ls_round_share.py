"""Share of the fleet MSO's lockstep rounds spent on line-search retries:
each outer L-BFGS-B iteration takes as many rounds as its worst running
lane's Armijo backtracks, and every round past the first is a retry.  The
program's ``n_mso_ls_rounds`` over ``n_rounds`` from ``stats_snapshot()``,
end of window minus start, in percent."""


def read(run):
    a, b = run.counters_start, run.counters_end
    keys = ("n_mso_ls_rounds", "n_rounds")
    if any(k not in c for c in (a, b) for k in keys):
        return None
    rounds = b["n_rounds"] - a["n_rounds"]
    if rounds <= 0:
        return None
    return 100.0 * (b["n_mso_ls_rounds"] - a["n_mso_ls_rounds"]) / rounds
