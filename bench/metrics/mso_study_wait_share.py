"""Share of the lockstep rounds that the requesting studies sat frozen:
for each study, the rounds its solve ran after the last of its restarts
stopped, while other studies' restarts kept the loop going.  The
program's ``n_mso_study_wait_rounds`` over ``n_mso_study_rounds`` (rounds
times requesting studies) from ``stats_snapshot()``, end of window minus
start, in percent."""


def read(run):
    a, b = run.counters_start, run.counters_end
    keys = ("n_mso_study_wait_rounds", "n_mso_study_rounds")
    if any(k not in c for c in (a, b) for k in keys):
        return None
    rounds = b["n_mso_study_rounds"] - a["n_mso_study_rounds"]
    if rounds <= 0:
        return None
    wait = b["n_mso_study_wait_rounds"] - a["n_mso_study_wait_rounds"]
    return 100.0 * wait / rounds
