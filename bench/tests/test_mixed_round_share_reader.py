"""CPU tests of the reader of the MSO's mixed-round counter, on
hand-built runs."""
from __future__ import annotations

import pytest

from bench import harness

# two steps of one block: 100 and 60 rounds, 120 of them mixed
START = {"n_steps": 1, "n_rounds": 50, "n_mso_mixed_rounds": 30}
DELTA = {"n_steps": 2, "n_rounds": 160, "n_mso_mixed_rounds": 120}


def _run(start, delta):
    return harness.Run(setup_s=0.0, counters_start=start,
                       counters_end={k: start[k] + delta[k] for k in start})


def test_mixed_round_share_reads_the_window():
    read = harness.load_reader("mso_mixed_round_share")
    assert read(_run(START, DELTA)) == pytest.approx(75.0)
    # a solve with no mixed round reads zero, not nothing
    assert read(_run(START, dict(DELTA, n_mso_mixed_rounds=0))) == 0.0


@pytest.mark.parametrize("case", ["no_counter", "no_rounds"])
def test_mixed_round_share_reads_nothing(case):
    read = harness.load_reader("mso_mixed_round_share")
    if case == "no_counter":
        # a program without the counter (the solver with a per-iteration
        # barrier) reads nothing, not a raise
        old = {k: START[k] for k in ("n_steps", "n_rounds")}
        run = _run(old, DELTA)
    else:
        # a window in which no round ran reads nothing either
        run = _run(START, {k: 0 for k in DELTA})
    assert read(run) is None
