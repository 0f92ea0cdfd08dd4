"""CPU tests of the readers of the MSO lockstep counters, the fleet's stage
spans and the service's request spans, on hand-built runs."""
from __future__ import annotations

import pytest

from bench import harness, stats

COUNTER_METRICS = ("mso_study_wait_share", "mso_ls_round_share",
                   "mso_capped_lanes_per_step", "eager_updates_per_step")
SPAN_METRICS = ("prefetch_ms_per_step", "svc_wait_ms_p95")

# two steps of one 640-lane block: 100 and 60 rounds
START = {"n_steps": 1, "n_rounds": 50, "n_points": 1000, "n_padded": 31000,
         "n_mso_ls_rounds": 5, "n_mso_study_rounds": 3200,
         "n_mso_study_wait_rounds": 1600, "n_mso_capped_lanes": 2,
         "n_eager_updates": 300}
DELTA = {"n_steps": 2, "n_rounds": 160, "n_points": 25600,
         "n_padded": 160 * 640 - 25600, "n_mso_ls_rounds": 40,
         "n_mso_study_rounds": 160 * 64,
         "n_mso_study_wait_rounds": 160 * 64 // 4,
         "n_mso_capped_lanes": 7, "n_eager_updates": 256}


def _span(name, ts, dur):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur}


def _counter_run(start):
    return harness.Run(setup_s=0.0, counters_start=start,
                       counters_end={k: start[k] + DELTA[k] for k in start})


def test_lockstep_counter_metrics():
    run = _counter_run(START)
    read = {m: harness.load_reader(m)(run)
            for m in ("mso_lane_occupancy",) + COUNTER_METRICS}
    assert read == pytest.approx({
        "mso_lane_occupancy": 25.0, "mso_study_wait_share": 25.0,
        "mso_ls_round_share": 25.0, "mso_capped_lanes_per_step": 3.5,
        "eager_updates_per_step": 128.0})
    # a program without the lockstep counters still has n_points, n_padded
    old = {k: START[k] for k in ("n_steps", "n_rounds", "n_points",
                                 "n_padded")}
    assert harness.load_reader("mso_lane_occupancy")(_counter_run(old)) == \
        pytest.approx(25.0)


@pytest.mark.parametrize("metric", COUNTER_METRICS)
def test_counter_readers_read_nothing_without_counters_or_steps(metric):
    # a program without the lockstep counters reads nothing, not a raise
    old = {k: START[k] for k in ("n_steps", "n_rounds", "n_points",
                                 "n_padded")}
    read = harness.load_reader(metric)
    assert read(_counter_run(old)) is None
    # a window in which no step ran reads nothing either
    assert read(harness.Run(setup_s=0.0, counters_start=START,
                            counters_end=dict(START))) is None


def test_stage_and_request_span_metrics():
    spans = []
    for k in range(3):               # 3 steps of 1000 us each
        t = 2000.0 * k
        spans += [dict(_span("bench.service_step", t, 1000.0),
                       args={"served": 2}),
                  _span("fleet.ask_batch", t + 10.0, 980.0),
                  _span("fleet.prefetch", t + 20.0, 300.0 + 100.0 * k),
                  _span("fleet.step", t + 500.0, 300.0),
                  _span("fleet.deliver", t + 850.0, 100.0)]
        # two requests a step, submitted before it, dispatched in it
        for i, wait in enumerate((100.0 * (k + 1), 1000.0 * (k + 1))):
            sub = t - wait + 30.0
            spans.append(dict(_span("svc.request", sub, wait + 900.0),
                              args={"rid": 2 * k + i, "tenant": "a",
                                    "study": i, "attempts": 1,
                                    "state": "done",
                                    "dispatch_us": sub + wait}))
    # shed while queued: never dispatched
    spans.append(dict(_span("svc.request", 100.0, 50.0),
                      args={"rid": 99, "dispatch_us": None}))
    prefetch = harness.load_reader("prefetch_ms_per_step")
    wait = harness.load_reader("svc_wait_ms_p95")
    run = harness.Run(setup_s=0.0, spans=spans, n_steps=3)
    assert prefetch(run) == pytest.approx(0.4)
    # waits 100, 200, 300, 1000, 2000, 3000 us
    assert wait(run) == pytest.approx(1e-3 * stats.percentile(
        [100, 200, 300, 1000, 2000, 3000], 95))
    # from step 1 on: prefetch of steps 1-2, waits dispatched from 2000 us
    late = harness.Run(setup_s=0.0, spans=spans, n_steps=3,
                       span_from_us=1500.0)
    assert prefetch(late) == pytest.approx(0.45)
    assert wait(late) == pytest.approx(1e-3 * stats.percentile(
        [200, 300, 2000, 3000], 95))
    assert wait(harness.Run(setup_s=0.0, spans=spans,
                            span_from_us=9000.0)) is None


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_stage_and_request_readers_return_nothing_without_spans(metric):
    assert harness.load_reader(metric)(
        harness.Run(setup_s=0.0, n_steps=3)) is None
