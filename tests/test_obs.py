"""Unified telemetry plane tests: tracer semantics (off-by-default,
ring bounds, thread safety), ProgramTimer passthrough, the fleet and
service spans and lockstep counters, the unified ``stats_snapshot()``
schema contract across all five engine layers, retrace-report merging
and the retrace-history cap, the AskEngine NaN guard, and Chrome-trace
export from both live tracers and WAL journals."""
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from faults import VirtualClock
from repro.analysis.runtime import (FiniteGuard, NonFiniteError,
                                    install_nan_guard, nan_guard_stats)
from repro.bo.sampler import FleetSampler, GPSampler
from repro.bo.space import BoxSpace
from repro.core.acquisition import logei_acq
from repro.core.mso import MsoOptions
from repro.engine import (AskConfig, AskEngine, EvalEngine, FleetConfig,
                          FleetEngine)
from repro.engine.cache import (CountingJit, merge_retrace_reports,
                                retrace_report)
from repro.obs import export as obs_export
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.serve.bo_service import BOService, TenantConfig

_MSO = MsoOptions(maxiter=40, pgtol=1e-2)


def _sphere(x):
    return float(np.sum((x - 0.4) ** 2))


@pytest.fixture(autouse=True)
def _tracer_off():
    """Every test starts and ends with the off-by-default contract."""
    obs_trace.disable()
    yield
    obs_trace.disable()


# ================================================================ tracer
def test_tracer_disabled_is_noop():
    assert not obs_trace.enabled() and obs_trace.get() is None
    with obs_trace.span("x", a=1):
        pass
    obs_trace.instant("y")
    assert obs_trace.get() is None          # still nothing to record into


def test_tracer_span_and_instant_shapes():
    tr = obs_trace.enable()
    with obs_trace.span("phase", bucket=8):
        obs_trace.instant("tick", n=3)
    evs = tr.events()
    assert [e["ph"] for e in evs] == ["i", "X"]   # span closes after
    inst, sp = evs
    assert inst["name"] == "tick" and inst["s"] == "t"
    assert inst["args"] == {"n": 3}
    assert sp["name"] == "phase" and sp["dur"] >= 0
    assert sp["args"] == {"bucket": 8}
    assert sp["ts"] <= inst["ts"]


def test_span_body_extends_args():
    """The body gets the span's args and may add what it learns by the
    end; with tracing off it gets the dict all the same."""
    with obs_trace.span("off", a=1) as args:
        args["b"] = 2
    tr = obs_trace.enable()
    with obs_trace.span("solve", bucket=8) as args:
        args.update(rounds=5)
    assert tr.events()[0]["args"] == {"bucket": 8, "rounds": 5}


def test_tracer_ring_drops_oldest():
    tr = obs_trace.enable(capacity=8)
    for i in range(20):
        obs_trace.instant(f"e{i}")
    evs = tr.events()
    assert len(evs) == 8
    assert [e["name"] for e in evs] == [f"e{i}" for i in range(12, 20)]
    assert tr.n_recorded == 20 and tr.n_dropped == 12
    tr.clear()
    assert tr.events() == [] and tr.n_recorded == 0


def test_tracer_thread_safety():
    tr = obs_trace.enable()
    n_threads, per = 4, 500

    def work(k):
        for i in range(per):
            obs_trace.instant(f"t{k}", i=i)

    threads = [threading.Thread(target=work, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert tr.n_recorded == n_threads * per
    assert len(tr.events()) == n_threads * per


class _FakeProgram:
    def __init__(self):
        self.n_compiles = 0
        self.n_calls = 0

    def __call__(self, x):
        self.n_calls += 1
        if self.n_calls == 1:
            self.n_compiles += 1            # "traces" on first call
        return x

    def retrace_summary(self):
        return {"causes": {"first-trace": 1}, "events": []}


def test_program_timer_passthrough_and_spans():
    inner = _FakeProgram()
    pt = obs_trace.ProgramTimer(inner, "prog")
    assert pt(7) == 7                       # disabled: pure passthrough
    assert pt.n_compiles == 1               # attribute forwarding
    assert pt.retrace_summary()["causes"] == {"first-trace": 1}

    tr = obs_trace.enable()
    assert pt(jnp.asarray(1.0)) == 1.0
    (ev,) = tr.events()
    assert ev["name"] == "prog" and ev["ph"] == "X"
    assert ev["args"]["compiled"] is False  # second call: cache hit
    assert inner.n_calls == 2


# ============================================= snapshot schema (sat. 1)
def _fleet_kw(**over):
    kw = dict(n_startup_trials=4, n_restarts=4, pad_multiple=8, slots=4,
              posterior_backend="xla", refit_interval=1, warm_start=False,
              mso_options=MsoOptions(**vars(_MSO)))
    kw.update(over)
    return kw


def test_snapshot_schema_all_layers(tmp_path):
    """The four documented stats_snapshot() layouts (plus the EvalEngine
    block they compose over) match the live objects exactly — the shapes
    can't silently drift from the schema again."""
    v = obs_metrics.validate_snapshot

    assert v("eval_engine", EvalEngine(logei_acq).stats_snapshot()) == []

    ask = AskEngine(EvalEngine(logei_acq),
                    AskConfig(dim=2, n_restarts=4, pad_bucket=8,
                              refit_interval=4))
    assert v("ask_engine", ask.stats_snapshot()) == []

    fleet = FleetEngine(EvalEngine(logei_acq),
                        FleetConfig(dim=2, n_restarts=4, slots=2,
                                    pad_bucket=8))
    assert v("fleet_engine", fleet.stats_snapshot()) == []

    fs = FleetSampler(BoxSpace.cube(2, 0.0, 1.0), n_studies=1, seed=0,
                      **_fleet_kw())
    assert v("fleet_sampler", fs.stats_snapshot()) == []

    # journaled plane: the optional journal_seq key is accepted
    clock = VirtualClock()
    fsj = FleetSampler([BoxSpace.cube(2, 0.0, 1.0)], seed=0,
                       journal_dir=str(tmp_path), sleep_fn=clock.sleep,
                       **_fleet_kw())
    svc = BOService(fsj, [TenantConfig("a", studies=(0,))], clock=clock)
    r = svc.submit_ask("a", 0)
    svc.service_step()
    assert r.done
    svc.submit_tell("a", 0, r.result.trial_id, _sphere(r.result.x))
    svc.service_step()
    snap = svc.stats_snapshot()
    assert "journal_seq" in snap
    assert v("bo_service", snap) == []
    # the lockstep and eager-update counters are part of the contract
    for key in ("n_mso_solves", "n_mso_iters", "n_mso_ls_rounds",
                "n_mso_mixed_rounds", "n_mso_study_rounds",
                "n_mso_study_wait_rounds", "n_mso_capped_lanes",
                "n_eager_updates"):
        assert key in obs_metrics.FLEET_ENGINE_KEYS
        assert isinstance(snap[key], int)
        bad = dict(snap)
        bad.pop(key)
        assert v("bo_service", bad)


def test_validate_snapshot_flags_drift():
    good = EvalEngine(logei_acq).stats_snapshot()
    bad = dict(good)
    bad.pop("n_rounds")
    bad["n_new_thing"] = 1
    errs = obs_metrics.validate_snapshot("eval_engine", bad)
    assert any("missing" in e and "n_rounds" in e for e in errs)
    assert any("unexpected" in e and "n_new_thing" in e for e in errs)
    assert obs_metrics.validate_snapshot("nope", good)


def test_fleet_stage_spans_and_solve_args(tmp_path):
    """A traced service step nests ``fleet.prefetch`` (observation sync
    and requests) and ``fleet.deliver`` (collect and journal) inside
    ``fleet.ask_batch``, and each ``fleet.step_block`` span carries its
    solve's lockstep numbers, which add up to the counters."""
    clock = VirtualClock()
    fs = FleetSampler([BoxSpace.cube(2, 0.0, 1.0)] * 2, seed=0,
                      journal_dir=str(tmp_path), sleep_fn=clock.sleep,
                      **_fleet_kw())
    owner = {0: "a", 1: "b"}
    svc = BOService(fs, [TenantConfig(t, studies=(s,))
                         for s, t in owner.items()], clock=clock)

    def round_():
        reqs = [svc.submit_ask(t, s) for s, t in owner.items()]
        assert svc.service_step() == 2
        for (s, t), r in zip(owner.items(), reqs):
            svc.submit_tell(t, s, r.result.trial_id, _sphere(r.result.x))

    for _ in range(4):                       # random start-up trials
        round_()
    before = svc.stats_snapshot()
    tr = obs_trace.enable()
    for _ in range(2):                       # two GP rounds
        round_()
    after = svc.stats_snapshot()
    evs = [e for e in tr.events() if e["ph"] == "X"]
    named = {n: [e for e in evs if e["name"] == n]
             for n in ("fleet.ask_batch", "fleet.prefetch", "fleet.deliver",
                       "fleet.step", "fleet.step_block")}
    assert all(len(v) == 2 for v in named.values())
    for outer, pre, dlv, step in zip(named["fleet.ask_batch"],
                                     named["fleet.prefetch"],
                                     named["fleet.deliver"],
                                     named["fleet.step"]):
        end = outer["ts"] + outer["dur"]
        assert outer["ts"] <= pre["ts"] and pre["ts"] + pre["dur"] <= \
            step["ts"] <= step["ts"] + step["dur"] <= dlv["ts"]
        assert dlv["ts"] + dlv["dur"] <= end + 1e-3
    solves = [e["args"] for e in named["fleet.step_block"]]
    for a in solves:
        assert a["rounds"] == 1 + a["iters"] + a["ls_rounds"]
        assert 0 <= a["wait_rounds"] < a["rounds"]
        assert 0 <= a["mixed"] <= a["rounds"] - 1
    for key, arg in (("n_rounds", "rounds"), ("n_mso_iters", "iters"),
                     ("n_mso_ls_rounds", "ls_rounds"),
                     ("n_mso_study_wait_rounds", "wait_rounds"),
                     ("n_mso_capped_lanes", "capped"),
                     ("n_mso_mixed_rounds", "mixed")):
        assert after[key] - before[key] == sum(a[arg] for a in solves)
    assert after["n_mso_solves"] - before["n_mso_solves"] == 2
    assert 0 <= after["n_mso_mixed_rounds"] - before["n_mso_mixed_rounds"] \
        <= (after["n_rounds"] - before["n_rounds"]
            - (after["n_mso_solves"] - before["n_mso_solves"]))
    # each GP round observes both studies' last trials in their slots
    assert after["n_eager_updates"] - before["n_eager_updates"] == 2 * 2 * 2


# ====================================== retrace accounting (sat. 2)
def test_merge_retrace_reports():
    a = {"causes": {"first-trace": 2, "shape": 1},
         "by_program": {"eval": {"first-trace": 2, "shape": 1}}}
    b = {"causes": {"first-trace": 3, "dtype": 1},
         "by_program": {"full": {"first-trace": 3, "dtype": 1}}}
    m = merge_retrace_reports(a, b)
    assert m["causes"] == {"first-trace": 5, "shape": 1, "dtype": 1}
    assert set(m["by_program"]) == {"eval", "full"}
    assert m["by_program"]["full"]["dtype"] == 1
    # empty merge and identity
    assert merge_retrace_reports() == {"causes": {}, "by_program": {}}
    assert merge_retrace_reports(a)["causes"] == a["causes"]


def test_retrace_report_aggregates_programs():
    cj = CountingJit(lambda x: x * 2, name="dbl")
    for n in (2, 3):                        # two shapes -> two traces
        cj(jnp.zeros(n))
    rep = retrace_report({"dbl": cj})
    assert sum(rep["causes"].values()) == 2
    assert rep["by_program"]["dbl"] == rep["causes"]


def test_retrace_event_history_is_capped(monkeypatch):
    """retrace_events must stay bounded however often a program retraces
    (the flight recorder keeps counters exact, history truncated)."""
    import repro.engine.cache as cache_mod
    monkeypatch.setattr(cache_mod, "_MAX_EVENTS", 4)
    cj = CountingJit(lambda x: x + 1, name="grow")
    for n in range(1, 11):                  # 10 distinct shapes
        cj(jnp.zeros(n))
    assert cj.n_compiles == 10              # counter stays exact
    assert len(cj.retrace_events) == 4      # history capped
    causes = cj.retrace_summary()["causes"]
    assert sum(causes.values()) == 4


# =================================== instrumentation stays trace-free
def _tiny_sampler(seed=3):
    return GPSampler(BoxSpace.cube(2, -1.0, 1.0), strategy="dbe_vec",
                     seed=seed, n_startup_trials=4, n_restarts=4,
                     fused=True, refit_interval=4, pad_multiple=8,
                     posterior_backend="xla", mso_options=_MSO)


def test_compile_counts_identical_with_tracing_on():
    """The obs contract's hard bar: enabling the tracer changes what gets
    *measured*, never what gets *compiled*."""
    s_off = _tiny_sampler()
    s_off.optimize(_sphere, 12)
    off = s_off.stats.engine

    tr = obs_trace.enable()
    s_on = _tiny_sampler()
    s_on.optimize(_sphere, 12)
    on = s_on.stats.engine

    for k in ("n_full_compiles", "n_incr_compiles", "n_ask_compiles"):
        assert on[k] == off[k], (k, on[k], off[k])
    assert on["retraces"]["causes"] == off["retraces"]["causes"]
    names = {e["name"] for e in tr.events()}
    assert "ask.suggest" in names           # ...and the run was traced
    assert any(n.startswith("ask.phase.") or n.startswith("ask.program.")
               for n in names)


# ================================================= NaN guard (sat. 3)
def test_nan_guard_on_solo_ask_engine():
    """install_nan_guard covers the two fused AskEngine programs (not
    just the fleet plane) and is idempotent over ProgramTimer stacking."""
    ask = AskEngine(EvalEngine(logei_acq),
                    AskConfig(dim=2, n_restarts=4, pad_bucket=8,
                              refit_interval=4))
    assert nan_guard_stats(ask) == {"installed": False,
                                    "n_guard_checks": 0}
    g1 = list(install_nan_guard(ask))
    g2 = list(install_nan_guard(ask))       # idempotent re-install
    assert len(g1) == 2 and [a is b for a, b in zip(g1, g2)] == [True] * 2
    assert isinstance(ask._full_jit, FiniteGuard)
    assert nan_guard_stats(ask)["installed"]

    rng = np.random.default_rng(0)
    for _ in range(5):
        xi = rng.uniform(0, 1, 2)
        ask.observe(xi, _sphere(xi))
    ask.suggest(jax.random.PRNGKey(0), fit_seed=0)
    assert nan_guard_stats(ask)["n_guard_checks"] >= 1


def test_nan_guard_trip_reports_obs_instant():
    tr = obs_trace.enable()
    guard = FiniteGuard(lambda x: x, "full")
    with pytest.raises(NonFiniteError, match="guarded program 'full'"):
        guard(jnp.asarray([1.0, float("nan")]))
    (ev,) = [e for e in tr.events() if e["name"] == "nan_guard.nonfinite"]
    assert ev["args"]["program"] == "full"
    assert ev["args"]["direction"] == "inputs"


# ================================================== export (live + WAL)
def test_live_chrome_trace_roundtrip(tmp_path):
    obs_trace.enable()
    with obs_trace.span("ask.phase.refit", n=4):
        pass
    obs_trace.instant("retrace", program="full", cause="shape")
    events = obs_trace.get().events()
    path = str(tmp_path / "trace.json")
    obs_export.write_chrome_trace(path, events, process_name="test",
                                  meta={"bench": "test"})
    with open(path) as f:
        obj = json.load(f)
    assert obs_export.validate_chrome_trace(obj) == []
    assert obj["displayTimeUnit"] == "ms"
    assert obj["otherData"] == {"bench": "test"}
    names = [e["name"] for e in obj["traceEvents"]]
    assert "process_name" in names          # pid metadata present
    assert "ask.phase.refit" in names and "retrace" in names


def test_validate_chrome_trace_rejects_malformed():
    assert obs_export.validate_chrome_trace([]) \
        == ["top level is list, expected object"]
    assert obs_export.validate_chrome_trace({}) \
        == ["traceEvents missing or not a list"]
    errs = obs_export.validate_chrome_trace({"traceEvents": [
        {"name": "a", "ph": "X", "pid": 1, "tid": 1, "ts": 0.0},
        {"ph": "i", "pid": "x", "tid": 1, "ts": 0.0, "args": 3},
    ]})
    assert any("dur" in e for e in errs)
    assert any("'name'" in e for e in errs)
    assert any("integer 'pid'" in e for e in errs)
    assert any("'args'" in e for e in errs)


def test_phase_breakdown():
    evs = [{"name": "a", "ph": "X", "ts": 0, "dur": 1000.0},
           {"name": "a", "ph": "X", "ts": 0, "dur": 3000.0},
           {"name": "b", "ph": "X", "ts": 0, "dur": 500.0},
           {"name": "c", "ph": "i", "ts": 0}]
    bd = obs_export.phase_breakdown(evs)
    assert set(bd) == {"a", "b"}            # instants excluded
    assert bd["a"]["count"] == 2 and bd["a"]["total_ms"] == 4.0
    assert bd["a"]["p50_ms"] == 2.0         # linear interp between 1, 3
    assert bd["b"]["p99_ms"] == 0.5


def _journaled_service(tmp_path):
    clock = VirtualClock()
    fs = FleetSampler([BoxSpace.cube(2, 0.0, 1.0)] * 2, seed=0,
                      journal_dir=str(tmp_path), sleep_fn=clock.sleep,
                      **_fleet_kw())
    svc = BOService(fs, [TenantConfig("a", studies=(0,)),
                         TenantConfig("b", studies=(1,))], clock=clock)
    return svc, clock


def test_timeline_from_journal(tmp_path):
    """WAL → Perfetto reconstruction: valid Chrome trace with request
    lifecycle spans on tenant tracks and fleet ops on study tracks —
    with tracing off (the post-mortem path needs no live tracer)."""
    svc, _ = _journaled_service(tmp_path)
    reqs = [svc.submit_ask(t, s) for t, s in (("a", 0), ("b", 1))]
    for _ in range(4):
        svc.service_step()
    assert all(r.done for r in reqs)
    for r in reqs:
        svc.submit_tell(r.tenant, r.study, r.result.trial_id,
                        _sphere(r.result.x))
    svc.service_step()
    inflight = svc.submit_ask("a", 0)       # left open: crash-visible
    assert not inflight.done

    trace = obs_export.timeline_from_journal(str(tmp_path))
    assert obs_export.validate_chrome_trace(trace) == []
    assert trace["otherData"]["source"] == "wal-journal"
    assert trace["otherData"]["n_records"] > 0

    evs = trace["traceEvents"]
    spans = [e for e in evs if e["ph"] == "X"]
    done = [e for e in spans if e["name"] == "request"]
    assert len(done) == 2                   # one lifecycle span per ask
    assert {e["args"]["tenant"] for e in done} == {"a", "b"}
    open_spans = [e for e in spans if e["name"] == "request(inflight)"]
    assert len(open_spans) == 1 and open_spans[0]["args"]["open"]
    # both planes present, with named tracks
    pids = {e["pid"] for e in evs}
    assert {obs_export.FLEET_PID, obs_export.SVC_PID} <= pids
    tnames = [e["args"]["name"] for e in evs
              if e["ph"] == "M" and e["name"] == "thread_name"]
    assert "tenant a" in tnames and "scheduler" in tnames


def test_obs_cli_timeline_and_validate(tmp_path, capsys):
    from repro.obs.__main__ import main as obs_main

    svc, _ = _journaled_service(tmp_path)
    r = svc.submit_ask("a", 0)
    svc.service_step()
    assert r.done

    out = str(tmp_path / "timeline.json")
    assert obs_main(["timeline", str(tmp_path), "-o", out]) == 0
    assert obs_main(["validate", out]) == 0
    bad = str(tmp_path / "bad.json")
    with open(bad, "w") as f:
        json.dump({"traceEvents": [{"oops": 1}]}, f)
    assert obs_main(["validate", bad]) == 1
    capsys.readouterr()


def test_obs_cli_overhead_budget():
    from repro.obs.__main__ import main as obs_main

    assert obs_main(["overhead", "--n", "20000"]) == 0
    # an impossible budget must fail loudly, not silently pass
    assert obs_main(["overhead", "--n", "2000",
                     "--budget-ns", "0.0001"]) == 1
