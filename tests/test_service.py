"""BO-as-a-service tests: DRR weighted fairness / starvation freedom,
deadline budgets under a virtual clock, bounded backoff retries (service
and engine level), the overload ladder, drain semantics, journal replay
of in-flight service requests, and the out-of-order tell property.

Everything timing-related runs on :class:`faults.VirtualClock` — no real
sleeps, no wall-clock margins."""
import numpy as np
import pytest

from hypothesis import given, settings
from hypothesis import strategies as st
from faults import FaultInjector, VirtualClock
from repro.bo.journal import InjectedCrash, StudyJournal
from repro.bo.sampler import FleetSampler
from repro.bo.space import BoxSpace
from repro.core.mso import MsoOptions
from repro.engine import FleetFullError
from repro.serve.bo_service import (BOService, DeadlineExceeded,
                                    OverloadConfig, RequestFailed,
                                    ServiceDraining, TenantConfig,
                                    TenantShedError)
import os

_MSO = MsoOptions(maxiter=40, pgtol=1e-2)


def _sphere(x):
    return float(np.sum((x - 0.4) ** 2))


def _fleet_kw(**over):
    kw = dict(n_startup_trials=4, n_restarts=4, pad_multiple=8, slots=4,
              posterior_backend="xla", refit_interval=1, warm_start=False,
              mso_options=MsoOptions(**vars(_MSO)))
    kw.update(over)
    return kw


def _journal_records(d):
    path = os.path.join(d, "journal.log")
    return StudyJournal._scan_and_truncate(path, truncate=False)[0]


def _mk_service(n_studies, tenants, *, journal_dir=None, fi=None,
                clock=None, fleet_over=None, **svc_kw):
    clock = clock if clock is not None else VirtualClock()
    fs = FleetSampler([BoxSpace.cube(2, 0.0, 1.0)] * n_studies, seed=0,
                      journal_dir=journal_dir, fault_injector=fi,
                      sleep_fn=clock.sleep, **_fleet_kw(
                          **(fleet_over or {})))
    return BOService(fs, tenants, clock=clock, **svc_kw), clock


def _serve(svc, reqs, max_steps=50):
    for _ in range(max_steps):
        if all(r.done for r in reqs):
            return
        svc.service_step()
    raise AssertionError(
        f"requests not served: {[(r.rid, r.state) for r in reqs]}")


# ============================================= DRR fairness / starvation
def test_drr_weighted_fairness_and_no_starvation():
    """A heavy tenant flooding its queues must not delay a light
    tenant's requests: DRR gives the light tenant its weighted share
    every round, so its per-request latency is bounded (one round)
    regardless of the flood."""
    svc, _ = _mk_service(4, [
        TenantConfig("heavy", weight=2.0, studies=(0, 1)),
        TenantConfig("light", weight=1.0, studies=(2,)),
        TenantConfig("slow", weight=0.5, studies=(3,)),
    ])
    flood = [svc.submit_ask("heavy", s) for _ in range(6) for s in (0, 1)]
    slow_reqs = []
    for rnd in range(8):
        light = svc.submit_ask("light", 2)
        slow_reqs.append(svc.submit_ask("slow", 3))
        svc.service_step()
        # starvation freedom: light is served the round it was submitted
        assert light.done and light.result is not None, \
            f"round {rnd}: light starved ({light.state})"
    assert all(r.done for r in flood)
    snap = svc.stats_snapshot()["svc_tenants"]
    assert snap["heavy"]["served"] == 12
    assert snap["light"]["served"] == 8
    # weight 0.5 accumulates a unit deficit every other round
    assert 3 <= snap["slow"]["served"] <= 4
    assert svc.n_shed == 0 and svc.n_rejected == 0


def test_drr_one_inflight_per_study_per_round():
    """A study's suggest is one slot reservation: two queued asks for
    the same study serve on consecutive rounds, not the same one."""
    svc, _ = _mk_service(1, [TenantConfig("a", studies=(0,))])
    r1, r2 = svc.submit_ask("a", 0), svc.submit_ask("a", 0)
    assert svc.service_step() == 1
    assert r1.done and not r2.done
    assert svc.service_step() == 1
    assert r2.done
    assert r1.result.trial_id != r2.result.trial_id


# ============================================================ deadlines
def test_deadline_shed_while_queued(tmp_path):
    d = str(tmp_path)
    svc, clock = _mk_service(2, [TenantConfig("a", studies=(0, 1))],
                             journal_dir=d)
    req = svc.submit_ask("a", 0, deadline=0.5)
    ok = svc.submit_ask("a", 1, deadline=10.0)
    clock.advance(1.0)                     # past req's budget, not ok's
    svc.service_step()
    assert req.state == "shed" and isinstance(req.error, DeadlineExceeded)
    assert ok.done and ok.result is not None
    snap = svc.stats_snapshot()
    assert snap["svc_deadline_miss"] == 1 and snap["svc_shed"] == 1
    recs = [r for r in _journal_records(d) if r["op"] == "svc_shed"]
    assert len(recs) == 1 and recs[0]["req"] == req.rid
    assert "deadline" in recs[0]["reason"]
    assert recs[0]["kind"] == "deadline"   # replay keeps the error class
    # the freed study keeps serving; a later ask just works
    again = svc.submit_ask("a", 0)
    svc.service_step()
    assert again.done and again.result is not None


def test_request_spans_record_submit_dispatch_and_end():
    """With tracing on, every request that reaches a terminal state
    records one ``svc.request`` span from its submit, keyed by ``rid``,
    whose ``dispatch_us`` is its last dispatch (none if it never left
    the queue)."""
    from repro.obs import trace as obs_trace
    tr = obs_trace.enable()
    try:
        svc, clock = _mk_service(
            3, [TenantConfig("a", studies=(0, 1)),
                TenantConfig("b", studies=(2,))],
            fi=FaultInjector(ask_fail={1: 1}), backoff_base=0.1)
        served = svc.submit_ask("a", 0)
        retried = svc.submit_ask("a", 1)
        late = svc.submit_ask("b", 2, deadline=0.5)
        clock.advance(1.0)                 # late's budget is spent
        for _ in range(10):
            if retried.done:
                break
            svc.service_step()
            clock.advance(0.5)             # release the backoff
        spans = [e for e in tr.events() if e["name"] == "svc.request"]
    finally:
        obs_trace.disable()
    by_rid = {sp["args"]["rid"]: sp for sp in spans}
    assert len(spans) == len(by_rid) == 3
    for req in (served, retried, late):
        args = by_rid[req.rid]["args"]
        assert (args["tenant"], args["study"]) == (req.tenant, req.study)
        assert (args["state"], args["attempts"]) == (req.state,
                                                    req.attempts)
    assert by_rid[late.rid]["args"]["dispatch_us"] is None
    assert by_rid[retried.rid]["args"]["attempts"] == 2
    for req in (served, retried):
        sp = by_rid[req.rid]
        assert sp["ts"] <= sp["args"]["dispatch_us"] <= sp["ts"] + sp["dur"]
    # the retry's last dispatch came after the first round's
    assert (by_rid[retried.rid]["args"]["dispatch_us"]
            > by_rid[served.rid]["args"]["dispatch_us"])


def test_deadline_miss_in_flight_via_injected_latency(tmp_path):
    """A suggestion that comes back after its deadline (injected
    full-refit latency on the virtual clock) is cancel-and-shed: the
    request fails, the trial is never told, the slot reservation is
    withdrawn, and the shed is journaled."""
    d = str(tmp_path)
    tenants = [TenantConfig("a", studies=(0,)), TenantConfig("b",
                                                             studies=(1,))]
    fi = FaultInjector()
    svc, clock = _mk_service(2, tenants, journal_dir=d, fi=fi)
    for _ in range(5):                     # through startup into GP asks
        reqs = [svc.submit_ask("a", 0), svc.submit_ask("b", 1)]
        _serve(svc, reqs)
        for r in reqs:
            svc.submit_tell(r.tenant, r.study, r.result.trial_id,
                            _sphere(r.result.x))
    n_before = len(svc.fs.samplers[0].trials)
    fi.full_latency[0] = [10.0, 1]         # next full refit: +10 virtual s
    late = svc.submit_ask("a", 0, deadline=5.0)
    intime = svc.submit_ask("b", 1, deadline=100.0)
    _serve(svc, [late, intime])
    assert late.state == "shed" and isinstance(late.error,
                                               DeadlineExceeded)
    assert "in flight" in str(late.error)
    assert intime.done and intime.result is not None
    assert fi.n_full_delays == 1 and clock.slept_s >= 10.0
    # the computed trial exists but stays pending (recovery re-evaluates)
    assert svc.fs.samplers[0].trials[n_before].state == "pending"
    recs = [r for r in _journal_records(d) if r["op"] == "svc_shed"]
    assert len(recs) == 1 and recs[0]["req"] == late.rid


# ====================================================== backoff retries
def test_transient_dispatch_failure_retries_with_bounded_backoff(
        tmp_path):
    d = str(tmp_path)
    svc, clock = _mk_service(
        1, [TenantConfig("a", studies=(0,))], journal_dir=d,
        fi=FaultInjector(ask_fail={0: 3}), max_retries=5,
        backoff_base=0.1, backoff_cap=0.25, backoff_jitter=0.25)
    req = svc.submit_ask("a", 0)
    for _ in range(20):
        if req.done:
            break
        svc.service_step()
        clock.advance(0.5)                 # release the backoff
    assert req.done and req.result is not None
    assert req.attempts == 4               # 3 vetoes + 1 success
    recs = [r for r in _journal_records(d) if r["op"] == "svc_retry"]
    assert [r["attempt"] for r in recs] == [1, 2, 3]
    for i, r in enumerate(recs):
        base = min(0.1 * 2.0 ** i, 0.25)   # bounded: cap then jitter
        assert base <= r["delay_s"] <= base * 1.25
    assert recs[0]["delay_s"] < recs[1]["delay_s"]
    snap = svc.stats_snapshot()
    assert snap["svc_retries"] == 3 and snap["svc_shed"] == 0


def test_retry_exhaustion_fails_request_and_isolates_tenant(tmp_path):
    d = str(tmp_path)
    svc, clock = _mk_service(
        2, [TenantConfig("a", studies=(0,)), TenantConfig("b",
                                                          studies=(1,))],
        journal_dir=d,
        fi=FaultInjector(ask_fail={0: 99}), max_retries=2,
        backoff_base=0.01, backoff_cap=0.02)
    bad = svc.submit_ask("a", 0)
    good = svc.submit_ask("b", 1)
    for _ in range(20):
        if bad.done and good.done:
            break
        svc.service_step()
        clock.advance(0.1)
    assert good.done and good.result is not None     # isolation
    assert bad.state == "failed" and isinstance(bad.error, RequestFailed)
    assert bad.attempts == 3               # initial + max_retries
    recs = [r for r in _journal_records(d) if r["op"] == "svc_shed"]
    assert len(recs) == 1 and recs[0]["kind"] == "failed"
    assert "retries exhausted" in recs[0]["reason"]


def test_backoff_delays_deterministic_across_runs(tmp_path):
    """Same seeds, same faults → bit-identical jittered delay sequence
    (the backoff rng is fixed-seed; no wall clock leaks in)."""
    def run(sub):
        d = str(tmp_path / sub)
        svc, clock = _mk_service(
            1, [TenantConfig("a", studies=(0,))], journal_dir=d,
            fi=FaultInjector(ask_fail={0: 3}), max_retries=5)
        req = svc.submit_ask("a", 0)
        for _ in range(20):
            if req.done:
                break
            svc.service_step()
            clock.advance(1.0)
        return [r["delay_s"] for r in _journal_records(d)
                if r["op"] == "svc_retry"]
    a, b = run("a"), run("b")
    assert len(a) == 3 and a == b


def test_engine_quarantine_retry_backoff_counters(tmp_path):
    """Satellite: the fleet's quarantine retry loop honors bounded
    exponential backoff (journaled, charged to the sleep hook) and
    surfaces retry/backoff counters in stats_snapshot()."""
    d = str(tmp_path)
    clock = VirtualClock()
    inj = FaultInjector(full_fail={1: 1})
    fs = FleetSampler([BoxSpace.cube(2, 0.0, 1.0)] * 2, seed=2,
                      journal_dir=d, fault_injector=inj,
                      sleep_fn=clock.sleep,
                      **_fleet_kw(retry_backoff_base=0.05,
                                  retry_backoff_cap=0.4,
                                  retry_backoff_jitter=0.25))
    for _ in range(6):
        for i, t in enumerate(fs.ask_all()):
            fs.tell(i, t.trial_id, _sphere(t.x))
    assert inj.n_full_vetoed == 1
    snap = fs.stats_snapshot()
    assert snap["n_retries"] >= 1 and snap["n_retry_backoffs"] >= 1
    assert snap["backoff_total_s"] > 0.0
    recs = [r for r in _journal_records(d) if r["op"] == "backoff"]
    assert len(recs) == snap["n_retry_backoffs"]
    for r in recs:
        assert 0.05 <= r["delay_s"] <= 0.4 * 1.25 and 1 in r["sids"]
    # the delay was charged to the (virtual) sleep hook, not wall time
    assert clock.slept_s == pytest.approx(snap["backoff_total_s"])
    # compile economy: retries + backoff reuse the same programs
    assert snap["n_fleet_compiles"] <= 3


def test_cancel_ask_is_deterministic_to_undo():
    """cancel_request withdraws a pending/uncollected suggest; because
    keys derive from the trial count, re-asking recomputes the identical
    point — a deadline shed never perturbs the trajectory."""
    def mk():
        return FleetSampler([BoxSpace.cube(2, 0.0, 1.0)] * 2, seed=4,
                            **_fleet_kw())
    a, b = mk(), mk()
    for fs in (a, b):
        for _ in range(5):
            for i, t in enumerate(fs.ask_all()):
                fs.tell(i, t.trial_id, _sphere(t.x))
    # a: prefetch + step + cancel (sheds the computed result), then ask
    assert a.samplers[0].prefetch_suggest()
    a.fleet.step()
    assert a.cancel_ask(0) is True
    assert a.cancel_ask(0) is False        # nothing left to withdraw
    ta = a.ask_batch([0])[0]
    tb = b.ask_batch([0])[0]
    np.testing.assert_array_equal(ta.x, tb.x)


# ======================================================= overload ladder
def test_overload_reject_rung_and_deescalation(tmp_path):
    d = str(tmp_path)
    svc, _ = _mk_service(
        2, [TenantConfig("a", studies=(0,)), TenantConfig("b",
                                                          studies=(1,))],
        journal_dir=d,
        overload=OverloadConfig(reject_depth=3, degrade_depth=50,
                                shed_depth=60))
    backlog = [svc.submit_ask("a", 0) for _ in range(3)]
    svc.service_step()                     # depth 3 >= 3: rung -> reject
    assert svc.stats_snapshot()["svc_rung"] == "reject"
    with pytest.raises(FleetFullError, match="rung reject"):
        svc.submit_ask("b", 1)
    assert svc.stats_snapshot()["svc_tenants"]["b"]["rejected"] == 1
    _serve(svc, backlog)                   # queue drains...
    svc.service_step()
    assert svc.stats_snapshot()["svc_rung"] == "admit"     # ...de-escalates
    ok = svc.submit_ask("b", 1)            # admissions resume
    svc.service_step()
    assert ok.done and ok.result is not None
    rungs = [(r["from"], r["rung"]) for r in _journal_records(d)
             if r["op"] == "svc_overload"]
    assert rungs == [("admit", "reject"), ("reject", "admit")]
    recs = [r for r in _journal_records(d) if r["op"] == "svc_reject"]
    assert len(recs) == 1 and recs[0]["tenant"] == "b"


def test_overload_degrade_and_shed_lowest_weight_tenant(tmp_path):
    d = str(tmp_path)
    svc, _ = _mk_service(
        3, [TenantConfig("gold", weight=4.0, studies=(0,)),
            TenantConfig("silver", weight=2.0, studies=(1,)),
            TenantConfig("bronze", weight=1.0, studies=(2,))],
        journal_dir=d,
        overload=OverloadConfig(reject_depth=2, degrade_depth=4,
                                shed_depth=6))
    backlog = [svc.submit_ask("gold", 0) for _ in range(3)]
    backlog += [svc.submit_ask("bronze", 2) for _ in range(3)]
    victim = svc.submit_ask("bronze", 2)   # depth 7 >= 6 at next step
    svc.service_step()
    snap = svc.stats_snapshot()
    assert snap["svc_rung"] == "shed_tenant"
    t = snap["svc_tenants"]
    # rung 2 degraded silver... no: both actions pick the lowest weight
    # still standing — bronze degrades (solo path), then is shed
    assert t["bronze"]["is_shed"] and t["bronze"]["degraded"]
    assert not t["gold"]["is_shed"] and not t["gold"]["degraded"]
    assert not t["silver"]["is_shed"]
    assert svc.fs.samplers[2]._fleet is None      # left the fleet plane
    assert svc.fs.samplers[0]._fleet is not None
    assert victim.state == "shed" and isinstance(victim.error,
                                                 TenantShedError)
    with pytest.raises(TenantShedError):
        svc.submit_ask("bronze", 2)
    with pytest.raises(TenantShedError):
        svc.submit_tell("bronze", 2, 0, 1.0)
    recs = _journal_records(d)
    deg = [r for r in recs if r["op"] == "svc_degrade"]
    shd = [r for r in recs if r["op"] == "svc_shed_tenant"]
    assert len(deg) == 1 and deg[0]["tenant"] == "bronze"
    assert len(shd) == 1 and shd[0]["tenant"] == "bronze"
    assert victim.rid in shd[0]["dropped"]
    # the WAL shows the rung transition before its effects
    ops = [r["op"] for r in recs]
    assert ops.index("svc_overload") < ops.index("svc_degrade") \
        < ops.index("svc_shed_tenant")
    # gold keeps being served after the shed; once its backlog drains
    # the ladder de-escalates and admissions resume
    _serve(svc, backlog)
    svc.service_step()
    assert svc.stats_snapshot()["svc_rung"] == "admit"
    ok = svc.submit_ask("gold", 0)
    _serve(svc, [ok])
    assert ok.result is not None


def test_tenant_shed_resolves_backoff_delayed_requests(tmp_path):
    """Shedding a tenant resolves its backoff-delayed requests exactly
    like its queued ones (TenantShedError, counted, in the journal drop
    list) — no client is left polling a request that can never finish."""
    d = str(tmp_path)
    svc, _ = _mk_service(
        2, [TenantConfig("big", weight=2.0, studies=(0,)),
            TenantConfig("small", weight=1.0, studies=(1,))],
        journal_dir=d, fi=FaultInjector(ask_fail={1: 99}),
        overload=OverloadConfig(reject_depth=2, degrade_depth=4,
                                shed_depth=6))
    stuck = svc.submit_ask("small", 1)
    svc.service_step()                     # dispatch veto -> backoff
    assert stuck.state == "delayed"
    backlog = [svc.submit_ask("big", 0) for _ in range(6)]
    svc.service_step()                     # depth 7 >= 6: shed small
    assert svc.stats_snapshot()["svc_rung"] == "shed_tenant"
    assert stuck.done and stuck.state == "shed"
    assert isinstance(stuck.error, TenantShedError)
    snap = svc.stats_snapshot()["svc_tenants"]["small"]
    assert snap["shed"] == 1 and snap["is_shed"]
    shd = [r for r in _journal_records(d)
           if r["op"] == "svc_shed_tenant"]
    assert len(shd) == 1 and stuck.rid in shd[0]["dropped"]
    _serve(svc, backlog)                   # the survivor keeps serving


def test_p99_rung_deescalates_after_queue_drains(tmp_path):
    """SLO-driven reject must not latch: p99 only refreshes on
    completions, so once the backlog drains the p99 rungs suspend and
    admissions resume (regression: a stale over-SLO window used to
    lock the service in reject forever)."""
    d = str(tmp_path)
    svc, clock = _mk_service(
        1, [TenantConfig("a", studies=(0,))], journal_dir=d,
        overload=OverloadConfig(reject_depth=1000, p99_slo=0.6,
                                min_samples=3, window=8))
    for _ in range(3):                     # over-SLO window: ~1s each
        req = svc.submit_ask("a", 0)
        clock.advance(1.0)
        svc.service_step()
        assert req.done and req.result is not None
    assert svc.p99() >= 1.0
    queued = svc.submit_ask("a", 0)        # backlog: p99 rung engages
    svc.service_step()
    assert queued.done                     # rung 1 serves the backlog
    assert svc.stats_snapshot()["svc_rung"] == "reject"
    with pytest.raises(FleetFullError, match="p99"):
        svc.submit_ask("a", 0)
    svc.service_step()                     # empty queue: p99 suspends
    assert svc.stats_snapshot()["svc_rung"] == "admit"
    ok = svc.submit_ask("a", 0)            # admissions resume
    svc.service_step()
    assert ok.done and ok.result is not None
    rungs = [(r["from"], r["rung"]) for r in _journal_records(d)
             if r["op"] == "svc_overload"]
    # the stale window may re-engage while ok is queued (it still gets
    # served); what must hold is the engage/de-escalate pair, not a
    # permanent latch
    assert rungs[:2] == [("admit", "reject"), ("reject", "admit")]


def test_tenant_queue_cap_isolates_backlog_spam():
    svc, _ = _mk_service(
        2, [TenantConfig("spam", studies=(0,)), TenantConfig("calm",
                                                             studies=(1,))],
        overload=OverloadConfig(reject_depth=100, tenant_queue_cap=2))
    for _ in range(2):
        svc.submit_ask("spam", 0)
    with pytest.raises(FleetFullError, match="backlog"):
        svc.submit_ask("spam", 0)
    ok = svc.submit_ask("calm", 1)         # unaffected by spam's cap
    svc.service_step()
    assert ok.done and ok.result is not None


def test_nan_tell_spam_costs_only_the_spammer(tmp_path):
    """Poison tells are refused synchronously before the WAL: the
    spammer sees ValueError, the journal never acknowledges, and other
    tenants' service is untouched."""
    d = str(tmp_path)
    svc, _ = _mk_service(2, [TenantConfig("spam", studies=(0,)),
                             TenantConfig("calm", studies=(1,))],
                         journal_dir=d)
    t = svc.submit_ask("spam", 0)
    svc.service_step()
    n_recs = len(_journal_records(d))
    for _ in range(5):
        with pytest.raises(ValueError, match="failed=True"):
            svc.submit_tell("spam", 0, t.result.trial_id, float("nan"))
    assert len(_journal_records(d)) == n_recs      # nothing acknowledged
    assert svc.stats_snapshot()["svc_tenants"]["spam"]["bad_tells"] == 5
    ok = svc.submit_ask("calm", 1)
    svc.service_step()
    assert ok.done and ok.result is not None


# ========================================================= drain/recover
def test_drain_journals_pending_queue_and_recover_restores_it(tmp_path):
    d = str(tmp_path)
    svc, _ = _mk_service(2, [TenantConfig("a", studies=(0,)),
                             TenantConfig("b", studies=(1,))],
                         journal_dir=d, max_batch=1)
    served = svc.submit_ask("a", 0)
    held = [svc.submit_ask("b", 1), svc.submit_ask("a", 0)]
    svc.service_step()                     # max_batch=1: serves only one
    assert served.done
    svc.drain()
    for r in held:
        assert r.state == "shed" and isinstance(r.error, ServiceDraining)
    recs = _journal_records(d)
    dr = [r for r in recs if r["op"] == "svc_drain"]
    assert len(dr) == 1
    assert dr[0]["queued"] == sorted(r.rid for r in held)
    assert recs[-1]["op"] == "drain"       # fleet drained after service
    with pytest.raises(ServiceDraining):
        svc.submit_ask("a", 0)

    svc2, rep = BOService.recover(d, clock=VirtualClock())
    assert rep.truncated_bytes == 0
    restored = svc2.recovered["queued"]
    assert [(r.rid, r.tenant, r.study) for r in restored] == \
           [(r.rid, r.tenant, r.study) for r in held]
    _serve(svc2, restored)
    assert all(r.result is not None for r in restored)


@pytest.mark.parametrize("kill_seq", [18, 40])
def test_service_crash_recovery_bitwise(tmp_path, ref_service_run,
                                        kill_seq):
    """Kill the process (injected) mid-service at a journal offset;
    recover; the restored pending queue re-dispatches and every study's
    suggestion trajectory matches the uninterrupted twin bit-for-bit
    (refit_interval=1)."""
    d = str(tmp_path)
    rounds, ref_x = ref_service_run
    clock = VirtualClock()
    fi = FaultInjector(kill_at_seq=kill_seq)
    svc, _ = _mk_service(2, _SCRIPT_TENANTS, journal_dir=d, fi=fi,
                         clock=clock)
    crashed = False
    try:
        _run_script(svc, rounds)
    except InjectedCrash:
        crashed = True
    assert crashed

    with pytest.warns(UserWarning, match="dropping"):
        svc2, rep = BOService.recover(d, clock=VirtualClock())
    assert rep.truncated_bytes > 0
    # resync: re-tell every asked-but-never-told trial (same objective,
    # same x, same y), then drive the restored queue to completion
    for i, tid in rep.pending:
        owner = svc2._study_owner[i]
        svc2.submit_tell(owner, i, tid,
                         _sphere(svc2.fs.samplers[i].trials[tid].x))
    queued = svc2.recovered["queued"]
    if queued:
        _serve(svc2, queued)
        for r in queued:
            svc2.submit_tell(r.tenant, r.study, r.result.trial_id,
                             _sphere(r.result.x))
    # top up each study independently to the scripted round count
    while True:
        todo = [i for i in range(2)
                if len(svc2.fs.samplers[i].trials) < rounds]
        if not todo:
            break
        reqs = [svc2.submit_ask(svc2._study_owner[i], i) for i in todo]
        _serve(svc2, reqs)
        for r in reqs:
            svc2.submit_tell(r.tenant, r.study, r.result.trial_id,
                             _sphere(r.result.x))
    for i in range(2):
        got = svc2.fs.samplers[i].trials
        assert len(got) >= rounds
        for k in range(rounds):
            np.testing.assert_array_equal(
                ref_x[i][k], got[k].x, err_msg=f"study {i} trial {k}")


_SCRIPT_TENANTS = [TenantConfig("a", weight=2.0, studies=(0,)),
                   TenantConfig("b", weight=1.0, studies=(1,))]


def _run_script(svc, rounds):
    """The canonical scripted workload both the victim and the twin run:
    one ask per tenant per round, served then told."""
    for r in range(rounds):
        if r == 3 and svc.fs.ckpt is not None:
            svc.fs.checkpoint()            # replay starts mid-journal
        reqs = [svc.submit_ask("a", 0), svc.submit_ask("b", 1)]
        _serve(svc, reqs)
        for req in reqs:
            svc.submit_tell(req.tenant, req.study, req.result.trial_id,
                            _sphere(req.result.x))


@pytest.fixture(scope="module")
def ref_service_run():
    rounds = 6
    svc, _ = _mk_service(2, _SCRIPT_TENANTS)
    _run_script(svc, rounds)
    return rounds, [[np.array(t.x) for t in s.trials]
                    for s in svc.fs.samplers]


# ========================================================= async facade
def test_async_ask_resolves_via_event():
    """Clients of the async facade park on an Event until the server
    task resolves their request — results arrive without a sleep(0)
    busy-poll, and tells close the loop."""
    import asyncio
    svc, _ = _mk_service(1, [TenantConfig("a", studies=(0,))])

    async def main():
        server = asyncio.create_task(svc.run())
        t = await asyncio.wait_for(svc.ask("a", 0), timeout=60)
        await svc.tell("a", 0, t.trial_id, _sphere(t.x))
        svc.stop()
        await server
        return t
    t = asyncio.run(main())
    assert t is not None and svc.n_completed == 1
    assert svc.fs.samplers[0].trials[t.trial_id].state == "complete"


def test_async_ask_woken_on_shed():
    """A request that can never complete (perma-vetoed dispatch, then
    deadline expiry in backoff) must wake its async waiter with the
    shed error instead of hanging it forever."""
    import asyncio
    svc, clock = _mk_service(1, [TenantConfig("a", studies=(0,))],
                             fi=FaultInjector(ask_fail={0: 99}))

    async def main():
        server = asyncio.create_task(svc.run())
        task = asyncio.create_task(svc.ask("a", 0, deadline=0.01))
        # let the server dispatch (veto -> backoff), then push the
        # virtual clock past the deadline so the next round sheds it
        for _ in range(200):
            if task.done():
                break
            clock.advance(0.02)
            await asyncio.sleep(0.002)
        with pytest.raises(DeadlineExceeded):
            await asyncio.wait_for(task, timeout=60)
        svc.stop()
        await server
    asyncio.run(main())
    assert svc.n_deadline_miss == 1


# ================================================= out-of-order tells
@settings(max_examples=3, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_out_of_order_tells_match_direct_drive(seed):
    """Property: the service layer is pure scheduling — under any tenant
    interleaving of tells (including tells held back across round
    boundaries, landing after the next ask), per-study trajectories are
    bit-identical to driving the FleetSampler directly with the same
    per-study ask/tell schedule."""
    rng = np.random.default_rng(seed)
    rounds, S = 5, 2
    order = [rng.permutation(S) for _ in range(rounds)]
    hold = [int(rng.integers(0, S + 1)) for _ in range(rounds)]  # S=none

    svc, _ = _mk_service(S, [TenantConfig("a", studies=(0,)),
                             TenantConfig("b", studies=(1,))],
                         fleet_over=dict(n_startup_trials=2))
    owner = {0: "a", 1: "b"}
    held = {}                              # study -> (trial_id, y)
    for r in range(rounds):
        reqs = [svc.submit_ask(owner[i], i) for i in range(S)]
        _serve(svc, reqs)
        for i, (tid, y) in held.items():   # late: lands AFTER next ask
            svc.submit_tell(owner[i], i, tid, y)
        held = {}
        for i in order[r]:
            t = reqs[i].result
            if i == hold[r]:
                held[i] = (t.trial_id, _sphere(t.x))
            else:
                svc.submit_tell(owner[i], i, t.trial_id, _sphere(t.x))
    for i, (tid, y) in held.items():
        svc.submit_tell(owner[i], i, tid, y)

    fs = FleetSampler([BoxSpace.cube(2, 0.0, 1.0)] * S, seed=0,
                      **_fleet_kw(n_startup_trials=2))
    held = {}
    for r in range(rounds):
        trials = fs.ask_batch(range(S))
        for i, (tid, y) in held.items():
            fs.tell(i, tid, y)
        held = {}
        for i in order[r]:
            t = trials[i]
            assert not isinstance(t, Exception)
            if i == hold[r]:
                held[i] = (t.trial_id, _sphere(t.x))
            else:
                fs.tell(i, t.trial_id, _sphere(t.x))
    for i, (tid, y) in held.items():
        fs.tell(i, tid, y)

    for i in range(S):
        a, b = svc.fs.samplers[i].trials, fs.samplers[i].trials
        assert len(a) == len(b) == rounds
        for ta, tb in zip(a, b):
            np.testing.assert_array_equal(ta.x, tb.x,
                                          err_msg=f"study {i}")
