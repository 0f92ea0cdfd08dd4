"""BO-as-a-service under open-loop Poisson load: latency, goodput, QoS.

Drives :class:`repro.serve.bo_service.BOService` the way the north-star
workload does (ROADMAP item 3): named tenants with heterogeneous weights
and arrival rates submit ask requests on an *open-loop* schedule —
arrival times are drawn up front from seeded per-tenant Poisson
processes, and a request is submitted when its arrival time comes due
whether or not the service has caught up (so backlog builds honestly
under overload instead of the load adapting to the server).  Completed
asks are told back immediately with a synthetic objective, closing the
BO loop.

Tenant mixes (each is one benchmark configuration, >=1 row per tenant):

* **uniform** — three equal-weight tenants at the same moderate rate:
  the baseline fairness row (per-tenant p50/p99 should be close).
* **skew** — a heavy low-priority tenant (2 studies, burst arrivals, no
  deadline) floods the service while a light high-weight tenant submits
  sparse deadline-carrying requests.  The QoS claim under test: DRR
  isolates the light tenant — its p99 stays bounded (and below the
  flooding tenant's) and it sheds nothing, no matter the backlog next
  door.  --check-compiles asserts exactly that (zero cross-tenant
  starvation), plus the fleet compile-economy budget (<=3 traces per
  (bucket, slots) shape — tenancy, deadlines, and overload handling are
  host-side and add no programs).

--chaos adds a kill-and-recover row: the same skewed workload runs
journaled with fault injection — deterministic latency injection (slow
full refits + slow tells) plus an injected process kill ~60% through
the expected journal stream.  :meth:`BOService.recover` rebuilds the
service, re-tells the suggests that were in flight at the kill, serves
the restored pending queue, then finishes the arrival schedule.
Reported: goodput over the whole incident (must stay > 0), the pre-
crash / post-recovery split, deadline misses, sheds, and replay cost —
field-compatible with ``benchmarks/fleet_throughput.py --chaos`` so the
two BENCH files diff against each other.

Emits BENCH_serve.json (append-only row array + a ``summary`` dict of
headline scalars, same contract as the other BENCH files).

--trace enables the obs span tracer for the whole run (off by default):
DRR-round/dispatch spans and QoS instants (sheds, rung changes,
degrades) land in --trace-out as Chrome-trace JSON, and the summary
gains a per-phase breakdown.  --check-compiles still holds WITH tracing
on — instrumentation must never add programs.

Usage:
  python benchmarks/bo_serve.py [--tiny] [--requests N] [--seed K]
      [--chaos] [--check-compiles] [--trace]
      [--trace-out BENCH_serve_trace.json] [--out BENCH_serve.json]
"""
import argparse
import json
import os
import platform
import shutil
import sys
import tempfile
import time

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np                                     # noqa: E402

from repro.analysis.runtime import (install_nan_guard,  # noqa: E402
                                    nan_guard_stats)
from repro.bo.objectives import make_objective         # noqa: E402
from repro.bo.sampler import FleetSampler              # noqa: E402
from repro.bo.space import BoxSpace                    # noqa: E402
from repro.core.mso import MsoOptions                  # noqa: E402
from repro.engine import FleetFullError                # noqa: E402
from repro.obs import export as obs_export             # noqa: E402
from repro.obs import trace as obs_trace               # noqa: E402
from repro.serve.bo_service import (BOService,         # noqa: E402
                                    TenantConfig)

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), "..", "tests"))


def _tenant_specs(args):
    """mix -> [(name, weight, n_studies, rate_hz, deadline_s, n_reqs)]"""
    n = args.requests
    return {
        "uniform": [
            ("t0", 1.0, 1, args.rate_mid, None, n),
            ("t1", 1.0, 1, args.rate_mid, None, n),
            ("t2", 1.0, 1, args.rate_mid, None, n),
        ],
        "skew": [
            ("heavy", 1.0, 2, args.rate_burst, None, 2 * n),
            ("light", 4.0, 1, args.rate_low, args.light_deadline, n),
        ],
    }


def _arrivals(specs, seed):
    """Open-loop Poisson schedule: [(t_arr, tenant, study, deadline)],
    sorted by arrival time, drawn up front from a seeded generator."""
    rng = np.random.default_rng(seed)
    events = []
    study_base = 0
    for name, _w, n_studies, rate, deadline, n_reqs in specs:
        t = 0.0
        for k in range(n_reqs):
            t += float(rng.exponential(1.0 / rate))
            study = study_base + (k % n_studies)
            events.append((t, name, study, deadline))
        study_base += n_studies
    events.sort(key=lambda e: e[0])
    return events


def _build(specs, *, journal_dir=None, fi=None, args):
    S = sum(s[2] for s in specs)
    objs = [make_objective("sphere", args.D, seed=i) for i in range(S)]
    spaces = [BoxSpace.cube(args.D, *o.bounds) for o in objs]
    tenants, base = [], 0
    for name, w, n_studies, _r, deadline, _n in specs:
        tenants.append(TenantConfig(
            name, weight=w, studies=tuple(range(base, base + n_studies)),
            deadline=deadline))
        base += n_studies
    fs = FleetSampler(spaces, seed=0, slots=min(args.slots, S),
                      n_startup_trials=args.n_startup, n_restarts=args.B,
                      pad_multiple=args.pad, posterior_backend="xla",
                      refit_interval=args.refit_interval,
                      journal_dir=journal_dir, fault_injector=fi,
                      mso_options=MsoOptions())
    if args.debug_nans:
        install_nan_guard(fs.fleet)
    svc = BOService(fs, tenants, max_retries=3, backoff_base=0.01,
                    backoff_cap=0.1)
    return svc, objs


def _pump(svc, objs, events, state, deadline_guard=120.0):
    """Drive the open-loop schedule to completion: submit due arrivals,
    step the service, tell finished asks.  ``state`` carries the cursor
    and told-set so a chaos run can resume mid-schedule."""
    t0 = state.setdefault("t0", time.perf_counter())
    inflight = state.setdefault("inflight", [])
    i = state.get("cursor", 0)
    guard = time.perf_counter() + deadline_guard
    while True:
        now = time.perf_counter() - t0
        while i < len(events) and events[i][0] <= now:
            _t, tenant, study, deadline = events[i]
            i += 1
            state["cursor"] = i
            try:
                inflight.append(svc.submit_ask(tenant, study,
                                               deadline=deadline))
            except FleetFullError:
                state["n_rejected"] = state.get("n_rejected", 0) + 1
        svc.service_step()
        still = []
        for req in inflight:
            if req.state == "done":
                svc.submit_tell(req.tenant, req.study,
                                req.result.trial_id,
                                objs[req.study](req.result.x))
            elif not req.done:
                still.append(req)
        inflight[:] = still
        if i >= len(events) and not inflight:
            return time.perf_counter() - t0
        if time.perf_counter() > guard:
            raise SystemExit(f"bo_serve: schedule stalled "
                             f"({len(inflight)} in flight, "
                             f"{len(events) - i} not yet due)")
        if i < len(events) and not svc.queue_depth() and not inflight:
            # idle until the next arrival (open-loop: never early)
            time.sleep(min(events[i][0] - now, 0.05))


def _tenant_rows(svc, mix, wall):
    rows = []
    snap = svc.stats_snapshot()
    for name, t in snap["svc_tenants"].items():
        lat = np.asarray(svc.tenant_latencies(name))
        rows.append({
            "mode": "serve", "mix": mix, "tenant": name,
            "weight": t["weight"], "submitted": t["submitted"],
            "served": t["served"], "shed": t["shed"],
            "deadline_miss": t["deadline_miss"],
            "rejected": t["rejected"], "retries": t["retries"],
            "p50_ms": (round(1e3 * float(np.quantile(lat, 0.5)), 3)
                       if lat.size else None),
            "p99_ms": (round(1e3 * float(np.quantile(lat, 0.99)), 3)
                       if lat.size else None),
        })
    return rows


def _overall_row(svc, mix, wall, extra=None):
    snap = svc.stats_snapshot()
    lats = np.asarray([x for name in snap["svc_tenants"]
                       for x in svc.tenant_latencies(name)])
    n_buckets = len({blk.bucket for blk in svc.fs.fleet._blocks}) or 1
    row = {
        "mode": "serve_overall", "mix": mix,
        "wall_s": round(wall, 3),
        "completed": snap["svc_completed"],
        "goodput_sps": snap["svc_completed"] / wall,
        "deadline_miss": snap["svc_deadline_miss"],
        "shed": snap["svc_shed"],
        "rejected": snap["svc_rejected"],
        "retries": snap["svc_retries"],
        "rung_changes": snap["svc_rung_changes"],
        "p50_ms": (round(1e3 * float(np.quantile(lats, 0.5)), 3)
                   if lats.size else None),
        "p99_ms": (round(1e3 * float(np.quantile(lats, 0.99)), 3)
                   if lats.size else None),
        "n_buckets": n_buckets,
        "n_compiles_total": snap["n_fleet_compiles"],
        "retrace_causes": snap["retraces"]["causes"],
        **(extra or {}),
    }
    return row


def run_mix(mix, specs, args):
    svc, objs = _build(specs, args=args)
    events = _arrivals(specs, args.seed)
    wall = _pump(svc, objs, events, {})
    extra = ({"nan_guard": nan_guard_stats(svc.fs.fleet)}
             if args.debug_nans else None)
    rows = _tenant_rows(svc, mix, wall) + \
        [_overall_row(svc, mix, wall, extra)]
    over = rows[-1]
    print(f"serve_bench,{mix},completed={over['completed']},"
          f"goodput={over['goodput_sps']:.2f}/s,p50={over['p50_ms']}ms,"
          f"p99={over['p99_ms']}ms,miss={over['deadline_miss']},"
          f"shed={over['shed']},compiles={over['n_compiles_total']}",
          flush=True)
    if args.check_compiles:
        assert over["n_compiles_total"] <= 3 * over["n_buckets"], \
            f"{mix}: {over['n_compiles_total']} traces for " \
            f"{over['n_buckets']} buckets (must be <= 3/bucket); " \
            f"retrace causes: {over['retrace_causes']}"
        if mix == "skew":
            by = {r["tenant"]: r for r in rows if r.get("tenant")}
            light, heavy = by["light"], by["heavy"]
            assert light["shed"] == 0 and light["deadline_miss"] == 0, \
                f"skew: light tenant starved: {light}"
            assert light["p99_ms"] is not None and \
                light["p99_ms"] <= heavy["p99_ms"], \
                f"skew: light p99 {light['p99_ms']}ms not bounded by " \
                f"flooding tenant's {heavy['p99_ms']}ms"
            print(f"serve_bench,{mix},fairness check OK "
                  f"(light p99={light['p99_ms']}ms <= heavy "
                  f"p99={heavy['p99_ms']}ms, light shed=0)", flush=True)
        print(f"serve_bench,{mix},compile check OK "
              f"({over['n_compiles_total']} traces)", flush=True)
    return rows


def run_chaos(args):
    """Kill-and-recover under load: the skew mix, journaled, with
    injected refit/tell latency and a process kill ~60% through the
    expected journal stream."""
    from faults import FaultInjector
    from repro.bo.journal import InjectedCrash

    specs = _tenant_specs(args)["skew"]
    events = _arrivals(specs, args.seed)
    # ~4 records per served request (svc_ask, svc_dispatch, ask, tell)
    kill_seq = max(4, int(0.6 * 4 * len(events)))
    fi = FaultInjector(kill_at_seq=kill_seq,
                       full_latency={0: (0.02, 3)},
                       tell_latency=(0.005, 5))
    d = tempfile.mkdtemp(prefix="bo_serve_chaos_")
    svc, objs = _build(specs, journal_dir=d, fi=fi, args=args)
    state = {}
    t0 = time.perf_counter()
    crashed = False
    try:
        _pump(svc, objs, events, state)
    except InjectedCrash:
        crashed = True
    wall1 = time.perf_counter() - t0
    if not crashed:
        shutil.rmtree(d)
        raise SystemExit(f"--chaos: kill_seq={kill_seq} never reached "
                         f"(--requests too small)")
    completed_pre = svc.n_completed

    t0 = time.perf_counter()
    svc2, rep = BOService.recover(d)
    recover_wall = time.perf_counter() - t0
    if args.debug_nans:
        install_nan_guard(svc2.fs.fleet)
    # re-tell what was in flight at the kill, serve the restored queue,
    # then finish the arrival schedule (the remaining events are all
    # "due" — the outage consumed their arrival times)
    for i, tid in rep.pending:
        svc2.submit_tell(svc2._study_owner[i], i, tid,
                         objs[i](svc2.fs.samplers[i].trials[tid].x))
    t0 = time.perf_counter()
    state2 = {"cursor": state.get("cursor", 0),
              "inflight": list(svc2.recovered["queued"]),
              "t0": t0 - (events[state["cursor"] - 1][0]
                          if state.get("cursor") else 0.0)}
    wall2 = _pump(svc2, objs, events, state2)
    wall2 = time.perf_counter() - t0
    svc2.drain()

    snap = svc2.stats_snapshot()
    n_buckets = len({blk.bucket for blk in svc2.fs.fleet._blocks}) or 1
    completed = completed_pre + snap["svc_completed"]
    total_wall = wall1 + recover_wall + wall2
    row = {
        "mode": "serve_chaos", "mix": "skew",
        "kill_seq": kill_seq,
        "n_records": rep.n_records,
        "truncated_bytes": rep.truncated_bytes,
        "replay_ms": round(rep.replay_ms, 3),
        "recover_wall_ms": round(1e3 * recover_wall, 3),
        "inflight_at_crash": len(rep.pending),
        "restored_queue": len(svc2.recovered["queued"]),
        "injected_delay_s": round(fi.injected_delay_s, 3),
        "completed": completed,
        "goodput_sps": completed / total_wall,
        "goodput_pre_crash_sps": completed_pre / wall1,
        "goodput_post_recovery_sps": (snap["svc_completed"] / wall2
                                      if wall2 > 0 else None),
        "deadline_miss": snap["svc_deadline_miss"],
        "shed": snap["svc_shed"],
        "retries": snap["svc_retries"],
        "n_buckets": n_buckets,
        "n_compiles_total": snap["n_fleet_compiles"],
        "retrace_causes": snap["retraces"]["causes"],
    }
    if args.debug_nans:
        row["nan_guard"] = nan_guard_stats(svc2.fs.fleet)
    print(f"serve_bench,chaos,kill_seq={kill_seq},"
          f"goodput={row['goodput_sps']:.2f}/s "
          f"(pre={row['goodput_pre_crash_sps']:.2f},"
          f"post={row['goodput_post_recovery_sps']:.2f}),"
          f"inflight_at_crash={row['inflight_at_crash']},"
          f"miss={row['deadline_miss']},shed={row['shed']},"
          f"compiles={row['n_compiles_total']}", flush=True)
    if args.check_compiles:
        assert rep.truncated_bytes > 0, \
            "chaos: injected kill left no torn record"
        assert row["goodput_sps"] > 0 and completed > 0, \
            "chaos: no goodput through the incident"
        assert fi.n_full_delays > 0 or fi.n_tell_delays > 0, \
            "chaos: latency injection never fired"
        assert row["n_compiles_total"] <= 3 * n_buckets, \
            f"chaos: {row['n_compiles_total']} traces for {n_buckets} " \
            f"buckets after recovery (must be <= 3/bucket); " \
            f"retrace causes: {row['retrace_causes']}"
        print(f"serve_bench,chaos,checks OK (recovered, goodput "
              f"{row['goodput_sps']:.2f}/s, {row['n_compiles_total']} "
              f"traces)", flush=True)
    shutil.rmtree(d)
    return [row]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: few requests, small GP buckets")
    ap.add_argument("--requests", type=int, default=None,
                    help="requests per (unit-rate) tenant")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chaos", action="store_true",
                    help="add a journaled kill-and-recover row with "
                    "latency injection")
    ap.add_argument("--check-compiles", action="store_true")
    ap.add_argument("--trace", action="store_true",
                    help="enable the obs span tracer (off by default); "
                    "adds a per-phase breakdown to the summary and "
                    "writes the Chrome-trace JSON to --trace-out")
    ap.add_argument("--trace-out", default="BENCH_serve_trace.json")
    ap.add_argument("--debug-nans", action="store_true",
                    help="wrap the three fleet block programs in a "
                    "finite-guard: every float leaf entering/leaving "
                    "them is checked; raises NonFiniteError naming the "
                    "program and leaf (one host sync per call)")
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args(argv)

    if args.tiny:
        args.requests = args.requests or 8
        args.D, args.B, args.pad = 3, 4, 8
        args.refit_interval, args.n_startup = 4, 4
        args.slots = 4
    else:
        args.requests = args.requests or 24
        args.D, args.B, args.pad = 4, 8, 16
        args.refit_interval, args.n_startup = 4, 6
        args.slots = 8
    args.rate_mid, args.rate_burst, args.rate_low = 20.0, 200.0, 4.0
    args.light_deadline = 60.0

    if args.trace:
        obs_trace.enable()

    rows = []
    for mix, specs in _tenant_specs(args).items():
        rows.extend(run_mix(mix, specs, args))
    if args.chaos:
        rows.extend(run_chaos(args))

    summary = {}
    if args.trace:
        events = obs_trace.get().events()
        summary["phase_breakdown"] = obs_export.phase_breakdown(events)
        obs_export.write_chrome_trace(
            args.trace_out, events, process_name="bo_serve",
            meta={"bench": "bo_serve"})
        print(f"wrote {args.trace_out} ({len(events)} trace events)")
    for r in rows:
        if r["mode"] == "serve_overall":
            m = r["mix"]
            summary[f"{m}_goodput_sps"] = r["goodput_sps"]
            summary[f"{m}_p50_ms"] = r["p50_ms"]
            summary[f"{m}_p99_ms"] = r["p99_ms"]
            summary[f"{m}_deadline_miss"] = r["deadline_miss"]
            summary[f"{m}_shed"] = r["shed"]
            summary[f"{m}_retrace_causes"] = r["retrace_causes"]
            if "nan_guard" in r:
                summary[f"{m}_nan_guard_checks"] = \
                    r["nan_guard"]["n_guard_checks"]
        elif r["mode"] == "serve":
            # per-tenant tails for every mix (the obs snapshot schema
            # carries the counters; latency quantiles live here)
            summary[f"{r['mix']}_{r['tenant']}_p50_ms"] = r["p50_ms"]
            summary[f"{r['mix']}_{r['tenant']}_p99_ms"] = r["p99_ms"]
        elif r["mode"] == "serve_chaos":
            summary["chaos_goodput_sps"] = r["goodput_sps"]
            summary["chaos_goodput_post_recovery_sps"] = \
                r["goodput_post_recovery_sps"]
            summary["chaos_inflight_at_crash"] = r["inflight_at_crash"]
            summary["chaos_deadline_miss"] = r["deadline_miss"]
            summary["chaos_shed"] = r["shed"]
            summary["chaos_retrace_causes"] = r["retrace_causes"]

    record = {
        "bench": "bo_serve",
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "device": jax.devices()[0].device_kind,
        "jax_backend": jax.default_backend(),
        "python": platform.python_version(),
        "mode": "tiny" if args.tiny else "default",
        "requests": args.requests,
        "seed": args.seed,
        "summary": summary,
        "rows": rows,
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    print(f"wrote {args.out} ({len(rows)} rows)")
    return rows


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
