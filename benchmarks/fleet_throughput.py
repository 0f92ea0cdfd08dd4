"""Fleet ask throughput: S concurrent studies through one fleet plane vs
a loop of single-study fused AskEngines.

For each fleet size S the same trial schedule runs twice:

* **loop** — S independent `GPSampler(fused=True)` studies served one
  `ask()` at a time (the PR-2 pipeline: already one compiled program per
  suggest, but the device sees B≈10 restarts at a time and every study
  carries its OWN jitted programs — compile cost is O(S · #buckets));
* **fleet** — the same S studies through ONE `FleetSampler`: every
  round, all suggest requests batch into one `fleet.step()` running the
  stacked (S, B, D) programs per slot block; blocks of equal (bucket,
  slots) shape share executables, so compile cost is O(#buckets),
  independent of S.

Two throughput numbers per run:

* **aggregate** (the headline serving metric): S·rounds / total wall
  over ALL post-startup suggest rounds — XLA traces included, because
  admitting a study into the fleet is free while admitting one to the
  loop compiles fresh per-study programs.  This is where the fleet's
  compile economy turns into wall-clock at scale.
* **steady** (the per-trial metric): S / median(round wall) over rounds
  where every study took the incremental O(n²) program and nothing
  traced — PR 2's steady-state definition lifted to the fleet.  On CPU
  the lockstep fleet pays max-study rounds here and roughly breaks even
  with the loop; on wide-vector backends the stacked programs win both.

--check-compiles asserts fleet compile counts ≤ 3 per (bucket, slots)
shape and independent of S, and (xla, S=16 in the sweep) the ≥4×
aggregate speedup acceptance target.  The pallas_interpret backend runs
for correctness/compile accounting only — interpreter-mode emulation of
the vmapped posterior kernel is python-speed, so its wall-clock rows
are not a performance signal.

Emits BENCH_fleet.json.

--mesh N adds fleet_mesh rows: the same fleet with its slot blocks
sharded over 1 and N devices (cfg.slots is the per-device width).
--check-compiles then additionally asserts compile counts do not move
with the device count — the mesh half of the compile-economy invariant.

--chaos adds a kill-and-recover row: the same schedule runs journaled
(``journal_dir``), a fault injector kills the "process" at a journal
offset mid-run (plus one injected unhealthy refit → quarantine),
``FleetSampler.recover`` rebuilds the fleet, and the schedule completes.
Reported: recovery time (journal replay ms per 100 trials — the headline
``summary`` scalar) and goodput under faults (completed suggests per
second of total wall, crash and recovery included).  --check-compiles
then also asserts the recovered fleet stays within the ≤3-traces-per-
(bucket, slots) budget — recovery and quarantine add no programs.

Usage:
  python benchmarks/fleet_throughput.py [--tiny] [--rounds N]
      [--fleet-sizes 1 4 16 64] [--slots K] [--mesh N] [--chaos]
      [--backends xla pallas_interpret ...] [--check-compiles]
      [--out BENCH_fleet.json]
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

from repro.analysis.runtime import install_nan_guard, nan_guard_stats  # noqa: E402
from repro.bo.objectives import make_objective         # noqa: E402
from repro.obs import export as obs_export             # noqa: E402
from repro.obs import trace as obs_trace               # noqa: E402
from repro.bo.sampler import FleetSampler, GPSampler   # noqa: E402
from repro.bo.space import BoxSpace                    # noqa: E402
from repro.core.mso import MsoOptions                  # noqa: E402

SPEEDUP_TARGET_S = 16       # acceptance: >=4x aggregate at S=16 (xla CPU)
SPEEDUP_TARGET = 4.0


def _objectives(S, D, seed=0):
    return [make_objective("sphere", D, seed=seed + i) for i in range(S)]


def _sampler_kw(args, backend):
    return dict(n_startup_trials=args.n_startup, n_restarts=args.B,
                pad_multiple=args.pad, posterior_backend=backend,
                refit_interval=args.refit_interval,
                mso_options=MsoOptions())


def run_loop(S, backend, args):
    """Baseline: S independent fused AskEngine studies, asked in a loop."""
    objs = _objectives(S, args.D)
    samplers = [GPSampler(BoxSpace.cube(args.D, *objs[i].bounds),
                          strategy="dbe_vec", fused=True, seed=i,
                          **_sampler_kw(args, backend))
                for i in range(S)]

    def compiles():
        return sum(s._ask.stats_snapshot()["n_ask_compiles"]
                   for s in samplers if s._ask is not None)

    round_ms, steady = [], []
    for r in range(args.rounds):
        c0 = compiles()
        t0 = time.perf_counter()
        trials = [s.ask() for s in samplers]
        wall = time.perf_counter() - t0
        kinds = [s.last_ask_info.kind if s.last_ask_info is not None
                 else "startup" for s in samplers]
        round_ms.append(1e3 * wall)
        steady.append(all(k == "incremental" for k in kinds)
                      and compiles() == c0)
        for s, t, obj in zip(samplers, trials, objs):
            s.tell(t.trial_id, obj(t.x))
    return round_ms, steady, {"n_compiles_total": compiles()}


def run_fleet(S, backend, args, mesh_devices=None):
    """One FleetSampler serving all S studies per round.

    ``mesh_devices`` shards the fleet's slot blocks over that many
    devices (``cfg.slots`` is the PER-DEVICE width, so the per-device
    slot count shrinks as devices are added and the compiled local
    program stays fixed-width — the placement-independence invariant)."""
    objs = _objectives(S, args.D)
    mesh = None
    slots = min(args.slots, S)
    if mesh_devices is not None:
        from repro.launch.mesh import make_fleet_mesh
        mesh = make_fleet_mesh(mesh_devices)
        slots = max(1, min(args.slots, -(-S // mesh_devices)))
    fs = FleetSampler([BoxSpace.cube(args.D, *o.bounds) for o in objs],
                      seed=0, slots=slots, mesh=mesh,
                      **_sampler_kw(args, backend))
    if args.debug_nans:
        install_nan_guard(fs.fleet)
    round_ms, steady = [], []
    for r in range(args.rounds):
        c0 = fs.stats_snapshot()["n_fleet_compiles"]
        t0 = time.perf_counter()
        trials = fs.ask_all()
        wall = time.perf_counter() - t0
        kinds = [s.last_ask_info.kind if s.last_ask_info is not None
                 else "startup" for s in fs.samplers]
        round_ms.append(1e3 * wall)
        steady.append(all(k == "incremental" for k in kinds)
                      and fs.stats_snapshot()["n_fleet_compiles"] == c0)
        for i, (t, obj) in enumerate(zip(trials, objs)):
            fs.tell(i, t.trial_id, obj(t.x))
    snap = fs.stats_snapshot()
    n_buckets = len({blk.bucket for blk in fs.fleet._blocks})
    extra = {
        "n_buckets": n_buckets,
        "n_blocks": snap["n_blocks"],
        "n_compiles_total": snap["n_fleet_compiles"],
        "n_full_refits": snap["n_full_refits"],
        "n_incremental": snap["n_incremental"],
        "n_fallbacks": snap["n_fallbacks"],
        "n_migrations": snap["n_migrations"],
        "retrace_causes": snap["retraces"]["causes"],
    }
    if args.debug_nans:
        extra["nan_guard"] = nan_guard_stats(fs.fleet)
    if mesh_devices is not None:
        extra.update({
            "mesh_devices": snap["n_devices"],
            "slots_per_device_width": slots,
            "occupancy_per_device": snap["slots_per_device"],
            "n_migrations_intra": snap["n_migrations_intra"],
            "n_migrations_cross": snap["n_migrations_cross"],
        })
    return round_ms, steady, extra


def run_chaos(S, backend, args):
    """Kill-and-recover under fault injection: journaled fleet, one
    injected unhealthy refit (→ quarantine), an injected crash at a
    journal offset, ``FleetSampler.recover``, then the schedule
    completes.  Returns one ``fleet_chaos`` row."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "..", "tests"))
    from faults import FaultInjector
    from repro.bo.journal import InjectedCrash

    objs = _objectives(S, args.D)
    spaces = [BoxSpace.cube(args.D, *o.bounds) for o in objs]
    d = tempfile.mkdtemp(prefix="fleet_chaos_")
    # land the kill ~60% through the expected ask+tell record stream
    kill_seq = max(2, int(0.6 * args.rounds * 2 * S))
    inj = FaultInjector(kill_at_seq=kill_seq, full_fail={0: 1})
    fs = FleetSampler(spaces, seed=0, slots=min(args.slots, S),
                      journal_dir=d, fault_injector=inj,
                      **_sampler_kw(args, backend))
    if args.debug_nans:
        install_nan_guard(fs.fleet)
    t0 = time.perf_counter()
    crashed = False
    try:
        for r in range(args.rounds):
            if r == args.n_startup + 1:
                fs.checkpoint()          # bound the replay length
            trials = fs.ask_all()
            for i, (t, obj) in enumerate(zip(trials, objs)):
                fs.tell(i, t.trial_id, obj(t.x))
    except InjectedCrash:
        crashed = True
    wall1 = time.perf_counter() - t0
    if not crashed:
        raise SystemExit(f"--chaos: kill_seq={kill_seq} never reached "
                         f"(rounds={args.rounds} too small)")
    completed_pre = sum(sum(t.state == "complete" for t in s.trials)
                        for s in fs.samplers)

    t0 = time.perf_counter()
    fs2, rep = FleetSampler.recover(d)
    recover_wall = time.perf_counter() - t0
    if args.debug_nans:
        install_nan_guard(fs2.fleet)
    n_at_recovery = sum(len(s.trials) for s in fs2.samplers)
    for i, tid in rep.pending:           # asked-but-never-told: re-eval
        fs2.tell(i, tid, objs[i](fs2.samplers[i].trials[tid].x))
    t0 = time.perf_counter()
    while min(len(s.trials) for s in fs2.samplers) < args.rounds:
        trials = fs2.ask_all()
        for i, (t, obj) in enumerate(zip(trials, objs)):
            fs2.tell(i, t.trial_id, obj(t.x))
    wall2 = time.perf_counter() - t0
    fs2.drain()

    snap = fs2.stats_snapshot()
    n_buckets = len({blk.bucket for blk in fs2.fleet._blocks})
    completed = sum(sum(t.state == "complete" for t in s.trials)
                    for s in fs2.samplers)
    # quarantine survives recovery as trial state (the engine counter is
    # per-process; the journal record is what persists)
    quarantined = sum(sum(t.state == "quarantined" for t in s.trials)
                      for s in fs2.samplers)
    total_wall = wall1 + recover_wall + wall2
    replay_per_100 = 100.0 * rep.replay_ms / max(n_at_recovery, 1)
    # goodput / loss breakdown, field-compatible with benchmarks/
    # bo_serve.py's chaos row: the fleet analog of a deadline miss is a
    # suggest in flight at the kill (asked, never told) — recovery
    # re-evaluates it rather than losing it, so it is counted separately
    # from work that completed cleanly on either side of the crash
    completed_post = completed - completed_pre
    row = {
        "backend": backend, "mode": "fleet_chaos", "S": S,
        "rounds": args.rounds, "D": args.D, "B": args.B,
        "pad": args.pad, "slots": min(args.slots, S),
        "refit_interval": args.refit_interval,
        "n_startup": args.n_startup,
        "kill_seq": kill_seq,
        "snapshot_step": rep.snapshot_step,
        "n_records": rep.n_records,
        "n_replayed": rep.n_replayed,
        "truncated_bytes": rep.truncated_bytes,
        "n_pending_retold": len(rep.pending),
        "n_trials_at_recovery": n_at_recovery,
        "replay_ms": round(rep.replay_ms, 3),
        "recover_wall_ms": round(1e3 * recover_wall, 3),
        "replay_ms_per_100_trials": round(replay_per_100, 3),
        "completed_suggests": completed,
        "goodput_sps": completed / total_wall,
        "goodput_pre_crash_sps": completed_pre / wall1,
        "goodput_post_recovery_sps": (completed_post / wall2
                                      if wall2 > 0 else None),
        "inflight_at_crash": len(rep.pending),
        "deadline_miss": 0,      # the fleet plane has no request deadlines
        "shed": 0,               # nothing is dropped: recovery re-evals
        "n_quarantined": quarantined,
        "n_buckets": n_buckets,
        "n_compiles_total": snap["n_fleet_compiles"],
        "retrace_causes": snap["retraces"]["causes"],
    }
    if args.debug_nans:
        row["nan_guard"] = nan_guard_stats(fs2.fleet)
    print(f"fleet_bench,{backend},S={S},chaos,kill_seq={kill_seq},"
          f"replay={replay_per_100:.2f}ms/100trials,"
          f"goodput={row['goodput_sps']:.2f}/s,"
          f"quarantined={quarantined},"
          f"compiles={snap['n_fleet_compiles']}", flush=True)
    if args.check_compiles:
        assert quarantined >= 1, \
            "chaos: injected unhealthy refit never quarantined"
        assert rep.truncated_bytes > 0, \
            "chaos: injected crash left no torn record"
        assert snap["n_fleet_compiles"] <= 3 * n_buckets, \
            f"chaos: {snap['n_fleet_compiles']} traces for {n_buckets} " \
            f"buckets after recovery (must be <= 3/bucket); " \
            f"retrace causes: {snap['retraces']['by_program']}"
        print(f"fleet_bench,{backend},S={S},chaos compile check OK "
              f"({snap['n_fleet_compiles']} traces, {n_buckets} buckets)",
              flush=True)
    shutil.rmtree(d)
    return row


def _throughputs(S, round_ms, steady, n_startup):
    """(aggregate sps over all post-startup rounds incl. traces,
    steady-state sps, #steady rounds)."""
    post = round_ms[n_startup:]
    agg = S * len(post) / (sum(post) / 1e3) if post else None
    sm = [m for m, keep in zip(round_ms, steady) if keep]
    sps = S / (float(np.median(sm)) / 1e3) if sm else None
    return agg, sps, len(sm)


def bench_backend(backend, sizes, args):
    rows = []
    fleet_compiles = {}
    for S in sizes:
        res = {}
        for mode, runner in (("loop", run_loop), ("fleet", run_fleet)):
            round_ms, steady, extra = runner(S, backend, args)
            agg, sps, n_steady = _throughputs(S, round_ms, steady,
                                              args.n_startup)
            row = {
                "backend": backend, "mode": mode, "S": S,
                "rounds": args.rounds, "D": args.D, "B": args.B,
                "pad": args.pad, "slots": min(args.slots, S),
                "refit_interval": args.refit_interval,
                "n_startup": args.n_startup,
                "round_ms": [round(m, 3) for m in round_ms],
                "suggests_per_sec_aggregate": agg,
                "suggests_per_sec_steady": sps,
                "n_steady_rounds": n_steady,
                **extra,
            }
            rows.append(row)
            res[mode] = row
            sps_s = f"{sps:.2f}/s" if sps else "n/a"
            agg_s = f"{agg:.2f}/s" if agg else "n/a"
            print(f"fleet_bench,{backend},S={S},{mode},"
                  f"aggregate={agg_s},steady={sps_s},"
                  f"compiles={extra['n_compiles_total']}", flush=True)
        lo, fl = res["loop"], res["fleet"]
        speed = None            # rounds <= n_startup: nothing to compare
        if lo["suggests_per_sec_aggregate"] and \
                fl["suggests_per_sec_aggregate"]:
            speed = (fl["suggests_per_sec_aggregate"]
                     / lo["suggests_per_sec_aggregate"])
        speed_steady = None
        if lo["suggests_per_sec_steady"] and fl["suggests_per_sec_steady"]:
            speed_steady = (fl["suggests_per_sec_steady"]
                            / lo["suggests_per_sec_steady"])
        print(f"fleet_bench,{backend},S={S},speedup_aggregate="
              f"{speed if speed else float('nan'):.2f}x,speedup_steady="
              f"{speed_steady if speed_steady else float('nan'):.2f}x",
              flush=True)
        rows.append({"backend": backend, "S": S, "summary": True,
                     "speedup_aggregate": speed,
                     "speedup_steady": speed_steady})
        fleet_compiles[S] = (fl["n_compiles_total"], fl["n_buckets"])
        fleet_retraces = fl["retrace_causes"]

        # mesh rows: the same fleet sharded over 1 and --mesh devices —
        # compile counts must not move with the device count
        if args.mesh and backend == "xla":
            mesh_compiles = {}
            for ndev in sorted({1, args.mesh}):
                round_ms, steady, extra = run_fleet(S, backend, args,
                                                    mesh_devices=ndev)
                agg, sps, n_steady = _throughputs(S, round_ms, steady,
                                                  args.n_startup)
                rows.append({
                    "backend": backend, "mode": "fleet_mesh", "S": S,
                    "rounds": args.rounds, "D": args.D, "B": args.B,
                    "pad": args.pad,
                    "refit_interval": args.refit_interval,
                    "n_startup": args.n_startup,
                    "round_ms": [round(m, 3) for m in round_ms],
                    "suggests_per_sec_aggregate": agg,
                    "suggests_per_sec_steady": sps,
                    "n_steady_rounds": n_steady,
                    **extra,
                })
                mesh_compiles[ndev] = (extra["n_compiles_total"],
                                       extra["n_buckets"])
                agg_s = f"{agg:.2f}/s" if agg else "n/a"
                print(f"fleet_bench,{backend},S={S},mesh={ndev}dev,"
                      f"aggregate={agg_s},"
                      f"compiles={extra['n_compiles_total']},"
                      f"occupancy={extra['occupancy_per_device']}",
                      flush=True)
            if args.check_compiles:
                vals = set(mesh_compiles.values())
                assert len(vals) == 1, \
                    f"S={S}: fleet compile counts vary with device " \
                    f"count: {mesh_compiles}"
                compiles, n_buckets = vals.pop()
                assert compiles <= 3 * n_buckets, \
                    f"S={S} mesh: {compiles} traces for {n_buckets} " \
                    f"buckets (must be <= 3/bucket); retrace causes: " \
                    f"{extra['retrace_causes']}"
                print(f"fleet_bench,{backend},S={S},mesh compile check "
                      f"OK {mesh_compiles}", flush=True)

    if args.check_compiles:
        for S, (compiles, n_buckets) in fleet_compiles.items():
            assert compiles <= 3 * n_buckets, \
                f"S={S}: {compiles} fleet traces for {n_buckets} buckets " \
                f"(must be <= 3/bucket); retrace causes: {fleet_retraces}"
        if len(fleet_compiles) > 1:
            vals = set(fleet_compiles.values())
            assert len(vals) == 1, \
                f"fleet compile counts vary with S: {fleet_compiles}"
        print(f"fleet_bench,{backend},compile check OK {fleet_compiles}",
              flush=True)
        if SPEEDUP_TARGET_S in sizes and backend == "xla":
            sp = [r["speedup_aggregate"] for r in rows
                  if r.get("summary") and r["S"] == SPEEDUP_TARGET_S][0]
            assert sp is not None and sp >= SPEEDUP_TARGET, \
                f"S={SPEEDUP_TARGET_S} speedup {sp} < {SPEEDUP_TARGET}x"
            print(f"fleet_bench,{backend},speedup check OK ({sp:.2f}x)",
                  flush=True)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: S=4, small GP buckets, xla only")
    ap.add_argument("--rounds", type=int, default=None,
                    help="ask/tell rounds per study (incl. startup)")
    ap.add_argument("--fleet-sizes", type=int, nargs="+", default=None)
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--backends", nargs="+", default=None,
                    choices=("xla", "pallas", "pallas_interpret"))
    ap.add_argument("--check-compiles", action="store_true")
    ap.add_argument("--mesh", type=int, default=None,
                    help="also run the fleet sharded over 1..N devices "
                    "(needs --xla_force_host_platform_device_count>=N "
                    "or N real devices)")
    ap.add_argument("--chaos", action="store_true",
                    help="add a journaled kill-and-recover row (fault "
                    "injection): recovery time + goodput under faults")
    ap.add_argument("--debug-nans", action="store_true",
                    help="wrap the three fleet block programs in a "
                    "finite-guard: every float leaf entering/leaving "
                    "them is checked; raises NonFiniteError naming the "
                    "program and leaf (one host sync per call)")
    ap.add_argument("--trace", action="store_true",
                    help="enable the obs span tracer (off by default); "
                    "adds a per-phase breakdown to the summary and "
                    "writes the Chrome-trace JSON to --trace-out")
    ap.add_argument("--trace-out", default="BENCH_fleet_trace.json")
    ap.add_argument("--out", default="BENCH_fleet.json")
    args = ap.parse_args(argv)

    if args.trace:
        obs_trace.enable()

    if args.mesh is not None and args.mesh > len(jax.devices()):
        raise SystemExit(
            f"--mesh {args.mesh} needs {args.mesh} visible devices, have "
            f"{len(jax.devices())} (set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={args.mesh})")

    if args.tiny:
        args.rounds = args.rounds or 14
        args.D, args.B, args.pad = 3, 4, 8
        args.refit_interval, args.n_startup = 4, 4
        args.slots = args.slots or 4
        args.fleet_sizes = args.fleet_sizes or [4]
        args.backends = args.backends or ["xla"]
    else:
        args.rounds = args.rounds or 34
        args.D, args.B, args.pad = 6, 10, 32
        args.refit_interval, args.n_startup = 8, 10
        args.slots = args.slots or 16
        args.fleet_sizes = args.fleet_sizes or [1, 4, 16, 64]
        args.backends = args.backends or ["xla", "pallas_interpret"]

    out = []
    for backend in args.backends:
        sizes = args.fleet_sizes
        if backend != "xla":
            # interpret-mode emulation is slow; cover the scaling story
            # with the endpoints
            sizes = [S for S in sizes if S <= SPEEDUP_TARGET_S]
        out.extend(bench_backend(backend, sizes, args))

    if args.chaos:
        out.append(run_chaos(args.fleet_sizes[0], "xla", args))

    # headline scalars, one per configuration — dashboards and PR diffs
    # read these without walking the row arrays
    summary = {}
    if args.trace:
        events = obs_trace.get().events()
        summary["phase_breakdown"] = obs_export.phase_breakdown(events)
        obs_export.write_chrome_trace(
            args.trace_out, events, process_name="fleet_throughput",
            meta={"bench": "fleet_throughput"})
        print(f"wrote {args.trace_out} ({len(events)} trace events)")
    for r in out:
        if r.get("summary"):
            summary[f"{r['backend']}_S{r['S']}_speedup_aggregate"] = \
                r["speedup_aggregate"]
            if r["speedup_steady"] is not None:
                summary[f"{r['backend']}_S{r['S']}_speedup_steady"] = \
                    r["speedup_steady"]
        elif r.get("mode") == "fleet_mesh":
            summary[f"{r['backend']}_S{r['S']}_mesh{r['mesh_devices']}"
                    f"_aggregate_sps"] = r["suggests_per_sec_aggregate"]
        elif r.get("mode") == "fleet":
            summary[f"{r['backend']}_S{r['S']}_retrace_causes"] = \
                r["retrace_causes"]
            if "nan_guard" in r:
                summary[f"{r['backend']}_S{r['S']}_nan_guard_checks"] = \
                    r["nan_guard"]["n_guard_checks"]
        elif r.get("mode") == "fleet_chaos":
            summary[f"{r['backend']}_S{r['S']}_chaos_replay_ms_per"
                    f"_100_trials"] = r["replay_ms_per_100_trials"]
            summary[f"{r['backend']}_S{r['S']}_chaos_goodput_sps"] = \
                r["goodput_sps"]
            summary[f"{r['backend']}_S{r['S']}_chaos_goodput_post"
                    f"_recovery_sps"] = r["goodput_post_recovery_sps"]
            summary[f"{r['backend']}_S{r['S']}_chaos_inflight"
                    f"_at_crash"] = r["inflight_at_crash"]
            summary[f"{r['backend']}_S{r['S']}_chaos_deadline_miss"] = \
                r["deadline_miss"]
            summary[f"{r['backend']}_S{r['S']}_chaos_shed"] = r["shed"]
            summary[f"{r['backend']}_S{r['S']}_chaos_retrace_causes"] = \
                r["retrace_causes"]

    record = {
        "bench": "fleet_throughput",
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
        "device": jax.devices()[0].device_kind,
        "jax_backend": jax.default_backend(),
        "python": platform.python_version(),
        "mode": "tiny" if args.tiny else "default",
        "mesh": args.mesh,
        "summary": summary,
        "rows": out,
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    print(f"wrote {args.out} ({len(out)} rows)")
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
