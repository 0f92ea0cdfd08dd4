"""One run of one benchmark cell: set-up, the measured window, the check.

The cell names a configuration (``bench/configs/<config>.json``: the
deployment) and a traffic mix (``bench/traffic/<mix>.json``).  Each metric
of ``BENCHMARK.json`` is read by ``bench/metrics/<metric>.py``.  The harness
knows none of them by name: a later cell, mix or metric is new files.

Everything goes through the served path: ``BOService`` (the sync core:
``submit_ask`` / ``service_step`` / ``submit_tell``) over a
``FleetSampler`` over a ``FleetEngine``, with the write-ahead journal on.

Set-up, in order, all of it counted in ``setup_s``: process start and
imports, the persistent compile cache, every study grown to its starting
n by random trials through the normal ask/tell calls, then two GP rounds:
the cold full refit, which is the traffic's own state, and the first
incremental round, which loads the last program the window uses.  Then
the window: a fixed amount of work, ``window_asks`` GP asks of every
study (the configuration sizes it to fit ``run_seconds``), from the
window's start to its last completion; no clock cuts it short, so a
slower program takes longer and never serves less.  Then, outside every
metric, the check against ``bench/reference.py``.
"""
from __future__ import annotations

import importlib.util
import json
import math
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import reference, traffic, tracing
from bench.bbob import BBOBFunction
from bench.compile_meter import CompileMeter

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
RUNS = BENCH / ".runs"               # journal and trace of the current run
GP_WARM_ROUNDS = 2                   # cold full refit + first incremental
# profiled start of a traced window: writing out its trace (~650k device
# ops a second) took 35-111 s for 1.5 s on the v5e, and the window waits
# for it, so a traced run keeps to 0.5 s to stay well inside its time
TRACE_SECONDS = 0.5
REGRET_STARTS = 10                   # the reference's uniform restarts
LATE_WAIT_S = 60.0                   # how long the window waits with no
                                     # answer before it gives an ask up


class BenchError(RuntimeError):
    """The run cannot be made: no result is printed."""


# ------------------------------------------------------------ the cell
def load_spec() -> Dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def load_cell(workload: str, spec: Dict) -> Dict:
    """The cell's entry, its configuration and mix, and its metrics."""
    cells = {c["name"]: c for c in spec["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json; "
                         f"have {sorted(cells)}")
    cell = cells[workload]
    with open(BENCH / "configs" / f"{cell['config']}.json") as f:
        config = json.load(f)
    with open(BENCH / "traffic" / f"{cell['traffic']}.json") as f:
        mix = traffic.validate(json.load(f))

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    return {"cell": cell, "config": config, "mix": mix,
            "end_to_end": mine(spec["end_to_end"]),
            "per_layer": mine(spec["per_layer"])}


def load_reader(metric: str) -> Callable:
    """``read(run) -> float | None`` of ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}",
                                                  path)
    if spec is None:
        raise BenchError(f"no reader {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------ the run
@dataclass
class Ask:
    """One ask of the window, as its worker saw it."""
    study: int
    due: float                       # perf_counter when it was issued
    req: object                      # the service's request handle
    done: Optional[float] = None     # perf_counter when it came back
    info: object = None              # the suggest's SuggestInfo


@dataclass
class Run:
    """What one run measured; the metric readers take it."""
    setup_s: float
    seed: int = 0
    t_start: float = 0.0
    t_end: float = 0.0
    asks: List[Ask] = field(default_factory=list)
    n_steps: int = 0                 # service steps that served asks
    setup_compiles: int = 0
    setup_compile_s: float = 0.0
    setup_cache_loads: int = 0
    window_compiles: int = 0
    window_cache_loads: int = 0
    spans: List[Dict] = field(default_factory=list)
    counters_start: Dict = field(default_factory=dict)
    counters_end: Dict = field(default_factory=dict)
    trace: Optional[Dict] = None
    trace_lo_ns: float = 0.0
    trace_hi_ns: float = 0.0
    trace_offset_ns: Optional[float] = None
    # tracer time from which no profiler work overlaps the window: the
    # per-step span metrics read only the steps that start after it
    span_from_us: float = 0.0
    device: Dict = field(default_factory=dict)
    deployment: Optional["Deployment"] = None

    @property
    def latencies(self) -> List[float]:
        return [a.done - a.due for a in self.asks if a.done is not None]

    @property
    def n_completed(self) -> int:
        return sum(a.done is not None for a in self.asks)

    def clean_steps(self):
        """The spans of the window's steps that start after
        ``span_from_us``, and how many of those steps served asks."""
        spans = [sp for sp in self.spans if sp.get("ts", 0.0)
                 >= self.span_from_us]
        n = sum(1 for sp in spans if sp.get("name") == "bench.service_step"
                and sp.get("args", {}).get("served", 0))
        return spans, n


def derive_seeds(seed: int) -> Dict[str, int]:
    """Seeds below 2**30 for the program, the objectives and the order,
    from any whole number."""
    ss = np.random.SeedSequence(seed % 2**63)
    prog, obj, order, ref = (int(v) & 0x3FFFFFFF
                             for v in ss.generate_state(4))
    return {"program": prog, "objectives": obj, "order": order,
            "reference": ref}


def study_order(seed: int, n: int) -> List[int]:
    """The order of the studies for ``--seed``: which tenant owns which
    study, and in which order the workers issue their asks and tells."""
    rng = np.random.default_rng(derive_seeds(seed)["order"])
    return [int(i) for i in rng.permutation(n)]


def check_device(chips: int) -> None:
    """A BenchError unless JAX finds a TPU with at least ``chips``
    chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU, but JAX found platform "
                         f"{devs[0].platform!r} ({devs[0].device_kind}, "
                         f"{len(devs)} devices)")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")


class Deployment:
    """The configuration's service, studies, tenants and workers."""

    def __init__(self, config: Dict, seed: int, run_dir: Path):
        """The studies, their objectives and their random starting trials
        come from the configuration's ``data_seed``: every run serves the
        same set of studies, the same work, so that runs with different
        seeds measure alike.  ``seed`` sets their order (tenant ownership
        and the workers' order)."""
        from repro.bo.sampler import FleetSampler
        from repro.bo.space import BoxSpace
        from repro.serve.bo_service import (BOService, OverloadConfig,
                                            TenantConfig)
        c = config
        seeds = derive_seeds(int(c["data_seed"]))
        self.dim = int(c["dim"])
        self.lower, self.upper = float(c["lower"]), float(c["upper"])
        self.budget = int(c["n_budget"])
        n = int(c["studies"])
        if not c["guarantees"]["journal"]:
            raise BenchError("every configuration journals its asks and "
                             "tells; the check reads them back")
        self.journal_dir = run_dir / "journal"
        self.objectives = [
            BBOBFunction(c["objectives"][i % len(c["objectives"])],
                         self.dim, seeds["objectives"] + i)
            for i in range(n)]
        self.fs = FleetSampler(
            BoxSpace.cube(self.dim, self.lower, self.upper), n_studies=n,
            seed=seeds["program"], slots=int(c["slots"]),
            n_startup_trials=int(c["n_start"]),
            n_restarts=int(c["n_restarts"]),
            pad_multiple=int(c["pad_multiple"]),
            refit_interval=int(c["refit_interval"]),
            gp_fit_restarts=int(c["gp_fit_restarts"]),
            posterior_backend="auto",
            journal_dir=str(self.journal_dir))
        self.order = study_order(seed, n)
        tenants, self.owner, first = [], {}, 0
        for t in c["tenants"]:
            own = tuple(self.order[first:first + int(t["studies"])])
            first += len(own)
            tenants.append(TenantConfig(t["name"], weight=float(t["weight"]),
                                        studies=own))
            self.owner.update({s: t["name"] for s in own})
        if first != n:
            raise BenchError(f"tenants own {first} studies, config has {n}")
        self.svc = BOService(self.fs, tenants, quantum=float(c["quantum"]),
                             overload=OverloadConfig(reject_depth=2 * n,
                                                     degrade_depth=4 * n,
                                                     shed_depth=8 * n))
        # per study, (trial id, x, y) of every tell, in order
        self.told: List[List] = [[] for _ in range(n)]

    @property
    def n_studies(self) -> int:
        return len(self.objectives)

    def ask(self, study: int):
        return self.svc.submit_ask(self.owner[study], study)

    def tell(self, study: int, trial) -> None:
        y = self.objectives[study](trial.x)
        self.svc.submit_tell(self.owner[study], study, trial.trial_id, y)
        self.told[study].append((trial.trial_id, np.array(trial.x), y))

    def serve_rounds(self, n: int) -> None:
        """Set-up rounds: every study asks, one service step serves them
        all, every worker tells."""
        for _ in range(n):
            reqs = [(s, self.ask(s)) for s in self.order]
            self.svc.service_step()
            for s, req in reqs:
                if req.state != "done":
                    raise BenchError(f"set-up ask of study {s} ended "
                                     f"{req.state}: {req.error}")
                self.tell(s, req.result)

    def wants_more(self, study: int) -> bool:
        return len(self.told[study]) < self.budget


def run_cell(config: Dict, mix: Dict, *, seed: int, trace: bool,
             meter: CompileMeter, t_process: float, run_dir: Path,
             late_wait_s: float = LATE_WAIT_S) -> Run:
    """Set up, measure the window, read the device; no checks yet."""
    import jax
    from repro.obs import trace as obs

    if mix["loop"] != "closed":
        raise BenchError(f"no cell runs the {mix['loop']!r} loop yet")
    k = int(config["window_asks"])
    if int(config["n_start"]) + GP_WARM_ROUNDS + k > int(config["n_budget"]):
        raise BenchError("the window's asks would run past the studies' "
                         "trial budget")
    shutil.rmtree(run_dir, ignore_errors=True)
    run_dir.mkdir(parents=True)
    meter.phase = "setup"
    dep = Deployment(config, seed, run_dir)
    dep.serve_rounds(int(config["n_start"]) + GP_WARM_ROUNDS)

    tracer = obs.enable(capacity=1 << 22) if trace else None
    run = Run(setup_s=0.0, seed=seed)
    run.counters_start = dep.svc.stats_snapshot()
    # the profiler's op-level trace of a float64-emulated step runs to
    # ~650k device ops a second (minutes to write out a whole step), so
    # it covers only the window's first TRACE_SECONDS
    profile = _Profile(run_dir / "profile", tracer) if trace else None

    meter.phase = "window"
    t0 = time.perf_counter()
    run.t_start = t0
    run.setup_s = t0 - t_process
    pending: Dict[int, Ask] = {}
    issued = {s: 0 for s in dep.order}

    def issue(s: int) -> None:
        issued[s] += 1
        pending[s] = Ask(s, time.perf_counter(), dep.ask(s))

    def step() -> None:
        a = tracer.now_us() if tracer else 0.0
        served = dep.svc.service_step()
        if tracer:
            tracer.record_span("bench.service_step", a, tracer.now_us() - a,
                               served=served)
        if served:
            run.n_steps += 1
        else:
            time.sleep(0.01)          # nothing was due: do not spin

    def collect() -> int:
        t_done = time.perf_counter()
        a = tracer.now_us() if tracer else 0.0
        n = 0
        for s in list(pending):
            ask = pending[s]
            if not ask.req.done:
                continue
            del pending[s]
            run.asks.append(ask)
            n += 1
            if ask.req.state != "done":
                continue
            ask.done = t_done
            ask.info = dep.fs.samplers[s].last_ask_info
            dep.tell(s, ask.req.result)
            if issued[s] < k:
                issue(s)
        if tracer:
            tracer.record_span("bench.workers", a, tracer.now_us() - a)
        return n

    if profile is not None:
        profile.arm()
    for s in dep.order:
        issue(s)
    last = time.perf_counter()
    while pending:
        step()
        if profile is not None:
            # the next step starts once the profiler is stopped and its
            # trace written: from there on the span metrics read
            profile.wait()
        if collect():
            last = time.perf_counter()
        elif time.perf_counter() - last > late_wait_s:
            break                     # the rest is never answered
    run.asks.extend(pending.values())
    pending.clear()
    done = [a.done for a in run.asks if a.done is not None]
    run.t_end = max(done) if done else time.perf_counter()
    meter.phase = "after"
    run.setup_compiles = meter.compiles("setup")
    run.setup_compile_s = meter.compile_s("setup")
    run.setup_cache_loads = meter.hits("setup")
    run.window_compiles = meter.compiles("window")
    run.window_cache_loads = meter.hits("window")
    run.counters_end = dep.svc.stats_snapshot()

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    run.device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": 1,
                  "memory_peak_bytes": int(stats.get("peak_bytes_in_use",
                                                     0))}
    if tracer is not None:
        run.spans = tracer.events()
        if tracer.n_dropped:
            raise BenchError(f"span ring dropped {tracer.n_dropped} events")
        obs.disable()
        lo_us, hi_us, run.span_from_us = profile.join()
        run.trace = tracing.extract(str(run_dir / "profile"))
        run.trace_offset_ns = tracing.sync_offset_ns(run.trace, lo_us)
        if run.trace_offset_ns is None:
            raise BenchError("the profiler trace lost its sync mark")
        run.trace_lo_ns = run.trace_offset_ns + 1e3 * lo_us
        run.trace_hi_ns = run.trace_offset_ns + 1e3 * hi_us
    run.deployment = dep
    return run


class _Profile:
    """The JAX profiler over the first TRACE_SECONDS of the window.  It
    starts in set-up; :meth:`arm` marks the window's start (the SYNC
    mark, on both clocks) and starts a timer thread that stops it, while
    the window runs on; :meth:`join` waits for the stop and returns the
    traced window and the moment the stop returned, in tracer
    microseconds: writing the trace out competes with the window for the
    host until then."""

    def __init__(self, log_dir: Path, tracer):
        import jax
        self.tracer = tracer
        self.stopped: Dict[str, object] = {}
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(log_dir), profiler_options=opts)

    def arm(self) -> None:
        import threading
        import jax
        self.lo = self.tracer.now_us()
        with jax.profiler.TraceAnnotation(tracing.SYNC):
            pass
        self.timer = threading.Timer(TRACE_SECONDS, self._stop)
        self.timer.start()

    def _stop(self) -> None:
        import jax
        self.stopped["at"] = self.tracer.now_us()
        try:
            jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 — re-raised in join()
            self.stopped["error"] = e
        self.stopped["returned"] = self.tracer.now_us()

    def wait(self) -> None:
        self.timer.join(timeout=600.0)

    def join(self):
        self.wait()
        if self.timer.is_alive():
            raise BenchError("the profiler did not stop within 600 s")
        if "error" in self.stopped:
            raise BenchError(f"the profiler failed: {self.stopped['error']!r}")
        return self.lo, self.stopped["at"], self.stopped["returned"]


# ------------------------------------------------------------ the check
def check_numbers(run: Run, dtype=np.float64,
                  limits: Optional[Dict] = None) -> Dict[str, float]:
    """The numbers ``correct`` compares, from what the window produced.

    - ``unserved_asks``: acknowledged asks of the window that did not come
      back with a finite suggestion inside the box;
    - ``journal_missing``: acknowledged tells (set-up and window) and
      delivered suggestions of the window that the journal does not give
      back as they were acknowledged;
    - ``logei_gap_nats``: over every suggestion of the window, the largest
      gap between the LogEI the service's MSO reported at its suggestion
      and the reference's LogEI there, for the GP the suggest saw;
    - ``pgrad_inf``: over every suggestion, the largest infinity norm of
      the projected gradient of the reference's −LogEI there, which the
      MSO's stopping test holds under its ``pgtol`` (an MSO that stopped
      early reads large);
    - ``incumbent_excess_nats``: over every suggestion, the most by which
      the reference's LogEI at the study's best observation exceeds its
      LogEI at the suggestion (the MSO starts from that observation, so a
      sound one never ends below it);
    - ``regret_nats``: the mean over every suggestion of the LogEI that
      the reference's own multistart L-BFGS-B (``REGRET_STARTS`` starts
      drawn from the run's seed, the best observation and the suggestion)
      finds above the suggestion's: an MSO with too few restarts.  Only
      where ``limits`` compares it, or ``limits`` is not given;
    - ``map_gain_nats``: over every full refit whose θ a suggestion of the
      window used, how far the reference's L-BFGS-B still lowers the fit's
      MAP objective from that θ, on the data the refit saw.

    With ``dtype=np.float32`` the reference stands in for the program
    (the control): its float32 LogEI against the float64 one.
    """
    dep = run.deployment
    lo, hi = dep.lower, dep.upper
    unserved = 0
    for a in run.asks:
        x = None if a.done is None else np.asarray(a.req.result.x)
        if x is None or x.shape != (dep.dim,) or not np.all(np.isfinite(x)) \
                or np.any(x < lo) or np.any(x > hi):
            unserved += 1
    records = reference.read_journal(str(dep.journal_dir / "journal.log"))
    tells, asks_x, theta_at, theta, fit_of = {}, {}, {}, {}, {}
    for r in records:
        op = r.get("op")
        if op == "tell":
            tells[(r["study"], r["trial"])] = r["y"]
        elif op == "refit":
            theta[r["sid"]] = r["theta"]
            fit_of[r["sid"]] = None          # fitted for the next ask
        elif op == "ask":
            s = r["study"]
            asks_x[(s, r["trial"])] = r["x"]
            if not r["startup"]:
                if s in fit_of and fit_of[s] is None:
                    fit_of[s] = r["trial"]       # the refit fed this ask
                theta_at[(s, r["trial"])] = (theta.get(s), fit_of.get(s))
    missing = 0
    for s, seq in enumerate(dep.told):
        for tid, _x, y in seq:
            if tells.get((s, tid)) != y:
                missing += 1

    def observed(study: int, before: int):
        prior = [(x, y) for tid, x, y in dep.told[study] if tid < before]
        xo = (np.stack([p[0] for p in prior]) - lo) / (hi - lo)
        return xo, np.array([p[1] for p in prior]), len(prior) == before

    want_regret = limits is None or "regret_nats" in limits
    starts_rng = np.random.default_rng(derive_seeds(run.seed)["reference"])
    gap = pgrad = 0.0
    regrets = []
    excess = -math.inf                      # below 0 where every one beat it
    fits = {}
    for a in run.asks:
        if a.done is None:
            continue
        t = a.req.result
        key = (a.study, t.trial_id)
        if asks_x.get(key) != list(map(float, t.x)):
            missing += 1
        th, fit_trial = theta_at.get(key, (None, None))
        xo, yo, whole = observed(a.study, t.trial_id)
        if th is None or a.info is None or not whole:
            gap = pgrad = excess = math.inf
            regrets.append(math.inf)
            continue
        fits[(a.study, fit_trial)] = th
        xq = (np.asarray(t.x) - lo) / (hi - lo)
        try:
            logei = reference.logei_fn(xo, yo, th)
            ref = logei(xq)[0]
            got = (float(np.asarray(a.info.best_acq)) if dtype == np.float64
                   else float(reference.logei_at(xo, yo, th, xq,
                                                 dtype=dtype)[0]))
            inc = logei(xo[np.argmin(yo)])[0]
            pg = reference.projected_grad_inf(xo, yo, th, xq)
            if want_regret:
                regrets.append(reference.logei_regret(
                    xo, yo, th, xq,
                    starts_rng.random((REGRET_STARTS, dep.dim))))
        except np.linalg.LinAlgError:      # K not positive definite
            gap = pgrad = excess = math.inf
            regrets.append(math.inf)
            continue
        gap = max(gap, _finite(abs(got - ref)))
        pgrad = max(pgrad, _finite(pg))
        excess = max(excess, _finite(inc - ref))
    map_gain = 0.0
    for (study, fit_trial), th in fits.items():
        xo, yo, _ = observed(study, fit_trial)
        try:
            map_gain = max(map_gain,
                           _finite(reference.map_polish(xo, yo, th)))
        except np.linalg.LinAlgError:
            map_gain = math.inf
    out = {"unserved_asks": float(unserved),
           "journal_missing": float(missing),
           "logei_gap_nats": gap,
           "pgrad_inf": pgrad,
           "incumbent_excess_nats": excess if run.n_completed else 0.0,
           "map_gain_nats": map_gain}
    if want_regret:
        out["regret_nats"] = _finite(np.mean(regrets)) if regrets else 0.0
    return out


def _finite(v: float) -> float:
    return float(v) if np.isfinite(v) else math.inf


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> Dict:
    """Each number that has a limit beside it; a number is within it when
    it is finite and no larger."""
    return {k: {"value": numbers[k], "limit": lim,
                "ok": bool(np.isfinite(numbers[k]) and numbers[k] <= lim)}
            for k, lim in limits.items()}


# ------------------------------------------------------------ the result
def result_line(run: Run, metrics: List[Dict], trace: bool,
                checks: Dict) -> Dict:
    out_metrics = {}
    for m in metrics:
        v = load_reader(m["name"])(run)
        if v is not None:
            out_metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = dict(run.device)
    line = {"correct": all(c["ok"] for c in checks.values()),
            "attempted": len(run.asks),
            "failed": int(checks["unserved_asks"]["value"]),
            "metrics": out_metrics, "device": device}
    if trace and run.trace is not None:
        lo, hi = run.trace_lo_ns, run.trace_hi_ns
        busy = tracing.device_busy(run.trace, lo, hi)
        if busy is not None:
            device["busy_s"] = busy
        device["window_s"] = 1e-9 * (hi - lo)
        line["breakdown"] = {
            "device_ops": tracing.top_device_ops(run.trace, lo, hi),
            "idle_gaps": tracing.idle_gaps(run.trace, run.spans,
                                           run.trace_offset_ns, lo, hi)}
    # a number that has none (a reference that could not be computed)
    # is null: the line stays JSON
    line["checks"] = {k: {"value": float(c["value"])
                          if math.isfinite(c["value"]) else None,
                          "limit": c["limit"]}
                      for k, c in checks.items()}
    return line


def main(argv=None, t_process: Optional[float] = None) -> int:
    import argparse
    t_process = time.perf_counter() if t_process is None else t_process
    ap = argparse.ArgumentParser(description="Run one cell of the BO "
                                 "service's chip benchmark.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="run_seconds; the window is a fixed amount of "
                    "work sized to fit it, and says so when it does not")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    run_dir = RUNS / args.workload
    try:
        line, run = measure(args, t_process, run_dir)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    finally:
        shutil.rmtree(run_dir, ignore_errors=True)
    print(f"setup: {run.setup_s:.3f} s, of it {run.setup_compile_s:.3f} s "
          f"compiling {run.setup_compiles} programs; "
          f"{run.setup_cache_loads} loaded from the persistent cache",
          file=sys.stderr)
    print(f"window: {run.n_completed} asks in {run.n_steps} steps over "
          f"{run.t_end - run.t_start:.3f} s; compiles "
          f"{run.window_compiles} and persistent-cache loads "
          f"{run.window_cache_loads} inside the window (should be 0)",
          file=sys.stderr)
    if run.t_end - run.t_start > args.seconds:
        print(f"window: its fixed work outlasted --seconds {args.seconds}",
              file=sys.stderr)
    for k, c in line["checks"].items():
        v = "none" if c["value"] is None else repr(float(c["value"]))
        print(f"check {k} {v} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


def use_compile_cache() -> None:
    """JAX's persistent compile cache where the program's entry points
    keep it (``repro.launch.compile_cache``: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``<checkout>/.jax_cache``).  Every program goes
    in, so that only a cell's first run in a checkout compiles."""
    import jax
    from repro.launch.compile_cache import use_compile_cache as use
    use()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def measure(args, t_process: float, run_dir: Path):
    cell = load_cell(args.workload, load_spec())
    import jax
    jax.config.update("jax_enable_x64", True)
    use_compile_cache()
    check_device(int(cell["cell"]["chips"]))
    with CompileMeter() as meter:
        run = run_cell(cell["config"], cell["mix"], seed=args.seed,
                       trace=bool(args.trace), meter=meter,
                       t_process=t_process, run_dir=run_dir)
    limits = cell["config"]["limits"]
    checks = judge(check_numbers(run, limits=limits), limits)
    metrics = cell["per_layer"] if args.trace else cell["end_to_end"]
    return result_line(run, metrics, bool(args.trace), checks), run
