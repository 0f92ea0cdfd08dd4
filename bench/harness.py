"""One run of one benchmark cell: set-up, the measured window, the check.

The cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<mix>.json``).  The configuration names its
deployment, ``bench/deployments/<deployment>.py``, which builds the
served path and judges what it served.  Each metric of
``BENCHMARK.json`` is read by ``bench/metrics/<metric>.py``.  The harness
knows none of them by name: a later cell, mix or metric is new files,
and so is a configuration of another shape (studies of several
dimensions, several fleets, other search spaces): a new deployment
module beside its configuration.

A deployment module defines:

- ``Deployment(config, seed, run_dir)``: the service and its studies,
  with ``order`` (every study, in the workers' order), ``n_studies``,
  ``tenant_studies`` (each tenant's studies, in the configuration's
  order of tenants), ``ask(study)`` (a service request handle),
  ``tell(study, trial)``, ``serve_rounds(n)`` (set-up's whole rounds),
  ``svc`` (whose ``service_step`` serves and whose ``stats_snapshot``
  the counter metrics read), ``box(study) -> (lower, upper, dim)``,
  ``ask_info(study)`` (the study's last suggest, as the program reported
  it), ``journal_records()``, ``told`` (per study, every acknowledged
  ``(trial id, x, y)``) and ``budget``;
- ``window_room(config, told)``: the most asks a study may take in a
  window that it starts with ``told`` trials told;
- ``check_numbers(run, dtype, limits)``: the numbers ``correct``
  compares.

Set-up, in order, all of it counted in ``setup_s``: process start and
imports, the persistent compile cache, every study grown to its starting
n by random trials through the normal ask/tell calls, then two GP rounds:
the cold full refit, which is the traffic's own state, and the first
incremental round, which loads the last program the window uses.  Then
the window, a fixed amount of work from its start to its last
completion; no clock cuts it short, so a slower program takes longer and
never serves less:

- closed loop: ``window_asks`` GP asks of every study, each issued by
  the study's worker once it has told its last suggestion;
- open loop: the mix's first ``arrivals`` Poisson arrivals, drawn from
  the configuration's ``data_seed`` (``traffic.open_schedule``), the
  window starting at the first.  Before each service step every arrival
  that is due is submitted; after it every suggestion that came back is
  told at once.  An ask's latency runs from its scheduled arrival.

Then, outside every metric, the deployment's check against
``bench/reference.py``.
"""
from __future__ import annotations

import importlib.util
import json
import math
import re
import shutil
import sys
import time
from collections import Counter
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

from bench import traffic, tracing
from bench.compile_meter import CompileMeter

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
RUNS = BENCH / ".runs"               # journal and trace of the current run
GP_WARM_ROUNDS = 2                   # cold full refit + first incremental
# profiled start of a traced window: writing out its trace (~650k device
# ops a second) took 35-111 s for 1.5 s on the v5e, and the window waits
# for it, so a traced run keeps to 0.5 s to stay well inside its time
TRACE_SECONDS = 0.5
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
    deployment_of(config)

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    return {"cell": cell, "config": config, "mix": mix,
            "end_to_end": mine(spec["end_to_end"]),
            "per_layer": mine(spec["per_layer"])}


def deployment_of(config: Dict):
    """The module ``bench/deployments/<deployment>.py`` that the
    configuration names, loaded once per process."""
    name = config.get("deployment")
    if not isinstance(name, str) or not re.fullmatch(r"[A-Za-z0-9_]+",
                                                      name):
        raise BenchError(f"the configuration names no deployment module: "
                         f"'deployment' is {name!r}")
    key = f"bench_deployment_{name}"
    if key not in sys.modules:
        path = BENCH / "deployments" / f"{name}.py"
        if not path.is_file():
            raise BenchError(f"no deployment module {path}")
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[key] = mod
    return sys.modules[key]


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
                                     # (closed) or scheduled (open)
    req: object                      # the service's request handle, or
                                     # None where the service refused it
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
    deployment: object = None        # the deployment module's Deployment

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
    """Seeds below 2**30 for the program, the objectives, the order, the
    reference's starts and an open mix's arrivals, from any whole
    number."""
    ss = np.random.SeedSequence(seed % 2**63)
    prog, obj, order, ref, arrivals = (int(v) & 0x3FFFFFFF
                                       for v in ss.generate_state(5))
    return {"program": prog, "objectives": obj, "order": order,
            "reference": ref, "arrivals": arrivals}


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


def open_schedule(config: Dict, mix: Dict):
    """An open mix's arrivals for this configuration's tenants, drawn from
    its ``data_seed``: ``(t, tenant, position)``, ``t`` in seconds from
    the first arrival, ``position`` the index among the tenant's
    studies.  The same for every ``--seed``."""
    tenants = [(float(t["weight"]), int(t["studies"]))
               for t in config["tenants"]]
    seed = derive_seeds(int(config["data_seed"]))["arrivals"]
    try:
        return traffic.open_schedule(mix, tenants, seed)
    except ValueError as e:
        raise BenchError(str(e)) from None


def run_cell(config: Dict, mix: Dict, *, seed: int, trace: bool,
             meter: CompileMeter, t_process: float, run_dir: Path,
             late_wait_s: float = LATE_WAIT_S) -> Run:
    """Set up, measure the window, read the device; no checks yet."""
    import jax
    from repro.obs import trace as obs

    deployment = deployment_of(config)
    told = int(config["n_start"]) + GP_WARM_ROUNDS
    if mix["loop"] == "closed":
        schedule = None
        most = int(config["window_asks"])
    else:
        schedule = open_schedule(config, mix)
        most = max(Counter((k, i) for _, k, i in schedule).values())
    room = deployment.window_room(config, told)
    if most > room:
        raise BenchError(f"the window asks up to {most} times of one "
                         f"study, which would run past its trial budget "
                         f"or its pad bucket ({room} asks fit)")
    shutil.rmtree(run_dir, ignore_errors=True)
    run_dir.mkdir(parents=True)
    meter.phase = "setup"
    dep = deployment.Deployment(config, seed, run_dir)
    dep.serve_rounds(told)

    tracer = obs.enable(capacity=1 << 22) if trace else None
    run = Run(setup_s=0.0, seed=seed, deployment=dep)
    run.counters_start = dep.svc.stats_snapshot()
    # the profiler's op-level trace of a float64-emulated step runs to
    # ~650k device ops a second (minutes to write out a whole step), so
    # it covers only the window's first TRACE_SECONDS
    profile = _Profile(run_dir / "profile", tracer) if trace else None

    meter.phase = "window"
    window = _Window(run, dep, tracer, profile, t_process, late_wait_s)
    if schedule is None:
        window.closed(most)
    else:
        window.open([(t, dep.tenant_studies[k][i])
                     for t, k, i in schedule])
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
    return run


class _Window:
    """The measured window's loop over one deployment: the closed loop's
    workers or the open loop's schedule, one service step at a time."""

    def __init__(self, run: Run, dep, tracer, profile, t_process: float,
                 late_wait_s: float):
        self.run, self.dep = run, dep
        self.tracer, self.profile = tracer, profile
        self.t_process = t_process
        self.late_wait_s = late_wait_s

    def start(self) -> float:
        t0 = time.perf_counter()
        self.run.t_start = t0
        self.run.setup_s = t0 - self.t_process
        if self.profile is not None:
            self.profile.arm()
        return t0

    def step(self) -> float:
        """One service step; then, in a traced run, the wait for the
        profiler to stop and write its trace (the span metrics read from
        there on).  Returns the seconds of that wait."""
        tr = self.tracer
        a = tr.now_us() if tr else 0.0
        served = self.dep.svc.service_step()
        if tr:
            tr.record_span("bench.service_step", a, tr.now_us() - a,
                           served=served)
        if served:
            self.run.n_steps += 1
        else:
            time.sleep(0.01)          # nothing was due: do not spin
        if self.profile is None:
            return 0.0
        w = time.perf_counter()
        self.profile.wait()
        return time.perf_counter() - w

    def finish(self, ask: Ask, t_done: float) -> bool:
        """Record an ask that ended; tell its suggestion at once.  False
        where it ended without one."""
        self.run.asks.append(ask)
        if ask.req.state != "done":
            return False
        ask.done = t_done
        ask.info = self.dep.ask_info(ask.study)
        self.dep.tell(ask.study, ask.req.result)
        return True

    def workers(self, collect: Callable[[float], int]) -> int:
        t_done = time.perf_counter()
        tr = self.tracer
        a = tr.now_us() if tr else 0.0
        n = collect(t_done)
        if tr:
            tr.record_span("bench.workers", a, tr.now_us() - a)
        return n

    def closed(self, k: int) -> None:
        """Every study's worker asks ``k`` times, each ask once it has
        told the last suggestion."""
        dep = self.dep
        pending: Dict[int, Ask] = {}
        issued = {s: 0 for s in dep.order}

        def issue(s: int) -> None:
            issued[s] += 1
            pending[s] = Ask(s, time.perf_counter(), dep.ask(s))

        def collect(t_done: float) -> int:
            n = 0
            for s in list(pending):
                ask = pending[s]
                if not ask.req.done:
                    continue
                del pending[s]
                n += 1
                if self.finish(ask, t_done) and issued[s] < k:
                    issue(s)
            return n

        self.start()
        for s in dep.order:
            issue(s)
        last = time.perf_counter()
        while pending:
            self.step()
            if self.workers(collect):
                last = time.perf_counter()
            elif time.perf_counter() - last > self.late_wait_s:
                break                 # the rest is never answered
        self.run.asks.extend(pending.values())

    def open(self, schedule) -> None:
        """Submit each ``(t, study)`` of the schedule once it is due, ``t``
        seconds after the window's start, whatever the service's speed.
        A traced run's wait for the profiler shifts the rest of the
        schedule by as much, so the steps after it see the arrivals that
        an untraced run sees."""
        pending: List[Ask] = []

        def collect(t_done: float) -> int:
            done = [a for a in pending if a.req.done]
            pending[:] = [a for a in pending if not a.req.done]
            for ask in done:
                self.finish(ask, t_done)
            return len(done)

        base = self.start()
        nxt, last = 0, base
        while nxt < len(schedule) or pending:
            if not pending:
                wait = base + schedule[nxt][0] - time.perf_counter()
                if wait > 0.0:
                    time.sleep(wait)
                last = time.perf_counter()
            now = time.perf_counter()
            while nxt < len(schedule) and base + schedule[nxt][0] <= now:
                t, s = schedule[nxt]
                nxt += 1
                try:
                    req = self.dep.ask(s)
                except RuntimeError:  # refused: counts as unserved
                    self.run.asks.append(Ask(s, base + t, None))
                    continue
                pending.append(Ask(s, base + t, req))
            if not pending:
                continue
            base += self.step()
            if self.workers(collect):
                last = time.perf_counter()
            elif time.perf_counter() - last > self.late_wait_s:
                break                 # the rest is never answered
        self.run.asks.extend(pending)
        self.run.asks.extend(Ask(s, base + t, None)
                             for t, s in schedule[nxt:])


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
    check = deployment_of(cell["config"]).check_numbers
    checks = judge(check(run, limits=limits), limits)
    metrics = cell["per_layer"] if args.trace else cell["end_to_end"]
    return result_line(run, metrics, bool(args.trace), checks), run
