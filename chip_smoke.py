#!/usr/bin/env python3
"""Smoke run of the BO service's ask path on a TPU.

One process drives the served path through the entry points a user calls,
at a study size users run, and checks what comes out:

  A  the service (the main path): ``BOService`` over a ``FleetSampler``.
     4 tenants (weights 1, 1, 2, 4) own 64 BBOB studies at D=20, with
     B=10 restarts, 16 slots per block and pad multiple 32.  Each study
     is grown to n=193 by its random startup trials; then 3 rounds of
     asks are served and told back.  Round 1 refits fully, rounds 2-3
     take the incremental program.
  B  the paper's method: ``GPSampler(strategy="dbe")``, coroutine D-BE
     over ``EvalEngine``, 3 asks at the same D and n.
  C  the solo fused path: ``GPSampler(strategy="dbe_vec")``, 3 asks.  It
     is the one path whose incremental program donates its buffers.

x64 is on and ``posterior_backend="auto"``, as in every entry point.  GP
state is then float64, and ``auto`` serves it with the float64 Cholesky
posterior (``xla``), on the TPU too: the fused Pallas kernel is float32,
and near the data of a confident GP its variance is rounding noise (see
``engine.posterior.resolve_backend``).  The kernel still runs on the chip
here, on the served studies' GPs, against the same reference, but its
check only catches reduced-precision matmul passes (``posterior_tol``).
Phases warm up (compile) concurrently, one thread each, then
take their last ask one at a time.  Every check below fails the run;
nothing is caught and turned into 0:

  * every suggestion is finite and inside its box;
  * the service sheds, fails, rejects, retries, quarantines, parks and
    degrades nothing;
  * no program is traced after warm-up: <=3 programs per fleet
    (bucket, slots) shape, <=2 per solo bucket;
  * for 4 studies, the chip's served posterior and its Pallas posterior,
    mean and variance at the suggestion and at 256 random points, agree
    with the float64 reference ``gp.gpr.predict`` on the host CPU to
    ``posterior_tol`` of the amplitude;
  * at every suggestion the float64 reference LogEI is no lower than at
    the incumbent (``LOGEI_TOL``): one restart starts at the incumbent;
  * with ``--chips 4``, the mesh's suggestions equal one device's bit for
    bit, and each of the four chips holds a quarter of the studies.

Usage::

    python chip_smoke.py              # phases A, B, C on one chip
    python chip_smoke.py --chips 4    # phase A on make_fleet_mesh(4) and
                                      # make_fleet_mesh(1), compared

Without a TPU it exits non-zero, naming the platform JAX found.  Timings
are smoke readings, not benchmarks.  The last line of stdout is one JSON
object: ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# the float64 reference runs on the host CPU beside the chip
_platforms = os.environ.get("JAX_PLATFORMS")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

jax.config.update("jax_enable_x64", True)

from repro.bo.objectives import make_objective  # noqa: E402
from repro.bo.sampler import FleetSampler, GPSampler  # noqa: E402
from repro.bo.space import BoxSpace  # noqa: E402
from repro.core.acquisition import log_ei  # noqa: E402
from repro.engine import bucket_ladder, posterior  # noqa: E402
from repro.gp.fit import fit_gp, pad_bucket_for, standardize  # noqa: E402
from repro.gp.gpr import fit_gram, predict, with_kinv  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_fleet_mesh  # noqa: E402
from repro.serve.bo_service import (BOService, OverloadConfig,  # noqa: E402
                                    TenantConfig)

# The paper's §5 BBOB set: f1 sphere, f6 attractive sector, f7 step
# ellipsoidal, f15 rotated Rastrigin, on [-5, 5]^D.
OBJECTIVES = ("sphere", "attractive_sector", "step_ellipsoidal", "rastrigin")
TENANT_WEIGHTS = (1.0, 1.0, 2.0, 4.0)

U32 = float(np.finfo(np.float32).eps) / 2     # float32 unit roundoff


def posterior_tol(n: int, amp: float, noise: float) -> float:
    """Allowed |chip - reference| / amplitude for the posterior mean and
    variance.  The kernel rounds K⁻¹, α and the cross-gram to float32 and
    forms the variance as amp - kᵀK⁻¹k, which cancels: each rounding is
    amplified by ‖K⁻¹‖ ≈ 1/noise, and n of them accumulate like √n.  So
    the error scale is √n·u32·amp/noise.  At D=20, n=195 on the four BBOB
    objectives the interpreter stays 4x inside it and a v5e 3x; the MXU's
    default single bfloat16 pass exceeded it several hundredfold.

    That is all this bound catches in the Pallas kernel: reduced-precision
    matmul passes.  It does not hold the kernel's variance to the true
    variance where that is small.  Near the data of a confident GP (sphere
    studies, amp/noise ~1e5) the bound is ~0.1·amp while the variance is
    ~1e-5·amp, and float32 leaves it rounding noise; the report prints
    the error at the suggestions over the reference variance there.  The
    served float64 path is held to
    the same bound; on a v5e its errors were below 1e-7·amp."""
    return float(np.sqrt(n)) * U32 * max(1.0, amp / noise)

# LogEI slack in nats.  The served path is float64, but a TPU emulates
# float64 and its transcendentals are not correctly rounded: a v5e put
# the variance off by 2.5e-8·amp, where near the incumbent the variance
# itself is down to 6e-6·amp.  That moves log σ by up to ~2e-3 nats, so
# a suggestion that merely ties the incumbent must not fail on it.
LOGEI_TOL = 0.01


@dataclass(frozen=True)
class Size:
    dim: int = 20
    restarts: int = 10
    studies: int = 64
    slots: int = 16          # per device
    pad: int = 32
    n_startup: int = 193     # random trials before the first GP ask
    rounds: int = 3          # GP asks per study
    n_checked: int = 4       # studies whose posterior is compared
    n_random: int = 256      # random query points per compared study


class SmokeFailure(RuntimeError):
    """A check of the smoke failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


_T0 = time.perf_counter()


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - _T0:7.1f} s] {msg}", flush=True)


class CompileMeter:
    """XLA compiles and persistent-cache hits per phase, from JAX's own
    monitoring events.  A compile runs in the thread that calls the
    program, so the calling thread's phase name owns it."""

    def __init__(self):
        self._local = threading.local()
        self._lock = threading.Lock()
        self.spans: dict = {}            # phase -> [(start, end)] seconds
        self.cache_hits: dict = {}
        jax.monitoring.register_event_time_span_listener(self._span)
        jax.monitoring.register_event_listener(self._event)

    def phase(self, name: str) -> None:
        self._local.phase = name

    def _name(self) -> str:
        return getattr(self._local, "phase", "setup")

    def _span(self, event, start_time, end_time, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.spans.setdefault(self._name(), []).append(
                    (start_time, end_time))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                k = self._name()
                self.cache_hits[k] = self.cache_hits.get(k, 0) + 1

    def line(self, name: str) -> str:
        spans = self.spans.get(name, [])
        return (f"{sum(e - s for s, e in spans):.1f} s XLA compile over "
                f"{len(spans)} programs, {self.cache_hits.get(name, 0)} "
                f"persistent-cache hits")

    def overlap_line(self) -> str:
        """Summed compile seconds against the wall-clock seconds in which
        at least one compile ran: their ratio is the overlap the
        concurrent warm-up bought."""
        spans = sorted(sp for v in self.spans.values() for sp in v)
        wall, end = 0.0, -np.inf
        for s, e in spans:
            wall += max(0.0, e - max(s, end))
            end = max(end, e)
        total = sum(e - s for s, e in spans)
        return (f"XLA compile {total:.1f} s summed over {len(spans)} "
                f"programs, {wall:.1f} s of wall clock")


def on_cpu(a):
    return jax.device_put(np.asarray(a), jax.devices("cpu")[0])


def reference_gp(x_obs, y_obs, params):
    """The float64 reference on the host CPU: the exact GP of the live
    observations (unit cube, raw minimized y) at θ = ``params``.
    Returns (GPState, incumbent's standardized value)."""
    y_std, _, _ = standardize(on_cpu(-np.asarray(y_obs)))
    gp = fit_gram(on_cpu(x_obs), y_std, jax.tree.map(on_cpu, params))
    return gp, jnp.max(y_std)


def check_logei(tag, x_obs, y_obs, params, x_sugg) -> float:
    """The suggestion's reference LogEI is no lower than the
    incumbent's (within LOGEI_TOL); returns the margin in nats."""
    inc = np.asarray(x_obs)[int(np.argmin(np.asarray(y_obs)))]
    gp, best = reference_gp(x_obs, y_obs, params)
    mean, var = predict(gp, on_cpu(np.stack([np.asarray(x_sugg), inc])))
    lei = np.asarray(log_ei(mean, var, best))
    check(bool(np.all(np.isfinite(lei))),
          f"{tag}: non-finite reference LogEI")
    margin = float(lei[0] - lei[1])
    check(margin >= -LOGEI_TOL,
          f"{tag}: reference LogEI at the suggestion {lei[0]:.6g} is below "
          f"the incumbent's {lei[1]:.6g} by more than {LOGEI_TOL}")
    return margin


def check_box(tag, space: BoxSpace, x) -> None:
    x = np.asarray(x)
    check(x.shape == (space.dim,) and bool(np.all(np.isfinite(x))),
          f"{tag}: suggestion not finite: {x}")
    check(bool(np.all((x >= space.lower) & (x <= space.upper))),
          f"{tag}: suggestion outside its box: {x}")


class ServicePhase:
    """Phase A: the multi-tenant service over one fleet."""

    def __init__(self, name, size: Size, seed: int, mesh=None):
        self.name, self.size, self.seed = name, size, seed
        # off the chip (rehearsals) the kernel runs in interpret mode
        self.kernel_backend = ("pallas" if jax.default_backend() == "tpu"
                               else "pallas_interpret")
        self.space = BoxSpace.cube(size.dim, -5.0, 5.0)
        self.objectives = [make_objective(OBJECTIVES[i % len(OBJECTIVES)],
                                          size.dim, seed=seed + i)
                           for i in range(size.studies)]
        self.fs = FleetSampler(self.space, n_studies=size.studies,
                               seed=seed, slots=size.slots,
                               n_startup_trials=size.n_startup,
                               n_restarts=size.restarts,
                               pad_multiple=size.pad,
                               posterior_backend="auto", mesh=mesh)
        per = size.studies // len(TENANT_WEIGHTS)
        self.tenants = [TenantConfig(f"t{k}", weight=w,
                                     studies=tuple(range(k * per,
                                                         (k + 1) * per)))
                        for k, w in enumerate(TENANT_WEIGHTS)]
        self.owner = {s: t.name for t in self.tenants for s in t.studies}
        # one round queues one ask per study: the overload ladder sits
        # above that, and one DRR round (quantum x lightest weight >=
        # studies per tenant) dispatches the whole round in one step
        q = size.studies
        self.svc = BOService(self.fs, self.tenants, quantum=float(per),
                             overload=OverloadConfig(reject_depth=2 * q,
                                                     degrade_depth=4 * q,
                                                     shed_depth=8 * q))
        self.suggestions = []       # per GP round: (studies, D) array
        self.round_s = []           # per GP round: service_step wall
        self.compiles_after_warmup = None

    @property
    def backend(self) -> str:
        return self.fs.fleet.cfg.backend

    def _round(self, gp_round: bool):
        reqs = [self.svc.submit_ask(self.owner[s], s)
                for s in range(self.size.studies)]
        t0 = time.perf_counter()
        served = self.svc.service_step()
        wall = time.perf_counter() - t0
        check(served == len(reqs), f"{self.name}: served {served} of "
              f"{len(reqs)} asks in one round")
        xs = []
        for s, req in enumerate(reqs):
            check(req.state == "done", f"{self.name}: study {s} request "
                  f"ended {req.state}: {req.error}")
            check_box(f"{self.name} study {s}", self.space, req.result.x)
            xs.append(req.result.x)
        if gp_round:
            self.suggestions.append(np.stack(xs))
            self.round_s.append(wall)
            log(f"{self.name}: GP round {len(self.round_s)} served in "
                f"{wall:.2f} s")
        return reqs

    def _tell(self, reqs) -> None:
        for s, req in enumerate(reqs):
            t = req.result
            self.svc.submit_tell(self.owner[s], s, t.trial_id,
                                 self.objectives[s](t.x))

    def warm(self) -> None:
        for _ in range(self.size.n_startup):
            self._tell(self._round(gp_round=False))
        log(f"{self.name}: {self.size.n_startup} startup rounds served")
        for _ in range(self.size.rounds - 1):
            self._tell(self._round(gp_round=True))
        self.compiles_after_warmup = self._compiles()

    def steady(self) -> None:
        reqs = self._round(gp_round=True)
        self.check_posteriors()
        self._tell(reqs)

    def _compiles(self) -> int:
        return self.svc.stats_snapshot()["n_fleet_compiles"]

    def check_posteriors(self) -> None:
        """The served and the Pallas posterior on the chip vs the float64
        host reference, and the reference LogEI at every last-round
        suggestion."""
        size, fleet = self.size, self.fs.fleet
        rng = np.random.default_rng(self.seed)
        picked = np.linspace(0, size.studies - 1, size.n_checked).astype(int)
        posts = {path: jax.jit(lambda gp, xq, b=b: posterior(gp, xq,
                                                             backend=b))
                 for path, b in (("served", self.backend),
                                 ("pallas", self.kernel_backend))}
        self.max_err = {path: {"mean": 0.0, "var": 0.0, "of_tol": 0.0,
                               "var_at_sugg": 0.0}
                        for path in posts}
        self.logei_margin = np.inf
        for s in range(size.studies):
            # one device: a slice of a mesh-sharded block is not
            gp = jax.device_put(fleet.gp_state(s), jax.devices()[0])
            sampler = self.fs.samplers[s]
            done = [t for t in sampler.trials if t.state == "complete"]
            check(len(done) == self.size.n_startup + self.size.rounds - 1,
                  f"{self.name}: study {s} has {len(done)} observations")
            x_obs = self.space.to_unit(np.stack([t.x for t in done]))
            y_obs = np.array([t.y for t in done])
            x_sugg = self.space.to_unit(self.suggestions[-1][s])
            self.logei_margin = min(self.logei_margin, check_logei(
                f"{self.name} study {s}", x_obs, y_obs, gp.params, x_sugg))
            if s not in picked:
                continue
            xq = np.concatenate([x_sugg[None],
                                 rng.uniform(0, 1, (size.n_random,
                                                    size.dim))])
            m_ref, v_ref = predict(reference_gp(x_obs, y_obs,
                                                gp.params)[0], on_cpu(xq))
            amp = float(np.asarray(gp.params.amplitude))
            tol = posterior_tol(len(y_obs), amp,
                                float(np.asarray(gp.params.noise)))
            for path, post in posts.items():
                g = with_kinv(gp) if path == "pallas" else gp
                m_chip, v_chip = post(g, jnp.asarray(xq))
                errs = self.max_err[path]
                for key, a, b in (("mean", m_chip, m_ref),
                                  ("var", v_chip, v_ref)):
                    err = float(np.max(np.abs(np.asarray(a)
                                              - np.asarray(b)))) / amp
                    errs[key] = max(errs[key], err)
                    errs["of_tol"] = max(errs["of_tol"], err / tol)
                    check(err <= tol,
                          f"{self.name} study {s}: {path} posterior {key} "
                          f"differs from the float64 reference by "
                          f"{err:.3g} x amplitude (tolerance {tol:.3g})")
                # reported, not checked: the error where LogEI reads the
                # variance, against the variance itself
                v0, r0 = float(v_chip[0]), float(v_ref[0])
                errs["var_at_sugg"] = max(errs["var_at_sugg"],
                                          abs(v0 - r0) / r0)

    def check_service(self) -> dict:
        snap = self.svc.stats_snapshot()
        n = self.size.studies
        for key in ("svc_shed", "svc_rejected", "svc_retries",
                    "svc_deadline_miss", "n_shed", "n_rejected",
                    "n_quarantined", "n_parked", "n_degraded",
                    "n_fallbacks"):
            check(snap[key] == 0, f"{self.name}: {key} = {snap[key]}")
        check(snap["svc_rung"] == "admit",
              f"{self.name}: overload rung {snap['svc_rung']}")
        check(snap["svc_completed"] == n * (self.size.n_startup
                                            + self.size.rounds),
              f"{self.name}: {snap['svc_completed']} asks completed")
        check(snap["n_full_refits"] == n
              and snap["n_incremental"] == n * (self.size.rounds - 1),
              f"{self.name}: {snap['n_full_refits']} full refits and "
              f"{snap['n_incremental']} incremental ones; rounds after "
              f"the first must all be incremental")
        check(snap["n_fleet_compiles"] <= 3,
              f"{self.name}: {snap['n_fleet_compiles']} fleet programs "
              f"for one (bucket, slots) shape (budget 3): "
              f"{snap['retraces']}")
        check(snap["n_fleet_compiles"] == self.compiles_after_warmup,
              f"{self.name}: traced after warm-up: {snap['retraces']}")
        check(snap["n_compiles"] == 0,
              f"{self.name}: eval-engine programs traced on the fleet "
              f"path: {snap['retraces']}")
        return snap

    def report(self, meter: CompileMeter) -> dict:
        snap = self.check_service()
        gp = self.fs.fleet.gp_state(0)
        steady_ms = 1e3 * self.round_s[-1]
        print(f"{self.name}: backend {self.backend}, dtype "
              f"{gp.x_train.dtype}, devices {snap['n_devices']}, "
              f"live studies per device {snap['slots_per_device']}")
        print(f"{self.name}: {meter.line(self.name)}; fleet programs "
              f"{snap['n_fleet_compiles']} (full {snap['n_full_compiles']},"
              f" incr {snap['n_incr_compiles']}, mso "
              f"{snap['n_mso_compiles']})")
        print(f"{self.name}: GP rounds wall s "
              f"{[round(w, 3) for w in self.round_s]}; steady round "
              f"{steady_ms:.1f} ms = {steady_ms / self.size.studies:.2f} "
              f"ms/ask (smoke reading, not a benchmark)")
        print(f"{self.name}: {snap['svc_completed']} asks served, "
              f"{self.size.rounds * self.size.studies} from the GP; sheds "
              f"and failed requests {snap['svc_shed']}, quarantines "
              f"{snap['n_quarantined']}, parks {snap['n_parked']}, "
              f"degrades {snap['n_degraded']}, full refits "
              f"{snap['n_full_refits']}, incremental "
              f"{snap['n_incremental']}")
        for path, e in self.max_err.items():
            b = self.backend if path == "served" else self.kernel_backend
            print(f"{self.name}: {path} posterior ({b}) vs the float64 "
                  f"host reference, largest error / amplitude: mean "
                  f"{e['mean']:.3g}, var {e['var']:.3g}, at most "
                  f"{e['of_tol']:.3g} of the tolerance; var error at the "
                  f"suggestion / reference var there {e['var_at_sugg']:.3g}")
        print(f"{self.name}: smallest LogEI margin over the incumbent "
              f"{self.logei_margin:.4g} nats (tolerance {LOGEI_TOL})")
        return snap


class SamplerPhase:
    """Phases B and C: one GPSampler study, ``size.rounds`` asks."""

    def __init__(self, name, size: Size, seed: int, strategy: str):
        self.name, self.size, self.strategy = name, size, strategy
        self.space = BoxSpace.cube(size.dim, -5.0, 5.0)
        self.objective = make_objective("rastrigin", size.dim, seed=seed)
        self.sampler = GPSampler(self.space, strategy=strategy, seed=seed,
                                 n_startup_trials=size.n_startup,
                                 n_restarts=size.restarts,
                                 pad_multiple=size.pad,
                                 posterior_backend="auto")
        self.ask_s = []
        self.margins = []
        self.compiles_after_warmup = None

    @property
    def backend(self) -> str:
        return self.sampler.posterior_backend

    def _compiles(self) -> int:
        s = self.sampler
        n = s.engine.n_compiles
        if s._ask is not None:
            n += s._ask.stats_snapshot()["n_ask_compiles"]
        return n

    def _ask(self) -> None:
        s = self.sampler
        done = [t for t in s.trials if t.state == "complete"]
        x_obs = self.space.to_unit(np.stack([t.x for t in done]))
        y_obs = np.array([t.y for t in done])
        fit_seed = s.seed + len(s.trials)
        t0 = time.perf_counter()
        t = s.ask()
        self.ask_s.append(time.perf_counter() - t0)
        log(f"{self.name}: ask {len(self.ask_s)} took {self.ask_s[-1]:.2f} s")
        check_box(f"{self.name} ask {len(self.ask_s)}", self.space, t.x)
        if s._ask is not None:                 # fused: the program's θ
            params = s._ask.gp_state().params
        else:                # host path: the same fit, same seed, again
            gp = fit_gp(jnp.asarray(x_obs), standardize(jnp.asarray(-y_obs))[0],
                        n_restarts=s.gp_fit_restarts, seed=fit_seed,
                        pad_bucket=s.pad_multiple)
            params = gp.params
        self.dtype = params.log_lengthscale.dtype
        self.margins.append(check_logei(
            f"{self.name} ask {len(self.ask_s)}", x_obs, y_obs, params,
            self.space.to_unit(t.x)))
        s.tell(t.trial_id, self.objective(t.x))

    def warm(self) -> None:
        s = self.sampler
        for _ in range(self.size.n_startup):
            t = s.ask()
            s.tell(t.trial_id, self.objective(t.x))
        for _ in range(self.size.rounds - 1):
            self._ask()
        self.compiles_after_warmup = self._compiles()

    def steady(self) -> None:
        self._ask()

    def report(self, meter: CompileMeter) -> None:
        s = self.sampler
        n = self._compiles()
        eng = s.engine.stats_snapshot()
        if s._ask is not None:
            ak = s._ask.stats_snapshot()
            check(ak["n_ask_compiles"] <= 2 and ak["n_fallbacks"] == 0
                  and ak["n_incremental"] == self.size.rounds - 1,
                  f"{self.name}: ask programs {ak['n_ask_compiles']} "
                  f"(budget 2), incremental {ak['n_incremental']}, "
                  f"fallbacks {ak['n_fallbacks']}: {ak['retraces']}")
            check(n == self.compiles_after_warmup,
                  f"{self.name}: traced after warm-up: {ak['retraces']}")
            detail = (f"ask programs {ak['n_ask_compiles']} (full "
                      f"{ak['n_full_compiles']}, incr "
                      f"{ak['n_incr_compiles']}), incremental "
                      f"{ak['n_incremental']}")
        else:
            # the coroutine evaluates shrinking active sets, padded to
            # the plan's bucket ladder: one program per bucket at most,
            # and a later ask may still meet a bucket for the first time
            n_buckets = len(bucket_ladder(self.size.restarts))
            causes = set(eng["retraces"]["causes"])
            check(eng["n_eval_compiles"] <= n_buckets
                  and causes <= {"first-trace", "shape"},
                  f"{self.name}: {eng['n_eval_compiles']} eval programs "
                  f"for {n_buckets} buckets: {eng['retraces']}")
            detail = (f"eval programs {eng['n_eval_compiles']} (budget: "
                      f"one per evaluation bucket, {n_buckets})")
        print(f"{self.name}: strategy {self.strategy}, backend "
              f"{self.backend}, dtype {self.dtype}")
        print(f"{self.name}: {meter.line(self.name)}; {detail}")
        print(f"{self.name}: ask wall s "
              f"{[round(w, 3) for w in self.ask_s]}; steady ask "
              f"{1e3 * self.ask_s[-1]:.1f} ms (smoke reading, not a "
              f"benchmark); smallest LogEI margin over the incumbent "
              f"{min(self.margins):.4g} nats")


def run_phases(phases, meter: CompileMeter) -> None:
    """Warm every phase up concurrently (their compiles overlap on the
    host's cores), then take each phase's last ask alone."""
    def warm(ph):
        meter.phase(ph.name)
        ph.warm()
        log(f"{ph.name}: warm-up done; {meter.line(ph.name)}")

    with ThreadPoolExecutor(len(phases)) as pool:
        for fut in [pool.submit(warm, ph) for ph in phases]:
            fut.result()
    for ph in phases:
        meter.phase(ph.name)
        ph.steady()
    meter.phase("report")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache = use_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{dev.platform!r} ({dev.device_kind}, {len(devices)} "
              f"devices)", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 2
    size = Size()
    b0 = pad_bucket_for(size.n_startup, size.pad)
    b1 = pad_bucket_for(size.n_startup + size.rounds, size.pad)
    check(b0 == b1, "the GP rounds must stay in one pad bucket")
    print(f"chip_smoke: {dev.platform} {dev.device_kind} x{len(devices)}, "
          f"jax {jax.__version__}, x64 {jax.config.jax_enable_x64}, "
          f"compile cache {cache}")
    print(f"chip_smoke: n_startup_trials={size.n_startup}, so each study's "
          f"first GP ask sees n={size.n_startup} in pad bucket {b0}, and "
          f"its {size.rounds} GP rounds (n up to "
          f"{size.n_startup + size.rounds}) stay in that bucket: one "
          f"compiled (bucket, slots) shape")
    meter = CompileMeter()
    t0 = time.perf_counter()
    meter.phase("setup")
    if args.chips == 4:
        a4 = ServicePhase("A mesh4", size, args.seed,
                          mesh=make_fleet_mesh(4))
        a1 = ServicePhase("A mesh1", size, args.seed,
                          mesh=make_fleet_mesh(1))
        phases = [a4, a1]
    else:
        phases = [ServicePhase("A service", size, args.seed),
                  SamplerPhase("B dbe", size, args.seed, "dbe"),
                  SamplerPhase("C dbe_vec", size, args.seed, "dbe_vec")]
    for ph in phases:
        check(ph.backend == "xla",
              f"{ph.name}: float64 state must be served by the float64 "
              f"posterior, got {ph.backend!r}")
    run_phases(phases, meter)
    snaps = [ph.report(meter) for ph in phases]
    if args.chips == 4:
        check(snaps[0]["slots_per_device"] == [size.slots] * 4,
              f"mesh4 live studies per device "
              f"{snaps[0]['slots_per_device']}, want {[size.slots] * 4}")
        diffs = [float(np.max(np.abs(x4 - x1)))
                 for x4, x1 in zip(a4.suggestions, a1.suggestions)]
        same = all(np.array_equal(x4, x1)
                   for x4, x1 in zip(a4.suggestions, a1.suggestions))
        print(f"mesh4 vs mesh1: suggestions bitwise equal {same}; largest "
              f"difference per round {diffs}")
        check(same, "mesh4 suggestions differ from mesh1's: every chip "
              "runs the same slot-local program, so they must be equal")
    print(f"chip_smoke: {meter.overlap_line()}; total "
          f"{time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
