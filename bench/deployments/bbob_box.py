"""Deployment ``bbob_box``: studies of one dimension D in one box, served
by one ``BOService`` over one ``FleetSampler``, with the write-ahead
journal on, and the check that decides ``correct`` over what it served.

The configuration names the box (``dim``, ``lower``, ``upper``), the
BBOB objectives the studies cycle through, the tenants with their
weights and study counts, and the sampler's settings.  The studies, their
objectives and their random starting trials come from its ``data_seed``.
"""
from __future__ import annotations

import math
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

from bench import reference
from bench.bbob import BBOBFunction
from bench.harness import BenchError, derive_seeds, study_order

REGRET_STARTS = 10                   # the reference's uniform restarts


def window_room(config: Dict, told: int) -> int:
    """The most asks one study may take in a window that it starts with
    ``told`` trials told: inside its trial budget, and with every
    observation of the window inside the pad bucket that set-up's
    programs were loaded for (one more would move the study to a larger
    block, whose programs the window would compile)."""
    pad = int(config["pad_multiple"])
    bucket = -(-told // pad) * pad
    return min(int(config["n_budget"]), bucket) - told


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
        self.tenant_studies: List[Tuple[int, ...]] = []
        for t in c["tenants"]:
            own = tuple(self.order[first:first + int(t["studies"])])
            first += len(own)
            tenants.append(TenantConfig(t["name"], weight=float(t["weight"]),
                                        studies=own))
            self.tenant_studies.append(own)
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

    def box(self, study: int) -> Tuple[float, float, int]:
        return self.lower, self.upper, self.dim

    def ask(self, study: int):
        return self.svc.submit_ask(self.owner[study], study)

    def ask_info(self, study: int):
        """The SuggestInfo of the study's last suggest."""
        return self.fs.samplers[study].last_ask_info

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
        # a study's first full refit after set-up's cold one starts from
        # its fitted θ, which the fleet unpacks with eager ops: compile
        # them here, where the window's first such refit would
        import jax.numpy as jnp
        from repro.gp.fit import unpack_theta
        unpack_theta(jnp.zeros((self.dim + 2,), jnp.float64), self.dim)

    def journal_records(self) -> List[Dict]:
        return reference.read_journal(str(self.journal_dir / "journal.log"))


def check_numbers(run, dtype=np.float64,
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

    Each study is judged in its own box, as the deployment gives it.  With
    ``dtype=np.float32`` the reference stands in for the program (the
    control): its float32 LogEI against the float64 one.
    """
    dep = run.deployment
    unserved = 0
    for a in run.asks:
        lo, hi, dim = dep.box(a.study)
        x = None if a.done is None else np.asarray(a.req.result.x)
        if x is None or x.shape != (dim,) or not np.all(np.isfinite(x)) \
                or np.any(x < lo) or np.any(x > hi):
            unserved += 1
    tells, asks_x, theta_at, theta, fit_of = {}, {}, {}, {}, {}
    for r in dep.journal_records():
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
        lo, hi, _ = dep.box(study)
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
        lo, hi, dim = dep.box(a.study)
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
                    starts_rng.random((REGRET_STARTS, dim))))
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
