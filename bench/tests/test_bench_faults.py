"""The check that decides ``correct``, shown to fail.

Each test drives a whole run of a tiny cell on the CPU through the
harness (everything but its look for a chip), with the served path
sound or broken underneath, and reads ``correct``:

- sound: correct, and the float32 control (the reference in float32 in
  the program's place) is not;
- a refit step that returns its GP state unchanged;
- half of the asks of each step left out (never answered);
- a suggestion altered where it is produced;
- a tell acknowledged but never journaled;
- an MSO capped at a few L-BFGS-B iterations;
- an MSO whose restarts all start from the best observation;
- a MAP refit that stops after one iteration.

And the window is a fixed amount of work: a uniformly slower service
step never improves the rate or the tail.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np
import pytest

from bench import harness
from bench.compile_meter import CompileMeter

FIXTURES = Path(__file__).resolve().parent / "fixtures"
MIX = {"loop": "closed"}


def _config(name):
    return json.loads((FIXTURES / f"{name}.json").read_text())


def _run(tmp_path, config, seed=11):
    with CompileMeter() as meter:
        run = harness.run_cell(config, MIX, seed=seed, trace=False,
                               meter=meter,
                               t_process=time.perf_counter(),
                               run_dir=tmp_path / "run", late_wait_s=1.0)
    return run


def _line(run, config, dtype=np.float64):
    checks = harness.judge(
        harness.check_numbers(run, dtype=dtype, limits=config["limits"]),
        config["limits"])
    metrics = [{"name": "ask_p50_s", "unit": "s"},
               {"name": "setup_s", "unit": "s"}]
    return harness.result_line(run, metrics, False, checks)


def test_sound_run_is_correct_and_float32_control_is_not(tmp_path):
    config = _config("tiny_dense")
    run = _run(tmp_path, config)
    line = _line(run, config)
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert run.window_compiles == 0
    assert list(line)[-1] == "checks"
    control = _line(run, config, dtype=np.float32)
    assert not control["correct"], control["checks"]
    gap = control["checks"]["logei_gap_nats"]["value"]
    # None: the float32 Cholesky failed, which fails the control too
    assert gap is None or gap > config["limits"]["logei_gap_nats"]


def _stale_incr(self, x, y, n_valid, theta, chol_old, alpha_old, kinv_old,
                do_incr):
    import jax.numpy as jnp
    return chol_old, alpha_old, kinv_old, jnp.ones_like(do_incr)


def _half_dispatch(orig):
    def dispatch(self, batch):
        return orig(self, batch[: len(batch) // 2])
    return dispatch


def _altered_mso(orig):
    def mso(self, *args):
        import jax.numpy as jnp
        best_x, stats = orig(self, *args)
        return jnp.clip(best_x + 0.05, 0.0, 1.0), stats
    return mso


def _tell_not_journaled(orig):
    def append(self, record):
        if record.get("op") == "tell":
            return self.seq
        return orig(self, record)
    return append


def _capped_lbfgsb(orig, maxiter):
    def lbfgsb(fun, x0, lower, upper, opts, *args, **kw):
        return orig(fun, x0, lower, upper, opts._replace(maxiter=maxiter),
                    *args, **kw)
    return lbfgsb


def _one_start(orig):
    def restart_points(key, x, y_std, valid, n_restarts):
        import jax.numpy as jnp
        x0, best_val = orig(key, x, y_std, valid, n_restarts)
        return jnp.broadcast_to(x0[:1], x0.shape), best_val
    return restart_points


def _unfitted(orig):
    def refit_core(*args, fit_opts, **kw):
        return orig(*args, fit_opts=fit_opts._replace(maxiter=1), **kw)
    return refit_core


@pytest.mark.parametrize("fault", ["stale_state", "half_dropped",
                                   "altered_answer", "lost_tell",
                                   "capped_mso", "one_restart",
                                   "unfitted_theta"])
def test_fault_makes_run_incorrect(tmp_path, monkeypatch, fault):
    import repro.engine.fleet as fleet
    from repro.bo.journal import StudyJournal
    from repro.engine.fleet import FleetEngine
    from repro.serve.bo_service import BOService
    if fault == "capped_mso":
        monkeypatch.setattr(fleet, "lbfgsb_minimize",
                            _capped_lbfgsb(fleet.lbfgsb_minimize, 3))
    elif fault == "one_restart":
        monkeypatch.setattr(fleet, "restart_points",
                            _one_start(fleet.restart_points))
    elif fault == "unfitted_theta":
        monkeypatch.setattr(fleet, "refit_core",
                            _unfitted(fleet.refit_core))
    elif fault == "stale_state":
        monkeypatch.setattr(FleetEngine, "_incr_impl", _stale_incr)
    elif fault == "altered_answer":
        monkeypatch.setattr(FleetEngine, "_mso_impl",
                            _altered_mso(FleetEngine._mso_impl))
    elif fault == "lost_tell":
        monkeypatch.setattr(StudyJournal, "append",
                            _tell_not_journaled(StudyJournal.append))
    else:
        # set-up serves whole rounds; the window loses half of each step
        serve_rounds = harness.Deployment.serve_rounds

        def serve_then_break(self, n):
            serve_rounds(self, n)
            monkeypatch.setattr(BOService, "_dispatch",
                                _half_dispatch(BOService._dispatch))
        monkeypatch.setattr(harness.Deployment, "serve_rounds",
                            serve_then_break)
    config = _config("tiny")
    line = _line(_run(tmp_path, config), config)
    assert not line["correct"], line["checks"]


def test_slower_step_never_improves_rate_or_tail(tmp_path, monkeypatch):
    """The same fixed work, once with every service step 0.3 s slower:
    the rate falls and the tail grows; neither reads as a gain."""
    from repro.serve.bo_service import BOService
    config = _config("tiny")
    read = {m: harness.load_reader(m)
            for m in ("asks_per_s", "ask_p95_s", "ask_p50_s")}
    fast = _run(tmp_path / "fast", config)
    step = BOService.service_step

    def slow_step(self, *args, **kw):
        time.sleep(0.3)
        return step(self, *args, **kw)
    monkeypatch.setattr(BOService, "service_step", slow_step)
    slow = _run(tmp_path / "slow", config)
    assert slow.n_completed == fast.n_completed == len(fast.asks) > 0
    assert read["asks_per_s"](slow) < read["asks_per_s"](fast)
    assert read["ask_p95_s"](slow) > read["ask_p95_s"](fast)
    assert read["ask_p50_s"](slow) > read["ask_p50_s"](fast)
