"""CPU tests of the benchmark's harness: statistics, span arithmetic, trace
reduction, the reference, the journal reader, the traffic generator, and
how the harness finds its files.  No TPU topology is described here."""
from __future__ import annotations

import json
import math
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import harness, reference, stats, tracing, traffic

ROOT = Path(__file__).resolve().parents[2]
FIXTURES = Path(__file__).resolve().parent / "fixtures"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
WORKLOADS = [c["name"] for c in SPEC["workloads"]]
METRICS = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]


# ---------------------------------------------------------------- stats
def _run(latencies, t_start=0.0, t_end=None):
    run = harness.Run(setup_s=1.0, t_start=t_start)
    t = t_start
    for lat in latencies:
        t += lat
        run.asks.append(harness.Ask(0, t - lat, None, done=t))
    run.t_end = t if t_end is None else t_end
    return run


def test_percentile_interpolates_between_ranks():
    assert stats.percentile([3.0, 1.0, 2.0, 4.0], 50) == 2.5
    assert stats.percentile([5.0], 95) == 5.0
    xs = list(range(101))
    assert stats.percentile(xs, 95) == 95.0
    assert stats.percentile([0.0, 10.0], 95) == pytest.approx(9.5)


def test_stall_moves_tail_and_rate():
    steady = _run([1.0] * 100)
    stalled = _run([1.0] * 94 + [30.0] * 6)
    p95 = harness.load_reader("ask_p95_s")
    rate = harness.load_reader("asks_per_s")
    assert p95(steady) == pytest.approx(1.0)
    assert p95(stalled) > 20.0
    assert rate(steady) == pytest.approx(1.0)
    assert rate(stalled) == pytest.approx(100 / 274.0)
    assert harness.load_reader("ask_p50_s")(stalled) == pytest.approx(1.0)


def test_rate_counts_the_whole_window():
    # a stall after the last completion is not in the window; one before
    # the first completion is
    run = _run([1.0] * 10, t_start=0.0)
    assert stats.window_rate(run.n_completed, run.t_start, run.t_end) == 1.0
    late_first = _run([11.0] + [1.0] * 9)
    assert stats.window_rate(late_first.n_completed, 0.0,
                             late_first.t_end) == pytest.approx(0.5)
    with pytest.raises(ValueError):
        stats.window_rate(1, 2.0, 2.0)


# ------------------------------------------------------- span arithmetic
def _span(name, ts, dur):
    return {"name": name, "ph": "X", "ts": ts, "dur": dur}


def test_self_time_subtracts_nested_children_once():
    parents = [(0.0, 100.0), (200.0, 260.0)]
    children = [(10.0, 30.0), (20.0, 40.0), (90.0, 120.0), (210.0, 220.0)]
    # covered: [10, 40] + [90, 100] in the first, [210, 220] in the second
    assert tracing.self_time(parents, children) == pytest.approx(
        100 - 40 + 60 - 10)


def test_per_step_span_metrics():
    spans = []
    for k in range(4):               # 4 steps of 1000 us each
        t = 2000.0 * k
        spans += [dict(_span("bench.service_step", t, 1000.0),
                       args={"served": 8}),
                  _span("fleet.step", t + 100.0, 800.0),
                  _span("fleet.program.incr", t + 150.0, 200.0),
                  _span("fleet.program.mso", t + 400.0, 400.0),
                  _span("journal.append", t + 920.0, 50.0)]
    spans.append({"name": "svc.shed", "ph": "i", "ts": 5.0})
    run = harness.Run(setup_s=0.0, spans=spans, n_steps=4)
    read = {m: harness.load_reader(m) for m in (
        "svc_ms_per_step", "fleet_host_ms_per_step", "refit_ms_per_step",
        "mso_ms_per_step")}
    assert read["svc_ms_per_step"](run) == pytest.approx(0.2)
    assert read["fleet_host_ms_per_step"](run) == pytest.approx(0.2)
    assert read["refit_ms_per_step"](run) == pytest.approx(0.2)
    assert read["mso_ms_per_step"](run) == pytest.approx(0.4)
    # the profiler's stop returned during step 1: steps 2-3 are read,
    # whatever the profiler did to steps 0-1
    slowed = [dict(sp, dur=5 * sp["dur"])
              if sp["ph"] == "X" and sp["ts"] < 4000.0 else sp
              for sp in spans]
    late = harness.Run(setup_s=0.0, spans=slowed, n_steps=4,
                       span_from_us=3500.0)
    assert late.clean_steps()[1] == 2
    assert read["svc_ms_per_step"](late) == pytest.approx(0.2)
    assert read["mso_ms_per_step"](late) == pytest.approx(0.4)
    assert harness.Run(setup_s=0.0, spans=spans,
                       span_from_us=9000.0).clean_steps()[1] == 0
    run.counters_start = {"n_rounds": 100, "n_steps": 3}
    run.counters_end = {"n_rounds": 400, "n_steps": 7}
    assert harness.load_reader("mso_rounds_per_step")(run) == 75.0


def test_span_readers_return_nothing_without_spans():
    run = harness.Run(setup_s=0.0, n_steps=3)
    for m in ("svc_ms_per_step", "fleet_host_ms_per_step",
              "refit_ms_per_step", "mso_ms_per_step",
              "step_start_idle_share"):
        assert harness.load_reader(m)(run) is None


# ------------------------------------------------------- trace reduction
def test_union_and_clip():
    assert tracing.union([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]
    assert tracing.clip([(0, 4), (5, 9)], 2, 6) == [(2, 4), (5, 6)]


def _trace_fixture():
    with open(FIXTURES / "v5e_trace.json") as f:
        return json.load(f)


def test_recorded_trace_busy_and_idle():
    fx = _trace_fixture()
    trace, lo, hi = fx["trace"], fx["lo"], fx["hi"]
    busy = tracing.device_busy(trace, lo, hi)
    window = 1e-9 * (hi - lo)
    assert 0.0 < busy <= window
    ops = tracing.top_device_ops(trace, lo, hi)
    assert 0 < len(ops) <= 10
    assert all(ops[i][1] >= ops[i + 1][1] for i in range(len(ops) - 1))
    # summed over calls an op can exceed the union, never the window
    assert ops[0][1] <= window
    gaps = tracing.idle_gaps(trace, fx["spans"], fx["offset"], lo, hi)
    assert sum(g[1] for g in gaps) == pytest.approx(window - busy,
                                                    rel=1e-9, abs=1e-12)
    run = harness.Run(setup_s=0.0, trace=trace, trace_lo_ns=lo,
                      trace_hi_ns=hi)
    share = harness.load_reader("step_start_idle_share")(run)
    assert share == pytest.approx(100.0 * (1 - busy / window))
    assert fx["expected"]["busy_s"] == pytest.approx(busy, rel=1e-12)
    assert fx["expected"]["idle_gaps"][0][0] == gaps[0][0]


def test_idle_gaps_named_by_innermost_host_span():
    ev = [["op", 0.0, 100.0, "m"], ["op", 300.0, 100.0, "m"],
          ["op", 600.0, 400.0, "m"]]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ev}]}]}
    spans = [_span("fleet.step", 0.0, 0.5), _span("journal.append", 0.45,
                                                  0.05)]
    # us -> ns: fleet.step [0, 500), journal.append [450, 500)
    gaps = dict(tracing.idle_gaps(trace, spans, 0.0, 0.0, 1000.0))
    assert gaps["fleet.step"] == pytest.approx(1e-9 * (200 + 50))
    assert gaps["journal.append"] == pytest.approx(1e-9 * 50)
    assert gaps["host: no span"] == pytest.approx(1e-9 * 100)
    assert tracing.device_busy(trace, 0.0, 1000.0) == pytest.approx(6e-7)


def test_trace_without_device_ops_reads_nothing():
    trace = {"planes": [{"name": "/host:CPU", "lines": []}]}
    assert tracing.device_busy(trace, 0.0, 1.0) is None
    assert tracing.idle_gaps(trace, [], 0.0, 0.0, 1.0) == []


# ------------------------------------------------------------ reference
def test_log_h_matches_direct_formula_and_is_continuous():
    from scipy.stats import norm
    z = np.linspace(-8.0, 6.0, 141)
    direct = np.log(norm.pdf(z) + z * norm.cdf(z))
    np.testing.assert_allclose(reference.log_h(z), direct, rtol=1e-9)
    zb = np.array([reference.Z_ASYMPTOTIC - 1e-9, reference.Z_ASYMPTOTIC])
    a, b = reference.log_h(zb)
    assert abs(a - b) < 1e-7


def test_reference_logei_matches_program_at_small_size():
    import jax.numpy as jnp
    from repro.core.acquisition import log_ei
    from repro.gp.fit import standardize, unpack_theta
    from repro.gp.gpr import fit_gram, predict
    rng = np.random.default_rng(7)
    x = rng.uniform(size=(30, 3))
    y = np.sum((x - 0.3) ** 2, axis=1) + 0.01 * rng.standard_normal(30)
    theta = np.array([-1.0, -0.5, 0.2, 0.3, -6.0])
    xq = rng.uniform(size=(5, 3))
    ys, _, _ = standardize(jnp.asarray(-y))
    gp = fit_gram(jnp.asarray(x), ys, unpack_theta(jnp.asarray(theta), 3))
    m, v = predict(gp, jnp.asarray(xq))
    want = np.asarray(log_ei(m, v, jnp.max(ys)))
    got = reference.logei_at(x, y, theta, xq)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-9)
    lo32 = reference.logei_at(x, y, theta, xq, dtype=np.float32)
    assert lo32.dtype == np.float32


def _small_gp(seed=3, n=30, d=3):
    rng = np.random.default_rng(seed)
    x = rng.uniform(size=(n, d))
    y = np.sum((x - 0.3) ** 2, axis=1) + 0.01 * rng.standard_normal(n)
    return rng, x, y


def test_reference_map_objective_matches_program_and_its_gradient():
    import jax.numpy as jnp
    from repro.gp.fit import _neg_map_objective
    rng, x, y = _small_gp()
    theta = np.array([-1.0, -0.5, 0.2, 0.3, -4.0])
    ys = reference.standardized(y)
    f, g = reference.neg_log_posterior(theta, x, ys)
    want = _neg_map_objective(jnp.asarray(theta), jnp.asarray(x),
                              jnp.asarray(ys), jnp.ones(30, bool), 3,
                              "matern52")
    assert f == pytest.approx(float(want), rel=1e-10)
    eye = 1e-6 * np.eye(5)
    fd = [(reference.neg_log_posterior(theta + e, x, ys)[0]
           - reference.neg_log_posterior(theta - e, x, ys)[0]) / 2e-6
          for e in eye]
    np.testing.assert_allclose(g, fd, rtol=1e-5, atol=1e-6)
    # from its own optimum the polish gains nothing; from θ0 a lot
    gain = reference.map_polish(x, y, theta)
    assert gain > 0.1
    from scipy.optimize import minimize
    res = minimize(reference.neg_log_posterior, theta, args=(x, ys),
                   jac=True, method="L-BFGS-B",
                   bounds=[(-4, 4)] * 3 + [(-6, 6), (-10, 2)],
                   options={"maxiter": 1000, "ftol": 1e-15, "gtol": 1e-9})
    assert reference.map_polish(x, y, res.x) < 1e-8


def test_reference_projected_gradient_and_regret_of_a_suggestion():
    rng, x, y = _small_gp()
    theta = np.array([-1.0, -0.5, 0.2, 0.3, -6.0])
    start = rng.uniform(size=3)
    assert reference.projected_grad_inf(x, y, theta, start) > 1e-2
    logei = reference.logei_fn(x, y, theta)
    top = reference._maximize(logei, start)
    # the point SciPy's L-BFGS-B reached from there: nothing left
    from scipy.optimize import minimize

    def neg(z):
        f, g = reference._logei_value_grad(logei, z)
        return -f, -g
    xs = minimize(neg, start, jac=True, method="L-BFGS-B",
                  bounds=[(0, 1)] * 3,
                  options={"maxiter": 500, "ftol": 1e-15, "gtol": 1e-10}).x
    assert logei(xs)[0] == pytest.approx(top, abs=1e-9)
    assert reference.projected_grad_inf(x, y, theta, xs) < 1e-4
    starts = rng.uniform(size=(10, 3))
    regret = reference.logei_regret(x, y, theta, start, starts)
    assert regret >= top - logei(start)[0] - 1e-9 > 0.0
    assert reference.logei_regret(x, y, theta, xs, starts) <= regret


def test_journal_reader_reads_back_and_stops_at_a_torn_tail(tmp_path):
    from repro.bo.journal import StudyJournal
    j = StudyJournal(str(tmp_path))
    for k in range(3):
        j.append({"op": "tell", "study": k, "trial": 1, "y": 0.5 * k})
    j.close()
    path = tmp_path / "journal.log"
    recs = reference.read_journal(str(path))
    assert [r["study"] for r in recs] == [0, 1, 2]
    data = path.read_bytes()
    path.write_bytes(data[:-7])
    assert len(reference.read_journal(str(path))) == 2


# -------------------------------------------------------------- traffic
def test_poisson_arrivals_rate_and_order():
    rng = np.random.default_rng(3)
    ev = traffic.poisson_arrivals([40.0, 10.0], [[0, 1], [2]], 50.0, rng)
    assert all(a[0] <= b[0] for a, b in zip(ev, ev[1:]))
    n0 = sum(e[1] == 0 for e in ev)
    assert abs(n0 - 2000) < 5 * math.sqrt(2000)
    assert {e[2] for e in ev if e[1] == 0} == {0, 1}
    assert max(e[0] for e in ev) < 50.0


def test_tenant_rates_zipf_and_weights():
    mix = {"loop": "open", "rate_per_s": 10.0, "tenant_shares": "zipf",
           "zipf_s": 1.0}
    r = traffic.tenant_rates(mix, [1, 1, 2, 4])
    assert sum(r) == pytest.approx(10.0)
    assert r[0] == pytest.approx(2 * r[1]) == pytest.approx(4 * r[3])
    w = traffic.tenant_rates({"loop": "open", "rate_per_s": 8.0}, [1, 3])
    assert w == [2.0, 6.0]
    with pytest.raises(ValueError):
        traffic.validate({"loop": "bursty"})


# ----------------------------------------------------- files by name
@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_found_by_name(workload):
    cell = harness.load_cell(workload, SPEC)
    entry = cell["cell"]
    cfg_entry = next(c for c in SPEC["configs"]
                     if c["name"] == entry["config"])
    assert cell["config"] == json.loads((ROOT / cfg_entry["file"])
                                        .read_text())
    for key in cfg_entry["reduced"]:
        assert key in cell["config"]
    assert {"unserved_asks", "journal_missing", "logei_gap_nats",
            "pgrad_inf", "incumbent_excess_nats",
            "map_gain_nats"} <= set(cell["config"]["limits"])
    assert int(cell["config"]["window_asks"]) > 0
    assert cell["mix"] == json.loads(
        (ROOT / "bench" / "traffic" / f"{entry['traffic']}.json")
        .read_text())
    names = [m["name"] for m in cell["end_to_end"]]
    assert "setup_s" in names and len(names) >= 2
    assert cell["per_layer"]


def test_unknown_workload_is_refused():
    with pytest.raises(harness.BenchError):
        harness.load_cell("no_such.cell", SPEC)


@pytest.mark.parametrize("metric", METRICS)
def test_metric_reader_found_by_name(metric):
    assert callable(harness.load_reader(metric))


def test_seed_orders_the_same_studies():
    a = harness.study_order(2**31 + 5, 64)
    assert sorted(a) == list(range(64))
    assert a == harness.study_order(2**31 + 5, 64)
    assert a != harness.study_order(2**31 + 6, 64)


def test_seeds_derive_for_any_whole_number():
    big = harness.derive_seeds(2**31 + 12345)
    assert big == harness.derive_seeds(2**31 + 12345)
    assert big != harness.derive_seeds(2**31 + 12346)
    for s in (0, 7, 2**40, -3):
        assert all(0 <= v < 2**30 for v in harness.derive_seeds(s).values())


# --------------------------------------------------------- no chip here
def _bench_cmd(cwd):
    return [sys.executable, "bench/run.py", "--workload", WORKLOADS[0],
            "--seed", "1", "--seconds", "1", "--trace", "0"]


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(_bench_cmd(ROOT), cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr and "'cpu'" in out.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".runs"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(_bench_cmd(tmp_path), cwd=tmp_path, env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
