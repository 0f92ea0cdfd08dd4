"""Evaluation-engine tests: bucketed pad-or-shrink scheduling, compile
accounting, q-batch joint acquisition, and the fused posterior backend."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import coroutine as co
from repro.core.acquisition import logei_acq, qlogei_acq, qlogei_state
from repro.core.mso import MsoOptions, maximize_acqf
from repro.bo.sampler import FleetSampler, GPSampler
from repro.bo.space import BoxSpace
from repro.engine import (EvalEngine, EvalPlan, bucket_ladder, fused_logei_acq,
                          posterior, resolve_backend)
from repro.gp.gpr import GPState, fit_gram, pad_gp, with_kinv
from repro.gp.kernels import init_params
from repro.kernels.matern.ops import matern52_posterior_op
from repro.kernels.matern.ref import matern52_posterior_ref


def sphere_acq(state, X):
    del state
    return -jnp.sum((X - 0.5) ** 2, axis=tuple(range(1, X.ndim)))


@pytest.fixture(scope="module")
def gp50():
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.uniform(0, 1, (50, 4)))
    y = jnp.asarray(np.sin(8 * np.asarray(X)).sum(1))
    # moderate incumbent: keeps LogEI in a numerically ordinary range
    # (an unfitted GP with best=max(y) pushes z < -25, where MC estimators
    # and f32 comparisons both measure nothing but the tail asymptotics)
    best = float(jnp.quantile(y, 0.3))
    return with_kinv(fit_gram(X, y, init_params(4))), best


# ------------------------------------------------------------------- plan
def test_bucket_ladder():
    assert bucket_ladder(10) == (1, 2, 4, 8, 10)
    assert bucket_ladder(8) == (1, 2, 4, 8)
    assert bucket_ladder(1) == (1,)


def test_plan_bucket_for():
    plan = EvalPlan.for_batch(10, 3)
    assert [plan.bucket_for(k) for k in (1, 2, 3, 5, 8, 9, 10)] == \
        [1, 2, 4, 8, 8, 10, 10]
    fixed = EvalPlan.for_batch(10, 3, bucketed=False)
    assert all(fixed.bucket_for(k) == 10 for k in range(1, 11))
    with pytest.raises(ValueError):
        plan.bucket_for(11)


# -------------------------------------------------- pad-or-shrink economy
def test_padded_eval_identical_to_unpadded():
    """Padding up to a bucket and slicing back must be bitwise invisible."""
    eng = EvalEngine(sphere_acq)
    plan = EvalPlan.for_batch(8, 3)
    be = eng.evaluator(None, plan)
    rng = np.random.default_rng(1)
    X8 = rng.uniform(0, 1, (8, 3))
    f8, g8 = be(X8)
    for k in (1, 2, 3, 5, 7):
        fk, gk = be(X8[:k])            # padded to bucket_for(k) internally
        np.testing.assert_array_equal(fk, f8[:k])
        np.testing.assert_array_equal(gk, g8[:k])


def test_bucketing_compile_economy():
    """A mixed-size run (the shrinking schedule) compiles once per bucket,
    not once per active-set size."""
    eng = EvalEngine(sphere_acq)
    plan = EvalPlan.for_batch(10, 3)
    be = eng.evaluator(None, plan)
    rng = np.random.default_rng(2)
    for k in (10, 9, 8, 7, 6, 5, 4, 3, 2, 1, 1, 2, 5, 10):
        be(rng.uniform(0, 1, (k, 3)))
    assert eng._eval_jit.n_compiles <= len(plan.buckets)
    # and the padded-row accounting is consistent
    assert eng.stats.n_points == 10 + 9 + 8 + 7 + 6 + 5 + 4 + 3 + 2 + 1 \
        + 1 + 2 + 5 + 10
    assert eng.stats.n_padded > 0
    assert set(eng.stats.bucket_rounds) <= set(plan.buckets)


def test_values_shares_cache_with_evaluator():
    """values() reuses the evaluator's jitted primitive: same shapes ⇒
    zero extra compiles, and it returns +acq (max scale)."""
    eng = EvalEngine(sphere_acq)
    plan = EvalPlan.for_batch(8, 3)
    be = eng.evaluator(None, plan)
    rng = np.random.default_rng(12)
    X = rng.uniform(0, 1, (8, 3))
    f_neg, _ = be(X)
    n0 = eng._eval_jit.n_compiles
    v_flat = eng.values(None, X.reshape(8, 3), plan=plan)   # flat + plan
    v_direct = eng.values(None, X)                          # already shaped
    assert eng._eval_jit.n_compiles == n0                   # cache hit
    np.testing.assert_allclose(v_flat, -f_neg)
    np.testing.assert_allclose(v_direct, -f_neg)


def test_lockstep_shares_engine_and_compiles_once():
    eng = EvalEngine(sphere_acq)
    x0 = np.random.default_rng(3).uniform(0, 1, (6, 3))
    for _ in range(3):
        res = maximize_acqf(sphere_acq, x0, 0.0, 1.0, strategy="dbe_vec",
                            options=MsoOptions(maxiter=50, pgtol=1e-8),
                            engine=eng)
    assert eng._vec_jit.n_compiles == 1
    np.testing.assert_allclose(res.best_x, 0.5, atol=1e-5)


def test_lockstep_surfaces_eval_economy_in_stats():
    """dbe_vec rounds/evals land in EngineStats (and thus BENCH rows):
    the fastest strategy must not report 0 evaluation work."""
    eng = EvalEngine(sphere_acq)
    x0 = np.random.default_rng(13).uniform(0, 1, (6, 3))
    res = maximize_acqf(sphere_acq, x0, 0.0, 1.0, strategy="dbe_vec",
                        options=MsoOptions(maxiter=50, pgtol=1e-8),
                        engine=eng)
    es = res.engine_stats
    assert es["n_rounds"] == res.n_rounds > 0
    assert es["n_points"] == int(np.sum(res.n_evals)) > 0
    # frozen-row evaluations are the lockstep analogue of padding waste
    assert es["n_padded"] == res.n_rounds * 6 - es["n_points"] >= 0
    assert es["bucket_rounds"].get(6) == res.n_rounds


# ------------------------------------------------ shrinking active set
def test_dbe_batch_sizes_non_increasing():
    """Converged restarts leave and never re-join: the evaluation batch
    shrinks monotonically (paper §4)."""
    eng = EvalEngine(sphere_acq)
    plan = EvalPlan.for_batch(6, 3)
    rng = np.random.default_rng(4)
    x0 = rng.uniform(0, 1, (6, 3))
    x0[0] = 0.5                       # converges instantly
    x0[1] = 0.499999                  # converges almost instantly
    out = co.run_dbe_coroutine(eng.evaluator(None, plan), x0,
                               np.zeros(3), np.ones(3),
                               m=10, maxiter=100, pgtol=1e-10)
    sizes = out.batch_sizes
    assert sizes[0] == 6
    assert sizes[-1] < 6
    assert all(a >= b for a, b in zip(sizes, sizes[1:]))


# ------------------------------------------------------------- q-batch
def test_qlogei_reduces_to_logei_at_q1(gp50):
    """Smoothed MC qLogEI at q=1 tracks analytic LogEI to the smoothing/MC
    tolerance — the joint path is a strict generalization."""
    gp, best = gp50
    rng = np.random.default_rng(5)
    X = jnp.asarray(rng.uniform(0, 1, (16, 4)))
    la = logei_acq((gp, jnp.asarray(best)), X)
    state = qlogei_state(gp, best, 1, n_samples=4096, seed=0)
    qla = qlogei_acq(state, X[:, None, :])
    # MC + softmax smoothing: agreement is statistical, not bitwise, and
    # only where EI is non-negligible (a 4096-draw estimator cannot see
    # EI ~ e^-40; those points just need to stay strongly negative)
    head = np.asarray(la) > -5.0
    assert head.sum() >= 5
    err = np.abs(np.asarray(qla - la))
    assert float(err[head].max()) < 0.35, (qla, la)
    assert np.all(np.asarray(qla)[~head] < -2.0)


def test_maximize_acqf_joint_q2(gp50):
    """maximize_acqf q=2: joint candidates optimize, improve over their
    inits, and a joint pair beats duplicating the single best point."""
    gp, best = gp50
    q = 2
    state = qlogei_state(gp, best, q, n_samples=128, seed=0)
    rng = np.random.default_rng(6)
    x0 = rng.uniform(0, 1, (5, q, 4))
    # seed one restart with the single-point LogEI maximizer duplicated:
    # L-BFGS-B descends monotonically, so the joint optimum must end up
    # at least as good as the best duplicated single point
    r1 = maximize_acqf(logei_acq, x0[:, 0, :], 0.0, 1.0,
                       acq_state=(gp, jnp.asarray(best)), strategy="dbe",
                       options=MsoOptions(maxiter=80, pgtol=1e-6))
    x0[0] = r1.best_x[None, :].repeat(q, 0)
    init_vals = np.asarray(qlogei_acq(state, jnp.asarray(x0)))
    res = maximize_acqf(qlogei_acq, x0, 0.0, 1.0, acq_state=state,
                        strategy="dbe", q=q,
                        options=MsoOptions(maxiter=80, pgtol=1e-6))
    assert res.x.shape == (5, q, 4)
    assert res.best_x.shape == (q, 4)
    assert res.best_acq >= float(np.max(init_vals)) - 1e-9


def test_joint_q2_all_strategies_agree(gp50):
    gp, best = gp50
    state = qlogei_state(gp, best, 2, n_samples=64, seed=0)
    x0 = np.random.default_rng(7).uniform(0, 1, (4, 2, 4))
    init_best = float(np.max(np.asarray(qlogei_acq(state,
                                                   jnp.asarray(x0)))))
    bests = {}
    for s in ("seq", "dbe", "dbe_vec"):
        r = maximize_acqf(qlogei_acq, x0, 0.0, 1.0, acq_state=state,
                          strategy=s, q=2,
                          options=MsoOptions(maxiter=80, pgtol=1e-6))
        bests[s] = r.best_acq
        assert r.best_acq >= init_best - 1e-9, (s, r.best_acq, init_best)
    v = np.array(list(bests.values()))
    # same landscape, local optimizers: comparable, not identical
    assert np.max(v) - np.min(v) < 1.0, bests


# ----------------------------------------------------- fused posterior
def test_fused_posterior_matches_ref_interpret():
    """Pallas kernel (interpret mode) vs jnp oracle at equal precision."""
    rng = np.random.default_rng(8)
    for n, D, k in [(7, 3, 5), (50, 5, 33), (130, 8, 129)]:
        X = jnp.asarray(rng.uniform(0, 1, (n, D)), jnp.float32)
        y = jnp.asarray(np.sin(5 * np.asarray(X)).sum(1), jnp.float32)
        gp = with_kinv(fit_gram(X, y, init_params(D, jnp.float32),
                                jitter=1e-4))
        Xq = jnp.asarray(rng.uniform(0, 1, (k, D)), jnp.float32)
        ils = jnp.exp(-gp.params.log_lengthscale)
        args = (Xq, gp.x_train, gp.alpha, gp.kinv, ils,
                gp.params.amplitude)
        m_ref, v_ref = matern52_posterior_ref(*args)
        m_pal, v_pal = matern52_posterior_op(*args, backend="pallas",
                                             interpret=True)
        scale = float(jnp.max(jnp.abs(m_ref))) + 1.0
        np.testing.assert_allclose(np.asarray(m_pal) / scale,
                                   np.asarray(m_ref) / scale, atol=1e-5)
        np.testing.assert_allclose(np.asarray(v_pal), np.asarray(v_ref),
                                   atol=1e-5)


def test_fused_posterior_grad_matches_ref():
    """The custom VJP routes gradients through the oracle exactly."""
    rng = np.random.default_rng(9)
    X = jnp.asarray(rng.uniform(0, 1, (40, 4)))
    y = jnp.asarray(np.sin(6 * np.asarray(X)).sum(1))
    gp = with_kinv(fit_gram(X, y, init_params(4)))
    Xq = jnp.asarray(rng.uniform(0, 1, (9, 4)))
    ils = jnp.exp(-gp.params.log_lengthscale)
    args = (gp.x_train, gp.alpha, gp.kinv, ils, gp.params.amplitude)

    def val(f):
        def g(xq):
            m, v = f(xq, *args)
            # linear functional: unit cotangents, so the VJPs compare
            # exactly (a nonlinear readout would mix in the f32 forward)
            return jnp.sum(m) + jnp.sum(v)
        return g

    g_pal = jax.grad(val(lambda *a: matern52_posterior_op(
        *a, backend="pallas", interpret=True)))(Xq)
    g_ref = jax.grad(val(matern52_posterior_ref))(Xq)
    np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_ref),
                               rtol=1e-10, atol=1e-12)


def test_fused_logei_acq_matches_xla_path(gp50):
    """The engine's fused LogEI backend == the classic Cholesky LogEI."""
    gp, best = gp50
    state = (gp, jnp.asarray(best))
    X = jnp.asarray(np.random.default_rng(10).uniform(0, 1, (12, 4)))
    a_x = logei_acq(state, X)
    a_f = fused_logei_acq("pallas_interpret")(state, X)
    # f32 kernel vs f64 Cholesky: log-scale tail values amplify the
    # variance's relative f32 error, hence rtol (not atol) dominates
    np.testing.assert_allclose(np.asarray(a_f), np.asarray(a_x),
                               rtol=1e-3, atol=1e-4)


def test_fused_backend_through_mso(gp50):
    """Full D-BE maximization on the fused backend lands on the same
    optimum as the xla backend."""
    gp, best = gp50
    state = (gp, jnp.asarray(best))
    x0 = np.random.default_rng(11).uniform(0, 1, (6, 4))
    opts = MsoOptions(maxiter=100, pgtol=1e-5)
    r_xla = maximize_acqf(logei_acq, x0, 0.0, 1.0, acq_state=state,
                          strategy="dbe", options=opts)
    r_fused = maximize_acqf(fused_logei_acq("pallas_interpret"), x0,
                            0.0, 1.0, acq_state=state, strategy="dbe",
                            options=opts)
    assert abs(r_fused.best_acq - r_xla.best_acq) < 1e-2


@pytest.mark.parametrize("backend", ["pallas", "pallas_interpret"])
@pytest.mark.parametrize("kernel,drop_kinv", [("matern52", True),
                                              ("rbf", False)],
                         ids=["no-kinv", "rbf"])
def test_pallas_posterior_refuses_unfusable_state(gp50, backend, kernel,
                                                  drop_kinv):
    """A Pallas backend never falls back to the Cholesky predict: a state
    the fused kernel cannot serve raises instead."""
    gp, _ = gp50
    bad = GPState(x_train=gp.x_train, y_train=gp.y_train, params=gp.params,
                  chol=gp.chol, alpha=gp.alpha, kernel=kernel,
                  kinv=None if drop_kinv else gp.kinv)
    X = jnp.asarray(np.random.default_rng(12).uniform(0, 1, (5, 4)))
    with pytest.raises(ValueError, match="kinv"):
        posterior(bad, X, backend=backend)


def test_auto_backend_resolves_to_xla_off_tpu():
    """Off the TPU, ``auto`` is the Cholesky path, chosen where the
    samplers resolve their backend."""
    assert jax.default_backend() != "tpu"
    assert resolve_backend("auto") == "xla"
    space = BoxSpace.cube(3, 0.0, 1.0)
    s = GPSampler(space)
    assert s.posterior_backend == "xla" and s._acq_fn is logei_acq
    fs = FleetSampler(space, n_studies=2, slots=2)
    assert fs.fleet.cfg.backend == "xla"
    assert fs.engine.acq_fn is logei_acq


@pytest.mark.parametrize("x64", [True, False], ids=["x64", "x32"])
def test_auto_backend_on_tpu_is_xla(monkeypatch, x64):
    """On a TPU too, ``auto`` is the Cholesky path whatever the state's
    dtype: the float32 kernel runs only when asked for by name."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", x64)
    try:
        assert resolve_backend("auto") == "xla"
    finally:
        jax.config.update("jax_enable_x64", was)


def test_pad_gp_extends_kinv(gp50):
    gp, _ = gp50
    gpp = pad_gp(gp, 64)
    assert gpp.kinv is not None
    n = gp.x_train.shape[0]
    np.testing.assert_allclose(np.asarray(gpp.kinv[:n, :n]),
                               np.asarray(gp.kinv))
    np.testing.assert_array_equal(np.asarray(gpp.kinv[n:, :n]), 0.0)
