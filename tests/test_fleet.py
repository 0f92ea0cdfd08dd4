"""Fleet ask plane tests: vmapped GP cores vs sequential calls, slot /
batch-composition independence (bitwise), compile economy independent of
fleet size, and the leading-batch lockstep solver."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.bo.sampler import FleetSampler, GPSampler
from repro.bo.space import BoxSpace
from repro.core.lbfgsb import LbfgsbOptions, lbfgsb_minimize
from repro.core.mso import MsoOptions
from repro.engine import EvalEngine, FleetConfig, FleetEngine
from repro.engine.ask import incr_core, refit_core
from repro.gp.fit import (FIT_OPTS, _FAR, pad_bucket_for, theta_bounds,
                          theta_init_grid)
from repro.launch.mesh import make_fleet_mesh

_MSO = MsoOptions(maxiter=40, pgtol=1e-2)


def _sphere(x):
    return float(np.sum((x - 0.4) ** 2))


def _fleet_kw(**over):
    kw = dict(n_startup_trials=4, n_restarts=4, pad_multiple=8,
              posterior_backend="xla", mso_options=MsoOptions(**vars(_MSO)))
    kw.update(over)
    return kw


def _padded_study(rng, n, b, D):
    """One padded study: n live points in a b-row _FAR-padded buffer."""
    x = np.full((b, D), _FAR) + np.arange(b)[:, None]
    x[:n] = rng.uniform(0, 1, (n, D))
    y = np.zeros((b,))
    y[:n] = np.sin(4 * x[:n]).sum(1)
    return jnp.asarray(x), jnp.asarray(y)


# ------------------------------------------------------- vmapped GP cores
def test_vmapped_refit_core_matches_sequential():
    """fit_padded_core under jax.vmap with heterogeneous per-study n
    masks == per-study sequential calls to <=1e-8 (both backends' output
    set: theta, chol, alpha, kinv)."""
    rng = np.random.default_rng(0)
    b, D, R = 16, 3, 2
    ns = [3, 7, 12, 16]                      # heterogeneous masks
    xs, ys = zip(*[_padded_study(rng, n, b, D) for n in ns])
    x, y = jnp.stack(xs), jnp.stack(ys)
    dt = x.dtype
    thetas = jnp.stack([theta_init_grid(D, dt, R, seed) for seed in ns])
    tlo, tup = theta_bounds(D, dt)
    tlo = jnp.broadcast_to(tlo, thetas.shape)
    tup = jnp.broadcast_to(tup, thetas.shape)
    nv = jnp.asarray(ns, jnp.int32)

    def core(x_s, y_s, n_s, th, lo, up):
        return refit_core(x_s, y_s, n_s, th, lo, up, dim=D,
                          kernel="matern52", backend="pallas_interpret",
                          fit_opts=FIT_OPTS)

    out_v = jax.vmap(core)(x, y, nv, thetas, tlo, tup)
    for i in range(len(ns)):
        out_s = core(x[i], y[i], nv[i], thetas[i], tlo[i], tup[i])
        for leaf_v, leaf_s in zip(out_v, out_s):
            np.testing.assert_allclose(np.asarray(leaf_v[i]),
                                       np.asarray(leaf_s), atol=1e-8)


def test_vmapped_incr_core_matches_sequential_across_migration():
    """incremental_update (via incr_core) under jax.vmap with
    heterogeneous n: growing each study one observation at a time stays
    <=1e-8 vs per-study sequential calls, including after a bucket
    migration (host-compacted re-entry into a larger padded buffer)."""
    rng = np.random.default_rng(1)
    D, R = 2, 2
    S = 3
    live = [rng.uniform(0, 1, (20, D)) for _ in range(S)]
    yall = [np.sin(3 * X).sum(1) for X in live]

    def seeded(b, ns):
        """Stacked padded buffers + per-study full fits at count ns."""
        xs, ys, fits = [], [], []
        for s in range(S):
            x = np.full((b, D), _FAR) + np.arange(b)[:, None]
            x[:ns[s]] = live[s][:ns[s]]
            y = np.zeros((b,))
            y[:ns[s]] = yall[s][:ns[s]]
            x, y = jnp.asarray(x), jnp.asarray(y)
            th = theta_init_grid(D, x.dtype, R, s)
            lo, up = theta_bounds(D, x.dtype)
            fits.append(refit_core(
                x, y, jnp.asarray(ns[s]), th,
                jnp.broadcast_to(lo, th.shape), jnp.broadcast_to(up, th.shape),
                dim=D, kernel="matern52", backend="pallas_interpret",
                fit_opts=FIT_OPTS))
            xs.append(x)
            ys.append(y)
        return list(xs), list(ys), fits

    def check_growth(b, n0, steps):
        xs, ys, fits = seeded(b, [n0, n0 + 1, n0 + 2])
        theta = jnp.stack([f[2] for f in fits])
        chol = jnp.stack([f[3] for f in fits])
        kinv = jnp.stack([f[5] for f in fits])
        ns = [n0, n0 + 1, n0 + 2]
        for step in range(steps):
            for s in range(S):                  # append one obs per study
                i = ns[s]
                xs[s] = xs[s].at[i].set(jnp.asarray(live[s][i]))
                ys[s] = ys[s].at[i].set(float(yall[s][i]))
                ns[s] = i + 1
            x, y = jnp.stack(xs), jnp.stack(ys)
            nv = jnp.asarray(ns, jnp.int32)

            def core(x_s, y_s, n_s, th, ch, ki):
                out = incr_core(x_s, y_s, n_s, th, ch, ki, dim=D,
                                kernel="matern52")
                return out[3], out[4], out[5], out[6]

            ch_v, al_v, ki_v, ok_v = jax.vmap(core)(x, y, nv, theta,
                                                    chol, kinv)
            assert bool(jnp.all(ok_v))
            for s in range(S):
                ch_s, al_s, ki_s, ok_s = core(x[s], y[s], nv[s], theta[s],
                                              chol[s], kinv[s])
                assert bool(ok_s)
                np.testing.assert_allclose(np.asarray(ch_v[s]),
                                           np.asarray(ch_s), atol=1e-8)
                np.testing.assert_allclose(np.asarray(al_v[s]),
                                           np.asarray(al_s), atol=1e-8)
                np.testing.assert_allclose(np.asarray(ki_v[s]),
                                           np.asarray(ki_s), atol=1e-8)
            chol, kinv = ch_v, ki_v
        return ns

    ns = check_growth(b=8, n0=3, steps=3)       # fill the 8-bucket
    assert ns == [6, 7, 8]
    # bucket migration: re-enter a 16-row buffer (fresh factor, as the
    # fleet scheduler does) and keep growing incrementally there
    check_growth(b=16, n0=9, steps=4)


# --------------------------------------- slot / batch-composition freedom
def _drive(sampler_or_fleet, rounds, record_study=0):
    xs = []
    if isinstance(sampler_or_fleet, FleetSampler):
        for _ in range(rounds):
            trials = sampler_or_fleet.ask_all()
            xs.append(trials[record_study].x.copy())
            for s, t in enumerate(trials):
                sampler_or_fleet.tell(s, t.trial_id, _sphere(t.x))
    else:
        for _ in range(rounds):
            t = sampler_or_fleet.ask()
            xs.append(t.x.copy())
            sampler_or_fleet.tell(t.trial_id, _sphere(t.x))
    return np.array(xs)


def test_fleet_solo_equals_company_bitwise():
    """A study's trajectory is bit-for-bit independent of which other
    studies share the fleet batch (refit_interval=1, warm_start=False:
    the deterministic full-refit regime, crossing a bucket boundary)."""
    kw = _fleet_kw(refit_interval=1, warm_start=False)
    space = BoxSpace.cube(2, -1.0, 1.0)
    solo = FleetSampler(space, n_studies=1, seed=5, slots=4, **kw)
    company = FleetSampler(space, n_studies=4, seed=5, slots=4, **kw)
    xs_solo = _drive(solo, 12)
    xs_company = _drive(company, 12)
    np.testing.assert_array_equal(xs_solo, xs_company)
    assert company.fleet.n_migrations >= 4     # crossed the 8-bucket


def test_fleet_slot_permutation_bitwise():
    """Admission order permutes slot assignment; per-study results must
    not move by a single bit."""
    cfg = FleetConfig(dim=2, n_restarts=4, slots=4, pad_bucket=8,
                      refit_interval=2, warm_start=True,
                      gp_fit_restarts=2,
                      mso=LbfgsbOptions(m=10, maxiter=40, pgtol=1e-2,
                                        ftol=0.0, maxls=25))
    rng = np.random.default_rng(7)
    obs = {s: rng.uniform(0, 1, (4, 2)) for s in range(3)}

    def run(order):
        from repro.core.acquisition import logei_acq
        fleet = FleetEngine(EvalEngine(logei_acq), cfg)
        for sid in order:
            fleet.add_study(sid)
            for x in obs[sid]:
                fleet.observe(sid, x, _sphere(x))
        out = {}
        for trial in range(3):                  # full + incremental steps
            for sid in order:
                fleet.request_suggest(sid, jax.random.fold_in(
                    jax.random.PRNGKey(100 + sid), trial), fit_seed=sid)
            fleet.step()
            for sid in order:
                x, info = fleet.pop_result(sid)
                out.setdefault(sid, []).append((x, info.kind))
                fleet.observe(sid, np.clip(x, 0, 1),
                              _sphere(np.clip(x, 0, 1)))
        return out

    a = run([0, 1, 2])
    b = run([2, 0, 1])
    for sid in range(3):
        for (xa, ka), (xb, kb) in zip(a[sid], b[sid]):
            assert ka == kb
            np.testing.assert_array_equal(xa, xb)


def test_fleet_matches_askengine():
    """Fleet-served suggestions track the solo fused AskEngine pipeline
    (vmap lowering may shift last-ulp rounding; trajectories must agree
    to 1e-10 over a full run crossing a bucket boundary)."""
    kw = _fleet_kw(refit_interval=1, warm_start=False)
    space = BoxSpace.cube(2, -1.0, 1.0)
    ref = GPSampler(space, strategy="dbe_vec", fused=True, seed=5, **kw)
    fleet = FleetSampler(space, n_studies=1, seed=5, slots=2, **kw)
    xs_ref = _drive(ref, 12)
    xs_fleet = _drive(fleet, 12)
    np.testing.assert_allclose(xs_fleet, xs_ref, atol=1e-10)


# ----------------------------------------------------- scheduler economy
def test_fleet_compile_counts_independent_of_fleet_size():
    """3 programs per (bucket, slots) shape; serving more studies (same
    slot width) reuses the same executables — compile counts depend on
    the bucket ladder only, never on S."""
    space = BoxSpace.cube(2, -1.0, 1.0)
    counts = {}
    for S in (2, 4):
        fs = FleetSampler(space, n_studies=S, seed=0, slots=2,
                          **_fleet_kw(refit_interval=4))
        fs.optimize(_sphere, 10)                # startup 4 + 6 suggests
        snap = fs.stats_snapshot()
        n_buckets = len({blk.bucket for blk in fs.fleet._blocks})
        assert snap["n_fleet_compiles"] <= 3 * n_buckets
        counts[S] = (snap["n_fleet_compiles"], n_buckets)
    assert counts[2] == counts[4], counts


def test_fleet_incremental_steady_state_and_quality():
    """Defaults (incremental on, warm starts): rank-one steps dominate,
    no fallbacks, and the fleet still optimizes every study."""
    fs = FleetSampler(BoxSpace.cube(2, -1.0, 1.0), n_studies=3, seed=0,
                      slots=4, **_fleet_kw(refit_interval=6))
    best = fs.optimize(_sphere, 16)
    assert all(b.y < 0.25 for b in best), [b.y for b in best]
    snap = fs.stats_snapshot()
    assert snap["n_incremental"] > snap["n_full_refits"]
    assert snap["n_fallbacks"] == 0
    assert snap["n_migrations"] == 3            # every study crossed b=8
    # placement observability: every migration is classified, and on one
    # device every migration is trivially intra-device
    assert snap["n_migrations_intra"] + snap["n_migrations_cross"] \
        == snap["n_migrations"]
    assert snap["n_migrations_cross"] == 0
    assert snap["n_devices"] == 1
    assert snap["slots_per_device"] == [3]
    assert snap["queue_depth"] == 0


def test_fleet_stats_placement_keys():
    """stats_snapshot() placement observability: queue depth tracks the
    registered-but-unadmitted set; per-device occupancy tracks installs."""
    from repro.core.acquisition import logei_acq
    cfg = FleetConfig(dim=2, n_restarts=4, slots=2, pad_bucket=8,
                      mso=LbfgsbOptions(m=10, maxiter=20, pgtol=1e-2,
                                        ftol=0.0, maxls=25))
    fleet = FleetEngine(EvalEngine(logei_acq), cfg)
    fleet.add_study("a")
    fleet.add_study("b")
    snap = fleet.stats_snapshot()
    assert snap["n_devices"] == 1
    assert snap["slots_per_device"] == [0]
    assert snap["queue_depth"] == 2          # registered, not yet admitted
    rng = np.random.default_rng(0)
    for x in rng.uniform(0, 1, (2, 2)):
        fleet.observe("a", x, _sphere(x))
        fleet.observe("b", x, _sphere(x))
    fleet.request_suggest("a", jax.random.PRNGKey(0), fit_seed=0)
    fleet.step()
    snap = fleet.stats_snapshot()
    assert snap["queue_depth"] == 0
    assert snap["slots_per_device"] == [2]
    assert snap["n_migrations_intra"] == snap["n_migrations_cross"] == 0


def test_fleet_mesh1_matches_unsharded_bitwise():
    """A 1-device fleet mesh is pure plumbing: trajectories and compile
    counts match the unsharded fleet bit for bit (the in-process half of
    the placement-independence invariant; the multi-device half runs in
    tests/test_fleet_mesh.py subprocesses)."""
    kw = _fleet_kw(refit_interval=4)
    space = BoxSpace.cube(2, -1.0, 1.0)
    plain = FleetSampler(space, n_studies=3, seed=5, slots=3, **kw)
    meshed = FleetSampler(space, n_studies=3, seed=5, slots=3,
                          mesh=make_fleet_mesh(1), **kw)
    xs_plain = _drive(plain, 10)
    xs_mesh = _drive(meshed, 10)
    np.testing.assert_array_equal(xs_plain, xs_mesh)
    sp, sm = plain.stats_snapshot(), meshed.stats_snapshot()
    assert sp["n_fleet_compiles"] == sm["n_fleet_compiles"]
    assert sm["n_devices"] == 1


def test_fleet_admission_and_errors():
    from repro.core.acquisition import logei_acq
    cfg = FleetConfig(dim=2, n_restarts=4, slots=2, pad_bucket=8)
    fleet = FleetEngine(EvalEngine(logei_acq), cfg)
    fleet.add_study("a")
    with pytest.raises(ValueError, match="already registered"):
        fleet.add_study("a")
    fleet.observe("a", np.array([0.5, 0.5]), 1.0)
    fleet.request_suggest("a")
    with pytest.raises(ValueError, match=">= 2"):
        fleet.step()
    # a sampler attached mid-run must be rejected
    s = GPSampler(BoxSpace.cube(2, -1.0, 1.0), strategy="dbe_vec",
                  fused=True, n_startup_trials=1, n_restarts=4,
                  pad_multiple=8)
    t = s.ask()
    s.tell(t.trial_id, 1.0)
    with pytest.raises(ValueError, match="before the first trial"):
        s.attach_fleet(fleet)


# ------------------------------------------------- leading-batch solver
def test_lbfgsb_leading_batch_matches_2d():
    """(S, B, D) solves == the S independent (B, D) solves, bitwise: the
    flattened fleet shares rounds but frozen rows never move."""
    rng = np.random.default_rng(3)
    S, B, D = 3, 4, 2
    centers = jnp.asarray(rng.uniform(-1, 1, (S, 1, D)))

    def make_fun(c):
        def fun(xb):
            d = xb - c
            return jnp.sum(d * d, -1), 2.0 * d
        return fun

    def fleet_fun(x):                            # (S, B, D)
        d = x - centers
        return jnp.sum(d * d, -1), 2.0 * d

    x0 = jnp.asarray(rng.uniform(-2, 2, (S, B, D)))
    lo, up = -jnp.ones((D,)), jnp.ones((D,))
    opts = LbfgsbOptions(maxiter=50)
    res = lbfgsb_minimize(fleet_fun, x0, lo, up, opts)
    assert res.x.shape == (S, B, D)
    assert res.rounds.ndim == 0
    for s in range(S):
        ref = lbfgsb_minimize(make_fun(centers[s]), x0[s], lo, up, opts)
        np.testing.assert_array_equal(np.asarray(res.x[s]),
                                      np.asarray(ref.x))
        np.testing.assert_array_equal(np.asarray(res.f[s]),
                                      np.asarray(ref.f))
        np.testing.assert_array_equal(np.asarray(res.status[s]),
                                      np.asarray(ref.status))


# ------------------------------------------------------ lockstep counters
def _counted_fleet(n_studies=3, maxiter=40):
    from repro.core.acquisition import logei_acq
    cfg = FleetConfig(dim=2, n_restarts=4, slots=4, pad_bucket=8,
                      refit_interval=2, gp_fit_restarts=2,
                      mso=LbfgsbOptions(m=10, maxiter=maxiter, pgtol=1e-2,
                                        ftol=0.0, maxls=25))
    fleet = FleetEngine(EvalEngine(logei_acq), cfg)
    rng = np.random.default_rng(11)
    for sid in range(n_studies):
        fleet.add_study(sid)
        for x in rng.uniform(0, 1, (4, 2)):
            fleet.observe(sid, x, _sphere(x) + 0.3 * sid)
    return fleet


def _spy_lockstep(fleet):
    """Record the per-study numbers each solve's counters were folded
    from: {slot: (solve rounds, last done_round of its restarts)}."""
    seen = []
    orig = fleet._count_lockstep

    def spy(req_slots, rounds, k, status, done_round, mixed):
        seen.append({s: (int(rounds[s]), int(done_round[s].max()))
                     for s in req_slots})
        return orig(req_slots, rounds, k, status, done_round, mixed)
    fleet._count_lockstep = spy
    return seen


def test_fleet_lockstep_counters_add_up():
    """Every round is the start round, one of the deepest restart's
    iterations or a round beyond them; at most every round but the start
    mixes one restart's line-search retry with another's new iteration;
    each requesting study waits the rounds after its last restart
    stopped, so the slowest study waits none."""
    fleet = _counted_fleet()
    seen = _spy_lockstep(fleet)
    before = {**fleet.engine.stats_snapshot(), **fleet.stats_snapshot()}
    for trial in range(3):                      # full + incremental steps
        for sid in range(3):
            fleet.request_suggest(sid)
        assert fleet.step() == 3
        for sid in range(3):
            x, _ = fleet.pop_result(sid)
            fleet.observe(sid, np.clip(x, 0, 1), _sphere(np.clip(x, 0, 1)))
    after = {**fleet.engine.stats_snapshot(), **fleet.stats_snapshot()}
    d = {k: after[k] - before[k] for k in (
        "n_rounds", "n_mso_solves", "n_mso_iters", "n_mso_ls_rounds",
        "n_mso_mixed_rounds", "n_mso_study_rounds",
        "n_mso_study_wait_rounds", "n_mso_capped_lanes", "n_steps")}
    assert d["n_mso_solves"] == d["n_steps"] == 3
    assert d["n_rounds"] == (d["n_mso_solves"] + d["n_mso_iters"]
                             + d["n_mso_ls_rounds"])
    assert d["n_mso_iters"] > 0 and d["n_mso_ls_rounds"] >= 0
    assert 0 <= d["n_mso_mixed_rounds"] <= d["n_rounds"] - d["n_mso_solves"]
    assert d["n_mso_study_rounds"] == 3 * d["n_rounds"]
    assert 0 <= d["n_mso_capped_lanes"] <= 3 * 3 * fleet.cfg.n_restarts
    waits = [{s: r - last for s, (r, last) in solve.items()}
             for solve in seen]
    assert d["n_mso_study_wait_rounds"] == sum(sum(w.values())
                                               for w in waits)
    for w in waits:
        assert min(w.values()) == 0             # the slowest study
        assert max(w.values()) > 0              # one stopped early


def test_fleet_capped_lanes_counted():
    """Restarts stopped by maxiter are counted, requesting lanes only."""
    fleet = _counted_fleet(n_studies=2, maxiter=1)
    fleet.request_suggest(0)
    fleet.step()
    snap = fleet.stats_snapshot()
    assert 0 < snap["n_mso_capped_lanes"] <= fleet.cfg.n_restarts
    assert snap["n_mso_iters"] == 1


def test_fleet_eager_updates_two_per_observed_trial():
    """observe() writes a slot's x and y rows: two eager device updates
    per trial once the study holds a slot; admission and eviction count
    theirs too."""
    fleet = _counted_fleet(n_studies=2)
    assert fleet.stats_snapshot()["n_eager_updates"] == 0  # queued only
    fleet.request_suggest(0)
    fleet.step()
    n0 = fleet.stats_snapshot()["n_eager_updates"]
    assert n0 == 2 * 2                     # two installs: x and y rows
    x, _ = fleet.pop_result(0)
    for k in range(4):                     # n 4 -> 8, bucket 8 holds
        fleet.observe(0, x, 1.0 + k)
        assert fleet.stats_snapshot()["n_eager_updates"] == n0 + 2 * (k + 1)
    n1 = fleet.stats_snapshot()["n_eager_updates"]
    fleet.observe(0, x, 5.0)               # n 8 -> 9: leaves the bucket
    # eviction resets x, y, theta, chol and alpha of the slot
    assert fleet.stats_snapshot()["n_eager_updates"] == n1 + 5
