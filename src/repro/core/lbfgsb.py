"""Batched bound-constrained L-BFGS-B in pure JAX.

This is the device-resident realization of the paper's D-BE scheme
("Decouple QN updates, Batch Evaluations"): every restart carries its own
limited-memory state stacked along a leading batch axis ``(B, m, D)`` and
its own line search, and ONE ``lax.while_loop`` runs batched evaluation
rounds.  In a round every running restart asks for the point its own
L-BFGS-B needs next (a line search's next trial, or the first trial of
its next iteration), and one batched call evaluates them all; no restart
waits for another's line search, so a solve takes as many rounds as its
longest restart takes evaluations.  Because each restart's two-loop
recursion reads only its own history slice, the implied inverse-Hessian
approximation is block-diagonal **by construction** — the exact property
the paper's coroutine buys on top of scipy, with zero per-iteration host
round trips.

Algorithm: projected quasi-Newton (Schmidt et al.) — gradient projection for
the bound active set + L-BFGS two-loop direction on the free variables +
projected-path backtracking Armijo line search.  Convergence criteria mirror
scipy's L-BFGS-B (``pgtol`` on the infinity norm of the projected gradient,
``ftol`` relative-decrease, ``maxiter``).

The same solver expresses all three of the paper's MSO schemes:

* D-BE  — call with the natural ``(B, D)`` restart layout (block states).
* C-BE  — call with ``B=1`` on the flattened ``(1, B*D)`` summed objective
          (one shared dense-over-BD state → off-diagonal artifacts).
* SEQ.  — call per-restart with ``B=1`` (reference trajectories).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Array = jax.Array

# Status codes (per restart).
RUNNING = 0
CONV_PGTOL = 1
CONV_FTOL = 2
CONV_MAXITER = 3
CONV_LS_FAIL = 4


class LbfgsbOptions(NamedTuple):
    m: int = 10
    maxiter: int = 200
    pgtol: float = 1e-5
    ftol: float = 1e-12          # relative f decrease; 0 disables
    maxls: int = 25
    armijo_c1: float = 1e-4
    ls_shrink: float = 0.5
    bound_eps: float = 1e-10     # active-set detection slack
    curv_eps: float = 1e-10      # curvature-pair acceptance threshold


class LbfgsbState(NamedTuple):
    """Stacked per-restart solver state. All leaves lead with B."""
    x: Array            # (B, D) current iterate (always inside [l, u])
    f: Array            # (B,)
    g: Array            # (B, D)
    s_hist: Array       # (B, m, D) displacement history (circular)
    y_hist: Array       # (B, m, D) gradient-difference history (circular)
    rho: Array          # (B, m)   1 / s.y per slot
    start: Array        # (B,) int32 circular-buffer head (oldest slot)
    length: Array       # (B,) int32 number of valid slots
    gamma: Array        # (B,)  H0 = gamma * I scaling
    k: Array            # (B,) int32 iteration count
    status: Array       # (B,) int32 RUNNING / CONV_*
    n_evals: Array      # (B,) int32 per-restart *active* objective evals
    rounds: Array       # () int32 number of batched evaluation rounds
    done_round: Array   # (B,) int32 ``rounds`` when the restart stopped
    d: Array            # (B, D) search direction of the iteration under way
    t: Array            # (B,)  its line search's current trial step
    tries: Array        # (B,) int32 trial points it has evaluated so far
    mixed_rounds: Array  # () int32 rounds mixing retries and first trials


class LbfgsbResult(NamedTuple):
    x: Array            # (B, D)
    f: Array            # (B,)
    g: Array            # (B, D)
    k: Array            # (B,) iterations taken
    status: Array       # (B,)
    n_evals: Array      # (B,)
    rounds: Array       # () total batched rounds; max(n_evals) on one
                        # device, each lane evaluating once a round
    done_round: Array   # (B,) rounds at the end of the restart's last
                        # iteration (1 if converged at the start point);
                        # rounds - done_round is how long it sat frozen
    mixed_rounds: Array  # () rounds in which one live lane retried a
                         # line search while another took the first
                         # trial of an iteration
    state: LbfgsbState  # final full state (history introspection)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _proj(x: Array, lower: Array, upper: Array) -> Array:
    return jnp.clip(x, lower, upper)


def projected_grad(x: Array, g: Array, lower: Array, upper: Array) -> Array:
    """scipy-style projected gradient: x - P(x - g)."""
    return x - _proj(x - g, lower, upper)


def _active_mask(x, g, lower, upper, eps):
    """Coordinates pinned at a bound with the gradient pushing outward."""
    at_lo = (x <= lower + eps) & (g > 0)
    at_hi = (x >= upper - eps) & (g < 0)
    return at_lo | at_hi


def _ordered_history(state: LbfgsbState, m: int):
    """Gather history slots in chronological order (j=0 oldest)."""
    B = state.x.shape[0]
    j = jnp.arange(m, dtype=jnp.int32)
    order = (state.start[:, None] + j[None, :]) % m               # (B, m)
    s_ord = jnp.take_along_axis(state.s_hist, order[:, :, None], axis=1)
    y_ord = jnp.take_along_axis(state.y_hist, order[:, :, None], axis=1)
    rho_ord = jnp.take_along_axis(state.rho, order, axis=1)
    valid = j[None, :] < state.length[:, None]                    # (B, m)
    return s_ord, y_ord, rho_ord, valid


def two_loop_direction(g: Array, s_ord: Array, y_ord: Array, rho_ord: Array,
                       valid: Array, gamma: Array) -> Array:
    """Batched L-BFGS two-loop recursion: returns H·g (NOT negated).

    All inputs carry a leading batch axis; history is chronological
    (slot 0 oldest).  Invalid slots are masked to no-ops, so restarts with
    different history lengths coexist in one call.
    """
    m = s_ord.shape[1]
    q = g
    alphas = []
    for jj in range(m - 1, -1, -1):     # newest -> oldest
        a = rho_ord[:, jj] * jnp.einsum("bd,bd->b", s_ord[:, jj], q)
        a = jnp.where(valid[:, jj], a, 0.0)
        q = q - a[:, None] * y_ord[:, jj]
        alphas.append(a)
    alphas = alphas[::-1]               # index by chronological jj
    r = gamma[:, None] * q
    for jj in range(m):                 # oldest -> newest
        b = rho_ord[:, jj] * jnp.einsum("bd,bd->b", y_ord[:, jj], r)
        b = jnp.where(valid[:, jj], b, 0.0)
        r = r + (alphas[jj] - b)[:, None] * s_ord[:, jj]
    return r


def inv_hessian_dense(state: LbfgsbState, m: int) -> Array:
    """Materialize the implied inverse Hessian H (B, D, D) from history.

    Used by the off-diagonal-artifact experiments: applying the two-loop
    recursion to the identity columns yields the dense matrix the recursion
    implicitly represents.
    """
    B, D = state.x.shape
    s_ord, y_ord, rho_ord, valid = _ordered_history(state, m)
    eye = jnp.eye(D, dtype=state.x.dtype)

    def col(e):
        gb = jnp.broadcast_to(e[None, :], (B, D))
        return two_loop_direction(gb, s_ord, y_ord, rho_ord, valid,
                                  state.gamma)
    cols = jax.vmap(col, out_axes=2)(eye)       # (B, D, D): H e_j in col j
    return cols


# ---------------------------------------------------------------------------
# the solver
# ---------------------------------------------------------------------------

def _init_state(fun_batched, x0, lower, upper, opts: LbfgsbOptions
                ) -> LbfgsbState:
    B, D = x0.shape
    x0 = _proj(x0, lower, upper)
    f0, g0 = fun_batched(x0)
    dt = x0.dtype
    zeros_hist = jnp.zeros((B, opts.m, D), dt)
    return LbfgsbState(
        x=x0, f=f0, g=g0,
        s_hist=zeros_hist, y_hist=zeros_hist,
        rho=jnp.zeros((B, opts.m), dt),
        start=jnp.zeros((B,), jnp.int32),
        length=jnp.zeros((B,), jnp.int32),
        gamma=jnp.ones((B,), dt),
        k=jnp.zeros((B,), jnp.int32),
        status=jnp.full((B,), RUNNING, jnp.int32),
        n_evals=jnp.ones((B,), jnp.int32),
        rounds=jnp.asarray(1, jnp.int32),
        done_round=jnp.zeros((B,), jnp.int32),
        d=jnp.zeros((B, D), dt),
        t=jnp.zeros((B,), dt),
        tries=jnp.zeros((B,), jnp.int32),
        mixed_rounds=jnp.asarray(0, jnp.int32),
    )


def _check_initial_convergence(state: LbfgsbState, lower, upper,
                               opts: LbfgsbOptions) -> LbfgsbState:
    pg = projected_grad(state.x, state.g, lower, upper)
    done = jnp.max(jnp.abs(pg), axis=-1) <= opts.pgtol
    status = jnp.where(done, CONV_PGTOL, state.status)
    return state._replace(status=status.astype(jnp.int32),
                          done_round=jnp.where(done, state.rounds,
                                               state.done_round))


def _direction(state: LbfgsbState, lower, upper, opts: LbfgsbOptions
               ) -> Tuple[Array, Array]:
    """Each lane's search direction at its iterate, and its first trial
    step: unit for quasi-Newton steps, conservative on a cold start."""
    B = state.x.shape[0]
    act = _active_mask(state.x, state.g, lower, upper, opts.bound_eps)
    gm = jnp.where(act, 0.0, state.g)
    s_ord, y_ord, rho_ord, valid = _ordered_history(state, opts.m)
    d = -two_loop_direction(gm, s_ord, y_ord, rho_ord, valid, state.gamma)
    d = jnp.where(act, 0.0, d)
    # descent check; fall back to projected steepest descent
    dg = jnp.einsum("bd,bd->b", d, gm)
    gnorm2 = jnp.einsum("bd,bd->b", gm, gm)
    bad = dg > -1e-12 * jnp.maximum(gnorm2, 1e-30)
    d = jnp.where(bad[:, None], -gm, d)
    dinf = jnp.max(jnp.abs(d), axis=-1)
    t0 = jnp.where((state.length == 0),
                   jnp.minimum(1.0, 1.0 / jnp.maximum(dinf, 1e-30)),
                   jnp.ones((B,), state.x.dtype))
    return d, t0


def _round(fun_batched, lower, upper, opts: LbfgsbOptions,
           state: LbfgsbState) -> LbfgsbState:
    """One batched evaluation round.  Every running lane evaluates the
    trial point of its own projected backtracking Armijo line search;
    a lane that accepts (or exhausts ``maxls``) ends its iteration here
    and, if still running, starts the next one at once."""
    dt = state.x.dtype
    running = state.status == RUNNING                            # (B,)

    x_trial = jnp.where(
        running[:, None],
        _proj(state.x + state.t[:, None] * state.d, lower, upper), state.x)
    # stopped lanes re-evaluate their own point; the mask discards it
    f_t, g_t = fun_batched(x_trial)
    step_vec = x_trial - state.x
    gs = jnp.einsum("bd,bd->b", state.g, step_vec)
    armijo = f_t <= state.f + opts.armijo_c1 * gs
    # accept also if projection collapsed the step to ~zero (stuck)
    stuck = jnp.max(jnp.abs(step_vec), axis=-1) <= 1e-30
    accepted = running & (armijo | stuck)
    tries = state.tries + running.astype(jnp.int32)
    ls_failed = running & ~accepted & (tries >= opts.maxls)
    ends = accepted | ls_failed                  # the lane's iteration ends
    # on failure keep the old iterate
    x_new = jnp.where(accepted[:, None], x_trial, state.x)
    f_new = jnp.where(accepted, f_t, state.f)
    g_new = jnp.where(accepted[:, None], g_t, state.g)

    # ---- curvature-pair update (masked, circular buffer) ------------------
    s_vec = x_new - state.x
    y_vec = g_new - state.g
    sy = jnp.einsum("bd,bd->b", s_vec, y_vec)
    yy = jnp.einsum("bd,bd->b", y_vec, y_vec)
    ss = jnp.einsum("bd,bd->b", s_vec, s_vec)
    curv_ok = sy > opts.curv_eps * jnp.sqrt(
        jnp.maximum(ss, 1e-300) * jnp.maximum(yy, 1e-300))
    do_push = accepted & curv_ok

    full = state.length == opts.m
    slot = (state.start + state.length % opts.m) % opts.m        # write pos
    onehot = jax.nn.one_hot(slot, opts.m, dtype=dt) * \
        do_push.astype(dt)[:, None]                              # (B, m)
    s_hist = state.s_hist * (1 - onehot)[:, :, None] + \
        onehot[:, :, None] * s_vec[:, None, :]
    y_hist = state.y_hist * (1 - onehot)[:, :, None] + \
        onehot[:, :, None] * y_vec[:, None, :]
    rho_new = jnp.where(do_push, 1.0 / jnp.where(do_push, sy, 1.0), 0.0)
    rho = state.rho * (1 - onehot) + onehot * rho_new[:, None]
    start = jnp.where(do_push & full, (state.start + 1) % opts.m,
                      state.start)
    length = jnp.where(do_push, jnp.minimum(state.length + 1, opts.m),
                       state.length)
    gamma = jnp.where(do_push, sy / jnp.maximum(yy, 1e-300), state.gamma)

    # ---- convergence tests, for lanes whose iteration ended ---------------
    pg = projected_grad(x_new, g_new, lower, upper)
    conv_pg = jnp.max(jnp.abs(pg), axis=-1) <= opts.pgtol
    denom = jnp.maximum(jnp.maximum(jnp.abs(state.f), jnp.abs(f_new)), 1.0)
    conv_f = (opts.ftol > 0) & ((state.f - f_new) <= opts.ftol * denom)
    k_new = state.k + ends.astype(jnp.int32)
    conv_it = k_new >= opts.maxiter

    status = state.status
    status = jnp.where(ends & conv_pg, CONV_PGTOL, status)
    status = jnp.where(ends & ~conv_pg & conv_f, CONV_FTOL, status)
    status = jnp.where(ends & (status == RUNNING) & ls_failed,
                       CONV_LS_FAIL, status)
    status = jnp.where(ends & (status == RUNNING) & conv_it,
                       CONV_MAXITER, status)
    status = status.astype(jnp.int32)

    # a round in which one live lane retries while another takes the
    # first trial of an iteration; a solver that ends every lane's line
    # search before any lane's next iteration has no such round
    retry = running & (state.tries > 0)
    mixed = jnp.any(retry) & jnp.any(running & ~retry)
    rounds = state.rounds + 1
    new = state._replace(
        x=x_new, f=f_new, g=g_new,
        s_hist=s_hist, y_hist=y_hist, rho=rho,
        start=start, length=length, gamma=gamma,
        k=k_new, status=status,
        n_evals=state.n_evals + running.astype(jnp.int32),
        rounds=rounds,
        done_round=jnp.where(ends & (status != RUNNING), rounds,
                             state.done_round),
        mixed_rounds=state.mixed_rounds + mixed.astype(jnp.int32))

    # ---- the next iteration's direction, for lanes that go on -------------
    d_next, t0 = _direction(new, lower, upper, opts)
    again = ends & (status == RUNNING)
    return new._replace(
        d=jnp.where(again[:, None], d_next, state.d),
        t=jnp.where(again, t0, jnp.where(running & ~ends,
                                         state.t * opts.ls_shrink, state.t)),
        tries=jnp.where(ends, 0, tries))


def _minimize_2d(fun_batched, x0, lower, upper,
                 options: LbfgsbOptions) -> LbfgsbResult:
    """The core (B, D) solve (see :func:`lbfgsb_minimize`)."""
    state = _init_state(fun_batched, x0, lower, upper, options)
    state = _check_initial_convergence(state, lower, upper, options)
    d, t0 = _direction(state, lower, upper, options)
    state = state._replace(d=d, t=t0)

    step = functools.partial(_round, fun_batched, lower, upper, options)
    state = lax.while_loop(
        lambda s: jnp.any(s.status == RUNNING), step, state)
    return LbfgsbResult(x=state.x, f=state.f, g=state.g, k=state.k,
                        status=state.status, n_evals=state.n_evals,
                        rounds=state.rounds, done_round=state.done_round,
                        mixed_rounds=state.mixed_rounds, state=state)


def lbfgsb_minimize(
    fun_batched: Callable[[Array], Tuple[Array, Array]],
    x0: Array,
    lower: Array,
    upper: Array,
    options: LbfgsbOptions = LbfgsbOptions(),
) -> LbfgsbResult:
    """Minimize independent D-dimensional problems in shared rounds.

    The batch may carry an *arbitrary leading shape*: ``x0`` of shape
    ``(*batch, D)`` runs ``prod(batch)`` problems through ONE
    ``lax.while_loop`` (the fleet-ask requirement: a ``(S, B, D)`` fleet
    of studies × restarts shares its evaluation rounds, instead of
    vmapping S separate ``while_loop``s).  Every result leaf leads with
    ``batch`` again; ``rounds`` and ``mixed_rounds`` stay scalars (rounds
    are shared by construction).  Each problem's result is what solving
    it alone gives.

    Args:
      fun_batched: maps ``(*batch, D)`` → ``(batch values, (*batch, D)
        grads)``.  One call == one *batched evaluation round* in the
        paper's sense.
      x0: ``(*batch, D)`` initial points.
      lower/upper: broadcastable to ``x0.shape`` box bounds (±inf ok).
    """
    if x0.ndim < 2:
        raise ValueError(f"x0 must be (*batch, D), got {x0.shape}")
    if options.maxls < 1:
        raise ValueError(f"maxls must be >= 1, got {options.maxls}")
    lower = jnp.broadcast_to(jnp.asarray(lower, x0.dtype), x0.shape)
    upper = jnp.broadcast_to(jnp.asarray(upper, x0.dtype), x0.shape)
    if x0.ndim == 2:
        return _minimize_2d(fun_batched, x0, lower, upper, options)

    batch_shape, D = x0.shape[:-1], x0.shape[-1]

    def fun_flat(xf):
        f, g = fun_batched(xf.reshape(batch_shape + (D,)))
        return f.reshape(-1), g.reshape(-1, D)

    res = _minimize_2d(fun_flat, x0.reshape(-1, D),
                       lower.reshape(-1, D), upper.reshape(-1, D), options)

    def unflat(leaf):
        if leaf.ndim == 0:          # shared round counter
            return leaf
        return leaf.reshape(batch_shape + leaf.shape[1:])

    return jax.tree.map(unflat, res)


def lbfgsb_minimize_jit(fun_batched, x0, lower, upper,
                        options: LbfgsbOptions = LbfgsbOptions()):
    """jit-compiled entry point (options are static)."""
    @functools.partial(jax.jit, static_argnums=())
    def run(x0, lower, upper):
        return lbfgsb_minimize(fun_batched, x0, lower, upper, options)
    return run(x0, lower, upper)


# ---------------------------------------------------------------------------
# dense BFGS (for the unbounded off-diagonal-artifact appendix experiments)
# ---------------------------------------------------------------------------

class BfgsState(NamedTuple):
    x: Array; f: Array; g: Array
    hinv: Array          # (B, D, D)
    k: Array; status: Array


def bfgs_minimize(fun_batched, x0, *, maxiter=200, gtol=1e-8, maxls=25,
                  armijo_c1=1e-4, shrink=0.5) -> BfgsState:
    """Batched dense-BFGS (no bounds). Keeps the full (B, D, D) inverse
    Hessian so the artifact experiments can inspect it directly."""
    B, D = x0.shape
    dt = x0.dtype
    f0, g0 = fun_batched(x0)
    eye = jnp.broadcast_to(jnp.eye(D, dtype=dt), (B, D, D))
    st = BfgsState(x=x0, f=f0, g=g0, hinv=eye,
                   k=jnp.zeros((B,), jnp.int32),
                   status=jnp.where(
                       jnp.max(jnp.abs(g0), axis=-1) <= gtol,
                       CONV_PGTOL, RUNNING).astype(jnp.int32))

    def cond(s: BfgsState):
        return jnp.any(s.status == RUNNING)

    def body(s: BfgsState):
        running = s.status == RUNNING
        d = -jnp.einsum("bij,bj->bi", s.hinv, s.g)
        dg = jnp.einsum("bd,bd->b", d, s.g)
        bad = dg >= 0
        d = jnp.where(bad[:, None], -s.g, d)

        def ls_cond(c):
            t, acc, tries = c[0], c[1], c[5]
            return jnp.any(running & ~acc & (tries < maxls))

        def ls_body(c):
            t, acc, xn, fn, gn, tries = c
            xt = s.x + t[:, None] * d
            ft, gt = fun_batched(xt)
            gs = jnp.einsum("bd,bd->b", s.g, xt - s.x)
            ok = ft <= s.f + armijo_c1 * gs
            newly = running & ~acc & ok
            take = newly[:, None]
            return (jnp.where(newly | acc, t, t * shrink), acc | newly,
                    jnp.where(take, xt, xn), jnp.where(newly, ft, fn),
                    jnp.where(take, gt, gn),
                    tries + (running & ~acc).astype(jnp.int32))

        t0 = jnp.ones((B,), dt)
        c0 = (t0, ~running, s.x, s.f, s.g, jnp.zeros((B,), jnp.int32))
        t, acc, x_new, f_new, g_new, _ = lax.while_loop(ls_cond, ls_body, c0)
        fail = running & ~acc
        x_new = jnp.where(fail[:, None], s.x, x_new)
        f_new = jnp.where(fail, s.f, f_new)
        g_new = jnp.where(fail[:, None], s.g, g_new)

        sv = x_new - s.x
        yv = g_new - s.g
        sy = jnp.einsum("bd,bd->b", sv, yv)
        ok = running & ~fail & (sy > 1e-12)
        rho = 1.0 / jnp.where(ok, sy, 1.0)
        eyeD = jnp.eye(D, dtype=dt)
        V = eyeD[None] - rho[:, None, None] * \
            jnp.einsum("bi,bj->bij", sv, yv)
        h_upd = jnp.einsum("bik,bkl,bjl->bij", V, s.hinv, V) + \
            rho[:, None, None] * jnp.einsum("bi,bj->bij", sv, sv)
        hinv = jnp.where(ok[:, None, None], h_upd, s.hinv)

        conv = jnp.max(jnp.abs(g_new), axis=-1) <= gtol
        k_new = s.k + running.astype(jnp.int32)
        status = s.status
        status = jnp.where(running & conv, CONV_PGTOL, status)
        status = jnp.where(running & (status == RUNNING) & fail,
                           CONV_LS_FAIL, status)
        status = jnp.where(running & (status == RUNNING) &
                           (k_new >= maxiter), CONV_MAXITER, status)
        keep = running[:, None]
        return BfgsState(x=jnp.where(keep, x_new, s.x),
                         f=jnp.where(running, f_new, s.f),
                         g=jnp.where(keep, g_new, s.g),
                         hinv=hinv, k=k_new,
                         status=status.astype(jnp.int32))

    return lax.while_loop(cond, body, st)


def make_batched_value_and_grad(f_single: Callable[[Array], Array]):
    """Lift a single-point objective x:(D,)→() to the batched interface."""
    vg = jax.vmap(jax.value_and_grad(f_single))

    def fun_batched(xb):
        return vg(xb)
    return fun_batched
