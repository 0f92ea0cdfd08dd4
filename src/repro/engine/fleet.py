"""The fleet ask plane — vmapped multi-study suggest with slot-based
continuous batching.

PR 2 fused one study's whole suggest path into one device program per GP
size bucket (``engine/ask.py``); at BO sizes (B≈10 restarts, D≈8) that
program still leaves the device almost idle.  This module applies the
paper's D-BE argument once more, *across studies*: stack S whole studies
along a new leading axis — exactly as ``dbe_vec`` stacked restarts — and
serve every study's ``suggest()`` from ONE compiled program per
(GP size bucket, slot count):

* **stacked study state** — per-slot padded ``X (S, b, D)`` / ``y (S,
  b)`` buffers with per-slot observation counts, θ ``(S, P)``, Cholesky
  factors ``(S, b, b)`` and (fused posterior backends) K⁻¹ stacks;
* **vmapped GP cores** — ``refit_core`` / ``incr_core`` (the study-axis
  halves of the PR-2 ask pipeline) run under ``jax.vmap`` with
  heterogeneous per-study ``n`` masks;
* **one lockstep solve for the whole fleet** — restart sampling per slot
  (per-study PRNG streams) feeds a single ``(S, B, D)`` L-BFGS-B solve:
  ``core.lbfgsb`` takes the leading batch shape natively, so QN
  iterations and line-search rounds are shared across the fleet instead
  of vmapping S separate ``while_loop``s;
* **slot-based continuous batching** — mirroring ``serve/engine.py``:
  fixed slot blocks grouped by ``pad_bucket_for`` bucket, queued studies
  admitted at trial boundaries, studies migrating blocks on bucket
  growth (host-side state compaction, θ carried for warm starts), idle
  slots frozen behind benign masked rows.  Blocks of the same (bucket,
  slots) shape share compiled programs, so compile counts stay
  O(#buckets) — independent of how many studies the fleet serves.

Exactness mirrors PR 2: per-slot rows are updated element-wise along the
study axis and the lockstep solver freezes converged/idle rows, so a
study's trajectory is bit-for-bit independent of its slot and of which
other studies share the batch (tests/test_fleet.py).

**Mesh sharding** — pass ``mesh=`` (a 1-D ``"study"`` mesh from
``launch.mesh.make_fleet_mesh``) and every slot block widens to
``cfg.slots × ndev`` rows placed behind ``NamedSharding(mesh,
P("study"))``: device d owns the ``cfg.slots`` contiguous slots
``[d·slots, (d+1)·slots)`` and the three block programs run under
``shard_map``, so each device refits and solves only its own slots.  The
hot loop needs NO cross-device collectives: every stacked op is already
element-wise along the study axis, and each device's lockstep
``while_loop`` runs until its own rows converge.  Pinning the *local*
width to ``cfg.slots`` on every mesh size is what makes trajectories
bit-for-bit placement-independent: a vmap's width changes last-ulp
lowering, but each device always traces the identical fixed-width local
program, and a study's position inside that program is covered by PR 3's
bitwise slot/batch-composition-independence invariant.  The host-side
scheduler balances admissions across per-device occupancy and routes
bucket-growth migrations through the same evict → host-compact →
re-admit path, which now doubles as the cross-device state move; compile
counts stay O(#buckets), independent of S *and* of the mesh's device
count (the programs key on the mesh and the (bucket, slots) shape, never
on per-device occupancy).
"""
from __future__ import annotations

import contextlib
import time
import zlib
from dataclasses import dataclass
from typing import Callable, Dict, Hashable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.core.lbfgsb import (CONV_LS_FAIL, CONV_MAXITER, LbfgsbOptions,
                               lbfgsb_minimize)
from repro.distributed.sharding import (fleet_pspec, fleet_sharding,
                                       gspmd_lowering)
from repro.engine.ask import (_MSO_DEFAULT, SuggestInfo, incr_core,
                              refit_core, restart_points)
from repro.engine.cache import CountingJit, retrace_report
from repro.engine.engine import EvalEngine
from repro.engine.plan import EvalPlan
from repro.obs import trace as obs
from repro.gp.fit import (FIT_OPTS, _FAR, pad_bucket_for, standardize_masked,
                          theta_bounds, theta_init_grid, unpack_theta)
from repro.gp.gpr import GPState

Array = jax.Array


class FleetFullError(RuntimeError):
    """Admission rejected: the fleet is at its configured capacity
    (``max_studies`` / ``max_queue``).  Callers either surface the
    rejection or degrade to the solo :class:`~repro.engine.ask.AskEngine`
    path (see ``FleetSampler(degrade=...)``)."""


class FleetStudyError(RuntimeError):
    """A study left the fleet (load-shed past its admission deadline, or
    parked after exhausting quarantine retries).  Sync callers get it
    raised; async callers receive the instance through the result
    mailbox (``pop_result``) in place of a suggestion."""


@dataclass(frozen=True)
class FleetConfig:
    """Static description of one fleet ask plane (everything here is baked
    into the compiled programs; a fleet serves studies that share it)."""
    dim: int
    n_restarts: int = 10             # B: incumbent + (B-1) uniform
    slots: int = 8                   # compiled slot-batch width PER DEVICE
    kernel: str = "matern52"
    backend: str = "xla"             # resolved posterior backend
    pad_bucket: int = 32             # GP size-bucket quantum
    refit_interval: int = 8          # full MAP refit cadence (≥1)
    warm_start: bool = True          # seed MAP fits from the slot's prev θ
    gp_fit_restarts: int = 2
    gp_fit_maxiter: int = 60
    mso: LbfgsbOptions = _MSO_DEFAULT
    # robustness knobs — all host-side scheduling/retry policy; none is
    # baked into a compiled program, so changing them never retraces
    max_studies: Optional[int] = None    # live-study cap (admission gate)
    max_queue: Optional[int] = None      # registration-queue cap
    max_blocks: Optional[int] = None     # slot-block cap (device memory)
    admission_timeout: Optional[float] = None   # seconds queued → shed
    quarantine_retries: int = 2          # bad-refit retries before parking
    # bounded exponential backoff between quarantine retries (0 disables:
    # immediate re-runs).  Jitter decorrelates a block's retry storms
    # from its neighbors'; the draw comes from a dedicated host RNG so it
    # is deterministic per engine and never touches study PRNG streams.
    retry_backoff_base: float = 0.0      # seconds before retry attempt 1
    retry_backoff_cap: float = 2.0       # backoff ceiling (seconds)
    retry_backoff_jitter: float = 0.25   # multiplicative jitter fraction

    def __post_init__(self):
        if self.slots < 1:
            raise ValueError("slots must be >= 1")
        if self.refit_interval < 1:
            raise ValueError("refit_interval must be >= 1")
        if self.n_restarts < 2:
            raise ValueError("n_restarts must be >= 2")
        if self.quarantine_retries < 0:
            raise ValueError("quarantine_retries must be >= 0")
        if self.retry_backoff_base < 0.0:
            raise ValueError("retry_backoff_base must be >= 0")


class _Study:
    """Host-side record of one study: observations (source of truth for
    admission/migration compaction), slot assignment, refit bookkeeping,
    and the pending-request/result mailbox."""

    __slots__ = ("sid", "xs", "ys", "tags", "block", "slot", "n_fit",
                 "since_refit", "has_factor", "has_theta", "theta_host",
                 "trial", "pending", "result", "from_device", "deadline",
                 "shed", "parked")

    def __init__(self, sid: Hashable):
        self.sid = sid
        self.xs: List[np.ndarray] = []
        self.ys: List[float] = []
        self.tags: List[Optional[Hashable]] = []   # caller trial ids
        self.block: Optional["_Block"] = None
        self.slot = -1
        self.from_device: Optional[int] = None   # device before migration
        self.n_fit = 0
        self.since_refit = 0
        self.has_factor = False          # factor rows valid (incr eligible)
        self.has_theta = False           # θ row fitted (warm-start eligible)
        self.theta_host: Optional[np.ndarray] = None   # carried on migration
        self.trial = 0                   # suggest counter (default PRNG)
        self.pending: Optional[Tuple[Array, int]] = None  # (key, fit_seed)
        self.result = None  # (x, SuggestInfo) | FleetStudyError | None
        self.deadline: Optional[float] = None    # admission deadline (mono)
        self.shed: Optional[str] = None          # load-shed reason
        self.parked: Optional[str] = None        # quarantine-parked reason

    @property
    def n(self) -> int:
        return len(self.ys)


# Idle slots carry this many benign pseudo-observations: the _FAR pattern
# gives a ~diagonal gram, zero standardized targets, and a fast-converging
# frozen row — never NaNs that would stall the shared lockstep loops.
_IDLE_N = 2


class _Block:
    """One slot block: ``width`` studies padded to one GP size bucket.

    ``width`` is ``cfg.slots`` per mesh device (``cfg.slots`` exactly when
    unsharded): the slot axis splits evenly over the mesh, so every device
    runs the SAME local program on exactly ``cfg.slots`` rows no matter
    how many devices the mesh has — which is what makes trajectories
    bit-for-bit placement-independent (a vmap's width changes last-ulp
    lowering; a slot's position inside a fixed-width vmap never does).
    Blocks with equal (bucket, width) share the fleet's compiled programs
    (the CountingJit caches key on shapes), so adding blocks never adds
    traces.
    """

    def __init__(self, cfg: FleetConfig, bucket: int, dtype,
                 sharding=None, width: Optional[int] = None):
        S, b, D = width or cfg.slots, bucket, cfg.dim
        self.bucket = bucket
        self.sharding = sharding         # NamedSharding(mesh, P(study))
        idle = np.full((b, D), _FAR) + np.arange(b)[:, None]
        self.idle_x = np.asarray(idle)               # host row template
        self.x = self._pin(jnp.asarray(np.tile(idle[None], (S, 1, 1)),
                                       dtype))
        self.y = self._pin(jnp.zeros((S, b), dtype))
        th0 = np.zeros((D + 2,))
        th0[-1] = -4.0                               # theta_init_grid base
        self.theta0 = np.asarray(th0)
        self.theta = self._pin(jnp.asarray(np.tile(th0[None], (S, 1)),
                                           dtype))
        eye = np.eye(b)
        self.chol = self._pin(jnp.asarray(np.tile(eye[None], (S, 1, 1)),
                                          dtype))
        self.alpha = self._pin(jnp.zeros((S, b), dtype))
        self.kinv = (None if cfg.backend == "xla" else
                     self._pin(jnp.asarray(np.tile(eye[None], (S, 1, 1)),
                                           dtype)))
        self.studies: List[Optional[_Study]] = [None] * S

    def _pin(self, a: Array) -> Array:
        """Keep block state on its mesh placement: host-side compaction
        updates (.at[].set scatters) must never silently gather a block
        onto one device."""
        return a if self.sharding is None else jax.device_put(
            a, self.sharding)

    def free_slot(self) -> int:
        for s, st in enumerate(self.studies):
            if st is None:
                return s
        return -1

    def n_valid(self) -> np.ndarray:
        nv = np.full((len(self.studies),), _IDLE_N, np.int32)
        for s, st in enumerate(self.studies):
            if st is not None:
                nv[s] = st.n
        return nv


class FleetEngine:
    """Serve S concurrent studies' ask() from one device program.

    Usage is a request/step/result cycle (continuous batching, mirroring
    ``serve.ServeEngine``): ``observe()`` appends per-study observations,
    ``request_suggest()`` enqueues a study's next ask, ``step()`` admits
    queued studies and runs one fused fleet program per active block, and
    ``pop_result()`` collects each study's suggestion.  ``suggest()``
    wraps the cycle for synchronous (solo) callers — any other studies'
    pending requests ride along in the same step.

    ``mesh`` (optional): a 1-D study mesh (``make_fleet_mesh``).  Slot
    blocks then span ``cfg.slots`` slots on EVERY mesh device
    (``slots × ndev`` total), ``NamedSharding``-split along the slot
    axis, and the three block programs run under ``shard_map`` — each
    device serves only its own fixed-width shard with no collectives in
    the hot loop.  Trajectories are bit-for-bit identical across mesh
    sizes (and to the unsharded fleet); the scheduler balances admissions
    over per-device occupancy and bucket-growth migration becomes a
    cross-device state move when the target slot lives on another device.
    """

    def __init__(self, engine: EvalEngine, cfg: FleetConfig,
                 mesh: Optional[Mesh] = None, journal=None,
                 fault_injector=None, sleep_fn=None):
        self.engine = engine
        self.cfg = cfg
        self.mesh = mesh
        # backoff/latency sleeps go through this hook so tests (and the
        # BO service's virtual-clock mode) can charge simulated time
        # instead of wall-clocking; deterministic jitter from a host RNG
        self._sleep = time.sleep if sleep_fn is None else sleep_fn
        self._backoff_rng = np.random.default_rng(0xB0)
        # durability + chaos hooks (both host-side, both optional):
        # ``journal`` duck-types StudyJournal.append (admission, migration,
        # refit-θ, quarantine, shed records — the sampler journals
        # asks/tells); ``fault_injector`` may override the incremental ok
        # flags / full-refit health flags to force the fallback and
        # quarantine paths deterministically (tests/faults.py)
        self.journal = journal
        self.fault_injector = fault_injector
        # notified as (sid, trial_tag, reason) when an observation is
        # quarantined — FleetSampler marks the owning Trial
        self.on_quarantine: Optional[Callable] = None
        self._plan = EvalPlan.for_batch(cfg.n_restarts, cfg.dim)
        self._fit_opts = FIT_OPTS._replace(maxiter=cfg.gp_fit_maxiter)
        # how the block programs lower; see the mesh branch below
        self._partitioner = contextlib.nullcontext
        if mesh is None:
            self._ndev = 1
            self._slot_sharding = None
            full_impl, incr_impl, mso_impl = (
                self._full_impl, self._incr_impl, self._mso_impl)
            jit_kw: dict = {}
        else:
            if len(mesh.axis_names) != 1:
                raise ValueError("fleet mesh must be 1-D (the study axis);"
                                 f" got axes {mesh.axis_names}")
            self._ndev = int(mesh.devices.size)
            self._slot_sharding = fleet_sharding(mesh)
            # one shard_map per block program: every operand/result leads
            # with the slot axis, so a single P(study) prefix spec splits
            # them all; each device runs the identical slot-local program
            # (check_vma off: nothing is replicated, nothing is reduced)
            spec = fleet_pspec(1, mesh.axis_names[0])

            def smap(fn):
                return jax.shard_map(fn, mesh=mesh, in_specs=spec,
                                     out_specs=spec, check_vma=False)

            full_impl, incr_impl, mso_impl = (
                smap(self._full_impl), smap(self._incr_impl),
                smap(self._mso_impl))
            # key the jit caches on (mesh, spec): host-built per-step
            # operands (keys, masks, θ inits) land on the mesh here, so
            # cache identity never depends on live-device occupancy
            jit_kw = {"in_shardings": self._slot_sharding}
            # the full refit's MAP fit holds a float64 Cholesky, which the
            # TPU compiler refuses to partition under Shardy: every call
            # of the mesh programs lowers through GSPMD instead
            self._partitioner = gspmd_lowering
        # three programs per (bucket, slots) shape: full refit,
        # incremental refit, and the fleet MSO tail
        self._full_jit = CountingJit(full_impl, **jit_kw)
        self._incr_jit = CountingJit(incr_impl, **jit_kw)
        self._mso_jit = CountingJit(mso_impl, **jit_kw)
        # obs device-completion timing (block-until-ready spans when the
        # tracer is enabled; passthrough otherwise) — wrapped AFTER the
        # CountingJit assignments so those call sites stay intact
        self._full_jit = obs.ProgramTimer(self._full_jit,
                                          "fleet.program.full")
        self._incr_jit = obs.ProgramTimer(self._incr_jit,
                                          "fleet.program.incr")
        self._mso_jit = obs.ProgramTimer(self._mso_jit,
                                         "fleet.program.mso")
        # a block spans the whole mesh: cfg.slots slots per device
        self._slots_total = cfg.slots * self._ndev
        self._dtype = jnp.asarray(0.0).dtype
        self._studies: Dict[Hashable, _Study] = {}
        self._queue: List[_Study] = []       # awaiting a slot
        self._blocks: List[_Block] = []
        self._base_key = jax.random.PRNGKey(0)
        # economy counters
        self.n_full_refits = 0
        self.n_incremental = 0
        self.n_fallbacks = 0
        self.n_steps = 0
        self.n_admissions = 0
        self.n_migrations = 0
        self.n_migrations_intra = 0      # re-admitted on the same device
        self.n_migrations_cross = 0      # ... on a different device
        # robustness counters
        self.n_rejected = 0              # admissions refused (fleet full)
        self.n_shed = 0                  # queued studies past deadline
        self.n_quarantined = 0           # observations dropped as poison
        self.n_parked = 0                # studies retired by quarantine
        self.n_retries = 0               # quarantine retry refit launches
        self.n_retry_backoffs = 0        # backoff sleeps taken
        self.backoff_total_s = 0.0       # total backoff charged (seconds)
        # lockstep counters, summed over MSO solves (one per block step):
        # n_rounds = solves + iters + ls_rounds; a requesting study's
        # wait rounds are those its restarts sat frozen after the last
        # of them stopped, while other lanes kept the loop running
        self.n_mso_solves = 0
        self.n_mso_iters = 0             # deepest lane's iterations (max k)
        self.n_mso_ls_rounds = 0         # rounds past the deepest lane's
                                         # one per iteration
        self.n_mso_mixed_rounds = 0      # rounds in which one lane retried
                                         # while another began an iteration
        self.n_mso_study_rounds = 0      # rounds x requesting studies
        self.n_mso_study_wait_rounds = 0
        self.n_mso_capped_lanes = 0      # requesting lanes at maxiter or
                                         # a failed line search
        self.n_eager_updates = 0         # block scatters outside programs

    def _journal(self, record: dict) -> None:
        if self.journal is not None:
            self.journal.append(record)

    # ----------------------------------------------------------- host api
    def add_study(self, sid: Hashable,
                  deadline: Optional[float] = None) -> None:
        """Register a study; it is admitted to a slot at the next trial
        boundary (step) once it has observations.

        Backpressure: raises :class:`FleetFullError` when the live-study
        or registration-queue caps are hit.  ``deadline`` (absolute
        ``time.monotonic()`` value, default now + ``admission_timeout``)
        bounds how long the study may wait queued for a slot before being
        load-shed."""
        if sid in self._studies:
            raise ValueError(f"study {sid!r} already registered")
        cfg = self.cfg
        live = sum(1 for s in self._studies.values()
                   if s.shed is None and s.parked is None)
        reason = None
        if cfg.max_studies is not None and live >= cfg.max_studies:
            reason = (f"fleet full: {live} live studies "
                      f"(max_studies={cfg.max_studies})")
        elif (cfg.max_queue is not None
                and len(self._queue) >= cfg.max_queue):
            reason = (f"admission queue full: {len(self._queue)} waiting "
                      f"(max_queue={cfg.max_queue})")
        if reason is not None:
            self.n_rejected += 1
            self._journal({"op": "reject", "sid": sid, "reason": reason})
            obs.instant("fleet.reject", sid=str(sid), reason=reason)
            raise FleetFullError(reason)
        st = _Study(sid)
        if deadline is None and cfg.admission_timeout is not None:
            deadline = time.monotonic() + cfg.admission_timeout
        st.deadline = deadline
        self._studies[sid] = st
        self._queue.append(st)

    def observe(self, sid: Hashable, x_unit, y: float,
                tag: Optional[Hashable] = None) -> None:
        """Append one observation (unit-cube x, raw minimized y).  ``tag``
        is the caller's trial id, carried so a later quarantine can name
        the offending trial.

        Guardrail: non-finite values are refused here — one NaN in a slot
        row would poison the stacked standardization/gram for the whole
        block and stall the shared lockstep ``while_loop``s."""
        st = self._studies[sid]
        x_unit = np.asarray(x_unit, np.float64).reshape(self.cfg.dim)
        y = float(y)
        if not (np.all(np.isfinite(x_unit)) and np.isfinite(y)):
            raise ValueError(
                f"study {sid!r}: non-finite observation "
                f"(trial {tag!r}, y={y!r}) — report evaluation failures "
                f"with failed=True; they must never reach GP data")
        st.xs.append(x_unit)
        st.ys.append(y)
        st.tags.append(tag)
        blk = st.block
        if blk is None:
            return
        if pad_bucket_for(st.n, self.cfg.pad_bucket) > blk.bucket:
            # bucket migration: journal, then evict and re-admit
            # (compacted into a larger block) at the next trial boundary
            self.n_migrations += 1
            self._journal({"op": "migrate", "sid": sid, "n": st.n})
            obs.instant("fleet.migrate", sid=str(sid), n=st.n)
            self._evict(st)
        else:
            i = st.n - 1
            self._set(blk, "x", (st.slot, i), x_unit)
            self._set(blk, "y", (st.slot, i), y)

    def request_suggest(self, sid: Hashable, key: Optional[Array] = None,
                        fit_seed: Optional[int] = None) -> None:
        """Enqueue one suggest for ``sid`` (no-op if one is already
        pending or an uncollected result is waiting).  ``key`` defaults
        to the fleet's per-study stream ``fold_in(fold_in(base,
        study), trial)``; ``fit_seed`` to the trial counter."""
        st = self._studies[sid]
        if st.shed is not None or st.parked is not None:
            state = "shed" if st.shed is not None else "parked"
            raise FleetStudyError(
                f"study {sid!r} left the fleet ({state}): "
                f"{st.shed or st.parked}")
        if st.pending is not None or st.result is not None:
            return
        if key is None:
            # crc32, not hash(): string sids must give the same stream in
            # every process (hash() is salted per interpreter)
            sid_tag = zlib.crc32(repr(sid).encode()) & 0x7FFFFFFF
            skey = jax.random.fold_in(self._base_key, sid_tag)
            key = jax.random.fold_in(skey, st.trial)
        if fit_seed is None:
            fit_seed = st.trial
        st.pending = (key, int(fit_seed))

    def pop_result(self, sid: Hashable
                   ) -> Optional[Tuple[np.ndarray, SuggestInfo]]:
        """Collect (and clear) the study's suggestion, if ready."""
        st = self._studies[sid]
        res, st.result = st.result, None
        return res

    def cancel_request(self, sid: Hashable) -> bool:
        """Withdraw a study's pending suggest request (deadline shed at
        the service layer): frees the slot's per-step reservation so the
        next block step does no work for it.  An already-computed but
        uncollected result is discarded too — safe, because suggest keys
        are caller-derived, so re-requesting with the same key and the
        same observations recomputes the identical suggestion.  Returns
        whether anything was actually withdrawn."""
        st = self._studies[sid]
        had = st.pending is not None or st.result is not None
        st.pending = None
        st.result = None
        return had

    def suggest(self, sid: Hashable, key: Optional[Array] = None,
                fit_seed: Optional[int] = None
                ) -> Tuple[np.ndarray, SuggestInfo]:
        """Synchronous ask for one study: request → step → collect (other
        studies' pending requests are batched into the same step)."""
        self.request_suggest(sid, key, fit_seed)
        self.step()
        res = self.pop_result(sid)
        assert res is not None
        if isinstance(res, FleetStudyError):
            raise res
        return res

    def gp_state(self, sid: Hashable) -> GPState:
        """The study's current fitted GPState, sliced from its slot row
        (tests/introspection; mirrors ``AskEngine.gp_state``)."""
        st = self._studies[sid]
        if st.block is None or not st.has_factor:
            raise ValueError(f"study {sid!r} has no fitted state in a slot")
        blk = st.block

        def row(a):      # slot index as data: one program for every slot
            return jax.lax.dynamic_index_in_dim(a, st.slot, keepdims=False)

        valid = jnp.arange(blk.bucket) < st.n_fit
        y_std, _, _ = standardize_masked(-row(blk.y), valid)
        return GPState(x_train=row(blk.x), y_train=y_std,
                       params=unpack_theta(row(blk.theta), self.cfg.dim),
                       chol=row(blk.chol), alpha=row(blk.alpha),
                       kernel=self.cfg.kernel,
                       kinv=None if blk.kinv is None else row(blk.kinv))

    def study_theta(self, sid: Hashable) -> Optional[np.ndarray]:
        """The study's last fully-refit θ (for snapshots), or None if no
        full refit has committed yet."""
        st = self._studies[sid]
        if st.block is not None and st.has_theta:
            return np.asarray(st.block.theta[st.slot])
        return None if not st.has_theta else st.theta_host

    def restore_theta(self, sid: Hashable, theta) -> None:
        """Re-seed a (not yet admitted) study's warm-start θ — the
        recovery path replays journaled full-refit θs through here so a
        post-recovery warm-started refit matches the uninterrupted run
        bit-for-bit (same mechanism as the migration theta_host carry)."""
        st = self._studies[sid]
        st.theta_host = np.asarray(theta, np.float64)
        st.has_theta = True

    def study_state(self, sid: Hashable) -> Tuple[str, Optional[str]]:
        """(state, reason): ``live`` / ``queued`` with reason None, or
        ``shed`` / ``parked`` with the recorded reason — callers poll this
        to decide when to degrade to the solo path."""
        st = self._studies[sid]
        if st.parked is not None:
            return "parked", st.parked
        if st.shed is not None:
            return "shed", st.shed
        return ("live", None) if st.block is not None else ("queued", None)

    def step(self) -> int:
        """One trial boundary: admit queued studies, then run one fused
        program set per block holding pending requests.  Returns the
        number of suggestions produced."""
        self._admit()
        for st in self._queue:
            if st.pending is not None:
                st.pending = None      # drop the bad request: one broken
                raise ValueError(      # study must not wedge the fleet
                    f"study {st.sid!r} requested suggest() with "
                    f"{st.n} observations; needs >= 2")
        tr = obs.get()
        t0 = tr.now_us() if tr is not None else 0.0
        served = 0
        with self._partitioner():
            for blk in self._blocks:
                with obs.span("fleet.step_block",
                              bucket=blk.bucket) as args:
                    served += self._step_block(blk, args)
        if tr is not None and served:
            tr.record_span("fleet.step", t0, tr.now_us() - t0,
                           served=served, n_blocks=len(self._blocks))
        self.n_steps += 1 if served else 0
        return served

    def stats_snapshot(self) -> dict:
        n_compiles = (self._full_jit.n_compiles + self._incr_jit.n_compiles
                      + self._mso_jit.n_compiles)
        return {
            "n_studies": len(self._studies),
            "n_blocks": len(self._blocks),
            "n_full_refits": self.n_full_refits,
            "n_incremental": self.n_incremental,
            "n_fallbacks": self.n_fallbacks,
            "n_steps": self.n_steps,
            "n_admissions": self.n_admissions,
            "n_migrations": self.n_migrations,
            "n_migrations_intra": self.n_migrations_intra,
            "n_migrations_cross": self.n_migrations_cross,
            "n_rejected": self.n_rejected,
            "n_shed": self.n_shed,
            "n_quarantined": self.n_quarantined,
            "n_parked": self.n_parked,
            "n_retries": self.n_retries,
            "n_retry_backoffs": self.n_retry_backoffs,
            "backoff_total_s": round(self.backoff_total_s, 6),
            "n_mso_solves": self.n_mso_solves,
            "n_mso_iters": self.n_mso_iters,
            "n_mso_ls_rounds": self.n_mso_ls_rounds,
            "n_mso_mixed_rounds": self.n_mso_mixed_rounds,
            "n_mso_study_rounds": self.n_mso_study_rounds,
            "n_mso_study_wait_rounds": self.n_mso_study_wait_rounds,
            "n_mso_capped_lanes": self.n_mso_capped_lanes,
            "n_eager_updates": self.n_eager_updates,
            "n_devices": self._ndev,
            "slots_per_device": self._device_occupancy(),
            "queue_depth": len(self._queue),
            "n_full_compiles": self._full_jit.n_compiles,
            "n_incr_compiles": self._incr_jit.n_compiles,
            "n_mso_compiles": self._mso_jit.n_compiles,
            "n_fleet_compiles": n_compiles,
            "retraces": retrace_report({"full": self._full_jit,
                                        "incr": self._incr_jit,
                                        "mso": self._mso_jit}),
        }

    # ------------------------------------------------------- scheduler
    def _slot_device(self, slot: int) -> int:
        """Mesh device owning ``slot``: NamedSharding splits the slot
        axis into ndev contiguous shards of ``cfg.slots`` rows each."""
        return slot // self.cfg.slots

    def _device_occupancy(self) -> List[int]:
        """Live studies resident on each mesh device (all blocks)."""
        occ = [0] * self._ndev
        for blk in self._blocks:
            for s, st in enumerate(blk.studies):
                if st is not None:
                    occ[self._slot_device(s)] += 1
        return occ

    def _pick_slot(self, bucket: int) -> Optional[Tuple["_Block", int]]:
        """Balanced admission: among free slots in ``bucket``-blocks, take
        the one whose device holds the fewest live studies (ties: earliest
        block, lowest slot — on a 1-device mesh this degenerates to the
        PR-3 first-free-slot rule)."""
        occ = self._device_occupancy()
        best = None
        for bi, bl in enumerate(self._blocks):
            if bl.bucket != bucket:
                continue
            for s, cur in enumerate(bl.studies):
                if cur is None:
                    key = (occ[self._slot_device(s)], bi, s)
                    if best is None or key < best[1]:
                        best = ((bl, s), key)
        return None if best is None else best[0]

    def _admit(self) -> None:
        still: List[_Study] = []
        now = time.monotonic()
        for st in self._queue:
            if st.shed is not None or st.parked is not None:
                continue                 # left the fleet while queued
            if st.n < 1:                 # nothing to pad yet: stay queued
                still.append(st)
                continue
            bucket = pad_bucket_for(st.n, self.cfg.pad_bucket)
            pick = self._pick_slot(bucket)
            if pick is None:
                if (self.cfg.max_blocks is not None
                        and len(self._blocks) >= self.cfg.max_blocks):
                    # no slot and no room to grow: shed waiters past
                    # their admission deadline, keep the rest queued
                    if st.deadline is not None and now > st.deadline:
                        self._shed(st, "admission deadline exceeded "
                                   f"({len(self._blocks)} blocks full)")
                    else:
                        still.append(st)
                    continue
                blk = _Block(self.cfg, bucket, self._dtype,
                             self._slot_sharding, self._slots_total)
                self._blocks.append(blk)
                occ = self._device_occupancy()
                slot = min(range(self._slots_total),
                           key=lambda s: (occ[self._slot_device(s)], s))
            else:
                blk, slot = pick
            self._install(st, blk, slot)
            self.n_admissions += 1
        self._queue = still

    def _shed(self, st: _Study, reason: str) -> None:
        """Load-shed a queued study (never one holding a slot): it stops
        being schedulable; the owning sampler degrades to the solo path
        when it sees the state (``study_state``)."""
        self.n_shed += 1
        self._journal({"op": "shed", "sid": st.sid, "reason": reason})
        obs.instant("fleet.shed", sid=str(st.sid), reason=reason)
        st.shed = reason
        st.pending = None

    def shed_study(self, sid: Hashable, reason: str) -> None:
        """Mark a registered study as load-shed (journal-replay path:
        recovery re-applies shed records through here)."""
        st = self._studies[sid]
        if st.block is not None:
            self._clear_slot(st)
        if st.shed is None:
            self._shed(st, reason)

    def _install(self, st: _Study, blk: _Block, slot: int) -> None:
        """Host-side state compaction: copy the study's live observations
        into the block's padded slot row (θ carried for warm starts).  On
        a mesh this IS the cross-device move — the compacted row lands on
        whichever device owns the target slot."""
        n = st.n
        x_row = np.array(blk.idle_x)
        x_row[:n] = np.stack(st.xs)
        y_row = np.zeros((blk.bucket,))
        y_row[:n] = st.ys
        self._set(blk, "x", slot, x_row)
        self._set(blk, "y", slot, y_row)
        if st.theta_host is not None:
            self._set(blk, "theta", slot, st.theta_host)
        self._journal({"op": "admit", "sid": st.sid,
                       "bucket": blk.bucket, "slot": slot, "n": n})
        obs.instant("fleet.admit", sid=str(st.sid), bucket=blk.bucket,
                    slot=slot, n=n)
        blk.studies[slot] = st
        st.block, st.slot = blk, slot
        if st.from_device is not None:       # bucket-growth re-admission
            if self._slot_device(slot) == st.from_device:
                self.n_migrations_intra += 1
            else:
                self.n_migrations_cross += 1
            st.from_device = None

    def _set(self, blk: _Block, name: str, idx, value) -> None:
        """One eager device update of a block buffer, outside the three
        block programs (observe, install, evict, quarantine): counted in
        ``n_eager_updates``."""
        a = getattr(blk, name)
        setattr(blk, name, blk._pin(a.at[idx].set(jnp.asarray(value,
                                                                a.dtype))))
        self.n_eager_updates += 1

    def _clear_slot(self, st: _Study) -> None:
        """Free the study's slot: save θ for a warm start, reset the row
        to the benign idle pattern (the _FAR invariant holds for every
        non-live slot, whatever removed its study)."""
        blk, s = st.block, st.slot
        if st.has_theta:
            st.theta_host = np.asarray(blk.theta[s])
        eye = np.eye(blk.bucket)
        self._set(blk, "x", s, blk.idle_x)
        self._set(blk, "y", s, np.zeros((blk.bucket,)))
        self._set(blk, "theta", s, blk.theta0)
        self._set(blk, "chol", s, eye)
        self._set(blk, "alpha", s, np.zeros((blk.bucket,)))
        if blk.kinv is not None:
            self._set(blk, "kinv", s, eye)
        blk.studies[s] = None
        st.block, st.slot = None, -1
        st.from_device = self._slot_device(s)
        st.has_factor = False            # the factor dies with the bucket

    def _evict(self, st: _Study) -> None:
        """Bucket migration: free the slot and re-queue for re-admission
        (compacted) into a larger block."""
        self._clear_slot(st)
        self._queue.append(st)

    def _park(self, st: _Study, reason: str) -> None:
        """Retire a study the fleet cannot serve (quarantine retries
        exhausted, or too few clean observations left): free its slot and
        fail the pending request through the result mailbox."""
        self.n_parked += 1
        self._journal({"op": "park", "sid": st.sid, "reason": reason})
        obs.instant("fleet.park", sid=str(st.sid), reason=reason)
        if st.block is not None:
            self._clear_slot(st)
        st.parked = reason
        st.pending = None
        st.result = FleetStudyError(f"study {st.sid!r} parked: {reason}")

    def _quarantine_newest(self, st: _Study, reason: str) -> None:
        """Drop the study's newest observation from GP data with a
        recorded reason (WAL first), resetting its slot row entry to the
        benign idle value; park the study if too few clean observations
        remain."""
        k = st.n - 1
        x_bad, y_bad, tag = st.xs[-1], st.ys[-1], st.tags[-1]
        self.n_quarantined += 1
        self._journal({"op": "quarantine", "sid": st.sid, "trial": tag,
                       "x": x_bad.tolist(), "y": y_bad, "reason": reason})
        obs.instant("fleet.quarantine", sid=str(st.sid),
                    trial=str(tag), reason=reason)
        st.xs.pop()
        st.ys.pop()
        st.tags.pop()
        blk, s = st.block, st.slot
        if blk is not None:
            self._set(blk, "x", (s, k), blk.idle_x[k])
            self._set(blk, "y", (s, k), 0.0)
        st.n_fit = min(st.n_fit, st.n)
        st.has_factor = False        # the factor summed the dropped row
        if self.on_quarantine is not None:
            self.on_quarantine(st.sid, tag, reason)
        if st.n < 2 and st.block is not None:
            self._park(st, f"only {st.n} clean observations "
                       f"after quarantine")

    def _step_block(self, blk: _Block, solve: dict) -> int:
        """Refit and solve the block's requesting studies; adds the MSO
        solve's lockstep numbers to ``solve`` (the step_block span's
        args)."""
        cfg = self.cfg
        req = [(s, st) for s, st in enumerate(blk.studies)
               if st is not None and st.pending is not None]
        if not req:
            return 0
        for s, st in req:
            if st.n < 2:
                st.pending = None      # drop, don't wedge (see step())
                raise ValueError(f"suggest() for study {st.sid!r} needs "
                                 f">= 2 observations, have {st.n}")
        S = self._slots_total
        nv = jnp.asarray(blk.n_valid())
        sids = [None if s is None else s.sid for s in blk.studies]

        # refit_interval=k ⇒ a full MAP refit every k-th suggest (per
        # slot; k=1 disables incremental updates) — same predicate as
        # AskEngine.suggest
        kind: Dict[int, str] = {}
        do_incr = np.zeros((S,), bool)
        for s, st in req:
            incremental = (st.has_factor and st.n - st.n_fit == 1
                           and st.since_refit < cfg.refit_interval - 1)
            if incremental:
                do_incr[s] = True
                kind[s] = "incremental"
            else:
                kind[s] = "full"

        if do_incr.any():
            chol, alpha, kinv, ok = self._incr_jit(
                blk.x, blk.y, nv, blk.theta, blk.chol, blk.alpha,
                blk.kinv, jnp.asarray(do_incr))
            blk.chol, blk.alpha, blk.kinv = chol, alpha, kinv
            ok = np.asarray(ok)
            if self.fault_injector is not None:
                ok = self.fault_injector.incr_ok(ok, sids)
            for s, st in req:
                if not do_incr[s]:
                    continue
                if ok[s]:
                    st.since_refit += 1
                    self.n_incremental += 1
                else:                    # exactness fallback: refit for real
                    kind[s] = "fallback"
                    self.n_fallbacks += 1
                    self.engine.record_refit_fallback()

        full_slots = [s for s, _ in req if kind[s] != "incremental"]
        if full_slots:
            dt = blk.x.dtype
            R = cfg.gp_fit_restarts
            # ONE warm-start snapshot for the whole retry loop: a retry
            # must not warm-start from the unhealthy θ it is retrying
            theta_host = np.asarray(blk.theta)
            tlo, tup = theta_bounds(cfg.dim, dt)
            pending_full = list(full_slots)
            for attempt in range(cfg.quarantine_retries + 1):
                pf = set(pending_full)
                rows = []
                for s in range(S):
                    st = blk.studies[s]
                    if s in pf:
                        init = None
                        if cfg.warm_start and st.has_theta:
                            init = unpack_theta(
                                jnp.asarray(theta_host[s], dt), cfg.dim)
                        rows.append(theta_init_grid(
                            cfg.dim, dt, R, st.pending[1], init=init))
                    else:                # masked-out slot: benign inits
                        rows.append(theta_init_grid(cfg.dim, dt, R, 0))
                thetas = jnp.stack(rows)            # (S, R, P)
                do_full = np.zeros((S,), bool)
                do_full[pending_full] = True
                nv = jnp.asarray(blk.n_valid())
                theta, chol, alpha, kinv, okf = self._full_jit(
                    blk.x, blk.y, nv, thetas,
                    jnp.broadcast_to(tlo, thetas.shape),
                    jnp.broadcast_to(tup, thetas.shape),
                    jnp.asarray(do_full), blk.theta, blk.chol, blk.alpha,
                    blk.kinv)
                blk.theta, blk.chol, blk.alpha, blk.kinv = \
                    theta, chol, alpha, kinv
                fi = self.fault_injector
                if fi is not None and hasattr(fi, "full_delay"):
                    # injected refit latency: charge the sleep hook (a
                    # virtual clock in tests) — data/timing only, the
                    # compiled program is untouched
                    d = fi.full_delay([blk.studies[s].sid
                                       for s in pending_full])
                    if d > 0.0:
                        self._sleep(d)
                okf = np.asarray(okf)
                if self.fault_injector is not None:
                    okf = self.fault_injector.full_ok(okf, sids)
                bad = [s for s in pending_full if not okf[s]]
                for s in pending_full:
                    if okf[s]:
                        st = blk.studies[s]
                        st.since_refit = 0
                        st.has_theta = True
                        self.n_full_refits += 1
                        if self.journal is not None:
                            self._journal({
                                "op": "refit", "sid": st.sid,
                                "theta": np.asarray(
                                    blk.theta[s]).tolist()})
                if not bad:
                    break
                # quarantine: drop each unhealthy slot's newest
                # observation (the likeliest poison) and refit just those
                # slots — a pure data change (same shapes), so retries
                # reuse the same compiled program
                nxt = []
                for s in bad:
                    st = blk.studies[s]
                    self._quarantine_newest(
                        st, f"full refit unhealthy "
                        f"(attempt {attempt + 1})")
                    if st.block is None:     # parked mid-quarantine
                        continue
                    if attempt < cfg.quarantine_retries:
                        nxt.append(s)
                    else:
                        self._park(st, "quarantine retries exhausted "
                                   f"({cfg.quarantine_retries + 1} "
                                   f"unhealthy refits)")
                pending_full = nxt
                if not pending_full:
                    break
                # bounded exponential backoff (with jitter) before the
                # retry: a persistently unhealthy slot must not hot-spin
                # full refits back-to-back.  Host-side only — the retry
                # still reuses the same compiled program.
                self.n_retries += len(pending_full)
                if cfg.retry_backoff_base > 0.0:
                    delay = min(cfg.retry_backoff_base * (2.0 ** attempt),
                                cfg.retry_backoff_cap)
                    delay *= 1.0 + (cfg.retry_backoff_jitter
                                    * float(self._backoff_rng.random()))
                    self.n_retry_backoffs += 1
                    self.backoff_total_s += delay
                    self._journal({"op": "backoff", "attempt": attempt + 1,
                                   "delay_s": delay,
                                   "sids": [blk.studies[s].sid
                                            for s in pending_full]})
                    obs.instant("fleet.backoff", attempt=attempt + 1,
                                delay_s=delay, n_studies=len(pending_full))
                    self._sleep(delay)
            nv = jnp.asarray(blk.n_valid())
            # parked studies dropped their requests mid-phase
            req = [(s, st) for s, st in req if st.pending is not None]
            if not req:
                return 0

        keys = np.zeros((S, 2), np.uint32)
        for s, st in req:
            keys[s] = np.asarray(st.pending[0])
        best_x, stats = self._mso_jit(
            jnp.asarray(keys), blk.x, blk.y, nv, blk.theta, blk.chol,
            blk.alpha, blk.kinv)
        bx = np.asarray(best_x)                     # ONE (S, D) transfer
        k_arr, ev_arr, rounds, bacq, status, done_round, mixed = stats
        # rounds is per-slot: each slot reports its own device's lockstep
        # round count (devices loop independently on a mesh; on one
        # device every slot sees the same shared count)
        rounds = np.asarray(rounds)
        for s, st in req:
            st.n_fit = st.n
            st.has_factor = True
            st.trial += 1
            info = SuggestInfo(kind=kind[s], n_iters=k_arr[s],
                               n_evals=ev_arr[s], rounds=rounds[s],
                               best_acq=bacq[s])
            st.result = (bx[s], info)
            st.pending = None
        # frozen idle/non-requesting rows are the fleet's padding
        # analogue: only requesters' evals count as live points
        ev_live = np.zeros((S, cfg.n_restarts), np.int64)
        for s, _ in req:
            ev_live[s] = np.asarray(ev_arr[s])
        self.engine.record_lockstep_economy(S * cfg.n_restarts,
                                            int(rounds.max()), ev_live)
        solve.update(self._count_lockstep(
            [s for s, _ in req], rounds, np.asarray(k_arr),
            np.asarray(status), np.asarray(done_round), np.asarray(mixed)))
        return len(req)

    def _count_lockstep(self, req_slots: List[int], rounds: np.ndarray,
                        k: np.ndarray, status: np.ndarray,
                        done_round: np.ndarray, mixed: np.ndarray
                        ) -> Dict[str, int]:
        """Fold one MSO solve into the lockstep counters.  ``rounds`` and
        ``mixed`` are per slot, ``k``/``status``/``done_round`` per slot
        and restart.  On a mesh each device loops on its own; the solve's
        rounds, as in ``n_rounds``, are those of the device that looped
        longest, and a study waits on its own device's loop.  The deepest
        lane alone takes ``1 + k`` evaluations, so ``ls_rounds``, the
        rounds beyond its iterations, is never negative."""
        lanes = self.cfg.slots                   # slots per device
        d = int(np.argmax(rounds)) // lanes
        n_rounds = int(rounds.max())
        iters = int(k[d * lanes:(d + 1) * lanes].max())
        ls_rounds = n_rounds - 1 - iters
        n_mixed = int(mixed[d * lanes])
        r = rounds[req_slots]
        wait = int(np.sum(r - done_round[req_slots].max(axis=1)))
        capped = int(np.isin(status[req_slots],
                             (CONV_MAXITER, CONV_LS_FAIL)).sum())
        self.n_mso_solves += 1
        self.n_mso_iters += iters
        self.n_mso_ls_rounds += ls_rounds
        self.n_mso_mixed_rounds += n_mixed
        self.n_mso_study_rounds += int(r.sum())
        self.n_mso_study_wait_rounds += wait
        self.n_mso_capped_lanes += capped
        return {"rounds": n_rounds, "iters": iters, "ls_rounds": ls_rounds,
                "wait_rounds": wait, "capped": capped, "mixed": n_mixed}

    # ------------------------------------------------------- device side
    def _full_impl(self, x, y, n_valid, thetas, tlo, tup, do_full,
                   theta_old, chol_old, alpha_old, kinv_old):
        """Vmapped full refit over the slot axis; ``do_full`` masks which
        slots commit (the rest keep their previous state).

        Also returns a per-slot health flag: a refit that produced
        non-finite θ/α or a broken Cholesky (non-PD gram → NaN or
        non-positive diagonal) must NOT be served — the unhealthy slot
        keeps its previous (benign) state and the host quarantines the
        likeliest poison observation and retries.  Masked-out slots are
        vacuously healthy."""
        cfg = self.cfg

        def one(x_s, y_s, nv, th, lo, up):
            _, _, theta, chol, alpha, kinv = refit_core(
                x_s, y_s, nv, th, lo, up, dim=cfg.dim, kernel=cfg.kernel,
                backend=cfg.backend, fit_opts=self._fit_opts)
            return theta, chol, alpha, kinv

        theta_n, chol_n, alpha_n, kinv_n = jax.vmap(one)(
            x, y, n_valid, thetas, tlo, tup)
        diag = jnp.diagonal(chol_n, axis1=-2, axis2=-1)
        healthy = (jnp.all(jnp.isfinite(theta_n), axis=-1)
                   & jnp.all(jnp.isfinite(alpha_n), axis=-1)
                   & jnp.all(jnp.isfinite(diag) & (diag > 0.0), axis=-1))
        ok = healthy | ~do_full

        def sel(new, old):
            m = (do_full & ok).reshape((-1,) + (1,) * (new.ndim - 1))
            return jnp.where(m, new, old)

        kinv = None if kinv_old is None else sel(kinv_n, kinv_old)
        return (sel(theta_n, theta_old), sel(chol_n, chol_old),
                sel(alpha_n, alpha_old), kinv, ok)

    def _incr_impl(self, x, y, n_valid, theta, chol_old, alpha_old,
                   kinv_old, do_incr):
        """Vmapped rank-one refit over the slot axis; a slot commits only
        when requested (``do_incr``) AND its Schur complement is sound."""
        cfg = self.cfg

        def one(x_s, y_s, nv, th, ch, ki):
            _, _, _, chol_new, alpha, kinv_new, ok = incr_core(
                x_s, y_s, nv, th, ch, ki, dim=cfg.dim, kernel=cfg.kernel)
            return chol_new, alpha, kinv_new, ok

        chol_n, alpha_n, kinv_n, ok = jax.vmap(one)(
            x, y, n_valid, theta, chol_old, kinv_old)
        commit = do_incr & ok

        def sel(new, old):
            m = commit.reshape((-1,) + (1,) * (new.ndim - 1))
            return jnp.where(m, new, old)

        kinv = None if kinv_old is None else sel(kinv_n, kinv_old)
        return sel(chol_n, chol_old), sel(alpha_n, alpha_old), kinv, ok

    def _mso_impl(self, keys, x, y, n_valid, theta, chol, alpha, kinv):
        """The fleet MSO tail: per-slot restart sampling feeds ONE
        (S, B, D) solve in shared rounds; per-slot argmax selects
        suggestions."""
        cfg = self.cfg
        b = x.shape[1]

        def prep(key, x_s, y_s, nv):
            valid = jnp.arange(b) < nv
            y_std, _, _ = standardize_masked(-y_s, valid)
            x0, best_val = restart_points(key, x_s, y_std, valid,
                                          cfg.n_restarts)
            return y_std, x0, best_val

        y_std, x0, best_val = jax.vmap(prep)(keys, x, y, n_valid)
        params = jax.vmap(lambda th: unpack_theta(th, cfg.dim))(theta)
        gp = GPState(x_train=x, y_train=y_std, params=params, chol=chol,
                     alpha=alpha, kernel=cfg.kernel, kinv=kinv)
        fun = self.engine.fleet_device_fun((gp, best_val), self._plan)
        res = lbfgsb_minimize(fun, x0, jnp.zeros_like(x0),
                              jnp.ones_like(x0), cfg.mso)
        best = jnp.argmax(-res.f, axis=1)                     # (S,)
        best_x = jnp.take_along_axis(
            res.x, best[:, None, None], axis=1)[:, 0]         # (S, D)
        best_acq = -jnp.take_along_axis(res.f, best[:, None], axis=1)[:, 0]
        # per-slot rounds: under shard_map this is the owning device's
        # (independent) round count, and every output leads with the
        # slot axis so one P(study) out-spec covers the whole pytree
        rounds = jnp.full((x.shape[0],), res.rounds)
        mixed = jnp.full((x.shape[0],), res.mixed_rounds)
        return best_x, (res.k, res.n_evals, rounds, best_acq, res.status,
                        res.done_round, mixed)
