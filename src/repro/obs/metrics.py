"""The unified ``stats_snapshot()`` schema contract.

The one documented layout for the four engine-layer ``stats_snapshot()``
dicts (AskEngine, FleetEngine, FleetSampler, BOService) plus the
EvalEngine block they compose over.  The layers nest by dict union
(FleetSampler = EvalEngine ∪ FleetEngine ∪ fleet extras; BOService =
FleetSampler ∪ ``svc_*``), which is exactly how the snapshots are built
in code — :func:`validate_snapshot` checks an actual snapshot against
the schema so the shapes can't silently drift again (the schema-shape
test in ``tests/test_obs.py``).  All host state; nothing here touches
jax.
"""
from __future__ import annotations

from typing import Any, Dict, List, Mapping

# --------------------------------------------------------------- schemas
#
# The documented snapshot layout.  Each entry lists the exact top-level
# keys a layer's stats_snapshot() returns; composite layers are built by
# union, mirroring the dict-union construction in code.  ``optional``
# keys appear only in some configurations (journaled planes).

RETRACES_KEYS = frozenset({"causes", "by_program"})

EVAL_ENGINE_KEYS = frozenset({
    "n_compiles", "n_eval_compiles", "n_lockstep_compiles", "n_rounds",
    "n_points", "n_padded", "n_refit_fallbacks", "bucket_rounds",
    "retraces"})

ASK_ENGINE_KEYS = frozenset({
    "n_full_refits", "n_incremental", "n_fallbacks", "n_full_compiles",
    "n_incr_compiles", "n_ask_compiles", "retraces"})

FLEET_ENGINE_KEYS = frozenset({
    "n_studies", "n_blocks", "n_full_refits", "n_incremental",
    "n_fallbacks", "n_steps", "n_admissions", "n_migrations",
    "n_migrations_intra", "n_migrations_cross", "n_rejected", "n_shed",
    "n_quarantined", "n_parked", "n_retries", "n_retry_backoffs",
    "backoff_total_s", "n_mso_solves", "n_mso_iters", "n_mso_ls_rounds",
    "n_mso_mixed_rounds",
    "n_mso_study_rounds", "n_mso_study_wait_rounds", "n_mso_capped_lanes",
    "n_eager_updates", "n_devices", "slots_per_device", "queue_depth",
    "n_full_compiles", "n_incr_compiles", "n_mso_compiles",
    "n_fleet_compiles", "retraces"})

FLEET_SAMPLER_KEYS = (EVAL_ENGINE_KEYS | FLEET_ENGINE_KEYS
                      | frozenset({"n_degraded"}))

SERVICE_KEYS = frozenset({
    "svc_rung", "svc_queue_depth", "svc_completed", "svc_shed",
    "svc_deadline_miss", "svc_rejected", "svc_retries",
    "svc_rung_changes", "svc_watchdog_alarms", "svc_p99_s",
    "svc_tenants"})

TENANT_KEYS = frozenset({
    "weight", "queue", "submitted", "served", "shed", "deadline_miss",
    "rejected", "bad_tells", "retries", "degraded", "is_shed"})

SNAPSHOT_SCHEMAS: Dict[str, Dict[str, frozenset]] = {
    "eval_engine": {"required": EVAL_ENGINE_KEYS,
                    "optional": frozenset()},
    "ask_engine": {"required": ASK_ENGINE_KEYS,
                   "optional": frozenset()},
    "fleet_engine": {"required": FLEET_ENGINE_KEYS,
                     "optional": frozenset()},
    # journal_seq appears iff the plane is journaled
    "fleet_sampler": {"required": FLEET_SAMPLER_KEYS,
                      "optional": frozenset({"journal_seq"})},
    "bo_service": {"required": FLEET_SAMPLER_KEYS | SERVICE_KEYS,
                   "optional": frozenset({"journal_seq"})},
}


def validate_snapshot(component: str, snap: Mapping[str, Any]
                      ) -> List[str]:
    """Structural check of a ``stats_snapshot()`` dict against the
    documented schema.  Returns a list of error strings (empty = valid):
    missing keys, unexpected keys, malformed ``retraces`` / tenant
    sub-blocks."""
    schema = SNAPSHOT_SCHEMAS.get(component)
    if schema is None:
        return [f"unknown component {component!r} "
                f"(know {sorted(SNAPSHOT_SCHEMAS)})"]
    errors: List[str] = []
    keys = set(snap.keys())
    missing = schema["required"] - keys
    extra = keys - schema["required"] - schema["optional"]
    if missing:
        errors.append(f"{component}: missing keys {sorted(missing)}")
    if extra:
        errors.append(f"{component}: unexpected keys {sorted(extra)}")
    rt = snap.get("retraces")
    if "retraces" in schema["required"] and isinstance(rt, Mapping):
        if set(rt.keys()) != RETRACES_KEYS:
            errors.append(f"{component}: retraces keys "
                          f"{sorted(rt.keys())} != {sorted(RETRACES_KEYS)}")
    elif "retraces" in schema["required"] and rt is not None:
        errors.append(f"{component}: retraces is {type(rt).__name__}, "
                      f"expected mapping")
    tenants = snap.get("svc_tenants")
    if "svc_tenants" in keys and isinstance(tenants, Mapping):
        for name, t in tenants.items():
            tk = set(t.keys())
            if tk != TENANT_KEYS:
                errors.append(
                    f"{component}: tenant {name!r} keys differ: "
                    f"missing {sorted(TENANT_KEYS - tk)}, "
                    f"extra {sorted(tk - TENANT_KEYS)}")
    return errors
