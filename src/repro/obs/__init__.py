"""Unified telemetry plane: tracing, snapshot schemas, and trace export.

Three host-only modules threaded through every engine layer (EvalEngine
→ AskEngine → FleetEngine → FleetSampler → BOService):

* :mod:`repro.obs.trace` — process-global span tracer (ring-buffered
  spans + instants, zero-cost no-op when disabled) and the
  ``ProgramTimer`` block-until-ready wrapper for device programs;
* :mod:`repro.obs.metrics` — the documented ``stats_snapshot()``
  schema contract (``SNAPSHOT_SCHEMAS``, ``validate_snapshot``);
* :mod:`repro.obs.export` — Chrome-trace/Perfetto JSON from live
  traces, timeline reconstruction from WAL journals, and the
  per-phase latency breakdowns the BENCH writers embed.

Contract (ROADMAP invariant): all obs state is host-side, off by
default, and enabling it never changes what XLA compiles.

``export`` is intentionally not imported here — it pulls in
``repro.bo.journal``; import it explicitly (``from repro.obs import
export``) where needed so ``repro.obs.trace`` stays importable from the
lowest engine layers without cycles.
"""
from repro.obs import metrics, trace  # noqa: F401
from repro.obs.metrics import (SNAPSHOT_SCHEMAS,  # noqa: F401
                               validate_snapshot)
from repro.obs.trace import (ProgramTimer, Tracer, disable,  # noqa: F401
                             enable, enabled, get, instant, span)
