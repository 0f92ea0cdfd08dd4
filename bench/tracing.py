"""From profiler traces and host spans to the numbers the benchmark reports.

Three inputs, one clock:

- the JAX profiler's trace of a short steady window, reduced by
  :func:`extract` to plain lists of ``[name, start_ns, dur_ns, module]``
  per plane and line (the form the test fixture is recorded in);
- the program's own host spans (``repro.obs`` Chrome-trace events, ``ts``
  and ``dur`` in microseconds from the tracer's creation);
- the harness's own spans, recorded on the same tracer clock.

The harness marks one moment on both clocks (a ``TraceAnnotation`` named
``SYNC`` opened at a known tracer time), which maps host spans onto the
trace's nanoseconds.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

SYNC = "bench.sync"
# device planes' line that holds one event per executed operation;
# "XLA Modules" (one per program) stands in where a backend has no ops
OP_LINES = ("XLA Ops", "XLA Modules")

Interval = Tuple[float, float]


# --------------------------------------------------------------- extract
def extract(log_dir: str) -> Dict:
    """Read the newest ``*.xplane.pb`` under ``log_dir`` into plain data:
    ``{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
    dur_ns, module], ...]}]}]}``.  Host lines keep only the harness's
    annotations, device planes their op and program lines.  The program
    (``module``) is left empty: the TPU's op events do not carry it, and
    :func:`top_device_ops` takes it from the enclosing program event."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    planes = []
    for plane in pd.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if device and line.name not in OP_LINES:
                continue
            # a float64-emulated step runs ~650k device ops a second:
            # keep this loop to the three fields
            evs = [[e.name, e.start_ns, e.duration_ns, ""]
                   for e in line.events
                   if device or e.name.startswith("bench.")]
            if evs:
                lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# ----------------------------------------------------------- intervals
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping intervals; sorted, disjoint."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def total(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def device_planes(trace: Dict) -> List[Dict]:
    return [p for p in trace["planes"] if p["name"].startswith("/device:")]


def _op_events(plane: Dict) -> List[list]:
    by_name = {ln["name"]: ln["events"] for ln in plane["lines"]}
    for name in OP_LINES:
        if by_name.get(name):
            return by_name[name]
    return []


def device_busy(trace: Dict, lo: float, hi: float) -> Optional[float]:
    """Seconds in ``[lo, hi]`` (ns) in which an operation ran, averaged
    over the device planes; None where the trace has no device ops."""
    planes = [p for p in device_planes(trace) if _op_events(p)]
    if not planes:
        return None
    busy = [total(clip(union((e[1], e[1] + e[2]) for e in _op_events(p)),
                       lo, hi)) for p in planes]
    return 1e-9 * sum(busy) / len(busy)


def _short(op: str) -> str:
    """``%fusion.12 = f32[64]{0} fusion(...)`` -> ``fusion.12``."""
    return op.split(" = ", 1)[0].lstrip("%")


def top_device_ops(trace: Dict, lo: float, hi: float, k: int = 10
                   ) -> List[list]:
    """The ``k`` device operations that took the most time in the window,
    summed over calls and averaged over devices: ``[[name, seconds]]``,
    named ``<program>/<op>``, the program taken from the op's own stats or
    else from the "XLA Modules" event that encloses it."""
    planes = [p for p in device_planes(trace) if _op_events(p)]
    acc: Dict[str, float] = defaultdict(float)
    for p in planes:
        mods = sorted((e[1], e[1] + e[2], e[0]) for ln in p["lines"]
                      if ln["name"] == "XLA Modules" for e in ln["events"])
        starts = [m[0] for m in mods]
        for name, s, d, module in _op_events(p):
            t = total(clip([(s, s + d)], lo, hi))
            if t <= 0:
                continue
            if not module:
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and mods[i][1] >= s + d:
                    module = mods[i][2]
            op = _short(name)
            acc[f"{module}/{op}" if module else op] += t
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, 1e-9 * t / len(planes)] for n, t in ranked]


def sync_offset_ns(trace: Dict, sync_us: float) -> Optional[float]:
    """Trace time minus host-tracer time, in ns, from the SYNC mark that
    opened at tracer time ``sync_us``."""
    for p in trace["planes"]:
        for ln in p["lines"]:
            for e in ln["events"]:
                if e[0] == SYNC:
                    return e[1] - 1e3 * sync_us
    return None


def host_segments(spans: Sequence[Dict], offset_ns: float
                  ) -> List[Tuple[float, float, str]]:
    """The host timeline in trace ns, cut at every span boundary, each
    piece named by the innermost (shortest) complete span open over it;
    pieces with no span open are left out."""
    host = [(1e3 * sp["ts"] + offset_ns,
             1e3 * (sp["ts"] + sp["dur"]) + offset_ns, sp["name"])
            for sp in spans if sp.get("ph") == "X" and sp["dur"] > 0]
    bounds = sorted({x for h in host for x in h[:2]})
    starts = sorted(host)
    out, active, i = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(starts) and starts[i][0] <= a:
            active.append(starts[i])
            i += 1
        active = [h for h in active if h[1] > a]
        if active:
            inner = min(active, key=lambda h: h[1] - h[0])
            out.append((a, b, inner[2]))
    return out


def idle_gaps(trace: Dict, spans: Sequence[Dict], offset_ns: float,
              lo: float, hi: float, k: int = 10) -> List[list]:
    """Device-idle time in ``[lo, hi]`` named by what the host was doing:
    the idle stretches of the first device plane, charged piece by piece
    to the innermost host span open over them (``"host: no span"`` where
    none is).  Returns the ``k`` names with most idle seconds,
    ``[[name, seconds]]``."""
    planes = [p for p in device_planes(trace) if _op_events(p)]
    if not planes:
        return []
    busy = clip(union((e[1], e[1] + e[2]) for e in _op_events(planes[0])),
                lo, hi)
    idle, t = [], lo
    for s, e in busy:
        if s > t:
            idle.append((t, s))
        t = max(t, e)
    if t < hi:
        idle.append((t, hi))
    segs = host_segments(spans, offset_ns)
    acc: Dict[str, float] = defaultdict(float)
    j = 0
    for s, e in idle:
        covered = 0.0
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        m = j
        while m < len(segs) and segs[m][0] < e:
            a, b = max(segs[m][0], s), min(segs[m][1], e)
            if b > a:
                acc[segs[m][2]] += b - a
                covered += b - a
            m += 1
        acc["host: no span"] += (e - s) - covered
    ranked = sorted(((n, v) for n, v in acc.items() if v > 0),
                    key=lambda kv: -kv[1])[:k]
    return [[n, 1e-9 * v] for n, v in ranked]


# -------------------------------------------------------- span arithmetic
def spans_named(spans: Sequence[Dict], prefix: str) -> List[Interval]:
    """``(start_us, end_us)`` of the complete spans whose name is
    ``prefix`` or starts with ``prefix + "."``."""
    return [(sp["ts"], sp["ts"] + sp["dur"]) for sp in spans
            if sp.get("ph") == "X" and (sp["name"] == prefix
                                        or sp["name"].startswith(prefix
                                                                 + "."))]


def self_time(parents: Sequence[Interval], children: Sequence[Interval]
              ) -> float:
    """Summed parent time not covered by any child: the parents' self
    time, in the spans' unit."""
    kids = union(children)
    return sum((e - s) - total(clip(kids, s, e)) for s, e in parents)
