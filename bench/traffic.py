"""The general traffic generator: a mix file's parameters to a schedule.

A mix is a JSON file under ``bench/traffic/``.  Its ``loop`` says how
asks arrive:

- ``"closed"``: every study has one worker.  A worker that gets its
  suggestion evaluates it, tells the value back and asks again at once,
  so every study always has one ask in flight.  Parameters: none.
- ``"open"``: independent arrivals on a schedule drawn up front from the
  seed (:func:`poisson_arrivals`), whatever the service's speed.
  Parameters: ``rate_per_s`` (all tenants together) and ``tenant_shares``
  (``"weights"`` to split it by tenant weight, or ``"zipf"`` with
  ``zipf_s``).  No cell runs an open mix yet.

An ask is due when its worker issues it (closed) or at its scheduled
arrival (open); its latency runs from then to its suggestion.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

LOOPS = ("closed", "open")


def validate(mix: Dict) -> Dict:
    if mix.get("loop") not in LOOPS:
        raise ValueError(f"traffic mix: loop must be one of {LOOPS}, got "
                         f"{mix.get('loop')!r}")
    if mix["loop"] == "open":
        if not float(mix["rate_per_s"]) > 0.0:
            raise ValueError("traffic mix: rate_per_s must be > 0")
        if mix.get("tenant_shares", "weights") not in ("weights", "zipf"):
            raise ValueError("traffic mix: tenant_shares is 'weights' or "
                             "'zipf'")
    return mix


def tenant_rates(mix: Dict, weights: Sequence[float]) -> List[float]:
    """Split an open mix's total rate over the tenants."""
    if mix.get("tenant_shares", "weights") == "zipf":
        s = float(mix.get("zipf_s", 1.0))
        shares = np.arange(1, len(weights) + 1, dtype=float) ** -s
    else:
        shares = np.asarray(weights, float)
    shares = shares / shares.sum()
    return [float(mix["rate_per_s"]) * x for x in shares]


def poisson_arrivals(rates: Sequence[float],
                     studies: Sequence[Sequence[int]], seconds: float,
                     rng: np.random.Generator
                     ) -> List[Tuple[float, int, int]]:
    """Open-loop Poisson schedule over ``seconds``: ``(t, tenant, study)``
    sorted by arrival time.  Tenant k arrives at ``rates[k]`` per second
    and cycles through its ``studies[k]``.  (After the repository's
    ``benchmarks/bo_serve.py``.)"""
    events = []
    for k, (rate, own) in enumerate(zip(rates, studies)):
        t, i = 0.0, 0
        while True:
            t += float(rng.exponential(1.0 / rate))
            if t >= seconds:
                break
            events.append((t, k, own[i % len(own)]))
            i += 1
    events.sort(key=lambda e: e[0])
    return events
