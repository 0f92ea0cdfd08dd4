"""The general traffic generator: a mix file's parameters to a schedule.

A mix is a JSON file under ``bench/traffic/``.  Its ``loop`` says how
asks arrive:

- ``"closed"``: every study has one worker.  A worker that gets its
  suggestion evaluates it, tells the value back and asks again at once,
  so every study always has one ask in flight.  Parameters: none.
- ``"open"``: independent arrivals on a schedule drawn up front from a
  seed (:func:`open_schedule`), whatever the service's speed.
  Parameters: ``rate_per_s`` (all tenants together), ``tenant_shares``
  (``"weights"`` to split it by tenant weight, or ``"zipf"`` with
  ``zipf_s``, the first tenant the heaviest) and ``arrivals``, the fixed
  number of asks the window serves.

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
        if not int(mix.get("arrivals", 0)) > 0:
            raise ValueError("traffic mix: arrivals must be > 0")
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


def open_schedule(mix: Dict, tenants: Sequence[Tuple[float, int]],
                  seed: int) -> List[Tuple[float, int, int]]:
    """The first ``arrivals`` events of an open mix over ``tenants``
    (``(weight, number of studies)`` each), drawn from ``seed``:
    ``(t, tenant, position)`` with ``t`` from the first arrival and
    ``position`` the index among the tenant's studies, which each tenant
    cycles through."""
    n = int(mix["arrivals"])
    # twice the expected span: a Poisson count of mean 2n falls short of
    # n with a chance below e^{-0.3 n}
    horizon = 2.0 * n / float(mix["rate_per_s"])
    events = poisson_arrivals(tenant_rates(mix, [w for w, _ in tenants]),
                              [range(c) for _, c in tenants], horizon,
                              np.random.default_rng(seed))
    if len(events) < n:
        raise ValueError(f"traffic mix: {len(events)} arrivals drawn in "
                         f"{horizon:g} s, fewer than the {n} asked for")
    t0 = events[0][0]
    return [(t - t0, k, i) for t, k, i in events[:n]]
