"""Whole-window arithmetic over the client's own records.

Exact percentiles (linear interpolation between order statistics, as
``numpy.percentile``'s default), the fair-share reference and Jain's index.
``max_min_fair`` and ``jain_index`` are copies of the program's
``repro.control.congestion.max_min_fair`` and
``repro.serve.multiplex.jain_index``, kept here so that the yardstick does
not move with the program.
"""
from __future__ import annotations

import math
from typing import Dict, Mapping, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100) of ``values``; None when empty."""
    xs = sorted(float(v) for v in values)
    if not xs:
        return None
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def max_min_fair(capacity: float, demands: Mapping[int, float],
                 weights: Optional[Mapping[int, float]] = None
                 ) -> Dict[int, float]:
    """Weighted max-min fair allocation by progressive filling: tenants
    whose demand is below their weighted share are satisfied, and what
    they leave is divided again among the rest."""
    if capacity <= 0 or not demands:
        return {t: 0.0 for t in demands}
    w = {t: (weights.get(t, 1.0) if weights else 1.0) for t in demands}
    alloc = {t: 0.0 for t in demands}
    active = {t for t, d in demands.items() if d > 0 and w[t] > 0}
    remaining = float(capacity)
    wsum = sum(w[t] for t in active)
    while active and remaining > 1e-12 and wsum > 1e-300:
        share = remaining / wsum
        satisfied = {t for t in active if demands[t] <= w[t] * share + 1e-12}
        if not satisfied:
            for t in active:
                alloc[t] += w[t] * share
            break
        for t in satisfied:
            alloc[t] = float(demands[t])
            remaining -= demands[t]
            wsum -= w[t]
        active -= satisfied
    return alloc


def jain_index(xs: Sequence[float]) -> float:
    """Jain's fairness index (sum x)^2 / (n sum x^2): 1 is equal, 1/n one
    hog. Non-finite entries count as 0; an empty or all-zero vector is 1."""
    v = [x if math.isfinite(x) else 0.0 for x in xs]
    sq = sum(x * x for x in v)
    if not v or sq == 0.0:
        return 1.0
    return sum(v) ** 2 / (len(v) * sq)


def fair_jain(served: Mapping[int, float], demands: Mapping[int, float],
              weights: Mapping[int, float]) -> Optional[float]:
    """Jain's index over tenants of served_i / fair_i, where fair is the
    weighted max-min share of what was served in all, given what each
    tenant offered. Tenants with no fair share are left out."""
    capacity = sum(served.values())
    fair = max_min_fair(capacity, dict(demands), dict(weights))
    xs = [served.get(t, 0.0) / f for t, f in fair.items() if f > 0]
    return jain_index(xs) if xs else None


def ttft_samples(requests, window_start: float, window_end: float,
                 tenants) -> list:
    """Time to first token of each request due inside the window from
    ``tenants``: first token's delivery minus the due time. A request with
    no first token at the close counts at its age at the close."""
    out = []
    for r in requests:
        if r.tenant not in tenants or not window_start <= r.due < window_end:
            continue
        first = r.token_times[0] if r.token_times else None
        if first is None or first > window_end:
            out.append(window_end - r.due)
        else:
            out.append(first - r.due)
    return out


def queue_wait_samples(requests, window_start: float, window_end: float,
                       tenants) -> list:
    """Due time to the return of the scheduler call that picked the
    request, over the same requests as ``ttft_samples``; a request not
    picked by the close counts at its age at the close."""
    out = []
    for r in requests:
        if r.tenant not in tenants or not window_start <= r.due < window_end:
            continue
        if r.picked is None or r.picked > window_end:
            out.append(window_end - r.due)
        else:
            out.append(r.picked - r.due)
    return out


def itl_samples(requests, window_start: float, window_end: float) -> list:
    """Gaps between consecutive delivered tokens of one request, over every
    gap that ends inside the window."""
    out = []
    for r in requests:
        ts = r.token_times
        for a, b in zip(ts, ts[1:]):
            if window_start <= b < window_end:
                out.append(b - a)
    return out


def tokens_in_window(requests, window_start: float,
                     window_end: float) -> int:
    """Generated tokens delivered inside the window, all tenants."""
    return sum(1 for r in requests for t in r.token_times
               if window_start <= t < window_end)
