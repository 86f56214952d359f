"""The server's request-stage histograms, read over the window.

The server keeps each stage of a request (``cmd_wait_hist``,
``queue_wait_hist``, ``admit_hist``) as cumulative counts in log-spaced
buckets whose upper edges are ``hist_bounds_s``. The window's counts are
the end's less the start's, summed over a fleet's ``per_replica`` where
present; a percentile interpolates linearly inside its bucket. A server
that keeps no such histogram reads as nothing.
"""
from __future__ import annotations

from typing import Dict, List, Optional


def _summed(stats: Dict, key: str) -> Optional[List[int]]:
    per = [p for p in (stats.get("per_replica") or [stats]) if p]
    hists = [p[key] for p in per if key in p]
    if not hists:
        return None
    return [sum(col) for col in zip(*hists)]


def window_counts(run, key: str) -> Optional[List[int]]:
    """Bucket counts of stage ``key`` observed inside the window."""
    a = _summed(run.stats["start"], key)
    b = _summed(run.stats["end"], key)
    if a is None or b is None:
        return None
    return [y - x for x, y in zip(a, b)]


def quantile(counts: List[int], bounds: List[float], q: float) -> float:
    """The ``q``-quantile (0-1) of bucketed ``counts``: bucket ``i`` holds
    (``bounds[i - 1]``, ``bounds[i]``], the first from 0 and the last
    above ``bounds[-1]`` (read as that edge)."""
    target = q * sum(counts)
    cum = 0
    for i, c in enumerate(counts):
        if c and cum + c >= target:
            lo = bounds[i - 1] if i > 0 else 0.0
            hi = bounds[min(i, len(bounds) - 1)]
            return lo + (hi - lo) * (target - cum) / c
        cum += c
    return bounds[-1]


def percentile_ms(run, key: str, pct: float = 95) -> Optional[float]:
    """The window's ``pct``-th percentile of stage ``key`` in ms; None when
    the server keeps no such stage or the window saw none."""
    counts = window_counts(run, key)
    if counts is None or sum(counts) <= 0:
        return None
    per = run.stats["end"].get("per_replica") or [run.stats["end"]]
    bounds = next(p["hist_bounds_s"] for p in per if p)
    return 1e3 * quantile(counts, bounds, pct / 100)
