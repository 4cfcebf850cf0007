"""Percentiles and the window's end-to-end arithmetic, from the harness's
own timestamps (seconds from the window's opening)."""

from __future__ import annotations

import math


def percentile(values: list[float], q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation between
    closest ranks (numpy's default); NaN for no values."""
    if not values:
        return math.nan
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def ttft(reqs, seconds: float) -> list[float]:
    """Time to first token of every request due in the window, from its
    due time.  One with no token by the window's end enters at
    ``seconds - due``, so a stall cannot hide."""
    out = []
    for r in reqs:
        if not 0.0 <= r.due < seconds:
            continue
        first = r.token_t[0] if r.token_t else math.inf
        out.append((first if first <= seconds else seconds) - r.due)
    return out


def gaps(reqs, seconds: float) -> list[float]:
    """Every gap between consecutive output tokens of one request, both
    tokens inside the window."""
    out = []
    for r in reqs:
        t = [x for x in r.token_t if 0.0 <= x <= seconds]
        out.extend(b - a for a, b in zip(t, t[1:]))
    return out


def tokens_in(reqs, seconds: float) -> int:
    return sum(1 for r in reqs for x in r.token_t if 0.0 <= x <= seconds)
