"""Readers over the program's counters, taken as differences across the window."""

from __future__ import annotations

from typing import Optional


def ratio_pct(obs: dict, numerator: str, denominator: str) -> Optional[float]:
    c = obs.get("counters", {})
    if not c.get(denominator):
        return None
    return 100.0 * c[numerator] / c[denominator]


def window_ms_per_count(obs: dict, counter: str) -> Optional[float]:
    """Window milliseconds per count: the mean period of the counted event."""
    n = obs.get("counters", {}).get(counter)
    if not n:
        return None
    return 1e3 * obs["window_s"] / n
