"""Readers over the harness's own host timers."""

from __future__ import annotations

from typing import Optional


def mean_ms(obs: dict, seconds: str, count: str) -> Optional[float]:
    h = obs.get("host", {})
    if not h.get(count):
        return None
    return 1e3 * h[seconds] / h[count]
