"""A share read off two attributes of the program's own spans."""

from __future__ import annotations

from typing import Optional

from benchmark.readers import spans


def mean_share_pct(obs: dict, span: str, part: str, whole: str) -> Optional[float]:
    """Mean over the captured ``span`` spans of ``part / whole`` (two of the
    span's attributes), in percent; nothing where no span carries both."""
    shares = [
        s["attrs"][part] / s["attrs"][whole]
        for s in spans.captured()
        if s["name"] == span and s.get("attrs", {}).get(whole) and part in s["attrs"]
    ]
    return 100.0 * sum(shares) / len(shares) if shares else None
