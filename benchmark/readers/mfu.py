"""The whole step's share of the chip's peak: required FLOPs (``flops.py``,
counted by the driver over the work the window completed) over window seconds
over chips x peak FLOP/s."""

from __future__ import annotations

from typing import Optional


def share_of_peak_pct(obs: dict) -> Optional[float]:
    work = obs.get("work", {}).get("required_flops")
    if not work or not obs.get("window_s"):
        return None
    return 100.0 * work / obs["window_s"] / (obs["chips"] * obs["peak"]["flops_per_s"])
