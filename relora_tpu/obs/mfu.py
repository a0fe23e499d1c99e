"""Peak-FLOPs detection and live MFU from compiled-step cost analysis.

The trainer's **live MFU gauge** reads this module: per-update MFU computed
from the actual FLOPs XLA reports for the compiled train step
(``lower(...).cost_analysis()['flops']``), falling back to the 6ND
approximation when cost analysis is unavailable.  cost_analysis counts what
the program *really* does — attention scores, remat recomputation, LoRA
factor matmuls — where 6ND is a dense-transformer estimate, so the two can
legitimately differ by tens of percent under remat.

Peak-FLOPs resolution: on platform ``tpu`` a ``device_kind`` substring match
against :data:`PEAK_FLOPS_BY_KIND` and nothing else — a TPU the table has
never heard of is an error, not a default.  Elsewhere (the CPU backend in
tests, GPUs): ``RELORA_TPU_PEAK_FLOPS``, then the table, then the v5e default.
"""

from __future__ import annotations

import os
from typing import Any, Optional

__all__ = [
    "PEAK_FLOPS_BY_KIND",
    "PEAK_FLOPS_DEFAULT",
    "peak_flops",
    "step_flops_from_cost_analysis",
]

#: bf16 peak FLOPs/s of one chip, keyed by a lowercase substring of
#: ``jax.devices()[0].device_kind``.  Order matters: first match wins, so
#: longer / more specific kinds come before their prefixes (v5e before v5,
#: v6e before v6).  A v5e chip reports ``"TPU v5 lite"``.
PEAK_FLOPS_BY_KIND = (
    ("v6e", 918e12),        # Trillium
    ("v5p", 459e12),
    ("v5 lite", 197e12),    # what a v5e chip reports (Google Cloud "TPU v5e")
    ("v5e", 197e12),
    ("v5litepod", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
    ("h100", 989e12),       # dense bf16, SXM
    ("a100", 312e12),
)

#: one TPU v5e chip — used off-TPU when the device kind is unrecognized,
#: e.g. the CPU backend in tests
PEAK_FLOPS_DEFAULT = 197e12


def peak_flops(device: Optional[Any] = None) -> float:
    """Peak bf16 FLOPs/s for ``device`` (default: ``jax.devices()[0]``).

    A TPU must be in the table: an unknown TPU kind raises.  Off-TPU,
    ``RELORA_TPU_PEAK_FLOPS`` overrides the table and an unknown kind gets
    :data:`PEAK_FLOPS_DEFAULT`.
    """
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = str(getattr(device, "device_kind", "")).lower()
    on_tpu = getattr(device, "platform", "") == "tpu"
    env = os.environ.get("RELORA_TPU_PEAK_FLOPS")
    if env and not on_tpu:
        return float(env)
    for needle, flops in PEAK_FLOPS_BY_KIND:
        if needle in kind:
            return flops
    if on_tpu:
        raise ValueError(
            f"no peak FLOP/s on record for TPU device_kind {device.device_kind!r}; "
            "add it to relora_tpu.obs.mfu.PEAK_FLOPS_BY_KIND with its source"
        )
    return PEAK_FLOPS_DEFAULT


def step_flops_from_cost_analysis(cost: Any) -> Optional[float]:
    """Total FLOPs from ``lowered.cost_analysis()`` (a dict on the installed
    JAX).  Returns None when there is no positive 'flops' entry (some
    backends report nothing), signalling the caller to fall back to 6ND.
    """
    if not isinstance(cost, dict):
        return None
    try:
        total = float(cost.get("flops", 0.0))
    except (TypeError, ValueError):
        return None
    return total if total > 0 else None
