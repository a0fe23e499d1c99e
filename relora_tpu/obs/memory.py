"""HBM accounting: static memory plans, per-pytree byte breakdowns, and a
cadence-gated live ``memory_stats`` poller.

Three views of device memory, from cheapest to most detailed:

- :func:`pytree_bytes` / :func:`pytree_breakdown` — pure metadata sums over a
  pytree's leaf shapes (concrete arrays or ``ShapeDtypeStruct``): what the
  *resident state* (params / opt_state / KV cache) occupies.  No device work.
- :func:`xla_memory_plan` / :func:`plan_for` — XLA's own static plan for one
  compiled program (``compiled.memory_analysis()``): argument / output / temp
  / donated-alias bytes.  ``plan_for`` lowers **and compiles** — an AOT
  compile does NOT warm the traced-call jit cache on this jax, so callers
  gate it (the trainer honors ``RELORA_TPU_MEM_PLAN=0``).
- :func:`live_memory_stats` / :class:`MemoryPoller` — the allocator's live
  and peak gauges.  ``device.memory_stats()`` returns None on the CPU
  backend; the normalized schema keeps ``available: False`` there so CPU and
  TPU runs share one code path.

Everything imports jax lazily, keeping ``relora_tpu.obs`` import-light.  The
module is registered hot (analysis/hotpaths.py): nothing here may sync the
host on device *values* — ``memory_stats()`` is an allocator-metadata read,
not a computation fence, and even so the poller is only ever called at the
metrics cadence (the trainer's flush), never per step.
"""

from __future__ import annotations

import math
import re
from typing import Any, Dict, Mapping, Optional

__all__ = [
    "pytree_bytes",
    "pytree_breakdown",
    "xla_memory_plan",
    "plan_for",
    "live_memory_stats",
    "hbm_peak_gb",
    "reconcile",
    "placement",
    "collective_counts",
    "MemoryPoller",
]


def _leaf_nbytes(leaf: Any) -> int:
    """Bytes of one leaf: concrete arrays via ``.nbytes``, abstract leaves
    (ShapeDtypeStruct) via shape x itemsize, non-array leaves count zero."""
    nbytes = getattr(leaf, "nbytes", None)
    if nbytes is not None:
        return int(nbytes)
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        return 0
    itemsize = getattr(dtype, "itemsize", None)
    if itemsize is None:
        import numpy as np

        itemsize = np.dtype(dtype).itemsize
    # shape/itemsize are python metadata, not device values — no sync here
    return int(math.prod(shape)) * int(itemsize)  # noqa: RTL202


def pytree_bytes(tree: Any) -> int:
    """Total bytes of a pytree's array leaves (concrete or abstract)."""
    import jax

    return sum(_leaf_nbytes(leaf) for leaf in jax.tree_util.tree_leaves(tree))


def pytree_breakdown(named: Mapping[str, Any]) -> Dict[str, int]:
    """``{"params": tree, "opt_state": tree, ...}`` -> flat byte counts per
    group plus ``total_bytes`` — the per-pytree HBM plan the trainer emits
    as a ``memory_plan`` event into metrics.jsonl."""
    out: Dict[str, int] = {}
    total = 0
    for name, tree in named.items():
        b = pytree_bytes(tree)
        out[f"{name}_bytes"] = b
        total += b
    out["total_bytes"] = total
    return out


#: CompiledMemoryStats fields worth surfacing; the serialized HLO proto blob
#: and pjrt-internal extras are deliberately excluded
_PLAN_FIELDS = (
    "generated_code_size_in_bytes",
    "argument_size_in_bytes",
    "output_size_in_bytes",
    "alias_size_in_bytes",
    "temp_size_in_bytes",
    "host_generated_code_size_in_bytes",
    "host_argument_size_in_bytes",
    "host_output_size_in_bytes",
    "host_alias_size_in_bytes",
    "host_temp_size_in_bytes",
)


def xla_memory_plan(compiled: Any) -> Optional[Dict[str, int]]:
    """Normalize ``compiled.memory_analysis()`` into a plain dict.

    Keys drop the ``_size_in_bytes`` suffix (``argument_bytes``,
    ``temp_bytes``, ...).  ``plan_total_bytes`` is the static residency
    estimate: arguments + outputs + temporaries + generated code, minus the
    alias bytes that donation lets outputs share with inputs.  Returns None
    when the backend offers no analysis.
    """
    try:
        stats = compiled.memory_analysis()
    except Exception:
        return None
    if stats is None:
        return None
    out: Dict[str, int] = {}
    for field in _PLAN_FIELDS:
        value = getattr(stats, field, None)
        if isinstance(value, int) and (value != 0 or not field.startswith("host_")):
            out[field[: -len("_size_in_bytes")] + "_bytes"] = value
    if not out:
        return None
    out["plan_total_bytes"] = max(
        0,
        out.get("argument_bytes", 0)
        + out.get("output_bytes", 0)
        + out.get("temp_bytes", 0)
        + out.get("generated_code_bytes", 0)
        - out.get("alias_bytes", 0),
    )
    return out


def plan_for(jitted_fn: Any, *args: Any, **kwargs: Any) -> Dict[str, Any]:
    """Static memory plan of one jitted entry point: ``lower(...).compile()``
    then :func:`xla_memory_plan`.  Arguments may be concrete arrays or
    ``jax.ShapeDtypeStruct`` — mixing is fine.

    NOTE: the AOT compile this performs does not populate the traced-call
    cache, so the first real call still pays its own compile.  Call it where
    a duplicate compile is acceptable (startup, tests, reports) and gate it
    for large models.  Never raises: failures come back as ``{"error": ...}``.
    """
    try:
        compiled = jitted_fn.lower(*args, **kwargs).compile()
    except Exception as e:  # backend-specific; a plan must never fail the run
        return {"error": f"{type(e).__name__}: {e}"}
    return xla_memory_plan(compiled) or {"error": "memory_analysis unavailable"}


def live_memory_stats(device: Any = None) -> Dict[str, Any]:
    """Allocator live/peak gauges in one schema for every backend.

    TPU/GPU backends report ``bytes_in_use`` / ``peak_bytes_in_use`` /
    ``bytes_limit``; the CPU backend's ``memory_stats()`` is None, which
    comes back as ``available: False`` with None values — callers never
    branch on the backend, only on the fields.
    """
    stats = None
    try:
        import jax

        if device is None:
            device = jax.devices()[0]
        stats = device.memory_stats()
    except Exception:
        stats = None
    out: Dict[str, Any] = {
        "available": stats is not None,
        "bytes_in_use": None,
        "peak_bytes_in_use": None,
        "bytes_limit": None,
    }
    if stats:
        for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            value = stats.get(key)
            if value is not None:
                out[key] = int(value)
    return out


def hbm_peak_gb(device: Any = None) -> Optional[float]:
    """Peak allocator bytes in GB, or None where the backend keeps no stats
    (CPU) — the single code path behind the ``hbm_peak_gb`` BENCH field."""
    peak = live_memory_stats(device).get("peak_bytes_in_use")
    return round(peak / 1e9, 2) if peak is not None else None


def reconcile(plan_total_bytes: Optional[int], live: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Plan-vs-actual: how much of the static plan the allocator's peak
    confirms.  ``live_vs_plan`` > 1 means the plan undercounts (fragmentation,
    other programs resident); None when either side is unknown."""
    if live is None:
        live = live_memory_stats()
    peak = live.get("peak_bytes_in_use")
    out: Dict[str, Any] = {
        "plan_total_bytes": plan_total_bytes,
        "live_peak_bytes": peak,
        "live_vs_plan": None,
    }
    if plan_total_bytes and peak:
        out["live_vs_plan"] = round(peak / plan_total_bytes, 4)
    return out


def placement(params: Any, batch: Any) -> Dict[str, Any]:
    """Which devices really hold the parameters and the batch, as aligned
    lists over the local devices (lists, not dicts: a metrics event keeps
    them as they are): ``device_ids``; ``param_bytes`` of parameter shards on
    each (a replicated leaf counts in full on every device that holds it);
    each allocator's ``bytes_in_use``; and ``batch_devices``, the ids the
    batch is laid out over.  A mesh that was meant to spread the model and
    put everything on the first device shows here."""
    import jax

    devices = jax.local_devices()
    by_device = {d.id: 0 for d in devices}
    for leaf in jax.tree_util.tree_leaves(params):
        for shard in getattr(leaf, "addressable_shards", ()):
            by_device[shard.device.id] += shard.data.nbytes  # host metadata, no device read
    return {
        "device_ids": [d.id for d in devices],
        "param_bytes": [by_device[d.id] for d in devices],
        "bytes_in_use": [live_memory_stats(d)["bytes_in_use"] for d in devices],
        "batch_devices": sorted(d.id for d in batch.sharding.device_set),
    }


_COLLECTIVE_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)(?:-start)?\("
)


def collective_counts(hlo_text: str) -> list:
    """Collective instructions in a compiled program's text as sorted
    ``[kind, count]`` pairs (an async pair counts once, at its ``-start``)."""
    counts: Dict[str, int] = {}
    for kind in _COLLECTIVE_RE.findall(hlo_text):
        counts[kind] = counts.get(kind, 0) + 1
    return [[kind, n] for kind, n in sorted(counts.items())]


class MemoryPoller:
    """Cadence-gated live-memory gauges.

    ``poll()`` reads the allocator stats once and mirrors them into a
    :class:`~relora_tpu.obs.metrics.MetricsRegistry` as ``hbm_*`` gauges.
    It must only be called at the metrics cadence (the trainer calls it from
    the ``log_every`` flush) — never inside the per-step hot loop, where even
    an allocator-metadata read per step is wasted host time.
    """

    def __init__(self, registry: Any = None, device: Any = None):
        self.registry = registry
        self.device = device
        self.last: Optional[Dict[str, Any]] = None

    def poll(self) -> Dict[str, Any]:
        stats = live_memory_stats(self.device)
        self.last = stats
        if self.registry is not None and stats["available"]:
            for key in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
                value = stats.get(key)
                if value is not None:
                    self.registry.set_gauge(f"hbm_{key}", float(value))
        return stats
