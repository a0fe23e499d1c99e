"""What every cell shares: finding the cell's files by name, the look for the
chip, the peaks, the count of compilations, percentiles, and the one result
line.  Nothing here knows a particular cell, configuration or metric."""

from __future__ import annotations

import importlib
import json
import os
import sys
import time
from typing import Any, Callable, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

# exit codes: 2 = the checkout holds no program, 3 = no chip (or too few)
EXIT_NO_PROGRAM, EXIT_NO_CHIP = 2, 3


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with the files its names point to."""

    def __init__(self, name: str, bench: Optional[dict] = None, base: str = BENCH_DIR, root: str = ROOT):
        bench = bench or load_benchmark()
        self.base = base
        entry = next((w for w in bench["workloads"] if w["name"] == name), None)
        if entry is None:
            raise SystemExit(f"BENCHMARK.json has no workload {name!r}")
        self.name = name
        self.chips = entry["chips"]
        self.bench = bench
        conf = next(c for c in bench["configs"] if c["name"] == entry["config"])
        self.config_name = conf["name"]
        self.config_file = os.path.join(root, conf["file"])
        with open(self.config_file) as f:
            self.config = json.load(f)
        self.workload = load_json(base, "workloads", f"{name}.json")
        self.traffic = load_json(base, "traffic", f"{entry['traffic']}.json")

    def metrics(self, group: str) -> list:
        """The cell's metrics of ``end_to_end`` or ``per_layer``: those with
        no ``workloads`` key, or with this cell in it."""
        return [m for m in self.bench[group] if self.name in m.get("workloads", [self.name])]


def program_present() -> bool:
    return os.path.isdir(os.path.join(ROOT, "relora_tpu")) and os.path.exists(os.path.join(ROOT, "main.py"))


def setup_jax_cache() -> str:
    """The persistent compilation cache, where the program itself puts it:
    ``JAX_COMPILATION_CACHE_DIR`` or ``.jax_compile_cache/`` in the checkout.
    The server's programs compile in under JAX's one-second threshold, so the
    threshold is dropped in this process."""
    from relora_tpu.utils.logging import enable_compile_cache

    where = enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def device_info(chips: int) -> dict:
    """The devices as JAX reports them; exits 3 unless they are ``chips``
    TPU chips."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu" or len(devs) != chips:
        print(
            f"the cell needs {chips} tpu chip(s); JAX found {len(devs)} x {d0.platform!r} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}): no result",
            file=sys.stderr,
        )
        raise SystemExit(EXIT_NO_CHIP)
    return {"platform": d0.platform, "kind": d0.device_kind, "count": len(devs)}


def peaks_for(kind: str) -> dict:
    table = load_json("peaks.json")
    if kind not in table:
        raise SystemExit(f"benchmark/peaks.json has no entry for device kind {kind!r}")
    return table[kind]


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device (0 where the backend does not say)."""
    import jax

    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileCounter:
    """Counts backend compilations through JAX's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_: Any) -> None:
        if event == self.EVENT:
            self.count += 1


def percentile(values: list, q: float) -> float:
    """Linear-interpolated percentile of a non-empty list (numpy's default)."""
    xs = sorted(values)
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def load_reader(spec: str) -> Callable:
    """``"trace.program_ms"`` -> ``benchmark.readers.trace.program_ms``."""
    module, fn = spec.rsplit(".", 1)
    return getattr(importlib.import_module(f"benchmark.readers.{module}"), fn)


def per_layer_metrics(cell: Cell, obs: dict) -> dict:
    """Every per-layer metric of the cell through its own reader.  A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in cell.metrics("per_layer"):
        spec = load_json(cell.base, "metrics", f"{m['name']}.json")
        value = load_reader(spec["reader"])(obs, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


class Check:
    """The numbers compared for ``correct``, each beside its limit."""

    def __init__(self):
        self.rows: list = []

    def add(self, name: str, value: float, limit: float) -> None:
        self.rows.append((name, float(value), float(limit)))

    @property
    def correct(self) -> bool:
        # a NaN fails: it is not <= anything
        return bool(self.rows) and all(v <= lim for _, v, lim in self.rows)

    def as_dict(self) -> dict:
        return {n: {"value": v, "limit": lim} for n, v, lim in self.rows}

    def print_stderr(self) -> None:
        for n, v, lim in self.rows:
            mark = "ok" if v <= lim else "OVER"
            print(f"compared {n} = {v:.6g} (limit {lim:.6g}) {mark}", file=sys.stderr)
        print(f"correct = {self.correct}", file=sys.stderr, flush=True)


def now() -> float:
    return time.perf_counter()
