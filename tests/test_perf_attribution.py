"""Performance attribution: compile/retrace telemetry, HBM accounting, the
MFU-gap waterfall, and the perf_report tool.

The contract under test (docs/observability.md): warmup compiles are tagged
expected and steady-state retraces are not; metrics.jsonl carries an
``mfu_gap`` breakdown whose shares sum to ~100%; memory plans come back in
one normalized schema on every backend.
"""

import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relora_tpu.obs.compile import CompileWatcher, abstract_signature, signature_diff
from relora_tpu.obs import memory as obs_memory

REPO = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# CompileWatcher unit behavior
# ---------------------------------------------------------------------------


def _counting_fn():
    calls = []

    def f(x):
        calls.append(x.shape)
        return x * 2

    return jax.jit(f), calls


def test_watcher_first_call_expected_then_warm_path():
    watcher = CompileWatcher(service="test")
    jitted, _ = _counting_fn()
    f = watcher.wrap("f", jitted)
    f(jnp.ones((4,)))
    f(jnp.ones((4,)))  # same signature: no new event
    events = watcher.compile_events()
    assert len(events) == 1
    assert events[0].expected and events[0].reason == "first_call"
    assert watcher.steady_state_retraces == 0


def test_watcher_shape_unstable_call_trips_retrace_counter():
    """The acceptance case: a deliberately shape-unstable toy step trips
    ``compile/steady_state_retraces`` while the warmup compile does not."""
    watcher = CompileWatcher(service="test")
    f = watcher.wrap("step", jax.jit(lambda x: x + 1))
    with watcher.expected_compiles("warmup"):
        f(jnp.ones((4,)))
    assert watcher.steady_state_retraces == 0
    f(jnp.ones((5,)))  # shape-unstable input after warmup
    assert watcher.steady_state_retraces == 1
    retrace = watcher.compile_events()[-1]
    assert not retrace.expected and retrace.reason == "steady_state"
    assert retrace.changed == ["leaf[0]: float32(4,) -> float32(5,)"]


def test_watcher_expected_compiles_reason_and_nesting():
    watcher = CompileWatcher(service="test")
    f = watcher.wrap("g", jax.jit(lambda x: x))
    f(jnp.ones((2,)))  # first_call
    with watcher.expected_compiles("memory_plan"):
        f(jnp.ones((3,)))
    assert watcher.steady_state_retraces == 0
    assert [e.reason for e in watcher.compile_events()] == ["first_call", "memory_plan"]
    summary = watcher.summary()
    assert summary["compiles"] == 2 and summary["by_fn"] == {"g": 2}


def test_watcher_counters_and_metrics_events(tmp_path):
    from relora_tpu.obs.metrics import MetricsRegistry
    from relora_tpu.utils.logging import MetricsLogger

    registry = MetricsRegistry()
    metrics = MetricsLogger(run_dir=str(tmp_path))
    watcher = CompileWatcher(service="test", registry=registry, metrics=metrics)
    f = watcher.wrap("h", jax.jit(lambda x: x))
    f(jnp.ones((2,)))
    f(jnp.ones((3,)))
    metrics.finish()
    assert registry.counter_value("compile_total", label=("fn", "h")) == 2
    assert registry.counter_value("compile_steady_state_retraces", label=("fn", "h")) == 1
    records = [
        json.loads(line)
        for line in (tmp_path / "metrics.jsonl").read_text().splitlines()
    ]
    compiles = [r for r in records if r.get("_event") == "compile"]
    assert [c["expected"] for c in compiles] == [True, False]
    assert compiles[1]["changed"]


def test_watcher_attribute_passthrough():
    watcher = CompileWatcher()
    f = watcher.wrap("f", jax.jit(lambda x: x * 2))
    # .lower must reach the jitted fn so plan_for works on wrapped functions
    compiled = f.lower(jax.ShapeDtypeStruct((4,), jnp.float32)).compile()
    assert compiled is not None


def test_abstract_signature_and_diff():
    _, sig_a = abstract_signature((jnp.ones((2, 3)), 7), {})
    _, sig_b = abstract_signature((jnp.ones((2, 4)), 7), {})
    assert sig_a[0] == "float32(2, 3)" and sig_a[1] == "7"
    assert signature_diff(sig_a, sig_b) == ["leaf[0]: float32(2, 3) -> float32(2, 4)"]
    assert signature_diff(None, sig_b) == []
    assert signature_diff(sig_a, sig_a) == ["<structure changed, leaf shapes identical>"]


# ---------------------------------------------------------------------------
# HBM accounting
# ---------------------------------------------------------------------------


def test_pytree_bytes_mixed_concrete_and_abstract():
    tree = {
        "a": jnp.ones((4, 4), jnp.float32),  # 64
        "b": jax.ShapeDtypeStruct((3,), jnp.int32),  # 12
        "c": None,  # 0
        "d": 5,  # scalar leaf with no shape: 0
    }
    assert obs_memory.pytree_bytes(tree) == 64 + 12
    breakdown = obs_memory.pytree_breakdown({"x": tree["a"], "y": tree["b"]})
    assert breakdown == {"x_bytes": 64, "y_bytes": 12, "total_bytes": 76}


def test_live_memory_stats_schema_on_cpu():
    stats = obs_memory.live_memory_stats()
    assert set(stats) == {"available", "bytes_in_use", "peak_bytes_in_use", "bytes_limit"}
    if not stats["available"]:  # CPU backend: no allocator stats, None values
        assert stats["bytes_in_use"] is None
        assert obs_memory.hbm_peak_gb() is None


def test_plan_for_reports_real_buffer_sizes():
    f = jax.jit(lambda a, b: a @ b)
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    plan = obs_memory.plan_for(f, x, x)
    assert "error" not in plan
    assert plan["argument_bytes"] == 2 * 64 * 64 * 4
    assert plan["output_bytes"] == 64 * 64 * 4
    assert plan["plan_total_bytes"] >= plan["output_bytes"]


def test_plan_for_never_raises():
    class Bad:
        def lower(self, *a, **k):
            raise RuntimeError("no lowering for you")

    plan = obs_memory.plan_for(Bad())
    assert plan == {"error": "RuntimeError: no lowering for you"}


def test_reconcile():
    out = obs_memory.reconcile(1000, live={"peak_bytes_in_use": 1500})
    assert out["live_vs_plan"] == 1.5
    assert obs_memory.reconcile(1000, live={"peak_bytes_in_use": None})["live_vs_plan"] is None
    assert obs_memory.reconcile(None, live={"peak_bytes_in_use": 5})["live_vs_plan"] is None


def test_memory_poller_sets_gauges_when_available():
    from relora_tpu.obs.metrics import MetricsRegistry

    registry = MetricsRegistry()
    poller = obs_memory.MemoryPoller(registry=registry)
    stats = poller.poll()
    assert poller.last is stats
    if stats["available"]:
        assert registry.gauge_value("hbm_bytes_in_use") > 0


# ---------------------------------------------------------------------------
# Trainer integration: waterfall + memory plan + zero retraces + perf_report
# ---------------------------------------------------------------------------


def test_trainer_emits_mfu_gap_and_memory_plan(tmp_path, monkeypatch):
    """An 8-step CPU run writes the full attribution record set, and
    ``tools/perf_report.py`` renders it with zero steady-state retraces."""
    from test_end_to_end import TINY, FakeTokens, make_cfg, make_iterators
    from relora_tpu.train.trainer import Trainer

    monkeypatch.setenv("RELORA_TPU_MEM_PLAN", "1")  # conftest defaults it off
    cfg = make_cfg(
        tmp_path, num_training_steps=8, log_every=4, eval_every=100, save_every=100
    )
    trainer = Trainer(cfg, model_cfg=TINY)
    train_f, eval_f = make_iterators(cfg, trainer, FakeTokens(n=256))
    trainer.fit(train_f(), eval_f)

    assert trainer.compile_watcher.steady_state_retraces == 0
    records = [
        json.loads(line)
        for line in (tmp_path / "ckpt" / "metrics.jsonl").read_text().splitlines()
    ]

    gaps = [r for r in records if "mfu_gap/wall_s" in r]
    assert gaps, "no mfu_gap records in metrics.jsonl"
    for gap in gaps:
        shares = [gap[f"mfu_gap/{k}"] for k in ("data_fetch", "dispatch", "compute", "host")]
        assert all(0.0 <= s <= 1.0 for s in shares)
        # prefetch overlaps window boundaries, so allow a little slack
        assert 0.9 <= sum(shares) <= 1.15, gap
        assert gap["compile/steady_state_retraces"] == 0
        assert gap["mfu_gap/window_steps"] >= 1

    plans = [r for r in records if r.get("_event") == "memory_plan"]
    sources = {p.get("source") for p in plans}
    assert "pytree" in sources and "xla_train_step" in sources
    pytree_plan = next(p for p in plans if p["source"] == "pytree")
    assert pytree_plan["params_bytes"] > 0
    assert pytree_plan["total_bytes"] >= pytree_plan["params_bytes"]
    xla_plan = next(p for p in plans if p["source"] == "xla_train_step")
    assert xla_plan["plan_total_bytes"] > 0

    compiles = [r for r in records if r.get("_event") == "compile"]
    assert compiles and all(c["expected"] for c in compiles)

    # the report tool renders the run and its retrace assertion passes
    proc = subprocess.run(
        [
            sys.executable,
            str(REPO / "tools" / "perf_report.py"),
            str(tmp_path / "ckpt"),
            "--assert-no-retraces",
        ],
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "MFU-gap waterfall" in proc.stdout
    assert "per-pytree" in proc.stdout
    assert "steady-state retraces: 0" in proc.stdout


def test_perf_report_asserts_on_synthetic_retrace(tmp_path):
    run = tmp_path / "run"
    run.mkdir()
    lines = [
        {"_event": "compile", "fn": "step", "expected": True, "reason": "first_call",
         "duration_s": 1.0, "changed": []},
        {"_event": "compile", "fn": "step", "expected": False, "reason": "steady_state",
         "duration_s": 1.0, "changed": ["leaf[0]: float32(4,) -> float32(5,)"]},
        {"mfu_gap/wall_s": 1.0, "mfu_gap/window_steps": 4, "mfu_gap/data_fetch": 0.1,
         "mfu_gap/dispatch": 0.2, "mfu_gap/compute": 0.6, "mfu_gap/host": 0.1,
         "compile/steady_state_retraces": 1},
    ]
    (run / "metrics.jsonl").write_text("\n".join(json.dumps(l) for l in lines) + "\n")
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "perf_report.py"), str(run),
         "--assert-no-retraces"],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 1
    assert "steady-state retraces: 1" in proc.stdout
    assert "RETRACE step" in proc.stdout


# ---------------------------------------------------------------------------
# Engine warmup report + un-warmed bucket retrace
# ---------------------------------------------------------------------------


@pytest.mark.serve
def test_engine_warmup_report_and_unwarmed_bucket_retrace():
    from test_serve import TINY_LLAMA, make_engine

    engine, _, _ = make_engine(TINY_LLAMA, cache_size=32)
    report = engine.warmup(2, prompt_buckets=(16, 32))
    assert report["batch"] == 2
    assert report["prompt_buckets"] == [16, 32]
    assert report["shapes"]["prefill"] == [[1, 16], [1, 32]]
    assert report["shapes"]["decode"] == [2, 1]
    assert report["n_compiles"] == len(report["compiles"]) >= 4  # 2 prefill + insert + decode
    # first-ever signature per fn classifies as first_call, later buckets as
    # warmup — every one of them is expected, none count as retraces
    assert all(c["reason"] in ("first_call", "warmup") for c in report["compiles"])
    assert engine.compile_watcher.steady_state_retraces == 0

    # traffic inside a warmed bucket: warm path, no event
    n_events = len(engine.compile_watcher.compile_events())
    engine.prefill(jnp.zeros((1, 16), jnp.int32))
    assert len(engine.compile_watcher.compile_events()) == n_events

    # a prompt landing in an un-warmed bucket is a steady-state retrace
    engine.prefill(jnp.zeros((1, 24), jnp.int32))
    assert engine.compile_watcher.steady_state_retraces == 1
    assert engine.compile_watcher.compile_events()[-1].fn == "prefill"


@pytest.mark.serve
def test_engine_memory_plans():
    from test_serve import TINY_LLAMA, make_engine

    engine, _, _ = make_engine(TINY_LLAMA, cache_size=32)
    plans = engine.memory_plans(2, prompt_buckets=(16,))
    pt = plans["pytree"]
    assert pt["params_bytes"] > 0 and pt["kv_cache_bytes"] > 0
    assert pt["total_bytes"] == pt["params_bytes"] + pt["kv_cache_bytes"]
    for key in ("prefill_b16", "insert", "decode"):
        assert key in plans
        plan = plans[key]
        assert "error" in plan or plan["plan_total_bytes"] > 0
    # AOT planning never counts as a retrace
    assert engine.compile_watcher.steady_state_retraces == 0


@pytest.mark.serve
def test_scheduler_records_batch_fill_and_prefill_stall(tmp_path):
    from test_serve import TINY_LLAMA, make_engine
    from relora_tpu.serve.scheduler import ContinuousBatchingScheduler, Request
    from relora_tpu.utils.logging import MetricsLogger

    engine, _, _ = make_engine(TINY_LLAMA, cache_size=48)
    metrics = MetricsLogger(run_dir=str(tmp_path))
    sched = ContinuousBatchingScheduler(engine, max_batch=2, metrics=metrics)
    sched.run([Request(uid=i, prompt=[1, 2, 3], max_new_tokens=4) for i in range(3)])
    metrics.finish()
    records = [
        json.loads(line)
        for line in (tmp_path / "metrics.jsonl").read_text().splitlines()
    ]
    steps = [r for r in records if "serve/batch_fill" in r]
    assert steps
    for r in steps:
        assert 0.0 <= r["serve/batch_fill"] <= 1.0
        assert 0.0 <= r["serve/prefill_stall_share"] <= 1.0
        assert r["serve/prefill_stall_s"] >= 0.0
        assert r["compile/steady_state_retraces"] == 0
    assert max(r["serve/batch_fill"] for r in steps) == 1.0  # 3 reqs, 2 slots
