"""Trainer auxiliaries: skip_batches, NaN abort threshold, lagged metrics,
profiler cadence, metrics logger."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tests.test_end_to_end import TINY, FakeTokens, make_cfg, make_iterators


@pytest.mark.slow
def test_skip_batches_blacklist(tmp_path):
    """--skip_batches consumes data but performs no update at those steps
    (torchrun_main.py:772-775)."""
    from relora_tpu.train.trainer import Trainer

    data = FakeTokens(n=512)
    cfg = make_cfg(
        tmp_path, num_training_steps=12, relora=None, use_peft=False,
        scheduler="cosine", cycle_length=12, skip_batches="3,5", save_every=100,
    )
    trainer = Trainer(cfg, model_cfg=TINY)
    f, _ = make_iterators(cfg, trainer, data)
    res = trainer.fit(f(), None)
    assert res["update_step"] == 12
    # 12 update steps counted, but only 10 device updates happened
    assert int(trainer.state.step) == 10
    # metrics.jsonl has no entries for the skipped update steps.  The skip
    # check uses the pre-increment counter and logs use the post-increment
    # one (both reference semantics), so skipping {3,5} means logged
    # update_steps exclude {4,6}.
    lines = [json.loads(l) for l in open(os.path.join(cfg.save_dir, "metrics.jsonl"))]
    steps_logged = {l["update_step"] for l in lines if "update_step" in l}
    assert 4 not in steps_logged and 6 not in steps_logged
    assert 3 in steps_logged and 5 in steps_logged


@pytest.mark.slow
def test_nan_abort_threshold(tmp_path):
    """Sustained NaN updates abort the run (torchrun_main.py:820-822)."""
    from relora_tpu.train.trainer import Trainer

    data = FakeTokens(n=512)
    cfg = make_cfg(
        tmp_path, num_training_steps=100, relora=None, use_peft=False,
        scheduler="cosine", cycle_length=100, save_every=1000,
        nan_abort_fraction=0.02,
    )
    trainer = Trainer(cfg, model_cfg=TINY)
    # poison the params so every loss is NaN
    trainer.state = trainer.state.replace(
        params=jax.tree_util.tree_map(
            lambda x: jnp.full_like(x, jnp.nan) if x.dtype == jnp.float32 else x,
            trainer.state.params,
        )
    )
    f, _ = make_iterators(cfg, trainer, data)
    res = trainer.fit(f(), None)
    assert res["aborted"] is True
    assert res["n_skipped"] > 2  # crossed the 2% threshold then stopped
    assert res["update_step"] < 100


def test_step_profiler_cadence(tmp_path, monkeypatch):
    from relora_tpu.utils import profiling

    events = []
    monkeypatch.setattr(
        profiling.jax.profiler, "start_trace", lambda d: events.append("start")
    )
    monkeypatch.setattr(profiling.jax.profiler, "stop_trace", lambda: events.append("stop"))
    prof = profiling.StepProfiler(str(tmp_path), wait=1, warmup=1, active=2, repeat=2)
    for _ in range(12):
        prof.step()
    prof.stop()
    # two complete trace windows, started after wait+warmup each cycle
    assert events == ["start", "stop", "start", "stop"]


def test_metrics_logger_jsonl(tmp_path):
    from relora_tpu.utils.logging import MetricsLogger

    m = MetricsLogger(run_dir=str(tmp_path))
    m.log({"loss": jnp.asarray(1.5), "update_step": 3}, step=7)
    m.alert("test", "message")
    m.finish()
    lines = [json.loads(l) for l in open(tmp_path / "metrics.jsonl")]
    assert lines[0]["loss"] == 1.5 and lines[0]["_step"] == 7


@pytest.mark.slow
def test_trainable_scaling_end_to_end(tmp_path):
    """--train_scaling: lora_s leaves exist, train, get logged as mean
    effective (tanh) scale, and reset to zero on merge."""
    from relora_tpu.train.trainer import Trainer

    data = FakeTokens(n=512)
    cfg = make_cfg(tmp_path, train_scaling=True, num_training_steps=16,
                   relora=8, cycle_length=8, save_every=100)
    trainer = Trainer(cfg, model_cfg=TINY)
    assert "lora_s" in trainer.state.params["layers"]["self_attn"]["q_proj"]
    f, _ = make_iterators(cfg, trainer, data)
    res = trainer.fit(f(), None)
    assert res["update_step"] == 16 and trainer.n_lora_restarts == 1
    lines = [json.loads(l) for l in open(os.path.join(cfg.save_dir, "metrics.jsonl"))]
    scal = [l["lora_scaling"] for l in lines if "lora_scaling" in l]
    assert scal and all(-1.0 <= s <= 1.0 for s in scal)
    # per-layer logging under train_scaling (torchrun_main.py:937-942 parity):
    # scan-stacked modules expand to one entry per layer
    per_layer = [k for k in lines[-2] if k.startswith("lora_scaling/")]
    assert any("layer0" in k and "q_proj" in k for k in per_layer), per_layer
    assert any("layer1" in k for k in per_layer)
    # merge at step 9 zeroed the scalings
    s_leaf = np.asarray(trainer.state.params["layers"]["self_attn"]["q_proj"]["lora_s"])
    # one step of training after the merge may have nudged it slightly
    assert np.abs(s_leaf).max() < 0.1


@pytest.mark.slow
def test_evaluate_respects_token_target(tmp_path):
    """evaluate() stops at target_tokens during training and runs the full
    set at -1 (torchrun_main.py:144, 984-1003 semantics)."""
    from relora_tpu.train.trainer import Trainer

    data = FakeTokens(n=256)
    cfg = make_cfg(tmp_path, num_training_steps=8, relora=None, use_peft=False,
                   scheduler="cosine", cycle_length=8, save_every=100)
    trainer = Trainer(cfg, model_cfg=TINY)
    _, eval_factory = make_iterators(cfg, trainer, data)
    # full pass: 256 seqs x 15 shifted tokens
    loss_full, n_full = trainer.evaluate(eval_factory(), target_tokens=-1)
    assert n_full == 256 * 15
    # capped pass stops after crossing the target, overshooting by at most
    # ONE batch (4 seqs x 16 tokens) — not sync_every-1 batches
    loss_cap, n_cap = trainer.evaluate(eval_factory(), target_tokens=200)
    assert 200 <= n_cap <= 200 + 4 * 16
    assert np.isfinite(loss_full) and np.isfinite(loss_cap)


@pytest.mark.slow
def test_eval_every_zero_disables_midtraining_eval(tmp_path):
    """0 means 'disabled' for every cadence knob (eval_every, save_every,
    relora) — none may crash the update-step modulo; the final eval still
    runs, capped by final_eval_tokens."""
    from relora_tpu.train.trainer import Trainer

    data = FakeTokens(n=512)
    # relora=0 with cycle_length omitted: the scheduler cycle fallback and
    # the reset cadence must both see the normalized None, not 0; 5 steps
    # crosses the step a relora=4 run would reset at
    cfg = make_cfg(
        tmp_path, num_training_steps=5, relora=0, use_peft=True,
        scheduler="cosine", cycle_length=None, eval_every=0, save_every=0,
        final_eval_tokens=256,
    )
    assert cfg.relora is None
    trainer = Trainer(cfg, model_cfg=TINY)
    f, ef = make_iterators(cfg, trainer, data)
    res = trainer.fit(f(), ef)
    assert res["update_step"] == 5
    assert trainer.n_lora_restarts == 0
    lines = [json.loads(l) for l in open(os.path.join(cfg.save_dir, "metrics.jsonl"))]
    # mid-training and final evals share the "final_eval_loss" key (reference
    # wandb-schema parity), so exactly one entry proves no mid-training eval ran
    finals = [l for l in lines if "final_eval_loss" in l]
    assert len(finals) == 1
    # the 256-token cap bounds the final eval to cap + one microbatch
    assert finals[0]["final_eval_tokens"] <= 256 + cfg.batch_size * cfg.max_length


def test_enable_compile_cache_env_control(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set -> JAX reads it itself and the code sets
    no directory; unset -> the one fixed path inside the checkout."""
    from relora_tpu.utils import logging as rlog

    before = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", "sentinel")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/outside")
        assert rlog.enable_compile_cache() == "/somewhere/outside"
        assert jax.config.jax_compilation_cache_dir == "sentinel"

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        fixed = os.path.join(repo, ".jax_compile_cache")
        assert rlog.enable_compile_cache() == fixed == rlog.COMPILE_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
