"""Shared throughput-measurement core for bench.py and scripts/bench_sweep.py.

One implementation of the model/optimizer construction, warmup, sync, and
timed loop, so the headline bench and the lever-sweep harness cannot drift.
Throughput definition parity: tokens_in_update / update_time
(torchrun_main.py:928-931).
"""

from __future__ import annotations

import time
from typing import Optional

from relora_tpu.obs.memory import hbm_peak_gb as obs_hbm_peak_gb
from relora_tpu.obs.mfu import PEAK_FLOPS_DEFAULT
from relora_tpu.obs.mfu import peak_flops as detect_peak_flops

# kept for importers; the actual per-device table (and the
# RELORA_TPU_PEAK_FLOPS override) lives in relora_tpu.obs.mfu
PEAK_FLOPS_V5E = PEAK_FLOPS_DEFAULT


def run_throughput_bench(
    model_name: str,
    *,
    micro_batch: int = 8,
    grad_accum: int = 1,
    seq: int = 1024,
    remat: bool = True,
    remat_policy: str = "full",
    loss_impl: str = "dense",
    vocab_chunk: int = 8192,
    logits_dtype: str = "f32",
    attn: str = "auto",
    rank: Optional[int] = 128,
    quantize: Optional[str] = None,
    base_dtype: Optional[str] = None,
    lora_fused="auto",
    dropout: float = 0.1,
    warmup_steps: int = 3,
    measure_steps: int = 10,
    magnitude_reset: bool = False,
    peak_flops: Optional[float] = None,
) -> dict:
    """Build the ReLoRA train step for ``model_name`` and measure steady-state
    training throughput on the default backend.  Returns a dict with
    tokens_per_sec / mfu / step_time_s / loss / device.

    ``rank=None`` (or 0) benches the full-rank configuration (every param
    trainable).  ``magnitude_reset=True`` runs one magnitude-pruning
    optimizer reset between warmup and the timed window (proves the path
    on-chip; the 1B recipe amortizes its cost over 1000 steps, so it is
    deliberately excluded from the per-step figure).
    """
    from relora_tpu.utils.logging import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp

    from relora_tpu.config.model import MODEL_ZOO
    from relora_tpu.core.optim import build_optimizer
    from relora_tpu.core.partition import partition
    from relora_tpu.core.relora import LoraSpec, trainable_param_mask
    from relora_tpu.models.llama import LlamaForCausalLM
    from relora_tpu.models.params_util import init_params
    from relora_tpu.train.state import TrainState
    from relora_tpu.train.step import make_train_step

    cfg = MODEL_ZOO[model_name]
    spec = (
        LoraSpec(
            r=rank,
            alpha=32,
            dropout=dropout,
            quantize=quantize,
            base_dtype=base_dtype,
            fused=lora_fused,
        )
        if rank
        else None
    )
    model = LlamaForCausalLM(
        cfg,
        lora=spec,
        dtype=jnp.bfloat16,
        scan_layers=True,
        remat=remat,
        remat_policy=remat_policy,
        attention_impl=attn,
        logits_dtype=jnp.bfloat16 if logits_dtype == "bf16" else jnp.float32,
    )
    sample = jnp.zeros((1, 8), jnp.int32)
    params = init_params(model, jax.random.PRNGKey(0), sample)
    mask = trainable_param_mask(params)
    tx = build_optimizer(schedule=lambda s: 1e-3)
    opt_state = jax.jit(tx.init)(partition(params, mask)[0])
    state = TrainState.create(params, opt_state)
    step = jax.jit(
        make_train_step(model, tx, mask, loss_impl=loss_impl, vocab_chunk=vocab_chunk),
        donate_argnums=0,
    )

    batch = jax.random.randint(
        jax.random.PRNGKey(1), (grad_accum, micro_batch, seq), 0, cfg.vocab_size
    )
    rng = jax.random.PRNGKey(2)

    # always at least one untimed step: primes the compile cache and binds
    # `metrics` for the pre-measure sync even when warmup_steps == 0 — the
    # result dict reports warmup_steps_effective so a --warmup 0 sweep can
    # see the floor was applied rather than misattribute the measurement
    warmup_steps_effective = max(warmup_steps, 1)
    for i in range(warmup_steps_effective):
        state, metrics = step(state, batch, jax.random.fold_in(rng, i))
    if magnitude_reset:
        from relora_tpu.core.optim import reset_optimizer_state

        reset = jax.jit(
            lambda s: s.replace(
                opt_state=reset_optimizer_state(s.opt_state, mode="magnitude", ratio=0.9)
            ),
            donate_argnums=0,
        )
        state = reset(state)
        # fence the reset's device execution out of the timed window
        jax.block_until_ready(state.opt_state)
    float(metrics["loss"])  # full sync: the pulled scalar depends on the whole step

    t0 = time.perf_counter()
    for i in range(measure_steps):
        state, metrics = step(state, batch, jax.random.fold_in(rng, 100 + i))
    # the final loss depends on every preceding step's params, so this one
    # sync forces the whole chain to have executed
    final_loss = float(metrics["loss"])
    dt = time.perf_counter() - t0

    tokens_per_update = grad_accum * micro_batch * seq
    tokens_per_sec = tokens_per_update * measure_steps / dt
    # one schema for CPU and TPU: obs/memory normalizes the backends that
    # keep no allocator stats (CPU) to None instead of a raw `or {}` dance
    hbm_peak_gb = obs_hbm_peak_gb(jax.devices()[0])
    # 6*N per token fwd+bwd on the dense (equivalent) params
    n_params = cfg.num_params(include_embeddings=False) + cfg.vocab_size * cfg.hidden_size
    if peak_flops is None:
        # per-device table keyed on device_kind; RELORA_TPU_PEAK_FLOPS overrides
        peak_flops = detect_peak_flops(jax.devices()[0])
    mfu = tokens_per_sec * 6 * n_params / peak_flops
    return {
        "tokens_per_sec": round(tokens_per_sec, 1),
        "mfu": round(mfu, 4),
        "peak_flops": peak_flops,
        "step_time_s": round(dt / measure_steps, 4),
        "tokens_per_update": tokens_per_update,
        "warmup_steps_effective": warmup_steps_effective,
        "loss": final_loss,
        "hbm_peak_gb": hbm_peak_gb,
        "device": str(jax.devices()[0]),
    }
