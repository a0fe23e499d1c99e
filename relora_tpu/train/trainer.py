"""Training orchestration: the TPU-native torchrun_main.main().

Owns what the reference's 700-line main() owns (torchrun_main.py:338-1018):
mesh/process setup, model+optimizer construction, warm-start / resume /
autoresume, the update loop with its two reset triggers, NaN accounting,
evaluation, checkpointing, and metrics — but with all device work factored
into the pure jitted functions of relora_tpu.train.step /
core.relora / core.optim, so the loop itself is trivial host logic.

Trigger semantics preserved exactly (SURVEY.md §3.1): resets fire at
``(update_step - scheduler_start_step) % cycle == 1`` — the step *after* the
scheduler boundary — and are gated by ``can_reset_*`` so a warm-started model
completes its first partial cycle (torchrun_main.py:874-912); ``relora``
(merge cadence) and ``cycle_length`` (optimizer/LR cadence) stay independent
knobs.
"""

from __future__ import annotations

import functools
import os
import time
from typing import Any, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from relora_tpu.config.model import ModelConfig, load_model_config
from relora_tpu.config.training import TrainingConfig
from relora_tpu.core.optim import (
    build_optimizer,
    init_opt_state_sharded,
    reset_optimizer_state,
    zeroed_fraction,
)
from relora_tpu.core.partition import partition
from relora_tpu.core.relora import (
    LoraSpec,
    merge_and_reinit,
    split_param_counts,
    trainable_param_mask,
)
from relora_tpu.core.schedules import make_schedule
from relora_tpu.models.llama import LlamaForCausalLM
from relora_tpu.models.params_util import init_params, logical_partition_specs
from relora_tpu.obs import flight
from relora_tpu.obs import memory as obs_memory
from relora_tpu.obs.compile import CompileWatcher
from relora_tpu.obs.metrics import MetricsRegistry
from relora_tpu.obs.mfu import peak_flops, step_flops_from_cost_analysis
from relora_tpu.obs.tracer import Tracer
from relora_tpu.parallel.mesh import (
    MeshSpec,
    batch_sharding,
    eval_batch_sharding,
    make_mesh,
    mesh_metadata,
    param_shardings,
)
from relora_tpu.train import checkpoint as ckpt
from relora_tpu.train.resilience import LossSpikeDetector, PreemptionGuard, SpikeEvent
from relora_tpu.train.state import TrainState
from relora_tpu.train.step import make_eval_step, make_train_step, make_watch_histograms
from relora_tpu.utils import faults
from relora_tpu.utils.logging import MetricsLogger, get_logger, set_process_index

logger = get_logger(__name__)

PyTree = Any

_DTYPES = {
    "bfloat16": jnp.bfloat16,
    "bf16": jnp.bfloat16,
    "float32": jnp.float32,
    "fp32": jnp.float32,
}


#: metric keys materialized as ints (counts), everything else as floats
_INT_METRICS = frozenset({"skipped", "n_skipped"})

#: per-direction ICI bandwidth per chip (v4/v5e-class link budget), used to
#: cost the modeled collectives behind the mfu_gap "comms" share.  Like the
#: roofline constants in ops/attention_dispatch, absolute accuracy matters
#: less than the ratio against measured device time — the modeled seconds
#: are clamped to the compute fence actually observed at the flush.
ICI_BW_BYTES = float(os.environ.get("RELORA_TPU_ICI_BW", 9.0e10))


def _pull_metric_records(metric_dicts):
    """Materialize a batch of per-step device metric dicts in ONE bulk
    device->host transfer and return plain-Python records.

    This is the sanctioned landing spot for train-loop host syncs (see
    docs/static-analysis.md, RTL2xx): the fit loop accumulates device-side
    metric dicts for ``log_every`` updates and pays a single blocking round
    trip here, instead of one ``float()`` per metric per step inside the
    hot loop.  Values come back as Python floats (counts as ints) so the
    logging code downstream never touches a device array.
    """
    host = jax.device_get(list(metric_dicts))
    return [
        {k: (int(v) if k in _INT_METRICS else float(v)) for k, v in d.items()}
        for d in host
    ]


def _fence_metrics(metric_dicts) -> float:
    """Wait for the newest pending metric dict to finish computing and return
    the wait in seconds — the "compute" share of the mfu_gap waterfall.

    Lives outside the hot functions (RTL203) for the same reason as
    ``_pull_metric_records``: it runs once per ``log_every`` flush, right
    before the bulk pull, so it splits the sync the flush already pays into
    a device-wait part and a transfer part without adding a new sync point.
    The newest dict depends on every preceding step's params, so this one
    fence covers the whole window.
    """
    t0 = time.perf_counter()
    jax.block_until_ready(metric_dicts[-1])
    return time.perf_counter() - t0


def build_model(model_cfg: ModelConfig, lora: Optional[LoraSpec], cfg: TrainingConfig):
    if model_cfg.family not in ("llama", "neox"):
        raise ValueError(
            f"the {model_cfg.family} family is served, not trained: neither the paper nor the "
            "model defines ReLoRA over routed experts (ROADMAP.md R4)"
        )
    compute_dtype = _DTYPES[cfg.dtype]
    if cfg.sp_size > 1:
        # context parallelism: sequence sharded; ring streams K/V blocks
        # (ring_zigzag additionally load-balances the causal mask),
        # ulysses all-to-alls to head sharding
        if cfg.sp_impl not in ("ring", "ring_zigzag", "ulysses"):
            raise ValueError(
                f"sp_impl must be 'ring', 'ring_zigzag' or 'ulysses', got {cfg.sp_impl!r}"
            )
        attention_impl = cfg.sp_impl
    elif cfg.flash_attention and _on_tpu():
        # explicit forcing knob: bypass the dispatcher, always the pallas arm
        attention_impl = "pallas"
    else:
        # per-shape roofline dispatch (ops/attention_dispatch.choose_training_arm):
        # flash vs xla vs naive chosen from (B, S, heads, head_dim) with
        # backward cost modeled, flash struck off-TPU automatically
        attention_impl = "auto"
    kwargs = dict(
        config=model_cfg,
        lora=lora,
        dtype=compute_dtype,
        scan_layers=True,
        remat=cfg.remat,
        remat_policy=cfg.remat_policy,
        attention_impl=attention_impl,
        logits_dtype=jnp.bfloat16 if cfg.bf16_logits else jnp.float32,
    )
    if model_cfg.family == "llama":
        return LlamaForCausalLM(**kwargs)
    from relora_tpu.models.pythia import GPTNeoXForCausalLM

    return GPTNeoXForCausalLM(**kwargs)


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


class Trainer:
    """End-to-end training driver.  Typical use::

        trainer = Trainer(cfg)
        trainer.fit(train_iter_factory, eval_iter_factory)
    """

    def __init__(
        self,
        cfg: TrainingConfig,
        model_cfg: Optional[ModelConfig] = None,
        mesh=None,
    ):
        cfg.finalize()
        self.cfg = cfg
        set_process_index(jax.process_index())

        # ---- mesh / batch arithmetic -------------------------------------
        self.mesh = mesh if mesh is not None else make_mesh(
            MeshSpec(
                data=cfg.dp_size if cfg.dp_size else -1,
                fsdp=cfg.fsdp_size,
                tensor=cfg.tp_size,
                sequence=cfg.sp_size,
            )
        )
        from relora_tpu.parallel.mesh import set_current_mesh

        set_current_mesh(self.mesh)
        mesh_shape = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        self.n_batch_shards = mesh_shape["data"] * mesh_shape["fsdp"]
        self.grad_accum = cfg.grad_accum_for(self.n_batch_shards)
        logger.info(
            f"mesh={mesh_shape} grad_accum={self.grad_accum} "
            f"global_microbatch={cfg.batch_size * self.n_batch_shards} "
            f"total_batch={cfg.total_batch_size}"
        )

        # ---- model -------------------------------------------------------
        if model_cfg is None:
            model_cfg = load_model_config(cfg.model_config or cfg.model_name_or_path)
        self.model_cfg = model_cfg
        # base kernels are only materialized when something needs them
        # (parity: need_linear_weight, torchrun_main.py:531-553)
        need_linear_weight = (
            cfg.relora is not None
            or cfg.force_keep_original
            or cfg.warmed_up_model is not None
        )
        self.lora_spec = (
            LoraSpec(
                r=cfg.lora_r,
                alpha=cfg.lora_alpha,
                dropout=cfg.lora_dropout,
                trainable_scaling=cfg.train_scaling,
                quantize=cfg.quantize,
                use_double_quant=cfg.use_double_quant,
                base_dtype=cfg.base_dtype,
                lora_only=not need_linear_weight,
                fused="auto" if cfg.lora_fused == "auto" else cfg.lora_fused == "true",
            )
            if cfg.use_peft
            else None
        )
        self.model = build_model(model_cfg, self.lora_spec, cfg)

        sample = jnp.zeros((1, cfg.max_length), jnp.int32)
        self.param_specs = logical_partition_specs(self.model, sample)
        self.shardings = param_shardings(self.mesh, self.param_specs)
        self.batch_shard = batch_sharding(self.mesh, seq_sharded=cfg.sp_size > 1)
        self.eval_batch_shard = eval_batch_sharding(self.mesh, seq_sharded=cfg.sp_size > 1)

        # ---- counters (may be overwritten by resume) ---------------------
        self.update_step = 0
        self.global_step = 0
        self.tokens_seen = 0
        self.tokens_seen_before = 0
        self.n_lora_restarts = 0
        self.n_optimizer_resets = 0
        self.n_spike_rollbacks = 0
        self._local_updates = 0
        self._resumed = False
        self._wandb_id: Optional[str] = None

        # ---- resolve resume target (parity: torchrun_main.py:374-399) ----
        self.resume_dir: Optional[str] = None
        if cfg.autoresume and cfg.save_dir and os.path.isdir(cfg.save_dir):
            training_state, self.resume_dir = ckpt.get_last_checkpoint(cfg.save_dir)
            if self.resume_dir:
                self._guard_batch_size_unchanged()
        elif cfg.resume_from:
            self.resume_dir = cfg.resume_from
            self._guard_batch_size_unchanged()

        # ---- params ------------------------------------------------------
        init_rng = jax.random.PRNGKey(cfg.seed)
        with self.mesh:
            params = jax.jit(
                lambda r: init_params(self.model, r, sample),
                out_shardings=self.shardings,
            )(init_rng)
        counts = split_param_counts(params)
        logger.info(
            f"params: total={counts['total_params']/1e6:.2f}M "
            f"trainable={counts['trainable_params']/1e6:.2f}M "
            f"lora={counts['lora_params']/1e6:.2f}M "
            f"equivalent={counts['equivalent_params']/1e6:.2f}M"
        )
        self.param_counts = counts
        self._comms_per_update_s = self._modeled_comms_per_update_s()
        if self._comms_per_update_s:
            logger.info(
                f"modeled comms: {self._comms_per_update_s * 1e3:.2f} ms/update "
                f"over ICI (mfu_gap/comms share)"
            )

        if cfg.warmed_up_model and not self.resume_dir:
            params = self._load_warm_start(params, cfg.warmed_up_model)

        # ---- optimizer + schedule ----------------------------------------
        self.trainable_mask = trainable_param_mask(params)
        if self.resume_dir:
            ts = ckpt.load_training_state(self.resume_dir)
            self.update_step = ts["update_step"]
            self.global_step = ts["global_step"]
            self.tokens_seen = ts["tokens_seen"]
            self.tokens_seen_before = ts.get("tokens_seen_before", 0)
            self.n_lora_restarts = ts.get("n_lora_restarts", 0)
            self.n_optimizer_resets = ts.get("n_optimizer_resets", 0)
            self.n_spike_rollbacks = ts.get("n_spike_rollbacks", 0)
            # a previous run's automatic spike rollback may have extended the
            # blacklist; without merging it a restart would replay the
            # poisoned window
            cfg.skip_batches |= set(ts.get("skip_batches") or ())
            self._wandb_id = ts.get("wandb_id")
            self._resumed = True
            # Keep the schedule identical across restarts: restore the
            # schedule origin instead of re-deriving it from the resume point
            # (the reference re-derives, subtly reshaping the schedule on
            # every autoresume — we persist it for bit-exact resume, the
            # reference's own oracle (f) in SURVEY.md §4).
            self.scheduler_start_step = ts.get("scheduler_start_step", self.update_step)
        else:
            if cfg.warmed_up_model:
                ws = self._warm_start_counters(cfg.warmed_up_model)
                if ws:
                    self.update_step = ws.get("update_step", 0)
                    self.global_step = ws.get("global_step", 0)
                    self.tokens_seen = ws.get("tokens_seen", 0)
            # scheduler runs over the remaining steps with a fresh first
            # warmup (parity: torchrun_main.py:679-691)
            self.scheduler_start_step = self.update_step

        self.schedule = make_schedule(
            cfg.scheduler,
            lr=cfg.lr,
            num_training_steps=cfg.num_training_steps - self.scheduler_start_step,
            warmup_steps=cfg.warmup_steps,
            min_lr_ratio=cfg.min_lr_ratio,
            cycle_length=cfg.cycle_length or cfg.relora,
            restart_warmup_steps=cfg.restart_warmup_steps,
            adjust_step=cfg.adjust_step,
        )
        self.tx = build_optimizer(
            schedule=self.schedule,
            beta1=cfg.adam_beta1,
            beta2=cfg.adam_beta2,
            eps=cfg.adam_eps,
            weight_decay=cfg.weight_decay,
        )

        with self.mesh:
            trainable, _ = partition(params, self.trainable_mask)
            opt_state = init_opt_state_sharded(
                self.tx,
                trainable,
                self.mesh,
                shardings=partition(self.shardings, self.trainable_mask)[0],
            )
        self.state = TrainState.create(params, opt_state)
        self.state = self.state.replace(step=jnp.asarray(self.update_step, jnp.int32))
        self.state = self._normalize_placement(self.state)

        if self.resume_dir and cfg.load_optimizer_state_on_resume:
            self.state = self._normalize_placement(self._restore_state(self.resume_dir))
            logger.info(f"Restored full train state from {self.resume_dir}")
        elif self.resume_dir:
            from relora_tpu.core.optim import set_schedule_count

            restored = self._restore_state(self.resume_dir)
            self.state = self.state.replace(
                params=restored.params,
                # fresh optimizer, but the LR schedule continues from the
                # checkpoint position (parity: scheduler replay,
                # torchrun_main.py:693-699)
                opt_state=set_schedule_count(
                    self.state.opt_state, self.update_step - self.scheduler_start_step
                ),
            )
            logger.info(f"Restored params (fresh optimizer) from {self.resume_dir}")

        # ---- compiled programs -------------------------------------------
        # metric LR is reported relative to the schedule origin, matching the
        # optax-internal count (both freeze on NaN-skipped updates)
        start = self.scheduler_start_step
        zigzag_ring = cfg.sp_size if (cfg.sp_size > 1 and cfg.sp_impl == "ring_zigzag") else None
        # the Megatron pipeline packs seq_length+1-token windows: the model
        # reads seq_length of them (train/step._model_inputs)
        window = cfg.megatron_dataset_config is not None and zigzag_ring is None
        self._train_step = jax.jit(
            make_train_step(
                self.model,
                self.tx,
                self.trainable_mask,
                clip_grad_norm=cfg.clip_grad_norm,
                schedule=lambda s: self.schedule(s - start),
                grad_breakdown=cfg.wandb_watch,
                zigzag_ring=zigzag_ring,
                next_token_window=window,
                loss_impl=cfg.loss_impl,
                vocab_chunk=cfg.vocab_chunk,
                log_per_layer_scaling=cfg.train_scaling,
                nan_grad_steps=faults.nan_grad_steps(),
            ),
            donate_argnums=0,
        )
        self._eval_step = jax.jit(
            make_eval_step(
                self.model,
                zigzag_ring=zigzag_ring,
                loss_impl=cfg.loss_impl,
                vocab_chunk=cfg.vocab_chunk,
                next_token_window=window,
            )
        )
        # wandb.watch parity (torchrun_main.py:624-627): histograms run as
        # their own compiled program at eval cadence, never in the hot step
        self._watch_step = (
            jax.jit(
                make_watch_histograms(
                    self.model,
                    self.trainable_mask,
                    loss_impl=cfg.loss_impl,
                    vocab_chunk=cfg.vocab_chunk,
                    zigzag_ring=zigzag_ring,
                    next_token_window=window,
                )
            )
            if cfg.wandb_watch
            else None
        )
        if self.lora_spec is not None:
            # prune-retrain state (relora_tpu/compress): the keep-mask is
            # computed once at the first merge past prune_start_step, then
            # baked into the merge program so every later cycle re-zeroes the
            # pruned positions before requant.  Resume restores the sidecar
            # so the holes survive a process restart.
            self._prune_mask = None
            self._prune_meta: Optional[dict] = None
            if self.resume_dir and cfg.prune_enabled:
                from relora_tpu.compress import prune as compress_prune

                mask, meta = compress_prune.load_mask(self.resume_dir)
                if mask is not None:
                    self._prune_mask = mask
                    self._prune_meta = meta
                    logger.info(
                        f"Restored prune mask from {self.resume_dir} "
                        f"(sparsity {meta.get('sparsity', 0) if meta else 0:.3f})"
                    )
            self._build_merge_fn()
        self._reset_fn = jax.jit(
            functools.partial(
                reset_optimizer_state,
                mode=cfg.optimizer_reset_mode or "zero",
                ratio=cfg.optimizer_reset_ratio,
            ),
            donate_argnums=0,
        )

        # ---- observability ----------------------------------------------
        run_config = dict(cfg.to_dict())
        run_config.update(
            {
                "model": model_cfg.to_dict(),
                "mesh": mesh_shape,
                "grad_accum": self.grad_accum,
                **{k: v / 1e6 for k, v in counts.items()},
            }
        )
        self.metrics = MetricsLogger(
            run_dir=cfg.save_dir,
            run_name=None,
            config=run_config,
            use_wandb=cfg.wandb,
            resume_id=self._wandb_id,
            source="train",  # fleet series schema: obs/fleet.py joins this file
        )
        self._wandb_id = self.metrics.run_id
        # span tracer for the update loop (data_fetch / dispatch / metric_pull
        # / checkpoint / merge / reset); finished spans land in the flight
        # recorder ring buffer for crash dumps, and optionally in a JSONL
        # stream when RELORA_TPU_TRACE_DIR is set
        trace_dir = os.environ.get("RELORA_TPU_TRACE_DIR")
        self.tracer = Tracer(
            service="train",
            jsonl_path=os.path.join(trace_dir, "train_spans.jsonl") if trace_dir else None,
        )
        self.obs = MetricsRegistry(namespace="relora_train")
        # compile telemetry: the wrapped step tracks its abstract call
        # signatures — a recompile after the first one is a steady-state
        # retrace (compile_steady_state_retraces counter, `compile` events
        # in metrics.jsonl; see docs/observability.md)
        self.compile_watcher = CompileWatcher(
            service="train", tracer=self.tracer, registry=self.obs, metrics=self.metrics
        )
        self._train_step = self.compile_watcher.wrap("train_step", self._train_step)
        # HBM accounting: live gauges polled at the metric-flush cadence, and
        # the per-pytree plan (what the resident state occupies) emitted once
        self._mem_poller = obs_memory.MemoryPoller(registry=self.obs)
        self._memory_plan = obs_memory.pytree_breakdown(
            {"params": self.state.params, "opt_state": self.state.opt_state}
        )
        self.metrics.event(
            "memory_plan",
            step=self.update_step,
            source="pytree",
            **self._memory_plan,
            **{f"live_{k}": v for k, v in self._mem_poller.poll().items()},
        )
        if cfg.save_dir:
            flight.configure(dump_dir=cfg.save_dir)
        # live MFU: measured step FLOPs (XLA cost_analysis, filled in lazily
        # on the first batch) over the device's peak; 6ND analytic fallback
        self._peak_flops = peak_flops()
        self._n_params_6nd = (
            model_cfg.num_params(include_embeddings=False)
            + model_cfg.vocab_size * model_cfg.hidden_size
        )
        self._step_flops: Optional[float] = None
        self._mfu_measured = False
        if cfg.save_dir and jax.process_index() == 0:
            os.makedirs(cfg.save_dir, exist_ok=True)
            cfg.save(os.path.join(cfg.save_dir, "training_config.yaml"))

    # ------------------------------------------------------------------
    def _build_merge_fn(self) -> None:
        """(Re)compile the merge-and-reinit program with the current prune
        mask and reset-init dial baked in.

        Rebuilt at most twice per run (construction + the first prune event)
        — merge cadence, never the hot step.  out_shardings pins the merged
        tree to the same placement as the donated input: without it a
        tp/fsdp-sharded param tree could come back replicated after a
        merge-and-reinit, silently turning every later train step into a
        resharding collective."""
        from relora_tpu.compress.resets import make_reinit_fn

        self._merge_fn = jax.jit(
            functools.partial(
                merge_and_reinit,
                spec=self.lora_spec,
                a_init=make_reinit_fn(self.cfg.reset_init),
                mask=self._prune_mask,
            ),
            donate_argnums=0,
            out_shardings=self.shardings,
        )

    def _maybe_compute_prune_mask(self) -> None:
        """First prune event: derive the fixed keep-mask from the just-merged
        base, zero the pruned positions in place, and rebake the merge
        program so every later cycle re-applies the mask before requant."""
        cfg = self.cfg
        if (
            self._prune_mask is not None
            or not cfg.prune_enabled
            or self.update_step < cfg.prune_start_step
        ):
            return
        from relora_tpu.compress import prune as compress_prune

        t0 = time.time()
        self._prune_mask = magnitude = compress_prune.magnitude_mask(
            self.state.params,
            cfg.prune_sparsity,
            scope=cfg.prune_scope,
            nm=cfg.prune_nm,
        )
        stats = compress_prune.sparsity_stats(magnitude)
        self._prune_meta = {
            "target_sparsity": cfg.prune_sparsity,
            "scope": cfg.prune_scope,
            "nm": cfg.prune_nm,
            "computed_at_step": self.update_step,
        }
        with self.mesh:
            masked = jax.jit(
                functools.partial(compress_prune.apply_mask, mask=self._prune_mask),
                donate_argnums=0,
                out_shardings=self.shardings,
            )(self.state.params)
        self.state = self.state.replace(params=masked)
        jax.block_until_ready(self.state.params)
        self._build_merge_fn()
        self.metrics.event(
            "prune_mask_computed",
            step=self.update_step,
            sparsity=stats["sparsity"],
            mask_crc32=compress_prune.mask_checksum(magnitude),
        )
        logger.info(
            f"Prune mask computed at update {self.update_step}: "
            f"{stats['sparsity']*100:.2f}% of base weights zeroed "
            f"({time.time() - t0:.2f}s)"
        )

    # ------------------------------------------------------------------
    def _restore_state(self, path: str) -> PyTree:
        """Restore a full TrainState from ``path`` onto this mesh.

        Same-topology checkpoints take Orbax's fast path (shards restored
        straight onto the recorded layout).  A checkpoint whose manifest
        records a *different* mesh shape or chip count — a preempted-and-
        resized run — goes through the elastic reshard: host-side restore,
        then re-placement under this mesh's partition rules, optimizer
        state included (train/elastic.py)."""
        from relora_tpu.train import elastic

        meta = ckpt.load_manifest_metadata(path)
        if elastic.needs_reshard(meta, self.mesh):
            ok, reason = elastic.validate_reshard(meta, self.mesh)
            if not ok:
                raise RuntimeError(f"cannot elastically resume from {path}: {reason}")
            logger.info(
                f"Elastic resume: checkpoint saved on {meta.get('chip_count')} "
                f"chip(s) {meta.get('mesh_shape')}, resharding onto "
                f"{dict(zip(self.mesh.axis_names, self.mesh.devices.shape))}"
            )
            return elastic.restore_resharded(path, self.state)
        return ckpt.restore_checkpoint(path, self.state)

    def _normalize_placement(self, tree: PyTree) -> PyTree:
        """Ensure every leaf lives on this mesh's device set: leaves already
        sharded over the full mesh are kept; stragglers (jit-placed or
        checkpoint-restored scalars committed to one device) are replicated.
        jit requires all arguments to share one device set."""
        from jax.sharding import NamedSharding, PartitionSpec

        mesh_devices = set(self.mesh.devices.flat)
        rep = NamedSharding(self.mesh, PartitionSpec())

        def fix(leaf):
            if not hasattr(leaf, "sharding"):
                return leaf
            try:
                if set(leaf.sharding.device_set) == mesh_devices:
                    return leaf
            except Exception:
                pass
            return jax.device_put(leaf, rep)

        return jax.tree_util.tree_map(fix, tree)

    def _guard_batch_size_unchanged(self) -> None:
        """Resume with a different batch size breaks the data rewind
        (parity: torchrun_main.py:710-716)."""
        import yaml

        p = os.path.join(os.path.dirname(self.resume_dir), "training_config.yaml")
        if not os.path.exists(p) and self.cfg.save_dir:
            p = os.path.join(self.cfg.save_dir, "training_config.yaml")
        if os.path.exists(p):
            with open(p) as f:
                old = yaml.safe_load(f)
            if old.get("batch_size") != self.cfg.batch_size:
                raise RuntimeError(
                    "Cannot resume from a checkpoint with a different batch size"
                )

    def _load_warm_start(self, params: PyTree, path: str) -> PyTree:
        """Full-rank weights into a (possibly LoRA) tree — the
        full-rank→ReLoRA transition (torchrun_main.py:505-553)."""
        from relora_tpu.models.hf_compat import graft_base_weights, hf_to_params

        state_dir = os.path.join(path, ckpt.STATE_SUBDIR)
        if os.path.isdir(state_dir):
            # a previous run of ours (any shape — full-rank or LoRA):
            # template-free host restore, then graft by name
            base = ckpt.restore_params_host(path)
        else:
            bin_path = os.path.join(path, "pytorch_model.bin")
            if not os.path.exists(bin_path):
                raise ValueError(f"warmed_up_model {path!r} has neither state/ nor pytorch_model.bin")
            import torch

            sd = torch.load(bin_path, map_location="cpu", weights_only=True)
            base = hf_to_params(sd, self.model_cfg, scan_layers=True)
        grafted = graft_base_weights(params, base)
        logger.info(f"Warm-started base weights from {path}")
        return grafted

    def _warm_start_counters(self, path: str) -> Optional[dict]:
        p = os.path.join(path, ckpt.TRAINING_STATE_FILE)
        if os.path.exists(p):
            import json

            with open(p) as f:
                return json.load(f)
        logger.warning(f"No training state found in {path}; counters start from zero")
        return None

    # ------------------------------------------------------------------
    def device_batch(self, local_batch: np.ndarray) -> jax.Array:
        """Host numpy -> global sharded device array.  3-D arrays are train
        updates (ga, local_micro, seq); 2-D are eval batches (micro, seq)."""
        shard = self.batch_shard if local_batch.ndim == 3 else self.eval_batch_shard
        if jax.process_count() == 1:
            return jax.device_put(local_batch, shard)
        return jax.make_array_from_process_local_data(shard, local_batch)

    def _prefetched(self, train_iter, depth: int = 2):
        """Keep ``depth`` batches already transferred to the device, so host
        reads and H2D copies overlap the running step (device_put is async;
        starting the next transfer before the current step is consumed keeps
        it off the critical path)."""
        import collections

        queue = collections.deque()
        it = iter(train_iter)
        try:
            while len(queue) < depth:
                queue.append(self.device_batch(next(it)))
        except StopIteration:
            pass
        while queue:
            out = queue.popleft()
            try:
                queue.append(self.device_batch(next(it)))
            except StopIteration:
                pass
            yield out

    # ------------------------------------------------------------------
    def _measure_step_flops(self, batch, rng) -> Optional[float]:
        """Total FLOPs of one compiled train step, from XLA's cost model.

        Runs once, lazily, on the first real batch (abstract lowering under
        the arguments' own shardings, no device work).  Returns None when the
        backend's cost model reports no FLOPs or ``RELORA_TPU_LIVE_MFU=0``;
        the MFU gauge then uses the 6ND analytic estimate
        (docs/observability.md).  A step that cannot be lowered or compiled
        raises: the run would fail at its first update anyway.

        Side effect: reuses the lowering for the train step's static HBM plan
        (``compiled.memory_analysis()`` -> a ``memory_plan`` event).  That
        path DOES compile, and an AOT compile does not warm the traced-call
        cache (the persistent cache, where on, serves the second compile) —
        ``RELORA_TPU_MEM_PLAN=0`` skips it."""
        if os.environ.get("RELORA_TPU_LIVE_MFU", "1") == "0":
            return None

        def abs_of(x):
            # committed arrays (the sharded state, the placed batch) lower
            # under their sharding, so the plan is the real step's
            placed = isinstance(x, jax.Array) and x.committed
            return jax.ShapeDtypeStruct(
                np.shape(x), x.dtype, sharding=x.sharding if placed else None
            )

        abs_args = jax.tree_util.tree_map(abs_of, (self.state, batch, rng))
        with self.mesh:
            lowered = self._train_step.lower(*abs_args)
        flops = step_flops_from_cost_analysis(lowered.cost_analysis())
        if os.environ.get("RELORA_TPU_MEM_PLAN", "1") != "0":
            t0 = time.monotonic()
            with self.mesh, self.compile_watcher.expected_compiles("memory_plan"):
                compiled = lowered.compile()
            plan = obs_memory.xla_memory_plan(compiled)
            if plan:
                # this AOT compile is the step's real one where the persistent
                # cache is on (the first call then loads it): record its time
                plan["compile_s"] = round(time.monotonic() - t0, 2)
                # the program's text is megabytes at 1B: read it only where
                # there can be a collective
                plan["collectives"] = (
                    obs_memory.collective_counts(compiled.as_text())
                    if self.mesh.size > 1
                    else []
                )
                recon = obs_memory.reconcile(plan.get("plan_total_bytes"))
                recon.pop("plan_total_bytes", None)  # already in the plan
                self.metrics.event(
                    "memory_plan",
                    step=self.update_step,
                    source="xla_train_step",
                    **plan,
                    **recon,
                )
        if flops:
            logger.info(f"live MFU: measured step cost {flops:.3e} FLOPs (cost_analysis)")
        return flops

    # ------------------------------------------------------------------
    def _modeled_comms_per_update_s(self) -> float:
        """Analytic per-update collective seconds for the current mesh:
        grad all-reduce over data×fsdp, fsdp param all-gather (fwd + bwd
        re-gather), and tp activation all-reduces (2 fwd + 2 bwd per layer
        per microbatch), each costed as a ring over ICI
        (``2(n-1)/n × bytes / BW``).  Zero on a single-chip mesh.  This is
        the model behind the ``mfu_gap/comms`` share: it decomposes the
        measured compute fence, it does not add to it."""
        shape = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        n_batch = shape["data"] * shape["fsdp"]
        n_f, n_t = shape["fsdp"], shape["tensor"]
        act_bytes = jnp.dtype(_DTYPES[self.cfg.dtype]).itemsize
        ring = lambda n, nbytes: 2.0 * (n - 1) / n * nbytes
        total = 0.0
        if n_batch > 1:
            # grads sync once per update in f32, trainable params only
            total += ring(n_batch, self.param_counts["trainable_params"] * 4)
        if n_f > 1:
            # params all-gather for fwd and again for the remat'd bwd
            total += 2.0 * ring(n_f, self.param_counts["total_params"] * act_bytes)
        if n_t > 1:
            mc = self.model_cfg
            act = self.cfg.batch_size * self.cfg.max_length * mc.hidden_size * act_bytes
            total += 4.0 * mc.num_hidden_layers * self.grad_accum * ring(n_t, act)
        return total / ICI_BW_BYTES

    # ------------------------------------------------------------------
    def fit(
        self,
        train_iter: Iterator[np.ndarray],
        eval_iter_factory=None,
        train_iter_factory=None,
    ) -> dict:
        """The update loop (parity: torchrun_main.py:768-947).

        ``train_iter_factory`` (optional) rebuilds the training iterator from
        the trainer's *current* counters — required for automatic loss-spike
        rollback, which rewinds ``update_step`` and needs the data stream
        re-aligned to it.  Without it, spikes are detected and logged but not
        rolled back.  SIGTERM/SIGINT during the loop triggers a graceful
        emergency checkpoint at the next update boundary
        (``cfg.handle_preemption``); the result dict reports ``preempted``.
        """
        cfg = self.cfg
        exhausted = True  # for-else: did the data run out before the step budget?
        update_start = time.time()
        rng = jax.random.PRNGKey(cfg.seed + 1)
        saved_at = -1
        aborted = False
        preempted = False
        detector = (
            LossSpikeDetector(
                cfg.spike_threshold,
                window=cfg.spike_window,
                min_history=cfg.spike_min_history,
                patience=cfg.spike_patience,
            )
            if cfg.spike_threshold > 0
            else None
        )
        spike: Optional[SpikeEvent] = None

        from relora_tpu.utils.profiling import maybe_make_profiler

        prof = maybe_make_profiler(cfg, run_name=os.path.basename(cfg.save_dir or "run"))

        logger.info(
            f"Starting training at update step {self.update_step} "
            f"({cfg.num_training_steps - self.update_step} to go)"
        )
        # Metrics are materialized with a one-step lag: float()-ing the
        # current step's device metrics would block the host on the step's
        # completion every iteration; by
        # logging the previous step's metrics while the current one computes,
        # data loading and logging overlap device work.  With
        # cfg.log_every > 1 the lag grows to at most log_every updates and
        # all lagged records are pulled in ONE bulk transfer
        # (_pull_metric_records).  The NaN-abort check runs on materialized
        # values, so it lags by the same bound — a few extra steps before an
        # abort is harmless.
        pending: list = []  # (metrics, update_step, global_step, tokens, dt, counters, span_s)
        window_t0 = time.perf_counter()  # mfu_gap waterfall window start

        def flush_pending() -> bool:
            """Log all lagged metric records; returns False if training must
            abort.  One bulk device pull for the whole batch — keep
            float()/int() on device values out of here (RTL202).

            Also emits the mfu_gap waterfall for the flushed window: the
            flush's single sync is split into a device-wait fence (the
            "compute" share) and the transfer, and the window's wall time is
            partitioned into data_fetch / dispatch / compute / comms / host
            shares that sum to ~100% by construction (comms is the modeled
            collective time carved out of the fence; host is the residual:
            transfer, logging, python, and any eval/checkpoint cadence work
            that landed in the window)."""
            nonlocal spike, window_t0
            if not pending:
                return True
            with self.tracer.span("metric_pull", n_records=len(pending)):
                devs = [p[0] for p in pending]
                compute_s = _fence_metrics(devs)
                records = _pull_metric_records(devs)
            batch = [(m, *rest) for m, (_, *rest) in zip(records, pending)]
            pending.clear()
            now = time.perf_counter()
            wall = now - window_t0
            window_t0 = now
            data_s = sum(b[-1][0] for b in batch)
            disp_s = sum(b[-1][1] for b in batch)
            if wall > 0:
                host_s = max(0.0, wall - data_s - disp_s - compute_s)
                # comms-vs-compute split of the device fence: the modeled
                # collective seconds (clamped so a wrong model can never
                # claim more than the device time actually measured) come
                # out of the compute share, so the five shares still sum to
                # ~100% and a growing comms share reads as "the step is
                # waiting on ICI, not on the MXU"
                comms_s = min(compute_s, self._comms_per_update_s * len(batch))
                gap = {
                    "mfu_gap/window_steps": len(batch),
                    "mfu_gap/wall_s": round(wall, 4),
                    "mfu_gap/data_fetch": round(min(1.0, data_s / wall), 4),
                    "mfu_gap/dispatch": round(min(1.0, disp_s / wall), 4),
                    "mfu_gap/compute": round(min(1.0, (compute_s - comms_s) / wall), 4),
                    "mfu_gap/comms": round(min(1.0, comms_s / wall), 4),
                    "mfu_gap/host": round(min(1.0, host_s / wall), 4),
                    "compile/steady_state_retraces": self.compile_watcher.steady_state_retraces,
                }
                for key in ("data_fetch", "dispatch", "compute", "comms", "host"):
                    self.obs.set_gauge(f"mfu_gap_{key}", gap[f"mfu_gap/{key}"])
                # live HBM gauges at the same cadence (no-op on CPU; the
                # poller must never run inside the per-step loop)
                mem = self._mem_poller.poll()
                if mem["available"]:
                    gap["hbm/bytes_in_use"] = mem["bytes_in_use"]
                    gap["hbm/peak_bytes_in_use"] = mem["peak_bytes_in_use"]
                self.metrics.log(gap, step=batch[-1][2])
            for metrics, at_step, at_global, tokens_in_update, dt, counters, _span_s in batch:
                if metrics["skipped"]:
                    logger.error(
                        f"NaN update skipped at step {at_step} "
                        f"({metrics['n_skipped']} total)"
                    )
                    self.metrics.event(
                        "nan_skip", step=at_step, n_skipped=metrics["n_skipped"]
                    )
                    if metrics["n_skipped"] > cfg.nan_abort_fraction * cfg.num_training_steps:
                        logger.error("More than 5% of updates NaN-skipped; aborting")
                        return False
                loss_val = faults.perturb("loss", metrics["loss"], step=at_step)
                if detector is not None and spike is None:
                    spike = detector.update(at_step, loss_val)
                tokens_per_sec = tokens_in_update / dt
                # live MFU: measured step FLOPs when the backend's cost model
                # provided them, 6ND otherwise (same formula as bench MFU)
                if self._step_flops:
                    mfu = self._step_flops / dt / self._peak_flops
                else:
                    mfu = tokens_per_sec * 6 * self._n_params_6nd / self._peak_flops
                self.obs.set_gauge("mfu", mfu)
                self.obs.set_gauge("throughput_tokens_per_s", tokens_per_sec)
                record = {
                    "loss": loss_val,
                    "lr": metrics.get("lr", 0.0),
                    "update_step": at_step,
                    "grad_norm": metrics["grad_norm"],
                    "mfu": mfu,
                    "throughput_tokens": tokens_per_sec,
                    "throughput_examples": cfg.total_batch_size / dt,
                    "throughput_batches": self.grad_accum * self.n_batch_shards / dt,
                    # snapshotted when the record was created, so counts
                    # attribute to the update they happened at despite the lag
                    **counters,
                }
                # extra metrics (grad_norm/* breakdown, lora_scaling, ...)
                for k, v in metrics.items():
                    if k not in record and k not in ("skipped", "n_skipped"):
                        record[k] = v
                self.metrics.log(record, step=at_global)
            return True

        if self.update_step >= cfg.num_training_steps:
            # already-finished run (e.g. autoresume past the budget): don't
            # pull/transfer any data
            train_iter = iter(())
        try:
            with PreemptionGuard(enabled=cfg.handle_preemption) as guard:
              # the while wrapper exists solely for spike rollback: a rollback
              # rewinds counters and restarts the for loop on a rebuilt iterator
              while True:
                restart = False
                exhausted = True
                batches = self._prefetched(train_iter)
                while True:
                  # one "update_step" span per iteration; the explicit next() puts
                  # the data wait inside it as a "data_fetch" child (a for-loop
                  # fetches in the header, outside any span).  Two-space nesting
                  # keeps the loop body's indentation unchanged.
                  with self.tracer.span("update_step", step=self.update_step):
                    with self.tracer.span("data_fetch") as sp_fetch:
                        batch = next(batches, None)
                    if batch is None:
                        break  # data ran out; exhausted stays True (for-else parity)
                    if self.update_step >= cfg.num_training_steps:
                        exhausted = False
                        break
                    if self.update_step in cfg.skip_batches:
                        # loss-spike blacklist, manual (torchrun_main.py:772-775)
                        # or auto-extended by rollback: the batch is consumed
                        # (data stream stays aligned) but its transfer is wasted
                        # — acceptable for a rare blacklist
                        self.metrics.event("batch_skipped", step=self.update_step)
                        self.update_step += 1
                        self.global_step += self.grad_accum
                        continue

                    self.tokens_seen += int(batch.size)

                    if not self._mfu_measured:
                        # first real batch: ask XLA's cost model what one step
                        # costs, so the MFU gauge uses measured FLOPs not 6ND
                        self._mfu_measured = True
                        self.metrics.event(
                            "placement",
                            step=self.update_step,
                            **obs_memory.placement(self.state.params, batch),
                        )
                        self._step_flops = self._measure_step_flops(
                            batch, jax.random.fold_in(rng, self.update_step)
                        )
                    with self.tracer.span("dispatch", step=self.update_step) as sp_dispatch:
                        # async dispatch: this span is enqueue cost, not device
                        # step time — the blocking pull happens in metric_pull
                        self.state, metrics = self._train_step(
                            self.state, batch, jax.random.fold_in(rng, self.update_step)
                        )
                    self.update_step += 1
                    self._local_updates += 1
                    self.global_step += self.grad_accum

                    # ---- graceful preemption --------------------------------
                    faults.tick("preempt", self.update_step)
                    if guard.requested:
                        self.metrics.event(
                            "preemption", step=self.update_step, signum=guard.signum
                        )
                        flush_pending()
                        if cfg.save_dir:
                            path = self.save(time.time() - update_start)
                            if path:
                                saved_at = self.update_step
                                self.metrics.event(
                                    "emergency_checkpoint",
                                    step=self.update_step,
                                    path=path,
                                )
                        preempted = True
                        exhausted = False
                        break

                    # ---- save -----------------------------------------------
                    if (
                        cfg.save_dir
                        and cfg.save_every > 0
                        and self._local_updates > 1
                        and self.update_step % cfg.save_every == 0
                    ):
                        if self.save(time.time() - update_start):
                            saved_at = self.update_step

                    # ---- eval -----------------------------------------------
                    if (
                        eval_iter_factory is not None
                        and cfg.eval_every > 0
                        and self.update_step % cfg.eval_every == 0
                    ):
                        with self.tracer.span("eval", step=self.update_step):
                            eval_loss, eval_tokens = self.evaluate(
                                eval_iter_factory(), cfg.eval_tokens_during_training
                            )
                        self.metrics.log(
                            {"final_eval_loss": eval_loss, "final_eval_tokens": eval_tokens},
                            step=self.global_step,
                        )
                        logger.info(f"Eval loss at step {self.update_step}: {eval_loss:.4f}")

                    # ---- wandb.watch histograms (torchrun_main.py:624-627) --
                    if (
                        self._watch_step is not None
                        and cfg.eval_every > 0
                        and self.update_step % cfg.eval_every == 0
                    ):
                        with self.tracer.span("watch_histograms", step=self.update_step):
                            hists = self._watch_step(
                                self.state.params,
                                batch[0],
                                jax.random.fold_in(rng, 2**30 + self.update_step),
                            )
                            # one bulk transfer: per-element int()/float() on device
                            # arrays would sync with the device once per bin
                            self.metrics.log_histograms(
                                jax.device_get(hists), step=self.global_step
                            )

                    # ---- ReLoRA merge (torchrun_main.py:874-893) ------------
                    relora_every = cfg.relora  # 0 normalized to None in finalize
                    can_merge = relora_every is not None and (
                        self._resumed or self._local_updates >= relora_every
                    )
                    if can_merge and (self.update_step - self.scheduler_start_step) % relora_every == 1:
                        t0 = time.time()
                        self.n_lora_restarts += 1
                        with self.tracer.span(
                            "relora_merge", step=self.update_step, n=self.n_lora_restarts
                        ):
                            self.state = self.state.replace(
                                params=self._merge_fn(
                                    self.state.params,
                                    jax.random.fold_in(jax.random.PRNGKey(cfg.seed + 2), self.update_step),
                                )
                            )
                            jax.block_until_ready(self.state.params)
                            # PERP prune-retrain: first eligible merge fixes
                            # the mask (later merges re-apply it inside
                            # _merge_fn before requant)
                            self._maybe_compute_prune_mask()
                        logger.info(
                            f"LoRA merge #{self.n_lora_restarts} at update {self.update_step} "
                            f"took {time.time() - t0:.2f}s"
                        )

                    # ---- optimizer reset (torchrun_main.py:895-912) ---------
                    cycle = cfg.cycle_length or cfg.relora
                    can_reset = cfg.relora is not None and cycle is not None and (
                        self._resumed or self._local_updates >= cycle
                    )
                    if can_reset and (self.update_step - self.scheduler_start_step) % cycle == 1:
                        self.n_optimizer_resets += 1
                        reset_rng = jax.random.fold_in(jax.random.PRNGKey(cfg.seed + 3), self.update_step)
                        with self.tracer.span(
                            "optimizer_reset", step=self.update_step, n=self.n_optimizer_resets
                        ):
                            self.state = self.state.replace(
                                opt_state=self._reset_fn(self.state.opt_state, rng=reset_rng)
                            )
                            z = float(zeroed_fraction(self.state.opt_state))
                        logger.info(
                            f"Optimizer reset #{self.n_optimizer_resets} "
                            f"({cfg.optimizer_reset_mode}) at update {self.update_step}: "
                            f"{z*100:.2f}% of moments zero"
                        )
                        # post-reset LR sanity (training_utils.py:391-404)
                        lr_now = float(self.schedule(jnp.asarray(self.update_step - self.scheduler_start_step)))
                        if lr_now > self.cfg.lr:
                            self.metrics.alert(
                                "Learning rate issue",
                                f"LR after reset is {lr_now} > max {self.cfg.lr}",
                            )

                    # ---- metrics (torchrun_main.py:918-943), lagged ---------
                    # flush BEFORE appending: with log_every=1 this is exactly
                    # the historical one-step lag; larger values batch up to
                    # log_every records into one device pull
                    if len(pending) >= cfg.log_every and not flush_pending():
                        exhausted = False
                        aborted = True
                        break
                    update_time = time.time() - update_start
                    update_start = time.time()
                    tokens_in_update = self.tokens_seen - self.tokens_seen_before
                    self.tokens_seen_before = self.tokens_seen
                    pending.append(
                        (
                            metrics,
                            self.update_step,
                            self.global_step,
                            tokens_in_update,
                            update_time,
                            {
                                "tokens_seen": self.tokens_seen,
                                "n_lora_restarts": self.n_lora_restarts,
                                "n_optimizer_resets": self.n_optimizer_resets,
                            },
                            # per-step host-side time for the mfu_gap
                            # waterfall (spans are closed by here)
                            (sp_fetch.duration_s or 0.0, sp_dispatch.duration_s or 0.0),
                        )
                    )
                    if prof is not None:
                        # per update step, regardless of the flush cadence
                        prof.step()

                    # ---- loss-spike rollback --------------------------------
                    if spike is not None:
                        ev, spike = spike, None
                        rolled_back = self._handle_spike(
                            ev, can_realign=train_iter_factory is not None
                        )
                        detector.reset_streak()
                        if rolled_back:
                            # drop the lagged metric records — the steps they
                            # describe were just undone
                            pending.clear()
                            restart = True
                            exhausted = False
                            break
                if restart:
                    train_iter = train_iter_factory()
                    update_start = time.time()
                    continue
                break
        except BaseException:
            # any crash inside the update loop leaves a flight dump
            # behind: the last ~2k spans/events, rendered by
            # tools/trace_report.py (docs/observability.md)
            flight.dump_on_fault("crash")
            raise
        finally:
            if prof is not None:
                # close(), not stop(): a mid-window exit must not leak
                # the process-global jax.profiler trace
                prof.close()
        if not flush_pending():
            aborted = True
        if exhausted and self.update_step < cfg.num_training_steps:
            # for-else equivalent (torchrun_main.py:945-947)
            logger.warning("Reached the end of the dataset before num_training_steps")

        # final save + eval (torchrun_main.py:956-1012)
        if cfg.save_dir and self.update_step != saved_at:
            self.save(time.time() - update_start)
        result = {
            "update_step": self.update_step,
            "tokens_seen": self.tokens_seen,
            "aborted": aborted,
            "preempted": preempted,
            "n_rollbacks": self.n_spike_rollbacks,
            "n_skipped": int(self.state.n_skipped),  # noqa: RTL202 - once, after the loop
        }
        if eval_iter_factory is not None and not preempted:
            final_loss, final_tokens = self.evaluate(
                eval_iter_factory(), target_tokens=cfg.final_eval_tokens
            )
            self.metrics.log(
                {"final_eval_loss": final_loss, "final_eval_tokens": final_tokens},
                step=self.global_step,
            )
            result["final_eval_loss"] = final_loss
        self.metrics.finish()
        self.tracer.close()  # flush + release the JSONL sink, if configured
        # fence pending async checkpoint writes before declaring the run done
        # (process exit must not truncate an in-flight save)
        ckpt.wait_for_save()
        logger.info("Training finished")
        return result

    # ------------------------------------------------------------------
    def evaluate(
        self,
        eval_iter: Iterator[np.ndarray],
        target_tokens: int = -1,
        sync_every: int = 8,
    ):
        """Token-weighted mean eval loss (parity: evaluate_model,
        torchrun_main.py:143-189; target 10M during training, 100M final,
        -1 = full set).

        Loss/token sums accumulate on-device and are pulled to the host only
        every ``sync_every`` batches (and once at the end) — the reference's
        per-batch ``.item()`` round trip is the kind of host sync the train
        loop carefully lags.  The token target is tracked host-side from
        batch shapes (free — no device sync), so the loop drains early when
        the target is near and overshoots by at most one batch (same bound as
        the reference), not ``sync_every - 1``.
        """
        pending: list = []  # device-side partial sums, drained in one pull
        loss_sum = 0.0
        n_tokens = 0.0
        expected_tokens = 0  # host-side upper bound on device n_tokens

        def drain():
            nonlocal loss_sum, n_tokens
            if not pending:
                return
            # one stacked pull = one blocking device round trip per drain
            sums = np.asarray(
                jnp.stack(
                    [
                        jnp.sum(jnp.stack([p[k] for p in pending]))
                        for k in ("loss_sum", "n_tokens")
                    ]
                )
            )
            s_loss, s_tok = sums.tolist()  # host array -> plain floats
            loss_sum += s_loss
            n_tokens += s_tok
            pending.clear()
            if np.isnan(loss_sum):
                raise RuntimeError("NaN in evaluation loss")

        for arr in eval_iter:
            pending.append(self._eval_step(self.state.params, self.device_batch(arr)))
            # shifted-label estimate: the loss sees at most seq-1 targets per
            # row (fewer with padding), so batch*(seq-1) upper-bounds the
            # loss-token count far tighter than raw batch size.  The device
            # n_tokens is a global sum over hosts, each feeding an
            # equally-shaped local slice, so scale by process_count to keep
            # the host-side estimate an upper bound on the global count.
            shape = np.shape(arr)  # host-side metadata, no device transfer
            expected_tokens += (
                shape[0] * max(shape[-1] - 1, 1) * jax.process_count()
            )
            if len(pending) >= max(sync_every, 1) or (
                target_tokens > 0 and expected_tokens >= target_tokens
            ):
                drain()
                if target_tokens > 0:
                    if n_tokens >= target_tokens:
                        break
                    # re-arm the early-drain trigger from the true count:
                    # with padded data the host estimate overshoots, and
                    # without this reset every subsequent batch would drain
                    # (one device round trip each) until the real count
                    # caught up — exactly the per-batch sync sync_every
                    # exists to avoid
                    expected_tokens = int(n_tokens)
        drain()
        return loss_sum / max(n_tokens, 1.0), n_tokens

    # ------------------------------------------------------------------
    def _handle_spike(self, spike: SpikeEvent, can_realign: bool) -> bool:
        """Roll back to the last committed checkpoint preceding the spike and
        blacklist the poisoned update window.  Returns True when a rollback
        happened (the caller must rebuild the data iterator); on False the
        spike is logged and training continues forward."""
        cfg = self.cfg
        self.metrics.event(
            "loss_spike",
            step=spike.last_step,
            first_step=spike.first_step,
            last_step=spike.last_step,
            loss=spike.loss,
            median=spike.median,
            mad=spike.mad,
        )
        logger.error(
            f"Sustained loss spike over updates {spike.first_step}..{spike.last_step} "
            f"(loss={spike.loss:.4f}, baseline median={spike.median:.4f}, "
            f"mad={spike.mad:.4f})"
        )
        # forensics before any rollback mutates state: what was the loop
        # doing in the steps leading up to the spike?
        flight.dump_on_fault("loss_spike")
        reason = None
        if self.n_spike_rollbacks >= cfg.max_spike_rollbacks:
            reason = f"rollback budget exhausted ({cfg.max_spike_rollbacks})"
        elif not can_realign:
            reason = "no train_iter_factory to realign the data stream"
        elif not cfg.save_dir:
            reason = "no save_dir to roll back to"
        if reason is None:
            # the spike's own steps may have just been checkpointed; only a
            # checkpoint strictly before the spike is a valid target
            ckpt.wait_for_save()
            ts, target = ckpt.get_last_checkpoint(
                cfg.save_dir, before_step=spike.first_step
            )
            if target is None:
                reason = "no committed checkpoint precedes the spike"
        if reason is not None:
            logger.error(f"Loss spike NOT rolled back: {reason}")
            self.metrics.event("rollback_skipped", step=spike.last_step, reason=reason)
            return False
        # skip indices are matched against the pre-increment counter, so
        # skipping index k suppresses logged update k+1: the spiked logged
        # window [first, last] maps to indices [first-1, last-1], and the
        # margin extends the blacklist past the last observed outlier
        new_skips = set(
            range(spike.first_step - 1, spike.last_step + cfg.spike_rollback_margin)
        )
        cfg.skip_batches |= new_skips
        self.state = self._normalize_placement(self._restore_state(target))
        if self.lora_spec is not None and cfg.prune_enabled:
            # the rollback target may predate the prune event: resync the
            # mask (or its absence) from the target's sidecar so the merge
            # program matches the restored weights
            from relora_tpu.compress import prune as compress_prune

            self._prune_mask, self._prune_meta = compress_prune.load_mask(target)
            self._build_merge_fn()
        self.update_step = ts["update_step"]
        self.global_step = ts["global_step"]
        self.tokens_seen = ts["tokens_seen"]
        self.tokens_seen_before = ts.get("tokens_seen_before", self.tokens_seen)
        self.n_lora_restarts = ts.get("n_lora_restarts", self.n_lora_restarts)
        self.n_optimizer_resets = ts.get("n_optimizer_resets", self.n_optimizer_resets)
        # same trigger gating as a process-restart resume: the first partial
        # cycle after the rollback point completes before new merges/resets
        self._local_updates = 0
        self._resumed = True
        self.n_spike_rollbacks += 1
        self.metrics.event(
            "rollback",
            step=self.update_step,
            target=target,
            skip_batches=sorted(new_skips),
            n_spike_rollbacks=self.n_spike_rollbacks,
        )
        logger.warning(
            f"Rolled back to {target} (update {self.update_step}); "
            f"blacklisted batch indices {sorted(new_skips)} "
            f"(rollback {self.n_spike_rollbacks}/{cfg.max_spike_rollbacks})"
        )
        return True

    # ------------------------------------------------------------------
    def save(self, update_time: float = 0.0) -> str:
        training_state = {
            "global_step": self.global_step,
            "update_step": self.update_step,
            "tokens_seen": self.tokens_seen,
            "tokens_seen_before": self.tokens_seen_before,
            "n_lora_restarts": self.n_lora_restarts,
            "n_optimizer_resets": self.n_optimizer_resets,
            "update_time": update_time,
            "wandb_id": self._wandb_id,
            # extensions over the reference schema: the schedule origin lets
            # resume rebuild the exact same LR schedule (see __init__), and
            # the blacklist/rollback counters make automatic spike recovery
            # survive a process restart
            "scheduler_start_step": self.scheduler_start_step,
            "skip_batches": sorted(self.cfg.skip_batches),
            "n_spike_rollbacks": self.n_spike_rollbacks,
        }
        try:
            with self.tracer.span("checkpoint", step=self.update_step):
                path = ckpt.save_checkpoint(
                    self.cfg.save_dir,
                    self.update_step,
                    self.state,
                    training_state,
                    self.lora_spec,
                    retries=self.cfg.save_retries,
                    retry_backoff=self.cfg.save_retry_backoff,
                    manifest_metadata=mesh_metadata(self.mesh),
                )
        except (OSError, ValueError) as e:
            # a lost periodic checkpoint must not kill a long run: the
            # previous committed checkpoint stays the resume target and the
            # next save cadence tries again
            logger.error(f"Checkpoint save at step {self.update_step} abandoned: {e}")
            self.metrics.event("save_failed", step=self.update_step, error=str(e))
            return ""
        if getattr(self, "_prune_mask", None) is not None and jax.process_index() == 0:
            # mask sidecar rides in the checkpoint dir (and its manifest's
            # file walk): resume and the serving/export paths read it back
            from relora_tpu.compress import prune as compress_prune

            compress_prune.save_mask(path, self._prune_mask, self._prune_meta)
        ckpt.delete_old_checkpoints(self.cfg.save_dir, self.cfg.keep_checkpoints)
        return path
