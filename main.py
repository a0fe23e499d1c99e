"""relora-tpu training CLI — the torchrun_main.py equivalent.

Single entry point for pretraining (full-rank or ReLoRA) on TPU.  Unlike the
reference there is no process launcher: on a TPU pod slice, run this same
script on every host (`jax.distributed.initialize` discovers the slice); on
one host it just runs.

Examples (reference README parity)::

    # full-rank warmup
    python main.py --model_config llama_35m --dataset_path data/c4_tok \
        --batch_size 24 --total_batch_size 1152 --lr 5e-4 \
        --num_training_steps 10000 --save_dir ckpts/warmup

    # ReLoRA from the warmup
    python main.py --model_config llama_250m --dataset_path data/c4_tok \
        --batch_size 24 --total_batch_size 1152 --lr 1e-3 --use_peft true \
        --relora 5000 --cycle_length 5000 --restart_warmup_steps 100 \
        --scheduler cosine_restarts --warmed_up_model ckpts/warmup/model_10000 \
        --num_training_steps 20000 --save_dir ckpts/relora

    # or everything from a YAML recipe (reference format)
    python main.py --training_config training_configs/1B_v1.0.yaml
"""

from __future__ import annotations

import json
import os


def main(argv=None) -> dict:
    from relora_tpu.utils.logging import enable_compile_cache

    enable_compile_cache()
    from relora_tpu.config.training import parse_train_args
    from relora_tpu.utils.logging import get_logger

    logger = get_logger("relora_tpu.main")
    cfg = parse_train_args(argv)

    import jax

    if cfg.prng_impl:
        # e.g. 'rbg': hardware random bits instead of threefry — dropout
        # bits per LoRA-wrapped linear are a measurable TPU cost
        jax.config.update("jax_default_prng_impl", cfg.prng_impl)

    if int(os.environ.get("RELORA_TPU_DISTRIBUTED", "0")):
        # multi-host pod: coordinator discovery via TPU metadata
        jax.distributed.initialize()

    from relora_tpu.train.trainer import Trainer

    trainer = Trainer(cfg)

    if cfg.dataset_path is not None:
        train_factory, eval_factory = _hf_data(cfg, trainer)
    else:
        train_factory, eval_factory = _megatron_data(cfg, trainer)

    result = trainer.fit(
        train_factory(), eval_factory, train_iter_factory=train_factory
    )
    logger.info(f"Result: {result}")
    return result


def _hf_data(cfg, trainer):
    """Pretokenized HF dataset path (parity: torchrun_main.py:431-462 incl.
    provenance/size checks)."""
    import datasets

    from relora_tpu.data.hf_pipeline import TokenBatchIterator
    from relora_tpu.utils.logging import get_logger

    logger = get_logger("relora_tpu.main")
    ds = datasets.load_from_disk(cfg.dataset_path)
    if isinstance(ds, datasets.DatasetDict):
        train_ds = ds["train"]
        eval_ds = ds.get("validation") or ds.get("test")
    else:
        split = ds.train_test_split(test_size=min(2000, max(2, len(ds) // 100)), seed=cfg.seed)
        train_ds, eval_ds = split["train"], split["test"]

    # provenance check (parity: torchrun_main.py:452-455)
    prov = os.path.join(cfg.dataset_path, "args.json")
    if os.path.exists(prov):
        with open(prov) as f:
            args = json.load(f)
        if args.get("sequence_length") not in (None, cfg.max_length):
            raise ValueError(
                f"Dataset was pretokenized with sequence_length="
                f"{args.get('sequence_length')}, but max_length={cfg.max_length}"
            )

    # dataset big enough for the planned run (parity: torchrun_main.py:446-450)
    planned_tokens = cfg.num_training_steps * cfg.total_batch_size * cfg.max_length
    available = len(train_ds) * cfg.max_length
    if available < planned_tokens:
        logger.warning(
            f"Dataset has ~{available:,} tokens but the run plans "
            f"{planned_tokens:,}; training will stop early"
        )

    import jax

    def train_factory():
        return iter(
            TokenBatchIterator(
                train_ds,
                microbatch=cfg.batch_size * trainer.n_batch_shards // jax.process_count(),
                grad_accum=trainer.grad_accum,
                skip_updates=trainer.update_step,
                process_index=jax.process_index(),
                process_count=jax.process_count(),
            )
        )

    def eval_factory():
        return iter(
            TokenBatchIterator(
                eval_ds,
                microbatch=cfg.batch_size * trainer.n_batch_shards // jax.process_count(),
                grad_accum=None,
                process_index=jax.process_index(),
                process_count=jax.process_count(),
            )
        )

    return train_factory, eval_factory


def _megatron_data(cfg, trainer):
    """Megatron mmap dataset path (parity: load_megatron_dataset,
    torchrun_main.py:276-319)."""
    from relora_tpu.data.megatron import build_train_valid_test_iterators

    return build_train_valid_test_iterators(cfg, trainer)


if __name__ == "__main__":
    main()
