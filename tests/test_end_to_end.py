"""End-to-end slice: Trainer on a tiny synthetic dataset through the full
ReLoRA lifecycle — warmup, merges, optimizer resets, checkpoint, resume.

Systematizes the reference's manual smoke-test battery (README.dev.md) and
the resume-continuity oracle (SURVEY.md §4 (f))."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relora_tpu.config.model import ModelConfig
from relora_tpu.config.training import TrainingConfig

TINY = ModelConfig(
    vocab_size=128,
    hidden_size=32,
    intermediate_size=64,
    num_hidden_layers=2,
    num_attention_heads=2,
    max_sequence_length=32,
)


class FakeTokens:
    """Deterministic synthetic token stream shaped like a pretokenized set."""

    def __init__(self, n=512, seq=16, vocab=128, seed=0):
        rs = np.random.RandomState(seed)
        # learnable structure: token i often followed by (i+1) % vocab
        rows = []
        for _ in range(n):
            start = rs.randint(vocab)
            rows.append([(start + j) % vocab for j in range(seq)])
        self.arr = np.asarray(rows, dtype=np.int32)

    def __len__(self):
        return len(self.arr)

    def __getitem__(self, idx):
        return {"input_ids": self.arr[idx]}


def make_cfg(tmp_path, **kw):
    base = dict(
        dataset_path="/synthetic",  # not actually read; iterators are built here
        batch_size=4,
        total_batch_size=8,
        max_length=16,
        lr=5e-3,
        scheduler="cosine_restarts",
        warmup_steps=2,
        restart_warmup_steps=2,
        num_training_steps=24,
        cycle_length=8,
        relora=8,
        use_peft=True,
        lora_r=4,
        save_dir=str(tmp_path / "ckpt"),
        save_every=8,
        eval_every=100,
        seed=0,
        dp_size=2,  # 2-device data-parallel submesh of the 8 virtual devices
    )
    base.update(kw)
    return TrainingConfig(**base).finalize()


def make_iterators(cfg, trainer, data):
    from relora_tpu.data.hf_pipeline import TokenBatchIterator

    def train_factory():
        return iter(
            TokenBatchIterator(
                data,
                microbatch=cfg.batch_size * trainer.n_batch_shards,
                grad_accum=trainer.grad_accum,
                skip_updates=trainer.update_step,
            )
        )

    def eval_factory():
        return iter(
            TokenBatchIterator(data, microbatch=cfg.batch_size, grad_accum=None)
        )

    return train_factory, eval_factory


@pytest.mark.slow
def test_full_relora_lifecycle(tmp_path):
    from relora_tpu.train.trainer import Trainer

    cfg = make_cfg(tmp_path)
    data = FakeTokens(n=1024)
    trainer = Trainer(cfg, model_cfg=TINY)
    train_factory, eval_factory = make_iterators(cfg, trainer, data)

    result = trainer.fit(train_factory(), eval_factory)
    assert result["update_step"] == 24
    assert trainer.n_lora_restarts == 2  # merges at update 9 and 17
    assert trainer.n_optimizer_resets == 2
    assert result["final_eval_loss"] < 5.0  # learned something (ln(128)=4.85)
    assert result["n_skipped"] == 0

    # checkpoint artifacts (schema parity: torchrun_main.py:256-267)
    ckpt_dir = os.path.join(cfg.save_dir, "model_24")
    assert os.path.isdir(os.path.join(ckpt_dir, "state"))
    with open(os.path.join(ckpt_dir, "training_state.json")) as f:
        ts = json.load(f)
    assert ts["update_step"] == 24 and ts["n_lora_restarts"] == 2
    with open(os.path.join(ckpt_dir, "relora_config.json")) as f:
        rc = json.load(f)
    assert rc["r"] == 4
    assert os.path.exists(os.path.join(cfg.save_dir, "training_config.yaml"))
    # metrics written
    assert os.path.exists(os.path.join(cfg.save_dir, "metrics.jsonl"))


@pytest.mark.slow
def test_autoresume_continues_exactly(tmp_path):
    """Train 16 steps in one run; separately train 8 then autoresume for 8
    more.  Final params must match bit-for-bit (oracle (f): resume
    bit-exactness)."""
    from relora_tpu.train.trainer import Trainer

    data = FakeTokens(n=1024)

    # run A: straight through 16 steps, no checkpointing interference
    cfg_a = make_cfg(tmp_path / "a", num_training_steps=16, save_every=16, relora=8, cycle_length=8)
    tr_a = Trainer(cfg_a, model_cfg=TINY)
    fa, _ = make_iterators(cfg_a, tr_a, data)
    tr_a.fit(fa(), None)

    # run B: same 16-step config, but the data stream is cut after 8 updates
    # (simulating preemption); a checkpoint lands at step 8 via save_every
    import itertools

    cfg_b = make_cfg(tmp_path / "b", num_training_steps=16, save_every=8, relora=8, cycle_length=8)
    tr_b1 = Trainer(cfg_b, model_cfg=TINY)
    fb, _ = make_iterators(cfg_b, tr_b1, data)
    tr_b1.fit(itertools.islice(fb(), 8), None)

    cfg_b2 = make_cfg(
        tmp_path / "b", num_training_steps=16, save_every=16, relora=8, cycle_length=8, autoresume=True
    )
    tr_b2 = Trainer(cfg_b2, model_cfg=TINY)
    assert tr_b2.update_step == 8  # picked up the checkpoint
    fb2, _ = make_iterators(cfg_b2, tr_b2, data)
    tr_b2.fit(fb2(), None)

    leaves_a = jax.tree_util.tree_leaves(tr_a.state.params)
    leaves_b = jax.tree_util.tree_leaves(tr_b2.state.params)
    for la, lb in zip(leaves_a, leaves_b):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


@pytest.mark.slow
def test_warm_start_from_full_rank(tmp_path):
    """Full-rank warmup then ReLoRA warm start (the reference's core workflow,
    README.md:69-89): base weights transfer, LoRA leaves appear fresh."""
    from relora_tpu.train.trainer import Trainer

    data = FakeTokens(n=1024)
    cfg_full = make_cfg(
        tmp_path / "full",
        use_peft=False,
        relora=None,
        scheduler="cosine",
        cycle_length=8,
        num_training_steps=8,
        save_every=8,
    )
    tr_full = Trainer(cfg_full, model_cfg=TINY)
    ff, _ = make_iterators(cfg_full, tr_full, data)
    tr_full.fit(ff(), None)
    warm_dir = os.path.join(cfg_full.save_dir, "model_8")

    cfg_re = make_cfg(
        tmp_path / "re",
        warmed_up_model=warm_dir,
        num_training_steps=24,
        relora=8,
        cycle_length=8,
    )
    tr_re = Trainer(cfg_re, model_cfg=TINY)
    assert tr_re.update_step == 8  # counters carried over
    # base kernels match the warmup result
    np.testing.assert_allclose(
        np.asarray(tr_re.state.params["layers"]["mlp"]["gate_proj"]["kernel"]),
        np.asarray(tr_full.state.params["layers"]["mlp"]["gate_proj"]["kernel"]),
        rtol=1e-6,
    )
    # LoRA leaves exist and B is zero (init-equivalence)
    assert float(np.abs(np.asarray(tr_re.state.params["layers"]["mlp"]["gate_proj"]["lora_b"])).max()) == 0.0
    fr, _ = make_iterators(cfg_re, tr_re, data)
    res = tr_re.fit(fr(), None)
    assert res["update_step"] == 24
    assert tr_re.n_lora_restarts >= 1


@pytest.mark.slow
def test_relora_quality_tracks_full_rank(tmp_path):
    """The paper's quality claim at toy scale: ReLoRA (warmup -> LoRA cycles
    with merges) reaches an eval loss close to full-rank training on the same
    total step budget (BASELINE.json: 'loss within 1% of full-rank' at scale;
    here we allow a loose factor since the model/data are tiny)."""
    from relora_tpu.train.trainer import Trainer

    data = FakeTokens(n=4096, seq=16)
    total_steps = 60
    warm_steps = 20

    # full-rank baseline
    cfg_full = make_cfg(
        tmp_path / "full", use_peft=False, relora=None, scheduler="cosine",
        cycle_length=total_steps, num_training_steps=total_steps,
        save_every=1000, lr=3e-3,
    )
    tr_full = Trainer(cfg_full, model_cfg=TINY)
    f_full, e_full = make_iterators(cfg_full, tr_full, data)
    full_loss, _ = (lambda r: (r["final_eval_loss"], r))(tr_full.fit(f_full(), e_full))

    # relora: short full-rank warmup, then LoRA cycles
    cfg_warm = make_cfg(
        tmp_path / "warm", use_peft=False, relora=None, scheduler="cosine",
        cycle_length=warm_steps, num_training_steps=warm_steps,
        save_every=warm_steps, lr=3e-3,
    )
    tr_warm = Trainer(cfg_warm, model_cfg=TINY)
    f_warm, _ = make_iterators(cfg_warm, tr_warm, data)
    tr_warm.fit(f_warm(), None)

    cfg_re = make_cfg(
        tmp_path / "re",
        warmed_up_model=str(tmp_path / "warm" / "ckpt" / f"model_{warm_steps}"),
        num_training_steps=total_steps, relora=10, cycle_length=10,
        warmup_steps=2, restart_warmup_steps=2, lr=6e-3,  # ~2x full-rank lr (README.md:19-20)
        save_every=1000,
    )
    tr_re = Trainer(cfg_re, model_cfg=TINY)
    f_re, e_re = make_iterators(cfg_re, tr_re, data)
    res = tr_re.fit(f_re(), e_re)
    assert tr_re.n_lora_restarts >= 3
    relora_loss = res["final_eval_loss"]

    # both learned substantially vs random init (ln(128) = 4.85), and relora
    # tracks full-rank
    assert full_loss < 4.0 and relora_loss < 4.0
    assert relora_loss < full_loss * 1.35


@pytest.mark.slow
def test_reset_schedule_phase_alignment(tmp_path):
    """Step-trace golden test for the reset/scheduler coupling (SURVEY.md §7
    'hard parts'): merges fire at cycle step 1, and the logged LR follows the
    cosine_restarts re-warmup exactly at those steps."""
    from relora_tpu.core.schedules import make_schedule
    from relora_tpu.train.trainer import Trainer

    cfg = make_cfg(tmp_path, num_training_steps=24, relora=8, cycle_length=8,
                   warmup_steps=2, restart_warmup_steps=2, save_every=100)
    data = FakeTokens(n=1024)
    trainer = Trainer(cfg, model_cfg=TINY)
    f, _ = make_iterators(cfg, trainer, data)
    trainer.fit(f(), None)

    lines = [json.loads(l) for l in open(os.path.join(cfg.save_dir, "metrics.jsonl"))]
    lr_by_step = {l["update_step"]: l["lr"] for l in lines if "lr" in l}
    restarts_by_step = {l["update_step"]: l["n_lora_restarts"] for l in lines if "n_lora_restarts" in l}

    sched = make_schedule("cosine_restarts", lr=cfg.lr, num_training_steps=24,
                          warmup_steps=2, min_lr_ratio=cfg.min_lr_ratio,
                          cycle_length=8, restart_warmup_steps=2)
    # logged LR at update u is the schedule at step u-1 (lr applied BY that update)
    for u, lr in lr_by_step.items():
        assert lr == pytest.approx(float(sched(u - 1)), rel=1e-5), f"step {u}"
    # LR drops to ~0 exactly at the cycle boundaries (steps 8 and 16 applied
    # schedule(8)=0 at update 9's log? schedule(8)=restart boundary -> 0)
    assert lr_by_step[9] == pytest.approx(float(sched(8)), abs=1e-9)
    assert float(sched(8)) == 0.0 and float(sched(16)) == 0.0
    # merges recorded at updates 9 and 17 (cycle step 1), in the same log
    # record where the rewarmup begins
    assert restarts_by_step[8] == 0 and restarts_by_step[9] == 1
    assert restarts_by_step[16] == 1 and restarts_by_step[17] == 2


@pytest.mark.slow
def test_seed_determinism(tmp_path):
    """Two fresh runs with the same seed produce bit-identical params."""
    from relora_tpu.train.trainer import Trainer

    data = FakeTokens(n=512)
    outs = []
    for sub in ("a", "b"):
        cfg = make_cfg(tmp_path / sub, num_training_steps=8, relora=8, cycle_length=8,
                       save_every=100)
        tr = Trainer(cfg, model_cfg=TINY)
        f, _ = make_iterators(cfg, tr, data)
        tr.fit(f(), None)
        outs.append(tr.state.params)
    for a, b in zip(jax.tree_util.tree_leaves(outs[0]), jax.tree_util.tree_leaves(outs[1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
def test_wandb_watch_histograms(tmp_path):
    """--wandb_watch logs param+grad histograms at eval cadence (the
    reference's wandb.watch observability, torchrun_main.py:624-627) plus
    the per-subtree grad-norm breakdown in the step metrics."""
    from relora_tpu.train.trainer import Trainer

    cfg = make_cfg(
        tmp_path, wandb_watch=True, eval_every=4, num_training_steps=8,
        relora=None, cycle_length=8, scheduler="cosine",
    )
    data = FakeTokens(n=256)
    trainer = Trainer(cfg, model_cfg=TINY)
    train_factory, eval_factory = make_iterators(cfg, trainer, data)
    trainer.fit(train_factory(), eval_factory)

    hist_records = []
    norm_records = []
    with open(os.path.join(cfg.save_dir, "metrics.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            if any(k.startswith("hist/") for k in rec):
                hist_records.append(rec)
            if any(k.startswith("grad_norm/") for k in rec):
                norm_records.append(rec)
    # eval cadence 4 over 8 steps -> histograms at steps 4 and 8
    assert len(hist_records) == 2, [sorted(r) for r in hist_records]
    rec = hist_records[-1]
    param_keys = [k for k in rec if k.startswith("hist/param/")]
    grad_keys = [k for k in rec if k.startswith("hist/grad/")]
    assert param_keys and grad_keys, sorted(rec)
    for k in param_keys + grad_keys:
        h = rec[k]
        assert len(h["edges"]) == len(h["counts"]) + 1
        assert sum(h["counts"]) > 0
        assert h["edges"][0] < h["edges"][-1]
    # grads over trainable-only subtrees; params over the full tree
    assert any("lora" in k.lower() or "layers" in k for k in grad_keys), grad_keys
    assert norm_records, "grad_norm/* breakdown missing with wandb_watch"
