"""Training cells: ``Trainer.fit``, the call ``main.py`` makes, fed by the
Megatron pipeline through an iterator the harness owns.

The iterator lets the warm-up updates through (the first of them are the ones
the reference follows), blocks on the state, opens the window, and stops
yielding when the window's seconds are up, so ``fit`` ends as at end of data.
One ``Trainer`` — one compiled step with its state — serves set-up, the
compared updates and the window.

``Trainer._prefetched`` keeps two batches ahead: when the iterator is asked
for batch ``n``, updates ``0 .. n-3`` have been dispatched.
"""

from __future__ import annotations

import os
import shutil

import numpy as np

from benchmark import flops, harness, weights
from benchmark.traffic.corpus import write_corpus

PREFETCH = 2  # Trainer._prefetched's depth


def train_argv(cell: harness.Cell, mega_yaml: str, seed: int) -> list:
    """The trainer's flags, as ``main.py`` takes them."""
    w = cell.workload
    argv = [
        "--megatron_dataset_config", mega_yaml,
        "--model_config", cell.config_file,
        "--max_length", str(w["seq_length"]),
        "--lora_r", str(w["lora_r"]), "--lora_alpha", str(w["lora_alpha"]),
        "--lora_dropout", str(w["lora_dropout"]),
        "--batch_size", str(w["micro_batch"]), "--total_batch_size", str(w["global_batch"]),
        "--seed", str(seed % (2**31 - 1)),
    ]
    for k, v in w["flags"].items():
        argv += [f"--{k}", str(v)]
    return argv


def hyperparameters(cell: harness.Cell) -> dict:
    """What the reference needs to follow the trainer's first updates."""
    w, f = cell.workload, cell.workload["flags"]
    return {
        "lora_r": w["lora_r"], "lora_alpha": w["lora_alpha"],
        "lr": f["lr"], "warmup_steps": f["warmup_steps"],
        "adam_beta1": f["adam_beta1"], "adam_beta2": f["adam_beta2"], "adam_eps": f["adam_eps"],
        "weight_decay": f["weight_decay"], "clip_grad_norm": f["clip_grad_norm"],
    }


def _adam_mu(opt_state):
    import optax

    found = [s for s in opt_state if isinstance(s, optax.ScaleByAdamState)]
    if len(found) != 1:
        raise RuntimeError("the trainer's optimizer state holds no single Adam state")
    return found[0].mu


def _flat_trainable(tree) -> dict:
    """``{path: leaf}`` for the non-None leaves of the program's nested dict."""
    return {p: v for p, v in weights.flatten(tree).items() if v is not None}


class WindowFeed:
    """The iterator between the Megatron pipeline and ``Trainer.fit``."""

    def __init__(self, source, trainer, cell, key, seconds: float, trace_dir, compiles):
        self.source = iter(source)
        self.trainer = trainer
        self.w = cell.workload
        self.cfg = cell.config
        self.key = key
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.n = 0  # batches asked for so far
        self.batches: list = []  # the compared updates' batches, for the reference
        self.readings: dict = {}
        self.t_open = self.t_close = None
        self.trace_span = None  # (t_start, t_stop) on the host clock
        self._trace_on = False
        self.updates_in_window = 0
        self.data_wait_s = 0.0
        self.data_waits = 0
        self.compiles = compiles
        self.compiles_in_window = 0
        self.profiler_s = 0.0  # the window's seconds inside start_trace and stop_trace
        self.done = False

    # -- readings of the compared updates, taken from the live state -------
    def _grad_norms(self):
        import jax

        from benchmark.reference.neox import leaf_norms

        b1 = self.w["flags"]["adam_beta1"]
        mu = _flat_trainable(_adam_mu(self.trainer.state.opt_state))
        fn = jax.jit(lambda m: leaf_norms({p: v / (1.0 - b1) for p, v in m.items()}))
        return jax.device_get(fn(mu))

    def _change_norms(self):
        import jax

        from benchmark.reference.neox import is_frozen, leaf_norms

        shapes = weights.flatten(weights.param_shapes(self.cfg, self.w["lora_r"]))
        paths = [p for p in shapes if not is_frozen(p, shapes)]
        now = weights.flatten(self.trainer.state.params)

        def change(key, leaves):
            return leaf_norms({p: leaves[p] - weights.leaf_from_seed(key, p, shapes[p]) for p in paths})

        return jax.device_get(jax.jit(change)(self.key, {p: now[p] for p in paths}))

    def __iter__(self):
        return self

    def __next__(self):
        import jax

        n, self.n = self.n, self.n + 1
        dispatched = max(0, n - PREFETCH)
        warm, compared = self.w["warmup_updates"], self.w["compared_updates"]
        if self.done:
            raise StopIteration
        if dispatched == 1 and "grad_norms" not in self.readings:
            self.readings["grad_norms"] = self._grad_norms()
        if dispatched == compared and "change_norms" not in self.readings:
            self.readings["change_norms"] = self._change_norms()
        if dispatched == warm and self.t_open is None:
            jax.block_until_ready(self.trainer.state)
            self._compiles_at_open = self.compiles.count
            self.t_open = harness.now()
        if self.t_open is not None:
            in_window = dispatched - warm
            if self.trace_dir and not self._trace_on and self.trace_span is None and in_window == 2:
                jax.block_until_ready(self.trainer.state)
                t = harness.now()
                jax.profiler.start_trace(self.trace_dir)
                self._trace_on = True
                self._trace_t0, self._trace_from = harness.now(), in_window
                self.profiler_s += self._trace_t0 - t
            elif self._trace_on and in_window - self._trace_from >= self.w["trace_updates"]:
                jax.block_until_ready(self.trainer.state)
                t1 = harness.now()
                jax.profiler.stop_trace()
                self._trace_on = False
                self.trace_span = (self._trace_t0, t1, in_window - self._trace_from)
                self.profiler_s += harness.now() - t1
            if harness.now() - self.t_open >= self.seconds and not self._trace_on:
                jax.block_until_ready(self.trainer.state)
                self.t_close = harness.now()
                self.compiles_in_window = self.compiles.count - self._compiles_at_open
                self.updates_in_window = in_window
                self.done = True
                raise StopIteration
        t0 = harness.now()
        batch = next(self.source)
        if self.t_open is not None:
            self.data_wait_s += harness.now() - t0
            self.data_waits += 1
        if n < compared:
            self.batches.append(np.array(batch[0]))
        return batch


def build(cell: harness.Cell, seed: int, work_dir: str):
    """The trainer ``main.py`` would build, holding the benchmark's weights."""
    from relora_tpu.config.training import parse_train_args
    from relora_tpu.data.megatron import build_train_valid_test_iterators
    from relora_tpu.train.trainer import Trainer

    shutil.rmtree(work_dir, ignore_errors=True)
    mega = write_corpus(cell.traffic, cell.config["vocab_size"], seed, work_dir)
    cfg = parse_train_args(train_argv(cell, mega, seed))
    trainer = Trainer(cfg)
    place_weights(trainer, cell, seed)
    train_factory, _ = build_train_valid_test_iterators(cfg, trainer)
    return trainer, train_factory


def first_batches(cell: harness.Cell, seed: int) -> list:
    """The compared updates' batches, from the pipeline alone (for the
    control's readings, which need no program)."""
    import types

    from relora_tpu.config.training import parse_train_args
    from relora_tpu.data.megatron import build_train_valid_test_iterators

    work_dir = os.path.join(harness.ROOT, ".bench_work", cell.name, "data")
    shutil.rmtree(work_dir, ignore_errors=True)
    cfg = parse_train_args(train_argv(cell, write_corpus(cell.traffic, cell.config["vocab_size"], seed, work_dir), seed))
    stub = types.SimpleNamespace(n_batch_shards=1, grad_accum=1, update_step=0)
    it = iter(build_train_valid_test_iterators(cfg, stub)[0]())
    return [np.array(next(it)[0]) for _ in range(cell.workload["compared_updates"])]


def place_weights(trainer, cell: harness.Cell, seed: int) -> None:
    """Replace the trainer's own initial parameters by the benchmark's."""
    import jax

    lora_r = cell.workload["lora_r"]
    want = weights.flatten(weights.param_shapes(cell.config, lora_r))
    have = {p: tuple(v.shape) for p, v in weights.flatten(trainer.state.params).items()}
    if have != {p: tuple(s) for p, s in want.items()}:
        odd = sorted(set(have.items()) ^ set((p, tuple(s)) for p, s in want.items()))[:6]
        raise RuntimeError(f"the trainer's parameter tree is not the configuration's: {odd}")
    jax.tree_util.tree_map(lambda x: x.delete(), trainer.state.params)
    params = weights.make_weights(cell.config, seed, lora_r, out_shardings=trainer.shardings)
    trainer.state = trainer.state.replace(params=params)


def capture_losses(trainer) -> list:
    """The losses ``fit`` logs, in update order, without a metrics file."""
    seen: list = []
    log = trainer.metrics.log

    def tee(metrics, step=None):
        if "loss" in metrics and "update_step" in metrics:
            seen.append((int(metrics["update_step"]), float(metrics["loss"])))
        return log(metrics, step=step)

    trainer.metrics.log = tee
    return seen


def compare(check: harness.Check, program: dict, reference: dict, limits: dict) -> None:
    """The training numbers, each beside its limit (``PERF.md`` §2 says where
    each limit comes from)."""
    # a loss is compared where the cell's file gives it a limit: PERF.md §2
    # says which updates' losses have no upper reading and are left out
    for i, want in enumerate(reference["losses"]):
        name = f"loss_{i + 1}_gap"
        if name in limits:
            got = program["losses"][i] if i < len(program["losses"]) else float("nan")
            check.add(name, abs(got - want), limits[name])
    check.add("grad_norm_gap", worst_leaf_gap(program["grad_norms"], reference["grad_norms"]), limits["grad_norm_gap"])
    # leaves whose first gradient is nought to rounding in the reference move
    # under Adam by round-off alone: left out of the change by that rule
    g = np.concatenate([np.ravel(v) for v in reference["grad_norms"].values()])
    floor = 1e-3 * float(np.median(g))
    moved = {
        p: np.where(np.ravel(reference["grad_norms"][p]) >= floor, 1.0, np.nan) for p in reference["change_norms"]
    }
    check.add(
        "change_norm_gap",
        worst_leaf_gap(program["change_norms"], reference["change_norms"], moved),
        limits["change_norm_gap"],
    )


def worst_leaf_gap(program: dict, reference: dict, keep: dict = None) -> float:
    """The worst leaf's |program's norm - reference's norm| over the larger of
    the reference's norm of that leaf and of the median leaf."""
    ref = np.concatenate([np.ravel(reference[p]) for p in sorted(reference)])
    got = np.concatenate([np.ravel(program[p]) for p in sorted(reference)])
    gap = np.abs(got - ref) / np.maximum(ref, np.median(ref))
    if keep is not None:
        gap = gap * np.concatenate([np.ravel(keep[p]) for p in sorted(reference)])
    if np.isnan(got).any():
        return float("nan")
    return float(np.nanmax(gap))


def run_reference(cell: harness.Cell, seed: int, batches: list, *, cast=None, half_batch=False) -> dict:
    from benchmark.reference import neox

    params = weights.make_weights(cell.config, seed, cell.workload["lora_r"])
    return neox.train_readings(
        params, batches, cell.config, hyperparameters(cell), cast=neox.CASTS[cast or "f32"], half_batch=half_batch
    )


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool, compiles: harness.CompileCounter) -> dict:
    import jax

    work_dir = os.path.join(harness.ROOT, ".bench_work", cell.name)
    trace_dir = os.path.join(work_dir, "trace") if trace else None
    trainer, train_factory = build(cell, seed, os.path.join(work_dir, "data"))
    losses = capture_losses(trainer)
    feed = WindowFeed(train_factory(), trainer, cell, weights.seed_key(seed), seconds, trace_dir, compiles)
    result = trainer.fit(feed, None, train_iter_factory=None)
    if feed.t_close is None:
        raise RuntimeError(f"fit ended before the window closed: {result}")
    window_s = feed.t_close - feed.t_open
    w = cell.workload
    tokens_per_update = w["global_batch"] * w["seq_length"]
    tokens = feed.updates_in_window * tokens_per_update
    peak = harness.memory_peak_bytes()
    steady_retraces = trainer.compile_watcher.steady_state_retraces

    program = {
        "losses": [l for _, l in sorted(losses)][: w["compared_updates"]],
        "grad_norms": feed.readings["grad_norms"],
        "change_norms": feed.readings["change_norms"],
    }
    # free the program's state before the reference runs
    jax.tree_util.tree_map(lambda x: x.delete() if hasattr(x, "delete") else None, trainer.state)
    del trainer
    t_ref = harness.now()
    reference = run_reference(cell, seed, feed.batches)
    check = harness.Check()
    compare(check, program, reference, w["limits"])
    check.add("compiles_in_window", feed.compiles_in_window + steady_retraces, 0)
    shutil.rmtree(os.path.join(work_dir, "data"), ignore_errors=True)

    return {
        "end_to_end": {"train_tokens_per_s": tokens / window_s},
        "attempted": feed.updates_in_window,
        "failed": int(result.get("n_skipped", 0)),
        "t_open": feed.t_open,
        "window_s": window_s,
        # per-layer rates leave out the seconds the profiler itself held the loop
        "layer_window_s": window_s - feed.profiler_s,
        "memory_peak_bytes": peak,
        "check": check,
        "reference_s": harness.now() - t_ref,
        "trace_dir": trace_dir,
        "trace_span": feed.trace_span,
        "debug": {"batches": feed.batches, "program": program, "reference": reference},
        "obs": {
            "counters": {"updates": feed.updates_in_window, "compiles_in_window": feed.compiles_in_window},
            "host": {"data_wait_s": feed.data_wait_s, "data_waits": feed.data_waits},
            "work": {
                "required_flops": tokens * flops.train_flops_per_token(cell.config, w["seq_length"], w["lora_r"]),
                "flash_attention": flops.scaled(
                    flops.flash_attention_train(cell.config, w["global_batch"], w["seq_length"]),
                    feed.trace_span[2] if feed.trace_span else 0,
                ),
            },
        },
    }
