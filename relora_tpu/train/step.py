"""The jitted train/eval steps: scan gradient accumulation, NaN gating,
clipping — one compiled program per recipe.

Reference hot loop (torchrun_main.py:768-944): per-microbatch forward/backward
with Python-side accumulation, clip_grad_norm over trainable params (:805-808),
an all-reduced NaN gate that skips optimizer *and* scheduler on any NaN in the
update (:810-822), counters incremented regardless.

Here the whole update is one XLA program: ``lax.scan`` over the microbatch
axis accumulates grads on-device (no host round trips, reference's
grad-accum loop :796-800), the NaN gate is a ``jnp.where`` masked state
select (schedule state rolls back too, exactly matching the reference's
frozen scheduler on skipped steps), and under a mesh the batch/param
shardings make XLA insert the DDP/FSDP collectives.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from relora_tpu.core.optim import clip_by_global_norm
from relora_tpu.core.partition import combine, partition
from relora_tpu.train.losses import causal_lm_loss
from relora_tpu.train.state import TrainState

PyTree = Any


def _head_key(model) -> str:
    """Param name of the output projection ('lm_head' for llama, 'embed_out'
    for neox) — needed by the chunked-CE path."""
    cfg = getattr(model, "config", None)
    return "embed_out" if cfg is not None and cfg.family == "neox" else "lm_head"


def _zigzag_inputs(tokens: jax.Array, ring: int):
    """Permute tokens into the zigzag layout with matching positions and
    pre-shifted labels (position i's successor is not i+1 after permuting,
    so the shift happens in original order first)."""
    from relora_tpu.parallel.ring_attention import zigzag_permutation

    B, S = tokens.shape
    perm = jnp.asarray(zigzag_permutation(S, ring))  # static at trace time
    labels = jnp.concatenate(
        [tokens[:, 1:], jnp.full((B, 1), -100, tokens.dtype)], axis=1
    )
    return tokens[:, perm], labels[:, perm], perm[None, :]


def _model_inputs(tokens: jax.Array, zigzag_ring: Optional[int], next_token_window: bool):
    """``(tokens_in, labels, positions)`` for one batch of rows.

    ``next_token_window``: each row is a ``seq_length + 1``-token window (what
    the Megatron pipeline packs) — the model reads the first ``seq_length``
    tokens and is scored on the last ``seq_length``.  For a causal model
    these are the very predictions that reading the whole window and dropping
    the last position's logits gives, but the model runs at ``seq_length``
    (2048: tile-aligned, flash attention applies) instead of ``seq_length + 1``
    (2049: every activation padded, and a shape the TPU compiler fails on at
    one row per chip).  Otherwise the loss shifts inside (``labels=None``)."""
    if zigzag_ring:
        return _zigzag_inputs(tokens, zigzag_ring)
    if next_token_window:
        return tokens[:, :-1], tokens[:, 1:], None
    return tokens, None, None


def _make_loss_fn(
    model,
    *,
    loss_impl: str = "dense",
    vocab_chunk: int = 8192,
    zigzag_ring: Optional[int] = None,
    next_token_window: bool = False,
) -> Callable:
    """``loss_fn(trainable, frozen, tokens, rng) -> loss`` shared by the
    train step and the watch-histogram pass (one definition of the
    training loss; the chunked path never materializes (B, S, vocab)
    logits)."""
    if loss_impl not in ("dense", "chunked"):
        raise ValueError(f"loss_impl must be 'dense' or 'chunked', got {loss_impl!r}")

    def loss_fn(trainable: PyTree, frozen: PyTree, tokens: jax.Array, rng) -> jax.Array:
        params = combine(trainable, frozen)
        tokens_in, labels, positions = _model_inputs(tokens, zigzag_ring, next_token_window)
        if loss_impl == "chunked":
            from relora_tpu.train.losses import chunked_softmax_ce

            hidden = model.apply(
                {"params": params},
                tokens_in,
                positions=positions,
                deterministic=False,
                return_hidden=True,
                rngs={"dropout": rng},
            )
            if labels is None:
                B = tokens.shape[0]
                labels = jnp.concatenate(
                    [tokens[:, 1:], jnp.full((B, 1), -100, tokens.dtype)], axis=1
                )
            loss, _ = chunked_softmax_ce(
                hidden, params[_head_key(model)]["kernel"], labels, chunk_size=vocab_chunk
            )
            return loss
        logits = model.apply(
            {"params": params},
            tokens_in,
            positions=positions,
            deterministic=False,
            rngs={"dropout": rng},
        )
        loss, _ = causal_lm_loss(logits, tokens_in, labels=labels)
        return loss

    return loss_fn


def make_train_step(
    model,
    tx: optax.GradientTransformation,
    trainable_mask: PyTree,
    *,
    clip_grad_norm: float = 1.0,
    schedule: Optional[Callable] = None,
    grad_breakdown: bool = False,
    zigzag_ring: Optional[int] = None,
    next_token_window: bool = False,
    loss_impl: str = "dense",  # dense | chunked (streamed vocab CE)
    vocab_chunk: int = 8192,
    log_per_layer_scaling: bool = False,
    nan_grad_steps: Tuple[int, ...] = (),
) -> Callable[[TrainState, jax.Array, jax.Array], Tuple[TrainState, dict]]:
    """Build ``train_step(state, batch, rng) -> (state, metrics)``.

    ``batch``: int32 token ids shaped ``(grad_accum, microbatch, seq)`` —
    ``seq + 1`` under ``next_token_window`` (:func:`_model_inputs`).
    With ``zigzag_ring`` set, the model runs in the zigzag sequence layout
    (attention impl 'ring_zigzag'): tokens/positions/labels are permuted
    consistently inside the step.  The returned function is pure; jit it
    with donated state, e.g.::

        step = jax.jit(make_train_step(...), donate_argnums=0)

    ``nan_grad_steps`` (fault injection, utils/faults.py): device step
    counts at which the accumulated gradients are poisoned with NaN before
    clipping, exercising the NaN gate exactly where a real overflow would
    hit it.  Empty (the default) compiles to nothing.
    """

    loss_fn = _make_loss_fn(
        model,
        loss_impl=loss_impl,
        vocab_chunk=vocab_chunk,
        zigzag_ring=zigzag_ring,
        next_token_window=next_token_window,
    )
    grad_fn = jax.value_and_grad(loss_fn)

    def train_step(state: TrainState, batch: jax.Array, rng: jax.Array):
        trainable, frozen = partition(state.params, trainable_mask)
        ga = batch.shape[0]
        rngs = jax.random.split(rng, ga)

        def micro(acc, inp):
            tokens, mrng = inp
            loss, grads = grad_fn(trainable, frozen, tokens, mrng)
            acc_grads, acc_loss, acc_nan = acc
            acc_grads = jax.tree_util.tree_map(jnp.add, acc_grads, grads)
            return (acc_grads, acc_loss + loss, acc_nan + jnp.isnan(loss)), None

        zero_grads = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), trainable
        )
        (grads, loss_sum, nan_count), _ = jax.lax.scan(
            micro, (zero_grads, jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)), (batch, rngs)
        )
        grads = jax.tree_util.tree_map(lambda g: g / ga, grads)
        mean_loss = loss_sum / ga

        if nan_grad_steps:
            poison = functools.reduce(
                jnp.logical_or, [state.step == s for s in nan_grad_steps]
            )
            grads = jax.tree_util.tree_map(
                lambda g: jnp.where(poison, jnp.full_like(g, jnp.nan), g), grads
            )

        if clip_grad_norm > 0:
            grads, grad_norm = clip_by_global_norm(grads, clip_grad_norm)
        else:
            from relora_tpu.core.optim import global_norm

            grad_norm = global_norm(grads)

        updates, new_opt_state = tx.update(grads, state.opt_state, trainable)
        new_trainable = optax.apply_updates(trainable, updates)

        # NaN gate (parity: torchrun_main.py:813-822): on any NaN in the
        # accumulated update, keep params AND optimizer/schedule state
        # unchanged (the reference skips optimizer.step() and
        # scheduler.step()); update_step still advances.
        skip = (nan_count > 0) | ~jnp.isfinite(grad_norm)

        def select(new, old):
            return jax.tree_util.tree_map(
                lambda n, o: jnp.where(skip, o, n), new, old
            )

        final_trainable = select(new_trainable, trainable)
        final_opt_state = select(new_opt_state, state.opt_state)

        new_state = state.replace(
            step=state.step + 1,
            params=combine(final_trainable, frozen),
            opt_state=final_opt_state,
            n_skipped=state.n_skipped + skip.astype(jnp.int32),
        )
        metrics = {
            "loss": mean_loss,
            "grad_norm": grad_norm,
            "skipped": skip.astype(jnp.float32),
            "n_skipped": new_state.n_skipped,
        }
        if schedule is not None:
            # the optax schedule count lives in opt_state and rolls back on
            # NaN skips, so the count the update actually used is the number
            # of previously *applied* steps, not state.step
            metrics["lr"] = schedule(state.step - state.n_skipped)
        if grad_breakdown:
            # per-top-level-subtree grad norms (the observability wandb.watch
            # provided in the reference, torchrun_main.py:624-627)
            from relora_tpu.core.optim import global_norm

            for key, sub in grads.items():
                metrics[f"grad_norm/{key}"] = global_norm(sub)
        # trainable-scaling observability (parity: per-layer lora_scaling
        # logging under --train_scaling, torchrun_main.py:937-942)
        scaling_leaves = [
            (path, leaf)
            for path, leaf in jax.tree_util.tree_flatten_with_path(final_trainable)[0]
            if str(getattr(path[-1], "key", path[-1])) == "lora_s"
        ]
        if scaling_leaves:
            # mean of the *effective* scales (tanh applied per leaf, exactly
            # as the forward pass uses them)
            effective = [jnp.tanh(l.astype(jnp.float32)) for _, l in scaling_leaves]
            metrics["lora_scaling"] = jnp.mean(jnp.stack([e.mean() for e in effective]))
            if log_per_layer_scaling:
                for (path, _), eff in zip(scaling_leaves, effective):
                    name = ".".join(
                        str(getattr(k, "key", k)) for k in path[:-1]
                    )
                    if eff.ndim >= 1 and eff.shape[0] > 1:
                        # scan-stacked: leading axis is the layer index
                        per_layer = eff.reshape(eff.shape[0], -1).mean(axis=1)
                        for i in range(eff.shape[0]):
                            metrics[f"lora_scaling/{name}/layer{i}"] = per_layer[i]
                    else:
                        metrics[f"lora_scaling/{name}"] = eff.mean()
        return new_state, metrics

    return train_step


def make_eval_step(
    model,
    zigzag_ring: Optional[int] = None,
    loss_impl: str = "dense",
    vocab_chunk: int = 8192,
    next_token_window: bool = False,
) -> Callable[[PyTree, jax.Array], dict]:
    """``eval_step(params, tokens) -> {loss_sum_weighted, n_tokens}``.

    Under jit with a sharded batch, the sums are global (XLA inserts the
    psum) — the explicit ``dist.all_reduce`` of the reference's
    evaluate_model (torchrun_main.py:159-183) is implicit here.  Caller
    divides accumulated loss by accumulated tokens.
    """

    def eval_step(params: PyTree, tokens: jax.Array) -> dict:
        tokens_in, labels, positions = _model_inputs(tokens, zigzag_ring, next_token_window)
        if loss_impl == "chunked":
            from relora_tpu.train.losses import chunked_softmax_ce

            hidden = model.apply(
                {"params": params},
                tokens_in,
                positions=positions,
                deterministic=True,
                return_hidden=True,
            )
            if labels is None:
                B = tokens.shape[0]
                labels = jnp.concatenate(
                    [tokens[:, 1:], jnp.full((B, 1), -100, tokens.dtype)], axis=1
                )
            loss, n = chunked_softmax_ce(
                hidden, params[_head_key(model)]["kernel"], labels, chunk_size=vocab_chunk
            )
        else:
            logits = model.apply(
                {"params": params}, tokens_in, positions=positions, deterministic=True
            )
            loss, n = causal_lm_loss(logits, tokens_in, labels=labels)
        return {"loss_sum": loss * n, "n_tokens": n}

    return eval_step


def make_watch_histograms(
    model,
    trainable_mask: PyTree,
    *,
    n_bins: int = 64,
    loss_impl: str = "dense",
    vocab_chunk: int = 8192,
    zigzag_ring: Optional[int] = None,
    next_token_window: bool = False,
):
    """Parameter + gradient histograms per top-level subtree — the
    observability ``wandb.watch(model)`` provided in the reference
    (torchrun_main.py:624-627), as a pure jittable function run off the hot
    path at watch cadence (the train step itself only carries the cheap
    grad-norm breakdown).

    Returns ``watch(params, tokens, rng) -> {"hist/param/<key>": (counts,
    edges), "hist/grad/<key>": ...}`` where ``tokens`` is ONE microbatch
    ``(micro, seq)``.  Gradients come from a dedicated backward pass using
    the SAME loss as training (loss_impl/zigzag honored — a chunked-loss
    config stays chunked here, its whole point is that dense logits don't
    fit), so the histograms reflect raw per-parameter grads, not the
    accumulated/clipped update.

    Each subtree is histogrammed leaf-by-leaf against shared min/max
    edges and the counts summed — no concatenated f32 copy of the whole
    subtree (that transient would double the frozen base's footprint)."""
    loss_fn = _make_loss_fn(
        model,
        loss_impl=loss_impl,
        vocab_chunk=vocab_chunk,
        zigzag_ring=zigzag_ring,
        next_token_window=next_token_window,
    )

    def hist_tree(tree: PyTree, prefix: str) -> dict:
        out = {}
        for key, sub in tree.items():
            leaves = [
                l.ravel().astype(jnp.float32)
                for l in jax.tree_util.tree_leaves(sub)
            ]
            if not leaves:
                continue
            # min/max over FINITE values only: one NaN grad (the event the
            # step's NaN gate deliberately survives) must not poison the
            # edges into all-NaN and crash the wandb sink
            fin = [jnp.isfinite(l) for l in leaves]
            lo = functools.reduce(
                jnp.minimum,
                [jnp.min(jnp.where(f, l, jnp.inf)) for l, f in zip(leaves, fin)],
            )
            hi = functools.reduce(
                jnp.maximum,
                [jnp.max(jnp.where(f, l, -jnp.inf)) for l, f in zip(leaves, fin)],
            )
            any_finite = jnp.isfinite(lo) & jnp.isfinite(hi)
            lo = jnp.where(any_finite, lo, 0.0)
            hi = jnp.where(any_finite & (hi > lo), hi, lo + 1e-6)
            edges = lo + (hi - lo) * jnp.arange(n_bins + 1, dtype=jnp.float32) / n_bins
            counts = sum(
                # non-finite values become +inf: always beyond the finite
                # top edge, so histogram drops them instead of polluting a
                # bin (hi + 1.0 would collapse onto the edge once hi >= 2^24
                # in f32 and count spikes into the top bin)
                jnp.histogram(jnp.where(f, l, jnp.inf), bins=edges)[0]
                for l, f in zip(leaves, fin)
            )
            out[f"{prefix}{key}"] = (counts, edges)
        return out

    def watch(params: PyTree, tokens: jax.Array, rng: jax.Array) -> dict:
        trainable, frozen = partition(params, trainable_mask)
        grads = jax.grad(loss_fn)(trainable, frozen, tokens, rng)
        out = hist_tree(params, "hist/param/")
        out.update(hist_tree(grads, "hist/grad/"))
        return out

    return watch
