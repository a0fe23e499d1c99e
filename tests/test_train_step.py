"""Train-step tests: loss descent, NaN gating, and the sharded multi-device
path on an 8-virtual-device CPU mesh (the capability the reference never had
an equivalent of — SURVEY.md §4)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from relora_tpu.config.model import MODEL_ZOO, ModelConfig
from relora_tpu.core.optim import build_optimizer
from relora_tpu.core.relora import LoraSpec, trainable_param_mask
from relora_tpu.models.llama import LlamaForCausalLM
from relora_tpu.models.params_util import init_params, logical_partition_specs
from relora_tpu.parallel.mesh import (
    MeshSpec,
    batch_sharding,
    make_mesh,
    param_shardings,
    shard_params,
)
from relora_tpu.train.state import TrainState
from relora_tpu.train.step import make_eval_step, make_train_step

TINY = ModelConfig(
    vocab_size=128,
    hidden_size=32,
    intermediate_size=64,
    num_hidden_layers=2,
    num_attention_heads=2,
    max_sequence_length=32,
)


def build(lora=None, lr=1e-2):
    model = LlamaForCausalLM(TINY, lora=lora, dtype=jnp.float32)
    params = init_params(model, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    mask = trainable_param_mask(params)
    tx = build_optimizer(schedule=lambda s: lr)
    from relora_tpu.core.partition import partition

    trainable, _ = partition(params, mask)
    opt_state = tx.init(trainable)
    state = TrainState.create(params, opt_state)
    step = make_train_step(model, tx, mask, clip_grad_norm=1.0, schedule=lambda s: lr)
    return model, state, step


def test_loss_decreases_full_rank():
    model, state, step = build()
    step = jax.jit(step, donate_argnums=0)
    batch = jax.random.randint(jax.random.PRNGKey(1), (2, 4, 16), 0, 128)  # (ga, micro, seq)
    first = None
    for i in range(30):
        state, metrics = step(state, batch, jax.random.PRNGKey(i))
        if first is None:
            first = float(metrics["loss"])
    assert int(state.step) == 30
    assert float(metrics["loss"]) < first * 0.7
    assert float(metrics["lr"]) == pytest.approx(1e-2)
    assert int(state.n_skipped) == 0


def test_loss_decreases_lora_only_trainables_move():
    spec = LoraSpec(r=4, alpha=32, dropout=0.0)
    model, state, step = build(lora=spec)
    step = jax.jit(step, donate_argnums=0)
    batch = jax.random.randint(jax.random.PRNGKey(1), (1, 4, 16), 0, 128)
    frozen_kernel_before = np.asarray(
        state.params["layers"]["self_attn"]["q_proj"]["kernel"]
    ).copy()
    lora_b_before = np.asarray(
        state.params["layers"]["self_attn"]["q_proj"]["lora_b"]
    ).copy()
    first = None
    for i in range(20):
        state, metrics = step(state, batch, jax.random.PRNGKey(i))
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first
    # frozen base kernel unchanged; lora_b moved off zero
    np.testing.assert_array_equal(
        np.asarray(state.params["layers"]["self_attn"]["q_proj"]["kernel"]),
        frozen_kernel_before,
    )
    assert np.abs(np.asarray(state.params["layers"]["self_attn"]["q_proj"]["lora_b"])).max() > 0
    assert np.abs(lora_b_before).max() == 0


def test_bf16_base_storage_trains_and_stays_bf16():
    """base_dtype='bf16' stores ONLY the frozen LoRA-base kernels in bf16
    (trainables — LoRA factors, embeddings, norms, lm_head — keep the f32
    master) and the step still descends."""
    spec = LoraSpec(r=4, alpha=32, dropout=0.0, base_dtype="bf16")
    model, state, step = build(lora=spec)
    attn = state.params["layers"]["self_attn"]
    assert attn["q_proj"]["kernel"].dtype == jnp.bfloat16
    assert attn["q_proj"]["lora_a"].dtype == jnp.float32
    assert state.params["embed_tokens"]["embedding"].dtype == jnp.float32
    assert state.params["lm_head"]["kernel"].dtype == jnp.float32

    step = jax.jit(step, donate_argnums=0)
    batch = jax.random.randint(jax.random.PRNGKey(1), (1, 4, 16), 0, 128)
    first = None
    for i in range(20):
        state, metrics = step(state, batch, jax.random.PRNGKey(i))
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first
    assert state.params["layers"]["self_attn"]["q_proj"]["kernel"].dtype == jnp.bfloat16


def test_nan_gate_skips_update_but_advances_step():
    model, state, step = build()
    step = jax.jit(step)
    # poison one param with NaN -> loss is NaN -> update must be skipped
    poisoned = state.replace(
        params={
            **state.params,
            "lm_head": {
                "kernel": state.params["lm_head"]["kernel"].at[0, 0].set(jnp.nan)
            },
        }
    )
    batch = jax.random.randint(jax.random.PRNGKey(1), (1, 2, 16), 0, 128)
    new_state, metrics = step(poisoned, batch, jax.random.PRNGKey(0))
    assert float(metrics["skipped"]) == 1.0
    assert int(new_state.step) == 1
    assert int(new_state.n_skipped) == 1
    # untouched (non-poisoned) params identical — no partial update
    np.testing.assert_array_equal(
        np.asarray(new_state.params["embed_tokens"]["embedding"]),
        np.asarray(poisoned.params["embed_tokens"]["embedding"]),
    )
    # optimizer state unchanged (schedule count rolled back too)
    for a, b in zip(
        jax.tree_util.tree_leaves(new_state.opt_state),
        jax.tree_util.tree_leaves(poisoned.opt_state),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_logged_lr_tracks_applied_schedule_after_skip():
    """After a NaN skip the reported lr must match the rolled-back schedule
    count (number of applied updates), not state.step."""
    schedule = lambda s: 1e-2 * (s + 1)
    model = LlamaForCausalLM(TINY, lora=None, dtype=jnp.float32)
    from relora_tpu.models.params_util import init_params as ip

    params = ip(model, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    mask = trainable_param_mask(params)
    tx = build_optimizer(schedule=schedule)
    from relora_tpu.core.partition import partition

    opt_state = tx.init(partition(params, mask)[0])
    state = TrainState.create(params, opt_state)
    step = jax.jit(make_train_step(model, tx, mask, schedule=schedule))
    batch = jax.random.randint(jax.random.PRNGKey(1), (1, 2, 16), 0, 128)

    # poison params -> skipped step; then a clean step
    poisoned = state.replace(
        params={
            **state.params,
            "lm_head": {"kernel": state.params["lm_head"]["kernel"].at[0, 0].set(jnp.nan)},
        }
    )
    s1, m1 = step(poisoned, batch, jax.random.PRNGKey(0))
    assert float(m1["skipped"]) == 1.0
    # repair params, keep counters: next applied update uses schedule count 0
    repaired = s1.replace(
        params={
            **s1.params,
            "lm_head": {"kernel": jnp.nan_to_num(s1.params["lm_head"]["kernel"])},
        }
    )
    s2, m2 = step(repaired, batch, jax.random.PRNGKey(2))
    assert float(m2["skipped"]) == 0.0
    # step index was 1 but 0 updates applied before it -> lr = schedule(0)
    np.testing.assert_allclose(float(m2["lr"]), schedule(0), rtol=1e-6)


def test_eval_step_returns_weighted_sums():
    model, state, _ = build()
    eval_step = jax.jit(make_eval_step(model))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (4, 16), 0, 128)
    out = eval_step(state.params, tokens)
    assert float(out["n_tokens"]) == 4 * 15
    assert np.isfinite(float(out["loss_sum"]))


@pytest.mark.parametrize("loss_impl", ["dense", "chunked"])
def test_next_token_window_gives_the_same_loss_at_the_aligned_length(loss_impl):
    """A seq+1-token window (the Megatron pipeline's rows) read as seq inputs
    + seq labels scores the very predictions that reading all seq+1 tokens
    and dropping the last position's logits does — the model just runs at
    seq (tile-aligned) instead of seq+1."""
    model, state, _ = build()
    tokens = jax.random.randint(jax.random.PRNGKey(2), (4, 17), 0, 128)
    kw = dict(loss_impl=loss_impl, vocab_chunk=64)
    whole = jax.jit(make_eval_step(model, **kw))(state.params, tokens)
    window = jax.jit(make_eval_step(model, next_token_window=True, **kw))(state.params, tokens)
    assert float(window["n_tokens"]) == float(whole["n_tokens"]) == 4 * 16
    np.testing.assert_allclose(float(window["loss_sum"]), float(whole["loss_sum"]), rtol=1e-5)

    # and the train step takes such windows: one update, finite loss
    tx = build_optimizer(schedule=lambda s: 1e-2)
    mask = trainable_param_mask(state.params)
    step = jax.jit(
        make_train_step(model, tx, mask, schedule=lambda s: 1e-2, next_token_window=True, **kw)
    )
    _, metrics = step(state, tokens[None], jax.random.PRNGKey(0))
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.usefixtures("devices")
def test_sharded_train_step_on_mesh():
    """FSDP×TP×DP sharded step on 8 virtual devices: params sharded by the
    logical rules, batch sharded on (data, fsdp), one step runs and the loss
    matches the unsharded step."""
    spec = LoraSpec(r=4, alpha=32, dropout=0.0)
    model = LlamaForCausalLM(TINY, lora=spec, dtype=jnp.float32)
    sample = jnp.zeros((1, 8), jnp.int32)
    params = init_params(model, jax.random.PRNGKey(0), sample)
    mask = trainable_param_mask(params)
    tx = build_optimizer(schedule=lambda s: 1e-2)
    from relora_tpu.core.partition import partition

    trainable, _ = partition(params, mask)
    opt_state = tx.init(trainable)
    state = TrainState.create(params, opt_state)
    step_fn = make_train_step(model, tx, mask, schedule=lambda s: 1e-2)

    mesh = make_mesh(MeshSpec(data=2, fsdp=2, tensor=2))
    specs = logical_partition_specs(model, sample)
    shardings = param_shardings(mesh, specs)
    sharded_params = shard_params(params, shardings)
    sharded_state = TrainState.create(sharded_params, jax.jit(tx.init)(partition(sharded_params, mask)[0]))

    batch = jax.random.randint(jax.random.PRNGKey(1), (2, 8, 16), 0, 128)
    sharded_batch = jax.device_put(batch, batch_sharding(mesh))

    jitted = jax.jit(step_fn)
    new_sharded, m_sharded = jitted(sharded_state, sharded_batch, jax.random.PRNGKey(0))
    new_plain, m_plain = jax.jit(step_fn)(state, batch, jax.random.PRNGKey(0))

    assert np.isfinite(float(m_sharded["loss"]))
    assert float(m_sharded["loss"]) == pytest.approx(float(m_plain["loss"]), rel=1e-4)
    # param kernels really are distributed: embed dim sharded over fsdp
    k = new_sharded.params["layers"]["self_attn"]["q_proj"]["kernel"]
    assert not k.sharding.is_fully_replicated
    # and the updated sharded params match the unsharded update
    np.testing.assert_allclose(
        np.asarray(new_sharded.params["layers"]["mlp"]["gate_proj"]["lora_b"]),
        np.asarray(new_plain.params["layers"]["mlp"]["gate_proj"]["lora_b"]),
        atol=1e-5,
    )


def test_mesh_spec_validation():
    with pytest.raises(ValueError):
        MeshSpec(data=3, fsdp=3).resolve(8)
    assert MeshSpec(data=-1, fsdp=4).resolve(8) == (2, 4, 1, 1)


@pytest.mark.usefixtures("devices")
def test_sequence_parallel_train_step_ring_attention():
    """Full train step with context parallelism: sequence sharded over a
    4-way ring, loss matches the single-device step."""
    from relora_tpu.parallel.mesh import set_current_mesh

    spec = LoraSpec(r=4, alpha=32, dropout=0.0)
    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    set_current_mesh(mesh)
    try:
        model = LlamaForCausalLM(TINY, lora=spec, dtype=jnp.float32, attention_impl="ring")
        ref_model = LlamaForCausalLM(TINY, lora=spec, dtype=jnp.float32)
        sample = jnp.zeros((1, 8), jnp.int32)
        params = init_params(ref_model, jax.random.PRNGKey(0), sample)
        mask = trainable_param_mask(params)
        tx = build_optimizer(schedule=lambda s: 1e-2)
        from relora_tpu.core.partition import partition

        opt_state = tx.init(partition(params, mask)[0])

        sharded_params = shard_params(params, param_shardings(mesh, logical_partition_specs(ref_model, sample)))
        with mesh:
            sharded_state = TrainState.create(
                sharded_params, jax.jit(tx.init)(partition(sharded_params, mask)[0])
            )
        plain_state = TrainState.create(params, opt_state)

        batch = jax.random.randint(jax.random.PRNGKey(1), (1, 4, 32), 0, 128)
        sharded_batch = jax.device_put(batch, batch_sharding(mesh, seq_sharded=True))

        step_ring = jax.jit(make_train_step(model, tx, mask, schedule=lambda s: 1e-2))
        step_ref = jax.jit(make_train_step(ref_model, tx, mask, schedule=lambda s: 1e-2))
        _, m_ring = step_ring(sharded_state, sharded_batch, jax.random.PRNGKey(2))
        _, m_ref = step_ref(plain_state, batch, jax.random.PRNGKey(2))
        assert float(m_ring["loss"]) == pytest.approx(float(m_ref["loss"]), rel=1e-4)
    finally:
        set_current_mesh(None)


@pytest.mark.usefixtures("devices")
def test_zigzag_layout_train_step_matches_plain():
    """End-to-end zigzag context parallelism: the permuted-layout train step
    (zigzag attention + permuted positions + pre-shifted labels) computes
    the same loss as the plain single-device step."""
    from relora_tpu.parallel.mesh import set_current_mesh

    spec = LoraSpec(r=4, alpha=32, dropout=0.0)
    mesh = make_mesh(MeshSpec(data=2, sequence=4))
    set_current_mesh(mesh)
    try:
        zz_model = LlamaForCausalLM(TINY, lora=spec, dtype=jnp.float32, attention_impl="ring_zigzag")
        ref_model = LlamaForCausalLM(TINY, lora=spec, dtype=jnp.float32)
        sample = jnp.zeros((2, 8), jnp.int32)
        params = init_params(ref_model, jax.random.PRNGKey(0), sample)
        mask = trainable_param_mask(params)
        tx = build_optimizer(schedule=lambda s: 1e-2)
        from relora_tpu.core.partition import partition

        sharded_params = shard_params(
            params, param_shardings(mesh, logical_partition_specs(ref_model, sample))
        )
        with mesh:
            zz_state = TrainState.create(
                sharded_params, jax.jit(tx.init)(partition(sharded_params, mask)[0])
            )
        plain_state = TrainState.create(params, tx.init(partition(params, mask)[0]))

        batch = jax.random.randint(jax.random.PRNGKey(1), (1, 4, 32), 0, 128)
        zz_batch = jax.device_put(batch, batch_sharding(mesh, seq_sharded=True))

        step_zz = jax.jit(make_train_step(zz_model, tx, mask, schedule=lambda s: 1e-2, zigzag_ring=4))
        step_ref = jax.jit(make_train_step(ref_model, tx, mask, schedule=lambda s: 1e-2))
        new_zz, m_zz = step_zz(zz_state, zz_batch, jax.random.PRNGKey(2))
        new_ref, m_ref = step_ref(plain_state, batch, jax.random.PRNGKey(2))
        # zigzag loss averages over S valid labels vs S-1 in the shifted
        # path (the permuted layout keeps a -100 sentinel for the final
        # token), so compare losses directly: same mean over the same
        # (token, target) pairs
        assert float(m_zz["loss"]) == pytest.approx(float(m_ref["loss"]), rel=1e-4)
        # and gradients moved the same trainables the same way
        np.testing.assert_allclose(
            np.asarray(new_zz.params["layers"]["mlp"]["gate_proj"]["lora_b"]),
            np.asarray(new_ref.params["layers"]["mlp"]["gate_proj"]["lora_b"]),
            atol=1e-5,
        )
    finally:
        set_current_mesh(None)


def test_chunked_loss_train_step_matches_dense():
    """loss_impl=chunked (streamed vocab CE from hidden states) gives the
    same loss and updates as the dense path."""
    spec = LoraSpec(r=4, alpha=32, dropout=0.0)
    model = LlamaForCausalLM(TINY, lora=spec, dtype=jnp.float32)
    params = init_params(model, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    mask = trainable_param_mask(params)
    tx = build_optimizer(schedule=lambda s: 1e-2)
    from relora_tpu.core.partition import partition

    mk_state = lambda: TrainState.create(params, tx.init(partition(params, mask)[0]))
    batch = jax.random.randint(jax.random.PRNGKey(1), (1, 4, 16), 0, 128)

    dense = jax.jit(make_train_step(model, tx, mask, schedule=lambda s: 1e-2))
    chunked = jax.jit(
        make_train_step(model, tx, mask, schedule=lambda s: 1e-2,
                        loss_impl="chunked", vocab_chunk=48)  # 128 vocab, padded chunks
    )
    s_d, m_d = dense(mk_state(), batch, jax.random.PRNGKey(2))
    s_c, m_c = chunked(mk_state(), batch, jax.random.PRNGKey(2))
    assert float(m_c["loss"]) == pytest.approx(float(m_d["loss"]), rel=1e-5)
    np.testing.assert_allclose(
        np.asarray(s_c.params["layers"]["mlp"]["gate_proj"]["lora_b"]),
        np.asarray(s_d.params["layers"]["mlp"]["gate_proj"]["lora_b"]),
        atol=1e-6,
    )
    # lm_head is trainable; the chunked path's gradient through the streamed
    # projection matches the dense path's
    np.testing.assert_allclose(
        np.asarray(s_c.params["lm_head"]["kernel"]),
        np.asarray(s_d.params["lm_head"]["kernel"]),
        atol=1e-6,
    )


def _recipe_model(policy):
    """llama_9m as the recipe trains it: bf16, scanned, LoRA dropout 0.1."""
    return LlamaForCausalLM(
        MODEL_ZOO["llama_9m"],
        lora=LoraSpec(r=128, alpha=32, dropout=0.1),
        dtype=jnp.bfloat16,
        scan_layers=True,
        remat=True,
        remat_policy=policy,
    )


@functools.lru_cache(maxsize=None)
def _recipe_params():
    # the policy changes what the backward pass keeps, not the parameters
    return init_params(_recipe_model("full"), jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))


@functools.lru_cache(maxsize=None)
def _loss_after_three_updates(policy):
    from relora_tpu.core.partition import partition

    model, params = _recipe_model(policy), _recipe_params()
    mask = trainable_param_mask(params)
    tx = build_optimizer(schedule=lambda s: 1e-3)
    state = TrainState.create(params, tx.init(partition(params, mask)[0]))
    step = jax.jit(make_train_step(model, tx, mask))
    batch = jax.random.randint(jax.random.PRNGKey(1), (1, 2, 32), 0, model.config.vocab_size)
    for i in range(3):
        state, metrics = step(state, batch, jax.random.fold_in(jax.random.PRNGKey(2), i))
    return float(metrics["loss"])


@pytest.mark.parametrize("policy", ["dots", "dots_narrow", "dots_all"])
def test_remat_policy_matches_full(policy):
    """Saving matmul outputs instead of recomputing the whole layer must be
    a pure scheduling change: the same losses as 'full'."""
    full = _loss_after_three_updates("full")
    assert np.isfinite(full)
    np.testing.assert_allclose(_loss_after_three_updates(policy), full, rtol=1e-5)


def test_remat_policy_dots_narrow_predicate():
    """dots_narrow saves hidden-width dot outputs, recomputes wider ones and
    batched dots — checked directly against the policy callable."""
    from relora_tpu.models.params_util import remat_policy

    pol = remat_policy("dots_narrow", max_save_width=64)

    class P:
        name = "dot_general"

    class Aval:
        def __init__(self, shape):
            self.shape = shape

    dn = lambda rhs_c, batch=(): {"dimension_numbers": (((1,), rhs_c), (batch, batch))}
    # hidden-width projection (rhs 64x64): saved
    assert pol(P(), Aval((8, 64)), Aval((64, 64)), **dn((0,)))
    # wide MLP projection (rhs 64x171): recomputed
    assert not pol(P(), Aval((8, 64)), Aval((64, 171)), **dn((0,)))
    # down-projection back to hidden (rhs 171x64): saved
    assert pol(P(), Aval((8, 171)), Aval((171, 64)), **dn((0,)))
    # batched dot (attention QK^T shape): recomputed regardless of width
    assert not pol(P(), Aval((2, 8, 16)), Aval((2, 16, 8)), **dn((1,), (0,)))
    # non-dot primitives: never saved
    class Q:
        name = "exp"

    assert not pol(Q(), Aval((8, 64)))
    with pytest.raises(ValueError, match="max_save_width"):
        remat_policy("dots_narrow")


def test_remat_policy_unknown_raises():
    from relora_tpu.models.params_util import remat_policy

    with pytest.raises(ValueError, match="remat policy"):
        remat_policy("bogus")
