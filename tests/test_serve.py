"""Serve-path tests: cached-decode parity, engine steps, continuous batching.

The acceptance oracle for the inference subsystem: prefill + decode-with-cache
must reproduce the teacher-forced full forward *exactly* (f32, atol 1e-5) at
every position for both model families, and the continuous-batching scheduler
must drain a mixed-length, staggered, early-EOS batch to the same tokens as
unbatched greedy decode.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relora_tpu.config.model import ModelConfig
from relora_tpu.models.params_util import init_params
from relora_tpu.serve.engine import InferenceEngine, bucket_length, build_decode_model
from relora_tpu.serve.sampling import SamplingParams
from relora_tpu.serve.scheduler import ContinuousBatchingScheduler, Request

pytestmark = pytest.mark.serve

TINY_LLAMA = ModelConfig(
    family="llama",
    vocab_size=256,
    hidden_size=64,
    intermediate_size=160,
    num_hidden_layers=2,
    num_attention_heads=4,
    max_sequence_length=64,
)
TINY_NEOX = ModelConfig(
    family="neox",
    vocab_size=256,
    hidden_size=64,
    intermediate_size=160,
    num_hidden_layers=2,
    num_attention_heads=4,
    max_sequence_length=64,
    rotary_pct=0.25,
)
TINY_GQA = ModelConfig(
    family="llama",
    vocab_size=256,
    hidden_size=64,
    intermediate_size=160,
    num_hidden_layers=2,
    num_attention_heads=4,
    num_key_value_heads=2,
    max_sequence_length=64,
)

FAMILIES = [
    pytest.param(TINY_LLAMA, id="llama"),
    pytest.param(TINY_NEOX, id="neox"),
    pytest.param(TINY_GQA, id="llama-gqa"),
]


def make_engine(cfg, *, cache_size=32, scan_layers=True, seed=0):
    model = build_decode_model(cfg, cache_size=cache_size, scan_layers=scan_layers)
    base = type(model)(cfg, lora=None, dtype=jnp.float32, scan_layers=scan_layers)
    ids = jnp.zeros((1, 8), jnp.int32)
    params = init_params(base, jax.random.PRNGKey(seed), ids)
    engine = InferenceEngine(
        cfg, params, cache_size=cache_size, scan_layers=scan_layers
    )
    return engine, base, params


@pytest.mark.parametrize("cfg", FAMILIES)
@pytest.mark.parametrize("scan_layers", [True, False], ids=["scan", "unroll"])
def test_prefill_decode_matches_full_forward(cfg, scan_layers):
    """Acceptance parity: prefill(0..p) then one-token decode for each later
    position reproduces the teacher-forced logits at EVERY position."""
    engine, base, params = make_engine(cfg, scan_layers=scan_layers)
    S, prefill_len = 12, 5
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0, cfg.vocab_size)
    full = base.apply({"params": params}, ids)

    logits, cache = engine.prefill(ids[:, :prefill_len])
    np.testing.assert_allclose(
        np.asarray(logits), np.asarray(full[:, :prefill_len]), atol=1e-5
    )
    pos = np.full((2, 1), prefill_len, np.int32)
    for t in range(prefill_len, S):
        step, cache = engine.decode(cache, ids[:, t : t + 1], jnp.asarray(pos))
        np.testing.assert_allclose(np.asarray(step), np.asarray(full[:, t]), atol=1e-5)
        pos += 1


@pytest.mark.parametrize("cfg", [FAMILIES[0], FAMILIES[1]])
def test_right_padded_prefill_parity(cfg):
    """Rows shorter than the prefill bucket must produce the same logits (at
    their real positions) and the same decode continuation as unpadded rows —
    pad garbage beyond a row's length is overwritten before it is visible."""
    engine, base, params = make_engine(cfg)
    L = 6
    ids = jax.random.randint(jax.random.PRNGKey(2), (1, L), 0, cfg.vocab_size)
    full = base.apply({"params": params}, ids)

    padded = np.zeros((1, 16), np.int32)
    padded[0, :L] = np.asarray(ids[0])
    logits, cache = engine.prefill(jnp.asarray(padded))
    np.testing.assert_allclose(np.asarray(logits[:, :L]), np.asarray(full), atol=1e-5)

    # greedy continuation from the padded cache == teacher-forced next logits
    nxt = jnp.argmax(logits[:, L - 1], axis=-1)
    step, _ = engine.decode(cache, nxt[:, None], jnp.full((1, 1), L, jnp.int32))
    ref_ids = jnp.concatenate([ids, nxt[:, None]], axis=1)
    ref = base.apply({"params": params}, ref_ids)
    np.testing.assert_allclose(np.asarray(step[0]), np.asarray(ref[0, L]), atol=1e-5)


def unbatched_greedy(engine, prompt, max_new_tokens, eos_id=None):
    """Reference decode: one request alone through the engine."""
    [tokens] = engine.generate(
        [list(prompt)], max_new_tokens=max_new_tokens, eos_id=eos_id
    )
    return tokens


@pytest.mark.parametrize("cfg", [FAMILIES[0], FAMILIES[1]])
def test_scheduler_matches_unbatched_greedy(cfg):
    """Acceptance: staggered admissions + mixed lengths + early EOS drain to
    exactly the unbatched greedy tokens."""
    engine, _, _ = make_engine(cfg, cache_size=48)
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(0, cfg.vocab_size, size=n)) for n in (3, 7, 5, 11, 2)]
    max_new = 8

    # pick an EOS that actually fires early for at least one request: a token
    # some unbatched greedy stream emits mid-generation
    refs_no_eos = [unbatched_greedy(engine, p, max_new) for p in prompts]
    eos_id = refs_no_eos[1][2]
    refs = [unbatched_greedy(engine, p, max_new, eos_id=eos_id) for p in prompts]
    assert any(len(r) < max_new for r in refs), "EOS must fire early for the test to bite"
    assert len({len(r) for r in refs}) > 1, "mixed completion lengths expected"

    # max_batch=2 over 5 requests forces staggered admissions and slot reuse
    sched = ContinuousBatchingScheduler(engine, max_batch=2, eos_id=eos_id)
    completions = sched.run(
        [Request(uid=i, prompt=p, max_new_tokens=max_new) for i, p in enumerate(prompts)]
    )
    assert sorted(completions) == list(range(len(prompts)))
    for i, ref in enumerate(refs):
        assert completions[i].tokens == ref, f"request {i} diverged from unbatched greedy"
        expected = "eos" if ref[-1] == eos_id else "length"
        assert completions[i].finish_reason == expected


def test_scheduler_sampled_stream_independent_of_batching():
    """A sampled request's tokens depend on (key, uid, step) only — not on
    which other requests shared its decode batches."""
    engine, _, _ = make_engine(TINY_LLAMA, cache_size=48)
    key = jax.random.PRNGKey(7)
    reqs = [
        Request(uid=i, prompt=[1 + i, 2, 3], max_new_tokens=6, temperature=0.9)
        for i in range(3)
    ]
    solo = {}
    for r in reqs:
        sched = ContinuousBatchingScheduler(engine, max_batch=1, key=key)
        solo[r.uid] = sched.run([r])[r.uid].tokens
    batched = ContinuousBatchingScheduler(engine, max_batch=3, key=key).run(reqs)
    for r in reqs:
        assert batched[r.uid].tokens == solo[r.uid]


def test_scheduler_incremental_api_matches_run():
    """submit + step-until-idle produces exactly what run() produces, and the
    token callbacks replay each request's stream in order — the contract the
    HTTP front-end is built on."""
    engine, _, _ = make_engine(TINY_LLAMA, cache_size=48)
    key = jax.random.PRNGKey(3)
    reqs = [
        Request(uid=i, prompt=[1 + i, 2, 3], max_new_tokens=5, temperature=0.5)
        for i in range(3)
    ]
    ref = ContinuousBatchingScheduler(engine, max_batch=2, key=key).run(reqs)

    sched = ContinuousBatchingScheduler(engine, max_batch=2, key=key)
    streamed = {}
    completions = {}
    for r in reqs:
        sched.submit(
            r,
            on_token=lambda uid, tok, idx: streamed.setdefault(uid, []).append((idx, tok)),
            on_finish=lambda c: completions.__setitem__(c.uid, c),
        )
    while sched.has_work():
        sched.step()
    assert sorted(completions) == sorted(ref)
    for uid in ref:
        assert completions[uid].tokens == ref[uid].tokens
        assert [i for i, _ in streamed[uid]] == list(range(len(ref[uid].tokens)))
        assert [t for _, t in streamed[uid]] == ref[uid].tokens


def test_scheduler_validate_request_and_duplicate_uid():
    engine, _, _ = make_engine(TINY_LLAMA, cache_size=16)
    sched = ContinuousBatchingScheduler(engine, max_batch=1)
    with pytest.raises(ValueError, match="empty prompt"):
        sched.validate_request(Request(uid=0, prompt=[], max_new_tokens=4))
    with pytest.raises(ValueError, match="cache entries"):
        sched.validate_request(Request(uid=0, prompt=[1] * 10, max_new_tokens=10))
    with pytest.raises(ValueError, match="max_new_tokens"):
        sched.validate_request(Request(uid=0, prompt=[1], max_new_tokens=0))
    sched.submit(Request(uid=5, prompt=[1, 2], max_new_tokens=4))
    with pytest.raises(ValueError, match="already in flight"):
        sched.submit(Request(uid=5, prompt=[3, 4], max_new_tokens=4))


def test_scheduler_cancel():
    """cancel() mid-decode reports the partial output and frees the slot;
    cancelling a queued request reports empty output; unknown uids (already
    finished — cancellation raced completion) return None."""
    engine, _, _ = make_engine(TINY_LLAMA, cache_size=48)
    sched = ContinuousBatchingScheduler(engine, max_batch=1)
    finishes = []
    sched.submit(
        Request(uid=0, prompt=[1, 2], max_new_tokens=8), on_finish=finishes.append
    )
    sched.submit(
        Request(uid=1, prompt=[3, 4], max_new_tokens=2), on_finish=finishes.append
    )
    sched.step()  # admits uid 0 (token 0) and decodes one round (token 1)
    assert sched.active_slots == 1 and sched.queue_depth == 1

    queued = sched.cancel(1)
    assert queued.finish_reason == "cancelled" and queued.tokens == []
    active = sched.cancel(0)
    assert active.finish_reason == "cancelled" and len(active.tokens) == 2
    assert sched.cancel(0) is None
    assert sched.active_slots == 0 and not sched.has_work()
    assert [c.uid for c in finishes] == [1, 0]


def test_scheduler_deadline_timeout():
    """Deadlines expire at step boundaries: a decoding request keeps its
    partial output with reason "timeout"; a request whose deadline passed
    while queued is never admitted (no prefill spent on it)."""
    engine, _, _ = make_engine(TINY_LLAMA, cache_size=48)
    sched = ContinuousBatchingScheduler(engine, max_batch=1)
    finishes = []
    sched.submit(
        Request(uid=0, prompt=[1, 2], max_new_tokens=40),
        on_finish=finishes.append,
        deadline=time.monotonic() + 60.0,
    )
    sched.submit(
        Request(uid=1, prompt=[3, 4], max_new_tokens=4),
        on_finish=finishes.append,
        deadline=time.monotonic() - 1.0,  # already expired when admission runs
    )
    for _ in range(3):
        sched.step()
    # force uid 0 past its deadline instead of sleeping: the expiry check
    # runs at the next step boundary either way
    sched._slots[0].deadline = time.monotonic() - 1.0
    done = {c.uid: c for c in sched.step()}
    assert done[0].finish_reason == "timeout"
    assert 0 < len(done[0].tokens) < 40
    assert done[1].finish_reason == "timeout" and done[1].tokens == []
    assert not sched.has_work()
    assert sorted(c.uid for c in finishes) == [0, 1]


def test_scheduler_step_gauge_records(tmp_path):
    """Every decode step logs queue-depth / active-slot gauges so load
    tooling has a per-step signal."""
    import json

    from relora_tpu.utils.logging import MetricsLogger

    engine, _, _ = make_engine(TINY_LLAMA, cache_size=48)
    metrics = MetricsLogger(run_dir=str(tmp_path))
    sched = ContinuousBatchingScheduler(engine, max_batch=2, metrics=metrics)
    sched.run([Request(uid=i, prompt=[1, 2, 3], max_new_tokens=4) for i in range(3)])
    metrics.finish()
    records = [
        json.loads(line) for line in (tmp_path / "metrics.jsonl").read_text().splitlines()
    ]
    gauges = [r for r in records if "serve/decode_step" in r]
    assert gauges
    assert [g["serve/decode_step"] for g in gauges] == list(range(1, len(gauges) + 1))
    assert all("serve/queue_depth" in g and "serve/active_slots" in g for g in gauges)
    assert max(g["serve/active_slots"] for g in gauges) == 2
    assert max(g["serve/queue_depth"] for g in gauges) >= 1


def test_scheduler_metrics_records(tmp_path):
    import json

    from relora_tpu.utils.logging import MetricsLogger

    engine, _, _ = make_engine(TINY_LLAMA, cache_size=48)
    metrics = MetricsLogger(run_dir=str(tmp_path))
    sched = ContinuousBatchingScheduler(engine, max_batch=2, metrics=metrics)
    sched.run([Request(uid=i, prompt=[1, 2, 3], max_new_tokens=4) for i in range(3)])
    metrics.finish()
    records = [
        json.loads(line) for line in (tmp_path / "metrics.jsonl").read_text().splitlines()
    ]
    served = [r for r in records if "serve_request" in r]
    assert len(served) == 3
    for r in served:
        assert r["serve/output_tokens"] == 4
        assert r["serve/finish_reason"] == "length"
        assert r["serve/latency_s"] >= r["serve/ttft_s"] >= 0.0
        assert r["serve/decode_tokens_per_s"] > 0.0


def test_generate_respects_eos_and_budget():
    engine, _, _ = make_engine(TINY_LLAMA, cache_size=48)
    outs = engine.generate([[5, 6], [7, 8, 9]], max_new_tokens=5)
    assert all(len(t) == 5 for t in outs)
    eos = outs[0][1]
    outs_eos = engine.generate([[5, 6], [7, 8, 9]], max_new_tokens=5, eos_id=eos)
    assert outs_eos[0] == outs[0][:2]  # truncated at its own EOS


def test_cache_capacity_guard():
    engine, _, _ = make_engine(TINY_LLAMA, cache_size=16)
    with pytest.raises(ValueError, match="exceeds cache capacity"):
        engine.generate([[1] * 10], max_new_tokens=10)
    sched = ContinuousBatchingScheduler(engine, max_batch=1)
    with pytest.raises(ValueError, match="cache entries"):
        sched.run([Request(uid=0, prompt=[1] * 10, max_new_tokens=10)])


def test_bucket_length():
    assert bucket_length(1) == 16
    assert bucket_length(16) == 16
    assert bucket_length(17) == 32
    assert bucket_length(100) == 128
    with pytest.raises(ValueError):
        bucket_length(0)


def test_engine_unmerged_lora_matches_merged():
    """serve.py --no-merge path: an engine holding raw LoRA factors (decode
    forward routed through the shape-aware dispatcher, weights_static) must
    generate exactly the same tokens as the default merge-at-load engine."""
    from relora_tpu.core.relora import LoraSpec, merged_params

    spec = LoraSpec(r=4, alpha=8)
    lora_model = build_decode_model(TINY_LLAMA, cache_size=32, lora=spec)
    ids = jnp.zeros((1, 8), jnp.int32)
    raw = init_params(lora_model, jax.random.PRNGKey(0), ids)
    # lora_b is zeros at init; perturb every lora_b so the branch contributes
    raw = jax.tree_util.tree_map_with_path(
        lambda path, t: (
            jax.random.normal(
                jax.random.PRNGKey(abs(hash(jax.tree_util.keystr(path))) % (2**31)),
                t.shape,
                t.dtype,
            )
            * 0.1
            if any(getattr(k, "key", None) == "lora_b" for k in path)
            else t
        ),
        raw,
    )
    unmerged = InferenceEngine(TINY_LLAMA, raw, cache_size=32, lora=spec)
    merged = InferenceEngine(
        TINY_LLAMA, merged_params(raw, spec), cache_size=32
    )
    prompts = [[1, 2, 3], [4, 5, 6, 7]]
    out_unmerged = unmerged.generate(prompts, max_new_tokens=6)
    out_merged = merged.generate(prompts, max_new_tokens=6)
    assert out_unmerged == out_merged


def test_engine_on_mesh():
    """Same engine code under an explicit device mesh: params shard per the
    logical rules, the cache batch axis shards over data, results match the
    meshless engine."""
    from relora_tpu.parallel.mesh import MeshSpec, make_mesh

    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    mesh = make_mesh(MeshSpec(data=2, fsdp=1, tensor=1, sequence=1), jax.devices()[:2])
    engine, base, params = make_engine(TINY_LLAMA, cache_size=32)
    sharded = InferenceEngine(TINY_LLAMA, params, cache_size=32, mesh=mesh)
    out_ref = engine.generate([[1, 2, 3], [4, 5, 6]], max_new_tokens=4)
    out_mesh = sharded.generate([[1, 2, 3], [4, 5, 6]], max_new_tokens=4)
    assert out_ref == out_mesh


# -- the held tree: every leaf in the dtype the decode model declares ---------

HELD_FAMILIES = [pytest.param(TINY_NEOX, id="neox"), pytest.param(TINY_LLAMA, id="llama")]
PAGED_BF16 = dict(cache_size=32, dtype=jnp.bfloat16, page_size=8, num_pages=9, chunk_size=8)


def f32_tree(cfg, seed=0, lora=None):
    """What a checkpoint restores to: the training layout, every float leaf f32."""
    model = type(build_decode_model(cfg, cache_size=32))(cfg, lora=lora, dtype=jnp.float32)
    return init_params(model, jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32))


def declared_dtype(path, compute):
    """The dtype a leaf is used in, by its name: norm leaves, ``lora_s`` and
    int8 scales in f32, int8 codes as they are, the rest in the compute dtype."""
    *modules, name = (k.key for k in path)
    if name == "kernel_q":
        return jnp.int8
    if name in ("lora_s", "kernel_scale") or any("norm" in m for m in modules):
        return jnp.float32
    return compute


def dtypes_of(tree):
    return jax.tree_util.tree_map(lambda x: x.dtype, tree)


@pytest.mark.parametrize("cfg", HELD_FAMILIES)
def test_engine_holds_every_leaf_in_its_declared_dtype(cfg):
    """An engine that computes in bf16 over an f32 tree holds kernels, linear
    biases, embeddings and LoRA factors in bf16, and what the forward uses in
    f32 in f32; one that computes in f32 holds the very leaves it was handed."""
    from relora_tpu.core.relora import LoraSpec

    spec = LoraSpec(r=4, alpha=8, dropout=0.0, trainable_scaling=True, quantize="int8")
    for lora, tree in ((None, f32_tree(cfg)), (spec, f32_tree(cfg, lora=spec))):
        assert all(x.dtype in (jnp.float32, jnp.int8) for x in jax.tree_util.tree_leaves(tree))
        held = InferenceEngine(cfg, tree, cache_size=32, dtype=jnp.bfloat16, lora=lora).params
        names = set()
        for path, leaf in jax.tree_util.tree_leaves_with_path(held):
            assert leaf.dtype == declared_dtype(path, jnp.bfloat16), jax.tree_util.keystr(path)
            names.add(path[-1].key)
        assert {"kernel", "embedding", "scale"} <= names
        if lora is not None:
            assert {"lora_a", "lora_b", "lora_s", "kernel_q", "kernel_scale"} <= names
        same = InferenceEngine(cfg, tree, cache_size=32, dtype=jnp.float32, lora=lora).params
        assert all(a is b for a, b in zip(jax.tree_util.tree_leaves(same), jax.tree_util.tree_leaves(tree), strict=True))
    assert ("bias" in names) == (cfg.family == "neox")  # GPT-NeoX's linears have biases, held in bf16


@pytest.mark.parametrize("cfg", HELD_FAMILIES)
def test_held_bf16_weights_give_the_logits_of_weights_cast_at_use(cfg):
    """Rounding the f32 tree once, when the engine is built, gives the paged
    programs the bits that casting every weight at its use gave them: the
    logits of ``prefill_chunk`` and ``decode_paged`` equal, bit for bit, those
    of the same model declared in f32 and applied to the f32 tree."""
    from relora_tpu.models.step import StepContext
    from relora_tpu.serve.engine import _chunk_positions, _forward

    tree = f32_tree(cfg)
    engine = InferenceEngine(cfg, tree, **PAGED_BF16)
    assert engine.params["layers"]["mlp"]["down_proj" if cfg.family == "llama" else "dense_4h_to_h"]["kernel"].dtype == jnp.bfloat16
    cast_at_use = engine.paged_model.clone(param_dtype=jnp.float32)

    @jax.jit
    def step(pool, ids, positions, tables):
        return _forward(cast_at_use, tree, pool, ids, StepContext(positions=positions, tables=tables))[:2]

    ids = jax.random.randint(jax.random.PRNGKey(3), (1, 8), 0, cfg.vocab_size)
    table = np.arange(1, 5, dtype=np.int32)[None, :]
    logits, pool = engine.prefill_chunk(ids, 0, engine.init_pool(), table)
    want, want_pool = step(engine.init_pool(), ids, _chunk_positions(0, 1, 8), engine.tables_by_kind(table, 0))
    assert logits.dtype == want.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want))

    token, pos = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32), jnp.full((1, 1), 8, jnp.int32)
    logits, _ = engine.decode_paged(pool, token, pos, table)
    want, _ = step(want_pool, token, pos, engine.tables_by_kind(table))
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(want[:, -1, :]))


def test_an_f32_checkpoint_reloads_into_the_held_dtypes():
    """``reload_params`` and ``load_draft_params`` cast an f32 checkpoint tree
    onto the live tree's dtypes: the leaves land in bf16 where the engine holds
    bf16, the tokens are a fresh engine's over that checkpoint, and base and
    draft replay the compiled programs (no steady-state retrace)."""
    from relora_tpu.serve.scheduler import PagedContinuousBatchingScheduler

    first, second = f32_tree(TINY_NEOX, seed=0), f32_tree(TINY_NEOX, seed=1)

    def host(tree):
        return jax.tree_util.tree_map(np.asarray, tree)

    def drain(engine):
        reqs = [Request(uid=i, prompt=[3 + i, 5, 7, 11, 13], max_new_tokens=6) for i in range(3)]
        done = PagedContinuousBatchingScheduler(engine, max_batch=2, eos_id=-1).run(reqs)
        return {uid: c.tokens for uid, c in done.items()}

    engine = InferenceEngine(TINY_NEOX, first, **PAGED_BF16)
    engine.warmup(2)
    held = dtypes_of(engine.params)
    assert held["embed_in"]["embedding"] == jnp.bfloat16 and held["final_layer_norm"]["scale"] == jnp.float32
    before = drain(engine)
    engine.reload_params(host(second))
    engine.load_draft_params(host(first))
    assert dtypes_of(engine.params) == held == dtypes_of(engine.draft_params)
    after = drain(engine)
    assert after != before
    assert after == drain(InferenceEngine(TINY_NEOX, second, **PAGED_BF16))

    ids = jnp.asarray([[2, 4, 6, 8, 10, 12, 14, 16]], jnp.int32)
    table = np.arange(1, 5, dtype=np.int32)[None, :]
    drafted, _ = engine.draft_prefill_chunk(ids, 0, engine.init_pool(2), table)
    fresh, _ = InferenceEngine(TINY_NEOX, first, **PAGED_BF16).prefill_chunk(ids, 0, engine.init_pool(2), table)
    np.testing.assert_array_equal(np.asarray(drafted), np.asarray(fresh))
    assert engine.compile_watcher.steady_state_retraces == 0


@pytest.mark.parametrize("family", ["neox", "llama"])
def test_the_trainers_model_declares_what_it_declared(family):
    """``build_decode_model`` alone gives a ``param_dtype``: the trainer's
    model keeps every parameter in f32 (a bf16 frozen base only where the
    spec asks for one, ``lora_s`` in f32 either way)."""
    from relora_tpu.config.training import TrainingConfig
    from relora_tpu.core.relora import LoraSpec
    from relora_tpu.train.trainer import build_model

    cfg = TINY_NEOX if family == "neox" else TINY_LLAMA
    train_cfg = TrainingConfig(dataset_path="x", batch_size=1, total_batch_size=1)
    ids = jnp.zeros((1, 8), jnp.int32)
    for spec in (None, LoraSpec(r=4, trainable_scaling=True), LoraSpec(r=4, trainable_scaling=True, base_dtype="bf16")):
        model = build_model(cfg, spec, train_cfg)
        assert model.param_dtype == jnp.float32 and model.dtype == jnp.bfloat16
        abstract = jax.eval_shape(lambda: init_params(model, jax.random.PRNGKey(0), ids))
        for path, leaf in jax.tree_util.tree_leaves_with_path(abstract):
            frozen_base = spec is not None and spec.base_dtype == "bf16" and path[-1].key == "kernel" and "lora_a" in _siblings(abstract, path)
            assert leaf.dtype == (jnp.bfloat16 if frozen_base else jnp.float32), jax.tree_util.keystr(path)


def _siblings(tree, path):
    for k in path[:-1]:
        tree = tree[k.key]
    return tree


@pytest.mark.parametrize("family", ["mimo", "afmoe"])
def test_routed_engines_hold_the_bf16_tree_they_are_handed(family, tmp_path):
    """The families that were always handed bf16 weights: the engine holds
    the very leaves — matrices bf16, 1-D leaves f32 — and casts none."""
    import importlib

    tiny = importlib.import_module(f"test_{family}")  # the family's tiny configuration
    weights = importlib.import_module(f"benchmark.weights_{family}")
    tree = weights.make_weights(tiny.TINY, 7)
    engine = InferenceEngine(
        tiny._config(tmp_path), tree, cache_size=128, dtype=jnp.bfloat16, page_size=4, num_pages=70, chunk_size=8
    )
    handed, held = jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(engine.params)
    assert all(a is b for a, b in zip(held, handed, strict=True))
    assert all(x.dtype == (jnp.bfloat16 if x.ndim > 1 else jnp.float32) for x in held)
    assert engine.param_bytes() == sum(x.nbytes for x in handed)
