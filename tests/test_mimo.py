"""The ``mimo_v2`` family (models/mimo.py, ops/moe.py, models/step.py) at
tiny widths that keep every inequality of the published ones: K heads wider
than V heads (12 / 8), rotary on fewer features than a head has (4), one kv
head in global layers and two in window layers, a window (8) shorter than
the sequences, 8 experts of which a token takes 2, and the published period
of seven layers (dense + global, five window, one global).

The oracle is the benchmark's plain reference (``benchmark/reference/mimo.py``,
f32, no cache, no kernel), which imports nothing of the program.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights_mimo
from benchmark.reference import mimo as reference
from relora_tpu.config.model import ModelConfig
from relora_tpu.core.relora import LoraSpec
from relora_tpu.models.hybrid import RoutedExperts
from relora_tpu.models.mimo import MimoForCausalLM
from relora_tpu.models.step import PAGED, RING
from relora_tpu.obs.metrics import MetricsRegistry
from relora_tpu.serve.engine import InferenceEngine
from relora_tpu.serve.paging import pages_needed
from relora_tpu.serve.scheduler import PagedContinuousBatchingScheduler, Request

TINY = dict(
    model_type="mimo_v2", hidden_size=32, intermediate_size=64, num_hidden_layers=7,
    hybrid_layer_pattern=[0, 1, 1, 1, 1, 1, 0], moe_layer_freq=[0, 1, 1, 1, 1, 1, 1],
    num_attention_heads=4, num_key_value_heads=1, swa_num_key_value_heads=2, head_dim=12, v_head_dim=8,
    partial_rotary_factor=0.334, rope_theta=1e7, swa_rope_theta=1e4, sliding_window=8,
    add_swa_attention_sink_bias=True, add_full_attention_sink_bias=False, attention_value_scale=0.707,
    moe_intermediate_size=16, n_routed_experts=8, experts_held=2, expert_offset=2, num_experts_per_tok=2,
    norm_topk_prob=True, routed_scaling_factor=None, scoring_func="sigmoid", n_group=1, topk_group=1,
    layernorm_epsilon=1e-5, vocab_size=64, max_position_embeddings=4096,
    init={"sink_std": 1.0, "select_bias_std": 0.1},
)


def _config(tmp_path, **over) -> ModelConfig:
    path = tmp_path / "config.json"
    path.write_text(json.dumps({**TINY, **over}))
    return ModelConfig.from_hf_json(str(path))


@pytest.fixture(scope="module")
def cfg(tmp_path_factory):
    return _config(tmp_path_factory.mktemp("mimo"))


@pytest.fixture(scope="module")
def params():
    return weights_mimo.make_weights(TINY, 7, dtype=jnp.float32)


def _tokens(seed, n):
    return np.random.RandomState(seed).randint(0, TINY["vocab_size"], size=n)


def _engine(cfg, params, **kw):
    kw = {"cache_size": 128, "dtype": jnp.float32, "page_size": 4, "num_pages": 70, "chunk_size": 8, **kw}
    return InferenceEngine(cfg, params, **kw)


def test_full_forward_is_the_reference(cfg, params):
    toks = _tokens(0, 40)
    model = MimoForCausalLM(cfg, dtype=jnp.float32, param_dtype=jnp.float32)
    got = model.apply({"params": params}, jnp.asarray(toks)[None])[0]
    want = reference.forward(params, jnp.asarray(toks), TINY)
    assert float(jnp.abs(got - want).max()) < 1e-5 and float(jnp.abs(want).max()) > 0.1


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_each_part_of_the_mathematics_shows_in_the_logits(params, fault):
    """The sink, the selection bias, the value scale, the window's last
    position and the router's precision each change the result: none of them
    is drawn so narrow that a forward could leave it out unseen."""
    toks = jnp.asarray(_tokens(0, 40))
    want = reference.forward(params, toks, TINY)
    assert float(jnp.abs(reference.forward(params, toks, TINY, faults=(fault,)) - want).max()) > 1e-3


def test_chunked_prefill_then_decode_is_the_reference(cfg, params):
    """A 29-token prompt in chunks of 8 (the window is 8: every chunk
    straddles it), then 46 decodes through the pool, in slot 1 of 3 beside
    idle rows.  The slot's ring holds 5 pages of 4: 75 tokens wrap it three
    times."""
    eng = _engine(cfg, params)
    seq = _tokens(1, 75)
    want = np.asarray(reference.forward(params, jnp.asarray(seq), TINY))
    n_prompt, slot, B = 29, 1, 3
    pool = eng.init_pool(B)
    table = np.zeros((1, eng.block_table_width), np.int32)
    table[0, :20] = np.arange(1, 21)
    got = np.zeros_like(want)
    for start in range(0, n_prompt, 8):
        n = min(8, n_prompt - start)
        ids = np.zeros((1, 8), np.int32)
        ids[0, :n] = seq[start : start + n]
        logits, pool = eng.prefill_chunk(ids, start, pool, table, slot=slot)
        got[start : start + n] = np.asarray(logits)[0, :n]
    tables = np.zeros((B, eng.block_table_width), np.int32)
    tables[slot] = table[0]
    for p in range(n_prompt, len(seq)):
        tok, pos = np.zeros((B, 1), np.int32), np.zeros((B, 1), np.int32)
        tok[slot, 0], pos[slot, 0] = seq[p], p
        logits, pool = eng.decode_paged(pool, tok, pos, tables)
        got[p] = np.asarray(logits)[slot]
    assert np.abs(got - want).max() < 1e-5
    local, hit = np.asarray(eng.moe_counts)
    assert 0 <= local <= B * 2 * 6 and 0 <= hit <= 2 * 6


def _experts_layer(cfg, p, x, offset, held):
    share = dataclasses.replace(cfg, experts_held=held, expert_offset=offset)
    mine = {**p, "gate_up": p["gate_up"][offset : offset + held], "down": p["down"][offset : offset + held]}
    module = RoutedExperts(share, dtype=jnp.float32, param_dtype=jnp.float32)
    y, state = module.apply({"params": mine}, x[None], mutable=["stats"])
    return y[0], np.asarray(state["stats"]["moe"])


@pytest.fixture(scope="module")
def whole_layer():
    """An expert layer's weights with all 8 experts, and 50 tokens."""
    uncut = {**TINY, "experts_held": 8, "expert_offset": 0}
    p = weights_mimo.make_layer(uncut, weights_mimo.seed_key(11), 3, jnp.float32)["experts"]
    x = jax.random.normal(jax.random.PRNGKey(5), (50, TINY["hidden_size"]), jnp.float32)
    return uncut, p, x


def test_the_shares_add_up(cfg, whole_layer):
    """Four chips of two experts each: their parts sum to the uncut
    reference layer, and their local assignments to tokens x top_k."""
    uncut, p, x = whole_layer
    want = reference._experts(x, p, uncut, None, ())
    parts = [_experts_layer(cfg, p, x, offset, 2) for offset in (0, 2, 4, 6)]
    assert float(jnp.abs(sum(y for y, _ in parts) - want).max()) < 1e-5
    assert sum(int(s[0]) for _, s in parts) == 50 * 2
    # and one share alone is the reference's for that share
    alone = reference._experts(x, {**p, "gate_up": p["gate_up"][2:4], "down": p["down"][2:4]}, uncut, None, (), held=(2, 2))
    assert float(jnp.abs(parts[1][0] - alone).max()) < 1e-5


def test_dropless_when_every_token_chooses_the_held_experts(cfg, whole_layer):
    uncut, p, x = whole_layer
    eager = {**p, "select_bias": p["select_bias"].at[2:4].add(100.0)}
    y, stats = _experts_layer(cfg, eager, x, 2, 2)
    assert stats.tolist() == [50 * 2, 2]
    want = reference._experts(x, eager, uncut, None, ())
    assert float(jnp.abs(y - want).max()) < 1e-5
    # and when none does
    shy = {**p, "select_bias": p["select_bias"].at[2:4].add(-100.0)}
    y, stats = _experts_layer(cfg, shy, x, 2, 2)
    assert stats.tolist() == [0, 0] and float(jnp.abs(y).max()) == 0.0


def test_a_long_request_holds_a_constant_ring_and_frees_its_pages(cfg, params):
    """3,000 tokens through the scheduler: the window layers' cache is the
    slot's 5-page ring from first token to last, the global layers' pages
    are what admission allocated, and retirement frees them all."""
    eng = _engine(cfg, params, cache_size=3072, page_size=16, num_pages=400, chunk_size=64)
    registry = MetricsRegistry()
    sch = PagedContinuousBatchingScheduler(eng, max_batch=2, eos_id=-1, prefix_cache=False, key=jax.random.PRNGKey(0))
    sch.obs_registry = registry
    sch.publish_constants()  # as the server does where it attaches its registry
    ring = next(c for c in eng.cache_specs(2) if c.kind == RING)
    assert ring.table_width == -(-(8 + 64) // 16) + 1 and ring.num_pages == 1 + 2 * ring.table_width
    req = Request(uid=1, prompt=_tokens(2, 2960).tolist(), max_new_tokens=40)
    sch.submit(req)
    held, done = set(), []
    while not done:
        done = sch.step()
        if sch.active_slots:
            held.add(sch.allocator.used_pages)
    assert held == {pages_needed(3000, 16)}  # allocated once, at admission, for the global layers
    assert sch.allocator.used_pages == 0 and len(done[0].tokens) == 40
    pool = sch._pool
    assert pool["layers_1"]["attn"]["k"].shape[0] == ring.num_pages  # 11 pages, whatever the length
    assert pool["layers_0"]["attn"]["k"].shape[0] == 400
    assert registry.gauge_value("window_ring_pages") == ring.table_width
    assert registry.gauge_value("kv_cache_bytes_ring") == eng.pool_bytes(2, RING)
    assert registry.gauge_value("kv_cache_bytes_paged") + registry.gauge_value("kv_cache_bytes_ring") == eng.pool_bytes(2)
    assert registry.counter_value("moe_assignments_local_total") <= registry.counter_value("moe_assignments_total")
    # the served tokens are the reference's greedy ones
    seq = jnp.asarray(list(req.prompt) + done[0].tokens, jnp.int32)
    logits = np.asarray(reference.forward(params, seq, TINY))
    assert logits[len(req.prompt) - 1 : len(seq) - 1].argmax(-1).tolist() == done[0].tokens


def test_decode_step_says_what_each_cache_kind_reads(cfg, params):
    from relora_tpu.obs.tracer import Tracer

    eng = _engine(cfg, params)
    sch = PagedContinuousBatchingScheduler(eng, max_batch=2, eos_id=-1, prefix_cache=False, key=jax.random.PRNGKey(0))
    sch.tracer = Tracer(service="test")
    sch.run([Request(uid=1, prompt=_tokens(3, 20).tolist(), max_new_tokens=6)])
    last = [s for s in sch.tracer.recorder.spans() if s["name"] == "decode_step"][-1]["attrs"]
    paged, ring = eng.cache_specs(2)
    pos = 20 + 4  # the fifth decode
    assert last["kv_bytes_global"] == (pos + 1) * paged.bytes_per_token == (pos + 1) * 2 * 1 * 20 * 4
    assert last["kv_bytes_window"] == 8 * ring.bytes_per_token == 8 * 5 * 2 * 20 * 4
    assert last["kv_bytes"] == last["kv_bytes_global"] + last["kv_bytes_window"]
    assert last["expert_bytes"] % (3 * 32 * 16 * 4) == 0 and last["moe_assignments_local"] >= 0
    chunks = [s["attrs"] for s in sch.tracer.recorder.spans() if s["name"] == "prefill_chunk"]
    assert "moe_assignments_local" in chunks[-1] and "moe_assignments_local" not in chunks[0]


def test_the_decodes_pull_converts_no_count_of_the_chunk_sent_ahead(cfg, params):
    """The next round's chunk lies behind the decode on the device: the decode's
    pull reads the decode's own counts (``decode_step.expert_bytes`` is what
    ``moe_experts_roofline.serve`` divides by) and leaves the chunk's pending."""
    from relora_tpu.obs.tracer import Tracer

    eng = _engine(cfg, params)
    registry = MetricsRegistry()
    sch = PagedContinuousBatchingScheduler(
        eng, max_batch=2, eos_id=-1, prefix_cache=False, key=jax.random.PRNGKey(0), obs_registry=registry,
    )
    sch.tracer = Tracer(service="test")
    forwards = []  # (program, tokens routed, the counts as the device holds them), in dispatch order
    for name, tokens in (("decode_paged", 2), ("prefill_chunk", 8)):
        def spied(*a, _real=getattr(eng, name), _name=name, _tokens=tokens, **kw):
            out = _real(*a, **kw)
            forwards.append((_name, _tokens, eng.moe_counts))
            return out
        setattr(eng, name, spied)
    try:
        sch.submit(Request(uid=1, prompt=_tokens(3, 5).tolist(), max_new_tokens=6))
        sch.step()
        sch.submit(Request(uid=2, prompt=_tokens(4, 20).tolist(), max_new_tokens=3))
        sch.step()  # uid 2's first chunk, the decode, uid 2's second chunk behind it
    finally:
        del eng.decode_paged, eng.prefill_chunk
    assert [f[0] for f in forwards] == ["prefill_chunk", "decode_paged", "prefill_chunk", "decode_paged", "prefill_chunk"]
    fanout = TINY["num_experts_per_tok"] * sum(TINY["moe_layer_freq"])
    *pulled, (_, _, ahead_counts) = forwards
    assert sch._ahead is not None and [(r, c is ahead_counts) for r, c in sch._moe_pending] == [(8 * fanout, True)]
    assert registry.counter_value("moe_assignments_total") == sum(t for _, t, _ in pulled) * fanout
    assert registry.counter_value("moe_experts_hit_total") == sum(int(np.asarray(c)[1]) for _, _, c in pulled)
    step = [s for s in sch.tracer.recorder.spans() if s["name"] == "decode_step"][-1]["attrs"]
    local, hit = (int(v) for v in np.asarray(pulled[-1][2]))  # the decode's, the last forward before the chunk
    assert (step["moe_assignments_local"], step["expert_bytes"]) == (local, hit * sch._expert_bytes)
    assert int(np.asarray(ahead_counts)[1]) != hit  # a chunk of eight tokens hits other experts than a row does
    sch.step()  # the next decode's pull takes the chunk's counts up, and leaves those of the chunk behind it
    assert registry.counter_value("moe_assignments_total") == (sum(t for _, t, _ in pulled) + 8 + 2) * fanout
    assert [r for r, _ in sch._moe_pending] == [8 * fanout]


@pytest.mark.parametrize(
    "feature, kw",
    [
        ("the contiguous cache", dict(page_size=None, num_pages=None)),
        ("adapters", dict(lora=LoraSpec(r=4))),
        ("int8 pages", dict(kv_dtype="int8")),
        ("speculation", dict(spec_k=2)),
        ("packed steps", dict(token_budget=64)),
    ],
)
def test_engine_refuses_by_name_what_the_family_cannot_do(cfg, params, feature, kw):
    with pytest.raises(ValueError, match=f"cannot do {feature} yet"):
        _engine(cfg, params, **kw)


def test_engine_refuses_tp_by_name(cfg, params):
    from relora_tpu.parallel.mesh import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(data=1, fsdp=1, tensor=2, sequence=1), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="cannot do tp yet"):
        _engine(cfg, params, mesh=mesh)


@pytest.mark.parametrize(
    "feature, kw",
    [("prefix reuse", dict(prefix_cache=True)), ("page migration", dict(prefix_cache=False, role="prefill"))],
)
def test_scheduler_refuses_by_name_what_the_family_cannot_do(cfg, params, feature, kw):
    with pytest.raises(ValueError, match=f"cannot do {feature} yet"):
        PagedContinuousBatchingScheduler(_engine(cfg, params), max_batch=2, eos_id=-1, **kw)


def test_the_trainer_refuses_the_family_by_name(cfg):
    from relora_tpu.config.training import TrainingConfig
    from relora_tpu.train.trainer import build_model

    with pytest.raises(ValueError, match="mimo family is served, not trained"):
        build_model(cfg, None, TrainingConfig(dataset_path="x", batch_size=1, total_batch_size=1))


def test_the_engine_keeps_the_weights_as_handed(cfg):
    """bf16 in, bf16 held, and the plans' resident bytes are those (what the
    programs add in temporaries at the cell's size: tests/test_tpu_compile.py)."""
    bf16 = weights_mimo.make_weights(TINY, 7)
    eng = _engine(cfg, bf16, dtype=jnp.bfloat16)
    flat = weights_mimo.flatten(eng.params)
    assert all(v.dtype == jnp.bfloat16 for p, v in flat.items() if v.ndim > 1)
    plans = eng.memory_plans(2)
    assert plans["pytree"]["params_bytes"] == sum(v.nbytes for v in flat.values())
    assert plans["pytree"]["kv_cache_bytes"] == eng.pool_bytes(2)
    assert all("error" not in plans[name] for name in ("decode_paged", "prefill_chunk")), plans
