"""The ``afmoe`` family (models/afmoe.py over models/hybrid.py, ops/moe.py,
models/step.py) at tiny widths that keep the published shape: grouped queries
(4 heads on 2 kv heads), a window (8) shorter than the sequences, the cell's
five layers (a dense sliding one, then sliding, sliding, sliding, full, all
routed), 8 experts of which a token takes 2 and this chip holds 2, one shared
expert, ``route_scale`` and the embedding multiplier as published.

The oracle is the benchmark's plain reference (``benchmark/reference/afmoe.py``,
f32, no cache, no kernel), which imports nothing of the program.
"""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_afmoe, weights_afmoe
from benchmark.reference import afmoe as reference
from relora_tpu.config.model import ModelConfig
from relora_tpu.core.relora import LoraSpec
from relora_tpu.models.afmoe import AfmoeForCausalLM, AfmoeLayer
from relora_tpu.models.hybrid import RoutedExperts
from relora_tpu.models.step import RING, StepContext
from relora_tpu.obs.metrics import MetricsRegistry
from relora_tpu.serve.engine import InferenceEngine
from relora_tpu.serve.paging import pages_needed
from relora_tpu.serve.scheduler import PagedContinuousBatchingScheduler, Request

SLIDE, FULL = "sliding_attention", "full_attention"
TINY = dict(
    model_type="afmoe", hidden_size=32, intermediate_size=64, num_hidden_layers=5,
    layer_types=[SLIDE, SLIDE, SLIDE, SLIDE, FULL], num_dense_layers=1, global_attn_every_n_layers=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8, rope_theta=10000, sliding_window=8,
    moe_intermediate_size=16, num_experts=8, experts_held=2, expert_offset=2, num_experts_per_tok=2,
    num_shared_experts=1, route_norm=True, route_scale=2.448, score_func="sigmoid", n_group=1, topk_group=1,
    mup_enabled=True, rms_norm_eps=1e-5, vocab_size=64, max_position_embeddings=4096, rope_scaling=None,
    tie_word_embeddings=False, init={"select_bias_std": 0.1},
)


def _config(tmp_path, **over) -> ModelConfig:
    path = tmp_path / "config.json"
    path.write_text(json.dumps({**TINY, **over}))
    return ModelConfig.from_hf_json(str(path))


@pytest.fixture(scope="module")
def cfg(tmp_path_factory):
    return _config(tmp_path_factory.mktemp("afmoe"))


@pytest.fixture(scope="module")
def params():
    return weights_afmoe.make_weights(TINY, 7, dtype=jnp.float32)


def _tokens(seed, n):
    return np.random.RandomState(seed).randint(0, TINY["vocab_size"], size=n)


def _engine(cfg, params, **kw):
    kw = {"cache_size": 128, "dtype": jnp.float32, "page_size": 4, "num_pages": 70, "chunk_size": 8, **kw}
    return InferenceEngine(cfg, params, **kw)


def test_the_configuration_reads_the_hf_keys(cfg):
    assert cfg.family == "afmoe" and cfg.layer_window == (1, 1, 1, 1, 0) and cfg.layer_moe == (0, 1, 1, 1, 1)
    assert (cfg.attn_gate, cfg.qk_norm, cfg.global_rotary, cfg.sandwich_norm) == (True, True, False, True)
    assert cfg.embed_scale == 32**0.5 and cfg.n_shared_experts == 1 and cfg.routed_scaling_factor == 2.448
    assert (cfg.n_routed_experts, cfg.experts_held, cfg.expert_offset, cfg.num_experts_per_tok) == (8, 2, 2, 2)
    held = sum(int(np.prod(shape)) for shape in weights_afmoe.flatten(weights_afmoe.param_shapes(TINY)).values())
    assert cfg.num_params() == held


def test_the_weights_are_the_models_tree(cfg):
    from relora_tpu.models.params_util import init_params

    model = AfmoeForCausalLM(cfg, dtype=jnp.float32, param_dtype=jnp.float32)
    abstract = jax.eval_shape(lambda: init_params(model, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))
    have = {p: tuple(v.shape) for p, v in weights_afmoe.flatten(abstract).items()}
    want = {p: tuple(s) for p, s in weights_afmoe.flatten(weights_afmoe.param_shapes(TINY)).items()}
    assert have == want


def test_full_forward_is_the_reference(cfg, params):
    toks = _tokens(0, 40)
    model = AfmoeForCausalLM(cfg, dtype=jnp.float32, param_dtype=jnp.float32)
    got = model.apply({"params": params}, jnp.asarray(toks)[None])[0]
    want = reference.forward(params, jnp.asarray(toks), TINY)
    assert float(jnp.abs(got - want).max()) < 1e-4 and float(jnp.abs(want).max()) > 0.1


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_each_part_of_the_mathematics_shows_in_the_logits(params, fault):
    """The gate, the q/k norms, rotary where it belongs and nowhere else, a
    post-norm, the shared expert, the route scale, the selection bias, the
    embedding multiplier and the window's last position each change the
    result: none is drawn so narrow that a forward could leave it out unseen."""
    toks = jnp.asarray(_tokens(0, 40))
    want = reference.forward(params, toks, TINY)
    assert float(jnp.abs(reference.forward(params, toks, TINY, faults=(fault,)) - want).max()) > 1e-3


def test_chunked_prefill_then_decode_is_the_reference(cfg, params):
    """A 29-token prompt in chunks of 8 (the window is 8: every chunk
    straddles it), then 46 decodes through both cache kinds, in slot 1 of 3
    beside idle rows.  The slot's ring holds 5 pages of 4: the request
    outgrows it, 75 tokens wrap it three times, and the chunk at 16..23
    straddles the wrap (logical pages 4 and 5 live in entries 4 and 0)."""
    eng = _engine(cfg, params)
    seq = _tokens(1, 75)
    want = np.asarray(reference.forward(params, jnp.asarray(seq), TINY))
    n_prompt, slot, B = 29, 1, 3
    pool = eng.init_pool(B)
    ring = next(c for c in eng.cache_specs(B) if c.kind == RING)
    assert ring.table_width == 5 and ring.layers == 4
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
    assert np.abs(got - want).max() < 1e-4
    local, hit = np.asarray(eng.moe_counts)
    assert 0 <= local <= B * 2 * 4 and 0 <= hit <= 2 * 4


def _moe_layer(cfg, p, x, offset, held):
    """A whole routed layer's FFN branch (pre-norm input ``x``) on one share."""
    share = dataclasses.replace(cfg, experts_held=held, expert_offset=offset)
    mine = {**p["experts"], "gate_up": p["experts"]["gate_up"][offset : offset + held],
            "down": p["experts"]["down"][offset : offset + held]}
    module = RoutedExperts(share, dtype=jnp.float32, param_dtype=jnp.float32)
    y, state = module.apply({"params": mine}, x[None], mutable=["stats"])
    return y[0], np.asarray(state["stats"]["moe"])


@pytest.fixture(scope="module")
def whole_layer():
    """A routed layer's weights with all 8 experts, and 50 tokens."""
    uncut = {**TINY, "experts_held": 8, "expert_offset": 0}
    p = weights_afmoe.make_layer(uncut, weights_afmoe.seed_key(11), 3, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (50, TINY["hidden_size"]), jnp.float32)
    return uncut, p, x


def test_the_shares_and_the_shared_expert_once_add_up(cfg, whole_layer):
    """Four chips of two experts each: their routed parts plus the shared
    expert *counted once* are the uncut reference's whole FFN, and their local
    assignments sum to tokens x top_k."""
    uncut, p, x = whole_layer
    want = reference._ffn(x, p, uncut, None, ())
    parts = [_moe_layer(cfg, p, x, offset, 2) for offset in (0, 2, 4, 6)]
    shared = reference._mlp(x, p["shared_expert"], None)
    routed = sum(y for y, _ in parts)
    assert float(jnp.abs(routed + shared - want).max()) < 1e-6
    # counted by no chip, or by two, it is another layer
    assert min(float(jnp.abs(routed + n * shared - want).max()) for n in (0, 2)) > 1e-3
    assert sum(int(s[0]) for _, s in parts) == 50 * 2
    # and one chip's whole layer (its share, the shared expert, the four norms)
    # is the reference's for that share
    mine = {**p, "experts": {**p["experts"], "gate_up": p["experts"]["gate_up"][2:4], "down": p["experts"]["down"][2:4]}}
    layer = AfmoeLayer(cfg, True, True, jnp.float32, jnp.float32)
    got = layer.apply({"params": mine}, x[None], StepContext(positions=jnp.arange(50)[None]), mutable=["stats"])[0][0]
    alone = reference.layer(x, mine, uncut, True, held=(2, 2))
    assert float(jnp.abs(got - alone).max()) < 1e-4


def test_dropless_when_every_token_chooses_the_held_experts(cfg, whole_layer):
    uncut, p, x = whole_layer
    e = p["experts"]
    eager = {**p, "experts": {**e, "select_bias": e["select_bias"].at[2:4].add(100.0)}}
    y, stats = _moe_layer(cfg, eager, x, 2, 2)
    assert stats.tolist() == [50 * 2, 2]
    want = reference._experts(x, eager["experts"], uncut, None, ())
    assert float(jnp.abs(y - want).max()) < 1e-5
    # and when none does
    shy = {**p, "experts": {**e, "select_bias": e["select_bias"].at[2:4].add(-100.0)}}
    y, stats = _moe_layer(cfg, shy, x, 2, 2)
    assert stats.tolist() == [0, 0] and float(jnp.abs(y).max()) == 0.0


def test_a_long_request_holds_a_constant_ring_and_frees_its_pages(cfg, params):
    """3,000 tokens through the scheduler: the window layers' cache is the
    slot's ring from first token to last, the full layer's pages are what
    admission allocated, and retirement frees them all; the decode spans say
    that the row's ring has wrapped."""
    from relora_tpu.obs.tracer import Tracer

    eng = _engine(cfg, params, cache_size=3072, page_size=16, num_pages=400, chunk_size=64)
    registry = MetricsRegistry()
    sch = PagedContinuousBatchingScheduler(eng, max_batch=2, eos_id=-1, prefix_cache=False, key=jax.random.PRNGKey(0))
    sch.obs_registry = registry
    sch.publish_constants()  # as the server does where it attaches its registry
    sch.tracer = Tracer(service="test")
    ring = next(c for c in eng.cache_specs(2) if c.kind == RING)
    assert ring.table_width == -(-(8 + 64) // 16) + 1 and ring.num_pages == 1 + 2 * ring.table_width
    req = Request(uid=1, prompt=_tokens(2, 2960).tolist(), max_new_tokens=40)
    sch.submit(req)
    held, done = set(), []
    while not done:
        done = sch.step()
        if sch.active_slots:
            held.add(sch.allocator.used_pages)
    assert held == {pages_needed(3000, 16)}  # allocated once, at admission, for the full layer
    assert sch.allocator.used_pages == 0 and len(done[0].tokens) == 40
    pool = sch._pool
    assert pool["layers_1"]["attn"]["k"].shape[0] == ring.num_pages  # 13 pages, whatever the length
    assert pool["layers_4"]["attn"]["k"].shape[0] == 400
    assert registry.gauge_value("window_ring_pages") == ring.table_width
    assert registry.gauge_value("ring_wrapped_rows") == 1
    assert registry.gauge_value("kv_cache_bytes_ring") == eng.pool_bytes(2, RING)
    assert registry.gauge_value("kv_cache_bytes_paged") + registry.gauge_value("kv_cache_bytes_ring") == eng.pool_bytes(2)
    assert registry.counter_value("moe_assignments_local_total") <= registry.counter_value("moe_assignments_total")
    decodes = [s["attrs"] for s in sch.tracer.recorder.spans() if s["name"] == "decode_step"][-39:]
    assert len(decodes) == 39 and all(a["rows_past_window"] == 1 == a["active_slots"] for a in decodes)
    # the served tokens are the reference's greedy ones
    seq = jnp.asarray(list(req.prompt) + done[0].tokens, jnp.int32)
    logits = np.asarray(reference.forward(params, seq, TINY))
    assert logits[len(req.prompt) - 1 : len(seq) - 1].argmax(-1).tolist() == done[0].tokens


def test_decode_step_says_what_each_cache_kind_reads(cfg, params):
    from relora_tpu.obs.tracer import Tracer

    eng = _engine(cfg, params)
    sch = PagedContinuousBatchingScheduler(eng, max_batch=2, eos_id=-1, prefix_cache=False, key=jax.random.PRNGKey(0))
    sch.tracer = Tracer(service="test")
    sch.run([Request(uid=1, prompt=_tokens(3, 5).tolist(), max_new_tokens=6)])
    # the recorder is the process's: this request's five decodes are its last five
    spans = [s["attrs"] for s in sch.tracer.recorder.spans() if s["name"] == "decode_step"][-5:]
    paged, ring = eng.cache_specs(2)
    assert (paged.layers, ring.layers, paged.k_dim, paged.k_pad) == (1, 4, 8, 120)
    # the first decode is at position 5, inside the window of 8; the fifth at 9, past it
    assert [a["rows_past_window"] for a in spans] == [0, 0, 0, 1, 1]
    last, pos = spans[-1], 5 + 4
    assert last["kv_bytes_global"] == (pos + 1) * paged.bytes_per_token == (pos + 1) * 1 * 2 * 16 * 4
    assert last["kv_bytes_window"] == 8 * ring.bytes_per_token == 8 * 4 * 2 * 16 * 4
    assert last["kv_bytes"] == last["kv_bytes_global"] + last["kv_bytes_window"]
    # the benchmark's own arithmetic from shapes gives the bytes the program counted
    assert flops_afmoe.kv_read_bytes(TINY, pos, itemsize=4) == {"global": last["kv_bytes_global"], "window": last["kv_bytes_window"]}
    assert last["expert_bytes"] % (3 * 32 * 16 * 4) == 0 and last["moe_assignments_local"] >= 0


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
    with pytest.raises(ValueError, match=f"the afmoe family cannot do {feature} yet"):
        _engine(cfg, params, **kw)


@pytest.mark.parametrize(
    "feature, kw",
    [("prefix reuse", dict(prefix_cache=True)), ("page migration", dict(prefix_cache=False, role="prefill"))],
)
def test_scheduler_refuses_by_name_what_the_family_cannot_do(cfg, params, feature, kw):
    with pytest.raises(ValueError, match=f"the afmoe family cannot do {feature} yet"):
        PagedContinuousBatchingScheduler(_engine(cfg, params), max_batch=2, eos_id=-1, **kw)


def test_the_trainer_refuses_the_family_by_name(cfg):
    from relora_tpu.config.training import TrainingConfig
    from relora_tpu.train.trainer import build_model

    with pytest.raises(ValueError, match="afmoe family is served, not trained"):
        build_model(cfg, None, TrainingConfig(dataset_path="x", batch_size=1, total_batch_size=1))


@pytest.mark.parametrize(
    "over, message",
    [
        (dict(n_group=2), "group-limited routing"),
        (dict(topk_group=2), "group-limited routing"),
        (dict(score_func="softmax"), "score_func 'softmax' is not supported"),
        (dict(layer_types=[SLIDE] * 4), "layer_types must name num_hidden_layers = 5"),
        (dict(layer_types=[SLIDE] * 4 + ["chunked_attention"]), "chunked_attention"),
        (dict(experts_held=4, expert_offset=6), "experts 6 .. 9 are not among the 8 routed"),
        (dict(rope_scaling={"type": "linear", "factor": 2.0}), "rope_scaling is not supported"),
    ],
)
def test_the_configuration_refuses_by_name_what_the_family_lacks(tmp_path, over, message):
    with pytest.raises(ValueError, match=message):
        _config(tmp_path, **over)


def test_the_engine_keeps_the_weights_as_handed(cfg):
    """bf16 in, bf16 held, and the plans' resident bytes are those (what the
    programs add in temporaries at the cell's size: tests/test_tpu_compile.py)."""
    bf16 = weights_afmoe.make_weights(TINY, 7)
    eng = _engine(cfg, bf16, dtype=jnp.bfloat16)
    flat = weights_afmoe.flatten(eng.params)
    assert all(v.dtype == jnp.bfloat16 for p, v in flat.items() if v.ndim > 1)
    plans = eng.memory_plans(2)
    assert plans["pytree"]["params_bytes"] == sum(v.nbytes for v in flat.values())
    assert plans["pytree"]["kv_cache_bytes"] == eng.pool_bytes(2)
    assert all("error" not in plans[name] for name in ("decode_paged", "prefill_chunk")), plans
