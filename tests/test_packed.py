"""Packed mixed-batch serving tests: the single-dispatch round oracle.

A drain through ``PagedContinuousBatchingScheduler(packed=True)`` must be
**token-identical** to the sequential paged drain for the same request
stream — greedy and sampled, llama and neox, base and multi-tenant LoRA,
spec drafting on and off — because every packed token attends only its own
slot's pages (``row_map`` routing) and sampling keys stay
``(uid, token_index)``.  On top of parity: a loaded round issues exactly
ONE model dispatch, packing never changes allocator accounting, a row's
tokens don't depend on who else rides the dispatch, and a packed warmup
covers every steady-state shape (zero retraces under churn).
"""

import numpy as np

import jax
import jax.numpy as jnp
import pytest

from relora_tpu.config.model import ModelConfig
from relora_tpu.core.relora import LoraSpec
from relora_tpu.models.params_util import init_params
from relora_tpu.serve.adapters import AdapterRegistry, extract_lora_factors
from relora_tpu.serve.engine import InferenceEngine, build_decode_model
from relora_tpu.serve.scheduler import PagedContinuousBatchingScheduler, Request

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

MAX_BATCH = 2
CHUNK = 8


_ENGINES: dict = {}


def make_engine(cfg, *, spec_k=0, cache_size=32, lora=None, adapter_slots=0, fresh=False):
    """One paged engine with a token budget: it can run BOTH the sequential
    round (prefill_chunk/decode_paged/verify_paged) and the packed step, so
    parity drains share every weight bit by construction.  Also returns the
    raw (pre-slot-stacked) params — LoRA factors extract from those.

    Engines are cached per config so tests reuse jit caches (pools live on
    the scheduler, so sharing is safe); ``fresh=True`` opts out for tests
    that assert on the engine's compile telemetry from a clean slate."""
    key = (cfg.family, spec_k, cache_size, lora is not None, adapter_slots)
    if not fresh and key in _ENGINES:
        return _ENGINES[key]
    model = build_decode_model(cfg, cache_size=cache_size, lora=lora)
    base = type(model)(cfg, lora=lora, dtype=jnp.float32, scan_layers=True)
    params = init_params(base, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    window = spec_k + 1 if spec_k else 1
    engine = InferenceEngine(
        cfg,
        params,
        cache_size=cache_size,
        page_size=8,
        num_pages=3 * (cache_size // 8) + 1,
        chunk_size=CHUNK,
        spec_k=spec_k,
        token_budget=MAX_BATCH * window + CHUNK,
        lora=lora,
        adapter_slots=adapter_slots,
    )
    if not fresh:
        _ENGINES[key] = (engine, params)
    return engine, params


def mixed_requests(vocab, *, adapters=False):
    """Mixed lengths (page-straddling + multi-chunk), greedy AND sampled,
    staggered through max_batch=2 slots, with uid 4 likely to hit EOS."""
    rng = np.random.default_rng(11)
    mk = lambda uid, L, new, **kw: Request(
        uid=uid, prompt=rng.integers(1, vocab, L).tolist(), max_new_tokens=new, **kw
    )
    adapter = (lambda uid: (None, "t0", "t1")[uid % 3]) if adapters else (lambda uid: None)
    return [
        mk(1, 13, 6, adapter=adapter(1)),
        mk(2, 5, 9, temperature=0.8, top_p=0.9, adapter=adapter(2)),
        mk(3, 21, 4, adapter=adapter(3)),
        mk(4, 3, 7, temperature=1.1, adapter=adapter(4)),
    ]


def drain(engine, reqs, *, packed, spec="off", **kwargs):
    sched = PagedContinuousBatchingScheduler(
        engine,
        max_batch=MAX_BATCH,
        eos_id=9,
        key=jax.random.PRNGKey(42),
        packed=packed,
        spec=spec,
        **kwargs,
    )
    completions = sched.run(reqs)
    return sched, {uid: c.tokens for uid, c in completions.items()}


# -- the parity oracle --------------------------------------------------------


@pytest.mark.parametrize("spec", ["off", "ngram"])
@pytest.mark.parametrize(
    "cfg",
    [
        TINY_LLAMA,
        # neox rides the slow battery: same row_map code path, but its
        # engine's compile set doesn't fit the tier-1 wall-clock budget
        pytest.param(TINY_NEOX, marks=pytest.mark.slow),
    ],
    ids=["llama", "neox"],
)
def test_packed_token_identical_to_sequential(cfg, spec):
    """The packed single-dispatch drain reproduces the sequential paged
    drain token for token — greedy and sampled rows, with and without
    speculative drafting riding the packed window."""
    engine, _ = make_engine(cfg, spec_k=3)  # shared: spec_k only adds capability
    reqs = mixed_requests(cfg.vocab_size)
    _, want = drain(engine, reqs, packed=False, spec=spec)
    sched, got = drain(engine, reqs, packed=True, spec=spec)
    assert got == want
    assert sched.dispatch_stats()["mode"] == "packed"


@pytest.mark.slow  # compile-heavy (grouped-LoRA engine): full battery only
def test_packed_parity_with_adapters():
    """Multi-tenant rows keep parity: each packed token routes through its
    slot's adapter index exactly as the sequential round does."""
    lspec = LoraSpec(r=4, alpha=8)
    engine, raw = make_engine(TINY_LLAMA, lora=lspec, adapter_slots=3)
    base_factors = extract_lora_factors(raw)

    def tenant_factors(seed):
        # lora_b initializes to zero, so scaling won't do: inject noise into
        # both factors to give each tenant a genuinely different delta
        return jax.tree_util.tree_map(
            lambda t: t
            + 0.1
            * jax.random.normal(jax.random.PRNGKey(seed), t.shape, t.dtype),
            base_factors,
        )

    def registry():
        reg = AdapterRegistry(
            None, 3, expected_r=lspec.r, writer=engine.adapter_writer()
        )
        for g, name in enumerate(("t0", "t1")):
            reg.preload(name, tenant_factors(11 + g), lspec.scale)
        return reg

    reqs = mixed_requests(TINY_LLAMA.vocab_size, adapters=True)
    _, want = drain(engine, reqs, packed=False, adapter_registry=registry())
    _, got = drain(engine, reqs, packed=True, adapter_registry=registry())
    assert got == want
    # adapters actually changed the output: an adapter-less drain on the
    # same engine (every row on slot 0, the identity adapter) differs
    _, plain = drain(engine, mixed_requests(TINY_LLAMA.vocab_size), packed=True)
    assert plain != want


def test_packed_parity_without_prefix_cache():
    engine, _ = make_engine(TINY_LLAMA, spec_k=3)
    reqs = mixed_requests(TINY_LLAMA.vocab_size)
    _, want = drain(engine, reqs, packed=False, prefix_cache=False)
    sched, got = drain(engine, reqs, packed=True, prefix_cache=False)
    assert got == want
    assert sched.allocator.used_pages == 0


# -- one dispatch per round ---------------------------------------------------


def test_loaded_round_is_one_dispatch():
    """A round with a decoding row AND a pending multi-chunk prefill issues
    exactly one step_paged call — none of the sequential trio run."""
    engine, _ = make_engine(TINY_LLAMA, spec_k=3)
    sched = PagedContinuousBatchingScheduler(
        engine, max_batch=MAX_BATCH, packed=True
    )
    sched.submit(Request(uid=1, prompt=[1, 2, 3], max_new_tokens=8))
    sched.step()  # uid 1 prefills (+ first decode) — now decoding
    sched.submit(Request(uid=2, prompt=list(range(1, 22)), max_new_tokens=4))

    before = engine.compile_watcher.call_counts()
    d0 = sched.dispatch_stats()
    sched.step()  # decode row + first prefill chunk of uid 2, together
    after = engine.compile_watcher.call_counts()
    d1 = sched.dispatch_stats()

    delta = lambda name: after.get(name, 0) - before.get(name, 0)
    assert delta("step_paged") == 1
    assert delta("prefill_chunk") == 0
    assert delta("decode_paged") == 0
    assert delta("verify_paged") == 0
    assert d1["model_dispatches"] - d0["model_dispatches"] == 1
    assert d1["rounds"] - d0["rounds"] == 1

    # and the whole remaining drain stays at one dispatch per round
    sched.run([])
    stats = sched.dispatch_stats()
    assert stats["model_dispatches"] == stats["rounds"]
    assert stats["dispatches_per_round"] == 1.0
    assert 0.0 < stats["packed_token_utilization"] <= 1.0


# -- packing is invisible to everything but the dispatch count ----------------


def test_row_isolation_solo_vs_crowded():
    """A greedy request's tokens don't depend on who else rides the packed
    dispatch: alone, or packed beside decode neighbours and a fat prefill."""
    engine, _ = make_engine(TINY_LLAMA, spec_k=3)
    probe = lambda uid: Request(
        uid=uid, prompt=[7, 3, 11, 5, 2, 13, 1], max_new_tokens=6
    )
    _, solo = drain(engine, [probe(1)], packed=True, prefix_cache=False)

    rng = np.random.default_rng(5)
    crowd = [
        probe(1),
        Request(uid=2, prompt=rng.integers(1, 256, 4).tolist(), max_new_tokens=9,
                temperature=0.9),
        Request(uid=3, prompt=rng.integers(1, 256, 19).tolist(), max_new_tokens=5),
    ]
    _, crowded = drain(engine, crowd, packed=True, prefix_cache=False)
    assert crowded[1] == solo[1]


def test_allocator_accounting_unchanged_by_packing():
    """Packing changes dispatch economics only: page alloc/free traffic,
    peak usage, and the end state match the sequential drain exactly."""
    stats = {}
    for packed in (False, True):
        engine, _ = make_engine(TINY_LLAMA, spec_k=3)
        reqs = mixed_requests(TINY_LLAMA.vocab_size)
        sched, _ = drain(engine, reqs, packed=packed)
        sched.prefix_cache.clear()
        assert sched.allocator.used_pages == 0
        alloc = sched.allocator
        stats[packed] = (alloc.free_pages, alloc.peak_used, sched.prefix_cache.stats())
    assert stats[True] == stats[False]


# -- compile discipline -------------------------------------------------------


def test_packed_warmup_no_steady_state_retrace():
    """warmup(packed=True) compiles every token-budget bucket; afterwards a
    churny drain — staggered admits, a mid-decode cancel, spec windows
    filling and draining — never retraces."""
    engine, _ = make_engine(TINY_LLAMA, spec_k=3, fresh=True)
    report = engine.warmup(MAX_BATCH, packed=True)
    assert report["token_budget"] == engine.token_budget
    assert report["packed_buckets"] == list(engine.packed_buckets())
    assert report["shapes"]["step_paged"] == [
        [1, b] for b in engine.packed_buckets()
    ]

    sched = PagedContinuousBatchingScheduler(
        engine, max_batch=MAX_BATCH, eos_id=9, packed=True, spec="ngram"
    )
    rng = np.random.default_rng(3)
    for uid, L in enumerate((2, 7, 9, 17, 23), start=1):
        sched.submit(
            Request(
                uid=uid,
                prompt=rng.integers(1, 256, L).tolist(),
                max_new_tokens=6,
                temperature=0.7 if uid % 2 else 0.0,
            )
        )
        sched.step()
        if uid == 3:
            sched.cancel(1)
    sched.run([])
    assert engine.compile_watcher.steady_state_retraces == 0


# -- the round's spans: the same names as the sequential step -----------------


def test_packed_decode_step_counts_the_table_entries_a_decode_must_walk():
    from tests.test_paging import check_live_page_counts

    check_live_page_counts(make_engine(TINY_LLAMA)[0], packed=True)


def test_packed_round_has_the_sequential_rounds_span_names():
    """S1 will move the serving cell to the packed step: its rounds must
    feed the same metrics, so they carry the same span names and attributes
    (no ``prefill_chunk``: the one dispatch carries the prompts' tokens)."""
    from tests.test_paging import check_round_tree, traced_drain

    engine, _ = make_engine(TINY_LLAMA)
    reqs = mixed_requests(TINY_LLAMA.vocab_size)
    sched, rounds, children, completions, calls = traced_drain(
        engine, reqs, spy="step_paged", packed=True
    )
    decode_steps = check_round_tree(rounds, children)
    assert len(rounds) == len(decode_steps) == len(calls) == sched._round_total
    for r in rounds:
        names = [k["name"] for k in children[r["span_id"]]]
        assert names == ["admit", "decode_prep", "decode_step", "commit", "round_metrics"]
        assert r["attrs"]["dispatches"] == 1
    per_token = engine.kv_bytes_per_token()
    for step, (_pool, _ids, poss, *_rest) in zip(decode_steps, calls):
        # the decoding rows' one-token windows come first in the packed window
        n = step["attrs"]["active_slots"]
        want = float((np.asarray(poss)[0, :n] + 1).sum()) * per_token
        assert step["attrs"]["kv_bytes"] == pytest.approx(want)
    commits = [k for r in rounds for k in children[r["span_id"]] if k["name"] == "commit"]
    assert sum(c["attrs"]["tokens"] for c in commits) == sum(len(c.tokens) for c in completions.values())
