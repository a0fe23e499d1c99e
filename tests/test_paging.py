"""Paged KV cache tests: allocator/prefix-cache bookkeeping, chunked-prefill
parity, and the acceptance oracle for the paged scheduler — a drain through
``PagedContinuousBatchingScheduler`` must be **token-identical** to the
contiguous ``ContinuousBatchingScheduler`` for the same request stream
(greedy and sampled, staggered admissions, early EOS), because the paged
attention gather reconstructs the contiguous contraction exactly and
sampling keys stay ``(uid, token_index)``.
"""

import functools
import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relora_tpu.config.model import ModelConfig
from relora_tpu.models.params_util import init_params
from relora_tpu.models.pythia import GPTNeoXForCausalLM
from relora_tpu.serve.engine import InferenceEngine, build_decode_model
from relora_tpu.serve import sampling
from relora_tpu.serve.paging import NULL_PAGE, PageAllocator, PrefixCache, pages_needed
from relora_tpu.serve.scheduler import (
    ContinuousBatchingScheduler,
    PagedContinuousBatchingScheduler,
    Request,
)
from relora_tpu.utils.logging import MetricsLogger

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


# -- host-side bookkeeping ----------------------------------------------------


def test_pages_needed():
    assert pages_needed(1, 8) == 1
    assert pages_needed(8, 8) == 1
    assert pages_needed(9, 8) == 2
    assert pages_needed(0, 8) == 0


class TestPageAllocator:
    def test_null_page_reserved(self):
        alloc = PageAllocator(4, 8)
        pages = alloc.alloc(3)
        assert NULL_PAGE not in pages
        assert sorted(pages) == [1, 2, 3]

    def test_alloc_all_or_nothing(self):
        alloc = PageAllocator(5, 8)  # 4 usable pages
        assert alloc.alloc(3) is not None
        free_before = alloc.free_pages
        assert alloc.alloc(2) is None  # only 1 free: nothing allocated
        assert alloc.free_pages == free_before
        assert alloc.alloc(1) is not None
        assert alloc.free_pages == 0

    def test_decref_frees_incref_shares(self):
        alloc = PageAllocator(4, 8)
        [a, b] = alloc.alloc(2)
        alloc.incref([a])
        assert alloc.refcount(a) == 2
        assert alloc.decref([a, b]) == 1  # only b reached zero
        assert alloc.used_pages == 1
        assert alloc.decref([a]) == 1
        assert alloc.used_pages == 0

    def test_double_free_raises(self):
        alloc = PageAllocator(4, 8)
        [a] = alloc.alloc(1)
        alloc.decref([a])
        with pytest.raises(ValueError, match="double free"):
            alloc.decref([a])
        with pytest.raises(ValueError, match="invalid page"):
            alloc.decref([NULL_PAGE])

    def test_peak_used(self):
        alloc = PageAllocator(6, 8)
        pages = alloc.alloc(4)
        alloc.decref(pages)
        assert alloc.peak_used == 4
        assert alloc.used_pages == 0


class TestPrefixCache:
    def test_hashed_tokens_counts_what_the_digest_is_fed(self):
        """Every page-aligned prefix is hashed from token 0: a 1,024-token
        prompt at page 16 feeds the digest 63 prefixes on a missed lookup
        and 64 on a register, about 32 times its length each."""
        alloc = PageAllocator(66, 16)
        cache = PrefixCache(alloc)
        prompt = list(range(1024))
        assert cache.lookup(prompt) == ([], 0)
        assert cache.hashed_tokens == 16 * sum(range(1, 64)) == 32_256
        assert cache.register(prompt, alloc.alloc(64)) == 64
        assert cache.hashed_tokens - 32_256 == 16 * sum(range(1, 65)) == 33_280
        got, n = cache.lookup(prompt)  # a hit stops at the longest prefix: one digest
        assert n == 63 * 16 and cache.hashed_tokens == 32_256 + 33_280 + 63 * 16
        alloc.decref(got)

    def test_lookup_caps_below_full_prompt(self):
        """At least one prompt token must re-prefill: a prompt of exactly
        k pages only ever matches a (k-1)-page prefix."""
        alloc = PageAllocator(8, 4)
        cache = PrefixCache(alloc)
        prompt = list(range(8))  # exactly 2 pages
        pages = alloc.alloc(2)
        cache.register(prompt, pages)
        got, n = cache.lookup(prompt)
        assert n == 4 and got == pages[:1]
        alloc.decref(got)

    def test_register_lookup_roundtrip_increfs(self):
        alloc = PageAllocator(8, 4)
        cache = PrefixCache(alloc)
        prompt = list(range(10))  # 2 full pages + tail
        pages = alloc.alloc(pages_needed(10, 4))
        assert cache.register(prompt, pages) == 2
        got, n = cache.lookup(prompt + [99])
        assert n == 8 and got == pages[:2]
        # owner + the k=1 entry + the k=2 entry + lookup
        assert alloc.refcount(pages[0]) == 4
        assert alloc.refcount(pages[1]) == 3  # owner + k=2 entry + lookup
        # different tokens: no hit
        assert cache.lookup([7] * 10) == ([], 0)
        assert cache.stats()["hits"] == 1 and cache.stats()["lookups"] == 2

    def test_eviction_respects_live_refs(self):
        """Evicting an entry drops only the cache's reference: a page a live
        request still holds stays allocated."""
        alloc = PageAllocator(4, 4)
        cache = PrefixCache(alloc)
        prompt = list(range(5))
        pages = alloc.alloc(2)
        cache.register(prompt, pages)
        shared, _ = cache.lookup(prompt)  # live consumer increfs pages[0]
        freed = cache.clear()
        assert freed == 0  # owner + consumer refs keep everything alive
        alloc.decref(pages)  # owner retires
        assert alloc.refcount(shared[0]) == 1  # consumer still holds it
        assert alloc.decref(shared) == 1

    def test_lru_capacity(self):
        alloc = PageAllocator(16, 2)
        cache = PrefixCache(alloc, max_entries=2)
        for start in (0, 10, 20):
            pages = alloc.alloc(1)
            cache.register([start, start + 1, start + 2], pages)
            alloc.decref(pages)
        assert len(cache) == 2
        assert cache.lookup([0, 1, 2]) == ([], 0)  # oldest evicted
        got, _ = cache.lookup([20, 21, 22])
        assert got
        alloc.decref(got)


# -- engine: chunked prefill and memory --------------------------------------


def make_engines(cfg, *, cache_size=32, page_size=8, num_pages=None, chunk_size=8, spec_k=0):
    model = build_decode_model(cfg, cache_size=cache_size)
    base = type(model)(cfg, lora=None, dtype=jnp.float32, scan_layers=True)
    params = init_params(base, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    contiguous = InferenceEngine(cfg, params, cache_size=cache_size)
    paged = InferenceEngine(
        cfg,
        params,
        cache_size=cache_size,
        page_size=page_size,
        num_pages=num_pages or 3 * (cache_size // page_size) + 1,
        chunk_size=chunk_size,
        spec_k=spec_k,
    )
    return contiguous, paged


@pytest.mark.parametrize("cfg", [TINY_LLAMA, TINY_NEOX], ids=["llama", "neox"])
@pytest.mark.parametrize("chunk", [4, 8, 32])
def test_chunked_prefill_matches_whole(cfg, chunk):
    """Driving a prompt through fixed-size prefill chunks produces the same
    logits at every real position as one whole contiguous prefill — checked
    at every chunk boundary, including the ragged last chunk."""
    contiguous, paged = make_engines(cfg, chunk_size=chunk)
    L = 13
    prompt = np.asarray(
        jax.random.randint(jax.random.PRNGKey(3), (L,), 0, cfg.vocab_size)
    )
    whole, _ = contiguous.prefill(jnp.asarray(prompt[None, :]))

    pool = paged.init_pool()
    table = np.zeros((1, paged.block_table_width), np.int32)
    n_pages = pages_needed(L, paged.page_size)
    table[0, :n_pages] = np.arange(1, n_pages + 1)
    for start in range(0, L, chunk):
        ids = np.zeros((1, chunk), np.int32)
        n_real = min(chunk, L - start)
        ids[0, :n_real] = prompt[start : start + n_real]
        logits, pool = paged.prefill_chunk(jnp.asarray(ids), start, pool, table)
        np.testing.assert_allclose(
            np.asarray(logits[:, :n_real]),
            np.asarray(whole[:, start : start + n_real]),
            atol=1e-5,
        )


def neox_paged_engine():
    """A paged NeoX engine, the training-mode model it serves and the
    parameters both are given."""
    base = GPTNeoXForCausalLM(TINY_NEOX, dtype=jnp.float32)
    params = init_params(base, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    paged = InferenceEngine(TINY_NEOX, params, cache_size=32, page_size=8, num_pages=13, chunk_size=8)
    return paged, base, params


def test_neox_paged_programs_match_the_training_forward():
    """NeoX's serving forward hands the QKV product to its head split across
    a barrier the training forward lacks; the logits stay the training
    forward's: ``prefill_chunk`` at every real position of two chunks, the
    ragged last included, then ``decode_paged`` at every later position."""
    paged, base, params = neox_paged_engine()
    L, S, chunk = 11, 16, paged.chunk_size
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(7), (1, S), 0, TINY_NEOX.vocab_size))
    full = np.asarray(base.apply({"params": params}, jnp.asarray(ids)))

    pool = paged.init_pool()
    table = np.zeros((1, paged.block_table_width), np.int32)
    table[0, : pages_needed(S, paged.page_size)] = np.arange(1, pages_needed(S, paged.page_size) + 1)
    for start in range(0, L, chunk):
        n_real = min(chunk, L - start)
        chunk_ids = np.zeros((1, chunk), np.int32)
        chunk_ids[0, :n_real] = ids[0, start : start + n_real]
        logits, pool = paged.prefill_chunk(jnp.asarray(chunk_ids), start, pool, table)
        np.testing.assert_allclose(np.asarray(logits[:, :n_real]), full[:, start : start + n_real], atol=1e-5)
    for t in range(L, S):
        step, pool = paged.decode_paged(pool, ids[:, t : t + 1], np.full((1, 1), t, np.int32), table)
        np.testing.assert_allclose(np.asarray(step), full[:, t], atol=1e-5)


def test_only_the_serving_forward_carries_the_qkv_barrier():
    """Every paged program of a NeoX engine lowers with the QKV barrier; the
    training forward and its remat gradient, which the train step runs, lower
    without one."""
    paged, base, params = neox_paged_engine()
    for name, (jitted, args) in paged.paged_programs(4).items():
        assert "optimization_barrier" in jitted.lower(*args).as_text(), name

    ids = jnp.zeros((2, 16), jnp.int32)
    remat = base.clone(remat=True)

    def loss(p):
        return remat.apply({"params": p}, ids).mean()

    assert "optimization_barrier" not in jax.jit(base.apply).lower({"params": params}, ids).as_text()
    assert "optimization_barrier" not in jax.jit(jax.grad(loss)).lower(params).as_text()


def test_memory_plans_pool_scales_with_pages():
    """The paged kv_cache entry is the page pool: bytes scale with num_pages
    and undercut the contiguous max_batch × cache_size reservation."""
    contiguous, paged = make_engines(TINY_LLAMA, num_pages=13)
    small = paged.memory_plans(4)["pytree"]["kv_cache_bytes"]
    _, bigger = make_engines(TINY_LLAMA, num_pages=25)
    big = bigger.memory_plans(4)["pytree"]["kv_cache_bytes"]
    assert big / small == pytest.approx(25 / 13, rel=1e-6)
    contiguous_kv = contiguous.memory_plans(4)["pytree"]["kv_cache_bytes"]
    # 12 usable pages × 8 tokens = 96 cache entries vs 4 × 32 = 128
    assert small < contiguous_kv


# -- the pool rides the layer loop whole (PR 35) ------------------------------

@functools.lru_cache(maxsize=None)
def pool_engines(family):
    """Scanned paged engines, stored and int8 pool, whose 513 pages outweigh
    everything else a step holds, verify window and packed step included;
    kept, since the tests that read their compiled programs run no step."""
    cfg = {"llama": TINY_LLAMA, "neox": TINY_NEOX}[family]
    return make_paged_pair(cfg, num_pages=513, spec_k=2, token_budget=16)


def pool_sized_moves(text, stacked_leaf_shape):
    """``(op, shape)`` of every ``dynamic-slice`` and ``copy`` in a compiled
    program's text that makes, and every ``dynamic-update-slice`` that writes,
    a whole K or V leaf or one layer's pages of it."""
    L, P, ps, n_kv, hd = stacked_leaf_shape
    of_pool_size = {(L, P, ps, n_kv, hd), (L * P, ps, n_kv, hd), (1, P, ps, n_kv, hd), (P, ps, n_kv, hd)}
    shape_of = {
        name: tuple(int(d) for d in dims.split(",") if d)
        for name, dims in re.findall(r"(%[\w.\-]+) = \w+\[([\d,]*)\]", text)
    }
    moved = []
    for name, op, operands in re.findall(r"(%[\w.\-]+) = \S+ (dynamic-slice|dynamic-update-slice|copy)\(([^)]*)\)", text):
        # a slice or a copy by what it makes, a write-back by what it writes
        size = shape_of[operands.split(", ")[1]] if op == "dynamic-update-slice" else shape_of[name]
        if size in of_pool_size:
            moved.append((op, size))
    return moved


@pytest.mark.parametrize("program", ["decode_paged", "prefill_chunk", "verify_paged", "step_paged"])
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("family", ["llama", "neox"])
def test_no_program_slices_or_copies_the_pool(family, kv_dtype, program):
    """The stacked pool is a carry of the layer loop, addressed in place by
    page id: the compiled program aliases every pool leaf to its result, takes
    no layer's K or V pages out of the stack (``dynamic-slice``), writes none
    back (``dynamic-update-slice`` of a layer's pages) and copies neither a
    layer's pages nor a whole leaf — so what it holds beside its arguments is
    less than the pool.  (The parent of PR 35 held the pool once more and a
    layer's pages besides: 1.8 to 2.9 times the pool on these engines.)  The
    int8 pool's scale leaves, 1/128 of its codes here, are aliased too; XLA's
    CPU backend copies them where a write needs the scales before and after."""
    from relora_tpu.obs import memory as obs_memory

    eng = pool_engines(family)[kv_dtype == "int8"]
    jitted, args = eng.paged_programs(4)[program]
    compiled = jitted.lower(*args).compile()
    text = compiled.as_text()

    leaves = jax.tree_util.tree_leaves(eng.pool_shapes())
    aliased = re.search(r"input_output_alias={(.*?) }, entry_computation_layout", text).group(1)
    assert aliased.count("may-alias") + aliased.count("must-alias") == len(leaves)
    plan = obs_memory.xla_memory_plan(compiled)
    assert plan["alias_bytes"] == eng.pool_bytes()
    assert plan["temp_bytes"] < eng.pool_bytes()

    assert not pool_sized_moves(text, next(x.shape for x in leaves if x.ndim == 5))


@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
@pytest.mark.parametrize("cfg", [TINY_LLAMA, TINY_NEOX], ids=["llama", "neox"])
def test_layers_write_only_their_own_pages(cfg, kv_dtype):
    """Every layer reaches its pages in the stacked leaves by ``layer *
    num_pages + page``: after a chunk and a decode on a zero pool each layer's
    K and V are other than zero exactly at the written ``(page, offset)``s —
    its own, at its own values — and its page 0 holds only what the idle
    decode row wrote there."""
    stored, quant = make_paged_pair(cfg, num_pages=13)
    eng = quant if kv_dtype == "int8" else stored
    pool = eng.init_pool()
    rng = np.random.default_rng(5)
    # positions 4..11 of a request that holds pages 3 and 7
    ids = rng.integers(1, cfg.vocab_size, (1, 8)).astype(np.int32)
    _logits, pool = eng.prefill_chunk(jnp.asarray(ids), 4, pool, np.asarray([[3, 7, 0, 0]], np.int32))
    # row 0 goes on at position 12, row 1 is another request at position 2, row 2 is idle
    tables = np.asarray([[3, 7, 0, 0], [5, 0, 0, 0], [NULL_PAGE] * 4], np.int32)
    token = rng.integers(1, cfg.vocab_size, (3, 1)).astype(np.int32)
    _logits, pool = eng.decode_paged(pool, jnp.asarray(token), np.asarray([[12], [2], [0]], np.int32), tables)

    written = np.zeros((13, 8), bool)
    written[3, 4:] = written[7, :5] = written[5, 2] = written[NULL_PAGE, 0] = True
    (leaves,) = pool["layers"].values()
    for name in ("k", "v"):
        leaf = np.asarray(leaves[name])
        assert leaf.shape[:3] == (cfg.num_hidden_layers, 13, 8)
        for layer in leaf:
            np.testing.assert_array_equal(np.any(layer != 0, axis=(-1, -2)), written)
        assert not np.array_equal(leaf[0][written], leaf[1][written])
    if kv_dtype == "int8":
        for name in ("k_scale", "v_scale"):
            for layer in np.asarray(leaves[name]):
                np.testing.assert_array_equal(np.any(layer != 0, axis=-1), written.any(axis=1))


@pytest.mark.parametrize("cfg", [TINY_LLAMA, TINY_NEOX], ids=["llama", "neox"])
@pytest.mark.parametrize("scan_layers", [True, False], ids=["scanned", "unrolled"])
def test_paged_model_is_given_its_pool(cfg, scan_layers):
    """An init of the paged model makes the contiguous model's parameters and
    no pool; a step without the pool says what is missing; and the pool the
    engine makes (the family's ``pool_shapes``) is the tree a step returns."""
    kw = dict(cache_size=32, scan_layers=scan_layers)
    model = build_decode_model(cfg, page_size=8, num_pages=13, **kw)
    ids, table = jnp.zeros((1, 8), jnp.int32), jnp.zeros((1, 4), jnp.int32)
    made = model.init(jax.random.PRNGKey(0), ids, block_tables=table)
    assert set(made) == {"params"}
    plain = build_decode_model(cfg, **kw).init(jax.random.PRNGKey(0), ids)["params"]
    assert jax.tree_util.tree_structure(made["params"]) == jax.tree_util.tree_structure(plain)
    with pytest.raises(ValueError, match="needs its page pool"):
        model.apply(made, ids, positions=jnp.arange(8)[None, :], block_tables=table, mutable=["cache"])

    eng = InferenceEngine(cfg, made["params"], page_size=8, num_pages=13, chunk_size=8, **kw)
    pool = eng.init_pool()
    L = cfg.num_hidden_layers
    assert set(pool) == ({"layers"} if scan_layers else {f"layers_{i}" for i in range(L)})
    shapes = {x.shape for x in jax.tree_util.tree_leaves(pool)}
    assert shapes == {((L,) if scan_layers else ()) + (13, 8, cfg.kv_heads, cfg.head_dim)}
    _logits, after = eng.prefill_chunk(ids, 0, pool, np.asarray([[1, 0, 0, 0]], np.int32))
    assert jax.tree_util.tree_structure(after) == jax.tree_util.tree_structure(eng.pool_shapes())


def test_warmup_covers_all_shapes_no_retrace():
    """Paged warmup compiles the chunk + decode pair; afterwards a drain of
    mixed prompt lengths (short, page-straddling, multi-chunk) triggers no
    steady-state retrace."""
    _, paged = make_engines(TINY_LLAMA, chunk_size=8)
    report = paged.warmup(2)
    assert report["shapes"] == {"prefill_chunk": [1, 8], "decode_paged": [2, 1]}
    sched = PagedContinuousBatchingScheduler(paged, max_batch=2)
    reqs = [
        Request(uid=i, prompt=list(range(1, L + 1)), max_new_tokens=3)
        for i, L in enumerate((2, 7, 9, 17, 23))
    ]
    sched.run(reqs)
    assert paged.compile_watcher.steady_state_retraces == 0


def test_contiguous_default_warmup_covers_every_bucket():
    """Satellite: warmup's default prompt_buckets covers every power-of-two
    bucket up to capacity, so a long prompt after warmup never retraces."""
    contiguous, _ = make_engines(TINY_LLAMA)
    assert contiguous.default_prompt_buckets() == (16, 32)
    report = contiguous.warmup(2)
    assert report["prompt_buckets"] == [16, 32]
    sched = ContinuousBatchingScheduler(contiguous, max_batch=2)
    sched.run([Request(uid=0, prompt=list(range(1, 25)), max_new_tokens=4)])
    assert contiguous.compile_watcher.steady_state_retraces == 0


# -- scheduler: the token-parity oracle ---------------------------------------


def mixed_requests(vocab):
    """Mixed lengths (page-straddling + multi-chunk), greedy AND sampled,
    staggered through max_batch=2 slots, with uid 4 likely to hit EOS."""
    rng = np.random.default_rng(11)
    mk = lambda uid, L, new, **kw: Request(
        uid=uid, prompt=rng.integers(1, vocab, L).tolist(), max_new_tokens=new, **kw
    )
    return [
        mk(1, 13, 6),
        mk(2, 5, 9, temperature=0.8, top_p=0.9),
        mk(3, 21, 4),
        mk(4, 3, 7, temperature=1.1),
    ]


def drain(sched_cls, engine, reqs, **kwargs):
    sched = sched_cls(engine, max_batch=2, eos_id=9, key=jax.random.PRNGKey(42), **kwargs)
    completions = sched.run(reqs)
    return sched, {uid: c.tokens for uid, c in completions.items()}


@pytest.mark.parametrize("cfg", [TINY_LLAMA, TINY_NEOX], ids=["llama", "neox"])
def test_paged_drain_token_identical_to_contiguous(cfg):
    contiguous, paged = make_engines(cfg)
    reqs = mixed_requests(cfg.vocab_size)
    _, want = drain(ContinuousBatchingScheduler, contiguous, reqs)
    sched, got = drain(PagedContinuousBatchingScheduler, paged, reqs)
    assert got == want
    # all request pages released: only prefix-cache refs remain, and
    # clearing the cache drains the allocator completely
    sched.prefix_cache.clear()
    assert sched.allocator.used_pages == 0


def test_paged_parity_without_prefix_cache():
    contiguous, paged = make_engines(TINY_LLAMA)
    reqs = mixed_requests(TINY_LLAMA.vocab_size)
    _, want = drain(ContinuousBatchingScheduler, contiguous, reqs)
    sched, got = drain(
        PagedContinuousBatchingScheduler, paged, reqs, prefix_cache=False
    )
    assert got == want
    assert sched.allocator.used_pages == 0


def test_cancel_mid_decode_frees_pages():
    _, paged = make_engines(TINY_LLAMA)
    sched = PagedContinuousBatchingScheduler(paged, max_batch=2, prefix_cache=False)
    free0 = sched.allocator.free_pages
    sched.submit(Request(uid=1, prompt=[1, 2, 3, 4, 5], max_new_tokens=8))
    sched.submit(Request(uid=2, prompt=[6, 7, 8], max_new_tokens=8))
    for _ in range(3):  # both prefilled, a few decode steps in
        sched.step()
    assert sched.active_slots == 2
    completion = sched.cancel(1)
    assert completion.finish_reason == "cancelled" and completion.tokens
    assert sched.allocator.free_pages == free0 - pages_needed(
        3 + 8, paged.page_size
    )
    while sched.has_work():
        sched.step()
    assert sched.allocator.free_pages == free0  # pinned: no page leaked


def test_pool_exhaustion_queues_fifo():
    """When the pool cannot cover the queue head, it stays queued — FIFO, no
    skip-ahead — and admits once the running request retires."""
    # 5 usable pages of 8: one request reserves ceil((13+6)/8)=3
    _, paged = make_engines(TINY_LLAMA, num_pages=6)
    sched = PagedContinuousBatchingScheduler(paged, max_batch=2, prefix_cache=False)
    sched.submit(Request(uid=1, prompt=list(range(1, 14)), max_new_tokens=6))
    sched.submit(Request(uid=2, prompt=list(range(1, 14)), max_new_tokens=6))
    sched.submit(Request(uid=3, prompt=[1, 2], max_new_tokens=2))  # would fit!
    sched.step()
    # head (uid 2) needs 3 pages, only 2 free: stays queued, and uid 3 does
    # NOT jump the line even though its 1 page would fit
    assert sched.active_slots == 1 and sched.queue_depth == 2
    done = {}
    while sched.has_work():
        for c in sched.step():
            done[c.uid] = c
    assert set(done) == {1, 2, 3}
    assert done[1].tokens == done[2].tokens  # same prompt, both greedy
    assert sched.allocator.used_pages == 0


def test_prefix_hit_serves_identical_tokens():
    """A prompt served through shared prefix pages produces exactly the
    tokens the cold run produced — and the shared pages survive the donor
    retiring (refcounts, not ownership)."""
    _, paged = make_engines(TINY_LLAMA)
    sched = PagedContinuousBatchingScheduler(paged, max_batch=2)
    prompt = list(range(1, 22))  # 21 tokens: 2 full shareable pages
    cold = sched.run([Request(uid=1, prompt=prompt, max_new_tokens=5)])[1].tokens
    assert sched.prefix_cache.stats()["entries"] > 0
    # donor finished; its pages persist only through the cache's refs
    warm = sched.run([Request(uid=2, prompt=prompt, max_new_tokens=5)])[2].tokens
    assert warm == cold
    assert sched.prefix_cache.hits >= 1
    # a longer prompt sharing the prefix also matches its cold equivalent
    longer = prompt + [30, 31, 32]
    warm_long = sched.run([Request(uid=3, prompt=longer, max_new_tokens=5)])[3].tokens
    fresh = PagedContinuousBatchingScheduler(paged, max_batch=2, prefix_cache=False)
    cold_long = fresh.run([Request(uid=4, prompt=longer, max_new_tokens=5)])[4].tokens
    assert warm_long == cold_long


def test_prefix_eviction_never_corrupts_active_request():
    """Allocation pressure evicts prefix entries while a consumer request is
    mid-decode on those shared pages; its output must not change."""
    # 5 usable pages: uid2 (2 shared + 1 fresh) + uid3 (3 fresh) overflows,
    # so uid3's admission forces prefix eviction while uid2 is live
    _, paged = make_engines(TINY_LLAMA, num_pages=6)
    reference = PagedContinuousBatchingScheduler(paged, max_batch=2, prefix_cache=False)
    prompt = list(range(1, 18))  # 17 tokens: 2 shareable pages of 8
    want = reference.run([Request(uid=0, prompt=prompt, max_new_tokens=6)])[0].tokens

    sched = PagedContinuousBatchingScheduler(paged, max_batch=2)
    assert sched.run([Request(uid=1, prompt=prompt, max_new_tokens=6)])[1].tokens == want
    # consumer admits on the shared pages, then pressure from uid 3 forces
    # prefix eviction mid-flight (9 usable pages: 3+3 live + 2 cached > 9)
    sched.submit(Request(uid=2, prompt=prompt, max_new_tokens=6))
    sched.step()  # admit + first chunk; holds the shared pages
    assert sched.prefix_cache.hits >= 1
    sched.submit(Request(uid=3, prompt=list(range(40, 57)), max_new_tokens=6))
    done = {}
    while sched.has_work():
        for c in sched.step():
            done[c.uid] = c
    assert done[2].tokens == want  # eviction dropped refs, not live pages
    sched.prefix_cache.clear()
    assert sched.allocator.used_pages == 0


def test_paged_metrics_records(tmp_path):
    """Satellite: the paged scheduler's per-step records carry the pool and
    prefix gauges, and the request records still appear."""
    _, paged = make_engines(TINY_LLAMA)
    metrics = MetricsLogger(run_dir=str(tmp_path))
    sched = PagedContinuousBatchingScheduler(paged, max_batch=2, metrics=metrics)
    sched.run([Request(uid=1, prompt=list(range(1, 14)), max_new_tokens=4)])
    metrics.finish()
    records = [
        json.loads(line)
        for line in (tmp_path / "metrics.jsonl").read_text().splitlines()
        if line.strip()
    ]
    steps = [r for r in records if "serve/decode_step" in r]
    assert steps, records
    for key in (
        "serve/kv_pages_used",
        "serve/kv_pages_free",
        "serve/prefix_cache_hit_rate",
        "serve/prefill_pad_share",
        "serve/batch_fill",
        "serve/prefill_stall_share",
    ):
        assert key in steps[-1], key
    assert steps[-1]["serve/kv_pages_used"] >= 0
    assert any("serve_request" in r for r in records)


def test_paged_scheduler_rejects_contiguous_engine():
    contiguous, _ = make_engines(TINY_LLAMA)
    with pytest.raises(ValueError, match="page_size"):
        PagedContinuousBatchingScheduler(contiguous, max_batch=2)


# -- speculative rounds never move page accounting ----------------------------
#
# The design invariant under test: every verify-window write (accepted OR
# rejected) lands inside the request's worst-case admission allocation or the
# null page, so draft/verify/reject sequences are invisible to the allocator —
# rollback is host-side bookkeeping only.  tests/test_spec.py pins output
# parity; these pin the page accounting under mid-stream disruption.


def spec_sched(paged):
    return PagedContinuousBatchingScheduler(
        paged,
        max_batch=2,
        eos_id=9,
        key=jax.random.PRNGKey(42),
        prefix_cache=False,
        spec="ngram",
    )


def _step_until_drafting(sched, cap=10):
    for _ in range(cap):
        sched.step()
        if sched.spec_stats()["drafted"] > 0:
            return
    raise AssertionError("no draft fired within the step cap")


@pytest.mark.spec
def test_spec_rounds_restore_allocator_exactly():
    """Property: after a full drain with drafting rounds the free count
    returns exactly to its pre-request value — speculation allocates and
    frees nothing of its own."""
    _, paged = make_engines(TINY_LLAMA, spec_k=4)
    sched = spec_sched(paged)
    free0 = sched.allocator.free_pages
    rng = np.random.default_rng(3)
    sched.run(
        [
            Request(uid=1, prompt=[3, 5, 7] * 4, max_new_tokens=8),
            Request(uid=2, prompt=rng.integers(1, 256, 13).tolist(), max_new_tokens=6),
            Request(uid=3, prompt=[2, 4] * 6, max_new_tokens=7),
        ]
    )
    assert sched.spec_stats()["drafted"] > 0
    assert sched.allocator.free_pages == free0
    assert sched.allocator.used_pages == 0


@pytest.mark.spec
@pytest.mark.slow
def test_cancel_mid_verify_frees_only_victim_pages():
    """Cancelling a request between verify rounds frees exactly its own
    reservation; the surviving slot's pages stay live and its greedy output
    still matches a solo non-speculative run."""
    _, paged = make_engines(TINY_LLAMA, spec_k=4)
    sched = spec_sched(paged)
    free0 = sched.allocator.free_pages
    survivor_prompt = [2, 4] * 5
    sched.submit(Request(uid=1, prompt=[3, 5, 7] * 4, max_new_tokens=10))
    sched.submit(Request(uid=2, prompt=survivor_prompt, max_new_tokens=10))
    _step_until_drafting(sched)
    assert sched.active_slots == 2
    completion = sched.cancel(1)
    assert completion.finish_reason == "cancelled"
    # the victim's full worst-case reservation came back, nothing else
    assert sched.allocator.free_pages == free0 - pages_needed(
        len(survivor_prompt) + 10, paged.page_size
    )
    done = {}
    while sched.has_work():
        for c in sched.step():
            done[c.uid] = c
    assert sched.allocator.free_pages == free0  # pinned: no page leaked
    reference = PagedContinuousBatchingScheduler(
        paged, max_batch=2, eos_id=9, key=jax.random.PRNGKey(42), prefix_cache=False
    )
    want = reference.run(
        [Request(uid=2, prompt=survivor_prompt, max_new_tokens=10)]
    )[2].tokens
    assert done[2].tokens == want  # live pages untouched by the cancel


@pytest.mark.spec
@pytest.mark.slow
def test_deadline_expiry_mid_spec_restores_free_count():
    """A deadline expiring between verify rounds retires the slot with its
    partial output and returns its pages — the draft/verify machinery holds
    no page state that could leak across the expiry."""
    _, paged = make_engines(TINY_LLAMA, spec_k=4)
    sched = spec_sched(paged)
    free0 = sched.allocator.free_pages
    sched.submit(
        Request(uid=1, prompt=[3, 5, 7] * 4, max_new_tokens=10),
        deadline=time.monotonic() + 60.0,
    )
    sched.submit(Request(uid=2, prompt=[2, 4] * 5, max_new_tokens=8))
    _step_until_drafting(sched)
    # yank the running deadline into the past: the next round expires it
    slot = next(s for s in sched._slots if s is not None and s.request.uid == 1)
    slot.deadline = time.monotonic() - 1.0
    done = {}
    while sched.has_work():
        for c in sched.step():
            done[c.uid] = c
    assert done[1].finish_reason == "timeout" and done[1].tokens
    assert done[2].finish_reason in ("eos", "length")
    assert sched.allocator.free_pages == free0
    assert sched.allocator.used_pages == 0


# -- int8 KV pool: the quantization dial ---------------------------------------


def make_paged_pair(cfg, *, cache_size=32, page_size=8, num_pages=None, chunk_size=8, **engine_kw):
    """Same params, same pool geometry, two kv_dtype settings: the stored
    pool (bf16 = compute dtype) vs int8 codes + per-page scales."""
    model = build_decode_model(cfg, cache_size=cache_size)
    base = type(model)(cfg, lora=None, dtype=jnp.float32, scan_layers=True)
    params = init_params(base, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    kw = dict(
        cache_size=cache_size,
        page_size=page_size,
        num_pages=num_pages or 3 * (cache_size // page_size) + 1,
        chunk_size=chunk_size,
        **engine_kw,
    )
    stored = InferenceEngine(cfg, params, **kw)
    quant = InferenceEngine(cfg, params, kv_dtype="int8", **kw)
    return stored, quant


@pytest.mark.parametrize("cfg", [TINY_LLAMA, TINY_NEOX], ids=["llama", "neox"])
def test_int8_greedy_tokens_identical_to_bf16(cfg):
    """Acceptance: the int8 pool serves token-identical greedy completions.
    Per-(page, kv_head) scales keep the logit perturbation far below the
    greedy argmax margin on these prompts (pinned — a regression here means
    the quantizer or the in-kernel dequant changed)."""
    stored, quant = make_paged_pair(cfg)
    reqs = [r for r in mixed_requests(cfg.vocab_size) if r.temperature == 0.0]
    assert len(reqs) == 2  # uids 1 and 3: page-straddling + multi-chunk
    _, want = drain(PagedContinuousBatchingScheduler, stored, reqs)
    sched, got = drain(PagedContinuousBatchingScheduler, quant, reqs)
    assert got == want
    sched.prefix_cache.clear()
    assert sched.allocator.used_pages == 0


def test_int8_sampled_tokens_track_bf16():
    """Sampled requests see quantization through the softmax, so exact
    parity is not guaranteed — but on these short completions the perturbed
    logits must keep the same sampling decisions (same keys, same
    temperature): any divergence beyond a token or two means the
    quantization error grew out of its design envelope."""
    stored, quant = make_paged_pair(TINY_LLAMA)
    reqs = [r for r in mixed_requests(TINY_LLAMA.vocab_size) if r.temperature != 0.0]
    _, want = drain(PagedContinuousBatchingScheduler, stored, reqs)
    _, got = drain(PagedContinuousBatchingScheduler, quant, reqs)
    assert set(got) == set(want)
    for uid in want:
        a, b = want[uid], got[uid]
        agree = sum(x == y for x, y in zip(a, b))
        assert agree >= max(1, len(a) - 2), (uid, a, b)


def test_memory_plans_int8_halves_cache_bytes():
    """Acceptance: at equal num_pages the int8 pool (codes + f32 per-page
    scales) costs at most 0.55x the bf16-engine pool bytes.  (This tiny
    engine stores at f32 compute dtype, so the measured ratio is ~0.26;
    against a true bf16 pool the same leaves give ~0.51.)"""
    stored, quant = make_paged_pair(TINY_LLAMA, num_pages=13)
    stored_kv = stored.memory_plans(4)["pytree"]["kv_cache_bytes"]
    quant_kv = quant.memory_plans(4)["pytree"]["kv_cache_bytes"]
    assert quant_kv <= 0.55 * stored_kv
    assert quant.pool_bytes() == quant_kv
    assert quant.kv_bytes_per_token() == pytest.approx(
        quant_kv / (13 * 8), rel=1e-6
    )
    # int8 codes dominate; scales are the small remainder
    n_scales = 2 * TINY_LLAMA.num_hidden_layers * 13 * TINY_LLAMA.num_attention_heads
    assert quant_kv == stored_kv // 4 + n_scales * 4


def test_int8_warmup_covers_all_shapes_no_retrace():
    """The quantized write path (gather-requantize-scatter + scale updates)
    must not add steady-state retraces: warmup's two shapes still cover a
    mixed drain."""
    _, quant = make_paged_pair(TINY_LLAMA, chunk_size=8)
    report = quant.warmup(2)
    assert report["shapes"] == {"prefill_chunk": [1, 8], "decode_paged": [2, 1]}
    assert report["kv_dtype"] == "int8"
    sched = PagedContinuousBatchingScheduler(quant, max_batch=2)
    reqs = [
        Request(uid=i, prompt=list(range(1, L + 1)), max_new_tokens=3)
        for i, L in enumerate((2, 7, 9, 17, 23))
    ]
    sched.run(reqs)
    assert quant.compile_watcher.steady_state_retraces == 0


def test_paged_metrics_kv_bytes_gauges(tmp_path):
    """Satellite: decode-step records carry the HBM dial gauges, and the
    int8 engine reports the smaller pool."""
    stored, quant = make_paged_pair(TINY_LLAMA)
    values = {}
    for name, engine in (("stored", stored), ("int8", quant)):
        metrics = MetricsLogger(run_dir=str(tmp_path / name))
        sched = PagedContinuousBatchingScheduler(engine, max_batch=2, metrics=metrics)
        sched.run([Request(uid=1, prompt=list(range(1, 14)), max_new_tokens=4)])
        metrics.finish()
        records = [
            json.loads(line)
            for line in (tmp_path / name / "metrics.jsonl").read_text().splitlines()
            if line.strip()
        ]
        step = [r for r in records if "serve/decode_step" in r][-1]
        assert step["serve/kv_cache_bytes"] == engine.pool_bytes()
        assert step["serve/kv_bytes_per_token"] == pytest.approx(
            engine.kv_bytes_per_token(), rel=1e-3
        )
        assert sched.paging_stats()["kv_dtype"] == ("int8" if name == "int8" else "bf16")
        # byte accounting tracks the page accounting exactly (prefix-cache
        # refs keep some pages resident after the drain)
        page_bytes = engine.pool_bytes() // engine.num_pages
        assert sched.allocator.used_bytes == sched.allocator.used_pages * page_bytes
        sched.prefix_cache.clear()
        assert sched.allocator.used_bytes == 0
        values[name] = step["serve/kv_cache_bytes"]
    assert values["int8"] < 0.55 * values["stored"]


# -- the round's spans (docs/observability.md, "The serving round") -----------

ROUND_CHILDREN = {
    "admit", "prefill_chunk", "prefix_register", "first_token", "decode_prep", "decode_step", "commit", "round_metrics",
    "pull",  # the first token of a chunk that the last round's decode_step sent ahead
}


def traced_drain(engine, reqs, *, spy: str, **kwargs):
    """Drain ``reqs`` under a real Tracer; returns the scheduler, the span
    tree as ``(rounds, children by parent id)``, the completions and the
    positional arguments of every call of the engine method ``spy``."""
    from relora_tpu.obs.flight import FlightRecorder
    from relora_tpu.obs.tracer import Tracer

    rec = FlightRecorder(span_capacity=1 << 16)
    calls = []
    real = getattr(engine, spy)

    def spied(*args, **kw):
        # the scheduler's tables are live numpy state: keep them as they were
        calls.append(tuple(a.copy() if isinstance(a, np.ndarray) else a for a in args))
        return real(*args, **kw)

    setattr(engine, spy, spied)
    try:
        sched = PagedContinuousBatchingScheduler(
            engine, max_batch=2, eos_id=9, key=jax.random.PRNGKey(42),
            tracer=Tracer(service="serve", recorder=rec), **kwargs,
        )
        # as the server submits: each request under its own trace id, so its
        # ``decode`` span is the request's and not a child of the round
        completions = {}
        for req in reqs:
            sched.submit(req, trace_id=f"request-{req.uid}")
        while sched.has_work():
            completions.update({c.uid: c for c in sched.step()})
    finally:
        delattr(engine, spy)  # the instance attribute; the method is the class's
    assert rec.dropped_spans == 0
    children = {}
    for s in rec.spans():
        children.setdefault(s["parent_id"], []).append(s)
    rounds = [s for s in rec.spans() if s["name"] == "round"]
    return sched, rounds, children, completions, calls


def check_round_tree(rounds, children):
    """Every round holds the children of the span table, each inside its
    parent; returns the decode_step spans in round order."""
    decode_steps = []
    assert [r["attrs"]["round"] for r in rounds] == list(range(len(rounds)))
    for r in rounds:
        kids = children[r["span_id"]]
        names = [k["name"] for k in kids]
        assert set(names) <= ROUND_CHILDREN and names[0] == "admit", names
        # the next round's chunk, sent behind the decode before its pull, is this round's dispatch
        ahead = [g for k in kids if k["name"] == "decode_step" for g in children[k["span_id"]] if g["name"] == "prefill_chunk"]
        assert r["attrs"]["dispatches"] == names.count("prefill_chunk") + names.count("decode_step") + len(ahead) > 0
        assert r["attrs"]["chunk_ahead"] == len(ahead) <= 1 and all(g["attrs"]["ahead"] == 1 for g in ahead)
        if r["attrs"]["decoding"]:
            assert names[-4:] == ["decode_prep", "decode_step", "commit", "round_metrics"], names
        for k in kids:
            assert r["t_start"] <= k["t_start"] <= k["t_end"] <= r["t_end"]
            grandkids = children.get(k["span_id"], [])
            if k["name"] == "decode_step":
                assert [g["name"] for g in grandkids] in (["dispatch", "pull"], ["dispatch", "prefill_chunk", "pull"])
                assert all(g["trace_id"].startswith("request-") and g["span_id"] not in children for g in grandkids[1:-1])
                # the rows' draws, enqueued inside the dispatch (a packed
                # round of prompt tokens only has no row to draw for)
                samples = children.get(grandkids[0]["span_id"], [])
                assert [g["name"] for g in samples] == ["sample"] * bool(k["attrs"]["active_slots"])
                decode_steps.append(k)
            elif k["name"] == "prefill_chunk":
                assert [g["name"] for g in grandkids] in ([], ["pull"])
                assert k["trace_id"].startswith("request-")  # the request's trace, the round's child
                assert 0 < k["attrs"]["real"] <= k["attrs"]["chunk"] and "ahead" not in k["attrs"]
            elif k["name"] == "pull":
                assert grandkids == [] and names[names.index("pull") + 1] in ("prefix_register", "first_token")
            else:
                # the prefix hash, where it runs: a lookup an admission, a
                # register in the packed round's commit (the sequential
                # round's is the round's own child, after the last chunk)
                inside = {"admit": "prefix_lookup", "commit": "prefix_register"}.get(k["name"])
                assert {g["name"] for g in grandkids} <= {inside}, (k["name"], grandkids)
            for g in grandkids:
                assert k["t_start"] <= g["t_start"] <= g["t_end"] <= k["t_end"]
    return decode_steps


def test_round_spans_split_the_round_where_the_device_waits():
    """A drain under a real Tracer yields per ``round`` the children of the
    span table, children inside the parent, ``kv_bytes`` equal to the sum of
    (position + 1) over the decoding rows times ``kv_bytes_per_token``, and
    token counts that add up to what was served."""
    _, paged = make_engines(TINY_LLAMA)
    reqs = mixed_requests(TINY_LLAMA.vocab_size)
    sched, rounds, children, completions, calls = traced_drain(paged, reqs, spy="decode_paged")
    decode_steps = check_round_tree(rounds, children)
    assert len(rounds) == sched._round_total and len(decode_steps) == len(calls) > 4
    per_token = paged.kv_bytes_per_token()
    for step, (_pool, _tokens, positions, tables) in zip(decode_steps, calls):
        live = np.asarray(tables).any(axis=1)  # a decoding row's table is not all null
        want = float((np.asarray(positions)[:, 0] + 1)[live].sum()) * per_token
        assert step["attrs"]["kv_bytes"] == pytest.approx(want) and want > 0
        assert step["attrs"]["active_slots"] == int(live.sum())
    served = sum(len(c.tokens) for c in completions.values())
    commits = [k for r in rounds for k in children[r["span_id"]] if k["name"] == "commit"]
    first_tokens = len(reqs)  # sampled inside the prompt's last prefill_chunk
    assert sum(c["attrs"]["tokens"] for c in commits) + first_tokens == served
    admits = [k for r in rounds for k in children[r["span_id"]] if k["name"] == "admit"]
    assert sum(a["attrs"]["admitted"] for a in admits) == len(reqs)


def check_live_page_counts(paged, *, packed):
    """``live_pages`` of a ``decode_step`` span is the sum over the decoding
    rows of ``position // page_size + 1`` (the rows' positions as the model
    was handed them), and the round's ``serve/decode_live_page_share`` is
    that over the ``max_batch x table width`` entries a kernel that walked
    every one would visit.  (tests/test_packed.py runs it on the packed step.)"""

    class Records:
        def __init__(self):
            self.records = []

        def log(self, record):
            self.records.append(record)

    from relora_tpu.obs.metrics import MetricsRegistry

    metrics, registry = Records(), MetricsRegistry()
    sched, rounds, children, _, calls = traced_drain(
        paged,
        mixed_requests(TINY_LLAMA.vocab_size),
        spy="step_paged" if packed else "decode_paged",
        packed=packed,
        metrics=metrics,
        obs_registry=registry,
    )
    decode_steps = check_round_tree(rounds, children)
    shares = [r["serve/decode_live_page_share"] for r in metrics.records if "serve/decode_step" in r]
    assert len(decode_steps) == len(calls) == len(shares) > 4
    ps, entries = paged.page_size, 2 * paged.block_table_width
    seen = set()
    for step, share, (_pool, _tokens, positions, *rest) in zip(decode_steps, shares, calls):
        positions = np.asarray(positions)
        if packed:  # the decoding rows' one-token windows come first
            positions = positions[0, : step["attrs"]["active_slots"]]
        else:  # a decoding row's table is not all null
            positions = positions[:, 0][np.asarray(rest[0]).any(axis=1)]
        want = int((positions // ps + 1).sum())
        assert step["attrs"]["live_pages"] == want
        assert share == pytest.approx(want / entries, abs=1e-4)
        seen.add(want)
    assert len(seen) > 2 and max(seen) > 2  # rows crossed page boundaries
    assert registry.gauge_value("decode_live_page_share") == pytest.approx(shares[-1], abs=1e-4)


def test_decode_step_counts_the_table_entries_a_decode_must_walk():
    check_live_page_counts(make_engines(TINY_LLAMA)[1], packed=False)


def test_idle_step_leaves_no_span():
    from relora_tpu.obs.flight import FlightRecorder
    from relora_tpu.obs.tracer import Tracer

    _, paged = make_engines(TINY_LLAMA)
    rec = FlightRecorder()
    sched = PagedContinuousBatchingScheduler(
        paged, max_batch=2, tracer=Tracer(service="serve", recorder=rec)
    )
    assert sched.step() == [] and rec.spans() == []


# -- the round's programs (PERF.md §6, PR 27): the host fills numpy, the -------
# -- round's jitted programs derive the rest ----------------------------------


def round_sched():
    """A warmed paged scheduler of five rows: one drain has compiled every
    shape a round uses (chunk, decode, the sampler at five rows and at one),
    so whatever runs afterwards is dispatch, not tracing."""
    _, paged = make_engines(TINY_LLAMA, num_pages=41)
    sched = PagedContinuousBatchingScheduler(
        paged, max_batch=5, key=jax.random.PRNGKey(42), prefix_cache=False
    )
    rng = np.random.default_rng(5)
    sched.run([Request(uid=90 + i, prompt=rng.integers(1, 256, 11).tolist(), max_new_tokens=3) for i in range(2)])
    return sched, rng


def submit_short(sched, rng, uid, **kw):
    """A request whose prompt is one chunk and whose output outlasts the test."""
    sched.submit(Request(uid=uid, prompt=rng.integers(1, 256, 5).tolist(), max_new_tokens=24, **kw))


def decoding_rows(sched):
    return sum(s is not None and s.decoding for s in sched._slots)


def test_round_builds_no_key_and_no_stack_on_the_host(monkeypatch):
    """From admission through first tokens to a decode round of 4 live rows,
    greedy and sampled: an eager ``jax.random.fold_in`` or ``jnp.stack`` (a
    key per row built on the host) fails the step.  Inside a jitted program
    their arguments are tracers, and pass."""
    sched, rng = round_sched()
    real_fold_in, real_stack = jax.random.fold_in, jnp.stack

    def guarded(real):
        def fn(*args, **kwargs):
            flat = jax.tree_util.tree_leaves((args, kwargs))
            assert any(isinstance(a, jax.core.Tracer) for a in flat), (
                f"eager {real.__name__} in a scheduler round"
            )
            return real(*args, **kwargs)

        return fn

    monkeypatch.setattr(jax.random, "fold_in", guarded(real_fold_in))
    monkeypatch.setattr(jnp, "stack", guarded(real_stack))
    with pytest.raises(AssertionError, match="eager fold_in"):
        jax.random.fold_in(sched.key, 1)  # the guard sees what it should
    np.testing.assert_array_equal(  # and lets a traced call through
        np.asarray(jax.jit(lambda k: jax.random.fold_in(k, 1))(sched.key)),
        np.asarray(real_fold_in(sched.key, 1)),
    )
    for uid, kw in enumerate([{}, {"temperature": 0.9, "top_p": 0.8}, {}, {"temperature": 1.2}]):
        submit_short(sched, rng, uid + 1, **kw)
    while decoding_rows(sched) < 4:
        assert sched.step() == []
    before = [len(s.tokens) for s in sched._slots if s is not None]
    assert sched.step() == [] and decoding_rows(sched) == 4
    assert [len(s.tokens) for s in sched._slots if s is not None] == [n + 1 for n in before]
    assert not hasattr(sched, "_request_key")


def xla_programs(fn, trace_dir):
    """How many XLA programs ran while ``fn()`` did, with the names of the
    jitted functions called: the CPU client's execute events in a
    ``jax.profiler`` session, the count a chip's trace gives as the events of
    its "XLA Modules" line."""
    import glob

    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(trace_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    names = [
        e.name
        for plane in ProfileData.from_file(path).planes
        if plane.name == "/host:CPU"
        for line in plane.lines
        for e in line.events
    ]
    called = sorted({n for n in names if n.startswith("PjitFunction(")})
    return sum(n.endswith("Executable::Execute") for n in names), called


class BackendCompiles:
    """Counts JAX's backend compilations while ``on`` is set (a monitoring
    listener cannot be taken off again, so it is switched)."""

    def __init__(self):
        import jax.monitoring

        self.count, self.on = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, duration, **_):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


#: temperature and top_p of the first request and of requests 2-4, and the
#: path the sampler's one program takes in the four-row round
ROUND_MIXES = {
    "mixed": ((0.9, 1.0), [(1.1, 1.0), (0.0, 1.0), (1.1, 1.0)], "categorical"),
    "greedy": ((0.0, 1.0), [(0.0, 1.0), (0.0, 0.5), (0.0, 1.0)], "greedy"),
    "sampled": ((0.7, 0.9), [(1.1, 1.0), (1.0, 0.8), (0.0, 1.0)], "nucleus"),
}


@pytest.mark.parametrize("mix", list(ROUND_MIXES))
def test_programs_a_round_do_not_grow_with_the_rows(tmp_path, mix):
    """A decode round dispatches two programs (``decode_paged`` and the
    sampler) with 1 row decoding and with 4; a prompt's chunk is one more, and
    so is the next round's chunk, sent behind the decode with its first token's
    draw: none per row, whether the rows are greedy, sampled or mixed.
    The scheduler was warmed by greedy requests alone: the first sampled draw
    takes another branch of the program it has, and compiles nothing."""
    from relora_tpu.obs.metrics import MetricsRegistry

    first, others, path = ROUND_MIXES[mix]
    sched, rng = round_sched()
    sched.obs_registry = MetricsRegistry()
    counted, called = xla_programs(lambda: jnp.ones(3) + 1, tmp_path / "probe")
    assert counted >= 1, "the profile shows no execute event: the count below would be blind"
    compiles = BackendCompiles()
    compiles.on = True
    jax.jit(lambda x: x * 3 + len(mix))(jnp.ones(2))  # a program nothing has compiled yet
    assert compiles.count >= 1, "no compile event: the count below would be blind"
    compiles.count = 0

    submit_short(sched, rng, 1, temperature=first[0], top_p=first[1])
    sched.step()  # its chunk, its first token, its first decode
    assert decoding_rows(sched) == 1
    one_row, called_1 = xla_programs(sched.step, tmp_path / "rows1")

    for uid, (temperature, top_p) in zip((2, 3, 4), others):
        submit_short(sched, rng, uid, temperature=temperature, top_p=top_p)
        sched.step()
    assert decoding_rows(sched) == 4
    draws = lambda: {p: sched.obs_registry.counter_value("sample_draws_total", ("path", p)) for p in sampling.PATHS}
    before = draws()
    four_rows, called_4 = xla_programs(sched.step, tmp_path / "rows4")
    assert one_row == four_rows == 2, (one_row, called_1, four_rows, called_4)
    assert {p: n - before[p] for p, n in draws().items() if n != before[p]} == {path: 1}

    # a two-chunk prompt: its first chunk rides a round with the four decodes, and its
    # second (with the first token's draw) goes behind that decode, ahead of the pull
    sched.submit(Request(uid=5, prompt=rng.integers(1, 256, 13).tolist(), max_new_tokens=4))
    with_chunk, called_c = xla_programs(sched.step, tmp_path / "chunk")
    assert decoding_rows(sched) == 4 and sched._slots[4].prefill_progress == 13
    # chunk; decode, sampler; chunk, its last position's logits (a slice and a squeeze), the one-row sampler
    assert with_chunk == 7, (with_chunk, called_c)
    landed, called_l = xla_programs(sched.step, tmp_path / "landed")  # the round that chunk was sent for runs no other
    assert decoding_rows(sched) == 5 and landed == 2, (landed, called_l)
    compiles.on = False
    assert compiles.count == 0, "a draw compiled: the sampler's path must be chosen inside its program"


def test_sample_draws_total_counts_each_draw_under_its_batchs_path():
    """``sample_draws_total{path=...}`` takes one count a dispatch of the
    sampler, labelled by ``sampling.batch_path`` over the rows' temperatures
    and top_ps (the predicate the program branches on), and the round's
    ``sample`` span carries the same ``path``."""
    from relora_tpu.obs.flight import FlightRecorder
    from relora_tpu.obs.metrics import MetricsRegistry
    from relora_tpu.obs.tracer import Tracer

    sched, rng = round_sched()
    rec = FlightRecorder()
    sched.obs_registry, sched.tracer = MetricsRegistry(), Tracer(service="serve", recorder=rec)
    count = lambda p: sched.obs_registry.counter_value("sample_draws_total", ("path", p))
    uids = iter(range(10, 20))

    def step(**kw):
        """Submit a request with ``kw`` (if any), run a round: the draws it
        counted by path, and the ``path`` of its ``sample`` spans."""
        if kw:
            submit_short(sched, rng, next(uids), **kw)
        before = {p: count(p) for p in sampling.PATHS}
        n_spans = len(rec.spans())
        sched.step()
        spans = [s["attrs"]["path"] for s in rec.spans()[n_spans:] if s["name"] == "sample"]
        return {p: count(p) - before[p] for p in sampling.PATHS}, spans

    # a greedy request alone: its first token and its first decode, both greedy
    assert step(temperature=0.0) == ({"greedy": 2, "categorical": 0, "nucleus": 0}, ["greedy"])
    assert step() == ({"greedy": 1, "categorical": 0, "nucleus": 0}, ["greedy"])
    # a greedy row's top_p asks for nothing
    assert step(temperature=0.0, top_p=0.5) == ({"greedy": 2, "categorical": 0, "nucleus": 0}, ["greedy"])
    # a sampling row: its one-row first token and the round it joins are categorical
    assert step(temperature=0.8) == ({"greedy": 0, "categorical": 2, "nucleus": 0}, ["categorical"])
    # a nucleus row turns the round; its neighbours' tokens do not depend on it
    assert step(temperature=0.8, top_p=0.9) == ({"greedy": 0, "categorical": 0, "nucleus": 2}, ["nucleus"])
    assert step() == ({"greedy": 0, "categorical": 0, "nucleus": 1}, ["nucleus"])
    assert "sample_draws_total.greedy" in sched.obs_registry.snapshot()


# -- one cache spec per layer kind (models/step.py) -----------------------------

TINY_MIMO = ModelConfig(
    family="mimo", vocab_size=256, hidden_size=32, intermediate_size=64, num_hidden_layers=4,
    num_attention_heads=4, num_key_value_heads=1, max_sequence_length=256, rotary_pct=0.334,
    layer_window=(0, 1, 1, 0), layer_moe=(0, 1, 1, 1), qk_head_dim=12, v_head_dim=8, window_kv_heads=2,
    sliding_window=8, window_sink=True, value_scale=0.707, moe_intermediate_size=16,
    n_routed_experts=8, experts_held=2, expert_offset=0, num_experts_per_tok=2,
)


def _spec_engine(model_cfg, kv_dtype="bf16"):
    model = build_decode_model(model_cfg, cache_size=64)
    params = init_params(model, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    return InferenceEngine(
        model_cfg, params, cache_size=64, page_size=4, num_pages=40, chunk_size=8, kv_dtype=kv_dtype
    )


@pytest.mark.parametrize("model_cfg", [TINY_LLAMA, TINY_NEOX, TINY_MIMO], ids=["llama", "neox", "mimo"])
def test_cache_specs_say_what_the_pool_holds(model_cfg):
    """The engine reads one thing for all three families: a spec per cache
    kind whose bytes are the pool's leaves', and whose per-token bytes are
    what the scheduler prices a decode's reads with."""
    from relora_tpu.models.step import PAGED, RING

    eng = _spec_engine(model_cfg)
    specs = eng.cache_specs(3)
    leaves = jax.tree_util.tree_leaves(eng.pool_shapes(3))
    assert sum(c.pool_bytes for c in specs) == eng.pool_bytes(3) == sum(
        int(np.prod(x.shape)) * x.dtype.itemsize for x in leaves
    )
    paged = specs[0]
    assert paged.kind == PAGED and paged.num_pages == 40 and paged.table_width == 16 and paged.window == 0
    assert eng.pool_bytes(3, PAGED) == paged.pool_bytes
    if model_cfg.family != "mimo":
        assert len(specs) == 1 and eng.pool_bytes(3, RING) == 0
        assert paged.bytes_per_token == eng.kv_bytes_per_token() == 2 * 2 * 4 * 16 * 4
        assert set(eng.tables_by_kind(np.zeros((3, 16), np.int32))) == {PAGED}
        return
    ring = specs[1]
    assert ring.kind == RING and ring.layers == 2 and ring.window == 8
    assert ring.table_width == (8 + 8) // 4 + 1 and ring.num_pages == 1 + 3 * 5
    assert (paged.k_dim, paged.v_dim, paged.k_pad, paged.kv_heads, ring.kv_heads) == (12, 8, 116, 1, 2)
    assert paged.bytes_per_token == 2 * 1 * 20 * 4 and ring.bytes_per_token == 2 * 2 * 20 * 4
    assert paged.read_bytes(99) == 100 * paged.bytes_per_token and ring.read_bytes(99) == 8 * ring.bytes_per_token
    assert ring.read_bytes(2) == 3 * ring.bytes_per_token
    # the pool's leaves: a K head stored in whole 128-lane tiles, V as it is
    shapes = eng.pool_shapes(3)
    assert shapes["layers_0"]["attn"]["k"].shape == (40, 4, 1, 128) and shapes["layers_0"]["attn"]["v"].shape == (40, 4, 1, 8)
    assert shapes["layers_1"]["attn"]["k"].shape == (16, 4, 2, 128)


def test_ring_tables_are_the_slots_own_pages_and_null_for_idle_rows():
    from relora_tpu.models.step import PAGED, RING

    eng = _spec_engine(TINY_MIMO)
    paged = np.zeros((3, 16), np.int32)
    paged[1, :3] = [7, 9, 2]
    tables = eng.tables_by_kind(paged)
    assert np.array_equal(np.asarray(tables[PAGED]), paged)
    assert tables[RING].tolist() == [[0] * 5, [6, 7, 8, 9, 10], [0] * 5]
    # a prefill chunk of the request that will decode in slot 2
    assert eng.tables_by_kind(paged[1:2], slot=2)[RING].tolist() == [[11, 12, 13, 14, 15]]


def test_allocator_counts_global_pages_only():
    """Admission allocates the paged kind's pages; a window layer's ring costs
    a request nothing, however long it is."""
    eng = _spec_engine(TINY_MIMO)
    sched = PagedContinuousBatchingScheduler(eng, max_batch=3, eos_id=-1, prefix_cache=False)
    assert sched.allocator.page_bytes == eng.pool_bytes(3, "paged") // 40
    sched.submit(Request(uid=1, prompt=list(range(30)), max_new_tokens=10))
    sched.step()
    assert sched.allocator.used_pages == pages_needed(40, 4)
