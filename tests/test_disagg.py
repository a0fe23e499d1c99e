"""Disaggregated prefill/decode tests: the cross-replica handoff oracle.

A two-pool drain — long prompts prefilled on a ``role="prefill"`` scheduler,
their finished page runs shipped through the real wire framing
(``encode_page_run``/``decode_page_run``) into a ``role="decode"`` peer,
short prompts decoded on the peer directly — must be **token-identical** to
one mixed replica draining the same request stream, because the migrated
run carries the exact pool bytes (int8 codes + per-page k/v scales), the
exact positions, and sampling keys stay ``(uid, token_index)``.  On top of
parity: every failure path (sink rejection, malformed frame, pool/slot
exhaustion) fails *open* to local decode with the same tokens, donor-side
prefix exports pin their pages against eviction for the transfer's
lifetime, and a warmed receiver adopts migrated runs with zero
steady-state retraces.
"""

import numpy as np

import jax
import pytest

from relora_tpu.config.model import ModelConfig
from relora_tpu.models.params_util import init_params
from relora_tpu.serve import disagg, wire
from relora_tpu.serve.engine import InferenceEngine, build_decode_model
from relora_tpu.serve.paging import PageAllocator, PrefixCache
from relora_tpu.serve.scheduler import PagedContinuousBatchingScheduler, Request

pytestmark = [pytest.mark.serve, pytest.mark.disagg]

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
PAGE = 8
CACHE = 32
THRESHOLD = 12  # prompt tokens at/above this go to the prefill pool

_ENGINES: dict = {}


def make_engine(cfg, *, fresh=False, kv_dtype="int8"):
    """One int8-pool paged engine per config (the wire's 4x-under-bf16 claim
    rides the int8 codes + per-page scales, so the tests exercise exactly
    that layout; ``kv_dtype="bf16"`` is the arm the byte ratio is held against).
    Cached so parity drains share jit caches and weights."""
    key = (cfg.family, kv_dtype)
    if not fresh and key in _ENGINES:
        return _ENGINES[key]
    model = build_decode_model(cfg, cache_size=CACHE)
    base = type(model)(cfg, dtype=jax.numpy.float32, scan_layers=True)
    params = init_params(base, jax.random.PRNGKey(0), jax.numpy.zeros((1, 8), jax.numpy.int32))
    engine = InferenceEngine(
        cfg,
        params,
        cache_size=CACHE,
        page_size=PAGE,
        num_pages=3 * (CACHE // PAGE) + 1,
        chunk_size=CHUNK,
        kv_dtype=kv_dtype,
    )
    if not fresh:
        _ENGINES[key] = engine
    return engine


def make_sched(engine, role="mixed", **kw):
    return PagedContinuousBatchingScheduler(
        engine,
        max_batch=MAX_BATCH,
        eos_id=9,
        key=jax.random.PRNGKey(42),
        role=role,
        **kw,
    )


def mixed_requests(vocab=256):
    """Long (prefill-pool) and short (decode-pool) prompts interleaved,
    greedy AND sampled — the sampled rows prove the keys travel."""
    rng = np.random.default_rng(7)
    mk = lambda uid, L, new, **kw: Request(
        uid=uid, prompt=rng.integers(1, vocab, L).tolist(), max_new_tokens=new, **kw
    )
    return [
        mk(1, 13, 6),
        mk(2, 5, 8, temperature=0.8, top_p=0.9),
        mk(3, 21, 5, temperature=1.1),
        mk(4, 3, 6),
    ]


def drain_disagg_pair(engine, reqs, *, wire_hook=None, sink_override=None):
    """Drive a prefill-role donor and a decode-role receiver to completion,
    relaying every handoff through the real wire framing.  Returns
    ``(completions, donor, recv)``; a handoff that cannot land immediately
    (receiver slots full) waits, like the in-flight async transfer it
    models, and any insert error fails open to donor-local decode."""
    donor = make_sched(engine, role="prefill")
    recv = make_sched(engine, role="decode")
    completions = {}

    def finish(c):
        assert c.tokens is not None
        assert completions.setdefault(c.uid, c) is c, f"uid {c.uid} finished twice"

    handoffs = []
    if sink_override is not None:
        donor.migration_sink = sink_override
    else:
        def sink(record, entries):
            blob = wire.encode_page_run(record, entries)
            if wire_hook is not None:
                blob = wire_hook(blob)
            handoffs.append((int(record["uid"]), blob))
            return True

        donor.migration_sink = sink

    for req in reqs:
        pool = donor if len(req.prompt) >= THRESHOLD else recv
        assert disagg.classify_request(len(req.prompt), THRESHOLD) == (
            "prefill" if pool is donor else "decode"
        )
        pool.submit(req, on_finish=finish)

    for _ in range(400):
        if not (donor.has_work() or recv.has_work() or handoffs):
            break
        if donor.has_work():
            donor.step()
        still_waiting = []
        for uid, blob in handoffs:
            try:
                record, arrays = wire.decode_page_run(blob)
                recv.submit_migrated(record, arrays, on_finish=finish)
                donor.migration_commit(uid, len(blob))
            except RuntimeError:
                still_waiting.append((uid, blob))  # no free slot: transfer waits
            except Exception as e:
                donor.migration_failed(uid, str(e))
        handoffs[:] = still_waiting
        if recv.has_work():
            recv.step()
    else:
        raise AssertionError("disagg drain did not converge")
    return completions, donor, recv


# -- wire framing -------------------------------------------------------------


def test_wire_round_trip_bitwise():
    rng = np.random.default_rng(3)
    arrays = []
    for i, (dtype, shape) in enumerate(
        [("int8", (2, 3, 8, 4, 16)), ("float32", (2, 3, 8)), ("bfloat16", (1, 4))]
    ):
        if dtype == "bfloat16":
            raw = rng.integers(0, 256, int(np.prod(shape)) * 2, dtype=np.uint8).tobytes()
        else:
            raw = np.ascontiguousarray(
                rng.integers(-100, 100, shape).astype(dtype)
            ).tobytes()
        arrays.append((f"leaf{i}", dtype, shape, raw))
    meta = {"uid": 7, "prompt": [1, 2, 3], "position": 3, "n_pages": 1}
    blob = wire.encode_page_run(meta, arrays)
    meta2, arrays2 = wire.decode_page_run(blob)
    assert meta2 == meta
    assert len(arrays2) == len(arrays)
    for (n, d, s, raw), (n2, d2, s2, raw2) in zip(arrays, arrays2):
        assert (n2, d2, tuple(s2)) == (n, d, tuple(s))
        assert raw2 == raw  # bitwise: the pool bytes survive the frame intact
    # a second encode of the same inputs is byte-identical (stable framing)
    assert wire.encode_page_run(meta, arrays) == blob


def test_wire_rejects_torn_and_corrupt_frames():
    blob = wire.encode_page_run(
        {"uid": 1}, [("k", "int8", (2, 2), bytes(range(4)))]
    )
    for bad in (
        b"",  # empty
        blob[:7],  # shorter than any valid frame
        blob[:-3],  # truncated mid-crc
        blob[: len(blob) // 2],  # torn payload
        b"XXXX" + blob[4:],  # bad magic
        blob[:-4] + b"\x00\x00\x00\x00",  # crc mismatch
        blob + b"trailing",  # crc covers length: garbage tail rejected
        blob[:10] + bytes([blob[10] ^ 0xFF]) + blob[11:],  # flipped byte
    ):
        with pytest.raises(ValueError):
            wire.decode_page_run(bad)


# -- roles, classification, peers ---------------------------------------------


def test_classify_and_pick_peers():
    assert disagg.classify_request(128, 128) == "prefill"
    assert disagg.classify_request(127, 128) == "decode"
    peers = [
        {"rid": "r0", "host": "h", "port": 1, "role": "prefill"},
        {"rid": "r1", "host": "h", "port": 2, "role": "decode"},
        {"rid": "r2", "host": "h", "port": 3, "role": "mixed"},
        {"rid": "r3", "host": "h", "port": 4, "role": "decode"},
    ]
    picks = disagg.pick_peers(peers, role="decode", exclude_rid="r1")
    assert [p["rid"] for p in picks] == ["r3", "r2"]  # role first, mixed fallback
    picks = disagg.pick_peers(
        [p for p in peers if p["role"] != "decode"], role="decode", exclude_rid="r0"
    )
    assert [p["rid"] for p in picks] == ["r2"]  # degraded fleet: mixed only


def test_prefix_directory_update_lookup_drop():
    d = disagg.PrefixPageDirectory(max_entries=4)
    d.update("r0", "h0", 1, ["aa", "bb"])
    d.update("r1", "h1", 2, ["bb", "cc"])
    # caller order (longest prefix first) wins; r1 re-advertised "bb" last
    assert d.lookup(["zz", "bb"]) == ("bb", "r1", "h1", 2)
    # exclude keeps a replica from fetching from itself
    assert d.lookup(["cc"], exclude_rid="r1") is None
    d.update("r0", "h0", 1, ["aa"])  # "bb" no longer advertised by r0 either
    d.drop_replica("r1")
    assert d.lookup(["bb", "cc", "aa"]) == ("aa", "r0", "h0", 1)
    # LRU bound: flooding evicts the oldest entries without breaking rid sets
    d.update("r2", "h2", 3, [f"d{i}" for i in range(6)])
    assert len(d) <= 4
    d.drop_replica("r2")
    assert d.lookup([f"d{i}" for i in range(6)]) is None


# -- donor-side export pinning ------------------------------------------------


def test_prefix_cache_acquire_pins_against_eviction():
    """Property (seeded sweep): pages pinned by ``acquire`` for an in-flight
    export NEVER return to the free list — not under LRU eviction, not under
    ``clear``, not under allocation pressure — until the matching decref."""
    rng = np.random.default_rng(13)
    for trial in range(25):
        alloc = PageAllocator(num_pages=17, page_size=4)
        cache = PrefixCache(alloc, max_entries=int(rng.integers(1, 5)))
        live = []  # (digest_hex, pinned_pages)
        registered = []
        for op in range(40):
            roll = rng.random()
            if roll < 0.45:
                n_pages = int(rng.integers(1, 4))
                pages = alloc.alloc(n_pages)
                if pages is None:
                    cache.evict(n_pages)
                    pages = alloc.alloc(n_pages)
                if pages is None:
                    continue
                prompt = rng.integers(1, 99, n_pages * 4).tolist()
                cache.register(prompt, pages)
                registered.append(prompt)
                alloc.decref(pages)  # cache refs keep the run alive
            elif roll < 0.7 and cache.digests():
                digest = str(rng.choice(cache.digests()))
                got = cache.acquire(digest)
                if got is not None:
                    live.append((digest, got[0]))
            elif roll < 0.85:
                cache.evict(int(rng.integers(1, 17)))
            elif live:
                digest, pages = live.pop(int(rng.integers(len(live))))
                alloc.decref(pages)
            # invariant: every pinned page is still referenced, and a fresh
            # all-or-nothing alloc can never be handed a pinned page
            pinned = {p for _, pages in live for p in pages}
            for p in pinned:
                assert alloc.refcount(p) >= 1, f"trial {trial}: pinned page {p} freed"
            grab = alloc.alloc(alloc.free_pages)
            if grab is not None:
                assert not (set(grab) & pinned)
                alloc.decref(grab)
        cache.clear()
        for digest, pages in live:
            pinned = set(pages)
            assert all(alloc.refcount(p) >= 1 for p in pinned)
            alloc.decref(pages)
        assert alloc.used_pages == 0  # every pin released -> pool fully free
        assert cache.acquire("zz") is None  # non-hex digest: miss, not a raise


# -- migration parity ---------------------------------------------------------


def test_migrated_insert_zero_steady_state_retraces():
    """warmup(migrate=True) compiles the page-run gather/scatter buckets;
    afterwards a full disagg drain — exports, wire, adopts, decodes to
    finish — never retraces on either side.  Runs first in this section so
    the warmed engine it builds is the one every later llama test reuses:
    the module pays one compile budget, not two."""
    engine = make_engine(TINY_LLAMA, fresh=True)
    report = engine.warmup(MAX_BATCH, migrate=True)
    assert report["shapes"]["page_run"] == list(engine.page_run_buckets())
    completions, donor, recv = drain_disagg_pair(engine, mixed_requests())
    assert len(completions) == 4
    assert recv._migrated_inserts == 2
    assert engine.compile_watcher.steady_state_retraces == 0
    _ENGINES[TINY_LLAMA.family, "int8"] = engine


def mixed_baseline(engine):
    """One mixed-replica drain per engine, memoized: three parity tests
    compare against the identical request stream, so run it once."""
    key = id(engine)
    if key not in _BASELINES:
        _BASELINES[key] = make_sched(engine).run(mixed_requests())
    return _BASELINES[key]


_BASELINES: dict = {}


@pytest.mark.parametrize(
    "cfg",
    [
        TINY_LLAMA,
        # neox rides the slow battery: same gather/scatter and key path, but
        # its compile set doesn't fit the tier-1 wall-clock budget
        pytest.param(TINY_NEOX, marks=pytest.mark.slow),
    ],
    ids=lambda c: c.family,
)
def test_disagg_drain_token_identical(cfg):
    """The tentpole oracle: prefill-pool + decode-pool greedy/sampled drain
    == one mixed replica, token for token, reason for reason — and the
    handoff really happened (pages migrated over the wire, not failed open).
    """
    engine = make_engine(cfg)
    baseline = mixed_baseline(engine)
    completions, donor, recv = drain_disagg_pair(engine, mixed_requests())
    assert set(completions) == set(baseline)
    for uid, base in baseline.items():
        got = completions[uid]
        assert got.tokens == base.tokens, f"uid {uid} diverged"
        assert got.finish_reason == base.finish_reason
    assert recv._migrated_inserts == 2  # both long prompts adopted remotely
    assert donor._pages_migrated > 0
    assert donor._migration_bytes > 0
    assert donor._migration_failures == 0
    # all donor pages freed after commit; receiver retired its slots clean
    if donor.prefix_cache is not None:
        donor.prefix_cache.clear()
        recv.prefix_cache.clear()
    assert donor.allocator.used_pages == 0
    assert recv.allocator.used_pages == 0


def test_int8_pool_migrates_under_a_third_of_the_bf16_bytes():
    """Quantized page payloads are the point of migrating an int8 pool: the
    same long prompts through the same wire cost at most 0.3x the bytes of
    the unquantized pool, per-page scales and framing included.
    ``kv_dtype="bf16"`` stores pages at the engine's compute dtype, which
    is f32 in this tiny engine: the bound is a quarter plus overhead."""
    donors = {}
    for kv_dtype in ("int8", "bf16"):
        engine = make_engine(TINY_LLAMA, kv_dtype=kv_dtype)
        completions, donor, recv = drain_disagg_pair(engine, mixed_requests())
        assert len(completions) == 4
        assert recv._migrated_inserts == 2
        assert donor._migration_failures == 0
        donors[kv_dtype] = donor
    assert donors["int8"]._pages_migrated == donors["bf16"]._pages_migrated > 0
    assert 0 < donors["int8"]._migration_bytes <= 0.3 * donors["bf16"]._migration_bytes


def test_disagg_sink_rejection_fails_open_token_identical():
    """A handoff the sink refuses (no peers, closed loop, cancelled ticket)
    must leave the donor decoding locally with the SAME tokens — the client
    stream never notices, the failure is a counter."""
    engine = make_engine(TINY_LLAMA)
    baseline = mixed_baseline(engine)
    completions, donor, recv = drain_disagg_pair(
        engine, mixed_requests(), sink_override=lambda record, entries: False
    )
    assert {u: c.tokens for u, c in completions.items()} == {
        u: c.tokens for u, c in baseline.items()
    }
    assert donor._migration_failures == 2
    assert recv._migrated_inserts == 0


def test_disagg_corrupt_frame_fails_open_token_identical():
    """A frame torn in flight decodes to ValueError on the receiver; the
    donor fails open and the drain stays token-identical, zero drops."""
    engine = make_engine(TINY_LLAMA)
    baseline = mixed_baseline(engine)
    completions, donor, recv = drain_disagg_pair(
        engine, mixed_requests(), wire_hook=lambda blob: blob[:-9]
    )
    assert {u: c.tokens for u, c in completions.items()} == {
        u: c.tokens for u, c in baseline.items()
    }
    assert recv._migrated_inserts == 0
    assert donor._migration_failures == 2  # typed fail-open, never a drop
    assert len(completions) == 4


def test_submit_migrated_rejects_inconsistent_runs():
    engine = make_engine(TINY_LLAMA)
    donor = make_sched(engine, role="prefill")
    recv = make_sched(engine, role="decode")
    grabbed = {}
    donor.migration_sink = lambda record, entries: grabbed.update(
        record=dict(record), entries=entries
    ) or True
    req = mixed_requests()[0]
    donor.submit(req)
    for _ in range(20):
        if grabbed:
            break
        donor.step()
    assert grabbed, "donor never exported the run"
    record, entries = grabbed["record"], grabbed["entries"]

    bad = dict(record, position=record["position"] + 1)
    with pytest.raises(ValueError, match="inconsistent"):
        recv.submit_migrated(bad, entries)
    bad = dict(record, n_pages=record["n_pages"] + 1)
    with pytest.raises(ValueError, match="inconsistent"):
        recv.submit_migrated(bad, entries)
    # malformed entries (wrong leaf set) must reject before touching the pool
    with pytest.raises(ValueError):
        recv.submit_migrated(record, entries[:1])
    assert recv.allocator.used_pages == 0  # every rejection rolled back

    recv.submit_migrated(record, entries)
    with pytest.raises(ValueError, match="already in flight"):
        recv.submit_migrated(record, entries)  # dup uid
    donor.migration_commit(record["uid"], 0)
    recv.cancel(record["uid"])
    recv.run([])

