"""Speculative decoding on the paged engine: draft K via prompt lookup,
verify in one ``(batch, K+1)`` forward, commit the longest accepted prefix.

The contract under test is the parity oracle: a greedy drain through
``spec="ngram"`` must be **token-identical** to the non-speculative paged
drain for the same request stream — acceptance is argmax match, so every
committed token is exactly what step-by-step decode would have produced.
Sampled rows are not token-pinned (the residual/bonus draws consume a
different fold of the same ``(uid, token_index)`` key) but their committed
marginal must equal the filtered target distribution ``sample()`` draws
from, which ``test_spec_verify_draws_sampled_marginal`` pins by Monte Carlo.
Page-accounting invariants under speculation live in tests/test_paging.py.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relora_tpu.config.model import ModelConfig
from relora_tpu.models.params_util import init_params
from relora_tpu.serve.engine import InferenceEngine, build_decode_model
from relora_tpu.serve.sampling import sample_rows, spec_verify_draws, top_k_mask, top_p_mask
from relora_tpu.serve.scheduler import PagedContinuousBatchingScheduler, Request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = [pytest.mark.serve, pytest.mark.spec]

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


def make_paged_pair(cfg, *, cache_size=32, spec_k=4, page_size=8, chunk_size=8):
    """Two paged engines over the SAME params: plain, and spec_k-enabled."""
    model = build_decode_model(cfg, cache_size=cache_size)
    base = type(model)(cfg, lora=None, dtype=jnp.float32, scan_layers=True)
    params = init_params(base, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    kw = dict(
        cache_size=cache_size,
        page_size=page_size,
        num_pages=3 * (cache_size // page_size) + 1,
        chunk_size=chunk_size,
    )
    plain = InferenceEngine(cfg, params, **kw)
    spec = InferenceEngine(cfg, params, spec_k=spec_k, **kw)
    return plain, spec


def spec_requests(vocab):
    """Greedy rows with self-repeating prompts (the prompt-lookup regime),
    one greedy random prompt (drafting may never fire: fallback shape), and
    a sampled row — staggered through max_batch=2 slots."""
    rng = np.random.default_rng(7)
    return [
        Request(uid=1, prompt=[3, 5, 7] * 4, max_new_tokens=8),
        Request(uid=2, prompt=rng.integers(1, vocab, 13).tolist(), max_new_tokens=6),
        Request(uid=3, prompt=[2, 4] * 6, max_new_tokens=7, temperature=0.8, top_p=0.9),
        Request(uid=4, prompt=rng.integers(1, vocab, 5).tolist(), max_new_tokens=5),
    ]


def drain(engine, reqs, **kwargs):
    sched = PagedContinuousBatchingScheduler(
        engine, max_batch=2, eos_id=9, key=jax.random.PRNGKey(42), **kwargs
    )
    completions = sched.run(reqs)
    return sched, {uid: c.tokens for uid, c in completions.items()}


# -- the drafter --------------------------------------------------------------


def test_ngram_draft_prompt_lookup():
    _, eng = make_paged_pair(TINY_LLAMA)
    sched = PagedContinuousBatchingScheduler(eng, max_batch=2, spec="ngram")
    # longest suffix n-gram that recurs wins; proposal is what followed it
    assert sched._ngram_draft([1, 2, 3, 4, 2, 3], 3) == [4, 2, 3]
    # most recent earlier occurrence wins over an older one
    assert sched._ngram_draft([7, 9, 1, 7, 9, 2, 7, 9], 2) == [2, 7]
    # proposal is capped at k and at the end of the context
    assert sched._ngram_draft([1, 2, 3, 4, 2, 3], 1) == [4]
    assert sched._ngram_draft([5, 6, 5, 6], 8) == [5, 6]
    # no recurrence, no draft — and degenerate inputs stay empty
    assert sched._ngram_draft([1, 2, 3, 4, 5], 4) == []
    assert sched._ngram_draft([1, 2, 3], 0) == []
    assert sched._ngram_draft([1], 4) == []


# -- the verify sampler -------------------------------------------------------


def test_spec_verify_draws_greedy_exact():
    """temperature<=0 rows: accept iff the draft equals the row argmax, and
    the corrective token is the argmax — no randomness anywhere."""
    key = jax.random.PRNGKey(5)
    logits = jax.random.normal(key, (2, 3, 16), jnp.float32)
    am = np.asarray(jnp.argmax(logits, axis=-1))
    draft = np.array([[am[0, 0], 11], [3, am[1, 1]]], np.int32)  # mixed hits
    accept, alt = spec_verify_draws(
        logits,
        jnp.asarray(draft),
        jax.random.PRNGKey(42),
        jnp.array([1, 2], jnp.int32),
        jnp.array([0, 4], jnp.int32),
        jnp.array([2, 2], jnp.int32),
        temperature=jnp.zeros(2),
    )
    np.testing.assert_array_equal(np.asarray(accept), am[:, :2] == draft)
    np.testing.assert_array_equal(np.asarray(alt), am)


def test_spec_verify_draws_sampled_marginal():
    """Rejection sampling with a deterministic proposal: committed token =
    draft if u < p(draft) else residual sample — the marginal over many
    independent (uid, index) streams must equal the filtered target
    distribution, and never land outside its support."""
    V, N = 12, 20000
    row = jax.random.normal(jax.random.PRNGKey(9), (V,), jnp.float32) * 2.0
    temp, top_k, top_p = 0.7, 5, 0.9
    # the target distribution exactly as sample() builds it
    filtered = top_p_mask(top_k_mask(row[None, :], top_k), jnp.asarray([top_p]))
    target = np.asarray(jax.nn.softmax(filtered / temp, axis=-1))[0]
    d = int(np.argsort(target)[-2])  # a mid-probability in-support draft

    logits = jnp.broadcast_to(row, (N, 2, V))
    accept, alt = spec_verify_draws(
        logits,
        jnp.full((N, 1), d, jnp.int32),
        jax.random.PRNGKey(0),
        jnp.arange(N, dtype=jnp.int32),
        jnp.zeros(N, jnp.int32),
        jnp.ones(N, jnp.int32),
        temperature=jnp.full(N, temp),
        top_k=top_k,
        top_p=top_p,
    )
    committed = np.where(np.asarray(accept)[:, 0], d, np.asarray(alt)[:, 0])
    emp = np.bincount(committed, minlength=V) / N
    # accept rate is p(draft) itself (deterministic proposal), marginal is
    # the target; 20k draws put the per-token noise well under 0.02
    assert np.asarray(accept)[:, 0].mean() == pytest.approx(target[d], abs=0.02)
    np.testing.assert_allclose(emp, target, atol=0.02)
    assert emp[target < 1e-12].sum() == 0.0  # filtered-out tokens never appear


@pytest.mark.parametrize("neighbour_top_p", [1.0, 0.5], ids=["alone", "beside_a_nucleus"])
@pytest.mark.parametrize("top_p", [1.0, 0.6])
def test_spec_verify_and_sample_share_one_filtered_support(top_p, neighbour_top_p):
    """``spec_verify_draws`` and ``sample`` filter through one helper
    (``sampling.filter_logits``): a ``top_p = 1`` row's target is the
    unfiltered distribution in both, with a nucleus row in the batch or
    without, and a ``top_p < 1`` row's is its nucleus in both.  The logits'
    last 24 tokens lie 30 below the rest, a tail that ``top_p_mask(x, 1.0)``
    drops in f32 and that temperature 90 draws all the time."""
    V, tail, N, temp = 64, 24, 512, 90.0
    row = np.array(jax.random.normal(jax.random.PRNGKey(4), (V,), jnp.float32))
    row[V - tail :] -= 30.0
    masked_at_one = np.asarray(top_p_mask(jnp.asarray(row)[None], jnp.ones(1)))[0]
    assert (masked_at_one[V - tail :] < -1e30).all()  # the premise: not the identity
    logits = jnp.broadcast_to(jnp.asarray(row), (N, 2, V))
    top_ps = np.tile(np.array([top_p, neighbour_top_p], np.float32), N // 2)
    mine = np.arange(N) % 2 == 0  # the rows under test; the odd ones are the neighbours
    uids, start = jnp.arange(N, dtype=jnp.int32), jnp.full(N, 7, jnp.int32)
    base, temps = jax.random.PRNGKey(1), jnp.full(N, temp)
    d = V - 1  # a drafted token in the tail

    def verify(k_eff):
        accept, alt = spec_verify_draws(
            logits, jnp.full((N, 1), d, jnp.int32), base, uids, start,
            jnp.full(N, k_eff, jnp.int32), temperature=temps, top_p=jnp.asarray(top_ps),
        )
        return np.asarray(accept)[:, 0], np.asarray(alt)

    # no draft: the bonus draw at slot 0 is sample's own draw, token for token
    _, alt = verify(0)
    plain = np.asarray(
        sample_rows(logits[:, 0, :], base, uids, start, temperature=temps, top_p=jnp.asarray(top_ps))
    )
    np.testing.assert_array_equal(alt[:, 0], plain)
    in_tail = plain[mine] >= V - tail
    # a drafted tail token: accepted with its probability under the same target,
    # and the residual draw stays inside the same support
    accept, alt = verify(1)
    if top_p < 1.0:
        assert not in_tail.any() and not accept[mine].any()
        assert (alt[mine] < V - tail).all()
    else:
        assert 0.15 < in_tail.mean() < 0.45  # 0.30: 24 of 64 tokens at exp(-30/90) of the others' weight
        assert 1 <= accept[mine].sum() <= 12  # p(d) is about 1/64 over 256 rows
        assert 0.15 < (alt[mine, 0] >= V - tail).mean() < 0.45


# -- the parity oracle --------------------------------------------------------


@pytest.mark.parametrize("cfg", [TINY_LLAMA, TINY_NEOX], ids=["llama", "neox"])
def test_greedy_spec_drain_token_identical(cfg):
    """Acceptance: greedy requests through the speculative scheduler emit
    exactly the tokens the non-speculative paged drain emits — staggered
    admissions, drafting rows sharing batches with fallback rows."""
    plain, spec_eng = make_paged_pair(cfg)
    reqs = spec_requests(cfg.vocab_size)
    _, want = drain(plain, reqs)
    sched, got = drain(spec_eng, reqs, spec="ngram")
    for uid in (1, 2, 4):  # the greedy rows are token-pinned
        assert got[uid] == want[uid], f"uid {uid}"
    # the sampled row is distribution-pinned, not token-pinned: just sane
    assert got[3] and all(0 <= t < cfg.vocab_size for t in got[3])
    stats = sched.spec_stats()
    assert stats["mode"] == "ngram" and stats["k"] == 4
    assert stats["drafted"] > 0  # the repetitive prompts did draft
    assert 0 <= stats["accepted"] <= stats["drafted"]
    assert stats["accept_rate"] == pytest.approx(
        stats["accepted"] / max(stats["drafted"], 1), abs=1e-3
    )
    # every request page released once the prefix cache lets go
    if sched.prefix_cache is not None:
        sched.prefix_cache.clear()
    assert sched.allocator.used_pages == 0


@pytest.mark.slow
def test_spec_multi_token_commits_on_repetitive_generation():
    """A prompt the model answers with a loop: speculation must actually
    accept (multi-token commits), and the output still matches non-spec."""
    plain, spec_eng = make_paged_pair(TINY_LLAMA, cache_size=64)
    reqs = [
        Request(uid=1, prompt=[3, 5, 7] * 5, max_new_tokens=40),
        Request(uid=2, prompt=[2, 4] * 7, max_new_tokens=40),
    ]
    _, want = drain(plain, reqs)
    sched, got = drain(spec_eng, reqs, spec="ngram")
    assert got == want
    stats = sched.spec_stats()
    assert stats["accepted"] > 0, stats  # real multi-token commits happened
    # accepted drafts shrink the step count below one-per-token
    total = sum(len(t) for t in want.values())
    assert sched._step_count < total / 2 + len(reqs) * 4


@pytest.mark.slow
def test_request_spec_false_opts_out():
    """Per-request opt-out: spec=False rows never draft, so the round takes
    the plain decode shape and output matches non-spec exactly (sampled
    included — same keys, same sampler)."""
    plain, spec_eng = make_paged_pair(TINY_LLAMA)
    reqs = [
        Request(uid=1, prompt=[3, 5, 7] * 4, max_new_tokens=6, spec=False),
        Request(uid=2, prompt=[2, 4] * 5, max_new_tokens=6, temperature=0.9, spec=False),
    ]
    _, want = drain(plain, reqs)
    sched, got = drain(spec_eng, reqs, spec="ngram")
    assert got == want
    assert sched.spec_stats()["drafted"] == 0


# -- compile discipline -------------------------------------------------------


def test_spec_warmup_shapes_and_no_retrace():
    """Warmup compiles all three shapes (chunk, decode, verify); a drain
    mixing drafting rounds with fallback rounds then retraces nothing."""
    _, spec_eng = make_paged_pair(TINY_LLAMA)
    report = spec_eng.warmup(2)
    assert report["shapes"]["decode_paged"] == [2, 1]
    assert report["shapes"]["verify_paged"] == [2, 5]
    assert report["spec_k"] == 4
    sched = PagedContinuousBatchingScheduler(
        spec_eng, max_batch=2, eos_id=9, key=jax.random.PRNGKey(42), spec="ngram"
    )
    # one prompt-lookup row (drafts -> verify shape) + one random row
    # (never drafts -> fallback decode shape) is the full shape mix
    sched.run(spec_requests(TINY_LLAMA.vocab_size)[:2])
    assert spec_eng.compile_watcher.steady_state_retraces == 0


@pytest.mark.slow
def test_spec_memory_plans_include_verify():
    _, spec_eng = make_paged_pair(TINY_LLAMA)
    plans = spec_eng.memory_plans(2)
    assert "verify_paged" in plans


# -- configuration guards -----------------------------------------------------


def test_spec_configuration_guards():
    plain, spec_eng = make_paged_pair(TINY_LLAMA)
    with pytest.raises(ValueError, match="spec_k >= 1"):
        PagedContinuousBatchingScheduler(plain, max_batch=2, spec="ngram")
    with pytest.raises(ValueError, match="spec must be"):
        PagedContinuousBatchingScheduler(spec_eng, max_batch=2, spec="lookahead")
    with pytest.raises(ValueError, match="requires the paged engine"):
        InferenceEngine(TINY_LLAMA, spec_eng.params, cache_size=32, spec_k=4)


@pytest.mark.slow
def test_cli_spec_requires_paged():
    """serve.py refuses --spec without --paged (the verify window writes
    through block tables), and --spec with a degenerate --spec-k."""
    sys.path.insert(0, ROOT)
    import serve

    common = [
        "--model_config", "llama_9m",
        "--random-init",
        "--cache-size", "64",
        "--prompt", "1 2 3",
        "--max-new-tokens", "2",
    ]
    with pytest.raises(SystemExit, match="requires --paged"):
        serve.main(common + ["--spec", "ngram"])
    with pytest.raises(SystemExit, match="spec-k"):
        serve.main(common + ["--paged", "--spec", "ngram", "--spec-k", "0"])
