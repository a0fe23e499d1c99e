"""Sampling policy tests (ISSUE satellite): greedy == argmax, temperature→0
converges to greedy, top-p never leaves the nucleus, top-k never leaves the
top k, and fixed-seed determinism across jit/no-jit."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relora_tpu.serve.sampling import (
    SamplingParams,
    request_key,
    sample,
    sample_rows,
    top_k_mask,
    top_p_mask,
)

pytestmark = pytest.mark.serve


def rand_logits(key, B=4, V=50, scale=3.0):
    return jax.random.normal(key, (B, V)) * scale


def test_greedy_equals_argmax():
    logits = rand_logits(jax.random.PRNGKey(0))
    out = sample(logits, jax.random.PRNGKey(1), temperature=0.0)
    np.testing.assert_array_equal(np.asarray(out), np.argmax(np.asarray(logits), axis=-1))


def test_temperature_to_zero_converges_to_greedy():
    logits = rand_logits(jax.random.PRNGKey(2))
    greedy = np.argmax(np.asarray(logits), axis=-1)
    for i, temp in enumerate([0.05, 0.01, 0.001]):
        draws = np.stack(
            [
                np.asarray(sample(logits, jax.random.PRNGKey(100 + i * 10 + j), temperature=temp))
                for j in range(8)
            ]
        )
        frac = (draws == greedy[None, :]).mean()
        if temp <= 0.001:
            assert frac == 1.0, f"temperature {temp} should be indistinguishable from greedy"
    # and exactly-zero is exactly greedy even per-row in a mixed batch
    temps = jnp.array([0.0, 1.0, 0.0, 1.0])
    out = np.asarray(sample(logits, jax.random.PRNGKey(3), temperature=temps))
    np.testing.assert_array_equal(out[[0, 2]], greedy[[0, 2]])


def test_top_p_never_samples_outside_nucleus():
    logits = rand_logits(jax.random.PRNGKey(4), B=8, V=32)
    top_p = 0.7
    probs = np.asarray(jax.nn.softmax(logits, axis=-1))
    # nucleus per row: smallest descending-prob prefix with mass >= top_p
    nucleus = []
    for row in probs:
        order = np.argsort(row)[::-1]
        cum = np.cumsum(row[order])
        k = int(np.searchsorted(cum, top_p)) + 1
        nucleus.append(set(order[:k].tolist()))
    for seed in range(50):
        out = np.asarray(
            sample(logits, jax.random.PRNGKey(1000 + seed), temperature=1.0, top_p=top_p)
        )
        for b, tok in enumerate(out):
            assert int(tok) in nucleus[b], f"row {b} sampled {tok} outside its nucleus"


def test_top_p_mask_keeps_argmax():
    """Even a tiny top_p must keep at least the most likely token."""
    logits = rand_logits(jax.random.PRNGKey(5))
    masked = np.asarray(top_p_mask(logits, jnp.asarray(0.01)))
    finite = np.isfinite(np.where(masked < -1e30, -np.inf, masked))
    assert (finite.sum(axis=-1) >= 1).all()
    np.testing.assert_array_equal(
        np.argmax(masked, axis=-1), np.argmax(np.asarray(logits), axis=-1)
    )


def test_top_k_never_samples_outside_top_k():
    logits = rand_logits(jax.random.PRNGKey(6), B=6, V=40)
    k = 5
    top = np.argsort(np.asarray(logits), axis=-1)[:, -k:]
    for seed in range(30):
        out = np.asarray(
            sample(logits, jax.random.PRNGKey(2000 + seed), temperature=1.5, top_k=k)
        )
        for b, tok in enumerate(out):
            assert int(tok) in top[b]


def test_fixed_seed_determinism_across_jit():
    logits = rand_logits(jax.random.PRNGKey(7))
    key = jax.random.PRNGKey(42)
    kwargs = dict(temperature=0.8, top_k=10, top_p=0.9)
    eager = np.asarray(sample(logits, key, **kwargs))
    jitted = jax.jit(functools.partial(sample, **kwargs))
    np.testing.assert_array_equal(np.asarray(jitted(logits, key)), eager)
    np.testing.assert_array_equal(np.asarray(jitted(logits, key)), eager)  # stable


def test_per_row_keys():
    """A (B, key) stack draws each row independently: row i's draw equals a
    single-row call with that key."""
    logits = rand_logits(jax.random.PRNGKey(8), B=3)
    keys = jnp.stack([jax.random.fold_in(jax.random.PRNGKey(9), i) for i in range(3)])
    batched = np.asarray(sample(logits, keys, temperature=1.0))
    for i in range(3):
        solo = np.asarray(sample(logits[i : i + 1], keys[i], temperature=1.0))
        assert batched[i] == solo[0]


def test_sampling_params_validation():
    with pytest.raises(ValueError):
        SamplingParams(top_k=-1)
    with pytest.raises(ValueError):
        SamplingParams(top_p=0.0)
    with pytest.raises(ValueError):
        SamplingParams(top_p=1.5)
    assert SamplingParams().temperature == 0.0


def test_top_k_mask_disabled_passthrough():
    logits = rand_logits(jax.random.PRNGKey(10))
    np.testing.assert_array_equal(np.asarray(top_k_mask(logits, 0)), np.asarray(logits))
    np.testing.assert_array_equal(
        np.asarray(top_k_mask(logits, logits.shape[-1])), np.asarray(logits)
    )


# -- sample_rows: the schedulers' sampler, keys built inside the program -------

#: the top of the highest uid space a replica mints from (serve/server.py:
#: ``uid_base = (crc32(id) % 1021 + 1) << 21``, counting up), 2**31 - 1, and the
#: last value ``fold_in`` takes at all
UID_SERVER_MAX = (1022 << 21) - 1
UID_EDGE = [0, 1, 2**31 - 1, UID_SERVER_MAX, 2**32 - 1]


def host_keys(base, uids, idxs):
    """The keys the schedulers built before: two eager fold_ins a row."""
    return jnp.stack(
        [jax.random.fold_in(jax.random.fold_in(base, int(u)), int(i)) for u, i in zip(uids, idxs)]
    )


@pytest.mark.parametrize("uid", UID_EDGE)
@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
def test_sample_rows_token_identical_to_host_built_keys(uid, jitted):
    """Temperature 1.0, mixed top_p, rows of mixed token index: every draw
    equals ``sample`` over host-built ``fold_in(fold_in(key, uid), idx)``."""
    B, V = 6, 97
    base = jax.random.PRNGKey(11)
    logits = rand_logits(jax.random.PRNGKey(uid % 1000), B=B, V=V)
    uids = np.array([uid, uid, 5, uid, 7, uid], np.uint32)
    idxs = np.array([0, 1, 0, 300, 2047, 17], np.int32)
    top_ps = np.array([1.0, 0.9, 0.5, 0.95, 1.0, 0.7], np.float32)
    temps = np.ones(B, np.float32)
    fn = jax.jit(sample_rows, static_argnames=("top_k",)) if jitted else sample_rows
    got = fn(logits, base, uids, idxs, temperature=temps, top_k=0, top_p=top_ps)
    want = sample(logits, host_keys(base, uids, idxs), temperature=temps, top_p=top_ps)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the keys themselves, bit for bit
    keys = jax.vmap(request_key, in_axes=(None, 0, 0))(base, uids, idxs)
    np.testing.assert_array_equal(np.asarray(keys), np.asarray(host_keys(base, uids, idxs)))


@pytest.mark.parametrize("dtype", [np.uint32, np.int32, np.int64], ids=lambda d: np.dtype(d).name)
def test_request_key_takes_any_integer_dtype(dtype):
    """``spec_verify_draws`` hands int32 uids, the schedulers uint32: the same
    low 32 bits give the same key."""
    base = jax.random.PRNGKey(3)
    uids = np.array([0, 9, 2**31 - 1], dtype)
    idxs = np.array([4, 0, 1], np.int32)
    keys = jax.vmap(request_key, in_axes=(None, 0, 0))(base, jnp.asarray(uids), idxs)
    np.testing.assert_array_equal(np.asarray(keys), np.asarray(host_keys(base, uids, idxs)))


def test_sample_rows_mixed_greedy_and_sampled_batch():
    """Greedy rows take the argmax whatever their key; sampled rows draw as
    ``sample`` does; a row the scheduler left at zeros is a greedy row."""
    B, V = 8, 64
    base = jax.random.PRNGKey(5)
    logits = rand_logits(jax.random.PRNGKey(6), B=B, V=V)
    uids = np.array([3, 0, 2**31 - 1, 0, 8, 9, 0, UID_SERVER_MAX], np.uint32)
    idxs = np.array([1, 0, 5, 0, 2, 2, 0, 40], np.int32)
    temps = np.array([1.0, 0.0, 0.7, 0.0, 0.0, 1.3, 0.0, 1.0], np.float32)
    top_ps = np.array([0.9, 1.0, 1.0, 1.0, 0.5, 0.8, 1.0, 1.0], np.float32)
    got = np.asarray(sample_rows(logits, base, uids, idxs, temperature=temps, top_p=top_ps))
    want = np.asarray(sample(logits, host_keys(base, uids, idxs), temperature=temps, top_p=top_ps))
    np.testing.assert_array_equal(got, want)
    greedy = np.argmax(np.asarray(logits), axis=-1)
    np.testing.assert_array_equal(got[temps == 0.0], greedy[temps == 0.0])


@pytest.mark.parametrize("temperature,top_p", [(1.0, 1.0), (0.8, 0.6), (0.0, 1.0)])
def test_sample_rows_one_row_equals_the_scalar_key_call(temperature, top_p):
    """A first token used to be ``sample`` with ONE key and Python scalars;
    it is now a one-row ``sample_rows`` at token index 0.  Same token."""
    base = jax.random.PRNGKey(21)
    for uid in (0, 4, UID_SERVER_MAX):
        logits = rand_logits(jax.random.PRNGKey(uid % 7), B=1, V=211)
        key = jax.random.fold_in(jax.random.fold_in(base, uid), 0)
        want = sample(logits, key, temperature=temperature, top_p=top_p)
        got = sample_rows(
            logits, base, np.array([uid], np.uint32), np.zeros(1, np.int32),
            temperature=np.array([temperature], np.float32), top_p=np.array([top_p], np.float32),
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
