"""Sampling policy tests (ISSUE satellite): greedy == argmax, temperature→0
converges to greedy, top-p never leaves the nucleus, top-k never leaves the
top k, and fixed-seed determinism across jit/no-jit."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from relora_tpu.serve.sampling import (
    PATHS,
    SamplingParams,
    batch_path,
    filter_logits,
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


# -- the sampler does only the work some row of its batch needs ---------------
# -- (PERF.md §6, PR 31): one lax.switch on batch_path inside the program ------

#: (temperatures, top_ps) of a batch, by the path it takes
BATCHES = {
    "greedy": ([0.0, 0.0, 0.0, 0.0], [1.0, 1.0, 0.6, 1.0]),  # a greedy row's top_p asks for nothing
    "categorical": ([0.0, 0.7, 1.0, 1.3], [0.5, 1.0, 1.0, 1.0]),
    "nucleus": ([0.0, 0.7, 1.0, 1.3], [1.0, 1.0, 0.8, 1.0]),
    "nucleus_everywhere": ([0.9, 0.7, 1.0, 1.3], [0.3, 0.9, 0.8, 0.99]),
}


def tail_logits(B=4, V=64, tail=24, seed=12):
    """Logits whose last ``tail`` tokens lie 30 below the rest: in f32 the
    cumulative sum has rounded to 1.0 before them, so ``top_p_mask(x, 1.0)``
    drops them, while at a high temperature the unfiltered distribution draws
    them all the time."""
    logits = np.array(rand_logits(jax.random.PRNGKey(seed), B=B, V=V, scale=1.0))
    logits[:, V - tail :] -= 30.0
    return jnp.asarray(logits)


def draw_rows(logits, temps, top_ps, *, uid0=100, index=3, top_k=0, fn=sample_rows):
    B = logits.shape[0]
    return np.asarray(
        fn(
            logits, jax.random.PRNGKey(13), np.arange(uid0, uid0 + B, dtype=np.uint32),
            np.full(B, index, np.int32), temperature=np.asarray(temps, np.float32),
            top_k=top_k, top_p=np.asarray(top_ps, np.float32),
        )
    )


@pytest.mark.parametrize("name", list(BATCHES))
@pytest.mark.parametrize("kind", ["numpy", "jnp", "traced"])
def test_batch_path_is_one_predicate_for_host_and_device(name, kind):
    """The scheduler counts paths from numpy, the program branches on traced
    jnp: the same function, the same answer."""
    temps, top_ps = (np.asarray(v, np.float32) for v in BATCHES[name])
    if kind == "numpy":
        got = batch_path(temps, top_ps)
        assert isinstance(got, np.integer)  # no device touched on the host's side
    elif kind == "jnp":
        got = batch_path(jnp.asarray(temps), jnp.asarray(top_ps))
    else:
        got = jax.jit(batch_path)(temps, top_ps)
    assert PATHS[int(got)] == name.split("_")[0]


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("top_k", [0, 7])
def test_all_greedy_batch_is_the_argmax_and_the_nucleus_paths_answer(jitted, top_k):
    """Greedy rows are the same argmax in all three branches: alone, beside a
    sampling row, and beside a row that asks for a nucleus."""
    logits = rand_logits(jax.random.PRNGKey(14), B=4, V=211)
    fn = jax.jit(sample_rows, static_argnames=("top_k",)) if jitted else sample_rows
    want = np.argmax(np.asarray(logits), axis=-1)
    np.testing.assert_array_equal(draw_rows(logits, *BATCHES["greedy"], top_k=top_k, fn=fn), want)
    for temps, top_ps in ([0.0, 0.0, 0.0, 1.0], [1.0] * 4), ([0.0, 0.0, 0.0, 1.0], [1.0, 1.0, 1.0, 0.5]):
        got = draw_rows(logits, temps, top_ps, top_k=top_k, fn=fn)
        np.testing.assert_array_equal(got[:3], want[:3])


@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("logits_of", ["random", "f32_tail"])
def test_sampled_top_p_one_batch_is_the_plain_categorical(jitted, logits_of):
    """``temperature > 0, top_p = 1``: row for row
    ``categorical(request_key, logits / T)``, the unfiltered distribution —
    also where ``top_p_mask(x, 1.0)`` would have cut a tail under f32 epsilon."""
    logits = tail_logits() if logits_of == "f32_tail" else rand_logits(jax.random.PRNGKey(15), B=4, V=64)
    temps = np.array([0.7, 1.0, 40.0, 90.0], np.float32)
    fn = jax.jit(sample_rows, static_argnames=("top_k",)) if jitted else sample_rows
    base = jax.random.PRNGKey(13)
    for index in range(6):
        got = draw_rows(logits, temps, [1.0] * 4, index=index, fn=fn)
        for i in range(4):
            key = request_key(base, np.uint32(100 + i), np.int32(index))
            assert got[i] == int(jax.random.categorical(key, logits[i] / temps[i]))


@pytest.mark.parametrize("name", list(BATCHES))
@pytest.mark.parametrize("top_k", [0, 9])
@pytest.mark.parametrize("logits_of", ["random", "f32_tail"])
def test_a_rows_draw_does_not_depend_on_its_batch(name, top_k, logits_of):
    """In a batch of greedy, ``top_p = 1`` and ``top_p < 1`` rows each row
    equals its own one-row call, whichever branch the batch took: a
    ``top_p = 1`` row draws the same token beside a nucleus row as without."""
    logits = tail_logits() if logits_of == "f32_tail" else rand_logits(jax.random.PRNGKey(16), B=4, V=64)
    temps, top_ps = BATCHES[name]
    temps = [t * 50 for t in temps] if logits_of == "f32_tail" else temps  # reach into the tail
    for index in (0, 1, 2):
        got = draw_rows(logits, temps, top_ps, index=index, top_k=top_k)
        for i in range(4):
            solo = draw_rows(
                logits[i : i + 1], temps[i : i + 1], top_ps[i : i + 1],
                uid0=100 + i, index=index, top_k=top_k,
            )
            assert got[i] == solo[0], (name, index, i)


def test_top_p_one_row_draws_from_the_tail_beside_a_nucleus_row():
    """What the per-row guard is for: at temperature 90 a ``top_p = 1`` row
    lands in the f32 tail about as often as its 24 of 64 tokens say, with a
    nucleus neighbour or without, and the neighbour never does."""
    logits, V, tail = tail_logits(), 64, 24
    for top_ps in ([1.0, 1.0, 1.0, 1.0], [1.0, 0.9, 1.0, 0.5]):
        draws = np.stack([draw_rows(logits, [90.0] * 4, top_ps, index=i) for i in range(40)])
        in_tail = draws >= V - tail
        for row, top_p in enumerate(top_ps):
            if top_p < 1.0:
                assert not in_tail[:, row].any()
            else:
                assert 6 <= in_tail[:, row].sum() <= 26  # 15 expected of 40
    # the mask alone, at top_p 1.0, is not the identity here (it drops the tail
    # of the rows whose cumulative sum reached 1.0): the guard is not idle
    assert (np.asarray(top_p_mask(logits, jnp.ones(4)))[:, V - tail :] < -1e30).any()
    assert (np.asarray(filter_logits(logits, 0, jnp.ones(4))) == np.asarray(logits)).all()


def _eqns(jaxpr, *, into_cond):
    """Every equation of ``jaxpr`` and of the jaxprs nested in its equations'
    parameters; a ``cond``'s branches only where ``into_cond``."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "cond" and not into_cond:
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, into_cond=into_cond)


@pytest.mark.parametrize("top_k", [0, 5])
def test_sample_rows_sorts_only_inside_the_nucleus_branch(top_k):
    """One program, one conditional: no ``sort`` outside it, none in its
    ``greedy`` and ``categorical`` branches, and no random bits in ``greedy``."""
    B, V = 4, 97
    jaxpr = jax.make_jaxpr(functools.partial(sample_rows, top_k=top_k))(
        jnp.zeros((B, V)), jax.random.PRNGKey(0), np.zeros(B, np.uint32), np.zeros(B, np.int32),
        temperature=np.zeros(B, np.float32), top_p=np.ones(B, np.float32),
    ).jaxpr
    outside = [e.primitive.name for e in _eqns(jaxpr, into_cond=False)]
    assert "sort" not in outside and outside.count("cond") == 1
    [switch] = [e for e in _eqns(jaxpr, into_cond=False) if e.primitive.name == "cond"]
    per_branch = [
        {e.primitive.name for e in _eqns(branch.jaxpr, into_cond=True)}
        for branch in switch.params["branches"]
    ]
    assert len(per_branch) == len(PATHS)
    greedy, categorical, nucleus = per_branch
    assert "sort" in nucleus and "sort" not in greedy | categorical
    assert not {"random_bits", "threefry2x32", "cumsum", "exp"} & greedy
    assert ("top_k" in categorical) == (top_k > 0)
