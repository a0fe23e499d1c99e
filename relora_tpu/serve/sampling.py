"""Jittable token sampling for the decode loop.

One function, ``sample``, covers the standard policies — greedy, temperature,
top-k, top-p (nucleus) — composed in the usual order: top-k filter, then
nucleus filter, then temperature-scaled categorical.  Everything traces under
``jax.jit``:

- ``temperature`` and ``top_p`` may be traced scalars or per-row ``(B,)``
  arrays (the continuous-batching scheduler mixes requests with different
  sampling settings in one decode step).  ``temperature <= 0`` selects greedy
  for that row; ``top_p = 1`` means the unfiltered distribution for that row.
- The program does only the work some row of the batch needs.  ``batch_path``
  classifies the batch from the two vectors (``PATHS``: ``greedy`` when no
  row samples, ``categorical`` when some row samples and no sampling row asks
  for a nucleus, ``nucleus`` otherwise) and ``sample`` branches on it with one
  ``lax.switch`` *inside* the compiled program: an all-greedy batch is an
  argmax (no mask, no softmax, no random bits), and only the ``nucleus``
  branch holds ``top_p_mask``'s two sorts.  The choice is a device-side
  conditional, never a static argument, so one program per shape serves every
  mix and a sampled request after greedy ones compiles nothing.  A row's
  token does not depend on the branch its batch took: greedy rows are the
  same argmax in all three, and the nucleus mask is applied per row, only
  where that row's ``top_p < 1`` (``filter_logits``) — in f32
  ``top_p_mask(x, 1.0)`` is not the identity (it drops the tail once the
  cumulative sum rounds to 1.0), so without the per-row guard a ``top_p = 1``
  row would draw from another support beside a nucleus row than without one.
- ``top_k`` is a static int (it changes the ``lax.top_k`` shape); 0 disables.
- ``key`` is either one PRNG key shared across the batch, or a stacked
  ``(B, key_size)`` batch of per-row keys.  Per-row keys make a request's
  sample stream independent of which other requests happen to share its
  batch — fold in the request id, not the slot index.

``sample_rows`` is what the schedulers call: ``sample`` over per-row keys it
derives itself, inside the jitted program, from the scheduler's base key and
two integer vectors — each row's request id and the index of the token being
drawn (``request_key``).  The host hands over numpy and dispatches once.

``spec_verify_draws`` is the speculative-decoding verify sampler: one jitted
pass over the verify window's ``(B, S, V)`` logits that produces everything
the scheduler's host-side accept/rollback walk needs — greedy accept bits,
rejection-sampling accept bits (uniform vs the *filtered* target probability
of each drafted token), and per-row alternative tokens (residual sample on
rejection, plain sample for the bonus position).  All PRNG keys derive from
the same ``(uid, token_index)`` scheme the plain decode path uses, folded
with small constants per draw kind, so a request's committed stream stays
independent of batch composition and of how many drafts rode along.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

_NEG_INF = jnp.finfo(jnp.float32).min

#: what a batch asks of the sampler, by ``batch_path``'s index: the branches of
#: ``sample`` and the labels of the schedulers' ``sample_draws_total``
PATHS = ("greedy", "categorical", "nucleus")


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling policy.  ``temperature=0`` is greedy."""

    temperature: float = 0.0
    top_k: int = 0  # 0 disables; static (changes compiled shapes)
    top_p: float = 1.0

    def __post_init__(self):
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")


def top_k_mask(logits: jax.Array, k: int) -> jax.Array:
    """Keep the k largest logits per row, -inf the rest.  ``k`` static."""
    if k <= 0 or k >= logits.shape[-1]:
        return logits
    kth = jax.lax.top_k(logits, k)[0][..., -1:]
    return jnp.where(logits < kth, _NEG_INF, logits)


def top_p_mask(logits: jax.Array, top_p: jax.Array) -> jax.Array:
    """Nucleus filter: keep the smallest set of tokens whose probability mass
    reaches ``top_p``, -inf the rest.  A token stays iff the mass *strictly
    before* it (descending order) is < top_p — so the argmax always survives
    and the kept set's mass is the smallest one >= top_p."""
    order = jnp.argsort(logits, axis=-1)[..., ::-1]  # descending
    sorted_logits = jnp.take_along_axis(logits, order, axis=-1)
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    mass_before = jnp.cumsum(probs, axis=-1) - probs
    keep_sorted = mass_before < jnp.asarray(top_p, jnp.float32)[..., None]
    inverse = jnp.argsort(order, axis=-1)
    keep = jnp.take_along_axis(keep_sorted, inverse, axis=-1)
    return jnp.where(keep, logits, _NEG_INF)


def batch_path(temperature, top_p):
    """Index into ``PATHS`` of the work a batch needs, from its per-row
    ``(B,)`` ``temperature`` and ``top_p``: 0 when no row samples, 1 when some
    row samples and none of the sampling rows has ``top_p < 1``, 2 otherwise.
    numpy in, numpy out; jnp (traced or not) in, jnp out — the host's count of
    the paths and the branch the device takes are this one predicate."""
    sampling = temperature > 0.0
    nucleus = sampling & (top_p < 1.0)
    return sampling.any().astype(np.int32) + nucleus.any().astype(np.int32)


def filter_logits(logits: jax.Array, top_k: int, top_p: jax.Array) -> jax.Array:
    """The target's support, ``(N, V)`` rows under per-row ``(N,)`` ``top_p``:
    top-k, then the nucleus mask on the rows with ``top_p < 1`` alone.  Both
    ``sample`` and ``spec_verify_draws`` draw from this, so they cannot drift."""
    filtered = top_k_mask(logits, top_k)
    return jnp.where((top_p < 1.0)[:, None], top_p_mask(filtered, top_p), filtered)


@functools.lru_cache(maxsize=None)
def _branches(top_k: int):
    """``sample``'s branches in ``PATHS``' order, over ``(logits, key, temp,
    top_p)``.  One tuple per ``top_k`` for the life of the process, so that an
    eager call finds the conditional it traced before."""

    def greedy(logits, key, temp, top_p):
        return jnp.argmax(logits, axis=-1)

    def draw(filtered, logits, key, temp):
        scaled = filtered / jnp.maximum(temp, 1e-6)[:, None]
        if key.ndim > 1:  # per-row keys
            drawn = jax.vmap(jax.random.categorical)(key, scaled)
        else:
            drawn = jax.random.categorical(key, scaled)
        return jnp.where(temp <= 0.0, jnp.argmax(logits, axis=-1), drawn)

    def categorical(logits, key, temp, top_p):
        return draw(top_k_mask(logits, top_k), logits, key, temp)

    def nucleus(logits, key, temp, top_p):
        return draw(filter_logits(logits, top_k, top_p), logits, key, temp)

    return greedy, categorical, nucleus


def sample(
    logits: jax.Array,
    key: jax.Array,
    *,
    temperature=0.0,
    top_k: int = 0,
    top_p=1.0,
) -> jax.Array:
    """Sample next-token ids ``(B,)`` from logits ``(B, V)``.

    ``temperature``/``top_p`` broadcast per-row; rows with ``temperature <= 0``
    take the argmax.  ``key`` is one key or a ``(B, ...)`` stack of keys.  One
    ``lax.switch`` on ``batch_path`` runs the branch the batch needs.
    """
    logits = logits.astype(jnp.float32)
    B = logits.shape[0]
    temp = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (B,))
    top_p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (B,))
    return jax.lax.switch(batch_path(temp, top_p), _branches(top_k), logits, key, temp, top_p)


def request_key(base_key: jax.Array, uid, token_index) -> jax.Array:
    """The key of one draw: a request's sample stream is keyed by (uid, token
    index), never by the slot it landed in or what shares its batch.
    ``fold_in`` takes its data as uint32, so any integer dtype that holds the
    uid's low 32 bits gives the key the host would build from the Python int."""
    return jax.random.fold_in(jax.random.fold_in(base_key, uid), token_index)


def sample_rows(
    logits: jax.Array,
    base_key: jax.Array,
    uids: jax.Array,
    token_index: jax.Array,
    *,
    temperature,
    top_k: int = 0,
    top_p=1.0,
) -> jax.Array:
    """:func:`sample` with each row's :func:`request_key` built here, inside
    the program: ``uids`` and ``token_index`` are ``(B,)`` integers the host
    fills in numpy, so a round's draws cost one dispatch whatever ``B`` is
    (a key per row built on the host is several tiny programs per row)."""
    keys = jax.vmap(request_key, in_axes=(None, 0, 0))(base_key, uids, token_index)
    return sample(logits, keys, temperature=temperature, top_k=top_k, top_p=top_p)


#: fold_in constants separating the verify round's PRNG draws per
#: (uid, token_index): 1 = acceptance uniform, 2 = residual/bonus sample.
#: Each (uid, token_index, kind) is consumed at most once over a request's
#: lifetime — a rejected round never commits the indices past the rejection,
#: and the round that commits an index is the only round whose walk uses its
#: draws — so reuse across rounds never correlates committed samples.
_SPEC_ACCEPT = 1
_SPEC_ALT = 2


def spec_verify_draws(
    logits: jax.Array,
    draft: jax.Array,
    base_key: jax.Array,
    uids: jax.Array,
    start_index: jax.Array,
    k_eff: jax.Array,
    *,
    temperature,
    top_k: int = 0,
    top_p=1.0,
):
    """Everything the speculative accept/rollback walk needs, in one jit.

    Inputs: ``logits`` ``(B, S, V)`` from the verify forward (row ``i``
    predicts generated-token index ``start_index + i``), ``draft`` ``(B,
    S-1)`` the drafted candidates (``draft[:, i]`` judged by logits row
    ``i``), ``uids``/``start_index``/``k_eff`` ``(B,)`` int32 — request id,
    index of the first token this window can commit, and how many leading
    draft entries are real (the rest is padding).  ``temperature``/``top_p``
    broadcast per-row like :func:`sample`; ``top_k`` is static.

    Returns ``(accept, alt)``:

    - ``accept`` ``(B, S-1)`` bool — greedy rows accept iff the draft equals
      the row argmax; sampled rows accept with probability ``p(draft)``
      under the *same* filtered target distribution :func:`sample` draws
      from (top-k → top-p → temperature), the textbook deterministic-
      proposal rejection rule, so the committed marginal is exactly the
      target distribution.
    - ``alt`` ``(B, S)`` int32 — the token to commit when the walk stops at
      row ``i``: for ``i < k_eff`` the residual sample (target with the
      rejected draft token removed, renormalized); for ``i == k_eff`` a
      plain target sample (the bonus after full acceptance).  Greedy rows
      get the row argmax everywhere.

    Host walk per row: ``a`` = leading accepts among the first ``k_eff``
    entries; commit ``draft[:a]`` then ``alt[a]``.
    """
    logits = logits.astype(jnp.float32)
    B, S, V = logits.shape
    greedy = jnp.argmax(logits, axis=-1)  # (B, S)

    temp = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (B,))
    top_p_b = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (B,))
    flat = logits.reshape(B * S, V)
    filtered = filter_logits(flat, top_k, jnp.repeat(top_p_b, S)).reshape(B, S, V)
    scaled = filtered / jnp.maximum(temp, 1e-6)[:, None, None]
    probs = jax.nn.softmax(scaled, axis=-1)  # (B, S, V) the target p

    # per-(row, window-slot) keys: the SAME (uid, token_index) stream the
    # plain decode path folds (sample_rows), built in-device like there
    window = start_index.astype(jnp.int32)[:, None] + jnp.arange(S, dtype=jnp.int32)
    keys = jax.vmap(jax.vmap(request_key, in_axes=(None, None, 0)), in_axes=(None, 0, 0))(
        base_key, uids.astype(jnp.int32), window
    )

    accept_keys = jax.vmap(jax.vmap(lambda k: jax.random.fold_in(k, _SPEC_ACCEPT)))(
        keys
    )
    alt_keys = jax.vmap(jax.vmap(lambda k: jax.random.fold_in(k, _SPEC_ALT)))(keys)
    # a row that drafted nothing commits exactly one token — the bonus draw
    # at window slot 0 — and consumes no acceptance uniform, so it uses the
    # PLAIN (uid, token_index) key there: its committed stream is bit-equal
    # to the non-window sample() path no matter which rounds carried drafts
    # for other rows (the packed scheduler relies on this invariance)
    no_draft = (k_eff.astype(jnp.int32) == 0)[:, None]  # (B, 1)
    slot0 = jnp.arange(S, dtype=jnp.int32)[None, :] == 0
    alt_keys = jnp.where((no_draft & slot0)[..., None], keys, alt_keys)

    # acceptance: rows 0..S-2 judge draft[:, 0..S-2]
    p_draft = jnp.take_along_axis(probs[:, :-1, :], draft[..., None], axis=-1)[..., 0]
    u = jax.vmap(jax.vmap(jax.random.uniform))(accept_keys[:, :-1])
    accept_sampled = u < p_draft
    accept_greedy = greedy[:, :-1] == draft
    accept = jnp.where((temp <= 0.0)[:, None], accept_greedy, accept_sampled)

    # alternative tokens: residual (draft slot zeroed, renormalized) where a
    # real draft exists, plain target at the bonus slot; categorical over
    # log-probs is invariant to the normalizer, so masking the scaled logits
    # IS the renormalized residual draw
    slot = jnp.arange(S, dtype=jnp.int32)[None, :]  # (1, S)
    has_draft = slot < k_eff.astype(jnp.int32)[:, None]  # (B, S)
    draft_full = jnp.concatenate(
        [draft, jnp.zeros((B, 1), draft.dtype)], axis=1
    )  # (B, S); last col unused (has_draft is False there)
    onehot = jax.nn.one_hot(draft_full, V, dtype=bool)
    residual_logits = jnp.where(
        has_draft[..., None] & onehot, _NEG_INF, scaled
    )
    alt_sampled = jax.vmap(jax.vmap(jax.random.categorical))(
        alt_keys, residual_logits
    )
    alt = jnp.where((temp <= 0.0)[:, None], greedy, alt_sampled)
    return accept, alt
