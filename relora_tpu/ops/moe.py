"""Routed experts, the share of them one chip holds.

A router of the published width scores every expert; a token's ``top_k`` are
chosen and weighted over all of them; this chip computes what the experts it
holds (``offset .. offset + held - 1``) add for the tokens that chose them.
What the absent experts would add is the other chips' part of the sum and is
not computed here (model-configs guide, section 4: the usual cut).

The experts' two products are grouped ones: the local (token, expert)
assignments, sorted by expert, run through ``jax.lax.ragged_dot`` over the
``(held, h, 2f)`` gate-and-up and ``(held, f, h)`` down stacks.  On the TPU
that lowers to a Mosaic grouped matmul whose grid follows the group sizes
(HLO instructions ``%ragged-dot-metadata*`` and ``%ragged-dot-none*``, the
names a device trace prints): an expert no token chose is not read, and the
work is the local assignments', not ``tokens x top_k``.  Dropless: every
local assignment is computed, whatever the routing; the rows are sized for
the case that all of them are local.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def route(
    x: jax.Array, router: jax.Array, select_bias: jax.Array, *, top_k: int,
    norm_topk: bool = True, scaling: float = 1.0,
) -> Tuple[jax.Array, jax.Array]:
    """Sigmoid scores in f32, the ``top_k`` largest of score + selection bias
    (``noaux_tc`` with one group), weights from the scores alone.  ``x`` is
    ``(T, h)``, ``router`` ``(h, E)``; returns expert ids and weights ``(T, top_k)``."""
    with jax.named_scope("moe_router"):
        scores = jax.nn.sigmoid(
            jnp.matmul(
                x.astype(jnp.float32), router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST, preferred_element_type=jnp.float32,
            )
        )
        _, chosen = jax.lax.top_k(scores + select_bias.astype(jnp.float32), top_k)
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        if norm_topk:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return chosen.astype(jnp.int32), weights * scaling


def local_experts(
    x: jax.Array, chosen: jax.Array, weights: jax.Array, gate_up: jax.Array, down: jax.Array,
    *, offset: int,
) -> Tuple[jax.Array, jax.Array]:
    """``sum_c w_c * down_c(silu(gate_c x) * up_c x)`` over the chosen experts
    held here.  ``gate_up`` is ``(held, h, 2f)`` (gate then up), ``down``
    ``(held, f, h)``.  Returns ``(T, h)`` in f32 and ``[local assignments,
    distinct experts hit]`` as int32."""
    with jax.named_scope("moe_experts"):
        T, k = chosen.shape
        held, f = down.shape[0], down.shape[1]
        expert = chosen.reshape(T * k) - offset
        local = (expert >= 0) & (expert < held)
        group = jnp.where(local, expert, held)  # the absent experts' sort last
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=held + 1)[:held].astype(jnp.int32)
        n_local = jnp.sum(sizes)
        rows = jnp.take(x, order // k, axis=0)  # (T*k, h), grouped by expert
        hidden = jax.lax.ragged_dot(rows, gate_up, sizes, preferred_element_type=jnp.float32)
        act = (jax.nn.silu(hidden[:, :f]) * hidden[:, f:]).astype(x.dtype)
        out = jax.lax.ragged_dot(act, down, sizes, preferred_element_type=jnp.float32)
        # rows past the last group belong to no expert: whatever the product
        # left there (it writes only the groups' tiles) is not a result
        keep = (jnp.arange(T * k) < n_local)[:, None]
        out = jnp.where(keep, out * jnp.take(weights.reshape(T * k), order)[:, None], 0.0)
        y = jnp.take(out, jnp.argsort(order), axis=0).reshape(T, k, -1).sum(axis=1)
        return y, jnp.stack([n_local, jnp.sum(sizes > 0)]).astype(jnp.int32)
