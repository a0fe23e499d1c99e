"""What the families with layers of unlike kinds (models/mimo.py,
models/afmoe.py) share once a layer's own mathematics is done.

- :func:`attend` takes a layer's q, k and v as they are ready — projected,
  normed, rotated, whatever the family does to them — and gives the attended
  output: through the step's cache kinds (the layer's table, the two writes,
  ``paged_attention``) when serving, as the plain masked attention otherwise.
- :class:`RoutedExperts` is a router over the published number of experts and
  the share of them this chip holds (ops/moe.py).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from relora_tpu.config.model import ModelConfig
from relora_tpu.models.llama import pool_must_be_given
from relora_tpu.models.step import PAGED, RING, StepContext
from relora_tpu.ops import moe
from relora_tpu.ops.attention import cached_attention
from relora_tpu.ops.attention_dispatch import paged_attention


def normal_init(std: float):
    return nn.initializers.normal(stddev=std)


def attend(
    module: nn.Module, q: jax.Array, k: jax.Array, v: jax.Array, ctx: StepContext,
    *, window: Optional[int], scale: float, sink: Optional[jax.Array] = None,
) -> jax.Array:
    """``(B, S, N, d_v)`` for ``q`` ``(B, S, N, d_k)`` over ``k``/``v`` ``(B,
    S, n_kv, d)`` and what ``module`` (an attention layer with ``decode`` and
    ``page_size``) has cached before them.  ``window`` (tokens a query sees,
    itself included) makes the layer a window layer: its cache is the slot's
    ring, a global layer's the request's pages."""
    B, S = q.shape[:2]
    if not module.decode or module.is_initializing():
        # the plain forward (tests, and an init that makes no cache)
        return cached_attention(q, k, v, ctx.positions, scale=scale, window=window, sink=sink)
    if module.page_size < 1:
        raise ValueError("this family is served from the paged engine only (page_size set)")
    table = ctx.tables[RING if window else PAGED]
    ck = module.variable("cache", "k", pool_must_be_given)
    cv = module.variable("cache", "v", pool_must_be_given)
    positions = jnp.broadcast_to(ctx.positions, (B, S)).astype(jnp.int32)
    # a K head is stored with zero features after it up to whole
    # 128-lane tiles (CacheSpec.k_pad); the queries get the same zeros
    pad = ((0, 0), (0, 0), (0, 0), (0, ck.value.shape[-1] - q.shape[-1]))
    q, k = jnp.pad(q, pad), jnp.pad(k, pad)
    # logical page p is entry p % W: itself in a table as wide as the
    # cache, the ring's entry in a window layer's
    pages = jnp.take_along_axis(table, (positions // module.page_size) % table.shape[1], axis=1)
    offs = positions % module.page_size
    ck.value = ck.value.at[pages, offs].set(k.astype(ck.value.dtype))
    cv.value = cv.value.at[pages, offs].set(v.astype(cv.value.dtype))
    return paged_attention(q, ck.value, cv.value, table, positions, scale=scale, window=window, sink=sink)


class RoutedExperts(nn.Module):
    """Router over all ``n_routed_experts``; the experts held here.  Sows
    ``[local assignments, distinct experts hit]`` into ``stats``."""

    config: ModelConfig
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        B, S, h = x.shape
        f, held = cfg.moe_intermediate_size, cfg.experts_held
        std = cfg.initializer_range
        router = self.param("router", normal_init(std), (h, cfg.n_routed_experts), self.param_dtype)
        select_bias = self.param("select_bias", nn.initializers.zeros_init(), (cfg.n_routed_experts,), jnp.float32)
        gate_up = self.param("gate_up", normal_init(std), (held, h, 2 * f), self.param_dtype)
        down = self.param("down", normal_init(std), (held, f, h), self.param_dtype)
        tokens = x.reshape(B * S, h).astype(self.dtype)
        chosen, weights = moe.route(
            tokens, router, select_bias, top_k=cfg.num_experts_per_tok,
            norm_topk=cfg.norm_topk_prob, scaling=cfg.routed_scaling_factor,
        )
        y, stats = moe.local_experts(
            tokens, chosen, weights, gate_up.astype(self.dtype), down.astype(self.dtype),
            offset=cfg.expert_offset,
        )
        self.sow("stats", "moe", stats, reduce_fn=jnp.add, init_fn=lambda: jnp.zeros((2,), jnp.int32))
        return y.reshape(B, S, h).astype(self.dtype)


def layer_pool_shapes(layer_window, specs, dtype) -> dict:
    """The ``cache`` collection an unrolled model's paged forward wants: per
    layer (``layers_{i}/attn``) a K and a V pool of its kind's spec."""
    by_kind = {s.kind: s for s in specs}
    tree = {}
    for i, window in enumerate(layer_window):
        s = by_kind[RING if window else PAGED]
        tree[f"layers_{i}"] = {
            "attn": {
                name: jax.ShapeDtypeStruct((s.num_pages, s.page_size, s.kv_heads, dim), dtype)
                for name, dim in (("k", s.k_dim + s.k_pad), ("v", s.v_dim))
            }
        }
    return tree


#: what the serving stack cannot do yet for a family whose layers keep unlike
#: caches; asking for one is an error by its name (serve/engine.py,
#: serve/scheduler.py)
REFUSES = (
    "the contiguous cache", "prefix reuse", "speculation", "adapters", "int8 pages",
    "tp", "packed steps", "page migration",
)
