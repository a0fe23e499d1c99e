"""MiMo-V2 language model (HF ``mimo_v2``), for serving.

What the family brings that Llama and GPT-NeoX lack, and where each lives:

- **Layers of unlike kinds** (``ModelConfig.layer_window`` / ``layer_moe``):
  global or sliding-window attention, a dense SwiGLU FFN or routed experts.
  Each layer is a module of its own (``layers_{i}``) with its own cache
  leaves — no stacked ``nn.scan``, and no slice out of a stacked pool.
- **Attention** with K heads wider than V heads (``qk_head_dim`` 192,
  ``v_head_dim`` 128), rotary on the leading ``rotary_dim`` features of a
  head with a base per layer kind, a value scale, and — window layers — a
  learned sink score per query head that joins the softmax's denominator
  (ops/attention.py takes all of these as shapes and operands).
- **Two cache kinds** (models/step.py): global layers keep pages reached
  through the request's block table; window layers keep a ring of pages per
  decode slot that holds the last ``sliding_window`` tokens and never grows.
- **Routed experts** (ops/moe.py): a sigmoid router of the published width,
  of whose experts this chip holds ``experts_held``.

The forward takes one :class:`~relora_tpu.models.step.StepContext`; without
one it is the plain full-sequence forward the tests compare with the
reference (benchmark/reference/mimo.py).  No LoRA wrapper: ``train/`` refuses
the family.  Each MoE layer sows ``[local assignments, distinct experts
hit]`` into the ``stats`` collection.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from relora_tpu.config.model import ModelConfig
from relora_tpu.models.llama import LlamaMLP, RMSNorm, apply_rotary, pool_must_be_given, rotary_tables
from relora_tpu.models.step import PAGED, RING, CacheSpec, StepContext
from relora_tpu.ops import moe
from relora_tpu.ops.attention import cached_attention
from relora_tpu.ops.attention_dispatch import paged_attention


def _normal(std: float):
    return nn.initializers.normal(stddev=std)


class MimoAttention(nn.Module):
    config: ModelConfig
    window: bool
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False
    page_size: int = 0

    @nn.compact
    def __call__(self, x: jax.Array, ctx: StepContext) -> jax.Array:
        cfg = self.config
        n, dk, dv = cfg.num_attention_heads, cfg.qk_head_dim, cfg.v_head_dim
        n_kv = cfg.window_kv_heads if self.window else cfg.kv_heads
        B, S, h = x.shape
        std = cfg.initializer_range
        widths = (n * dk, n_kv * dk, n_kv * dv)
        qkv = self.param("qkv_proj", _normal(std), (h, sum(widths)), self.param_dtype)
        o = self.param("o_proj", _normal(std), (n * dv, h), self.param_dtype)
        has_sink = cfg.window_sink if self.window else cfg.global_sink
        sink = self.param("sink", nn.initializers.zeros_init(), (n,), jnp.float32) if has_sink else None

        y = jnp.dot(x.astype(self.dtype), qkv.astype(self.dtype))
        q, k, v = jnp.split(y, (widths[0], widths[0] + widths[1]), axis=-1)
        q = q.reshape(B, S, n, dk)
        k = k.reshape(B, S, n_kv, dk)
        v = (v.reshape(B, S, n_kv, dv) * cfg.value_scale).astype(self.dtype)

        rot = cfg.rotary_dim
        cos, sin = rotary_tables(
            ctx.positions, rot, cfg.window_rotary_base if self.window else cfg.rotary_emb_base
        )

        def rotate(t):
            return jnp.concatenate([apply_rotary(t[..., :rot], cos, sin), t[..., rot:]], axis=-1)

        q, k = rotate(q), rotate(k)
        window = cfg.sliding_window if self.window else None
        scale = dk**-0.5
        if not self.decode or self.is_initializing():
            # the plain forward (tests, and an init that makes no cache)
            out = cached_attention(q, k, v, ctx.positions, scale=scale, window=window, sink=sink)
        elif self.page_size < 1:
            raise ValueError("this family is served from the paged engine only (page_size set)")
        else:
            table = ctx.tables[RING if self.window else PAGED]
            ck = self.variable("cache", "k", pool_must_be_given)
            cv = self.variable("cache", "v", pool_must_be_given)
            positions = jnp.broadcast_to(ctx.positions, (B, S)).astype(jnp.int32)
            # a K head is stored with zero features after it up to whole
            # 128-lane tiles (CacheSpec.k_pad); the queries get the same zeros
            pad = ((0, 0), (0, 0), (0, 0), (0, ck.value.shape[-1] - dk))
            q, k = jnp.pad(q, pad), jnp.pad(k, pad)
            # logical page p is entry p % W: itself in a table as wide as the
            # cache, the ring's entry in a window layer's
            pages = jnp.take_along_axis(table, (positions // self.page_size) % table.shape[1], axis=1)
            offs = positions % self.page_size
            ck.value = ck.value.at[pages, offs].set(k.astype(ck.value.dtype))
            cv.value = cv.value.at[pages, offs].set(v.astype(cv.value.dtype))
            out = paged_attention(
                q, ck.value, cv.value, table, positions, scale=scale, window=window, sink=sink
            )
        return jnp.dot(out.reshape(B, S, n * dv).astype(self.dtype), o.astype(self.dtype))


class MimoExperts(nn.Module):
    """Router over all ``n_routed_experts``; the experts held here."""

    config: ModelConfig
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        cfg = self.config
        B, S, h = x.shape
        f, held = cfg.moe_intermediate_size, cfg.experts_held
        std = cfg.initializer_range
        router = self.param("router", _normal(std), (h, cfg.n_routed_experts), self.param_dtype)
        select_bias = self.param("select_bias", nn.initializers.zeros_init(), (cfg.n_routed_experts,), jnp.float32)
        gate_up = self.param("gate_up", _normal(std), (held, h, 2 * f), self.param_dtype)
        down = self.param("down", _normal(std), (held, f, h), self.param_dtype)
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


class MimoLayer(nn.Module):
    config: ModelConfig
    window: bool
    routed: bool
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False
    page_size: int = 0

    @nn.compact
    def __call__(self, x: jax.Array, ctx: StepContext) -> jax.Array:
        cfg = self.config
        a = RMSNorm(eps=cfg.rms_norm_eps, dtype=self.dtype, name="input_layernorm")(x)
        x = x + MimoAttention(
            cfg, self.window, self.dtype, self.param_dtype, self.decode, self.page_size, name="attn"
        )(a, ctx)
        m = RMSNorm(eps=cfg.rms_norm_eps, dtype=self.dtype, name="post_attention_layernorm")(x)
        if self.routed:
            return x + MimoExperts(cfg, self.dtype, self.param_dtype, name="experts")(m)
        return x + LlamaMLP(cfg, None, self.dtype, name="mlp")(m)


class MimoForCausalLM(nn.Module):
    """Causal LM returning f32 logits over the vocabulary rows held here."""

    config: ModelConfig
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False
    page_size: int = 0

    #: the engine hands this family one StepContext, not keywords
    takes_step_context = True
    #: what the serving stack cannot do for this family yet; asking for one
    #: is an error by its name (serve/engine.py, serve/scheduler.py)
    refuses = (
        "the contiguous cache", "prefix reuse", "speculation", "adapters", "int8 pages",
        "tp", "packed steps", "page migration",
    )

    @nn.compact
    def __call__(self, input_ids: jax.Array, ctx: Optional[StepContext] = None) -> jax.Array:
        cfg = self.config
        if ctx is None:
            ctx = StepContext(positions=jnp.arange(input_ids.shape[1])[None, :])
        embedding = self.param(
            "embed_tokens", _normal(cfg.initializer_range), (cfg.vocab_size, cfg.hidden_size), self.param_dtype
        )
        x = jnp.take(embedding, input_ids, axis=0).astype(self.dtype)
        for i, (window, routed) in enumerate(zip(cfg.layer_window, cfg.layer_moe)):
            x = MimoLayer(
                cfg, bool(window), bool(routed), self.dtype, self.param_dtype,
                self.decode, self.page_size, name=f"layers_{i}",
            )(x, ctx)
        x = RMSNorm(eps=cfg.rms_norm_eps, dtype=self.dtype, name="norm")(x)
        lm_head = self.param(
            "lm_head", _normal(cfg.initializer_range), (cfg.hidden_size, cfg.vocab_size), self.param_dtype
        )
        return jnp.dot(x, lm_head.astype(self.dtype), preferred_element_type=jnp.float32)

    def pool_shapes(self, specs: Tuple[CacheSpec, ...], dtype) -> dict:
        """The ``cache`` collection the paged forward wants: per layer a K
        and a V pool of its kind's spec."""
        by_kind = {s.kind: s for s in specs}
        tree = {}
        for i, window in enumerate(self.config.layer_window):
            s = by_kind[RING if window else PAGED]
            tree[f"layers_{i}"] = {
                "attn": {
                    name: jax.ShapeDtypeStruct((s.num_pages, s.page_size, s.kv_heads, dim), dtype)
                    for name, dim in (("k", s.k_dim + s.k_pad), ("v", s.v_dim))
                }
            }
        return tree
