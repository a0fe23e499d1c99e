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
from relora_tpu.models.hybrid import REFUSES, RoutedExperts, attend, layer_pool_shapes, normal_init
from relora_tpu.models.llama import LlamaMLP, RMSNorm, apply_rotary, rotary_tables
from relora_tpu.models.step import CacheSpec, StepContext


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
        qkv = self.param("qkv_proj", normal_init(std), (h, sum(widths)), self.param_dtype)
        o = self.param("o_proj", normal_init(std), (n * dv, h), self.param_dtype)
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

        out = attend(
            self, rotate(q), rotate(k), v, ctx,
            window=cfg.sliding_window if self.window else None, scale=dk**-0.5, sink=sink,
        )
        return jnp.dot(out.reshape(B, S, n * dv).astype(self.dtype), o.astype(self.dtype))


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
            return x + RoutedExperts(cfg, self.dtype, self.param_dtype, name="experts")(m)
        return x + LlamaMLP(cfg, None, self.dtype, self.param_dtype, name="mlp")(m)


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
    refuses = REFUSES

    @nn.compact
    def __call__(self, input_ids: jax.Array, ctx: Optional[StepContext] = None) -> jax.Array:
        cfg = self.config
        if ctx is None:
            ctx = StepContext(positions=jnp.arange(input_ids.shape[1])[None, :])
        embedding = self.param(
            "embed_tokens", normal_init(cfg.initializer_range), (cfg.vocab_size, cfg.hidden_size), self.param_dtype
        )
        x = jnp.take(embedding, input_ids, axis=0).astype(self.dtype)
        for i, (window, routed) in enumerate(zip(cfg.layer_window, cfg.layer_moe)):
            x = MimoLayer(
                cfg, bool(window), bool(routed), self.dtype, self.param_dtype,
                self.decode, self.page_size, name=f"layers_{i}",
            )(x, ctx)
        x = RMSNorm(eps=cfg.rms_norm_eps, dtype=self.dtype, name="norm")(x)
        lm_head = self.param(
            "lm_head", normal_init(cfg.initializer_range), (cfg.hidden_size, cfg.vocab_size), self.param_dtype
        )
        return jnp.dot(x, lm_head.astype(self.dtype), preferred_element_type=jnp.float32)

    def pool_shapes(self, specs: Tuple[CacheSpec, ...], dtype) -> dict:
        """The ``cache`` collection the paged forward wants: per layer a K
        and a V pool of its kind's spec."""
        return layer_pool_shapes(self.config.layer_window, specs, dtype)
