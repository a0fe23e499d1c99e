"""Arcee Trinity language model (HF ``afmoe``), for serving.

What the family brings that the other three lack; what it shares with
MiMo-V2 (layers of unlike kinds, two cache kinds, routed experts) lives in
models/hybrid.py and ops/moe.py and is called, not copied.

- **Gated attention with q/k norms**: beside q, k and v the layer projects a
  gate ``g`` as wide as q; every q and k head is RMS-normed over its features
  (one learned scale vector each) before any rotation; the attended output is
  multiplied by ``sigmoid(g)`` before the output projection.
- **Positional encoding by layer kind**: window layers rotate q and k
  (rotate-half RoPE on the whole head) and see the last ``sliding_window``
  tokens; global layers take no positional encoding at all.
- **Sandwich norms**: the residual adds a *normed* branch,
  ``x += N_post(Attn(N_in(x)))`` and ``x += N_post(FFN(N_pre(x)))`` — four
  norms a layer.
- **A shared expert beside the routed ones**: ``FFN = Shared(m) + sum_c w_c
  Expert_c(m)``; the shared expert is every chip's alike, the routed sum is
  over the experts held here (``ModelConfig.experts_held``).
- The embedding is multiplied by ``embed_scale`` (``mup_enabled``:
  ``sqrt(hidden_size)``).

The forward takes one :class:`~relora_tpu.models.step.StepContext`; without
one it is the plain full-sequence forward the tests compare with the
reference (benchmark/reference/afmoe.py).  No LoRA wrapper: ``train/`` refuses
the family.  Weights are held in ``param_dtype``, the type they are handed in.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from relora_tpu.config.model import ModelConfig
from relora_tpu.models.hybrid import REFUSES, RoutedExperts, attend, layer_pool_shapes, normal_init
from relora_tpu.models.llama import LlamaMLP, RMSNorm, apply_rotary, rotary_tables
from relora_tpu.models.step import CacheSpec, StepContext


class AfmoeAttention(nn.Module):
    config: ModelConfig
    window: bool
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32
    decode: bool = False
    page_size: int = 0

    @nn.compact
    def __call__(self, x: jax.Array, ctx: StepContext) -> jax.Array:
        cfg = self.config
        n, n_kv, d = cfg.num_attention_heads, cfg.kv_heads, cfg.qk_head_dim
        B, S, h = x.shape
        std = cfg.initializer_range
        widths = (n * d, n_kv * d, n_kv * d, n * d)  # q, k, v and the gate, side by side
        qkvg = self.param("qkvg_proj", normal_init(std), (h, sum(widths)), self.param_dtype)
        o = self.param("o_proj", normal_init(std), (n * d, h), self.param_dtype)

        y = jnp.dot(x.astype(self.dtype), qkvg.astype(self.dtype))
        q, k, v, g = jnp.split(y, (widths[0], widths[0] + widths[1], sum(widths[:3])), axis=-1)
        with jax.named_scope("qk_norm"):
            q = RMSNorm(eps=cfg.rms_norm_eps, dtype=self.dtype, name="q_norm")(q.reshape(B, S, n, d))
            k = RMSNorm(eps=cfg.rms_norm_eps, dtype=self.dtype, name="k_norm")(k.reshape(B, S, n_kv, d))
        if self.window or cfg.global_rotary:
            cos, sin = rotary_tables(ctx.positions, d, cfg.window_rotary_base if self.window else cfg.rotary_emb_base)
            q, k = apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)
        out = attend(
            self, q, k, v.reshape(B, S, n_kv, d), ctx,
            window=cfg.sliding_window if self.window else None, scale=d**-0.5,
        )
        with jax.named_scope("attn_gate"):
            out = out.reshape(B, S, n * d).astype(self.dtype) * jax.nn.sigmoid(g.astype(jnp.float32)).astype(self.dtype)
        return jnp.dot(out, o.astype(self.dtype))


class AfmoeLayer(nn.Module):
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

        def norm(name):
            return RMSNorm(eps=cfg.rms_norm_eps, dtype=self.dtype, name=name)

        a = AfmoeAttention(
            cfg, self.window, self.dtype, self.param_dtype, self.decode, self.page_size, name="attn"
        )(norm("input_layernorm")(x), ctx)
        x = x + norm("post_attention_layernorm")(a)
        m = norm("pre_mlp_layernorm")(x)
        if not self.routed:
            f = LlamaMLP(cfg, None, self.dtype, self.param_dtype, name="mlp")(m)
        else:
            f = RoutedExperts(cfg, self.dtype, self.param_dtype, name="experts")(m)
            if cfg.n_shared_experts:
                # every chip computes the shared expert alike: one SwiGLU as
                # wide as the shared experts together
                shared = dataclasses.replace(
                    cfg, intermediate_size=cfg.n_shared_experts * cfg.moe_intermediate_size
                )
                with jax.named_scope("shared_expert"):
                    f = f + LlamaMLP(shared, None, self.dtype, self.param_dtype, name="shared_expert")(m)
        return x + norm("post_mlp_layernorm")(f)


class AfmoeForCausalLM(nn.Module):
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
        x = (jnp.take(embedding, input_ids, axis=0).astype(jnp.float32) * cfg.embed_scale).astype(self.dtype)
        for i, (window, routed) in enumerate(zip(cfg.layer_window, cfg.layer_moe)):
            x = AfmoeLayer(
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
