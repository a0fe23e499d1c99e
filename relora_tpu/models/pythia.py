"""TPU-native GPT-NeoX / Pythia decoder (Flax) with first-class LoRA leaves.

Capability parity with the reference's modified HF GPT-NeoX
(peft_pretraining/modeling_pythia.py): fused QKV ``query_key_value`` linear
(:108), partial rotary embeddings (``rotary_pct``, :97, :184-197), parallel
residual blocks (:443-456), LayerNorm with biases, GELU MLP, causal SDPA
(:245-295), and a causal-LM head (:701-857).

Used by the production 1B recipe (training_configs/1B_v1.0.yaml:
EleutherAI/pythia-1b warm start).  Weight layout matches HF exactly — the
fused QKV out-dim is interleaved per head as (heads, 3, head_dim) — so
hf_compat transfers Pythia checkpoints without reshuffling.  The serving
forward hands the QKV product to the head split across an optimization
barrier, so the product reads its layer of the stacked kernel in place.

Same TPU-first choices as models/llama.py: scan-over-layers, optional remat,
bf16 matmuls with f32 norms/rotary/softmax.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from relora_tpu.config.model import ModelConfig
from relora_tpu.core.relora import LoraSpec
from relora_tpu.models.llama import (
    apply_rotary,
    attend_with_cache,
    attend_with_paged_cache,
    paged_pool_shapes,
    rotary_tables,
    scan_layers,
)
from relora_tpu.models.lora import LoRALinear
from relora_tpu.ops.attention import dot_product_attention


class LayerNorm(nn.Module):
    """f32 LayerNorm with bias (NeoX style)."""

    eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones_init(), ("embed",)),
            (x.shape[-1],),
            jnp.float32,
        )
        bias = self.param(
            "bias",
            nn.with_logical_partitioning(nn.initializers.zeros_init(), ("embed",)),
            (x.shape[-1],),
            jnp.float32,
        )
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + self.eps)
        return (y * scale + bias).astype(self.dtype)


class NeoXAttention(nn.Module):
    config: ModelConfig
    lora: Optional[LoraSpec] = None
    dtype: jnp.dtype = jnp.bfloat16
    attention_impl: str = "auto"
    decode: bool = False
    cache_size: int = 0
    # page_size > 0 switches the decode cache to the shared paged pool
    # (see models/llama.attend_with_paged_cache)
    page_size: int = 0
    num_pages: int = 0
    kv_dtype: str = "bf16"
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, cos, sin, positions=None, deterministic: bool = True, block_tables=None, adapter_idx=None, row_map=None, layer=None):
        cfg = self.config
        h, n, hd = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim
        rot = cfg.rotary_dim

        qkv = LoRALinear(
            3 * h,
            use_bias=True,
            lora=self.lora,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_axes=("embed", "qkv"),
            name="query_key_value",
        )(x, deterministic, adapter_idx)
        B, S = x.shape[:2]
        if self.decode:
            # were XLA to fold the head split into the product, each layer would slice its kernel into
            # VMEM and copy it transposed; serving only: the training step makes no such copy
            qkv = jax.lax.optimization_barrier(qkv)
        # HF NeoX fused layout: out dim is (heads, 3 * head_dim) interleaved
        qkv = qkv.reshape(B, S, n, 3 * hd)
        q, k, v = qkv[..., :hd], qkv[..., hd : 2 * hd], qkv[..., 2 * hd :]

        # partial rotary: rotate the first rotary_dim dims, pass the rest
        # (modeling_pythia.py:184-197)
        q = jnp.concatenate([apply_rotary(q[..., :rot], cos, sin), q[..., rot:]], axis=-1)
        k = jnp.concatenate([apply_rotary(k[..., :rot], cos, sin), k[..., rot:]], axis=-1)

        if self.decode and self.page_size > 0:
            out = attend_with_paged_cache(self, q, k, v, positions, block_tables, row_map, layer)
        elif self.decode:
            out = attend_with_cache(self, q, k, v, positions)
        else:
            out = dot_product_attention(q, k, v, causal=True, impl=self.attention_impl)
        out = out.reshape(B, S, h)
        return LoRALinear(
            h,
            use_bias=True,
            lora=self.lora,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_axes=("qkv", "embed"),
            name="dense",
        )(out, deterministic, adapter_idx)


class NeoXMLP(nn.Module):
    config: ModelConfig
    lora: Optional[LoraSpec] = None
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, deterministic: bool = True, adapter_idx=None):
        cfg = self.config
        dense = functools.partial(
            LoRALinear, use_bias=True, lora=self.lora, dtype=self.dtype, param_dtype=self.param_dtype
        )
        y = dense(cfg.intermediate_size, kernel_axes=("embed", "mlp"), name="dense_h_to_4h")(
            x, deterministic, adapter_idx
        )
        y = nn.gelu(y, approximate=False)
        return dense(cfg.hidden_size, kernel_axes=("mlp", "embed"), name="dense_4h_to_h")(
            y, deterministic, adapter_idx
        )


class NeoXLayer(nn.Module):
    """Scan-compatible block; parallel residual by default
    (modeling_pythia.py:443-456)."""

    config: ModelConfig
    lora: Optional[LoraSpec] = None
    dtype: jnp.dtype = jnp.bfloat16
    attention_impl: str = "auto"
    decode: bool = False
    cache_size: int = 0
    page_size: int = 0
    num_pages: int = 0
    kv_dtype: str = "bf16"
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, cos, sin, positions=None, deterministic: bool = True, block_tables=None, adapter_idx=None, row_map=None, layer=None):
        cfg = self.config
        attn_in = LayerNorm(eps=cfg.layer_norm_eps, dtype=self.dtype, name="input_layernorm")(x)
        attn_out = NeoXAttention(
            cfg, self.lora, self.dtype, self.attention_impl,
            self.decode, self.cache_size, self.page_size, self.num_pages,
            self.kv_dtype, self.param_dtype,
            name="attention"
        )(attn_in, cos, sin, positions, deterministic, block_tables, adapter_idx, row_map, layer)
        mlp_in = LayerNorm(
            eps=cfg.layer_norm_eps, dtype=self.dtype, name="post_attention_layernorm"
        )(x if cfg.use_parallel_residual else x + attn_out)
        mlp_out = NeoXMLP(cfg, self.lora, self.dtype, self.param_dtype, name="mlp")(mlp_in, deterministic, adapter_idx)
        if cfg.use_parallel_residual:
            # x + attn(ln1(x)) + mlp(ln2(x))
            return x + attn_out + mlp_out, None
        return x + attn_out + mlp_out, None  # sequential: mlp_in already includes attn


class GPTNeoXForCausalLM(nn.Module):
    """Causal LM with f32 logits (parity: modeling_pythia.py:701-857)."""

    config: ModelConfig
    lora: Optional[LoraSpec] = None
    dtype: jnp.dtype = jnp.bfloat16
    scan_layers: bool = True
    remat: bool = False
    remat_policy: str = "full"  # 'full' | 'dots' (see params_util.remat_policy)
    attention_impl: str = "auto"
    logits_dtype: jnp.dtype = jnp.float32
    # inference: decode=True turns on the per-layer KV caches ("cache"
    # variable collection) of capacity cache_size (see serve/engine.py);
    # page_size > 0 additionally switches them to the shared paged pool,
    # reached through the ``block_tables`` call argument; kv_dtype="int8"
    # stores the pool quantized (see models/llama.attend_with_paged_cache)
    decode: bool = False
    cache_size: int = 0
    page_size: int = 0
    num_pages: int = 0
    kv_dtype: str = "bf16"
    # the type the matrices, linear biases, embedding and LoRA factors are
    # declared in: f32 for training; the serving engine gives the compute
    # dtype, and holds the tree so.  LayerNorm leaves are f32 either way.
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(
        self,
        input_ids: jax.Array,
        positions: Optional[jax.Array] = None,
        deterministic: bool = True,
        return_hidden: bool = False,
        block_tables: Optional[jax.Array] = None,
        adapter_idx: Optional[jax.Array] = None,
        row_map: Optional[jax.Array] = None,
    ) -> jax.Array:
        cfg = self.config
        x = nn.Embed(
            cfg.vocab_size,
            cfg.hidden_size,
            embedding_init=nn.with_logical_partitioning(
                nn.initializers.normal(stddev=cfg.initializer_range), ("vocab", "embed")
            ),
            param_dtype=self.param_dtype,
            dtype=self.dtype,
            name="embed_in",
        )(input_ids)

        if positions is None:
            positions = jnp.arange(input_ids.shape[1])[None, :]
        cos, sin = rotary_tables(
            positions,
            cfg.rotary_dim,
            cfg.rotary_emb_base,
            scaling_type=cfg.rope_scaling_type,
            scaling_factor=cfg.rope_scaling_factor,
            max_position=cfg.max_sequence_length,
            current_length=input_ids.shape[1],
        )

        block = NeoXLayer
        if self.remat:
            from relora_tpu.models.params_util import remat_policy

            block = nn.remat(
                block,
                prevent_cse=not self.scan_layers,
                static_argnums=(5,),
                policy=remat_policy(
                    self.remat_policy, max_save_width=self.config.hidden_size
                ),
            )
        layer_kwargs = dict(
            config=cfg, lora=self.lora, dtype=self.dtype,
            attention_impl=self.attention_impl, decode=self.decode,
            cache_size=self.cache_size, page_size=self.page_size,
            num_pages=self.num_pages, kv_dtype=self.kv_dtype, param_dtype=self.param_dtype,
        )
        if self.scan_layers:
            x = scan_layers(
                block, layer_kwargs, cfg.num_hidden_layers,
                x, cos, sin, positions, deterministic, block_tables, adapter_idx, row_map,
            )
        else:
            for i in range(cfg.num_hidden_layers):
                x, _ = block(**layer_kwargs, name=f"layers_{i}")(
                    x, cos, sin, positions, deterministic, block_tables, adapter_idx, row_map
                )

        x = LayerNorm(eps=cfg.layer_norm_eps, dtype=self.dtype, name="final_layer_norm")(x)
        if return_hidden:
            return x
        logits = LoRALinear(
            cfg.vocab_size,
            lora=None,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_axes=("embed", "vocab"),
            name="embed_out",
        )(x)
        return logits.astype(self.logits_dtype)

    def pool_shapes(self, specs, dtype) -> dict:
        """The page pool the paged forward wants in its ``cache`` collection."""
        return paged_pool_shapes(self, "attention", specs, dtype)
