"""TPU-native Llama decoder (Flax) with first-class LoRA leaves.

Capability parity with the reference's self-contained HF-style Llama
(peft_pretraining/modeling_llama.py): RMSNorm (:74-91), rotary embeddings
(:94-141), SwiGLU MLP (:144-158), causal SDPA attention that deliberately
ignores padding masks (:221-224), decoder stack with optional gradient
checkpointing (:552-567), and a causal-LM head with shifted CE loss
(:694-708).

TPU-first design choices (not a port):
- Decoder layers run under ``nn.scan`` by default: one compiled layer body
  iterated L times (compile time O(1) in depth, params stacked on a leading
  "layers" axis that the sharding rules and merge-and-reinit understand).
- Optional ``nn.remat`` wraps the scanned body for activation checkpointing.
- All matmuls in bf16 on the MXU; norms, rotary, softmax and the loss in f32.
- LoRA is declared per-layer via ``LoraSpec`` (see models/lora.py), matching
  the reference's target-module policy: every linear inside attention and MLP
  (torchrun_main.py:542-553), never the embedding or lm_head.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn

from relora_tpu.config.model import ModelConfig
from relora_tpu.core.relora import LoraSpec
from relora_tpu.models.lora import LoRALinear
from relora_tpu.ops.attention import cached_attention, dot_product_attention
from relora_tpu.ops.attention_dispatch import packed_attention, paged_attention


def attend_with_cache(
    module: nn.Module,
    q: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    positions: jax.Array,
) -> jax.Array:
    """Append this call's K/V into the module's fixed-capacity cache
    variables ("cache" collection, shape (B, cache_size, n_kv, head_dim))
    and attend against the full cache with the position mask.

    Shared by both attention families (llama.LlamaAttention,
    pythia.NeoXAttention).  ``positions`` (B|1, T) must be contiguous along
    T — the write is a per-row dynamic_update_slice starting at
    ``positions[:, 0]`` (prefill: 0..S-1; decode: T=1 at the slot's length).
    Under ``nn.scan`` the cache variables stack on the leading "layers"
    axis, exactly like the params.
    """
    B, T = q.shape[:2]
    capacity = module.cache_size
    if capacity < 1:
        raise ValueError("decode=True requires cache_size >= 1")
    n_kv, hd = k_new.shape[2], k_new.shape[3]
    ck = module.variable("cache", "k", jnp.zeros, (B, capacity, n_kv, hd), k_new.dtype)
    cv = module.variable("cache", "v", jnp.zeros, (B, capacity, n_kv, hd), v_new.dtype)
    positions = jnp.broadcast_to(positions, (B, T)).astype(jnp.int32)

    def write(cache, new, start):
        return jax.lax.dynamic_update_slice(cache, new, (start, 0, 0))

    ck.value = jax.vmap(write)(ck.value, k_new.astype(ck.value.dtype), positions[:, 0])
    cv.value = jax.vmap(write)(cv.value, v_new.astype(cv.value.dtype), positions[:, 0])
    return cached_attention(q, ck.value, cv.value, positions)


def pool_must_be_given(*_):
    raise ValueError("the paged forward needs its page pool in the 'cache' collection (engine.init_pool)")


def attend_with_paged_cache(
    module: nn.Module,
    q: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    positions: jax.Array,
    block_tables: jax.Array,
    row_map: Optional[jax.Array] = None,
    layer: Optional[jax.Array] = None,
) -> jax.Array:
    """Paged twin of :func:`attend_with_cache`: K/V pages live in one shared
    pool ("cache" collection, shape (num_pages, page_size, n_kv, head_dim) —
    no batch axis) and each row reaches its entries through ``block_tables``
    (B, W), W = cache_size // page_size.  This call's K/V scatter to
    ``pool[table[b, pos // page_size], pos % page_size]``; attention gathers
    the row's logical cache back out (ops/attention.paged_cached_attention).

    A logical page index beyond the row's table width clips to the last
    column, and padded table entries hold the null page (serve/paging.py) —
    so garbage writes from idle decode rows and chunk padding land where
    nothing ever reads unmasked.

    The pool is the caller's to give (:func:`paged_pool_shapes`,
    serve/engine.init_pool); an init makes parameters and no pool.  Under
    ``nn.scan`` the leaves carry a leading layers axis, ``(L, num_pages,
    ...)``, and ride the layer loop *whole*, as a carry: no layer's pages are
    ever sliced out of the stack or written back into it.  ``layer`` (the
    loop's index) says which pages are this layer's: the leaf is viewed as
    ``(L * num_pages, ...)`` — a reshape that moves nothing — and ``layer *
    num_pages`` is added to every table entry, so the write, the gather and
    the kernels below address the stack in place by page id.  Page 0 of each
    layer stays that layer's null page.

    ``module.kv_dtype == "int8"`` stores the pool as int8 codes plus f32
    per-``(page, kv_head)`` absmax scales (ops/quant.quantize_kv_page
    layout).  Pages fill incrementally — one chunk or decode token at a
    time — so each write maintains the scales as a *running max*: grow the
    touched pages' scales to cover the incoming tokens, requantize the
    already-written codes of exactly those pages by ``old/new``, then write
    the fresh tokens at the new scale.  Untouched pages never move, and
    duplicate page indices in one write scatter identical values, so the
    update is well-defined.  Garbage writes can inflate the null page's
    scale — it is only ever read masked, like its codes.

    ``row_map`` (T,) switches to the *packed mixed-batch* layout: B must be
    1, tokens are laid out token-major and may belong to different requests,
    ``block_tables`` is the whole (R, W) slot-table matrix, and each token
    writes and attends through ``block_tables[row_map[t]]`` at its own
    position (ops/attention_dispatch.packed_attention).  One forward then
    serves any mix of decode rows, verify windows and prefill chunks.
    """
    B, T = q.shape[:2]
    ps, num_pages = module.page_size, module.num_pages
    if num_pages < 2:
        raise ValueError("paged decode requires num_pages >= 2 (page 0 is the null page)")
    if block_tables is None:
        raise ValueError("paged decode requires block_tables (got None)")
    if row_map is not None and B != 1:
        raise ValueError(f"packed (row_map) forward is token-major: B must be 1, got {B}")
    if module.is_initializing():  # parameters only: the pool is never an init's to make
        return dot_product_attention(q, k_new, v_new, causal=True, impl="xla")
    n_kv = k_new.shape[2]
    quantized = getattr(module, "kv_dtype", "bf16") == "int8"
    names = ("k", "v", "k_scale", "v_scale") if quantized else ("k", "v")
    leaves = {name: module.variable("cache", name, pool_must_be_given) for name in names}
    if layer is None:
        pool = {name: leaf.value for name, leaf in leaves.items()}
    else:
        # the stacked leaves as one run of pages, this layer's at its offset
        pool = {name: leaf.value.reshape((-1,) + leaf.value.shape[2:]) for name, leaf in leaves.items()}
        block_tables = block_tables + layer * leaves["k"].value.shape[1]
    positions = jnp.broadcast_to(positions, (B, T)).astype(jnp.int32)
    W = block_tables.shape[1]
    logical = jnp.clip(positions // ps, 0, W - 1)
    if row_map is None:
        rows = jnp.take_along_axis(block_tables, logical, axis=1)  # (B, T) pool pages
    else:
        # per-token tables: token t writes through block_tables[row_map[t]]
        token_tables = jnp.take(
            block_tables, row_map.reshape(T).astype(jnp.int32), axis=0
        )  # (T, W)
        rows = jnp.take_along_axis(
            token_tables, logical.reshape(T, 1), axis=1
        ).reshape(B, T)
    offs = positions % ps

    flat_rows = rows.reshape(-1)  # (B*T,)
    # a tenant always enters a page at offset 0, so an offset-0 write starts
    # that page's life: clear the previous tenant's scale (and, via ratio=0,
    # its codes) instead of running-maxing into it.  Without this a recycled
    # page quantizes its new tenant at whatever stale scale the old tenant
    # left behind, making int8 decode depend on pool allocation history —
    # greedy tokens would differ by batch composition.
    fresh = jnp.where((offs == 0)[..., None], 0.0, 1.0)  # (B, T, 1)

    def write_quantized(codes, scales, new):
        new32 = new.astype(jnp.float32)
        # candidate per-token scale: absmax over head_dim -> (B, T, n_kv)
        cand = jnp.maximum(jnp.max(jnp.abs(new32), axis=-1) / 127.0, 1e-12)
        scales = scales.at[rows].mul(fresh)  # recycled pages forget their past
        new_scale = scales.at[rows].max(cand)  # running max per (page, head)
        # requantize only the touched pages by old/new (1.0 when unchanged);
        # first-touch pages have old == 0 -> ratio 0, but their codes are 0
        ratio = jnp.take(scales, flat_rows, axis=0) / jnp.take(
            new_scale, flat_rows, axis=0
        )  # (B*T, n_kv)
        old_pages = jnp.take(codes, flat_rows, axis=0).astype(jnp.float32)
        requant = jnp.clip(
            jnp.round(old_pages * ratio[:, None, :, None]), -127, 127
        ).astype(jnp.int8)
        codes = codes.at[flat_rows].set(requant)
        # fresh tokens at the new scale of their page
        tok_scale = jnp.take(new_scale, flat_rows, axis=0).reshape(B, T, n_kv)
        q_new = jnp.clip(
            jnp.round(new32 / tok_scale[..., None]), -127, 127
        ).astype(jnp.int8)
        return codes.at[rows, offs].set(q_new), new_scale

    if quantized:
        pool["k"], pool["k_scale"] = write_quantized(pool["k"], pool["k_scale"], k_new)
        pool["v"], pool["v_scale"] = write_quantized(pool["v"], pool["v_scale"], v_new)
    else:
        pool["k"] = pool["k"].at[rows, offs].set(k_new.astype(pool["k"].dtype))
        pool["v"] = pool["v"].at[rows, offs].set(v_new.astype(pool["v"].dtype))
    for name, leaf in leaves.items():
        leaf.value = pool[name].reshape(leaf.value.shape)
    k, v = pool.pop("k"), pool.pop("v")  # what is left are the scales, if any
    if row_map is not None:
        return packed_attention(q, k, v, block_tables, row_map, positions, **pool)
    return paged_attention(q, k, v, block_tables, positions, **pool)


def paged_pool_shapes(module: nn.Module, attention: str, specs, dtype) -> dict:
    """The ``cache`` collection the paged forward of a Llama or GPT-NeoX
    model wants: under its attention module (``attention`` is its name) a K
    and a V leaf ``(num_pages, page_size, kv_heads, head_dim)`` of the one
    cache kind's spec (models/step.CacheSpec), and for an int8 pool the f32
    scales ``(num_pages, kv_heads)``; stacked on a leading layers axis under
    ``layers`` when the model scans, a ``layers_{i}`` each when it does not."""
    (s,) = specs
    leaves = {
        "k": jax.ShapeDtypeStruct((s.num_pages, s.page_size, s.kv_heads, s.k_dim), dtype),
        "v": jax.ShapeDtypeStruct((s.num_pages, s.page_size, s.kv_heads, s.v_dim), dtype),
    }
    if jnp.dtype(dtype) == jnp.int8:
        scale = jax.ShapeDtypeStruct((s.num_pages, s.kv_heads), jnp.float32)
        leaves.update(k_scale=scale, v_scale=scale)
    if not module.scan_layers:
        return {f"layers_{i}": {attention: dict(leaves)} for i in range(s.layers)}
    stacked = {
        name: jax.ShapeDtypeStruct((s.layers,) + leaf.shape, leaf.dtype) for name, leaf in leaves.items()
    }
    return {"layers": {attention: stacked}}


class RMSNorm(nn.Module):
    """y = x / rms(x) * scale, computed in f32 (parity: modeling_llama.py:74-91)."""

    eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param(
            "scale",
            nn.with_logical_partitioning(nn.initializers.ones_init(), ("embed",)),
            (x.shape[-1],),
            jnp.float32,
        )
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (x32 * scale).astype(self.dtype)


def rotary_tables(
    positions: jax.Array,
    head_dim: int,
    base: float = 10000.0,
    *,
    scaling_type: Optional[str] = None,
    scaling_factor: float = 1.0,
    max_position: Optional[int] = None,
    current_length: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """cos/sin tables for HF-convention RoPE, f32, shape (..., seq, head_dim).

    Parity: the reference caches cos/sin up to max_seq and regrows on demand
    (modeling_llama.py:94-141); under jit, shapes are static so we just
    compute for the positions given — XLA folds this into the step.

    Context extension (parity: rope scaling, modeling_pythia.py:333-375):
    ``linear`` divides positions by the factor; ``dynamic`` (NTK) raises the
    frequency base when the current length exceeds the trained max.  Both are
    static under jit (lengths are shapes).
    """
    pos = positions.astype(jnp.float32)
    if scaling_type == "linear":
        pos = pos / scaling_factor
    elif scaling_type == "dynamic" and max_position and current_length and current_length > max_position:
        base = base * (
            scaling_factor * current_length / max_position - (scaling_factor - 1)
        ) ** (head_dim / (head_dim - 2))
    elif scaling_type not in (None, "linear", "dynamic"):
        raise ValueError(f"Unknown rope scaling type {scaling_type!r}")
    inv_freq = 1.0 / (base ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    freqs = jnp.einsum("...s,d->...sd", pos, inv_freq)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.cos(emb), jnp.sin(emb)


def _rotate_half(x: jax.Array) -> jax.Array:
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def apply_rotary(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Apply RoPE to (B, S, N, H) with (B?, S, H) tables (HF rotate-half
    convention, modeling_llama.py:126-141), in f32 for accuracy."""
    cos = cos[..., :, None, :]
    sin = sin[..., :, None, :]
    x32 = x.astype(jnp.float32)
    return (x32 * cos + _rotate_half(x32) * sin).astype(x.dtype)


class LlamaAttention(nn.Module):
    config: ModelConfig
    lora: Optional[LoraSpec] = None
    dtype: jnp.dtype = jnp.bfloat16
    attention_impl: str = "auto"
    # decode=True switches to the KV-cached inference forward: K/V of the
    # tokens in this call are appended into fixed-capacity cache variables
    # at ``positions`` and attention runs masked against the whole cache.
    decode: bool = False
    cache_size: int = 0
    # page_size > 0 switches the decode cache to the paged pool (shared
    # (num_pages, page_size, n_kv, head_dim) buffers reached through the
    # forward's ``block_tables`` argument — see attend_with_paged_cache)
    page_size: int = 0
    num_pages: int = 0
    # "bf16" stores pool pages at the compute dtype (unquantized); "int8"
    # stores codes + per-(page, kv_head) scales — see attend_with_paged_cache
    kv_dtype: str = "bf16"
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        cos: jax.Array,
        sin: jax.Array,
        positions: Optional[jax.Array] = None,
        deterministic: bool = True,
        block_tables: Optional[jax.Array] = None,
        adapter_idx: Optional[jax.Array] = None,
        row_map: Optional[jax.Array] = None,
        layer: Optional[jax.Array] = None,
    ) -> jax.Array:
        cfg = self.config
        h, n, hd = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim
        n_kv = cfg.kv_heads
        dense = functools.partial(
            LoRALinear, lora=self.lora, dtype=self.dtype, param_dtype=self.param_dtype, use_bias=False
        )
        q = dense(h, kernel_axes=("embed", "qkv"), name="q_proj")(x, deterministic, adapter_idx)
        k = dense(n_kv * hd, kernel_axes=("embed", "kv"), name="k_proj")(x, deterministic, adapter_idx)
        v = dense(n_kv * hd, kernel_axes=("embed", "kv"), name="v_proj")(x, deterministic, adapter_idx)

        B, S = x.shape[:2]
        q = q.reshape(B, S, n, hd)
        k = k.reshape(B, S, n_kv, hd)
        v = v.reshape(B, S, n_kv, hd)
        q = apply_rotary(q, cos, sin)
        k = apply_rotary(k, cos, sin)
        # grouped-query attention: K/V keep their n_kv heads all the way into
        # the attention impls (no jnp.repeat — the repeat would materialize
        # n/n_kv× the K/V bytes in HBM and ride the ring at full width)
        if self.decode and self.page_size > 0:
            out = attend_with_paged_cache(
                self, q, k, v, positions, block_tables, row_map, layer
            )
        elif self.decode:
            out = attend_with_cache(self, q, k, v, positions)
        else:
            out = dot_product_attention(q, k, v, causal=True, impl=self.attention_impl)
        out = out.reshape(B, S, h)
        return dense(h, kernel_axes=("qkv", "embed"), name="o_proj")(out, deterministic, adapter_idx)


class LlamaMLP(nn.Module):
    """SwiGLU: down(silu(gate(x)) * up(x)) (parity: modeling_llama.py:144-158)."""

    config: ModelConfig
    lora: Optional[LoraSpec] = None
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(
        self, x: jax.Array, deterministic: bool = True,
        adapter_idx: Optional[jax.Array] = None,
    ) -> jax.Array:
        cfg = self.config
        dense = functools.partial(
            LoRALinear, lora=self.lora, dtype=self.dtype, param_dtype=self.param_dtype, use_bias=False
        )
        gate = dense(cfg.intermediate_size, kernel_axes=("embed", "mlp"), name="gate_proj")(x, deterministic, adapter_idx)
        up = dense(cfg.intermediate_size, kernel_axes=("embed", "mlp"), name="up_proj")(x, deterministic, adapter_idx)
        fused = nn.silu(gate) * up
        return dense(cfg.hidden_size, kernel_axes=("mlp", "embed"), name="down_proj")(fused, deterministic, adapter_idx)


class LlamaDecoderLayer(nn.Module):
    """Pre-norm block (parity: modeling_llama.py:243-308).

    Signature is scan-compatible: ``(x, cos, sin, positions, det,
    block_tables, adapter_idx, row_map[, layer]) -> (x, None)``; ``layer`` is
    the loop's index, given where the stacked page pool rides the loop whole
    (:func:`attend_with_paged_cache`).
    """

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
        a = RMSNorm(eps=cfg.rms_norm_eps, dtype=self.dtype, name="input_layernorm")(x)
        a = LlamaAttention(
            cfg, self.lora, self.dtype, self.attention_impl,
            self.decode, self.cache_size, self.page_size, self.num_pages,
            self.kv_dtype, self.param_dtype,
            name="self_attn"
        )(a, cos, sin, positions, deterministic, block_tables, adapter_idx, row_map, layer)
        x = x + a
        m = RMSNorm(eps=cfg.rms_norm_eps, dtype=self.dtype, name="post_attention_layernorm")(x)
        m = LlamaMLP(cfg, self.lora, self.dtype, self.param_dtype, name="mlp")(m, deterministic, adapter_idx)
        return x + m, None


def scan_layers(block, layer_kwargs: dict, length: int, x: jax.Array, *broadcast) -> jax.Array:
    """``length`` layers of ``block`` as one ``nn.scan`` named ``layers``:
    parameters stacked on a leading layers axis, ``x`` the carry, ``broadcast``
    the same for every layer.  The contiguous decode cache stacks like the
    parameters.  The page pool does not: it is a *carry* of the loop, so each
    layer sees the stacked leaves whole and is told its index (a scanned
    ``arange``) to find its pages in them (:func:`attend_with_paged_cache`) —
    stacked as a scanned input and output, every iteration would slice the
    layer's pages out of the pool and write all of them back."""
    paged = layer_kwargs["decode"] and layer_kwargs["page_size"] > 0
    variable_axes = {"params": 0}
    if layer_kwargs["decode"] and not paged:
        variable_axes["cache"] = 0
    scanned = nn.scan(
        block,
        variable_axes=variable_axes,
        variable_carry="cache" if paged else False,
        split_rngs={"params": True, "dropout": True},
        in_axes=(nn.broadcast,) * len(broadcast) + ((0,) if paged else ()),
        length=length,
        metadata_params={nn.PARTITION_NAME: "layers"},
    )
    index = (jnp.arange(length, dtype=jnp.int32),) if paged else ()
    x, _ = scanned(**layer_kwargs, name="layers")(x, *broadcast, *index)
    return x


def decoder_stack(
    module: nn.Module,
    x: jax.Array,
    positions: Optional[jax.Array],
    deterministic: bool,
    input_len: int,
    block_tables: Optional[jax.Array] = None,
    adapter_idx: Optional[jax.Array] = None,
    row_map: Optional[jax.Array] = None,
) -> jax.Array:
    """Shared decoder body: rotary tables + (scanned or unrolled) layers +
    final norm.  Called from inside a parent's @nn.compact, so submodules
    ("layers"/"layers_i", "norm") register on the caller's scope — both heads
    share one param layout."""
    cfg = module.config
    if positions is None:
        positions = jnp.arange(input_len)[None, :]
    cos, sin = rotary_tables(
        positions,
        cfg.head_dim,
        cfg.rotary_emb_base,
        scaling_type=cfg.rope_scaling_type,
        scaling_factor=cfg.rope_scaling_factor,
        max_position=cfg.max_sequence_length,
        current_length=input_len,
    )

    decode = getattr(module, "decode", False)
    block = LlamaDecoderLayer
    if module.remat:
        from relora_tpu.models.params_util import remat_policy

        block = nn.remat(
            block,
            prevent_cse=not module.scan_layers,
            static_argnums=(5,),  # deterministic
            policy=remat_policy(
                getattr(module, "remat_policy", "full"),
                max_save_width=cfg.hidden_size,
            ),
        )
    layer_kwargs = dict(
        config=cfg,
        lora=module.lora,
        dtype=module.dtype,
        attention_impl=module.attention_impl,
        decode=decode,
        cache_size=getattr(module, "cache_size", 0),
        page_size=getattr(module, "page_size", 0),
        num_pages=getattr(module, "num_pages", 0),
        kv_dtype=getattr(module, "kv_dtype", "bf16"),
        param_dtype=getattr(module, "param_dtype", jnp.float32),
    )
    if module.scan_layers:
        x = scan_layers(
            block, layer_kwargs, cfg.num_hidden_layers,
            x, cos, sin, positions, deterministic, block_tables, adapter_idx, row_map,
        )
    else:
        for i in range(cfg.num_hidden_layers):
            x, _ = block(**layer_kwargs, name=f"layers_{i}")(
                x, cos, sin, positions, deterministic, block_tables, adapter_idx,
                row_map,
            )
    return RMSNorm(eps=cfg.rms_norm_eps, dtype=module.dtype, name="norm")(x)


def token_embed(module: nn.Module, input_ids: jax.Array) -> jax.Array:
    cfg = module.config
    return nn.Embed(
        cfg.vocab_size,
        cfg.hidden_size,
        embedding_init=nn.with_logical_partitioning(
            nn.initializers.normal(stddev=cfg.initializer_range), ("vocab", "embed")
        ),
        param_dtype=getattr(module, "param_dtype", jnp.float32),
        dtype=module.dtype,
        name="embed_tokens",
    )(input_ids)


class LlamaForCausalLM(nn.Module):
    """Causal LM returning f32 logits (parity: modeling_llama.py:603-757).

    ``scan_layers=True`` stacks the decoder params on a leading "layers" axis
    (compile-time win); ``remat=True`` rematerializes each layer in the
    backward pass (parity with gradient checkpointing,
    modeling_llama.py:552-567).
    """

    config: ModelConfig
    lora: Optional[LoraSpec] = None
    dtype: jnp.dtype = jnp.bfloat16
    scan_layers: bool = True
    remat: bool = False
    remat_policy: str = "full"  # 'full' | 'dots' (see params_util.remat_policy)
    attention_impl: str = "auto"
    # f32 logits are the safe default; bf16 halves the (B, S, vocab) HBM
    # footprint — the loss upcasts to f32 either way
    logits_dtype: jnp.dtype = jnp.float32
    # inference: decode=True turns on the per-layer KV caches ("cache"
    # variable collection) of capacity cache_size (see serve/engine.py);
    # page_size > 0 additionally switches them to the shared paged pool,
    # reached through the ``block_tables`` call argument; kv_dtype="int8"
    # stores the pool quantized (codes + scales, attend_with_paged_cache)
    decode: bool = False
    cache_size: int = 0
    page_size: int = 0
    num_pages: int = 0
    kv_dtype: str = "bf16"
    # the type the matrices, embedding and LoRA factors are declared in: f32
    # for training; the serving engine gives the compute dtype, and holds the
    # tree so.  RMSNorm scales are f32 either way.
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
        x = token_embed(self, input_ids)
        x = decoder_stack(
            self, x, positions, deterministic, input_ids.shape[1], block_tables,
            adapter_idx, row_map,
        )
        if return_hidden:
            # chunked-CE path: the caller streams the lm_head projection
            # itself (train/losses.chunked_softmax_ce); init always runs with
            # return_hidden=False so the head param exists
            return x
        logits = LoRALinear(
            self.config.vocab_size,
            lora=None,  # lm_head is never LoRA-wrapped (target-module policy)
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            kernel_axes=("embed", "vocab"),
            name="lm_head",
        )(x)
        return logits.astype(self.logits_dtype)

    def pool_shapes(self, specs, dtype) -> dict:
        """The page pool the paged forward wants in its ``cache`` collection."""
        return paged_pool_shapes(self, "self_attn", specs, dtype)


class LlamaBackbone(nn.Module):
    """Decoder stack without a head (shared by the classification model)."""

    config: ModelConfig
    lora: Optional[LoraSpec] = None
    dtype: jnp.dtype = jnp.bfloat16
    scan_layers: bool = True
    remat: bool = False
    remat_policy: str = "full"
    attention_impl: str = "auto"

    @nn.compact
    def __call__(self, input_ids, positions=None, deterministic: bool = True):
        x = token_embed(self, input_ids)
        return decoder_stack(self, x, positions, deterministic, input_ids.shape[1])


class LlamaForSequenceClassification(nn.Module):
    """Classification/regression head over the last non-pad token
    (parity: modeling_llama.py:775-879 — bias-free ``score`` head, pooling at
    the final non-padding position, regression when num_labels == 1)."""

    config: ModelConfig
    num_labels: int = 2
    pad_token_id: Optional[int] = None
    lora: Optional[LoraSpec] = None
    dtype: jnp.dtype = jnp.bfloat16
    scan_layers: bool = True
    remat: bool = False
    remat_policy: str = "full"
    attention_impl: str = "auto"

    @nn.compact
    def __call__(self, input_ids, deterministic: bool = True):
        h = LlamaBackbone(
            self.config,
            lora=self.lora,
            dtype=self.dtype,
            scan_layers=self.scan_layers,
            remat=self.remat,
            remat_policy=self.remat_policy,
            attention_impl=self.attention_impl,
            name="model",
        )(input_ids, deterministic=deterministic)
        logits = LoRALinear(
            self.num_labels,
            lora=None,
            dtype=self.dtype,
            kernel_axes=("embed", None),
            name="score",
        )(h)
        if self.pad_token_id is None:
            last = jnp.full((input_ids.shape[0],), input_ids.shape[1] - 1)
        else:
            not_pad = (input_ids != self.pad_token_id).astype(jnp.int32)
            last = jnp.maximum(not_pad.sum(axis=-1) - 1, 0)
        pooled = jnp.take_along_axis(logits, last[:, None, None], axis=1)[:, 0, :]
        return pooled.astype(jnp.float32)
