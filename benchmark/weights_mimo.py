"""The benchmark's weights for the ``mimo_v2`` family: every leaf drawn from
``--seed`` by its path, on the device, in the type the cell serves in.

The layout is the served model's (``relora_tpu/models/mimo.py``): a layer a
subtree ``layers_{i}`` with ``attn/qkv_proj`` (q, k, v side by side),
``attn/o_proj``, ``attn/sink`` where the layer kind has one, and either a
dense ``mlp`` or ``experts`` (``router``, ``select_bias`` and the held
experts' ``gate_up`` and ``down`` stacks); kernels stored ``(in, out)``.

The program is handed :func:`make_weights` in bf16.  The plain reference
makes the same leaves a layer at a time (:func:`make_layer`, :func:`make_ends`)
from the same draws, rounded to bf16 and widened again, so both sides hold the
same numbers and neither takes anything the other has made.

Kernels and the embedding are N(0, 0.02), norm scales 1 + N(0, 0.02).  The
sink scores and the router's selection biases are drawn as wide as the
configuration's ``init`` says (``sink_std``, ``select_bias_std``): wide enough
that a forward that leaves either out gives another result, and the biases no
wider than the gaps between the top sigmoid scores they add to — at the
published widths the top eight of 256 lie within a few hundredths of each
other, and at N(0, 0.1) a seed's luck decided which experts took most of the
traffic, and with it how much work the 16 held here got (`PERF.md` section 6,
PR 34).
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from benchmark.weights import flatten, seed_key  # noqa: F401  (flatten: the drivers' tree check)

STD = 0.02


def layer_shapes(cfg: dict, i: int) -> dict:
    h, n = cfg["hidden_size"], cfg["num_attention_heads"]
    dk, dv = cfg["head_dim"], cfg["v_head_dim"]
    window = bool(cfg["hybrid_layer_pattern"][i])
    n_kv = cfg["swa_num_key_value_heads"] if window else cfg["num_key_value_heads"]
    attn = {"qkv_proj": (h, n * dk + n_kv * dk + n_kv * dv), "o_proj": (n * dv, h)}
    if cfg["add_swa_attention_sink_bias" if window else "add_full_attention_sink_bias"]:
        attn["sink"] = (n,)
    shapes = {"input_layernorm": {"scale": (h,)}, "post_attention_layernorm": {"scale": (h,)}, "attn": attn}
    if cfg["moe_layer_freq"][i]:
        f, held = cfg["moe_intermediate_size"], cfg.get("experts_held", cfg["n_routed_experts"])
        shapes["experts"] = {
            "router": (h, cfg["n_routed_experts"]), "select_bias": (cfg["n_routed_experts"],),
            "gate_up": (held, h, 2 * f), "down": (held, f, h),
        }
    else:
        f = cfg["intermediate_size"]
        shapes["mlp"] = {name: {"kernel": shape} for name, shape in
                         (("gate_proj", (h, f)), ("up_proj", (h, f)), ("down_proj", (f, h)))}
    return shapes


def end_shapes(cfg: dict) -> dict:
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed_tokens": (v, h), "norm": {"scale": (h,)}, "lm_head": (h, v)}


def param_shapes(cfg: dict) -> dict:
    shapes = end_shapes(cfg)
    for i in range(cfg["num_hidden_layers"]):
        shapes[f"layers_{i}"] = layer_shapes(cfg, i)
    return shapes


def leaf_from_seed(key: jax.Array, path: str, shape: tuple, dtype, init: dict) -> jax.Array:
    """One leaf by its path (traceable): drawn in f32, rounded to ``dtype``.
    Sink scores, selection biases and norm scales stay f32, as the model
    keeps them."""
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    name = path.rsplit("/", 1)[-1]
    draw = jax.random.normal(k, shape, jnp.float32)
    if f"{name}_std" in init:
        return init[f"{name}_std"] * draw
    if name == "scale":
        return 1.0 + STD * draw
    return (STD * draw).astype(dtype)


def build(key: jax.Array, shapes: dict, dtype, init: dict, prefix: str = "") -> dict:
    out = {}
    for k in sorted(shapes):
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(shapes[k], dict):
            out[k] = build(key, shapes[k], dtype, init, path)
        else:
            out[k] = leaf_from_seed(key, path, shapes[k], dtype, init)
    return out


def make_weights(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The whole tree on the device in one jitted call."""
    shapes = param_shapes(cfg)
    return jax.jit(lambda key: build(key, shapes, dtype, cfg["init"]))(seed_key(seed))


def make_layer(cfg: dict, key: jax.Array, i: int, dtype=jnp.bfloat16) -> dict:
    """Layer ``i`` alone (traceable), the same leaves :func:`make_weights` gives."""
    return build(key, layer_shapes(cfg, i), dtype, cfg["init"], f"layers_{i}")


def make_ends(cfg: dict, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """Embedding, final norm and head alone (traceable)."""
    return build(key, end_shapes(cfg), dtype, cfg["init"])
