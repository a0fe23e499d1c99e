"""The benchmark's weights for the ``afmoe`` family: every leaf drawn from
``--seed`` by its layer and its path, on the device, in the type the cell
serves in.

The layout is the served model's (``relora_tpu/models/afmoe.py``): a layer a
subtree ``layers_{i}`` with its four norms, ``attn/qkvg_proj`` (q, k, v and
the gate side by side), ``attn/o_proj``, ``attn/q_norm`` and ``attn/k_norm``,
and either a dense ``mlp`` or ``experts`` (``router``, ``select_bias`` and the
held experts' ``gate_up`` and ``down`` stacks) beside a ``shared_expert``;
kernels stored ``(in, out)``.

The program is handed :func:`make_weights` in bf16.  The plain reference makes
the same leaves a layer at a time (:func:`make_layer`, :func:`make_ends`) from
the same draws, rounded to bf16 and widened again, so both sides hold the same
numbers and neither takes anything the other has made.

A layer is drawn from the seed's key folded with its index, each leaf from
that folded with its path inside the layer: layers of one kind are one
compiled program.  A stack of experts is drawn an expert at a time, so that
the draw holds one expert in f32 and not thirty-two — the tree is 8.6 GB of a
16 GB chip, and a 2.4 GB f32 copy of a stack beside its random bits does not
fit next to it.

Kernels and the embedding are N(0, 0.02), norm scales (the per-head q/k norms'
too) 1 + N(0, 0.02), the router's selection biases as wide as the
configuration's ``init`` says (``select_bias_std``; `PERF.md` section 6, PR 34,
on why no wider than the gaps between the top sigmoid scores).
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from benchmark.weights import flatten, seed_key  # noqa: F401  (flatten: the drivers' tree check)

STD = 0.02


def _swiglu_shapes(h: int, f: int) -> dict:
    return {name: {"kernel": shape} for name, shape in
            (("gate_proj", (h, f)), ("up_proj", (h, f)), ("down_proj", (f, h)))}


def layer_shapes(cfg: dict, i: int) -> dict:
    h, n, n_kv, d = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    shapes = {
        name: {"scale": (h,)}
        for name in ("input_layernorm", "post_attention_layernorm", "pre_mlp_layernorm", "post_mlp_layernorm")
    }
    shapes["attn"] = {
        "qkvg_proj": (h, 2 * n * d + 2 * n_kv * d), "o_proj": (n * d, h),
        "q_norm": {"scale": (d,)}, "k_norm": {"scale": (d,)},
    }
    if i < cfg["num_dense_layers"]:
        shapes["mlp"] = _swiglu_shapes(h, cfg["intermediate_size"])
        return shapes
    f, held = cfg["moe_intermediate_size"], cfg.get("experts_held", cfg["num_experts"])
    shapes["experts"] = {
        "router": (h, cfg["num_experts"]), "select_bias": (cfg["num_experts"],),
        "gate_up": (held, h, 2 * f), "down": (held, f, h),
    }
    if cfg.get("num_shared_experts"):
        shapes["shared_expert"] = _swiglu_shapes(h, cfg["num_shared_experts"] * f)
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
    Selection biases and norm scales stay f32, as the model keeps them."""
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    name = path.rsplit("/", 1)[-1]
    if f"{name}_std" in init:
        return init[f"{name}_std"] * jax.random.normal(k, shape, jnp.float32)
    if name == "scale":
        return 1.0 + STD * jax.random.normal(k, shape, jnp.float32)
    if len(shape) == 3:  # a stack of experts: one expert's draw at a time
        return jax.lax.map(
            lambda e: (STD * jax.random.normal(jax.random.fold_in(k, e), shape[1:], jnp.float32)).astype(dtype),
            jnp.arange(shape[0]),
        )
    return (STD * jax.random.normal(k, shape, jnp.float32)).astype(dtype)


def build(key: jax.Array, shapes: dict, dtype, init: dict, prefix: str = "") -> dict:
    out = {}
    for k in sorted(shapes):
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(shapes[k], dict):
            out[k] = build(key, shapes[k], dtype, init, path)
        else:
            out[k] = leaf_from_seed(key, path, shapes[k], dtype, init)
    return out


def make_layer(cfg: dict, key: jax.Array, i, dtype=jnp.bfloat16, routed=None) -> dict:
    """Layer ``i`` alone (traceable; ``i`` may be traced where ``routed`` says
    which kind of layer it is): the same leaves :func:`make_weights` gives."""
    if routed is None:
        routed = i >= cfg["num_dense_layers"]
    shapes = layer_shapes(cfg, cfg["num_dense_layers"] if routed else 0)
    return build(jax.random.fold_in(key, i + 1), shapes, dtype, cfg["init"])


def make_ends(cfg: dict, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """Embedding, final norm and head alone (traceable)."""
    return build(key, end_shapes(cfg), dtype, cfg["init"])


def layer_maker(cfg: dict, dtype):
    """``fn(key, i, routed=...)``, jitted: one program per kind of layer."""
    return jax.jit(lambda key, i, routed: make_layer(cfg, key, i, dtype, routed), static_argnames="routed")


def make_weights(cfg: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The whole tree on the device, a layer a call: what a call draws in f32
    before rounding is then one layer's and not the tree's."""
    key = seed_key(seed)
    layer = layer_maker(cfg, dtype)
    tree = jax.jit(lambda k: make_ends(cfg, k, dtype))(key)
    for i in range(cfg["num_hidden_layers"]):
        tree[f"layers_{i}"] = layer(key, jnp.int32(i), routed=i >= cfg["num_dense_layers"])
    return tree
