"""The benchmark's weights: every leaf of a GPT-NeoX parameter tree drawn
from ``--seed`` on the device, in one jitted call, in float32 (the type the
trainer keeps its state in and ``serve.py`` serves a checkpoint in).

The program under test is handed these weights; the plain reference makes the
same tree by calling the same function, so neither takes anything the other
has made.  The layout is GPT-NeoX's (HF ``GPTNeoXForCausalLM``) with the
layers stacked on a leading axis, kernels stored ``(in, out)``; a LoRA rank
``r > 0`` adds ``lora_a (in, r)`` and ``lora_b (r, out)`` beside the kernel of
every attention and MLP linear, as ReLoRA wraps them.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

# standard deviation of kernels, embeddings and biases (GPT-NeoX
# ``initializer_range``); biases and LayerNorm offsets are drawn too, so that a
# dropped bias shows in the comparison
STD = 0.02


def param_shapes(cfg: dict, lora_r: int = 0) -> dict:
    """Nested dict of leaf shapes for a GPT-NeoX config (HF key names)."""
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    L, v = cfg["num_hidden_layers"], cfg["vocab_size"]

    def linear(n_in: int, n_out: int) -> dict:
        leaves = {"kernel": (L, n_in, n_out), "bias": (L, n_out)}
        if lora_r:
            leaves["lora_a"] = (L, n_in, lora_r)
            leaves["lora_b"] = (L, lora_r, n_out)
        return leaves

    norm = {"scale": (L, h), "bias": (L, h)}
    return {
        "embed_in": {"embedding": (v, h)},
        "layers": {
            "input_layernorm": dict(norm),
            "post_attention_layernorm": dict(norm),
            "attention": {"query_key_value": linear(h, 3 * h), "dense": linear(h, h)},
            "mlp": {"dense_h_to_4h": linear(h, f), "dense_4h_to_h": linear(f, h)},
        },
        "final_layer_norm": {"scale": (h,), "bias": (h,)},
        "embed_out": {"kernel": (h, v)},
    }


def flatten(tree: dict, prefix: str = "") -> dict:
    """``{"a/b/c": leaf}`` for a nested dict."""
    out = {}
    for k in sorted(tree):
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(tree[k], dict):
            out.update(flatten(tree[k], path))
        else:
            out[path] = tree[k]
    return out


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number (the driver's exceed 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def leaf_from_seed(key: jax.Array, path: str, shape: tuple) -> jax.Array:
    """One leaf, by its path (traceable); :func:`build` makes the tree of them."""
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    name = path.rsplit("/", 1)[-1]
    if name == "scale":
        return 1.0 + STD * jax.random.normal(k, shape, jnp.float32)
    if name == "lora_a":
        bound = shape[-2] ** -0.5
        return jax.random.uniform(k, shape, jnp.float32, -bound, bound)
    return STD * jax.random.normal(k, shape, jnp.float32)


def build(key: jax.Array, shapes: dict, prefix: str = "") -> dict:
    """Traceable: the whole tree from one key."""
    out = {}
    for k in sorted(shapes):
        path = f"{prefix}/{k}" if prefix else k
        out[k] = build(key, shapes[k], path) if isinstance(shapes[k], dict) else leaf_from_seed(key, path, shapes[k])
    return out


def make_weights(cfg: dict, seed: int, lora_r: int = 0, out_shardings=None) -> dict:
    """The tree on the device, from the seed, in one jitted call."""
    shapes = param_shapes(cfg, lora_r)
    fn = jax.jit(lambda key: build(key, shapes), out_shardings=out_shardings)
    return fn(seed_key(seed))
