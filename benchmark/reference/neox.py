"""Plain GPT-NeoX reference: forward pass, next-token loss, gradients and the
AdamW update, in ``jax.numpy`` and float32 with every matrix product at
``Precision.HIGHEST``.  No kernel, no cache, no batching tricks; it imports
nothing of the program under test and makes its own weights from the seed
(``benchmark/weights.py``).

It follows the published model (EleutherAI GPT-NeoX as in HF
``GPTNeoXForCausalLM``): fused QKV laid out ``(heads, 3, head_dim)``, rotary
embeddings on the first ``rotary_pct`` of each head (rotate-half convention),
LayerNorm with bias, exact GELU, parallel residual, untied output head; ReLoRA
adds ``scale * (x @ A) @ B`` to every attention and MLP linear and freezes that
linear's kernel.  Work is done a row at a time (``lax.scan`` over rows, layers
re-computed in the backward pass) so that the published widths fit one chip
beside nothing else.

``cast`` puts the same mathematics into a lower precision for the control:
every matrix product's operands are rounded to that type first.
"""

from __future__ import annotations

import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def _rounded(x, q):
    """``q(x)`` in the forward pass; the gradient passes straight through."""
    return x + lax.stop_gradient(q(x) - x)


def cast_bf16(x):
    return _rounded(x, lambda v: v.astype(jnp.bfloat16).astype(jnp.float32))


def cast_fp8(x):
    """Per-tensor scaled float8 (e4m3): what an fp8 matmul would be fed."""

    def q(v):
        amax = jnp.max(jnp.abs(v))
        s = jnp.where(amax > 0, 448.0 / amax, 1.0)
        return (v * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s

    return _rounded(x, q)


CASTS = {"f32": None, "bf16": cast_bf16, "fp8": cast_fp8}


def _mm(x, w, cast):
    if cast is not None:
        x, w = cast(x), cast(w)
    return jnp.matmul(x, w, precision=HIGHEST, preferred_element_type=jnp.float32)


def _linear(x, p, cast, lora_scale):
    y = _mm(x, p["kernel"], cast) + p["bias"]
    if "lora_a" in p:
        y = y + _mm(_mm(x, p["lora_a"], cast), p["lora_b"], cast) * lora_scale
    return y


def _layer_norm(x, p, eps):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _rotary(x, positions, rot, base):
    """Rotate the first ``rot`` features of each head; x is (B, S, n, hd)."""
    inv_freq = 1.0 / (base ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    half = rot // 2
    rotated = jnp.concatenate([-xr[..., half:], xr[..., :half]], -1)
    return jnp.concatenate([xr * jnp.cos(ang) + rotated * jnp.sin(ang), xp], -1)


def _attention(x, p, cfg, cast, lora_scale):
    B, S, h = x.shape
    n = cfg["num_attention_heads"]
    hd = h // n
    rot = int(hd * cfg.get("rotary_pct", 1.0))
    qkv = _linear(x, p["query_key_value"], cast, lora_scale).reshape(B, S, n, 3 * hd)
    q, k, v = qkv[..., :hd], qkv[..., hd : 2 * hd], qkv[..., 2 * hd :]
    pos = jnp.arange(S)
    base = cfg.get("rotary_emb_base", 10000.0)
    q, k = _rotary(q, pos, rot, base), _rotary(k, pos, rot, base)
    if cast is not None:
        q, k, v = cast(q), cast(k), cast(v)
    scores = jnp.einsum("bqnh,bknh->bnqk", q, k, precision=HIGHEST) / math.sqrt(hd)
    causal = pos[:, None] >= pos[None, :]
    probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
    if cast is not None:
        probs = cast(probs)
    out = jnp.einsum("bnqk,bknh->bqnh", probs, v, precision=HIGHEST).reshape(B, S, h)
    return _linear(out, p["dense"], cast, lora_scale)


def _layer(x, p, cfg, cast, lora_scale):
    eps = cfg.get("layer_norm_eps", 1e-5)
    attn = _attention(_layer_norm(x, p["input_layernorm"], eps), p["attention"], cfg, cast, lora_scale)
    mlp_in = x if cfg.get("use_parallel_residual", True) else x + attn
    y = _layer_norm(mlp_in, p["post_attention_layernorm"], eps)
    y = _linear(y, p["mlp"]["dense_h_to_4h"], cast, lora_scale)
    y = jax.nn.gelu(y, approximate=False)
    y = _linear(y, p["mlp"]["dense_4h_to_h"], cast, lora_scale)
    return x + attn + y


def forward(params: dict, tokens, cfg: dict, *, lora_scale: float = 0.0, cast: Optional[Callable] = None):
    """Logits (B, S, vocab) in float32 for token ids (B, S)."""
    x = jnp.take(params["embed_in"]["embedding"], tokens, axis=0)

    @jax.checkpoint
    def body(x, p):
        return _layer(x, p, cfg, cast, lora_scale), None

    x, _ = lax.scan(body, x, params["layers"])
    x = _layer_norm(x, params["final_layer_norm"], cfg.get("layer_norm_eps", 1e-5))
    return _mm(x, params["embed_out"]["kernel"], cast)


def next_token_loss(logits, labels):
    """Mean cross-entropy of ``labels`` (B, S) under ``logits`` (B, S, V)."""
    lp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(lp, labels[..., None], axis=-1).mean()


# --------------------------------------------------------------------------
# training: gradients of the trainable leaves and the AdamW update
# --------------------------------------------------------------------------


def is_frozen(path: str, all_paths) -> bool:
    """ReLoRA freezes the kernel of every linear that carries LoRA factors."""
    return path.endswith("/kernel") and path[: -len("kernel")] + "lora_a" in all_paths


def unflatten(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, name = path.split("/")
        for k in parents:
            node = node.setdefault(k, {})
        node[name] = leaf
    return tree


def leaf_norms(flat: dict) -> dict:
    """L2 norm of every leaf; a leaf stacked over layers gives one per layer."""
    out = {}
    for path, x in flat.items():
        x = x.astype(jnp.float32)
        if path.startswith("layers/"):
            out[path] = jnp.sqrt(jnp.sum(jnp.square(x.reshape(x.shape[0], -1)), axis=1))
        else:
            out[path] = jnp.sqrt(jnp.sum(jnp.square(x)))
    return out


def warmup_lr(hp: dict, step: int) -> float:
    """The schedule's first warm-up, which is all the compared steps see:
    linear from 0 over ``warmup_steps`` updates (ReLoRA's cosine_restarts)."""
    if step >= hp["warmup_steps"]:
        raise ValueError("the reference follows only updates inside the first warm-up")
    return hp["lr"] * step / hp["warmup_steps"]


def make_train_step(cfg: dict, hp: dict, cast: Optional[Callable] = None):
    """``step(trainable, frozen, mu, nu, batch, lr, t) -> (trainable, mu, nu,
    loss, clipped_grads)`` over flat ``{path: leaf}`` dicts; ``batch`` is
    ``(rows, seq + 1)`` token windows, ``t`` the 1-based update count."""
    lora_scale = hp["lora_alpha"] / hp["lora_r"]
    b1, b2, eps, wd = hp["adam_beta1"], hp["adam_beta2"], hp["adam_eps"], hp["weight_decay"]

    def loss_fn(trainable, frozen, row):
        params = unflatten({**frozen, **trainable})
        logits = forward(params, row[None, :-1], cfg, lora_scale=lora_scale, cast=cast)
        return next_token_loss(logits, row[None, 1:])

    def step(trainable, frozen, mu, nu, batch, lr, t):
        def body(acc, row):
            loss, grads = jax.value_and_grad(loss_fn)(trainable, frozen, row)
            return (jax.tree_util.tree_map(jnp.add, acc[0], grads), acc[1] + loss), None

        zeros = jax.tree_util.tree_map(jnp.zeros_like, trainable)
        (grads, loss), _ = lax.scan(body, (zeros, jnp.zeros((), jnp.float32)), batch)
        n = batch.shape[0]
        grads = jax.tree_util.tree_map(lambda g: g / n, grads)
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
        clip = jnp.minimum(1.0, hp["clip_grad_norm"] / (norm + 1e-6))
        grads = jax.tree_util.tree_map(lambda g: g * clip, grads)
        mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
        nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
        c1, c2 = 1 - b1**t, 1 - b2**t

        def update(p, m, v):
            return p - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps) + wd * p)

        return jax.tree_util.tree_map(update, trainable, mu, nu), mu, nu, loss / n, grads

    return jax.jit(step, donate_argnums=(0, 2, 3))


def train_readings(params: dict, batches, cfg: dict, hp: dict, *, cast=None, half_batch: bool = False) -> dict:
    """Follow ``len(batches)`` updates from ``params`` (nested tree, consumed).
    Returns ``losses`` (one per update), ``grad_norms`` (per leaf, the first
    update's gradient as the optimizer gets it, i.e. clipped) and
    ``change_norms`` (per leaf, parameters after the last update minus
    before the first)."""
    from benchmark.weights import flatten

    flat = flatten(params)
    paths = set(flat)
    frozen = {p: v for p, v in flat.items() if is_frozen(p, paths)}
    trainable = {p: v for p, v in flat.items() if p not in frozen}
    del flat, params
    start = jax.tree_util.tree_map(jnp.copy, trainable)
    mu = jax.tree_util.tree_map(jnp.zeros_like, trainable)
    nu = jax.tree_util.tree_map(jnp.zeros_like, trainable)
    step = make_train_step(cfg, hp, cast)
    norms = jax.jit(leaf_norms)
    losses, grad_norms = [], None
    for i, batch in enumerate(batches):
        batch = jnp.asarray(batch, jnp.int32)
        if half_batch:
            batch = batch[: batch.shape[0] // 2]
        trainable, mu, nu, loss, grads = step(trainable, frozen, mu, nu, batch, warmup_lr(hp, i), i + 1)
        losses.append(float(loss))
        if i == 0:
            grad_norms = jax.device_get(norms(grads))
        del grads
    change = jax.jit(lambda a, b: leaf_norms(jax.tree_util.tree_map(jnp.subtract, a, b)))(trainable, start)
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": jax.device_get(change)}


# --------------------------------------------------------------------------
# serving: how far below the reference's best logit each served token lies
# --------------------------------------------------------------------------


def make_gap_fn(cfg: dict, cast: Optional[Callable] = None):
    """``gaps(params, seq, chosen) -> (gap, best)`` for one padded sequence
    ``seq (S,)``: at each position the reference's logit of its best token
    minus its logit of ``chosen`` — the next token of ``seq`` (``cast`` None),
    or the token a ``cast`` forward pass puts first (the control)."""

    def gaps(params, seq):
        logits = forward(params, seq[None, :], cfg)[0]
        best = logits.max(-1)
        if cast is None:
            chosen = jnp.concatenate([seq[1:], seq[:1]])
        else:
            chosen = forward(params, seq[None, :], cfg, cast=cast)[0].argmax(-1)
        picked = jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0]
        return best - picked

    return jax.jit(gaps)
