"""Plain ``mimo_v2`` language-model reference: the forward pass in
``jax.numpy`` and float32, every matrix product at ``Precision.HIGHEST``
(and ``jax.default_matmul_precision("highest")`` around the whole).  No
kernel, no cache, no batching; it imports nothing of the program under test
and makes its own weights from the seed (``benchmark/weights_mimo.py``), a
layer at a time, so that the published widths fit one chip beside nothing
else.  Attention is blocked over queries for the same reason.

The equations (ISSUE 34, Tentpole 1; the assumptions are listed under
``assumed`` in ``benchmark/configs/mimo_v2.5.json``).  ``x`` is (tokens, h);
RMSNorm has a learned scale; no linear has a bias.  Layer l:
``x += Attn_k(RMSNorm(x))``, ``x += FFN_l(RMSNorm(x))``; ``k`` is global where
``hybrid_layer_pattern[l]`` is 0 and window where it is 1.

- Attention: ``q, k, v = x Wq, x Wk, value_scale * x Wv`` with ``d_qk`` wider
  than ``d_v``; rotate-half RoPE on the first ``int(d_qk *
  partial_rotary_factor)`` features of every q and k head, base ``rope_theta``
  (global) or ``swa_rope_theta`` (window); scores ``q.k / sqrt(d_qk)`` over
  ``j <= i`` (global) or ``i - sliding_window < j <= i`` (window).  Where the
  layer kind has a sink, ``p_ij = exp(s_ij) / (sum_j' exp(s_ij') + exp(b_h))``.
- FFN, dense: ``Wd(silu(Wg x) * Wu x)``.  Routed: ``r = sigmoid(x Wr)`` in
  f32; chosen = the ``num_experts_per_tok`` largest of ``r + e``; weights
  ``r[chosen] / sum r[chosen]``; the sum runs over the chosen experts *held
  here* (``expert_offset .. expert_offset + experts_held - 1``) — routing and
  normalisation over all of them.

``cast`` puts the same mathematics into a lower precision for the control:
every matrix product's operands are rounded to that type first.  ``faults``
plants what a forward can get wrong (a dropped sink, selection bias or value
scale, a window one short, a bf16 router) so that a test can say the
comparison sees each.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import weights_mimo

HIGHEST = lax.Precision.HIGHEST
QUERY_BLOCK = 512


def cast_bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def cast_fp8(x):
    """Per-tensor scaled float8 (e4m3): what an fp8 matmul would be fed."""
    amax = jnp.max(jnp.abs(x))
    s = jnp.where(amax > 0, 448.0 / amax, 1.0)
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


CASTS = {"f32": None, "bf16": cast_bf16, "fp8": cast_fp8}
FAULTS = ("no_sink", "no_select_bias", "no_value_scale", "window_minus_one", "bf16_router")


def _mm(x, w, cast):
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if cast is not None:
        x, w = cast(x), cast(w)
    return jnp.matmul(x, w, precision=HIGHEST, preferred_element_type=jnp.float32)


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rotary(x, rot, base):
    """Rotate the first ``rot`` features of each head; x is (S, n, d)."""
    inv_freq = 1.0 / (base ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    xr, xp = x[..., :rot], x[..., rot:]
    rotated = jnp.concatenate([-xr[..., rot // 2 :], xr[..., : rot // 2]], -1)
    return jnp.concatenate([xr * jnp.cos(ang) + rotated * jnp.sin(ang), xp], -1)


def _attention(x, p, cfg, window, cast, faults):
    S = x.shape[0]
    n, dk, dv = cfg["num_attention_heads"], cfg["head_dim"], cfg["v_head_dim"]
    n_kv = cfg["swa_num_key_value_heads"] if window else cfg["num_key_value_heads"]
    qkv = _mm(x, p["qkv_proj"], cast)
    q = qkv[:, : n * dk].reshape(S, n, dk)
    k = qkv[:, n * dk : (n + n_kv) * dk].reshape(S, n_kv, dk)
    v = qkv[:, (n + n_kv) * dk :].reshape(S, n_kv, dv)
    if "no_value_scale" not in faults:
        v = v * cfg["attention_value_scale"]
    rot = int(dk * cfg["partial_rotary_factor"])
    base = cfg["swa_rope_theta"] if window else cfg["rope_theta"]
    q, k = _rotary(q, rot, base), _rotary(k, rot, base)
    if cast is not None:
        q, k, v = cast(q), cast(k), cast(v)
    g = n // n_kv
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)  # (S, n, d): plain, not clever
    span = cfg["sliding_window"] - ("window_minus_one" in faults)
    sink = p.get("sink") if "no_sink" not in faults else None
    j = jnp.arange(S)

    def block(start):
        i = start + jnp.arange(QUERY_BLOCK)
        qb = lax.dynamic_slice_in_dim(q, start, QUERY_BLOCK, axis=0)
        s = jnp.einsum("qnd,knd->nqk", qb, k, precision=HIGHEST) / math.sqrt(dk)
        seen = j[None, :] <= i[:, None]
        if window:
            seen = seen & (j[None, :] > i[:, None] - span)
        s = jnp.where(seen[None], s, -jnp.inf)
        top = s.max(-1, keepdims=True)
        if sink is not None:
            top = jnp.maximum(top, sink[:, None, None])
        e = jnp.exp(s - top)
        denom = e.sum(-1, keepdims=True)
        if sink is not None:
            denom = denom + jnp.exp(sink[:, None, None] - top)
        probs = e / denom
        if cast is not None:
            probs = cast(probs)
        return jnp.einsum("nqk,knd->qnd", probs, v, precision=HIGHEST)

    pad = -S % QUERY_BLOCK
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    out = lax.map(block, jnp.arange(0, S + pad, QUERY_BLOCK)).reshape(S + pad, n * dv)[:S]
    return _mm(out, p["o_proj"], cast)


def _swiglu(x, gate, up, down, cast):
    return _mm(jax.nn.silu(_mm(x, gate, cast)) * _mm(x, up, cast), down, cast)


def _experts(x, p, cfg, cast, faults, held=None):
    """The held experts' part of the routed sum; ``held`` overrides the
    configuration's share as ``(offset, count)`` (the shares-add-up test)."""
    router = p["router"].astype(jnp.float32)
    if "bf16_router" in faults:
        scores = jax.nn.sigmoid(
            jnp.matmul(cast_bf16(x), cast_bf16(router), precision=HIGHEST).astype(jnp.bfloat16).astype(jnp.float32)
        )
    else:
        scores = jax.nn.sigmoid(jnp.matmul(x, router, precision=HIGHEST))
    select = scores if "no_select_bias" in faults else scores + p["select_bias"]
    _, chosen = lax.top_k(select, cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.get("norm_topk_prob", True):
        weights = weights / weights.sum(-1, keepdims=True)
    weights = weights * (cfg.get("routed_scaling_factor") or 1.0)
    offset, count = held or (cfg.get("expert_offset", 0), p["down"].shape[0])
    f = p["down"].shape[1]

    def one(y, c):
        w_c = jnp.sum(jnp.where(chosen == offset + c, weights, 0.0), axis=-1, keepdims=True)
        gate_up = p["gate_up"][c]
        return y + w_c * _swiglu(x, gate_up[:, :f], gate_up[:, f:], p["down"][c], cast), None

    y, _ = lax.scan(one, jnp.zeros_like(x), jnp.arange(count))
    return y


def layer(x, p, cfg: dict, i: int, cast: Optional[Callable] = None, faults=(), held=None):
    """Layer ``i`` on ``x`` (S, h) with that layer's weights ``p``."""
    eps = cfg["layernorm_epsilon"]
    window = bool(cfg["hybrid_layer_pattern"][i])
    x = x + _attention(_rms_norm(x, p["input_layernorm"]["scale"], eps), p["attn"], cfg, window, cast, faults)
    y = _rms_norm(x, p["post_attention_layernorm"]["scale"], eps)
    if cfg["moe_layer_freq"][i]:
        return x + _experts(y, p["experts"], cfg, cast, faults, held)
    m = p["mlp"]
    return x + _swiglu(y, m["gate_proj"]["kernel"], m["up_proj"]["kernel"], m["down_proj"]["kernel"], cast)


def forward(params: dict, tokens, cfg: dict, *, cast: Optional[Callable] = None, faults=()):
    """Logits (S, vocab) in float32 for token ids (S,), from a whole tree."""
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed_tokens"].astype(jnp.float32), tokens, axis=0)
        for i in range(cfg["num_hidden_layers"]):
            x = layer(x, params[f"layers_{i}"], cfg, i, cast, faults)
        return head(x, params, cfg, cast)


def head(x, ends: dict, cfg: dict, cast: Optional[Callable] = None):
    return _mm(_rms_norm(x, ends["norm"]["scale"], cfg["layernorm_epsilon"]), ends["lm_head"], cast)


# --------------------------------------------------------------------------
# serving: how far below the reference's best logit each served token lies,
# at the cell's size — weights made a layer at a time, layers outermost
# --------------------------------------------------------------------------


def _widen(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def gaps(cfg: dict, seed: int, seqs, cast: Optional[Callable] = None, faults=(), dtype=jnp.bfloat16):
    """``(R, S)``: at each position of each padded sequence the reference's
    logit of its best token minus its logit of the chosen one — the next token
    of the sequence, or (the control) the token that a forward pass in ``cast``
    precision or with ``faults`` planted puts first.  The weights are the
    seed's, rounded to ``dtype`` as the program holds them; one layer's exist
    at a time."""
    key = weights_mimo.seed_key(seed)
    seqs = jnp.asarray(seqs, jnp.int32)
    control = cast is not None or bool(faults)
    passes = ((None, ()), (cast, tuple(faults))) if control else ((None, ()),)
    frozen = _freeze(cfg)
    with jax.default_matmul_precision("highest"):
        ends = _widen(jax.jit(lambda k: weights_mimo.make_ends(cfg, k, dtype))(key))
        embed = jax.jit(lambda e, s: jnp.take(e, s, axis=0))
        xs = [[embed(ends["embed_tokens"], s) for s in seqs] for _ in passes]
        for i in range(cfg["num_hidden_layers"]):
            p = _widen(jax.jit(lambda k, i=i: weights_mimo.make_layer(cfg, k, i, dtype))(key))
            for c, (how, planted) in enumerate(passes):
                step = _layer_fn(frozen, i, how, planted)
                xs[c] = [step(x, p) for x in xs[c]]
            jax.tree_util.tree_map(lambda a: a.delete(), p)
        out = []
        for r, seq in enumerate(seqs):
            logits = _head_fn(frozen, None)(xs[0][r], ends)
            if not control:
                chosen = jnp.concatenate([seq[1:], seq[:1]])
            else:
                chosen = _head_fn(frozen, cast)(xs[1][r], ends).argmax(-1)
            out.append(logits.max(-1) - jnp.take_along_axis(logits, chosen[:, None], axis=-1)[:, 0])
        return jnp.stack(out)


def _freeze(cfg: dict):
    """A hashable view of the configuration's numbers (the jit caches below)."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, list, type(None)))))


@functools.lru_cache(maxsize=None)
def _kind_fn(frozen, window: int, routed: int, cast, faults):
    """One compiled layer function per kind of layer, precision and faults."""
    cfg = {k: list(v) if isinstance(v, tuple) else v for k, v in frozen}
    i = next(j for j in range(cfg["num_hidden_layers"])
             if (cfg["hybrid_layer_pattern"][j], cfg["moe_layer_freq"][j]) == (window, routed))
    return jax.jit(lambda x, p: layer(x, p, cfg, i, cast, faults))


def _layer_fn(frozen, i: int, cast, faults=()):
    cfg = dict(frozen)
    return _kind_fn(frozen, cfg["hybrid_layer_pattern"][i], cfg["moe_layer_freq"][i], cast, faults)


@functools.lru_cache(maxsize=None)
def _head_fn(frozen, cast):
    cfg = dict(frozen)
    return jax.jit(lambda x, ends: head(x, ends, cfg, cast))
