"""Plain ``afmoe`` (Arcee Trinity) language-model reference: the forward pass
in ``jax.numpy`` and float32, every matrix product at ``Precision.HIGHEST``
(and ``jax.default_matmul_precision("highest")`` around the whole).  No
kernel, no cache, no batching; it imports nothing of the program under test
and makes its own weights from the seed (``benchmark/weights_afmoe.py``), a
layer at a time, so that the published widths fit one chip beside nothing
else.  Attention is blocked over queries for the same reason (an 11,264 x
11,264 x 48 score matrix is 24 GB).

The equations (ISSUE 36; what the catalog's keys alone do not state is listed
under ``assumed`` in ``benchmark/configs/trinity_large_preview.json``).  ``x``
is (tokens, h); no linear has a bias; every RMSNorm has a learned scale, eps
``rms_norm_eps``.

- Embedding: ``x = E[ids] * sqrt(hidden_size)`` (``mup_enabled``).
- Layer l, sandwich norms: ``x += N_post_attn(Attn_l(N_in(x)))``;
  ``x += N_post_mlp(FFN_l(N_pre_mlp(x)))``.
- ``Attn_l(a)``: ``q, k, v, g = a Wq, a Wk, a Wv, a Wg`` (g as wide as q);
  ``q = N_q(q)``, ``k = N_k(k)``: RMSNorm over the features of every head, one
  scale vector each.  Where ``layer_types[l]`` is ``sliding_attention``:
  rotate-half RoPE on all features of q and k, base ``rope_theta``, and keys
  ``i - sliding_window < j <= i``.  Where it is ``full_attention``: no
  positional encoding at all, keys ``j <= i``.  Scores ``q.k / sqrt(d)``,
  softmax, ``o = P v``; the gate ``o = o * sigmoid(g)`` elementwise, before
  ``Wo``; output ``o Wo``.
- ``FFN_l(m)``, ``l < num_dense_layers``: ``Wd(silu(Wg m) * Wu m)``.  Else:
  ``s = sigmoid(m Wr)`` in f32 over all ``num_experts``; chosen = the
  ``num_experts_per_tok`` largest of ``s + b`` (selection only); ``w =
  s[chosen] / (sum s[chosen] + 1e-20) * route_scale``; ``FFN = Shared(m) +
  sum_c w_c Expert_c(m)`` — the sum over the chosen experts *held here*
  (``expert_offset .. expert_offset + experts_held - 1``), routing and
  normalisation over all of them.
- Final RMSNorm, then the untied head over the vocabulary rows held here.

``cast`` puts the same mathematics into a lower precision for the control:
every matrix product's operands are rounded to that type first.  ``faults``
plants what a forward can get wrong, one part each (:data:`FAULTS`), so that a
test can say the comparison sees each.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from benchmark import weights_afmoe

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
#: each drops (or adds) one part of the mathematics
FAULTS = (
    "no_attn_gate", "no_qk_norm", "no_window_rotary", "global_rotary", "no_post_attn_norm",
    "no_shared_expert", "no_route_scale", "no_select_bias", "no_embed_scale", "window_minus_one",
)


def _mm(x, w, cast):
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    if cast is not None:
        x, w = cast(x), cast(w)
    return jnp.matmul(x, w, precision=HIGHEST, preferred_element_type=jnp.float32)


def _rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rotary(x, base):
    """Rotate-half RoPE on every feature of each head; x is (S, n, d)."""
    d = x.shape[-1]
    inv_freq = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., d // 2 :], x[..., : d // 2]], -1)
    return x * jnp.cos(ang) + rotated * jnp.sin(ang)


def _attention(x, p, cfg, window, cast, faults):
    S = x.shape[0]
    n, n_kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    y = _mm(x, p["qkvg_proj"], cast)
    q = y[:, : n * d].reshape(S, n, d)
    k = y[:, n * d : (n + n_kv) * d].reshape(S, n_kv, d)
    v = y[:, (n + n_kv) * d : (n + 2 * n_kv) * d].reshape(S, n_kv, d)
    gate = y[:, (n + 2 * n_kv) * d :]
    if "no_qk_norm" not in faults:
        eps = cfg["rms_norm_eps"]
        q, k = _rms_norm(q, p["q_norm"]["scale"], eps), _rms_norm(k, p["k_norm"]["scale"], eps)
    if (window and "no_window_rotary" not in faults) or (not window and "global_rotary" in faults):
        q, k = _rotary(q, cfg["rope_theta"]), _rotary(k, cfg["rope_theta"])
    if cast is not None:
        q, k, v = cast(q), cast(k), cast(v)
    g = n // n_kv
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)  # (S, n, d): plain, not clever
    span = cfg["sliding_window"] - ("window_minus_one" in faults)
    j = jnp.arange(S)

    def block(start):
        i = start + jnp.arange(QUERY_BLOCK)
        qb = lax.dynamic_slice_in_dim(q, start, QUERY_BLOCK, axis=0)
        s = jnp.einsum("qnd,knd->nqk", qb, k, precision=HIGHEST) / math.sqrt(d)
        seen = j[None, :] <= i[:, None]
        if window:
            seen = seen & (j[None, :] > i[:, None] - span)
        probs = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        if cast is not None:
            probs = cast(probs)
        return jnp.einsum("nqk,knd->qnd", probs, v, precision=HIGHEST)

    pad = -S % QUERY_BLOCK
    q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
    out = lax.map(block, jnp.arange(0, S + pad, QUERY_BLOCK)).reshape(S + pad, n * d)[:S]
    if "no_attn_gate" not in faults:
        out = out * jax.nn.sigmoid(gate)
    return _mm(out, p["o_proj"], cast)


def _swiglu(x, gate, up, down, cast):
    return _mm(jax.nn.silu(_mm(x, gate, cast)) * _mm(x, up, cast), down, cast)


def _mlp(x, m, cast):
    return _swiglu(x, m["gate_proj"]["kernel"], m["up_proj"]["kernel"], m["down_proj"]["kernel"], cast)


def _experts(x, p, cfg, cast, faults, held=None):
    """The held experts' part of the routed sum; ``held`` overrides the
    configuration's share as ``(offset, count)`` (the shares-add-up test)."""
    scores = jax.nn.sigmoid(jnp.matmul(x, p["router"].astype(jnp.float32), precision=HIGHEST))
    select = scores if "no_select_bias" in faults else scores + p["select_bias"]
    _, chosen = lax.top_k(select, cfg["num_experts_per_tok"])
    weights = jnp.take_along_axis(scores, chosen, axis=-1)
    if cfg.get("route_norm", True):
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    if "no_route_scale" not in faults:
        weights = weights * (cfg.get("route_scale") or 1.0)
    offset, count = held or (cfg.get("expert_offset", 0), p["down"].shape[0])
    f = p["down"].shape[1]

    def one(y, c):
        w_c = jnp.sum(jnp.where(chosen == offset + c, weights, 0.0), axis=-1, keepdims=True)
        gate_up = p["gate_up"][c]
        return y + w_c * _swiglu(x, gate_up[:, :f], gate_up[:, f:], p["down"][c], cast), None

    y, _ = lax.scan(one, jnp.zeros_like(x), jnp.arange(count))
    return y


def _ffn(m, p, cfg, cast, faults, held=None):
    if "experts" not in p:
        return _mlp(m, p["mlp"], cast)
    y = _experts(m, p["experts"], cfg, cast, faults, held)
    if "shared_expert" in p and "no_shared_expert" not in faults:
        y = y + _mlp(m, p["shared_expert"], cast)
    return y


def layer(x, p, cfg: dict, window: bool, cast: Optional[Callable] = None, faults=(), held=None):
    """One layer on ``x`` (S, h) with that layer's weights ``p``; dense or
    routed by what ``p`` holds, sliding or full by ``window``."""
    eps = cfg["rms_norm_eps"]
    a = _attention(_rms_norm(x, p["input_layernorm"]["scale"], eps), p["attn"], cfg, window, cast, faults)
    if "no_post_attn_norm" not in faults:
        a = _rms_norm(a, p["post_attention_layernorm"]["scale"], eps)
    x = x + a
    f = _ffn(_rms_norm(x, p["pre_mlp_layernorm"]["scale"], eps), p, cfg, cast, faults, held)
    return x + _rms_norm(f, p["post_mlp_layernorm"]["scale"], eps)


def is_window(cfg: dict, i: int) -> bool:
    return cfg["layer_types"][i] == "sliding_attention"


def embed(ends: dict, tokens, cfg: dict, faults=()):
    x = jnp.take(ends["embed_tokens"].astype(jnp.float32), tokens, axis=0)
    return x if "no_embed_scale" in faults or not cfg.get("mup_enabled") else x * math.sqrt(cfg["hidden_size"])


def head(x, ends: dict, cfg: dict, cast: Optional[Callable] = None):
    return _mm(_rms_norm(x, ends["norm"]["scale"], cfg["rms_norm_eps"]), ends["lm_head"], cast)


def forward(params: dict, tokens, cfg: dict, *, cast: Optional[Callable] = None, faults=()):
    """Logits (S, vocab) in float32 for token ids (S,), from a whole tree."""
    with jax.default_matmul_precision("highest"):
        x = embed(params, tokens, cfg, faults)
        for i in range(cfg["num_hidden_layers"]):
            x = layer(x, params[f"layers_{i}"], cfg, is_window(cfg, i), cast, faults)
        return head(x, params, cfg, cast)


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
    key = weights_afmoe.seed_key(seed)
    seqs = jnp.asarray(seqs, jnp.int32)
    control = cast is not None or bool(faults)
    passes = ((None, ()), (cast, tuple(faults))) if control else ((None, ()),)
    frozen = _freeze(cfg)
    make_layer = weights_afmoe.layer_maker(cfg, dtype)
    with jax.default_matmul_precision("highest"):
        ends = _widen(jax.jit(lambda k: weights_afmoe.make_ends(cfg, k, dtype))(key))
        xs = [[_embed_fn(frozen, planted)(ends, s) for s in seqs] for _, planted in passes]
        for i in range(cfg["num_hidden_layers"]):
            routed = i >= cfg["num_dense_layers"]
            p = _widen(make_layer(key, jnp.int32(i), routed=routed))
            for c, (how, planted) in enumerate(passes):
                step = _layer_fn(frozen, is_window(cfg, i), how, planted)
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


def _thaw(frozen) -> dict:
    return {k: list(v) if isinstance(v, tuple) else v for k, v in frozen}


@functools.lru_cache(maxsize=None)
def _layer_fn(frozen, window: bool, cast, faults=()):
    """One compiled layer function per kind of attention, precision and
    faults (jit tells a dense layer's tree from a routed one's)."""
    cfg = _thaw(frozen)
    return jax.jit(lambda x, p: layer(x, p, cfg, window, cast, faults))


@functools.lru_cache(maxsize=None)
def _embed_fn(frozen, faults=()):
    cfg = _thaw(frozen)
    return jax.jit(lambda ends, s: embed(ends, s, cfg, faults))


@functools.lru_cache(maxsize=None)
def _head_fn(frozen, cast):
    cfg = _thaw(frozen)
    return jax.jit(lambda x, ends: head(x, ends, cfg, cast))
